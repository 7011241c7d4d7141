"""Metric-name lint: every instrument in the catalog follows the naming
convention, so future PRs adding instruments can't drift.

Rules (docs/OBSERVABILITY.md "naming"):
  * prefix ``aios_tpu_``, snake_case ``[a-z0-9_]`` only;
  * a unit suffix from the approved set — ``_seconds``, ``_bytes``,
    ``_total`` (primary trio), plus ``_ratio`` and ``_per_second`` for
    unitless/rate gauges, ``_pages`` for KV page-pool occupancy
    gauges (pages are the pool's native capacity unit — converting to
    bytes at scrape time would bake in dtype/geometry and break A/B
    comparisons across cache dtypes), and ``_info`` for identity
    gauges (the Prometheus *_info convention: constant value 1, the
    payload entirely in labels — a unit suffix would claim a
    measurement the series deliberately does not make);
  * label names snake_case, bounded per-metric label count;
  * non-empty help text.
"""

import re

import aios_tpu.obs.instruments  # noqa: F401 - registers the catalog
from aios_tpu.obs.metrics import REGISTRY

NAME_RE = re.compile(r"^aios_tpu_[a-z0-9_]+$")
LABEL_RE = re.compile(r"^[a-z][a-z0-9_]*$")
UNIT_SUFFIXES = ("_seconds", "_bytes", "_total", "_ratio", "_per_second",
                 "_pages", "_info")


def _catalog():
    metrics = [
        m for m in REGISTRY.collect() if m.name.startswith("aios_tpu_")
    ]
    assert metrics, "instrument catalog registered nothing"
    return metrics


def test_metric_names_are_prefixed_snake_case():
    for m in _catalog():
        assert NAME_RE.match(m.name), (
            f"{m.name}: must match aios_tpu_[a-z0-9_]+ (snake_case)"
        )


def test_metric_names_carry_a_unit_suffix():
    for m in _catalog():
        assert m.name.endswith(UNIT_SUFFIXES), (
            f"{m.name}: metric names end in a unit suffix "
            f"{UNIT_SUFFIXES} (add the unit, or extend the approved set "
            f"in docs/OBSERVABILITY.md AND here with a reviewed rationale)"
        )


def test_histograms_are_timed_in_seconds():
    for m in _catalog():
        if m.kind == "histogram":
            assert m.name.endswith("_seconds"), (
                f"{m.name}: histograms in this codebase measure durations; "
                f"use base-unit seconds"
            )


def test_counters_end_in_total():
    for m in _catalog():
        if m.kind == "counter":
            assert m.name.endswith("_total"), (
                f"{m.name}: counters use the _total suffix"
            )


def test_label_names_snake_case_and_bounded():
    for m in _catalog():
        assert len(m.labelnames) <= 4, (
            f"{m.name}: {len(m.labelnames)} labels — cardinality budget is "
            f"4; aggregate instead"
        )
        for ln in m.labelnames:
            assert LABEL_RE.match(ln), f"{m.name}: bad label name {ln!r}"
            assert ln not in ("le", "overflow"), (
                f"{m.name}: label {ln!r} collides with reserved names"
            )


def test_help_text_present():
    for m in _catalog():
        assert m.help.strip(), f"{m.name}: empty help text"


# -- the serving family (aios_tpu/serving/) --------------------------------

SERVING_EXPECTED = {
    "aios_tpu_serving_replicas_total": "gauge",
    "aios_tpu_serving_replica_occupancy_ratio": "gauge",
    "aios_tpu_serving_routing_decisions_total": "counter",
    "aios_tpu_serving_shed_total": "counter",
    "aios_tpu_serving_quota_rejections_total": "counter",
    "aios_tpu_serving_queue_wait_seconds": "histogram",
    "aios_tpu_serving_replica_restarts_total": "counter",
    "aios_tpu_serving_failover_total": "counter",
}


def test_serving_family_complete_and_typed():
    """The replica-pool instruments the ISSUE 2 catalog promises exist,
    with the promised kinds — and any NEW aios_tpu_serving_* metric must
    be added here (and to docs/SERVING.md) so the family stays reviewed."""
    serving = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_serving_")
    }
    assert serving == SERVING_EXPECTED


# -- the long-context tier family (window+sink compression + sp prefill) --

KV_COMPRESS_EXPECTED = {
    "aios_tpu_kv_compress_slots_total": "gauge",
    "aios_tpu_kv_compress_pages_pruned_total": "gauge",
    "aios_tpu_kv_compress_resident_pages": "gauge",
}


def test_kv_compress_family_complete_and_typed():
    """The window+sink compression instruments the ISSUE 13 catalog
    promises exist, with the promised kinds — and any NEW
    aios_tpu_kv_compress_* metric must be added here (and to
    docs/ENGINE_PERF.md + OBSERVABILITY.md) so the family stays
    reviewed. slots/pages_pruned are monotonic engine counters summed
    over the per-model engine WeakSet; resident_pages reads live
    allocator state at scrape time."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_kv_compress_")
    }
    assert family == KV_COMPRESS_EXPECTED
    for m in _catalog():
        if m.name.startswith("aios_tpu_kv_compress_") or \
                m.name == "aios_tpu_prefill_seq_sharded_total":
            assert tuple(m.labelnames) == ("model",), (
                f"{m.name}: long-context metrics carry exactly the model "
                f"label (replicas aggregate through the engine WeakSet)"
            )


def test_seq_prefill_counter_registered_over_engine_weakset():
    """aios_tpu_prefill_seq_sharded_total and the compression counters
    must register through the WeakSet-summed callbacks in
    _register_gauges (set_function is last-writer-wins across replica
    engines — the aios_tpu_prefix_host_* lesson)."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.engine import engine as engine_mod

    assert any(
        m.name == "aios_tpu_prefill_seq_sharded_total" for m in _catalog()
    )
    mi = module_info_for(engine_mod)
    used = names_used_in(mi.functions["TPUEngine._register_gauges"].node)
    for name in ("KV_COMPRESS_SLOTS", "KV_COMPRESS_PAGES_PRUNED",
                 "KV_COMPRESS_RESIDENT", "PREFILL_SEQ_SHARDED"):
        assert name in used, f"{name} not registered over the WeakSet"


# -- the prefix-cache host tier family (engine/paged.py HostPageStore) -----

PREFIX_HOST_EXPECTED = {
    "aios_tpu_prefix_host_resident_bytes": "gauge",
    "aios_tpu_prefix_host_spills_total": "gauge",
    "aios_tpu_prefix_host_restores_total": "gauge",
    "aios_tpu_prefix_host_hits_total": "gauge",
    "aios_tpu_prefix_host_misses_total": "gauge",
    "aios_tpu_prefix_host_corrupt_total": "gauge",
    "aios_tpu_prefix_host_restore_seconds": "histogram",
}


def test_prefix_host_family_complete_and_typed():
    """The host spill tier instruments the ISSUE 4 catalog promises
    exist, with the promised kinds — and any NEW aios_tpu_prefix_host_*
    metric must be added here (and to docs/OBSERVABILITY.md) so the
    family stays reviewed."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_prefix_host_")
    }
    assert family == PREFIX_HOST_EXPECTED


def test_prefix_host_labels_are_model_only():
    """Host-tier series stay one-per-model: the store is per engine
    (replica stats sum through pool.stats()), so nothing here may grow a
    per-hash or per-replica label."""
    for m in _catalog():
        if m.name.startswith("aios_tpu_prefix_host_"):
            assert tuple(m.labelnames) == ("model",), (
                f"{m.name}: host-tier metrics carry exactly the model label"
            )


# -- the grammar jump-ahead family (engine.jump_step, ISSUE 7) -------------

ENGINE_JUMP_EXPECTED = {
    "aios_tpu_engine_jump_ahead_dispatches_total": "gauge",
    "aios_tpu_engine_jump_ahead_tokens_total": "gauge",
}


def test_engine_jump_ahead_family_complete_and_typed():
    """The jump-ahead instruments the ISSUE 7 catalog promises exist,
    with the promised kinds — and any NEW aios_tpu_engine_jump_ahead_*
    metric must be added here (and to docs/ENGINE_PERF.md +
    OBSERVABILITY.md) so the family stays reviewed. They are monotonic
    engine counters read at scrape time over a per-model WeakSet of
    replica engines (set_function is last-writer-wins — the
    aios_tpu_prefix_host_* lesson, not repeated a third time)."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_engine_jump_ahead_")
    }
    assert family == ENGINE_JUMP_EXPECTED
    for m in _catalog():
        if m.name.startswith("aios_tpu_engine_jump_ahead_"):
            assert tuple(m.labelnames) == ("model",), (
                f"{m.name}: jump-ahead metrics carry exactly the model "
                f"label (replicas aggregate through the engine WeakSet)"
            )


def test_engine_jump_ahead_gauges_aggregate_over_engine_weakset():
    """The scrape callbacks must SUM over _ENGINES_BY_MODEL — a bare
    weakref.ref(self) registration would report only the last replica.
    Checked on the AST (analysis.core walker), not a source grep."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.engine import engine as engine_mod

    mi = module_info_for(engine_mod)
    fn = mi.functions["TPUEngine._register_gauges"]
    used = names_used_in(fn.node)
    assert "_ENGINES_BY_MODEL" in used
    for name in ("ENGINE_JUMP_DISPATCHES", "ENGINE_JUMP_TOKENS",
                 "SPEC_ROUNDS", "SPEC_ACCEPTED"):
        assert name in used, f"{name} not registered over the WeakSet"


# -- the speculative-decode family (engine.spec_step + batcher EWMA) -------

SPEC_EXPECTED = {
    "aios_tpu_spec_rounds_total": "gauge",
    "aios_tpu_spec_accepted_total": "gauge",
    "aios_tpu_spec_acceptance_ratio": "gauge",
}


def test_spec_family_complete_and_typed():
    """The speculative-decode instruments the ROADMAP item promises
    exist, with the promised kinds — rounds/accepted are WeakSet-summed
    engine counters; the acceptance ratio is the per-batcher EWMA that
    drives the AIOS_TPU_SPEC_MIN_ACCEPT auto-disable, averaged over
    replica batchers. Since the draft-model proposer landed, every
    series carries the (model, proposer) label pair."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_spec_")
    }
    assert family == SPEC_EXPECTED
    for m in _catalog():
        if m.name.startswith("aios_tpu_spec_"):
            assert tuple(m.labelnames) == ("model", "proposer"), (
                f"{m.name}: spec metrics carry exactly the "
                f"(model, proposer) label pair"
            )


def test_spec_proposers_are_a_closed_enum():
    """The ``proposer`` label values come from spec.SPEC_PROPOSERS and
    nowhere else — the engine and batcher gauge registrations iterate
    the tuple (the SLO OBJECTIVES pattern), so a new proposer is a
    reviewed enum change, not a stray string that grows the label set."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.engine import batching, engine, spec

    assert spec.SPEC_PROPOSERS == ("ngram", "draft")
    mi = module_info_for(engine)
    fn = mi.functions["TPUEngine._register_gauges"]
    assert "SPEC_PROPOSERS" in names_used_in(fn.node), (
        "engine spec gauges must be registered by iterating the "
        "SPEC_PROPOSERS enum"
    )
    bi = module_info_for(batching)
    init = bi.functions["ContinuousBatcher.__init__"]
    assert "SPEC_PROPOSERS" in names_used_in(init.node), (
        "batcher acceptance gauges must be registered by iterating the "
        "SPEC_PROPOSERS enum"
    )


# -- the decode dispatch family (pipelined batcher, engine/batching.py) ----

ENGINE_DISPATCH_EXPECTED = {
    "aios_tpu_engine_dispatch_host_gap_seconds": "histogram",
    "aios_tpu_engine_dispatch_inflight_total": "gauge",
    "aios_tpu_engine_dispatch_flushes_total": "counter",
}


def test_engine_dispatch_family_complete_and_typed():
    """The decode-dispatch instruments the ISSUE 6 catalog promises
    exist, with the promised kinds — and any NEW
    aios_tpu_engine_dispatch_* metric must be added here (and to
    docs/ENGINE_PERF.md + OBSERVABILITY.md) so the family stays
    reviewed. The kind map doubles as the unsuffixed-unit gate for this
    PR's additions: a dispatch metric not ending in an approved unit
    suffix fails test_metric_names_carry_a_unit_suffix AND this
    equality."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_engine_dispatch_")
    }
    assert family == ENGINE_DISPATCH_EXPECTED
    for name in family:
        assert name.endswith(UNIT_SUFFIXES), (
            f"{name}: dispatch metrics carry a unit suffix like every "
            f"other family"
        )


def test_engine_dispatch_flush_causes_bounded():
    """Flush causes are a fixed enum (see ContinuousBatcher
    _flush_pending call sites) — the label must never grow a per-request
    or per-slot dimension. Call sites are enumerated on the AST."""
    from aios_tpu.analysis.core import module_info_for, string_call_args
    from aios_tpu.engine import batching

    mi = module_info_for(batching)
    causes = {
        lit for lit, _ in string_call_args(mi.tree, ("_flush_pending",))
    }
    assert causes, "no _flush_pending call sites found"
    assert causes <= {"constrained", "spec", "evict", "idle"}


# -- the device-time attribution family (obs/devprof.py, ISSUE 14) ---------

DEVPROF_EXPECTED = {
    "aios_tpu_devprof_dispatches_total": "gauge",
    "aios_tpu_devprof_device_seconds_total": "gauge",
    "aios_tpu_devprof_mfu_ratio": "gauge",
    "aios_tpu_devprof_hbm_bandwidth_utilization_ratio": "gauge",
    "aios_tpu_devprof_tenant_device_seconds_total": "counter",
}


def test_devprof_family_complete_and_typed():
    """The device-time attribution instruments the ISSUE 14 catalog
    promises exist, with the promised kinds and unit suffixes — and any
    NEW aios_tpu_devprof_* metric must be added here (and to
    docs/OBSERVABILITY.md) so the family stays reviewed. Per-graph
    series carry exactly (model, graph) and are WeakSet-summed over
    replica ledgers; ONLY the tenant counter carries the tenant label,
    and it carries it ALONE (the quota-metric precedent — a tenant x
    model label product is unbounded; the per-model breakdown lives in
    /debug/devprof JSON)."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_devprof_")
    }
    assert family == DEVPROF_EXPECTED
    for m in _catalog():
        if m.name == "aios_tpu_devprof_tenant_device_seconds_total":
            assert tuple(m.labelnames) == ("tenant",)
        elif m.name.startswith("aios_tpu_devprof_"):
            assert tuple(m.labelnames) == ("model", "graph"), (
                f"{m.name}: devprof series carry exactly (model, graph)"
            )
        if m.name.startswith("aios_tpu_devprof_"):
            assert m.name.endswith(UNIT_SUFFIXES)


def test_devprof_graph_kinds_closed_enum():
    """The ``graph`` label values come from devprof.GRAPH_KINDS and
    nowhere else: the engine's gauge registration iterates the tuple
    (the SLO-objectives pattern) over the per-model ledger WeakSet, and
    every ledger call site — the ``_devprof_note(<kind>, ...)`` hooks on
    the dispatch paths — passes a literal member of the enum (checked on
    the AST, so a stray string cannot mint a new series)."""
    from aios_tpu.analysis.core import (
        iter_calls, module_info_for, names_used_in, string_call_args,
    )
    from aios_tpu.engine import engine as engine_mod
    from aios_tpu.obs import devprof

    mi = module_info_for(engine_mod)
    used = names_used_in(mi.functions["TPUEngine._register_gauges"].node)
    assert "GRAPH_KINDS" in used, (
        "devprof gauge children must be registered by iterating the "
        "GRAPH_KINDS enum"
    )
    assert "ledgers_for" in used, (
        "devprof gauges must aggregate over the per-model ledger WeakSet"
    )
    for name in ("DEVPROF_DISPATCHES", "DEVPROF_DEVICE_SECONDS",
                 "DEVPROF_MFU", "DEVPROF_HBM_UTIL"):
        assert name in used, f"{name} not registered over the WeakSet"
    kinds = {
        lit for lit, _ in string_call_args(mi.tree, ("_devprof_note",), 0)
    }
    assert kinds, "no _devprof_note call sites found in the engine"
    unknown = kinds - set(devprof.GRAPH_KINDS)
    assert not unknown, (
        f"ledger call sites use kinds {sorted(unknown)} not in the "
        f"closed GRAPH_KINDS enum — extend the enum (reviewed) instead "
        f"of inventing strings"
    )
    # the graph kinds the BATCHER attributes by (its _rec_dispatch
    # graph= argument and the spec/jump attribution) are members too
    from aios_tpu.engine import batching
    import ast as ast_mod

    bi = module_info_for(batching)
    batcher_kinds = set()
    for call in iter_calls(bi.tree):
        for kw in call.keywords:
            if kw.arg == "graph" and isinstance(kw.value, ast_mod.Constant):
                batcher_kinds.add(kw.value.value)
    batcher_kinds |= {
        lit for lit, _ in string_call_args(bi.tree, ("devprof_est_s",), 0)
    }
    assert batcher_kinds, "no batcher attribution call sites found"
    assert batcher_kinds <= set(devprof.GRAPH_KINDS)


# -- the SLO family (obs/slo.py, fed by the flight recorder, ISSUE 8) ------

SLO_EXPECTED = {
    "aios_tpu_slo_attainment_ratio": "gauge",
    "aios_tpu_slo_burn_rate_ratio": "gauge",
    "aios_tpu_slo_breaches_total": "counter",
}


def test_slo_family_complete_and_typed():
    """The SLO instruments the ISSUE 8 catalog promises exist, with the
    promised kinds — and any NEW aios_tpu_slo_* metric must be added
    here (and to docs/OBSERVABILITY.md) so the family stays reviewed.
    Labels are exactly (model, objective): the per-tenant breakdown
    stays in /debug/slo JSON because a tenant x model label product is
    unbounded (the test_serving_label_conventions rationale)."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_slo_")
    }
    assert family == SLO_EXPECTED
    for m in _catalog():
        if m.name.startswith("aios_tpu_slo_"):
            assert tuple(m.labelnames) == ("model", "objective"), (
                f"{m.name}: SLO metrics carry exactly (model, objective)"
            )


def test_slo_objectives_are_a_closed_enum():
    """The ``objective`` label values come from slo.OBJECTIVES and
    nowhere else — the gauge registrations iterate the tuple, so a new
    objective is a reviewed enum change, not a stray string."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.obs import slo

    assert slo.OBJECTIVES == ("ttft", "tpot", "availability")
    mi = module_info_for(slo)
    fn = mi.functions["SLOEngine._register_gauges"]
    assert "OBJECTIVES" in names_used_in(fn.node), (
        "SLO gauge children must be registered by iterating the "
        "OBJECTIVES enum"
    )


# -- flight-recorder closed enums (obs/flightrec.py, ISSUE 8) --------------
# The bounded-flush-cause pattern (ISSUE 6), extended: every event kind,
# shed cause, and abort cause the recorder can emit comes from ONE shared
# closed enum, so neither the recorder output nor any aios_tpu_slo_* /
# aios_tpu_serving_* label built on it can grow free-form label sets.


def _call_site_kinds(*modules):
    """Event kinds used at ``.event("<kind>", ...)`` /
    ``.model_event(<model>, "<kind>", ...)`` call sites in the given
    modules — AST call-argument extraction via the analysis walker, so
    wrapped lines and keyword noise can't hide a call site the way they
    could from the old regexes."""
    from aios_tpu.analysis.core import module_info_for, string_call_args

    kinds = set()
    for mod in modules:
        mi = module_info_for(mod)
        kinds |= {
            lit for lit, _ in string_call_args(mi.tree, ("event",), 0)
        }
        kinds |= {
            lit for lit, _ in string_call_args(mi.tree, ("model_event",), 1)
        }
    return kinds


def test_recorder_event_kinds_bounded():
    """Every event-kind string at every recorder call site — batcher,
    pool, engine, runtime service, the failover controller, the fault
    injector, and flightrec itself — is a member of the closed
    flightrec.EVENT_KINDS enum."""
    from aios_tpu.engine import batching, engine as engine_mod
    from aios_tpu.faults import inject as faults_inject
    from aios_tpu.faults import net as faults_net
    from aios_tpu.fleet import breaker as fleet_breaker
    from aios_tpu.fleet import disagg as fleet_disagg
    from aios_tpu.fleet import drain as fleet_drain
    from aios_tpu.fleet import kvx as fleet_kvx
    from aios_tpu.fleet import router as fleet_router
    from aios_tpu.obs import fleet, flightrec, incidents, tsdb
    from aios_tpu.runtime import service as runtime_service
    from aios_tpu.serving import autoscale, failover, pool

    kinds = _call_site_kinds(
        batching, engine_mod, pool, runtime_service, flightrec,
        failover, faults_inject, faults_net, autoscale, fleet,
        fleet_breaker, fleet_disagg, fleet_drain, fleet_kvx, fleet_router,
        incidents, tsdb,
    )
    assert kinds, "no recorder event call sites found"
    unknown = kinds - set(flightrec.EVENT_KINDS)
    assert not unknown, (
        f"event kinds {sorted(unknown)} not in the closed EVENT_KINDS "
        f"enum — extend the enum (reviewed) instead of inventing strings"
    )


def test_shed_causes_one_shared_enum():
    """Admission, the pool's shed tallies, and the recorder's shed
    events all draw from the SAME tuple object —
    obs.flightrec.SHED_CAUSES — so the aios_tpu_serving_shed_total label
    set and the timeline shed_cause field cannot drift apart."""
    from aios_tpu.analysis.core import (
        module_info_for, names_used_in, string_call_args,
    )
    from aios_tpu.obs import flightrec
    from aios_tpu.serving import admission, pool

    assert pool.SHED_CAUSES is flightrec.SHED_CAUSES
    assert admission.SHED_CAUSES is flightrec.SHED_CAUSES
    adm_mi = module_info_for(admission)
    init = adm_mi.functions["AdmissionController.__init__"]
    assert "SHED_CAUSES" in names_used_in(init.node), (
        "the shed-counter children must be built from the shared enum"
    )
    # every cause raised anywhere must be a member (`.shed("<cause>", ...)`
    # call sites in admission AND pool, via the shared AST walker)
    pool_mi = module_info_for(pool)
    causes = {
        lit
        for mi in (adm_mi, pool_mi)
        for lit, _ in string_call_args(mi.tree, ("shed",), 0)
    }
    assert causes, "no shed call sites found"
    assert causes <= set(flightrec.SHED_CAUSES)


def test_abort_reasons_normalize_onto_closed_enum():
    """Every abort_reason string the batcher can set maps to a
    NON-'other' member of flightrec.ABORT_CAUSES — a new abort path must
    extend the mapping (reviewed), or its timelines and SLO samples
    degrade to the catch-all bucket."""
    from aios_tpu.analysis.core import (
        assigned_string_literals, call_string_heads, module_info_for,
    )
    from aios_tpu.engine import batching
    from aios_tpu.obs import flightrec

    mi = module_info_for(batching)
    literals = {
        lit for lit, _ in assigned_string_literals(mi.tree, "abort_reason")
    }
    literals |= {
        lit for lit, _ in call_string_heads(mi.tree, "_terminate_outstanding")
    }
    assert literals, "no abort_reason literals found in the batcher"
    for reason in literals:
        cause = flightrec.abort_cause(reason)
        assert cause in flightrec.ABORT_CAUSES
        assert cause != "other", (
            f"abort_reason {reason!r} falls into the catch-all bucket; "
            f"extend flightrec.abort_cause/ABORT_CAUSES"
        )


def test_faults_family_complete_and_typed():
    """The fault-injection instrument the ISSUE 10 catalog promises:
    one counter, labeled (point, mode), both drawn from the closed
    faults.POINTS / faults.MODES enums — a fired fault must never mint
    a free-form label value."""
    from aios_tpu import faults

    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_faults_")
    }
    assert family == {"aios_tpu_faults_injected_total": "counter"}
    for m in _catalog():
        if m.name.startswith("aios_tpu_faults_"):
            assert tuple(m.labelnames) == ("point", "mode")
    # the only strings handed to the point label come from the catalog:
    # FaultPlan.check validates the name against the parsed schedule,
    # whose keys _parse restricts to faults.POINTS
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.faults import inject

    mi = module_info_for(inject)
    assert "POINTS" in names_used_in(mi.functions["_parse"].node)
    assert set(faults.MODES) == {"nth", "prob", "after"}


AUTOSCALE_EXPECTED = {
    "aios_tpu_autoscale_actions_total": "counter",
}


def test_autoscale_family_complete_and_typed():
    """The SLO-autoscaler instrument the ISSUE 15 catalog promises, with
    labels exactly (model, action, cause) — any NEW aios_tpu_autoscale_*
    metric must be added here (and to docs/OBSERVABILITY.md) so the
    family stays reviewed."""
    family = {
        m.name: m.kind for m in _catalog()
        if m.name.startswith("aios_tpu_autoscale_")
    }
    assert family == AUTOSCALE_EXPECTED
    for m in _catalog():
        if m.name.startswith("aios_tpu_autoscale_"):
            assert tuple(m.labelnames) == ("model", "action", "cause")


def test_autoscale_enums_closed_and_iterated_at_registration():
    """``action`` and ``cause`` label values come from the closed
    autoscale.ACTIONS / CAUSES tuples and nowhere else: the controller
    pre-registers every (action, cause) child by iterating both enums
    (the SLO-objectives pattern), and every ``_record(action, cause)``
    call site's literals are members."""
    from aios_tpu.analysis.core import (
        call_string_heads, module_info_for, names_used_in,
    )
    from aios_tpu.serving import autoscale

    assert autoscale.ACTIONS == (
        "scale_up", "scale_down", "degrade", "restore",
    )
    assert autoscale.CAUSES == (
        "burn", "ceiling", "recovery", "kill_switch",
    )
    assert autoscale.LADDER == (
        "spec_off", "jump_off", "shed_best_effort",
    )
    mi = module_info_for(autoscale)
    init = mi.functions["AutoscaleController.__init__"]
    used = names_used_in(init.node)
    assert "ACTIONS" in used and "CAUSES" in used, (
        "autoscale metric children must be pre-registered by iterating "
        "the closed enums"
    )
    # every action literal handed to _record is an ACTIONS member (the
    # cause rides the second positional arg; heads() yields the first)
    heads = {lit for lit, _ in call_string_heads(mi.tree, "_record")}
    assert heads, "no _record call sites found"
    assert heads <= set(autoscale.ACTIONS)
    import ast as ast_mod

    from aios_tpu.analysis.core import iter_calls

    causes = set()
    for call in iter_calls(mi.tree):
        fn = call.func
        name = getattr(fn, "attr", getattr(fn, "id", ""))
        if name == "_record" and len(call.args) >= 2 and isinstance(
            call.args[1], ast_mod.Constant
        ):
            causes.add(call.args[1].value)
    assert causes and causes <= set(autoscale.CAUSES)


# -- the fleet telemetry family (obs/fleet.py, ISSUE 16) -------------------

# Every aios_tpu_fleet_* family, pinned name -> (kind, labelnames):
# the ISSUE 16 membership plane carries (host, role) — the per-process
# identity axes — while the ISSUE 17 data plane (kvx transfers, fleet
# routing) carries model plus ONE closed-enum dimension, the serving
# metric convention. Any NEW fleet metric must be added here (and to
# docs/OBSERVABILITY.md) so the family stays reviewed.
FLEET_EXPECTED = {
    "aios_tpu_fleet_member_up_total": ("gauge", ("host", "role")),
    "aios_tpu_fleet_member_transitions_total": (
        "counter", ("host", "role", "state")),
    "aios_tpu_fleet_scrape_failures_total": ("counter", ("host", "role")),
    "aios_tpu_fleet_kvx_pages_total": ("counter", ("model", "direction")),
    "aios_tpu_fleet_kvx_bytes_total": ("counter", ("model", "direction")),
    "aios_tpu_fleet_kvx_failures_total": ("counter", ("model", "cause")),
    "aios_tpu_fleet_route_total": ("counter", ("model", "reason")),
    # ISSUE 18 fault domains: the breaker gauge is an EDGE series —
    # host is the OBSERVING side, peer the judged side (value = index
    # into the closed BREAKER_STATES enum); the announce counter keys
    # by peer address alone (the asymmetric-partition signature)
    "aios_tpu_fleet_peer_breaker_state_total": ("gauge", ("host", "peer")),
    "aios_tpu_fleet_announce_failures_total": ("counter", ("peer",)),
}


def test_fleet_family_complete_and_typed():
    """The fleet-plane instruments the ISSUE 16/17 catalogs promise
    exist with the promised kinds AND exactly the pinned label sets —
    membership metrics on (host, role), data-plane metrics on (model,
    <closed enum>). An unreviewed aios_tpu_fleet_* metric fails here."""
    family = {
        m.name: (m.kind, tuple(m.labelnames)) for m in _catalog()
        if m.name.startswith("aios_tpu_fleet_")
    }
    assert family == FLEET_EXPECTED


def test_fleet_member_states_closed_and_iterated_at_registration():
    """The ``state`` label values come from the closed
    fleet.MEMBER_STATES tuple and nowhere else: the registry
    pre-registers every (host, role, state) child by iterating the enum
    (the autoscale/SLO registration pattern), so a new lifecycle state
    is a reviewed enum change, never a stray label value."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.obs import fleet

    assert fleet.MEMBER_STATES == ("up", "suspect", "dead")
    mi = module_info_for(fleet)
    fn = mi.functions["FleetRegistry._register_member_metrics"]
    assert "MEMBER_STATES" in names_used_in(fn.node), (
        "fleet transition children must be pre-registered by iterating "
        "the MEMBER_STATES enum"
    )
    # the failure detector compares states by enum POSITION (a detector
    # may only worsen a state) — it must read the same tuple
    tick = mi.functions["FleetRegistry.tick"]
    assert "MEMBER_STATES" in names_used_in(tick.node)


def test_fleet_kvx_and_route_enums_closed_and_iterated_at_registration():
    """The data-plane label values come from the closed enum tuples and
    nowhere else: ``direction``/``cause`` from kvx.KVX_DIRECTIONS /
    KVX_FAIL_CAUSES, ``reason`` from router.FLEET_ROUTE_REASONS — and
    each registration helper pre-registers every child by iterating its
    enum (the MEMBER_STATES/autoscale pattern), so a new transfer
    failure mode or routing outcome is a reviewed enum change, never a
    stray label value."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.fleet import kvx, router

    assert kvx.KVX_DIRECTIONS == ("push", "pull")
    assert kvx.KVX_FAIL_CAUSES == (
        "unavailable", "timeout", "crc_mismatch", "decode_error", "empty",
        "breaker_open",
    )
    assert router.FLEET_ROUTE_REASONS == (
        "local", "no_peer", "remote_pull", "handoff", "handoff_resume",
        "fallback_local",
    )
    kmi = module_info_for(kvx)
    used = names_used_in(kmi.functions["register_kvx_metrics"].node)
    assert "KVX_DIRECTIONS" in used and "KVX_FAIL_CAUSES" in used, (
        "kvx metric children must be pre-registered by iterating the "
        "closed enums"
    )
    rmi = module_info_for(router)
    assert "FLEET_ROUTE_REASONS" in names_used_in(
        rmi.functions["register_route_metrics"].node
    ), (
        "route metric children must be pre-registered by iterating "
        "FLEET_ROUTE_REASONS"
    )


def test_fault_domain_enums_closed_and_pinned():
    """The ISSUE 18 fault-domain vocabularies are closed enums, pinned
    here so growing any of them is a reviewed change: breaker states
    (the gauge VALUE is an index into the tuple — order is part of the
    contract), drain phases (descriptor ``phase`` values and the
    /fleet/drain response vocabulary), the per-edge net fault points
    (a subset of the faults.POINTS catalog), and the net surface /
    string-param scoping keys the injector recognizes."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu import faults
    from aios_tpu.faults import inject, net
    from aios_tpu.fleet import breaker, drain

    assert breaker.BREAKER_STATES == ("closed", "open", "half_open")
    assert drain.DRAIN_PHASES == ("serving", "draining", "leaving")
    assert net.NET_POINTS == (
        "net.partition", "net.partition_oneway", "net.delay",
        "net.drop_after",
    )
    assert set(net.NET_POINTS) <= set(faults.POINTS), (
        "every net point must live in the faults.POINTS catalog so "
        "_parse accepts it and the injected-total label stays closed"
    )
    assert net.SURFACES == ("rpc", "http")
    assert inject._STR_PARAMS == ("src", "dst", "surface"), (
        "the per-edge scoping params are the ONLY string-valued fault "
        "params; anything else must stay a float"
    )
    # the gauge value and the emitted transition both come from the
    # SAME tuple: _emit indexes BREAKER_STATES (checked on the AST)
    bmi = module_info_for(breaker)
    assert "BREAKER_STATES" in names_used_in(
        bmi.functions["BreakerBoard._emit"].node
    ), "breaker gauge values must be indices into BREAKER_STATES"


def test_process_info_gauge_is_an_identity_series():
    """aios_tpu_process_info is the catalog's one *_info gauge: identity
    entirely in labels (host, rank, role, version), value pinned to 1 by
    fleet.stamp_process_info — the join key for every federated series
    and every bench.py JSON line."""
    family = [m for m in _catalog() if m.name == "aios_tpu_process_info"]
    assert len(family) == 1
    m = family[0]
    assert m.kind == "gauge"
    assert tuple(m.labelnames) == ("host", "rank", "role", "version")


def test_failover_outcomes_closed_enum():
    """The failover counter's outcome label values are members of the
    closed failover.FAILOVER_OUTCOMES tuple at every call site."""
    from aios_tpu.analysis.core import iter_calls, module_info_for
    import ast as ast_mod

    from aios_tpu.serving import failover

    mi = module_info_for(failover)
    outcomes = set()
    for call in iter_calls(mi.tree):
        for kw in call.keywords:
            if kw.arg == "outcome" and isinstance(
                kw.value, ast_mod.Constant
            ):
                outcomes.add(kw.value.value)
    assert outcomes, "no failover outcome call sites found"
    assert outcomes <= set(failover.FAILOVER_OUTCOMES)


# -- the tsdb + incident families (obs/tsdb.py, obs/incidents.py, ISSUE 20) -

# The black-box ring's self-accounting: sample passes and per-verb query
# counts are monotonic counters; the live/dropped series counts are
# gauges (they can fall on clear()). Any NEW aios_tpu_tsdb_* metric must
# be added here (and to docs/OBSERVABILITY.md) so the family stays
# reviewed.
TSDB_EXPECTED = {
    "aios_tpu_tsdb_sample_passes_total": ("counter", ()),
    "aios_tpu_tsdb_series_total": ("gauge", ()),
    "aios_tpu_tsdb_dropped_series_total": ("gauge", ()),
    "aios_tpu_tsdb_queries_total": ("counter", ("verb",)),
}

INCIDENTS_EXPECTED = {
    "aios_tpu_incidents_total": ("counter", ("cause",)),
    "aios_tpu_incidents_suppressed_total": ("counter", ("cause",)),
}


def test_tsdb_family_complete_and_typed():
    family = {
        m.name: (m.kind, tuple(m.labelnames)) for m in _catalog()
        if m.name.startswith("aios_tpu_tsdb_")
    }
    assert family == TSDB_EXPECTED


def test_incidents_family_complete_and_typed():
    family = {
        m.name: (m.kind, tuple(m.labelnames)) for m in _catalog()
        if m.name.startswith("aios_tpu_incidents_")
    }
    assert family == INCIDENTS_EXPECTED


def test_tsdb_query_verbs_closed_and_iterated_at_registration():
    """The ``verb`` label values come from the closed tsdb.QUERY_VERBS
    tuple and nowhere else: the ring pre-registers every verb child by
    iterating the enum (the autoscale/SLO registration pattern), and
    query() validates against the same tuple — so a new query verb is a
    reviewed enum change, never a stray label value."""
    from aios_tpu.analysis.core import module_info_for, names_used_in
    from aios_tpu.obs import tsdb

    assert tsdb.QUERY_VERBS == (
        "raw", "rate", "avg", "min", "max", "p50", "p90", "p95", "p99",
    )
    assert tsdb.SERIES_KINDS == ("delta", "gauge")
    mi = module_info_for(tsdb)
    assert "QUERY_VERBS" in names_used_in(
        mi.functions["Tsdb._register_metrics"].node
    ), "tsdb query children must be pre-registered by iterating QUERY_VERBS"
    assert "QUERY_VERBS" in names_used_in(mi.functions["Tsdb.query"].node), (
        "query() must validate verbs against the same closed enum"
    )


def test_incident_trigger_causes_closed_and_iterated_at_registration():
    """The ``cause`` label values come from the closed
    incidents.TRIGGER_CAUSES tuple and nowhere else: the store
    pre-registers every cause child by iterating the enum, notify()
    normalizes unknown strings onto it, every literal a trigger hook
    hands to notify() is a member (checked on the AST across the three
    non-flightrec hooks), and the flightrec snapshot causes — which ride
    through notify() verbatim — are a subset."""
    from aios_tpu.analysis.core import (
        module_info_for, names_used_in, string_call_args,
    )
    from aios_tpu.faults import inject as faults_inject
    from aios_tpu.fleet import breaker as fleet_breaker
    from aios_tpu.obs import flightrec, incidents
    from aios_tpu.serving import autoscale

    assert incidents.TRIGGER_CAUSES == (
        "abort", "autoscale", "breaker_open", "crash_respawn", "fault",
        "manual", "no_progress", "shed_spike", "slo_breach",
    )
    mi = module_info_for(incidents)
    assert "TRIGGER_CAUSES" in names_used_in(
        mi.functions["IncidentStore._register_metrics"].node
    ), "incident children must be pre-registered by iterating the enum"
    assert "TRIGGER_CAUSES" in names_used_in(
        mi.functions["IncidentStore.notify"].node
    ), "notify() must normalize causes against the same closed enum"
    causes = set()
    for mod in (autoscale, fleet_breaker, faults_inject):
        hmi = module_info_for(mod)
        causes |= {
            lit for lit, _ in string_call_args(hmi.tree, ("notify",), 1)
        }
    assert causes == {"autoscale", "breaker_open", "fault"}, (
        f"trigger hooks emit causes {sorted(causes)} — each hook owns "
        f"exactly one TRIGGER_CAUSES member"
    )
    assert set(flightrec.SNAPSHOT_CAUSES) <= set(incidents.TRIGGER_CAUSES), (
        "snapshot causes ride through notify() verbatim, so every one "
        "must be a TRIGGER_CAUSES member"
    )


def test_debug_route_index_complete():
    """Every route the HTTP handler dispatches on (the ``path == "/..."``
    comparisons, collected on the AST) appears in the ROUTES index that
    GET /debug renders, and vice versa — a new endpoint that skips the
    index fails here."""
    import ast as ast_mod

    from aios_tpu.analysis.core import module_info_for
    from aios_tpu.obs import http as http_mod

    mi = module_info_for(http_mod)
    dispatched = set()
    for node in ast_mod.walk(mi.tree):
        if not isinstance(node, ast_mod.Compare):
            continue
        for cand in [node.left, *node.comparators]:
            if isinstance(cand, ast_mod.Constant) and isinstance(
                cand.value, str
            ) and cand.value.startswith("/"):
                dispatched.add(cand.value)
    indexed = {route for _, route, _ in http_mod.ROUTES}
    assert dispatched == indexed, (
        f"route index out of sync: dispatched-but-unindexed "
        f"{sorted(dispatched - indexed)}, indexed-but-undispatched "
        f"{sorted(indexed - dispatched)}"
    )


def test_serving_label_conventions():
    """Serving labels stay low-cardinality by construction: routing
    reasons and shed causes are fixed enums (see serving/pool.py); only
    the quota metric carries the tenant label, and nothing carries both
    tenant and model (series count = tenants x models would blow the
    child cap under many co-resident models)."""
    for m in _catalog():
        if not m.name.startswith("aios_tpu_serving_"):
            continue
        assert not ("tenant" in m.labelnames and "model" in m.labelnames), (
            f"{m.name}: tenant x model label product is unbounded"
        )
