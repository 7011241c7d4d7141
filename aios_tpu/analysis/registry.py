"""The declared concurrency model of the serving plane.

This file is the single place where a reviewer states WHICH locks exist,
what each one is allowed to shelter, which fields they guard, and which
functions run with a lock already held by their caller (hooks reached
through dynamic dispatch the AST cannot follow). The rules in
:mod:`aios_tpu.analysis.rules` are generic; everything repo-specific
lives here, so adding a lock to the serving plane is a one-line reviewed
registry change — and forgetting to add it means the analyzer simply
does not defend it, which a reviewer can see at a glance.

The same declarations drive the runtime half: ``locks.make_lock(<name>)``
call sites in the declared modules switch to the order-checking
:class:`~aios_tpu.analysis.locks.DebugLock` under ``AIOS_TPU_LOCK_DEBUG=1``
(the lock NAMES here and there must match — ``test_analysis`` checks it).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

# hazard classes rule lock-discipline knows how to spot. A lock forbids
# these three unless it says otherwise; "placement" (below) is forbidden
# only by a lock that names it
HAZARDS = ("dispatch", "readback", "rpc")


@dataclass(frozen=True)
class LockDecl:
    """One declared lock: where it lives and what its body must not do."""

    name: str          # registry id, also the DebugLock name
    module: str        # dotted module
    class_name: str    # owning class (subclasses inherit the discipline)
    attr: str          # attribute the lock is stored under
    forbids: Tuple[str, ...] = HAZARDS

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.module, self.class_name, self.attr)


# -- the lock registry -------------------------------------------------------
# The engine lock's JOB is sheltering the dispatch + donated state swap,
# so it forbids only host-blocking work (D2H readback, RPC) — exactly the
# class of bug PRs 4 and 6 each fixed by hand. Every other serving-plane
# lock is a pure bookkeeping lock: a dispatch or readback under it stalls
# the router/scheduler/scrape threads that share it.

LOCKS: Tuple[LockDecl, ...] = (
    # ... and "placement": the lock is what the dispatch worker waits for
    # between two decode dispatches, so what the scheduler's thread does
    # under it (a retirement, an admission's set-up) holds the device up.
    # Operands are built before the lock and handed to the compiled graph
    # as numpy values; what stays is waived with its reason (PR 41)
    LockDecl("engine", "aios_tpu.engine.engine", "TPUEngine", "_lock",
             forbids=("readback", "rpc", "placement")),
    LockDecl("engine_spill", "aios_tpu.engine.engine", "TPUEngine",
             "_spill_lock"),
    LockDecl("prefix_index", "aios_tpu.engine.paged", "_PrefixIndexBase",
             "_lock"),
    LockDecl("host_store", "aios_tpu.engine.paged", "HostPageStore",
             "_lock"),
    LockDecl("batcher", "aios_tpu.engine.batching", "ContinuousBatcher",
             "_lock"),
    LockDecl("batcher_queue", "aios_tpu.engine.batching",
             "ContinuousBatcher", "_qlock"),
    LockDecl("json_masks", "aios_tpu.engine.batching", "ContinuousBatcher",
             "_json_masks_lock"),
    LockDecl("pool", "aios_tpu.serving.pool", "ReplicaPool", "_lock"),
    LockDecl("router", "aios_tpu.serving.router", "Router", "_lock"),
    LockDecl("admission", "aios_tpu.serving.admission",
             "AdmissionController", "_lock"),
    LockDecl("token_bucket", "aios_tpu.serving.admission", "TokenBucket",
             "_lock"),
    LockDecl("recorder", "aios_tpu.obs.flightrec", "FlightRecorder",
             "_lock"),
    LockDecl("slo", "aios_tpu.obs.slo", "SLOEngine", "_lock"),
    LockDecl("model_manager", "aios_tpu.runtime.model_manager",
             "ModelManager", "_lock"),
    LockDecl("faults", "aios_tpu.faults.inject", "FaultPlan", "_lock"),
    LockDecl("failover", "aios_tpu.serving.failover", "FailoverHandle",
             "_lock"),
    LockDecl("devprof", "aios_tpu.obs.devprof", "DevprofLedger", "_lock"),
    # autoscale: pure bookkeeping (hold counters, action journal, the
    # added-engine list) — engine builds and pool mutations run outside
    LockDecl("autoscale", "aios_tpu.serving.autoscale",
             "AutoscaleController", "_lock"),
    # fleet: pure bookkeeping (member table, transition journal, peer
    # set) — announces/scrapes (urllib) and metric/recorder emission
    # for state edges always run outside it
    LockDecl("fleet", "aios_tpu.obs.fleet", "FleetRegistry", "_lock"),
    # handoff: cancel/terminal flags and the live local-handle ref on a
    # disaggregated stream — the transfer RPCs themselves (push, fetch,
    # the handoff stream) always run outside it
    LockDecl("handoff", "aios_tpu.fleet.disagg", "HandoffHandle", "_lock"),
    # quarantine: per-peer breaker bookkeeping (EWMAs, state, probe
    # budget) — the cross-host calls whose outcomes feed it always run
    # outside, and metric/recorder emission for state edges happens
    # after release (no quarantine->recorder lock edge)
    LockDecl("quarantine", "aios_tpu.fleet.breaker", "BreakerBoard",
             "_lock"),
    # drain: the phase flag and the one-shot worker handle — the drain
    # protocol itself (pool drain, kvx pushes, the leaving announce)
    # runs on its worker thread outside the lock
    LockDecl("drain", "aios_tpu.fleet.drain", "DrainCoordinator",
             "_lock"),
    # tsdb: the series map and per-series ring/wheel deques — registry
    # reads (which take metric locks) run before it, metric emission
    # after release; queries copy points under it and aggregate outside
    LockDecl("tsdb", "aios_tpu.obs.tsdb", "Tsdb", "_lock"),
    # incidents: bundle deque, cooldown stamps, id counter — bundle
    # construction (tsdb/recorder/faults/devprof reads) and metric/
    # recorder emission always run outside it
    LockDecl("incidents", "aios_tpu.obs.incidents", "IncidentStore",
             "_lock"),
)


# -- static type hints the AST cannot infer ---------------------------------
# (module, class, field) -> (module, class): lets the one-level call walk
# cross object boundaries (`self.engine.step(...)` under a batcher lock is
# a dispatch; `self.prefix_index.put(...)` under the engine lock acquires
# the index lock).

FIELD_TYPES: Dict[Tuple[str, str, str], Tuple[str, str]] = {
    ("aios_tpu.engine.engine", "TPUEngine", "prefix_index"):
        ("aios_tpu.engine.paged", "_PrefixIndexBase"),
    ("aios_tpu.engine.engine", "TPUEngine", "host_store"):
        ("aios_tpu.engine.paged", "HostPageStore"),
    ("aios_tpu.engine.batching", "ContinuousBatcher", "engine"):
        ("aios_tpu.engine.engine", "TPUEngine"),
    ("aios_tpu.serving.pool", "ReplicaPool", "router"):
        ("aios_tpu.serving.router", "Router"),
    ("aios_tpu.serving.pool", "ReplicaPool", "admission"):
        ("aios_tpu.serving.admission", "AdmissionController"),
    ("aios_tpu.serving.pool", "Replica", "engine"):
        ("aios_tpu.engine.engine", "TPUEngine"),
    ("aios_tpu.serving.pool", "Replica", "batcher"):
        ("aios_tpu.engine.batching", "ContinuousBatcher"),
}

# module-level singletons: bare/dotted name -> (module, class)
GLOBAL_TYPES: Dict[str, Tuple[str, str]] = {
    "RECORDER": ("aios_tpu.obs.flightrec", "FlightRecorder"),
}

# -- caller-held lock contexts ----------------------------------------------
# (module, qualname) -> lock names already held when the function runs.
# These are the dynamic-dispatch seams the AST cannot see through; each
# entry mirrors a docstring contract in the named function.

CONTEXT_FNS: Dict[Tuple[str, str], Tuple[str, ...]] = {
    # PrefixIndex eviction paths run from engine-lock-holding callers
    # (see _PrefixIndexBase._drop docstring), and _drop invokes the
    # engine's spill hook synchronously.
    ("aios_tpu.engine.paged", "_PrefixIndexBase._drop"): ("engine",),
    ("aios_tpu.engine.engine", "TPUEngine._spill_pages"): ("engine",),
    # ring accessor contract: only FlightRecorder.finish calls it, under
    # the recorder lock (the lazy setdefault would race otherwise)
    ("aios_tpu.obs.flightrec", "FlightRecorder._ring"): ("recorder",),
    # journal appends happen inside the state-transition critical
    # sections of _observe/tick (see _journal_append docstring)
    ("aios_tpu.obs.fleet", "FleetRegistry._journal_append"): ("fleet",),
}

# hook attributes whose call target is registered dynamically:
# (module, attr-name called as `self.<attr>(...)`) -> (module, qualname)
HOOK_TARGETS: Dict[Tuple[str, str], Tuple[str, str]] = {
    ("aios_tpu.engine.paged", "spill"):
        ("aios_tpu.engine.engine", "TPUEngine._spill_pages"),
    ("aios_tpu.engine.paged", "reclaimer"):
        ("aios_tpu.engine.paged", "_PrefixIndexBase.reclaim"),
}

# locals whose class the AST cannot infer: (module, qualname, local name)
# -> (module, class), so that `eng._lock` in a driver object's method
# resolves to the engine's lock
LOCAL_TYPES: Dict[Tuple[str, str, str], Tuple[str, str]] = {
    ("aios_tpu.engine.engine", "ChunkedPrefill.step_async", "eng"):
        ("aios_tpu.engine.engine", "TPUEngine"),
}

# closure-passed locks: (module, qualname, local name) -> lock name
# (the static spill worker receives the spill lock as a parameter)
LOCAL_LOCKS: Dict[Tuple[str, str, str], str] = {
    ("aios_tpu.engine.engine", "TPUEngine._spill_worker", "lock"):
        "engine_spill",
}

# -- hazard call shapes ------------------------------------------------------
# Device dispatch: jit construction/lowering, jitted-handle accessors
# (the engine's per-kind graph caches), and the engine's dispatching
# public surface (what a batcher/pool calls).

DISPATCH_TERMINALS = frozenset({
    "jit", "lower", "device_put", "jump_step", "spec_step",
    "step", "step_async", "step_masked", "prefill",
})
DISPATCH_FN_HANDLE_RE = re.compile(
    r"^_(step|unified|masked_step|prefill|chunk|spec|jump|restore|hist)_fn$"
)

# D2H readback / host-blocking device sync. `np.asarray` is the repo's
# readback idiom (jnp.asarray is H2D and does NOT match).
READBACK_CHAINS = frozenset({("np", "asarray")})
READBACK_TERMINALS = frozenset({
    "block_until_ready", "device_get", "item", "copy_to_host_async",
})

# Host->device placement of an operand one value at a time, and an eager
# `x.at[...].set(...)` (several small programs): each a point at which the
# thread gives the interpreter up while it holds the lock. Reported in the
# lock-readback family (the same cure: move it out of the lock's body).
# The `.at[...]` shape is matched on the AST (rules._hazard_class).
PLACEMENT_CHAINS = frozenset({
    ("jnp", "asarray"), ("jnp", "array"), ("jnp", "int32"),
    ("jnp", "float32"),
})
AT_UPDATE_TERMINALS = frozenset({"set", "add", "multiply", "min", "max"})

# blocking RPC / host waits: gRPC stubs, channel readiness, future
# results, sleeps, joins. `.get(` is deliberately absent (dict.get).
RPC_TERMINALS = frozenset({
    "sleep", "channel_ready_future", "result", "wait",
})
RPC_CHAIN_MARKER = "stub"  # any chain segment containing this matches


# -- dispatch hygiene (rule jit-warmup) --------------------------------------
# Serving-path modules where a jax.jit call site must be reachable from
# an AOT-warmup registration (the PR 6 "compile counters flat after
# warmup" invariant, statically). ops/ and parallel/ build kernels at
# import/trace time and are exercised by their own tests.

DISPATCH_HYGIENE_MODULES: Tuple[str, ...] = (
    "aios_tpu.engine.engine",
    "aios_tpu.engine.batching",
    # draft-model speculation (spec.DraftModel): its propose/ingest
    # bodies are jitted from engine.py behind compile_draft_spec_fn /
    # compile_draft_ingest_fns, but the module itself is serving-path —
    # a jax.jit added here must be reachable from a warmup registration
    # like everything else on the decode hot path
    "aios_tpu.engine.spec",
)

# a function whose NAME matches counts as a warmup registration root
WARMUP_ROOT_RE = re.compile(r"^(warmup|_compile_aot|compile_\w+)$")


# -- silent-except (rule silent-except) -------------------------------------
# Broad `except Exception` / `except BaseException` / bare `except:`
# handlers in these module prefixes must RECORD the failure — re-raise,
# log it, or land an abort/terminal cause — or carry an
# `# aios: waive(silent-except): <reason>` pragma. Fault paths are the
# least-exercised code in the tree; one that swallows its evidence is an
# observability black hole exactly when the operator needs it most.

SILENT_EXCEPT_PREFIXES: Tuple[str, ...] = (
    "aios_tpu.serving", "aios_tpu.engine",
)

# terminal callee names that count as recording the failure: logging,
# flight-recorder terminal events, gRPC error surfacing, and the
# batcher/pool abort plumbing (which sets abort_reason downstream)
SILENT_EXCEPT_RECORDERS = frozenset({
    "exception", "error", "warning", "critical",
    "finish", "finish_shed", "model_event", "snapshot",
    "abort", "set_details",
    "_abort_all", "_terminate_outstanding", "_finish", "_rec_close",
    "shed", "note_failed_restore",
})

BROAD_EXCEPTION_NAMES = frozenset({"Exception", "BaseException"})


# -- knob/docs drift (rule knob-docs) ---------------------------------------

KNOB_RE = re.compile(r"AIOS_TPU_[A-Z0-9_]+")
CONFIG_DOC = "docs/CONFIG.md"

# metric constructors that must only run inside the instruments catalog
METRIC_CTORS = frozenset({"Counter", "Gauge", "Histogram"})
METRIC_PREFIX = "aios_tpu_"
METRIC_CATALOG_MODULES = frozenset({
    "aios_tpu.obs.instruments", "aios_tpu.obs.metrics",
})


@dataclass
class Registry:
    """Bundle of the declarations above; tests construct custom ones to
    drive rule fixtures, production uses :data:`DEFAULT`."""

    locks: Tuple[LockDecl, ...] = LOCKS
    field_types: Dict[Tuple[str, str, str], Tuple[str, str]] = field(
        default_factory=lambda: dict(FIELD_TYPES))
    global_types: Dict[str, Tuple[str, str]] = field(
        default_factory=lambda: dict(GLOBAL_TYPES))
    context_fns: Dict[Tuple[str, str], Tuple[str, ...]] = field(
        default_factory=lambda: dict(CONTEXT_FNS))
    hook_targets: Dict[Tuple[str, str], Tuple[str, str]] = field(
        default_factory=lambda: dict(HOOK_TARGETS))
    local_locks: Dict[Tuple[str, str, str], str] = field(
        default_factory=lambda: dict(LOCAL_LOCKS))
    local_types: Dict[Tuple[str, str, str], Tuple[str, str]] = field(
        default_factory=lambda: dict(LOCAL_TYPES))
    dispatch_hygiene_modules: Tuple[str, ...] = DISPATCH_HYGIENE_MODULES
    silent_except_prefixes: Tuple[str, ...] = SILENT_EXCEPT_PREFIXES
    silent_except_recorders: frozenset = SILENT_EXCEPT_RECORDERS

    def lock_named(self, name: str) -> Optional[LockDecl]:
        for d in self.locks:
            if d.name == name:
                return d
        return None

    def locks_in_module(self, module: str) -> Tuple[LockDecl, ...]:
        return tuple(d for d in self.locks if d.module == module)


DEFAULT = Registry()
