"""A model's set-up in spans (obs/flightrec.py `SETUP_PHASES`, the `compile`
event, the compile-cache counters): what `load_model` of the tiny model leaves
behind, on the CPU. Counts and containment only; a time is never asserted."""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import pytest

from aios_tpu.engine import model as M
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.obs import flightrec
from aios_tpu.runtime.model_manager import ModelManager

SERVED = "setup-tiny"  # the served name: LoadModel's `model_name`
# the configuration's name: the key of the model's lane, of its requests'
# timelines and of the phases' ring, whatever name it is served under
PRESET = "tiny-test"
STAGES = ("warmup.trace", "warmup.lower", "warmup.compile")
PARTS = ("load.weights", "load.engine", "load.warmup", "load.attach")
# what the parent commit compiles for this load (lower().compile(), PR 37)
PARENT_COMPILES = 5


def _compile_events(model):
    return [f for _, _, kind, f in flightrec.RECORDER.model_events(model)
            if kind == "compile"]


def _spans_since(began):
    """The spans of the configuration's ring opened since ``began`` (other
    loads of the same preset in this process wrote to it before)."""
    return [(n, t0, t1) for _, n, t0, t1 in flightrec.RECORDER.phases(PRESET)
            if t0 >= began]


@pytest.fixture(scope="module")
def loaded():
    """The tiny model through `load_model`, warm-up on, and what it left
    before any request: its counters, its spans, its `compile` events."""
    seen, began = len(_compile_events(PRESET)), time.monotonic()
    manager = ModelManager(num_slots=2, warm_compile=True)
    managed = manager.load_model(SERVED, f"synthetic://{PRESET}", 128)
    try:
        yield {
            "managed": managed,
            "stats": dict(managed.pool.stats()),
            "spans": _spans_since(began),
            "events": _compile_events(PRESET)[seen:],
        }
    finally:
        manager.unload_model(SERVED)


def test_every_graph_compiled_ahead_has_one_triple_and_one_compile_event(loaded):
    stats, events = loaded["stats"], loaded["events"]
    graphs = stats["xla_compiles"]
    assert graphs == PARENT_COMPILES
    for stage in STAGES:
        assert stats[f"phase_{stage}_count"] == graphs, stage
    assert len(events) == graphs
    assert len({(e["graph"], e["key"]) for e in events}) == graphs
    for e in events:
        assert set(e) == {"graph", "key", "trace_ms", "lower_ms", "compile_ms",
                          "cpu_ms", "cache_hit"}
    # the ring holds the triples in the order of the stages, graph after graph
    names = [n for n, _, _ in loaded["spans"] if n.startswith("warmup.")]
    assert names == list(STAGES) * graphs


def test_the_three_stages_sum_to_xla_compile_s(loaded):
    stats, events = loaded["stats"], loaded["events"]
    staged = sum(stats[f"phase_{stage}_seconds"] for stage in STAGES)
    assert stats["xla_compile_s"] == pytest.approx(staged, abs=0.006)  # rounded to 0.01
    said = sum(e["trace_ms"] + e["lower_ms"] + e["compile_ms"] for e in events)
    assert said / 1e3 == pytest.approx(staged, abs=1e-3 * len(events))
    # tracing and lowering ran on the calling thread: its CPU seconds are
    # counted, and an event's are its graph's share of them
    assert stats["warmup_trace_cpu_seconds"] > 0
    assert sum(e["cpu_ms"] for e in events) / 1e3 == pytest.approx(
        stats["warmup_trace_cpu_seconds"], abs=1e-3 * len(events))


def test_load_model_contains_its_four_parts_and_they_do_not_overlap(loaded):
    by = {}
    for name, t0, t1 in loaded["spans"]:
        by.setdefault(name, []).append((t0, t1))
    assert {n: len(v) for n, v in by.items() if n.startswith("load.")} == dict.fromkeys(
        ("load.model",) + PARTS, 1)
    (m0, m1), = by["load.model"]
    parts = sorted(by[p][0] for p in PARTS)
    assert parts == [by[p][0] for p in PARTS]  # in the order of the code
    assert m0 <= parts[0][0] and parts[-1][1] <= m1
    assert all(a[1] <= b[0] for a, b in zip(parts, parts[1:]))
    # every stage of every graph lies in the warm-up or in the attach
    (w0, w1), (a0, a1) = by["load.warmup"][0], by["load.attach"][0]
    for stage in STAGES:
        assert all(w0 <= t0 and t1 <= w1 or a0 <= t0 and t1 <= a1
                   for t0, t1 in by[stage]), stage
    stats = loaded["stats"]
    named = sum(stats[f"phase_{p}_seconds"] for p in PARTS)
    assert 0 <= stats["phase_load.model_seconds"] - named
    assert stats["phase_load.model_count"] == 1


def test_setup_seconds_three_keys_are_their_spans_seconds(loaded):
    stats, setup = loaded["stats"], loaded["managed"].setup_seconds
    assert set(setup) == {"weights", "engines", "warmup"}
    for key, span in (("weights", "load.weights"), ("engines", "load.engine"),
                      ("warmup", "load.warmup")):
        assert setup[key] == round(stats[f"phase_{span}_seconds"], 2), key


def test_a_name_outside_both_closed_lists_still_raises():
    ph = flightrec.Phases("setup-closed")
    assert not set(flightrec.PHASES) & set(flightrec.SETUP_PHASES)
    assert set(ph.seconds) == set(flightrec.PHASES) | set(flightrec.SETUP_PHASES)
    for name in ("load.nothing", "warmup", "batcher.load"):
        with pytest.raises(KeyError):
            ph.end(ph.begin(name))
    stats = ph.stats()
    for name in flightrec.SETUP_PHASES:
        assert stats[f"phase_{name}_count"] == 0 and stats[f"phase_{name}_seconds"] == 0.0
    assert len(stats) == 2 * len(flightrec.PHASES + flightrec.SETUP_PHASES) + 1


def test_the_cache_counters_follow_jax_s_own_two_events(loaded):
    from jax import monitoring
    from jax._src import monitoring as registry

    listeners = len(registry.get_event_listeners())
    requests, hits = flightrec.compile_cache()
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")  # not one of the two
    monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert flightrec.compile_cache() == (requests + 2, hits + 1)
    # one listener a process, however often the counters are read
    assert len(registry.get_event_listeners()) == listeners
    stats = loaded["managed"].pool.stats()
    assert (stats["compile_cache_requests"], stats["compile_cache_hits"]) == (
        requests + 2, hits + 1)
    engine = loaded["managed"].engine.stats()
    assert (engine["compile_cache_requests"], engine["compile_cache_hits"]) == (
        requests + 2, hits + 1)


def test_two_replicas_sum_their_spans_and_report_the_process_s_cache_once(monkeypatch):
    monkeypatch.setenv("AIOS_TPU_REPLICAS", "2")
    manager = ModelManager(num_slots=2, warm_compile=True)
    managed = manager.load_model("setup-two", f"synthetic://{PRESET}", 128)
    try:
        stats = managed.pool.stats()
        assert stats["replicas"] == 2
        assert stats["phase_load.model_count"] == stats["phase_load.weights_count"] == 1
        assert stats["phase_load.attach_count"] == 1
        assert stats["phase_load.engine_count"] == stats["phase_load.warmup_count"] == 2
        assert stats["xla_compiles"] == 2 * PARENT_COMPILES
        assert stats["phase_warmup.compile_count"] == stats["xla_compiles"]
        # a replica's engine keeps the phases LoadModel made for it
        engines = [r.engine for r in managed.pool.replicas]
        assert engines[0].phases is not engines[1].phases
        assert [e.phases.counts["load.model"] for e in engines] == [1, 0]
        assert [e.phases.counts["load.engine"] for e in engines] == [1, 1]
        assert (stats["compile_cache_requests"], stats["compile_cache_hits"]) == (
            flightrec.compile_cache())
    finally:
        manager.unload_model("setup-two")


def _streams(engine):
    b = ContinuousBatcher(engine, chunk_steps=4, admit_chunk_steps=4)
    try:
        return [b.submit(Request(prompt_ids=[3 + i, 17, 91], max_tokens=13,
                                 temperature=0.0)).tokens() for i in range(3)]
    finally:
        b.shutdown()


def test_staged_compiles_give_the_parent_s_text_streams_and_compile_count(monkeypatch):
    """`jitfn.trace(*args).lower().compile()` against the parent's
    `jitfn.lower(*args).compile()`: the same StableHLO text (so the same
    entry of a machine's compile cache), the same count of graphs, the same
    tokens for the same seed."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)

    def engine(name):
        return TPUEngine(dataclasses.replace(TINY_TEST, name=name), params,
                         num_slots=2, max_context=128, cache_dtype=jnp.float32)

    staged = engine("setup-staged")
    jitfn, args = staged._make_step_jit(4), staged._step_example()
    assert jitfn.trace(*args).lower().as_text() == jitfn.lower(*args).as_text()
    staged.warmup(step_sizes=(2, 4), prefill_chunk=0)
    compiled = staged.stats()["xla_compiles"]
    assert staged.phases.counts["warmup.compile"] == compiled > 0
    mine = _streams(staged)
    assert staged.stats()["xla_compiles"] == compiled  # none after the warm-up
    staged.close()

    def as_the_parent(self, kind, store, key, jitfn, example_args):
        if key not in store:
            store[key] = jitfn.lower(*example_args).compile()
            self.compile_events += 1

    monkeypatch.setattr(TPUEngine, "_compile_aot", as_the_parent)
    parent = engine("setup-parent")
    parent.warmup(step_sizes=(2, 4), prefill_chunk=0)
    assert parent.stats()["xla_compiles"] == compiled
    assert parent.phases.counts["warmup.compile"] == 0
    assert _streams(parent) == mine
    parent.close()


class _SlowAnnotation:
    """A profiler whose own calls take a while, as S6's stall did."""

    enabled = True
    pause = 0.02

    def __init__(self, name, **args):
        self.name, self.args = name, args

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        time.sleep(self.pause)
        return self

    def __exit__(self, *exc):
        time.sleep(self.pause)
        return False


def test_the_profiler_s_own_calls_are_counted_beside_the_spans_not_in_them(monkeypatch):
    ph = flightrec.Phases("setup-annotated")
    assert ph.stats()["trace_annotation_seconds"] == 0.0
    with ph.phase("warmup.trace", kind="step", key="2"):
        pass
    assert ph.stats()["trace_annotation_seconds"] == 0.0  # no profile is being taken
    monkeypatch.setattr(ph, "_annotation", _SlowAnnotation)
    span = ph.begin("warmup.trace", kind="step", key="2")
    assert span.ann.args == {"kind": "step", "key": "2"}
    ph.end(span)
    with ph.phase("engine.enqueue"):
        pass
    stats = ph.stats()
    # four calls of the profiler, each outside the span it opens or closes
    assert stats["trace_annotation_seconds"] >= 4 * _SlowAnnotation.pause
    assert stats["phase_warmup.trace_seconds"] + stats["phase_engine.enqueue_seconds"] < (
        _SlowAnnotation.pause)
    assert stats["phase_warmup.trace_count"] == 2 and stats["phase_engine.enqueue_count"] == 1
    _SlowAnnotation.enabled = False
    try:
        before = ph.stats()["trace_annotation_seconds"]
        with ph.phase("engine.enqueue") as off:
            pass
        assert off.ann is None and ph.stats()["trace_annotation_seconds"] == before
    finally:
        _SlowAnnotation.enabled = True


def test_compile_events_ride_the_engine_lane_of_the_chrome_trace(loaded):
    assert "compile" in flightrec.EVENT_KINDS
    lane = [e for e in flightrec.RECORDER.model_events(PRESET) if e[2] == "compile"]
    # one model under one name: the served name keys no ring of its own
    assert flightrec.RECORDER.phases(SERVED) == []
    trace = flightrec.chrome_trace([], lane, flightrec.RECORDER.phases(PRESET))
    json.dumps(trace)  # what /debug/trace serves
    events = trace["traceEvents"]
    compiles = [e for e in events if e.get("name") == "compile"]
    assert len(compiles) >= PARENT_COMPILES
    assert all(e["ph"] == "i" and e["tid"] == 0 and "graph" in e["args"] for e in compiles)
    spans = {e["name"] for e in events if e.get("cat") == "phase"}
    # (`load.states` is a model's with a state kind alone: tests/test_ling3.py)
    assert set(flightrec.SETUP_PHASES) - {"load.states"} <= spans


def test_with_the_recorder_disabled_set_up_still_counts_and_leaves_no_event(monkeypatch):
    monkeypatch.setattr(flightrec.RECORDER, "enabled", False)
    began = time.monotonic()
    manager = ModelManager(num_slots=2, warm_compile=True)
    managed = manager.load_model("setup-off", f"synthetic://{PRESET}", 128)
    try:
        stats = managed.pool.stats()
        assert stats["phase_warmup.compile_count"] == stats["xla_compiles"] == PARENT_COMPILES
        assert stats["phase_load.model_count"] == 1
        assert _spans_since(began) == []
    finally:
        manager.unload_model("setup-off")


def test_a_failed_load_closes_load_model_and_counts_its_seconds(monkeypatch):
    made = []
    new = flightrec.Phases

    def keep(*args):
        made.append(new(*args))
        return made[-1]

    monkeypatch.setattr(flightrec, "Phases", keep)
    manager = ModelManager(num_slots=2, warm_compile=True)
    with pytest.raises(Exception):
        manager.load_model("setup-none", "synthetic://no-such-preset", 128)
    (phases,) = made
    assert phases.counts["load.model"] == phases.counts["load.weights"] == 1
    assert phases.seconds["load.model"] >= phases.seconds["load.weights"] > 0
    assert phases.counts["load.engine"] == 0
    assert phases.recent(4) == []  # it never learnt its model's name: no ring


def test_set_up_s_spans_open_on_no_thread_but_the_loader_s(loaded):
    """No span of the set-up's list is opened once the model serves: a few
    requests later every set-up count stands where `load_model` left it."""
    managed, before = loaded["managed"], loaded["stats"]
    outs = [managed.batcher.submit(Request(prompt_ids=[5, 6, 7], max_tokens=6,
                                           temperature=0.0)).tokens() for _ in range(2)]
    assert [len(o) for o in outs] == [6, 6]
    after = managed.pool.stats()
    for name in flightrec.SETUP_PHASES:
        assert after[f"phase_{name}_count"] == before[f"phase_{name}_count"], name
    assert after["xla_compiles"] == before["xla_compiles"]
    assert after["phase_batcher.dispatch_count"] > before["phase_batcher.dispatch_count"]
