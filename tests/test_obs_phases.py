"""The scheduler loop's own record (obs/flightrec.py `Phases`, the batcher's
stall and no-progress records, the `queue` event's `slot_free`): what a few
ticks of a tiny model leave behind, on the CPU."""

import dataclasses
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from aios_tpu import faults
from aios_tpu.engine import batching
from aios_tpu.engine import model as M
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.obs import flightrec
from aios_tpu.serving.pool import ReplicaPool

DEVICE_SIDE = {"engine.lock_wait", "engine.enqueue", "engine.readback"}


@pytest.fixture(scope="module")
def params():
    return M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


def _engine(params, name, **kw):
    """A tiny engine under a model name of its own, so that its phase ring,
    its model lane and its snapshot cooldown are no other test's."""
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_context", 128)
    kw.setdefault("cache_dtype", jnp.float32)
    return TPUEngine(dataclasses.replace(TINY_TEST, name=name), params, **kw)


def _spans(model):
    return sorted(((n, t0, t1) for _, n, t0, t1 in flightrec.RECORDER.phases(model)),
                  key=lambda s: (s[1], -s[2]))


def _stalls(model):
    return [f for _, _, kind, f in flightrec.RECORDER.model_events(model) if kind == "stall"]


def _run(b, prompts, max_tokens=12):
    handles = [b.submit(Request(prompt_ids=list(p), max_tokens=max_tokens, temperature=0.0))
               for p in prompts]
    return [h.tokens() for h in handles]


def _inside(span, outer):
    return any(o[1] <= span[1] and span[2] <= o[2] for o in outer)


def test_every_phase_of_the_closed_list_is_counted_and_kept_nested_as_the_code_nests(params):
    # the synchronous loop, a chunked admission (40 tokens in chunks of 16)
    # beside a short one, every graph compiled lazily
    eng = _engine(params, "phases-sync")
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2, prefill_chunk=16,
                          pipeline=False)
    outs = _run(b, [[3, 5, 7], list(range(1, 41))])
    time.sleep(0.12)  # two idle waits
    b.shutdown()
    assert [len(o) for o in outs] == [12, 12]
    spans = _spans("phases-sync")
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    stats = b.stats()
    for name, got in by.items():
        assert stats[f"phase_{name}_count"] == len(got), name
        assert stats[f"phase_{name}_seconds"] == pytest.approx(
            sum(t1 - t0 for _, t0, t1 in got)), name
    assert set(by) == set(flightrec.PHASES) - {
        "batcher.fence", "batcher.consume", "batcher.evict", "batcher.retire"}
    # nested as the code nests them
    for name in DEVICE_SIDE:
        assert all(_inside(s, by["batcher.dispatch"]) for s in by[name]), name
    assert all(_inside(s, by["batcher.prefill"] + by["batcher.admit"])
               for s in by["engine.prefill"])
    assert all(_inside(s, by["engine.enqueue"] + by["engine.prefill"])
               for s in by["engine.compile"])
    assert len(by["engine.prefill"]) >= 4  # three chunks and a whole prompt
    # this loop reads an admission's first token where it admits: one wait a
    # request, inside the phase that issued its last prefill program
    assert len(by["batcher.first_token"]) == 2
    assert all(_inside(s, by["batcher.prefill"] + by["batcher.admit"])
               for s in by["batcher.first_token"])
    # and in the order of a tick: the loop's own phases follow one another,
    # none overlaps the next, and a dispatch is followed by its emit
    loop = [s for s in spans
            if s[0].startswith("batcher.") and s[0] != "batcher.first_token"]
    assert all(a[2] <= b_[1] for a, b_ in zip(loop, loop[1:]))
    names = [s[0] for s in loop]
    for i, name in enumerate(names[:-1]):
        if name == "batcher.dispatch":
            assert names[i + 1] == "batcher.emit"
        if name in ("batcher.prefill", "batcher.admit"):
            assert names[i + 1] in ("batcher.admit", "batcher.dispatch", "batcher.reap",
                                    "batcher.idle")
    assert names[0] == "batcher.reap"
    eng.close()

    # the pipelined loop, the default, fences on the dispatch it handed to
    # the worker, and is judged by the one it consumed
    eng = _engine(params, "phases-pipe")
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=4, prefill_chunk=16)
    assert b.pipeline
    assert [len(o) for o in _run(b, [[3, 5, 7], list(range(1, 41))])] == [12, 12]
    b.shutdown()
    assert b.stats()["phase_batcher.fence_count"] >= 1
    assert b.stats()["phase_batcher.consume_count"] >= 1
    assert {"batcher.fence", "batcher.consume"} <= {n for n, _, _ in _spans("phases-pipe")}
    # and retires behind the dispatch it has just handed over: the engine's
    # half of a tick's retirements is a phase of the tick's own, after its emit
    assert b.stats()["phase_batcher.retire_count"] >= 1
    assert b.stats()["retirements_behind_dispatch"] == 2
    # and reads first tokens behind the dispatch it issued after their
    # prefill: a phase of the tick's own, after every admit and dispatch of it
    pipe = [s for s in _spans("phases-pipe") if s[0].startswith("batcher.")]
    firsts = [s for s in pipe if s[0] == "batcher.first_token"]
    assert len(firsts) == 2 == b.stats()["admissions"]
    assert not any(_inside(s, [o for o in pipe if o[0] != "batcher.first_token"])
                   for s in firsts)
    for f in firsts:
        before = [s[0] for s in pipe if s[2] <= f[1]]
        last = len(before) - 1 - before[::-1].index("batcher.reap")
        assert "batcher.dispatch" in before[last:]
    assert b._last_dispatch_s > 0
    # a dispatch behind a prompt chunk still on the device has that chunk's
    # time in front of its own: a running median of its own
    assert {4, ("behind_chunk", 4)} <= set(b._dispatch_hist)
    eng.close()

    # a page pool too small for three streams: one is evicted
    eng = _engine(params, "phases-evict", num_slots=3, max_context=256,
                  paged_pool_rows=96, page_size=32)
    b = ContinuousBatcher(eng)
    outs = _run(b, [[s + 1, 2, 3] for s in range(3)], max_tokens=80)
    b.shutdown()
    assert b.pool_evictions >= 1 and b.stats()["phase_batcher.evict_count"] >= 1
    assert any(n == "batcher.evict" for n, _, _ in _spans("phases-evict"))
    eng.close()


def test_with_the_recorder_disabled_the_counters_run_and_the_ring_stays_empty(params, monkeypatch):
    monkeypatch.setattr(flightrec.RECORDER, "enabled", False)
    eng = _engine(params, "phases-off")
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2)
    assert [len(o) for o in _run(b, [[3, 5, 7]])] == [12]
    b.shutdown()
    eng.close()  # the dispatch worker has ended its last one
    stats = b.stats()
    assert stats["phase_batcher.dispatch_count"] >= 3
    assert stats["phase_batcher.dispatch_seconds"] > 0
    assert stats["phase_engine.enqueue_count"] == stats["phase_batcher.dispatch_count"]
    assert flightrec.RECORDER.phases("phases-off") == []


@pytest.mark.parametrize("loop", ["sync", "pipelined"])
def test_an_injected_dispatch_delay_leaves_one_stall_event_naming_the_dispatch(params, loop):
    # the synchronous loop waits for the device under batcher.dispatch, so
    # the dispatch reads long against its median; the pipelined tick only
    # hands its dispatch over there, so its host time reads long against
    # the dispatch it consumed
    model = f"phases-stall-{loop}"
    eng = _engine(params, model, num_slots=1)
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2,
                          pipeline=loop == "pipelined")
    try:
        _run(b, [[3, 5, 7]], max_tokens=8)  # every graph compiled
        before = b.stats()
        # the 14th dispatch from here sleeps 250 ms: hundreds of times a
        # dispatch of this model, and the median of its size is known by then
        faults.activate("dispatch.delay=nth:14,delay_ms=250")
        assert [len(o) for o in _run(b, [[3, 5, 7, 9]], max_tokens=100)] == [100]
    finally:
        faults.deactivate()
        b.shutdown()
    # (a tick of this tiny model that a loaded host holds for longer than
    # one of its millisecond dispatches is a stall too: not the one meant)
    named = [f for f in _stalls(model)
             if f["phase"] == "batcher.dispatch" and f["ms"] >= 250]
    assert len(named) == 1
    assert named[0]["tick_ms"] >= named[0]["ms"]
    assert named[0]["live"] == 1 and named[0]["waiting"] == 0
    after = b.stats()
    assert after["loop_stalls"] - before["loop_stalls"] >= 1
    assert after["loop_stall_seconds"] - before["loop_stall_seconds"] >= 0.24
    eng.close()


def test_a_request_held_back_leaves_one_no_progress_snapshot_with_its_state(params, monkeypatch):
    monkeypatch.setattr(batching, "NO_PROGRESS_MIN_SECS", 0.2)
    monkeypatch.setattr(batching, "NO_PROGRESS_CHECK_SECS", 0.02)
    eng = _engine(params, "phases-stuck", num_slots=1)
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2)

    def snaps():
        return [s for s in flightrec.RECORDER.snapshots()
                if s["model"] == "phases-stuck" and s["cause"] == "no_progress"]

    try:
        _run(b, [[3, 5, 7]], max_tokens=8)
        assert b.stats()["oldest_no_progress_s"] == 0 and not snaps()
        b._free_slots = lambda: []  # no slot is ever found free: it waits
        h = b.submit(Request(prompt_ids=[3, 5, 7], max_tokens=8, temperature=0.0,
                             request_id="held-back"))
        deadline = time.monotonic() + 10
        while not snaps() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert b.stats()["oldest_no_progress_s"] >= 0.2
        time.sleep(0.3)  # more checks pass: the request is noted once
        del b._free_slots
        assert len(h.tokens()) == 8
        assert b.stats()["oldest_no_progress_s"] == 0
    finally:
        b.shutdown()
    (snap,) = snaps()
    state = snap["detail"]
    assert state["request_id"] == "held-back" and state["where"] == "_waiting"
    assert state["slot"] == -1 and state["produced"] == 0 and state["slot_length"] == 0
    assert state["engine_active"] is False
    assert state["cancelled"] is False and state["done"] is False
    assert state["no_progress_s"] > 0.2 and state["waiting"] == 1 and state["live"] == 0
    assert 0 < len(state["phases"]) <= 64
    assert {p["name"] for p in state["phases"]} <= set(flightrec.PHASES)
    assert state["phases"][-1]["name"] in ("batcher.idle", "batcher.reap")
    eng.close()


def test_the_queue_event_says_whether_a_slot_stood_free(params):
    eng = _engine(params, "phases-queue", num_slots=1)
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2)
    first = b.submit(Request(prompt_ids=[3, 5, 7], max_tokens=40, temperature=0.0,
                             request_id="q-first"))
    second = b.submit(Request(prompt_ids=[3, 5, 9], max_tokens=4, temperature=0.0,
                              request_id="q-second"))
    first.tokens(), second.tokens()
    b.shutdown()
    queue = {tl.request_id: f for tl in flightrec.RECORDER.recent("phases-queue")
             for _, kind, f in tl.events if kind == "queue"}
    assert queue["q-first"]["slot_free"] is True
    assert queue["q-second"]["slot_free"] is False
    assert queue["q-second"]["wait_ms"] > queue["q-first"]["wait_ms"]
    eng.close()


def test_the_pool_adds_up_the_phases_and_takes_the_largest_no_progress(params):
    engines = [_engine(params, "phases-pool"), _engine(params, "phases-pool")]
    pool = ReplicaPool("phases-pool", engines,
                       lambda e: ContinuousBatcher(e, chunk_steps=4, admit_chunk_steps=2))
    try:
        for r in pool.replicas:
            assert len(r.batcher.submit(Request(prompt_ids=[3, 5, 7], max_tokens=8,
                                                temperature=0.0)).tokens()) == 8
        for r, held in zip(pool.replicas, (1.0, 3.0)):
            r.batcher._free_slots = lambda: []
            r.batcher.submit(Request(prompt_ids=[3, 5], max_tokens=2)) \
                ._live.progress_at = time.monotonic() - held
        stats = pool.stats()
        per_replica = [r.batcher.stats() for r in pool.replicas]
    finally:
        pool.shutdown()
        for e in engines:
            e.close()
    assert stats["phase_batcher.dispatch_count"] == sum(
        s["phase_batcher.dispatch_count"] for s in per_replica) >= 4
    assert stats["phase_engine.readback_seconds"] == pytest.approx(
        sum(s["phase_engine.readback_seconds"] for s in per_replica), rel=0.2)
    assert 3.0 <= stats["oldest_no_progress_s"] < 4.0  # the largest, not 4 = the sum
    assert stats["loop_stalls"] == sum(s["loop_stalls"] for s in per_replica)
    assert all(isinstance(v, (int, float)) for v in stats.values())  # flat scalars


def test_the_scheduler_is_one_more_track_of_the_chrome_trace(params):
    eng = _engine(params, "phases-trace")
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2)
    _run(b, [[3, 5, 7]])
    b.shutdown()
    rec = flightrec.RECORDER
    trace = flightrec.chrome_trace(rec.recent("phases-trace"), rec.model_events("phases-trace"),
                                   rec.phases("phases-trace"))
    track = [e for e in trace["traceEvents"] if e["tid"] == flightrec._SCHEDULER_TID]
    assert {"ph": "M", "pid": track[0]["pid"], "tid": flightrec._SCHEDULER_TID,
            "name": "thread_name", "args": {"name": "scheduler"}} in track
    xs = [e for e in track if e["ph"] == "X"]
    assert {"batcher.dispatch", "batcher.emit", "engine.enqueue"} <= {e["name"] for e in xs}
    # on the request tracks' axis: the dispatches lie inside the request's envelope
    (env,) = [e for e in trace["traceEvents"] if e["name"] == "request[retired]"]
    inside = [e for e in xs if e["name"] == "batcher.dispatch"
              and env["ts"] <= e["ts"] and e["ts"] + e["dur"] <= env["ts"] + env["dur"] + 1e3]
    assert len(inside) >= 3
    with pytest.raises(KeyError):  # the list of phases is closed
        with eng.phases.phase("batcher.other"):
            pass
    eng.close()


def test_phases_closed_from_more_threads_than_cores_lose_no_count():
    # the scheduler thread, the pipelined dispatch worker and direct engine
    # callers close phases of one Phases: a lost update would show here
    ph = flightrec.Phases("phases-threads")
    n_threads, n_each = 4 * (os.cpu_count() or 4), 1500

    def work():
        for _ in range(n_each):
            ph.end(ph.begin("engine.lock_wait"))

    threads = [threading.Thread(target=work, daemon=True) for _ in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert ph.counts["engine.lock_wait"] == n_threads * n_each
    assert ph.stats()["phase_engine.lock_wait_seconds"] > 0
    assert len(flightrec.RECORDER.phases("phases-threads")) == min(
        n_threads * n_each, flightrec.PHASE_RING)
