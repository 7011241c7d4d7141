"""The readers of the scheduler-phase metrics (benchmark/layer_metrics/
phases.py): on a trace small enough to compute by hand, on a recorded extract
of a chip run that holds the host plane beside the device's
(`mixtral-d6-longprompt`, TPU v5 lite, PR 24; made by make_phase_extract.py),
without a trace, and on a program that has no phases."""

import gzip
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import make_phase_extract  # noqa: E402
from benchmark.harness import manifest, xplane  # noqa: E402

phases = make_phase_extract.load_phases()
MS = 1_000_000  # the hand-made trace is written in milliseconds


def _ms(events):
    return [(name, s * MS, d * MS) for name, s, d in events]


@pytest.fixture()
def by_hand():
    """Two decode programs and a prefill between them; the device is idle in
    [200, 260), [300, 400) and [480, 500) of its window [100, 500)."""
    return {
        "/device:TPU:0": {
            xplane.MODULES_LINE: _ms([("jit__lambda(1)", 100, 100),
                                      ("jit__prefill_chunk_impl(2)", 260, 40),
                                      ("jit__lambda(1)", 400, 100)]),
            xplane.OPS_LINE: _ms([("%a", 100, 100), ("%b", 260, 40), ("%c", 400, 80)]),
        },
        "/host:CPU": {"python3": _ms([
            ("batcher.dispatch", 88, 118), ("engine.enqueue", 90, 20),
            ("engine.readback", 110, 95), ("batcher.emit", 206, 14),
            ("batcher.reap", 222, 2), ("batcher.admit", 224, 81),
            ("engine.prefill", 230, 72), ("batcher.idle", 310, 70),
            ("batcher.reap", 382, 2), ("batcher.dispatch", 390, 120),
            ("engine.enqueue", 392, 10), ("engine.readback", 402, 106),
            ("some.other.annotation", 0, 600),
        ])},
    }


def _ctx(planes, **kw):
    base = dict(planes=planes, cache={}, before={}, after={}, samples=[], w0=0.0, w1=40.0,
                timelines=[], due=lambda: [], timeline_of=lambda: {})
    ctx = SimpleNamespace(**{**base, **kw})
    ctx.delta = lambda key: (ctx.after[key] - ctx.before[key]
                             if key in ctx.before and key in ctx.after else None)
    return ctx


def test_the_trace_readers_give_the_values_computed_by_hand(by_hand):
    ctx = _ctx(by_hand)
    # idle 60 + 100 + 20 = 180 of 400; the 70 inside batcher.idle are no work
    assert phases.device_idle_with_work_pct(ctx) == pytest.approx(100 * 110 / 400)
    # of those 110, no phase covers [220, 222), [305, 310), [380, 382), [384, 390)
    assert phases.batcher_gap_unnamed_pct(ctx) == pytest.approx(100 * 15 / 110)
    assert phases.batcher_emit_ms(ctx) == pytest.approx(14.0)
    assert phases.batcher_admit_ms(ctx) == pytest.approx(81.0)  # the one tick that admitted
    assert phases.engine_enqueue_ms(ctx) == pytest.approx(15.0)  # of 20 and 10
    split = phases._split(by_hand)
    assert (split["window"], split["idle"]) == (400 * MS, 180 * MS)
    idle_in_idle_phase = split["idle"] - split["idle_with_work"]
    assert idle_in_idle_phase == 70 * MS  # the two parts add up to device.idle_pct
    # the profiler kept no span that was open when it started: with the first
    # dispatch (and what nests in it) and the emit gone, the idle time before
    # the first span left, [200, 222), is not held against the list of phases
    cut = dict(by_hand)
    cut["/host:CPU"] = {"python3": by_hand["/host:CPU"]["python3"][4:]}
    assert phases.device_idle_with_work_pct(_ctx(cut)) == pytest.approx(100 * 110 / 400)
    assert phases.batcher_gap_unnamed_pct(_ctx(cut)) == pytest.approx(100 * 13 / 88)


def test_the_clock_check_pairs_each_decode_program_with_its_spans(by_hand):
    check = phases.clock_check(by_hand)
    assert (check["programs"], check["paired"], check["inside_pct"]) == (2, 2, 100.0)
    assert check["start_after_enqueue_start_ms"] == [8.0, 9.0, 10.0]
    assert check["readback_end_after_program_end_ms"] == [5.0, 6.5, 8.0]
    assert check["offset_by_order_ms"] == pytest.approx(6.5)
    assert phases.clock_offset_ns(by_hand) == 0
    # the host's clock 50 ms ahead of the device's: no program lies inside its
    # spans, pairing by order finds the offset, and with it applied the split
    # is what it was
    ahead = dict(by_hand)
    ahead["/host:CPU"] = {"python3": [(n, s + 50 * MS, d) for n, s, d in
                                      by_hand["/host:CPU"]["python3"]]}
    assert phases.clock_check(ahead)["inside_pct"] == 0.0
    assert phases.clock_offset_ns(ahead) == -(50 * MS + 6.5 * MS)
    moved = phases._split(ahead)
    assert moved["idle_with_work"] == pytest.approx(110 * MS, abs=7 * MS)


def test_an_idle_gap_is_named_by_the_deepest_phase_that_covers_most_of_it(by_hand):
    spans = phases.phase_spans(by_hand)
    assert "some.other.annotation" not in spans
    assert phases.name_gap(spans, 300 * MS, 400 * MS) == ("batcher.idle", pytest.approx(0.7))
    # [200, 260): readback 5, emit 14, reap 2, admit alone 6, engine.prefill 30
    assert phases.name_gap(spans, 200 * MS, 260 * MS) == ("engine.prefill", pytest.approx(0.5))
    said = []
    phases.report(by_hand, say=said.append)
    assert said[0].startswith("clock check: 100.00 % of 2 decode programs")
    assert "= with work 27.500 % + scheduler in batcher.idle 17.500 %" in said[1]
    assert said[2].startswith("longest idle gaps by phase: batcher.idle 100.0 ms (70 %); "
                              "engine.prefill 60.0 ms (50 %); engine.readback 20.0 ms (100 %)")


def test_interval_arithmetic():
    assert phases.merge([(5, 9), (1, 3), (2, 4), (9, 9), (8, 12)]) == [(1, 4), (5, 12)]
    assert phases.subtract([(0, 10), (20, 30)], [(2, 3), (5, 22), (29, 40)]) == [
        (0, 2), (3, 5), (22, 29)]
    assert phases.subtract([(0, 10)], []) == [(0, 10)]
    assert phases.total([(0, 2), (3, 5)]) == 4
    assert phases.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "data", "trace_phases_mixtral_d6_longprompt.json.gz")
    with gzip.open(path, "rt") as fh:
        return make_phase_extract.from_extract(json.load(fh))


def _brute_split(planes):
    """The split again, the slow way: every boundary cuts the window into
    pieces, each piece is idle or not and lies in a phase or not."""
    dev = planes["/device:TPU:0"]
    w0, w1 = xplane.window_ns(dev)
    host = [e for lines in planes.values() for line, evs in lines.items()
            if line == "phases" for e in evs]
    cuts = sorted({w0, w1} | {t for _, s, d in dev[xplane.OPS_LINE] + host
                              for t in (s, s + d) if w0 < t < w1})  # span edges cut too
    first, last = min(s for _, s, _ in host), max(s + d for _, s, d in host)
    idle = with_work = seen = unnamed = 0
    for a, b in zip(cuts, cuts[1:]):
        if any(s <= a and b <= s + d for _, s, d in dev[xplane.OPS_LINE]):
            continue
        over = {n for n, s, d in host if s <= a and b <= s + d}
        idle += b - a
        if "batcher.idle" not in over:
            with_work += b - a
            if first <= a and b <= last:
                seen += b - a
                unnamed += (b - a) if not over else 0
    return w1 - w0, idle, with_work, seen, unnamed


def test_on_the_recorded_extract_the_readers_agree_with_the_slow_way(recorded):
    ctx = _ctx(recorded)
    window, idle, with_work, seen, unnamed = _brute_split(recorded)
    busy_s, window_s = xplane.busy_and_window_seconds(recorded)
    assert window == pytest.approx(window_s * 1e9) and idle == pytest.approx((window_s - busy_s) * 1e9)
    assert phases.device_idle_with_work_pct(ctx) == pytest.approx(100.0 * with_work / window)
    assert phases.batcher_gap_unnamed_pct(ctx) == pytest.approx(100.0 * unnamed / seen)
    split = phases._split(recorded)
    assert split["idle"] == idle
    check = phases.clock_check(recorded)
    assert check["inside_pct"] == 100.0 and phases.clock_offset_ns(recorded) == 0
    assert 0 < check["readback_end_after_program_end_ms"][1] < 5
    assert RECORDED == {
        "programs": check["programs"],
        "idle_with_work_pct": round(phases.device_idle_with_work_pct(ctx), 3),
        "gap_unnamed_pct": round(phases.batcher_gap_unnamed_pct(ctx), 3),
        "emit_ms": round(phases.batcher_emit_ms(ctx), 3),
        "admit_ms": round(phases.batcher_admit_ms(ctx), 3),
        "enqueue_ms": round(phases.engine_enqueue_ms(ctx), 3),
    }


# read off the extract once: make_phase_extract.py's report of it (6 emit
# spans, 2 prefill spans in 2 ticks, 5 enqueue spans) and the slow way above
RECORDED = {"programs": 6, "idle_with_work_pct": 4.231, "gap_unnamed_pct": 0.471,
            "emit_ms": 3.362, "admit_ms": 36.141, "enqueue_ms": 1.181}


def test_without_a_trace_or_without_phases_every_reader_finds_nothing(by_hand):
    trace_readers = (phases.device_idle_with_work_pct, phases.batcher_gap_unnamed_pct,
                     phases.batcher_emit_ms, phases.batcher_admit_ms, phases.engine_enqueue_ms)
    for reader in trace_readers:
        assert reader(_ctx(None)) is None
    # the parent's program: a device plane, and a host plane with no phase in it
    bare = {"/device:TPU:0": by_hand["/device:TPU:0"],
            "/host:CPU": {"python3": _ms([("some.other.annotation", 0, 600)])}}
    for reader in trace_readers:
        assert reader(_ctx(bare)) is None
    assert phases.clock_check(bare) is None
    said = []
    phases.report(bare, say=said.append)
    assert said == ["phases: the trace holds no phase of the scheduler loop"]
    # and counters and timelines of a program that has neither
    old = _ctx(None, before={"completed": 1}, after={"completed": 9},
               samples=[(0.0, {"completed": 3})])
    assert phases.batcher_stall_pct(old) is None
    assert phases.batcher_no_progress_max_s(old) is None
    assert phases.serving_free_slot_wait_ms(old) is None


def test_the_counter_and_timeline_readers():
    ctx = _ctx(None, before={"loop_stall_seconds": 0.5}, after={"loop_stall_seconds": 2.5},
               samples=[(0.0, {"oldest_no_progress_s": 0.2}), (0.2, {"oldest_no_progress_s": 1.7}),
                        (0.4, {"oldest_no_progress_s": 0.0})])
    assert phases.batcher_stall_pct(ctx) == pytest.approx(5.0)  # 2 s of a 40 s window
    assert phases.batcher_no_progress_max_s(ctx) == 1.7

    def timeline(events):
        return SimpleNamespace(events=events)

    tls = {
        "a": timeline([(0.0, "admit", {}), (0.1, "queue", {"wait_ms": 30.0, "slot_free": True})]),
        "b": timeline([(0.1, "queue", {"wait_ms": 900.0, "slot_free": False})]),
        "c": timeline([(0.1, "queue", {"wait_ms": 50.0, "slot_free": True})]),
        "d": timeline([(0.1, "queue", {"wait_ms": 70.0})]),  # the parent's event
    }
    due = [SimpleNamespace(task_id=k) for k in ("a", "b", "c", "d", "not-recorded")]
    ctx = _ctx(None, due=lambda: due, timeline_of=lambda: tls)
    assert phases.serving_free_slot_wait_ms(ctx) == pytest.approx(40.0)


def test_the_two_counter_metrics_are_in_the_cpu_rehearsal_s_line(tmp_path):
    import subprocess

    import rehearsal_root

    root = rehearsal_root.build(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--root", root,
         "--workload", "tiny-moe-arrivals", "--seed", "3000000007", "--seconds", "3",
         "--trace", "1", "--rehearsal-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    assert got["batcher.stall_pct"]["unit"] == "%" and got["batcher.stall_pct"]["value"] >= 0
    assert 0 <= got["batcher.no_progress_max_s"]["value"] < 3
    # what reads the trace or a span's time is no count: a CPU run leaves it out
    assert not {"device.idle_with_work_pct", "batcher.gap_unnamed_pct", "batcher.emit_ms",
                "batcher.admit_ms", "engine.enqueue_ms", "serving.free_slot_wait_ms"} & set(got)


def test_the_committed_benchmark_lists_the_eight_metrics_beside_their_reader():
    man = manifest.Manifest(REPO)
    manifest.check(man)
    files = {f["name"]: f for f in man.layer_metric_files()
             if f["reader"].startswith("phases.py:")}
    assert len(files) == 8
    listed = {m["name"]: m for m in man.doc["per_layer"]}
    cells = [w["name"] for w in man.doc["workloads"]]
    for name, f in files.items():
        assert callable(getattr(phases, f["reader"].split(":")[1]))
        want = cells if f["kinds"] == ["all"] else [c for c in cells if c.endswith("longprompt")]
        assert listed[name]["workloads"] == want
    assert files["serving.free_slot_wait_ms"]["kinds"] == ["open_arrivals"]
    for cell in cells:
        got = {f["name"] for f in man.layer_metrics_of(cell)} & set(files)
        assert len(got) == (8 if cell.endswith("longprompt") else 7)
