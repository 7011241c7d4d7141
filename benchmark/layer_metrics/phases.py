"""Readers of the metrics that read the scheduler loop's own phases.

The program (aios_tpu/obs/flightrec.py `Phases`) closes each phase of the
batcher's tick and of the engine's dispatch bodies into three places; the
readers here take each from where the harness already keeps it:

- the host plane of the profiler's trace (`ctx.planes`, every plane whose
  name starts with `/host:`): one event per phase, named `batcher.<x>` or
  `engine.<x>`, on the clock of the device's programs;
- the program's counters (`ctx.before` / `ctx.after` / `ctx.samples`):
  `loop_stall_seconds`, `oldest_no_progress_s`;
- the flight recorder's timelines (`ctx.timelines`): the `queue` event's
  `slot_free`.

A program without phases (the parent of the PR that added them) has none of
the three, and every reader then returns None.

`report(planes)` prints what PERF.md quotes from a traced run: the clock
check, the two parts of the device's idle share, the longest idle gaps by
phase. `tests/benchmark/make_phase_extract.py` calls it.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from benchmark.harness import metrics, xplane

Interval = Tuple[int, int]  # start_ns, end_ns

IDLE = "batcher.idle"
# the deeper phase names a gap: an engine phase lies inside a batcher phase
_DEPTH = {"engine.compile": 3, "engine.lock_wait": 2, "engine.enqueue": 2,
          "engine.readback": 2, "engine.prefill": 2}
CLOCK_TOLERANCE_NS = 1_000_000


# -- intervals ----------------------------------------------------------------


def merge(intervals) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """What of the merged intervals `a` no interval of the merged `b` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if s < hi and e > lo]


# -- the two planes -----------------------------------------------------------


def phase_spans(planes, offset_ns: int = 0) -> Dict[str, List[Interval]]:
    """Phase name -> its spans by start, from the host planes. `offset_ns`
    is added to every instant (host clock -> device clock)."""
    out: Dict[str, List[Interval]] = {}
    for plane_name, lines in planes.items():
        if not plane_name.startswith("/host:"):
            continue
        for events in lines.values():
            for name, s, d in events:
                if name.startswith(("batcher.", "engine.")):
                    out.setdefault(name, []).append((s + offset_ns, s + d + offset_ns))
    for spans in out.values():
        spans.sort()
    return out


def _device(planes):
    dev = xplane.device_planes(planes)
    return dev[sorted(dev)[0]] if dev else None


def device_idle(planes) -> Tuple[List[Interval], int, int]:
    """(idle intervals, window start, window end) of the first device, as
    `xplane.busy_and_window_seconds` takes them: the window runs from the
    first program's start to the last one's end and operations are clipped
    to it."""
    plane = _device(planes)
    if plane is None:
        return [], 0, 0
    w0, w1 = xplane.window_ns(plane)
    busy = merge(clip(((s, s + d) for _, s, d in plane.get(xplane.OPS_LINE, [])), w0, w1))
    return subtract([(w0, w1)], busy), w0, w1


def _decode_programs(planes) -> List[Interval]:
    return [(s, s + d) for name, s, d in xplane.modules(planes)
            if xplane.module_kind(name) == "decode"]


def clock_check(planes) -> Optional[dict]:
    """Do the host's spans and the device's programs lie on one clock? Each
    decode program should run inside [start of its `engine.enqueue`, end of
    the `engine.readback` that follows]. Programs are paired with the last
    enqueue that starts before they end (no offset assumed beyond that); the
    share inside within 1 ms is reported, and beside it the offset that
    pairing by order alone would give (`offset_by_order_ms`)."""
    spans = phase_spans(planes)
    enq, rb = spans.get("engine.enqueue", []), spans.get("engine.readback", [])
    progs = _decode_programs(planes)
    if not enq or not rb or not progs:
        return None
    enq_starts = [s for s, _ in enq]
    rb_ends = sorted(e for _, e in rb)
    inside = paired = 0
    lead, lag = [], []
    for p0, p1 in progs:
        i = bisect.bisect_right(enq_starts, p1) - 1
        if i < 0:
            continue  # its enqueue lies before the traced window
        e0, e1 = enq[i]
        j = bisect.bisect_left(rb_ends, e1)
        if j == len(rb_ends):
            continue  # its readback ends after the traced window
        paired += 1
        lead.append(p0 - e0)
        lag.append(rb_ends[j] - p1)
        if p0 >= e0 - CLOCK_TOLERANCE_NS and p1 <= rb_ends[j] + CLOCK_TOLERANCE_NS:
            inside += 1
    if not paired:
        return None
    return {
        "programs": len(progs), "paired": paired,
        "inside_pct": 100.0 * inside / paired,
        "start_after_enqueue_start_ms": _quartiles(lead),
        "readback_end_after_program_end_ms": _quartiles(lag),
        "offset_by_order_ms": _offset_by_order_ns(progs, rb_ends) / 1e6,
    }


def _offset_by_order_ns(progs: List[Interval], rb_ends: List[int]) -> float:
    """Median of (readback end - program end) with the k-th program paired
    to the (k + shift)-th readback, no clock assumed: a readback ends a
    near-constant time after its program, so of the few shifts the window's
    edges allow the right one is where the differences spread least."""
    best = None
    for shift in range(-2, 3):
        diffs = [rb_ends[k + shift] - p1 for k, (_, p1) in enumerate(progs)
                 if 0 <= k + shift < len(rb_ends)]
        if len(diffs) < 2:
            continue
        spread = metrics.percentile(diffs, 75) - metrics.percentile(diffs, 25)
        if best is None or spread < best[0]:
            best = (spread, metrics.percentile(diffs, 50))
    return best[1] if best else 0.0


def _quartiles(ns) -> List[float]:
    return [round(metrics.percentile(ns, q) / 1e6, 4) for q in (0, 50, 100)]


def clock_offset_ns(planes) -> int:
    """0 where the clocks agree (99 % of the decode programs inside their
    spans within 1 ms); else the offset pairing by order gives."""
    check = clock_check(planes)
    if check is None or check["inside_pct"] >= 99.0:
        return 0
    # the readback's own lag behind its program (a millisecond or two)
    # goes with it: the smaller error where the clocks do not agree
    return -int(check["offset_by_order_ms"] * 1e6)


def _split(planes, cache: Optional[dict] = None) -> Optional[dict]:
    """The device's idle time in the traced window, split by what the
    scheduler was in: `batcher.idle` (nothing to do), any other phase, or
    no named phase at all. Nanoseconds; kept in a Context's `cache`.

    The profiler keeps no annotation that was open when it started or when
    it stopped, so the phase at either edge of the trace is missing from the
    host plane. `seen` is the idle-with-work time between the first span's
    start and the last span's end, where every phase is on record; `unnamed`
    is the part of it no span covers."""
    if planes is None:
        return None
    if cache is not None and "phase_split" in cache:
        return cache["phase_split"]
    spans = phase_spans(planes, clock_offset_ns(planes))
    idle, w0, w1 = device_idle(planes)
    out = None
    if spans and w1 > w0:
        waiting = merge(spans.get(IDLE, []))
        with_work = subtract(idle, waiting)
        named = merge(iv for name, ivs in spans.items() if name != IDLE for iv in ivs)
        seen = clip(with_work, min(s for ivs in spans.values() for s, _ in ivs),
                    max(e for ivs in spans.values() for _, e in ivs))
        out = {"window": w1 - w0, "idle": total(idle),
               "idle_with_work": total(with_work), "seen": total(seen),
               "unnamed": total(subtract(seen, named)),
               "gaps": idle, "spans": spans}
    if cache is not None:
        cache["phase_split"] = out
    return out


# -- readers: the device trace and the host plane ------------------------------


def device_idle_with_work_pct(ctx):
    split = _split(ctx.planes, ctx.cache)
    return 100.0 * split["idle_with_work"] / split["window"] if split else None


def batcher_gap_unnamed_pct(ctx):
    split = _split(ctx.planes, ctx.cache)
    if not split:
        return None
    return 100.0 * split["unnamed"] / split["seen"] if split["seen"] else 0.0


def _median_ms(spans: List[Interval]) -> Optional[float]:
    return metrics.percentile([(e - s) / 1e6 for s, e in spans], 50) if spans else None


def batcher_emit_ms(ctx):
    if ctx.planes is None:
        return None
    return _median_ms(phase_spans(ctx.planes).get("batcher.emit", []))


def engine_enqueue_ms(ctx):
    if ctx.planes is None:
        return None
    return _median_ms(phase_spans(ctx.planes).get("engine.enqueue", []))


def admission_ms_by_tick(spans: Dict[str, List[Interval]]) -> List[float]:
    """`batcher.prefill` + `batcher.admit` of each tick in which either ran.
    `batcher.reap` opens every tick, so its starts are the ticks' edges."""
    edges = [s for s, _ in spans.get("batcher.reap", [])]
    by_tick: Dict[int, float] = {}
    for name in ("batcher.prefill", "batcher.admit"):
        for s, e in spans.get(name, []):
            tick = bisect.bisect_right(edges, s)
            by_tick[tick] = by_tick.get(tick, 0.0) + (e - s) / 1e6
    return list(by_tick.values())


def batcher_admit_ms(ctx):
    if ctx.planes is None:
        return None
    v = admission_ms_by_tick(phase_spans(ctx.planes))
    return metrics.percentile(v, 50) if v else None


# -- readers: the flight recorder and the counters -----------------------------


def serving_free_slot_wait_ms(ctx):
    tls = ctx.timeline_of()
    v = []
    for r in ctx.due():
        tl = tls.get(r.task_id)
        for _, kind, f in (list(tl.events) if tl is not None else ()):
            if kind == "queue" and f.get("slot_free"):
                v.append(float(f["wait_ms"]))
    return sum(v) / len(v) if v else None


def batcher_stall_pct(ctx):
    stalled = ctx.delta("loop_stall_seconds")
    return 100.0 * stalled / (ctx.w1 - ctx.w0) if stalled is not None else None


def batcher_no_progress_max_s(ctx):
    v = [s["oldest_no_progress_s"] for _, s in ctx.samples if "oldest_no_progress_s" in s]
    return max(v) if v else None


# -- what a traced run prints for PERF.md ---------------------------------------


def name_gap(spans: Dict[str, List[Interval]], g0: int, g1: int) -> Tuple[str, float]:
    """The phase that covers most of the idle gap [g0, g1), the deepest one
    where phases nest, and the share of the gap it covers."""
    cuts = {g0, g1}
    inside = []
    for name, ivs in spans.items():
        for s, e in clip(ivs, g0, g1):
            inside.append((s, e, _DEPTH.get(name, 1), name))
            cuts.update((s, e))
    edges = sorted(cuts)
    by_name: Dict[str, int] = {}
    for a, b in zip(edges, edges[1:]):
        over = [(depth, name) for s, e, depth, name in inside if s <= a and e >= b]
        name = max(over)[1] if over else "unnamed"
        by_name[name] = by_name.get(name, 0) + b - a
    name = max(by_name, key=by_name.get)
    return name, by_name[name] / (g1 - g0)


def report(planes, say=print, gaps: int = 5) -> None:
    check = clock_check(planes)
    split = _split(planes)
    if check is None or split is None:
        say("phases: the trace holds no phase of the scheduler loop")
        return
    say(f"clock check: {check['inside_pct']:.2f} % of {check['paired']} decode programs "
        f"(of {check['programs']}) inside [engine.enqueue start, engine.readback end] within "
        f"1 ms; program start - enqueue start ms min / p50 / max "
        f"{check['start_after_enqueue_start_ms']}; readback end - program end ms "
        f"{check['readback_end_after_program_end_ms']}; offset by order "
        f"{check['offset_by_order_ms']:.4f} ms; offset applied "
        f"{clock_offset_ns(planes) / 1e6:.4f} ms")
    w = split["window"]
    say(f"device idle {100.0 * split['idle'] / w:.3f} % of {w / 1e9:.3f} s = with work "
        f"{100.0 * split['idle_with_work'] / w:.3f} % + scheduler in batcher.idle "
        f"{100.0 * (split['idle'] - split['idle_with_work']) / w:.3f} %; of the idle time "
        f"with work between the first and the last span on record "
        f"({split['seen'] / 1e6:.3f} of {split['idle_with_work'] / 1e6:.3f} ms) no phase covers "
        f"{100.0 * split['unnamed'] / max(split['seen'], 1):.3f} %")
    longest = sorted(split["gaps"], key=lambda g: g[0] - g[1])[:gaps]
    say("longest idle gaps by phase: " + "; ".join(
        "%s %.1f ms (%.0f %%)" % (name, (g1 - g0) / 1e6, 100.0 * share)
        for (g0, g1), (name, share) in ((g, name_gap(split["spans"], *g)) for g in longest)))
    spans = split["spans"]
    say("phases in the trace, count / median ms / total s: " + "; ".join(
        "%s %d / %.3f / %.3f" % (name, len(ivs), _median_ms(ivs), total(ivs) / 1e9)
        for name, ivs in sorted(spans.items())))
