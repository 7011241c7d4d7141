"""One small reader per per-layer metric. A reader takes the run's Context
and returns a number, or None where it finds nothing to read (the harness
then leaves the metric out of the line). A metric file under
`benchmark/layer_metrics/` names its reader: `"reader": "fn"` is a function
here, `"reader": "file.py:fn"` one in a file beside the metric file, so a
later PR adds a metric without editing this module. What a reader needs of
the model (its sizes, its roofline counts, its trace markers) it takes from
the Context: `ctx.arch` is the architecture's module, `ctx.dims` its sizes.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import metrics, roofline, xplane
from .loadgen import Record


@dataclass
class Context:
    arch: object  # the architecture's module (benchmark/archs/<arch>.py)
    dims: object  # its `dims_of(config)`
    mix: dict
    slots: int
    records: Sequence[Record]
    w0: float
    w1: float
    before: Dict[str, float]  # the program's counters at window open
    after: Dict[str, float]  # and at window close
    samples: List[Tuple[float, Dict[str, float]]]  # and sampled in between
    timelines: Sequence[object]  # the flight recorder's finished records
    peaks: Optional[Dict[str, float]] = None  # None off the chip
    peak_bytes: int = 0
    planes: Optional[dict] = None  # the reduced trace, in a traced run
    trace_w0: float = 0.0  # host instants of the traced window
    trace_w1: float = 0.0
    cache: dict = field(default_factory=dict)

    def delta(self, key: str) -> Optional[float]:
        if key not in self.before or key not in self.after:
            return None
        return self.after[key] - self.before[key]

    def due(self) -> List[Record]:
        return [r for r in metrics.in_window(self.records, self.w0, self.w1) if r.ok]

    def timeline_of(self) -> Dict[str, object]:
        if "tl" not in self.cache:
            self.cache["tl"] = {t.request_id: t for t in self.timelines}
        return self.cache["tl"]


def _ttft(ctx: Context) -> List[float]:
    return [(r.chunks[0] - r.due) * 1e3 for r in ctx.due()]


def loadgen_late_p99_ms(ctx):
    late = metrics.lateness_ms(ctx.records, ctx.w0, ctx.w1)
    return metrics.percentile(late, 99) if late else None


def rpc_ttft_p50_ms(ctx):
    v = _ttft(ctx)
    return metrics.percentile(v, 50) if v else None


def rpc_ttft_p90_ms(ctx):
    v = _ttft(ctx)
    return metrics.percentile(v, 90) if v else None


def rpc_itl_p99_ms(ctx):
    gaps = metrics.gaps_in_window(ctx.records, ctx.w0, ctx.w1)
    return 1e3 * metrics.percentile(gaps, 99) if gaps else None


def rpc_ttft_over_engine_ms(ctx):
    """Client's time to the first chunk less the batcher's own, per request:
    what the gRPC surface adds. Both instants are time.monotonic() of this
    process; the batcher's comes from the flight recorder's record."""
    tls = ctx.timeline_of()
    v = []
    for r in ctx.due():
        tl = tls.get(r.task_id)
        if tl is not None and getattr(tl, "ttft_ms", 0.0):
            v.append((r.chunks[0] - r.sent) * 1e3 - tl.ttft_ms)
    return metrics.percentile(v, 50) if v else None


def serving_queue_wait_ms(ctx):
    tls = ctx.timeline_of()
    v = [tls[r.task_id].queue_wait_ms for r in ctx.due() if r.task_id in tls]
    return sum(v) / len(v) if v else None


def batcher_slot_use_pct(ctx):
    steps = ctx.delta("decode_steps")
    if not steps:
        return None
    firsts = sum(1 for r in ctx.records
                 if r.chunks and ctx.w0 <= r.chunks[0] < ctx.w1)
    decoded = metrics.chunks_in_window(ctx.records, ctx.w0, ctx.w1) - firsts
    return 100.0 * decoded / (ctx.slots * steps)


def batcher_ttft_fast_share_pct(ctx):
    hits, misses = ctx.delta("prefix_hits"), ctx.delta("prefix_misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)


def kv_prefix_hit_pct(ctx):
    reused = ctx.delta("prefix_rows_reused")
    rows = sum(r.turn.prompt_tokens for r in ctx.due())
    if reused is None or not rows:
        return None
    return 100.0 * reused / rows


def kv_pages_peak_pct(ctx):
    v = [100.0 * s["kv_pages_in_use"] / (s["kv_pages_in_use"] + s["kv_pages_free"])
         for _, s in ctx.samples if "kv_pages_in_use" in s]
    return max(v) if v else None


def engine_compiles_in_window(ctx):
    return ctx.delta("xla_compiles")


# -- from the device trace ---------------------------------------------------


def _decode(ctx) -> List[Tuple[float, int]]:
    if ctx.planes is None:
        return []
    if "decode" not in ctx.cache:
        ctx.cache["decode"] = xplane.decode_steps(
            ctx.planes, **ctx.arch.trace_markers(ctx.dims))
    return ctx.cache["decode"]


def _step_seconds(ctx) -> Optional[float]:
    runs = _decode(ctx)
    steps = sum(n for _, n in runs)
    return sum(s for s, _ in runs) / steps if steps else None


def model_decode_step_ms(ctx):
    runs = _decode(ctx)
    if not runs:
        return None
    return metrics.percentile([1e3 * s / n for s, n in runs], 50)


def batcher_host_gap_ms(ctx):
    if ctx.planes is None:
        return None
    gaps = [g * 1e3 for what, g in xplane.module_gaps(ctx.planes)
            if what == "between_decode_dispatches"]
    return metrics.percentile(gaps, 99) if gaps else None


def _load_at(ctx, t: float) -> Tuple[int, int]:
    """(streams decoding, rows in their contexts) at host instant t, from
    what the clients saw."""
    active = rows = 0
    for r in ctx.records:
        if r.chunks and r.chunks[0] <= t < r.chunks[-1]:
            active += 1
            rows += r.turn.prompt_tokens + bisect.bisect_right(r.chunks, t)
    return active, rows


def kernels_decode_roofline_pct(ctx):
    step = _step_seconds(ctx)
    if step is None or ctx.peaks is None:
        return None
    least, n, t = 0.0, 0, ctx.trace_w0
    while t < ctx.trace_w1:
        active, rows = _load_at(ctx, t)
        if active:
            least += roofline.least_seconds(
                ctx.arch.decode_step_ops(ctx.dims, active, rows),
                ctx.arch.decode_step_bytes(ctx.dims, active, rows), ctx.peaks,
            )["seconds"]
            n += 1
        t += 0.05
    return 100.0 * least / n / step if n else None


def _prefills(ctx) -> List[Tuple[int, int]]:
    """(rows before, new rows) of every prefill dispatch recorded inside the
    traced window, from the flight recorder's per-request events."""
    out = []
    for tl in ctx.timelines:
        before = 0
        for t_rel, kind, f in list(tl.events):
            if kind != "prefill":
                continue
            cached = int(f.get("cached_rows", 0)) + int(f.get("restored_rows", 0))
            # a chunk's `tokens` are the rows it consumed; a whole-prompt
            # prefill's are the prompt's, cached rows included
            new = int(f["tokens"]) - (0 if "chunk" in f else cached)
            before += cached
            if new > 0 and ctx.trace_w0 <= tl.t0 + t_rel < ctx.trace_w1:
                out.append((before, new))
            before += max(new, 0)
    return out


def model_prefill_ms_per_ktok(ctx):
    if ctx.planes is None:
        return None
    tokens = sum(n for _, n in _prefills(ctx))
    seconds = xplane.prefill_seconds(ctx.planes)
    return 1e6 * seconds / tokens if tokens and seconds else None


def kernels_prefill_roofline_pct(ctx):
    if ctx.planes is None or ctx.peaks is None:
        return None
    seconds = xplane.prefill_seconds(ctx.planes)
    runs = _prefills(ctx)
    if not runs or not seconds:
        return None
    least = sum(
        roofline.least_seconds(
            ctx.arch.prefill_ops(ctx.dims, [b + n], [b]),
            ctx.arch.prefill_bytes(ctx.dims, n), ctx.peaks,
        )["seconds"]
        for b, n in runs
    )
    return 100.0 * least / seconds


def device_idle_pct(ctx):
    if ctx.planes is None:
        return None
    busy, window = xplane.busy_and_window_seconds(ctx.planes)
    return 100.0 * (1.0 - busy / window) if window else None


def device_peak_hbm_gb(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peaks is not None and ctx.peak_bytes else None
