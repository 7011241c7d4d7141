"""Readers of the four metrics a stack of linear-attention (KDA) layers adds
(`archs/bailing_hybrid.py`): the device time of the recurrence's decode step
as a share of the decode programs', its share of the bandwidth roofline, the
chunked recurrence's share of its roofline in the prefill programs, and the
share of the prefilled rows that a prefix hit would have served had the index
held the recurrent state (the program refuses such a hit).

A device event is named by its instruction's text and not by its scope, so
the recurrence is found as `mla.py` and `hc.py` find their kernels: by the
names of the program's two jitted functions in the RESULT name, `kda_step`
(one call a KDA layer a decode step) and `kda_chunk` (one call a KDA layer a
prefill program: the part of the chunked form that carries the state; the
sub-chunks' triangular solves beside it are plain XLA, keep no name of where
they came from and are not counted, so the chunk's share stands above what
the whole form would read).

Each returns None where it finds nothing to read: an untraced run, a trace
without the kernels, an architecture whose file counts no recurrence, a
program without the counters."""

from __future__ import annotations

import re

from benchmark.harness import readers, roofline, xplane


def _calls(ctx, kernel: str, kind: str):
    """(seconds in the kernel named `kernel`, seconds of the programs of
    `kind` that ran it, calls) on the first device of the trace."""
    if ctx.planes is None:
        return None
    dev = xplane.device_planes(ctx.planes)
    if not dev:
        return None
    own = re.compile(rf"^%?{kernel}[.\d]*$")
    programs = [(s, s + d) for name, s, d in xplane.modules(ctx.planes)
                if xplane.module_kind(name) == kind]
    first = dev[sorted(dev)[0]]
    calls = [(s, d) for name, s, d in first.get(xplane.OPS_LINE, [])
             if own.match(name.split(" = ", 1)[0].strip())
             and any(a <= s < b for a, b in programs)]
    if not calls:
        return None
    inside = {(a, b) for a, b in programs if any(a <= s < b for s, _ in calls)}
    return (sum(d for _, d in calls) / 1e9, sum(b - a for a, b in inside) / 1e9,
            len(calls))


def _kda_layers(ctx) -> int:
    count = getattr(ctx.dims, "count", None)
    return count("kda") if count else 0


def model_kda_decode_share_pct(ctx):
    found = _calls(ctx, "kda_step", "decode")
    if found is None or not found[1] or not ctx.delta("kda_rows_decode"):
        return None
    return 100.0 * found[0] / found[1]


def kernels_kda_decode_roofline_pct(ctx):
    step_bytes = getattr(ctx.arch, "kda_step_bytes", None)
    found = _calls(ctx, "kda_step", "decode")
    layers = _kda_layers(ctx)
    if step_bytes is None or found is None or ctx.peaks is None or not layers:
        return None
    seconds, _, calls = found
    least, n, t = 0.0, 0, ctx.trace_w0
    while t < ctx.trace_w1:  # the load a step met, as the decode roofline samples it
        active, _ = readers._load_at(ctx, t)
        if active:
            least += step_bytes(ctx.dims, active) / ctx.peaks["hbm_bytes_per_s"]
            n += 1
        t += 0.05
    if not n:
        return None
    return 100.0 * (least / n) / (seconds / (calls / layers))


def kernels_kda_prefill_roofline_pct(ctx):
    counts = [getattr(ctx.arch, n, None) for n in ("kda_chunk_ops", "kda_chunk_bytes")]
    found = _calls(ctx, "kda_chunk", "prefill")
    if None in counts or found is None or ctx.peaks is None:
        return None
    runs = readers._prefills(ctx)  # (rows before, new rows) a prefill program
    if not runs:
        return None
    least = sum(
        roofline.least_seconds(counts[0](ctx.dims, new), counts[1](ctx.dims, new),
                               ctx.peaks)["seconds"]
        for _, new in runs)
    return 100.0 * least / found[0]


def kv_prefix_refused_state_pct(ctx):
    """Of the rows the prefill programs computed in the window (padding
    included: `kda_rows_prefill` counts them once a KDA layer), those a prefix
    hit would have served. Both are the program's counters, taken as the
    admission is made: a request's own length would count the turns that were
    sent before the window or end after it on one side alone."""
    refused = ctx.delta("prefix_rows_refused_state")
    computed = ctx.delta("kda_rows_prefill")
    layers = _kda_layers(ctx)
    if refused is None or not computed or not layers:
        return None
    return 100.0 * refused / (computed / layers)
