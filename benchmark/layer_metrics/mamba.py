"""Readers of the four metrics a stack with Mamba-2 state-space layers adds
(`archs/nemotron_h.py`): the device time of the recurrence's decode step as a
share of the decode programs', its share of the bandwidth roofline, the
chunked recurrence's share of its roofline in the prefill programs over the
REAL rows prefilled, and how full the house of state slots was.

A device event is named by its instruction's text and not by its scope, so
the recurrence is found as `kda.py` finds its kernels: by the names of the
program's two jitted functions in the RESULT name, `mamba_step` (one call a
Mamba layer a decode step) and `mamba_chunk` (one call a Mamba layer a
prefill program: the part of the chunked form that carries the state; the
sub-chunks' products within themselves are plain XLA beside it, keep no name
of where they came from and are not counted, so the chunk's share stands
above what the whole form would read).

Each returns None where it finds nothing to read: an untraced run, a trace
without the kernels, an architecture whose file counts no recurrence, a
program without the counters."""

from __future__ import annotations

import os

from benchmark.harness import manifest, readers, roofline


# (seconds in a kernel, seconds of the programs of a kind that ran it, calls)
_calls = manifest.load_file(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "kda.py"),
    "benchmark_reader")._calls


def _mamba_layers(ctx) -> int:
    count = getattr(ctx.dims, "count", None)
    return count("mamba2") if count else 0


def model_mamba_decode_share_pct(ctx):
    found = _calls(ctx, "mamba_step", "decode")
    if found is None or not found[1] or not ctx.delta("mamba_rows_decode"):
        return None
    return 100.0 * found[0] / found[1]


def kernels_mamba_decode_roofline_pct(ctx):
    step_bytes = getattr(ctx.arch, "mamba_step_bytes", None)
    found = _calls(ctx, "mamba_step", "decode")
    layers = _mamba_layers(ctx)
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


def kernels_mamba_prefill_roofline_pct(ctx):
    counts = [getattr(ctx.arch, n, None) for n in ("mamba_chunk_ops", "mamba_chunk_bytes")]
    found = _calls(ctx, "mamba_chunk", "prefill")
    if None in counts or found is None or ctx.peaks is None:
        return None
    runs = readers._prefills(ctx)  # (rows before, REAL new rows) a prefill program
    if not runs:
        return None
    least = sum(
        roofline.least_seconds(counts[0](ctx.dims, new), counts[1](ctx.dims, new),
                               ctx.peaks)["seconds"]
        for _, new in runs)
    return 100.0 * least / found[0]


def kv_state_slots_peak_pct(ctx):
    """The most slots whose recurrent state was live at a sample, of the
    slots there are."""
    v = [s["kv_state_slots"] for _, s in ctx.samples if "kv_state_slots" in s]
    return 100.0 * max(v) / ctx.slots if v and ctx.slots else None
