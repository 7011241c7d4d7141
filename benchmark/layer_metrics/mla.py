"""Readers of the metrics a latent-attention configuration adds: the latent
decode kernel's share of its roofline and of the decode step (device trace),
and the expert counters of a layer that holds a share of its experts (the
program's `moe_picks_total`, `moe_picks_local`, `moe_expert_rows`).

Each returns None where it finds nothing to read: an architecture whose file
counts no latent kernel, a trace without the kernel, a program without the
counters."""

from __future__ import annotations

from benchmark.harness import readers, roofline, xplane


def _kernel_and_program_seconds(ctx):
    """(seconds in the architecture's decode kernel, seconds of the decode
    programs it ran in, kernel calls) on the first device of the trace."""
    if ctx.planes is None:
        return None
    dev = xplane.device_planes(ctx.planes)
    if not dev:
        return None
    kernel = ctx.arch.trace_markers(ctx.dims)["decode_kernel"]
    programs = [(s, s + d) for name, s, d in xplane.modules(ctx.planes)
                if xplane.module_kind(name) == "decode"]
    first = dev[sorted(dev)[0]]
    # an event is named by its instruction's text, operands included: the
    # kernel's own instruction is the one whose RESULT carries the name
    calls = [(s, d) for name, s, d in first.get(xplane.OPS_LINE, [])
             if kernel in name.split(" = ", 1)[0]
             and any(a <= s < b for a, b in programs)]
    if not calls:
        return None
    inside = {(a, b) for a, b in programs if any(a <= s < b for s, _ in calls)}
    return (sum(d for _, d in calls) / 1e9, sum(b - a for a, b in inside) / 1e9,
            len(calls))


def kernels_mla_decode_roofline_pct(ctx):
    counts = [getattr(ctx.arch, n, None) for n in ("mla_decode_ops", "mla_decode_bytes")]
    found = _kernel_and_program_seconds(ctx)
    if None in counts or found is None or ctx.peaks is None:
        return None
    seconds, _, calls = found
    per_step = ctx.arch.trace_markers(ctx.dims)["kernels_per_step"]
    least, n, t = 0.0, 0, ctx.trace_w0
    while t < ctx.trace_w1:  # the load a step met, as the decode roofline samples it
        active, rows = readers._load_at(ctx, t)
        if active:
            least += roofline.least_seconds(
                counts[0](ctx.dims, active, rows), counts[1](ctx.dims, active, rows),
                ctx.peaks)["seconds"]
            n += 1
        t += 0.05
    if not n:
        return None
    return 100.0 * (least / n) / (seconds / (calls / per_step))


def model_mla_decode_share_pct(ctx):
    found = _kernel_and_program_seconds(ctx)
    if found is None or not found[1]:
        return None
    return 100.0 * found[0] / found[1]


def _ratio(ctx, over: str, under: str, scale: float):
    a, b = ctx.delta(over), ctx.delta(under)
    return scale * a / b if a is not None and b else None


def moe_local_pick_share_pct(ctx):
    return _ratio(ctx, "moe_picks_local", "moe_picks_total", 100.0)


def moe_rows_per_local_pick(ctx):
    return _ratio(ctx, "moe_expert_rows", "moe_picks_local", 1.0)
