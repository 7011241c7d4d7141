"""Readers of the three metrics that pages by kind add (a stack of window and
full attention layers: `archs/mellum.py`): how much of the window kind's
pages trimming gave back, how full the full kind's pages stood, and the device
time of the window layers' attention as a share of all programs'.

The counters are the program's (`pool.stats()`: `kv_window_pages_trimmed`,
`kv_window_pages_allocated`, `kv_full_pages_live`, `kv_full_pages`; the live
count is the pages the slots map, where `kv_full_pages_in_use` also counts
what the prefix index keeps of finished requests and so stands at the pool
whenever sharing is on). A device
event is named by its instruction's text and not by its scope, so the window
layers' attention is found as `hc.py` and `mla.py` find their kernels: by the
name of the program's jitted function in the RESULT name:
`window_decode_attention`, the decode step's kernel over the window kind's
pages. A chunk's window attention is plain XLA (`model._prefill_chunk_kinds`:
a loop of fusions over the 12 gathered pages); its operations keep no name of
where they came from, so the share is the decode kernels' alone until that
attention is a kernel (PERF.md section 7).

Each returns None where it finds nothing to read: an untraced run, a trace
without the names, a program without the counters."""

from __future__ import annotations

import re

from benchmark.harness import xplane

WINDOW_ATTENTION = re.compile(r"^%?window_decode_attention[.\d]*$")


def kv_window_trim_share_pct(ctx):
    trimmed = ctx.delta("kv_window_pages_trimmed")
    allocated = ctx.delta("kv_window_pages_allocated")
    if trimmed is None or not allocated:
        return None
    return 100.0 * trimmed / allocated


def kv_full_pages_peak_pct(ctx):
    v = [100.0 * s["kv_full_pages_live"] / s["kv_full_pages"]
         for _, s in ctx.samples
         if s.get("kv_full_pages") and "kv_full_pages_live" in s]
    return max(v) if v else None


def window_attention_events(ctx):
    """The window layers' attention events (name, start, duration) on the
    first device."""
    if ctx.planes is None:
        return []
    dev = xplane.device_planes(ctx.planes)
    if not dev:
        return []
    first = dev[sorted(dev)[0]]
    return [e for e in first.get(xplane.OPS_LINE, [])
            if WINDOW_ATTENTION.match(e[0].split(" = ", 1)[0].strip())]


def model_window_attn_share_pct(ctx):
    attention = sum(d for _, _, d in window_attention_events(ctx)) / 1e9
    programs = sum(d for _, _, d in xplane.modules(ctx.planes or {})) / 1e9
    if not attention or not programs:
        return None
    return 100.0 * attention / programs
