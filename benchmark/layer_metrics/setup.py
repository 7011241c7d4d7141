"""Readers of the seven metrics beneath `setup_s`: what the program itself
records of a model's set-up, from `LoadModel`'s entry to ready.

The program (aios_tpu/obs/flightrec.py `Phases`, the closed list
`SETUP_PHASES`) closes `load.model` around the whole of `load_model`, its
four parts (`load.weights`, `load.engine`, `load.warmup`, `load.attach`)
inside it, and one `warmup.trace` / `warmup.lower` / `warmup.compile` triple
around the stages of each graph compiled ahead of time. Their seconds and
counts are always-on counters of `pool.stats()` (`phase_<name>_seconds`),
beside `xla_compiles`, `warmup_trace_cpu_seconds` (thread CPU seconds inside
the trace and lower spans) and `compile_cache_requests` / `compile_cache_hits`
(JAX's own events of its persistent compile cache).

Set-up is over when the window opens, so every reader takes `ctx.before`, the
counters as the window opens: none needs the profiler. A program without the
counters (the parent of the PR that added them) has none of the keys, and
every reader but `engine_graphs_compiled`, whose counter is older, then
returns None."""

from __future__ import annotations

from typing import Optional

PARTS = ("load.weights", "load.engine", "load.warmup", "load.attach")


def _seconds(ctx, *phases: str) -> Optional[float]:
    """The summed seconds of the named phases at the window's opening."""
    keys = [f"phase_{p}_seconds" for p in phases]
    if any(k not in ctx.before for k in keys):
        return None
    return sum(ctx.before[k] for k in keys)


def rpc_load_model_s(ctx):
    return _seconds(ctx, "load.model")


def rpc_load_unnamed_pct(ctx):
    whole, named = _seconds(ctx, "load.model"), _seconds(ctx, *PARTS)
    if not whole or named is None:
        return None
    return 100.0 * (whole - named) / whole


def engine_warmup_trace_s(ctx):
    return _seconds(ctx, "warmup.trace", "warmup.lower")


def engine_warmup_compile_s(ctx):
    return _seconds(ctx, "warmup.compile")


def engine_warmup_offcpu_pct(ctx):
    wall = _seconds(ctx, "warmup.trace", "warmup.lower")
    cpu = ctx.before.get("warmup_trace_cpu_seconds")
    if not wall or cpu is None:
        return None
    return 100.0 * (1.0 - cpu / wall)


def engine_graphs_compiled(ctx):
    return ctx.before.get("xla_compiles")


def engine_compile_cache_miss_count(ctx):
    requests = ctx.before.get("compile_cache_requests")
    hits = ctx.before.get("compile_cache_hits")
    if requests is None or hits is None:
        return None
    return requests - hits
