#!/usr/bin/env python3
"""One traced run of a cell with the scheduler's phases read out of it:

    python3 tests/benchmark/make_phase_extract.py --workload <cell> --seed <n> \
        --seconds 40 [--trace 0] [--extract <out.json.gz>] [--hlo <out.txt>]

runs `benchmark/run.py --trace 1` in this process, keeps the reduced trace it
loads, and after the run's own line prints the stall events and no_progress
snapshots the flight recorder holds and what `layer_metrics/phases.py`
`report` says of the trace (the clock check, the idle share's two parts, the
longest idle gaps by phase). The run's JSON line is printed again last.

`--extract` writes the small recorded trace `test_bench_phases.py` reads: the
first device's programs, its busy intervals (operations merged: their names
are not needed) and the host planes' phase events inside `--extract-seconds`
from `--extract-from` seconds after the first prefill program. `--hlo` writes
the compiled text of the engine's decode step graphs (the profiler's events
carry no operation metadata on the TPU; the text has each instruction's
`jax.named_scope` path and source line). Not part of a run of the benchmark.
"""

from __future__ import annotations

import argparse
import gzip
import importlib.util
import json
import os
import sys
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import xplane  # noqa: E402

BUSY = "%busy"


def load_phases():
    path = os.path.join(REPO, "benchmark", "layer_metrics", "phases.py")
    spec = importlib.util.spec_from_file_location("benchmark_reader_phases", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def extract(planes: Dict[str, xplane.Plane], phases, start_s: float, seconds: float) -> dict:
    mods = xplane.modules(planes)
    anchor = next((m for m in mods if xplane.module_kind(m[0]) == "prefill"), mods[0])
    t0 = anchor[1] + int(start_s * 1e9)
    t1 = t0 + int(seconds * 1e9)
    dev = xplane.device_planes(planes)
    first = sorted(dev)[0]
    names: Dict[str, int] = {}

    def rows(events):
        return [[names.setdefault(n, len(names)), s - t0, d] for n, s, d in events]

    busy = phases.merge((s, s + d) for _, s, d in dev[first].get(xplane.OPS_LINE, [])
                        if t0 <= s and s + d <= t1)
    out = {first: {
        xplane.MODULES_LINE: rows(m for m in dev[first].get(xplane.MODULES_LINE, [])
                                  if t0 <= m[1] and m[1] + m[2] <= t1),
        xplane.OPS_LINE: rows((BUSY, s, e - s) for s, e in busy),
    }}
    for plane, lines in planes.items():
        if not plane.startswith("/host:"):
            continue
        kept = [ev for events in lines.values() for ev in events
                if ev[0].startswith(("batcher.", "engine.")) and t0 <= ev[1] and ev[1] + ev[2] <= t1]
        if kept:
            out[plane] = {"phases": rows(sorted(kept, key=lambda e: e[1]))}
    return {"names": list(names), "planes": out, "window_ns": t1 - t0}


def from_extract(doc: dict) -> Dict[str, xplane.Plane]:
    names = doc["names"]
    return {plane: {line: [(names[i], s, d) for i, s, d in events]
                    for line, events in lines.items()}
            for plane, lines in doc["planes"].items()}


def keep_step_graphs(path: str) -> None:
    """Have the engine write the compiled text of every decode step graph it
    builds into `path` (operation metadata: each instruction's
    `jax.named_scope` path and source line)."""
    from aios_tpu.engine.engine import TPUEngine

    real = TPUEngine._compile_aot

    def compile_aot(self, kind, store, key, *args, **kw):
        known = key in store
        real(self, kind, store, key, *args, **kw)
        if kind == "step" and not known and hasattr(store[key], "as_text"):
            with open(path, "a") as fh:
                fh.write(f"### step graph {key!r}\n{store[key].as_text()}\n")

    TPUEngine._compile_aot = compile_aot


def say_loop_records(say=print) -> None:
    """The loop's own records of this process: every stall event and every
    no_progress snapshot the flight recorder holds."""
    from aios_tpu.obs import flightrec

    stalls = [f for _, _, kind, f in flightrec.RECORDER.model_events() if kind == "stall"]
    say(f"stall events: {len(stalls)}" + "".join(f"\n  {f}" for f in stalls[-40:]))
    held = [s for s in flightrec.RECORDER.snapshots() if s["cause"] == "no_progress"]
    say(f"no_progress snapshots: {len(held)}" + "".join(
        f"\n  {json.dumps(s.get('detail'))[:1500]}" for s in held))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--extract", default="")
    p.add_argument("--extract-from", type=float, default=-0.1)
    p.add_argument("--extract-seconds", type=float, default=0.6)
    p.add_argument("--hlo", default="")
    args, run_args = p.parse_known_args()

    from benchmark import run as bench_run

    phases = load_phases()
    kept = {}
    real_load = xplane.load

    def load(path):
        kept["planes"] = real_load(path)
        return kept["planes"]

    xplane.load = load
    if args.hlo:
        keep_step_graphs(args.hlo)
    lines = []

    def say(text: str) -> None:
        lines.append(text)
        print(text, flush=True)

    bench_run.say = say
    if "--trace" not in run_args:
        run_args += ["--trace", "1"]
    rc = bench_run.main(run_args)
    say_loop_records()
    if "planes" in kept:
        phases.report(kept["planes"], say=print)
        if args.extract:
            with gzip.open(args.extract, "wt") as fh:
                json.dump(extract(kept["planes"], phases, args.extract_from,
                                  args.extract_seconds), fh, separators=(",", ":"))
    if lines and lines[-1].startswith("{"):
        print(lines[-1], flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
