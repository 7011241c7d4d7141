"""From the profiler's `.xplane.pb` to the few numbers the readers need.

`load` turns the file into plain tuples with nothing but JAX; everything
after it works on those tuples, so the reduction is tested on a small
recorded extract (tests/benchmark/data/) without a chip.

A device plane is `/device:TPU:<n>`. Its "XLA Modules" line has one event per
execution of a compiled program (named `jit_<function>(<id>)`), its "XLA Ops"
line one event per executed HLO operation (named by the instruction's text;
control-flow operations enclose the operations of their bodies).

Which kernel marks a decode step, and how often a step runs it, is the
architecture's (`trace_markers(d)` of its file under `benchmark/archs/`):
`decode_steps` takes both as arguments. The names of the engine's decode and
prefill programs are the program's own, the same whatever model it serves.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns
Plane = Dict[str, List[Event]]  # line name -> events

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
# operations that only enclose others: counted in no ranking, but busy all the same
_CONTROL = re.compile(r"^%?(while|conditional|call)[.\d]*$")
DECODE_MODULE = "jit__lambda"  # the engine's step graphs are lambdas
PREFILL_MODULE = re.compile(r"prefill|chunk")


def find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Plane]:
    from jax.profiler import ProfileData

    out: Dict[str, Plane] = {}
    for plane in ProfileData.from_file(path).planes:
        lines: Plane = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events
            )
        out[plane.name] = lines
    return out


def device_planes(planes: Dict[str, Plane]) -> Dict[str, Plane]:
    return {k: v for k, v in planes.items() if k.startswith("/device:TPU:")}


def union_ns(events: Iterable[Event]) -> int:
    """Nanoseconds covered by at least one event."""
    total, end = 0, -1
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s + d <= end:
            continue
        total += s + d - max(s, end)
        end = s + d
    return total


def window_ns(plane: Plane) -> Tuple[int, int]:
    """The traced window on the device's own clock: from the start of the
    first program recorded on this device to the end of the last. The host's
    instants around start_trace / stop_trace are on another clock and span."""
    evs = plane.get(MODULES_LINE) or plane.get(OPS_LINE) or []
    if not evs:
        return 0, 0
    return min(s for _, s, _ in evs), max(s + d for _, s, d in evs)


def busy_and_window_seconds(planes: Dict[str, Plane]) -> Tuple[float, float]:
    """Seconds in which an operation ran inside the device's traced window,
    and that window's length, both averaged over the device planes. The
    window opens and closes with a program, so idle time before the first and
    after the last is in neither number."""
    dev = device_planes(planes)
    if not dev:
        return 0.0, 0.0
    busy = window = 0
    for plane in dev.values():
        w0, w1 = window_ns(plane)
        busy += union_ns((n, max(s, w0), min(s + d, w1) - max(s, w0))
                         for n, s, d in plane.get(OPS_LINE, []) if s < w1 and s + d > w0)
        window += w1 - w0
    return busy / len(dev) / 1e9, window / len(dev) / 1e9


def is_control(name: str) -> bool:
    return bool(_CONTROL.match(name.split(" = ", 1)[0].strip()))


def top_ops(planes: Dict[str, Plane], n: int = 10) -> List[Tuple[str, float]]:
    """The operations with most device time, control flow left out."""
    total: Dict[str, int] = {}
    for plane in device_planes(planes).values():
        for name, _, d in plane.get(OPS_LINE, []):
            if not is_control(name):
                total[name] = total.get(name, 0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(_short(k), v / 1e9) for k, v in ranked]


def _short(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:64]


def modules(planes: Dict[str, Plane]) -> List[Event]:
    """Program executions on the first device, by start."""
    dev = device_planes(planes)
    if not dev:
        return []
    first = dev[sorted(dev)[0]]
    return sorted(first.get(MODULES_LINE, []), key=lambda e: e[1])


def module_kind(name: str) -> str:
    if name.startswith(DECODE_MODULE):
        return "decode"
    return "prefill" if PREFILL_MODULE.search(name) else "other"


def decode_steps(planes: Dict[str, Plane], decode_kernel: str, kernels_per_step: int
                 ) -> List[Tuple[float, int]]:
    """(device seconds, steps) of each decode program execution. A program
    runs several steps per dispatch; a step runs the kernel named
    `decode_kernel` `kernels_per_step` times (an attention kernel, once per
    layer that has it), so its count inside a program's interval gives the
    steps."""
    dev = device_planes(planes)
    if not dev:
        return []
    first = dev[sorted(dev)[0]]
    kernels = sorted(s for name, s, _ in first.get(OPS_LINE, [])
                     if decode_kernel in name)
    out = []
    for name, s, d in modules(planes):
        if module_kind(name) != "decode":
            continue
        n = bisect.bisect_left(kernels, s + d) - bisect.bisect_left(kernels, s)
        if n >= kernels_per_step:
            out.append((d / 1e9, n // kernels_per_step))
    return out


def prefill_seconds(planes: Dict[str, Plane]) -> float:
    return sum(d for name, _, d in modules(planes)
               if module_kind(name) == "prefill") / 1e9


def module_gaps(planes: Dict[str, Plane]) -> List[Tuple[str, float]]:
    """Idle gaps between consecutive program executions, named by the
    programs on either side (what the batcher was doing)."""
    mods = [m for m in modules(planes) if module_kind(m[0]) != "other"]
    out = []
    for (n0, s0, d0), (n1, s1, _) in zip(mods, mods[1:]):
        gap = s1 - (s0 + d0)
        if gap <= 0:
            continue
        k0, k1 = module_kind(n0), module_kind(n1)
        what = ("between_decode_dispatches" if k0 == k1 == "decode"
                else f"during_a_prefill_admission.{k0}-{k1}")
        out.append((what, gap / 1e9))
    return out


def from_extract(doc: dict) -> Dict[str, Plane]:
    names = doc["names"]
    return {doc["plane"]: {
        line: [(names[i], s, d) for i, s, d in evs]
        for line, evs in doc["lines"].items()
    }}
