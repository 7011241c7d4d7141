#!/usr/bin/env python3
"""Regenerates the small recorded trace the xplane tests read:

    python3 tests/benchmark/make_trace_extract.py <file.xplane.pb> <out.json.gz>

keeps the first device's module and operation events that lie wholly inside
0.4 s around its first prefill program, names in a table, instants from the
window's start. Not part of a run of the benchmark.
"""

from __future__ import annotations

import gzip
import json
import os
import sys
from typing import Dict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.harness import xplane  # noqa: E402


def extract(planes: Dict[str, xplane.Plane], before_s: float = 0.08,
            after_s: float = 0.32) -> dict:
    mods = xplane.modules(planes)
    anchor = next((m for m in mods if xplane.module_kind(m[0]) == "prefill"), None)
    if anchor is None:
        return {}
    t0, t1 = anchor[1] - int(before_s * 1e9), anchor[1] + int(after_s * 1e9)
    dev = xplane.device_planes(planes)
    first = dev[sorted(dev)[0]]
    names: Dict[str, int] = {}
    lines = {}
    for line in (xplane.MODULES_LINE, xplane.OPS_LINE):
        lines[line] = [
            [names.setdefault(n, len(names)), s - t0, d]
            for n, s, d in first.get(line, []) if t0 <= s and s + d <= t1
        ]
    return {"plane": sorted(dev)[0], "names": list(names), "lines": lines,
            "window_ns": t1 - t0}


if __name__ == "__main__":
    with gzip.open(sys.argv[2], "wt") as fh:
        json.dump(extract(xplane.load(sys.argv[1])), fh, separators=(",", ":"))
