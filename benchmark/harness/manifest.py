"""BENCHMARK.json and the data files it names: loading, finding, checking.

`run.py` runs `check` at start and a test runs it too. A cell's files are
found by name under the benchmark's directory: `configs/<config>.json`,
`traffic/<traffic>.json`, and every `layer_metrics/*.json` whose `kinds`
lists the traffic's kind (or `all`) — never a cell's name, so that a later
cell picks up the metrics that are there without an edit. Code that belongs
to one architecture or one metric is a file of its own there too, loaded by
its location: `archs/<arch>.py` for the `arch` a configuration file states,
`layer_metrics/<file>.py` for a metric's reader.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import re
import sys
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_END_TO_END = 4  # besides setup_s


class ManifestError(ValueError):
    pass


_LOADED: Dict[str, object] = {}


def load_file(path: str, prefix: str):
    """The module in the file at `path`, loaded once a process by its
    location and not by a package name, so that a later PR adds such a file
    without editing an index. It is entered in `sys.modules` under
    `<prefix>_<file>` (dataclasses look their module up there)."""
    path = os.path.abspath(path)
    if path not in _LOADED:
        name = f"{prefix}_{os.path.basename(path)[:-3]}"
        if not os.path.isfile(path):
            raise ManifestError(f"no file {path}")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


class Manifest:
    def __init__(self, root: str) -> None:
        self.root = root
        self.doc = _json(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.doc["paths"][0])

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return _json(os.path.join(self.root, self.config_entry(name)["file"]))

    def arch_file(self, config: dict) -> str:
        """Where the file of the architecture a configuration names lies:
        `archs/<arch>.py` under the benchmark's directory."""
        name = config.get("arch")
        if not isinstance(name, str) or not NAME.match(name):
            raise ManifestError(f"a configuration's `arch` is a name, not {name!r}")
        return os.path.join(self.dir, "archs", f"{name}.py")

    def arch(self, config: dict):
        """That file's module (it imports JAX)."""
        return load_file(self.arch_file(config), "benchmark_arch")

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.dir, "traffic", f"{name}.json"))

    def layer_metric_files(self) -> List[dict]:
        out = []
        for path in sorted(glob.glob(os.path.join(self.dir, "layer_metrics", "*.json"))):
            doc = _json(path)
            doc["_path"] = path
            out.append(doc)
        return out

    def end_to_end_of(self, cell: str) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def layer_metrics_of(self, cell: str) -> List[dict]:
        """The metric files whose kind matches the cell's traffic and that
        BENCHMARK.json lists for the cell."""
        kind = self.traffic(self.cell(cell)["traffic"])["kind"]
        listed = {m["name"]: m for m in self.doc["per_layer"]
                  if "workloads" not in m or cell in m["workloads"]}
        return [f for f in self.layer_metric_files()
                if f["name"] in listed
                and ("all" in f["kinds"] or kind in f["kinds"])]


def check(m: Manifest) -> None:
    """Raises ManifestError on the first rule of the contract that a later
    edit would most easily break."""
    doc = m.doc
    e2e = {x["name"]: x for x in doc["end_to_end"]}
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    if len(e2e) - 1 > MAX_END_TO_END:
        raise ManifestError(f"more than {MAX_END_TO_END} end-to-end metrics besides setup_s")
    names: List[str] = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in doc[group]:
            if entry["name"] in seen:
                raise ManifestError(f"{group}: {entry['name']!r} appears twice")
            seen.add(entry["name"])
            names.append(entry["name"])
    names += [w[k] for w in doc["workloads"] for k in ("config", "traffic")]
    names += [k for c in doc["configs"] for k in c["reduced"]]
    for n in names:
        if not NAME.match(n):
            raise ManifestError(f"name {n!r} is not letters, digits, '_', '.', '-'")
    for x in doc["end_to_end"] + doc["per_layer"]:
        if not UNIT.match(x["unit"]):
            raise ManifestError(f"unit {x['unit']!r} of {x['name']}")
        if x["source"] not in SOURCES or x["better"] not in ("lower", "higher"):
            raise ManifestError(f"source or better of {x['name']}")
    for entry in doc["configs"] + doc["workloads"] + doc["per_layer"]:
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            if not 1 <= len(text) <= 200 or "\n" in text or "\t" in text:
                raise ManifestError(f"{key} of {entry['name']} is not 1 to 200 characters on one line")
    cells = {w["name"]: w for w in doc["workloads"]}
    for w in doc["workloads"]:
        arch_file = m.arch_file(m.config(w["config"]))
        if not os.path.isfile(arch_file):
            raise ManifestError(f"configuration {w['config']}: there is no {arch_file}")
        m.traffic(w["traffic"])
        if len(m.end_to_end_of(w["name"])) < 2:
            raise ManifestError(f"cell {w['name']} reports no end-to-end metric besides setup_s")
        if not m.layer_metrics_of(w["name"]):
            raise ManifestError(f"cell {w['name']} reports no per-layer metric")
    files = {f["name"]: f for f in m.layer_metric_files()}
    for x in doc["per_layer"]:
        if x["moves"] not in e2e:
            raise ManifestError(f"{x['name']} moves {x['moves']!r}, which is no end-to-end metric")
        f = files.get(x["name"])
        if f is None:
            raise ManifestError(f"per-layer metric {x['name']} has no file under layer_metrics/")
        for key in ("unit", "layer", "moves", "source", "better"):
            if f[key] != x[key]:
                raise ManifestError(f"{x['name']}: {key} differs between its file and BENCHMARK.json")
        for c in x.get("workloads", list(cells)):
            if c not in cells:
                raise ManifestError(f"{x['name']} lists unknown cell {c!r}")
            reported = {e["name"] for e in m.end_to_end_of(c)}
            if x["moves"] not in reported:
                raise ManifestError(f"{x['name']} moves {x['moves']}, which cell {c} does not report")
            kind = m.traffic(cells[c]["traffic"])["kind"]
            if "all" not in f["kinds"] and kind not in f["kinds"]:
                raise ManifestError(f"{x['name']} lists cell {c}, whose traffic kind {kind} its file does not")
