"""Builds a temporary benchmark root for the CPU rehearsals: a copy of the
committed benchmark plus three configurations (one of them of another
architecture, with that architecture's file), two traffic mixes, three cells
and one per-layer metric ADDED as new files and new entries — no file that is
there is edited, which is what a later PR is held to. `added()` lists what
was added; every other file under `benchmark/` is byte for byte the
committed one (test_bench_rehearsal.py holds it to that)."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")

READER = '''def completed(ctx):
    """Requests the pool completed in the window (a counter of the program)."""
    return ctx.delta("completed")
'''

CELLS = {"tiny-agents": ("tiny-test", "tiny-agents"),
         "tiny-moe-arrivals": ("tiny-moe", "tiny-arrivals"),
         "tiny-other-agents": ("tiny-other", "tiny-agents")}
KINDS = {"tiny-agents": "closed_agents", "tiny-moe-arrivals": "open_arrivals",
         "tiny-other-agents": "closed_agents"}
# what a PR that adds a configuration of another architecture brings: these
# files, and entries in BENCHMARK.json; relative to the root
OTHER_ARCH = {"benchmark/archs/otherfamily.py": "otherfamily.py",
              "benchmark/configs/tiny-other.json": "tiny-other.json",
              "tests/benchmark/data/published/tiny-other.json": "tiny-other.published.json"}


def build(tmp: str) -> str:
    root = os.path.join(tmp, "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    for config in ("tiny-test", "tiny-moe"):
        shutil.copy(os.path.join(TINY, f"{config}.json"),
                    os.path.join(root, "benchmark", "configs"))
    for dest, source in OTHER_ARCH.items():
        os.makedirs(os.path.dirname(os.path.join(root, dest)), exist_ok=True)
        shutil.copy(os.path.join(TINY, source), os.path.join(root, dest))
    for config in ("tiny-test", "tiny-moe", "tiny-other"):
        with open(os.path.join(root, "benchmark", "configs", f"{config}.json")) as fh:
            source = json.load(fh)["source"]
        doc["configs"].append({
            "name": config, "source": source,
            "file": f"benchmark/configs/{config}.json", "reduced": [],
            "why": "CPU rehearsal size"})
    for traffic in ("tiny-agents", "tiny-arrivals"):
        shutil.copy(os.path.join(TINY, f"{traffic}.json"),
                    os.path.join(root, "benchmark", "traffic"))
    for name, (config, traffic) in CELLS.items():
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": 1,
                                 "why": "CPU rehearsal"})
    # a new cell's name is appended to the `workloads` of the metrics it reports
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" not in m:
            continue
        path = os.path.join(root, "benchmark", "layer_metrics", f"{m['name']}.json")
        if os.path.exists(path):
            with open(path) as fh:
                wanted = json.load(fh)["kinds"]
            m["workloads"] += [c for c, k in KINDS.items()
                               if "all" in wanted or k in wanted]
    for m in doc["end_to_end"]:
        if m["name"] == "ttft_p80_ms":
            m["workloads"].append("tiny-moe-arrivals")
        if m["name"] == "out_tok_s":
            m["workloads"] += ["tiny-agents", "tiny-other-agents"]
    new = {"name": "pool.completed", "unit": "count", "better": "higher",
           "layer": "admission + routing", "source": "program_counter",
           "moves": "tpot_p50_ms"}
    doc["per_layer"].append({**new, "workloads": list(CELLS)})
    with open(os.path.join(root, "benchmark", "layer_metrics", "pool.completed.json"), "w") as fh:
        json.dump({**new, "kinds": ["all"], "reader": "pool_completed.py:completed"}, fh)
    with open(os.path.join(root, "benchmark", "layer_metrics", "pool_completed.py"), "w") as fh:
        fh.write(READER)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    return root


def added() -> set:
    """The files under the root's `benchmark/` that the committed one lacks."""
    return {f"benchmark/configs/{c}.json" for c in ("tiny-test", "tiny-moe", "tiny-other")} | {
        f"benchmark/traffic/{t}.json" for t in ("tiny-agents", "tiny-arrivals")} | {
        "benchmark/archs/otherfamily.py", "benchmark/layer_metrics/pool.completed.json",
        "benchmark/layer_metrics/pool_completed.py"}
