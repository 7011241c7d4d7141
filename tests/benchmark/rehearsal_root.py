"""Builds a temporary benchmark root for the CPU rehearsals: a copy of the
committed data plus one configuration, one traffic mix, one cell and one
per-layer metric ADDED as new files and new entries — no file that is there
is edited, which is what a later PR is held to."""

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


def build(tmp: str) -> str:
    root = os.path.join(tmp, "root")
    os.makedirs(root)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    cells = {"tiny-agents": ("tiny-test", "tiny-agents"),
             "tiny-moe-arrivals": ("tiny-moe", "tiny-arrivals")}
    for config in ("tiny-test", "tiny-moe"):
        shutil.copy(os.path.join(TINY, f"{config}.json"),
                    os.path.join(root, "benchmark", "configs"))
        doc["configs"].append({
            "name": config, "source": "aios_tpu/engine/config.py",
            "file": f"benchmark/configs/{config}.json", "reduced": [],
            "why": "CPU rehearsal size"})
    for traffic in ("tiny-agents", "tiny-arrivals"):
        shutil.copy(os.path.join(TINY, f"{traffic}.json"),
                    os.path.join(root, "benchmark", "traffic"))
    for name, (config, traffic) in cells.items():
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": 1,
                                 "why": "CPU rehearsal"})
    kinds = {"tiny-agents": "closed_agents", "tiny-moe-arrivals": "open_arrivals"}
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" not in m:
            continue
        path = os.path.join(root, "benchmark", "layer_metrics", f"{m['name']}.json")
        if os.path.exists(path):
            with open(path) as fh:
                wanted = json.load(fh)["kinds"]
            m["workloads"] += [c for c, k in kinds.items()
                               if "all" in wanted or k in wanted]
    for m in doc["end_to_end"]:
        if m["name"] == "ttft_p80_ms":
            m["workloads"].append("tiny-moe-arrivals")
        if m["name"] == "out_tok_s":
            m["workloads"].append("tiny-agents")
    new = {"name": "pool.completed", "unit": "count", "better": "higher",
           "layer": "admission + routing", "source": "program_counter",
           "moves": "tpot_p50_ms"}
    doc["per_layer"].append({**new, "workloads": list(cells)})
    with open(os.path.join(root, "benchmark", "layer_metrics", "pool.completed.json"), "w") as fh:
        json.dump({**new, "kinds": ["all"], "reader": "pool_completed.py:completed"}, fh)
    with open(os.path.join(root, "benchmark", "layer_metrics", "pool_completed.py"), "w") as fh:
        fh.write(READER)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    return root
