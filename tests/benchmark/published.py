"""The rule on what a configuration may change from its source, stated once.

A configuration file lists under `reduced` every key whose value differs from
the source's, and BENCHMARK.json's entry lists the same keys. No key of
`reduced` is a width. The `model-configs` guide's section 4 allows four cuts:
the depth, the experts held here, the rows of the vocabulary held here, and
the context. What the SOURCE says is data: one file per configuration,
`tests/benchmark/data/published/<config>.json` under the benchmark's root,

    {"source": "...",
     "published": {<key>: <the source's value>, ...},   every key of `reduced`, every width
     "widths": [<key>, ...],                            which of them are widths
     "cut": {<key of reduced>: "depth" | "experts_held" | "vocabulary" | "context"}}

so that a PR that adds a configuration adds its own file and edits no test.
"""

from __future__ import annotations

import json
import os
import re

CUTS = ("depth", "experts_held", "vocabulary", "context")
# a width by its name, whatever a file says: a hidden, intermediate, latent,
# state, projection or head size (any `_size` but the vocabulary's, which the
# guide lets a chip hold a share of), an expansion factor, the experts per token
WIDTH_NAME = re.compile(
    r"(_dim|_rank|_size)$|latent|proj|expand|expansion|experts_per_tok"
    r"|^(d_model|d_ff|n_embd|n_inner)$")


def check(man, name: str) -> dict:
    """Holds configuration `name` of the manifest `man` to the rule, against
    its published-values file; returns that file."""
    entry, config = man.config_entry(name), man.config(name)
    path = os.path.join(man.root, "tests", "benchmark", "data", "published", f"{name}.json")
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    published, widths, cut = doc["published"], doc["widths"], doc["cut"]
    assert doc["source"] == entry["source"] == config["source"]
    assert widths and set(widths) <= set(published)
    for key in list(entry["reduced"]) + list(config["reduced"]):
        assert key not in widths and (key == "vocab_size" or not WIDTH_NAME.search(key)), \
            f"{name}: {key} is a width"
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(cut)
    for key in entry["reduced"]:
        assert cut[key] in CUTS, (name, key, cut[key])
        assert config[key] != published[key], f"{name}: {key} is listed and was not changed"
    for key, value in published.items():
        if key not in entry["reduced"]:  # the widths among them
            assert config[key] == value, f"{name}: {key} differs from the source, unlisted"
    assert isinstance(config.get("arch"), str) and config["check"]["logit_gap_limit"] > 0
    return doc
