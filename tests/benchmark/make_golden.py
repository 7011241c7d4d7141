"""What `tests/benchmark/data/golden/parent.{json,npz}` hold, computed anew.

The files were recorded from the tree at 964e4ed, before any architecture's
code moved out of `benchmark/harness/` into `benchmark/archs/`: the same
sizes, seeds and inputs through that tree's `weights.build_params`,
`reference.logits_for` and `roofline.*`. `compute()` takes the same through
the architecture's file; `test_bench_golden.py` holds the two equal, bit for
bit. To record again (only ever from a tree whose numbers are the yardstick):

    JAX_PLATFORMS=cpu python3 tests/benchmark/make_golden.py <out-prefix>
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from typing import Dict, Tuple

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

SEEDS = (1, 2 ** 31 + 7)  # one above 2**31: the driver's seeds are
SIZES = {  # the tiny dense and mixture sizes of test_bench_control.py
    "dense": dict(layers=4, hidden=256, ffn=512, heads=4, kv_heads=2, head_dim=64,
                  vocab=2048, experts=0, top_k=0, rope_theta=1e4, eps=1e-5, window=None),
    "moe": dict(layers=3, hidden=256, ffn=256, heads=4, kv_heads=2, head_dim=64,
                vocab=2048, experts=4, top_k=2, rope_theta=1e6, eps=1e-5, window=64),
}
CONFIGS = ("mistral-7b-int8", "mixtral-8x7b-int8-d6")


def digest(a) -> str:
    a = np.asarray(a)
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(a.tobytes()).hexdigest()


def canary() -> str:
    """Whether this machine sums a float32 product in the order the recording
    machine did: where it does not, logits can agree to rounding only."""
    import jax

    x = np.random.RandomState(0).standard_normal((64, 256)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        return digest(jax.jit(lambda a: a @ a.T @ a)(x))


def compute() -> Tuple[dict, Dict[str, np.ndarray]]:
    import jax

    from benchmark.harness import manifest, reference

    man = manifest.Manifest(REPO)
    arch = man.arch(man.config(CONFIGS[0]))
    doc: dict = {"weights": {}, "roofline": {}, "logits": {}, "canary": canary()}
    arrays: Dict[str, np.ndarray] = {}
    for name, sizes in SIZES.items():
        d = arch.Dims(**sizes)
        for seed in SEEDS:
            leaves = jax.tree_util.tree_flatten_with_path(arch.build_params(d, seed))[0]
            doc["weights"][f"{name}/{seed}"] = {
                jax.tree_util.keystr(p): digest(v) for p, v in leaves}
        seq = [int(t) for t in np.random.RandomState(11).randint(0, 2048, 200)]
        out = reference.logits_for(arch, d, SEEDS[1], [seq], [len(seq) - 16],
                                   ("float32", arch.CONTROL))
        arrays[f"{name}_float32"] = out["float32"][0]
        arrays[f"{name}_margin"] = out["router_margin"][0]
        doc["logits"][name] = {"float32": digest(out["float32"][0]),
                               "int4": digest(out[arch.CONTROL][0]),
                               "router_margin": digest(out["router_margin"][0])}
    for cfg in CONFIGS:
        config = man.config(cfg)
        a = man.arch(config)
        d = a.dims_of(config)
        doc["roofline"][cfg] = {
            "decode_step_bytes(8,10400)": float(a.decode_step_bytes(d, 8, 10400)).hex(),
            "decode_step_bytes(3,2777.5)": float(a.decode_step_bytes(d, 3, 2777.5)).hex(),
            "decode_step_ops(8,10400)": float(a.decode_step_ops(d, 8, 10400)).hex(),
            "prefill_ops([1536],[1024])": float(a.prefill_ops(d, [1536], [1024])).hex(),
            "prefill_ops([512,3072],[0,0])": float(a.prefill_ops(d, [512, 3072], [0, 0])).hex(),
            "prefill_bytes(512)": float(a.prefill_bytes(d, 512)).hex(),
            "prefill_bytes(3)": float(a.prefill_bytes(d, 3)).hex(),
        }
    return doc, arrays


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    doc, arrays = compute()
    with open(sys.argv[1] + ".json", "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
    np.savez_compressed(sys.argv[1] + ".npz", **arrays)
