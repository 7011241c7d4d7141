"""A second architecture TO THE HARNESS, for the rehearsal of how a PR adds
one: `rehearsal_root.py` copies this file into the temporary root's
`benchmark/archs/`, and the configuration `tiny-other` reaches it by its
`arch` key alone. The program runs only the Mistral family, so this file
serves that family's tree (it loads `mistral.py`, which lies beside it in the
root, by location). What makes it another architecture to the harness is that
it is a module of another name whose sizes come from ANOTHER family's config
keys, with its own `model_fields` and trace markers; it states no control, so
the configuration's `check.control` is what a run reads.
"""

from __future__ import annotations

import os
from typing import Dict

from benchmark.harness.manifest import load_file

_M = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "mistral.py"),
               "benchmark_arch")


def dims_of(config: dict):
    hidden, heads = int(config["n_embd"]), int(config["n_head"])
    return _M.Dims(
        layers=int(config["n_layer"]), hidden=hidden, ffn=int(config["n_inner"]),
        heads=heads, kv_heads=int(config["n_head_kv"]), head_dim=hidden // heads,
        vocab=int(config["vocab_size"]), experts=0, top_k=0,
        rope_theta=float(config["rotary_base"]),
        eps=float(config["layer_norm_epsilon"]), window=None)


def context_length(config: dict) -> int:
    return int(config["n_positions"])


def model_fields(config: dict, context: int) -> Dict[str, object]:
    d = dims_of(config)
    return dict(
        name=config["assumed"]["served_name"], vocab_size=d.vocab, hidden_size=d.hidden,
        intermediate_size=d.ffn, num_layers=d.layers, num_heads=d.heads,
        num_kv_heads=d.kv_heads, head_dim=d.head_dim, max_context=context,
        rope_theta=d.rope_theta, rms_norm_eps=d.eps)


def trace_markers(d) -> Dict[str, object]:
    """The program's decode kernel, by the name it has in a trace."""
    return {"decode_kernel": "paged_decode_attention", "kernels_per_step": d.layers}


build_params, build_layer, build_top = _M.build_params, _M.build_layer, _M.build_top
embed, block, head = _M.embed, _M.block, _M.head
decode_step_bytes, decode_step_ops = _M.decode_step_bytes, _M.decode_step_ops
prefill_ops, prefill_bytes = _M.prefill_ops, _M.prefill_bytes
