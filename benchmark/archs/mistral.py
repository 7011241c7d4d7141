"""The Mistral family as the benchmark knows it: Mistral-7B (a dense SwiGLU
FFN) and Mixtral (the same block with a softmax top-k router over experts).
A configuration file names this file by `"arch": "mistral"`; nothing in
`benchmark/run.py` or `benchmark/harness/` names it, or anything in it.

One file per architecture holds everything that knows the architecture:

1. `dims_of(config)`: its sizes, frozen and hashable (static under `jit`),
   from the keys of the model's published config.json;
2. the seeded weights in the layout the program serves (`build_params`,
   `build_layer`, `build_top`);
3. the plain reference's pieces (`embed`, `block`, `head`), which
   `harness/reference.py` drives layer by layer;
4. the least bytes and operations of a decode step and of a prefill
   (`decode_step_bytes/ops`, `prefill_ops/bytes`), which the roofline readers
   divide by the device's time;
5. `trace_markers(d)`: the kernel that marks a decode step in the profiler's
   trace, and how many times a step runs it;
6. `context_length(config)` and `model_fields(config, context)`: the
   positions to load the model for, and the program's model fields as a plain
   dict. This file imports nothing of the program: `harness/manager.py` alone
   turns the dict into the program's `ModelConfig`.

Layout (the checkpoint format the configurations' `assumed` lists state):
`w_qkv` is [wq | wk | wv] along columns, `w_gateup` / `we_gateup` are
[gate | up]; every layer has the same tree, stacked along a leading axis.

The reference departs from the published model in nothing of the
mathematics: RMSNorm, rotary embedding in the half-rotation convention,
grouped-query causal attention with the sliding window, SwiGLU, and for the
mixture-of-experts FFN softmax over all experts, top-k, renormalised. The
weights are the int8 checkpoint's `q * s`, which is the model the
configurations state; the step below it, their control, is int4.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmark.harness import reference as R
from benchmark.harness import weights as W
from benchmark.harness.roofline import expected_distinct_experts, matrix_bytes

KV_BYTES = 2  # bfloat16 cache, as the configurations state
CONTROL = "int4"  # the precision below the int8 these weights are served in


@dataclass(frozen=True)
class Dims:
    layers: int
    hidden: int
    ffn: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int  # 0: dense FFN
    top_k: int
    rope_theta: float
    eps: float
    window: Optional[int]

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def dims_of(config: dict) -> Dims:
    """Sizes from a configuration file (keys as in the model's config.json)."""
    heads = int(config["num_attention_heads"])
    hidden = int(config["hidden_size"])
    return Dims(
        layers=int(config["num_hidden_layers"]), hidden=hidden,
        ffn=int(config["intermediate_size"]), heads=heads,
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or hidden // heads),
        vocab=int(config["vocab_size"]),
        experts=int(config.get("num_local_experts") or 0),
        top_k=int(config.get("num_experts_per_tok") or 0),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        window=config.get("sliding_window"),
    )


def context_length(config: dict) -> int:
    """The positions the model is loaded for."""
    return int(config["max_position_embeddings"])


def model_fields(config: dict, context: int) -> Dict[str, object]:
    """The fields of the program's `ModelConfig` for this configuration."""
    d = dims_of(config)
    return dict(
        name=config["assumed"]["served_name"], vocab_size=d.vocab,
        hidden_size=d.hidden, intermediate_size=d.ffn, num_layers=d.layers,
        num_heads=d.heads, num_kv_heads=d.kv_heads, head_dim=d.head_dim,
        max_context=context, rope_theta=d.rope_theta, rms_norm_eps=d.eps,
        sliding_window=d.window, num_experts=d.experts,
        num_experts_per_tok=d.top_k or 2,
    )


def trace_markers(d: Dims) -> Dict[str, object]:
    """The paged attention kernel runs once per layer per decode step."""
    return {"decode_kernel": "paged_decode_attention", "kernels_per_step": d.layers}


# -- weights -------------------------------------------------------------------


def layer_leaves(d: Dims, key) -> Dict[str, object]:
    """The leaves of ONE layer (no layer axis) from that layer's key."""
    ks = jax.random.split(key, 8)
    out = {
        "attn_norm": W.norm(ks[0], d.hidden),
        "ffn_norm": W.norm(ks[1], d.hidden),
        "w_qkv": W.qleaf(ks[2], (d.hidden, d.q_dim + 2 * d.kv_dim)),
        "wo": W.qleaf(ks[3], (d.q_dim, d.hidden)),
    }
    if d.experts:
        out["w_router"] = W.small(ks[4], (d.hidden, d.experts))
        out["we_gateup"] = W.qleaf(ks[5], (d.experts, d.hidden, 2 * d.ffn))
        out["we_down"] = W.qleaf(ks[6], (d.experts, d.ffn, d.hidden))
    else:
        out["w_gateup"] = W.qleaf(ks[5], (d.hidden, 2 * d.ffn))
        out["w_down"] = W.qleaf(ks[6], (d.ffn, d.hidden))
    return out


def top_leaves(d: Dims, k_embed, k_norm, k_head) -> Dict[str, object]:
    return {
        "embed": W.small(k_embed, (d.vocab, d.hidden)),
        "final_norm": W.norm(k_norm, d.hidden),
        "lm_head": W.qleaf(k_head, (d.hidden, d.vocab)),
    }


def build_params(d: Dims, seed: int):
    """The whole serving tree, on the device, in one jitted call."""
    return W.build_stack(layer_leaves, top_leaves, d, seed)


def build_layer(d: Dims, seed: int, layer: int):
    """Layer `layer` of the same tree, alone (for the reference)."""
    return W.build_stack_layer(layer_leaves, d, seed, layer)


def build_top(d: Dims, seed: int):
    """Embedding, final norm and output head of the same tree."""
    return W.build_stack_top(top_leaves, d, seed)


# -- the plain reference -------------------------------------------------------


def embed(top, ids):
    return top["embed"][ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _block(d: Dims, x, lw, precision: str):
    """One transformer block over one sequence x [T, E] float32: the block's
    output and each position's router margin (infinite without a router)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = R.rms(x, lw["attn_norm"], d.eps)
    qkv = h @ R.dense(lw["w_qkv"], precision)
    q = qkv[:, :d.q_dim].reshape(t, d.heads, d.head_dim)
    k = qkv[:, d.q_dim:d.q_dim + d.kv_dim].reshape(t, d.kv_heads, d.head_dim)
    v = qkv[:, d.q_dim + d.kv_dim:].reshape(t, d.kv_heads, d.head_dim)
    q, k = R.rope(q, pos, d.rope_theta), R.rope(k, pos, d.rope_theta)
    rep = d.heads // d.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    mask = pos[:, None] >= pos[None, :]
    if d.window:
        mask = mask & (pos[:, None] - pos[None, :] < d.window)

    def head(qh, kh, vh):  # one head at a time keeps the [T, T] scores small
        s = (qh @ kh.T) / jnp.sqrt(jnp.float32(d.head_dim))
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ vh

    att = jax.lax.map(lambda a: head(*a), (q.swapaxes(0, 1), k.swapaxes(0, 1),
                                           v.swapaxes(0, 1)))
    x = x + att.swapaxes(0, 1).reshape(t, d.q_dim) @ R.dense(lw["wo"], precision)
    h = R.rms(x, lw["ffn_norm"], d.eps)

    def ffn(gateup, down):
        gu = h @ gateup
        return (jax.nn.silu(gu[:, :d.ffn]) * gu[:, d.ffn:]) @ down

    if not d.experts:
        return x + ffn(R.dense(lw["w_gateup"], precision),
                       R.dense(lw["w_down"], precision)), jnp.full((t,), jnp.inf)
    router = h @ lw["w_router"].astype(jnp.float32)
    # by how much the last expert chosen leads the first one left out: where
    # this is small, rounding anywhere upstream changes WHICH experts run
    ranked = jax.lax.top_k(router, d.top_k + 1)[0]
    margin = ranked[:, d.top_k - 1] - ranked[:, d.top_k]
    probs = jax.nn.softmax(router, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, d.top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], top_i].set(top_w)

    def expert(acc, e):  # every expert over every token, weighted; one at a time
        one = jax.tree.map(lambda a: a[e], (lw["we_gateup"], lw["we_down"]))
        y = ffn(R.dense(one[0], precision), R.dense(one[1], precision))
        return acc + gate[:, e][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(d.experts))
    return x + y, margin


def block(d: Dims, x, lw, layer: int, precision: str):
    """Layer `layer` of the reference; every layer of this family is alike."""
    return _block(d, x, lw, precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(d: Dims, x, final_norm, lm_head, precision: str):
    return R.rms(x, final_norm, d.eps) @ R.dense(lm_head, precision)


def head(d: Dims, x, top, precision: str):
    return _head(d, x, top["final_norm"], top["lm_head"], precision)


# -- the least bytes and operations ---------------------------------------------
# The cache is 2 x kv_dim bfloat16 values a row a layer; every expert is held here.


def attention_bytes(d: Dims) -> int:
    """Per layer: fused qkv and output projections, and the two norms."""
    return (matrix_bytes(d.hidden, d.q_dim + 2 * d.kv_dim)
            + matrix_bytes(d.q_dim, d.hidden) + 2 * 2 * d.hidden)


def ffn_bytes(d: Dims) -> int:
    """One FFN (one expert, for a mixture): gate|up and down."""
    return matrix_bytes(d.hidden, 2 * d.ffn) + matrix_bytes(d.ffn, d.hidden)


def decode_step_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least HBM bytes of one decode step for `active` slots whose contexts
    hold `context_rows` rows together."""
    if d.experts:
        ffn = (expected_distinct_experts(d.experts, d.top_k, active) * ffn_bytes(d)
               + 2 * d.hidden * d.experts)  # and the bfloat16 router
    else:
        ffn = ffn_bytes(d)
    weights = d.layers * (attention_bytes(d) + ffn)
    head = matrix_bytes(d.hidden, d.vocab) + 2 * d.hidden
    embed = active * d.hidden * 2
    kv = context_rows * d.layers * 2 * d.kv_dim * KV_BYTES
    return weights + head + embed + kv


def _row_ops(d: Dims) -> int:
    """Operations of the layers' matrices for one row."""
    return d.layers * 2 * (
        d.hidden * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.hidden
        + (d.top_k if d.experts else 1) * 3 * d.hidden * d.ffn
        + (d.hidden * d.experts if d.experts else 0)
    )


def decode_step_ops(d: Dims, active: float, context_rows: float) -> float:
    per_token = _row_ops(d) + 2 * d.hidden * d.vocab
    return active * per_token + context_rows * d.layers * 4 * d.q_dim


def prefill_ops(d: Dims, prompt_tokens: Sequence[int], cached_rows: Sequence[int]
                ) -> float:
    """Least operations to admit prompts of these lengths of which the first
    `cached_rows[i]` rows were already in the cache: the matrices for every
    new row, causal attention of each new row over what precedes it, and one
    output-head row per prompt."""
    per_row = _row_ops(d)
    total = 0.0
    for t, c in zip(prompt_tokens, cached_rows):
        new = t - c
        pairs = new * c + new * (new + 1) / 2  # (query, key) pairs under the mask
        total += new * per_row + pairs * d.layers * 4 * d.q_dim
    return total


def prefill_bytes(d: Dims, new_rows: float) -> float:
    """Least HBM bytes of one prefill program: the layers' weights once (for
    a mixture, the experts `new_rows` tokens are expected to select)."""
    if d.experts:
        ffn = expected_distinct_experts(d.experts, d.top_k, new_rows) * ffn_bytes(d)
    else:
        ffn = ffn_bytes(d)
    return d.layers * (attention_bytes(d) + ffn)
