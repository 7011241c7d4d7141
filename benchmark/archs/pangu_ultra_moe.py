"""openPangu-Ultra-MoE (`model_type` `pangu_ultra_moe`) as the benchmark
knows it: latent attention, sandwich norms, leading dense layers, then layers
of routed experts beside a shared one. A configuration file names this file
by `"arch": "pangu_ultra_moe"`; it imports nothing of the program.

The layer, one row `x` of the residual (`rms` = RMSNorm, every norm with a
learned weight):

- attention input `h = rms(x)`. Queries: `cq = rms(h W_dq)`, `q = cq W_uq`
  as heads of [nope | rope]; the rope part gets the rotary embedding. Keys and
  values: `h W_dkv = [c | k_r]`, `c = rms(c)`, `k_r` rotated and SHARED by all
  heads; `k_nope_i = c W_uk_i`, `v_i = c W_uv_i` per head. Scores
  `(q_nope_i . k_nope_j + q_rope_i . k_r_j) / sqrt(nope + rope)`, causal
  softmax, `o = concat_i(p_i v) W_o`. The reference computes this EXPANDED
  form only: no cache, no absorption.
- sandwich norm: `x += rms(attn(rms(x)))`, then `x += rms(mlp(rms(x)))`.
- the first `dense_layers` layers: `mlp(h) = (silu(h W_g) * (h W_u)) W_d`.
- the layers after them: `z = h W_r` over ALL `experts` (float32),
  `sigmoid(z)`, the `top_k` largest renormalised and scaled by `scale`;
  `mlp(h) = shared(h) + sum_{e picked, e held here} w_e expert_e(h)`. Of the
  `experts` the router ranks only `held` lie here, from `first` on (one chip's
  share of a deployment that divides each layer's experts: the configuration
  file states it); what the absent ones would add is left out, here and in
  the program alike. `margin` ranks all the router's logits.
- the multi-token-prediction layer is not part of the next-token logits and
  is not built.

Layout (the checkpoint format the configuration's `assumed` states): `w_dqkv`
is [W_dq | W_dkv] along columns, `w_uk` and `w_uv` are the two column groups
of the published W_ukv by head, `w_gateup` / `ws_gateup` / `we_gateup` are
[gate | up]. The leading dense layers are stacked under `lead_layers`, the
expert layers under `layers`. Layer `l` is a pure function of
`layer_key(seed, l)` and expert `e` of `fold_in(., e)`, so every share of a
layer holds the same bytes for an expert that the uncut layer does.

What the config.json does not state is the family's convention and listed in
the configuration file under `assumed`: sigmoid scoring, no group-limited
routing, no selection bias, rotary in the half-rotation layout of
`harness/reference.py` `rope`, norms on `cq` and `c`, softmax scale
`(nope + rope) ** -0.5`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from benchmark.harness import reference as R
from benchmark.harness import weights as W
from benchmark.harness.roofline import expected_distinct_experts, matrix_bytes

KV_BYTES = 2  # bfloat16 latent cache, as the configuration states
CONTROL = "int4"  # the precision below the int8 these weights are served in
FFN_CHUNK = 4608  # columns of a wide FFN the reference holds in float32 at a time


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    hidden: int
    dense_ffn: int
    expert_ffn: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    vocab: int
    experts: int  # the router's width, as published
    held: int  # of them, held here
    first: int  # the first one held here
    top_k: int
    shared: int
    scale: float
    norm_topk: bool
    rope_theta: float
    eps: float

    def routed(self, layer: int) -> bool:
        return layer >= self.dense_layers

    @property
    def row(self) -> int:
        """Values of one cache row a layer, as published."""
        return self.kv_rank + self.rope


def dims_of(config: dict) -> Dims:
    """Sizes from a configuration file (keys as in the model's config.json;
    `n_routed_experts` counts the experts HELD, `router_n_experts` and
    `first_routed_expert` state the router's width and the share beside it)."""
    held = int(config["n_routed_experts"])
    return Dims(
        layers=int(config["num_hidden_layers"]),
        dense_layers=int(config["first_k_dense_replace"]),
        hidden=int(config["hidden_size"]),
        dense_ffn=int(config["intermediate_size"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]), vocab=int(config["vocab_size"]),
        experts=int(config.get("router_n_experts", held)), held=held,
        first=int(config.get("first_routed_expert", 0)),
        top_k=int(config["num_experts_per_tok"]),
        shared=int(config["n_shared_experts"]),
        scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        rope_theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
    )


def context_length(config: dict) -> int:
    return int(config["max_position_embeddings"])


def model_fields(config: dict, context: int) -> Dict[str, object]:
    """The fields of the program's `ModelConfig` for this configuration."""
    d = dims_of(config)
    return dict(
        name=config["assumed"]["served_name"], vocab_size=d.vocab,
        hidden_size=d.hidden, intermediate_size=d.dense_ffn, num_layers=d.layers,
        num_heads=d.heads, num_kv_heads=d.heads, head_dim=d.nope + d.rope,
        max_context=context, rope_theta=d.rope_theta, rms_norm_eps=d.eps,
        num_experts=d.experts, num_experts_per_tok=d.top_k,
        moe_intermediate_size=d.expert_ffn, norm_topk_prob=d.norm_topk,
        experts_held=d.held, first_expert=d.first, moe_scoring="sigmoid",
        routed_scaling_factor=d.scale, n_shared_experts=d.shared,
        first_k_dense=d.dense_layers, q_lora_rank=d.q_rank,
        kv_lora_rank=d.kv_rank, qk_nope_head_dim=d.nope,
        qk_rope_head_dim=d.rope, v_head_dim=d.v_dim, sandwich_norm=True,
    )


def trace_markers(d: Dims) -> Dict[str, object]:
    """The latent decode kernel runs once per layer per decode step."""
    return {"decode_kernel": "paged_mla_decode_attention", "kernels_per_step": d.layers}


# -- weights: a layer's tree depends on its index --------------------------------


def _ffn_leaves(k_up, k_down, hidden: int, width: int, lead=()):
    return (W.qleaf(k_up, lead + (hidden, 2 * width)),
            W.qleaf(k_down, lead + (width, hidden)))


def layer_leaves(d: Dims, routed: bool, key) -> Dict[str, object]:
    """ONE layer's tree (no layer axis) from that layer's key."""
    ks = jax.random.split(key, 16)
    out = {
        "attn_norm": W.norm(ks[0], d.hidden),
        "q_a_norm": W.norm(ks[1], d.q_rank),
        "kv_a_norm": W.norm(ks[2], d.kv_rank),
        "post_attn_norm": W.norm(ks[3], d.hidden),
        "ffn_norm": W.norm(ks[4], d.hidden),
        "post_ffn_norm": W.norm(ks[5], d.hidden),
        "w_dqkv": W.qleaf(ks[6], (d.hidden, d.q_rank + d.kv_rank + d.rope)),
        "w_uq": W.qleaf(ks[7], (d.q_rank, d.heads * (d.nope + d.rope))),
        "w_uk": W.qleaf(ks[8], (d.kv_rank, d.heads * d.nope)),
        "w_uv": W.qleaf(ks[9], (d.kv_rank, d.heads * d.v_dim)),
        "wo": W.qleaf(ks[10], (d.heads * d.v_dim, d.hidden)),
    }
    if not routed:
        out["w_gateup"], out["w_down"] = _ffn_leaves(
            ks[11], ks[12], d.hidden, d.dense_ffn)
        return out
    out["w_router"] = W.small(ks[11], (d.hidden, d.experts))
    out["ws_gateup"], out["ws_down"] = _ffn_leaves(
        ks[12], ks[13], d.hidden, d.shared * d.expert_ffn)
    out["we_gateup"], out["we_down"] = jax.vmap(lambda e: _ffn_leaves(
        *jax.random.split(jax.random.fold_in(ks[14], e)), d.hidden, d.expert_ffn)
    )(d.first + jnp.arange(d.held))
    return out


def top_leaves(d: Dims, k_embed, k_norm, k_head) -> Dict[str, object]:
    return {"embed": W.small(k_embed, (d.vocab, d.hidden)),
            "final_norm": W.norm(k_norm, d.hidden),
            "lm_head": W.qleaf(k_head, (d.hidden, d.vocab))}


@functools.partial(jax.jit, static_argnums=(0,))
def _build(d: Dims, seed_lo, seed_hi):
    def stack(routed: bool, lo: int, hi: int):
        return jax.lax.map(
            lambda l: layer_leaves(d, routed, W.layer_key(seed_lo, seed_hi, l)),
            jnp.arange(lo, hi))

    out = {"layers": stack(True, d.dense_layers, d.layers),
           **top_leaves(d, *W.roots(seed_lo, seed_hi)[1:])}
    if d.dense_layers:
        out["lead_layers"] = stack(False, 0, d.dense_layers)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(d: Dims, routed: bool, seed_lo, seed_hi, layer):
    return layer_leaves(d, routed, W.layer_key(seed_lo, seed_hi, layer))


def build_params(d: Dims, seed: int):
    """The whole serving tree, on the device: the leading dense layers
    stacked under `lead_layers`, the expert layers under `layers`."""
    return _build(d, *W.split_seed(seed))


def build_layer(d: Dims, seed: int, layer: int):
    """Layer `layer` of the same tree, alone (for the reference)."""
    return _one_layer(d, d.routed(int(layer)), *W.split_seed(seed), jnp.int32(layer))


def build_top(d: Dims, seed: int):
    return W.build_stack_top(top_leaves, d, seed)


# -- the plain reference ---------------------------------------------------------


def embed(top, ids):
    return top["embed"][ids].astype(jnp.float32)


def _swiglu(h, gateup, down, width: int, precision: str):
    """(silu(h W_g) * (h W_u)) W_d, a FFN_CHUNK of the width at a time so a
    wide FFN's float32 matrices never exist whole."""
    out = jnp.zeros_like(h)
    for lo in range(0, width, FFN_CHUNK):
        hi = min(lo + FFN_CHUNK, width)
        g = R.dense({"q": gateup["q"][:, lo:hi], "s": gateup["s"][:, lo:hi]}, precision)
        u = R.dense({"q": gateup["q"][:, width + lo:width + hi],
                     "s": gateup["s"][:, width + lo:width + hi]}, precision)
        # (a lower precision's groups run along rows, and FFN_CHUNK is whole
        # groups of them: a chunk re-quantizes as the whole matrix would)
        dn = R.dense({"q": down["q"][lo:hi], "s": down["s"]}, precision)
        out = out + (jax.nn.silu(h @ g) * (h @ u)) @ dn
    return out


def attention(d: Dims, x, lw, precision: str):
    """rms_post(attn(rms_in(x))) for one sequence x [T, E], expanded form, a
    head at a time so only one [T, T] score matrix exists."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = R.rms(x, lw["attn_norm"], d.eps)
    down = h @ R.dense(lw["w_dqkv"], precision)
    cq = R.rms(down[:, :d.q_rank], lw["q_a_norm"], d.eps)
    c = R.rms(down[:, d.q_rank:d.q_rank + d.kv_rank], lw["kv_a_norm"], d.eps)
    k_r = R.rope(down[:, None, d.q_rank + d.kv_rank:], pos, d.rope_theta)[:, 0]
    heads = lambda leaf, w: R.dense(leaf, precision).reshape(  # noqa: E731
        -1, d.heads, w).swapaxes(0, 1)
    mask = pos[:, None] >= pos[None, :]
    scale = 1.0 / jnp.sqrt(jnp.float32(d.nope + d.rope))

    def head(w):
        w_uq, w_uk, w_uv = w  # [q_rank, nope+rope], [kv_rank, nope], [kv_rank, v]
        q = cq @ w_uq
        q_r = R.rope(q[:, None, d.nope:], pos, d.rope_theta)[:, 0]
        s = (q[:, :d.nope] @ (c @ w_uk).T + q_r @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ (c @ w_uv)

    att = jax.lax.map(head, (heads(lw["w_uq"], d.nope + d.rope),
                             heads(lw["w_uk"], d.nope), heads(lw["w_uv"], d.v_dim)))
    out = att.swapaxes(0, 1).reshape(t, d.heads * d.v_dim) @ R.dense(lw["wo"], precision)
    return R.rms(out, lw["post_attn_norm"], d.eps)


def moe_parts(d: Dims, h, lw, precision: str):
    """(what the experts HELD HERE add, what the shared expert adds, the
    router's margin) for normed rows h [T, E]. The router ranks all
    `d.experts`; the margin is over them, whatever share is held."""
    logits = h @ lw["w_router"].astype(jnp.float32)
    ranked = jax.lax.top_k(logits, d.top_k + 1)[0]
    margin = ranked[:, d.top_k - 1] - ranked[:, d.top_k]
    top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), d.top_k)
    if d.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = d.scale * top_w
    gate = jnp.zeros_like(logits).at[jnp.arange(h.shape[0])[:, None], top_i].set(top_w)

    def expert(acc, j):  # every held expert over every token, weighted
        one = jax.tree.map(lambda a: a[j], (lw["we_gateup"], lw["we_down"]))
        y = _swiglu(h, *one, d.expert_ffn, precision)
        return acc + gate[:, d.first + j][:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(d.held))
    shared = _swiglu(h, lw["ws_gateup"], lw["ws_down"], d.shared * d.expert_ffn,
                     precision)
    return routed, shared, margin


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _block(d: Dims, x, lw, routed: bool, precision: str):
    x = x + attention(d, x, lw, precision)
    h = R.rms(x, lw["ffn_norm"], d.eps)
    if not routed:
        y = _swiglu(h, lw["w_gateup"], lw["w_down"], d.dense_ffn, precision)
        margin = jnp.full((x.shape[0],), jnp.inf)
    else:
        routed_part, shared, margin = moe_parts(d, h, lw, precision)
        y = routed_part + shared
    return x + R.rms(y, lw["post_ffn_norm"], d.eps), margin


def block(d: Dims, x, lw, layer: int, precision: str):
    """Layer `layer` of the reference: dense below `d.dense_layers`."""
    return _block(d, x, lw, d.routed(int(layer)), precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(d: Dims, x, final_norm, lm_head, precision: str):
    return R.rms(x, final_norm, d.eps) @ R.dense(lm_head, precision)


def head(d: Dims, x, top, precision: str):
    return _head(d, x, top["final_norm"], top["lm_head"], precision)


# -- the least bytes and operations ---------------------------------------------
# A cache row is `d.row` = kv_rank + rope bfloat16 values a layer, as published
# (the program pads the rotary part to a lane tile: that shows as a lower share,
# not as a smaller count). Of a layer's experts the `held` lie here.


def attention_bytes(d: Dims) -> int:
    """Per layer: the five attention matrices and the six norms."""
    return (matrix_bytes(d.hidden, d.q_rank + d.row)
            + matrix_bytes(d.q_rank, d.heads * (d.nope + d.rope))
            + matrix_bytes(d.kv_rank, d.heads * (d.nope + d.v_dim))
            + matrix_bytes(d.heads * d.v_dim, d.hidden)
            + 2 * (4 * d.hidden + d.q_rank + d.kv_rank))


def ffn_bytes(d: Dims, width: int) -> int:
    return matrix_bytes(d.hidden, 2 * width) + matrix_bytes(width, d.hidden)


def held_touched(d: Dims, tokens: float) -> float:
    """Held experts that `tokens` tokens are expected to touch, each picking
    `top_k` of the router's `experts` uniformly."""
    return d.held / d.experts * expected_distinct_experts(d.experts, d.top_k, tokens)


def layers_bytes(d: Dims, tokens: float) -> float:
    """The layers' weights once, of the routed experts those `tokens` touch."""
    expert_layer = (attention_bytes(d) + ffn_bytes(d, d.shared * d.expert_ffn)
                    + 2 * d.hidden * d.experts  # the bfloat16 router
                    + held_touched(d, tokens) * ffn_bytes(d, d.expert_ffn))
    dense_layer = attention_bytes(d) + ffn_bytes(d, d.dense_ffn)
    return d.dense_layers * dense_layer + (d.layers - d.dense_layers) * expert_layer


def decode_step_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least HBM bytes of one decode step for `active` slots whose contexts
    hold `context_rows` rows together."""
    head = matrix_bytes(d.hidden, d.vocab) + 2 * d.hidden
    return (layers_bytes(d, active) + head + active * d.hidden * 2
            + mla_decode_bytes(d, active, context_rows))


def _row_matrix_ops(d: Dims) -> float:
    """Operations of the layers' matrices for one row. Decode carries the
    query into the latent space and the result out of it (absorbed); prefill
    expands the new row's latent into its heads' keys and values: the same
    count either way."""
    up = d.heads * d.kv_rank * (d.nope + d.v_dim)
    attn = (d.hidden * (d.q_rank + d.row) + d.q_rank * d.heads * (d.nope + d.rope)
            + up + d.heads * d.v_dim * d.hidden)
    expert_layer = (3 * d.hidden * d.shared * d.expert_ffn + d.hidden * d.experts
                    + d.top_k * d.held / d.experts * 3 * d.hidden * d.expert_ffn)
    return 2 * (d.layers * attn + d.dense_layers * 3 * d.hidden * d.dense_ffn
                + (d.layers - d.dense_layers) * expert_layer)


def decode_step_ops(d: Dims, active: float, context_rows: float) -> float:
    per_token = _row_matrix_ops(d) + 2 * d.hidden * d.vocab
    return active * per_token + mla_decode_ops(d, active, context_rows)


def mla_decode_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least bytes of a step's `d.layers` latent decode kernels: the cache
    rows once at the published row, queries in and latent results out."""
    io = active * d.heads * (d.row + d.kv_rank) * 2
    return d.layers * (context_rows * d.row * KV_BYTES + io)


def mla_decode_ops(d: Dims, active: float, context_rows: float) -> float:
    """Operations of a step's latent decode kernels, absorbed form: every
    head scores a row's `d.row` values and weighs its `kv_rank` latent."""
    del active
    return context_rows * d.layers * d.heads * (d.row + d.kv_rank) * 2


def prefill_ops(d: Dims, prompt_tokens: Sequence[int], cached_rows: Sequence[int]
                ) -> float:
    """Least operations to admit prompts of these lengths of which the first
    `cached_rows[i]` rows were already in the cache: the matrices for every
    new row, causal attention of each new row over what precedes it in the
    expanded form's head sizes, and one output-head row per prompt. Expanding
    the CACHED rows' latents again is the program's choice of form, not the
    algorithm's need, and is not counted."""
    per_row = _row_matrix_ops(d)
    total = 0.0
    for t, c in zip(prompt_tokens, cached_rows):
        new = t - c
        pairs = new * c + new * (new + 1) / 2
        total += new * per_row + pairs * d.layers * d.heads * 2 * (
            d.nope + d.rope + d.v_dim)
    return total


def prefill_bytes(d: Dims, new_rows: float) -> float:
    """Least HBM bytes of one prefill program: the layers' weights once."""
    return layers_bytes(d, new_rows)
