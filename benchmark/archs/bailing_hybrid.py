"""Ling-3.0 (`model_type` `bailing_hybrid`) as the benchmark knows it: linear
attention (Kimi Delta Attention, KDA) layers and latent-attention (MLA)
layers five to one, leading dense layers, then layers of routed experts
chosen by groups beside a shared one. A configuration file names this file by
`"arch": "bailing_hybrid"`; it imports nothing of the program.

Kind of layer `l` (the configuration's `layer_types`, written out): MLA where
(published l + 1) % `layer_group_size` == 0, else KDA. All norms are RMSNorm
with a learned weight, no bias anywhere. One row `x` of the residual:

KDA layer, H heads of K key and K value channels (`head_dim`), `h = rms(x)`:
- `[uq | uk | uv | a | z] = h W_in` (five column groups of H K each).
- short convolution, depthwise and causal over uq | uk | uv, then SiLU:
  `c_t = silu(sum_j w_j * u_{t-taps+1+j})`, rows before the start zero.
- q, k = l2(c^q), l2(c^k) a head; q times K^-1/2; v = c^v.
- `beta = sigmoid(h W_beta)` [H]; decay a channel `log alpha = lower_bound *
  sigmoid(exp(A_head) * (a + b))`, so alpha in (e^lower_bound, 1).
- state, float32, from zero, [K, K] a head: `S' = (I - beta k k^T) Diag(alpha)
  S + beta k v^T`; `o = S'^T q`. The reference runs it ROW BY ROW under
  `lax.scan`: no chunking, no cache.
- `y = (rms_head(o) * sigmoid(z)) W_o`; `x += y`.

MLA layer, `h = rms(x)`: `q = h W_q` as heads of [nope | rope] (no bottleneck:
`q_lora_rank` null); `[c | r] = h W_dkv`, `c = rms(c)`; each query head's
nope + rope channels and the shared rotary key `r` are normed (learned
weights) before rotation; rotary on INTERLEAVED pairs (2i, 2i + 1);
`k_nope_i = c W_uk_i`, `v_i = c W_uv_i`; scores `(q_nope . k_nope + q_rope .
r) / sqrt(nope + rope)`, causal softmax; each head's output times
`sigmoid(h W_z)_head`; `y = concat(a) W_o`; `x += y`. Expanded form only.

FFN: `h2 = rms(x)`; the first `dense_layers` layers SwiGLU of `dense_ffn`;
after them `s = sigmoid(h2 W_r)` over ALL `experts` in float32; the choice is
made on `s + bias`: `groups` groups of consecutive experts, a group's score
the sum of its two largest biased scores, the `top_groups` best groups stay,
the `top_k` largest biased scores among their experts are chosen; weights
the UNBIASED `s` of the chosen over their sum, times `scale`;
`y = sum_e w_e expert_e(h2) + shared(h2)`. Of the `experts` only `held` lie
here, from `first` on (one chip's share: the configuration file states the
deployment); what the absent ones would add is left out, here and in the
program alike. `margin` is the least, in a logit's worth (4 x a sigmoid
score's gap), of the last chosen against the first left out among the kept
groups' biased scores and of the last kept group's score against the first
dropped one's. A nonzero swiglu limit is not built (the config does not say
what it clamps); the multi-token-prediction layer is not built.

Layout (the configuration's `assumed` states it): `kda_in` = [W_q | W_k | W_v
| W_f | W_g] by columns, `kda_conv` [taps, 3 H K], `w_dqkv` = [W_q | W_dkv],
`w_uk` / `w_uv` the two column groups of W_kv_b by head, [gate | up] fused.
The leading dense layers are stacked under `lead_layers`; of the layers
after them what every layer has (norms, router, experts) is stacked over all
of them under `layers`, and a kind's own mixer leaves over that kind's layers
under `layers.by_kind.<kind>`. Layer `l` is a pure function of
`layer_key(seed, l)` and expert `e` of `fold_in(., e)`.

`CONTROL` is "int4". One more control is this file's own (a `check.control`
may name it): "state_bf16" rounds the KDA state to bfloat16 after every row
and leaves the matrices at float32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.harness import reference as R
from benchmark.harness import weights as W
from benchmark.harness.roofline import expected_distinct_experts, matrix_bytes

KV_BYTES = 2  # bfloat16 latent cache
STATE_BYTES = 4  # float32 recurrent state
CONTROL = "int4"
STATE_CONTROLS = ("state_bf16",)
# An answer of this cell is up to 1,536 tokens and `harness/reference.py`
# keeps KEEP = 256 logit rows a sequence ("the longest answer fits", of the
# cells before this one); it reads the constant at each call, so the one
# process that loads this file compares whole answers. A `benchmark` PR
# should make it a parameter of the configuration's `check` (PERF.md 7).
R.KEEP = max(R.KEEP, 2048)


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    layer_types: Tuple[str, ...]
    hidden: int
    dense_ffn: int
    expert_ffn: int
    shared_ffn: int
    heads: int
    head_dim: int  # KDA's key and value channels a head
    conv: int
    lower_bound: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    vocab: int
    experts: int  # the router's width, as published
    held: int
    first: int
    top_k: int
    groups: int
    top_groups: int
    scale: float
    norm_topk: bool
    rope_theta: float
    eps: float

    def routed(self, layer: int) -> bool:
        return layer >= self.dense_layers

    def kind(self, layer: int) -> str:
        return self.layer_types[layer]

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_types)

    @property
    def row(self) -> int:
        """Values of one latent cache row a layer, as published."""
        return self.kv_rank + self.rope

    @property
    def kda_width(self) -> int:
        return self.heads * self.head_dim


def dims_of(config: dict) -> Dims:
    """Sizes from a configuration file (keys as in the model's config.json;
    `num_experts` counts the experts HELD, `router_n_experts` and
    `first_routed_expert` state the router's width and the share beside it)."""
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(config.get(key, ())):
            raise ValueError(
                f"{key} has a nonzero entry: the config does not say what the "
                "limit clamps, and nothing is guessed")
    held = int(config["num_experts"])
    return Dims(
        layers=int(config["num_hidden_layers"]),
        dense_layers=int(config["first_k_dense_replace"]),
        layer_types=tuple(config["layer_types"]),
        hidden=int(config["hidden_size"]),
        dense_ffn=int(config["intermediate_size"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config["num_shared_experts"])
        * int(config["moe_shared_expert_intermediate_size"]),
        heads=int(config["num_attention_heads"]), head_dim=int(config["head_dim"]),
        conv=int(config["short_conv_kernel_size"]),
        lower_bound=float(config["kda_lower_bound"]),
        kv_rank=int(config["kv_lora_rank"]), nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]), v_dim=int(config["v_head_dim"]),
        vocab=int(config["vocab_size"]),
        experts=int(config.get("router_n_experts", held)), held=held,
        first=int(config.get("first_routed_expert", 0)),
        top_k=int(config["num_experts_per_tok"]),
        groups=int(config["n_group"]), top_groups=int(config["topk_group"]),
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
        routed_scaling_factor=d.scale,
        n_shared_experts=d.shared_ffn // d.expert_ffn,
        first_k_dense=d.dense_layers, q_lora_rank=0, kv_lora_rank=d.kv_rank,
        qk_nope_head_dim=d.nope, qk_rope_head_dim=d.rope, v_head_dim=d.v_dim,
        layer_types=list(d.layer_types), kda_heads=d.heads,
        kda_key_dim=d.head_dim, kda_value_dim=d.head_dim, kda_conv=d.conv,
        kda_lower_bound=d.lower_bound, latent_qk_norm=True,
        rope_interleave=True, attn_head_gate=True, n_group=d.groups,
        topk_group=d.top_groups,
    )


def trace_markers(d: Dims) -> Dict[str, object]:
    """The latent decode kernel runs once per MLA layer per decode step."""
    return {"decode_kernel": "paged_mla_decode_attention",
            "kernels_per_step": d.count("mla")}


# -- weights: a layer's tree depends on its index --------------------------------


def _ffn_leaves(k_up, k_down, hidden: int, width: int):
    return W.qleaf(k_up, (hidden, 2 * width)), W.qleaf(k_down, (width, hidden))


def _f32(key, shape, scale: float, offset: float = 0.0):
    return offset + W.raw_bytes(key, shape).astype(jnp.float32) * (scale / W.INT8_STD)


def mixer_leaves(d: Dims, kind: str, key) -> Dict[str, object]:
    """The leaves of ONE layer's mixer (no layer axis) from that layer's key."""
    ks = jax.random.split(jax.random.fold_in(key, 1), 12)
    hk = d.kda_width
    if kind == "kda":
        return {
            "kda_in": W.qleaf(ks[0], (d.hidden, 5 * hk)),
            "kda_beta": W.small(ks[1], (d.hidden, d.heads)),
            # taps of about a half: the convolution neither kills nor blows up
            "kda_conv": W.small(ks[2], (d.conv, 3 * hk), 0.5 / W.INT8_STD),
            # log alpha spreads over (lower_bound, 0): exp(A) about 1, b in
            # (-5, -1) against a pre-activation of about unit spread, so that
            # a channel forgets in anything from a few rows to never
            "kda_A": _f32(ks[3], (d.heads,), 0.25),
            "kda_b": _f32(ks[4], (hk,), 1.15, -3.0),
            "kda_onorm": W.norm(ks[5], d.head_dim),
            "wo": W.qleaf(ks[6], (hk, d.hidden)),
        }
    return {
        "w_dqkv": W.qleaf(ks[0], (d.hidden, d.heads * (d.nope + d.rope) + d.row)),
        "q_head_norm": W.norm(ks[1], d.nope + d.rope),
        "k_rope_norm": W.norm(ks[2], d.rope),
        "kv_a_norm": W.norm(ks[3], d.kv_rank),
        "w_uk": W.qleaf(ks[4], (d.kv_rank, d.heads * d.nope)),
        "w_uv": W.qleaf(ks[5], (d.kv_rank, d.heads * d.v_dim)),
        "w_hgate": W.small(ks[7], (d.hidden, d.heads)),
        "wo": W.qleaf(ks[6], (d.heads * d.v_dim, d.hidden)),
    }


def common_leaves(d: Dims, routed: bool, key) -> Dict[str, object]:
    """What every layer has whatever its mixer: the two norms and the FFN."""
    ks = jax.random.split(jax.random.fold_in(key, 0), 8)
    out = {"attn_norm": W.norm(ks[0], d.hidden), "ffn_norm": W.norm(ks[1], d.hidden)}
    if not routed:
        out["w_gateup"], out["w_down"] = _ffn_leaves(ks[2], ks[3], d.hidden, d.dense_ffn)
        return out
    out["w_router"] = W.small(ks[2], (d.hidden, d.experts))
    # about N(0, 0.01): enough to move the choice, and not the weights
    out["router_bias"] = _f32(ks[3], (d.experts,), 0.01)
    out["ws_gateup"], out["ws_down"] = _ffn_leaves(ks[4], ks[5], d.hidden, d.shared_ffn)
    out["we_gateup"], out["we_down"] = jax.vmap(lambda e: _ffn_leaves(
        *jax.random.split(jax.random.fold_in(ks[6], e)), d.hidden, d.expert_ffn)
    )(d.first + jnp.arange(d.held))
    return out


def top_leaves(d: Dims, k_embed, k_norm, k_head) -> Dict[str, object]:
    return {"embed": W.small(k_embed, (d.vocab, d.hidden)),
            "final_norm": W.norm(k_norm, d.hidden),
            "lm_head": W.qleaf(k_head, (d.hidden, d.vocab))}


@functools.partial(jax.jit, static_argnums=(0,))
def _build(d: Dims, seed_lo, seed_hi):
    key = lambda l: W.layer_key(seed_lo, seed_hi, l)  # noqa: E731
    after = range(d.dense_layers, d.layers)
    by_kind = {
        kind: jax.lax.map(
            lambda l, kind=kind: mixer_leaves(d, kind, key(l)),
            jnp.asarray([l for l in after if d.kind(l) == kind], jnp.int32))
        for kind in dict.fromkeys(d.kind(l) for l in after)
    }
    out = {
        "layers": {
            **jax.lax.map(lambda l: common_leaves(d, True, key(l)),
                          jnp.arange(d.dense_layers, d.layers)),
            "by_kind": by_kind,
        },
        **top_leaves(d, *W.roots(seed_lo, seed_hi)[1:]),
    }
    if d.dense_layers:
        (kind,) = set(d.layer_types[:d.dense_layers])
        out["lead_layers"] = jax.lax.map(
            lambda l: {**common_leaves(d, False, key(l)),
                       **mixer_leaves(d, kind, key(l))},
            jnp.arange(d.dense_layers))
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _one_layer(d: Dims, routed: bool, kind: str, seed_lo, seed_hi, layer):
    key = W.layer_key(seed_lo, seed_hi, layer)
    return {**common_leaves(d, routed, key), **mixer_leaves(d, kind, key)}


def build_params(d: Dims, seed: int):
    """The whole serving tree, on the device (the module's header: layout)."""
    return _build(d, *W.split_seed(seed))


def build_layer(d: Dims, seed: int, layer: int):
    """Layer `layer` of the same tree, alone and flat (for the reference)."""
    layer = int(layer)
    return _one_layer(d, d.routed(layer), d.kind(layer), *W.split_seed(seed),
                      jnp.int32(layer))


def build_top(d: Dims, seed: int):
    return W.build_stack_top(top_leaves, d, seed)


# -- the plain reference ---------------------------------------------------------


def embed(top, ids):
    return top["embed"][ids].astype(jnp.float32)


def _parts(precision: str):
    """(the matrices' precision, which control of the state or None)."""
    return ("float32", precision) if precision in STATE_CONTROLS else (precision, None)


def _swiglu(h, gateup, down, width: int, precision: str):
    g = R.dense({"q": gateup["q"][:, :width], "s": gateup["s"][:, :width]}, precision)
    u = R.dense({"q": gateup["q"][:, width:], "s": gateup["s"][:, width:]}, precision)
    return (jax.nn.silu(h @ g) * (h @ u)) @ R.dense(down, precision)


def kda(d: Dims, x, lw, precision: str, variant=None):
    """The KDA mixer's output for one sequence x [T, E], the recurrence row
    by row from a zero state."""
    t, hh, kk = x.shape[0], d.heads, d.head_dim
    f32 = jnp.float32
    h = R.rms(x, lw["attn_norm"], d.eps)
    wide = h @ R.dense(lw["kda_in"], precision)
    u, a, z = wide[:, :3 * hh * kk], wide[:, 3 * hh * kk:4 * hh * kk], wide[:, 4 * hh * kk:]
    taps = lw["kda_conv"].astype(f32)
    padded = jnp.concatenate([jnp.zeros((d.conv - 1, u.shape[1]), f32), u])
    c = jax.nn.silu(sum(taps[j] * padded[j:j + t] for j in range(d.conv)))
    q, k, v = (c[:, i * hh * kk:(i + 1) * hh * kk].reshape(t, hh, kk) for i in range(3))
    l2 = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = l2(q) * kk ** -0.5, l2(k)
    beta = jax.nn.sigmoid(h @ lw["kda_beta"].astype(f32))  # [T, H]
    rate = jnp.exp(lw["kda_A"])[:, None]
    log_alpha = d.lower_bound * jax.nn.sigmoid(
        rate * (a.reshape(t, hh, kk) + lw["kda_b"].reshape(hh, kk)))
    keep = (lambda s: s.astype(jnp.bfloat16).astype(f32)) if variant == "state_bf16" \
        else (lambda s: s)

    def row(s, r):
        q, k, v, g, b = r
        s = s * jnp.exp(g)[..., None]
        s = s + (b[:, None] * k)[..., None] * (
            v - jnp.einsum("hkv,hk->hv", s, k))[:, None, :]
        s = keep(s)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    _, o = jax.lax.scan(row, jnp.zeros((hh, kk, kk), f32), (q, k, v, log_alpha, beta))
    o = R.rms(o, lw["kda_onorm"], d.eps).reshape(t, hh * kk)
    return (o * jax.nn.sigmoid(z)) @ R.dense(lw["wo"], precision)


def rope_pairs(x, positions, theta):
    """Rotary embedding over interleaved pairs (2i, 2i + 1); x [T, heads, D]."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def mla(d: Dims, x, lw, precision: str):
    """The MLA mixer's output for one sequence x [T, E], expanded form, a head
    at a time so only one [T, T] score matrix exists."""
    t = x.shape[0]
    pos = jnp.arange(t)
    qw = d.heads * (d.nope + d.rope)
    h = R.rms(x, lw["attn_norm"], d.eps)
    down = h @ R.dense(lw["w_dqkv"], precision)
    q = R.rms(down[:, :qw].reshape(t, d.heads, d.nope + d.rope),
              lw["q_head_norm"], d.eps)
    c = R.rms(down[:, qw:qw + d.kv_rank], lw["kv_a_norm"], d.eps)
    k_r = R.rms(down[:, qw + d.kv_rank:], lw["k_rope_norm"], d.eps)
    k_r = rope_pairs(k_r[:, None, :], pos, d.rope_theta)[:, 0]
    q_r = rope_pairs(q[..., d.nope:], pos, d.rope_theta)
    heads = lambda leaf, w: R.dense(leaf, precision).reshape(  # noqa: E731
        -1, d.heads, w).swapaxes(0, 1)
    mask = pos[:, None] >= pos[None, :]
    scale = 1.0 / jnp.sqrt(jnp.float32(d.nope + d.rope))

    def head(w):
        q_n, q_rot, w_uk, w_uv = w
        s = (q_n @ (c @ w_uk).T + q_rot @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ (c @ w_uv)

    att = jax.lax.map(head, (q[..., :d.nope].swapaxes(0, 1), q_r.swapaxes(0, 1),
                             heads(lw["w_uk"], d.nope), heads(lw["w_uv"], d.v_dim)))
    gate = jax.nn.sigmoid(h @ lw["w_hgate"].astype(jnp.float32))  # [T, H]
    att = att.swapaxes(0, 1) * gate[..., None]
    return att.reshape(t, d.heads * d.v_dim) @ R.dense(lw["wo"], precision)


def choose(d: Dims, scores, bias):
    """(the chosen experts [T, top_k], the margin [T]) of unbiased sigmoid
    scores [T, experts] under the group-limited choice on scores + bias."""
    t = scores.shape[0]
    biased = scores + bias
    by_group = biased.reshape(t, d.groups, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], -1)
    ranked_g, best = jax.lax.top_k(group_score, min(d.top_groups + 1, d.groups))
    kept = jnp.any(best[:, :d.top_groups, None] == jnp.arange(d.groups), axis=1)
    among = jnp.where(kept[..., None], by_group, -jnp.inf).reshape(t, -1)
    ranked, idx = jax.lax.top_k(among, d.top_k + 1)
    margin = ranked[:, d.top_k - 1] - ranked[:, d.top_k]
    if d.top_groups < d.groups:
        margin = jnp.minimum(margin, ranked_g[:, d.top_groups - 1] - ranked_g[:, d.top_groups])
    return idx[:, :d.top_k], 4.0 * margin


def moe_parts(d: Dims, h, lw, precision: str):
    """(what the experts HELD HERE add, what the shared expert adds, the
    router's margin) for normed rows h [T, E]."""
    scores = jax.nn.sigmoid(h @ lw["w_router"].astype(jnp.float32))
    top_i, margin = choose(d, scores, lw["router_bias"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if d.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = d.scale * top_w
    gate = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], top_i].set(top_w)

    def expert(acc, j):  # every held expert over every token, weighted
        one = jax.tree.map(lambda a: a[j], (lw["we_gateup"], lw["we_down"]))
        y = _swiglu(h, *one, d.expert_ffn, precision)
        return acc + gate[:, d.first + j][:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(d.held))
    shared = _swiglu(h, lw["ws_gateup"], lw["ws_down"], d.shared_ffn, precision)
    return routed, shared, margin


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _block(d: Dims, x, lw, routed: bool, kind: str, precision: str):
    matrices, variant = _parts(precision)
    x = x + (kda(d, x, lw, matrices, variant) if kind == "kda"
             else mla(d, x, lw, matrices))
    h = R.rms(x, lw["ffn_norm"], d.eps)
    if not routed:
        return x + _swiglu(h, lw["w_gateup"], lw["w_down"], d.dense_ffn, matrices), \
            jnp.full((x.shape[0],), jnp.inf)
    routed_part, shared, margin = moe_parts(d, h, lw, matrices)
    return x + routed_part + shared, margin


def block(d: Dims, x, lw, layer: int, precision: str):
    """Layer `layer` of the reference: its kind and its FFN by its index."""
    layer = int(layer)
    return _block(d, x, lw, d.routed(layer), d.kind(layer), precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(d: Dims, x, final_norm, lm_head, precision: str):
    return R.rms(x, final_norm, d.eps) @ R.dense(lm_head, _parts(precision)[0])


def head(d: Dims, x, top, precision: str):
    return _head(d, x, top["final_norm"], top["lm_head"], precision)


# -- the least bytes and operations ---------------------------------------------
# A latent cache row is `d.row` bfloat16 values an MLA layer, as published. A
# KDA layer keeps one float32 state [heads, K, K] a slot: a step reads and
# writes it once, and so does a chunk, whatever implements either.


def kda_matrix_bytes(d: Dims) -> int:
    hk = d.kda_width
    return (matrix_bytes(d.hidden, 5 * hk) + matrix_bytes(hk, d.hidden)
            + 2 * (d.hidden * d.heads + d.conv * 3 * hk + d.head_dim + 2 * d.hidden)
            + 4 * (d.heads + hk))


def mla_matrix_bytes(d: Dims) -> int:
    return (matrix_bytes(d.hidden, d.heads * (d.nope + d.rope) + d.row)
            + matrix_bytes(d.kv_rank, d.heads * (d.nope + d.v_dim))
            + matrix_bytes(d.heads * d.v_dim, d.hidden)
            + 2 * (2 * d.hidden + d.nope + 2 * d.rope + d.kv_rank + d.hidden * d.heads))


def ffn_bytes(d: Dims, width: int) -> int:
    return matrix_bytes(d.hidden, 2 * width) + matrix_bytes(width, d.hidden)


def held_touched(d: Dims, tokens: float) -> float:
    """Held experts that `tokens` tokens are expected to touch. A token's
    choice is `top_k` of the `top_groups` kept groups' experts; over seeded
    weights every expert is as likely as any other."""
    return d.held / d.experts * expected_distinct_experts(d.experts, d.top_k, tokens)


def layers_bytes(d: Dims, tokens: float) -> float:
    """The layers' weights once, of the routed experts those `tokens` touch."""
    mixers = d.count("kda") * kda_matrix_bytes(d) + d.count("mla") * mla_matrix_bytes(d)
    expert_ffn = (ffn_bytes(d, d.shared_ffn) + 2 * d.hidden * d.experts + 4 * d.experts
                  + held_touched(d, tokens) * ffn_bytes(d, d.expert_ffn))
    return (mixers + d.dense_layers * ffn_bytes(d, d.dense_ffn)
            + (d.layers - d.dense_layers) * expert_ffn)


def kda_state_bytes(d: Dims) -> int:
    """One slot's recurrent state of one KDA layer."""
    return d.heads * d.head_dim * d.head_dim * STATE_BYTES


def kda_step_bytes(d: Dims, active: float) -> float:
    """Least bytes of a decode step's recurrences: each live slot's state of
    each KDA layer read once and written once, and the row's q, k, v, decay,
    beta in and o out."""
    io = d.heads * (5 * d.head_dim + 1) * 4
    return d.count("kda") * active * (2 * kda_state_bytes(d) + io)


def kda_row_ops(d: Dims) -> float:
    """Operations of ONE row's recurrence in one KDA layer: the decay, the
    state's reading by k, the rank-one update and its reading by q."""
    return d.heads * 7.0 * d.head_dim * d.head_dim


def kda_step_ops(d: Dims, active: float) -> float:
    return d.count("kda") * active * kda_row_ops(d)


def kda_chunk_ops(d: Dims, rows: float) -> float:
    """Operations of the recurrence over a chunk of `rows` rows, all KDA
    layers: the count of the mathematics row by row, which a chunked form
    may exceed and may not undercut."""
    return d.count("kda") * rows * kda_row_ops(d)


def kda_chunk_bytes(d: Dims, rows: float) -> float:
    """Least bytes of the same: the slot's state in and out once a layer, and
    each row's q, k, v, decay, beta in and o out."""
    io = d.heads * (5 * d.head_dim + 1) * 4
    return d.count("kda") * (2 * kda_state_bytes(d) + rows * io)


def decode_step_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least HBM bytes of one decode step for `active` slots whose contexts
    hold `context_rows` rows together."""
    head = matrix_bytes(d.hidden, d.vocab) + 2 * d.hidden
    return (layers_bytes(d, active) + head + active * d.hidden * 2
            + mla_decode_bytes(d, active, context_rows) + kda_step_bytes(d, active))


def _row_matrix_ops(d: Dims) -> float:
    """Operations of the layers' matrices for one row."""
    hk = d.kda_width
    kda_layer = d.hidden * (5 * hk + d.heads) + hk * d.hidden + d.conv * 3 * hk
    mla_layer = (d.hidden * (d.heads * (d.nope + d.rope) + d.row + d.heads)
                 + d.heads * d.kv_rank * (d.nope + d.v_dim)
                 + d.heads * d.v_dim * d.hidden)
    expert_layer = (3 * d.hidden * d.shared_ffn + d.hidden * d.experts
                    + d.top_k * d.held / d.experts * 3 * d.hidden * d.expert_ffn)
    return 2 * (d.count("kda") * kda_layer + d.count("mla") * mla_layer
                + d.dense_layers * 3 * d.hidden * d.dense_ffn
                + (d.layers - d.dense_layers) * expert_layer)


def decode_step_ops(d: Dims, active: float, context_rows: float) -> float:
    per_token = _row_matrix_ops(d) + 2 * d.hidden * d.vocab
    return (active * per_token + mla_decode_ops(d, active, context_rows)
            + kda_step_ops(d, active))


def mla_decode_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least bytes of a step's latent decode kernels (one an MLA layer): the
    cache rows once at the published row, queries in and latent results out."""
    io = active * d.heads * (d.row + d.kv_rank) * 2
    return d.count("mla") * (context_rows * d.row * KV_BYTES + io)


def mla_decode_ops(d: Dims, active: float, context_rows: float) -> float:
    del active
    return context_rows * d.count("mla") * d.heads * (d.row + d.kv_rank) * 2


def prefill_ops(d: Dims, prompt_tokens: Sequence[int], cached_rows: Sequence[int]
                ) -> float:
    """Least operations to admit prompts of these lengths of which the first
    `cached_rows[i]` rows were already in the cache: the matrices and the
    KDA recurrence for every new row, causal attention of each new row over
    what precedes it in the MLA layers, and one output-head row per prompt."""
    per_row = _row_matrix_ops(d) + d.count("kda") * kda_row_ops(d)
    total = 0.0
    for t, c in zip(prompt_tokens, cached_rows):
        new = t - c
        pairs = new * c + new * (new + 1) / 2
        total += new * per_row + pairs * d.count("mla") * d.heads * 2 * (
            d.nope + d.rope + d.v_dim)
    return total


def prefill_bytes(d: Dims, new_rows: float) -> float:
    """Least HBM bytes of one prefill program: the layers' weights once and
    the slot's states in and out."""
    return layers_bytes(d, new_rows) + d.count("kda") * 2 * kda_state_bytes(d)
