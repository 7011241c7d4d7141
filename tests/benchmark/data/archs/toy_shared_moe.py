"""A toy architecture that no harness module names, to prove what the seam
of `benchmark/archs/` has to carry for the models now published: layers that
DIFFER BY INDEX. Tiny widths; never a cell. The test that drives it
(tests/benchmark/test_bench_archs.py) finds it by location, as a run finds a
configuration's `arch`.

- the first `dense_layers` layers have a dense SwiGLU FFN;
- the layers after them route every token over `experts` experts (sigmoid
  scores, the top `top_k` renormalised and scaled by `scale`) and add one
  shared expert that every token runs;
- of the `experts` the router ranks, only `held` lie here, from `first` on:
  the share of one chip of a deployment that divides each layer's experts
  (the `model-configs` guide, section 4). The router keeps its published
  width and top-k; the layer adds what ITS experts give for the tokens routed
  to them, and what the absent ones would have added is left out.

Expert `e` is made from `fold_in(key, e)`, so every share holds the same
bytes for it that the uncut layer (`held` = `experts`) does.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.harness import reference as R
from benchmark.harness import weights as W

CONTROL = "int4"


@dataclass(frozen=True)
class Dims:
    layers: int = 3
    dense_layers: int = 1
    hidden: int = 64
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    dense_ffn: int = 128
    expert_ffn: int = 32
    experts: int = 16  # the router's width, as published
    held: int = 4  # of them, held here
    first: int = 0  # the first one held here
    top_k: int = 4
    scale: float = 2.5
    vocab: int = 512
    rope_theta: float = 1e4
    eps: float = 1e-5

    def routed(self, layer: int) -> bool:
        return layer >= self.dense_layers


def dims_of(config: dict) -> Dims:
    return Dims(**{k: v for k, v in config.items() if k in Dims.__dataclass_fields__})


# -- weights: a layer's tree depends on its index --------------------------------


def _ffn_leaves(k_up, k_down, hidden, width, lead=()):
    return {"gateup": W.qleaf(k_up, lead + (hidden, 2 * width)),
            "down": W.qleaf(k_down, lead + (width, hidden))}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(d: Dims, layer: int, seed_lo, seed_hi):
    ks = jax.random.split(W.layer_key(seed_lo, seed_hi, layer), 8)
    kv = d.kv_heads * d.head_dim
    out = {
        "attn_norm": W.norm(ks[0], d.hidden), "ffn_norm": W.norm(ks[1], d.hidden),
        "w_qkv": W.qleaf(ks[2], (d.hidden, d.heads * d.head_dim + 2 * kv)),
        "wo": W.qleaf(ks[3], (d.heads * d.head_dim, d.hidden)),
    }
    if not d.routed(layer):
        return {**out, "ffn": _ffn_leaves(ks[4], ks[5], d.hidden, d.dense_ffn)}
    out["router"] = W.small(ks[4], (d.hidden, d.experts), scale=0.3 / W.INT8_STD)
    out["shared"] = _ffn_leaves(ks[5], ks[6], d.hidden, d.expert_ffn)
    out["experts"] = jax.vmap(lambda e: _ffn_leaves(
        *jax.random.split(jax.random.fold_in(ks[7], e)), d.hidden, d.expert_ffn)
    )(d.first + jnp.arange(d.held))
    return out


def build_layer(d: Dims, seed: int, layer: int):
    return _layer(d, int(layer), *W.split_seed(seed))


def top_leaves(d: Dims, k_embed, k_norm, k_head) -> Dict[str, object]:
    return {"embed": W.small(k_embed, (d.vocab, d.hidden)),
            "final_norm": W.norm(k_norm, d.hidden),
            "lm_head": W.qleaf(k_head, (d.hidden, d.vocab))}


def build_top(d: Dims, seed: int):
    return W.build_stack_top(top_leaves, d, seed)


def build_params(d: Dims, seed: int):
    """A list of unlike layers: nothing stacks them."""
    return {"layers": [build_layer(d, seed, l) for l in range(d.layers)],
            **build_top(d, seed)}


# -- the plain reference ---------------------------------------------------------


def embed(top, ids):
    return top["embed"][ids].astype(jnp.float32)


def _swiglu(h, leaves, width, precision):
    gu = h @ R.dense(leaves["gateup"], precision)
    return (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ R.dense(leaves["down"], precision)


def attention(d: Dims, x, lw, precision: str):
    t = x.shape[0]
    pos = jnp.arange(t)
    qd, kd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    qkv = R.rms(x, lw["attn_norm"], d.eps) @ R.dense(lw["w_qkv"], precision)
    q = R.rope(qkv[:, :qd].reshape(t, d.heads, d.head_dim), pos, d.rope_theta)
    k = R.rope(qkv[:, qd:qd + kd].reshape(t, d.kv_heads, d.head_dim), pos, d.rope_theta)
    v = qkv[:, qd + kd:].reshape(t, d.kv_heads, d.head_dim)
    k, v = (jnp.repeat(a, d.heads // d.kv_heads, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d.head_dim))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return x + att.reshape(t, qd) @ R.dense(lw["wo"], precision)


def moe_parts(d: Dims, h, lw, precision: str):
    """(what the experts HELD HERE add, what the shared expert adds, the
    router's margin) for normed rows h [T, E]. The router ranks all
    `d.experts`; the margin is over them, whatever share is held."""
    logits = h @ lw["router"].astype(jnp.float32)
    ranked = jax.lax.top_k(logits, d.top_k + 1)[0]
    margin = ranked[:, d.top_k - 1] - ranked[:, d.top_k]
    top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), d.top_k)
    top_w = d.scale * top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gate = jnp.zeros_like(logits).at[jnp.arange(h.shape[0])[:, None], top_i].set(top_w)
    routed = jnp.zeros_like(h)
    for j in range(d.held):  # every held expert over every token, weighted
        one = jax.tree.map(lambda a: a[j], lw["experts"])
        routed += gate[:, d.first + j][:, None] * _swiglu(h, one, d.expert_ffn, precision)
    return routed, _swiglu(h, lw["shared"], d.expert_ffn, precision), margin


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def block(d: Dims, x, lw, layer: int, precision: str):
    x = attention(d, x, lw, precision)
    h = R.rms(x, lw["ffn_norm"], d.eps)
    if not d.routed(layer):
        return (x + _swiglu(h, lw["ffn"], d.dense_ffn, precision),
                jnp.full((x.shape[0],), jnp.inf))
    routed, shared, margin = moe_parts(d, h, lw, precision)
    return x + routed + shared, margin


@functools.partial(jax.jit, static_argnums=(0, 3))
def head(d: Dims, x, top, precision: str):
    return R.rms(x, top["final_norm"], d.eps) @ R.dense(top["lm_head"], precision)
