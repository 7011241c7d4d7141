"""Seeded weights, made by the benchmark: they are the run's input data.

One jitted call builds the whole tree on the device from `--seed`, in the
type it is served in (int8 matrices with per-column float32 scales, bfloat16
norms, embedding and router). Layer `l` is a pure function of
`fold_in(key, l)`, so the reference regenerates one layer at a time from the
seed alone and takes nothing the program has touched.

Layout (the checkpoint format the configuration's `assumed` list states):
`w_qkv` is [wq | wk | wv] along columns, `w_gateup` / `we_gateup` are
[gate | up]; a quantized matrix is `{"q": int8 [K, N], "s": float32 [1, N]}`
and stands for `q * s`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

W_STD = 0.02  # standard deviation of every matrix entry, as initializer_range
_INT8_STD = 73.9  # of a uniform byte


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


def _bytes(key, shape):
    return jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint8), jnp.int8
    )


def _qleaf(key, shape) -> Dict[str, jax.Array]:
    kq, ks = jax.random.split(key)
    n = shape[-1]
    s = (W_STD / _INT8_STD) * (0.5 + jax.random.uniform(ks, shape[:-2] + (1, n)))
    return {"q": _bytes(kq, shape), "s": s.astype(jnp.float32)}


def _small(key, shape, scale=W_STD / _INT8_STD, offset=0.0):
    """A bfloat16 leaf from raw bytes (jax.random.normal compiles slowly on
    the TPU for large shapes; bytes do not)."""
    return (offset + _bytes(key, shape).astype(jnp.float32) * scale).astype(
        jnp.bfloat16
    )


def layer_leaves(d: Dims, key) -> Dict[str, object]:
    """The leaves of ONE layer (no layer axis) from that layer's key."""
    ks = jax.random.split(key, 8)
    out = {
        "attn_norm": _small(ks[0], (d.hidden,), 0.1 / _INT8_STD, 1.0),
        "ffn_norm": _small(ks[1], (d.hidden,), 0.1 / _INT8_STD, 1.0),
        "w_qkv": _qleaf(ks[2], (d.hidden, d.q_dim + 2 * d.kv_dim)),
        "wo": _qleaf(ks[3], (d.q_dim, d.hidden)),
    }
    if d.experts:
        out["w_router"] = _small(ks[4], (d.hidden, d.experts))
        out["we_gateup"] = _qleaf(ks[5], (d.experts, d.hidden, 2 * d.ffn))
        out["we_down"] = _qleaf(ks[6], (d.experts, d.ffn, d.hidden))
    else:
        out["w_gateup"] = _qleaf(ks[5], (d.hidden, 2 * d.ffn))
        out["w_down"] = _qleaf(ks[6], (d.ffn, d.hidden))
    return out


def _split(seed: int):
    """--seed may pass 2**31: two int32 halves, traced, so that one compiled
    program serves every seed."""
    return jnp.int32(seed % (2 ** 31)), jnp.int32(seed // (2 ** 31))


def _roots(seed_lo, seed_hi):
    """(layers, embed, final_norm, lm_head) keys."""
    return jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi), 4
    )


def _top(d: Dims, seed_lo, seed_hi) -> Dict[str, object]:
    _, ke, kn, kh = _roots(seed_lo, seed_hi)
    return {
        "embed": _small(ke, (d.vocab, d.hidden)),
        "final_norm": _small(kn, (d.hidden,), 0.1 / _INT8_STD, 1.0),
        "lm_head": _qleaf(kh, (d.hidden, d.vocab)),
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _build(d: Dims, seed_lo, seed_hi):
    k_layers = _roots(seed_lo, seed_hi)[0]
    layers = jax.lax.map(
        lambda l: layer_leaves(d, jax.random.fold_in(k_layers, l)),
        jnp.arange(d.layers),
    )
    return {"layers": layers, **_top(d, seed_lo, seed_hi)}


@functools.partial(jax.jit, static_argnums=(0,))
def _one_layer(d: Dims, seed_lo, seed_hi, layer):
    return layer_leaves(
        d, jax.random.fold_in(_roots(seed_lo, seed_hi)[0], layer)
    )


def build_params(d: Dims, seed: int):
    """The whole serving tree, on the device, in one jitted call."""
    return _build(d, *_split(seed))


def build_layer(d: Dims, seed: int, layer: int):
    """Layer `layer` of the same tree, alone (for the reference)."""
    return _one_layer(d, *_split(seed), jnp.int32(layer))


def build_top(d: Dims, seed: int):
    """Embedding, final norm and output head of the same tree."""
    return jax.jit(_top, static_argnums=(0,))(d, *_split(seed))
