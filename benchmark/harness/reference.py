"""The plain reference: the published forward pass in float32, no kernels,
no cache, no batching, one layer's weights on the device at a time.

It imports nothing of the program and takes nothing the program made: the
weights come from `weights.py` and the seed. Matrix products run under
`default_matmul_precision("highest")`, or the TPU would compute them in
bfloat16 passes. Departures from the published model: none in the mathematics
(RMSNorm, rotary embedding in the half-rotation convention, grouped-query
causal attention with the sliding window, SwiGLU, and for the
mixture-of-experts FFN softmax over all experts, top-k, renormalised); the
weights are the int8 checkpoint's `q * s`, which is the model the
configuration states.

`precision="int4"` is the CONTROL of "how correct is decided": the same
forward with every matrix re-quantized to 4 bits in groups of 128 rows, the
step below the configuration's int8 that a later PR would be tempted by.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

INT4_GROUP = 128


def _int4(w):
    """Symmetric 4-bit re-quantization in groups of INT4_GROUP rows."""
    k, n = w.shape[-2], w.shape[-1]
    g = INT4_GROUP if k % INT4_GROUP == 0 else k
    wg = w.reshape(w.shape[:-2] + (k // g, g, n))
    scale = jnp.max(jnp.abs(wg), axis=-2, keepdims=True) / 7.0
    q = jnp.clip(jnp.round(wg / jnp.where(scale > 0, scale, 1.0)), -8, 7)
    return (q * scale).reshape(w.shape)


def _dense(leaf, precision: str):
    w = leaf["q"].astype(jnp.float32) * leaf["s"]
    return _int4(w) if precision == "int4" else w


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnums=(0, 3))
def _block(d: W.Dims, x, lw, precision: str):
    """One transformer block over one sequence x [T, E] float32: the block's
    output and each position's router margin (infinite without a router)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, lw["attn_norm"], d.eps)
    qkv = h @ _dense(lw["w_qkv"], precision)
    q = qkv[:, :d.q_dim].reshape(t, d.heads, d.head_dim)
    k = qkv[:, d.q_dim:d.q_dim + d.kv_dim].reshape(t, d.kv_heads, d.head_dim)
    v = qkv[:, d.q_dim + d.kv_dim:].reshape(t, d.kv_heads, d.head_dim)
    q, k = _rope(q, pos, d.rope_theta), _rope(k, pos, d.rope_theta)
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
    x = x + att.swapaxes(0, 1).reshape(t, d.q_dim) @ _dense(lw["wo"], precision)
    h = _rms(x, lw["ffn_norm"], d.eps)

    def ffn(gateup, down):
        gu = h @ gateup
        return (jax.nn.silu(gu[:, :d.ffn]) * gu[:, d.ffn:]) @ down

    if not d.experts:
        return x + ffn(_dense(lw["w_gateup"], precision),
                       _dense(lw["w_down"], precision)), jnp.full((t,), jnp.inf)
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
        y = ffn(_dense(one[0], precision), _dense(one[1], precision))
        return acc + gate[:, e][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(d.experts))
    return x + y, margin


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(d: W.Dims, x, final_norm, lm_head, precision: str):
    return _rms(x, final_norm, d.eps) @ _dense(lm_head, precision)


KEEP = 256  # logits rows kept per sequence: the longest answer fits


def bucket(n: int, step: int = 512) -> int:
    return -(-n // step) * step


def logits_for(d: W.Dims, seed: int, sequences: Sequence[Sequence[int]],
               keep_from: Sequence[int], precisions: Sequence[str] = ("float32",),
               pad_to: int = 0) -> Dict[str, List[np.ndarray]]:
    """Logits of each sequence at positions `keep_from[i]` to its end (at
    most KEEP rows), per precision; under "router_margin" each layer's router
    margin at those positions [rows, layers], in the first precision's own
    forward. Layer-major: one layer's weights live at a
    time and serve every sequence. Sequences are padded to `pad_to` positions
    (or to a multiple of 512), which a causal model's earlier positions cannot
    see; one length for a whole cell means one compiled block for it."""
    with jax.default_matmul_precision("highest"):
        top = W.build_top(d, seed)
        xs: Dict[str, list] = {p: [] for p in precisions}
        for seq in sequences:
            ids = np.zeros(max(bucket(len(seq)), pad_to), np.int32)
            ids[:len(seq)] = seq
            x = top["embed"][ids].astype(jnp.float32)
            for p in precisions:
                xs[p].append(x)
        margins: List[list] = [[] for _ in sequences]
        for l in range(d.layers):
            lw = W.build_layer(d, seed, l)
            for p in precisions:
                done = [_block(d, x, lw, p) for x in xs[p]]
                xs[p] = [x for x, _ in done]
                if p == precisions[0]:
                    for m, (_, new) in zip(margins, done):
                        m.append(new)
            del lw
        out: Dict[str, List[np.ndarray]] = {"router_margin": [
            np.stack([np.asarray(a) for a in m], -1)[k:len(seq)]
            for m, seq, k in zip(margins, sequences, keep_from)]}
        for p in precisions:
            out[p] = []
            for x, seq, k in zip(xs[p], sequences, keep_from):
                n = len(seq) - k
                if not 0 < n <= KEEP:
                    raise ValueError(f"{n} rows asked of a sequence; KEEP is {KEEP}")
                rows = np.minimum(np.arange(k, k + KEEP), x.shape[0] - 1)
                out[p].append(np.asarray(_head(
                    d, x[rows], top["final_norm"], top["lm_head"], p))[:n])
        return out


def served_gaps(logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """By how much each served token's reference logit lies below the
    reference's best, position by position. `logits[i]` predicts `served[i]`."""
    served = np.asarray(served)
    return logits.max(-1) - logits[np.arange(len(served)), served]


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray) -> np.ndarray:
    """The same gap for the token the lower precision puts first."""
    return served_gaps(ref_logits, low_logits.argmax(-1))
