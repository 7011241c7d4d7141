"""Functional JAX implementation of the Llama-family decoder.

One code path serves TinyLlama-1.1B, Mistral-7B (GQA + sliding window),
DeepSeek-R1-Distill-8B and Qwen3-14B (QK-norm) — the four local tiers of the
reference intelligence hierarchy (SURVEY.md section 2.3). The design is
TPU-first:

  * layer parameters are stacked on a leading axis and the block stack runs
    under `jax.lax.scan` — one traced layer, fast compiles, XLA-friendly;
  * all matmuls are bf16 einsums (MXU), normalization/softmax accumulate in
    fp32;
  * masks are computed from positions with static shapes — no dynamic shapes
    anywhere, so prefill/decode jit cleanly onto the MXU;
  * three entry points: `forward_full` (training/parity), `prefill`
    (returns per-layer K/V for cache insertion), `decode_step` (batched
    single-token step over a slot cache — the continuous-batching hot loop).

Params pytree layout (E=hidden, Q=heads*head_dim, K=kv_heads*head_dim,
F=intermediate, L=layers, V=vocab, D=head_dim):

  embed      [V, E]
  layers/attn_norm [L, E]   layers/ffn_norm [L, E]
  layers/wq  [L, E, Q]      layers/wk [L, E, K]   layers/wv [L, E, K]
  layers/wo  [L, Q, E]
  layers/w_gate [L, E, F]   layers/w_up [L, E, F] layers/w_down [L, F, E]
  layers/q_norm [L, D]      layers/k_norm [L, D]      (only if cfg.qk_norm)
  final_norm [E]
  lm_head    [E, V]                                   (absent if tied)
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from . import moe as moe_mod
from .config import ModelConfig, RopeParams

Params = Dict[str, jnp.ndarray]

# weights that get the int8 serving treatment (contraction dim is axis -2);
# we_* are the expert-stacked MoE leaves (the router stays bf16 — tiny)
QUANT_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down",
)


def matmul(x: jnp.ndarray, w, qmm=None, kind: str = "col") -> jnp.ndarray:
    """x @ w where w is a dense array, an int8 leaf {"q", "s"} or an int4
    leaf {"q4", "s4"}.

    Quantized leaves stream their narrow format from HBM (int8 via XLA's
    mixed dot or the Pallas qmm; int4 via the packed-nibble Pallas kernel —
    a quarter of the bf16 decode bandwidth); elsewhere they dequantize
    inline.

    ``qmm`` — explicit int4 matmul callable f(x, leaf, kind), overriding
    the kernel ladder for q4 leaves; the tensor-parallel engine passes
    ShardingPlan.int4_matmul_impl so each device runs the packed-nibble
    kernel on its own shard under shard_map. ``kind`` names the Megatron
    role of this matmul ("col" | "row" | "head") so the impl picks the
    right specs + collective.
    """
    if isinstance(w, dict):
        if "q4" in w:
            if qmm is not None:
                return qmm(x, w, kind)
            from ..ops.int4_matmul import (
                infer_group,
                int4_matmul,
                int4_matmul_reference,
                kernel_supported,
            )

            p4, s4 = w["q4"], w["s4"]
            g = infer_group(p4, s4)
            if ops.use_pallas() and kernel_supported(
                p4.shape[-2] * 2, p4.shape[-1], g
            ):
                return int4_matmul(x, p4, s4)
            return int4_matmul_reference(x, p4, s4)
        w_q, s = w["q"], w["s"]
        if ops.use_pallas():
            import os

            from ..ops.quantized_matmul import supports_pallas_qmm

            if os.environ.get(
                "AIOS_TPU_PALLAS_QMM"
            ) == "1" and supports_pallas_qmm(w_q.shape[-2], w_q.shape[-1]):
                return ops.quantized_matmul(x, w_q, s)
            # XLA's mixed int8xbf16 dot streams the int8 operand directly
            # (measured faster than per-op Pallas launches at decode sizes)
            y = jax.lax.dot_general(
                x,
                w_q,
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return (y * s).astype(x.dtype)
        return (x.astype(jnp.float32) @ (w_q.astype(jnp.float32) * s)).astype(
            x.dtype
        )
    return x @ w


def _int4_group(key: str, K: int, N: int, tp: int = 1,
                target: str = "auto") -> int:
    """Scale-group size for serving weight ``key`` [K, N] as an int4 leaf,
    or 0 when it must stay int8 — the one eligibility rule shared by
    ``quantize_params`` and ``init_quantized_params``.

    Under a tp-sharded plan the kernel runs per device on a [K, N/tp]
    (column-parallel) or [K/tp, N] (row-parallel) shard, so eligibility —
    and the scale-group size — must hold for the SHARD dims, not the
    global ones. lm_head shards its vocab like a column projection.

    On TPU a q4 leaf the kernel can't serve would dequantize to bf16 in
    HBM every step — strictly worse than int8 — so kernel-ineligible dims
    fall back to int8 there. On an intended CPU run every quantized leaf
    dequantizes inline anyway, so storage eligibility is enough (keeps
    tiny test geometries on int4). ``target="tpu"`` forces the strict
    kernel rule regardless of the local backend — prepare_model uses it so
    a checkpoint prepared on a CPU build box never bakes in leaves a TPU
    can only serve through the HBM-dequant path.
    """
    from ..ops.int4_matmul import kernel_supported, pick_group, supports_int4

    local_K, local_N = K, N
    if tp > 1:
        if key in ("wo", "w_down"):
            local_K = K // tp if K % tp == 0 else 0
        else:
            local_N = N // tp if N % tp == 0 else 0
    group = pick_group(local_K)
    eligible = (
        local_K > 0
        and local_N > 0
        and supports_int4(K, N, group)
        and (
            kernel_supported(local_K, local_N, group)
            or (target != "tpu" and not ops.use_pallas())
        )
    )
    return group if eligible else 0


def quantize_params(
    params: Params, include_head: bool = True, fuse: bool = True,
    mode: str = "int8", target: str = "auto", tp: int = 1,
) -> Params:
    """Convert matmul weights to int8 serving leaves {"q": int8, "s": f32}.

    Serving-format transformations applied together:
      * symmetric per-output-channel int8 — halves the weight bytes streamed
        from HBM per decode step (the measured bottleneck);
      * matmul fusion (``fuse=True``) — wq|wk|wv concatenate into one
        [E, Q+2KV] ``w_qkv`` and w_gate|w_up into one [E, 2F] ``w_gateup``,
        so each decode step issues 4 weight matmuls per layer instead of 7;
      * a tied lm_head is materialized as its own quantized [E, V] matrix so
        the logits matmul streams int8 too.

    ``fuse=False`` keeps the seven per-layer weights separate — required
    under a tensor-parallel sharding plan, where each projection's output
    dim shards on the tp axis and a fused concat would interleave q/k/v
    columns across shards (sharding.py quantized-leaf rules).

    Norms and the embedding gather stay bf16 (negligible bandwidth). The
    dense layout is untouched — training and sharding plans use it.

    ``mode="int4"`` emits group-wise int4 leaves {"q4": packed nibbles,
    "s4": [G, 1, N] scales} instead (ops/int4_matmul.py) — half the int8
    bytes, matching the reference's Q4-class GGUF serving precision.
    Leaves whose dims don't fit the int4 layout, and the expert-stacked
    MoE leaves (moe._expert_einsum reads int8 leaves only), fall back to
    int8.
    """
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown weight quantization mode {mode!r}")
    if target not in ("auto", "tpu"):
        raise ValueError(f"unknown quantization target {target!r}")
    out = dict(params)
    src = params["layers"]
    layers = {
        k: v
        for k, v in src.items()
        if k not in QUANT_KEYS
    }
    moe = "w_router" in src
    if fuse:
        qkv = jnp.concatenate([src["wq"], src["wk"], src["wv"]], axis=-1)
        to_quant = (("w_qkv", qkv), ("wo", src["wo"]))
        if moe:
            gateup = jnp.concatenate([src["we_gate"], src["we_up"]], axis=-1)
            to_quant += (("we_gateup", gateup), ("we_down", src["we_down"]))
        else:
            gateup = jnp.concatenate([src["w_gate"], src["w_up"]], axis=-1)
            to_quant += (("w_gateup", gateup), ("w_down", src["w_down"]))
    else:
        to_quant = tuple((k, src[k]) for k in QUANT_KEYS if k in src)
    def quant_leaf(key, w):
        if mode == "int4" and not key.startswith("we_"):
            from ..ops.int4_matmul import quantize_int4

            group = _int4_group(key, w.shape[-2], w.shape[-1], tp, target)
            if group:
                p, s = quantize_int4(w, group=group)
                return {"q4": p, "s4": s}
        q, s = ops.quantize_int8(w, axis=-2)
        return {"q": q, "s": s}

    for key, w in to_quant:
        layers[key] = quant_leaf(key, w)
    out["layers"] = layers
    if include_head:
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        out["lm_head"] = quant_leaf("lm_head", head)
    return out


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with fp32 accumulation, output in x.dtype."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * weight


def rope_tables(
    positions: jnp.ndarray, head_dim: int, theta: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for the given absolute positions.

    Returns arrays of shape positions.shape + (head_dim,) using the
    half-rotation (HF transformers) convention: the frequency vector is
    duplicated across the two halves of the head dimension.
    """
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., half]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(dim: int, rope: RopeParams) -> np.ndarray:
    """The rotary frequencies [dim / 2] under YaRN: dimension i keeps
    1 / theta^(2i/dim) where it turns more than ``beta_fast`` times in
    ``original_context`` positions, takes that over ``factor`` where it
    turns fewer than ``beta_slow`` times, and a linear blend between the
    two dimensions those counts correspond to. The one blend: the latent
    block's rotary part and a grouped-query layer both come here."""
    d, base = dim, float(rope.theta)
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns: float) -> float:
        return d * math.log(rope.original_context / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(rope.beta_fast)), 0)
    high = min(math.ceil(dim_of(rope.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / rope.factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rope_tables_of(
    positions: jnp.ndarray, head_dim: int, rope: RopeParams
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables of one kind of layer: ``rope_tables`` where there is
    no scaling (the same trace), else YaRN's frequencies with cos and sin
    both times ``attention_factor``."""
    if rope.factor <= 1.0 and rope.attention_factor == 1.0:
        return rope_tables(positions, head_dim, rope.theta)
    angles = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(head_dim, rope)
    angles = jnp.concatenate([angles, angles], axis=-1)
    scale = jnp.float32(rope.attention_factor)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def kind_of(lp) -> Optional[str]:
    """The kind of the layer whose tree this is, where its stack mixes two
    (scan_segments writes it beside the leaves); None in a stack of one."""
    return lp.get("layer_kind")


def rope_by_kind(positions: jnp.ndarray, cfg: ModelConfig):
    """{kind: (cos, sin)} at ``positions`` for every kind of layer the stack
    has; a stack of one kind has the one key None."""
    # in the period's own order: a set's order follows the process's hash
    # seed, the graph's text with it, and a compile cache keyed by that text
    # then misses every other start
    return {
        kind: rope_tables_of(positions, cfg.head_dim, cfg.rope_of(kind))
        for kind in (dict.fromkeys(cfg.period_kinds) or {None: None})
    }


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate q or k. x: [B, T, H, D]; cos/sin: [B, T, D]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    return (x.astype(jnp.float32) * cos + rotated.astype(jnp.float32) * sin).astype(
        x.dtype
    )


def gqa_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, KH, D]
    v: jnp.ndarray,  # [B, S, KH, D]
    mask: jnp.ndarray,  # bool [B, T, S] or [T, S]
) -> jnp.ndarray:
    """Grouped-query attention, fp32 softmax. Returns [B, T, H, D]."""
    B, T, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    q = q.reshape(B, T, KH, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(D)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None, :, :], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(B, T, H, D)


def blockwise_cache_attention(
    q: jnp.ndarray,  # [1, Tc, H, D]
    kv_block,  # j -> (k, v) [block, KH, D] each: key tile j of the view
    n_blocks,  # tiles 0 .. n_blocks-1 are folded; may be traced
    abs_pos: jnp.ndarray,  # [Tc] absolute position of each query row
    window: Optional[int],
    live_from: Optional[jnp.ndarray] = None,  # scalar: live window start
    sink: int = 0,  # static sink rows (window+sink KV compression)
    col0=0,  # scalar, may be traced: the position of tile 0's first row
) -> jnp.ndarray:
    """Chunk-vs-cache attention via an online softmax over KV tiles.

    The [Tc, C] score matrix never materializes, and neither does the
    slot's view of the pool: ``kv_block(j)`` reads tile j where it lies (a
    slot's pages gathered from the pool, ``paged_kv_block``; the slot-cache
    form hands tiles of a view it sliced once) and each [Tc, block] tile is
    folded into running (max,
    denom, accumulator) stats under ``lax.fori_loop`` (the flash recurrence
    in plain XLA, so it runs on every backend). ``n_blocks`` is the caller's
    ``chunk_kv_tiles``: the loop ends at the tile of the chunk's last row,
    not at the table's. A tile above it is masked whole and would add exact
    zeros (p = exp(-1e30 - m) = 0, alpha = 1), so the bounded fold equals
    the fold over the whole table element for element. Query row i sees
    cache col j iff j <= abs_pos[i] (and inside the sliding window) — the
    row's own K/V was written to the cache before this is called, so the
    diagonal is always visible and the denominator can't be zero.
    """
    B, Tc, H, D = q.shape
    block, KH, _ = jax.eval_shape(kv_block, 0)[0].shape
    G = H // KH
    qf = q[0].reshape(Tc, KH, G, D).astype(jnp.float32) / np.sqrt(D)

    def fold(j, carry):
        m, l, acc = carry
        kblk, vblk = (t.astype(jnp.float32) for t in kv_block(j))
        # tile 0 starts at position col0 (a window layer's chunk reads from
        # its window's first page, not from row 0)
        cols = col0 + j * block + jnp.arange(block)
        s = jnp.einsum("tkgd,ckd->kgtc", qf, kblk)  # [KH, G, Tc, block]
        visible = cols[None, :] <= abs_pos[:, None]  # [Tc, block]
        if window is not None:
            visible = visible & (cols[None, :] > abs_pos[:, None] - window)
        if live_from is not None:
            # window+sink KV compression: cache rows in [sink, live_from)
            # were pruned mid-admission; the chunk must not attend them
            visible = visible & (
                (cols[None, :] < sink) | (cols[None, :] >= live_from)
            )
        s = jnp.where(visible[None, None], s, jnp.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)  # rescale of previous stats
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("kgtc,ckd->kgtd", p, vblk)
        return m_new, l, acc

    init = (
        jnp.full((KH, G, Tc), -1e30, jnp.float32),
        jnp.zeros((KH, G, Tc), jnp.float32),
        jnp.zeros((KH, G, Tc, D), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_blocks, fold, init)
    out = acc / l[..., None]
    # [KH, G, Tc, D] -> [1, Tc, H, D]
    return out.transpose(2, 0, 1, 3).reshape(B, Tc, H, D).astype(q.dtype)


def chunk_kv_tiles(start, Tc: int, rows: int, tile: int, col0=0,
                   minimum=jnp.minimum):
    """Key tiles of ``tile`` rows a chunk's attention folds, of a view of
    ``rows`` rows from position ``col0`` on: those up to the tile of the
    chunk's last row, start + Tc - 1, and no more than the view has (a final
    bucket's padding may overrun it). ``start`` and ``col0`` traced in a
    graph; ``minimum=min`` reckons the same count from the host's ints."""
    return minimum((start + Tc - 1 - col0) // tile + 1, rows // tile)


def causal_mask(T: int, window: Optional[int]) -> jnp.ndarray:
    """[T, T] causal (optionally sliding-window) mask."""
    rows = jnp.arange(T)[:, None]
    cols = jnp.arange(T)[None, :]
    m = cols <= rows
    if window is not None:
        m = m & (cols > rows - window)
    return m


# ---------------------------------------------------------------------------
# One transformer block (shared by all entry points)
# ---------------------------------------------------------------------------


def _project_qkv(x, lp, cfg: ModelConfig, cos, sin, qmm=None):
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    return qkv_of(h, lp, cfg, cos, sin, qmm)


def qkv_of(h, lp, cfg: ModelConfig, cos, sin, qmm=None):
    """Normed rows h [B, T, E] -> (q [B, T, H, D], k, v [B, T, KH, D]), q and
    k rotated unless the model applies no rotary embedding (``cfg.rotary``)."""
    B, T, E = h.shape
    if "w_qkv" in lp:  # fused serving layout (quantize_params)
        Q, KV = cfg.q_dim, cfg.kv_dim
        qkv = matmul(h, lp["w_qkv"], qmm)
        q, k, v = (
            qkv[..., :Q],
            qkv[..., Q : Q + KV],
            qkv[..., Q + KV :],
        )
    else:
        q = matmul(h, lp["wq"], qmm)
        k = matmul(h, lp["wk"], qmm)
        v = matmul(h, lp["wv"], qmm)
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if cfg.rotary:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def apply_block(x, lp, cfg: ModelConfig, cos, sin, mask, attention=None,
                with_aux: bool = False, qmm=None,
                moe_dense: bool = False):
    """One transformer block on [B, T, E]; returns (x', (k, v)) — or
    (x', (k, v, moe_aux)) when ``with_aux``.

    The single source of truth for block structure — the prefill/training
    forward, the decode step, and the pipeline-parallel stage all build on
    it (pipeline.py discards the returned k/v).
    """
    x, k, v = _attend(x, lp, cfg, cos, sin, mask, attention, qmm)
    mlp_out, aux = _mlp_aux(x, lp, cfg, allow_dispatch=with_aux,
                            moe_dense=moe_dense, qmm=qmm)
    x = x + mlp_out
    if with_aux:
        return x, (k, v, aux)
    return x, (k, v)


def _attend(x, lp, cfg: ModelConfig, cos, sin, mask, attention=None, qmm=None):
    """The attention sublayer of a block over its own rows; (x', k, v)."""
    attention = attention or gqa_attention
    B, T = x.shape[0], x.shape[1]
    q, k, v = _project_qkv(x, lp, cfg, cos, sin, qmm)
    attn = attention(q, k, v, mask)
    return x + matmul(attn.reshape(B, T, -1), lp["wo"], qmm, "row"), k, v


def _mlp_aux(
    x,
    lp,
    cfg: ModelConfig,
    allow_dispatch: bool = False,
    moe_dense: bool = False,
    qmm=None,
):
    """FFN sublayer; returns (out, moe_aux) — aux is the router
    load-balancing term (0.0 for dense models), consumed only by the
    training forward (forward_full with_aux=True)."""
    h = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
    return ffn(h, lp, cfg, allow_dispatch, moe_dense, qmm)[:2]


def zero_stats(cfg: ModelConfig):
    """What a serving graph of a model with a router carries through its
    layers beside the residual: the expert counters (moe.pick_stats), from
    zero. Nothing for a model without one."""
    return (jnp.zeros((moe_mod.PICK_STATS,), jnp.int32),) if cfg.moe else ()


def add_stats(stats, new):
    """The carried counters plus one layer's (a dense layer has none)."""
    if not stats or new is None:
        return stats
    return (stats[0] + new,)


def _add_mlp(x, stats, lp, cfg: ModelConfig, moe_dense, qmm, live=None):
    """A serving block's FFN sublayer: (x plus it, the carried expert
    counters plus the layer's). ``live``: a decode step's slot mask."""
    h = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
    out, _, new = ffn(h, lp, cfg, False, moe_dense, qmm, live)
    return x + out, add_stats(stats, new)


def _swiglu(h, lp, prefix: str, width: int, qmm=None, act: str = "swiglu"):
    """One SwiGLU FFN over normed rows from the leaves ``<prefix>gateup``
    (the fused serving layout [gate | up], quantize_params) or
    ``<prefix>gate`` / ``<prefix>up``, and ``<prefix>down``. With ``act``
    "relu2" (ModelConfig.expert_act) the FFN has no gate:
    ``down(relu(up(x))^2)`` from ``<prefix>up`` and ``<prefix>down``."""
    if act == "relu2":
        u = matmul(h, lp[prefix + "up"], qmm).astype(jnp.float32)
        z = jnp.square(jax.nn.relu(u)).astype(h.dtype)
        return matmul(z, lp[prefix + "down"], qmm, "row")
    if prefix + "gateup" in lp:
        gu = matmul(h, lp[prefix + "gateup"], qmm)
        gate_pre, u = gu[..., :width], gu[..., width:]
    else:
        gate_pre = matmul(h, lp[prefix + "gate"], qmm)
        u = matmul(h, lp[prefix + "up"], qmm)
    g = jax.nn.silu(gate_pre.astype(jnp.float32)).astype(h.dtype)
    return matmul(g * u, lp[prefix + "down"], qmm, "row")


def ffn(
    h,  # [B, T, E] normed hidden states
    lp,
    cfg: ModelConfig,
    allow_dispatch: bool = False,
    moe_dense: bool = False,
    qmm=None,
    live=None,  # [B] bool: the slots a DECODE step decodes; its graphs alone
):
    """The FFN of one layer over NORMED rows; returns (out, moe_aux,
    stats). Which FFN is the layer's own tree's to say (a dense layer has
    ``w_gateup``/``w_gate``, an expert layer ``w_router``, and beside it
    ``ws_*`` where every token also runs a shared expert), so leading dense
    layers and expert layers go through one function.

    The expert path is chosen by what the graph is, from STATIC shapes and
    the config: a decode step (the one graph that hands ``live``) visits
    the experts its live rows picked (moe.visit_serves), token counts at
    which it computes fewer rows take the exact grouped path
    (moe.grouped_serves: a prefill chunk or bucket; on both, ``lp``'s expert
    leaves may be the whole stacks, read in place at
    ``lp["expert_layer"]``; a layer scan that hands the stacks whole at a
    lower count, _scan_periods, gets the grouped path there too: the dense
    product cannot read them in place), the training forward
    (``allow_dispatch``) the
    capacity dispatch at large token counts, and everything else — the
    small prefill buckets, and every graph of an engine under a sharding
    plan (``moe_dense``) — the exact dense-over-held path.

    ``stats`` is moe.pick_stats (int32 [4]) for a layer with a router, else
    None: every serving graph of such a model carries the counters.
    """
    if "w_router" not in lp:
        out = _swiglu(h, lp, "w_", cfg.intermediate_size, qmm, cfg.expert_act)
        return out, jnp.float32(0.0), None
    n_tok = h.shape[0] * h.shape[1]
    stats = None
    # a scope renumbers a compiled graph's instructions: only the graphs of
    # a model that holds a share (new with the scope) get this one
    with (jax.named_scope("moe_routed") if cfg.expert_share
          else contextlib.nullcontext()):
        if allow_dispatch and n_tok >= 1024:
            # The capacity-based dispatch path may DROP overflow picks, so
            # only the training forward (``allow_dispatch``, i.e. with_aux)
            # takes it, at large token counts — every serving path (decode,
            # chunked/bucketed prefill) stays on an exact path.
            out, aux = moe_mod.moe_ffn_dispatch(h, lp, cfg)
        elif live is not None and moe_mod.visit_serves(cfg, moe_dense):
            out, aux, stats = moe_mod.moe_ffn_visit(h, lp, cfg, live)
        elif "expert_layer" in lp or moe_mod.grouped_serves(
            n_tok, cfg, moe_dense, allow_dispatch
        ):
            out, aux, stats = moe_mod.moe_ffn_grouped(h, lp, cfg)
        else:
            out, aux, stats = moe_mod.moe_ffn_dense(
                h, lp, cfg, with_stats=True
            )
    if "ws_gateup" in lp or "ws_gate" in lp or "ws_up" in lp:
        with jax.named_scope("moe_shared"):
            out = out + _swiglu(
                h, lp, "ws_", cfg.n_shared_experts * cfg.expert_dim, qmm,
                cfg.expert_act,
            )
    if stats is None:  # the training forward's dispatch counts nothing
        stats = jnp.zeros((moe_mod.PICK_STATS,), jnp.int32)
    return out, aux, stats


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def forward_full(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    attn_fn=None,
    kernels: Optional[bool] = None,
    with_aux: bool = False,
) -> jnp.ndarray:
    """Full-sequence causal forward; logits [B, T, V] in fp32.

    Used for training, numeric-parity testing and as the prefill core.
    ``attn_fn`` swaps the attention implementation (e.g. ring attention for
    sequence-parallel training); it defaults to in-core GQA attention.
    ``kernels=False`` forces the pure-XLA path — required under autodiff:
    the Pallas flash kernel is forward-only (no VJP rule yet).
    ``with_aux`` additionally returns the mean per-layer MoE
    load-balancing loss (0.0 for dense models): (logits, aux).
    """
    out = _forward_with_kv(
        params, cfg, tokens, attn_fn, kernels, with_aux=with_aux
    )
    return (out[0], out[3]) if with_aux else out[0]


def prefill(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, kernels=None,
    qmm=None, attn_fn=None, moe_dense: bool = False, logit_row=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Causal forward returning (logits [B,T,V], k [L,B,T,KH,D], v [...]).
    A latent-attention model returns its cache rows in the same places, as
    one "head" each: the latents [L,B,T,1,kv_lora_rank] and the padded
    rotary parts [L,B,T,1,128] (engine/paged.py header). A model with a
    router returns its expert counters (moe.pick_stats summed over the
    layers, int32 [4]) as one more value, here and from every serving graph
    below.

    The engine copies the returned K/V into the request's cache slot.
    ``attn_fn`` swaps the attention implementation — the sequence-sharded
    prefill path passes the ring/Ulysses adapter here so one huge
    prompt's forward spreads over the mesh's sp axis. With ``logit_row``
    (a traced index) the final norm and the head run on that row alone and
    the logits are [B, 1, V].
    """
    return _forward_with_kv(
        params, cfg, tokens, attn_fn=attn_fn, kernels=kernels, qmm=qmm,
        moe_dense=moe_dense, logit_row=logit_row,
    )


def _use_kernels(kernels: Optional[bool]) -> bool:
    return ops.use_pallas() if kernels is None else bool(kernels)


def _final_logits(x: jnp.ndarray, params: Params, cfg: ModelConfig, qmm=None):
    """Shared tail of every entry point: final RMSNorm + (possibly tied,
    possibly int8) lm_head matmul; logits in fp32."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return matmul(x, head, qmm, "head").astype(jnp.float32)


def _ragged_min_c() -> int:
    """Cache length where the ragged decode kernel starts winning over
    XLA's fused full-cache read (measured crossover on v5e ~2k rows;
    AIOS_TPU_RAGGED_MIN_C overrides for A/B runs, read at trace time)."""
    import os

    try:
        return int(os.environ.get("AIOS_TPU_RAGGED_MIN_C", "2048"))
    except ValueError:
        return 2048


def _int8_ragged_enabled() -> bool:
    """Gate for the int8-KV ragged decode kernel (read at trace time):
    interpret-mode-verified, but OFF by default until its crossover is
    measured on a real chip (the dequantizing XLA path is the baseline)."""
    import os

    return os.environ.get("AIOS_TPU_INT8_RAGGED", "").lower() in (
        "1", "true", "on",
    )


def _use_ragged_kernel(
    kernels: Optional[bool],
    C: int,
    cfg: ModelConfig,
    quant_cache: bool,
    quant_kernel_ok: bool = False,
) -> bool:
    """The ragged-attention crossover, shared by decode_step and
    verify_step: the kernel's DMA-only-valid-rows win beats its per-layer
    launch cost either on a long cache outright (>= _ragged_min_c rows,
    the TinyLlama-measured crossover) or on a large-model cache whose
    C x (KH x D) slab is >= 1 MiB of rows per slot (Mistral-7B at 1k rows
    measures +11% whole-step throughput on v5e).

    ``quant_kernel_ok`` — whether the CALLER has an int8-capable kernel
    for this path: decode_step and verify_step pass
    _int8_ragged_enabled() (their ladders include the int8 kernel
    variants, env-gated until measured on chip); callers without one pass
    False and their int8-KV paths stay on XLA. decode_step_paged does NOT
    use this crossover at all — like its bf16 path, the paged kernel is
    always preferable to the gather fallback, so it gates only on
    _use_kernels + the env flag."""
    kv_row = cfg.num_kv_heads * cfg.head_dim
    # the int8 kernel variants DMA-slice the cache axis on lanes, so they
    # need 128-aligned kv blocks (C % 128 == 0 makes pick_block_kv choose
    # >= 128); ineligible geometries stay on the XLA dequant path instead
    # of tripping the kernels' alignment guard
    int8_geometry_ok = C % 128 == 0
    return (
        _use_kernels(kernels)
        and (C >= _ragged_min_c() or C * kv_row >= 1 << 20)
        and (not quant_cache or (quant_kernel_ok and int8_geometry_ok))
    )


def _forward_with_kv(params, cfg: ModelConfig, tokens, attn_fn=None, kernels=None,
                     with_aux: bool = False, qmm=None,
                     moe_dense: bool = False, logit_row=None):
    if cfg.mla:
        from . import latent

        return latent.forward_with_kv(
            params, cfg, tokens, attn_fn=attn_fn, with_aux=with_aux,
            qmm=qmm, moe_dense=moe_dense, logit_row=logit_row,
        )
    if cfg.sublayers:
        from . import mamba2

        return mamba2.forward_with_kv(
            params, cfg, tokens, attn_fn=attn_fn, with_aux=with_aux,
            qmm=qmm, moe_dense=moe_dense, logit_row=logit_row,
        )
    B, T = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    ropes = rope_by_kind(positions, cfg)

    # Attention implementation ladder: explicit attn_fn (ring attention for
    # sequence parallelism) > Pallas flash kernel (TPU, block-aligned T) >
    # naive masked GQA. Flash is what keeps 8k-token prefills inside HBM —
    # it never materializes the [T, T] score matrix. A stack of two kinds
    # has the ladder once a kind: each with its window.
    def ladder(window):
        if attn_fn is None and _use_kernels(kernels) and T >= 128 and T % 128 == 0:
            def attention(q, k, v, mask):
                return ops.flash_attention(q, k, v, causal=True, window=window)
        else:
            attention = attn_fn or gqa_attention
        return attention, causal_mask(T, window)

    views = {kind: (*ropes[kind], *ladder(cfg.window_of(kind))) for kind in ropes}

    if with_aux:
        if cfg.kinds:
            raise ValueError(
                f"{cfg.name}: the training forward (with_aux) scans layers "
                "of one kind; a stack of window and full layers serves only"
            )
        cos, sin, attention, mask = views[None]

        def block(x, lp):
            return apply_block(x, lp, cfg, cos, sin, mask, attention, True,
                               qmm=qmm, moe_dense=moe_dense)

        x, (ks, vs, auxs) = jax.lax.scan(block, x, params["layers"])
        logits = _final_logits(x, params, cfg, qmm)
        return logits, ks, vs, jnp.mean(auxs)

    def block(carry, layer):
        x, stats = carry
        cos, sin, attention, mask = views[kind_of(layer[0])]
        x, k, v = _attend(x, layer[0], cfg, cos, sin, mask, attention, qmm)
        x, stats = _add_mlp(x, stats, layer[0], cfg, moe_dense, qmm)
        return (x, stats), (k, v)

    (x, stats), (ks, vs) = scan_segments(
        block, (x, zero_stats(cfg)), layer_segments(params),
        moe_mod.grouped_serves(B * T, cfg, moe_dense), cfg.period_kinds,
    )
    if logit_row is not None:
        x = jax.lax.dynamic_slice_in_dim(x, logit_row, 1, axis=1)
    logits = _final_logits(x, params, cfg, qmm)
    return (logits, ks, vs, *stats)


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [1, Tc] int32 — one chunk of one prompt
    slot: jnp.ndarray,  # scalar int32 — destination cache slot
    start: jnp.ndarray,  # scalar int32 — absolute position of tokens[0]
    k_cache: jnp.ndarray,  # [L, S, C, KH, D]
    v_cache: jnp.ndarray,  # [L, S, C, KH, D]
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    qmm=None,  # int4 matmul impl (x, leaf, kind) -> y; see matmul()
    moe_dense: bool = False,
):
    """One chunk of an incremental prefill against the slot cache.

    Writes the chunk's K/V at rows [start, start+Tc) of ``slot`` and attends
    each chunk token over all cache rows written so far (causal within the
    chunk, everything before ``start`` visible, sliding window honoured) —
    so an 8k prompt can be admitted as 16 x 512-token chunks with decode
    dispatches for the other slots interleaved between them, instead of one
    monolithic prefill that stalls every active request (the head-of-line
    block the reference inherits from llama-server's serial queue,
    SURVEY.md section 7 hard-part #1).

    Returns (logits [1, Tc, V] fp32, k_cache', v_cache'[, scales'][, stats]).
    Rows past ``start+Tc`` are garbage and masked; the caller samples from
    the logits row of the prompt's true last token on the final chunk.
    """
    B, Tc = tokens.shape
    C = k_cache.shape[2]
    quant_cache = cache_scales is not None
    x = params["embed"][tokens]  # [1, Tc, E]
    positions = start + jnp.arange(Tc)[None, :]  # [1, Tc]
    cos, sin = rope_tables_of(positions, cfg.head_dim, cfg.rope_of(None))

    kv_tile = min(512, C)  # NB: local `block` below would shadow this
    blockwise = C % kv_tile == 0  # else one mask over the slot's whole view
    if not blockwise:
        # chunk row i (abs pos start+i) sees cache col j iff j <= start+i
        cols = jnp.arange(C)[None, :]  # [1, C]
        abs_pos = positions[0][:, None]  # [Tc, 1]
        mask = cols <= abs_pos
        if cfg.sliding_window is not None:
            mask = mask & (cols > abs_pos - cfg.sliding_window)
        mask = mask[None]  # [1, Tc, C]

    write_at = (slot, start, jnp.int32(0), jnp.int32(0))

    def block(carry, layer):
        x, stats = carry
        lp, k_l, v_l, *scales_l = layer
        k_s, v_s = scales_l or (None, None)
        q, k_new, v_new = _project_qkv(x, lp, cfg, cos, sin, qmm)
        # k_new/v_new [1, Tc, KH, D] drop straight into the slot-cache layout
        # [S, C, KH, D] at (slot, start, 0, 0)
        if quant_cache:
            kq, ks_new = quantize_kv(k_new)
            vq, vs_new = quantize_kv(v_new)
            k_l = jax.lax.dynamic_update_slice(k_l, kq, write_at)
            v_l = jax.lax.dynamic_update_slice(v_l, vq, write_at)
            k_s = jax.lax.dynamic_update_slice(k_s, ks_new, write_at[:-1])
            v_s = jax.lax.dynamic_update_slice(v_s, vs_new, write_at[:-1])
        else:
            k_l = jax.lax.dynamic_update_slice(
                k_l, k_new.astype(k_l.dtype), write_at
            )
            v_l = jax.lax.dynamic_update_slice(
                v_l, v_new.astype(v_l.dtype), write_at
            )

        def view_of(cache, scale):
            """The slot's rows [C, KH, D] in q's dtype, sliced out once."""
            rows = jax.lax.dynamic_index_in_dim(cache, slot, 0, keepdims=False)
            if not quant_cache:
                return rows.astype(q.dtype)
            s = jax.lax.dynamic_index_in_dim(scale, slot, 0, keepdims=False)
            return dequantize_kv(rows, s, q.dtype)

        k_all, v_all = view_of(k_l, k_s), view_of(v_l, v_s)
        if blockwise:
            # the view cast once and every tile of it folded, as before PR 44:
            # tiles sliced from the carried cache inside a loop that ends at
            # the chunk's last tile cost a 512-row Mistral chunk 95-102 ms
            # where this costs 87 (PERF.md section 6, PR 44)
            k32, v32 = k_all.astype(jnp.float32), v_all.astype(jnp.float32)
            attn = blockwise_cache_attention(
                q,
                lambda j: (
                    jax.lax.dynamic_slice_in_dim(k32, j * kv_tile, kv_tile),
                    jax.lax.dynamic_slice_in_dim(v32, j * kv_tile, kv_tile),
                ),
                C // kv_tile, positions[0], cfg.sliding_window,
            )
        else:
            attn = gqa_attention(q, k_all[None], v_all[None], mask)
        x = x + matmul(attn.reshape(B, Tc, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm)
        return (x, stats), (k_l, v_l, *((k_s, v_s) if quant_cache else ()))

    x, k_cache, v_cache, scales, stats = _scan_layers_over_cache(
        block, x, params, k_cache, v_cache, cache_scales, cfg,
        moe_mod.grouped_serves(B * Tc, cfg, moe_dense),
    )
    logits = _final_logits(x, params, cfg, qmm)
    if quant_cache:
        return (logits, k_cache, v_cache, scales, *stats)
    return (logits, k_cache, v_cache, *stats)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] int32 — one new token per slot
    lengths: jnp.ndarray,  # [B] int32 — tokens already in each slot's cache
    k_cache: jnp.ndarray,  # [L, B, C, KH, D]
    v_cache: jnp.ndarray,  # [L, B, C, KH, D]
    kernels: Optional[bool] = None,
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    active: Optional[jnp.ndarray] = None,  # [B] bool
    attn_impl=None,  # (q [B,H,D], k_l, v_l, lengths) -> [B,H,D]
    moe_dense: bool = False,
    qmm=None,  # int4 matmul impl (x, leaf, kind) -> y; see matmul()
):
    """One batched decode step over the slot cache.

    Writes the new K/V at row ``lengths[b]`` of each slot, attends over all
    valid rows (with sliding window if configured), and returns
    (logits [B, V] fp32, k_cache', v_cache'[, (k_scales', v_scales')][, stats]).
    Intended to be jitted with the caches donated so XLA updates them in
    place.

    ``active`` — slots marked False write their (ignored) K/V to the
    sacrificial last cache row and attend over zero rows, so an inactive or
    mid-chunked-prefill slot costs no cache bandwidth and cannot corrupt
    rows an incremental admission has already written. The fixed-shape
    graph still computes every slot's matmuls; the cache traffic and
    writes are gated, and in a model with a router an inactive slot's row
    picks no expert (moe.moe_ffn_visit). None means all slots active.

    ``kernels`` — None picks the Pallas ragged-attention kernel on TPU
    (reads only rows [0, length] per slot from HBM); False forces the naive
    full-cache path (required when the cache is sharded over a mesh — the
    kernel is per-device).

    ``cache_scales`` — (k_scales, v_scales) [L, B, C, KH] f32 marks an int8
    KV cache: new rows are quantized per (row, head) on write and the cache
    dequantizes while being read — half the cache HBM traffic and footprint
    of bf16 (the attention math itself stays bf16/fp32).

    ``attn_impl`` — explicit attention callable, overriding the kernel
    ladder; used by the tensor-parallel engine to run the ragged kernel
    per-device under shard_map (ShardingPlan.ragged_attention). bf16
    caches only.
    """
    B = tokens.shape[0]
    C = k_cache.shape[2]
    quant_cache = cache_scales is not None
    use_kernel = attn_impl is None and _use_ragged_kernel(
        kernels, C, cfg, quant_cache
    )
    # int8-KV ragged kernel: scales fold into the score/value dots so the
    # cache streams as int8 (half the bytes) AND only valid rows DMA
    use_int8_kernel = (
        attn_impl is None
        and quant_cache
        and _use_ragged_kernel(
            kernels, C, cfg, quant_cache,
            quant_kernel_ok=_int8_ragged_enabled(),
        )
    )
    if active is None:
        write_rows = lengths
        read_lengths = lengths
        active = jnp.ones((B,), jnp.bool_)
    else:
        write_rows = jnp.where(active, lengths, C - 1)
        # read length -1 would be ideal; 0 exposes one (overwritten-before-
        # read for active slots, garbage-but-ignored otherwise) row, which
        # keeps the mask/kernel contract "row `length` was just written"
        read_lengths = jnp.where(active, lengths, 0)
    x = params["embed"][tokens][:, None, :]  # [B, 1, E]
    cos, sin = rope_tables_of(lengths[:, None], cfg.head_dim, cfg.rope_of(None))

    batch_idx = jnp.arange(B)
    if use_kernel or use_int8_kernel or attn_impl is not None:
        mask = None
    else:
        cols = jnp.arange(C)[None, :]
        # col j is visible if it holds a written token (j <= lengths, since
        # the new token is written before attending) and is inside the window
        mask = cols <= read_lengths[:, None]
        if cfg.sliding_window is not None:
            mask = mask & (cols > (read_lengths[:, None] - cfg.sliding_window))
        mask = mask[:, None, :]  # [B, 1, C]

    def block(carry, layer):
        x, stats = carry
        lp, k_l, v_l, *scales_l = layer
        k_s, v_s = scales_l or (None, None)
        q, k_new, v_new = _project_qkv(x, lp, cfg, cos, sin, qmm)
        if quant_cache:
            kq, ks_new = quantize_kv(k_new[:, 0])
            vq, vs_new = quantize_kv(v_new[:, 0])
            k_l = k_l.at[batch_idx, write_rows].set(kq)
            v_l = v_l.at[batch_idx, write_rows].set(vq)
            k_s = k_s.at[batch_idx, write_rows].set(ks_new)
            v_s = v_s.at[batch_idx, write_rows].set(vs_new)
            if use_int8_kernel:
                attn = ops.decode_attention_int8(
                    q[:, 0], k_l, v_l, k_s, v_s, read_lengths,
                    window=cfg.sliding_window,
                )[:, None]
            else:
                attn = gqa_attention(
                    q,
                    dequantize_kv(k_l, k_s, q.dtype),
                    dequantize_kv(v_l, v_s, q.dtype),
                    mask,
                )
        else:
            k_l = k_l.at[batch_idx, write_rows].set(k_new[:, 0].astype(k_l.dtype))
            v_l = v_l.at[batch_idx, write_rows].set(v_new[:, 0].astype(v_l.dtype))
            if attn_impl is not None:
                attn = attn_impl(q[:, 0], k_l, v_l, read_lengths)[:, None]
            elif use_kernel:
                attn = ops.decode_attention(
                    q[:, 0], k_l, v_l, read_lengths, window=cfg.sliding_window
                )[:, None]
            else:
                attn = gqa_attention(q, k_l, v_l, mask)
        x = x + matmul(attn.reshape(B, 1, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm, active)
        return (x, stats), (k_l, v_l, *((k_s, v_s) if quant_cache else ()))

    x, k_cache, v_cache, scales, stats = _scan_layers_over_cache(
        block, x, params, k_cache, v_cache, cache_scales, cfg,
        moe_mod.visit_serves(cfg, moe_dense),
    )
    logits = _final_logits(x[:, 0], params, cfg, qmm)
    if quant_cache:
        return (logits, k_cache, v_cache, scales, *stats)
    return (logits, k_cache, v_cache, *stats)


def layer_segments(params: Params) -> Tuple[dict, ...]:
    """The layer stack as homogeneous segments, each a tree stacked on a
    leading layer axis: the leading dense layers (``lead_layers``, where a
    model has them: their tree differs from an expert layer's), then the
    periodic stack (``layers``). One segment is every other model's case."""
    if "lead_layers" in params:
        return (params["lead_layers"], params["layers"])
    return (params["layers"],)


def _experts_apart(layers, apart: bool):
    """A stacked layer tree as (the part a layer scan slices, the expert
    stacks it keeps whole: none unless ``apart``)."""
    whole = {
        name: layers[name] for name in moe_mod.EXPERT_LEAVES
        if apart and name in layers
    }
    return {k: v for k, v in layers.items() if k not in whole}, whole


def _with_experts(lp, whole, l):
    """A scanned layer's tree with the whole expert stacks beside it and
    ``expert_layer``, its index into them (moe._experts_in_place and
    moe.moe_ffn_visit read it)."""
    return {**lp, **whole, "expert_layer": l} if whole else lp


def scan_segments(block, carry, segments, experts_whole: bool = False,
                  kinds: Tuple[str, ...] = (), lead_kinds: Tuple[str, ...] = ()):
    """Run ``block(carry, (layer_params, l))`` over every layer of every
    segment in turn, one ``lax.scan`` a segment, the carry (the residual,
    the page pools, the expert counters) going through all of them and the
    layer index ``l`` running on across segments. Returns (carry, what the
    blocks emitted, stacked over all layers; None where they emit nothing).

    ``experts_whole`` (a graph whose token count takes the grouped expert
    path, moe.grouped_serves, or a decode step, moe.visit_serves) keeps a
    segment's expert stacks OUT of the scanned operands: the block gets
    them whole, ``[L, X, in, out]``, with ``expert_layer``, the layer's
    index into them, and the loop or kernel reads ``w[l, e]`` where it
    lies. A scanned slice of them would be its operand, and so a copy of
    the layer's experts each layer.

    ``kinds`` (ModelConfig.period_kinds: a stack that mixes kinds of layer)
    makes the scan's body one PERIOD, its layers unrolled in it: the window,
    the rotary table and the pages a layer reads are constants of its place
    in the period, so ONE scan serves the whole stack where a scan a run of
    like layers would be ten. The block finds its layer's kind and place in
    the tree (``layer_kind``, ``layer_in_period``; ``kind_of``).
    ``lead_kinds`` are the kinds of the leading dense layers before the
    pattern (ModelConfig.lead_kinds), where the stack has them."""
    if kinds:
        return _scan_periods(block, carry, segments, kinds, lead_kinds)
    first, emitted = 0, []
    for seg in segments:
        n = jax.tree.leaves(seg)[0].shape[0]
        scanned, whole = _experts_apart(seg, experts_whole)

        def layer_block(carry, layer, whole=whole, first=first):
            lp, l = layer
            return block(carry, (_with_experts(lp, whole, l - first), l))

        carry, ys = jax.lax.scan(
            layer_block, carry, (scanned, jnp.arange(first, first + n))
        )
        emitted.append(ys)
        first += n
    return carry, _join_emitted(emitted)


def _join_emitted(emitted):
    """What the segments' scans emitted, as one stack over all layers."""
    if emitted[0] is None:
        return None
    if len(emitted) == 1:
        return emitted[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *emitted)


def _scan_periods(block, carry, segments, kinds, lead_kinds=()):
    """``scan_segments`` for a stack that mixes kinds of layer: one scan over
    the periods of its periodic segment, the period's layers unrolled in the
    body; a segment of leading dense layers before it (``lead_kinds``, all of
    one kind) is a scan of periods of one.

    The layers of a period may differ in their TREES too (a ``kda`` and an
    ``mla`` mixer): what every layer has alike (norms, the FFN) is stacked
    over all of the segment's layers, and a kind's own leaves over that
    kind's layers alone, under ``by_kind``; the block's tree is the two
    together, with ``kind_index``, the layer's place among the stack's
    layers of its kind (the index of its state or of its pages' layer).

    In a stack of sub-layers (engine/mamba2.py) the FFN is a kind's own too:
    all that the layers have alike is their one norm, and the ``moe`` kind's
    leaves, its expert stacks among them, stand under ``by_kind`` like a
    mixer's (``expert_layer`` is then the layer's place among the moe layers).

    The expert stacks stay whole at EVERY token count, so model.ffn reads
    them in place (the visit path in a decode step, the grouped path
    elsewhere, below moe.grouped_pays too): the body slices the stacked
    leaves itself, and there the dense product's layout change of a sliced
    expert stack is hoisted by the TPU compiler to a copy of the WHOLE stack
    (4.9 GB at 64 experts of 2304 x 1792 over 20 layers:
    tests/test_mosaic_aot.py -k two_kinds). Below ``grouped_pays`` the
    grouped path computes up to a tile an expert where the dense path
    computes a few rows, and reads the same bytes."""
    *lead, seg = segments
    first, emitted = 0, []
    for lead_seg in lead:
        (kind,) = set(lead_kinds)
        n = jax.tree.leaves(lead_seg)[0].shape[0]

        def lead_block(carry, layer, kind=kind):
            lp, l = layer
            return block(carry, (
                {**lp, "layer_kind": kind, "layer_in_period": 0,
                 "kind_index": l}, l,
            ))

        carry, ys = jax.lax.scan(
            lead_block, carry, (lead_seg, jnp.arange(first, first + n))
        )
        emitted.append(ys)
        first += n
    period = len(kinds)
    by_kind = seg.get("by_kind", {})
    common = {k: v for k, v in seg.items() if k != "by_kind"}
    n = jax.tree.leaves(common)[0].shape[0]
    scanned, whole = _experts_apart(common, True)
    own_leaves = {k: _experts_apart(v, True) for k, v in by_kind.items()}
    # a layer's place among its period's layers of its kind, and how many
    # layers of each kind stand before the pattern
    place = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
    before = {kind: lead_kinds.count(kind) for kind in set(kinds)}

    def period_block(carry, l0):
        ys = []
        for i, kind in enumerate(kinds):
            # layer l0 + i of the stacked leaves, sliced where the block
            # runs: scanning them as [L / period, period, ..] operands made
            # the compiler copy the whole stack into another layout (4.9 GB
            # of expert weights at the benchmark's widths)
            lp = jax.tree.map(
                lambda a, i=i: jax.lax.dynamic_index_in_dim(
                    a, l0 + i, 0, keepdims=False
                ), scanned,
            )
            lp = {**_with_experts(lp, whole, l0 + i),
                  "layer_kind": kind, "layer_in_period": i}
            if by_kind:
                own = l0 // period * kinds.count(kind) + place[i]
                sliced, stacks = own_leaves[kind]
                lp.update(jax.tree.map(
                    lambda a, own=own: jax.lax.dynamic_index_in_dim(
                        a, own, 0, keepdims=False
                    ), sliced,
                ), kind_index=before[kind] + own)
                lp = _with_experts(lp, stacks, own)
            carry, y = block(carry, (lp, first + l0 + i if first else l0 + i))
            ys.append(y)
        if ys[0] is None:
            return carry, None
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    carry, ys = jax.lax.scan(period_block, carry, jnp.arange(0, n, period))
    if ys is not None:
        ys = jax.tree.map(lambda a: a.reshape(n, *a.shape[2:]), ys)
    emitted.append(ys)
    return carry, _join_emitted(emitted)


def _scan_layers_over_cache(block, x, params, k_cache, v_cache, cache_scales,
                            cfg: ModelConfig, experts_whole: bool = False):
    """The dense-cache graphs' layer scan: layer ``l``'s slices of the two
    caches (and of an int8 cache's scales) are scanned operands and results,
    the residual and the expert counters the carry.

    ``block((x, stats), (layer_params, k_l, v_l, *scales_l))`` returns
    ((x, stats), (k_l, v_l, *scales_l)). Returns (x, k_cache, v_cache,
    scales-or-None, stats)."""
    layers, whole = _experts_apart(params["layers"], experts_whole)

    def layer_block(carry, layer):
        lp, l, *caches = layer
        return block(carry, (_with_experts(lp, whole, l), *caches))

    xs = (layers, jnp.arange(k_cache.shape[0]), k_cache, v_cache,
          *(cache_scales or ()))
    (x, stats), (k_cache, v_cache, *scales) = jax.lax.scan(
        layer_block, (x, zero_stats(cfg)), xs
    )
    return x, k_cache, v_cache, tuple(scales) or None, stats


def _scan_layers_over_pool(block, x, params, k_pool, v_pool, cache_scales,
                           cfg: ModelConfig, experts_whole: bool = False):
    """Run ``block`` over the layer stack with the page pools (and int8
    scales) as the scan CARRY: layer ``l`` reads and writes
    ``pool[l, page, row]`` in place. Scanning the pools as xs -> ys instead
    makes XLA hold a second whole pool while the loop runs (the stacked ys
    buffer cannot share the donated input's) — on a 16 GB chip that is the
    difference between Mistral-7B's 4096-row context fitting and not.

    ``block((x, k_pool, v_pool, scales, stats), (layer_params, l))``
    returns the same carry: ``scales`` the pair of an int8 pool or (),
    ``stats`` from ``zero_stats(cfg)``. Returns (x, k_pool, v_pool,
    scales-or-None, stats)."""
    carry = (x, k_pool, v_pool, tuple(cache_scales or ()), zero_stats(cfg))
    (x, k_pool, v_pool, scales, stats), _ = scan_segments(
        block, carry, layer_segments(params), experts_whole, cfg.period_kinds
    )
    return x, k_pool, v_pool, scales or None, stats


def chunk_pages(table_row, start, Tc: int, P: int):
    """Where a chunk's rows [start, start+Tc) land (ops.write_rows: whole
    pages, or inside one): the pages, and the row of the first page it
    starts on."""
    if Tc >= P:  # page-aligned chunk spanning Tc/P whole pages
        nb = Tc // P
        # pad with sacrificial entries so a final bucket whose padding
        # overruns max_context (possible when a prefix match de-aligns
        # chunk starts) slices cleanly: overflow rows land on page 0
        # instead of dynamic_slice clamping the start a block early and
        # corrupting the previous chunk's rows
        table_ext = jnp.concatenate(
            [table_row, jnp.zeros((nb,), table_row.dtype)]
        )
        pages = jax.lax.dynamic_slice(table_ext, (start // P,), (nb,))
    else:  # chunk inside one page
        pages = jax.lax.dynamic_slice(table_row, (start // P,), (1,))
    return pages, start % P


def prefill_chunk_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [1, Tc] int32 — one chunk of one prompt
    start: jnp.ndarray,  # scalar int32 — absolute position of tokens[0]
    k_pool: jnp.ndarray,  # [L, N, P, KH*D]
    v_pool: jnp.ndarray,  # [L, N, P, KH*D]
    table_row: jnp.ndarray,  # [MB] int32 — the slot's block->page map
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    qmm=None,  # int4 matmul impl (x, leaf, kind) -> y; see matmul()
    win_start: Optional[jnp.ndarray] = None,  # scalar: live window start
    sink_rows: int = 0,  # static sink rows (window+sink KV compression)
    moe_dense: bool = False,
    layout=None,  # paged.PoolLayout: the pool of a stack of two kinds
    states=(),  # the state kind's arrays (a stack with kda layers), and
    slot=None,  # whose state the chunk advances,
    n_valid=None,  # and how many of its rows are real (None: all)
):
    """One chunk of an incremental prefill against the PAGED cache.

    Same contract as ``prefill_chunk`` (write rows [start, start+Tc) of the
    slot, attend each chunk token over everything written so far), with the
    rows scattered into the page pool through ``table_row``. Because chunk
    sizes and page sizes are both powers of two, a chunk either spans whole
    pages (Tc >= P, start page-aligned) or sits inside one page (Tc < P) —
    the rows are written by whole pages or as one slice of a page
    (ops.write_rows), never by an index-array scatter of single rows.
    Chunk attention makes no view of the slot: its online softmax gathers
    one key tile at a time from the pool through ``table_row``
    (``paged_kv_block``) and ends at the tile of the chunk's last row
    (``chunk_kv_tiles``), so a chunk reads the pages its rows can see and
    not the table's ``MB x P`` rows. The caller must have backed rows
    [0, start+Tc) — unbacked blocks map the sacrificial page 0, which the
    mask never exposes below ``start+Tc``.

    ``cache_scales`` marks an int8 pool (rows quantize on write, a
    gathered tile dequantizes). Returns (logits [1, Tc, V] fp32, k_pool',
    v_pool'[, scales'][, stats]).

    ``layout`` (a stack of window and full layers; ``table_row`` is then the
    slot's two tables side by side): a layer writes and reads its kind's
    pages of its period (engine/paged.py header), a full layer the slot's
    pages up to the chunk's end, a window layer those from its window's
    first block to the chunk's end.
    """
    if cfg.mla:
        from . import latent

        return latent.prefill_chunk_paged(
            params, cfg, tokens, start, k_pool, v_pool, table_row,
            qmm=qmm, moe_dense=moe_dense, states=states, slot=slot,
            n_valid=n_valid,
        )
    if cfg.sublayers:
        from . import mamba2

        return mamba2.prefill_chunk_paged(
            params, cfg, tokens, start, k_pool, v_pool, table_row,
            qmm=qmm, moe_dense=moe_dense, states=states, slot=slot,
            n_valid=n_valid,
        )
    B, Tc = tokens.shape
    P = k_pool.shape[2]
    quant_pool = cache_scales is not None
    x = params["embed"][tokens]  # [1, Tc, E]
    positions = start + jnp.arange(Tc)[None, :]  # [1, Tc]
    if layout is not None:
        return _prefill_chunk_kinds(
            params, cfg, x, positions, start, k_pool, v_pool, table_row,
            qmm, moe_dense, layout,
        )
    MB = table_row.shape[0]
    C_log = MB * P
    cos, sin = rope_tables_of(positions, cfg.head_dim, cfg.rope_of(None))

    pages, off = chunk_pages(table_row, start, Tc, P)

    kv_tile = _kv_tile(C_log, P)
    n_tiles = chunk_kv_tiles(start, Tc, C_log, kv_tile)

    def block(carry, layer):
        x, k_pool, v_pool, scales, stats = carry
        lp, l = layer
        q, k_new, v_new = _project_qkv(x, lp, cfg, cos, sin, qmm)
        if quant_pool:
            k_s, v_s = scales
            kq, ks = quantize_kv(k_new[0])
            vq, vs = quantize_kv(v_new[0])
            k_pool = ops.write_rows(k_pool, l, ops.merge_heads(kq), pages, off)
            v_pool = ops.write_rows(v_pool, l, ops.merge_heads(vq), pages, off)
            k_s = ops.write_rows(k_s, l, ks, pages, off)
            v_s = ops.write_rows(v_s, l, vs, pages, off)
            scales = (k_s, v_s)
        else:
            k_pool = ops.write_rows(
                k_pool, l, ops.merge_heads(k_new[0]), pages, off
            )
            v_pool = ops.write_rows(
                v_pool, l, ops.merge_heads(v_new[0]), pages, off
            )
        attn = blockwise_cache_attention(
            q,
            paged_kv_block(k_pool, v_pool, l, table_row, kv_tile,
                           cfg.head_dim, q.dtype, scales),
            n_tiles,
            positions[0],
            cfg.sliding_window,
            live_from=win_start,
            sink=sink_rows,
        )
        x = x + matmul(attn.reshape(B, Tc, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm)
        return (x, k_pool, v_pool, tuple(scales), stats), None

    x, k_pool, v_pool, scales, stats = _scan_layers_over_pool(
        block, x, params, k_pool, v_pool, cache_scales, cfg,
        moe_mod.grouped_serves(B * Tc, cfg, moe_dense),
    )
    logits = _final_logits(x, params, cfg, qmm)
    if quant_pool:
        return (logits, k_pool, v_pool, scales, *stats)
    return (logits, k_pool, v_pool, *stats)


def _kv_tile(rows: int, P: int) -> int:
    """The key tile of a chunk's blockwise attention over ``rows`` rows of
    pages of ``P``: whole pages."""
    t = min(512, rows)
    return t if rows % t == 0 and t % P == 0 else P


def paged_kv_block(k_pool, v_pool, layer, pages, tile: int, head_dim: int,
                   dtype, scales=()):
    """``blockwise_cache_attention``'s ``kv_block`` over a slot's pages:
    tile j is the ``tile // P`` pages ``pages[j * bp:(j + 1) * bp]`` of
    ``layer``, gathered from the pool where they lie and split to heads
    [tile, KH, D]. An int8 pool (``scales`` = its (k_s, v_s)) dequantizes
    the tile it gathered."""
    bp = tile // k_pool.shape[2]

    def kv_block(j):
        pg = jax.lax.dynamic_slice_in_dim(pages, j * bp, bp)
        if scales:
            return tuple(
                gather_dequant(pool, s, layer, pg, dtype)
                for pool, s in zip((k_pool, v_pool), scales)
            )
        return tuple(
            ops.gather_pages(pool, layer, pg, head_dim).astype(dtype)
            for pool in (k_pool, v_pool)
        )

    return kv_block


def window_chunk_blocks(window: int, Tc: int, P: int) -> int:
    """Pages a window layer's chunk attention gathers: those that hold a
    row of [start - window + 1, start + Tc), for a page-aligned ``start``."""
    return -(-(window - 1) // P) + -(-Tc // P)


def _prefill_chunk_kinds(params, cfg: ModelConfig, x, positions, start,
                         k_pool, v_pool, table_row, qmm, moe_dense, layout):
    """``prefill_chunk_paged`` for a stack of window and full layers (bf16
    pool): the same block, each layer with its kind's table, page range,
    window and rotary table."""
    B, Tc = x.shape[:2]
    P = k_pool.shape[2]
    period = len(layout.kinds)
    ropes = rope_by_kind(positions, cfg)
    tables = {k: layout.table_of(table_row, k) for k in ropes}
    W = cfg.sliding_window
    # the window kind reads from its window's first block: the first row a
    # query of this chunk sees is start - W + 1
    nbw = window_chunk_blocks(W, Tc, P)
    first_blk = jnp.maximum(start - W + 1, 0) // P

    def view(pages, col0, rows):
        """A kind's view of the slot: (its pages, the position of its first
        row, its key tile, how many of its tiles this chunk folds)."""
        tile = _kv_tile(rows, P)
        return pages, col0, tile, chunk_kv_tiles(start, Tc, rows, tile, col0)

    reads = {
        "full": view(tables["full"], 0, layout.max_blocks * P),
        "window": view(
            jax.lax.dynamic_slice(
                jnp.concatenate(
                    [tables["window"], jnp.zeros((nbw,), table_row.dtype)]
                ), (first_blk,), (nbw,),
            ),
            first_blk * P, nbw * P,
        ),
    }
    writes = {k: chunk_pages(tables[k], start, Tc, P) for k in ropes}

    def block(carry, layer):
        x, k_pool, v_pool, scales, stats = carry
        lp, l = layer
        kind, base = kind_of(lp), layout.bases[lp["layer_in_period"]]
        at = l // period  # the period whose pages this layer's are
        q, k_new, v_new = _project_qkv(x, lp, cfg, *ropes[kind], qmm)
        pages, off = writes[kind]
        k_pool = ops.write_rows(
            k_pool, at, ops.merge_heads(k_new[0]), pages + base, off
        )
        v_pool = ops.write_rows(
            v_pool, at, ops.merge_heads(v_new[0]), pages + base, off
        )
        read, col0, tile, n_tiles = reads[kind]
        with jax.named_scope(f"attention_{kind}"):
            attn = blockwise_cache_attention(
                q,
                paged_kv_block(k_pool, v_pool, at, read + base, tile,
                               cfg.head_dim, q.dtype),
                n_tiles, positions[0], cfg.window_of(kind), col0=col0,
            )
        x = x + matmul(attn.reshape(B, Tc, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm)
        return (x, k_pool, v_pool, scales, stats), None

    x, k_pool, v_pool, _, stats = _scan_layers_over_pool(
        block, x, params, k_pool, v_pool, None, cfg,
        moe_mod.grouped_serves(B * Tc, cfg, moe_dense),
    )
    return (_final_logits(x, params, cfg, qmm), k_pool, v_pool, *stats)


def chunk_tiles_on_host(cfg: ModelConfig, start: int, Tc: int,
                        max_blocks: int, P: int) -> Tuple[int, int]:
    """(key tiles folded, key tiles the slot's table maps) by one chunk of
    ``prefill_chunk_paged``, summed over the attention layers whose view is
    the slot's whole table (every layer of a one-kind stack, the ``full``
    kind of a stack of two), reckoned from the host's ints where the chunk
    is issued: the engine's ``prefill_kv_tiles_read`` / ``_mapped``. A window
    kind's view is already its window and the chunk (``kv_window_pages_*``
    tell how far that engages), and a latent stack's chunks fold in
    engine/latent.py: neither counts here."""
    if cfg.mla:
        return 0, 0
    layers = cfg.layers_of("full") if cfg.kinds else cfg.row_layers
    rows = max_blocks * P
    tile = _kv_tile(rows, P)
    return (layers * chunk_kv_tiles(start, Tc, rows, tile, minimum=min),
            layers * (rows // tile))


def write_prompt_rows(pools, rows, table_row, layout=None):
    """A whole prompt's rows ``[L, T, W]`` (one array a pool) into the pools
    through the slot's table(s), by whole pages from row 0 of its first
    page. A pool by kind (``layout``) takes each layer's rows at its kind's
    pages of its period."""
    if layout is None:
        return tuple(
            ops.write_rows(p, None, r, table_row) for p, r in zip(pools, rows)
        )
    period = len(layout.kinds)
    out = []
    for pool, r in zip(pools, rows):
        r = r.reshape(r.shape[0] // period, period, *r.shape[1:])
        for i, kind in enumerate(layout.kinds):
            pool = ops.write_rows(
                pool, None, r[:, i],
                layout.table_of(table_row, kind) + layout.bases[i],
            )
        out.append(pool)
    return tuple(out)


def decode_step_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] int32 — one new token per slot
    lengths: jnp.ndarray,  # [B] int32 — logical rows already in each slot
    k_pool: jnp.ndarray,  # [L, N, P, KH*D] — shared page pool
    v_pool: jnp.ndarray,  # [L, N, P, KH*D]
    tables: jnp.ndarray,  # [B, MB] int32 — logical block -> physical page
    kernels: Optional[bool] = None,
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    active: Optional[jnp.ndarray] = None,  # [B] bool
    moe_dense: bool = False,
    qmm=None,  # int4 matmul impl (x, leaf, kind) -> y; see matmul()
    pool_impl=None,  # per-device pool write+attend; see ShardingPlan
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink_rows: int = 0,  # static sink rows (window+sink KV compression)
    layout=None,  # paged.PoolLayout: the pool of a stack of two kinds
    states=(),  # the state kind's arrays (a stack with kda layers)
):
    """One batched decode step over the PAGED slot cache.

    Identical contract to ``decode_step`` except K/V rows live in a shared
    page pool read through per-slot tables (ops/paged_attention.py): the
    new row is scattered to (page ``tables[b, lengths[b] // P]``, offset
    ``lengths[b] % P``), and attention reads only the pages that hold valid
    rows. The pool rides the layer scan as its carry and is handed to the
    attention whole, with the layer's index: of the pool, a step touches
    the rows it scatters and the pages the tables name, and makes no slice
    or relayout of a layer's pages (tests/test_paged_kernel.py holds the
    step's jaxpr to that). Inactive slots write the sacrificial page 0
    (paged.py) and read zero rows. The caller must have BACKED row
    ``lengths[b]`` for every active slot (PageAllocator.ensure) — an
    unbacked entry maps page 0 and would silently cross-talk through the
    sacrificial page.

    ``cache_scales`` — (k_scales, v_scales) [L, N, P, KH] f32 marks an
    int8 POOL: rows quantize on write; attention either streams the int8
    pages through the paged kernel with scales folded into the dots
    (AIOS_TPU_INT8_RAGGED=1, ops.paged_decode_attention_int8) or
    dequantizes a gathered per-slot view on the XLA path. Returns
    (logits [B, V] fp32, k_pool', v_pool'[, (k_scales', v_scales')][, stats]).

    ``win_starts``/``sink_rows`` (window+sink KV compression,
    docs/ENGINE_PERF.md "Long-context tier"): slot b attends only rows
    < sink_rows or >= win_starts[b]; its pruned middle pages were
    released back to the pool and the stale table entries map the
    sacrificial page. win_starts[b] = 0 makes the mask a no-op.
    Unsupported with ``pool_impl`` (the dp-replicated shard_map twin —
    the engine never arms compression there).

    ``layout`` (a stack of window and full layers; ``tables`` holds the two
    tables a slot side by side): a layer writes its row to, and reads, its
    kind's pages of its period (engine/paged.py header), a window layer
    through ``ops.window_decode_attention``. bf16 pool, no ``pool_impl``.
    """
    if cfg.mla:
        from . import latent

        return latent.decode_step_paged(
            params, cfg, tokens, lengths, k_pool, v_pool, tables,
            kernels=kernels, active=active, moe_dense=moe_dense, qmm=qmm,
            states=states,
        )
    if cfg.sublayers:
        from . import mamba2

        return mamba2.decode_step_paged(
            params, cfg, tokens, lengths, k_pool, v_pool, tables,
            kernels=kernels, active=active, moe_dense=moe_dense, qmm=qmm,
            states=states,
        )
    if layout is not None:
        return _decode_step_kinds(
            params, cfg, tokens, lengths, k_pool, v_pool, tables, kernels,
            active, moe_dense, qmm, layout,
        )
    B = tokens.shape[0]
    P = k_pool.shape[2]
    quant_pool = cache_scales is not None
    if win_starts is not None and pool_impl is not None:
        raise ValueError(
            "window+sink KV compression has no dp-replicated pool twin"
        )
    use_kernel = _use_kernels(kernels) and not quant_pool
    # int8 pool through the paged kernel (same env gate as the dense int8
    # ragged kernel): pages stream as int8 with scales folded into the dots
    use_int8_kernel = (
        _use_kernels(kernels) and quant_pool and _int8_ragged_enabled()
    )
    if active is None:
        write_pages_of = lengths
        read_lengths = lengths
        act = jnp.ones((B,), jnp.bool_)
    else:
        act = active
        write_pages_of = jnp.where(active, lengths, 0)
        read_lengths = jnp.where(active, lengths, 0)
    blk = write_pages_of // P
    pages = jnp.where(
        act, jnp.take_along_axis(tables, blk[:, None], axis=1)[:, 0], 0
    )
    offs = jnp.where(act, write_pages_of % P, P - 1)
    if win_starts is not None:
        # inactive slots read zero rows; a stale window start must not
        # survive into their (ignored) mask either
        win_starts = jnp.where(act, win_starts, 0)

    # jax.named_scope below names the parts of one step in the operations'
    # metadata (what a profile's op_name shows); no module or instruction
    # is renamed by it
    with jax.named_scope("embed"):
        x = params["embed"][tokens][:, None, :]  # [B, 1, E]
        cos, sin = rope_tables_of(lengths[:, None], cfg.head_dim, cfg.rope_of(None))
    ffn_scope = "moe" if cfg.num_experts else "ffn"

    def block(carry, layer):
        x, k_pool, v_pool, scales, stats = carry
        lp, l = layer
        # no scope of its own: one around the projection renumbers the
        # compiled graph's instructions, and the benchmark's records name
        # operations by those numbers
        q, k_new, v_new = _project_qkv(x, lp, cfg, cos, sin, qmm)
        if pool_impl is not None:
            # the shard_map twin writes and attends one layer's pool slice
            if quant_pool:
                k_s, v_s = scales
                attn, k_l, v_l, k_sl, v_sl = pool_impl(
                    q[:, 0], k_new[:, 0], v_new[:, 0], k_pool[l], v_pool[l],
                    k_s[l], v_s[l], tables, read_lengths, pages, offs,
                )
                scales = (k_s.at[l].set(k_sl), v_s.at[l].set(v_sl))
            else:
                attn, k_l, v_l = pool_impl(
                    q[:, 0], k_new[:, 0], v_new[:, 0], k_pool[l], v_pool[l],
                    tables, read_lengths, pages, offs,
                )
            k_pool = k_pool.at[l].set(k_l)
            v_pool = v_pool.at[l].set(v_l)
            attn = attn[:, None]
        elif quant_pool:
            k_s, v_s = scales
            with jax.named_scope("kv_write"):
                k_pool, k_s = scatter_quant(
                    k_pool, k_s, (l, pages, offs), k_new[:, 0]
                )
                v_pool, v_s = scatter_quant(
                    v_pool, v_s, (l, pages, offs), v_new[:, 0]
                )
            with jax.named_scope("attention"):
                attn = paged_int8_attend(
                    q[:, 0], k_pool, v_pool, k_s, v_s, l, tables,
                    read_lengths,
                    window=cfg.sliding_window,
                    use_int8_kernel=use_int8_kernel,
                    win_starts=win_starts, sink=sink_rows,
                )[:, None]
            scales = (k_s, v_s)
        else:
            with jax.named_scope("kv_write"):
                k_pool = k_pool.at[l, pages, offs].set(
                    ops.merge_heads(k_new[:, 0]).astype(k_pool.dtype)
                )
                v_pool = v_pool.at[l, pages, offs].set(
                    ops.merge_heads(v_new[:, 0]).astype(v_pool.dtype)
                )
            # the carried pool goes to the kernel whole, with the layer's
            # index: it reads the pages the tables name where they lie
            with jax.named_scope("attention"):
                if use_kernel:
                    attn = ops.paged_decode_attention(
                        q[:, 0], k_pool, v_pool, l, tables, read_lengths,
                        window=cfg.sliding_window,
                        win_starts=win_starts,
                        sink=sink_rows if win_starts is not None else None,
                    )[:, None]
                else:
                    attn = ops.paged_decode_attention_reference(
                        q[:, 0], k_pool, v_pool, l, tables, read_lengths,
                        window=cfg.sliding_window,
                        win_starts=win_starts, sink=sink_rows,
                    )[:, None]
        with jax.named_scope("attn_out"):
            x = x + matmul(attn.reshape(B, 1, -1), lp["wo"], qmm, "row")
        with jax.named_scope(ffn_scope):
            x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm, act)
        return (x, k_pool, v_pool, tuple(scales), stats), None

    x, k_pool, v_pool, scales, stats = _scan_layers_over_pool(
        block, x, params, k_pool, v_pool, cache_scales, cfg,
        moe_mod.visit_serves(cfg, moe_dense),
    )
    with jax.named_scope("final_logits"):
        logits = _final_logits(x[:, 0], params, cfg, qmm)
    if quant_pool:
        return (logits, k_pool, v_pool, scales, *stats)
    return (logits, k_pool, v_pool, *stats)


def _decode_step_kinds(params, cfg: ModelConfig, tokens, lengths, k_pool,
                       v_pool, tables, kernels, active, moe_dense, qmm, layout):
    """``decode_step_paged`` for a stack of window and full layers (bf16
    pool): the same step, each layer with its kind's table, page range,
    window and rotary table."""
    B = tokens.shape[0]
    P = k_pool.shape[2]
    period = len(layout.kinds)
    use_kernel = _use_kernels(kernels)
    act = jnp.ones((B,), jnp.bool_) if active is None else active
    at_rows = jnp.where(act, lengths, 0)  # an inactive slot reads no row
    offs = jnp.where(act, at_rows % P, P - 1)
    with jax.named_scope("embed"):
        x = params["embed"][tokens][:, None, :]  # [B, 1, E]
        ropes = rope_by_kind(lengths[:, None], cfg)
    by_kind = {k: layout.table_of(tables, k) for k in ropes}
    # the page of its kind each slot's new row goes to; an inactive slot's
    # goes to the range's sacrificial page (+ base below)
    pages = {
        k: jnp.where(
            act, jnp.take_along_axis(t, (at_rows // P)[:, None], axis=1)[:, 0], 0
        ) for k, t in by_kind.items()
    }

    def attend(kind, q, k_pool, v_pool, at, tbl):
        # the window kind's kernel is the full kind's under a jitted name of
        # its own (ops.window_decode_attention): a device trace tells the
        # two kinds' attention apart by it
        window = {"window": cfg.sliding_window} if kind == "window" else {}
        if not use_kernel:
            fn = ops.paged_decode_attention_reference
        elif window:
            fn = ops.window_decode_attention
        else:
            fn = ops.paged_decode_attention
        return fn(q, k_pool, v_pool, at, tbl, at_rows, **window)

    def block(carry, layer):
        x, k_pool, v_pool, scales, stats = carry
        lp, l = layer
        kind, base = kind_of(lp), layout.bases[lp["layer_in_period"]]
        at = l // period  # the period whose pages this layer's are
        q, k_new, v_new = _project_qkv(x, lp, cfg, *ropes[kind], qmm)
        with jax.named_scope("kv_write"):
            k_pool = k_pool.at[at, pages[kind] + base, offs].set(
                ops.merge_heads(k_new[:, 0]).astype(k_pool.dtype)
            )
            v_pool = v_pool.at[at, pages[kind] + base, offs].set(
                ops.merge_heads(v_new[:, 0]).astype(v_pool.dtype)
            )
        with jax.named_scope(f"attention_{kind}"):
            attn = attend(
                kind, q[:, 0], k_pool, v_pool, at, by_kind[kind] + base
            )[:, None]
        with jax.named_scope("attn_out"):
            x = x + matmul(attn.reshape(B, 1, -1), lp["wo"], qmm, "row")
        with jax.named_scope("moe" if cfg.num_experts else "ffn"):
            x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm, act)
        return (x, k_pool, v_pool, scales, stats), None

    x, k_pool, v_pool, _, stats = _scan_layers_over_pool(
        block, x, params, k_pool, v_pool, None, cfg,
        moe_mod.visit_serves(cfg, moe_dense),
    )
    with jax.named_scope("final_logits"):
        logits = _final_logits(x[:, 0], params, cfg, qmm)
    return (logits, k_pool, v_pool, *stats)


def verify_step_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32 — [last_token, draft_0..draft_{T-2}]
    lengths: jnp.ndarray,  # [B] int32
    k_pool: jnp.ndarray,  # [L, N, P, KH*D]
    v_pool: jnp.ndarray,  # [L, N, P, KH*D]
    tables: jnp.ndarray,  # [B, MB] int32
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    active: Optional[jnp.ndarray] = None,  # [B] bool
    moe_dense: bool = False,
    qmm=None,  # int4 matmul impl (x, leaf, kind) -> y; see matmul()
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink_rows: int = 0,  # static sink rows (window+sink KV compression)
    layout=None,  # paged.PoolLayout: the pool of a stack of two kinds
):
    """``verify_step`` over the PAGED cache: the T in-flight rows scatter
    through the page tables (inactive slots -> sacrificial page 0), and
    attention reads each slot's gathered logical view with the same causal
    mask. Same saturated-slot caveat as the dense version: rows clamped at
    the cache end collide, so callers must not consume tokens from
    saturated slots. The caller must have BACKED rows
    ``lengths[b] .. lengths[b]+T-1`` for every active slot.
    ``cache_scales`` marks an int8 pool.

    Returns (logits [B, T, V] fp32, k_pool', v_pool'[, scales'][, stats]).
    """
    if cfg.mla:
        from . import latent

        return latent.verify_step_paged(
            params, cfg, tokens, lengths, k_pool, v_pool, tables,
            active=active, moe_dense=moe_dense, qmm=qmm,
        )
    if cfg.state_kinds:
        raise ValueError(
            f"{cfg.name}: a verify step over {cfg.state_kind} layers would "
            "have to roll a rejected token back out of the recurrent state; "
            "no graph does"
        )
    B, T = tokens.shape
    MB = tables.shape[1] if layout is None else layout.max_blocks
    P = k_pool.shape[2]
    C = MB * P
    quant_pool = cache_scales is not None
    if active is None:
        active = jnp.ones((B,), jnp.bool_)
    if layout is not None:
        return _verify_step_kinds(
            params, cfg, tokens, lengths, k_pool, v_pool, tables, active,
            moe_dense, qmm, layout,
        )
    offs_t = jnp.arange(T)[None, :]
    positions = lengths[:, None] + offs_t  # [B, T]
    rows = jnp.minimum(positions, C - 1)
    blk = rows // P
    pages = jnp.take_along_axis(tables, blk, axis=1)  # [B, T] (tiny gather)
    pages = jnp.where(active[:, None], pages, 0)
    offs = jnp.where(active[:, None], rows % P, P - 1)
    qpos = jnp.where(active[:, None], positions, 0)
    cols = jnp.arange(C)[None, None, :]
    mask = cols <= qpos[..., None]  # [B, T, C]
    if cfg.sliding_window is not None:
        mask = mask & (cols > (qpos[..., None] - cfg.sliding_window))
    if win_starts is not None:
        # window+sink KV compression: the pruned middle [sink, win_start)
        # must not score — the verify rows themselves always land past
        # the live window start (they extend the trailing window)
        ws = jnp.where(active, win_starts, 0)
        mask = mask & (
            (cols < sink_rows) | (cols >= ws[:, None, None])
        )

    x = params["embed"][tokens]  # [B, T, E]
    cos, sin = rope_tables_of(positions, cfg.head_dim, cfg.rope_of(None))

    def block(carry, layer):
        x, k_pool, v_pool, scales, stats = carry
        lp, l = layer
        q, k_new, v_new = _project_qkv(x, lp, cfg, cos, sin, qmm)
        if quant_pool:
            k_s, v_s = scales
            k_pool, k_s = scatter_quant(k_pool, k_s, (l, pages, offs), k_new)
            v_pool, v_s = scatter_quant(v_pool, v_s, (l, pages, offs), v_new)
            k_all = gather_dequant(k_pool, k_s, l, tables, q.dtype)
            v_all = gather_dequant(v_pool, v_s, l, tables, q.dtype)
            scales = (k_s, v_s)
        else:
            k_pool = k_pool.at[l, pages, offs].set(
                ops.merge_heads(k_new).astype(k_pool.dtype)
            )
            v_pool = v_pool.at[l, pages, offs].set(
                ops.merge_heads(v_new).astype(v_pool.dtype)
            )
            # logical per-slot views; same HBM bytes as the dense masked
            # read
            k_all = ops.gather_pages(k_pool, l, tables, cfg.head_dim)
            v_all = ops.gather_pages(v_pool, l, tables, cfg.head_dim)
        attn = gqa_attention(q, k_all, v_all, mask)
        x = x + matmul(attn.reshape(B, T, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm)
        return (x, k_pool, v_pool, tuple(scales), stats), None

    x, k_pool, v_pool, scales, stats = _scan_layers_over_pool(
        block, x, params, k_pool, v_pool, cache_scales, cfg,
        moe_mod.grouped_serves(B * T, cfg, moe_dense),
    )
    logits = _final_logits(x, params, cfg, qmm)
    if quant_pool:
        return (logits, k_pool, v_pool, scales, *stats)
    return (logits, k_pool, v_pool, *stats)


def _verify_step_kinds(params, cfg: ModelConfig, tokens, lengths, k_pool,
                       v_pool, tables, active, moe_dense, qmm, layout):
    """``verify_step_paged`` for a stack of window and full layers (bf16
    pool; what the grammar's jump-ahead dispatches): each layer scatters to
    and gathers its kind's pages of its period, under its kind's mask and
    rotary table."""
    B, T = tokens.shape
    P = k_pool.shape[2]
    C = layout.max_blocks * P
    period = len(layout.kinds)
    positions = lengths[:, None] + jnp.arange(T)[None, :]  # [B, T]
    rows = jnp.minimum(positions, C - 1)
    offs = jnp.where(active[:, None], rows % P, P - 1)
    qpos = jnp.where(active[:, None], positions, 0)
    cols = jnp.arange(C)[None, None, :]
    causal = cols <= qpos[..., None]  # [B, T, C]
    ropes = rope_by_kind(positions, cfg)
    by_kind = {k: layout.table_of(tables, k) for k in ropes}
    pages = {
        k: jnp.where(
            active[:, None], jnp.take_along_axis(t, rows // P, axis=1), 0
        ) for k, t in by_kind.items()
    }
    masks = {
        "full": causal,
        "window": causal & (cols > (qpos[..., None] - cfg.sliding_window)),
    }
    x = params["embed"][tokens]  # [B, T, E]

    def block(carry, layer):
        x, k_pool, v_pool, scales, stats = carry
        lp, l = layer
        kind, base = kind_of(lp), layout.bases[lp["layer_in_period"]]
        at = l // period
        q, k_new, v_new = _project_qkv(x, lp, cfg, *ropes[kind], qmm)
        k_pool = k_pool.at[at, pages[kind] + base, offs].set(
            ops.merge_heads(k_new).astype(k_pool.dtype)
        )
        v_pool = v_pool.at[at, pages[kind] + base, offs].set(
            ops.merge_heads(v_new).astype(v_pool.dtype)
        )
        k_all = ops.gather_pages(k_pool, at, by_kind[kind] + base, cfg.head_dim)
        v_all = ops.gather_pages(v_pool, at, by_kind[kind] + base, cfg.head_dim)
        attn = gqa_attention(q, k_all, v_all, masks[kind])
        x = x + matmul(attn.reshape(B, T, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm)
        return (x, k_pool, v_pool, scales, stats), None

    x, k_pool, v_pool, _, stats = _scan_layers_over_pool(
        block, x, params, k_pool, v_pool, None, cfg,
        moe_mod.grouped_serves(B * T, cfg, moe_dense),
    )
    return (_final_logits(x, params, cfg, qmm), k_pool, v_pool, *stats)


def verify_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32 — [last_token, draft_0..draft_{T-2}]
    lengths: jnp.ndarray,  # [B] int32 — tokens already in each slot's cache
    k_cache: jnp.ndarray,  # [L, B, C, KH, D]
    v_cache: jnp.ndarray,  # [L, B, C, KH, D]
    kernels: Optional[bool] = None,
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    active: Optional[jnp.ndarray] = None,  # [B] bool
    moe_dense: bool = False,
    qmm=None,  # int4 matmul impl (x, leaf, kind) -> y; see matmul()
):
    """Batched multi-token decode for speculative verification.

    The T tokens per slot are the pending ``last_token`` followed by T-1
    draft tokens; all T K/V rows are written at rows
    ``lengths[b] .. lengths[b]+T-1`` in one pass and every row of logits
    comes back, so the caller can accept the longest draft prefix that
    matches the model's own predictions (engine/spec.py). Because batched
    decode is weight-bandwidth-bound, verifying T positions costs roughly
    the same HBM traffic as a 1-token decode step — accepted drafts are
    nearly free tokens. This is the TPU replacement for the speculative /
    lookahead decoding the reference's llama.cpp backend exposes via
    llama-server's ``--draft`` options (SURVEY.md section 2.3).

    Same conventions as ``decode_step``: ``active`` gating writes inactive
    slots' rows to the sacrificial last cache row and exposes zero cache
    rows to them; ``cache_scales`` marks an int8 KV cache. Queries attend
    causally: query t of slot b sees cache cols ``<= lengths[b]+t`` (its own
    row included — written before the read), inside the sliding window.

    Rows written past ``C-2`` collapse onto the last cache row (scatter
    order is undefined there) — callers must clamp draft counts so accepted
    rows stay ``<= C-2``; unaccepted garbage rows are masked by ``lengths``
    afterwards. A slot already AT ``lengths == C-1`` collapses all T writes
    (including row 0's) onto the raced last row, so its outputs are
    indeterminate: callers must not consume tokens from saturated slots
    (the batcher retires them at the cache end; ``generate`` stops
    consuming mid-dispatch). Returns (logits [B, T, V] fp32, k_cache',
    v_cache'[, scales'][, stats]).
    """
    B, T = tokens.shape
    C = k_cache.shape[2]
    quant_cache = cache_scales is not None
    if active is None:
        active = jnp.ones((B,), jnp.bool_)
    offs = jnp.arange(T)[None, :]  # [1, T]
    # absolute position of each query row (garbage for inactive slots)
    positions = lengths[:, None] + offs  # [B, T]
    write_rows = jnp.where(
        active[:, None], jnp.minimum(positions, C - 1), C - 1
    )  # [B, T]
    # inactive slots expose only (overwritten-before-read) col 0, matching
    # the decode_step convention
    qpos = jnp.where(active[:, None], positions, 0)  # [B, T]
    # Ragged multi-query kernel: DMAs only the blocks holding valid rows,
    # same crossover rule as decode_step's single-query kernel
    # (_use_ragged_kernel). bf16 caches take the plain kernel; int8-KV
    # routes through the int8 variant (scales folded into the dots, same
    # AIOS_TPU_INT8_RAGGED gate as decode — drafts score at half the
    # cache bandwidth). Saturated slots run through whichever path the
    # batch takes with clamped/colliding rows — their outputs are
    # unconsumed by the saturation contract above; the kernel clamps its
    # DMA bound at the cache end so the VALID slots stay exact.
    routed = _use_ragged_kernel(
        kernels, C, cfg, quant_cache,
        quant_kernel_ok=_int8_ragged_enabled(),
    )
    use_kernel = routed and not quant_cache
    use_int8_kernel = routed and quant_cache
    if use_kernel or use_int8_kernel:
        mask = None
        strides = active.astype(jnp.int32)
        read_base = jnp.where(active, lengths, 0)
    else:
        cols = jnp.arange(C)[None, None, :]  # [1, 1, C]
        mask = cols <= qpos[..., None]  # [B, T, C]
        if cfg.sliding_window is not None:
            mask = mask & (cols > (qpos[..., None] - cfg.sliding_window))

    x = params["embed"][tokens]  # [B, T, E]
    cos, sin = rope_tables_of(positions, cfg.head_dim, cfg.rope_of(None))
    batch_idx = jnp.arange(B)[:, None]  # [B, 1] pairs with write_rows [B, T]

    def block(carry, layer):
        x, stats = carry
        lp, k_l, v_l, *scales_l = layer
        k_s, v_s = scales_l or (None, None)
        q, k_new, v_new = _project_qkv(x, lp, cfg, cos, sin, qmm)
        if quant_cache:
            kq, ks_new = quantize_kv(k_new)  # [B, T, KH, D], [B, T, KH]
            vq, vs_new = quantize_kv(v_new)
            k_l = k_l.at[batch_idx, write_rows].set(kq)
            v_l = v_l.at[batch_idx, write_rows].set(vq)
            k_s = k_s.at[batch_idx, write_rows].set(ks_new)
            v_s = v_s.at[batch_idx, write_rows].set(vs_new)
            if use_int8_kernel:
                attn = ops.multiquery_decode_attention_int8(
                    q, k_l, v_l, k_s, v_s, read_base, strides,
                    window=cfg.sliding_window,
                )
            else:
                attn = gqa_attention(
                    q,
                    dequantize_kv(k_l, k_s, q.dtype),
                    dequantize_kv(v_l, v_s, q.dtype),
                    mask,
                )
        else:
            k_l = k_l.at[batch_idx, write_rows].set(k_new.astype(k_l.dtype))
            v_l = v_l.at[batch_idx, write_rows].set(v_new.astype(v_l.dtype))
            if use_kernel:
                attn = ops.multiquery_decode_attention(
                    q, k_l, v_l, read_base, strides,
                    window=cfg.sliding_window,
                )
            else:
                attn = gqa_attention(q, k_l, v_l, mask)
        x = x + matmul(attn.reshape(B, T, -1), lp["wo"], qmm, "row")
        x, stats = _add_mlp(x, stats, lp, cfg, moe_dense, qmm)
        return (x, stats), (k_l, v_l, *((k_s, v_s) if quant_cache else ()))

    x, k_cache, v_cache, scales, stats = _scan_layers_over_cache(
        block, x, params, k_cache, v_cache, cache_scales, cfg,
        moe_mod.grouped_serves(B * T, cfg, moe_dense),
    )
    logits = _final_logits(x, params, cfg, qmm)
    if quant_cache:
        return (logits, k_cache, v_cache, scales, *stats)
    return (logits, k_cache, v_cache, *stats)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


class _LeafBuilder:
    """Builds the leaves of a random params tree, one key per random leaf.

    With ``shardings`` (a path -> Sharding callable,
    ShardingPlan.sharding_for) every leaf is built under jit with that
    out-sharding, so each device generates only its own shard and neither
    one chip nor device 0 of a mesh ever holds a whole leaf; without it
    the leaf is built eagerly on the default device. Values do not depend
    on ``shardings``."""

    def __init__(self, key: jax.Array, dtype, shardings=None) -> None:
        self._keys = iter(jax.random.split(key, 16))
        self.dtype = dtype
        self._shardings = shardings

    def key(self) -> jax.Array:
        return next(self._keys)

    def place(self, path: str, fn):
        if self._shardings is None:
            return fn()
        return jax.jit(fn, out_shardings=self._shardings(path))()

    def ones(self, path: str, shape):
        return self.place(path, lambda: jnp.ones(shape, self.dtype))

    def normal(self, path: str, shape, scale: float = 0.02):
        k = self.key()
        return self.place(path, lambda: (
            jax.random.normal(k, shape, jnp.float32) * scale
        ).astype(self.dtype))


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16, shardings=None
) -> Params:
    """Random params (scaled-normal init) — for tests, benches and training.
    ``shardings`` — see ``_LeafBuilder``."""
    build = _LeafBuilder(key, dtype, shardings)
    ones, normal = build.ones, build.normal

    L, E, F, D = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    layers = {
        "attn_norm": ones("layers/attn_norm", (L, E)),
        "ffn_norm": ones("layers/ffn_norm", (L, E)),
        "wq": normal("layers/wq", (L, E, cfg.q_dim)),
        "wk": normal("layers/wk", (L, E, cfg.kv_dim)),
        "wv": normal("layers/wv", (L, E, cfg.kv_dim)),
        "wo": normal("layers/wo", (L, cfg.q_dim, E)),
    }
    if cfg.moe:
        X, Fm = cfg.num_experts, cfg.expert_dim
        layers["w_router"] = normal("layers/w_router", (L, E, X))
        layers["we_gate"] = normal("layers/we_gate", (L, X, E, Fm))
        layers["we_up"] = normal("layers/we_up", (L, X, E, Fm))
        layers["we_down"] = normal("layers/we_down", (L, X, Fm, E))
    else:
        layers["w_gate"] = normal("layers/w_gate", (L, E, F))
        layers["w_up"] = normal("layers/w_up", (L, E, F))
        layers["w_down"] = normal("layers/w_down", (L, F, E))
    if cfg.qk_norm:
        layers["q_norm"] = ones("layers/q_norm", (L, D))
        layers["k_norm"] = ones("layers/k_norm", (L, D))
    params: Params = {
        "embed": normal("embed", (cfg.vocab_size, E)),
        "layers": layers,
        "final_norm": ones("final_norm", (E,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal("lm_head", (E, cfg.vocab_size))
    return params


def init_quantized_params(
    cfg: ModelConfig, key: jax.Array, fuse: bool = True, dtype=jnp.bfloat16,
    mode: str = "int8", tp: int = 1, shardings=None,
) -> Params:
    """Random params built DIRECTLY in the quantized serving layout
    (``quantize_params`` output shapes) — the bf16 weights never
    materialize, so a 7B model inits in ~7 GB of HBM instead of ~22 GB
    (int4: ~3.5 GB). For ``synthetic://`` sources, benchmarks and
    dry-runs: decode throughput is weight-value-independent (same bytes
    streamed, same FLOPs), and each quantized tensor tiles one random 2-D
    block over the layer axis to keep the init's own peak memory at one
    layer's worth. ``tp`` applies ``quantize_params``'s shard-local int4
    eligibility (with ``fuse=False``); ``shardings`` — see
    ``_LeafBuilder``.
    """
    build = _LeafBuilder(key, dtype, shardings)
    ones, normal, place = build.ones, build.normal, build.place
    L, E, F, D = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    V = cfg.vocab_size

    def qleaf(path, shape, force_int8: bool = False):
        K, N = shape[-2], shape[-1]
        k = build.key()
        g = 0
        if mode == "int4" and not force_int8:
            g = _int4_group(path.rsplit("/", 1)[-1], K, N, tp)
        # raw random bytes, not randint: on the TPU randint's unbiased
        # range reduction takes 30-50 s to COMPILE per 100M-element shape
        # (measured, PR 21) where bits takes ~2 s — and every byte is a
        # valid int8 weight / pair of int4 nibbles as it stands
        if g:
            return {
                "q4": place(path + "/q4", lambda: jnp.broadcast_to(
                    jax.random.bits(k, (K // 2, N), jnp.uint8),
                    shape[:-2] + (K // 2, N),
                )),
                "s4": place(path + "/s4", lambda: jnp.full(
                    shape[:-2] + (K // g, 1, N), 0.02 / 7.0, jnp.float32
                )),
            }
        return {
            "q": place(path + "/q", lambda: jnp.broadcast_to(
                jax.lax.bitcast_convert_type(
                    jax.random.bits(k, (K, N), jnp.uint8), jnp.int8
                ),
                shape,
            )),
            "s": place(path + "/s", lambda: jnp.full(
                shape[:-2] + (1, N), 0.02 / 127.0, jnp.float32
            )),
        }

    layers = {
        "attn_norm": ones("layers/attn_norm", (L, E)),
        "ffn_norm": ones("layers/ffn_norm", (L, E)),
    }
    if cfg.qk_norm:
        layers["q_norm"] = ones("layers/q_norm", (L, D))
        layers["k_norm"] = ones("layers/k_norm", (L, D))
    if fuse:
        layers["w_qkv"] = qleaf("layers/w_qkv", (L, E, cfg.q_dim + 2 * cfg.kv_dim))
        layers["wo"] = qleaf("layers/wo", (L, cfg.q_dim, E))
    else:
        layers["wq"] = qleaf("layers/wq", (L, E, cfg.q_dim))
        layers["wk"] = qleaf("layers/wk", (L, E, cfg.kv_dim))
        layers["wv"] = qleaf("layers/wv", (L, E, cfg.kv_dim))
        layers["wo"] = qleaf("layers/wo", (L, cfg.q_dim, E))
    if cfg.moe:
        X, Fm = cfg.num_experts, cfg.expert_dim
        layers["w_router"] = normal("layers/w_router", (L, E, X))
        # expert leaves stay int8 in int4 mode (moe._expert_einsum reads
        # int8 leaves only, matching quantize_params)
        if fuse:
            layers["we_gateup"] = qleaf(
                "layers/we_gateup", (L, X, E, 2 * Fm), force_int8=True
            )
            layers["we_down"] = qleaf(
                "layers/we_down", (L, X, Fm, E), force_int8=True
            )
        else:
            layers["we_gate"] = qleaf(
                "layers/we_gate", (L, X, E, Fm), force_int8=True
            )
            layers["we_up"] = qleaf(
                "layers/we_up", (L, X, E, Fm), force_int8=True
            )
            layers["we_down"] = qleaf(
                "layers/we_down", (L, X, Fm, E), force_int8=True
            )
    elif fuse:
        layers["w_gateup"] = qleaf("layers/w_gateup", (L, E, 2 * F))
        layers["w_down"] = qleaf("layers/w_down", (L, F, E))
    else:
        layers["w_gate"] = qleaf("layers/w_gate", (L, E, F))
        layers["w_up"] = qleaf("layers/w_up", (L, E, F))
        layers["w_down"] = qleaf("layers/w_down", (L, F, E))
    return {
        "embed": normal("embed", (V, E)),
        "layers": layers,
        "final_norm": ones("final_norm", (E,)),
        "lm_head": qleaf("lm_head", (E, V)),
    }


def serving_weight_bytes(params: Params) -> int:
    """Bytes of weight data streamed from HBM per decode step (every
    matmul weight + scales; embedding gather excluded — one row)."""
    total = 0
    for leaf in jax.tree.leaves(params["layers"]) + jax.tree.leaves(
        params.get("lm_head", [])
    ):
        total += leaf.size * leaf.dtype.itemsize
    return total


def init_kv_cache(
    cfg: ModelConfig, num_slots: int, max_len: int, dtype=jnp.bfloat16
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    shape = (cfg.num_layers, num_slots, max_len, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_kv_scales(
    cfg: ModelConfig, num_slots: int, max_len: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(row, kv-head) scales for an int8 KV cache."""
    shape = (cfg.num_layers, num_slots, max_len, cfg.num_kv_heads)
    return jnp.ones(shape, jnp.float32), jnp.ones(shape, jnp.float32)


def paged_int8_attend(q, k_pool, v_pool, k_s, v_s, layer, tables, lengths,
                      *, window, use_int8_kernel, win_starts=None, sink=0):
    """Decode attention over layer ``layer`` of an int8 page pool
    ([B,H,D] -> [B,H,D]; pools [L,N,P,KH*D], scales [L,N,P,KH]): the kernel
    path streams int8 pages with scales folded into the dots; the XLA path
    dequantizes a gathered per-slot view. The single source of truth for
    the int8-pool read — decode_step_paged AND the dp-replicated shard_map
    body (sharding.paged_pool_impl) both call it, so mask/window semantics
    cannot drift between them.
    ``win_starts``/``sink`` apply the window+sink compressed mask."""
    if use_int8_kernel:
        return ops.paged_decode_attention_int8(
            q, k_pool, v_pool, k_s, v_s, layer, tables, lengths,
            window=window, win_starts=win_starts,
            sink=sink if win_starts is not None else None,
        )
    C = tables.shape[1] * k_pool.shape[2]
    cols = jnp.arange(C)[None, :]
    mask = cols <= lengths[:, None]
    if window is not None:
        mask = mask & (cols > (lengths[:, None] - window))
    if win_starts is not None:
        mask = mask & ((cols < sink) | (cols >= win_starts[:, None]))
    return gqa_attention(
        q[:, None],
        gather_dequant(k_pool, k_s, layer, tables, q.dtype),
        gather_dequant(v_pool, v_s, layer, tables, q.dtype),
        mask[:, None, :],
    )[:, 0]


def scatter_quant(
    pool: jnp.ndarray,  # [..., N, P, KH*D] int8
    scales: jnp.ndarray,  # [..., N, P, KH] f32
    idx: tuple,  # (pages, offs), or (layer, pages, offs) on the whole pool
    rows: jnp.ndarray,  # [..., KH, D] new rows (idx arrays broadcast-match)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize rows and scatter values + scales into an int8 page pool —
    the single write-side quantization contract for every paged path."""
    q, s = quantize_kv(rows)
    return pool.at[idx].set(ops.merge_heads(q)), scales.at[idx].set(s)


def gather_dequant(
    pool: jnp.ndarray,  # [L, N, P, KH*D] int8
    scales: jnp.ndarray,  # [L, N, P, KH] f32
    layer,  # scalar layer index
    tables: jnp.ndarray,  # [..., MB] int32
    dtype,
) -> jnp.ndarray:
    """Materialize dequantized logical views [..., MB*P, KH, D] from an
    int8 page pool — the read-side twin of ``scatter_quant``."""
    s = scales[layer, tables]  # [..., MB, P, KH]
    s = s.reshape(*tables.shape[:-1], -1, s.shape[-1])
    D = pool.shape[-1] // s.shape[-1]
    return dequantize_kv(ops.gather_pages(pool, layer, tables, D), s, dtype)


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the head dim. x [..., D] -> (int8 [..., D], f32 [...])."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(
        dtype
    )
