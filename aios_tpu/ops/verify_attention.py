"""Ragged MULTI-QUERY decode attention: T in-flight queries per slot.

The speculative verify step scores a slot's pending token plus K draft
tokens in one forward (model.verify_step). Its attention is T queries per
slot over that slot's valid cache rows — without a kernel it falls back to
a full-cache masked read, paying C-row HBM traffic per slot regardless of
how short the slot actually is. This kernel generalizes the single-query
ragged decode kernel (decode_attention.py): same double-buffered
HBM→VMEM DMA over only the blocks that hold valid rows, but each block is
scored against all T queries, with the causal staircase applied per query
(query t sees cols <= base + t·stride).

``stride`` is 1 for active slots and 0 for inactive ones, matching
verify_step's convention that inactive slots expose only the
overwritten-before-read col 0 for every query.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mq_kernel(
    len_ref,  # SMEM [B] int32 — base: row `len` holds query 0's row
    stride_ref,  # SMEM [B] int32 — 1 active (staircase), 0 inactive
    q_ref,  # VMEM [1, T, H, D]
    k_hbm,  # ANY  [B, C, KH*D]  (bf16, or int8 when quantized)
    v_hbm,  # ANY  [B, C, KH*D]
    *rest,  # quantized: ks_hbm [B, KH, C] f32 (head-major — the lane dim
    #         must be the 128-aligned cache axis), vs_hbm, o_ref; else o_ref
    num_kv_heads: int,
    head_dim: int,
    block_kv: int,
    window: Optional[int],
    sm_scale: float,
    quantized: bool = False,
):
    if quantized:
        ks_hbm, vs_hbm, o_ref = rest
    else:
        (o_ref,) = rest
    b = pl.program_id(0)
    KH, D, bk = num_kv_heads, head_dim, block_kv
    T, H = q_ref.shape[1], q_ref.shape[2]
    G = H // KH

    base = len_ref[b]
    stride = stride_ref[b]
    C = k_hbm.shape[1]
    # rows [0, base + (T-1)*stride] are visible to SOME query; clamp at the
    # cache end — a saturated slot's clamped writes collide there and its
    # outputs are unconsumed by contract, but the DMA must stay in bounds
    total = jnp.minimum(base + (T - 1) * stride + 1, C)
    n_blk = pl.cdiv(total, bk)
    if window is not None:
        # earliest col any query needs is query 0's window start
        start_blk = jnp.maximum(base + 1 - window, 0) // bk
    else:
        start_blk = jnp.int32(0)

    # [T*G, D] per kv head, rows ordered (t, g)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [T, H, D]
    qpos = base + jnp.arange(T) * stride  # [T] each query's own row

    def body(k_buf, v_buf, sems, ks_buf=None, vs_buf=None):
        def dma(buf_hbm, scr, slot, blk, sem_idx):
            return pltpu.make_async_copy(
                buf_hbm.at[b, pl.ds(blk * bk, bk)],
                scr.at[slot],
                sems.at[slot, sem_idx],
            )

        def dma_scales(buf_hbm, scr, slot, blk, sem_idx):
            # head-major scales: slice the lane (cache) axis, heads full
            return pltpu.make_async_copy(
                buf_hbm.at[b, :, pl.ds(blk * bk, bk)],
                scr.at[slot],
                sems.at[slot, sem_idx],
            )

        def start_all(slot, blk):
            dma(k_hbm, k_buf, slot, blk, 0).start()
            dma(v_hbm, v_buf, slot, blk, 1).start()
            if quantized:
                dma_scales(ks_hbm, ks_buf, slot, blk, 2).start()
                dma_scales(vs_hbm, vs_buf, slot, blk, 3).start()

        def wait_all(slot, blk):
            dma(k_hbm, k_buf, slot, blk, 0).wait()
            dma(v_hbm, v_buf, slot, blk, 1).wait()
            if quantized:
                dma_scales(ks_hbm, ks_buf, slot, blk, 2).wait()
                dma_scales(vs_hbm, vs_buf, slot, blk, 3).wait()

        start_all(0, start_blk)

        def loop(i, carry):
            m, l, acc = carry  # [KH*T*G, 1], [KH*T*G, 1], [KH*T*G, D]
            slot = jax.lax.rem(i - start_blk, 2)

            @pl.when(i + 1 < n_blk)
            def _prefetch():
                start_all(1 - slot, i + 1)

            wait_all(slot, i)
            kb = k_buf[slot]  # [bk, KH*D]
            vb = v_buf[slot]
            ksb = ks_buf[slot] if quantized else None  # [KH, bk] f32
            vsb = vs_buf[slot] if quantized else None

            cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, (T, bk), 1)
            valid = cols <= qpos[:, None]  # causal staircase per query
            if window is not None:
                valid = jnp.logical_and(valid, cols > qpos[:, None] - window)
            # [T, bk] -> [T*G, bk] (repeat per query's G heads)
            validg = jnp.repeat(valid, G, axis=0)

            parts = []
            for h in range(KH):
                qh = q[:, h * G : (h + 1) * G, :].reshape(T * G, D)
                kh = kb[:, h * D : (h + 1) * D]
                if quantized:
                    kh = kh.astype(jnp.float32)
                s = jax.lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [T*G, bk]
                if quantized:
                    s = s * ksb[h][None, :]
                parts.append(jnp.where(validg, s, NEG_INF))
            s_all = jnp.concatenate(parts, axis=0)  # [KH*T*G, bk]

            m_cur = jnp.max(s_all, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s_all - m_new)
            p = jnp.where(
                jnp.concatenate([validg] * KH, axis=0), p, 0.0
            )
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)

            outs = []
            for h in range(KH):
                ph = p[h * T * G : (h + 1) * T * G, :]
                if quantized:
                    ph = ph * vsb[h][None, :]
                else:
                    ph = ph.astype(vb.dtype)
                vh = vb[:, h * D : (h + 1) * D]
                if quantized:
                    vh = vh.astype(jnp.float32)
                outs.append(
                    jax.lax.dot_general(
                        ph, vh, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )
            acc_new = acc * alpha + jnp.concatenate(outs, axis=0)
            return m_new, l_new, acc_new

        init = (
            jnp.full((KH * T * G, 1), NEG_INF, jnp.float32),
            jnp.zeros((KH * T * G, 1), jnp.float32),
            jnp.zeros((KH * T * G, D), jnp.float32),
        )
        m, l, acc = jax.lax.fori_loop(start_blk, n_blk, loop, init)
        safe_l = jnp.where(l <= 0.0, 1.0, l)
        out = acc / safe_l  # [KH*T*G, D]
        out = out.reshape(KH, T, G, D).transpose(1, 0, 2, 3)
        o_ref[0] = out.reshape(T, H, D).astype(o_ref.dtype)

    if quantized:
        pl.run_scoped(
            body,
            k_buf=pltpu.VMEM((2, bk, KH * D), jnp.int8),
            v_buf=pltpu.VMEM((2, bk, KH * D), jnp.int8),
            sems=pltpu.SemaphoreType.DMA((2, 4)),
            ks_buf=pltpu.VMEM((2, KH, bk), jnp.float32),
            vs_buf=pltpu.VMEM((2, KH, bk), jnp.float32),
        )
    else:
        pl.run_scoped(
            body,
            k_buf=pltpu.VMEM((2, bk, KH * D), k_hbm.dtype),
            v_buf=pltpu.VMEM((2, bk, KH * D), v_hbm.dtype),
            sems=pltpu.SemaphoreType.DMA((2, 2)),
        )


def _mq_call(q, k_cache, v_cache, lengths, strides, scales, *, window,
             block_kv, interpret):
    """Shared pallas_call plumbing for both cache dtypes."""
    from .decode_attention import pick_block_kv

    B, T, H, D = q.shape
    C, KH = k_cache.shape[1], k_cache.shape[2]
    bk = pick_block_kv(C) if block_kv is None else min(block_kv, C)
    if C % bk:
        raise ValueError(f"block_kv {bk} must evenly divide cache length {C}")
    quantized = scales is not None
    if quantized and bk % 128 and not interpret:
        raise ValueError(
            f"int8 mq kernel needs 128-aligned kv blocks, got {bk} "
            f"(cache length {C})"
        )
    kernel = functools.partial(
        _mq_kernel,
        num_kv_heads=KH,
        head_dim=D,
        block_kv=bk,
        window=window,
        sm_scale=1.0 / float(np.sqrt(D)),
        quantized=quantized,
    )
    cache_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (
        2 + (2 if quantized else 0)
    )
    args = [
        lengths.astype(jnp.int32),
        strides.astype(jnp.int32),
        q,
        k_cache.reshape(B, C, KH * D),
        v_cache.reshape(B, C, KH * D),
    ]
    if quantized:
        # [B, C, KH] -> head-major [B, KH, C] (see decode_attention.py)
        args.extend(s.transpose(0, 2, 1) for s in scales)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, T, H, D), q.dtype),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths
            pl.BlockSpec(memory_space=pltpu.SMEM),  # strides
            pl.BlockSpec((1, T, H, D), lambda b: (b, 0, 0, 0)),
            *cache_specs,
        ],
        out_specs=pl.BlockSpec((1, T, H, D), lambda b: (b, 0, 0, 0)),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit, static_argnames=("window", "block_kv", "interpret")
)
def multiquery_decode_attention(
    q: jnp.ndarray,  # [B, T, H, D] — T in-flight queries per slot
    k_cache: jnp.ndarray,  # [B, C, KH, D]
    v_cache: jnp.ndarray,  # [B, C, KH, D]
    lengths: jnp.ndarray,  # [B] int32 — query 0's own (just-written) row
    strides: jnp.ndarray,  # [B] int32 — 1 active, 0 inactive
    *,
    window: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged multi-query decode attention; returns [B, T, H, D]."""
    return _mq_call(
        q, k_cache, v_cache, lengths, strides, None,
        window=window, block_kv=block_kv, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("window", "block_kv", "interpret")
)
def multiquery_decode_attention_int8(
    q: jnp.ndarray,  # [B, T, H, D]
    k_cache: jnp.ndarray,  # [B, C, KH, D] int8
    v_cache: jnp.ndarray,  # [B, C, KH, D] int8
    k_scales: jnp.ndarray,  # [B, C, KH] f32
    v_scales: jnp.ndarray,  # [B, C, KH] f32
    lengths: jnp.ndarray,  # [B] int32
    strides: jnp.ndarray,  # [B] int32
    *,
    window: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query ragged attention over an INT8 KV cache: the cache
    streams as int8 with per-(row, kv-head) scales folded into the
    score/value dots — speculative verify at half the cache bandwidth."""
    return _mq_call(
        q, k_cache, v_cache, lengths, strides, (k_scales, v_scales),
        window=window, block_kv=block_kv, interpret=interpret,
    )


def multiquery_decode_attention_int8_reference(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,  # [B, C, KH, D] int8
    v_cache: jnp.ndarray,
    k_scales: jnp.ndarray,  # [B, C, KH] f32
    v_scales: jnp.ndarray,
    lengths: jnp.ndarray,
    strides: jnp.ndarray,
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Dequantize-then-attend ground truth for the int8 mq kernel."""
    kf = k_cache.astype(jnp.float32) * k_scales[..., None]
    vf = v_cache.astype(jnp.float32) * v_scales[..., None]
    return multiquery_decode_attention_reference(
        q, kf, vf, lengths, strides, window=window
    )


def multiquery_decode_attention_reference(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    strides: jnp.ndarray,
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Naive jnp multi-query ragged attention (CPU fallback + parity)."""
    B, T, H, D = q.shape
    C, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qpos = lengths[:, None] + jnp.arange(T)[None, :] * strides[:, None]
    cols = jnp.arange(C)[None, None, :]
    mask = cols <= qpos[..., None]  # [B, T, C]
    if window is not None:
        mask = mask & (cols > qpos[..., None] - window)
    qg = q.reshape(B, T, KH, G, D)
    s = jnp.einsum("btkgd,bckd->bkgtc", qg, k_cache).astype(jnp.float32)
    s = s / np.sqrt(D)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgtc,bckd->btkgd", p, v_cache)
    return out.reshape(B, T, H, D)
