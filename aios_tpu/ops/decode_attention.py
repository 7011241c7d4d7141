"""Ragged batched-decode attention over the slot KV cache.

The decode hot loop attends one new query token per slot against that slot's
cache rows [0, length]. A naive XLA implementation reads the *entire*
[C, KH, D] cache for every slot every step; this kernel instead DMAs only
the blocks that contain valid rows (double-buffered HBM→VMEM, overlapping
copy with compute), so a slot that is 100 tokens into a 8192-row cache reads
~1% of the naive bandwidth. Sliding-window models additionally skip blocks
below the window start.

Layout: caches stay exactly as the engine stores them — [B, C, KH, D]
reshaped (free) to [B, C, KH*D] so VMEM tiles are lane-aligned. Grid is
(B,); each program owns one slot and runs the online-softmax recurrence over
its kv blocks with per-kv-head MXU dots.

ONE kernel body serves both cache dtypes (`quantized` is a trace-time
flag): bf16 caches stream as-is; int8 caches stream as int8 (half the HBM
bytes) with their per-(row, kv-head) scales DMA'd alongside and folded into
the score and value dots — s[g,c] = (q·k_i8)[g,c]·ks[c],
out = (p·vs) @ v_i8 — so the dequantized cache never materializes.

This is the TPU-native replacement for the per-request attention inside
llama.cpp's decode loop (SURVEY.md section 2.3 / section 3.2 "THE hot loop").
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


def _decode_kernel(
    len_ref,  # SMEM [B] int32
    q_ref,  # VMEM [1, H, D]
    k_hbm,  # ANY  [B, C, KH*D]  (bf16, or int8 when quantized)
    v_hbm,  # ANY  [B, C, KH*D]
    *rest,  # quantized: ks_hbm [B, KH, C] f32, vs_hbm [B, KH, C] f32, o_ref
    #         else: o_ref
    # (scales arrive head-major so the lane dim is the 128-aligned cache
    #  axis — a [.., C, KH] layout would DMA-slice KH lanes, which Mosaic
    #  rejects for KH < 128)
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
    H = q_ref.shape[1]
    G = H // KH

    length = len_ref[b]  # row `length` holds the just-written token
    total = length + 1
    n_blk = pl.cdiv(total, bk)
    if window is not None:
        start_blk = jnp.maximum(total - window, 0) // bk
    else:
        start_blk = jnp.int32(0)

    if quantized:
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [H, D]
    else:
        q = q_ref[0] * sm_scale

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
            m, l, acc = carry  # [H, 1], [H, 1], [H, D] f32
            slot = jax.lax.rem(i - start_blk, 2)

            @pl.when(i + 1 < n_blk)
            def _prefetch():
                start_all(1 - slot, i + 1)

            wait_all(slot, i)
            kb = k_buf[slot]  # [bk, KH*D]
            vb = v_buf[slot]
            ksb = ks_buf[slot] if quantized else None  # [KH, bk] f32
            vsb = vs_buf[slot] if quantized else None

            cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            valid = cols <= length
            if window is not None:
                valid = jnp.logical_and(valid, cols > length - window)

            # scores for all H query heads, grouped by kv head
            parts = []
            for h in range(KH):
                qh = q[h * G : (h + 1) * G, :]  # [G, D]
                kh = kb[:, h * D : (h + 1) * D]  # [bk, D]
                if quantized:
                    kh = kh.astype(jnp.float32)
                sh = jax.lax.dot_general(
                    qh,
                    kh,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [G, bk] — int8 magnitudes are exact in f32
                if quantized:
                    sh = sh * ksb[h][None, :]
                parts.append(sh)
            s = jnp.concatenate(parts, axis=0)  # [H, bk]
            s = jnp.where(valid, s, NEG_INF)

            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)  # [H, bk]
            p = jnp.where(valid, p, 0.0)  # fully-masked tile => p would be 1
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)

            outs = []
            pv = p if quantized else p.astype(vb.dtype)
            for h in range(KH):
                ph = pv[h * G : (h + 1) * G, :]  # [G, bk]
                if quantized:
                    ph = ph * vsb[h][None, :]
                vh = vb[:, h * D : (h + 1) * D]  # [bk, D]
                if quantized:
                    vh = vh.astype(jnp.float32)
                outs.append(
                    jax.lax.dot_general(
                        ph,
                        vh,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )
            acc_new = acc * alpha + jnp.concatenate(outs, axis=0)
            return m_new, l_new, acc_new

        init = (
            jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, D), jnp.float32),
        )
        m, l, acc = jax.lax.fori_loop(start_blk, n_blk, loop, init)
        safe_l = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0] = (acc / safe_l).astype(o_ref.dtype)

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


def pick_block_kv(C: int, preferred: int = 256) -> int:
    """Largest power-of-two block <= preferred that divides the cache."""
    bk = min(preferred, C)
    while bk > 1 and C % bk:
        bk //= 2
    return bk


def _ragged_call(q, k_cache, v_cache, lengths, scales, *, window, block_kv,
                 interpret):
    """Shared pallas_call plumbing for both cache dtypes."""
    B, H, D = q.shape
    C, KH = k_cache.shape[1], k_cache.shape[2]
    bk = pick_block_kv(C) if block_kv is None else min(block_kv, C)
    if C % bk:
        raise ValueError(
            f"block_kv {bk} must evenly divide cache length {C}"
        )
    quantized = scales is not None
    if quantized and bk % 128 and not interpret:
        # Mosaic tiles lanes at 128: a smaller block would DMA-slice an
        # unaligned lane extent of the caches (interpret mode has no
        # such constraint and the tests use tiny blocks there)
        raise ValueError(
            f"int8 ragged kernel needs 128-aligned kv blocks, got {bk} "
            f"(cache length {C})"
        )
    kernel = functools.partial(
        _decode_kernel,
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
        q,
        k_cache.reshape(B, C, KH * D),
        v_cache.reshape(B, C, KH * D),
    ]
    if quantized:
        # engine stores scales [B, C, KH]; the kernel wants them head-major
        # [B, KH, C] so its DMA slices the 128-aligned cache axis on lanes.
        # The transpose costs ~3% of one int8 cache sweep (f32 scales are
        # 4/D of the cache bytes) — second-order next to the ragged win.
        args.extend(s.transpose(0, 2, 1) for s in scales)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths
            pl.BlockSpec((1, H, D), lambda b: (b, 0, 0)),
            *cache_specs,  # caches (+ scales) stay in HBM
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b: (b, 0, 0)),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit, static_argnames=("window", "block_kv", "interpret")
)
def decode_attention(
    q: jnp.ndarray,  # [B, H, D] — one new query per slot
    k_cache: jnp.ndarray,  # [B, C, KH, D]
    v_cache: jnp.ndarray,  # [B, C, KH, D]
    lengths: jnp.ndarray,  # [B] int32; row `lengths[b]` is the newest token
    *,
    window: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged decode attention; returns [B, H, D]."""
    return _ragged_call(
        q, k_cache, v_cache, lengths, None,
        window=window, block_kv=block_kv, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("window", "block_kv", "interpret")
)
def decode_attention_int8(
    q: jnp.ndarray,  # [B, H, D] — one new query per slot
    k_cache: jnp.ndarray,  # [B, C, KH, D] int8
    v_cache: jnp.ndarray,  # [B, C, KH, D] int8
    k_scales: jnp.ndarray,  # [B, C, KH] f32
    v_scales: jnp.ndarray,  # [B, C, KH] f32
    lengths: jnp.ndarray,  # [B] int32
    *,
    window: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged decode attention over an INT8 KV cache; returns [B, H, D]."""
    return _ragged_call(
        q, k_cache, v_cache, lengths, (k_scales, v_scales),
        window=window, block_kv=block_kv, interpret=interpret,
    )


def decode_attention_int8_reference(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,  # [B, C, KH, D] int8
    v_cache: jnp.ndarray,
    k_scales: jnp.ndarray,  # [B, C, KH] f32
    v_scales: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Dequantize-then-attend ground truth for the int8 kernel (fp32)."""
    kf = k_cache.astype(jnp.float32) * k_scales[..., None]
    vf = v_cache.astype(jnp.float32) * v_scales[..., None]
    return decode_attention_reference(
        q, kf, vf, lengths, window=window
    )


def decode_attention_reference(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Naive jnp ragged decode attention (CPU fallback + parity truth)."""
    B, H, D = q.shape
    C, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bckd->bkgc", qg, k_cache).astype(jnp.float32)
    s = s / np.sqrt(D)
    cols = jnp.arange(C)[None, :]
    mask = cols <= lengths[:, None]
    if window is not None:
        mask = mask & (cols > lengths[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgc,bckd->bkgd", p, v_cache)
    return out.reshape(B, H, D)
