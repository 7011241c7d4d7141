"""Paged ragged decode attention: the slot cache behind a page table.

Same online-softmax recurrence as `decode_attention` (one query per slot
against that slot's valid cache rows, double-buffered HBM→VMEM DMA), except
K/V rows live in a shared *page pool* instead of one contiguous slab per
slot: logical block i of slot b is physical page `tables[b, i]` of
`[N, P, KH*D]`. The kernel reads the table from SMEM and DMAs only the
pages that hold valid rows, so HBM is reserved per *page in use*, not per
`num_slots x max_context` — that decoupling is what lets many long-context
slots oversubscribe a fixed pool (SURVEY.md section 7.2 "paged KV cache in
HBM"; the fixed-shape-jit half of hard part #1).

The pool never moves: growth is a host-side free-list allocation plus a new
table row passed with the next dispatch. Shapes stay static everywhere —
the table is [B, MAX_BLOCKS] with garbage entries beyond each slot's
length, never read because the loop bound comes from `lengths`.
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


def _paged_decode_kernel(
    len_ref,  # SMEM [B] int32
    tbl_ref,  # SMEM [B, MB] int32 — logical block -> physical page
    *args,  # [ws_ref SMEM [B] when sink is not None,] q_ref, k_pool,
    #         v_pool, then quantized: ks_pool [N, KH, P] f32 (head-major —
    #         the lane dim must be the 128-aligned page axis), vs_pool,
    #         o_ref; else o_ref
    num_kv_heads: int,
    head_dim: int,
    page_size: int,
    window: Optional[int],
    sink: Optional[int],
    sm_scale: float,
    quantized: bool = False,
):
    # window+sink KV compression (docs/ENGINE_PERF.md "Long-context
    # tier"): ws_ref[b] is where slot b's live trailing window begins —
    # rows in [sink, ws_ref[b]) were pruned from the pool and their table
    # entries remap the sacrificial page, so they must score as invalid.
    # ws = 0 makes the extra mask a no-op (uncompressed slot).
    if sink is not None:
        ws_ref, q_ref, k_pool, v_pool, *rest = args
    else:
        ws_ref = None
        q_ref, k_pool, v_pool, *rest = args
    if quantized:
        ks_pool, vs_pool, o_ref = rest
    else:
        (o_ref,) = rest
    b = pl.program_id(0)
    KH, D, P = num_kv_heads, head_dim, page_size
    H = q_ref.shape[1]
    G = H // KH

    length = len_ref[b]  # row `length` holds the just-written token
    total = length + 1
    n_blk = pl.cdiv(total, P)
    if window is not None:
        start_blk = jnp.maximum(total - window, 0) // P
    else:
        start_blk = jnp.int32(0)

    if quantized:
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [H, D]
    else:
        q = q_ref[0] * sm_scale

    def body(k_buf, v_buf, sems, ks_buf=None, vs_buf=None):
        def dma(pool, scr, slot, blk, sem_idx):
            # THE paged indirection: logical block -> physical page
            return pltpu.make_async_copy(
                pool.at[tbl_ref[b, blk]],
                scr.at[slot],
                sems.at[slot, sem_idx],
            )

        def start_all(slot, blk):
            dma(k_pool, k_buf, slot, blk, 0).start()
            dma(v_pool, v_buf, slot, blk, 1).start()
            if quantized:
                dma(ks_pool, ks_buf, slot, blk, 2).start()
                dma(vs_pool, vs_buf, slot, blk, 3).start()

        def wait_all(slot, blk):
            dma(k_pool, k_buf, slot, blk, 0).wait()
            dma(v_pool, v_buf, slot, blk, 1).wait()
            if quantized:
                dma(ks_pool, ks_buf, slot, blk, 2).wait()
                dma(vs_pool, vs_buf, slot, blk, 3).wait()

        start_all(0, start_blk)

        def loop(i, carry):
            m, l, acc = carry  # [H, 1], [H, 1], [H, D] f32
            slot = jax.lax.rem(i - start_blk, 2)

            @pl.when(i + 1 < n_blk)
            def _prefetch():
                start_all(1 - slot, i + 1)

            wait_all(slot, i)
            kb = k_buf[slot]  # [P, KH*D]
            vb = v_buf[slot]
            ksb = ks_buf[slot] if quantized else None  # [KH, P] f32
            vsb = vs_buf[slot] if quantized else None

            cols = i * P + jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
            valid = cols <= length
            if window is not None:
                valid = jnp.logical_and(valid, cols > length - window)
            if sink is not None:
                valid = jnp.logical_and(
                    valid,
                    jnp.logical_or(cols < sink, cols >= ws_ref[b]),
                )

            parts = []
            for h in range(KH):
                qh = q[h * G : (h + 1) * G, :]  # [G, D]
                kh = kb[:, h * D : (h + 1) * D]  # [P, D]
                if quantized:
                    kh = kh.astype(jnp.float32)
                sh = jax.lax.dot_general(
                    qh,
                    kh,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if quantized:
                    sh = sh * ksb[h][None, :]
                parts.append(sh)
            s = jnp.concatenate(parts, axis=0)  # [H, P]
            s = jnp.where(valid, s, NEG_INF)

            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)

            outs = []
            pv = p if quantized else p.astype(vb.dtype)
            for h in range(KH):
                ph = pv[h * G : (h + 1) * G, :]  # [G, P]
                if quantized:
                    ph = ph * vsb[h][None, :]
                vh = vb[:, h * D : (h + 1) * D]  # [P, D]
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
            k_buf=pltpu.VMEM((2, P, KH * D), jnp.int8),
            v_buf=pltpu.VMEM((2, P, KH * D), jnp.int8),
            sems=pltpu.SemaphoreType.DMA((2, 4)),
            ks_buf=pltpu.VMEM((2, KH, P), jnp.float32),
            vs_buf=pltpu.VMEM((2, KH, P), jnp.float32),
        )
    else:
        pl.run_scoped(
            body,
            k_buf=pltpu.VMEM((2, P, KH * D), k_pool.dtype),
            v_buf=pltpu.VMEM((2, P, KH * D), v_pool.dtype),
            sems=pltpu.SemaphoreType.DMA((2, 2)),
        )


def _paged_call(q, k_pool, v_pool, tables, lengths, scales, *, window,
                win_starts, sink, interpret):
    """Shared pallas_call plumbing for both pool dtypes."""
    B, H, D = q.shape
    N, P, KH = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    quantized = scales is not None
    compressed = win_starts is not None
    if compressed and sink is None:
        raise ValueError("win_starts needs a static sink row count")
    if quantized and P % 128 and not interpret:
        # Same Mosaic lane constraint as the ragged int8 kernel
        # (decode_attention.py): the scale transpose below puts the page
        # axis on lanes, so a non-128-aligned page_size would fail deep
        # inside Mosaic instead of here.
        raise ValueError(
            f"int8 paged kernel needs a 128-aligned page_size, got {P}"
        )
    kernel = functools.partial(
        _paged_decode_kernel,
        num_kv_heads=KH,
        head_dim=D,
        page_size=P,
        window=window,
        sink=sink if compressed else None,
        sm_scale=1.0 / float(np.sqrt(D)),
        quantized=quantized,
    )
    pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (
        2 + (2 if quantized else 0)
    )
    args = [
        lengths.astype(jnp.int32),
        tables.astype(jnp.int32),
    ]
    ws_specs = []
    if compressed:
        args.append(win_starts.astype(jnp.int32))
        ws_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    args += [
        q,
        k_pool.reshape(N, P, KH * D),
        v_pool.reshape(N, P, KH * D),
    ]
    if quantized:
        # [N, P, KH] -> head-major [N, KH, P]: the whole-page DMA then has
        # the 128-row page axis on lanes (see decode_attention.py)
        args.extend(s.transpose(0, 2, 1) for s in scales)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths
            pl.BlockSpec(memory_space=pltpu.SMEM),  # page tables
            *ws_specs,  # window starts (compressed engines only)
            pl.BlockSpec((1, H, D), lambda b: (b, 0, 0)),
            *pool_specs,  # pools (+ scales) stay in HBM
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b: (b, 0, 0)),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("window", "sink", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] — one new query per slot
    k_pool: jnp.ndarray,  # [N, P, KH, D] — shared page pool
    v_pool: jnp.ndarray,  # [N, P, KH, D]
    tables: jnp.ndarray,  # [B, MB] int32 — logical block -> physical page
    lengths: jnp.ndarray,  # [B] int32; row `lengths[b]` is the newest token
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink: Optional[int] = None,  # static sink row count (with win_starts)
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged ragged decode attention; returns [B, H, D]. With
    ``win_starts``/``sink`` (window+sink KV compression) slot b attends
    only rows < sink or >= win_starts[b] — the pruned middle is masked."""
    return _paged_call(
        q, k_pool, v_pool, tables, lengths, None,
        window=window, win_starts=win_starts, sink=sink,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("window", "sink", "interpret"))
def paged_decode_attention_int8(
    q: jnp.ndarray,  # [B, H, D]
    k_pool: jnp.ndarray,  # [N, P, KH, D] int8
    v_pool: jnp.ndarray,  # [N, P, KH, D] int8
    k_scales: jnp.ndarray,  # [N, P, KH] f32 (layer slice of the pool scales)
    v_scales: jnp.ndarray,  # [N, P, KH] f32
    tables: jnp.ndarray,  # [B, MB] int32
    lengths: jnp.ndarray,  # [B] int32
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink: Optional[int] = None,  # static sink row count (with win_starts)
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged ragged decode attention over an INT8 page pool: pages stream
    as int8 (half the HBM bytes) with per-(page-row, kv-head) scales
    folded into the score/value dots — same contract as
    decode_attention_int8 with the page-table indirection (and the same
    ``win_starts``/``sink`` compressed mask as the bf16 kernel)."""
    return _paged_call(
        q, k_pool, v_pool, tables, lengths, (k_scales, v_scales),
        window=window, win_starts=win_starts, sink=sink,
        interpret=interpret,
    )


def paged_decode_attention_int8_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,  # [N, P, KH, D] int8
    v_pool: jnp.ndarray,
    k_scales: jnp.ndarray,  # [N, P, KH] f32
    v_scales: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,
    sink: Optional[int] = None,
) -> jnp.ndarray:
    """Dequantize-then-attend ground truth for the int8 paged kernel."""
    kf = k_pool.astype(jnp.float32) * k_scales[..., None]
    vf = v_pool.astype(jnp.float32) * v_scales[..., None]
    return paged_decode_attention_reference(
        q, kf, vf, tables, lengths, window=window,
        win_starts=win_starts, sink=sink,
    )


def gather_pages(pool: jnp.ndarray, table_row: jnp.ndarray) -> jnp.ndarray:
    """Materialize one slot's logical cache view [MB*P, KH, D] from the
    pool. Copies — used by the CPU reference path and by prefill-chunk
    attention (compute-bound, so the copy is cheap there); the decode hot
    path reads pages in place via the kernel."""
    MB = table_row.shape[0]
    P, KH, D = pool.shape[1], pool.shape[2], pool.shape[3]
    return pool[table_row].reshape(MB * P, KH, D)


def paged_decode_attention_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink: Optional[int] = None,  # static sink row count (with win_starts)
) -> jnp.ndarray:
    """Naive jnp paged decode attention (CPU fallback + parity truth):
    gathers each slot's pages into a contiguous view, then does the same
    masked attention as the dense reference. ``win_starts``/``sink``
    apply the window+sink compressed mask (rows in [sink, win_starts[b])
    are pruned and must not score)."""
    B, H, D = q.shape
    KH = k_pool.shape[2]
    G = H // KH
    k = jax.vmap(lambda t: gather_pages(k_pool, t))(tables)  # [B, C, KH, D]
    v = jax.vmap(lambda t: gather_pages(v_pool, t))(tables)
    C = k.shape[1]
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bckd->bkgc", qg, k).astype(jnp.float32)
    s = s / np.sqrt(D)
    cols = jnp.arange(C)[None, :]
    mask = cols <= lengths[:, None]
    if window is not None:
        mask = mask & (cols > lengths[:, None] - window)
    if win_starts is not None:
        mask = mask & ((cols < int(sink)) | (cols >= win_starts[:, None]))
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgc,bckd->bkgd", p, v)
    return out.reshape(B, H, D)
