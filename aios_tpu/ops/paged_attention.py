"""Paged ragged decode attention: the slot cache behind a page table.

Same online-softmax recurrence as `decode_attention` (one query per slot
against that slot's valid cache rows, double-buffered HBM→VMEM DMA), except
K/V rows live in a shared *page pool* instead of one contiguous slab per
slot: logical block i of slot b, at layer l, is physical page
`pool[l, tables[b, i]]` of the stacked pool `[L, N, P, KH*D]` — the very
array the model's layer loop carries, in the layout it is stored in
(engine/paged.py): a row's kv heads side by side on the lanes. The kernel
takes that array whole (HBM, `memory_space=pl.ANY`) plus the layer's index
as a scalar in SMEM, reads the table from SMEM and DMAs only the pages
that hold valid rows, so HBM is reserved per *page in use*, not per
`num_slots x max_context` — that decoupling is what lets many long-context
slots oversubscribe a fixed pool (SURVEY.md section 7.2 "paged KV cache in
HBM"; the fixed-shape-jit half of hard part #1).

The pool never moves, neither between dispatches nor inside a step: growth
is a host-side free-list allocation plus a new table row passed with the
next dispatch, and a decode step touches, of the pool, the one row a slot
scatters and the pages the tables name. No slice, reshape or copy of a
layer's pages stands between the scan's carry and the `pallas_call`; kv
head h of a page is its lanes [h*D, (h+1)*D), for any head size. Everything
else that reads or writes the pool reshapes only what is small: new rows
[.., KH, D] -> [.., KH*D] on the way in (`merge_heads`, and `write_rows`
for a prompt's consecutive rows), gathered pages back on the way out
(`gather_pages`: a key tile's pages for a prefill chunk, a slot's for the
verify step and the CPU reference). Shapes stay static everywhere —
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
    lyr_ref,  # SMEM [1] int32 — which layer of the stacked pool to read
    *args,  # [ws_ref SMEM [B] when sink is not None,] q_ref, k_pool,
    #         v_pool [L, N, P, KH*D] in HBM, then quantized: ks_pool
    #         [N, KH, P] f32 (one layer, head-major — the lane dim must be
    #         the 128-aligned page axis), vs_pool, o_ref; else o_ref
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
    lyr = lyr_ref[0]

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
        def copies(slot, blk):
            # THE paged indirection: logical block -> physical page, read
            # where it lies in the pool the layer loop carries
            pg = tbl_ref[b, blk]
            pairs = [(k_pool.at[lyr, pg], k_buf), (v_pool.at[lyr, pg], v_buf)]
            if quantized:
                pairs += [(ks_pool.at[pg], ks_buf), (vs_pool.at[pg], vs_buf)]
            return [
                pltpu.make_async_copy(src, scr.at[slot], sems.at[slot, i])
                for i, (src, scr) in enumerate(pairs)
            ]

        def start_all(slot, blk):
            for c in copies(slot, blk):
                c.start()

        def wait_all(slot, blk):
            for c in copies(slot, blk):
                c.wait()

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

    scratch = dict(
        k_buf=pltpu.VMEM((2, P, KH * D), k_pool.dtype),
        v_buf=pltpu.VMEM((2, P, KH * D), v_pool.dtype),
        sems=pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)),
    )
    if quantized:
        scratch.update(
            ks_buf=pltpu.VMEM((2, KH, P), jnp.float32),
            vs_buf=pltpu.VMEM((2, KH, P), jnp.float32),
        )
    pl.run_scoped(body, **scratch)


def _paged_call(q, k_pool, v_pool, layer, tables, lengths, scales, *,
                window, win_starts, sink, interpret):
    """Shared pallas_call plumbing for both pool dtypes. The pools go in
    whole and untouched: no slice, reshape or copy of them is made here."""
    B, H, D = q.shape
    P, KH = k_pool.shape[2], k_pool.shape[3] // D
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
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    args = [
        lengths.astype(jnp.int32),
        tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
    ]
    if compressed:
        args.append(win_starts.astype(jnp.int32))
    args += [q, k_pool, v_pool]
    if quantized:
        # this layer's [N, P, KH] scales -> head-major [N, KH, P]: the
        # whole-page DMA then has the 128-row page axis on lanes (see
        # decode_attention.py). 1.2 MB at Mistral's geometry; the int8
        # VALUES are not copied
        args.extend(s[layer].transpose(0, 2, 1) for s in scales)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        grid=(B,),
        in_specs=[
            smem,  # lengths
            smem,  # page tables
            smem,  # layer
            *([smem] if compressed else []),  # window starts
            pl.BlockSpec((1, H, D), lambda b: (b, 0, 0)),
            *([hbm] * (4 if quantized else 2)),  # pools (+ scales) in HBM
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b: (b, 0, 0)),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("window", "sink", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] — one new query per slot
    k_pool: jnp.ndarray,  # [L, N, P, KH*D] — every layer's shared pages
    v_pool: jnp.ndarray,  # [L, N, P, KH*D]
    layer: jnp.ndarray,  # scalar int32 — the layer whose pages are read
    tables: jnp.ndarray,  # [B, MB] int32 — logical block -> physical page
    lengths: jnp.ndarray,  # [B] int32; row `lengths[b]` is the newest token
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink: Optional[int] = None,  # static sink row count (with win_starts)
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged ragged decode attention over layer ``layer`` of the stacked
    pool; returns [B, H, D]. With ``win_starts``/``sink`` (window+sink KV
    compression) slot b attends only rows < sink or >= win_starts[b] — the
    pruned middle is masked."""
    return _paged_call(
        q, k_pool, v_pool, layer, tables, lengths, None,
        window=window, win_starts=win_starts, sink=sink,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def window_decode_attention(
    q: jnp.ndarray,  # [B, H, D]
    k_pool: jnp.ndarray,  # [L, N, P, KH*D] bf16
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,
    tables: jnp.ndarray,  # [B, MAX_BLOCKS] int32: the window kind's pages
    lengths: jnp.ndarray,  # [B] int32
    *,
    window: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """``paged_decode_attention`` for the WINDOW layers of a stack that also
    has full ones (engine/paged.py header): the same kernel under a jitted
    name of its own, because a device trace names a kernel by the jitted
    function it lies in and the two kinds' attention are told apart there."""
    return _paged_call(
        q, k_pool, v_pool, layer, tables, lengths, None,
        window=window, win_starts=None, sink=None, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("window", "sink", "interpret"))
def paged_decode_attention_int8(
    q: jnp.ndarray,  # [B, H, D]
    k_pool: jnp.ndarray,  # [L, N, P, KH*D] int8
    v_pool: jnp.ndarray,  # [L, N, P, KH*D] int8
    k_scales: jnp.ndarray,  # [L, N, P, KH] f32
    v_scales: jnp.ndarray,  # [L, N, P, KH] f32
    layer: jnp.ndarray,  # scalar int32
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
        q, k_pool, v_pool, layer, tables, lengths, (k_scales, v_scales),
        window=window, win_starts=win_starts, sink=sink,
        interpret=interpret,
    )


def paged_decode_attention_int8_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,  # [L, N, P, KH*D] int8
    v_pool: jnp.ndarray,
    k_scales: jnp.ndarray,  # [L, N, P, KH] f32
    v_scales: jnp.ndarray,
    layer: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,
    sink: Optional[int] = None,
) -> jnp.ndarray:
    """Dequantize-then-attend ground truth for the int8 paged kernel (the
    one layer's pages only)."""
    D = q.shape[-1]

    def dequant(pool, scales):
        x = split_heads(pool[layer], D).astype(jnp.float32)
        return merge_heads(x * scales[layer][..., None])[None]

    return paged_decode_attention_reference(
        q, dequant(k_pool, k_scales), dequant(v_pool, v_scales), 0, tables,
        lengths, window=window, win_starts=win_starts, sink=sink,
    )


def merge_heads(rows: jnp.ndarray) -> jnp.ndarray:
    """K/V rows [..., KH, D] as the pool stores them, [..., KH*D]."""
    return rows.reshape(*rows.shape[:-2], -1)


def split_heads(rows: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """Stored rows [..., KH*D] back to [..., KH, D]."""
    return rows.reshape(*rows.shape[:-1], -1, head_dim)


def write_rows(
    pool: jnp.ndarray,  # [L, N, P, W] — k/v values (W = KH*D) or scales
    layer,  # scalar layer index with rows [T, W]; None with rows [L, T, W]
    rows: jnp.ndarray,  # T consecutive rows of ONE slot
    block_pages: jnp.ndarray,  # [>= ceil(T/P)] the pages those rows land on
    offset=0,  # row of block_pages[0] the first row lands on (T < P only)
) -> jnp.ndarray:
    """Write a prompt's (or a chunk's) consecutive rows into the pool by
    whole pages: T a multiple of P starting on a page boundary, or T < P
    inside one page (offset + T <= P: the slice update would clamp, not
    drop, rows past the page) — the only two cases power-of-two buckets,
    chunks and page sizes give. A row-granular scatter (`.at[:, pages, offs]`) of the
    same rows costs 8.7 x on the chip (PERF.md, PR 25): a stored row spans
    eight lane tiles. Decode and verify, whose rows go to different slots'
    pages, keep the row scatter."""
    P = pool.shape[2]
    T, W = rows.shape[-2:]
    rows = rows.astype(pool.dtype)
    if T % P == 0:
        at = slice(None) if layer is None else layer
        return pool.at[at, block_pages[: T // P]].set(
            rows.reshape(*rows.shape[:-2], T // P, P, W)
        )
    if T > P:
        raise ValueError(f"{T} rows are neither whole pages of {P} nor one")
    return jax.lax.dynamic_update_slice(
        pool,
        rows.reshape(-1, 1, T, W),
        (0 if layer is None else layer, block_pages[0], offset, 0),
    )


def gather_pages(
    pool: jnp.ndarray, layer, tables: jnp.ndarray, head_dim: int
) -> jnp.ndarray:
    """Materialize logical cache views [..., MB*P, KH, D] of layer
    ``layer`` from the stacked pool [L, N, P, KH*D], one per table row
    [..., MB]. Copies the pages the tables name and nothing else — every
    reader but the decode kernel (which reads pages in place) comes through
    here: a chunked prefill with ONE key tile's slice of the slot's table at
    a time (model.paged_kv_block), the speculative verify and the CPU
    reference with whole tables."""
    pages = pool[layer, tables]  # [..., MB, P, KH*D]
    rows = pages.reshape(*tables.shape[:-1], -1, pages.shape[-1])
    return split_heads(rows, head_dim)


def paged_decode_attention_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,  # [L, N, P, KH*D] — the kernel's own operands
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,  # scalar int32
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    window: Optional[int] = None,
    win_starts: Optional[jnp.ndarray] = None,  # [B] int32 live-window start
    sink: Optional[int] = None,  # static sink row count (with win_starts)
) -> jnp.ndarray:
    """Naive jnp paged decode attention (CPU fallback + parity truth):
    gathers each slot's pages of layer ``layer`` into a contiguous view,
    then does the same masked attention as the dense reference.
    ``win_starts``/``sink`` apply the window+sink compressed mask (rows in
    [sink, win_starts[b]) are pruned and must not score)."""
    B, H, D = q.shape
    k = gather_pages(k_pool, layer, tables, D)  # [B, C, KH, D]
    v = gather_pages(v_pool, layer, tables, D)
    C, KH = k.shape[1], k.shape[2]
    G = H // KH
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
