"""Paged decode attention over LATENT pages (multi-head latent attention,
absorbed form).

A latent-attention model caches, per row and layer, one compressed latent
``c`` (``kv_lora_rank`` values, 512 for the published models) and one rotary
key part ``k_r`` (``qk_rope_head_dim`` values, 64) that ALL heads share —
not per-head keys and values. The pool stores them in two arrays
(engine/paged.py header): ``c_pool [L, N, P, 512]`` and
``r_pool [L, N, P, 128]``, the rotary part in lanes [0, 64) of one whole
128-lane tile and zeros above (a 64-lane row is a lane-padded tile in HBM
either way, and Mosaic refuses a page DMA narrower than a tile: PERF.md,
PR 25).

In the absorbed form a head's query is carried into the latent space once
(``q_lat_i = q_nope_i W_uk_i^T``), so a decode step scores every head
against the SAME page:

    s_ij = (q_lat_i . c_j + q_rope_i . k_r_j) * sm_scale
    o_lat_i = sum_j softmax(s_i)_j c_j          (the caller applies W_uv_i)

One program serves one slot with every head at once: a page is read once
for all heads, scores are one ``[H, 512] x [512, R]`` product plus one
``[H, 128] x [128, R]``, and values one ``[H, R] x [R, 512]``. At 128
heads that is 242 operations a cache byte, the v5e's ridge: the kernel is
bound by the MXU and by HBM at once, so each loop iteration takes
``pages_per_iter`` pages (R = pages_per_iter * P rows), double-buffered —
fewer, larger products and DMA waits than a page at a time. Measured on the
v5e at 32 slots of 8.5k rows (PERF.md, PR 27): 1,446 / 914 / 750 / 720 us a
call at 1 / 2 / 4 / 8 pages an iteration against a least of 384; masking
only the last iteration changed nothing (751 / 717): what is left is the
MXU at a 128-row left operand, which pays a weight-tile load per 128 rows
streamed (57 % of its peak), not the vector work or the DMA.

Like ``paged_decode_attention`` the kernel takes the carried pools whole
(``memory_space=pl.ANY``) with the layer's index in SMEM and DMAs only the
pages the table names: no slice or copy of a layer's pages is made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
PAGES_PER_ITER = 4


def _mla_decode_kernel(
    len_ref,  # SMEM [B] int32
    tbl_ref,  # SMEM [B, MB] int32 — logical block -> physical page
    lyr_ref,  # SMEM [1] int32
    ql_ref,  # VMEM [1, H, Dc] — queries in the latent space
    qr_ref,  # VMEM [1, H, Dr] — rotary query parts, zero above the rotary dims
    c_pool,  # HBM [L, N, P, Dc]
    r_pool,  # HBM [L, N, P, Dr]
    o_ref,  # VMEM [1, H, Dc]
    lse_ref,  # VMEM [1, H, 128] f32 — log-sum-exp of each head's scores
    *,
    page_size: int,
    pages_per_iter: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    P, PB = page_size, pages_per_iter
    R = P * PB
    H, Dc = ql_ref.shape[1], ql_ref.shape[2]
    lyr = lyr_ref[0]
    length = len_ref[b]  # row `length` holds the just-written token
    n_blk = pl.cdiv(length + 1, P)
    n_it = pl.cdiv(n_blk, PB)
    ql = ql_ref[0] * sm_scale
    qr = qr_ref[0] * sm_scale

    def body(c_buf, r_buf, sems):
        def copies(slot, it):
            out = []
            for j in range(PB):
                # blocks past the slot's last re-read its last page (always
                # a valid page id); their columns lie beyond `length` and
                # are masked
                pg = tbl_ref[b, jnp.minimum(it * PB + j, n_blk - 1)]
                rows = pl.ds(j * P, P)
                out.append(pltpu.make_async_copy(
                    c_pool.at[lyr, pg], c_buf.at[slot, rows], sems.at[slot, 0, j]))
                out.append(pltpu.make_async_copy(
                    r_pool.at[lyr, pg], r_buf.at[slot, rows], sems.at[slot, 1, j]))
            return out

        for c in copies(0, 0):
            c.start()

        def loop(i, carry):
            m, l, acc = carry  # [H, 1], [H, 1], [H, Dc] f32
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_it)
            def _prefetch():
                for c in copies(1 - slot, i + 1):
                    c.start()

            for c in copies(slot, i):
                c.wait()
            cb = c_buf[slot]  # [R, Dc]
            rb = r_buf[slot]  # [R, Dr]
            cols = i * R + jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
            valid = cols <= length
            contract_last = (((1,), (1,)), ((), ()))
            s = jax.lax.dot_general(
                ql, cb, contract_last, preferred_element_type=jnp.float32
            ) + jax.lax.dot_general(
                qr, rb, contract_last, preferred_element_type=jnp.float32
            )  # [H, R]
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(cb.dtype), cb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, Dc]
            return m_new, l_new, acc * alpha + pv

        init = (
            jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, Dc), jnp.float32),
        )
        m, l, acc = jax.lax.fori_loop(0, n_it, loop, init)
        safe_l = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(safe_l), lse_ref.shape[1:])

    pl.run_scoped(
        body,
        c_buf=pltpu.VMEM((2, R, Dc), c_pool.dtype),
        r_buf=pltpu.VMEM((2, R, qr_ref.shape[2]), r_pool.dtype),
        sems=pltpu.SemaphoreType.DMA((2, 2, PB)),
    )


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "pages_per_iter", "interpret")
)
def paged_mla_decode_attention(
    q_lat: jnp.ndarray,  # [B, H, Dc] — q_nope_i W_uk_i^T, one query per slot
    q_rope: jnp.ndarray,  # [B, H, Dr] — rotary query part, zero-padded to Dr
    c_pool: jnp.ndarray,  # [L, N, P, Dc] — every layer's latent pages
    r_pool: jnp.ndarray,  # [L, N, P, Dr] — and their rotary key parts
    layer: jnp.ndarray,  # scalar int32
    tables: jnp.ndarray,  # [B, MB] int32 — logical block -> physical page
    lengths: jnp.ndarray,  # [B] int32; row `lengths[b]` is the newest token
    *,
    sm_scale: float,  # (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
    pages_per_iter: int = PAGES_PER_ITER,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed latent decode attention over layer ``layer`` of the stacked
    latent pool; returns ``o_lat`` [B, H, Dc] (softmax-weighted latents;
    the caller applies each head's W_uv)."""
    B, H, Dc = q_lat.shape
    Dr = q_rope.shape[-1]
    P = c_pool.shape[2]
    kernel = functools.partial(
        _mla_decode_kernel, page_size=P,
        pages_per_iter=max(1, min(pages_per_iter, tables.shape[1])),
        sm_scale=float(sm_scale),
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # Two results on purpose. A profile names a device operation by its
    # instruction's text, operands included, and the benchmark counts a
    # decode step's kernels by this function's name in those texts. A call
    # with ONE result is read by its consumer under the call's own name (the
    # W_uv product then counted as a second kernel a layer: PERF.md, PR 27);
    # a call with two is read through tuple elements, so the kernel's own
    # instruction is the only operation that carries the name. The second is
    # each head's log-sum-exp, lane-replicated: what a later split of a
    # slot's pages over several programs would merge by.
    out, _ = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Dc), q_lat.dtype),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
        ),
        grid=(B,),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((1, H, Dc), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, Dr), lambda b: (b, 0, 0)),
            hbm, hbm,
        ],
        out_specs=(
            pl.BlockSpec((1, H, Dc), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, 128), lambda b: (b, 0, 0)),
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32), tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), q_lat, q_rope, c_pool, r_pool,
    )
    return out


def paged_mla_decode_attention_reference(
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    c_pool: jnp.ndarray,
    r_pool: jnp.ndarray,
    layer: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    sm_scale: float,
) -> jnp.ndarray:
    """The same attention in plain jnp (CPU serving path and parity truth):
    gathers each slot's pages of layer ``layer`` and attends over rows
    [0, lengths[b]] with a float32 softmax."""
    c = c_pool[layer, tables]  # [B, MB, P, Dc]
    r = r_pool[layer, tables]
    B = tables.shape[0]
    c = c.reshape(B, -1, c.shape[-1])
    r = r.reshape(B, -1, r.shape[-1])
    f32 = jnp.float32  # widened operands: see engine/latent.py _einsum32
    s = (
        jnp.einsum("bhc,bsc->bhs", q_lat.astype(f32), c.astype(f32))
        + jnp.einsum("bhr,bsr->bhs", q_rope.astype(f32), r.astype(f32))
    ) * sm_scale
    mask = jnp.arange(c.shape[1])[None, :] <= lengths[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
    out = jnp.einsum("bhs,bsc->bhc", p.astype(f32), c.astype(f32))
    return out.astype(q_lat.dtype)
