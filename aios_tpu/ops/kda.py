"""The gated delta rule with a decay a channel (Kimi Delta Attention, KDA).

One head keeps a float32 state ``S`` [K, V] (key channels by value
channels). A row with query ``q`` and key ``k`` (both [K], L2-normalised by
the caller, ``q`` scaled), value ``v`` [V], write strength ``beta`` in (0, 1)
and decay ``alpha`` in (0, 1)^K (``g = log alpha``) does

    S' = (I - beta k k^T) Diag(alpha) S + beta k v^T        o = S'^T q

which is, with ``u = v - S^T (alpha * k)`` (what the decayed state does not
yet say of ``k``): ``S' = alpha * S + (beta k) u^T``.

Three forms of it:

* ``recurrence_reference``: row by row under ``lax.scan``. The truth the
  other two are tested against; never the served prefill.
* ``chunked``: the served prefill. Rows go ``SUB`` (64) at a time: inside a
  sub-chunk the products of (I - beta k k^T) Diag(alpha) are taken in the
  WY / UT-transform form (one unit-lower-triangular solve a sub-chunk, made
  for all sub-chunks at once), and only four products a sub-chunk touch the
  carried state: on the chip those run as one kernel, ``kda_chunk``, a
  head's state in VMEM over its sub-chunks. With ``G_i`` the running sum of ``g`` inside a sub-chunk,
  ``L = strict_lower(Kg Kn^T)``, ``Kg_i = k_i e^{G_i}``, ``Kn_j = k_j
  e^{-G_j}``:

      U = (I + L Diag(beta))^{-1} (V - Kg S_0)
      O = (Q e^{G}) S_0 + lower(Qg Kn^T) Diag(beta) U
      S_C = e^{G_C} * S_0 + (K e^{G_C - G} beta)^T U

  ``e^{-G_j}`` alone overflows (g may reach the lower bound, -5, a row), so
  the pairwise products are made a ``BLOCK`` (16) of rows at a time: row
  block I scales its own rows by ``e^{G_i - r_I}`` (``r_I`` = G just before
  the block: at most 1) and the columns by ``e^{r_I - G_j}``, at most 1
  for the blocks before it and at most e^{5 x 16} = e^80, finite in
  float32, inside it. A row with ``beta`` 0 and ``g`` 0 is an identity
  update: how rows beyond a chunk's true length are padded.
* ``kda_step``: one row a slot, the slot's state updated IN PLACE in the
  carried pool [L, S + 1, H, K, V] (engine/paged.py header: the state kind):
  a Pallas kernel whose blocks are (layer, slot, half the heads), the pool
  aliased to its result, so a layer's states are read once and written
  once and nothing pool-sized is copied. ``decode_step_reference`` is the
  same step in plain jnp (the CPU's serving path and the parity truth).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 64  # rows of one sub-chunk: one triangular solve, one state update
BLOCK = 16  # rows whose decays are factored as e^{G_i} e^{-G_j}
MAX_EXP = 80.0  # the largest exponent a factor may take: BLOCK rows of g >= -5
HIGHEST = jax.lax.Precision.HIGHEST  # state arithmetic is float32


def recurrence_reference(q, k, v, g, beta, s0):
    """Row by row. q, k, g [T, H, K]; v [T, H, V]; beta [T, H]; s0
    [H, K, V] float32. Returns (o [T, H, V] float32, the state after)."""
    f32 = jnp.float32

    def row(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[..., None]
        u = v - jnp.einsum("hkv,hk->hv", s, k, precision=HIGHEST)
        s = s + (beta[:, None] * k)[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q, precision=HIGHEST)

    xs = tuple(a.astype(f32) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(row, s0.astype(f32), xs)
    return o, s


def _pairwise(a, b, G, r):
    """lower-or-equal part's M_ij = sum_c a_ic b_jc e^{G_ic - G_jc} for rows
    i >= j of one sub-chunk, BLOCK rows of i at a time. a, b, G
    [.., SUB, K]; r [.., SUB / BLOCK, K] (G just before each block).
    Entries above the diagonal are finite garbage; callers mask them."""
    nb = SUB // BLOCK
    lead = a.shape[:-2]
    K = a.shape[-1]
    Gb = G.reshape(*lead, nb, BLOCK, K)
    ab = a.reshape(*lead, nb, BLOCK, K) * jnp.exp(Gb - r[..., None, :])
    # columns as row block I sees them: [.., nb, SUB, K]
    # (columns of LATER blocks would pass MAX_EXP: they are masked, so capped)
    bI = b[..., None, :, :] * jnp.exp(
        jnp.minimum(r[..., :, None, :] - G[..., None, :, :], MAX_EXP)
    )
    m = jnp.einsum("...ibc,...ijc->...ibj", ab, bI, precision=HIGHEST)
    return m.reshape(*lead, SUB, SUB)


def _sub_chunk_kernel(w_ref, u0_ref, qg_ref, p_ref, kt_ref, decay_ref, s0_ref,
                      o_ref, s_out_ref, s_scr):
    """One (head, sub-chunk) of ``chunked``'s sequential part: the head's
    state stays in VMEM over its sub-chunks (the grid's inner axis), read
    from HBM before the first and written after the last."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first():
        s_scr[...] = s0_ref[0]

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32,
        )

    s = s_scr[...]  # [K, V]
    u = u0_ref[0, 0] - dot(w_ref[0, 0], s)
    o_ref[0, 0] = dot(qg_ref[0, 0], s) + dot(p_ref[0, 0], u)
    K = s.shape[0]
    # Diag(decay) s on the MXU: the decay lies along the state's rows, and a
    # row vector scales a matrix's columns (of the identity: the diagonal)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1))
    s = dot(jnp.where(eye, decay_ref[0, 0], 0.0), s) + dot(kt_ref[0, 0], u)
    s_scr[...] = s

    @pl.when(i == pl.num_programs(1) - 1)
    def _last():
        s_out_ref[0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(w, u0, qg, P, kt, decay, s0, *, interpret: bool = False):
    """The sequential part of ``chunked`` as one kernel: w, qg [n, H, SUB, K];
    u0 [n, H, SUB, V]; P [n, H, SUB, SUB]; kt [n, H, K, SUB] (the end-decayed
    keys, transposed); decay [n, H, 1, K]; s0 [H, K, V]. Returns (o
    [n, H, SUB, V], the state after)."""
    n, H, _, K = w.shape
    V = u0.shape[-1]
    by_sub = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1, *tail), lambda h, i: (i, h, 0, 0))
    state = pl.BlockSpec((1, K, V), lambda h, i: (h, 0, 0))
    return pl.pallas_call(
        _sub_chunk_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((n, H, SUB, V), jnp.float32),
            jax.ShapeDtypeStruct((H, K, V), jnp.float32),
        ),
        grid=(H, n),
        in_specs=[by_sub(SUB, K), by_sub(SUB, V), by_sub(SUB, K),
                  by_sub(SUB, SUB), by_sub(K, SUB), by_sub(1, K), state],
        out_specs=(by_sub(SUB, V), state),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w, u0, qg, P, kt, decay, s0)


def chunked(q, k, v, g, beta, s0, use_kernel: bool = False,
            interpret: bool = False):
    """The chunked form over T rows (a multiple of SUB). Shapes as
    ``recurrence_reference``; float32 inside. ``use_kernel``: the part that
    carries the state runs as ``kda_chunk`` (the chip's path), else as a
    ``lax.scan`` of the same products. Returns (o [T, H, V] float32, the
    state after)."""
    f32 = jnp.float32
    T, H, K = q.shape
    V = v.shape[-1]
    n = T // SUB
    # [n, H, SUB, .]: sub-chunks lead, heads beside them
    split = lambda a: a.astype(f32).reshape(n, SUB, H, -1).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = beta.astype(f32).reshape(n, SUB, H).transpose(0, 2, 1)  # [n, H, SUB]
    G = jnp.cumsum(g, axis=2)
    # G just before each block of BLOCK rows (0 before the first)
    r = jnp.concatenate(
        [jnp.zeros_like(G[:, :, :1]), G[:, :, BLOCK - 1:-1:BLOCK]], axis=2
    )
    rows = jnp.arange(SUB)
    strict = rows[:, None] > rows[None, :]
    A = jnp.where(strict, _pairwise(k, k, G, r), 0.0) * beta[:, :, None, :]
    P = jnp.where(
        rows[:, None] >= rows[None, :], _pairwise(q, k, G, r), 0.0
    ) * beta[:, :, None, :]
    eG = jnp.exp(G)
    kg, qg = k * eG, q * eG
    # (I + A)^{-1} [Kg | V]: unit lower triangular, every sub-chunk at once
    solved = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(SUB, dtype=f32), jnp.concatenate([kg, v], axis=-1),
        lower=True, unit_diagonal=True,
    )
    w, u0 = solved[..., :K], solved[..., K:]
    g_end = G[:, :, -1:, :]  # [n, H, 1, K]
    k_end = k * jnp.exp(g_end - G) * beta[..., None]

    def sub(s, x):
        w, u0, qg, P, k_end, decay = x
        u = u0 - jnp.einsum("hik,hkv->hiv", w, s, precision=HIGHEST)
        o = jnp.einsum("hik,hkv->hiv", qg, s, precision=HIGHEST) + jnp.einsum(
            "hij,hjv->hiv", P, u, precision=HIGHEST
        )
        s = s * decay[..., None] + jnp.einsum(
            "hik,hiv->hkv", k_end, u, precision=HIGHEST
        )
        return s, o

    decay = jnp.exp(g_end)  # [n, H, 1, K]
    if use_kernel:
        o, s = kda_chunk(
            w, u0, qg, P, k_end.swapaxes(-1, -2), decay, s0.astype(f32),
            interpret=interpret,
        )
    else:
        s, o = jax.lax.scan(
            sub, s0.astype(f32), (w, u0, qg, P, k_end, decay[:, :, 0])
        )
    return o.transpose(0, 2, 1, 3).reshape(T, H, V), s


def decode_step_reference(q, k, v, g, beta, pool, layer, slots):
    """One row a batch entry against ``pool`` [L, S + 1, H, K, V], entry b's
    state at ``pool[layer, slots[b]]`` (distinct; a dead entry is handed the
    scratch slot with beta 0 and g 0). q, k, g [B, H, K]; v [B, H, V]; beta
    [B, H]. Returns (o [B, H, V] float32, the pool)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    s = pool[layer, slots] * jnp.exp(g)[..., None]
    u = v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=HIGHEST)
    s = s + (beta[..., None] * k)[..., None] * u[:, :, None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=HIGHEST)
    return o, pool.at[layer, slots].set(s)


HEADS_PER_PROGRAM = 16  # a program's state block: 16 x 128 x 128 x 4 B = 1 MB


def _decode_kernel(lyr_ref, slot_ref, cols_ref, v_ref, s_ref, o_ref, out_ref, *,
                   scratch: int):
    """One (entry, head group): ``cols_ref`` [1, K, 4 * HG] holds, a head,
    the four vectors the update needs along the state's ROWS, as columns
    (made so by the caller: XLA transposes a few KB where the kernel would
    relayout): alpha, beta k, alpha k, q. A dead entry's program (it was
    handed the scratch slot) does no arithmetic: the block goes back as it
    came."""
    del lyr_ref
    hg = v_ref.shape[1]
    live = slot_ref[pl.program_id(0)] != scratch

    @pl.when(live)
    def _update():
        cols = cols_ref[0]  # [K, 4 * hg]
        for h in range(hg):
            s = s_ref[0, 0, h]  # [K, V]
            col = lambda j: cols[:, j * hg + h: j * hg + h + 1]  # noqa: E731
            u = v_ref[0, h:h + 1, :] - jnp.sum(s * col(2), axis=0, keepdims=True)
            s = s * col(0) + col(1) * u
            out_ref[0, 0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(s * col(3), axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _pass():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(q, k, v, g, beta, pool, layer, slots, *,
             interpret: bool = False):
    """``decode_step_reference`` with the pool updated in place (shapes
    there; a dead entry's slot is the pool's last, the scratch slot, and its
    o is zeros): the pool is aliased to the second result, so the graph that
    donates its state runs this with no copy of it. A program moves its 1 MB
    block in and out whether its entry is live or dead (PERF.md, PR 40, has
    what giving a dead entry its neighbour's block bought and cost)."""
    f32 = jnp.float32
    B, H, K = q.shape
    V = v.shape[-1]
    hg = min(HEADS_PER_PROGRAM, H)
    groups = H // hg
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    alpha = jnp.exp(g)
    # [B, H / hg, K, 4 * hg]: a head group's vectors as columns
    cols = jnp.stack([alpha, beta[..., None] * k, alpha * k, q], axis=1)
    cols = cols.reshape(B, 4, groups, hg, K).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(B * groups, K, 4 * hg)
    state = pl.BlockSpec((1, 1, hg, K, V),
                         lambda b, j, lyr, slot: (lyr[0], slot[b], j, 0, 0))
    rows = pl.BlockSpec((1, hg, V), lambda b, j, lyr, slot: (b, j, 0))
    o, pool = pl.pallas_call(
        functools.partial(_decode_kernel, scratch=pool.shape[1] - 1),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, V), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, groups),
            in_specs=[
                pl.BlockSpec((1, K, 4 * hg),
                             lambda b, j, lyr, slot: (b * groups + j, 0, 0)),
                rows, state,
            ],
            out_specs=(rows, state),
        ),
        # operands count the two prefetched scalars: the pool is the fifth
        input_output_aliases={4: 1},
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
        cols, v, pool,
    )
    return o, pool
