"""The selective state-space recurrence of Mamba-2 (the SSD form: a scalar
decay a head).

One head keeps a float32 state ``S`` [P, N] (its P channels by N state
values). A row with input ``x`` [P], step ``dt`` > 0, decay ``a = dt * A``
(``A`` < 0, one number a head) and the projections ``B``, ``C`` [N] (shared by
the heads of a group) does

    S' = e^a S + (dt x) B^T          y = S' C

(the skip ``D x`` and everything around the recurrence are the caller's:
engine/mamba2.py). A row with ``dt`` 0 is an identity update: how rows beyond
a chunk's true length are padded.

Three forms of it:

* ``recurrence_reference``: row by row under ``lax.scan``. The truth the
  other two are tested against; never the served prefill.
* ``chunked``: the served prefill. Rows go ``SUB`` (128, the published
  ``chunk_size``) at a time. With ``G_i`` the running sum of ``a`` inside a
  sub-chunk (``a`` <= 0, so every ``e^{G_i - G_j}``, j <= i, is at most 1 and
  is taken directly: no factoring as the delta rule's decays a channel need):

      Y = lower(e^{G_i - G_j} (C_i . B_j)) (dt x) + e^{G} (C S_0^T)
      S_C = e^{G_C} S_0 + (dt x e^{G_C - G})^T B

  The first product touches no state and is made for all sub-chunks at once
  on the MXU; the two that carry the state run on the chip as ONE kernel,
  ``mamba_chunk``: a group's heads' states in VMEM over their sub-chunks.
* ``mamba_step``: one row a slot, the slot's state updated IN PLACE in the
  carried pool [L, S + 1, H, P, N] (engine/paged.py header: the state kind):
  a Pallas kernel whose blocks are (layer, slot, up to 64 heads), the pool
  aliased to its result. The entries are visited live ones first, so the
  dead ones (each handed the scratch slot) share ONE block that is fetched
  and written once: a live slot's state is read once and written once and
  nothing else of the pool moves. ``decode_step_reference`` is the same step
  in plain jnp (the CPU's serving path and the parity truth).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 128  # rows of one sub-chunk: the published chunk_size
HIGHEST = jax.lax.Precision.HIGHEST  # state arithmetic is float32


def _by_head(bc, H: int):
    """B or C [.., G, N] as each head reads it, [.., H, N]: head h reads
    group h // (H / G)."""
    return jnp.repeat(bc, H // bc.shape[-2], axis=-2)


def recurrence_reference(x, dt, a, B, C, s0):
    """Row by row. x [T, H, P]; dt, a [T, H]; B, C [T, G, N]; s0 [H, P, N]
    float32. Returns (y [T, H, P] float32, the state after)."""
    f32 = jnp.float32
    H = x.shape[1]

    def row(s, r):
        x, dt, a, B, C = r
        s = s * jnp.exp(a)[:, None, None] + (
            (dt[:, None] * x)[..., None] * _by_head(B, H)[:, None, :]
        )
        return s, jnp.einsum("hpn,hn->hp", s, _by_head(C, H), precision=HIGHEST)

    xs = tuple(v.astype(f32) for v in (x, dt, a, B, C))
    s, y = jax.lax.scan(row, s0.astype(f32), xs)
    return y, s


def _chunk_kernel(ct_ref, eg_ref, xt_ref, b_ref, dec_ref, s0_ref,
                  yt_ref, s_out_ref, s_scr):
    """One (group, sub-chunk) of ``chunked``'s sequential part: the group's
    heads' states stay in VMEM over their sub-chunks (the grid's inner axis),
    read from HBM before the first and written after the last."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first():
        s_scr[...] = s0_ref[...]

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32,
        )

    ct = ct_ref[0, 0]  # [N, SUB]: the group's C, transposed
    b = b_ref[0, 0]  # [SUB, N]
    for h in range(s_scr.shape[0]):
        s = s_scr[h]  # [P, N]
        # what the state before the sub-chunk gives each row, transposed:
        # [P, N] @ [N, SUB], each row's column scaled by its e^{G}
        yt_ref[0, h] = dot(s, ct) * eg_ref[0, h]
        s_scr[h] = s * dec_ref[0, h] + dot(xt_ref[0, h], b)

    @pl.when(i == pl.num_programs(1) - 1)
    def _last():
        s_out_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_chunk(ct, eg, xt, b, dec, s0, *, interpret: bool = False):
    """The sequential part of ``chunked`` as one kernel: ct [n, G, N, SUB]
    (C transposed); eg [n, H, 1, SUB] (each row's e^{G}); xt [n, H, P, SUB]
    (dt x e^{G_C - G}, transposed); b [n, G, SUB, N]; dec [n, H, 1, N] (the
    sub-chunk's whole decay e^{G_C}, along the state's lanes); s0 [H, P, N].
    Returns (yt [n, H, P, SUB]: what the carried state gives each row,
    transposed; the state after)."""
    n, G, N, _ = ct.shape
    H, P = xt.shape[1], xt.shape[2]
    hg = H // G
    group = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1, *tail), lambda g, i: (i, g, 0, 0))
    heads = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, hg, *tail), lambda g, i: (i, g, 0, 0))
    state = pl.BlockSpec((hg, P, N), lambda g, i: (g, 0, 0))
    return pl.pallas_call(
        _chunk_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((n, H, P, SUB), jnp.float32),
            jax.ShapeDtypeStruct((H, P, N), jnp.float32),
        ),
        grid=(G, n),
        in_specs=[group(N, SUB), heads(1, SUB), heads(P, SUB), group(SUB, N),
                  heads(1, N), state],
        out_specs=(heads(P, SUB), state),
        scratch_shapes=[pltpu.VMEM((hg, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ct, eg, xt, b, dec, s0)


def chunked(x, dt, a, B, C, s0, use_kernel: bool = False,
            interpret: bool = False):
    """The chunked form over T rows (a multiple of SUB). Shapes as
    ``recurrence_reference``; float32 inside. ``use_kernel``: the part that
    carries the state runs as ``mamba_chunk`` (the chip's path), else as a
    ``lax.scan`` of the same products. Returns (y [T, H, P] float32, the
    state after)."""
    f32 = jnp.float32
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    hg = H // G
    n = T // SUB
    # [n, H, SUB, .]: sub-chunks lead, heads (or groups) beside them
    split = lambda v: v.astype(f32).reshape(n, SUB, v.shape[1], -1).transpose(0, 2, 1, 3)  # noqa: E731
    xdt = split(x * dt.astype(f32)[..., None])  # [n, H, SUB, P]
    B, C = split(B), split(C)  # [n, G, SUB, N]
    Gs = jnp.cumsum(split(a)[..., 0], axis=-1)  # [n, H, SUB]
    rows = jnp.arange(SUB)
    lower = rows[:, None] >= rows[None, :]
    # masked before the exponential: above the diagonal the difference is >= 0
    decay = jnp.exp(jnp.where(lower, Gs[..., :, None] - Gs[..., None, :], -jnp.inf))
    cb = jnp.einsum("cgis,cgjs->cgij", C, B, precision=HIGHEST)
    m = decay.reshape(n, G, hg, SUB, SUB) * cb[:, :, None]
    y = jnp.einsum(
        "cghij,cghjp->cghip", m, xdt.reshape(n, G, hg, SUB, P),
        precision=HIGHEST,
    ).reshape(n, H, SUB, P)
    g_end = Gs[..., -1:]  # [n, H, 1]
    x_end = xdt * jnp.exp(g_end - Gs)[..., None]  # [n, H, SUB, P]
    eg, dec = jnp.exp(Gs), jnp.exp(g_end)

    def sub(s, r):
        C, eg, x_end, B, dec = r
        y0 = jnp.einsum("gin,ghpn->ghip", C, s.reshape(G, hg, P, N),
                        precision=HIGHEST).reshape(H, SUB, P)
        s = s * dec[..., None] + jnp.einsum(
            "ghip,gin->ghpn", x_end.reshape(G, hg, SUB, P), B, precision=HIGHEST
        ).reshape(H, P, N)
        return s, y0 * eg[..., None]

    if use_kernel:
        yt, s = mamba_chunk(
            C.swapaxes(-1, -2), eg[:, :, None, :], x_end.swapaxes(-1, -2), B,
            jnp.broadcast_to(dec[..., None], (n, H, 1, N)), s0.astype(f32),
            interpret=interpret,
        )
        y = y + yt.swapaxes(-1, -2)
    else:
        s, y0 = jax.lax.scan(sub, s0.astype(f32), (C, eg, x_end, B, dec))
        y = y + y0
    return y.transpose(0, 2, 1, 3).reshape(T, H, P), s


def decode_step_reference(x, dt, a, B, C, pool, layer, slots):
    """One row a batch entry against ``pool`` [L, S + 1, H, P, N], entry b's
    state at ``pool[layer, slots[b]]`` (distinct; a dead entry is handed the
    scratch slot with dt 0 and a 0). x [E, H, P]; dt, a [E, H]; B, C
    [E, G, N]. Returns (y [E, H, P] float32, the pool)."""
    f32 = jnp.float32
    H = x.shape[1]
    x, dt, a, B, C = (v.astype(f32) for v in (x, dt, a, B, C))
    s = pool[layer, slots] * jnp.exp(a)[..., None, None] + (
        (dt[..., None] * x)[..., None] * _by_head(B, H)[:, :, None, :]
    )
    y = jnp.einsum("bhpn,bhn->bhp", s, _by_head(C, H), precision=HIGHEST)
    return y, pool.at[layer, slots].set(s)


HEADS_PER_PROGRAM = 64  # a program's state block: 64 x 64 x 128 x 4 B = 4 MB
_VMEM_LIMIT = 48 * 1024 * 1024  # the block twice in and twice out, and room


def _step_kernel(lyr_ref, slot_ref, xcol_ref, rows_ref, s_ref, y_ref, out_ref,
                 *, scratch: int):
    """One (head group, entry): ``xcol_ref`` [1, P, hg] holds, a head, dt x
    along the state's ROWS as a column; ``rows_ref`` [1, 3, hg, N] the three
    vectors along its lanes, a row a head: the decay (one number, repeated),
    B and C. A dead entry's program (it was handed the scratch slot) does no
    arithmetic: the block goes back as it came."""
    del lyr_ref
    hg = s_ref.shape[2]
    live = slot_ref[pl.program_id(1)] != scratch

    @pl.when(live)
    def _update():
        xcol = xcol_ref[0]  # [P, hg]
        lane = jax.lax.broadcasted_iota(jnp.int32, xcol.shape, 1)
        y = jnp.zeros_like(xcol)
        for h in range(hg):
            row = lambda j: rows_ref[0, j, h:h + 1, :]  # noqa: E731
            s = s_ref[0, 0, h] * row(0) + xcol[:, h:h + 1] * row(1)
            out_ref[0, 0, h] = s
            # the head's y lies along the state's rows: its column of the block
            y = jnp.where(lane == h, jnp.sum(s * row(2), axis=1, keepdims=True), y)
        y_ref[0] = y

    @pl.when(jnp.logical_not(live))
    def _pass():
        out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_step(x, dt, a, B, C, pool, layer, slots, *, interpret: bool = False):
    """``decode_step_reference`` with the pool updated in place (shapes
    there; a dead entry's slot is the pool's last, the scratch slot, and its
    y is zeros): the pool is aliased to the second result, so the graph that
    donates its state runs this with no copy of it. The grid runs the
    entries innermost, live ones first: consecutive dead entries name the
    same block (the scratch slot's), which the pipeline then neither fetches
    nor writes again."""
    f32 = jnp.float32
    E, H, P = x.shape
    N = B.shape[-1]
    hg = min(HEADS_PER_PROGRAM, H)
    groups = H // hg
    scratch = pool.shape[1] - 1
    order = jnp.argsort(slots == scratch, stable=True)  # live entries first
    x, dt, a, B, C = (v.astype(f32)[order] for v in (x, dt, a, B, C))
    # [E * groups, P, hg]: a head group's dt x as columns
    xcol = (dt[..., None] * x).reshape(E, groups, hg, P).transpose(0, 1, 3, 2)
    xcol = xcol.reshape(E * groups, P, hg)
    rows = jnp.stack([
        jnp.broadcast_to(jnp.exp(a)[..., None], (E, H, N)),
        _by_head(B, H), _by_head(C, H),
    ], axis=1).reshape(E, 3, groups, hg, N).transpose(0, 2, 1, 3, 4)
    rows = rows.reshape(E * groups, 3, hg, N)
    state = pl.BlockSpec((1, 1, hg, P, N),
                         lambda j, b, lyr, slot: (lyr[0], slot[b], j, 0, 0))
    by_entry = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, *tail), lambda j, b, lyr, slot: (b * groups + j,) + (0,) * len(tail))
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, scratch=scratch),
        out_shape=(
            jax.ShapeDtypeStruct((E * groups, P, hg), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(groups, E),
            in_specs=[by_entry(P, hg), by_entry(3, hg, N), state],
            out_specs=(by_entry(P, hg), state),
        ),
        # operands count the two prefetched scalars: the pool is the fifth
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32)[order],
        xcol, rows, pool,
    )
    y = y.reshape(E, groups, P, hg).transpose(0, 1, 3, 2).reshape(E, H, P)
    return jnp.zeros_like(y).at[order].set(y), pool
