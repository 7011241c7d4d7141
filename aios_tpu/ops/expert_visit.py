"""The routed experts of a DECODE step: only the experts a live row picked
are read, each where it lies in the stacks (the mathematics and its caller:
engine/moe.py ``moe_ffn_visit``).

A decode step has 8-32 rows and is bound by the expert weights' bytes, and
its live rows pick a few of the held experts (about 4 of 64, 2.4 of 8, 4-5 of
16 a layer call in the benchmark's cells; 7.1 of 8 with eight live slots).
The kernel runs ONE grid over (visit, tile): visit
``i`` is the ``i``-th touched expert in ascending order (``visit_list``, made
on the device from the step's own picks; the grid's first bound is the
touched count, a traced value), and its tiles are whole-row blocks of that
expert's int8 matrices, ``[tk, 2F]`` of the fused ``[gate | up]`` and then
``[tkd, E]`` of ``down``, each a contiguous run of the stack at ``[l, e]``
that the block spec's index map names from the scalar-prefetched list — no
slice of a layer's experts exists as an operand, and the pipeline streams
visit ``i+1``'s first tile under visit ``i``'s last product. Every visit
runs over ALL the step's rows (a sublane tile or two: nothing is gathered),
its SwiGLU gated per row by the router's weight (zero for a row that did not
pick it) before the down product, and the visits add up in float32 in the
one resident ``[N, E]`` block, cast once by the caller.

Arithmetic, cast for cast, is ``moe.moe_ffn_dense`` restricted to the
touched experts, but for the sum over experts, which stays float32 here
(the dense path rounds each expert's result to bfloat16 first).

Measured on the v5e (PERF.md, PR 32): a visit takes 16.4-16.8 us at 11.0 MB
an expert (13.4 at 819 GB/s), 68 at 47 MB (57.6), 246 at 176 MB (215),
against 15.1 / 69.5 / 242 an expert for the dense path's fusions; the loop
of XLA products that the CPU runs reads 20.6-22 / 77-83 / 249-256. Blocks of
1 to 3 MB read within 3 % of one another, 4 and 8 MB 6 and 20 % slower at
the smallest expert (a block's product then outlasts the next block's DMA);
``pl.Buffered(3)`` is refused by this Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_BYTES = 2 * 1024 * 1024  # an int8 weight block the pipeline holds twice
_VMEM_LIMIT = 96 * 1024 * 1024


def visit_list(touched: jnp.ndarray):
    """(visit [X] int32, n scalar int32) of ``touched`` [X] bool: the
    touched experts in ascending order, then the last of them repeated, and
    how many there are. ``visit[i]`` is the number of experts that lie
    before the ``i``-th touched one."""
    X = touched.shape[0]
    rank = jnp.cumsum(touched.astype(jnp.int32))
    n = rank[-1]
    upto = jnp.minimum(jnp.arange(X, dtype=jnp.int32), n - 1)
    visit = jnp.sum(rank[None, :] <= upto[:, None], axis=1, dtype=jnp.int32)
    return visit, n


def _tile_rows(rows: int, width: int) -> int:
    """The most rows, a multiple of 128 that divides ``rows``, of an int8
    block ``[rows', width]`` within TILE_BYTES (128 where none is)."""
    if rows % 128:  # (an ungated expert's width: ``supports_pallas``)
        return rows
    best = 128
    for t in range(128, rows + 1, 128):
        if rows % t == 0 and t * width <= TILE_BYTES:
            best = t
    return best


def supports_pallas(E: int, F: int, act: str = "swiglu") -> bool:
    """Lane-aligned widths: the row tiles are multiples of 128. An UNGATED
    expert (``act`` "relu2": one up matrix and no [gate | up] to split at a
    lane boundary) may have a width that is whole int8 tiles of 32 rows only,
    because BOTH its matrices are stored with the width along their rows (the
    up matrix transposed, ``[F, E]``: the TPU's own layout of an int8
    ``[.., E, F]`` array with F no whole lane tiles puts E on the lanes, and
    a kernel handed it would be handed a COPY of the stack every call:
    tests/test_mosaic_aot.py -k sublayers); its down matrix is then ONE block
    (``_tile_rows``)."""
    return E % 128 == 0 and (F % 128 == 0 or (act == "relu2" and F % 32 == 0))


def _visit_kernel(visit_ref, n_ref, lyr_ref, x_ref, g_ref, wgu_ref, sgu_ref,
                  wd_ref, sd_ref, o_ref, gu_acc, z_scr, *, nku: int, F: int,
                  act: str = "swiglu"):
    i, s = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    dt = x_ref.dtype
    nkd, _, tkd = z_scr.shape

    @pl.when((i == 0) & (s == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    live = i < n_ref[0]  # false only in the one step of a visit-less call

    @pl.when(live & (s < nku))
    def _gate_up():
        if act == "swiglu":
            part = jnp.dot(x_ref[s], wgu_ref[...].astype(dt),
                           preferred_element_type=f32)
        else:  # the up matrix lies transposed: a K-tile is a run of columns
            part = jax.lax.dot_general(
                x_ref[s], wgu_ref[...].astype(dt), (((1,), (1,)), ((), ())),
                preferred_element_type=f32,
            )

        @pl.when(s == 0)
        def _():
            gu_acc[...] = part

        @pl.when(s > 0)
        def _():
            gu_acc[...] += part

    @pl.when(live & (s == nku - 1))
    def _swiglu():
        gu = (gu_acc[...] * sgu_ref[...]).astype(dt)
        if act == "swiglu":
            a, u = gu[:, :F], gu[:, F:]
        e = visit_ref[i]
        lane = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
        gate = jnp.sum(jnp.where(lane == e, g_ref[...], 0.0), axis=1,
                       keepdims=True)
        if act == "swiglu":
            z = jax.nn.silu(a.astype(f32)).astype(dt) * u * gate.astype(dt)
        else:  # relu2, no gate matrix: the one product, squared
            z = jnp.square(jax.nn.relu(gu.astype(f32))).astype(dt) * gate.astype(dt)
        for k in range(nkd):
            z_scr[k] = z[:, k * tkd:(k + 1) * tkd]

    @pl.when(live & (s >= nku))
    def _down():
        y = jnp.dot(z_scr[s - nku], wd_ref[...].astype(dt),
                    preferred_element_type=f32)
        o_ref[...] += y * sd_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "act"))
def expert_visit(
    x: jnp.ndarray,  # [N, E] — the step's normed rows, every one of them
    gates: jnp.ndarray,  # [N, X] float32 — a row's weight for each held expert
    visit: jnp.ndarray,  # [X] int32 — ``visit_list``
    n: jnp.ndarray,  # scalar int32 — how many of them are touched
    layer: jnp.ndarray,  # scalar int32 — the layer's index into the stacks
    wgu_q: jnp.ndarray,  # [L, X, E, 2F] int8 — fused [gate | up]
    wgu_s: jnp.ndarray,  # [L, X, 1, 2F] float32
    wd_q: jnp.ndarray,  # [L, X, F, E] int8
    wd_s: jnp.ndarray,  # [L, X, 1, E] float32
    *,
    interpret: bool = False,
    act: str = "swiglu",
):
    """sum over the touched experts e of
    ``(swiglu(x @ gateup[l, e]) * gates[:, e]) @ down[l, e]``: [N, E]
    float32. With ``act`` "relu2" the first stack is the up matrices alone,
    TRANSPOSED, ``[L, X, F, E]`` (``supports_pallas``; their scales
    ``[L, X, 1, F]``), and an expert is ``relu(x @ up^T)^2 @ down``."""
    N, E = x.shape
    X, F = wd_q.shape[1], wd_q.shape[2]
    W = wgu_s.shape[3]  # 2F, or F without a gate
    tk, tkd = _tile_rows(E, W), _tile_rows(F, E)
    nku, nkd = E // tk, F // tkd
    x3 = x.reshape(N, nku, tk).transpose(1, 0, 2)  # a K-tile a leading index

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, s, *_: (0,) * len(shape))

    def at(rows, width, tile):  # a block of expert visit[i]'s matrix
        return pl.BlockSpec(
            (None, None, rows, width),
            lambda i, s, visit, n, lyr: (lyr[0], visit[i], tile(s), 0),
        )

    up = at(tk, W, lambda s: jnp.minimum(s, nku - 1))
    if act != "swiglu":  # [F, tk] of the transposed up matrix
        up = pl.BlockSpec(
            (None, None, F, tk),
            lambda i, s, visit, n, lyr: (lyr[0], visit[i], 0, jnp.minimum(s, nku - 1)),
        )
    n1 = jnp.asarray(n, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_visit_kernel, nku=nku, F=F, **(
            {} if act == "swiglu" else {"act": act})),
        out_shape=jax.ShapeDtypeStruct((N, E), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a call with no visit still has its one step, which zeroes the
            # result
            grid=(jnp.maximum(n1[0], 1), nku + nkd),
            in_specs=[
                whole(nku, N, tk),
                whole(N, X),
                up,
                at(1, W, lambda s: 0),
                at(tkd, E, lambda s: jnp.maximum(s - nku, 0)),
                at(1, E, lambda s: 0),
            ],
            out_specs=whole(N, E),
            scratch_shapes=[
                pltpu.VMEM((N, W), jnp.float32),
                pltpu.VMEM((nkd, N, tkd), x.dtype),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        visit.astype(jnp.int32), n1, jnp.asarray(layer, jnp.int32).reshape(1),
        x3, gates.astype(jnp.float32), wgu_q, wgu_s, wd_q, wd_s,
    )
