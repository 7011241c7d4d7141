"""The routed experts of a PREFILL chunk: each touched expert's weights are
streamed once and run over the rows that picked it (the mathematics and its
caller: engine/moe.py ``moe_ffn_grouped``). The prefill sibling of
ops/expert_visit.py, whose block sizes and width rule it shares.

The caller lays the picks out BY EXPERT, each expert's segment rounded up to
whole ROW_BLOCKs of rows (``segments``). The kernel's unit of work is an
expert's segment, not a tile of rows: ONE grid over (unit, weight block),
where a unit is an expert that got a pick together with up to ``row_cap`` of
its rows (``unit_list``: an expert with more takes further units, each a
second stream of its weights; the cap is what the kernel's VMEM holds of the
rows' float32 sums, 256 rows at Mixtral's 28,672 of them a row and 512
elsewhere, so a second unit takes a router that sends one expert half a
chunk). The grid's first bound is the number of units, a traced value. A
unit's weight blocks are whole-row blocks of that expert's int8 matrices,
``[tk, 2F]`` of the fused ``[gate | up]`` and then ``[tkd, E]`` of ``down``,
each a contiguous run of the stack at ``[l, e]`` named by the block spec's
index map from the scalar-prefetched lists, so the pipeline streams the next
unit's first block under this unit's last product. The rows stay in HBM but
for the unit's own row blocks, which the kernel copies in (the next unit's
under this unit's products) and, once the down product is whole, out again:
nothing is zero-filled, and a row no segment owns is never written and never
read.

A weight block's product runs a PASS of 128 rows at a time in a rolled loop,
trips read from the unit's own block count, and a rest of up to TAIL rows in
a product of its own (a longer rest is one more PASS). A weight tile pushed
through the MXU costs most of what 128 rows cost whatever fewer follow it (an
11.0 MB expert's 672 tiles take 16-18 us at 16 to 128 rows beside 13.4 us of
bytes: PERF.md, PR 37), so
finer trips would pay the expert's weights once a trip, and a product's rows
beyond the segment's blocks hold whatever the buffer held: their sums are
never read. What follows the picks at ROW_BLOCK grain is everything a row
costs: its place in the buffers, its copies in and out, its SwiGLU and its
scaling. The loops over rows and over SwiGLU's column chunks are rolled and a
K-tile is a lane slice of the resident rows (lane offsets are multiples of
128), so the kernel is two seconds of compile and a tenth of a second of
tracing whatever the widths: every graph's warm start pays the trace, and
with 28,672 sums a row the unrolled form was 200 operations and 0.4 s.

Arithmetic, cast for cast, is the XLA loop's over the same layout
(``moe._experts_in_place``): int8 -> the activation dtype, float32
accumulation, x scales, SwiGLU in the activation dtype, float32 down
product x scales. The per-token gate and sum stay with the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .expert_visit import _VMEM_LIMIT, _tile_rows, supports_pallas  # noqa: F401

ROW_BLOCK = 32  # rows an expert's segment is rounded up to
PASS = 128  # rows of one product: the MXU's height
TAIL = 64  # a rest of up to this many rows below a PASS has a product of its own
ROW_CAP_MAX = 512  # rows of one unit at most
_COLS = 2048  # columns of one product: what bounds the kernel's temporaries


def row_cap(E: int, F: int, itemsize: int = 2, act: str = "swiglu") -> int:
    """The most rows of one unit: what half the kernel's VMEM holds of a row's
    float32 ``[gate | up]`` accumulator (``up`` alone for an ungated expert),
    SwiGLU result, float32 down product and two copies of the row itself, in
    whole PASSes, ROW_CAP_MAX at most."""
    a_row = (1 if act == "relu2" else 2) * F * 4 + F * itemsize + E * 4 + 2 * E * itemsize
    return max(min(_VMEM_LIMIT // 2 // a_row, ROW_CAP_MAX) // PASS, 1) * PASS


def segments(counts: jnp.ndarray):
    """(blocks [X], first_row [X]) of ``counts`` [X], the picks that fell on
    each held expert: the ROW_BLOCKs of its segment and the row its segment
    starts at, the segments laid end to end in the experts' order."""
    blocks = (counts + ROW_BLOCK - 1) // ROW_BLOCK
    return blocks, (jnp.cumsum(blocks) - blocks) * ROW_BLOCK


def buffer_rows(picks: int, X: int) -> int:
    """Rows that hold every layout of ``picks`` picks over ``X`` experts:
    the picks and a part-filled ROW_BLOCK an expert, in whole multiples of
    that rounding room. The room is most of the buffer at small token counts
    and many experts, so the graphs of 16 to 256 tokens of a 64-expert model
    get ONE buffer size, and with it one trace of the kernel between them:
    a trace is paid once a distinct shape at every start, 0.9 s on the
    chip's host inside the engine (PERF.md, PR 37); the rows beyond a
    layout are gathered from the zero row and read by nobody."""
    room = X * ROW_BLOCK
    return (-(-picks // room) + 1) * room


def unit_list(blocks: jnp.ndarray, cap: int, picks: int):
    """(expert [U], first_block [U], n_blocks [U], n scalar), all int32: the
    units of ``blocks`` [X] (``segments``) in ascending order, an expert's
    segment cut into runs of ``cap`` rows at most, then the last unit
    repeated. U is static: an expert has one part-filled unit at most."""
    X = blocks.shape[0]
    cb = cap // ROW_BLOCK
    U = X + buffer_rows(picks, X) // cap
    passes = (blocks + cb - 1) // cb
    unit_end = jnp.cumsum(passes)
    n = unit_end[-1]
    u = jnp.clip(jnp.arange(U, dtype=jnp.int32), 0, jnp.maximum(n - 1, 0))
    e = jnp.minimum(
        jnp.sum(u[:, None] >= unit_end[None, :], axis=1, dtype=jnp.int32), X - 1
    )
    p = u - (unit_end - passes)[e]  # which of its expert's units
    first = (jnp.cumsum(blocks) - blocks)[e] + p * cb
    nb = jnp.clip(blocks[e] - p * cb, 0, cb)
    return e, first.astype(jnp.int32), nb.astype(jnp.int32), n.astype(jnp.int32)


def _col_chunk(width: int) -> int:
    """The most columns, a multiple of 128 that divides ``width``, within
    _COLS."""
    best = 128
    for c in range(128, min(width, _COLS) + 1, 128):
        if width % c == 0:
            best = c
    return best


def _group_kernel(exp_ref, blk_ref, nb_ref, n_ref, lyr_ref, x_hbm, wgu_ref,
                  sgu_ref, wd_ref, sd_ref, y_hbm, x_scr, gu_acc, z_scr, y_scr,
                  in_sem, out_sem, *, nku: int, nkd: int, F: int,
                  act: str = "swiglu", width: int = 0):
    i, s = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    dt = x_scr.dtype
    E = y_scr.shape[1]
    W = gu_acc.shape[1]  # 2F, or F without a gate
    tk, tkd = E // nku, F // nkd
    RB = ROW_BLOCK
    last = nku + nkd - 1
    n = n_ref[0]
    live = i < n  # false only in the one step of a call with no unit
    nb = nb_ref[i]
    slot = jax.lax.rem(i, 2)

    def rows_in(u, slot, b):  # row block b of unit u, HBM -> its slot
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds((blk_ref[u] + b) * RB, RB), :],
            x_scr.at[slot, pl.ds(b * RB, RB), :],
            in_sem.at[slot],
        )

    def rows_out(u, b):  # row block b of unit u's result -> HBM
        return pltpu.make_async_copy(
            y_scr.at[pl.ds(b * RB, RB), :],
            y_hbm.at[pl.ds((blk_ref[u] + b) * RB, RB), :],
            out_sem.at[0],
        )

    def each(count, fn, when=True):
        """``fn(b)`` for b below ``count``, where ``when`` holds: a loop of no
        trip is the kernel's one conditional but for the products' rests (a
        ``pl.when`` around a loop costs every graph's start a second trace
        and lowering: 23 constructs were 0.3 s a graph on the chip's host)."""
        def body(b, carry):
            fn(b)
            return carry

        jax.lax.fori_loop(0, jnp.where(when, count, 0), body, 0)

    def block(ref, b):  # row block b of a scratch buffer
        return ref.at[pl.ds(pl.multiple_of(b * RB, RB), RB), :]

    # the unit's rows: the next unit's start under this unit's products (the
    # first unit's own start with them: the two lie end to end in HBM), this
    # unit's are awaited, and its sums start at zero
    first = live & (s == 0)
    nxt = jnp.clip(i + 1, 0, jnp.maximum(n - 1, 0))
    own = jnp.where(i == 0, nb, 0)  # blocks of this unit still to start
    ahead = jnp.where(i + 1 < n, nb_ref[nxt], 0)

    def start(b):
        mine = b < own
        rows_in(jnp.where(mine, i, nxt), jnp.where(mine, slot, 1 - slot),
                jnp.where(mine, b, b - own)).start()

    def arrive(b):
        rows_in(i, slot, b).wait()
        block(gu_acc, b)[...] = jnp.zeros((RB, W), f32)

    each(own + ahead, start, first)
    each(nb, arrive, first)
    # the unit before has its result out of the buffer
    prev = jnp.maximum(i - 1, 0)
    each(nb_ref[prev], lambda b: rows_out(prev, b).wait(),
         live & (s == nku) & (i > 0))

    # a weight block's product: whole PASSes of rows in a rolled loop, one
    # body, then a rest of up to TAIL rows in a product of its own
    cg, cd = _col_chunk(W), _col_chunk(E)
    whole = (nb * RB) // PASS
    rest = nb * RB - whole * PASS
    passes = whole + (rest > TAIL).astype(jnp.int32)

    def cols(c, width):  # a run of columns, lane-aligned
        return pl.ds(pl.multiple_of(c * width, 128), width)

    def gate_up(first, rows):
        at = pl.ds(pl.multiple_of(first, RB), rows)
        x = x_scr[slot, at, cols(s, tk)]
        # unrolled: chunk c + 1's int8 -> bf16 under chunk c's product (rolled,
        # Mixtral's 14 chunks a block read 3.18 ms a layer call against 2.60)
        for c in range(0, W, cg):
            if act == "swiglu":
                gu_acc[at, c:c + cg] += jnp.dot(
                    x, wgu_ref[:, c:c + cg].astype(dt), preferred_element_type=f32
                )
            else:  # the up matrix lies transposed: its columns are rows here
                gu_acc[at, c:c + cg] += jax.lax.dot_general(
                    x, wgu_ref[c:c + cg, :].astype(dt), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32,
                )

    def down(first, rows):
        at = pl.ds(pl.multiple_of(first, RB), rows)
        z = z_scr[at, cols(jnp.maximum(s - nku, 0), tkd)]
        for c in range(0, E, cd):
            y_scr[at, c:c + cd] += jnp.dot(
                z, wd_ref[:, c:c + cd].astype(dt), preferred_element_type=f32
            )

    for product, when in ((gate_up, live & (s < nku)), (down, live & (s >= nku))):
        each(passes, lambda p, product=product: product(p * PASS, PASS), when)

        @pl.when(when & (rest > 0) & (rest <= TAIL))
        def _(product=product):
            product(whole * PASS, TAIL)

    def swiglu(b):
        def chunk(k):
            gate, up = cols(k, tkd), pl.ds(pl.multiple_of(F + k * tkd, 128), tkd)
            acc = block(gu_acc, b)
            a = (acc[:, gate] * sgu_ref[:, gate]).astype(dt)
            if act == "relu2":  # no gate matrix: the one product, squared
                z = jnp.square(jax.nn.relu(a.astype(f32))).astype(dt)
                if width:  # columns beyond the expert's width: blocks' rest
                    col = k * tkd + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
                    z = jnp.where(col < width, z, jnp.zeros_like(z))
                block(z_scr, b)[:, gate] = z
                return
            u = (acc[:, up] * sgu_ref[:, up]).astype(dt)
            block(z_scr, b)[:, gate] = jax.nn.silu(a.astype(f32)).astype(dt) * u

        each(nkd, chunk)
        block(y_scr, b)[...] = jnp.zeros((RB, E), f32)  # the down product's sums

    each(nb, swiglu, live & (s == nku - 1))

    def store(b):
        rows = block(y_scr, b)
        rows[...] = rows[...] * sd_ref[...]
        rows_out(i, b).start()

    each(nb, store, live & (s == last))
    each(nb, lambda b: rows_out(i, b).wait(), live & (s == last) & (i == n - 1))


@functools.partial(jax.jit, static_argnames=("cap", "interpret", "act"))
def expert_group(
    x: jnp.ndarray,  # [M, E] — the picks' rows laid out by expert (``segments``)
    expert: jnp.ndarray,  # [U] int32 — ``unit_list``
    first_block: jnp.ndarray,  # [U] int32
    n_blocks: jnp.ndarray,  # [U] int32
    n: jnp.ndarray,  # scalar int32 — how many units there are
    layer: jnp.ndarray,  # scalar int32 — the layer's index into the stacks
    wgu_q: jnp.ndarray,  # [L, X, E, 2F] int8 — fused [gate | up]
    wgu_s: jnp.ndarray,  # [L, X, 1, 2F] float32
    wd_q: jnp.ndarray,  # [L, X, F, E] int8
    wd_s: jnp.ndarray,  # [L, X, 1, E] float32
    *,
    cap: int,
    interpret: bool = False,
    act: str = "swiglu",
):
    """Row by row ``swiglu(x @ gateup[l, e]) @ down[l, e]`` for the expert
    ``e`` whose segment the row lies in: [M, E] float32, defined on the rows
    of the units' row blocks alone. With ``act`` "relu2" the first stack is
    the up matrices alone, TRANSPOSED, ``[L, X, F, E]``
    (expert_visit.supports_pallas): ``relu(x @ up^T)^2 @ down``."""
    M, E = x.shape
    F = wd_q.shape[2]
    W = wgu_s.shape[3]  # 2F, or F without a gate
    extra = {} if act == "swiglu" else {"act": act}
    if F % 128:
        # an ungated expert's width in whole int8 tiles of 32 rows only
        # (expert_visit.supports_pallas): a scratch row that is not whole
        # lane tiles cannot be sliced at a traced row, so the kernel runs at
        # the width rounded UP to lane tiles: the weight blocks overhang
        # their stacks (what lies beyond is never defined and always finite
        # int8), and the activation is zeroed beyond the true width, so the
        # overhanging rows of ``down`` multiply zeros
        extra["width"] = F
        F = W = -(-F // 128) * 128
    tk, tkd = _tile_rows(E, W), _tile_rows(F, E)
    nku, nkd = E // tk, F // tkd

    def at(rows, width, tile):  # a block of unit i's expert's matrix
        return pl.BlockSpec(
            (None, None, rows, width),
            lambda i, s, exp, blk, nb, n, lyr: (lyr[0], exp[i], tile(s), 0),
        )

    up = at(tk, W, lambda s: jnp.minimum(s, nku - 1))
    if act != "swiglu":  # [F, tk] of the transposed up matrix
        up = pl.BlockSpec(
            (None, None, W, tk),
            lambda i, s, exp, blk, nb, n, lyr: (
                lyr[0], exp[i], 0, jnp.minimum(s, nku - 1)),
        )
    n1 = jnp.asarray(n, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_group_kernel, nku=nku, nkd=nkd, F=F, **extra),
        out_shape=jax.ShapeDtypeStruct((M, E), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # a call with no unit still has its one step, which does nothing
            grid=(jnp.maximum(n1[0], 1), nku + nkd),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                up,
                at(1, W, lambda s: 0),
                at(tkd, E, lambda s: jnp.maximum(s - nku, 0)),
                at(1, E, lambda s: 0),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, cap, E), x.dtype),
                pltpu.VMEM((cap, W), jnp.float32),
                pltpu.VMEM((cap, F), x.dtype),
                pltpu.VMEM((cap, E), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        expert.astype(jnp.int32), first_block.astype(jnp.int32),
        n_blocks.astype(jnp.int32), n1,
        jnp.asarray(layer, jnp.int32).reshape(1),
        x, wgu_q, wgu_s, wd_q, wd_s,
    )
