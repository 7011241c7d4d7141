"""A chunk's grouped-query attention folds the key tiles up to its last row's,
each read from the pool where it lies (PR 44), on the CPU at small sizes.

The plain reference is the fold as it was: a scan over EVERY tile of the slot's
gathered view (`_scan_over_view`, kept here). A tile above the chunk's last row
is masked whole and adds exact zeros (p = 0, alpha = 1), so the bounded fold
equals the same fold run to the table's end element for element; the scan is a
second program, whose body XLA may contract differently, and is held to float32
rounding."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_mellum2 as mellum2  # noqa: E402
import test_nemotron3 as nemotron3  # noqa: E402

from aios_tpu.engine import model  # noqa: E402
from aios_tpu.engine.config import TINY_MOE, TINY_TEST  # noqa: E402
from aios_tpu.engine.engine import TPUEngine  # noqa: E402

# -- the plain reference: the parent's scan over the gathered view -------------------


def _scan_over_view(q, k, v, abs_pos, window, block=512, live_from=None, sink=0,
                    col0=None):
    """`model.blockwise_cache_attention` as it was before PR 44: q [1, Tc, H, D]
    over the whole view k, v [1, C, KH, D], cast to float32 at once, a
    `lax.scan` over all C // block tiles."""
    B, Tc, H, D = q.shape
    C = k.shape[1]
    KH = k.shape[2]
    G = H // KH
    qf = q[0].reshape(Tc, KH, G, D).astype(jnp.float32) / np.sqrt(D)
    nb = C // block
    kb = k[0].astype(jnp.float32).reshape(nb, block, KH, D)
    vb = v[0].astype(jnp.float32).reshape(nb, block, KH, D)
    colsb = jnp.arange(C).reshape(nb, block)
    if col0 is not None:
        colsb = colsb + col0

    def fold(carry, xs):
        m, l, acc = carry
        kblk, vblk, cols = xs
        s = jnp.einsum("tkgd,ckd->kgtc", qf, kblk)
        visible = cols[None, :] <= abs_pos[:, None]
        if window is not None:
            visible = visible & (cols[None, :] > abs_pos[:, None] - window)
        if live_from is not None:
            visible = visible & (
                (cols[None, :] < sink) | (cols[None, :] >= live_from)
            )
        s = jnp.where(visible[None, None], s, jnp.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("kgtc,ckd->kgtd", p, vblk)
        return (m_new, l, acc), None

    init = (
        jnp.full((KH, G, Tc), -1e30, jnp.float32),
        jnp.zeros((KH, G, Tc), jnp.float32),
        jnp.zeros((KH, G, Tc, D), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(fold, init, (kb, vb, colsb))
    out = acc / l[..., None]
    return out.transpose(2, 0, 1, 3).reshape(B, Tc, H, D).astype(q.dtype)


# -- (a) the fold alone ------------------------------------------------------------

C, PAGE, H, D = 4096, 128, 8, 16
W = 1024


def _view(mode, start, Tc):
    """(rows of the slot the view holds: a slice, col0, window, live_from, sink)
    as the callers make them: the whole table; the table of a model with a
    window; a window kind's pages from its window's first block
    (`model._prefill_chunk_kinds`); a slot pruned to sink + window."""
    if mode == "full":
        return slice(0, C), 0, None, None, 0
    if mode == "window_table":
        return slice(0, C), 0, W, None, 0
    if mode == "sink":  # rows [256, live_from) were pruned mid-admission
        return slice(0, C), 0, None, jnp.int32(max(start - 640, 0) // PAGE * PAGE), 256
    nbw = model.window_chunk_blocks(W, Tc, PAGE)
    first = max(start - W + 1, 0) // PAGE
    return slice(first * PAGE, (first + nbw) * PAGE), jnp.int32(first * PAGE), W, None, 0


MODES = ["full", "window_table", "window_kind", "sink"]
# every start x chunk x view at 4 K/V heads; 2 and 8 heads at one start (a case compiles
# three programs: the whole product would be a hundred more of them for nothing new)
CASES = [(start, Tc, mode, 4) for start in (0, PAGE, 512, 1536, "C - Tc")
         for Tc in (128, 256, 512) for mode in MODES]
CASES += [(1536, Tc, mode, kv_heads) for Tc in (128, 256, 512) for mode in MODES
          for kv_heads in (2, 8)]


@pytest.mark.parametrize("start, Tc, mode, kv_heads", CASES)
def test_the_bounded_fold_is_the_whole_fold_and_the_parents_scan(start, Tc, mode, kv_heads):
    start = C - Tc if start == "C - Tc" else start
    rng = np.random.default_rng(start + Tc + kv_heads)
    q = jnp.asarray(rng.standard_normal((1, Tc, H, D)), jnp.float32) * 2.0
    # rows above the chunk's last are whatever the pages hold: never read into a sum
    k_all, v_all = (
        jnp.pad(jnp.asarray(rng.standard_normal((C, kv_heads, D)), jnp.float32),
                ((0, 8 * PAGE), (0, 0), (0, 0)))
        for _ in range(2)
    )
    rows, col0, window, live_from, sink = _view(mode, start, Tc)
    k, v = k_all[rows], v_all[rows]
    n_rows = k.shape[0]
    tile = model._kv_tile(n_rows, PAGE)
    abs_pos = start + jnp.arange(Tc)

    def kv_block(j):
        return (jax.lax.dynamic_slice_in_dim(k, j * tile, tile),
                jax.lax.dynamic_slice_in_dim(v, j * tile, tile))

    @jax.jit
    def fold(n_blocks):
        return model.blockwise_cache_attention(
            q, kv_block, n_blocks, abs_pos, window,
            live_from=live_from, sink=sink, col0=col0)

    seen = model.chunk_kv_tiles(jnp.int32(start), Tc, n_rows, tile, col0)
    assert int(seen) == min((start + Tc - 1 - rows.start) // tile + 1, n_rows // tile)
    bounded, whole = fold(seen), fold(jnp.int32(n_rows // tile))
    assert np.array_equal(np.asarray(bounded), np.asarray(whole))
    scan = _scan_over_view(q, k[None], v[None], abs_pos, window, tile,
                           live_from=live_from, sink=sink, col0=col0)
    np.testing.assert_allclose(np.asarray(bounded), np.asarray(scan), rtol=1e-6, atol=1e-6)
    if mode == "full" and start + Tc < C:  # the bound is not the table's: tiles were skipped
        assert int(seen) < n_rows // tile


# -- (b) the three paged callers against the parent's formulation ------------------

CTX, P, CHUNK = 2048, 128, 256  # a table of four 512-row tiles, chunks of half a tile

PLAIN = TINY_TEST.scaled(name="tiny-plain", max_context=CTX)
MOE = TINY_MOE.scaled(name="tiny-moe", max_context=CTX)
# window 512 + a 256-row chunk in flight: a window layer's view is 6 pages, tiled by the page
KINDS = dataclasses.replace(mellum2.CFG, max_context=CTX, sliding_window=512)
SUBLAYERS = model.ModelConfig(**nemotron3.A.model_fields(
    dict(nemotron3.TINY, max_position_embeddings=CTX), CTX))


def _engine(cfg, weights, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return TPUEngine(cfg, weights, num_slots=2, max_context=CTX, page_size=P,
                     paged_pool_rows=3 * CTX, **kw)


def _weights(name):
    if name == "sublayers":
        return SUBLAYERS, nemotron3.A.build_params(nemotron3.D, nemotron3.SEED), {}
    cfg = {"plain": PLAIN, "int8_pool": MOE, "kinds": KINDS}[name]
    weights = model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, weights, ({"cache_dtype": jnp.int8} if name == "int8_pool" else {})


def _chunk_logits(monkeypatch, cfg, weights, ids, kw, parents):
    """The logits of every chunk of ``ids`` admitted through the engine's own
    driver, and its first token; ``parents``: with the fold as it was, a scan
    over every tile of the view, gathered and cast whole."""
    seen = []
    real = model.prefill_chunk_paged

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), out[0])
        return out

    def gathered_scan(q, kv_block, n_blocks, abs_pos, window, live_from=None, sink=0,
                      col0=0):
        tiles = [kv_block(jnp.int32(j)) for j in range(n_blocks)]  # static: the view's
        k, v = (jnp.concatenate([t[i] for t in tiles])[None] for i in (0, 1))
        return _scan_over_view(q, k, v, abs_pos, window, tiles[0][0].shape[0], live_from,
                               sink, col0)

    with monkeypatch.context() as m:
        m.setattr(model, "prefill_chunk_paged", spy)
        if parents:
            m.setattr(model, "blockwise_cache_attention", gathered_scan)
            m.setattr(model, "chunk_kv_tiles",
                      lambda start, Tc, rows, tile, col0=0, minimum=None: rows // tile)
        eng = _engine(cfg, weights, **kw)
        admission = eng.start_chunked_prefill(0, ids, temperature=0.0, top_p=1.0, chunk=CHUNK)
        while (token := admission.step()) is None:
            pass
        jax.effects_barrier()
    return seen, token, eng


@pytest.mark.parametrize("rows", [2 * CHUNK, 4 * CHUNK + 40], ids=["two_chunks", "five_chunks"])
@pytest.mark.parametrize("name", ["plain", "kinds", "sublayers", "int8_pool"])
def test_a_paged_callers_chunks_are_the_parents(monkeypatch, name, rows):
    cfg, weights, kw = _weights(name)
    ids = mellum2._ids(rows, 7)
    ours, token, eng = _chunk_logits(monkeypatch, cfg, weights, ids, kw, parents=False)
    theirs, their_token, _ = _chunk_logits(monkeypatch, cfg, weights, ids, kw, parents=True)
    assert len(ours) == len(theirs) == -(-rows // CHUNK)
    last = (rows - 1) % CHUNK  # the final bucket's rows above it are padding
    for i, (a, b) in enumerate(zip(ours, theirs)):
        n = CHUNK if i < len(ours) - 1 else last + 1
        np.testing.assert_allclose(a[0, :n], b[0, :n], rtol=2e-5, atol=2e-5)
    assert token == their_token
    stats = eng.stats()
    assert 0 < stats["prefill_kv_tiles_read"] < stats["prefill_kv_tiles_mapped"]


# -- (c) what a chunk's graph holds ------------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("name", ["plain", "kinds", "sublayers", "int8_pool"])
def test_a_chunks_graph_holds_no_view_of_the_table_and_loops_to_a_traced_bound(name):
    """As tests/test_paged_kernel.py holds the decode step's: nothing the chunk's
    graph makes has the slot's whole table of rows (MB x P), and the fold is a
    `while` whose bound is a value of the graph, one an attention layer of the
    scan's body."""
    cfg, weights, kw = _weights(name)
    eng = _engine(cfg, weights, **kw)
    state = eng.state
    scales = (state["k_s"], state["v_s"]) if eng.quant_cache else None

    def chunk(params, k, v, toks, start, row, scales, states):
        return model.prefill_chunk_paged(
            params, cfg, toks, start, k, v, row, cache_scales=scales,
            layout=eng._layout, states=states, slot=jnp.int32(0))

    jaxpr = jax.make_jaxpr(chunk)(
        eng.params, state["k"], state["v"], jnp.zeros((1, CHUNK), jnp.int32),
        jnp.int32(512), jnp.asarray(eng.allocator.tables[0]), scales,
        eng._states_of(state)).jaxpr
    whole_view = [
        f"{e.primitive.name}: {v.aval}" for e in _eqns(jaxpr) for v in e.outvars
        if CTX in getattr(v.aval, "shape", ()) and v.aval.size >= CTX * cfg.kv_dim
    ]
    assert whole_view == []
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1, "the layer scan"
    loops = [e for e in _eqns(scans[0].params["jaxpr"].jaxpr) if e.primitive.name == "while"
             and any(getattr(v.aval, "shape", None) == (cfg.num_kv_heads,
                     cfg.num_heads // cfg.num_kv_heads, CHUNK, cfg.head_dim)
                     for v in e.outvars)]
    per_body = {"plain": 1, "int8_pool": 1, "kinds": len(cfg.period_kinds),
                "sublayers": cfg.period_kinds.count("full")}[name]
    assert len(loops) == per_body
    for loop in loops:  # i < bound, the bound a variable of the loop: not a literal
        (cond,) = [e for e in loop.params["cond_jaxpr"].jaxpr.eqns if e.primitive.name == "lt"]
        assert all(type(v).__name__ != "Literal" for v in cond.invars)


# -- (d) the engagement counters ---------------------------------------------------


@pytest.mark.parametrize("name, read, mapped", [
    # chunks at 0, 256, 512, 768 and a final bucket of 64 at 1,024: their last rows lie
    # in tiles 0, 0, 1, 1, 2 of the table's four, in each of 2 layers
    ("plain", 2 * (1 + 1 + 2 + 2 + 3), 2 * 5 * 4),
    # the 2 full layers as above; the 6 window layers' views are their window and the
    # chunk, not the table: not counted
    ("kinds", 2 * (1 + 1 + 2 + 2 + 3), 2 * 5 * 4),
    # the stack's 2 attention layers alone
    ("sublayers", 2 * (1 + 1 + 2 + 2 + 3), 2 * 5 * 4),
])
def test_the_counters_are_the_hand_count(name, read, mapped):
    cfg, weights, kw = _weights(name)
    eng = _engine(cfg, weights, **kw)
    admission = eng.start_chunked_prefill(
        0, mellum2._ids(4 * CHUNK + 40, 3), temperature=0.0, top_p=1.0, chunk=CHUNK)
    while admission.step() is None:
        pass
    stats = eng.stats()
    assert (stats["prefill_kv_tiles_read"], stats["prefill_kv_tiles_mapped"]) == (read, mapped)


def test_a_latent_stacks_chunks_count_nothing():
    """Latent attention folds in engine/latent.py, whose loop was bounded before."""
    cfg = dataclasses.replace(PLAIN, kv_lora_rank=16, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16)
    assert cfg.mla
    assert model.chunk_tiles_on_host(cfg, 512, 256, 16, 128) == (0, 0)
    assert model.chunk_tiles_on_host(PLAIN, 512, 256, 16, 128) == (2 * 2, 2 * 4)
