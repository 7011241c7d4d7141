"""Paged KV cache: allocator, paged attention parity, engine equivalence.

The paged cache must be OBSERVABLY identical to the dense slot cache —
same tokens, same masks — while reserving HBM per page in use instead of
per num_slots x max_context (SURVEY.md section 7.2, hard part no. 1's
fixed-shape half). Kernel parity runs under the Pallas interpreter on CPU,
in tests/test_paged_kernel.py (tier 1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.engine.paged import PageAllocator, PoolExhausted

# compile-heavy tier: excluded from the fast commit gate (pytest -m fast)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def params():
    return model.init_params(TINY_TEST, jax.random.PRNGKey(1), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_ensure_and_free():
    a = PageAllocator(num_pages=9, page_size=16, num_slots=2, max_blocks=8)
    assert a.free_pages == 8  # page 0 is sacrificial
    assert a.ensure(0, 17) is True  # 2 blocks
    assert a.ensure(0, 17) is False  # idempotent
    assert a.pages_in_use() == 2
    assert a.slot_rows_backed(0) == 32
    assert (a.tables[0, :2] > 0).all()
    assert (a.tables[0, 2:] == 0).all()
    a.free_slot(0)
    assert a.pages_in_use() == 0
    assert (a.tables[0] == 0).all()


def test_allocator_exhaustion_keeps_state():
    a = PageAllocator(num_pages=4, page_size=16, num_slots=2, max_blocks=8)
    a.ensure(0, 32)  # 2 of 3 free pages
    with pytest.raises(PoolExhausted):
        a.ensure(1, 33)  # needs 3, only 1 free
    assert a.free_pages == 1
    assert a.slot_rows_backed(1) == 0
    a.free_slot(0)
    assert a.ensure(1, 33) is True  # now it fits


def test_allocator_pages_are_exclusive():
    a = PageAllocator(num_pages=9, page_size=16, num_slots=4, max_blocks=2)
    for s in range(4):
        a.ensure(s, 32)
    pages = a.tables[:, :2].ravel().tolist()
    assert len(set(pages)) == 8  # no page handed to two slots
    assert 0 not in pages


# ---------------------------------------------------------------------------
# paged attention parity: tests/test_paged_kernel.py (tier 1) — the kernel
# and the reference on a stacked three-layer pool, every mask, both dtypes
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# engine equivalence
# ---------------------------------------------------------------------------


def make_dense(params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_context", 256)
    kw.setdefault("cache_dtype", jnp.float32)
    return TPUEngine(TINY_TEST, params, **kw)


def make_paged(params, pool_rows=4 * 256, page_size=32, **kw):
    return make_dense(
        params, paged_pool_rows=pool_rows, page_size=page_size, **kw
    )


def test_paged_generate_matches_dense(params):
    prompt = [1, 2, 3, 4, 5]
    dense = make_dense(params)
    ref = dense.generate(prompt, max_new_tokens=48, temperature=0.0)
    dense.close()
    pg = make_paged(params)
    got = pg.generate(prompt, max_new_tokens=48, temperature=0.0)
    pg.close()
    assert got == ref


def test_paged_batched_slots_match_dense(params):
    prompts = {0: [1, 2, 3], 1: list(range(7, 47)), 3: [9, 8, 7, 6]}
    outs = {}
    for paged in (False, True):
        eng = make_paged(params) if paged else make_dense(params)
        for s, p in prompts.items():
            eng.prefill(s, p, temperature=0.0)
        toks = eng.step(12)  # [12, S]
        outs[paged] = {s: toks[:, s].tolist() for s in prompts}
        eng.close()
    assert outs[True] == outs[False]


def test_paged_oversubscription_and_reuse(params):
    """Logical capacity (4 slots x 256) is 4x the physical pool; short
    requests run fine and released pages recycle."""
    eng = make_paged(params, pool_rows=256, page_size=32)
    for round_ in range(3):
        for s in range(4):
            eng.prefill(s, [1 + s, 2, 3], temperature=0.0)
        eng.step(4)
        for s in range(4):
            eng.release(s)
        assert eng.allocator.pages_in_use() == 0
    eng.close()


def test_paged_chunked_prefill_matches_monolithic(params):
    """Chunk-admitting a prompt through the page tables must land exactly
    where a monolithic paged prefill does — same first token, same
    follow-on decode."""
    prompt = [int(t) for t in np.random.default_rng(5).integers(1, 500, 150)]
    eng = make_paged(params)
    first_mono = eng.prefill(0, prompt, temperature=0.0)
    mono = [first_mono] + eng.step(8)[:, 0].tolist()
    eng.close()

    eng = make_paged(params)
    pc = eng.start_chunked_prefill(0, prompt, temperature=0.0, chunk=64)
    first = None
    while first is None:
        first = pc.step()
    got = [first] + eng.step(8)[:, 0].tolist()
    eng.close()
    assert got == mono


def test_paged_chunked_prefill_interleaved_decode(params):
    """A paged chunk admission with decode dispatches interleaved must
    match the dense engine's chunked admission output for both slots."""
    long_prompt = [int(t) for t in np.random.default_rng(6).integers(1, 500, 150)]
    prompts = [[1, 2, 3], long_prompt]
    outs = {}
    for paged in (False, True):
        eng = make_paged(params) if paged else make_dense(params)
        b = ContinuousBatcher(eng, prefill_chunk=64)
        hs = [
            b.submit(Request(prompt_ids=p, max_tokens=24, temperature=0.0))
            for p in prompts
        ]
        outs[paged] = [h.tokens() for h in hs]
        b.shutdown()
        assert b.last_error is None
        eng.close()
    assert outs[True] == outs[False]


def test_paged_chunked_admission_exhaustion_survives(params):
    """Mid-admission pool exhaustion must never kill the scheduler: either
    a victim is evicted or the admission itself fails cleanly."""
    eng = make_paged(params, pool_rows=128, page_size=32, num_slots=2,
                     prefix_cache=False)  # isolate the eviction policy
    b = ContinuousBatcher(eng, prefill_chunk=64)
    small = b.submit(Request(prompt_ids=[1, 2, 3], max_tokens=60,
                             temperature=0.0))
    # feasible alone (4 pages) but not alongside the decoding request
    big = b.submit(Request(prompt_ids=[2] * 120, max_tokens=8,
                           temperature=0.0))
    small_out = small.tokens()
    big_out = big.tokens()
    b.shutdown()
    assert b.last_error is None
    assert len(small_out) > 0
    # whichever resolution happened (admission failed, or admitted and
    # later evicted when decode needed one page more than the pool), every
    # stream terminated and all pages recycled
    assert eng.allocator.pages_in_use() == 0
    assert len(big_out) <= 8
    eng.close()


def test_paged_pool_exhaustion_raises(params):
    eng = make_paged(params, pool_rows=64, page_size=32)  # 2 usable pages
    eng.prefill(0, [1] * 30, temperature=0.0)  # 1 page
    eng.prefill(1, [2] * 30, temperature=0.0)  # 1 page
    with pytest.raises(PoolExhausted):
        eng.step(8)  # slot 0 needs rows 30..37 -> a third page
    eng.close()


def test_batcher_evicts_longest_on_exhaustion(params):
    eng = make_paged(params, pool_rows=96, page_size=32, num_slots=3)
    b = ContinuousBatcher(eng)
    hs = [
        b.submit(Request(prompt_ids=[s + 1, 2, 3], max_tokens=80,
                         temperature=0.0))
        for s in range(3)
    ]
    outs = [h.tokens() for h in hs]
    b.shutdown()
    assert b.last_error is None
    assert b.pool_evictions >= 1  # someone was retired early
    assert all(len(o) > 0 for o in outs)
    assert any(len(o) == 80 for o in outs)  # and someone ran to completion
    assert eng.allocator.pages_in_use() == 0
    eng.close()


# ---------------------------------------------------------------------------
# sliding-window page trimming
# ---------------------------------------------------------------------------


def test_windowed_paged_trims_dead_pages(params):
    """On sliding-window models, pages wholly below the window free back
    to the pool mid-generation — physical usage stays bounded by the
    window while the logical length keeps growing; output matches dense."""
    cfg = TINY_TEST.scaled(sliding_window=16)
    wparams = model.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    dense = TPUEngine(cfg, wparams, num_slots=2, max_context=128,
                      cache_dtype=jnp.float32)
    dense.prefill(0, [1, 2, 3], temperature=0.0)
    ref = [int(t) for t in dense.step(96)[:, 0]]
    dense.close()

    eng = TPUEngine(cfg, wparams, num_slots=2, max_context=128,
                    cache_dtype=jnp.float32, paged_pool_rows=256, page_size=8)
    eng.prefill(0, [1, 2, 3], temperature=0.0)
    got = []
    peak = 0
    for _ in range(12):
        got.extend(int(t) for t in eng.step(8)[:, 0])
        peak = max(peak, eng.allocator.pages_in_use())
    assert got == ref
    # window 16 rows = 2 pages + in-flight block + growth headroom; far
    # below the ~13 pages a 99-row untrimmed slot would hold
    assert peak <= 6, peak
    eng.close()
    assert len(got) == 96


def test_windowed_chunked_admission_fits_small_pool(params):
    """A windowed prompt LARGER than the physical pool chunk-admits fine:
    blocks the remaining chunks can't attend to free as admission
    advances, so residency is bounded by the window, not the prompt."""
    cfg = TINY_TEST.scaled(sliding_window=16)
    wparams = model.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    prompt = [int(t) for t in np.random.default_rng(15).integers(1, 500, 150)]
    dense = TPUEngine(cfg, wparams, num_slots=2, max_context=256,
                      cache_dtype=jnp.float32)
    pc = dense.start_chunked_prefill(0, prompt, temperature=0.0, chunk=16)
    first = None
    while first is None:
        first = pc.step()
    ref = [first] + [int(t) for t in dense.step(8)[:, 0]]
    dense.close()

    eng = TPUEngine(cfg, wparams, num_slots=2, max_context=256,
                    cache_dtype=jnp.float32, paged_pool_rows=80, page_size=8)
    pc = eng.start_chunked_prefill(0, prompt, temperature=0.0, chunk=16)
    first = None
    while first is None:
        first = pc.step()  # 150 rows through a 80-row pool
    got = [first] + [int(t) for t in eng.step(8)[:, 0]]
    assert eng.allocator.pages_in_use() <= 10
    eng.release(0)
    assert got == ref

    # the batcher's feasibility fast-fail must account for the trimming
    # too: the same pool-exceeding prompt admits through the scheduler
    b = ContinuousBatcher(eng, prefill_chunk=16)
    out = b.generate(prompt, max_tokens=6, temperature=0.0)
    b.shutdown()
    assert b.last_error is None
    assert out == ref[:6]
    eng.close()


# ---------------------------------------------------------------------------
# int8 pool
# ---------------------------------------------------------------------------


def test_paged_int8_pool_matches_dense_int8(params):
    """int8 paged pool must decode exactly like the dense int8 KV cache —
    same quantizer on write, same dequantized values on read."""
    prompt = [3, 17, 91, 4, 55, 8]
    dense = make_dense(params, cache_dtype=jnp.int8)
    ref = dense.generate(prompt, max_new_tokens=24, temperature=0.0)
    dense.close()
    eng = make_paged(params, cache_dtype=jnp.int8)
    got = eng.generate(prompt, max_new_tokens=24, temperature=0.0)
    eng.close()
    assert got == ref


def test_paged_int8_chunked_and_prefix(params):
    """Chunk admission and prefix reuse both run over the int8 pool."""
    prompt = [int(t) for t in np.random.default_rng(14).integers(1, 500, 150)]
    dense = make_dense(params, cache_dtype=jnp.int8)
    ref = dense.generate(prompt, max_new_tokens=16, temperature=0.0)
    dense.close()
    eng = make_paged(params, cache_dtype=jnp.int8)
    pc = eng.start_chunked_prefill(0, prompt, temperature=0.0, chunk=64)
    first = None
    while first is None:
        first = pc.step()
    got = [first] + [int(t) for t in eng.step(15)[:, 0]]
    eng.release(0)
    hit = eng.generate(prompt, max_new_tokens=16, temperature=0.0)
    assert eng.prefix_rows_reused > 0
    eng.close()
    assert got == ref
    assert hit == ref


def test_paged_int8_speculative(params):
    prompt = [1, 2, 3]
    dense = make_dense(params, cache_dtype=jnp.int8)
    ref = dense.generate(prompt, max_new_tokens=48, temperature=0.0)
    dense.close()
    eng = make_paged(params, cache_dtype=jnp.int8)
    got = eng.generate(
        prompt, max_new_tokens=48, temperature=0.0, speculative=True
    )
    eng.close()
    assert got == ref


# ---------------------------------------------------------------------------
# speculative decoding over the paged cache
# ---------------------------------------------------------------------------


def test_paged_spec_generate_matches_dense_and_plain(params):
    prompt = [1, 2, 3]
    dense = make_dense(params)
    ref = dense.generate(prompt, max_new_tokens=64, temperature=0.0)
    dense.close()
    eng = make_paged(params)
    got = eng.generate(
        prompt, max_new_tokens=64, temperature=0.0, speculative=True
    )
    rounds = eng.decode_steps
    eng.close()
    assert got == ref
    assert rounds < len(ref) - 1  # drafts actually accepted


def test_paged_spec_backs_pages_for_accepted_runs(params):
    """Full-draft acceptance grows lengths by K+1 per round — the worst
    case must be page-backed up front so the scan can't write unbacked
    rows."""
    eng = make_paged(params, pool_rows=4 * 256, page_size=32)
    eng.prefill(0, [5, 6, 5, 6, 5, 6, 5, 6], temperature=0.0)
    for _ in range(6):
        eng.spec_step(4, draft_len=7)
    backed = eng.allocator.slot_rows_backed(0)
    assert backed >= eng.slot_length(0) + 1
    eng.close()


def test_paged_spec_batcher_evicts_on_exhaustion(params):
    """Speculative dispatches hit the same eviction policy as plain steps
    when the worst-case growth can't be page-backed."""
    eng = make_paged(params, pool_rows=96, page_size=32, num_slots=3,
                     prefix_cache=False)
    b = ContinuousBatcher(eng, speculative=True)
    hs = [
        b.submit(Request(prompt_ids=[s + 1, 2, 3], max_tokens=80,
                         temperature=0.0))
        for s in range(3)
    ]
    outs = [h.tokens() for h in hs]
    b.shutdown()
    assert b.last_error is None  # exhaustion evicted, never aborted
    assert b.pool_evictions >= 1
    assert all(len(o) > 0 for o in outs)
    assert eng.allocator.pages_in_use() == 0
    eng.close()


def test_paged_prefix_plus_spec_agent_fast_path(params):
    """The full agent fast path: resubmitted preamble maps cached pages,
    then speculative rounds decode — output identical to the dense plain
    engine."""
    prompt = [int(t) for t in np.random.default_rng(13).integers(1, 500, 100)]
    dense = make_dense(params)
    ref = dense.generate(prompt, max_new_tokens=32, temperature=0.0)
    dense.close()
    eng = make_paged(params)
    eng.generate(prompt, max_new_tokens=4, temperature=0.0)  # registers
    got = eng.generate(
        prompt, max_new_tokens=32, temperature=0.0, speculative=True
    )
    assert eng.prefix_rows_reused > 0
    eng.close()
    assert got == ref


# ---------------------------------------------------------------------------
# prefix caching
# ---------------------------------------------------------------------------


def test_prefix_hit_reuses_pages_and_matches_cold(params):
    """Resubmitting a prompt must map its cached prefix pages instead of
    recomputing them — and decode exactly the same tokens as a cold run."""
    prompt = [int(t) for t in np.random.default_rng(7).integers(1, 500, 100)]
    cold = make_paged(params)  # page_size 32: 100 tokens -> 3 full blocks
    ref = cold.generate(prompt, max_new_tokens=24, temperature=0.0)
    cold.close()

    eng = make_paged(params)
    first = eng.generate(prompt, max_new_tokens=24, temperature=0.0)
    assert eng.prefix_rows_reused == 0  # cold: nothing to match
    again = eng.generate(prompt, max_new_tokens=24, temperature=0.0)
    assert eng.prefix_rows_reused == 96  # 3 x 32-row blocks mapped, not computed
    assert eng.prefix_index.hits == 1
    eng.close()
    assert first == ref
    assert again == ref


def test_prefix_divergent_tails_share_only_common_blocks(params):
    base = [int(t) for t in np.random.default_rng(8).integers(1, 500, 64)]
    a, btail = base + [7, 8, 9], base + [11, 12, 13]
    dense = make_dense(params)
    ref_a = dense.generate(a, max_new_tokens=16, temperature=0.0)
    ref_b = dense.generate(btail, max_new_tokens=16, temperature=0.0)
    dense.close()

    eng = make_paged(params)
    got_a = eng.generate(a, max_new_tokens=16, temperature=0.0)
    got_b = eng.generate(btail, max_new_tokens=16, temperature=0.0)
    assert eng.prefix_rows_reused == 64  # the 2 shared base blocks
    eng.close()
    assert (got_a, got_b) == (ref_a, ref_b)


def test_prefix_shared_pages_survive_owner_release(params):
    """Slot A releases while slot B still maps the shared prefix — B's
    decode must stay correct and the pages must not be recycled."""
    prompt = [int(t) for t in np.random.default_rng(9).integers(1, 500, 80)]
    dense = make_dense(params)
    dense.prefill(1, prompt, temperature=0.0)
    ref = dense.step(12)[:, 1].tolist()
    dense.close()

    eng = make_paged(params)
    eng.prefill(0, prompt, temperature=0.0)  # registers blocks
    eng.prefill(1, prompt, temperature=0.0)  # shares them
    assert eng.prefix_rows_reused > 0
    eng.release(0)  # owner goes away; index + slot 1 still hold refs
    got = eng.step(12)[:, 1].tolist()
    eng.close()
    assert got == ref


def test_prefix_hit_tail_overrun_is_safe(params):
    """A prefix match de-aligns the tail's chunk starts, so the final
    bucket's padding can overrun max_context (start=32 + bucket=512 > 512
    here): the padded table slice must route overflow rows to the
    sacrificial page instead of clamping a block early — output must match
    the dense engine exactly."""
    rng = np.random.default_rng(12)
    base = [int(t) for t in rng.integers(1, 500, 40)]
    y = base[:32] + [int(t) for t in rng.integers(1, 500, 479)]  # len 511
    dense = make_dense(params, max_context=512)
    ref = dense.generate(y, max_new_tokens=8, temperature=0.0)
    dense.close()

    eng = make_paged(params, pool_rows=1024, page_size=32, max_context=512)
    eng.generate(base, max_new_tokens=4, temperature=0.0)  # registers block 0
    got = eng.generate(y, max_new_tokens=8, temperature=0.0)
    assert eng.prefix_rows_reused == 32  # the de-aligning 1-block match
    eng.close()
    assert got == ref


def test_prefix_index_reclaims_under_pressure(params):
    """Cold index pages are reclaimed instead of raising PoolExhausted."""
    eng = make_paged(params, pool_rows=256, page_size=32, num_slots=2)
    # fill the index: 3 distinct prompts x 2+ full blocks each
    rng = np.random.default_rng(10)
    for i in range(3):
        p = [int(t) for t in rng.integers(1, 500, 70)]
        eng.prefill(0, p, temperature=0.0)
        eng.release(0)
    assert eng.allocator.free_pages < 8  # index is holding pages
    # a fresh prompt needing more pages than the free list has
    big = [int(t) for t in rng.integers(1, 500, 200)]
    first = eng.prefill(0, big, temperature=0.0)  # must NOT raise
    assert 0 <= first < TINY_TEST.vocab_size
    eng.close()


def test_prefix_chunked_admission_hit(params):
    """A long prompt resubmitted through chunked admission maps its prefix
    and produces the dense engine's exact output."""
    prompt = [int(t) for t in np.random.default_rng(11).integers(1, 500, 180)]
    outs = {}
    for paged in (False, True):
        eng = make_paged(params) if paged else make_dense(params)
        b = ContinuousBatcher(eng, prefill_chunk=64)
        o1 = b.generate(prompt, max_tokens=12, temperature=0.0)
        o2 = b.generate(prompt, max_tokens=12, temperature=0.0)
        outs[paged] = (o1, o2)
        if paged:
            assert eng.prefix_rows_reused > 0
        b.shutdown()
        eng.close()
    assert outs[True] == outs[False]


def test_warmup_leaves_prefix_index_empty(params):
    eng = make_paged(params, pool_rows=1024, page_size=32)
    eng.warmup(step_sizes=(1,))
    assert len(eng.prefix_index.snapshot()) == 0
    assert eng.allocator.pages_in_use() == 0
    out1 = eng.generate([1, 2, 3], max_new_tokens=8, temperature=0.0)
    assert len(out1) == 8
    eng.close()


def test_batcher_fails_only_oversized_prompt(params):
    eng = make_paged(params, pool_rows=64, page_size=32, num_slots=2)
    b = ContinuousBatcher(eng)
    big = b.submit(Request(prompt_ids=[1] * 120, max_tokens=4,
                           temperature=0.0))  # needs 4 pages, pool has 2
    small = b.submit(Request(prompt_ids=[1, 2, 3], max_tokens=8,
                             temperature=0.0))
    big_out = big.tokens()
    small_out = small.tokens()
    b.shutdown()
    assert b.last_error is None
    assert big_out == []  # failed cleanly, iterator ended
    assert len(small_out) == 8  # unaffected
    eng.close()


# ---------------------------------------------------------------------------
# paged pool under tensor parallelism (dp=sp=1)
# ---------------------------------------------------------------------------


def test_paged_pool_composes_with_tp(params, cpu_devices):
    """Pages shard kv heads over tp; outputs bit-match single-chip paged,
    prefix caching still hits, and the int8 pool rides along."""
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    plan = ShardingPlan(build_mesh(2, dp=1, tp=2))
    kw = dict(num_slots=4, max_context=256, cache_dtype=jnp.float32,
              paged_pool_rows=4 * 256, page_size=32)
    ref = TPUEngine(TINY_TEST, params, **kw)
    tp = TPUEngine(TINY_TEST, params, shardings=plan, **kw)
    try:
        assert str(tp.state["k"].sharding.spec).find("'tp'") != -1
        prompt = [1, 2, 3, 4, 5] * 3
        assert tp.generate(prompt, max_new_tokens=24, temperature=0.0) == \
            ref.generate(prompt, max_new_tokens=24, temperature=0.0)
        pre = list(range(1, 70))
        tp.prefill(0, pre + [7], temperature=0.0)
        tp.release(0)
        before = tp.prefix_rows_reused
        tp.prefill(1, pre + [9], temperature=0.0)
        assert tp.prefix_rows_reused > before  # prefix hit under TP
    finally:
        tp.close()
        ref.close()


def test_paged_pool_int8_under_tp(params, cpu_devices):
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    plan = ShardingPlan(build_mesh(2, dp=1, tp=2))
    kw = dict(num_slots=2, max_context=128, cache_dtype=jnp.int8,
              paged_pool_rows=256, page_size=32)
    ref = TPUEngine(TINY_TEST, params, **kw)
    tp = TPUEngine(TINY_TEST, params, shardings=plan, **kw)
    try:
        assert tp.generate([1, 2, 3, 4], max_new_tokens=12,
                           temperature=0.0) == \
            ref.generate([1, 2, 3, 4], max_new_tokens=12, temperature=0.0)
    finally:
        tp.close()
        ref.close()


def test_paged_pool_composes_with_sp_mesh(params, cpu_devices):
    """An sp>1 MESH no longer disables paging: the pool's shard_map specs
    name only dp/tp, so it replicates over the sp axis and decode matches
    the sp-free paged engine. (A context that must SHARD over sp uses
    seq_sharded_cache instead — the model manager's HBM-budget check
    picks per model; see test_runtime_service.py.)"""
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    plan = ShardingPlan(build_mesh(4, sp=2, tp=2))
    eng = TPUEngine(TINY_TEST, params, num_slots=4, max_context=256,
                    cache_dtype=jnp.float32, paged_pool_rows=256,
                    page_size=32, shardings=plan)
    ref_plan = ShardingPlan(build_mesh(2, tp=2))
    ref = TPUEngine(TINY_TEST, params, num_slots=4, max_context=256,
                    cache_dtype=jnp.float32, paged_pool_rows=256,
                    page_size=32, shardings=ref_plan)
    for e in (eng, ref):
        e.prefill(0, [1, 2, 3, 4], temperature=0.0)
    got = eng.step(2)
    want = ref.step(2)
    assert got.tolist() == want.tolist(), (
        "paged decode over an sp mesh diverged from the sp-free pool"
    )

    # seq-sharded + paged on the SAME engine stays impossible (pages hold
    # contiguous rows of one slot and cannot split across sp shards)
    with pytest.raises(ValueError, match="exclusive"):
        TPUEngine(TINY_TEST, params, num_slots=4, max_context=256,
                  cache_dtype=jnp.float32, paged_pool_rows=256,
                  page_size=32, shardings=plan, seq_sharded_cache=True)


# ---------------------------------------------------------------------------
# int8 page pool through the paged kernel (interpret mode)
# ---------------------------------------------------------------------------


def test_paged_decode_step_int8_kernel_wiring(monkeypatch):
    """AIOS_TPU_INT8_RAGGED=1 routes the int8 POOL decode through the
    paged kernel (reference body stands in on CPU); outputs match the
    gather-dequant XLA path."""
    import aios_tpu.ops as ops_mod

    cfg = TINY_TEST
    params = model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, N, P, MB = 2, 9, 16, 4
    toks = jnp.asarray([1, 2], jnp.int32)
    lens = jnp.asarray([5, 11], jnp.int32)
    k = jnp.zeros((cfg.num_layers, N, P, cfg.num_kv_heads * cfg.head_dim),
                  jnp.int8)
    v = jnp.zeros_like(k)
    scales = (
        jnp.ones((cfg.num_layers, N, P, cfg.num_kv_heads), jnp.float32),
        jnp.ones((cfg.num_layers, N, P, cfg.num_kv_heads), jnp.float32),
    )
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    ref = model.decode_step_paged(
        params, cfg, toks, lens, k, v, tables, kernels=False,
        cache_scales=scales,
    )[0]

    called = {}

    def fake_kernel(q, k_pool, v_pool, k_s, v_s, layer, tbl, lengths,
                    window=None, win_starts=None, sink=None):
        called["hit"] = True
        assert k_pool.shape == k.shape and k_s.shape == scales[0].shape
        return ops_mod.paged_decode_attention_int8_reference(
            q, k_pool, v_pool, k_s, v_s, layer, tbl, lengths, window=window
        )

    monkeypatch.setenv("AIOS_TPU_INT8_RAGGED", "1")
    monkeypatch.setattr(
        ops_mod, "paged_decode_attention_int8", fake_kernel
    )
    got = model.decode_step_paged(
        params, cfg, toks, lens, k, v, tables, kernels=True,
        cache_scales=scales,
    )[0]
    assert called.get("hit")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_paged_cancel_eviction_prefix_soak(params):
    """Randomized soak over the riskiest composition: shared-prefix pages
    (refcounted), pool-exhaustion eviction, and request CANCELLATION all
    interleaving on one paged engine. Invariant at quiesce: page
    accounting balances exactly — every page is free or pinned by the
    prefix index; nothing leaks, nothing double-frees. The pool is sized
    to GUARANTEE exhaustion (asserted below), so the eviction path really
    interleaves with the cancel reaping."""
    import random
    import threading
    import time

    rng = random.Random(7)
    engine = TPUEngine(
        TINY_TEST, params, num_slots=4, max_context=256,
        cache_dtype=jnp.float32, paged_pool_rows=256, page_size=32,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=1)
    preamble = [7] * 64  # two full pages shared across most requests
    handles = []
    try:
        for i in range(24):
            prompt = (preamble if i % 3 else [5, i + 1]) + [
                rng.randrange(1, 250) for _ in range(rng.randrange(1, 30))
            ]
            handles.append(b.submit(Request(
                prompt_ids=prompt, max_tokens=rng.randrange(40, 150),
                temperature=0.0,
            )))
            if i % 2:
                victim = rng.choice(handles)
                victim.cancel()  # may be queued, live, or already done
            time.sleep(rng.random() * 0.02)
        drainers = [threading.Thread(target=h.tokens, daemon=True)
                    for h in handles]
        for t in drainers:
            t.start()
        end = time.time() + 120  # shared deadline, not 120 s per thread
        for t in drainers:
            t.join(timeout=max(0.1, end - time.time()))
        assert all(not t.is_alive() for t in drainers), "stranded consumer"
        assert b.active_count == 0 and b.queue_depth() == 0
        # the composition actually happened: evictions AND cancellations
        assert b.pool_evictions > 0, "pool never exhausted; soak is vacuous"
        assert b.cancellations > 0
        alloc = engine.allocator
        # quiesced accounting: usable pages (total minus the sacrificial
        # page) = free pages + pages pinned by the prefix index
        pinned = len(set(engine.prefix_index.snapshot().values()))
        usable = alloc.num_pages - alloc.replicas
        assert alloc.free_pages + pinned == usable, (
            alloc.free_pages, pinned, usable,
        )
        # no slot holds rows after quiesce
        for s in range(engine.num_slots):
            assert alloc.slot_rows_backed(s) == 0
    finally:
        b.shutdown()
        engine.close()


def test_eviction_prefers_low_priority_victims(params):
    """Pool-exhaustion eviction retires the LOWEST-priority live request
    (longest within a level) — a strategic stream survives while a longer
    operational one is sacrificed."""
    import time

    engine = TPUEngine(
        TINY_TEST, params, num_slots=3, max_context=256,
        cache_dtype=jnp.float32, paged_pool_rows=160, page_size=16,
        prefix_cache=False,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=2)
    try:
        # 9 usable pages (1 sacrificial); one low and one high stream
        # both growing until the pool exhausts
        low1 = b.submit(Request(prompt_ids=[1] * 40, max_tokens=500,
                                temperature=0.0, priority=0))
        high = b.submit(Request(prompt_ids=[2] * 40, max_tokens=500,
                                temperature=0.0, priority=3))
        deadline = time.time() + 30
        while b.active_count < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert b.active_count == 2
        # both grow until the pool exhausts; eviction must hit the
        # priority-0 stream even when lengths are close
        deadline = time.time() + 60
        while b.pool_evictions < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert b.pool_evictions >= 1
        low_toks = low1.tokens()
        high_toks = high.tokens()
        # the low-priority stream was cut short; the high one ran longer
        assert len(high_toks) > len(low_toks), (len(high_toks), len(low_toks))
    finally:
        b.shutdown()
        engine.close()


def test_low_priority_admission_waits_instead_of_evicting_high(params):
    """A low-priority admission must NOT evict strictly higher-priority
    live streams; it waits queued and admits once they drain."""
    import time

    engine = TPUEngine(
        TINY_TEST, params, num_slots=2, max_context=256,
        cache_dtype=jnp.float32, paged_pool_rows=256, page_size=16,
        prefix_cache=False,
    )
    b = ContinuousBatcher(engine, chunk_steps=2, admit_chunk_steps=2)
    try:
        # 15 usable pages; each high peaks at 7 pages (40-row prompt + 60
        # tokens), so the two FIT together and never self-evict — only
        # the low admission conflicts
        highs = [b.submit(Request(prompt_ids=[2 + i] * 40, max_tokens=60,
                                  temperature=0.0, priority=3))
                 for i in range(2)]
        deadline = time.time() + 60
        while engine.allocator.pages_in_use() < 12 and time.time() < deadline:
            time.sleep(0.02)  # highs near peak: <= 3 pages free
        assert engine.allocator.pages_in_use() >= 12
        # 64-row prompt needs 4 pages > free margin -> PoolExhausted, and
        # the only victims outrank the requester -> admission must WAIT
        low = b.submit(Request(prompt_ids=[1] * 64, max_tokens=4,
                               temperature=0.0, priority=0))
        high_toks = [h.tokens() for h in highs]
        # the high streams ran their FULL budgets — never evicted to make
        # room for the low request
        assert all(len(t) == 60 for t in high_toks), [len(t) for t in high_toks]
        low_toks = low.tokens()  # admits after the highs drain
        assert len(low_toks) == 4
        assert b.pool_evictions == 0
    finally:
        b.shutdown()
        engine.close()
