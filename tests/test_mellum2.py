"""Window and full attention layers in one stack (Mellum 2's pattern, PR 35)
on the CPU at a tiny size: hidden 64, 2 periods of 3 window + 1 full layer,
window 8, pages of 4 rows, 8 experts top-2, context 64. The engine and the
continuous batcher serve it from pages by kind (engine/paged.py header); the
plain full forward (`model.forward_full`, no cache) is what served logits are
held to."""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model, paged
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_MOE, TINY_TEST, ModelConfig, RopeParams
from aios_tpu.engine.engine import TPUEngine, refuse_for_two_kinds

P, W, CTX = 4, 8, 64
YARN = RopeParams(theta=500000.0, factor=16.0, original_context=16,
                  attention_factor=1.2772588722239782)
CFG = ModelConfig(
    name="tiny-mellum", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16, max_context=CTX,
    rms_norm_eps=1e-6, rope_theta=500000.0, sliding_window=W,
    layer_types=("window", "window", "window", "full") * 2,
    rope_by_kind=(("full", YARN), ("window", RopeParams(theta=500000.0))),
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
)


@pytest.fixture(scope="module")
def params():
    return model.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _ids(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, size=n)]


class _Engine(TPUEngine):
    """A deployment whose admission chunk is the 16 rows these tests admit by:
    the window kind's pool is sized from it (a window, 8 rows, and a chunk in
    flight: 24 rows = 6 pages for each context `paged_pool_rows` holds), and a
    prompt of more than those 24 rows admits in chunks."""

    prefill_chunk_default = 16


def _engine(params, slots=2, contexts=None, **kw):
    """``contexts``: how many whole contexts the FULL kind's pool holds (and so
    how many 6-page window shares the window kind's does); slots + 1 unless said."""
    kw.setdefault("paged_pool_rows", (slots + 1 if contexts is None else contexts) * CTX)
    return _Engine(CFG, params, num_slots=slots, page_size=P,
                   cache_dtype=jnp.float32, **kw)


def _plain(params, seq):
    """Logits of the plain forward at the last position of ``seq``."""
    return np.asarray(model.forward_full(params, CFG, jnp.asarray([seq]))[0, -1])


def _agrees(params, seq, token):
    """The served token is the plain forward's best, or within rounding of it."""
    logits = _plain(params, seq)
    return logits.max() - logits[token] < 1e-3


# -- the configuration -------------------------------------------------------------


def test_a_stack_of_two_kinds_has_a_period_and_a_table_a_kind():
    assert CFG.kinds and CFG.period == 4
    assert CFG.period_kinds == ("window", "window", "window", "full")
    assert CFG.window_of("window") == W and CFG.window_of("full") is None
    assert CFG.rope_of("full") == YARN and CFG.rope_of("window").factor == 1.0
    uneven = ("window", "full", "window", "window", "full", "full", "full", "window")
    assert dataclasses.replace(CFG, layer_types=uneven).period == 8  # the stack is the body
    # a model of one kind is the old model
    plain = TINY_TEST.scaled(sliding_window=32)
    assert not plain.kinds and plain.period == 1 and plain.period_kinds == ()
    assert plain.window_of(None) == 32 and plain.rope_of(None).theta == plain.rope_theta
    # plain data is taken too (the benchmark's files import nothing of the program)
    same = dataclasses.replace(
        CFG, layer_types=list(CFG.layer_types),
        rope_by_kind=[("full", tuple(dataclasses.asdict(YARN).items())),
                      ("window", (("theta", 500000.0),))])
    assert same == CFG


@pytest.mark.parametrize("change, message", [
    (dict(layer_types=("window",) * 7), "for each of the 8 layers"),
    (dict(sliding_window=None), "window of the window kind"),
    (dict(layer_types=("local",) * 8), "unknown kinds"),
    (dict(rope_by_kind=(("full", RopeParams(factor=4.0)),)), "needs rope_original_context"),
    # a stack of one kind has ONE table, the model's own fields'
    (dict(layer_types=("full",) * 8, sliding_window=None), "rope_by_kind is for a stack that mixes"),
    (dict(layer_types=()), "rope_by_kind is for a stack that mixes"),
])
def test_what_the_pattern_cannot_be_is_refused_by_name(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_yarn_serves_the_grouped_query_block_and_shares_the_latent_blend():
    """One blend: the latent block's frequencies come from the same function;
    the full kind's cos and sin carry the attention factor, the window
    kind's table is the plain one, trace for trace."""
    from aios_tpu.engine import latent

    pos = jnp.arange(40)[None]
    cos_w, sin_w = model.rope_tables_of(pos, 16, CFG.rope_of("window"))
    want, _ = model.rope_tables(pos, 16, 500000.0)
    np.testing.assert_array_equal(cos_w, want)
    cos_f, _ = model.rope_tables_of(pos, 16, YARN)
    assert float(jnp.abs(cos_f).max()) == pytest.approx(YARN.attention_factor, rel=1e-6)
    freqs = model.yarn_inv_freq(16, YARN)
    plain = 1.0 / 500000.0 ** (np.arange(8) / 8)
    assert freqs[0] == pytest.approx(plain[0]) and freqs[-1] == pytest.approx(plain[-1] / 16)
    mla = ModelConfig(
        name="m", vocab_size=8, hidden_size=8, intermediate_size=8, num_layers=1,
        num_heads=2, num_kv_heads=2, head_dim=24, q_lora_rank=8, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=8, rope_theta=500000.0,
        rope_factor=16.0, rope_original_context=16)
    np.testing.assert_array_equal(latent.yarn_inv_freq(mla), freqs)


# -- served against the plain forward ---------------------------------------------


def test_a_whole_prompt_then_decode_six_windows_past_it_agrees_with_the_plain_forward(params):
    eng = _engine(params)
    prompt = _ids(13, 1)
    seq = list(prompt)
    token = eng.prefill(0, prompt, temperature=0.0)
    assert _agrees(params, seq, token)
    for _ in range(6 * W):
        seq.append(token)
        token = int(eng.step(1)[0, 0])
        assert _agrees(params, seq, token), len(seq)
    stats = eng.stats()
    assert len(seq) > 16 + 5 * W  # past the YaRN original length and 6 windows on
    assert stats["kv_window_pages_trimmed"] >= 10
    assert eng.allocator.slot_pages_resident(0, "window") <= W // P + 2
    assert eng.allocator.slot_pages_resident(0, "full") >= -(-len(seq) // P)
    assert stats["kv_pages_in_use"] == (
        stats["kv_full_pages_in_use"] + stats["kv_window_pages_in_use"])


def test_a_prompt_in_chunks_then_decode_agrees_with_the_plain_forward(params):
    eng = _engine(params)
    prompt = _ids(45, 2)
    admission = eng.start_chunked_prefill(1, prompt, temperature=0.0, top_p=1.0, chunk=16)
    token = None
    while token is None:
        token = admission.step()
    assert _agrees(params, prompt, token)
    # admission trimmed as it went: the window kind never held the prompt
    assert eng.stats()["kv_window_pages_trimmed"] > 0
    assert eng.allocator.slot_pages_resident(1, "window") <= (W + 16) // P + 1
    assert eng.allocator.slot_pages_resident(1, "full") == -(-45 // P)
    seq = list(prompt)
    for _ in range(12):
        seq.append(token)
        token = int(eng.step(1)[0, 1])
        assert _agrees(params, seq, token), len(seq)


def test_the_batcher_serves_short_and_long_prompts_in_one_queue(params):
    """Through the continuous batcher: chunked admission, the pipelined
    2-step loop, both slots live; every stream's tokens are the plain
    forward's."""
    eng = _engine(params)
    batcher = ContinuousBatcher(eng, prefill_chunk=16)
    try:
        prompts = [_ids(n, 10 + n) for n in (6, 41, 19, 50)]
        handles = [batcher.submit(Request(prompt_ids=p, max_tokens=10, temperature=0.0,
                                          request_id=f"r{i}"))
                   for i, p in enumerate(prompts)]
        streams = [list(h) for h in handles]
    finally:
        batcher.shutdown()
    for prompt, stream in zip(prompts, streams):
        assert len(stream) == 10
        seq = list(prompt)
        for token in stream:
            assert _agrees(params, seq, token), (len(prompt), len(seq))
            seq.append(token)
    stats = eng.stats()
    assert stats["kv_window_pages_trimmed"] > 0 and stats["prefix_hits_refused_window"] == 0


def test_the_grammar_s_jump_ahead_appends_through_both_kinds(params):
    eng = _engine(params)
    prompt = _ids(21, 3)
    token = eng.prefill(0, prompt, temperature=0.0)
    forced = np.zeros((2, 4), np.int32)
    forced[0, :3] = [5, 6, 7]
    eng.jump_step(forced, np.asarray([3, 0], np.int32))
    after = int(eng.step(1)[0, 0])
    assert _agrees(params, prompt + [token, 5, 6, 7], after)


# -- residency ----------------------------------------------------------------------


def test_a_second_slot_reuses_the_pages_the_first_trimmed_and_both_stay_right(params):
    """A window pool too small for two slots' whole contexts: 12 pages, where
    the two contexts end at 2 x 14. The second slot is admitted onto pages the
    first gave back, while the first goes on."""
    eng = _engine(params, contexts=2, prefix_cache=False)
    assert eng.allocator.window.num_pages - 1 == 12
    first = _ids(30, 4)
    seqs = {0: list(first)}
    tokens = {0: eng.prefill(0, first, temperature=0.0)}
    for _ in range(4):
        seqs[0].append(tokens[0])
        tokens[0] = int(eng.step(1)[0, 0])
    def live(slot):  # the pages of the blocks the slot still holds
        a = eng.allocator.window
        return {int(p) for p in a.tables[slot][int(a._trimmed[slot]):int(a._blocks_used[slot])]}

    held = live(0)
    trimmed = eng.stats()["kv_window_pages_trimmed"]
    assert trimmed >= 5 and len(held) <= 4
    second = _ids(22, 5)
    admission = eng.start_chunked_prefill(1, second, temperature=0.0, top_p=1.0, chunk=16)
    while (tok := admission.step()) is None:
        pass
    seqs[1], tokens[1] = list(second), tok
    reused = live(1)
    assert reused and not reused & held  # pages of its own, from the freed ones
    assert eng.stats()["kv_window_pages_allocated"] > 12  # more than the pool ever held
    for _ in range(16):
        out = eng.step(1)
        for s in (0, 1):
            seqs[s].append(tokens[s])
            tokens[s] = int(out[0, s])
            assert _agrees(params, seqs[s], tokens[s]), (s, len(seqs[s]))
    # what was trimmed is no longer this slot's: its references are gone
    gone = int(eng.allocator.window._trimmed[0])
    assert gone > 0 and eng.allocator.slot_pages_resident(0, "window") <= W // P + 2


def test_pool_exhausted_names_the_kind_and_grows_neither(params):
    eng = _engine(params, contexts=1, prefix_cache=False)  # one 6-page window share
    eng.prefill(0, _ids(24, 6), temperature=0.0)  # all 6, and 6 of the full kind's 16
    before = eng.allocator.full.pages_in_use()
    with pytest.raises(paged.PoolExhausted, match="of the window kind") as err:
        eng.prefill(1, _ids(9, 7), temperature=0.0)
    assert err.value.kind == "window"
    assert eng.allocator.full.pages_in_use() == before  # both kinds or neither
    small = _engine(params, paged_pool_rows=5 * P, prefix_cache=False)
    with pytest.raises(paged.PoolExhausted, match="of the full kind") as err:
        small.prefill(0, _ids(40, 6), temperature=0.0)  # 10 pages of a 5-page kind
    assert err.value.kind == "full"
    one = paged.PageAllocator(4, P, 1, 16)
    with pytest.raises(paged.PoolExhausted) as err:
        one.ensure(0, 40)
    assert err.value.kind == "" and "kind" not in str(err.value)


def test_the_victim_is_the_slot_that_holds_most_of_the_kind_that_ran_short(params):
    """Slot 0 is long (most full-kind pages, its window kind down to its last
    window), slot 1 short and untrimmed (most window-kind pages)."""
    from aios_tpu.engine.batching import _Live

    def scene():
        eng = _engine(params, slots=3, prefix_cache=False)
        batcher = ContinuousBatcher(eng, prefill_chunk=16)
        eng.prefill(0, _ids(50, 20), temperature=0.0)
        eng.step(1)
        eng.prefill(1, _ids(15, 21), temperature=0.0)
        lives = {s: _Live(req=Request(prompt_ids=[1], request_id=f"v{s}"), slot=s)
                 for s in (0, 1)}
        with batcher._lock:
            batcher._live.update(lives)
        return eng, batcher, lives

    eng, batcher, lives = scene()
    alloc = eng.allocator
    assert alloc.slot_pages_resident(0, "full") > alloc.slot_pages_resident(1, "full")
    assert alloc.slot_pages_resident(1, "window") > alloc.slot_pages_resident(0, "window")
    try:
        assert batcher._evict_longest(kind="window") == "evicted"
        assert lives[1].done and not lives[0].done
    finally:
        batcher.shutdown()
    eng, batcher, lives = scene()
    try:
        assert batcher._evict_longest(kind="full") == "evicted"
        assert lives[0].done and not lives[1].done
    finally:
        batcher.shutdown()


# -- prefix sharing -----------------------------------------------------------------


def test_a_prefix_hit_is_served_where_the_window_rows_are_held(params):
    """A short shared prefix (under a window: nothing trimmed) and the end of a
    long one (its last window held) are served; the tail agrees with the
    plain forward."""
    eng = _engine(params, slots=2)
    system = _ids(16, 7)
    first = system + _ids(5, 8)
    eng.prefill(0, first, temperature=0.0)
    eng.release(0)
    second = system + _ids(9, 9)
    token = eng.prefill(1, second, temperature=0.0)
    stats = eng.stats()
    assert stats["prefix_rows_reused"] == 16 and stats["prefix_hits_refused_window"] == 0
    assert _agrees(params, second, token)
    seq = list(second)
    for _ in range(10):
        seq.append(token)
        token = int(eng.step(1)[0, 1])
        assert _agrees(params, seq, token)
    eng.release(1)
    # the end of a long prompt admitted in chunks: the window kind holds its
    # last window, the full kind every block
    long = _ids(44, 11)
    admission = eng.start_chunked_prefill(0, long + _ids(3, 12), temperature=0.0,
                                          top_p=1.0, chunk=16)
    while admission.step() is None:
        pass
    eng.release(0)
    again = long + _ids(6, 13)
    token = eng.prefill(1, again, temperature=0.0)
    assert eng.stats()["prefix_rows_reused"] == 16 + 44
    assert eng.stats()["prefix_hits_refused_window"] == 0
    assert _agrees(params, again, token)


@pytest.mark.parametrize("given", ["handed_in", "none"])
def test_the_batcher_serves_both_kinds_prefix_hits_on_the_submitters_hashes(params, given):
    """Through the continuous batcher, the window kind's branch of the match
    (``WindowPrefixPages``): a short shared prefix served, a long prompt's
    first blocks refused where the window kind let them go. With the
    hashes ``submit`` made on its caller's thread and with none (the engine
    hashes under its lock, as it did) the hits, the refusals and every
    stream are the same, and the plain forward's."""
    eng = _engine(params, slots=2)
    if given == "none":
        for name in ("prefill_async", "start_chunked_prefill"):
            def bare(*a, _real=getattr(eng, name), **k):
                return _real(*a, **dict(k, given=None))
            setattr(eng, name, bare)
    system, long = _ids(16, 7), _ids(44, 11)
    prompts = [system + _ids(5, 8), system + _ids(9, 9),  # the second: served
               long + _ids(3, 12), long[:16] + _ids(9, 14)]  # the fourth: refused
    batcher = ContinuousBatcher(eng, prefill_chunk=16)
    try:
        streams = [batcher.submit(Request(prompt_ids=p, max_tokens=6,
                                          temperature=0.0)).tokens()
                   for p in prompts]  # one after another: each finds the last's blocks
    finally:
        batcher.shutdown()
    for prompt, stream in zip(prompts, streams):
        seq = list(prompt)
        for token in stream:
            assert _agrees(params, seq, token), (len(prompt), len(seq))
            seq.append(token)
    stats = eng.stats()
    assert stats["prefix_rows_reused"] == 16 and stats["prefix_hits_refused_window"] == 1
    assert stats["admissions_prehashed"] == (4 if given == "handed_in" else 0)
    assert stats["history_backfills_skipped"] == 0  # this engine keeps a history


def test_a_prefix_hit_is_refused_where_the_window_rows_are_gone_and_counted(params):
    """A prompt that shares only the FIRST 16 rows of a 44-row prompt admitted
    in chunks: the full kind still holds those blocks, the window kind let
    them go as admission advanced, so the hit is refused and the prompt
    prefills whole; the refusal is counted and the answer is right."""
    eng = _engine(params, slots=2)
    long = _ids(44, 14)
    admission = eng.start_chunked_prefill(0, long + _ids(3, 15), temperature=0.0,
                                          top_p=1.0, chunk=16)
    while admission.step() is None:
        pass
    eng.release(0)
    assert eng.prefix_overlap_rows(long[:16] + _ids(7, 16)) == 16  # the full kind's
    short = long[:16] + _ids(7, 16)
    token = eng.prefill(1, short, temperature=0.0)
    stats = eng.stats()
    assert stats["prefix_hits_refused_window"] == 1 and stats["prefix_rows_reused"] == 0
    assert _agrees(params, short, token)
    # cut short, not refused: 40 shared rows, of which the window kind holds a
    # window that ends at block 9 or later only if the first admission kept it
    eng.release(1)
    held = eng.window_prefix
    assert len(held) > 0 and held.servable([b"x"], W) == (0, [])


def test_the_window_side_gives_its_pages_back_under_pressure(params):
    """Registered window pages are held by one reference each and reclaimed,
    coldest first, when the window kind's free list runs dry."""
    eng = _engine(params, slots=2, contexts=2)  # 12 window-kind pages
    for n, seed in ((20, 30), (24, 31), (28, 32)):
        eng.prefill(0, _ids(n, seed), temperature=0.0)
        eng.release(0)
    kept = len(eng.window_prefix)
    assert kept > 0
    eng.prefill(0, _ids(46, 33), temperature=0.0)  # needs 12 of the 12 pages
    assert len(eng.window_prefix) < kept + 11
    assert eng.allocator.window.pages_in_use() <= 12


# -- refusals, counters, names ------------------------------------------------------


@pytest.mark.parametrize("kw, feature", [
    (dict(paged_pool_rows=None), "dense slot cache"),
    (dict(cache_dtype=jnp.int8), "int8 KV pool"),
    (dict(prefix_host_bytes=1 << 20), "host spill tier and its KVX entries"),
    (dict(kv_compress_after=16), "window and sink KV compression"),
    (dict(seq_prefill_min=32), "sequence sharded prefill"),
])
def test_what_cannot_take_two_kinds_is_refused_by_name_at_load(params, kw, feature):
    with pytest.raises(ValueError, match=f"tiny-mellum.*{feature}.*two kinds"):
        TPUEngine(CFG, params, num_slots=2, page_size=P,
                  **{"paged_pool_rows": 3 * CTX, **kw})


def test_the_other_refusals_name_the_model_and_a_model_of_one_kind_passes():
    for asked in ("a_sharding_plan_or_the_dp_replicated_pool_twin",
                  "a_draft_model_and_its_speculation", "KVX_entries"):
        with pytest.raises(ValueError, match="tiny-mellum.*pages of two kinds"):
            refuse_for_two_kinds(CFG, **{asked: True})
    refuse_for_two_kinds(CFG, a_draft_model_and_its_speculation=False)
    refuse_for_two_kinds(TINY_MOE, an_int8_KV_pool=True)


def test_the_counters_by_kind_and_the_scopes(params):
    eng = _engine(params)
    stats = eng.stats()
    for key in ("kv_full_pages_in_use", "kv_window_pages_in_use", "kv_window_pages_allocated",
                "kv_window_pages_trimmed", "prefix_hits_refused_window", "kv_full_pages",
                "kv_pages_in_use", "kv_pages_free"):
        assert key in stats, key
    assert stats["kv_pages_in_use"] + stats["kv_pages_free"] == (
        stats["kv_full_pages"] + stats["kv_window_pages"])
    # the live count is what the slots map; a finished request's blocks stay
    # in use (the prefix index holds them) and are no longer live
    eng.prefill(0, _ids(21, 50), temperature=0.0)
    busy = eng.stats()
    assert busy["kv_full_pages_live"] == busy["kv_full_pages_in_use"] == -(-21 // P)
    eng.release(0)
    idle = eng.stats()
    assert idle["kv_full_pages_live"] == 0 < idle["kv_full_pages_in_use"]
    one_kind = TPUEngine(TINY_MOE, model.init_params(TINY_MOE, jax.random.PRNGKey(0)),
                         num_slots=2, paged_pool_rows=256, page_size=16).stats()
    assert "kv_window_pages_trimmed" not in one_kind and "kv_pages_in_use" in one_kind
    # a scope lives in an operation's name stack, which the jaxpr prints
    shapes = jax.eval_shape(lambda: params)
    pool = jax.ShapeDtypeStruct((2, eng._layout.pages, P, 32), jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    step = str(jax.make_jaxpr(lambda p, c, r, t, n, tb: model.decode_step_paged(
        p, CFG, t, n, c, r, tb, kernels=False, layout=eng._layout))(
        shapes, pool, pool, i32(2), i32(2), i32(2, 32)).jaxpr.pretty_print(name_stack=True))
    chunk = str(jax.make_jaxpr(lambda p, c, r, t, s, row: model.prefill_chunk_paged(
        p, CFG, t, s, c, r, row, layout=eng._layout))(
        shapes, pool, pool, i32(1, 16), i32(), i32(32)).jaxpr.pretty_print(name_stack=True))
    for text in (step, chunk):
        assert "attention_window" in text and "attention_full" in text
    assert step.count("scan") <= chunk.count("scan")  # one layer scan a graph


def test_the_flight_recorder_s_admission_record_has_the_pages_by_kind(params):
    from aios_tpu.obs import flightrec

    seen = []
    listener = seen.append
    flightrec.RECORDER.add_listener(listener)
    eng = _engine(params)
    batcher = ContinuousBatcher(eng, prefill_chunk=16)
    try:
        list(batcher.submit(Request(prompt_ids=_ids(40, 40), max_tokens=3, temperature=0.0,
                                    request_id="rec-pages")))
    finally:
        batcher.shutdown()
        flightrec.RECORDER._listeners.remove(listener)
    mine = [t for t in seen if t.request_id == "rec-pages"]
    events = [fields for t in mine for _, kind, fields in t.events if kind == "prefill"]
    assert events and all("pages_full" in e and "pages_window" in e for e in events)
    assert events[-1]["pages_full"] == 10 and events[-1]["pages_window"] <= (W + 16) // P + 1


# -- a stack of ONE kind under YaRN -------------------------------------------------


@pytest.mark.parametrize("window", [None, W], ids=["full", "windowed"])
def test_a_stack_of_one_kind_under_yarn_reads_the_table_it_wrote_with(window):
    """Every graph of a one-kind grouped-query stack takes its rotary table
    from `cfg.rope_of(None)`: the whole prompt, a prompt in chunks and the
    decode steps past the YaRN original length agree with the plain forward
    (a step that rotated by the unscaled table would read a cache written with
    the scaled one)."""
    cfg = TINY_TEST.scaled(
        name="tiny-yarn", max_context=CTX, sliding_window=window,
        rope_theta=500000.0, rope_factor=16.0, rope_original_context=16)
    assert cfg.rope_of(None).attention_factor == pytest.approx(YARN.attention_factor)
    unscaled = dataclasses.replace(cfg, rope_factor=1.0, rope_original_context=0)
    weights = model.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    for name in ("wq", "wk"):  # sharp scores, so that the table shows in the logits
        weights["layers"][name] = weights["layers"][name] * 8.0

    def plain(seq, c=cfg):
        return np.asarray(model.forward_full(weights, c, jnp.asarray([seq]))[0, -1])

    eng = TPUEngine(cfg, weights, num_slots=2, page_size=P, cache_dtype=jnp.float32,
                    paged_pool_rows=3 * CTX)
    whole, chunked = _ids(11, 40), _ids(37, 41)
    tokens = {0: eng.prefill(0, whole, temperature=0.0)}
    admission = eng.start_chunked_prefill(1, chunked, temperature=0.0, top_p=1.0, chunk=16)
    while (token := admission.step()) is None:
        pass
    tokens[1] = token
    seqs = {0: list(whole), 1: list(chunked)}
    differs = 0
    for _ in range(20):
        for s in (0, 1):
            logits = plain(seqs[s])
            assert logits.max() - logits[tokens[s]] < 1e-3, (s, len(seqs[s]))
            differs += int(np.abs(logits - plain(seqs[s], unscaled)).max() > 0.05)
            seqs[s].append(tokens[s])
        out = eng.step(1)
        tokens = {s: int(out[0, s]) for s in (0, 1)}
    assert len(seqs[0]) > 16 and differs > 30  # the scaling is not a no-op here


# -- models of one kind are what they were ----------------------------------------

# sha256 (first 16 hex digits) of the lowered text of a paged decode step, a
# paged chunk and a whole-prompt prefill at the PARENT commit (280ee42), made
# there by this very function; the CHUNK's is PR 44's tree's (its attention
# folds key tiles read from the pool up to the chunk's last row: a change to
# every grouped-query chunk, made on purpose; tests/test_chunk_attention_bound.py
# holds it to the parent's formulation)
PARENT = {
    "mistral": ["e4271ea22153727a", "8b1b931c47ba5ce6", "00e78a00d231f314"],
    "mixtral": ["d03baabe4dc9304f", "19aee64d03155112", "c4651cfc6953f120"],
}


def _lowered(cfg):
    shapes = jax.eval_shape(
        lambda: model.quantize_params(model.init_params(cfg, jax.random.PRNGKey(0))))
    pools = tuple(jax.ShapeDtypeStruct((cfg.num_layers, 8, 16, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    def step(p, c, r, toks, lens, tables):
        return model.decode_step_paged(p, cfg, toks, lens, c, r, tables, kernels=False)

    def chunk(p, c, r, toks, start, row):
        return model.prefill_chunk_paged(p, cfg, toks, start, c, r, row)

    def whole(p, toks):
        return model.prefill(p, cfg, toks, kernels=False)

    return [jax.jit(step).lower(shapes, *pools, i32(2), i32(2), i32(2, 8)).as_text(),
            jax.jit(chunk).lower(shapes, *pools, i32(1, 16), i32(), i32(8)).as_text(),
            jax.jit(whole).lower(shapes, i32(1, 32)).as_text()]


@pytest.mark.parametrize("name, cfg", [
    ("mistral", TINY_TEST.scaled(max_context=128, sliding_window=32)),
    ("mixtral", TINY_MOE.scaled(max_context=128)),
])
def test_a_model_of_one_kind_lowers_to_the_graphs_it_had(name, cfg):
    texts = _lowered(cfg)
    assert [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts] == PARENT[name]
    assert all("attention_window" not in t and "layer_kind" not in t for t in texts)


# -- two processes lower the same text ---------------------------------------------

_LOWER_EVERY_SERVING_GRAPH = """
import hashlib, json, sys
sys.path.insert(0, {tests!r})
import jax
import test_mellum2 as t
eng = t._engine(t.model.init_params(t.CFG, jax.random.PRNGKey(0), dtype=t.jnp.float32))
texts = {{}}
def lower_only(kind, store, key, jitfn, args):
    texts[f"{{kind}} {{key}}"] = hashlib.sha256(
        jitfn.lower(*args).as_text().encode()).hexdigest()[:16]
eng._compile_aot = lower_only
eng.warmup()
print(json.dumps({{"set_order": list(set(t.CFG.period_kinds)), "texts": texts}}))
"""


def test_a_stack_of_two_kinds_lowers_to_the_same_text_whatever_the_hash_seed():
    """Every graph ``warmup`` compiles for the tiny stack of two kinds, lowered
    in two processes whose hash seeds put a SET of the two kinds in opposite
    orders: the same text, so a compile cache keyed by it hits on every start
    (``model.rope_by_kind`` walks the period's own order)."""
    here = os.path.dirname(os.path.abspath(__file__))
    runs = []
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [os.path.dirname(here), os.environ.get("PYTHONPATH", "")]))
        out = subprocess.run(
            [sys.executable, "-c", _LOWER_EVERY_SERVING_GRAPH.format(tests=here)],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    one, two = runs
    assert one["set_order"] != two["set_order"], "the seeds no longer tell"
    assert {name.split()[0] for name in one["texts"]} >= {"prefill", "chunk", "step"}
    assert one["texts"] == two["texts"]
