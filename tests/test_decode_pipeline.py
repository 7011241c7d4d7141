"""Pipelined decode loop + dispatch-free AOT warmup (ISSUE 6).

Two guarantees under test:
  * token identity: the depth-2 pipelined batcher (AIOS_TPU_DECODE_PIPELINE)
    emits byte-for-byte the streams the sync loop emits — greedy AND
    sampled with a fixed seed — including across retirement boundaries,
    ``force_pending_token`` (grammar-constrained admission), and
    chunked-prefill interleaving, where the pipeline must flush;
  * no compile after warmup: ``engine.warmup()`` AOT-compiles every graph
    the serving path can hit, so a post-warmup sweep across every prefill
    bucket, both chunked-admission paths, every decode chunk size, the
    masked step, and the prefix-hit path moves ``engine.stats()``'s
    compile counters by exactly zero.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model as M
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import (
    DECODE_STEPS, JUMP_BUCKETS, PendingFirstToken, TPUEngine,
)
from aios_tpu.engine.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def params():
    return M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_engine(params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_context", 128)
    kw.setdefault("cache_dtype", jnp.float32)
    return TPUEngine(TINY_TEST, params, **kw)


def run_batch(params, pipeline, reqs, *, engine_kw=None, batcher_kw=None,
              warm=True):
    """One engine+batcher lifecycle: submit ``reqs`` (dicts) up front,
    drain every stream, return (per-request token lists, batcher, engine
    stats)."""
    eng = make_engine(params, **(engine_kw or {}))
    if warm:
        eng.warmup(step_sizes=(2, 4), prefill_chunk=32)
    kw = dict(chunk_steps=4, admit_chunk_steps=2, pipeline=pipeline)
    kw.update(batcher_kw or {})
    b = ContinuousBatcher(eng, **kw)
    try:
        handles = [b.submit(Request(**r)) for r in reqs]
        outs = [h.tokens() for h in handles]
        stats = dict(eng.stats())
        stats["flushes"] = b.flushes
        stats["dispatches"] = b.decode_dispatches
        stats["evictions"] = b.pool_evictions
        stats["aborted"] = [h.abort_reason for h in handles]
        return outs, stats
    finally:
        b.shutdown()
        eng.close()


def test_pipeline_token_identical_greedy(params):
    """Same streams pipeline-on vs -off at temperature 0, with staggered
    max_tokens so requests retire at different dispatch boundaries and a
    stop token that fires mid-dispatch."""
    reqs = [
        dict(prompt_ids=[3 + i, 17, 91, 4 + i], max_tokens=18 + 5 * i,
             temperature=0.0)
        for i in range(4)
    ]
    off, s_off = run_batch(params, False, reqs)
    # make one request stop early on a token the free run actually emits
    reqs[1]["stop_ids"] = (off[1][4],)
    off, s_off = run_batch(params, False, reqs)
    on, s_on = run_batch(params, True, reqs)
    assert on == off
    assert len(off[1]) <= 5 + 1  # the stop actually fired
    # the pipelined run really pipelined: dispatches were issued ahead
    assert s_on["dispatches"] > 0


def test_pipeline_token_identical_sampled(params):
    """Fixed engine seed, temperature > 0: the pipelined dispatch chain
    consumes the SAME per-dispatch key splits, so sampled streams match
    token-for-token."""
    reqs = [
        dict(prompt_ids=[7 + i, 2, 55], max_tokens=21 + 4 * i,
             temperature=0.85, top_p=0.9)
        for i in range(4)
    ]
    off, _ = run_batch(params, False, reqs)
    on, _ = run_batch(params, True, reqs)
    assert on == off
    assert any(len(set(t)) > 1 for t in on)  # actually sampled something


def test_pipeline_flushes_idle_after_retirement(params):
    """When the whole batch retires, the next tick flushes (or shutdown
    drops) the speculatively-issued dispatch; the stream itself is exactly
    max_tokens long."""
    reqs = [dict(prompt_ids=[5, 6, 7], max_tokens=13, temperature=0.0)]
    off, _ = run_batch(params, False, reqs)
    on, stats = run_batch(params, True, reqs)
    assert on == off and len(on[0]) == 13


def test_pipeline_constrained_flush_and_force_pending_token(params):
    """A json_mode request admitted mid-stream forces its pending opener
    (force_pending_token) and rides 1-step masked dispatches — the
    pipeline must drain first (cause=constrained), and both the
    constrained and the co-resident unconstrained stream stay correct."""
    tok = ByteTokenizer()
    eng = make_engine(params)
    eng.warmup(step_sizes=(2, 4), prefill_chunk=32, masked_step=True)
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2,
                          pipeline=True, tokenizer=tok)
    try:
        plain = b.submit(Request(prompt_ids=tok.encode("plain"),
                                 max_tokens=60, temperature=0.0))
        # consume a few tokens FIRST: after >= 1 plain decode tick the
        # pipeline holds an in-flight dispatch (and keeps holding one,
        # tick over tick) — so the constrained admission below MUST
        # drain it, deterministically
        it = iter(plain)
        t_plain = [next(it) for _ in range(4)]
        constrained = b.submit(Request(
            prompt_ids=tok.encode("emit json"), max_tokens=40,
            temperature=0.9, stop_ids=(tok.eos_id,), json_mode=True,
        ))
        t_plain += list(it)
        t_json = constrained.tokens()
        parsed = json.loads(tok.decode(t_json))
        assert isinstance(parsed, dict)
        assert len(t_plain) == 60
        # the constrained tick drained the pipeline at least once while
        # the plain stream was mid-flight
        assert b.flushes >= 1
    finally:
        b.shutdown()
        eng.close()


def test_pipeline_chunked_prefill_interleave_identical(params):
    """A long prompt admitting chunk-by-chunk between pipelined decode
    dispatches: streams match the sync loop exactly (the chunk writes and
    the in-flight decode order through the donated state chain)."""
    long_prompt = (np.arange(1, 90) % 250 + 1).tolist()  # > prefill_chunk 32
    reqs = [
        dict(prompt_ids=[9, 8, 7], max_tokens=24, temperature=0.0),
        dict(prompt_ids=long_prompt, max_tokens=12, temperature=0.0),
        dict(prompt_ids=[41, 2], max_tokens=16, temperature=0.0),
    ]
    kw = dict(batcher_kw=dict(prefill_chunk=32))
    off, _ = run_batch(params, False, reqs, **kw)
    on, _ = run_batch(params, True, reqs, **kw)
    assert on == off
    assert len(on[1]) == 12


def test_pipeline_pool_eviction_flush(params):
    """Pool exhaustion mid-decode with a dispatch in flight: the eviction
    path flushes first (the victim keeps every token it produced before
    the abort), the survivor completes, and the engine state stays
    coherent."""
    # 4 usable pages (128 rows): both streams fit at admission (1 page
    # each) but cross their 3rd-page boundary together mid-decode — 6
    # pages wanted, 4 exist — so the dispatch path must evict the
    # priority-0 stream while the priority-1 survivor (80 rows = 3 pages
    # peak) still completes
    reqs = [
        dict(prompt_ids=list(range(1, 31)), max_tokens=50, temperature=0.0,
             priority=1),
        dict(prompt_ids=list(range(40, 70)), max_tokens=80, temperature=0.0),
    ]
    outs, stats = run_batch(
        params, True, reqs,
        engine_kw=dict(num_slots=2, paged_pool_rows=128, page_size=32,
                       prefix_cache=False),
    )
    assert stats["evictions"] >= 1
    aborted = [r for r in stats["aborted"] if r]
    assert aborted and "evicted" in aborted[0]
    # the survivor (higher priority) ran to completion
    survivor = [o for o, r in zip(outs, stats["aborted"]) if not r]
    assert survivor and len(survivor[0]) > 0


@pytest.mark.parametrize("admission", ["chunked", "whole"])
def test_no_compile_after_warmup_serving_sweep(params, admission):
    """The AOT readiness gate covers the WHOLE serving surface, and what the
    batcher cannot dispatch stays outside it. With chunked admission on (a
    chunk of 128 rows under buckets to 512) ``warmup`` compiles no
    whole-prompt graph above the chunk size; with it off every bucket the
    pool can back. Either way prompts of every bucket's length THROUGH THE
    BATCHER, a prefix hit behind them, then the engine's own walk of the
    whole-prompt buckets it compiled, the prefix chunk's path, every warmed
    decode size and the grammar-masked step move the compile counters by
    exactly zero."""
    chunk = 128 if admission == "chunked" else 0
    eng = TPUEngine(
        TINY_TEST.scaled(max_context=512), params, num_slots=2,
        max_context=512, cache_dtype=jnp.float32,
        paged_pool_rows=512, page_size=32, prefix_host_bytes=32 << 20,
    )
    try:
        eng.warmup(step_sizes=(1, 2, 8, 16), masked_step=True,
                   prefill_chunk=chunk)
        whole = [b for b in eng.buckets if not chunk or b <= chunk]
        assert sorted(b for b in eng._prefill_fns if isinstance(b, int)) == whole
        assert whole[-1] == (128 if chunk else 512)
        b = ContinuousBatcher(eng, prefill_chunk=chunk)
        before = eng.stats()["xla_compiles"]
        rng = np.random.default_rng(7)
        prompts = [[int(t) for t in rng.integers(1, 500, n)]
                   for n in [bk // 2 + 1 for bk in eng.buckets] + [128, 420]]
        try:
            assert b.prefill_chunk == (chunk or None)
            # the last resubmitted with one more token: a prefix HIT
            for prompt in prompts + [prompts[-1] + [5]]:
                h = b.submit(Request(prompt_ids=prompt, max_tokens=3,
                                     temperature=0.0))
                assert len(h.tokens()) == 3 and not h.aborted
            assert eng.stats()["prefix_rows_reused"] > 0
        finally:
            b.shutdown()
        # the engine's own surface: every whole-prompt graph it compiled
        for bk in whole:
            prompt = [int(t) for t in rng.integers(1, 500, bk // 2 + 1)]
            eng.prefill(0, prompt, temperature=0.0)
            eng.step(1)
            eng.release(0)
        # chunked admission at the prefix chunk (mid + final chunk graphs)
        long_prompt = [int(t) for t in rng.integers(1, 500, 420)]
        pc = eng.start_chunked_prefill(0, long_prompt, chunk=eng._prefix_chunk)
        while pc.step() is None:
            pass
        # both batcher chunk sizes + the masked step + a forced token
        for n in (1, 2, 8, 16):
            eng.step(n)
        eng.force_pending_token(0, 3)
        eng.step_masked(np.zeros((2, TINY_TEST.vocab_size), np.float32))
        eng.release(0)
        # prefix-HIT path: resubmit -> history backfill + tail chunks
        eng.prefill(0, long_prompt + [5], temperature=0.0)
        eng.release(0)
        assert eng.stats()["xla_compiles"] == before, (
            "serving sweep compiled a graph warmup should have covered"
        )
    finally:
        eng.close()


def test_warmup_covers_host_tier_restore(params):
    """Spill -> restore after warmup compiles nothing: the bucketed
    restore scatters were AOT-built behind the readiness gate."""
    eng = TPUEngine(
        TINY_TEST.scaled(max_context=512), params, num_slots=2,
        max_context=512, cache_dtype=jnp.float32,
        paged_pool_rows=512, page_size=32, prefix_host_bytes=32 << 20,
    )
    try:
        eng.warmup(step_sizes=(1,))
        before = eng.stats()["xla_compiles"]
        rng = np.random.default_rng(11)
        preamble = [int(t) for t in rng.integers(1, 500, 321)]
        eng.prefill(0, preamble, temperature=0.0)
        eng.release(0)
        pressure = [int(t) for t in rng.integers(1, 500, 480)]
        eng.prefill(0, pressure, temperature=0.0)  # reclaim -> spill
        eng.release(0)
        deadline = __import__("time").time() + 10
        while eng.host_store.spills < 2 and __import__("time").time() < deadline:
            __import__("time").sleep(0.02)
        eng.prefill(0, preamble, temperature=0.0)  # host-tier restore
        eng.release(0)
        assert eng.stats().get("host_tier_restores", 0) >= 1
        assert eng.stats()["xla_compiles"] == before
    finally:
        eng.close()


@pytest.mark.parametrize(
    "mode", ["default", "plain", "json_forced", "speculative",
             "plain_pipelined"])
def test_warmup_and_attach_compile_exactly_what_the_loop_dispatches(
        params, mode):
    """After ``warmup()`` as the model manager calls it and a default
    batcher's attach, the step registry holds the loop's sizes (and
    the masked step where json mode is forced) and nothing else, the
    speculative and jump registries the same sizes and the jump
    buckets, and a serving wave with more requests than slots (so every
    size dispatches) compiles nothing and dispatches no size but those.
    A batcher made with nothing said runs the pipelined loop."""
    tok = ByteTokenizer()
    forced, spec = mode == "json_forced", mode == "speculative"
    eng = make_engine(params, num_slots=2)
    eng.warmup(masked_step=forced)
    warmed = {n for n in eng._step_fns if n != "masked"}
    dispatched = set()
    for name in ("step", "step_async"):
        def counted(n=1, _fn=getattr(eng, name)):
            dispatched.add(n)
            return _fn(n)
        setattr(eng, name, counted)
    how = {} if mode == "default" else {"pipeline": mode == "plain_pipelined"}
    b = ContinuousBatcher(eng, speculative=spec, tokenizer=tok, **how)
    try:
        assert b.pipeline == (mode in ("default", "plain_pipelined"))
        assert (b.admit_chunk_steps, b.chunk_steps) == (
            DECODE_STEPS, DECODE_STEPS)
        sizes = {DECODE_STEPS}
        assert set(eng._step_fns) == sizes | ({"masked"} if forced else set())
        assert {k[0] for k in eng._spec_fns} == (sizes if spec else set())
        assert set(eng._jump_fns) == (set(JUMP_BUCKETS) if forced else set())
        before = eng.stats()["xla_compiles"]
        handles = [
            b.submit(Request(prompt_ids=tok.encode("ab" * (3 + i)),
                             max_tokens=24 + 7 * i, temperature=0.0,
                             json_mode=forced and i == 1))
            for i in range(5)
        ]
        outs = [h.tokens() for h in handles]
        assert all(outs) and not any(h.aborted for h in handles)
        assert eng.stats()["xla_compiles"] == before
        assert dispatched <= warmed and (dispatched or spec)
    finally:
        b.shutdown()
        eng.close()


def test_an_arrival_waits_at_most_three_dispatches_of_the_other_stream(params):
    """A request submitted while a run of dispatches is in flight gets its
    first token after at most 3 x DECODE_STEPS further tokens of the stream
    beside it: the rest of the running dispatch, the one already issued
    behind it, the one issued beside the admission. Counted in the order
    the scheduler emits, never in time."""
    eng = make_engine(params, num_slots=2)
    b = ContinuousBatcher(eng)
    order = []
    emit = b._emit

    def noted(live, token, slot_len=None):
        order.append(live.req.request_id)
        emit(live, token, slot_len=slot_len)

    b._emit = noted
    try:
        first = b.submit(Request(prompt_ids=[3, 17, 91], max_tokens=120,
                                 temperature=0.0, request_id="running"))
        it = iter(first)
        for _ in range(3 * DECODE_STEPS + 1):  # dispatches follow one another
            next(it)
        order.append("submitted")
        second = b.submit(Request(prompt_ids=[9, 8, 7], max_tokens=4,
                                  temperature=0.0, request_id="arrival"))
        assert len(second.tokens()) == 4 and len(list(it)) > 0
    finally:
        b.shutdown()
        eng.close()
    after = order[order.index("submitted") + 1:]
    waited = after[:after.index("arrival")]
    assert set(waited) <= {"running"}
    assert len(waited) <= 3 * DECODE_STEPS


def test_a_stream_that_ends_frees_its_slot_within_two_dispatches(params):
    """A stream whose last token is decoded by step t of its slot hands
    the slot back with at most 2 x DECODE_STEPS steps decoded past t: the
    rest of that dispatch and the one already issued behind it."""
    eng = make_engine(params, num_slots=1)
    b = ContinuousBatcher(eng)
    released = []
    release = eng.release_pages  # the slot's pages are back: a tenant may come

    def noted(slot):
        released.append(eng.decode_steps)
        release(slot)

    eng.release_pages = noted
    tokens = 4 * DECODE_STEPS + 2  # the prefill's, then one a step
    try:
        out = b.submit(Request(prompt_ids=[3, 17, 91, 4], max_tokens=tokens,
                               temperature=0.0)).tokens()
    finally:
        b.shutdown()
        eng.close()
    assert len(out) == tokens
    assert 0 <= released[0] - (tokens - 1) <= 2 * DECODE_STEPS


@pytest.mark.parametrize(
    "why", ["budget", "stop", "context_cap", "context_cap_pipelined"])
def test_a_slot_that_cannot_take_another_row_finishes_and_frees(params, why):
    """A request whose budget runs out, whose stop token comes, or whose
    slot reaches the last row of its context in the middle of a dispatch
    (2 steps each: a request waits) ends there, without an abort, and hands
    its slot (the engine has one) to the request waiting behind it, which
    runs to its end. The context cap is judged on the slot's length after
    the dispatch, so the stream ends within a dispatch of the last row,
    never beyond it."""
    ctx = 32 if why.startswith("context_cap") else 128
    free = run_batch(params, False, [dict(
        prompt_ids=[3, 17, 91, 4], max_tokens=40, temperature=0.0)],
        engine_kw=dict(num_slots=1), warm=False)[0][0]
    first = dict(prompt_ids=[3, 17, 91, 4], max_tokens=40, temperature=0.0)
    if why == "budget":
        first["max_tokens"], want = 6, free[:6]
    elif why == "stop":
        stop = free[5]
        first["stop_ids"], want = (stop,), free[:free.index(stop) + 1]
    else:
        want = None
    second = dict(prompt_ids=[9, 8, 7], max_tokens=6, temperature=0.0)
    outs, stats = run_batch(
        params, why.endswith("pipelined"), [first, second],
        engine_kw=dict(num_slots=1, max_context=ctx),
        batcher_kw=dict(chunk_steps=DECODE_STEPS,
                        admit_chunk_steps=DECODE_STEPS),
        warm=False)
    if want is None:
        # 4 prompt rows of 32: at most 28 tokens, one of them the prefill's
        assert ctx - 4 - DECODE_STEPS <= len(outs[0]) <= ctx - 4
        want = free[:len(outs[0])]
    assert outs[0] == want and 0 < len(want) < 40
    assert len(outs[1]) == 6
    assert stats["aborted"] == ["", ""]


def test_batcher_attach_compiles_missing_sizes_without_dispatch(params):
    """A batcher with non-default chunk sizes attaching to a warmed
    engine AOT-compiles its sizes — engine state must not move (the old
    path dispatched real steps to compile them)."""
    eng = make_engine(params)
    eng.warmup(step_sizes=(16,), prefill_chunk=0)
    try:
        b = ContinuousBatcher(eng, chunk_steps=5, admit_chunk_steps=3)
        try:
            assert {3, 5} <= set(eng._step_fns)
            assert eng.decode_steps == 0
        finally:
            b.shutdown()
    finally:
        eng.close()


def test_pending_decode_lengths_snapshot(params):
    """step_async dispatches run FIFO on the engine's dispatch worker,
    and each pending handle carries the post-dispatch lengths of ITS
    dispatch — later dispatches must not leak into the snapshot (the
    out-of-cache retirement anchor)."""
    eng = make_engine(params)
    try:
        eng.prefill(0, [1, 2, 3], temperature=0.0)
        p1 = eng.step_async(2)
        p2 = eng.step_async(4)
        assert p1.wait().shape == (2, 4)
        assert p2.wait().shape == (4, 4)
        assert p1.lengths[0] == 5 and p2.lengths[0] == 9
        assert eng.slot_length(0) == 9
        # the fence used by the batcher's tick ordering
        p2.wait_started()
    finally:
        eng.close()


# -- an admission's first token is read behind the next decode dispatch ------

PAGED = dict(num_slots=3, paged_pool_rows=3 * 128, page_size=32)
SHARED = (np.arange(1, 41) % 250 + 1).tolist()  # a page and a quarter
LATE = {
    "whole": dict(prompt_ids=[9, 8, 7, 6]),
    # its first page is what the running stream's prompt registered
    "prefix_hit": dict(prompt_ids=SHARED[:36] + [5, 4]),
    # three chunks of 32, the last one partial
    "chunked": dict(prompt_ids=(np.arange(3, 83) % 250 + 1).tolist()),
}


def serve_with_arrival(params, pipeline, running, late, at=3, engine_kw=None,
                       batcher_kw=None, calls=None, active=None):
    """``running`` are submitted up front; ``late`` is submitted from the
    scheduler's own thread as it makes its ``at``-th decode dispatch, so that
    it is admitted by the tick after it in either loop (an arrival from
    another thread lands on whatever tick the race picks, and a sampled
    stream then draws from another place of the key chain). ``calls`` takes
    the order of the engine calls of the late request's admission, and
    ``active`` the number of active slots as each later dispatch is made."""
    eng = make_engine(params, **(engine_kw or PAGED))
    kw = dict(chunk_steps=2, admit_chunk_steps=2, prefill_chunk=32,
              pipeline=pipeline)
    kw.update(batcher_kw or {})
    b = ContinuousBatcher(eng, **kw)
    handles, dispatches = [], []
    note = calls.append if calls is not None else (lambda what: None)

    def arrive(what):
        dispatches.append(what)
        if len(dispatches) == at:
            handles.append(b.submit(Request(**late)))
        elif handles:
            note(what)
            if active is not None:
                active.append(int(eng.active.sum()))

    for name in ("step", "step_async", "step_masked"):
        def counted(*a, _real=getattr(eng, name), _name=name):
            arrive(_name)
            return _real(*a)
        setattr(eng, name, counted)
    force = eng.force_pending_token

    def forced(slot, token):
        note("force_pending_token")
        force(slot, token)

    eng.force_pending_token = forced
    try:
        first = [b.submit(Request(**r)) for r in running]
        outs = [h.tokens() for h in first]
        assert handles, "the streams ended before the arrival"
        outs.append(handles[0].tokens())
        stats = dict(eng.stats(), **b.stats())
        stats["pool_evictions"] = b.pool_evictions
        stats["aborted"] = [h.abort_reason for h in first + handles]
        return outs, stats
    finally:
        b.shutdown()
        eng.close()
        assert eng.prefix_index is not None \
            or eng.stats().get("kv_pages_in_use", 0) == 0


@pytest.mark.parametrize("temperature", [0.0, 0.85], ids=["greedy", "sampled"])
@pytest.mark.parametrize("admission", sorted(LATE))
def test_an_admission_beside_running_streams_serves_the_sync_loops_tokens(
        params, admission, temperature):
    """A request admitted while two streams decode (a whole prompt, a prefix
    hit's tail, a chunked admission's final chunk): the pipelined loop issues
    the decode dispatch behind its last prefill program and reads its first
    token after; every stream, the arrival's and the running ones' before and
    after it, is token for token what the synchronous loop serves, which
    reads the token where it admits."""
    sample = dict(temperature=temperature, top_p=0.9)
    running = [dict(prompt_ids=SHARED, max_tokens=40, **sample),
               dict(prompt_ids=[41, 2, 77], max_tokens=33, **sample)]
    late = dict(LATE[admission], max_tokens=12, **sample)
    off, s_off = serve_with_arrival(params, False, running, late)
    on, s_on = serve_with_arrival(params, True, running, late)
    assert on == off
    assert [len(o) for o in on] == [40, 33, 12]
    assert s_on["aborted"] == ["", "", ""]
    if temperature:
        assert any(len(set(o)) > 2 for o in on)
    if admission == "prefix_hit":
        assert s_on["prefix_rows_reused"] >= 32
    # the mechanism engaged at every admission of the pipelined loop, and
    # never in the other
    assert s_on["admissions"] == s_off["admissions"] == 3
    assert s_on["admissions_read_after_dispatch"] == 3
    assert s_off["admissions_read_after_dispatch"] == 0
    assert s_on["phase_batcher.first_token_count"] == 3


@pytest.mark.parametrize("constrained", [False, True],
                         ids=["plain", "constrained"])
def test_the_dispatch_behind_an_admission_is_issued_before_its_first_token_is_read(
        params, monkeypatch, constrained):
    """The order of the engine calls on an admission tick of the pipelined
    loop: prefill program, decode dispatch, THEN the read of the first token.
    A request with a constraint reads at once, overwrites the token with its
    forced opener, and only then rides a (masked) dispatch: the counter does
    not count it."""
    calls = []
    wait = PendingFirstToken.wait

    def read(self):
        if self.token is None:
            calls.append("first_token.wait")
        return wait(self)

    monkeypatch.setattr(PendingFirstToken, "wait", read)
    tok = ByteTokenizer()
    late = dict(prompt_ids=tok.encode("emit json"), max_tokens=16,
                temperature=0.0, stop_ids=(tok.eos_id,), json_mode=constrained)
    running = [dict(prompt_ids=[3, 17, 91], max_tokens=30, temperature=0.0)]
    outs, stats = serve_with_arrival(
        params, True, running, late, calls=calls,
        engine_kw=dict(num_slots=2), batcher_kw=dict(tokenizer=tok))
    assert len(outs[0]) == 30 and outs[1]
    # the running stream's own read, then what follows the arrival: the
    # engine calls of the tick that admitted it
    reads = [i for i, c in enumerate(calls) if c == "first_token.wait"]
    assert len(reads) == 2 and reads[0] == 0
    late_read = reads[1]
    if constrained:
        assert late_read == 1
        assert calls[2] == "force_pending_token"
        assert calls[3] == "step_masked"
        assert isinstance(json.loads(tok.decode(outs[1])), dict)
    else:
        assert calls[1:3] == ["step_async", "first_token.wait"]
        assert "force_pending_token" not in calls
    assert stats["admissions"] == 2
    # the running stream's own admission found nothing live and was read
    # behind the dispatch it started
    assert stats["admissions_read_after_dispatch"] == (1 if constrained else 2)


@pytest.mark.parametrize("why", ["budget", "stop"])
def test_a_first_token_that_is_the_last_retires_its_stream_and_frees_its_slot(
        params, why):
    """``max_tokens`` 1, or a stop token sampled by the prefill: the stream
    ends where its first token is read, without an abort; the dispatch
    already issued for its slot decodes that column into nothing, the slot
    is free again within two dispatches of its admission, and the stream
    beside it is what it is alone."""
    alone = run_batch(params, True, [dict(
        prompt_ids=[3, 17, 91], max_tokens=30, temperature=0.0)],
        engine_kw=PAGED, warm=False)[0][0]
    free = run_batch(params, True, [dict(
        prompt_ids=[9, 8, 7, 6], max_tokens=4, temperature=0.0)],
        engine_kw=PAGED, warm=False)[0][0]
    late = dict(prompt_ids=[9, 8, 7, 6], temperature=0.0)
    late.update(dict(max_tokens=1) if why == "budget"
                else dict(max_tokens=9, stop_ids=(free[0],)))
    running = [dict(prompt_ids=[3, 17, 91], max_tokens=30, temperature=0.0)]
    active = []
    outs, stats = serve_with_arrival(
        params, True, running, late, active=active,
        engine_kw=dict(PAGED, prefix_cache=False))
    assert outs == [alone, free[:1]]
    assert stats["aborted"] == ["", ""]
    assert stats["admissions_read_after_dispatch"] == 2
    # the dispatch behind its prefill ran with its slot, the next without
    assert active[:2] == [2, 1] and set(active[2:]) <= {1}
    assert stats["kv_pages_in_use"] == 0


def _hold_first_token(monkeypatch, nth):
    """Make the ``nth`` read of a first token block until the test lets it
    go: (it is being waited for, let it go)."""
    reading, go = threading.Event(), threading.Event()
    wait = PendingFirstToken.wait
    reads = []

    def held(self):
        if self.token is None:
            reads.append(self)
            if len(reads) == nth:
                reading.set()
                assert go.wait(timeout=60)
        return wait(self)

    monkeypatch.setattr(PendingFirstToken, "wait", held)
    return reading, go


@pytest.mark.parametrize("what", ["cancel", "shutdown"])
def test_a_stream_ended_from_outside_while_its_first_token_is_pending(
        params, monkeypatch, what):
    """The client cancels, or the model is unloaded, while the scheduler
    waits for an admission's first token behind the dispatch it issued: the
    token is dropped (cancel) or delivered and the stream then ended as
    unloaded (shutdown), nobody hangs, and every page comes back."""
    eng = make_engine(params, **dict(PAGED, prefix_cache=False))
    b = ContinuousBatcher(eng)
    reading, go = _hold_first_token(monkeypatch, 2)  # the late request's
    try:
        running = b.submit(Request(prompt_ids=[3, 17, 91], max_tokens=400,
                                   temperature=0.0))
        it = iter(running)
        next(it)
        late = b.submit(Request(prompt_ids=[9, 8, 7, 6, 5], max_tokens=50,
                                temperature=0.0))
        assert reading.wait(timeout=60)
        assert late._live.first_token_at == 0.0
        if what == "cancel":
            late.cancel()
            go.set()
            assert late.tokens() == [] and not late.aborted
            running.cancel()
            list(it)
            b.shutdown()
            assert b.cancellations == 2
        else:
            closer = threading.Thread(target=b.shutdown)
            closer.start()
            go.set()
            closer.join(timeout=60)
            assert not closer.is_alive()
            got = late.tokens()
            assert len(got) >= 1 and late.abort_reason == "model unloading"
            assert running.abort_reason == "model unloading"
            list(it)
        assert b._firsts == []
        assert eng.stats()["kv_pages_in_use"] == 0
        assert len(eng.free_slots()) == eng.num_slots
    finally:
        go.set()
        b.shutdown()
        eng.close()


def test_the_dispatch_behind_an_admission_runs_out_of_pages(params):
    """Three pages: the running stream holds two, the arrival's prompt of 32
    rows takes the third, and the dispatch issued behind its prefill cannot
    back row 33. The arrival's first token, pending when that dispatch was
    issued, is delivered; the failure surfaces at the next tick's consume
    and evicts it (the lower priority); the other stream runs to its end and
    no page is lost."""
    running = [dict(prompt_ids=list(range(1, 31)), max_tokens=30,
                    temperature=0.0, priority=1)]
    late = dict(prompt_ids=list(range(40, 72)), max_tokens=20, temperature=0.0)
    outs, stats = serve_with_arrival(
        params, True, running, late, at=4,
        engine_kw=dict(num_slots=2, paged_pool_rows=96, page_size=32,
                       prefix_cache=False),
        batcher_kw=dict(prefill_chunk=0))
    assert stats["aborted"][0] == "" and len(outs[0]) == 30
    assert "evicted" in stats["aborted"][1]
    assert len(outs[1]) >= 1
    assert stats["pool_evictions"] == 1
    assert stats["admissions_read_after_dispatch"] == 2
    assert stats["kv_pages_in_use"] == 0
