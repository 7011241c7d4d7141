"""A stream's retirement and the next admission's set-up, off the window
between two decode dispatches (ISSUE 41).

Three guarantees under test:
  * a prompt is hashed once, on the thread that submits it: the pool keeps
    the hashes it routes on, ``ContinuousBatcher.submit`` makes them for
    callers that come without, the engine's prefix match takes them where
    they are its own truncation's and hashes again where not;
  * an admission's operands reach the graphs as numpy values and the
    history backfill of a matched prefix is issued only where a history is
    kept: streams are token for token what the engine serves alone;
  * a retirement's engine half (page frees, device resets, the timeline's
    close, ``_END``) runs behind the dispatch the pipelined tick has just
    handed over, never in front of it, and no new tenant takes the slot
    before its pages are back.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model as M
from aios_tpu.engine import batching, paged
from aios_tpu.engine.batching import _END, ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import ChunkedPrefill, TPUEngine
from aios_tpu.serving.config import ServingConfig
from aios_tpu.serving.pool import ReplicaPool

P = 16  # rows of a page
PAGED = dict(num_slots=3, max_context=128, paged_pool_rows=4 * 128,
             page_size=P, cache_dtype=jnp.float32)
SHARED = (np.arange(1, 41) % 250 + 1).tolist()  # two pages and a half


@pytest.fixture(scope="module")
def params():
    return M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_engine(params, **kw):
    return TPUEngine(TINY_TEST, params, **dict(PAGED, **kw))


@pytest.fixture()
def hashed(monkeypatch):
    """Every call of ``paged.chain_hashes``: (thread name, blocks)."""
    calls = []
    real = paged.chain_hashes

    def counting(token_ids, page_size, num_blocks):
        calls.append((threading.current_thread().name, num_blocks))
        return real(token_ids, page_size, num_blocks)

    monkeypatch.setattr(paged, "chain_hashes", counting)
    return calls


# -- (1) hashed once, by the thread that submits -----------------------------


def test_a_prompt_is_hashed_once_from_the_pools_submit_to_the_index(params, hashed):
    """Two replicas, so that the router probes both on the hashes: from
    ``ReplicaPool.submit`` through routing, the batcher's queue, the prefix
    match and the registration, ``chain_hashes`` runs once a request, on the
    caller's thread, and the second request's hit is served on them."""
    engines = [make_engine(params), make_engine(params)]
    pool = ReplicaPool(
        "tiny", engines,
        lambda e: ContinuousBatcher(e, chunk_steps=2, prefill_chunk=32),
        ServingConfig(),
    )
    try:
        me = threading.current_thread().name
        first = pool.submit(Request(prompt_ids=SHARED + [9, 8], max_tokens=6,
                                    temperature=0.0)).tokens()
        assert len(first) == 6
        assert hashed == [(me, 2)]  # (42 - 1) // 16 blocks, hashed here
        second = pool.submit(Request(prompt_ids=SHARED + [7], max_tokens=6,
                                     temperature=0.0)).tokens()
        assert len(second) == 6
        assert hashed == [(me, 2), (me, 2)]
        stats = pool.stats()
        assert stats["prefix_rows_reused"] == 2 * P  # the hit, on those hashes
        assert stats["admissions_prehashed"] == stats["admissions"] == 2
    finally:
        pool.shutdown()
        for e in engines:
            e.close()


def test_the_batchers_submit_hashes_for_a_caller_that_comes_without(params, hashed):
    """A direct caller (tests, bench.py, the fleet paths): ``submit`` hashes
    on the caller's thread, the scheduler's thread never does."""
    eng = make_engine(params)
    b = ContinuousBatcher(eng, chunk_steps=2, prefill_chunk=32)
    try:
        for tail in ([9, 8], [7]):
            req = Request(prompt_ids=SHARED + tail, max_tokens=4, temperature=0.0)
            assert req.prefix_hashes is None
            assert len(b.submit(req).tokens()) == 4
            assert req.prefix_hashes == eng.prompt_hashes(req.prompt_ids)
        assert [name for name, _ in hashed[:2]] == [threading.current_thread().name] * 2
        assert not any(name.startswith("continuous-batcher") for name, _ in hashed)
        assert eng.stats()["admissions_prehashed"] == b.stats()["admissions"] == 2
        assert eng.stats()["prefix_rows_reused"] == 2 * P
    finally:
        b.shutdown()
        eng.close()


def test_hashes_of_another_truncation_are_not_trusted(params, hashed):
    """The rule lives in ``prompt_hashes``: the ``(rows - 1) // page`` blocks
    of the prompt's last ``max_context - 1`` ids. Hashes computed over
    another context's truncation, another page size or another prompt's
    length are hashed again (and not counted as the submitter's); a prompt
    past the context is matched and registered on ITS last rows."""
    eng = make_engine(params)
    long = (np.arange(0, 200) % 250 + 1).tolist()  # past the 127 rows kept
    mine = eng.prompt_hashes(long)
    assert (mine.rows, mine.page_size, len(mine.hashes)) == (127, P, 7)
    assert mine.hashes == paged.chain_hashes(long[-127:], P, 7)
    assert eng.prompt_hashes(long, mine) is mine  # its own: taken as it is
    # the same count of blocks, over other rows: a context one row shorter
    theirs = paged.PromptHashes(126, P, paged.chain_hashes(long[-126:], P, 7))
    assert len(theirs.hashes) == 7 and theirs.hashes != mine.hashes
    assert eng.prompt_hashes(long, theirs) == mine
    # another page size
    wrong_page = paged.PromptHashes(127, 32, theirs.hashes)
    assert eng.prompt_hashes(long, wrong_page) == mine
    del hashed[:]
    first = eng.prefill_async(0, long, given=theirs).wait()
    assert hashed == [("MainThread", 7)]  # hashed again, under the lock
    assert eng.admissions_prehashed == 0
    eng.release(0)
    del hashed[:]
    again = eng.prefill_async(1, long, given=mine).wait()
    assert hashed == [] and eng.admissions_prehashed == 1
    assert again == first
    # the second admission found what the first had registered under the
    # right hashes: all seven blocks (15 tail rows stay to be computed)
    assert eng.stats()["prefix_rows_reused"] == 7 * P
    eng.close()


# -- (2) the same streams ----------------------------------------------------

ADMISSIONS = {
    # (prompt, what the first request's prompt had registered)
    "whole_miss": [9, 8, 7, 6],
    "whole_hit": SHARED[:36] + [5, 4],
    "chunked_miss": (np.arange(3, 83) % 250 + 1).tolist(),
    "chunked_hit": SHARED + (np.arange(7, 67) % 250 + 1).tolist(),
}


@pytest.mark.parametrize("given", ["handed_in", "none", "stale"])
@pytest.mark.parametrize("admission", sorted(ADMISSIONS))
def test_streams_are_what_the_engine_serves_alone(params, admission, given):
    """Greedy, over a prefix hit and a miss, a whole-prompt and a chunked
    admission, with the submitter's hashes, with none (the engine hashes
    under its lock, as it did) and with another truncation's: the batcher's
    streams are token for token ``engine.generate``'s on a fresh engine."""
    prompt = ADMISSIONS[admission]
    alone = make_engine(params, prefix_cache=False)
    want_first = alone.generate(SHARED + [3], max_new_tokens=5, temperature=0.0)
    want = alone.generate(prompt, max_new_tokens=9, temperature=0.0)
    alone.close()
    eng = make_engine(params)
    b = ContinuousBatcher(eng, chunk_steps=2, prefill_chunk=32)
    if given == "none":
        # nobody's hashes reach the engine: it hashes under its lock
        for name in ("prefill_async", "start_chunked_prefill"):
            def bare(*a, _real=getattr(eng, name), **k):
                return _real(*a, **dict(k, given=None))
            setattr(eng, name, bare)
    try:
        first = b.submit(Request(prompt_ids=SHARED + [3], max_tokens=5,
                                 temperature=0.0)).tokens()
        req = Request(prompt_ids=prompt, max_tokens=9, temperature=0.0)
        if given == "stale":
            req.prefix_hashes = paged.PromptHashes(len(prompt), 2 * P, [b"x"])
        got = b.submit(req).tokens()
    finally:
        b.shutdown()
    assert first == want_first and got == want
    stats = eng.stats()
    hit = admission.endswith("_hit")
    assert stats["prefix_rows_reused"] == (2 * P if hit else 0)
    # another truncation's are made again by ``submit``, on its caller's thread
    assert stats["admissions_prehashed"] == (0 if given == "none" else 2)
    eng.close()


def test_a_history_that_is_kept_is_still_backfilled_and_read(params):
    """``track_history`` true: a matched prefix's ids are written into the
    history (operands as numpy values, the same program), the n-gram
    proposer reads them, and a speculative stream over a prefix hit is the
    plain stream. False: the backfill is not issued, and counted."""
    prompt = SHARED + SHARED[:20]  # repeats itself: drafts get accepted
    out = {}
    for track in (True, False):
        eng = make_engine(params, track_history=track)
        eng.generate(SHARED + [3], max_new_tokens=2, temperature=0.0)  # registers
        before = np.asarray(eng.state["history"])[1].copy()
        first = eng.prefill_async(1, prompt).wait()
        stats = eng.stats()
        assert stats["prefix_rows_reused"] == 2 * P
        assert stats["history_backfills_skipped"] == (0 if track else 1)
        row = np.asarray(eng.state["history"])[1]
        if track:
            assert row[: len(prompt)].tolist() == prompt  # prefix AND tail
            tokens, counts = eng.spec_step(4, draft_len=3, ngram=2)
            served = [first] + [int(t) for r in range(4)
                                for t in tokens[r, 1, : counts[r, 1]]]
            out[track] = served
        else:
            # nothing reads it: the matched rows were left as they were
            assert row[: 2 * P].tolist() == before[: 2 * P].tolist()
            with pytest.raises(ValueError, match="track_history"):
                eng.spec_step(4, draft_len=3, ngram=2)
            out[track] = [first] + [int(eng.step(1)[0, 1]) for _ in range(12)]
        eng.close()
    n = min(len(out[True]), len(out[False]))
    assert n >= 5 and out[True][:n] == out[False][:n]


@pytest.mark.parametrize("room", ["little", "plenty"])
def test_a_chunk_s_operands_are_placed_behind_the_hand_over_before_it(
        params, monkeypatch, room):
    """The pipelined loop with a chunked admission beside a running stream.
    Where the scheduler has little room a tick (its last wait for a
    dispatch's tokens was under ``STAGE_UNDER_SLACK_S``) every chunk is
    issued on operands ``stage`` put on the device one tick earlier, behind
    that tick's hand-over (``step_async``), so that the issue itself places
    only the slot's table; where it has plenty the issue itself stages
    them first thing, before its lock; the stream is the same."""
    monkeypatch.setattr(batching, "STAGE_UNDER_SLACK_S",
                        1e9 if room == "little" else 0.0)
    prompt = ADMISSIONS["chunked_miss"]  # 80 rows: chunks of 32, 32, 16
    alone = make_engine(params, prefix_cache=False)
    want = alone.generate(prompt, max_new_tokens=6, temperature=0.0)
    alone.close()
    eng = make_engine(params, prefix_cache=False)
    log = []
    real_async, real_chunk_fn = eng.step_async, eng._chunk_fn

    def step_async(n=1):
        log.append("hand_over")
        return real_async(n)

    def chunk_fn(bucket, final):
        fn = real_chunk_fn(bucket, final)

        def call(params_, state, *ops):
            log.append(("chunk", [isinstance(o, jax.Array) for o in ops]))
            return fn(params_, state, *ops)

        return call

    eng.step_async, eng._chunk_fn = step_async, chunk_fn
    real_stage = ChunkedPrefill.stage

    def stage(self):
        real_stage(self)
        log.append("stage")

    b = ContinuousBatcher(eng, chunk_steps=2, prefill_chunk=32, pipeline=True)
    try:
        ChunkedPrefill.stage = stage
        running = b.submit(Request(prompt_ids=[3, 17, 91], max_tokens=60,
                                   temperature=0.0))
        next(iter(running))  # live: every tick from here hands a dispatch over
        got = b.submit(Request(prompt_ids=prompt, max_tokens=6,
                               temperature=0.0)).tokens()
        running.cancel()
    finally:
        ChunkedPrefill.stage = real_stage
        b.shutdown()
    assert got == want
    chunks = [i for i, what in enumerate(log) if isinstance(what, tuple)]
    assert len(chunks) == 3
    for i in chunks:
        placed = log[i][1]
        # staged: ids, slot, start (and the final chunk's four scalars) are
        # on the device already; the table row is the call's own placement
        assert all(placed[:-1]) and not placed[-1], log[i]
        before = [w for w in log[:i] if not isinstance(w, tuple)]
        if room == "little":
            # placed behind the hand-over of the tick before; the issue's
            # own call of ``stage`` finds them there
            assert before[-3:] == ["hand_over", "stage", "stage"], log[: i + 1]
        else:
            assert before[-3:-1] != ["hand_over", "stage"], log[: i + 1]
    eng.close()


def test_staged_operands_outlive_a_chunk_that_found_no_page(params):
    """``step_async`` raises PoolExhausted before any state is touched; the
    operands staged for that chunk serve the retry, and a chunk nobody
    staged builds its own."""
    eng = make_engine(params, prefix_cache=False)
    prompt = ADMISSIONS["chunked_miss"]
    want = eng.prefill(0, prompt, temperature=0.0)
    pc = eng.start_chunked_prefill(1, prompt, chunk=32)
    pc.stage()
    staged = pc._staged
    real_ensure = eng.allocator.ensure

    def no_page(slot, rows):
        raise paged.PoolExhausted(2, 0)

    eng.allocator.ensure = no_page
    with pytest.raises(paged.PoolExhausted):
        pc.step_async()
    assert pc._staged is staged and pc.pos == 0
    eng.allocator.ensure = real_ensure
    assert pc.step_async() is None and pc._staged is None and pc.pos == 32
    assert pc.step_async() is None  # nobody staged: the issue does, first thing
    pc.stage()
    assert pc.step_async().wait() == want
    pc.stage()  # done: nothing to place
    assert pc._staged is None
    eng.close()


# -- (3) a retirement runs behind the hand-over -------------------------------


class _WatchedLock:
    """The engine lock with every acquisition noted: (thread kind, what the
    scheduler was doing). No sleeps: the order is read from the record."""

    def __init__(self, lock, log):
        self._lock, self.log = lock, log

    def __enter__(self):
        self._lock.acquire()
        name = threading.current_thread().name
        self.log.append("worker" if name.startswith("decode-dispatch") else
                        "scheduler" if name.startswith("continuous-batcher")
                        else "other")

    def __exit__(self, *exc):
        self._lock.release()


def test_a_retirement_takes_the_engine_lock_behind_the_dispatch_handed_over(params):
    """The pipelined loop, one stream that ends while another runs: between
    the hand-over of a dispatch (``step_async``) and the retirement's
    ``release_pages`` the dispatch worker has taken the engine lock; and
    when the consumer sees ``_END`` the slot is free, its pages are back
    and the counters are final."""
    eng = make_engine(params, prefix_cache=False)
    log = []
    eng._lock = _WatchedLock(eng._lock, log)
    real_async, real_release = eng.step_async, eng.release_pages

    def step_async(n=1):
        log.append("hand_over")
        return real_async(n)

    def release_pages(slot):
        log.append(f"release_pages:{slot}")
        real_release(slot)

    eng.step_async, eng.release_pages = step_async, release_pages
    b = ContinuousBatcher(eng, chunk_steps=2, pipeline=True)
    seen = {}
    real_put = None
    try:
        long = b.submit(Request(prompt_ids=[3, 17, 91], max_tokens=40,
                                temperature=0.0))
        short = b.submit(Request(prompt_ids=[9, 8, 7, 6, 5], max_tokens=7,
                                 temperature=0.0))
        real_put = short._live.out_q.put

        def put(item, *a, **k):
            if item is _END:
                slot = short._live.slot
                seen.update(
                    free=slot in eng.free_slots(),
                    pages=eng.allocator.slot_pages_resident(slot),
                    completed=b.completed,
                    held=[l.slot for l in b._retired],
                )
            return real_put(item, *a, **k)

        short._live.out_q.put = put
        assert len(short.tokens()) == 7
        assert len(long.tokens()) == 40
    finally:
        b.shutdown()
    assert seen == dict(free=True, pages=0, completed=1, held=[])
    releases = [i for i, what in enumerate(log) if what.startswith("release_pages")]
    assert len(releases) == 2
    for i in releases:
        handed = max(j for j in range(i) if log[j] == "hand_over")
        between = log[handed + 1 : i]
        # the worker held the lock before the retirement asked for it, and
        # the scheduler took it for nothing else in between
        assert "worker" in between, log[handed : i + 2]
        assert "scheduler" not in between, log[handed : i + 2]
        assert log[i + 1] == "scheduler"  # the release itself
    stats = b.stats()
    assert stats["retirements_behind_dispatch"] == 2
    assert stats["phase_batcher.retire_count"] == 2
    assert eng.stats()["kv_pages_in_use"] == 0
    eng.close()


def test_a_retirement_with_no_dispatch_pending_runs_at_once(params):
    """The synchronous loop, and a stream ended from outside: nothing to run
    behind, so the slot's pages go back where the stream ends, as before,
    and the counter does not count it."""
    eng = make_engine(params, prefix_cache=False)
    b = ContinuousBatcher(eng, chunk_steps=2, pipeline=False)
    try:
        assert len(b.generate([3, 17, 91], max_tokens=6, temperature=0.0)) == 6
        held = b.submit(Request(prompt_ids=[9, 8, 7], max_tokens=10_000,
                                temperature=0.0))
        it = iter(held)
        next(it)
        held.cancel()
        list(it)
    finally:
        b.shutdown()
    stats = b.stats()
    assert stats["retirements_behind_dispatch"] == 0
    assert stats["phase_batcher.retire_count"] == 0  # inside emit and reap, as before
    assert b.completed == 1 and b.cancellations == 1
    assert eng.stats()["kv_pages_in_use"] == 0
    assert len(eng.free_slots()) == eng.num_slots
    # the device's half of both releases (one program each: _reset_slot)
    assert not np.asarray(eng.state["active"]).any()
    assert int(np.asarray(eng.state["lengths"])[0]) == 0  # both ran in slot 0
    eng.close()


def test_a_freed_slot_takes_no_new_tenant_before_its_pages_are_back(params):
    """One slot, two requests: the second waits for the slot the first
    holds. When the first ends its slot reads free on the host at once, yet
    the batcher offers it to nobody until the pages are back: at every
    admission's engine call the slot holds no page of its last tenant, and
    the second stream is what it is alone."""
    alone = make_engine(params, num_slots=1, prefix_cache=False)
    want = alone.generate([9, 8, 7, 6, 5], max_new_tokens=6, temperature=0.0)
    alone.close()
    eng = make_engine(params, num_slots=1, prefix_cache=False)
    b = ContinuousBatcher(eng, chunk_steps=2, pipeline=True)
    resident, offered = [], []
    real_prefill, real_retire = eng.prefill_async, eng.retire

    def prefill_async(slot, ids, *a, **k):
        resident.append(eng.allocator.slot_pages_resident(slot))
        return real_prefill(slot, ids, *a, **k)

    def retire(slot):
        real_retire(slot)
        # free on the host, its pages still its own: not offered
        offered.append((slot in eng.free_slots(), list(b._free_slots()),
                        eng.allocator.slot_pages_resident(slot)))

    eng.prefill_async, eng.retire = prefill_async, retire
    try:
        first = b.submit(Request(prompt_ids=[3, 17, 91], max_tokens=5,
                                 temperature=0.0))
        second = b.submit(Request(prompt_ids=[9, 8, 7, 6, 5], max_tokens=6,
                                  temperature=0.0))
        assert len(first.tokens()) == 5
        assert second.tokens() == want
    finally:
        b.shutdown()
    assert resident == [0, 0]
    assert offered[0][0] is True and offered[0][1] == [] and offered[0][2] >= 1
    assert eng.stats()["kv_pages_in_use"] == 0
    eng.close()


def test_a_failing_tick_still_ends_the_streams_it_had_retired(params):
    """The scheduler fails between a retirement's host half and its engine
    half: the stream that had ended ends WITHOUT an abort reason, its pages
    go back, and the streams still running end as aborted."""
    eng = make_engine(params, prefix_cache=False)
    b = ContinuousBatcher(eng, chunk_steps=2, pipeline=True)
    real = b._settle_retired
    fired = []

    def settle():
        if b._retired and not fired:
            fired.append(len(b._retired))
            raise RuntimeError("synthetic failure behind the hand-over")
        real()

    b._settle_retired = settle
    try:
        long = b.submit(Request(prompt_ids=[3, 17, 91], max_tokens=200,
                                temperature=0.0))
        short = b.submit(Request(prompt_ids=[9, 8, 7], max_tokens=5,
                                 temperature=0.0))
        assert len(short.tokens()) == 5 and not short.aborted
        long.tokens()
        assert long.aborted and "scheduler failed" in long.abort_reason
        assert isinstance(b.last_error, RuntimeError) and fired == [1]
    finally:
        b.shutdown()
    assert b._retired == [] and eng.stats()["kv_pages_in_use"] == 0
    eng.close()
