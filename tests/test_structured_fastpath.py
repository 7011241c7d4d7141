"""Structured-agent fast path (ISSUE 7): compressed-FSM jump-ahead
decoding + radix-tree prefix cache.

Four guarantees under test:
  * forced-run collapse: chains of singleton automaton states (the mask
    admits exactly one token) emit in ONE multi-token jump dispatch, and
    greedy constrained streams are token-identical jump-ahead ON vs OFF
    while the dispatch count drops >= 2x on schema-forced workloads;
  * no compile after warmup: the jump graphs are AOT-built behind the
    readiness gate (run-length buckets), extending the PR 6 invariant to
    the constrained path;
  * radix-index invariants: no page is ever simultaneously free-listed
    and tree-referenced — across leaf-LRU eviction, pool-pressure
    reclaim, host-tier spill, and restore re-insertion — and a prompt
    diverging MID-CHAIN from a cached prompt still hits the shared
    prefix (partial-node overlap, node splitting);
  * spec auto-disable: a collapsed EWMA draft-acceptance ratio suspends
    speculation (plain decode serves) and re-probes after the window.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import jsonmode, jsonschema
from aios_tpu.engine import model as M
from aios_tpu.engine import paged
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.engine.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def params():
    return M.init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


# enum-heavy: almost every position is grammar-forced once the first byte
# of each enum/bool disambiguates — the orchestrator tool-call shape
TOOL_SCHEMA = {
    "type": "object",
    "properties": {
        "tool": {
            "type": "string",
            "enum": ["read_file", "write_file", "list_dir"],
        },
        "path": {"type": "string", "enum": ["slash_tmp", "slash_etc"]},
        "recursive": {"type": "boolean"},
    },
    "required": ["tool", "path", "recursive"],
}

# free-form string + nested subtree: forced runs interleave with sampled
# content, exercising the mixed run/step cadence
MIXED_SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string"},
        "count": {"type": "integer"},
    },
    "required": ["name", "count"],
}


def _token_table():
    tok = ByteTokenizer()
    return tok, jsonmode.token_bytes_table(tok, TINY_TEST.vocab_size)


# -- forced-run detection (host-side, no engine) ----------------------------


def test_forced_run_detection_schema_key_literal():
    """After '{"r' the key trie has one candidate ('recursive'), so the
    whole remaining literal + '":' is a singleton chain; the run stops at
    the boolean value (two admissible openers)."""
    tok, table = _token_table()
    cache = jsonschema.SchemaMaskCache(
        table, tok.eos_id, TOOL_SCHEMA, compact=True
    )
    c = jsonmode.JsonConstraint(cache)
    for b in b'{"r':
        tok_id = b  # ByteTokenizer: token id == byte value
        c.advance(tok_id)
    assert not c.failed
    run = c.forced_run(32)
    assert bytes(run) == b'ecursive":'
    # every run token really is the unique admissible one
    probe = jsonmode.JsonConstraint(cache)
    for b in b'{"r':
        probe.advance(b)
    for t in run:
        row = probe.mask_row()
        assert np.flatnonzero(row == 0.0).tolist() == [t]
        probe.advance(t)


def test_forced_run_respects_budget_gate():
    """When the budget-feasibility gate would alter the dispatched row,
    run detection stops — the per-step path owns the closing walk."""
    tok, table = _token_table()
    cache = jsonschema.SchemaMaskCache(
        table, tok.eos_id, TOOL_SCHEMA, compact=True
    )
    c = jsonmode.JsonConstraint(cache)
    for b in b'{"r':
        c.advance(b)
    assert c.forced_run(32, remaining=3) == []
    long_enough = c.forced_run(32, remaining=256)
    assert bytes(long_enough) == b'ecursive":'


def test_compact_mode_rejects_structural_whitespace_only():
    """compact=True outlaws inter-element whitespace but keeps spaces
    inside string content (enum values / keys may contain them)."""
    st = jsonmode.start_state()
    assert jsonmode.run_bytes(st, b'{ "a": 1 }') is not None
    assert jsonmode.run_bytes(st, b'{ "a":1}', compact=True) is None
    assert jsonmode.run_bytes(st, b'{"a":1}', compact=True) is not None
    assert jsonmode.run_bytes(st, b'{"a":"x y"}', compact=True) is not None


# -- jump-ahead through the continuous batcher ------------------------------


def _run_constrained(params, jump, reqs, *, engine_kw=None):
    tok = ByteTokenizer()
    kw = dict(num_slots=4, max_context=128, cache_dtype=jnp.float32)
    kw.update(engine_kw or {})
    eng = TPUEngine(TINY_TEST, params, **kw)
    eng.warmup(step_sizes=(2, 4), prefill_chunk=0, masked_step=True)
    b = ContinuousBatcher(
        eng, chunk_steps=4, admit_chunk_steps=2, tokenizer=tok,
        jump_ahead=jump,
    )
    try:
        handles = [b.submit(Request(**r)) for r in reqs]
        outs = [h.tokens() for h in handles]
        return outs, dict(eng.stats())
    finally:
        b.shutdown()
        eng.close()


def _schema_req(i, schema=TOOL_SCHEMA, **kw):
    tok = ByteTokenizer()
    req = dict(
        prompt_ids=tok.encode(f"emit json {i}"), max_tokens=64,
        temperature=0.0, stop_ids=(tok.eos_id,), json_schema=schema,
    )
    req.update(kw)
    return req


def test_jump_ahead_greedy_identity_and_dispatch_reduction(params):
    """Two waves through ONE off/on arm pair (warmup is the expensive
    part on this container):

    * wave 1 — greedy constrained decode with jump-ahead ON emits
      token-identical streams to OFF: schema-forced, generic json_mode,
      and a co-resident unconstrained stream;
    * wave 2 — the acceptance bar: >= 2x fewer engine dispatches on a
      schema-forced workload (dispatch counters, deterministic on CPU).
    """
    tok = ByteTokenizer()
    arms = {}
    try:
        for jump in (False, True):
            eng = TPUEngine(TINY_TEST, params, num_slots=4,
                            max_context=128, cache_dtype=jnp.float32)
            eng.warmup(step_sizes=(2, 4), prefill_chunk=0,
                       masked_step=True)
            arms[jump] = (eng, ContinuousBatcher(
                eng, chunk_steps=4, admit_chunk_steps=2, tokenizer=tok,
                jump_ahead=jump,
            ))
        # -- wave 1: mixed-batch token identity
        reqs = [
            _schema_req(0),
            _schema_req(1, schema=MIXED_SCHEMA),
            dict(prompt_ids=tok.encode("emit json 2"), max_tokens=48,
                 temperature=0.0, stop_ids=(tok.eos_id,), json_mode=True),
            dict(prompt_ids=tok.encode("plain"), max_tokens=20,
                 temperature=0.0),
        ]
        outs = {}
        for jump, (eng, b) in arms.items():
            handles = [b.submit(Request(**dict(r))) for r in reqs]
            outs[jump] = [h.tokens() for h in handles]
        assert outs[True] == outs[False]
        assert arms[True][0].jump_dispatches > 0
        for out in outs[True][:2]:
            parsed = json.loads(
                tok.decode([t for t in out if t != tok.eos_id])
            )
            assert isinstance(parsed, dict)
        # -- wave 2: schema-forced dispatch reduction
        steps, waves = {}, {}
        for jump, (eng, b) in arms.items():
            before = eng.decode_steps
            handles = [
                b.submit(Request(**_schema_req(10 + i))) for i in range(2)
            ]
            waves[jump] = [h.tokens() for h in handles]
            steps[jump] = eng.decode_steps - before
        assert waves[True] == waves[False]
        assert steps[False] >= 2 * steps[True], steps
        s_on = arms[True][0].stats()
        # the jump path emitted the bulk of the forced tokens
        assert s_on["jump_tokens"] >= s_on["jump_dispatches"] * 2
    finally:
        for eng, b in arms.values():
            b.shutdown()
            eng.close()


@pytest.mark.slow
def test_jump_ahead_sampled_schema_still_conforms(params):
    """Sampled constrained streams under jump-ahead stay schema-exact
    (forced tokens are sampler-independent; the sampled remainder draws
    a shifted key chain — the documented caveat)."""
    reqs = [_schema_req(0, temperature=0.9, top_p=0.9)]
    on, s_on = _run_constrained(params, True, reqs)
    tok = ByteTokenizer()
    parsed = json.loads(
        tok.decode([t for t in on[0] if t != tok.eos_id])
    )
    assert parsed["tool"] in TOOL_SCHEMA["properties"]["tool"]["enum"]
    assert parsed["path"] in TOOL_SCHEMA["properties"]["path"]["enum"]
    assert isinstance(parsed["recursive"], bool)
    assert s_on.get("jump_dispatches", 0) > 0


@pytest.mark.slow
def test_jump_no_compile_after_warmup(params):
    """PR 6 invariant extended to the jump path: warmup(masked_step=True)
    AOT-builds the run-length-bucketed jump graphs, so a full constrained
    generation — including prefix-hit resubmission — compiles nothing."""
    tok = ByteTokenizer()
    eng = TPUEngine(
        TINY_TEST.scaled(max_context=512), params, num_slots=2,
        max_context=512, cache_dtype=jnp.float32,
        paged_pool_rows=512, page_size=32, prefix_host_bytes=32 << 20,
    )
    b = None
    try:
        eng.warmup(step_sizes=(1, 2, 8, 16), masked_step=True)
        b = ContinuousBatcher(
            eng, chunk_steps=4, admit_chunk_steps=2, tokenizer=tok,
            jump_ahead=True,
        )
        before = eng.stats()["xla_compiles"]
        prompt = tok.encode("the same long preamble " * 12)
        for _ in range(2):  # second pass rides the radix prefix hit
            h = b.submit(Request(
                prompt_ids=prompt, max_tokens=64, temperature=0.0,
                stop_ids=(tok.eos_id,), json_schema=TOOL_SCHEMA,
            ))
            out = h.tokens()
            assert json.loads(
                tok.decode([t for t in out if t != tok.eos_id])
            )
        stats = eng.stats()
        assert stats["jump_dispatches"] > 0
        assert stats["prefix_rows_reused"] > 0
        assert stats["xla_compiles"] == before, (
            "constrained serving compiled a graph warmup should cover"
        )
    finally:
        if b is not None:
            b.shutdown()
        eng.close()


# -- radix prefix index -----------------------------------------------------


def _chains(alloc, n_tokens, seed, page_size=4):
    rng = np.random.default_rng(seed)
    ids = [int(t) for t in rng.integers(1, 500, n_tokens)]
    hashes = paged.chain_hashes(ids, page_size, n_tokens // page_size)
    return ids, hashes


def test_radix_partial_node_overlap_and_split():
    """A chain diverging MID-NODE still scores (peek) and maps (match)
    its shared prefix; the node splits at the divergence point and both
    branches stay reachable."""
    alloc = paged.PageAllocator(32, 4, 2, 16)
    ix = paged.RadixPrefixIndex(alloc, max_pages=31)
    ids_a, hashes_a = _chains(alloc, 24, seed=1)  # 6 blocks
    pages_a = alloc.alloc_pages(6)
    ix.put(hashes_a, pages_a)
    # B shares 3 blocks (12 tokens) then diverges
    ids_b = ids_a[:12] + [int(t) + 1 for t in ids_a[12:]]
    hashes_b = paged.chain_hashes(ids_b, 4, 6)
    assert hashes_b[:3] == hashes_a[:3] and hashes_b[3] != hashes_a[3]
    assert ix.peek(hashes_b) == 3  # partial-node overlap credited
    assert ix.peek(hashes_a) == 6
    got = ix.match(hashes_b)
    assert got == pages_a[:3]
    # graft B's divergent tail; both chains fully resolvable afterwards
    pages_b = pages_a[:3] + alloc.alloc_pages(3)
    ix.put(hashes_b, pages_b)
    assert ix.peek(hashes_a) == 6
    assert ix.peek(hashes_b) == 6
    snap = ix.snapshot()
    assert len(snap) == 9
    assert set(snap.values()) == set(pages_a) | set(pages_b[3:])


def test_radix_leaf_lru_evicts_deepest_blocks_first():
    """Eviction past max_pages pops leaf TAILS of the coldest chain —
    the shared preamble survives while divergent tails age out — and the
    evicted pairs reach the spill hook before their references drop."""
    alloc = paged.PageAllocator(32, 4, 2, 16)
    ix = paged.RadixPrefixIndex(alloc, max_pages=8)
    spilled = []
    ix.spill = spilled.extend
    ids_a, hashes_a = _chains(alloc, 24, seed=2)  # 6 blocks
    pages_a = alloc.alloc_pages(6)
    ix.put(hashes_a, pages_a)
    for p in pages_a:
        alloc.decref(p)  # the tree holds the only reference now
    ids_b = ids_a[:8] + [int(t) + 1 for t in ids_a[8:]]
    hashes_b = paged.chain_hashes(ids_b, 4, 6)
    pages_b_tail = alloc.alloc_pages(4)
    ix.put(hashes_b, pages_a[:2] + pages_b_tail)
    for p in pages_b_tail:
        alloc.decref(p)
    # 6 + 4 = 10 entries > 8: two of chain A's DEEPEST blocks evicted
    # (B's tail was touched more recently)
    assert [h for h, _ in spilled] == [hashes_a[5], hashes_a[4]]
    snap = ix.snapshot()
    assert hashes_a[3] in snap and hashes_a[5] not in snap
    assert ix.peek(hashes_b) == 6  # B untouched
    # invariant: no page simultaneously free-listed and tree-referenced
    assert not set(alloc._free[0]) & set(snap.values())


def test_radix_reclaim_skips_shared_pages_bottom_up():
    """Pool-pressure reclaim only frees pages held ONLY by the tree, and
    only as tree suffixes — a live slot's mapped prefix pins its chain."""
    alloc = paged.PageAllocator(32, 4, 2, 16)
    ix = paged.RadixPrefixIndex(alloc, max_pages=31)
    _, hashes = _chains(alloc, 24, seed=3)
    pages = alloc.alloc_pages(6)
    ix.put(hashes, pages)
    for p in pages:
        alloc.decref(p)
    # a slot maps the first 4 blocks (refcount 2 there)
    alloc.map_shared(0, pages[:4])
    assert ix.reclaimable() == 2
    assert ix.reclaim(6) == 2  # only the unshared tail freed
    snap = ix.snapshot()
    assert set(snap.values()) == set(pages[:4])
    assert not set(alloc._free[0]) & set(snap.values())
    alloc.free_slot(0)
    assert ix.reclaim(6) == 4  # now poppable bottom-up
    assert ix.snapshot() == {}


def test_radix_engine_mid_chain_divergence_gets_prefix_hit(params):
    """Acceptance: two sequential requests sharing a long system prefix —
    the second hits the radix cache (prefix_rows_reused > 0) even though
    its prompt diverges mid-chain (inside the first prompt's cached
    run)."""
    eng = TPUEngine(
        TINY_TEST.scaled(max_context=512), params, num_slots=2,
        max_context=512, cache_dtype=jnp.float32,
        paged_pool_rows=512, page_size=32,
    )
    try:
        assert isinstance(eng.prefix_index, paged.RadixPrefixIndex)
        rng = np.random.default_rng(5)
        a = [int(t) for t in rng.integers(1, 500, 300)]
        eng.prefill(0, a, temperature=0.0)
        eng.release(0)
        before = eng.prefix_rows_reused
        b = a[:270] + [int(t) for t in rng.integers(1, 500, 40)]
        eng.prefill(0, b, temperature=0.0)
        eng.release(0)
        # blocks 0..7 (256 rows) are shared; divergence at row 270 is
        # inside block 8 — the radix walk still maps the shared run
        assert eng.prefix_rows_reused - before == 256
    finally:
        eng.close()


def test_radix_spill_restore_interleaving_invariants(params):
    """Pool-pressure reclaim spills tree entries to the host tier; a
    later resubmission restores them into FRESH pages and re-inserts
    them into the tree at the right position. At every checkpoint no
    page is simultaneously free-listed and (tree-referenced or mapped)
    — the test_host_tier reclaim/restore invariant, radix edition."""
    eng = TPUEngine(
        TINY_TEST.scaled(max_context=512), params, num_slots=2,
        max_context=512, cache_dtype=jnp.float32,
        paged_pool_rows=512, page_size=32, prefix_host_bytes=32 << 20,
    )

    def check_invariant():
        alloc = eng.allocator
        free = set(alloc._free[0])
        referenced = set(eng.prefix_index.snapshot().values())
        for s in range(eng.num_slots):
            used = int(alloc._blocks_used[s])
            referenced.update(int(p) for p in alloc.tables[s, :used])
        assert not free & referenced, (free, referenced)

    try:
        rng = np.random.default_rng(6)
        preamble = [int(t) for t in rng.integers(1, 500, 321)]  # 10 blocks
        eng.prefill(0, preamble, temperature=0.0)
        eng.release(0)
        check_invariant()
        pressure = [int(t) for t in rng.integers(1, 500, 480)]  # 15 blocks
        eng.prefill(0, pressure, temperature=0.0)  # reclaim -> spill
        check_invariant()
        eng.release(0)
        deadline = time.time() + 10
        while eng.host_store.spills < 2 and time.time() < deadline:
            time.sleep(0.02)
        eng.prefill(0, preamble, temperature=0.0)  # host-tier restore
        check_invariant()
        eng.release(0)
        stats = eng.stats()
        assert stats.get("host_tier_restores", 0) >= 1
        assert stats.get("prefix_rows_restored", 0) > 0
        # the restored segment is back in the TREE: a third submission
        # maps it straight from HBM (no further host-tier restores)
        restores = stats["host_tier_restores"]
        reused = eng.prefix_rows_reused
        eng.prefill(0, preamble, temperature=0.0)
        eng.release(0)
        check_invariant()
        assert eng.prefix_rows_reused > reused
        assert eng.stats()["host_tier_restores"] == restores
    finally:
        eng.close()


def test_radix_escape_hatch_selects_flat_index(params):
    eng = TPUEngine(
        TINY_TEST.scaled(max_context=512), params, num_slots=2,
        max_context=512, cache_dtype=jnp.float32,
        paged_pool_rows=512, page_size=32, prefix_radix=False,
    )
    try:
        assert type(eng.prefix_index) is paged.PrefixIndex
    finally:
        eng.close()


# -- speculative auto-disable -----------------------------------------------


def test_spec_ewma_autodisable_and_reprobe(params):
    """Deterministic unit drive of the EWMA machinery: zero acceptance
    under a positive floor suspends the proposer; an expired window
    grants a PROBE-COUNT-SEEDED re-probe — the floor re-judges only
    after SPEC_PROBE_DISPATCHES probe dispatches accumulate into a
    fresh cumulative average, so one unlucky probe can no longer
    re-disable instantly (the old zeroed-EWMA behavior)."""
    from aios_tpu.engine.batching import SPEC_PROBE_DISPATCHES

    eng = TPUEngine(TINY_TEST, params, num_slots=4, max_context=128,
                    cache_dtype=jnp.float32)
    b = ContinuousBatcher(eng, speculative=True, spec_min_accept=0.5)
    try:
        assert b.spec_proposers == ("ngram",)
        assert b._spec_active() and b._spec_proposer() == "ngram"
        # a dispatch where every live slot emitted exactly 1 token/round
        counts = np.ones((2, 4), np.int64)
        b._spec_measure("ngram", counts, {0: 2, 1: 2})
        assert b.spec_ewma["ngram"] == 0.0
        assert b.spec_autodisables == 1
        assert not b._spec_active()
        # window expiry -> fresh evidence, judged over the probe budget
        b._spec_off_until["ngram"] = time.monotonic() - 1
        assert b._spec_active()
        assert b.spec_ewma["ngram"] is None
        assert b._spec_probe_left["ngram"] == SPEC_PROBE_DISPATCHES
        # one BAD probe (the fix this knob exists for): verdict deferred
        b._spec_measure("ngram", counts, {0: 2, 1: 2})
        assert b._spec_active(), "one bad probe must not re-disable"
        full = np.full((2, 4), b.spec_draft_len + 1, np.int64)
        b._spec_measure("ngram", full, {0: 2, 1: 2})
        b._spec_measure("ngram", full, {0: 2, 1: 2})
        # cumulative probe average (0 + 1 + 1) / 3 clears the floor
        assert b._spec_active()
        assert abs(b.spec_ewma["ngram"] - 2.0 / 3.0) < 1e-9
        # rounds past a slot's retirement are EXCLUDED: slot 0 retired
        # after round 1, its round-2 zero-acceptance column must not
        # drag the (perfect) served acceptance down
        b.spec_ewma["ngram"] = None
        mixed = np.full((2, 4), b.spec_draft_len + 1, np.int64)
        mixed[1, 0] = 1  # unserved continuation round, nothing accepted
        b._spec_measure("ngram", mixed, {0: 1, 1: 2})
        assert b.spec_ewma["ngram"] == 1.0 and b._spec_active()
    finally:
        b.shutdown()
        eng.close()


def test_spec_autodisable_end_to_end_sampled(params):
    """Sampled slots never speculate, so their acceptance ratio is 0 by
    construction: with a floor set, the first spec dispatch suspends
    speculation and the stream finishes on the plain path."""
    eng = TPUEngine(TINY_TEST, params, num_slots=4, max_context=128,
                    cache_dtype=jnp.float32)
    b = ContinuousBatcher(
        eng, chunk_steps=4, admit_chunk_steps=2, speculative=True,
        spec_min_accept=0.25,
    )
    try:
        out = b.submit(Request(
            prompt_ids=[7, 2, 55], max_tokens=24, temperature=0.9,
        )).tokens()
        assert len(out) == 24  # the stream completed on the plain path
        assert b.spec_autodisables >= 1
        # re-arm the window so a slow container can't expire it (and
        # trigger a legitimate re-probe) before the next request drains
        b._spec_off_until["ngram"] = time.monotonic() + 300
        rounds = eng.spec_rounds
        out2 = b.submit(Request(
            prompt_ids=[9, 4, 33], max_tokens=12, temperature=0.9,
        )).tokens()
        assert len(out2) == 12
        assert eng.spec_rounds == rounds  # suspended: no spec dispatches
    finally:
        b.shutdown()
        eng.close()
