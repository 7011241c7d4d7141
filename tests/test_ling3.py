"""Linear-attention (KDA) layers that keep a recurrent state a slot, five to
one with latent-attention layers under a router that chooses by groups, on
the paged serving path: the program against the plain reference
(benchmark/archs/bailing_hybrid.py, which imports nothing of the program), at
a small size on the CPU, on seeded random weights. LOGITS are compared, never
tokens.

Two comparisons, each with the reason for its tolerance and a control that has
to exceed it: the SAME mathematics in float32 (the serving tree's matrices
dequantized: only the order of the sums differs, so a state kept in bfloat16
fails it by four orders), and the serving types (bfloat16 activations on int8
weights) against the float32 reference, which the int4 control fails.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from aios_tpu.engine import kda, latent, model, moe, paged  # noqa: E402
from aios_tpu.engine.batching import ContinuousBatcher  # noqa: E402
from aios_tpu.engine.config import ModelConfig, RopeParams  # noqa: E402
from aios_tpu.engine.engine import TPUEngine, refuse_for_state_kind  # noqa: E402
from aios_tpu.ops import kda as kda_ops  # noqa: E402
from benchmark.harness import reference  # noqa: E402
from benchmark.harness.manifest import load_file  # noqa: E402


def _arch(name):
    return load_file(os.path.join(REPO, "benchmark", "archs", f"{name}.py"),
                     "benchmark_arch")


A = _arch("bailing_hybrid")

# hidden 128; 1 dense KDA layer, then two periods K K M; 4 heads of 16 (KDA) and
# of 16 + 8 (MLA), latent rank 32; 16 experts in 4 groups of which 2 stay, top-2,
# beside a shared one
TYPES = ["kda"] + ["kda", "kda", "mla"] * 2
TINY = dict(
    num_hidden_layers=7, first_k_dense_replace=1, layer_types=TYPES,
    hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
    moe_shared_expert_intermediate_size=64, num_shared_experts=1,
    num_attention_heads=4, head_dim=16, short_conv_kernel_size=4,
    kda_lower_bound=-5, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=512, num_experts=16, num_experts_per_tok=2,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    rope_theta=10000.0, rms_norm_eps=1e-6, max_position_embeddings=128,
    assumed={"served_name": "tiny-ling"},
)
SEED = 2 ** 31 + 11
P = 16  # rows of a page
D = A.dims_of(TINY)
CFG = ModelConfig(**A.model_fields(TINY, 128))

# The same mathematics in float32: 2.4e-6 read (the order of the sums); the
# reference with its state rounded to bfloat16 after every row reads 0.06-0.13
# and with int4 matrices 0.77.
F32_TOL = 1e-4
# Positions whose least router margin (the reference's own, a logit's worth)
# is under this change experts under bfloat16 rounding: left out, as the
# benchmark's `correct` leaves them out.
MARGIN = 0.004
# bfloat16 activations on int8 weights against the float32 reference: 0.070-0.078
# read at a logit std of 0.24 (the decay gate's pre-activation is a bfloat16
# matmul result, and a channel that forgets slowly sums its error over the
# rows); the int4 control reads 0.77. Between, with room on both sides.
LOGIT_TOL = 0.25


@pytest.fixture(scope="module")
def params():
    # converted once, as the engine converts it at load (latent.serving_layout)
    return latent.serving_layout(A.build_params(D, SEED), CFG)[0]


def _dense(tree):
    """The serving tree with every matrix dequantized and every leaf float32."""
    if isinstance(tree, dict):
        if set(tree) == {"q", "s"}:
            return tree["q"].astype(jnp.float32) * tree["s"]
        return {k: _dense(v) for k, v in tree.items()}
    return tree.astype(jnp.float32)


@pytest.fixture(scope="module")
def params32(params):
    return _dense(params)


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, D.vocab, n)]


@pytest.fixture(scope="module")
def ref96():
    ids = _ids(96)
    out = reference.logits_for(A, D, SEED, [ids], [0],
                               ("float32", "int4", "state_bf16"), pad_to=0)
    return ids, out, out["router_margin"][0].min(-1) >= MARGIN


def _states(slots, dtype, fill=0.0):
    kind = paged.SlotStates(CFG.layers_of("kda"), slots, *CFG.kda_state_shapes)
    return (jnp.full(kind.state_shape, fill, jnp.float32),
            jnp.full(kind.tail_shape, fill, dtype))


def _pools(dtype, pages=24):
    return tuple(jnp.zeros((CFG.row_layers, pages, P, w), dtype)
                 for w in CFG.kv_row_dims)


# -- (a) the whole-prompt forward ---------------------------------------------------


def test_full_forward_matches_reference(params, params32, ref96):
    ids, ref, decided = ref96
    want = ref["float32"][0]
    assert decided.sum() > 50 and want.std() > 0.1
    with jax.default_matmul_precision("highest"):
        got32 = np.asarray(model.forward_full(params32, CFG, jnp.asarray([ids]),
                                              kernels=False))[0]
    assert np.abs(got32 - want).max() < F32_TOL
    got = np.asarray(model.forward_full(params, CFG, jnp.asarray([ids]), kernels=False))[0]
    assert np.abs(got - want)[decided].max() < LOGIT_TOL
    assert np.abs(ref["int4"][0] - want)[decided].max() > 2 * LOGIT_TOL
    for control in ("int4", "state_bf16"):  # a bfloat16 state fails the float32 bar
        assert np.abs(ref[control][0] - want).max() > 100 * F32_TOL


# -- (b) chunks, then decode, through the state pool and the latent pool ----------


def _serve(prm, dtype, ids):
    """Slot 0 admits 43 rows in chunks of 16 + 16 + 11 (the last padded to 16)
    over scattered pages, from states and tails left FULL of another tenant's
    values, then decodes 12 steps beside a dead slot 1."""
    states, (c_pool, r_pool) = _states(2, dtype, fill=7.0), _pools(dtype)
    tables = jnp.asarray([[3, 1, 4, 7, 0, 0, 0, 0], [2, 5, 9, 6, 0, 0, 0, 0]], jnp.int32)

    @jax.jit  # one trace for the three chunks, one for the twelve steps
    def chunk(prm, toks, start, c_pool, r_pool, states, n):
        return latent.prefill_chunk_paged(
            prm, CFG, toks, start, c_pool, r_pool, tables[0], states=states,
            slot=jnp.int32(0), n_valid=n)

    @jax.jit
    def step(prm, toks, lengths, c_pool, r_pool, states):
        return latent.decode_step_paged(
            prm, CFG, toks, lengths, c_pool, r_pool, tables, kernels=False,
            active=jnp.asarray([True, False]), states=states)

    rows = []
    for start, n in ((0, 16), (16, 16), (32, 11)):
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = ids[start:start + n]
        lg, c_pool, r_pool, *states, picks = chunk(
            prm, jnp.asarray(toks), jnp.int32(start), c_pool, r_pool, tuple(states),
            jnp.int32(n))
        assert int(picks[0]) == 16 * CFG.num_experts_per_tok * 6
        rows.extend(np.asarray(lg)[0][:n])
    dead = [np.asarray(s) for s in (states[0][:, 1], states[1][:, :, 1])]
    lengths = np.array([43, 0])
    for _ in range(12):
        lg, c_pool, r_pool, *states, _ = step(
            prm, jnp.asarray([ids[lengths[0]], 0]), jnp.asarray(lengths), c_pool,
            r_pool, tuple(states))
        rows.append(np.asarray(lg)[0])
        lengths[0] += 1
    assert np.array_equal(np.asarray(states[0][:, 1]), dead[0])  # a dead slot's state
    assert np.array_equal(np.asarray(states[1][:, :, 1]), dead[1])  # and tail: untouched
    return np.stack(rows)


def test_chunked_prefill_then_decode_through_the_state_pool(params, params32, ref96):
    ids, ref, decided = ref96
    want = ref["float32"][0][:55]
    with jax.default_matmul_precision("highest"):
        got32 = _serve(params32, jnp.float32, ids)
    assert np.abs(got32 - want).max() < F32_TOL  # chunks of 16, 16, 11: not 64s
    got = _serve(params, jnp.bfloat16, ids)
    assert np.abs(got - want)[decided[:55]].max() < LOGIT_TOL
    assert np.abs(ref["state_bf16"][0][:55] - want).max() > 100 * F32_TOL


# -- (c) the chunked form against the row-by-row recurrence ------------------------


def _rows(T, H, K, lower, seed=0):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    g = lower * jax.nn.sigmoid(3.0 + f(T, H, K))  # most channels near the bound
    return unit(f(T, H, K)), unit(f(T, H, K)), f(T, H, K), g, jax.nn.sigmoid(f(T, H)), f(H, K, K)


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("lower", [-5.0, -0.01])
def test_the_chunked_form_is_the_recurrence(lower, kernel):
    """150 rows (two sub-chunks of 64 and 22 rows, padded with identity rows to
    192) at log alpha near -5 a row, where e^{-G} of a whole sub-chunk would
    overflow, and near 0, where nothing is forgotten."""
    T, H, K = 150, 2, 16
    q, k, v, g, beta, s0 = _rows(T, H, K, lower)
    want_o, want_s = kda_ops.recurrence_reference(q, k, v, g, beta, s0)
    pad = lambda a: jnp.pad(a, [(0, -T % kda_ops.SUB)] + [(0, 0)] * (a.ndim - 1))  # noqa: E731
    o, s = kda_ops.chunked(*(pad(a) for a in (q, k, v, g, beta)), s0,
                           use_kernel=kernel, interpret=True)
    assert float(jnp.abs(want_o).max()) > 0.5
    assert float(jnp.abs(o[:T] - want_o).max()) < 1e-5  # float32 sums in another order
    assert float(jnp.abs(s - want_s).max()) < 1e-5  # the padded rows changed nothing


@pytest.mark.parametrize("kernel", [False, True])
def test_a_decode_step_updates_its_slots_in_place_and_no_other(kernel):
    B, H, K, L, S = 3, 4, 16, 2, 4
    q, k, v, g, beta, _ = _rows(B, H, K, -5.0, seed=1)
    pool = jnp.asarray(np.random.RandomState(2).randn(L, S + 1, H, K, K), jnp.float32)
    slots = jnp.asarray([2, 0, S])  # the third entry is dead: the scratch slot,
    beta = beta.at[2].set(0.0)  # handed an identity update
    g = g.at[2].set(0.0)
    want_o, want_s = kda_ops.recurrence_reference(q[:1], k[:1], v[:1], g[:1], beta[:1],
                                                  pool[1, 2])
    step = (lambda *a: kda_ops.kda_step(*a, interpret=True)) if kernel \
        else kda_ops.decode_step_reference
    o, new = step(q, k, v, g, beta, pool, jnp.int32(1), slots)
    assert float(jnp.abs(o[0] - want_o[0]).max()) < 1e-5
    assert float(jnp.abs(new[1, 2] - want_s).max()) < 1e-5
    untouched = np.ones((L, S + 1), bool)
    untouched[1, [2, 0]] = False
    assert np.array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])


# -- (d) the engine: a slot taken twice, prefix hits refused, counters -------------


def _engine(params, **kw):
    return TPUEngine(CFG, params, num_slots=2, max_context=128,
                     paged_pool_rows=3 * 128, page_size=P, **kw)


def test_a_slot_taken_twice_gives_its_second_tenant_what_a_fresh_slot_gives(params):
    ids = _ids(45, 3)
    eng = _engine(params)
    assert eng.state["k"].shape[0] == 2 and eng.state["kda_s"].shape[:2] == (5, 3)
    first = eng.generate(ids, max_new_tokens=10, temperature=0.0)
    ref = reference.logits_for(A, D, SEED, [ids + first], [len(ids) - 1],
                               ("float32",), pad_to=0)["float32"][0]
    assert reference.served_gaps(ref[:10], first).max() < LOGIT_TOL
    second = eng.generate(ids[:30], max_new_tokens=6, temperature=0.0)  # slot 0 again
    assert second == _engine(params).generate(ids[:30], max_new_tokens=6, temperature=0.0)
    stats = eng.stats()
    # 45 and 30 rows in chunks of 128 -> buckets 64 and 32, through 5 kda layers
    assert stats["kda_rows_prefill"] == (64 + 32) * 5
    assert stats["kda_rows_decode"] > 0 and stats["kv_state_slots"] == 0
    one = 5 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert eng.slot_states.slot_bytes == one and stats["kv_state_bytes"] == 3 * one
    # the second prompt shared one block of 16 rows with the first: refused
    assert stats["prefix_hits_refused_state"] == 1
    assert stats["prefix_rows_refused_state"] == 16 and "prefix_rows_reused" not in stats
    eng.generate(ids, max_new_tokens=2, temperature=0.0)  # (45 - 1) // 16 = 2 blocks
    assert eng.stats()["prefix_rows_refused_state"] == 16 + 32
    assert eng.prefix_index is None and not eng._prefill_fns  # every prompt in chunks
    assert eng.phases.counts["load.states"] == 1  # the arrays' allocation, named


@pytest.mark.parametrize("given", ["handed_in", "none"])
def test_the_state_kind_counts_its_refused_hits_on_the_submitters_hashes(params, given):
    """The state kind's branch of the match (``refused_prefixes``): no prefix
    index, so the pool's routing hashes nothing and the batcher's ``submit``
    is the one that hashes, on its caller's thread. With its hashes and
    with none (the engine hashes under its lock, as it did) the refusals
    counted and the streams are the same, and a fresh engine's."""
    from aios_tpu.engine.batching import Request

    ids = _ids(45, 3)
    want = [_engine(params).generate(p, max_new_tokens=5, temperature=0.0)
            for p in (ids, ids[:30])]
    eng = _engine(params)
    assert eng.prefix_hashes(ids) == [] and len(eng.prompt_hashes(ids).hashes) == 2
    if given == "none":
        for name in ("prefill_async", "start_chunked_prefill"):
            def bare(*a, _real=getattr(eng, name), **k):
                return _real(*a, **dict(k, given=None))
            setattr(eng, name, bare)
    batcher = ContinuousBatcher(eng)
    try:
        got = [batcher.submit(Request(prompt_ids=p, max_tokens=5,
                                      temperature=0.0)).tokens()
               for p in (ids, ids[:30])]
    finally:
        batcher.shutdown()
    assert got == want
    stats = eng.stats()
    # the second prompt shared one block of 16 rows with the first: refused
    assert stats["prefix_hits_refused_state"] == 1
    assert stats["prefix_rows_refused_state"] == 16
    assert stats["admissions_prehashed"] == (2 if given == "handed_in" else 0)
    assert stats["history_backfills_skipped"] == 0  # nothing is ever matched


def test_the_flight_recorder_s_admission_record_has_the_state_s_bytes(params):
    from aios_tpu.engine.batching import Request
    from aios_tpu.obs import flightrec

    eng = _engine(params)
    batcher = ContinuousBatcher(eng)
    try:
        rec = flightrec.RECORDER.begin(CFG.name, "ling-rec", prompt_tokens=20)
        out = batcher.submit(Request(prompt_ids=_ids(20, 5), max_tokens=4,
                                     temperature=0.0, rec=rec)).tokens()
        assert len(out) == 4
        if rec is not None:
            fields = [f for _, kind, f in rec.events if kind == "prefill"]
            assert fields and fields[0]["state_bytes"] == eng.slot_states.slot_bytes
    finally:
        batcher.shutdown()


# -- (e) routing by groups ----------------------------------------------------------


def test_a_token_picks_inside_its_groups_and_bias_moves_the_choice_alone():
    r = np.random.RandomState(4)
    h = jnp.asarray(r.randn(200, 128), jnp.float32)
    w = jnp.asarray(r.randn(128, 16) * 0.1, jnp.float32)
    scores, weights, idx = moe.route(h, w, CFG, jnp.zeros((16,), jnp.float32))
    groups = np.asarray(idx) // 4
    s = np.asarray(scores).reshape(200, 4, 4)
    best = np.argsort(-np.sort(s, -1)[..., -2:].sum(-1), -1)[:, :2]
    assert all(set(g) <= set(b) for g, b in zip(groups, best))
    plain = np.asarray(jax.lax.top_k(scores, 2)[1])  # without groups: other picks
    assert (np.sort(plain, -1) != np.sort(np.asarray(idx), -1)).any()
    want, _ = A.choose(D, scores, jnp.zeros((16,), jnp.float32))
    assert np.array_equal(np.sort(np.asarray(want), -1), np.sort(np.asarray(idx), -1))
    # a bias moves the choice, and the weights stay the unbiased scores'
    bias = jnp.asarray(r.randn(16) * 0.2, jnp.float32)
    _, w_b, idx_b = moe.route(h, w, CFG, bias)
    assert (np.sort(np.asarray(idx_b), -1) != np.sort(np.asarray(idx), -1)).any()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx_b), -1)
    assert np.allclose(np.asarray(w_b), 2.5 * chosen / chosen.sum(-1, keepdims=True),
                       atol=1e-6)
    want_b, _ = A.choose(D, scores, bias)
    assert np.array_equal(np.sort(np.asarray(want_b), -1), np.sort(np.asarray(idx_b), -1))


# -- (f) the shares add up to the layer ---------------------------------------------


def test_the_shares_routed_parts_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Four chips each hold a group of 4 of the layer's 16 experts: what the
    program's FFN gives on each (its routed part and the shared expert), with
    the shared expert counted once, is what the uncut reference gives."""
    whole = A.build_layer(D, SEED, 3)
    h = jnp.asarray(np.random.RandomState(6).randn(24, 128), jnp.float32)
    routed, shared, _ = A.moe_parts(D, h, whole, "float32")
    total = jnp.zeros_like(h)
    for share in range(4):
        cfg = CFG.scaled(experts_held=4, first_expert=4 * share)
        lp = _dense(dict(whole))
        for name in ("we_gateup", "we_down"):
            lp[name] = lp[name][4 * share:4 * share + 4]
        with jax.default_matmul_precision("highest"):
            out, _, stats = model.ffn(h[None], lp, cfg)
        total = total + out[0] - shared
        assert int(stats[1]) <= int(stats[0]) == 24 * 2
    assert float(jnp.abs(routed).max()) > 0.01
    assert float(jnp.abs(total - routed).max()) < 1e-5


# -- (g) refusals by name -------------------------------------------------------------


@pytest.mark.parametrize("asked, words", [
    (dict(paged_pool_rows=None), "the dense slot cache"),
    (dict(cache_dtype=jnp.int8), "an int8 KV pool"),
    (dict(prefix_host_bytes=1 << 20), "the host spill tier and its KVX entries"),
    (dict(kv_compress_after=64), "window and sink KV compression"),
    (dict(seq_prefill_min=32), "sequence sharded prefill"),
])
def test_what_cannot_take_a_state_is_refused_by_name(params, asked, words):
    kw = dict(num_slots=2, max_context=128, paged_pool_rows=384, page_size=P)
    kw.update(asked)
    with pytest.raises(ValueError, match="cannot take a state yet") as err:
        TPUEngine(CFG, params, **kw)
    assert "tiny-ling" in str(err.value) and words in str(err.value)


@pytest.mark.parametrize("asked", [
    "a_sharding_plan", "a_draft_model_and_the_verify_graph",
    "speculative_decoding_and_its_rollback",
    "the_grammar_jump_ahead_and_its_verify_graph",
])
def test_the_refusal_names_the_model_and_the_feature(asked):
    with pytest.raises(ValueError, match=asked.replace("_", " ")):
        refuse_for_state_kind(CFG, **{asked: True})
    refuse_for_state_kind(CFG, **{asked: False})
    refuse_for_state_kind(CFG.scaled(layer_types=(), kda_heads=0), **{asked: True})


def test_speculation_jump_ahead_and_the_verify_graph_are_refused(params):
    eng = _engine(params)
    assert not eng.spec_supported
    with pytest.raises(ValueError, match="speculative decoding"):
        ContinuousBatcher(eng, speculative=True)
    with pytest.raises(ValueError, match="grammar jump ahead"):
        ContinuousBatcher(eng, jump_ahead=True)
    with pytest.raises(ValueError, match="grammar jump ahead"):
        eng.jump_step(np.zeros((2, 2), np.int32), np.ones((2,), np.int32))
    with pytest.raises(ValueError, match="roll a rejected token back"):
        latent.verify_step_paged(eng.params, CFG, jnp.zeros((2, 2), jnp.int32),
                                 jnp.zeros((2,), jnp.int32), eng.state["k"],
                                 eng.state["v"], jnp.zeros((2, 8), jnp.int32))
    batcher = ContinuousBatcher(eng)  # the default's ON falls to the masked step
    try:
        assert not batcher.jump_ahead
    finally:
        batcher.shutdown()


@pytest.mark.parametrize("change, words", [
    (dict(expert_swiglu_limit_list=[0, 0, 4]), "expert_swiglu_limit_list"),
    (dict(share_expert_swiglu_limit_list=[5]), "share_expert_swiglu_limit_list"),
])
def test_a_nonzero_swiglu_limit_is_refused_by_name(change, words):
    with pytest.raises(ValueError, match=words):
        A.dims_of({**TINY, **change})
    A.dims_of({**TINY, **{k: [0] * 7 for k in change}})


@pytest.mark.parametrize("change, words", [
    (dict(kda_lower_bound=-6.0), "would overflow float32"),
])
def test_a_decay_the_chunked_form_cannot_factor_is_refused(params, change, words):
    with pytest.raises(ValueError, match=words):
        TPUEngine(CFG.scaled(**change), params, num_slots=2, max_context=128,
                  paged_pool_rows=384, page_size=P)


@pytest.mark.parametrize("fields, words", [
    (dict(layer_types=["kda"] * 6 + ["full"]), "names one of"),
    (dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0,
          latent_qk_norm=False, rope_interleave=False, attn_head_gate=False,
          first_k_dense=0), "latent-attention stack"),
    (dict(kda_heads=0), "need kda_heads"),
    (dict(n_group=3), "group-limited routing"),
    (dict(topk_group=5), "group-limited routing"),
])
def test_the_configuration_s_new_fields_are_checked(fields, words):
    with pytest.raises(ValueError, match=words):
        CFG.scaled(**fields)


# -- (h) the other latent and two-kind models lower to the graphs they had ---------

# sha256 (first 16 hex digits) of the lowered text of a paged decode step and a
# paged chunk at the PARENT commit (f58dabb), made there by `_lowered` below
# under this suite's own conftest (the device count is in the text); the
# grouped-query CHUNK's (mellum) is PR 44's tree's (tests/test_mellum2.py says why),
# and the two latent models' are PR 46's tree's, over the tree the engine lays
# out at load (latent.serving_layout: the per-head matrices heads-major, the
# products written over them; tests/test_latent.py holds those to the
# checkpoint layout's products)
PARENT = {
    "pangu_ultra_moe": ["5ec7cd5da090e555", "2ec10ad65d0f7a6b"],
    "xing4": ["bbcd5bba40fe2099", "37850bb33a4385fa"],
    "mellum": ["184a45bb2dc10b44", "1b9f82fa5e4b5f73"],
}
PANGU = dict(
    num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=512, n_routed_experts=8, router_n_experts=32,
    first_routed_expert=8, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, rope_theta=25600000.0,
    rms_norm_eps=1e-5, max_position_embeddings=128,
    assumed={"served_name": "tiny-pangu"},
)
XING = dict(
    num_hidden_layers=3, first_k_dense_replace=1, hidden_size=128,
    intermediate_size=256, moe_intermediate_size=64, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=512, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.0, norm_topk_prob=True,
    rope_theta=10000.0, rms_norm_eps=1e-6, max_position_embeddings=128,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30,
    rope_scaling=dict(type="yarn", factor=64, original_max_position_embeddings=32,
                      beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    assumed={"served_name": "tiny-xing"},
)
MELLUM = ModelConfig(
    name="tiny-mellum", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16, max_context=64,
    rms_norm_eps=1e-6, rope_theta=500000.0, sliding_window=8,
    layer_types=("window", "window", "window", "full") * 2,
    rope_by_kind=(("full", RopeParams(theta=500000.0, factor=16.0, original_context=16,
                                      attention_factor=1.2772588722239782)),
                  ("window", RopeParams(theta=500000.0))),
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
)


def _lowered(cfg, shapes, layout=None):
    layers = cfg.num_layers // cfg.period if layout is not None else cfg.num_layers
    pages = layout.pages if layout is not None else 8
    pools = tuple(jax.ShapeDtypeStruct((layers, pages, 16, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    blocks = 8 * (2 if layout is not None else 1)
    kw = dict(layout=layout) if layout is not None else {}

    def step(p, c, r, toks, lens, tables):
        return model.decode_step_paged(p, cfg, toks, lens, c, r, tables, kernels=False, **kw)

    def chunk(p, c, r, toks, start, row):
        return model.prefill_chunk_paged(p, cfg, toks, start, c, r, row, **kw)

    return [jax.jit(step).lower(shapes, *pools, i32(2), i32(2), i32(2, blocks)).as_text(),
            jax.jit(chunk).lower(shapes, *pools, i32(1, 16), i32(), i32(blocks)).as_text()]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_models_before_this_one_lower_to_the_graphs_they_had(name, monkeypatch):
    """With the new modules out of reach (a call into either would raise), the
    decode step and the chunk of a Pangu-, a Xing4- and a Mellum2-shaped model
    are, byte for byte, the parent's."""
    monkeypatch.setattr(latent, "kda", None)
    monkeypatch.setattr(kda, "kda_ops", None)
    if name == "mellum":
        layout = paged.KindPageAllocator(9, 7, 16, 2, 8, MELLUM.period_kinds).layout
        shapes = jax.eval_shape(lambda: model.quantize_params(
            model.init_params(MELLUM, jax.random.PRNGKey(0))))
        texts = _lowered(MELLUM, shapes, layout)
    else:
        arch = _arch(name)
        tiny = PANGU if name == "pangu_ultra_moe" else XING
        cfg = ModelConfig(**arch.model_fields(tiny, 128))
        texts = _lowered(cfg, jax.eval_shape(lambda: latent.serving_layout(
            arch.build_params(arch.dims_of(tiny), 1), cfg)[0]))
    assert [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts] == PARENT[name]
    assert all("kda" not in t for t in texts)
