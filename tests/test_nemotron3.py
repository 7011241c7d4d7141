"""A stack of sub-layers (a Mamba-2 state-space mixer that keeps a state a
slot, an expert FFN of ungated relu^2 experts, or grouped-query attention
without a rotary embedding, ONE a layer) on the paged serving path: the
program against the plain reference (benchmark/archs/nemotron_h.py, which
imports nothing of the program), at a small size on the CPU, on seeded random
weights. LOGITS are compared, never tokens.

Two comparisons, each with the reason for its tolerance and a control that has
to exceed it: the SAME mathematics in float32 (the serving tree's matrices
dequantized: only the order of the sums differs, so a state kept in bfloat16
fails it by three orders and a rotary embedding by one and a half), and the
serving types
(bfloat16 activations on int8 weights) against the float32 reference, which
the int4 control fails.
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
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_ling3 as ling3  # noqa: E402

from aios_tpu.engine import latent, mamba2, model, moe, paged  # noqa: E402
from aios_tpu.engine.batching import ContinuousBatcher  # noqa: E402
from aios_tpu.engine.config import ModelConfig  # noqa: E402
from aios_tpu.engine.engine import TPUEngine, refuse_for_state_kind  # noqa: E402
from aios_tpu.ops import expert_group, expert_visit  # noqa: E402
from aios_tpu.ops import mamba2 as ssm_ops  # noqa: E402
from benchmark.harness import reference  # noqa: E402
from benchmark.harness.manifest import load_file  # noqa: E402


def _arch(name):
    return load_file(os.path.join(REPO, "benchmark", "archs", f"{name}.py"),
                     "benchmark_arch")


A = _arch("nemotron_h")

# hidden 128; two periods M E M E M * E; 8 Mamba heads of 16 channels, a state
# of 128, 2 groups; 4 query heads on 2 K/V heads of 16; 16 experts of width 96
# (no whole lane tile, as the published 1,856 is none) top-2 beside a shared one
TINY = dict(
    num_hidden_layers=14, hybrid_override_pattern="MEMEM*E" * 2, hidden_size=128,
    intermediate_size=64, moe_intermediate_size=96,
    moe_shared_expert_intermediate_size=192, n_shared_experts=1, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=128, n_groups=2, conv_kernel=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
    n_routed_experts=16, num_experts_per_tok=2, n_group=1, topk_group=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, layer_norm_epsilon=1e-5,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    max_position_embeddings=128, assumed={"served_name": "tiny-nemo"},
)
SEED = 2 ** 31 + 11
P = 16  # rows of a page
D = A.dims_of(TINY)
CFG = ModelConfig(**A.model_fields(TINY, 128))

# The same mathematics in float32: 1.0e-6 read whole and 1.4e-6 in chunks (the
# order of the sums); the reference with its state rounded to bfloat16 after
# every row reads 2.6e-4 (1.6e-4 to 2.6e-4 over three seeds: no router's pick
# flips under it since the routers' biases are calibrated, where 0.11 was read
# before, a flipped pick's worth), with int4 matrices 0.43, and the program
# with a rotary embedding on 0.0030 (two attention layers of fourteen, four
# heads of 16 channels over 96 rows). The bar stands a decade above the first
# and a decade below the bfloat16 state.
F32_TOL = 2e-5
# Positions whose least router margin (the reference's own, a logit's worth)
# is under this change experts under bfloat16 rounding: left out, as the
# benchmark's `correct` leaves them out.
# (a pick flipped at a margin of 0.0068 in this model's six routers, and moved
# its row's logits by 0.20)
MARGIN = 0.01
# bfloat16 activations on int8 weights against the float32 reference: 0.03-0.12
# read at a logit std of 0.24 over the decided positions (0.12 two rows after a
# pick that flipped at a margin of 0.0011: the Mamba states carry it on); the
# int4 control reads 0.43. Between, with room on both sides.
LOGIT_TOL = 0.15


@pytest.fixture(scope="module")
def params():
    return A.build_params(D, SEED)


def _dense(tree):
    """The serving tree with every matrix dequantized and every leaf float32
    (an expert's transposed up matrix has its scales along its rows)."""
    if isinstance(tree, dict):
        if set(tree) == {"q", "s"}:
            return tree["q"].astype(jnp.float32) * tree["s"]
        return {k: (v["q"].astype(jnp.float32) * v["s"].swapaxes(-1, -2)
                    if k == "we_up_t" else _dense(v)) for k, v in tree.items()}
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
    kind = paged.SlotStates.of(CFG, slots)
    return (jnp.full(kind.state_shape, fill, jnp.float32),
            jnp.full(kind.tail_shape, fill, dtype))


def _pools(dtype, pages=24):
    return tuple(jnp.zeros((CFG.row_layers, pages, P, w), dtype)
                 for w in CFG.kv_row_dims)


# -- the configuration -----------------------------------------------------------


def test_the_configuration_answers_for_the_new_kinds():
    assert CFG.sublayers and CFG.state_kinds and CFG.state_kind == "mamba2"
    assert not CFG.kinds and not CFG.mla and CFG.moe and not CFG.rotary
    assert CFG.period == 7 and CFG.lead_kinds == ()
    assert CFG.period_kinds == ("mamba2", "moe", "mamba2", "moe", "mamba2", "full", "moe")
    assert (CFG.layers_of("mamba2"), CFG.layers_of("moe"), CFG.row_layers) == (6, 6, 2)
    assert CFG.state_shapes == ((8, 16, 128), (3, 8 * 16 + 2 * 2 * 128))
    assert CFG.kv_row_dims == (32, 32) and CFG.expert_act == "relu2"
    # the six configurations before this one are what they were
    plain = ModelConfig(name="m", vocab_size=8, hidden_size=8, intermediate_size=8,
                        num_layers=2, num_heads=2, num_kv_heads=1, head_dim=4)
    assert (plain.expert_act, plain.rotary, plain.state_kind, plain.sublayers,
            plain.row_layers) == ("swiglu", True, None, False, 2)


# -- (a) prefill in chunks, then decode, through the state array and the pool ------


def test_full_forward_matches_reference(params, params32, ref96):
    ids, ref, decided = ref96
    want = ref["float32"][0]
    assert decided.sum() > 40 and want.std() > 0.1
    with jax.default_matmul_precision("highest"):
        got32 = np.asarray(model.forward_full(params32, CFG, jnp.asarray([ids]),
                                              kernels=False))[0]
        rotary = np.asarray(model.forward_full(
            params32, CFG.scaled(rotary=True), jnp.asarray([ids]), kernels=False))[0]
    assert np.abs(got32 - want).max() < F32_TOL
    got = np.asarray(model.forward_full(params, CFG, jnp.asarray([ids]), kernels=False))[0]
    assert np.abs(got - want)[decided].max() < LOGIT_TOL
    assert np.abs(ref["int4"][0] - want)[decided].max() > 2 * LOGIT_TOL
    assert np.abs(ref["int4"][0] - want).max() > 100 * F32_TOL
    # a bfloat16 state fails the float32 bar
    assert np.abs(ref["state_bf16"][0] - want).max() > 5 * F32_TOL
    assert np.abs(rotary - want).max() > 10 * F32_TOL  # and so does a rotary embedding


def _serve(prm, dtype, ids, chunks, upto):
    """Prefill ``ids`` in ``chunks`` [(rows of the chunk's graph, real rows)]
    into slot 1 of 3, then decode to ``upto``; the logits of every row and the
    states after. The states start at 7.0: the first chunk resets its slot's."""
    states, pools = _states(3, dtype, 7.0), _pools(dtype)
    table = jnp.arange(1, 9, dtype=jnp.int32)  # 8 blocks = 128 rows
    slot, pos, logits = 1, 0, []
    for tc, nv in chunks:
        toks = np.zeros((1, tc), np.int32)
        toks[0, :nv] = ids[pos:pos + nv]
        lg, k, v, s, t, *_ = model.prefill_chunk_paged(
            prm, CFG, jnp.asarray(toks), jnp.int32(pos), *pools, table,
            states=states, slot=jnp.int32(slot), n_valid=jnp.int32(nv))
        pools, states = (k, v), (s, t)
        logits.append(np.asarray(lg[0, :nv]))
        pos += nv
    tables = jnp.zeros((3, 8), jnp.int32).at[slot].set(table)
    active = jnp.zeros((3,), bool).at[slot].set(True)
    for i in range(pos, upto):
        lg, k, v, s, t, *_ = model.decode_step_paged(
            prm, CFG, jnp.zeros((3,), jnp.int32).at[slot].set(ids[i]),
            jnp.zeros((3,), jnp.int32).at[slot].set(i), *pools, tables,
            kernels=False, active=active, states=states)
        pools, states = (k, v), (s, t)
        logits.append(np.asarray(lg[slot])[None])
    return np.concatenate(logits), states


def test_chunked_prefill_then_decode_through_the_state_array(params, params32, ref96):
    """53 rows admitted as a whole chunk of 32 and a final one of 21 real rows
    of 32 (neither a multiple of 16 nor of the sub-chunk), then 17 decode
    steps: every row's logits against the reference's full forward."""
    ids, ref, decided = ref96
    want = ref["float32"][0][:70]
    with jax.default_matmul_precision("highest"):
        got32, states = _serve(params32, jnp.float32, ids, ((32, 32), (32, 21)), 70)
    assert np.abs(got32 - want).max() < F32_TOL
    # the dead slots' states and tails are what they were
    for s in states:
        assert float(jnp.abs(s[:, 0] - 7.0).max()) == 0.0 if s.ndim == 5 else True
    assert float(jnp.abs(states[0][:, 2] - 7.0).max()) == 0.0
    assert float(jnp.abs(states[1][:, :, 0].astype(jnp.float32) - 7.0).max()) == 0.0
    got, _ = _serve(params, jnp.bfloat16, ids, ((32, 32), (32, 21)), 70)
    assert np.abs(got - want)[decided[:70]].max() < LOGIT_TOL


# -- (b) the chunked form and both kernels against the recurrence -------------------


def _recurrence_inputs(T, rate, seed=0):
    """Rows whose ``dt A`` lies near ``rate`` (0: a state that never forgets;
    -8: one that forgets within a row)."""
    H, Pc, G, N = 8, 16, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H, Pc))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)) - 2.0) + 0.01
    a = rate * (0.5 + jax.random.uniform(ks[2], (T, H)))
    B = jax.random.normal(ks[3], (T, G, N))
    C = jax.random.normal(ks[4], (T, G, N))
    return x, dt, a, B, C, jax.random.normal(ks[5], (H, Pc, N))


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("rate", [-1e-4, -8.0])
def test_the_chunked_form_is_the_recurrence(rate, kernel):
    x, dt, a, B, C, s0 = _recurrence_inputs(256, rate)
    live = (jnp.arange(256) < 201)[:, None]  # padded rows: identity updates
    dt, a = jnp.where(live, dt, 0.0), jnp.where(live, a, 0.0)
    want_y, want_s = ssm_ops.recurrence_reference(x, dt, a, B, C, s0)
    y, s = ssm_ops.chunked(x, dt, a, B, C, s0, use_kernel=kernel, interpret=True)
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y)[:201].max()) < 1e-5 * max(scale, 1.0)
    assert float(jnp.abs(s - want_s).max()) < 1e-5 * max(scale, 1.0)
    # the state after the padded rows is the state after the last real row
    _, at_201 = ssm_ops.recurrence_reference(x[:201], dt[:201], a[:201], B[:201],
                                             C[:201], s0)
    assert float(jnp.abs(s - at_201).max()) < 1e-5 * max(scale, 1.0)


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("rate", [-1e-4, -8.0])
def test_a_decode_step_updates_its_slots_in_place_and_no_other(rate, kernel):
    x, dt, a, B, C, _ = _recurrence_inputs(5, rate, 1)
    L, S = 2, 5
    pool = jnp.asarray(np.random.RandomState(2).randn(L, S + 1, 8, 16, 128), jnp.float32)
    slots = jnp.asarray([2, S, 0, S, 4])  # two entries are dead: the scratch slot,
    dead = (slots == S)[:, None]  # handed an identity update
    dt, a = jnp.where(dead, 0.0, dt), jnp.where(dead, 0.0, a)
    step = (lambda *v: ssm_ops.mamba_step(*v, interpret=True)) if kernel \
        else ssm_ops.decode_step_reference
    y, new = step(x, dt, a, B, C, pool, jnp.int32(1), slots)
    for e, slot in ((0, 2), (2, 0), (4, 4)):
        want_y, want_s = ssm_ops.recurrence_reference(
            x[e:e + 1], dt[e:e + 1], a[e:e + 1], B[e:e + 1], C[e:e + 1], pool[1, slot])
        assert float(jnp.abs(y[e] - want_y[0]).max()) < 1e-4
        assert float(jnp.abs(new[1, slot] - want_s).max()) < 1e-5
    untouched = np.ones((L, S + 1), bool)
    untouched[1, [2, 0, 4]] = False
    assert np.array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])


# -- (c) the engine: a slot taken twice, prefix hits refused, counters -------------


def _engine(params, **kw):
    return TPUEngine(CFG, params, num_slots=2, max_context=128,
                     paged_pool_rows=3 * 128, page_size=P, **kw)


def test_a_slot_taken_twice_gives_its_second_tenant_what_a_fresh_slot_gives(params):
    ids = _ids(45, 3)
    eng = _engine(params)
    assert eng.state["k"].shape[0] == 2 and eng.state["mamba2_s"].shape[:2] == (6, 3)
    assert eng.state["mamba2_tail"].shape == (6, 3, 16, 640)
    first = eng.generate(ids, max_new_tokens=10, temperature=0.0)
    ref = reference.logits_for(A, D, SEED, [ids + first], [len(ids) - 1],
                               ("float32",), pad_to=0)["float32"][0]
    assert reference.served_gaps(ref[:10], first).max() < LOGIT_TOL
    second = eng.generate(ids[:30], max_new_tokens=6, temperature=0.0)  # slot 0 again
    assert second == _engine(params).generate(ids[:30], max_new_tokens=6, temperature=0.0)
    stats = eng.stats()
    # 45 and 30 rows in chunks of 128 -> buckets 64 and 32, through 6 Mamba layers
    assert stats["mamba_rows_prefill"] == (64 + 32) * 6
    assert stats["mamba_rows_decode"] > 0 and stats["kv_state_slots"] == 0
    assert "kda_rows_prefill" not in stats
    one = 6 * (8 * 16 * 128 * 4 + 3 * 640 * 2)
    assert eng.slot_states.slot_bytes == one and stats["kv_state_bytes"] == 3 * one
    # the second prompt shared one block of 16 rows with the first: refused
    assert stats["prefix_hits_refused_state"] == 1
    assert stats["prefix_rows_refused_state"] == 16 and "prefix_rows_reused" not in stats
    assert eng.prefix_index is None and not eng._prefill_fns  # every prompt in chunks
    assert eng.phases.counts["load.states"] == 1  # the arrays' allocation, named


def test_the_flight_recorder_s_admission_record_has_the_state_layers(params):
    from aios_tpu.engine.batching import Request
    from aios_tpu.obs import flightrec

    eng = _engine(params)
    batcher = ContinuousBatcher(eng)
    try:
        rec = flightrec.RECORDER.begin(CFG.name, "nemo-rec", prompt_tokens=20)
        out = batcher.submit(Request(prompt_ids=_ids(20, 5), max_tokens=4,
                                     temperature=0.0, rec=rec)).tokens()
        assert len(out) == 4
        if rec is not None:
            fields = [f for _, kind, f in rec.events if kind == "prefill"]
            assert fields and fields[0]["state_bytes"] == eng.slot_states.slot_bytes
            assert fields[0]["state_layers"] == 6
    finally:
        batcher.shutdown()


# -- (d) the three expert paths, ungated and gated -----------------------------------


def _plain_sum(h, lp, cfg):
    """sum over a token's chosen held experts of weight x expert(h), in float32."""
    flat = h.reshape(-1, h.shape[-1]).astype(jnp.float32)
    _, weights, idx = moe.route(flat, lp["w_router"], cfg, lp.get("router_bias"))
    out = np.zeros(flat.shape, np.float32)
    for n in range(flat.shape[0]):
        for w, e in zip(np.asarray(weights[n]), np.asarray(idx[n])):
            e = int(e) - cfg.first_expert
            if not 0 <= e < cfg.held_experts:
                continue
            if cfg.expert_act == "relu2":
                z = np.maximum(np.asarray(flat[n] @ lp["we_up_t"][e].T), 0.0) ** 2
            else:
                gu = np.asarray(flat[n] @ lp["we_gateup"][e])
                F = gu.shape[0] // 2
                z = gu[:F] / (1.0 + np.exp(-gu[:F])) * gu[F:]
            out[n] += float(w) * (z @ np.asarray(lp["we_down"][e]))
    return out.reshape(h.shape)


@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_the_three_expert_paths_are_the_plain_sum_over_experts(act):
    cfg = CFG.scaled(expert_act=act, experts_held=8, first_expert=4)
    rng = np.random.RandomState(7)
    E, F, X = 128, 96, 8
    lp = {"w_router": jnp.asarray(rng.randn(E, 16) * 0.5, jnp.float32),
          "router_bias": jnp.asarray(rng.randn(16) * 0.01, jnp.float32),
          "we_down": jnp.asarray(rng.randn(X, F, E) * 0.1, jnp.float32)}
    if act == "relu2":
        lp["we_up_t"] = jnp.asarray(rng.randn(X, F, E) * 0.1, jnp.float32)
    else:
        lp["we_gateup"] = jnp.asarray(rng.randn(X, E, 2 * F) * 0.1, jnp.float32)
    h = jnp.asarray(rng.randn(2, 12, E), jnp.float32)
    want = _plain_sum(h, lp, cfg)
    assert np.abs(want).max() > 0.05
    with jax.default_matmul_precision("highest"):
        dense = moe.moe_ffn_dense(h, lp, cfg)[0]
        grouped = moe.moe_ffn_grouped(h, lp, cfg)[0]
        live = jnp.asarray([True, False])
        visit = moe.moe_ffn_visit(h, lp, cfg, live)[0]
    assert np.abs(np.asarray(dense) - want).max() < 1e-4
    assert np.abs(np.asarray(grouped) - want).max() < 1e-4
    assert np.abs(np.asarray(visit)[0] - want[0]).max() < 1e-4
    assert np.abs(np.asarray(visit)[1]).max() == 0.0  # a dead slot picks no expert


@pytest.mark.parametrize("kernel", ["visit", "group"])
def test_the_expert_kernels_run_an_ungated_expert_of_a_width_that_is_no_lane_tile(kernel):
    """Both kernels, interpreted, on the serving layout (int8 stacks, the up
    matrices transposed) at a width of 96 = three int8 tiles of 32 rows and no
    whole lane tile, against the loop of XLA products over the same layout."""
    cfg = CFG.scaled(experts_held=8, first_expert=0)
    E, F, X, L = 128, 96, 8, 2
    assert expert_visit.supports_pallas(E, F, act="relu2")
    assert not expert_visit.supports_pallas(E, F)
    rng = np.random.RandomState(9)
    q8 = lambda *s: jnp.asarray(rng.randint(-127, 128, s), jnp.int8)  # noqa: E731
    sc = lambda *s: jnp.asarray(0.002 * (0.5 + rng.rand(*s)), jnp.float32)  # noqa: E731
    stacks = (q8(L, X, F, E), sc(L, X, 1, F), q8(L, X, F, E), sc(L, X, 1, E))
    lp = {"we_up_t": {"q": stacks[0], "s": stacks[1]},
          "we_down": {"q": stacks[2], "s": stacks[3]}, "expert_layer": 1}
    act, down = moe._experts_in_place(lp, F, "relu2")
    if kernel == "visit":
        x = jnp.asarray(rng.randn(8, E), jnp.bfloat16)
        gates = jnp.asarray(rng.rand(8, X) * (rng.rand(8, X) < 0.4), jnp.float32)
        touched = jnp.any(gates > 0, axis=0)
        visit, n = expert_visit.visit_list(touched)
        got = expert_visit.expert_visit(x, gates, visit, n, 1, *stacks,
                                        act="relu2", interpret=True)
        want = sum(down(act(x, e) * gates[:, e:e + 1].astype(x.dtype), e)
                   for e in range(X) if bool(touched[e]))
        assert float(jnp.abs(got - want).max()) < 2e-2 * float(jnp.abs(want).max())
        return
    counts = jnp.asarray([40, 0, 3, 70, 0, 0, 33, 1], jnp.int32)
    blocks, first_row = expert_group.segments(counts)
    M = expert_group.buffer_rows(256, X)
    x_rows = jnp.asarray(rng.randn(M, E), jnp.bfloat16)
    cap = expert_group.row_cap(E, F, 2, act="relu2")
    got = expert_group.expert_group(
        x_rows, *expert_group.unit_list(blocks, cap, 256), 1, *stacks, cap=cap,
        act="relu2", interpret=True)
    for e in range(X):
        rows = slice(int(first_row[e]), int(first_row[e]) + int(counts[e]))
        if int(counts[e]):
            want = down(act(x_rows[rows], e), e)
            assert float(jnp.abs(got[rows] - want).max()) < 2e-2 * float(
                jnp.abs(want).max()), e


# -- (e) the share test of the guide's section 4 ------------------------------------


def test_the_shares_routed_parts_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Two chips each hold 8 of the layer's 16 experts: what the program's FFN
    gives on each (its routed part and the shared expert), with the shared
    expert counted once, is what the uncut reference gives."""
    whole = A.build_layer(D, SEED, 1)  # an `E` layer
    assert D.kind(1) == "moe"
    h = jnp.asarray(np.random.RandomState(6).randn(24, 128), jnp.float32)
    routed, shared, _ = A.moe_parts(D, h, whole, "float32")
    total = jnp.zeros_like(h)
    for share in range(2):
        cfg = CFG.scaled(experts_held=8, first_expert=8 * share)
        lp = _dense(dict(whole))
        for name in ("we_up_t", "we_down"):
            lp[name] = lp[name][8 * share:8 * share + 8]
        with jax.default_matmul_precision("highest"):
            out, _, stats = model.ffn(h[None], lp, cfg)
        total = total + out[0] - shared
        assert int(stats[1]) <= int(stats[0]) == 24 * 2
    assert float(jnp.abs(routed).max()) > 0.01
    assert float(jnp.abs(total - routed).max()) < 1e-5


# -- (f) refusals by name -------------------------------------------------------------


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
    assert "tiny-nemo" in str(err.value) and words in str(err.value)
    assert "state-space (mamba2) layers" in str(err.value)
    assert "grouped-query page pool" in str(err.value)


@pytest.mark.parametrize("asked", [
    "a_sharding_plan", "a_draft_model_and_the_verify_graph",
    "speculative_decoding_and_its_rollback",
    "the_grammar_jump_ahead_and_its_verify_graph",
])
def test_the_refusal_names_the_model_and_the_feature(asked):
    with pytest.raises(ValueError, match=asked.replace("_", " ")):
        refuse_for_state_kind(CFG, **{asked: True})
    refuse_for_state_kind(CFG, **{asked: False})


def test_speculation_jump_ahead_and_the_verify_graph_are_refused(params):
    eng = _engine(params)
    assert not eng.spec_supported
    with pytest.raises(ValueError, match="speculative decoding"):
        ContinuousBatcher(eng, speculative=True)
    with pytest.raises(ValueError, match="grammar jump ahead"):
        ContinuousBatcher(eng, jump_ahead=True)
    with pytest.raises(ValueError, match="roll a rejected token back"):
        model.verify_step_paged(eng.params, CFG, jnp.zeros((2, 2), jnp.int32),
                                jnp.zeros((2,), jnp.int32), eng.state["k"],
                                eng.state["v"], jnp.zeros((2, 8), jnp.int32))
    with pytest.raises(ValueError, match="no training forward"):
        model.forward_full(eng.params, CFG, jnp.zeros((1, 8), jnp.int32), with_aux=True)
    batcher = ContinuousBatcher(eng)  # the default's ON falls to the masked step
    try:
        assert not batcher.jump_ahead
    finally:
        batcher.shutdown()


@pytest.mark.parametrize("fields, words", [
    (dict(layer_types=["mamba2"] * 13 + ["window"]), "names one of"),
    (dict(layer_types=["moe", "full"] * 7), "has mamba2 layers"),
    (dict(ssm_heads=0), "ssm_heads in whole ssm_groups"),
    (dict(ssm_groups=3), "ssm_heads in whole ssm_groups"),
    (dict(num_experts=0, experts_held=0), "moe sub-layers need num_experts"),
    (dict(sliding_window=8), "plain grouped-query layer"),
    (dict(qk_norm=True), "plain grouped-query layer"),
    (dict(expert_act="gelu"), "unknown expert_act"),
    (dict(layer_types=["kda"] * 13 + ["mamba2"]), "names one of"),
])
def test_the_configuration_s_new_fields_are_checked(fields, words):
    with pytest.raises(ValueError, match=words):
        CFG.scaled(**fields)


# -- (g) the models before this one lower to the graphs they had -------------------

# sha256 (first 16 hex digits) of the lowered text of a paged decode step and a
# paged chunk at the PARENT commit (bc560d3), made there by `_lowered` below
# under this suite's own conftest (the device count is in the text); the two
# grouped-query CHUNKS' are PR 44's tree's (tests/test_mellum2.py says why) and
# the three latent models' PR 46's, over the tree the engine lays out at load
# (tests/test_ling3.py says why)
PARENT = {
    "mixtral": ["e6721efaa0108966", "3fb524e1445269a1"],
    "pangu_ultra_moe": ["5ec7cd5da090e555", "2ec10ad65d0f7a6b"],
    "xing4": ["bbcd5bba40fe2099", "37850bb33a4385fa"],
    "mellum": ["184a45bb2dc10b44", "1b9f82fa5e4b5f73"],
    "bailing_hybrid": ["26fb444b3905063a", "d9ffba9f39abd430"],
}
MIXTRAL = ModelConfig(
    name="tiny-mixtral", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, max_context=128,
    rope_theta=1000000.0, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32,
)
# the tiny models of tests/test_ling3.py's own section (h), and its own
PANGU, XING, MELLUM, LING = ling3.PANGU, ling3.XING, ling3.MELLUM, ling3.TINY


def _lowered(cfg, shapes, layout=None):
    layers = cfg.num_layers // cfg.period if layout is not None else cfg.row_layers
    pages = layout.pages if layout is not None else 8
    pools = tuple(jax.ShapeDtypeStruct((layers, pages, 16, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    blocks = 8 * (2 if layout is not None else 1)
    kw = dict(layout=layout) if layout is not None else {}
    states, more = (), ()
    if cfg.state_kinds:  # the state kind's arrays, and the chunk's slot and rows
        kind = paged.SlotStates(cfg.layers_of("kda"), 2, *cfg.kda_state_shapes)
        states = (jax.ShapeDtypeStruct(kind.state_shape, jnp.float32),
                  jax.ShapeDtypeStruct(kind.tail_shape, jnp.bfloat16))
        more = (i32(), i32())

    def step(p, c, r, toks, lens, tables, *s):
        return model.decode_step_paged(p, cfg, toks, lens, c, r, tables, kernels=False,
                                       **kw, **(dict(states=s) if s else {}))

    def chunk(p, c, r, toks, start, row, *s):
        if s:
            kw.update(states=s[:2], slot=s[2], n_valid=s[3])
        return model.prefill_chunk_paged(p, cfg, toks, start, c, r, row, **kw)

    return [jax.jit(step).lower(shapes, *pools, i32(2), i32(2), i32(2, blocks),
                                *states).as_text(),
            jax.jit(chunk).lower(shapes, *pools, i32(1, 16), i32(), i32(blocks),
                                 *states, *more).as_text()]


def lowered_hashes(name):
    """The two hashes of model ``name``: here, and at the parent commit."""
    if name == "mellum":
        layout = paged.KindPageAllocator(9, 7, 16, 2, 8, MELLUM.period_kinds).layout
        shapes = jax.eval_shape(lambda: model.quantize_params(
            model.init_params(MELLUM, jax.random.PRNGKey(0))))
        texts = _lowered(MELLUM, shapes, layout)
    elif name == "mixtral":
        shapes = jax.eval_shape(lambda: model.quantize_params(
            model.init_params(MIXTRAL, jax.random.PRNGKey(0))))
        texts = _lowered(MIXTRAL, shapes)
    else:
        arch = _arch(name)
        tiny = {"pangu_ultra_moe": PANGU, "xing4": XING, "bailing_hybrid": LING}[name]
        cfg = ModelConfig(**arch.model_fields(tiny, 128))
        texts = _lowered(cfg, jax.eval_shape(lambda: latent.serving_layout(
            arch.build_params(arch.dims_of(tiny), 1), cfg)[0]))
    return [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts], texts


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_models_before_this_one_lower_to_the_graphs_they_had(name, monkeypatch):
    """With the new modules out of reach (a call into either would raise), the
    decode step and the chunk of a Mixtral-, a Pangu-, a Xing4-, a Mellum2- and
    a Ling-shaped model are, byte for byte, the parent's: the SwiGLU expert
    paths, the rotary embedding, the scan by periods and the state kind's
    graphs as they were."""
    monkeypatch.setattr(mamba2, "ssm_ops", None)
    hashes, texts = lowered_hashes(name)
    assert hashes == PARENT[name]
    assert all("mamba" not in t for t in texts)
