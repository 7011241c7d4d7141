"""The grouped expert path (moe.moe_ffn_grouped) at Mixtral's shape of
routing: softmax top-2 of 8, every expert held. Prefill token counts run each
expert over the rows routed to it only, reading the stacked expert weights in
place at (layer, expert); the dense path (every expert over every token) is
the reference, and which of the two serves follows the static shapes alone
(moe.grouped_pays). Below, the chip's form of the same path (ONE kernel a
layer call, ops/expert_group.py) runs interpreted against the loop and the
dense path at the four MoE configurations' shapes of routing.

These live beside tests/test_moe.py and not in it because that module is the
slow tier as a whole (its ``pytestmark``): the cases here are small and run in
the commit gate.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu import ops
from aios_tpu.engine import model as M
from aios_tpu.engine import moe
from aios_tpu.engine.config import ModelConfig
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.ops import expert_group

CFG = ModelConfig(
    name="tiny-top2of8", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16, max_context=512,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
)
TOL = 0.02  # of the largest output: tests/test_latent.py's, 5 x bf16's 2^-8
TAKES_ALL, TAKES_NONE = 3, 5


@functools.lru_cache(maxsize=None)
def _layers(leaves: str):
    """The three layers' stacked trees in one of the serving layouts."""
    params = M.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    if leaves != "bf16":
        params = M.quantize_params(params, fuse=leaves == "int8-fused")
    layers = params["layers"]
    assert ("we_gateup" in layers) == (leaves == "int8-fused")
    assert isinstance(layers["we_down"], dict) == (leaves != "bf16")
    return layers


def _forced(layers):
    """The same layers with every token's first pick forced onto one expert
    and another never picked (the rows' first value is a constant 4)."""
    w = layers["w_router"].astype(jnp.float32)
    w = w.at[:, 0, TAKES_ALL].set(50.0).at[:, 0, TAKES_NONE].set(-50.0)
    return {**layers, "w_router": w.astype(layers["w_router"].dtype)}


@jax.jit
def _dense(h, layers, l):
    lp = jax.tree.map(lambda a: a[l], layers)
    out, _, stats = moe.moe_ffn_dense(h, lp, CFG, with_stats=True)
    _, _, idx = moe.route(h[0], lp["w_router"], CFG)
    return out, stats, idx


@jax.jit
def _grouped(h, layers, l):
    """As the layer scans hand it over: the layer's own leaves sliced, the
    expert stacks whole with the layer's index beside them."""
    scanned, whole = M._experts_apart(layers, True)
    lp = M._with_experts(jax.tree.map(lambda a: a[l], scanned), whole, l)
    out, _, stats = moe.moe_ffn_grouped(h, lp, CFG)
    return out, stats


def _one_pick(h_row, lp, e, gate):
    """What expert ``e`` adds for one normed row at weight ``gate``, from the
    dequantized weights in float32."""
    def w(name):
        leaf = jax.tree.map(lambda a: a[e], lp[name])
        if isinstance(leaf, dict):
            return leaf["q"].astype(jnp.float32) * leaf["s"]
        return leaf.astype(jnp.float32)

    x = h_row.astype(jnp.float32)
    if "we_gateup" in lp:
        gu = x @ w("we_gateup")
        g, u = gu[:CFG.expert_dim], gu[CFG.expert_dim:]
    else:
        g, u = x @ w("we_gate"), x @ w("we_up")
    return gate * ((jax.nn.silu(g) * u) @ w("we_down"))


@pytest.mark.parametrize("routing", ["free", "one-takes-all"])
@pytest.mark.parametrize("n_tok", [130, 256, 300, 512])
@pytest.mark.parametrize("leaves", ["bf16", "int8-fused", "int8"])
def test_grouped_matches_dense_at_top2_of_8(leaves, n_tok, routing):
    """Grouped against dense on each of three stacked layers read at
    ``expert_layer`` 0, 1, 2: the same picks and weights, float32
    accumulation in both, a different order of adding the experts' parts.
    130 and 300 tokens leave a row block part-filled; forced routing gives
    one expert every token and another none."""
    layers = _layers(leaves)
    if routing == "one-takes-all":
        layers = _forced(layers)
    h = jax.random.normal(jax.random.PRNGKey(n_tok), (1, n_tok, 64), jnp.bfloat16)
    h = h.at[..., 0].set(4.0)
    for l in range(CFG.num_layers):
        want, s_dense, idx = _dense(h, layers, l)
        got, s_grouped = _grouped(h, layers, l)
        want = np.asarray(want, np.float32)
        tol = TOL * np.abs(want).max()
        assert np.abs(np.asarray(got, np.float32) - want).max() < tol, l
        total, local, rows, visited = s_grouped.tolist()
        assert total == local == 2 * n_tok == s_dense.tolist()[0]
        assert s_dense.tolist()[2:] == [8 * n_tok, 8]
        counts = np.bincount(np.asarray(idx).ravel(), minlength=8)
        RB = expert_group.ROW_BLOCK
        assert rows == int(np.sum(-(-counts // RB)) * RB)
        assert visited == np.count_nonzero(counts)
        if routing == "one-takes-all":
            assert counts[TAKES_ALL] == n_tok and counts[TAKES_NONE] == 0
    # the control: ONE pick of one token dropped is a fault this tolerance sees
    lp = jax.tree.map(lambda a: a[l], layers)
    _, weights, idx = moe.route(h[0], lp["w_router"], CFG)
    dropped = np.asarray(_one_pick(h[0, 7], lp, idx[7, 0], weights[7, 0]))
    assert np.abs(dropped).max() > tol


def test_layers_are_read_at_their_own_index():
    """The in-place index is the layer's: the three layers' results differ,
    and each equals the grouped path over that layer's slice alone."""
    layers = _layers("int8-fused")
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64), jnp.bfloat16)
    outs = [np.asarray(_grouped(h, layers, l)[0], np.float32) for l in range(3)]
    assert np.abs(outs[0] - outs[1]).max() > 0.1 * np.abs(outs[0]).max()
    assert np.abs(outs[1] - outs[2]).max() > 0.1 * np.abs(outs[1]).max()
    for l in range(3):
        alone, _, _ = moe.moe_ffn_grouped(
            h, jax.tree.map(lambda a: a[l], layers), CFG)
        np.testing.assert_array_equal(np.asarray(alone, np.float32), outs[l])


MIXTRAL = dataclasses.replace(
    CFG, name="mixtral-widths", hidden_size=4096, moe_intermediate_size=14336)
PANGU = dataclasses.replace(
    CFG, name="pangu-share", hidden_size=7680, moe_intermediate_size=2048,
    num_experts=256, num_experts_per_tok=8, experts_held=16,
    moe_scoring="sigmoid")


@pytest.mark.parametrize("cfg, n_tok, grouped", [
    (MIXTRAL, 512, True),   # 1,024 picks + 8 part passes against 4,096 rows
    (MIXTRAL, 256, True),   # 512 + 1,024 against 2,048
    (MIXTRAL, 128, False),  # 256 + 1,024 against 1,024
    (MIXTRAL, 8, False),    # a decode step
    (PANGU, 512, True),     # 256 + 2,048 against 8,192
    (PANGU, 256, True),     # 128 + 2,048 against 4,096
    (PANGU, 128, False),    # 64 + 2,048 against 2,048
    (PANGU, 32, False),     # a decode step
], ids=lambda v: getattr(v, "name", str(v)))
def test_grouped_pays_follows_the_static_shapes(cfg, n_tok, grouped):
    assert moe.grouped_pays(n_tok, cfg) is grouped
    assert moe.grouped_serves(n_tok, cfg) is grouped
    # an engine under a sharding plan (the expert axis may be sharded over
    # ep) and the training forward keep theirs, whatever the shapes
    assert not moe.grouped_serves(n_tok, cfg, moe_dense=True)
    assert not moe.grouped_serves(n_tok, cfg, allow_dispatch=True)


def _greedy(engine, prompt, chunk):
    pc = engine.start_chunked_prefill(0, prompt, temperature=0.0, chunk=chunk)
    first = None
    while first is None:
        first = pc.step()
    return [first] + [int(t) for t in engine.step(8)[:, 0]]


def test_chunked_prefill_through_grouped_yields_the_dense_tokens(monkeypatch):
    """A 300-token prompt admitted in a 256-token chunk (grouped: 512 picks
    and 8 part passes against 2,048 rows) and a final 64-token bucket (dense),
    then greedy decode (the visit path: the one live slot's two experts a
    layer over both rows): the tokens of an engine for which the grouped path
    never pays, so that every prefill graph of it runs dense.
    The counters say which path ran: dense-over-all computes exactly 4 rows
    a pick (8 experts over every token, 2 picks a token) and reads 8 experts
    a layer call."""
    cfg = dataclasses.replace(CFG, num_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = (np.arange(1, 301) * 7 % 500 + 1).tolist()
    kw = dict(num_slots=2, max_context=512, cache_dtype=jnp.float32,
              paged_pool_rows=3 * 512)
    auto = TPUEngine(cfg, params, **kw)
    assert not auto._moe_dense and auto.counts_picks
    got = _greedy(auto, prompt, 256)
    picks, local, rows = (auto.moe_picks_total, auto.moe_picks_local,
                          auto.moe_expert_rows)
    auto.close()
    # chunk 256 + final 64 + 8 steps of the ONE live slot, 2 picks a token,
    # 2 layers
    assert picks == local == 2 * 2 * (256 + 64 + 8)
    assert picks < rows < 4 * picks
    monkeypatch.setattr(moe, "grouped_pays", lambda n_tok, cfg: False)
    dense = TPUEngine(cfg, params, **kw)
    want = _greedy(dense, prompt, 256)
    assert dense.moe_picks_total == picks
    # two prefill graphs x 2 layers x all 8; 8 steps x 2 layers x the 2 picked
    assert dense.moe_experts_visited == 2 * 2 * 8 + 8 * 2 * 2
    # 8 experts over every prompt row; a visit runs both slots' rows
    assert dense.moe_expert_rows == 2 * 8 * (256 + 64) + 2 * (8 * 2 * 2)
    assert auto.moe_experts_visited <= dense.moe_experts_visited
    dense.close()
    assert got == want


# -- which path a graph takes, and what every graph counts ------------------

PATHS = ("moe_ffn_dense", "moe_ffn_grouped", "moe_ffn_dispatch",
         "moe_ffn_visit")


@pytest.fixture
def traced(monkeypatch):
    """The expert paths traced since the last ``clear()``, by name."""
    seen = []
    for name in PATHS:
        real = getattr(moe, name)

        def spy(*a, _real=real, _name=name, **kw):
            seen.append(_name)
            return _real(*a, **kw)

        monkeypatch.setattr(moe, name, spy)
    return seen


@functools.lru_cache(maxsize=None)
def _two_layers():
    cfg = dataclasses.replace(CFG, num_layers=2)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _small_engine(num_slots=2, **kw):
    cfg, params = _two_layers()
    kw.setdefault("paged_pool_rows", num_slots * 512)
    return TPUEngine(cfg, params, num_slots=num_slots, max_context=512,
                     cache_dtype=jnp.float32, **kw)


def _trace_chunk(eng, n_tok):
    """Lower the mid-prompt chunk graph of ``n_tok`` tokens."""
    args = [eng.params, eng.state, jnp.zeros((1, n_tok), jnp.int32),
            jnp.int32(0), jnp.int32(0)]
    if eng.paged:
        args.append(jnp.asarray(eng.allocator.tables[0]))
    eng._make_chunk_jit(False).lower(*args)


@pytest.mark.parametrize("case", [
    "decode", "decode_under_plan", "chunk512", "chunk512_under_plan",
    "train2048"])
def test_the_expert_path_follows_token_count_plan_and_training_alone(
        case, traced):
    """What is left of the choice: a decode dispatch visits the experts its
    live rows picked, a 512-token chunk runs each expert over its own rows,
    the same two graphs of an engine under a sharding plan run every held
    expert over every token (the engine's one bit), and the training forward
    at 2,048 tokens the capacity dispatch. No string and no environment name
    selects a path."""
    from aios_tpu.engine.engine import DECODE_STEPS
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    if case == "train2048":
        cfg, params = _two_layers()
        jax.eval_shape(
            lambda p, t: M.forward_full(p, cfg, t, kernels=False, with_aux=True),
            params, jnp.zeros((4, 512), jnp.int32))
        assert set(traced) == {"moe_ffn_dispatch"}
        return
    plan = None
    if case.endswith("_under_plan"):
        plan = ShardingPlan(build_mesh(8, dp=2, ep=2, tp=2))
    eng = _small_engine(shardings=plan,
                        paged_pool_rows=None if plan else 2 * 512)
    try:
        assert eng._moe_dense is (plan is not None)
        if case.startswith("decode"):
            eng._make_step_jit(DECODE_STEPS).lower(*eng._step_example())
        else:
            _trace_chunk(eng, 512)
    finally:
        eng.close()
    want = {"decode": "moe_ffn_visit", "chunk512": "moe_ffn_grouped"}
    assert set(traced) == {want.get(case, "moe_ffn_dense")}


@pytest.mark.parametrize("num_slots, grouped", [(8, False), (16, True)],
                         ids=["below_the_edge", "above_the_edge"])
def test_a_verify_feed_takes_the_path_a_prefill_of_its_token_count_takes(
        num_slots, grouped, traced):
    """A jump run of 16 tokens a slot feeds 17 rows a slot (the pending
    token leads): 136 at 8 slots and 272 at 16; at top-2 of 8 the grouped
    path pays from 171 rows on. The verify graph asks the question the
    chunk graph of that many tokens asks."""
    from aios_tpu.engine.engine import JUMP_BUCKETS

    n_tok = num_slots * (JUMP_BUCKETS[-1] + 1)
    # pages of 8 rows: a chunk is whole pages, and 136 and 272 are
    eng = _small_engine(num_slots, paged_pool_rows=4 * 512, page_size=8)
    try:
        assert moe.grouped_pays(n_tok, eng.cfg) is grouped
        _trace_chunk(eng, n_tok)
        as_prefill = set(traced)
        traced.clear()
        eng._make_jump_jit().lower(
            eng.params, eng.state, eng._tables_operand(),
            jnp.zeros((num_slots, JUMP_BUCKETS[-1]), jnp.int32),
            jnp.zeros((num_slots,), jnp.int32))
    finally:
        eng.close()
    assert set(traced) == as_prefill
    assert as_prefill == {"moe_ffn_grouped" if grouped else "moe_ffn_dense"}


@pytest.mark.parametrize("graph", ["step", "masked", "spec", "jump"])
def test_router_counters_come_back_from_every_decode_graph_by_the_next_scan_dispatch(
        graph):
    """``moe_picks_total`` ends at rows x top-k x expert layers whichever
    graph fed the rows: a decode step's LIVE rows (the one slot that was
    prefilled), and every slot's in a verify feed, live or not (those graphs
    are of fixed shape and hand no mask). Only the scan graphs (step, masked)
    append the device's sums to their token readback; a speculative round
    and a jump run add into the sums as a prefill does, and the next scan
    dispatch brings them back."""
    eng = _small_engine()
    S, per_row = eng.num_slots, 2 * 2  # top-2, two expert layers
    live = 1
    try:
        eng.prefill(0, [5, 9, 5, 9, 5, 9, 5, 9], temperature=0.0)
        eng.step(1)  # the prefill's counts come back here
        before = eng.moe_picks_total
        if graph == "step":
            eng.step(2)
            rows = 2 * live
        elif graph == "masked":
            eng.step_masked(np.zeros((S, eng.cfg.vocab_size), np.float32))
            rows = live
        else:
            if graph == "spec":
                eng.spec_step(1, draft_len=3)
            else:
                eng.jump_step(np.full((S, 4), 7, np.int32),
                              np.asarray([4, 0], np.int32))
            assert eng.moe_picks_total == before  # held on the device
            eng.step(1)
            # the pending token leads a feed: 3 drafts, or a 4-token run
            rows = (5 if graph == "jump" else 4) * S + live
        assert eng.moe_picks_total == before + rows * per_row
        assert eng.moe_picks_local == eng.moe_picks_total
    finally:
        eng.close()


# -- the chip's form: one kernel a layer call (ops/expert_group.py) ---------

WIDE = dataclasses.replace(  # lane-aligned widths: the kernel's condition
    CFG, name="wide-top2of8", hidden_size=128, moe_intermediate_size=128,
    num_layers=2, head_dim=32)
KINDS = {
    # Mixtral: softmax top-2 of 8, every expert held
    "top2of8": WIDE,
    # the Pangu share: sigmoid x 2.5 top-8 of 256, a sixteenth held from 48 on
    "16of256-from48": dataclasses.replace(
        WIDE, name="wide-16of256", num_experts=256, experts_held=16,
        first_expert=48, num_experts_per_tok=8, moe_scoring="sigmoid",
        routed_scaling_factor=2.5),
    # xing4: sigmoid top-4 of 64 under a selection bias
    "top4of64-bias": dataclasses.replace(
        WIDE, name="wide-top4of64", num_experts=64, num_experts_per_tok=4,
        moe_scoring="sigmoid", routed_scaling_factor=2.0),
    # mellum2: softmax top-8 of 64, the stacks handed whole (`expert_layer`)
    "top8of64-whole": dataclasses.replace(
        WIDE, name="wide-top8of64", num_experts=64, num_experts_per_tok=8),
}


@functools.lru_cache(maxsize=None)
def _wide_layer(kind: str, forced: bool):
    """One expert layer in the fused int8 serving layout (for the "whole"
    kind two layers stacked, read at ``expert_layer`` 1). ``forced``: held
    expert TAKES_ALL gets a pick of every token and TAKES_NONE none (the
    rows' first value is a constant 4)."""
    cfg = KINDS[kind]
    E, F, X, Xr = cfg.hidden_size, cfg.expert_dim, cfg.held_experts, cfg.num_experts
    ks = jax.random.split(jax.random.PRNGKey(len(kind)), 5)
    router = jax.random.normal(ks[0], (E, Xr), jnp.float32) * 0.05
    if forced:
        # logits of +-12 beside the others' unit spread: decided, and the
        # other picks still differ by token
        router = router.at[0, cfg.first_expert + TAKES_ALL].set(3.0)
        router = router.at[0, cfg.first_expert + TAKES_NONE].set(-3.0)
    whole = kind.endswith("whole")
    L = 2 if whole else 1

    def w(k, *shape):
        a = (jax.random.normal(k, (L,) + shape, jnp.float32) * 0.08)
        q, s = ops.quantize_int8(a.astype(jnp.bfloat16), axis=-2)
        return {"q": q, "s": s}

    lp = {"w_router": router, "we_gateup": w(ks[1], X, E, 2 * F),
          "we_down": w(ks[2], X, F, E)}
    if kind == "top4of64-bias":
        lp["router_bias"] = jax.random.normal(ks[3], (Xr,), jnp.float32) * 0.02
    if whole:
        return {**lp, "expert_layer": jnp.int32(1)}
    return {k: (jax.tree.map(lambda a: a[0], v) if k.startswith("we_") else v)
            for k, v in lp.items()}


def _one_layer(lp):
    """The tree the dense path reads: one layer's ``[X, in, out]`` leaves."""
    if "expert_layer" not in lp:
        return lp
    return {k: (jax.tree.map(lambda a: a[1], v) if k.startswith("we_") else v)
            for k, v in lp.items() if k != "expert_layer"}


@pytest.fixture
def interpreted(monkeypatch):
    """The chip's choice of form on the CPU: the kernel, interpreted."""
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(
        expert_group, "expert_group",
        functools.partial(expert_group.expert_group, interpret=True))


@pytest.mark.parametrize("routing", ["free", "one-takes-all"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_kernel_equals_the_loop_and_the_dense_path(
        kind, routing, request, monkeypatch):
    """300 tokens (no expert's count a multiple of the row block but by
    chance) through the loop, the interpreted kernel and the dense path:
    the same picks, the same layout, the same counters, which equal a count
    by hand from the router alone. Forced routing gives one held expert all
    300 tokens, more rows than one unit holds (``row_cap`` held to 256
    here: two products of 128 rows a weight block, then a second unit, a
    second stream of that expert), and another none (no unit, nothing
    read)."""
    monkeypatch.setattr(expert_group, "ROW_CAP_MAX", 256)
    cfg, forced = KINDS[kind], routing == "one-takes-all"
    lp = _wide_layer(kind, forced)
    n_tok, RB = 300, expert_group.ROW_BLOCK
    h = jax.random.normal(jax.random.PRNGKey(n_tok), (1, n_tok, 128), jnp.bfloat16)
    h = h.at[..., 0].set(4.0)
    run = lambda: jax.jit(  # noqa: E731 - traced anew under the fixture
        lambda h, lp: moe.moe_ffn_grouped(h, lp, cfg))(h, lp)
    loop, _, s_loop = run()
    request.getfixturevalue("interpreted")
    kern, _, s_kern = run()
    want, _, s_dense = moe.moe_ffn_dense(h, _one_layer(lp), cfg, with_stats=True)
    loop, kern, want = (np.asarray(a, np.float32) for a in (loop, kern, want))
    tol = TOL * np.abs(want).max()
    assert np.abs(loop - want).max() < tol
    assert np.abs(kern - want).max() < tol
    assert np.abs(kern - loop).max() < tol / 2  # cast for cast the same
    # the counters by hand
    _, _, idx = moe.route(h[0], lp["w_router"], cfg, lp.get("router_bias"))
    rel = np.asarray(idx).ravel() - cfg.first_expert
    counts = np.bincount(rel[(rel >= 0) & (rel < cfg.held_experts)],
                         minlength=cfg.held_experts)
    by_hand = [n_tok * cfg.num_experts_per_tok, int(counts.sum()),
               int(np.sum(-(-counts // RB)) * RB), np.count_nonzero(counts)]
    assert s_loop.tolist() == s_kern.tolist() == by_hand
    assert s_dense.tolist()[:2] == by_hand[:2]
    assert (counts % RB != 0).any()
    cap = expert_group.row_cap(128, 128)
    units = expert_group.unit_list(
        jnp.asarray(-(-counts // RB), jnp.int32), cap, by_hand[0])
    if forced:
        assert counts[TAKES_ALL] == n_tok > cap and counts[TAKES_NONE] == 0
        assert int(units[3]) == by_hand[3] + 1  # one expert takes two units
        both = np.asarray(units[0])[:int(units[3])] == TAKES_ALL
        assert np.asarray(units[2])[:int(units[3])][both].tolist() == [
            cap // RB, -(-(n_tok - cap) // RB)]
    else:
        assert int(units[3]) == by_hand[3]


def test_units_are_an_experts_segment_cut_at_the_row_cap():
    """Blocks (3, 0, 9, 1) at a cap of 4 blocks: expert 0 one unit, expert 1
    none, expert 2 three (4 + 4 + 1 blocks, each starting where the last
    ended), expert 3 one; then the last unit repeated. No block at all: no
    unit. The cap follows from the widths: 256 rows wherever half the
    kernel's VMEM holds them (all four configurations' widths)."""
    RB = expert_group.ROW_BLOCK
    e, first, nb, n = expert_group.unit_list(
        jnp.asarray([3, 0, 9, 1], jnp.int32), 4 * RB, 13 * RB)
    assert int(n) == 5
    assert e.tolist()[:6] == [0, 2, 2, 2, 3, 3]
    assert first.tolist()[:6] == [0, 3, 7, 11, 12, 12]
    assert nb.tolist()[:6] == [3, 4, 4, 1, 1, 1]
    e, first, nb, n = expert_group.unit_list(
        jnp.zeros((4,), jnp.int32), 4 * RB, 13 * RB)
    assert int(n) == 0 and nb.tolist() == [0] * len(nb)
    blocks, first_row = expert_group.segments(jnp.asarray([1, 0, 33, 64]))
    assert blocks.tolist() == [1, 0, 2, 2]
    assert first_row.tolist() == [0, RB, RB, 3 * RB]
    for E, F in ((7680, 2048), (3584, 1024), (2304, 896)):
        assert expert_group.row_cap(E, F) == 512
    assert expert_group.row_cap(4096, 14336) == 256
    assert expert_group.row_cap(4096, 8 * 14336) == 128  # one product at least


@pytest.mark.parametrize("rests", ["1-to-64", "65-to-127"])
def test_a_segments_rest_below_a_pass_has_its_own_product_or_one_more_pass(rests):
    """The kernel alone, interpreted, over segments whose rows beyond the
    whole passes of 128 number 32 and 64 (a product of their own, TAIL) or
    96 (one more pass), with and without a whole pass before the rest, an
    expert with no pick between them and the last expert of the stack: row
    for row the loop's products over the same layout."""
    E = F = 128
    X, RB = 6, expert_group.ROW_BLOCK
    counts = {"1-to-64": [20, 0, 64, 129, 190, 1],    # rests 32, -, 64, 32, 64, 32
              "65-to-127": [70, 0, 96, 200, 353, 65]}[rests]  # 96, -, 96, 96, 96, 96
    blocks, first_row = expert_group.segments(jnp.asarray(counts, jnp.int32))
    rest_rows = (np.asarray(blocks) * RB) % expert_group.PASS
    want_rests = {0, 32, 64} if rests == "1-to-64" else {0, 96}
    assert set(rest_rows.tolist()) == want_rests
    picks = int(sum(counts))
    M_rows = expert_group.buffer_rows(picks, X)
    ks = jax.random.split(jax.random.PRNGKey(sum(counts)), 3)
    x = jax.random.normal(ks[0], (M_rows, E), jnp.bfloat16)

    def w(k, *shape):
        a = jax.random.normal(k, (2,) + shape, jnp.float32) * 0.08
        q, s = ops.quantize_int8(a.astype(jnp.bfloat16), axis=-2)
        return {"q": q, "s": s}

    lp = {"we_gateup": w(ks[1], X, E, 2 * F), "we_down": w(ks[2], X, F, E),
          "expert_layer": jnp.int32(1)}
    cap = 256
    got = expert_group.expert_group(
        x, *expert_group.unit_list(blocks, cap, picks), lp["expert_layer"],
        lp["we_gateup"]["q"], lp["we_gateup"]["s"], lp["we_down"]["q"],
        lp["we_down"]["s"], cap=cap, interpret=True)
    swiglu, down = moe._experts_in_place(lp, F)
    got = np.asarray(got, np.float32)
    for e, (n, r0) in enumerate(zip(counts, np.asarray(first_row).tolist())):
        if not n:
            continue
        rows = slice(r0, r0 + n)
        want = np.asarray(down(swiglu(x[rows], e), e), np.float32)
        assert np.abs(got[rows] - want).max() < TOL / 2 * np.abs(want).max(), e
    if rests == "65-to-127":  # 353 rows: a second unit of that expert
        assert int(expert_group.unit_list(blocks, cap, picks)[3]) == 6


@pytest.mark.parametrize("picks, X", [(2048, 64), (300, 8), (4097, 16), (7, 3)])
def test_the_two_level_rank_equals_a_cumsum_over_all_the_picks(picks, X):
    """``moe._ranks`` on random keys, some of them ``X`` (a pick on an expert
    held elsewhere): each pick's count of earlier picks on its own expert and
    each expert's total, as a one-hot cumsum over all the picks gives them;
    a pick on no held expert ranks 0."""
    key = jax.random.randint(jax.random.PRNGKey(picks), (picks,), 0, X + 1)
    rank, counts = jax.jit(moe._ranks, static_argnums=1)(key, X)
    onehot = np.eye(X + 1, dtype=np.int64)[np.asarray(key)][:, :X]
    want = ((np.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
    assert rank.dtype == counts.dtype == jnp.int32
    assert np.asarray(rank).tolist() == want.tolist()
    assert np.asarray(counts).tolist() == onehot.sum(0).tolist()
    assert (np.asarray(key) == X).any() or picks < 16
