"""Latent attention (MLA), sandwich norms, a leading dense layer and an expert
layer that holds a share of its experts, on the paged serving path: the
program against the plain reference (benchmark/archs/pangu_ultra_moe.py, which
imports nothing of the program), at a small size on the CPU, on seeded random
weights in the serving types (int8 matrices, bfloat16 norms, router and
activations). LOGITS are compared, never tokens.

Every tolerance states its reason and comes with a control that has to exceed
it: the reference with every matrix re-quantized to int4 (the step below the
int8 these weights are served in), or the same mathematics accumulated in
bfloat16 where float32 is stated.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from aios_tpu import ops  # noqa: E402
from aios_tpu.engine import latent, model, moe, paged  # noqa: E402
from aios_tpu.engine.config import ModelConfig  # noqa: E402
from aios_tpu.ops import expert_group  # noqa: E402
from benchmark.harness import reference  # noqa: E402
from benchmark.harness.manifest import load_file  # noqa: E402

A = load_file(os.path.join(REPO, "benchmark", "archs", "pangu_ultra_moe.py"),
              "benchmark_arch")

# hidden 64, 4 heads of 16 + 8, ranks 24 / 16, 32 experts of which 8 are held
# (from the 8th on), top-4, 1 dense + 2 expert layers
TINY = dict(
    num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=512, n_routed_experts=8, router_n_experts=32,
    first_routed_expert=8, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, rope_theta=25600000.0,
    rms_norm_eps=1e-5, max_position_embeddings=128,
    assumed={"served_name": "tiny-pangu"},
)
SEED = 2 ** 31 + 11
P = 16  # rows of a page
D = A.dims_of(TINY)
CFG = ModelConfig(**A.model_fields(TINY, 128))

# Where the reference's router margin (here the 4th against the 5th logit, of
# a standard deviation of 0.16) is under this in any layer, bfloat16
# activations (an error of about 0.001 in a router logit) pick another expert
# than float32 does and the logits move wholesale (0.08-0.13 read there, the
# size of the int4 control): such positions say nothing of the arithmetic and
# are left out, as the benchmark's `correct` leaves them out.
MARGIN = 0.004
# bfloat16 activations and cache against the float32 reference on int8
# weights: 0.006-0.009 read over 4 seeds at a logit std of 0.17; the int4
# control reads 0.2-0.35. Between the two, with room on both sides.
LOGIT_TOL = 0.03


@pytest.fixture(scope="module")
def params():
    """The benchmark's tree (the checkpoint layout), converted once as the
    engine converts it at load: every graph reads the serving layout."""
    return latent.serving_layout(A.build_params(D, SEED), CFG)[0]


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, D.vocab, n)]


def _reference(seqs, precisions=("float32",)):
    out = reference.logits_for(A, D, SEED, seqs, [0] * len(seqs), precisions, pad_to=0)
    decided = [m.min(-1) >= MARGIN for m in out["router_margin"]]
    return out, decided


def _pools(pages=24):
    widths = CFG.kv_row_dims
    assert widths == (16, 128)
    return tuple(jnp.zeros((CFG.num_layers, pages, P, w), jnp.bfloat16) for w in widths)


def _bf(a):
    """What bfloat16 keeps of float32 values."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _weighted_sum_bf16(p, rows):
    """sum_s p[..., s] * rows[..., s, :] with a bfloat16 ACCUMULATOR: rounded
    after every add, which is what replacing float32 accumulation means."""
    terms = jnp.moveaxis(p[..., None] * rows, -2, 0)
    acc, _ = jax.lax.scan(lambda a, t: (_bf(a + t), None), jnp.zeros_like(terms[0]), terms)
    return acc


def test_config_fields_and_stored_row():
    assert CFG.mla and CFG.expert_share and CFG.held_experts == 8
    assert CFG.num_experts == 32 and CFG.first_expert == 8
    # the published row is kv_lora_rank + qk_rope_head_dim values; the rotary
    # part is stored as one whole lane tile
    assert CFG.kv_row_dims == (16, 128)
    grouped_query = ModelConfig(name="g", vocab_size=8, hidden_size=8,
                                intermediate_size=8, num_layers=1, num_heads=2,
                                num_kv_heads=1, head_dim=4)
    assert grouped_query.kv_row_dims == (4, 4) and not grouped_query.mla
    with pytest.raises(ValueError, match="latent-attention block only"):
        dataclasses.replace(grouped_query, sandwich_norm=True)
    with pytest.raises(ValueError, match="are not among the router's"):
        dataclasses.replace(CFG, first_expert=30)


def test_full_forward_matches_reference(params):
    """The expanded form over a whole prompt (what a bucketed prefill runs),
    through the leading dense layer and both expert layers."""
    ids = _ids(96)
    ref, decided = _reference([ids], ("float32", "int4"))
    got = np.asarray(model.forward_full(params, CFG, jnp.asarray([ids]), kernels=False))[0]
    keep = decided[0]
    assert keep.sum() > 40
    assert np.abs(got - ref["float32"][0])[keep].max() < LOGIT_TOL
    assert np.abs(ref["int4"][0] - ref["float32"][0])[keep].max() > LOGIT_TOL


def test_chunked_prefill_then_decode_through_the_latent_pool(params):
    """Two slots of unequal length on one pool: slot 0 admits 40 rows in
    chunks of 16 + 16 + 8 over pages 3, 1, 4 (page boundaries crossed inside
    the prompt and again while decoding); slot 1 maps slot 0's first page as a
    prefix-cache hit would and admits only its tail behind it. Then both
    decode 12 steps in one batch. Every logit row, prefill and decode, is held
    to the reference's full forward of the same tokens."""
    a, tail_b = _ids(52, 1), _ids(20, 2)
    b = a[:P] + tail_b  # shares a's first page
    ref, decided = _reference([a, b], ("float32", "int4"))
    c_pool, r_pool = _pools()
    tables = jnp.asarray([[3, 1, 4, 7, 0, 0, 0, 0], [3, 5, 9, 2, 0, 0, 0, 0]], jnp.int32)
    rows = {0: [], 1: []}

    def chunk(slot, toks, start):
        nonlocal c_pool, r_pool
        lg, c_pool, r_pool, picks = latent.prefill_chunk_paged(
            params, CFG, jnp.asarray([toks]), jnp.int32(start), c_pool, r_pool,
            tables[slot])
        assert int(picks[0]) == len(toks) * CFG.num_experts_per_tok * 2
        rows[slot].extend(np.asarray(lg)[0])

    for start, n in ((0, 16), (16, 16), (32, 8)):
        chunk(0, a[start:start + n], start)
    chunk(1, b[P:P + 8], P)  # behind the shared page: rows 16..23
    lengths = np.array([40, 24])
    for step in range(12):
        toks = jnp.asarray([a[lengths[0]], b[lengths[1]]])
        lg, c_pool, r_pool, picks = latent.decode_step_paged(
            params, CFG, toks, jnp.asarray(lengths), c_pool, r_pool, tables,
            kernels=False)
        total, local, expert_rows, visited = picks.tolist()
        assert total == 2 * CFG.num_experts_per_tok * 2
        # the held experts a row picked, each over both rows, in 2 layers
        assert local // 2 <= visited <= local
        assert expert_rows == 2 * visited <= 2 * CFG.held_experts * 2
        rows[0].append(np.asarray(lg)[0])
        rows[1].append(np.asarray(lg)[1])
        lengths += 1
    got_a, got_b = np.stack(rows[0]), np.stack(rows[1])
    assert got_a.shape[0] == 52 and got_b.shape[0] == 20
    err_a = np.abs(got_a - ref["float32"][0])[decided[0]]
    err_b = np.abs(got_b - ref["float32"][1][P:P + 20])[decided[1][P:P + 20]]
    assert decided[0].sum() > 20 and decided[1][P:].sum() > 8
    assert err_a.max() < LOGIT_TOL and err_b.max() < LOGIT_TOL
    control = np.abs(ref["int4"][0] - ref["float32"][0])[decided[0]]
    assert control.max() > LOGIT_TOL


def test_absorbed_decode_matches_expanded_attention(params):
    """One layer's attention for the newest row, both forms on the SAME
    cached latents: absorbed (decode: the query carried into the latent
    space, the result out of it) against expanded (prefill: keys and values
    made from the latents). Both bfloat16 with float32 accumulation; they
    differ by the rounding of q_lat and o_lat to bfloat16, 2^-8 relative a
    value: 0.003-0.006 of the largest output read, held to 0.01 of it. With
    the 120 rows' weighted sum kept in a bfloat16 accumulator the absorbed
    form exceeds that."""
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    n = 120
    h = jax.random.normal(jax.random.PRNGKey(3), (1, n, CFG.hidden_size), jnp.bfloat16)
    pos = jnp.arange(n)[None]
    q_nope, q_rope, c, k_r = latent._project(h, lp, CFG, pos)
    k_nope, v = latent._expand(c[0], lp)

    def whole(j):
        return k_nope, k_r[0], v

    expanded = latent._attend_expanded(
        q_nope[0, -1:], q_rope[0, -1:], jnp.asarray([n - 1]), whole, 1, n,
        latent.sm_scale(CFG), CFG.v_head_dim)[0].reshape(-1)
    q_lat = latent._absorb_q(q_nope[0, -1], lp)  # [H, Dc]

    f32 = jnp.float32
    s = (jnp.einsum("hc,sc->hs", q_lat.astype(f32), c[0].astype(f32))
         + jnp.einsum("hr,sr->hs", q_rope[0, -1].astype(f32), k_r[0].astype(f32))
         ) * latent.sm_scale(CFG)
    p = _bf(jax.nn.softmax(s, axis=-1))

    def finish(o_lat):
        return np.asarray(latent._unabsorb_o(o_lat.astype(c.dtype), lp), f32)

    want = np.asarray(expanded, f32)
    tol = 0.01 * np.abs(want).max()
    assert np.abs(finish(jnp.einsum("hs,sc->hc", p, c[0].astype(f32))) - want).max() < tol
    low = finish(_weighted_sum_bf16(p[:, None, :], c[0].astype(f32)[None, None])[:, 0])
    assert np.abs(low - want).max() > tol


# (heads, nope, rope, v, q_rank, kv_rank): the Pangu-like model of this file
# and a Xing4-like one (more heads, wider ranks, a value head narrower than a key's)
LAYOUT_SHAPES = {"pangu-like": (4, 16, 8, 16, 24, 16), "xing4-like": (8, 16, 8, 8, 48, 32)}


def _checkpoint_tree(cfg, int8, key):
    """lead_layers (1 layer) and layers (2) holding what `_project` reads, in the
    checkpoint layout: int8 leaves {"q", "s"} or plain float32 arrays."""
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, kl, E = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.hidden_size
    shapes = {"w_dqkv": (E, ql + kl + dr), "w_uq": (ql, H * (dn + dr)),
              "w_uk": (kl, H * dn), "w_uv": (kl, H * dv)}

    def stack(L, key):
        out = {"q_a_norm": 1 + 0.1 * jax.random.normal(key, (L, ql)),
               "kv_a_norm": 1 + 0.1 * jax.random.normal(key, (L, kl))}
        for i, (name, (K, N)) in enumerate(shapes.items()):
            kq, ks = jax.random.split(jax.random.fold_in(key, i))
            if int8:
                out[name] = {
                    "q": jax.random.randint(kq, (L, K, N), -127, 128, jnp.int8),
                    "s": 0.002 * (0.5 + jax.random.uniform(ks, (L, 1, N)))}
            else:
                out[name] = 0.2 * jax.random.normal(kq, (L, K, N))
        return out

    return {"lead_layers": stack(1, jax.random.fold_in(key, 100)),
            "layers": stack(2, jax.random.fold_in(key, 200))}


def _plain(w):
    return w["q"].astype(jnp.float32) * w["s"] if isinstance(w, dict) else w


@pytest.mark.parametrize("leaves", ["int8", "plain"])
@pytest.mark.parametrize("shape", sorted(LAYOUT_SHAPES))
def test_serving_layout_is_exact_and_idempotent(shape, leaves):
    """`serving_layout` permutes columns and transposes whole blocks: over the
    converted tree `_project`'s four results, `_absorb_q`, `_unabsorb_o` and
    `_expand` are the checkpoint layout's products written out in plain
    jax.numpy (`cq @ w_uq` reshaped and cut, a per-head einsum), to float32
    round-off (float32 rows in: the two sides differ in where an int8 leaf's
    scales are applied and in the order of the sums; 1e-6 of the largest value
    read, held to 2e-5 of it). Converting twice is converting once."""
    H, dn, dr, dv, ql, kl = LAYOUT_SHAPES[shape]
    cfg = dataclasses.replace(
        CFG, num_heads=H, qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv,
        q_lora_rank=ql, kv_lora_rank=kl)
    raw = _checkpoint_tree(cfg, leaves == "int8", jax.random.PRNGKey(7))
    laid, relaid = latent.serving_layout(raw, cfg)
    assert relaid == 6  # w_uq, w_uk, w_uv of both layer trees
    assert "w_uq" not in laid["layers"] and "w_uq" in raw["layers"]
    nope = laid["layers"]["w_uq_nope"]
    assert (nope["q"] if leaves == "int8" else nope).shape == (2, H, ql, dn)
    again, second = latent.serving_layout(laid, cfg)
    assert second == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(laid), jax.tree.leaves(again)))

    def close(got, want):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                                   atol=2e-5 * np.abs(want).max())

    B, T, eps = 2, 5, cfg.rms_norm_eps
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    h = jax.random.normal(ks[0], (B, T, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    cos, sin = latent.rope_tables(pos, cfg)
    for seg, l in (("lead_layers", 0), ("layers", 1)):
        lp = jax.tree.map(lambda a: a[l], laid[seg])
        w = {k: _plain(jax.tree.map(lambda a: a[l], raw[seg][k]))
             for k in ("w_dqkv", "w_uq", "w_uk", "w_uv")}
        down = h @ w["w_dqkv"]
        cq = model.rms_norm(down[..., :ql], raw[seg]["q_a_norm"][l], eps)
        q = (cq @ w["w_uq"]).reshape(B, T, H, dn + dr)
        c = model.rms_norm(down[..., ql:ql + kl], raw[seg]["kv_a_norm"][l], eps)
        want = (q[..., :dn], model.apply_rope(q[..., dn:], cos, sin), c,
                model.apply_rope(down[..., None, ql + kl:], cos, sin)[:, :, 0])
        got = latent._project(h, lp, cfg, pos)
        for g, x in zip(got, want):
            close(g, x)
        q_lat = jnp.einsum("bthd,chd->bthc", want[0], w["w_uk"].reshape(kl, H, dn))
        close(latent._absorb_q(got[0], lp), q_lat)
        o_lat = jax.random.normal(ks[1], (B, H, kl))
        close(latent._unabsorb_o(o_lat, lp),
              jnp.einsum("bhc,chd->bhd", o_lat, w["w_uv"].reshape(kl, H, dv)).reshape(B, -1))
        rows = jax.random.normal(ks[2], (7, kl))
        k_nope, v = latent._expand(rows, lp)
        close(k_nope, (rows @ w["w_uk"]).reshape(7, H, dn))
        close(v, (rows @ w["w_uv"]).reshape(7, H, dv))


@pytest.mark.parametrize("pages_per_iter", [1, 2, 4])
def test_mla_kernel_interpret_matches_reference(pages_per_iter):
    """The Pallas kernel (interpret mode) against its jnp reference, lengths
    that end inside a page, on a page boundary, at one row and over an
    iteration's pages. Both take bfloat16 pages and accumulate in float32;
    the order of the float32 sums differs: 1e-5 of the largest output read,
    held to 0.002 of it. The same sums in a bfloat16 accumulator exceed
    that."""
    B, H, Dc, Dr, L, N, MB = 5, 4, 128, 128, 2, 48, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    # float32 arrays of bfloat16 VALUES: the CPU's dot thunk, which interpret
    # mode runs on, refuses bf16 x bf16 -> f32
    bf = _bf
    q_lat = bf(jax.random.normal(ks[0], (B, H, Dc)))
    q_rope = jnp.pad(bf(jax.random.normal(ks[1], (B, H, 8))),
                     ((0, 0), (0, 0), (0, Dr - 8)))
    c_pool = bf(jax.random.normal(ks[2], (L, N, P, Dc)))
    r_pool = jnp.pad(bf(jax.random.normal(ks[3], (L, N, P, 8))),
                     ((0, 0),) * 3 + ((0, Dr - 8),))
    tables = jnp.asarray(np.random.RandomState(1).permutation(N - 1)[:B * MB]
                         .reshape(B, MB) + 1, jnp.int32)
    lengths = jnp.asarray([0, 15, 16, 77, 127], jnp.int32)
    scale = 24 ** -0.5
    want = ops.paged_mla_decode_attention_reference(
        q_lat, q_rope, c_pool, r_pool, 1, tables, lengths, sm_scale=scale)
    got = ops.paged_mla_decode_attention(
        q_lat, q_rope, c_pool, r_pool, jnp.int32(1), tables, lengths,
        sm_scale=scale, pages_per_iter=pages_per_iter, interpret=True)
    want32 = np.asarray(want, np.float32)
    tol = 0.002 * np.abs(want32).max()
    assert np.abs(np.asarray(got, np.float32) - want32).max() < tol

    c = c_pool[1, tables].reshape(B, -1, Dc)
    r = r_pool[1, tables].reshape(B, -1, Dr)
    s = (jnp.einsum("bhc,bsc->bhs", q_lat, c) + jnp.einsum("bhr,bsr->bhs", q_rope, r)
         ) * scale
    s = jnp.where((jnp.arange(c.shape[1])[None] <= lengths[:, None])[:, None], s, -1e30)
    low = _weighted_sum_bf16(jax.nn.softmax(s, axis=-1), c[:, None])
    assert np.abs(np.asarray(low, np.float32) - want32).max() > tol


def test_grouped_experts_match_dense_over_held(params):
    """The dropless grouped path (prefill token counts) against every held
    expert over every token, on the program's own expert layer: the same
    picks, the same int8 weights, float32 accumulation in both; they differ
    in the ORDER the experts' parts are added (float32 against bfloat16
    partial sums), 2^-8 of the result: 0.004 of the largest value read. Rows
    follow the picks that landed here, a row block at a time."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 256, CFG.hidden_size), jnp.bfloat16)
    assert moe.grouped_pays(256, CFG) and not moe.grouped_pays(128, CFG)
    dense, _, s_dense = moe.moe_ffn_dense(h, lp, CFG, with_stats=True)
    grouped, _, s_grouped = moe.moe_ffn_grouped(h, lp, CFG)
    want = np.asarray(dense, np.float32)
    assert np.abs(np.asarray(grouped, np.float32) - want).max() < 0.02 * np.abs(want).max()
    total, local, rows, visited = s_grouped.tolist()
    assert (total, local) == tuple(s_dense.tolist()[:2]) == (256 * 4, local)
    assert 0 < local < total and s_dense.tolist()[2:] == [256 * 8, 8]
    RB = expert_group.ROW_BLOCK
    assert visited * RB <= rows and 0 < visited <= 8
    # an expert's segment rounded up to a row block; never a dropped pick
    assert local <= rows < local + 8 * RB and rows % RB == 0
    # and through model.ffn the static token count alone chooses between them
    out, _, stats = model.ffn(h, lp, CFG)
    assert stats.tolist() == s_grouped.tolist()
    shared = model._swiglu(h, lp, "ws_", CFG.expert_dim)
    np.testing.assert_allclose(
        np.asarray(out - shared, np.float32), np.asarray(grouped, np.float32),
        atol=0.02 * np.abs(want).max())


def test_shares_add_up_to_the_uncut_layer(params):
    """What ties the share to the model: on the PROGRAM's own expert layer
    (model.ffn), the routed parts of all four shares of 8 experts, with the
    shared expert counted once, add up to the reference's uncut layer (all 32
    experts held). Expert e is made from fold_in(key, e), so a share holds the
    bytes the uncut layer has for it. bfloat16 activations against float32:
    0.01 of the largest value read; the int4 control reads 0.1 and over."""
    uncut = dataclasses.replace(D, held=D.experts, first=0)
    lw = A.build_layer(uncut, SEED, 2)
    h = jax.random.normal(jax.random.PRNGKey(4), (48, D.hidden), jnp.float32)
    with jax.default_matmul_precision("highest"):
        parts = {p: A.moe_parts(uncut, h, lw, p) for p in ("float32", "int4")}
    want = np.asarray(parts["float32"][0] + parts["float32"][1])
    control = np.asarray(parts["int4"][0] + parts["int4"][1])
    decided = np.asarray(parts["float32"][2]) >= MARGIN
    hb = h.astype(jnp.bfloat16)[None]
    total = np.zeros_like(want)
    for first in range(0, D.experts, D.held):
        share = dataclasses.replace(D, first=first)
        lp = A.build_layer(share, SEED, 2)
        cfg = dataclasses.replace(CFG, first_expert=first)
        np.testing.assert_array_equal(  # the same bytes as the uncut layer's
            np.asarray(lp["we_down"]["q"]),
            np.asarray(lw["we_down"]["q"][first:first + D.held]))
        out, _, stats = model.ffn(hb, lp, cfg)
        shared = model._swiglu(hb, lp, "ws_", cfg.expert_dim)
        total += np.asarray(out - shared, np.float32)[0]
        if first == 0:
            total += np.asarray(shared, np.float32)[0]
    tol = 0.03 * np.abs(want).max()
    assert decided.sum() > 20
    assert np.abs(total - want)[decided].max() < tol
    assert np.abs(control - want)[decided].max() > tol


def test_sigmoid_router_scales_and_ranks_all_experts(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(2), (10, CFG.hidden_size), jnp.float32)
    scores, weights, idx = moe.route(h, lp["w_router"], CFG)
    assert scores.shape == (10, 32) and idx.shape == (10, 4)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-5)
    assert int(idx.max()) > CFG.first_expert + CFG.held_experts - 1 or int(idx.min()) < 8
    w_here, idx_here, here = moe.local_picks(weights, idx, CFG)
    assert ((np.asarray(w_here) == 0) == ~np.asarray(here)).all()
    assert 0 <= int(idx_here.min()) and int(idx_here.max()) < CFG.held_experts


def test_latent_page_entries_are_refused_by_name():
    """The host spill tier and KVX carry K/V pages only: a latent page (its
    two arrays differ in width) is refused loudly, never shipped as keys and
    values."""
    latent_page = {"k": np.zeros((3, P, 16), np.float32), "v": np.zeros((3, P, 128), np.float32)}
    with pytest.raises(paged.LatentEntryUnsupported, match="latent"):
        paged.pack_entry(latent_page)
    with pytest.raises(paged.LatentEntryUnsupported):
        paged.HostPageStore(1 << 20).put(b"h", latent_page)
    kv_page = {"k": np.ones((3, P, 16), np.float32), "v": np.ones((3, P, 16), np.float32)}
    assert paged.unpack_entry(paged.pack_entry(kv_page))["k"].shape == (3, P, 16)


def _engine(params, **kw):
    from aios_tpu.engine.engine import TPUEngine

    kw.setdefault("paged_pool_rows", 3 * 128)
    return TPUEngine(CFG, params, num_slots=2, max_context=128, page_size=P, **kw)


@pytest.mark.parametrize("kw,named", [
    (dict(paged_pool_rows=None), "dense slot cache"),
    (dict(cache_dtype=jnp.int8), "int8 KV pool"),
    (dict(kv_compress_after=64), "window and sink KV compression"),
    (dict(seq_prefill_min=64), "sequence sharded prefill"),
    (dict(prefix_host_bytes=1 << 20), "host spill tier"),
])
def test_engine_refuses_what_a_latent_pool_cannot_serve(params, kw, named):
    with pytest.raises(ValueError, match=f"tiny-pangu.*{named}"):
        _engine(params, **kw)


def test_engine_serves_through_prefix_cache_and_counts_picks(params):
    """The engine's own path: chunked admission, a prefix-cache hit on the
    second prompt, batched decode, the constrained decoder's masked step and
    jump-ahead append; the served greedy tokens are held to the reference by
    the gap of their logit under the reference's best (the benchmark's
    `correct`), and the counters come back with the tokens."""
    eng = _engine(params)
    try:
        system = _ids(64, 7)
        first = eng.generate(system + _ids(9, 8), max_new_tokens=6, temperature=0.0)
        eng.release(0)
        prompt = system + _ids(11, 9)
        tok = eng.prefill(0, prompt, temperature=0.0)
        stats = eng.stats()
        assert stats["prefix_hits"] >= 1 and stats["prefix_rows_reused"] >= 64
        assert stats["kv_row_bytes"] == (16 + 128) * 2
        served = [tok] + [int(eng.step(1)[0, 0]) for _ in range(5)]
        mask = np.zeros((2, CFG.vocab_size), np.float32)
        served.append(int(eng.step_masked(mask)[0, 0]))
        forced = np.zeros((2, 4), np.int32)
        forced[0, :3] = [5, 6, 7]
        eng.jump_step(forced, np.asarray([3, 0], np.int32))
        served += [5, 6, 7, int(eng.step(1)[0, 0])]
        stats = eng.stats()
        assert stats["moe_picks_total"] > 0 and stats["jump_dispatches"] == 1
        assert 0 < stats["moe_picks_local"] < stats["moe_picks_total"]
        assert stats["moe_expert_rows"] >= stats["moe_picks_local"]
        assert len(first) == 6
        seq = prompt + served
        ref, decided = _reference([seq])
        logits = ref["float32"][0]
        free = [i for i in range(len(served)) if i not in (7, 8, 9)]  # forced ones
        rows = np.asarray([len(prompt) - 1 + i for i in free])
        gaps = reference.served_gaps(logits[rows], [served[i] for i in free])
        # a greedy token of sound arithmetic lies within the two sides'
        # rounding of the reference's best wherever routing is decided
        assert gaps[decided[0][rows]].max() < 2 * LOGIT_TOL
    finally:
        eng.close()


def test_engine_lays_the_checkpoint_tree_out_at_load_and_counts_it():
    """The benchmark's tree as it is made (the checkpoint layout) through
    TPUEngine: the engine converts it, says how many matrices it re-laid
    (`latent_leaves_relaid`; none for a tree that is laid out already, which is
    how a scale-up's engine gets replica 0's, and none for a grouped-query
    model), and the greedy tokens it decodes are `forward_with_kv`'s on the
    converted tree: each lies within the two forms' rounding (absorbed against
    expanded, LOGIT_TOL) of that forward's best."""
    from aios_tpu.engine.config import TINY_TEST
    from aios_tpu.engine.engine import TPUEngine

    raw = A.build_params(D, SEED)
    eng = _engine(raw)
    try:
        assert eng.stats()["latent_leaves_relaid"] == 6
        assert "w_uq" not in eng.params["layers"] and "w_uq" in raw["layers"]
        prompt = _ids(40, 5)
        served = eng.generate(prompt, max_new_tokens=8, temperature=0.0)
        laid, _ = latent.serving_layout(raw, CFG)
        seq = prompt + served
        logits = np.asarray(model.forward_full(
            laid, CFG, jnp.asarray([seq]), kernels=False))[0]
        rows = logits[len(prompt) - 1:len(seq) - 1]
        gaps = rows.max(-1) - rows[np.arange(len(served)), served]
        assert gaps.max() < LOGIT_TOL
        assert (gaps == 0).sum() >= len(served) - 1
        again = _engine(eng.params)
        try:
            assert again.stats()["latent_leaves_relaid"] == 0
            assert again.generate(prompt, max_new_tokens=8, temperature=0.0) == served
        finally:
            again.close()
    finally:
        eng.close()
    plain = TPUEngine(TINY_TEST, model.init_params(TINY_TEST, jax.random.PRNGKey(0)),
                      num_slots=2, max_context=64)
    try:
        assert plain.stats()["latent_leaves_relaid"] == 0
    finally:
        plain.close()


def test_speculation_is_refused_for_a_latent_pool(params):
    from aios_tpu.engine.engine import refuse_for_latent_pool

    with pytest.raises(ValueError, match="tiny-pangu.*speculative decoding"):
        refuse_for_latent_pool(CFG, speculative_decoding_with_verify_step_paged=True)
    refuse_for_latent_pool(CFG, speculative_decoding_with_verify_step_paged=False)


def test_kvx_fetch_refuses_a_latent_engine_by_name(params):
    """The transfer plane's Fetch names the refusal instead of shipping a
    latent page as keys and values."""
    import grpc

    from aios_tpu.fleet import kvx

    eng = _engine(params)

    class Aborted(Exception):
        pass

    class Context:
        def abort(self, code, detail):
            self.code, self.detail = code, detail
            raise Aborted(detail)

    try:
        eng.generate(_ids(40, 3), max_new_tokens=2, temperature=0.0)
        hashes = eng.prefix_hashes(_ids(40, 3))
        assert hashes
        with pytest.raises(paged.LatentEntryUnsupported, match="tiny-pangu"):
            eng.export_hashes(hashes)
        managed = type("M", (), {"engine": eng})()
        service = kvx.KvxService(type("Mgr", (), {"get": lambda self, n: managed})())
        ctx = Context()
        request = type("R", (), {"model": "tiny-pangu", "hashes": hashes, "budget_bytes": 0})()
        with pytest.raises(Aborted, match="no KVX entry kind"):
            list(service.Fetch(request, ctx))
        assert ctx.code == grpc.StatusCode.FAILED_PRECONDITION
    finally:
        eng.close()
