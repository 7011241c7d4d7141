"""The paged decode kernel against its reference, and what the decode step
does to the pool around it (tier 1: interpret mode, tiny shapes, seconds).

The kernel takes the WHOLE stacked pool [L, N, P, KH*D] and a layer index
and reads the pages the tables name where they lie. So every parity case
runs on a three-layer pool whose layers hold different values, at the
first, a middle and the last layer: a kernel that read another layer's
pages would fail. The engine-level equivalences (paged == dense token for
token) are in tests/test_paged.py (slow tier).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aios_tpu.engine import model
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.ops import (
    decode_attention_reference,
    paged_decode_attention,
    paged_decode_attention_int8,
    paged_decode_attention_int8_reference,
    paged_decode_attention_reference,
    write_rows,
)

L = 3
LAYERS = [0, 1, L - 1]  # first, middle, last
MASKS = {
    "plain": {},
    "window": {"window": 24},
    "win_starts+sink": {"sink": 16},  # win_starts added per case
}


def stacked_pools(rng, B, C, KH, D, P, int8=False):
    """Dense [L, B, C, KH, D] caches, every layer different, and a stacked
    page pool holding the same rows behind ONE shuffled page table (page 0
    is the sacrificial page and holds noise no table maps)."""
    MB = C // P
    N = 1 + B * MB
    tables = 1 + rng.permutation(B * MB).reshape(B, MB)

    def one():
        if int8:
            dense = rng.integers(-127, 128, (L, B, C, KH, D)).astype(np.int8)
        else:
            dense = rng.normal(size=(L, B, C, KH, D)).astype(np.float32)
        pool = rng.integers(-9, 9, (L, N, P, KH * D)).astype(dense.dtype)
        pool[:, tables] = dense.reshape(L, B, MB, P, KH * D)
        return jnp.asarray(dense), jnp.asarray(pool)

    (kd, kp), (vd, vp) = one(), one()
    return kd, vd, kp, vp, jnp.asarray(tables, jnp.int32)


def mask_kwargs(mask, win_starts):
    kw = dict(MASKS[mask])
    if "sink" in kw:
        kw["win_starts"] = jnp.asarray(win_starts, jnp.int32)
    return kw


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("window", [None, 24])
def test_paged_reference_matches_dense_reference(window, layer):
    rng = np.random.default_rng(0)
    B, C, KH, D, H, P = 3, 64, 2, 8, 4, 16
    kd, vd, kp, vp, tables = stacked_pools(rng, B, C, KH, D, P)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    lengths = jnp.asarray([5, 31, 63], jnp.int32)
    ref = decode_attention_reference(
        q, kd[layer], vd[layer], lengths, window=window
    )
    got = paged_decode_attention_reference(
        q, kp, vp, layer, tables, lengths, window=window
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("pool", ["float", "int8"])
def test_paged_kernel_matches_reference(pool, mask, layer):
    """One kernel body for both pool dtypes and every mask, on the layer it
    was asked for. `layer` goes in traced, as the layer scan hands it."""
    rng = np.random.default_rng(1)
    B, C, KH, D, H, P = 3, 64, 2, 16, 8, 16
    int8 = pool == "int8"
    _, _, kp, vp, tables = stacked_pools(rng, B, C, KH, D, P, int8=int8)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    lengths = jnp.asarray([0, 29, 63], jnp.int32)
    kw = mask_kwargs(mask, [0, 16, 32])
    lyr = jnp.asarray(layer, jnp.int32)
    if int8:
        N = kp.shape[1]
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (L, N, P, KH)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (L, N, P, KH)), jnp.float32)
        got = jax.jit(
            lambda i: paged_decode_attention_int8(
                q, kp, vp, ks, vs, i, tables, lengths, interpret=True, **kw
            )
        )(lyr)
        ref = paged_decode_attention_int8_reference(
            q, kp, vp, ks, vs, layer, tables, lengths, **kw
        )
        others = [
            paged_decode_attention_int8_reference(
                q, kp, vp, ks, vs, o, tables, lengths, **kw
            )
            for o in range(L) if o != layer
        ]
    else:
        got = jax.jit(
            lambda i: paged_decode_attention(
                q, kp, vp, i, tables, lengths, interpret=True, **kw
            )
        )(lyr)
        ref = paged_decode_attention_reference(
            q, kp, vp, layer, tables, lengths, **kw
        )
        others = [
            paged_decode_attention_reference(
                q, kp, vp, o, tables, lengths, **kw
            )
            for o in range(L) if o != layer
        ]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    for other in others:  # the case can tell the layers apart
        assert float(jnp.max(jnp.abs(other - ref))) > 1e-2


@pytest.mark.parametrize("layer", LAYERS)
def test_paged_kernel_ignores_unmapped_pages(layer):
    """Rows beyond a slot's length live on pages the table never reads —
    poisoning every such page of the layer, the sacrificial page and EVERY
    page of the other layers must not change the output."""
    rng = np.random.default_rng(2)
    B, C, KH, D, H, P = 1, 64, 2, 8, 4, 16
    _, _, kp, vp, tables = stacked_pools(rng, B, C, KH, D, P)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    lengths = jnp.asarray([20], jnp.int32)  # blocks 0-1 valid; 2-3 unread
    base = paged_decode_attention(
        q, kp, vp, layer, tables, lengths, interpret=True
    )
    keep = [int(tables[0, 0]), int(tables[0, 1])]
    poison_k = jnp.full_like(kp, 1e9).at[layer, keep].set(kp[layer, keep])
    poison_v = jnp.full_like(vp, 1e9).at[layer, keep].set(vp[layer, keep])
    got = paged_decode_attention(
        q, poison_k, poison_v, layer, tables, lengths, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("all_layers", [True, False])
@pytest.mark.parametrize(
    "T,offset", [(16, 0), (48, 0), (8, 0), (4, 12)],
    ids=["one-page", "three-pages", "half-page", "inside-a-page"],
)
def test_write_rows_matches_the_row_scatter(T, offset, all_layers):
    """Whole pages or one slice of a page: the same pool as writing the
    rows one by one at (page, row), in every layer or in the one asked."""
    rng = np.random.default_rng(3)
    N, P, W = 9, 16, 32
    pool = jnp.asarray(rng.normal(size=(L, N, P, W)), jnp.float32)
    blocks = jnp.asarray([5, 2, 7, 1], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(L, T, W)), jnp.float32)
    pages = np.repeat(np.asarray(blocks), P)[:T]
    offs = offset + np.arange(T) % P
    if all_layers:
        got = write_rows(pool, None, rows, blocks, offset)
        want = pool.at[:, pages, offs].set(rows)
    else:
        got = jax.jit(lambda l: write_rows(pool, l, rows[1], blocks, offset))(
            jnp.asarray(1, jnp.int32)
        )
        want = pool.at[1, pages, offs].set(rows[1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the decode step around the kernel: nothing the size of a layer's pool
# slice is made inside the layer scan
# ---------------------------------------------------------------------------

# call-like primitives: looked INTO, not counted themselves
_TRANSPARENT = {"pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
                "custom_vjp_call", "remat", "checkpoint"}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):  # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _pool_sized_eqns(jaxpr, at_least):
    """Equations with an operand or a result of `at_least` elements or
    more, other than the row scatter and the kernel call."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call" or name.startswith("scatter"):
            continue
        if name in _TRANSPARENT:
            for sub in _sub_jaxprs(eqn):
                out += _pool_sized_eqns(sub, at_least)
            continue
        sizes = [
            int(np.prod(v.aval.shape))
            for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v, "aval") and hasattr(v.aval, "shape")
        ]
        if sizes and max(sizes) >= at_least:
            out.append(f"{name}: {[str(v.aval) for v in eqn.outvars]}")
    return out


def test_decode_step_paged_moves_no_pool_slice():
    """On the kernel path the layer scan holds, of the pool's size, only
    the in-place row scatter and the kernel call: no slice of the layer's
    pages out of the carry, no reshape of them for the kernel (the four
    75.8 MB copies a layer that PR 25 removed: PERF.md section 6)."""
    # a pool whose layer slice outweighs every weight matrix of the tiny
    # model: size alone tells the pool's operations apart
    cfg = dataclasses.replace(TINY_TEST, num_layers=3)
    params = model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    N, P = 65, 16
    pool = jnp.zeros(
        (cfg.num_layers, N, P, cfg.num_kv_heads * cfg.head_dim), jnp.float32
    )
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    def step(params, k, v, toks, lens):
        return model.decode_step_paged(
            params, cfg, toks, lens, k, v, tables, kernels=True
        )

    jaxpr = jax.make_jaxpr(step)(
        params, pool, pool, jnp.asarray([1, 2], jnp.int32),
        jnp.asarray([5, 11], jnp.int32),
    ).jaxpr
    scans = [
        e for e in jaxpr.eqns
        if e.primitive.name == "scan"
        and any(getattr(v.aval, "shape", None) == pool.shape for v in e.invars)
    ]
    assert len(scans) == 1, "the layer scan that carries the pool"
    body = scans[0].params["jaxpr"].jaxpr
    names = [e.primitive.name for e in body.eqns]
    assert any(n.startswith("scatter") for n in names), names
    layer_slice = N * P * cfg.num_kv_heads * cfg.head_dim
    assert _pool_sized_eqns(body, layer_slice) == []
    # and the kernel is in there, taking the whole pool
    calls = []

    def find(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                calls.append(e)
            for sub in _sub_jaxprs(e):
                find(sub)

    find(body)
    assert len(calls) == 1
    assert sum(v.aval.shape == pool.shape for v in calls[0].invars) == 2


# -- the decode step's experts (PR 32) ----------------------------------------


def _vars_outside_the_visit(jaxpr):
    """Every variable's shape in ``jaxpr`` and below, but inside the visit:
    its loop (``while``) on the CPU, its kernel call on the chip."""
    shapes = {tuple(v.aval.shape) for v in (*jaxpr.invars, *jaxpr.constvars)
              if hasattr(v.aval, "shape")}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "pallas_call"):
            continue
        shapes |= {tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                   if hasattr(v, "aval") and hasattr(v.aval, "shape")}
        for sub in _sub_jaxprs(eqn):
            shapes |= _vars_outside_the_visit(sub)
    return shapes


def _moe_step_layer_body(moe_dense):
    from aios_tpu.engine.config import TINY_MOE

    cfg = dataclasses.replace(TINY_MOE, num_layers=3)
    params = model.quantize_params(
        model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    pool = jnp.zeros((cfg.num_layers, 9, 16, cfg.num_kv_heads * cfg.head_dim),
                     jnp.bfloat16)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    def step(params, k, v, toks, lens, active):
        return model.decode_step_paged(
            params, cfg, toks, lens, k, v, tables, kernels=False,
            active=active, moe_dense=moe_dense)

    jaxpr = jax.make_jaxpr(step)(
        params, pool, pool, jnp.asarray([1, 2], jnp.int32),
        jnp.asarray([5, 11], jnp.int32), jnp.asarray([True, False]),
    ).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"
             and any(getattr(v.aval, "shape", None) == pool.shape for v in e.invars)]
    assert len(scans) == 1, "the layer scan that carries the pool"
    X, E, F = cfg.num_experts, cfg.hidden_size, cfg.expert_dim
    return scans[0].params["jaxpr"].jaxpr, {(X, E, 2 * F), (X, F, E)}, cfg


def test_decode_step_reads_its_experts_where_they_lie():
    """The layer scan of a MoE decode step has no variable of a layer's
    experts' shape ``[X, in, out]`` outside the visit: the stacks reach the
    body whole (``[L, X, in, out]``, not scanned) and only the visit indexes
    them. Under a sharding plan's dense path the scanned slices are there
    (the control: this test sees them)."""
    body, layer_shapes, cfg = _moe_step_layer_body(moe_dense=False)
    seen = _vars_outside_the_visit(body)
    assert not layer_shapes & seen, layer_shapes & seen
    whole = {(cfg.num_layers, *s) for s in layer_shapes}
    assert whole <= seen  # handed whole, as the scan's constants
    dense_body, _, _ = _moe_step_layer_body(moe_dense=True)
    assert layer_shapes <= _vars_outside_the_visit(dense_body)


def test_the_live_mask_reaches_only_the_graphs_that_use_it(monkeypatch):
    """A Mistral-shaped decode step (no router) and a Pangu-shaped CHUNK (a
    prefill graph: it hands no mask) lower, text for text, to what they are
    with the mask dropped on its way to the FFN, which is how the parent
    called it; the MoE decode steps trace the visit (the control)."""
    import os
    import sys

    from aios_tpu.engine import latent
    from aios_tpu.engine.config import TINY_MOE, ModelConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness.manifest import load_file

    arch = load_file(os.path.join(root, "benchmark", "archs", "pangu_ultra_moe.py"),
                     "benchmark_arch")
    tiny = dict(
        num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, vocab_size=512, n_routed_experts=8, router_n_experts=32,
        first_routed_expert=8, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True, rope_theta=25600000.0,
        rms_norm_eps=1e-5, max_position_embeddings=128,
        assumed={"served_name": "tiny-pangu"})
    pangu = ModelConfig(**arch.model_fields(tiny, 128))
    pangu_shapes = jax.eval_shape(lambda: latent.serving_layout(
        arch.build_params(arch.dims_of(tiny), 1), pangu)[0])
    mistral = dataclasses.replace(TINY_TEST, num_layers=3)
    mixtral = dataclasses.replace(TINY_MOE, num_layers=3)

    def quantized(cfg):
        return jax.eval_shape(lambda: model.quantize_params(
            model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    def pools(cfg):
        return tuple(jax.ShapeDtypeStruct((cfg.num_layers, 9, 16, w), jnp.bfloat16)
                     for w in (cfg.kv_row_dims if cfg.mla else
                               (cfg.num_kv_heads * cfg.head_dim,) * 2))

    def step_text(cfg, shapes):
        def step(p, k, v, toks, lens, tables, active):
            return model.decode_step_paged(p, cfg, toks, lens, k, v, tables,
                                           kernels=False, active=active)
        return jax.jit(step).lower(
            shapes, *pools(cfg), i32(2), i32(2), i32(2, 8),
            jax.ShapeDtypeStruct((2,), jnp.bool_)).as_text()

    def chunk_text(cfg, shapes):
        def chunk(p, k, v, toks, start, row):
            return model.prefill_chunk_paged(p, cfg, toks, start, k, v, row)
        return jax.jit(chunk).lower(
            shapes, *pools(cfg), i32(1, 16), i32(), i32(8)).as_text()

    from aios_tpu.engine import moe

    visits = []
    real_visit = moe.moe_ffn_visit
    monkeypatch.setattr(
        moe, "moe_ffn_visit",
        lambda *a, **kw: (visits.append(1), real_visit(*a, **kw))[1])

    def texts():
        return (step_text(mistral, quantized(mistral)),
                chunk_text(pangu, pangu_shapes))

    with_mask = texts()
    assert visits == []
    step_text(mixtral, quantized(mixtral))
    step_text(pangu, pangu_shapes)
    assert len(visits) == 2  # the control: one traced layer body a MoE step
    real = model.ffn
    monkeypatch.setattr(
        model, "ffn",
        lambda h, lp, cfg, allow_dispatch=False, moe_dense=False, qmm=None,
        live=None: real(h, lp, cfg, allow_dispatch, moe_dense, qmm))
    assert latent.model is model  # the latent block calls through the module
    dropped = texts()
    assert with_mask[0] == dropped[0], "a Mistral-shaped decode step"
    assert with_mask[1] == dropped[1], "a Pangu-shaped chunk"
