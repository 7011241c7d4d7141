"""Mixture-of-experts: HF parity, dispatch/dense equivalence, EP sharding.

The reference's only MoE access is the cloud qwen3:30b endpoint behind the
api-gateway (api-gateway/src/main.rs:70-88); serving MoE models locally
(Qwen3-30B-A3B / Mixtral class) is a TPU-build extension. Ground truth is
transformers' Mixtral/Qwen3-MoE implementations on CPU fp32, same pattern
as test_model_parity.py.
"""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

from aios_tpu.engine import model as M
from aios_tpu.engine import moe as moe_mod
from aios_tpu.engine import weights as W
from aios_tpu.engine.config import (
    MIXTRAL_8X7B,
    QWEN3_30B_A3B,
    TINY_MOE,
    from_gguf_metadata,
    from_hf_config,
)

# compile-heavy tier: excluded from the fast commit gate (pytest -m fast)
pytestmark = pytest.mark.slow

ATOL = 2e-4
RTOL = 2e-4


def _hf_logits(hf_model, tokens):
    with torch.no_grad():
        out = hf_model(torch.tensor(tokens, dtype=torch.long))
    return out.logits.float().numpy()


def _tokens(cfg, batch=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)


@pytest.fixture(scope="module")
def mixtral_pair():
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_cfg = MixtralConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=2,
        num_attention_heads=8,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        sliding_window=None,
        attn_implementation="eager",
    )
    torch.manual_seed(7)
    hf = MixtralForCausalLM(hf_cfg).eval()
    cfg = from_hf_config(hf_cfg.to_dict(), name="tiny-mixtral-test")
    return hf, cfg


@pytest.fixture(scope="module")
def qwen3_moe_pair():
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    hf_cfg = Qwen3MoeConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        moe_intermediate_size=32,
        num_hidden_layers=2,
        num_attention_heads=8,
        num_key_value_heads=2,
        head_dim=8,
        num_experts=8,
        num_experts_per_tok=3,
        norm_topk_prob=True,
        decoder_sparse_step=1,
        mlp_only_layers=[],
        max_position_embeddings=64,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(8)
    hf = Qwen3MoeForCausalLM(hf_cfg).eval()
    cfg = from_hf_config(hf_cfg.to_dict(), name="tiny-qwen3moe-test")
    return hf, cfg


def test_mixtral_config_mapping(mixtral_pair):
    _, cfg = mixtral_pair
    assert cfg.moe and cfg.num_experts == 4 and cfg.num_experts_per_tok == 2
    assert cfg.expert_dim == 96  # mixtral experts use intermediate_size
    assert cfg.norm_topk_prob  # mixtral always renormalizes top-k


def test_qwen3_moe_config_mapping(qwen3_moe_pair):
    _, cfg = qwen3_moe_pair
    assert cfg.moe and cfg.num_experts == 8 and cfg.num_experts_per_tok == 3
    assert cfg.expert_dim == 32  # qwen3-moe has a separate expert width
    assert cfg.qk_norm


def test_mixtral_logits_parity(mixtral_pair):
    hf, cfg = mixtral_pair
    tokens = _tokens(cfg)
    params = W.params_from_hf_state_dict(hf.state_dict(), cfg)
    np.testing.assert_allclose(
        np.asarray(M.forward_full(params, cfg, tokens, kernels=False)),
        _hf_logits(hf, tokens),
        atol=ATOL,
        rtol=RTOL,
    )


def test_qwen3_moe_logits_parity(qwen3_moe_pair):
    hf, cfg = qwen3_moe_pair
    tokens = _tokens(cfg, seed=4)
    params = W.params_from_hf_state_dict(hf.state_dict(), cfg)
    np.testing.assert_allclose(
        np.asarray(M.forward_full(params, cfg, tokens, kernels=False)),
        _hf_logits(hf, tokens),
        atol=ATOL,
        rtol=RTOL,
    )


# ---------------------------------------------------------------------------
# dense vs dispatch
# ---------------------------------------------------------------------------


def _layer0(params):
    return {k: v[0] for k, v in params["layers"].items()}


def test_dispatch_matches_dense_at_full_capacity():
    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    lp = _layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.hidden_size))
    dense, aux_d = moe_mod.moe_ffn_dense(h, lp, cfg)
    N = h.shape[0] * h.shape[1]
    disp, aux_p = moe_mod.moe_ffn_dispatch(
        h, lp, cfg, capacity=N * cfg.num_experts_per_tok
    )
    np.testing.assert_allclose(dense, disp, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(aux_d, aux_p, atol=1e-6, rtol=1e-6)


def test_dispatch_drops_only_overflow_tokens():
    """With capacity 8 on a 4-expert/top-2 router over 32 tokens, some
    picks overflow; output must stay finite and within the span of the
    dense result (dropped picks zero one expert's contribution)."""
    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    lp = _layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.hidden_size))
    out, aux = moe_mod.moe_ffn_dispatch(h, lp, cfg, capacity=8)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(float(aux))


def test_spec_decode_on_a_router_model():
    """Speculative rounds on a model with a router (its verify feed of 4
    tokens runs every expert over every token, like decode) emit the
    tokens plain greedy decode emits."""
    from aios_tpu.engine.engine import TPUEngine

    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = TPUEngine(cfg, params, num_slots=1, max_context=64,
                    cache_dtype=jnp.float32)
    ref = eng.generate([1, 2, 3, 4] * 3, max_new_tokens=16, temperature=0.0)
    eng.release(0)
    first = eng.prefill(0, [1, 2, 3, 4] * 3, temperature=0.0)
    got = [first]
    while len(got) < 16:
        toks, counts = eng.spec_step(1, draft_len=3)
        got.extend(toks[0, 0, : int(counts[0, 0])].tolist())
    eng.close()
    assert got[:16] == ref


def test_load_balance_aux_perfectly_balanced():
    """Uniform router probs + uniform assignment -> aux == 1.0."""
    probs = jnp.full((8, 4), 0.25)
    idx = jnp.tile(jnp.asarray([[0, 1], [2, 3]], jnp.int32), (4, 1))
    aux = moe_mod.load_balance_aux(probs, idx, 4)
    np.testing.assert_allclose(float(aux), 1.0, atol=1e-6)


def test_serving_forward_never_dispatches(monkeypatch):
    """The serving forward (no with_aux) stays on an exact path even at
    >=1024 tokens — the capacity dispatch is the training forward's alone
    (it drops overflow picks and would skew prefill logits)."""
    called = []
    real = moe_mod.moe_ffn_dispatch

    def spy(*a, **kw):
        called.append(True)
        return real(*a, **kw)

    monkeypatch.setattr(moe_mod, "moe_ffn_dispatch", spy)
    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = _tokens(cfg, batch=1, seq=1024, seed=21)
    M.forward_full(params, cfg, tokens, kernels=False)
    assert not called
    M.forward_full(params, cfg, tokens, kernels=False, with_aux=True)
    assert called


def test_pp_train_step_moe_aux(cpu_devices):
    """Pipeline-parallel training must fold the MoE aux in (same contract
    as the GSPMD step) — bubble ticks' garbage-activation aux excluded."""
    from aios_tpu.engine.train import make_optimizer
    from aios_tpu.parallel.pipeline import (
        build_pp_mesh,
        make_pp_train_step,
        shard_pp_params,
    )

    cfg = TINY_MOE
    mesh = build_pp_mesh(pp=2, dp=2)
    params = shard_pp_params(
        M.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32), mesh
    )
    pp_init, pp_step = make_pp_train_step(
        cfg, mesh, num_microbatches=2,
        optimizer=make_optimizer(warmup_steps=1, total_steps=10),
    )
    state = pp_init(params)
    B = 2 * 2 * 2  # MB * dp * rows
    batch = {
        "tokens": jnp.asarray(_tokens(cfg, batch=B, seq=16, seed=17)),
        "loss_mask": jnp.ones((B, 16), jnp.float32),
    }
    state, metrics = jax.jit(pp_step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # the load-balance term is X*sum(f*P) >= 1 for any real routing; a
    # bubble-polluted or missing aux would show up as 0 or garbage
    assert 0.9 < float(metrics["moe_aux"]) < 4.0


def test_runtime_resolves_moe_presets_exactly():
    from aios_tpu.runtime.model_manager import ModelManager

    cfg = ModelManager._resolve_preset("qwen3-30b-a3b")
    assert cfg.moe and cfg.num_experts == 128
    assert ModelManager._resolve_preset("qwen3-14b").moe is False
    assert ModelManager._resolve_preset("tiny-moe").moe


def test_forward_full_with_aux():
    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = _tokens(cfg, seed=6)
    logits, aux = M.forward_full(
        params, cfg, tokens, kernels=False, with_aux=True
    )
    base = M.forward_full(params, cfg, tokens, kernels=False)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(base))
    assert 0.9 < float(aux) < 4.0  # X * sum(f*P) >= 1, small for random


# ---------------------------------------------------------------------------
# decode + quantized serving
# ---------------------------------------------------------------------------


def test_moe_decode_matches_forward():
    """Teacher-forced decode_step logits equal forward_full's rows."""
    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    seq = _tokens(cfg, batch=1, seq=8, seed=11)[0]
    full = np.asarray(
        M.forward_full(params, cfg, seq[None, :], kernels=False)
    )[0]
    k, v = M.init_kv_cache(cfg, 1, 16, jnp.float32)
    for t in range(len(seq)):
        logits, k, v, _picks = M.decode_step(
            params,
            cfg,
            jnp.asarray(seq[t : t + 1]),
            jnp.asarray([t], jnp.int32),
            k,
            v,
            kernels=False,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], full[t], atol=1e-4, rtol=1e-4
        )


def test_moe_quantized_decode_close():
    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jnp.ones((4,), jnp.int32)
    zeros = jnp.zeros((4,), jnp.int32)
    ref, *_ = M.decode_step(
        params, cfg, toks, zeros, *M.init_kv_cache(cfg, 4, 16, jnp.float32),
        kernels=False,
    )
    for fuse in (True, False):
        qp = M.quantize_params(params, fuse=fuse)
        assert ("we_gateup" in qp["layers"]) == fuse
        assert isinstance(qp["layers"]["we_down"], dict)
        assert not isinstance(qp["layers"]["w_router"], dict)  # router bf16
        got, *_ = M.decode_step(
            qp, cfg, toks, zeros, *M.init_kv_cache(cfg, 4, 16, jnp.float32),
            kernels=False,
        )
        assert np.argmax(np.asarray(got), -1).tolist() == np.argmax(
            np.asarray(ref), -1
        ).tolist()
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=0.05, rtol=0.05
        )


def test_init_quantized_params_moe_shapes():
    cfg = TINY_MOE
    qp = M.init_quantized_params(cfg, jax.random.PRNGKey(1))
    X, E, Fm = cfg.num_experts, cfg.hidden_size, cfg.expert_dim
    L = cfg.num_layers
    assert qp["layers"]["we_gateup"]["q"].shape == (L, X, E, 2 * Fm)
    assert qp["layers"]["we_gateup"]["s"].shape == (L, X, 1, 2 * Fm)
    assert qp["layers"]["we_down"]["q"].shape == (L, X, Fm, E)
    assert qp["layers"]["w_router"].shape == (L, E, X)


def test_moe_paged_decode_matches_dense_cache():
    """MoE flows through the paged KV pool unchanged (the FFN is
    orthogonal to the cache layout)."""
    from aios_tpu.engine.engine import TPUEngine

    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = [1, 2, 3, 4, 5]
    dense = TPUEngine(cfg, params, num_slots=2, max_context=128,
                      cache_dtype=jnp.float32)
    ref = dense.generate(prompt, max_new_tokens=24, temperature=0.0)
    dense.close()
    paged = TPUEngine(cfg, params, num_slots=2, max_context=128,
                      cache_dtype=jnp.float32,
                      paged_pool_rows=256, page_size=32)
    got = paged.generate(prompt, max_new_tokens=24, temperature=0.0)
    paged.close()
    assert got == ref


# ---------------------------------------------------------------------------
# expert parallelism on the virtual mesh
# ---------------------------------------------------------------------------


def test_ep_sharded_train_step(cpu_devices):
    from aios_tpu.engine.train import make_optimizer, make_train_step
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    cfg = TINY_MOE
    mesh = build_mesh(8, dp=2, ep=2, tp=2)
    plan = ShardingPlan(mesh)
    plan.validate(cfg, num_slots=4)
    params = plan.put_params(
        M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    init_state, train_step = make_train_step(
        cfg, mesh, optimizer=make_optimizer(warmup_steps=1, total_steps=10)
    )
    state = init_state(params)
    batch = {
        "tokens": jnp.asarray(_tokens(cfg, batch=4, seq=16, seed=13)),
        "loss_mask": jnp.ones((4, 16), jnp.float32),
    }
    state, metrics = jax.jit(train_step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["moe_aux"]))
    assert int(state["step"]) == 1


def test_ep_sharded_engine_decode_matches_single_device(cpu_devices):
    from aios_tpu.engine.engine import TPUEngine
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    cfg = TINY_MOE
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    plan = ShardingPlan(build_mesh(8, dp=2, ep=2, tp=2))
    eng = TPUEngine(
        cfg, params, num_slots=4, max_context=64,
        cache_dtype=jnp.float32, shardings=plan,
    )
    ref = TPUEngine(cfg, params, num_slots=4, max_context=64,
                    cache_dtype=jnp.float32)
    try:
        first = eng.prefill(0, [1, 2, 3, 4], temperature=0.0)
        toks = eng.step(3)
        f0 = ref.prefill(0, [1, 2, 3, 4], temperature=0.0)
        t0 = ref.step(3)
        assert first == f0
        # slot 0 alone decodes: a row of an inactive slot picks no expert on
        # the single device's path and every one under the plan's, so what
        # it samples (read by nobody) differs
        assert toks[:, 0].tolist() == t0[:, 0].tolist()
    finally:
        eng.close()
        ref.close()


@pytest.mark.parametrize("seq_parallel", ["ring", "ulysses"])
def test_moe_train_composes_with_sequence_parallel(cpu_devices, seq_parallel):
    """MoE (ep) x sequence parallelism (sp) x TP in one train step: the
    expert FFN is orthogonal to the attention sharding, so ring/Ulysses
    and the ep psum compose on the same mesh."""
    from aios_tpu.engine.train import make_optimizer, make_train_step
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    cfg = TINY_MOE  # 4 heads, 2 kv heads: ulysses sp=2 divides both
    mesh = build_mesh(8, dp=1, sp=2, ep=2, tp=2)
    plan = ShardingPlan(mesh)
    plan.validate(cfg, num_slots=2)
    params = plan.put_params(
        M.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    )
    init_state, train_step = make_train_step(
        cfg, mesh, optimizer=make_optimizer(warmup_steps=1, total_steps=10),
        seq_parallel=seq_parallel,
    )
    state = init_state(params)
    batch = {
        "tokens": jnp.asarray(_tokens(cfg, batch=2, seq=32, seed=31)),
        "loss_mask": jnp.ones((2, 32), jnp.float32),
    }
    state, metrics = jax.jit(train_step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert 0.9 < float(metrics["moe_aux"]) < 4.0


def test_ep_requires_moe_config():
    from aios_tpu.engine.config import TINY_TEST
    from aios_tpu.parallel.sharding import ShardingPlan, build_mesh

    plan = ShardingPlan(build_mesh(8, dp=2, ep=2, tp=2))
    with pytest.raises(AssertionError):
        plan.validate(TINY_TEST, num_slots=4)


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------


def test_moe_preset_param_counts():
    total = QWEN3_30B_A3B.num_params()
    active = QWEN3_30B_A3B.active_params()
    assert 29e9 < total < 32e9, total
    assert 2.5e9 < active < 4e9, active
    assert 45e9 < MIXTRAL_8X7B.num_params() < 48e9


def test_moe_config_from_gguf_metadata():
    md = {
        "general.architecture": "qwen3moe",
        "general.name": "Qwen3 30B A3B",
        "qwen3moe.block_count": 48,
        "qwen3moe.embedding_length": 2048,
        "qwen3moe.feed_forward_length": 6144,
        "qwen3moe.expert_feed_forward_length": 768,
        "qwen3moe.expert_count": 128,
        "qwen3moe.expert_used_count": 8,
        "qwen3moe.attention.head_count": 32,
        "qwen3moe.attention.head_count_kv": 4,
        "qwen3moe.attention.key_length": 128,
        "qwen3moe.context_length": 32768,
        "qwen3moe.vocab_size": 151936,
    }
    cfg = from_gguf_metadata(md)
    assert cfg.moe and cfg.num_experts == 128 and cfg.num_experts_per_tok == 8
    assert cfg.expert_dim == 768
    assert cfg.qk_norm  # qwen3* arch
    assert cfg.head_dim == 128
