"""`benchmark/archs/pangu_ultra_moe.py` without the program: its reference
against the same layer written plainly in numpy float64, its roofline counts
against the configuration's own arithmetic, the readers of the four metrics the
configuration adds, and `run.py` end to end on the CPU at a tiny size."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import rehearsal_root  # noqa: E402
from benchmark.harness import manifest, reference  # noqa: E402

A = manifest.load_file(os.path.join(REPO, "benchmark", "archs", "pangu_ultra_moe.py"),
                       "benchmark_arch")
MLA = manifest.load_file(os.path.join(REPO, "benchmark", "layer_metrics", "mla.py"),
                         "benchmark_reader")
CELL = "pangu-ultra-ep16-agents32"
TINY = dict(
    source="a CPU test size, never a cell", arch="pangu_ultra_moe",
    num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=512, n_routed_experts=8, router_n_experts=32,
    first_routed_expert=8, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, rope_theta=25600000.0,
    rms_norm_eps=1e-5, max_position_embeddings=512, reduced={},
    assumed={"served_name": "tiny-pangu", "slots": 3},
    check={"requests": 2, "router_margin_min": 0.004, "gap_percentile": 95,
           "logit_gap_limit": 0.06, "bulk_percentile": 75, "bulk_gap_limit": 0.06},
)
D = A.dims_of(TINY)
SEED = 2 ** 31 + 5


def _w(leaf):
    return np.asarray(leaf["q"], np.float64) * np.asarray(leaf["s"], np.float64)


def _rms(x, weight, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * np.asarray(weight, np.float64)


def _rope(x, theta):  # x [T, heads, dim], half-rotation
    t, _, dim = x.shape
    half = dim // 2
    ang = np.arange(t)[:, None] / theta ** (np.arange(half) / half)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, gateup, down, width):
    gu = h @ _w(gateup)
    gate, up = gu[:, :width], gu[:, width:]
    return (gate / (1 + np.exp(-gate)) * up) @ _w(down)


def _plain_layer(d, x, lw, routed):
    """One layer in numpy float64, from the description alone."""
    t = x.shape[0]
    h = _rms(x, lw["attn_norm"], d.eps)
    down = h @ _w(lw["w_dqkv"])
    cq = _rms(down[:, :d.q_rank], lw["q_a_norm"], d.eps)
    c = _rms(down[:, d.q_rank:d.q_rank + d.kv_rank], lw["kv_a_norm"], d.eps)
    k_r = _rope(down[:, None, d.q_rank + d.kv_rank:], d.rope_theta)[:, 0]
    q = (cq @ _w(lw["w_uq"])).reshape(t, d.heads, d.nope + d.rope)
    q_nope, q_rope = q[..., :d.nope], _rope(q[..., d.nope:], d.rope_theta)
    k_nope = (c @ _w(lw["w_uk"])).reshape(t, d.heads, d.nope)
    v = (c @ _w(lw["w_uv"])).reshape(t, d.heads, d.v_dim)
    s = (np.einsum("qhd,khd->hqk", q_nope, k_nope) + np.einsum("qhr,kr->hqk", q_rope, k_r)
         ) / np.sqrt(d.nope + d.rope)
    s = np.where(np.arange(t)[:, None] >= np.arange(t)[None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    att = np.einsum("hqk,khd->qhd", p, v).reshape(t, -1) @ _w(lw["wo"])
    x = x + _rms(att, lw["post_attn_norm"], d.eps)
    h = _rms(x, lw["ffn_norm"], d.eps)
    if not routed:
        y = _swiglu(h, lw["w_gateup"], lw["w_down"], d.dense_ffn)
    else:
        z = h @ np.asarray(lw["w_router"], np.float64)
        score = 1 / (1 + np.exp(-z))
        y = _swiglu(h, lw["ws_gateup"], lw["ws_down"], d.shared * d.expert_ffn)
        for i in range(t):
            top = np.argsort(-score[i])[:d.top_k]
            for e in top:
                if d.first <= e < d.first + d.held:
                    one = {k: {"q": lw[k]["q"][e - d.first], "s": lw[k]["s"][e - d.first]}
                           for k in ("we_gateup", "we_down")}
                    w = d.scale * score[i, e] / score[i, top].sum()
                    y[i] += w * _swiglu(h[i:i + 1], one["we_gateup"], one["we_down"],
                                        d.expert_ffn)[0]
    return x + _rms(y, lw["post_ffn_norm"], d.eps)


def test_the_reference_is_the_layer_written_plainly():
    """Dense layer 0 and expert layers 1-2 (8 of 32 experts held, from the
    8th), then the head: the float32 reference under `highest` against numpy
    float64 agrees to float32 rounding (1e-4 of a logit std of 0.17)."""
    ids = [int(t) for t in np.random.RandomState(1).randint(0, D.vocab, 40)]
    got = reference.logits_for(A, D, SEED, [ids], [0], pad_to=0)["float32"][0]
    top = A.build_top(D, SEED)
    x = np.asarray(top["embed"], np.float64)[ids]
    for l in range(D.layers):
        x = _plain_layer(D, x, A.build_layer(D, SEED, l), D.routed(l))
    want = _rms(x, top["final_norm"], D.eps) @ _w(top["lm_head"])
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_tree_is_the_programs_layout_and_a_share_keeps_its_experts_bytes():
    params = A.build_params(D, SEED)
    assert set(params) == {"embed", "final_norm", "lm_head", "lead_layers", "layers"}
    assert params["lead_layers"]["w_gateup"]["q"].shape == (1, 64, 256)
    assert params["layers"]["we_gateup"]["q"].shape == (2, 8, 64, 64)
    assert params["layers"]["w_router"].shape == (2, 64, 32)
    assert "w_router" not in params["lead_layers"] and "w_gateup" not in params["layers"]
    one = A.build_layer(D, SEED, 2)
    np.testing.assert_array_equal(np.asarray(one["we_down"]["q"]),
                                  np.asarray(params["layers"]["we_down"]["q"][1]))
    import dataclasses

    uncut = A.build_layer(dataclasses.replace(D, held=D.experts, first=0), SEED, 2)
    np.testing.assert_array_equal(np.asarray(uncut["we_down"]["q"][8:16]),
                                  np.asarray(one["we_down"]["q"]))


def test_the_counts_are_the_configurations_arithmetic():
    man = manifest.Manifest(REPO)
    config = man.config(man.cell(CELL)["config"])
    d = A.dims_of(config)
    assert (d.layers, d.dense_layers, d.held, d.experts, d.first) == (5, 1, 16, 256, 0)
    assert d.row == 576 and A.trace_markers(d) == {
        "decode_kernel": "paged_mla_decode_attention", "kernels_per_step": 5}
    # ISSUE 27's count: 4.92 B parameters, 5.1 GB in the serving types
    every = A.layers_bytes(d, 1e9) + A.matrix_bytes(d.hidden, d.vocab)
    assert 4.75e9 < every < 4.85e9  # int8 matrices + scales, without the bf16 embedding
    assert abs(A.held_touched(d, 32) - 10.2) < 0.1
    # 32 slots of 8.6k rows: 1.6 GB of latent pages at the published row
    rows = 32 * 8600
    assert abs(A.mla_decode_bytes(d, 32, rows) / 1e9 - 1.6) < 0.05
    # the absorbed form does 128 x (576 + 512) x 2 operations a 1,152-byte row
    assert round(A.mla_decode_ops(d, 32, rows) / (rows * d.layers * d.row * 2)) == 242
    step = A.decode_step_bytes(d, 32, rows)
    assert 5.0e9 < step < 5.6e9  # 3.7 GB of weights touched + the cache
    assert A.decode_step_ops(d, 32, rows) > A.mla_decode_ops(d, 32, rows)
    one = A.prefill_ops(d, [8192 + 256], [8192])
    assert one < A.prefill_ops(d, [8192 + 256], [0]) / 10
    assert A.prefill_bytes(d, 256) < every


def test_the_configuration_states_its_share_and_what_it_assumed():
    man = manifest.Manifest(REPO)
    config = man.config("openpangu-ultra-moe-int8-ep16-d5")
    assert config["router_n_experts"] == 256 and config["first_routed_expert"] == 0
    assert sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
         "max_position_embeddings"])
    assert "16 chips share each layer's experts" in config["reduced"]["n_routed_experts"]
    assumed = config["assumed"]
    for key in ("router_scoring", "router_groups", "router_bias", "rotary_layout",
                "latent_norms", "softmax_scale"):
        assert "by the family's convention; the config has no key for it" in assumed[key]
    assert "NOT loaded" in assumed["multi_token_prediction"]
    assert set(config["check"]) == set(man.config("mixtral-8x7b-int8-d6")["check"])
    mix = man.traffic("agents32-sys8k")
    assert (mix["kind"], mix["agents"], mix["system_tokens"], mix["turns_per_agent"]) == (
        "closed_agents", 32, 8192, 64)
    assert (mix["task_tokens"], mix["answer_tokens"], mix["think_s"], mix["temperature"],
            mix["greedy_every"], mix["warm_s"]) == ([64, 256], [64, 192], 0, 0.7, 4, 8)
    only = {m["name"] for m in man.doc["per_layer"] if m.get("workloads") == [CELL]}
    assert only == {"kernels.mla_decode_roofline_pct", "model.mla_decode_share_pct",
                    "moe.local_pick_share_pct", "moe.rows_per_local_pick"}
    e2e = {m["name"] for m in man.end_to_end_of(CELL)}
    assert e2e == {"tpot_p50_ms", "out_tok_s", "setup_s"}


def _ctx(**kw):
    base = dict(planes=None, peaks=None, arch=A, dims=D, records=[], trace_w0=0.0,
                trace_w1=0.0, before={}, after={})
    base.update(kw)
    ctx = SimpleNamespace(**base)
    ctx.delta = lambda k: (ctx.after[k] - ctx.before[k]
                           if k in ctx.before and k in ctx.after else None)
    return ctx


def test_the_new_readers_read_their_counters_and_return_nothing_without_them():
    empty = _ctx()
    for fn in (MLA.kernels_mla_decode_roofline_pct, MLA.model_mla_decode_share_pct,
               MLA.moe_local_pick_share_pct, MLA.moe_rows_per_local_pick):
        assert fn(empty) is None  # a program without the counters, an untraced run
    ctx = _ctx(before={"moe_picks_total": 100, "moe_picks_local": 10, "moe_expert_rows": 50},
               after={"moe_picks_total": 1700, "moe_picks_local": 110, "moe_expert_rows": 3250})
    assert MLA.moe_local_pick_share_pct(ctx) == 6.25
    assert MLA.moe_rows_per_local_pick(ctx) == 32.0
    # a trace: two decode programs of 2 steps x 3 layers, kernels of 10 us
    kernel = "%paged_mla_decode_attention.7 = bf16[3,4,16] custom-call(...)"
    ops = [(kernel, 1000 + 20_000 * i, 10_000) for i in range(6)] + [
        (kernel, 500_000 + 20_000 * i, 10_000) for i in range(6)] + [
        ("%fusion.1", 200, 300)]
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit__lambda(1)", 0, 150_000), ("jit__lambda(1)", 499_000, 150_000),
                        ("jit__final_chunk_impl(2)", 300_000, 50_000)],
        "XLA Ops": ops}}
    traced = _ctx(planes=planes)
    assert MLA.model_mla_decode_share_pct(traced) == pytest.approx(100 * 120 / 300)
    other = SimpleNamespace(trace_markers=lambda d: {"decode_kernel": "paged_decode_attention",
                                                     "kernels_per_step": 3})
    assert MLA.model_mla_decode_share_pct(_ctx(planes=planes, arch=other)) is None
    assert MLA.kernels_mla_decode_roofline_pct(_ctx(planes=planes, arch=other,
                                                    peaks={"x": 1})) is None


def test_run_py_serves_the_architecture_end_to_end_on_the_cpu(tmp_path):
    """A tiny configuration of this architecture as a cell of a temporary
    root: the real server, the latent pool, prefix hits, the reference and its
    int4 control; the counters' two metrics are in the line."""
    root = rehearsal_root.build(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-pangu.json"), "w") as fh:
        json.dump(TINY, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({"name": "tiny-pangu", "source": TINY["source"],
                           "file": "benchmark/configs/tiny-pangu.json", "reduced": [],
                           "why": "CPU rehearsal size"})
    doc["workloads"].append({"name": "tiny-pangu-agents", "config": "tiny-pangu",
                             "traffic": "tiny-agents", "chips": 1, "why": "CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if {"tiny-agents", CELL} & set(m.get("workloads", [])):
            m["workloads"].append("tiny-pangu-agents")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--root", root,
         "--workload", "tiny-pangu-agents", "--seed", "3000000001", "--seconds", "3",
         "--trace", "1", "--rehearsal-cpu", "--control", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    got = line["metrics"]
    assert 10 < got["moe.local_pick_share_pct"]["value"] < 40  # 25 under even routing
    assert got["moe.rows_per_local_pick"]["value"] >= 1
    assert got["batcher.ttft_fast_share_pct"]["value"] > 90
    assert "kernels.mla_decode_roofline_pct" not in got  # never from a CPU run
    assert "control (the int4 reference's first token" in done.stdout
