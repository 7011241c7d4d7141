"""`benchmark/archs/mellum.py`: its reference through the generic loop against
the same model written plainly in numpy float64 and against the program's own
forward pass, its roofline counts against the configuration's arithmetic, the
configuration file against the catalog row, the cell against the rule, the
three readers that pages by kind add, and `run.py` end to end on the CPU at a
tiny size."""

import json
import math
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import published  # noqa: E402
import rehearsal_root  # noqa: E402
from benchmark.harness import manifest, reference  # noqa: E402

A = manifest.load_file(os.path.join(REPO, "benchmark", "archs", "mellum.py"),
                       "benchmark_arch")
WIN = manifest.load_file(os.path.join(REPO, "benchmark", "layer_metrics", "window.py"),
                         "benchmark_reader")
CONFIG = "mellum2-12b-a2.5b-int8-d20"
CELL = "mellum2-d20-mixedlen"
W, F = A.WINDOW, A.FULL
TINY = dict(
    source="a CPU test size, never a cell", arch="mellum", model_type="mellum",
    attention_bias=False, tie_word_embeddings=False, hidden_act="silu",
    num_hidden_layers=8, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=512, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, rms_norm_eps=1e-6, sliding_window=64,
    max_position_embeddings=512, layer_types=[W, W, W, F] * 2,
    mlp_layer_types=["sparse"] * 8,
    # the YaRN original length scaled down with the context: positions past it
    rope_parameters={
        F: dict(rope_type="yarn", rope_theta=500000, factor=16,
                original_max_position_embeddings=32, beta_fast=32, beta_slow=1,
                attention_factor=1.2772588722239782),
        W: dict(rope_type="default", rope_theta=500000)},
    reduced={}, assumed={"served_name": "tiny-mellum", "slots": 3},
    check={"requests": 2, "router_margin_min": 0.02, "gap_percentile": 95,
           "logit_gap_limit": 0.08, "bulk_percentile": 75, "bulk_gap_limit": 0.08},
)
D = A.dims_of(TINY)
SEED = 2 ** 31 + 7


def _w(leaf):
    return np.asarray(leaf["q"], np.float64) * np.asarray(leaf["s"], np.float64)


def _f(a):
    return np.asarray(a, np.float64)


def _rms(x, weight, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f(weight)


def _freqs(d, rope):
    """A kind's frequencies, dimension by dimension, from the description."""
    dim = d.head_dim
    if rope.factor <= 1:
        return np.asarray([rope.theta ** (-2 * i / dim) for i in range(dim // 2)])
    at = lambda turns: dim * math.log(rope.original / (turns * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(rope.theta))
    low, high = max(math.floor(at(rope.beta_fast)), 0), min(math.ceil(at(rope.beta_slow)),
                                                             dim - 1)
    out = []
    for i in range(dim // 2):
        plain = rope.theta ** (-2 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(plain / rope.factor * ramp + plain * (1 - ramp))
    return np.asarray(out)


def _rotate(d, rope, x):  # x [T, heads, head_dim], half-rotation
    t, _, dim = x.shape
    ang = np.arange(t)[:, None] * _freqs(d, rope)
    cos = rope.attention_factor * np.cos(ang)[:, None, :]
    sin = rope.attention_factor * np.sin(ang)[:, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _plain_layer(d, x, lw, kind):
    """One layer over x [T, hidden], a query at a time."""
    t = x.shape[0]
    rope = d.rope_window if kind == W else d.rope_full
    qkv = _rms(x, lw["attn_norm"], d.eps) @ _w(lw["w_qkv"])
    q = _rotate(d, rope, qkv[:, :d.q_dim].reshape(t, d.heads, d.head_dim))
    k = _rotate(d, rope, qkv[:, d.q_dim:d.q_dim + d.kv_dim].reshape(
        t, d.kv_heads, d.head_dim))
    v = qkv[:, d.q_dim + d.kv_dim:].reshape(t, d.kv_heads, d.head_dim)
    att = np.zeros((t, d.heads, d.head_dim))
    for i in range(t):
        first = max(i - d.window + 1, 0) if kind == W else 0
        for h in range(d.heads):
            g = h // (d.heads // d.kv_heads)
            s = k[first:i + 1, g] @ q[i, h] / math.sqrt(d.head_dim)
            p = np.exp(s - s.max())
            att[i, h] = (p / p.sum()) @ v[first:i + 1, g]
    x = x + att.reshape(t, d.q_dim) @ _w(lw["wo"])
    h2 = _rms(x, lw["ffn_norm"], d.eps)
    logits = h2 @ _f(lw["w_router"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for i in range(t):
        chosen = np.argsort(-probs[i])[:d.top_k]
        for e in chosen:
            gu = h2[i] @ _w({"q": lw["we_gateup"]["q"][e], "s": lw["we_gateup"]["s"][e]})
            act = gu[:d.ffn] / (1 + np.exp(-gu[:d.ffn])) * gu[d.ffn:]
            y[i] += probs[i, e] / probs[i, chosen].sum() * (
                act @ _w({"q": lw["we_down"]["q"][e], "s": lw["we_down"]["s"][e]}))
    return x + y


def test_the_generic_loop_gives_the_plainly_written_whole_model_s_logits():
    """Both kinds of layer, both rotary tables at positions past the window
    (64) and past the YaRN original length (32), top-2 of 8 renormalised, the
    head: float32 under `highest` against numpy float64."""
    ids = [int(t) for t in np.random.RandomState(1).randint(0, D.vocab, 150)]
    out = reference.logits_for(A, D, SEED, [ids], [0], pad_to=0)
    # reference.KEEP rows at most: the last 150 of a 150-row sequence
    got, margin = out["float32"][0], out["router_margin"][0]
    top = A.build_top(D, SEED)
    x = _f(top["embed"])[ids]
    for l in range(D.layers):
        x = _plain_layer(D, x, A.build_layer(D, SEED, l), D.kinds[l])
    want = _rms(x, top["final_norm"], D.eps) @ _w(top["lm_head"])
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert margin.shape == (150, D.layers) and np.isfinite(margin).all() and (margin > 0).all()


def test_the_reference_and_the_program_s_forward_agree_on_both_kinds():
    """The served model (the program's `ModelConfig` from `model_fields`, the
    seeded tree, bfloat16 activations) against the float32 reference: the
    same first token at nearly every position, past the window and past the
    YaRN original length."""
    import jax.numpy as jnp

    from aios_tpu.engine import model
    from aios_tpu.engine.config import ModelConfig

    cfg = ModelConfig(**A.model_fields(TINY, 512))
    assert cfg.kinds and cfg.period == 4 and cfg.rope_of("full").factor == 16
    assert cfg.rope_of("full").original_context == 32 and cfg.window_of("window") == 64
    ids = [int(t) for t in np.random.RandomState(3).randint(0, D.vocab, 200)]
    served = np.asarray(model.forward_full(A.build_params(D, SEED), cfg, jnp.asarray([ids])))[0]
    ref = reference.logits_for(A, D, SEED, [ids], [0], pad_to=0)["float32"][0]
    gaps = reference.served_gaps(ref, served.argmax(-1))
    assert np.percentile(gaps, 75) == 0.0 and gaps.max() < 0.5, gaps.max()
    assert np.abs(served - ref).mean() < 0.05 * ref.std()


def test_the_controls_move_the_logits_and_the_reference_itself_does_not():
    seq = [int(t) for t in np.random.RandomState(2).randint(0, D.vocab, 250)]
    out = reference.logits_for(A, D, SEED, [seq], [100],
                               ("float32", A.CONTROL) + A.KIND_CONTROLS)
    ref = out["float32"][0]
    assert reference.served_gaps(ref[:-1], ref[:-1].argmax(-1)).max() == 0.0
    moved = {c: float(np.abs(out[c][0] - ref).max()) for c in (A.CONTROL,) + A.KIND_CONTROLS}
    limit = TINY["check"]["logit_gap_limit"]
    # every kept row lies past window + a page here, so the wider window is
    # seen; two full layers of eight under head_dim 16 make YaRN's part small
    # at this size (PERF.md has the chip's readings at the published widths)
    assert moved["int4"] > limit and moved["window_page"] > limit, moved
    assert moved["no_yarn"] > 0.01, moved


def test_the_tree_is_the_programs_layout():
    params = A.build_params(D, SEED)
    assert set(params) == {"embed", "final_norm", "lm_head", "layers"}
    layers = params["layers"]
    assert layers["w_qkv"]["q"].shape == (8, 128, 64 + 2 * 32)
    assert layers["we_gateup"]["q"].shape == (8, 8, 128, 128)
    assert layers["we_down"]["s"].shape == (8, 8, 1, 128)
    assert layers["w_router"].shape == (8, 128, 8)
    one = A.build_layer(D, SEED, 5)
    np.testing.assert_array_equal(np.asarray(one["we_down"]["q"]),
                                  np.asarray(layers["we_down"]["q"][5]))
    # every layer and every expert distinct
    q = np.asarray(layers["we_gateup"]["q"])
    assert (q[0, 0] != q[0, 1]).any() and (q[0, 0] != q[1, 0]).any()


def test_the_counts_are_the_configurations_arithmetic():
    man = manifest.Manifest(REPO)
    d = A.dims_of(man.config(CONFIG))
    assert (d.layers, d.full_layers, d.window_layers, d.experts, d.top_k) == (20, 5, 15, 64, 8)
    assert A.trace_markers(d) == {"decode_kernel": "decode_attention", "kernels_per_step": 20}
    assert "decode_attention" in "paged_decode_attention" and \
        "decode_attention" in "window_decode_attention"
    # ISSUE 35's count: attention 21.23 M, an expert 6.19 M, a layer 417.7 M
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert abs(attn - 21.23e6) < 0.01e6 and 3 * 2304 * 896 == 6_193_152
    assert A.attention_bytes(d) == pytest.approx(attn, rel=0.01)
    assert A.ffn_bytes(d) == pytest.approx(6_193_152, rel=0.01)
    every = A.layers_bytes(d, 1e9) + A.matrix_bytes(d.hidden, d.vocab)
    assert 8.5e9 < every < 8.8e9  # 20 x 417.7 M + the head, int8 + scales
    # 4 live slots touch 64 (1 - (7/8)^4) = 26.5 experts of a layer
    assert 64 * (1 - (1 - 8 / 64) ** 4) == pytest.approx(26.48, abs=0.01)
    # a step of 16 slots at 4,000 rows each: the full layers read every row, a
    # window layer 1,024 a slot
    assert A.keys_read(d, 16, 64000) == 5 * 64000 + 15 * 16 * 1024
    assert A.keys_read(d, 16, 16 * 600) == 20 * 16 * 600  # under a window: alike
    cache = A.keys_read(d, 16, 64000) * 2 * 512 * 2
    step = A.decode_step_bytes(d, 16, 64000)
    assert step == pytest.approx(A.layers_bytes(d, 16) + A.matrix_bytes(2304, 98304)
                                 + 2 * 2304 + 16 * 2304 * 2 + cache)
    assert A.decode_step_ops(d, 16, 64000) > 16 * 2 * 2304 * 98304
    # a prompt's window layers: position p sees min(p + 1, 1024) keys
    assert A.window_pairs(d, 4, 0) == 1 + 2 + 3 + 4
    assert A.window_pairs(d, 3000, 0) == 1024 * 1025 / 2 + (3000 - 1024) * 1024
    assert A.window_pairs(d, 3000, 2000) == 1000 * 1024
    per_row = 20 * 2 * (2304 * 5120 + 4096 * 2304 + 8 * 3 * 2304 * 896 + 2304 * 64)
    full = 512 * 7168 + 512 * 513 / 2
    assert A.prefill_ops(d, [7680], [7168]) == pytest.approx(
        512 * per_row + 4 * 4096 * (5 * full + 15 * 512 * 1024))
    # at 7.8k rows a window layer's attention is an eighth of a full layer's
    assert full / (512 * 1024) == pytest.approx(7.25, abs=0.01)
    assert A.prefill_bytes(d, 512) == A.layers_bytes(d, 512) < every


def test_the_configuration_file_equals_the_catalog_row_outside_its_cuts():
    man = manifest.Manifest(REPO)
    manifest.check(man)
    doc = published.check(man, CONFIG)
    assert doc["published"]["num_hidden_layers"] == 28 and "head_dim" in doc["widths"]
    config = man.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert row["source_url"] == config["source"] == man.config_entry(CONFIG)["source"]
    changed = {k for k, v in row["config"].items() if config[k] != v}  # key by key
    assert changed == set(config["reduced"]) == set(man.config_entry(CONFIG)["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types", "max_position_embeddings"}
    assert config["layer_types"] == row["config"]["layer_types"][:20]
    assert config["mlp_layer_types"] == row["config"]["mlp_layer_types"][:20]
    assert (config["num_hidden_layers"], config["max_position_embeddings"]) == (20, 16384)
    assert (config["num_experts"], config["num_experts_per_tok"], config["vocab_size"],
            config["sliding_window"], config["moe_intermediate_size"]) == (
        64, 8, 98304, 1024, 896)
    assert config["rope_parameters"] == row["config"]["rope_parameters"]
    assumed = config["assumed"]
    for key in ("qk_norm", "window_edge", "attention_factor", "rotary_layout", "router_order"):
        assert "the config has no key for it" in assumed[key], key
    assert "NOT loaded" in assumed["multi_token_prediction"] and assumed["slots"] == 16
    assert "BY KIND" in assumed["kv_cache"] and "pipeline stages" in assumed["deployment"]
    assert set(config["check"]) == set(man.config("mixtral-8x7b-int8-d6")["check"])


def test_the_cell_is_listed_where_the_long_prompt_cells_are_and_nowhere_else():
    man = manifest.Manifest(REPO)
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "mixedlen-mellum20", 1)
    mix = man.traffic("mixedlen-mellum20")
    assert (mix["kind"], mix["prompt_tokens"], mix["prompt_dist"], mix["answer_tokens"],
            mix["temperature"], mix["greedy_every"], mix["warm_s"], mix["n_requests"]) == (
        "open_arrivals", [512, 15360], "log_uniform", [32, 96], 0.7, 4, 8, 400)
    assert mix["traffic_seed"] not in {
        man.traffic(t)["traffic_seed"]
        for t in ("longprompt-m7", "longprompt-x6", "longprompt-xing13")}
    assert {m["name"] for m in man.end_to_end_of(CELL)} == {
        "ttft_p80_ms", "tpot_p50_ms", "setup_s"}
    per_layer = man.doc["per_layer"]
    twin = {m["name"] for m in per_layer if "mixtral-d6-longprompt" in m.get("workloads", [])}
    mine = {m["name"] for m in per_layer if CELL in m.get("workloads", [])}
    new = {"kv.window_trim_share_pct", "kv.full_pages_peak_pct", "model.window_attn_share_pct"}
    # all the long-prompt cells' but `serving.free_slot_wait_ms`, which
    # tests/benchmark/test_bench_phases.py (not this PR's to edit) holds to the
    # cells whose names end in "longprompt"; `serving.queue_wait_ms` reads the
    # same wait for this cell
    assert new <= mine and mine - new <= twin - {"serving.free_slot_wait_ms"}
    assert {m["name"] for m in per_layer if m.get("workloads") == [CELL]} == new
    assert {f["name"] for f in man.layer_metrics_of(CELL)} == mine
    # appended after the sixth cell, on one chip; nothing here counts the cells
    # or pins this one as the last (tests/benchmark/test_bench_xing4.py did,
    # and a later PR may not edit it: tests/conftest.py says what that costs)
    names = [w["name"] for w in man.doc["workloads"]]
    assert names.index(CELL) == names.index("xing4-d13-longprompt") + 1
    assert man.cell(CELL)["chips"] == 1
    knee = float(re.search(r"Knee (\d+\.\d+)/s", mix["sweep"]).group(1))
    assert mix["rate_rps"] == pytest.approx(0.7 * knee)
    assert man.doc["per_layer"][-3]["name"] == "kv.window_trim_share_pct"  # appended
    moved = {m["name"]: m["moves"] for m in per_layer if m["name"] in new}
    # the share reads the decode kernels alone (a chunk's window attention has
    # no name in a trace), so it is declared to move what a decode step moves
    assert moved == {"kv.window_trim_share_pct": "ttft_p80_ms",
                     "kv.full_pages_peak_pct": "ttft_p80_ms",
                     "model.window_attn_share_pct": "tpot_p50_ms"}


def test_what_the_sixth_cell_s_own_test_held_of_it_is_still_held():
    """tests/benchmark/test_bench_xing4.py pins its cell as the LAST of SIX and
    its two metrics as the last of `per_layer`; a seventh cell makes both pins
    false, no later PR may edit that file, and tests/conftest.py marks the one
    test as expected to fail. Everything else it held of `xing4-d13-longprompt`
    is held here, so only the count and the last-place pins are lost."""
    man = manifest.Manifest(REPO)
    xing_cell, xing_config = "xing4-d13-longprompt", "xing4-29b-a4b-int8-d13"
    cell = man.cell(xing_cell)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        xing_config, "longprompt-xing13", 1)
    mix = man.traffic("longprompt-xing13")
    assert (mix["kind"], mix["prompt_tokens"], mix["prompt_dist"], mix["answer_tokens"],
            mix["temperature"], mix["greedy_every"], mix["warm_s"], mix["n_requests"]) == (
        "open_arrivals", [1024, 7168], "log_uniform", [32, 96], 0.7, 4, 8, 400)
    assert mix["traffic_seed"] not in {man.traffic(t)["traffic_seed"]
                                       for t in ("longprompt-m7", "longprompt-x6")}
    knee = float(re.search(r"Knee (\d+\.\d+)/s", mix["sweep"]).group(1))
    assert mix["rate_rps"] == pytest.approx(0.7 * knee)
    assert {m["name"] for m in man.end_to_end_of(xing_cell)} == {
        "ttft_p80_ms", "tpot_p50_ms", "setup_s"}
    per_layer = man.doc["per_layer"]
    twin = {m["name"] for m in per_layer if "mixtral-d6-longprompt" in m.get("workloads", [])}
    mine = {m["name"] for m in per_layer if xing_cell in m.get("workloads", [])}
    new = {"model.hc_share_pct", "kernels.hc_mix_roofline_pct"}
    assert mine == twin | new and len(twin) == 24
    assert {m["name"] for m in per_layer if m.get("workloads") == [xing_cell]} == new
    assert {f["name"] for f in man.layer_metrics_of(xing_cell)} == mine
    # its two metrics still stand together, in its order, after every older one
    names = [m["name"] for m in per_layer]
    at = names.index("model.hc_share_pct")
    assert names[at:at + 2] == sorted(new, reverse=True)
    assert all(w["chips"] == 1 for w in man.doc["workloads"])
    assert [w["name"] for w in man.doc["workloads"]][:6][-1] == xing_cell


def test_no_arrival_falls_where_the_harness_stops_offering():
    """`loadgen.stop_and_drain` reads its list of request threads while the
    dispatcher may be starting one (PERF.md section 7, PR 35 (f)): a request
    due in the second after the window's close can be started and never
    joined, and then reads as lost. The window closes `warm_s` + 40 s after
    the first arrival's clock starts and `stop_and_drain` is called up to
    about a second later; this file's `traffic_seed` leaves that stretch empty,
    as the three older open-loop files happen to."""
    from benchmark.harness import loadgen

    man = manifest.Manifest(REPO)
    seconds = man.doc["run_seconds"]
    for name in ("mixedlen-mellum20", "longprompt-xing13", "longprompt-m7", "longprompt-x6"):
        mix = man.traffic(name)
        close = mix["warm_s"] + seconds
        (lane,) = loadgen.build_schedule(mix)
        near = [t.due_s for t in lane if close - 0.05 < t.due_s < close + 1.0]
        assert not near, (name, near)
    mine = man.traffic("mixedlen-mellum20")
    (lane,) = loadgen.build_schedule(mine)
    close = mine["warm_s"] + seconds
    assert not [t.due_s for t in lane if close - 0.2 < t.due_s < close + 2.0]
    assert sum(1 for t in lane if mine["warm_s"] <= t.due_s < close) >= 50


def _ctx(**kw):
    base = dict(planes=None, peaks=None, arch=A, dims=D, samples=[], trace_w0=0.0,
                trace_w1=0.0, before={}, after={})
    base.update(kw)
    ctx = SimpleNamespace(**base)
    ctx.delta = lambda key: (ctx.after[key] - ctx.before[key]
                             if key in ctx.before and key in ctx.after else None)
    return ctx


EXTRACT = os.path.join(HERE, "data", "trace_window_attention_extract.json")


def test_the_three_readers_on_a_committed_extract():
    """Counters as `pool.stats()` gives them and a device plane's lines as the
    profiler names them: the window kind's decode kernel by its RESULT name,
    not an event that merely reads one, not the full kind's kernel."""
    for fn in (WIN.kv_window_trim_share_pct, WIN.kv_full_pages_peak_pct,
               WIN.model_window_attn_share_pct):
        assert fn(_ctx()) is None  # the parent: no counter, no trace
    with open(EXTRACT, encoding="utf-8") as fh:
        doc = json.load(fh)
    from benchmark.harness import xplane

    planes = xplane.from_extract(doc["trace"])
    ctx = _ctx(planes=planes, before=doc["before"], after=doc["after"],
               samples=[(t, s) for t, s in doc["samples"]])
    assert WIN.kv_window_trim_share_pct(ctx) == pytest.approx(100 * (830 - 110) / (950 - 150))
    # the pages the slots map, not what the prefix index keeps beside them
    assert WIN.kv_full_pages_peak_pct(ctx) == pytest.approx(100 * 640 / 2176)
    names = [e[0].split(" = ")[0] for e in WIN.window_attention_events(ctx)]
    assert names == ["%window_decode_attention.7", "%window_decode_attention.7"]
    assert WIN.model_window_attn_share_pct(ctx) == pytest.approx(100 * (9 + 9) / 1000)
    # a model of one kind under the same files: counters without the keys
    bare = _ctx(planes={"/device:TPU:0": {"XLA Modules": [("jit__lambda(1)", 0, 10)],
                                          "XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 5)]}},
                before={"kv_pages_in_use": 1}, after={"kv_pages_in_use": 2},
                samples=[(0.0, {"kv_pages_in_use": 1, "kv_pages_free": 9})])
    for fn in (WIN.kv_window_trim_share_pct, WIN.kv_full_pages_peak_pct,
               WIN.model_window_attn_share_pct):
        assert fn(bare) is None


def test_rehearsal_root_still_holds_every_committed_file_byte_identical(tmp_path):
    root = rehearsal_root.build(str(tmp_path))
    for base, _, files in os.walk(os.path.join(REPO, "benchmark")):
        if "__pycache__" in base:
            continue
        for name in files:
            here = os.path.join(base, name)
            there = os.path.join(root, os.path.relpath(here, REPO))
            with open(here, "rb") as a, open(there, "rb") as b:
                assert a.read() == b.read(), here
    manifest.check(manifest.Manifest(root))


def test_run_py_serves_the_architecture_end_to_end_on_the_cpu(tmp_path):
    """A tiny configuration of this architecture as an open-loop cell of a
    temporary root: the real server, pages by kind, both kinds' graphs, the
    reference and its int4 control; the counters by kind in the line."""
    root = rehearsal_root.build(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-mellum.json"), "w") as fh:
        json.dump(TINY, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({"name": "tiny-mellum", "source": TINY["source"],
                           "file": "benchmark/configs/tiny-mellum.json", "reduced": [],
                           "why": "CPU rehearsal size"})
    doc["workloads"].append({"name": "tiny-mellum-arrivals", "config": "tiny-mellum",
                             "traffic": "tiny-arrivals", "chips": 1, "why": "CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "tiny-moe-arrivals" in m.get("workloads", []):
            m["workloads"].append("tiny-mellum-arrivals")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--root", root,
         "--workload", "tiny-mellum-arrivals", "--seed", "3000000001", "--seconds", "3",
         "--trace", "1", "--rehearsal-cpu", "--control", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    got = line["metrics"]
    assert got["engine.compiles_in_window"]["value"] == 0
    assert 0 < got["kv.full_pages_peak_pct"]["value"] <= 100
    assert "kv.window_trim_share_pct" in got
    assert "model.window_attn_share_pct" not in got  # never from a CPU run
    assert "control (the int4 reference's first token" in done.stdout
