"""`benchmark/archs/nemotron_h.py` and what PR 42 adds to the benchmark: the
configuration file against the catalog row, the cut against the rule, the
traffic file's schedule, the new entries' place in BENCHMARK.json (every older
entry byte for byte and in its order), the roofline counts against a hand
count, the four readers on a small trace, and `run.py` end to end on the CPU
at a toy size."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import published  # noqa: E402
import rehearsal_root  # noqa: E402
from benchmark.harness import loadgen, manifest  # noqa: E402

A = manifest.load_file(os.path.join(REPO, "benchmark", "archs", "nemotron_h.py"),
                       "benchmark_arch")
MAMBA = manifest.load_file(os.path.join(REPO, "benchmark", "layer_metrics", "mamba.py"),
                           "benchmark_reader")
CONFIG = "nemotron-3-nano-int8-ep2-d28"
CELL = "nemotron3-ep2-d28-shortchat"
NEW = ["model.mamba_decode_share_pct", "kernels.mamba_decode_roofline_pct",
       "kernels.mamba_prefill_roofline_pct", "kv.state_slots_peak_pct"]
# what stood last in `per_layer` before this PR: PR 40's four
BEFORE = "kv.prefix_refused_state_pct"
TINY = dict(
    source="a CPU test size, never a cell", arch="nemotron_h", model_type="nemotron_h",
    num_hidden_layers=14, hybrid_override_pattern="MEMEM*E" * 2, hidden_size=128,
    intermediate_size=64, moe_intermediate_size=96,
    moe_shared_expert_intermediate_size=192, n_shared_experts=1, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=128, n_groups=2, conv_kernel=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
    n_routed_experts=8, router_n_experts=16, first_routed_expert=0,
    num_experts_per_tok=2, n_group=1, topk_group=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, layer_norm_epsilon=1e-5, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, max_position_embeddings=512, reduced={},
    assumed={"served_name": "tiny-nemo", "slots": 3},
    check={"requests": 2, "router_margin_min": 0.02, "gap_percentile": 95,
           "logit_gap_limit": 0.3, "bulk_percentile": 75, "bulk_gap_limit": 0.3},
)


def _row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        return next(r for r in map(json.loads, fh)
                    if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def test_the_configuration_is_the_catalog_row_cut_as_stated():
    man = manifest.Manifest(REPO)
    doc = published.check(man, CONFIG)
    assert doc["published"]["num_hidden_layers"] == 52 and "mamba_head_dim" in doc["widths"]
    config, row = man.config(CONFIG), _row()
    assert row["source_url"] == config["source"] == man.config_entry(CONFIG)["source"]
    assert set(row["config"]) <= set(config)  # every key of the row under its name
    changed = {k for k, v in row["config"].items() if config[k] != v}  # key by key
    assert changed == set(config["reduced"]) == set(man.config_entry(CONFIG)["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    for key, value in doc["published"].items():
        assert row["config"][key] == value, key  # the data file is the row's
    # four whole repeats of the pattern's MEMEM*E, its first 28 characters
    whole = row["config"]["hybrid_override_pattern"]
    assert config["hybrid_override_pattern"] == whole[:28] == "MEMEM*E" * 4
    assert (whole.count("M"), whole.count("E"), whole.count("*"), len(whole)) == (23, 23, 6, 52)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["router_n_experts"], config["first_routed_expert"],
            config["vocab_size"], config["max_position_embeddings"]) == (
        28, 64, 128, 0, 65536, 4096)
    # the guide's floors: whole periods, >= 4 layers, >= 8 experts, >= an eighth
    # of the vocabulary; one of 2 chips' share of both
    assert 28 % 7 == 0 and 64 * 2 == row["config"]["n_routed_experts"]
    assert config["vocab_size"] * 2 == row["config"]["vocab_size"]
    assumed = config["assumed"]
    assert "NO rotary embedding" in assumed["no_rotary"]
    assert "float32 recurrent state" in assumed["state_types"]
    for key in ("time_step_limit", "gated_norm"):
        assert "the config has no key for it" in assumed[key], key
    assert assumed["slots"] == 32 and "two pipeline stages of 2 chips" in assumed["deployment"]
    assert "intermediate_size" in assumed["unused"] and "expand" in assumed["unused"]
    assert set(config["check"]) >= set(man.config("openpangu-ultra-moe-int8-ep16-d5")["check"])
    d = A.dims_of(config)
    assert (d.count("mamba2"), d.count("moe"), d.count("full"), d.held, d.experts, d.top_k,
            d.ssm_heads, d.ssm_head_dim, d.ssm_state, d.ssm_groups, d.conv, d.heads,
            d.kv_heads, d.head_dim, d.expert_ffn, d.shared_ffn) == (
        12, 12, 4, 64, 128, 6, 64, 64, 128, 8, 4, 32, 2, 128, 1856, 3712)
    assert (d.inner, d.conv_dim, d.in_width) == (4096, 6144, 10304)
    fields = A.model_fields(config, 4096)
    assert (fields["expert_act"], fields["rotary"], fields["experts_held"],
            fields["first_expert"], fields["moe_scoring"]) == ("relu2", False, 64, 0, "sigmoid")
    assert fields["layer_types"][:7] == ["mamba2", "moe", "mamba2", "moe", "mamba2", "full", "moe"]
    with pytest.raises(ValueError, match="dense `-` kind"):
        A.dims_of({**config, "hybrid_override_pattern": "M-" * 14})


def test_the_traffic_is_one_lane_of_short_chat_the_same_for_every_seed():
    man = manifest.Manifest(REPO)
    mix = man.traffic("shortchat-nemo28")
    assert (mix["kind"], mix["prompt_tokens"], mix["prompt_dist"], mix["answer_tokens"],
            mix["temperature"], mix["greedy_every"], mix["warm_s"]) == (
        "open_arrivals", [128, 1024], "log_uniform", [48, 192], 0.7, 4, 8)
    assert mix["traffic_seed"] not in {
        man.traffic(w["traffic"])["traffic_seed"] for w in man.doc["workloads"]
        if w["name"] != CELL}
    # enough requests for 70 s at the committed rate: warm_s, the window, the drain
    assert mix["n_requests"] >= 70 * mix["rate_rps"]
    assert "Knee" in mix["sweep"] and f"{mix['rate_rps']:g}" in mix["sweep"]
    lanes = loadgen.build_schedule(mix)
    assert len(lanes) == 1 and len(lanes[0]) == mix["n_requests"]
    turns = lanes[0]
    assert all(128 <= t.prompt_tokens <= 1024 and 48 <= t.answer_tokens <= 192 for t in turns)
    n = len(turns)
    assert 380 < sum(t.prompt_tokens for t in turns) / n < 480  # log-uniform: about 430
    assert sum(t.answer_tokens for t in turns) / n == pytest.approx(120, abs=1)
    assert sum(t.greedy for t in turns) == pytest.approx(n / 4, abs=1)
    # the longest sequence fits the context and the reference's kept rows as they are
    from benchmark.harness import reference

    # (256 as committed; `archs/bailing_hybrid.py` raises it for its own process)
    assert max(t.answer_tokens for t in turns) < 256 <= reference.KEEP
    assert max(t.prompt_tokens + t.answer_tokens for t in turns) + 64 < 4096
    # the schedule is the file's: --seed chooses bytes, never the job
    assert loadgen.schedule_bytes(mix) == loadgen.schedule_bytes(dict(mix))
    one = loadgen.fill(turns[0], 1, {True: 40, False: 20}, 4)
    other = loadgen.fill(turns[0], 2, {True: 40, False: 20}, 4)
    assert one != other and [len(x) for x in one] == [len(x) for x in other]


def test_no_arrival_falls_where_the_harness_stops_offering():
    """`loadgen.stop_and_drain` reads its list of request threads while the
    dispatcher may be starting one (PERF.md section 7, PR 35 (f)): a request
    due just after the window's close can be started and never joined, and then
    reads as lost and the run as not correct. At 5.6 arrivals a second a draw
    leaves that stretch empty only by one of its few longest gaps, at most
    ln(2 n_requests) / rate long: this file's draw puts one there (a later
    change of `rate_rps`, `warm_s`, `n_requests` or `run_seconds` has to look
    again)."""
    man = manifest.Manifest(REPO)
    mix = man.traffic("shortchat-nemo28")
    close = mix["warm_s"] + man.doc["run_seconds"]
    (lane,) = loadgen.build_schedule(mix)
    assert not [t.due_s for t in lane if close - 0.2 < t.due_s < close + 1.8]
    due = [t for t in lane if mix["warm_s"] <= t.due_s < close]
    # 5.6 a second less the hole's two seconds, more or less the draw's luck
    assert 200 <= len(due) <= 230
    assert 400 < sum(t.prompt_tokens for t in due) / len(due) < 460
    assert 115 < sum(t.answer_tokens for t in due) / len(due) < 125


def test_the_cell_and_its_four_metrics_are_appended_and_nothing_older_moved():
    man = manifest.Manifest(REPO)
    manifest.check(man)
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "shortchat-nemo28", 1)
    assert all(w["chips"] == 1 for w in man.doc["workloads"])
    names = [w["name"] for w in man.doc["workloads"]]
    assert names.index(CELL) == names.index("ling3-ep8-d19-reasoners48") + 1
    assert {m["name"] for m in man.end_to_end_of(CELL)} == {
        "ttft_p80_ms", "tpot_p50_ms", "setup_s"}
    per_layer = man.doc["per_layer"]
    twin = {m["name"] for m in per_layer
            if "mellum2-d20-mixedlen" in m.get("workloads", [])}
    mine = {m["name"] for m in per_layer if CELL in m.get("workloads", [])}
    # the other open-loop cell of a stack of kinds' and four, less its own three
    # and the two of the prefix index: a model with a state kind builds no index,
    # so those readers find nothing in this cell to read
    assert mine == (twin - {"kv.prefix_hit_pct", "kv.window_trim_share_pct",
                            "kv.full_pages_peak_pct", "model.window_attn_share_pct"}
                    ) | set(NEW)
    assert len(mine) == 22 + 4 and "batcher.ttft_fast_share_pct" not in mine
    assert {m["name"] for m in per_layer if m.get("workloads") == [CELL]} == set(NEW)
    assert {f["name"] for f in man.layer_metrics_of(CELL)} == mine
    # the four stand together, in this order, right AFTER what stood last before
    # them: a later PR's entries go after them and break nothing here
    order = [m["name"] for m in per_layer]
    at = order.index(BEFORE) + 1
    assert order[at:at + 4] == NEW
    moved = {m["name"]: (m["moves"], m["layer"], m["source"], m["better"])
             for m in per_layer if m["name"] in NEW}
    assert moved == {
        "model.mamba_decode_share_pct": ("tpot_p50_ms", "model", "device_trace", "lower"),
        "kernels.mamba_decode_roofline_pct": ("tpot_p50_ms", "kernels", "device_trace", "higher"),
        "kernels.mamba_prefill_roofline_pct": ("ttft_p80_ms", "kernels", "device_trace", "higher"),
        "kv.state_slots_peak_pct": ("ttft_p80_ms", "KV manager", "program_counter", "lower"),
    }
    # every older entry as the parent had it, in its order: taking this PR's
    # names and entries away leaves the parent's document (the parent's text is
    # not in a checkout; its shape is: nothing but appended names and entries)
    doc = json.loads(json.dumps(man.doc))
    doc["configs"] = [c for c in doc["configs"] if c["name"] != CONFIG]
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] != CELL]
    doc["per_layer"] = [m for m in doc["per_layer"] if m["name"] not in NEW]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"].count(CELL) == 1
            assert m["workloads"].index(CELL) > m["workloads"].index("mellum2-d20-mixedlen")
    assert [c["name"] for c in doc["configs"]] == [
        "mistral-7b-int8", "mixtral-8x7b-int8-d6", "openpangu-ultra-moe-int8-ep16-d5",
        "xing4-29b-a4b-int8-d13", "mellum2-12b-a2.5b-int8-d20", "ling-3.0-flash-int8-ep8-d19"]
    assert [w["name"] for w in doc["workloads"]] == [
        "mistral7b-agents8", "mixtral-d6-agents8", "mistral7b-longprompt",
        "mixtral-d6-longprompt", "pangu-ultra-ep16-agents32", "xing4-d13-longprompt",
        "mellum2-d20-mixedlen", "ling3-ep8-d19-reasoners48"]
    # the lists this cell is NOT on, each for its reason (ISSUE 42)
    for name in ("moe.local_pick_share_pct", "moe.rows_per_local_pick",
                 "serving.free_slot_wait_ms", "kv.prefix_hit_pct",
                 "batcher.ttft_fast_share_pct", "kv.prefix_refused_state_pct",
                 "rpc.load_model_s", "engine.warmup_compile_s"):
        assert name not in mine, name


def test_the_routers_biases_give_every_seed_the_same_work():
    """`router_biases`: what the published model's training does to
    `e_score_correction_bias`, done once over seeded tokens. Rows that share a
    large common part (what depth makes of random matrices at the cell's
    widths) send most picks to a few experts under the drawn bias, whichever
    the seed's matrices favour; under the calibrated one every expert is chosen
    about as often as any other. The program's tree and the reference's layer
    read the same array, and a trace for shapes computes and keeps nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import reference as R
    from benchmark.harness import weights as W

    d = A.dims_of(TINY)
    even = d.top_k / d.experts
    for seed in (7, 2 ** 31 + 5):
        lw = A._one_layer(d, "moe", *W.split_seed(seed), jnp.int32(1))
        rng = np.random.RandomState(seed % 97)
        xs = jnp.asarray(3.0 * rng.randn(d.hidden) + rng.randn(4, 256, d.hidden),
                         jnp.float32)
        _, bias = A._calibration_layer(d, xs, lw, "moe")
        scores = jax.nn.sigmoid(R.rms(xs, lw["norm"], d.eps).reshape(-1, d.hidden)
                                @ lw["w_router"].astype(jnp.float32))

        def share(b):
            picks = np.asarray(A.choose(d, scores, b)[0]).ravel()
            return np.bincount(picks, minlength=d.experts) / len(picks) * d.top_k

        drawn, set_ = share(lw["router_bias"]), share(bias)
        assert drawn.max() > 3 * even and drawn.min() < even / 3
        assert set_.max() < 1.3 * even and set_.min() > even / 1.3
        assert abs(float(bias.mean())) < 1e-6
    A._BIASES.clear()
    shapes = jax.eval_shape(lambda: A.build_params(d, 7))
    assert shapes["layers"]["by_kind"]["moe"]["router_bias"].shape == (6, 16)
    assert not A._BIASES
    tree = A.build_params(d, 7)
    rows = np.asarray(tree["layers"]["by_kind"]["moe"]["router_bias"])
    assert list(A._BIASES) == [(d, 7)] and rows.std() > 0
    moe_layers = [l for l in range(d.layers) if d.kind(l) == "moe"]
    for i, l in enumerate(moe_layers):
        assert np.array_equal(np.asarray(A.build_layer(d, 7, l)["router_bias"]), rows[i])
    assert "router_bias" not in A.build_layer(d, 7, 0)


def test_the_roofline_counts_against_a_hand_count():
    d = A.dims_of(manifest.Manifest(REPO).config(CONFIG))
    state = 64 * 64 * 128 * 4  # one slot's state of one Mamba layer: 2.1 MB
    assert A.mamba_state_bytes(d) == state == 2_097_152
    # a slot's states over the 12 Mamba layers: the issue's 25.2 MB
    assert 12 * state == pytest.approx(25.2e6, rel=0.01)
    # a step of 12 live slots reads and writes each state once: the issue's 0.6 GB
    io = (2 * 4096 + 64 + 2 * 8 * 128) * 4
    assert A.mamba_step_bytes(d, 12) == 12 * 12 * (2 * state + io)
    assert A.mamba_step_bytes(d, 12) == pytest.approx(0.61e9, rel=0.02)
    assert A.mamba_step_ops(d, 12) == 12 * 12 * 64 * 5 * 64 * 128
    # a chunk: the row-by-row count of its REAL rows, the slot's states once
    assert A.mamba_chunk_ops(d, 300) == 12 * 300 * 64 * 5 * 64 * 128
    assert A.mamba_chunk_bytes(d, 300) == 12 * (2 * state + 300 * io)
    # matrices: the issue's arithmetic (38.74 M a Mamba mixer, 23.40 M an
    # attention mixer, 9.98 M an expert of TWO matrices, 19.96 M the shared one)
    mamba = 2688 * 10304 + 4096 * 2688 + 5 * 6144 + 4096 + 3 * 64 + 2688
    assert mamba == pytest.approx(38.74e6, rel=0.001)
    assert A.mamba_matrix_bytes(d) == pytest.approx(mamba, rel=0.005)  # int8 + column scales
    attn = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    assert attn == pytest.approx(23.40e6, rel=0.001)
    assert A.attn_matrix_bytes(d) == pytest.approx(attn, rel=0.005)
    assert A.ffn_bytes(d, 1856) == 2 * 2688 * 1856 + 4 * (1856 + 2688)
    assert A.ffn_bytes(d, 1856) == pytest.approx(9.98e6, rel=0.002)
    assert A.ffn_bytes(d, 3712) == pytest.approx(19.96e6, rel=0.002)
    # a decode step's least bytes at 12 slots of 500 rows: the weights the step
    # touches + states + K/V rows; the held experts 12 tokens touch a layer
    touched = 64 * (1 - (1 - 6 / 128) ** 12)
    assert A.held_touched(d, 12) == pytest.approx(touched) and 27 < touched < 29
    step = A.decode_step_bytes(d, 12, 12 * 500)
    assert 4.5e9 < step < 5.5e9  # the issue's about 5 GB
    assert A.attn_decode_bytes(d, 12, 6000) == 4 * (6000 * 512 * 2 + 12 * 2 * 4096 * 2)
    assert A.trace_markers(d) == {"decode_kernel": "paged_decode_attention",
                                  "kernels_per_step": 4}
    # prefill: every new row through the recurrence and the matrices
    assert A.prefill_ops(d, [400], [0]) > 400 * 12 * A.mamba_row_ops(d)
    assert A.prefill_bytes(d, 512) > A.layers_bytes(d, 512)
    # the whole share: the issue's 8.82 B parameters
    params = (12 * mamba + 4 * attn + 12 * (64 * 2 * 2688 * 1856 + 2 * 2688 * 3712
                                            + 2688 * 128) + 2 * 65536 * 2688)
    assert params == pytest.approx(8.82e9, rel=0.005)


def _ctx(**kw):
    d = A.dims_of(manifest.Manifest(REPO).config(CONFIG))
    base = dict(planes=None, peaks=None, arch=A, dims=d, samples=[], trace_w0=0.0,
                trace_w1=0.0, before={}, after={}, records=[], timelines=[], w0=0.0,
                w1=1.0, cache={}, slots=32)
    base.update(kw)
    ctx = SimpleNamespace(**base)
    ctx.delta = lambda key: (ctx.after[key] - ctx.before[key]
                             if key in ctx.before and key in ctx.after else None)
    ctx.due = lambda: [r for r in ctx.records if r.ok]
    return ctx


def test_the_four_readers_on_a_small_trace_and_on_the_parent():
    readers = (MAMBA.model_mamba_decode_share_pct, MAMBA.kernels_mamba_decode_roofline_pct,
               MAMBA.kernels_mamba_prefill_roofline_pct, MAMBA.kv_state_slots_peak_pct)
    for fn in readers:
        assert fn(_ctx()) is None  # the parent: no counter, no trace
    from benchmark.harness.peaks import PEAKS

    d = _ctx().dims
    us = 1000
    # one decode program of one step (12 mamba_step calls of 100 us) and one
    # chunk program (12 mamba_chunk calls of 60 us); an event that only READS a
    # kernel's result does not count
    ops = [(f"%mamba_step.{i % 2} = (f32[64,64,32], f32[12,33,64,64,128]) custom-call()",
            1000 * us + i * 400 * us, 100 * us) for i in range(12)]
    ops += [("%fusion.9 = f32[32,4096] fusion(%mamba_step.1)", 8000 * us, 50 * us)]
    ops += [(f"%mamba_chunk.3 = (f32[4,64,64,128], f32[64,64,128]) custom-call()",
             20000 * us + i * 1000 * us, 60 * us) for i in range(12)]
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit__lambda(7)", 0, 10000 * us),
                        ("jit__final_chunk_impl(9)", 20000 * us, 20000 * us)],
        "XLA Ops": ops}}
    turn = SimpleNamespace(prompt_tokens=300, answer_tokens=100)
    rec = SimpleNamespace(turn=turn, chunks=[-1.0 + 0.1 * i for i in range(100)], ok=True)
    tl = SimpleNamespace(t0=0.0, request_id="x",
                         events=[(0.01, "prefill", {"tokens": 300, "chunk": 1})])
    ctx = _ctx(planes=planes, peaks=PEAKS["TPU v5 lite"], records=[rec] * 12, timelines=[tl],
               trace_w0=0.0, trace_w1=0.04,
               samples=[(0.1, {"kv_state_slots": 9}), (0.3, {"kv_state_slots": 14}),
                        (0.5, {"kv_pages_in_use": 3})],
               before={"mamba_rows_decode": 0, "mamba_rows_prefill": 0},
               after={"mamba_rows_decode": 768, "mamba_rows_prefill": 512 * 12})
    assert MAMBA.model_mamba_decode_share_pct(ctx) == pytest.approx(100 * 12 * 100 / 10000)
    least = A.mamba_step_bytes(d, 12) / 819e9
    assert MAMBA.kernels_mamba_decode_roofline_pct(ctx) == pytest.approx(
        100 * least / (12 * 100e-6))
    from benchmark.harness import roofline

    chunk = roofline.least_seconds(A.mamba_chunk_ops(d, 300), A.mamba_chunk_bytes(d, 300),
                                   PEAKS["TPU v5 lite"])["seconds"]
    assert MAMBA.kernels_mamba_prefill_roofline_pct(ctx) == pytest.approx(
        100 * chunk / (12 * 60e-6))
    assert MAMBA.kv_state_slots_peak_pct(ctx) == pytest.approx(100 * 14 / 32)
    for fn in readers:
        assert 0 < fn(ctx) <= 100
    # no decode row counted: the share reads nothing
    idle = _ctx(planes=planes, before={"mamba_rows_decode": 5}, after={"mamba_rows_decode": 5})
    assert MAMBA.model_mamba_decode_share_pct(idle) is None


def test_run_py_serves_the_architecture_end_to_end_on_the_cpu(tmp_path):
    """A toy configuration of this architecture as an open-loop cell of a
    temporary root: the real server, the state kind beside the K/V pool,
    chunked admission of every prompt, the reference row by row and its
    control; the state's counters in the line."""
    root = rehearsal_root.build(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-nemo.json"), "w") as fh:
        json.dump(TINY, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({"name": "tiny-nemo", "source": TINY["source"],
                           "file": "benchmark/configs/tiny-nemo.json", "reduced": [],
                           "why": "CPU rehearsal size"})
    doc["workloads"].append({"name": "tiny-nemo-arrivals", "config": "tiny-nemo",
                             "traffic": "tiny-arrivals", "chips": 1, "why": "CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "tiny-moe-arrivals" in m.get("workloads", []) or m["name"] in NEW:
            m["workloads"].append("tiny-nemo-arrivals")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--root", root,
         "--workload", "tiny-nemo-arrivals", "--seed", "3000000001", "--seconds", "6",
         "--trace", "1", "--rehearsal-cpu", "--control", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    got = line["metrics"]
    assert got["engine.compiles_in_window"]["value"] == 0
    assert 0 < got["kv.state_slots_peak_pct"]["value"] <= 100
    assert "model.mamba_decode_share_pct" not in got  # never from a CPU run
    assert "kv.prefix_hit_pct" not in got  # a state kind builds no index
    assert "control (the int4 reference's first token" in done.stdout
