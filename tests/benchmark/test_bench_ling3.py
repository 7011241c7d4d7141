"""`benchmark/archs/bailing_hybrid.py` and what PR 40 adds to the benchmark:
the configuration file against the catalog row, the cut against the rule, the
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

A = manifest.load_file(os.path.join(REPO, "benchmark", "archs", "bailing_hybrid.py"),
                       "benchmark_arch")
KDA = manifest.load_file(os.path.join(REPO, "benchmark", "layer_metrics", "kda.py"),
                         "benchmark_reader")
CONFIG = "ling-3.0-flash-int8-ep8-d19"
CELL = "ling3-ep8-d19-reasoners48"
NEW = ["model.kda_decode_share_pct", "kernels.kda_decode_roofline_pct",
       "kernels.kda_prefill_roofline_pct", "kv.prefix_refused_state_pct"]
# what stood last in `per_layer` before this PR: PR 38's seven
BEFORE = "engine.compile_cache_miss_count"
TINY = dict(
    source="a CPU test size, never a cell", arch="bailing_hybrid",
    model_type="bailing_hybrid", num_hidden_layers=7, first_k_dense_replace=1,
    layer_types=["kda"] + ["kda", "kda", "mla"] * 2, hidden_size=128,
    intermediate_size=256, moe_intermediate_size=64,
    moe_shared_expert_intermediate_size=64, num_shared_experts=1,
    num_attention_heads=4, head_dim=16, short_conv_kernel_size=4, kda_lower_bound=-5,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    vocab_size=512, num_experts=8, router_n_experts=16, first_routed_expert=0,
    num_experts_per_tok=2, n_group=4, topk_group=2, routed_scaling_factor=2.5,
    norm_topk_prob=True, rope_theta=10000.0, rms_norm_eps=1e-6,
    max_position_embeddings=512, expert_swiglu_limit_list=[0] * 7,
    share_expert_swiglu_limit_list=[0] * 7, reduced={},
    assumed={"served_name": "tiny-ling", "slots": 3},
    check={"requests": 2, "router_margin_min": 0.02, "gap_percentile": 95,
           "logit_gap_limit": 0.3, "bulk_percentile": 75, "bulk_gap_limit": 0.3},
)


def _row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        return next(r for r in map(json.loads, fh) if r["name"] == "Ling-3.0-flash")


def test_the_configuration_is_the_catalog_row_cut_as_stated():
    man = manifest.Manifest(REPO)
    doc = published.check(man, CONFIG)
    assert doc["published"]["num_hidden_layers"] == 42 and "head_dim" in doc["widths"]
    config, row = man.config(CONFIG), _row()
    assert row["source_url"] == config["source"] == man.config_entry(CONFIG)["source"]
    assert set(row["config"]) <= set(config)  # every key of the row under its name
    changed = {k for k, v in row["config"].items() if config[k] != v}  # key by key
    assert changed == set(config["reduced"]) == set(man.config_entry(CONFIG)["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
        "max_position_embeddings", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"}
    for key, value in doc["published"].items():
        assert row["config"][key] == value, key  # the data file is the row's
    kept = [0] + list(range(2, 20))  # published layers: the dense one, three periods
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert config[key] == [row["config"][key][l] for l in kept] == [0] * 19
    group = row["config"]["layer_group_size"]
    assert config["layer_types"] == [
        "mla" if (l + 1) % group == 0 else "kda" for l in kept]
    assert config["layer_types"][1:7] == ["kda", "kda", "kda", "mla", "kda", "kda"]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_experts"], config["router_n_experts"],
            config["first_routed_expert"], config["vocab_size"],
            config["max_position_embeddings"]) == (19, 1, 64, 512, 0, 19648, 4096)
    # the guide's floors: whole periods, >= 4 layers after the dense one, >= 8
    # experts, >= an eighth of the vocabulary; one group of the router's eight
    assert (19 - 1) % group == 0 and 64 == 512 // config["n_group"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assumed = config["assumed"]
    for key in ("layer_types", "kda_decay_gate", "kda_qk_norm", "mla_qk_norm", "kda_conv",
                "output_gates", "group_score"):
        assert "the config has no key for it" in assumed[key], key
    assert assumed["slots"] == 48 and "16 chips" in assumed["deployment"]
    assert "num_nextn_predict_layers" in assumed["unused"]
    assert set(config["check"]) == set(man.config("openpangu-ultra-moe-int8-ep16-d5")["check"])
    d = A.dims_of(config)
    assert (d.count("kda"), d.count("mla"), d.held, d.experts, d.groups, d.top_groups,
            d.top_k, d.heads, d.head_dim) == (16, 3, 64, 512, 8, 4, 8, 32, 128)
    fields = A.model_fields(config, 4096)
    assert (fields["q_lora_rank"], fields["n_group"], fields["topk_group"],
            fields["experts_held"], fields["first_expert"]) == (0, 8, 4, 64, 0)


def test_the_traffic_is_forty_eight_lanes_of_long_answers_the_same_for_every_seed():
    man = manifest.Manifest(REPO)
    mix = man.traffic("reasoners48")
    assert (mix["kind"], mix["agents"], mix["turns_per_agent"], mix["system_tokens"],
            mix["task_tokens"], mix["answer_tokens"], mix["think_s"], mix["temperature"],
            mix["greedy_every"], mix["warm_s"]) == (
        "closed_agents", 48, 16, 1024, [64, 256], [512, 1536], 0, 0.7, 4, 8)
    assert mix["traffic_seed"] not in {
        man.traffic(t)["traffic_seed"] for t in ("agents8", "agents32-sys8k")}
    lanes = loadgen.build_schedule(mix)
    assert len(lanes) == 48 == man.config(CONFIG)["assumed"]["slots"]
    for lane in lanes:
        assert len(lane) == 16
        assert all(1024 + 64 <= t.prompt_tokens <= 1024 + 256 for t in lane)
        answers = sorted(t.answer_tokens for t in lane)
        assert 512 <= answers[0] and answers[-1] <= 1536
        assert sum(answers) / 16 == pytest.approx(1024, abs=1)  # stratified: the mean holds
        assert sum(t.greedy for t in lane) == 4
    # the longest sequence fits the context and the reference's kept rows
    assert max(t.prompt_tokens + t.answer_tokens for lane in lanes for t in lane) < 4096
    from benchmark.harness import reference

    assert reference.KEEP > 1536  # this architecture's file says why
    # the schedule is the file's: --seed chooses bytes, never the job
    assert loadgen.schedule_bytes(mix) == loadgen.schedule_bytes(dict(mix))
    one = loadgen.fill(lanes[0][0], 1, {True: 40, False: 20}, 4)
    other = loadgen.fill(lanes[0][0], 2, {True: 40, False: 20}, 4)
    assert one != other and [len(x) for x in one] == [len(x) for x in other]


def test_the_cell_and_its_four_metrics_are_appended_and_nothing_older_moved():
    man = manifest.Manifest(REPO)
    manifest.check(man)
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reasoners48", 1)
    assert all(w["chips"] == 1 for w in man.doc["workloads"])
    names = [w["name"] for w in man.doc["workloads"]]
    assert names.index(CELL) == names.index("mellum2-d20-mixedlen") + 1
    assert {m["name"] for m in man.end_to_end_of(CELL)} == {
        "tpot_p50_ms", "out_tok_s", "setup_s"}
    per_layer = man.doc["per_layer"]
    twin = {m["name"] for m in per_layer
            if "pangu-ultra-ep16-agents32" in m.get("workloads", [])}
    mine = {m["name"] for m in per_layer if CELL in m.get("workloads", [])}
    # the other closed-loop latent cell's and four, less the share of admissions
    # that hit the prefix cache: a model with a state kind has no index to count
    # `prefix_hits` in, so that reader finds nothing in this cell to read
    assert mine == (twin - {"batcher.ttft_fast_share_pct"}) | set(NEW)
    assert {m["name"] for m in per_layer if m.get("workloads") == [CELL]} == set(NEW)
    assert {f["name"] for f in man.layer_metrics_of(CELL)} == mine
    # the four stand together, in this order, right after what stood last
    # before them: a later PR's entries go after them and break nothing here
    order = [m["name"] for m in per_layer]
    at = order.index(BEFORE) + 1
    assert order[at:at + 4] == NEW
    moved = {m["name"]: (m["moves"], m["layer"], m["source"], m["better"])
             for m in per_layer if m["name"] in NEW}
    assert moved == {
        "model.kda_decode_share_pct": ("tpot_p50_ms", "model", "device_trace", "lower"),
        "kernels.kda_decode_roofline_pct": ("tpot_p50_ms", "kernels", "device_trace", "higher"),
        "kernels.kda_prefill_roofline_pct": ("out_tok_s", "kernels", "device_trace", "higher"),
        "kv.prefix_refused_state_pct": ("out_tok_s", "KV manager", "program_counter", "lower"),
    }
    # every older entry as the parent had it, in its order: taking this PR's
    # names and entries away leaves the parent's document (the parent's text is
    # not in a checkout; its shape is: nothing but appended names and entries)
    doc = json.loads(json.dumps(man.doc))
    doc["configs"] = [c for c in doc["configs"] if c["name"] != CONFIG]
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] != CELL]
    doc["per_layer"] = [m for m in doc["per_layer"] if m["name"] not in NEW]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            assert m["workloads"].count(CELL) <= 1
            if CELL in m["workloads"]:
                assert m["workloads"][-1] == CELL or m["workloads"].index(CELL) > \
                    m["workloads"].index("pangu-ultra-ep16-agents32")
    assert [c["name"] for c in doc["configs"]][:5] == [
        "mistral-7b-int8", "mixtral-8x7b-int8-d6", "openpangu-ultra-moe-int8-ep16-d5",
        "xing4-29b-a4b-int8-d13", "mellum2-12b-a2.5b-int8-d20"]
    assert [w["name"] for w in doc["workloads"]][:7] == [
        "mistral7b-agents8", "mixtral-d6-agents8", "mistral7b-longprompt",
        "mixtral-d6-longprompt", "pangu-ultra-ep16-agents32", "xing4-d13-longprompt",
        "mellum2-d20-mixedlen"]


def test_what_the_fifth_cell_s_own_test_holds_is_held_of_the_lists_without_this_cell(
        monkeypatch):
    """test_bench_pangu.py pins the latent kernel's and the held experts' four
    metrics to its own cell ALONE. This cell runs the same kernel over a share
    of its experts and is appended to their lists, that file is not this PR's to
    edit, and tests/conftest.py marks its one test as expected to fail. Its whole
    body runs here on the lists with this cell's name taken out: that the Pangu
    cell stands alone there is all that is lost."""
    import test_bench_pangu as theirs

    four = {"kernels.mla_decode_roofline_pct", "model.mla_decode_share_pct",
            "moe.local_pick_share_pct", "moe.rows_per_local_pick"}

    class WithoutThisCell(manifest.Manifest):
        def __init__(self, root):
            super().__init__(root)
            for m in self.doc["per_layer"]:
                if m["name"] in four:  # appended, and nothing else of it changed
                    assert m["workloads"] == ["pangu-ultra-ep16-agents32", CELL]
                    m["workloads"] = m["workloads"][:1]

    monkeypatch.setattr(manifest, "Manifest", WithoutThisCell)
    theirs.test_the_configuration_states_its_share_and_what_it_assumed()


def test_what_set_up_s_own_test_holds_is_held_of_the_lists_without_this_cell(monkeypatch):
    """test_bench_setup.py pins set-up's seven metrics to four cells. This
    cell's set-up is the longest there is and it is appended to their lists;
    that file is not this PR's to edit and tests/conftest.py marks its one test
    as expected to fail. Its whole body runs here on the lists with this cell's
    name taken out: that the four stand alone there is all that is lost."""
    import test_bench_setup as theirs

    class WithoutThisCell(manifest.Manifest):
        def __init__(self, root):
            super().__init__(root)
            for m in self.doc["per_layer"]:
                if m["moves"] == "setup_s":  # appended, and nothing else changed
                    assert m["workloads"] == theirs.LISTED + [CELL]
                    m["workloads"] = m["workloads"][:-1]

    monkeypatch.setattr(manifest, "Manifest", WithoutThisCell)
    theirs.test_the_committed_benchmark_lists_the_seven_beneath_setup_s()


def test_the_roofline_counts_against_a_hand_count():
    d = A.dims_of(manifest.Manifest(REPO).config(CONFIG))
    state = 32 * 128 * 128 * 4  # one slot's state of one KDA layer: 2.1 MB
    assert A.kda_state_bytes(d) == state == 2_097_152
    # a slot's states over the 16 KDA layers: the issue's 33.5 MB
    assert 16 * state == pytest.approx(33.5e6, rel=0.01)
    # a step of 48 live slots reads and writes every state once: 3.2 GB
    io = 32 * (5 * 128 + 1) * 4
    assert A.kda_step_bytes(d, 48) == 16 * 48 * (2 * state + io)
    assert A.kda_step_bytes(d, 48) == pytest.approx(3.28e9, rel=0.01)
    assert A.kda_step_ops(d, 48) == 16 * 48 * 32 * 7 * 128 * 128
    # a chunk: the row-by-row count of its rows, the slot's states once
    assert A.kda_chunk_ops(d, 512) == 16 * 512 * 32 * 7 * 128 * 128
    assert A.kda_chunk_bytes(d, 512) == 16 * (2 * state + 512 * io)
    # matrices: the issue's arithmetic (63.05 M a KDA mixer, 31.97 M an MLA one)
    kda = 2560 * 5 * 4096 + 4096 * 2560 + 2560 * 32 + 4 * 3 * 4096
    assert kda == pytest.approx(63.05e6, rel=0.001)
    assert A.kda_matrix_bytes(d) == pytest.approx(kda, rel=0.005)  # int8 + column scales
    mla = 2560 * (32 * 192 + 576) + 512 * 32 * 256 + 4096 * 2560 + 2560 * 32
    assert mla == pytest.approx(31.97e6, rel=0.001)
    assert A.mla_matrix_bytes(d) == pytest.approx(mla, rel=0.01)
    # a decode step's least bytes at 48 slots of 2,000 rows: weights the step
    # touches + states + latent rows; the held experts 48 tokens touch a layer
    touched = 64 * (1 - (1 - 8 / 512) ** 48)
    assert A.held_touched(d, 48) == pytest.approx(touched) and 33 < touched < 35
    step = A.decode_step_bytes(d, 48, 48 * 2000)
    assert 7.5e9 < step < 9.5e9  # the issue's about 8.6 GB
    assert A.mla_decode_bytes(d, 48, 96000) == 3 * (96000 * 576 * 2 + 48 * 32 * 1088 * 2)
    assert A.trace_markers(d) == {"decode_kernel": "paged_mla_decode_attention",
                                  "kernels_per_step": 3}
    # prefill: every new row through the recurrence and the matrices
    assert A.prefill_ops(d, [1200], [0]) > 1200 * 16 * A.kda_row_ops(d)
    assert A.prefill_bytes(d, 512) > A.layers_bytes(d, 512)


def _ctx(**kw):
    d = A.dims_of(manifest.Manifest(REPO).config(CONFIG))
    base = dict(planes=None, peaks=None, arch=A, dims=d, samples=[], trace_w0=0.0,
                trace_w1=0.0, before={}, after={}, records=[], timelines=[], w0=0.0,
                w1=1.0, cache={})
    base.update(kw)
    ctx = SimpleNamespace(**base)
    ctx.delta = lambda key: (ctx.after[key] - ctx.before[key]
                             if key in ctx.before and key in ctx.after else None)
    ctx.due = lambda: [r for r in ctx.records if r.ok]
    return ctx


def test_the_four_readers_on_a_small_trace_and_on_the_parent():
    readers = (KDA.model_kda_decode_share_pct, KDA.kernels_kda_decode_roofline_pct,
               KDA.kernels_kda_prefill_roofline_pct, KDA.kv_prefix_refused_state_pct)
    for fn in readers:
        assert fn(_ctx()) is None  # the parent: no counter, no trace
    from benchmark.harness.peaks import PEAKS

    d = _ctx().dims
    us = 1000
    # one decode program of one step (16 kda_step calls of 300 us) and one
    # chunk program (16 kda_chunk calls of 100 us); an event that only READS a
    # kernel's result does not count
    ops = [(f"%kda_step.{i % 2} = (f32[48,32,128], f32[16,49,32,128,128]) custom-call()",
            1000 * us + i * 400 * us, 300 * us) for i in range(16)]
    ops += [("%fusion.9 = f32[48,4096] fusion(%kda_step.1)", 8000 * us, 50 * us)]
    ops += [(f"%kda_chunk.3 = (f32[8,32,64,128], f32[32,128,128]) custom-call()",
             20000 * us + i * 1000 * us, 100 * us) for i in range(16)]
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit__lambda(7)", 0, 10000 * us),
                        ("jit__final_chunk_impl(9)", 20000 * us, 20000 * us)],
        "XLA Ops": ops}}
    turn = SimpleNamespace(prompt_tokens=1200, answer_tokens=100)
    rec = SimpleNamespace(turn=turn, chunks=[-1.0 + 0.1 * i for i in range(100)], ok=True)
    tl = SimpleNamespace(t0=0.0, request_id="x",
                         events=[(0.01, "prefill", {"tokens": 512, "chunk": 1})])
    ctx = _ctx(planes=planes, peaks=PEAKS["TPU v5 lite"], records=[rec] * 48, timelines=[tl],
               trace_w0=0.0, trace_w1=0.04,
               before={"kda_rows_decode": 0, "prefix_rows_refused_state": 0,
                       "kda_rows_prefill": 0},
               after={"kda_rows_decode": 768, "prefix_rows_refused_state": 48 * 1024,
                      "kda_rows_prefill": 48 * (512 + 512 + 256) * 16})
    assert KDA.model_kda_decode_share_pct(ctx) == pytest.approx(100 * 16 * 300 / 10000)
    least = A.kda_step_bytes(d, 48) / 819e9
    assert KDA.kernels_kda_decode_roofline_pct(ctx) == pytest.approx(
        100 * least / (16 * 300e-6))
    from benchmark.harness import roofline

    chunk = roofline.least_seconds(A.kda_chunk_ops(d, 512), A.kda_chunk_bytes(d, 512),
                                   PEAKS["TPU v5 lite"])["seconds"]
    assert KDA.kernels_kda_prefill_roofline_pct(ctx) == pytest.approx(
        100 * chunk / (16 * 100e-6))
    assert KDA.kv_prefix_refused_state_pct(ctx) == pytest.approx(100 * 1024 / 1280)
    for fn in readers[:3]:
        assert 0 < fn(ctx) <= 100
    # no decode row counted: the share reads nothing
    idle = _ctx(planes=planes, before={"kda_rows_decode": 5}, after={"kda_rows_decode": 5})
    assert KDA.model_kda_decode_share_pct(idle) is None


def test_run_py_serves_the_architecture_end_to_end_on_the_cpu(tmp_path):
    """A toy configuration of this architecture as a closed-loop cell of a
    temporary root: the real server, the state kind beside the latent pool,
    chunked admission of every prompt, the reference row by row and its
    control; the state's counters in the line."""
    root = rehearsal_root.build(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-ling.json"), "w") as fh:
        json.dump(TINY, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({"name": "tiny-ling", "source": TINY["source"],
                           "file": "benchmark/configs/tiny-ling.json", "reduced": [],
                           "why": "CPU rehearsal size"})
    doc["workloads"].append({"name": "tiny-ling-agents", "config": "tiny-ling",
                             "traffic": "tiny-agents", "chips": 1, "why": "CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "tiny-other-agents" in m.get("workloads", []) or m["name"] in NEW:
            m["workloads"].append("tiny-ling-agents")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--root", root,
         "--workload", "tiny-ling-agents", "--seed", "3000000001", "--seconds", "6",
         "--trace", "1", "--rehearsal-cpu", "--control", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    got = line["metrics"]
    assert got["engine.compiles_in_window"]["value"] == 0
    # every turn after an agent's first repeats its 128-token system prompt: a
    # block the index would have served, refused for want of the state
    assert 0 < got["kv.prefix_refused_state_pct"]["value"] <= 100
    assert "model.kda_decode_share_pct" not in got  # never from a CPU run
    assert "control (the int4 reference's first token" in done.stdout
