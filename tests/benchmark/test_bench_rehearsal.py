"""run.py end to end on the CPU at a tiny size: the keys of the last line,
no device metric from a CPU run, a cell / configuration / mix / per-layer
metric added by new files and entries alone, and `correct` coming out false
when the timed path is broken underneath."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import rehearsal_root  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")


def _run(args, cwd=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)  # one CPU device, as the rehearsal is meant
    return subprocess.run([sys.executable, RUN] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal_root.build(str(tmp_path_factory.mktemp("bench")))


def test_adding_cells_and_an_architecture_edits_no_file_that_is_there(root):
    """Every committed file under benchmark/ is in the temporary root byte for
    byte; what the root has besides is what `rehearsal_root` says it added."""
    import filecmp

    committed, there = set(), set()
    for base, found in ((os.path.join(REPO, "benchmark"), committed),
                        (os.path.join(root, "benchmark"), there)):
        for folder, _, files in os.walk(base):
            if "__pycache__" not in folder:
                found.update(os.path.relpath(os.path.join(folder, f), os.path.dirname(base))
                             for f in files if not f.endswith(".pyc"))
    assert committed <= there and there - committed == rehearsal_root.added()
    assert len(committed) > 40
    for rel in sorted(committed):
        assert filecmp.cmp(os.path.join(REPO, rel), os.path.join(root, rel), shallow=False), rel
    # the third configuration is of another architecture by its `arch` key alone,
    # and keeps to the rule on what a configuration may change by its own file
    sys.path.insert(0, HERE)
    import published
    from benchmark.harness import manifest

    man = manifest.Manifest(root)
    manifest.check(man)
    assert man.config("tiny-other")["arch"] == "otherfamily"
    assert published.check(man, "tiny-other")["widths"][0] == "n_embd"
    assert not os.path.exists(os.path.join(REPO, "benchmark", "archs", "otherfamily.py"))


@pytest.mark.parametrize("cell,trace", [("tiny-agents", 1), ("tiny-moe-arrivals", 0),
                                        ("tiny-other-agents", 1)])
def test_rehearsal_prints_the_contract_s_last_line_and_no_device_metric(root, cell, trace):
    other = cell == "tiny-other-agents"  # of another architecture: its control is read too
    done = _run(["--root", root, "--workload", cell, "--seed", "3000000001",
                 "--seconds", "3", "--trace", str(trace), "--rehearsal-cpu"]
                + (["--control", "1"] if other else []))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    timed = {m["name"] for m in doc["end_to_end"]} | {
        m["name"] for m in doc["per_layer"] if m["source"] != "program_counter"}
    assert not timed & set(line["metrics"])
    if trace:
        # the metric ADDED by new files alone is read by its own reader
        assert line["metrics"]["pool.completed"]["value"] >= line["attempted"] - 8
        assert line["metrics"]["engine.compiles_in_window"]["value"] == 0
        assert 0 < line["metrics"]["batcher.slot_use_pct"]["value"] <= 100
    else:
        assert line["metrics"] == {}
    # every completed request returned exactly max_tokens chunks
    assert any(l.startswith("requests: attempted") and "failed 0" in l for l in lines)
    assert any(l.startswith("correct: served-token logit gap p") for l in lines)
    assert "no time is printed" in done.stdout
    # every number compared stands beside its limit: last in the line, last on stderr
    assert list(line)[-1] == "compared" and line["compared"]["failed"] == {"value": 0, "limit": 0}
    gap = next(v for k, v in line["compared"].items() if k.startswith("gap_p"))
    assert 0 <= gap["value"] <= gap["limit"]
    said = [l for l in done.stderr.splitlines() if l.startswith("compared: ")]
    assert len(said) == len(line["compared"]) and done.stderr.strip().endswith(said[-1])
    if other:
        # the control is the one the configuration's `check` names (the file of
        # its architecture states none); that it FAILS is shown at a size that
        # can carry it (test_bench_control.py): 30 tokens of a 64-wide model cannot
        low = next(l for l in lines if l.startswith("control (the int4 reference"))
        assert float(low.split("gap p100 ")[1].split(" ")[0]) >= 0.0, low


def test_without_a_chip_it_exits_non_zero_and_prints_no_result():
    done = _run(["--workload", "mistral7b-agents8", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert done.returncode not in (0, None)
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_in_a_directory_with_only_the_benchmark_it_exits_non_zero(tmp_path):
    import shutil

    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark", ignore=ignore)
    shutil.copytree(HERE, tmp_path / "tests" / "benchmark", ignore=ignore)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mistral7b-agents8",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode not in (0, None)
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def _run_with_altered_stream(root, monkeypatch, cell, seed, which):
    """Skips the look for a chip (the rehearsal flag) and drives the rest of
    a run in this process, with one token of every greedy stream altered on
    its way out of the server. `which(fields)` is that token's place."""
    before = dict(os.environ)
    from benchmark import run as bench_run
    from benchmark.harness import loadgen, manager

    real = manager.Served.stream

    def broken(self, fields, deadline_s=None):
        n = 0
        for text, done in real(self, fields, deadline_s):
            n += 1
            if n == which(fields) and fields["temperature"] == loadgen.GREEDY_TEMPERATURE:
                text = f"{(int(text, 16) + 1) % 512:04x}"
            yield text, done

    monkeypatch.setattr(manager.Served, "stream", broken)
    try:
        return bench_run.main(["--root", root, "--workload", cell, "--seed", str(seed),
                               "--seconds", "2", "--trace", "0", "--rehearsal-cpu"])
    finally:
        os.environ.clear()
        os.environ.update(before)


def test_a_token_altered_where_it_is_produced_makes_correct_false(root, monkeypatch, capsys):
    rc = _run_with_altered_stream(root, monkeypatch, "tiny-agents", 12, lambda f: 5)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0


def test_the_rule_of_the_configurations_with_a_router_sees_what_the_old_one_let_pass(
        root, monkeypatch, capsys):
    """The mixture-of-experts rule: a high percentile of the gaps where the
    reference's routing is not a near-tie, beside the 75th percentile of all
    gaps (which alone was the rule once). The LAST token of every greedy
    stream is altered — one token in eight at this size, and no later position
    is conditioned on it — so the bulk of the gaps stays exact and the
    75th percentile passes. The percentile over the kept positions does not."""
    rc = _run_with_altered_stream(root, monkeypatch, "tiny-moe-arrivals", 12,
                                  lambda f: f["max_tokens"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    said = next(l for l in out if l.startswith("correct: served-token logit gap p95"))
    assert line["correct"] is False and line["failed"] == 0, said
    bulk = float(said.split("of all gaps ")[1].split(" ")[0])
    assert bulk <= 0.05, said  # the second condition alone would have passed
    gap = float(said.split("gap p95 ")[1].split(" ")[0])
    assert gap > 0.05, said
    print(said)


def test_the_heap_trim_of_set_up_takes_time_and_never_stops_a_run(monkeypatch):
    """`run.trim_heap` hands freed pages back before the window opens (PERF.md
    section 7: left to glibc it stalled every stream for 0.7-0.9 s inside the
    window of a checkout's first run). With another libc it does nothing."""
    import ctypes

    from benchmark import run as bench_run

    assert 0.0 <= bench_run.trim_heap() < 60.0

    def no_glibc(name):
        raise OSError(name)

    monkeypatch.setattr(ctypes, "CDLL", no_glibc)
    assert 0.0 <= bench_run.trim_heap() < 1.0
