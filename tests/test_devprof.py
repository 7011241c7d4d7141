"""Device-time attribution (obs/devprof.py, ISSUE 14).

Covers: the per-graph cost ledger units (register/note/sample,
roofline resolution, the closed GRAPH_KINDS enum), the extended PR 6/7/8
invariant — devprof ON vs OFF leaves token streams (greedy AND sampled),
dispatch counts, and compile counters identical through the pipelined
batcher — per-request/tenant attribution, the bounded one-at-a-time
``/debug/profile`` capture route, and the scripts/benchdiff.py
regression sentinel (exit non-zero on a seeded 20% per-graph
regression; refuse cross-schema diffs).
"""

import importlib.util
import json
import os
import time
import urllib.error
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from aios_tpu.engine import model as M
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.obs import devprof, flightrec
from aios_tpu.obs import instruments as obs
from aios_tpu.obs.http import start_metrics_server

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# ledger units
# ---------------------------------------------------------------------------


class _FakeCompiled:
    def __init__(self, flops, byt):
        self._ca = {"flops": flops, "bytes accessed": byt}

    def cost_analysis(self):
        return self._ca


def test_ledger_note_sample_and_costs():
    led = devprof.DevprofLedger("m", device_kind="TPU v5 lite", sample_n=4)
    led.register("step", 8, _FakeCompiled(100.0, 1000.0), 0.5)
    # dispatch 1 is due a sample, then every 4th
    assert led.note("step", 8) is True
    for _ in range(3):
        assert led.note("step", 8) is False
    assert led.note("step", 8) is True
    led.sample("step", 8, 0.002)
    led.sample("step", 8, 0.004)
    snap = led.snapshot()["graphs"]["step"]
    assert snap["dispatches"] == 5
    assert snap["compiles"] == 1
    assert snap["est_flops"] == pytest.approx(500.0)
    assert snap["est_bytes"] == pytest.approx(5000.0)
    assert snap["samples"] == 2
    assert snap["device_seconds_per_dispatch"] == pytest.approx(
        0.003, rel=1e-3
    )
    assert snap["device_seconds"] == pytest.approx(0.015, rel=1e-3)
    # roofline: sampled flops (2 x 100) over sampled seconds over peak
    assert snap["mfu"] == pytest.approx(
        200.0 / 0.006 / 197e12, rel=1e-2
    )
    assert snap["hbm_bw_util"] == pytest.approx(
        2000.0 / 0.006 / 819e9, rel=1e-2
    )
    assert led.mean_s("step") == pytest.approx(0.003, rel=1e-3)
    assert led.mean_s("prefill") is None
    # the last sample is poppable exactly once
    assert led.take_last_sample() == ("step", 0.004)
    assert led.take_last_sample() is None


def test_ledger_rejects_unknown_graph_kind():
    led = devprof.DevprofLedger("m", device_kind="", sample_n=1)
    with pytest.raises(ValueError, match="GRAPH_KINDS"):
        led.register("warp_drive", 1, None, 0.0)


def test_unknown_device_kind_omits_utilization():
    led = devprof.DevprofLedger("m", device_kind="cpu", sample_n=1)
    assert led.peaks is None
    led.register("step", 1, _FakeCompiled(10.0, 10.0), 0.1)
    led.note("step", 1)
    led.sample("step", 1, 0.001)
    snap = led.snapshot()["graphs"]["step"]
    # raw seconds kept, utilization gauges omitted (no invented peaks)
    assert "device_seconds" in snap
    assert "mfu" not in snap and "hbm_bw_util" not in snap
    # known kinds resolve by exact device_kind; nothing else does
    assert devprof.resolve_peaks("TPU v4") == (275e12, 1228e9)
    assert devprof.resolve_peaks("TPU v5 litepod") is None
    assert devprof.resolve_peaks("") is None


# ---------------------------------------------------------------------------
# the PR 6/7/8 invariant, extended: devprof is metadata + sampling only
# ---------------------------------------------------------------------------


def _wave(monkeypatch, enabled):
    """One engine+pipelined-batcher lifecycle: sequential greedy AND
    sampled single-request waves (deterministic dispatch counts), with
    devprof armed or not at construction."""
    if enabled:
        monkeypatch.setenv("AIOS_TPU_DEVPROF", "1")
        monkeypatch.setenv("AIOS_TPU_DEVPROF_SAMPLE", "2")
    else:
        monkeypatch.delenv("AIOS_TPU_DEVPROF", raising=False)
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0),
                           dtype=jnp.float32)
    eng = TPUEngine(TINY_TEST, params, num_slots=2, max_context=128,
                    cache_dtype=jnp.float32)
    eng.warmup(step_sizes=(2, 4), prefill_chunk=0)
    compiles_after_warmup = eng.stats()["xla_compiles"]
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=4,
                          pipeline=True)
    try:
        outs = []
        for i in range(2):  # greedy
            outs.append(b.submit(Request(
                prompt_ids=[3 + i, 17, 91], max_tokens=13,
                temperature=0.0,
            )).tokens())
        for i in range(2):  # sampled (same engine seed both arms)
            outs.append(b.submit(Request(
                prompt_ids=[7 + i, 23, 55], max_tokens=11,
                temperature=0.7, top_p=0.9,
            )).tokens())
        # the loop issued one more dispatch before the last request
        # retired: let it land, or decode_steps is read before or after
        # the worker counts it
        deadline = time.monotonic() + 30
        while b._pending is not None and time.monotonic() < deadline:
            time.sleep(0.005)
        return {
            "outs": outs,
            "decode_steps": eng.stats()["decode_steps"],
            "compile_delta":
                eng.stats()["xla_compiles"] - compiles_after_warmup,
            "snapshot": eng.devprof_snapshot(),
        }
    finally:
        b.shutdown()
        eng.close()


def test_devprof_on_off_streams_and_compiles_identical(monkeypatch):
    tenant_before = obs.DEVPROF_TENANT_SECONDS.labels(
        tenant="anonymous"
    ).value
    on = _wave(monkeypatch, True)
    off = _wave(monkeypatch, False)
    assert on["compile_delta"] == 0, (
        "devprof ON compiled post-warmup — registration must be "
        "metadata-only"
    )
    assert off["compile_delta"] == 0
    assert on["decode_steps"] == off["decode_steps"]
    assert on["outs"] == off["outs"]
    # the ON arm actually measured: step+prefill dispatches counted,
    # samples landed, and the static cost estimates are populated
    graphs = on["snapshot"]["graphs"]
    assert off["snapshot"] is None
    assert graphs["step"]["dispatches"] > 0
    assert graphs["prefill"]["dispatches"] == 4
    assert graphs["step"]["samples"] > 0
    assert graphs["step"]["est_flops"] > 0
    # per-request attribution reached the timelines and the tenant
    # counter was billed at retirement
    tls = [
        t for t in flightrec.RECORDER.recent(model=TINY_TEST.name,
                                             limit=256)
        if t.tokens_out in (13, 11) and t.device_us > 0
    ]
    assert len(tls) >= 4
    ev_dev = [
        e for t in tls for e in t.to_dict()["events"]
        if "dev_us" in e and e["dev_us"] > 0
    ]
    assert ev_dev, "no dispatch event carried a sampled dev_us join"
    assert obs.DEVPROF_TENANT_SECONDS.labels(
        tenant="anonymous"
    ).value > tenant_before


@pytest.mark.slow
def test_devprof_live_grpc_streams_and_compiles_identical():
    """The acceptance-criteria path: with devprof enabled on the LIVE
    gRPC surface, response streams and engine compile counters are
    byte-identical to disabled, and the ON run's ledger + tenant
    billing actually populated."""
    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import runtime_pb2
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve

    def run(enabled):
        mp = pytest.MonkeyPatch()
        mp.setenv("AIOS_TPU_PAGED_KV", "auto")
        if enabled:
            mp.setenv("AIOS_TPU_DEVPROF", "1")
            mp.setenv("AIOS_TPU_DEVPROF_SAMPLE", "2")
        else:
            mp.delenv("AIOS_TPU_DEVPROF", raising=False)
        manager = ModelManager(num_slots=2, warm_compile=False)
        manager.load_model("devprof-live", "synthetic://tiny-test",
                           context_length=256)
        server, service, port = serve(
            address="127.0.0.1:0", manager=manager, block=False,
            metrics_port=0,
        )
        channel = rpc.insecure_channel(f"127.0.0.1:{port}")
        stub = services.AIRuntimeStub(channel)
        try:
            texts = []
            for i in range(3):
                resp = stub.Infer(runtime_pb2.InferRequest(
                    prompt=f"devprof live check {i}", max_tokens=8,
                    temperature=0.0, requesting_agent="devprof-agent",
                    task_id=f"devprof-live-{int(enabled)}-{i}",
                ))
                texts.append(resp.text)
            eng = manager.models["devprof-live"].pool.replicas[0].engine
            return {
                "texts": texts,
                "compiles": eng.stats()["xla_compiles"],
                "decode_steps": eng.stats()["decode_steps"],
                "snapshot": eng.devprof_snapshot(),
            }
        finally:
            channel.close()
            server.stop(grace=None)
            if service.metrics_server is not None:
                service.metrics_server.shutdown()
            manager.unload_model("devprof-live")
            mp.undo()

    billed_before = obs.DEVPROF_TENANT_SECONDS.labels(
        tenant="devprof-agent"
    ).value
    on = run(True)
    off = run(False)
    assert on["texts"] == off["texts"]
    assert on["compiles"] == off["compiles"]
    assert on["decode_steps"] == off["decode_steps"]
    assert off["snapshot"] is None
    assert on["snapshot"]["graphs"]["step"]["dispatches"] > 0
    assert obs.DEVPROF_TENANT_SECONDS.labels(
        tenant="devprof-agent"
    ).value > billed_before


# ---------------------------------------------------------------------------
# /debug/profile: bounded, one-at-a-time, disabled without a dump dir
# ---------------------------------------------------------------------------


def _drain_capture(deadline_s: float = 120.0) -> None:
    deadline = time.monotonic() + deadline_s
    while devprof.capture_status()["busy"]:
        assert time.monotonic() < deadline, "capture never finished"
        time.sleep(0.05)


def test_profile_capture_route(tmp_path, monkeypatch):
    """Route semantics (403 disabled / 200 start / 409 busy / status
    clears) with the profiler itself mocked — the real jax.profiler
    capture rides the slow tier below (its first use imports the
    TF-profiler machinery, ~seconds)."""
    import contextlib

    import jax as jax_mod

    started = []

    @contextlib.contextmanager
    def fake_trace(path):
        os.makedirs(path, exist_ok=True)
        started.append(path)
        yield

    monkeypatch.setattr(jax_mod.profiler, "trace", fake_trace)
    server, port = start_metrics_server(port=0)
    url = f"http://127.0.0.1:{port}/debug/profile"
    try:
        monkeypatch.delenv("AIOS_TPU_DEVPROF_DUMP_DIR", raising=False)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{url}?secs=0.2", timeout=5)
        assert err.value.code == 403

        monkeypatch.setenv("AIOS_TPU_DEVPROF_DUMP_DIR", str(tmp_path))
        body = json.loads(urllib.request.urlopen(
            f"{url}?secs=2.0", timeout=5
        ).read().decode())
        assert body["profiling"] and body["path"].startswith(str(tmp_path))
        assert body["secs"] == pytest.approx(2.0)
        # one at a time: a second request during the window is a 409
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{url}?secs=0.2", timeout=5)
        assert err.value.code == 409
        _drain_capture()
        assert started and os.path.isdir(body["path"])
        # /debug/devprof serves the ledgers + capture state
        dbg = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/devprof", timeout=5
        ).read().decode())
        assert dbg["capture"]["busy"] is False
    finally:
        server.shutdown()


def test_capture_secs_hard_cap(tmp_path, monkeypatch):
    import contextlib

    import jax as jax_mod

    @contextlib.contextmanager
    def fake_trace(path):
        os.makedirs(path, exist_ok=True)
        yield

    monkeypatch.setattr(jax_mod.profiler, "trace", fake_trace)
    monkeypatch.setenv("AIOS_TPU_DEVPROF_DUMP_DIR", str(tmp_path))
    monkeypatch.setattr(devprof, "CAPTURE_MAX_SECS", 0.2)
    _drain_capture()
    info = devprof.start_capture(9999.0)
    assert info["secs"] == pytest.approx(0.2)
    _drain_capture()


@pytest.mark.slow
def test_profile_capture_real_jax_profiler(tmp_path, monkeypatch):
    """One REAL jax.profiler capture end to end: the trace directory
    lands under the dump dir with actual profiler output."""
    monkeypatch.setenv("AIOS_TPU_DEVPROF_DUMP_DIR", str(tmp_path))
    _drain_capture()
    info = devprof.start_capture(0.3)
    _drain_capture()
    assert os.path.isdir(info["path"])
    assert os.listdir(info["path"]), "profiler wrote nothing"


# ---------------------------------------------------------------------------
# scripts/benchdiff.py: the per-graph regression sentinel
# ---------------------------------------------------------------------------


def _benchdiff():
    spec = importlib.util.spec_from_file_location(
        "benchdiff", ROOT / "scripts" / "benchdiff.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ledger_line(step_s=0.002, step_disp=18, schema=1):
    return {
        "schema_version": schema,
        "metric": "devprof per-graph device-time ledger",
        "devprof": {
            "model": "m", "device_kind": "cpu", "sample_every": 8,
            "graphs": {
                "step": {
                    "dispatches": step_disp, "samples": 3,
                    "device_seconds_per_dispatch": step_s,
                    "device_seconds": step_s * step_disp,
                },
                "prefill": {
                    "dispatches": 6, "samples": 1,
                    "device_seconds_per_dispatch": 0.03,
                    "device_seconds": 0.18,
                },
            },
        },
    }


def _write(tmp_path, name, line):
    p = tmp_path / name
    p.write_text(json.dumps(line) + "\n")
    return str(p)


def test_benchdiff_clean_and_seeded_regression(tmp_path, capsys):
    bd = _benchdiff()
    base = _write(tmp_path, "base.json", _ledger_line())
    same = _write(tmp_path, "same.json", _ledger_line())
    assert bd.main([base, same]) == 0
    # a seeded 20% per-graph device-time regression exits non-zero at
    # the default threshold (the ISSUE 14 acceptance criterion)
    slow = _write(tmp_path, "slow.json", _ledger_line(step_s=0.0024))
    assert bd.main([base, slow]) == 1
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["verdict"] == "regression"
    assert verdict["regressions"][0]["graph"] == "step"
    # dispatch-count inflation on the fixed workload is a regression too
    more = _write(tmp_path, "more.json", _ledger_line(step_disp=24))
    assert bd.main([base, more]) == 1


def test_benchdiff_refuses_cross_schema(tmp_path, capsys):
    bd = _benchdiff()
    base = _write(tmp_path, "base.json", _ledger_line(schema=0))
    new = _write(tmp_path, "new.json", _ledger_line(schema=1))
    assert bd.main([base, new]) == 2
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["verdict"] == "schema_mismatch"
    # and unusable inputs (no ledger line) are a 2 as well, not a pass
    empty = _write(tmp_path, "empty.json", {"metric": "x"})
    assert bd.main([base, empty]) == 2


def test_bench_emit_stamps_schema_version(capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_emit_probe", ROOT / "bench.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.emit({"metric": "probe", "value": 1.0})
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["schema_version"] == mod.BENCH_SCHEMA_VERSION
    assert "platform" in line and "device_kind" in line
