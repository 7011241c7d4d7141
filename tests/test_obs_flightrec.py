"""Flight recorder + SLO engine (ISSUE 8).

Four layers under test:
  * e2e: a request served through the LIVE gRPC surface yields one
    complete ordered timeline (route -> admit -> queue -> prefill ->
    decode -> retire) retrievable from ``/debug/trace`` as valid Chrome
    trace-event JSON, with shed and abort paths recorded too;
  * recorder mechanics: ring bound, disable switch, span folding,
    anomaly snapshots (abort / shed spike) with cooldown;
  * SLO window math: attainment / burn rate / breach edges / window
    pruning with injected clocks;
  * the PR 6/7 invariant extended to observability: with the recorder
    ON, compile counters stay flat after warmup and dispatch counts are
    identical to recorder OFF (host-side-only instrumentation).
"""

import json
import threading
import time
import urllib.request

import grpc
import jax
import jax.numpy as jnp
import pytest

from aios_tpu import rpc, services
from aios_tpu.engine import model as M
from aios_tpu.engine.batching import ContinuousBatcher, Request
from aios_tpu.engine.config import TINY_TEST
from aios_tpu.engine.engine import TPUEngine
from aios_tpu.obs import flightrec, slo
from aios_tpu.obs.flightrec import FlightRecorder, Timeline
from aios_tpu.obs.http import start_metrics_server
from aios_tpu.obs.slo import SLOConfig, SLOEngine
from aios_tpu.proto_gen import runtime_pb2
from aios_tpu.runtime.model_manager import ModelManager
from aios_tpu.runtime.service import serve

MODEL = "flight-test"


# ---------------------------------------------------------------------------
# live gRPC surface (the acceptance-criteria path)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def flight_server():
    """Tiny pool behind a live gRPC server + the obs HTTP endpoint."""
    mp = pytest.MonkeyPatch()
    mp.setenv("AIOS_TPU_PAGED_KV", "auto")
    manager = ModelManager(num_slots=2, warm_compile=False)
    manager.load_model(MODEL, "synthetic://tiny-test", context_length=256)
    server, service, port = serve(
        address="127.0.0.1:0", manager=manager, block=False, metrics_port=0
    )
    channel = rpc.insecure_channel(f"127.0.0.1:{port}")
    yield services.AIRuntimeStub(channel), manager, service
    channel.close()
    server.stop(grace=None)
    if service.metrics_server is not None:
        service.metrics_server.shutdown()
    manager.unload_model(MODEL)
    mp.undo()


def _timeline_for(request_id, model=MODEL, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for tl in flightrec.RECORDER.recent(model=model, limit=256):
            if tl.request_id == request_id:
                return tl
        time.sleep(0.02)
    raise AssertionError(f"no timeline for {request_id!r}")


def test_e2e_timeline_through_live_grpc(flight_server):
    """One Infer through the live socket -> one complete ordered
    timeline: route -> admit -> queue -> prefill -> decode -> retire,
    with summary fields filled and the RPC trace id attached."""
    stub, _, _ = flight_server
    resp = stub.Infer(runtime_pb2.InferRequest(
        prompt="flight recorder check", max_tokens=8, temperature=0.0,
        requesting_agent="flight-agent", task_id="flight-e2e-1",
    ))
    assert resp.model_used == MODEL
    tl = _timeline_for("flight-e2e-1")
    assert tl.state == "retired"
    assert tl.tenant == "flight-agent"
    assert tl.trace_id, "timeline must carry the RPC's trace id"
    assert tl.tokens_out > 0
    assert tl.ttft_ms > 0
    assert tl.prompt_tokens > 0
    kinds = [k for _, k, _ in tl.events]
    # ordering: first occurrence of each lifecycle stage is monotonic
    order = ["route", "admit", "queue", "decode", "retire"]
    positions = [kinds.index(k) for k in order]
    assert positions == sorted(positions), (order, kinds)
    # the prefill's event is stamped where its first token was read, which
    # the pipelined loop does after it has issued the decode dispatch behind
    # the prefill: the prefill BEGAN (stamp - dur_ms) before that dispatch
    assert kinds.index("queue") < kinds.index("prefill") < kinds.index("retire")
    t_prefill, _, prefill = tl.events[kinds.index("prefill")]
    assert t_prefill - prefill["dur_ms"] / 1e3 < tl.events[kinds.index("decode")][0]
    assert kinds.count("retire") == 1
    # per-dispatch decode ticks carry occupancy + step count
    decode = [f for _, k, f in tl.events if k == "decode"]
    assert decode and all("n" in f and "occ" in f for f in decode)


def test_spans_fold_into_timeline(flight_server):
    """The previously-dormant tracing exporter feeds finished spans into
    the timeline sharing their trace id (the runtime.decode span at
    minimum — the RPC server span may close after the client returns)."""
    stub, _, _ = flight_server
    stub.Infer(runtime_pb2.InferRequest(
        prompt="span folding", max_tokens=4, temperature=0.0,
        task_id="flight-span-1",
    ))
    tl = _timeline_for("flight-span-1")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        spans = [f for _, k, f in tl.events if k == "span"]
        if any(s.get("name") == "runtime.decode" for s in spans):
            return
        time.sleep(0.02)
    raise AssertionError(
        f"no runtime.decode span folded in: {[e for e in tl.events]}"
    )


def test_debug_routes_serve_trace_and_requests(flight_server):
    """/debug/trace parses as Chrome trace-event JSON containing the
    served request; /debug/requests and /debug/spans answer too."""
    stub, _, service = flight_server
    stub.Infer(runtime_pb2.InferRequest(
        prompt="debug route check", max_tokens=4, temperature=0.0,
        task_id="flight-debug-1",
    ))
    _timeline_for("flight-debug-1")
    base = f"http://127.0.0.1:{service.metrics_port}"

    trace = json.loads(urllib.request.urlopen(
        f"{base}/debug/trace?model={MODEL}", timeout=5).read().decode())
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    for ev in trace["traceEvents"]:
        assert {"ph", "pid", "tid", "name"} <= set(ev)
        if ev["ph"] in ("X", "i"):
            assert "ts" in ev
    names = {e["name"] for e in trace["traceEvents"]}
    assert "request[retired]" in names
    tids = {
        e["tid"] for e in trace["traceEvents"]
        if e.get("cat") == "request"
        and e["args"].get("request_id") == "flight-debug-1"
    }
    assert tids, "served request missing from /debug/trace"

    reqs = json.loads(urllib.request.urlopen(
        f"{base}/debug/requests?model={MODEL}", timeout=5
    ).read().decode())
    assert any(
        r["request_id"] == "flight-debug-1" for r in reqs["requests"]
    )

    spans = json.loads(urllib.request.urlopen(
        f"{base}/debug/spans?name=runtime", timeout=5).read().decode())
    assert spans["spans"], "finished-span ring unreadable"

    slo_view = json.loads(urllib.request.urlopen(
        f"{base}/debug/slo", timeout=5).read().decode())
    assert MODEL in slo_view["models"]
    assert set(slo_view["models"][MODEL]["objectives"]) == set(
        slo.OBJECTIVES
    )

    # an aged-out / unknown snapshot id is a 404, not a 200-with-error
    # body a `curl -f` runbook script would archive as a capture
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(
            f"{base}/debug/trace?snapshot=99999", timeout=5
        )
    assert err.value.code == 404


def test_shed_path_records_timeline(flight_server):
    """A request shed at the front door finishes as state=shed with the
    closed-enum cause + retry-after recorded."""
    _, manager, _ = flight_server
    pool = manager.models[MODEL].pool
    shed_before = flightrec.RECORDER.recent(model=MODEL, limit=256)
    pool._draining = True
    try:
        with pytest.raises(Exception) as err:
            pool.submit(
                Request(prompt_ids=[5, 6, 7], max_tokens=4,
                        temperature=0.0, request_id="flight-shed-1"),
                tenant="shed-tenant",
            )
        assert getattr(err.value, "cause", "") == "draining"
    finally:
        pool._draining = False
    tl = _timeline_for("flight-shed-1")
    assert tl.state == "shed"
    assert tl.shed_cause == "draining"
    assert tl.retry_after_ms > 0
    assert tl.tenant == "shed-tenant"
    kinds = [k for _, k, _ in tl.events]
    assert "shed" in kinds and "retire" not in kinds
    assert len(flightrec.RECORDER.recent(model=MODEL, limit=256)) == \
        len(shed_before) + 1


# ---------------------------------------------------------------------------
# abort path + anomaly snapshots (direct batcher — no pool needed)
# ---------------------------------------------------------------------------


def test_abort_records_closed_cause_and_snapshots():
    """A shutdown mid-request aborts its stream: the timeline finishes
    aborted with the normalized closed-enum cause, and the abort freezes
    an anomaly snapshot holding the evidence."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0),
                           dtype=jnp.float32)
    eng = TPUEngine(TINY_TEST, params, num_slots=2, max_context=128,
                    cache_dtype=jnp.float32)
    b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=2)
    # an abort of this model by an earlier test file of the same worker, less
    # than the cooldown ago, would claim the one snapshot this abort is due
    flightrec.RECORDER._snapshot_at.pop((TINY_TEST.name, "abort"), None)
    try:
        h = b.submit(Request(prompt_ids=[3, 5, 7], max_tokens=512,
                             temperature=0.0, request_id="flight-abort-1"))
    finally:
        b.shutdown()  # terminates the outstanding request
        eng.close()
    h.tokens()  # stream ended
    assert h.aborted
    tl = _timeline_for("flight-abort-1", model=TINY_TEST.name)
    assert tl.state == "aborted"
    assert tl.abort_cause == "model_unloading"
    assert tl.abort_cause in flightrec.ABORT_CAUSES
    # auto-triggered snapshots build on a background thread (the freeze
    # must not stall the scheduler): poll for the snapshot CONTAINING
    # this request — the global 8-deep snapshot store can already hold a
    # stale (tiny-test, abort) snapshot from an earlier suite file, and
    # exiting on the first (model, cause) match would assert against
    # that stale freeze while this abort's build is still running
    deadline = time.monotonic() + 10.0
    snaps = []
    while time.monotonic() < deadline and not snaps:
        snaps = [
            s for s in flightrec.RECORDER.snapshots()
            if s["model"] == TINY_TEST.name and s["cause"] == "abort"
            and any(
                t["request_id"] == "flight-abort-1"
                for t in s["timelines"]
            )
        ]
        time.sleep(0.02)
    assert snaps, (
        "abort must freeze an anomaly snapshot holding this request"
    )


def test_the_wait_for_first_tokens_is_a_phase_a_ticks_host_time_leaves_out():
    """``batcher.first_token`` is in the closed list, a wait on the device:
    it closes into its own seconds and count, and the tick's host time
    (what a stall is judged by) has neither it nor, where the synchronous
    loop reads inside the admission, its share of the phase around it."""
    name = "batcher.first_token"
    assert name in flightrec.PHASES and name in flightrec.DEVICE_WAIT_PHASES
    ph = flightrec.Phases("phases-first-token")
    ph.tick_thread = threading.get_ident()
    with ph.phase("batcher.admit") as admit:
        with ph.phase(name) as inside:  # the synchronous loop
            time.sleep(0.01)
    with ph.phase("batcher.dispatch"):
        pass
    with ph.phase(name) as behind:  # the pipelined loop, of the tick's own
        time.sleep(0.01)
    host, under_dispatch = ph.take_tick()
    assert set(host) == {"batcher.admit"}
    assert host["batcher.admit"] == pytest.approx(admit.dt - inside.dt)
    assert set(under_dispatch) == {"batcher.dispatch"}
    stats = ph.stats()
    assert stats[f"phase_{name}_count"] == 2
    assert stats[f"phase_{name}_seconds"] == pytest.approx(inside.dt + behind.dt)
    assert stats[f"phase_{name}_seconds"] >= 0.02
    assert [n for _, n, _, _ in flightrec.RECORDER.phases("phases-first-token")] == [
        name, "batcher.admit", "batcher.dispatch", name]


def test_shed_spike_triggers_snapshot_with_cooldown():
    rec = FlightRecorder(ring=8, enabled=True)

    def spike_snaps():
        return [s for s in rec.snapshots() if s["cause"] == "shed_spike"]

    for _ in range(flightrec.SHED_SPIKE_N):
        rec.finish_shed(None, "queue_full", 100, model="spike-model")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not spike_snaps():
        time.sleep(0.02)  # spike snapshots build on a background thread
    assert len(spike_snaps()) == 1
    # a second burst inside the cooldown must NOT thrash the store (the
    # cooldown stamp is claimed synchronously, so this is race-free)
    for _ in range(flightrec.SHED_SPIKE_N):
        rec.finish_shed(None, "queue_full", 100, model="spike-model")
    time.sleep(0.1)
    assert len(spike_snaps()) == 1


# ---------------------------------------------------------------------------
# recorder mechanics (private instances)
# ---------------------------------------------------------------------------


def _fake_timeline(rec, model, rid, ttft=10.0, state="retired"):
    tl = rec.begin(model, rid, "t", prompt_tokens=4)
    tl.ttft_ms = ttft
    tl.tokens_out = 8
    rec.finish(tl, state)
    return tl


def test_ring_buffer_bound_respected():
    rec = FlightRecorder(ring=4, enabled=True)
    for i in range(10):
        _fake_timeline(rec, "ring-model", f"r{i}")
    recent = rec.recent(model="ring-model", limit=100)
    assert len(recent) == 4
    assert [t.request_id for t in recent] == ["r6", "r7", "r8", "r9"]


def test_disabled_recorder_is_inert():
    rec = FlightRecorder(ring=4, enabled=False)
    assert rec.begin("m", "r") is None
    rec.finish(None)  # no-ops, no raise
    rec.finish_shed(None, "quota", 100, model="m")
    assert rec.recent() == []


def test_event_cap_counts_drops():
    rec = FlightRecorder(ring=4, enabled=True)
    tl = rec.begin("cap-model", "r")
    for i in range(flightrec.MAX_EVENTS + 50):
        tl.event("decode", n=1)
    assert len(tl.events) == flightrec.MAX_EVENTS
    assert tl.dropped_events == 50
    rec.finish(tl)  # the terminal retire event also lands in the cap
    assert tl.to_dict()["dropped_events"] == 51


def test_chrome_trace_shape_unit():
    rec = FlightRecorder(ring=8, enabled=True)
    tl = rec.begin("trace-model", "req-x", "tenant-z", trace_id="ab" * 16)
    tl.event("route", replica=1, reason="prefix", overlap_rows=128)
    tl.queue_wait_ms = 2.5
    tl.event("prefill", tokens=64, dur_ms=3.0, cached_rows=128)
    tl.event("decode", n=16, occ=3, dur_ms=5.0, gap_ms=0.2)
    tl.ttft_ms, tl.tpot_ms, tl.tokens_out = 12.0, 1.5, 33
    rec.finish(tl)
    rec.model_event("trace-model", "spill", pages=3)
    doc = flightrec.chrome_trace(
        rec.recent(model="trace-model"), rec.model_events("trace-model")
    )
    doc = json.loads(json.dumps(doc))  # must be JSON-serializable
    evs = doc["traceEvents"]
    assert [e for e in evs if e["ph"] == "M"], "metadata events missing"
    xs = [e for e in evs if e["ph"] == "X"]
    assert {"request[retired]", "queue", "prefill", "decode"} <= {
        e["name"] for e in xs
    }
    for e in xs:
        assert e["dur"] > 0 and e["ts"] > 0
    spills = [e for e in evs if e["name"] == "spill"]
    assert spills and spills[0]["tid"] == 0  # model lane rides tid 0
    # a frozen snapshot renders through the SAME path: durations and the
    # engine lane survive the freeze instead of degrading to instants
    snap = rec.snapshot("trace-model", "manual")
    frozen = json.loads(json.dumps(flightrec.snapshot_trace(snap)))
    fx = {e["name"] for e in frozen["traceEvents"] if e["ph"] == "X"}
    assert {"request[retired]", "queue", "prefill", "decode"} <= fx
    assert any(e["name"] == "spill" and e["tid"] == 0
               for e in frozen["traceEvents"])


def test_span_folding_by_trace_id():
    rec = FlightRecorder(ring=8, enabled=True)
    tl = rec.begin("span-model", "r1", trace_id="cd" * 16)
    rec.finish(tl)

    class FakeSpan:
        trace_id = "cd" * 16
        span_id = "ef" * 8
        name = "rpc.server/Infer"
        status = "ok"
        duration_s = 0.012

    rec.export_span(FakeSpan())
    spans = [f for _, k, f in tl.events if k == "span"]
    assert spans and spans[0]["name"] == "rpc.server/Infer"
    rec.export_span(type("S", (FakeSpan,), {"trace_id": "99" * 16})())
    assert len([1 for _, k, _ in tl.events if k == "span"]) == 1


def test_abort_cause_normalization():
    assert flightrec.abort_cause("evicted: KV pool exhausted") == "evicted"
    assert flightrec.abort_cause(
        "prompt exceeds the KV page pool") == "prompt_too_large"
    assert flightrec.abort_cause(
        "scheduler failed: ValueError('x')") == "scheduler_failed"
    assert flightrec.abort_cause("model unloading") == "model_unloading"
    assert flightrec.abort_cause("???") == "other"


# ---------------------------------------------------------------------------
# SLO window math (private engines, injected clocks)
# ---------------------------------------------------------------------------


def _slo(target=0.9, min_samples=5, window=60.0):
    return SLOEngine(SLOConfig(
        ttft_ms=100.0, tpot_ms=10.0, target=target,
        window_secs=window, min_samples=min_samples,
    ))


def test_slo_attainment_and_burn_rate():
    eng = _slo()
    for i in range(8):
        eng.record("slo-a", "t1", ttft_ms=50.0, tpot_ms=5.0, now=100.0)
    for i in range(2):
        eng.record("slo-a", "t2", ttft_ms=500.0, tpot_ms=5.0, now=100.0)
    ev = eng.evaluate("slo-a", now=100.0)
    assert ev["ttft"]["attainment"] == pytest.approx(0.8)
    # burn rate: (1 - 0.8) / (1 - 0.9) = 2x budget
    assert ev["ttft"]["burn_rate"] == pytest.approx(2.0)
    assert ev["ttft"]["breached"] is True
    assert ev["tpot"]["attainment"] == 1.0
    assert ev["tpot"]["breached"] is False
    assert ev["availability"]["attainment"] == 1.0


def test_slo_min_samples_gate_and_breach_edges():
    eng = _slo(min_samples=5)
    b0 = eng.breaches
    for _ in range(4):  # under min_samples: terrible but never breaches
        eng.record("slo-b", ttft_ms=999.0, now=10.0)
    assert eng.evaluate("slo-b", now=10.0)["ttft"]["breached"] is False
    assert eng.breaches == b0
    eng.record("slo-b", ttft_ms=999.0, now=10.0)  # 5th sample: breach edge
    assert eng.evaluate("slo-b", now=10.0)["ttft"]["breached"] is True
    assert eng.breaches == b0 + 1
    # staying breached is NOT a new edge
    eng.record("slo-b", ttft_ms=999.0, now=11.0)
    eng.evaluate("slo-b", now=11.0)
    assert eng.breaches == b0 + 1


def test_slo_window_prunes_old_samples():
    eng = _slo(window=60.0)
    for _ in range(6):
        eng.record("slo-c", ttft_ms=999.0, now=10.0)
    assert eng.evaluate("slo-c", now=20.0)["ttft"]["samples"] == 6
    ev = eng.evaluate("slo-c", now=200.0)  # window slid past everything
    assert ev["ttft"]["samples"] == 0
    assert ev["ttft"]["attainment"] == 1.0  # empty window never degrades


def test_slo_availability_counts_sheds_and_aborts():
    eng = _slo()
    for _ in range(3):
        eng.record("slo-d", ok=True, ttft_ms=10.0, now=5.0)
    eng.record("slo-d", ok=False, now=5.0)  # shed: no ttft sample
    ev = eng.evaluate("slo-d", now=5.0)
    assert ev["availability"]["attainment"] == pytest.approx(0.75)
    assert ev["availability"]["samples"] == 4
    assert ev["ttft"]["samples"] == 3  # latency objectives skip no-token


def test_slo_tenant_breakdown_and_health():
    # real clock here: health() evaluates with time.monotonic(), so the
    # samples must sit inside the real window
    now = time.monotonic()
    eng = _slo(min_samples=2)
    for _ in range(3):
        eng.record("slo-e", "good", ttft_ms=10.0, now=now)
        eng.record("slo-e", "bad", ttft_ms=999.0, now=now)
    tenants = eng.tenants("slo-e", now=now)
    assert tenants["good"]["ttft_attainment"] == 1.0
    assert tenants["bad"]["ttft_attainment"] == 0.0
    h = eng.health()
    assert h["status"] == "degraded"
    assert "slo-e" in h["slo_breached"]
    # annotate_health flips a healthy payload only on breach
    payload = {"status": "ok", "service": "x"}
    out = dict(payload)
    out.update({k: v for k, v in h.items() if k != "slo"})
    assert out["status"] == "degraded"


def test_timeline_observe_maps_states_to_samples():
    eng = _slo()
    tl = Timeline("slo-f", "r1", "tx", "", 4, 0)
    tl.state, tl.ttft_ms, tl.tpot_ms, tl.tokens_out = "retired", 5.0, 1.0, 9
    eng.observe(tl)
    aborted = Timeline("slo-f", "r2", "tx", "", 4, 0)
    aborted.state = "aborted"
    eng.observe(aborted)
    cancelled = Timeline("slo-f", "r3", "tx", "", 4, 0)
    cancelled.state = "cancelled"
    eng.observe(cancelled)  # client's choice: not a plane failure
    ev = eng.evaluate("slo-f", now=time.monotonic())
    assert ev["availability"]["samples"] == 2
    assert ev["availability"]["attainment"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# /healthz status-code satellite
# ---------------------------------------------------------------------------


def test_healthz_returns_503_when_degraded():
    server, port = start_metrics_server(
        port=0, health_fn=lambda: {"status": "degraded", "why": "test"}
    )
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5
            )
        assert err.value.code == 503
        body = json.loads(err.value.read().decode())
        assert body["status"] == "degraded" and body["why"] == "test"
    finally:
        server.shutdown()


def test_healthz_returns_503_when_health_fn_raises():
    def boom():
        raise RuntimeError("probe failure")

    server, port = start_metrics_server(port=0, health_fn=boom)
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5
            )
        assert err.value.code == 503
        assert json.loads(err.value.read().decode())["status"] == "degraded"
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# the extended PR 6/7 invariant: recorder is host-side only
# ---------------------------------------------------------------------------


def test_recorder_no_compile_and_dispatch_identical(monkeypatch):
    """With the recorder ON, compile counters stay FLAT after warmup and
    the token streams are identical to recorder OFF. The dispatched steps
    are equal to within one dispatch a request: the default loop issues
    dispatch N+1 before it consumes N (PR 30), so whether a dispatch of
    nothing follows a request's last token is timing, one ``chunk_steps``
    apart, recorder or no recorder. Single-request waves, so nothing else
    varies (no admission-timing variance in the chunk-size choice)."""
    params = M.init_params(TINY_TEST, jax.random.PRNGKey(0),
                           dtype=jnp.float32)

    def wave(enabled):
        monkeypatch.setattr(flightrec.RECORDER, "enabled", enabled)
        eng = TPUEngine(TINY_TEST, params, num_slots=2, max_context=128,
                        cache_dtype=jnp.float32)
        eng.warmup(step_sizes=(2, 4), prefill_chunk=0)
        compiles_after_warmup = eng.stats()["xla_compiles"]
        b = ContinuousBatcher(eng, chunk_steps=4, admit_chunk_steps=4)
        try:
            outs = []
            for i in range(3):  # sequential: deterministic dispatch count
                outs.append(b.submit(Request(
                    prompt_ids=[3 + i, 17, 91], max_tokens=13,
                    temperature=0.0,
                )).tokens())
            return {
                "outs": outs,
                # decode_steps counts every dispatched step at the engine
                # — for sequential single-request waves deterministic but
                # for the dispatch issued ahead of each request's last.
                # (batcher.decode_dispatches is NOT compared: that
                # counter skips the first dispatch after an idle gap,
                # and whether an idle tick lands between sequential
                # requests is a race on this 2-core box.)
                "decode_steps": eng.stats()["decode_steps"],
                "compile_delta":
                    eng.stats()["xla_compiles"] - compiles_after_warmup,
            }
        finally:
            b.shutdown()
            eng.close()

    on, off = wave(True), wave(False)
    assert on["compile_delta"] == 0, (
        "recorder ON compiled post-warmup — it must be host-side only"
    )
    assert off["compile_delta"] == 0
    assert abs(on["decode_steps"] - off["decode_steps"]) <= 3 * 4  # requests x chunk_steps
    assert on["outs"] == off["outs"]
    # and the ON wave actually recorded: 3 retired timelines with decode
    # ticks, the OFF wave recorded nothing new for those ids
    tls = [
        t for t in flightrec.RECORDER.recent(model=TINY_TEST.name,
                                             limit=256)
        if t.tokens_out == 13
    ]
    assert len(tls) >= 3
