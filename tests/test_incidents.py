"""Incident-bundle units (aios_tpu/obs/incidents.py, ISSUE 20).

Deterministic tier: arming matrix, the notify funnel's cooldown/
suppression accounting on an injected clock, bundle sections (armed and
unarmed tsdb), the trigger hooks (flightrec snapshot, breaker open,
fired fault), the bounded store + HTTP surface + disk dump, and THE
acceptance determinism check: a seeded ``pool.scheduler_crash`` wave run
twice produces identical bundles modulo timestamps.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from aios_tpu.obs import flightrec, incidents, tsdb
from aios_tpu.obs.incidents import (
    IncidentConfig,
    IncidentStore,
    MAX_INCIDENTS,
    TRIGGER_CAUSES,
)


def _store(clock=None, **kw) -> IncidentStore:
    cfg = IncidentConfig()
    cfg.window_secs = kw.pop("window_secs", 0.0)
    cfg.cooldown_secs = kw.pop("cooldown_secs", 0.0)
    for k, v in kw.items():
        setattr(cfg, k, v)
    return IncidentStore(cfg, clock=clock or time.time)


def _wait_for(store, n, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        incs = store.incidents()
        if len(incs) >= n:
            return incs
        time.sleep(0.02)
    raise AssertionError(
        f"only {len(store.incidents())} of {n} bundles built in time"
    )


# -- config / arming --------------------------------------------------------


def test_arming_matrix(monkeypatch):
    monkeypatch.delenv("AIOS_TPU_INCIDENTS", raising=False)
    monkeypatch.delenv("AIOS_TPU_TSDB", raising=False)
    assert not IncidentConfig().enabled
    monkeypatch.setenv("AIOS_TPU_TSDB", "1")  # rides the tsdb arming
    assert IncidentConfig().enabled
    monkeypatch.setenv("AIOS_TPU_INCIDENTS", "0")  # explicit off wins
    assert not IncidentConfig().enabled
    monkeypatch.delenv("AIOS_TPU_TSDB", raising=False)
    monkeypatch.setenv("AIOS_TPU_INCIDENTS", "1")  # explicit on alone
    assert IncidentConfig().enabled
    monkeypatch.setenv("AIOS_TPU_INCIDENT_WINDOW_SECS", "5")
    monkeypatch.setenv("AIOS_TPU_INCIDENT_COOLDOWN_SECS", "7")
    cfg = IncidentConfig()
    assert (cfg.window_secs, cfg.cooldown_secs) == (5.0, 7.0)


def test_maybe_start_noop_when_unarmed(monkeypatch):
    monkeypatch.delenv("AIOS_TPU_INCIDENTS", raising=False)
    monkeypatch.delenv("AIOS_TPU_TSDB", raising=False)
    prev = incidents.install(None)
    try:
        assert incidents.maybe_start() is None
        assert not incidents.enabled()
        incidents.notify("m", "manual")  # the funnel is a pure no-op
        assert incidents.STORE is None
    finally:
        incidents.install(prev)


# -- the notify funnel ------------------------------------------------------


def test_cooldown_suppresses_and_counts():
    now = [0.0]
    store = _store(clock=lambda: now[0], cooldown_secs=30.0)
    assert store.notify("m", "manual", sync=True) is not None
    now[0] += 10.0
    assert store.notify("m", "manual", sync=True) is None  # suppressed
    # a different (model, cause) pair has its own stamp
    assert store.notify("m2", "manual", sync=True) is not None
    now[0] += 25.0  # 35s since the first -> cooldown elapsed
    assert store.notify("m", "manual", sync=True) is not None
    ids = [b["id"] for b in store.incidents()]
    assert ids == [1, 2, 3]


def test_unknown_cause_normalizes_to_manual():
    store = _store()
    b = store.notify("m", "definitely_not_a_cause", sync=True)
    assert b["cause"] == "manual"
    assert set(TRIGGER_CAUSES) == {
        "abort", "autoscale", "breaker_open", "crash_respawn", "fault",
        "manual", "no_progress", "shed_spike", "slo_breach",
    }


def test_bundle_sections_unarmed_tsdb():
    prev = tsdb.install(None)
    try:
        store = _store()
        b = store.notify("m", "manual", sync=True, note="x")
        assert b["tsdb"] == {"armed": False, "series": [], "truncated": 0}
        assert b["fields"] == {"note": "x"}
        assert b["window"]["start"] <= b["at"] <= b["window"]["end"]
        assert isinstance(b["faults"], list)
        assert isinstance(b["devprof"], dict)
        assert isinstance(b["lock_trips"], list)
        assert b["flightrec"]["snapshot_id"] is None
    finally:
        tsdb.install(prev)


def test_bundle_freezes_tsdb_window_and_marks_model_lane():
    from aios_tpu.obs.metrics import Gauge, MetricsRegistry
    from aios_tpu.obs.tsdb import Tsdb, TsdbConfig

    reg = MetricsRegistry()
    g = Gauge("aios_tpu_t_inc_ratio", "h", registry=reg)
    g.set(1.0)
    ring = Tsdb(cfg=TsdbConfig(), registry=reg)
    ring.sample_once()
    prev = tsdb.install(ring)
    try:
        store = _store(window_secs=60.0)
        b = store.notify("inc-model", "manual", sync=True)
        assert b["tsdb"]["armed"] is True
        assert any(s["name"] == "aios_tpu_t_inc_ratio"
                   for s in b["tsdb"]["series"])
        # the bundle itself lands on the model lane as an event the
        # closed EVENT_KINDS enum covers
        lane = flightrec.RECORDER.model_events("inc-model")
        assert any(
            k == "incident" and f.get("incident_id") == b["id"]
            for _, _, k, f in lane
        )
    finally:
        tsdb.install(prev)


def test_store_is_bounded():
    now = [0.0]
    store = _store(clock=lambda: now[0])
    for i in range(MAX_INCIDENTS + 5):
        now[0] += 1.0
        store.notify(f"m{i}", "manual", sync=True)
    incs = store.incidents()
    assert len(incs) == MAX_INCIDENTS
    assert incs[-1]["id"] == MAX_INCIDENTS + 5


def test_dump_dir_writes_bundle_json(tmp_path):
    store = _store(dump_dir=str(tmp_path))
    b = store.notify("m", "manual", sync=True)
    path = tmp_path / f"incident-m-manual-{b['id']}.json"
    assert path.exists()
    assert json.loads(path.read_text())["cause"] == "manual"


# -- trigger hooks ----------------------------------------------------------


def test_flightrec_snapshot_triggers_incident():
    store = _store()
    prev = incidents.install(store)
    try:
        snap = flightrec.RECORDER.snapshot("snaptrig-model", "abort")
        assert snap is not None
        incs = _wait_for(store, 1)
        assert incs[0]["cause"] == "abort"
        assert incs[0]["model"] == "snaptrig-model"
        # the matching snapshot is folded into the bundle
        assert incs[0]["flightrec"]["snapshot_id"] == snap["id"]
    finally:
        incidents.install(prev)


def test_breaker_open_edge_triggers_incident():
    from aios_tpu.fleet import breaker

    store = _store()
    prev = incidents.install(store)
    try:
        board = breaker.BreakerBoard(clock=lambda: 0.0)
        for _ in range(4):  # past the default threshold -> open edge
            board.record_failure("sickhost", "unavailable")
        incs = _wait_for(store, 1)
        assert incs[0]["cause"] == "breaker_open"
        assert incs[0]["model"] == "fleet"
        assert incs[0]["fields"]["peer"] == "sickhost"
    finally:
        incidents.install(prev)


def test_fired_fault_triggers_incident():
    from aios_tpu import faults

    store = _store()
    prev = incidents.install(store)
    faults.activate("seed=1;pool.scheduler_crash=nth:1")
    try:
        act = faults.point("pool.scheduler_crash", "faulted-model")
        assert act is not None
        incs = _wait_for(store, 1)
        assert incs[0]["cause"] == "fault"
        assert incs[0]["model"] == "faulted-model"
        assert incs[0]["fields"]["point"] == "pool.scheduler_crash"
    finally:
        faults.deactivate()
        incidents.install(prev)


# -- HTTP surface -----------------------------------------------------------


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as r:
        return r.status, r.read().decode()


def test_debug_incidents_http():
    from aios_tpu.obs.http import start_metrics_server

    store = _store()
    store.notify("m", "manual", sync=True, note="hi")
    prev = incidents.install(store)
    server, port = start_metrics_server(port=0)
    try:
        status, body = _get(port, "/debug/incidents")
        data = json.loads(body)
        assert status == 200 and len(data["incidents"]) == 1
        meta = data["incidents"][0]
        assert meta["cause"] == "manual" and meta["fields"] == {"note": "hi"}
        assert "tsdb" not in meta  # the list is metadata, not bundles
        status, body = _get(port, f"/debug/incidents?id={meta['id']}")
        assert status == 200 and "tsdb" in json.loads(body)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/debug/incidents?id=999")
        assert ei.value.code == 404
    finally:
        incidents.install(prev)
        server.shutdown()


def test_debug_incidents_404_when_unarmed():
    from aios_tpu.obs.http import start_metrics_server

    prev = incidents.install(None)
    server, port = start_metrics_server(port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/debug/incidents")
        assert ei.value.code == 404
    finally:
        incidents.install(prev)
        server.shutdown()


# -- THE determinism acceptance (engine tier) -------------------------------


MODEL = "incident-crash"


@pytest.fixture(scope="module")
def crash_pool():
    import jax
    import jax.numpy as jnp

    from aios_tpu.engine import model as model_mod
    from aios_tpu.engine.batching import ContinuousBatcher
    from aios_tpu.engine.config import TINY_TEST
    from aios_tpu.engine.engine import TPUEngine
    from aios_tpu.serving import ReplicaPool, ServingConfig

    cfg = TINY_TEST.scaled(name=MODEL, max_context=256)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    engines = [
        TPUEngine(cfg, params, num_slots=2, max_context=256,
                  cache_dtype=jnp.float32)
        for _ in range(2)
    ]
    pool = ReplicaPool(
        MODEL, engines,
        lambda e: ContinuousBatcher(e, chunk_steps=2, admit_chunk_steps=2),
        ServingConfig(replicas=2, failover_retries=2),
    )
    yield pool
    pool.shutdown()


def _crash_wave(pool, tag, n=4, max_tokens=24):
    from aios_tpu.engine.batching import Request

    handles = [
        pool.submit(
            Request(prompt_ids=[3 + i, 7, 11], max_tokens=max_tokens,
                    temperature=0.0, request_id=f"{tag}-{i}"),
            tenant="chaos-tenant",
        )
        for i in range(n)
    ]
    streams = {}
    threads = []
    for i, h in enumerate(handles):
        t = threading.Thread(
            target=lambda i=i, h=h: streams.__setitem__(i, h.tokens()),
            daemon=True,
        )
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=120)
    return [streams.get(i) for i in range(n)]


def _normalize(bundle):
    """A bundle modulo timestamps, ids, and the cross-layer state that
    legitimately accumulates across runs (devprof counters, lane
    history): the trigger identity, its fields, and the fired-fault
    evidence must reproduce exactly."""
    return {
        "model": bundle["model"],
        "cause": bundle["cause"],
        "fields": bundle["fields"],
        "faults": [
            {k: e.get(k) for k in ("point", "mode", "hit", "model")}
            for e in bundle["faults"]
        ],
    }


def test_seeded_crash_incident_bundles_identical_across_runs(crash_pool):
    """ISSUE 20 acceptance: the same seeded ``pool.scheduler_crash``
    wave run twice produces incident bundles identical modulo
    timestamps — the chaos pipeline's replayable-verdict rule extended
    to the incident layer."""
    from aios_tpu import faults

    def run(tag):
        store = _store()
        prev = incidents.install(store)
        faults.activate("seed=2;pool.scheduler_crash=nth:6")
        try:
            streams = _crash_wave(crash_pool, tag)
            assert all(s for s in streams), "a request died in the wave"
            incs = _wait_for(store, 1)
        finally:
            faults.deactivate()
            incidents.install(prev)
        fault_incs = [b for b in incs if b["cause"] == "fault"]
        assert fault_incs, "the fired fault never produced an incident"
        return [_normalize(b) for b in fault_incs]

    first = run("inc-a")
    second = run("inc-b")
    assert first == second
    assert first[0]["model"] == MODEL
    assert first[0]["fields"]["point"] == "pool.scheduler_crash"
    assert first[0]["faults"][-1]["hit"] == 6
