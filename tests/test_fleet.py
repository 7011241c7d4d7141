"""Fleet telemetry plane units (aios_tpu/obs/fleet.py, ISSUE 16).

Fast CPU tier: config/env parsing, the membership state machine on an
injected clock, exposition relabel/merge, trace stitching, SLO rollups,
the HTTP surface over a real ephemeral-port server, the multihost env
contract, and the multi-target storm routing/verdict helpers. The slow
tier runs scripts/fleet_smoke.py — two REAL runtime processes
federating, stitching one trace, and one dying deterministically."""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from aios_tpu.obs import fleet
from aios_tpu.obs.fleet import (
    FleetConfig,
    FleetRegistry,
    MEMBER_STATES,
    merge_expositions,
    relabel_exposition,
    stitch_chrome_traces,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- config / identity ------------------------------------------------------


def test_fleet_config_defaults_inactive(monkeypatch):
    for var in ("AIOS_TPU_FLEET", "AIOS_TPU_FLEET_PEERS"):
        monkeypatch.delenv(var, raising=False)
    cfg = FleetConfig()
    assert not cfg.active()
    assert cfg.interval_secs == 2.0
    assert cfg.suspect_secs == 6.0
    assert cfg.dead_secs == 15.0
    assert cfg.seed_peers() == ()


def test_fleet_config_env_parsing(monkeypatch):
    monkeypatch.setenv("AIOS_TPU_FLEET", "1")
    monkeypatch.setenv("AIOS_TPU_FLEET_PEERS", "10.0.0.1:9100, 10.0.0.2:9100")
    monkeypatch.setenv("AIOS_TPU_FLEET_INTERVAL_SECS", "0.5")
    monkeypatch.setenv("AIOS_TPU_FLEET_SUSPECT_SECS", "2")
    monkeypatch.setenv("AIOS_TPU_FLEET_DEAD_SECS", "4")
    cfg = FleetConfig()
    assert cfg.active()
    assert cfg.peers == ("10.0.0.1:9100", "10.0.0.2:9100")
    assert cfg.seed_peers() == cfg.peers
    assert (cfg.interval_secs, cfg.suspect_secs, cfg.dead_secs) == (
        0.5, 2.0, 4.0)


def test_fleet_peers_alone_activate(monkeypatch):
    monkeypatch.delenv("AIOS_TPU_FLEET", raising=False)
    monkeypatch.setenv("AIOS_TPU_FLEET_PEERS", "10.0.0.9:9100")
    assert FleetConfig().active()


def test_fleet_seed_peers_fall_back_to_coordinator(monkeypatch):
    """With no explicit peer list, the multihost coordinator host on
    AIOS_TPU_FLEET_SEED_PORT seeds membership — one seed is enough,
    gossip converges the rest."""
    monkeypatch.delenv("AIOS_TPU_FLEET_PEERS", raising=False)
    monkeypatch.setenv("AIOS_TPU_COORDINATOR", "10.1.2.3:8476")
    monkeypatch.setenv("AIOS_TPU_NUM_PROCESSES", "2")
    monkeypatch.setenv("AIOS_TPU_PROCESS_ID", "1")
    monkeypatch.setenv("AIOS_TPU_FLEET_SEED_PORT", "9200")
    assert FleetConfig().seed_peers() == ("10.1.2.3:9200",)


def test_process_identity_env_overrides(monkeypatch):
    monkeypatch.setenv("AIOS_TPU_FLEET_HOST", "hostX")
    monkeypatch.setenv("AIOS_TPU_FLEET_ROLE", "orchestrator")
    monkeypatch.setenv("AIOS_TPU_COORDINATOR", "10.1.2.3:8476")
    monkeypatch.setenv("AIOS_TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("AIOS_TPU_PROCESS_ID", "3")
    ident = fleet.process_identity("runtime")
    assert ident["host"] == "hostX"
    assert ident["role"] == "orchestrator"  # env wins over the service name
    assert ident["rank"] == "3"
    import aios_tpu

    assert ident["version"] == aios_tpu.__version__


def test_process_identity_defaults_are_unique_per_process(monkeypatch):
    for var in ("AIOS_TPU_FLEET_HOST", "AIOS_TPU_FLEET_ROLE",
                "AIOS_TPU_COORDINATOR", "AIOS_TPU_MULTIHOST"):
        monkeypatch.delenv(var, raising=False)
    ident = fleet.process_identity("runtime")
    assert ident["host"].endswith(f":{os.getpid()}")
    assert ident["role"] == "runtime"
    assert ident["rank"] == "0"


def test_stamp_process_info_sets_identity_gauge(monkeypatch):
    from aios_tpu.obs import instruments

    monkeypatch.setenv("AIOS_TPU_FLEET_HOST", "stamp-test")
    ident = fleet.stamp_process_info("runtime")
    assert instruments.PROCESS_INFO.labels(**ident).value == 1.0


# -- the membership state machine (injected clock) --------------------------


def _registry(now, **cfg_overrides):
    cfg = FleetConfig()
    cfg.suspect_secs = cfg_overrides.get("suspect_secs", 5.0)
    cfg.dead_secs = cfg_overrides.get("dead_secs", 10.0)
    cfg.peers = ()
    return FleetRegistry(
        {"host": "hostA", "role": "runtime", "rank": "0", "version": "t"},
        "127.0.0.1:9100", cfg=cfg, clock=lambda: now[0],
    )


def _desc(host, addr="127.0.0.1:9101", **extra):
    return {"host": host, "role": "runtime", "rank": "1", "version": "t",
            "metrics_addr": addr, **extra}


def test_member_lifecycle_up_suspect_dead_and_recovery():
    now = [100.0]
    reg = _registry(now)
    reg.receive(_desc("hostB"))
    states = {m["host"]: m["state"] for m in reg.members()}
    assert states == {"hostA": "up", "hostB": "up"}

    # inside the suspect window nothing moves
    assert reg.tick(now=104.0) == []
    # past it: exactly one up -> suspect edge
    assert reg.tick(now=106.0) == [("hostB", "runtime", "up", "suspect")]
    # a detector tick never un-suspects (recovery needs fresh evidence)
    assert reg.tick(now=106.5) == []
    # past the dead window: suspect -> dead
    assert reg.tick(now=111.0) == [("hostB", "runtime", "suspect", "dead")]
    assert reg.tick(now=200.0) == []  # dead is terminal for the detector

    # a fresh announce resurrects: dead -> up (restarts are the common case)
    now[0] = 200.0
    reg.receive(_desc("hostB"))
    states = {m["host"]: m["state"] for m in reg.members()}
    assert states["hostB"] == "up"

    edges = [(e["host"], e["from"], e["to"]) for e in reg.journal()]
    assert edges == [
        ("hostA", "", "up"),
        ("hostB", "", "up"),
        ("hostB", "up", "suspect"),
        ("hostB", "suspect", "dead"),
        ("hostB", "dead", "up"),
    ]


def test_detector_never_ages_self():
    now = [0.0]
    reg = _registry(now)
    assert reg.tick(now=1e6) == []
    assert reg.members()[0]["state"] == "up"


def test_journal_is_bounded():
    now = [0.0]
    reg = _registry(now)
    for i in range(300):
        now[0] = i * 100.0
        reg.receive(_desc("hostB"))  # dead -> up
        reg.tick(now=now[0] + 50.0)  # up -> suspect -> (next round) dead
        reg.tick(now=now[0] + 99.0)
    assert len(reg.journal()) <= fleet._MAX_JOURNAL


def test_receive_returns_self_and_gossips_peers():
    now = [0.0]
    reg = _registry(now)
    reply = reg.receive(_desc("hostB", addr="127.0.0.1:9101"))
    assert reply["member"]["host"] == "hostA"
    assert reply["member"]["metrics_addr"] == "127.0.0.1:9100"
    assert "pools" in reply["member"] and "slo" in reply["member"]
    # hostB's endpoint is now gossiped to the NEXT announcer
    reply2 = reg.receive(_desc("hostC", addr="127.0.0.1:9102"))
    assert "127.0.0.1:9101" in reply2["peers"]


def test_health_summary_rolls_up_burn_and_attainment(monkeypatch):
    # self's descriptor reads the LIVE slo tracker; earlier suite tests may
    # have left burn there, so pin it empty to keep the rollup hermetic
    monkeypatch.setattr(fleet, "_self_slo", lambda: {})
    now = [0.0]
    reg = _registry(now)
    reg.receive(_desc("hostB", slo={
        "worst_burn": 3.5,
        "attainment": {"m": {"ttft": 0.91, "tpot": 0.99}},
    }))
    reg.receive(_desc("hostC", addr="127.0.0.1:9102", slo={
        "worst_burn": 0.2,
        "attainment": {"m": {"ttft": 0.99, "tpot": 0.97}},
    }))
    s = reg.health_summary()
    assert s["size"] == 3 and s["up"] == 3
    assert s["worst_burn"] == {"host": "hostB", "burn": 3.5}
    # fleet attainment = the MINIMUM any member reports per objective
    assert s["attainment"] == {"ttft": 0.91, "tpot": 0.97}


def test_scrape_targets_exclude_self_and_dead():
    now = [0.0]
    reg = _registry(now)
    reg.receive(_desc("hostB", addr="127.0.0.1:9101"))
    reg.receive(_desc("hostC", addr="127.0.0.1:9102"))
    assert [t[0] for t in reg._scrape_targets()] == ["hostB", "hostC"]
    reg.tick(now=11.0)  # both dead
    assert reg._scrape_targets() == []


# -- exposition relabel / merge ---------------------------------------------

EXPO_A = """\
# HELP aios_tpu_rpc_requests_total RPCs
# TYPE aios_tpu_rpc_requests_total counter
aios_tpu_rpc_requests_total{service="runtime"} 4
# HELP aios_tpu_queue_wait_seconds waits
# TYPE aios_tpu_queue_wait_seconds histogram
aios_tpu_queue_wait_seconds_bucket{le="1"} 2
aios_tpu_queue_wait_seconds_bucket{le="+Inf"} 3
aios_tpu_queue_wait_seconds_sum 1.5
aios_tpu_queue_wait_seconds_count 3
up 1
"""

EXPO_B = """\
# HELP aios_tpu_rpc_requests_total RPCs from B
# TYPE aios_tpu_rpc_requests_total counter
aios_tpu_rpc_requests_total{service="runtime"} 9
aios_tpu_already{host="elsewhere",x="1"} 2
"""


def test_relabel_injects_host_and_keeps_histogram_family_together():
    fams = relabel_exposition(EXPO_A, "h1")
    by_name = {f[0]: f for f in fams}
    assert by_name["aios_tpu_rpc_requests_total"][3] == [
        'aios_tpu_rpc_requests_total{host="h1",service="runtime"} 4'
    ]
    # _bucket/_sum/_count ride under the histogram family header
    hist = by_name["aios_tpu_queue_wait_seconds"]
    assert hist[2] == "histogram"
    assert len(hist[3]) == 4
    assert hist[3][2] == 'aios_tpu_queue_wait_seconds_sum{host="h1"} 1.5'
    # an unlabeled sample gains the label set outright
    assert by_name["up"][3] == ['up{host="h1"} 1']


def test_relabel_passes_through_preexisting_host_label():
    fams = relabel_exposition(EXPO_B, "h2")
    samples = [s for f in fams for s in f[3]]
    assert 'aios_tpu_already{host="elsewhere",x="1"} 2' in samples


def test_merge_expositions_families_contiguous_first_help_wins():
    text = merge_expositions([("h1", EXPO_A), ("h2", EXPO_B)])
    lines = text.splitlines()
    # exactly one header pair for the shared family, first HELP text wins
    assert lines.count("# HELP aios_tpu_rpc_requests_total RPCs") == 1
    assert "# HELP aios_tpu_rpc_requests_total RPCs from B" not in text
    # both hosts' samples sit directly under that one header
    i = lines.index("# TYPE aios_tpu_rpc_requests_total counter")
    assert lines[i + 1:i + 3] == [
        'aios_tpu_rpc_requests_total{host="h1",service="runtime"} 4',
        'aios_tpu_rpc_requests_total{host="h2",service="runtime"} 9',
    ]


# -- trace stitching ---------------------------------------------------------


def _timeline(model, request_id, trace_id):
    return {
        "model": model, "request_id": request_id, "tenant": "t",
        "state": "completed", "submitted_at": 100.0, "duration_ms": 5.0,
        "queue_wait_ms": 1.0, "trace_id": trace_id,
        "events": [{"t_ms": 0.0, "kind": "admission"}],
    }


def test_stitch_chrome_traces_one_lane_group_per_host():
    merged = stitch_chrome_traces({
        "hostA": [_timeline("m", "r1", "T")],
        "hostB": [_timeline("m", "r2", "T")],
    })
    names = {
        ev["args"]["name"]
        for ev in merged["traceEvents"]
        if ev.get("name") == "process_name"
    }
    assert names == {"host:hostA model:m", "host:hostB model:m"}
    # hosts occupy disjoint pid blocks (hostA < stride <= hostB)
    pids = {
        ev["args"]["name"]: ev["pid"]
        for ev in merged["traceEvents"]
        if ev.get("name") == "process_name"
    }
    assert pids["host:hostA model:m"] < fleet._PID_STRIDE
    assert pids["host:hostB model:m"] >= fleet._PID_STRIDE


# -- the HTTP surface over a real ephemeral-port server ----------------------


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as r:
        return r.status, r.read().decode()


def test_fleet_http_surface(monkeypatch):
    from aios_tpu.obs.http import start_metrics_server

    monkeypatch.setenv("AIOS_TPU_FLEET_HOST", "httpA")
    now = [0.0]
    server, port = start_metrics_server(port=0)
    reg = _registry(now)
    prev = fleet.install(reg)
    try:
        # /healthz names the ACTUAL bound port (ephemeral discoverability)
        status, body = _get(port, "/healthz")
        assert status == 200 and json.loads(body)["metrics_port"] == port

        # announce folds the peer in and answers with us + gossip
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/fleet/announce",
            data=json.dumps(_desc("httpB")).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            reply = json.loads(r.read().decode())
        assert reply["member"]["host"] == "hostA"

        status, body = _get(port, "/fleet/members")
        data = json.loads(body)
        hosts = {m["host"] for m in data["members"]}
        assert {"hostA", "httpB"} <= hosts
        assert data["summary"]["up"] >= 2

        # federation: own registry renders with our host label injected
        status, body = _get(port, "/metrics/fleet")
        assert status == 200
        assert 'host="hostA"' in body

        # malformed announce -> 400, not a crashed endpoint
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/fleet/announce", data=b"[1,2]",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 400
    finally:
        fleet.install(prev)
        server.shutdown()


def test_fleet_routes_404_when_unarmed():
    from aios_tpu.obs.http import start_metrics_server

    prev = fleet.install(None)
    server, port = start_metrics_server(port=0)
    try:
        for path in ("/metrics/fleet", "/fleet/members",
                     "/debug/trace/fleet?trace=x"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(port, path)
            assert ei.value.code == 404, path
    finally:
        fleet.install(prev)
        server.shutdown()


def test_slo_annotate_health_folds_fleet_summary():
    from aios_tpu.obs import slo

    now = [0.0]
    reg = _registry(now)
    prev = fleet.install(reg)
    try:
        payload = slo.annotate_health({"status": "ok"})
        assert payload["fleet"]["size"] == 1
        assert payload["fleet"]["up"] == 1
    finally:
        fleet.install(prev)


def test_stats_providers_feed_heartbeat_and_survive_errors():
    def good():
        return {"m": {"waiting": 2}}

    def bad():
        raise RuntimeError("sick pool")

    fleet.clear_stats_providers()
    try:
        fleet.add_stats_provider(good)
        fleet.add_stats_provider(bad)
        pools = fleet._self_pools()
        assert pools["m"] == {"waiting": 2}
        assert "provider" in pools["_error"]
    finally:
        fleet.clear_stats_providers()


def test_self_descriptor_refreshes_member_row():
    """Regression pin (ISSUE 17 satellite): the heartbeat descriptor
    must sample pool stats AT ANNOUNCE TIME and refresh self's stored
    member row. Before the fix, self's row held the boot-time snapshot
    forever — a degrade-ladder controller mid-walk was invisible to
    /fleet/members and fleetctl."""
    level = {"v": 0}
    fleet.clear_stats_providers()
    try:
        fleet.add_stats_provider(lambda: {"m": {"degrade_level": level["v"]}})
        now = [100.0]
        reg = _registry(now)
        row = next(m for m in reg.members() if m["self"])
        assert row["pools"]["m"]["degrade_level"] == 0
        level["v"] = 2  # the ladder walks between heartbeats
        reg.self_descriptor()
        row = next(m for m in reg.members() if m["self"])
        assert row["pools"]["m"]["degrade_level"] == 2
    finally:
        fleet.clear_stats_providers()


def test_gprefix_and_kvx_addr_piggyback_on_heartbeat():
    """The fleet data plane rides the EXISTING heartbeat: digest
    providers and the transfer endpoint land in the descriptor and in
    the membership rows peers score against."""
    digest = {"m": {"page": 32, "tails": {"ab12cd34ef567890": 3}}}
    fleet.clear_digest_providers()
    try:
        fleet.add_digest_provider(lambda: digest)
        fleet.set_transfer_addr("1.2.3.4:9400")
        now = [100.0]
        reg = _registry(now)
        desc = reg.self_descriptor()
        assert desc["gprefix"] == digest
        assert desc["kvx_addr"] == "1.2.3.4:9400"
        row = next(m for m in reg.members() if m["self"])
        assert row["gprefix"] == digest
        assert row["kvx_addr"] == "1.2.3.4:9400"
    finally:
        fleet.clear_digest_providers()
        fleet.set_transfer_addr("")


def test_digest_provider_errors_survive():
    def bad():
        raise RuntimeError("sick engine")

    fleet.clear_digest_providers()
    try:
        fleet.add_digest_provider(bad)
        digest = fleet._self_gprefix()
        assert "provider" in digest["_error"]
    finally:
        fleet.clear_digest_providers()


# -- the multihost env contract ---------------------------------------------


def test_env_contract_unset_is_single_host():
    from aios_tpu.parallel import multihost

    assert multihost.env_contract({}) is None


def test_env_contract_explicit_coordinator():
    from aios_tpu.parallel import multihost

    c = multihost.env_contract({
        "AIOS_TPU_COORDINATOR": "10.0.0.1:8476",
        "AIOS_TPU_NUM_PROCESSES": "4",
        "AIOS_TPU_PROCESS_ID": "2",
    })
    assert c.coordinator == "10.0.0.1:8476"
    assert c.num_processes == 4 and c.process_id == 2
    assert not c.auto


@pytest.mark.parametrize("missing", [
    {"AIOS_TPU_COORDINATOR": "10.0.0.1:8476"},
    {"AIOS_TPU_COORDINATOR": "10.0.0.1:8476",
     "AIOS_TPU_NUM_PROCESSES": "4"},
    {"AIOS_TPU_COORDINATOR": "10.0.0.1:8476",
     "AIOS_TPU_PROCESS_ID": "0"},
    {"AIOS_TPU_COORDINATOR": "10.0.0.1:8476",
     "AIOS_TPU_NUM_PROCESSES": "4", "AIOS_TPU_PROCESS_ID": ""},
])
def test_env_contract_incomplete_explicit_path_raises(missing):
    from aios_tpu.parallel import multihost

    with pytest.raises(ValueError, match="AIOS_TPU_COORDINATOR requires"):
        multihost.env_contract(missing)


@pytest.mark.parametrize("val", ["auto", "1", "AUTO"])
def test_env_contract_auto(val):
    from aios_tpu.parallel import multihost

    c = multihost.env_contract({"AIOS_TPU_MULTIHOST": val})
    assert c.auto and c.coordinator == ""


def test_env_contract_auto_with_coordinator_needs_no_companions():
    """AIOS_TPU_MULTIHOST=auto beside a coordinator is the pod
    self-describe path: the companion vars are optional there."""
    from aios_tpu.parallel import multihost

    c = multihost.env_contract({
        "AIOS_TPU_MULTIHOST": "auto",
        "AIOS_TPU_COORDINATOR": "10.0.0.1:8476",
    })
    assert c.auto and c.coordinator == "10.0.0.1:8476"


# -- multi-target storm routing / verdict -----------------------------------


def test_target_of_deterministic_and_tenant_affine():
    from aios_tpu.loadgen import target_of

    assert target_of("anyone", 1) == 0
    assert target_of("anyone", 0) == 0
    ts = [target_of(f"tenant-{i}", 3) for i in range(64)]
    assert ts == [target_of(f"tenant-{i}", 3) for i in range(64)]  # stable
    assert set(ts) == {0, 1, 2}  # spreads across targets
    # same tenant, same target, always (cache-coupled families stay put)
    assert len({target_of("chat", 3) for _ in range(10)}) == 1


def test_per_target_verdict_aggregation():
    from aios_tpu.loadgen.driver import Outcome
    from aios_tpu.loadgen.report import _per_target
    from aios_tpu.loadgen.trace import Call

    def call(tenant, deadline_ms=0):
        return Call(t=0.0, tenant=tenant, klass="interactive",
                    task_id=f"t-{tenant}", prompt="p", max_tokens=1,
                    temperature=0.0, streaming=False,
                    deadline_ms=deadline_ms, level="")

    outcomes = [
        Outcome(call=call("a"), status="ok", extras={"target": 0}),
        Outcome(call=call("b"), status="shed", extras={"target": 1}),
        Outcome(call=call("c", deadline_ms=50), status="shed",
                extras={"target": 1}),
    ]
    per = _per_target(outcomes)
    assert per["0"] == {"submitted": 1, "completed": 1, "shed": 0,
                       "rejected": 0}
    # the deadline tenant's submission pins; its outcome does not
    assert per["1"] == {"submitted": 2, "completed": 0, "shed": 1,
                       "rejected": 0}


def test_per_target_empty_for_single_endpoint_storms():
    from aios_tpu.loadgen.driver import Outcome
    from aios_tpu.loadgen.report import _per_target
    from aios_tpu.loadgen.trace import Call

    c = Call(t=0.0, tenant="a", klass="interactive", task_id="t",
             prompt="p", max_tokens=1, temperature=0.0, streaming=False,
             deadline_ms=0, level="")
    assert _per_target([Outcome(call=c, status="ok")]) == {}


def test_scenario_endpoints_field_parses():
    from aios_tpu.loadgen.scenario import _build

    sc = _build({
        "scenario": {"name": "multi", "seed": 1, "duration_secs": 1.0,
                     "endpoints": ["127.0.0.1:1", "127.0.0.1:2"]},
        "tenants": [{"name": "chat"}],
    }, "inline")
    assert sc.endpoints == ("127.0.0.1:1", "127.0.0.1:2")


# -- fleetctl --json (ISSUE 17 satellite) -----------------------------------


def _fleetctl():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fleetctl", os.path.join(REPO, "scripts", "fleetctl.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleetctl_json_status_and_top(capsys):
    """``--json`` replaces the terse verdict with the full row set —
    same fields the table renders (plus kvx_addr), same exit codes."""
    mod = _fleetctl()
    data = {
        "members": [
            {"host": "hostA", "role": "runtime", "state": "up",
             "age_secs": 0.1, "rank": "0", "version": "t", "pid": 1,
             "metrics_addr": "a:1", "kvx_addr": "a:2", "self": True,
             "pools": {"m": {"waiting": 1, "batch_occupancy": 0.5,
                             "degrade_level": 2}},
             "slo": {"worst_burn": 1.5}},
            {"host": "hostB", "role": "decode", "state": "suspect",
             "age_secs": 7.0, "rank": "1", "version": "t", "pid": 2,
             "metrics_addr": "b:1", "kvx_addr": "b:2", "self": False,
             "pools": {}, "slo": {}},
        ],
        "journal": [{"host": "hostB", "role": "decode", "from": "up",
                     "to": "suspect", "at": 0.0}],
    }
    rc = mod.cmd_status(data, as_json=True)
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1 and out["pass"] is False
    assert out["size"] == 2 and out["up"] == 1
    assert out["members"][0]["kvx_addr"] == "a:2"
    assert out["journal"][0]["to"] == "suspect"
    rc = mod.cmd_top(data, as_json=True)
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1 and out["pass"] is False
    # worst burn sorts first, load triple flattened per row
    assert out["members"][0]["host"] == "hostA"
    assert out["members"][0]["worst_burn"] == 1.5
    assert out["members"][0]["degrade_level"] == 2
    assert out["members"][0]["waiting"] == 1
    # the terse verdict path is unchanged
    rc = mod.cmd_status(data)
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1 and out["not_up"] == [
        {"host": "hostB", "role": "decode", "state": "suspect"}
    ]


# -- the two-process e2e (slow tier) ----------------------------------------


@pytest.mark.slow
def test_fleet_smoke_two_real_processes():
    """scripts/fleet_smoke.py end to end: two runtime processes on
    ephemeral ports federate /metrics/fleet, stitch one traced request
    into per-host Chrome lanes, fleetctl exits 0, and the killed
    worker's up -> suspect -> dead journal is identical across two
    runs."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=580,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["pass"] and verdict["identical"] and verdict["lifecycle"]
