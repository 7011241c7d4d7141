#!/usr/bin/env python3
"""Incident-bundle smoke: two REAL processes, a seeded fault storm, and
a deterministic incident verdict (the preflight.sh gate 9;
docs/TESTING.md, docs/RUNBOOK.md §12).

One round:

  1. spawn worker A (scripts/fleet_worker.py — fleet + tsdb + incidents
     armed) and worker B seeded with A's metrics endpoint AND a seeded
     fault schedule (``AIOS_TPU_FAULTS=seed=7;pool.scheduler_crash=
     nth:4``) — membership converges through announce gossip;
  2. drive a request wave at B over gRPC until the seeded crash fires;
     the injector's fired-fault hook must freeze an incident bundle with
     cause ``fault`` on B;
  3. assert the bundle carries the fired-fault journal evidence
     (point/mode/hit) AND a non-empty tsdb window (the ring was sampling
     while the wave ran);
  4. assert ``GET /debug/tsdb/fleet`` on A federates tsdb series from
     BOTH hosts, and ``fleetctl history`` against A exits 0;
  5. normalize the fault-cause bundles (cause, model, trigger fields,
     fired-fault tail) into the round verdict.

The whole round runs TWICE; the verdicts must be identical (the seeded
schedule makes the crash — and therefore the incident — replayable).
Human progress goes to stderr; ONE JSON verdict line goes to stdout.
Exit 0 on pass.

FLEET_SMOKE_TIME_SCALE stretches every window and timeout on slow
containers, same as the other fleet smokes.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SCALE = float(os.environ.get("FLEET_SMOKE_TIME_SCALE", "1") or 1)
INTERVAL = 0.3 * SCALE
MODEL = "fleet-smoke"  # the one model fleet_worker.py loads
FAULT_SPEC = "seed=7;pool.scheduler_crash=nth:4"


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def worker_env(host_id: str, peers: str = "", faults: str = "") -> dict:
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": REPO,
        "AIOS_TPU_FLEET": "1",
        "AIOS_TPU_FLEET_HOST": host_id,
        "AIOS_TPU_FLEET_PEERS": peers,
        "AIOS_TPU_FLEET_INTERVAL_SECS": str(INTERVAL),
        # the observability plane under test: the ring samples fast so
        # the bundle's window is non-empty within a short wave, and the
        # incident builder's aftermath wait stays short
        "AIOS_TPU_TSDB": "1",
        "AIOS_TPU_TSDB_STEP_SECS": "0.2",
        "AIOS_TPU_INCIDENT_WINDOW_SECS": "1",
        "AIOS_TPU_INCIDENT_COOLDOWN_SECS": "0",
        "AIOS_TPU_FAULTS": faults,
    }


def spawn_worker(host_id: str, peers: str = "", faults: str = "") -> tuple:
    """-> (Popen, grpc_port, metrics_port); waits for the ready line."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_worker.py")],
        env=worker_env(host_id, peers, faults), cwd=REPO,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True,
    )
    deadline = time.monotonic() + 180 * SCALE
    while True:
        line = p.stdout.readline()
        if line.startswith("FLEET_WORKER_READY "):
            ports = json.loads(line.split(" ", 1)[1])
            return p, ports["grpc_port"], ports["metrics_port"]
        if not line and p.poll() is not None:
            raise RuntimeError(f"worker {host_id} died before ready")
        if time.monotonic() > deadline:
            p.kill()
            raise RuntimeError(f"worker {host_id} never became ready")


def fetch_json(port: int, path: str) -> dict:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return json.loads(r.read().decode("utf-8"))


def poll(fn, what: str, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(0.1 * SCALE)
    raise RuntimeError(f"timed out waiting for {what}")


def request_wave(grpc_port: int, tag: str, n: int = 6) -> None:
    """Enough scheduler ticks to walk the seeded nth:4 crash trigger
    past its firing point (the pool respawns and keeps serving)."""
    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import runtime_pb2

    for i in range(n):
        channel = rpc.insecure_channel(f"127.0.0.1:{grpc_port}")
        try:
            services.AIRuntimeStub(channel).Infer(
                runtime_pb2.InferRequest(
                    model=MODEL, prompt=f"storm {tag} {i}",
                    max_tokens=8, temperature=5e-5,
                    task_id=f"incident-smoke-{tag}-{i}",
                ),
                timeout=120,
            )
        finally:
            channel.close()


def norm_bundle(bundle: dict) -> dict:
    """A bundle modulo timestamps/ids/window content: the trigger
    identity and the fired-fault evidence must replay exactly."""
    return {
        "model": bundle["model"],
        "cause": bundle["cause"],
        "fields": bundle["fields"],
        "faults": [
            {k: e.get(k) for k in ("point", "mode", "hit", "model")}
            for e in bundle["faults"]
            if e.get("point") == "pool.scheduler_crash"
        ],
    }


def run_round(tag: str) -> dict:
    pa, _grpc_a, metrics_a = spawn_worker("hostA")
    pb = None
    try:
        pb, grpc_b, metrics_b = spawn_worker(
            "hostB", peers=f"127.0.0.1:{metrics_a}", faults=FAULT_SPEC,
        )
        log(f"[{tag}] workers up: A metrics={metrics_a}, "
            f"B grpc={grpc_b} metrics={metrics_b} faults={FAULT_SPEC!r}")

        def both_up():
            members = fetch_json(metrics_a, "/fleet/members")["members"]
            ups = {m["host"] for m in members if m["state"] == "up"}
            return ups == {"hostA", "hostB"}

        poll(both_up, "both members up on A", 30 * SCALE)
        log(f"[{tag}] membership converged")

        request_wave(grpc_b, tag)

        def fault_incident():
            incs = fetch_json(metrics_b, "/debug/incidents")["incidents"]
            return [m for m in incs if m["cause"] == "fault"]

        metas = poll(fault_incident, "a fault-cause incident on B",
                     30 * SCALE)
        bundles = [
            fetch_json(metrics_b, f"/debug/incidents?id={m['id']}")
            for m in metas
        ]
        log(f"[{tag}] {len(bundles)} fault incident(s) frozen on B")

        # the bundle holds the cross-layer evidence, not just the label:
        # the fired-fault journal entry AND a sampled tsdb window
        assert any(
            e.get("point") == "pool.scheduler_crash" and e.get("hit") == 4
            for b in bundles for e in b["faults"]
        ), "no bundle carries the fired pool.scheduler_crash journal entry"
        assert any(
            b["tsdb"]["armed"] and b["tsdb"]["series"] for b in bundles
        ), "no bundle froze a non-empty tsdb window"
        log(f"[{tag}] bundle carries fault journal + tsdb window")

        # the crash-respawn edge must be visible in a frozen window: the
        # scheduler crash increments the restarts counter, the ring
        # samples it as a delta, and SOME bundle's window (the fault
        # trigger's aftermath, or the crash_respawn snapshot's own
        # incident) holds a positive point for it
        def respawn_edge_frozen():
            metas = fetch_json(metrics_b, "/debug/incidents")["incidents"]
            for m in metas:
                b = fetch_json(metrics_b,
                               f"/debug/incidents?id={m['id']}")
                for s in b["tsdb"]["series"]:
                    if (s["name"] == "aios_tpu_serving_replica_"
                                     "restarts_total"
                            and sum(v for _, v in s["points"]) > 0):
                        return True
            return False

        poll(respawn_edge_frozen,
             "the crash-respawn edge in a frozen tsdb window", 30 * SCALE)
        log(f"[{tag}] a frozen window shows the crash-respawn edge")

        def federated_tsdb():
            got = fetch_json(
                metrics_a,
                "/debug/tsdb/fleet?name=aios_tpu_tsdb_sample_passes_total"
                "&verb=raw&window=60",
            )
            hosts = {
                h for h, payload in got.get("hosts", {}).items()
                if payload.get("series")
            }
            return {"hostA", "hostB"} <= hosts

        poll(federated_tsdb, "tsdb series from both hosts on A",
             15 * SCALE)
        log(f"[{tag}] /debug/tsdb/fleet federates both hosts")

        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "fleetctl.py"),
             "history", "aios_tpu_tsdb_sample_passes_total",
             "--target", f"127.0.0.1:{metrics_a}"],
            cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ).returncode
        assert rc == 0, f"fleetctl history exited {rc} with live series"
        log(f"[{tag}] fleetctl history: 0")

        return {
            "bundles": sorted(
                (norm_bundle(b) for b in bundles),
                key=lambda b: json.dumps(b, sort_keys=True),
            ),
        }
    finally:
        for p in (pa, pb):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()


def main() -> int:
    rounds = [run_round("round1"), run_round("round2")]
    identical = rounds[0] == rounds[1]
    has_fault = any(
        b["cause"] == "fault"
        and b["fields"].get("point") == "pool.scheduler_crash"
        for b in rounds[0]["bundles"]
    )
    verdict = {
        "smoke": "incidents",
        "fault_spec": FAULT_SPEC,
        "bundles": rounds[0]["bundles"],
        "identical": identical,
        "fault_incident": has_fault,
        "pass": identical and has_fault,
    }
    print(json.dumps(verdict, sort_keys=True))
    if not identical:
        log("FAIL: incident verdicts diverged across seeded runs:")
        log(f"  round1: {rounds[0]}")
        log(f"  round2: {rounds[1]}")
    if not has_fault:
        log(f"FAIL: no fault-cause incident for the seeded crash: "
            f"{rounds[0]['bundles']}")
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
