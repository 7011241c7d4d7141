#!/usr/bin/env python3
"""Fleet telemetry smoke: two REAL processes federate, stitch, and die
deterministically (the preflight.sh gate 6; docs/TESTING.md).

One round:

  1. spawn worker A (scripts/fleet_worker.py, fleet armed, no peers)
     and worker B seeded with A's bound metrics endpoint — membership
     converges through announce gossip;
  2. poll A's ``/fleet/members`` until BOTH members are "up", and
     assert A's ``/healthz`` carries the actual bound ``metrics_port``
     (the ephemeral-port discoverability contract);
  3. assert ``/metrics/fleet`` on A carries
     ``aios_tpu_fleet_member_up_total`` samples for both host labels;
  4. issue one traced request to EACH worker under a single client span
     (the interceptors carry the traceparent across the gRPC boundary)
     and assert ``/debug/trace/fleet?trace=<id>`` renders ONE stitched
     Chrome trace with a lane group per host;
  5. ``fleetctl status`` against A exits 0 showing both members;
  6. kill B and poll A's journal until the ``up -> suspect -> dead``
     edges land; assert ``/metrics/fleet`` dropped hostB's samples.

The whole round runs TWICE; the membership-transition journals —
normalized to (host, role, from, to) — must be identical across runs
(the failure detector is deterministic given the same death). Human
progress goes to stderr; ONE JSON verdict line goes to stdout. Exit 0
on pass.

Tuned short via the AIOS_TPU_FLEET_*_SECS knobs; FLEET_SMOKE_TIME_SCALE
stretches every window and timeout on slow containers.
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
SUSPECT = 1.5 * SCALE
DEAD = 3.0 * SCALE
MODEL = "fleet-smoke"


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def worker_env(host_id: str, peers: str = "") -> dict:
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": REPO,
        "AIOS_TPU_FLEET": "1",
        # explicit host ids: the default includes the pid, which would
        # make the cross-run journal comparison vacuously fail
        "AIOS_TPU_FLEET_HOST": host_id,
        "AIOS_TPU_FLEET_PEERS": peers,
        "AIOS_TPU_FLEET_INTERVAL_SECS": str(INTERVAL),
        "AIOS_TPU_FLEET_SUSPECT_SECS": str(SUSPECT),
        "AIOS_TPU_FLEET_DEAD_SECS": str(DEAD),
    }


def spawn_worker(host_id: str, peers: str = "") -> tuple:
    """-> (Popen, grpc_port, metrics_port); waits for the ready line."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_worker.py")],
        env=worker_env(host_id, peers), cwd=REPO,
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


def fetch_text(port: int, path: str) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.read().decode("utf-8")


def poll(fn, what: str, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(0.1 * SCALE)
    raise RuntimeError(f"timed out waiting for {what}")


def traced_requests(ports: list) -> str:
    """One client span wrapping one Infer per worker -> the trace id
    both processes' flight recorders now share."""
    from aios_tpu import rpc, services
    from aios_tpu.obs import tracing
    from aios_tpu.proto_gen import runtime_pb2

    with tracing.start_span("fleet-smoke") as span:
        for i, port in enumerate(ports):
            channel = rpc.insecure_channel(f"127.0.0.1:{port}")
            try:
                services.AIRuntimeStub(channel).Infer(
                    runtime_pb2.InferRequest(
                        model=MODEL, prompt="stitch me across the fleet",
                        max_tokens=4, temperature=5e-5,
                        task_id=f"fleet-smoke-{i}",
                    ),
                    timeout=120,
                )
            finally:
                channel.close()
        return span.trace_id


def norm_journal(journal: list) -> list:
    return [(e["host"], e["role"], e["from"], e["to"]) for e in journal]


def run_round(tag: str) -> list:
    """One full smoke round -> the normalized journal from worker A."""
    pa, grpc_a, metrics_a = spawn_worker("hostA")
    pb = None
    try:
        pb, grpc_b, metrics_b = spawn_worker(
            "hostB", peers=f"127.0.0.1:{metrics_a}"
        )
        log(f"[{tag}] workers up: A grpc={grpc_a} metrics={metrics_a}, "
            f"B grpc={grpc_b} metrics={metrics_b}")

        # ephemeral-port discoverability: /healthz names the bound port
        hz = fetch_json(metrics_a, "/healthz")
        assert hz.get("metrics_port") == metrics_a, hz

        def both_up():
            members = fetch_json(metrics_a, "/fleet/members")["members"]
            ups = {m["host"] for m in members if m["state"] == "up"}
            return ups == {"hostA", "hostB"}

        poll(both_up, "both members up on A", 30 * SCALE)
        log(f"[{tag}] membership converged")

        def federated():
            text = fetch_text(metrics_a, "/metrics/fleet")
            # process_info is a series only its OWN process exports
            # (identity in labels) — seeing hostB's proves the scrape,
            # not just A's bookkeeping about B
            return ('aios_tpu_fleet_member_up_total{host="hostA"' in text
                    and 'aios_tpu_process_info{host="hostB"' in text)

        poll(federated, "both hosts in /metrics/fleet", 15 * SCALE)
        log(f"[{tag}] federation carries both host labels")

        trace = traced_requests([grpc_a, grpc_b])

        def stitched():
            got = fetch_json(
                metrics_a, f"/debug/trace/fleet?trace={trace}"
            )
            hosts = {
                ev["args"]["name"].split(" ", 1)[0]
                for ev in got.get("traceEvents", [])
                if ev.get("name") == "process_name"
            }
            return {"host:hostA", "host:hostB"} <= hosts
        poll(stitched, "two host lanes in the stitched trace", 15 * SCALE)
        log(f"[{tag}] stitched trace {trace} has both host lanes")

        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "fleetctl.py"),
             "status", "--target", f"127.0.0.1:{metrics_a}"],
            cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ).returncode
        assert rc == 0, f"fleetctl status exited {rc} with both members up"
        log(f"[{tag}] fleetctl status: 0")

        pb.kill()
        pb.wait()
        pb = None

        def b_dead():
            members = fetch_json(metrics_a, "/fleet/members")["members"]
            return any(m["host"] == "hostB" and m["state"] == "dead"
                       for m in members)

        poll(b_dead, "hostB aging to dead", (DEAD + 10) * SCALE)
        # the dead host's SCRAPED series are gone; A's own membership
        # gauge about hostB legitimately stays (member_up=0 + absence of
        # hostB's self-exported series IS the death signal)
        text = fetch_text(metrics_a, "/metrics/fleet")
        assert 'aios_tpu_process_info{host="hostB"' not in text, \
            "/metrics/fleet still carries the dead host's scraped series"
        assert ('aios_tpu_fleet_member_up_total{host="hostB"'
                ',role="runtime"} 0' in text), \
            "member_up gauge for the dead host should read 0"
        journal = norm_journal(
            fetch_json(metrics_a, "/fleet/members")["journal"]
        )
        log(f"[{tag}] hostB suspect->dead observed; journal: {journal}")
        return journal
    finally:
        for p in (pa, pb):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()


def main() -> int:
    journals = [run_round("round1"), run_round("round2")]
    identical = journals[0] == journals[1]
    expected_edges = [
        ("hostB", "runtime", "", "up"),
        ("hostB", "runtime", "up", "suspect"),
        ("hostB", "runtime", "suspect", "dead"),
    ]
    has_lifecycle = all(e in journals[0] for e in expected_edges)
    verdict = {
        "smoke": "fleet",
        "journal": [list(e) for e in journals[0]],
        "identical": identical,
        "lifecycle": has_lifecycle,
        "pass": identical and has_lifecycle,
    }
    print(json.dumps(verdict, sort_keys=True))
    if not identical:
        log("FAIL: membership journals diverged across seeded runs:")
        log(f"  round1: {journals[0]}")
        log(f"  round2: {journals[1]}")
    if not has_lifecycle:
        log(f"FAIL: lifecycle edges missing from {journals[0]}")
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
