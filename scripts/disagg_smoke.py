#!/usr/bin/env python3
"""Disaggregation smoke: three REAL processes serve one stream across
the fleet data plane (the preflight.sh gate 7; docs/TESTING.md).

One round:

  1. spawn prefill host A (scripts/fleet_worker.py, role=prefill, no
     peers) and issue the reference request — with no decode peer the
     router counts ``no_peer`` and serves locally, so the reference
     text comes from THE SAME weights the disaggregated run will use;
  2. spawn decode hosts B and C seeded with A's metrics endpoint; B
     carries ``AIOS_TPU_FAULTS="...;fleet.host_kill=nth:3,exit=1"`` —
     a scheduled process kill on the 3rd handed-off token;
  3. poll A's ``/fleet/members`` until both decode rows are "up" and
     advertise a ``kvx_addr`` (the transfer endpoint gossip);
  4. issue the SAME request again: A prefills + emits the first token,
     pushes the KV chain, and hands the stream to B (least-loaded,
     lexicographic tie-break -> deterministic). B dies mid-stream with
     exit status 17 (disagg.KILL_EXIT_STATUS — assert the kill we
     scheduled is the death we observed); A re-hands the stream to C
     with every already-relayed token, and the response text must be
     byte-identical to the single-host reference;
  5. assert A's ``/metrics``: ``route_total`` counted exactly one
     ``no_peer``, one ``handoff``, one ``handoff_resume``, zero
     ``fallback_local``; ``kvx_pages_total{direction="push"}`` moved a
     whole chain (> 0, same page count every run);
  6. poll A's membership until C's row gossips a non-empty prefix
     digest for the model — the decode host now ADVERTISES the chain
     it restored, closing the gossiped-prefix-index loop end to end.

The whole round runs TWICE; the port-free verdicts (text, route
counters, pushed pages, B's exit status) must be identical across runs.
Human progress goes to stderr; ONE JSON verdict line goes to stdout.
Exit 0 on pass.

Tuned short via the AIOS_TPU_FLEET_*_SECS knobs; FLEET_SMOKE_TIME_SCALE
stretches every window and timeout on slow containers.
"""

import json
import os
import re
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
KILL_EXIT_STATUS = 17  # disagg.KILL_EXIT_STATUS, pinned here on purpose
PROMPT = (
    "disaggregate this stream across the fleet: the prefill host "
    "computes the prompt pages once, pushes the chain over the wire, "
    "and a decode host carries the tokens home even when its first "
    "target dies mid-flight"
)
MAX_TOKENS = 16


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def worker_env(host_id: str, fleet_role: str, peers: str = "",
               faults: str = "") -> dict:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": REPO,
        "AIOS_TPU_FLEET": "1",
        "AIOS_TPU_FLEET_HOST": host_id,
        "AIOS_TPU_FLEET_ROLE": fleet_role,
        "AIOS_TPU_FLEET_PEERS": peers,
        "AIOS_TPU_FLEET_INTERVAL_SECS": str(INTERVAL),
        "AIOS_TPU_FLEET_SUSPECT_SECS": str(SUSPECT),
        "AIOS_TPU_FLEET_DEAD_SECS": str(DEAD),
        # the data plane needs pages to ship: paged KV + a host-RAM
        # spill tier on every member (model_manager env knobs)
        "AIOS_TPU_PAGED_KV": "auto",
        "AIOS_TPU_PREFIX_HOST_BYTES": str(32 << 20),
    }
    env.pop("AIOS_TPU_FAULTS", None)
    if faults:
        env["AIOS_TPU_FAULTS"] = faults
    return env


def spawn_worker(host_id: str, fleet_role: str, peers: str = "",
                 faults: str = "") -> tuple:
    """-> (Popen, grpc_port, metrics_port); waits for the ready line."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_worker.py")],
        env=worker_env(host_id, fleet_role, peers, faults), cwd=REPO,
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


def infer(grpc_port: int, task_id: str) -> str:
    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import runtime_pb2

    channel = rpc.insecure_channel(f"127.0.0.1:{grpc_port}")
    try:
        resp = services.AIRuntimeStub(channel).Infer(
            runtime_pb2.InferRequest(
                model=MODEL, prompt=PROMPT, max_tokens=MAX_TOKENS,
                temperature=5e-5, task_id=task_id,
            ),
            timeout=180,
        )
        return resp.text
    finally:
        channel.close()


def counter(metrics_text: str, name: str, **labels) -> float:
    """One sample's value out of the exposition text, 0.0 when the
    child was never touched (pre-registered children render as 0)."""
    want = {k: str(v) for k, v in labels.items()}
    for line in metrics_text.splitlines():
        m = re.match(rf"^{re.escape(name)}\{{([^}}]*)\}} (\S+)$", line)
        if m:
            got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
            if got == want:
                return float(m.group(2))
    return 0.0


def run_round(tag: str) -> dict:
    """One full smoke round -> the port-free verdict dict."""
    pa, grpc_a, metrics_a = spawn_worker("hostA", "prefill")
    pb = pc = None
    try:
        # reference BEFORE any decode peer exists: the router counts
        # no_peer and serves the stream locally on A
        ref = infer(grpc_a, "disagg-smoke-ref")
        log(f"[{tag}] reference from solo prefill host: {len(ref)} chars")

        pb, _, _ = spawn_worker(
            "hostB", "decode", peers=f"127.0.0.1:{metrics_a}",
            faults="seed=7;fleet.host_kill=nth:3,exit=1",
        )
        pc, _, _ = spawn_worker(
            "hostC", "decode", peers=f"127.0.0.1:{metrics_a}",
        )

        def decoders_ready():
            members = fetch_json(metrics_a, "/fleet/members")["members"]
            ready = {
                m["host"] for m in members
                if m["state"] == "up" and m.get("role") == "decode"
                and m.get("kvx_addr")
            }
            return {"hostB", "hostC"} <= ready

        poll(decoders_ready, "decode hosts up with kvx_addr on A",
             30 * SCALE)
        log(f"[{tag}] decode hosts gossiped their transfer endpoints")

        out = infer(grpc_a, "disagg-smoke-kill")
        b_status = pb.wait(timeout=30 * SCALE)
        pb = None
        log(f"[{tag}] disaggregated stream done; hostB exit={b_status}")

        metrics = fetch_text(metrics_a, "/metrics")
        routes = {
            reason: counter(
                metrics, "aios_tpu_fleet_route_total",
                model=MODEL, reason=reason,
            )
            for reason in ("no_peer", "handoff", "handoff_resume",
                           "fallback_local")
        }
        pushed = counter(
            metrics, "aios_tpu_fleet_kvx_pages_total",
            model=MODEL, direction="push",
        )

        def survivor_gossips_chain():
            members = fetch_json(metrics_a, "/fleet/members")["members"]
            for m in members:
                if m["host"] == "hostC":
                    return bool((m.get("gprefix") or {}).get(MODEL))
            return False

        gossip = False
        try:
            poll(survivor_gossips_chain,
                 "hostC advertising a prefix digest for the model",
                 15 * SCALE)
            gossip = True
        except RuntimeError:
            pass
        log(f"[{tag}] routes={routes} pushed_pages={pushed} "
            f"gossip={gossip}")

        verdict = {
            "text_matches": out == ref,
            "text_len": len(ref),
            "killed_exit": b_status,
            "routes": routes,
            "pushed_pages": pushed,
            "gossip": gossip,
        }
        verdict["pass"] = (
            verdict["text_matches"]
            and b_status == KILL_EXIT_STATUS
            and routes["no_peer"] == 1.0
            and routes["handoff"] == 1.0
            and routes["handoff_resume"] == 1.0
            and routes["fallback_local"] == 0.0
            and pushed > 0
            and gossip
        )
        if not verdict["pass"]:
            log(f"[{tag}] FAIL detail: ref={ref!r} out={out!r}")
        return verdict
    finally:
        for p in (pa, pb, pc):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()


def main() -> int:
    rounds = [run_round("round1"), run_round("round2")]
    identical = rounds[0] == rounds[1]
    verdict = {
        "smoke": "disagg",
        "round": rounds[0],
        "identical": identical,
        "pass": identical and all(r["pass"] for r in rounds),
    }
    print(json.dumps(verdict, sort_keys=True))
    if not identical:
        log("FAIL: verdicts diverged across seeded runs:")
        log(f"  round1: {rounds[0]}")
        log(f"  round2: {rounds[1]}")
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
