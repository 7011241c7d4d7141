#!/usr/bin/env python3
"""Fault-domain smoke: three REAL processes survive a seeded asymmetric
partition, a mid-stream link sever, and a graceful drain — with the
survivor streams token-identical to a solo reference (the preflight.sh
gate 8; docs/TESTING.md, docs/FAULTS.md "Per-edge network faults").

The cast (each process carries its OWN seeded ``AIOS_TPU_FAULTS``
schedule — per-edge faults are client-side, so each host injects only
its own outbound edges plus its announce-reply gate):

  A  prefill host. Schedule: ``net.drop_after=nth:1,dst=hostB,
     surface=rpc,after_msgs=3`` — the FIRST A->B response stream (the
     first Handoff) severs after 3 messages. Breaker knobs tightened
     (threshold 1, 2 probes, short cooldown) so one sever quarantines
     and two federation scrapes heal.
  B  decode host. Schedule: ``net.partition_oneway=nth:4,until=60,
     dst=hostA,surface=http`` — after ~1 clean announce round, EVERY
     B->A http edge traversal in the hit window [4, 60] drops: B's
     outbound announces refuse at check_send AND B's replies to A's
     announces are withheld by the server-side gate (A's descriptor
     still folds — that direction is clean). Plus ``dispatch.delay=
     prob:1.0`` so decoded tokens trickle at a real cadence and the
     drain provably lands mid-stream.
  C  decode host, no faults — the control: it must finish the smoke
     with ZERO breaker transitions (healthy fleets never quarantine).

The acts:

  1. solo reference on A (``no_peer`` route — same weights as the
     fleet runs);
  2. spawn C, wait up; spawn B, wait up (B's hits 1-3 let the first
     announce fold B's full descriptor into A before the window slams);
  3. asymmetric-partition evidence: A walks B up->suspect->dead while
     B still sees A "up" (the reverse edge is clean); A counts
     announce failures to B; the window exhausts and A heals B to up;
  4. stream 1: A hands off to B (least-loaded lexicographic tie), the
     link severs after 3 chunks, the breaker opens (-> B quarantined),
     the resume ladder re-hands to C, and the text matches the
     reference exactly;
  5. quarantine heals: polling A's ``/metrics/fleet`` drives federation
     scrapes of B — the half-open probes — until the breaker gauge
     returns to closed; C's gauge never left 0;
  6. drain e2e: a live StreamInfer routes to B again, then ``fleetctl
     drain --host hostB`` walks B through draining->leaving: B aborts
     the relay per-token (A re-hands to C mid-stream), pushes its hot
     chains to C, announces ``phase=leaving``, exits 0 — and the
     joined stream text still matches the reference.

The whole round runs TWICE; the port-free verdicts must be identical
across runs (the seeded-determinism contract). Human progress goes to
stderr; ONE JSON verdict line goes to stdout. Exit 0 on pass.
"""

import json
import os
import re
import subprocess
import sys
import tempfile
import threading
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
# chosen for its generation shape on synthetic://tiny-test: 200
# char-level tokens (>= one full 128-token KV page, so chains export
# and the drain has hot pages to push) and a full 16-token generation
# whose streamed deltas concatenate to exactly the unary text
PROMPT = "0 1 2 3 4 5 6 7 8 9 " * 10
MAX_TOKENS = 16
# B's per-token decode delay: wide enough that spawning fleetctl (a
# stdlib-only CLI) provably lands the drain before the stream finishes
DELAY_MS = int(150 * SCALE)

FAULTS_A = (
    "seed=11;net.drop_after=nth:1,dst=hostB,surface=rpc,after_msgs=3"
)
FAULTS_B = (
    "seed=11;net.partition_oneway=nth:4,until=60,dst=hostA,surface=http"
    f";dispatch.delay=prob:1.0,delay_ms={DELAY_MS}"
)
# one sever opens the breaker; two clean federation scrapes close it
BREAKER_ENV_A = {
    "AIOS_TPU_FLEET_BREAKER_THRESHOLD": "1",
    "AIOS_TPU_FLEET_BREAKER_PROBES": "2",
    "AIOS_TPU_FLEET_BREAKER_COOLDOWN_SECS": str(0.4 * SCALE),
    "AIOS_TPU_FLEET_BREAKER_MAX_COOLDOWN_SECS": str(2.0 * SCALE),
}


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def worker_env(host_id: str, fleet_role: str, peers: str = "",
               faults: str = "", extra: dict = None) -> dict:
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
        "AIOS_TPU_PAGED_KV": "auto",
        "AIOS_TPU_PREFIX_HOST_BYTES": str(32 << 20),
    }
    env.pop("AIOS_TPU_FAULTS", None)
    if faults:
        env["AIOS_TPU_FAULTS"] = faults
    if extra:
        env.update(extra)
    return env


def spawn_worker(host_id: str, fleet_role: str, peers: str = "",
                 faults: str = "", extra: dict = None,
                 stderr=subprocess.DEVNULL) -> tuple:
    """-> (Popen, grpc_port, metrics_port); waits for the ready line."""
    p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_worker.py")],
        env=worker_env(host_id, fleet_role, peers, faults, extra),
        cwd=REPO, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=stderr, text=True,
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


def member_row(port: int, host: str) -> dict:
    for m in fetch_json(port, "/fleet/members")["members"]:
        if m.get("host") == host:
            return m
    return {}


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


def stream_infer(grpc_port: int, task_id: str) -> str:
    """StreamInfer the prompt with the incremental-delta client
    contract -> the joined text."""
    from aios_tpu import rpc, services
    from aios_tpu.proto_gen import runtime_pb2

    channel = rpc.insecure_channel(f"127.0.0.1:{grpc_port}")
    parts = []
    try:
        for chunk in services.AIRuntimeStub(channel).StreamInfer(
            runtime_pb2.InferRequest(
                model=MODEL, prompt=PROMPT, max_tokens=MAX_TOKENS,
                temperature=5e-5, task_id=task_id,
            ),
            timeout=180,
        ):
            if chunk.done:
                break
            parts.append(chunk.text)
        return "".join(parts)
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


def counter_any(metrics_text: str, name: str, **labels) -> float:
    """Sum of every sample whose labels INCLUDE the given subset —
    for families keyed by ephemeral ports (the announce peer label)."""
    want = {k: str(v) for k, v in labels.items()}
    total = 0.0
    for line in metrics_text.splitlines():
        m = re.match(rf"^{re.escape(name)}\{{([^}}]*)\}} (\S+)$", line)
        if m:
            got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
            if all(got.get(k) == v for k, v in want.items()):
                total += float(m.group(2))
    return total


def breaker_gauge(metrics_a: int, peer: str) -> float:
    return counter(
        fetch_text(metrics_a, "/metrics"),
        "aios_tpu_fleet_peer_breaker_state_total",
        host="hostA", peer=peer,
    )


def run_round(tag: str) -> dict:
    """One full smoke round -> the port-free verdict dict."""
    pa, grpc_a, metrics_a = spawn_worker(
        "hostA", "prefill", faults=FAULTS_A, extra=BREAKER_ENV_A,
    )
    pb = pc = None
    b_errlog = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".hostB.stderr", delete=False,
    )
    try:
        # -- act 1: solo references (the no_peer route, twice). The
        # streamed reference is collected with the SAME incremental
        # client as the drain act — unary and streamed detokenization
        # may legitimately resegment differently ---------------------
        ref = infer(grpc_a, "partition-smoke-ref")
        ref_s = stream_infer(grpc_a, "partition-smoke-ref-stream")
        log(f"[{tag}] solo references: unary={len(ref)} chars, "
            f"streamed={len(ref_s)} chars")

        # -- act 2: C (control) first, then B (its fault window starts
        # counting the moment its announce loop does) --------------------
        pc, _, _ = spawn_worker(
            "hostC", "decode", peers=f"127.0.0.1:{metrics_a}",
        )
        poll(
            lambda: member_row(metrics_a, "hostC").get("state") == "up"
            and member_row(metrics_a, "hostC").get("kvx_addr"),
            "hostC up with kvx_addr on A", 30 * SCALE,
        )
        pb, _, metrics_b = spawn_worker(
            "hostB", "decode", peers=f"127.0.0.1:{metrics_a}",
            faults=FAULTS_B, stderr=b_errlog,
        )
        poll(
            lambda: member_row(metrics_a, "hostB").get("state") == "up"
            and member_row(metrics_a, "hostB").get("kvx_addr"),
            "hostB up with kvx_addr on A (the pre-window announce)",
            30 * SCALE,
        )
        log(f"[{tag}] both decode hosts folded into A's table")

        # -- act 3: the asymmetric partition ----------------------------
        poll(
            lambda: member_row(metrics_a, "hostB").get("state")
            == "suspect",
            "A suspecting hostB", 30 * SCALE,
        )
        poll(
            lambda: member_row(metrics_a, "hostB").get("state") == "dead",
            "A declaring hostB dead", 30 * SCALE,
        )
        # the reverse edge is clean: B still sees A up, mid-partition
        asym = member_row(metrics_b, "hostA").get("state") == "up"
        announce_fails = counter_any(
            fetch_text(metrics_a, "/metrics"),
            "aios_tpu_fleet_announce_failures_total",
        )
        poll(
            lambda: member_row(metrics_a, "hostB").get("state") == "up",
            "the window exhausting and A healing hostB", 60 * SCALE,
        )
        partition_fired = counter(
            fetch_text(metrics_b, "/metrics"),
            "aios_tpu_faults_injected_total",
            point="net.partition_oneway", mode="nth",
        )
        log(f"[{tag}] partition arc complete: asym={asym} "
            f"announce_fails={announce_fails} fired={partition_fired}")

        # -- act 4: the severed handoff + quarantine --------------------
        out1 = infer(grpc_a, "partition-smoke-sever")
        sever_fired = counter(
            fetch_text(metrics_a, "/metrics"),
            "aios_tpu_faults_injected_total",
            point="net.drop_after", mode="nth",
        )
        quarantined = breaker_gauge(metrics_a, "hostB")
        log(f"[{tag}] severed stream done: sever_fired={sever_fired} "
            f"breaker(hostB)={quarantined}")

        # -- act 5: federation scrapes are the half-open probes ---------
        def breaker_closed():
            fetch_text(metrics_a, "/metrics/fleet")  # drives the scrape
            return breaker_gauge(metrics_a, "hostB") == 0.0

        poll(breaker_closed, "the breaker healing through probes",
             30 * SCALE)
        control_gauge = breaker_gauge(metrics_a, "hostC")
        log(f"[{tag}] quarantine healed; control breaker(hostC)="
            f"{control_gauge}")

        # -- act 6: graceful drain under a LIVE stream. The watcher
        # thread fires fleetctl the moment A's route counter shows the
        # second handoff established (the stream is live ON hostB),
        # well inside the ~15-token decode window -----------------------
        fleetctl = {}

        def drain_watcher():
            deadline = time.monotonic() + 60 * SCALE
            while time.monotonic() < deadline:
                v = counter(
                    fetch_text(metrics_a, "/metrics"),
                    "aios_tpu_fleet_route_total",
                    model=MODEL, reason="handoff",
                )
                if v >= 2.0:
                    fleetctl["proc"] = subprocess.Popen(
                        [
                            sys.executable,
                            os.path.join(REPO, "scripts", "fleetctl.py"),
                            "drain", "--target",
                            f"127.0.0.1:{metrics_a}",
                            "--host", "hostB",
                            "--timeout", str(30 * SCALE), "--json",
                        ],
                        cwd=REPO, stdout=subprocess.PIPE,
                        stderr=subprocess.DEVNULL, text=True,
                    )
                    return
                time.sleep(0.05 * SCALE)

        watcher = threading.Thread(target=drain_watcher, daemon=True)
        watcher.start()
        out2 = stream_infer(grpc_a, "partition-smoke-drain")
        watcher.join(timeout=60 * SCALE)
        ctl = fleetctl.get("proc")
        if ctl is None:
            raise RuntimeError(
                "the drain never started: the second handoff was never "
                "observed on the route counter"
            )
        b_status = pb.wait(timeout=60 * SCALE)
        pb = None
        ctl_out, _ = ctl.communicate(timeout=60 * SCALE)
        ctl_verdict = json.loads(ctl_out.strip().splitlines()[-1])
        b_phase = member_row(metrics_a, "hostB").get("phase")
        b_errlog.flush()
        with open(b_errlog.name) as f:
            m = re.search(r"drain push moved (\d+)/(\d+)", f.read())
        drain_pushed = int(m.group(1)) if m else -1
        log(f"[{tag}] drain done: b_exit={b_status} "
            f"fleetctl_exit={ctl.returncode} phase={b_phase} "
            f"pushed={drain_pushed}")

        # -- the verdict ------------------------------------------------
        metrics = fetch_text(metrics_a, "/metrics")
        routes = {
            reason: counter(
                metrics, "aios_tpu_fleet_route_total",
                model=MODEL, reason=reason,
            )
            for reason in ("no_peer", "handoff", "handoff_resume",
                           "fallback_local")
        }
        verdict = {
            "text1_matches": out1 == ref,
            "text2_matches": out2 == ref_s,
            "text_len": len(ref),
            "stream_len": len(ref_s),
            "routes": routes,
            "asym_b_saw_a_up": asym,
            "announce_failures_counted": announce_fails > 0,
            "partition_fired": partition_fired > 0,
            "sever_fired": sever_fired,
            "quarantine_entered": quarantined == 1.0,
            "control_breaker_untouched": control_gauge == 0.0,
            "b_exit": b_status,
            "fleetctl_exit": ctl.returncode,
            "fleetctl_pass": bool(ctl_verdict.get("pass")),
            "b_phase_leaving": b_phase == "leaving",
            "drain_pushed_pages": drain_pushed,
        }
        verdict["pass"] = (
            verdict["text1_matches"] and verdict["text2_matches"]
            and routes["no_peer"] == 2.0
            and routes["handoff"] == 2.0
            and routes["handoff_resume"] == 2.0
            and routes["fallback_local"] == 0.0
            and verdict["asym_b_saw_a_up"]
            and verdict["announce_failures_counted"]
            and verdict["partition_fired"]
            and sever_fired == 1.0
            and verdict["quarantine_entered"]
            and verdict["control_breaker_untouched"]
            and b_status == 0
            and ctl.returncode == 0
            and verdict["fleetctl_pass"]
            and verdict["b_phase_leaving"]
            and drain_pushed > 0
        )
        if not verdict["pass"]:
            log(f"[{tag}] FAIL detail: ref={ref!r} out1={out1!r} "
                f"out2={out2!r}")
        return verdict
    finally:
        for p in (pa, pb, pc):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        b_errlog.close()
        try:
            os.unlink(b_errlog.name)
        except OSError:
            pass


def main() -> int:
    rounds = [run_round("round1"), run_round("round2")]
    identical = rounds[0] == rounds[1]
    verdict = {
        "smoke": "partition",
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
