"""Multi-host data plane e2e: REAL processes, real TCP collectives.

Two `python tests/multihost_worker.py` children each play one host (4
virtual CPU devices apiece), join the process group through the
AIOS_TPU_COORDINATOR env contract, build the global ("dp","sp","tp") mesh
with dp spanning the hosts, and run (a) the cross-host all-reduce probe
and (b) one sharded train step whose gradient all-reduce crosses the
process boundary — both ranks must report the identical loss. This is the
TPU-native counterpart of the reference's multi-node story, which stops
at gRPC remote execution (cluster.rs / remote_exec.rs) and never shares
model state across nodes; here the collective data plane does
(SURVEY.md section 5 "Distributed communication backend").

CPU collectives run over TCP (gloo) — the same code rides DCN on real
pods, where `jax.distributed.initialize` auto-detects the topology.
"""

import os
import socket
import subprocess
import sys

import pytest

# compile-heavy tier: excluded from the fast commit gate (pytest -m fast)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_group_allreduce_and_train():
    port = _free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO,
    }
    worker = os.path.join(REPO, "tests", "multihost_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), "2", f"127.0.0.1:{port}"],
            env=env,
            cwd=REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    finally:
        # a rank that died early leaves its peer blocked in the coordinator
        # barrier — never leak it past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-2000:]}"
    ok_lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("WORKER_OK")
    ]
    assert len(ok_lines) == 2, outs
    # both ranks must agree on the all-reduce AND the post-all-reduce loss
    results = {line.split(" ", 2)[2] for line in ok_lines}
    assert len(results) == 1, ok_lines
