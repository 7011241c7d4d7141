#!/usr/bin/env python3
"""Boot the five-service stack on the chip and put one goal through it.

    python3 scripts/chip_boot_smoke.py        # on a machine with a TPU
    JAX_PLATFORMS=cpu python3 scripts/chip_boot_smoke.py synthetic://tiny-test 128
                                              # rehearsal of the script

The parent — this script — never imports JAX: a chip belongs to one
process, and that process is the runtime service ``scripts/run-aios.sh``
spawns through the boot supervisor. The script starts the launcher with an
empty ``AIOS_MODEL_DIR``, waits for "aiOS boot complete", loads
``synthetic://mistral-7b`` over gRPC, asks the tools service what hardware
it sees (it must learn it from the runtime), sends one inference through
the gateway and one goal through the orchestrator, then checks that the
runtime is the only process of the tree that imported JAX and tears the
tree down. One JSON line at the end; non-zero exit on any failure.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import psutil

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from aios_tpu import rpc, services  # noqa: E402  (JAX-free)
from aios_tpu.proto_gen import (  # noqa: E402
    api_gateway_pb2, common_pb2, orchestrator_pb2, runtime_pb2, tools_pb2,
)


def jax_importers(root_pid: int) -> list:
    """Command lines of the supervisor's descendants with jaxlib mapped."""
    holders = []
    for proc in psutil.Process(root_pid).children(recursive=True):
        try:
            if any("jaxlib" in m.path for m in proc.memory_maps()):
                holders.append(" ".join(proc.cmdline()))
        except psutil.Error:
            continue  # exited while we looked
    return holders


def main() -> int:
    assert "jax" not in sys.modules
    model_path = sys.argv[1] if len(sys.argv) > 1 else "synthetic://mistral-7b"
    context = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    platform = "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu" else "tpu"
    work = Path(tempfile.mkdtemp(prefix="aios-boot-smoke-"))
    (work / "models").mkdir()
    log_path = work / "supervisor.log"
    env = {**os.environ, "AIOS_AUDIT_DB": str(work / "audit.db")}
    t0 = time.time()
    with open(log_path, "w") as log:
        sup = subprocess.Popen(
            [str(REPO / "scripts" / "run-aios.sh"),
             "--data-dir", str(work / "data"),
             "--model-dir", str(work / "models")],
            stdout=log, stderr=subprocess.STDOUT, env=env,
        )
    result = {"ok": False}
    try:
        while "aiOS boot complete" not in log_path.read_text():
            assert sup.poll() is None, "supervisor died:\n" + log_path.read_text()
            assert time.time() - t0 < 300, "boot timed out:\n" + log_path.read_text()
            time.sleep(1)
        result["boot_seconds"] = round(time.time() - t0, 1)

        def stub(name, cls):
            return cls(rpc.insecure_channel(services.service_address(name)))

        runtime = stub("runtime", services.AIRuntimeStub)
        t1 = time.time()
        status = runtime.LoadModel(runtime_pb2.LoadModelRequest(
            model_name="mistral-7b", model_path=model_path,
            context_length=context,
        ), timeout=900)
        assert status.status == "ready", status
        result["load_seconds"] = round(time.time() - t1, 1)
        health = runtime.HealthCheck(common_pb2.Empty(), timeout=30).details
        result["device"] = {k: health[k] for k in
                            ("platform", "device_kind", "devices")}
        assert health["platform"] == platform, health["platform"]

        hw = stub("tools", services.ToolRegistryStub).Execute(
            tools_pb2.ExecuteRequest(
                tool_name="hw.info", agent_id="system_agent",
                input_json=b"{}", reason="chip boot smoke",
            ), timeout=60)
        assert hw.success, hw.error
        info = json.loads(hw.output_json)
        assert info.get("accelerator_backend") == platform, info
        result["hw_info_accelerators"] = info["accelerators"]

        reply = stub("gateway", services.ApiGatewayStub).Infer(
            api_gateway_pb2.ApiInferRequest(
                prompt="boot smoke status check", max_tokens=32,
                temperature=0.7,
            ), timeout=300)
        result["gateway_infer"] = {"model_used": reply.model_used,
                                   "tokens_used": reply.tokens_used}

        orch = stub("orchestrator", services.OrchestratorStub)
        goal = orch.SubmitGoal(orchestrator_pb2.SubmitGoalRequest(
            description="check disk usage", priority=5), timeout=60)
        deadline = time.time() + 120
        state = "timeout"
        while time.time() < deadline:
            state = orch.GetGoalStatus(
                common_pb2.GoalId(id=goal.id), timeout=30).goal.status
            if state in ("completed", "failed"):
                break
            time.sleep(0.5)
        result["goal_status"] = state
        assert state == "completed", state

        holders = jax_importers(sup.pid)
        result["processes_that_imported_jax"] = holders
        assert len(holders) == 1 \
            and "aios_tpu.runtime.service" in holders[0], holders
        result["ok"] = True
    except BaseException as exc:  # noqa: BLE001 - reported, then re-raised
        result["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        raise
    finally:
        sup.send_signal(signal.SIGTERM)
        try:
            sup.wait(timeout=60)
        except subprocess.TimeoutExpired:
            sup.kill()
        result["supervisor_log_tail"] = log_path.read_text()[-1500:]
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
