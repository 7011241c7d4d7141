#!/usr/bin/env python3
"""One fleet-smoke member process: a real runtime service on ephemeral
ports with the fleet telemetry plane armed.

Spawned by scripts/fleet_smoke.py (and the slow tier of
tests/test_fleet.py) with the fleet env already set — AIOS_TPU_FLEET,
AIOS_TPU_FLEET_HOST, AIOS_TPU_FLEET_PEERS, the interval/suspect/dead
windows. Loads one synthetic model, binds gRPC and metrics on port 0,
prints ONE ready line

    FLEET_WORKER_READY {"grpc_port": N, "metrics_port": M}

then blocks until stdin closes (the parent's shutdown signal — cleaner
than SIGTERM racing the heartbeat thread) or it is killed (the failure-
detection half of the smoke kills a worker mid-flight on purpose).
"""

import json
import os
import sys

# CPU-only child: keep XLA on the host platform (multihost_worker.py idiom)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = "fleet-smoke"


def main() -> int:
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve

    manager = ModelManager(num_slots=2, warm_compile=False)
    manager.load_model(MODEL, "synthetic://tiny-test", context_length=256)
    server, service, port = serve(
        address="127.0.0.1:0", manager=manager, block=False,
        metrics_port=0,
    )
    print("FLEET_WORKER_READY " + json.dumps({
        "grpc_port": port, "metrics_port": service.metrics_port,
    }), flush=True)
    sys.stdin.read()  # parent closes stdin to shut us down
    server.stop(grace=None)
    if service.metrics_server is not None:
        service.metrics_server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
