"""Worker process for the multi-host e2e test (tests/test_multihost.py).

Each instance is "one host": it joins the process group via the
AIOS_TPU_* env contract, builds the global mesh, runs the cross-host
all-reduce probe, then one sharded train step whose gradient all-reduce
crosses the process boundary. Both ranks must print the identical loss —
that is the proof the data plane spans hosts.

Run: python tests/multihost_worker.py <pid> <nprocs> <coordinator>
(env JAX_PLATFORMS=cpu, 4 virtual devices per process — the test sets
these).
"""

import sys

import numpy as np


def main() -> int:
    pid, n, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    import os

    os.environ["AIOS_TPU_COORDINATOR"] = coord
    os.environ["AIOS_TPU_NUM_PROCESSES"] = str(n)
    os.environ["AIOS_TPU_PROCESS_ID"] = str(pid)

    from aios_tpu.parallel import multihost

    assert multihost.initialize_from_env(), "process group must initialize"

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rank, nprocs, local = multihost.process_info()
    assert (rank, nprocs) == (pid, n)
    assert jax.device_count() == local * n

    mesh = multihost.build_global_mesh(sp=1, tp=2)
    local_dp = local // 2
    assert mesh.shape == {"dp": n * local_dp, "sp": 1, "ep": 1, "tp": 2}, mesh.shape
    # every host must see the same global sum: sum over ranks of
    # (rank+1) * local_dp
    total = multihost.cross_host_allreduce_check(mesh)
    expect = sum((r + 1) * local_dp for r in range(n))
    assert total == expect, (total, expect)

    from aios_tpu.engine import model
    from aios_tpu.engine.config import TINY_TEST
    from aios_tpu.engine.train import make_optimizer, make_train_step
    from aios_tpu.parallel.sharding import ShardingPlan

    plan = ShardingPlan(mesh)
    params = model.init_params(TINY_TEST, jax.random.PRNGKey(0), jnp.float32)
    init_state, train_step = make_train_step(
        TINY_TEST, mesh, optimizer=make_optimizer(1, 10)
    )
    state = init_state(plan.put_params(params))
    B = n * local_dp * 2  # 2 rows per dp shard
    rows = B // n
    rng = np.random.default_rng(0)  # same stream on every rank
    gtok = rng.integers(0, TINY_TEST.vocab_size, (B, 16)).astype(np.int32)
    sh = NamedSharding(mesh, P("dp"))
    batch = {
        "tokens": jax.make_array_from_process_local_data(
            sh, gtok[pid * rows : (pid + 1) * rows]
        ),
        "loss_mask": jax.make_array_from_process_local_data(
            sh, np.ones((rows, 16), np.float32)
        ),
    }
    state, metrics = jax.jit(train_step)(state, batch)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss)
    print(f"WORKER_OK {pid} allreduce={total:.1f} loss={loss:.6f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
