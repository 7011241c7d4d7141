"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so every sharding path (TP/DP/SP)
is exercised without TPU hardware. Env vars must be set before the first
`import jax` anywhere in the test process, which is why this lives at the
top of conftest.
"""

import os

# The test suite runs on a virtual 8-device CPU mesh whatever the session
# env says; JAX_PLATFORMS=cpu is also what tells aios_tpu.backend that the
# CPU is intended (jnp references serve). The chip is reached only through
# the chip tool (python chip_smoke.py). Set before backends initialize.
os.environ["JAX_PLATFORMS"] = "cpu"

# Dynamic lock-order verification: every declared serving-plane lock
# (aios_tpu/analysis/registry.py) becomes a named, order-checking
# DebugLock, so the e2e tests double as deadlock detection — an AB/BA
# acquisition inversion raises LockOrderError with both stacks instead
# of hanging a run someday. setdefault: AIOS_TPU_LOCK_DEBUG=0 in the
# environment turns it off for A/B timing comparisons.
os.environ.setdefault("AIOS_TPU_LOCK_DEBUG", "1")
# gRPC's C core logs the occasional GOAWAY at INFO straight to stderr,
# which lands in the middle of pytest's progress dots (and the tier-1
# verify command counts those dots per line)
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture()
def tmp_db_path(tmp_path):
    return str(tmp_path / "test.db")


# A test under tests/benchmark/ (a directory BENCHMARK.json lists under
# `paths`: a PR that adds a cell may add files there and edit none) that pins
# the benchmark's LAST cell and its count: true until the next cell is
# appended, and no later PR may correct it there. What it holds of its own
# cell is held on by the later cells' tests (test_bench_mellum2.py).
PINS_THE_LAST_CELL = {
    "test_bench_xing4.py::test_the_cell_is_listed_where_the_long_prompt_cells_are_and_nowhere_else":
        "asserts that xing4-d13-longprompt is the last of 6 cells and its two "
        "metrics the last of per_layer; PR 35 appends a seventh cell and three "
        "metrics, and may not edit files under tests/benchmark/",
    # the driver refused PR 38's seven entries BEFORE mellum2's three (ISSUE 38's
    # way round this pin) as a change to an accepted entry: new entries go last.
    # test_bench_setup.py runs this test's whole body on the list up to its three.
    "test_bench_mellum2.py::test_the_cell_is_listed_where_the_long_prompt_cells_are_and_nowhere_else":
        "asserts that mellum2-d20-mixedlen's three metrics are the last of "
        "per_layer; PR 38 appends seven metrics of set-up after them, and may "
        "neither edit files under tests/benchmark/ nor insert before an entry",
    # a pin of another sort, lost the same way: the latent kernel's and the
    # held experts' four metrics listed for the Pangu cell ALONE. PR 40's cell
    # is the second that runs that kernel over a share of its experts and is
    # appended to their lists; test_bench_ling3.py runs this test's whole body
    # on the lists without the new cell's name.
    "test_bench_pangu.py::test_the_configuration_states_its_share_and_what_it_assumed":
        "asserts that four per-layer metrics list pangu-ultra-ep16-agents32 and "
        "no other cell; PR 40 appends a second latent-attention cell that holds "
        "a share of its experts to their lists, and may not edit files under "
        "tests/benchmark/",
    "test_bench_setup.py::test_the_committed_benchmark_lists_the_seven_beneath_setup_s":
        "asserts that set-up's seven metrics list four cells and no other; PR "
        "40's cell, whose set-up is the longest there is, is appended to their "
        "lists (test_bench_ling3.py runs this test's whole body without it)",
}


def pytest_collection_modifyitems(config, items):
    """Everything not marked slow is the fast commit-gate tier
    (`pytest -m fast` — service plane + runtime surface, <2 min on CPU)."""
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.fast)
        for pinned, why in PINS_THE_LAST_CELL.items():
            if item.nodeid.endswith(pinned):
                item.add_marker(pytest.mark.xfail(reason=why, strict=False))
