"""The device is never chosen by quiet fallback (aios_tpu/backend.py).

One module decides the backend: JAX_PLATFORMS=cpu means the CPU is intended
and the jnp references serve; anything else must come up on a TPU or raise.
These cases cover that decision and what reads it — the compile-cache
placement, the engine's readiness gate, the model manager's mesh / weight
build / replica placement, the JAX-free boot path, and the two entry points
that must refuse a machine without a chip (bench.py, chip_smoke.py).
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from aios_tpu import backend

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def fresh_decision():
    backend.decide.cache_clear()
    yield
    backend.decide.cache_clear()


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, timeout=300,
        cwd=str(REPO), env={**os.environ, "PYTHONPATH": str(REPO), **env},
    )


# -- the decision -----------------------------------------------------------


def test_cpu_is_served_only_when_asked_for(monkeypatch, fresh_decision):
    from aios_tpu import ops

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert backend.decide() == "cpu"
    assert not backend.on_tpu() and not ops.use_pallas()

    # libtpu installed, TPU failed to initialise: JAX falls back to the CPU
    # with a warning — the stack must not follow it there
    backend.decide.cache_clear()
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(backend.BackendError, match="not 'tpu'"):
        backend.decide()
    with pytest.raises(backend.BackendError):
        ops.use_pallas()

    # "cpu,tpu" is not "exactly cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(backend.BackendError):
        backend.decide()


def test_tpu_decision_places_the_compile_cache(monkeypatch, fresh_decision):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert backend.decide() == "tpu"
    fixed = str(REPO / ".jax_cache")
    assert backend.compile_cache_dir() == fixed
    assert updates["jax_compilation_cache_dir"] == fixed
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored

    # placed from outside: no code path sets a directory
    backend.decide.cache_clear()
    updates.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert backend.decide() == "tpu"
    assert backend.compile_cache_dir() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in updates


# -- the readiness gate -------------------------------------------------------


def test_failed_aot_compile_fails_the_load_on_tpu(monkeypatch):
    from aios_tpu.engine import model as model_mod
    from aios_tpu.engine.config import TINY_TEST
    from aios_tpu.engine.engine import TPUEngine

    params = model_mod.init_params(TINY_TEST, jax.random.PRNGKey(0))
    engine = TPUEngine(TINY_TEST, params, num_slots=2, max_context=64)

    class Unlowerable:  # traces, and fails where the kernels lower
        def trace(self, *args):
            return self

        def lower(self):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    try:
        store = {}
        engine._compile_aot("step", store, 1, Unlowerable(), ())
        assert 1 in store  # intended CPU run: lazy first-dispatch compile

        monkeypatch.setattr(backend, "on_tpu", lambda: True)
        with pytest.raises(RuntimeError, match="Mosaic"):
            engine._compile_aot("step", {}, 1, Unlowerable(), ())
    finally:
        engine.close()


def test_autoload_counts_failures_and_the_service_exits_nonzero(
    monkeypatch, tmp_path
):
    from aios_tpu.runtime import service
    from aios_tpu.runtime.model_manager import ModelManager

    (tmp_path / "broken.gguf").write_bytes(b"not a gguf file")
    mgr = ModelManager(num_slots=2, warm_compile=False)
    assert mgr.autoload(str(tmp_path)) == []
    assert list(mgr.autoload_failures) == ["broken"]

    monkeypatch.setenv("AIOS_MODEL_DIR", str(tmp_path))
    monkeypatch.setattr(
        service, "serve", lambda **kw: pytest.fail("served with no model")
    )
    assert service.main() == 1


# -- the model manager ----------------------------------------------------------


def test_mesh_that_cannot_be_honoured_raises(monkeypatch):
    from aios_tpu.runtime.model_manager import _plan_from_env

    monkeypatch.setenv("AIOS_TPU_MESH", "tp=999")
    with pytest.raises(ValueError, match="needs 999 devices"):
        _plan_from_env()
    monkeypatch.setenv("AIOS_TPU_MESH", "bogus")
    with pytest.raises(ValueError, match="malformed"):
        _plan_from_env()
    monkeypatch.setenv("AIOS_TPU_MESH", "tp=1")
    assert _plan_from_env() is None


def test_unknown_devices_get_no_invented_numbers(monkeypatch):
    from aios_tpu.obs import devprof
    from aios_tpu.runtime import model_manager as mm

    monkeypatch.delenv("AIOS_TPU_HBM_GB", raising=False)
    assert mm._chip_hbm_bytes() is None  # host RAM is not budgeted
    monkeypatch.setenv("AIOS_TPU_HBM_GB", "16")
    assert mm._chip_hbm_bytes() == 16e9
    # a TPU that reports no limit is an error, not a v5e
    monkeypatch.delenv("AIOS_TPU_HBM_GB")
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="bytes_limit"):
        mm._chip_hbm_bytes()

    assert devprof.resolve_peaks("TPU v5 lite") == (197e12, 819e9)
    assert devprof.resolve_peaks("TPU v5 litepod") is None  # no neighbours


def _matmul_leaves(params):
    leaves = dict(params["layers"])
    leaves["lm_head"] = params["lm_head"]
    return {k: v for k, v in leaves.items() if not k.endswith("norm")}


@pytest.mark.parametrize("mode,qkey,dtype", [
    ("int8", "q", jnp.int8), ("int4", "q4", jnp.uint8),
])
def test_synthetic_quantized_weights_never_exist_dense(
    monkeypatch, mode, qkey, dtype
):
    from aios_tpu.engine import model as model_mod
    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setattr(
        model_mod, "init_params",
        lambda *a, **k: pytest.fail("built a dense tree for a quantized load"),
    )
    monkeypatch.setattr(
        model_mod, "quantize_params",
        lambda *a, **k: pytest.fail("quantized a dense tree at load"),
    )
    mgr = ModelManager(num_slots=2, warm_compile=False, quantize=mode)
    m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        leaves = _matmul_leaves(m.engine.params)
        assert set(leaves) == {"w_qkv", "wo", "w_gateup", "w_down", "lm_head"}
        for name, leaf in leaves.items():
            assert leaf[qkey].dtype == dtype, name
        assert m.engine.quant_mode == mode
        assert m.engine.step(2).shape[1] == 2
    finally:
        mgr.unload_model("tiny")


def test_synthetic_weights_under_a_plan_are_built_sharded(monkeypatch):
    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setenv("AIOS_TPU_MESH", "dp=2,tp=2")
    for mode in (False, "int8"):
        mgr = ModelManager(num_slots=2, warm_compile=False, quantize=mode)
        _, params, _ = mgr._load_weights("tiny", "synthetic://tiny-test", 0)
        leaf = params["layers"]["wq"]
        leaf = leaf["q"] if mode else leaf
        # born on the mesh, tp-sharded on the output dim: no device ever
        # held the whole leaf
        assert leaf.sharding.spec == mgr.plan.spec_for("layers/wq")
        assert len(leaf.sharding.device_set) == 4
        assert leaf.addressable_shards[0].data.shape[-1] == leaf.shape[-1] // 2


def test_replicas_without_a_plan_get_a_device_each(monkeypatch, cpu_devices):
    from aios_tpu.runtime.model_manager import ModelManager

    mgr = ModelManager(num_slots=2, warm_compile=False)
    assert mgr._replica_devices(4, shared=False) == list(cpu_devices[:4])
    assert mgr._replica_devices(1, shared=False) == [None]
    assert mgr._replica_devices(4, shared=True) == [None] * 4  # one draft
    assert mgr._replica_devices(99, shared=False) == [None] * 99

    monkeypatch.setenv("AIOS_TPU_REPLICAS", "2")
    m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        homes = []
        for r in m.pool.replicas:
            placed = {
                d for leaf in jax.tree.leaves((r.engine.params, r.engine.state))
                for d in leaf.devices()
            }
            assert len(placed) == 1
            homes.append(placed.pop())
        assert homes == list(cpu_devices[:2])
    finally:
        mgr.unload_model("tiny")


# -- one process per chip -----------------------------------------------------


def test_boot_path_and_hw_info_never_import_jax():
    code = (
        "import sys\n"
        "import aios_tpu.boot.supervisor as sup\n"
        "from aios_tpu.boot.config import AiosConfig\n"
        "from aios_tpu.boot.hardware import detect\n"
        "from aios_tpu.tools.handlers.system import hw_info\n"
        "sup.default_services(AiosConfig()); detect()\n"
        "info = hw_info({})\n"
        "assert info['accelerators'] == [], info\n"
        "assert 'unreachable' in info['accelerator_error'], info\n"
        "assert 'jax' not in sys.modules, 'the boot path imported jax'\n"
    )
    # no runtime listens here: hw_info must say so, not open the device
    r = _run(["-c", code], AIOS_RUNTIME_ADDR="127.0.0.1:1")
    assert r.returncode == 0, r.stderr[-2000:]


def test_hw_info_reads_the_device_from_the_runtime(monkeypatch):
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve
    from aios_tpu.tools.handlers.system import hw_info

    server, _, port = serve(
        address="127.0.0.1:0",
        manager=ModelManager(num_slots=2, warm_compile=False), block=False,
    )
    try:
        monkeypatch.setenv("AIOS_RUNTIME_ADDR", f"127.0.0.1:{port}")
        info = hw_info({})
        assert info["accelerator_backend"] == "cpu"
        assert info["accelerator_kind"] == jax.devices()[0].device_kind
        assert len(info["accelerators"]) == len(jax.devices())
    finally:
        server.stop(grace=None)


# -- entry points that need the chip ------------------------------------------


def test_bench_refuses_a_machine_without_a_tpu():
    src = (REPO / "bench.py").read_text()
    assert "AIOS_BENCH_PROBE" not in src
    assert "subprocess" not in src  # no child may touch JAX
    r = _run(["bench.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "BackendError" in r.stderr
    assert r.stdout.strip() == ""  # no zero line passed off as a result


def test_chip_smoke_fails_without_a_chip_and_prints_no_result():
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
