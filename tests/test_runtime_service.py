"""AIRuntime service: RPC surface, routing ladders, error codes, streaming.

Mirrors the reference's runtime service tests (grpc_service.rs:240-336 test
the Unavailable/InvalidArgument/FailedPrecondition paths by direct handler
invocation) but goes over a live localhost socket with a real tiny engine.
"""

import grpc
import pytest

from aios_tpu import rpc, services
from aios_tpu.proto_gen import common_pb2, runtime_pb2
from aios_tpu.runtime.model_manager import ModelManager
from aios_tpu.runtime.service import RuntimeService, serve


@pytest.fixture(scope="module")
def runtime_stub():
    manager = ModelManager(num_slots=2, warm_compile=False)
    server, service, port = serve(address="127.0.0.1:0", manager=manager, block=False)
    channel = rpc.insecure_channel(f"127.0.0.1:{port}")
    yield services.AIRuntimeStub(channel), manager
    channel.close()
    server.stop(grace=None)


@pytest.fixture()
def loaded_stub(runtime_stub):
    """``runtime_stub`` with ``tinyllama-test`` loaded, whichever worker runs
    the test: xdist's ``--dist load`` may hand this module's tests to several
    workers, each with its own module fixture, and a test that leaned on
    ``test_load_model_and_infer`` having run before it then found no model
    (4 failures in one of PR 44's whole runs). Loading a loaded model again
    returns the one that is there."""
    stub, _ = runtime_stub
    status = stub.LoadModel(
        runtime_pb2.LoadModelRequest(
            model_name="tinyllama-test", model_path="synthetic://tiny-test"
        )
    )
    assert status.status == "ready"
    return runtime_stub


def test_no_models_unavailable(runtime_stub):
    stub, _ = runtime_stub
    with pytest.raises(grpc.RpcError) as err:
        stub.Infer(runtime_pb2.InferRequest(prompt="hi"))
    assert err.value.code() == grpc.StatusCode.UNAVAILABLE


def test_reactive_level_rejected(runtime_stub):
    stub, _ = runtime_stub
    with pytest.raises(grpc.RpcError) as err:
        stub.Infer(
            runtime_pb2.InferRequest(prompt="hi", intelligence_level="reactive")
        )
    assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_load_model_and_infer(runtime_stub):
    stub, _ = runtime_stub
    status = stub.LoadModel(
        runtime_pb2.LoadModelRequest(
            model_name="tinyllama-test", model_path="synthetic://tiny-test"
        )
    )
    assert status.status == "ready"
    assert status.port == 0  # no HTTP sidecar on the TPU backend

    resp = stub.Infer(
        runtime_pb2.InferRequest(prompt="hello", max_tokens=8, temperature=0.0)
    )
    assert resp.model_used == "tinyllama-test"
    assert resp.tokens_used > 0
    assert resp.latency_ms >= 0

    models = stub.ListModels(common_pb2.Empty())
    assert [m.model_name for m in models.models] == ["tinyllama-test"]
    assert models.models[0].request_count >= 1


def test_operational_level_routes_to_tinyllama(loaded_stub):
    stub, _ = loaded_stub
    resp = stub.Infer(
        runtime_pb2.InferRequest(
            prompt="status?", intelligence_level="operational", max_tokens=4
        )
    )
    assert resp.model_used == "tinyllama-test"


def test_strategic_without_big_model_failed_precondition(runtime_stub):
    stub, _ = runtime_stub
    with pytest.raises(grpc.RpcError) as err:
        stub.Infer(
            runtime_pb2.InferRequest(
                prompt="plan", intelligence_level="strategic", max_tokens=4
            )
        )
    assert err.value.code() == grpc.StatusCode.FAILED_PRECONDITION
    assert "api-gateway" in err.value.details()


def test_explicit_unknown_model_not_found(runtime_stub):
    stub, _ = runtime_stub
    with pytest.raises(grpc.RpcError) as err:
        stub.Infer(runtime_pb2.InferRequest(prompt="x", model="nonexistent-13b"))
    assert err.value.code() == grpc.StatusCode.NOT_FOUND


def test_partial_name_matching(loaded_stub):
    stub, _ = loaded_stub
    resp = stub.Infer(
        runtime_pb2.InferRequest(prompt="x", model="TinyLlama", max_tokens=4)
    )
    assert resp.model_used == "tinyllama-test"


def test_stream_infer_token_by_token(loaded_stub):
    stub, _ = loaded_stub
    chunks = list(
        stub.StreamInfer(
            runtime_pb2.InferRequest(prompt="hello", max_tokens=6, temperature=0.0)
        )
    )
    assert chunks[-1].done
    assert all(not c.done for c in chunks[:-1])
    # genuinely incremental: more than one content chunk
    assert len(chunks) >= 2


def test_health_reports_models(loaded_stub):
    stub, _ = loaded_stub
    h = stub.HealthCheck(common_pb2.Empty())
    assert h.healthy
    assert h.details["backend"] == "jax-tpu"
    assert h.details["tinyllama-test"] == "ready"
    # serving counters ride the details map (additive observability)
    serving = h.details["tinyllama-test.serving"]
    assert "decode_steps=" in serving
    assert "completed=" in serving


def test_unload_model(runtime_stub):
    stub, manager = runtime_stub
    stub.LoadModel(
        runtime_pb2.LoadModelRequest(
            model_name="scratch", model_path="synthetic://tiny-test"
        )
    )
    out = stub.UnloadModel(runtime_pb2.UnloadModelRequest(model_name="scratch"))
    assert out.success
    out2 = stub.UnloadModel(runtime_pb2.UnloadModelRequest(model_name="scratch"))
    assert not out2.success
    assert manager.get("scratch") is None


def test_load_error_returns_internal(runtime_stub):
    stub, _ = runtime_stub
    with pytest.raises(grpc.RpcError) as err:
        stub.LoadModel(
            runtime_pb2.LoadModelRequest(
                model_name="bad", model_path="/nonexistent/file.gguf"
            )
        )
    assert err.value.code() == grpc.StatusCode.INTERNAL


def test_paged_auto_sizes_pool_from_slots_and_context(monkeypatch):
    """AIOS_TPU_PAGED_KV=auto (the production boot default) serves over a
    paged pool sized (num_slots + 1) x context with the prefix index on —
    the dense cache's HBM plus one slot of prefix-retention slack."""
    monkeypatch.setenv("AIOS_TPU_PAGED_KV", "auto")
    from aios_tpu.runtime.model_manager import ModelManager

    mgr = ModelManager(num_slots=2, warm_compile=False)
    assert mgr.paged_pool_rows == "auto"
    m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        eng = m.engine
        assert eng.paged
        assert eng.prefix_index is not None
        rows = (2 + 1) * 128
        # pool pages = 1 sacrificial + rows/page_size (page_size 128)
        assert eng.allocator.num_pages == 1 + rows // 128
    finally:
        mgr.unload_model("tiny")


def test_mesh_env_builds_sharding_plan(monkeypatch):
    """AIOS_TPU_MESH (the [models] mesh boot knob) gives the production
    runtime a multi-chip plan (a spec that cannot be honoured raises:
    tests/test_backend.py)."""
    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setenv("AIOS_TPU_MESH", "dp=2,tp=2")
    mgr = ModelManager(num_slots=2, warm_compile=False)
    assert mgr.plan is not None
    assert mgr.plan.dp == 2 and mgr.plan.tp == 2 and mgr.plan.sp == 1
    m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        assert m.state == "ready"
        assert m.engine.step(2).shape[1] == 2
    finally:
        mgr.unload_model("tiny")

    monkeypatch.setenv("AIOS_TPU_MESH", "tp=1")
    assert ModelManager(num_slots=2, warm_compile=False).plan is None


def test_long_context_auto_degrades_to_seq_sharded(monkeypatch):
    """With sp > 1 in the mesh, a model whose KV cache exceeds the
    per-chip HBM budget automatically gives up the paged pool and shards
    its context axis over sp (VERDICT r4 item 7's graceful path) — while a
    model that fits keeps paging."""
    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setenv("AIOS_TPU_MESH", "sp=2")
    monkeypatch.setenv("AIOS_TPU_PAGED_KV", "auto")

    # tiny budget: even the tiny-test cache overflows -> seq-sharded
    monkeypatch.setenv("AIOS_TPU_HBM_GB", "0.000001")
    mgr = ModelManager(num_slots=2, warm_compile=False)
    assert mgr.plan is not None and mgr.plan.sp == 2
    m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        assert m.engine.seq_sharded and not m.engine.paged
        assert m.state == "ready"
        assert m.engine.step(2).shape[1] == 2
    finally:
        mgr.unload_model("tiny")

    # ample budget: paging is kept — the pool replicates over the unused
    # sp axis and decode still executes
    monkeypatch.setenv("AIOS_TPU_HBM_GB", "16")
    mgr2 = ModelManager(num_slots=2, warm_compile=False)
    m2 = mgr2.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        assert m2.engine.paged and not m2.engine.seq_sharded
        assert m2.state == "ready"
        assert m2.engine.step(2).shape[1] == 2
    finally:
        mgr2.unload_model("tiny")


def test_hbm_budget_counts_co_resident_models(monkeypatch):
    """The auto-degrade budget charges models already resident in the
    manager: with a budget sized for ~one model, the first keeps its paged
    pool and the second (identical) model degrades to the seq-sharded
    cache instead of overflowing HBM."""
    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setenv("AIOS_TPU_MESH", "sp=2")
    monkeypatch.setenv("AIOS_TPU_PAGED_KV", "auto")
    monkeypatch.setenv("AIOS_TPU_HBM_GB", "16")  # ample: measure footprint
    probe = ModelManager(num_slots=2, warm_compile=False)
    ma = probe.load_model("a", "synthetic://tiny-test", context_length=128)
    footprint = ma.hbm_chip_bytes
    assert footprint > 0
    probe.unload_model("a")

    # budget ~= 2x one model's footprint minus a sliver: model A fits
    # paged; model B's KV no longer does once A is counted
    monkeypatch.setenv(
        "AIOS_TPU_HBM_GB", str((2 * footprint - 1) / 0.85 / 1e9)
    )
    mgr = ModelManager(num_slots=2, warm_compile=False)
    a = mgr.load_model("a", "synthetic://tiny-test", context_length=128)
    b = mgr.load_model("b", "synthetic://tiny-test", context_length=128)
    try:
        assert a.engine.paged and not a.engine.seq_sharded
        assert b.engine.seq_sharded and not b.engine.paged
    finally:
        mgr.unload_model("a")
        mgr.unload_model("b")


def test_seq_shard_force_wins_over_paging(monkeypatch):
    """An explicit AIOS_TPU_SEQ_SHARD_KV=1 drops the default paged pool
    and shards the context axis (the operator's force outranks the paging
    default — they are exclusive on one engine)."""
    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setenv("AIOS_TPU_MESH", "sp=2")
    monkeypatch.setenv("AIOS_TPU_PAGED_KV", "auto")
    monkeypatch.setenv("AIOS_TPU_SEQ_SHARD_KV", "1")
    monkeypatch.setenv("AIOS_TPU_HBM_GB", "16")
    mgr = ModelManager(num_slots=2, warm_compile=False)
    m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        assert m.engine.seq_sharded and not m.engine.paged
    finally:
        mgr.unload_model("tiny")


def test_hbm_shortfall_warns_without_sp_axis(monkeypatch, caplog):
    """A KV cache that cannot fit per-chip HBM on a mesh with no sp axis
    (or a single chip) still WARNS at load, so the first symptom is not a
    serve-time OOM."""
    import logging

    from aios_tpu.runtime.model_manager import ModelManager

    monkeypatch.setenv("AIOS_TPU_HBM_GB", "0.000001")
    monkeypatch.delenv("AIOS_TPU_MESH", raising=False)
    mgr = ModelManager(num_slots=2, warm_compile=False)
    with caplog.at_level(logging.WARNING, logger="aios.runtime.models"):
        m = mgr.load_model("tiny", "synthetic://tiny-test", context_length=128)
    try:
        assert not m.engine.seq_sharded  # nothing to degrade onto
        assert any(
            "seq-sharded degradation is unavailable" in r.message
            for r in caplog.records
        )
    finally:
        mgr.unload_model("tiny")
