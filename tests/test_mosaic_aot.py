"""Chipless Mosaic compilation tests for every Pallas kernel.

Interpret-mode parity (test_ops.py etc.) validates kernel MATH but not what
the real Mosaic compiler accepts — r3 proof: the int8-KV ragged kernel
family passed interpret mode yet failed on hardware, because Mosaic rejects
DMA-slicing a <128 lane extent (the per-(row, kv-head) scale arrays had the
tiny head count on lanes). These tests close that gap without needing a
chip: libtpu's AOT compiler builds each kernel against a v5e topology
description, so a Mosaic-invalid layout fails in CI the way it would fail
in serving.

The Mistral-7B cases at the bottom are the gate to run BEFORE any chip call
that touches ``aios_tpu/ops/`` or ``engine/model.py`` (docs/TESTING.md): the
attention kernels at the geometry chip_smoke.py serves (H=32, KH=8, D=128,
window 4096, with and without the window+sink operands) and the composed
prefill / chunk / paged-decode graphs on int8 and int4 weights, each of
which must also FIT — a graph that holds a second copy of the page pool
compiles on a big host and dies on a 16 GB chip.

Skips cleanly when no libtpu is importable (non-TPU dev machines).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# compile-heavy tier: excluded from the fast commit gate (pytest -m fast)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def rep_sharding(request):
    # skip ONLY when libtpu itself is absent (non-TPU dev machine); any
    # other failure to build the topology is a real regression of this
    # module's CI gate and must fail loudly
    try:
        import libtpu  # noqa: F401
    except ImportError:
        pytest.skip("libtpu not installed — no Mosaic AOT compiler here")

    # libtpu wants these before its first init; restore after the module
    # so the fake 4-chip topology can't leak into later tests that might
    # initialize a real TPU backend in this process
    mp = pytest.MonkeyPatch()
    request.addfinalizer(mp.undo)
    if "TPU_ACCELERATOR_TYPE" not in os.environ:
        mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    if "TPU_WORKER_HOSTNAMES" not in os.environ:
        mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")

    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2x1"
    )
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1), ("x",))
    return NamedSharding(mesh, PartitionSpec())


def aot_compile(rep, fn, *args, **static):
    f = jax.jit(
        functools.partial(fn, **static) if static else fn,
        in_shardings=(rep,) * len(args),
        out_shardings=rep,
    )
    f.trace(*args).lower().compile()  # raises on Mosaic rejection


# TinyLlama-shaped decode geometry (the shapes that caught the r3 bug)
B, H, KH, D, C = 8, 32, 4, 64, 4096


def test_aot_flash_attention(rep_sharding):
    from aios_tpu import ops

    T = 512
    q = jnp.ones((2, T, H, D), jnp.bfloat16)
    kv = jnp.ones((2, T, KH, D), jnp.bfloat16)
    aot_compile(rep_sharding, ops.flash_attention, q, kv, kv, causal=True)


def test_aot_quantized_matmul(rep_sharding):
    from aios_tpu import ops

    x = jnp.ones((8, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 5632), jnp.int8)
    s = jnp.ones((1, 5632), jnp.float32)
    aot_compile(rep_sharding, ops.quantized_matmul, x, w, s)


@pytest.mark.parametrize(
    "K,N",
    [
        (4096, 6144), (14336, 4096), (4096, 32000),
        # Mistral-7B TP-4 shard geometries (ShardingPlan.int4_matmul_impl
        # runs the kernel per device on these): col shards [K, N/4] for
        # wq / wk+wv / w_gate+w_up, row shards [K/4, N] for wo / w_down.
        # (lm_head's 32000/4 = 8000 is not 128-aligned — quantize_params'
        # tp-aware eligibility keeps that leaf int8, so no AOT case.)
        (4096, 1024), (4096, 256), (4096, 3584),
        (1024, 4096), (3584, 4096),
    ],
)
def test_aot_int4_matmul(rep_sharding, K, N):
    from aios_tpu.ops.int4_matmul import GROUP, int4_matmul

    x = jnp.ones((8, K), jnp.bfloat16)
    p = jnp.ones((K // 2, N), jnp.uint8)
    s = jnp.ones((K // GROUP, 1, N), jnp.float32)
    aot_compile(rep_sharding, int4_matmul, x, p, s)


def test_aot_ragged_decode_bf16(rep_sharding):
    from aios_tpu import ops

    q = jnp.ones((B, H, D), jnp.bfloat16)
    kc = jnp.ones((B, C, KH, D), jnp.bfloat16)
    lens = jnp.ones((B,), jnp.int32)
    aot_compile(rep_sharding, ops.decode_attention, q, kc, kc, lens)


def test_aot_ragged_decode_int8(rep_sharding):
    """The kernel that failed real Mosaic in r3 (scale lane layout)."""
    from aios_tpu import ops

    q = jnp.ones((B, H, D), jnp.bfloat16)
    kq = jnp.ones((B, C, KH, D), jnp.int8)
    ks = jnp.ones((B, C, KH), jnp.float32)
    lens = jnp.ones((B,), jnp.int32)
    aot_compile(
        rep_sharding, ops.decode_attention_int8, q, kq, kq, ks, ks, lens
    )


def test_aot_paged_decode_both_dtypes(rep_sharding):
    """The stacked-pool kernel at TinyLlama's geometry: head_dim 64 is a
    half-vreg lane slice of the stored [L, N, P, KH*D] page, which Mosaic
    has to take (a shipped tier serves through this kernel)."""
    from aios_tpu import ops

    L_, N_, P = 2, 64, 128
    q = jnp.ones((B, H, D), jnp.bfloat16)
    tbl = jnp.zeros((B, 32), jnp.int32)
    lens = jnp.ones((B,), jnp.int32)
    lyr = jnp.ones((), jnp.int32)
    kp = jnp.ones((L_, N_, P, KH * D), jnp.bfloat16)
    aot_compile(
        rep_sharding, ops.paged_decode_attention, q, kp, kp, lyr, tbl, lens
    )
    kq = jnp.ones((L_, N_, P, KH * D), jnp.int8)
    ps = jnp.ones((L_, N_, P, KH), jnp.float32)
    aot_compile(
        rep_sharding, ops.paged_decode_attention_int8,
        q, kq, kq, ps, ps, lyr, tbl, lens,
    )


def test_aot_multiquery_verify_both_dtypes(rep_sharding):
    from aios_tpu import ops

    T = 4
    qt = jnp.ones((B, T, H, D), jnp.bfloat16)
    lens = jnp.ones((B,), jnp.int32)
    strides = jnp.ones((B,), jnp.int32)
    kc = jnp.ones((B, C, KH, D), jnp.bfloat16)
    aot_compile(
        rep_sharding, ops.multiquery_decode_attention,
        qt, kc, kc, lens, strides,
    )
    kq = jnp.ones((B, C, KH, D), jnp.int8)
    ks = jnp.ones((B, C, KH), jnp.float32)
    aot_compile(
        rep_sharding, ops.multiquery_decode_attention_int8,
        qt, kq, kq, ks, ks, lens, strides,
    )


# ---------------------------------------------------------------------------
# Composed serving graphs — the exact jit units bench.py dispatches
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_aot_decode_step_int8_kv_ragged(rep_sharding, monkeypatch):
    """TinyLlama decode step with int8 KV + the ragged kernel family —
    the A/B arm that failed on hardware in r3."""
    monkeypatch.setenv("AIOS_TPU_INT8_RAGGED", "1")
    from aios_tpu.engine import model as M
    from aios_tpu.engine.config import TINYLLAMA_1_1B

    cfg = TINYLLAMA_1_1B
    params = M.init_quantized_params(cfg, jax.random.PRNGKey(0))
    k, v = M.init_kv_cache(cfg, 8, 4096, jnp.int8)
    ks, vs = M.init_kv_scales(cfg, 8, 4096)
    toks = jnp.ones((8,), jnp.int32)
    lens = jnp.ones((8,), jnp.int32)

    def step(params, toks, lens, k, v, ks, vs):
        return M.decode_step(params, cfg, toks, lens, k, v, kernels=True,
                             cache_scales=(ks, vs))

    args = (params, toks, lens, k, v, ks, vs)
    sh = jax.tree.map(lambda a: rep_sharding, args)
    jax.jit(step, in_shardings=sh).trace(*args).lower().compile()


@pytest.mark.slow
def test_aot_decode_step_int4_weights(rep_sharding):
    """Mistral-7B decode step on int4 serving weights (headline bench)."""
    from aios_tpu.engine import model as M
    from aios_tpu.engine.config import MISTRAL_7B

    cfg = MISTRAL_7B
    params = M.init_quantized_params(cfg, jax.random.PRNGKey(0), mode="int4")
    k, v = M.init_kv_cache(cfg, 8, 1024, jnp.bfloat16)
    toks = jnp.ones((8,), jnp.int32)
    lens = jnp.ones((8,), jnp.int32)

    def step(params, toks, lens, k, v):
        return M.decode_step(params, cfg, toks, lens, k, v, kernels=True)

    args = (params, toks, lens, k, v)
    sh = jax.tree.map(lambda a: rep_sharding, args)
    jax.jit(step, in_shardings=sh).trace(*args).lower().compile()


# ---------------------------------------------------------------------------
# Mistral-7B geometry — what chip_smoke.py serves (docs/TESTING.md: the gate
# before chip time is spent). Abstract operands only: nothing 7B-sized is
# materialized on the host.
# ---------------------------------------------------------------------------

MB, MH, MKH, MD, MC, MW = 8, 32, 8, 128, 4096, 4096
MP = 128  # page size
MN = 1 + (MB + 1) * MC // MP  # the "auto" pool: (slots + 1) x context rows


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>\(.*?\)|\S+) (?P<op>[\w\-]+)\("
)
# results that are another buffer's bytes under a new name, not work
_HLO_VIEWS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "conditional", "call"}


def _hlo_results(text):
    """(opcode, [element count of each array result]) for every
    instruction of an optimised HLO module that computes something."""
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None or m["op"] in _HLO_VIEWS:
            continue
        yield m["op"], [
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", m["type"])
        ]


def _hlo_copies(text, dtype):
    """Element count of every ``dtype`` array a `copy` of the module makes."""
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is not None and m["op"] == "copy":
            for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", m["type"]):
                if dt == dtype:
                    yield int(np.prod([int(d) for d in dims.split(",") if d]))


def sds(rep, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)


@pytest.mark.parametrize("T", [1024, 4096])
def test_aot_mistral_flash_prefill(rep_sharding, T):
    from aios_tpu import ops

    q = sds(rep_sharding, (1, T, MH, MD), jnp.bfloat16)
    kv = sds(rep_sharding, (1, T, MKH, MD), jnp.bfloat16)
    aot_compile(rep_sharding, ops.flash_attention, q, kv, kv, causal=True,
                window=MW)


def test_aot_mistral_ragged_decode_both_dtypes(rep_sharding):
    from aios_tpu import ops

    q = sds(rep_sharding, (MB, MH, MD), jnp.bfloat16)
    lens = sds(rep_sharding, (MB,), jnp.int32)
    kc = sds(rep_sharding, (MB, MC, MKH, MD), jnp.bfloat16)
    aot_compile(rep_sharding, ops.decode_attention, q, kc, kc, lens,
                window=MW)
    kq = sds(rep_sharding, (MB, MC, MKH, MD), jnp.int8)
    ks = sds(rep_sharding, (MB, MC, MKH), jnp.float32)
    aot_compile(rep_sharding, ops.decode_attention_int8, q, kq, kq, ks, ks,
                lens, window=MW)


ML = 32  # Mistral-7B's layers: the kernel takes the whole stacked pool


@pytest.mark.parametrize("compressed", [False, True])
def test_aot_mistral_paged_decode_both_dtypes(rep_sharding, compressed):
    """window=4096, and the win_starts/sink operands PR 13 added, on the
    stacked [32, 289, 128, ...] pool the layer loop carries."""
    from aios_tpu import ops

    q = sds(rep_sharding, (MB, MH, MD), jnp.bfloat16)
    tbl = sds(rep_sharding, (MB, MC // MP), jnp.int32)
    lens = sds(rep_sharding, (MB,), jnp.int32)
    lyr = sds(rep_sharding, (), jnp.int32)
    kp = sds(rep_sharding, (ML, MN, MP, MKH * MD), jnp.bfloat16)
    kq = sds(rep_sharding, (ML, MN, MP, MKH * MD), jnp.int8)
    ps = sds(rep_sharding, (ML, MN, MP, MKH), jnp.float32)
    if compressed:
        def bf16(q, k, v, i, t, l, ws):
            return ops.paged_decode_attention(
                q, k, v, i, t, l, window=MW, win_starts=ws, sink=MP)

        def int8(q, k, v, ks, vs, i, t, l, ws):
            return ops.paged_decode_attention_int8(
                q, k, v, ks, vs, i, t, l, window=MW, win_starts=ws, sink=MP)

        aot_compile(rep_sharding, bf16, q, kp, kp, lyr, tbl, lens, lens)
        aot_compile(rep_sharding, int8, q, kq, kq, ps, ps, lyr, tbl, lens,
                    lens)
    else:
        aot_compile(rep_sharding, ops.paged_decode_attention, q, kp, kp,
                    lyr, tbl, lens, window=MW)
        aot_compile(rep_sharding, ops.paged_decode_attention_int8, q, kq,
                    kq, ps, ps, lyr, tbl, lens, window=MW)


def test_aot_mistral_multiquery_verify_both_dtypes(rep_sharding):
    from aios_tpu import ops

    qt = sds(rep_sharding, (MB, 8, MH, MD), jnp.bfloat16)
    lens = sds(rep_sharding, (MB,), jnp.int32)
    kc = sds(rep_sharding, (MB, MC, MKH, MD), jnp.bfloat16)
    aot_compile(rep_sharding, ops.multiquery_decode_attention, qt, kc, kc,
                lens, lens, window=MW)
    kq = sds(rep_sharding, (MB, MC, MKH, MD), jnp.int8)
    ks = sds(rep_sharding, (MB, MC, MKH), jnp.float32)
    aot_compile(rep_sharding, ops.multiquery_decode_attention_int8, qt, kq,
                kq, ks, ks, lens, lens, window=MW)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_aot_mistral_serving_graphs_compile_and_fit(
    rep_sharding, monkeypatch, mode
):
    """The composed graphs chip_smoke.py phases 1-2 dispatch — a prefill
    bucket (flash kernel), a chunked-admission chunk and the paged decode
    step — traced as on the chip (kernels on, int8 mixed dot / int4
    kernel), pools donated. Beside compiling, each must leave the v5e's
    HBM room: temporaries stay under ONE page pool, i.e. the layer loop
    updates the pool in place instead of building a second one."""
    from aios_tpu import backend
    from aios_tpu.engine import model as M
    from aios_tpu.engine.config import MISTRAL_7B as cfg

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(
        lambda a: sds(rep, a.shape, a.dtype),
        jax.eval_shape(lambda: M.init_quantized_params(
            cfg, jax.random.PRNGKey(0), mode=mode)),
    )
    leaf = params["layers"]["w_gateup"]
    assert ("q4" if mode == "int4" else "q") in leaf  # the kernel's layout
    pool = sds(rep, (cfg.num_layers, MN, MP, MKH * MD), jnp.bfloat16)
    pool_bytes = 2 * cfg.num_layers * MN * MP * MKH * MD  # one of k / v
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731

    def prefill(p, toks):
        return M.prefill(p, cfg, toks, kernels=True)

    def chunk(p, k, v, toks, start, row):
        return M.prefill_chunk_paged(p, cfg, toks, start, k, v, row)

    def step(p, k, v, toks, lens, tables):
        return M.decode_step_paged(p, cfg, toks, lens, k, v, tables,
                                   kernels=True)

    graphs = {
        "prefill-1024": (prefill, (params, i32(1, 1024)), ()),
        "chunk-512": (chunk, (params, pool, pool, i32(1, 512), i32(),
                              i32(MC // MP)), (1, 2)),
        "decode-step": (step, (params, pool, pool, i32(MB), i32(MB),
                               i32(MB, MC // MP)), (1, 2)),
    }
    for name, (fn, args, donate) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        if name == "decode-step":
            # PR 25: the step reads the layer's pages where they lie in the
            # carried pool. No operation of the compiled graph makes one
            # layer's slice of it (75.8 MB: the copy, reshape and
            # dynamic-slice fusions that took 43 % of the step), and none
            # copies a whole pool
            slice_elems = MN * MP * MKH * MD
            made = [
                (op, res) for op, res in _hlo_results(compiled.as_text())
                if slice_elems in res
                or (op == "copy" and cfg.num_layers * slice_elems in res)
            ]
            assert made == [], f"{name}: pool-sized results {made[:4]}"
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < pool_bytes, (
            f"{name}: {mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries "
            "— a second copy of the page pool?"
        )
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert live < 15.75e9, f"{name}: {live / 1e9:.2f} GB live"


def _bench_model(config_name, arch_file, context):
    """(program ModelConfig, shapes of the benchmark's serving tree) of a
    committed configuration, built as `benchmark/harness/manager.py` does and,
    with latent attention, laid out as the engine lays it at load."""
    import json
    import sys

    from aios_tpu.engine import latent
    from aios_tpu.engine.config import ModelConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness.manifest import load_file

    arch = load_file(os.path.join(root, "benchmark", "archs", arch_file),
                     "benchmark_arch")
    with open(os.path.join(root, "benchmark", "configs", config_name)) as fh:
        config = json.load(fh)
    cfg = ModelConfig(**arch.model_fields(config, context))

    def build():
        params = arch.build_params(arch.dims_of(config), 1)
        return latent.serving_layout(params, cfg)[0] if cfg.mla else params

    return cfg, jax.eval_shape(build)


# --- the latent-attention configuration of the benchmark (PR 27) -----------


def test_aot_latent_serving_graphs_compile_and_fit(rep_sharding, monkeypatch):
    """openPangu-Ultra-MoE as the benchmark cuts it (published widths, 5
    layers, 16 of 256 experts held, 32 slots x 16,384 rows): the latent decode
    kernel alone, then the composed decode step, a mid chunk, the one-page and
    a sub-page final chunk and a whole-prompt prefill, traced as on the chip.
    Each must fit beside the 5.1 GB of weights and the 3.5 GB latent pool,
    and none may copy a whole pool array (the one-page chunk did, through
    the scatter's way: engine/latent.py `_write_chunk`)."""
    from aios_tpu import backend, ops
    from aios_tpu.engine import model as M

    cfg, shapes = _bench_model("openpangu-ultra-moe-int8-ep16-d5.json",
                               "pangu_ultra_moe.py", 16384)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    slots, blocks, pages = 32, 128, 33 * 128 + 1
    pools = tuple(sds(rep, (cfg.num_layers, pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    pool_elems = {cfg.num_layers * pages * 128 * w for w in cfg.kv_row_dims}
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731

    aot_compile(
        rep, ops.paged_mla_decode_attention,
        sds(rep, (slots, cfg.num_heads, 512), jnp.bfloat16),
        sds(rep, (slots, cfg.num_heads, 128), jnp.bfloat16), *pools,
        i32(), i32(slots, blocks), i32(slots), sm_scale=192 ** -0.5,
    )

    def chunk(p, c, r, toks, start, row):
        return M.prefill_chunk_paged(p, cfg, toks, start, c, r, row)

    def step(p, c, r, toks, lens, tables):
        return M.decode_step_paged(p, cfg, toks, lens, c, r, tables, kernels=True)

    graphs = {"decode-step": (step, (params, *pools, i32(slots), i32(slots),
                                     i32(slots, blocks)), (1, 2)),
              "prefill-512": (lambda p, t: M.prefill(p, cfg, t, kernels=True),
                              (params, i32(1, 512)), ())}
    for t in (512, 128, 64):
        graphs[f"chunk-{t}"] = (chunk, (params, *pools, i32(1, t), i32(),
                                        i32(blocks)), (1, 2))
    for name, (fn, args, donate) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        copied = [res for op, res in _hlo_results(compiled.as_text())
                  if op == "copy" and pool_elems & set(res)]
        assert copied == [], f"{name}: copies a whole pool array"
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 1.6e9, (
            f"{name}: {mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries")
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert live < 15.75e9, f"{name}: {live / 1e9:.2f} GB live"


@pytest.mark.parametrize("config_name, arch_file, context, slots", [
    ("openpangu-ultra-moe-int8-ep16-d5.json", "pangu_ultra_moe.py", 16384, 32),
    ("xing4-29b-a4b-int8-d13.json", "xing4.py", 8192, 16),
])
def test_aot_latent_graphs_relay_no_per_head_matrix(
    rep_sharding, monkeypatch, config_name, arch_file, context, slots
):
    """The latent block's per-head matrices lie heads-major from load
    (latent.serving_layout, PR 46): neither the decode dispatch as the engine
    builds it (a scan over `engine.DECODE_STEPS` steps, where a re-laid weight
    is hoisted to one copy of the whole stack) nor a chunk of 64 / 128 / 256 /
    512 rows copies anything with the element count of a layer's, or of a
    stack's, `w_uq` (whole, or its nope or rope part), `w_uk` or `w_uv` (int8
    results alone: a chunk's float32 accumulator `[128,512,128]` has `w_uk`'s
    count). In the checkpoint layout the Pangu dispatch copied `s8[4,1536,24576]`,
    `s8[1,1536,24576]`, `s8[4,512,16384]` and two `s8[512,128,128]` a layer
    step, the Xing4 one `s8[12,768,6144]` and two `s8[12,512,4096]`."""
    from aios_tpu import backend
    from aios_tpu.engine import engine as E
    from aios_tpu.engine import model as M

    cfg, shapes = _bench_model(config_name, arch_file, context)
    assert shapes["layers"]["w_uk"]["q"].dtype == jnp.int8
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    blocks = context // 128
    pages = (slots + 1) * blocks + 1
    pools = tuple(sds(rep, (cfg.num_layers, pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    layer = {cfg.q_lora_rank * H * (dn + dr), cfg.q_lora_rank * H * dn,
             cfg.q_lora_rank * H * dr, cfg.kv_lora_rank * H * dn,
             cfg.kv_lora_rank * H * cfg.v_head_dim}
    depths = {jax.tree.leaves(seg)[0].shape[0] for seg in M.layer_segments(shapes)}
    per_head = {n * elems for n in depths | {1} for elems in layer}

    def dispatch(p, c, r, toks, lens, tables):
        def one(carry, _):
            toks, lens, c, r = carry
            logits, c, r, *_ = M.decode_step_paged(
                p, cfg, toks, lens, c, r, tables, kernels=True)
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (toks, lens + 1, c, r), toks

        (_, _, c, r), toks = jax.lax.scan(
            one, (toks, lens, c, r), None, length=E.DECODE_STEPS)
        return toks, c, r

    def chunk(p, c, r, toks, start, row):
        return M.prefill_chunk_paged(p, cfg, toks, start, c, r, row)

    graphs = {"decode-dispatch": (dispatch, (params, *pools, i32(slots), i32(slots),
                                             i32(slots, blocks)))}
    for t in (64, 128, 256, 512):
        graphs[f"chunk-{t}"] = (chunk, (params, *pools, i32(1, t), i32(),
                                        i32(blocks)))
    for name, (fn, args) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
        copied = [n for n in _hlo_copies(compiled.as_text(), "s8") if n in per_head]
        assert copied == [], f"{name}: re-lays a per-head matrix: {copied[:4]}"


# --- the grouped expert path reads the stacked experts in place (PR 28) -----


@pytest.mark.parametrize("config_name, arch_file, context, pages, chunk", [
    # the 512-token chunk of `mixtral-d6-longprompt`: (8 + 1) x 4096 rows
    ("mixtral-8x7b-int8-d6.json", "mistral.py", 4096, 9 * 32 + 1, 512),
    # the bucket-256 tail prefill of `pangu-ultra-ep16-agents32`
    ("openpangu-ultra-moe-int8-ep16-d5.json", "pangu_ultra_moe.py", 16384,
     33 * 128 + 1, 256),
])
def test_aot_grouped_experts_are_read_in_place(
    rep_sharding, monkeypatch, config_name, arch_file, context, pages, chunk
):
    """A prefill chunk at a token count the grouped expert path serves
    (moe.grouped_pays), at the benchmark's widths: the graph compiles for the
    v5e, fits beside the weights, and makes nothing as large as ONE layer's
    expert stack (1.41 GB at Mixtral's widths, 755 MB at the Pangu share's):
    not in its temporaries, and no operation's result has the element count
    of a layer's gate-up or down stack (the copy a scanned slice of them was,
    `_dynamic-slice_bitcast_fusion s8[16,7680,4096]` in PR 27's trace), nor is
    a whole stack copied."""
    from aios_tpu import backend
    from aios_tpu.engine import model as M
    from aios_tpu.engine import moe

    cfg, shapes = _bench_model(config_name, arch_file, context)
    assert moe.grouped_pays(chunk, cfg)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    pools = tuple(sds(rep, (cfg.num_layers, pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731

    def chunk_graph(p, k, v, toks, start, row):
        return M.prefill_chunk_paged(p, cfg, toks, start, k, v, row)

    compiled = jax.jit(chunk_graph, donate_argnums=(1, 2)).lower(
        params, *pools, i32(1, chunk), i32(), i32(context // 128)
    ).compile()
    stacks = [params["layers"][n]["q"] for n in moe.EXPERT_LEAVES
              if n in params["layers"]]
    layer_bytes = sum(int(np.prod(a.shape[1:])) for a in stacks)
    layer_elems = {int(np.prod(a.shape[1:])) for a in stacks}
    stack_elems = {int(np.prod(a.shape)) for a in stacks}
    made = [
        (op, res) for op, res in _hlo_results(compiled.as_text())
        if layer_elems & set(res) or (op == "copy" and stack_elems & set(res))
    ]
    assert made == [], f"results as large as a layer's experts: {made[:4]}"
    # ONE kernel a layer call (the layer scan's body holds it once), and no
    # tile of 128 rows streams an expert any more
    text = compiled.as_text()
    assert len(re.findall(r"%expert_group[.\d]* = ", text)) == 1
    assert f"bf16[128,{2 * cfg.expert_dim}]" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, (
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries against "
        f"{layer_bytes / 1e9:.2f} GB of experts a layer")
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < 15.75e9, f"{live / 1e9:.2f} GB live"


# --- a prefill chunk streams each touched expert once (PR 37) ---------------


@pytest.mark.parametrize("config_name, arch_file, context", [
    ("mixtral-8x7b-int8-d6.json", "mistral.py", 4096),  # 4096 x 14336
    ("openpangu-ultra-moe-int8-ep16-d5.json", "pangu_ultra_moe.py", 16384),
    ("xing4-29b-a4b-int8-d13.json", "xing4.py", 8192),  # 3584 x 1024
    ("mellum2-12b-a2.5b-int8-d20.json", "mellum.py", 16384),  # 2304 x 896
])
def test_aot_prefill_chunks_stream_each_touched_expert_once(
    rep_sharding, config_name, arch_file, context
):
    """The grouped kernel alone (ops/expert_group.py: a traced grid bound,
    the stacks indexed at ``[l, expert[i]]`` by the block specs, the rows
    copied in and out by the kernel itself) at each MoE configuration's
    widths, for the picks of a 512-token chunk and of a 256-token bucket:
    it compiles for the v5e in seconds (one rolled product of 128 rows, no
    variant by row count), is ONE custom call, and makes nothing the size of
    a layer's experts: its only operands of that size are the stacks
    themselves, handed whole. The composed chunk graphs are
    ``-k grouped_experts`` (Mixtral, the Pangu share), ``-k stream_mixes``
    (xing4) and ``-k two_kinds`` (mellum2, four layers a period)."""
    import time

    from aios_tpu.ops import expert_group as eg

    cfg, shapes = _bench_model(config_name, arch_file, context)
    rep = rep_sharding
    layers = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)["layers"]
    E, F, X, k = (cfg.hidden_size, cfg.expert_dim, cfg.held_experts,
                  cfg.num_experts_per_tok)
    assert eg.supports_pallas(E, F)
    cap = eg.row_cap(E, F)
    assert cap % eg.PASS == 0 and eg.PASS % eg.ROW_BLOCK == 0
    stacks = [layers[n][m] for n in ("we_gateup", "we_down") for m in ("q", "s")]
    layer_elems = {int(np.prod(a.shape[1:])) for a in stacks[::2]}
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731
    for n_tok in (512, 256):
        M = eg.buffer_rows(n_tok * k, X)
        U = X + M // cap
        t0 = time.monotonic()
        compiled = jax.jit(functools.partial(eg.expert_group, cap=cap)).lower(
            sds(rep, (M, E), jnp.bfloat16), i32(U), i32(U), i32(U), i32(), i32(),
            *stacks).compile()
        assert time.monotonic() - t0 < 30, "the kernel's compile is a set-up cost"
        text = compiled.as_text()
        assert len(re.findall(r"%expert_group[.\d]* = ", text)) == 1
        made = [(op, res) for op, res in _hlo_results(text)
                if layer_elems & set(res)]
        assert made == [], f"results as large as a layer's experts: {made[:4]}"
        mem = compiled.memory_analysis()
        # the rows go in and the result comes out as they are: no copy
        assert mem.temp_size_in_bytes < 1 << 20


# --- a decode step visits the experts its live rows picked (PR 32) ----------


@pytest.mark.parametrize("config_name, arch_file, context, slots, pages", [
    ("mixtral-8x7b-int8-d6.json", "mistral.py", 4096, 8, 9 * 32 + 1),
    ("openpangu-ultra-moe-int8-ep16-d5.json", "pangu_ultra_moe.py", 16384, 32,
     33 * 128 + 1),
    ("xing4-29b-a4b-int8-d13.json", "xing4.py", 8192, 16, 17 * 64 + 1),
])
def test_aot_decode_steps_visit_their_experts_in_place(
    rep_sharding, monkeypatch, config_name, arch_file, context, slots, pages
):
    """The visit kernel alone at each MoE configuration's widths and rows
    (ops/expert_visit.py: a traced grid bound, the stacks indexed at
    ``[l, visit[i]]`` by the block specs), then the composed decode step as
    the benchmark serves it: it compiles for the v5e, fits beside the
    weights, holds one visit kernel a layer scan, and makes nothing as large
    as ONE layer's expert stack (the dense path's ``bf16[64,16,2048]``-sized
    results are a layer's experts over every row; a scanned slice of the
    stacks would be a copy of them: PR 27), nor copies a whole stack."""
    from aios_tpu import backend
    from aios_tpu.engine import model as M
    from aios_tpu.engine import moe
    from aios_tpu.ops import expert_visit as ev

    cfg, shapes = _bench_model(config_name, arch_file, context)
    assert moe.visit_serves(cfg)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    layers = params["layers"]
    E, F, X = cfg.hidden_size, cfg.expert_dim, cfg.held_experts
    assert ev.supports_pallas(E, F)
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731
    aot_compile(
        rep, ev.expert_visit, sds(rep, (slots, E), jnp.bfloat16),
        sds(rep, (slots, X), jnp.float32), i32(X), i32(), i32(),
        layers["we_gateup"]["q"], layers["we_gateup"]["s"],
        layers["we_down"]["q"], layers["we_down"]["s"],
    )
    pools = tuple(
        sds(rep, (cfg.num_layers, pages, 128, w), jnp.bfloat16)
        for w in (cfg.kv_row_dims if cfg.mla
                  else (cfg.num_kv_heads * cfg.head_dim,) * 2))

    def step(p, k, v, toks, lens, tables, active):
        return M.decode_step_paged(p, cfg, toks, lens, k, v, tables,
                                   kernels=True, active=active)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, *pools, i32(slots), i32(slots), i32(slots, context // 128),
        sds(rep, (slots,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%expert_visit[.\d]* = ", text)) == 1
    stacks = [layers[n]["q"] for n in ("we_gateup", "we_down")]
    layer_bytes = sum(int(np.prod(a.shape[1:])) for a in stacks)
    # by the dimensions' text: xing4's output head has a layer's gate-up
    # stack's element count (131,072 x 3,584 = 64 x 3,584 x 2,048)
    dims = lambda shape: "[" + ",".join(map(str, shape)) + "]"  # noqa: E731
    layer_dims = [dims(a.shape[1:]) for a in stacks]
    # every expert over every row: the dense path's results
    layer_dims += [dims((X, slots, 2 * F)), dims((X, slots, E))]
    stack_dims = [dims(a.shape) for a in stacks]
    made = []
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None or m["op"] in _HLO_VIEWS:
            continue
        if any(d in m["type"] for d in layer_dims) or (
                m["op"] == "copy" and any(d in m["type"] for d in stack_dims)):
            made.append(line.strip()[:160])
    assert made == [], f"results as large as a layer's experts: {made[:4]}"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, (
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries against "
        f"{layer_bytes / 1e9:.2f} GB of experts a layer")
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < 15.75e9, f"{live / 1e9:.2f} GB live"


# --- the residual of several mixed streams (PR 31) ---------------------------


def test_aot_stream_mixes_compile_and_fit(rep_sharding, monkeypatch):
    """Xing4.0 as the benchmark cuts it (published widths, 13 layers, all 64
    experts, the whole 131k vocabulary, 16 slots x 8,192 rows): the two stream
    kernels alone at a decode step's 16 rows and a chunk's 512, then the
    composed decode step (the latent kernel at 32 heads a slot), a mid and a
    final chunk and the largest whole-prompt prefill with its one-row head,
    each beside 10.5 GB of weights and the 2.3 GB latent pool. Each graph
    runs one `hc_pre` and one `hc_post` a sub-layer in each of its two layer
    scans (and `hc_pre` once more where every row's logits are made), and a
    graph is compiled in seconds (with the Sinkhorn rounds left to XLA the
    decode step's compile did not end in 40 minutes: ops/hyper_connections.py)."""
    import re

    from aios_tpu import backend, ops
    from aios_tpu.engine import model as M
    from aios_tpu.ops import hyper_connections as hck

    cfg, shapes = _bench_model("xing4-29b-a4b-int8-d13.json", "xing4.py", 8192)
    assert cfg.hc and cfg.num_heads == 32 and cfg.held_experts == 64
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    slots, blocks, pages = 16, 64, 17 * 64 + 1
    pools = tuple(sds(rep, (cfg.num_layers, pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731
    W = cfg.hc_mult * cfg.hidden_size
    for rows in (16, 512):
        assert hck.supports_pallas(rows, W, cfg.hc_mult)
        aot_compile(rep, hck.hc_pre, sds(rep, (rows, W), jnp.bfloat16),
                    sds(rep, (24, W), jnp.float32), sds(rep, (24, 2), jnp.float32),
                    n=4, norm_eps=1e-6, hc_eps=1e-6)
        aot_compile(rep, hck.hc_post, sds(rep, (rows, W), jnp.bfloat16),
                    sds(rep, (rows, cfg.hidden_size), jnp.bfloat16),
                    sds(rep, (rows, 20), jnp.float32), n=4)

    def step(p, c, r, toks, lens, tables):
        return M.decode_step_paged(p, cfg, toks, lens, c, r, tables, kernels=True)

    def mid(p, c, r, toks, start, row):
        return M.prefill_chunk_paged(p, cfg, toks, start, c, r, row)[1:]

    def final(p, c, r, toks, start, row, n):
        logits, *rest = M.prefill_chunk_paged(p, cfg, toks, start, c, r, row)
        return (logits[0, n - 1], *rest)

    def prefill(p, c, r, toks, n):
        logits, cs, rs, picks = M.prefill(p, cfg, toks, kernels=True, logit_row=n - 1)
        rows = jnp.arange(toks.shape[1] // 128, dtype=jnp.int32)
        return (logits[0, 0], ops.write_rows(c, None, cs[:, 0, :, 0], rows),
                ops.write_rows(r, None, rs[:, 0, :, 0], rows), picks)

    chunk_args = (params, *pools, i32(1, 512), i32(), i32(blocks))
    graphs = {
        "decode-step": (step, (params, *pools, i32(slots), i32(slots),
                               i32(slots, blocks)), 5),
        "chunk-512": (mid, chunk_args, 4),
        "final-chunk-512": (final, chunk_args + (i32(),), 5),
        "prefill-8192": (prefill, (params, *pools, i32(1, 8192), i32()), 4),
    }
    for name, (fn, args, pre_calls) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
        text = compiled.as_text()
        assert len(re.findall(r"%hc_pre[.\d]* = ", text)) == pre_calls, name
        assert len(re.findall(r"%hc_post[.\d]* = ", text)) == 4, name
        # the expert layers' scan: one grouped kernel a prefill graph (PR 37)
        assert len(re.findall(r"%expert_group[.\d]* = ", text)) == (
            0 if name == "decode-step" else 1), name
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert live < 15.75e9, f"{name}: {live / 1e9:.2f} GB live"


# --- window and full attention layers in one stack (PR 35) -------------------


def test_aot_two_kinds_compile_and_fit(rep_sharding, monkeypatch):
    """Mellum 2 as the benchmark cuts it (published widths, 20 layers = 5
    periods of 3 window + 1 full, all 64 experts, the whole 98k vocabulary, 16
    slots x 16,384 rows): the composed decode step, a mid and a final chunk
    and the largest whole-prompt prefill (2,048: a longer prompt admits in
    chunks, engine.prefill), each beside 9.1 GB of weights and
    the 3.65 GB pool by kind. ONE layer scan a graph (its body a period); a
    decode step runs the window kind's kernel under its own jitted name three
    times a period and the full kind's once; no graph copies a pool array."""
    from aios_tpu import backend
    from aios_tpu.engine import model as M
    from aios_tpu.engine import paged

    cfg, shapes = _bench_model("mellum2-12b-a2.5b-int8-d20.json", "mellum.py", 16384)
    assert cfg.kinds and cfg.period_kinds == ("window",) * 3 + ("full",)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    slots, blocks = 16, 128
    alloc = paged.KindPageAllocator(17 * 128 + 1, 17 * 12 + 1, 128, slots, blocks,
                                    cfg.period_kinds)
    layout = alloc.layout
    assert layout.pages == 2177 + 3 * 205 and layout.bases == (2177, 2382, 2587, 0)
    pools = tuple(sds(rep, (5, layout.pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    pool_elems = {5 * layout.pages * 128 * w for w in cfg.kv_row_dims}
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731

    def step(p, c, r, toks, lens, tables):
        return M.decode_step_paged(p, cfg, toks, lens, c, r, tables, kernels=True,
                                   layout=layout)

    def mid(p, c, r, toks, start, row):
        return M.prefill_chunk_paged(p, cfg, toks, start, c, r, row, layout=layout)[1:]

    def final(p, c, r, toks, start, row, n):
        logits, *rest = M.prefill_chunk_paged(p, cfg, toks, start, c, r, row,
                                              layout=layout)
        return (logits[0, n - 1], *rest)

    def prefill(p, c, r, toks, row, n):
        logits, ks, vs, picks = M.prefill(p, cfg, toks, kernels=True, logit_row=n - 1)
        from aios_tpu import ops

        rows = (ops.merge_heads(ks[:, 0]), ops.merge_heads(vs[:, 0]))
        return (logits[0, 0], *M.write_prompt_rows((c, r), rows, row, layout), picks)

    chunk_args = (params, *pools, i32(1, 512), i32(), i32(2 * blocks))
    graphs = {
        "decode-step": (step, (params, *pools, i32(slots), i32(slots),
                               i32(slots, 2 * blocks))),
        "chunk-512": (mid, chunk_args),
        "final-chunk-512": (final, chunk_args + (i32(),)),
        "prefill-2048": (prefill, (params, *pools, i32(1, 2048), i32(2 * blocks), i32())),
        # token counts the dense expert path serves: the layer scan slices
        # one layer of the stacks, and never copies them whole
        "prefill-128": (prefill, (params, *pools, i32(1, 128), i32(2 * blocks), i32())),
        "final-chunk-64": (final, (params, *pools, i32(1, 64), i32(), i32(2 * blocks), i32())),
    }
    for name, (fn, args) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP"):
            with open(os.path.join(os.environ["AOT_DUMP"], f"{name}.hlo"), "w") as fh:
                fh.write(text)
        stack_elems = {20 * 64 * 2304 * 1792, 20 * 64 * 896 * 2304}
        copied = [res for op, res in _hlo_results(text)
                  if op == "copy" and (pool_elems | stack_elems) & set(res)]
        assert copied == [], f"{name}: copies a whole pool array or expert stack"
        if name == "decode-step":
            assert len(re.findall(r"%window_decode_attention[.\d]* = ", text)) == 3
            assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 1
        else:  # the period's four expert layers: a grouped kernel each (PR 37)
            assert len(re.findall(r"%expert_group[.\d]* = ", text)) == 4, name
            assert "bf16[128,1792]" not in text, name
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert live < 15.75e9, f"{name}: {live / 1e9:.2f} GB live"
        print(f"{name}: {live / 1e9:.2f} GB live, temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB")


# --- a recurrent state a slot beside the latent pages (PR 40) ---------------


def test_aot_state_kind_compile_and_fit(rep_sharding, monkeypatch):
    """Ling-3.0-flash as the benchmark cuts it (published widths, 1 dense + 18
    layers in three periods K K K M K K, 64 of 512 experts held, 48 slots x
    4,096 rows): the recurrence's two kernels alone (`kda_step` in place in the
    state pool, `kda_chunk` over eight sub-chunks), then the composed decode
    step, a mid chunk and two final chunks, traced as on the chip. Each must
    fit beside the 8.2 GB of weights, the 1.6 GB of states and the 0.8 GB
    latent pool, and none may copy the state pool, a pool array or an expert
    stack (a decode step that copied the states would double its bytes)."""
    from aios_tpu import backend
    from aios_tpu.engine import model as M
    from aios_tpu.ops import kda as kda_ops

    cfg, shapes = _bench_model("ling-3.0-flash-int8-ep8-d19.json",
                               "bailing_hybrid.py", 4096)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    slots, blocks, pages = 48, 32, 49 * 32 + 1
    pools = tuple(sds(rep, (cfg.row_layers, pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    from aios_tpu.engine.paged import SlotStates

    kind = SlotStates(cfg.layers_of("kda"), slots, *cfg.kda_state_shapes)
    states = (sds(rep, kind.state_shape, jnp.float32),
              sds(rep, kind.tail_shape, jnp.bfloat16))
    big = {int(np.prod(a.shape)) for a in (*pools, *states)}
    big |= {int(np.prod(a.shape)) for name, a in shapes["layers"].items()
            if name.startswith("we_") for a in jax.tree.leaves(a)}
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: sds(rep, shape, jnp.float32)  # noqa: E731
    H, K = cfg.kda_heads, cfg.kda_key_dim

    aot_compile(rep, kda_ops.kda_step, f32(slots, H, K), f32(slots, H, K),
                f32(slots, H, K), f32(slots, H, K), f32(slots, H), states[0],
                i32(), i32(slots))
    aot_compile(rep, functools.partial(kda_ops.chunked, use_kernel=True),
                f32(512, H, K), f32(512, H, K), f32(512, H, K), f32(512, H, K),
                f32(512, H), f32(H, K, K))

    def chunk(p, c, r, s, t, toks, start, row, slot, n):
        return M.prefill_chunk_paged(p, cfg, toks, start, c, r, row,
                                     states=(s, t), slot=slot, n_valid=n)

    def step(p, c, r, s, t, toks, lens, tables, active):
        return M.decode_step_paged(p, cfg, toks, lens, c, r, tables,
                                   kernels=True, active=active, states=(s, t))

    graphs = {"decode-step": (step, (params, *pools, *states, i32(slots),
                                     i32(slots), i32(slots, blocks),
                                     sds(rep, (slots,), jnp.bool_)))}
    for t in (512, 128, 16):
        graphs[f"chunk-{t}"] = (chunk, (params, *pools, *states, i32(1, t),
                                        i32(), i32(blocks), i32(), i32()))
    for name, (fn, args) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(*args).compile()
        copied = [res for op, res in _hlo_results(compiled.as_text())
                  if op == "copy" and big & set(res)]
        assert copied == [], f"{name}: copies a pool, the states or an expert stack"
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: {live / 1e9:.2f} GB live, "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries")
        assert live < 15.75e9, f"{name}: {live / 1e9:.2f} GB live"


# --- the stack of sub-layers of the benchmark (PR 42) ------------------------


def test_aot_sublayers_compile_and_fit(rep_sharding, monkeypatch):
    """Nemotron-3-Nano as the benchmark cuts it (published widths, 28 layers
    in four periods M E M E M * E, 64 of 128 ungated relu^2 experts held, 32
    slots x 4,096 rows): the recurrence's two kernels alone (`mamba_step` in
    place in the state pool, `mamba_chunk` over four sub-chunks), the two
    expert kernels at an expert width of 1,856 = 14.5 lane tiles, then the
    composed decode step, a mid chunk and two final chunks, traced as on the
    chip. Each must fit beside the 9 GB of weights, the 0.85 GB of states and
    the 0.55 GB K/V pool, and none may copy the state pool, a pool array or an
    expert stack (a decode step that copied the states would double its
    bytes)."""
    from aios_tpu import backend
    from aios_tpu.engine import model as M
    from aios_tpu.ops import expert_group, expert_visit
    from aios_tpu.ops import mamba2 as ssm_ops

    cfg, shapes = _bench_model("nemotron-3-nano-int8-ep2-d28.json",
                               "nemotron_h.py", 4096)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    rep = rep_sharding
    params = jax.tree.map(lambda a: sds(rep, a.shape, a.dtype), shapes)
    slots, blocks, pages = 32, 32, 33 * 32 + 1
    pools = tuple(sds(rep, (cfg.row_layers, pages, 128, w), jnp.bfloat16)
                  for w in cfg.kv_row_dims)
    from aios_tpu.engine.paged import SlotStates

    kind = SlotStates.of(cfg, slots)
    assert kind.state_shape == (12, 33, 64, 64, 128)
    assert kind.tail_shape == (12, 3, 48, 6144) and cfg.row_layers == 4
    states = (sds(rep, kind.state_shape, jnp.float32),
              sds(rep, kind.tail_shape, jnp.bfloat16))
    moe_leaves = shapes["layers"]["by_kind"]["moe"]
    big = {int(np.prod(a.shape)) for a in (*pools, *states)}
    big |= {int(np.prod(a.shape)) for name, a in moe_leaves.items()
            if name.startswith("we_") for a in jax.tree.leaves(a)}
    # nor any other stack of matrices (what the chip showed of `ssm_in` at a
    # width of 80.5 lane tiles, a 332 MB copy a decode program, no compile
    # here can show: an entry layout is the compiler's to choose here and the
    # array's own there; every matrix's width is whole lane tiles instead)
    big |= {int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
            if int(np.prod(a.shape)) >= 2 ** 26}
    assert all(a.shape[-1] % 128 == 0 for a in jax.tree.leaves(shapes)
               if a.dtype == jnp.int8)
    i32 = lambda *shape: sds(rep, shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: sds(rep, shape, jnp.float32)  # noqa: E731
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state

    aot_compile(rep, ssm_ops.mamba_step, f32(slots, H, P), f32(slots, H),
                f32(slots, H), f32(slots, G, N), f32(slots, G, N), states[0],
                i32(), i32(slots))
    aot_compile(rep, functools.partial(ssm_ops.chunked, use_kernel=True),
                f32(512, H, P), f32(512, H), f32(512, H), f32(512, G, N),
                f32(512, G, N), f32(H, P, N))
    E, F, X = cfg.hidden_size, cfg.expert_dim, cfg.held_experts
    stacks = (sds(rep, (12, X, F, E), jnp.int8), f32(12, X, 1, F),
              sds(rep, (12, X, F, E), jnp.int8), f32(12, X, 1, E))
    assert expert_visit.supports_pallas(E, F, act="relu2")
    assert not expert_visit.supports_pallas(E, F)
    aot_compile(rep, functools.partial(expert_visit.expert_visit, act="relu2"),
                sds(rep, (slots, E), jnp.bfloat16), f32(slots, X), i32(X), i32(),
                i32(), *stacks)
    cap = expert_group.row_cap(E, F, 2, act="relu2")
    rows = expert_group.buffer_rows(512 * cfg.num_experts_per_tok, X)
    units = X + rows // cap
    aot_compile(rep, functools.partial(expert_group.expert_group, cap=cap,
                                       act="relu2"),
                sds(rep, (rows, E), jnp.bfloat16), i32(units), i32(units),
                i32(units), i32(), i32(), *stacks)

    def chunk(p, c, r, s, t, toks, start, row, slot, n):
        return M.prefill_chunk_paged(p, cfg, toks, start, c, r, row,
                                     states=(s, t), slot=slot, n_valid=n)

    def step(p, c, r, s, t, toks, lens, tables, active):
        return M.decode_step_paged(p, cfg, toks, lens, c, r, tables,
                                   kernels=True, active=active, states=(s, t))

    graphs = {"decode-step": (step, (params, *pools, *states, i32(slots),
                                     i32(slots), i32(slots, blocks),
                                     sds(rep, (slots,), jnp.bool_)))}
    for t in (512, 128, 16):
        graphs[f"chunk-{t}"] = (chunk, (params, *pools, *states, i32(1, t),
                                        i32(), i32(blocks), i32(), i32()))
    for name, (fn, args) in graphs.items():
        compiled = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(*args).compile()
        copied = [res for op, res in _hlo_results(compiled.as_text())
                  if op == "copy" and big & set(res)]
        assert copied == [], f"{name}: copies a pool, the states or an expert stack"
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: {live / 1e9:.2f} GB live, "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries")
        assert live < 15.75e9, f"{name}: {live / 1e9:.2f} GB live"
