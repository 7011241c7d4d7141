#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU

One process drives the main path the way a user would: ``ModelManager()``
with its defaults (8 slots, AOT warm compile), ``runtime.service.serve``
and an ``AIRuntimeStub`` over a localhost socket, under the serving
environment the default boot config produces (``AIOS_TPU_PAGED_KV=auto``).
The model is Mistral-7B at full width and depth with random weights from a
seed (``synthetic://mistral-7b``).

  phase 0  refuse anything but a TPU; report the device, versions, cache
  phase 1  int8 serving weights (the manager's one-chip default): LoadModel
           must come ``ready``; greedy tokens from the engine must agree
           with a ``jnp`` reference forward on the same weights; 8
           concurrent Infer (prompts of ~64 and ~1000 tokens) and one
           StreamInfer must all answer with the token counts asked for,
           chunks must arrive while decode is still running, and the
           engine's compile counters must be the same before the first
           request and after the last
  phase 2  the same on int4 weights (the packed-nibble Pallas matmul)
  phase 3  each Pallas kernel against its ``*_reference`` on the chip at
           Mistral geometry, and no ``pallas_call`` in interpret mode
  phase 4  phase 1 under ``AIOS_TPU_MESH=tp=4`` when four TPU devices are
           visible (else ``"skipped": "1 device"``), weights and KV spread

One flushed JSON line per phase, a summary line, then
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`` as
the last line of stdout. Any failed phase, any exception, or any platform
but ``tpu`` exits non-zero; without a TPU nothing is printed on stdout.
Wall times are smoke timings (set-up included), not benchmark results.

``--rehearsal`` is for debugging this script without a chip: it forces
``JAX_PLATFORMS=cpu``, swaps in ``synthetic://tiny-test``, runs the kernels
in interpret mode and stamps every line ``"rehearsal": true``. ``--phases``
restricts the run (phase 0 always runs); the driver passes neither.
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import functools
import importlib
import importlib.metadata
import json
import os
import sys
import threading
import time
import traceback

DEADLINE_SECS = 1150  # the contract allows 1200 s, compilation included
NUM_SLOTS = 8
MAX_TOKENS = 64
# The byte tokenizer of synthetic sources decodes ids >= 256 to no text, so
# a stream only carries chunks when low ids are sampled. A temperature this
# high makes sampling uniform over the vocabulary whatever the weights:
# 256/32000 of 1024 tokens is ~8 text chunks (none at all: e^-8).
STREAM_TOKENS = 1024
STREAM_TEMPERATURE = 100.0
VALUE_GAP = 0.5  # logits; synthetic logits have a std of ~0.8
SHORT_PROMPT_BYTES, LONG_PROMPT_BYTES = 48, 980


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def version_of(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def memory_stat(device, key: str) -> int:
    return int((device.memory_stats() or {}).get(key, 0))


# ---------------------------------------------------------------------------
# phases 1, 2, 4: the serving path over gRPC
# ---------------------------------------------------------------------------


def prompts() -> list:
    """4 short (~64 tokens with the chat template) and 4 long (~1000)
    prompts; the long ones share a preamble the way agents' system prompts
    do, so the prefix cache's hit path serves too."""
    preamble = ("You are one of eight agents of an operating system. "
                "Answer with a tool call. ") * 12
    out = []
    for i in range(4):
        out.append(f"agent {i}: list the processes using most memory."
                   .ljust(SHORT_PROMPT_BYTES, "."))
        tail = f" agent {i}: summarise the incident log above."
        out.append((preamble[: LONG_PROMPT_BYTES - len(tail)] + tail))
    return out


def value_check(managed) -> dict:
    """What comes out must be RIGHT, not just the right length: greedy
    tokens from the engine's serving graphs (paged prefill + decode, the
    Pallas kernels, the quantized matmuls) must be the (near-)argmax of a
    plain ``jnp`` reference forward over the same weights and tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aios_tpu.engine import model as model_mod
    from aios_tpu.engine.tokenizer import render_chat

    engine, cfg = managed.engine, managed.config
    n_new = 9  # the first token from prefill, then one warmed 8-step graph
    ids = managed.tokenizer.encode(
        render_chat(cfg.name, "value check: list the listening ports.")
    )
    got = engine.generate(ids, max_new_tokens=n_new, temperature=0.0)
    assert len(got) == n_new, got
    seq = jnp.asarray([ids + got[:-1]], jnp.int32)
    os.environ["AIOS_TPU_NO_PALLAS"] = "1"  # trace the references
    try:
        logits = jax.jit(
            lambda p, t: model_mod.forward_full(p, cfg, t, kernels=False)
        )(engine.params, seq)
        logits = np.asarray(logits[0, len(ids) - 1:], np.float32)
    finally:
        del os.environ["AIOS_TPU_NO_PALLAS"]
    assert logits.shape == (n_new, cfg.vocab_size), logits.shape
    assert np.isfinite(logits).all(), "reference logits are not finite"
    # bf16 near-ties may flip an argmax; a wrong token sits sigmas below
    gaps = logits.max(-1) - logits[np.arange(n_new), got]
    assert (gaps <= VALUE_GAP).all(), (
        f"engine tokens {got} are not the reference's choices: "
        f"logit gaps {gaps.round(3).tolist()} (logit std "
        f"{logits.std():.3f})"
    )
    return {"greedy_tokens": got,
            "max_logit_gap": round(float(gaps.max()), 4),
            "logit_std": round(float(logits.std()), 3)}


def serve_phase(phase: int, label: str, *, model_path: str, context: int,
                manager_kwargs: dict, rehearsal: bool) -> dict:
    import jax

    from aios_tpu import rpc, services
    from aios_tpu.engine.tokenizer import render_chat
    from aios_tpu.proto_gen import runtime_pb2
    from aios_tpu.runtime.model_manager import ModelManager
    from aios_tpu.runtime.service import serve

    name = "mistral-7b"
    t_phase = time.time()
    manager = ModelManager(**manager_kwargs)
    server, _service, port = serve(
        address="127.0.0.1:0", manager=manager, block=False
    )
    channel = rpc.insecure_channel(f"127.0.0.1:{port}")
    stub = services.AIRuntimeStub(channel)
    try:
        t0 = time.time()
        status = stub.LoadModel(runtime_pb2.LoadModelRequest(
            model_name=name, model_path=model_path, context_length=context,
        ), timeout=DEADLINE_SECS)
        load_s = time.time() - t0
        assert status.status == "ready", f"LoadModel -> {status.status!r}"
        managed = manager.get(name)
        cfg = managed.config
        if not rehearsal:
            assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                    cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size,
                    cfg.sliding_window) == (32, 4096, 32, 8, 128, 32000, 4096), \
                f"not full-width Mistral-7B: {cfg}"
        engine = managed.engine
        assert engine.num_slots == NUM_SLOTS and engine.paged
        note(f"phase {phase} ({label}): ready in {load_s:.1f}s "
             f"{managed.setup_seconds}")

        def compile_counters() -> tuple:
            s = managed.pool.stats()
            return s["xla_compiles"], s["xla_compile_s"]

        before = compile_counters()
        values = value_check(managed)
        results: list = [None] * NUM_SLOTS
        errors: list = []

        def infer(i: int, prompt: str) -> None:
            try:
                n_prompt = len(managed.tokenizer.encode(
                    render_chat(cfg.name, prompt)
                ))
                r = stub.Infer(runtime_pb2.InferRequest(
                    prompt=prompt, max_tokens=MAX_TOKENS, temperature=0.7,
                    model=name, task_id=f"smoke-{phase}-{i}",
                ), timeout=600)
                results[i] = (n_prompt, r.tokens_used - n_prompt)
            except Exception:  # noqa: BLE001 - reported, fails the phase
                errors.append(f"infer {i}: {traceback.format_exc()}")

        t0 = time.time()
        threads = [
            threading.Thread(target=infer, args=(i, p), daemon=True)
            for i, p in enumerate(prompts())
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "an Infer never returned"
        assert not errors, "\n".join(errors)
        infer_s = time.time() - t0
        completions = [c for _, c in results]
        # a sampled EOS legitimately ends a completion early (random
        # weights: ~1/vocab per token); anything else must be exact
        eos_early = sum(1 for c in completions if 0 <= c < MAX_TOKENS)
        assert all(0 <= c <= MAX_TOKENS for c in completions), completions
        assert rehearsal or eos_early <= 1, (  # tiny-test's vocab is 512
            f"completions short of max_tokens: {completions}"
        )

        # one stream: text chunks must arrive while decode is running
        stream_tokens = 256 if rehearsal else STREAM_TOKENS
        t0 = time.time()
        arrivals, done = [], False
        for chunk in stub.StreamInfer(runtime_pb2.InferRequest(
            prompt="stream a status report", max_tokens=stream_tokens,
            temperature=STREAM_TEMPERATURE, model=name,
            task_id=f"smoke-{phase}-stream",
        ), timeout=600):
            if chunk.done:
                done = True
            else:
                arrivals.append(time.time() - t0)
        stream_s = time.time() - t0
        assert done, "stream ended without its done chunk"
        assert arrivals, "stream produced no text chunk"
        assert arrivals[0] <= 0.9 * stream_s, (
            f"first chunk at {arrivals[0]:.2f}s of a {stream_s:.2f}s "
            "stream: chunks are not incremental"
        )
        after = compile_counters()
        assert after == before, (
            f"serving compiled past the readiness gate: {before} -> {after}"
        )
        stats = managed.pool.stats()
        return {
            "phase": phase, "ok": True, "label": label,
            "weights": engine.quant_mode or "bf16",
            "seconds": round(time.time() - t_phase, 1),
            "setup_seconds": {"load_model": round(load_s, 1),
                              **managed.setup_seconds},
            "compiles": before[0],
            "infer_seconds": round(infer_s, 2),
            "stream_seconds": round(stream_s, 2),
            "prompt_tokens": [n for n, _ in results],
            "tokens": sum(completions),
            "eos_early": eos_early,
            "stream_chunks": len(arrivals),
            "first_chunk_seconds": round(arrivals[0], 3),
            **values,
            "prefix_hits": stats.get("prefix_hits", 0),
            "decode_steps": stats.get("decode_steps", 0),
            "peak_bytes_in_use": max(
                memory_stat(d, "peak_bytes_in_use") for d in jax.devices()
            ),
            # taken with the model still loaded
            "per_device_bytes_in_use": [
                memory_stat(d, "bytes_in_use") for d in jax.devices()
            ],
        }
    finally:
        try:
            stub.UnloadModel(
                runtime_pb2.UnloadModelRequest(model_name=name), timeout=120
            )
        finally:
            channel.close()
            server.stop(grace=None)


def mesh_phase(rehearsal: bool, model_path: str, context: int) -> dict:
    import jax

    n = len(jax.devices())
    tp = 2 if rehearsal else 4  # tiny-test has two KV heads
    if n < tp:
        return {"phase": 4, "ok": True, "skipped": f"{n} device"}
    os.environ["AIOS_TPU_MESH"] = f"tp={tp}"
    try:
        out = serve_phase(
            4, f"tp={tp} mesh", model_path=model_path, context=context,
            manager_kwargs={}, rehearsal=rehearsal,
        )
    finally:
        del os.environ["AIOS_TPU_MESH"]
    per_device = out["per_device_bytes_in_use"][:tp]
    if any(per_device):
        mean = sum(per_device) / len(per_device)
        assert max(per_device) <= 1.5 * mean, (
            f"weights/KV not spread over the mesh: {per_device}"
        )
    return out


# ---------------------------------------------------------------------------
# phase 3: kernels against their references, at Mistral geometry
# ---------------------------------------------------------------------------


def kernel_phase(rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aios_tpu import ops

    # the package exports the function under the submodule's name
    i4 = importlib.import_module("aios_tpu.ops.int4_matmul")

    t0 = time.time()
    interp = {"interpret": True} if rehearsal else {}
    if rehearsal:  # tiny shapes: interpret mode is slow
        B, H, KH, D, P, C, T, W = 2, 4, 2, 64, 16, 64, 128, 48
        mm_shapes = [(8, 256, 128)]
    else:
        assert ops.use_pallas(), "ops.use_pallas() is False on the chip"
        B, H, KH, D, P, C, T, W = 8, 32, 8, 128, 128, 4096, 1024, 4096
        mm_shapes = [(8, 4096, 6144), (8, 4096, 28672), (8, 14336, 4096),
                     (8, 4096, 32000), (512, 4096, 6144)]
    checks = {}

    def compare(label, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape, (label, got.shape, want.shape)
        assert np.isfinite(got).all(), f"{label}: non-finite output"
        err = np.abs(got - want)
        checks[label] = round(float(err.max()), 5)
        assert (err <= tol + tol * np.abs(want)).all(), (
            f"{label}: max |err| {checks[label]} beyond rtol=atol={tol}"
        )

    def rand(key, shape, dtype):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))
    full = functools.partial(jax.default_matmul_precision, "highest")

    def check(label, tol, kernel_precision, kernel, reference, *args, **kw):
        # the references' f32 dots must not round through one bf16 pass;
        # the kernel runs as served, except on f32 operands, where full
        # precision is what makes the tight tolerance meaningful
        with kernel_precision():
            got = kernel(*args, **kw, **interp)
        with full():
            want = reference(*args, **kw)
        compare(label, got, want, tol)

    MB = C // P
    N = 1 + (NUM_SLOTS + 1) * MB  # the "auto" pool: (slots+1) x context
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(N - 1)[: B * MB]
        .reshape(B, MB) + 1, jnp.int32,
    )
    lengths = jnp.asarray(np.linspace(0, C - 2, B).astype(np.int32))
    win_starts = jnp.maximum((lengths // P - 2) * P, 0)
    # tolerances: tests/test_ops.py uses 2e-3 for the f32 attention
    # parities and 2e-2 for bf16 activations (the serving dtype)
    for dtype, tol, precision in (
        (jnp.float32, 2e-3, full),
        (jnp.bfloat16, 2e-2, contextlib.nullcontext),
    ):
        tag = jnp.dtype(dtype).name
        q = rand(next(keys), (1, T, H, D), dtype)
        k = rand(next(keys), (1, T, KH, D), dtype)
        v = rand(next(keys), (1, T, KH, D), dtype)
        for window in (W, T // 4):
            check(f"flash_prefill[{tag},window={window}]", tol, precision,
                  ops.flash_attention, ops.flash_attention_reference,
                  q, k, v, causal=True, window=window)
        qd = rand(next(keys), (B, H, D), dtype)
        # a three-layer stack, read at its middle layer: the kernel takes
        # the whole pool and the layer's index
        kp = rand(next(keys), (3, N, P, KH * D), dtype)
        vp = rand(next(keys), (3, N, P, KH * D), dtype)
        check(f"paged_decode[{tag}]", tol, precision,
              ops.paged_decode_attention,
              ops.paged_decode_attention_reference,
              qd, kp, vp, 1, tables, lengths, window=W)
        check(f"paged_decode[{tag},win_starts+sink]", tol, precision,
              ops.paged_decode_attention,
              ops.paged_decode_attention_reference,
              qd, kp, vp, 1, tables, lengths, window=None,
              win_starts=win_starts, sink=P)
        kc = rand(next(keys), (B, C, KH, D), dtype)
        vc = rand(next(keys), (B, C, KH, D), dtype)
        check(f"ragged_decode[{tag}]", tol, precision,
              ops.decode_attention, ops.decode_attention_reference,
              qd, kc, vc, lengths, window=W)
    for M, K, N_ in mm_shapes:
        w = rand(next(keys), (K, N_), jnp.float32) * 0.05
        x = rand(next(keys), (M, K), jnp.bfloat16)
        packed, scale = i4.quantize_int4(w)
        compare(
            f"int4_matmul[{M}x{K}x{N_}]",
            i4.int4_matmul(x, packed, scale, **interp),
            i4.int4_matmul_reference(x, packed, scale),
            2e-2,
        )
    return {"phase": 3, "ok": True, "seconds": round(time.time() - t0, 1),
            "max_abs_err": checks}


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="debug this script on the CPU with tiny-test; "
                         "never a chip result")
    ap.add_argument("--phases", default="1,2,3,4",
                    help="comma list of phases to run after phase 0")
    args = ap.parse_args()
    want = {int(p) for p in args.phases.split(",") if p.strip()}
    rehearsal = args.rehearsal
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    faulthandler.dump_traceback_later(DEADLINE_SECS, exit=True)

    from aios_tpu import backend  # fails here outside a checkout
    from aios_tpu.boot.config import AiosConfig, serving_env

    # written before anything can hang: device init is the first thing
    # that can
    cache_dir = backend.compile_cache_dir()
    versions = {d: version_of(d) for d in ("jax", "jaxlib", "libtpu")}
    note(f"versions {versions}; compile cache {cache_dir} "
         f"({cache_entries(cache_dir)} entries); initialising the backend")

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    stamp = {"rehearsal": True} if rehearsal else {}
    if not rehearsal:
        if dev.platform != "tpu":
            note(f"no TPU: JAX reports {device}; refusing to continue")
            return 2
        backend.require_tpu()
    entries_before = cache_entries(cache_dir)
    emit({"phase": 0, "ok": True, "device": device, **versions,
          "compile_cache_dir": cache_dir,
          "compile_cache_entries": entries_before, **stamp})

    os.environ.update(serving_env(AiosConfig()))  # AIOS_TPU_PAGED_KV=auto
    model_path = "synthetic://tiny-test" if rehearsal else "synthetic://mistral-7b"
    context = 2048 if rehearsal else 4096

    # every pallas_call built from here on is recorded: none may interpret
    from jax.experimental import pallas as pl

    built = []
    real_pallas_call = pl.pallas_call

    def recording_pallas_call(*a, **kw):
        built.append(bool(kw.get("interpret", False)))
        return real_pallas_call(*a, **kw)

    pl.pallas_call = recording_pallas_call

    phases = {
        1: lambda: serve_phase(
            1, "manager default weights", model_path=model_path,
            context=context, manager_kwargs={}, rehearsal=rehearsal),
        2: lambda: serve_phase(
            2, "int4 weights", model_path=model_path, context=context,
            manager_kwargs={"quantize": "int4"}, rehearsal=rehearsal),
        3: lambda: kernel_phase(rehearsal),
        4: lambda: mesh_phase(rehearsal, model_path, context),
    }
    ok = True
    for number in sorted(want & set(phases)):
        t0 = time.time()
        try:
            line = phases[number]()
        except Exception as exc:  # noqa: BLE001 - a failed phase fails the run
            traceback.print_exc()
            line = {"phase": number, "ok": False,
                    "seconds": round(time.time() - t0, 1),
                    "error": f"{type(exc).__name__}: {exc}"[:600]}
        ok = ok and line["ok"]
        emit({**line, **stamp})
    if not rehearsal and want & {1, 2, 3}:
        if not built or any(built):
            ok = False
            note(f"pallas_call check failed: {len(built)} built, "
                 f"{sum(built)} in interpret mode")
    emit({"summary": "smoke timings, not benchmark results",
          "pallas_calls_built": len(built),
          "compile_cache_entries_added":
              cache_entries(cache_dir) - entries_before, **stamp})
    emit({"ok": ok, "device": device, **stamp})  # the contract's last line
    faulthandler.cancel_dump_traceback_later()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
