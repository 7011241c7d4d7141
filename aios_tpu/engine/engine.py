"""The TPU decode engine: slot KV cache, bucketed prefill, batched decode.

This is the component that replaces llama.cpp end-to-end (SURVEY.md
section 2.3, "TPU equivalence requirement"): weights live in HBM, prefill and
the decode loop are jitted graphs with static shapes, sampling happens on
device, and ALL decode state (KV caches, slot lengths, last tokens, per-slot
sampling params, RNG key) is device-resident and donated — a decode dispatch
moves no state across the host boundary except the sampled tokens coming out.

Shape discipline (the TPU contract):
  * decode is ONE graph for the lifetime of the engine: `step_n` runs K
    decode steps under `lax.scan` per dispatch ([S] -> [K, S] tokens), so
    host/relay round-trip latency amortizes over K tokens. Continuous
    batching inserts/retires requests by mutating slot state, never by
    changing shapes.
  * prefill is compiled per power-of-two length bucket, so an arbitrary
    prompt costs at most 2x its length and never recompiles after warmup.

A slot lifecycle: prefill(slot, prompt) writes K/V rows [0, len) and samples
the first token -> step_n() extends every active slot -> release(slot).
Inactive slots keep decoding garbage (their outputs are ignored); that is the
price of a fixed-shape graph and it is what keeps XLA fast.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import kda, latent, model, paged, sampling, spec
from .config import ModelConfig
from .. import backend, faults, ops
from ..analysis.locks import make_lock
from ..obs import instruments as obs
from ..obs import devprof, flightrec

log = logging.getLogger("aios.engine")

DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)
# float32 logits of every row of a whole-prompt prefill that a paged engine
# still computes whole (`_prefill_impl_paged`)
WHOLE_LOGITS_BYTES = 3 << 29

# Run-length buckets for the grammar jump-ahead graphs (jump_step): a
# forced run of K tokens dispatches through the smallest bucket >= K, so
# warmup compiles len(JUMP_BUCKETS) graphs and serving never compiles.
# Two buckets on purpose: padding a short run up to the bucket is nearly
# free (the verify dispatch is weight-bandwidth-bound), while every extra
# bucket is another graph in every constrained deployment's warmup gate.
# Bounded by spec.HISTORY_PAD - 2 (the post-dispatch history scatter must
# stay inside the pad margin, same bound as speculative draft_len).
JUMP_BUCKETS = (4, 16)
assert JUMP_BUCKETS[-1] <= spec.HISTORY_PAD - 2

# Decode steps per dispatch of the continuous batcher's loop, whether or
# not a request waits for a slot: a finished slot and an arrival wait
# out a dispatch or two, and a prompt admitted in chunks gets one chunk
# between two dispatches, so the dispatch is short, and the pipelined
# loop hides the host's time per dispatch where a long one amortised it.
# Chosen on the chip (PERF.md section 6, PR 30): the shortest of 2, 4, 8
# and 16 that holds tpot_p50_ms in the benchmark's cells (steps of
# 10-12 ms; a model with a much shorter step may want more). warmup's
# default step size and the batcher's defaults and attach-time compile
# all read this, so the graph compiled behind the readiness gate is the
# graph the loop dispatches.
DECODE_STEPS = 2

# Width buckets for the standalone draft-KV bulk-ingest graphs: a freshly
# admitted (or failed-over) slot's draft cache trails the serving state by
# the whole prompt, and spec_step_draft catches it up in these power-of-
# two teacher-forced chunks before the fused rounds take over (whose
# per-round catch-up width is only draft_len+1 — the steady-state gap is
# 0 or 1). Capped at the shared prefill-chunk granularity; the draft tier
# is small, so each graph is a cheap compile.
DRAFT_INGEST_BUCKETS = (32, 64, 128, 256, 512)

# Live HostPageStores per model name: replica engines share the (model,)
# label on the aios_tpu_prefix_host_* gauges, so the scrape callbacks sum
# over this set instead of reporting whichever replica registered last.
_HOST_STORES_BY_MODEL: Dict[str, object] = {}

# Live engines per model name, for the same last-writer-wins reason: the
# aios_tpu_engine_jump_ahead_* and aios_tpu_spec_* gauges sum over every
# replica engine instead of reporting whichever registered last.
_ENGINES_BY_MODEL: Dict[str, object] = {}


def _cpu_device():
    from .checkpoint import cpu_device

    return cpu_device()


def _to_default_device(a):
    """jnp.asarray that also MOVES committed host arrays to the default
    backend's device. Both jnp.asarray AND bare jax.device_put(x) are
    identities on an array already committed to any device (jax 0.9
    semantics), so the target device must be explicit. An operator-pinned
    jax_default_device wins over devices()[0]."""
    target = getattr(jax.config, "jax_default_device", None)
    if isinstance(target, str):
        # the config validator accepts platform-name strings ('cpu'/'tpu');
        # device_put does not — resolve to that backend's first device
        target = jax.devices(target)[0]
    elif target is None:
        target = jax.devices()[0]
    return jax.device_put(jnp.asarray(a), target)


def _layer_leaves(params):
    """The values of a params tree's layer stack, a kind's own leaves
    (``by_kind``: model._scan_periods) beside those every layer has."""
    layers = params.get("layers", {}) if isinstance(params, dict) else {}
    for name, v in layers.items():
        if name == "by_kind":
            for own in v.values():
                yield from own.values()
        else:
            yield v


def _is_prequantized(params) -> bool:
    """True when the params tree already holds serving-quantized leaves
    ({"q","s"} int8 or {"q4","s4"} int4 dicts from quantize_params)."""
    return any(
        isinstance(v, dict) and ("q" in v or "q4" in v)
        for v in _layer_leaves(params)
    )


def _prequantized_mode(params) -> str:
    """The dominant stored serving mode of a prequantized tree ("int4" when
    any packed-nibble leaf exists — mixed trees are int4-with-int8-fallback
    by construction)."""
    for v in _layer_leaves(params):
        if isinstance(v, dict) and "q4" in v:
            return "int4"
    return "int8"


def _resolve_stored_mode(params, requested, *, quiet_default: bool = False):
    """The STORED serving mode of a prequantized tree wins over the
    engine-level request; flag a mismatch rather than silently reporting
    the wrong precision. ``quiet_default`` logs the no-request case at
    info (benches/prepared checkpoints pass quantized trees without a
    mode on purpose)."""
    stored = _prequantized_mode(params)
    if requested and requested != stored:
        log.warning(
            "checkpoint stores %s serving weights; requested quantize=%s "
            "is ignored (re-run prepare_model to change the stored mode)",
            stored, requested,
        )
    elif not requested and quiet_default:
        log.info(
            "serving prequantized %s weights (bf16 serving is unavailable "
            "for prepared-quantized trees)", stored,
        )
    return stored


def _is_fused_prequantized(params) -> bool:
    """True for the FUSED single-chip serving layout (w_qkv/w_gateup
    concats from quantize_params fuse=True) — it has no TP sharding rule
    (a fused concat would interleave q/k/v columns across shards)."""
    layers = params.get("layers", {}) if isinstance(params, dict) else {}
    return any(k in layers for k in ("w_qkv", "w_gateup", "we_gateup"))


# keys whose CONTRACTION dim (K) shards under tp (row-parallel); every
# other quantized projection — and the lm_head's vocab — shards its
# output dim N (column-parallel). Mirrors quantize_params's tp rule.
_ROW_PARALLEL_KEYS = ("wo", "w_down")


def _validate_prequantized_tp(params, tp: int) -> None:
    """A prepared (unfused) quantized tree must have been quantized for
    THIS tp degree: int4 scale groups are picked from shard-local dims, so
    a mismatched plan would hand the per-device kernel groups it cannot
    serve — and int8 {'q','s'} leaves need their SHARDED dim divisible by
    tp (N for column-parallel projections, K for the row-parallel
    _ROW_PARALLEL_KEYS) or the mismatch only surfaces as an opaque GSPMD
    shape error inside the first dispatch. Raise with the re-prepare
    recipe instead."""
    if tp <= 1:
        return
    from ..ops.int4_matmul import kernel_supported

    leaves = dict(params.get("layers", {}))
    if isinstance(params.get("lm_head"), dict):
        leaves["lm_head"] = params["lm_head"]
    bad = []
    mode = "int8"
    for key, v in leaves.items():
        if not isinstance(v, dict):
            continue
        if "q4" in v:
            mode = "int4"
            K, N = v["q4"].shape[-2] * 2, v["q4"].shape[-1]
            groups = v["s4"].shape[-3]
            group = K // groups
            if key in _ROW_PARALLEL_KEYS:
                ok = (K % tp == 0 and groups % tp == 0
                      and kernel_supported(K // tp, N, group))
            else:
                ok = N % tp == 0 and kernel_supported(K, N // tp, group)
        elif "q" in v:
            # int8: the contraction dim K shards for row-parallel
            # projections, the output dim N (and its per-channel scales)
            # everywhere else — quantize_params's tp rule
            K, N = v["q"].shape[-2], v["q"].shape[-1]
            ok = (K % tp == 0) if key in _ROW_PARALLEL_KEYS else (N % tp == 0)
        else:
            continue
        if not ok:
            bad.append(key)
    if bad:
        raise ValueError(
            f"prepared {mode} checkpoint is not servable under tp={tp} "
            f"(leaves {', '.join(bad)}): re-run scripts/prepare_model.py "
            f"--quantize {mode} --tp {tp} so shard-local eligibility and "
            "scale groups are baked for this plan"
        )


def _on_accelerator(params) -> bool:
    """True if ANY param leaf already lives on a non-CPU jax device (a
    mixed tree must not round-trip device weights through the host)."""
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array):
            try:
                if leaf.devices().pop().platform != "cpu":
                    return True
            # aios: waive(silent-except): placement probe over possibly-deleted arrays — an unreadable leaf just doesn't vote
            except Exception:  # noqa: BLE001
                continue
    return False


def _env_flag(name: str) -> Optional[bool]:
    """Tri-state env boolean: None when unset/blank (caller falls back to
    its config default), else the lenient truthiness the other AIOS_TPU_*
    knobs use."""
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return None
    return raw in ("1", "true", "on", "yes")


def _env_int(name: str) -> Optional[int]:
    """Lenient env integer: None when unset/blank/malformed (a bad knob
    logs and falls back instead of failing a model load — the
    serving-config convention)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        v = int(float(raw))
        if v < 0:
            raise ValueError("must be >= 0")
        return v
    except ValueError:
        log.warning("%s=%r ignored (expected a non-negative integer)",
                    name, raw)
        return None


def refuse_for_latent_pool(cfg, **asked) -> None:
    """Raise, naming the model and the feature, for a serving feature that
    cannot take a latent (MLA) page pool yet. No quiet fallback to another
    path: ``LoadModel`` fails with this error. ``asked`` maps a feature's
    description to whether the load asks for it."""
    if not cfg.mla:
        return
    wanted = [what for what, on in asked.items() if on]
    if wanted:
        streams = (
            f" or carry its residual of {cfg.hc_mult} mixed streams "
            "(engine/residual.py is called by the latent pool's four "
            "graphs only)" if cfg.hc else ""
        )
        raise ValueError(
            f"{cfg.name}: latent attention (MLA) serves from the bf16 "
            f"paged latent pool only; this load asks for "
            f"{' and '.join(w.replace('_', ' ') for w in wanted)}, which "
            f"cannot take a latent pool yet{streams}"
        )


def window_rows_a_slot(cfg, page_size: int, max_context: int,
                       chunk: int = 512) -> int:
    """Rows ONE slot's window kind holds at most (a stack of window and full
    layers), in whole pages: the window, one admission chunk in flight (at
    least two pages: a decode dispatch grows a slot across a page boundary)
    and the page the window's first row straddles. ``chunk`` defaults to
    ``TPUEngine.prefill_chunk_default``."""
    chunk = min(chunk or 1, max_context)
    blocks = -(-cfg.sliding_window // page_size) + max(-(-chunk // page_size), 2)
    return min(blocks * page_size, max_context)


def refuse_for_two_kinds(cfg, **asked) -> None:
    """Raise, naming the model and the feature, for a serving feature that
    cannot take the pool of a stack of window and full attention layers yet
    (pages, tables and residency by kind: engine/paged.py header). No quiet
    fallback: ``LoadModel`` fails with this error, as
    ``refuse_for_latent_pool`` does for the latent pool."""
    if not cfg.kinds:
        return
    wanted = [what for what, on in asked.items() if on]
    if wanted:
        raise ValueError(
            f"{cfg.name}: window and full attention layers in one stack "
            f"serve from the bf16 page pool by kind only; this load asks "
            f"for {' and '.join(w.replace('_', ' ') for w in wanted)}, "
            f"which cannot take pages of two kinds yet"
        )


def refuse_for_state_kind(cfg, **asked) -> None:
    """Raise, naming the model and the feature, for a serving feature that
    cannot take a recurrent state a slot (a stack with kda or mamba2 layers:
    the state kind, engine/paged.py header). No quiet fallback: ``LoadModel``
    fails with this error, as its two siblings' do."""
    kind = getattr(cfg, "state_kind", None)  # (a test's stub has none)
    if kind is None:
        return
    wanted = [what for what, on in asked.items() if on]
    if wanted:
        layers, pool = {
            "kda": ("linear-attention (kda)", "latent pool"),
            "mamba2": ("state-space (mamba2)", "grouped-query page pool"),
        }[kind]
        raise ValueError(
            f"{cfg.name}: {layers} layers keep one recurrent "
            f"state a slot beside the bf16 {pool}; this load asks for "
            f"{' and '.join(w.replace('_', ' ') for w in wanted)}, which "
            f"cannot take a state yet"
        )


# Device-resident decode state, threaded through the jitted cores as one
# donated pytree: {k, v, lengths, last_tokens, temps, top_ps, key}
DecodeState = Dict[str, jnp.ndarray]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _reset_slot(lengths, active, slot):
    """A freed slot's two device resets as ONE small program with one
    scalar operand. As two eager ``.at[slot].set(...)`` they were a dozen
    (index arithmetic, conversions, two scatters), each a point at which
    the calling thread gave the interpreter up with the engine lock held:
    14 ms a retirement among the serving threads (PERF.md, PR 41)."""
    return lengths.at[slot].set(0), active.at[slot].set(False)


class PendingDecode:
    """Handle for a decode dispatch running on one of the engine's
    dispatch workers (engine.step_async).

    The worker thread performs the whole dispatch — lock, graph call,
    device->host token readback — so the CALLER's thread overlaps its own
    host work (emit/detokenize/retire) with the device execution; on the
    CPU backend, where XLA executes "parallel" computations inline in the
    dispatching call, the worker is the ONLY way to get that overlap (the
    GIL is released inside the XLA call).

    ``wait()`` blocks until the tokens materialize and returns the host
    ``[n_steps, S]`` array. ``lengths`` (valid after ``wait()``)
    snapshots the host slot lengths AFTER this dispatch's advance — the
    batcher's out-of-cache retirement check must read the lengths as of
    THIS dispatch, not whatever later dispatches have since added
    (pipeline-on output would otherwise retire early and diverge from
    pipeline-off). ``wait_started()`` blocks until the dispatch holds the
    engine lock: ordering fence for callers about to issue further
    engine calls that must land AFTER this dispatch. ``device_s``
    (valid after ``wait()``) carries the dispatch's sampled device-time
    measurement when devprof took one (obs/devprof.py), None otherwise —
    the batcher joins it onto the flight-recorder event it recorded at
    submit time. ``spans`` (valid after ``wait()``) holds the seconds the
    worker spent in each phase of this dispatch (engine.lock_wait,
    engine.enqueue, engine.readback; for one issued while the dispatch
    before it was still on the device, the read-back alone, from that
    one's tokens to its own): what the batcher's stall judge takes for
    the dispatch's time, since no phase of the scheduler's own thread
    covers it."""

    __slots__ = ("_fut", "_started", "n_steps", "tokens", "lengths",
                 "device_s", "spans")

    def __init__(self, fut, n_steps: int, started: threading.Event) -> None:
        self._fut = fut
        self._started = started
        self.n_steps = int(n_steps)
        self.tokens: Optional[np.ndarray] = None
        self.lengths: Optional[np.ndarray] = None
        self.device_s: Optional[float] = None
        self.spans: Dict[str, float] = {}

    def wait_started(self) -> None:
        if self.tokens is not None or self._fut.done():
            return  # finished implies started; skip the event syscall
        self._started.wait()

    def wait(self) -> np.ndarray:
        if self.tokens is None:
            (self.tokens, self.lengths, self.device_s,
             self.spans) = self._fut.result()
        return self.tokens


class PendingFirstToken:
    """An admission's first token, sampled by the prefill program that was
    just issued and still on the device (engine.prefill_async,
    ChunkedPrefill.step_async).

    The program has already written the token into the state the next
    decode step reads, and the slot's host length and ``active`` flag are
    set, so a decode dispatch may be issued behind the prefill without
    it: the host needs the number only to send it to the client.
    ``wait()`` blocks until the prefill program has finished and returns
    it. It is called outside the engine lock, and lands the devprof
    sample the dispatch was due (graph call -> token on the host)."""

    __slots__ = ("_engine", "_first", "_dtok", "token")

    def __init__(self, engine: "TPUEngine", first, dtok) -> None:
        self._engine = engine
        self._first = first
        self._dtok = dtok
        self.token: Optional[int] = None

    def wait(self) -> int:
        if self.token is None:
            self.token = int(self._first)
            self._first = None
            self._engine._devprof_sample(self._dtok)
        return self.token


class TPUEngine:
    """Single-model decode engine over a fixed set of batch slots."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        num_slots: int = 8,
        max_context: Optional[int] = None,
        cache_dtype=jnp.bfloat16,
        seed: int = 0,
        shardings=None,  # optional ShardingPlan (aios_tpu.parallel.sharding)
        quantize=False,  # serving weights: False/True/"int8"/"int4"
        sharded_attention: Optional[bool] = None,  # shard_map ragged decode
        paged_pool_rows: Optional[int] = None,  # physical KV rows -> paged
        page_size: int = 128,
        prefix_cache: Optional[bool] = None,  # None -> on when paged
        prefix_host_bytes: Optional[int] = None,  # host spill tier budget
        host_restore_min_pages: Optional[int] = None,  # restore floor
        seq_sharded_cache: bool = False,  # shard KV context axis over sp
        track_history: bool = True,  # device-side token history (spec.py)
        prefix_radix: Optional[bool] = None,  # radix-tree prefix index
        draft: Optional["spec.DraftModel"] = None,  # draft-model proposer
        kv_compress_after: Optional[int] = None,  # window+sink threshold rows
        kv_sink_pages: Optional[int] = None,  # live leading (sink) pages
        kv_window_pages: Optional[int] = None,  # live trailing window pages
        seq_prefill_min: Optional[int] = None,  # sp-sharded prefill floor rows
        phases: Optional[flightrec.Phases] = None,  # LoadModel's; None -> own
    ) -> None:
        self.cfg = cfg
        self.num_slots = num_slots
        # Per-step history scatter exists ONLY for the n-gram speculative
        # proposer (spec.py reads history[s, :length+1]); deployments with
        # speculative decode off skip the write and its serial dependency
        # in the decode scan (ModelManager passes track_history=spec).
        self.track_history = bool(track_history)
        self.max_context = int(max_context or cfg.max_context)
        self.buckets = tuple(
            b for b in DEFAULT_BUCKETS if b <= self.max_context
        ) or (self.max_context,)
        self._lock = make_lock("engine")
        # the dispatch bodies' phases (flightrec.PHASES); the batcher
        # that drives this engine adds its own to the same object, and
        # LoadModel, which made it, the set-up's (flightrec.SETUP_PHASES)
        self.phases = phases if phases is not None else flightrec.Phases(cfg.name)
        self.plan = shardings
        # normalize the quantize knob to a mode: True -> int8 (the measured
        # single-chip default), "int4" -> packed-nibble group-wise int4
        # (ops/int4_matmul.py; half the int8 weight bytes). Under a
        # sharding plan int4 runs the kernel per device under shard_map
        # (ShardingPlan.int4_matmul_impl) — column-parallel shards with no
        # collective, row-parallel with the same tp psum GSPMD inserts for
        # the int8 dots — so BASELINE config 4 (Mistral TP) serves the
        # best weight format too.
        if quantize is True:
            quantize = "int8"
        elif not quantize:
            quantize = None
        elif quantize not in ("int8", "int4"):
            raise ValueError(f"unknown quantize mode {quantize!r}")
        self.quant_mode = quantize
        self.quantized = quantize is not None
        # int8 KV cache: half the cache footprint/traffic; scales ride along
        # in the decode state and rows quantize on write inside the graph
        self.quant_cache = cache_dtype == jnp.int8
        refuse_for_state_kind(
            cfg,
            the_dense_slot_cache=paged_pool_rows is None,
            an_int8_KV_pool=self.quant_cache,
            a_sharding_plan=shardings is not None,
            a_context_sharded_cache=bool(seq_sharded_cache),
            a_draft_model_and_the_verify_graph=draft is not None,
        )
        refuse_for_latent_pool(
            cfg,
            the_dense_slot_cache=paged_pool_rows is None,
            an_int8_KV_pool=self.quant_cache,
            a_sharding_plan_and_its_replicated_or_sharded_pool_twins=(
                shardings is not None
            ),
            a_context_sharded_cache=bool(seq_sharded_cache),
            a_draft_model=draft is not None,
        )
        refuse_for_two_kinds(
            cfg,
            the_dense_slot_cache=paged_pool_rows is None,
            an_int8_KV_pool=self.quant_cache,
            a_sharding_plan_or_the_dp_replicated_pool_twin=(
                shardings is not None
            ),
            a_context_sharded_cache=bool(seq_sharded_cache),
            a_draft_model_and_its_speculation=draft is not None,
        )
        if cfg.state_kind == "kda":
            kda.check(cfg)
        # Pallas kernels are per-device programs; under a sharding plan the
        # global-array paths must stay pure XLA (GSPMD partitions those) —
        # EXCEPT decode attention, which is head/slot-local and runs the
        # ragged kernel per device under shard_map (see _attn_impl below).
        self._kernels: Optional[bool] = False if shardings is not None else None
        # MoE: a prefill chunk or bucket, or a verify feed, of enough
        # tokens (moe.grouped_pays, from static shapes) runs each expert
        # over the rows routed to it only, reading the stacked int8 expert
        # weights where they lie (moe.moe_ffn_grouped; the layer scans hand
        # it the stacks whole); fewer tokens, and every decode step, run
        # every held expert over every token (moe.moe_ffn_dense).
        # Under a sharding plan the expert axis may be sharded over ep,
        # where the dense path's contraction is one psum and the grouped
        # path's per-expert reads would cross chips: every graph stays dense.
        self._moe_dense = shardings is not None

        if shardings is not None:
            if _is_prequantized(params):
                if _is_fused_prequantized(params):
                    # the fused concat layout has no TP sharding rule — a
                    # fused w_qkv would interleave q/k/v columns across
                    # shards. Unfused prepared artifacts load fine below.
                    # The recipe names the checkpoint's STORED mode so the
                    # re-prepare doesn't silently change precision.
                    raise ValueError(
                        "this prepared checkpoint stores the FUSED "
                        "single-chip layout; sharded plans need an unfused "
                        "artifact (scripts/prepare_model.py --quantize "
                        f"{_prequantized_mode(params)} --tp {shardings.tp}) "
                        "or the dense source with quantize at load time"
                    )
                # unfused prepared artifact (prepare_model --tp N): leaves
                # already match quantize_params(fuse=False, tp=...) — shard
                # straight to the mesh, no load-time quantization pass (the
                # BASELINE config-4 boot path: no dense-weight transient,
                # no per-boot quantization)
                self.quant_mode = quantize = _resolve_stored_mode(
                    params, quantize
                )
                self.quantized = True
                _validate_prequantized_tp(params, shardings.tp)
                self.params = shardings.put_params(params)
            elif quantize:
                # unfused layout: each projection's output dim shards on tp,
                # scales follow (sharding.py quantized-leaf rules); the
                # int8 x bf16 dot_generals partition like their dense
                # counterparts, with GSPMD inserting the same psums. int4
                # leaves quantize with SHARD-local eligibility/groups
                # (tp=...) — dims whose shards the kernel can't serve fall
                # back to int8 leaves.
                self.params = shardings.put_params(
                    model.quantize_params(
                        params, fuse=False, mode=quantize,
                        tp=shardings.tp,
                    )
                )
            else:
                self.params = shardings.put_params(params)
        else:
            if _is_prequantized(params):
                # prepared serving checkpoint (scripts/prepare_model.py
                # --quantize): the leaves are already {"q","s"}/{"q4","s4"}
                # — restore straight to device, nothing to quantize.
                self.quant_mode = quantize = _resolve_stored_mode(
                    params, quantize, quiet_default=True
                )
                self.quantized = True
                self.params = jax.tree.map(_to_default_device, params)
            elif quantize and not _on_accelerator(params):
                # Host-resident params (GGUF load, checkpoints staged on
                # CPU): quantize on the host CPU backend FIRST, then ship
                # only the quantized leaves. Transferring dense bf16 and
                # quantizing on-device would stage dense + quantized HBM
                # at once — an OOM for the 7B tier on a 16 GB chip.
                cpu = _cpu_device()
                if cpu is not None:
                    with jax.default_device(cpu):
                        qp = model.quantize_params(
                            jax.tree.map(jnp.asarray, params), mode=quantize
                        )
                    # explicit device_put: jnp.asarray on a CPU-committed
                    # jax.Array is an identity and would leave the weights
                    # host-resident (PCIe-speed decode)
                    self.params = jax.tree.map(_to_default_device, qp)
                else:
                    self.params = model.quantize_params(
                        jax.tree.map(jnp.asarray, params), mode=quantize
                    )
            else:
                # _to_default_device, not jnp.asarray: checkpoint restores
                # may hand CPU-COMMITTED jax.Arrays, which asarray would
                # leave on the host
                self.params = jax.tree.map(_to_default_device, params)
                if quantize:
                    self.params = model.quantize_params(
                        self.params, mode=quantize
                    )
        # a latent model's per-head matrices, heads-major from here on
        # (latent.serving_layout): the graphs read no other layout. The
        # engine keeps no reference to the checkpoint layout's arrays;
        # they go when the caller drops its tree (LoadModel does)
        self.latent_leaves_relaid = 0
        if cfg.mla:
            t0 = time.monotonic()
            self.params, self.latent_leaves_relaid = latent.serving_layout(
                self.params, cfg
            )
            if self.latent_leaves_relaid:
                jax.block_until_ready(self.params)
                log.info(
                    "%s: %d latent per-head matrices (w_uq, w_uk, w_uv) "
                    "re-laid heads-major in %.2f s", cfg.name,
                    self.latent_leaves_relaid, time.monotonic() - t0,
                )

        # Context-sharded KV: the cache's C axis splits over the mesh's sp
        # axis, so one slot's KV can exceed a single chip's HBM — XLA
        # partitions the decode attention over the sharded contraction
        # (partial softmax stats + psum over sp; sharding.CACHE_SPEC_SEQ).
        self.seq_sharded = bool(seq_sharded_cache)
        if self.seq_sharded:
            if shardings is None:
                raise ValueError("seq_sharded_cache needs a sharding plan")
            if paged_pool_rows is not None:
                raise ValueError(
                    "seq_sharded_cache and the paged pool are exclusive"
                )
            if self.max_context % shardings.sp:
                raise ValueError(
                    f"max_context {self.max_context} must divide by "
                    f"sp={shardings.sp} for a context-sharded cache"
                )

        # Ragged decode attention under shard_map: auto on TPU meshes with a
        # bf16 cache long enough for the kernel to win (same crossover as
        # the single-chip ladder); force with sharded_attention=True to
        # exercise the path on CPU virtual meshes (jnp reference body).
        self._attn_impl = None
        if sharded_attention and (shardings is None or self.quant_cache):
            raise ValueError(
                "sharded_attention=True needs a sharding plan and a bf16 KV "
                "cache (the ragged kernel reads bf16 caches only)"
            )
        if sharded_attention and self.seq_sharded:
            raise ValueError(
                "sharded_attention=True is incompatible with "
                "seq_sharded_cache: the shard_map ragged kernel assumes "
                "each device holds whole slots' context"
            )
        on_tpu = backend.on_tpu()
        if shardings is not None and not self.quant_cache and not self.seq_sharded:
            enable = (
                sharded_attention
                if sharded_attention is not None
                else on_tpu and self.max_context >= 2048
            )
            if enable:
                self._attn_impl = shardings.ragged_attention(
                    cfg.sliding_window, use_kernel=on_tpu
                )

        # int4 matmuls under a plan: matmul()'s default ladder would run
        # the per-device Pallas kernel on GSPMD-sharded GLOBAL arrays, so
        # every sharded consumer of q4 leaves must get an explicit impl —
        #   * decode steps: shard_map per-device kernel (bandwidth-bound,
        #     the path the int4 format exists for)
        #   * prefill / chunked prefill / speculative verify: the jnp
        #     reference body on global arrays, which GSPMD partitions like
        #     any dot (compute-bound passes; the inline dequant is noise
        #     there, and their [1, T, E] / [B, T, E] shapes don't fit the
        #     decode-shaped shard_map specs)
        self._qmm_impl = None
        self._qmm_gspmd = None
        if shardings is not None and quantize == "int4":
            from ..ops.int4_matmul import int4_matmul_reference

            self._qmm_impl = shardings.int4_matmul_impl(use_kernel=on_tpu)
            self._qmm_gspmd = (
                lambda x, leaf, kind: int4_matmul_reference(
                    x, leaf["q4"], leaf["s4"]
                )
            )

        # Paged KV cache: HBM is reserved per page IN USE, not per
        # num_slots x max_context — many long-context slots oversubscribe a
        # fixed pool (SURVEY.md section 7.2). Logical layout and outputs are
        # identical to the dense cache; the page indirection lives in
        # engine/paged.py (tables) + ops/paged_attention.py (reads).
        self.paged = paged_pool_rows is not None
        self.allocator: Optional[paged.PageAllocator] = None
        self.prefix_index: Optional[paged.PrefixIndex] = None
        self._prefix_chunk: Optional[int] = None
        self._pool_impl = None
        self._paged_scatter = None
        self.pool_replicas = 1
        # a stack of window and full layers: where each kind's pages lie
        # (paged.PoolLayout, a constant of the paged graphs), the window
        # kind's side of prefix sharing, and the hits it cut short
        self._layout = None
        self._whole_prompt_rows: Optional[int] = None
        self.window_prefix: Optional[paged.WindowPrefixPages] = None
        self.prefix_hits_refused_window = 0
        # the state kind (a stack with kda layers: paged.py's header): its
        # host-side account, the hits refused for want of a state and the
        # rows they would have served, and the rows through the state
        # layers by graph kind (rows x such layers, padding included:
        # ``kda_rows_*`` or ``mamba_rows_*`` in stats(), by the kind)
        self.slot_states: Optional[paged.SlotStates] = None
        self.refused_prefixes: Optional[paged.SeenPrefixes] = None
        self.prefix_hits_refused_state = 0
        self.prefix_rows_refused_state = 0
        self.state_rows_prefill = 0
        self.state_rows_decode = 0
        # key tiles the paged chunks' attention folded, and those the
        # slots' tables map (ChunkedPrefill.step_async)
        self.prefill_kv_tiles_read = 0
        self.prefill_kv_tiles_mapped = 0
        # the donated state's two keys of the state kind's arrays
        self._state_keys = (f"{cfg.state_kind}_s", f"{cfg.state_kind}_tail")
        if self.paged:
            # sp in the mesh: the pool (like any non-seq-sharded cache)
            # REPLICATES over the sp axis — its shard_map specs name only
            # dp/tp, so each sp slice runs the identical pool program. A
            # context that must SHARD over sp (exceeding per-chip HBM)
            # uses seq_sharded_cache instead — pages hold contiguous rows
            # of one slot and cannot split across sp shards; the model
            # manager's HBM-budget check picks between the two per model.
            if page_size < 1 or page_size & (page_size - 1):
                # chunked admission relies on power-of-two chunk/page sizes
                # never straddling (model.prefill_chunk_paged)
                raise ValueError(f"page_size {page_size} must be a power of 2")
            if self.max_context % page_size:
                raise ValueError(
                    f"max_context {self.max_context} must be a multiple of "
                    f"page_size {page_size}"
                )
            R = shardings.dp if shardings is not None else 1
            self.pool_replicas = R
            max_blocks = self.max_context // page_size
            # per replica: one sacrificial page + its share of the pool
            local_pages = 1 + max(
                1, -(-int(paged_pool_rows) // (page_size * R))
            )
            num_pages = R * local_pages
            if cfg.kinds:
                # pages by kind (paged.py's header): the full kind as any
                # model's; the window kind what the same count of contexts
                # (paged_pool_rows / max_context) can hold of a window and
                # an admission chunk in flight
                slot_rows = window_rows_a_slot(
                    cfg, page_size, self.max_context,
                    self.prefill_chunk_default,
                )
                window_pool_rows = -(
                    -int(paged_pool_rows) // self.max_context
                ) * slot_rows
                self.allocator = paged.KindPageAllocator(
                    local_pages, 1 + window_pool_rows // page_size,
                    page_size, num_slots, max_blocks, cfg.period_kinds,
                )
                self._layout = self.allocator.layout
                # a whole-prompt prefill leaves every row in the window
                # kind until the first decode trims: it serves prompts
                # within ONE slot's share, longer ones admit in chunks
                self._whole_prompt_rows = min(
                    slot_rows,
                    self.allocator.window.capacity_blocks() * page_size,
                )
                pool_shape = (cfg.num_layers // cfg.period, self._layout.pages)
            else:
                self.allocator = paged.PageAllocator(
                    num_pages, page_size, num_slots, max_blocks, replicas=R
                )
                # (a stack with a state kind: the layers that keep rows alone)
                pool_shape = (cfg.row_layers, num_pages)
            if cfg.state_kinds:
                self.slot_states = paged.SlotStates.of(cfg, num_slots)
                # every prompt admits in chunks: a chunk graph takes the
                # slot and the count of real rows, which the state needs
                self._whole_prompt_rows = 0
            # THE stored layout (paged.py's header): a row's kv heads
            # merged on the last axis; for latent attention the latents
            # and the padded rotary parts (ModelConfig.kv_row_dims)
            k, v = (
                jnp.zeros((*pool_shape, page_size, width), cache_dtype)
                for width in cfg.kv_row_dims
            )
            if R > 1:
                # dp-replicated pool: page ops must run per device under
                # shard_map (table ids are replica-local; a GSPMD gather
                # could not prove locality and would all-gather the pool).
                # Chunked admission and the prefix index stay off — both
                # read the pool during per-slot admission, which the
                # whole-prompt scatter path avoids.
                self._pool_impl = shardings.paged_pool_impl(
                    cfg.sliding_window, use_kernel=on_tpu,
                    quantized=self.quant_cache,
                )
                self._paged_scatter = shardings.paged_prefill_scatter(
                    quantized=self.quant_cache
                )
                self.prefill_chunk_default = 0  # instance override
                if prefix_cache:
                    log.info(
                        "prefix cache disabled: pages are replica-local "
                        "under a dp-partitioned pool"
                    )
                prefix_cache = False
            # Prefix caching rides on the page pool: prompts whose leading
            # full blocks hash-match an earlier prompt map those pages
            # instead of recomputing them (paged.PrefixIndex). The tail
            # (always >= 1 token) admits through the chunked path, which
            # attends over the mapped prefix for free. Matching needs a
            # chunk size the bucket grid can honour.
            self._prefix_chunk = max(
                (b for b in self.buckets
                 if b <= self.prefill_chunk_default
                 and self.max_context % b == 0),
                default=None,
            )
            if cfg.state_kinds and self._prefix_chunk is None:
                raise ValueError(
                    f"{cfg.name}: a stack with {cfg.state_kind} layers admits every "
                    f"prompt in chunks, and no prefill bucket up to "
                    f"{self.prefill_chunk_default} divides the context "
                    f"{self.max_context}"
                )
            if prefix_cache is None:
                prefix_cache = True
            if prefix_cache and self._prefix_chunk is not None:
                # radix tree by default (cross-request sharing by
                # construction, leaf-LRU eviction, partial-node overlap
                # credit for the router); AIOS_TPU_PREFIX_RADIX=0 /
                # ModelConfig.prefix_radix=False is the escape hatch back
                # to the flat hash-chain map
                if prefix_radix is None:
                    prefix_radix = _env_flag("AIOS_TPU_PREFIX_RADIX")
                if prefix_radix is None:
                    prefix_radix = bool(getattr(cfg, "prefix_radix", True))
                index_cls = (
                    paged.RadixPrefixIndex if prefix_radix
                    else paged.PrefixIndex
                )
                if cfg.state_kinds:
                    # no hit can be served without the state at its end
                    # (paged.py's header): the hashes alone are kept, to
                    # count the hits refused
                    self.refused_prefixes = paged.SeenPrefixes(num_pages)
                elif cfg.kinds:
                    # the index holds the full kind's pages; the window
                    # kind's ride beside it (paged.py's header)
                    self.prefix_index = index_cls(
                        self.allocator.full, max_pages=local_pages
                    )
                    self.window_prefix = paged.WindowPrefixPages(
                        self.allocator.window
                    )
                else:
                    self.prefix_index = index_cls(
                        self.allocator, max_pages=num_pages
                    )
        else:
            prefix_host_bytes = 0
            k, v = model.init_kv_cache(
                cfg, num_slots, self.max_context, cache_dtype
            )
        # speculative verify does global pool scatters; under a
        # dp-partitioned pool those need a shard_map twin that does not
        # exist yet — refuse rather than corrupt replica-local pages
        # (and a rejected token could not be rolled back out of a recurrent
        # state: no verify graph for a stack with kda layers either)
        self.spec_supported = not (
            (self.paged and self.pool_replicas > 1) or cfg.state_kinds
        )

        # -- Long-context tier (docs/ENGINE_PERF.md "Long-context tier") --
        # (1) Window+sink KV compression: past kv_compress_after rows a
        # slot's paged KV prunes to kv_sink_pages leading pages plus a
        # kv_window_pages trailing window (SnapStream/StreamingLLM-style,
        # PAPERS.md) — freed pages return to the pool (or survive under
        # their prefix-index references and spill through the PR 4 host
        # tier), and every attention graph masks the pruned middle via a
        # per-slot window-start operand that rides beside the page
        # tables. win_start = 0 keeps the mask a no-op, so below the
        # threshold streams are token-exact.
        def knob(explicit, env, default):
            # explicit constructor arg > env > ModelConfig default — the
            # prefix_radix resolution convention
            if explicit is not None:
                return int(explicit)
            v = _env_int(env)
            return int(default) if v is None else v

        self.kv_compress_after = knob(
            kv_compress_after, "AIOS_TPU_KV_COMPRESS_AFTER",
            getattr(cfg, "kv_compress_after", 0),
        )
        self.kv_sink_pages = max(knob(
            kv_sink_pages, "AIOS_TPU_KV_SINK_PAGES",
            getattr(cfg, "kv_sink_pages", 1),
        ), 1)
        self.kv_window_pages = max(knob(
            kv_window_pages, "AIOS_TPU_KV_WINDOW_PAGES",
            getattr(cfg, "kv_window_pages", 8),
        ), 1)
        self.kv_compress_armed = False
        self._sink_rows = 0
        refuse_for_state_kind(
            cfg, window_and_sink_KV_compression=self.kv_compress_after > 0
        )
        refuse_for_latent_pool(
            cfg, window_and_sink_KV_compression=self.kv_compress_after > 0
        )
        refuse_for_two_kinds(
            cfg, window_and_sink_KV_compression=self.kv_compress_after > 0
        )
        if self.kv_compress_after > 0:
            if not self.paged or self.pool_replicas > 1:
                log.warning(
                    "%s: kv_compress_after needs a paged, unreplicated "
                    "KV pool; compression disabled", cfg.name,
                )
            elif cfg.sliding_window is not None:
                log.warning(
                    "%s: kv_compress_after is redundant under a model "
                    "sliding window (residency is already bounded); "
                    "compression disabled", cfg.name,
                )
            else:
                P = self.allocator.page_size
                # the pruned mask needs sink + window to fit under the
                # threshold, or an armed slot could prune rows it is
                # still token-exactly below the threshold for
                floor = (self.kv_sink_pages + self.kv_window_pages) * P
                if self.kv_compress_after < floor:
                    log.info(
                        "%s: kv_compress_after %d raised to sink+window "
                        "floor %d", cfg.name, self.kv_compress_after,
                        floor,
                    )
                    self.kv_compress_after = floor
                self.kv_compress_armed = True
                self._sink_rows = self.kv_sink_pages * P
        # per-slot live-window start in ROWS (0 = uncompressed); rides
        # beside the page tables as a dispatch operand, never in the
        # donated state
        self._win_starts = np.zeros(num_slots, dtype=np.int32)
        self.kv_compress_slots = 0  # slots that crossed the threshold
        self.kv_pages_pruned = 0  # pages released by pruning

        # (2) Sequence-sharded prefill: prompts >= seq_prefill_min rows
        # prefill in ONE dispatch with the sequence sharded over the
        # mesh's sp axis (parallel/ring_attention.py make_ring_attn_fn /
        # ulysses.py make_ulysses_attn_fn) instead of serially through
        # chunked admission; the resulting KV scatters back into the
        # normal paged layout so decode, prefix-cache insertion,
        # spill/restore and failover see nothing new.
        self.seq_prefill_min = knob(
            seq_prefill_min, "AIOS_TPU_SEQ_PREFILL_MIN",
            getattr(cfg, "seq_prefill_min", 0),
        )
        refuse_for_state_kind(
            cfg, sequence_sharded_prefill=self.seq_prefill_min > 0
        )
        refuse_for_latent_pool(
            cfg, sequence_sharded_prefill=self.seq_prefill_min > 0
        )
        refuse_for_two_kinds(
            cfg, sequence_sharded_prefill=self.seq_prefill_min > 0
        )
        self._seq_attn = None
        self._seq_prefill_fns: Dict[int, object] = {}
        self.prefill_seq_sharded = 0
        if self.seq_prefill_min > 0:
            sp = shardings.sp if shardings is not None else 1
            if not self.paged or self.pool_replicas > 1 or sp <= 1:
                log.warning(
                    "%s: seq_prefill_min needs a paged, unreplicated "
                    "pool and a sharding plan with sp > 1; "
                    "sequence-sharded prefill disabled", cfg.name,
                )
                self.seq_prefill_min = 0
            else:
                impl = os.environ.get(
                    "AIOS_TPU_SEQ_PREFILL_IMPL", "ring"
                ).strip().lower() or "ring"
                if impl == "ulysses" and (
                    cfg.num_heads % sp or cfg.num_kv_heads % sp
                ):
                    log.warning(
                        "%s: ulysses seq prefill needs heads (%d/%d) "
                        "divisible by sp=%d; using ring", cfg.name,
                        cfg.num_heads, cfg.num_kv_heads, sp,
                    )
                    impl = "ring"
                if impl == "ulysses":
                    from ..parallel.ulysses import make_ulysses_attn_fn

                    self._seq_attn = make_ulysses_attn_fn(
                        shardings.mesh, "sp", window=cfg.sliding_window
                    )
                else:
                    from ..parallel.ring_attention import make_ring_attn_fn

                    self._seq_attn = make_ring_attn_fn(
                        shardings.mesh, "sp", window=cfg.sliding_window
                    )
                # routed buckets are powers of two >= sp (sp is a
                # power-of-two mesh axis), so the shard split is exact
                self.seq_prefill_min = max(self.seq_prefill_min, sp)
        if shardings is not None and self.paged:
            k, v = shardings.put_pool(k), shardings.put_pool(v)
        elif shardings is not None:
            k = shardings.put_cache(k, seq_shard=self.seq_sharded)
            v = shardings.put_cache(v, seq_shard=self.seq_sharded)
        states = ()
        if self.slot_states is not None:
            with self.phases.phase("load.states"):
                states = jax.block_until_ready((
                    jnp.zeros(self.slot_states.state_shape, jnp.float32),
                    jnp.zeros(self.slot_states.tail_shape, cache_dtype),
                ))
        self.state: DecodeState = {
            "k": k,
            "v": v,
            "lengths": jnp.zeros((num_slots,), jnp.int32),
            "last_tokens": jnp.zeros((num_slots,), jnp.int32),
            "temps": jnp.zeros((num_slots,), jnp.float32),
            "top_ps": jnp.ones((num_slots,), jnp.float32),
            # device-side mirror of the host `active` array: inactive slots
            # cost no cache bandwidth in decode and write only to the
            # sacrificial last row (model.decode_step)
            "active": jnp.zeros((num_slots,), jnp.bool_),
            # per-slot token history (prompt + generated) for device-side
            # n-gram draft proposal (spec.py); history[s, :lengths[s]+1]
            # mirrors cache rows + the pending last token
            "history": spec.init_history(num_slots, self.max_context),
            "key": jax.random.PRNGKey(seed),
        }
        # a model with a router counts its picks, the rows its expert
        # matmuls computed and the experts they read on the device
        # (moe.pick_stats): every graph adds to this, and a decode dispatch
        # hands the sum back with its tokens
        self.counts_picks = cfg.moe
        self.moe_picks_total = 0
        self.moe_picks_local = 0
        self.moe_expert_rows = 0
        self.moe_experts_visited = 0
        if self.counts_picks:
            self.state["moe_stats"] = model.zero_stats(cfg)[0]
        if states:
            for key, array in zip(self._state_keys, states):
                self.state[key] = array
        # rows x sub-layers whose residual was mixed (engine/residual.py),
        # from each dispatched program's static shapes: host-side, no
        # device work (`_devprof_note` sees every dispatch)
        self.hc_mix_rows = 0
        if self.quant_cache:
            if self.paged:
                # per-(page, row, kv-head) scales alongside the int8 pool
                s_shape = (
                    cfg.num_layers, k.shape[1], page_size, cfg.num_kv_heads,
                )
                k_s = jnp.ones(s_shape, jnp.float32)
                v_s = jnp.ones(s_shape, jnp.float32)
                if shardings is not None:
                    # pool scales [L, N, P, KH]: same spec as dense scales
                    # ([L, S, C, KH]) — axis 1 rides the size-1 dp axis,
                    # kv heads shard over tp
                    k_s = shardings.put_cache_scales(k_s)
                    v_s = shardings.put_cache_scales(v_s)
            else:
                k_s, v_s = model.init_kv_scales(
                    cfg, num_slots, self.max_context
                )
                if shardings is not None:
                    k_s = shardings.put_cache_scales(
                        k_s, seq_shard=self.seq_sharded
                    )
                    v_s = shardings.put_cache_scales(
                        v_s, seq_shard=self.seq_sharded
                    )
            self.state["k_s"] = k_s
            self.state["v_s"] = v_s

        # Draft-model speculation (spec.DraftModel): the small tier
        # proposes, the serving model verifies — single-device only (the
        # draft cache and its graphs have no shard_map twins), on top of
        # the same verify machinery/track-history requirements as n-gram
        # speculation. A config that can't carry it FALLS BACK to n-gram
        # (the batcher's proposer ladder) rather than failing the load;
        # a vocab mismatch is a hard error — draft tokens feed the
        # serving verify directly, so it could never produce sense.
        self.draft: Optional[spec.DraftModel] = None
        self.draft_state = None
        self._draft_host_lengths = np.zeros(num_slots, dtype=np.int64)
        # host mirror of "slot decodes greedily" (set at admission):
        # only greedy slots ever propose, so the bulk-ingest gap math
        # skips sampling slots instead of building draft KV their ok
        # gate guarantees is never read
        self._host_greedy = np.zeros(num_slots, dtype=bool)
        self._draft_fns: Dict[object, object] = {}
        if draft is not None:
            if draft.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft model vocab ({draft.cfg.vocab_size}) must "
                    f"match the serving model's ({cfg.vocab_size}) — they "
                    "must share one tokenizer"
                )
            if shardings is not None:
                log.warning(
                    "%s: draft-model speculation is single-device only; "
                    "falling back to the n-gram proposer under a sharding "
                    "plan", cfg.name,
                )
            elif not self.spec_supported:
                log.warning(
                    "%s: draft-model speculation unsupported on a "
                    "dp-replicated page pool; falling back to the n-gram "
                    "proposer", cfg.name,
                )
            elif not self.track_history:
                log.warning(
                    "%s: draft-model speculation needs the token history "
                    "(track_history=True); draft model ignored", cfg.name,
                )
            else:
                self.draft = draft
                # draft cache rows mirror history columns 1:1, so it is
                # sized to the SERVING context; bf16 stands in when the
                # serving cache is int8 (the draft path has no scales)
                self.draft_state = draft.init_state(
                    num_slots, self.max_context,
                    cache_dtype=(
                        cache_dtype if cache_dtype != jnp.int8
                        else jnp.bfloat16
                    ),
                )

        # host-side mirror for the scheduler
        self.active = np.zeros(num_slots, dtype=bool)
        self._host_lengths = np.zeros(num_slots, dtype=np.int64)

        self._step_fns: Dict[int, object] = {}
        self._prefill_fns: Dict[int, object] = {}
        self._chunk_fns: Dict[Tuple[int, bool], object] = {}
        self._spec_fns: Dict[Tuple[int, int, int], object] = {}
        self._restore_fns: Dict[int, object] = {}
        self._jump_fns: Dict[int, object] = {}  # run-length-bucketed
        # the dispatch workers behind step_async (built lazily: only
        # pipelined batchers use them); FIFO order is the dispatch
        # ordering contract, kept by each dispatch waiting for the one
        # before it to hold the engine lock (_dispatch_started)
        self._dispatch_pool = None
        self._dispatch_started: Optional[threading.Event] = None
        # when the last decode dispatch's tokens reached the host
        self._tokens_ready = 0.0
        self.decode_steps = 0
        self.prefix_rows_reused = 0
        self.prefix_rows_restored = 0
        # prefix matches that took the submitter's hashes (the prompt was
        # not hashed under the engine lock), and matched prefixes whose
        # history backfill was not issued because no history is kept
        self.admissions_prehashed = 0
        self.history_backfills_skipped = 0

        # Host-RAM spill tier behind the prefix cache: HBM evictions copy
        # their page KV device->host (paged.HostPageStore) instead of
        # dropping it; a later hash-chain hit restores the pages with a
        # device_put + scatter instead of a prefill forward pass. The
        # copy-out runs on a background thread (the engine lock only pays
        # for enqueuing the device-side gather); restores shorter than
        # host_restore_min_pages fall through to normal prefill (a short
        # device_put can lose to recompute).
        if prefix_host_bytes is None:
            prefix_host_bytes = getattr(cfg, "prefix_host_bytes", 0)
        refuse_for_state_kind(
            cfg,
            the_host_spill_tier_and_its_KVX_entries=(
                int(prefix_host_bytes or 0) > 0
            ),
        )
        refuse_for_latent_pool(
            cfg, the_host_spill_tier=int(prefix_host_bytes or 0) > 0
        )
        refuse_for_two_kinds(
            cfg,
            the_host_spill_tier_and_its_KVX_entries=(
                int(prefix_host_bytes or 0) > 0
            ),
        )
        self.host_store: Optional[paged.HostPageStore] = None
        self.host_restore_min_pages = max(int(host_restore_min_pages or 1), 1)
        self.host_restore_seconds = 0.0
        self._obs_restore_hist = None
        self._spill_q: Optional[object] = None
        self._spill_thread: Optional[threading.Thread] = None
        if self.prefix_index is not None and int(prefix_host_bytes) > 0:
            import queue as _queue

            self.host_store = paged.HostPageStore(int(prefix_host_bytes))
            # BOUNDED in PAGES: each queued batch pins its materialized
            # device-side gather copies until the worker lands them in
            # host RAM, so unbounded spilling would let an eviction burst
            # transiently hold many pools' worth of extra HBM on a chip
            # already sized near capacity. Pending pages are capped at
            # one pool's worth; past that, spills drop (plain eviction).
            self._spill_q = _queue.Queue()
            # pending-page counter shared by the engine thread (raise) and
            # the worker (lower) — int += is a read-modify-write, NOT
            # GIL-atomic, so it gets its own tiny lock
            self._spill_pending = 0  #: guarded_by _spill_lock
            self._spill_lock = make_lock("engine_spill")
            self._spill_max_pending = max(
                16, self.allocator.capacity_blocks()
            )
            import weakref

            # the worker must NOT root the engine (a bound-method target
            # would pin params + pool state forever if the engine were
            # dropped without close()) — it takes the queue/store/lock
            # directly and the pending counter through a weakref, the
            # same collectibility pattern as the _register_gauges
            # closures
            self._spill_thread = threading.Thread(
                target=TPUEngine._spill_worker,
                args=(self._spill_q, self.host_store, self._spill_lock,
                      weakref.ref(self)),
                name=f"prefix-host-spill-{cfg.name}",
                daemon=True,
            )
            self._spill_thread.start()
            self.prefix_index.spill = self._spill_pages
        self.spec_rounds = 0
        self.spec_tokens = 0
        self.spec_slot_rounds = 0
        # per-proposer splits of the speculative counters (the
        # aios_tpu_spec_*{proposer=...} label): rounds dispatched and
        # draft tokens accepted, keyed by spec.SPEC_PROPOSERS
        self.spec_proposer_rounds = {p: 0 for p in spec.SPEC_PROPOSERS}
        self.spec_proposer_accepted = {p: 0 for p in spec.SPEC_PROPOSERS}
        # draft-side dispatch accounting: bulk ingest dispatches (the
        # catch-up KV writes outside the fused round) and tokens proposed
        self.draft_ingest_dispatches = 0
        self.draft_proposed_tokens = 0
        # grammar jump-ahead accounting (jump_step): dispatches and the
        # forced tokens they appended — each dispatch replaced
        # jump_tokens/jump_dispatches masked single-token dispatches
        self.jump_dispatches = 0
        self.jump_tokens = 0
        # XLA compile-event accounting: every new jit graph counts once
        # and its FIRST dispatch's wall time — jax compiles synchronously
        # inside that call — is recorded as the compile stall. stats(),
        # bench.py, and the aios_tpu_engine_xla_* instruments all read
        # these, so a mid-serving compile (the TTFT-stall class warmup
        # exists to prevent) is visible instead of a mystery latency spike.
        self.compile_events = 0
        self.compile_seconds = 0.0
        self.warmup_trace_cpu_seconds = 0.0
        # Device-time attribution (obs/devprof.py): per-graph cost
        # ledger + sampled dispatch timing, OFF by default — the hot
        # paths pay one attribute None-check, the faults/ pattern. Read
        # at construction like the pipeline knob: a live engine never
        # grows instrumentation mid-serving.
        self._devprof: Optional[devprof.DevprofLedger] = None
        if devprof.enabled():
            self._devprof = devprof.DevprofLedger(cfg.name)
        self._obs_decode_steps = obs.ENGINE_DECODE_STEPS.labels(model=cfg.name)
        self._register_gauges()

    def _register_gauges(self) -> None:
        """Scrape-time gauges over live engine state. weakref-bound so a
        closed engine (close() frees HBM deterministically) can still be
        garbage-collected; a model reload under the same name re-registers
        and the stale callback is replaced."""
        import weakref

        name = self.cfg.name
        ref = weakref.ref(self)

        def slots() -> float:
            e = ref()
            return float(e.active.sum()) if e is not None else 0.0

        def occupancy() -> float:
            e = ref()
            if e is None or not e.num_slots:
                return 0.0
            return float(e.active.sum()) / e.num_slots

        obs.ENGINE_SLOTS_IN_USE.labels(model=name).set_function(slots)
        obs.ENGINE_OCCUPANCY.labels(model=name).set_function(occupancy)
        # jump-ahead + speculative counters: replica engines share the
        # (model,) label and set_function is last-writer-wins, so these
        # read a per-model WeakSet of live engines and report the SUM
        # (the aios_tpu_prefix_host_* aggregation pattern). Dead engines
        # drop out when collected.
        engines = _ENGINES_BY_MODEL.setdefault(name, weakref.WeakSet())
        engines.add(self)

        def engines_sum(attr):
            def read() -> float:
                return float(sum(getattr(e, attr) for e in engines))

            return read

        obs.ENGINE_JUMP_DISPATCHES.labels(model=name).set_function(
            engines_sum("jump_dispatches")
        )
        obs.ENGINE_JUMP_TOKENS.labels(model=name).set_function(
            engines_sum("jump_tokens")
        )
        # long-context tier: compression + sequence-sharded prefill
        # counters (same WeakSet-summed monotonic-engine-counter pattern)
        obs.KV_COMPRESS_SLOTS.labels(model=name).set_function(
            engines_sum("kv_compress_slots")
        )
        obs.KV_COMPRESS_PAGES_PRUNED.labels(model=name).set_function(
            engines_sum("kv_pages_pruned")
        )
        obs.PREFILL_SEQ_SHARDED.labels(model=name).set_function(
            engines_sum("prefill_seq_sharded")
        )

        def compressed_resident() -> float:
            return float(sum(
                e.compressed_resident_pages() for e in engines
            ))

        obs.KV_COMPRESS_RESIDENT.labels(model=name).set_function(
            compressed_resident
        )
        # spec counters carry the (model, proposer) label pair — one
        # series per proposer in the closed spec.SPEC_PROPOSERS enum,
        # each summing its per-proposer engine counter over the WeakSet
        def proposer_sum(attr, proposer):
            def read() -> float:
                return float(sum(
                    getattr(e, attr).get(proposer, 0) for e in engines
                ))

            return read

        for p in spec.SPEC_PROPOSERS:
            obs.SPEC_ROUNDS.labels(model=name, proposer=p).set_function(
                proposer_sum("spec_proposer_rounds", p)
            )
            obs.SPEC_ACCEPTED.labels(model=name, proposer=p).set_function(
                proposer_sum("spec_proposer_accepted", p)
            )
        if self._devprof is not None:
            # devprof family: per-graph children iterate the CLOSED
            # devprof.GRAPH_KINDS enum (the SLO-objectives pattern) and
            # SUM over the per-model WeakSet of replica ledgers. The
            # MFU / HBM-utilization gauges register only when the
            # device_kind's roofline is known (docs/HARDWARE.md) —
            # unknown kinds keep raw seconds and omit the ratios.
            ledgers = devprof.ledgers_for(name)

            def ledger_sum(kind, idx):
                def read() -> float:
                    return float(sum(
                        led.totals(kind)[idx] for led in ledgers
                    ))

                return read

            def ledger_device_s(kind):
                def read() -> float:
                    return float(sum(
                        led.device_seconds(kind) for led in ledgers
                    ))

                return read

            def ledger_util(kind, idx, peak_idx):
                # weighted across replicas: sum sampled flops/bytes over
                # sum sampled seconds (a per-replica mean-of-ratios
                # would over-weight idle replicas)
                def read() -> float:
                    num = sum(led.totals(kind)[idx] for led in ledgers)
                    den = sum(led.totals(kind)[4] for led in ledgers)
                    peaks = next(
                        (led.peaks for led in ledgers
                         if led.peaks is not None), None,
                    )
                    if not den or peaks is None:
                        return 0.0
                    return float(num / den / peaks[peak_idx])

                return read

            roofline = self._devprof.peaks is not None
            for g in devprof.GRAPH_KINDS:
                obs.DEVPROF_DISPATCHES.labels(
                    model=name, graph=g
                ).set_function(ledger_sum(g, 0))
                obs.DEVPROF_DEVICE_SECONDS.labels(
                    model=name, graph=g
                ).set_function(ledger_device_s(g))
                if roofline:
                    obs.DEVPROF_MFU.labels(
                        model=name, graph=g
                    ).set_function(ledger_util(g, 5, 0))
                    obs.DEVPROF_HBM_UTIL.labels(
                        model=name, graph=g
                    ).set_function(ledger_util(g, 6, 1))
        if self.allocator is not None:
            def pages_in_use() -> float:
                e = ref()
                return float(e.allocator.pages_in_use()) if e is not None else 0.0

            def page_util() -> float:
                e = ref()
                if e is None:
                    return 0.0
                total = e.allocator.pages_in_use() + e.allocator.free_pages
                return e.allocator.pages_in_use() / total if total else 0.0

            obs.ENGINE_KV_PAGES_IN_USE.labels(model=name).set_function(
                pages_in_use
            )
            obs.ENGINE_KV_PAGE_UTILIZATION.labels(model=name).set_function(
                page_util
            )
        if self.prefix_index is not None:
            def hits() -> float:
                e = ref()
                ix = e.prefix_index if e is not None else None
                return float(ix.hits) if ix is not None else 0.0

            def misses() -> float:
                e = ref()
                ix = e.prefix_index if e is not None else None
                return float(ix.misses) if ix is not None else 0.0

            obs.ENGINE_PREFIX_HITS.labels(model=name).set_function(hits)
            obs.ENGINE_PREFIX_MISSES.labels(model=name).set_function(misses)
        if self.host_store is not None:
            # Replica engines share the (model,) label, and set_function
            # is last-writer-wins — so every replica's callback reads a
            # shared per-model WeakSet of live stores and reports the SUM,
            # matching the pool.stats() aggregate. Dead pools drop out of
            # the set when their engines are collected.
            stores = _HOST_STORES_BY_MODEL.setdefault(name, weakref.WeakSet())
            stores.add(self.host_store)

            def store_stat(attr):
                def read() -> float:
                    return float(sum(getattr(s, attr) for s in stores))

                return read

            obs.PREFIX_HOST_BYTES.labels(model=name).set_function(
                store_stat("bytes_resident")
            )
            obs.PREFIX_HOST_SPILLS.labels(model=name).set_function(
                store_stat("spills")
            )
            obs.PREFIX_HOST_RESTORES.labels(model=name).set_function(
                store_stat("restores")
            )
            obs.PREFIX_HOST_HITS.labels(model=name).set_function(
                store_stat("hits")
            )
            obs.PREFIX_HOST_MISSES.labels(model=name).set_function(
                store_stat("misses")
            )
            obs.PREFIX_HOST_MISSES_CORRUPT.labels(model=name).set_function(
                store_stat("corruptions")
            )
            self._obs_restore_hist = obs.PREFIX_HOST_RESTORE_SECONDS.labels(
                model=name
            )

    # -- jitted cores -------------------------------------------------------

    def _tables_operand(self):
        """The per-dispatch paged operand: the page tables, paired with
        the per-slot live-window starts when window+sink KV compression
        is armed (the mask operand rides BESIDE the tables rather than in
        the donated state — it changes only at prune events, exactly like
        the tables change only at alloc events). Caller holds the engine
        lock."""
        # aios: waive(lock-readback): the decode dispatch's own operand, placed by the thread that makes the graph call (the worker); the tables change under the lock until this instant, and one placement a dispatch is what engine.enqueue_ms has always held
        t = jnp.asarray(self.allocator.tables)
        if self.kv_compress_armed:
            # aios: waive(lock-readback): as the tables beside it
            return (t, jnp.asarray(self._win_starts))
        return t

    def _states_of(self, st: DecodeState) -> tuple:
        """The state kind's arrays of a decode state, () where the model has
        no such kind."""
        if self.slot_states is None:
            return ()
        return tuple(st[key] for key in self._state_keys)

    @staticmethod
    def _split_tables(tables):
        """Unpack a ``_tables_operand`` value into (tables, win_starts);
        win_starts is None on engines without compression armed (their
        graphs are byte-identical to the pre-compression tree)."""
        if isinstance(tables, (tuple, list)):
            return tables[0], tables[1]
        return tables, None

    def _decode_body(self, params, st: DecodeState, sub, tables=None,
                     mask=None):
        """ONE decode step against whichever cache layout this engine runs
        — the body of the per-size scan graphs (``_step_impl``). Only the
        model call differs between the dense, int8-KV and paged layouts;
        sampling, history gating and the state rebuild are shared.
        ``mask`` [S, V] fp32 adds to the logits before sampling — the
        grammar-constraint hook (engine/jsonmode.py), step_masked only."""
        if self.paged:
            tables, win_starts = self._split_tables(tables)
            scales = (
                (st["k_s"], st["v_s"]) if self.quant_cache else None
            )
            out = model.decode_step_paged(
                params,
                self.cfg,
                st["last_tokens"],
                st["lengths"],
                st["k"],
                st["v"],
                tables,
                kernels=self._kernels,
                cache_scales=scales,
                active=st["active"],
                moe_dense=self._moe_dense,
                qmm=self._qmm_impl,
                pool_impl=self._pool_impl,
                win_starts=win_starts,
                sink_rows=self._sink_rows,
                layout=self._layout,
                states=self._states_of(st),
            )
            if self.quant_cache:
                logits, k, v, (k_s, v_s), *picks = out
            else:
                logits, k, v, *picks = out
            if self.slot_states is not None:
                slot_s, slot_tail, *picks = picks
        elif self.quant_cache:
            logits, k, v, (k_s, v_s), *picks = model.decode_step(
                params,
                self.cfg,
                st["last_tokens"],
                st["lengths"],
                st["k"],
                st["v"],
                kernels=self._kernels,
                cache_scales=(st["k_s"], st["v_s"]),
                active=st["active"],
                moe_dense=self._moe_dense,
                qmm=self._qmm_impl,
            )
        else:
            logits, k, v, *picks = model.decode_step(
                params,
                self.cfg,
                st["last_tokens"],
                st["lengths"],
                st["k"],
                st["v"],
                kernels=self._kernels,
                active=st["active"],
                attn_impl=self._attn_impl,
                moe_dense=self._moe_dense,
                qmm=self._qmm_impl,
            )
        moe_stats = st.get("moe_stats")
        with jax.named_scope("sampling"):
            if mask is not None:
                logits = logits + mask
            next_tokens = sampling.sample(
                logits, sub, st["temps"], st["top_ps"],
                exact=mask is not None,
            )
        slots = jnp.arange(self.num_slots)
        # new token's history col is lengths+1 (<= C, inside the pad);
        # inactive slots — retired or MID-CHUNKED-PREFILL — write to the
        # sacrificial last pad col instead, or interleaved dispatches
        # would scribble over prompt tokens the chunk admission already
        # wrote (K/V has the same gate via the sacrificial cache row)
        hcol = jnp.where(
            st["active"],
            st["lengths"] + 1,
            st["history"].shape[1] - 1,
        )
        st = {
            "k": k,
            "v": v,
            "lengths": jnp.minimum(st["lengths"] + 1, self.max_context - 1),
            "last_tokens": next_tokens,
            "temps": st["temps"],
            "top_ps": st["top_ps"],
            "active": st["active"],
            "history": (
                st["history"].at[slots, hcol].set(next_tokens)
                if self.track_history else st["history"]
            ),
            "key": st["key"],
        }
        if self.quant_cache:
            st["k_s"] = k_s
            st["v_s"] = v_s
        if self.counts_picks:
            st["moe_stats"] = moe_stats + picks[0]
        if self.slot_states is not None:
            st[self._state_keys[0]], st[self._state_keys[1]] = slot_s, slot_tail
        return st, next_tokens

    def _step_impl(self, params, state: DecodeState, n_steps: int, tables=None,
                   mask=None):
        """The decode scan: ``n_steps`` applications of ``_decode_body``
        in one dispatch (one traced body, XLA while-loop — never an
        unrolled graph)."""

        def one(carry, sub):
            return self._decode_body(params, carry, sub, tables, mask)

        # one batched split for the whole dispatch instead of a split per
        # step: keeps the threefry chain out of the scan's serial carry
        # dependency (measurable at TinyLlama step times) — keys[0] becomes
        # the next dispatch's base key, keys[1:] feed the steps
        keys = jax.random.split(state["key"], n_steps + 1)
        state = dict(state, key=keys[0])
        state, tokens = jax.lax.scan(one, state, keys[1:])
        if self.counts_picks:
            # the counters ride back with the tokens, in the one readback
            # there is: four rows below them, counter i in every column
            # of row n_steps + i (readers slice [:n_steps]); the device's
            # sums start again from zero
            stats = state["moe_stats"]
            rows = jnp.broadcast_to(
                stats[:, None], (stats.shape[0], tokens.shape[1])
            )
            tokens = jnp.concatenate([tokens, rows], axis=0)
            state = dict(state, moe_stats=jnp.zeros_like(stats))
        return state, tokens  # tokens [n_steps (+ 4), S]

    def _verify_feed(self, params, st: DecodeState, feed, tables=None):
        """One multi-token verify forward against whichever cache layout
        this engine runs — the shared dispatch body of ``_spec_impl``,
        ``_jump_impl`` and ``_draft_spec_impl``. ``feed`` is [S, W]
        ([last_token, draft/forced tokens...]); returns
        (logits [S, W, V], k, v, scales-or-None, expert counters-or-None)."""
        scales = (st["k_s"], st["v_s"]) if self.quant_cache else None
        if self.paged:
            tables, win_starts = self._split_tables(tables)
            out = model.verify_step_paged(
                params, self.cfg, feed, st["lengths"], st["k"], st["v"],
                tables, cache_scales=scales, active=st["active"],
                moe_dense=self._moe_dense, qmm=self._qmm_gspmd,
                win_starts=win_starts, sink_rows=self._sink_rows,
                layout=self._layout,
            )
        else:
            out = model.verify_step(
                params, self.cfg, feed, st["lengths"], st["k"], st["v"],
                kernels=self._kernels, cache_scales=scales,
                active=st["active"], moe_dense=self._moe_dense,
                qmm=self._qmm_gspmd,
            )
        logits, k, v, *rest = out
        scales = rest.pop(0) if self.quant_cache else None
        return logits, k, v, scales, (rest[0] if rest else None)

    def _spec_impl(
        self, params, state: DecodeState, n_rounds: int, draft_len: int,
        ngram: int, tables=None,
    ):
        """R speculative rounds in one dispatch: propose n-gram drafts from
        the device-resident history, verify them in a single multi-token
        forward, accept the longest matching prefix (spec.py). Every slot
        emits 1..draft_len+1 tokens per round; sampling (temp > 0) and
        inactive slots degrade to exactly one plain decode step per round,
        so this is a strict generalization of ``_step_impl``."""
        S, C, K = self.num_slots, self.max_context, draft_len
        slots = jnp.arange(S)
        # window+sink KV compression guard: a pruned slot proposes only
        # from matches inside its LIVE trailing window (never from the
        # pruned middle the verify attention can no longer see)
        _, win_starts = self._split_tables(tables)

        def one(st, _):
            drafts, _num = spec.propose_ngram(
                st["history"], st["lengths"], K, ngram, C,
                min_pos=win_starts,
            )
            # only greedy, active slots speculate; everyone else verifies
            # a row of -1 drafts (accept count 0 => plain decode step)
            ok = (st["temps"] < sampling.GREEDY_EPS) & st["active"]
            drafts = jnp.where(ok[:, None], drafts, -1)
            feed = jnp.concatenate(
                [st["last_tokens"][:, None], drafts], axis=1
            )  # [S, K+1]
            logits, k, v, new_scales, picks = self._verify_feed(
                params, st, feed, tables
            )
            if self.quant_cache:
                k_s, v_s = new_scales
            moe_stats = st.get("moe_stats")
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, K+1]
            a = spec.accept_counts(drafts, g)  # [S] in [0, K]
            key, sub = jax.random.split(st["key"])
            # row 0 == a plain decode step's logits; sample() takes argmax
            # for greedy rows, so this covers both kinds of slot
            first = sampling.sample(
                logits[:, 0], sub, st["temps"], st["top_ps"]
            )
            out_tokens = g.at[:, 0].set(first)  # [S, K+1]
            counts = a + 1  # tokens emitted this round per slot
            new_last = jnp.take_along_axis(out_tokens, a[:, None], axis=1)[:, 0]
            # accepted tokens land at history cols lengths+1 .. lengths+1+K
            # (within the HISTORY_PAD margin — no clamp, no write collisions
            # for active slots); inactive slots write the sacrificial last
            # pad col so interleaved dispatches can't corrupt a
            # mid-chunked-prefill slot's prompt history
            hidx = jnp.where(
                st["active"][:, None],
                st["lengths"][:, None] + 1 + jnp.arange(K + 1)[None, :],
                st["history"].shape[1] - 1,
            )
            st = {
                "k": k,
                "v": v,
                "lengths": jnp.minimum(st["lengths"] + counts, C - 1),
                "last_tokens": new_last,
                "temps": st["temps"],
                "top_ps": st["top_ps"],
                "active": st["active"],
                "history": st["history"].at[slots[:, None], hidx].set(out_tokens),
                "key": key,
            }
            if self.quant_cache:
                st["k_s"] = k_s
                st["v_s"] = v_s
            if self.counts_picks:
                st["moe_stats"] = moe_stats + picks
            return st, (out_tokens, counts)

        state, (tokens, counts) = jax.lax.scan(one, state, None, length=n_rounds)
        return state, (tokens, counts)  # [R, S, K+1], [R, S]

    # -- draft-model speculation (spec.DraftModel) --------------------------
    # The draft keeps its own dense KV cache whose rows [0, d_len) mirror
    # history[:, 0:d_len) — the same contract the serving cache keeps with
    # its lengths — so keeping it consistent across accept/reject/retire
    # is a matter of moving d_len, never of rewriting rows: accepted draft
    # rows were written by the draft itself, rejected rows fall beyond the
    # clamped d_len and are overwritten before they can be read.

    def _draft_ingest_body(self, dparams, dstate, history, t_lengths,
                           active, width: int):
        """Teacher-forced draft catch-up: ingest up to ``width`` history
        tokens per slot into the draft KV (rows [d_len, d_len+width)),
        advancing draft lengths toward the serving model's. Write-only —
        the draft's logits are discarded; this is a verify forward used
        as a bulk KV writer. Slots already caught up (or inactive) gate
        out via ``active``, so their writes land on the sacrificial row."""
        dcfg = self.draft.cfg
        d_len = dstate["lengths"]
        gap = jnp.maximum(t_lengths - d_len, 0)
        ing = active & (gap > 0)
        # [S, width] gather from the history buffer; small next to the
        # draft forward it feeds (not the [S, W] full-width gather class
        # propose_ngram avoids — width here is bounded by the ingest
        # bucket, not the context)
        idx = jnp.clip(
            d_len[:, None] + jnp.arange(width)[None, :],
            0, history.shape[1] - 1,
        )
        feed = jnp.take_along_axis(history, idx, axis=1)
        _logits, k, v = model.verify_step(
            dparams, dcfg, feed, d_len, dstate["k"], dstate["v"],
            kernels=self._kernels, active=ing,
        )[:3]  # a draft with a router: its counters are not kept
        new_len = d_len + jnp.where(ing, jnp.minimum(gap, width), 0)
        return {"k": k, "v": v, "lengths": new_len}

    def _draft_ingest_impl(self, dparams, dstate, history, t_lengths,
                           active, temps, width: int):
        """The standalone bulk-ingest graph (power-of-two ``width``
        buckets): freshly admitted slots' draft KV trails by the whole
        prompt, and burning fused-round catch-up budget on it would cost
        one round per CATCHUP-width chunk. Sampling slots never propose,
        so only greedy slots ingest. Serving state is read-only here;
        only the draft state is donated."""
        return self._draft_ingest_body(
            dparams, dstate, history, t_lengths,
            active & (temps < sampling.GREEDY_EPS), width,
        )

    def _draft_propose_body(self, dparams, dstate, t_last, ok, draft_len):
        """K autoregressive greedy draft steps: step 1 consumes the
        serving model's pending token (writing its draft-KV row at
        d_len), later steps consume the draft's own argmax. Non-proposing
        slots still run (fixed-shape graph) but write the sacrificial row
        and never advance. Returns (drafts [S, K] with -1 rows for
        non-proposing slots, new draft state)."""
        dcfg = self.draft.cfg
        C = dstate["k"].shape[2]

        def one(carry, _):
            k, v, cur_len, cur_tok = carry
            logits, k, v = model.decode_step(
                dparams, dcfg, cur_tok, cur_len, k, v,
                kernels=self._kernels, active=ok,
            )[:3]  # a draft with a router: its counters are not kept
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_len = jnp.where(ok, jnp.minimum(cur_len + 1, C - 1), cur_len)
            return (k, v, new_len, nxt), nxt

        (k, v, d_len, _), drafts = jax.lax.scan(
            one,
            (dstate["k"], dstate["v"], dstate["lengths"], t_last),
            None, length=draft_len,
        )
        drafts = jnp.where(ok[:, None], drafts.T, -1)  # [S, K]
        return drafts, {"k": k, "v": v, "lengths": d_len}

    def _draft_spec_impl(
        self, params, dparams, state: DecodeState, dstate, n_rounds: int,
        draft_len: int, catchup: int, tables=None,
    ):
        """R draft-model speculative rounds in ONE dispatch: each round
        catches the draft KV up to the serving state (teacher-forced,
        width ``catchup`` — steady-state gap is 0 or 1), runs K
        autoregressive draft steps, verifies the whole draft through the
        serving model's verify forward, accepts the longest matching
        prefix (exact for greedy slots — token streams identical to plain
        decode), and clamps the draft lengths back to the verified
        length so rejected draft rows become unreadable. Sampling and
        inactive slots degrade to one plain decode step per round,
        exactly like ``_spec_impl``; slots whose draft is still catching
        up (gap > catchup) also take the plain step this round and
        propose next round. Returns (state', dstate',
        (tokens [R, S, K+1], counts [R, S], proposed [R, S]))."""
        S, C, K = self.num_slots, self.max_context, draft_len
        slots = jnp.arange(S)
        # window+sink KV compression guard: the draft's dense KV mirrors
        # the FULL history, but a pruned slot's serving attention no
        # longer sees the middle — the draft would propose from context
        # the verify can't read, so pruned slots fall back to the plain
        # step inside the round (ok gate below)
        _, win_starts = self._split_tables(tables)

        def one(carry, _):
            st, dst = carry
            # sampling slots never propose (the ok gate below), so
            # building their draft KV would be pure ingest cost — gate
            # the catch-up on greedy too
            greedy_active = st["active"] & (
                st["temps"] < sampling.GREEDY_EPS
            )
            dst = self._draft_ingest_body(
                dparams, dst, st["history"], st["lengths"], greedy_active,
                catchup,
            )
            # propose only where the draft mirrors the serving cache
            # exactly AND the verify-write contract has room for a full
            # K-draft acceptance (accepted rows stay <= C-2)
            ok = (
                (st["temps"] < sampling.GREEDY_EPS)
                & st["active"]
                & (dst["lengths"] == st["lengths"])
                & (st["lengths"] + K <= C - 2)
            )
            if win_starts is not None:
                ok = ok & (win_starts == 0)
            drafts, dst = self._draft_propose_body(
                dparams, dst, st["last_tokens"], ok, K
            )
            proposed = jnp.where(ok, K, 0)
            feed = jnp.concatenate(
                [st["last_tokens"][:, None], drafts], axis=1
            )  # [S, K+1]
            logits, k, v, new_scales, picks = self._verify_feed(
                params, st, feed, tables
            )
            moe_stats = st.get("moe_stats")
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, K+1]
            a = spec.accept_counts(drafts, g)  # [S] in [0, K]
            key, sub = jax.random.split(st["key"])
            first = sampling.sample(
                logits[:, 0], sub, st["temps"], st["top_ps"]
            )
            out_tokens = g.at[:, 0].set(first)  # [S, K+1]
            counts = a + 1
            new_last = jnp.take_along_axis(
                out_tokens, a[:, None], axis=1
            )[:, 0]
            hidx = jnp.where(
                st["active"][:, None],
                st["lengths"][:, None] + 1 + jnp.arange(K + 1)[None, :],
                st["history"].shape[1] - 1,
            )
            new_lengths = jnp.minimum(st["lengths"] + counts, C - 1)
            st = {
                "k": k,
                "v": v,
                "lengths": new_lengths,
                "last_tokens": new_last,
                "temps": st["temps"],
                "top_ps": st["top_ps"],
                "active": st["active"],
                "history": st["history"].at[
                    slots[:, None], hidx
                ].set(out_tokens),
                "key": key,
            }
            if self.quant_cache:
                st["k_s"], st["v_s"] = new_scales
            if self.counts_picks:
                st["moe_stats"] = moe_stats + picks
            # draft sync: rows for accepted tokens are already correct
            # (the draft wrote them while proposing); everything past the
            # verified length — rejected drafts, or the bonus token's
            # still-unwritten row after a full accept — is clamped out
            dst = dict(dst, lengths=jnp.minimum(dst["lengths"], new_lengths))
            return (st, dst), (out_tokens, counts, proposed)

        (state, dstate), (tokens, counts, proposed) = jax.lax.scan(
            one, (state, dstate), None, length=n_rounds
        )
        return state, dstate, (tokens, counts, proposed)

    def _jump_impl(self, params, state: DecodeState, forced, counts,
                   tables=None):
        """Grammar jump-ahead: append a host-computed FORCED token run to
        each jumping slot in ONE multi-token dispatch. ``forced`` [S, K]
        holds the run tokens (rows padded past ``counts[s]``); a slot with
        ``counts[s] == c > 0`` scores [last_token, f_1..f_{c-1}] through
        the speculative-verify forward — acceptance pinned to all-accept:
        the tokens are grammar-forced, the model's opinion is moot — so
        its K/V rows land exactly as c masked single-token dispatches
        would have left them, ``last_tokens`` becomes f_c (the new pending
        token, K/V written by the next dispatch as usual) and ``lengths``
        advances by c. Slots with ``counts[s] == 0`` are NO-OPS: lengths
        and last_tokens unchanged (their row-0 K/V write is the value the
        next real dispatch rewrites identically; rows past the count land
        beyond ``lengths`` and are overwritten before ever being read).
        The RNG key is untouched — nothing samples here, so greedy AND
        the forced tokens of sampled streams are identical to the
        per-step path. Logits are computed by the verify forward but
        discarded; on TPU the dispatch is weight-bandwidth-bound like any
        decode step, so K forced tokens cost ~one step instead of K."""
        S, C, K = self.num_slots, self.max_context, forced.shape[1]
        slots = jnp.arange(S)
        st = state
        feed = jnp.concatenate([st["last_tokens"][:, None], forced], axis=1)
        _logits, k, v, new_scales, picks = self._verify_feed(params, st, feed,
                                                      tables)
        if self.quant_cache:
            k_s, v_s = new_scales
        jumped = counts > 0
        new_last = jnp.where(
            jumped,
            jnp.take_along_axis(feed, counts[:, None], axis=1)[:, 0],
            st["last_tokens"],
        )
        hist = st["history"]
        if self.track_history:
            # run tokens land at history cols lengths+1 .. lengths+K
            # (inside the HISTORY_PAD margin, K <= HISTORY_PAD - 2); cols
            # past the count are garbage beyond the new length, exactly
            # like the spec scatter. Non-jumping/inactive slots write the
            # sacrificial last pad column.
            hidx = jnp.where(
                (st["active"] & jumped)[:, None],
                st["lengths"][:, None] + 1 + jnp.arange(K)[None, :],
                hist.shape[1] - 1,
            )
            hist = hist.at[slots[:, None], hidx].set(forced)
        new = {
            "k": k,
            "v": v,
            "lengths": jnp.minimum(st["lengths"] + counts, C - 1),
            "last_tokens": new_last,
            "temps": st["temps"],
            "top_ps": st["top_ps"],
            "active": st["active"],
            "history": hist,
            "key": st["key"],
        }
        if self.quant_cache:
            new["k_s"] = k_s
            new["v_s"] = v_s
        if self.counts_picks:
            new["moe_stats"] = st["moe_stats"] + picks
        return new

    def _prefill_impl_paged(
        self, params, state: DecodeState, tokens, slot, true_len, temp, top_p,
        table_row, attn_fn=None,
    ):
        """Paged twin of ``_prefill_impl``: the prompt's K/V rows scatter
        into the page pool through ``table_row`` (the slot's block->page
        map; rows in unbacked blocks land on the sacrificial page 0 and are
        never read). ``attn_fn`` (a closure, not an operand) swaps the
        forward's attention — the sequence-sharded prefill graphs pass the
        ring/Ulysses adapter so a huge prompt's forward spreads over the
        mesh's sp axis while the scatter/sample/activate tail stays
        byte-for-byte the normal admission path."""
        # a prefill samples from ONE row of logits. Where every row's would
        # not fit beside the weights and the pool (rows x vocabulary x 4
        # bytes: 4.3 GB for 8,192 rows of a 131k vocabulary) the head runs
        # on that row alone; below the bound the graphs are what they were
        one_row = tokens.shape[1] * self.cfg.vocab_size * 4 > WHOLE_LOGITS_BYTES
        logits, ks, vs, *picks = model.prefill(
            params, self.cfg, tokens, kernels=self._kernels,
            qmm=self._qmm_gspmd, attn_fn=attn_fn,
            moe_dense=self._moe_dense,
            logit_row=true_len - 1 if one_row else None,
        )
        # ks/vs [L, 1, T, KH, D] -> the pool's rows [L, T, KH*D], written
        # from row 0 of the slot's first page, by whole pages
        if self.quant_cache:
            kq, ks_scale = model.quantize_kv(ks[:, 0])  # [L, T, KH, D/·]
            vq, vs_scale = model.quantize_kv(vs[:, 0])
            pools = (state["k"], state["v"], state["k_s"], state["v_s"])
            rows = (ops.merge_heads(kq), ops.merge_heads(vq),
                    ks_scale, vs_scale)
        else:
            pools = (state["k"], state["v"])
            rows = (ops.merge_heads(ks[:, 0]), ops.merge_heads(vs[:, 0]))
        if self._paged_scatter is not None:
            # dp-replicated pool: table ids are replica-local, so the
            # write must run per device (only the owning replica's
            # targets real pages — ShardingPlan.paged_prefill_scatter)
            k, v, *scales = self._paged_scatter(
                *pools, *rows, table_row, self.allocator.replica_of(slot)
            )
        else:
            k, v, *scales = model.write_prompt_rows(
                pools, rows, table_row, self._layout
            )
        key, sub = jax.random.split(state["key"])
        last = logits[0, 0 if one_row else true_len - 1][None, :]  # [1, V]
        first = sampling.sample(last, sub, temp[None], top_p[None])[0]
        history = jax.lax.dynamic_update_slice(
            state["history"], tokens, (slot, jnp.int32(0))
        )
        out = {
            "k": k,
            "v": v,
            "lengths": state["lengths"].at[slot].set(true_len),
            "last_tokens": state["last_tokens"].at[slot].set(first),
            "temps": state["temps"].at[slot].set(temp),
            "top_ps": state["top_ps"].at[slot].set(top_p),
            "active": state["active"].at[slot].set(True),
            "history": history.at[slot, true_len].set(first),
            "key": key,
        }
        if self.quant_cache:
            out["k_s"], out["v_s"] = scales
        if self.counts_picks:
            out["moe_stats"] = state["moe_stats"] + picks[0]
        return out, first

    def _prefill_impl(
        self, params, state: DecodeState, tokens, slot, true_len, temp, top_p
    ):
        logits, ks, vs, *picks = model.prefill(
            params, self.cfg, tokens, kernels=self._kernels,
            qmm=self._qmm_gspmd, moe_dense=self._moe_dense,
        )
        # ks/vs [L, B=1, T, KH, D] -> cache layout [L, slot, T, KH, D]
        start = (0, slot, 0, 0, 0)
        if self.quant_cache:
            kq, ks_scale = model.quantize_kv(ks)
            vq, vs_scale = model.quantize_kv(vs)
            k = jax.lax.dynamic_update_slice(state["k"], kq, start)
            v = jax.lax.dynamic_update_slice(state["v"], vq, start)
            k_s = jax.lax.dynamic_update_slice(
                state["k_s"], ks_scale, start[:-1]
            )
            v_s = jax.lax.dynamic_update_slice(
                state["v_s"], vs_scale, start[:-1]
            )
        else:
            k = jax.lax.dynamic_update_slice(
                state["k"], ks.astype(state["k"].dtype), start
            )
            v = jax.lax.dynamic_update_slice(
                state["v"], vs.astype(state["v"].dtype), start
            )
        key, sub = jax.random.split(state["key"])
        last = logits[0, true_len - 1][None, :]  # [1, V]
        first = sampling.sample(last, sub, temp[None], top_p[None])[0]
        history = jax.lax.dynamic_update_slice(
            state["history"], tokens, (slot, jnp.int32(0))
        )
        out = {
            "k": k,
            "v": v,
            "lengths": state["lengths"].at[slot].set(true_len),
            "last_tokens": state["last_tokens"].at[slot].set(first),
            "temps": state["temps"].at[slot].set(temp),
            "top_ps": state["top_ps"].at[slot].set(top_p),
            "active": state["active"].at[slot].set(True),
            "history": history.at[slot, true_len].set(first),
            "key": key,
        }
        if self.quant_cache:
            out["k_s"] = k_s
            out["v_s"] = v_s
        if self.counts_picks:
            out["moe_stats"] = state["moe_stats"] + picks[0]
        return out, first

    def _chunk_forward(self, params, state: DecodeState, tokens, slot, start,
                       table_row, win_start=None, n_valid=None):
        """One prefill chunk against whichever cache layout this engine
        runs (paged / int8 KV / dense); returns (logits, kv-state updates).
        The single place the layout dispatch lives — both chunk impls
        build on it. ``win_start`` (armed engines only) masks the pruned
        middle of a mid-admission compressed slot. ``n_valid`` (a final
        chunk's count of real rows) is what the state kind advances by."""
        upd: Dict[str, jnp.ndarray] = {}
        if self.paged:
            scales = (state["k_s"], state["v_s"]) if self.quant_cache else None
            out = model.prefill_chunk_paged(
                params, self.cfg, tokens, start, state["k"], state["v"],
                table_row, cache_scales=scales, qmm=self._qmm_gspmd,
                win_start=win_start, sink_rows=self._sink_rows,
                moe_dense=self._moe_dense, layout=self._layout,
                states=self._states_of(state), slot=slot, n_valid=n_valid,
            )
        else:
            scales = (state["k_s"], state["v_s"]) if self.quant_cache else None
            out = model.prefill_chunk(
                params, self.cfg, tokens, slot, start, state["k"], state["v"],
                cache_scales=scales, qmm=self._qmm_gspmd,
                moe_dense=self._moe_dense,
            )
        if self.quant_cache:
            logits, upd["k"], upd["v"], (upd["k_s"], upd["v_s"]), *picks = out
        else:
            logits, upd["k"], upd["v"], *picks = out
        if self.slot_states is not None:
            upd[self._state_keys[0]], upd[self._state_keys[1]], *picks = picks
        if self.counts_picks:
            upd["moe_stats"] = state["moe_stats"] + picks[0]
        return logits, upd

    def _prefill_chunk_impl(
        self, params, state: DecodeState, tokens, slot, start, table_row=None,
        win_start=None,
    ):
        """Mid-prompt chunk: write K/V rows [start, start+Tc), no sampling.
        Paged engines route the writes through ``table_row`` (the slot's
        block->page map) instead of the slot index."""
        _, upd = self._chunk_forward(params, state, tokens, slot, start,
                                     table_row, win_start)
        new = dict(state)
        new.update(upd)
        new["history"] = self._chunk_history(state, tokens, slot, start)
        return new

    @staticmethod
    def _chunk_history(state, tokens, slot, start):
        """Write a chunk's tokens at history cols [start, start+bucket),
        clamping overflow cols onto the sacrificial last pad column — a
        prefix match de-aligns chunk starts, so a final bucket's padding
        may overrun the buffer (dynamic_update_slice would clamp the START
        and silently shift real tokens)."""
        W = state["history"].shape[1]
        hcol = jnp.clip(start + jnp.arange(tokens.shape[1]), 0, W - 1)
        return state["history"].at[slot, hcol].set(tokens[0])

    def _final_chunk_impl(
        self, params, state: DecodeState, tokens, slot, start, n_valid,
        true_len, temp, top_p, table_row=None, win_start=None,
    ):
        """Last chunk: write K/V, then sample the first token from the
        logits row of the prompt's true last token and activate the slot."""
        logits, upd = self._chunk_forward(params, state, tokens, slot, start,
                                          table_row, win_start, n_valid)
        new = dict(state)
        new.update(upd)
        key, sub = jax.random.split(state["key"])
        last = logits[0, n_valid - 1][None, :]  # [1, V]
        first = sampling.sample(last, sub, temp[None], top_p[None])[0]
        history = self._chunk_history(state, tokens, slot, start)
        new["lengths"] = state["lengths"].at[slot].set(true_len)
        new["last_tokens"] = state["last_tokens"].at[slot].set(first)
        new["temps"] = state["temps"].at[slot].set(temp)
        new["top_ps"] = state["top_ps"].at[slot].set(top_p)
        new["active"] = state["active"].at[slot].set(True)
        new["history"] = history.at[slot, true_len].set(first)
        new["key"] = key
        return new, first

    def _instrument_compile(self, fn, kind: str):
        """Count the new jit graph and time its FIRST dispatch (jax traces
        and XLA-compiles synchronously inside that call; execution itself
        is async, so the first-call elapsed isolates the compile stall).
        Subsequent calls go straight through."""
        obs.ENGINE_XLA_COMPILES.labels(model=self.cfg.name, kind=kind).inc()
        self.compile_events += 1
        hist = obs.ENGINE_XLA_COMPILE_SECONDS.labels(
            model=self.cfg.name, kind=kind
        )
        state = {"first": True}

        def wrapper(*args, **kwargs):
            if not state["first"]:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            with self.phases.phase("engine.compile"):
                out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            state["first"] = False
            self.compile_seconds += dt
            hist.observe(dt)
            return out

        return wrapper

    # -- device-time attribution hooks (obs/devprof.py) ---------------------
    # Hot-path contract: one attribute None-check when devprof is off.
    # ``_devprof_note`` counts the dispatch ALWAYS (the per-graph
    # ledger) and returns a timing token only when this dispatch is due
    # a sample; its kind argument must be a devprof.GRAPH_KINDS literal
    # (tests/test_obs_lint.py enumerates the call sites on the AST).
    # ``_devprof_sample`` lands the host-measured completion delta —
    # call it after the result is already known ready (past an
    # np.asarray readback, or submit-side for deliberately-async
    # dispatches like the restore scatter); ``_devprof_sample_sync``
    # blocks on ``arrays`` first, so it must NEVER run under a declared
    # lock (the lock-readback rule the analyzer enforces).

    def _devprof_note(self, kind: str, key=None, need_slack: bool = False):
        if self.cfg.hc:
            # through two sub-layers a layer
            self.hc_mix_rows += (
                self._program_rows(kind, key) * 2 * self.cfg.num_layers
            )
        if self.slot_states is not None:
            rows = self._program_rows(kind, key) * self.slot_states.layers
            if kind in ("step", "masked"):
                self.state_rows_decode += rows
            else:
                self.state_rows_prefill += rows
        dp = self._devprof
        if dp is None:
            return None
        due = dp.note(kind, key)
        if due and need_slack and dp.queue_depth() > 1:
            # the depth-2 double buffer has a dispatch queued behind this
            # one: skip the sample rather than ever delaying it
            due = False
        return (kind, key, time.perf_counter()) if due else None

    def _program_rows(self, kind: str, key) -> int:
        """Token rows of the program of this dispatch, padding included."""
        if kind == "step":
            rows = int(key) * self.num_slots
        elif kind == "masked":
            rows = self.num_slots
        elif kind == "jump":  # the pending token and the forced run
            rows = (int(key) + 1) * self.num_slots
        elif kind == "prefill":
            rows = int(key)
        elif kind == "chunk":
            rows = int(key[0])
        else:  # no forward pass (history, restore), or refused at load
            return 0
        return rows

    def _devprof_sample(self, tok) -> Optional[float]:
        if tok is None:
            return None
        kind, key, t0 = tok
        dt = time.perf_counter() - t0
        self._devprof.sample(kind, key, dt)
        return dt

    def _devprof_sample_sync(self, tok, arrays) -> Optional[float]:
        if tok is None:
            return None
        jax.block_until_ready(arrays)
        return self._devprof_sample(tok)

    def devprof_est_s(self, kind: str) -> Optional[float]:
        """Mean sampled device-seconds per ``kind`` dispatch (None when
        devprof is off or unsampled) — the batcher's per-request
        attribution rate."""
        dp = self._devprof
        return dp.mean_s(kind) if dp is not None else None

    def devprof_take_sample(self):
        """Pop the ledger's most recent (kind, seconds) sample — the
        batcher joins it onto the flight-recorder event of the dispatch
        it just issued."""
        dp = self._devprof
        return dp.take_last_sample() if dp is not None else None

    def devprof_snapshot(self) -> Optional[dict]:
        """The per-graph ledger as a JSON-shaped dict (bench_devprof)."""
        dp = self._devprof
        return dp.snapshot() if dp is not None else None

    # -- jit builders -------------------------------------------------------
    # One builder per graph kind, shared by the LAZY getters (compile on
    # first dispatch, timed by _instrument_compile) and the AOT warmup
    # (jit.lower(...).compile() against the live state avals — traces and
    # compiles WITHOUT dispatching, so warmup needs no synthetic prompts,
    # no page allocations, and no prefix-index/host-store rollbacks).

    def _make_step_jit(self, n_steps: int):
        if self.paged:
            return jax.jit(
                lambda p, s, t: self._step_impl(p, s, n_steps, t),
                donate_argnums=(1,),
            )
        return jax.jit(
            lambda p, s: self._step_impl(p, s, n_steps),
            donate_argnums=(1,),
        )

    def _make_masked_jit(self):
        if self.paged:
            return jax.jit(
                lambda p, s, t, m: self._step_impl(p, s, 1, t, m),
                donate_argnums=(1,),
            )
        return jax.jit(
            lambda p, s, m: self._step_impl(p, s, 1, None, m),
            donate_argnums=(1,),
        )

    def _make_jump_jit(self):
        if self.paged:
            return jax.jit(
                lambda p, s, t, f, c: self._jump_impl(p, s, f, c, t),
                donate_argnums=(1,),
            )
        return jax.jit(
            lambda p, s, f, c: self._jump_impl(p, s, f, c),
            donate_argnums=(1,),
        )

    def _make_spec_jit(self, key: Tuple[int, int, int]):
        if self.paged:
            return jax.jit(
                lambda p, s, t: self._spec_impl(p, s, *key, tables=t),
                donate_argnums=(1,),
            )
        return jax.jit(
            lambda p, s: self._spec_impl(p, s, *key),
            donate_argnums=(1,),
        )

    def _make_draft_spec_jit(self, key: Tuple[int, int, int]):
        if self.paged:
            return jax.jit(
                lambda p, dp, s, ds, t: self._draft_spec_impl(
                    p, dp, s, ds, *key, tables=t
                ),
                donate_argnums=(2, 3),
            )
        return jax.jit(
            lambda p, dp, s, ds: self._draft_spec_impl(p, dp, s, ds, *key),
            donate_argnums=(2, 3),
        )

    def _make_draft_ingest_jit(self, width: int):
        return jax.jit(
            lambda dp, ds, h, tl, act, tm: self._draft_ingest_impl(
                dp, ds, h, tl, act, tm, width
            ),
            donate_argnums=(1,),
        )

    def _make_prefill_jit(self):
        impl = self._prefill_impl_paged if self.paged else self._prefill_impl
        return jax.jit(impl, donate_argnums=(1,))

    def _make_seq_prefill_jit(self):
        """Sequence-sharded whole-prompt prefill: ``_prefill_impl_paged``
        with the ring/Ulysses attention closed over — the forward's
        sequence axis shards over the mesh's sp axis, everything else
        (pool scatter, sample, activate) is the normal paged prefill."""
        attn = self._seq_attn
        return jax.jit(
            lambda p, s, t, sl, tl, tm, tp_, row: self._prefill_impl_paged(
                p, s, t, sl, tl, tm, tp_, row, attn_fn=attn
            ),
            donate_argnums=(1,),
        )

    def _make_chunk_jit(self, final: bool):
        impl = self._final_chunk_impl if final else self._prefill_chunk_impl
        return jax.jit(impl, donate_argnums=(1,))

    @staticmethod
    def _make_hist_jit():
        def impl(state, tokens, slot, start):
            new = dict(state)
            new["history"] = jax.lax.dynamic_update_slice(
                state["history"], tokens, (slot, start)
            )
            return new

        return jax.jit(impl, donate_argnums=(0,))

    # -- AOT compilation (warmup / readiness gate) --------------------------

    def _compile_aot(self, kind: str, store: Dict, key, jitfn,
                     example_args) -> None:
        """AOT-compile one graph against the live avals of
        ``example_args`` and store the compiled executable where the
        dispatch path looks it up. trace().lower().compile() never
        executes — no device state moves, nothing donates — so the whole
        serving surface can warm behind the readiness gate in compile
        time alone. Each stage is a span of its own (flightrec
        ``warmup.trace`` / ``.lower`` / ``.compile``), their sum is the
        graph's ``xla_compile_s``, and one ``compile`` event on the model
        lane says which graph it was. Counts the same compile-event
        accounting a lazy first dispatch would. On the TPU a graph that
        cannot lower or compile (Mosaic rejection, HBM overflow) raises, so
        LoadModel fails instead of reporting ``ready`` for a model whose
        first request would die; an intended CPU run keeps the lazy
        instrumented wrapper (the first real dispatch then compiles,
        visibly)."""
        if key in store:
            return
        ph, label = self.phases, str(key)
        args = {"kind": kind, "key": label}  # ride on the trace annotations
        requests0, hits0 = flightrec.compile_cache()
        try:
            # jitfn.lower(*args) is trace(*args).lower() (jax 0.9.0
            # pjit.jit_lower): the same text, so the same cache entry
            cpu0 = time.thread_time()
            with ph.phase("warmup.trace", **args) as traced:
                staged = jitfn.trace(*example_args)
            with ph.phase("warmup.lower", **args) as lowered:
                staged = staged.lower()
            cpu = time.thread_time() - cpu0
            with ph.phase("warmup.compile", **args) as compiled:
                fn = staged.compile()
        except Exception:  # noqa: BLE001 - lazy compile still serves on CPU
            if backend.on_tpu():
                log.error("AOT compile failed for %s graph %r", kind, key)
                raise
            log.exception(
                "AOT lowering failed for %s graph %r; deferring to "
                "first-dispatch compile", kind, key,
            )
            store[key] = self._instrument_compile(jitfn, kind)
            return
        dt = traced.dt + lowered.dt + compiled.dt
        obs.ENGINE_XLA_COMPILES.labels(model=self.cfg.name, kind=kind).inc()
        self.compile_events += 1
        self.compile_seconds += dt
        self.warmup_trace_cpu_seconds += cpu
        obs.ENGINE_XLA_COMPILE_SECONDS.labels(
            model=self.cfg.name, kind=kind
        ).observe(dt)
        requests, hits = flightrec.compile_cache()
        requests, hits = requests - requests0, hits - hits0
        flightrec.RECORDER.model_event(
            # "graph": an event's own kind is "compile"
            self.cfg.name, "compile", graph=kind, key=label,
            trace_ms=round(traced.dt * 1e3, 3),
            lower_ms=round(lowered.dt * 1e3, 3),
            compile_ms=round(compiled.dt * 1e3, 3),
            cpu_ms=round(cpu * 1e3, 3),
            # the persistent cache served every compile this graph asked of it
            cache_hit=bool(requests) and hits == requests,
        )
        if self._devprof is not None:
            # ledger registration: the compiled executable's static
            # cost_analysis (FLOPs + bytes per dispatch) + compile time,
            # under the same (kind, key) the dispatch path notes —
            # metadata only, no device state moves
            self._devprof.register(kind, key, fn, dt)
        store[key] = fn

    def _step_example(self) -> tuple:
        if self.paged:
            return (self.params, self.state, self._tables_operand())
        return (self.params, self.state)

    def compile_step_fn(self, n_steps: int) -> None:
        """Ensure the ``n_steps`` decode graph exists WITHOUT dispatching
        (the batcher calls this for its chunk sizes when it attaches to a
        warmed engine; warmup calls it for every serving step size)."""
        if n_steps not in self._step_fns:
            self._compile_aot(
                "step", self._step_fns, n_steps,
                self._make_step_jit(n_steps), self._step_example(),
            )

    def compile_masked_fn(self) -> None:
        if "masked" in self._step_fns:
            return
        mask = jnp.zeros((self.num_slots, self.cfg.vocab_size), jnp.float32)
        self._compile_aot(
            "masked", self._step_fns, "masked", self._make_masked_jit(),
            self._step_example() + (mask,),
        )

    def compile_spec_fn(self, n_rounds: int, draft_len: int,
                        ngram: int) -> None:
        key = (n_rounds, draft_len, ngram)
        if key in self._spec_fns or not self.spec_supported \
                or not self.track_history:
            return
        self._compile_aot(
            "spec", self._spec_fns, key, self._make_spec_jit(key),
            self._step_example(),
        )

    def compile_draft_spec_fn(self, n_rounds: int, draft_len: int) -> None:
        """Ensure the fused draft-propose + verify graph for
        ``n_rounds`` rounds exists WITHOUT dispatching (warmup and the
        batcher attach call this for the batcher's actual dispatch
        sizes, keeping the flat-compile-counters invariant). No-op when
        no draft model is attached."""
        if self.draft is None:
            return
        key = (n_rounds, draft_len, draft_len + 1)
        if key in self._draft_fns:
            return
        self._compile_aot(
            "draft_spec", self._draft_fns, key,
            self._make_draft_spec_jit(key),
            (self.params, self.draft.params, self.state, self.draft_state)
            + ((self._tables_operand(),) if self.paged else ()),
        )

    def compile_draft_ingest_fns(self) -> None:
        """Ensure every bulk draft-ingest bucket graph exists WITHOUT
        dispatching; no-op without a draft model."""
        if self.draft is None:
            return
        for w in self._draft_ingest_buckets():
            key = ("ingest", w)
            if key in self._draft_fns:
                continue
            self._compile_aot(
                "draft_ingest", self._draft_fns, key,
                self._make_draft_ingest_jit(w),
                (self.draft.params, self.draft_state,
                 self.state["history"], self.state["lengths"],
                 self.state["active"], self.state["temps"]),
            )

    def _draft_ingest_buckets(self) -> Tuple[int, ...]:
        bs = tuple(b for b in DRAFT_INGEST_BUCKETS if b <= self.max_context)
        return bs or DRAFT_INGEST_BUCKETS[:1]

    def compile_jump_fn(self, k_bucket: int) -> None:
        """Ensure the ``k_bucket``-run jump-ahead graph exists WITHOUT
        dispatching (warmup and the batcher attach call this for every
        JUMP_BUCKETS size so a constrained tick never compiles
        mid-serving). No-op where jump dispatches are unsupported (the
        dp-replicated pool, like speculative verify)."""
        if k_bucket in self._jump_fns or not self.spec_supported:
            return
        args = [self.params, self.state]
        if self.paged:
            args.append(self._tables_operand())
        args += [
            jnp.zeros((self.num_slots, k_bucket), jnp.int32),
            jnp.zeros((self.num_slots,), jnp.int32),
        ]
        self._compile_aot(
            "jump", self._jump_fns, k_bucket, self._make_jump_jit(),
            tuple(args),
        )

    def compile_prefill_fn(self, bucket: int) -> None:
        if bucket in self._prefill_fns:
            return
        args = (
            self.params, self.state, jnp.zeros((1, bucket), jnp.int32),
            jnp.int32(0), jnp.int32(1), jnp.float32(0.0), jnp.float32(1.0),
        )
        if self.paged:
            args = args + (jnp.asarray(self.allocator.tables[0]),)
        self._compile_aot(
            "prefill", self._prefill_fns, bucket, self._make_prefill_jit(),
            args,
        )

    def compile_seq_prefill_fn(self, bucket: int) -> None:
        """Ensure the sequence-sharded prefill graph for ``bucket`` exists
        WITHOUT dispatching (warmup calls this for every bucket the
        routing floor + pool can reach, keeping the flat-compile-counters
        invariant). No-op where seq-sharded prefill is disarmed."""
        if self._seq_attn is None or bucket in self._seq_prefill_fns:
            return
        args = (
            self.params, self.state, jnp.zeros((1, bucket), jnp.int32),
            jnp.int32(0), jnp.int32(1), jnp.float32(0.0), jnp.float32(1.0),
            jnp.asarray(self.allocator.tables[0]),
        )
        self._compile_aot(
            "seq_prefill", self._seq_prefill_fns, bucket,
            self._make_seq_prefill_jit(), args,
        )

    def compile_chunk_fn(self, bucket: int, final: bool) -> None:
        key = (bucket, final)
        if key in self._chunk_fns:
            return
        args = [
            self.params, self.state, jnp.zeros((1, bucket), jnp.int32),
            jnp.int32(0), jnp.int32(0),
        ]
        if final:
            args += [jnp.int32(1), jnp.int32(1), jnp.float32(0.0),
                     jnp.float32(1.0)]
        if self.paged:
            args.append(jnp.asarray(self.allocator.tables[0]))
            if self.kv_compress_armed:
                # armed engines' chunk graphs carry the slot's live-window
                # start (a prompt can cross the compression threshold
                # mid-admission)
                args.append(jnp.int32(0))
        self._compile_aot(
            "chunk", self._chunk_fns, key, self._make_chunk_jit(final),
            tuple(args),
        )

    def compile_hist_fn(self, bucket: int) -> None:
        key = ("hist", bucket)
        if key in self._prefill_fns:
            return
        args = (
            self.state, jnp.zeros((1, bucket), jnp.int32), jnp.int32(0),
            jnp.int32(0),
        )
        self._compile_aot("hist", self._prefill_fns, key,
                          self._make_hist_jit(), args)

    def compile_restore_fn(self, nb: int) -> None:
        if nb in self._restore_fns or not self.paged:
            return
        cfg, P = self.cfg, self.allocator.page_size
        pool = self.state["k"]  # nb pages of it: [L, nb, P, KH*D]
        z = jnp.zeros((pool.shape[0], nb, *pool.shape[2:]), pool.dtype)
        args = [self.state, z, z]
        if self.quant_cache:
            s = jnp.zeros((cfg.num_layers, nb, P, cfg.num_kv_heads),
                          jnp.float32)
            args += [s, s]
        args.append(jnp.zeros((nb,), jnp.int32))
        self._compile_aot(
            "restore", self._restore_fns, nb, self._make_restore_jit(),
            tuple(args),
        )

    # -- lazy getters (unwarmed engines compile on first dispatch) ----------

    def _step_fn(self, n_steps: int):
        fn = self._step_fns.get(n_steps)
        if fn is None:
            fn = self._instrument_compile(self._make_step_jit(n_steps), "step")
            self._step_fns[n_steps] = fn
        return fn

    def _masked_step_fn(self):
        """1-step decode with an additive per-slot logits mask (grammar-
        constrained decoding); same donated state contract as _step_fn."""
        fn = self._step_fns.get("masked")
        if fn is None:
            fn = self._instrument_compile(self._make_masked_jit(), "masked")
            self._step_fns["masked"] = fn
        return fn

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._instrument_compile(self._make_prefill_jit(), "prefill")
            self._prefill_fns[bucket] = fn
        return fn

    def _seq_prefill_fn(self, bucket: int):
        fn = self._seq_prefill_fns.get(bucket)
        if fn is None:
            fn = self._instrument_compile(
                self._make_seq_prefill_jit(), "seq_prefill"
            )
            self._seq_prefill_fns[bucket] = fn
        return fn

    def _spec_fn(self, n_rounds: int, draft_len: int, ngram: int):
        key = (n_rounds, draft_len, ngram)
        fn = self._spec_fns.get(key)
        if fn is None:
            fn = self._instrument_compile(self._make_spec_jit(key), "spec")
            self._spec_fns[key] = fn
        return fn

    def _draft_spec_fn(self, n_rounds: int, draft_len: int):
        key = (n_rounds, draft_len, draft_len + 1)
        fn = self._draft_fns.get(key)
        if fn is None:
            fn = self._instrument_compile(
                self._make_draft_spec_jit(key), "draft_spec"
            )
            self._draft_fns[key] = fn
        return fn

    def _draft_ingest_fn(self, width: int):
        key = ("ingest", width)
        fn = self._draft_fns.get(key)
        if fn is None:
            fn = self._instrument_compile(
                self._make_draft_ingest_jit(width), "draft_ingest"
            )
            self._draft_fns[key] = fn
        return fn

    def _jump_fn(self, k_bucket: int):
        fn = self._jump_fns.get(k_bucket)
        if fn is None:
            fn = self._instrument_compile(self._make_jump_jit(), "jump")
            self._jump_fns[k_bucket] = fn
        return fn

    def _chunk_fn(self, bucket: int, final: bool):
        key = (bucket, final)
        fn = self._chunk_fns.get(key)
        if fn is None:
            fn = self._instrument_compile(self._make_chunk_jit(final), "chunk")
            self._chunk_fns[key] = fn
        return fn

    def _hist_fn(self, bucket: int):
        key = ("hist", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            fn = self._make_hist_jit()
            self._prefill_fns[key] = fn
        return fn

    def _write_history(self, slot: int, ids: Sequence[int],
                       start: int = 0) -> None:
        """Backfill history cols [start, start+len(ids)) in bucket-sized
        dispatches (a matched prefix can exceed the largest bucket).
        Caller holds the engine lock: the operands go to the graph call
        as numpy values, placed by the call itself."""
        slot_op = np.int32(slot)
        pos = 0
        while pos < len(ids):
            seg = ids[pos : pos + self.buckets[-1]]
            bucket = self.bucket_for(len(seg))
            padded = np.zeros((1, bucket), dtype=np.int32)
            padded[0, : len(seg)] = seg
            self._devprof_note("hist", ("hist", bucket))
            self.state = self._hist_fn(bucket)(
                self.state, padded, slot_op, np.int32(start + pos),
            )
            pos += len(seg)

    def _maybe_compress(self, slot: int, length: Optional[int] = None) -> None:
        """Window+sink KV compression (caller holds the engine lock):
        once ``slot``'s length exceeds the threshold, release the page
        range between the sink pages and the trailing window back to the
        pool and advance the slot's live-window start — the mask operand
        every subsequent dispatch reads. Pages shared with the prefix
        index keep their index references (and spill through the host
        tier under pressure like any cold prefix page); only this slot's
        references drop. Monotone: the window start never rewinds.
        ``length`` is passed explicitly by mid-admission callers (the
        slot is not active yet and its host length is still 0)."""
        if not self.kv_compress_armed:
            return
        if length is None:
            if not self.active[slot]:
                return
            L = int(self._host_lengths[slot])
        else:
            L = int(length)
        if L <= self.kv_compress_after:
            return
        P = self.allocator.page_size
        # last block fully below the trailing window [L - window_rows, L]
        wb = (L - self.kv_window_pages * P) // P
        if wb <= self.kv_sink_pages:
            return
        if self._win_starts[slot] == 0:
            self.kv_compress_slots += 1
            flightrec.RECORDER.model_event(
                self.cfg.name, "kv_compress", slot=slot, length=L,
            )
        freed = self.allocator.prune_range(slot, self.kv_sink_pages, wb)
        self.kv_pages_pruned += freed
        self._win_starts[slot] = wb * P

    def _back_active_slots(self, grow_rows: int) -> None:
        """Back every active slot's next ``grow_rows`` rows BEFORE a paged
        dispatch (PoolExhausted surfaces with state untouched so the
        batcher can retire a victim and retry); windowed models first
        return pages attention can no longer reach, and compression-armed
        engines prune past-threshold slots to sink + window. Caller holds
        the engine lock."""
        for s in range(self.num_slots):
            if self.active[s]:
                if self.cfg.sliding_window is not None:
                    self.allocator.trim_below_window(
                        s,
                        int(self._host_lengths[s]),
                        self.cfg.sliding_window,
                    )
                self._maybe_compress(s)
                self.allocator.ensure(
                    s,
                    min(
                        int(self._host_lengths[s]) + grow_rows,
                        self.max_context,
                    ),
                )

    # -- prefix caching (paged engines; paged.PrefixIndex) ------------------
    # -- + host spill tier (paged.HostPageStore) ----------------------------

    def _spill_pages(self, evicted) -> None:
        """PrefixIndex eviction hook: capture the evicted pages' KV with a
        device-side gather, then hand the copies to the spill worker —
        the device->host transfer and store insert run off the lock.

        The gather is BLOCKED until its buffers materialize, under the
        engine lock: the pages free (and can be rewritten) the moment
        this hook returns, and the pool buffer must be clean to donate to
        the next dispatch — so the lock pays for the in-flight dispatch
        queue draining plus the gather itself. That cost lands only on
        eviction paths (pool-pressure admissions and index overflow),
        where the alternative was a full prefill recompute anyway."""
        if self.host_store is None or self._spill_q is None:
            return
        with self._spill_lock:
            if self._spill_pending + len(evicted) > self._spill_max_pending:
                pending = self._spill_pending
            else:
                pending = -1
                self._spill_pending += len(evicted)
        if pending >= 0:
            # the worker is behind an eviction burst: drop this spill
            # BEFORE enqueuing the gather (pending batches pin device
            # memory) — the evicted pages degrade to plain eviction
            log.warning(
                "host-tier spill backlog at %d pages; dropping %d page(s)",
                pending, len(evicted),
            )
            return
        try:
            # aios: waive(lock-readback): host-side page-id list, no device sync
            pages = np.asarray([p for _, p in evicted], np.int32)
            arrs = [self.state["k"][:, pages], self.state["v"][:, pages]]
            if self.quant_cache:
                arrs.append(self.state["k_s"][:, pages])
                arrs.append(self.state["v_s"][:, pages])
            # aios: waive(lock-readback): PR-4 contract — the gather must materialize under the engine lock; the evicted pages free (and can be rewritten by the next donated dispatch) the moment this hook returns
            jax.block_until_ready(arrs)
        except BaseException:
            # a failed gather (e.g. RESOURCE_EXHAUSTED materializing the
            # copies on a full chip) must give its reservation back, or
            # the leaked count eventually pins the backlog gate shut and
            # silently disables the tier; _drop's handler degrades this
            # eviction to a plain one
            with self._spill_lock:
                self._spill_pending -= len(evicted)
            raise
        self._spill_q.put(([h for h, _ in evicted], arrs))
        # flight-recorder model lane: spills belong to the MODEL's story
        # (pressure from whichever request forced the eviction), not to
        # one request's timeline — /debug/trace renders them on tid 0
        flightrec.RECORDER.model_event(
            self.cfg.name, "spill", pages=len(evicted)
        )

    @staticmethod
    def _spill_worker(q, store, lock, eng_ref) -> None:
        """Daemon loop: device->host copies + HostPageStore inserts for
        spilled pages. Best-effort — a failed spill degrades that
        eviction to the pre-host-tier behavior (KV lost, recompute on the
        next hit), never corrupts. Static on purpose: the thread owns
        only the queue/store/lock (a close() that times out on a deep
        backlog must not crash it mid-drain) and reaches the pending
        counter through ``eng_ref``, so an engine dropped WITHOUT close()
        stays collectible — the periodic get() timeout notices the dead
        weakref and exits."""
        import queue as _queue

        keys = ("k", "v", "k_s", "v_s")
        while True:
            try:
                item = q.get(timeout=60)
            except _queue.Empty:
                if eng_ref() is None:
                    return  # engine collected without close(); wind down
                continue
            if item is None:
                return
            hashes, arrs = item
            try:
                host = [np.asarray(a) for a in arrs]
                for i, h in enumerate(hashes):
                    store.put(h, {
                        k: np.ascontiguousarray(host[j][:, i])
                        for j, k in enumerate(keys[: len(host)])
                    })
            except Exception:  # noqa: BLE001 - spill is best-effort
                log.exception("host-tier spill worker failed")
            finally:
                eng = eng_ref()
                if eng is not None:
                    with lock:
                        eng._spill_pending -= len(hashes)

    def _restore_fn(self, bucket: int):
        """Jitted per-layer pool scatter for a host-tier restore of up to
        ``bucket`` pages. Power-of-two buckets bound the compile count;
        pad entries land on the sacrificial page 0, which is never read.

        Deliberately NOT donated: a restore fires under the same HBM
        pressure that evicted the pages, and a dispatch-time failure of a
        donating call can consume the state buffers first — wedging every
        later dispatch on 'Array has been deleted', strictly worse than
        the transient pool copy the undonated scatter pays. A failure
        here instead leaves ``self.state`` intact and the caller falls
        back to normal prefill."""
        fn = self._restore_fns.get(bucket)
        if fn is None:
            fn = self._instrument_compile(self._make_restore_jit(), "restore")
            self._restore_fns[bucket] = fn
        return fn

    def _make_restore_jit(self):
        if self.quant_cache:
            def impl(state, kh, vh, ksh, vsh, pages):
                new = dict(state)
                new["k"] = state["k"].at[:, pages].set(kh)
                new["v"] = state["v"].at[:, pages].set(vh)
                new["k_s"] = state["k_s"].at[:, pages].set(ksh)
                new["v_s"] = state["v_s"].at[:, pages].set(vsh)
                return new
        else:
            def impl(state, kh, vh, pages):
                new = dict(state)
                new["k"] = state["k"].at[:, pages].set(kh)
                new["v"] = state["v"].at[:, pages].set(vh)
                return new
        return jax.jit(impl)

    def _restore_from_host(self, slot: int, entries, lead_hashes=(),
                           lead_pages=()) -> List[int]:
        """Allocate landing pages for a host-tier chain hit, scatter the
        stored KV back into the pool, map the pages as ``slot``'s next
        logical blocks, and re-register their hashes in the HBM index.
        Returns the new pages — empty when the pool cannot back them
        (the caller falls back to normal prefill; nothing was touched).
        Caller holds the engine lock; the scatter dispatch is async, so
        the copy-in overlaps the request's tail-prefill chunking (any
        later read orders after it through the state data dependency)."""
        # clamp the chain to what the pool can PLAUSIBLY back before
        # allocating: an uncapped alloc_pages would first evict (and
        # blocking-gather) cold HBM prefix entries via the reclaimer,
        # then fail on the remaining shortfall anyway — paying the
        # eviction thrash for a restore that never happens. Truncation
        # keeps a chain prefix, which is still a valid restore.
        avail = self.allocator.free_pages_for(slot) \
            + self.prefix_index.reclaimable()
        if len(entries) > avail:
            entries = entries[:avail]
            if len(entries) < self.host_restore_min_pages:
                return []
        try:
            pages = self.allocator.alloc_pages(len(entries))
        except paged.PoolExhausted:
            return []
        t0 = time.perf_counter()
        n = len(pages)
        nb = 1
        while nb < n:
            nb *= 2
        pad = np.zeros(nb, np.int32)  # pad rows -> sacrificial page 0
        pad[:n] = pages

        def stacked(key):
            a = np.stack([e[key] for _, e in entries], axis=1)
            if nb > n:
                shape = list(a.shape)
                shape[1] = nb - n
                a = np.concatenate(
                    [a, np.zeros(shape, a.dtype)], axis=1
                )
            return jnp.asarray(a)

        # restore samples are submit-side by design: the scatter is
        # deliberately async (it overlaps the tail prefill), so the
        # sample covers staging + dispatch, like the restore histogram
        dtok = self._devprof_note("restore", nb)
        try:
            act = faults.point("host_store.restore_fail", self.cfg.name)
            if act is not None:
                # chaos: the restore dies mid-flight — recovery is the
                # REAL fallback below (pages returned, normal prefill)
                raise faults.InjectedFault(
                    f"injected restore failure (hit {act.hit})"
                )
            args = [stacked("k"), stacked("v")]
            if self.quant_cache:
                args += [stacked("k_s"), stacked("v_s")]
            self.state = self._restore_fn(nb)(
                self.state, *args, jnp.asarray(pad)
            )
        except BaseException:
            # staging or the scatter dispatch failed (a restore fires
            # exactly under the HBM pressure that evicted these pages, so
            # RESOURCE_EXHAUSTED here is plausible): give the allocated
            # pages back — leaking them at refcount 1 would shrink the
            # pool forever — and fall back to normal prefill. The probe
            # counted a hit; the restore never happened, so the store
            # records a miss too (the ratio predicts recompute cost).
            for p in pages:
                self.allocator.decref(p)
            if self.host_store is not None:
                self.host_store.note_failed_restore()
            log.exception(
                "host-tier restore failed; recomputing %d page(s)", n
            )
            return []
        dt = time.perf_counter() - t0
        self.host_restore_seconds += dt
        if self._obs_restore_hist is not None:
            self._obs_restore_hist.observe(dt)
        self._devprof_sample(dtok)
        self.allocator.append_owned(slot, pages)
        hashes = [h for h, _ in entries]
        # back in HBM: re-register so the NEXT prompt maps these pages
        # directly, and drop the host copies (they respill on eviction).
        # The lead (HBM-matched) part of the chain rides along so the
        # radix index can graft the restored segment at its true tree
        # position — a mid-chain insert has no meaning in a tree (the
        # flat index just LRU-refreshes the already-present lead).
        self.prefix_index.put(
            list(lead_hashes) + hashes, list(lead_pages) + pages
        )
        self.host_store.discard(hashes, restored=True)
        self.prefix_rows_restored += n * self.allocator.page_size
        flightrec.RECORDER.model_event(
            self.cfg.name, "restore", pages=n,
            rows=n * self.allocator.page_size,
        )
        return pages

    def _match_prefix(self, slot: int, ids: Sequence[int],
                      given: Optional[paged.PromptHashes] = None):
        """Map the longest hash-matched prompt prefix into ``slot``'s page
        table and, where the engine keeps a history, backfill it. ``ids``
        is the admission-truncated prompt (an int32 array or a list);
        ``given`` the submitter's hashes of it, taken where they are this
        truncation's (``prompt_hashes``): the prompt is then not hashed
        under the lock. HBM-resident blocks map as
        shared read-only pages (zero compute, zero new pages); when the
        hash chain continues into the host spill tier — and the run
        clears ``host_restore_min_pages`` — fresh pages are allocated and
        the stored KV scatters back in: a memcpy instead of a prefill
        forward pass. Restored pages get the same read-only guarantee by
        the same construction (matches cap at the prompt's last full
        block minus one row, so every tail/decode write lands past them).
        Returns (matched_rows, block_hashes). Caller holds the engine
        lock.

        matched_rows is page-aligned but NOT chunk-aligned — the tail's
        chunk starts inherit the misalignment, which the chunk writers are
        built for (prefill_chunk_paged's sacrificial-page slice padding,
        _chunk_history's clamped scatter)."""
        mine = self.prompt_hashes(ids, given)
        if mine is None:
            return 0, []
        if mine is given:
            self.admissions_prehashed += 1
        if not mine.hashes:
            return 0, []
        P = self.allocator.page_size
        hashes = mine.hashes
        if self.refused_prefixes is not None:
            # the state kind: the hit is refused and counted, not served
            seen = self.refused_prefixes.match(hashes)
            if seen:
                self.prefix_hits_refused_state += 1
                self.prefix_rows_refused_state += seen * P
            return 0, hashes
        pages = self.prefix_index.match(hashes)
        entries = []
        if self.host_store is not None and len(pages) < len(hashes):
            entries = self.host_store.match_chain(hashes[len(pages) :])
            if len(entries) < self.host_restore_min_pages:
                entries = []  # below the floor: recompute beats device_put
        if not pages and not entries:
            return 0, hashes
        if pages and self.window_prefix is not None:
            # two kinds: served where the window kind's rows are held too
            # (paged.py's header), cut to the longest hit that is
            served, held = self.window_prefix.servable(
                hashes[: len(pages)], self.cfg.sliding_window
            )
            if served < len(pages):
                self.prefix_hits_refused_window += 1
            pages = pages[:served]
            if not pages:
                return 0, hashes
            self.allocator.full.map_shared(slot, pages)
            self.allocator.window.map_shared(
                slot, held, first=served - len(held)
            )
            self.prefix_rows_reused += served * P
        elif pages:
            # map the HBM hits FIRST: their index references alone are
            # reclaimable (refcount 1), so taking the slot reference
            # before the restore's alloc_pages keeps a pressure-reclaim
            # from freeing the very pages this prompt just matched
            self.allocator.map_shared(slot, pages)
            self.prefix_rows_reused += len(pages) * P
        restored = (
            self._restore_from_host(
                slot, entries, hashes[: len(pages)], pages
            )
            if entries else []
        )
        matched = (len(pages) + len(restored)) * P
        if not matched:
            return 0, hashes
        if self.track_history:
            # the n-gram proposer reads history[0:length] — backfill the
            # shared region (padding past `matched` inside the last
            # segment's bucket is overwritten by the tail chunks writing
            # [matched, len))
            self._write_history(slot, ids[:matched])
        else:
            # no history is kept: the decode step leaves the array alone
            # and every proposer that reads it refuses to build, so the
            # programs that would fill it are not issued
            self.history_backfills_skipped += 1
        return matched, hashes

    def _register_prefix(self, slot: int, ids: List[int], hashes) -> None:
        """After a successful admission, publish the slot's fully-covered
        prompt blocks to the index so the NEXT prompt with this prefix
        skips their prefill. Caller holds the engine lock."""
        if self.refused_prefixes is not None:
            self.refused_prefixes.put(hashes)
            return
        if self.prefix_index is None or not hashes:
            return
        if self.window_prefix is not None:
            # two kinds: every block's full-kind page, and the window-kind
            # pages this slot still holds (its last window: paged.py header)
            tables = self.allocator.tables[slot]
            n, MB = len(hashes), self.allocator.max_blocks
            self.prefix_index.put(hashes, [int(p) for p in tables[:n]])
            held = min(int(self.allocator.window._trimmed[slot]), n)
            self.window_prefix.put(
                hashes[held:], [int(p) for p in tables[MB + held : MB + n]]
            )
            return
        if int(self.allocator._trimmed[slot]):
            # sliding-window trimming released leading blocks during this
            # admission; their table entries are stale and a prefix chain
            # must start at block 0 — nothing registrable
            return
        if int(self.allocator._pruned_hi[slot]):
            # window+sink pruning released the middle during this
            # admission; the sink pages are still a valid (short) chain
            # prefix, the rest maps the sacrificial page
            hashes = hashes[: int(self.allocator._pruned_lo[slot])]
            if not hashes:
                return
        pages = [int(self.allocator.tables[slot, b]) for b in range(len(hashes))]
        self.prefix_index.put(hashes, pages)

    def prompt_hashes(
        self, token_ids: Sequence[int],
        given: Optional[paged.PromptHashes] = None,
    ) -> Optional[paged.PromptHashes]:
        """The chain hashes admission matches and registers a prompt
        under — THE rule, in this one place: those of the
        ``(rows - 1) // page_size`` full blocks (at least one tail row
        remains) of the prompt's last ``rows = min(len, max_context - 1)``
        ids. Computed once a request on the thread that submits it (the
        serving pool for routing, else ``ContinuousBatcher.submit``) and
        handed down as ``given``, which is taken as it is where it was
        computed over this truncation and hashed again where not
        (another context length or page size). None where nothing would
        read them: no prefix index and no state kind's seen prefixes."""
        if self.prefix_index is None and self.refused_prefixes is None:
            return None
        P = self.allocator.page_size
        rows = min(len(token_ids), self.max_context - 1)
        if given is not None and (given.rows, given.page_size) == (rows, P):
            return given
        full = (rows - 1) // P
        ids = token_ids[len(token_ids) - rows :]
        return paged.PromptHashes(
            rows, P, paged.chain_hashes(ids, P, full) if full > 0 else []
        )

    def prefix_hashes(self, token_ids: List[int]) -> List[bytes]:
        """``prompt_hashes`` as the bare list the routers' overlap probes
        and the fleet's exports take; empty where there is no prefix
        index to probe (replicas of one model share page size and
        truncation)."""
        if self.prefix_index is None:
            return []
        return self.prompt_hashes(token_ids).hashes

    def prefix_overlap_rows(self, token_ids: List[int],
                            hashes: Optional[List[bytes]] = None) -> int:
        """How many leading prompt rows this engine's prefix cache already
        holds — the serving router's cache-aware score. Read-only: no
        hit/miss counters move, no LRU refresh, no pages map (scoring N
        replicas per request must not perturb the index), and it takes
        only the index's (and host store's) own locks — never the
        dispatch lock, so a replica mid-dispatch (or mid-compile) cannot
        stall routing. Rows resident only in the host spill tier count at
        ``paged.HOST_OVERLAP_DISCOUNT`` — routing still prefers true HBM
        residency but credits a replica that can restore the prefix with
        a memcpy over one that must recompute it. 0 on non-paged engines
        or when no full block matches."""
        if self.prefix_index is None:
            return 0
        if hashes is None:
            hashes = self.prefix_hashes(token_ids)
        if not hashes:
            return 0
        P = self.allocator.page_size
        n_hbm = self.prefix_index.peek(hashes)
        rows = n_hbm * P
        if self.host_store is not None and n_hbm < len(hashes):
            n_host = self.host_store.peek_chain(hashes[n_hbm:])
            if n_host >= self.host_restore_min_pages:
                rows += int(n_host * P * paged.HOST_OVERLAP_DISCOUNT)
        return rows

    # -- fleet data plane (aios_tpu/fleet/) ---------------------------------

    def export_prefix(self, token_ids: List[int], max_pages: int = 0):
        """Device->host copy of the longest HBM-resident chain prefix of
        the prompt — the transfer plane's push-on-prefill source.
        Returns ``[(hash, entry)]`` in the HostPageStore entry layout
        (the receiver ``put``s them straight into its host tier, and its
        next ``_match_prefix`` restores them with a scatter instead of a
        prefill). Empty on non-paged engines or when no full block is
        resident.

        Lock discipline mirrors ``_spill_pages``: the gather must
        MATERIALIZE under the engine lock — the matched pages can be
        evicted and rewritten by the next dispatch the moment it
        releases — so the lock pays for the gather; the device->host
        copies then run outside it on the caller's (transfer) thread."""
        return self.export_hashes(self.prefix_hashes(token_ids), max_pages)

    def export_hashes(self, hashes: List[bytes], max_pages: int = 0):
        """Hash-keyed flavor of :meth:`export_prefix` — the transfer
        servicer's ``Fetch`` path, where the puller sends chain hashes,
        not token ids. Same return shape and lock discipline."""
        if self.prefix_index is None or not hashes:
            return []
        if self.cfg.mla:
            raise paged.LatentEntryUnsupported(
                f"{self.cfg.name}: latent (MLA) pages have no KVX entry "
                "kind yet"
            )
        refuse_for_two_kinds(self.cfg, KVX_entries=True)
        with self._lock:
            snap = self.prefix_index.snapshot()
            chain = []
            for h in hashes:
                page = snap.get(h)
                if page is None:
                    break
                chain.append((h, page))
            if max_pages:
                chain = chain[:max_pages]
            if not chain:
                return []
            # aios: waive(lock-readback): host-side page-id list, no device sync
            pages = np.asarray([p for _, p in chain], np.int32)
            arrs = [self.state["k"][:, pages], self.state["v"][:, pages]]
            if self.quant_cache:
                arrs.append(self.state["k_s"][:, pages])
                arrs.append(self.state["v_s"][:, pages])
            # aios: waive(lock-readback): _spill_pages contract — the gather must materialize before the lock releases, or the exported pages could be rewritten by the next donated dispatch mid-copy
            jax.block_until_ready(arrs)
        keys = ("k", "v", "k_s", "v_s")
        host = [np.asarray(a) for a in arrs]
        return [
            (
                h,
                {
                    k: np.ascontiguousarray(host[j][:, i])
                    for j, k in enumerate(keys[: len(host)])
                },
            )
            for i, (h, _) in enumerate(chain)
        ]

    def prefix_digest(self, max_tails: int = 256) -> Dict[str, int]:
        """Bounded digest of this engine's cached chains for the
        gossiped fleet prefix index: truncated-hex chain hash ->
        depth-in-blocks (0 = depth unknown). HBM entries first (they
        are the cheap hits), then host-tier hashes into whatever of the
        cap remains. 64-bit truncation keeps heartbeats small; a
        collision can only misroute — the transfer then misses and the
        request falls back to local prefill."""
        if self.prefix_index is None:
            return {}
        out: Dict[str, int] = {}
        for h, blocks in self.prefix_index.digest(max_tails):
            out[h.hex()[:16]] = blocks
        if self.host_store is not None and len(out) < max_tails:
            for h in self.host_store.stored_hashes(max_tails - len(out)):
                out.setdefault(h.hex()[:16], 0)
        return out

    # -- public API ---------------------------------------------------------

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def free_slots(self) -> List[int]:
        return [i for i in range(self.num_slots) if not self.active[i]]

    def prefill(
        self,
        slot: int,
        token_ids: List[int],
        temperature: float = 0.0,
        top_p: float = 1.0,
    ) -> int:
        """Fill ``slot`` with a prompt; returns the first generated token."""
        return self.prefill_async(slot, token_ids, temperature, top_p).wait()

    def prefill_async(
        self,
        slot: int,
        token_ids: List[int],
        temperature: float = 0.0,
        top_p: float = 1.0,
        given: Optional[paged.PromptHashes] = None,
    ) -> PendingFirstToken:
        """Fill ``slot`` with a prompt and return as soon as its last
        prefill program is issued: the slot is active and a decode
        dispatch may follow at once; ``wait()`` of what comes back yields
        the first generated token. ``given``: the submitter's
        ``prompt_hashes`` of the prompt, if it has them."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        # one int32 array, made before the lock: the hash, the history
        # backfill and the chunks' operands are slices of it
        token_ids = np.asarray(token_ids, np.int32)[-(self.max_context - 1) :]
        true_len = len(token_ids)
        if true_len == 0:
            raise ValueError("empty prompt")

        matched, hashes = 0, []
        if self.prefix_index is not None or self.refused_prefixes is not None:
            with self._lock:
                matched, hashes = self._match_prefix(
                    slot, token_ids, given
                )
        if matched or (
            self._whole_prompt_rows is not None
            and true_len > self._whole_prompt_rows
            and self._prefix_chunk
        ):
            # tail-only admission through the chunked path, which attends
            # over the mapped prefix; release on failure so the shared
            # pages don't leak into the batcher's retry. (Pages by kind:
            # also a prompt past one slot's share of the window kind, which
            # chunks trim as they go.)
            pc = ChunkedPrefill(
                self, slot, token_ids, temperature, top_p,
                self._prefix_chunk, start_pos=matched, hashes=hashes,
            )
            try:
                first = pc.step_async()
                while first is None:
                    first = pc.step_async()
            except BaseException:
                self.release(slot)
                raise
            return first

        if self._seq_route_ok(true_len):
            return self._seq_prefill(
                slot, token_ids, temperature, top_p, hashes
            )

        bucket = self.bucket_for(true_len)
        padded = np.zeros((1, bucket), dtype=np.int32)
        padded[0, :true_len] = token_ids
        # numpy operands, placed by the graph call itself: nothing is
        # built or put on the device one by one under the lock
        ops = [
            padded, np.int32(slot), np.int32(true_len),
            np.float32(temperature), np.float32(top_p),
        ]

        with self.phases.phase("engine.prefill"), self._lock:
            if self.paged:
                # back the prompt's rows NOW (raises PoolExhausted before
                # any state is touched); the bucket's padding rows beyond
                # true_len land on the sacrificial page and are never read
                self.allocator.ensure(slot, true_len)
                ops.append(self.allocator.tables[slot].copy())
            dtok = self._devprof_note("prefill", bucket)
            self.state, first = self._prefill_fn(bucket)(
                self.params, self.state, *ops
            )
            self.active[slot] = True
            self._host_greedy[slot] = temperature < sampling.GREEDY_EPS
            self._host_lengths[slot] = true_len
            self._register_prefix(slot, token_ids, hashes)
        return PendingFirstToken(self, first, dtok)

    def _seq_route_ok(self, true_len: int) -> bool:
        """Whether a prompt of ``true_len`` rows routes through the
        sequence-sharded prefill: the path is armed, the prompt clears
        the routing floor, and the pool can in principle back the whole
        prompt at once (otherwise chunked admission — which composes
        with compression trimming — is the only admission that fits)."""
        return (
            self._seq_attn is not None
            and true_len >= self.seq_prefill_min
            and self.allocator.blocks_for(true_len)
            <= self.allocator.capacity_blocks()
        )

    def _seq_prefill(self, slot: int, ids: List[int], temperature: float,
                     top_p: float, hashes) -> PendingFirstToken:
        """Whole-prompt prefill in ONE dispatch with the sequence sharded
        over the mesh's sp axis (parallel/ring_attention.py or
        ulysses.py): every chip works a T/sp slice of the prompt instead
        of one replica grinding chunks serially. The resulting KV lands
        in the normal paged layout (the shared ``_prefill_impl_paged``
        scatter), so decode, prefix registration, spill/restore and
        failover are indistinguishable from a chunked admission. With
        compression armed the slot prunes immediately after admission —
        before prefix registration, so only the sink chain registers."""
        true_len = len(ids)
        bucket = self.bucket_for(true_len)
        padded = np.zeros((1, bucket), dtype=np.int32)
        padded[0, :true_len] = ids
        ops = (
            padded, np.int32(slot), np.int32(true_len),
            np.float32(temperature), np.float32(top_p),
        )
        with self.phases.phase("engine.prefill"), self._lock:
            self.allocator.ensure(slot, true_len)
            dtok = self._devprof_note("seq_prefill", bucket)
            self.state, first = self._seq_prefill_fn(bucket)(
                self.params, self.state, *ops,
                self.allocator.tables[slot].copy(),
            )
            self.active[slot] = True
            self._host_greedy[slot] = temperature < sampling.GREEDY_EPS
            self._host_lengths[slot] = true_len
            self.prefill_seq_sharded += 1
            flightrec.RECORDER.model_event(
                self.cfg.name, "seq_prefill", slot=slot, rows=true_len,
            )
            self._maybe_compress(slot)
            self._register_prefix(slot, ids, hashes)
        return PendingFirstToken(self, first, dtok)

    def start_chunked_prefill(
        self,
        slot: int,
        token_ids: List[int],
        temperature: float = 0.0,
        top_p: float = 1.0,
        chunk: int = 512,
        given: Optional[paged.PromptHashes] = None,
    ) -> "ChunkedPrefill":
        """Begin an incremental prefill of ``slot``; the caller drives it by
        calling ``.step()`` once per chunk and may run decode dispatches for
        the other slots in between (the continuous batcher does exactly
        that). Requires ``chunk`` to be a prefill bucket dividing
        max_context so chunk writes never spill past the cache end.
        ``given``: the submitter's ``prompt_hashes`` of the prompt."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if chunk not in self.buckets or self.max_context % chunk:
            raise ValueError(
                f"chunk {chunk} must be a prefill bucket dividing "
                f"max_context={self.max_context}"
            )
        if self.pool_replicas > 1:
            raise ValueError(
                "chunked admission is unsupported with a dp-replicated "
                "page pool (chunks read the pool during admission); use "
                "whole-prompt prefill"
            )
        ids = np.asarray(token_ids, np.int32)[-(self.max_context - 1) :]
        matched, hashes = 0, []
        if self.prefix_index is not None or self.refused_prefixes is not None:
            with self._lock:
                matched, hashes = self._match_prefix(slot, ids, given)
        if not matched and self._seq_route_ok(len(ids)):
            # the whole mesh prefills this prompt in one dispatch; the
            # driver keeps the ChunkedPrefill duck interface so the
            # batcher's admission loop (and its PoolExhausted recovery)
            # need not know which path ran
            return _SeqShardedPrefill(
                self, slot, ids, temperature, top_p, hashes
            )
        return ChunkedPrefill(
            self, slot, ids, temperature, top_p, chunk,
            start_pos=matched, hashes=hashes,
        )

    def step(self, n_steps: int = 1) -> np.ndarray:
        """Run ``n_steps`` batched decode steps in one dispatch.

        Returns tokens [n_steps, num_slots]; only columns where
        ``self.active`` are meaningful. Lengths advance for every slot
        (fixed-shape graph), clamped at the cache end.
        """
        return self._step_dispatch(n_steps)[0]

    def _step_dispatch(
        self, n_steps: int, started: Optional[threading.Event] = None,
        after: Optional[threading.Event] = None,
    ) -> Tuple[np.ndarray, np.ndarray, Optional[float], Dict[str, float]]:
        """The decode dispatch body: lock, graph call (donated state
        swap), host-length advance, then the blocking device->host token
        readback OUTSIDE the lock. Returns (tokens [n_steps, S] host
        array, post-dispatch host lengths, devprof's sample if it took
        one, seconds per phase of this dispatch). ``started`` (the
        step_async worker path) is set the moment the engine lock is
        held, so a caller can fence later engine calls behind this
        dispatch; ``after`` is the ``started`` of the dispatch issued
        before this one, which takes the lock first."""
        ph = self.phases
        try:
            lock_wait = ph.begin("engine.lock_wait")
            if after is not None:
                after.wait()
            with self._lock:
                ph.end(lock_wait)
                if started is not None:
                    started.set()
                with ph.phase("engine.enqueue", n=n_steps,
                              occ=int(self.active.sum())) as enqueue:
                    tables = ()
                    if self.paged:
                        self._back_active_slots(n_steps)
                        tables = (self._tables_operand(),)
                    fn = self._step_fn(n_steps)
                    # worker dispatches sample only with double-buffer
                    # slack (nothing queued behind this one), so a
                    # measurement never delays the next submission
                    dtok = self._devprof_note(
                        "step", n_steps, need_slack=started is not None
                    )
                    self.state, tokens = fn(self.params, self.state, *tables)
                self.decode_steps += n_steps
                self._obs_decode_steps.inc(n_steps)
                self._host_lengths = np.minimum(
                    self._host_lengths + n_steps, self.max_context - 1
                )
                lengths = self._host_lengths.copy()
            with ph.phase("engine.readback") as readback:
                host_all = np.asarray(tokens)
                host_tokens = host_all[:n_steps]
            self._take_picks(host_all, n_steps)
            # the readback above already blocked until the tokens
            # materialized, so the sample is the graph-call -> ready
            # delta at zero extra synchronization
            sample_s = self._devprof_sample(dtok)
            ready = readback.t0 + readback.dt
            before, self._tokens_ready = self._tokens_ready, ready
            if before > readback.t0:
                # issued ahead: the dispatch before this one was still on
                # the device, so this one's own time runs from that one's
                # tokens to its own, and its lock and enqueue were hidden
                spans = {"engine.readback": ready - before}
            else:
                spans = {
                    span.name: span.dt
                    for span in (lock_wait, enqueue, readback)
                }
            return host_tokens, lengths, sample_s, spans
        finally:
            if started is not None and self._devprof is not None:
                self._devprof.dequeue()

    def step_async(self, n_steps: int = 1) -> PendingDecode:
        """Run ``n_steps`` batched decode steps on the engine's dispatch
        worker thread and return WITHOUT blocking
        (``PendingDecode.wait()`` yields the host [n_steps, num_slots]
        array). The caller's thread is free through the whole dispatch —
        graph call AND token readback — so the pipelined continuous
        batcher (AIOS_TPU_DECODE_PIPELINE) emits/detokenizes/retires
        dispatch N's tokens while dispatch N+1 executes. A PoolExhausted
        from backing the slots surfaces at ``wait()`` with engine state
        untouched, exactly like the sync path.

        Two workers, so that the graph call of dispatch N+1 is made
        while N is still on the device and the device goes from one to
        the next without waiting for the host (a worker is blocked in
        N's read-back until N ends; measured: PERF.md section 6, PR 30).
        Dispatches stay FIFO (each takes the engine lock only after the
        one before it has) and serialize with every other engine call
        through the engine lock; use ``wait_started()`` before issuing
        engine calls that must order AFTER this dispatch."""
        if self._dispatch_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._dispatch_pool = ThreadPoolExecutor(
                max_workers=2,
                thread_name_prefix=f"decode-dispatch-{self.cfg.name}",
            )
        started = threading.Event()
        after, self._dispatch_started = self._dispatch_started, started
        if self._devprof is not None:
            # backlog accounting for the sampling slack check: the
            # worker only times a dispatch with nothing queued behind it
            self._devprof.enqueue()
        fut = self._dispatch_pool.submit(
            self._step_dispatch, n_steps, started, after
        )
        return PendingDecode(fut, n_steps, started)

    def step_masked(self, mask: np.ndarray) -> np.ndarray:
        """One batched decode step with a per-slot ADDITIVE logits mask
        [num_slots, vocab] fp32 (0 = allowed, -inf = forbidden) applied
        before sampling — grammar-constrained decoding (jsonmode.py).
        Returns tokens [1, num_slots]."""
        m = jnp.asarray(mask, jnp.float32)  # placed before the lock
        with self._lock:
            dtok = self._devprof_note("masked", "masked")
            if self.paged:
                self._back_active_slots(1)
                self.state, tokens = self._masked_step_fn()(
                    self.params, self.state, self._tables_operand(), m,
                )
            else:
                self.state, tokens = self._masked_step_fn()(
                    self.params, self.state, m
                )
            self.decode_steps += 1
            self._obs_decode_steps.inc()
            self._host_lengths = np.minimum(
                self._host_lengths + 1, self.max_context - 1
            )
        # readback OUTSIDE the lock (like _step_dispatch): concurrent
        # engine calls — force_pending_token, release, overlap probes that
        # do take the lock — need not wait for this dispatch to finish
        host_all = np.asarray(tokens)
        self._take_picks(host_all, 1)
        self._devprof_sample(dtok)
        return host_all[:1]

    def _take_picks(self, host_all: np.ndarray, n_steps: int) -> None:
        """Add the expert counters a ``_step_impl`` graph appended below
        its ``n_steps`` token rows (a model that counts none appends
        none)."""
        if self.counts_picks:
            total, local, rows, visited = host_all[n_steps:, 0]
            self.moe_picks_total += int(total)
            self.moe_picks_local += int(local)
            self.moe_expert_rows += int(rows)
            self.moe_experts_visited += int(visited)

    def jump_step(self, forced: np.ndarray, counts: np.ndarray) -> None:
        """Append grammar-FORCED token runs in ONE multi-token dispatch
        (compressed-FSM jump-ahead; the batcher's constrained tick).

        ``forced`` [num_slots, K] int32 holds each jumping slot's run
        (padded past its count); ``counts`` [num_slots] int32 in [0, K] —
        0 marks a slot this dispatch must not advance. K buckets up to
        the smallest ``JUMP_BUCKETS`` size (run-length-bucketed graphs,
        AOT-warmed), so steady-state constrained serving never
        recompiles. The caller must clamp each run so
        ``slot_length + counts[s] <= max_context - 2`` (the verify-write
        contract) and emits the run tokens itself — the forced tokens
        ARE the dispatch's output by construction."""
        refuse_for_state_kind(
            self.cfg, the_grammar_jump_ahead_and_its_verify_graph=True
        )
        if not self.spec_supported:
            raise ValueError(
                "jump-ahead dispatches are unsupported with a "
                "dp-replicated page pool (verify_step_paged has no "
                "shard_map pool twin)"
            )
        k = int(forced.shape[1])
        # round up to a JUMP_BUCKETS size (the exact set warmup compiled
        # — any other width would lazily build a graph mid-serving)
        kb = next((b for b in JUMP_BUCKETS if b >= k), None)
        if kb is None:
            raise ValueError(
                f"jump run of {k} tokens exceeds the largest bucket "
                f"({JUMP_BUCKETS[-1]}); clamp runs to jump_max"
            )
        forced = np.asarray(forced, np.int32)
        if kb > k:
            forced = np.concatenate(
                [forced, np.zeros((self.num_slots, kb - k), np.int32)],
                axis=1,
            )
        counts = np.asarray(counts, np.int32)
        with self._lock:
            args = ()
            if self.paged:
                self._back_active_slots(kb + 1)
                args = (self._tables_operand(),)
            dtok = self._devprof_note("jump", kb)
            self.state = self._jump_fn(kb)(
                self.params, self.state, *args, forced, counts,
            )
            self.decode_steps += 1
            self._obs_decode_steps.inc()
            self.jump_dispatches += 1
            self.jump_tokens += int(counts.sum())
            self._host_lengths = np.minimum(
                self._host_lengths + counts, self.max_context - 1
            )
            sync_ref = self.state["lengths"] if dtok is not None else None
        if dtok is not None:
            # jump has no token readback (the forced run IS the output);
            # a sampled dispatch blocks on the new state OUTSIDE the lock
            # — the constrained tick already drained the pipeline, so
            # nothing queues behind this
            self._devprof_sample_sync(dtok, sync_ref)

    def force_pending_token(self, slot: int, token_id: int) -> None:
        """Replace ``slot``'s pending (sampled-but-not-yet-consumed) token.

        Grammar-constrained requests use this right after prefill: the
        prefill graph samples the first token UNMASKED, so the batcher
        overwrites it with the grammar's forced opener (e.g. "{" for
        json_object mode) before any decode dispatch consumes it."""
        # aios: waive(lock-readback): two eager updates of the donated state, which only the lock's holder may touch; a constrained admission has flushed the pipeline, so no dispatch worker waits for the lock meanwhile
        with self._lock:
            col = int(self._host_lengths[slot])
            self.state["last_tokens"] = (
                self.state["last_tokens"].at[slot].set(token_id)
            )
            self.state["history"] = (
                self.state["history"].at[slot, col].set(token_id)
            )

    def spec_step(
        self, n_rounds: int = 8, draft_len: int = 7, ngram: int = 3
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Run ``n_rounds`` speculative decode rounds in one dispatch.

        Returns (tokens [n_rounds, num_slots, draft_len+1],
        counts [n_rounds, num_slots]): in round r, slot s emitted the first
        ``counts[r, s]`` entries of ``tokens[r, s]`` — at least 1 (a plain
        decode step's token), up to ``draft_len+1`` when the whole n-gram
        draft was accepted. Greedy slots emit exactly the plain-greedy
        sequence; temp>0 slots never speculate and emit 1 sampled
        token/round. Only columns where ``self.active`` are meaningful.
        """
        # upper bound keeps active slots' history writes strictly below the
        # sacrificial last pad column reserved for inactive slots
        if not 1 <= draft_len <= spec.HISTORY_PAD - 2:
            raise ValueError(
                f"draft_len must be in [1, {spec.HISTORY_PAD - 2}]"
            )
        if ngram < 1:
            raise ValueError("ngram must be >= 1")
        if not self.spec_supported:
            raise ValueError(
                "speculative decoding is unsupported with a dp-replicated "
                "page pool (verify_step_paged has no shard_map pool twin)"
            )
        if not self.track_history:
            raise ValueError(
                "speculative decoding needs the token history "
                "(track_history=True; the n-gram proposer reads it)"
            )
        with self._lock:
            if self.paged:
                # worst case: full acceptance every round; unused pages
                # recycle at release
                self._back_active_slots(n_rounds * (draft_len + 1))
                args = (self._tables_operand(),)
            else:
                args = ()
            dtok = self._devprof_note(
                "spec", (n_rounds, draft_len, ngram)
            )
            self.state, (tokens, counts) = self._spec_fn(
                n_rounds, draft_len, ngram
            )(self.params, self.state, *args)
            self.decode_steps += n_rounds
            self._obs_decode_steps.inc(n_rounds)
            self.spec_rounds += n_rounds
            self.spec_proposer_rounds["ngram"] += n_rounds
            # acceptance denominator: (round, active-slot) pairs — a
            # per-slot rate that doesn't scale with batch occupancy
            active_rounds = n_rounds * int(self.active.sum())
            self.spec_slot_rounds += active_rounds
        # the device->host readback happens OUTSIDE the engine lock
        # (the step()/step_masked() discipline, lock-readback rule):
        # concurrent peek/stats callers must not wait on the transfer
        counts = np.asarray(counts)
        tokens = np.asarray(tokens)
        self._devprof_sample(dtok)
        # fold the data-dependent length advance back in under the lock;
        # dispatches all come from the scheduler thread (spec ticks flush
        # the pipeline first), so nothing interleaves between the two
        # critical sections
        with self._lock:
            emitted = int(counts[:, self.active].sum())
            self.spec_tokens += emitted
            self.spec_proposer_accepted["ngram"] += max(
                emitted - active_rounds, 0
            )
            self._host_lengths = np.minimum(
                self._host_lengths + counts.sum(axis=0), self.max_context - 1
            )
        return tokens, counts

    def spec_step_draft(
        self, n_rounds: int = 8, draft_len: int = 7
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run ``n_rounds`` DRAFT-MODEL speculative rounds: the attached
        small model (spec.DraftModel, int4 weights) proposes K tokens per
        greedy slot and the serving model verifies them — propose,
        verify, accept, draft-KV sync all inside ONE fused dispatch per
        call (bulk draft catch-up for freshly admitted slots runs as
        separate ingest dispatches first).

        Returns (tokens [n_rounds, num_slots, draft_len+1],
        counts [n_rounds, num_slots], proposed [n_rounds, num_slots]) —
        tokens/counts exactly as ``spec_step``; ``proposed`` is the draft
        tokens offered per (round, slot) (0 or draft_len), the honest
        acceptance denominator for the per-proposer EWMA. Greedy slots
        emit exactly the plain-greedy sequence; temp>0 slots never
        speculate."""
        if self.draft is None:
            raise ValueError(
                "no draft model attached (TPUEngine(draft=...) / "
                "AIOS_TPU_DRAFT_MODEL)"
            )
        if not 1 <= draft_len <= spec.HISTORY_PAD - 2:
            raise ValueError(
                f"draft_len must be in [1, {spec.HISTORY_PAD - 2}]"
            )
        self._draft_catchup(headroom=draft_len + 1)
        with self._lock:
            if self.paged:
                self._back_active_slots(n_rounds * (draft_len + 1))
                args = (self._tables_operand(),)
            else:
                args = ()
            dtok = self._devprof_note(
                "draft_spec", (n_rounds, draft_len, draft_len + 1)
            )
            self.state, self.draft_state, (tokens, counts, proposed) = (
                self._draft_spec_fn(n_rounds, draft_len)(
                    self.params, self.draft.params, self.state,
                    self.draft_state, *args,
                )
            )
            self.decode_steps += n_rounds
            self._obs_decode_steps.inc(n_rounds)
            self.spec_rounds += n_rounds
            self.spec_proposer_rounds["draft"] += n_rounds
            active_rounds = n_rounds * int(self.active.sum())
            self.spec_slot_rounds += active_rounds
        # readbacks OUTSIDE the lock (lock-readback discipline); the
        # draft host-length mirror reads the post-dispatch device value
        # rather than replaying R rounds of catchup/propose/clamp math
        counts = np.asarray(counts)
        tokens = np.asarray(tokens)
        proposed = np.asarray(proposed)
        d_len = np.asarray(self.draft_state["lengths"])
        self._devprof_sample(dtok)
        with self._lock:
            emitted = int(counts[:, self.active].sum())
            self.spec_tokens += emitted
            self.spec_proposer_accepted["draft"] += max(
                emitted - active_rounds, 0
            )
            self.draft_proposed_tokens += int(proposed[:, self.active].sum())
            self._host_lengths = np.minimum(
                self._host_lengths + counts.sum(axis=0), self.max_context - 1
            )
            self._draft_host_lengths = d_len.astype(np.int64)
        return tokens, counts, proposed

    def _draft_catchup(self, headroom: int) -> None:
        """Bulk-ingest history into the draft KV until every active
        slot's draft gap fits inside the fused rounds' per-round
        catch-up width (``headroom``). Freshly admitted slots arrive
        with a whole-prompt gap; each pass advances every lagging slot
        by up to one ingest bucket. Dispatches all come from the
        scheduler thread (like spec_step), so the host mirrors can't
        race the device state."""
        buckets = self._draft_ingest_buckets()
        while True:
            gaps = (
                self._host_lengths - self._draft_host_lengths
            )[self.active & self._host_greedy]
            gap_max = int(gaps.max()) if gaps.size else 0
            if gap_max <= headroom:
                return
            w = next((b for b in buckets if b >= gap_max), buckets[-1])
            with self._lock:
                dtok = self._devprof_note("draft_ingest", ("ingest", w))
                self.draft_state = self._draft_ingest_fn(w)(
                    self.draft.params, self.draft_state,
                    self.state["history"], self.state["lengths"],
                    self.state["active"], self.state["temps"],
                )
                self.draft_ingest_dispatches += 1
            self._draft_host_lengths = np.asarray(
                self.draft_state["lengths"]
            ).astype(np.int64)
            self._devprof_sample(dtok)

    def release(self, slot: int) -> None:
        """Free ``slot``: its host half (``retire``) and its pages and
        device flags (``release_pages``) at once. The continuous batcher
        calls the halves apart, the second behind its next dispatch."""
        self.retire(slot)
        self.release_pages(slot)

    def retire(self, slot: int) -> None:
        """The host half of a release, no lock taken: the slot reads free
        (``free_slots``, the occupancy) and the next dispatch backs no
        rows for it. Its pages stay its own until ``release_pages``: the
        caller gives the slot to no new tenant before that."""
        self.active[slot] = False
        self._host_lengths[slot] = 0
        self._draft_host_lengths[slot] = 0
        self._host_greedy[slot] = False
        self._win_starts[slot] = 0  # next occupant starts uncompressed

    def release_pages(self, slot: int) -> None:
        """The engine half of a release, under the engine lock: the
        slot's pages (and state) go back and the device's length and
        active flag are reset (one small program: ``_reset_slot``)."""
        slot_op = np.int32(slot)
        # aios: waive(lock-readback): the draft length's eager reset, of an engine that speculates with a draft model alone; the continuous batcher calls this behind the dispatch it has handed over (_settle_retired), so the worker's enqueue does not wait for it
        with self._lock:
            if self.allocator is not None:
                self.allocator.free_slot(slot)  # pages recycle instantly
            if self.slot_states is not None:
                # nothing to hand back or to zero: the next tenant's first
                # chunk starts from zeros (paged.py's header)
                self.slot_states.free_slot(slot)
            self.state["lengths"], self.state["active"] = _reset_slot(
                self.state["lengths"], self.state["active"], slot_op
            )
            if self.draft_state is not None:
                # the next occupant's draft KV rebuilds from history via
                # ingest; zeroing the length is the whole reset
                self.draft_state["lengths"] = (
                    self.draft_state["lengths"].at[slot].set(0)
                )

    def slot_length(self, slot: int) -> int:
        return int(self._host_lengths[slot])

    def compressed_resident_pages(self) -> int:
        """Pages currently resident for slots pruned by window+sink
        compression (sink + trailing window + the partial block) — what
        ``aios_tpu_kv_compress_resident_pages`` reports, and the number
        the long-context bench compares against the uncompressed
        footprint."""
        if not self.kv_compress_armed or self.allocator is None:
            return 0
        return sum(
            self.allocator.slot_pages_resident(s)
            for s in range(self.num_slots)
            if self._win_starts[s] > 0
        )

    def stats(self) -> Dict[str, float]:
        """Serving counters for observability (HealthCheck details, the
        monitoring agent's metric push — the reference's llama-server
        exposes nothing comparable)."""
        out: Dict[str, float] = {
            "decode_steps": self.decode_steps,
            "active_slots": int(self.active.sum()),
            "batch_occupancy": round(
                float(self.active.sum()) / self.num_slots, 3
            ) if self.num_slots else 0.0,
            "xla_compiles": self.compile_events,
            "xla_compile_s": round(self.compile_seconds, 2),
            # thread CPU seconds inside the warmup.trace and warmup.lower
            # spans: their wall seconds less this is what the compiling
            # thread stood off the processor (the GIL, I/O)
            "warmup_trace_cpu_seconds": self.warmup_trace_cpu_seconds,
            # per-head matrices put heads-major at load
            # (latent.serving_layout); 0 without latent attention
            "latent_leaves_relaid": self.latent_leaves_relaid,
        }
        # JAX's persistent compile cache, process-wide (the pool reports
        # them once, not summed over replicas): misses = requests - hits
        out["compile_cache_requests"], out["compile_cache_hits"] = (
            flightrec.compile_cache()
        )
        if self.spec_rounds:
            out["spec_rounds"] = self.spec_rounds
            # mean tokens emitted per slot per verify round (1.0 = nothing
            # accepted; draft_len+1 = every draft accepted)
            out["spec_tokens_per_round"] = round(
                self.spec_tokens / max(self.spec_slot_rounds, 1), 2
            )
            out["spec_accepted"] = max(
                self.spec_tokens - self.spec_slot_rounds, 0
            )
            for p in spec.SPEC_PROPOSERS:
                if self.spec_proposer_rounds[p]:
                    out[f"spec_{p}_rounds"] = self.spec_proposer_rounds[p]
                    out[f"spec_{p}_accepted"] = (
                        self.spec_proposer_accepted[p]
                    )
        if self.draft is not None:
            out["draft_ingest_dispatches"] = self.draft_ingest_dispatches
            out["draft_proposed_tokens"] = self.draft_proposed_tokens
            if self.draft_proposed_tokens:
                out["draft_acceptance"] = round(
                    self.spec_proposer_accepted["draft"]
                    / self.draft_proposed_tokens, 3
                )
        if self.jump_dispatches:
            out["jump_dispatches"] = self.jump_dispatches
            out["jump_tokens"] = self.jump_tokens
        if self.allocator is not None:
            # the sum over kinds where the pool has two
            out["kv_pages_in_use"] = self.allocator.pages_in_use()
            out["kv_pages_free"] = self.allocator.free_pages
            if self._layout is not None:
                out.update(self.allocator.stats())
                out["prefix_hits_refused_window"] = (
                    self.prefix_hits_refused_window
                )
            # one cache row of one layer, as STORED (both pool arrays)
            out["kv_row_bytes"] = sum(self.cfg.kv_row_dims) * (
                self.state["k"].dtype.itemsize if self.state else 0
            )
            # model.chunk_tiles_on_host, summed over the chunks issued
            out["prefill_kv_tiles_read"] = self.prefill_kv_tiles_read
            out["prefill_kv_tiles_mapped"] = self.prefill_kv_tiles_mapped
        if self.slot_states is not None:
            out.update(self.slot_states.stats())
            rows = {"kda": "kda", "mamba2": "mamba"}[self.cfg.state_kind]
            out[f"{rows}_rows_prefill"] = self.state_rows_prefill
            out[f"{rows}_rows_decode"] = self.state_rows_decode
            out["prefix_hits_refused_state"] = self.prefix_hits_refused_state
            out["prefix_rows_refused_state"] = self.prefix_rows_refused_state
        if self.counts_picks:
            # summed on the device, read back with the decode tokens
            out["moe_picks_total"] = self.moe_picks_total
            out["moe_picks_local"] = self.moe_picks_local
            out["moe_expert_rows"] = self.moe_expert_rows
            out["moe_experts_visited"] = self.moe_experts_visited
        if self.cfg.hc:
            out["hc_mix_rows"] = self.hc_mix_rows
        if self.kv_compress_armed:
            out["kv_compress_slots"] = self.kv_compress_slots
            out["kv_compress_pages_pruned"] = self.kv_pages_pruned
            out["kv_compress_resident_pages"] = self.compressed_resident_pages()
        if self._seq_attn is not None:
            out["prefill_seq_sharded"] = self.prefill_seq_sharded
        if self.prefix_index is not None or self.refused_prefixes is not None:
            # beside the batcher's `admissions`: those matched on hashes
            # the submitter's thread had computed, and the matched
            # prefixes whose history backfill was left out
            out["admissions_prehashed"] = self.admissions_prehashed
            out["history_backfills_skipped"] = self.history_backfills_skipped
        if self.prefix_index is not None:
            out["prefix_hits"] = self.prefix_index.hits
            out["prefix_misses"] = self.prefix_index.misses
            out["prefix_rows_reused"] = self.prefix_rows_reused
        if self.host_store is not None:
            s = self.host_store
            out["prefix_rows_restored"] = self.prefix_rows_restored
            out["host_tier_bytes"] = s.bytes_resident
            out["host_tier_capacity_bytes"] = s.max_bytes
            out["host_tier_spills"] = s.spills
            out["host_tier_restores"] = s.restores
            out["host_tier_hits"] = s.hits
            out["host_tier_misses"] = s.misses
            out["host_tier_corrupt"] = s.corruptions
            out["host_tier_restore_s"] = round(self.host_restore_seconds, 3)
        return out

    def close(self) -> None:
        """Release device memory NOW. The jitted step fns close over
        ``self`` (self._step_fns -> lambda -> self), so a dropped engine is
        an uncollected reference CYCLE and its HBM survives until a gc pass
        — on a 16 GB chip that breaks the next model load. Explicitly
        breaking the cycle and dropping the arrays frees the buffers
        deterministically (model_manager.unload_model and the bench rely on
        this)."""
        import gc

        if self._spill_q is not None:
            # stop accepting spills, then drain + stop the worker BEFORE
            # dropping the state (its queued items hold materialized
            # gather results, independent of the pool buffer). _spill_q
            # itself stays set: a worker that outlives the join (deep
            # backlog) drains through its local reference and exits on
            # the sentinel — nulling it would crash the worker mid-drain.
            if self.prefix_index is not None:
                self.prefix_index.spill = None
            self._spill_q.put(None)
            if self._spill_thread is not None:
                self._spill_thread.join(timeout=5)
            self._spill_thread = None
        if self.host_store is not None:
            # after the worker exited this empties the store for good; on
            # a timed-out join the straggler's late inserts are bounded
            # by the store budget and freed when the engine is collected
            self.host_store.clear()
        if self._dispatch_pool is not None:
            # drain the decode-dispatch worker BEFORE dropping the state:
            # a queued dispatch running against cleared state would die on
            # a confusing error inside the worker instead of here
            self._dispatch_pool.shutdown(wait=True)
            self._dispatch_pool = None
        with self._lock:
            self._step_fns.clear()
            self._prefill_fns.clear()
            self._chunk_fns.clear()
            self._spec_fns.clear()
            self._restore_fns.clear()
            self._jump_fns.clear()
            self._draft_fns.clear()
            self._seq_prefill_fns.clear()
            self._seq_attn = None
            self.state = {}
            self.params = None
            self.draft = None  # DraftModel params may be pool-shared
            self.draft_state = None
            self._attn_impl = None
        gc.collect()

    # Admission granularity for long prompts; the batcher's default chunk
    # size and warmup's pre-compiled chunk graphs both read this, so the
    # production graphs and the readiness gate can't drift apart.
    prefill_chunk_default = 512

    def admission_chunk(self, prefill_chunk: Optional[int] = None) -> int:
        """Rows of one chunk of chunked admission, 0 where every prompt
        prefills whole: ``prefill_chunk`` (None -> prefill_chunk_default)
        where it is a prefill bucket that divides ``max_context`` and the
        pool is not dp-partitioned (chunks read the pool during admission).
        The batcher admits by it and ``warmup`` compiles by it."""
        ck = self.prefill_chunk_default if prefill_chunk is None else prefill_chunk
        if (ck and ck in self.buckets and self.max_context % ck == 0
                and self.pool_replicas == 1):
            return ck
        return 0

    def warmup(
        self,
        # must cover every step size the continuous batcher dispatches —
        # a size missing here compiles for multiple seconds ON the
        # scheduler thread at first use, stalling every live request
        # (measured: ~2 s added to all 8 agents' TTFT)
        step_sizes: Tuple[int, ...] = (DECODE_STEPS,),
        prefill_chunk: Optional[int] = None,  # None -> prefill_chunk_default
        masked_step: bool = False,  # also compile the grammar-masked step
        spec_sizes: Tuple[int, ...] = (),  # speculative round counts
        spec_draft_len: int = 7,
        spec_ngram: int = 3,
        # jump-ahead run buckets; None -> JUMP_BUCKETS when masked_step
        # (constrained deployments dispatch jump_step), () to skip
        jump_sizes: Optional[Tuple[int, ...]] = None,
    ) -> None:
        """AOT-compile every graph the serving path can hit (LoadModel
        readiness gate — the reference's /health polling equivalent,
        model_manager.rs:222-263; without this the first Infer would eat
        20-40 s of XLA compile).

        Dispatch-free: each graph is ``jit.lower(...).compile()``d against
        the live param/state avals, so warmup moves no device state — no
        synthetic prompts, no page allocations, no prefix-index or
        host-store pollution to roll back — and ``engine.stats()`` compile
        counters stay FLAT afterwards (the no-compile-after-warmup
        regression gate in tests/test_decode_pipeline.py).

        Coverage: the whole-prompt prefill graph of every power-of-two
        bucket the pool can back AND the batcher prefills whole: with
        chunked admission on (``admission_chunk``, the rule
        ``ContinuousBatcher`` reads too) a longer prompt admits in chunks,
        so the buckets above the chunk size are left out; with it off (0,
        or a dp-partitioned pool) every bucket. Then the chunked-admission
        graphs (mid chunk + every final bucket <= ``prefill_chunk``; pass
        the batcher's size if it overrides the shared default, 0 to skip),
        the prefix-HIT graphs (history backfill per bucket + the
        prefix-chunk tail graphs), every ``step_sizes`` decode graph, the
        grammar-masked step when ``masked_step``, speculative round graphs
        for ``spec_sizes``, and the host-tier restore scatter buckets.

        A caller outside the batcher (``generate``, the single-shot CLI)
        that prefills a prompt longer than the chunk size whole compiles
        that bucket's graph on first use, as any decode size outside
        ``step_sizes`` does.
        """
        t0 = time.perf_counter()
        before = self.compile_events
        ck = self.admission_chunk(prefill_chunk)
        for bucket in self.buckets:
            if self.paged and self.allocator.blocks_for(
                bucket // 2 + 1
            ) > self.allocator.capacity_blocks():
                continue  # pool can't back prompts of this bucket anyway
            if (self._whole_prompt_rows is not None
                    and bucket // 2 + 1 > self._whole_prompt_rows):
                continue  # such a prompt admits in chunks (prefill_async)
            if not ck or bucket <= ck:
                self.compile_prefill_fn(bucket)
            if (
                self._seq_attn is not None
                and bucket >= self.bucket_for(self.seq_prefill_min)
            ):
                # every bucket the routing floor can reach gets its
                # sp-sharded twin, so a huge admission never compiles
                # on the scheduler thread
                self.compile_seq_prefill_fn(bucket)
        if ck:
            self.compile_chunk_fn(ck, final=False)
            for b in self.buckets:
                if b > ck:
                    break
                self.compile_chunk_fn(b, final=True)
        if self.prefix_index is not None:
            # the HIT path: history backfill for the matched rows + the
            # tail's chunk graphs at the prefix chunk size (distinct from
            # the batcher's chunk size when they diverge)
            for b in self.buckets:
                self.compile_hist_fn(b)
            pc = self._prefix_chunk
            if pc:
                self.compile_chunk_fn(pc, final=False)
                for b in self.buckets:
                    if b > pc:
                        break
                    self.compile_chunk_fn(b, final=True)
        for n in step_sizes:
            self.compile_step_fn(n)
        if masked_step:  # json-mode deployments dispatch step_masked
            self.compile_masked_fn()
        if jump_sizes is None:
            # jump-ahead rides the constrained path, but respect the
            # escape hatch: a deployment that disabled it must not pay
            # len(JUMP_BUCKETS) jump-graph compiles (and resident
            # executables) at every engine start
            enabled = _env_flag("AIOS_TPU_JUMP_AHEAD")
            if enabled is None:
                enabled = bool(getattr(self.cfg, "jump_ahead", True))
            jump_sizes = JUMP_BUCKETS if (masked_step and enabled) else ()
        for k in jump_sizes:
            self.compile_jump_fn(k)
        for n in spec_sizes:
            self.compile_spec_fn(n, spec_draft_len, spec_ngram)
            # the draft proposer serves the same round sizes; its n-gram
            # twin above stays warm too (the batcher's auto-disable
            # ladder falls back draft -> ngram without a compile stall)
            self.compile_draft_spec_fn(n, spec_draft_len)
        if spec_sizes and self.draft is not None:
            self.compile_draft_ingest_fns()
        if self.host_store is not None:
            # a restore chain is bounded by the prompt's full blocks AND
            # the pool; the last bucket rounds UP past capacity (a 10-page
            # restore on a 15-page pool buckets to 16 — stopping at
            # nb <= cap would leave exactly that bucket to compile
            # mid-serving)
            cap = min(
                self.allocator.capacity_blocks(),
                (self.max_context - 1) // self.allocator.page_size,
            )
            nb = 1
            while True:
                self.compile_restore_fn(nb)
                if nb >= cap:
                    break
                nb *= 2
        log.info(
            "%s: warmup AOT-compiled %d graph(s) in %.1fs",
            self.cfg.name, self.compile_events - before,
            time.perf_counter() - t0,
        )

    # -- convenience (tests, single-shot CLI) -------------------------------

    def generate(
        self,
        token_ids: List[int],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_p: float = 1.0,
        stop_tokens: Tuple[int, ...] = (),
        slot: int = 0,
        chunk: int = 8,
        speculative: bool = False,
        draft_len: int = 7,
        ngram: int = 3,
    ) -> List[int]:
        """Single-request generation loop (the continuous-batching scheduler
        in engine/batching.py is the production path). ``speculative=True``
        decodes via n-gram speculative rounds (spec.py) — identical greedy
        output, fewer dispatches; ``speculative="draft"`` uses the
        attached draft model instead; sampling requests fall back to
        plain stepping on their own."""
        first = self.prefill(slot, token_ids, temperature, top_p)
        out = [first]
        while len(out) < max_new_tokens and out[-1] not in stop_tokens:
            budget = min(chunk, max_new_tokens - len(out))
            room = self.max_context - 1 - self.slot_length(slot)
            if room <= 0:
                break
            if speculative:
                pre = self.slot_length(slot)  # before the dispatch mutates it
                if speculative == "draft":
                    toks, counts, _ = self.spec_step_draft(
                        min(budget, room), draft_len=draft_len
                    )
                else:
                    toks, counts = self.spec_step(
                        min(budget, room), draft_len=draft_len, ngram=ngram
                    )
                flat: List[int] = []
                for r in range(toks.shape[0]):
                    if pre >= self.max_context - 1:
                        # slot saturated mid-dispatch: later rounds' cache
                        # writes collapse onto the last row (verify_step's
                        # scatter contract) — their tokens are indeterminate
                        # and must not be consumed
                        break
                    flat.extend(int(t) for t in toks[r, slot, : counts[r, slot]])
                    pre += int(counts[r, slot])
                toks = flat
            else:
                toks = self.step(min(budget, room))[:, slot].tolist()
            for t in toks:
                out.append(int(t))
                if t in stop_tokens:
                    break
            if len(out) > max_new_tokens:  # speculative overshoot
                del out[max_new_tokens:]
        self.release(slot)
        if stop_tokens:
            for i, t in enumerate(out):
                if t in stop_tokens:
                    return out[: i + 1]
        return out


class ChunkedPrefill:
    """Driver for an in-flight incremental prefill of one slot.

    Each ``step()`` call processes one chunk (holding the engine lock only
    for that chunk's dispatch); between calls the owner may run
    ``engine.step`` for the other slots. The final chunk samples the first
    token and activates the slot: ``step_async()`` hands the token back
    still on the device (a decode dispatch may be issued behind the chunk
    before it is read), ``step()`` reads it.

    While chunks are in flight the slot's device-side ``active`` flag stays
    False, so interleaved decode dispatches write this slot's (ignored) K/V
    to the sacrificial last cache row — never corrupting rows the prefill
    has already filled — and stream zero cache rows for it
    (model.decode_step's ``active`` gating). The sacrificial row is never
    read: the mask only exposes rows [0, length] and a request retires when
    its length reaches max_context - 1.
    """

    def __init__(
        self,
        engine: TPUEngine,
        slot: int,
        token_ids: List[int],
        temperature: float,
        top_p: float,
        chunk: int,
        start_pos: int = 0,  # rows already in the cache (matched prefix)
        hashes=(),  # block hashes to publish to the prefix index when done
    ) -> None:
        # one int32 array, made once: a chunk's operand is a slice of it
        ids = np.asarray(token_ids, np.int32)[-(engine.max_context - 1) :]
        if not len(ids):
            raise ValueError("empty prompt")
        self.engine = engine
        self.slot = slot
        self.ids = ids
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.chunk = int(chunk)
        self.pos = int(start_pos)
        self.hashes = hashes
        self.first: Optional[PendingFirstToken] = None
        # the next chunk's operands, where ``stage`` has placed them
        self._staged: Optional[tuple] = None

    @property
    def done(self) -> bool:
        return self.first is not None

    def step(self) -> Optional[int]:
        """Process the next chunk; returns the first sampled token when the
        prompt is fully admitted, else None."""
        first = self.step_async()
        return None if first is None else first.wait()

    def stage(self) -> None:
        """Put the next chunk's operands on the device, ahead of the lock
        and the graph call that issue it: the ones that need no lock (the
        slot's table and window start join them under it, as numpy values
        the call places). ``step_async`` does it first thing where nobody
        has; the pipelined batcher does it a tick ahead, behind a decode
        dispatch it has handed over and while the device still runs the
        one before, where it has little room (a placement is a
        millisecond of the calling thread among the serving threads, and
        a final chunk has seven), so that the next tick's issue is the
        lock, the table and the graph call (PERF.md, PR 41)."""
        if self.done or self._staged is not None:
            return
        remaining = len(self.ids) - self.pos
        final = remaining <= self.chunk
        n = min(self.chunk, remaining)
        bucket = self.engine.bucket_for(n) if final else self.chunk
        padded = np.zeros((1, bucket), dtype=np.int32)
        padded[0, :n] = self.ids[self.pos : self.pos + n]
        ops = [padded, np.int32(self.slot), np.int32(self.pos)]
        if final:
            ops += [
                np.int32(n), np.int32(len(self.ids)),
                np.float32(self.temperature), np.float32(self.top_p),
            ]
        self._staged = (n, final, bucket, tuple(jnp.asarray(o) for o in ops))

    def step_async(self) -> Optional[PendingFirstToken]:
        """Issue the next chunk; returns the pending first token once the
        final chunk is issued, else None."""
        if self.done:
            return self.first
        eng = self.engine
        self.stage()
        n, final, bucket, ops = self._staged
        with eng.phases.phase("engine.prefill"), eng._lock:
            if eng.paged:
                # back this chunk's rows before dispatching; PoolExhausted
                # surfaces to the batcher with all state untouched. On
                # windowed models, blocks the remaining chunks can no
                # longer attend to free as admission advances — a 64k
                # prompt's residency is bounded by the window, not the
                # prompt (registration then skips the trimmed slot).
                # Compression-armed engines prune the same way: once the
                # admitted rows cross the threshold, the middle pages
                # free and later chunks mask them, so a long prompt's
                # peak residency is sink + window + one chunk.
                if eng.cfg.sliding_window is not None:
                    eng.allocator.trim_below_window(
                        self.slot, self.pos, eng.cfg.sliding_window
                    )
                eng._maybe_compress(self.slot, length=self.pos)
                eng.allocator.ensure(self.slot, self.pos + n)
                if eng.slot_states is not None:
                    eng.slot_states.take(self.slot)
                ops += (eng.allocator.tables[self.slot].copy(),)
                read, mapped = model.chunk_tiles_on_host(
                    eng.cfg, self.pos, bucket, eng.allocator.max_blocks,
                    eng.allocator.page_size,
                )
                eng.prefill_kv_tiles_read += read
                eng.prefill_kv_tiles_mapped += mapped
                if eng.kv_compress_armed:
                    ops += (np.int32(eng._win_starts[self.slot]),)
            dtok = eng._devprof_note("chunk", (bucket, final))
            if final:
                eng.state, first = eng._chunk_fn(bucket, True)(
                    eng.params, eng.state, *ops
                )
                eng.active[self.slot] = True
                eng._host_greedy[self.slot] = (
                    self.temperature < sampling.GREEDY_EPS
                )
                eng._host_lengths[self.slot] = len(self.ids)
                eng._register_prefix(self.slot, self.ids, self.hashes)
                self.first = PendingFirstToken(eng, first, dtok)
            else:
                eng.state = eng._chunk_fn(bucket, False)(
                    eng.params, eng.state, *ops
                )
        if not final:
            # mid-chunk samples are submit-side (their writes overlap the
            # next chunk's staging); a final chunk's lands where its
            # token is read
            eng._devprof_sample(dtok)
        self._staged = None  # kept until here: a PoolExhausted retries it
        self.pos += n
        return self.first


class _SeqShardedPrefill:
    """ChunkedPrefill-shaped driver for the sequence-sharded prefill:
    ONE ``step()`` runs the whole sp-sharded admission dispatch
    (engine._seq_prefill), so the batcher's incremental-admission loop —
    including its PoolExhausted eviction/retry recovery — drives both
    paths identically. ``pos`` moves 0 -> len(ids) in that single step,
    which is what the flight recorder's per-chunk rows-consumed
    accounting reads."""

    def __init__(self, engine: TPUEngine, slot: int, token_ids: List[int],
                 temperature: float, top_p: float, hashes) -> None:
        self.engine = engine
        self.slot = slot
        self.ids = list(token_ids)
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.hashes = hashes
        self.pos = 0
        self.first: Optional[PendingFirstToken] = None

    @property
    def done(self) -> bool:
        return self.first is not None

    def step(self) -> Optional[int]:
        return self.step_async().wait()

    def stage(self) -> None:
        """Nothing to place ahead: the one dispatch takes the prompt whole."""

    def step_async(self) -> PendingFirstToken:
        if not self.done:
            self.first = self.engine._seq_prefill(
                self.slot, self.ids, self.temperature, self.top_p,
                self.hashes,
            )
            self.pos = len(self.ids)
        return self.first
