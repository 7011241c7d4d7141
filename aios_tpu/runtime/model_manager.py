"""Model lifecycle + intelligence-level routing for the TPU runtime.

Reference parity (runtime/src/model_manager.rs):
  * name -> managed model registry with states loading/ready/error/unloading
    (model_manager.rs:24-29) — here a model is an in-process TPUEngine +
    ContinuousBatcher + tokenizer, not a llama-server child, so "loading"
    covers dequantize + device_put + warm-compile and "ready" means the
    decode graph is compiled (the /health polling of the reference,
    model_manager.rs:222-263, collapses into warmup()).
  * startup auto-scan of AIOS_MODEL_DIR for *.gguf with context length
    chosen by file size (runtime/src/main.rs:65-132).
  * select_model_for_level routing ladders with partial case-insensitive
    name matching (model_manager.rs:462-518): reactive -> None;
    operational -> tinyllama > deepseek > mistral; tactical -> deepseek >
    qwen3 > mistral > tinyllama; strategic -> qwen3 > deepseek > mistral.

TPU-specific: `synthetic://<preset>` model paths build a random-weight model
of that architecture (benchmarks and tests run without weight files).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp

from .. import backend
from ..analysis.locks import make_lock
from ..engine import gguf as gguf_mod
from ..engine import model as model_mod
from ..engine import weights as weights_mod
from ..engine.batching import ContinuousBatcher
from ..engine.config import (
    PRESETS,
    ModelConfig,
    from_gguf_metadata,
    TINY_MOE,
    TINY_TEST,
)
from ..engine.engine import TPUEngine
from ..engine.tokenizer import (
    BaseTokenizer,
    ByteTokenizer,
    HFTokenizer,
    gguf_tokenizer,
)
from ..obs import flightrec
from ..serving import ReplicaPool, ServingConfig

log = logging.getLogger("aios.runtime.models")

STATE_LOADING = "loading"
STATE_READY = "ready"
STATE_ERROR = "error"
STATE_UNLOADING = "unloading"

# Routing ladders per intelligence level (model_manager.rs:462-505).
LEVEL_LADDERS: Dict[str, List[str]] = {
    "reactive": [],
    "operational": ["tinyllama", "deepseek", "mistral"],
    "tactical": ["deepseek", "qwen3", "mistral", "tinyllama"],
    "strategic": ["qwen3", "deepseek", "mistral"],
}


@dataclass
class ManagedModel:
    name: str
    config: ModelConfig
    # replica 0's engine/batcher, kept for single-replica callers and
    # HealthCheck snapshots; the POOL is the serving entry point
    engine: TPUEngine
    batcher: ContinuousBatcher
    tokenizer: BaseTokenizer
    state: str = STATE_LOADING
    loaded_at: int = 0
    last_used: int = 0
    request_count: int = 0
    error: str = ""
    # estimated per-chip HBM this model pins (weights + KV); co-resident
    # loads subtract it from the auto-degradation budget
    hbm_chip_bytes: float = 0.0
    # the replica pool fronting this model (aios_tpu/serving/): admission
    # -> cache-aware routing -> one replica's batcher. None only for
    # error-state placeholders.
    pool: Optional[ReplicaPool] = None
    # load identity, so a LoadModel for the same name with a different
    # source/geometry hot-swaps instead of returning the stale pool
    model_path: str = ""
    context_length: int = 0
    # where the load's wall time went (set-up is a cost every cold start
    # pays): weights = read/build to device, engines = placement + state
    # allocation, warmup = AOT compiles behind the readiness gate
    setup_seconds: Dict[str, float] = field(default_factory=dict)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def touch(self) -> None:
        self.last_used = int(time.time())
        self.request_count += 1

    def submit(self, req, tenant: str = "anonymous", deadline_s=None):
        """Serving entry point: through the pool (admission + routing)
        when present, straight to the batcher otherwise. Raises
        serving.AdmissionError on shed."""
        pool = self.pool
        if pool is not None:
            return pool.submit(req, tenant=tenant, deadline_s=deadline_s)
        return self.batcher.submit(req)


def _context_for_file_size(n_bytes: int) -> int:
    """Context length by GGUF file size, as the reference's auto-loader
    chooses ctx/threads (runtime/src/main.rs:86-98)."""
    gb = n_bytes / 1e9
    if gb > 8:
        return 8192
    if gb > 2:
        return 4096
    return 2048


def _plan_from_env():
    """Build a sharding plan from AIOS_TPU_MESH ("dp=2,sp=2,tp=2"; missing
    axes default to 1) — how a multi-chip deployment's boot config selects
    its mesh (the [models] mesh knob -> serving_env()). Returns None when
    unset or when every axis is 1. A spec that cannot be honoured —
    malformed, or needing more devices than are visible — raises: a
    deployment sized for four chips must not come up on one."""
    spec = os.environ.get("AIOS_TPU_MESH", "").strip().lower()
    if not spec:
        return None
    axes = {"dp": 1, "sp": 1, "ep": 1, "tp": 1}
    try:
        for part in spec.split(","):
            k, _, v = part.strip().partition("=")
            if k not in axes:
                raise ValueError(f"unknown mesh axis {k!r}")
            axes[k] = int(v)
            if axes[k] < 1:
                raise ValueError(f"axis {k} must be >= 1")
    except ValueError as exc:
        raise ValueError(f"AIOS_TPU_MESH={spec!r} is malformed: {exc}") from exc
    n = axes["dp"] * axes["sp"] * axes["ep"] * axes["tp"]
    if n == 1:
        return None
    from ..parallel.sharding import ShardingPlan, build_mesh

    if len(jax.devices()) < n:
        raise ValueError(
            f"AIOS_TPU_MESH={spec!r} needs {n} devices, found "
            f"{len(jax.devices())}"
        )
    return ShardingPlan(build_mesh(
        n, dp=axes["dp"], sp=axes["sp"], ep=axes["ep"], tp=axes["tp"]
    ))


def _chip_hbm_bytes() -> Optional[float]:
    """Per-device HBM capacity: AIOS_TPU_HBM_GB override, else the TPU's
    reported limit. None on an intended CPU run (host RAM is not budgeted);
    a TPU that reports no limit raises rather than being assumed a v5e."""
    env = os.environ.get("AIOS_TPU_HBM_GB", "")
    if env:
        try:
            return float(env) * 1e9
        except ValueError:
            log.warning("AIOS_TPU_HBM_GB=%r ignored (not a number)", env)
    if not backend.on_tpu():
        return None
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        raise RuntimeError(
            f"{dev.device_kind} reports no HBM bytes_limit; set "
            "AIOS_TPU_HBM_GB to its per-chip capacity"
        )
    return float(stats["bytes_limit"])


class ModelManager:
    """Registry of co-resident TPU models sharing the chip's HBM."""

    def __init__(
        self,
        num_slots: int = 8,
        sharding_plan=None,
        warm_compile: bool = True,
        quantize: Union[bool, str, None] = None,  # None=auto, bool, "int8"/"int4"
    ) -> None:
        self.models: Dict[str, ManagedModel] = {}
        self.autoload_failures: Dict[str, str] = {}
        self.num_slots = num_slots
        if sharding_plan is None:
            sharding_plan = _plan_from_env()
        self.plan = sharding_plan
        self.warm_compile = warm_compile
        # int8 serving weights: the default on single-chip TPU (the reference
        # serves Q4 GGUF through llama.cpp, so int8 is *more* precise than
        # its default); AIOS_TPU_QUANTIZE=0 forces bf16 serving. Intended CPU
        # runs (tests, smokes) keep dense weights — without the TPU int8 dot
        # they would re-dequantize every matmul.
        # explicit = the operator chose a mode (param or env); auto-derived
        # defaults must not argue with a prepared checkpoint's stored mode.
        # Derived as "did not fall through to the auto branch" so the
        # recognized-value list exists in exactly one place (the chain).
        self.quantize_explicit = quantize is not None
        if quantize is None:
            self.quantize_explicit = True
            env = os.environ.get("AIOS_TPU_QUANTIZE", "").lower()
            if env in ("0", "false", "off"):
                quantize = False
            elif env in ("1", "true", "int8"):
                quantize = "int8"
            elif env == "int4":
                # group-wise packed-nibble int4 (ops/int4_matmul.py): half
                # the int8 weight bytes, Q4-class precision like the
                # reference's GGUF serving format
                quantize = "int4"
            else:
                self.quantize_explicit = False  # fell through to auto
                if env:
                    log.warning(
                        "unrecognized AIOS_TPU_QUANTIZE=%r (expected 0/1/"
                        "int8/int4); using the auto default", env,
                    )
                # default: int8 on single-chip TPU; sharded serving keeps
                # the conservative bf16 default until measured on a real
                # mesh — but an EXPLICIT AIOS_TPU_QUANTIZE=1 is honored
                # either way (the engine shards the unfused int8 layout)
                quantize = (
                    "int8" if sharding_plan is None and backend.on_tpu()
                    else False
                )
        elif quantize is True:
            quantize = "int8"
        self.quantize = quantize or False
        # AIOS_TPU_KV_CACHE=int8 halves KV-cache footprint/traffic (the
        # long-context + co-residency lever); default bf16. Composes with a
        # sharding plan: cache + scales shard by the plan's cache rules and
        # the dequantizing attention partitions under GSPMD.
        kv_env = os.environ.get("AIOS_TPU_KV_CACHE", "").lower()
        self.cache_dtype = jnp.bfloat16
        if kv_env == "int8":
            self.cache_dtype = jnp.int8
        elif kv_env and kv_env not in ("bf16", "bfloat16"):
            log.warning(
                "unrecognized AIOS_TPU_KV_CACHE=%r (expected 'int8'); "
                "using bf16",
                kv_env,
            )
        # AIOS_TPU_PAGED_KV serves every model over a paged KV cache
        # (engine/paged.py): slots x context becomes a logical limit, HBM
        # is spent per page in use, and prompt-prefix pages are SHARED
        # across requests (paged.PrefixIndex) — the lever that takes the
        # 8 agents' common preambles off the prefill path entirely.
        #   <rows>  — fixed physical pool of that many rows
        #   auto    — size per model at load: (num_slots + 1) x context
        #             rows, i.e. the dense cache's HBM plus one slot's
        #             worth of slack so prefix pages can outlive their
        #             originating request without starving admissions.
        #             The production boot config defaults to auto
        #             (boot/config.py [models] paged_kv_rows).
        #   0/off   — dense slot cache.
        # Composes with tp and dp plans (dp partitions the pool per
        # replica); sp-sharded contexts use AIOS_TPU_SEQ_SHARD_KV instead.
        self.paged_pool_rows: Optional[Union[int, str]] = None
        paged_env = os.environ.get("AIOS_TPU_PAGED_KV", "").lower()
        if paged_env in ("auto",):
            self.paged_pool_rows = "auto"
        elif paged_env not in ("", "0", "off", "false"):
            try:
                rows = int(paged_env)
            except ValueError:
                rows = 0
            if rows > 0:
                self.paged_pool_rows = rows
            else:
                log.warning(
                    "AIOS_TPU_PAGED_KV=%r ignored (expected a positive "
                    "row count, 'auto', or 0/off)", paged_env,
                )
        # AIOS_TPU_PREFIX_HOST_BYTES gives the prefix cache a host-RAM
        # spill tier (engine/paged.py HostPageStore): evicted prefix
        # pages' KV copies device->host inside this byte budget and
        # restores with a device_put + scatter on a later hash-chain hit
        # — a memcpy instead of a prefill recompute. Unset defers to
        # ModelConfig.prefix_host_bytes (0 = off); 0 forces it off.
        self.prefix_host_bytes: Optional[int] = None
        host_env = os.environ.get("AIOS_TPU_PREFIX_HOST_BYTES", "")
        if host_env:
            try:
                v = int(float(host_env))
                if v < 0:
                    raise ValueError("must be >= 0")
                self.prefix_host_bytes = v
            except ValueError:
                log.warning(
                    "AIOS_TPU_PREFIX_HOST_BYTES=%r ignored (expected a "
                    "non-negative byte count)", host_env,
                )
        # AIOS_TPU_HOST_RESTORE_MIN_PAGES floors the restore path: a
        # host-tier chain shorter than this many pages prefills normally
        # (device_put of a short prefix can lose to recompute). Default 1.
        self.host_restore_min_pages: Optional[int] = None
        floor_env = os.environ.get("AIOS_TPU_HOST_RESTORE_MIN_PAGES", "")
        if floor_env:
            try:
                v = int(float(floor_env))
                if v < 1:
                    raise ValueError("must be >= 1")
                self.host_restore_min_pages = v
            except ValueError:
                log.warning(
                    "AIOS_TPU_HOST_RESTORE_MIN_PAGES=%r ignored (expected "
                    "an integer >= 1)", floor_env,
                )
        # sp > 1 in the mesh no longer disables paging wholesale: the pool
        # replicates over sp, and the per-model HBM-budget check at load
        # time degrades only the models that actually need their context
        # sharded (seq_sharded_cache) — see the auto-degrade branch in
        # _build_engine's config resolution below.
        # AIOS_TPU_SPECULATIVE=1 turns on n-gram speculative decode
        # dispatches (engine/spec.py): greedy agent requests — tool-call
        # JSON, quoted context — emit several tokens per verify round with
        # identical output. Off by default until measured per deployment.
        self.speculative = os.environ.get(
            "AIOS_TPU_SPECULATIVE", ""
        ).lower() in ("1", "true", "on")
        # AIOS_TPU_SEQ_SHARD_KV=1 shards every model's KV context axis over
        # the mesh's sp axis (long-context serving: one slot's cache spans
        # chips); needs a sharding plan with sp > 1
        self.seq_shard_kv = sharding_plan is not None and os.environ.get(
            "AIOS_TPU_SEQ_SHARD_KV", ""
        ).lower() in ("1", "true", "on")
        self._lock = make_lock("model_manager")

    @staticmethod
    def _kv_row_bytes(cfg, cache_dtype) -> float:
        """Bytes one KV row (both k and v, all layers) occupies."""
        item = 1 if cache_dtype == jnp.int8 else 2
        return cfg.row_layers * sum(cfg.kv_row_dims) * item

    def _kv_bytes_per_chip(self, cfg, ctx, cache_dtype, kw) -> float:
        """Estimated per-chip HBM the KV cache will pin under the current
        plan: slots shard over dp and kv heads over tp; the paged pool's
        rows split across dp replicas. sp does NOT divide the estimate
        unless the cache is seq-sharded — which is exactly what the
        auto-degrade check decides."""
        row = self._kv_row_bytes(cfg, cache_dtype)
        dp = tp = 1
        if self.plan is not None:
            dp, tp = self.plan.dp, self.plan.tp
        rows = kw.get("paged_pool_rows") or self.num_slots * ctx
        if cfg.state_kinds:
            # the state kind (engine/paged.py header): a fixed size a slot
            # beside the cache rows of the layers that have rows
            from ..engine.paged import SlotStates

            states = SlotStates.of(cfg, self.num_slots)
            return row * rows + states.stats()["kv_state_bytes"]
        if cfg.kinds and kw.get("paged_pool_rows"):
            # pages by kind (engine/paged.py header): the full layers hold
            # the pool's rows, the window layers what the same share of
            # slots holds of a window
            from ..engine.engine import window_rows_a_slot

            per_layer = row / cfg.num_layers
            window = sum(k == "window" for k in cfg.layer_types)
            held = -(-rows // ctx) * window_rows_a_slot(
                cfg, kw.get("page_size", 128), ctx
            )
            return per_layer * (
                (cfg.num_layers - window) * rows + window * held
            )
        return row * rows / (dp * tp)

    # -- loading ------------------------------------------------------------

    def _replica_plans(self, n: int) -> List:
        """One sharding plan per replica. With enough devices each replica
        gets its OWN submesh slice (n disjoint dp x sp x ep x tp meshes);
        otherwise every replica shares the manager's plan/devices — the
        CPU-test and oversubscribed layout."""
        if n <= 1 or self.plan is None:
            return [self.plan] * n
        plan = self.plan
        size = plan.dp * plan.sp * plan.ep * plan.tp
        devs = jax.devices()
        if len(devs) < n * size:
            log.info(
                "%d replicas share one %d-device mesh (%d devices visible)",
                n, size, len(devs),
            )
            return [plan] * n
        from ..parallel.sharding import ShardingPlan, build_mesh

        return [
            ShardingPlan(build_mesh(
                devices=devs[i * size:(i + 1) * size],
                dp=plan.dp, sp=plan.sp, ep=plan.ep, tp=plan.tp,
            ))
            for i in range(n)
        ]

    def _replica_devices(self, n: int, shared: bool) -> List:
        """The device each replica commits to when there is no plan:
        replica i gets device i once enough devices are visible (four
        one-chip replicas on a four-chip host), so params, cache and page
        pool follow it instead of piling onto device 0. ``[None] * n``
        (everything on the default device) under a plan — submeshes place
        those — for a single replica, on an oversubscribed host, and when
        the replicas ``shared`` one device-resident draft model."""
        devs = jax.local_devices()
        if self.plan is not None or shared or n <= 1 or len(devs) < n:
            return [None] * n
        return devs[:n]

    def load_model(
        self,
        name: str,
        path: str = "",
        context_length: int = 0,
    ) -> ManagedModel:
        with self._lock:
            existing = self.models.get(name)
        if existing is not None and existing.state == STATE_READY:
            want_replicas = ServingConfig.from_env(
                existing.config.replicas
            ).replicas
            have_replicas = (
                len(existing.pool.replicas) if existing.pool is not None else 1
            )
            if (
                existing.model_path == path
                and existing.context_length == (context_length or 0)
                and have_replicas == want_replicas
            ):
                return existing
            # different source/geometry/replica count: fall through and
            # HOT-SWAP — the new pool is built first, swapped into the
            # registry, and the old one drains in the background so
            # in-flight streams finish on the engines they started on
            log.info(
                "%s: reload with changed config; hot-swapping the pool",
                name,
            )

        t0 = time.time()
        # the model's set-up, named (flightrec.SETUP_PHASES): replica 0's
        # phases hold load.model and what is done once a model, each
        # replica's its own engine and warm-up, and pool.stats() sums them
        phases = [flightrec.Phases()]
        flightrec.compile_cache()  # counts from here: the weights' compiles too
        whole = phases[0].begin("load.model")
        try:
            with phases[0].phase("load.weights"):
                cfg, params, tokenizer = self._load_weights(
                    name, path, context_length
                )
                # the ring of the model's lane and its requests' timelines
                phases[0].named(cfg.name)
                jax.block_until_ready(params)  # weight builds dispatch async
            serving_cfg = ServingConfig.from_env(
                cfg.replicas,
                draft_model_default=getattr(cfg, "draft_model", ""),
            )
            n_replicas = max(1, serving_cfg.replicas)
            plans = self._replica_plans(n_replicas)
            # replicas on DISJOINT submeshes cost 1x per chip (each chip
            # hosts one replica); replicas sharing a device set multiply
            # the per-chip footprint — both the budget check below and the
            # recorded hbm_chip_bytes must use the same factor
            devices = self._replica_devices(
                n_replicas, shared=bool(serving_cfg.draft_model)
            )
            repl_factor = n_replicas
            if n_replicas > 1 and (
                devices[0] is not None
                or (self.plan is not None and plans[0] is not self.plan)
            ):
                repl_factor = 1
            cache_dtype = self.cache_dtype
            ctx = context_length or cfg.max_context
            # Draft-model speculation (ModelConfig.draft_model /
            # AIOS_TPU_DRAFT_MODEL / boot [models] draft_model): load the
            # paired small model ONCE — its int4 params are shared
            # read-only by every replica engine, each of which keeps its
            # own slot-aligned draft KV state. Built BEFORE the HBM
            # budget math below so the draft's weights + dense KV cache
            # count against the per-chip budget like any co-resident
            # footprint. A paired draft implies speculative serving for
            # this model even when the global AIOS_TPU_SPECULATIVE knob
            # is off (the draft exists for nothing else); the proposer
            # ladder still carries the n-gram fallback.
            draft = None
            spec_on = self.speculative
            draft_bytes = 0.0
            if serving_cfg.draft_model:
                draft = self._build_draft(
                    serving_cfg.draft_model, cfg, ctx, tokenizer
                )
                if draft is not None:
                    spec_on = True
                    # weights are device-shared across replica engines
                    # (one DraftModel object); the dense draft KV is
                    # allocated PER ENGINE, and a draft only survives
                    # with plan=None, where replicas share the device
                    # set — so the KV term pays repl_factor times
                    draft_bytes = (
                        draft.weight_bytes()
                        + self._kv_row_bytes(draft.cfg, jnp.bfloat16)
                        * self.num_slots * ctx * repl_factor
                    )
            from ..engine.engine import (
                refuse_for_latent_pool,
                refuse_for_state_kind,
                refuse_for_two_kinds,
            )

            refuse_for_state_kind(
                cfg, speculative_decoding_and_its_rollback=spec_on
            )
            refuse_for_latent_pool(
                cfg, speculative_decoding_with_verify_step_paged=spec_on
            )
            refuse_for_two_kinds(
                cfg, speculative_decoding_and_its_rollback=spec_on
            )
            kw = {}
            pool_rows = self.paged_pool_rows
            if pool_rows == "auto":
                # dense-cache HBM + one slot of slack (prefix retention)
                pool_rows = (self.num_slots + 1) * ctx
            if pool_rows is not None:
                # page size must divide the context; 128 aligns with the
                # kernel block and every power-of-two bucket >= 128. An
                # indivisible context degrades to the dense cache (like
                # every other invalid paged config) instead of failing load.
                # AIOS_TPU_PREFIX_CACHE=0 disables prompt-prefix page
                # sharing (on by default with the paged cache)
                prefix = os.environ.get(
                    "AIOS_TPU_PREFIX_CACHE", "1"
                ).lower() not in ("0", "false", "off")
                # host spill tier: env wins over the model config (the
                # convention everywhere); both resolve HERE so the
                # HealthCheck host-tier occupancy keys and the engine
                # agree on whether the tier exists
                host_bytes = self.prefix_host_bytes
                if host_bytes is None:
                    host_bytes = cfg.prefix_host_bytes
                tier_kw = dict(
                    prefix_host_bytes=host_bytes,
                    host_restore_min_pages=self.host_restore_min_pages,
                )
                if ctx % 128 == 0:
                    kw = dict(
                        paged_pool_rows=pool_rows, page_size=128,
                        prefix_cache=prefix, **tier_kw,
                    )
                elif ctx % 16 == 0 and cache_dtype != jnp.int8:
                    # the int8 paged kernel needs 128-aligned pages
                    # (_paged_call guard) — resolve that conflict HERE,
                    # at the same altitude as the sibling config
                    # conflicts, not as a load-time kernel ValueError
                    kw = dict(
                        paged_pool_rows=pool_rows, page_size=16,
                        prefix_cache=prefix, **tier_kw,
                    )
                else:
                    log.warning(
                        "AIOS_TPU_PAGED_KV ignored for %s: context %d "
                        "needs a multiple of %d; serving dense", name, ctx,
                        128 if cache_dtype == jnp.int8 else 16,
                    )
            if self.seq_shard_kv:
                if self.plan is not None and self.plan.sp > 1 \
                        and ctx % self.plan.sp == 0:
                    if kw:
                        # the operator explicitly asked for the sp-sharded
                        # cache; it and the paged pool are exclusive, so
                        # the explicit force wins over the paging default
                        log.info(
                            "%s: AIOS_TPU_SEQ_SHARD_KV drops the paged "
                            "pool (exclusive with the sp-sharded cache)",
                            name,
                        )
                    kw = dict(seq_sharded_cache=True)
                else:
                    log.warning(
                        "AIOS_TPU_SEQ_SHARD_KV ignored for %s: needs "
                        "sp > 1 dividing context %d", name, ctx,
                    )
            # Per-chip HBM footprint estimate (recorded on the managed
            # model so later co-resident loads can budget against it).
            # Prepared trees are already in serving precision; dense trees
            # shrink when the engine quantizes them later.
            from ..engine.engine import _is_prequantized

            factor = 1.0 if _is_prequantized(params) else {
                "int8": 0.5, "int4": 0.25,
            }.get(self.quantize, 1.0)
            tp = self.plan.tp if self.plan is not None else 1
            weight_chip = model_mod.serving_weight_bytes(params) * factor / tp
            kv_chip = self._kv_bytes_per_chip(cfg, ctx, cache_dtype, kw)
            hbm_estimate = weight_chip + kv_chip
            chip_hbm = _chip_hbm_bytes()
            if chip_hbm is not None and not kw.get("seq_sharded_cache"):
                # Long-context auto-degradation (the graceful path a boot
                # config with sp > 1 selects without any extra knob): when
                # this model's KV cache cannot fit the per-chip HBM budget
                # even paged, shard the context axis over sp instead —
                # giving up paging/prefix sharing (pages hold contiguous
                # rows and cannot split across sp shards) but keeping the
                # model servable. Estimates carry a 15% headroom;
                # co-resident models' footprints count against the budget.
                # Without a usable sp axis the shortfall is still WARNED so
                # the first symptom isn't a serve-time OOM.
                # co-resident models count against the budget — INCLUDING
                # a still-READY same-name entry: during a hot-swap the old
                # pool keeps serving (and pinning HBM) while the new one
                # builds, so the transient is 2x, not a replacement
                resident = sum(
                    mm.hbm_chip_bytes for mm in self.models.values()
                    if mm.name != name or mm.state == STATE_READY
                )
                budget = (
                    chip_hbm * 0.85
                    - weight_chip * repl_factor - resident - draft_bytes
                )
                sp = self.plan.sp if self.plan is not None else 1
                if kv_chip * repl_factor > max(budget, 0.0):
                    # the seq-sharded config is a DENSE num_slots x ctx
                    # cache sharded over dp x tp x sp — recompute its
                    # estimate rather than dividing the PAGED estimate by
                    # sp (the paged pool may hold more rows than the dense
                    # cache, which overstated the degraded footprint and
                    # could degrade onto a layout that saves nothing)
                    dp = self.plan.dp if self.plan is not None else 1
                    tp = self.plan.tp if self.plan is not None else 1
                    seq_kv = (
                        self._kv_row_bytes(cfg, cache_dtype)
                        * self.num_slots * ctx / (dp * tp * sp)
                    )
                    if sp > 1 and ctx % sp == 0 and seq_kv < kv_chip:
                        log.warning(
                            "%s: KV cache needs ~%.1f GB/chip (budget "
                            "~%.1f GB after weights + co-resident "
                            "models); sharding the context axis over "
                            "sp=%d (~%.1f GB/chip%s) and dropping the "
                            "paged pool",
                            name, kv_chip * repl_factor / 1e9,
                            max(budget, 0.0) / 1e9,
                            sp, seq_kv * repl_factor / 1e9,
                            "" if seq_kv * repl_factor <= max(budget, 0.0)
                            else ", STILL over budget — HBM may overflow",
                        )
                        kw = dict(seq_sharded_cache=True)
                        hbm_estimate = weight_chip + seq_kv
                    else:
                        if sp <= 1:
                            why = "no sp axis in the mesh"
                        elif ctx % sp:
                            why = f"context {ctx} does not divide by sp={sp}"
                        else:
                            why = (
                                f"the seq-sharded cache (~{seq_kv / 1e9:.1f}"
                                " GB/chip) would not shrink the footprint"
                            )
                        log.warning(
                            "%s: KV cache needs ~%.1f GB/chip (budget "
                            "~%.1f GB) and the seq-sharded degradation "
                            "is unavailable (%s) — loading anyway and "
                            "HBM may overflow",
                            name, kv_chip * repl_factor / 1e9,
                            max(budget, 0.0) / 1e9, why,
                        )
            quantize = self.quantize
            if not self.quantize_explicit:
                if quantize and _is_prequantized(params):
                    # auto-derived default meets a prepared checkpoint:
                    # serve the stored mode without a mismatch warning
                    quantize = None
            elif not quantize:
                from ..engine.engine import _prequantized_mode

                if _is_prequantized(params):
                    # the engine cannot distinguish explicit bf16 from
                    # the auto default; surface the ignored override HERE,
                    # where explicitness is known
                    log.warning(
                        "explicit bf16 request (quantize=False or "
                        "AIOS_TPU_QUANTIZE=0) for %s ignored: checkpoint "
                        "stores prepared %s serving weights (re-run "
                        "prepare_model without --quantize for bf16 "
                        "serving)", name, _prequantized_mode(params),
                    )
            engines = []
            try:
                for i in range(n_replicas):
                    # the engine commits params and allocates its state on
                    # the ambient default device
                    scope = (
                        jax.default_device(devices[i])
                        if devices[i] is not None
                        else contextlib.nullcontext()
                    )
                    if i:
                        phases.append(flightrec.Phases(cfg.name))
                    ph = phases[i]
                    with scope:
                        with ph.phase("load.engine"):
                            engine = TPUEngine(
                                cfg,
                                params,
                                num_slots=self.num_slots,
                                max_context=ctx,
                                shardings=plans[i],
                                quantize=quantize,
                                cache_dtype=cache_dtype,
                                # the per-step history scatter serves only
                                # the speculative proposers — skip it (and
                                # its serial scan dependency) when
                                # speculative serving is off
                                track_history=spec_on,
                                draft=draft,
                                phases=ph,
                                **kw,
                            )
                            engines.append(engine)
                            jax.block_until_ready(
                                (engine.params, engine.state)
                            )
                        if self.warm_compile:
                            # json-mode deployments dispatch the
                            # grammar-masked step; compile it behind the
                            # readiness gate too (AOT, no dispatch).
                            # Speculative round graphs are covered when
                            # the pool's batchers attach below —
                            # ContinuousBatcher AOT-compiles its ACTUAL
                            # chunk sizes, still before STATE_READY
                            from .service import json_mode_forced

                            with ph.phase("load.warmup"):
                                engine.warmup(
                                    masked_step=json_mode_forced()
                                )
            except BaseException:
                # a failed replica build must not strand its siblings'
                # HBM until a gc pass
                for e in engines:
                    try:
                        e.close()
                    except Exception:  # noqa: BLE001
                        pass
                raise
            del params
            # long-context tier (docs/ENGINE_PERF.md): surface what the
            # engines armed — the knobs resolve env-over-config inside
            # the engine, so the load log is where an operator sees the
            # effective policy
            if getattr(engines[0], "kv_compress_armed", False):
                log.info(
                    "%s: window+sink KV compression armed (threshold %d "
                    "rows; %d sink + %d window pages/slot)", name,
                    engines[0].kv_compress_after,
                    engines[0].kv_sink_pages, engines[0].kv_window_pages,
                )
            if getattr(engines[0], "seq_prefill_min", 0):
                log.info(
                    "%s: sequence-sharded prefill armed (prompts >= %d "
                    "rows spread over sp=%d)", name,
                    engines[0].seq_prefill_min,
                    self.plan.sp if self.plan is not None else 1,
                )

            def batcher_factory(eng, _tok=tokenizer, _spec=spec_on):
                # the pool's spawn AND crash-respawn path — a replica
                # whose scheduler died gets an identical fresh batcher
                # (the proposer ladder re-resolves from eng.draft, so a
                # respawned replica keeps its draft rung)
                return ContinuousBatcher(
                    eng, speculative=_spec, tokenizer=_tok
                )

            try:
                with phases[0].phase("load.attach"):
                    pool = ReplicaPool(
                        name, engines, batcher_factory, serving_cfg
                    )
            except BaseException:
                # the pool shuts its partial batchers down itself; the
                # engines are still ours to free
                for e in engines:
                    try:
                        e.close()
                    except Exception:  # noqa: BLE001
                        pass
                raise
            managed = ManagedModel(
                name=name,
                config=cfg,
                engine=engines[0],
                batcher=pool.replicas[0].batcher,
                tokenizer=tokenizer,
                state=STATE_READY,
                loaded_at=int(time.time()),
                # every replica pins its own weights + KV; co-resident
                # replicas (shared device set) multiply the per-chip
                # footprint, disjoint submeshes pay 1x per chip.
                # draft_bytes already carries its own replica factor
                # (shared weights x1, per-engine KV x repl_factor)
                hbm_chip_bytes=hbm_estimate * repl_factor + draft_bytes,
                pool=pool,
                model_path=path,
                context_length=context_length or 0,
                # the three keys the benchmark's set-up line reads, from
                # the spans' own seconds
                setup_seconds={
                    key: round(sum(p.seconds[span] for p in phases), 2)
                    for key, span in (("weights", "load.weights"),
                                      ("engines", "load.engine"),
                                      ("warmup", "load.warmup"))
                },
            )
            # keep the replica-0 snapshot fresh across crash-respawns
            # (the pool swaps Replica.batcher; the ManagedModel field
            # would otherwise point at the dead scheduler)
            def _sync_batcher(idx, b, _m=managed):
                if idx == 0:
                    _m.batcher = b

            pool.on_respawn = _sync_batcher
            # SLO autoscaling closed loop (AIOS_TPU_AUTOSCALE, docs/
            # RUNBOOK.md §8): a per-pool controller scales replicas off
            # the windowed burn rate and walks the degrade ladder at the
            # ceiling. The engine factory clones replica 0's geometry
            # over its LIVE (already device-resident, possibly
            # prequantized) param tree, so a scale-up shares weight
            # buffers instead of re-reading the checkpoint; scale-up
            # replicas ride the manager's shared mesh (disjoint-submesh
            # growth would need devices the plan already claimed).
            from ..serving.autoscale import (
                AutoscaleController, enabled as autoscale_enabled,
            )

            if autoscale_enabled():
                def engine_factory(
                    _cfg=cfg, _ctx=ctx, _cache=cache_dtype, _kw=kw,
                    _spec=spec_on, _draft=draft, _pool=pool,
                    _warm=self.warm_compile, _plan=self.plan,
                    _slots=self.num_slots,
                ):
                    from .service import json_mode_forced

                    e0 = _pool.replicas[0].engine
                    eng = TPUEngine(
                        _cfg, e0.params, num_slots=_slots,
                        max_context=_ctx, shardings=_plan,
                        quantize=None, cache_dtype=_cache,
                        track_history=_spec, draft=_draft, **_kw,
                    )
                    if _warm:
                        eng.warmup(masked_step=json_mode_forced())
                    return eng

                AutoscaleController(
                    pool, engine_factory=engine_factory, start=True,
                )
                log.info(
                    "%s: SLO autoscaler attached (ceiling %d replicas)",
                    name, pool.autoscaler.cfg.max_replicas,
                )
            with self._lock:
                old = self.models.get(name)
                self.models[name] = managed
            if old is not None and old is not managed \
                    and old.state == STATE_READY:
                self._retire_async(old)
            log.info(
                "model %s ready in %.1fs (ctx=%d, %d slots, %d replica%s)",
                name,
                time.time() - t0,
                engines[0].max_context,
                engines[0].num_slots,
                n_replicas,
                "" if n_replicas == 1 else "s",
            )
            return managed
        except Exception as exc:
            # a FAILED hot-swap must not clobber the still-serving model:
            # keep the READY entry (its pool keeps serving; the caller
            # still sees the load error) and only register the error
            # placeholder when there was nothing working to preserve
            with self._lock:
                cur = self.models.get(name)
                if cur is None or cur.state != STATE_READY:
                    self.models[name] = ManagedModel(
                        name=name,
                        config=TINY_TEST,
                        engine=None,  # type: ignore[arg-type]
                        batcher=None,  # type: ignore[arg-type]
                        tokenizer=ByteTokenizer(),
                        state=STATE_ERROR,
                        error=str(exc),
                    )
            if cur is not None and cur.state == STATE_READY:
                log.error(
                    "model %s reload failed (%s); the previous pool keeps "
                    "serving", name, exc,
                )
            else:
                log.error("model %s failed to load: %s", name, exc)
            raise
        finally:
            # a failed load's seconds count too, and no trace annotation
            # stays entered
            phases[0].end(whole)

    def _build_draft(self, source: str, cfg: ModelConfig, ctx: int,
                     tokenizer: BaseTokenizer):
        """Resolve the paired draft model (a preset name like
        "tinyllama" or a weights path) into an int4 spec.DraftModel, or
        None when this deployment cannot carry one. Lenient like every
        other serving knob: a bad pairing logs and falls back to n-gram
        speculation instead of taking down the model load."""
        from ..engine import spec as spec_mod

        if self.plan is not None:
            log.warning(
                "%s: draft-model speculation is single-device only "
                "(no shard_map twins for the draft graphs); serving "
                "with n-gram speculation under AIOS_TPU_MESH", cfg.name,
            )
            return None
        try:
            p = Path(source)
            if source.endswith(".gguf") or "/" in source or p.exists():
                dcfg, dparams, dtok = self._load_weights(
                    p.stem.lower() or "draft", source, 0, draft=True
                )
            else:
                dcfg, dparams, dtok = self._load_weights(
                    source, "", 0, draft=True
                )
        except Exception as exc:  # noqa: BLE001 - lenient knob pattern
            log.warning(
                "%s: draft model %r failed to load (%s); serving with "
                "n-gram speculation", cfg.name, source, exc,
            )
            return None
        if dcfg.vocab_size != cfg.vocab_size:
            log.warning(
                "%s: draft model %s vocab (%d) does not match the "
                "serving vocab (%d) — they must share one tokenizer; "
                "serving with n-gram speculation",
                cfg.name, dcfg.name, dcfg.vocab_size, cfg.vocab_size,
            )
            return None
        # matching vocab SIZES do not imply the same tokenizer (32000 is
        # every Llama-family size): a mismatched pairing would propose
        # garbage ids with ~0 acceptance, and with the default
        # spec_min_accept=0 the ladder would never fall back — a silent
        # permanent throughput regression. Probe-encode through both.
        try:
            probe = 'The quick brown fox ran 42 {"tool": "call"}'
            if dtok.encode(probe) != tokenizer.encode(probe):
                log.warning(
                    "%s: draft model %s tokenizes differently (same "
                    "vocab size, different tokenizer) — draft proposals "
                    "would be garbage ids; serving with n-gram "
                    "speculation", cfg.name, dcfg.name,
                )
                return None
        except Exception as exc:  # noqa: BLE001 - lenient knob pattern
            log.warning(
                "%s: draft tokenizer probe failed (%s); pairing on "
                "vocab size alone", cfg.name, exc,
            )
        draft = spec_mod.DraftModel(dcfg, dparams, quantize="int4")
        log.info(
            "%s: paired draft model %s (%.0f MB serving weights, "
            "ctx %d)", cfg.name, dcfg.name,
            draft.weight_bytes() / 1e6, ctx,
        )
        return draft

    def _synthetic_params(self, cfg: ModelConfig, quantize, plan):
        """Seeded random weights built in the layout they will be SERVED
        in: quantized modes go straight to the int8/int4 serving leaves
        (fused on one chip, unfused and tp-eligible under a plan) and every
        leaf under a plan is generated already sharded — so neither one
        chip nor device 0 of a mesh ever holds a dense 7B tree plus its
        fp32 transients."""
        key = jax.random.PRNGKey(0)
        shardings = plan.sharding_for if plan is not None else None
        if quantize:
            return model_mod.init_quantized_params(
                cfg, key, fuse=plan is None, mode=quantize,
                tp=plan.tp if plan is not None else 1, shardings=shardings,
            )
        return model_mod.init_params(
            cfg, key, dtype=jnp.bfloat16, shardings=shardings
        )

    def _load_weights(self, name: str, path: str, context_length: int,
                      draft: bool = False):
        """Resolve (config, params, tokenizer) from a model source.
        ``draft`` — the source is a paired draft model: single-device,
        int4 (spec.DraftModel's serving mode)."""
        if path.startswith("synthetic://") or not path:
            preset_name = path.removeprefix("synthetic://") or name
            cfg = self._resolve_preset(preset_name)
            if draft:
                params = self._synthetic_params(cfg, "int4", None)
            else:
                params = self._synthetic_params(cfg, self.quantize, self.plan)
            return cfg, params, ByteTokenizer()

        p = Path(path)
        if p.is_file() and p.suffix == ".gguf":
            dtype = jnp.bfloat16
            params, cfg = weights_mod.params_from_gguf(str(p))
            params = weights_mod.map_params(params, lambda a: a.astype(dtype))
            f = gguf_mod.GGUFFile(p)
            tokenizer: BaseTokenizer
            if "tokenizer.ggml.tokens" in f.metadata:
                tokenizer = gguf_tokenizer(f.metadata)
            else:
                tokenizer = ByteTokenizer()
            if context_length:
                cfg = cfg.scaled(max_context=context_length)
            return cfg, params, tokenizer

        if p.is_dir():
            from ..engine import checkpoint as ckpt_mod

            if ckpt_mod.is_model_checkpoint(str(p)):
                # prepared aios-tpu checkpoint: params restore straight to
                # device, no GGUF parse/dequant on the serving path
                # host-stage only when a quantize pass may follow; plain
                # bf16 serving restores straight to the accelerator
                cfg, params, tokenizer = ckpt_mod.load_model_checkpoint(
                    str(p), host_stage=bool(self.quantize)
                )
                if context_length:
                    cfg = cfg.scaled(max_context=context_length)
                return cfg, params, tokenizer

            # HF checkpoint directory
            import json

            import safetensors.numpy

            with open(p / "config.json") as fh:
                hf_cfg = json.load(fh)
            from ..engine.config import from_hf_config

            cfg = from_hf_config(hf_cfg, name=name)
            sd = {}
            for st_file in sorted(p.glob("*.safetensors")):
                sd.update(safetensors.numpy.load_file(st_file))
            params = weights_mod.params_from_hf_state_dict(sd, cfg)
            params = weights_mod.map_params(params, lambda a: a.astype(jnp.bfloat16))
            return cfg, params, HFTokenizer(str(p))

        raise FileNotFoundError(f"model path not found: {path}")

    @staticmethod
    def _resolve_preset(name: str) -> ModelConfig:
        low = name.lower()
        if low in ("tiny-test", "tiny"):
            return TINY_TEST
        if low == "tiny-moe":
            return TINY_MOE
        if low in PRESETS:  # exact name wins before any fuzzy match
            return PRESETS[low]
        for key, cfg in PRESETS.items():
            if low in key or key in low or key.split("-")[0] in low:
                return cfg
        raise KeyError(f"no preset matches {name!r}")

    def autoload(self, model_dir: Optional[str] = None) -> List[str]:
        """Scan AIOS_MODEL_DIR for *.gguf and load each (main.rs:65-132).
        A model that fails to load is logged with its traceback and
        recorded in ``autoload_failures`` (name -> error); the caller
        decides whether a boot with failures may continue."""
        model_dir = model_dir or os.environ.get(
            "AIOS_MODEL_DIR", "/var/lib/aios/models"
        )
        loaded = []
        d = Path(model_dir)
        if not d.is_dir():
            return loaded
        for f in sorted(d.glob("*.gguf")):
            name = f.stem.lower()
            ctx = _context_for_file_size(f.stat().st_size)
            try:
                self.load_model(name, str(f), context_length=ctx)
                loaded.append(name)
            except Exception as exc:  # noqa: BLE001 - next file still loads
                log.exception("autoload of %s failed", f)
                self.autoload_failures[name] = repr(exc)
        return loaded

    # -- unloading ----------------------------------------------------------

    def unload_model(self, name: str) -> bool:
        with self._lock:
            managed = self.models.pop(name, None)
        if managed is None:
            return False
        managed.state = STATE_UNLOADING
        # the pool shuts every replica down (batcher + engine.close() —
        # close frees HBM deterministically; the jitted-step closures form
        # a ref cycle with the engine, so plain deref would leave the
        # weights resident until a gc pass)
        if managed.pool is not None:
            managed.pool.shutdown()
        else:
            if managed.batcher is not None:
                managed.batcher.shutdown()
            if managed.engine is not None:
                managed.engine.close()
        managed.engine = None  # type: ignore[assignment]
        managed.batcher = None  # type: ignore[assignment]
        return True

    def _retire_async(self, old: ManagedModel) -> None:
        """Hot-swap retirement: the replacement pool is already in the
        registry serving new requests; the OLD pool drains its in-flight
        streams in the background, then frees its HBM. The swapped-out
        ManagedModel keeps its pool reference until the drain thread is
        done with it, but its engine/batcher snapshots null immediately
        (HealthCheck must not read a closing engine)."""
        old.state = STATE_UNLOADING
        pool, batcher, engine = old.pool, old.batcher, old.engine
        old.engine = None  # type: ignore[assignment]
        old.batcher = None  # type: ignore[assignment]

        def _drain():
            if pool is not None:
                pool.shutdown(drain_timeout=30.0)
            else:
                if batcher is not None:
                    batcher.shutdown()
                if engine is not None:
                    engine.close()

        threading.Thread(
            target=_drain, name=f"retire-{old.name}", daemon=True
        ).start()

    # -- resolution ---------------------------------------------------------

    def get(self, name: str) -> Optional[ManagedModel]:
        return self.models.get(name)

    def ready_models(self) -> List[ManagedModel]:
        return [m for m in self.models.values() if m.state == STATE_READY]

    def find_by_partial_name(self, name: str) -> Optional[ManagedModel]:
        """Case-insensitive substring match (model_manager.rs:506-518)."""
        low = name.lower()
        exact = self.models.get(name)
        if exact is not None and exact.state == STATE_READY:
            return exact
        for m in self.ready_models():
            if low in m.name.lower() or m.name.lower() in low:
                return m
        return None

    def select_for_level(self, level: str) -> Optional[ManagedModel]:
        """Routing ladder; None for reactive or when nothing matches."""
        ladder = LEVEL_LADDERS.get(level.lower())
        if not ladder:
            return None
        for candidate in ladder:
            m = self.find_by_partial_name(candidate)
            if m is not None:
                return m
        return None
