"""aios.runtime.AIRuntime gRPC service over the TPU engine.

Reference parity (runtime/src/grpc_service.rs):
  * resolution order for Infer: explicit model name -> intelligence-level
    ladder -> any ready model -> UNAVAILABLE (grpc_service.rs:187-233);
  * reactive level is rejected with INVALID_ARGUMENT ("heuristics, no model",
    grpc_service.rs:208-211); strategic with no big model ready returns
    FAILED_PRECONDITION "route via api-gateway" (grpc_service.rs:213-216);
  * defaults: max_tokens 512, temperature 0.7 (inference.rs:103-112).

Improvement over the reference: StreamInfer is genuinely token-by-token (the
reference buffers the whole SSE body before chunking, inference.rs:257-353 —
a quirk SURVEY.md says to fix consciously). Chunks carry incremental
detokenized text; the final chunk has done=true and empty text.
"""

from __future__ import annotations

import logging
import os
import sys
import time
from typing import Iterator, Optional

import grpc
import jax

from .. import backend, rpc
from ..fleet import disagg as fleet_disagg
from ..fleet import drain as fleet_drain
from ..fleet import gprefix as fleet_gprefix
from ..obs import fleet, flightrec, instruments as obs, slo, tracing
from ..obs.http import maybe_start_metrics_server
from ..proto_gen import common_pb2, runtime_pb2
from ..services import KVTRANSFER, RUNTIME, AIRuntimeServicer, service_address
from ..engine.batching import Request
from ..engine.tokenizer import render_chat
from ..serving import AdmissionError, tenant_of
from .model_manager import (
    STATE_READY,
    ManagedModel,
    ModelManager,
)

log = logging.getLogger("aios.runtime")

DEFAULT_MAX_TOKENS = 512
DEFAULT_TEMPERATURE = 0.7
DEFAULT_TOP_P = 0.95


def json_mode_forced() -> bool:
    """AIOS_TPU_JSON_MODE=force: every non-streaming Infer is grammar-
    constrained to one JSON object (the reference's response_format
    behavior, inference.rs:114-122). Single accepted-value set shared by
    the per-request check and the model manager's warmup gate."""
    return os.environ.get("AIOS_TPU_JSON_MODE", "").lower() in (
        "force", "1", "on",
    )


class RuntimeService(AIRuntimeServicer):
    def __init__(self, manager: Optional[ModelManager] = None):
        self.manager = manager or ModelManager()
        self.started_at = time.time()
        # weakref: the process-global gauge must not pin a discarded
        # manager (and its loaded engines' HBM/caches) for process life
        import weakref

        ref = weakref.ref(self.manager)
        obs.RUNTIME_MODELS_READY.set_function(
            lambda: (lambda m: float(len(m.ready_models())) if m is not None
                     else 0.0)(ref())
        )

    # -- lifecycle RPCs -----------------------------------------------------

    def LoadModel(self, request, context):
        try:
            m = self.manager.load_model(
                request.model_name,
                request.model_path,
                context_length=request.context_length,
            )
        except Exception as exc:  # noqa: BLE001
            context.set_code(grpc.StatusCode.INTERNAL)
            context.set_details(f"load failed: {exc}")
            return runtime_pb2.ModelStatus(
                model_name=request.model_name, status="error"
            )
        return self._status_of(m)

    def UnloadModel(self, request, context):
        ok = self.manager.unload_model(request.model_name)
        return common_pb2.Status(
            success=ok,
            message="unloaded" if ok else f"model {request.model_name} not loaded",
        )

    def ListModels(self, request, context):
        return runtime_pb2.ModelList(
            models=[self._status_of(m) for m in self.manager.models.values()]
        )

    def HealthCheck(self, request, context):
        # list(): Load/Unload RPCs mutate the dict on other gRPC threads
        models = list(self.manager.models.values())
        details = {m.name: m.state for m in models}
        details["backend"] = "jax-tpu"
        # the device as JAX reports it: this is the one process that holds
        # the chip, so the tools service's hw.info reads it from here
        devices = jax.devices()
        details["platform"] = devices[0].platform
        details["device_kind"] = devices[0].device_kind
        # "; "-joined: a TPU device's own string carries commas
        details["devices"] = "; ".join(str(d) for d in devices)
        # per-model serving counters (spec acceptance, KV page usage,
        # prefix-cache hits, evictions) — additive observability the
        # reference's llama-server health probe has no equivalent for
        for m in models:
            # snapshot: a concurrent UnloadModel nulls these fields on the
            # same ManagedModel object mid-iteration
            pool, engine, batcher = m.pool, m.engine, m.batcher
            if pool is not None and engine is not None:
                # pool.stats() is the pool-level engine.stats(): counters
                # summed across replicas + routing/shed/occupancy detail
                stats = pool.stats()
            elif engine is not None and batcher is not None:
                stats = engine.stats()
                stats["pool_evictions"] = batcher.pool_evictions
                stats["completed"] = batcher.completed
                stats["cancelled"] = batcher.cancellations
                stats["waiting"] = batcher.queue_depth()
                stats["num_slots"] = engine.num_slots
            else:
                continue
            details[f"{m.name}.serving"] = ",".join(
                f"{k}={v}" for k, v in sorted(stats.items())
            )
        # SLO view (obs/slo.py): per-objective windowed attainment, with
        # breached objectives flagged — the gRPC twin of the /healthz
        # degradation signal
        for name in slo.ENGINE.models():
            ev = slo.ENGINE.evaluate(name)
            details[f"{name}.slo"] = ",".join(
                f"{o}={v['attainment']:.4f}"
                + ("!breach" if v["breached"] else "")
                for o, v in sorted(ev.items())
            )
        ready = len(self.manager.ready_models())
        return common_pb2.HealthStatus(
            healthy=True,
            service="runtime",
            message=f"{ready} model(s) ready",
            uptime_seconds=int(time.time() - self.started_at),
            details=details,
        )

    # -- inference RPCs -----------------------------------------------------

    def Infer(self, request, context):
        t0 = time.time()
        m = self._resolve_model(request, context)
        if m is None:
            return runtime_pb2.InferResponse()
        handle, n_prompt = self._submit(m, request, context=context)
        # decode span: child of the interceptor's RPC server span (same
        # handler thread), the leaf of the goal->task->agent->RPC->decode
        # hierarchy
        with tracing.start_span(
            "runtime.decode", model=m.name, rpc="Infer"
        ) as span:
            token_ids = [t for t in handle if t != m.tokenizer.eos_id]
            span.set_attribute("tokens", len(token_ids))
        obs.RUNTIME_INFER_LATENCY.labels(model=m.name, rpc="Infer").observe(
            time.time() - t0
        )
        if handle.aborted:
            # mid-request abort (model unload, scheduler failure): the
            # collected tokens are a truncation — error out, don't present
            # them as a completion. RETRYABLE causes (a crashed replica
            # whose failover budget was exhausted) additionally carry a
            # retry-after-ms hint, the admission-shed convention, so
            # compliant clients back off and resubmit instead of treating
            # the crash as permanent.
            retry_ms = getattr(handle, "retry_after_ms", 0)
            if retry_ms:
                context.set_trailing_metadata(
                    (("retry-after-ms", str(retry_ms)),)
                )
            context.abort(
                grpc.StatusCode.UNAVAILABLE,
                f"request aborted: {handle.abort_reason}",
            )
        text = m.tokenizer.decode(token_ids)
        latency_ms = int((time.time() - t0) * 1000)
        return runtime_pb2.InferResponse(
            text=text,
            tokens_used=n_prompt + len(token_ids),
            latency_ms=latency_ms,
            model_used=m.name,
        )

    def StreamInfer(self, request, context) -> Iterator[runtime_pb2.InferChunk]:
        t0 = time.time()
        m = self._resolve_model(request, context)
        if m is None:
            return
        handle, _ = self._submit(
            m, request, streaming=True, context=context
        )
        chunk_counter = obs.RUNTIME_STREAM_CHUNKS.labels(model=m.name)
        emitted = ""
        ids = []
        try:
            with tracing.start_span(
                "runtime.decode", model=m.name, rpc="StreamInfer"
            ) as span:
                for tok in handle:
                    if tok == m.tokenizer.eos_id:
                        break
                    ids.append(tok)
                    # incremental detokenization: emit the stable text delta
                    text = m.tokenizer.decode(ids)
                    if text.startswith(emitted):
                        delta = text[len(emitted) :]
                    else:  # rare resegmentation: resend from scratch marker
                        delta = text
                    if delta:
                        emitted = text
                        chunk_counter.inc()
                        yield runtime_pb2.InferChunk(text=delta, done=False)
                span.set_attribute("tokens", len(ids))
            obs.RUNTIME_INFER_LATENCY.labels(
                model=m.name, rpc="StreamInfer"
            ).observe(time.time() - t0)
            if handle.aborted:
                # an error status instead of a done-chunk: the client
                # must not mistake a mid-stream abort for a short
                # completion. RETRYABLE causes (crashed replica, failover
                # budget spent) surface UNAVAILABLE + retry-after-ms so
                # the client resubmits — the re-prefill is a prefix-cache
                # hit; deliberate aborts (unload) stay ABORTED.
                retry_ms = getattr(handle, "retry_after_ms", 0)
                if retry_ms:
                    context.set_trailing_metadata(
                        (("retry-after-ms", str(retry_ms)),)
                    )
                    context.set_code(grpc.StatusCode.UNAVAILABLE)
                else:
                    context.set_code(grpc.StatusCode.ABORTED)
                context.set_details(
                    f"stream aborted: {handle.abort_reason}"
                )
                return
            yield runtime_pb2.InferChunk(text="", done=True)
        finally:
            # a cancelled/disconnected client closes this generator at its
            # yield point (GeneratorExit) — abort the engine request NOW
            # rather than waiting for the termination callback, so the slot
            # and KV pages free within one scheduler tick (llama-server
            # parity: decode stops when the HTTP client goes away). No-op
            # on normal completion.
            handle.cancel()

    # -- helpers ------------------------------------------------------------

    def _submit(self, m: ManagedModel, request, streaming: bool = False,
                context=None):
        m.touch()
        prompt_text = render_chat(
            m.config.name, request.prompt, request.system_prompt
        )
        prompt_ids = m.tokenizer.encode(prompt_text)
        stop = (m.tokenizer.eos_id,) if m.tokenizer.eos_id is not None else ()
        # TPU extension field: grammar-guided structured output (the schema
        # subset of engine/jsonschema.py); malformed input is the caller's
        # error, surfaced as INVALID_ARGUMENT
        schema = None
        raw_schema = getattr(request, "json_schema", "")
        if raw_schema:
            import json as _json

            try:
                schema = _json.loads(raw_schema)
                if not isinstance(schema, dict):
                    raise ValueError("schema must be a JSON object")
            except ValueError as e:
                if context is not None:
                    context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        f"invalid json_schema: {e}",
                    )
                raise
        # The reference forces response_format=json_object on every
        # NON-streaming local inference (inference.rs:114-122, enforced by
        # llama-server's grammar engine). The TPU equivalent is logit-mask
        # grammar decoding (engine/jsonmode.py). Conscious default: OFF —
        # the blanket force would garble plain-text think() flows that the
        # reference only gets away with because its prompts all demand
        # JSON; AIOS_TPU_JSON_MODE=force restores exact reference behavior.
        json_mode = (
            schema is None and not streaming and json_mode_forced()
        )
        req = Request(
            prompt_ids=prompt_ids,
            max_tokens=request.max_tokens or DEFAULT_MAX_TOKENS,
            temperature=(
                request.temperature
                if request.temperature > 0
                else DEFAULT_TEMPERATURE
            ),
            top_p=DEFAULT_TOP_P,
            stop_ids=stop,
            request_id=request.task_id or "",
            json_mode=json_mode,
            json_schema=schema,
            # admission priority from the request's intelligence level:
            # priority ranks LATENCY SENSITIVITY as much as intelligence —
            # under slot contention, strategic reasoning admits ahead of
            # bulk operational traffic, and a reactive request (a quick
            # latency-sensitive probe that explicitly named a model — the
            # ladder rejects model-less reactive calls) ranks with
            # operational rather than at the bottom with unclassified
            # traffic (FIFO within a level; no wire change — the level
            # field already rides InferRequest)
            priority={
                "strategic": 3, "tactical": 2, "operational": 1,
                "reactive": 1,
            }.get(request.intelligence_level.lower(), 0),
        )
        # serving front door: per-tenant quota (tenant = agent id / task
        # prefix, per the pool's AIOS_TPU_TENANT_BY policy), bounded
        # queues, deadline feasibility — the propagated gRPC deadline is
        # the request's budget
        tenant = tenant_of(
            request, m.pool.cfg.tenant_by if m.pool is not None else "agent"
        )
        # flight recorder: the timeline opens HERE — the first point that
        # knows model, tenant, AND the RPC's trace identity (the server
        # interceptor's span is current on this handler thread), so shed
        # decisions, route choice, and scheduler events all land on one
        # record correlated with the span tree by trace id
        span = tracing.current_span()
        req.rec = flightrec.RECORDER.begin(
            m.name, req.request_id, tenant,
            trace_id=span.trace_id if span is not None else "",
            prompt_tokens=len(prompt_ids), priority=req.priority,
        )
        deadline_s = None
        if context is not None:
            tr = context.time_remaining()
            if tr is not None and tr < 3600 * 24 * 365:
                deadline_s = tr
        try:
            try:
                # fleet data plane rung (fleet/disagg.py): exactly
                # m.submit when the plane is disarmed; on a prefill-role
                # host the returned handle hands the stream to a decode
                # host after the first token
                handle = fleet_disagg.route_submit(
                    m, req, tenant=tenant, deadline_s=deadline_s
                )
            except AdmissionError as e:
                # load shed: RESOURCE_EXHAUSTED + a retry-after-ms
                # trailing-metadata hint instead of an unbounded queue;
                # PERMANENT conditions (cost can never fit the bucket)
                # are INVALID_ARGUMENT so clients don't retry forever
                if context is not None:
                    if not e.retriable:
                        context.abort(
                            grpc.StatusCode.INVALID_ARGUMENT,
                            f"request not admittable ({e.cause}): {e}",
                        )
                    context.set_trailing_metadata(
                        (("retry-after-ms", str(e.retry_after_ms)),)
                    )
                    context.abort(
                        grpc.StatusCode.RESOURCE_EXHAUSTED,
                        f"request shed ({e.cause}): {e}",
                    )
                raise
            except RuntimeError as e:
                # submit raced UnloadModel's shutdown: the batcher refuses
                # (rather than stranding the consumer forever)
                if context is not None:
                    context.abort(
                        grpc.StatusCode.UNAVAILABLE,
                        f"model {m.name} is unloading: {e}",
                    )
                raise
            if context is not None:
                # llama-server parity (model_manager.rs spawns a server that
                # aborts decode when its HTTP client goes away): a gRPC
                # disconnect/cancel frees the request's slot and KV pages
                # instead of decoding to max_tokens for nobody. Fires on
                # normal termination too — cancel() is a no-op then.
                # add_callback returns False (never firing) when the RPC
                # already terminated — cancel straight away then, or the
                # submitted request would decode for a client that is gone.
                if not context.add_callback(handle.cancel):
                    handle.cancel()
            return handle, len(prompt_ids)
        except ValueError as e:
            # unsupported schema constructs / scalar roots fail fast
            if context is not None and schema is not None:
                context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"unsupported json_schema: {e}",
                )
            raise

    def _resolve_model(self, request, context) -> Optional[ManagedModel]:
        """explicit name -> level ladder -> any ready -> gRPC error."""
        if request.model:
            m = self.manager.find_by_partial_name(request.model)
            if m is not None:
                return m
            context.set_code(grpc.StatusCode.NOT_FOUND)
            context.set_details(f"model {request.model} not loaded")
            return None

        level = request.intelligence_level.lower()
        if level == "reactive":
            context.set_code(grpc.StatusCode.INVALID_ARGUMENT)
            context.set_details(
                "reactive tasks use heuristics, not model inference"
            )
            return None
        if level:
            m = self.manager.select_for_level(level)
            if m is not None:
                return m
            if level == "strategic":
                context.set_code(grpc.StatusCode.FAILED_PRECONDITION)
                context.set_details(
                    "no strategic-tier model loaded; route via api-gateway"
                )
                return None

        ready = self.manager.ready_models()
        if ready:
            return ready[0]
        context.set_code(grpc.StatusCode.UNAVAILABLE)
        context.set_details("no models loaded")
        return None

    def _status_of(self, m: ManagedModel) -> runtime_pb2.ModelStatus:
        return runtime_pb2.ModelStatus(
            model_name=m.name,
            status=m.state,
            port=0,  # no HTTP sidecar on TPU
            loaded_at=m.loaded_at,
            last_used=m.last_used,
            request_count=m.request_count,
        )


def serve(
    address: Optional[str] = None,
    manager: Optional[ModelManager] = None,
    block: bool = True,
    metrics_port: Optional[int] = None,
):
    """Start the runtime gRPC server (reference binds [::]:50055,
    runtime/src/main.rs:140). ``metrics_port`` (or
    AIOS_RUNTIME_METRICS_PORT) also starts the /metrics + /healthz
    endpoint; its server and bound port ride on the service object."""
    address = address or service_address("runtime")
    server = rpc.create_server()
    service = RuntimeService(manager)
    rpc.add_to_server(RUNTIME, service, server)
    # the fleet transfer plane (aios.fleet.KvTransfer) rides the SAME
    # server — registered unconditionally (answering Fetch/Push/Handoff
    # on a solo host is harmless) so arming the fleet later needs no
    # restart
    rpc.add_to_server(
        KVTRANSFER, fleet_disagg.DisaggService(service.manager), server
    )
    port = server.add_insecure_port(address)
    server.start()
    # pool stats ride every fleet heartbeat (obs/fleet.py): peers rank
    # hosts by live occupancy/degrade level without scraping each model.
    # Registered before the metrics server so the registry's very first
    # announce already carries them.
    fleet.add_stats_provider(lambda: {
        m.name: m.pool.heartbeat_stats()
        for m in service.manager.ready_models()
        if m.pool is not None
    })
    # fleet data plane: publish this process's transfer endpoint + prefix
    # digest on the heartbeat, and arm the disagg routing rung
    host = address.rsplit(":", 1)[0].strip("[]")
    reach = "127.0.0.1" if host in ("", "0.0.0.0", "::", "localhost") else host
    fleet.set_transfer_addr(f"{reach}:{port}")
    fleet.add_digest_provider(fleet_gprefix.provider(service.manager))
    # the routing rung arms only on a configured fleet (or an explicit
    # role): a solo host keeps the exact pre-fleet submit path
    if fleet.FleetConfig().active() or os.environ.get("AIOS_TPU_FLEET_ROLE"):
        fleet_disagg.arm(service.manager)
        # the graceful-drain coordinator (POST /fleet/drain) arms with
        # the data plane: a solo host has no fleet to drain toward
        fleet_drain.arm(service.manager)
    service.metrics_server, service.metrics_port = maybe_start_metrics_server(
        "runtime",
        metrics_port,
        # the SLO view rides the probe: any breached objective flips
        # status to "degraded", which obs/http.py maps to HTTP 503 — so
        # load balancers eject the replica instead of reading prose
        health_fn=lambda: slo.annotate_health({
            "status": "ok",
            "service": "runtime",
            "models_ready": len(service.manager.ready_models()),
        }),
    )
    log.info("AIRuntime listening on %s", address)
    if block:
        server.wait_for_termination()
    return server, service, port


def main() -> int:
    logging.basicConfig(level=logging.INFO)
    # the backend is decided before anything compiles: without
    # JAX_PLATFORMS=cpu a missing TPU stops the service here, not after a
    # 7B model has been loaded onto the host
    log.info("serving backend: %s", backend.decide())
    # multi-host deployments set AIOS_TPU_COORDINATOR (+NUM_PROCESSES,
    # +PROCESS_ID) so every host's runtime joins one process group and the
    # engines see the global mesh; single-host is a no-op
    from ..parallel import multihost

    multihost.initialize_from_env()
    manager = ModelManager()
    loaded = manager.autoload()
    if manager.autoload_failures and not loaded:
        log.error(
            "every model in AIOS_MODEL_DIR failed to load: %s",
            ", ".join(sorted(manager.autoload_failures)),
        )
        return 1
    serve(manager=manager)
    return 0


if __name__ == "__main__":
    sys.exit(main())
