"""Continuous batching: many concurrent requests over one decode graph.

The reference serializes requests per model into llama-server's HTTP queue
and caps concurrent AI work at 3 (autonomy.rs Semaphore(3), SURVEY.md
section 2.4); here the 8+ agents' requests land in ONE batched decode step —
the scheduler assigns each request a cache slot, prefills it, and every
decode dispatch advances all active slots together. Tokens stream to each
caller through a per-request queue as dispatches complete.

Scheduling policy (single background thread, dispatch-level granularity):
  * admit waiting requests whenever slots are free (prefill immediately);
  * decode in short dispatches of `chunk_steps` tokens, each issued
    before the last one's tokens are consumed (the pipelined loop): a
    finished slot and an arrival wait out a few steps, and the host's
    time per dispatch runs while the device works on the next;
  * requests retire on EOS/stop token, max_tokens, or a full cache slot.
"""

from __future__ import annotations

import itertools
import logging
import os
import queue
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.locks import make_lock
from .engine import (
    DECODE_STEPS, JUMP_BUCKETS, ChunkedPrefill, PendingDecode,
    PendingFirstToken, TPUEngine, _env_flag, refuse_for_state_kind,
)
from .paged import PoolExhausted
from .sampling import GREEDY_EPS
from .spec import SPEC_PROPOSERS
from .. import faults
from ..obs import instruments as obs
from ..obs import flightrec

log = logging.getLogger("aios.batcher")

_END = object()

# Live batchers per model name: replica batchers share the (model,) label
# on the aios_tpu_engine_dispatch_inflight_total gauge, and set_function
# is last-writer-wins — so the scrape callback sums over this set instead
# of reporting whichever replica registered last (the same aggregation
# pattern as engine._HOST_STORES_BY_MODEL). Dead batchers drop out when
# collected; a shut-down batcher reports 0 (its pending is dropped).
_BATCHERS_BY_MODEL: Dict[str, object] = {}

# Queued requests gain +1 effective priority per this many seconds
# waiting, bounding starvation under sustained higher-priority traffic
# (a priority-0 request outranks a fresh strategic (3) after ~15 s).
PRIORITY_AGING_SECS = 5.0

# How long an EWMA-collapse keeps a proposer suspended before probe
# dispatches re-measure (the workload may have turned repetitive again).
# Default only — AIOS_TPU_SPEC_REPROBE_SECS / ModelConfig.spec_reprobe_secs
# / boot [models] spec_reprobe_secs override per deployment.
SPEC_REPROBE_SECS = 10.0

# EWMA smoothing for the per-dispatch draft-acceptance ratio.
SPEC_EWMA_ALPHA = 0.3

# Probe dispatches granted after a reprobe window expires: their ratios
# accumulate into a fresh cumulative average and the floor only re-judges
# once the budget is consumed — one unlucky probe dispatch (a single
# non-repetitive request in an otherwise healthy stream) can no longer
# re-disable speculation instantly on a zeroed EWMA. Deliberately NOT
# applied to a cold-started batcher: shutting speculation off fast on
# first evidence is the long-standing (and tested) cold-start behavior,
# and a wrong first verdict there costs one reprobe window, not a flap
# cycle.
SPEC_PROBE_DISPATCHES = 3

# retry-after hint for a retryable crash abort that reached the client
# (the pool's failover budget was exhausted, or there was no pool)
DEFAULT_RETRY_AFTER_MS = 1000

# A dispatch is a stall when it takes more than this many times the
# running median of its step count: the median over the last
# STALL_MEDIAN_OVER dispatches of that size, judged once it holds
# STALL_MEDIAN_MIN of them.
STALL_DISPATCH_FACTOR = 2.0
STALL_MEDIAN_OVER = 32
STALL_MEDIAN_MIN = 8

# A request the batcher holds (waiting, prefilling or live) that has not
# moved for this many times the last decode dispatch's duration, and for
# at least NO_PROGRESS_MIN_SECS (a tiny model's dispatch is milliseconds,
# and a queue behind full slots legitimately stands for seconds), leaves
# one recorder snapshot with cause "no_progress". Looked at once every
# NO_PROGRESS_CHECK_SECS.
# A chunk's operands are placed on the device a tick ahead (behind the
# hand-over, ChunkedPrefill.stage) where the scheduler's last wait for a
# dispatch's tokens, the room it has a tick, was under this. An unstaged
# issue is 8-11 ms of placements on the scheduler's thread and 5 ms of
# enqueue on the worker's, between the read of one dispatch's tokens and
# the enqueue of the next but one: with less room than that the device
# waits for them (a 14 ms dispatch: 12 ms of room, 11 % of the device
# idle), with more they are hidden already, and the placements' work
# right after an emission is then where it has always been for the
# streams' delivery (a 22-24 ms dispatch: PERF.md, PR 41)
STAGE_UNDER_SLACK_S = 0.018

NO_PROGRESS_DISPATCHES = 64
NO_PROGRESS_MIN_SECS = 10.0
NO_PROGRESS_CHECK_SECS = 1.0


@dataclass
class Request:
    prompt_ids: List[int]
    max_tokens: int = 256
    temperature: float = 0.7
    top_p: float = 0.95
    stop_ids: Tuple[int, ...] = ()
    request_id: str = ""
    # grammar-constrained decoding: output restricted to one JSON object
    # (the reference's non-streaming response_format=json_object behavior,
    # inference.rs:114-122, realized with logit masks instead of GBNF)
    json_mode: bool = False
    # structured outputs: output restricted to the exact SHAPE of this
    # schema (engine/jsonschema.py subset — known/required keys, enums,
    # typed scalars, nested/any subtrees). Wins over json_mode when both
    # are set (it is the stricter guarantee).
    json_schema: Optional[dict] = None
    # admission priority: higher admits first when slots are contended
    # (FIFO within a priority level — no wire field; the runtime derives
    # it from the request's intelligence level so strategic reasoning
    # doesn't queue behind bulk operational traffic)
    priority: int = 0
    # flight-recorder timeline (obs/flightrec.py) riding the request
    # through admission -> routing -> scheduling; opened by the runtime
    # service (with tenant + trace context), the pool, or the batcher —
    # whoever sees the request first. None when recording is disabled.
    rec: object = field(default=None, repr=False, compare=False)
    # transparent-failover controller (serving/failover.py), set by the
    # pool: when this request dies with a retryable abort, the controller
    # claims the terminal event and resumes the stream on a surviving
    # replica instead of surfacing a truncation. None = no failover.
    failover: object = field(default=None, repr=False, compare=False)
    # the prompt's chain hashes (paged.PromptHashes), computed once on the
    # thread that submits: by the pool, which routes on them, else by
    # ContinuousBatcher.submit. The engine's prefix match takes them where
    # they are its own truncation's (engine.prompt_hashes) and then does
    # not hash under its lock. None: not computed, or nothing reads them.
    prefix_hashes: object = field(default=None, repr=False, compare=False)


@dataclass
class _PendingFirst:
    """An admission whose last prefill program is issued and whose first
    token is not read yet: the engine's handle, and what its ``prefill``
    event says that was known at issue. The event is recorded at the
    read, where the engine time of the admission ends."""

    live: "_Live"
    first: PendingFirstToken
    t0: float  # the engine call of the admission's last program began
    fields: dict  # tokens, cached / restored rows, chunk, pages by kind
    slot_len: int  # the slot's rows as admitted, before any decode step


@dataclass
class _Live:
    req: Request
    slot: int
    produced: int = 0
    out_q: "queue.Queue" = field(default_factory=queue.Queue)
    first_token_at: float = 0.0
    submitted_at: float = 0.0
    done: bool = False
    admitted_at: float = 0.0  # first slot assignment (queue-wait boundary)
    cancelled: bool = False  # set by RequestHandle.cancel(); reaped by _tick
    # non-empty when the request was ABORTED (scheduler failure, model
    # unload) rather than finished/cancelled — consumers must not present
    # the truncated output as a normal completion
    abort_reason: str = ""
    constraint: object = None  # jsonmode.JsonConstraint when json_mode
    # instant of the last progress: submitted, admitted, a prefill chunk,
    # a dispatch that emitted for it (one stamp per dispatch, not a token)
    progress_at: float = 0.0
    slot_free: bool = False  # a slot stood free when it was submitted
    stuck_noted: bool = False  # its no_progress record was taken


@dataclass
class _PendingTick:
    """One pipelined decode dispatch in flight: the engine's pending
    handle plus the live map snapshotted AT DISPATCH TIME — its tokens
    belong to the requests that were live then (requests retired since
    have ``done`` set and their columns are dropped at consume).
    ``evs`` holds the flight-recorder event dicts recorded at submit so
    a devprof timing sample (known only when the worker finishes) can
    join them at consume time — all on the scheduler thread, and
    readers only copy FINISHED timelines, so the late join races
    nothing."""

    pending: PendingDecode
    lives: Dict[int, "_Live"]
    evs: tuple = ()
    key: object = None  # the running median it is judged against


class RequestHandle:
    """Caller-side view of an in-flight request (blocking token iterator)."""

    def __init__(self, live: _Live, batcher: "ContinuousBatcher"):
        self._live = live
        self._batcher = batcher

    def __iter__(self):
        while True:
            item = self._live.out_q.get()
            if item is _END:
                return
            yield item

    def tokens(self) -> List[int]:
        return list(self)

    def cancel(self) -> None:
        """Abort this request: its slot (and KV pages) free at the
        scheduler's next boundary and the token iterator ends. The llama.cpp
        parity point — llama-server aborts decode when the HTTP client
        disconnects — wired to gRPC disconnect by the runtime service.
        Idempotent; a no-op after completion."""
        self._live.cancelled = True
        self._batcher._wake.set()

    @property
    def aborted(self) -> bool:
        """True when the stream ended by ABORT (scheduler failure, model
        unload) — the collected tokens are a truncation, not a
        completion; serving layers map this to an error status."""
        return bool(self._live.abort_reason)

    @property
    def abort_reason(self) -> str:
        return self._live.abort_reason

    @property
    def retry_after_ms(self) -> int:
        """Backoff hint for a RETRYABLE abort (0 when not aborted, or
        when retrying cannot help — e.g. the prompt exceeds the pool).
        The runtime service forwards it as ``retry-after-ms`` trailing
        metadata, the same convention as admission sheds."""
        reason = self._live.abort_reason
        if not reason:
            return 0
        if flightrec.abort_cause(reason) in flightrec.RETRYABLE_ABORT_CAUSES:
            return DEFAULT_RETRY_AFTER_MS
        return 0

    @property
    def ttft_ms(self) -> float:
        if not self._live.first_token_at:
            return 0.0
        return (self._live.first_token_at - self._live.submitted_at) * 1000.0


class ContinuousBatcher:
    """Background scheduler marrying a request queue to engine slots."""

    def __init__(
        self,
        engine: TPUEngine,
        # steps a dispatch, and steps a dispatch while a request waits
        # for a slot: one size serves both (engine.DECODE_STEPS says how
        # it was chosen)
        chunk_steps: int = DECODE_STEPS,
        admit_chunk_steps: int = DECODE_STEPS,
        prefill_chunk: Optional[int] = None,  # None -> the engine's default
        speculative: bool = False,  # n-gram speculative decode dispatches
        spec_draft_len: int = 7,
        spec_ngram: int = 3,
        tokenizer=None,  # enables json_mode requests (mask table source)
        pipeline: Optional[bool] = None,  # depth-2 pipelined decode loop
        jump_ahead: Optional[bool] = None,  # grammar jump-ahead decoding
        spec_min_accept: Optional[float] = None,  # spec auto-disable floor
        spec_reprobe_secs: Optional[float] = None,  # reprobe window
    ) -> None:
        self.engine = engine
        # Pipelined decode, the default loop (AIOS_TPU_DECODE_PIPELINE=0
        # / ModelConfig.decode_pipeline=False is the way back to the
        # synchronous one): dispatch N+1 is enqueued BEFORE
        # dispatch N's tokens are consumed, so the host's emit/detokenize/
        # retire phase overlaps device execution instead of idling it — a
        # depth-2 double buffer over the plain decode path, with explicit
        # flushes at grammar-constrained ticks, pool-pressure evictions,
        # and idle boundaries (_flush_pending). Greedy token streams are
        # identical to the unpipelined loop (per-dispatch length
        # snapshots anchor out-of-cache retirement to the dispatch that
        # produced each token); sampled streams are identical for
        # batches admitted together (<= slots) — under queue pressure a
        # freed slot re-admits one dispatch later than the sync loop
        # would, shifting the shared key-split sequence.
        if pipeline is None:
            pipeline = _env_flag("AIOS_TPU_DECODE_PIPELINE")
        if pipeline is None:
            pipeline = bool(getattr(engine.cfg, "decode_pipeline", True))
        self.pipeline = bool(pipeline)
        self._pending: Optional[_PendingTick] = None
        self.flushes = 0
        # admissions of this tick whose first token is still on the device
        # (pipelined loop, no constraint), in the order they were admitted:
        # never kept across two ticks, and never while anything can end a
        # stream (_flush_pending reads them first). And how often that
        # happens: admissions, and those whose first token was read after
        # the decode dispatch behind them was issued
        self._firsts: List[_PendingFirst] = []
        self.admissions = 0
        self.admissions_read_after_dispatch = 0
        # retirements whose engine half (page frees, device resets, the
        # timeline's close, _END) is still to run: _finish frees the slot
        # on the host and queues the rest, _settle_retired runs it, at
        # once unless the pipelined tick holds it back until the dispatch
        # it has just handed over holds the engine lock (_decode_tick).
        # Never kept across two ticks. And how many ran behind a dispatch
        self._retired: List[_Live] = []
        self._hold_retired = False
        self.retirements_behind_dispatch = 0
        # the pipelined tick's last wait for a dispatch's tokens in a tick
        # that read no first token before it: the room the host has a
        # tick (STAGE_UNDER_SLACK_S). 0 until one is seen: stage
        self._slack_s = 0.0
        # host-gap accounting: wall time between consecutive decode
        # dispatches spent on the host (the device-idle window the
        # pipeline exists to close); bench_dispatch reads the totals
        self.decode_dispatches = 0
        self.host_gap_seconds = 0.0
        self._gap_mark: Optional[float] = None
        self._gap_wait = 0.0  # time blocked in consume-wait since the mark
        # the loop's phases (obs/flightrec.py PHASES), shared with the
        # engine's dispatch bodies; what _tick_done judges a stall from
        self.phases = engine.phases
        self.loop_stalls = 0
        self.loop_stall_seconds = 0.0
        # the size of the dispatch this tick completed (the key of the
        # running median it is judged against), None when it made none;
        # and the pipelined dispatch it consumed, issued a tick before
        self._dispatched: Optional[object] = None
        self._consumed: Optional[_PendingTick] = None
        # a prompt chunk was enqueued and not waited for: the next decode
        # dispatch has that chunk's device time in front of its own
        self._chunk_ahead = False
        self._last_dispatch_s = 0.0
        self._dispatch_hist: Dict[object, deque] = {}
        self._progress_checked = 0.0
        self._mask_base = None  # cached all-zeros [slots, vocab] device mask
        self.tokenizer = tokenizer
        self._json_masks = None  # lazy jsonmode.JsonMaskCache
        self._json_masks_lock = make_lock("json_masks")
        self._token_table = None  # shared token->bytes table
        self._byte_matrix = None  # shared (mat, lens) across mask caches
        from collections import OrderedDict

        self._schema_caches: "OrderedDict[str, object]" = OrderedDict()
        self.chunk_steps = chunk_steps
        self.admit_chunk_steps = admit_chunk_steps
        # Speculative dispatches (engine.spec_step) emit 1..draft_len+1
        # tokens per slot per round — greedy requests decode the identical
        # sequence in fewer dispatches (engine/spec.py); sampling requests
        # transparently take their usual one token per round.
        refuse_for_state_kind(
            engine.cfg, speculative_decoding_and_its_rollback=bool(speculative)
        )
        if speculative and not getattr(engine, "spec_supported", True):
            log.warning(
                "speculative decoding disabled: unsupported on this "
                "engine config (dp-replicated page pool)"
            )
            speculative = False
        self.speculative = speculative
        self.spec_draft_len = spec_draft_len
        self.spec_ngram = spec_ngram
        # Spec auto-disable (AIOS_TPU_SPEC_MIN_ACCEPT /
        # ModelConfig.spec_min_accept): when the EWMA draft-acceptance
        # ratio of this batcher's spec dispatches collapses below the
        # floor, speculation suspends — decode falls back to the
        # plain/pipelined path, whose per-dispatch cost the failed
        # drafts were inflating — and one probe dispatch re-measures
        # after SPEC_REPROBE_SECS. 0 = never auto-disable.
        if spec_min_accept is None:
            raw = os.environ.get("AIOS_TPU_SPEC_MIN_ACCEPT", "").strip()
            if raw:
                try:
                    spec_min_accept = float(raw)
                    if not 0.0 <= spec_min_accept <= 1.0:
                        raise ValueError("must be in [0, 1]")
                except ValueError as exc:
                    log.warning(
                        "AIOS_TPU_SPEC_MIN_ACCEPT=%r ignored (%s)", raw, exc
                    )
                    spec_min_accept = None
        if spec_min_accept is None:
            spec_min_accept = float(
                getattr(engine.cfg, "spec_min_accept", 0.0)
            )
        self.spec_min_accept = spec_min_accept
        # Reprobe window after an auto-disable (AIOS_TPU_SPEC_REPROBE_SECS
        # / ModelConfig.spec_reprobe_secs): how long a collapsed proposer
        # stays suspended before its probe dispatches re-measure.
        if spec_reprobe_secs is None:
            raw = os.environ.get("AIOS_TPU_SPEC_REPROBE_SECS", "").strip()
            if raw:
                try:
                    spec_reprobe_secs = float(raw)
                    if spec_reprobe_secs <= 0:
                        raise ValueError("must be > 0")
                except ValueError as exc:
                    log.warning(
                        "AIOS_TPU_SPEC_REPROBE_SECS=%r ignored (%s)",
                        raw, exc,
                    )
                    spec_reprobe_secs = None
        if spec_reprobe_secs is None:
            spec_reprobe_secs = float(
                getattr(engine.cfg, "spec_reprobe_secs", SPEC_REPROBE_SECS)
                or SPEC_REPROBE_SECS
            )
        self.spec_reprobe_secs = spec_reprobe_secs
        # Proposer ladder: draft-model speculation when the engine carries
        # a draft, prompt-lookup n-gram always (the floor of the ladder).
        # The constrained tick's FSM jump-ahead outranks both — it owns
        # the tick whenever a constrained slot has a forced run — so the
        # full preference order is jump-ahead -> draft -> ngram. Each
        # proposer keeps its OWN acceptance EWMA and suspension window,
        # so an auto-disable falls one rung (draft -> ngram -> off)
        # instead of turning speculation off all-or-nothing.
        self.spec_proposers: Tuple[str, ...] = (
            ("draft", "ngram") if engine.draft is not None else ("ngram",)
        )
        self.spec_ewma: Dict[str, Optional[float]] = {
            p: None for p in self.spec_proposers
        }
        self._spec_off_until: Dict[str, float] = {
            p: 0.0 for p in self.spec_proposers
        }
        # post-reprobe probe budget per proposer (SPEC_PROBE_DISPATCHES)
        self._spec_probe_left: Dict[str, int] = {
            p: 0 for p in self.spec_proposers
        }
        self._spec_probe_seen: Dict[str, int] = {
            p: 0 for p in self.spec_proposers
        }
        self.spec_autodisables = 0
        # Degrade switches (serving/autoscale.py ladder): the SLO-burn
        # controller flips these to shed OPTIONAL work under sustained
        # burn — speculation first (failed drafts inflate per-dispatch
        # cost), then grammar jump-ahead. Both paths are token-identical
        # on/off by construction, so a mid-stream flip never perturbs a
        # greedy stream; plain bool stores, safe to flip cross-thread.
        self.degrade_spec = False
        self.degrade_jump = False
        # Grammar jump-ahead (AIOS_TPU_JUMP_AHEAD /
        # ModelConfig.jump_ahead, default ON): chains of grammar-FORCED
        # tokens (singleton masks — schema key literals, ':', ',',
        # closers) emit host-side and append their KV in ONE multi-token
        # verify dispatch instead of one masked dispatch each. Greedy
        # streams are token-identical to the per-step path (forced
        # tokens of sampled streams too; the sampled remainder draws a
        # shifted key chain). Unsupported — like speculative verify — on
        # a dp-replicated page pool.
        if jump_ahead is None:
            jump_ahead = _env_flag("AIOS_TPU_JUMP_AHEAD")
        # asked for by argument or environment over a recurrent state:
        # refused by name (the default's ON falls to the masked step below)
        refuse_for_state_kind(
            engine.cfg,
            the_grammar_jump_ahead_and_its_verify_graph=bool(jump_ahead),
        )
        if jump_ahead is None:
            jump_ahead = bool(getattr(engine.cfg, "jump_ahead", True))
        self.jump_ahead = bool(jump_ahead) and getattr(
            engine, "spec_supported", True
        )
        self.jump_max = JUMP_BUCKETS[-1]
        # prompts longer than this admit incrementally (one cache-writing
        # chunk per scheduler pass) so a long admission never stalls decode
        # for the active slots; 0 disables. Defaults to the engine's
        # prefill_chunk_default — the same size warmup pre-compiles — and
        # falls back to monolithic prefill when the engine's bucket grid
        # can't honour the chunk size.
        self.prefill_chunk: Optional[int] = (
            engine.admission_chunk(prefill_chunk) or None
        )
        if prefill_chunk and self.prefill_chunk is None and (
            engine.pool_replicas > 1
        ):
            log.warning(
                "chunked admission disabled: unsupported on a "
                "dp-replicated page pool (whole-prompt prefill instead)"
            )
        # paged engines can run out of physical KV pages mid-stream; the
        # policy is to retire the LONGEST request (it has produced the most
        # and frees the most pages) and retry — counted for observability
        self.pool_evictions = 0
        self.cancellations = 0
        self._closed = False  # set by shutdown(); submit() refuses after
        self._waiting: "deque[_Live]" = deque()  #: guarded_by _qlock
        self._qlock = make_lock("batcher_queue")
        self._prefilling: Optional[Tuple[_Live, ChunkedPrefill]] = None
        self._prefill_chunks = 0  # chunks of the in-flight admission
        self._reserved_slot = -1  # slot mid-chunked-prefill (not yet active)
        self._live: Dict[int, _Live] = {}  #: guarded_by _lock
        self._wake = threading.Event()
        self._stop = False
        self._ids = itertools.count()
        self._lock = make_lock("batcher")
        self.completed = 0
        self.last_error: Optional[BaseException] = None
        # If the engine went through its warmup gate, make sure OUR dispatch
        # sizes are compiled too (warmup's defaults cover the default sizes;
        # a non-default chunk_steps would otherwise compile for seconds on
        # the scheduler thread at first dispatch, stalling live requests).
        # AOT — compile_step_fn lowers without dispatching, so attaching a
        # batcher never perturbs engine state. A never-warmed engine
        # (tests, lazy callers) is left lazy.
        if engine._step_fns:
            for n in {self.admit_chunk_steps, self.chunk_steps}:
                engine.compile_step_fn(n)
            if self.speculative:
                for n in {self.admit_chunk_steps, self.chunk_steps}:
                    engine.compile_spec_fn(
                        n, self.spec_draft_len, self.spec_ngram
                    )
                    # the draft proposer's fused graphs for the same round
                    # sizes (no-ops without a draft model), so the ladder
                    # never compiles mid-serving whichever rung serves
                    engine.compile_draft_spec_fn(n, self.spec_draft_len)
                if engine.draft is not None:
                    engine.compile_draft_ingest_fns()
            if self.jump_ahead and "masked" in engine._step_fns:
                # constrained serving was declared at warmup (the masked
                # graph is the same signal json-mode deployments use):
                # make sure every run-length bucket the constrained tick
                # can dispatch is compiled too (no-ops when warmup's
                # jump_sizes already covered them). Deployments that
                # never warmed the masked step keep the lazy behavior —
                # their first constrained request compiles both, visibly.
                for k in JUMP_BUCKETS:
                    engine.compile_jump_fn(k)
        # Metric children resolved ONCE (labels() is a locked dict lookup
        # — fine per request, too slow per decoded token); the queue-depth
        # gauge pulls live state at scrape time through a weakref so a
        # shut-down batcher can be collected.
        import weakref

        model_name = engine.cfg.name
        self._obs_tokens = obs.ENGINE_TOKENS.labels(model=model_name)
        self._obs_ttft = obs.ENGINE_TTFT.labels(model=model_name)
        self._obs_completed = obs.ENGINE_REQUESTS_COMPLETED.labels(
            model=model_name
        )
        self._obs_cancelled = obs.ENGINE_REQUESTS_CANCELLED.labels(
            model=model_name
        )
        self._obs_evictions = obs.ENGINE_POOL_EVICTIONS.labels(
            model=model_name
        )
        self._obs_tps = obs.ENGINE_TOKENS_PER_SECOND.labels(model=model_name)
        self._obs_gap = obs.ENGINE_DISPATCH_HOST_GAP.labels(model=model_name)
        _ref = weakref.ref(self)
        obs.ENGINE_QUEUE_DEPTH.labels(model=model_name).set_function(
            lambda: (lambda b: float(b.queue_depth()) if b is not None
                     else 0.0)(_ref())
        )
        peers = _BATCHERS_BY_MODEL.setdefault(model_name, weakref.WeakSet())
        peers.add(self)
        obs.ENGINE_DISPATCH_INFLIGHT.labels(model=model_name).set_function(
            lambda: float(sum(1 for b in peers if b._pending is not None))
        )

        def _acceptance(proposer):
            def read() -> float:
                vals = [
                    b.spec_ewma.get(proposer) for b in peers
                    if b.spec_ewma.get(proposer) is not None
                ]
                return float(sum(vals) / len(vals)) if vals else 0.0

            return read

        for p in SPEC_PROPOSERS:
            obs.SPEC_ACCEPTANCE.labels(
                model=model_name, proposer=p
            ).set_function(_acceptance(p))
        # tokens/sec gauge state: emitted tokens over a ~1 s window,
        # refreshed from the scheduler loop (decays to 0 when idle).
        # last_tps additionally keeps the most recent NON-ZERO rate so the
        # serving layer's deadline estimates survive idle gaps (the gauge
        # honestly decays to 0; feasibility math wants "how fast does this
        # replica decode when it decodes").
        self._rate_tokens = 0
        self._rate_t0 = time.monotonic()
        self.last_tps = 0.0
        # optional serving-layer hook: a Histogram child observed with the
        # submit->slot-assignment wait of each admitted request
        # (ReplicaPool sets it; None keeps the engine layer obs-free)
        self.queue_wait_obs = None
        self._thread = threading.Thread(
            target=self._run, name="continuous-batcher", daemon=True
        )
        self._thread.start()

    # -- public API ---------------------------------------------------------

    def _token_bytes(self):
        """Shared token->bytes table (built once; caller holds the lock)."""
        if self._token_table is None:
            from . import jsonmode

            if self.tokenizer is None:
                raise ValueError(
                    "json_mode/json_schema requires the batcher to know "
                    "the tokenizer"
                )
            self._token_table = jsonmode.token_bytes_table(
                self.tokenizer, self.engine.cfg.vocab_size
            )
        return self._token_table

    def _json_mask_cache(self):
        """Lazily build the per-model mask cache (one vocab walk; locked —
        concurrent first json_mode submits from the gRPC pool must share
        ONE cache, not each walk the vocab)."""
        with self._json_masks_lock:
            if self._json_masks is None:
                from . import jsonmode

                # compact=True: generation never emits structural
                # whitespace (canonical compact JSON, still valid), so
                # grammar-forced positions are SINGLETON states that
                # jump-ahead collapses into multi-token runs — and the
                # budget closing walk can't dither on whitespace
                self._json_masks = jsonmode.JsonMaskCache(
                    self._token_bytes(),
                    getattr(self.tokenizer, "eos_id", None),
                    compact=True,
                )
            return self._json_masks

    def _schema_mask_cache(self, schema: dict):
        """Per-(model, schema) mask cache; compiled once, shared by every
        request carrying the same schema (the autonomy loop resends its
        tool_calls schema on every reasoning round). LRU-bounded — the
        schema string is CLIENT input, and every cache pins per-state mask
        rows — with the vocab byte matrix built once and shared."""
        from . import jsonschema

        key = jsonschema.schema_cache_key(schema)
        with self._json_masks_lock:
            cache = self._schema_caches.get(key)
            if cache is not None:
                self._schema_caches.move_to_end(key)
                return cache
            table = self._token_bytes()
            if self._byte_matrix is None:
                base = self._json_masks
                if base is not None:
                    self._byte_matrix = (base._byte_mat, base._byte_lens)
            cache = jsonschema.SchemaMaskCache(
                table,
                getattr(self.tokenizer, "eos_id", None),
                schema,
                byte_matrix=self._byte_matrix,
                compact=True,  # same rationale as the json_mode cache
            )
            if self._byte_matrix is None:
                self._byte_matrix = (cache._byte_mat, cache._byte_lens)
            if cache.start_token_id is None:
                raise ValueError(
                    "json_schema root must be an object, array, or "
                    "any (scalar roots have no forced opener; wrap "
                    "them in an object)"
                )
            while len(self._schema_caches) >= 16:
                self._schema_caches.popitem(last=False)
            self._schema_caches[key] = cache
            return cache

    def queue_depth(self) -> int:
        """Requests waiting for a slot (admission backlog) — the slot-
        starvation signal the proactive generator watches."""
        with self._qlock:
            return len(self._waiting) + (
                1 if self._prefilling is not None else 0
            )

    def outstanding_tokens(self) -> int:
        """Work queued on this batcher, in tokens: waiting requests count
        prompt + budget (prefill is still ahead of them), live requests
        their remaining budget. Budgets are CAPPED at what the cache can
        actually hold — a max_tokens=50k request on an 8k context retires
        at the cache end, and counting the phantom 42k would make the
        serving layer's deadline estimates shed feasible requests. The
        router's least-loaded score and the admission layer's
        deadline-feasibility estimate both read this."""
        cap = self.engine.max_context
        with self._qlock:
            waiting = list(self._waiting)
            if self._prefilling is not None:
                waiting.append(self._prefilling[0])
        total = 0
        for l in waiting:
            # prompts truncate to the last cap-1 ids at admission — count
            # what will actually prefill, not the client's raw length —
            # and decode retires at the cache end, so the budget term is
            # bounded by the room left AFTER that prompt
            p = min(len(l.req.prompt_ids), cap - 1)
            total += p + max(min(l.req.max_tokens, cap - p), 0)
        with self._lock:
            total += sum(
                max(
                    min(
                        l.req.max_tokens - l.produced,
                        cap - self.engine.slot_length(l.slot),
                    ),
                    0,
                )
                for l in self._live.values()
            )
        return total

    def tokens_per_second(self) -> float:
        """Most recent non-zero observed decode rate (tokens/sec across
        all slots); 0.0 until the first measured window."""
        return self.last_tps

    def submit(self, req: Request) -> RequestHandle:
        if not req.prompt_ids:
            # fail fast on the caller's thread — an exception on the
            # scheduler thread would strand every waiter
            raise ValueError("empty prompt")
        if not req.request_id:
            req.request_id = f"req-{next(self._ids)}"
        if req.rec is None:
            # direct batcher callers (tests, bench) still get a timeline;
            # serving-path requests arrive with one already opened
            req.rec = flightrec.RECORDER.begin(
                self.engine.cfg.name, req.request_id,
                prompt_tokens=len(req.prompt_ids), priority=req.priority,
            )
        elif not req.rec.request_id:
            req.rec.request_id = req.request_id  # auto-assigned id above
        now = time.monotonic()
        live = _Live(req=req, slot=-1, submitted_at=now, progress_at=now)
        if req.json_schema is not None:
            from . import jsonmode

            # built on the CALLER's thread (fail fast + keep the vocab
            # walk / schema compile off the scheduler thread)
            cache = self._schema_mask_cache(req.json_schema)
            min_bytes = cache._distance(cache.start())
            max_tok_bytes = cache._byte_mat.shape[1]
            if req.max_tokens * max_tok_bytes < min_bytes:
                # even all-longest tokens cannot carry the schema's minimal
                # completion: the output could only truncate
                raise ValueError(
                    f"max_tokens={req.max_tokens} cannot fit the schema's "
                    f"minimal completion ({min_bytes} bytes)"
                )
            live.constraint = jsonmode.JsonConstraint(cache)
        elif req.json_mode:
            from . import jsonmode

            live.constraint = jsonmode.JsonConstraint(self._json_mask_cache())
        # hashed here, on the caller's thread, where the pool has not (and
        # again where its hashes are another truncation's): the admission's
        # prefix match then takes the engine lock without hashing under it
        req.prefix_hashes = self.engine.prompt_hashes(
            req.prompt_ids, req.prefix_hashes
        )
        with self._qlock:
            if self._closed:
                # shutdown() already drained the queue; an enqueue now
                # would never be scheduled NOR terminated — its consumer
                # would block forever (the UnloadModel/submit race)
                raise RuntimeError("batcher is shut down")
            # a slot nobody ahead in the queue will take: what wait
            # follows is for the loop to come round, not for capacity
            live.slot_free = len(self._free_slots()) > len(self._waiting)
            self._waiting.append(live)
        self._wake.set()
        return RequestHandle(live, self)

    def generate(self, prompt_ids: Sequence[int], **kw) -> List[int]:
        return self.submit(Request(prompt_ids=list(prompt_ids), **kw)).tokens()

    def shutdown(self) -> None:
        with self._qlock:
            self._closed = True  # new submits refuse from here on
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            # a long dispatch (large-model prefill) can hold _tick past
            # 10 s; the loop exits right after it sees _stop, so wait
            # more before touching shared state
            log.warning("batcher scheduler still in a dispatch; waiting")
            self._thread.join(timeout=60)
        if self._thread.is_alive():
            # a dispatch that never returns (a hung device): releasing slots
            # under a thread that may still write them risks use-after-
            # free — leave the state alone and surface the condition
            log.error(
                "batcher scheduler did not stop after 70s; outstanding "
                "requests are NOT terminated (hung dispatch?)"
            )
            return
        # the pushed throughput gauge would otherwise freeze at its last
        # measured rate (ghost tok/s for an unloaded model); zeroed AFTER
        # the join so a final in-flight _tick can't overwrite it. The pull
        # gauges (queue depth, occupancy) decay through their weakrefs.
        self._obs_tps.set(0.0)
        # terminate every outstanding request AFTER the scheduler stopped:
        # nothing will ever deliver their end-of-stream once the thread is
        # gone, so a consumer blocked in out_q.get() — e.g. a StreamInfer
        # handler whose model is being UnloadModel'ed mid-stream — would
        # hang forever
        self._terminate_outstanding("model unloading")

    @property
    def active_count(self) -> int:
        with self._lock:
            return len(self._live)

    # -- scheduler loop -----------------------------------------------------

    def _advance_prefill(self) -> None:
        """Run ONE chunk of the in-flight chunked prefill (if any); decode
        dispatches for the active slots happen between calls."""
        if self._prefilling is None:
            return
        live, pc = self._prefilling
        t0 = time.monotonic()
        pos0 = pc.pos
        reused0 = getattr(self.engine, "prefix_rows_reused", 0)
        restored0 = getattr(self.engine, "prefix_rows_restored", 0)
        while True:
            try:
                first = pc.step_async()
                break
            except PoolExhausted as e:
                # mid-admission exhaustion: free pages and retry the SAME
                # chunk NOW — deferring to the next tick would let _admit()
                # hand the freed pages to a new request and force another
                # eviction. With nobody left to evict the admission itself
                # is the victim (its partial pages release); when only
                # HIGHER-priority streams hold the pool, keep the partial
                # admission and retry next tick (they drain eventually).
                outcome = self._evict_longest(
                    e.replica, requester_priority=live.req.priority,
                    kind=e.kind,
                )
                if outcome == "blocked":
                    return
                if outcome == "empty":
                    self._prefilling = None
                    self._reserved_slot = -1
                    live.done = True
                    live.abort_reason = "evicted: KV pool exhausted"
                    self.engine.release(live.slot)
                    self._rec_close(live)
                    live.out_q.put(_END)
                    return
        self._prefill_chunks += 1
        live.progress_at = time.monotonic()
        # tokens = rows actually consumed this chunk (the FINAL chunk is
        # usually partial — recording the nominal chunk size would
        # overstate the prompt in every chunked timeline)
        fields = self._prefill_fields(
            live, pc.pos - pos0, reused0, restored0,
            chunk=self._prefill_chunks,
        )
        if first is None:
            self._chunk_ahead = True
            self._rec_prefill(live, t0, fields)
            return
        self._prefilling = None
        self._reserved_slot = -1
        self._admitted(live, first, t0, fields)

    def _free_slots(self) -> List[int]:
        """Slots a new tenant may take: free on the host, not reserved by
        the chunked admission, and with their last tenant's pages back."""
        held = {live.slot for live in self._retired}
        held.add(self._reserved_slot)
        return [s for s in self.engine.free_slots() if s not in held]

    def _admit(self) -> None:
        while True:
            free = self._free_slots()
            if not free:
                return
            with self._qlock:
                if not self._waiting:
                    return
                # highest EFFECTIVE priority admits first: queue age adds
                # +1 level per AGING_SECS, so sustained high-priority
                # traffic cannot starve a waiting request forever, and
                # within a level the continuous boost makes the oldest
                # strictly maximal (FIFO holds)
                now = time.monotonic()
                live = max(
                    self._waiting,
                    key=lambda l: l.req.priority
                    + (now - l.submitted_at) / PRIORITY_AGING_SECS,
                )
                self._waiting.remove(live)
            if not live.admitted_at:
                # first slot assignment ends the queue wait (requeues —
                # pool-exhaustion retries, chunked-admission turns — keep
                # their original boundary)
                live.admitted_at = live.progress_at = time.monotonic()
                if self.queue_wait_obs is not None:
                    self.queue_wait_obs.observe(
                        live.admitted_at - live.submitted_at
                    )
                rec = live.req.rec
                if rec is not None:
                    wait_ms = (
                        live.admitted_at - live.submitted_at
                    ) * 1000.0
                    rec.queue_wait_ms = wait_ms
                    rec.event("queue", wait_ms=round(wait_ms, 3),
                              slot_free=live.slot_free)
            alloc = self.engine.allocator
            if alloc is not None and alloc.replicas > 1:
                # dp-partitioned pool: admit onto the replica with the
                # most free pages — picking a starved replica would evict
                # a live stream while another replica sits idle
                slot = max(free, key=alloc.free_pages_for)
            else:
                slot = free[0]
            live.slot = slot
            ids = live.req.prompt_ids
            need_rows = all_rows = min(len(ids), self.engine.max_context - 1)
            window = self.engine.cfg.sliding_window
            if (
                alloc is not None
                and window is not None
                and self.prefill_chunk is not None
            ):
                # chunked admission on windowed models trims as it goes —
                # peak residency is window + one in-flight chunk (plus a
                # page of straddle), not the whole prompt
                need_rows = min(
                    need_rows, window + self.prefill_chunk + alloc.page_size
                )
            elif (
                alloc is not None
                and getattr(self.engine, "kv_compress_armed", False)
                and self.prefill_chunk is not None
            ):
                # window+sink KV compression prunes mid-admission the
                # same way: a prompt longer than the pool still admits,
                # peaking at sink + window + one in-flight chunk (plus a
                # page of straddle per boundary) — this is what opens
                # long-document prompts beyond the per-slot pool share
                comp_rows = (
                    self.engine.kv_sink_pages + self.engine.kv_window_pages
                ) * alloc.page_size
                need_rows = min(
                    need_rows,
                    max(self.engine.kv_compress_after, comp_rows)
                    + self.prefill_chunk + 2 * alloc.page_size,
                )
            # pages by kind: the full kind budgets the prompt's every row, the
            # window kind what the bound above leaves of it
            never_fits = alloc is not None and not alloc.can_hold(
                all_rows, need_rows
            )
            if never_fits:
                # the prompt can NEVER fit the pool — fail it up front;
                # evicting live requests one per tick would truncate every
                # co-resident stream before reaching the same conclusion
                log.warning(
                    "request %s prompt (%d tokens) exceeds the whole KV "
                    "page pool; failing it", live.req.request_id, len(ids),
                )
                live.done = True
                live.abort_reason = "prompt exceeds the KV page pool"
                self._rec_close(live)
                live.out_q.put(_END)
                continue
            chunked = self.prefill_chunk is not None and len(ids) > self.prefill_chunk
            if chunked:
                if self._prefilling is not None:
                    # one incremental admission at a time; FIFO order holds
                    with self._qlock:
                        self._waiting.appendleft(live)
                    return
                self._prefilling = (
                    live,
                    self.engine.start_chunked_prefill(
                        slot,
                        ids,
                        temperature=live.req.temperature,
                        top_p=live.req.top_p,
                        chunk=self.prefill_chunk,
                        given=live.req.prefix_hashes,
                    ),
                )
                self._prefill_chunks = 0
                self._reserved_slot = slot
                continue
            t0 = time.monotonic()
            reused0 = getattr(self.engine, "prefix_rows_reused", 0)
            restored0 = getattr(self.engine, "prefix_rows_restored", 0)
            try:
                first = self.engine.prefill_async(
                    slot,
                    ids,
                    temperature=live.req.temperature,
                    top_p=live.req.top_p,
                    given=live.req.prefix_hashes,
                )
            except PoolExhausted as e:
                with self._qlock:
                    self._waiting.appendleft(live)  # keep FIFO order
                outcome = self._evict_longest(
                    e.replica, requester_priority=live.req.priority,
                    kind=e.kind,
                )
                if outcome == "empty":
                    # nothing to evict: the prompt is bigger than the whole
                    # pool — fail just this request, not the scheduler
                    with self._qlock:
                        self._waiting.popleft()
                    live.done = True
                    live.abort_reason = "prompt exceeds the KV page pool"
                    self._rec_close(live)
                    live.out_q.put(_END)
                # "blocked": the pool is held by strictly higher-priority
                # streams — the admission stays queued and retries as they
                # drain; "evicted": retry next pass with the freed pages
                return
            self._admitted(live, first, t0, self._prefill_fields(
                live, len(ids), reused0, restored0
            ))

    def _admitted(self, live: _Live, first: PendingFirstToken, t0: float,
                  fields: dict) -> None:
        """The request's last prefill program is issued: its slot is
        active and decodes with the next dispatch. The pipelined loop
        goes on with the first token still on the device, so that the
        dispatch behind the prefill is issued while it runs, and reads
        it after (_decode_tick). What needs the number first reads it
        now: a constrained request, whose forced opener overwrites it
        before any dispatch, and the synchronous loop."""
        self.admissions += 1
        pend = _PendingFirst(
            live, first, t0, fields, self.engine.slot_length(live.slot)
        )
        with self._lock:
            self._live[live.slot] = live
        ahead = self.pipeline and live.constraint is None
        # the next dispatch then has the prefill's device time in front
        # of its own, as behind a prompt chunk that was not waited for
        self._chunk_ahead = ahead
        if ahead:
            self._firsts.append(pend)
        else:
            self._land_first(pend)

    def _land_first(self, pend: _PendingFirst) -> None:
        """Read a pending first token (the wait for the prefill program,
        batcher.first_token: a wait on the device) and hand it on: the
        prefill's event, time to first token, the token itself. One that
        ends the stream retires it here; a dispatch already issued for
        its slot decodes that column into nothing, as for any stream
        that ended."""
        live = pend.live
        with self.phases.phase("batcher.first_token"):
            token = pend.first.wait()
        self._rec_prefill(live, pend.t0, pend.fields)
        if live.constraint is not None:
            token = self._constrained_first(live, token)
        live.first_token_at = live.progress_at = time.monotonic()
        self._obs_ttft.observe(live.first_token_at - live.submitted_at)
        self._emit(live, token, slot_len=pend.slot_len)

    def _land_firsts(self) -> None:
        firsts, self._firsts = self._firsts, []
        for pend in firsts:
            self._land_first(pend)

    def _constrained_first(self, live: _Live, first: int) -> int:
        """Grammar-constrained requests overwrite the unmasked first token
        sampled by prefill with the grammar's forced opener ('{')."""
        cache = live.constraint.cache
        forced = cache.start_token_id
        if forced is None:  # no "{" token in vocab: fail open, unconstrained
            log.warning("json_mode: vocab has no '{' token; unconstrained")
            live.constraint = None
            return first
        self.engine.force_pending_token(live.slot, forced)
        live.constraint.advance(forced)
        return forced

    def _emit(self, live: _Live, token: int,
              slot_len: Optional[int] = None) -> None:
        if live.cancelled:
            return  # reaped (slot freed) at the next tick boundary
        live.produced += 1
        self._obs_tokens.inc()
        self._rate_tokens += 1
        live.out_q.put(token)
        hit_stop = token in live.req.stop_ids
        out_of_budget = live.produced >= live.req.max_tokens
        # pipelined consumes pass the slot length AS OF the dispatch that
        # produced this token — the engine's live length already includes
        # the in-flight next dispatch, and reading it would retire
        # requests one dispatch early (diverging from the sync loop)
        if slot_len is None:
            slot_len = self.engine.slot_length(live.slot)
        out_of_cache = slot_len >= self.engine.max_context - 1
        if hit_stop or out_of_budget or out_of_cache:
            self._finish(live)

    # -- pipelined decode (depth-2 double buffer) ---------------------------

    def _consume(self, tick: _PendingTick) -> None:
        """Emit one finished dispatch's tokens to whoever is still live:
        the wait for them (batcher.consume, a wait on the device), then
        the emission (batcher.emit, the host's own time).

        A PoolExhausted surfacing from the dispatch worker (the ensure()
        failed; engine state untouched) retires a victim here instead —
        the batch retries on a later dispatch, exactly like the sync
        loop's dispatch-site handling."""
        t0 = time.monotonic()
        try:
            with self.phases.phase("batcher.consume"):
                tokens = tick.pending.wait()
        except PoolExhausted as e:
            self._gap_wait += time.monotonic() - t0
            # the depth-2 buffer already issued the NEXT dispatch against
            # the same exhausted pool — collect its (identical) failure
            # BEFORE evicting, or the eviction path would flush it, see a
            # second PoolExhausted, and retire a second victim for ONE
            # pressure event
            nxt, self._pending = self._pending, None
            if nxt is not None:
                try:
                    nxt.pending.wait()
                except PoolExhausted:
                    pass  # state untouched; the post-evict tick retries
                else:
                    self._pending = nxt  # it ran after all: deliver it
            self._evict_longest(e.replica, kind=e.kind)
            return
        self._gap_wait += time.monotonic() - t0
        self._consumed = tick
        with self.phases.phase("batcher.emit"):
            dev = tick.pending.device_s
            if dev is not None:
                # late devprof join: the sampled device-µs of the dispatch
                # the worker just finished, onto the events recorded at
                # its submit (scheduler-thread-only mutation of LIVE
                # timelines — readers copy finished rings, never these)
                for ev in tick.evs:
                    ev["dev_us"] = round(dev * 1e6, 1)
            lengths = tick.pending.lengths
            for row in tokens:
                for slot, live in tick.lives.items():
                    if live.done:
                        continue
                    self._emit(
                        live, int(row[slot]), slot_len=int(lengths[slot])
                    )
            self._progressed(tick.lives.values())

    @staticmethod
    def _progressed(lives) -> None:
        """One stamp for every request a dispatch just emitted for."""
        now = time.monotonic()
        for live in lives:
            live.progress_at = now

    def _flush_pending(self, cause: str) -> None:
        """Consume the in-flight pipelined dispatch NOW. Called whenever
        the next dispatch cannot be issued ahead of consumption:
        grammar-constrained ticks (the mask depends on every emitted
        token), speculative ticks, pool-pressure evictions (a victim's
        already-produced tokens must land before its stream aborts), and
        idle boundaries. The first tokens of this tick's admissions are
        read before it: whatever comes next emits for their streams or
        ends them, and the dispatch in flight may be one issued behind
        them (_consume puts it back when the one before ran out of
        pages). No-op when nothing is pending."""
        self._land_firsts()
        tick = self._pending
        if tick is None:
            return
        self._pending = None
        self.flushes += 1
        # labels() resolves per FLUSH, not per token — the locked lookup
        # is fine at this rate (unlike the per-token children above)
        obs.ENGINE_DISPATCH_FLUSHES.labels(
            model=self.engine.cfg.name, cause=cause
        ).inc()
        self._consume(tick)

    def _note_dispatch(self) -> Optional[float]:
        """Record and return the host gap since the previous decode
        dispatch (the window the device idles in the sync loop; the
        pipeline's whole point is to hide it) — None for the first
        dispatch after an idle boundary. Call immediately BEFORE
        dispatching; the dispatch site stamps ``_gap_mark`` when the
        engine call returns.
        Time the pipelined tick spent BLOCKED waiting on the previous
        dispatch's tokens (``_gap_wait``) is subtracted — that's device
        time, and counting it would make the pipelined gap read as if
        the host were busier than the sync loop's."""
        act = faults.point("dispatch.delay", self.engine.cfg.name)
        if act is not None and act.delay_s > 0:
            # injected host stall: lands in the host-gap accounting like
            # any real slow-host phase would (docs/FAULTS.md)
            time.sleep(act.delay_s)
        gap = None
        if self._gap_mark is not None:
            gap = time.monotonic() - self._gap_mark - self._gap_wait
            gap = max(gap, 0.0)
            self.host_gap_seconds += gap
            self.decode_dispatches += 1
            self._obs_gap.observe(gap)
        self._gap_wait = 0.0
        return gap

    def _dispatch_key(self, n: int) -> object:
        """The running median a plain dispatch of ``n`` steps is judged
        against. One issued behind a prompt chunk that is still on the
        device carries that chunk's time, as long as a short dispatch's
        own: it has a median of its own."""
        behind, self._chunk_ahead = self._chunk_ahead, False
        return ("behind_chunk", n) if behind else n

    # -- flight-recorder hooks (obs/flightrec.py) ---------------------------
    # One event per DISPATCH per live request — never per token — and
    # every call is a no-op when the request carries no timeline, so the
    # recorder can be disabled without touching a single dispatch.

    def _rec_dispatch(self, lives, kind: str, n: int,
                      gap: Optional[float] = None,
                      dur_s: Optional[float] = None,
                      graph: str = "step", join_sample: bool = True,
                      **extra) -> list:
        """Record one dispatch on every live timeline. ``graph`` names
        the devprof GRAPH_KINDS entry this dispatch ran as: when devprof
        is armed, a fresh timing sample of that kind joins the event as
        ``dev_us`` (``join_sample=False`` for pipelined submits — their
        sample lands at consume, see _consume) and the ledger's mean
        device time, split by occupancy, accrues on each timeline's
        estimated device_us. Returns the recorded event dicts."""
        occ = len(lives)
        fields = dict(n=n, occ=occ, **extra)
        if gap is not None:
            fields["gap_ms"] = round(gap * 1e3, 3)
        if dur_s is not None:
            fields["dur_ms"] = round(dur_s * 1e3, 3)
        est = None
        if self.engine._devprof is not None:
            if join_sample:
                s = self.engine.devprof_take_sample()
                if s is not None and s[0] == graph:
                    fields["dev_us"] = round(s[1] * 1e6, 1)
            est = self.engine.devprof_est_s(graph)
        evs = []
        for live in lives:
            rec = live.req.rec
            if rec is not None and not live.done:
                ev = rec.event(kind, **fields)
                if ev is not None:
                    evs.append(ev)
                if est:
                    rec.device_us += est * 1e6 / max(occ, 1)
        return evs

    def _prefill_fields(self, live: _Live, tokens: int, reused0: float,
                        restored0: float,
                        chunk: Optional[int] = None) -> dict:
        """What a prefill event says of the program just issued, all of
        it the host's own knowledge: read before another admission of
        the same pass moves the engine's counters."""
        fields = dict(tokens=tokens)
        cached = getattr(self.engine, "prefix_rows_reused", 0) - reused0
        restored = (
            getattr(self.engine, "prefix_rows_restored", 0) - restored0
        )
        if cached:
            fields["cached_rows"] = int(cached)
        if restored:
            fields["restored_rows"] = int(restored)
        if chunk is not None:
            fields["chunk"] = chunk
        states = getattr(self.engine, "slot_states", None)
        if states is not None:
            # the state kind: what the slot holds beside its cache rows,
            # over how many state layers
            fields["state_bytes"] = states.slot_bytes
            fields["state_layers"] = states.layers
        if self.engine.cfg.kinds and live.slot >= 0:
            # pages by kind: what this admission left the slot holding
            alloc = self.engine.allocator
            fields["pages_full"] = alloc.slot_pages_resident(live.slot, "full")
            fields["pages_window"] = alloc.slot_pages_resident(
                live.slot, "window"
            )
        return fields

    def _rec_prefill(self, live: _Live, t0: float, fields: dict) -> None:
        """Record one prefill program on the request's timeline, ``t0``
        to now: a mid-chunk as it is issued, an admission's last program
        where its first token was read."""
        # pop the engine's sample FIRST (even when this request carries
        # no timeline) so a prefill-kind sample can never linger and
        # mis-join a later dispatch's event
        sample = None
        if self.engine._devprof is not None:
            sample = self.engine.devprof_take_sample()
        rec = live.req.rec
        if rec is None:
            return
        dur_s = time.monotonic() - t0
        fields["dur_ms"] = round(dur_s * 1e3, 3)
        if sample is not None and sample[0] in (
            "prefill", "chunk", "seq_prefill"
        ):
            fields["dev_us"] = round(sample[1] * 1e6, 1)
        if self.engine._devprof is not None:
            # prefill is request-exclusive and its time ends where the
            # first token was read: bill the measured wall time (an upper
            # bound on device time, exact on the CPU backend)
            rec.device_us += dur_s * 1e6
        rec.event("prefill", **fields)

    def _rec_close(self, live: _Live) -> None:
        """Finalize the request's timeline into the recorder ring —
        called on EVERY end-of-life path, right before the consumer's
        end-of-stream lands. Accounting is CUMULATIVE over the timeline
        (one client request may span several batcher attempts under
        transparent failover): tokens accumulate, TTFT anchors to the
        timeline's origin (failover delay counts against it — the SLO
        contract), TPOT spreads the post-first-token wall time over
        every token the client actually received."""
        rec = live.req.rec
        if rec is None:
            return
        rec.tokens_out += live.produced
        if live.first_token_at and not rec.ttft_ms:
            rec.ttft_ms = (live.first_token_at - rec.t0) * 1000.0
        if rec.ttft_ms and rec.tokens_out > 1:
            rec.tpot_ms = (
                ((time.monotonic() - rec.t0) * 1000.0 - rec.ttft_ms)
                / (rec.tokens_out - 1)
            )
        if live.abort_reason:
            fo = live.req.failover
            if fo is not None and fo.claims(live.abort_reason):
                # the failover controller owns this request's terminal
                # event: it either resumes the stream on a surviving
                # replica (the SAME timeline keeps accumulating) or
                # finishes it aborted once the retry budget exhausts —
                # finishing here would freeze the record mid-recovery
                # and ding SLO availability for a request the client
                # may yet see complete
                return
        # terminal from here on: bill the tenant's estimated device
        # seconds ONCE (finish() freezes the timeline below; a
        # failover-resumed request reaches this point only on its final
        # attempt, with device_us accumulated across every attempt)
        if (
            self.engine._devprof is not None
            and rec.device_us
            and not rec.finished_at
        ):
            obs.DEVPROF_TENANT_SECONDS.labels(tenant=rec.tenant).inc(
                rec.device_us / 1e6
            )
        if live.abort_reason:
            flightrec.RECORDER.finish(
                rec, "aborted", abort_reason=live.abort_reason
            )
        elif live.cancelled:
            flightrec.RECORDER.finish(rec, "cancelled")
        else:
            flightrec.RECORDER.finish(rec, "retired")

    def _finish(self, live: _Live, *, was_cancelled: bool = False,
                abort_reason: str = "") -> None:
        live.done = True
        if abort_reason:
            # the stream is a truncation, not a completion: consumers see
            # handle.aborted and surface an error/resubmit condition
            # instead of presenting the cut-short text as a normal answer
            live.abort_reason = abort_reason
        with self._lock:
            self._live.pop(live.slot, None)
        # free on the host at once (what the next dispatch's backing and
        # the occupancy read); the engine's half follows in
        # _settle_retired, and no new tenant takes the slot before it
        self._retired.append(live)
        self.engine.retire(live.slot)
        if was_cancelled:
            self.cancellations += 1
            self._obs_cancelled.inc()
        else:
            self.completed += 1
            self._obs_completed.inc()
        if not self._hold_retired:
            self._settle_retired()

    def _settle_retired(self) -> None:
        """The engine's half of the retirements _finish queued: the page
        frees and the two device resets under the engine lock, the
        timeline's close, the consumer's end of stream. With a pipelined
        dispatch handed over it runs BEHIND it: only once that dispatch
        holds the engine lock, so that the worker's enqueue is never kept
        waiting by a retirement (the device idled 5-24 ms where it was:
        PERF.md, PR 41); every later engine call orders after the
        dispatch anyway. With none pending it runs as it is. A stream
        leaves the queue before its turn: one that fails still ends, and
        the rest stay queued for the teardown."""
        if self._retired and self._pending is not None:
            self._pending.pending.wait_started()
            self.retirements_behind_dispatch += len(self._retired)
        while self._retired:
            live = self._retired.pop(0)
            try:
                self.engine.release_pages(live.slot)
                self._rec_close(live)
            finally:
                # _END goes last: when a consumer unblocks, the slot is
                # free, its pages are back and the counters are final
                live.out_q.put(_END)

    def _reap_cancelled(self) -> None:
        """Free every cancelled request before admission/decode: queued ones
        drop out of the wait list, a cancelled chunked admission releases
        its reserved slot mid-prefill, and live slots release their cache
        (the pages a disconnected agent was pinning)."""
        with self._qlock:
            still = deque()
            dropped: List[_Live] = []
            for live in self._waiting:
                (dropped if live.cancelled else still).append(live)
            if dropped:
                self._waiting = still
        for live in dropped:
            live.done = True
            self.cancellations += 1
            self._obs_cancelled.inc()
            self._rec_close(live)
            live.out_q.put(_END)
        if self._prefilling is not None and self._prefilling[0].cancelled:
            live = self._prefilling[0]
            self._prefilling = None
            self._reserved_slot = -1
            self._finish(live, was_cancelled=True)
        with self._lock:
            cancelled = [l for l in self._live.values() if l.cancelled]
        for live in cancelled:
            self._finish(live, was_cancelled=True)

    def _evict_longest(
        self, replica: Optional[int] = None,
        requester_priority: Optional[int] = None,
        kind: str = "",
    ) -> str:
        """Retire the lowest-priority live request, longest first within a
        priority level (frees the most pages; where the pool has pages by
        kind, the request that holds most of the ``kind`` that ran short:
        PoolExhausted.kind), so a pool-exhausted
        dispatch can make progress without sacrificing strategic work to
        keep bulk traffic alive. ``replica`` restricts the hunt to the
        starved replica of a dp-partitioned pool — evicting elsewhere
        frees nothing useful. ``requester_priority`` (admission paths)
        refuses to evict a victim that STRICTLY outranks the requester —
        the admission waits instead.

        Returns "evicted", "empty" (nothing live to evict), or "blocked"
        (only higher-priority victims exist)."""
        with self.phases.phase("batcher.evict"):
            # land the in-flight pipelined tokens first: the victim keeps what
            # it already produced (matching the sync loop), and a retirement
            # during the flush may itself free the pages this hunt is after
            self._flush_pending("evict")
            alloc = self.engine.allocator

            def holds(l):
                # pages by kind: the kind that ran short picks the victim
                if kind:
                    return alloc.slot_pages_resident(l.slot, kind)
                return self.engine.slot_length(l.slot)

            with self._lock:
                candidates = [
                    l for l in self._live.values()
                    if replica is None or alloc.replica_of(l.slot) == replica
                ]
                if not candidates:
                    return "empty"
                victim = min(
                    candidates, key=lambda l: (l.req.priority, -holds(l)),
                )
            if (
                requester_priority is not None
                and victim.req.priority > requester_priority
            ):
                return "blocked"
            log.warning(
                "KV page pool exhausted; retiring lowest-priority longest "
                "request %s (priority %d, %d rows) to free pages",
                victim.req.request_id,
                victim.req.priority,
                self.engine.slot_length(victim.slot),
            )
            self.pool_evictions += 1
            self._obs_evictions.inc()
            # the victim's stream is a truncation: mark it aborted so the
            # serving layer returns an error/resubmittable status instead of
            # a silently short normal completion
            self._finish(victim, abort_reason="evicted: KV pool exhausted")
            return "evicted"

    def _abort_all(self, exc: BaseException) -> None:
        """A scheduler-thread failure must surface, not strand callers: every
        live / mid-prefill / queued request is terminated (its iterator ends)
        and the error is kept for inspection."""
        self.last_error = exc
        log.exception("continuous batcher scheduler failed; aborting requests")
        self._terminate_outstanding(f"scheduler failed: {exc!r}"[:200])

    def _terminate_outstanding(self, reason: str) -> None:
        """End every live / mid-prefill / queued request (slot released,
        iterator ends with its abort_reason set, so the serving layer
        reports an error instead of presenting the truncation as a normal
        completion). Called on scheduler failure and on shutdown — any
        path after which no scheduler pass will run again."""
        victims: List[_Live] = []
        # an in-flight pipelined dispatch dies with the scheduler: its
        # tokens would extend streams that are being aborted as
        # truncations anyway, so drop, don't emit; a first token still
        # on the device goes the same way, unread
        self._pending = None
        self._firsts = []
        # retirements a failing tick left queued ended as they ended:
        # their streams close without an abort reason
        self._hold_retired = False
        while self._retired:
            try:
                self._settle_retired()
            # aios: waive(silent-except): best-effort page release during teardown of a stream that had already finished — the failure that brought the scheduler down is recorded by _abort_all
            except Exception:  # noqa: BLE001
                pass
        if self._prefilling is not None:
            victims.append(self._prefilling[0])
            self._prefilling = None
            self._reserved_slot = -1
        with self._lock:
            victims.extend(self._live.values())
            self._live.clear()
        with self._qlock:
            victims.extend(self._waiting)
            self._waiting.clear()
        for live in victims:
            live.done = True
            live.abort_reason = reason
            if live.slot >= 0:
                try:
                    self.engine.release(live.slot)
                # aios: waive(silent-except): best-effort slot release during teardown — the abort itself is recorded via live.abort_reason on the very next line
                except Exception:  # noqa: BLE001
                    pass
            self._rec_close(live)
            live.out_q.put(_END)

    def _run(self) -> None:
        self.phases.tick_thread = threading.get_ident()
        while not self._stop:
            try:
                self._tick()
            except Exception as exc:  # noqa: BLE001
                self._abort_all(exc)

    # -- the loop's own record (stalls, requests that stopped moving) -------

    def _tick_done(self, host: Dict[str, float],
                   under_dispatch: Dict[str, float]) -> None:
        """Judge the tick that just ended (the next one calls this from
        inside its first phase, so that the loop's bookkeeping has a
        name too). A stall is a tick whose host
        time (flightrec.Phases: everything outside batcher.dispatch and
        batcher.idle that is no wait on the device) was longer than the
        last decode dispatch — the device starved for more than a whole
        dispatch — or whose dispatch took more than STALL_DISPATCH_FACTOR
        times the running median for its step count. It is counted and
        leaves one model event naming the phase that took longest. A
        pipelined tick hands its dispatch to the worker and consumes the
        one before: what lay under its batcher.dispatch is host time (no
        wait on the device is in it), and the dispatch it is judged by is
        the one it consumed, by the seconds the worker spent on it."""
        dispatched, self._dispatched = self._dispatched, None
        consumed, self._consumed = self._consumed, None
        if dispatched is None:
            host = {**host, **under_dispatch}
            under_dispatch = {}
            if consumed is not None:
                dispatched = consumed.key
                under_dispatch = consumed.pending.spans
        self._judge_stall(host, under_dispatch, dispatched)
        now = time.monotonic()
        if now - self._progress_checked >= NO_PROGRESS_CHECK_SECS:
            self._progress_checked = now
            self._check_progress(now)

    def _judge_stall(self, host: Dict[str, float],
                     under_dispatch: Dict[str, float],
                     dispatched: Optional[object]) -> None:
        stalled, phases = 0.0, None
        dispatch_s = sum(under_dispatch.values())
        if dispatched is not None:
            dur = dispatch_s
            hist = self._dispatch_hist.get(dispatched)
            if hist is None:
                hist = self._dispatch_hist[dispatched] = deque(
                    maxlen=STALL_MEDIAN_OVER
                )
            if len(hist) >= STALL_MEDIAN_MIN:
                median = statistics.median(hist)
                if dur > STALL_DISPATCH_FACTOR * median:
                    stalled, phases = dur - median, under_dispatch
            hist.append(dur)
            self._last_dispatch_s = dur
        host_s = sum(host.values())
        if self._last_dispatch_s and host_s > max(
            self._last_dispatch_s, stalled
        ):
            stalled, phases = host_s, host
        if not phases:
            return
        self.loop_stalls += 1
        self.loop_stall_seconds += stalled
        longest = max(phases, key=phases.get)
        flightrec.RECORDER.model_event(
            self.engine.cfg.name, "stall", phase=longest,
            ms=round(phases[longest] * 1e3, 3),
            tick_ms=round((host_s + dispatch_s) * 1e3, 3),
            live=self.active_count, waiting=self.queue_depth(),
        )

    def _held(self) -> List[Tuple[str, _Live]]:
        """Every request the batcher holds, with where it sits."""
        with self._qlock:
            held = [("_waiting", l) for l in self._waiting]
        prefilling = self._prefilling
        if prefilling is not None:
            held.append(("_prefilling", prefilling[0]))
        with self._lock:
            held.extend(("_live", l) for l in self._live.values())
        return held

    def oldest_no_progress_s(self) -> float:
        """Seconds since the request that has stood still longest last
        moved (0 with nothing held)."""
        now = time.monotonic()
        return max(
            (now - l.progress_at for _, l in self._held() if not l.done),
            default=0.0,
        )

    def _check_progress(self, now: float) -> None:
        limit = max(
            NO_PROGRESS_DISPATCHES * self._last_dispatch_s,
            NO_PROGRESS_MIN_SECS,
        )
        for where, live in self._held():
            if (live.done or live.stuck_noted
                    or now - live.progress_at <= limit):
                continue
            live.stuck_noted = True
            state = {
                "request_id": live.req.request_id,
                "where": where,
                "no_progress_s": round(now - live.progress_at, 3),
                "slot": live.slot,
                "produced": live.produced,
                "max_tokens": live.req.max_tokens,
                "prompt_tokens": len(live.req.prompt_ids),
                "slot_length": (
                    self.engine.slot_length(live.slot)
                    if live.slot >= 0 else 0
                ),
                "engine_active": (
                    bool(self.engine.active[live.slot])
                    if live.slot >= 0 else False
                ),
                "cancelled": live.cancelled,
                "done": live.done,
                "live": self.active_count,
                "waiting": self.queue_depth(),
                "phases": self.phases.recent(64),
            }
            log.warning(
                "request %s (%s, slot %d, %d of %d tokens) has not moved "
                "for %.1f s", live.req.request_id, where, live.slot,
                live.produced, live.req.max_tokens, now - live.progress_at,
            )
            # one snapshot an episode: the recorder's cooldown drops the
            # rest, and the first holds the state worth having
            flightrec.RECORDER.snapshot(
                self.engine.cfg.name, "no_progress", sync=False,
                detail=state,
            )

    def stats(self) -> Dict[str, float]:
        """The loop's counters, flat: seconds and count per phase, the
        stalls, and how long the stillest held request has stood."""
        out = self.phases.stats()
        out["loop_stalls"] = self.loop_stalls
        out["loop_stall_seconds"] = round(self.loop_stall_seconds, 6)
        # how often the pipeline engages: a flush is a dispatch consumed
        # before the next could be issued ahead of it
        out["decode_dispatches"] = self.decode_dispatches
        out["dispatch_flushes"] = self.flushes
        # how often an admission's wait is hidden: those whose first
        # token was read after the dispatch behind them was issued
        out["admissions"] = self.admissions
        out["admissions_read_after_dispatch"] = (
            self.admissions_read_after_dispatch
        )
        # retirements whose engine half ran behind a dispatch that held
        # the engine lock (the rest had none to run behind)
        out["retirements_behind_dispatch"] = self.retirements_behind_dispatch
        out["oldest_no_progress_s"] = round(self.oldest_no_progress_s(), 3)
        return out

    # -- speculative auto-disable (per-proposer EWMA acceptance floor) ------

    def _spec_proposer(self, greedy_live: bool = True) -> Optional[str]:
        """Which proposer the next decode tick should dispatch with, or
        None when every rung of the ladder is suspended. Each proposer
        keeps its own EWMA and suspension window, so a collapsed draft
        model falls back to n-gram (not to nothing) and a collapsed
        n-gram still leaves the draft serving. An expired window grants
        the proposer SPEC_PROBE_DISPATCHES probe dispatches on a fresh
        cumulative average before the floor re-judges (a zeroed EWMA let
        one bad probe re-disable instantly). ``greedy_live=False`` skips
        the draft rung: with no greedy slot live the draft's K propose
        steps are pure overhead AND produce no measurable acceptance, so
        the tick falls through to n-gram, whose zero-acceptance EWMA
        suspends speculation properly."""
        now = time.monotonic()
        for p in self.spec_proposers:
            if p == "draft" and not greedy_live:
                continue
            off = self._spec_off_until[p]
            if off:
                if now < off:
                    continue
                self._spec_off_until[p] = 0.0
                self.spec_ewma[p] = None
                self._spec_probe_left[p] = SPEC_PROBE_DISPATCHES
                self._spec_probe_seen[p] = 0
            return p
        return None

    def _spec_active(self) -> bool:
        """Whether the next decode tick may dispatch speculatively at
        all (any rung of the proposer ladder available)."""
        if self.degrade_spec:
            return False
        return self._spec_proposer() is not None

    def _spec_measure(self, proposer: str, counts,
                      consumed: Dict[int, int], proposed=None) -> None:
        """Fold one spec dispatch's acceptance into ``proposer``'s EWMA
        and suspend THAT proposer when it collapses below the floor.
        ``counts`` is the dispatch's [rounds, num_slots] emitted-token
        matrix; ``consumed`` maps slot -> rounds whose tokens were
        actually EMITTED (each emits 1 + accepted-drafts). Rounds past a
        request's mid-dispatch retirement are excluded — their drafts
        score a continuation that is never served, and folding them in
        would suspend speculation on workloads whose served tokens
        accept perfectly well. ``proposed`` (draft proposer) is the
        [rounds, num_slots] offered-token matrix: the denominator counts
        only real proposals, so sampled-heavy batches don't read as
        rejection — the n-gram proposer keeps its historical
        every-round denominator."""
        if proposed is None:
            possible = sum(consumed.values()) * self.spec_draft_len
        else:
            possible = sum(
                float(proposed[:r, s].sum()) for s, r in consumed.items()
            )
        if not possible:
            return
        accepted = sum(
            float(counts[:r, s].sum()) - r for s, r in consumed.items()
        )
        ratio = max(accepted, 0.0) / possible
        prev = self.spec_ewma[proposer]
        if prev is None:
            self.spec_ewma[proposer] = ratio
            self._spec_probe_seen[proposer] = 1
        elif self._spec_probe_left[proposer] > 0:
            # probe phase: cumulative average over the probe budget (an
            # EWMA seeded from one sample would weight it like a whole
            # collapsed history)
            n = self._spec_probe_seen[proposer]
            self.spec_ewma[proposer] = (prev * n + ratio) / (n + 1)
            self._spec_probe_seen[proposer] = n + 1
        else:
            self.spec_ewma[proposer] = (
                (1 - SPEC_EWMA_ALPHA) * prev + SPEC_EWMA_ALPHA * ratio
            )
        if self._spec_probe_left[proposer] > 0:
            self._spec_probe_left[proposer] -= 1
            if self._spec_probe_left[proposer] > 0:
                return  # verdict deferred until the probe budget drains
        if (
            self.spec_min_accept > 0
            and self.spec_ewma[proposer] < self.spec_min_accept
        ):
            self._spec_off_until[proposer] = (
                time.monotonic() + self.spec_reprobe_secs
            )
            self.spec_autodisables += 1
            log.info(
                "%s: %s speculation suspended (EWMA acceptance %.3f < "
                "floor %.3f); re-probing in %.0fs",
                self.engine.cfg.name, proposer, self.spec_ewma[proposer],
                self.spec_min_accept, self.spec_reprobe_secs,
            )

    # -- grammar jump-ahead (compressed-FSM run collapse) -------------------

    def _jump_tick(self, constrained) -> bool:
        """Collapse chains of grammar-FORCED tokens into one multi-token
        dispatch (engine.jump_step) instead of one masked dispatch each.

        Each constrained slot's automaton is probed for a forced run —
        states whose effective mask admits exactly ONE token (schema key
        literals, '":', '",', closing braces; see
        JsonConstraint.forced_run). Runs of >= 2 tokens pay for a jump:
        the dispatch appends their K/V through the verify machinery with
        acceptance pinned to all-accept, and the tokens emit host-side
        (they ARE the only tokens any sampler could produce, so streams
        are identical to the per-step path). Slots without a run — and
        unconstrained co-residents — do not advance this dispatch; the
        next tick serves them with the usual masked step, so a mixed
        batch pays at most one extra tick per run while the run itself
        collapses from len(run) dispatches to one.

        Returns True when a jump dispatch was issued (the tick is done).
        """
        runs: Dict[int, List[int]] = {}
        for s_, live in constrained:
            c = live.constraint
            if c is None or getattr(c, "failed", False):
                continue
            rem = live.req.max_tokens - live.produced
            # the verify-write contract: post-run length <= C-2
            room = self.engine.max_context - 2 - self.engine.slot_length(s_)
            cap = min(self.jump_max, rem, room)
            if cap < 2:
                continue
            run = c.forced_run(cap, remaining=rem,
                               stop_ids=live.req.stop_ids)
            if len(run) >= 2:
                runs[s_] = run
        if not runs:
            return False
        k = max(len(r) for r in runs.values())
        forced = np.zeros((self.engine.num_slots, k), np.int32)
        counts = np.zeros((self.engine.num_slots,), np.int32)
        for s_, run in runs.items():
            forced[s_, : len(run)] = run
            counts[s_] = len(run)
        try:
            with self.phases.phase("batcher.dispatch"):
                gap = self._note_dispatch()
                t0 = time.monotonic()
                self.engine.jump_step(forced, counts)
                self._gap_mark = time.monotonic()
        except PoolExhausted as e:
            self._evict_longest(e.replica, kind=e.kind)  # retry next tick
            return True
        self._dispatched = ("jump", k)
        with self.phases.phase("batcher.emit"):
            self._jump_emit(constrained, runs, gap, t0)
        return True

    def _jump_emit(self, constrained, runs, gap, t0) -> None:
        dur_ms = round((self._gap_mark - t0) * 1e3, 3)
        dev_us = None
        est_us = 0.0
        if self.engine._devprof is not None:
            s = self.engine.devprof_take_sample()
            if s is not None and s[0] == "jump":
                dev_us = round(s[1] * 1e6, 1)
            est = self.engine.devprof_est_s("jump")
            if est:
                est_us = est * 1e6 / max(len(runs), 1)
        by_slot = dict(constrained)
        for s_ in sorted(runs):
            live = by_slot[s_]
            if live.done:
                continue
            rec = live.req.rec
            if rec is not None:
                rec.event(
                    "jump", k=len(runs[s_]), occ=len(runs),
                    dur_ms=dur_ms,
                    **({"gap_ms": round(gap * 1e3, 3)}
                       if gap is not None else {}),
                    **({"dev_us": dev_us}
                       if dev_us is not None else {}),
                )
                rec.device_us += est_us
            for tok in runs[s_]:
                live.constraint.advance(tok)
                self._emit(live, tok)
                if live.done:
                    break
        self._progressed(by_slot[s_] for s_ in runs)

    def _tick(self) -> None:
        phase = self.phases.phase
        last_tick = self.phases.take_tick()
        if self._pending is not None:
            # ordering fence: the pipelined dispatch handed to the worker
            # last tick must HOLD the engine lock before this tick issues
            # any engine call (slot releases, admissions, chunk writes) —
            # those must land after it, or the slot set it was issued
            # against could change under it
            with phase("batcher.fence"):
                self._pending.pending.wait_started()
        with phase("batcher.reap"):
            self._tick_done(*last_tick)
            self._refresh_rate()
            self._reap_cancelled()
        # the next two get a span only in the ticks in which they have
        # work: every running stream waits through them
        if self._prefilling is not None:
            with phase("batcher.prefill"):
                self._advance_prefill()
        with self._qlock:
            anyone_waiting = bool(self._waiting)
        if anyone_waiting and self._free_slots():
            with phase("batcher.admit"):
                self._admit()
        self._decode_tick()

    def _refresh_rate(self) -> None:
        now = time.monotonic()
        if now - self._rate_t0 >= 1.0:
            rate = self._rate_tokens / (now - self._rate_t0)
            self._obs_tps.set(rate)
            if rate > 0:
                # remember the decoding-time rate across idle windows (the
                # gauge decays to 0; deadline feasibility must not)
                self.last_tps = rate
            self._rate_tokens = 0
            self._rate_t0 = now

    def _decode_tick(self) -> None:
        """The tick's second half: one decode dispatch for the live
        slots and the emission of its tokens, or the idle wait."""
        phase = self.phases.phase
        with self._lock:
            slots = {s: l for s, l in self._live.items()}
        if slots:
            # chaos: a scheduler crash mid-decode — the exception rides
            # the real _run -> _abort_all -> respawn path, gated on live
            # slots so idle wake-loop ticks don't consume trigger hits
            # (an nth:N schedule then counts DECODE ticks, which is what
            # a deterministic crash drill wants to aim at)
            act = faults.point("pool.scheduler_crash", self.engine.cfg.name)
            if act is not None:
                raise faults.InjectedFault(
                    f"injected scheduler crash ({act.mode}, hit {act.hit})"
                )
        if not slots:
            # nothing live NOW: land whatever the last pipelined dispatch
            # produced (its requests retired mid-consume, so this usually
            # just drops garbage columns) before going idle
            self._flush_pending("idle")
            self._gap_mark = None
            if self._prefilling is not None:
                return  # nothing to decode; keep chunking
            with phase("batcher.idle"):
                self._wake.wait(timeout=0.05)
                self._wake.clear()
            return
        constrained = [
            (s_, l) for s_, l in slots.items() if l.constraint is not None
        ]
        if constrained:
            # grammar masks change per emitted token, so constrained slots
            # ride 1-step dispatches — and the mask for the NEXT step
            # depends on every token emitted so far, so the pipeline
            # drains first. Rows are cached DEVICE-resident per automaton
            # state and scattered into a cached all-zeros [S, V] base, so
            # unconstrained co-resident slots cost nothing (no per-slot
            # row stack, no per-step PCIe traffic).
            self._flush_pending("constrained")
            if self.jump_ahead and not self.degrade_jump \
                    and self._jump_tick(constrained):
                return
            import jax.numpy as jnp

            by_slot = dict(constrained)
            idx = sorted(by_slot)
            rows = [
                by_slot[s_].constraint.device_mask(
                    remaining=by_slot[s_].req.max_tokens
                    - by_slot[s_].produced
                )
                for s_ in idx
            ]
            if len(idx) == self.engine.num_slots:
                mask = jnp.stack(rows)
            else:
                base = self._mask_base
                if base is None:
                    base = self._mask_base = jnp.zeros(
                        (self.engine.num_slots, self.engine.cfg.vocab_size),
                        jnp.float32,
                    )
                mask = base.at[jnp.asarray(idx, jnp.int32)].set(
                    jnp.stack(rows)
                )
            try:
                with phase("batcher.dispatch"):
                    gap = self._note_dispatch()
                    t0 = time.monotonic()
                    tokens = self.engine.step_masked(mask)
                    self._gap_mark = time.monotonic()
            except PoolExhausted as e:
                self._evict_longest(e.replica, kind=e.kind)
                return
            self._dispatched = 1
            with phase("batcher.emit"):
                self._rec_dispatch(
                    slots.values(), "decode", 1, gap,
                    self._gap_mark - t0, graph="masked", constrained=True,
                )
                for slot, live in list(slots.items()):
                    if live.done:
                        continue
                    tok = int(tokens[0, slot])
                    if live.constraint is not None:
                        live.constraint.advance(tok)
                    self._emit(live, tok)
                self._progressed(slots.values())
            return
        # keep admission latency low when someone is waiting (constrained
        # ticks above ignore chunking — they are always 1 step). n is
        # always one of the batcher's two sizes (one and the same by
        # default) — each step size is its own XLA
        # graph, so clamping n to a data-dependent remaining-budget (as an
        # earlier version did) triggers fresh multi-second compiles on this
        # thread mid-serving; overshooting a request's max_tokens just
        # produces ignored tokens, which costs microseconds instead
        with self._qlock:
            anyone_waiting = bool(self._waiting) or self._prefilling is not None
        n = self.admit_chunk_steps if anyone_waiting else self.chunk_steps
        proposer = None
        if self.speculative and not self.degrade_spec:
            # the draft rung needs a greedy slot to propose for; without
            # one it falls through to n-gram (see _spec_proposer)
            greedy_live = any(
                l.req.temperature < GREEDY_EPS for l in slots.values()
            )
            proposer = self._spec_proposer(greedy_live)
        if proposer is not None:
            # [n, S, K+1] tokens, [n, S] counts — emit each round's accepted
            # run in order; _emit retires requests mid-dispatch as usual.
            # Speculative dispatches consume their own output synchronously
            # (acceptance counts gate the emit), so they never pipeline;
            # drain any pending plain dispatch first.
            self._flush_pending("spec")
            proposed = None
            try:
                with phase("batcher.dispatch"):
                    gap = self._note_dispatch()
                    t0 = time.monotonic()
                    if proposer == "draft":
                        tokens, counts, proposed = (
                            self.engine.spec_step_draft(
                                n, draft_len=self.spec_draft_len
                            )
                        )
                    else:
                        tokens, counts = self.engine.spec_step(
                            n, draft_len=self.spec_draft_len,
                            ngram=self.spec_ngram,
                        )
                    self._gap_mark = time.monotonic()
            except PoolExhausted as e:
                self._evict_longest(e.replica, kind=e.kind)  # retry next tick
                return
            self._dispatched = ("spec", proposer, n)
            with phase("batcher.emit"):
                self._spec_emit(slots, proposer, tokens, counts, proposed,
                                gap, t0)
            return
        if self.pipeline:
            # depth-2 double buffer: hand dispatch N+1 to the engine's
            # dispatch worker, THEN consume dispatch N — the host's
            # emit/retire phase runs while N+1 executes (the worker holds
            # the blocking graph call + readback, so this overlaps even
            # on the CPU backend, where XLA executes inline in the
            # dispatching thread). Tokens stream identically to the sync
            # loop: each dispatch's live map and post-dispatch lengths
            # are snapshotted, so late retirements drop exactly the
            # columns the sync loop would never have dispatched. A
            # PoolExhausted surfaces at consume time (_consume evicts).
            prev = self._pending
            with phase("batcher.dispatch"):
                gap = self._note_dispatch()
                handle = self.engine.step_async(n)
                self._gap_mark = time.monotonic()
                # the worker's timing sample (if this dispatch drew one)
                # joins these events at consume time — see _consume
                evs = self._rec_dispatch(
                    slots.values(), "decode", n, gap, pipelined=True,
                    join_sample=False,
                )
                self._pending = _PendingTick(
                    handle, slots, tuple(evs), self._dispatch_key(n)
                )
            # the dispatch is issued behind this tick's prefill programs:
            # the device goes from the last of them into it while the
            # host reads the first tokens. ``prev`` ended before the
            # prefills began, yet its tokens go out AFTER the read:
            # measured on the chip, the streams' p99 gap is 1.6-2 ms
            # shorter than with ``prev`` consumed first (PERF.md, PR 39)
            self.admissions_read_after_dispatch += len(self._firsts)
            if (self._prefilling is not None
                    and self._slack_s < STAGE_UNDER_SLACK_S):
                # the chunk the NEXT tick issues: its operands go to the
                # device now, while the dispatch before this one still
                # runs and this thread would only wait for its tokens
                with phase("batcher.prefill"):
                    self._prefilling[1].stage()
            landing = bool(self._firsts)
            # a stream that ends in these tokens is retired behind the
            # dispatch just handed over (_settle_retired): the worker
            # takes the engine lock first, the retirement's frees after
            self._hold_retired = True
            try:
                self._land_firsts()
                if prev is not None:
                    self._consume(prev)
                    if not landing:
                        self._slack_s = self._gap_wait
            finally:
                self._hold_retired = False
            if self._retired:
                with phase("batcher.retire"):
                    self._settle_retired()
            return
        try:
            with phase("batcher.dispatch"):
                gap = self._note_dispatch()
                t0 = time.monotonic()
                tokens = self.engine.step(n)  # [n, num_slots]
                self._gap_mark = time.monotonic()
        except PoolExhausted as e:
            # retire the longest request and retry on the next tick; the
            # failed ensure() left all engine state untouched
            self._evict_longest(e.replica, kind=e.kind)
            return
        self._dispatched = self._dispatch_key(n)
        with phase("batcher.emit"):
            self._rec_dispatch(
                slots.values(), "decode", n, gap, self._gap_mark - t0
            )
            for step_row in tokens:
                for slot, live in list(slots.items()):
                    if live.done:
                        continue
                    self._emit(live, int(step_row[slot]))
            self._progressed(slots.values())

    def _spec_emit(self, slots, proposer, tokens, counts, proposed,
                   gap, t0) -> None:
        """Emit one speculative dispatch's accepted runs, record it on
        every served timeline and fold its acceptance into the
        proposer's EWMA."""
        dur_ms = round((self._gap_mark - t0) * 1e3, 3)
        graph = "draft_spec" if proposer == "draft" else "spec"
        dev_us = None
        est_us = 0.0
        if self.engine._devprof is not None:
            s = self.engine.devprof_take_sample()
            if s is not None and s[0] == graph:
                dev_us = round(s[1] * 1e6, 1)
            est = self.engine.devprof_est_s(graph)
            if est:
                est_us = est * 1e6 / max(len(slots), 1)
        consumed: Dict[int, int] = {}
        for r in range(tokens.shape[0]):
            for slot, live in list(slots.items()):
                if live.done:
                    continue
                consumed[slot] = r + 1  # this round's tokens are served
                for j in range(int(counts[r, slot])):
                    self._emit(live, int(tokens[r, slot, j]))
                    if live.done:
                        break
        for slot, live in slots.items():
            rounds = consumed.get(slot)
            rec = live.req.rec
            if rec is not None and rounds:
                # emitted = rounds + accepted drafts for this slot's
                # SERVED rounds (the _spec_measure accounting)
                rec.event(
                    "spec", rounds=rounds, proposer=proposer,
                    emitted=int(counts[:rounds, slot].sum()),
                    draft_len=self.spec_draft_len, dur_ms=dur_ms,
                    **({"gap_ms": round(gap * 1e3, 3)}
                       if gap is not None else {}),
                    **({"dev_us": dev_us}
                       if dev_us is not None else {}),
                )
                rec.device_us += est_us
        self._progressed(slots[s_] for s_ in consumed)
        self._spec_measure(proposer, counts, consumed, proposed)
