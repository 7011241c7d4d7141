"""The metric catalog: every instrument the stack registers, in one place.

Naming convention (enforced by tests/test_obs_lint.py):
  * prefix ``aios_tpu_``, snake_case ``[a-z0-9_]`` only;
  * unit suffix from the approved set: ``_seconds``, ``_bytes``,
    ``_total`` (counts and count-valued gauges), ``_ratio``,
    ``_per_second``, ``_usd_total`` (spend counters end in ``_total``
    with the currency inline), ``_info`` (the Prometheus info-gauge
    convention: constant 1, identity in labels — unitless by design).

Keeping every definition here (rather than scattered at point of use)
makes drift visible in review, keeps duplicate-registration impossible,
and gives the lint test one import to check. Hot paths resolve label
children once and hold them (see ContinuousBatcher) — ``labels()`` is a
dict lookup under a lock, fine for RPC rates, too slow per decoded token.

docs/OBSERVABILITY.md mirrors this catalog; update both together.
"""

from __future__ import annotations

from .metrics import Counter, Gauge, Histogram

# -- RPC layer (client + server interceptors, aios_tpu/rpc.py) -------------

RPC_REQUESTS = Counter(
    "aios_tpu_rpc_requests_total",
    "RPCs started, by side (client|server), service, and method",
    ("side", "service", "method"),
)
RPC_ERRORS = Counter(
    "aios_tpu_rpc_errors_total",
    "RPCs finished non-OK, by side, service, method, and status code",
    ("side", "service", "method", "code"),
)
RPC_LATENCY = Histogram(
    "aios_tpu_rpc_latency_seconds",
    "RPC wall time start->termination (streams: until exhausted)",
    ("side", "service", "method"),
)

# -- engine: decode loop + continuous batcher ------------------------------

ENGINE_DECODE_STEPS = Counter(
    "aios_tpu_engine_decode_steps_total",
    "Decode steps executed (each advances every active slot one token)",
    ("model",),
)
ENGINE_TOKENS = Counter(
    "aios_tpu_engine_generated_tokens_total",
    "Tokens emitted to request streams by the continuous batcher",
    ("model",),
)
ENGINE_TOKENS_PER_SECOND = Gauge(
    "aios_tpu_engine_tokens_per_second",
    "Recent decode throughput per model (tokens/sec/chip, ~1 s window)",
    ("model",),
)
ENGINE_TTFT = Histogram(
    "aios_tpu_engine_ttft_seconds",
    "Submission -> first sampled token through the continuous batcher",
    ("model",),
)
ENGINE_OCCUPANCY = Gauge(
    "aios_tpu_engine_batch_occupancy_ratio",
    "Active decode slots / total slots (scrape-time)",
    ("model",),
)
ENGINE_SLOTS_IN_USE = Gauge(
    "aios_tpu_engine_slots_in_use_total",
    "Active decode slots (scrape-time)",
    ("model",),
)
ENGINE_QUEUE_DEPTH = Gauge(
    "aios_tpu_engine_queue_depth_total",
    "Requests waiting for a slot (admission backlog, scrape-time)",
    ("model",),
)
ENGINE_KV_PAGES_IN_USE = Gauge(
    "aios_tpu_engine_kv_pages_in_use_total",
    "Paged-KV physical pages currently mapped (scrape-time)",
    ("model",),
)
ENGINE_KV_PAGE_UTILIZATION = Gauge(
    "aios_tpu_engine_kv_page_utilization_ratio",
    "Paged-KV pages in use / pool capacity (scrape-time)",
    ("model",),
)
ENGINE_PREFIX_HITS = Gauge(
    "aios_tpu_engine_prefix_cache_hits_total",
    "Prompt-prefix cache hits (monotonic, read from the prefix index)",
    ("model",),
)
ENGINE_PREFIX_MISSES = Gauge(
    "aios_tpu_engine_prefix_cache_misses_total",
    "Prompt-prefix cache misses (monotonic, read from the prefix index)",
    ("model",),
)
ENGINE_REQUESTS_COMPLETED = Counter(
    "aios_tpu_engine_requests_completed_total",
    "Requests retired normally (EOS / max_tokens / full cache)",
    ("model",),
)
ENGINE_REQUESTS_CANCELLED = Counter(
    "aios_tpu_engine_requests_cancelled_total",
    "Requests cancelled by the caller (gRPC disconnect, unload)",
    ("model",),
)
ENGINE_POOL_EVICTIONS = Counter(
    "aios_tpu_engine_pool_evictions_total",
    "Live requests retired to free KV pages under pool exhaustion",
    ("model",),
)
ENGINE_XLA_COMPILES = Counter(
    "aios_tpu_engine_xla_compiles_total",
    "XLA graph builds by kind "
    "(step|masked|prefill|chunk|spec|jump|hist|restore)",
    ("model", "kind"),
)
ENGINE_XLA_COMPILE_SECONDS = Histogram(
    "aios_tpu_engine_xla_compile_seconds",
    "First-dispatch wall time of each new XLA graph (trace+compile stall)",
    ("model", "kind"),
    buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0, 160.0),
)

# -- decode dispatch loop (pipelined batcher, AIOS_TPU_DECODE_PIPELINE) ----
# The dispatch family watches the host<->device seam of the decode loop:
# how long the host spends between consecutive decode dispatches (the
# device-idle window in the sync loop — the pipeline exists to hide it),
# whether a pipelined dispatch is currently in flight, and how often the
# pipeline had to drain early (constrained ticks, evictions, idle).

ENGINE_DISPATCH_HOST_GAP = Histogram(
    "aios_tpu_engine_dispatch_host_gap_seconds",
    "Host wall time between consecutive decode dispatches (emit/detok/"
    "retire/bookkeeping; the device idles through this unless pipelined)",
    ("model",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 1.0),
)
ENGINE_DISPATCH_INFLIGHT = Gauge(
    "aios_tpu_engine_dispatch_inflight_total",
    "Pipelined decode dispatches enqueued but not yet consumed, summed "
    "over the model's replica batchers (0..replicas; scrape-time)",
    ("model",),
)
ENGINE_DISPATCH_FLUSHES = Counter(
    "aios_tpu_engine_dispatch_flushes_total",
    "Pipelined decode flushes by cause "
    "(constrained|spec|evict|idle)",
    ("model", "cause"),
)

# -- grammar jump-ahead decoding (engine.jump_step; batching constrained
# tick) — monotonic engine counters read at scrape time, SUMMED over a
# per-model WeakSet of live replica engines (set_function is last-writer-
# wins; the aios_tpu_prefix_host_* aggregation pattern).

ENGINE_JUMP_DISPATCHES = Gauge(
    "aios_tpu_engine_jump_ahead_dispatches_total",
    "Multi-token jump-ahead dispatches (each replaced a chain of masked "
    "single-token dispatches; monotonic, summed over replica engines)",
    ("model",),
)
ENGINE_JUMP_TOKENS = Gauge(
    "aios_tpu_engine_jump_ahead_tokens_total",
    "Grammar-forced tokens emitted via jump-ahead runs (monotonic, "
    "summed over replica engines)",
    ("model",),
)

# -- speculative decoding (engine.spec_step / spec_step_draft) -------------
# Rounds/accepted are engine counters (WeakSet-summed like the jump
# family); the acceptance ratio is the per-batcher EWMA driving the
# AIOS_TPU_SPEC_MIN_ACCEPT auto-disable, averaged over live replica
# batchers at scrape time. Every series carries the ``proposer`` label —
# the CLOSED enum spec.SPEC_PROPOSERS (ngram | draft), pinned by
# test_obs_lint — so the draft-model and prompt-lookup proposers read as
# separate series and the ladder's fallbacks are visible in the metrics.

SPEC_ROUNDS = Gauge(
    "aios_tpu_spec_rounds_total",
    "Speculative verify rounds dispatched by proposer (ngram|draft; "
    "monotonic, summed over replica engines)",
    ("model", "proposer"),
)
SPEC_ACCEPTED = Gauge(
    "aios_tpu_spec_accepted_total",
    "Draft tokens accepted by speculative verify (emitted tokens minus "
    "the one guaranteed token per slot-round; by proposer, monotonic, "
    "summed over replica engines)",
    ("model", "proposer"),
)
SPEC_ACCEPTANCE = Gauge(
    "aios_tpu_spec_acceptance_ratio",
    "EWMA draft-acceptance ratio (accepted / proposed) per model and "
    "proposer, averaged over replica batchers; drives the per-proposer "
    "AIOS_TPU_SPEC_MIN_ACCEPT auto-disable ladder",
    ("model", "proposer"),
)

# -- long-context tier (docs/ENGINE_PERF.md "Long-context tier") -----------
# Window+sink KV compression + sequence-sharded prefill. Counters are
# monotonic engine counters read at scrape time, SUMMED over the
# per-model WeakSet of live replica engines (the jump/spec pattern);
# the resident gauge reads live allocator state.

KV_COMPRESS_SLOTS = Gauge(
    "aios_tpu_kv_compress_slots_total",
    "Slots whose KV crossed the compression threshold and pruned to "
    "sink + window pages (monotonic, summed over replica engines)",
    ("model",),
)
KV_COMPRESS_PAGES_PRUNED = Gauge(
    "aios_tpu_kv_compress_pages_pruned_total",
    "KV pages released back to the pool by window+sink pruning "
    "(monotonic, summed over replica engines)",
    ("model",),
)
KV_COMPRESS_RESIDENT = Gauge(
    "aios_tpu_kv_compress_resident_pages",
    "Pages currently resident for compressed slots (sink + trailing "
    "window + partial block; scrape-time, summed over replica engines)",
    ("model",),
)
PREFILL_SEQ_SHARDED = Gauge(
    "aios_tpu_prefill_seq_sharded_total",
    "Prompts admitted through the sequence-sharded (sp-axis ring/"
    "Ulysses) prefill path instead of chunked admission (monotonic, "
    "summed over replica engines)",
    ("model",),
)

# -- prefix-cache host spill tier (engine/paged.py HostPageStore) ----------
# Monotonic store counters surface as count-valued gauges read at scrape
# time (the ENGINE_PREFIX_* pattern); only the restore latency is a true
# histogram observed on the restore path.

PREFIX_HOST_BYTES = Gauge(
    "aios_tpu_prefix_host_resident_bytes",
    "Host-RAM bytes holding spilled prefix-page KV (scrape-time)",
    ("model",),
)
PREFIX_HOST_SPILLS = Gauge(
    "aios_tpu_prefix_host_spills_total",
    "Prefix pages spilled device->host on HBM eviction (monotonic)",
    ("model",),
)
PREFIX_HOST_RESTORES = Gauge(
    "aios_tpu_prefix_host_restores_total",
    "Prefix pages restored host->device into fresh pool pages (monotonic)",
    ("model",),
)
PREFIX_HOST_HITS = Gauge(
    "aios_tpu_prefix_host_hits_total",
    "Host-tier chain probes that found at least one spilled page "
    "(monotonic)",
    ("model",),
)
PREFIX_HOST_MISSES = Gauge(
    "aios_tpu_prefix_host_misses_total",
    "Host-tier chain probes that found nothing (monotonic)",
    ("model",),
)
PREFIX_HOST_MISSES_CORRUPT = Gauge(
    "aios_tpu_prefix_host_corrupt_total",
    "Spilled pages whose crc32 failed verification at restore probe "
    "time — dropped and recomputed instead of restored (monotonic)",
    ("model",),
)
PREFIX_HOST_RESTORE_SECONDS = Histogram(
    "aios_tpu_prefix_host_restore_seconds",
    "Host-side wall time to stage + dispatch one host->device prefix "
    "restore (the scatter itself is async and overlaps tail prefill)",
    ("model",),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0),
)

# -- SLO engine (obs/slo.py, fed by the obs/flightrec.py recorder) ---------
# Labeled (model, objective) with ``objective`` drawn from the closed
# slo.OBJECTIVES enum (ttft|tpot|availability). The per-tenant breakdown
# deliberately stays in /debug/slo JSON — a tenant x model label product
# would be unbounded (the test_serving_label_conventions rationale).

SLO_ATTAINMENT = Gauge(
    "aios_tpu_slo_attainment_ratio",
    "Fraction of windowed requests meeting the objective's target "
    "(objective=ttft|tpot|availability; scrape-time, sliding window)",
    ("model", "objective"),
)
SLO_BURN_RATE = Gauge(
    "aios_tpu_slo_burn_rate_ratio",
    "Error-budget burn rate: (1 - attainment) / (1 - target); 1.0 burns "
    "exactly at budget, >1 eats future budget (scrape-time)",
    ("model", "objective"),
)
SLO_BREACHES = Counter(
    "aios_tpu_slo_breaches_total",
    "Windowed attainment fell below target (edge-triggered per "
    "(model, objective); each breach freezes a flight-recorder snapshot)",
    ("model", "objective"),
)

# -- runtime service -------------------------------------------------------

RUNTIME_INFER_LATENCY = Histogram(
    "aios_tpu_runtime_infer_latency_seconds",
    "Per-model inference RPC wall time (rpc = Infer|StreamInfer)",
    ("model", "rpc"),
)
RUNTIME_STREAM_CHUNKS = Counter(
    "aios_tpu_runtime_stream_chunks_total",
    "Text chunks emitted by StreamInfer",
    ("model",),
)
RUNTIME_MODELS_READY = Gauge(
    "aios_tpu_runtime_models_ready_total",
    "Models in the ready state (scrape-time)",
)

# -- serving layer (replica pool + router + admission, aios_tpu/serving/) --
# Labeled by the MANAGED model name (pool name), not the config name —
# two managed models of the same architecture must not collapse into one
# series. ``replica`` is the replica index (bounded by the replica count).

SERVING_REPLICAS = Gauge(
    "aios_tpu_serving_replicas_total",
    "Live replicas in the pool (scrape-time)",
    ("model",),
)
SERVING_REPLICA_OCCUPANCY = Gauge(
    "aios_tpu_serving_replica_occupancy_ratio",
    "Per-replica active decode slots / total slots (scrape-time)",
    ("model", "replica"),
)
SERVING_ROUTING_DECISIONS = Counter(
    "aios_tpu_serving_routing_decisions_total",
    "Replica selections by reason (prefix|sticky|least_loaded|spill|single)",
    ("model", "reason"),
)
SERVING_SHED = Counter(
    "aios_tpu_serving_shed_total",
    "Requests shed at the front door, by cause "
    "(quota|deadline|queue_full|draining)",
    ("model", "cause"),
)
SERVING_QUOTA_REJECTIONS = Counter(
    "aios_tpu_serving_quota_rejections_total",
    "Token-bucket quota rejections per tenant",
    ("tenant",),
)
SERVING_QUEUE_WAIT = Histogram(
    "aios_tpu_serving_queue_wait_seconds",
    "Submission -> batcher admission (slot assignment) wall time",
    ("model",),
)
SERVING_REPLICA_RESTARTS = Counter(
    "aios_tpu_serving_replica_restarts_total",
    "Replica batchers respawned after a scheduler crash "
    "(the spawner-style restart counter, serving-side)",
    ("model",),
)
SERVING_FAILOVERS = Counter(
    "aios_tpu_serving_failover_total",
    "In-flight requests re-routed after a replica failure, by outcome "
    "(resumed = resubmitted to a surviving replica; exhausted = retry "
    "budget spent, surfaced as UNAVAILABLE + retry-after)",
    ("model", "outcome"),
)

# -- SLO autoscaler (serving/autoscale.py, docs/RUNBOOK.md §8) -------------
# ``action`` and ``cause`` are the CLOSED autoscale.ACTIONS / CAUSES
# enums; the controller pre-registers every (action, cause) child by
# iterating both tuples at construction (the SLO-objectives pattern), so
# a new action is a reviewed enum change, not a stray label value.

AUTOSCALE_ACTIONS = Counter(
    "aios_tpu_autoscale_actions_total",
    "SLO-burn autoscaler actions (action=scale_up|scale_down|degrade|"
    "restore off the windowed burn rate; cause=burn|ceiling|recovery|"
    "kill_switch). Every action also lands on the flight recorder's "
    "model lane with level/replica evidence",
    ("model", "action", "cause"),
)

# -- device-time attribution (obs/devprof.py, docs/OBSERVABILITY.md) -------
# Armed by AIOS_TPU_DEVPROF; every series' ``graph`` label is drawn from
# the CLOSED devprof.GRAPH_KINDS enum (the engine registers the children
# by iterating it — the SLO-objectives pattern), and all per-graph
# series are monotonic ledger counters read at scrape time, SUMMED over
# the per-model WeakSet of live replica ledgers (set_function is
# last-writer-wins — the aios_tpu_prefix_host_* lesson). Only the
# tenant counter is a true Counter, and it carries the tenant label
# ALONE (the quota-metric precedent: a tenant x model product is
# unbounded; the per-model breakdown lives in /debug/devprof JSON).

DEVPROF_DISPATCHES = Gauge(
    "aios_tpu_devprof_dispatches_total",
    "Device dispatches per serving-graph kind (graph in the closed "
    "devprof.GRAPH_KINDS enum; monotonic, summed over replica ledgers)",
    ("model", "graph"),
)
DEVPROF_DEVICE_SECONDS = Gauge(
    "aios_tpu_devprof_device_seconds_total",
    "Estimated device-busy seconds per graph kind: mean sampled "
    "completion time extrapolated over all dispatches (monotonic-ish, "
    "summed over replica ledgers; raw even when the roofline is unknown)",
    ("model", "graph"),
)
DEVPROF_MFU = Gauge(
    "aios_tpu_devprof_mfu_ratio",
    "Model FLOPs utilization per graph kind: static cost_analysis FLOPs "
    "of sampled dispatches / sampled seconds / the device_kind's peak "
    "FLOP/s (docs/HARDWARE.md roofline table; omitted on unknown kinds)",
    ("model", "graph"),
)
DEVPROF_HBM_UTIL = Gauge(
    "aios_tpu_devprof_hbm_bandwidth_utilization_ratio",
    "HBM bandwidth utilization per graph kind: cost_analysis bytes of "
    "sampled dispatches / sampled seconds / the device_kind's peak "
    "HBM bytes/s (docs/HARDWARE.md; omitted on unknown kinds)",
    ("model", "graph"),
)
DEVPROF_TENANT_SECONDS = Counter(
    "aios_tpu_devprof_tenant_device_seconds_total",
    "Estimated device-seconds billed per tenant at request retirement "
    "(timeline attribution: per-dispatch ledger means split by batch "
    "occupancy + measured prefill time; per-model detail in "
    "/debug/devprof)",
    ("tenant",),
)

# -- fleet telemetry plane (obs/fleet.py, docs/OBSERVABILITY.md) -----------
# Every series is labeled (host, role) — host ids are one-per-process
# (bounded by fleet size, never per-request), and the transitions
# counter's ``state`` label is the CLOSED fleet.MEMBER_STATES enum
# (up|suspect|dead); the registry pre-registers every (host, role,
# state) child by iterating the tuple when a member is first seen (the
# autoscale/SLO registration pattern).

FLEET_MEMBER_UP = Gauge(
    "aios_tpu_fleet_member_up_total",
    "1 while the member's heartbeat is fresh, 0 once the failure "
    "detector marks it suspect/dead (the fleet 'up' boolean, per host "
    "and role)",
    ("host", "role"),
)
FLEET_TRANSITIONS = Counter(
    "aios_tpu_fleet_member_transitions_total",
    "Membership state-machine edges by destination state (state in the "
    "closed fleet.MEMBER_STATES enum: up|suspect|dead; every edge also "
    "lands in the transition journal and on the fleet recorder lane)",
    ("host", "role", "state"),
)
FLEET_SCRAPE_FAILURES = Counter(
    "aios_tpu_fleet_scrape_failures_total",
    "Federation/stitch fetches of a live member's endpoint that failed "
    "(the host drops out of that /metrics/fleet response — absence plus "
    "this counter is the signal)",
    ("host", "role"),
)
FLEET_KVX_PAGES = Counter(
    "aios_tpu_fleet_kvx_pages_total",
    "HostPageStore entries shipped over the fleet transfer plane, by "
    "direction (closed kvx.KVX_DIRECTIONS enum: push = prefill host "
    "streaming pages out, pull = decode host fetching on miss)",
    ("model", "direction"),
)
FLEET_KVX_BYTES = Counter(
    "aios_tpu_fleet_kvx_bytes_total",
    "Payload bytes shipped over the fleet transfer plane, by direction "
    "(same closed direction enum as the pages counter; packed wire "
    "bytes, crc envelopes excluded)",
    ("model", "direction"),
)
FLEET_KVX_FAILURES = Counter(
    "aios_tpu_fleet_kvx_failures_total",
    "Transfers that failed and fell back to local prefill, by cause "
    "(closed kvx.KVX_FAIL_CAUSES enum — crc_mismatch is the receiving "
    "end of the verified-at-both-ends contract rejecting a payload)",
    ("model", "cause"),
)
FLEET_ROUTE = Counter(
    "aios_tpu_fleet_route_total",
    "Fleet-level routing decisions by reason (closed "
    "router.FLEET_ROUTE_REASONS enum: the sticky -> overlap -> "
    "least-loaded ladder extended fleet-wide, plus the disagg handoff "
    "outcomes)",
    ("model", "reason"),
)
FLEET_PEER_BREAKER = Gauge(
    "aios_tpu_fleet_peer_breaker_state_total",
    "Per-peer circuit-breaker state as an index into the closed "
    "breaker.BREAKER_STATES enum (0=closed, 1=open, 2=half_open; "
    "anything non-zero means the peer is quarantined — routed around "
    "until consecutive successful probes clear it). host is the "
    "OBSERVING side of the edge",
    ("host", "peer"),
)
FLEET_ANNOUNCE_FAILURES = Counter(
    "aios_tpu_fleet_announce_failures_total",
    "Heartbeat announces that never got a reply, per peer address — "
    "a climbing single-peer count with members still up is the "
    "asymmetric-partition signature (RUNBOOK §11)",
    ("peer",),
)

# -- process identity (obs/fleet.py stamp, every metrics endpoint) ---------

PROCESS_INFO = Gauge(
    "aios_tpu_process_info",
    "Process identity info-gauge (constant 1): host id, multihost rank, "
    "service role, package version — joins federated scrapes and bench "
    "captures to the process that produced them",
    ("host", "rank", "role", "version"),
)

# -- fault injection (aios_tpu/faults/, docs/FAULTS.md) --------------------

FAULTS_INJECTED = Counter(
    "aios_tpu_faults_injected_total",
    "Faults fired by the seeded injection layer (point = injection-point "
    "name from faults.POINTS, mode = nth|prob|after)",
    ("point", "mode"),
)

# -- black-box time series (obs/tsdb.py, docs/OBSERVABILITY.md) ------------
# Armed by AIOS_TPU_TSDB; the ring samples every registered instrument,
# including this family (its own bookkeeping is three series — noise-
# free). The queries counter's ``verb`` label is the CLOSED
# tsdb.QUERY_VERBS enum, pre-registered by iterating the tuple at ring
# construction (the autoscale/SLO registration pattern); the series /
# dropped gauges are fn-backed live state (monotonic for dropped).

TSDB_SAMPLES = Counter(
    "aios_tpu_tsdb_sample_passes_total",
    "Sampler passes completed (one pass reads the whole registry and "
    "appends one point per live series)",
)
TSDB_SERIES = Gauge(
    "aios_tpu_tsdb_series_total",
    "Series currently tracked by the ring (scrape-time; bounded by "
    "AIOS_TPU_TSDB_MAX_SERIES)",
)
TSDB_DROPPED = Gauge(
    "aios_tpu_tsdb_dropped_series_total",
    "Distinct series refused by the cardinality cap (monotonic, "
    "scrape-time) — the no-silent-truncation contract: a non-zero value "
    "means the ring is blind to that many series",
)
TSDB_QUERIES = Counter(
    "aios_tpu_tsdb_queries_total",
    "/debug/tsdb expressions evaluated, by verb (the closed "
    "tsdb.QUERY_VERBS enum: raw|rate|avg|min|max|p50|p90|p95|p99)",
    ("verb",),
)

# -- incident bundles (obs/incidents.py, docs/OBSERVABILITY.md) ------------
# ``cause`` is the CLOSED incidents.TRIGGER_CAUSES enum, pre-registered
# by iterating the tuple at store construction; suppressed counts the
# per-(model, cause) cooldown swallowing a trigger burst — fired +
# suppressed is the true trigger rate.

INCIDENTS = Counter(
    "aios_tpu_incidents_total",
    "Incident bundles frozen, by trigger cause (closed "
    "incidents.TRIGGER_CAUSES enum; each bundle = tsdb window + "
    "flightrec snapshot + fault journal + devprof + lock-watchdog "
    "state, served at /debug/incidents)",
    ("cause",),
)
INCIDENTS_SUPPRESSED = Counter(
    "aios_tpu_incidents_suppressed_total",
    "Triggers swallowed by the per-(model, cause) cooldown — a burst "
    "freezes exactly one bundle; this counter keeps the rest visible",
    ("cause",),
)

# -- orchestrator ----------------------------------------------------------

GOAL_TASKS = Counter(
    "aios_tpu_goal_tasks_total",
    "Task outcomes recorded by the result aggregator (outcome=success|failure)",
    ("outcome",),
)
GOAL_TASK_TOKENS = Counter(
    "aios_tpu_goal_task_tokens_total",
    "Model tokens consumed by recorded task outcomes",
)
GOAL_TASK_DURATION = Histogram(
    "aios_tpu_goal_task_duration_seconds",
    "Wall time of recorded task outcomes",
)
DECISIONS = Counter(
    "aios_tpu_decisions_total",
    "Decisions logged, by intelligence level",
    ("level",),
)
SCHEDULER_FIRED = Counter(
    "aios_tpu_scheduler_fired_total",
    "Cron schedules fired into goal submission",
)
ROUTER_TASKS = Counter(
    "aios_tpu_router_tasks_total",
    "Task routing outcomes (outcome=routed|ai_path|no_capable_agent)",
    ("outcome",),
)

# -- agents ----------------------------------------------------------------

AGENT_RESTARTS = Counter(
    "aios_tpu_agent_restarts_total",
    "Agent child-process restarts by the spawner",
    ("agent",),
)

# -- api gateway -----------------------------------------------------------

GATEWAY_SPEND = Counter(
    "aios_tpu_gateway_spend_usd_total",
    "Cloud spend recorded against provider budgets (USD)",
    ("provider",),
)
GATEWAY_TOKENS = Counter(
    "aios_tpu_gateway_tokens_total",
    "Cloud tokens by provider and direction (input|output)",
    ("provider", "direction"),
)

# -- memory tiers ----------------------------------------------------------

MEMORY_TIER_LOOKUPS = Counter(
    "aios_tpu_memory_tier_lookups_total",
    "Tier lookups (tier=operational|working|longterm|knowledge, "
    "result=hit|miss)",
    ("tier", "result"),
)

# -- tools -----------------------------------------------------------------

TOOL_INVOCATIONS = Counter(
    "aios_tpu_tool_invocations_total",
    "Tool executions recorded in the audit ledger (outcome=success|failure)",
    ("tool", "outcome"),
)
