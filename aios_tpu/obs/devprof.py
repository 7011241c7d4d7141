"""Device-time attribution: per-graph cost ledger + sampled dispatch timing.

Every observability layer before this one was host-side by construction
(the PR 8 flight recorder stamps *when* a dispatch was submitted, the
PR 9 analyzer proves *who may* dispatch) — none of them could say what a
dispatch COST on the device. Production engines attribute device time
per kernel/graph to drive capacity and regression decisions (RTP-LLM,
PAPERS.md); this module closes that gap for the serving plane:

  * **Per-graph cost ledger** — every AOT-compiled serving graph
    (prefill buckets, decode steps, masked/jump/spec/draft/verify,
    restore, the seq-sharded twins) registers at warmup/attach with the
    static ``compiled.cost_analysis()`` FLOPs + bytes estimates and its
    compile seconds, keyed by the CLOSED :data:`GRAPH_KINDS` enum (the
    same kind strings as ``aios_tpu_engine_xla_compiles_total``); every
    dispatch increments that graph kind's counters.
  * **Sampled device timing** — every Nth dispatch
    (``AIOS_TPU_DEVPROF_SAMPLE``, default 32) the dispatch site times
    completion via a block-until-ready delta; the decode dispatch worker
    samples ONLY when the depth-2 double buffer has slack (no second
    dispatch queued behind it), so the pipeline never stalls for a
    measurement. Samples feed per-graph device-seconds plus derived MFU
    and HBM-bandwidth-utilization gauges against the per-``device_kind``
    peaks in docs/HARDWARE.md (the roofline source of truth); an unknown
    device kind omits the utilization gauges and keeps raw seconds.
  * **Per-request / per-tenant attribution** — sampled device-µs join
    the flight recorder's dispatch events, timelines total estimated
    device-seconds (``Timeline.device_us``), and the batcher bills
    ``aios_tpu_devprof_tenant_device_seconds_total`` at retirement — the
    accounting primitive per-tenant cost and capacity need.
  * **On-demand capture** — ``/debug/profile?secs=N`` (obs/http.py) runs
    a bounded, one-at-a-time ``jax.profiler`` trace into
    ``AIOS_TPU_DEVPROF_DUMP_DIR`` (409 while one is running, hard cap
    :data:`CAPTURE_MAX_SECS`, disabled unless the dump dir is set).

Everything is OFF by default and compiled into the hot paths as the
same near-zero-cost no-op pattern as ``aios_tpu/faults``: the engine
holds ``self._devprof = None`` unless ``AIOS_TPU_DEVPROF`` armed it at
construction, and every hot-path touch is one attribute ``None`` check.
With devprof ON, token streams, dispatch counts, and compile counters
are identical to OFF (tests/test_devprof.py pins it — the PR 6/7/8
invariant, extended).

Timing caveat: a sample measures graph-call start -> result-ready on the
host, which on the TPU backend is device execution plus dispatch/readback
overhead (an upper bound on device busy time) and on the CPU backend is
exact (XLA executes inline). Restore and mid-chunk samples are
submit-side (their scatters are deliberately async) — documented per
kind in docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
import weakref
from typing import Dict, Optional, Tuple

from ..analysis.locks import make_lock

log = logging.getLogger("aios.obs")

__all__ = [
    "GRAPH_KINDS", "DEVICE_PEAKS", "CAPTURE_MAX_SECS", "DevprofLedger",
    "CaptureBusy", "CaptureDisabled", "enabled", "sample_every",
    "local_device_kind", "ledgers_for", "snapshot_all", "start_capture",
    "capture_status",
]

# The CLOSED enum of serving-graph kinds — one entry per XLA graph
# family the engine compiles (the ``kind`` strings of
# aios_tpu_engine_xla_compiles_total). Ledger call sites must use these
# literals (tests/test_obs_lint.py checks every ``_devprof_note`` call
# site on the AST); :meth:`DevprofLedger.register` rejects anything
# else, so a new graph family is a reviewed enum change, not a stray
# string growing the ``graph`` label set.
GRAPH_KINDS = (
    "step",          # plain decode (the dispatch-worker path)
    "masked",        # grammar-masked 1-step decode
    "prefill",       # whole-prompt prefill buckets
    "seq_prefill",   # sequence-sharded (sp-axis) prefill twins
    "chunk",         # chunked-admission mid/final chunks
    "spec",          # n-gram speculative verify rounds
    "draft_spec",    # fused draft-model propose+verify rounds
    "draft_ingest",  # bulk draft-KV catch-up writes
    "jump",          # grammar jump-ahead multi-token verify
    "restore",       # host-tier KV restore scatters
    "hist",          # prefix-hit history backfill
)

# Published per-chip peaks, keyed by jax ``device_kind``: (dense bf16
# FLOP/s, HBM bytes/s). docs/HARDWARE.md holds the same table and is the
# ROOFLINE SOURCE OF TRUTH — update both together. An unmatched kind
# (CPU backend, future chips) keeps raw device-seconds and omits the
# MFU / HBM-utilization gauges rather than inventing a denominator.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}

# /debug/profile hard cap: a profiler trace buffers device events in
# memory and stalls nothing, but an unbounded capture would grow until
# the operator remembers it — 60 s covers any realistic triage window.
CAPTURE_MAX_SECS = 60.0

_DEFAULT_SAMPLE_EVERY = 32


def enabled() -> bool:
    """Whether ``AIOS_TPU_DEVPROF`` arms the ledger (read at ENGINE
    CONSTRUCTION — arming is a per-engine decision, like the pipeline
    knob, so a live engine never grows instrumentation mid-serving)."""
    return os.environ.get("AIOS_TPU_DEVPROF", "").lower() in (
        "1", "on", "true", "yes"
    )


def sample_every() -> int:
    """``AIOS_TPU_DEVPROF_SAMPLE``: time every Nth dispatch (default
    32, floor 1 = every dispatch; the lenient-env convention)."""
    raw = os.environ.get("AIOS_TPU_DEVPROF_SAMPLE", "").strip()
    if not raw:
        return _DEFAULT_SAMPLE_EVERY
    try:
        return max(int(raw), 1)
    except ValueError:
        log.warning(
            "AIOS_TPU_DEVPROF_SAMPLE=%r ignored (expected a positive "
            "integer)", raw,
        )
        return _DEFAULT_SAMPLE_EVERY


def local_device_kind() -> str:
    """The jax ``device_kind`` of device 0, or "" when no backend is
    reachable (devprof then keeps raw seconds, no roofline)."""
    try:
        import jax

        return str(getattr(jax.devices()[0], "device_kind", ""))
    except Exception as exc:  # noqa: BLE001 - obs must not break loading
        log.warning("devprof: no jax backend for device_kind (%s)", exc)
        return ""


def resolve_peaks(device_kind: str) -> Optional[Tuple[float, float]]:
    """(peak FLOP/s, peak HBM bytes/s) for a device kind, or None when
    the kind is not in the table (utilization gauges are then omitted).
    Exact ``device_kind`` keys only: a chip the table does not list gets
    no denominator, never a neighbour's."""
    return DEVICE_PEAKS.get(device_kind)


def _cost_of(compiled) -> Optional[Tuple[float, float]]:
    """(flops, bytes) per dispatch from an AOT-compiled executable's
    static cost analysis; None when the backend provides nothing usable
    (the ledger then keeps dispatch counts and timing, no roofline)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - optional metadata, backend-dependent
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    byt = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and byt <= 0.0:
        return None
    return (flops, byt)


class _GraphStat:
    """Per-graph-kind accumulators. ``sampled_*`` sum over the sampled
    dispatches only; the estimated total device time extrapolates their
    mean over every dispatch."""

    __slots__ = (
        "dispatches", "est_flops", "est_bytes", "compiles",
        "compile_seconds", "samples", "sampled_seconds", "sampled_flops",
        "sampled_bytes",
    )

    def __init__(self) -> None:
        self.dispatches = 0
        self.est_flops = 0.0
        self.est_bytes = 0.0
        self.compiles = 0
        self.compile_seconds = 0.0
        self.samples = 0
        self.sampled_seconds = 0.0
        self.sampled_flops = 0.0
        self.sampled_bytes = 0.0


# per-model WeakSets of live ledgers (one per replica engine): the
# scrape gauges and /debug/devprof SUM over them (the
# aios_tpu_prefix_host_* aggregation lesson — set_function is
# last-writer-wins across replicas). Plain lock: registration happens at
# engine construction / debug reads only, never on a dispatch path.
_LEDGERS: Dict[str, "weakref.WeakSet[DevprofLedger]"] = {}
_reg_lock = threading.Lock()


def ledgers_for(model: str) -> "weakref.WeakSet[DevprofLedger]":
    with _reg_lock:
        return _LEDGERS.setdefault(model, weakref.WeakSet())


class DevprofLedger:
    """One engine's device-time ledger: per-graph dispatch counters,
    static cost estimates, and sampled completion timings. All methods
    are O(1) dict work under the ledger's own lock — never a dispatch,
    readback, or RPC (the analyzer's devprof lock declaration)."""

    def __init__(self, model: str, device_kind: Optional[str] = None,
                 sample_n: Optional[int] = None) -> None:
        self.model = model
        self.device_kind = (
            device_kind if device_kind is not None else local_device_kind()
        )
        self.peaks = resolve_peaks(self.device_kind)
        self.sample_n = sample_n if sample_n is not None else sample_every()
        self._lock = make_lock("devprof")
        self._graphs: Dict[str, _GraphStat] = {}  #: guarded_by _lock
        # (kind, graph-store key) -> (flops, bytes) per dispatch
        self._costs: Dict[Tuple[str, object], Tuple[float, float]] = {}  #: guarded_by _lock
        self._backlog = 0  #: guarded_by _lock
        self._last: Optional[Tuple[str, float]] = None  #: guarded_by _lock
        ledgers_for(model).add(self)

    # -- registration (warmup / attach) ------------------------------------

    def register(self, kind: str, key, compiled, compile_s: float) -> None:
        """Record one AOT-compiled graph: its compile time and the
        static cost estimate the dispatch counters will charge per
        dispatch. ``kind`` must be a :data:`GRAPH_KINDS` member."""
        if kind not in GRAPH_KINDS:
            raise ValueError(
                f"unknown devprof graph kind {kind!r} (closed enum "
                f"GRAPH_KINDS — extend it with review)"
            )
        cost = _cost_of(compiled) if compiled is not None else None
        with self._lock:
            g = self._graphs.setdefault(kind, _GraphStat())
            g.compiles += 1
            g.compile_seconds += float(compile_s)
            if cost is not None:
                self._costs[(kind, key)] = cost

    # -- hot path ----------------------------------------------------------

    def note(self, kind: str, key=None) -> bool:
        """Count one dispatch of ``kind``; True when this dispatch is
        due a timing sample (the 1st, then every Nth)."""
        with self._lock:
            g = self._graphs.setdefault(kind, _GraphStat())
            g.dispatches += 1
            cost = self._costs.get((kind, key))
            if cost is not None:
                g.est_flops += cost[0]
                g.est_bytes += cost[1]
            return (g.dispatches - 1) % self.sample_n == 0

    def sample(self, kind: str, key, secs: float) -> None:
        """Land one completion-timing sample for ``kind``."""
        with self._lock:
            g = self._graphs.setdefault(kind, _GraphStat())
            g.samples += 1
            g.sampled_seconds += secs
            cost = self._costs.get((kind, key))
            if cost is not None:
                g.sampled_flops += cost[0]
                g.sampled_bytes += cost[1]
            self._last = (kind, secs)

    def take_last_sample(self) -> Optional[Tuple[str, float]]:
        """Pop the most recent (kind, seconds) sample — the batcher
        joins it onto the flight-recorder event of the dispatch it just
        issued (all dispatches of one batcher are scheduler-thread
        sequential, so last-sample is that dispatch's or None)."""
        with self._lock:
            last, self._last = self._last, None
            return last

    # dispatch-worker backlog (the depth-2 double buffer): the worker
    # samples only when nothing is queued behind it, so a measurement
    # never delays the next dispatch's submission.

    def enqueue(self) -> None:
        with self._lock:
            self._backlog += 1

    def dequeue(self) -> None:
        with self._lock:
            self._backlog = max(self._backlog - 1, 0)

    def queue_depth(self) -> int:
        with self._lock:
            return self._backlog

    # -- reads -------------------------------------------------------------

    def mean_s(self, kind: str) -> Optional[float]:
        """Mean sampled device-seconds per dispatch of ``kind`` (None
        before the first sample) — the per-request attribution rate."""
        with self._lock:
            g = self._graphs.get(kind)
            if g is None or not g.samples:
                return None
            return g.sampled_seconds / g.samples

    def totals(self, kind: str) -> Tuple[float, float, float, float, float,
                                         float, float]:
        """(dispatches, est_flops, est_bytes, samples, sampled_seconds,
        sampled_flops, sampled_bytes) for gauge aggregation across
        replica ledgers."""
        with self._lock:
            g = self._graphs.get(kind)
            if g is None:
                return (0.0,) * 7
            return (
                float(g.dispatches), g.est_flops, g.est_bytes,
                float(g.samples), g.sampled_seconds, g.sampled_flops,
                g.sampled_bytes,
            )

    def device_seconds(self, kind: str) -> float:
        """Estimated total device-busy seconds for ``kind``: mean
        sampled completion time extrapolated over every dispatch."""
        with self._lock:
            g = self._graphs.get(kind)
            if g is None or not g.samples:
                return 0.0
            return g.sampled_seconds / g.samples * g.dispatches

    def snapshot(self) -> dict:
        """The ledger as JSON-shaped dict (bench_devprof /
        /debug/devprof): one entry per graph kind that dispatched or
        compiled, with utilization only where the roofline is known."""
        with self._lock:
            graphs = {k: g for k, g in self._graphs.items()
                      if g.dispatches or g.compiles}
            out: dict = {
                "model": self.model,
                "device_kind": self.device_kind,
                "sample_every": self.sample_n,
                "graphs": {},
            }
            for kind in GRAPH_KINDS:
                g = graphs.get(kind)
                if g is None:
                    continue
                entry: dict = {
                    "dispatches": g.dispatches,
                    "compiles": g.compiles,
                    "compile_seconds": round(g.compile_seconds, 4),
                    "est_flops": g.est_flops,
                    "est_bytes": g.est_bytes,
                    "samples": g.samples,
                    "sampled_seconds": round(g.sampled_seconds, 6),
                }
                if g.samples:
                    per = g.sampled_seconds / g.samples
                    entry["device_seconds_per_dispatch"] = round(per, 6)
                    entry["device_seconds"] = round(per * g.dispatches, 4)
                if self.peaks is not None and g.sampled_seconds > 0:
                    # 4 significant digits, NOT round(x, 4): a CPU-run
                    # ratio against a TPU roofline is ~1e-10 and a fixed
                    # decimal rounding would zero it out of the JSON
                    pf, pb = self.peaks
                    if g.sampled_flops:
                        entry["mfu"] = float(
                            f"{g.sampled_flops / g.sampled_seconds / pf:.4g}"
                        )
                    if g.sampled_bytes:
                        entry["hbm_bw_util"] = float(
                            f"{g.sampled_bytes / g.sampled_seconds / pb:.4g}"
                        )
                out["graphs"][kind] = entry
            return out


def snapshot_all(model: str = "") -> dict:
    """Every live ledger's snapshot, grouped per model (the
    /debug/devprof payload; replica ledgers list separately — the
    metric gauges do the summing)."""
    with _reg_lock:
        items = {
            m: list(s) for m, s in _LEDGERS.items()
            if (not model or m == model)
        }
    return {
        "capture": capture_status(),
        "models": {
            m: [led.snapshot() for led in leds]
            for m, leds in items.items() if leds
        },
    }


# -- on-demand profiler capture (/debug/profile) ----------------------------

class CaptureBusy(RuntimeError):
    """A capture is already running (HTTP 409)."""


class CaptureDisabled(RuntimeError):
    """AIOS_TPU_DEVPROF_DUMP_DIR is not set (HTTP 403)."""


_capture_lock = threading.Lock()  # capture start/stop only, never hot-path
_capture = {"busy": False, "path": "", "started": 0.0, "secs": 0.0}


def capture_status() -> dict:
    with _capture_lock:
        return dict(_capture)


def start_capture(secs: float) -> dict:
    """Start a bounded ``jax.profiler`` trace into
    ``AIOS_TPU_DEVPROF_DUMP_DIR`` on a daemon thread; one at a time.
    Returns {path, secs}; raises :class:`CaptureDisabled` /
    :class:`CaptureBusy`. ``secs`` clamps to (0, CAPTURE_MAX_SECS]."""
    dump_dir = os.environ.get("AIOS_TPU_DEVPROF_DUMP_DIR", "").strip()
    if not dump_dir:
        raise CaptureDisabled(
            "profiler capture disabled: set AIOS_TPU_DEVPROF_DUMP_DIR"
        )
    if "jax" not in sys.modules:
        # only the process that holds the chip can trace it; a service
        # that never imported JAX must not open the device to find out
        raise CaptureDisabled(
            "profiler capture disabled: this process does not run JAX"
        )
    secs = min(max(float(secs), 0.05), CAPTURE_MAX_SECS)
    with _capture_lock:
        if _capture["busy"]:
            raise CaptureBusy(
                f"capture already running ({_capture['path']}, "
                f"{_capture['secs']:g}s)"
            )
        path = os.path.join(dump_dir, f"devprof-{int(time.time())}")
        _capture.update(
            busy=True, path=path, started=time.time(), secs=secs
        )

    def run() -> None:
        try:
            import jax

            os.makedirs(path, exist_ok=True)
            with jax.profiler.trace(path):
                time.sleep(secs)
            log.warning("devprof capture (%.2fs) -> %s", secs, path)
        except Exception:  # noqa: BLE001 - capture must never crash serving
            log.exception("devprof capture failed")
        finally:
            with _capture_lock:
                _capture["busy"] = False

    threading.Thread(
        target=run, name="devprof-capture", daemon=True
    ).start()
    return {"profiling": True, "path": path, "secs": secs}
