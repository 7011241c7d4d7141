#!/usr/bin/env bash
# ONE pre-merge gate chaining every cheap self-judging check the tree
# carries (docs/TESTING.md) — run it before pushing a serving-plane
# change and read the first failure:
#
#   1. scripts/analyze.sh        — static concurrency / dispatch /
#                                  knob-docs / metric-catalog analysis
#                                  (exit 1 on any unwaived finding);
#   2. the obs-lint subset       — metric naming, typed families, closed
#                                  enums (tests/test_obs_lint.py);
#   3. bench.py --chaos          — the seeded chaos storm, run twice,
#                                  deterministic or fail (scripts/chaos.sh
#                                  semantics, docs/FAULTS.md); arms cover
#                                  plain, draft-speculation and
#                                  longctx compression;
#   4. the devprof sentinel      — bench.py --devprof captured fresh and
#                                  diffed against the committed
#                                  BASELINE_DEVPROF.json by
#                                  scripts/benchdiff.py: a per-graph
#                                  dispatch-count or device-time
#                                  regression past the budget fails the
#                                  gate (docs/OBSERVABILITY.md
#                                  "Device-time attribution");
#   5. the storm smoke           — bench.py --storm --smoke: the seeded
#                                  trace-driven tenant mix (streaming
#                                  chat + fork-shaped agent families +
#                                  a quota storm) drives the live gRPC
#                                  surface twice and the deterministic
#                                  verdict must be identical and PASS
#                                  (aios_tpu/loadgen/, docs/TESTING.md)
#                                  — every PR is gated under
#                                  contention-realistic load;
#   6. the fleet smoke           — scripts/fleet_smoke.py: two real
#                                  runtime processes on ephemeral ports
#                                  federate /metrics/fleet, stitch one
#                                  trace across the gRPC boundary, and
#                                  one is killed — the up -> suspect ->
#                                  dead journal must be identical across
#                                  two runs (aios_tpu/obs/fleet.py,
#                                  docs/RUNBOOK.md §9);
#   7. the disagg smoke          — scripts/disagg_smoke.py: one prefill
#                                  + two decode processes serve one
#                                  stream through the fleet data plane —
#                                  KV chain pushed over the wire, the
#                                  first decode host killed mid-stream
#                                  (exit 17), the survivor finishes the
#                                  stream token-identically to a solo
#                                  run, and the survivor gossips the
#                                  restored prefix digest; run twice,
#                                  verdicts identical (aios_tpu/fleet/,
#                                  docs/SERVING.md, docs/RUNBOOK.md §10);
#   8. the partition smoke        — scripts/partition_smoke.py: three
#                                  processes under seeded PER-EDGE
#                                  network faults — an asymmetric
#                                  partition walks one host to dead and
#                                  back while the reverse edge stays
#                                  clean, a handoff severs mid-stream
#                                  into quarantine + resume, federation
#                                  probes heal the breaker, and a
#                                  graceful drain re-hands a live stream
#                                  and exits 0 — token-identical to solo,
#                                  run twice, verdicts identical
#                                  (aios_tpu/faults/net.py,
#                                  aios_tpu/fleet/breaker.py,
#                                  aios_tpu/fleet/drain.py,
#                                  docs/FAULTS.md, docs/RUNBOOK.md §11);
#   9. the incident smoke         — scripts/incident_smoke.py: two
#                                  processes with the tsdb ring +
#                                  incident store armed, one seeded with
#                                  a fault storm — the fired crash must
#                                  freeze an incident bundle carrying
#                                  the fault journal AND a non-empty
#                                  tsdb window, /debug/tsdb/fleet must
#                                  federate both hosts, and fleetctl
#                                  history must exit 0; run twice,
#                                  verdicts identical
#                                  (aios_tpu/obs/tsdb.py,
#                                  aios_tpu/obs/incidents.py,
#                                  docs/OBSERVABILITY.md,
#                                  docs/RUNBOOK.md §12).
#
# The devprof threshold here is looser than benchdiff's default: the
# committed baseline was captured on a different run of a noisy shared-
# CPU container, so only gross per-graph timing regressions (and ANY
# deterministic dispatch-count inflation past the same budget) fail.
# Same-machine A/Bs should diff two fresh captures at the default 0.15.
#
# Usage:
#   scripts/preflight.sh                # full gate
#   PREFLIGHT_DEVPROF_THRESHOLD=0.25 scripts/preflight.sh
set -euo pipefail
cd "$(dirname "$0")/.."

threshold="${PREFLIGHT_DEVPROF_THRESHOLD:-0.75}"
workdir="$(mktemp -d)"
trap 'rm -rf "$workdir"' EXIT

echo "[preflight 1/9] static analysis (scripts/analyze.sh)" >&2
scripts/analyze.sh

echo "[preflight 2/9] obs-lint subset (tests/test_obs_lint.py)" >&2
python -m pytest tests/test_obs_lint.py -q -p no:cacheprovider

echo "[preflight 3/9] seeded chaos storm (bench.py --chaos; plain/draft/longctx arms)" >&2
python bench.py --chaos > "$workdir/chaos.json"

echo "[preflight 4/9] devprof sentinel (bench.py --devprof vs" \
     "BASELINE_DEVPROF.json, threshold +${threshold})" >&2
python bench.py --devprof > "$workdir/devprof.json"
python scripts/benchdiff.py BASELINE_DEVPROF.json \
    "$workdir/devprof.json" --threshold "$threshold"

echo "[preflight 5/9] storm smoke (bench.py --storm --smoke," \
     "seeded, run twice, deterministic verdict)" >&2
python bench.py --storm --smoke > "$workdir/storm.json"

echo "[preflight 6/9] fleet smoke (scripts/fleet_smoke.py: two" \
     "processes federate + stitch, one dies, journals identical)" >&2
python scripts/fleet_smoke.py > "$workdir/fleet.json"

echo "[preflight 7/9] disagg smoke (scripts/disagg_smoke.py: prefill" \
     "+ 2 decode processes, kill + resume, token-identical twice)" >&2
python scripts/disagg_smoke.py > "$workdir/disagg.json"

echo "[preflight 8/9] partition smoke (scripts/partition_smoke.py:" \
     "per-edge faults, quarantine, graceful drain, identical twice)" >&2
python scripts/partition_smoke.py > "$workdir/partition.json"

echo "[preflight 9/9] incident smoke (scripts/incident_smoke.py: seeded" \
     "fault storm -> replayable incident bundles, identical twice)" >&2
python scripts/incident_smoke.py > "$workdir/incidents.json"

echo "[preflight] PASS" >&2
