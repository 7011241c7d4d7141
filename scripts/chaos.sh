#!/usr/bin/env bash
# Seeded chaos storm against a live 2-replica pool — the pre-merge
# robustness gate (docs/FAULTS.md, docs/TESTING.md), the fault-tolerance
# sibling of scripts/analyze.sh.
#
# Runs bench.py --chaos: the SAME seeded fault schedule (replica
# scheduler crash + probabilistic dispatch delays) against fresh pools
# under a concurrent greedy wave — THREE ARMS (a plain pool; a
# draft-speculation pool with a paired DraftModel + speculative
# batchers; and a longctx pool with window+sink KV compression armed
# and prompts long enough to prune mid-storm), each run twice. Exit is
# NON-ZERO on any stuck request, any aborted stream (transparent
# failover must complete every greedy request), a nondeterministic
# re-run (token streams, terminal states, and the nth-mode
# injected-fault sequence must be identical — including the compressed
# arm's pruned streams), or a draft-arm stream that diverges from the
# plain arm's (speculation may change dispatch counts, never tokens —
# even across a mid-storm crash and the failover-time draft-KV
# rebuild).
#
# Usage:
#   scripts/chaos.sh                 # default seed (42)
#   scripts/chaos.sh --seed 7        # a different storm
#   CHAOS_SEED=7 scripts/chaos.sh    # same, env-style for CI matrices
#
# Reading a failure: the JSON line on stdout carries stuck/aborted
# counts + the nth fault sequence; the flight recorder's crash_respawn
# snapshot (GET /debug/snapshots on a live deployment, or the
# AIOS_TPU_FLIGHTREC_DUMP_DIR files) holds the per-request timelines.
# docs/RUNBOOK.md "chaos drill" walks the live-pool version.
#
# The gate also fails LOUDLY when the fault schedule never fired
# (faults_armed=false in the JSON): an empty faults.fired() journal —
# e.g. a point name mis-spelled during a refactor — used to let the
# storm pass vacuously, proving nothing.
set -euo pipefail
cd "$(dirname "$0")/.."

seed="${CHAOS_SEED:-42}"
if [[ "${1:-}" == "--seed" && -n "${2:-}" ]]; then
  seed="$2"
fi

exec python bench.py --chaos --chaos-seed "$seed"
