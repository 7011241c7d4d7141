"""The benchmark: `python3 benchmark/run.py --workload <cell> ...` (see BENCHMARK.json)."""
