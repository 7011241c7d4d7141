"""The least bytes and operations a step needs, from shapes alone.

These are LOWER bounds on the work of the algorithm, so that no honest
program can read over 100 % of its roofline: every weight byte once at its
stored width, only the embedding rows touched, only the cache rows of the
active slots, and for a mixture of experts only the experts a step is
expected to select — never all of them.

The counts themselves are the architecture's (its file under
`benchmark/archs/`: `decode_step_bytes`, `decode_step_ops`, `prefill_ops`,
`prefill_bytes`, with the bytes of a cache row and the experts held its own);
the readers call them through the run's Context. Here is what every count
shares: a stored matrix's bytes, the experts a step is expected to touch,
and the least time for a number of operations and bytes.
"""

from __future__ import annotations

from typing import Dict


def matrix_bytes(k: int, n: int) -> int:
    return k * n + 4 * n  # int8 entries and one float32 scale per column


def expected_distinct_experts(experts: int, top_k: int, tokens: float) -> float:
    """Experts a layer touches for `tokens` tokens when each picks `top_k` of
    `experts` uniformly (seeded random weights): E·(1 − (1 − k/E)^tokens)."""
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def least_seconds(ops: float, bytes_: float, peaks: Dict[str, float],
                  ops_peak: str = "bf16_flops") -> Dict[str, float]:
    by_ops = ops / peaks[ops_peak]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "operations" if by_ops >= by_bytes else "bytes"}
