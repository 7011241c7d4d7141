"""The least bytes and operations a step needs, from shapes alone.

These are LOWER bounds on the work of the algorithm, so that no honest
program can read over 100 % of its roofline: every weight byte once at its
stored width, only the embedding rows touched, only the K/V rows of the
active slots, and for a mixture of experts only the experts a step is
expected to select — never all of them.
"""

from __future__ import annotations

from typing import Dict, Sequence

from .weights import Dims

KV_BYTES = 2  # bfloat16 cache, as the configurations state


def _matrix_bytes(k: int, n: int) -> int:
    return k * n + 4 * n  # int8 entries and one float32 scale per column


def expected_distinct_experts(experts: int, top_k: int, tokens: float) -> float:
    """Experts a layer touches for `tokens` tokens when each picks `top_k` of
    `experts` uniformly (seeded random weights): E·(1 − (1 − k/E)^tokens)."""
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def attention_bytes(d: Dims) -> int:
    """Per layer: fused qkv and output projections, and the two norms."""
    return (_matrix_bytes(d.hidden, d.q_dim + 2 * d.kv_dim)
            + _matrix_bytes(d.q_dim, d.hidden) + 2 * 2 * d.hidden)


def ffn_bytes(d: Dims) -> int:
    """One FFN (one expert, for a mixture): gate|up and down."""
    return _matrix_bytes(d.hidden, 2 * d.ffn) + _matrix_bytes(d.ffn, d.hidden)


def decode_step_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least HBM bytes of one decode step for `active` slots whose contexts
    hold `context_rows` rows together."""
    if d.experts:
        ffn = (expected_distinct_experts(d.experts, d.top_k, active) * ffn_bytes(d)
               + 2 * d.hidden * d.experts)  # and the bfloat16 router
    else:
        ffn = ffn_bytes(d)
    weights = d.layers * (attention_bytes(d) + ffn)
    head = _matrix_bytes(d.hidden, d.vocab) + 2 * d.hidden
    embed = active * d.hidden * 2
    kv = context_rows * d.layers * 2 * d.kv_dim * KV_BYTES
    return weights + head + embed + kv


def decode_step_ops(d: Dims, active: float, context_rows: float) -> float:
    per_token = d.layers * 2 * (
        d.hidden * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.hidden
        + (d.top_k if d.experts else 1) * 3 * d.hidden * d.ffn
        + (d.hidden * d.experts if d.experts else 0)
    ) + 2 * d.hidden * d.vocab
    return active * per_token + context_rows * d.layers * 4 * d.q_dim


def prefill_ops(d: Dims, prompt_tokens: Sequence[int], cached_rows: Sequence[int]
                ) -> float:
    """Least operations to admit prompts of these lengths of which the first
    `cached_rows[i]` rows were already in the cache: the matrices for every
    new row, causal attention of each new row over what precedes it, and one
    output-head row per prompt."""
    per_row = d.layers * 2 * (
        d.hidden * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.hidden
        + (d.top_k if d.experts else 1) * 3 * d.hidden * d.ffn
        + (d.hidden * d.experts if d.experts else 0)
    )
    total = 0.0
    for t, c in zip(prompt_tokens, cached_rows):
        new = t - c
        pairs = new * c + new * (new + 1) / 2  # (query, key) pairs under the mask
        total += new * per_row + pairs * d.layers * 4 * d.q_dim
    return total


def prefill_bytes(d: Dims, new_rows: float) -> float:
    """Least HBM bytes of one prefill program: the layers' weights once (for
    a mixture, the experts `new_rows` tokens are expected to select)."""
    if d.experts:
        ffn = expected_distinct_experts(d.experts, d.top_k, new_rows) * ffn_bytes(d)
    else:
        ffn = ffn_bytes(d)
    return d.layers * (attention_bytes(d) + ffn)


def least_seconds(ops: float, bytes_: float, peaks: Dict[str, float],
                  ops_peak: str = "bf16_flops") -> Dict[str, float]:
    by_ops = ops / peaks[ops_peak]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "operations" if by_ops >= by_bytes else "bytes"}
