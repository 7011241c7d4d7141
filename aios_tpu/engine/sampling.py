"""On-device token sampling: temperature, top-k, top-p, greedy.

Runs inside the jitted decode step (no host round-trip per token), vectorized
over slots with *per-slot* sampling parameters — different agents' requests in
the same continuous batch can use different temperatures (the reference's
per-request `temperature` field, runtime.proto InferRequest).

Replaces llama-server's sampler chain for the parameters the reference
actually exposes (temperature; plus top-k/top-p which llama-server applies
with its defaults — inference.rs:103-112 sends temperature only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GREEDY_EPS = 1e-4  # temperatures below this mean argmax


def top_p_filter(logits: jnp.ndarray, top_p: jnp.ndarray) -> jnp.ndarray:
    """Mask logits outside the nucleus. logits [B, V], top_p [B] in (0, 1]."""
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    # keep tokens while the cumulative mass *before* them is < top_p
    keep_sorted = (cumulative - sorted_probs) < top_p[:, None]
    # threshold = smallest logit still kept
    kept_logits = jnp.where(keep_sorted, sorted_logits, jnp.inf)
    threshold = jnp.min(kept_logits, axis=-1, keepdims=True)
    return jnp.where(logits >= threshold, logits, -jnp.inf)


def top_k_filter(logits: jnp.ndarray, top_k: jnp.ndarray) -> jnp.ndarray:
    """Mask logits below the k-th largest. top_k [B] int32 (0 = disabled)."""
    V = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    k = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))
    threshold = jnp.take_along_axis(sorted_logits, (k - 1)[:, None], axis=-1)
    return jnp.where(logits >= threshold, logits, -jnp.inf)


# Candidate pool for the decode-loop sampler. A full-vocab sort per step is
# the naive approach and measurably slow on TPU; restricting top-p to the 64
# highest logits matches llama.cpp's own sampler chain, which applies
# top-k 40 *before* top-p by default (the reference sends temperature only,
# inference.rs:103-112, so llama-server uses those defaults).
# AIOS_TPU_SAMPLE_POOL overrides the pool size (read at trace time, so it
# must be set before the decode graph first compiles).
DEFAULT_TOPK_CAP = 64


def topk_cap() -> int:
    import os

    raw = os.environ.get("AIOS_TPU_SAMPLE_POOL", "")
    if not raw:
        return DEFAULT_TOPK_CAP
    try:
        cap = int(raw)
    except ValueError:
        raise ValueError(
            f"AIOS_TPU_SAMPLE_POOL={raw!r} is not an integer"
        ) from None
    if cap < 1:
        # fail loudly: 0 is NOT "disabled" here (that would put a full-vocab
        # sort in the decode graph); a silent pool of 1 would make all
        # sampling greedy
        raise ValueError("AIOS_TPU_SAMPLE_POOL must be >= 1")
    return cap


def sample(
    logits: jnp.ndarray,  # [B, V] fp32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]; 1.0 keeps the whole candidate pool (the pool
    # itself is still capped, see below — NOT a full-vocab nucleus)
    top_k: jnp.ndarray | None = None,  # [B] int32; 0 => the whole pool
    exact: bool = False,  # exact top-k pool (grammar-masked steps)
) -> jnp.ndarray:
    """Sample one token per row; temperature < GREEDY_EPS rows take argmax.

    Nucleus + top-k filtering run on the ``topk_cap()`` highest logits via
    ``lax.top_k`` — no full-vocab sort in the decode graph. Consequently the
    candidate pool is capped: top_k values above the cap (or 0, "disabled")
    sample from the best ``topk_cap()`` tokens, and top-p mass beyond them is
    truncated — even at top_p=1.0 — matching llama-server, whose default
    chain applies top-k 40 before top-p. Raise AIOS_TPU_SAMPLE_POOL if a
    deployment needs a wider nucleus.
    """
    B, V = logits.shape
    K = min(topk_cap(), V)
    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(temperature, GREEDY_EPS)[:, None]
    # approx_max_k hits the TPU-optimized partial-reduction path (~16%
    # faster whole-step decode on Mistral-7B batch 8 vs exact lax.top_k over
    # the 32k vocab); on CPU it lowers to the exact sort, so tests are
    # deterministic. Missing a tail candidate with ~5% probability is well
    # within the tolerance of a sampling pool (llama.cpp's own chain
    # truncates harder, top-k 40). Results come back sorted descending.
    if exact:
        # Grammar-constrained steps MUST use the exact pool: the additive
        # mask can leave only a handful of allowed tokens (sometimes just
        # EOS), and approx_max_k's ~5% per-token miss rate could build a
        # pool with zero allowed entries — softmax over uniform -1e30s
        # would then emit a forbidden token and break the JSON guarantee.
        vals, idx = jax.lax.top_k(logits / temp, K)
    else:
        vals, idx = jax.lax.approx_max_k(
            logits / temp, K, recall_target=0.95
        )  # [B, K] sorted desc
    if top_k is not None:
        kk = jnp.where(top_k <= 0, K, jnp.minimum(top_k, K))
        pos = jnp.arange(K)[None, :]
        vals = jnp.where(pos < kk[:, None], vals, -jnp.inf)
    probs = jax.nn.softmax(vals, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    keep = (cumulative - probs) < top_p[:, None]
    vals = jnp.where(keep, vals, -jnp.inf)
    choice = jax.random.categorical(key, vals, axis=-1)  # [B] in [0, K)
    sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]

    return jnp.where(temperature < GREEDY_EPS, greedy, sampled).astype(jnp.int32)
