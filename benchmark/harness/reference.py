"""The plain reference: the published forward pass in float32, no kernels,
no cache, no batching, one layer's weights on the device at a time.

It imports nothing of the program and takes nothing the program made: the
weights come from the seed. What is here is common to every architecture:
the layer-major loop, the padding, which rows are kept, the comparison of a
served token with the reference's best, and the few pieces of mathematics
most published blocks share (`rms`, `rope`, `dense`). The forward pass itself
is the architecture's: its file under `benchmark/archs/` supplies

    build_top(d, seed), build_layer(d, seed, l)   the seeded weights
    embed(top, ids) -> x [T, E] float32
    block(d, x, lw, l, precision) -> (x, margin [T])   layer `l`, by its index
    head(d, x, top, precision) -> logits [rows, vocab]

and states its departures from the published model. Matrix products run
under `default_matmul_precision("highest")`, or the TPU would compute them
in bfloat16 passes. `margin` is by how much a router's last choice leads the
first one left out (over all experts of the published router, whatever share
of them is held here), infinite in a layer without a router.

A precision other than "float32" is a CONTROL of "how correct is decided":
the same forward with every matrix re-quantized one step below what the
configuration states (its `check.control`), the step a later PR would be
tempted by. `PRECISIONS` holds the steps there are.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

INT4_GROUP = 128


def int4(w):
    """Symmetric 4-bit re-quantization in groups of INT4_GROUP rows."""
    k, n = w.shape[-2], w.shape[-1]
    g = INT4_GROUP if k % INT4_GROUP == 0 else k
    wg = w.reshape(w.shape[:-2] + (k // g, g, n))
    scale = jnp.max(jnp.abs(wg), axis=-2, keepdims=True) / 7.0
    q = jnp.clip(jnp.round(wg / jnp.where(scale > 0, scale, 1.0)), -8, 7)
    return (q * scale).reshape(w.shape)


# what a matrix is re-quantized by, per precision; a configuration served in
# another type adds the step below it here when it arrives
PRECISIONS = {"float32": None, "int4": int4}


def dense(leaf, precision: str):
    """The float32 matrix a quantized leaf stands for, at `precision`."""
    w = leaf["q"].astype(jnp.float32) * leaf["s"]
    lower = PRECISIONS[precision]
    return lower(w) if lower else w


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding in the half-rotation convention; x [T, heads, D]."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


KEEP = 256  # logits rows kept per sequence: the longest answer fits


def bucket(n: int, step: int = 512) -> int:
    return -(-n // step) * step


def logits_for(arch, d, seed: int, sequences: Sequence[Sequence[int]],
               keep_from: Sequence[int], precisions: Sequence[str] = ("float32",),
               pad_to: int = 0) -> Dict[str, List[np.ndarray]]:
    """Logits of each sequence at positions `keep_from[i]` to its end (at
    most KEEP rows), per precision; under "router_margin" each layer's router
    margin at those positions [rows, layers], in the first precision's own
    forward. `arch` is the architecture's module and `d` its sizes
    (`d.layers` is the one field read here). Layer-major: one layer's weights
    live at a time and serve every sequence. Sequences are padded to `pad_to`
    positions (or to a multiple of 512), which a causal model's earlier
    positions cannot see; one length for a whole cell means one compiled
    block for it."""
    with jax.default_matmul_precision("highest"):
        top = arch.build_top(d, seed)
        xs: Dict[str, list] = {p: [] for p in precisions}
        for seq in sequences:
            ids = np.zeros(max(bucket(len(seq)), pad_to), np.int32)
            ids[:len(seq)] = seq
            x = arch.embed(top, ids)
            for p in precisions:
                xs[p].append(x)
        margins: List[list] = [[] for _ in sequences]
        for l in range(d.layers):
            lw = arch.build_layer(d, seed, l)
            for p in precisions:
                done = [arch.block(d, x, lw, l, p) for x in xs[p]]
                xs[p] = [x for x, _ in done]
                if p == precisions[0]:
                    for m, (_, new) in zip(margins, done):
                        m.append(new)
            del lw
        out: Dict[str, List[np.ndarray]] = {"router_margin": [
            np.stack([np.asarray(a) for a in m], -1)[k:len(seq)]
            for m, seq, k in zip(margins, sequences, keep_from)]}
        for p in precisions:
            out[p] = []
            for x, seq, k in zip(xs[p], sequences, keep_from):
                n = len(seq) - k
                if not 0 < n <= KEEP:
                    raise ValueError(f"{n} rows asked of a sequence; KEEP is {KEEP}")
                rows = np.minimum(np.arange(k, k + KEEP), x.shape[0] - 1)
                out[p].append(np.asarray(arch.head(d, x[rows], top, p))[:n])
        return out


def served_gaps(logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """By how much each served token's reference logit lies below the
    reference's best, position by position. `logits[i]` predicts `served[i]`."""
    served = np.asarray(served)
    return logits.max(-1) - logits[np.arange(len(served)), served]


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray) -> np.ndarray:
    """The same gap for the token the lower precision puts first."""
    return served_gaps(ref_logits, low_logits.argmax(-1))
