"""Seeded weights, made by the benchmark: they are the run's input data.

What is here is common to every architecture: how `--seed` becomes keys, and
how a key becomes a leaf in the type it is served in (an int8 matrix with
per-column float32 scales, a bfloat16 vector or table). WHICH leaves a layer
has, and in what layout, is the architecture's: its file under
`benchmark/archs/` supplies `build_params`, `build_layer` and `build_top`
(harness/manifest.py `load_arch`), and may build them with the helpers for a
stack of equal layers below. Layer `l` is a pure function of
`layer_key(seed, l)`, so the reference regenerates one layer at a time from
the seed alone and takes nothing the program has touched.

A quantized matrix is `{"q": int8 [K, N], "s": float32 [1, N]}` and stands
for `q * s`.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

W_STD = 0.02  # standard deviation of every matrix entry, as initializer_range
INT8_STD = 73.9  # of a uniform byte


def raw_bytes(key, shape):
    return jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint8), jnp.int8
    )


def qleaf(key, shape) -> Dict[str, jax.Array]:
    kq, ks = jax.random.split(key)
    n = shape[-1]
    s = (W_STD / INT8_STD) * (0.5 + jax.random.uniform(ks, shape[:-2] + (1, n)))
    return {"q": raw_bytes(kq, shape), "s": s.astype(jnp.float32)}


def small(key, shape, scale=W_STD / INT8_STD, offset=0.0):
    """A bfloat16 leaf from raw bytes (jax.random.normal compiles slowly on
    the TPU for large shapes; bytes do not)."""
    return (offset + raw_bytes(key, shape).astype(jnp.float32) * scale).astype(
        jnp.bfloat16
    )


def norm(key, width: int):
    """A norm's weight: about 1, bfloat16."""
    return small(key, (width,), 0.1 / INT8_STD, 1.0)


def split_seed(seed: int):
    """--seed may pass 2**31: two int32 halves, traced, so that one compiled
    program serves every seed."""
    return jnp.int32(seed % (2 ** 31)), jnp.int32(seed // (2 ** 31))


def roots(seed_lo, seed_hi):
    """(layers, embed, final_norm, lm_head) keys."""
    return jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi), 4
    )


def layer_key(seed_lo, seed_hi, layer):
    """The key all of layer `layer` is made from."""
    return jax.random.fold_in(roots(seed_lo, seed_hi)[0], layer)


# -- a stack of equal layers ---------------------------------------------------
# `layer_leaves(d, key)` gives ONE layer's tree (no layer axis) from that
# layer's key, `top_leaves(d, k_embed, k_norm, k_head)` the tree around the
# stack; both are the architecture's, as is `d`. An architecture whose layers
# differ by index builds its own tree from `layer_key`.

LayerFn = Callable[[object, jax.Array], Dict[str, object]]
TopFn = Callable[[object, jax.Array, jax.Array, jax.Array], Dict[str, object]]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _build(layer_leaves: LayerFn, top_leaves: TopFn, d, seed_lo, seed_hi):
    k_layers, *k_top = roots(seed_lo, seed_hi)
    layers = jax.lax.map(
        lambda l: layer_leaves(d, jax.random.fold_in(k_layers, l)),
        jnp.arange(d.layers),
    )
    return {"layers": layers, **top_leaves(d, *k_top)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(layer_leaves: LayerFn, d, seed_lo, seed_hi, layer):
    return layer_leaves(d, layer_key(seed_lo, seed_hi, layer))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _top(top_leaves: TopFn, d, seed_lo, seed_hi):
    return top_leaves(d, *roots(seed_lo, seed_hi)[1:])


def build_stack(layer_leaves: LayerFn, top_leaves: TopFn, d, seed: int):
    """The whole serving tree, on the device, in one jitted call: `d.layers`
    equal layers stacked along a leading axis under "layers"."""
    return _build(layer_leaves, top_leaves, d, *split_seed(seed))


def build_stack_layer(layer_leaves: LayerFn, d, seed: int, layer: int):
    """Layer `layer` of the same tree, alone (for the reference)."""
    return _one_layer(layer_leaves, d, *split_seed(seed), jnp.int32(layer))


def build_stack_top(top_leaves: TopFn, d, seed: int):
    """What lies around the stack in the same tree (embedding, output head)."""
    return _top(top_leaves, d, *split_seed(seed))
