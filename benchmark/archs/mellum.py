"""Mellum 2 (`model_type` `mellum`) as the benchmark knows it: a grouped-query
stack whose layers are of two KINDS, sliding-window and full attention
(`layer_types`), each kind with its own rotary table (`rope_parameters`), and
every layer's FFN a softmax top-k router over many small experts. A
configuration file names this file by `"arch": "mellum"`; it imports nothing of
the program.

One token at position t, x in R^hidden, layer l of kind k(l):

- h = rms(x). q = h Wq as `heads` heads of `head_dim`, k = h Wk and v = h Wv as
  `kv_heads`; no bias, no normalisation of queries and keys. q and k rotated by
  the kind's table, half-rotation layout: the window kind plain,
  inv_freq_i = theta^(-2i/head_dim); the full kind under YaRN, inv_freq blended
  between that and that over `factor` by the linear ramp between the dimensions
  that turn `beta_fast` and `beta_slow` times in `original` positions, cos and
  sin both times `attention_factor` (scores carry its square).
- scores q k^T / sqrt(head_dim), query head i on kv head i // (heads /
  kv_heads); causal; a window layer also masks keys at positions <= t - window
  (a query sees itself and the window - 1 rows before it); softmax in float32;
  x <- x + (P v) Wo.
- h2 = rms(x); g = softmax(h2 Wr) over all experts, the `top_k` largest
  chosen, their weights divided by their sum (`norm_topk_prob`);
  x <- x + sum_e w_e (silu(h2 Wgate_e) * (h2 Wup_e)) Wdown_e. `margin` is by
  how much the last chosen router logit leads the first one left out.
- after the last layer the final norm and the untied head.

`intermediate_size` is used by no layer (`mlp_layer_types` is all sparse); the
multi-token-prediction head is not part of the next-token logits and is not
built.

Layout: `w_qkv` = [Wq | Wk | Wv] along columns, `we_gateup` = [gate | up] a
expert, every layer the same tree stacked along a leading axis (the kind is
the layer's index's, not the tree's); expert e from `fold_in(key, e)`.

The reference runs on the chip after the window, beside a server that holds
most of its memory: the residual and what lies around the stack (embedding,
head) stay on the HOST, a block takes one sequence to the device and hands it
back, attention scores one head and `Q_BLOCK` queries at a time against every
key (the mask is the whole of the window: the reference skips nothing), the
head runs `HEAD_BLOCK` columns at a time.

`CONTROL` is "int4": every matrix re-quantized one step below. Two more
controls are this file's own (a `check.control` may name them): "window_page"
gives the window layers one page (128 rows) more than the window, and
"no_yarn" rotates the full layers by the plain table; both leave the matrices
at float32. Either is a different model than the configuration states, of the
kind a fault in the per-kind tables or residency would serve.

THE COUNTS ARE OF THE WORK, NOT OF THE IMPLEMENTATION: a window layer's
attention is counted over min(rows, window) keys whether or not the program
skips the rest. The harness hands `decode_step_bytes/ops` the rows of all
active slots TOGETHER; a slot's window rows are taken as min(rows / active,
window) x active, which is at least the true sum of min(rows_i, window)
(min is concave), so the least time is counted a little high where slots'
contexts straddle the window and `kernels.decode_roofline_pct` reads a little
high there: by less than the window layers' cache share of a step's bytes
(about 4 % at 16 slots of 4k rows).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import reference as R
from benchmark.harness import weights as W
from benchmark.harness.roofline import expected_distinct_experts, matrix_bytes

KV_BYTES = 2  # bfloat16 cache
CONTROL = "int4"
KIND_CONTROLS = ("window_page", "no_yarn")
PAGE = 128  # rows "window_page" widens the window by
Q_BLOCK = 512  # query rows the reference's attention scores at a time
HEAD_BLOCK = 16384  # columns of the output head it holds in float32 at a time
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class Rope:
    theta: float
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class Dims:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int
    top_k: int
    ffn: int  # one expert's width
    norm_topk: bool
    eps: float
    window: int
    kinds: Tuple[str, ...]  # WINDOW or FULL, a layer
    rope_window: Rope
    rope_full: Rope

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def full_layers(self) -> int:
        return sum(k == FULL for k in self.kinds)

    @property
    def window_layers(self) -> int:
        return self.layers - self.full_layers


def _rope_of(section: dict) -> Rope:
    kind = section.get("rope_type", "default")
    if kind == "default":
        return Rope(theta=float(section["rope_theta"]))
    if kind != "yarn":
        raise ValueError(f"rope_parameters of type {kind!r}")
    factor = float(section["factor"])
    return Rope(
        theta=float(section["rope_theta"]), factor=factor,
        original=int(section["original_max_position_embeddings"]),
        beta_fast=float(section["beta_fast"]), beta_slow=float(section["beta_slow"]),
        attention_factor=float(section.get("attention_factor")
                               or 0.1 * math.log(factor) + 1.0),
    )


def dims_of(config: dict) -> Dims:
    layers = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])
    if len(kinds) != layers or set(kinds) - {WINDOW, FULL}:
        raise ValueError(f"layer_types names {WINDOW} or {FULL} for each of {layers} layers")
    if set(config["mlp_layer_types"]) != {"sparse"} or len(config["mlp_layer_types"]) != layers:
        raise ValueError("every layer's FFN is sparse in this family")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("no attention bias and an untied head, as published")
    return Dims(
        layers=layers, hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        vocab=int(config["vocab_size"]), experts=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        ffn=int(config["moe_intermediate_size"]),
        norm_topk=bool(config["norm_topk_prob"]), eps=float(config["rms_norm_eps"]),
        window=int(config["sliding_window"]), kinds=kinds,
        rope_window=_rope_of(config["rope_parameters"][WINDOW]),
        rope_full=_rope_of(config["rope_parameters"][FULL]),
    )


def context_length(config: dict) -> int:
    return int(config["max_position_embeddings"])


def _rope_fields(rope: Rope) -> tuple:
    """A rotary table as the program's `RopeParams` fields, as plain pairs."""
    return (("theta", rope.theta), ("factor", rope.factor),
            ("original_context", rope.original), ("beta_fast", rope.beta_fast),
            ("beta_slow", rope.beta_slow), ("attention_factor", rope.attention_factor))


def model_fields(config: dict, context: int) -> Dict[str, object]:
    """The fields of the program's `ModelConfig` for this configuration."""
    d = dims_of(config)
    names = {WINDOW: "window", FULL: "full"}
    return dict(
        name=config["assumed"]["served_name"], vocab_size=d.vocab,
        hidden_size=d.hidden, intermediate_size=int(config["intermediate_size"]),
        num_layers=d.layers, num_heads=d.heads, num_kv_heads=d.kv_heads,
        head_dim=d.head_dim, max_context=context, rope_theta=d.rope_full.theta,
        rms_norm_eps=d.eps, sliding_window=d.window,
        layer_types=tuple(names[k] for k in d.kinds),
        rope_by_kind=(("full", _rope_fields(d.rope_full)),
                      ("window", _rope_fields(d.rope_window))),
        num_experts=d.experts, num_experts_per_tok=d.top_k,
        moe_intermediate_size=d.ffn, norm_topk_prob=d.norm_topk,
    )


def trace_markers(d: Dims) -> Dict[str, object]:
    """A decode step runs one paged attention kernel a layer: the full kind's
    under the name `paged_decode_attention`, the window kind's under
    `window_decode_attention`; both names hold the marker."""
    return {"decode_kernel": "decode_attention", "kernels_per_step": d.layers}


# -- weights ---------------------------------------------------------------------


def layer_leaves(d: Dims, key) -> Dict[str, object]:
    """ONE layer's tree (no layer axis) from that layer's key."""
    ks = jax.random.split(key, 6)

    def expert(e):
        k_up, k_down = jax.random.split(jax.random.fold_in(ks[5], e))
        return (W.qleaf(k_up, (d.hidden, 2 * d.ffn)), W.qleaf(k_down, (d.ffn, d.hidden)))

    gateup, down = jax.vmap(expert)(jnp.arange(d.experts))
    return {
        "attn_norm": W.norm(ks[0], d.hidden),
        "ffn_norm": W.norm(ks[1], d.hidden),
        "w_qkv": W.qleaf(ks[2], (d.hidden, d.q_dim + 2 * d.kv_dim)),
        "wo": W.qleaf(ks[3], (d.q_dim, d.hidden)),
        "w_router": W.small(ks[4], (d.hidden, d.experts)),
        "we_gateup": gateup, "we_down": down,
    }


def top_leaves(d: Dims, k_embed, k_norm, k_head) -> Dict[str, object]:
    return {
        "embed": W.small(k_embed, (d.vocab, d.hidden)),
        "final_norm": W.norm(k_norm, d.hidden),
        "lm_head": W.qleaf(k_head, (d.hidden, d.vocab)),
    }


def build_params(d: Dims, seed: int):
    """The whole serving tree, on the device, in one jitted call."""
    return W.build_stack(layer_leaves, top_leaves, d, seed)


def build_layer(d: Dims, seed: int, layer: int):
    """Layer `layer` of the same tree, alone (for the reference)."""
    return W.build_stack_layer(layer_leaves, d, seed, layer)


def build_top(d: Dims, seed: int):
    """What lies around the stack, on the HOST (numpy): 0.7 GB of embedding
    and head that no layer needs, beside a server that holds 13 of 15.75 GB."""
    return jax.device_get(W.build_stack_top(top_leaves, d, seed))


# -- the plain reference ---------------------------------------------------------


def embed(top, ids):
    """[T, hidden] float32 on the host (`block` brings one sequence at a time
    to the device)."""
    return np.asarray(top["embed"])[np.asarray(ids)].astype(np.float32)


def inv_freq(d: Dims, rope: Rope):
    """Rotary frequencies [head_dim / 2] of one kind's table."""
    dim = d.head_dim
    plain = 1.0 / rope.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.factor <= 1.0:
        return jnp.asarray(plain, jnp.float32)

    def dim_of(turns):
        return dim * math.log(rope.original / (turns * 2 * math.pi)) / (
            2 * math.log(rope.theta))

    low = max(math.floor(dim_of(rope.beta_fast)), 0)
    high = min(math.ceil(dim_of(rope.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(plain / rope.factor * ramp + plain * (1.0 - ramp), jnp.float32)


def rotate(d: Dims, rope: Rope, x, positions):
    """Rotary embedding of one kind, half-rotation; x [T, heads, head_dim]."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq(d, rope)
    cos = rope.attention_factor * jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = rope.attention_factor * jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _parts(precision: str):
    """(the matrices' precision, which control of the kinds or None)."""
    return ("float32", precision) if precision in KIND_CONTROLS else (precision, None)


def attention(d: Dims, x, lw, kind: str, precision: str, variant=None):
    """attn(rms(x)) Wo for one sequence x [T, hidden], a head and a block of
    queries at a time."""
    t = x.shape[0]
    pos = jnp.arange(t)
    rope = d.rope_window if kind == WINDOW else d.rope_full
    if kind == FULL and variant == "no_yarn":
        rope = Rope(theta=rope.theta)
    window = d.window + (PAGE if variant == "window_page" else 0)
    h = R.rms(x, lw["attn_norm"], d.eps)
    qkv = h @ R.dense(lw["w_qkv"], precision)
    q = rotate(d, rope, qkv[:, :d.q_dim].reshape(t, d.heads, d.head_dim), pos)
    k = rotate(d, rope, qkv[:, d.q_dim:d.q_dim + d.kv_dim].reshape(
        t, d.kv_heads, d.head_dim), pos)
    v = qkv[:, d.q_dim + d.kv_dim:].reshape(t, d.kv_heads, d.head_dim)
    group = d.heads // d.kv_heads
    k, v = k.swapaxes(0, 1), v.swapaxes(0, 1)  # [kv_heads, T, D]

    def head(i):
        qh, kh, vh = q[:, i], k[i // group], v[i // group]

        def rows(block):  # a block of queries over every key: [Q, T] scores
            qb, at = block
            s = (qb @ kh.T) / jnp.sqrt(jnp.float32(d.head_dim))
            seen = at[:, None] >= pos[None, :]
            if kind == WINDOW:
                seen = seen & (at[:, None] - pos[None, :] < window)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

        if t <= Q_BLOCK or t % Q_BLOCK:
            return rows((qh, pos))
        blocks = (qh.reshape(t // Q_BLOCK, Q_BLOCK, d.head_dim),
                  pos.reshape(t // Q_BLOCK, Q_BLOCK))
        return jax.lax.map(rows, blocks).reshape(t, d.head_dim)

    att = jax.lax.map(head, jnp.arange(d.heads))  # [heads, T, D]
    return att.swapaxes(0, 1).reshape(t, d.q_dim) @ R.dense(lw["wo"], precision)


def moe(d: Dims, h, lw, precision: str):
    """(the chosen experts' weighted sum, the router's margin)."""
    router = h @ lw["w_router"].astype(jnp.float32)
    ranked = jax.lax.top_k(router, d.top_k + 1)[0]
    margin = ranked[:, d.top_k - 1] - ranked[:, d.top_k]
    probs = jax.nn.softmax(router, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], top_i].set(top_w)

    def expert(acc, e):  # every expert over every token, weighted; one at a time
        gateup, down = jax.tree.map(lambda a: a[e], (lw["we_gateup"], lw["we_down"]))
        gu = h @ R.dense(gateup, precision)
        y = (jax.nn.silu(gu[:, :d.ffn]) * gu[:, d.ffn:]) @ R.dense(down, precision)
        return acc + gate[:, e][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(d.experts))
    return y, margin


@functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(1,))
def _block(d: Dims, x, lw, kind: str, precision: str):
    matrices, variant = _parts(precision)
    x = x + attention(d, x, lw, kind, matrices, variant)
    y, margin = moe(d, R.rms(x, lw["ffn_norm"], d.eps), lw, matrices)
    return x + y, margin


def block(d: Dims, x, lw, layer: int, precision: str):
    """Layer `layer` of the reference over x [T, hidden], its kind taken from
    its index; taken from the host and handed back to it."""
    out, margin = _block(d, jnp.asarray(x), lw, d.kinds[int(layer)], precision)
    return np.asarray(out), margin


@functools.partial(jax.jit, static_argnums=(4,))
def _head_block(x, final_norm, q, s, eps_and_precision):
    eps, precision = eps_and_precision
    return R.rms(x, final_norm, eps) @ R.dense({"q": q, "s": s}, precision)


def head(d: Dims, x, top, precision: str):
    """The final norm and the head over rows x [R, hidden], a HEAD_BLOCK of the
    vocabulary at a time (a lower precision's groups run along rows, so a
    block of columns re-quantizes as the whole matrix would)."""
    matrices, _ = _parts(precision)
    q, s = top["lm_head"]["q"], top["lm_head"]["s"]
    x = jnp.asarray(x)
    return np.concatenate([
        np.asarray(_head_block(x, top["final_norm"], q[:, lo:lo + HEAD_BLOCK],
                               s[:, lo:lo + HEAD_BLOCK], (d.eps, matrices)))
        for lo in range(0, q.shape[1], HEAD_BLOCK)], axis=-1)


# -- the least bytes and operations ---------------------------------------------
# Of the WORK (this file's header): a window layer over min(rows, window) keys.


def attention_bytes(d: Dims) -> int:
    """Per layer: fused qkv and output projections, and the two norms."""
    return (matrix_bytes(d.hidden, d.q_dim + 2 * d.kv_dim)
            + matrix_bytes(d.q_dim, d.hidden) + 2 * 2 * d.hidden)


def ffn_bytes(d: Dims) -> int:
    """One expert: gate|up and down."""
    return matrix_bytes(d.hidden, 2 * d.ffn) + matrix_bytes(d.ffn, d.hidden)


def layers_bytes(d: Dims, tokens: float) -> float:
    """The layers' weights once: of the experts, those `tokens` rows are
    expected to touch, as `archs/xing4.py` counts them; the bfloat16 router."""
    touched = expected_distinct_experts(d.experts, d.top_k, tokens)
    return d.layers * (attention_bytes(d) + touched * ffn_bytes(d)
                       + 2 * d.hidden * d.experts)


def keys_read(d: Dims, active: float, context_rows: float) -> float:
    """Cache rows x layers a decode step of `active` slots attends over:
    every row in a full layer, at most the window in a window layer (taken
    at the slots' mean context: this file's header)."""
    if active <= 0:
        return 0.0
    windowed = active * min(context_rows / active, float(d.window))
    return d.full_layers * context_rows + d.window_layers * windowed


def decode_step_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least HBM bytes of one decode step for `active` slots whose contexts
    hold `context_rows` rows together."""
    head = matrix_bytes(d.hidden, d.vocab) + 2 * d.hidden
    cache = keys_read(d, active, context_rows) * 2 * d.kv_dim * KV_BYTES
    return layers_bytes(d, active) + head + active * d.hidden * 2 + cache


def _row_ops(d: Dims) -> int:
    """Operations of the layers' matrices for one row."""
    return d.layers * 2 * (
        d.hidden * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.hidden
        + d.top_k * 3 * d.hidden * d.ffn + d.hidden * d.experts
    )


def decode_step_ops(d: Dims, active: float, context_rows: float) -> float:
    per_token = _row_ops(d) + 2 * d.hidden * d.vocab
    return active * per_token + keys_read(d, active, context_rows) * 4 * d.q_dim


def window_pairs(d: Dims, tokens: int, cached: int) -> float:
    """(query, key) pairs of a window layer for the rows [cached, tokens): the
    row at position p sees min(p + 1, window) keys."""
    w = d.window

    def upto(n):  # sum of min(p + 1, w) for p < n
        m = min(n, w)
        return m * (m + 1) / 2 + max(n - w, 0) * w

    return upto(tokens) - upto(cached)


def prefill_ops(d: Dims, prompt_tokens: Sequence[int], cached_rows: Sequence[int]
                ) -> float:
    """Least operations to admit prompts of these lengths of which the first
    `cached_rows[i]` rows were already in the cache: the matrices for every
    new row, and attention of each new row over what it sees: everything
    before it in a full layer, its window in a window layer."""
    per_row = _row_ops(d)
    total = 0.0
    for t, c in zip(prompt_tokens, cached_rows):
        new = t - c
        full = new * c + new * (new + 1) / 2
        total += new * per_row + 4 * d.q_dim * (
            d.full_layers * full + d.window_layers * window_pairs(d, t, c))
    return total


def prefill_bytes(d: Dims, new_rows: float) -> float:
    """Least HBM bytes of one prefill program: the layers' weights once, of
    the experts those the new rows are expected to select."""
    return layers_bytes(d, new_rows)
