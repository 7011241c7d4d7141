"""Nemotron-H (`model_type` `nemotron_h`: NVIDIA-Nemotron-3-Nano-30B-A3B) as
the benchmark knows it: a stack whose every layer is ONE sub-layer under ONE
norm, `x += F(rms(x))`, and F is by the layer's letter in
`hybrid_override_pattern`: `M` a Mamba-2 state-space mixer, `E` an expert FFN
of ungated relu^2 experts beside a shared one, `*` grouped-query attention. A
configuration file names this file by `"arch": "nemotron_h"`; it imports
nothing of the program.

All norms are RMSNorm with a learned weight; no bias but the convolution's.
One sequence `x` [T, E], `h = rms(x)`:

`M`, H heads of P channels, a state of N values a channel, B and C in G groups
of H / G heads (`d_inner` = H P; `expand` is read by nothing):
- `[z | u | dt] = h W_in` (H P | H P + 2 G N | H columns).
- short convolution, depthwise and causal over u, WITH bias, then SiLU:
  `c_t = silu(b + sum_j w_j * u_{t-taps+1+j})`, rows before the start zero.
- `[x | B | C] = c` (x [H, P]; B, C [G, N]); head h reads group h // (H / G).
- `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, both one number a head; no
  clamp (`time_step_limit` (0, inf)).
- state, float32, from zero, [P, N] a head: `S_t = exp(dt_t A) S_{t-1} +
  (dt_t x_t) B_t^T`; `y_t = S_t C_t + D x_t`. The reference runs it ROW BY ROW
  under `lax.scan`: no chunking, no cache.
- the GATED norm, gate first: `y = rms_groups(y * silu(z))` over G groups of
  H P / G channels with one learned weight a channel; `x += y W_out`.

`*`: `q = h W_q` (heads x head_dim), `k, v` (kv_heads x head_dim each), causal
`softmax(q k^T / sqrt(head_dim))`, heads / kv_heads query heads a K/V head,
`x += a W_o`. NO rotary embedding: the published `nemotron_h` attention applies
none (the state-space layers carry position; Nemotron-H's report says so), and
`rope_theta` / `partial_rotary_factor` in the config are read by nothing.

`E`: `s = sigmoid(h W_r)` over ALL `experts` in float32; the choice is made on
`s + bias` (`n_group` 1, `topk_group` 1: every expert in the one group), the
`top_k` largest; weights the UNBIASED `s` of the chosen over their sum (+
1e-20), times `scale`; an expert is `W_down relu(W_up u)^2`, TWO matrices, no
gate; `y = sum_e w_e expert_e(h) + shared(h)`, the shared expert the same form
at its own width. Of the `experts` only `held` lie here, from `first` on (one
chip's share: the configuration file states the deployment); what the absent
ones would add is left out, here and in the program alike. `margin` is by how
much the last chosen biased score leads the first left out, in a logit's
worth (4 x a sigmoid score's gap); infinite in an `M` or `*` layer.
`intermediate_size` is the dense `-` kind's, which this pattern never uses.

Layout (the configuration's `assumed` states it): what every layer has, its
one norm, is stacked over all layers under `layers.norm`; a kind's own leaves
over that kind's layers under `layers.by_kind.<kind>` (`mamba2`, `moe`,
`full`): `ssm_in` = [W_z | W_xBC | W_dt | 0] by columns (zero columns up to
whole lane tiles), `ssm_conv` [taps, H P + 2
G N] with `ssm_conv_b`, `w_qkv` = [W_q | W_k | W_v], `we_up_t` / `we_down` the
held experts' stacks, the up matrices TRANSPOSED ([held, F, E] int8 with
their scales [held, 1, F]: the expert width 1,856 is 14.5 lane tiles, and the
TPU lays an int8 [.., E, F] array out with E on the lanes). Layer `l` is a
pure function of `layer_key(seed, l)` and expert `e` of `fold_in(., e)`, but
for an `E` layer's `router_bias`, which is a function of the seed and of the
layers before it (`router_biases`): what the published model's training does
to `e_score_correction_bias`, moving it until every expert is chosen as often
as any other, is done here once, over CALIBRATION rows of seeded tokens run
through these layers. Without it the routing is the seed's: over random
matrices the rows' common part grows layer by layer (relu^2 and the Mamba gate
have a mean; the cosine of two tokens' rows reaches 0.6 by the twelfth `E`
layer at these widths), a few experts are chosen 15-19 times as often as the
even share, how many a decode step touches differs from seed to seed by 7-10 %
and `tpot_p50_ms` with it (PERF.md section 6, PR 42).

`CONTROL` is "int4". One more control is this file's own (a `check.control`
may name it): "state_bf16" rounds the Mamba state to bfloat16 after every row
and leaves the matrices at float32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.harness import reference as R
from benchmark.harness import weights as W
from benchmark.harness.roofline import expected_distinct_experts, matrix_bytes

KV_BYTES = 2  # bfloat16 K/V pages
STATE_BYTES = 4  # float32 recurrent state
CONTROL = "int4"
STATE_CONTROLS = ("state_bf16",)
KINDS = {"M": "mamba2", "E": "moe", "*": "full"}


@dataclass(frozen=True)
class Dims:
    layers: int
    layer_types: Tuple[str, ...]
    hidden: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_ffn: int
    shared_ffn: int
    vocab: int
    experts: int  # the router's width, as published
    held: int
    first: int
    top_k: int
    scale: float
    norm_topk: bool
    dt_min: float
    dt_max: float
    dt_floor: float
    eps: float

    def kind(self, layer: int) -> str:
        return self.layer_types[layer]

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_types)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        return self.inner + self.conv_dim + self.ssm_heads

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def dims_of(config: dict) -> Dims:
    """Sizes from a configuration file (keys as in the model's config.json;
    `n_routed_experts` counts the experts HELD, `router_n_experts` and
    `first_routed_expert` state the router's width and the share beside it)."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != int(config["num_hidden_layers"]) or set(pattern) - set(KINDS):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} names one of {sorted(KINDS)} for "
            f"each of the {config['num_hidden_layers']} layers (the dense `-` kind "
            "is not built: the published pattern has none)")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("the router's groups are built as the published 1 of 1")
    held = int(config["n_routed_experts"])
    return Dims(
        layers=len(pattern), layer_types=tuple(KINDS[c] for c in pattern),
        hidden=int(config["hidden_size"]),
        ssm_heads=int(config["mamba_num_heads"]),
        ssm_head_dim=int(config["mamba_head_dim"]),
        ssm_state=int(config["ssm_state_size"]), ssm_groups=int(config["n_groups"]),
        conv=int(config["conv_kernel"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config["n_shared_experts"])
        * int(config["moe_shared_expert_intermediate_size"]),
        vocab=int(config["vocab_size"]),
        experts=int(config.get("router_n_experts", held)), held=held,
        first=int(config.get("first_routed_expert", 0)),
        top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        dt_min=float(config["time_step_min"]), dt_max=float(config["time_step_max"]),
        dt_floor=float(config["time_step_floor"]),
        eps=float(config["layer_norm_epsilon"]),
    )


def context_length(config: dict) -> int:
    return int(config["max_position_embeddings"])


def model_fields(config: dict, context: int) -> Dict[str, object]:
    """The fields of the program's `ModelConfig` for this configuration."""
    d = dims_of(config)
    return dict(
        name=config["assumed"]["served_name"], vocab_size=d.vocab,
        hidden_size=d.hidden, intermediate_size=int(config["intermediate_size"]),
        num_layers=d.layers, num_heads=d.heads, num_kv_heads=d.kv_heads,
        head_dim=d.head_dim, max_context=context, rms_norm_eps=d.eps,
        num_experts=d.experts, num_experts_per_tok=d.top_k,
        moe_intermediate_size=d.expert_ffn, norm_topk_prob=d.norm_topk,
        experts_held=d.held, first_expert=d.first, moe_scoring="sigmoid",
        routed_scaling_factor=d.scale,
        n_shared_experts=d.shared_ffn // d.expert_ffn,
        layer_types=list(d.layer_types), ssm_heads=d.ssm_heads,
        ssm_head_dim=d.ssm_head_dim, ssm_state=d.ssm_state,
        ssm_groups=d.ssm_groups, ssm_conv=d.conv, expert_act="relu2",
        rotary=False,
    )


def trace_markers(d: Dims) -> Dict[str, object]:
    """The paged attention kernel runs once per `*` layer per decode step."""
    return {"decode_kernel": "paged_decode_attention",
            "kernels_per_step": d.count("full")}


# -- weights: a layer's tree depends on its kind -----------------------------------


def _ffn_leaves(k_up, k_down, hidden: int, width: int):
    return W.qleaf(k_up, (hidden, width)), W.qleaf(k_down, (width, hidden))


def _f32(key, shape, scale: float, offset: float = 0.0):
    return offset + W.raw_bytes(key, shape).astype(jnp.float32) * (scale / W.INT8_STD)


def _lane_padded(leaf):
    """A quantized matrix with zero columns up to whole lane tiles of 128:
    `ssm_in`'s 10,304 columns are 80.5 tiles, and the TPU relaid an int8 stack
    of that width out and back in EVERY decode program (a 332 MB copy, a tenth
    of the device's time: my chip run, PR 42)."""
    pad = -leaf["q"].shape[-1] % 128
    return {"q": jnp.pad(leaf["q"], ((0, 0), (0, pad))),
            "s": jnp.pad(leaf["s"], ((0, 0), (0, pad)), constant_values=1.0)}


def _unit(key, shape):
    """Uniform in [0, 1) from raw bytes, float32."""
    return (W.raw_bytes(key, shape).astype(jnp.float32) + 128.0) / 256.0


def kind_leaves(d: Dims, kind: str, key) -> Dict[str, object]:
    """The leaves of ONE layer of `kind` (no layer axis; its norm apart) from
    that layer's key."""
    ks = jax.random.split(jax.random.fold_in(key, 1), 12)
    if kind == "mamba2":
        # the published initialisation where a range matters: a step
        # log-uniform in [time_step_min, time_step_max] floored at
        # time_step_floor and dt_bias its inverse softplus, A uniform in
        # [1, 16], D = 1: exp(dt A) lies where a trained model's does, and
        # the state neither dies nor grows
        step = jnp.exp(_unit(ks[3], (d.ssm_heads,))
                       * (jnp.log(d.dt_max) - jnp.log(d.dt_min)) + jnp.log(d.dt_min))
        step = jnp.maximum(step, d.dt_floor)
        return {
            "ssm_in": _lane_padded(W.qleaf(ks[0], (d.hidden, d.in_width))),
            # taps of about a half: the convolution neither kills nor blows up
            "ssm_conv": W.small(ks[1], (d.conv, d.conv_dim), 0.5 / W.INT8_STD),
            "ssm_conv_b": W.small(ks[2], (d.conv_dim,), 0.1 / W.INT8_STD),
            "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssm_A_log": jnp.log(1.0 + 15.0 * _unit(ks[4], (d.ssm_heads,))),
            "ssm_D": jnp.ones((d.ssm_heads,), jnp.float32),
            "ssm_norm": W.norm(ks[5], d.inner),
            "wo": W.qleaf(ks[6], (d.inner, d.hidden)),
        }
    if kind == "full":
        return {"w_qkv": W.qleaf(ks[0], (d.hidden, d.q_dim + 2 * d.kv_dim)),
                "wo": W.qleaf(ks[1], (d.q_dim, d.hidden))}
    out = {"w_router": W.small(ks[0], (d.hidden, d.experts)),
           # about N(0, 0.01), where no calibration has been (a layer made
           # alone from its key); `build_params` and `build_layer` put
           # `router_biases`' row in its place
           "router_bias": _f32(ks[1], (d.experts,), 0.01)}
    out["ws_up"], out["ws_down"] = _ffn_leaves(ks[2], ks[3], d.hidden, d.shared_ffn)
    up, out["we_down"] = jax.vmap(lambda e: _ffn_leaves(
        *jax.random.split(jax.random.fold_in(ks[4], e)), d.hidden, d.expert_ffn)
    )(d.first + jnp.arange(d.held))
    # a routed expert's up matrix lies TRANSPOSED, [F, E] with a scale a row
    out["we_up_t"] = {"q": up["q"].swapaxes(-1, -2), "s": up["s"]}
    return out


def norm_leaf(d: Dims, key):
    """A layer's one norm."""
    return W.norm(jax.random.fold_in(key, 0), d.hidden)


def _embedding(d: Dims, k_embed):
    return W.small(k_embed, (d.vocab, d.hidden))


def top_leaves(d: Dims, k_embed, k_norm, k_head) -> Dict[str, object]:
    return {"embed": _embedding(d, k_embed),
            "final_norm": W.norm(k_norm, d.hidden),
            "lm_head": W.qleaf(k_head, (d.hidden, d.vocab))}


@functools.partial(jax.jit, static_argnums=(0,))
def _build(d: Dims, seed_lo, seed_hi, biases):
    key = lambda l: W.layer_key(seed_lo, seed_hi, l)  # noqa: E731
    by_kind = {
        kind: jax.lax.map(
            lambda l, kind=kind: kind_leaves(d, kind, key(l)),
            jnp.asarray([l for l in range(d.layers) if d.kind(l) == kind], jnp.int32))
        for kind in dict.fromkeys(d.layer_types)
    }
    by_kind["moe"]["router_bias"] = biases
    return {
        "layers": {
            "norm": jax.lax.map(lambda l: norm_leaf(d, key(l)), jnp.arange(d.layers)),
            "by_kind": by_kind,
        },
        **top_leaves(d, *W.roots(seed_lo, seed_hi)[1:]),
    }


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(d: Dims, kind: str, seed_lo, seed_hi, layer):
    key = W.layer_key(seed_lo, seed_hi, layer)
    return {"norm": norm_leaf(d, key), **kind_leaves(d, kind, key)}


CALIBRATION = (4, 256)  # sequences x rows of seeded tokens the biases are set over


@functools.partial(jax.jit, static_argnums=(0,))
def _calibration_rows(d: Dims, seed_lo, seed_hi):
    """The embedded rows [sequences, rows, E] of the calibration's tokens."""
    k_embed = W.roots(seed_lo, seed_hi)[1]
    ids = jax.random.randint(jax.random.fold_in(k_embed, 1), CALIBRATION, 0, d.vocab)
    return embed({"embed": _embedding(d, k_embed)}, ids)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _calibration_layer(d: Dims, xs, lw, kind: str):
    """(the calibration's rows after one layer of `kind`, that layer's bias):
    in an `E` layer, first, the bias under which every expert's biased score
    is among the `top_k` largest for the same share of the rows, `top_k` /
    `experts`: minus that quantile of its scores (a constant apart)."""
    bias = jnp.zeros((0,), jnp.float32)
    if kind == "moe":
        h = R.rms(xs, lw["norm"], d.eps).reshape(-1, d.hidden)
        scores = jax.nn.sigmoid(h @ lw["w_router"].astype(jnp.float32))
        cut = jnp.quantile(scores, 1.0 - d.top_k / d.experts, axis=0)
        bias = jnp.mean(cut) - cut
        lw = {**lw, "router_bias": bias}
    return jax.lax.map(lambda x: _block(d, x, lw, kind, "float32")[0], xs), bias


_BIASES: Dict[tuple, jax.Array] = {}  # the last seed's: a run builds it once


def router_biases(d: Dims, seed: int):
    """`router_bias` of every `E` layer, [E layers, experts] float32 (the
    module's header): the reference's own float32 forward of the calibration's
    rows, layer by layer, each `E` layer's bias set before the rows go through
    it. One layer's weights live at a time."""
    if (d, seed) in _BIASES:
        return _BIASES[d, seed]
    lo, hi = W.split_seed(seed)
    out = []
    with jax.default_matmul_precision("highest"):
        xs = _calibration_rows(d, lo, hi)
        for l, kind in enumerate(d.layer_types):
            xs, bias = _calibration_layer(
                d, xs, _one_layer(d, kind, lo, hi, jnp.int32(l)), kind)
            if kind == "moe":
                out.append(bias)
    biases = jnp.stack(out)
    if not isinstance(biases, jax.core.Tracer):  # (a trace for shapes keeps nothing)
        _BIASES.clear()
        _BIASES[d, seed] = biases
    return biases


def build_params(d: Dims, seed: int):
    """The whole serving tree, on the device (the module's header: layout)."""
    return _build(d, *W.split_seed(seed), router_biases(d, seed))


def build_layer(d: Dims, seed: int, layer: int):
    """Layer `layer` of the same tree, alone and flat (for the reference)."""
    layer = int(layer)
    lw = _one_layer(d, d.kind(layer), *W.split_seed(seed), jnp.int32(layer))
    if d.kind(layer) == "moe":
        lw["router_bias"] = router_biases(d, seed)[d.layer_types[:layer].count("moe")]
    return lw


def build_top(d: Dims, seed: int):
    return W.build_stack_top(top_leaves, d, seed)


# -- the plain reference ---------------------------------------------------------


def embed(top, ids):
    return top["embed"][ids].astype(jnp.float32)


def _parts(precision: str):
    """(the matrices' precision, which control of the state or None)."""
    return ("float32", precision) if precision in STATE_CONTROLS else (precision, None)


def _relu2_ffn(h, up, down, precision: str):
    return jnp.square(jax.nn.relu(h @ R.dense(up, precision))) @ R.dense(down, precision)


def mamba(d: Dims, h, lw, precision: str, variant=None):
    """The Mamba-2 mixer's output for one sequence's normed rows h [T, E], the
    recurrence row by row from a zero state."""
    f32 = jnp.float32
    t, hh, pp, gg, nn = h.shape[0], d.ssm_heads, d.ssm_head_dim, d.ssm_groups, d.ssm_state
    wide = h @ R.dense(lw["ssm_in"], precision)  # (zero columns beyond in_width)
    z, u, dt = wide[:, :d.inner], wide[:, d.inner:d.inner + d.conv_dim], \
        wide[:, d.inner + d.conv_dim:d.in_width]
    taps = lw["ssm_conv"].astype(f32)
    padded = jnp.concatenate([jnp.zeros((d.conv - 1, u.shape[1]), f32), u])
    c = jax.nn.silu(lw["ssm_conv_b"].astype(f32)
                    + sum(taps[j] * padded[j:j + t] for j in range(d.conv)))
    x = c[:, :d.inner].reshape(t, hh, pp)
    per_head = lambda m: jnp.repeat(m.reshape(t, gg, nn), hh // gg, axis=1)  # noqa: E731
    b = per_head(c[:, d.inner:d.inner + gg * nn])
    cc = per_head(c[:, d.inner + gg * nn:])
    dt = jax.nn.softplus(dt + lw["ssm_dt_bias"])  # [T, H]
    decay = jnp.exp(dt * -jnp.exp(lw["ssm_A_log"]))
    keep = (lambda s: s.astype(jnp.bfloat16).astype(f32)) if variant == "state_bf16" \
        else (lambda s: s)

    def row(s, r):
        x, b, cc, dt, decay = r
        s = keep(decay[:, None, None] * s + (dt[:, None] * x)[..., None] * b[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, cc)

    _, y = jax.lax.scan(row, jnp.zeros((hh, pp, nn), f32), (x, b, cc, dt, decay))
    y = (y + lw["ssm_D"][:, None] * x).reshape(t, d.inner) * jax.nn.silu(z)
    g = y.reshape(t, gg, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + d.eps)
    y = g.reshape(t, d.inner) * lw["ssm_norm"].astype(f32)
    return y @ R.dense(lw["wo"], precision)


def attention(d: Dims, h, lw, precision: str):
    """Grouped-query attention of one sequence's normed rows h [T, E]: no
    rotary embedding, a query head at a time so only one [T, T] score matrix
    exists."""
    t = h.shape[0]
    qkv = h @ R.dense(lw["w_qkv"], precision)
    q = qkv[:, :d.q_dim].reshape(t, d.heads, d.head_dim).swapaxes(0, 1)
    k = qkv[:, d.q_dim:d.q_dim + d.kv_dim].reshape(t, d.kv_heads, d.head_dim)
    v = qkv[:, d.q_dim + d.kv_dim:].reshape(t, d.kv_heads, d.head_dim)
    group = d.heads // d.kv_heads
    pos = jnp.arange(t)
    mask = pos[:, None] >= pos[None, :]
    scale = 1.0 / jnp.sqrt(jnp.float32(d.head_dim))

    def head(w):
        q_h, i = w
        k_h = jax.lax.dynamic_index_in_dim(k, i // group, 1, keepdims=False)
        v_h = jax.lax.dynamic_index_in_dim(v, i // group, 1, keepdims=False)
        s = (q_h @ k_h.T) * scale
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ v_h

    att = jax.lax.map(head, (q, jnp.arange(d.heads)))
    return att.swapaxes(0, 1).reshape(t, d.q_dim) @ R.dense(lw["wo"], precision)


def choose(d: Dims, scores, bias):
    """(the chosen experts [T, top_k], the margin [T]) of unbiased sigmoid
    scores [T, experts] under the choice on scores + bias."""
    ranked, idx = jax.lax.top_k(scores + bias, d.top_k + 1)
    return idx[:, :d.top_k], 4.0 * (ranked[:, d.top_k - 1] - ranked[:, d.top_k])


def moe_parts(d: Dims, h, lw, precision: str):
    """(what the experts HELD HERE add, what the shared expert adds, the
    router's margin) for normed rows h [T, E]."""
    scores = jax.nn.sigmoid(h @ lw["w_router"].astype(jnp.float32))
    top_i, margin = choose(d, scores, lw["router_bias"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if d.norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = d.scale * top_w
    gate = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], top_i].set(top_w)

    def expert(acc, j):  # every held expert over every token, weighted
        up, down = jax.tree.map(lambda a: a[j], (lw["we_up_t"], lw["we_down"]))
        up = {"q": up["q"].T, "s": up["s"]}  # [E, F], as every other matrix
        return acc + gate[:, d.first + j][:, None] * _relu2_ffn(h, up, down, precision), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(d.held))
    return routed, _relu2_ffn(h, lw["ws_up"], lw["ws_down"], precision), margin


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _block(d: Dims, x, lw, kind: str, precision: str):
    matrices, variant = _parts(precision)
    h = R.rms(x, lw["norm"], d.eps)
    none = jnp.full((x.shape[0],), jnp.inf)
    if kind == "mamba2":
        return x + mamba(d, h, lw, matrices, variant), none
    if kind == "full":
        return x + attention(d, h, lw, matrices), none
    routed, shared, margin = moe_parts(d, h, lw, matrices)
    return x + routed + shared, margin


def block(d: Dims, x, lw, layer: int, precision: str):
    """Layer `layer` of the reference: its one sub-layer by its index."""
    return _block(d, x, lw, d.kind(int(layer)), precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(d: Dims, x, final_norm, lm_head, precision: str):
    return R.rms(x, final_norm, d.eps) @ R.dense(lm_head, _parts(precision)[0])


def head(d: Dims, x, top, precision: str):
    return _head(d, x, top["final_norm"], top["lm_head"], precision)


# -- the least bytes and operations ---------------------------------------------
# A K/V row is 2 x kv_heads x head_dim bfloat16 values a `*` layer. An `M`
# layer keeps one float32 state [heads, P, N] a slot: a step reads and writes
# it once, and so does a chunk, whatever implements either. An expert is TWO
# matrices.


def mamba_matrix_bytes(d: Dims) -> int:
    return (matrix_bytes(d.hidden, d.in_width) + matrix_bytes(d.inner, d.hidden)
            + 2 * ((d.conv + 1) * d.conv_dim + d.inner + d.hidden)
            + 4 * 3 * d.ssm_heads)


def attn_matrix_bytes(d: Dims) -> int:
    return (matrix_bytes(d.hidden, d.q_dim + 2 * d.kv_dim)
            + matrix_bytes(d.q_dim, d.hidden) + 2 * d.hidden)


def ffn_bytes(d: Dims, width: int) -> int:
    return matrix_bytes(d.hidden, width) + matrix_bytes(width, d.hidden)


def held_touched(d: Dims, tokens: float) -> float:
    """Held experts that `tokens` tokens are expected to touch: over seeded
    weights every expert is as likely as any other."""
    return d.held / d.experts * expected_distinct_experts(d.experts, d.top_k, tokens)


def layers_bytes(d: Dims, tokens: float) -> float:
    """The layers' weights once, of the routed experts those `tokens` touch."""
    expert_layer = (ffn_bytes(d, d.shared_ffn) + 2 * d.hidden * d.experts
                    + 4 * d.experts + 2 * d.hidden
                    + held_touched(d, tokens) * ffn_bytes(d, d.expert_ffn))
    return (d.count("mamba2") * mamba_matrix_bytes(d)
            + d.count("full") * attn_matrix_bytes(d) + d.count("moe") * expert_layer)


def mamba_state_bytes(d: Dims) -> int:
    """One slot's recurrent state of one `M` layer."""
    return d.ssm_heads * d.ssm_head_dim * d.ssm_state * STATE_BYTES


def _mamba_row_io(d: Dims) -> int:
    """A row's x, dt, B, C in and y out, float32."""
    return (2 * d.inner + d.ssm_heads + 2 * d.ssm_groups * d.ssm_state) * 4


def mamba_step_bytes(d: Dims, active: float) -> float:
    """Least bytes of a decode step's recurrences: each live slot's state of
    each `M` layer read once and written once, and the row's vectors."""
    return d.count("mamba2") * active * (2 * mamba_state_bytes(d) + _mamba_row_io(d))


def mamba_row_ops(d: Dims) -> float:
    """Operations of ONE row's recurrence in one `M` layer: the decay, the
    rank-one update and the state's reading by C."""
    return d.ssm_heads * 5.0 * d.ssm_head_dim * d.ssm_state


def mamba_step_ops(d: Dims, active: float) -> float:
    return d.count("mamba2") * active * mamba_row_ops(d)


def mamba_chunk_ops(d: Dims, rows: float) -> float:
    """Operations of the recurrence over a chunk of `rows` REAL rows, all `M`
    layers: the count of the mathematics row by row, which a chunked form may
    exceed and may not undercut."""
    return d.count("mamba2") * rows * mamba_row_ops(d)


def mamba_chunk_bytes(d: Dims, rows: float) -> float:
    """Least bytes of the same: the slot's state in and out once a layer, and
    each row's vectors."""
    return d.count("mamba2") * (2 * mamba_state_bytes(d) + rows * _mamba_row_io(d))


def attn_decode_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least bytes of a step's attention kernels (one a `*` layer): the K/V
    rows of the streams decoding once, queries in and results out."""
    io = active * 2 * d.q_dim * 2
    return d.count("full") * (context_rows * 2 * d.kv_dim * KV_BYTES + io)


def attn_decode_ops(d: Dims, active: float, context_rows: float) -> float:
    del active
    return context_rows * d.count("full") * d.heads * d.head_dim * 2 * 2


def decode_step_bytes(d: Dims, active: float, context_rows: float) -> float:
    """Least HBM bytes of one decode step for `active` slots whose contexts
    hold `context_rows` rows together."""
    head = matrix_bytes(d.hidden, d.vocab) + 2 * d.hidden
    return (layers_bytes(d, active) + head + active * d.hidden * 2
            + attn_decode_bytes(d, active, context_rows) + mamba_step_bytes(d, active))


def _row_matrix_ops(d: Dims) -> float:
    """Operations of the layers' matrices for one row."""
    mamba_layer = d.hidden * d.in_width + d.inner * d.hidden + d.conv * d.conv_dim
    attn_layer = d.hidden * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.hidden
    expert_layer = (2 * d.hidden * d.shared_ffn + d.hidden * d.experts
                    + d.top_k * d.held / d.experts * 2 * d.hidden * d.expert_ffn)
    return 2 * (d.count("mamba2") * mamba_layer + d.count("full") * attn_layer
                + d.count("moe") * expert_layer)


def decode_step_ops(d: Dims, active: float, context_rows: float) -> float:
    per_token = _row_matrix_ops(d) + 2 * d.hidden * d.vocab
    return (active * per_token + attn_decode_ops(d, active, context_rows)
            + mamba_step_ops(d, active))


def prefill_ops(d: Dims, prompt_tokens: Sequence[int], cached_rows: Sequence[int]
                ) -> float:
    """Least operations to admit prompts of these lengths of which the first
    `cached_rows[i]` rows were already in the cache: the matrices and the
    Mamba recurrence for every new row, causal attention of each new row over
    what precedes it in the `*` layers, and one output-head row per prompt."""
    per_row = _row_matrix_ops(d) + d.count("mamba2") * mamba_row_ops(d)
    total = 0.0
    for t, c in zip(prompt_tokens, cached_rows):
        new = t - c
        pairs = new * c + new * (new + 1) / 2
        total += new * per_row + pairs * d.count("full") * d.heads * 2 * 2 * d.head_dim
    return total


def prefill_bytes(d: Dims, new_rows: float) -> float:
    """Least HBM bytes of one prefill program: the layers' weights once and
    the slot's states in and out."""
    return layers_bytes(d, new_rows) + d.count("mamba2") * 2 * mamba_state_bytes(d)
