"""Mixture-of-experts FFN: router + expert computation, TPU-first.

An expert is a SwiGLU, ``down(silu(gate(x)) * up(x))`` with [gate | up] fused,
or, with ``cfg.expert_act`` "relu2", ungated: ``down(relu(up(x))^2)``, two
matrices, the up matrices stored transposed (``we_up_t``: EXPERT_LEAVES). The
activation is a static property of the model and goes through the three
paths and both kernels; nothing of the SwiGLU paths' graphs changes for it.

What serves (top-k routed experts, one math; which of the three a
graph takes follows from what the graph IS and from static shapes, never
from an option: ``visit_serves``, ``grouped_serves``):

  * ``moe_ffn_visit`` — a DECODE step (the graph that hands the FFN its
    live-slot mask), not under a sharding plan: the step is bound by the
    expert weights' BYTES, so only the held experts that a LIVE row picked
    are read, each where it lies in the stacks at (layer, expert), in
    ascending order, a trip count read on the device from the step's own
    picks. A visited expert runs over all the step's rows (8-32: nothing is
    gathered), gated per row. Exact and dropless. A row of an inactive slot
    picks no expert. With few live rows against many held experts (a layer
    call touches about 4 of 64, 2.4 of 8 and 4-5 of 16 in the benchmark's
    open-loop and half-used cells, 7.1 of 8 with eight live slots: PERF.md,
    PR 32) that is a sixteenth to a third of the bytes; with every expert
    touched it is every expert's bytes once, as the dense path reads them.
  * ``moe_ffn_grouped`` — PREFILL token counts (a chunk or a bucket of
    enough tokens, ``grouped_pays``): each held expert runs over the rows
    routed to it and no others. The picks are laid out by expert, each
    expert's segment rounded up to a ROW_BLOCK of 32 rows, and the unit of
    work is an expert's SEGMENT: on the chip one kernel a layer call
    (ops/expert_group.py) streams each touched expert's int8 weights ONCE,
    where they lie in the stacks at (layer, expert), over that expert's own
    rows; off the chip a loop of the same products over the same layout.
    Exact and dropless. At 2 of 8 and 512 tokens that is 1,024-1,280 expert
    rows where every expert over every token is 4,096, and eight streams of
    an expert where a tile of 128 rows streamed it twelve times (PR 37).
  * ``moe_ffn_dense`` — every expert processes every token; per-token gate
    weights (zero for unselected experts) scale the outputs. Exact and
    dropless. Every held expert's weights are streamed whatever was picked:
    the path of every graph under a sharding plan (decode steps too), of the
    training forward's small token counts, and of the prefill buckets too
    small for a pass of the MXU an expert (``grouped_pays``). The einsum
    contracts over the expert axis, so under
    expert parallelism (experts sharded on the mesh's ``ep`` axis) each
    device computes its local experts and XLA inserts one psum over ``ep``
    — no hand-written collectives, same GSPMD recipe as the Megatron TP
    rules (parallel/sharding.py).
  * ``moe_ffn_dispatch`` — GShard-style capacity-based dispatch/combine
    one-hot einsums: tokens route to per-expert queues of ``capacity``
    slots, experts run a batched SwiGLU over their queues, outputs combine
    back weighted by the gates. FLOPs scale with k/num_experts instead of
    num_experts — the TRAINING forward's path at large token counts. Tokens
    beyond an expert's capacity are dropped (their contribution from that
    expert is zero), the standard training trade; with generous capacity
    the result is bit-identical to the dense path (tested).

A chip may hold a SHARE of a layer's experts (``cfg.experts_held`` of the
router's ``cfg.num_experts``, from ``cfg.first_expert`` on: wide expert
parallelism, each layer divided over several chips). The router keeps its
published width and top-k; every FFN here computes the part of the result
that the HELD experts give for the tokens routed to them (``local_picks``),
and what the absent ones would add is left out — on one chip the layer runs
without its exchange; ``moe_ffn_grouped`` lays out only the picks that
landed here, and ``moe_ffn_visit`` visits only the held experts among them.

Replaces: nothing in the reference — its only MoE access is the cloud
qwen3:30b endpoint behind the api-gateway (api-gateway/src/main.rs:70-88).
Serving the Qwen3-30B-A3B tier locally is a TPU-build extension.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import ops
from ..ops import expert_group, expert_visit
from .config import ModelConfig


def _expert_einsum(spec: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """einsum where w may be a dense array or an int8 leaf {"q", "s"}.

    Quantized expert leaves keep per-output-channel scales on a size-1
    contraction axis (model.quantize_params, axis=-2), so scaling the
    einsum output by a broadcast of ``s`` reproduces the dequantized
    result — the expert-stacked twin of model.matmul. The spec's output
    must keep the expert axis leading (``x...``): the scales are
    per-(expert, out-channel), so they can only be applied before any
    reduction over experts.
    """
    if isinstance(w, dict):
        w_q, s = w["q"], w["s"]
        assert spec.split("->")[1][0] == "x", spec
        y = jnp.einsum(
            spec, x, w_q, preferred_element_type=jnp.float32
        )
        # s [X, 1, out] -> [X, 1, out] broadcasting over the token/queue axis
        return (y * jnp.squeeze(s, axis=-2)[:, None, :]).astype(x.dtype)
    return jnp.einsum(spec, x, w)


def _relu2(u: jnp.ndarray) -> jnp.ndarray:
    """An ungated expert's activation (``cfg.expert_act`` "relu2"):
    relu(u)^2, squared in float32, in u's dtype."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(u.dtype)


def _within_best_groups(choice: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Scores [N, X] with every expert outside the ``cfg.topk_group`` best of
    the ``cfg.n_group`` groups of consecutive experts at -inf; a group's
    score is the sum of its two largest."""
    by_group = choice.reshape(choice.shape[0], cfg.n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, cfg.topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(cfg.n_group), axis=1)
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(choice.shape)


def route(
    h: jnp.ndarray,  # [N, E] normalized hidden states
    w_router,  # [E, X]
    cfg: ModelConfig,
    bias=None,  # [X] float32: a layer's ``router_bias`` leaf, where it has one
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing over ALL ``cfg.num_experts`` experts, whatever share
    of them is held here. Returns (probs [N, X] fp32, weights [N, k] fp32,
    idx [N, k] int32). ``probs`` is the full softmax (for the
    load-balancing aux loss); ``weights`` are the selected gates,
    renormalized over the top-k set when cfg.norm_topk_prob (the
    Mixtral/Qwen3-MoE convention). With ``cfg.moe_scoring == "sigmoid"``
    (the DeepSeek-V3 family) the scores are independent sigmoids, the top-k
    of them renormalized and then scaled by ``cfg.routed_scaling_factor``.
    A selection ``bias`` (the layer tree's ``router_bias`` leaf: the
    auxiliary-loss-free balancing of that family) is added to the scores for
    the CHOICE of the top-k only; the weights are the unbiased scores of
    the chosen. With ``cfg.n_group`` the choice is group-limited: the
    experts are n_group groups of consecutive experts, a group's score is
    the sum of its two largest (biased) scores, and only the experts of
    the ``cfg.topk_group`` best groups can be chosen."""
    if isinstance(w_router, dict):  # never quantized, but be safe
        w_router = w_router["q"].astype(jnp.float32) * w_router["s"]
    logits = (h.astype(jnp.float32) @ w_router.astype(jnp.float32))
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        if cfg.n_group:
            choice = _within_best_groups(choice, cfg)
        if choice is scores:
            weights, idx = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        else:
            _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
            weights = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights * jnp.float32(cfg.routed_scaling_factor)
        return scores, weights, idx.astype(jnp.int32)
    if cfg.moe_scoring != "softmax":
        raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
    if bias is not None:
        raise ValueError(
            f"{cfg.name}: a router_bias leaf (selection bias) goes with "
            "sigmoid scoring; softmax routing has none"
        )
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, weights, idx.astype(jnp.int32)


def local_picks(weights: jnp.ndarray, idx: jnp.ndarray, cfg: ModelConfig):
    """The router's picks as the HELD experts see them: (weights with the
    picks of absent experts zeroed, idx relative to ``cfg.first_expert`` and
    clipped into the held range, which picks are held here). With every
    expert held this is the identity, traces nothing, and the third is
    None."""
    if not cfg.expert_share:
        return weights, idx, None
    rel = idx - cfg.first_expert
    here = (rel >= 0) & (rel < cfg.held_experts)
    return (
        jnp.where(here, weights, 0.0),
        jnp.clip(rel, 0, cfg.held_experts - 1),
        here,
    )


PICK_STATS = 4  # the numbers of ``pick_stats``


def pick_stats(here: jnp.ndarray, expert_rows, visited, picks=None) -> jnp.ndarray:
    """int32 [PICK_STATS]: (picks the router made, picks that fell on an
    expert held here, rows the expert matmuls computed, experts whose
    weights were read) for one layer's call. ``here`` [N, k] marks the
    counted picks that landed here; ``picks`` is how many were counted where
    that is not every row's (a decode step counts its live rows' alone)."""
    return jnp.stack([
        jnp.asarray(here.size if picks is None else picks, jnp.int32),
        jnp.sum(here, dtype=jnp.int32),
        jnp.asarray(expert_rows, jnp.int32),
        jnp.asarray(visited, jnp.int32),
    ])


def gate_matrix(
    weights: jnp.ndarray, idx: jnp.ndarray, num_experts: int
) -> jnp.ndarray:
    """Scatter top-k (weights, idx) into a full [N, X] gate matrix."""
    onehot = jax.nn.one_hot(idx, num_experts, dtype=weights.dtype)  # [N,k,X]
    return jnp.einsum("nk,nkx->nx", weights, onehot)


def load_balance_aux(
    probs: jnp.ndarray, idx: jnp.ndarray, num_experts: int
) -> jnp.ndarray:
    """Switch-transformer load-balancing loss for one layer:
    X * sum_x(fraction_of_tokens_routed_to_x * mean_router_prob_x).
    Equals 1.0 under perfect balance; minimized jointly with the LM loss
    (train.py weights it by moe_aux_coef)."""
    X = num_experts
    counts = jnp.sum(
        jax.nn.one_hot(idx, X, dtype=jnp.float32), axis=(0, 1)
    )  # [X] — how many (token, slot) picks landed on each expert
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    mean_prob = jnp.mean(probs, axis=0)
    return X * jnp.sum(frac * mean_prob)


def moe_ffn_dense(
    h: jnp.ndarray,  # [B, T, E] normalized hidden states
    lp,  # layer params holding w_router / we_gate / we_up / we_down
    cfg: ModelConfig,
    with_stats: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Exact dropless MoE FFN over the experts held here; returns (out
    [B, T, E], aux scalar fp32), and with ``with_stats`` ``pick_stats``
    third (every held expert runs over every token)."""
    B, T, E = h.shape
    flat = h.reshape(B * T, E)
    probs, weights, idx = route(flat, lp["w_router"], cfg, lp.get("router_bias"))
    weights, idx_here, here = local_picks(weights, idx, cfg)
    gates = gate_matrix(weights, idx_here, cfg.held_experts).astype(h.dtype)

    if cfg.expert_act == "relu2":  # ungated: one up stack, [X, F, E]
        z = _relu2(_expert_einsum("ne,xfe->xnf", flat, lp["we_up_t"]))
    else:
        if "we_gateup" in lp:  # fused serving layout (model.quantize_params)
            F = cfg.expert_dim
            gu = _expert_einsum("ne,xef->xnf", flat, lp["we_gateup"])
            g, u = gu[..., :F], gu[..., F:]
        else:
            g = _expert_einsum("ne,xef->xnf", flat, lp["we_gate"])
            u = _expert_einsum("ne,xef->xnf", flat, lp["we_up"])
        z = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u  # [X, N, F]
    z = z * gates.T[..., None]  # gate before down-proj: scales per (x, n)
    # Down-project then contract the expert axis — one psum over ep under
    # GSPMD. Quantized leaves need the per-expert scale applied before the
    # expert reduction, hence the explicit xne intermediate + sum.
    if isinstance(lp["we_down"], dict):
        y = _expert_einsum("xnf,xfe->xne", z, lp["we_down"])
        out = jnp.sum(y.astype(jnp.float32), axis=0).astype(h.dtype)
    else:
        out = jnp.einsum("xnf,xfe->ne", z, lp["we_down"])
    aux = load_balance_aux(probs, idx, cfg.num_experts)
    if with_stats:
        if here is None:
            here = jnp.ones(idx.shape, jnp.bool_)
        return out.reshape(B, T, E), aux, pick_stats(
            here, B * T * cfg.held_experts, cfg.held_experts
        )
    return out.reshape(B, T, E), aux


def moe_ffn_dispatch(
    h: jnp.ndarray,  # [B, T, E] normalized hidden states
    lp,
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-based GShard dispatch MoE FFN; returns (out, aux).

    ``capacity`` (per-expert queue length) defaults to
    ceil(N * k / X * capacity_factor) rounded up to a multiple of 8 —
    static, so the jit graph is fixed-shape regardless of routing.
    """
    B, T, E = h.shape
    N = B * T
    X, k = cfg.held_experts, cfg.num_experts_per_tok
    flat = h.reshape(N, E)
    probs, weights, idx_all = route(flat, lp["w_router"], cfg, lp.get("router_bias"))
    weights, idx, here = local_picks(weights, idx_all, cfg)
    if here is not None:
        # picks of absent experts one-hot off the end: they queue nowhere
        idx = jnp.where(here, idx, X)

    if capacity is None:
        capacity = max(8, int(-(-N * k * capacity_factor // X)))
        capacity = min(-(-capacity // 8) * 8, N * k)

    # Queue position of each (token, slot) pick within its expert, in
    # (token-major, slot-minor) priority order: a running count of prior
    # picks of the same expert.
    onehot = jax.nn.one_hot(idx, X, dtype=jnp.int32).reshape(N * k, X)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)  # picks before this one
    pos = jnp.sum(pos * onehot, axis=-1).reshape(N, k)  # [N, k]
    keep = pos < capacity  # dropped picks contribute zero

    # dispatch [N, k, X, cap] collapses to bool [N, X, cap]; combine is the
    # same structure carrying the gate weights.
    slot_oh = jax.nn.one_hot(
        jnp.where(keep, pos, capacity), capacity, dtype=h.dtype
    )  # [N, k, cap] — overflow rows one-hot off the end -> all-zero
    exp_oh = jax.nn.one_hot(idx, X, dtype=h.dtype)  # [N, k, X]
    combine = jnp.einsum(
        "nk,nkx,nkc->nxc", weights.astype(h.dtype), exp_oh, slot_oh
    )
    dispatch = jnp.einsum("nkx,nkc->nxc", exp_oh, slot_oh)

    xe = jnp.einsum("nxc,ne->xce", dispatch, flat)  # [X, cap, E]
    if cfg.expert_act == "relu2":
        z = _relu2(_expert_einsum("xce,xfe->xcf", xe, lp["we_up_t"]))
    else:
        if "we_gateup" in lp:
            F = cfg.expert_dim
            gu = _expert_einsum("xce,xef->xcf", xe, lp["we_gateup"])
            g, u = gu[..., :F], gu[..., F:]
        else:
            g = _expert_einsum("xce,xef->xcf", xe, lp["we_gate"])
            u = _expert_einsum("xce,xef->xcf", xe, lp["we_up"])
        z = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
    ye = _expert_einsum("xcf,xfe->xce", z, lp["we_down"])
    out = jnp.einsum("nxc,xce->ne", combine, ye)
    aux = load_balance_aux(probs, idx_all, cfg.num_experts)
    return out.reshape(B, T, E), aux


# the leaves of an expert layer that hold one matrix an expert
# (``we_up_t``: an UNGATED expert's up matrix, transposed: ``[X, F, E]`` with
# its scales ``[X, 1, F]``, a column of the matrix a row of the leaf, so that
# a width F that is no whole lane tiles lies along rows in both its matrices:
# ops/expert_visit.py ``supports_pallas``)
EXPERT_LEAVES = ("we_gateup", "we_gate", "we_up", "we_down", "we_up_t")


def grouped_pays(n_tok: int, cfg: ModelConfig) -> bool:
    """Whether ``moe_ffn_grouped`` does less MXU work than dense-over-held
    for ``n_tok`` tokens, reckoned from static shapes. Either path streams a
    held expert's weights once at most, and a weight tile pushed through the
    MXU costs a PASS of 128 rows whatever fewer follow it (ops/expert_group.py),
    so the measure is rows in whole passes: the picks expected to land on a
    held expert plus at most one part-filled PASS a held expert, against
    every held expert over every token. At 2 of 8 with all held that is
    2,048 against 4,096 rows for 512 tokens and 1,280 against 1,024 for 128;
    at 8 of 256 with 16 held, 2,176 against 4,096 for 256 tokens and 2,112
    against 2,048 for 128. Timed alone on the v5e both ways (PERF.md, PR 37)
    the buckets of 64 and 128 tokens that a reckoning in ROW_BLOCKs would
    hand over run dense in 1.06-1.45 ms a layer against 1.31-1.55 grouped at
    64 experts and at the held sixteenth (every expert is touched anyway and
    the layout is extra); Mixtral's bucket 128 alone reads the other way
    (2.21 against 2.60) and stays where it was. A decode step, which is
    bound by the experts' bytes, is never asked (``visit_serves``), and
    under a sharding plan, where it is, its few rows do not pay."""
    held = cfg.held_experts
    picks = -(-n_tok * cfg.num_experts_per_tok * held // cfg.num_experts)
    return picks + held * expert_group.PASS < held * n_tok


def grouped_serves(
    n_tok: int, cfg: ModelConfig, moe_dense: bool = False,
    allow_dispatch: bool = False,
) -> bool:
    """Whether a graph over ``n_tok`` tokens runs its expert layers through
    ``moe_ffn_grouped``: not an engine's under a sharding plan
    (``moe_dense``), not the training forward (its loop runs a
    data-dependent number of trips, which reverse-mode differentiation
    cannot unroll), and the path pays. model.ffn and the layer scans that
    hand it the expert stacks whole ask the same question."""
    return (
        cfg.moe and not moe_dense and not allow_dispatch
        and grouped_pays(n_tok, cfg)
    )


def visit_serves(cfg: ModelConfig, moe_dense: bool = False) -> bool:
    """Whether a DECODE step's graph runs its expert layers through
    ``moe_ffn_visit``: a model with a router, not under a sharding plan.
    That the graph is a decode step is its own to say: it hands model.ffn
    its live-slot mask, and its layer scan hands the expert stacks whole."""
    return cfg.moe and not moe_dense


def _first_stack(lp, cfg: ModelConfig):
    """The stack an expert's first product reads: the fused [gate | up], or
    an ungated expert's up matrices alone, transposed (EXPERT_LEAVES; None
    where the layout has neither: the unfused leaves of a float tree)."""
    return lp.get("we_up_t" if cfg.expert_act == "relu2" else "we_gateup")


def _kernel_act(cfg: ModelConfig) -> dict:
    """The two expert kernels' static ``act`` argument: nothing for the
    SwiGLU they default to (their jitted calls stay as they were)."""
    return {} if cfg.expert_act == "swiglu" else {"act": cfg.expert_act}


def _experts_in_place(lp, F: int, act: str = "swiglu"):
    """(swiglu(x, e), down(z, e)) of one layer's experts read WHERE THEY
    LIE: expert ``e``'s SwiGLU (``act`` "relu2": its ungated relu(up)^2) over
    rows ``x`` (in x's dtype) and its down
    product (float32), each matrix product indexing ``w[l, e]`` itself.
    ``lp``'s expert leaves may be one layer's ``[X, in, out]`` or, with
    ``lp["expert_layer"]`` the layer's index into them, the whole stacks
    ``[L, X, in, out]``."""
    whole = "expert_layer" in lp
    l = lp["expert_layer"] if whole else 0

    def at(a, e):  # expert e's matrix (or scales) where the stack holds it
        stack = a if whole else a[None]
        return jax.lax.dynamic_slice(
            stack, (l, e, 0, 0), (1, 1) + stack.shape[2:]
        )[0, 0]

    def qdot(x, w, e, spec="ni,io->no"):
        # [rows, in] @ expert e's [in, out] (``spec``: or its [out, in]);
        # float32 out
        if isinstance(w, dict):
            y = jnp.einsum(
                spec, x, at(w["q"], e), preferred_element_type=jnp.float32,
            )
            return y * at(w["s"], e)[0]
        return jnp.einsum(spec, x, at(w, e), preferred_element_type=jnp.float32)

    def swiglu(x, e):
        if act == "relu2":
            return _relu2(qdot(x, lp["we_up_t"], e, "ni,oi->no").astype(x.dtype))
        if "we_gateup" in lp:  # fused serving layout (quantize_params)
            gu = qdot(x, lp["we_gateup"], e).astype(x.dtype)
            a, u = gu[:, :F], gu[:, F:]
        else:
            a = qdot(x, lp["we_gate"], e).astype(x.dtype)
            u = qdot(x, lp["we_up"], e).astype(x.dtype)
        return jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * u

    return swiglu, lambda z, e: qdot(z, lp["we_down"], e)


def moe_ffn_visit(
    h: jnp.ndarray,  # [B, T, E] normalized hidden states
    lp,
    cfg: ModelConfig,
    live: jnp.ndarray,  # [B] bool — the slots that decode in this step
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact dropless MoE FFN of a DECODE step; returns (out, aux,
    ``pick_stats``).

    The rows of slots that are not ``live`` pick no expert (their result is
    zero and read by nobody). From the live rows' picks that fall on a held
    expert: which held experts are touched, in ascending order, and how many
    (``expert_visit.visit_list``: on the device, no readback). Each touched
    expert then runs over ALL the rows, gated per row by the router's weight
    (zero for a row that did not pick it), and the visits add up in float32:
    ``moe_ffn_dense`` restricted to the experts whose gate column is not all
    zero, reading nothing of the others. The trip count is the touched
    count. ``lp``'s expert leaves as for ``moe_ffn_grouped``: the decode
    steps' layer scans hand the stacks whole. On the chip the serving
    layout's int8 leaves go through ONE kernel a layer
    (ops/expert_visit.py); anything else, and the CPU, through a loop of the
    same products."""
    B, T, E = h.shape
    N, k, X, F = B * T, cfg.num_experts_per_tok, cfg.held_experts, cfg.expert_dim
    flat = h.reshape(N, E)
    probs, weights, idx = route(flat, lp["w_router"], cfg, lp.get("router_bias"))
    weights, idx_here, here = local_picks(weights, idx, cfg)
    rows = jnp.broadcast_to(live[:, None], (B, T)).reshape(N, 1)
    here = jnp.broadcast_to(rows, idx.shape) if here is None else here & rows
    gates = gate_matrix(jnp.where(here, weights, 0.0), idx_here, X)
    touched = jnp.any(
        jax.nn.one_hot(jnp.where(here, idx_here, X), X, dtype=jnp.bool_),
        axis=(0, 1),
    )
    visit, n = expert_visit.visit_list(touched)

    gu, dn, act = _first_stack(lp, cfg), lp["we_down"], _kernel_act(cfg)
    if (ops.use_pallas() and isinstance(gu, dict) and isinstance(dn, dict)
            and expert_visit.supports_pallas(E, F, **act)):
        stacks = (gu["q"], gu["s"], dn["q"], dn["s"])
        if "expert_layer" not in lp:
            stacks = tuple(a[None] for a in stacks)
        out = expert_visit.expert_visit(
            flat, gates, visit, n, lp.get("expert_layer", 0), *stacks, **act
        )
    else:
        swiglu, down = _experts_in_place(lp, F, cfg.expert_act)

        def one(i, acc):
            e = visit[i]
            gate = jax.lax.dynamic_slice_in_dim(gates, e, 1, axis=1)
            return acc + down(swiglu(flat, e) * gate.astype(h.dtype), e)

        out = jax.lax.fori_loop(0, n, one, jnp.zeros((N, E), jnp.float32))
    aux = load_balance_aux(probs, idx, cfg.num_experts)
    return (
        out.astype(h.dtype).reshape(B, T, E), aux,
        pick_stats(here, n * N, n, picks=jnp.sum(rows, dtype=jnp.int32) * k),
    )


def _ranks(key: jnp.ndarray, X: int):
    """(rank [P], counts [X]) int32 of ``key`` [P] (an expert below ``X`` or
    ``X`` for none): how many earlier picks fell on the same expert, and how
    many fell on each. A running count over picks in two levels, blocks of
    128 picks through a triangular product (zeros and ones, summed in
    float32: exact) and the blocks' totals through a short cumsum: the
    cumsum over all the picks is a reduce-window of 100 us at 2,048 picks
    on the v5e (PERF.md, PR 37)."""
    P, B = key.shape[0], 128
    G = -(-P // B)
    hot = jax.nn.one_hot(
        jnp.pad(key, (0, G * B - P), constant_values=X), X, dtype=jnp.bfloat16
    ).reshape(G, B, X)
    before = jnp.tril(jnp.ones((B, B), jnp.bfloat16), -1)
    within = jnp.einsum(
        "rc,gcx->grx", before, hot, preferred_element_type=jnp.float32
    )
    totals = jnp.sum(hot, axis=1, dtype=jnp.float32)  # [G, X]
    upto = jnp.cumsum(totals, axis=0)
    rank = jnp.sum(
        (within + (upto - totals)[:, None, :]) * hot.astype(jnp.float32), axis=-1
    )
    return rank.reshape(G * B)[:P].astype(jnp.int32), upto[-1].astype(jnp.int32)


def moe_ffn_grouped(
    h: jnp.ndarray,  # [B, T, E] normalized hidden states
    lp,
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact dropless MoE FFN for PREFILL token counts; returns (out, aux,
    ``pick_stats``).

    The picks that fall on a held expert are laid out by expert, each
    expert's segment of rows rounded up to whole ROW_BLOCKs
    (``expert_group.segments``; no capacity is fixed and no pick is
    dropped). The unit of work is an expert's SEGMENT: on the chip the
    serving layout's int8 leaves go through ONE kernel a layer call
    (ops/expert_group.py), which streams each touched expert's weights once,
    WHERE THEY LIE in the stacks (the layer scans hand them whole, because a
    layer's slice taken by the scan would become an operand, and a copy),
    and runs them over that expert's own rows; anything else, and the CPU,
    through a loop of the same products over the same layout, a ROW_BLOCK a
    trip (``_experts_in_place``). Each token then adds up its picks' rows,
    gated, in float32. ``pick_stats`` counts the rows of the segments'
    blocks (every pick's row and the rounding) and the experts that have
    one."""
    B, T, E = h.shape
    N, k, X, F = B * T, cfg.num_experts_per_tok, cfg.held_experts, cfg.expert_dim
    RB = expert_group.ROW_BLOCK
    flat = h.reshape(N, E)
    probs, weights, idx = route(flat, lp["w_router"], cfg, lp.get("router_bias"))
    weights, idx_here, here = local_picks(weights, idx, cfg)
    if here is None:
        here = jnp.ones(idx.shape, jnp.bool_)
    key = jnp.where(here, idx_here, X).reshape(N * k)  # absent: no expert
    rank, counts = _ranks(key, X)
    blocks, first_row = expert_group.segments(counts)
    n_blocks = jnp.sum(blocks)
    # every pick a row of its own; the picks of absent experts off the end
    M = expert_group.buffer_rows(N * k, X)
    pos = jnp.where(
        key < X, first_row[jnp.minimum(key, X - 1)] + rank, M
    ).astype(jnp.int32)
    src = jnp.full((M,), N, jnp.int32).at[pos].set(
        jnp.arange(N * k, dtype=jnp.int32) // k, mode="drop"
    )
    x_rows = jnp.concatenate([flat, jnp.zeros((1, E), flat.dtype)])[src]

    gu, dn, act = _first_stack(lp, cfg), lp["we_down"], _kernel_act(cfg)
    if (ops.use_pallas() and isinstance(gu, dict) and isinstance(dn, dict)
            and expert_group.supports_pallas(E, F, **act)):
        stacks = (gu["q"], gu["s"], dn["q"], dn["s"])
        if "expert_layer" not in lp:
            stacks = tuple(a[None] for a in stacks)
        cap = expert_group.row_cap(E, F, flat.dtype.itemsize, **act)
        y_rows = expert_group.expert_group(
            x_rows, *expert_group.unit_list(blocks, cap, N * k),
            lp.get("expert_layer", 0), *stacks, cap=cap, **act,
        )
    else:
        swiglu, down = _experts_in_place(lp, F, cfg.expert_act)
        block_end = jnp.cumsum(blocks)

        def block(i, y_rows):
            e = jnp.sum(i >= block_end, dtype=jnp.int32)  # the block's expert
            x = jax.lax.dynamic_slice(x_rows, (i * RB, 0), (RB, E))
            return jax.lax.dynamic_update_slice(
                y_rows, down(swiglu(x, e), e), (i * RB, 0)
            )

        y_rows = jax.lax.fori_loop(
            0, n_blocks, block, jnp.zeros((M, E), jnp.float32)
        )
    # a token's result: its picks' rows, gated in float32 (a pick of an
    # absent expert reads nothing at weight zero)
    picked = y_rows.at[pos].get(mode="fill", fill_value=0).reshape(N, k, E)
    out = jnp.einsum("nk,nke->ne", weights.astype(jnp.float32), picked)
    aux = load_balance_aux(probs, idx, cfg.num_experts)
    return (
        out.astype(h.dtype).reshape(B, T, E), aux,
        pick_stats(here, n_blocks * RB, jnp.sum(blocks > 0)),
    )
