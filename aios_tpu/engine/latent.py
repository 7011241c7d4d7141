"""Multi-head latent attention (MLA) on the paged serving path.

The block of the DeepSeek-V3 family (openPangu-Ultra-MoE among it), as
``engine/model.py`` dispatches to it for ``cfg.mla`` models. One row of the
residual ``x``, ``h = rms(x)``:

    cq = rms(h W_dq)                      q = cq W_uq -> heads of [nope | rope]
    [c | k_r] = h W_dkv                   c = rms(c); k_r, q_rope rotated
    k_nope_i = c W_uk_i, v_i = c W_uv_i   per head i; k_r is shared by all heads
    s_ij = (q_nope_i . k_nope_j + q_rope_i . k_r_j) / sqrt(nope + rope)
    o = concat_i(softmax(s_i) v) W_o

The cache holds ``[c | k_r]`` a row a layer, not per-head keys and values
(engine/paged.py header: two pool arrays, ``[.., kv_lora_rank]`` and the
rotary part padded to 128 lanes). Checkpoint leaves: ``w_dqkv`` is
[W_dq | W_dkv] along columns, ``w_uq`` ``[q_lora_rank, heads * (nope + rope)]``,
``w_uk`` / ``w_uv`` the two column groups of the published W_ukv, each
``[kv_lora_rank, heads * dim]``. The graphs below read the per-head matrices
HEADS-MAJOR (``serving_layout``, once at load: ``w_uq_nope``
``[heads, q_lora_rank, nope]``, ``w_uq_rope`` ``[q_lora_rank, heads * rope]``,
``w_uk`` / ``w_uv`` ``[heads, kv_lora_rank, dim]``): the absorbed form's
products are batched over the heads, and the TPU compiler otherwise re-lays
the WEIGHTS heads-major inside every decode dispatch.

Two forms of the same attention:

* EXPANDED (prefill of new rows, over cached latent rows too): keys and
  values are made from the latents a block of rows at a time and attended
  with an online softmax, so neither the scores nor the expanded cache of a
  long prefix ever exist whole; the loop runs only over the blocks the
  newest row can see.
* ABSORBED (decode, one row a slot): ``q_lat_i = q_nope_i W_uk_i^T`` scores
  against ``c`` directly and ``o_i = (sum_j p_ij c_j) W_uv_i``, so a step
  reads the latent pages once for all heads
  (ops/paged_mla_attention.py) and expands nothing.

With ``cfg.sandwich_norm`` each sub-layer's output gets its own norm before
the residual add. The residual itself is ``engine/residual.py``'s: one row a
token, or with ``cfg.hc_mult`` > 1 several mixed streams; every entry point
below goes through its ``expand`` / ``pre`` / ``post`` / ``collapse``. With
``cfg.rope_factor`` > 1 the rotary part runs under YaRN (``rope_tables``,
``sm_scale``). The FFN is ``model.ffn``: a leading dense layer and an
expert layer differ only in their trees, and the stack is scanned segment by
segment (``model.layer_segments``) with the pools carried through.

Four variations of the block, each traced away where its field is at its
default: ``cfg.q_lora_rank`` 0 is one direct query projection (``w_dqkv`` is
then [W_q | W_dkv], and there is no ``w_uq`` or ``q_a_norm``);
``cfg.latent_qk_norm`` norms each query head (``q_head_norm``) and the shared
rotary key (``k_rope_norm``) before rotation: both key-side norms act on what
is CACHED, so the absorbed form holds; ``cfg.rope_interleave`` rotates pairs
(2i, 2i + 1) and keeps the rotated values in [evens | odds] order, in the
queries and in the cache alike (a score is a sum over pairs: their order does
not enter); ``cfg.attn_head_gate`` multiplies each head's output by
``sigmoid(h w_hgate)`` of its own row.

A stack may mix these layers with ``kda`` layers (``cfg.state_kinds``;
engine/kda.py has their mixer), five to one in the one model that does: the
scan's body is then one period whose layers' trees differ
(``model.scan_segments``), only the ``mla`` layers have a layer of the latent
pool (``kind_index`` of a layer's tree), and the graphs carry the state
kind's two arrays beside the pools (engine/paged.py header): they take them
as ``states`` and hand them back after the pools.

Entry points mirror ``model``'s and return the same tuples, with the pools
in the places of K and V, and like them ``moe.pick_stats`` summed over the
layers as one more value where the model has a router.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from . import kda, model, moe, residual
from .config import ModelConfig

NEG_INF = -1e30
Q_TILE = 512  # query rows attended at a time, and the kv block beside them


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def sm_scale(cfg: ModelConfig) -> float:
    scale = float(cfg.qk_head_dim) ** -0.5
    if cfg.rope_factor > 1.0 and cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """The rotary part's frequencies [rope / 2] under YaRN
    (``model.yarn_inv_freq`` has the blend)."""
    return model.yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_of(None))


def rope_tables(positions, cfg: ModelConfig):
    """cos / sin [.., rope] of the rotary part at ``positions``:
    ``model.rope_tables`` where there is no scaling (the same trace)."""
    dr = cfg.qk_rope_head_dim
    if cfg.rope_factor <= 1.0:
        return model.rope_tables(positions, dr, cfg.rope_theta)
    angles = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def _einsum32(spec: str, a, b):
    """einsum of bfloat16 (or int8) values accumulated in float32. The
    operands are widened first: the same values, the product XLA's TPU
    backend runs in one bfloat16 pass either way, and the CPU backend's dot
    thunk refuses bf16 x bf16 -> f32 at some shapes."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def _rope_pairs(x, cos, sin):
    """The rotary embedding over pairs (2i, 2i + 1) of x [B, T, H, D], the
    result in [evens | odds] order (cos / sin [B, T, D] as
    ``model.apply_rope`` takes them: pair i turns by column i)."""
    half = x.shape[-1] // 2
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    cos = cos[..., None, :half]
    sin = sin[..., None, :half]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _lay_heads(tree, heads: int, nope: int):
    """The checkpoint-layout leaves of a layer tree ({``w_uq`` / ``w_uk`` /
    ``w_uv``: an int8 leaf or a plain array}) in the serving layout: a column
    permutation and a transpose of whole blocks, no value changes. An int8
    leaf's scales [.., 1, heads * d] go the way of its values."""

    def cut(a):  # [.., K, heads * d] -> [.., K, heads, d]
        return a.reshape(*a.shape[:-1], heads, a.shape[-1] // heads)

    def by_head(a):  # -> [.., heads, K, d]
        return jnp.moveaxis(cut(a), -2, -3)

    out = {}
    for name, w in tree.items():
        if name == "w_uq":
            out["w_uq_nope"] = jax.tree.map(lambda a: by_head(a)[..., :nope], w)
            out["w_uq_rope"] = jax.tree.map(
                lambda a: cut(a)[..., nope:].reshape(*a.shape[:-1], -1), w
            )
        else:
            out[name] = jax.tree.map(by_head, w)
    return out


def _in_checkpoint_layout(name: str, w, cfg: ModelConfig) -> bool:
    """Whether leaf ``name`` of a layer tree still lies as the checkpoint
    stores it; False where it is heads-major already. Read from its shape."""
    if isinstance(w, dict) and "q4" in w:
        raise ValueError(
            f"{cfg.name}: {name} is an int4 leaf; the latent block's per-head "
            "products read int8 leaves and plain arrays only"
        )
    if name == "w_uq":
        return True  # the serving layout has no leaf of this name
    shape = (w["q"] if isinstance(w, dict) else w).shape
    rank = cfg.kv_lora_rank
    d = cfg.v_head_dim if name == "w_uv" else cfg.qk_nope_head_dim
    if shape[-3:] == (cfg.num_heads, rank, d):
        return False
    if shape[-2:] == (rank, cfg.num_heads * d):
        return True
    raise ValueError(
        f"{cfg.name}: {name} {shape} is neither [.., {rank}, "
        f"{cfg.num_heads} x {d}] nor [.., {cfg.num_heads}, {rank}, {d}]"
    )


def serving_layout(params, cfg: ModelConfig):
    """``params`` with every latent layer's per-head matrices in the layout
    the graphs below read (module header), and how many matrices were
    re-laid: (tree, count). Applied once where the weights reach the device
    (TPUEngine); a tree already in the serving layout passes through, count
    0. Int8 leaves and plain arrays alike; scales follow their columns."""
    relaid = 0

    def walk(tree):
        nonlocal relaid
        if not isinstance(tree, dict) or "q" in tree or "q4" in tree:
            return tree
        tree = {k: walk(v) for k, v in tree.items()}
        old = {
            name: tree[name] for name in ("w_uq", "w_uk", "w_uv")
            if name in tree and _in_checkpoint_layout(name, tree[name], cfg)
        }
        if not old:
            return tree
        relaid += len(old)
        new = _lay_heads(old, cfg.num_heads, cfg.qk_nope_head_dim)
        return {**{k: v for k, v in tree.items() if k not in old}, **new}

    return walk(params), relaid


def _head_rows(spec: str, x, w):
    """einsum ``spec`` of rows ``x`` with a heads-major leaf ``w`` (int8
    {"q", "s"} or a plain array) whose OUTPUT columns are the last axis of
    both the leaf and the result: the int8 values multiplied as they are,
    float32 accumulation, the per-column scales on the result."""
    if isinstance(w, dict):
        return (_einsum32(spec, x, w["q"]) * w["s"][:, 0]).astype(x.dtype)
    return _einsum32(spec, x, w).astype(x.dtype)


def _project(h, lp, cfg: ModelConfig, positions, qmm=None):
    """Normed rows h [B, T, E] -> (q_nope [B,T,H,nope], q_rope [B,T,H,rope]
    rotated, c [B,T,kv_lora_rank] normed, k_r [B,T,rope] rotated)."""
    B, T, _ = h.shape
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps = cfg.rms_norm_eps
    cos, sin = rope_tables(positions, cfg)
    rotate = _rope_pairs if cfg.rope_interleave else model.apply_rope
    with jax.named_scope("mla_q"):
        down = model.matmul(h, lp["w_dqkv"], qmm)
        if ql:
            cq = model.rms_norm(down[..., :ql], lp["q_a_norm"], eps)
            q_nope = _head_rows("btk,hkd->bthd", cq, lp["w_uq_nope"])
            q_rope = model.matmul(cq, lp["w_uq_rope"], qmm)
            q_rope = q_rope.reshape(B, T, cfg.num_heads, dr)
        else:  # a direct query projection: w_dqkv is [W_q | W_dkv]
            ql = cfg.num_heads * (dn + dr)
            q = down[..., :ql].reshape(B, T, cfg.num_heads, dn + dr)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
        if cfg.latent_qk_norm:  # over a head's nope and rope parts together
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            q = model.rms_norm(q, lp["q_head_norm"], eps)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = rotate(q_rope, cos, sin)
    c = model.rms_norm(down[..., ql:ql + kl], lp["kv_a_norm"], eps)
    k_r = down[..., ql + kl:]
    if cfg.latent_qk_norm:
        k_r = model.rms_norm(k_r, lp["k_rope_norm"], eps)
    k_r = rotate(k_r[:, :, None, :], cos, sin)
    return q_nope, q_rope, c, k_r[:, :, 0]


def _pad_rope(x, cfg: ModelConfig):
    """Rotary parts [..., rope] as the pool stores them: one whole lane
    tile, zeros above the rotary dims."""
    return jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, cfg.kv_row_dims[1] - x.shape[-1])]
    )


def _absorb_q(q_nope, lp):
    """q_lat_i = q_nope_i W_uk_i^T: [..., H, nope] -> [..., H, kv_lora_rank].
    An int8 leaf's per-column scales lie on the contracted axis here, so
    they go onto the query first."""
    w = lp["w_uk"]
    q = q_nope.astype(jnp.float32)
    if isinstance(w, dict):
        q, w = q * w["s"][:, 0], w["q"]
    return _einsum32("...hd,hcd->...hc", q, w).astype(q_nope.dtype)


def _unabsorb_o(o_lat, lp):
    """o_i = o_lat_i W_uv_i: [..., H, kv_lora_rank] -> [..., H * v_head_dim]."""
    o = _head_rows("...hc,hcd->...hd", o_lat, lp["w_uv"])
    return o.reshape(*o_lat.shape[:-2], -1)


def _expand(c_rows, lp):
    """Latent rows [S, kv_lora_rank] -> (k_nope [S, H, nope], v [S, H, v])."""
    return (
        _head_rows("sc,hcd->shd", c_rows, lp["w_uk"]),
        _head_rows("sc,hcd->shd", c_rows, lp["w_uv"]),
    )


def _attend_expanded(q_nope, q_rope, q_pos, kv_block, n_blocks, blk: int,
                     scale: float, v_dim: int):
    """Expanded attention of ONE sequence's query rows (q_nope [T, H, nope],
    q_rope [T, H, rope], at absolute positions q_pos [T]) over kv blocks
    0 .. n_blocks-1 of ``blk`` rows: an online softmax in float32, so the
    [T, S] scores never exist whole. ``kv_block(j)`` gives block j's
    (k_nope [blk, H, nope], k_r [blk, rope], v [blk, H, v]); ``n_blocks``
    may be traced (the loop then runs only as far as the newest row sees).
    Row i sees column j iff j <= q_pos[i]; block 0 always holds column 0, so
    no row's denominator is zero. Returns [T, H, v]."""
    T, H, _ = q_nope.shape
    f32 = jnp.float32

    def fold(j, carry):
        m, l, acc = carry
        k_nope, k_r, v = kv_block(j)
        s = (_einsum32("thd,shd->hts", q_nope, k_nope)
             + _einsum32("thr,sr->hts", q_rope, k_r)) * scale
        cols = j * blk + jnp.arange(blk)
        s = jnp.where((cols[None, :] <= q_pos[:, None])[None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + _einsum32(
            "hts,shd->htd", p.astype(v.dtype), v
        )
        return m_new, l, acc

    init = (
        jnp.full((H, T), NEG_INF, f32),
        jnp.zeros((H, T), f32),
        jnp.zeros((H, T, v_dim), f32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_blocks, fold, init)
    return (acc / l[..., None]).transpose(1, 0, 2).astype(q_nope.dtype)


def _attend_own_rows(q_nope, q_rope, c, k_r, lp, cfg: ModelConfig):
    """Causal expanded attention of one sequence over its own T rows (the
    whole-prompt prefill): keys and values are expanded once, queries go a
    Q_TILE at a time and each tile stops at its own diagonal block."""
    T = q_nope.shape[0]
    k_nope, v = _expand(c, lp)
    tiled = T > Q_TILE and T % Q_TILE == 0
    blk = Q_TILE if tiled else T

    def kv_block(j):
        at = j * blk
        return (
            jax.lax.dynamic_slice_in_dim(k_nope, at, blk),
            jax.lax.dynamic_slice_in_dim(k_r, at, blk),
            jax.lax.dynamic_slice_in_dim(v, at, blk),
        )

    def tile(i, qn, qr):
        return _attend_expanded(
            qn, qr, i * blk + jnp.arange(blk), kv_block, i + 1, blk,
            sm_scale(cfg), cfg.v_head_dim,
        )

    if not tiled:
        return tile(0, q_nope, q_rope)
    n = T // blk
    out = jax.lax.map(
        lambda a: tile(*a),
        (jnp.arange(n), q_nope.reshape(n, blk, *q_nope.shape[1:]),
         q_rope.reshape(n, blk, *q_rope.shape[1:])),
    )
    return out.reshape(T, *out.shape[2:])


def _attn_input(x, lp, cfg: ModelConfig):
    """(normed rows the attention reads, what the residual's ``post`` needs
    of this sub-layer's mix: None for a one-row residual)."""
    u, mix = residual.pre(x, lp, "attn", cfg)
    return model.rms_norm(u, lp["attn_norm"], cfg.rms_norm_eps), mix


def _head_gate(attn_flat, h, lp, cfg: ModelConfig):
    """Each head's output [.., H * v] times sigmoid(h w_hgate) of its row."""
    gate = jax.nn.sigmoid(
        h.astype(jnp.float32) @ lp["w_hgate"].astype(jnp.float32)
    )
    heads = attn_flat.reshape(*attn_flat.shape[:-1], cfg.num_heads, -1)
    return (heads * gate[..., None].astype(heads.dtype)).reshape(attn_flat.shape)


def _finish_block(x, mix, attn_flat, lp, cfg: ModelConfig, moe_dense, qmm,
                  allow_dispatch: bool = False, live=None, gate_rows=None,
                  mixed=None):
    """Output projection and FFN of one block, with the sandwich norms;
    returns (x', moe_aux, stats-or-None). ``live``: a decode step's slot
    mask (model.ffn). ``gate_rows``: the normed rows the attention read,
    where ``cfg.attn_head_gate`` gates its heads by them. ``mixed``: a kda
    layer's mixer output, already through its own output projection."""
    eps = cfg.rms_norm_eps
    if mixed is not None:
        x = residual.post(x, mixed, mix, cfg)
    else:
        with jax.named_scope("mla_out"):
            if cfg.attn_head_gate:
                attn_flat = _head_gate(attn_flat, gate_rows, lp, cfg)
            a = model.matmul(attn_flat, lp["wo"], qmm, "row")
            if cfg.sandwich_norm:
                a = model.rms_norm(a, lp["post_attn_norm"], eps)
            x = residual.post(x, a, mix, cfg)
    u, mix = residual.pre(x, lp, "ffn", cfg)
    h = model.rms_norm(u, lp["ffn_norm"], eps)
    m, aux, stats = model.ffn(h, lp, cfg, allow_dispatch, moe_dense, qmm, live)
    if cfg.sandwich_norm:
        m = model.rms_norm(m, lp["post_ffn_norm"], eps)
    return residual.post(x, m, mix, cfg), aux, stats


def _scan(block, carry, params, cfg: ModelConfig, experts_whole: bool):
    """``model.scan_segments`` over this model's stack: segment by segment,
    or period by period where kda and mla layers mix."""
    return model.scan_segments(
        block, carry, model.layer_segments(params), experts_whole,
        cfg.period_kinds, cfg.lead_kinds,
    )


def _pool_layer(lp, l):
    """The latent pool's layer of this block: its place among the mla
    layers where the stack has others, else its index."""
    return lp.get("kind_index", l)


def forward_with_kv(params, cfg: ModelConfig, tokens, attn_fn=None,
                    with_aux: bool = False, qmm=None,
                    moe_dense: bool = False, logit_row=None):
    """``model._forward_with_kv`` for a latent-attention model: (logits
    [B, T, V], latents [L, B, T, 1, kv_lora_rank], padded rotary parts
    [L, B, T, 1, 128][, mean moe aux][, stats]). A stack with kda layers
    returns None for both: it admits in chunks alone (the engine's rule),
    and this whole-prompt forward is its parity path."""
    if attn_fn is not None:
        raise ValueError(
            f"{cfg.name}: latent attention has no sequence-sharded prefill"
        )
    if cfg.state_kinds and with_aux:
        raise ValueError(
            f"{cfg.name}: the training forward (with_aux) has no kda layers"
        )
    B, T = tokens.shape
    x = residual.expand(params["embed"][tokens], cfg)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    def block(carry, layer):
        x, *stats = carry
        lp, _ = layer
        h, mix = _attn_input(x, lp, cfg)
        if model.kind_of(lp) == "kda":
            y, _, _ = kda.mix_prompt(h, lp, cfg, T, qmm)
            x, _, new = _finish_block(
                x, mix, None, lp, cfg, moe_dense, qmm, mixed=y
            )
            return (x, *model.add_stats(stats, new)), None
        q_nope, q_rope, c, k_r = _project(h, lp, cfg, positions, qmm)
        with jax.named_scope("attention"):
            attn = jax.vmap(
                lambda qn, qr, c1, kr1: _attend_own_rows(
                    qn, qr, c1, kr1, lp, cfg
                )
            )(q_nope, q_rope, c, k_r)
        x, aux, new = _finish_block(
            x, mix, attn.reshape(B, T, -1), lp, cfg, moe_dense, qmm, with_aux,
            gate_rows=h,
        )
        if cfg.state_kinds:
            return (x, *model.add_stats(stats, new)), None
        rows = (c[:, :, None, :], _pad_rope(k_r, cfg)[:, :, None, :], aux)
        return (x, *model.add_stats(stats, new)), rows

    (x, *stats), rows = _scan(
        block, (x, *model.zero_stats(cfg)), params, cfg,
        moe.grouped_serves(B * T, cfg, moe_dense, with_aux),
    )
    cs, rs, auxs = rows or (None, None, None)
    if logit_row is not None:  # the one row a prefill samples from
        x = jax.lax.dynamic_slice_in_dim(x, logit_row, 1, axis=1)
    logits = model._final_logits(
        residual.collapse(x, params, cfg), params, cfg, qmm
    )
    out = (logits, cs, rs)
    if with_aux:
        out += (jnp.mean(auxs),)
    return out + tuple(stats)


def _write_chunk(pool, l, rows, pages, off):
    """A chunk's rows [T, W] into layer ``l`` of the pool: whole pages
    through ``ops.write_rows``, and a chunk of at most ONE page as one slice
    update. A one-page chunk (T == P) must not go the scatter's way: with a
    single page index the TPU compiler relays the whole pool out and back
    around it (two pool-sized copies and 3.5 GB of temporaries in the
    compiled final-128 graph; chipless compile, PR 27)."""
    P = pool.shape[2]
    if rows.shape[0] > P:
        return ops.write_rows(pool, l, rows, pages, off)
    return jax.lax.dynamic_update_slice(
        pool, rows.astype(pool.dtype)[None, None], (l, pages[0], off, 0)
    )


def prefill_chunk_paged(params, cfg: ModelConfig, tokens, start, c_pool,
                        r_pool, table_row, qmm=None,
                        moe_dense: bool = False, states=(), slot=None,
                        n_valid=None):
    """``model.prefill_chunk_paged`` over the latent pool: the chunk's
    latent rows are written by whole pages (or inside one), then each new
    row attends, in the EXPANDED form, over the slot's cached latent rows
    and the chunk's own — a turn's task behind its cached system prompt.
    Returns (logits [1, Tc, V], c_pool', r_pool'[, stats]).

    ``states`` (a stack with kda layers: the state kind's two arrays,
    engine/paged.py header) is carried beside the pools and handed back
    after them; ``slot`` is whose state the chunk advances and ``n_valid``
    how many of its rows are real (None: all)."""
    B, Tc = tokens.shape
    MB = table_row.shape[0]
    P = c_pool.shape[2]
    C_log = MB * P
    dr = cfg.qk_rope_head_dim
    x = residual.expand(params["embed"][tokens], cfg)
    positions = start + jnp.arange(Tc)[None, :]
    pages, off = model.chunk_pages(table_row, start, Tc, P)
    blk = Q_TILE if C_log % Q_TILE == 0 else P
    n_blocks = (start + Tc + blk - 1) // blk  # as far as the newest row sees
    if states:  # the scan carries the slot's own tails, not every slot's
        states, tails = (states[0], kda.slot_tails(states[1], slot)), states[1]

    def block(carry, layer):
        x, c_pool, r_pool, states, *stats = carry
        lp, l = layer
        h, mix = _attn_input(x, lp, cfg)
        if model.kind_of(lp) == "kda":
            y, *states = kda.mix_chunk(
                h, lp, cfg, *states, lp["kind_index"], slot, start,
                Tc if n_valid is None else n_valid, qmm,
            )
            x, _, new = _finish_block(
                x, mix, None, lp, cfg, moe_dense, qmm, mixed=y
            )
            return (x, c_pool, r_pool, tuple(states),
                    *model.add_stats(stats, new)), None
        l = _pool_layer(lp, l)
        q_nope, q_rope, c, k_r = _project(h, lp, cfg, positions, qmm)
        with jax.named_scope("mla_kv_write"):
            c_pool = _write_chunk(c_pool, l, c[0], pages, off)
            r_pool = _write_chunk(r_pool, l, _pad_rope(k_r[0], cfg), pages, off)
        with jax.named_scope("attention"):
            # the slot's logical view (a copy of its pages; the decode
            # kernel reads them in place)
            c_all = c_pool[l, table_row].reshape(C_log, -1)
            r_all = r_pool[l, table_row].reshape(C_log, -1)

            def kv_block(j):
                c_blk = jax.lax.dynamic_slice_in_dim(c_all, j * blk, blk)
                r_blk = jax.lax.dynamic_slice_in_dim(r_all, j * blk, blk)
                k_nope, v = _expand(c_blk.astype(h.dtype), lp)
                return k_nope, r_blk[:, :dr].astype(h.dtype), v

            attn = _attend_expanded(
                q_nope[0], q_rope[0], positions[0], kv_block, n_blocks, blk,
                sm_scale(cfg), cfg.v_head_dim,
            )
        x, _, new = _finish_block(
            x, mix, attn.reshape(B, Tc, -1), lp, cfg, moe_dense, qmm,
            gate_rows=h,
        )
        return (x, c_pool, r_pool, states, *model.add_stats(stats, new)), None

    (x, c_pool, r_pool, states, *stats), _ = _scan(
        block, (x, c_pool, r_pool, tuple(states), *model.zero_stats(cfg)),
        params, cfg, moe.grouped_serves(B * Tc, cfg, moe_dense),
    )
    if states:
        states = (states[0], kda.put_slot_tails(tails, states[1], slot))
    logits = model._final_logits(
        residual.collapse(x, params, cfg), params, cfg, qmm
    )
    return (logits, c_pool, r_pool, *states, *stats)


def _write_targets(tables, rows, active, P: int):
    """(pages, offsets) of logical rows [B] or [B, T] through the tables;
    inactive slots write the sacrificial page 0's last row."""
    act = active.reshape(active.shape + (1,) * (rows.ndim - 1))
    pages = jnp.take_along_axis(
        tables, (rows // P).reshape(rows.shape[0], -1), axis=1
    ).reshape(rows.shape)
    return jnp.where(act, pages, 0), jnp.where(act, rows % P, P - 1)


def decode_step_paged(params, cfg: ModelConfig, tokens, lengths, c_pool,
                      r_pool, tables, kernels: Optional[bool] = None,
                      active=None, moe_dense: bool = False, qmm=None,
                      states=()):
    """``model.decode_step_paged`` over the latent pool, in the ABSORBED
    form: each slot's new latent row is scattered to its page, and the
    kernel scores every head against the latent pages where they lie in the
    carried pool. Returns (logits [B, V], c_pool', r_pool'[, stats]); with
    ``states`` (prefill_chunk_paged) the state kind's arrays after the
    pools, each kda layer's states updated in place."""
    B = tokens.shape[0]
    P = c_pool.shape[2]
    if active is None:
        active = jnp.ones((B,), jnp.bool_)
    rows = jnp.where(active, lengths, 0)
    pages, offs = _write_targets(tables, rows, active, P)
    use_kernel = model._use_kernels(kernels)
    attend = (
        ops.paged_mla_decode_attention if use_kernel
        else ops.paged_mla_decode_attention_reference
    )
    with jax.named_scope("embed"):
        x = residual.expand(params["embed"][tokens][:, None, :], cfg)

    def block(carry, layer):
        x, c_pool, r_pool, states, *stats = carry
        lp, l = layer
        h, mix = _attn_input(x, lp, cfg)
        if model.kind_of(lp) == "kda":
            y, *states = kda.mix_step(
                h, lp, cfg, *states, lp["kind_index"], active, use_kernel, qmm
            )
            x, _, new = _finish_block(
                x, mix, None, lp, cfg, moe_dense, qmm, live=active, mixed=y
            )
            return (x, c_pool, r_pool, tuple(states),
                    *model.add_stats(stats, new)), None
        l = _pool_layer(lp, l)
        q_nope, q_rope, c, k_r = _project(h, lp, cfg, lengths[:, None], qmm)
        with jax.named_scope("mla_q"):
            q_lat = _absorb_q(q_nope[:, 0], lp)
        with jax.named_scope("mla_kv_write"):
            c_pool = c_pool.at[l, pages, offs].set(c[:, 0].astype(c_pool.dtype))
            r_pool = r_pool.at[l, pages, offs].set(
                _pad_rope(k_r[:, 0], cfg).astype(r_pool.dtype)
            )
        with jax.named_scope("attention"):
            o_lat = attend(
                q_lat, _pad_rope(q_rope[:, 0], cfg), c_pool, r_pool, l,
                tables, rows, sm_scale=sm_scale(cfg),
            )
        with jax.named_scope("mla_out"):
            attn = _unabsorb_o(o_lat, lp)[:, None]
        x, _, new = _finish_block(
            x, mix, attn, lp, cfg, moe_dense, qmm, live=active, gate_rows=h
        )
        return (x, c_pool, r_pool, states, *model.add_stats(stats, new)), None

    (x, c_pool, r_pool, states, *stats), _ = _scan(
        block, (x, c_pool, r_pool, tuple(states), *model.zero_stats(cfg)),
        params, cfg, moe.visit_serves(cfg, moe_dense),
    )
    with jax.named_scope("final_logits"):
        logits = model._final_logits(
            residual.collapse(x[:, 0], params, cfg), params, cfg, qmm
        )
    return (logits, c_pool, r_pool, *states, *stats)


def verify_step_paged(params, cfg: ModelConfig, tokens, lengths, c_pool,
                      r_pool, tables, active=None,
                      moe_dense: bool = False, qmm=None):
    """``model.verify_step_paged`` over the latent pool (the constrained
    decoder's jump-ahead append): the T in-flight rows of every slot are
    scattered through the tables, and each attends, absorbed, over its
    slot's gathered latent view up to itself, one slot at a time. Returns
    (logits [B, T, V], c_pool', r_pool'[, stats])."""
    if cfg.state_kinds:
        raise ValueError(
            f"{cfg.name}: a verify step over kda layers would have to roll a "
            "rejected token back out of the recurrent state; no graph does"
        )
    B, T = tokens.shape
    MB, P = tables.shape[1], c_pool.shape[2]
    C = MB * P
    if active is None:
        active = jnp.ones((B,), jnp.bool_)
    positions = lengths[:, None] + jnp.arange(T)[None, :]  # [B, T]
    pages, offs = _write_targets(tables, jnp.minimum(positions, C - 1), active, P)
    qpos = jnp.where(active[:, None], positions, 0)
    x = residual.expand(params["embed"][tokens], cfg)
    scale = sm_scale(cfg)

    def slot_attend(args):
        q_lat, q_rope, c, r, pos = args  # [T,H,Dc] [T,H,Dr] [C,Dc] [C,Dr] [T]
        s = (
            _einsum32("thc,sc->hts", q_lat, c)
            + _einsum32("thr,sr->hts", q_rope, r)
        ) * scale
        s = jnp.where((jnp.arange(C)[None, :] <= pos[:, None])[None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
        return _einsum32("hts,sc->thc", p, c).astype(q_lat.dtype)

    def block(carry, layer):
        x, c_pool, r_pool, *stats = carry
        lp, l = layer
        h, mix = _attn_input(x, lp, cfg)
        q_nope, q_rope, c, k_r = _project(h, lp, cfg, positions, qmm)
        c_pool = c_pool.at[l, pages, offs].set(c.astype(c_pool.dtype))
        r_pool = r_pool.at[l, pages, offs].set(
            _pad_rope(k_r, cfg).astype(r_pool.dtype)
        )
        o_lat = jax.lax.map(slot_attend, (
            _absorb_q(q_nope, lp), _pad_rope(q_rope, cfg),
            c_pool[l, tables].reshape(B, C, -1).astype(h.dtype),
            r_pool[l, tables].reshape(B, C, -1).astype(h.dtype), qpos,
        ))
        x, _, new = _finish_block(
            x, mix, _unabsorb_o(o_lat, lp), lp, cfg, moe_dense, qmm,
            gate_rows=h,
        )
        return (x, c_pool, r_pool, *model.add_stats(stats, new)), None

    (x, c_pool, r_pool, *stats), _ = model.scan_segments(
        block, (x, c_pool, r_pool, *model.zero_stats(cfg)),
        model.layer_segments(params),
        moe.grouped_serves(B * T, cfg, moe_dense),
    )
    logits = model._final_logits(
        residual.collapse(x, params, cfg), params, cfg, qmm
    )
    return (logits, c_pool, r_pool, *stats)
