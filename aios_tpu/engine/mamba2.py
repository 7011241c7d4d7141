"""A stack of SUB-LAYERS on the serving path: every layer is ONE sub-layer
under one norm, ``x + F(rms(x))``, and F is a Mamba-2 state-space mixer that
keeps a fixed-size state a slot, a grouped-query attention layer that keeps
rows in pages, or an expert FFN alone (``config.SUBLAYER_KINDS``: ``mamba2``,
``full``, ``moe``; the Nemotron-H family's ``M``, ``*``, ``E``).

``engine/model.py`` dispatches here for ``cfg.sublayers`` models, as it does
to ``engine/latent.py`` for latent attention. The scan is ``model``'s
(``scan_segments``: the body is one period of the pattern, each kind's leaves
stacked over that kind's layers under ``by_kind``, the one norm over all of
them), the attention layer is ``model``'s grouped-query block without a
rotary embedding (``cfg.rotary``), the expert FFN is ``model.ffn``. What is
this module's own is the ``mamba2`` mixer. One row, ``h = rms(x)``, H heads of
P channels, a state of N values a channel, B and C in G groups of heads:

    [z | u | dt] = h W_in                  (H P | H P + 2 G N | H wide; the
                                           leaf may carry zero columns after
                                           them, up to whole lane tiles)
    c_t = silu(b + sum_j w_j * u_{t-taps+1+j})   a depthwise causal convolution
                                           of taps rows over u; rows before
                                           the start are zero
    [x | B | C] = c                        x [H, P]; B, C [G, N]
    dt = softplus(dt + dt_bias)            [H];  A = -exp(A_log), a head
    S' = e^{dt A} S + (dt x) B^T           [P, N] a head, float32
    y = S' C + D x
    out = rms_groups(y * silu(z)) W_out    the gate first, then the norm over
                                           G groups of H P / G channels

What a slot keeps a layer (engine/paged.py header: the state kind): ``S``
[H, P, N] float32 and the convolution's tail, the last taps - 1 rows of u,
bfloat16 (as [taps - 1, slot, width] a layer). Three forms, one a graph kind,
as engine/kda.py's:

* ``mix_prompt``: a whole prompt from a zero state (the chunked form).
* ``mix_chunk``: an admission chunk of one slot: state and tail in, state
  and tail out; a chunk that starts at row 0 reads zeros whatever the slot's
  last tenant left; rows beyond the chunk's true length are identity updates
  and do not advance the tail.
* ``mix_step``: a decode step, one row a live slot, the states updated in
  place in the carried pool (ops/mamba2.py); dead slots' states and tails are
  untouched.

State arithmetic is float32; u, z and dt enter as the bfloat16 the matmul
gives.

The entry points mirror ``model``'s and return the same tuples: the K/V pool
(of the stack's ``full`` layers alone) in the places of K and V, then the
state kind's two arrays, then ``moe.pick_stats`` summed over the expert
layers.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import ops
from ..ops import mamba2 as ssm_ops
from . import kda, model, moe
from .config import ModelConfig

# a slot's tails in and out of the array around a chunk's scan: kda's layout
slot_tails, put_slot_tails = kda.slot_tails, kda.put_slot_tails


def _project(h, lp, cfg: ModelConfig, qmm=None):
    """Normed rows h [.., E] -> (z [.., H P], u [.., H P + 2 G N] before the
    convolution, dt [.., H] f32 after the softplus, a = dt A [.., H] f32)."""
    inner, width = cfg.ssm_inner, cfg.ssm_conv_dim
    with jax.named_scope("mamba_in"):
        wide = model.matmul(h, lp["ssm_in"], qmm)
        z, u = wide[..., :inner], wide[..., inner:inner + width]
        dt = jax.nn.softplus(
            wide[..., inner + width:inner + width + cfg.ssm_heads].astype(jnp.float32)
            + lp["ssm_dt_bias"].astype(jnp.float32)
        )
        a = -jnp.exp(lp["ssm_A_log"].astype(jnp.float32)) * dt
    return z, u, dt, a


def _conv(u_all, lp, taps: int, T: int):
    """silu of the depthwise causal convolution with its bias: ``u_all``
    [taps - 1 + T, .., W] (the tail, then the new rows) -> [T, .., W] f32."""
    w = lp["ssm_conv"].astype(jnp.float32)  # [taps, W]
    u_all = u_all.astype(jnp.float32)
    acc = sum(w[j] * u_all[j:j + T] for j in range(taps))
    return jax.nn.silu(acc + lp["ssm_conv_b"].astype(jnp.float32))


def _heads(c, cfg: ModelConfig):
    """Convolved rows [.., W] -> (x [.., H, P], B [.., G, N], C [.., G, N])."""
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    lead = c.shape[:-1]
    x = c[..., :H * P].reshape(*lead, H, P)
    B = c[..., H * P:H * P + G * N].reshape(*lead, G, N)
    C = c[..., H * P + G * N:].reshape(*lead, G, N)
    return x, B, C


def _out(y, x, z, lp, cfg: ModelConfig, dtype, qmm=None):
    """y, x [.., H, P] float32, z [.., H P] -> the mixer's output [.., E]:
    the skip, the gate, the norm by groups, the output projection."""
    G = cfg.ssm_groups
    with jax.named_scope("mamba_out"):
        y = y + lp["ssm_D"].astype(jnp.float32)[:, None] * x
        y = y.reshape(*z.shape) * jax.nn.silu(z.astype(jnp.float32))
        g = y.reshape(*z.shape[:-1], G, -1)
        g = g * jax.lax.rsqrt(
            jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps
        )
        y = g.reshape(*z.shape) * lp["ssm_norm"].astype(jnp.float32)
        return model.matmul(y.astype(dtype), lp["wo"], qmm, "row")


def _rows(h, lp, cfg: ModelConfig, s0, tail, n_valid, qmm=None):
    """The chunked form over ONE sequence's rows h [T, E] from state ``s0``
    [H, P, N] and tail [taps - 1, W]; rows from ``n_valid`` on are identity
    updates. Returns (y [T, E], state after, tail after)."""
    T = h.shape[0]
    taps = cfg.ssm_conv
    z, u, dt, a = _project(h, lp, cfg, qmm)
    u_all = jnp.concatenate([tail.astype(u.dtype), u], axis=0)
    x, B, C = _heads(_conv(u_all, lp, taps, T), cfg)
    live = (jnp.arange(T) < n_valid)[:, None]
    dt, a = jnp.where(live, dt, 0.0), jnp.where(live, a, 0.0)
    pad = -T % ssm_ops.SUB
    xs = (x, dt, a, B, C)
    if pad:  # identity rows up to a whole sub-chunk
        xs = tuple(
            jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)) for v in xs
        )
    with jax.named_scope("mamba_chunk"):
        y, s = ssm_ops.chunked(*xs, s0, ops.use_pallas())
    # the last taps - 1 rows that were real: rows n_valid .. of [tail | u]
    new_tail = jax.lax.dynamic_slice_in_dim(u_all, n_valid, taps - 1, axis=0)
    return _out(y[:T], x, z, lp, cfg, h.dtype, qmm), s, new_tail.astype(tail.dtype)


def mix_prompt(h, lp, cfg: ModelConfig, n_valid, qmm=None):
    """Whole prompts h [B, T, E] from zero states; ``n_valid`` [B] or a
    scalar. Returns (y [B, T, E], states [B, H, P, N], tails [B, taps-1, W])."""
    (sh, th) = cfg.state_shapes
    B = h.shape[0]
    s0 = jnp.zeros(sh, jnp.float32)
    tail = jnp.zeros(th, h.dtype)
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    return jax.vmap(
        lambda hb, n: _rows(hb, lp, cfg, s0, tail, n, qmm)
    )(h, n_valid)


def mix_chunk(h, lp, cfg: ModelConfig, states, tail_rows, layer, slot, start,
              n_valid, qmm=None):
    """One admission chunk h [1, Tc, E] of ``slot`` against the state pool
    ``states`` [L, S + 1, H, P, N] and the slot's tails ``tail_rows``
    [L, taps - 1, W] (``slot_tails``) at mamba2 layer ``layer``. Returns
    (y [1, Tc, E], states', tail_rows')."""
    fresh = start == 0
    s0 = jnp.where(fresh, 0.0, states[layer, slot])
    tail = jnp.where(fresh, jnp.zeros((), tail_rows.dtype), tail_rows[layer])
    y, s, tail = _rows(h[0], lp, cfg, s0, tail, n_valid, qmm)
    states = jax.lax.dynamic_update_slice(
        states, s[None, None], (layer, slot, 0, 0, 0)
    )
    tail_rows = jax.lax.dynamic_update_slice(tail_rows, tail[None], (layer, 0, 0))
    return y[None], states, tail_rows


def mix_step(h, lp, cfg: ModelConfig, states, tails, layer, active,
             use_kernel: bool, qmm=None):
    """A decode step: h [B, 1, E], one row a slot (B = the pool's S slots,
    slot b's state at ``states[layer, b]``); a dead slot is handed the
    scratch slot (row S) and an identity update. Returns (y [B, 1, E],
    states', tails')."""
    B = h.shape[0]
    taps = cfg.ssm_conv
    z, u, dt, a = _project(h[:, 0], lp, cfg, qmm)  # [B, ..]
    old = tails[layer, :, :B]  # [taps - 1, B, W]
    u_all = jnp.concatenate([old, u[None].astype(old.dtype)], axis=0)
    c = _conv(u_all, lp, taps, 1)[0]  # [B, W]: the taps lead, as a chunk's rows do
    x, Bm, C = _heads(c, cfg)
    dt = jnp.where(active[:, None], dt, 0.0)
    a = jnp.where(active[:, None], a, 0.0)
    slots = jnp.where(active, jnp.arange(B), states.shape[1] - 1)
    step = ssm_ops.mamba_step if use_kernel else ssm_ops.decode_step_reference
    with jax.named_scope("mamba_step"):
        y, states = step(x, dt, a, Bm, C, states, layer, slots)
    new = jnp.where(active[None, :, None], u_all[1:], old)
    tails = jax.lax.dynamic_update_slice(tails, new[None], (layer, 0, 0, 0))
    return _out(y, x, z, lp, cfg, h.dtype, qmm)[:, None], states, tails


# ---------------------------------------------------------------------------
# The stack: one scan whose body dispatches on the layer's kind
# ---------------------------------------------------------------------------


def _scan(block, carry, params, cfg: ModelConfig):
    """``model.scan_segments`` over this model's stack, period by period; the
    expert stacks stay whole at every token count (model._scan_periods)."""
    return model.scan_segments(
        block, carry, model.layer_segments(params), True, cfg.period_kinds,
    )


def _normed(x, lp, cfg: ModelConfig):
    return model.rms_norm(x, lp["norm"], cfg.rms_norm_eps)


def _rope(positions, cfg: ModelConfig):
    """(cos, sin) for the ``full`` layers at ``positions``; (None, None) where
    the model applies no rotary embedding (``cfg.rotary``: model.qkv_of)."""
    if not cfg.rotary:
        return None, None
    return model.rope_tables_of(positions, cfg.head_dim, cfg.rope_of(None))


def _add_experts(x, stats, lp, cfg: ModelConfig, moe_dense, qmm, live=None):
    """An ``moe`` layer: (x plus its expert FFN, the counters plus its)."""
    out, _, new = model.ffn(_normed(x, lp, cfg), lp, cfg, False, moe_dense, qmm, live)
    return x + out, model.add_stats(stats, new)


def forward_with_kv(params, cfg: ModelConfig, tokens, attn_fn=None,
                    with_aux: bool = False, qmm=None,
                    moe_dense: bool = False, logit_row=None):
    """``model._forward_with_kv`` for a stack of sub-layers: (logits
    [B, T, V], None, None[, stats]). Such a stack admits in chunks alone (the
    engine's rule): this whole-prompt forward is its parity path, and returns
    no cache rows."""
    if attn_fn is not None or with_aux:
        raise ValueError(
            f"{cfg.name}: a stack of sub-layers has no sequence-sharded "
            "prefill and no training forward"
        )
    B, T = tokens.shape
    x = params["embed"][tokens]
    mask = model.causal_mask(T, None)
    rope = _rope(jnp.broadcast_to(jnp.arange(T), (B, T)), cfg)

    def block(carry, layer):
        x, *stats = carry
        lp, _ = layer
        kind = model.kind_of(lp)
        if kind == "moe":
            x, stats = _add_experts(x, stats, lp, cfg, moe_dense, qmm)
        elif kind == "mamba2":
            y, _, _ = mix_prompt(_normed(x, lp, cfg), lp, cfg, T, qmm)
            x = x + y
        else:
            q, k, v = model.qkv_of(_normed(x, lp, cfg), lp, cfg, *rope, qmm)
            attn = model.gqa_attention(q, k, v, mask)
            x = x + model.matmul(attn.reshape(B, T, -1), lp["wo"], qmm, "row")
        return (x, *stats), None

    (x, *stats), _ = _scan(block, (x, *model.zero_stats(cfg)), params, cfg)
    if logit_row is not None:  # the one row a prefill samples from
        x = jax.lax.dynamic_slice_in_dim(x, logit_row, 1, axis=1)
    return (model._final_logits(x, params, cfg, qmm), None, None, *stats)


def prefill_chunk_paged(params, cfg: ModelConfig, tokens, start, k_pool,
                        v_pool, table_row, qmm=None, moe_dense: bool = False,
                        states=(), slot=None, n_valid=None):
    """``model.prefill_chunk_paged`` for a stack of sub-layers: a ``full``
    layer writes the chunk's K/V rows to ITS layer of the pool (its place
    among the full layers) and attends over the slot's pages, a ``mamba2``
    layer advances ``slot``'s state by the chunk's ``n_valid`` real rows
    (None: all). Returns (logits [1, Tc, V], k_pool', v_pool', states',
    tails'[, stats])."""
    B, Tc = tokens.shape
    P = k_pool.shape[2]
    x = params["embed"][tokens]
    positions = start + jnp.arange(Tc)[None, :]
    rope = _rope(positions, cfg)
    pages, off = model.chunk_pages(table_row, start, Tc, P)
    rows = table_row.shape[0] * P  # the slot's table, of which a chunk
    kv_tile = model._kv_tile(rows, P)  # folds the tiles up to its last row's
    n_tiles = model.chunk_kv_tiles(start, Tc, rows, kv_tile)
    # the scan carries the slot's own tails, not every slot's (engine/kda.py)
    states, tails = (states[0], slot_tails(states[1], slot)), states[1]

    def block(carry, layer):
        x, k_pool, v_pool, states, *stats = carry
        lp, _ = layer
        kind = model.kind_of(lp)
        if kind == "moe":
            x, stats = _add_experts(x, stats, lp, cfg, moe_dense, qmm)
        elif kind == "mamba2":
            y, *states = mix_chunk(
                _normed(x, lp, cfg), lp, cfg, *states, lp["kind_index"], slot,
                start, Tc if n_valid is None else n_valid, qmm,
            )
            x = x + y
        else:
            l = lp["kind_index"]
            q, k_new, v_new = model.qkv_of(
                _normed(x, lp, cfg), lp, cfg, *rope, qmm
            )
            k_pool = ops.write_rows(k_pool, l, ops.merge_heads(k_new[0]), pages, off)
            v_pool = ops.write_rows(v_pool, l, ops.merge_heads(v_new[0]), pages, off)
            with jax.named_scope("attention"):
                attn = model.blockwise_cache_attention(
                    q,
                    model.paged_kv_block(k_pool, v_pool, l, table_row, kv_tile,
                                         cfg.head_dim, q.dtype),
                    n_tiles, positions[0], None,
                )
            x = x + model.matmul(attn.reshape(B, Tc, -1), lp["wo"], qmm, "row")
        return (x, k_pool, v_pool, tuple(states), *stats), None

    (x, k_pool, v_pool, states, *stats), _ = _scan(
        block, (x, k_pool, v_pool, states, *model.zero_stats(cfg)), params, cfg
    )
    states = (states[0], put_slot_tails(tails, states[1], slot))
    logits = model._final_logits(x, params, cfg, qmm)
    return (logits, k_pool, v_pool, *states, *stats)


def decode_step_paged(params, cfg: ModelConfig, tokens, lengths, k_pool,
                      v_pool, tables, kernels: Optional[bool] = None,
                      active=None, moe_dense: bool = False, qmm=None,
                      states=()):
    """``model.decode_step_paged`` for a stack of sub-layers: a ``full``
    layer scatters each slot's new K/V row to its page and reads the pages
    where they lie in the carried pool, a ``mamba2`` layer updates the live
    slots' states in place. Returns (logits [B, V], k_pool', v_pool',
    states', tails'[, stats])."""
    B = tokens.shape[0]
    P = k_pool.shape[2]
    if active is None:
        active = jnp.ones((B,), jnp.bool_)
    rows = jnp.where(active, lengths, 0)  # an inactive slot reads no row
    pages = jnp.where(
        active, jnp.take_along_axis(tables, (rows // P)[:, None], axis=1)[:, 0], 0
    )
    offs = jnp.where(active, rows % P, P - 1)
    use_kernel = model._use_kernels(kernels)
    attend = (
        ops.paged_decode_attention if use_kernel
        else ops.paged_decode_attention_reference
    )
    with jax.named_scope("embed"):
        x = params["embed"][tokens][:, None, :]  # [B, 1, E]
        rope = _rope(lengths[:, None], cfg)

    def block(carry, layer):
        x, k_pool, v_pool, states, *stats = carry
        lp, _ = layer
        kind = model.kind_of(lp)
        if kind == "moe":
            with jax.named_scope("moe"):
                x, stats = _add_experts(x, stats, lp, cfg, moe_dense, qmm, active)
        elif kind == "mamba2":
            y, *states = mix_step(
                _normed(x, lp, cfg), lp, cfg, *states, lp["kind_index"], active,
                use_kernel, qmm,
            )
            x = x + y
        else:
            l = lp["kind_index"]
            q, k_new, v_new = model.qkv_of(
                _normed(x, lp, cfg), lp, cfg, *rope, qmm
            )
            with jax.named_scope("kv_write"):
                k_pool = k_pool.at[l, pages, offs].set(
                    ops.merge_heads(k_new[:, 0]).astype(k_pool.dtype)
                )
                v_pool = v_pool.at[l, pages, offs].set(
                    ops.merge_heads(v_new[:, 0]).astype(v_pool.dtype)
                )
            with jax.named_scope("attention"):
                attn = attend(q[:, 0], k_pool, v_pool, l, tables, rows)[:, None]
            with jax.named_scope("attn_out"):
                x = x + model.matmul(attn.reshape(B, 1, -1), lp["wo"], qmm, "row")
        return (x, k_pool, v_pool, tuple(states), *stats), None

    (x, k_pool, v_pool, states, *stats), _ = _scan(
        block, (x, k_pool, v_pool, tuple(states), *model.zero_stats(cfg)),
        params, cfg,
    )
    with jax.named_scope("final_logits"):
        logits = model._final_logits(x[:, 0], params, cfg, qmm)
    return (logits, k_pool, v_pool, *states, *stats)
