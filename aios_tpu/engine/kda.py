"""Kimi Delta Attention (KDA) layers on the serving path: a linear-attention
mixer that keeps, a slot, one float32 state in place of cache rows.

``engine/latent.py`` dispatches here for the ``kda`` layers of a stack that
mixes them with latent-attention layers (``cfg.layer_types``); the FFN, the
residual and the scan are that module's. One row ``x`` of the residual,
``h = rms(x)``, H heads of K key and V value channels:

    [uq | uk | uv | a | z] = h W_in           (one matrix: 3 HK + 2 HV wide)
    c_t = silu(sum_j w_j * u_{t-taps+1+j})    a depthwise causal convolution
                                              of taps rows over uq | uk | uv;
                                              rows before the start are zero
    q, k = l2(c^q), l2(c^k) a head; q *= K^-1/2;  v = c^v
    beta = sigmoid(h W_beta)                  [H]
    log alpha = lower_bound * sigmoid(exp(A_head) * (a + b))   a channel
    S' = (I - beta k k^T) Diag(alpha) S + beta k v^T;  o = S'^T q
    y = (rms_head(o) * sigmoid(z)) W_o

What a slot keeps a layer (engine/paged.py header: the state kind): ``S``
[H, K, V] float32 and the convolution's tail, the last taps - 1 rows of
uq | uk | uv, bfloat16 (as [taps - 1, slot, width] a layer). Three forms, one a graph kind, as latent.py's:

* ``mix_prompt``: a whole prompt from a zero state (the chunked form).
* ``mix_chunk``: an admission chunk of one slot: state and tail in, state
  and tail out; a chunk that starts at row 0 reads zeros whatever the slot's
  last tenant left; rows beyond the chunk's true length are identity updates
  and do not advance the tail.
* ``mix_step``: a decode step, one row a live slot, the states updated in
  place in the carried pool (ops/kda.py); dead slots' states and tails are
  untouched.

State arithmetic is float32; q, k, v and the gates enter as the bfloat16
the matmul gives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import ops
from ..ops import kda as kda_ops
from . import model
from .config import ModelConfig


def check(cfg: ModelConfig) -> None:
    """The chunked form's one assumption of the configuration."""
    if -cfg.kda_lower_bound * kda_ops.BLOCK > kda_ops.MAX_EXP:
        raise ValueError(
            f"{cfg.name}: kda_lower_bound {cfg.kda_lower_bound} over "
            f"{kda_ops.BLOCK} rows passes e^{kda_ops.MAX_EXP:g}: the chunked "
            "form's factored decays would overflow float32 (ops/kda.py)"
        )


def _project(h, lp, cfg: ModelConfig, qmm=None):
    """Normed rows h [.., E] -> (u [.., 3 widths] before the convolution,
    beta [.., H] f32, g = log alpha [.., H, K] f32, z [.., H * V])."""
    H, K, V = cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim
    conv_w = H * (2 * K + V)
    with jax.named_scope("kda_in"):
        wide = model.matmul(h, lp["kda_in"], qmm)
        u = wide[..., :conv_w]
        a = wide[..., conv_w:conv_w + H * K].astype(jnp.float32)
        z = wide[..., conv_w + H * K:]
        beta = jax.nn.sigmoid(
            h.astype(jnp.float32) @ lp["kda_beta"].astype(jnp.float32)
        )
        a = a.reshape(*a.shape[:-1], H, K)
        rate = jnp.exp(lp["kda_A"].astype(jnp.float32))[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            rate * (a + lp["kda_b"].astype(jnp.float32).reshape(H, K))
        )
    return u, beta, g, z


def _conv(u_all, lp, taps: int, T: int):
    """silu of the depthwise causal convolution: ``u_all`` [taps - 1 + T, .., W]
    (the tail, then the new rows) -> [T, .., W] float32."""
    w = lp["kda_conv"].astype(jnp.float32)  # [taps, W]
    u_all = u_all.astype(jnp.float32)
    acc = sum(w[j] * u_all[j:j + T] for j in range(taps))
    return jax.nn.silu(acc)


def _heads(c, cfg: ModelConfig):
    """Convolved rows [.., W] -> (q [.., H, K] l2-normed and scaled, k
    l2-normed, v [.., H, V]), float32."""
    H, K, V = cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim
    lead = c.shape[:-1]
    q = c[..., :H * K].reshape(*lead, H, K)
    k = c[..., H * K:2 * H * K].reshape(*lead, H, K)
    v = c[..., 2 * H * K:].reshape(*lead, H, V)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    return l2(q) * (float(K) ** -0.5), l2(k), v


def _out(o, z, lp, cfg: ModelConfig, dtype, qmm=None):
    """o [.., H, V] float32, z [.., H * V] -> the mixer's output [.., E]."""
    with jax.named_scope("kda_out"):
        o = model.rms_norm(o, lp["kda_onorm"].astype(jnp.float32),
                           cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(z.astype(jnp.float32))
        y = (o.reshape(*z.shape) * gate).astype(dtype)
        return model.matmul(y, lp["wo"], qmm, "row")


def _rows(h, lp, cfg: ModelConfig, s0, tail, n_valid, qmm=None):
    """The chunked form over ONE sequence's rows h [T, E] from state ``s0``
    [H, K, V] and tail [taps - 1, W]; rows from ``n_valid`` on are identity
    updates. Returns (y [T, E], state after, tail after)."""
    T = h.shape[0]
    taps = cfg.kda_conv
    u, beta, g, z = _project(h, lp, cfg, qmm)
    u_all = jnp.concatenate([tail.astype(u.dtype), u], axis=0)
    q, k, v = _heads(_conv(u_all, lp, taps, T), cfg)
    live = (jnp.arange(T) < n_valid)
    beta = jnp.where(live[:, None], beta, 0.0)
    g = jnp.where(live[:, None, None], g, 0.0)
    pad = -T % kda_ops.SUB
    if pad:  # identity rows up to a whole sub-chunk
        q, k, v, g, beta = (
            jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            for a in (q, k, v, g, beta)
        )
    with jax.named_scope("kda_chunk"):
        o, s = kda_ops.chunked(q, k, v, g, beta, s0, ops.use_pallas())
    # the last taps - 1 rows that were real: rows n_valid .. of [tail | u]
    new_tail = jax.lax.dynamic_slice_in_dim(u_all, n_valid, taps - 1, axis=0)
    return _out(o[:T], z, lp, cfg, h.dtype, qmm), s, new_tail.astype(tail.dtype)


def mix_prompt(h, lp, cfg: ModelConfig, n_valid, qmm=None):
    """Whole prompts h [B, T, E] from zero states; ``n_valid`` [B] or a
    scalar. Returns (y [B, T, E], states [B, H, K, V], tails [B, taps-1, W])."""
    (sh, th) = cfg.kda_state_shapes
    B = h.shape[0]
    s0 = jnp.zeros(sh, jnp.float32)
    tail = jnp.zeros(th, h.dtype)
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    return jax.vmap(
        lambda hb, n: _rows(hb, lp, cfg, s0, tail, n, qmm)
    )(h, n_valid)


def slot_tails(tails, slot):
    """One slot's tails of every kda layer, [L, taps - 1, W]: what a chunk's
    layer scan carries in the big array's place (sliced by layer and slot
    inside the scan, the TPU compiler relaid the whole array out and back a
    chunk program: tests/test_mosaic_aot.py -k state_kind)."""
    return jax.lax.dynamic_index_in_dim(tails, slot, axis=2, keepdims=False)


def put_slot_tails(tails, rows, slot):
    """``slot_tails``' rows back into the array, after the scan."""
    return jax.lax.dynamic_update_slice(tails, rows[:, :, None], (0, 0, slot, 0))


def mix_chunk(h, lp, cfg: ModelConfig, states, tail_rows, layer, slot, start,
              n_valid, qmm=None):
    """One admission chunk h [1, Tc, E] of ``slot`` against the state pool
    ``states`` [L, S + 1, H, K, V] and the slot's tails ``tail_rows``
    [L, taps - 1, W] (``slot_tails``) at kda layer ``layer``. Returns
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
    taps = cfg.kda_conv
    u, beta, g, z = _project(h[:, 0], lp, cfg, qmm)  # [B, ..]
    old = tails[layer, :, :B]  # [taps - 1, B, W]
    u_all = jnp.concatenate([old, u[None].astype(old.dtype)], axis=0)
    c = _conv(u_all, lp, taps, 1)[0]  # [B, W]: the taps lead, as a chunk's rows do
    q, k, v = _heads(c, cfg)
    beta = jnp.where(active[:, None], beta, 0.0)
    g = jnp.where(active[:, None, None], g, 0.0)
    slots = jnp.where(active, jnp.arange(B), states.shape[1] - 1)
    step = kda_ops.kda_step if use_kernel else kda_ops.decode_step_reference
    with jax.named_scope("kda_step"):
        o, states = step(q, k, v, g, beta, states, layer, slots)
    new = jnp.where(active[None, :, None], u_all[1:], old)
    tails = jax.lax.dynamic_update_slice(tails, new[None], (layer, 0, 0, 0))
    return _out(o, z, lp, cfg, h.dtype, qmm)[:, None], states, tails
