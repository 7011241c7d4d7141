"""GPipe-style pipeline parallelism over a ``pp`` mesh axis.

Layers are stacked on a leading axis (model.py's param layout), so pipeline
stages fall out of GSPMD sharding alone: `P("pp")` on that axis gives every
device a contiguous block of layers. The schedule is expressed as one
`lax.scan` over ticks inside `shard_map`:

  tick t: stage 0 ingests microbatch t's embeddings; every stage applies its
  local layer block; the last stage (which at tick t holds microbatch
  t-(S-1)) folds that microbatch's cross-entropy into an accumulator behind
  `lax.cond`; activations rotate one hop stage->stage+1 via `lax.ppermute`
  (ICI neighbor exchange). After MB + S - 1 ticks every microbatch has
  crossed all stages; the pipeline bubble is the standard GPipe S-1 ticks.

Activation memory per device is ONE microbatch regardless of batch size, and
weight memory is num_layers/S of the stack — the axis that lets models
deeper than one chip's HBM train. Composes with the ``dp`` axis (microbatch
rows sharded across dp inside the same shard_map); tensor/sequence
parallelism live on the GSPMD path (sharding.py / ring_attention.py).

The reference has no training and no model parallelism of any kind
(SURVEY.md section 2.4); this module is part of the TPU build's
"distributed is first-class" mandate.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine import model
from ..engine.config import ModelConfig


def build_pp_mesh(
    pp: int, dp: int = 1, devices=None
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    assert pp * dp <= len(devices), (pp, dp, len(devices))
    arr = np.asarray(devices[: pp * dp]).reshape(pp, dp)
    return Mesh(arr, axis_names=("pp", "dp"))


def pp_param_specs(params) -> dict:
    """PartitionSpecs: layer stack sharded over pp, everything else replicated."""

    def walk(tree, under_layers):
        out = {}
        for key, leaf in tree.items():
            if isinstance(leaf, dict):
                out[key] = walk(leaf, under_layers or key == "layers")
            else:
                out[key] = P("pp") if under_layers else P()
        return out

    return walk(params, False)


def shard_pp_params(params, mesh: Mesh):
    specs = pp_param_specs(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(jnp.asarray(x), NamedSharding(mesh, s)),
        params,
        specs,
    )


def make_pp_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    num_microbatches: int,
    optimizer: Optional[optax.GradientTransformation] = None,
    remat: bool = True,
    moe_aux_coef: float = 0.01,
) -> Tuple[Callable, Callable]:
    """Returns (init_state, train_step) for pipeline-parallel training.

    Batches are {"tokens": [B, T], "loss_mask": [B, T]} with
    B % (num_microbatches * dp) == 0; the step reshapes to
    [MB, mb, T] microbatches internally. MoE configs fold the router
    load-balancing aux (weighted by ``moe_aux_coef``) into the loss, same
    contract as the GSPMD train step (engine/train.py).
    """
    from ..engine.train import make_optimizer

    optimizer = optimizer or make_optimizer()
    S = mesh.shape["pp"]
    MB = num_microbatches
    assert cfg.num_layers % S == 0, (
        f"layers {cfg.num_layers} not divisible by pp={S}"
    )

    def stage_apply(layers_local, x):
        """Run this stage's layer block on activations x [mb, T, E];
        returns (x', stage aux sum over local layers)."""
        mb, T, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(T), (mb, T))
        cos, sin = model.rope_tables_of(positions, cfg.head_dim, cfg.rope_of(None))
        mask = model.causal_mask(T, cfg.sliding_window)

        def blk(x, lp):
            x, (_, _, aux) = model.apply_block(
                x, lp, cfg, cos, sin, mask, with_aux=True
            )
            return x, aux

        blk_fn = jax.checkpoint(blk) if remat else blk
        x, auxs = jax.lax.scan(blk_fn, x, layers_local)
        return x, jnp.sum(auxs)

    def pp_loss(params, tokens_mb, mask_mb):
        """Inside shard_map: tokens_mb [MB, mb_local, T] per device."""
        s = jax.lax.axis_index("pp")
        mb, T = tokens_mb.shape[1], tokens_mb.shape[2]
        E = cfg.hidden_size
        layers_local = params["layers"]
        embed = params["embed"]
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T

        perm = [(i, (i + 1) % S) for i in range(S)]

        def microbatch_loss(y, mb_idx):
            from ..engine.train import token_cross_entropy

            h = model.rms_norm(y, params["final_norm"], cfg.rms_norm_eps)
            logits = model.matmul(h, head).astype(jnp.float32)
            return token_cross_entropy(
                logits, tokens_mb[mb_idx], mask_mb[mb_idx]
            )

        def tick(carry, t):
            x_in, loss_acc, denom_acc, aux_acc = carry
            in_idx = jnp.clip(t, 0, MB - 1)
            fresh = embed[tokens_mb[in_idx]].astype(x_in.dtype)  # [mb, T, E]
            x = jnp.where(s == 0, fresh, x_in)
            y, aux_t = stage_apply(layers_local, x)
            # stage s holds microbatch t-s at tick t; bubble ticks run the
            # router on garbage activations, so their aux must not count
            holds_mb = jnp.logical_and(t - s >= 0, t - s < MB)
            aux_acc = aux_acc + jnp.where(holds_mb, aux_t, 0.0)

            out_idx = t - (S - 1)
            is_producer = jnp.logical_and(
                s == S - 1, jnp.logical_and(out_idx >= 0, out_idx < MB)
            )
            dl, dd = jax.lax.cond(
                is_producer,
                lambda: microbatch_loss(y, jnp.clip(out_idx, 0, MB - 1)),
                lambda: (jnp.float32(0.0), jnp.float32(0.0)),
            )
            x_next = jax.lax.ppermute(y, "pp", perm)
            return (x_next, loss_acc + dl, denom_acc + dd, aux_acc), None

        x0 = jnp.zeros((mb, T, E), embed.dtype)
        (_, loss_sum, denom, aux_sum), _ = jax.lax.scan(
            tick,
            (x0, jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0)),
            jnp.arange(MB + S - 1),
        )
        loss_sum = jax.lax.psum(loss_sum, ("pp", "dp"))
        denom = jax.lax.psum(denom, ("pp", "dp"))
        # sum over (stages x valid ticks x local layers) = layers x MB,
        # summed again over dp shards -> mean per (layer, microbatch, shard)
        aux_sum = jax.lax.psum(aux_sum, ("pp", "dp"))
        aux_mean = aux_sum / jnp.float32(
            cfg.num_layers * MB * mesh.shape["dp"]
        )
        return loss_sum / jnp.maximum(denom, 1.0), aux_mean

    def loss_fn(params, tokens, loss_mask):
        B, T = tokens.shape
        dp = mesh.shape["dp"]
        assert B % (MB * dp) == 0, (
            f"batch {B} must be divisible by microbatches*dp = {MB}*{dp}"
        )
        mb = B // MB
        tokens_mb = tokens.reshape(MB, mb, T)
        mask_mb = loss_mask.reshape(MB, mb, T)

        specs = pp_param_specs(params)
        sharded = partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                specs,
                P(None, "dp", None),
                P(None, "dp", None),
            ),
            out_specs=(P(), P()),
            check_rep=False,
        )
        ce, aux = sharded(pp_loss)(params, tokens_mb, mask_mb)
        return ce + moe_aux_coef * aux, aux

    def init_state(params):
        return {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    def train_step(state, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch["tokens"], batch["loss_mask"]
        )
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        return new_state, {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            "moe_aux": aux,
        }

    return init_state, train_step
