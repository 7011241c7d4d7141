"""Mesh construction and parameter/cache sharding plans.

Megatron-style tensor parallelism expressed as GSPMD annotations: we place
NamedShardings on params and KV caches, and XLA inserts the ICI collectives
(all-reduce after row-parallel matmuls, all-gather for the vocab-sharded
embedding) — no hand-written collective calls on the decode path, per the
scaling-book recipe: pick a mesh, annotate, let XLA do the rest.

Axes:
  dp — data/replica axis: batch slots in decode, batch in training
  sp — sequence axis: ring-attention sequence parallelism (long context)
  ep — expert axis: MoE experts sharded across chips (engine/moe.py); the
       dense-MoE einsum contracts the expert axis, so GSPMD inserts one
       psum over ep per MoE layer — expert parallelism with no explicit
       dispatch collectives
  tp — model axis: attention heads + FFN hidden sharded across chips
       (innermost: the per-matmul allreduce rides the fastest ICI links)

Equivalent role in the reference: none (single-process llama.cpp); this is
the "Mistral-7B tensor-parallel decode across 4 chips (ICI all-reduce)"
benchmark config of BASELINE.md.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.config import ModelConfig


def build_mesh(
    n_devices: Optional[int] = None,
    *,
    dp: int = 1,
    sp: int = 1,
    ep: int = 1,
    tp: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a (dp, sp, ep, tp) mesh. Unspecified tp absorbs the rest."""
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if tp is None:
        assert n % (dp * sp * ep) == 0, (n, dp, sp, ep)
        tp = n // (dp * sp * ep)
    assert dp * sp * ep * tp == n, f"mesh {dp}x{sp}x{ep}x{tp} != {n} devices"
    arr = np.asarray(devices).reshape(dp, sp, ep, tp)
    return Mesh(arr, axis_names=("dp", "sp", "ep", "tp"))


# Partition rules for the engine params pytree (path suffix -> spec).
# Column-parallel projections shard the output dim on tp; row-parallel ones
# shard the input dim, and GSPMD inserts the psum on their outputs.
PARAM_RULES: Dict[str, P] = {
    "embed": P("tp", None),  # vocab-sharded
    "layers/attn_norm": P(None, None),
    "layers/ffn_norm": P(None, None),
    "layers/q_norm": P(None, None),
    "layers/k_norm": P(None, None),
    "layers/wq": P(None, None, "tp"),
    "layers/wk": P(None, None, "tp"),
    "layers/wv": P(None, None, "tp"),
    "layers/wo": P(None, "tp", None),
    "layers/w_gate": P(None, None, "tp"),
    "layers/w_up": P(None, None, "tp"),
    "layers/w_down": P(None, "tp", None),
    # MoE leaves [L, X, in, out]: experts over ep, expert-FFN hidden over tp
    # (the router is tiny and stays replicated)
    "layers/w_router": P(None, None, None),
    "layers/we_gate": P(None, "ep", None, "tp"),
    "layers/we_up": P(None, "ep", None, "tp"),
    "layers/we_gateup": P(None, "ep", None, "tp"),
    "layers/we_down": P(None, "ep", "tp", None),
    "final_norm": P(None),
    "lm_head": P(None, "tp"),
}

# KV cache [L, slots, C, KH, D]: slots over dp, kv heads over tp.
CACHE_SPEC = P(None, "dp", None, "tp", None)
# int8 KV-cache scales [L, slots, C, KH] ride the same placement.
CACHE_SCALE_SPEC = P(None, "dp", None, "tp")
# Page pool [L, N, P, KH*D] (engine/paged.py): pages over dp, and the
# merged head axis over tp — a contiguous 1/tp of it is KH/tp whole heads.
POOL_SPEC = P(None, "dp", None, "tp")
# Context-sharded variant: the C axis additionally splits over sp, so one
# slot's KV can exceed a single chip's HBM (long-context serving). XLA
# partitions the decode attention over the sharded contraction itself —
# per-shard partial max/denominator/accumulator with psums over sp, the
# flash-decoding-across-chips pattern — while row writes stay local to the
# owning shard (verified: no cache-sized all-gathers in the lowered HLO).
CACHE_SPEC_SEQ = P(None, "dp", "sp", "tp", None)
CACHE_SCALE_SPEC_SEQ = P(None, "dp", "sp", "tp")


@dataclass
class ShardingPlan:
    """Placement helper handed to TPUEngine / the trainer."""

    mesh: Mesh

    def spec_for(self, path: str) -> P:
        if path in PARAM_RULES:
            return PARAM_RULES[path]
        # int8 serving leaves {"q", "s"} (model.quantize_params fuse=False):
        # the int8 tensor shards exactly like the dense weight it replaces;
        # the per-output-channel scale is size 1 on the contraction dim
        # (axis -2), so its spec is the weight's with that axis unsharded.
        if path.endswith(("/q", "/s")):
            base = PARAM_RULES.get(path[:-2])
            if base is not None:
                if path.endswith("/q"):
                    return base
                return P(*base[:-2], None, base[-1])
        # int4 serving leaves {"q4", "s4"} (packed nibbles + group scales):
        # q4 [..., K/2, N] shards exactly like the dense weight (nibble
        # pairs never straddle a shard: K/tp stays even for every real
        # geometry); s4 [..., G, 1, N] is the weight's spec with the
        # contraction axis carrying the group axis and a fresh unsharded
        # axis in front of N.
        if path.endswith(("/q4", "/s4")):
            base = PARAM_RULES.get(path[:-3])
            if base is not None:
                if path.endswith("/q4"):
                    return base
                return P(*base[:-1], None, base[-1])
        raise KeyError(f"no partition rule for param {path!r}")

    def sharding_for(self, path: str) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(path))

    def params_shardings(self, params) -> Dict:
        def walk(tree, prefix=""):
            out = {}
            for k, v in tree.items():
                path = f"{prefix}{k}"
                if isinstance(v, dict):
                    out[k] = walk(v, path + "/")
                else:
                    out[k] = self.sharding_for(path)
            return out

        return walk(params)

    def put_params(self, params):
        shardings = self.params_shardings(params)
        return jax.tree.map(
            lambda x, s: jax.device_put(jax.numpy.asarray(x), s), params, shardings
        )

    def put_cache(self, cache, seq_shard: bool = False):
        spec = CACHE_SPEC_SEQ if seq_shard else CACHE_SPEC
        return jax.device_put(cache, NamedSharding(self.mesh, spec))

    def put_pool(self, pool):
        return jax.device_put(pool, NamedSharding(self.mesh, POOL_SPEC))

    def put_cache_scales(self, scales, seq_shard: bool = False):
        spec = CACHE_SCALE_SPEC_SEQ if seq_shard else CACHE_SCALE_SPEC
        return jax.device_put(scales, NamedSharding(self.mesh, spec))

    def ragged_attention(self, window: Optional[int], use_kernel: bool):
        """Per-device ragged decode attention under shard_map.

        Attention is head- and slot-local, so with q sharded (dp, tp) and
        the per-layer cache (dp, none, tp) every device attends its own
        [B/dp, C, KH/tp, D] shard with ZERO collectives — the Pallas ragged
        kernel (ops/decode_attention.py) runs per device exactly as on one
        chip. ``use_kernel=False`` swaps in the jnp reference body (CPU
        virtual meshes; numerics identical), which is how the dryrun and the
        test suite exercise this path without TPU hardware.

        Returns attn(q [B,H,D], k_l [B,C,KH,D], v_l [B,C,KH,D], lengths [B])
        -> [B, H, D], for model.decode_step's ``attn_impl`` hook.
        """
        from .. import ops

        def local(q, k_l, v_l, lengths):
            if use_kernel:
                return ops.decode_attention(q, k_l, v_l, lengths, window=window)
            return ops.decode_attention_reference(
                q, k_l, v_l, lengths, window=window
            )

        return jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P("dp", "tp", None),
                P("dp", None, "tp", None),
                P("dp", None, "tp", None),
                P("dp"),
            ),
            out_specs=P("dp", "tp", None),
            check_vma=False,
        )

    def int4_matmul_impl(self, use_kernel: bool):
        """Per-device packed-nibble int4 matmuls under shard_map.

        The int4 kernel (ops/int4_matmul.py) is a per-device Pallas
        program, so under a sharding plan it cannot ride GSPMD like the
        int8 dot_generals do. Same answer as ragged decode attention: run
        the kernel on each device's weight shard under shard_map —
        Megatron TP done by hand for exactly these matmuls.

          col  — column-parallel (wq/wk/wv/w_gate/w_up): the output dim is
                 tp-sharded, activations replicated; zero collectives.
          row  — row-parallel (wo/w_down): the contraction dim (and its
                 scale groups) is tp-sharded; a psum over tp completes the
                 partial products — the same all-reduce GSPMD inserts for
                 the dense/int8 layouts.
          head — the lm_head [E, V] with vocab tp-sharded (col pattern on
                 rank-2 activations [B, E]).

        Each device picks kernel vs jnp reference from its LOCAL shard
        dims (a shard can be kernel-ineligible even when the global shape
        is not); ``use_kernel=False`` forces the reference body — how CPU
        virtual meshes (dryrun, tests) exercise this path bit-for-bit.

        Returns f(x, leaf, kind) -> y for model.matmul's ``qmm`` hook.
        """
        from ..ops.int4_matmul import (
            infer_group,
            int4_matmul,
            int4_matmul_reference,
            kernel_supported,
        )

        def local_mm(x_l, q4_l, s4_l):
            g = infer_group(q4_l, s4_l)
            if use_kernel and kernel_supported(
                q4_l.shape[-2] * 2, q4_l.shape[-1], g
            ):
                return int4_matmul(x_l, q4_l, s4_l)
            return int4_matmul_reference(x_l, q4_l, s4_l)

        mesh = self.mesh
        specs = {
            # (x, q4, s4) in_specs, out_spec, psum over tp?
            "col": (
                (P("dp", None, None), P(None, "tp"), P(None, None, "tp")),
                P("dp", None, "tp"),
                False,
            ),
            "row": (
                (P("dp", None, "tp"), P("tp", None), P("tp", None, None)),
                P("dp", None, None),
                True,
            ),
            "head": (
                (P("dp", None), P(None, "tp"), P(None, None, "tp")),
                P("dp", "tp"),
                False,
            ),
        }
        fns = {}
        for kind, (in_specs, out_spec, reduce_tp) in specs.items():
            def local(x_l, q4_l, s4_l, _reduce=reduce_tp):
                y = local_mm(x_l, q4_l, s4_l)
                return jax.lax.psum(y, "tp") if _reduce else y

            fns[kind] = jax.shard_map(
                local,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_spec,
                check_vma=False,
            )

        def qmm(x, leaf, kind):
            return fns[kind](x, leaf["q4"], leaf["s4"])

        return qmm

    def paged_pool_impl(self, window: Optional[int], use_kernel: bool,
                        quantized: bool):
        """Per-device paged-pool write + attend under shard_map (dp > 1).

        Under a dp-replicated plan the page pool's physical page axis
        shards over dp and table entries are REPLICA-LOCAL ids
        (engine/paged.py PageAllocator replicas=...). A GSPMD gather
        through the tables could not prove locality and would all-gather
        the pool; under shard_map each device scatters/gathers its own
        slots' rows in its own pool shard — zero collectives, exactly the
        single-chip paged path per device. kv heads additionally shard
        over tp, like the dense cache.

        Returns, for the bf16 pool,
          f(q [B,H,D], k_new [B,KH,D], v_new, k_l [N,P,KH*D], v_l,
            tables [B,MB], lengths [B], pages [B], offs [B])
            -> (attn [B,H,D], k_l', v_l')
        and for the int8 pool the same with (k_s [N,P,KH], v_s) appended
        to inputs and outputs. Plugged into model.decode_step_paged's
        ``pool_impl`` hook.
        """
        from .. import ops
        from ..engine import model as model_mod

        def local_bf16(q, k_new, v_new, k_l, v_l, tables, lengths, pages,
                       offs):
            k_l = k_l.at[pages, offs].set(
                ops.merge_heads(k_new).astype(k_l.dtype)
            )
            v_l = v_l.at[pages, offs].set(
                ops.merge_heads(v_new).astype(v_l.dtype)
            )
            # the kernels take a stacked pool and a layer: this device's
            # slice of ONE layer is a one-layer stack
            if use_kernel:
                attn = ops.paged_decode_attention(
                    q, k_l[None], v_l[None], 0, tables, lengths,
                    window=window,
                )
            else:
                attn = ops.paged_decode_attention_reference(
                    q, k_l[None], v_l[None], 0, tables, lengths,
                    window=window,
                )
            return attn, k_l, v_l

        def local_int8(q, k_new, v_new, k_l, v_l, k_s, v_s, tables,
                       lengths, pages, offs):
            k_l, k_s = model_mod.scatter_quant(k_l, k_s, (pages, offs), k_new)
            v_l, v_s = model_mod.scatter_quant(v_l, v_s, (pages, offs), v_new)
            attn = model_mod.paged_int8_attend(
                q, k_l[None], v_l[None], k_s[None], v_s[None], 0, tables,
                lengths, window=window,
                use_int8_kernel=(
                    use_kernel and model_mod._int8_ragged_enabled()
                ),
            )
            return attn, k_l, v_l, k_s, v_s

        pool = scale = P("dp", None, "tp")
        vec = P("dp", "tp", None)
        if quantized:
            in_specs = (vec, vec, vec, pool, pool, scale, scale,
                        P("dp", None), P("dp"), P("dp"), P("dp"))
            out_specs = (vec, pool, pool, scale, scale)
            fn = local_int8
        else:
            in_specs = (vec, vec, vec, pool, pool,
                        P("dp", None), P("dp"), P("dp"), P("dp"))
            out_specs = (vec, pool, pool)
            fn = local_bf16
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def paged_prefill_scatter(self, quantized: bool):
        """Per-device write of a whole prefilled prompt's K/V rows into
        the dp-sharded page pool (replica-local page ids, like
        ``paged_pool_impl``). The prompt's forward pass itself is
        replicated over dp (B=1 — dp has nothing to split), so every
        device computes the same rows; only the OWNING replica's write
        targets real pages — the rest write their local sacrificial
        page 0, which is never read.

        bf16: f(k_pool [L,N,P,KH*D], v_pool, k_rows [L,T,KH*D], v_rows,
               blocks [MB] (the slot's table row), owner scalar)
               -> (k_pool', v_pool')
        int8: scales [L,N,P,KH] and their rows [L,T,KH] ride along, after
              the values, among the pools and among the rows.
        """
        from .. import ops

        n = 4 if quantized else 2

        def local(*args):
            pools, rows, (blocks, owner) = args[:n], args[n:2 * n], args[2 * n:]
            mine = jax.lax.axis_index("dp") == owner
            pg = jnp.where(mine, blocks, 0)
            return tuple(
                ops.write_rows(p, None, r, pg) for p, r in zip(pools, rows)
            )

        return jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(POOL_SPEC,) * n + (P(None, None, "tp"),) * n
            + (P(None), P()),
            out_specs=(POOL_SPEC,) * n,
            check_vma=False,
        )

    @property
    def tp(self) -> int:
        return self.mesh.shape["tp"]

    @property
    def dp(self) -> int:
        return self.mesh.shape["dp"]

    @property
    def sp(self) -> int:
        return self.mesh.shape["sp"]

    @property
    def ep(self) -> int:
        return self.mesh.shape.get("ep", 1)

    def validate(self, cfg: ModelConfig, num_slots: int) -> None:
        tp, dp, ep = self.tp, self.dp, self.ep
        assert cfg.num_kv_heads % tp == 0, (
            f"kv heads {cfg.num_kv_heads} not divisible by tp={tp}"
        )
        assert cfg.num_heads % tp == 0
        if cfg.moe:
            assert cfg.num_experts % ep == 0, (
                f"experts {cfg.num_experts} not divisible by ep={ep}"
            )
            assert cfg.expert_dim % tp == 0
        else:
            assert ep == 1, "ep>1 requires a MoE config"
            assert cfg.intermediate_size % tp == 0
        assert num_slots % dp == 0, f"slots {num_slots} not divisible by dp={dp}"


def single_device_plan() -> Optional[ShardingPlan]:
    """None when there is nothing to shard (1 device)."""
    if len(jax.devices()) == 1:
        return None
    return ShardingPlan(build_mesh())
