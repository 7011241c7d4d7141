"""Model configurations for the Llama-family decoder.

One architecture class covers every local model tier the reference routes to
(runtime/src/model_manager.rs:462-518): TinyLlama-1.1B (operational),
Mistral-7B (tactical, GQA + sliding window), DeepSeek-R1-Distill-8B
(tactical, Llama-3 shape), Qwen3-14B (strategic, QK-norm). Configs can be
built from presets, GGUF metadata, or HF config dicts.

``layer_types`` names a kind a layer from one of THREE closed lists, one a
form of stack: LAYER_KINDS (a grouped-query stack of window and full layers),
STATE_KINDS (a latent-attention stack with kda layers) and SUBLAYER_KINDS (a
stack whose layers are one sub-layer each; why a third list: at its
definition).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional


@dataclass(frozen=True)
class RopeParams:
    """The rotary table of one kind of layer: plain where ``factor`` <= 1,
    else YaRN (model.yarn_inv_freq has the blend). ``attention_factor``
    multiplies cos and sin, so scores carry its square (the grouped-query
    block's convention; the latent block scales its softmax instead)."""

    theta: float = 10000.0
    factor: float = 1.0
    original_context: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


# the kinds of attention layer a grouped-query stack can mix
# (ModelConfig.layer_types): ``window`` sees itself and the
# sliding_window - 1 rows before it, ``full`` every row
LAYER_KINDS = ("full", "window")
# and those of a latent-attention stack: ``mla`` keeps latent rows in pages,
# ``kda`` (Kimi Delta Attention, engine/kda.py) one fixed-size recurrent
# state a slot and no rows (engine/paged.py header: the state kind)
STATE_KINDS = ("kda", "mla")
# and those of a stack of SUB-LAYERS (engine/mamba2.py), whose every layer is
# ONE sub-layer under one norm, x + F(norm(x)): ``mamba2`` a state-space mixer
# that keeps a state a slot (the state kind again, another shape), ``full`` a
# grouped-query attention layer that keeps rows in pages, ``moe`` an expert
# FFN alone. A third closed list and not the second widened: the second's
# layers are mixer AND FFN under two norms, and no stack mixes the two forms.
SUBLAYER_KINDS = ("full", "mamba2", "moe")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_context: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = False
    qk_norm: bool = False  # Qwen3-style per-head RMSNorm on q/k
    # Mixture-of-experts (0 experts = dense FFN). The router picks
    # num_experts_per_tok experts per token; their gate weights are softmax
    # probabilities renormalized over the selected set when norm_topk_prob.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: Optional[int] = None
    norm_topk_prob: bool = True
    # ``num_experts`` is the ROUTER's width. A chip that holds a share of a
    # layer's experts (wide expert parallelism: the layer is divided over
    # several chips) holds ``experts_held`` of them from ``first_expert``
    # on; 0 = all. The router still ranks all ``num_experts``; the layer
    # adds what ITS experts give for the tokens routed to them.
    experts_held: int = 0
    first_expert: int = 0
    # router scoring: "softmax" (Mixtral/Qwen3-MoE) or "sigmoid" (the
    # DeepSeek-V3 family: independent scores, the top-k renormalized, then
    # scaled by routed_scaling_factor)
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # experts every token runs beside the routed ones (width
    # n_shared_experts * moe_intermediate_size)
    n_shared_experts: int = 0
    # the first first_k_dense layers keep a dense FFN (width
    # intermediate_size); the layers after them are expert layers
    first_k_dense: int = 0
    # Multi-head latent attention (kv_lora_rank > 0): queries through a
    # q_lora_rank bottleneck, keys/values through one kv_lora_rank latent a
    # row plus qk_rope_head_dim rotary values shared by all heads — the
    # cache holds those, not per-head K/V. A head is qk_nope_head_dim +
    # qk_rope_head_dim wide for scores and v_head_dim for values.
    # q_lora_rank 0 = one direct query projection (no bottleneck, no norm).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a post-norm on each sub-layer's output besides the pre-norm:
    # x + norm(attn(norm(x))), x + norm(mlp(norm(x)))
    sandwich_norm: bool = False
    # A residual of hc_mult > 1 mixed streams a token (manifold-constrained
    # hyper-connections: engine/residual.py has the equations). Each
    # sub-layer reads a learned mix of the streams and writes back through
    # a doubly-stochastic matrix made by hc_sinkhorn_iters rounds of row and
    # column normalisation of exp(clip(., *hc_res_clamp)); hc_eps guards
    # the divisions and the input mix. Built for the latent-attention block
    # only.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # YaRN scaling of the rotary embedding (rope_factor > 1; the latent
    # block's rotary part, or every layer of a grouped-query stack that
    # rope_by_kind does not name): frequencies blended between 1/theta^(2i/d)
    # and that over rope_factor, by a linear ramp between the dimensions
    # that turn rope_beta_fast and rope_beta_slow times in
    # rope_original_context positions. The softmax scale is multiplied by
    # (0.1 rope_mscale_all_dim ln(rope_factor) + 1)^2; cos and sin stay
    # unscaled (the published mscale equals mscale_all_dim). That is the
    # latent block; the grouped-query block multiplies cos and sin by
    # 0.1 ln(rope_factor) + 1 instead (rope_of).
    rope_factor: float = 1.0
    rope_original_context: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # A kind per layer, ``window`` or ``full`` (LAYER_KINDS), for a stack
    # that mixes the two; () = every layer alike, windowed iff
    # sliding_window. The layer scan's body is one period of the pattern
    # (model.scan_segments; ``period``).
    # Each kind has its own pages and residency rule (engine/paged.py
    # KindPageAllocator) and its own rotary table: ``rope_by_kind`` pairs a
    # kind with its RopeParams; a kind it does not name takes the fields
    # above. The grouped-query block only, and only where the stack mixes
    # kinds: a stack of one kind has the one table of the fields above.
    layer_types: tuple = ()
    rope_by_kind: tuple = ()
    # A latent-attention stack may mix ``mla`` layers with ``kda`` layers
    # (STATE_KINDS in layer_types; engine/kda.py has the equations): kda_heads
    # heads of kda_key_dim key and kda_value_dim value channels, a depthwise
    # causal convolution of kda_conv taps before them, a decay a channel of
    # log alpha in (kda_lower_bound, 0), and a float32 state
    # [kda_heads, kda_key_dim, kda_value_dim] a slot a layer in place of rows.
    kda_heads: int = 0
    kda_key_dim: int = 0
    kda_value_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    # the latent block's variations, each off where a model has none:
    # learned RMSNorms on each query head and on the shared rotary key
    # before rotation; rotary pairs (2i, 2i+1) in place of (i, i + half);
    # each head's attention output times sigmoid(h W_z)_head
    latent_qk_norm: bool = False
    rope_interleave: bool = False
    attn_head_gate: bool = False
    # A stack of sub-layers (SUBLAYER_KINDS in layer_types; engine/mamba2.py
    # has the equations): a ``mamba2`` layer has ssm_heads heads of
    # ssm_head_dim channels, a state of ssm_state values a channel, its B and
    # C projections in ssm_groups groups of heads, and a depthwise causal
    # convolution of ssm_conv taps before them; a slot keeps a float32 state
    # [ssm_heads, ssm_head_dim, ssm_state] a layer in place of rows.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv: int = 4
    # what an expert (routed, shared, or the dense FFN) does between its two
    # products: "swiglu" = down(silu(gate(x)) * up(x)), three matrices with
    # [gate | up] fused; "relu2" = down(relu(up(x))^2), two matrices, no gate
    expert_act: str = "swiglu"
    # False: a grouped-query layer applies no rotary embedding to q and k
    # (the state-space layers of its stack carry position)
    rotary: bool = True
    # group-limited routing (sigmoid scoring): the router's experts are
    # n_group groups of consecutive experts, a group scores the sum of its
    # two largest biased scores, and the top-k is taken among the topk_group
    # best groups' experts. 0 = no groups.
    n_group: int = 0
    topk_group: int = 0
    # serving replicas per managed model (aios_tpu/serving/): N independent
    # engine+batcher replicas behind one cache-aware router. 1 = the
    # single-engine layout; AIOS_TPU_REPLICAS overrides at load time.
    replicas: int = 1
    # host-RAM spill tier behind the prefix cache (engine/paged.py
    # HostPageStore): byte budget for evicted prefix pages' KV, restored
    # device-side on a later hash-chain hit instead of re-prefilled.
    # 0 = off; AIOS_TPU_PREFIX_HOST_BYTES overrides at load time.
    prefix_host_bytes: int = 0
    # pipelined decode loop (engine/batching.py): decode dispatch N+1 is
    # enqueued before dispatch N's tokens are emitted/detokenized, so the
    # host phase overlaps device execution instead of idling it. The
    # default loop; False (or AIOS_TPU_DECODE_PIPELINE=0 at load time) is
    # the synchronous one (docs/ENGINE_PERF.md).
    decode_pipeline: bool = True
    # grammar jump-ahead for constrained decoding (engine/batching.py
    # _jump_tick): chains of grammar-FORCED tokens (singleton masks —
    # schema key literals, '":', '",', closers) emit host-side and append
    # their KV in ONE multi-token dispatch instead of one masked dispatch
    # each. Greedy-identical to the per-step path; AIOS_TPU_JUMP_AHEAD
    # overrides at load time (docs/ENGINE_PERF.md).
    jump_ahead: bool = True
    # auto-disable speculation per batcher and PER PROPOSER when that
    # proposer's EWMA draft-acceptance ratio collapses below this floor
    # (the ladder falls draft -> ngram -> off; plain/pipelined decode
    # serves meanwhile and probe dispatches re-measure periodically).
    # 0 = never auto-disable. AIOS_TPU_SPEC_MIN_ACCEPT overrides.
    spec_min_accept: float = 0.0
    # how long an auto-disabled proposer stays suspended before its probe
    # dispatches re-measure (engine/batching.py SPEC_PROBE_DISPATCHES of
    # them re-judge on a fresh cumulative average).
    # AIOS_TPU_SPEC_REPROBE_SECS overrides at load time.
    spec_reprobe_secs: float = 10.0
    # draft-model speculation (engine/spec.py DraftModel): the model
    # source — a preset name like "tinyllama" or a weights path — loaded
    # as an int4 draft whose proposals the serving model verifies in one
    # dispatch (docs/ENGINE_PERF.md). "" = n-gram prompt-lookup only.
    # Requires the serving and draft models to share a tokenizer/vocab;
    # single-device pools only (dp-replicated pools fall back to n-gram).
    # AIOS_TPU_DRAFT_MODEL overrides at load time.
    draft_model: str = ""
    # radix-tree prefix index (engine/paged.py RadixPrefixIndex): cross-
    # request prefix sharing by construction with leaf-LRU eviction and
    # partial-node overlap credit for the router. False = the legacy flat
    # hash-chain map (escape hatch). AIOS_TPU_PREFIX_RADIX overrides.
    prefix_radix: bool = True
    # Long-context tier (docs/ENGINE_PERF.md "Long-context tier"):
    # window+sink KV compression — once a slot's length exceeds this many
    # rows, its paged KV is pruned to kv_sink_pages leading pages (the
    # attention sinks) plus a sliding window of kv_window_pages trailing
    # pages; the freed middle pages return to the pool and decode masks
    # attend only to the live rows (SnapStream/StreamingLLM-style,
    # PAPERS.md). 0 = off (exact full attention). Below the threshold
    # streams are token-exact; above it they are a deterministic
    # approximation. Paged engines with an unreplicated pool only.
    # AIOS_TPU_KV_COMPRESS_AFTER overrides at load time.
    kv_compress_after: int = 0
    # leading pages kept live under KV compression (attention sinks —
    # the first tokens anchor the softmax; >= 1).
    # AIOS_TPU_KV_SINK_PAGES overrides.
    kv_sink_pages: int = 1
    # trailing sliding-window pages kept live under KV compression
    # (>= 1). AIOS_TPU_KV_WINDOW_PAGES overrides.
    kv_window_pages: int = 8
    # sequence-sharded prefill (parallel/ring_attention.py / ulysses.py):
    # prompts at least this many rows long prefill in ONE dispatch with
    # the sequence sharded over the mesh's sp axis instead of serially
    # through chunked admission — the whole mesh works one huge prompt's
    # prefill, and the resulting KV scatters back into the normal paged
    # layout so decode/prefix-cache/spill/failover see nothing new.
    # 0 = off. Needs a sharding plan with sp > 1 and an unreplicated
    # paged pool. AIOS_TPU_SEQ_PREFILL_MIN overrides.
    seq_prefill_min: int = 0

    def __post_init__(self) -> None:
        # plain data (a list; a kind's table as pairs of RopeParams' fields)
        # is taken too: the benchmark's files import nothing of the program
        if not isinstance(self.layer_types, tuple):
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_by_kind", tuple(
            (kind, rope if isinstance(rope, RopeParams) else RopeParams(**dict(rope)))
            for kind, rope in self.rope_by_kind
        ))
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"{self.name}: unknown moe_scoring {self.moe_scoring!r}")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"{self.name}: unknown expert_act {self.expert_act!r}")
        if self.experts_held and not (
            0 <= self.first_expert
            and self.first_expert + self.experts_held <= self.num_experts
        ):
            raise ValueError(
                f"{self.name}: experts [{self.first_expert}, "
                f"{self.first_expert + self.experts_held}) are not among the "
                f"router's {self.num_experts}"
            )
        if (self.first_k_dense or self.sandwich_norm) and not self.mla:
            raise ValueError(
                f"{self.name}: leading dense layers and sandwich norms are "
                "built for the latent-attention block only "
                "(engine/latent.py); the grouped-query block has neither"
            )
        if self.hc and not self.mla:
            raise ValueError(
                f"{self.name}: a residual of several mixed streams "
                "(hc_mult > 1) is built for the latent-attention block only "
                "(engine/latent.py, engine/residual.py); the grouped-query "
                "block writes x + F(norm(x))"
            )
        for rope in (self.rope_of(None), *(r for _, r in self.rope_by_kind)):
            if rope.factor > 1.0 and rope.original_context <= 0:
                raise ValueError(
                    f"{self.name}: YaRN (rope_factor {rope.factor}) needs "
                    "rope_original_context"
                )
        self._check_layer_types()
        if self.mla and not (
            self.qk_nope_head_dim and self.qk_rope_head_dim and self.v_head_dim
        ):
            raise ValueError(
                f"{self.name}: latent attention needs the three head dims "
                "beside kv_lora_rank (q_lora_rank 0 is a direct query "
                "projection)"
            )
        if (self.latent_qk_norm or self.rope_interleave
                or self.attn_head_gate) and not self.mla:
            raise ValueError(
                f"{self.name}: latent_qk_norm, rope_interleave and "
                "attn_head_gate are the latent-attention block's "
                "(engine/latent.py)"
            )
        if self.n_group or self.topk_group:
            if not (
                self.moe_scoring == "sigmoid" and self.n_group > 0
                and self.num_experts % self.n_group == 0
                and 0 < self.topk_group <= self.n_group
                and self.num_experts // self.n_group >= 2
                and self.topk_group * (self.num_experts // self.n_group)
                >= self.num_experts_per_tok
            ):
                raise ValueError(
                    f"{self.name}: group-limited routing needs sigmoid "
                    f"scoring and n_group ({self.n_group}) groups of at "
                    f"least two of the router's {self.num_experts} experts, "
                    f"of which topk_group ({self.topk_group}) hold the "
                    f"top {self.num_experts_per_tok}"
                )

    def _check_layer_types(self) -> None:
        types = self.layer_types
        if not types and not self.rope_by_kind:
            return
        if "kda" in types:
            return self._check_state_kinds()
        if "mamba2" in types or "moe" in types:
            return self._check_sublayers()
        unknown = (set(types) | {k for k, _ in self.rope_by_kind}) - set(LAYER_KINDS)
        if unknown or (types and len(types) != self.num_layers):
            raise ValueError(
                f"{self.name}: layer_types names one of {LAYER_KINDS} for "
                f"each of the {self.num_layers} layers, and rope_by_kind "
                f"pairs those kinds with RopeParams; got {len(types)} "
                f"entries and the unknown kinds {sorted(unknown)}"
            )
        if self.mla:
            raise ValueError(
                f"{self.name}: layers of two kinds are built for the "
                "grouped-query block only (the latent pool has one table "
                "a slot)"
            )
        if self.rope_by_kind and not self.kinds:
            raise ValueError(
                f"{self.name}: rope_by_kind is for a stack that mixes window "
                "and full layers; a stack of one kind has one rotary table, "
                "that of rope_theta and the rope_* fields"
            )
        if ("window" in types) != (self.sliding_window is not None):
            raise ValueError(
                f"{self.name}: sliding_window is the window of the window "
                "kind: set it iff layer_types has a window layer"
            )

    def _check_state_kinds(self) -> None:
        types = self.layer_types
        unknown = set(types) - set(STATE_KINDS)
        if unknown or len(types) != self.num_layers or self.rope_by_kind:
            raise ValueError(
                f"{self.name}: a stack with kda layers names one of "
                f"{STATE_KINDS} for each of the {self.num_layers} layers and "
                f"has one rotary table; got {len(types)} entries, the "
                f"unknown kinds {sorted(unknown)} and rope_by_kind "
                f"{self.rope_by_kind!r}"
            )
        if not (self.mla and self.kda_heads and self.kda_key_dim
                and self.kda_value_dim and self.kda_conv >= 2
                and self.kda_lower_bound < 0):
            raise ValueError(
                f"{self.name}: kda layers stand beside mla layers alone, in "
                "a latent-attention stack (kv_lora_rank > 0; a state-space "
                "layer beside grouped-query pages is the mamba2 kind of "
                "SUBLAYER_KINDS), and need kda_heads, kda_key_dim, "
                "kda_value_dim, kda_conv >= 2 taps and kda_lower_bound < 0"
            )
        if self.hc or self.sandwich_norm or self.sliding_window is not None:
            raise ValueError(
                f"{self.name}: kda layers are built beside the plain latent "
                "block: no mixed streams, sandwich norms or window"
            )

    def _check_sublayers(self) -> None:
        types = self.layer_types
        unknown = set(types) - set(SUBLAYER_KINDS)
        if unknown or len(types) != self.num_layers or self.rope_by_kind:
            raise ValueError(
                f"{self.name}: a stack of sub-layers names one of "
                f"{SUBLAYER_KINDS} for each of the {self.num_layers} layers "
                f"and has no rotary table by kind; got {len(types)} entries, "
                f"the unknown kinds {sorted(unknown)} and rope_by_kind "
                f"{self.rope_by_kind!r}"
            )
        if not ("mamba2" in types and self.ssm_heads and self.ssm_head_dim
                and self.ssm_state and self.ssm_groups
                and self.ssm_heads % self.ssm_groups == 0
                and self.ssm_conv >= 2):
            raise ValueError(
                f"{self.name}: a stack of sub-layers has mamba2 layers (a "
                "stack without them is the grouped-query block's) and needs "
                "ssm_heads in whole ssm_groups, ssm_head_dim, ssm_state and "
                "ssm_conv >= 2 taps"
            )
        if "moe" in types and not self.moe:
            raise ValueError(
                f"{self.name}: moe sub-layers need num_experts (the dense "
                "FFN kind of such a stack is not built)"
            )
        if (self.mla or self.hc or self.sandwich_norm or self.first_k_dense
                or self.sliding_window is not None or self.qk_norm):
            raise ValueError(
                f"{self.name}: a stack of sub-layers is built beside the "
                "plain grouped-query layer: no latent attention, mixed "
                "streams, sandwich norms, leading dense layers, window or "
                "q/k norms"
            )

    @property
    def state_kind(self) -> Optional[str]:
        """The kind of layer that keeps a recurrent state a slot in place of
        cache rows (engine/paged.py's state kind): ``kda`` in a
        latent-attention stack, ``mamba2`` in a stack of sub-layers, None in
        a stack without one."""
        for kind in ("kda", "mamba2"):
            if kind in self.layer_types:
                return kind
        return None

    @property
    def state_kinds(self) -> bool:
        """True when some layers keep a recurrent state a slot."""
        return self.state_kind is not None

    @property
    def sublayers(self) -> bool:
        """True for a stack whose layers are one sub-layer each
        (SUBLAYER_KINDS; engine/mamba2.py)."""
        return "mamba2" in self.layer_types

    @property
    def kinds(self) -> bool:
        """True when the stack mixes window and full attention layers."""
        return len(set(self.layer_types)) > 1 and not self.state_kinds

    def layers_of(self, kind: str) -> int:
        """How many layers of ``kind`` the stack has."""
        return sum(k == kind for k in self.layer_types)

    @property
    def row_layers(self) -> int:
        """Layers that keep cache ROWS in pages: all but the ``kda`` ones;
        of a stack of sub-layers its ``full`` layers alone."""
        if self.sublayers:
            return self.layers_of("full")
        return self.num_layers - self.layers_of("kda")

    @property
    def kda_state_shapes(self) -> tuple:
        """One slot's state kind, a kda layer: the float32 state
        [heads, key, value] and the bfloat16 convolution tail (the last
        kda_conv - 1 rows of the q | k | v projections)."""
        h, k, v = self.kda_heads, self.kda_key_dim, self.kda_value_dim
        return (h, k, v), (self.kda_conv - 1, h * (2 * k + v))

    @property
    def ssm_inner(self) -> int:
        """A mamba2 layer's inner width: heads x channels."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Columns the mamba2 convolution runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def state_shapes(self) -> tuple:
        """One slot's state kind a layer of ``state_kind``: (the float32
        state's shape, the bfloat16 convolution tail's [taps - 1, width])."""
        if self.state_kind == "mamba2":
            return (
                (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                (self.ssm_conv - 1, self.ssm_conv_dim),
            )
        return self.kda_state_shapes

    @property
    def period(self) -> int:
        """Layers in one repeat of ``layer_types`` (1 for a stack of one
        kind): the least divisor of the depth the pattern repeats with, the
        depth itself where it never does (the scan's body is then the stack).
        The leading dense layers of a stack with kda layers stand before
        the pattern (their tree differs: model.layer_segments)."""
        if not (self.kinds or self.state_kinds):
            return 1
        types = self.layer_types[len(self.lead_kinds):]
        return next(
            p for p in range(1, len(types) + 1)
            if len(types) % p == 0
            and all(types[i] == types[i % p] for i in range(len(types)))
        )

    @property
    def period_kinds(self) -> tuple:
        """The kind of each layer of one period; () for a stack of one."""
        if not (self.kinds or self.state_kinds):
            return ()
        lead = len(self.lead_kinds)
        return tuple(self.layer_types[lead: lead + self.period])

    @property
    def lead_kinds(self) -> tuple:
        """The kinds of the leading dense layers that stand before the
        pattern (a stack with kda layers; () otherwise)."""
        if not self.state_kinds:
            return ()
        return tuple(self.layer_types[: self.first_k_dense])

    def window_of(self, kind: Optional[str]) -> Optional[int]:
        """The window of a layer of ``kind`` (None: a stack of one kind)."""
        return None if kind == "full" else self.sliding_window

    def rope_of(self, kind: Optional[str]) -> RopeParams:
        """The rotary parameters of a layer of ``kind``: ``rope_by_kind``'s
        where it names the kind, else the model's own fields."""
        for k, rope in self.rope_by_kind:
            if k == kind:
                return rope
        factor = float(self.rope_factor)
        scale = 1.0
        if factor > 1.0 and not self.mla:
            scale = 0.1 * math.log(factor) + 1.0
        return RopeParams(
            theta=float(self.rope_theta), factor=factor,
            original_context=int(self.rope_original_context),
            beta_fast=float(self.rope_beta_fast),
            beta_slow=float(self.rope_beta_slow), attention_factor=scale,
        )

    @property
    def moe(self) -> bool:
        return self.num_experts > 0

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def hc(self) -> bool:
        """True when the residual is several mixed streams a token."""
        return self.hc_mult > 1

    @property
    def held_experts(self) -> int:
        """Experts whose weights live here (the leading axis of we_*)."""
        return self.experts_held or self.num_experts

    @property
    def expert_share(self) -> bool:
        """True when this chip holds a proper share of each layer's experts."""
        return self.moe and self.held_experts < self.num_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_row_dims(self) -> tuple:
        """Stored widths of one cache row in the two pool arrays
        (engine/paged.py header): per-head K and V merged for grouped-query
        attention; for latent attention the compressed latent, and the
        rotary part padded to a whole 128-lane tile."""
        if self.mla:
            return (self.kv_lora_rank, -(-self.qk_rope_head_dim // 128) * 128)
        return (self.kv_dim, self.kv_dim)

    @property
    def expert_dim(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def group_size(self) -> int:
        return self.num_heads // self.num_kv_heads

    def scaled(self, **overrides) -> "ModelConfig":
        return replace(self, **overrides)

    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        e = self.vocab_size * self.hidden_size
        attn = self.hidden_size * self.q_dim * 2 + self.hidden_size * self.kv_dim * 2
        if self.moe:
            mlp = self.hidden_size * self.num_experts + (
                self.num_experts * 3 * self.hidden_size * self.expert_dim
            )
        else:
            mlp = 3 * self.hidden_size * self.intermediate_size
        norms = 2 * self.hidden_size
        head = 0 if self.tie_word_embeddings else e
        return e + self.num_layers * (attn + mlp + norms) + self.hidden_size + head

    def active_params(self) -> int:
        """Params touched per token (MoE: only the routed experts' FFNs) —
        the number that sets decode FLOPs, vs num_params() which sets HBM
        footprint."""
        if not self.moe:
            return self.num_params()
        e = self.vocab_size * self.hidden_size
        attn = self.hidden_size * self.q_dim * 2 + self.hidden_size * self.kv_dim * 2
        mlp = self.hidden_size * self.num_experts + (
            self.num_experts_per_tok * 3 * self.hidden_size * self.expert_dim
        )
        head = 0 if self.tie_word_embeddings else e
        return e + self.num_layers * (attn + mlp) + head


# ---------------------------------------------------------------------------
# Presets — the model tiers of the reference intelligence hierarchy
# ---------------------------------------------------------------------------

TINYLLAMA_1_1B = ModelConfig(
    name="tinyllama-1.1b",
    vocab_size=32000,
    hidden_size=2048,
    intermediate_size=5632,
    num_layers=22,
    num_heads=32,
    num_kv_heads=4,
    head_dim=64,
    max_context=2048,
    rope_theta=10000.0,
)

MISTRAL_7B = ModelConfig(
    name="mistral-7b",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_context=8192,
    rope_theta=10000.0,
    sliding_window=4096,
)

DEEPSEEK_R1_8B = ModelConfig(
    # DeepSeek-R1-Distill-Llama-8B: Llama-3.1-8B geometry
    name="deepseek-r1-8b",
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_context=8192,
    rope_theta=500000.0,
)

QWEN3_14B = ModelConfig(
    name="qwen3-14b",
    vocab_size=151936,
    hidden_size=5120,
    intermediate_size=17408,
    num_layers=40,
    num_heads=40,
    num_kv_heads=8,
    head_dim=128,
    max_context=8192,
    rope_theta=1000000.0,
    rms_norm_eps=1e-6,
    qk_norm=True,
)

QWEN3_30B_A3B = ModelConfig(
    # The MoE tier the reference only reaches via the cloud gateway
    # (qwen3:30b-128k @ api.viwoapp.net, api-gateway/src/main.rs:70-88):
    # served locally here — 30B params in HBM, ~3B active per token.
    name="qwen3-30b-a3b",
    vocab_size=151936,
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    max_context=32768,
    rope_theta=1000000.0,
    rms_norm_eps=1e-6,
    qk_norm=True,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
)

MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_context=32768,
    rope_theta=1000000.0,
    num_experts=8,
    num_experts_per_tok=2,
)

PRESETS: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        TINYLLAMA_1_1B,
        MISTRAL_7B,
        DEEPSEEK_R1_8B,
        QWEN3_14B,
        QWEN3_30B_A3B,
        MIXTRAL_8X7B,
    )
}

# Tiny variants for tests / dry runs (same code paths, trivial sizes).
# vocab 512 covers the ByteTokenizer's 258 ids (bos=256, eos=257).
TINY_TEST = ModelConfig(
    name="tiny-test",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_context=128,
)

TINY_MOE = ModelConfig(
    name="tiny-moe",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_context=128,
    num_experts=4,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
)


def resolve(name: str) -> ModelConfig:
    """Case-insensitive partial matching, like the reference's
    select_model_for_level (model_manager.rs:506-518)."""
    low = name.lower()
    if low in PRESETS:
        return PRESETS[low]
    for key, cfg in PRESETS.items():
        if low in key or key in low:
            return cfg
    raise KeyError(f"unknown model config: {name}")


def from_gguf_metadata(md: Dict[str, Any]) -> ModelConfig:
    """Build a config from GGUF metadata keys (llama/mistral/qwen archs)."""
    arch = md.get("general.architecture", "llama")

    def key(suffix: str, default=None):
        return md.get(f"{arch}.{suffix}", default)

    heads = int(key("attention.head_count"))
    kv_heads = int(key("attention.head_count_kv", heads))
    hidden = int(key("embedding_length"))
    head_dim = int(key("attention.key_length", hidden // heads))
    vocab = int(md.get("tokenizer.ggml.tokens and vocab", 0)) or len(
        md.get("tokenizer.ggml.tokens", [])
    ) or int(key("vocab_size", 32000))
    num_experts = int(key("expert_count", 0) or 0)
    return ModelConfig(
        num_experts=num_experts,
        num_experts_per_tok=int(key("expert_used_count", 2) or 2),
        moe_intermediate_size=(
            int(key("expert_feed_forward_length"))
            if key("expert_feed_forward_length")
            else None
        ),
        norm_topk_prob=bool(key("expert_weights_norm", True)),
        name=md.get("general.name", arch).lower().replace(" ", "-"),
        vocab_size=vocab,
        hidden_size=hidden,
        intermediate_size=int(key("feed_forward_length")),
        num_layers=int(key("block_count")),
        num_heads=heads,
        num_kv_heads=kv_heads,
        head_dim=head_dim,
        max_context=int(key("context_length", 4096)),
        rope_theta=float(key("rope.freq_base", 10000.0)),
        rms_norm_eps=float(key("attention.layer_norm_rms_epsilon", 1e-5)),
        sliding_window=(
            int(key("attention.sliding_window")) if key("attention.sliding_window") else None
        ),
        qk_norm=arch.startswith("qwen3"),
    )


def from_hf_config(hf: Dict[str, Any], name: str = "hf-model") -> ModelConfig:
    """Build a config from a HuggingFace config dict
    (Llama/Mistral/Qwen3/Mixtral/Qwen3-MoE)."""
    heads = hf["num_attention_heads"]
    # num_local_experts (mixtral) / num_experts (qwen3_moe)
    num_experts = hf.get("num_local_experts") or hf.get("num_experts") or 0
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        max_context=hf.get("max_position_embeddings", 4096),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        sliding_window=hf.get("sliding_window"),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        qk_norm=hf.get("model_type", "") in ("qwen3", "qwen3_moe"),
        num_experts=num_experts,
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        moe_intermediate_size=hf.get("moe_intermediate_size"),
        # mixtral always renormalizes the top-k weights; qwen3_moe gates it
        norm_topk_prob=hf.get("norm_topk_prob", True),
    )
