"""Model configuration for the JAX engine's native model families.

The reference framework delegates the model to external engines (vLLM /
SGLang / TRT-LLM); the TPU build runs its own models, so the config lives
here.  Shapes follow the HF `LlamaConfig` field names so checkpoints load
without a translation table (reference consumes the same HF config when
building its ModelDeploymentCard, /root/reference/lib/llm/src/model_card.rs:118).
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Optional


MOE_ACTS = ("silu", "gpt_oss_glu", "relu_glu", "relu2")
# a layer's ONE mixer under `ModelConfig.layer_pattern`
LAYER_KINDS = {"M": "a state-space (Mamba-2) mixer", "E": "an expert "
               "feed-forward", "*": "attention",
               # falcon_h1: a layer that is BOTH, then a dense feed-forward;
               # every layer of a model that has one (`__post_init__`)
               "P": "a Mamba-2 mixer and attention side by side"}
# a decoder-hybrid-decoder's layers (phi4flash), each a mixer THEN a dense
# feed-forward: the self half's "S" and "W" in turn, its last pair "S", "F";
# the cross half's "G" and "C" in turn
CROSS_LAYER_KINDS = {
    "S": "a Mamba-1 selective-scan mixer",
    "W": "differential attention under the window",
    "F": "full differential attention, whose pages the cross half reads",
    "G": "a gated memory unit over the last S layer's scan output",
    "C": "differential cross-attention over the F layer's pages"}
MOE_SCORINGS = ("softmax", "sigmoid")


@dataclass(frozen=True)
class CacheSpec:
    """What one token leaves in the page pool, a layer: the ONE description
    every allocator, exporter and log reads.  The pool is two arrays [L, P,
    page, *dims], one per entry of `plane_dims`:

    "kv"      keys and values, [heads, width] each.
    "latent"  latent attention stores no per-head keys or values: the
              rotated rotary key all heads share (`key_width` values), and
              the normalised latent (`width` values) from which every
              head's key and value are computed when they are attended to.

    A latent plane is stored as whole 128-lane tiles, a power of two and
    at least two of them a token ([4, 128] for a 512-wide latent, [2, 128]
    for a 64-wide key and 192 zeros): the geometry of a [heads, 128] key
    page, which the TPU compiler keeps where it is.  ONE plane of [1, 576]
    rows, or of [5, 128], it stores in another order than its scatter and
    its gather want and re-lays out around both (four copies of the whole
    pool a step); a [1, 128] key plane likewise, twice a step (AOT for a
    v5e, PR 35).  `bytes_per_token_layer` counts what is stored."""

    kind: str  # "kv" | "latent"
    heads: int
    width: int
    key_width: int = 0  # latent: the shared rotary key's values
    # "kv": heads narrower than a lane tile stored side by side as whole
    # tiles, [heads * width / 128, 128] a token: the same values in the same
    # order as [heads, width] (`ops.pallas_attention.packed_plane`)
    packed: bool = False

    @property
    def values(self) -> int:
        """Values a token leaves a layer, padding apart."""
        if self.kind == "latent":
            return self.key_width + self.width
        return 2 * self.heads * self.width

    @property
    def plane_dims(self) -> tuple:
        """The trailing [*, *] of the pool's two arrays (k's, v's)."""
        if self.packed:
            return ((self.heads * self.width // 128, 128),) * 2
        if self.kind != "latent":
            return ((self.heads, self.width),) * 2

        def tiles(n):
            t = 2
            while t * 128 < n:
                t *= 2
            return (t, 128)

        return tiles(self.key_width), tiles(self.width)

    def bytes_per_token_layer(self, itemsize: int) -> int:
        return sum(a * b for a, b in self.plane_dims) * itemsize


@dataclass(frozen=True)
class StateSpec:
    """What one SEQUENCE leaves a state-space layer, whatever its length:
    the ONE description every allocator and log reads, as `CacheSpec` is
    for what a token leaves a page.  The pool beside the pages is two
    arrays [layers, slots, ...]: the convolution's last `conv_kernel - 1`
    inputs in the served dtype, stored as whole 128-lane tiles (a [3, 6144]
    window as [144, 128], the geometry of a key page), and the recurrent
    state `state_dims` in float32: a Mamba-2 layer's [heads, head_dim,
    state]; a Mamba-1 layer's [state, channels], the channels under the
    lanes (a [5120, 16] state stored the other way round would fill an
    eighth of every tile).  `state_dims` () is a state that is the window
    ALONE (a gated short convolution: lfm2_moe's [2, 2048] a layer): the
    pool is then the one array, and nothing is allocated, read, written or
    snapshotted for a recurrent part (`recurrent`)."""

    layers: int
    state_dims: tuple
    conv_dim: int
    conv_kernel: int

    @property
    def window_dims(self) -> tuple:
        """The window as the pool stores it: [tiles, 128]."""
        n = (self.conv_kernel - 1) * self.conv_dim
        return (-(-n // 128), 128)

    @property
    def recurrent(self) -> bool:
        """Is there a recurrent state beside the window?"""
        return bool(self.state_dims)

    def bytes_per_slot(self, itemsize: int) -> int:
        """One sequence's state over every state-space layer: the window in
        the served dtype, the recurrent state (if any) in float32."""
        w = self.window_dims[0] * self.window_dims[1] * itemsize
        h = 4 * math.prod(self.state_dims) if self.recurrent else 0
        return self.layers * (w + h)


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only transformer.

    Covers the Llama family (Llama 2/3, TinyLlama, Mistral-style GQA) and
    Mixtral/DeepSeek-style MoE variants via ``num_experts``.
    """

    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 22
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    # Qwen2-VL multimodal rope: head_dim//2 rotary frequencies split
    # into (temporal, height, width) sections; text tokens carry equal
    # ids on all three streams (ops.apply_mrope).  None = standard rope.
    mrope_section: Optional[tuple] = None
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # gpt-oss biases o_proj too (qwen2 biases only q/k/v)
    attention_out_bias: bool = False
    # sliding-window attention (Mistral/GPT-OSS family): tokens attend to
    # at most the last `sliding_window` positions.  `layer_types` (HF
    # convention: "sliding_attention" / "full_attention" per layer) mixes
    # windowed and full layers; None = every layer windowed.
    sliding_window: Optional[int] = None
    layer_types: Optional[tuple] = None
    # learnable per-head attention-sink logits (GPT-OSS): an extra column
    # in the softmax denominator that soaks up attention mass
    attention_sinks: bool = False
    # MoE (0 = dense)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: Optional[int] = None
    # "auto" (default): dropless and deterministic per token; which of the
    #   two dropless forms below runs is chosen by the token count of the
    #   step being traced (models/llama.py `_moe`, measured on the chip)
    # "ragged": dropless, the rows sorted by expert (exactly O(T*k) FFN
    #   rows; MaxText's sparse-matmul pattern): one grouped kernel on a
    #   single-device TPU program (ops/pallas_moe.py), ragged_dot elsewhere
    # "dense": all experts compute all tokens (the equality oracle)
    # "capacity": GShard capacity-bounded one-hot dispatch (einsum
    #   all-to-all under GSPMD; tokens past capacity drop)
    moe_impl: str = "auto"
    # capacity-dispatch headroom: C = ceil(G*k*factor/E);
    # <= 0 selects the dense all-experts path (equality oracle / tiny tests)
    moe_capacity_factor: float = 1.25
    # dispatch group size: tokens are dispatched within groups of this many
    # so the one-hot dispatch tensor stays O(T*G), not O(T^2)
    moe_group_size: int = 256
    # expert activation: "silu" (mixtral-style silu(gate)*up) or
    # "gpt_oss_glu" (clamped gate*sigmoid(1.702*gate) * (up+1) — HF
    # GptOssExperts with limit 7.0); moe_bias adds router + per-expert
    # gate/up/down biases (gpt-oss carries all four)
    # "relu_glu" is SmallThinker's sparse ReGLU: relu(gate) * up
    moe_act: str = "silu"
    moe_bias: bool = False
    # the router reads the LAYER'S INPUT (the residual stream before
    # input_layernorm and attention: SmallThinker) instead of the
    # post-attention normed stream the experts read
    moe_router_pre_attn: bool = False
    # per-layer rotary switch (SmallThinker `rope_layout`): 1 = rotate q/k,
    # 0 = no positions at all in that layer.  None = every layer rotates,
    # and the layer scans carry no extra operand.
    rope_layout: Optional[tuple] = None
    # latent attention (deepseek_v3: `kv_lora_rank` > 0).  Queries pass a
    # rank-`q_lora_rank` bottleneck with a norm; keys and values come from
    # ONE normalised latent of `kv_lora_rank` values a token beside ONE
    # rotary key of `qk_rope_head_dim` shared by all heads, and those two
    # are all the cache holds (`cache_spec`).  Scores are
    # `qk_nope_head_dim + qk_rope_head_dim` wide, values `v_head_dim`.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading layers that carry a dense feed-forward of `intermediate_size`
    # in a model whose other layers are expert layers
    # (`first_k_dense_replace`): the params carry them as a second stack,
    # `dense_layers`, ahead of `layers`
    first_k_dense: int = 0
    # experts every token passes beside the routed ones (one SwiGLU of
    # width n_shared_experts * moe_intermediate_size)
    n_shared_experts: int = 0
    # the router: "softmax" = top-k on the logits, softmax over the chosen;
    # "sigmoid" = deepseek_v3's `noaux_tc`: sigmoid scores, a bias added
    # for CHOOSING only, the choice limited to the `moe_topk_group` best of
    # `moe_n_group` groups (a group's score: its two largest biased
    # scores), weights from the unbiased scores normalised over the chosen
    # and multiplied by `moe_routed_scale`
    moe_scoring: str = "softmax"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scale: float = 1.0
    # the chip's share of an expert layer: `num_experts` are HELD here,
    # rank `moe_ep_rank` of `moe_ep_size` chips that share each layer; the
    # router keeps its full width `num_experts * moe_ep_size`, and the
    # layer computes its own experts' part of the result.  1 / 0: all held
    moe_ep_size: int = 1
    moe_ep_rank: int = 0
    # hyper-connections (xing4_0: `hc_mult` > 0): the residual is
    # `hc_mult` streams [..., hc_mult, hidden], every layer half reads a
    # learned mix of them and writes back through a `hc_mult` x `hc_mult`
    # matrix that `hc_sinkhorn_iters` Sinkhorn steps drive towards the
    # doubly stochastic ones from logits clipped to `hc_res_clamp`
    # (`ops/hyper_connections.py`).  0: the residual is x + f(x)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_res_clamp: tuple = (0.0, 0.0)
    # a layer that is ONE mixer with one norm and one residual add
    # (nemotron_h): character l of `layer_pattern` names layer l's, "M" a
    # Mamba-2 state-space mixer, "E" an expert feed-forward, "*" attention
    # (`LAYER_KINDS`).  Only the "*" layers have pages (`num_kv_layers`);
    # the "M" layers leave a sequence a state (`state_spec`).  "P"
    # (falcon_h1) is a layer of another build: a Mamba-2 mixer and attention
    # side by side from ONE norm, their sum on the residual, then a dense
    # feed-forward under its own norm; such a layer is in BOTH counts, it
    # owns pages and a state slot.  None: every layer is attention, then a
    # feed-forward
    layer_pattern: Optional[str] = None
    # the Mamba-2 mixer: `ssm_heads` heads of `ssm_head_dim`, B and C shared
    # by the heads of each of `ssm_groups` groups, a recurrent state of
    # `ssm_state` values a head channel, a causal depthwise convolution of
    # `ssm_conv_kernel` taps, the scan computed `ssm_chunk` tokens a block
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # the Mamba-1 mixer (phi4flash; 0: Mamba-2): ONE head of `ssm_head_dim`
    # channels, each with `ssm_state` decays of its own (`A` [channels,
    # state]: no block form), the step size through a `ssm_dt_rank`
    # bottleneck, the convolution over x alone
    ssm_dt_rank: int = 0
    # differential attention (phi4flash): heads pair off by parity, a
    # layer's output is softmax(q1 k1) V - lambda softmax(q2 k2) V over the
    # value PAIRS, then an rms norm a pair (`models/phi4flash.py`)
    diff_attention: bool = False
    # the shared expert's own width where the family states one
    # (nemotron_h); None: n_shared_experts * moe_intermediate_size
    moe_shared_intermediate_size: Optional[int] = None
    # attention applies no positions at all (nemotron_h: positions enter
    # through the state-space layers)
    attention_rope: bool = True
    # layers of two SHAPES (laguna: `models/laguna.py`).  `layer_heads`:
    # each layer's query heads (its `wq` is [hidden, heads * head_dim]: no
    # layer is padded to the widest); `mlp_layer_types`: "dense" | "sparse"
    # a layer; `rope_parameters`: ((layer type, ((key, value), ...)), ...)
    # of rope_theta, rope_type, partial_rotary_factor and yarn's keys, a
    # rope of its own for each kind in `layer_types`; `attention_gate`: sigmoid(normed input @ [hidden,
    # heads]) times each head's attention output, before `wo`.  None /
    # False: every layer has `num_attention_heads`, `rope_theta`, no gate
    layer_heads: Optional[tuple] = None
    mlp_layer_types: Optional[tuple] = None
    rope_parameters: Optional[tuple] = None
    attention_gate: bool = False
    # an RMS norm with a weight over the `head_dim` values of EACH head of
    # q and of k, after the projections and before the rope (lfm2's
    # `q_layernorm` / `k_layernorm`, qwen3's `q_norm` / `k_norm`): params
    # `q_head_norm`, `k_head_norm` [layers, head_dim]
    qk_norm: bool = False
    # a layer whose mixer is a gated short convolution (lfm2_moe: the layers
    # `layer_types` names "conv", walked by `models/laguna.py` beside the
    # attention layers): `[B, C, u] = in_proj(z)`, a causal depthwise
    # convolution of `short_conv_kernel` taps over `B * u` without bias or
    # activation, `out_proj(C * conv)`.  Such a layer keeps no keys: what a
    # sequence leaves it is the convolution's last `short_conv_kernel - 1`
    # inputs, a window in the state slots and nothing else (`state_spec`)
    short_conv_kernel: int = 0
    # added to the SUM of the chosen experts' sigmoid scores before the
    # router's weights are divided by it (lfm2_moe's 1e-6; 0: the sum alone)
    moe_norm_eps: float = 0.0
    # muP multipliers on ACTIVATIONS (falcon_h1; 1.0 / None: none).  Each is
    # applied to a product's float32 output before it is rounded, never
    # folded into a bf16 weight: the embedded tokens; the logits; the keys
    # (on top of `attention_in_multiplier`, which scales what q, k and v
    # read); attention's output; what the state-space mixer reads and what
    # it gives; `ssm_multipliers`, five factors over the parts [z | x | B |
    # C | dt] of `in_proj`'s output (`ssm_mup_vector`); `mlp_multipliers`,
    # (on the gate's product, on the down projection's)
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Optional[tuple] = None
    mlp_multipliers: Optional[tuple] = None
    # identity
    model_type: str = "llama"
    name: str = "llama"
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.moe_act not in MOE_ACTS:
            raise ValueError(
                f"moe_act must be one of {MOE_ACTS}, got {self.moe_act!r}")
        L = self.num_hidden_layers
        if self.rope_layout is not None and len(self.rope_layout) != L:
            raise ValueError(
                f"rope_layout has {len(self.rope_layout)} entries for "
                f"{L} layers")
        if self.moe_scoring not in MOE_SCORINGS:
            raise ValueError(f"moe_scoring must be one of {MOE_SCORINGS}, "
                             f"got {self.moe_scoring!r}")
        if not 0 <= self.moe_ep_rank < self.moe_ep_size:
            raise ValueError(f"moe_ep_rank {self.moe_ep_rank} is not one of "
                             f"{self.moe_ep_size} ranks")
        if self.first_k_dense and not 0 < self.first_k_dense < L:
            raise ValueError(
                f"first_k_dense {self.first_k_dense} must leave an expert "
                f"layer among {L}")
        if self.is_moe and self.router_width % self.moe_n_group:
            raise ValueError(
                f"moe_n_group {self.moe_n_group} must divide the router's "
                f"{self.router_width} outputs")
        if self.hc_mult and not self.is_latent:
            raise ValueError("hyper-connections (hc_mult) are implemented "
                             "around latent attention only (xing4_0)")
        if self.layer_pattern is not None:
            kinds = CROSS_LAYER_KINDS if self.cross_decoder else LAYER_KINDS
            bad = set(self.layer_pattern) - set(kinds)
            if bad or len(self.layer_pattern) != L:
                raise ValueError(
                    f"layer_pattern must name {L} layers by "
                    f"{sorted(kinds)}, got {self.layer_pattern!r}")
            if "P" in self.layer_pattern and set(self.layer_pattern) != {"P"}:
                raise ValueError(
                    "a layer of both mixers ('P': falcon_h1) stands beside "
                    f"no layer of one, got {self.layer_pattern!r}")
        elif self.moe_act == "relu2":
            raise ValueError("ungated relu2 experts are implemented under a "
                             "layer_pattern only (nemotron_h)")
        for key in ("layer_heads", "mlp_layer_types"):
            per_layer = getattr(self, key)
            if per_layer is not None and len(per_layer) != L:
                raise ValueError(f"{key} has {len(per_layer)} entries for "
                                 f"{L} layers")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def num_kv_groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def router_width(self) -> int:
        """The router's outputs: every expert of the layer, held or not."""
        return self.num_experts * self.moe_ep_size

    @property
    def first_expert(self) -> int:
        """Global index of the first expert held here."""
        return self.num_experts * self.moe_ep_rank

    @property
    def num_moe_layers(self) -> int:
        if self.layer_pattern is not None:
            return self.layer_pattern.count("E")
        if self.mlp_layer_types is not None:
            return self.mlp_layer_types.count("sparse")
        return (self.num_hidden_layers - self.first_k_dense
                if self.is_moe else 0)

    @property
    def layer_kinds(self) -> Optional[tuple]:
        """Each layer's (layer type, feed-forward type, query heads) where
        layers differ in SHAPE (`layer_heads`); None: one shape."""
        if self.layer_heads is None:
            return None
        return tuple(zip(self.layer_types, self.mlp_layer_types,
                         self.layer_heads))

    @property
    def cross_decoder(self) -> bool:
        """A decoder-hybrid-decoder (phi4flash: `CROSS_LAYER_KINDS`): the
        layers after "F" read what "F" and the last "S" left, and run only
        where a row samples (`models/phi4flash.py`)."""
        return "C" in (self.layer_pattern or "")

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is a gated short convolution (`layer_types`
        "conv" under `short_conv_kernel`)."""
        if not self.short_conv_kernel:
            return 0
        return sum(t == "conv" for t in self.layer_types)

    @property
    def num_kv_layers(self) -> int:
        """Layers that leave a token keys and values: the page pool's (the
        "C" layers read "F"'s pages, the "G" layers and the short
        convolutions keep nothing)."""
        if self.layer_pattern is not None:
            return sum(self.layer_pattern.count(c) for c in "*WFP")
        return self.num_hidden_layers - self.conv_layers

    @property
    def state_spec(self) -> Optional[StateSpec]:
        """What a sequence leaves the layers that keep a state a SEQUENCE
        (state-space mixers, short convolutions); None without."""
        if self.conv_layers:  # a window alone: no recurrent part
            return StateSpec(self.conv_layers, (), self.hidden_size,
                             self.short_conv_kernel)
        pattern = self.layer_pattern or ""
        # (a "P" layer is counted here AND in `num_kv_layers`)
        n = sum(pattern.count(c) for c in "MSP")
        if not n:
            return None
        dims = ((self.ssm_state, self.ssm_inner) if self.ssm_dt_rank else
                (self.ssm_heads, self.ssm_head_dim, self.ssm_state))
        return StateSpec(n, dims, self.ssm_conv_dim, self.ssm_conv_kernel)

    @property
    def ssm_inner(self) -> int:
        """The state-space mixer's inner width: heads x head_dim (Mamba-2's
        is not `expand` x hidden; Mamba-1 is one head)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the convolution runs over: x beside every group's B, C
        (Mamba-2), or x alone (Mamba-1)."""
        if self.ssm_dt_rank:
            return self.ssm_inner
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_mup_vector(self) -> Optional[tuple]:
        """What `in_proj`'s float32 output [z | x | B | C | dt] is multiplied
        by, value for value, as (factor, width) runs: `ssm_multipliers` over
        the five parts, times `ssm_in_multiplier` (the scale of what the
        projection read, which commutes with it); None without either."""
        if self.ssm_multipliers is None and self.ssm_in_multiplier == 1.0:
            return None
        gn = self.ssm_groups * self.ssm_state
        widths = (self.ssm_inner, self.ssm_inner, gn, gn, self.ssm_heads)
        return tuple((m * self.ssm_in_multiplier, w) for m, w in zip(
            self.ssm_multipliers or (1.0,) * 5, widths))

    @property
    def shared_expert_width(self) -> int:
        return (self.moe_shared_intermediate_size
                or self.n_shared_experts
                * (self.moe_intermediate_size or self.intermediate_size))

    @property
    def hc_mixer_width(self) -> int:
        """Outputs of a layer half's mixer: pre and post weights of the
        `hc_mult` streams and the matrix between them."""
        return self.hc_mult * (self.hc_mult + 2)

    @property
    def residual_report(self) -> dict:
        """What the layer loops carry (the worker's RESIDUAL line)."""
        if not self.hc_mult:
            return {"kind": "add", "streams": 1}
        return {"kind": "hyper_connections", "streams": self.hc_mult,
                "sinkhorn_iters": self.hc_sinkhorn_iters,
                "res_clamp": list(self.hc_res_clamp)}

    @property
    def cache_spec(self) -> CacheSpec:
        if self.is_latent:
            return CacheSpec("latent", 1, self.kv_lora_rank,
                             self.qk_rope_head_dim)
        if self.diff_attention:
            # the same values in the same order as [heads, head_dim], read
            # as 2 rows: heads 2i and 2i + 1 side by side are the key pair
            # [k1 | k2] and the value pair [v1 | v2] differential attention
            # reads, 128 wide, and a row holds half of the pairs.  A plane
            # of [10, 128] the TPU compiler pads to 16 rows and re-lays out
            # around the scatter (three pool-sized temporaries a step), one
            # of [1, 1280] likewise; 2 rows it keeps where they are (AOT
            # for a v5e, PR 48)
            return CacheSpec("kv", 2, self.num_key_value_heads
                             * self.head_dim_ // 2)
        kv, hd = self.num_key_value_heads, self.head_dim_
        # Heads narrower than a lane tile (8 of 64) whose token fills whole
        # tiles are stored as tiles, two heads a tile, which the prefill
        # kernel reads as they are stored.  Stored [8, 64] the TPU compiler
        # pads every head to a tile and copies the whole pool into that
        # form every step (AOT for a v5e, PR 55).  It turns on the head
        # width and on the ONE layer loop that carries such a plane (`models/
        # laguna.py`, the families with `layer_kinds`: it reshapes a chunk's
        # rows to the plane and decodes through itself); every other path
        # knows a page as [heads, head_dim] and keeps that layout (ROADMAP
        # D3), and the paths that cannot carry a walker's family refuse it
        # (`require_one_layer_shape`, `require_plain_cache`)
        narrow = hd < 128 and 128 % hd == 0 and kv * hd % 256 == 0
        return CacheSpec("kv", kv, hd,
                         packed=narrow and self.layer_kinds is not None)

    @property
    def latent_softmax_scale(self) -> float:
        """Latent attention's score scale: 1/sqrt(score width), times the
        square of yarn's `mscale_all_dim` amplitude (deepseek_v3 folds it
        into the scale: `ops.rope_attention_scale` is the cos/sin factor,
        mscale / mscale_all_dim, and does not cover it)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling or {}
        all_dim = rs.get("mscale_all_dim")
        factor = float(rs.get("factor", 1.0))
        if all_dim and factor > 1.0:
            m = 0.1 * float(all_dim) * math.log(factor) + 1.0
            scale *= m * m
        return scale

    def layer_windows(self) -> list:
        """Per-layer attention window (0 = full attention)."""
        L = self.num_hidden_layers
        if not self.sliding_window:
            return [0] * L
        if self.layer_types is None:
            return [self.sliding_window] * L
        if len(self.layer_types) != L:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{L} layers"
            )
        return [
            self.sliding_window if "sliding" in t else 0
            for t in self.layer_types
        ]

    def num_params(self) -> int:
        """Approximate parameter count (for memory planning)."""
        h, v, l = self.hidden_size, self.vocab_size, self.num_hidden_layers
        hd, nh = self.head_dim_, self.num_attention_heads
        if self.layer_pattern is not None:
            return self._num_params_pattern()
        if self.layer_kinds is not None:
            return self._num_params_kinds()
        if self.is_latent:
            qr, r = self.q_lora_rank, self.kv_lora_rank
            nope, pe, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                            self.v_head_dim)
            attn = (h * qr + qr + qr * nh * (nope + pe) + h * (r + pe) + r
                    + r * nh * (nope + vd) + nh * vd * h)
        else:
            attn = h * (nh * hd) + 2 * h * (
                self.num_key_value_heads * hd) + (nh * hd) * h
        dense = 3 * h * self.intermediate_size
        if not self.is_moe:
            return l * (attn + dense + 2 * h) + v * h * (
                1 if self.tie_word_embeddings else 2) + h
        # the experts HELD here, the router's full width (and its choosing
        # bias under the sigmoid router), the shared expert
        ffn_inter = self.moe_intermediate_size or self.intermediate_size
        mlp = (self.num_experts * 3 * h * ffn_inter + h * self.router_width
               + (self.router_width if self.moe_scoring == "sigmoid" else 0)
               + self.n_shared_experts * 3 * h * ffn_inter)
        emb = v * h * (1 if self.tie_word_embeddings else 2)
        k = self.first_k_dense
        # hyper-connections: two mixers a layer (phi, 3 scales, a base) and
        # the head's (phi, 1 scale, a base)
        n = self.hc_mult
        mixers = (l * 2 * (n * h * self.hc_mixer_width + 3
                           + self.hc_mixer_width)
                  + n * h * n + 1 + n) if n else 0
        return (l * (attn + 2 * h) + k * dense + (l - k) * mlp + emb + h
                + mixers)

    def _num_params_kinds(self) -> int:
        """`num_params` of layers of several shapes (`layer_kinds`)."""
        h, hd = self.hidden_size, self.head_dim_
        kv = self.num_key_value_heads * hd
        sparse = (h * self.router_width
                  + (self.router_width if self.moe_scoring == "sigmoid"
                     else 0)
                  + self.num_experts * 3 * h * self.moe_intermediate_size
                  + 3 * h * self.shared_expert_width)
        ffn = {"dense": 3 * h * self.intermediate_size, "sparse": sparse}
        gate = h if self.attention_gate else 0
        norms = 2 * hd if self.qk_norm else 0
        # a short convolution: in_proj [h, 3h], the taps, out_proj [h, h]
        conv = 4 * h * h + self.short_conv_kernel * h
        layers = sum((conv if kind == "conv" else 2 * h * nh * hd
                      + 2 * h * kv + gate * nh + norms) + ffn[mlp] + 2 * h
                     for kind, mlp, nh in self.layer_kinds)
        emb = self.vocab_size * h * (1 if self.tie_word_embeddings else 2)
        return layers + emb + h

    def _num_params_pattern(self) -> int:
        """`num_params` under a `layer_pattern`: every layer is its one
        mixer and one norm."""
        h, pat = self.hidden_size, self.layer_pattern
        d, cd, nh = self.ssm_inner, self.ssm_conv_dim, self.ssm_heads
        q = self.num_attention_heads * self.head_dim_
        kv = self.num_key_value_heads * self.head_dim_
        if self.cross_decoder:
            N, r, hd = self.ssm_state, self.ssm_dt_rank, self.head_dim_
            mamba = (h * 2 * d + d * self.ssm_conv_kernel + d
                     + d * (r + 2 * N) + r * d + d + d * N + d + d * h)
            diff = 4 * hd + 2 * hd + q * h + h  # lambdas, subln, out_proj
            mixer = {"S": mamba, "W": h * (q + 2 * kv) + q + 2 * kv + diff,
                     "G": 2 * h * d, "C": h * q + q + diff}
            mixer["F"] = mixer["W"]
            # two LayerNorms (weight and bias) and the feed-forward a layer
            layer = 4 * h + 3 * h * self.intermediate_size
            return (sum(mixer[c] + layer for c in pat)
                    + self.vocab_size * h + 2 * h)
        mamba = (h * (d + cd + nh) + cd * self.ssm_conv_kernel + cd + 3 * nh
                 + d + d * h)
        attn = h * q + 2 * h * kv + q * h
        fm = self.moe_intermediate_size or 0
        gated = 2 if self.moe_act == "relu2" else 3
        moe = (h * self.router_width
               + (self.router_width if self.moe_scoring == "sigmoid" else 0)
               + self.num_experts * gated * h * fm
               + (gated * h * self.shared_expert_width
                  if self.n_shared_experts else 0))
        emb = self.vocab_size * h * (1 if self.tie_word_embeddings else 2)
        # both mixers under one norm, a dense SwiGLU under another
        both = mamba + attn + 3 * h * self.intermediate_size + 2 * h
        return (pat.count("M") * (mamba + h) + pat.count("*") * (attn + h)
                + pat.count("E") * (moe + h) + pat.count("P") * both
                + emb + h)

    @staticmethod
    def from_hf_config(d: dict, name: str = "") -> "ModelConfig":
        """Build from a HF ``config.json`` dict (llama/mistral/mixtral/qwen2)."""
        num_experts = d.get("num_local_experts", d.get("n_routed_experts", 0)) or 0
        if d.get("model_type") == "smallthinker":
            return ModelConfig(**_smallthinker_fields(d, name))
        if d.get("model_type") == "deepseek_v3":
            return ModelConfig(**_deepseek_v3_fields(d, name))
        if d.get("model_type") == "xing4_0":
            return ModelConfig(**_xing4_0_fields(d, name))
        if d.get("model_type") == "nemotron_h":
            return ModelConfig(**_nemotron_h_fields(d, name))
        if d.get("model_type") == "phi4flash":
            return ModelConfig(**_phi4flash_fields(d, name))
        if d.get("model_type") == "laguna":
            return ModelConfig(**_laguna_fields(d, name))
        if d.get("model_type") == "lfm2_moe":
            return ModelConfig(**_lfm2_moe_fields(d, name))
        if d.get("model_type") == "falcon_h1":
            return ModelConfig(**_falcon_h1_fields(d, name))
        if d.get("mamba_d_ssm") or d.get("ssm_multipliers"):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for a state-space "
                "mixer beside attention in a layer under muP multipliers "
                "(mamba_d_ssm, ssm_multipliers) and only falcon_h1's is "
                "implemented: the llama branch would build another model")
        if d.get("conv_L_cache") or "conv" in (d.get("layer_types") or ()):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for short "
                "convolution layers (conv_L_cache, layer_types 'conv') and "
                "only lfm2_moe's are implemented: the llama branch would "
                "build another model")
        if d.get("num_attention_heads_per_layer"):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for head counts "
                "by layer (num_attention_heads_per_layer) and only "
                "laguna's are implemented: the llama branch would build "
                "another model")
        if d.get("mb_per_layer"):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for state-space "
                "layers among its attention layers (mb_per_layer) and only "
                "phi4flash's are implemented: the llama branch would build "
                "another model")
        if d.get("hybrid_override_pattern"):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for a layer "
                "pattern (hybrid_override_pattern) and only nemotron_h's is "
                "implemented: the llama branch would build another model")
        if d.get("kv_lora_rank"):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for latent "
                "attention (kv_lora_rank) and only deepseek_v3's and "
                "xing4_0's are implemented: the llama branch would build "
                "another model")
        if d.get("hc_mult"):
            raise ValueError(
                f"model_type {d.get('model_type')!r} asks for "
                "hyper-connections (hc_mult) and only xing4_0's are "
                "implemented: the llama branch would build another model")
        return ModelConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("intermediate_size", 4 * d["hidden_size"]),
            num_hidden_layers=d["num_hidden_layers"],
            num_attention_heads=d["num_attention_heads"],
            num_key_value_heads=d.get(
                "num_key_value_heads", d["num_attention_heads"]
            ),
            head_dim=d.get("head_dim"),
            max_position_embeddings=d.get("max_position_embeddings", 4096),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=d.get("rope_scaling"),
            # Qwen2-VL: rope_scaling {"type"|"rope_type": "mrope",
            # "mrope_section": [t, h, w]} (HF Qwen2VLConfig)
            mrope_section=(
                tuple(d["rope_scaling"]["mrope_section"])
                if (d.get("rope_scaling") or {}).get(
                    "rope_type", (d.get("rope_scaling") or {}).get("type")
                ) in ("mrope", "default") and
                (d.get("rope_scaling") or {}).get("mrope_section")
                else None
            ),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            # HF Qwen2Config has no attention_bias field — its attention
            # hardcodes qkv bias on (o_proj off); mirror that default
            attention_bias=(attn_bias := d.get(
                "attention_bias",
                d.get("model_type") in ("qwen2", "qwen2_vl",
                                        "qwen2_vl_text", "qwen2_5_vl",
                                        "qwen2_5_vl_text", "gpt_oss"),
            )),
            # gpt-oss biases o_proj too — ONE resolution of
            # attention_bias drives both fields so they cannot split
            attention_out_bias=(
                d.get("model_type") == "gpt_oss" and attn_bias
            ),
            num_experts=num_experts,
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            moe_intermediate_size=d.get("moe_intermediate_size"),
            moe_act=("gpt_oss_glu" if d.get("model_type") == "gpt_oss"
                     else "silu"),
            moe_bias=d.get("model_type") == "gpt_oss",
            # Qwen2.5 ships sliding_window=131072 with
            # use_sliding_window=false — HF only engages the window when
            # the flag is on (absent = on, the Mistral convention)
            sliding_window=(d.get("sliding_window")
                            if d.get("use_sliding_window", True) else None),
            layer_types=(tuple(d["layer_types"])
                         if d.get("layer_types") else None),
            # GPT-OSS attention always carries learnable sinks (HF
            # GptOssAttention `sinks` parameter)
            attention_sinks=d.get(
                "attention_sinks", d.get("model_type") == "gpt_oss"
            ),
            # HF Qwen3Attention norms each head of q and k (`q_norm`,
            # `k_norm`) before the rope, whatever its config says
            qk_norm=d.get("model_type") == "qwen3",
            model_type=d.get("model_type", "llama"),
            name=name or d.get("_name_or_path", "llama"),
        )

    @staticmethod
    def from_pretrained(path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return ModelConfig.from_hf_config(json.load(f), name=os.path.basename(path))


def _layout(d: dict, key: str, n_layers: int) -> list:
    layout = d.get(key)
    if layout is None:
        raise ValueError(f"a smallthinker config.json needs {key!r}")
    if len(layout) != n_layers or any(v not in (0, 1) for v in layout):
        raise ValueError(
            f"{key} must hold {n_layers} entries of 0 or 1, got {layout!r}")
    return [int(v) for v in layout]


def _smallthinker_fields(d: dict, name: str) -> dict:
    """PowerInfer SmallThinker (config.json of SmallThinker-21BA3B-Instruct):
    ReLU-gated experts with no dense layer and no `intermediate_size`, a
    router that reads the layer's input, top-k on the logits then softmax
    over the chosen, and two 0/1 layouts: which layers are windowed
    (`sliding_window_layout`) and which rotate q/k (`rope_layout`)."""
    L = d["num_hidden_layers"]
    if not d.get("moe_primary_router_apply_softmax", True):
        raise ValueError(
            "smallthinker: only the softmax router is implemented "
            "(moe_primary_router_apply_softmax false is the 4B's sigmoid)")
    if d.get("rope_scaling"):
        raise ValueError("smallthinker: rope_scaling is not implemented")
    windowed = _layout(d, "sliding_window_layout", L)
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        # no dense feed-forward anywhere: the expert width stands in, so
        # that nothing that sizes by intermediate_size reads a made-up 4h
        intermediate_size=d["moe_ffn_hidden_size"],
        num_hidden_layers=L,
        num_attention_heads=d["num_attention_heads"],
        num_key_value_heads=d["num_key_value_heads"],
        head_dim=d.get("head_dim"),
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        num_experts=d["moe_num_primary_experts"],
        num_experts_per_tok=d["moe_num_active_primary_experts"],
        moe_intermediate_size=d["moe_ffn_hidden_size"],
        moe_act="relu_glu",
        moe_router_pre_attn=True,
        sliding_window=(d.get("sliding_window_size")
                        if any(windowed) else None),
        layer_types=tuple("sliding_attention" if w else "full_attention"
                          for w in windowed),
        rope_layout=tuple(_layout(d, "rope_layout", L)),
        model_type="smallthinker",
        name=name or d.get("_name_or_path", "smallthinker"),
    )


def _nemotron_h_fields(d: dict, name: str) -> dict:
    """NVIDIA Nemotron-H (`model_type` "nemotron_h": Nemotron-3-Nano): a
    layer is ONE mixer, named by `hybrid_override_pattern`: a Mamba-2
    state-space mixer, an expert feed-forward of ungated relu^2 experts
    under the `noaux_tc` sigmoid router with a shared expert, or GQA
    attention without positions.  `n_routed_experts` counts the experts HELD
    by this rank (`ep_rank`, default 0) of `ep_size`, as deepseek_v3's.
    What is not implemented is refused by the key that asks for it."""
    def refuse(key, why):
        raise ValueError(f"nemotron_h: {key} {d.get(key)!r} {why}")

    L = d["num_hidden_layers"]
    pattern = d.get("hybrid_override_pattern")
    if not isinstance(pattern, str) or len(pattern) != L or (
            set(pattern) - set(LAYER_KINDS)):
        if isinstance(pattern, str) and "-" in pattern:
            refuse("hybrid_override_pattern", "has dense feed-forward layers "
                   "('-'), which are not implemented")
        refuse("hybrid_override_pattern", f"must name {L} layers by "
               f"{sorted(LAYER_KINDS)}")
    for key, want in (("mamba_hidden_act", "silu"),
                      ("mlp_hidden_act", "relu2")):
        if d.get(key, want) != want:
            refuse(key, f"only {want} is implemented")
    for key in ("use_bias", "mamba_proj_bias", "mlp_bias", "attention_bias"):
        if d.get(key):
            refuse(key, "projection biases are not implemented")
    if not d.get("use_conv_bias", True):
        refuse("use_conv_bias", "the convolution is implemented with its "
               "bias")
    if d.get("residual_in_fp32"):
        refuse("residual_in_fp32", "the residual is carried in the served "
               "dtype")
    if d.get("sliding_window"):
        refuse("sliding_window", "windowed attention is not implemented "
               "under a layer pattern")
    if not d.get("norm_topk_prob", True):
        refuse("norm_topk_prob", "only normalised weights are implemented")
    if d.get("n_shared_experts", 1) != 1:
        refuse("n_shared_experts", "one shared expert of "
               "moe_shared_expert_intermediate_size is implemented")
    lo, hi = d.get("time_step_limit", (0.0, float("inf")))
    if lo > 0.0 or hi != float("inf"):
        refuse("time_step_limit", "a clamped step size is not implemented")
    if d["mamba_num_heads"] % d["n_groups"]:
        refuse("n_groups", f"must divide mamba_num_heads "
               f"{d['mamba_num_heads']}")
    if "E" in pattern and not d.get("n_routed_experts"):
        refuse("n_routed_experts", "is needed: the pattern has expert layers")
    eps = d.get("layer_norm_epsilon", d.get("norm_eps", 1e-5))
    if d.get("norm_eps", eps) != eps:
        refuse("norm_eps", f"differs from layer_norm_epsilon {eps!r}: one "
               "epsilon serves every norm")
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        # no dense feed-forward anywhere: the expert width stands in
        intermediate_size=d["moe_intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=d["num_attention_heads"],
        num_key_value_heads=d["num_key_value_heads"],
        head_dim=d.get("head_dim"),
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rms_norm_eps=eps,
        # keys that the family's attention does not read (`attention_rope`)
        rope_theta=float(d.get("rope_theta", 10000.0)),
        attention_rope=False,
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        layer_pattern=pattern,
        ssm_heads=d["mamba_num_heads"],
        ssm_head_dim=d["mamba_head_dim"],
        ssm_groups=d["n_groups"],
        ssm_state=d["ssm_state_size"],
        ssm_conv_kernel=d["conv_kernel"],
        ssm_chunk=d.get("chunk_size", 128),
        num_experts=d.get("n_routed_experts", 0),
        num_experts_per_tok=d.get("num_experts_per_tok", 1),
        moe_intermediate_size=d["moe_intermediate_size"],
        moe_shared_intermediate_size=d.get(
            "moe_shared_expert_intermediate_size"),
        n_shared_experts=d.get("n_shared_experts", 1),
        moe_act="relu2",
        moe_scoring="sigmoid",
        moe_n_group=d.get("n_group", 1),
        moe_topk_group=d.get("topk_group", 1),
        moe_routed_scale=float(d.get("routed_scaling_factor", 1.0)),
        moe_ep_size=d.get("ep_size", 1),
        moe_ep_rank=d.get("ep_rank", 0),
        model_type="nemotron_h",
        name=name or d.get("_name_or_path", "nemotron_h"),
    )


def _phi4flash_fields(d: dict, name: str) -> dict:
    """Microsoft Phi-4-mini-flash (`model_type` "phi4flash"): a
    decoder-hybrid-decoder.  Layer l of L, each a mixer then a dense SwiGLU
    feed-forward behind LayerNorms: l even up to L/2 a Mamba-1 mixer, l odd
    below L/2 differential attention under `sliding_window`, l = L/2 + 1
    full differential attention, and past it the cross half: l even a gated
    memory unit over layer L/2's scan output, l odd differential
    cross-attention over layer L/2 + 1's keys and values.  No positions
    anywhere.  The Mamba-1 sizes are the family's defaults where
    config.json has no key for them.  What is not implemented is refused by
    the key that asks for it."""
    def refuse(key, why):
        raise ValueError(f"phi4flash: {key} {d.get(key)!r} {why}")

    L, h = d["num_hidden_layers"], d["hidden_size"]
    if d.get("mb_per_layer", 2) != 2:
        refuse("mb_per_layer", "only every second layer a state-space mixer "
               "is implemented")
    if L < 8 or L % 4:
        refuse("num_hidden_layers", "must be a multiple of 4 and at least "
               "8: half self-decoder (state-space and windowed layers in "
               "turn, then one full layer), half cross-decoder")
    if d.get("hidden_act", "silu") != "silu":
        refuse("hidden_act", "only silu is implemented")
    for key in ("mlp_bias", "lm_head_bias"):
        if d.get(key):
            refuse(key, "is not implemented")
    if not d.get("tie_word_embeddings", True):
        refuse("tie_word_embeddings", "only the tied head is implemented")
    if not isinstance(d.get("sliding_window"), int) or (
            d["sliding_window"] <= 0):
        refuse("sliding_window", "must be the windowed layers' one width "
               "in tokens")
    nq, nkv = d["num_attention_heads"], d.get("num_key_value_heads",
                                              d["num_attention_heads"])
    if nkv % 2 or nq % nkv:
        refuse("num_key_value_heads", "differential attention pairs heads "
               f"by parity: must be even and divide the {nq} query heads")
    if d.get("rope_scaling"):
        refuse("rope_scaling", "the family's attention applies no "
               "positions: nothing would read it")
    expand = d.get("mamba_expand", 2)
    dt_rank = d.get("mamba_dt_rank", "auto")
    half = L // 2
    pattern = "SW" * (half // 2) + "SF" + "GC" * ((half - 2) // 2)
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=h,
        intermediate_size=d["intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=nq,
        num_key_value_heads=nkv,
        head_dim=d.get("head_dim"),
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        # one epsilon: the LayerNorms' and the pair norm's (`subln`)
        rms_norm_eps=d.get("layer_norm_eps", 1e-5),
        attention_rope=False,
        tie_word_embeddings=True,
        attention_bias=True,
        attention_out_bias=True,
        sliding_window=d["sliding_window"],
        diff_attention=True,
        layer_pattern=pattern,
        ssm_heads=1,
        ssm_head_dim=expand * h,
        ssm_state=d.get("mamba_d_state", 16),
        ssm_conv_kernel=d.get("mamba_d_conv", 4),
        ssm_dt_rank=(-(-h // 16) if dt_rank == "auto" else dt_rank),
        model_type="phi4flash",
        name=name or d.get("_name_or_path", "phi4flash"),
    )


LAGUNA_LAYER_TYPES = ("full_attention", "sliding_attention")


def _laguna_fields(d: dict, name: str) -> dict:
    """poolside Laguna (`model_type` "laguna": Laguna-XS.2): GQA layers of
    two SHAPES in one model.  `layer_types` names each layer's attention,
    full or under `sliding_window`; `num_attention_heads_per_layer` its
    query heads (the KV heads and the head size are one); `rope_parameters`
    a rope for each layer type (theta, yarn or none, the share of a head it
    rotates); `mlp_layer_types` a dense SwiGLU of `intermediate_size` or
    `num_experts` SwiGLU experts of `moe_intermediate_size` under a softmax
    router, top-k renormalised and times `moe_routed_scaling_factor`, beside
    one shared expert; `gating` a sigmoid gate a head on attention's output.
    The published keys do not say the gate's form, the router's scores, how
    the shared expert joins or whether q and k are normalised: the readings
    taken are `models/laguna.py`'s docstring.  What is not implemented is
    refused by the key that asks for it."""
    def refuse(key, why):
        raise ValueError(f"laguna: {key} {d.get(key)!r} {why}")

    L = d["num_hidden_layers"]
    for key, kinds in (("layer_types", LAGUNA_LAYER_TYPES),
                       ("mlp_layer_types", ("dense", "sparse"))):
        v = d.get(key)
        if not isinstance(v, (list, tuple)) or len(v) != L or (
                set(v) - set(kinds)):
            refuse(key, f"must name {L} layers by {list(kinds)}")
    heads = d.get("num_attention_heads_per_layer")
    nkv = d["num_key_value_heads"]
    if not isinstance(heads, (list, tuple)) or len(heads) != L or any(
            not isinstance(n, int) or n <= 0 or n % nkv for n in heads):
        refuse("num_attention_heads_per_layer", f"must give {L} layers a "
               f"multiple of the {nkv} key/value heads each")
    by_type = dict(zip(d["layer_types"], heads))
    if any(by_type[t] != n for t, n in zip(d["layer_types"], heads)):
        refuse("num_attention_heads_per_layer", "must give the layers of "
               "one layer type one head count: a stack a type is built")
    if d.get("gating") is not True and d.get("gating") != "per-head":
        refuse("gating", "only the per-head sigmoid gate on attention's "
               "output (true, or 'per-head') is implemented")
    if set(d.get("gating_types") or ["per_head"]) != {"per_head"}:
        refuse("gating_types", "only per_head in every layer is implemented")
    if d.get("moe_apply_router_weight_on_input"):
        refuse("moe_apply_router_weight_on_input", "the router's weight "
               "multiplies an expert's OUTPUT here")
    if d.get("moe_router_logit_softcapping"):
        refuse("moe_router_logit_softcapping", "a cap on the router's "
               "logits is not implemented")
    if not d.get("norm_topk_prob", True):
        refuse("norm_topk_prob", "only renormalised weights are implemented")
    if d.get("decoder_sparse_step", 1) != 1:
        refuse("decoder_sparse_step", "mlp_layer_types says which layers "
               "are sparse; a step besides it is not implemented")
    if "sparse" in d["mlp_layer_types"] and not d.get("num_experts"):
        refuse("num_experts", "is needed: mlp_layer_types has sparse layers")
    for key in ("attention_bias", "mlp_bias"):
        if d.get(key):
            refuse(key, "projection biases are not implemented")
    if d.get("hidden_act", "silu") != "silu":
        refuse("hidden_act", "only silu is implemented")
    windowed = "sliding_attention" in d["layer_types"]
    if windowed and (not isinstance(d.get("sliding_window"), int)
                     or d["sliding_window"] <= 0):
        refuse("sliding_window", "must be the windowed layers' one width "
               "in tokens")
    ropes = d.get("rope_parameters")
    if not isinstance(ropes, dict) or any(
            not isinstance(ropes.get(t), dict) for t in set(d["layer_types"])):
        refuse("rope_parameters", "must hold a rope for each layer type in "
               "layer_types")
    hd = d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"]
    for t in set(d["layer_types"]):
        kind = ropes[t].get("rope_type", "default")
        if kind not in ("default", "yarn"):
            refuse("rope_parameters", f"{t}: only the default rope and "
                   "yarn are implemented")
        rotated = hd * float(ropes[t].get("partial_rotary_factor", 1.0))
        if rotated != int(rotated) or int(rotated) % 2 or not (
                0 < rotated <= hd):
            refuse("rope_parameters", f"{t}: partial_rotary_factor must "
                   f"leave an even share of the head's {hd} values")
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_hidden_layers=L,
        # the widest layer's, for what sizes by one number; the layer loop
        # reads `layer_heads`
        num_attention_heads=max(heads),
        num_key_value_heads=nkv,
        head_dim=hd,
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        sliding_window=d["sliding_window"] if windowed else None,
        layer_types=tuple(d["layer_types"]),
        layer_heads=tuple(heads),
        mlp_layer_types=tuple(d["mlp_layer_types"]),
        # hashable, as a jitted forward's static config has to be
        rope_parameters=tuple(
            (t, tuple(sorted(ropes[t].items())))
            for t in sorted(set(d["layer_types"]))),
        attention_gate=True,
        num_experts=d.get("num_experts", 0),
        num_experts_per_tok=d.get("num_experts_per_tok", 1),
        moe_intermediate_size=d.get("moe_intermediate_size"),
        moe_shared_intermediate_size=d.get("shared_expert_intermediate_size"),
        n_shared_experts=1 if d.get("shared_expert_intermediate_size") else 0,
        moe_routed_scale=float(d.get("moe_routed_scaling_factor", 1.0)),
        model_type="laguna",
        name=name or d.get("_name_or_path", "laguna"),
    )


LFM2_LAYER_TYPES = ("conv", "full_attention")


def _lfm2_moe_fields(d: dict, name: str) -> dict:
    """Liquid AI LFM2-MoE (`model_type` "lfm2_moe": LFM2-24B-A2B, LFM2-8B-A1B).
    `layer_types` names each layer's MIXER: "conv", a gated short convolution
    of `conv_L_cache` taps without bias or activation, or "full_attention",
    GQA with an RMS norm over each head of q and of k before the rope.  The
    first `num_dense_layers` layers carry a dense SwiGLU of
    `intermediate_size`, the others `num_experts` SwiGLU experts of
    `moe_intermediate_size` behind a sigmoid router whose `expert_bias`
    joins the scores for CHOOSING only (`use_expert_bias`), the chosen
    scores divided by their sum + 1e-6 (`norm_topk_prob`) and multiplied by
    `routed_scaling_factor`; no shared expert.  The final norm is named
    `embedding_norm` and the head is tied.  A conv layer keeps no keys
    (`num_kv_layers`), and what a sequence leaves it is a window alone
    (`state_spec`).  `models/laguna.py` walks the layers.  What is not
    implemented is refused by the key that asks for it."""
    def refuse(key, why):
        raise ValueError(f"lfm2_moe: {key} {d.get(key)!r} {why}")

    L = d["num_hidden_layers"]
    types = d.get("layer_types")
    if not isinstance(types, (list, tuple)) or len(types) != L or (
            set(types) - set(LFM2_LAYER_TYPES)):
        refuse("layer_types", f"must name {L} layers by "
               f"{list(LFM2_LAYER_TYPES)}")
    dense = d.get("num_dense_layers", 0)
    if not isinstance(dense, int) or not 0 <= dense <= L:
        refuse("num_dense_layers", f"must count the leading dense layers "
               f"among {L}")
    taps = d.get("conv_L_cache")
    if "conv" in types and (not isinstance(taps, int) or taps < 2):
        refuse("conv_L_cache", "must give the short convolution's taps, 2 "
               "or more: the layer list has conv layers")
    if d.get("conv_bias"):
        refuse("conv_bias", "biases on the convolution and its projections "
               "are not implemented")
    if dense < L and not d.get("num_experts"):
        refuse("num_experts", "is needed: layers past num_dense_layers are "
               "expert layers")
    if not d.get("use_expert_bias", True):
        refuse("use_expert_bias", "only the router with its choosing bias is "
               "implemented")
    if not d.get("norm_topk_prob", True):
        refuse("norm_topk_prob", "only normalised weights are implemented")
    eps = d.get("norm_eps", 1e-5)
    if d.get("rms_norm_eps", eps) != eps:
        refuse("rms_norm_eps", f"differs from norm_eps {eps!r}: one epsilon "
               "serves every norm")
    for key in ("sliding_window", "rope_scaling", "attention_bias",
                "mlp_bias"):
        if d.get(key):
            refuse(key, "is not implemented for this family")
    ropes = d.get("rope_parameters")
    if ropes is None:
        ropes = {"rope_theta": d.get("rope_theta", 1000000.0),
                 "rope_type": "default"}
    if not isinstance(ropes, dict) or ropes.get(
            "rope_type", "default") != "default":
        refuse("rope_parameters", "only the default rope (rope_theta alone) "
               "is implemented")
    if float(ropes.get("partial_rotary_factor", 1.0)) != 1.0:
        refuse("rope_parameters", "the whole head is rotated: a partial "
               "rotary factor is not implemented")
    nq = d["num_attention_heads"]
    nkv = d.get("num_key_value_heads", nq)
    if nq % nkv:
        refuse("num_key_value_heads", f"must divide the {nq} query heads")
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=nq,
        num_key_value_heads=nkv,
        head_dim=d.get("head_dim") or d["hidden_size"] // nq,
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rms_norm_eps=eps,
        rope_theta=float(ropes["rope_theta"]),
        tie_word_embeddings=d.get("tie_word_embeddings",
                                  d.get("tie_embedding", True)),
        layer_types=tuple(types),
        # a conv layer has no heads: its kind's stack holds no `wq`
        layer_heads=tuple(0 if t == "conv" else nq for t in types),
        mlp_layer_types=tuple("dense" if l < dense else "sparse"
                              for l in range(L)),
        rope_parameters=(("full_attention", (
            ("rope_theta", float(ropes["rope_theta"])),
            ("rope_type", "default"))),),
        qk_norm=True,
        short_conv_kernel=taps or 0,
        num_experts=d.get("num_experts", 0),
        num_experts_per_tok=d.get("num_experts_per_tok", 1),
        moe_intermediate_size=d.get("moe_intermediate_size"),
        moe_scoring="sigmoid",
        moe_routed_scale=float(d.get("routed_scaling_factor", 1.0)),
        moe_norm_eps=1e-6,
        model_type="lfm2_moe",
        name=name or d.get("_name_or_path", "lfm2_moe"),
    )


def _falcon_h1_fields(d: dict, name: str) -> dict:
    """TII Falcon-H1 (`model_type` "falcon_h1": Falcon-H1-34B-Instruct and its
    smaller siblings).  EVERY layer is a Mamba-2 mixer (inner width
    `mamba_d_ssm` = `mamba_n_heads` x `mamba_d_head`, not `mamba_expand` x
    hidden) and GQA attention with a whole-head rope side by side, both from
    the ONE `input_layernorm`, their sum on the residual; then a dense SwiGLU
    under `pre_ff_layernorm` (`layer_pattern` "P" a layer: `models/
    hybrid.py`).  The family's muP multipliers scale activations and are
    kept as fields (`ModelConfig.embedding_multiplier` and the lines after
    it).  Keys its code does not read are passed over (`mamba_expand`,
    `mamba_use_mlp`, `mlp_expansion_factor`, `num_logits_to_keep`); what is
    not implemented is refused by the key that asks for it."""
    def refuse(key, why):
        raise ValueError(f"falcon_h1: {key} {d.get(key)!r} {why}")

    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                "projectors_bias"):
        if d.get(key):
            refuse(key, "projection biases are not implemented")
    if not d.get("mamba_conv_bias", True):
        refuse("mamba_conv_bias", "the convolution is implemented with its "
               "bias")
    if d.get("hidden_act", "silu") != "silu":
        refuse("hidden_act", "only silu is implemented")
    if d.get("attn_layer_indices") is not None:
        refuse("attn_layer_indices", "every layer has attention beside its "
               "state-space mixer: a layer list is not implemented")
    if d.get("mamba_norm_before_gate"):
        refuse("mamba_norm_before_gate", "the gate comes first, then the "
               "grouped norm")
    if not d.get("mamba_rms_norm", True):
        refuse("mamba_rms_norm", "the gated output is implemented with its "
               "grouped rms norm")
    for key in ("rope_scaling", "sliding_window"):
        if d.get(key):
            refuse(key, "is not implemented for this family")
    nh, hp = d["mamba_n_heads"], d["mamba_d_head"]
    if d.get("mamba_d_ssm", nh * hp) != nh * hp:
        refuse("mamba_d_ssm", f"must be mamba_n_heads x mamba_d_head = "
               f"{nh * hp}: the mixer's inner width is its heads'")
    if nh % d.get("mamba_n_groups", 1):
        refuse("mamba_n_groups", f"must divide mamba_n_heads {nh}")
    nq = d["num_attention_heads"]
    nkv = d.get("num_key_value_heads", nq)
    if nq % nkv:
        refuse("num_key_value_heads", f"must divide the {nq} query heads")
    mults = {}
    for key, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
        m = d.get(key)
        if m is None:
            continue
        if not isinstance(m, (list, tuple)) or len(m) != n:
            refuse(key, f"must hold {n} factors")
        mults[key] = tuple(float(v) for v in m)
    for key in ("embedding_multiplier", "lm_head_multiplier",
                "key_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"):
        mults[key] = float(d.get(key, 1.0))
    L = d["num_hidden_layers"]
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=nq,
        num_key_value_heads=nkv,
        head_dim=d.get("head_dim") or d["hidden_size"] // nq,
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        layer_pattern="P" * L,
        ssm_heads=nh,
        ssm_head_dim=hp,
        ssm_groups=d.get("mamba_n_groups", 1),
        ssm_state=d["mamba_d_state"],
        ssm_conv_kernel=d.get("mamba_d_conv", 4),
        ssm_chunk=d.get("mamba_chunk_size", 128),
        **mults,
        model_type="falcon_h1",
        name=name or d.get("_name_or_path", "falcon_h1"),
    )


def _xing4_0_fields(d: dict, name: str) -> dict:
    """Xing4.0's architecture (`model_type` "xing4_0"): deepseek_v3's
    layers (latent attention, leading dense layers, the `noaux_tc` router
    with a shared expert) around a residual of `hc_mult` streams mixed by
    manifold-constrained hyper-connections.  Every hyper-connection key is
    needed, and what is not implemented is refused by the key that asks
    for it."""
    def refuse(key, why):
        raise ValueError(f"xing4_0: {key} {d.get(key)!r} {why}")

    fields = _deepseek_v3_fields(d, name, family="xing4_0")
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"):
        if d.get(key) is None:
            refuse(key, "is needed: this family's residual is "
                   "hyper-connections")
    if not isinstance(d["hc_mult"], int) or d["hc_mult"] < 2:
        refuse("hc_mult", "must be a whole number of streams, 2 or more")
    if not isinstance(d["hc_sinkhorn_iters"], int) or (
            d["hc_sinkhorn_iters"] < 1):
        refuse("hc_sinkhorn_iters", "must be 1 or more: the first step is "
               "the softmax's column normalisation")
    if not d["hc_eps"] > 0:
        refuse("hc_eps", "must be positive")
    lo, hi = d["mhc_h_res_clamp_min"], d["mhc_h_res_clamp_max"]
    if not lo < hi:
        refuse("mhc_h_res_clamp_min", f"must lie below "
               f"mhc_h_res_clamp_max {hi!r}")
    fields.update(hc_mult=d["hc_mult"],
                  hc_sinkhorn_iters=d["hc_sinkhorn_iters"],
                  hc_eps=float(d["hc_eps"]),
                  hc_res_clamp=(float(lo), float(hi)))
    return fields


def _deepseek_v3_fields(d: dict, name: str, family: str = "deepseek_v3"
                        ) -> dict:
    """DeepSeek-V3's architecture (config.json of deepseek_v3 checkpoints:
    DeepSeek-V3/R1, GigaChat3, Kimi-K2 ...): latent attention behind a
    query bottleneck, `first_k_dense_replace` dense layers and then expert
    layers under the `noaux_tc` grouped sigmoid router with a shared
    expert.  `n_routed_experts` counts the experts HELD by this rank
    (`ep_rank`, default 0) of `ep_size` ranks; the router is
    `n_routed_experts * ep_size` wide.  What is not implemented is refused
    by the key that asks for it."""
    def refuse(key, why):
        raise ValueError(f"{family}: {key} {d.get(key)!r} {why}")

    if not d.get("q_lora_rank"):
        refuse("q_lora_rank", "the full-rank query projection is not "
               "implemented")
    if not d.get("kv_lora_rank"):
        refuse("kv_lora_rank", "this family has latent attention")
    if d.get("topk_method", "noaux_tc") != "noaux_tc":
        refuse("topk_method", "only noaux_tc is implemented")
    if d.get("scoring_func", "sigmoid") != "sigmoid":
        refuse("scoring_func", "only sigmoid is implemented")
    if not d.get("norm_topk_prob", True):
        refuse("norm_topk_prob", "only normalised weights are implemented")
    if d.get("moe_layer_freq", 1) != 1:
        refuse("moe_layer_freq", "only an expert layer in every layer "
               "after the dense ones is implemented")
    if d.get("attention_bias"):
        refuse("attention_bias", "is not implemented for latent attention")
    if d.get("hidden_act", "silu") != "silu":
        refuse("hidden_act", "only silu is implemented")
    rs = d.get("rope_scaling")
    if rs and rs.get("rope_type", rs.get("type")) != "yarn":
        refuse("rope_scaling", "only yarn is implemented")
    L, k = d["num_hidden_layers"], d.get("first_k_dense_replace", 0)
    if not 0 <= k < L:
        refuse("first_k_dense_replace", f"must leave an expert layer "
               f"among {L}")
    return dict(
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=d["num_attention_heads"],
        # every head has its own key and value, computed from the latent
        num_key_value_heads=d["num_attention_heads"],
        head_dim=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        max_position_embeddings=d.get("max_position_embeddings", 4096),
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rope_scaling=rs,
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        kv_lora_rank=d["kv_lora_rank"],
        q_lora_rank=d["q_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"],
        v_head_dim=d["v_head_dim"],
        first_k_dense=k,
        num_experts=d["n_routed_experts"],
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        n_shared_experts=d.get("n_shared_experts", 0),
        moe_scoring="sigmoid",
        moe_n_group=d.get("n_group", 1),
        moe_topk_group=d.get("topk_group", 1),
        moe_routed_scale=float(d.get("routed_scaling_factor", 1.0)),
        moe_ep_size=d.get("ep_size", 1),
        moe_ep_rank=d.get("ep_rank", 0),
        model_type=family,
        name=name or d.get("_name_or_path", family),
    )


# -- canned configs ---------------------------------------------------------- #

def tiny_config(**over) -> ModelConfig:
    """Tiny model for tests (runs on the CPU mesh in milliseconds)."""
    base = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
        name="tiny-llama-test",
    )
    base.update(over)
    return ModelConfig(**base)


def tiny_moe_config(**over) -> ModelConfig:
    base = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
        num_experts=4,
        num_experts_per_tok=2,
        # a tiny step is far under the token count where "auto" leaves the
        # all-experts matmul: name the dispatch, so the tiny engines keep
        # running the form a served model runs above it
        moe_impl="ragged",
        name="tiny-moe-test",
    )
    base.update(over)
    return ModelConfig(**base)


LLAMA_3_2_1B = ModelConfig(
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_hidden_layers=16,
    num_attention_heads=32,
    num_key_value_heads=8,
    head_dim=64,
    max_position_embeddings=131072,
    rms_norm_eps=1e-5,
    rope_theta=500000.0,
    rope_scaling={
        "factor": 32.0,
        "high_freq_factor": 4.0,
        "low_freq_factor": 1.0,
        "original_max_position_embeddings": 8192,
        "rope_type": "llama3",
    },
    tie_word_embeddings=True,
    name="llama-3.2-1b",
)

LLAMA_3_1_8B = ModelConfig(
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    max_position_embeddings=131072,
    rms_norm_eps=1e-5,
    rope_theta=500000.0,
    rope_scaling={
        "factor": 8.0,
        "high_freq_factor": 4.0,
        "low_freq_factor": 1.0,
        "original_max_position_embeddings": 8192,
        "rope_type": "llama3",
    },
    name="llama-3.1-8b",
)

LLAMA_3_70B = ModelConfig(
    vocab_size=128256,
    hidden_size=8192,
    intermediate_size=28672,
    num_hidden_layers=80,
    num_attention_heads=64,
    num_key_value_heads=8,
    max_position_embeddings=131072,
    rms_norm_eps=1e-5,
    rope_theta=500000.0,
    name="llama-3-70b",
)

MIXTRAL_8X7B = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    max_position_embeddings=32768,
    rms_norm_eps=1e-5,
    rope_theta=1000000.0,
    num_experts=8,
    num_experts_per_tok=2,
    model_type="mixtral",
    name="mixtral-8x7b",
)

MISTRAL_7B = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    max_position_embeddings=32768,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
    sliding_window=4096,
    model_type="mistral",
    name="mistral-7b",
)

QWEN2_5_7B = ModelConfig(
    vocab_size=152064,
    hidden_size=3584,
    intermediate_size=18944,
    num_hidden_layers=28,
    num_attention_heads=28,
    num_key_value_heads=4,
    max_position_embeddings=32768,
    rms_norm_eps=1e-6,
    rope_theta=1000000.0,
    attention_bias=True,
    model_type="qwen2",
    name="qwen2.5-7b",
)

QWEN2_5_0_5B = ModelConfig(
    vocab_size=151936,
    hidden_size=896,
    intermediate_size=4864,
    num_hidden_layers=24,
    num_attention_heads=14,
    num_key_value_heads=2,
    max_position_embeddings=32768,
    rms_norm_eps=1e-6,
    rope_theta=1000000.0,
    attention_bias=True,
    tie_word_embeddings=True,
    model_type="qwen2",
    name="qwen2.5-0.5b",
)

GPT_OSS_20B = ModelConfig(
    # openai/gpt-oss-20b (HF GptOssConfig): 24-layer MoE, 32 experts
    # top-4, alternating sliding/full attention, learnable sinks,
    # biased router + clamped-GLU experts, o_proj bias
    vocab_size=201088,
    hidden_size=2880,
    intermediate_size=2880,
    num_hidden_layers=24,
    num_attention_heads=64,
    num_key_value_heads=8,
    head_dim=64,
    max_position_embeddings=131072,
    rms_norm_eps=1e-5,
    rope_theta=150000.0,
    rope_scaling={"rope_type": "yarn", "factor": 32.0,
                  "beta_fast": 32.0, "beta_slow": 1.0,
                  "original_max_position_embeddings": 4096,
                  "truncate": False},
    attention_bias=True,
    attention_out_bias=True,
    attention_sinks=True,
    sliding_window=128,
    layer_types=tuple(
        "sliding_attention" if i % 2 == 0 else "full_attention"
        for i in range(24)
    ),
    num_experts=32,
    num_experts_per_tok=4,
    moe_act="gpt_oss_glu",
    moe_bias=True,
    model_type="gpt_oss",
    name="gpt-oss-20b",
)

CONFIGS = {
    c.name: c
    for c in [LLAMA_3_2_1B, LLAMA_3_1_8B, LLAMA_3_70B, MIXTRAL_8X7B,
              MISTRAL_7B, QWEN2_5_7B, QWEN2_5_0_5B, GPT_OSS_20B]
}
