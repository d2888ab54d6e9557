"""Llama-family decoder in functional JAX with paged KV cache.

Architecture (not a torch translation):

- Params are a pytree of arrays with **per-layer weights stacked on axis 0**
  so the layer loop is a single ``lax.scan`` — one compiled layer body
  regardless of depth (80-layer 70B compiles as fast as a 2-layer test
  model).
- KV cache is the page pool from ``ops.paged_attention``, stacked per layer:
  ``k_pages/v_pages: [L, P, page, n_kv, hd]``.  The prefill-type layer
  loops (`prefill_layers`) leave it where it is: the scan runs over the
  params and a layer index, attention reads the pool by (layer, page), and
  one scatter after the loop lands every layer's new tokens in the donated
  pool (``ops.paged_attention.write_kv_layers``).  The per-step decode
  loop (`decode_layers`) still scans the pool beside the params and defers
  only the write; it moves off the scanned pool with a cell that decodes.
- All matmuls are bf16 with fp32 accumulation (``preferred_element_type``),
  sized for the MXU; no data-dependent control flow anywhere.
- MoE (Mixtral-style) uses one-hot dispatch einsums — expert-parallel
  sharding is applied externally via the specs in `param_pspecs`.

The reference delegates models to vLLM/TRT-LLM; this is the TPU-native
engine-side model (SURVEY.md §7 M1).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.export import register_namedtuple_serialization
from jax.sharding import PartitionSpec as P

from ..ops.latent_attention import (
    decode_parts,
    latent_attention,
    prefill_attention as latent_prefill_attention,
    rows_of,
)
from ..ops import hyper_connections as hc
from ..ops import pallas_hyper_connections as pallas_hc
from ..ops import pallas_moe
from ..ops import (
    apply_rope,
    decode_attention,
    layer_norm,
    prefill_attention,
    rms_norm,
    rope_attention_scale,
    rope_frequencies,
    write_kv_layers,
    write_kv_pages,
)
from ..analysis import xla_ledger
from .config import ModelConfig
from .quantization import matmul_any

Params = dict


# (registered: a step program's operand, whose tree the program store
# writes with the program's lowered module, `jax.export`)
@partial(register_namedtuple_serialization,
         serialized_name="dynamo_tpu.KVCache")
class KVCache(NamedTuple):
    """Paged pool for all layers, as `ModelConfig.cache_spec` describes it:
    keys and values [L, P, page, n_kv, hd] each, or, for latent attention,
    no per-head keys or values at all: in `k` the rotary key all heads
    share, in `v` the latent ([L, P, page, tiles, 128] each, whole lane
    tiles a token).  A model with state-space layers has a `StateCache` in
    its place (`create` decides)."""

    k: jax.Array
    v: jax.Array

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @staticmethod
    def create(
        cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
        state_slots: int = 0,
    ) -> "KVCache":
        lead = (cfg.num_kv_layers, num_pages, page_size)
        pages = (jnp.zeros((*lead, *dims), dtype)
                 for dims in cfg.cache_spec.plane_dims)
        spec = cfg.state_spec
        if spec is None:
            return KVCache(*pages)
        slots = (spec.layers, max(state_slots, 1))  # slot 0: trash
        return StateCache(
            *pages, jnp.zeros((*slots, *spec.window_dims), dtype),
            jnp.zeros((*slots, *spec.state_dims), jnp.float32)
            if spec.recurrent else None)


@partial(register_namedtuple_serialization,
         serialized_name="dynamo_tpu.StateCache")
class StateCache(NamedTuple):
    """`KVCache` of a model with state-space layers (`ModelConfig.
    state_spec`), every step's one donated operand: the pages of its
    attention layers alone in `k` and `v` (`ModelConfig.num_kv_layers`),
    and beside them the state SLOTS of its state-space layers: `conv` [Lm,
    slots, tiles, 128] the convolution's window, `ssm` [Lm, slots, heads,
    head_dim, state] the recurrent state in float32; None where the state is
    the window alone (`StateSpec.recurrent`: no second array, no leaf)."""

    k: jax.Array
    v: jax.Array
    conv: jax.Array
    ssm: Optional[jax.Array]

    num_pages = KVCache.num_pages
    page_size = KVCache.page_size


# --------------------------------------------------------------------------- #
# init / sharding
# --------------------------------------------------------------------------- #


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random init (tests / benchmarks). Real weights come from the loader."""
    h, hd = cfg.hidden_size, cfg.head_dim_
    nh, nkv, L = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.num_hidden_layers
    f = cfg.intermediate_size
    ks = iter(jax.random.split(key, 20))

    def w(k, *shape, scale=None):
        scale = scale or (1.0 / jnp.sqrt(shape[-2] if len(shape) > 1 else h))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    if cfg.layer_pattern is not None:
        from . import hybrid, phi4flash

        return (phi4flash if cfg.cross_decoder else hybrid).init_params(
            cfg, key, dtype)
    if cfg.layer_kinds is not None:
        from . import laguna

        return laguna.init_params(cfg, key, dtype)
    if cfg.is_latent or cfg.first_k_dense:
        return _init_params_stacks(cfg, key, dtype, w)
    layers = {
        "wq": w(next(ks), L, h, nh * hd),
        "wk": w(next(ks), L, h, nkv * hd),
        "wv": w(next(ks), L, h, nkv * hd),
        "wo": w(next(ks), L, nh * hd, h),
        "attn_norm": jnp.ones((L, h), dtype),
        "mlp_norm": jnp.ones((L, h), dtype),
    }
    if cfg.attention_bias:  # qwen2-style qkv bias (no o_proj bias)
        layers.update(
            {
                "bq": w(next(ks), L, nh * hd, scale=0.02),
                "bk": w(next(ks), L, nkv * hd, scale=0.02),
                "bv": w(next(ks), L, nkv * hd, scale=0.02),
            }
        )
    if cfg.attention_out_bias:  # gpt-oss biases o_proj too
        layers["bo"] = w(next(ks), L, h, scale=0.02)
    if cfg.attention_sinks:  # gpt-oss learnable per-head sink logits
        layers["sinks"] = w(next(ks), L, nh, scale=1.0)
    if cfg.qk_norm:  # weights that differ, so that a test sees them
        # (keys folded in: the draws of every other tensor stay as they were)
        for i, name in enumerate(("q_head_norm", "k_head_norm")):
            layers[name] = 1.0 + w(jax.random.fold_in(key, 100 + i), L, hd,
                                   scale=0.2)
    if cfg.is_moe:
        fm = cfg.moe_intermediate_size or f
        E = cfg.num_experts
        layers.update(
            {
                "router": w(next(ks), L, h, E),
                "w_gate": w(next(ks), L, E, h, fm),
                "w_up": w(next(ks), L, E, h, fm),
                "w_down": w(next(ks), L, E, fm, h),
            }
        )
        if cfg.moe_bias:  # gpt-oss: router + per-expert ffn biases
            layers.update(
                {
                    "router_b": w(next(ks), L, E, scale=0.02),
                    "b_gate": w(next(ks), L, E, fm, scale=0.02),
                    "b_up": w(next(ks), L, E, fm, scale=0.02),
                    "b_down": w(next(ks), L, E, h, scale=0.02),
                }
            )
    else:
        layers.update(
            {
                "w_gate": w(next(ks), L, h, f),
                "w_up": w(next(ks), L, h, f),
                "w_down": w(next(ks), L, f, h),
            }
        )
    params = {
        "embed": w(next(ks), cfg.vocab_size, h, scale=0.02),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(ks), h, cfg.vocab_size)
    return params


def _init_params_stacks(cfg: ModelConfig, key, dtype, w) -> Params:
    """`init_params` of a latent-attention model and of one with leading
    dense layers: the stack `dense_layers` ahead of the expert stack
    `layers` (`layer_stacks`)."""
    if not cfg.is_latent:
        raise ValueError("first_k_dense is implemented with latent "
                         "attention only (deepseek_v3)")
    h, nh, L = cfg.hidden_size, cfg.num_attention_heads, cfg.num_hidden_layers
    r, pe = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd, qr = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.q_lora_rank
    fm, E = cfg.moe_intermediate_size, cfg.num_experts
    ks = iter(jax.random.split(key, 60))
    streams, M = cfg.hc_mult, cfg.hc_mixer_width

    def mixer(half, *lead, width=M, scales=3):
        """One hyper-connection mixer, float32: unit-scale logits."""
        f32 = jnp.float32
        return {
            half + "_phi": jax.random.normal(
                next(ks), (*lead, streams, h, width), f32)
            * (streams * h) ** -0.5,
            half + "_scale": jnp.ones((*lead, scales), f32),
            half + "_base": jax.random.normal(next(ks), (*lead, width), f32),
        }

    def attn(n):
        mixers = ({**mixer("hc_attn", n), **mixer("hc_mlp", n)}
                  if streams else {})
        return {
            **mixers,
            "attn_norm": jnp.ones((n, h), dtype),
            "mlp_norm": jnp.ones((n, h), dtype),
            "wq_a": w(next(ks), n, h, qr),
            "q_norm": jnp.ones((n, qr), dtype),
            "wq_b": w(next(ks), n, qr, nh * (nope + pe)),
            "wkv_a": w(next(ks), n, h, r + pe),
            "kv_norm": jnp.ones((n, r), dtype),
            "w_uk": w(next(ks), n, nh, nope, r, scale=r ** -0.5),
            "w_uv": w(next(ks), n, nh, r, vd),
            "wo": w(next(ks), n, nh * vd, h),
        }

    def dense(n):
        f = cfg.intermediate_size
        return {"w_gate": w(next(ks), n, h, f), "w_up": w(next(ks), n, h, f),
                "w_down": w(next(ks), n, f, h)}

    k = cfg.first_k_dense
    params = {
        "embed": w(next(ks), cfg.vocab_size, h, scale=0.02),
        "final_norm": jnp.ones((h,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(ks), h, cfg.vocab_size)
    if streams:
        params.update(mixer("hc_head", width=streams, scales=1))
    if not cfg.is_moe:
        params["layers"] = {**attn(L), **dense(L)}
        return params
    if k:
        params["dense_layers"] = {**attn(k), **dense(k)}
    n = L - k
    layers = {
        **attn(n),
        "router": w(next(ks), n, h, cfg.router_width),
        "w_gate": w(next(ks), n, E, h, fm),
        "w_up": w(next(ks), n, E, h, fm),
        "w_down": w(next(ks), n, E, fm, h),
    }
    if cfg.moe_scoring == "sigmoid":
        layers["router_bias"] = (0.02 * jax.random.normal(
            next(ks), (n, cfg.router_width), jnp.float32))
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * fm
        layers.update({"ws_gate": w(next(ks), n, h, fs),
                       "ws_up": w(next(ks), n, h, fs),
                       "ws_down": w(next(ks), n, fs, h)})
    params["layers"] = layers
    return params


def layer_stacks(params: Params) -> Tuple[Params, ...]:
    """The model's stacks of layers, in layer order: the leading dense
    stack (`first_k_dense`) where the params carry one, then `layers`."""
    if "dense_layers" in params:
        return params["dense_layers"], params["layers"]
    return (params["layers"],)


def _scan_stacks(body, x, stacks, xs, whole: bool = False):
    """`lax.scan(body, x, (stack, *xs))` over each stack of layers in turn,
    the per-layer operands `xs` ([L, ...] each) cut to the stack's layers
    and the ys joined again.  One stack is the one scan it always was.
    With `whole` the body is `body(h, (lp, *xs), (stack, i))`: beside the
    layer's slices, the WHOLE stack the layer is of and its index there,
    for a consumer that reads the stack where it lies (`_moe`)."""
    if not isinstance(stacks, tuple):
        stacks = (stacks,)

    def scan(x, stack, xs):
        if not whole:
            return jax.lax.scan(body, x, (stack, *xs))
        n = jax.tree.leaves(stack)[0].shape[0]
        return jax.lax.scan(
            lambda h, s: body(h, s[1:], (stack, s[0])), x,
            (jnp.arange(n, dtype=jnp.int32), stack, *xs))

    if len(stacks) == 1:
        return scan(x, stacks[0], xs)
    at, outs = 0, []
    for stack in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        x, ys = scan(x, stack,
                     jax.tree.map(lambda a: a[at:at + n], tuple(xs)))
        outs.append(ys)
        at += n
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *outs)


def param_pspecs(cfg: ModelConfig, tp_axis: str = "tp", ep_axis: str = "tp") -> Params:
    """PartitionSpec tree matching `init_params` (megatron-style TP).

    Head-dim projections shard on heads; MLP shards gate/up on the ffn dim
    and down on its input; embeddings shard on vocab.  Layer-stacked arrays
    keep axis 0 (layers) replicated.
    """
    require_plain_cache(cfg, "a serving mesh")
    require_one_layer_shape(cfg, "a serving mesh")
    layers = {
        "wq": P(None, None, tp_axis),
        "wk": P(None, None, tp_axis),
        "wv": P(None, None, tp_axis),
        "wo": P(None, tp_axis, None),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    if cfg.attention_bias:  # biases shard with their projection's heads
        layers.update(
            {
                "bq": P(None, tp_axis),
                "bk": P(None, tp_axis),
                "bv": P(None, tp_axis),
            }
        )
    if cfg.attention_out_bias:  # output-dim bias: replicated over tp
        layers["bo"] = P(None, None)
    if cfg.attention_sinks:
        layers["sinks"] = P(None, tp_axis)
    if cfg.qk_norm:  # one weight a head VALUE: every shard's heads read it
        layers["q_head_norm"] = P(None, None)
        layers["k_head_norm"] = P(None, None)
    if cfg.is_moe:
        layers.update(
            {
                "router": P(None, None, None),
                "w_gate": P(None, ep_axis, None, None),
                "w_up": P(None, ep_axis, None, None),
                "w_down": P(None, ep_axis, None, None),
            }
        )
        if cfg.moe_bias:  # biases shard on the expert dim like weights
            layers.update(
                {
                    "router_b": P(None, None),
                    "b_gate": P(None, ep_axis, None),
                    "b_up": P(None, ep_axis, None),
                    "b_down": P(None, ep_axis, None),
                }
            )
    else:
        layers.update(
            {
                "w_gate": P(None, None, tp_axis),
                "w_up": P(None, None, tp_axis),
                "w_down": P(None, tp_axis, None),
            }
        )
    specs = {
        "embed": P(tp_axis, None),
        "final_norm": P(None),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, tp_axis)
    return specs


def kv_cache_pspec(tp_axis: str = "tp", pool_axes=None) -> KVCache:
    """KV pages shard on kv-heads (axis 3) under TP; with `pool_axes`
    (e.g. ("dp", "sp")) the PAGE axis additionally shards across those
    mesh axes — the partitioned pool layout (engine kv_partition)."""
    spec = P(None, pool_axes, None, tp_axis, None)
    return KVCache(spec, spec)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _proj(x: jax.Array, lp: Params, wkey: str, bkey: str,
          eq: str = "bsh,hd->bsd") -> jax.Array:
    """QKV projection with the optional qwen2-style additive bias."""
    y = matmul_any(x, lp[wkey], eq)
    if bkey in lp:
        y = y + lp[bkey]
    return y


def _qkv_proj(attn_in, lp: Params, cfg: ModelConfig, eq: str):
    """(q, k, v) projections — one fused [h, (nh+2*nkv)*hd] matmul when
    the params carry `wqkv` (fuse_projections): at small hidden sizes /
    batch the per-kernel overhead of three separate weight reads leaves
    HBM bandwidth idle; one larger read keeps the decode hot loop
    bandwidth-bound (measured ~250 GB/s → higher on 1B @ batch 8)."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim_)
    if "wqkv" in lp:
        y = matmul_any(attn_in, lp["wqkv"], eq)
        if "bqkv" in lp:
            y = y + lp["bqkv"]
        return (y[..., : nh * hd], y[..., nh * hd: (nh + nkv) * hd],
                y[..., (nh + nkv) * hd:])
    return (_proj(attn_in, lp, "wq", "bq", eq),
            _proj(attn_in, lp, "wk", "bk", eq),
            _proj(attn_in, lp, "wv", "bv", eq))


def _qk_norm(lp: Params, q: jax.Array, k: jax.Array, cfg: ModelConfig):
    """The RMS norm over each head of q and of k [..., heads, head_dim]
    (`ModelConfig.qk_norm`: a weight a head value, shared by the heads),
    AFTER the projections and BEFORE the rope; a layer without the two
    weights passes both as they are."""
    if "q_head_norm" not in lp:
        return q, k
    with jax.named_scope("attn.qk_norm"):
        return (rms_norm(q, lp["q_head_norm"], cfg.rms_norm_eps),
                rms_norm(k, lp["k_head_norm"], cfg.rms_norm_eps))


def _mlp(lp: Params, x: jax.Array, mults=None) -> jax.Array:
    """The dense SwiGLU; `mults` (`ModelConfig.mlp_multipliers`) scales the
    gate's product before the activation and the down projection's, both in
    float32."""
    if "w_gateup" in lp:  # fused gate‖up read (see _qkv_proj)
        y = matmul_any(x, lp["w_gateup"], "bsh,hf->bsf")
        f = y.shape[-1] // 2
        gate, up = y[..., :f], y[..., f:]
    else:
        gate = matmul_any(x, lp["w_gate"], "bsh,hf->bsf")
        up = matmul_any(x, lp["w_up"], "bsh,hf->bsf")
    if mults is not None:
        gate = gate * mults[0]
    act = jax.nn.silu(gate) * up
    out = matmul_any(act.astype(x.dtype), lp["w_down"], "bsf,fh->bsh")
    if mults is not None:
        out = out * mults[1]
    return out.astype(x.dtype)


def fuse_projections(params: Params) -> Params:
    """Concatenate each layer's q/k/v (and dense gate/up) weights along
    their OUTPUT axis into `wqkv` / `w_gateup` — numerically identical
    (per-output-channel int8 scales concatenate with their columns), but
    the decode hot loop reads 4 larger weights per layer instead of 7
    small ones.  MoE expert stacks keep their layout (the ragged/a2a
    dispatches address w_gate/w_up separately)."""
    from .quantization import is_quantized

    def cat(ws):
        if is_quantized(ws[0]):
            return {"q": jnp.concatenate([w["q"] for w in ws], axis=-1),
                    "s": jnp.concatenate([w["s"] for w in ws], axis=-1)}
        return jnp.concatenate(ws, axis=-1)

    layers = dict(params["layers"])
    layers["wqkv"] = cat([layers.pop("wq"), layers.pop("wk"),
                          layers.pop("wv")])
    if "bq" in layers:
        layers["bqkv"] = jnp.concatenate(
            [layers.pop("bq"), layers.pop("bk"), layers.pop("bv")], axis=-1
        )
    gate = layers.get("w_gate")
    dense_ndim = 3  # [L, h, f]; MoE stacks are [L, E, h, f]
    gndim = gate["q"].ndim if is_quantized(gate) else gate.ndim
    if gndim == dense_ndim:
        layers["w_gateup"] = cat([layers.pop("w_gate"),
                                  layers.pop("w_up")])
    return {**params, "layers": layers}


def moe_act(cfg: ModelConfig, gate: jax.Array, up: jax.Array) -> jax.Array:
    """Expert gating nonlinearity (float32 in/out).  "silu" is the
    mixtral family; "gpt_oss_glu" is HF GptOssExperts: gate clamped to
    <= 7, up to |7|, glu = gate*sigmoid(1.702*gate), out = (up+1)*glu;
    "relu_glu" is SmallThinker's sparse ReGLU, relu(gate) * up; "relu2" is
    nemotron_h's UNGATED expert, relu(up)^2: `gate` is None, the params hold
    no gate matrix and the expert bodies compute none (`_gated`)."""
    if cfg.moe_act == "relu2":
        return jnp.square(jax.nn.relu(up))
    if cfg.moe_act == "gpt_oss_glu":
        limit = 7.0
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
        return (up + 1.0) * (gate * jax.nn.sigmoid(1.702 * gate))
    if cfg.moe_act == "relu_glu":
        return jax.nn.relu(gate) * up
    if cfg.moe_act == "silu":
        return jax.nn.silu(gate) * up
    raise ValueError(f"unknown moe_act {cfg.moe_act!r}")


def _gated(cfg: ModelConfig) -> bool:
    """Does an expert multiply by a gate matrix beside `w_up`?"""
    return cfg.moe_act != "relu2"


def moe_router_logits(lp: Params, x: jax.Array, eq: str) -> jax.Array:
    out = jnp.einsum(eq, x, lp["router"],
                     preferred_element_type=jnp.float32)
    if "router_b" in lp:
        out = out + lp["router_b"]
    return out


def _route(lp: Params, rx: jax.Array, cfg: ModelConfig):
    """(weights, selected), each [..., k]: top-k on the router's float32
    logits, softmax over the chosen (equal to a softmax over all, the chosen
    renormalised), times `moe_routed_scale`.  `rx` [..., h] is what the router
    reads: the experts' input, or the layer's input for a family whose
    router sits before attention (`cfg.moe_router_pre_attn`)."""
    with jax.named_scope("moe.router"):
        logits = moe_router_logits(lp, rx, "...h,he->...e")
        if cfg.moe_scoring == "sigmoid":
            return _route_grouped_sigmoid(lp, logits, cfg)
        weights, selected = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        weights = jax.nn.softmax(weights, axis=-1)
        if cfg.moe_routed_scale != 1.0:  # laguna
            weights = weights * cfg.moe_routed_scale
        return weights, selected


def _route_grouped_sigmoid(lp: Params, logits: jax.Array, cfg: ModelConfig):
    """deepseek_v3's `noaux_tc` choice over the router's float32 logits
    [..., W]: scores sigmoid(logits); the bias joins them for CHOOSING
    only; a group's score is the sum of its two largest biased scores and
    only the `moe_topk_group` best groups stay; the k largest biased scores
    among those are chosen; their weights are the UNBIASED scores,
    normalised over the chosen and scaled by `moe_routed_scale`."""
    W, G = cfg.router_width, cfg.moe_n_group
    scores = jax.nn.sigmoid(logits)
    biased = scores + lp["router_bias"]
    grouped = biased.reshape(*biased.shape[:-1], G, W // G)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)  # [..., G]
    _, best = jax.lax.top_k(group_score, cfg.moe_topk_group)
    keep = jax.nn.one_hot(best, G, dtype=jnp.bool_).any(-2)  # [..., G]
    masked = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
        biased.shape)
    _, selected = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(scores, selected, axis=-1)
    total = chosen.sum(-1, keepdims=True)
    if cfg.moe_norm_eps:  # lfm2_moe divides by the sum + 1e-6
        total = total + cfg.moe_norm_eps
    weights = chosen / total * cfg.moe_routed_scale
    return weights, selected


def _held(selected: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Chosen experts' ids among the `num_experts` HELD here; an expert on
    another rank of the layer's share falls outside [0, num_experts)."""
    return selected - cfg.first_expert if cfg.first_expert else selected


@jax.named_scope("moe.shared")
def _moe_shared(lp: Params, x: jax.Array) -> jax.Array:
    """The shared expert every token passes (one SwiGLU, or without a gate
    matrix relu(up)^2), computed on every rank of the layer's share alike."""
    if "ws_gate" in lp:
        gate = matmul_any(x, lp["ws_gate"], "bsh,hf->bsf")
        up = matmul_any(x, lp["ws_up"], "bsh,hf->bsf")
        act = jax.nn.silu(gate) * up
    else:
        act = jnp.square(jax.nn.relu(
            matmul_any(x, lp["ws_up"], "bsh,hf->bsf")))
    return matmul_any(act.astype(x.dtype), lp["ws_down"],
                      "bsf,fh->bsh").astype(x.dtype)


# The all-experts products name the tokens first, the expert stacks second,
# up to this many experts held: the order every family before laguna was
# traced and timed with.  Past it the stacks come first.  The same sums; but
# with the tokens first and 256 experts of [2048, 512] the TPU compiler wants
# the stacks of `w_gate` and `w_up` in another layout and re-lays out the
# WHOLE layer-stacked arrays ahead of the loop, 2 x 2.5 GB that no 16 GB chip
# has beside the weights (AOT for a v5e, PR 52: every chunk but 64 tokens
# refused, 17.5-17.9 GB); with the stacks first it reads them where they are.
_TOKENS_FIRST_MAX_EXPERTS = 64


def _moe_dense(lp: Params, x: jax.Array, cfg: ModelConfig,
               router_x: Optional[jax.Array] = None, routed=None) -> jax.Array:
    """Every expert computes every token, one-hot combine: O(E) compute and
    no dispatch.  The equality oracle for the dispatched path, and the form
    `moe_impl="auto"` runs for a step of few tokens (see `_moe`)."""
    B, S, h = x.shape
    E = cfg.num_experts
    weights, selected = routed or _route(
        lp, x if router_x is None else router_x, cfg)  # [B,S,k]
    with jax.named_scope("moe.dispatch"):
        # an expert held elsewhere is out of range: an all-zero row
        onehot = jax.nn.one_hot(_held(selected, cfg), E,
                                dtype=x.dtype)  # [B,S,k,E]
        combine = jnp.einsum("bsk,bske->bse", weights.astype(x.dtype),
                             onehot)  # [B,S,E]
    with jax.named_scope("moe.experts"):
        def into(w):  # x [B, S, h] into every expert's [h, f] -> [E, B, S, f]
            if E > _TOKENS_FIRST_MAX_EXPERTS:
                return jnp.einsum("ehf,bsh->ebsf", w, x,
                                  preferred_element_type=jnp.float32)
            return jnp.einsum("bsh,ehf->ebsf", x, w,
                              preferred_element_type=jnp.float32)

        gate = into(lp["w_gate"]) if _gated(cfg) else None
        up = into(lp["w_up"])
        if "b_gate" in lp:
            gate = gate + lp["b_gate"][:, None, None, :]
            up = up + lp["b_up"][:, None, None, :]
        act = moe_act(cfg, gate, up).astype(x.dtype)
        out = jnp.einsum("ebsf,efh->ebsh", act, lp["w_down"], preferred_element_type=jnp.float32)
        if "b_down" in lp:
            out = out + lp["b_down"][:, None, None, :]
    with jax.named_scope("moe.combine"):
        return jnp.einsum("ebsh,bse->bsh", out.astype(x.dtype), combine)


_EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
_EXPERT_BIASES = ("b_gate", "b_up", "b_down")


def _moe_ragged(lp: Params, x: jax.Array, cfg: ModelConfig,
                router_x: Optional[jax.Array] = None, routed=None,
                stacks=None) -> jax.Array:
    """Dropless top-k MoE over rows sorted by expert (the MaxText/Megablocks
    "sparse matmul" pattern).

    Assignments are sorted by expert; each expert computes a ragged row
    group of its tokens, so compute is exactly O(T*k) FFN rows, no token
    is ever dropped, and every token's result is independent of what else
    is in the batch — the determinism the serving engine's disagg /
    migration / prefix-cache guarantees rely on.

    The row groups are multiplied by ONE grouped kernel on a single-device
    TPU program (`ops.pallas_moe`, which reads the layer's experts out of
    `stacks` = (the layer-stacked params, the layer's index) where they lie;
    without `stacks` the layer's own matrices are a stack of one), and by
    `jax.lax.ragged_dot` elsewhere: the CPU, and a program that is
    partitioned over a mesh, which a Pallas call is not
    (`pallas_moe.lowering`).  The same arithmetic either way.

    Where the step's [T, h] activations fit VMEM beside the kernel's weight
    blocks (`pallas_moe.rows_inside`) the rows move INSIDE the kernel: it is
    handed `xf` and the sorted order, gathers a tile's rows itself, applies
    the routing weights and returns every token's float32 sum, and no [A, h]
    array is gathered, multiplied or scattered by XLA.  Elsewhere (and
    around `ragged_dot` always) XLA gathers `xf[token_of]` and scatter-adds
    the weighted rows back.  The trace notes which (`moe_rows`)."""
    B, S, h = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    A = T * k

    xf = x.reshape(T, h)
    weights, selected = routed or _route(
        lp, x if router_x is None else router_x, cfg)
    weights, selected = weights.reshape(T, k), selected.reshape(T, k)

    share = cfg.moe_ep_size > 1
    kernel = pallas_moe.lowering(lp["w_up"], _gated(cfg))
    inside = kernel is not None and pallas_moe.rows_inside(
        T, h, x.dtype.itemsize)
    xla_ledger.note_path_choice(
        "moe_rows", "kernel" if inside else "xla",
        "no kernel in this trace" if kernel is None else
        f"[{T}, {h}] in VMEM: {inside}", tokens=T)
    with jax.named_scope("moe.dispatch"):
        expert_of = _held(selected, cfg).reshape(A)  # assignment → expert
        if share:
            # assignments to experts held elsewhere sort behind every
            # group: rows no group covers
            expert_of = jnp.where((expert_of >= 0) & (expert_of < E),
                                  expert_of, E)
        order = jnp.argsort(expert_of, stable=True)  # group by expert
        token_of = order // k  # assignment a (row-major [T, k]) is token a // k
        group_sizes = (expert_of[:, None] == jnp.arange(E)).sum(
            0, dtype=jnp.int32)
        wf = weights.reshape(A)[order].astype(jnp.float32)
        if not inside:
            xs = xf[token_of]  # [A, h] rows sorted by expert
            expert_sorted = expert_of[order]  # bias rows per sorted row

    with jax.named_scope("moe.experts"):
        if kernel is not None:
            stack, at = stacks or ({n: lp[n][None] for n in (
                *_EXPERT_MATRICES, *_EXPERT_BIASES) if n in lp}, 0)
            ys = pallas_moe.grouped_experts(
                xf if inside else xs, group_sizes, at,
                stack["w_gate"] if _gated(cfg) else None, stack["w_up"],
                stack["w_down"],
                tuple(stack[n] for n in _EXPERT_BIASES
                      ) if "b_gate" in lp else None,
                act=partial(moe_act, cfg), interpret=kernel,
                rows=(token_of, wf) if inside else None)
        else:
            ys = _ragged_experts(lp, xs, group_sizes, expert_sorted, cfg)
        if share and not inside:  # rows of experts held elsewhere
            ys = jnp.where((expert_sorted < E)[:, None], ys, 0.0)

    with jax.named_scope("moe.combine"):
        if inside:  # the kernel's sums, a token each
            return ys.reshape(B, S, h).astype(x.dtype)
        out = jnp.zeros((T, h), jnp.float32).at[token_of].add(
            ys * wf[:, None])
        return out.reshape(B, S, h).astype(x.dtype)


def _ragged_experts(lp: Params, xs: jax.Array, group_sizes: jax.Array,
                    expert_sorted: jax.Array, cfg: ModelConfig) -> jax.Array:
    """xs [A, h] sorted by expert -> float32 [A, h], each row through its
    expert: three `ragged_dot`s over the layer's own matrices."""
    gate = jax.lax.ragged_dot(
        xs, lp["w_gate"], group_sizes,
        preferred_element_type=jnp.float32,
    ) if _gated(cfg) else None
    up = jax.lax.ragged_dot(
        xs, lp["w_up"], group_sizes,
        preferred_element_type=jnp.float32,
    )
    if "b_gate" in lp:
        gate = gate + lp["b_gate"][expert_sorted]
        up = up + lp["b_up"][expert_sorted]
    act = moe_act(cfg, gate, up).astype(xs.dtype)
    ys = jax.lax.ragged_dot(
        act, lp["w_down"], group_sizes,
        preferred_element_type=jnp.float32,
    )  # [A, h]
    if "b_down" in lp:
        ys = ys + lp["b_down"][expert_sorted]
    return ys


def moe_stats_width(cfg: ModelConfig) -> int:
    """int32 columns a prefill-path step's stats carry: `moe_step_stats`'s
    three, a fourth under a chip's share of the layer (the assignments that
    chose an expert held here), and LAST, under hyper-connections, how far
    the step's mixing matrices are from doubly stochastic (`_hc_err_ppm`)."""
    return 3 + (cfg.moe_ep_size > 1) + (cfg.hc_mult > 0)


def moe_stats_columns(cfg: ModelConfig) -> int:
    """Columns of `moe_step_stats` itself among `moe_stats_width`'s."""
    return 4 if cfg.moe_ep_size > 1 else 3


def moe_step_stats(selected: jax.Array, num_experts: int,
                   valid: Optional[jax.Array] = None,
                   width: Optional[int] = None) -> jax.Array:
    """int32 [3] of one expert layer over one step's tokens: assignments
    (token, expert pairs), distinct experts touched, and the largest
    per-expert row count.  `selected` [..., k]: ids among the experts HELD
    (`_held`); `valid` [...] masks padded rows out (a bucket's padding all
    routes alike and would read as one hot expert).  With `width` 4 (a
    share of the layer: ids out of range are experts held elsewhere) the
    assignments count every choice and a fourth column those that chose a
    held expert."""
    onehot = jax.nn.one_hot(selected, num_experts, dtype=jnp.int32)
    ok = None if valid is None else valid[..., None, None].astype(jnp.int32)
    if ok is not None:
        onehot = onehot * ok
    counts = onehot.reshape(-1, num_experts).sum(0)
    stats = [counts.sum(), (counts > 0).sum(), counts.max()]
    if width == 4:
        every = jnp.ones(selected.shape + (1,), jnp.int32)
        stats = [(every if ok is None else every * ok).sum(), *stats[1:],
                 counts.sum()]
    return jnp.stack(stats)


def merge_moe_stats(per_layer: jax.Array, hc_err: bool = False) -> jax.Array:
    """[..., W] stats of several layers (or steps) -> [W]: assignments and
    experts touched add up, the largest load is the largest anywhere, and so
    is the trailing hyper-connection column (`hc_err`: the stats have one)."""
    flat = per_layer.reshape(-1, per_layer.shape[-1])
    largest = {2, flat.shape[1] - 1} if hc_err else {2}
    return jnp.stack([flat[:, i].max() if i in largest else flat[:, i].sum()
                      for i in range(flat.shape[1])])


# `moe_impl="auto"`: which of the two dropless forms one step's expert layers
# run, from what the trace can see: the experts HELD, the experts a token
# uses and the step's tokens (batch x chunk).  `all_experts_form` is the
# rule, `moe_form` its name on the step events.
#
# Both forms read every expert a token chose, and under a balanced router a
# step of 64 tokens or more chooses them all: the same bytes.  What differs
# is the work.  The all-experts matmul multiplies every held expert by every
# token, held / top-k times what was routed, and past a few hundred tokens
# those operations, not the weights' read, are its time; the dispatched form
# multiplies what was routed and pays a sort by expert; its rows are
# gathered, weighted and summed inside the grouped kernel (PR 56: 0.04 ms a
# layer at 512 tokens of 6 choices beside a kernel of 1.23, where XLA's
# gather and scatter around it were 0.26: PERF.md, finding 36).  And a step
# of FEW tokens cannot touch many experts at all: 16 tokens of 8 choices
# reach at most half of 256, and the dispatched form reads only those.
#
# `_TIMED`: (experts held, experts a token) -> (few, most): the all-experts
# form runs a step of MORE than `few` tokens and at most `most`.  Set from
# whole `prefill_step` programs on a v5e (PERF.md, PR 56, finding 36:
# `scripts/time_prefill_steps.py --impls dense,ragged --routing balanced`, and
# `balanced,piled` at four rows of 64; batch 1 under a full table, median of
# 15), each shape timed under a balanced router that touches every expert of
# the router's width alike: a cell's one-draw checkpoint piles a chunk onto
# few experts, and a form that reads only touched experts is flattered by it.
# ms, all-experts / dispatched, BALANCED, at 16, 64, 128, 256, 512 tokens and
# at four rows of 64 (PILED there in brackets):
#   SmallThinker (64 experts of 2560 x 768, top 6; 12 layers)
#     16.3 / 16.8   16.8 / 17.3   17.0 / 17.8   19.5 / 19.3   35.8 / 23.4   20.6 / 20.4  (20.7 / 12.7)
#   Laguna-XS.2 (256 of 2048 x 512, top 8; 1 dense + 6 expert layers, 8,192-
#   token tables)
#     17.2 / 11.1   17.6 / 17.7   18.2 / 18.1   23.1 / 19.1   37.0 / 21.7   22.8 / 18.8  (22.8 / 11.0)
#   Xing4.0 (64 of 3584 x 1024, top 4; 2 dense + 6 expert layers, latent)
#     17.5 / 16.8   18.9 / 18.1   20.2 / 20.1   25.7 / 23.1   45.4 / 30.8   25.2 / 22.5  (25.3 / 16.0)
#   GigaChat3.1's share (16 held of 256, 7168 x 2048, top 8; 1 dense + 6)
#     19.6 / 19.9   21.5 / 21.7   23.3 / 23.8   30.0 / 28.3   56.6 / 39.4   29.2 / 27.4  (29.4 / 29.9)
#   Nemotron-3-Nano's share (16 held of 128, 2688 x 1856 without a gate
#   matrix, top 6; 23 expert layers of 52; the kernel takes its stacks as
#   they are stored, `pallas_moe.f_major`)
#     18.1 / 18.4   21.5 / 19.6   21.3 / 19.3   23.8 / 24.3   41.5 / 29.8   29.4 / 29.6  (29.5 / 31.1)
#   LFM2-24B-A2B (64 of 2048 x 1536, top 4; 1 dense + 8 expert layers under
#   8,192-token tables; the all-experts column PR 56's, the dispatched one
#   PR 55's, median of 9, from before the rows moved inside; four rows of 64
#   PR 56's)
#     16.0 / 16.0   16.4 / 16.5   refused / 16.9     -     48.9 / 19.3   19.2 / 17.6  (19.1 / 9.9)
#   the same pair as Xing4.0's and SPLIT from it by its widths: at 128 tokens
#   and more the compiler copies the all-experts form's two [6, 64, 2048,
#   1536] stacks into another layout every step (2.25 GB each in the AOT
#   compile: the program does not fit beside the cell's pool), at 16 and 64
#   the balanced column is a tie within 0.2 ms and the checkpoint's router
#   gives the dispatched form 4.4 ms at 16 (PR 55)
# What PR 56 moved: SmallThinker's and GigaChat's 256-token steps, one row or
# four of 64, are the dispatched form's (by 0.2 and 1.7-1.8 ms; they were the
# all-experts form's by 1.0-3.1 while XLA moved the rows), and Nemotron's
# share dispatches its steps of up to 128 tokens (by 1.9-2.0 ms at 64 and
# 128; 0.3 behind at 16) and keeps the all-experts form at 256 alone (by 0.5
# ms one row, 0.2 four rows, 1.5 under the piled draw): a share's rows of
# experts held elsewhere, 7 of 8, are no longer sorted into tiles, fetched or
# scattered.  What it did NOT move though the balanced column would: Xing's
# steps of 16 and 64 tokens are the dispatched form's by 0.7-0.8 ms (128 is
# nobody's), and its bound stays at 128, because a step of so few tokens is
# also a DECODE step's, and the llama family's decode block hands no stack (a
# dispatched step there copies the layer's expert slice: ROADMAP S7r (f));
# Laguna, LFM2 and Nemotron decode through loops that hand theirs.  Under the
# piled router of a cell's checkpoint the dispatched form wins every shape
# timed of the whole-expert pairs by a third to a half.  (The dispatched
# column was timed with PR 56's first build, which placed a tile's rows at
# once; the build that stands places a visit's, and one layer of it is equal
# or 0.01-0.06 ms faster at every shape: PERF.md 7 (bm).)
_TIMED = {
    (64, 6): (0, 128),
    (256, 8): (16, 128),
    (64, 4): (0, 128),
    (16, 8): (0, 128),
    (16, 6): (128, 256),
}
# A pair timed AGAIN at other widths, which read otherwise: (experts held,
# experts a token, hidden, expert width) -> (few, most), before `_TIMED`'s
# bounds for the pair.  Xing4.0's pair at LFM2-24B-A2B's widths (1 dense + 8
# expert layers, PR 55): dispatched at EVERY size
_TIMED_AT = {
    (64, 4, 2048, 1536): (0, 0),
}
# A pair nobody timed keeps the bounds of the forms before the kernel (PR 31
# and PR 52): all-experts up to 1,024 tokens (Mixtral-8x7B 8 of 4096 x 14336
# top 2 and gpt-oss-20b 32 biased of 2880 x 2880 top 4, whose hidden size is
# no whole number of lanes and whose dispatched form stays `ragged_dot`,
# crossed between 1,024 and 2,048) and no more than 131,072 rows of [experts
# held x tokens], whose float32 products [E, tokens, hidden] are written and
# read whole (2 GB at 256 experts and 1,024 tokens).
_ALL_EXPERTS_MAX_TOKENS = 1024
_ALL_EXPERTS_MAX_ROWS = 131072


def all_experts_form(held: int, top_k: int, tokens: int,
                     widths: Optional[tuple] = None) -> bool:
    """Does a step of `tokens` tokens run the all-experts matmul over its
    `held` experts, `top_k` of them a token's own?  `widths`: the experts'
    (hidden, width), for a pair that was timed at more than one."""
    timed = _TIMED_AT.get((held, top_k, *widths)) if widths else None
    if timed is None:
        timed = _TIMED.get((held, top_k))
    if timed is not None:
        few, most = timed
        return few < tokens <= most
    return (tokens <= _ALL_EXPERTS_MAX_TOKENS
            and tokens * held <= _ALL_EXPERTS_MAX_ROWS)


def moe_form(cfg: ModelConfig, tokens: int) -> str:
    """The form `_moe` runs for a step of `tokens` tokens, as the step events
    and `/metrics.json` name it: "all_experts", "dispatched" (the rows sorted
    by expert, `_moe_ragged`: "ragged", "a2a" outside a shard_map, and "auto"
    past the rule) or "capacity"."""
    if cfg.moe_impl == "capacity":
        return "capacity"
    if cfg.moe_impl == "dense" or (cfg.moe_impl == "auto" and all_experts_form(
            cfg.num_experts, cfg.num_experts_per_tok, tokens,
            (cfg.hidden_size, cfg.moe_intermediate_size))):
        return "all_experts"
    return "dispatched"


def moe_rows(tokens: int) -> Optional[str]:
    """Where the rows of a dispatched step of `tokens` tokens moved, as its
    trace noted (`_moe_ragged`): "kernel" (inside the grouped kernel) or
    "xla" (gathered and scattered around it, or around `ragged_dot`); None
    where no such step was traced."""
    return xla_ledger.path_choice("moe_rows", tokens=tokens)


def _moe(lp: Params, x: jax.Array, cfg: ModelConfig,
         router_x: Optional[jax.Array] = None,
         valid: Optional[jax.Array] = None, stats: bool = False,
         stacks=None):
    """The expert layer over x [B, S, h].  `router_x` is what the router
    reads when that is not `x` (the layer's input: `moe_router_pre_attn`).
    With `stats`, returns (out, `moe_step_stats` over the rows `valid`
    marks).  `stacks`, from a layer loop: (the layer-stacked params `lp` is
    a layer of, the layer's index there), for the dispatched form to read
    the expert matrices in place (`_moe_ragged`); the all-experts form reads
    `lp`'s slices, which its products fuse.

    `moe_impl` "auto" is dropless, in the form the shapes of this trace
    select (`all_experts_form`); "dense", "ragged" and "capacity" run the
    form they name."""
    impl = cfg.moe_impl
    if impl == "capacity":
        if stats:
            raise ValueError("moe stats are not carried by moe_impl="
                             "'capacity' (it routes per group)")
        return _moe_capacity(lp, x, cfg, router_x=router_x)
    if impl not in ("auto", "ragged", "a2a", "dense"):
        raise ValueError(
            f"moe_impl must be auto|ragged|a2a|capacity|dense, got {impl!r}")
    routed = _route(lp, x if router_x is None else router_x, cfg)
    if moe_form(cfg, x.shape[0] * x.shape[1]) == "all_experts":
        out = _moe_dense(lp, x, cfg, routed=routed)
    else:
        # "a2a" (the wide-EP all-to-all, parallel/wide_ep.py) only exists
        # inside an explicit expert-sharded shard_map; outside one the
        # dropless ragged dispatch is the same math on one shard
        out = _moe_ragged(lp, x, cfg, routed=routed, stacks=stacks)
    if "ws_up" in lp:
        out = out + _moe_shared(lp, x)
    if not stats:
        return out
    return out, moe_step_stats(_held(routed[1], cfg), cfg.num_experts, valid,
                               moe_stats_columns(cfg))


def _moe_capacity(lp: Params, x: jax.Array, cfg: ModelConfig,
                  router_x: Optional[jax.Array] = None) -> jax.Array:
    """Top-k MoE via capacity-bounded expert dispatch (the GShard/Switch
    pattern — the TPU-native expert-parallel form).

    Tokens scatter into per-expert buffers ``[E, C, h]`` (C = capacity);
    each expert runs its FFN on its buffer only, so compute scales with
    ``k * capacity_factor``, not ``E`` (the reference reaches wide-EP via
    SGLang ``--ep-size``/DeepEP, SURVEY.md §2.6).  Under GSPMD with
    ``w_*`` sharded on E over the ep axis and tokens sharded over dp, XLA
    lowers the dispatch/combine einsums to the expert all-to-all over ICI.
    Tokens past an expert's capacity are dropped (standard GShard
    behavior) — their residual stream passes through unchanged.
    """
    B, S, h = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    cap_f = cfg.moe_capacity_factor
    if not _gated(cfg):
        raise ValueError("moe_impl='capacity' multiplies by a gate matrix: "
                         f"not implemented for moe_act {cfg.moe_act!r}")
    if cap_f <= 0:  # dense fallback (tests / tiny models)
        return _moe_dense(lp, x, cfg, router_x=router_x)

    # group tokens so the one-hot dispatch stays O(T*G) not O(T^2):
    # each group of G tokens gets its own capacity slice per expert
    G = min(T, cfg.moe_group_size)
    Tp = -(-T // G) * G
    n_g = Tp // G
    C = max(1, int(-(-G * k * cap_f // E)))

    xf = x.reshape(T, h)
    if Tp != T:
        xf = jnp.pad(xf, ((0, Tp - T), (0, 0)))
    xg = xf.reshape(n_g, G, h)
    rg = xg
    if router_x is not None:
        rf = router_x.reshape(T, h)
        if Tp != T:
            rf = jnp.pad(rf, ((0, Tp - T), (0, 0)))
        rg = rf.reshape(n_g, G, h)
    weights, selected = _route(lp, rg, cfg)  # [n_g, G, k]

    # position of each (token, slot) assignment within its expert's buffer
    oh = jax.nn.one_hot(selected, E, dtype=jnp.int32)  # [n_g, G, k, E]
    ohf = oh.reshape(n_g, G * k, E)
    pos = jnp.cumsum(ohf, axis=1) - ohf  # prior assignments per expert
    pos = (pos * ohf).sum(-1)  # [n_g, G*k]
    keep = (pos < C).astype(x.dtype)

    # dispatch/combine tensor [n_g, G*k, E, C] (one-hot in E and C)
    disp = (
        ohf.astype(x.dtype)[..., None]
        * jax.nn.one_hot(jnp.clip(pos, 0, C - 1), C, dtype=x.dtype)[..., None, :]
        * keep[..., None, None]
    )
    xrep = jnp.repeat(xg, k, axis=1)  # [n_g, G*k, h] (slot-adjacent order)
    xe = jnp.einsum(
        "gaec,gah->gech", disp, xrep, preferred_element_type=jnp.float32
    ).astype(x.dtype)  # [n_g, E, C, h]

    gate = jnp.einsum("gech,ehf->gecf", xe, lp["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.einsum("gech,ehf->gecf", xe, lp["w_up"], preferred_element_type=jnp.float32)
    if "b_gate" in lp:
        gate = gate + lp["b_gate"][None, :, None, :]
        up = up + lp["b_up"][None, :, None, :]
    act = moe_act(cfg, gate, up).astype(x.dtype)
    ye = jnp.einsum("gecf,efh->gech", act, lp["w_down"], preferred_element_type=jnp.float32)
    if "b_down" in lp:
        ye = ye + lp["b_down"][None, :, None, :]

    wf = weights.astype(x.dtype).reshape(n_g, G * k)
    out = jnp.einsum(
        "gaec,gech->gah", disp * wf[..., None, None], ye.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )  # [n_g, G*k, h] — one row per (token, slot) assignment
    out = out.reshape(n_g, G, k, h).sum(axis=2).reshape(Tp, h)[:T]
    return out.reshape(B, S, h).astype(x.dtype)


def _latent_qkv(lp: Params, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, inv_freq: jax.Array, rope_scale: float):
    """Latent attention's projections of x [B, S, h] (deepseek_v3).
    Returns (q_abs [B, S, nh, rank]: each head's query with `W_uk` folded
    in, q_pe [B, S, nh, pe]: its rotated rotary part, and what the cache
    holds of each token: k_pe [B, S, pe], the rotated shared key, and c_kv
    [B, S, rank], the normalised latent)."""
    B, S, _ = x.shape
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, pe = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = x.dtype
    with jax.named_scope("attn.q_lora"):
        attn_in = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        c_q = rms_norm(
            matmul_any(attn_in, lp["wq_a"], "bsh,hr->bsr").astype(dt),
            lp["q_norm"], cfg.rms_norm_eps)
        q = matmul_any(c_q, lp["wq_b"], "bsr,rd->bsd").astype(dt).reshape(
            B, S, nh, nope + pe)
    with jax.named_scope("attn.kv_latent"):
        ckv = matmul_any(attn_in, lp["wkv_a"], "bsh,hr->bsr").astype(dt)
        c_kv = rms_norm(ckv[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
        q_pe = apply_rope(q[..., nope:], positions, inv_freq,
                          scale=rope_scale)
        k_pe = apply_rope(ckv[..., None, r:], positions, inv_freq,
                          scale=rope_scale)[:, :, 0]
    with jax.named_scope("attn.kv_up"):
        q_abs = jnp.einsum("bshd,hdr->bshr", q[..., :nope], lp["w_uk"],
                           preferred_element_type=jnp.float32).astype(dt)
    return q_abs, q_pe, k_pe, c_kv


def _latent_out(lp: Params, attn: jax.Array, dt) -> jax.Array:
    """The output projection of attention over latents, [B, S, h] in `dt`
    (the residual is the caller's): `attn` [B, S, nh, rank] takes each
    head's `W_uv` first."""
    B, S, nh, _ = attn.shape
    with jax.named_scope("attn.kv_up"):
        o = jnp.einsum("bshr,hrv->bshv", attn, lp["w_uv"],
                       preferred_element_type=jnp.float32).astype(dt)
    with jax.named_scope("attn.out"):
        return matmul_any(o.reshape(B, S, -1), lp["wo"],
                          "bsd,dh->bsh").astype(dt)


def _feed_forward(lp: Params, x: jax.Array, x_in: jax.Array,
                  cfg: ModelConfig, chunk_lens=None, moe_stats: bool = False,
                  stacks=None):
    """The feed-forward half of a layer as a function of its input x [B, S,
    h] alone (the residual is the caller's, `_residual`): the layer's
    experts where it has a router, its dense SwiGLU otherwise (a leading
    layer of a dense-then-expert model).  -> (y, *stats): with `moe_stats`
    the layer's `moe_step_stats` over the rows below `chunk_lens` [B]
    (None: every row), zeros from a dense layer.  `stacks` is `_moe`'s."""
    with jax.named_scope("mlp"):
        mlp_in = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        if "router" not in lp:
            zeros = (jnp.zeros((moe_stats_columns(cfg),), jnp.int32),
                     ) if moe_stats else ()
            return (_mlp(lp, mlp_in, cfg.mlp_multipliers), *zeros)
        router_x = x_in if cfg.moe_router_pre_attn else None
        if not moe_stats:
            return (_moe(lp, mlp_in, cfg, router_x, stacks=stacks),)
        return _moe(lp, mlp_in, cfg, router_x, _valid_rows(x, chunk_lens),
                    stats=True, stacks=stacks)


def _valid_rows(x: jax.Array, chunk_lens) -> Optional[jax.Array]:
    """[B, S] bool: the rows of a chunk below `chunk_lens` [B] (None: every
    row counts and no mask is built)."""
    return (None if chunk_lens is None else
            jnp.arange(x.shape[1])[None, :] < chunk_lens[:, None])


def _residual(cfg: ModelConfig, lp: Params, half: str, x: jax.Array, f):
    """One half of a layer around the residual.  `f(u) -> (y, *aux)` is the
    half as a function of its input alone (its own norm inside).  A plain
    residual: u = x [B, S, h] and x' = x + y.  Hyper-connections (`half`
    names the mixer, "hc_attn" | "hc_mlp"): x [B, S, n x h] (`_streams`), u
    the mixer's read of the streams, x' its write back: two Pallas kernels
    a half on a single-device TPU trace (`ops/pallas_hyper_connections.py`,
    one pass over the streams each), the `jnp` forms of
    `ops/hyper_connections.py` elsewhere; the trace notes which
    (`hc_mixers`).  -> (x', aux, err): err [B, S] the mix's distance from
    doubly stochastic, None from a plain residual."""
    if not cfg.hc_mult:
        y, *aux = f(x)
        return x + y, aux, None
    n, lead = cfg.hc_mult, x.shape[:-1]
    mixer = (lp[half + "_phi"], lp[half + "_scale"], lp[half + "_base"])
    how = dict(iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
               clamp=cfg.hc_res_clamp, rms_eps=cfg.rms_norm_eps)
    kernel, why = pallas_hc.lowering(x, n)
    tokens = x.size // x.shape[-1]
    xla_ledger.note_path_choice(
        "hc_mixers", "xla" if kernel is None else "kernel", why,
        tokens=tokens)
    if kernel is None:
        xs = x.reshape(*lead, n, -1)
        m = hc.mix(xs, *mixer, **how)
        y, *aux = f(hc.pre(xs, m.pre))
        return hc.post(xs, y, m.post, m.res).reshape(x.shape), aux, m.err
    flat = x.reshape(tokens, -1)
    with jax.named_scope("hc.mix"):
        u, w = pallas_hc.read(flat, *mixer, interpret=kernel, **how)
    y, *aux = f(u.reshape(*lead, -1))
    with jax.named_scope("hc.post"):
        out = pallas_hc.write(flat, y.reshape(tokens, -1), w, n=n,
                              interpret=kernel)
    return (out.reshape(x.shape), aux,
            w[:, pallas_hc.columns(n)[3]].reshape(lead))


def hc_mixers(tokens: int) -> Optional[str]:
    """Which form the stream mixers of a step of `tokens` tokens took, as
    its trace noted (`_residual`): "kernel" | "xla"; None where no such
    step was traced."""
    return xla_ledger.path_choice("hc_mixers", tokens=tokens)


def _hc_err_ppm(errs, valid) -> jax.Array:
    """int32 [1]: the largest |row or column sum of R - 1| over a layer's
    two mixes and the rows `valid` marks (None: all), parts per million."""
    err = jnp.maximum(*errs)
    if valid is not None:
        err = jnp.where(valid, err, 0.0)
    return jnp.round(err.max() * 1e6).astype(jnp.int32)[None]


def _latent_layer(lp, x, cfg, attend, chunk_lens=None, moe_stats=False,
                  stacks=None):
    """The ONE latent-attention layer body of every step kind, around
    either residual.  `attend(u) -> (attn [B, S, nh, rank], k_pe, c_kv)`
    is the step's attention over its key sets, from the half's input.
    -> (x', k_pe, c_kv, *stats): the tokens' own cache rows, and with
    `moe_stats` the layer's stats (`moe_stats_width` columns)."""
    dt = x.dtype

    def attention(u):
        attn, k_pe, c_kv = attend(u)
        return _latent_out(lp, attn, dt), k_pe, c_kv

    h, (k_pe, c_kv), e_attn = _residual(cfg, lp, "hc_attn", x, attention)
    h, st, e_mlp = _residual(
        cfg, lp, "hc_mlp", h,
        lambda u: _feed_forward(lp, u, x, cfg, chunk_lens, moe_stats,
                                stacks))
    if moe_stats and cfg.hc_mult:
        st = [jnp.concatenate([st[0], _hc_err_ppm(
            (e_attn, e_mlp), _valid_rows(x, chunk_lens))])]
    return (h, k_pe, c_kv, *st)


def _layer_prefill_latent(lp, kv, layer, x, positions, page_table,
                          prefix_lens, chunk_lens, cfg, inv_freq, rope_scale,
                          moe_stats, attn_impl="xla", stacks=None):
    """`_layer_prefill` over latent pages: -> (x, (k_pe, c_kv, *stats)),
    the chunk's own cache rows as the pool stores them [B, S, tiles, 128]."""
    def attend(u):
        q_abs, q_pe, k_pe, c_kv = _latent_qkv(lp, u, positions, cfg,
                                              inv_freq, rope_scale)
        return latent_prefill_attention(
            q_abs, q_pe, k_pe, c_kv, kv.k, kv.v, page_table, prefix_lens,
            chunk_lens, cfg.latent_softmax_scale, impl=attn_impl,
            layer=layer), k_pe, c_kv

    h, k_pe, c_kv, *st = _latent_layer(lp, x, cfg, attend, chunk_lens,
                                       moe_stats, stacks)
    return h, (_stored(k_pe, kv.k), _stored(c_kv, kv.v), *st)


def _stored(rows: jax.Array, pool: jax.Array) -> jax.Array:
    """[..., width] rows as the pool stores them: [..., tiles, 128], zeros
    after the row's own values."""
    tiles, lanes = pool.shape[-2:]
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, tiles * lanes - rows.shape[-1])]
    return jnp.pad(rows, pad).reshape(*rows.shape[:-1], tiles, lanes)


def _layer_prefill(
    lp: Params,
    kv: KVCache,  # the WHOLE pool, read only (and only by page)
    layer,  # scalar layer index (traced: the loop's counter)
    x: jax.Array,  # [B, S, h]
    positions: jax.Array,  # [B, S]
    page_table: jax.Array,
    prefix_lens: jax.Array,
    chunk_lens: jax.Array,
    cfg: ModelConfig,
    inv_freq: jax.Array,
    attn_impl: str = "xla",
    window=None,  # per-layer sliding window (scalar; <= 0 → full)
    rope_pos=None,  # [B, 3, S] mrope streams (Qwen2-VL); None = standard
    rope_scale: float = 1.0,  # yarn amplitude factor
    rope_on=None,  # per-layer rotary switch (scalar; 0 → no positions)
    moe_stats: bool = False,  # ys gain this layer's `moe_step_stats`
    stacks=None,  # (the layer's whole stack, its index there): `_moe`'s
):
    """One decoder layer over a chunk.  Returns (x, (k, v)): the chunk's
    own keys and values [B, S, n_kv, hd], NOT a pool.  Attention reads the
    OLD pool's pages of `layer` plus the chunk itself, and layer l+1 never
    reads what layer l wrote, so the caller lands every layer's (k, v) in
    one scatter after the loop (`write_kv_layers`)."""
    if cfg.is_latent:
        return _layer_prefill_latent(
            lp, kv, layer, x, positions, page_table, prefix_lens, chunk_lens,
            cfg, inv_freq, rope_scale, moe_stats, attn_impl, stacks)
    B, S, h = x.shape
    nkv, hd = cfg.num_key_value_heads, cfg.head_dim_

    dt = x.dtype
    x_in = x  # what a pre-attention router reads
    with jax.named_scope("attn.qkv"):
        attn_in = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv_proj(attn_in, lp, cfg, "bsh,hd->bsd")
        # the layer's own query heads: what its `wq` gives (`layer_heads`)
        q = q.astype(dt).reshape(B, S, -1, hd)
        k = k.astype(dt).reshape(B, S, nkv, hd)
        v = v.astype(dt).reshape(B, S, nkv, hd)
        q, k = _qk_norm(lp, q, k, cfg)
        if rope_pos is not None:
            from ..ops import apply_mrope

            q = apply_mrope(q, rope_pos, inv_freq, cfg.mrope_section)
            k = apply_mrope(k, rope_pos, inv_freq, cfg.mrope_section)
        else:
            q, k = _rope_qk(q, k, positions, inv_freq, rope_scale, rope_on)

    attn = prefill_attention(
        q, k, v, kv.k, kv.v, page_table, prefix_lens, chunk_lens,
        impl=attn_impl, window=window, sink=lp.get("sinks"), layer=layer,
        packed=cfg.cache_spec.packed,
    )
    if "w_head_gate" in lp:
        attn = _head_gate(lp, attn_in, attn)
    with jax.named_scope("attn.out"):
        attn_out = matmul_any(
            attn.reshape(B, S, -1), lp["wo"], "bsd,dh->bsh"
        ).astype(x.dtype)
        if "bo" in lp:  # gpt-oss carries an o_proj bias
            attn_out = attn_out + lp["bo"].astype(x.dtype)
        x = x + attn_out

    y, *st = _feed_forward(lp, x, x_in, cfg, chunk_lens, moe_stats, stacks)
    return x + y, (k, v, *st)


@jax.named_scope("attn.gate")
def _head_gate(lp: Params, attn_in: jax.Array, attn: jax.Array) -> jax.Array:
    """Attention's output gate (`ModelConfig.attention_gate`): one scalar a
    head a token, sigmoid of the layer's normed input times `w_head_gate`
    [h, heads], times that head's output [B, S, heads, hd], before `wo`."""
    g = jax.nn.sigmoid(jnp.einsum("bsh,hn->bsn", attn_in, lp["w_head_gate"],
                                  preferred_element_type=jnp.float32))
    return (attn * g[..., None]).astype(attn.dtype)


def _layer_decode(
    lp: Params,
    kv_layer: Tuple[jax.Array, jax.Array],
    x: jax.Array,  # [B, h] — one token per seq
    positions: jax.Array,  # [B]
    page_table: jax.Array,
    seq_lens: jax.Array,  # [B] incl. new token
    cfg: ModelConfig,
    inv_freq: jax.Array,
    attn_impl: str = "xla",
    window=None,  # per-layer sliding window (scalar; <= 0 → full)
    rope_pos=None,  # [B] rope positions when they differ from the KV
    # slot index (mrope decode: slot + per-seq delta)
    rope_scale: float = 1.0,  # yarn amplitude factor
    defer_write: bool = False,  # return the new token's (k, v) instead
    # of writing the pool (the caller batch-scatters after the scan)
    rope_on=None,  # per-layer rotary switch (scalar; 0 → no positions)
    moe_stats: bool = False,  # returns (x, kv_out, `moe_step_stats`)
    stacks=None,  # (the layer's whole stack, its index there): `_moe`'s
):
    if cfg.is_latent:
        # always the deferred write: attend to the OLD pool's rows plus the
        # token's own row, which the caller lands after the loop
        def attend(u):
            q_abs, q_pe, k_pe, c_kv = _latent_qkv(lp, u, (
                positions if rope_pos is None else rope_pos)[:, None], cfg,
                inv_freq, rope_scale)
            return latent_attention(
                q_abs, q_pe, decode_parts(*kv_layer, k_pe, c_kv, page_table,
                                          seq_lens),
                cfg.latent_softmax_scale), k_pe, c_kv

        h, k_pe, c_kv, *st = _latent_layer(lp, x[:, None], cfg, attend,
                                           None, moe_stats, stacks)
        return (h[:, 0], (_stored(k_pe[:, 0], kv_layer[0]),
                          _stored(c_kv[:, 0], kv_layer[1])), *st)
    B, h = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    k_pages, v_pages = kv_layer

    dt = x.dtype
    x_in = x  # what a pre-attention router reads
    with jax.named_scope("attn.qkv"):
        attn_in = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv_proj(attn_in, lp, cfg, "bh,hd->bd")
        q = q.astype(dt).reshape(B, 1, nh, hd)
        k = k.astype(dt).reshape(B, 1, nkv, hd)
        v = v.astype(dt).reshape(B, 1, nkv, hd)
        q, k = _qk_norm(lp, q, k, cfg)
        rp = positions if rope_pos is None else rope_pos
        q, k = _rope_qk(q, k, rp[:, None], inv_freq, rope_scale, rope_on)
        q = q[:, 0]

    if defer_write:
        # deferred-write path: attend to the OLD pool + an explicit self
        # column; the caller lands every layer's (k, v) in ONE batched
        # scatter after the layer scan (`write_kv_layers`, the helper the
        # prefill loop writes through too).  A per-layer scatter + pool
        # read makes XLA copy the pool each layer-step — ~1.8ms/step at
        # 1B/batch-8; see decode_attention self_kv + decode_layers.  The
        # pool is still a scanned operand here (a slab is sliced out per
        # layer); `prefill_layers` shows the loop without that.
        attn = decode_attention(
            q, k_pages, v_pages, page_table, seq_lens, impl=attn_impl,
            window=window, sink=lp.get("sinks"),
            self_kv=(k[:, 0], v[:, 0]),
        )
        kv_out = (k[:, 0], v[:, 0])
    else:
        # write first, then attend over the full table (new token incl.).
        # DRIFT TRIPWIRE: decode_block_scan mirrors this layer body —
        # model features added here must be added there too.
        k_pages, v_pages = write_kv_pages(
            k_pages, v_pages, k, v, page_table, positions,
            jnp.ones_like(positions)
        )
        attn = decode_attention(
            q, k_pages, v_pages, page_table, seq_lens, impl=attn_impl,
            window=window, sink=lp.get("sinks"),
        )
        kv_out = (k_pages, v_pages)
    with jax.named_scope("attn.out"):
        attn_out = matmul_any(
            attn.reshape(B, nh * hd), lp["wo"], "bd,dh->bh"
        ).astype(x.dtype)
        if "bo" in lp:  # gpt-oss carries an o_proj bias
            attn_out = attn_out + lp["bo"].astype(x.dtype)
        x = x + attn_out

    y, *st = _feed_forward(lp, x[:, None], x_in[:, None], cfg, None,
                           moe_stats, stacks)
    return (x + y[:, 0], kv_out, *st)


def _rope_dim(cfg: ModelConfig) -> int:
    """Width of the rotated part of a head: all of it, or latent
    attention's separate rotary key."""
    return cfg.qk_rope_head_dim if cfg.is_latent else cfg.head_dim_


def _window_xs(cfg: ModelConfig):
    """Per-layer window operands for the layer scans: a single (L,) int32
    array appended to the scan xs when the model is windowed, () otherwise
    (bodies read `xs[3] if wins else None`).  One definition so the three
    forward paths cannot drift."""
    if not cfg.sliding_window:
        return ()
    return (jnp.asarray(cfg.layer_windows(), jnp.int32),)


def _rope_xs(cfg: ModelConfig):
    """Per-layer rotary switch for the layer scans, as `_window_xs`: one
    (L,) int32 array after the window's when the model gives a
    `rope_layout`, () otherwise, so that a model without one traces the
    programs it traced before (no operand, no `where`)."""
    if cfg.rope_layout is None:
        return ()
    return (jnp.asarray(cfg.rope_layout, jnp.int32),)


def _rope_qk(q, k, positions, inv_freq, scale, rope_on):
    """Rotate q and k; under a per-layer switch (`rope_on`, a traced
    scalar) a layer whose entry is 0 keeps both as they are: it sees no
    positions at all."""
    qr = apply_rope(q, positions, inv_freq, scale=scale)
    kr = apply_rope(k, positions, inv_freq, scale=scale)
    if rope_on is None:
        return qr, kr
    return jnp.where(rope_on > 0, qr, q), jnp.where(rope_on > 0, kr, k)


def require_flat_layer_scan(cfg: ModelConfig, layout: str) -> None:
    """A layout with its own copy of the layer body (sp's ring prefill,
    pp's stage slices) carries neither the per-layer rotary switch nor the
    router's pre-attention input: refuse such a family at start-up rather
    than rotate a position-free layer or route on the wrong stream."""
    if cfg.rope_layout is not None or cfg.moe_router_pre_attn:
        raise ValueError(
            f"{layout} does not carry a per-layer rope_layout or a "
            f"pre-attention router ({cfg.model_type}): serve this family "
            "flat or with --tp")


def require_plain_cache(cfg: ModelConfig, what: str) -> None:
    """Latent pages (one array a layer and no `v`), the dense-then-expert
    stacks and a residual of several streams (hyper-connections, which come
    with latent attention only: `ModelConfig.__post_init__`) are carried by
    the flat engine's own programs only: every path with its own copy of
    the layer body, the pool's spec or the page blobs refuses such a family
    at start-up."""
    if cfg.is_latent or cfg.first_k_dense:
        raise ValueError(
            f"{what} does not carry latent pages, a dense-then-expert "
            f"layer stack or a multi-stream residual yet ({cfg.model_type}): "
            "serve this family flat on one chip (replicas: --dp-ranks)")
    if cfg.layer_pattern is not None or cfg.state_spec is not None:
        raise ValueError(
            f"{what} does not carry a layer pattern, pages for some layers "
            f"only or state slots beside the pages yet ({cfg.model_type}): "
            "serve this family flat on one chip (replicas: --dp-ranks)")


def require_one_layer_shape(cfg: ModelConfig, what: str) -> None:
    """Layers of several shapes (`layer_heads`: laguna's head counts,
    lfm2_moe's short convolutions beside attention) are walked by the one
    layer loop of `models/laguna.py`, which every flat step kind but these
    rides: a path with a layer body, a weight layout or specs of its own
    refuses the family at start-up, by the key that asks."""
    if cfg.layer_kinds is None:
        return
    if cfg.conv_layers:
        raise ValueError(
            f"{what} does not carry mixers by layer (layer_types 'conv': "
            f"{cfg.model_type}): its layers are short convolutions beside "
            "attention and only the flat engine's one layer loop walks "
            "them; serve this family flat on one chip (replicas: "
            "--dp-ranks)")
    raise ValueError(
        f"{what} does not carry head counts by layer "
        f"(num_attention_heads_per_layer: {cfg.model_type}): its layers "
        "have two shapes and only the flat engine's one layer loop "
        "walks them; serve this family flat on one chip (replicas: "
        "--dp-ranks)")


def require_no_state(cfg: ModelConfig, what: str) -> None:
    """A step kind that would have to roll a sequence's state back, or that
    has its own layer body without one: refuse a family with state-space
    layers or short convolutions by name, at start-up."""
    if cfg.layer_pattern is not None or cfg.state_spec is not None:
        raise ValueError(
            f"{what} does not carry the recurrent state of state-space "
            f"layers ({cfg.model_type}): a state cannot be rolled back, or "
            "this step has a layer body of its own that threads none")


def _streams(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Embedded tokens [..., h] as the residual the layer loops carry:
    themselves, or under hyper-connections every stream alike, a token's n
    streams side by side [..., n x h] (the one layout the mixers' kernels
    take as a dense block, `ops/pallas_hyper_connections.py`).  A family's
    `embedding_multiplier` (falcon_h1) scales them first, in float32."""
    if cfg.embedding_multiplier != 1.0:
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)
    if not cfg.hc_mult:
        return x
    return jnp.concatenate([x] * cfg.hc_mult, axis=-1)


def _final_norm(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The final norm of the residual after the last layer [..., h]; under
    hyper-connections [..., n x h] the head's reduction of the streams
    comes first."""
    if cfg.hc_mult:
        x = hc.head_reduce(
            x.reshape(*x.shape[:-1], cfg.hc_mult, -1),
            params["hc_head_phi"], params["hc_head_scale"],
            params["hc_head_base"], eps=cfg.hc_eps, rms_eps=cfg.rms_norm_eps)
    if "final_norm_bias" in params:  # a family of LayerNorms (phi4flash)
        return layer_norm(x, params["final_norm"], params["final_norm_bias"],
                          cfg.rms_norm_eps)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


@jax.named_scope("head")
def _lm_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = _final_norm(params, cfg, x)
    head = params.get("lm_head")  # quantization adds one even when tied
    if head is None:
        if not cfg.tie_word_embeddings:
            raise KeyError(
                "untied model params are missing 'lm_head' — falling back "
                "to embed.T would silently produce wrong logits"
            )
        logits = jnp.einsum("...h,hv->...v", x, params["embed"].T,
                            preferred_element_type=jnp.float32)
    else:
        logits = matmul_any(x, head, "...h,hv->...v")
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return logits


def prefill_layers(
    layers: Params,
    cfg: ModelConfig,
    kv: KVCache,
    x: jax.Array,  # [B, S, h] — embedded input
    positions: jax.Array,  # [B, S]
    page_table: jax.Array,
    prefix_lens: jax.Array,
    chunk_lens: jax.Array,
    attn_impl: str = "xla",
    wins: Optional[Tuple[jax.Array, ...]] = None,  # per-layer windows xs
    rope_pos=None,  # [B, 3, S] mrope streams (Qwen2-VL multimodal)
    moe_stats: bool = False,  # also return the step's merged moe stats
):
    """Scan a STACK of decoder layers over an embedded chunk (the body of
    `forward_prefill`, exposed so pipeline stages can run their local
    layer slice — parallel/pp_engine.py).  Returns (x, kv), and with
    `moe_stats` (x, kv, int32 [3]: `merge_moe_stats` over the layers).

    The pool stays where it is: the scan runs over the layers' weights and
    a layer index, the body closes over `kv` and reads it by (layer, page),
    and the only `ys` are the chunk's own keys and values ([L, B, S, n_kv,
    hd]), which ONE scatter lands in the donated pool after the loop.  Do
    not scan the pool itself: XLA then slices a layer's slab out, stacks a
    new slab back and copies the whole pool at the loop's edge on every
    step (PERF.md, finding 10)."""
    inv_freq = rope_frequencies(_rope_dim(cfg), cfg.rope_theta,
                                cfg.rope_scaling)
    rs = rope_attention_scale(cfg.rope_scaling)
    if wins is None:
        wins = _window_xs(cfg)
    ropes = _rope_xs(cfg)

    def body(h, xs, stacks=None):
        lp, layer = xs[:2]
        return _layer_prefill(
            lp, kv, layer, h, positions, page_table, prefix_lens,
            chunk_lens, cfg, inv_freq, attn_impl,
            window=xs[2] if wins else None, rope_pos=rope_pos,
            rope_scale=rs, rope_on=xs[-1] if ropes else None,
            moe_stats=moe_stats, stacks=stacks,
        )

    n_layers = kv.k.shape[0]
    x, ys = _scan_stacks(
        body, x, layers,
        (jnp.arange(n_layers, dtype=jnp.int32), *wins, *ropes),
        whole=cfg.is_moe)
    valid = jnp.arange(x.shape[1])[None, :] < chunk_lens[:, None]
    kv = KVCache(*write_kv_layers(
        kv.k, kv.v, ys[0], ys[1], page_table, prefix_lens, valid))
    return ((x, kv, merge_moe_stats(ys[2], cfg.hc_mult > 0)) if moe_stats
            else (x, kv))


def decode_layers(
    layers: Params,
    cfg: ModelConfig,
    kv: KVCache,
    x: jax.Array,  # [B, h] — embedded last token
    positions: jax.Array,  # [B]
    page_table: jax.Array,
    attn_impl: str = "xla",
    wins: Optional[Tuple[jax.Array, ...]] = None,
    rope_offset=None,  # [B] added to positions for ROPE only (mrope
    # delta — the KV slot index stays the raw token index)
    moe_stats: bool = False,  # also return the step's merged moe stats
):
    """Scan a STACK of decoder layers for one decode step (the body of
    `forward_decode`, exposed for pipeline stages).  Returns (x, kv), and
    with `moe_stats` (x, kv, int32 [3])."""
    inv_freq = rope_frequencies(_rope_dim(cfg), cfg.rope_theta,
                                cfg.rope_scaling)
    rs = rope_attention_scale(cfg.rope_scaling)
    seq_lens = positions + 1
    if wins is None:
        wins = _window_xs(cfg)
    rope_pos = None if rope_offset is None else positions + rope_offset
    # deferred KV write (see _layer_decode): xla decode path only — the
    # Pallas kernel (long contexts under "adaptive") reads pages and has
    # no self column, so it keeps the write-first layout.  The choice is
    # static per trace (table width bucket).
    from ..ops.paged_attention import LATENT_DECODE_XLA, _adapt

    defer = _adapt(
        attn_impl, page_table, kv.k.shape[2],
        only_xla=LATENT_DECODE_XLA if cfg.is_latent else "") != "pallas"
    ropes = _rope_xs(cfg)

    def body(carry, xs, stacks=None):
        h = carry
        lp, k_pages, v_pages = xs[:3]
        h, kv_out, *st = _layer_decode(
            lp, (k_pages, v_pages), h, positions, page_table, seq_lens, cfg,
            inv_freq, attn_impl, window=xs[3] if wins else None,
            rope_pos=rope_pos, rope_scale=rs, defer_write=defer,
            rope_on=xs[-1] if ropes else None, moe_stats=moe_stats,
            stacks=stacks,
        )
        return h, (*kv_out, *st)

    x, ys = _scan_stacks(body, x, layers, (kv.k, kv.v, *wins, *ropes),
                         whole=cfg.is_moe)
    k_new, v_new = ys[:2]
    if defer:
        # ONE batched scatter lands every layer's new token ([L, B, kv,
        # hd]); out-of-window rows carry an all-trash table row, so their
        # slot is inside trash page 0
        k_new, v_new = write_kv_layers(
            kv.k, kv.v, k_new[:, :, None], v_new[:, :, None], page_table,
            positions, jnp.ones((positions.shape[0], 1), bool))
    kv = KVCache(k_new, v_new)
    return ((x, kv, merge_moe_stats(ys[2], cfg.hc_mult > 0)) if moe_stats
            else (x, kv))


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    kv: KVCache,
    tokens: jax.Array,  # [B, S]
    page_table: jax.Array,  # [B, max_pages]
    prefix_lens: jax.Array,  # [B]
    chunk_lens: jax.Array,  # [B]
    attn_impl: str = "xla",
    extra_embeds: Optional[jax.Array] = None,  # [B, S, h]
    extra_mask: Optional[jax.Array] = None,  # [B, S] bool
    mm_positions: Optional[jax.Array] = None,  # [B, 3, S] mrope streams
    moe_stats: bool = False,  # an expert model's step stats ride along
    samples: Optional[jax.Array] = None,  # [B] bool: rows that draw a token
    then=None,  # logits -> pytree, inside the head's conditional
):
    """Run a prefill chunk; returns (logits at the last valid position
    [B, V], kv), and with `moe_stats` a third: int32 [3], the step's
    (assignments, experts touched summed over layers, largest per-expert
    row count) over the chunk's valid tokens.

    `samples` says which rows a token is drawn from (a prompt's last
    chunk).  Given it, everything after the layer loop that exists only to
    produce a token (the gather of the last position, the final norm, the
    vocabulary matmul and the caller's `then(logits)`: sampling, logprobs)
    runs under ONE `lax.cond` on "any row samples", and a step in which
    none does returns zeros of the same shapes: a mid-prompt chunk does
    not read the vocabulary matrix, nor, in a decoder-hybrid-decoder
    (`models/phi4flash.py`), run the cross half of the layers.  One scalar for the step and never a
    select by row, which would run both sides.  What `then` returns takes
    the logits' place.  None: every row samples, no conditional.

    `extra_embeds`/`extra_mask` inject precomputed embeddings (vision
    tower patches) in place of the token embedding at masked positions —
    the multimodal prompt path (the reference forwards precomputed
    embeddings to its engines, sglang/request_handlers/multimodal/
    encode_worker_handler.py).  `mm_positions` supplies the per-token
    (temporal, height, width) rope streams for mrope models (Qwen2-VL);
    without it an mrope model ropes text-style (all streams equal),
    which is exact for text-only prompts."""
    B, S = tokens.shape
    positions = prefix_lens[:, None] + jnp.arange(S)[None, :]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]  # [B, S, h]
        if extra_embeds is not None:
            x = jnp.where(extra_mask[..., None],
                          extra_embeds.astype(x.dtype), x)
        x = _streams(cfg, x)
    handed = None
    if cfg.cross_decoder:
        # the self half over every token; the cross half is the head's
        from . import phi4flash

        x, kv, handed = phi4flash.self_layers(
            params, cfg, kv, x, page_table, prefix_lens, chunk_lens)
        st = []
    elif cfg.layer_pattern is not None:
        from . import hybrid

        x, kv, *st = hybrid.layers(params, cfg, kv, x, page_table,
                                   prefix_lens, chunk_lens, attn_impl,
                                   moe_stats)
    elif cfg.layer_kinds is not None:
        from . import laguna

        x, kv, *st = laguna.layers(params, cfg, kv, x, positions, page_table,
                                   prefix_lens, chunk_lens, attn_impl,
                                   moe_stats)
    else:
        x, kv, *st = prefill_layers(
            layer_stacks(params), cfg, kv, x, positions, page_table,
            prefix_lens, chunk_lens, attn_impl,
            rope_pos=mm_positions if cfg.mrope_section else None,
            moe_stats=moe_stats,
        )

    def head(x):
        last = jnp.maximum(chunk_lens - 1, 0)
        x_last = jnp.take_along_axis(
            x, last[:, None, None], axis=1)[:, 0]  # [B, h] | [B, n x h]
        if handed is not None:
            # no cross layer mixes positions but through what the self half
            # wrote for every token: a row needs them where it samples only
            x_last = phi4flash.cross_layers(
                params, cfg, kv, x_last, handed, last, page_table,
                prefix_lens)
        logits = _lm_logits(params, cfg, x_last)
        return logits if then is None else then(logits)

    if samples is None:
        return (head(x), kv, *st)
    blank = jax.eval_shape(head, x)
    out = jax.lax.cond(
        jnp.any(samples), head,
        lambda _: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), blank),
        x)
    return (out, kv, *st)


def forward_embed(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S]
    lens: jax.Array,  # [B] valid lengths
) -> jax.Array:
    """Sequence embeddings: mean-pooled final hidden states over valid
    tokens (decoder-as-embedder, the common llama-embedding recipe).
    Cache-free: nothing is cached before the one chunk (prefix 0) and what
    the chunk computes is thrown away, so the pool `_layer_prefill` reads is
    a one-slot stand-in that attention masks out whole."""
    B, S = tokens.shape
    require_no_state(cfg, "the embedding forward")
    require_one_layer_shape(cfg, "the embedding forward")
    kv = KVCache.create(cfg, 1, 1, jnp.float32)
    table = jnp.zeros((B, 1), jnp.int32)
    inv_freq = rope_frequencies(_rope_dim(cfg), cfg.rope_theta,
                                cfg.rope_scaling)
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    prefix = jnp.zeros((B,), jnp.int32)
    x = _streams(cfg, params["embed"][tokens])
    wins, ropes = _window_xs(cfg), _rope_xs(cfg)

    def body(h, xs):
        lp, layer = xs[:2]
        h, _ = _layer_prefill(
            lp, kv, layer, h, positions, table, prefix, lens, cfg, inv_freq,
            window=xs[2] if wins else None,
            rope_on=xs[-1] if ropes else None,
        )
        return h, None

    layer_ids = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    x, _ = _scan_stacks(body, x, layer_stacks(params),
                        (layer_ids, *wins, *ropes))
    x = _final_norm(params, cfg, x)
    mask = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * mask[..., None]).sum(1)
    pooled = pooled / jnp.maximum(lens[:, None].astype(jnp.float32), 1.0)
    # unit-normalize (cosine-ready, matches common embedding servers)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
    )


def forward_decode(
    params: Params,
    cfg: ModelConfig,
    kv: KVCache,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B] — position of this token
    page_table: jax.Array,  # [B, max_pages]
    attn_impl: str = "xla",
    rope_offset: Optional[jax.Array] = None,  # [B] mrope delta (rope
    # position = slot + delta; KV slots stay raw token indices)
    moe_stats: bool = False,  # as `forward_prefill`, over every row
):
    """One decode step for the whole batch; returns (logits [B, V], kv)."""
    with jax.named_scope("embed"):
        x = _streams(cfg, params["embed"][tokens])  # [B, h]
    if cfg.cross_decoder:
        # a chunk of one token through both halves of the layers
        from . import phi4flash

        ones = jnp.ones_like(positions)
        x, kv, handed = phi4flash.self_layers(
            params, cfg, kv, x[:, None], page_table, positions, ones)
        x = phi4flash.cross_layers(params, cfg, kv, x[:, 0], handed,
                                   ones - 1, page_table, positions)
        return (_lm_logits(params, cfg, x), kv)
    if cfg.layer_pattern is not None:
        # a chunk of one token through the one layer loop: the state is
        # read from and written to the row's slots, the token's keys and
        # values land after the loop
        from . import hybrid

        x, kv, *st = hybrid.layers(
            params, cfg, kv, x[:, None], page_table, positions,
            jnp.ones_like(positions), attn_impl, moe_stats)
        return (_lm_logits(params, cfg, x[:, 0]), kv, *st)
    if cfg.layer_kinds is not None:
        # a chunk of one token through the one loop over both shapes
        from . import laguna

        x, kv, *st = laguna.layers(
            params, cfg, kv, x[:, None], positions[:, None], page_table,
            positions, jnp.ones_like(positions), attn_impl, moe_stats)
        return (_lm_logits(params, cfg, x[:, 0]), kv, *st)
    x, kv, *st = decode_layers(
        layer_stacks(params), cfg, kv, x, positions, page_table, attn_impl,
        rope_offset=rope_offset, moe_stats=moe_stats,
    )
    return (_lm_logits(params, cfg, x), kv, *st)


def forward_verify(
    params: Params,
    cfg: ModelConfig,
    kv: KVCache,
    tokens: jax.Array,  # [B, S] — last accepted token + S-1 draft tokens
    page_table: jax.Array,  # [B, max_pages]
    prefix_lens: jax.Array,  # [B] — tokens whose KV is already written
    chunk_lens: jax.Array,  # [B]
    attn_impl: str = "xla",
    rope_offset: Optional[jax.Array] = None,  # [B] mrope delta (rope
    # position = slot + delta; KV slots stay raw token indices)
    moe_stats: bool = False,  # as `forward_prefill`
):
    """Score EVERY position of a short draft chunk in one forward: the
    fused verify step of self-speculative decoding.  Identical to
    `forward_prefill` except the logits come back for all S positions
    ([B, S, V]), so the caller can verify S-1 drafted tokens against the
    model's own per-position samples in a single weight read.

    KV for the whole chunk is written through the normal prefill path;
    positions whose draft is later REJECTED are rolled back logically,
    not physically — `prefix_lens`/`positions` masking means no later
    dispatch ever attends a slot at or beyond its row's committed
    length, and the slots are overwritten as decode advances.  Rides
    `prefill_layers`, so every model feature (sinks, windows, MoE,
    biases, mrope-as-shifted-rope) stays in ONE implementation — no
    drift tripwire needed against the prefill path."""
    B, S = tokens.shape
    require_no_state(cfg, "the draft-verify step")
    positions = prefix_lens[:, None] + jnp.arange(S)[None, :]
    if rope_offset is not None:
        # positions feed ONLY rope inside _layer_prefill (the KV write is
        # addressed by prefix/chunk), so the mrope delta rides here —
        # exactly `_layer_decode`'s rope_pos = slot + delta
        positions = positions + rope_offset[:, None]
    x = _streams(cfg, params["embed"][tokens])  # [B, S, h]
    if cfg.layer_kinds is not None:
        from . import laguna

        x, kv, *st = laguna.layers(params, cfg, kv, x, positions, page_table,
                                   prefix_lens, chunk_lens, attn_impl,
                                   moe_stats)
        return (_lm_logits(params, cfg, x), kv, *st)
    x, kv, *st = prefill_layers(
        layer_stacks(params), cfg, kv, x, positions, page_table, prefix_lens,
        chunk_lens, attn_impl, moe_stats=moe_stats,
    )
    return (_lm_logits(params, cfg, x), kv, *st)


def decode_block_scan(
    params: Params,
    cfg: ModelConfig,
    kv: KVCache,
    tokens: jax.Array,  # [B] — last sampled token per row
    positions: jax.Array,  # [B] — position the first new token lands at
    page_table: jax.Array,  # [B, W]
    n_steps: int,
    max_valid_pos: int,
    sample_step,  # (carry, logits, tok_prev, step) -> (carry, tok, ys)
    carry_init,  # engine-side carry (seeds/counters/penalty counts …)
    rope_offset: Optional[jax.Array] = None,  # [B] mrope delta
    active_init: Optional[jax.Array] = None,  # [B] bool — device-resident
    # stop mask; switches sample_step to the 4-tuple protocol
    # (carry, logits, tok_prev, step, act) -> (carry, tok, ys, act_next)
) -> Tuple[Any, Any, jax.Array, jax.Array, KVCache]:
    """`n_steps` decode steps with BLOCK-MATERIALIZED KV (r5 perf): the
    pool pages behind the block's table are gathered ONCE, in-block
    tokens accumulate in small ring buffers, and every new (k, v) lands
    in ONE batched pool scatter after the scan.  Per-step paged gathers
    ran at ~100 GB/s effective on v5e (scattered 16KB DMA chunks) and
    cost ~1.2ms/step at 1B/batch-8 — dense reads of the materialized
    block run at the ~750 GB/s stream rate.

    Returns (carry, ys_stacked, last_tok, positions + n_steps, kv).

    With `active_init` (the device-resident decode loop) the scan also
    carries a per-row ACTIVE mask: a row whose mask drops (stop token /
    budget exhausted, decided inside `sample_step`) freezes its position
    — later steps rope/attend with the frozen position (outputs are
    host-discarded) and the final scatter routes its writes to the trash
    page, so a finished row's pool pages are never touched again no
    matter how long the chain keeps running.  Positions then return as
    `positions + emitted` per row, not `+ n_steps`.
    DRIFT TRIPWIRE: this is a separate forward path from
    `_layer_decode`/`decode_attention` — any new model feature (bias,
    norm variant, softcap, rope flavor) added there MUST be mirrored
    here, and vice versa; the engine golden/greedy-equality suites
    (gpt-oss, qwen-vl, swa, pooled) run through THIS path on CPU and on
    short-context TPU, which is what catches a drift."""
    require_no_state(cfg, "the decode block over gathered pages")
    require_one_layer_shape(cfg, "the decode block over gathered pages")
    layers = layer_stacks(params)
    L = kv.k.shape[0]
    P, page = kv.k.shape[1], kv.k.shape[2]
    B, W = page_table.shape
    latent = cfg.is_latent
    nh = cfg.num_attention_heads
    # a latent pool's `k` holds the shared rotary key and its `v` the
    # latent, each as [tiles, 128] (the gathers, rings and the final
    # scatter below carry them as any k and v)
    nkv, hd = kv.k.shape[3], kv.k.shape[4]
    vdims = kv.v.shape[3:]
    T = n_steps
    inv_freq = rope_frequencies(_rope_dim(cfg), cfg.rope_theta,
                                cfg.rope_scaling)
    rs = rope_attention_scale(cfg.rope_scaling)
    wins, ropes = _window_xs(cfg), _rope_xs(cfg)
    dt = params["embed"].dtype
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    # 1. one gather of the block's cached context (loop-invariant)
    with jax.named_scope("kv.gather"):
        kg = kv.k[:, page_table].reshape(L, B, W * page, nkv, hd)
        vg = kv.v[:, page_table].reshape(L, B, W * page, *vdims)
    S = W * page
    spos = jnp.arange(S)[None, :]  # cached slot positions
    len0 = positions  # [B] cached tokens at block start
    groups = nh // nkv

    def attn_one(lp, kg_l, vg_l, rk_l, rv_l, q, k_self, v_self, pos, t,
                 window):
        """q [B, nh, hd] against cached kg_l [B, S] + ring [B, T] + self."""
        qg = q.reshape(B, nkv, groups, hd)
        s_c = jnp.einsum("bkgd,bskd->bkgs", qg, kg_l,
                         preferred_element_type=jnp.float32) * scale
        s_r = jnp.einsum("bkgd,btkd->bkgt", qg, rk_l,
                         preferred_element_type=jnp.float32) * scale
        s_s = jnp.einsum("bkgd,bkd->bkg", qg, k_self,
                         preferred_element_type=jnp.float32)[..., None] * scale
        cur = pos + 1  # context length incl. the new token
        ok_c = spos < len0[:, None]
        rpos = len0[:, None] + jnp.arange(T)[None, :]
        ok_r = jnp.arange(T)[None, :] < t
        if window is not None:
            in_w_c = (spos >= cur[:, None] - window) | (window <= 0)
            in_w_r = (rpos >= cur[:, None] - window) | (window <= 0)
            ok_c &= in_w_c
            ok_r &= in_w_r
        s_c = jnp.where(ok_c[:, None, None, :], s_c, -1e30)
        s_r = jnp.where(ok_r[:, None, None, :], s_r, -1e30)
        s_all = jnp.concatenate(
            [s_c.reshape(B, nh, S), s_r.reshape(B, nh, T),
             s_s.reshape(B, nh, 1)], axis=-1)
        sink = lp.get("sinks")
        if sink is not None:
            col = jnp.broadcast_to(
                sink.astype(jnp.float32)[None, :, None], (B, nh, 1))
            w_all = jax.nn.softmax(
                jnp.concatenate([s_all, col], -1), -1)[..., :-1]
        else:
            w_all = jax.nn.softmax(s_all, axis=-1)
        w_c = w_all[..., :S].reshape(B, nkv, groups, S)
        w_r = w_all[..., S:S + T].reshape(B, nkv, groups, T)
        w_s = w_all[..., -1:]  # [B, nh, 1]
        out = (jnp.einsum("bkgs,bskd->bkgd", w_c, vg_l.astype(jnp.float32))
               + jnp.einsum("bkgt,btkd->bkgd", w_r,
                            rv_l.astype(jnp.float32)))
        out = out.reshape(B, nh, hd)
        v_top = jnp.repeat(v_self, groups, axis=1).astype(jnp.float32)
        return (out + w_s * v_top).astype(q.dtype)

    masked = active_init is not None

    def step(carry, _):
        if masked:
            eng, tok, pos, t, act, rk, rv = carry
        else:
            eng, tok, pos, t, rk, rv = carry
            act = None
        ok = pos < max_valid_pos
        safe_pos = jnp.where(ok, pos, 0)
        rp = safe_pos if rope_offset is None else safe_pos + rope_offset
        with jax.named_scope("embed"):
            x = _streams(cfg, params["embed"][tok].astype(dt))

        def layer_latent(h, xs):
            lp, kg_l, vg_l, rk_l, rv_l = xs[:5]

            def attend(u):
                q_abs, q_pe, k_pe, c_kv = _latent_qkv(
                    lp, u, rp[:, None], cfg, inv_freq, rs)
                parts = [
                    (rows_of(kg_l), rows_of(vg_l),
                     (spos < len0[:, None])[:, None]),
                    (rows_of(rk_l), rows_of(rv_l), jnp.broadcast_to(
                        (jnp.arange(T) < t)[None, None], (B, 1, T))),
                    (k_pe, c_kv, jnp.ones((B, 1, 1), bool))]
                return latent_attention(
                    q_abs, q_pe, parts, cfg.latent_softmax_scale), k_pe, c_kv

            h, k_pe, c_kv = _latent_layer(lp, h[:, None], cfg, attend)
            return h[:, 0], (_stored(k_pe[:, 0], kv.k),
                             _stored(c_kv[:, 0], kv.v))

        def layer(h, xs):
            lp, kg_l, vg_l, rk_l, rv_l = xs[:5]
            window = xs[5] if wins else None
            h_in = h  # what a pre-attention router reads
            with jax.named_scope("attn.qkv"):
                attn_in = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                q, k, v = _qkv_proj(attn_in, lp, cfg, "bh,hd->bd")
                q = q.astype(dt).reshape(B, 1, nh, hd)
                k = k.astype(dt).reshape(B, 1, nkv, hd)
                v = v.astype(dt).reshape(B, 1, nkv, hd)
                q, k = _qk_norm(lp, q, k, cfg)
                q, k = _rope_qk(q, k, rp[:, None], inv_freq, rs,
                                xs[-1] if ropes else None)
                q, k, v = q[:, 0], k[:, 0], v[:, 0]
            with jax.named_scope("attn.core"):
                attn = attn_one(lp, kg_l, vg_l, rk_l, rv_l, q, k, v,
                                safe_pos, t, window)
            with jax.named_scope("attn.out"):
                attn_out = matmul_any(
                    attn.reshape(B, nh * hd), lp["wo"], "bd,dh->bh"
                ).astype(h.dtype)
                if "bo" in lp:
                    attn_out = attn_out + lp["bo"].astype(h.dtype)
                h = h + attn_out
            y, = _feed_forward(lp, h[:, None], h_in[:, None], cfg)
            return h + y[:, 0], (k, v)

        x, (ks, vs) = _scan_stacks(layer_latent if latent else layer, x,
                                   layers, (kg, vg, rk, rv, *wins, *ropes))
        # land this step's tokens in the rings (tiny update)
        rk = jax.lax.dynamic_update_slice(
            rk, ks[:, :, None].astype(rk.dtype), (0, 0, t, 0, 0))
        rv = jax.lax.dynamic_update_slice(
            rv, vs[:, :, None].astype(rv.dtype), (0, 0, t, 0, 0))
        logits = _lm_logits(params, cfg, x)
        if masked:
            # rows whose mask dropped freeze their position: the row
            # emitted its last token already, so later steps compute
            # discarded garbage and must not advance KV addressing
            eng, tok_next, ys, act_next = sample_step(
                eng, logits, tok, t, act)
            return (eng, tok_next, pos + act.astype(pos.dtype), t + 1,
                    act_next, rk, rv), (ys, act)
        eng, tok_next, ys = sample_step(eng, logits, tok, t)
        return (eng, tok_next, pos + 1, t + 1, rk, rv), ys

    rk0 = jnp.zeros((L, B, T, nkv, hd), kv.k.dtype)
    rv0 = jnp.zeros((L, B, T, *vdims), kv.v.dtype)
    if masked:
        (eng, tok, pos, _, _, rk, rv), (ys, acts) = jax.lax.scan(
            step, (carry_init, tokens, positions, jnp.int32(0),
                   active_init, rk0, rv0),
            None, length=T)
    else:
        (eng, tok, pos, _, rk, rv), ys = jax.lax.scan(
            step, (carry_init, tokens, positions, jnp.int32(0), rk0, rv0),
            None, length=T)

    # 3. one batched scatter of the whole block's KV into the pool
    return eng, ys, tok, pos, _scatter_block(
        kv, rk, rv, positions, page_table, max_valid_pos,
        jnp.swapaxes(acts, 0, 1) if masked else None)


def _scatter_block(kv: KVCache, rk, rv, positions, page_table,
                   max_valid_pos: int, acts) -> KVCache:
    """Land a decode block's ring buffers ([L, B, T, nkv, hd]) in the pool
    in one batched scatter; `acts` ([B, T] bool, or None) masks the steps a
    row was frozen in."""
    T = rk.shape[2]
    ok = positions[:, None] + jnp.arange(T)[None, :] < max_valid_pos  # [B, T]
    if acts is not None:
        # a frozen row's emitted prefix is contiguous from its initial
        # position, so the uniform position formula holds exactly where the
        # per-step mask is true; everything after the stop lands in trash
        ok &= acts
    return KVCache(*write_kv_layers(
        kv.k, kv.v, rk, rv, page_table, positions, ok))
