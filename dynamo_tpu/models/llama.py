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

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import (
    apply_rope,
    decode_attention,
    prefill_attention,
    rms_norm,
    rope_attention_scale,
    rope_frequencies,
    write_kv_layers,
    write_kv_pages,
)
from .config import ModelConfig
from .quantization import matmul_any

Params = dict


class KVCache(NamedTuple):
    """Paged KV pool for all layers: [L, P, page, n_kv, hd]."""

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
        cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16
    ) -> "KVCache":
        shape = (
            cfg.num_hidden_layers,
            num_pages,
            page_size,
            cfg.num_key_value_heads,
            cfg.head_dim_,
        )
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


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


def param_pspecs(cfg: ModelConfig, tp_axis: str = "tp", ep_axis: str = "tp") -> Params:
    """PartitionSpec tree matching `init_params` (megatron-style TP).

    Head-dim projections shard on heads; MLP shards gate/up on the ffn dim
    and down on its input; embeddings shard on vocab.  Layer-stacked arrays
    keep axis 0 (layers) replicated.
    """
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


def _mlp(lp: Params, x: jax.Array) -> jax.Array:
    if "w_gateup" in lp:  # fused gate‖up read (see _qkv_proj)
        y = matmul_any(x, lp["w_gateup"], "bsh,hf->bsf")
        f = y.shape[-1] // 2
        gate, up = y[..., :f], y[..., f:]
    else:
        gate = matmul_any(x, lp["w_gate"], "bsh,hf->bsf")
        up = matmul_any(x, lp["w_up"], "bsh,hf->bsf")
    act = jax.nn.silu(gate) * up
    return matmul_any(act.astype(x.dtype), lp["w_down"], "bsf,fh->bsh").astype(x.dtype)


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
    <= 7, up to |7|, glu = gate*sigmoid(1.702*gate), out = (up+1)*glu."""
    if cfg.moe_act == "gpt_oss_glu":
        limit = 7.0
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
        return (up + 1.0) * (gate * jax.nn.sigmoid(1.702 * gate))
    return jax.nn.silu(gate) * up


def moe_router_logits(lp: Params, x: jax.Array, eq: str) -> jax.Array:
    out = jnp.einsum(eq, x, lp["router"],
                     preferred_element_type=jnp.float32)
    if "router_b" in lp:
        out = out + lp["router_b"]
    return out


def _moe_dense(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Reference MoE: every expert computes every token, one-hot combine.
    O(E) compute — kept as the equality oracle for the dispatched path and
    for tiny test models where dispatch overhead dominates."""
    B, S, h = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    router_logits = moe_router_logits(lp, x, "bsh,he->bse")
    weights, selected = jax.lax.top_k(router_logits, k)  # [B,S,k]
    weights = jax.nn.softmax(weights, axis=-1)
    onehot = jax.nn.one_hot(selected, E, dtype=x.dtype)  # [B,S,k,E]
    combine = jnp.einsum("bsk,bske->bse", weights.astype(x.dtype), onehot)  # [B,S,E]
    gate = jnp.einsum("bsh,ehf->ebsf", x, lp["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.einsum("bsh,ehf->ebsf", x, lp["w_up"], preferred_element_type=jnp.float32)
    if "b_gate" in lp:
        gate = gate + lp["b_gate"][:, None, None, :]
        up = up + lp["b_up"][:, None, None, :]
    act = moe_act(cfg, gate, up).astype(x.dtype)
    out = jnp.einsum("ebsf,efh->ebsh", act, lp["w_down"], preferred_element_type=jnp.float32)
    if "b_down" in lp:
        out = out + lp["b_down"][:, None, None, :]
    return jnp.einsum("ebsh,bse->bsh", out.astype(x.dtype), combine)


def _moe_ragged(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Dropless top-k MoE via sort + `jax.lax.ragged_dot` (the
    MaxText/Megablocks "sparse matmul" pattern).

    Assignments are sorted by expert; each expert computes a ragged row
    group of its tokens, so compute is exactly O(T*k) FFN rows, no token
    is ever dropped, and every token's result is independent of what else
    is in the batch — the determinism the serving engine's disagg /
    migration / prefix-cache guarantees rely on."""
    B, S, h = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    A = T * k

    xf = x.reshape(T, h)
    router_logits = moe_router_logits(lp, xf, "th,he->te")
    weights, selected = jax.lax.top_k(router_logits, k)  # [T, k]
    weights = jax.nn.softmax(weights, axis=-1)

    expert_of = selected.reshape(A)  # assignment → expert
    order = jnp.argsort(expert_of, stable=True)  # group assignments by expert
    token_of = order // k  # assignment a (row-major [T, k]) is token a // k
    xs = xf[token_of]  # [A, h] rows sorted by expert
    group_sizes = jnp.bincount(expert_of, length=E)
    expert_sorted = expert_of[order]  # bias rows per sorted assignment

    gate = jax.lax.ragged_dot(
        xs, lp["w_gate"], group_sizes,
        preferred_element_type=jnp.float32,
    )
    up = jax.lax.ragged_dot(
        xs, lp["w_up"], group_sizes,
        preferred_element_type=jnp.float32,
    )
    if "b_gate" in lp:
        gate = gate + lp["b_gate"][expert_sorted]
        up = up + lp["b_up"][expert_sorted]
    act = moe_act(cfg, gate, up).astype(x.dtype)
    ys = jax.lax.ragged_dot(
        act, lp["w_down"], group_sizes,
        preferred_element_type=jnp.float32,
    )  # [A, h]
    if "b_down" in lp:
        ys = ys + lp["b_down"][expert_sorted]

    wf = weights.reshape(A)[order].astype(jnp.float32)
    out = jnp.zeros((T, h), jnp.float32).at[token_of].add(ys * wf[:, None])
    return out.reshape(B, S, h).astype(x.dtype)


def _moe(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.moe_impl in ("ragged", "a2a"):
        # "a2a" (the wide-EP all-to-all, parallel/wide_ep.py) only exists
        # inside an explicit expert-sharded shard_map; outside one the
        # dropless ragged dispatch is the same math on one shard
        return _moe_ragged(lp, x, cfg)
    if cfg.moe_impl == "dense":
        return _moe_dense(lp, x, cfg)
    if cfg.moe_impl == "capacity":
        return _moe_capacity(lp, x, cfg)
    raise ValueError(
        f"moe_impl must be ragged|a2a|capacity|dense, got {cfg.moe_impl!r}"
    )


def _moe_capacity(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
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
    if cap_f <= 0:  # dense fallback (tests / tiny models)
        return _moe_dense(lp, x, cfg)

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
    router_logits = moe_router_logits(lp, xg, "gth,he->gte")
    weights, selected = jax.lax.top_k(router_logits, k)  # [n_g, G, k]
    weights = jax.nn.softmax(weights, axis=-1)

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
):
    """One decoder layer over a chunk.  Returns (x, (k, v)): the chunk's
    own keys and values [B, S, n_kv, hd], NOT a pool.  Attention reads the
    OLD pool's pages of `layer` plus the chunk itself, and layer l+1 never
    reads what layer l wrote, so the caller lands every layer's (k, v) in
    one scatter after the loop (`write_kv_layers`)."""
    B, S, h = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_

    dt = x.dtype
    with jax.named_scope("attn.qkv"):
        attn_in = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv_proj(attn_in, lp, cfg, "bsh,hd->bsd")
        q = q.astype(dt).reshape(B, S, nh, hd)
        k = k.astype(dt).reshape(B, S, nkv, hd)
        v = v.astype(dt).reshape(B, S, nkv, hd)
        if rope_pos is not None:
            from ..ops import apply_mrope

            q = apply_mrope(q, rope_pos, inv_freq, cfg.mrope_section)
            k = apply_mrope(k, rope_pos, inv_freq, cfg.mrope_section)
        else:
            q = apply_rope(q, positions, inv_freq, scale=rope_scale)
            k = apply_rope(k, positions, inv_freq, scale=rope_scale)

    attn = prefill_attention(
        q, k, v, kv.k, kv.v, page_table, prefix_lens, chunk_lens,
        impl=attn_impl, window=window, sink=lp.get("sinks"), layer=layer,
    )
    with jax.named_scope("attn.out"):
        attn_out = matmul_any(
            attn.reshape(B, S, nh * hd), lp["wo"], "bsd,dh->bsh"
        ).astype(x.dtype)
        if "bo" in lp:  # gpt-oss carries an o_proj bias
            attn_out = attn_out + lp["bo"].astype(x.dtype)
        x = x + attn_out

    with jax.named_scope("mlp"):
        mlp_in = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        mlp_out = _moe(lp, mlp_in, cfg) if cfg.is_moe else _mlp(lp, mlp_in)
        return x + mlp_out, (k, v)


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
):
    B, h = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    k_pages, v_pages = kv_layer

    dt = x.dtype
    with jax.named_scope("attn.qkv"):
        attn_in = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv_proj(attn_in, lp, cfg, "bh,hd->bd")
        q = q.astype(dt).reshape(B, 1, nh, hd)
        k = k.astype(dt).reshape(B, 1, nkv, hd)
        v = v.astype(dt).reshape(B, 1, nkv, hd)
        rp = positions if rope_pos is None else rope_pos
        q = apply_rope(q, rp[:, None], inv_freq, scale=rope_scale)[:, 0]
        k = apply_rope(k, rp[:, None], inv_freq, scale=rope_scale)

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

    with jax.named_scope("mlp"):
        mlp_in = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        if cfg.is_moe:
            mlp_out = _moe(lp, mlp_in[:, None], cfg)[:, 0]
        else:
            mlp_out = _mlp(lp, mlp_in[:, None])[:, 0]
        return x + mlp_out, kv_out


def _window_xs(cfg: ModelConfig):
    """Per-layer window operands for the layer scans: a single (L,) int32
    array appended to the scan xs when the model is windowed, () otherwise
    (bodies read `xs[3] if wins else None`).  One definition so the three
    forward paths cannot drift."""
    if not cfg.sliding_window:
        return ()
    return (jnp.asarray(cfg.layer_windows(), jnp.int32),)


@jax.named_scope("head")
def _lm_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")  # quantization adds one even when tied
    if head is None:
        if not cfg.tie_word_embeddings:
            raise KeyError(
                "untied model params are missing 'lm_head' — falling back "
                "to embed.T would silently produce wrong logits"
            )
        return jnp.einsum("...h,hv->...v", x, params["embed"].T,
                          preferred_element_type=jnp.float32)
    return matmul_any(x, head, "...h,hv->...v")


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
) -> Tuple[jax.Array, KVCache]:
    """Scan a STACK of decoder layers over an embedded chunk (the body of
    `forward_prefill`, exposed so pipeline stages can run their local
    layer slice — parallel/pp_engine.py).

    The pool stays where it is: the scan runs over the layers' weights and
    a layer index, the body closes over `kv` and reads it by (layer, page),
    and the only `ys` are the chunk's own keys and values ([L, B, S, n_kv,
    hd]), which ONE scatter lands in the donated pool after the loop.  Do
    not scan the pool itself: XLA then slices a layer's slab out, stacks a
    new slab back and copies the whole pool at the loop's edge on every
    step (PERF.md, finding 10)."""
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    rs = rope_attention_scale(cfg.rope_scaling)
    if wins is None:
        wins = _window_xs(cfg)

    def body(h, xs):
        lp, layer = xs[:2]
        return _layer_prefill(
            lp, kv, layer, h, positions, page_table, prefix_lens,
            chunk_lens, cfg, inv_freq, attn_impl,
            window=xs[2] if wins else None, rope_pos=rope_pos,
            rope_scale=rs,
        )

    n_layers = kv.k.shape[0]
    x, (k_new, v_new) = jax.lax.scan(
        body, x, (layers, jnp.arange(n_layers, dtype=jnp.int32), *wins))
    valid = jnp.arange(x.shape[1])[None, :] < chunk_lens[:, None]
    return x, KVCache(*write_kv_layers(
        kv.k, kv.v, k_new, v_new, page_table, prefix_lens, valid))


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
) -> Tuple[jax.Array, KVCache]:
    """Scan a STACK of decoder layers for one decode step (the body of
    `forward_decode`, exposed for pipeline stages)."""
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    rs = rope_attention_scale(cfg.rope_scaling)
    seq_lens = positions + 1
    if wins is None:
        wins = _window_xs(cfg)
    rope_pos = None if rope_offset is None else positions + rope_offset
    # deferred KV write (see _layer_decode): xla decode path only — the
    # Pallas kernel (long contexts under "adaptive") reads pages and has
    # no self column, so it keeps the write-first layout.  The choice is
    # static per trace (table width bucket).
    from ..ops.paged_attention import _adapt

    defer = _adapt(attn_impl, page_table, kv.k.shape[2]) != "pallas"

    def body(carry, xs):
        h = carry
        lp, k_pages, v_pages = xs[:3]
        h, kv_out = _layer_decode(
            lp, (k_pages, v_pages), h, positions, page_table, seq_lens, cfg,
            inv_freq, attn_impl, window=xs[3] if wins else None,
            rope_pos=rope_pos, rope_scale=rs, defer_write=defer,
        )
        return h, kv_out

    x, (k_new, v_new) = jax.lax.scan(body, x, (layers, kv.k, kv.v, *wins))
    if not defer:
        return x, KVCache(k_new, v_new)
    # ONE batched scatter lands every layer's new token ([L, B, kv, hd]);
    # out-of-window rows carry an all-trash table row, so their slot is
    # inside trash page 0
    return x, KVCache(*write_kv_layers(
        kv.k, kv.v, k_new[:, :, None], v_new[:, :, None], page_table,
        positions, jnp.ones((positions.shape[0], 1), bool)))


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
) -> Tuple[jax.Array, KVCache]:
    """Run a prefill chunk; returns logits at the last valid position [B, V].

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
    x, kv = prefill_layers(
        params["layers"], cfg, kv, x, positions, page_table, prefix_lens,
        chunk_lens, attn_impl,
        rope_pos=mm_positions if cfg.mrope_section else None,
    )
    last = jnp.maximum(chunk_lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # [B, h]
    return _lm_logits(params, cfg, x_last), kv


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
    kv = KVCache.create(cfg, 1, 1, jnp.float32)
    table = jnp.zeros((B, 1), jnp.int32)
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    prefix = jnp.zeros((B,), jnp.int32)
    x = params["embed"][tokens]
    wins = _window_xs(cfg)

    def body(h, xs):
        lp, layer = xs[:2]
        h, _ = _layer_prefill(
            lp, kv, layer, h, positions, table, prefix, lens, cfg, inv_freq,
            window=xs[2] if wins else None,
        )
        return h, None

    layer_ids = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    x, _ = jax.lax.scan(body, x, (params["layers"], layer_ids, *wins))
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
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
) -> Tuple[jax.Array, KVCache]:
    """One decode step for the whole batch; returns logits [B, V]."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]  # [B, h]
    x, kv = decode_layers(
        params["layers"], cfg, kv, x, positions, page_table, attn_impl,
        rope_offset=rope_offset,
    )
    return _lm_logits(params, cfg, x), kv


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
) -> Tuple[jax.Array, KVCache]:
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
    positions = prefix_lens[:, None] + jnp.arange(S)[None, :]
    if rope_offset is not None:
        # positions feed ONLY rope inside _layer_prefill (the KV write is
        # addressed by prefix/chunk), so the mrope delta rides here —
        # exactly `_layer_decode`'s rope_pos = slot + delta
        positions = positions + rope_offset[:, None]
    x = params["embed"][tokens]  # [B, S, h]
    x, kv = prefill_layers(
        params["layers"], cfg, kv, x, positions, page_table, prefix_lens,
        chunk_lens, attn_impl,
    )
    return _lm_logits(params, cfg, x), kv


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
    layers = params["layers"]
    L = kv.k.shape[0]
    P, page = kv.k.shape[1], kv.k.shape[2]
    B, W = page_table.shape
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim_)
    T = n_steps
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta,
                                cfg.rope_scaling)
    rs = rope_attention_scale(cfg.rope_scaling)
    wins = _window_xs(cfg)
    dt = params["embed"].dtype
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    # 1. one gather of the block's cached context (loop-invariant)
    with jax.named_scope("kv.gather"):
        kg = kv.k[:, page_table].reshape(L, B, W * page, nkv, hd)
        vg = kv.v[:, page_table].reshape(L, B, W * page, nkv, hd)
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
            x = params["embed"][tok].astype(dt)

        def layer(h, xs):
            lp, kg_l, vg_l, rk_l, rv_l = xs[:5]
            window = xs[5] if wins else None
            with jax.named_scope("attn.qkv"):
                attn_in = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                q, k, v = _qkv_proj(attn_in, lp, cfg, "bh,hd->bd")
                q = q.astype(dt).reshape(B, 1, nh, hd)
                k = k.astype(dt).reshape(B, 1, nkv, hd)
                v = v.astype(dt).reshape(B, 1, nkv, hd)
                q = apply_rope(q, rp[:, None], inv_freq, scale=rs)[:, 0]
                k = apply_rope(k, rp[:, None], inv_freq, scale=rs)[:, 0]
                v = v[:, 0]
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
            with jax.named_scope("mlp"):
                mlp_in = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
                if cfg.is_moe:
                    mlp_out = _moe(lp, mlp_in[:, None], cfg)[:, 0]
                else:
                    mlp_out = _mlp(lp, mlp_in[:, None])[:, 0]
                return h + mlp_out, (k, v)

        x, (ks, vs) = jax.lax.scan(layer, x, (layers, kg, vg, rk, rv,
                                              *wins))
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
    rv0 = jnp.zeros((L, B, T, nkv, hd), kv.v.dtype)
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
