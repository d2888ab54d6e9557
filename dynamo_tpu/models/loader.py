"""HF checkpoint loader: safetensors → the stacked-layer param pytree.

Maps HF llama/mistral/mixtral weight names onto the scan-friendly layout of
`llama.init_params` (per-layer arrays stacked on axis 0, projections stored
input-major so forward einsums are transpose-free).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

try:
    from safetensors import safe_open
except ImportError:  # pragma: no cover
    safe_open = None


def _index(path: str) -> Dict[str, str]:
    """weight name → shard file."""
    idx_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(idx_path):
        with open(idx_path) as f:
            return json.load(f)["weight_map"]
    single = os.path.join(path, "model.safetensors")
    if not os.path.exists(single):
        raise FileNotFoundError(f"no safetensors checkpoint in {path}")
    # build the map lazily from the single file
    with safe_open(single, framework="np") as f:
        return {k: "model.safetensors" for k in f.keys()}


# nanoseconds this process's shard readers spent reading: the worker's
# `startup.weights` event tells reading (`read_us`) from placing by it
READ_STATS = {"read_ns": 0}


class _ShardReader:
    def __init__(self, path: str):
        self.path = path
        self.weight_map = _index(path)
        self._open: Dict[str, object] = {}

    def get(self, name: str) -> np.ndarray:
        t0 = time.monotonic_ns()
        shard = self.weight_map[name]
        if shard not in self._open:
            self._open[shard] = safe_open(
                os.path.join(self.path, shard), framework="np"
            )
        w = self._open[shard].get_tensor(name)
        READ_STATS["read_ns"] += time.monotonic_ns() - t0
        return w

    def has(self, name: str) -> bool:
        return name in self.weight_map


def stack_layers(reader: "_ShardReader", n_layers: int, fmt: str,
                 transpose: bool = True, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Stack per-layer tensors on axis 0 (input-major when `transpose`,
    so forward einsums are transpose-free)."""
    mats: List[np.ndarray] = []
    for i in range(n_layers):
        w = reader.get(fmt.format(i=i))
        mats.append(w.T if transpose else w)
    return jnp.asarray(np.stack(mats), dtype)


def load_params(path: str, cfg: ModelConfig, dtype=jnp.bfloat16,
                prefix: str = "", reader=None):
    """Load HF weights into the stacked pytree (host RAM → device on first
    use; callers shard with jax.device_put + NamedSharding).  `prefix`
    namespaces every tensor name (VLM checkpoints nest the LLM under
    "language_model."); `reader` reuses an open _ShardReader."""
    if safe_open is None:
        raise RuntimeError("safetensors not available")
    r = reader or _ShardReader(path)
    L = cfg.num_hidden_layers
    if cfg.is_latent:  # deepseek_v3, and xing4_0 around its layers
        return _load_deepseek_v3(r, cfg, dtype, prefix)
    if cfg.cross_decoder:
        return _load_phi4flash(r, cfg, dtype, prefix)
    if cfg.model_type == "falcon_h1":
        return _load_falcon_h1(r, cfg, dtype, prefix)
    if cfg.layer_pattern is not None:
        return _load_nemotron_h(r, cfg, dtype, prefix)
    if cfg.model_type == "lfm2_moe":
        return _load_lfm2_moe(r, cfg, dtype, prefix)
    if cfg.layer_kinds is not None:
        return _load_laguna(r, cfg, dtype, prefix)

    def stack(fmt: str, transpose: bool = True) -> jnp.ndarray:
        return stack_layers(r, L, fmt, transpose=transpose, dtype=dtype)

    p = prefix + "model.layers.{i}."
    layers = {
        "wq": stack(p + "self_attn.q_proj.weight"),
        "wk": stack(p + "self_attn.k_proj.weight"),
        "wv": stack(p + "self_attn.v_proj.weight"),
        "wo": stack(p + "self_attn.o_proj.weight"),
        "attn_norm": stack(p + "input_layernorm.weight", transpose=False),
        "mlp_norm": stack(p + "post_attention_layernorm.weight", transpose=False),
    }
    if cfg.attention_bias:  # qwen2-style — gate on the CONFIG so the
        # param tree always matches param_pspecs/init_params (a checkpoint/
        # config mismatch must be a load error, not a tp tree-map error)
        if not r.has(prefix + "model.layers.0.self_attn.q_proj.bias"):
            raise ValueError(
                "config declares attention_bias but the checkpoint has "
                "no self_attn.*_proj.bias tensors"
            )
        layers.update(
            {
                "bq": stack(p + "self_attn.q_proj.bias", transpose=False),
                "bk": stack(p + "self_attn.k_proj.bias", transpose=False),
                "bv": stack(p + "self_attn.v_proj.bias", transpose=False),
            }
        )
    elif r.has(prefix + "model.layers.0.self_attn.q_proj.bias"):
        raise ValueError(
            "checkpoint has self_attn.*_proj.bias tensors but the config "
            "does not declare attention_bias — refusing to silently drop "
            "them"
        )
    if cfg.attention_out_bias:  # gpt-oss biases o_proj too
        layers["bo"] = stack(p + "self_attn.o_proj.bias", transpose=False)
    if cfg.qk_norm:  # qwen3's names; [head_dim] a layer
        layers["q_head_norm"] = stack(p + "self_attn.q_norm.weight",
                                      transpose=False)
        layers["k_head_norm"] = stack(p + "self_attn.k_norm.weight",
                                      transpose=False)
    if cfg.attention_sinks:  # gpt-oss sink logits — gate on the CONFIG
        # (like every other consumer) so params and cfg cannot disagree
        if not r.has(prefix + "model.layers.0.self_attn.sinks"):
            raise ValueError(
                "config declares attention_sinks but the checkpoint has "
                "no self_attn.sinks tensors"
            )
        layers["sinks"] = stack(p + "self_attn.sinks", transpose=False)
    mxfp4 = r.has(
        prefix + "model.layers.0.mlp.experts.gate_up_proj_blocks"
    )
    if cfg.moe_bias and (mxfp4 or r.has(
        prefix + "model.layers.0.mlp.experts.gate_up_proj"
    )):
        # gpt-oss layout: stacked expert tensors with INTERLEAVED
        # gate/up columns (HF GptOssExperts: gate = [..., ::2]),
        # per-expert biases, and a biased router.  The published 120b/20b
        # checkpoints ship the expert mats as MXFP4 blocks+scales —
        # dequantize-on-load to `dtype` (models/mxfp4.py, bit-equal to
        # HF convert_moe_packed_tensors)
        def estack(name):
            return np.stack([
                r.get(prefix + f"model.layers.{i}.mlp.{name}")
                for i in range(L)
            ])

        def estack_proj(proj):
            """[L, E, Z, X] expert mats in the bf16-export layout,
            dequantizing per layer when the checkpoint is MXFP4 (a
            full-checkpoint f32 intermediate would be ~10x the 120b's
            bf16 footprint)."""
            if not mxfp4:
                return estack(f"experts.{proj}")
            from .mxfp4 import dequant_mxfp4

            np_dtype = jnp.dtype(dtype).type
            return np.stack([
                dequant_mxfp4(
                    r.get(prefix + f"model.layers.{i}.mlp.experts."
                                   f"{proj}_blocks"),
                    r.get(prefix + f"model.layers.{i}.mlp.experts."
                                   f"{proj}_scales"),
                ).astype(np_dtype)
                for i in range(L)
            ])

        gu = estack_proj("gate_up_proj")  # [L, E, h, 2f]
        gub = estack("experts.gate_up_proj_bias")  # [L, E, 2f]
        layers.update(
            {
                "router": jnp.asarray(
                    estack("router.weight").transpose(0, 2, 1), dtype
                ),  # [L, E, h] → [L, h, E]
                "router_b": jnp.asarray(estack("router.bias"), dtype),
                "w_gate": jnp.asarray(gu[..., ::2], dtype),
                "w_up": jnp.asarray(gu[..., 1::2], dtype),
                "b_gate": jnp.asarray(gub[..., ::2], dtype),
                "b_up": jnp.asarray(gub[..., 1::2], dtype),
                "w_down": jnp.asarray(estack_proj("down_proj"), dtype),
                "b_down": jnp.asarray(
                    estack("experts.down_proj_bias"), dtype
                ),
            }
        )
    elif cfg.is_moe:
        E = cfg.num_experts
        # Mixtral names its router `gate` and its expert matrices w1/w2/w3;
        # SmallThinker `primary_router` and gate/down/up
        router, (gate, down, up) = (
            ("primary_router", ("gate", "down", "up"))
            if cfg.model_type == "smallthinker"
            else ("gate", ("w1", "w2", "w3")))

        def stack_experts(sub: str) -> jnp.ndarray:
            out = []
            for i in range(L):
                per = [
                    r.get(
                        prefix + f"model.layers.{i}.block_sparse_moe.experts.{e}.{sub}.weight"
                    ).T
                    for e in range(E)
                ]
                out.append(np.stack(per))
            return jnp.asarray(np.stack(out), dtype)

        layers.update(
            {
                "router": stack(p + f"block_sparse_moe.{router}.weight"),
                "w_gate": stack_experts(gate),
                "w_down": stack_experts(down),
                "w_up": stack_experts(up),
            }
        )
    else:
        layers.update(
            {
                "w_gate": stack(p + "mlp.gate_proj.weight"),
                "w_up": stack(p + "mlp.up_proj.weight"),
                "w_down": stack(p + "mlp.down_proj.weight"),
            }
        )
    params = {
        "embed": jnp.asarray(r.get(prefix + "model.embed_tokens.weight"), dtype),
        "final_norm": jnp.asarray(r.get(prefix + "model.norm.weight"), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        if r.has(prefix + "lm_head.weight"):
            params["lm_head"] = jnp.asarray(r.get(prefix + "lm_head.weight").T, dtype)
        else:
            params["lm_head"] = params["embed"].T
    return params


def _load_nemotron_h(r: "_ShardReader", cfg: ModelConfig, dtype,
                     prefix: str = ""):
    """nemotron_h tensor names (`backbone.layers.{i}.mixer.*`, one mixer and
    one `norm` a layer) -> `models.hybrid.init_params`'s three stacks by
    kind.  Only the experts HELD here are read, under their global indices.
    The step-size bias, `A_log`, `D` and the router's choosing bias are kept
    float32 whatever the file's dtype; the convolution's [conv_dim, 1, K]
    weight is stored tap-major [K, conv_dim]."""
    pat = cfg.layer_pattern
    ids = {kind: [i for i, c in enumerate(pat) if c == kind] for kind in "M*E"}

    def get(i, name):
        return r.get(prefix + f"backbone.layers.{i}.{name}")

    def stack(kind, name, fn=lambda w: w.T, dt=dtype):
        return jnp.asarray(np.stack([fn(get(i, name)) for i in ids[kind]]),
                           dt)

    def same(w):
        return w

    def f32(kind, name):
        return stack(kind, name, lambda w: w.astype(np.float32), jnp.float32)

    held = range(cfg.first_expert, cfg.first_expert + cfg.num_experts)

    def experts(proj):
        return jnp.asarray(np.stack([np.stack([
            get(i, f"mixer.experts.{e}.{proj}_proj.weight").T for e in held])
            for i in ids["E"]]), dtype)

    params = {
        "embed": jnp.asarray(r.get(prefix + "backbone.embeddings.weight"),
                             dtype),
        "final_norm": jnp.asarray(r.get(prefix + "backbone.norm_f.weight"),
                                  dtype),
        "lm_head": jnp.asarray(r.get(prefix + "lm_head.weight").T, dtype),
        "ssm_layers": {
            "norm": stack("M", "norm.weight", same),
            "in_proj": stack("M", "mixer.in_proj.weight"),
            "conv_w": stack("M", "mixer.conv1d.weight",
                            lambda w: w.reshape(w.shape[0], -1).T),
            "conv_b": stack("M", "mixer.conv1d.bias", same),
            "dt_bias": f32("M", "mixer.dt_bias"),
            "A_log": f32("M", "mixer.A_log"),
            "D": f32("M", "mixer.D"),
            "gate_norm": stack("M", "mixer.norm.weight", same),
            "out_proj": stack("M", "mixer.out_proj.weight"),
        },
        "attn_layers": {
            "norm": stack("*", "norm.weight", same),
            "wq": stack("*", "mixer.q_proj.weight"),
            "wk": stack("*", "mixer.k_proj.weight"),
            "wv": stack("*", "mixer.v_proj.weight"),
            "wo": stack("*", "mixer.o_proj.weight"),
        },
        "moe_layers": {
            "norm": stack("E", "norm.weight", same),
            "router": stack("E", "mixer.gate.weight"),
            "router_bias": f32("E", "mixer.gate.e_score_correction_bias"),
            "w_up": experts("up"), "w_down": experts("down"),
            "ws_up": stack("E", "mixer.shared_experts.up_proj.weight"),
            "ws_down": stack("E", "mixer.shared_experts.down_proj.weight"),
        },
    }
    return params


def _load_falcon_h1(r: "_ShardReader", cfg: ModelConfig, dtype,
                    prefix: str = ""):
    """falcon_h1 tensor names (`model.layers.{i}.{input_layernorm, mamba.*,
    self_attn.*, pre_ff_layernorm, feed_forward.*}`) -> `models.hybrid.
    init_params`'s ONE stack of layers that are both mixers and a dense
    feed-forward.  The step-size bias, `A_log` and `D` are kept float32
    whatever the file's dtype; the convolution's [conv_dim, 1, K] weight is
    stored tap-major [K, conv_dim].  No multiplier touches a weight."""
    def stack(name, fn=lambda w: w.T, dt=dtype):
        return jnp.asarray(np.stack([fn(r.get(
            prefix + f"model.layers.{i}.{name}"))
            for i in range(cfg.num_hidden_layers)]), dt)

    def same(w):
        return w

    def f32(name):
        return stack(name, lambda w: w.astype(np.float32), jnp.float32)

    params = {
        "embed": jnp.asarray(r.get(prefix + "model.embed_tokens.weight"),
                             dtype),
        "final_norm": jnp.asarray(
            r.get(prefix + "model.final_layernorm.weight"), dtype),
        "par_layers": {
            "norm": stack("input_layernorm.weight", same),
            "in_proj": stack("mamba.in_proj.weight"),
            "conv_w": stack("mamba.conv1d.weight",
                            lambda w: w.reshape(w.shape[0], -1).T),
            "conv_b": stack("mamba.conv1d.bias", same),
            "dt_bias": f32("mamba.dt_bias"),
            "A_log": f32("mamba.A_log"),
            "D": f32("mamba.D"),
            "gate_norm": stack("mamba.norm.weight", same),
            "out_proj": stack("mamba.out_proj.weight"),
            "wq": stack("self_attn.q_proj.weight"),
            "wk": stack("self_attn.k_proj.weight"),
            "wv": stack("self_attn.v_proj.weight"),
            "wo": stack("self_attn.o_proj.weight"),
            "mlp_norm": stack("pre_ff_layernorm.weight", same),
            "w_gate": stack("feed_forward.gate_proj.weight"),
            "w_up": stack("feed_forward.up_proj.weight"),
            "w_down": stack("feed_forward.down_proj.weight"),
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(r.get(prefix + "lm_head.weight").T,
                                        dtype)
    return params


def _load_laguna(r: "_ShardReader", cfg: ModelConfig, dtype, prefix: str = ""):
    """laguna tensor names -> `models.laguna.init_params`'s stacks, one a
    kind of layer, each layer at its own head count.  The published config
    carries no tensor names: these are the softmax-router lineage's
    (`self_attn.{q,k,v,o}_proj`, `mlp.gate` the router, `mlp.experts.{e}.
    {gate,up,down}_proj`, `mlp.shared_expert.*`, a dense layer's `mlp.
    {gate,up,down}_proj`), and `self_attn.g_proj` [heads, hidden] for the
    output gate is a name set here."""
    from .laguna import stacks_of

    def stack_fn(ids):
        def stack(name, fn=lambda w: w.T):
            return jnp.asarray(np.stack([fn(r.get(
                prefix + f"model.layers.{i}.{name}")) for i in ids]), dtype)

        return stack

    def ffn(stack, at, keys=("w_gate", "w_up", "w_down")):
        return {key: stack(f"{at}{proj}_proj.weight")
                for key, proj in zip(keys, ("gate", "up", "down"))}

    params = {
        "embed": jnp.asarray(r.get(prefix + "model.embed_tokens.weight"),
                             dtype),
        "final_norm": jnp.asarray(r.get(prefix + "model.norm.weight"), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(
            r.get(prefix + "lm_head.weight").T, dtype)
    for name, ((_, mlp, _), ids) in stacks_of(cfg).items():
        stack = stack_fn(ids)
        layer = {
            "attn_norm": stack("input_layernorm.weight", lambda w: w),
            "mlp_norm": stack("post_attention_layernorm.weight", lambda w: w),
            "wq": stack("self_attn.q_proj.weight"),
            "wk": stack("self_attn.k_proj.weight"),
            "wv": stack("self_attn.v_proj.weight"),
            "wo": stack("self_attn.o_proj.weight"),
            "w_head_gate": stack("self_attn.g_proj.weight"),
        }
        if mlp == "dense":
            layer.update(ffn(stack, "mlp."))
        else:
            layer["router"] = stack("mlp.gate.weight")
            for key, proj in (("w_gate", "gate"), ("w_up", "up"),
                              ("w_down", "down")):
                layer[key] = jnp.stack([jnp.asarray(np.stack([r.get(
                    prefix + f"model.layers.{i}.mlp.experts.{e}.{proj}_proj."
                    "weight").T for e in range(cfg.num_experts)]), dtype)
                    for i in ids])
            if cfg.n_shared_experts:
                layer.update(ffn(stack, "mlp.shared_expert.",
                                 ("ws_gate", "ws_up", "ws_down")))
        params[name] = layer
    return params


def _load_lfm2_moe(r: "_ShardReader", cfg: ModelConfig, dtype,
                   prefix: str = ""):
    """lfm2_moe tensor names -> `models.laguna.init_params`'s stacks, one a
    kind of layer (conv + dense, attention + experts, conv + experts).  The
    names are the dense sibling's (transformers `models/lfm2`): `conv.
    {in_proj, conv, out_proj}` (the taps [hidden, 1, K], tap j the input K-1-j
    positions back: stored here [K, hidden]), `self_attn.{q,k,v,out}_proj`,
    `self_attn.{q,k}_layernorm`, `operator_norm`, `ffn_norm`, a dense
    layer's `feed_forward.{w1,w3,w2}` (gate, up, down), `model.
    embedding_norm` (the FINAL norm); and for the expert block, which that
    package does not have, the family's published names as known:
    `feed_forward.gate` (the router), `feed_forward.expert_bias` [experts]
    (kept float32: it only chooses), `feed_forward.experts.{e}.{w1,w3,w2}`."""
    from .laguna import stacks_of

    def stack_fn(ids):
        def stack(name, fn=lambda w: w.T, dt=dtype):
            return jnp.asarray(np.stack([fn(r.get(
                prefix + f"model.layers.{i}.{name}")) for i in ids]), dt)

        return stack

    ffn = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
    params = {
        "embed": jnp.asarray(r.get(prefix + "model.embed_tokens.weight"),
                             dtype),
        "final_norm": jnp.asarray(
            r.get(prefix + "model.embedding_norm.weight"), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(
            r.get(prefix + "lm_head.weight").T, dtype)
    for name, ((kind, mlp, _), ids) in stacks_of(cfg).items():
        stack = stack_fn(ids)
        layer = {"attn_norm": stack("operator_norm.weight", lambda w: w),
                 "mlp_norm": stack("ffn_norm.weight", lambda w: w)}
        if kind == "conv":
            layer.update({
                "in_proj": stack("conv.in_proj.weight"),
                "conv_w": stack("conv.conv.weight", lambda w: w[:, 0, :].T),
                "out_proj": stack("conv.out_proj.weight")})
        else:
            layer.update({
                "wq": stack("self_attn.q_proj.weight"),
                "wk": stack("self_attn.k_proj.weight"),
                "wv": stack("self_attn.v_proj.weight"),
                "wo": stack("self_attn.out_proj.weight"),
                "q_head_norm": stack("self_attn.q_layernorm.weight",
                                     lambda w: w),
                "k_head_norm": stack("self_attn.k_layernorm.weight",
                                     lambda w: w)})
        if mlp == "dense":
            layer.update({key: stack(f"feed_forward.{w}.weight")
                          for key, w in ffn})
        else:
            layer["router"] = stack("feed_forward.gate.weight")
            layer["router_bias"] = stack(
                "feed_forward.expert_bias",
                lambda w: w.astype(np.float32), jnp.float32)
            for key, w in ffn:
                layer[key] = jnp.stack([jnp.asarray(np.stack([r.get(
                    prefix + f"model.layers.{i}.feed_forward.experts.{e}."
                    f"{w}.weight").T for e in range(cfg.num_experts)]),
                    dtype) for i in ids])
        params[name] = layer
    return params


def _load_phi4flash(r: "_ShardReader", cfg: ModelConfig, dtype,
                    prefix: str = ""):
    """phi4flash tensor names (`model.layers.{l}.attn.*`, whatever the
    layer's mixer, `.mlp.{fc1, fc2}` and two LayerNorms a layer) ->
    `models.phi4flash.init_params`'s four stacks by kind.  `A_log` is
    stored state-major [N, d] (the channels under the lanes), float32, as
    the step-size bias, `D` and the four lambda vectors are whatever the
    file's dtype; the convolution's [d, 1, K] weight tap-major [K, d]."""
    pat = cfg.layer_pattern

    def stacks(kinds):
        ids = [i for i, c in enumerate(pat) if c in kinds]

        def stack(name, fn=lambda w: w.T, dt=dtype):
            return jnp.asarray(np.stack([fn(r.get(
                prefix + f"model.layers.{i}.{name}")) for i in ids]), dt)

        return stack

    def same(w):
        return w

    def f32(stack, name, fn=same):
        return stack(name, lambda w: fn(w).astype(np.float32), jnp.float32)

    def block(stack):  # the two LayerNorms and the feed-forward
        return {"norm": stack("input_layernorm.weight", same),
                "norm_b": stack("input_layernorm.bias", same),
                "mlp_norm": stack("post_attention_layernorm.weight", same),
                "mlp_norm_b": stack("post_attention_layernorm.bias", same),
                "w_gateup": stack("mlp.fc1.weight"),
                "w_down": stack("mlp.fc2.weight")}

    def diff(stack):
        inner = "attn.inner_cross_attn."
        return {"lambda": jnp.stack([f32(stack, inner + n) for n in (
                    "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")], 1),
                "subln": stack(inner + "subln.weight", same),
                "wo": stack("attn.out_proj.weight"),
                "bo": stack("attn.out_proj.bias", same)}

    S, A, G, C = (stacks(k) for k in ("S", "WF", "G", "C"))
    return {
        "embed": jnp.asarray(r.get(prefix + "model.embed_tokens.weight"),
                             dtype),
        "final_norm": jnp.asarray(
            r.get(prefix + "model.final_layernorm.weight"), dtype),
        "final_norm_bias": jnp.asarray(
            r.get(prefix + "model.final_layernorm.bias"), dtype),
        "ssm_layers": {
            **block(S),
            "in_proj": S("attn.in_proj.weight"),
            "conv_w": S("attn.conv1d.weight",
                        lambda w: w.reshape(w.shape[0], -1).T),
            "conv_b": S("attn.conv1d.bias", same),
            "x_proj": S("attn.x_proj.weight"),
            "dt_proj": S("attn.dt_proj.weight"),
            "dt_bias": f32(S, "attn.dt_proj.bias"),
            "A_log": f32(S, "attn.A_log", lambda w: w.T),
            "D": f32(S, "attn.D"),
            "out_proj": S("attn.out_proj.weight"),
        },
        "attn_layers": {**block(A), **diff(A),
                        "wqkv": A("attn.Wqkv.weight"),
                        "bqkv": A("attn.Wqkv.bias", same)},
        "gmu_layers": {**block(G), "w_in": G("attn.in_proj.weight"),
                       "w_out": G("attn.out_proj.weight")},
        "cross_layers": {**block(C), **diff(C),
                         "wq": C("attn.Wqkv.weight"),
                         "bq": C("attn.Wqkv.bias", same)},
    }


def _load_deepseek_v3(r: "_ShardReader", cfg: ModelConfig, dtype,
                      prefix: str = ""):
    """deepseek_v3 tensor names (and xing4_0's, which adds its mixers'
    to them) -> `llama.init_params`'s latent layout: the
    leading dense layers as the stack `dense_layers`, the expert layers as
    `layers`.  Only the experts HELD here are read, under their global
    indices (`cfg.first_expert` on).  The prediction module's tensors
    (`model.layers.{num_hidden_layers}...`) draft tokens and are not read.

    Two rewrites, both exact: the rotary columns of `q_b_proj` and
    `kv_a_proj_with_mqa` are stored as interleaved pairs (2i, 2i + 1) and
    are permuted to halves (i, i + pe/2), the layout `ops.apply_rope`
    rotates (q.k is the same sum in any common order); `kv_b_proj` is cut
    into each head's `w_uk` [nh, nope, rank] and `w_uv` [nh, rank, vd], the
    forms the absorbed attention multiplies by."""
    L, k, nh = cfg.num_hidden_layers, cfg.first_k_dense, cfg.num_attention_heads
    r_, pe = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    halves = np.concatenate([np.arange(0, pe, 2), np.arange(1, pe, 2)])

    def get(i, name):
        return r.get(prefix + f"model.layers.{i}.{name}")

    def stack(ids, name, fn=lambda w: w.T, dt=dtype):
        return jnp.asarray(np.stack([fn(get(i, name)) for i in ids]), dt)

    def q_b(w):  # [nh * (nope + pe), qr] -> [qr, nh * (nope + pe)]
        w = w.reshape(nh, nope + pe, -1)
        w = np.concatenate([w[:, :nope], w[:, nope:][:, halves]], axis=1)
        return w.reshape(nh * (nope + pe), -1).T

    def kv_a(w):  # [rank + pe, h] -> [h, rank + pe]
        return np.concatenate([w[:r_], w[r_:][halves]], axis=0).T

    def mixer(key, read):
        """One hyper-connection mixer (xing4_0) under the keys `key`_phi,
        _scale, _base, float32 whatever the file's dtype.  `read(part, to)`
        reads its tensor `part` through `to`: `fn` [M, n * hidden] (the
        streams' concatenation in, as a Linear stores it) -> phi [n,
        hidden, M]; `scale` and `base` as they are."""
        def f32(w):
            return np.asarray(w, np.float32)

        return {key + "_phi": read("fn", lambda w: f32(w).T.reshape(
                    cfg.hc_mult, cfg.hidden_size, -1)),
                key + "_scale": read("scale", f32),
                key + "_base": read("base", f32)}

    def mixers(ids):
        """The two mixers a layer, `hc_attn` and `hc_ffn`."""
        out = {}
        if cfg.hc_mult:
            for key, half in (("hc_attn", "hc_attn."), ("hc_mlp", "hc_ffn.")):
                out.update(mixer(key, lambda part, to: stack(
                    ids, half + part, to, jnp.float32)))
        return out

    def attn(ids):
        a = "self_attn."
        return {
            **mixers(ids),
            "attn_norm": stack(ids, "input_layernorm.weight", lambda w: w),
            "mlp_norm": stack(ids, "post_attention_layernorm.weight",
                              lambda w: w),
            "wq_a": stack(ids, a + "q_a_proj.weight"),
            "q_norm": stack(ids, a + "q_a_layernorm.weight", lambda w: w),
            "wq_b": stack(ids, a + "q_b_proj.weight", q_b),
            "wkv_a": stack(ids, a + "kv_a_proj_with_mqa.weight", kv_a),
            "kv_norm": stack(ids, a + "kv_a_layernorm.weight", lambda w: w),
            "w_uk": stack(ids, a + "kv_b_proj.weight", lambda w: w.reshape(
                nh, nope + vd, r_)[:, :nope]),
            "w_uv": stack(ids, a + "kv_b_proj.weight", lambda w: w.reshape(
                nh, nope + vd, r_)[:, nope:].transpose(0, 2, 1)),
            "wo": stack(ids, a + "o_proj.weight"),
        }

    def ffn(ids, at, keys=("w_gate", "w_up", "w_down")):
        return {key: stack(ids, f"{at}{proj}_proj.weight")
                for key, proj in zip(keys, ("gate", "up", "down"))}

    params = {
        "embed": jnp.asarray(r.get(prefix + "model.embed_tokens.weight"),
                             dtype),
        "final_norm": jnp.asarray(r.get(prefix + "model.norm.weight"), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(
            r.get(prefix + "lm_head.weight").T, dtype)
    if cfg.hc_mult:  # the head's reduction of the streams
        params.update(mixer("hc_head", lambda part, to: jnp.asarray(
            to(r.get(prefix + "model.hc_head." + part)))))
    if not cfg.is_moe:
        ids = range(L)
        params["layers"] = {**attn(ids), **ffn(ids, "mlp.")}
        return params
    if k:
        params["dense_layers"] = {**attn(range(k)), **ffn(range(k), "mlp.")}
    ids = range(k, L)
    held = range(cfg.first_expert, cfg.first_expert + cfg.num_experts)

    def experts(proj):
        return jnp.asarray(np.stack([np.stack([
            get(i, f"mlp.experts.{e}.{proj}_proj.weight").T for e in held])
            for i in ids]), dtype)

    layers = {
        **attn(ids),
        "router": stack(ids, "mlp.gate.weight"),
        # the choosing bias joins float32 scores: kept float32
        "router_bias": stack(ids, "mlp.gate.e_score_correction_bias",
                             lambda w: w.astype(np.float32), jnp.float32),
        "w_gate": experts("gate"), "w_up": experts("up"),
        "w_down": experts("down"),
    }
    if cfg.n_shared_experts:
        layers.update(ffn(ids, "mlp.shared_experts.",
                          ("ws_gate", "ws_up", "ws_down")))
    params["layers"] = layers
    return params
