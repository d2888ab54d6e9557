"""Tensor names and shapes of the LFM2-MoE family (Liquid AI LFM2-24B-A2B:
`model_type` "lfm2_moe"): by `layer_types` a gated short convolution
(`conv.in_proj` [3 hidden, hidden], `conv.conv` [hidden, 1, conv_L_cache],
`conv.out_proj`) or GQA attention without biases (`self_attn.{q,k,v,out}_proj`
and a norm weight a head value, `self_attn.{q,k}_layernorm` [head_dim]); the
first `num_dense_layers` layers a dense SwiGLU `feed_forward.{w1,w3,w2}`
(gate, up, down) of `intermediate_size`, the others `feed_forward.gate` (the
router), `feed_forward.expert_bias` [experts] and `feed_forward.experts.{e}.
{w1,w3,w2}` of `moe_intermediate_size`; `operator_norm` and `ffn_norm` a
layer, `model.embedding_norm` (the FINAL norm), and no `lm_head`: the head is
the embedding.  `model` is the configuration's `model` object (config.json
keys).

The conv and attention names are the dense sibling's (transformers 4.57.6,
`models/lfm2/modeling_lfm2.py`); the expert block's are ASSUMED (that package
has no `lfm2_moe`): the configuration's file says so.

Yields (name, shape, kind); kind is "weight" (random) or "ones".  ONES: the
norm scales (`operator_norm`, `ffn_norm`, `embedding_norm`, and the two head
norms, whose mechanism is the normalisation itself: `no_qk_norm` changes
every score whatever the weight).  WEIGHTS: everything else, the taps among
them (as ones they would be symmetric and `taps_reversed` would say nothing)
and `expert_bias` (the published checkpoints hold a trained one; drawn as a
weight, std about 0.014 against sigmoid scores that lie 0.3-0.7 apart over a
layer's 64 experts, it changes a token's fourth expert now and then, and
`bias_in_weights` moves every weight by about 0.014 / 2)."""


def tensors(model):
    H, I, F = (model["hidden_size"], model["intermediate_size"],
               model["moe_intermediate_size"])
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or H // nq
    K, E = model["conv_L_cache"], model["num_experts"]
    yield "model.embed_tokens.weight", (model["vocab_size"], H), "weight"
    for i, kind in enumerate(model["layer_types"]):
        p = f"model.layers.{i}."
        yield p + "operator_norm.weight", (H,), "ones"
        if kind == "conv":
            yield p + "conv.in_proj.weight", (3 * H, H), "weight"
            yield p + "conv.conv.weight", (H, 1, K), "weight"
            yield p + "conv.out_proj.weight", (H, H), "weight"
        else:
            a = p + "self_attn."
            yield a + "q_proj.weight", (nq * hd, H), "weight"
            yield a + "k_proj.weight", (nkv * hd, H), "weight"
            yield a + "v_proj.weight", (nkv * hd, H), "weight"
            yield a + "out_proj.weight", (H, nq * hd), "weight"
            yield a + "q_layernorm.weight", (hd,), "ones"
            yield a + "k_layernorm.weight", (hd,), "ones"
        yield p + "ffn_norm.weight", (H,), "ones"
        f = p + "feed_forward."
        if i < model["num_dense_layers"]:
            yield f + "w1.weight", (I, H), "weight"
            yield f + "w3.weight", (I, H), "weight"
            yield f + "w2.weight", (H, I), "weight"
            continue
        yield f + "gate.weight", (E, H), "weight"
        yield f + "expert_bias", (E,), "weight"
        for e in range(E):
            x = f + f"experts.{e}."
            yield x + "w1.weight", (F, H), "weight"
            yield x + "w3.weight", (F, H), "weight"
            yield x + "w2.weight", (H, F), "weight"
    yield "model.embedding_norm.weight", (H,), "ones"
