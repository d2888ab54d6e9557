"""Tensor names and shapes of the deepseek_v3 family (DeepSeek-V3 / R1,
GigaChat3, Kimi-K2: `model_type` "deepseek_v3") as its Hugging Face
checkpoints carry them: latent attention (`self_attn.{q_a_proj,
q_a_layernorm, q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj,
o_proj}`, no biases), `first_k_dense_replace` layers with a dense `mlp.{gate,
up, down}_proj` and then expert layers: `mlp.gate.{weight,
e_score_correction_bias}` (the router over ALL the layer's experts and the
bias it adds for choosing), `mlp.experts.{e}.{gate, up, down}_proj` and
`mlp.shared_experts.{gate, up, down}_proj`.  `model` is the configuration's
`model` object (config.json keys).  The names are ASSUMED from the family's
published modelling code (the catalog row carries no tensor names); the
configuration's file says so.

The chip's share.  `n_routed_experts` counts the experts HELD, rank `ep_rank`
(default 0) of `ep_size`: only those are written, under their GLOBAL indices
`ep_rank * n_routed_experts` on, as a shard of the whole checkpoint would
carry them; the router and its bias keep the full width `n_routed_experts *
ep_size`.  With `ep_size` 1 this is the whole published layout.  The
multi-token-prediction module (`num_nextn_predict_layers`: one more layer
after the last) drafts tokens and adds nothing to the model's own logits: it
is not written, as the family's published inference code does not load it.

Yields (name, shape, kind); kind is "weight" (random) or "ones" (norm
scales).  The correction bias gets the "weight" draw."""


def held_experts(model):
    n = model["n_routed_experts"]
    first = model.get("ep_rank", 0) * n
    return range(first, first + n)


def router_width(model):
    return model["n_routed_experts"] * model.get("ep_size", 1)


def tensors(model):
    H, I, F = (model["hidden_size"], model["intermediate_size"],
               model["moe_intermediate_size"])
    nh, qr, r = (model["num_attention_heads"], model["q_lora_rank"],
                 model["kv_lora_rank"])
    nope, pe, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    W = router_width(model)
    yield "model.embed_tokens.weight", (model["vocab_size"], H), "weight"
    for i in range(model["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        yield a + "q_a_proj.weight", (qr, H), "weight"
        yield a + "q_a_layernorm.weight", (qr,), "ones"
        yield a + "q_b_proj.weight", (nh * (nope + pe), qr), "weight"
        yield a + "kv_a_proj_with_mqa.weight", (r + pe, H), "weight"
        yield a + "kv_a_layernorm.weight", (r,), "ones"
        yield a + "kv_b_proj.weight", (nh * (nope + vd), r), "weight"
        yield a + "o_proj.weight", (H, nh * vd), "weight"
        if i < model["first_k_dense_replace"]:
            yield p + "mlp.gate_proj.weight", (I, H), "weight"
            yield p + "mlp.up_proj.weight", (I, H), "weight"
            yield p + "mlp.down_proj.weight", (H, I), "weight"
        else:
            yield p + "mlp.gate.weight", (W, H), "weight"
            yield p + "mlp.gate.e_score_correction_bias", (W,), "weight"
            for e in held_experts(model):
                x = p + f"mlp.experts.{e}."
                yield x + "gate_proj.weight", (F, H), "weight"
                yield x + "up_proj.weight", (F, H), "weight"
                yield x + "down_proj.weight", (H, F), "weight"
            S = F * model["n_shared_experts"]
            x = p + "mlp.shared_experts."
            yield x + "gate_proj.weight", (S, H), "weight"
            yield x + "up_proj.weight", (S, H), "weight"
            yield x + "down_proj.weight", (H, S), "weight"
        yield p + "input_layernorm.weight", (H,), "ones"
        yield p + "post_attention_layernorm.weight", (H,), "ones"
    yield "model.norm.weight", (H,), "ones"
    if not model.get("tie_word_embeddings", False):
        yield "lm_head.weight", (model["vocab_size"], H), "weight"
