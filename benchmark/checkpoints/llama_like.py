"""Tensor names and shapes of the Llama-like decoder family as Hugging Face
checkpoints carry them: Llama, Mistral, and Qwen2 (which adds q/k/v biases).
`model` is the configuration's `model` object (config.json keys).

Yields (name, shape, kind); kind is "weight" (random), "bias" (random) or
"ones" (norm scales)."""


def head_dim(model):
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])


def has_qkv_bias(model):
    # HF Qwen2 hard-codes q/k/v biases; Llama/Mistral carry `attention_bias`
    return model.get("attention_bias", model["model_type"] == "qwen2")


def tensors(model):
    H, I = model["hidden_size"], model["intermediate_size"]
    q = model["num_attention_heads"] * head_dim(model)
    kv = model["num_key_value_heads"] * head_dim(model)
    yield "model.embed_tokens.weight", (model["vocab_size"], H), "weight"
    for i in range(model["num_hidden_layers"]):
        p = f"model.layers.{i}."
        yield p + "self_attn.q_proj.weight", (q, H), "weight"
        yield p + "self_attn.k_proj.weight", (kv, H), "weight"
        yield p + "self_attn.v_proj.weight", (kv, H), "weight"
        yield p + "self_attn.o_proj.weight", (H, q), "weight"
        if has_qkv_bias(model):
            yield p + "self_attn.q_proj.bias", (q,), "bias"
            yield p + "self_attn.k_proj.bias", (kv,), "bias"
            yield p + "self_attn.v_proj.bias", (kv,), "bias"
        yield p + "mlp.gate_proj.weight", (I, H), "weight"
        yield p + "mlp.up_proj.weight", (I, H), "weight"
        yield p + "mlp.down_proj.weight", (H, I), "weight"
        yield p + "input_layernorm.weight", (H,), "ones"
        yield p + "post_attention_layernorm.weight", (H,), "ones"
    yield "model.norm.weight", (H,), "ones"
    if not model.get("tie_word_embeddings", False):
        yield "lm_head.weight", (model["vocab_size"], H), "weight"
