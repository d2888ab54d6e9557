"""Tensor names and shapes of the Laguna family (poolside Laguna-XS.2:
`model_type` "laguna"): GQA attention without biases whose query heads
differ by layer (`num_attention_heads_per_layer`: `q_proj` and `o_proj` of a
48-head layer are narrower than a 64-head layer's, and nothing is padded),
a per-head output gate (`self_attn.g_proj` [heads, hidden]), and a
feed-forward by `mlp_layer_types`: a dense `mlp.{gate,up,down}_proj`, or
`mlp.gate` (the router), `mlp.experts.{e}.{gate,up,down}_proj` and
`mlp.shared_expert.{gate,up,down}_proj`.  `model` is the configuration's
`model` object (config.json keys).

The names are ASSUMED (the catalog row carries none): the softmax-router
lineage's for attention, router, experts and the shared expert, and
`g_proj` is a name set here; the configuration's file says so.

Yields (name, shape, kind); kind is "weight" (random) or "ones".  ONES: the
three norm scales alone (`input_layernorm`, `post_attention_layernorm`,
`model.norm`), as every family here.  The gate is a "weight": under the one
draw (std about 0.014) its logit over a unit-RMS normed input of 2,048 values
has a std of about 0.63, so a head's gate lies around 0.35-0.65 and differs
by token and head: leaving it out about doubles a layer's attention output,
which `correct` sees (the `no_gate` control; PERF.md, PR 52).  Drawn as
`ones` it would be a constant sigmoid(sum of the normed input), the same for
every head: the mechanism would be there and say nothing."""


def tensors(model):
    H, I, F = (model["hidden_size"], model["intermediate_size"],
               model["moe_intermediate_size"])
    S = model["shared_expert_intermediate_size"]
    hd = model["head_dim"]
    kv = model["num_key_value_heads"] * hd
    yield "model.embed_tokens.weight", (model["vocab_size"], H), "weight"
    for i in range(model["num_hidden_layers"]):
        p = f"model.layers.{i}."
        nh = model["num_attention_heads_per_layer"][i]
        yield p + "self_attn.q_proj.weight", (nh * hd, H), "weight"
        yield p + "self_attn.k_proj.weight", (kv, H), "weight"
        yield p + "self_attn.v_proj.weight", (kv, H), "weight"
        yield p + "self_attn.o_proj.weight", (H, nh * hd), "weight"
        yield p + "self_attn.g_proj.weight", (nh, H), "weight"
        if model["mlp_layer_types"][i] == "dense":
            yield p + "mlp.gate_proj.weight", (I, H), "weight"
            yield p + "mlp.up_proj.weight", (I, H), "weight"
            yield p + "mlp.down_proj.weight", (H, I), "weight"
        else:
            yield p + "mlp.gate.weight", (model["num_experts"], H), "weight"
            for e in range(model["num_experts"]):
                x = p + f"mlp.experts.{e}."
                yield x + "gate_proj.weight", (F, H), "weight"
                yield x + "up_proj.weight", (F, H), "weight"
                yield x + "down_proj.weight", (H, F), "weight"
            x = p + "mlp.shared_expert."
            yield x + "gate_proj.weight", (S, H), "weight"
            yield x + "up_proj.weight", (S, H), "weight"
            yield x + "down_proj.weight", (H, S), "weight"
        yield p + "input_layernorm.weight", (H,), "ones"
        yield p + "post_attention_layernorm.weight", (H,), "ones"
    yield "model.norm.weight", (H,), "ones"
    yield "lm_head.weight", (model["vocab_size"], H), "weight"
