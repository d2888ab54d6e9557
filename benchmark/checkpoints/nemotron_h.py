"""Tensor names and shapes of the Nemotron-H family (NVIDIA-Nemotron-3-Nano:
`model_type` "nemotron_h") as its Hugging Face checkpoints carry them: a
layer is `backbone.layers.{i}.norm` and ONE `mixer`, which
`hybrid_override_pattern` names: "M" a Mamba-2 mixer (`in_proj`, `conv1d`
with its bias, `dt_bias`, `A_log`, `D`, the gated `norm`, `out_proj`), "*"
attention (`q_proj`, `k_proj`, `v_proj`, `o_proj`, no biases), "E" an expert
feed-forward (`gate.{weight, e_score_correction_bias}` over ALL the layer's
experts, `experts.{e}.{up, down}_proj` and `shared_experts.{up, down}_proj`:
no gate matrix anywhere).  `model` is the configuration's `model` object
(config.json keys).  The names are ASSUMED from the family's published
modelling code (the catalog row carries no tensor names); the
configuration's file says so.

The chip's share, as `checkpoints/deepseek_v3.py`: `n_routed_experts` counts
the experts HELD, rank `ep_rank` (default 0) of `ep_size`; only those are
written, under their GLOBAL indices; the router and its bias keep the full
width `n_routed_experts * ep_size`.

Yields (name, shape, kind); kind is "weight" (random) or "ones" (norm
scales).  `dt_bias`, `A_log`, `D`, the convolution and the correction bias
get the "weight" draw (`benchmark/lib/checkpoint.py` has two kinds): so
`A_log` and `dt_bias` come out near 0, A near -1 and a step size near 0.7:
a state that forgets in about ten tokens (the configuration's
`assumed.weights`)."""


def held_experts(model):
    n = model["n_routed_experts"]
    first = model.get("ep_rank", 0) * n
    return range(first, first + n)


def router_width(model):
    return model["n_routed_experts"] * model.get("ep_size", 1)


def tensors(model):
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    S = model["moe_shared_expert_intermediate_size"]
    nh, K = model["mamba_num_heads"], model["conv_kernel"]
    d = nh * model["mamba_head_dim"]
    cd = d + 2 * model["n_groups"] * model["ssm_state_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    W = router_width(model)
    yield "backbone.embeddings.weight", (model["vocab_size"], H), "weight"
    for i, kind in enumerate(model["hybrid_override_pattern"]):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        yield p + "norm.weight", (H,), "ones"
        if kind == "M":
            yield m + "in_proj.weight", (d + cd + nh, H), "weight"
            yield m + "conv1d.weight", (cd, 1, K), "weight"
            yield m + "conv1d.bias", (cd,), "weight"
            yield m + "dt_bias", (nh,), "weight"
            yield m + "A_log", (nh,), "weight"
            yield m + "D", (nh,), "weight"
            yield m + "norm.weight", (d,), "ones"
            yield m + "out_proj.weight", (H, d), "weight"
        elif kind == "*":
            yield m + "q_proj.weight", (q, H), "weight"
            yield m + "k_proj.weight", (kv, H), "weight"
            yield m + "v_proj.weight", (kv, H), "weight"
            yield m + "o_proj.weight", (H, q), "weight"
        else:
            yield m + "gate.weight", (W, H), "weight"
            yield m + "gate.e_score_correction_bias", (W,), "weight"
            for e in held_experts(model):
                x = m + f"experts.{e}."
                yield x + "up_proj.weight", (F, H), "weight"
                yield x + "down_proj.weight", (H, F), "weight"
            x = m + "shared_experts."
            yield x + "up_proj.weight", (S, H), "weight"
            yield x + "down_proj.weight", (H, S), "weight"
    yield "backbone.norm_f.weight", (H,), "ones"
    yield "lm_head.weight", (model["vocab_size"], H), "weight"
