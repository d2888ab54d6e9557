"""Bytes and operations a prefill step needs, from shapes alone, for the
LFM2-MoE family (Liquid AI LFM2-24B-A2B): by `layer_types` a gated short
convolution (`in_proj` [hidden, 3 hidden], three taps a channel, `out_proj`
[hidden, hidden]) or GQA attention with a norm a head on q and k; the first
`num_dense_layers` layers a dense SwiGLU of `intermediate_size`, the others
`num_experts` SwiGLU experts of `moe_intermediate_size` behind one router, of
which a token uses `num_experts_per_tok`; no shared expert; a tied head.

Counted.  Operations: for every token of the chunk, two per weight of each
layer's mixer (the conv layer's two projections, or attention's four at 32
query and 8 KV heads of 64) and of its feed-forward: the dense matrices, or
the router and the k experts the token USES (no token multiplies by an expert
it was not routed to, whatever the program does).  Bytes: every layer's mixer
and router weights once a step, the dense layer's matrices, and k expert
matrices an expert layer: what EVERY step must read, since a step's tokens
may all choose the same k.  Under the cell's random weights a chunk's tokens
do pile onto few experts (the configuration's `assumed.routing`), so a step
that touched 40 experts a layer is charged 4.

NOT counted: the convolution's taps and its two gates (3 + 2 operations a
channel a token against 8,192 of the projections: `short_conv_floor_s` leaves
them out too), the norms, attention's QK^T and PV over the context and the
keys and values read (`prefill_attn_floor_s` has them), the windows read and
written (57,344 B a row), the output head (only a prompt's last chunk
samples), the embedding gather, activations, page tables, and the experts
beyond k that a step's tokens happen to choose.  So each figure is a floor,
and a share of it cannot pass 100% by over-counting.

`routed_experts_floor_s` is the count of the routed experts' matmuls alone
from what a step really did (`moe_assignments` and `experts_hit` of its step
event), for `kernel.routed_experts_roofline`; `short_conv_floor_s` that of
the conv layers' mixers alone, for `kernel.short_conv_roofline`."""

BF16 = 2


def head_dim(model):
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def conv_params(model):
    """One conv mixer's two projections: [hidden, 3 hidden] and [hidden,
    hidden] (16.8 M at hidden 2048)."""
    return 4 * model["hidden_size"] ** 2


def conv_layers(model):
    return sum(t == "conv" for t in model["layer_types"])


def expert_params(model):
    """One routed expert's three matrices."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def every_step_params(model):
    """Parameters every step reads and every token multiplies by: each
    layer's mixer, the dense layers' feed-forward, and the router and k
    experts of an expert layer."""
    H, hd = model["hidden_size"], head_dim(model)
    attn = (2 * H * model["num_attention_heads"] * hd
            + 2 * H * model["num_key_value_heads"] * hd)
    total = 0
    for l, kind in enumerate(model["layer_types"]):
        total += conv_params(model) if kind == "conv" else attn
        if l < model["num_dense_layers"]:
            total += 3 * H * model["intermediate_size"]
        else:
            total += (H * model["num_experts"]
                      + model["num_experts_per_tok"] * expert_params(model))
    return total


def _floor(params, peaks, tokens):
    t_mem = BF16 * params / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * params / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    return _floor(every_step_params(model), peaks, tokens)


def short_conv_floor_s(model, peaks, tokens):
    """The least time the conv layers' mixers of one step can take: a conv
    layer's 4 x hidden^2 parameters read once, or two operations a parameter
    a token; the larger, over the conv layers."""
    return _floor(conv_layers(model) * conv_params(model), peaks, tokens)


def routed_experts_floor_s(model, peaks, assignments, experts_hit):
    """The least time the routed experts' matmuls of one step can take: the
    weights of the `experts_hit` experts it touched (summed over its expert
    layers) once, or two operations a weight for each of its `assignments`
    (token, expert) pairs (summed likewise); the larger."""
    expert = expert_params(model)
    t_mem = BF16 * experts_hit * expert / peaks["hbm_bytes_per_s"]
    t_flop = 2 * assignments * expert / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def prefill_attn_floor_s(model, peaks, tokens, ctx):
    """The least time attention over the context can take in one prefill
    step of ONE sequence: a chunk of `tokens` tokens whose last sees `ctx`
    keys, itself among them.  Per ATTENTION layer (the conv layers see no
    context): 4 x head_dim x query heads operations for every key a token can
    SEE (causal: the token at position p sees p keys; QK^T and PV, two
    operations a product), or the `ctx` keys and values read once in bf16;
    the larger, summed over the attention layers."""
    hd, nq, nkv = (head_dim(model), model["num_attention_heads"],
                   model["num_key_value_heads"])
    layers = len(model["layer_types"]) - conv_layers(model)
    prefix = ctx - tokens
    pairs = tokens * prefix + tokens * (tokens + 1) // 2
    t_flop = 4 * hd * nq * pairs / peaks["bf16_flops_per_s"]
    t_mem = 2 * ctx * nkv * hd * BF16 / peaks["hbm_bytes_per_s"]
    return layers * max(t_mem, t_flop), ("memory" if t_mem >= t_flop
                                         else "compute")
