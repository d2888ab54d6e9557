"""Bytes and operations a prefill step needs, from shapes alone, for the
SmallThinker family: GQA attention projections, one router and
`moe_num_primary_experts` ReLU-gated experts of three matrices in every
layer, `moe_num_active_primary_experts` of them used by a token; no dense
feed-forward anywhere.

Counted.  Operations: for every token of the chunk, two per weight of the
attention projections, the router and the k experts the token USES (no token
multiplies by an expert it was not routed to, whatever the program does).
Bytes: every layer's attention and router weights once a step, and k expert
matrices a layer: what EVERY step must read, since a step's tokens may all
choose the same k.  That is no idle caution here: under the cell's random
weights the router reads an un-normalised stream in which a direction all
tokens share grows with depth, and in the deeper layers every token of a
512-token chunk does choose the same experts (`moe_max_load` = the chunk's
tokens; `experts_hit` 210-374 of 768 a step; PERF.md, PR 31).  So a step
that touched 22 experts a layer is charged 6, and a program that reads all
64 (the all-experts matmul) reads a small share of this floor: the share
says how much a dispatch that reads only what was chosen could save.

NOT counted: attention's QK^T and PV (the step events carry no context
length), the keys and values read, the output head (only a prompt's last
chunk samples), the embedding gather, activations, page tables, and the
experts beyond k that a step's tokens happen to choose.  So the figure is a
floor, and a share of it cannot pass 100% by over-counting.

`experts_floor_s` is the count of the expert matmuls alone from what a step
really touched (`experts_hit` of its step event), for
`kernel.moe_experts_roofline`."""

BF16 = 2


def _dims(model):
    H, F = model["hidden_size"], model["moe_ffn_hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    attn = H * q + 2 * H * kv + q * H
    router = H * model["moe_num_primary_experts"]
    return attn + router, 3 * H * F


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    L, k = (model["num_hidden_layers"],
            model["moe_num_active_primary_experts"])
    shared, expert = _dims(model)
    t_mem = BF16 * L * (shared + k * expert) / peaks["hbm_bytes_per_s"]
    t_flop = (2 * tokens * L * (shared + k * expert)
              / peaks["bf16_flops_per_s"])
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def experts_floor_s(model, peaks, tokens, experts_hit):
    """The least time the expert matmuls of one step can take: the weights
    of the `experts_hit` experts it touched (summed over its layers) once,
    or the operations of the k experts each of its `tokens` tokens uses in
    every layer; the larger."""
    L, k = (model["num_hidden_layers"],
            model["moe_num_active_primary_experts"])
    _, expert = _dims(model)
    t_mem = BF16 * experts_hit * expert / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * k * L * expert / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def prefill_attn_floor_s(model, peaks, tokens, ctx):
    """The least time attention over the context can take in one prefill
    step of ONE sequence: a chunk of `tokens` tokens whose last sees `ctx`
    keys, itself among them.  Per layer: 4 x head_dim x query heads
    operations for every key a token can SEE (causal: the token at position
    p sees p keys, and in a windowed layer, `sliding_window_layout` 1, no
    more than `sliding_window_size`; QK^T and PV, two operations a product),
    or the keys and values of the positions ANY token of the chunk can see
    (`ctx`; windowed: the chunk and the window before it) read once, in
    bf16; the larger, summed over the layers.  Keys masked, padded or read
    twice are the implementation's own and are not counted."""
    nq, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    W, prefix = model["sliding_window_size"], ctx - tokens
    total, t_mem_all, t_flop_all = 0.0, 0.0, 0.0
    for windowed in model["sliding_window_layout"]:
        if windowed:
            m = min(max(W - prefix, 0), tokens)  # tokens still under W keys
            pairs = m * prefix + m * (m + 1) // 2 + (tokens - m) * W
            keys = min(ctx, tokens + W - 1)
        else:
            pairs = tokens * prefix + tokens * (tokens + 1) // 2
            keys = ctx
        t_flop = 4 * hd * nq * pairs / peaks["bf16_flops_per_s"]
        t_mem = 2 * keys * nkv * hd * BF16 / peaks["hbm_bytes_per_s"]
        total += max(t_mem, t_flop)
        t_mem_all, t_flop_all = t_mem_all + t_mem, t_flop_all + t_flop
    return total, ("memory" if t_mem_all >= t_flop_all else "compute")
