"""Bytes and operations a prefill step needs, from shapes alone, for the
Laguna family (poolside Laguna-XS.2): GQA attention whose query heads differ
by layer (`num_attention_heads_per_layer`), a per-head output gate, and by
`mlp_layer_types` a dense SwiGLU of `intermediate_size` or `num_experts`
SwiGLU experts of `moe_intermediate_size` behind one router, of which a
token uses `num_experts_per_tok`, beside one shared expert every token uses.

Counted.  Operations: for every token of the chunk, two per weight of each
layer's attention projections (at the layer's OWN head count), its gate, and
its feed-forward: the dense matrices, or the router, the k experts the token
USES (no token multiplies by an expert it was not routed to, whatever the
program does) and the shared expert.  Bytes: every layer's attention, gate,
router and shared-expert weights once a step, the dense layer's matrices,
and k expert matrices a sparse layer: what EVERY step must read, since a
step's tokens may all choose the same k.  Under the cell's random weights a
chunk's tokens do pile onto few experts (the configuration's
`assumed.routing`), so a step that touched 100 experts a layer is charged 8,
and a program that reads all 256 (the all-experts matmul, or a dispatched
form that copies the stacks) reads a small share of this floor: the share
says how much a form that reads only what was chosen could save.

NOT counted: attention's QK^T and PV over the context and the keys and
values read (`prefill_attn_floor_s` has them), the output head (only a
prompt's last chunk samples), the embedding gather, activations, page
tables, and the experts beyond k that a step's tokens happen to choose.  So
the figure is a floor, and a share of it cannot pass 100% by over-counting.

`routed_experts_floor_s` is the count of the routed experts' matmuls alone
from what a step really did (`moe_assignments` and `experts_hit` of its step
event), for `kernel.routed_experts_roofline`."""

BF16 = 2


def _layers(model):
    """[(attention + gate (+ router + shared expert) params, dense
    feed-forward params, sparse?)] a layer."""
    H, hd = model["hidden_size"], model["head_dim"]
    kv = model["num_key_value_heads"] * hd
    out = []
    for nh, mlp in zip(model["num_attention_heads_per_layer"],
                       model["mlp_layer_types"]):
        attn = 2 * H * nh * hd + 2 * H * kv + H * nh
        if mlp == "dense":
            out.append((attn, 3 * H * model["intermediate_size"], False))
        else:
            out.append((attn + H * model["num_experts"]
                        + 3 * H * model["shared_expert_intermediate_size"],
                        0, True))
    return out


def expert_params(model):
    """One routed expert's three matrices."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def every_step_params(model):
    """Parameters every step reads and every token multiplies by: each
    layer's attention, gate, router and shared expert, the dense layers, and
    k experts a sparse layer."""
    k, expert = model["num_experts_per_tok"], expert_params(model)
    return sum(shared + dense + (k * expert if sparse else 0)
               for shared, dense, sparse in _layers(model))


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    n = every_step_params(model)
    t_mem = BF16 * n / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * n / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def routed_experts_floor_s(model, peaks, assignments, experts_hit):
    """The least time the routed experts' matmuls of one step can take: the
    weights of the `experts_hit` experts it touched (summed over its sparse
    layers) once, or two operations a weight for each of its `assignments`
    (token, expert) pairs (summed likewise); the larger."""
    expert = expert_params(model)
    t_mem = BF16 * experts_hit * expert / peaks["hbm_bytes_per_s"]
    t_flop = 2 * assignments * expert / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def prefill_attn_floor_s(model, peaks, tokens, ctx):
    """The least time attention over the context can take in one prefill
    step of ONE sequence: a chunk of `tokens` tokens whose last sees `ctx`
    keys, itself among them.  Per layer, at the layer's OWN query heads
    (`num_attention_heads_per_layer`: 48 in a full layer, 64 in a windowed
    one) and its own reach (`layer_types`): 4 x head_dim x query heads
    operations for every key a token can SEE (causal: the token at position
    p sees p keys, and under "sliding_attention" no more than
    `sliding_window`; QK^T and PV, two operations a product), or the keys
    and values of the positions ANY token of the chunk can see (`ctx`;
    windowed: the chunk and the window before it) read once, in bf16; the
    larger, summed over the layers.  Keys masked, padded or read twice are
    the implementation's own and are not counted."""
    nkv, hd = model["num_key_value_heads"], model["head_dim"]
    W, prefix = model["sliding_window"], ctx - tokens
    total, t_mem_all, t_flop_all = 0.0, 0.0, 0.0
    for kind, nq in zip(model["layer_types"],
                        model["num_attention_heads_per_layer"]):
        if kind == "sliding_attention":
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
