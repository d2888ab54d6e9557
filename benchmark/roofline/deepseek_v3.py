"""Bytes and operations a prefill step needs, from shapes alone, for the
deepseek_v3 family: latent attention's five projections in every layer,
`first_k_dense_replace` dense feed-forwards, and in every other layer a
router over `n_routed_experts * ep_size` experts, one shared expert, and the
`n_routed_experts` routed experts HELD here (a chip's share of the layer).

`prefill_step_floor_s`.  Counted, bytes and operations alike: every layer's
attention projections (q_a, q_b, kv_a, kv_b, o), the dense layers'
feed-forward, the expert layers' router and shared expert: weights every
step reads once and every token multiplies by.  NOT counted: the routed
experts.  Under a share none is certain: a token's eight choices may all
lie on other chips (with an even router a token uses 8 / ep_size = 0.5 held
experts a layer), so no held expert is read by EVERY step and no token MUST
multiply by one; what a step's tokens really chose is `experts_floor_s`'s.
Nor attention's scores and values over the context (the step events carry
no context length), the latent rows read, the output head (only a prompt's
last chunk samples), the embedding gather, activations, page tables.  So
the figure is a floor, and a share of it cannot pass 100% by over-counting.
kv_b is counted once a token although the absorbed form the program runs
multiplies each QUERY by it, not each key: per token of the chunk the same
2 x 512 x 20480 operations.

`experts_floor_s` is the count of the held experts' matmuls alone from what
a step really touched: the weights of the `experts_hit` held experts it
touched (summed over its expert layers) once over the HBM peak, or the
operations of the assignments that chose a held expert over the bf16 peak;
the larger.  The step event does not say how many assignments were local
(`moe_local`) to this function's signature, so the operations counted are
the least any step that touched `experts_hit` experts does: one row each."""

BF16 = 2


def _dims(model):
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    nh, qr, r = (model["num_attention_heads"], model["q_lora_rank"],
                 model["kv_lora_rank"])
    nope, pe, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    attn = (H * qr + qr * nh * (nope + pe) + H * (r + pe)
            + r * nh * (nope + vd) + nh * vd * H)
    dense = 3 * H * model["intermediate_size"]
    router = H * model["n_routed_experts"] * model.get("ep_size", 1)
    shared = 3 * H * F * model["n_shared_experts"]
    return attn, dense, router + shared, 3 * H * F


def every_step_params(model):
    """Weights every prefill step reads and every token multiplies by."""
    L, k = model["num_hidden_layers"], model["first_k_dense_replace"]
    attn, dense, expert_layer, _ = _dims(model)
    return L * attn + k * dense + (L - k) * expert_layer


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    params = every_step_params(model)
    t_mem = BF16 * params / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * params / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def experts_floor_s(model, peaks, tokens, experts_hit):
    """The least time the held experts' matmuls of one step can take: the
    weights of the `experts_hit` held experts it touched (summed over its
    layers) once, or one row through each of them; the larger."""
    expert = _dims(model)[3]
    t_mem = BF16 * experts_hit * expert / peaks["hbm_bytes_per_s"]
    t_flop = 2 * experts_hit * expert / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")
