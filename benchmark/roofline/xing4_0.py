"""Bytes and operations a prefill step needs, from shapes alone, for the
xing4_0 family: deepseek_v3's layers (latent attention's five projections in
every layer, `first_k_dense_replace` dense feed-forwards, then a router over
`n_routed_experts * ep_size` experts, one shared expert and the routed
experts HELD here) around a residual of `hc_mult` streams, whose two mixers
a layer multiply every token's `hc_mult x hidden` values by a matrix `fn` of
`hc_mult^2 + 2 hc_mult` columns.

`prefill_step_floor_s`.  Counted, bytes and operations alike: every layer's
attention projections (q_a, q_b, kv_a, kv_b, o), the dense layers'
feed-forward, the expert layers' router and shared expert, the mixers' `fn`
(float32 in the program: 4 bytes a value) and, where `ep_size` is 1,
`num_experts_per_tok` ROUTED experts a layer: with every expert of the
layer held here each token multiplies by exactly that many held experts and
every step, of one token even, reads at least that many.  (Under a share,
`ep_size` > 1, none is certain, as `roofline/deepseek_v3.py` says, and none
is counted.)  NOT counted: the other experts a step's tokens touch (what a
step really touched is `experts_floor_s`'s), attention's scores and values
over the context, the latent rows read, the output head and the head's
mixer (only a prompt's last chunk samples), the embedding gather,
activations (the residual's streams among them: `hyper_conn_floor_s`), page
tables.  So the figure is a floor, and a share of it cannot pass 100% by
over-counting.

`experts_floor_s` is deepseek_v3's: the weights of the `experts_hit` held
experts a step touched (summed over its expert layers) once over the HBM
peak, or one row through each; the larger.

`hyper_conn_floor_s(model, peaks, tokens)`: the least time the stream
mixers' traffic of one step can take.  Per token and half of a layer the
residual is read once and written once (`hc_mult` rows each), the half's
input written once and its output read once: `(2 hc_mult + 2) x hidden`
values in the served dtype, over the HBM peak; two halves a layer.  LEFT
OUT: the second read of the residual that a `pre` not fused with the
mixer's product needs, `fn` itself (`prefill_step_floor_s` has it), the
product's operations and the Sinkhorn arithmetic (tens of divisions a token
and half: nothing beside 57 KB of traffic), the head's reduction."""

BF16, F32 = 2, 4


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


def mixer_params(model):
    """The two mixers' `fn` of one layer."""
    n = model["hc_mult"]
    return 2 * n * model["hidden_size"] * (n * n + 2 * n)


def every_step_params(model):
    """(bf16 weights, float32 weights) every prefill step reads and every
    token multiplies by."""
    L, k = model["num_hidden_layers"], model["first_k_dense_replace"]
    attn, dense, expert_layer, expert = _dims(model)
    certain = (model["num_experts_per_tok"]
               if model.get("ep_size", 1) == 1 else 0)
    return (L * attn + k * dense + (L - k) * (expert_layer
                                              + certain * expert),
            L * mixer_params(model))


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    served, mixers = every_step_params(model)
    t_mem = (BF16 * served + F32 * mixers) / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * (served + mixers) / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def experts_floor_s(model, peaks, tokens, experts_hit):
    """The least time the held experts' matmuls of one step can take: the
    weights of the `experts_hit` held experts it touched (summed over its
    layers) once, or one row through each of them; the larger."""
    expert = _dims(model)[3]
    t_mem = BF16 * experts_hit * expert / peaks["hbm_bytes_per_s"]
    t_flop = 2 * experts_hit * expert / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def hyper_conn_floor_s(model, peaks, tokens):
    """The least time the stream mixers' reads and writes of one step over
    `tokens` tokens can take: always the memory bound."""
    values = (2 * model["hc_mult"] + 2) * model["hidden_size"]
    halves = 2 * model["num_hidden_layers"]
    return (BF16 * values * halves * tokens / peaks["hbm_bytes_per_s"],
            "memory")
