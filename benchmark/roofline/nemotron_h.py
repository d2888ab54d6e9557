"""Bytes and operations a prefill step needs, from shapes alone, for the
nemotron_h family: a layer is ONE mixer, named by `hybrid_override_pattern`:
"M" a Mamba-2 mixer, "*" attention, "E" a router over `n_routed_experts *
ep_size` experts, one shared expert and the `n_routed_experts` routed
experts HELD here (a chip's share of the layer).  No gate matrix anywhere: an
expert is two matrices.

`prefill_step_floor_s`.  Counted, bytes and operations alike: every "M"
layer's `in_proj` and `out_proj`, every "*" layer's q, k, v and o, every "E"
layer's router and shared expert: weights every step reads once and every
token multiplies by.  NOT counted: the routed experts (under a share none is
certain: a token's six choices may all lie on other chips, so no held expert
is read by EVERY step and no token MUST multiply by one:
`roofline/deepseek_v3.py`'s argument; what a step's tokens really chose is
`experts_floor_s`'s).  Nor the state-space scan itself (`ssm_scan_floor_s`),
the convolution, attention's scores and values over the context (the step
events carry no context length), the keys and values read, the states read
and written, the output head (only a prompt's last chunk samples), the
embedding gather, norms, activations, page tables.  So the figure is a floor,
and a share of it cannot pass 100% by over-counting.

`experts_floor_s`, as `roofline/deepseek_v3.py`'s with two matrices an
expert: the weights of the `experts_hit` held experts a step touched (summed
over its expert layers) once over the HBM peak, or one row through each of
them over the bf16 peak; the larger.

`ssm_scan_floor_s` is the least the convolution, the scan and the gated norm
of one step can take.  Bytes: per token and "M" layer the scan's inputs read
once and its output written once in the served dtype (xBC 6144 + dt 64 + z
4096 read, y 4096 written: d + conv_dim + heads + d values), and per ROW and
layer the carried state read once and written once (float32 H, and the
convolution's window); over the HBM peak.  Operations: per token and layer
the recurrence's own, 5 x heads x head_dim x state (decay, outer product,
add, and the product with C: a multiply and an add), over the bf16 peak.  The
larger.  Left out: every temporary a chunked form writes between its steps
(the [chunk, chunk] decay and score blocks, float32 intermediates), the
convolution's taps and the norm's arithmetic: a fused kernel needs none of
them, and the share says what one could gain."""

BF16 = 2


def _dims(model):
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    d = model["mamba_num_heads"] * model["mamba_head_dim"]
    cd = d + 2 * model["n_groups"] * model["ssm_state_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    mamba = H * (d + cd + model["mamba_num_heads"]) + d * H
    attn = H * q + 2 * H * kv + q * H
    router = H * model["n_routed_experts"] * model.get("ep_size", 1)
    shared = 2 * H * model["moe_shared_expert_intermediate_size"]
    return mamba, attn, router + shared, 2 * H * F


def every_step_params(model):
    """Weights every prefill step reads and every token multiplies by."""
    pattern = model["hybrid_override_pattern"]
    mamba, attn, expert_layer, _ = _dims(model)
    return (pattern.count("M") * mamba + pattern.count("*") * attn
            + pattern.count("E") * expert_layer)


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


def ssm_scan_floor_s(model, peaks, tokens, rows):
    """The least time the convolution, the scan and the gated norm of one
    step over `tokens` tokens in `rows` sequences can take (module
    docstring)."""
    layers = model["hybrid_override_pattern"].count("M")
    nh, hp, N = (model["mamba_num_heads"], model["mamba_head_dim"],
                 model["ssm_state_size"])
    d = nh * hp
    cd = d + 2 * model["n_groups"] * N
    state = 4 * nh * hp * N + BF16 * (model["conv_kernel"] - 1) * cd
    t_mem = layers * (tokens * BF16 * (2 * d + cd + nh)
                      + rows * 2 * state) / peaks["hbm_bytes_per_s"]
    t_flop = layers * tokens * 5 * nh * hp * N / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")
