"""Bytes and operations a prefill step needs, from shapes alone, for the
falcon_h1 family: EVERY layer is a Mamba-2 mixer and GQA attention side by
side and then a dense SwiGLU, so every layer's count is the same.

`prefill_step_floor_s`.  Counted, bytes and operations alike: every layer's
`in_proj` and `out_proj`, its q, k, v and o and its three feed-forward
matrices (Falcon-H1-34B: 68.3 M + 31.5 M + 330.3 M = 430.1 M a layer; six
layers are 5.16 GB read, or 2 x 430 M x 6 operations a token): weights every
step reads once and every token multiplies by.  NOT counted: the state-space
scan itself (`ssm_scan_floor_s`), the convolution, attention's scores and
values over the context (`prefill_attn_floor_s`), the keys and values read,
the states read and written, the output head (only a prompt's last chunk
samples), the embedding gather, norms, activations, the multipliers, page
tables.  So the figure is a floor, and a share of it cannot pass 100% by
over-counting.

`ssm_scan_floor_s`, by `roofline/nemotron_h.py`'s contract at this family's
keys, is the least the convolution, the scan and the gated norm of one step
can take.  Bytes: per token and layer the scan's inputs read once and its
output written once in the served dtype (xBC 5120 + dt 32 + z 4096 read, y
4096 written: d + conv_dim + heads + d values), and per ROW and layer the
carried state read once and written once (float32 H [32, 128, 256], and the
convolution's window); over the HBM peak.  Operations: per token and layer
the recurrence's own, 5 x heads x head_dim x state (decay, outer product,
add, and the product with C: a multiply and an add), over the bf16 peak.  The
larger.  Left out: every temporary a chunked form writes between its steps,
the states handed out inside a chunk, the convolution's taps and the norm's
arithmetic: a fused kernel needs none of them, and the share says what one
could gain."""

BF16 = 2


def head_dim(model):
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])


def ssm_sizes(model):
    """(d, conv_dim, heads, head_dim, state) of the state-space half."""
    nh, hp, N = (model["mamba_n_heads"], model["mamba_d_head"],
                 model["mamba_d_state"])
    d = model.get("mamba_d_ssm") or nh * hp
    return d, d + 2 * model.get("mamba_n_groups", 1) * N, nh, hp, N


def layer_weight_params(model):
    H, hd = model["hidden_size"], head_dim(model)
    d, cd, nh, _, _ = ssm_sizes(model)
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    return (H * (d + cd + nh) + d * H + 2 * H * q + 2 * H * kv
            + 3 * H * model["intermediate_size"])


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    params = model["num_hidden_layers"] * layer_weight_params(model)
    t_mem = BF16 * params / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * params / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def prefill_attn_floor_s(model, peaks, tokens, ctx):
    """The least time attention over the context can take in one prefill
    step of ONE sequence: a chunk of `tokens` tokens whose last sees `ctx`
    keys, itself among them.  Per layer (every layer has attention): 4 x
    head_dim x query heads operations for every key a token can SEE (causal:
    the token at position p sees p keys; QK^T and PV, two operations a
    product), or the `ctx` keys and values read once in bf16; the larger,
    summed over the layers."""
    hd = head_dim(model)
    pairs = tokens * (ctx - tokens) + tokens * (tokens + 1) // 2
    t_flop = (4 * hd * model["num_attention_heads"] * pairs
              / peaks["bf16_flops_per_s"])
    t_mem = (2 * ctx * model["num_key_value_heads"] * hd * BF16
             / peaks["hbm_bytes_per_s"])
    L = model["num_hidden_layers"]
    return L * max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def ssm_scan_floor_s(model, peaks, tokens, rows):
    """The least time the convolution, the scan and the gated norm of one
    step over `tokens` tokens in `rows` sequences can take (module
    docstring)."""
    d, cd, nh, hp, N = ssm_sizes(model)
    state = 4 * nh * hp * N + BF16 * (model.get("mamba_d_conv", 4) - 1) * cd
    L = model["num_hidden_layers"]
    t_mem = L * (tokens * BF16 * (2 * d + cd + nh)
                 + rows * 2 * state) / peaks["hbm_bytes_per_s"]
    t_flop = L * tokens * 5 * nh * hp * N / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")
