"""Bytes and operations a prefill step needs, from shapes alone, for the
phi4flash family (`reference/phi4flash.py` has the layer equations): a layer
is a mixer THEN a dense SwiGLU feed-forward; the SELF half (layers 0 to L/2
+ 1: Mamba-1 mixers and differential attention in turn) runs over every
token of a chunk, the CROSS half (gated memory units and cross-attention) on
ONE position of a row that samples.

`prefill_step_floor_s`.  Counted, bytes and operations alike: the self
half's weights, which every step reads once and every token multiplies by:
per Mamba-1 layer `in_proj`, `x_proj`, `dt_proj` and `out_proj`, per
attention layer of the self half (layer L/2 + 1 among them) `Wqkv` and
`out_proj`, and every self layer's feed-forward.  NOT counted: the cross
half (only a step in which a row samples runs it, and then on one position
a row), the head (likewise), the selective scans (`selective_scan_floor_s`),
attention over the context (`prefill_attn_floor_s`), the convolution, the
keys, values and states read and written, the embedding gather, norms,
biases, activations.  So the figure is a floor, and a share of it cannot
pass 100% by over-counting.

`prefill_attn_floor_s` is the same for attention over the context alone: a
chunk of `tokens` tokens of ONE sequence whose last token sees `ctx` keys.
Per attention layer of the self half, the larger of 6 x head_dim x query
heads operations for every key a token can SEE (causal; under the window no
more than `sliding_window`; 2 x 64 a product for the scores and 2 x 128 for
the value PAIR, which is twice a head wide, over 40 query heads: 6 x 64 x
40) over the bf16 peak, and the visible keys and values read once in the
served dtype over the HBM peak; summed over the layers.  The cross layers'
single rows are left out.

`selective_scan_floor_s` is the least the convolution, the scan and the gate
of one step can take.  Bytes: per token and Mamba-1 layer x, z and dt read
and y written once in the served dtype (4 d values) and B and C (2 N), and
per ROW and layer the carried state (float32 [d, N]) and the convolution's
window (3 d in the served dtype) read once and written once; over the HBM
peak.  Operations: per token and layer 6 x d x N (the decay's product and
exponential, the state's multiply and add, the input's product, the product
with C and its sum, less what folds) over the bf16 peak.  The larger.  Left
out: every temporary a form that is not fused writes between its steps, the
convolution's taps, `x_proj` and `dt_proj` (counted in the step's floor): a
fused kernel needs none of them, and the share says what one could gain."""

BF16 = 2


def _sizes(model):
    h, f = model["hidden_size"], model["intermediate_size"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or h // nq
    dt_rank = model.get("mamba_dt_rank", "auto")
    return {"h": h, "f": f, "nq": nq, "nkv": nkv, "hd": hd,
            "d": model.get("mamba_expand", 2) * h,
            "N": model.get("mamba_d_state", 16),
            "K": model.get("mamba_d_conv", 4),
            "r": -(-h // 16) if dt_rank == "auto" else dt_rank,
            "W": model["sliding_window"],
            # Mamba-1 layers, and attention layers of the self half with
            # their windows (0: every key)
            "scans": model["num_hidden_layers"] // 4 + 1,
            "windows": [model["sliding_window"]]
            * (model["num_hidden_layers"] // 4) + [0]}


def every_step_params(model):
    """Weights every prefill step reads and every token multiplies by: the
    self half's."""
    z = _sizes(model)
    h, f, d, N, r = z["h"], z["f"], z["d"], z["N"], z["r"]
    q, kv = z["nq"] * z["hd"], z["nkv"] * z["hd"]
    mamba = h * 2 * d + d * (r + 2 * N) + r * d + d * h
    attn = h * (q + 2 * kv) + q * h
    return (z["scans"] * (mamba + 3 * h * f)
            + len(z["windows"]) * (attn + 3 * h * f))


def prefill_step_floor_s(model, peaks, tokens):
    """The least time one prefill step over `tokens` prompt tokens can take
    on this chip, and which bound sets it."""
    params = every_step_params(model)
    t_mem = BF16 * params / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * params / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def prefill_attn_floor_s(model, peaks, tokens, ctx):
    """The least time attention over the context can take in one prefill
    step of ONE sequence (module docstring)."""
    z = _sizes(model)
    prefix = ctx - tokens
    total, t_mem_all, t_flop_all = 0.0, 0.0, 0.0
    for W in z["windows"]:
        if W:
            m = min(max(W - prefix, 0), tokens)  # tokens still under W keys
            pairs = m * prefix + m * (m + 1) // 2 + (tokens - m) * W
            keys = min(ctx, tokens + W - 1)
        else:
            pairs = tokens * prefix + tokens * (tokens + 1) // 2
            keys = ctx
        t_flop = 6 * z["hd"] * z["nq"] * pairs / peaks["bf16_flops_per_s"]
        t_mem = (2 * keys * z["nkv"] * z["hd"] * BF16
                 / peaks["hbm_bytes_per_s"])
        total += max(t_mem, t_flop)
        t_mem_all, t_flop_all = t_mem_all + t_mem, t_flop_all + t_flop
    return total, ("memory" if t_mem_all >= t_flop_all else "compute")


def selective_scan_floor_s(model, peaks, tokens, rows):
    """The least time the convolution, the scan and the gate of one step
    over `tokens` tokens in `rows` sequences can take (module docstring)."""
    z = _sizes(model)
    d, N = z["d"], z["N"]
    state = 4 * d * N + BF16 * (z["K"] - 1) * d
    t_mem = z["scans"] * (tokens * BF16 * (4 * d + 2 * N)
                          + rows * 2 * state) / peaks["hbm_bytes_per_s"]
    t_flop = z["scans"] * tokens * 6 * d * N / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")
