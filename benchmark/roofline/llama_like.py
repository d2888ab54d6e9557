"""Bytes and operations a prefill step needs, from shapes alone, for a dense
Llama-like decoder (Llama, Mistral, Qwen2: GQA projections and a gated MLP of
three matrices in every layer).

Counted: every layer's projection and MLP weights, read once per step
whatever the chunk, and two operations per such weight per token of the
chunk.  NOT counted: attention's QK^T and PV (the step events carry no
context length; about 5% of the dense operations at 2048 tokens of context),
the keys and values read, the output head (only a prompt's last chunk
samples), the embedding gather, activations, page tables.  So the figure is
a floor, and a share of it cannot pass 100% by over-counting."""

BF16 = 2


def head_dim(model):
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])


def layer_weight_params(model):
    H, I = model["hidden_size"], model["intermediate_size"]
    q = model["num_attention_heads"] * head_dim(model)
    kv = model["num_key_value_heads"] * head_dim(model)
    return H * q + 2 * H * kv + q * H + 3 * H * I


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
    keys, itself among them.  Per layer: 4 x head_dim x query heads
    operations for every key a token can SEE (causal: the token at position
    p sees p keys; QK^T and PV, two operations a product), or the keys and
    values of the `ctx` visible positions read once, in bf16; the larger,
    summed over the layers.  Keys masked, padded or read twice are the
    implementation's own and are not counted."""
    hd = head_dim(model)
    pairs = tokens * (ctx - tokens) + tokens * (tokens + 1) // 2
    t_flop = (4 * hd * model["num_attention_heads"] * pairs
              / peaks["bf16_flops_per_s"])
    t_mem = (2 * ctx * model["num_key_value_heads"] * hd * BF16
             / peaks["hbm_bytes_per_s"])
    L = model["num_hidden_layers"]
    return L * max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")
