"""Plain reference forward of the Llama-like decoder family (Llama, Mistral,
Qwen2): float32 numpy on the host CPU (BLAS sgemm: true float32 products and
sums, so no `highest`-precision switch is needed as it would be on a TPU),
no KV cache, no kernels, no batching tricks.  It follows the Hugging Face
modelling code of these families: pre-norm residual blocks, RMSNorm in
float32, rotary embedding in the rotate-half convention over the whole head,
grouped-query causal attention (with a sliding window where the config sets
one), SwiGLU.

Weights are streamed: `read(name)` returns one tensor as float32 numpy, and
only one layer's tensors are alive at a time.

TOLERANCE — what |served logprob - reference logprob| may be for the served
path's top-1 token.  The served path computes in bf16 (8 bits of mantissa: a
logit of magnitude ~4 resolves to ~0.03) through 14-16 layers; the reference
in float32 from the same bf16 weights.  On the chip the largest difference
over steps whose context is known was 0.027 (Mistral widths, 16 layers) and
0.031 (Qwen2.5 widths, 14 layers) over 24 comparisons each (my chip runs,
PR 24); 0.06 is twice that.  Weights or activations held in 8 bits (int8/fp8:
3-4 bits of mantissa, errors 16-32 times bf16's) move a logit by several
tenths at these widths and fail it.
"""

LOGPROB_TOL = 0.06
# The served token's id is not visible to a client, so the comparison is of
# top-1 against top-1.  Where the reference's top two lie closer than this,
# bf16 may pick the other one: its logprob is the reference's second, so
# that step is allowed the gap on top of the tolerance (never more than the
# gap), and a greedy decode comparison ends there (the contexts part).
TIE_MARGIN = 0.06


def head_dim(model):
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])


def _rms(np, x, w, eps):
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + eps) * w


def _rope(np, x, pos, theta):
    # x [B, T, n, hd]; rotate-half over the whole head (HF convention)
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(np.float32) * inv[None, :]        # [T, hd/2]
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)[None, :, None, :]
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + np.concatenate([-x2, x1], -1) * sin


def _softmax(np, s):
    s = s - s.max(axis=-1, keepdims=True)
    e = np.exp(s)
    return e / e.sum(axis=-1, keepdims=True)


def tail_logprobs(read, model, batches, n_last):
    """batches: a list of int arrays [B, T] (rows of one batch have one
    length; batches may differ).  `read(name)` returns one checkpoint tensor
    as float32 numpy, and every tensor is read once, whatever the number of
    batches.  Returns, per batch, the float32 log-probabilities of the next
    token after each of the last `n_last` positions: [B, n_last, vocab]."""
    import numpy as np

    nq, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], head_dim(model))
    eps, theta = model["rms_norm_eps"], model.get("rope_theta", 10000.0)
    window = (model.get("sliding_window")
              if model.get("use_sliding_window", True) else None)
    bias = model.get("attention_bias", model["model_type"] == "qwen2")
    tied = model.get("tie_word_embeddings", False)
    embed = read("model.embed_tokens.weight")
    xs = [embed[np.asarray(t)] for t in batches]                 # [B, T, H]
    if not tied:
        del embed

    def attend(x, w):
        B, T, _ = x.shape
        pos = np.arange(T)
        i, j = pos[:, None], pos[None, :]
        mask = j <= i
        if window:
            mask = mask & (i - j < window)
        h = _rms(np, x, w["ln1"], eps)
        q, k, v = h @ w["q"].T, h @ w["k"].T, h @ w["v"].T
        if bias:
            q, k, v = q + w["qb"], k + w["kb"], v + w["vb"]
        q = _rope(np, q.reshape(B, T, nq, hd), pos, theta)
        k = _rope(np, k.reshape(B, T, nkv, hd), pos, theta)
        v = v.reshape(B, T, nkv, hd)
        # grouped-query: each KV head serves nq // nkv query heads
        k = np.repeat(k, nq // nkv, axis=2).transpose(0, 2, 3, 1)  # [B,n,hd,T]
        v = np.repeat(v, nq // nkv, axis=2).transpose(0, 2, 1, 3)  # [B,n,T,hd]
        s = (q.transpose(0, 2, 1, 3) @ k) / np.float32(hd ** 0.5)  # [B,n,T,T]
        a = _softmax(np, np.where(mask[None, None], s, -np.inf))
        o = (a @ v).transpose(0, 2, 1, 3).reshape(B, T, nq * hd)
        return x + o @ w["o"].T

    def mlp(x, w):
        h = _rms(np, x, w["ln2"], eps)
        g, u = h @ w["gate"].T, h @ w["up"].T
        return x + (g / (1.0 + np.exp(-g)) * u) @ w["down"].T    # SwiGLU

    for l in range(model["num_hidden_layers"]):
        p = f"model.layers.{l}."
        w = {"ln1": read(p + "input_layernorm.weight"),
             "q": read(p + "self_attn.q_proj.weight"),
             "k": read(p + "self_attn.k_proj.weight"),
             "v": read(p + "self_attn.v_proj.weight"),
             "o": read(p + "self_attn.o_proj.weight")}
        if bias:
            w.update(qb=read(p + "self_attn.q_proj.bias"),
                     kb=read(p + "self_attn.k_proj.bias"),
                     vb=read(p + "self_attn.v_proj.bias"))
        xs = [attend(x, w) for x in xs]
        w = {"ln2": read(p + "post_attention_layernorm.weight"),
             "gate": read(p + "mlp.gate_proj.weight"),
             "up": read(p + "mlp.up_proj.weight"),
             "down": read(p + "mlp.down_proj.weight")}
        xs = [mlp(x, w) for x in xs]
    norm = read("model.norm.weight")
    head = (embed if tied else read("lm_head.weight")).T
    out = []
    for x in xs:
        logits = _rms(np, x[:, -n_last:], norm, eps) @ head   # [B, n, vocab]
        logits = logits - logits.max(axis=-1, keepdims=True)
        out.append((logits - np.log(np.exp(logits).sum(
            axis=-1, keepdims=True))).astype(np.float32))
    return out
