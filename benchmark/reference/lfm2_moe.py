"""Plain reference forward of the LFM2-MoE family (Liquid AI LFM2-24B-A2B,
`model_type` "lfm2_moe"): float32 numpy on the host CPU (BLAS sgemm: true
float32 products and sums, so no `highest`-precision switch is needed as it
would be on a TPU), no KV cache, no state slots, no kernels, no batching
tricks, no dispatch: the short convolution walks the sequence TOKEN BY TOKEN,
an expert multiplies the rows routed to it, one expert after the other, and
only a token's own 4 experts touch it.

One layer `l`, input x [T, hidden]; n(.) an RMS norm with a weight, eps
`norm_eps`; no biases anywhere:

  1. z = n_op(x) (`operator_norm`); the MIXER by layer_types[l]:
     "conv": [B, C, u] = split3(W_in z) (hidden -> 3 hidden); g = B * u;
       c_t = w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t per channel (a causal
       depthwise convolution of `conv_L_cache` 3 taps, zeros before the
       sequence, NO bias, NO activation; `conv.conv.weight` [hidden, 1, 3],
       tap j the input 2 - j positions back); mixer = W_out (C * c)
     "full_attention": q = z Wq [T, 32, 64], k = z Wk, v = z Wv [T, 8, 64]
       (head_dim = hidden / heads); an RMS norm with a weight over the 64
       values of EACH head of q and of k (`q_layernorm`, `k_layernorm`),
       BEFORE the rope; the rope rotates the whole head (halves convention,
       theta 1e6); causal GQA, scale 64^-0.5, a KV head serving 4 query
       heads; mixer = W_o a
     h = x + mixer
  2. m = n_ffn(h) (`ffn_norm`); l < num_dense_layers: y = W2(silu(W1 m) *
     W3 m) at `intermediate_size`; else s = sigmoid(m Wg) over the 64
     experts (float32); the 4 largest of s + expert_bias are CHOSEN; their
     weights are the UNBIASED s of the chosen, divided by (their sum + 1e-6),
     times `routed_scaling_factor`; y = sum_j weight_j W2_j(silu(W1_j m) *
     W3_j m); no shared expert.  x' = h + y
  3. after the last layer n_emb (`embedding_norm`: the family's name for the
     FINAL norm), then the head, tied to the embedding.

WHERE THIS FILE DEPARTS from a published source (the configuration's
`assumed` says the same): the dense sibling's modelling code (transformers
4.57.6 `models/lfm2/modeling_lfm2.py`: `Lfm2ShortConv`, `Lfm2Attention`,
`Lfm2DecoderLayer`) gives 1 and 3 to the letter; that package has no
`lfm2_moe`, so step 2's expert block is the family's published code as known
(sigmoid scores, `expert_bias` added for choosing only, the 1e-6 in the
denominator), as are its tensor names (`TENSORS`); the head is taken tied
(the sibling's default; the row has no `tie_word_embeddings` key).

Weights are streamed: `read(name)` returns one tensor as float32 numpy; one
layer's mixer tensors and ONE expert's three matrices are alive at a time,
and every tensor is read once whatever the number of batches (the embedding
twice: it is the head too).  Attention runs
`ATTN_QUERY_BLOCK` queries at a time, so a 6,400-token probe never holds a
[T, T] score matrix a head.

TOLERANCES: |served logprob - reference logprob| of the top-1 token, as
`benchmark/lib/probes.py` compares them over 48 steps (6 probe texts of 48,
48, 48, 48, 1,200 and 6,400 tokens, 8 lengths each): ONE limit over the
largest of the 48, set from readings at the cell's full size
(`lfm2-24b-a2b-h9`; PERF.md finding 35 has the tables and their origin).

What the served path's distance IS, measured (the host alone, this file
against itself on the cell's checkpoint: `benchmark/tests/
routing_answers.py`, 384 steps): the experts the two sides CHOOSE, not their
arithmetic.  Under one random draw a token's 4th and 5th biased router
scores lie 0.012 apart at the median and under 0.001 for one token in
twenty; a stream rounded to bf16 as the served path rounds it (`bf16_stream`)
chooses another fourth expert for 8.5% of (token, expert layer) pairs, 2% in
the first expert layer and 13-16% in the last two (a flip moves the stream
that the later routers read), and a flipped expert is one of four WHOLE
experts.  The same rounded stream HELD to the float32 choices (`routing`)
reads 0.022 at most over the 384 steps; choosing for itself it reads
0.107-0.179 as the largest of 48 (eight sets of 48; 0.26% of steps past
0.17, none past 0.23).  The chip's own 48 steps read `SERVED_READING`, the
same in every run (the probes come from `weights_seed`): two steps at 0.132
and 0.167, 41 under 0.03, the shape the emulation gives.

So the limit has to stand above what a flip costs, and a wrong answer that
costs less than a flip passes it: that is this draw under a check that takes
the largest of 48, not a property of the program (PERF.md section 7 (bk)
names the two repairs, both edits to accepted benchmark files: a router drawn
with a trained router's margins, or the served path's choices handed to this
file's `routing`, under which bf16 reads 0.022 and 3 bits of mantissa
0.135-0.180).

  - `SERVED_READING`: the served path on the chip (bf16 weights, residual,
    pages and windows, float32 accumulation);
  - `CONTROL_READING`: this file with `lower_precision` against itself on
    the chip machine's host, the cell's 48 steps; over eight other sets of
    48 the same control reads 0.232-0.484 as the largest (0.135-0.180 with
    the choices held).

LOGPROB_TOL 0.23 lies between the two readings, 0.167 and 0.300, 0.063 from
the one and 0.070 from the other; by the emulation no step of a sound
program's 384 reads past it (one past 0.17) and the control fails it in each
of its eight sets of 48 (the nearest by 0.002).  It was read BEFORE it was
set: the first run stood at a guess of 0.06 and read `correct` false.  What
it does NOT exclude, and what was tried: the router, the experts and the
convolution's inputs ALONE at 3 bits of mantissa read 0.185 and pass it (a
limit that excluded them would refuse a sound program for a flip), which is
why the control computes the whole model.  What the 0.167 is not: the router
reading its input in bf16 alone moves 0.007 (`bf16_routing`).
TIE_MARGIN 0.06: where the reference's top two lie closer than that, bf16 may
pick the other one, whose logprob is the reference's second: that step is
allowed the gap on top of the tolerance (never more than the gap).  FAULTS
that `correct` cannot see at full size under the one draw (PERF.md section 7
(bj); tier-1 holds all eight at the tiny size): `norm_after_rope` (0.000001:
with the head norms' weights drawn as ones a rotation commutes with the
norm), `window_not_carried` (0.0018: a window is two tokens deep and the
compared positions lie 176 and 256 tokens past a chunk's start),
`bias_in_weights` (0.136: the drawn bias is a hundredth of a score, under a
flip's cost).  The other five fail it (read at 0.21, restated at 0.23):
`no_qk_norm` by 0.029, `silu_on_conv` 0.194, `gate_before_conv` 0.218,
`taps_reversed` 0.348, `embedding_norm_first` 8.7.
"""

# (largest difference, largest on a step whose top-2 gap is clear): the
# served path on the chip, 48 steps, the same in eight runs on seven seeds
# (my chip runs, PR 55): probe 3 (48 tokens), its first length, gap 0.3219
SERVED_READING = (0.166958, 0.166958)
# this file with `lower_precision=True` against itself at full size (largest,
# steps of 48 that fail at the two limits below; the chip machine's host,
# PR 55): 0.2997 and 0.2956 on steps whose gaps are 0.0041 and 0.0177
CONTROL_READING = (0.299657, 2)

LOGPROB_TOL = 0.23
TIE_MARGIN = 0.06
ATTN_QUERY_BLOCK = 512  # queries a block of attention; a test lowers it
# tokens a serving chunk holds: where `window_not_carried` drops the window
FAULT_CHUNK = 512

FAULTS = (
    "no_qk_norm",             # q and k go to the rope as projected
    "norm_after_rope",        # the per-head norm AFTER the rotation
    "silu_on_conv",           # Mamba's activation on the convolution
    "taps_reversed",          # c_t = w_2 g_{t-2} + w_1 g_{t-1} + w_0 g_t
    "window_not_carried",     # the convolution sees zeros before every chunk
    "bias_in_weights",        # the weights from s + expert_bias
    "embedding_norm_first",   # n_emb on the embedded tokens, none at the end
    "gate_before_conv",       # conv(C * B * u), nothing after it
)
CONTROLS = ("lower_precision", *FAULTS)

TENSORS = """model.embed_tokens.weight (the head too); model.layers.{i}.
{operator_norm, ffn_norm}.weight; conv layers: model.layers.{i}.conv.
{in_proj.weight [3 hidden, hidden], conv.weight [hidden, 1, K], out_proj.
weight}; attention layers: model.layers.{i}.self_attn.{q_proj, k_proj,
v_proj, out_proj}.weight, self_attn.{q_layernorm, k_layernorm}.weight
[head_dim]; dense layers: model.layers.{i}.feed_forward.{w1, w3, w2}.weight
(gate, up, down); expert layers (ASSUMED: not in transformers 4.57.6):
model.layers.{i}.feed_forward.{gate.weight [experts, hidden], expert_bias
[experts], experts.{e}.{w1, w3, w2}.weight}; model.embedding_norm.weight"""


def _rms(np, x, w, eps):
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + eps) * w


def _softmax(np, s):
    s = s - s.max(axis=-1, keepdims=True)
    e = np.exp(s)
    return e / e.sum(axis=-1, keepdims=True)


def _silu(np, x):
    return x / (1.0 + np.exp(-x))


def _sigmoid(np, x):
    return 1.0 / (1.0 + np.exp(-x))


def _rope(np, x, pos, inv):
    # x [B, T, n, hd]; rotate-half over the whole head
    ang = pos[:, None].astype(np.float32) * inv[None, :]        # [T, hd/2]
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)[None, :, None, :]
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)[None, :, None, :]
    half = x.shape[-1] // 2
    return x * cos + np.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def _round_mantissa(np, x, bits):
    """x with its float32 mantissa cut to `bits` bits (round to nearest):
    the control's lower precision, never used by the reference itself."""
    drop = 23 - bits
    i = np.ascontiguousarray(x, np.float32).view(np.uint32)
    i = (i + np.uint32(1 << (drop - 1))) & np.uint32(~((1 << drop) - 1)
                                                      & 0xFFFFFFFF)
    return i.view(np.float32)


def head_dim(model):
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def check_model(model):
    L = model["num_hidden_layers"]
    types = model["layer_types"]
    if len(types) != L or set(types) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types must name {L} layers by conv and "
                         f"full_attention, got {types!r}")
    if model.get("conv_bias"):
        raise ValueError("conv_bias is not written down here")
    if not model.get("use_expert_bias", True):
        raise ValueError("only use_expert_bias true is written down here")
    if not model.get("norm_topk_prob", True):
        raise ValueError("only normalised weights are written down here")
    if model.get("tie_word_embeddings", True) is not True:
        raise ValueError("only the tied head is written down here")
    rp = model.get("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default":
        raise ValueError("only the default rope is written down here")


def short_conv(np, w, z, faults=(), low=lambda a: a):
    """The gated short convolution over z [B, T, hidden] (normed), token by
    token: the K - 1 inputs before a token are a window that starts as
    zeros."""
    B, T, H = z.shape
    taps = w["conv"][:, 0, :]                                   # [H, K]
    if "taps_reversed" in faults:
        taps = taps[:, ::-1]
    K = taps.shape[1]
    bcu = z @ w["in"].T
    b, c, u = bcu[..., :H], bcu[..., H:2 * H], bcu[..., 2 * H:]
    g = low(b * u)
    if "gate_before_conv" in faults:
        g = c * g
    window = np.zeros((B, K - 1, H), np.float32)
    out = np.empty_like(g)
    for t in range(T):
        if "window_not_carried" in faults and t % FAULT_CHUNK == 0:
            window[:] = 0.0
        seen = np.concatenate([window, g[:, t:t + 1]], axis=1)  # [B, K, H]
        out[:, t] = np.einsum("bkh,hk->bh", seen, taps)
        window = seen[:, 1:]
    if "silu_on_conv" in faults:
        out = _silu(np, out)
    if "gate_before_conv" not in faults:
        out = c * out
    return low(out) @ w["out"].T


def forward(read, model, batches, n_last, lower_precision=False,
            bf16_routing=False, bf16_stream=False, margins=None,
            picks=None, routing=None, **faults):
    """`tail_logprobs` with the controls a test may switch on, each a
    keyword.  `lower_precision` computes the MODEL in the nearest storage
    precision below the bf16 the configuration states: every matrix (the
    embedding, the mixers' and the feed-forwards' projections, the router,
    the experts, the head) and every product's input (the normed streams, the
    convolution's inputs and output, attention's output, an expert's hidden
    values) rounded to 3 bits of mantissa, fp8 e4m3's grid without its range;
    norms, softmax, sigmoid and the sums stay float32.  The rest of the
    keywords before `**faults` are no faults but READINGS of what the served
    path's rounding costs: `bf16_routing`, the router alone reads its input
    rounded to bf16's 7 bits of mantissa, the least the served path does to
    it; `bf16_stream`, everything `lower_precision` rounds at 7 bits and the
    residual stream after every half layer as well, which is what the served
    path holds in bf16; `picks`, a dict, receives per (layer, batch) the
    experts chosen [B, T, k]; `routing`, such a dict, puts its choices in the
    place of the router's own (the weights stay this pass's scores of them):
    a rounded pass HELD to the plain pass's choices tells the cost of the
    arithmetic from the cost of a flipped expert; `margins`, a list, receives
    per expert layer and batch the [B, T] gap between the k-th and (k+1)-th
    biased router score.  `FAULTS` are one mechanism got wrong each, and
    `correct` has to fail on it."""
    import numpy as np

    check_model(model)
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"forward() has no control {sorted(unknown)}")
    faults = {f for f, on in faults.items() if on}
    H, nq, nkv = (model["hidden_size"], model["num_attention_heads"],
                  model["num_key_value_heads"])
    hd, eps = head_dim(model), model.get("norm_eps", 1e-5)
    E, k = model.get("num_experts", 0), model.get("num_experts_per_tok", 0)
    scale = np.float32(model.get("routed_scaling_factor", 1.0))
    theta = float((model.get("rope_parameters") or {}).get(
        "rope_theta", model.get("rope_theta", 1000000.0)))
    inv = (1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
           ).astype(np.float32)
    bits = 3 if lower_precision else 7 if bf16_stream else None
    low = (lambda a: _round_mantissa(np, a, bits)) if bits else (lambda a: a)
    # the residual stream is rounded only where the served path rounds it
    stream = low if bf16_stream else (lambda a: a)
    embed = low(read("model.embed_tokens.weight"))
    xs = [embed[np.asarray(t)] for t in batches]                 # [B, T, H]
    del embed
    final = read("model.embedding_norm.weight")
    if "embedding_norm_first" in faults:
        xs = [_rms(np, x, final, eps) for x in xs]

    def attend(z, w):
        B, T, _ = z.shape
        pos = np.arange(T)
        q = (z @ w["q"].T).reshape(B, T, nq, hd)
        kk = (z @ w["k"].T).reshape(B, T, nkv, hd)
        v = (z @ w["v"].T).reshape(B, T, nkv, hd)
        if "no_qk_norm" in faults:
            q, kk = _rope(np, q, pos, inv), _rope(np, kk, pos, inv)
        elif "norm_after_rope" in faults:
            q = _rms(np, _rope(np, q, pos, inv), w["qn"], eps)
            kk = _rms(np, _rope(np, kk, pos, inv), w["kn"], eps)
        else:
            q = _rope(np, _rms(np, q, w["qn"], eps), pos, inv)
            kk = _rope(np, _rms(np, kk, w["kn"], eps), pos, inv)
        # grouped-query: each KV head serves nq // nkv query heads
        q = q.transpose(0, 2, 1, 3)
        kk = np.repeat(kk, nq // nkv, axis=2).transpose(0, 2, 3, 1)
        v = np.repeat(v, nq // nkv, axis=2).transpose(0, 2, 1, 3)
        o = np.empty((B, nq, T, hd), np.float32)
        for a0 in range(0, T, ATTN_QUERY_BLOCK):
            a1 = min(a0 + ATTN_QUERY_BLOCK, T)
            mask = pos[None, :a1] <= pos[a0:a1, None]
            s = (q[:, :, a0:a1] @ kk[..., :a1]) / np.float32(hd ** 0.5)
            p = _softmax(np, np.where(mask[None, None], s, -np.inf))
            o[:, :, a0:a1] = p @ v[:, :, :a1]
        o = o.transpose(0, 2, 1, 3).reshape(B, T, nq * hd)
        return low(o) @ w["o"].T

    def swiglu(rows, gate, up, down):
        return low(_silu(np, rows @ gate.T) * (rows @ up.T)) @ down.T

    for l in range(model["num_hidden_layers"]):
        p = f"model.layers.{l}."
        op_norm = read(p + "operator_norm.weight")
        if model["layer_types"][l] == "conv":
            w = {"in": low(read(p + "conv.in_proj.weight")),
                 "conv": low(read(p + "conv.conv.weight")),
                 "out": low(read(p + "conv.out_proj.weight"))}
            hs = [stream(x + short_conv(np, w, low(_rms(np, x, op_norm, eps)),
                                        faults, low)) for x in xs]
        else:
            a = p + "self_attn."
            w = {"q": low(read(a + "q_proj.weight")),
                 "k": low(read(a + "k_proj.weight")),
                 "v": low(read(a + "v_proj.weight")),
                 "o": low(read(a + "out_proj.weight")),
                 "qn": read(a + "q_layernorm.weight"),
                 "kn": read(a + "k_layernorm.weight")}
            hs = [stream(x + attend(low(_rms(np, x, op_norm, eps)), w))
                  for x in xs]
        del w
        ffn_norm = read(p + "ffn_norm.weight")
        f = p + "feed_forward."
        if l < model.get("num_dense_layers", 0):
            gate, up, down = (low(read(f + f"{n}.weight"))
                              for n in ("w1", "w3", "w2"))
            xs = [stream(h + swiglu(m, gate, up, down))
                  for h in hs for m in (low(_rms(np, h, ffn_norm, eps)),)]
            continue
        router = low(read(f + "gate.weight"))
        bias = read(f + "expert_bias")
        ms, routed, ys = [], [], []
        for b_, h in enumerate(hs):
            m = low(_rms(np, h, ffn_norm, eps))
            seen = _round_mantissa(np, m, 7) if bf16_routing else m
            s = _sigmoid(np, seen @ router.T)                    # [B, T, E]
            biased = s + bias
            order = np.argsort(-biased, axis=-1, kind="stable")
            idx = order[..., :k]
            if routing is not None:
                idx = routing[(l, b_)]
            if picks is not None:
                picks[(l, b_)] = idx
            if margins is not None:
                top = np.take_along_axis(biased, order[..., k - 1:k + 1], -1)
                margins.append(top[..., 0] - top[..., 1])
            chosen = np.take_along_axis(
                biased if "bias_in_weights" in faults else s, idx, -1)
            weight = chosen / (chosen.sum(-1, keepdims=True)
                               + np.float32(1e-6)) * scale
            ms.append(m)
            routed.append((idx, weight))
            ys.append(np.zeros_like(h))
        for e in range(E):
            x_ = f + f"experts.{e}."
            gate, up, down = (low(read(x_ + f"{n}.weight"))
                              for n in ("w1", "w3", "w2"))
            for m, (idx, pr), y in zip(ms, routed, ys):
                b, t, j = np.nonzero(idx == e)
                if not b.size:
                    continue
                np.add.at(y, (b, t), pr[b, t, j][:, None]
                          * swiglu(m[b, t], gate, up, down))
        xs = [stream(h + y) for h, y in zip(hs, ys)]
    head = low(read("model.embed_tokens.weight")).T
    out = []
    for x in xs:
        x = x[:, -n_last:]
        if "embedding_norm_first" not in faults:
            x = _rms(np, x, final, eps)
        logits = low(x) @ head                                     # [B, n, vocab]
        logits = logits - logits.max(axis=-1, keepdims=True)
        out.append((logits - np.log(np.exp(logits).sum(
            axis=-1, keepdims=True))).astype(np.float32))
    return out


def tail_logprobs(read, model, batches, n_last):
    """batches: a list of int arrays [B, T] (rows of one batch have one
    length; batches may differ).  `read(name)` returns one checkpoint tensor
    as float32 numpy.  Returns, per batch, the float32 log-probabilities of
    the next token after each of the last `n_last` positions: [B, n_last,
    vocab]."""
    return forward(read, model, batches, n_last)


def router_margins(read, model, batches):
    """Per expert layer and batch, [B, T]: how far each token's k-th biased
    router score lies above its (k+1)-th.  A margin under the stream's
    rounding is where a bf16 program and this file can choose a different
    last expert."""
    margins = []
    forward(read, model, batches, 1, margins=margins)
    return margins
