"""Plain reference forward of Phi-4-mini-flash (`model_type` "phi4flash"; the
decoder-hybrid-decoder of arXiv:2507.06607, SambaY with differential
attention): float32 numpy on the host CPU (BLAS sgemm: true float32 products
and sums, what `default_matmul_precision("highest")` asks of an accelerator),
no cache, no chunks, no kernels: the state-space layers run their recurrence
ONE TOKEN AT A TIME, and differential attention's four products are written
out.  Attention runs `ATTN_QUERY_BLOCK` queries at a time against the keys
they can see, so that a probe of thousands of tokens never holds a [T, T]
score matrix a head (as `reference/smallthinker.py`).

Layer l of `num_hidden_layers` L (32), h = hidden_size, eps =
`layer_norm_eps`, LN = LayerNorm with mean, weight and bias:

    x <- x + mixer_l(LN(x));   x <- x + fc2(silu(g) * u), [g | u] = fc1(LN'(x))

`h0 = embed[token]`, no scale; logits = embed^T LN_f(x) (tied); NO positions
anywhere.  The mixers, by l:

  l even, l <= L/2            Mamba-1.  d = 2 h, N = 16, K = 4, r = ceil(h / 16)
       [x | z] = in_proj(u)                  h -> 2 d, no bias, x first
       x_t = silu(conv_b + sum_{j<K} conv_w[:, j] x_{t-K+1+j})
                                             depthwise, causal, zeros before
       [dl | B | C] = x_proj(x)              d -> r + N + N
       dt = softplus(dt_proj(dl) + dt_bias)  r -> d
       A = -exp(A_log)                       [d, N]: a decay for every
                                             channel AND state index
       H_t[c, n] = exp(dt_t[c] A[c, n]) H_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
       y_t[c] = sum_n C_t[n] H_t[c, n] + D[c] x_t[c]      H before = 0
       out = out_proj(y * silu(z))           d -> h
     Layer L/2 (16) ALSO hands out m_t = y_t, the scan's output before the
     gate.
  l odd, l < L/2              differential attention, keys t - W + 1 .. t
  l = L/2 + 1 (17)            differential attention, every key at or before t
       [q | k | v] = Wqkv(u) + b             h -> 40 x 64 | 20 x 64 | 20 x 64
       q1, q2 = even, odd query heads; k1, k2, v1, v2 likewise; query pair
       j reads key/value pair j // 2; V = [v1 | v2] (128 wide)
       P1 = softmax(q1 k1^T / 8 + mask);  P2 = softmax(q2 k2^T / 8 + mask)
       lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
       lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
       o = P1 V - lambda P2 V                [20, 128]
       o <- rmsnorm_128(o; w, eps) (1 - lambda_init(l))          (`subln`)
       out = out_proj(o read as 40 x 64) + b
  l even, l >= L/2 + 2        Gated Memory Unit: out = W2(silu(W1 u) * m_t),
                              W1 h -> d, W2 d -> h, m_t layer L/2's at the
                              SAME position.  It keeps nothing.
  l odd, l >= L/2 + 3         differential cross-attention: q alone (h -> 40
                              x 64, with bias), k and v are layer L/2 + 1's,
                              every key at or before t; the rest as above.

`forward` runs the cross half (l > L/2 + 1) at EVERY position.  No cross
layer mixes positions except through layer L/2 + 1's k, v and layer L/2's m,
so `tail_logprobs`, which returns the last `n_last` positions only, runs the
cross half on those positions alone (`cross_everywhere=False`):
tests/test_phi4flash.py holds the two forms equal.

ASSUMED (the catalog row carries config.json's keys, not the code; each is in
the configuration file under `assumed`): the layout by l above; d, N, K, r
and that the convolution has a bias and the projections none; the gate half
of fc1 first; differential attention in every attention layer, its pairing
by parity and its 128-wide value pair; the window rule (W keys with the
query's own); no positional encoding; H carried in float32; the tensor names
(`TENSORS` below).

Weights are streamed: `read(name)` returns one tensor as float32 numpy; one
layer's tensors are alive at a time, and every tensor is read once whatever
the number of batches.

TOLERANCES: see `TOLERANCES` below (a string, so that a reader of the module
finds the readings beside the limits they set).
"""

TOLERANCES = """|served logprob - reference logprob| of the top-1 token, as
`benchmark/lib/probes.py` compares them over 48 steps (6 probe texts of the
mix `longdoc-1tok`: 48, 48, 48, 48, 1200 and 6400 tokens; 8 steps each), from
two readings at the cell's full size (`phi4-mini-flash-3.8b`; PERF.md, PR 48,
finding 28, has them with their origin), with room on both sides:

  - the served path on the chip (bf16 weights and residual, float32
    accumulation, float32 recurrent state and scan): SERVED_READING below;
  - this file against itself with every matmul's operands outside attention
    rounded to 3 bits of mantissa (`lower_precision=True`: the nearest
    storage precision below bf16, fp8 e4m3's grid): CONTROL_READING below.

The served path's 48 steps read 0.2175 on ONE step (the second 48-token
probe, prefix + 5, where the reference's top token stands 0.67 above the
next: the step on which the lower precision and a numpy copy of this file
that rounds to bfloat16 wherever the served path does read their largest
too), then 0.0816, 0.0620, a median of 0.020; the same to the sixth digit in
every run (the probes come from `weights_seed`).  That it is bfloat16's
rounding and no fault of the program has two witnesses (PERF.md, finding 28):
the numpy copy (0.117, 0.081, 0.074 on 40 of the steps, median 0.019), and
the SAME step programs on the chip at these widths and 16 layers, which read
0.000082 in float32 and 0.0920 in bfloat16 against that depth's reference.
The lower precision reads 0.5505, 0.5261, 0.4547, 0.4469 the largest, a
median of 0.183.

LOGPROB_TOL 0.3 lies between 0.2175 and 0.5505: the served path reads 0.73 of
what it is allowed, the lower precision fails on 12 of the 48 steps (1.84 of
it the largest).  It is a COARSE limit, for two reasons that belong to the
comparison and to the draw, not to this file.  The number compared is the
logprob of a top-1 token among 200,064 whose logits a random model spreads
by 0.7: ANY model reads within about 0.5 of any other (every fault below
reads 0.40-0.87 at its largest and 0.14-0.20 in the median: they are all at
the ceiling), so the room between a rounding and a fault is never more than
three or four times.  And the checkpoint's draw (benchmark/checkpoints/
phi4flash.py) turns ONE Mamba-1 mixer on, layer 16's: with none on the
served path read 0.037 and a limit of 0.1 parted it from the attention
faults ten to one, but nothing of the scan or of the memory reached the
logits (`memory_after_gate`, `memory_shifted`, `no_decay` at 0.0002-0.012);
with all nine on it read 0.29 against 0.79.  A mixer is cubic in its input
and amplifies the rounding it inherits; one is the fewest that shows the
scan and the memory.
WHAT PASSES at this limit and should not: `cross_reads_layer_15` (0.2346,
0.1950, 0.1649, median 0.046): with the memory of order one the seven Gated
Memory Units add 1.0 a layer to the residual beside cross-attention's 0.14,
and WHICH layer's pages the cross half reads moves the logits no more than
the rounding does.  The cell's `why` says so; tests/test_phi4flash.py holds
it at a small size.
TIE_MARGIN 0.03: the served token's id is not visible to a client, so top-1
is compared with top-1; where the reference's top two lie closer than this
(4 of the 48 steps), bf16 may pick the other one, whose logprob is the
reference's second: that step is allowed the gap on top of the tolerance.

What these limits CANNOT see at full size (`assumed.weights` in the
configuration file): the checkpoint's one draw leaves `A_log` and the
step-size path near 0, so A is about -1 for every channel AND state index, a
step about 0.69, and a state forgets in about ten tokens: `scalar_decay`
computes the same numbers, and `state_not_carried` / `window_not_carried`
lose what the compared positions, 176 tokens and more past a 512-token
boundary, no longer feel.  tests/test_phi4flash.py (weights drawn as the
family initialises them) and scripts/check_selective_scan.py on the chip hold
those."""

# |served - reference| over the 48 probe steps on the chip (largest, next; my
# chip runs, PR 48, review round)
SERVED_READING = (0.217512, 0.081571)
# this file with `lower_precision=True` against itself at full size (largest,
# next, steps of 48 over LOGPROB_TOL; CPU, PR 48, review round)
CONTROL_READING = (0.550500, 0.526100, 12)

LOGPROB_TOL = 0.3
TIE_MARGIN = 0.03
ATTN_QUERY_BLOCK = 256  # queries a block of attention; a test lowers it
# tokens a serving chunk holds: where the faults that lose something between
# chunks lose it
FAULT_CHUNK = 512

FAULTS = (
    "ignore_window",          # the windowed layers see every earlier key
    "no_diff",                # lambda = 0: o = P1 V
    "no_subln",               # no rms norm over the pair's 128 values
    "cross_reads_layer_15",   # the cross layers take the LAST WINDOWED
                              # layer's k, v (layer L/2 - 1)
    "memory_after_gate",      # m = y * silu(z)
    "memory_shifted",         # m_t = y_{t-1}
    "layernorm_as_rmsnorm",   # no mean, no bias, in every LayerNorm
    "scalar_decay",           # A[c, n] = A[c, 0] for every n
    "no_decay",               # H_t = H_{t-1} + dt B x
    "state_not_carried",      # H starts from zero in every chunk
    "window_not_carried",     # the convolution sees zeros before every chunk
)

TENSORS = """model.embed_tokens.weight; model.layers.{l}.{input_layernorm,
post_attention_layernorm}.{weight, bias}; model.layers.{l}.mlp.{fc1,
fc2}.weight (fc1 [2 f, h]: the gate half first); Mamba-1 layers:
model.layers.{l}.attn.{in_proj, x_proj, out_proj}.weight, attn.conv1d.{weight
[d, 1, K], bias}, attn.dt_proj.{weight, bias}, attn.A_log [d, N], attn.D;
attention layers: attn.{Wqkv, out_proj}.{weight, bias}, attn.inner_cross_attn.
{lambda_q1, lambda_k1, lambda_q2, lambda_k2, subln.weight}; Gated Memory Units:
attn.{in_proj [d, h], out_proj [h, d]}.weight; cross-attention layers: as the
attention layers with attn.Wqkv [q, h] the queries' alone;
model.final_layernorm.{weight, bias}"""


def _silu(np, x):
    return x / (1.0 + np.exp(-x))


def _softplus(np, x):
    return np.logaddexp(x, 0.0)


def _softmax(np, s):
    s = s - s.max(axis=-1, keepdims=True)
    e = np.exp(s)
    return e / e.sum(axis=-1, keepdims=True)


def _round_mantissa(np, x, bits):
    """x with its float32 mantissa cut to `bits` bits (round to nearest):
    the control's lower precision, never used by the reference itself."""
    drop = 23 - bits
    i = np.ascontiguousarray(x, np.float32).view(np.uint32)
    i = (i + np.uint32(1 << (drop - 1))) & np.uint32(~((1 << drop) - 1)
                                                      & 0xFFFFFFFF)
    return i.view(np.float32)


def lambda_init(l):
    import math

    return 0.8 - 0.6 * math.exp(-0.3 * l)


def layer_kinds(model):
    """The mixer of each published layer index: "S" Mamba-1, "W" windowed,
    "F" full, "G" gated memory unit, "C" cross-attention."""
    L = model["num_hidden_layers"]
    half = L // 2
    if L < 8 or L % 4:
        raise ValueError(f"num_hidden_layers {L} must be a multiple of 4, "
                         "at least 8")
    def kind(l):
        if l <= half + 1:
            return "F" if l == half + 1 else "SW"[l % 2]
        return "GC"[l % 2]

    return "".join(kind(l) for l in range(L))


def sizes(model):
    h = model["hidden_size"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    dt_rank = model.get("mamba_dt_rank", "auto")
    return {"h": h, "f": model["intermediate_size"], "nq": nq, "nkv": nkv,
            "hd": model.get("head_dim") or h // nq,
            "d": model.get("mamba_expand", 2) * h,
            "N": model.get("mamba_d_state", 16),
            "K": model.get("mamba_d_conv", 4),
            "r": -(-h // 16) if dt_rank == "auto" else dt_rank,
            "W": model["sliding_window"], "eps": model["layer_norm_eps"]}


def check_model(model):
    layer_kinds(model)
    if model.get("mb_per_layer", 2) != 2:
        raise ValueError("only mb_per_layer 2 is written down here")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("only hidden_act silu is written down here")
    for key in ("mlp_bias", "lm_head_bias"):
        if model.get(key):
            raise ValueError(f"{key} is not written down here")
    if not model.get("tie_word_embeddings", True):
        raise ValueError("only the tied head is written down here")


def mamba(np, w, u, z_, faults=(), low=lambda a: a, chunk=FAULT_CHUNK):
    """The Mamba-1 mixer over u [B, T, h] (normed): the recurrence, one token
    after the other, from a zero state -> (out [B, T, h], m [B, T, d])."""
    B, T, _ = u.shape
    d, N, K, r = z_["d"], z_["N"], z_["K"], z_["r"]
    xz = low(u) @ w["in_proj"].T
    x, z = xz[..., :d], xz[..., d:]
    padded = np.concatenate([np.zeros((B, K - 1, d), np.float32), x], 1)
    if "window_not_carried" in faults:
        # the K-1 inputs before a chunk's first token read as zeros
        cols = [np.where(((np.arange(T) % chunk) + j >= K - 1)[None, :, None],
                         padded[:, j:j + T], 0.0) for j in range(K)]
    else:
        cols = [padded[:, j:j + T] for j in range(K)]
    x = _silu(np, w["conv_b"] + sum(
        c * w["conv_w"][:, j] for j, c in enumerate(cols)))
    dbc = low(x) @ w["x_proj"].T
    Bm, Cm = dbc[..., r:r + N], dbc[..., r + N:]
    dt = _softplus(np, low(dbc[..., :r]) @ w["dt_proj"].T + w["dt_bias"])
    A = -np.exp(w["A_log"])                                     # [d, N]
    if "scalar_decay" in faults:
        A = np.repeat(A[:, :1], N, axis=1)
    H = np.zeros((B, d, N), np.float32)
    y = np.empty((B, T, d), np.float32)
    for t in range(T):
        if t and t % chunk == 0 and "state_not_carried" in faults:
            H[:] = 0.0
        if "no_decay" not in faults:
            H *= np.exp(dt[:, t, :, None] * A)
        H += (dt[:, t] * x[:, t])[..., None] * Bm[:, t, None, :]
        y[:, t] = (H * Cm[:, t, None, :]).sum(-1)
    y = y + w["D"] * x
    gated = y * _silu(np, z)
    m = gated if "memory_after_gate" in faults else y
    if "memory_shifted" in faults:
        m = np.concatenate([np.zeros_like(m[:, :1]), m[:, :-1]], 1)
    return low(gated) @ w["out_proj"].T, m


def diff_attention(np, w, q, k, v, at, z_, l, window=None, faults=()):
    """Differential attention of layer l: queries q [B, Tq, nq, hd] at
    positions `at` [Tq] (ascending) over keys and values k, v [B, T, nkv,
    hd] -> o [B, Tq, nq * hd] before the output projection."""
    B, Tq = q.shape[:2]
    nq, nkv, hd = z_["nq"], z_["nkv"], z_["hd"]
    per = nq // nkv
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]          # [B, Tq, nq/2, hd]
    k1, k2 = k[:, :, 0::2], k[:, :, 1::2]          # [B, T, nkv/2, hd]
    V = np.concatenate([v[:, :, 0::2], v[:, :, 1::2]], -1)   # [.., 2 hd]
    # query pair j reads key/value pair j // per
    k1, k2, V = (np.repeat(a, per, axis=2).transpose(0, 2, 1, 3)
                 for a in (k1, k2, V))              # [B, nq/2, T, ..]
    q1, q2 = q1.transpose(0, 2, 1, 3), q2.transpose(0, 2, 1, 3)
    scale = np.float32(hd ** 0.5)
    init = np.float32(lambda_init(l))
    lam = (np.exp(np.dot(w["lq1"], w["lk1"])) - np.exp(np.dot(w["lq2"],
                                                              w["lk2"]))
           + init)
    if "no_diff" in faults:
        lam = np.float32(0.0)
    o = np.empty((B, nq // 2, Tq, 2 * hd), np.float32)
    for a0 in range(0, Tq, ATTN_QUERY_BLOCK):
        a1 = min(a0 + ATTN_QUERY_BLOCK, Tq)
        i = at[a0:a1, None]
        k0 = max(0, int(at[a0]) - window + 1) if window else 0
        kend = int(at[a1 - 1]) + 1
        j = np.arange(k0, kend)[None, :]
        mask = j <= i
        if window:
            mask = mask & (i - j < window)
        mask = mask[None, None]
        p1 = _softmax(np, np.where(
            mask, q1[:, :, a0:a1] @ k1[:, :, k0:kend].transpose(0, 1, 3, 2)
            / scale, -np.inf))
        p2 = _softmax(np, np.where(
            mask, q2[:, :, a0:a1] @ k2[:, :, k0:kend].transpose(0, 1, 3, 2)
            / scale, -np.inf))
        o[:, :, a0:a1] = (p1 @ V[:, :, k0:kend]
                          - lam * (p2 @ V[:, :, k0:kend]))
    if "no_subln" not in faults:
        var = np.mean(o * o, axis=-1, keepdims=True)
        o = o / np.sqrt(var + z_["eps"]) * w["subln"]
    o = o * (np.float32(1.0) - init)
    return o.transpose(0, 2, 1, 3).reshape(B, Tq, nq * hd)


def forward(read, model, batches, n_last, cross_everywhere=True,
            lower_precision=False, fault_chunk=FAULT_CHUNK, **faults):
    """`tail_logprobs` with the controls a test may switch on.
    `cross_everywhere` runs the cross half at every position (the plain
    form) and not at the returned ones alone; `lower_precision` rounds every
    matmul's operands outside attention and the head to 3 bits of mantissa;
    each keyword of `FAULTS` computes ONE thing wrong, the chunk-boundary
    ones every `fault_chunk` tokens."""
    import numpy as np

    check_model(model)
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    faults = {f for f, on in faults.items() if on}
    z_ = sizes(model)
    h, d, K, nq, nkv, hd = (z_[k] for k in ("h", "d", "K", "nq", "nkv", "hd"))
    kinds = layer_kinds(model)
    half = len(kinds) // 2
    low = ((lambda a: _round_mantissa(np, a, 3)) if lower_precision
           else (lambda a: a))

    def norm_of(prefix):
        wt, b = read(prefix + ".weight"), read(prefix + ".bias")

        def norm(x):
            if "layernorm_as_rmsnorm" in faults:
                return x / np.sqrt(np.mean(x * x, -1, keepdims=True)
                                   + z_["eps"]) * wt
            xc = x - x.mean(-1, keepdims=True)
            return xc / np.sqrt(np.mean(xc * xc, -1, keepdims=True)
                                + z_["eps"]) * wt + b

        return norm

    def lambdas(a):
        i = a + "inner_cross_attn."
        return {"lq1": read(i + "lambda_q1"), "lk1": read(i + "lambda_k1"),
                "lq2": read(i + "lambda_q2"), "lk2": read(i + "lambda_k2"),
                "subln": read(i + "subln.weight")}

    embed = read("model.embed_tokens.weight")
    xs = [embed[np.asarray(t)] for t in batches]                 # [B, T, h]
    ms, kvs, kvs_15 = None, None, None
    # positions a batch's cross half runs at
    ats = [np.arange(t.shape[1]) if cross_everywhere
           else np.arange(t.shape[1])[-n_last:] for t in map(np.asarray,
                                                             batches)]
    for l, kind in enumerate(kinds):
        p = f"model.layers.{l}."
        a = p + "attn."
        if l == half + 2:  # the cross half: at its own positions only
            xs = [x[:, at] for x, at in zip(xs, ats)]
            ms = [m[:, at] for m, at in zip(ms, ats)]
            if "cross_reads_layer_15" in faults:
                kvs = kvs_15
        us = list(map(norm_of(p + "input_layernorm"), xs))
        if kind == "S":
            w = {"in_proj": low(read(a + "in_proj.weight")),
                 "conv_w": read(a + "conv1d.weight").reshape(-1, K),
                 "conv_b": read(a + "conv1d.bias"),
                 "x_proj": low(read(a + "x_proj.weight")),
                 "dt_proj": low(read(a + "dt_proj.weight")),
                 "dt_bias": read(a + "dt_proj.bias"),
                 "A_log": read(a + "A_log"), "D": read(a + "D"),
                 "out_proj": low(read(a + "out_proj.weight"))}
            outs = [mamba(np, w, u, z_, faults, low, fault_chunk)
                    for u in us]
            ys = [o for o, _ in outs]
            if l == half:
                ms = [m for _, m in outs]
        elif kind in "WF":
            w = lambdas(a)
            wqkv, bqkv = low(read(a + "Wqkv.weight")), read(a + "Wqkv.bias")
            wo, bo = low(read(a + "out_proj.weight")), read(a + "out_proj.bias")
            window = (z_["W"] if kind == "W" and "ignore_window" not in faults
                      else None)
            ys, kept = [], []
            for u in us:
                B, T, _ = u.shape
                qkv = low(u) @ wqkv.T + bqkv
                q = qkv[..., :nq * hd].reshape(B, T, nq, hd)
                k = qkv[..., nq * hd:(nq + nkv) * hd].reshape(B, T, nkv, hd)
                v = qkv[..., (nq + nkv) * hd:].reshape(B, T, nkv, hd)
                o = diff_attention(np, w, q, k, v, np.arange(T), z_, l,
                                   window, faults)
                ys.append(low(o) @ wo.T + bo)
                kept.append((k, v))
            if kind == "F":
                kvs = kept
            elif l == half - 1:
                kvs_15 = kept
        elif kind == "G":
            w1, w2 = (low(read(a + "in_proj.weight")),
                      low(read(a + "out_proj.weight")))
            ys = [low(_silu(np, low(u) @ w1.T) * m) @ w2.T
                  for u, m in zip(us, ms)]
        else:
            w = lambdas(a)
            wq, bq = low(read(a + "Wqkv.weight")), read(a + "Wqkv.bias")
            wo, bo = low(read(a + "out_proj.weight")), read(a + "out_proj.bias")
            ys = []
            for u, (k, v), at in zip(us, kvs, ats):
                B, Tq, _ = u.shape
                q = (low(u) @ wq.T + bq).reshape(B, Tq, nq, hd)
                o = diff_attention(np, w, q, k, v, at, z_, l, None, faults)
                ys.append(low(o) @ wo.T + bo)
        xs = [x + y for x, y in zip(xs, ys)]
        fc1, fc2 = low(read(p + "mlp.fc1.weight")), low(read(p + "mlp.fc2.weight"))
        f, norm = fc2.shape[1], norm_of(p + "post_attention_layernorm")
        for i, x in enumerate(xs):
            gu = low(norm(x)) @ fc1.T
            xs[i] = x + low(_silu(np, gu[..., :f]) * gu[..., f:]) @ fc2.T
    out, norm = [], norm_of("model.final_layernorm")
    for x in xs:
        logits = norm(x[:, -n_last:]) @ embed.T
        logits = logits - logits.max(axis=-1, keepdims=True)
        out.append((logits - np.log(np.exp(logits).sum(
            axis=-1, keepdims=True))).astype(np.float32))
    return out


def tail_logprobs(read, model, batches, n_last):
    """batches: a list of int arrays [B, T] (rows of one batch have one
    length; batches may differ).  `read(name)` returns one checkpoint tensor
    as float32 numpy.  Returns, per batch, the float32 log-probabilities of
    the next token after each of the last `n_last` positions: [B, n_last,
    vocab].  The cross half runs on those positions alone (module
    docstring)."""
    return forward(read, model, batches, n_last, cross_everywhere=False)
