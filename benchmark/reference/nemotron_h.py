"""Plain reference forward of the Nemotron-H family (NVIDIA-Nemotron-3-Nano:
`model_type` "nemotron_h"): float32 numpy on the host CPU (BLAS sgemm: true
float32 products and sums), no cache, no kernels, no batching tricks, no
chunked scan: the state-space layers run their recurrence ONE TOKEN AT A
TIME, an expert multiplies the rows routed to it, one expert after the other.

Layer l of `num_hidden_layers` is `x <- x + mixer_l(rmsnorm(x, w_l))`, its
mixer named by character l of `hybrid_override_pattern`; then `norm_f` and an
untied `lm_head`.  (h = hidden_size; eps = `layer_norm_epsilon`.)

  M  Mamba-2.  d = mamba_num_heads x mamba_head_dim (NOT expand x h); G =
     n_groups; N = ssm_state_size; conv_dim = d + 2 G N.
       [z | xBC | dt] = in_proj(u)              d | conv_dim | heads, no bias
       xBC_t = silu(conv_b + sum_{j<K} conv_w[:, j] xBC_{t-K+1+j})
                                                depthwise, causal, zeros
                                                before the sequence
       [x | B | C] = xBC                        d | G N | G N; head i reads
                                                group i // (heads / G)
       dt = softplus(dt + dt_bias)              per head, no clamp
       A = -exp(A_log)                          per head
       H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t     [head_dim, N]
       y_t = H_t C_t + D x_t                    H before the sequence = 0
       y = rmsnorm over each of the G groups of (y * silu(z)), times w
                                                the gate FIRST, then the norm
       out = out_proj(y)                        d -> h, no bias
  E  logits = float32(u) Wr^T over ALL the layer's experts; s =
     sigmoid(logits); the k largest of s + e_score_correction_bias are chosen
     (n_group 1, topk_group 1: one group, always kept; wider grouping is the
     deepseek_v3 rule and is applied as such); weights = s[chosen] /
     sum(s[chosen]) x routed_scaling_factor;
     out = sum_e weight_e down_e(relu(up_e(u))^2) + down_s(relu(up_s(u))^2)
     NO gate matrix anywhere.
  *  q, k, v, o without bias; grouped-query heads; scale head_dim^-0.5;
     causal; NO positional encoding.

The chip's share: `n_routed_experts` counts the experts HELD, rank `ep_rank`
(default 0) of `ep_size`, global indices `ep_rank * n_routed_experts` on; the
router keeps its width `n_routed_experts * ep_size`; what the absent experts
would have added is left out, here as in the program, and that partial result
goes on to the next layer.  The shared expert is computed on every rank.

ASSUMED (the catalog row carries config.json's keys, not the code; each is in
the configuration file under `assumed`): the tensor names (`TENSORS` below);
`positions`: the attention applies no rotary embedding (`rope_theta` and
`partial_rotary_factor` are keys its code does not read); `state_dtype`: H is
float32; d = heads x head_dim; the gate-then-norm order; the convolution's
window carried in the served dtype.

Weights are streamed: `read(name)` returns one tensor as float32 numpy; one
layer's tensors (and ONE expert's two matrices) are alive at a time, and
every tensor is read once whatever the number of batches.

TOLERANCES — |served logprob - reference logprob| of the top-1 token, as
`benchmark/lib/probes.py` compares them over 40 steps (5 probe texts, 8
lengths each), from two readings at the cell's full size
(`nemotron3-nano-30b-ep8`; PERF.md, PR 44, has them with their origin), with
room on both sides:

  - the served path on the chip (bf16 weights and residual, float32
    accumulation and router scores, float32 recurrent state, the chunked
    scan): SERVED_READING below, the same to the sixth digit in every run
    (the probes come from `weights_seed`).  32 of the 40 steps read under
    0.081; the eight above are 0.1571, 0.1545, 0.1494, 0.1430, 0.1349,
    0.1285, 0.1097, 0.1097 (an earlier form of the same program, its scan's
    blocks under a `lax.scan`, read 0.1527, 0.1468, 0.1228, 0.1201, 0.1050:
    which near-ties flip moves with the program's rounding);
  - this file against itself with every matmul's operands outside attention
    rounded to 3 bits of mantissa (`lower_precision=True`: the nearest
    storage precision below bf16, fp8 e4m3's grid): CONTROL_READING below:
    0.2838, 0.2351, 0.2160, 0.2085 the largest, 17 of 40 steps over 0.1 and
    32 over 0.06.

As in the deepseek_v3 file, and at the same size (its served reading is
0.152), the chip's share makes the comparison coarse: where the bf16
program and this file choose a different last expert for a token, a HELD
expert comes or goes against an ABSENT one, a whole expert's output at
weight 2.5 / 6 = 0.42 beside the shared expert's 1, which cannot be taken
out without telling one side what the other chose.  Of the 32,821 (token,
layer) router choices of the probes 23% have a k-th gap under 0.0027 in
score and 4.8% under 0.0005 (`router_margins`, CPU, PR 44): a reader of a
failed probe can tell a near-tie from a fault by them.

LOGPROB_TOL 0.2 lies between 0.1571 and 0.2838: the served path reads 0.79
of what it is allowed, the lower precision fails by four steps (at 1.42,
1.18, 1.08 and 1.04 of it).  At 0.25 the control would fail by one step
only; at 0.16 the served path would read 0.98.  What the limit catches at
full size, this file against itself with one of `forward`'s faults (CPU, PR
44; largest step, steps of 40 over 0.2): a dropped `D` skip 0.739, 16;
relu not squared 0.511, 12; a gate's place on the experts 0.644, 25; B and
C of the wrong group 0.547, 9; the decay without its step size 0.405, 3.  NOT
caught at full size by any limit, because of the checkpoint's draw: the norm
before the gate (0.064, one step of 40 over 0.03), the norm over all 4096
values instead of each group's 512 (0.0002: under the one draw the eight
groups have nearly the same mean square), and the three faults that lose something
between chunks (below).  The tier-1 cases catch every one at a small size,
where the test draws the weights.

TIE_MARGIN 0.05.  The served token's id is not visible to a client, so top-1
is compared with top-1; where the reference's top two lie closer than this,
bf16 may pick the other one, whose logprob is the reference's second: that
step is allowed the gap on top of the tolerance.  The served path needs no
such allowance on these 40 steps (it passes at a margin of 0: the near-tied
steps differ by little), and from a margin of 0.16 on the lower precision
PASSES (its two largest steps have gaps of 0.155 and 0.160 and would be
forgiven them), which is why this is not the tolerance, as it is in the
deepseek_v3 file; 0.05 forgives the four steps whose gap is under it
(0.010-0.042) a flip and nothing else.

What these limits CANNOT see at full size (`assumed.weights` in the
configuration file): the checkpoint's one draw leaves `A_log` and `dt_bias`
near 0, so a state forgets in about ten tokens and the probes' compared
positions, 176 tokens and more past a 512-token boundary, do not feel a
state carried wrongly across one.  The tier-1 cases (tests/test_nemotron_h.py:
`A_log`, `dt_bias` drawn as the family initialises them) and the builder's
scratch checkpoint on the chip (PERF.md, PR 44) hold that.
"""

# |served - reference| over the 40 probe steps on the chip (largest, next;
# my chip runs, PR 44)
SERVED_READING = (0.157112, 0.154541)
# this file with `lower_precision=True` against itself at full size (largest,
# next, steps of 40 that fail at the two limits below; CPU, PR 44)
CONTROL_READING = (0.283850, 0.235112, 4)

LOGPROB_TOL = 0.2
TIE_MARGIN = 0.05

# tokens a serving chunk holds: where the `faults` that lose something
# between chunks lose it
FAULT_CHUNK = 512

FAULTS = (
    "state_not_carried",   # H starts from zero in every chunk
    "pad_advances_state",  # 16 pad positions decay H after every chunk
    "window_dropped",      # the convolution sees zeros before every chunk
    "norm_before_gate",    # rmsnorm(y) * silu(z)
    "norm_ungrouped",      # one rms over all d values, not one a group
    "no_d_skip",           # y_t = H_t C_t
    "relu_not_squared",    # down(relu(up(u)))
    "gated_experts",       # down(relu(up(u))^2 * up(u)): a gate's place
    "wrong_group",         # head i reads group i % G
    "decay_without_dt",    # H_t = exp(A) H_{t-1} + ...
)

TENSORS = """backbone.embeddings.weight; backbone.layers.{i}.norm.weight;
backbone.layers.{i}.mixer.{in_proj.weight, conv1d.weight [conv_dim, 1, K],
conv1d.bias, dt_bias, A_log, D, norm.weight, out_proj.weight} (M);
backbone.layers.{i}.mixer.{q_proj, k_proj, v_proj, o_proj}.weight (*);
backbone.layers.{i}.mixer.{gate.weight, gate.e_score_correction_bias,
experts.{e}.{up_proj, down_proj}.weight, shared_experts.{up_proj,
down_proj}.weight} (E); backbone.norm_f.weight; lm_head.weight"""


def _rms(np, x, w, eps):
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + eps) * w


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


def held_experts(model):
    n = model["n_routed_experts"]
    first = model.get("ep_rank", 0) * n
    return range(first, first + n)


def router_width(model):
    return model["n_routed_experts"] * model.get("ep_size", 1)


def check_model(model):
    L = model["num_hidden_layers"]
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != L or set(pattern) - set("M*E"):
        raise ValueError(f"hybrid_override_pattern must name {L} layers by "
                         f"M, * and E, got {pattern!r}")
    for key, want in (("mamba_hidden_act", "silu"),
                      ("mlp_hidden_act", "relu2")):
        if model.get(key, want) != want:
            raise ValueError(f"only {key} {want} is written down here")
    for key in ("use_bias", "mamba_proj_bias", "mlp_bias", "attention_bias"):
        if model.get(key):
            raise ValueError(f"{key} is not written down here")
    if not model.get("norm_topk_prob", True):
        raise ValueError("only normalised weights are written down here")


def mamba(np, w, u, model, faults=(), low=lambda a: a, chunk=FAULT_CHUNK):
    """The Mamba-2 mixer over u [B, T, h] (normed): the recurrence, one
    token after the other, from a zero state."""
    B, T, _ = u.shape
    nh, hp = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N, K = model["n_groups"], model["ssm_state_size"], model["conv_kernel"]
    d, eps = nh * hp, model["layer_norm_epsilon"]
    cd = d + 2 * G * N
    zxd = low(u) @ w["in_proj"].T
    z, xbc, dt = zxd[..., :d], zxd[..., d:d + cd], zxd[..., d + cd:]
    padded = np.concatenate([np.zeros((B, K - 1, cd), np.float32), xbc], 1)
    if "window_dropped" in faults:
        # the K-1 inputs before a chunk's first token read as zeros
        cols = [np.where(((np.arange(T) % chunk) + j >= K - 1)[None, :, None],
                         padded[:, j:j + T], 0.0) for j in range(K)]
    else:
        cols = [padded[:, j:j + T] for j in range(K)]
    xbc = _silu(np, w["conv_b"] + sum(
        c * w["conv_w"][:, j] for j, c in enumerate(cols)))
    x = xbc[..., :d].reshape(B, T, nh, hp)
    per = nh // G
    group = (np.arange(nh) % G if "wrong_group" in faults
             else np.arange(nh) // per)
    Bm = xbc[..., d:d + G * N].reshape(B, T, G, N)[:, :, group]  # [B,T,nh,N]
    Cm = xbc[..., d + G * N:].reshape(B, T, G, N)[:, :, group]
    dt = _softplus(np, dt + w["dt_bias"])                        # [B, T, nh]
    A = -np.exp(w["A_log"])                                      # [nh]
    decay = np.exp((np.ones_like(dt) if "decay_without_dt" in faults else dt)
                   * A)
    H = np.zeros((B, nh, hp, N), np.float32)
    y = np.empty((B, T, nh, hp), np.float32)
    pad_decay = np.exp(_softplus(np, w["dt_bias"]) * A * 16)
    for t in range(T):
        if t and t % chunk == 0:
            if "state_not_carried" in faults:
                H[:] = 0.0
            if "pad_advances_state" in faults:
                H *= pad_decay[None, :, None, None]
        H = (decay[:, t, :, None, None] * H
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * Bm[:, t, :, None, :])
        y[:, t] = np.einsum("bhpn,bhn->bhp", H, Cm[:, t])
    if "no_d_skip" not in faults:
        y = y + w["D"][:, None] * x
    y = y.reshape(B, T, d)
    gate = _silu(np, z)
    groups = 1 if "norm_ungrouped" in faults else G

    def grouped(v):
        parts = v.reshape(B, T, groups, -1)
        var = np.mean(parts * parts, axis=-1, keepdims=True)
        return (parts / np.sqrt(var + eps)).reshape(B, T, d)

    y = (grouped(y) * w["gate_norm"] * gate if "norm_before_gate" in faults
         else grouped(y * gate) * w["gate_norm"])
    return low(y) @ w["out_proj"].T


def attention(np, w, u, model):
    B, T, _ = u.shape
    nq, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    q = (u @ w["q"].T).reshape(B, T, nq, hd)
    k = (u @ w["k"].T).reshape(B, T, nkv, hd)
    v = (u @ w["v"].T).reshape(B, T, nkv, hd)
    k = np.repeat(k, nq // nkv, axis=2).transpose(0, 2, 3, 1)
    v = np.repeat(v, nq // nkv, axis=2).transpose(0, 2, 1, 3)
    s = (q.transpose(0, 2, 1, 3) @ k) / np.float32(hd ** 0.5)
    pos = np.arange(T)
    mask = pos[None, :] <= pos[:, None]
    p = _softmax(np, np.where(mask[None, None], s, -np.inf))
    o = (p @ v).transpose(0, 2, 1, 3).reshape(B, T, nq * hd)
    return o @ w["o"].T


def route(np, model, logits, bias):
    """(idx [..., k], weights [..., k]) of the `noaux_tc` choice over float32
    logits [..., W]: as `reference/deepseek_v3.py` `route`."""
    W, G = logits.shape[-1], model.get("n_group", 1)
    k = model["num_experts_per_tok"]
    s = 1.0 / (1.0 + np.exp(-logits))
    biased = s + bias
    grouped = biased.reshape(*biased.shape[:-1], G, W // G)
    if G > 1:
        top2 = np.sort(grouped, axis=-1)[..., -2:].sum(-1)
        best = np.argsort(-top2, axis=-1, kind="stable")[
            ..., :model.get("topk_group", 1)]
        keep = np.zeros(top2.shape, bool)
        np.put_along_axis(keep, best, True, axis=-1)
        grouped = np.where(keep[..., None], grouped, -np.inf)
    masked = grouped.reshape(biased.shape)
    order = np.argsort(-masked, axis=-1, kind="stable")
    idx = order[..., :k]
    chosen = np.take_along_axis(s, idx, -1)
    wts = (chosen / chosen.sum(-1, keepdims=True)
           * np.float32(model.get("routed_scaling_factor", 1.0)))
    gap = (np.take_along_axis(masked, idx[..., -1:], -1)
           - np.take_along_axis(masked, order[..., k:k + 1], -1))[..., 0]
    return idx, wts, gap


def _act(np, up, faults):
    r = np.maximum(up, 0.0)
    if "relu_not_squared" in faults:
        return r
    if "gated_experts" in faults:
        return r * r * up
    return r * r


def experts(np, read, prefix, model, us, faults=(), low=lambda a: a,
            shared=True, margins=None):
    """The expert feed-forward of the layer at `prefix` over each u of `us`
    ([B, T, h], normed): the held experts' part, and with `shared` the
    shared expert's."""
    router = low(read(prefix + "gate.weight"))
    bias = read(prefix + "gate.e_score_correction_bias")
    routed, ys = [], []
    for u in us:
        idx, wts, gap = route(np, model, low(u) @ router.T, bias)
        if margins is not None:
            margins.append(gap)
        routed.append((idx, wts))
        ys.append(np.zeros_like(u))
    for e in held_experts(model):
        up = low(read(prefix + f"experts.{e}.up_proj.weight"))
        down = low(read(prefix + f"experts.{e}.down_proj.weight"))
        for u, (idx, wts), y in zip(us, routed, ys):
            b, t, j = np.nonzero(idx == e)
            if not b.size:
                continue
            rows = low(u[b, t])
            np.add.at(y, (b, t), wts[b, t, j][:, None]
                      * (low(_act(np, rows @ up.T, faults)) @ down.T))
    if shared and model.get("n_shared_experts", 1):
        up = low(read(prefix + "shared_experts.up_proj.weight"))
        down = low(read(prefix + "shared_experts.down_proj.weight"))
        ys = [y + low(_act(np, low(u) @ up.T, faults)) @ down.T
              for u, y in zip(us, ys)]
    return ys


def forward(read, model, batches, n_last, lower_precision=False, faults=(),
            margins=None, fault_chunk=FAULT_CHUNK):
    """`tail_logprobs` with the controls a test may switch on:
    `lower_precision` rounds every matmul's operands outside attention to 3
    bits of mantissa; `faults` (of `FAULTS`) computes one thing wrong, the
    chunk-boundary ones every `fault_chunk` tokens; `margins`, a list,
    receives per expert layer and batch the [B, T] gap between the k-th and
    the (k+1)-th biased score."""
    import numpy as np

    check_model(model)
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps = model["layer_norm_epsilon"]
    K = model["conv_kernel"]
    low = ((lambda a: _round_mantissa(np, a, 3)) if lower_precision
           else (lambda a: a))
    embed = read("backbone.embeddings.weight")
    xs = [embed[np.asarray(t)] for t in batches]                 # [B, T, h]
    del embed
    for l, kind in enumerate(model["hybrid_override_pattern"]):
        p = f"backbone.layers.{l}."
        m = p + "mixer."
        norm = read(p + "norm.weight")
        us = [_rms(np, x, norm, eps) for x in xs]
        if kind == "M":
            w = {"in_proj": low(read(m + "in_proj.weight")),
                 "conv_w": read(m + "conv1d.weight").reshape(-1, K),
                 "conv_b": read(m + "conv1d.bias"),
                 "dt_bias": read(m + "dt_bias"), "A_log": read(m + "A_log"),
                 "D": read(m + "D"), "gate_norm": read(m + "norm.weight"),
                 "out_proj": low(read(m + "out_proj.weight"))}
            ys = [mamba(np, w, u, model, faults, low, fault_chunk)
                  for u in us]
        elif kind == "*":
            w = {n: read(m + f"{n}_proj.weight") for n in "qkvo"}
            ys = [attention(np, w, u, model) for u in us]
        else:
            ys = experts(np, read, m, model, us, faults, low,
                         margins=margins)
        xs = [x + y for x, y in zip(xs, ys)]
    norm = read("backbone.norm_f.weight")
    head = read("lm_head.weight").T
    out = []
    for x in xs:
        logits = _rms(np, x[:, -n_last:], norm, eps) @ head   # [B, n, vocab]
        logits = logits - logits.max(axis=-1, keepdims=True)
        out.append((logits - np.log(np.exp(logits).sum(
            axis=-1, keepdims=True))).astype(np.float32))
    return out


def tail_logprobs(read, model, batches, n_last):
    """batches: a list of int arrays [B, T] (rows of one batch have one
    length; batches may differ).  `read(name)` returns one checkpoint tensor
    as float32 numpy, and every tensor is read once, whatever the number of
    batches.  Returns, per batch, the float32 log-probabilities of the next
    token after each of the last `n_last` positions: [B, n_last, vocab]."""
    return forward(read, model, batches, n_last)


def router_margins(read, model, batches):
    """Per expert layer and batch, [B, T]: how far each token's k-th biased
    score lies above its (k+1)-th.  A margin under the stream's rounding is
    where a bf16 program and this file can choose a different last expert."""
    margins = []
    forward(read, model, batches, 1, margins=margins)
    return margins
