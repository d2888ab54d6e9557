"""Plain reference forward of the xing4_0 family (Xing4.0-29B-A4B): float32
numpy on the host CPU, no cache, no absorbed form, no kernels, no batching
tricks, computed in blocks.  The layers are deepseek_v3's (latent attention
behind a query bottleneck, `first_k_dense_replace` dense layers, then the
`noaux_tc` sigmoid router with a shared expert: `reference/deepseek_v3.py`,
whose helpers this file imports and whose equations it repeats without the
residual add); what is this family's own is the residual: `n = hc_mult`
streams X [n, H] a token, mixed around each half of a layer by
manifold-constrained hyper-connections.

  X_j = embed(token) for every stream j.
  Each half s of a layer (attention, feed-forward) has a mixer: fn [M, n H]
  (M = n^2 + 2n), scale [3], base [M].  Per token, `N` RMSNorm:
    v    = vec(X) (n H values);  r = (mean(v^2) + rms_norm_eps)^-1/2
    m    = r (fn v)                        a norm without a learned scale,
                                           applied after the product
    pre  = sigmoid(scale[0] m[:n]   + base[:n])   + hc_eps
    post = 2 sigmoid(scale[1] m[n:2n] + base[n:2n])
    R~   = clip(scale[2] mat(m[2n:]) + mat(base[2n:]),
                mhc_h_res_clamp_min, mhc_h_res_clamp_max)        n x n
    R    = softmax over the last index of R~, + hc_eps
    R    = R / (column sums + hc_eps), then hc_sinkhorn_iters - 1 times:
           R = R / (row sums + hc_eps);  R = R / (column sums + hc_eps)
           (a column sum runs over the first index, a row sum over the last)
    u    = sum_j pre_j X_j
    y    = F_s(N_s(u))     F: deepseek_v3's attention or feed-forward,
                           N_s the half's own norm weight
    X'_k = post_k y + sum_j R[j, k] X_j
  The head: m = r (fn_head v) (n values), w = sigmoid(scale m + base) +
  hc_eps, x = sum_j w_j X_j, then the final norm and lm_head.

ASSUMED, each by name in the configuration's file (the catalog row has the
config.json keys and a one-line description, not the code): the tensor
names (`hc_attn` / `hc_ffn` `.fn` `.scale` `.base` a layer, `model.hc_head`);
where hc_eps enters; that the first normalisation after the softmax runs
over the first index; the orientation of R in the last line (the paper
writes the transpose; both are doubly stochastic); the clip before the
exponential; mixers in float32; the multi-token-prediction module not
loaded; and deepseek_v3's own (interleaved rotary pairs, yarn's m^2 in the
softmax scale, masked groups).

Weights are streamed: `read(name)` returns one tensor as float32 numpy; one
layer's attention tensors and ONE expert's three matrices are alive at a
time, and every tensor is read once whatever the number of batches.

TOLERANCES, from two readings (PERF.md, PR 37, has every number).  A step
may differ by `LOGPROB_TOL`, and by the reference's own top-2 gap more where
that gap is under `TIE_MARGIN` (`benchmark/lib/probes.py`).  On the chip 29
of the 40 probe steps lie within 0.0241 of this file and eleven lie
0.036-0.530 off (0.530, 0.221, 0.221, 0.201, 0.169, 0.152 the largest).
Of the 16 steps of the first two probes, the four that are off are the four
where this file's OWN router has its nearest ties at the probe's last token
(4th against 5th biased score 0.0003-0.0015 apart in some layer; every step
whose margins all pass 0.0016 agrees; the served stream's bf16 rounding
moves a score by about 0.0007), and with every expert held and 4 of 64
chosen at weight 0.5 each, a flip swaps a quarter of the routed output for
another expert's:
as much as this draw's top-1 logprob moves when the WHOLE model is computed
at 3 bits of mantissa (largest 0.545).  A largest-difference limit alone
cannot part the two, the step's own gap can: the flipped steps have gaps of
0.02-0.37 (the served top-1 may be the runner-up there), so the margin is
the tolerance, as for deepseek_v3's share (PR 35), and the served path then
passes from 0.29 on (its tightest step: 0.530 off at a gap of 0.240) and
the lower-precision control up to 0.43.  0.36, the middle: the served path
passes by 0.070, the control fails by 0.076 and "res_transposed" by 0.069,
"post_without_2" by 0.495; NOT caught at full size by this limit, and
caught at the small size by tier-1 in float32: "one_sinkhorn_step" (passes
by 0.078), "head_mean" (0.280) and "no_clip" (the clip does not bind at unit
scale).  `benchmark/lib/probes.py` cannot tell a router near-tie from a
fault (PERF.md 7 (b)): until it can, this limit is as coarse as a flip.
"""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "reference_deepseek_v3",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "deepseek_v3.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

# |served - reference| over the 40 probe steps on the chip: the largest four
# of the eleven steps off by a router near-tie, and the largest of the other 29
SERVED_READING = (0.530334, 0.221447, 0.221122, 0.201114, 0.024074)
# this file with `lower_precision=True` against itself at full size: the
# largest three, and how many of 40 steps lie over 0.03
CONTROL_READING = (0.545482, 0.435531, 0.375133, 36)

LOGPROB_TOL = 0.36
TIE_MARGIN = 0.36

FAULTS = ("res_transposed", "one_sinkhorn_step", "post_without_2",
          "head_mean", "no_clip")

HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
           "mhc_h_res_clamp_max")


def check_model(model):
    base.check_model(model)
    for key in HC_KEYS:
        if model.get(key) is None:
            raise ValueError(f"{key} None: this family's residual is "
                             "hyper-connections")


def _sigmoid(np, x):
    return 1.0 / (1.0 + np.exp(-x))


def mix_logits(np, X, fn, rms_eps, low=lambda a: a):
    """r (fn v) of X [B, T, n, H] -> [B, T, M]."""
    B, T = X.shape[:2]
    v = X.reshape(B, T, -1)
    r = 1.0 / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + rms_eps)
    return r * (low(v) @ low(fn).T)


def sinkhorn(np, logits, iters, eps, clamp):
    """[..., n, n] logits -> R.  `clamp` None: a fault's missing clip."""
    if clamp is not None:
        logits = np.clip(logits, clamp[0], clamp[1])
    R = base._softmax(np, logits) + eps
    R = R / (R.sum(-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        R = R / (R.sum(-1, keepdims=True) + eps)
        R = R / (R.sum(-2, keepdims=True) + eps)
    return R


def mixer(np, model, X, w, faults=(), low=lambda a: a):
    """(pre [B, T, n], post [B, T, n], R [B, T, n, n]) of one half's mixer
    `w` = (fn, scale, base) over X [B, T, n, H]."""
    n, eps = model["hc_mult"], np.float32(model["hc_eps"])
    fn, scale, b = w
    m = mix_logits(np, X, fn, model["rms_norm_eps"], low)
    pre = _sigmoid(np, scale[0] * m[..., :n] + b[:n]) + eps
    post = _sigmoid(np, scale[1] * m[..., n:2 * n] + b[n:2 * n])
    if "post_without_2" not in faults:
        post = 2.0 * post
    logits = (scale[2] * m[..., 2 * n:] + b[2 * n:]).reshape(
        *m.shape[:-1], n, n)
    R = sinkhorn(
        np, logits,
        1 if "one_sinkhorn_step" in faults else model["hc_sinkhorn_iters"],
        eps, None if "no_clip" in faults else (
            model["mhc_h_res_clamp_min"], model["mhc_h_res_clamp_max"]))
    if "res_transposed" in faults:
        R = np.swapaxes(R, -1, -2)
    return pre.astype(np.float32), post.astype(np.float32), R.astype(
        np.float32)


def read_in(np, X, pre):
    """u = sum_j pre_j X_j: [B, T, H]."""
    return np.einsum("btn,btnh->bth", pre, X)


def write_back(np, X, y, post, R):
    """X'_k = post_k y + sum_j R[j, k] X_j."""
    return (post[..., None] * y[:, :, None, :]
            + np.einsum("btjk,btjh->btkh", R, X))


def forward(read, model, batches, n_last, lower_precision=False, faults=()):
    """`tail_logprobs` with the controls a test may switch on.
    `lower_precision` computes the model in the nearest storage precision
    below the bf16 the configuration states: the operands of EVERY product
    with a weight matrix (attention's projections, the dense feed-forward,
    the mixers, the router, the experts and the shared expert, the head),
    weights and the activations that enter them alike, are rounded to 3 bits
    of mantissa (fp8 e4m3's grid, without its range); attention's scores and
    sums, the norms and the mixers' sigmoids and Sinkhorn steps stay
    float32.
    `faults` names mistakes a comparison must catch: `FAULTS` here
    ("res_transposed": R applied as its transpose; "one_sinkhorn_step": 1
    step in place of `hc_sinkhorn_iters`; "post_without_2"; "head_mean": the
    head's reduction a plain mean of the streams; "no_clip": the logits of R
    not clipped) and deepseek_v3's own ("no_shared", "no_routed_scale",
    "no_mscale", "bias_in_weights", "ungrouped", "bf16_routing")."""
    import numpy as np

    check_model(model)
    n = model["hc_mult"]
    nh, r = model["num_attention_heads"], model["kv_lora_rank"]
    nope, pe, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    rs = model.get("rope_scaling")
    n_held = model["n_routed_experts"]
    first = model.get("ep_rank", 0) * n_held
    held = range(first, first + n_held)
    low = ((lambda a: base._round_mantissa(np, a, 3)) if lower_precision
           else (lambda a: a))
    rlow = ((lambda a: base._round_mantissa(np, a, 7))
            if "bf16_routing" in faults else low)
    rms, silu = base._rms, base._silu
    inv_freq = base.yarn_inv_freq(np, pe, theta, rs)
    amplitude, m_all = 1.0, 1.0
    if rs:
        factor = float(rs["factor"])
        m_all = base.get_mscale(np, factor,
                                float(rs.get("mscale_all_dim") or 0))
        amplitude = base.get_mscale(np, factor,
                                    float(rs.get("mscale", 1))) / m_all
    scale = np.float32((nope + pe) ** -0.5
                       * (1.0 if "no_mscale" in faults else m_all * m_all))

    embed = read("model.embed_tokens.weight")
    Xs = [np.repeat(embed[np.asarray(t)][:, :, None, :], n, axis=2)
          for t in batches]                                  # [B, T, n, H]
    tied = model.get("tie_word_embeddings", False)
    if not tied:
        del embed

    def mixer_of(prefix):
        return tuple(read(prefix + part) for part in ("fn", "scale", "base"))

    def attention(h, w):
        """Latent attention and its output projection of h = N(u)."""
        B, T, _ = h.shape
        pos = np.arange(T)
        causal = pos[None, :] <= pos[:, None]
        h = low(h)
        c_q = rms(np, h @ w["q_a"].T, w["q_ln"], eps)
        q = (low(c_q) @ w["q_b"].T).reshape(B, T, nh, nope + pe)
        ckv = h @ w["kv_a"].T                                # [B, T, r + pe]
        c_kv = rms(np, ckv[..., :r], w["kv_ln"], eps)
        k_pe = base._rope_pairs(np, ckv[..., None, r:], pos, inv_freq,
                                amplitude)                   # [B, T, 1, pe]
        q_pe = base._rope_pairs(np, q[..., nope:], pos, inv_freq, amplitude)
        kv_b = w["kv_b"].reshape(nh, nope + vd, r)
        out = np.empty((B, T, nh, vd), np.float32)
        block = max(1, min(nh, base._SCORE_BLOCK_BYTES
                           // max(1, B * T * T * 4)))
        for h0 in range(0, nh, block):
            hs = slice(h0, h0 + block)
            kv = np.einsum("btr,hdr->bthd", low(c_kv), kv_b[hs])
            k_nope, v = kv[..., :nope], kv[..., nope:]
            s = (np.einsum("bqhd,bkhd->bhqk", q[:, :, hs, :nope], k_nope)
                 + np.einsum("bqhd,bkd->bhqk", q_pe[:, :, hs],
                             k_pe[:, :, 0])) * scale
            p = base._softmax(np, np.where(causal[None, None], s, -np.inf))
            out[:, :, hs] = np.einsum("bhqk,bkhd->bqhd", p, v)
        return low(out.reshape(B, T, nh * vd)) @ w["o"].T

    def swiglu(rows, gate, up, down):
        return low(silu(np, rows @ gate.T) * (rows @ up.T)) @ down.T

    for l in range(model["num_hidden_layers"]):
        p = f"model.layers.{l}."
        a = p + "self_attn."
        # -- the attention half
        mw = mixer_of(p + "hc_attn.")
        mixes = [mixer(np, model, X, mw, faults, low) for X in Xs]
        ln1 = read(p + "input_layernorm.weight")
        w = {"q_a": low(read(a + "q_a_proj.weight")),
             "q_ln": read(a + "q_a_layernorm.weight"),
             "q_b": low(read(a + "q_b_proj.weight")),
             "kv_a": low(read(a + "kv_a_proj_with_mqa.weight")),
             "kv_ln": read(a + "kv_a_layernorm.weight"),
             "kv_b": low(read(a + "kv_b_proj.weight")),
             "o": low(read(a + "o_proj.weight"))}
        Xs = [write_back(np, X, attention(
            rms(np, read_in(np, X, mx[0]), ln1, eps), w), mx[1], mx[2])
            for X, mx in zip(Xs, mixes)]
        del w
        # -- the feed-forward half
        mw = mixer_of(p + "hc_ffn.")
        mixes = [mixer(np, model, X, mw, faults, low) for X in Xs]
        ln2 = read(p + "post_attention_layernorm.weight")
        us = [rms(np, read_in(np, X, mx[0]), ln2, eps)
              for X, mx in zip(Xs, mixes)]
        if l < model["first_k_dense_replace"]:
            gate, up, down = (low(read(p + f"mlp.{k}_proj.weight"))
                              for k in ("gate", "up", "down"))
            ys = [swiglu(low(u), gate, up, down) for u in us]
            del gate, up, down
        else:
            router = rlow(read(p + "mlp.gate.weight"))
            bias = read(p + "mlp.gate.e_score_correction_bias")
            routed, ys = [], []
            for u in us:
                logits = rlow(u) @ router.T                    # [B, T, W]
                if "bf16_routing" in faults:
                    logits = rlow(logits)
                routed.append(base.route(np, model, logits, bias, faults))
                ys.append(np.zeros_like(u))
            us = [low(u) for u in us]
            for e in held:
                x_ = p + f"mlp.experts.{e}."
                gate, up, down = (low(read(x_ + f"{k}_proj.weight"))
                                  for k in ("gate", "up", "down"))
                for u, (idx, wts), y in zip(us, routed, ys):
                    b, t, j = np.nonzero(idx == e)
                    if b.size:
                        np.add.at(y, (b, t), wts[b, t, j][:, None]
                                  * swiglu(u[b, t], gate, up, down))
            if "no_shared" not in faults and model.get("n_shared_experts"):
                x_ = p + "mlp.shared_experts."
                gate, up, down = (low(read(x_ + f"{k}_proj.weight"))
                                  for k in ("gate", "up", "down"))
                ys = [y + swiglu(u, gate, up, down) for u, y in zip(us, ys)]
        Xs = [write_back(np, X, y, mx[1], mx[2])
              for X, y, mx in zip(Xs, ys, mixes)]

    fn, hscale, hbase = mixer_of("model.hc_head.")
    norm = read("model.norm.weight")
    head = low(embed if tied else read("lm_head.weight")).T
    out = []
    for X in Xs:
        X = X[:, -n_last:]
        if "head_mean" in faults:
            x = X.mean(axis=2)
        else:
            m = mix_logits(np, X, fn, eps, low)
            x = read_in(np, X, (_sigmoid(np, hscale[0] * m + hbase)
                                + np.float32(model["hc_eps"])).astype(
                                    np.float32))
        logits = low(rms(np, x, norm, eps)) @ head         # [B, n, vocab]
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
