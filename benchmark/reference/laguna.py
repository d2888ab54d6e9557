"""Plain reference forward of the Laguna family (poolside Laguna-XS.2,
`model_type` "laguna"): float32 numpy on the host CPU (BLAS sgemm: true
float32 products and sums, so no `highest`-precision switch is needed as it
would be on a TPU), no KV cache, no kernels, no batching tricks, no dispatch:
an expert multiplies the rows routed to it, one expert after the other, and
only a token's own 8 experts touch it.

One layer `l`, input x [T, hidden] (RMSNorm eps from the config, no biases),
H_l = num_attention_heads_per_layer[l], kind = layer_types[l]:

  1. n = rmsnorm(x, w_in); q = n Wq [T, H_l, hd], k = n Wk, v = n Wv [T,
     n_kv, hd]
  2. rope_parameters[kind]: rotate the FIRST hd * partial_rotary_factor
     values of every head (rotate-half among themselves), the rest pass;
     "yarn": HF's `_compute_yarn_parameters` over the rotated width, cos and
     sin times `attention_factor`; "default": theta alone
  3. causal GQA attention, scale 1/sqrt(hd), a KV head serving H_l / n_kv
     query heads; "sliding_attention": key j visible to query i iff i -
     sliding_window < j <= i
  4. g = sigmoid(n Wg) [T, H_l]: one scalar a head; x = x + (g * a) Wo
  5. m = rmsnorm(x, w_post); mlp_layer_types[l] "dense":
     x = x + Wd(silu(Wg m) * (Wu m)); "sparse": logits = m Wr (float32),
     p = softmax(logits), the top-8 of p renormalised to sum 1, times
     moe_routed_scaling_factor; x = x + sum_j p_j expert_j(m) + shared(m),
     every expert and the shared one a SwiGLU
  6. after the last layer: rmsnorm, untied head.

FOUR READINGS the published config does not settle (the configuration's
`assumed` has the evidence), each a keyword of `forward` that switches to
the OTHER reading: the gate is per head (`gate_over_values`: one a value),
the router scores by softmax (`sigmoid_router`), the shared expert is added
ungated, q and k are not normalised.  The rest of the keywords are controls:
each takes one mechanism out, and `correct` has to fail on it.

Weights are streamed: `read(name)` returns one tensor as float32 numpy; one
layer's attention tensors and ONE expert's three matrices are alive at a
time, and every tensor is read once whatever the number of batches.

TOLERANCES — |served logprob - reference logprob| of the top-1 token, as
`benchmark/lib/probes.py` compares them over 48 steps (6 probe texts of 48,
48, 48, 48, 1,200 and 6,400 tokens, 8 lengths each).  Both are set from
readings at the cell's full size (`laguna-xs2-33b-h7`; PERF.md, PR 52, has
the whole table with its origin), with room on both sides:

  - the served path on the chip (bf16 weights, residual and pages, float32
    accumulation): largest difference 0.0429, largest on a step whose top-2
    gap is clear 0.0396; the same in every run, since the probes come from
    `weights_seed`.  Where it comes from: a token's 8th and 9th router
    logits lie closer than the bf16 rounding of the stream they are computed
    from, the two sides choose a different eighth expert for that token in
    that layer, and that swaps one of eight WHOLE experts (weights near 2.5 /
    8 each), not a held one for an absent one (PERF.md findings 16 and 20);
    `router_margins` reports the near-ties;
  - this file against itself, every keyword of `forward` in turn (CPU, the
    cell's checkpoint): `lower_precision` 0.0847 (0.0731 on a clear step),
    `sigmoid_router` 0.0916, `no_routed_scale` 0.2312, `no_attention_factor`
    0.2342, `no_shared_expert` 0.2913, `ignore_window` 0.3257 (the 1,200-
    and 6,400-token probes alone), `full_rotary` 0.3839, `one_rope` 0.4006,
    `dense_layer_as_expert` 0.4544, `no_gate` 0.4664, `equal_heads` 0.4779,
    `gate_over_values` 0.6567.

LOGPROB_TOL 0.055 lies between 0.0429 and 0.0847: the served path's largest
step is 0.0154 inside what it is allowed, the lower precision fails 4 of 48
steps (the largest 0.0181 past), and the mildest control that stands for a
missing mechanism, the routed scale, reads 4.2 times the limit (17 steps).
TIE_MARGIN 0.055: where the reference's top two lie closer than the
tolerance, bf16 may pick the other one, whose logprob is the reference's
second: that step is allowed the gap on top of the tolerance (never more
than the gap); 16 of the 48 steps have such a gap.
"""

LOGPROB_TOL = 0.055
TIE_MARGIN = 0.055
ATTN_QUERY_BLOCK = 512  # queries a block of attention; a test lowers it

CONTROLS = ("lower_precision", "ignore_window", "one_rope", "full_rotary",
            "no_attention_factor", "no_gate", "gate_over_values",
            "sigmoid_router", "no_routed_scale", "no_shared_expert",
            "equal_heads", "dense_layer_as_expert")


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


def rope_table(np, rp, head_dim, full_rotary=False, no_attention_factor=False):
    """(inv_freq [rotated / 2], amplitude) of one layer kind's rope."""
    import math

    share = 1.0 if full_rotary else float(rp.get("partial_rotary_factor", 1))
    dim, base = int(head_dim * share), float(rp["rope_theta"])
    inv = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    if rp.get("rope_type", "default") == "default":
        return inv.astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written down "
                         "here")
    factor, orig = float(rp["factor"]), float(
        rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rp.get("beta_slow", 1)))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    inv = (inv / factor) * ramp + inv * (1.0 - ramp)
    amp = rp.get("attention_factor")
    if amp is None:
        amp = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv.astype(np.float32), (1.0 if no_attention_factor
                                    else float(amp))


def _rope(np, x, pos, inv, amp):
    # x [B, T, n, hd]; rotate-half over the head's first 2 * len(inv) values
    d = 2 * inv.shape[0]
    ang = pos[:, None].astype(np.float32) * inv[None, :]        # [T, d/2]
    cos = (np.concatenate([np.cos(ang), np.cos(ang)], -1)
           * np.float32(amp))[None, :, None, :]
    sin = (np.concatenate([np.sin(ang), np.sin(ang)], -1)
           * np.float32(amp))[None, :, None, :]
    rot = x[..., :d]
    x1, x2 = rot[..., : d // 2], rot[..., d // 2:]
    out = rot * cos + np.concatenate([-x2, x1], -1) * sin
    return np.concatenate([out, x[..., d:]], -1)


def _round_mantissa(np, x, bits):
    """x with its float32 mantissa cut to `bits` bits (round to nearest):
    the control's lower precision, never used by the reference itself."""
    drop = 23 - bits
    i = np.ascontiguousarray(x, np.float32).view(np.uint32)
    i = (i + np.uint32(1 << (drop - 1))) & np.uint32(~((1 << drop) - 1)
                                                      & 0xFFFFFFFF)
    return i.view(np.float32)


def check_model(model):
    L = model["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(model[key]) != L:
            raise ValueError(f"{key} has {len(model[key])} entries for "
                             f"{L} layers")
    if model.get("gating") is not True:
        raise ValueError("only gating true is written down here")
    if model.get("tie_word_embeddings"):
        raise ValueError("only the untied head is written down here")
    for key in ("moe_apply_router_weight_on_input",
                "moe_router_logit_softcapping", "attention_bias"):
        if model.get(key):
            raise ValueError(f"{key} is not written down here")


def forward(read, model, batches, n_last, lower_precision=False,
            ignore_window=False, one_rope=False, full_rotary=False,
            no_attention_factor=False, no_gate=False, gate_over_values=False,
            sigmoid_router=False, no_routed_scale=False,
            no_shared_expert=False, equal_heads=False,
            dense_layer_as_expert=False, margins=None):
    """`tail_logprobs` with the controls a test may switch on, one mechanism
    each.  `lower_precision` rounds the router's and the experts' operands to
    3 bits of mantissa (the nearest storage precision below the bf16 the
    configuration states: fp8 e4m3's grid, without its range);
    `ignore_window` lets the windowed layers see every earlier key;
    `one_rope` gives every layer the windowed kind's table; `full_rotary`
    rotates the whole head in every layer; `no_attention_factor` leaves
    yarn's amplitude out; `no_gate` multiplies by no gate; `gate_over_values`
    (the other reading of `gating`) gates every VALUE of a head by a scalar
    of its own (value d of head n from the head's gate vector rolled by d: a
    [hidden, H_l * hd] gate of the same draw); `sigmoid_router` (the other
    reading of the router) scores by sigmoid, normalised over the chosen;
    `no_routed_scale` leaves `moe_routed_scaling_factor` out;
    `no_shared_expert` the shared expert; `equal_heads` lets only the first
    min(H) heads of every layer reach `Wo`; `dense_layer_as_expert` gives the
    dense feed-forward's output the routed scale, as a loop that took it for
    one always-chosen expert would.  `margins`, a list, receives per sparse
    layer and batch the [B, T] gap between the k-th and (k+1)-th router
    logit."""
    import numpy as np

    check_model(model)
    nkv, hd = model["num_key_value_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    E, k = model["num_experts"], model["num_experts_per_tok"]
    scale = 1.0 if no_routed_scale else float(
        model.get("moe_routed_scaling_factor", 1.0))
    wsize = model["sliding_window"]
    fewest = min(model["num_attention_heads_per_layer"])
    low = ((lambda a: _round_mantissa(np, a, 3)) if lower_precision
           else (lambda a: a))
    tables = {
        kind: rope_table(np, rp, hd, full_rotary, no_attention_factor)
        for kind, rp in model["rope_parameters"].items()
        if isinstance(rp, dict)}
    embed = read("model.embed_tokens.weight")
    xs = [embed[np.asarray(t)] for t in batches]                 # [B, T, H]
    del embed

    def attend(x, w, nq, table, window):
        B, T, _ = x.shape
        pos = np.arange(T)
        a = _rms(np, x, w["ln1"], eps)
        q = (a @ w["q"].T).reshape(B, T, nq, hd)
        kk = (a @ w["k"].T).reshape(B, T, nkv, hd)
        v = (a @ w["v"].T).reshape(B, T, nkv, hd)
        q, kk = _rope(np, q, pos, *table), _rope(np, kk, pos, *table)
        # grouped-query: each KV head serves nq // nkv query heads
        q = q.transpose(0, 2, 1, 3)
        kk = np.repeat(kk, nq // nkv, axis=2).transpose(0, 2, 3, 1)
        v = np.repeat(v, nq // nkv, axis=2).transpose(0, 2, 1, 3)
        o = np.empty((B, nq, T, hd), np.float32)
        # ATTN_QUERY_BLOCK queries at a time against the keys they can see,
        # so that a probe of thousands of tokens never holds a [T, T] score
        # matrix a head; a key outside the slice is one the mask would hide
        for a0 in range(0, T, ATTN_QUERY_BLOCK):
            a1 = min(a0 + ATTN_QUERY_BLOCK, T)
            k0 = max(0, a0 - window + 1) if window else 0
            i, j = pos[a0:a1, None], pos[None, k0:a1]
            mask = j <= i
            if window:
                mask = mask & (i - j < window)
            s = (q[:, :, a0:a1] @ kk[..., k0:a1]) / np.float32(hd ** 0.5)
            p = _softmax(np, np.where(mask[None, None], s, -np.inf))
            o[:, :, a0:a1] = p @ v[:, :, k0:a1]
        o = o.transpose(0, 2, 1, 3)                         # [B, T, nq, hd]
        if gate_over_values:
            g = np.stack([_sigmoid(np, a @ np.roll(w["g"], d, axis=1).T)
                          for d in range(hd)], -1)           # [B, T, nq, hd]
            o = o * g
        elif not no_gate:
            o = o * _sigmoid(np, a @ w["g"].T)[..., None]
        if equal_heads:
            o[:, :, fewest:] = 0.0
        return x + o.reshape(B, T, nq * hd) @ w["o"].T

    def swiglu(rows, gate, up, down):
        return low(_silu(np, rows @ gate.T) * (rows @ up.T)) @ down.T

    for l in range(model["num_hidden_layers"]):
        p = f"model.layers.{l}."
        w = {"ln1": read(p + "input_layernorm.weight"),
             "q": read(p + "self_attn.q_proj.weight"),
             "k": read(p + "self_attn.k_proj.weight"),
             "v": read(p + "self_attn.v_proj.weight"),
             "o": read(p + "self_attn.o_proj.weight"),
             "g": read(p + "self_attn.g_proj.weight")}
        kind = model["layer_types"][l]
        windowed = kind == "sliding_attention"
        table = tables["sliding_attention" if one_rope else kind]
        window = wsize if windowed and not ignore_window else None
        nq = model["num_attention_heads_per_layer"][l]
        hs = [attend(x, w, nq, table, window) for x in xs]
        del w
        ln2 = read(p + "post_attention_layernorm.weight")
        if model["mlp_layer_types"][l] == "dense":
            gate, up, down = (read(p + f"mlp.{n}_proj.weight")
                              for n in ("gate", "up", "down"))
            by = np.float32(scale if dense_layer_as_expert else 1.0)
            xs = [h + by * (_silu(np, m @ gate.T) * (m @ up.T)) @ down.T
                  for h in hs for m in (_rms(np, h, ln2, eps),)]
            continue
        router = low(read(p + "mlp.gate.weight"))
        ms, routed, ys = [], [], []
        for h in hs:
            m = low(_rms(np, h, ln2, eps))
            logits = m @ router.T                              # [B, T, E]
            order = np.argsort(-logits, axis=-1, kind="stable")
            idx = order[..., :k]
            top = np.take_along_axis(logits, idx, -1)
            if margins is not None:
                nxt = np.take_along_axis(logits, order[..., k:k + 1], -1)
                margins.append(top[..., -1] - nxt[..., 0])
            if sigmoid_router:
                score = _sigmoid(np, top)
                weight = score / score.sum(-1, keepdims=True)
            else:  # softmax over all, the chosen renormalised
                weight = _softmax(np, top)
            ms.append(m)
            routed.append((idx, weight * np.float32(scale)))
            ys.append(np.zeros_like(h))
        for e in range(E):
            x_ = p + f"mlp.experts.{e}."
            gate, up, down = (low(read(x_ + f"{n}_proj.weight"))
                              for n in ("gate", "up", "down"))
            for m, (idx, pr), y in zip(ms, routed, ys):
                b, t, j = np.nonzero(idx == e)
                if not b.size:
                    continue
                np.add.at(y, (b, t), pr[b, t, j][:, None]
                          * swiglu(m[b, t], gate, up, down))
        if not no_shared_expert:
            x_ = p + "mlp.shared_expert."
            gate, up, down = (low(read(x_ + f"{n}_proj.weight"))
                              for n in ("gate", "up", "down"))
            ys = [y + swiglu(m, gate, up, down) for m, y in zip(ms, ys)]
        xs = [h + y for h, y in zip(hs, ys)]
    norm = read("model.norm.weight")
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
    """Per sparse layer and batch, [B, T]: how far each token's k-th router
    logit lies above its (k+1)-th.  A margin under the stream's rounding is
    where a bf16 program and this file can choose a different last expert."""
    margins = []
    forward(read, model, batches, 1, margins=margins)
    return margins
