"""Plain reference forward of the SmallThinker family (PowerInfer
SmallThinker-21BA3B-Instruct): float32 numpy on the host CPU (BLAS sgemm:
true float32 products and sums, so no `highest`-precision switch is needed
as it would be on a TPU), no KV cache, no kernels, no batching tricks, no
dispatch: an expert multiplies the rows routed to it, one expert after the
other.

One layer `l`, input x [T, hidden] (RMSNorm eps from the config, no biases,
no QK-norm):

  1. r = x                       the router reads the layer's own input
  2. a = rmsnorm(x, w_in); q = a Wq, k = a Wk, v = a Wv
  3. rope_layout[l] == 1: rotate q, k (rotate-half over the whole head,
     rope_theta, no scaling); 0: leave them, the layer sees no positions
  4. causal GQA attention, scale 1/sqrt(head_dim); sliding_window_layout[l]
     == 1: key j visible to query i iff i - window < j <= i; 0: every j <= i
  5. h = x + attn Wo; m = rmsnorm(h, w_post)
  6. logits = r Wr (float32); (v6, idx) = top-k(logits); p = softmax(v6)
  7. y = sum_j p_j Wdown[idx_j]( relu(Wgate[idx_j] m) * (Wup[idx_j] m) )
  8. x' = h + y;  after the last layer: rmsnorm, untied head.

Departures from the published modelling code, each ASSUMED (the catalog row
has the config.json keys and a one-line description, not the code): the
router's input is the un-normalised residual stream; softmax over the chosen
k (the published "softmax over all, then renormalise the chosen" gives the
same numbers); `moe_primary_router_apply_softmax` false (the 4B's sigmoid
router) and `rope_scaling` are refused, not guessed.

Weights are streamed: `read(name)` returns one tensor as float32 numpy; one
layer's attention tensors and ONE expert's three matrices are alive at a
time, and every tensor is read once whatever the number of batches.

TOLERANCES — |served logprob - reference logprob| of the top-1 token, as
`benchmark/lib/probes.py` compares them over 40 steps (5 probe texts, 8
lengths each).  Both are set from two readings at the cell's full size
(`smallthinker-21b-h12`; PERF.md, PR 31, has them with their origin), with
room on both sides, and not from the dense family's 0.06:

  - the served path on the chip (bf16 weights and residual stream, float32
    accumulation): largest difference 0.0187, the next 0.0118; the same in
    every run, since the probes come from `weights_seed`;
  - this file against itself with the router's and the experts' operands
    rounded to 3 bits of mantissa (`lower_precision=True`: the nearest
    storage precision below bf16, fp8 e4m3's grid): largest 0.0410, ten
    steps over 0.02; and with the last chosen expert left out
    (`drop_last_expert=True`): largest 0.0667, median 0.0212.

LOGPROB_TOL 0.03 lies between 0.0187 and 0.0410: the served path reads at
most 0.62 of what it is allowed, the lower precision fails 2 of 40 steps,
the dropped expert 11.  At 0.06 both controls would have passed: this
family's top-1 logprob moves less under a perturbation than the dense
family's, because with random weights its router reads an un-normalised
stream and sends most tokens of a text to the same experts.

The router is where a bf16 program and a float32 reference can part: where
a token's k-th and (k+1)-th logits lie closer than the rounding of the
stream they are computed from, the two sides choose a different last expert
for that token in that layer, and with six near-equal weights that swaps a
sixth of one layer's expert output of one token.  It cannot be taken out
without telling one side what the other chose, so it is inside the served
reading above (and is why that reading's largest step stands out from the
rest); `router_margins` reports the near-ties, so that a reader of a failed
probe can tell one from a fault.

TIE_MARGIN 0.03.  The served token's id is not visible to a client, so
top-1 is compared with top-1; where the reference's top two lie closer than
the tolerance, bf16 may pick the other one, whose logprob is the
reference's second: that step is allowed the gap on top of the tolerance
(never more than the gap).  A quarter of the cell's steps have such a gap.
At the dense family's 0.06 half of them would, and the dropped expert's two
steps over 0.06 hid behind it.
"""

LOGPROB_TOL = 0.03
TIE_MARGIN = 0.03
ATTN_QUERY_BLOCK = 512  # queries a block of attention; a test lowers it


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


def _round_mantissa(np, x, bits):
    """x with its float32 mantissa cut to `bits` bits (round to nearest):
    the control's lower precision, never used by the reference itself."""
    drop = 23 - bits
    i = np.ascontiguousarray(x, np.float32).view(np.uint32)
    i = (i + np.uint32(1 << (drop - 1))) & np.uint32(~((1 << drop) - 1)
                                                      & 0xFFFFFFFF)
    return i.view(np.float32)


def check_model(model):
    if not model.get("moe_primary_router_apply_softmax", True):
        raise ValueError("only the softmax router is written down here")
    if model.get("rope_scaling"):
        raise ValueError("rope_scaling is not written down here")
    L = model["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        if len(model[key]) != L:
            raise ValueError(f"{key} has {len(model[key])} entries for "
                             f"{L} layers")


def forward(read, model, batches, n_last, lower_precision=False,
            drop_last_expert=False, ignore_window=False, margins=None):
    """`tail_logprobs` with the controls a test may switch on:
    `lower_precision` rounds the router's and the experts' operands to 3
    bits of mantissa (the nearest storage precision below the bf16 the
    configuration states: fp8 e4m3's grid, without its range); `drop_last_expert` leaves
    the k-th expert out; `ignore_window` lets the windowed layers see every
    earlier key (the fault a context past `sliding_window_size` is there to
    catch: below it the two agree to the bit); `margins`, a list, receives per layer and batch the
    [B, T] gap between the k-th and the (k+1)-th router logit."""
    import numpy as np

    check_model(model)
    nq, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    E, k = (model["moe_num_primary_experts"],
            model["moe_num_active_primary_experts"])
    wsize = model["sliding_window_size"]
    low = ((lambda a: _round_mantissa(np, a, 3)) if lower_precision
           else (lambda a: a))
    embed = read("model.embed_tokens.weight")
    xs = [embed[np.asarray(t)] for t in batches]                 # [B, T, H]
    tied = model.get("tie_word_embeddings", False)
    if not tied:
        del embed

    def attend(x, w, rotate, window):
        B, T, _ = x.shape
        pos = np.arange(T)
        a = _rms(np, x, w["ln1"], eps)
        q = (a @ w["q"].T).reshape(B, T, nq, hd)
        kk = (a @ w["k"].T).reshape(B, T, nkv, hd)
        v = (a @ w["v"].T).reshape(B, T, nkv, hd)
        if rotate:
            q, kk = _rope(np, q, pos, theta), _rope(np, kk, pos, theta)
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
        return x + o.transpose(0, 2, 1, 3).reshape(B, T, nq * hd) @ w["o"].T

    for l in range(model["num_hidden_layers"]):
        p = f"model.layers.{l}."
        w = {"ln1": read(p + "input_layernorm.weight"),
             "q": read(p + "self_attn.q_proj.weight"),
             "k": read(p + "self_attn.k_proj.weight"),
             "v": read(p + "self_attn.v_proj.weight"),
             "o": read(p + "self_attn.o_proj.weight")}
        rotate = bool(model["rope_layout"][l])
        window = (wsize if model["sliding_window_layout"][l]
                  and not ignore_window else None)
        router = low(read(p + "block_sparse_moe.primary_router.weight"))
        ln2 = read(p + "post_attention_layernorm.weight")
        hs, ms, routed, ys = [], [], [], []
        for x in xs:
            h = attend(x, w, rotate, window)
            logits = low(x) @ router.T                    # [B, T, E]: r = x
            order = np.argsort(-logits, axis=-1, kind="stable")
            idx = order[..., :k]
            top = np.take_along_axis(logits, idx, -1)
            if margins is not None:
                nxt = np.take_along_axis(logits, order[..., k:k + 1], -1)
                margins.append(top[..., -1] - nxt[..., 0])
            hs.append(h)
            ms.append(low(_rms(np, h, ln2, eps)))
            routed.append((idx, _softmax(np, top)))
            ys.append(np.zeros_like(h))
        del w
        for e in range(E):
            x_ = p + f"block_sparse_moe.experts.{e}."
            up, gate, down = (low(read(x_ + "up.weight")),
                              low(read(x_ + "gate.weight")),
                              low(read(x_ + "down.weight")))
            for m, (idx, pr), y in zip(ms, routed, ys):
                slots = k - 1 if drop_last_expert else k
                b, t, j = np.nonzero(idx[..., :slots] == e)
                if not b.size:
                    continue
                rows = m[b, t]                                   # [n, H]
                act = np.maximum(rows @ gate.T, 0.0) * (rows @ up.T)
                np.add.at(y, (b, t), pr[b, t, j][:, None]
                          * (low(act) @ down.T))
        xs = [h + y for h, y in zip(hs, ys)]
    norm = read("model.norm.weight")
    head = (embed if tied else read("lm_head.weight")).T
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
    """Per layer and batch, [B, T]: how far each token's k-th router logit
    lies above its (k+1)-th.  A margin under the stream's rounding is where
    a bf16 program and this file can choose a different last expert."""
    margins = []
    forward(read, model, batches, 1, margins=margins)
    return margins
