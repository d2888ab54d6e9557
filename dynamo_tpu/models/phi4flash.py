"""The layer loops of a decoder-hybrid-decoder (`ModelConfig.cross_decoder`:
phi4flash).  A layer is a mixer THEN a dense SwiGLU feed-forward, each
`x + f(layer_norm(x))`, and the layers fall into two halves:

  the SELF half (`self_layers`), over every token of a chunk: units of
  ("S" a Mamba-1 mixer, then "W" differential attention under the window),
  the last unit's attention "F" seeing every key.  ONE `lax.scan` over the
  units whatever the depth; the window is a SHAPE (the pages gathered), so
  the unit holds both attention bodies under a `lax.cond`.  The Mamba
  layer's weights are scanned; the attention layer's are NOT: an operand of
  a conditional is a buffer of its own, so a scanned slice handed to the
  branches is copied out of its stack every unit, where a slice that feeds
  a product in the same computation is read in place.  The branches close
  over the whole stacks (passed as they stand, like the pools) and index
  them by the unit, as `models/hybrid.py` `layers` does.  The half leaves a
  token keys and values in the pages of its attention layers, a sequence a
  state in the slots of its "S" layers (the same slots, snapshots and table
  columns `models/hybrid.py` has), and hands on the residual, the LAST "S"
  layer's scan output before its gate (`memory`) and "F"'s keys and values.

  the CROSS half (`cross_layers`), over ONE position a row: units of ("G" a
  gated memory unit, `W2 (silu(W1 u) * memory)`, then "C" differential
  cross-attention whose keys and values are "F"'s pages).  No cross layer
  mixes positions except through what the self half wrote for every token,
  so a row needs the cross half only where it samples: `llama.
  forward_prefill` runs it on the rows' last positions under the head's one
  conditional, and a mid-prompt chunk reads none of its weights.

Differential attention.  Heads pair off by parity: q1, q2 the even and odd
query heads, k1, k2 and v1, v2 the even and odd key/value heads, query pair j
reading key/value pair j // 2:

    o = softmax(q1 k1^T / sqrt(hd)) V - lambda softmax(q2 k2^T / sqrt(hd)) V
    V = [v1 | v2], 2 hd wide;  then an rms norm over each pair's 2 hd values

A token's 20 heads of 64 lie in memory as 10 pairs of 128, [k1 | k2] and
[v1 | v2], and the pool holds them once, in that order (`ModelConfig.
cache_spec`).  The four products are XLA's (`_attend`): the Pallas kernels
read a page as [page, heads, 128] and the TPU's DMA refuses a slice of 10
rows where the tiling wants 8 (Mosaic, AOT for a v5e, PR 48), so this family
has no kernel yet.  What `_attend` does about the context instead: a layer
under the window gathers the window's pages alone (the 33 before the chunk,
whatever the table), only the "F" layer and the cross half gather a row's
whole table.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import gather_kv, layer_norm, ssm, write_kv_layers
from ..ops.paged_attention import NEG_INF
from .config import ModelConfig
from .hybrid import (_as_tiles, _inside, read_window, split_table,
                     write_states)
from .llama import Params, StateCache, _mlp, _valid_rows
from .quantization import matmul_any

STACKS = {"S": "ssm_layers", "W": "attn_layers", "F": "attn_layers",
          "G": "gmu_layers", "C": "cross_layers"}


def lambda_init(layer: int) -> float:
    """The differential weight's constant part, by the layer's place in the
    whole model."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _lambda_inits(cfg: ModelConfig, kinds: str) -> np.ndarray:
    return np.asarray([lambda_init(l) for l, c in enumerate(cfg.layer_pattern)
                       if c in kinds], np.float32)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random init (tests).  The state-space tensors are drawn as the family
    initialises them (A = -(1..N) for every channel, step sizes in [0.001,
    0.1], D = 1), so that a state REMEMBERS across hundreds of tokens and a
    decay by state index differs from one by channel."""
    h, f, pat = cfg.hidden_size, cfg.intermediate_size, cfg.layer_pattern
    d, N, r, K = (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank,
                  cfg.ssm_conv_kernel)
    hd = cfg.head_dim_
    q, kvw = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    nS, nA = pat.count("S"), pat.count("W") + pat.count("F")
    nG, nC = pat.count("G"), pat.count("C")
    ks = iter(jax.random.split(key, 64))

    def w(*shape, scale=None):
        scale = scale or shape[-2] ** -0.5
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    def block(n):  # the two LayerNorms and the feed-forward of n layers
        return {"norm": 1 + w(n, h, scale=0.1), "norm_b": w(n, h, scale=0.1),
                "mlp_norm": 1 + w(n, h, scale=0.1),
                "mlp_norm_b": w(n, h, scale=0.1),
                "w_gateup": w(n, h, 2 * f), "w_down": w(n, f, h)}

    def diff(n):
        return {"lambda": 0.1 * jax.random.normal(
                    next(ks), (n, 4, hd), jnp.float32),
                "subln": 1 + w(n, 2 * hd, scale=0.1),
                "wo": w(n, q, h), "bo": w(n, h, scale=0.1)}

    dt0 = jnp.exp(jax.random.uniform(
        next(ks), (nS, d), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        "embed": w(cfg.vocab_size, h, scale=1.0),
        "final_norm": 1 + w(h, scale=0.1),
        "final_norm_bias": w(h, scale=0.1),
        "ssm_layers": {
            **block(nS),
            "in_proj": w(nS, h, 2 * d),
            "conv_w": w(nS, K, d, scale=0.5), "conv_b": w(nS, d, scale=0.1),
            "x_proj": w(nS, d, r + 2 * N),
            "dt_proj": w(nS, r, d),
            # inverse softplus of the drawn step size
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[None, :, None], (nS, N, d)),
            "D": jnp.ones((nS, d), jnp.float32),
            "out_proj": w(nS, d, h),
        },
        "attn_layers": {
            **block(nA), **diff(nA),
            "wqkv": w(nA, h, q + 2 * kvw),
            "bqkv": w(nA, q + 2 * kvw, scale=0.1),
        },
        "gmu_layers": {**block(nG), "w_in": w(nG, h, d),
                       "w_out": w(nG, d, h)},
        "cross_layers": {**block(nC), **diff(nC), "wq": w(nC, h, q),
                         "bq": w(nC, q, scale=0.1)},
    }


def _normed(lp: Params, cfg: ModelConfig, h: jax.Array, half: str = "norm"):
    return layer_norm(h, lp[half], lp[half + "_b"], cfg.rms_norm_eps)


def _ffn(lp: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        return h + _mlp(lp, _normed(lp, cfg, h, "mlp_norm"))


def _mamba(lp: Params, u: jax.Array, cfg: ModelConfig, window: jax.Array,
           h0: jax.Array, chunk_lens: jax.Array, page_size: int):
    """The Mamba-1 mixer over u [B, S, h] (normed) from a row's carried
    `window` [B, K-1, d] and state `h0` [B, N, d] -> (out [B, S, h], the
    scan's output before the gate [B, S, d], window', h', and [(window, h)]
    after each of `_inside`'s token counts).  Positions at or past
    `chunk_lens` move neither."""
    d, N, r, dt_ = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, u.dtype
    with jax.named_scope("ssm.in_proj"):
        xz = matmul_any(u, lp["in_proj"], "bsh,hd->bsd").astype(dt_)
        x, z = xz[..., :d], xz[..., d:]
    at = _inside(cfg, u.shape[1], page_size)
    with jax.named_scope("ssm.conv"):
        x, window, wins = ssm.conv(x, window, lp["conv_w"], lp["conv_b"],
                                   chunk_lens, at)
    with jax.named_scope("ssm.scan"):
        dbc = matmul_any(x, lp["x_proj"], "bsd,dr->bsr")
        step = jax.nn.softplus(
            matmul_any(dbc[..., :r].astype(dt_), lp["dt_proj"], "bsr,rd->bsd")
            + lp["dt_bias"])
        step = jnp.where(_valid_rows(u, chunk_lens)[..., None], step, 0.0)
        y, h, hs = ssm.selective_scan(
            x, step, -jnp.exp(lp["A_log"]), dbc[..., r:r + N],
            dbc[..., r + N:], h0, at)
        y = y + lp["D"] * x.astype(jnp.float32)
    with jax.named_scope("ssm.gate"):
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt_)
    with jax.named_scope("ssm.out_proj"):
        return (matmul_any(gated, lp["out_proj"], "bsd,dh->bsh").astype(dt_),
                y.astype(dt_), window, h, list(zip(wins, hs)))


def _gather(kv: StateCache, layer, table: jax.Array, prefix_lens: jax.Array,
            cfg: ModelConfig, window: int = 0):
    """The rows' earlier keys and values out of pool layer `layer`, as pairs
    [B, T, n_kv / 2, 2 hd], and where each sits in its sequence [B or 1, T].
    `window` (static) > 0: only the pages that positions `prefix_lens -
    window + 1` on can lie in, whatever the table."""
    page, pairs = kv.page_size, cfg.num_key_value_heads // 2
    pages = jnp.arange(table.shape[1])[None, :]
    if window:
        n = min(-(-(window - 1) // page) + 1, table.shape[1])
        first = jnp.maximum(prefix_lens - (window - 1), 0) // page
        pages = first[:, None] + jnp.arange(n)[None, :]
        # a page past the table's end is read from its last one, and keeps
        # its OWN positions: at or past `prefix_lens`, so no query sees it
        table = jnp.take_along_axis(
            table, jnp.minimum(pages, table.shape[1] - 1), axis=1)
    k, v = (a.reshape(*a.shape[:2], pairs, 2 * cfg.head_dim_)
            for a in gather_kv(kv.k, kv.v, table, layer))
    pos = (pages[:, :, None] * page + jnp.arange(page)[None, None, :])
    return k, v, pos.reshape(pos.shape[0], -1)  # [B or 1, T]


def _attend(q: jax.Array, k_new: jax.Array, v_new: jax.Array, before,
            prefix_lens: jax.Array, chunk_lens: jax.Array, cfg: ModelConfig,
            window: int = 0):
    """Differential attention's four products for a chunk: q [B, S, nh * hd]
    float32, the chunk's own keys and values as pairs [B, S, n_kv / 2, 2 hd],
    `before` the rows' earlier ones (`_gather`) -> (P1 V, P2 V), each [B, S,
    n_kv / 2, nh / n_kv, 2 hd] float32 (pair i's query pairs side by side).
    `window` (static) > 0: a query sees the `window` keys up to its own."""
    B, S, _ = q.shape
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim_)
    f32, dt_ = jnp.float32, k_new.dtype
    with jax.named_scope("attn.core"):
        k_pre, v_pre, k_pos = before
        i = jnp.arange(S)[None, :, None]
        j = jnp.arange(S)[None, None, :]
        see_pre = k_pos[:, None, :] < prefix_lens[:, None, None]
        see_new = (j <= i) & (j < chunk_lens[:, None, None])
        if window:
            see_pre &= k_pos[:, None, :] > prefix_lens[:, None, None] + i - window
            see_new &= j > i - window
        see = jnp.concatenate(
            [jnp.broadcast_to(see_pre, (B, S, k_pos.shape[-1])),
             jnp.broadcast_to(see_new, (B, S, S))], -1)[:, None, None]
        # head 4i + 2a + p is query pair 2i + a's q1 (p = 0) or q2
        q = (q * hd ** -0.5).astype(dt_).reshape(B, S, nkv // 2, nh // nkv,
                                                 2, hd)
        n_pre, outs = k_pre.shape[1], []
        for half in (0, 1):
            scores = jnp.concatenate([jnp.einsum(
                "bqiad,bsid->biaqs", q[..., half, :],
                k[..., half * hd:(half + 1) * hd], preferred_element_type=f32)
                for k in (k_pre, k_new)], axis=-1)
            w = jax.nn.softmax(jnp.where(see, scores, NEG_INF),
                               axis=-1).astype(dt_)
            outs.append(sum(jnp.einsum(
                "biaqs,bsiv->bqiav", part, v, preferred_element_type=f32)
                for part, v in ((w[..., :n_pre], v_pre),
                                (w[..., n_pre:], v_new))))
        return outs


def _diff_out(lp: Params, o1: jax.Array, o2: jax.Array, cfg: ModelConfig,
              lam_init, dtype) -> jax.Array:
    """`_attend`'s two products [B, S, pairs, 2, 2 hd] -> the layer's output
    [B, S, h]: the difference, the pair norm (`subln`) and its scale, then
    the output projection (the pairs' values read as heads of hd)."""
    B, S = o1.shape[:2]
    with jax.named_scope("attn.diff"):
        lq1, lk1, lq2, lk2 = lp["lambda"].astype(jnp.float32)
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + lam_init)
        o = o1 - lam * o2
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = (o * jax.lax.rsqrt(var + cfg.rms_norm_eps)
             * lp["subln"].astype(jnp.float32) * (1.0 - lam_init))
        o = o.astype(dtype).reshape(B, S, -1)
    with jax.named_scope("attn.out"):
        return (matmul_any(o, lp["wo"], "bsd,dh->bsh")
                + lp["bo"]).astype(dtype)


def _pairs(a: jax.Array, cfg: ModelConfig, dtype) -> jax.Array:
    """Keys or values [B, S, n_kv * hd] as the pool holds them: [B, S, n_kv
    / 2, 2 hd]."""
    return a.astype(dtype).reshape(*a.shape[:2], cfg.num_key_value_heads // 2,
                                   2 * cfg.head_dim_)


def self_layers(params: Params, cfg: ModelConfig, kv: StateCache,
                x: jax.Array, page_table: jax.Array, prefix_lens: jax.Array,
                chunk_lens: jax.Array):
    """The self half over an embedded chunk x [B, S, h] -> (x, kv, (memory
    [B, S, d], the "F" layer's keys, values [B, S, n_kv / 2, 2 hd])): the
    pool holds the chunk's keys, values and states on return, and the
    triple is what `cross_layers` reads beside the pool.  `page_table`
    carries the rows' state slots (`hybrid.split_table`); a decode step is
    a chunk of one."""
    B, S, _ = x.shape
    page_size, spec, dt_ = kv.page_size, cfg.state_spec, x.dtype
    table, slot_in, slot_out, slot_inside = split_table(page_table)
    valid = _valid_rows(x, chunk_lens)
    fresh = (slot_in == 0)
    q_w = cfg.num_attention_heads * cfg.head_dim_
    kv_w = cfg.num_key_value_heads * cfg.head_dim_

    def mamba(h, lp, layer):
        with jax.named_scope("state.read"):
            win = read_window(kv, spec, layer, slot_in, fresh)
            h0 = jnp.where(fresh[:, None, None], 0.0, kv.ssm[layer, slot_in])
        out, m, win, h1, inside = _mamba(
            lp, _normed(lp, cfg, h), cfg, win, h0, chunk_lens, page_size)
        return _ffn(lp, cfg, h + out), m, (
            _as_tiles(win, spec.window_dims), h1, *(
                (_as_tiles(w, spec.window_dims), hj) for w, hj in inside))

    def attention(h, layer, lam_init, window):
        # inside the branch, out of the whole stacks: see `unit`
        lp = jax.tree.map(lambda a: a[layer], params["attn_layers"])
        u = _normed(lp, cfg, h)
        with jax.named_scope("attn.qkv"):
            qkv = matmul_any(u, lp["wqkv"], "bsh,hd->bsd") + lp["bqkv"]
            k = _pairs(qkv[..., q_w:q_w + kv_w], cfg, dt_)
            v = _pairs(qkv[..., q_w + kv_w:], cfg, dt_)
        o1, o2 = _attend(
            qkv[..., :q_w], k, v,
            _gather(kv, layer, table, prefix_lens, cfg, window), prefix_lens,
            chunk_lens, cfg, window)
        out = _diff_out(lp, o1, o2, cfg, lam_init, dt_)
        return _ffn(lp, cfg, h + out), (k, v)

    def unit(carry, xs):
        h, _ = carry
        sp, layer, windowed, lam_init = xs
        h, m, state = mamba(h, sp, layer)
        # the window is a SHAPE here (the pages gathered): two bodies.  An
        # operand of a conditional is a buffer of its own, so the attention
        # layer's weights are not scanned (a scanned slice handed to the
        # branches was copied out of its stack every unit: 197 MB, a fifth
        # of the device at the published widths); the branches close over
        # the whole stacks, which pass as they stand, and index them, so a
        # matrix is read in place by the product it feeds
        h, kv_new = jax.lax.cond(
            windowed,
            lambda h: attention(h, layer, lam_init, cfg.sliding_window),
            lambda h: attention(h, layer, lam_init, 0), h)
        return (h, m), (state, kv_new)

    n = cfg.layer_pattern.count("S")
    windowed = np.asarray([c == "W" for c in cfg.layer_pattern if c in "WF"])
    (x, memory), (states, (k_new, v_new)) = jax.lax.scan(
        unit, (x, jnp.zeros((B, S, cfg.ssm_inner), dt_)),
        (params["ssm_layers"], jnp.arange(n, dtype=jnp.int32),
         jnp.asarray(windowed), jnp.asarray(_lambda_inits(cfg, "WF"))))
    plane = kv.k.shape[3:]  # the pairs as the pool's plane has them
    k_pool, v_pool = write_kv_layers(
        kv.k, kv.v, k_new.reshape(*k_new.shape[:3], *plane),
        v_new.reshape(*v_new.shape[:3], *plane), table, prefix_lens, valid)
    conv, pool = write_states(kv, states, slot_out, slot_inside)
    return (x, StateCache(k_pool, v_pool, conv, pool),
            (memory, k_new[-1], v_new[-1]))


def cross_layers(params: Params, cfg: ModelConfig, kv: StateCache,
                 x: jax.Array, handed, at: jax.Array, page_table: jax.Array,
                 prefix_lens: jax.Array) -> jax.Array:
    """The cross half over ONE position a row: x [B, h] the self half's
    residual at position `at` [B] of the chunk, `handed` what `self_layers`
    handed on (whole chunks: the rows' positions are taken here), `kv` the
    pool AFTER the chunk was written.  -> x [B, h].  The "C" layers' query
    sees the `prefix_lens + at` keys before its position in "F"'s pages and
    its own."""
    with jax.named_scope("cross"):
        B, dt_ = x.shape[0], x.dtype
        memory, k_own, v_own = (
            jnp.take_along_axis(a, at.reshape(B, *(1,) * (a.ndim - 1)), 1)
            for a in handed)  # [B, 1, ..]
        table = split_table(page_table)[0]
        earlier, one = prefix_lens + at, jnp.ones_like(at)
        # "F"'s pages, once for every "C" layer
        before = _gather(kv, cfg.num_kv_layers - 1, table, earlier, cfg)

        def unit(h, xs):
            gp, cp, lam_init = xs
            with jax.named_scope("gmu"):
                u = _normed(gp, cfg, h)
                g = matmul_any(u, gp["w_in"], "bsh,hd->bsd")
                g = (jax.nn.silu(g) * memory.astype(jnp.float32)).astype(dt_)
                h = h + matmul_any(g, gp["w_out"], "bsd,dh->bsh").astype(dt_)
            h = _ffn(gp, cfg, h)
            with jax.named_scope("attn.qkv"):
                q = matmul_any(_normed(cp, cfg, h), cp["wq"],
                               "bsh,hd->bsd") + cp["bq"]
            o1, o2 = _attend(q, k_own, v_own, before, earlier, one, cfg)
            h = h + _diff_out(cp, o1, o2, cfg, lam_init, dt_)
            return _ffn(cp, cfg, h), None

        x, _ = jax.lax.scan(
            unit, x[:, None],
            (params["gmu_layers"], params["cross_layers"],
             jnp.asarray(_lambda_inits(cfg, "C"))))
        return x[:, 0]
