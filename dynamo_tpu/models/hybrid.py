"""The layer loop of a model whose layer is ONE mixer (`ModelConfig.
layer_pattern`: nemotron_h): a Mamba-2 state-space mixer "M", an expert
feed-forward "E" or attention "*", each `x + mixer(rms_norm(x))`; and of a
model whose every layer is BOTH mixers (falcon_h1, "P"): `u = rms_norm(x)`
once, `x + mamba(u) + attention(u)`, the two side by side from the same `u`
and under the family's multipliers, then a dense feed-forward under a norm
of its own (`llama._feed_forward`).

Params are a stack a kind (`ssm_layers`, `attn_layers`, `moe_layers`,
`par_layers`), and the loop follows the pattern in ONE `lax.scan` whatever
the depth: the pattern is cut into units of (M, then *, then E), each kind at
most once a unit and in that order (nemotron_h's 52 layers are 23 units, six
of them with a "*"); a "P" layer is a unit by itself (`kinds_of`).  A kind
every unit has is scanned with the units; a kind only some have runs under a
`lax.cond` on the unit's flag and indexes its own stack.

Two pools stay where they are, read inside the loop and written after it:
the pages of the layers with attention (`StateCache.k`, `.v`) and, beside
them, the state slots of the layers with a state-space mixer
(`StateCache.conv`, `.ssm`: `ModelConfig.state_spec`); a "P" layer has a row
in both, at its own index.  A row reads its state from one slot and writes it to another,
and the scan hands out the state INSIDE the chunk too, every
`handout_every` tokens from the chunk's start (`snapshot_tokens`; a page in
a short row): up to `SNAP_COLS` further slots take those.  All ride in the
last `STATE_COLS` columns of the row's page table:
[read, write, inside...].  Slot 0 is no state at all: read, it is the zeros
before a sequence; written, it is trash (pad rows, no snapshot wanted).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import prefill_attention, rms_norm, rope_attention_scale
from ..ops import rope_frequencies, ssm, write_kv_layers
from .config import ModelConfig
from .llama import (Params, StateCache, _feed_forward, _moe, _qkv_proj,
                    _rope_qk, _valid_rows, merge_moe_stats,
                    moe_stats_columns)
from .quantization import matmul_any

# A chunk hands its state out, to be kept as a snapshot, after every so many
# blocks of the scan (`ModelConfig.ssm_chunk` tokens each: 128 x 1 for
# nemotron_h).  Chosen on the chip (PERF.md, PR 44: the cell's six seeds at
# 1, 2 and 4 blocks): a constant, not a flag.
SNAPSHOT_BLOCKS = 1
# most states a step hands out INSIDE a row's chunk (a 512-token chunk has
# three multiples of 128 inside it; a short row three whole pages)
SNAP_COLS = 3
# the trailing columns of a state family's page table: the slot a row's
# state is read from, the slot it is written to at the chunk's end, and the
# slots that take its state after 1, 2, ... `snapshot_tokens` of the chunk
STATE_COLS = 2 + SNAP_COLS


def snapshot_tokens(cfg: ModelConfig) -> int:
    return cfg.ssm_chunk * SNAPSHOT_BLOCKS


def handout_every(cfg: ModelConfig, tokens: int, page_size: int) -> int:
    """The tokens between the states a step hands out inside a row's chunk,
    counted from the chunk's start; `tokens` is the bucket the step runs at.
    A row of at most `SNAP_COLS` + 1 pages hands out after every PAGE: the
    scheduler ends a long prompt with such a row (`Scheduler._tail_start`),
    because its tail is where a follow-up parts from it (a document, then
    another question), and a follow-up that resumes at its last shared page
    is a short row itself.  Longer rows hand out every `snapshot_tokens`."""
    if tokens <= (SNAP_COLS + 1) * page_size:
        return page_size
    return snapshot_tokens(cfg)


class Units(NamedTuple):
    """`layer_pattern` as units of (M, *, E), or of (P,).  `has` [U, kinds]
    bool and `idx` [U, kinds] int (the layer's index in its kind's stack; 0
    where absent), in the order of `kinds_of(pattern)`."""

    has: np.ndarray
    idx: np.ndarray


KINDS = "M*E"  # a unit's layers of ONE mixer, in its order
STACKS = {"M": "ssm_layers", "*": "attn_layers", "E": "moe_layers",
          "P": "par_layers"}


def kinds_of(pattern: str) -> str:
    """The kinds a unit of this pattern may hold, in its order: a layer of
    both mixers ("P") is a unit by itself and stands beside no other kind
    (`ModelConfig.__post_init__`)."""
    return "P" if "P" in pattern else KINDS


def units_of(pattern: str) -> Units:
    kinds = kinds_of(pattern)
    has, idx, seen = [], [], [0] * len(kinds)
    at = len(kinds)  # past the last kind: the first layer opens a unit
    for ch in pattern:
        k = kinds.index(ch)
        if k < at:
            has.append([False] * len(kinds))
            idx.append([0] * len(kinds))
        has[-1][k], idx[-1][k] = True, seen[k]
        seen[k] += 1
        at = k + 1
    return Units(np.asarray(has, bool), np.asarray(idx, np.int32))


def split_table(page_table: jax.Array):
    """(pages' table, read slots [B], write slots [B], inside slots [B,
    SNAP_COLS]) of a state family's page table."""
    cols = page_table[:, -STATE_COLS:]
    return page_table[:, :-STATE_COLS], cols[:, 0], cols[:, 1], cols[:, 2:]


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random init (tests).  `A_log` and `dt_bias` are drawn as the family
    initialises them (A in [1, 16], step sizes in [0.001, 0.1]), so that a
    state REMEMBERS across hundreds of tokens."""
    h, pat = cfg.hidden_size, cfg.layer_pattern
    nM, nA, nE, nP = (pat.count(c) for c in KINDS + "P")
    d, cd, nh = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads
    q = cfg.num_attention_heads * cfg.head_dim_
    kvw = cfg.num_key_value_heads * cfg.head_dim_
    fm, fs, E = cfg.moe_intermediate_size, cfg.shared_expert_width, cfg.num_experts
    ks = iter(jax.random.split(key, 24))

    def w(*shape, scale=None):
        scale = scale or shape[-2] ** -0.5
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    def ssm_stack(n, dt0):  # a Mamba-2 mixer a layer, under its norm
        return {
            "norm": jnp.ones((n, h), dtype),
            "in_proj": w(n, h, d + cd + nh),
            "conv_w": w(n, cfg.ssm_conv_kernel, cd, scale=0.5),
            "conv_b": w(n, cd, scale=0.1),
            # inverse softplus of the drawn step size
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (n, nh), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((n, nh), jnp.float32),
            "gate_norm": jnp.ones((n, d), dtype),
            "out_proj": w(n, d, h),
        }

    def attn_stack(n):
        return {"wq": w(n, h, q), "wk": w(n, h, kvw), "wv": w(n, h, kvw),
                "wo": w(n, q, h)}

    dt0 = jnp.exp(jax.random.uniform(
        next(ks), (nM + nP, nh), jnp.float32, np.log(1e-3), np.log(1e-1)))
    if nP:  # every layer is both mixers and a dense feed-forward: ONE stack
        f = cfg.intermediate_size
        params = {
            "embed": w(cfg.vocab_size, h, scale=0.02),
            "final_norm": jnp.ones((h,), dtype),
            "par_layers": {
                **ssm_stack(nP, dt0), **attn_stack(nP),
                "mlp_norm": jnp.ones((nP, h), dtype),
                "w_gate": w(nP, h, f), "w_up": w(nP, h, f),
                "w_down": w(nP, f, h),
            },
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = w(h, cfg.vocab_size)
        return params
    params = {
        "embed": w(cfg.vocab_size, h, scale=0.02),
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": w(h, cfg.vocab_size),
        "ssm_layers": ssm_stack(nM, dt0),
        "attn_layers": {"norm": jnp.ones((nA, h), dtype), **attn_stack(nA)},
        "moe_layers": {
            "norm": jnp.ones((nE, h), dtype),
            "router": w(nE, h, cfg.router_width),
            "router_bias": 0.02 * jax.random.normal(
                next(ks), (nE, cfg.router_width), jnp.float32),
            "w_up": w(nE, E, h, fm), "w_down": w(nE, E, fm, h),
            "ws_up": w(nE, h, fs), "ws_down": w(nE, fs, h),
        },
    }
    return params


def _inside(cfg: ModelConfig, S: int, page_size: int) -> tuple:
    """Token counts inside a chunk of S at which a state is handed out:
    the multiples of `handout_every` short of the chunk's end, `SNAP_COLS`
    at most."""
    every = handout_every(cfg, S, page_size)
    return tuple(range(every, S, every))[:SNAP_COLS]


def _mamba(lp: Params, u: jax.Array, cfg: ModelConfig, window: jax.Array,
           h0: jax.Array, chunk_lens: jax.Array, page_size: int):
    """The Mamba-2 mixer over u [B, S, h] (normed) from a row's carried
    `window` [B, K-1, conv_dim] and state `h0` [B, nh, hp, N] -> (out [B,
    S, h], window', h', and [(window, h)] after each of `_inside`'s token
    counts).  Positions at or past `chunk_lens` move neither."""
    B, S, _ = u.shape
    d, nh, hp = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_head_dim
    G, N, dt_ = cfg.ssm_groups, cfg.ssm_state, u.dtype
    with jax.named_scope("ssm.in_proj"):
        zxd = matmul_any(u, lp["in_proj"], "bsh,hd->bsd")
        if cfg.ssm_mup_vector is not None:  # on the float32 product
            zxd = zxd * np.concatenate([np.full(n, m, np.float32)
                                        for m, n in cfg.ssm_mup_vector])
        zxd = zxd.astype(dt_)
        z, xbc, dt = (zxd[..., :d], zxd[..., d:d + cfg.ssm_conv_dim],
                      zxd[..., d + cfg.ssm_conv_dim:])
    at = _inside(cfg, S, page_size)
    with jax.named_scope("ssm.conv"):
        xbc, window, wins = ssm.conv(xbc, window, lp["conv_w"], lp["conv_b"],
                                     chunk_lens, at)
    with jax.named_scope("ssm.scan"):
        step = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        step = jnp.where(_valid_rows(u, chunk_lens)[..., None], step, 0.0)
        # the gated norm rides the scan: its kernel's epilogue (which reads
        # z where `in_proj` left it), or `ssm.gate_norm` after the `jnp` form
        y, h, hs = ssm.scan(
            xbc[..., :d].reshape(B, S, nh, hp), step, -jnp.exp(lp["A_log"]),
            xbc[..., d:d + G * N].reshape(B, S, G, N),
            xbc[..., d + G * N:].reshape(B, S, G, N), lp["D"], h0,
            cfg.ssm_chunk, at,
            gate=(z, lp["gate_norm"], cfg.rms_norm_eps, zxd))
        inside = list(zip(wins, hs))
    with jax.named_scope("ssm.out_proj"):
        out = matmul_any(y, lp["out_proj"], "bsd,dh->bsh")
        if cfg.ssm_out_multiplier != 1.0:
            out = out * cfg.ssm_out_multiplier
        return out.astype(dt_), window, h, inside


def _attention(lp: Params, u: jax.Array, cfg: ModelConfig, kv: StateCache,
               layer, positions, table, prefix_lens, chunk_lens, attn_impl):
    """GQA attention over u [B, S, h] (normed) and layer `layer`'s pages ->
    (out [B, S, h], k, v [B, S, n_kv, hd]: the chunk's own)."""
    B, S, _ = u.shape
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim_)
    with jax.named_scope("attn.qkv"):
        q, k, v = _qkv_proj(u, lp, cfg, "bsh,hd->bsd")
        # (the scale of what the three read commutes with the products)
        m_in, m_k = cfg.attention_in_multiplier, cfg.key_multiplier
        if m_in != 1.0:
            q, v = q * m_in, v * m_in
        if m_in * m_k != 1.0:
            k = k * (m_in * m_k)
        q = q.astype(u.dtype).reshape(B, S, nh, hd)
        k = k.astype(u.dtype).reshape(B, S, nkv, hd)
        v = v.astype(u.dtype).reshape(B, S, nkv, hd)
        if cfg.attention_rope:
            q, k = _rope_qk(
                q, k, positions,
                rope_frequencies(hd, cfg.rope_theta, cfg.rope_scaling),
                rope_attention_scale(cfg.rope_scaling), None)
    attn = prefill_attention(q, k, v, kv.k, kv.v, table, prefix_lens,
                             chunk_lens, impl=attn_impl, layer=layer)
    with jax.named_scope("attn.out"):
        out = matmul_any(attn.reshape(B, S, nh * hd), lp["wo"], "bsd,dh->bsh")
        if cfg.attention_out_multiplier != 1.0:
            out = out * cfg.attention_out_multiplier
        return out.astype(u.dtype), k, v


def layers(params: Params, cfg: ModelConfig, kv: StateCache, x: jax.Array,
           page_table: jax.Array, prefix_lens: jax.Array,
           chunk_lens: jax.Array, attn_impl: str = "xla",
           moe_stats: bool = False):
    """Every layer over an embedded chunk x [B, S, h], as
    `llama.prefill_layers`: -> (x, kv, *stats).  `page_table` carries the
    rows' state slots (`split_table`); a decode step is a chunk of one."""
    B, S, _ = x.shape
    page_size = kv.page_size
    table, slot_in, slot_out, slot_inside = split_table(page_table)
    units = units_of(cfg.layer_pattern)
    if moe_stats and "E" not in cfg.layer_pattern:
        raise ValueError(f"moe_stats: the pattern {cfg.layer_pattern!r} has "
                         "no expert layer to count")
    every = units.has.all(0)  # kinds that every unit has: scanned
    spec = cfg.state_spec
    positions = prefix_lens[:, None] + jnp.arange(S)[None, :]
    valid = _valid_rows(x, chunk_lens)
    n_stats = moe_stats_columns(cfg)
    fresh = (slot_in == 0)

    def normed(lp, h, mixer):
        return mixer(rms_norm(h, lp["norm"], cfg.rms_norm_eps))

    def carried(layer):  # the rows' window and state, from their slots
        with jax.named_scope("state.read"):
            win = read_window(kv, spec, layer, slot_in, fresh)
            h0 = jnp.where(fresh[:, None, None, None], 0.0,
                           read_state(kv.ssm, layer, slot_in))
        return win, h0

    def stored(win, h1, inside):  # as the slot pool stores them
        return (_as_tiles(win, spec.window_dims), h1, *(
            (_as_tiles(w, spec.window_dims), hj) for w, hj in inside))

    def mamba(h, lp, layer):
        win, h0 = carried(layer)
        out, *left = normed(lp, h, lambda u: _mamba(
            lp, u, cfg, win, h0, chunk_lens, page_size))
        return h + out, stored(*left)

    def attention(h, lp, layer):
        out, k, v = normed(lp, h, lambda u: _attention(
            lp, u, cfg, kv, layer, positions, table, prefix_lens, chunk_lens,
            attn_impl))
        return h + out, (k, v)

    def experts(h, lp, layer):
        with jax.named_scope("mlp"):
            out = normed(lp, h, lambda u: _moe(
                lp, u, cfg, None, valid, stats=moe_stats,
                stacks=(params[STACKS["E"]], layer)))
        out, *st = out if moe_stats else (out,)
        return h + out, tuple(st)

    def both(h, lp, layer):
        """A layer of both mixers, side by side from one normed input, then
        its dense feed-forward: -> (h, (the state's rows, the pages'))."""
        win, h0 = carried(layer)
        u = rms_norm(h, lp["norm"], cfg.rms_norm_eps)
        s, *left = _mamba(lp, u, cfg, win, h0, chunk_lens, page_size)
        a, k, v = _attention(lp, u, cfg, kv, layer, positions, table,
                             prefix_lens, chunk_lens, attn_impl)
        h = h + s + a
        (y,) = _feed_forward(lp, h, h, cfg)
        return h + y, (stored(*left), (k, v))

    kinds = kinds_of(cfg.layer_pattern)
    mixers = tuple({"M": mamba, "*": attention, "E": experts, "P": both}[c]
                   for c in kinds)
    a_state = (jnp.zeros((B, *spec.window_dims), kv.conv.dtype),
               jnp.zeros((B, *spec.state_dims), jnp.float32)) if spec else ()
    blank = (
        (*a_state, *(a_state for _ in _inside(cfg, S, page_size)))
        if spec else (),
        tuple(jnp.zeros((B, S, *p.shape[3:]), x.dtype)
              for p in (kv.k, kv.v)),
        (jnp.zeros((n_stats,), jnp.int32),) if moe_stats else (),
    )

    def body(h, xs):
        has, idx, scanned = xs
        outs = []
        for k, mixer in enumerate(mixers):
            if not units.has[:, k].any():
                outs.append(())
            elif every[k]:
                h, out = mixer(h, scanned[k], idx[k])
                outs.append(out)
            else:
                stack = params[STACKS[kinds[k]]]
                h, out = jax.lax.cond(
                    has[k],
                    lambda h, i, mixer=mixer, stack=stack: mixer(
                        h, jax.tree.map(lambda a: a[i], stack), i),
                    lambda h, i, k=k: (h, blank[k]), h, idx[k])
                outs.append(out)
        return h, tuple(outs)

    scanned = tuple(params[STACKS[c]] if every[k] else None
                    for k, c in enumerate(kinds))
    x, left = jax.lax.scan(
        body, x, (jnp.asarray(units.has), jnp.asarray(units.idx), scanned))

    def own(ys, k):  # the units that have kind k, in their stack's order
        rows = np.flatnonzero(units.has[:, k])
        return ys if every[k] else jax.tree.map(lambda a: a[rows], ys)

    if kinds == "P":  # every layer left both: a row a layer in either pool
        (states, pages), st_e = left[0], ()
    else:
        st_m, st_a, st_e = left
        states, pages = st_m and own(st_m, 0), st_a and own(st_a, 1)
    new = {}
    if pages:
        new["k"], new["v"] = write_kv_layers(
            kv.k, kv.v, *pages, table, prefix_lens, valid)
    if states:
        new["conv"], new["ssm"] = write_states(kv, states, slot_out,
                                               slot_inside)
    kv = kv._replace(**new)
    if not moe_stats:
        return x, kv
    return x, kv, merge_moe_stats(own(st_e, 2)[0])


def read_window(kv: StateCache, spec, layer, slot_in: jax.Array,
                fresh: jax.Array):
    """Layer `layer`'s carried windows of the rows whose states lie in slots
    `slot_in` [B], out of the pool's tiles: [B, K-1, conv_dim], zeros for
    the rows `fresh` marks (they read slot 0: the state before a
    sequence)."""
    k1, B = spec.conv_kernel - 1, slot_in.shape[0]
    win = kv.conv[layer, slot_in].reshape(B, -1)[
        :, :k1 * spec.conv_dim].reshape(B, k1, spec.conv_dim)
    return jnp.where(fresh[:, None, None], 0, win)


def read_state(pool: jax.Array, layer, slot_in: jax.Array) -> jax.Array:
    """Layer `layer`'s recurrent states in slots `slot_in` [B] of the pool
    [L, slots, heads, head_dim, N] -> [B, heads, head_dim, N].  A state
    wider than a lane tile (falcon_h1's N 256) is sliced out row by row:
    the TPU compiler gathers several rows of such a pool through a COPY OF
    THE POOL in 128-lane halves (AOT for a v5e, PR 59: two temporaries of
    1.42 GB beside a 3 GB pool, and the four-row step no longer fit the
    chip); a slice a row is a plain DMA."""
    if pool.shape[-1] <= 128:
        return pool[layer, slot_in]
    zero = jnp.zeros((), jnp.int32)
    return jnp.concatenate([jax.lax.dynamic_slice(
        pool, (jnp.asarray(layer, jnp.int32), slot_in[b], zero, zero, zero),
        (1, 1, *pool.shape[2:]))[0] for b in range(slot_in.shape[0])])


def write_states(kv: StateCache, states, slot_out: jax.Array,
                 slot_inside: jax.Array):
    """Every state-space layer's new states into the slot pools: `states`
    (window, state, *(window, state) inside the chunk), each [Lm, B, ..]
    -> (conv, ssm).  A state that is a window alone (`kv.ssm` None: short
    convolutions) has None for every `state` and writes the one pool."""
    win, h1, *inside = states
    with jax.named_scope("state.write"):
        conv, pool = kv.conv, kv.ssm
        for j, (w, hj) in enumerate(inside):  # before the chunk's end:
            # a row whose slots coincide keeps its latest state
            conv = conv.at[:, slot_inside[:, j]].set(w)
            if pool is not None:
                pool = pool.at[:, slot_inside[:, j]].set(hj)
        return conv.at[:, slot_out].set(win), (
            None if pool is None else pool.at[:, slot_out].set(h1))


def _as_tiles(win: jax.Array, dims: Tuple[int, int]) -> jax.Array:
    """A window [B, K-1, conv_dim] as the pool stores it: [B, tiles, 128],
    zeros after its own values."""
    flat = win.reshape(win.shape[0], -1)
    pad = dims[0] * dims[1] - flat.shape[1]
    return jnp.pad(flat, ((0, 0), (0, pad))).reshape(-1, *dims)
