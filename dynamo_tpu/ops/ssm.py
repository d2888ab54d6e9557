"""State-space layer operations: the causal depthwise convolution with a
carried window, Mamba-2's chunked selective scan from a carried state and its
grouped gated RMS norm, and Mamba-1's selective scan (`selective_scan`, at
the end: a decay for every channel AND state index, which has no block form).

A sequence leaves such a layer TWO things, whatever its length: the
convolution's last `kernel - 1` inputs (the window) and the recurrent state
`H` [heads, head_dim, state] in float32 (a gated short convolution, lfm2's
mixer, is `conv` alone: the window is all it leaves).  Every function here takes them in
and hands them out, so a prompt may be computed in chunks, one token at a
time, or padded to a bucket: positions at or past a row's `lens` move
neither (their step size is zeroed, and the window is taken where the real
tokens end).

The recurrence, per head (A < 0 a scalar, dt > 0 a token's step):

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t
    y_t = H_t C_t + D x_t

`scan` computes it a block of `chunk` tokens at a time (the "state-space
duality" form): inside a block the outputs are one masked [chunk, chunk]
product per head, a segment-sum of the log decays standing where attention
has its softmax; between blocks only `H` is carried.  It is the same
function of its inputs as the token-by-token loop, not another model.

Where the blocks run.  On a single TPU device a prefill chunk whose states
are handed out at block ends (the served path's 128-, 256- and 512-token
chunks) is ONE Pallas kernel a layer (`ops/pallas_ssm.py` `scan_pallas`:
a block's decays, scores and the group's state stay in VMEM, the gated norm
that follows the scan rides its epilogue).  Everywhere else (the CPU, a
mesh, a short row that hands its state out after every page, widths the
kernel's tiles do not hold) they are `scan_blocks`' plain `jnp`, which is
also what the kernel is tested against.  `pallas_ssm.scan_lowering` decides
from the trace's static shapes and `scan` notes the choice (`ssm_scan`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..analysis import xla_ledger
from . import pallas_ssm


def conv(xbc: jax.Array, window: jax.Array, w: jax.Array, b,
         lens: jax.Array, at: tuple = (), act=jax.nn.silu):
    """Causal depthwise convolution over the sequence, then `act`.

    xbc [B, S, C] the layer's new inputs; window [B, K-1, C] the inputs
    before them (zeros before the sequence); w [K, C] (tap j multiplies the
    input K-1-j positions back), b [C] or None (no bias: lfm2's short
    convolution); lens [B] real tokens a row; `at` static token counts
    inside the chunk; `act` the activation (Mamba's silu) or None.
    -> (out [B, S, C], window' [B, K-1, C]: the last K-1 inputs at or
    before each row's last real token, and the same after each of `at`
    tokens: right for the rows that have that many real tokens)."""
    k1 = window.shape[1]
    S = xbc.shape[1]
    padded = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
    acc = None if b is None else b.astype(jnp.float32)
    for j in range(k1 + 1):
        tap = (padded[:, j:j + S].astype(jnp.float32)
               * w[j].astype(jnp.float32))
        acc = tap if acc is None else acc + tap
    # padded[b, lens + j], j < K-1, are the inputs lens-(K-1)+j .. lens-1
    new = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, k1, 0))(
        padded, lens)
    inside = [padded[:, t:t + k1].astype(window.dtype) for t in at]
    if act is not None:
        acc = act(acc)
    return acc.astype(xbc.dtype), new.astype(window.dtype), inside


def scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
         Cm: jax.Array, D: jax.Array, h0: jax.Array, chunk: int,
         at: tuple = (), gate: tuple | None = None):
    """The selective scan over S tokens from state `h0`.

    x [B, S, nh, hp]; dt [B, S, nh] float32, already softplus'd and ZERO at
    the positions that must not move the state; A [nh] float32 (negative);
    Bm, Cm [B, S, G, N], head i reading group i // (nh / G); D [nh]; h0
    [B, nh, hp, N] float32.  S is a multiple of min(chunk, S).  `at`: static
    token counts short of S.
    -> (y [B, S, nh, hp] in x's dtype, h [B, nh, hp, N] float32, and the
    state after each of `at`'s token counts, a list: at a block's end the
    scan has it anyway, inside a block it costs the block's state update
    over the tokens before it; a caller may keep one as a snapshot).

    `gate` (z [B, S, nh x hp], w [nh x hp], eps): the gated norm that
    follows the scan in a Mamba-2 mixer; y is then [B, S, nh x hp] and
    `gate_norm(y, z, w, G, eps)` of the scan's.  A fourth member is the
    array z is the FIRST columns of (`in_proj`'s output): the kernel reads
    the gate there, where a slice handed to it is a copy of its own (13.5 +
    4.5 us a nemotron_h layer of 512 tokens on the v5e: PERF.md finding 40).

    Two lowerings of the one function, chosen from the trace's static
    shapes (`pallas_ssm.scan_lowering`, noted as path choice `ssm_scan`):
    ONE Pallas kernel on a single TPU device where every hand-out falls on a
    block's end (the gated norm in its epilogue: a grid step holds a whole
    group of the norm), `scan_blocks`' plain `jnp` elsewhere (the CPU, a
    mesh, a short row that hands out inside a block, widths the kernel's
    tiles do not hold)."""
    B, S, nh, hp = x.shape
    G, N = Bm.shape[2:]
    if S % min(chunk, S):
        raise ValueError(
            f"scan: {S} tokens are not whole blocks of {min(chunk, S)}")
    interpret, why = pallas_ssm.scan_lowering(x, G, N, chunk, at)
    xla_ledger.note_path_choice(
        "ssm_scan", "xla" if interpret is None else "pallas", why, rows=B,
        chunk=S)
    if interpret is None:
        y, h, hs = scan_blocks(x, dt, A, Bm, Cm, D, h0, chunk, at)
        if gate is not None:
            z, w, eps = gate[:3]
            with jax.named_scope("ssm.gate_norm"):
                y = gate_norm(y.reshape(B, S, nh * hp), z, w, G, eps)
        return y, h, hs
    block = pallas_ssm.scan_block(S, chunk, at)
    if gate is None:
        y, hs = pallas_ssm.scan_pallas(x, dt, A, Bm, Cm, D, h0, block,
                                       interpret=interpret)
        y = y.reshape(x.shape)
    else:
        z, w, eps, *whole = gate
        y, hs = pallas_ssm.scan_pallas(
            x, dt, A, Bm, Cm, D, h0, block, (whole[0] if whole else z, w),
            float(eps), interpret=interpret)
    return y, hs[-1], [hs[t // block - 1] for t in at]


def scan_blocks(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, D: jax.Array, h0: jax.Array, chunk: int,
                at: tuple = ()):
    """`scan` as plain `jnp`: the blocks one after the other, a block's
    decays and scores [heads, Q, Q] arrays of the program."""
    B, S, nh, hp = x.shape
    G, N = Bm.shape[2:]
    Q = min(chunk, S)
    nc, per = S // Q, nh // G
    f32 = jnp.float32

    def blocks(a):  # [B, S, ...] -> nc of [B, Q, ...]
        return [a[:, c * Q:(c + 1) * Q] for c in range(nc)]

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def block(h, xs, cuts):
        xq, dtq, bq, cq = xs  # [B,Q,nh,hp] [B,Q,nh] [B,Q,G,N] [B,Q,G,N]
        dth = jnp.moveaxis(dtq, 2, 1)  # [B, nh, Q]
        cum = jnp.cumsum(dth * A[:, None], axis=-1)  # L_t: log decays, <= 0
        # exp(L_t - L_s), s <= t: never above 1
        seg = cum[..., :, None] - cum[..., None, :]  # [B, nh, t, s]
        decay = jnp.where(tri, jnp.exp(seg), 0.0)
        cb = jnp.einsum("btgn,bsgn->bgts", cq, bq,
                        preferred_element_type=f32)  # [B, G, t, s]
        m = jnp.repeat(cb, per, axis=1) * decay * dth[:, :, None, :]
        y = jnp.einsum("bhts,bshp->bthp", m.astype(x.dtype), xq,
                       preferred_element_type=f32)
        # what the carried state adds: exp(L_t) C_t . H
        ch = jnp.einsum("btgn,bgkpn->btgkp", cq.astype(f32),
                        h.reshape(B, G, per, hp, N),
                        preferred_element_type=f32).reshape(B, Q, nh, hp)
        y = y + jnp.moveaxis(jnp.exp(cum), 1, 2)[..., None] * ch
        return [after(h, xs, cum, c) for c in cuts], after(h, xs, cum, Q), y

    def after(h, xs, cum, c):
        """The state after the block's first c tokens, from the state `h`
        before it."""
        xq, dtq, bq, _ = xs
        last = cum[..., c - 1]  # [B, nh]: L_c
        wgt = jnp.exp(last[..., None] - cum[..., :c]) * jnp.moveaxis(
            dtq, 2, 1)[..., :c]
        xw = (xq[:, :c].astype(f32) * jnp.moveaxis(wgt, 1, 2)[..., None]
              ).reshape(B, c, G, per, hp)
        add = jnp.einsum("bsgkp,bsgn->bgkpn", xw, bq[:, :c].astype(f32),
                         preferred_element_type=f32).reshape(B, nh, hp, N)
        return jnp.exp(last)[:, :, None, None] * h + add

    # the blocks one after the other, unrolled (a prompt chunk is at most a
    # few): a `lax.scan` would stack the blocks' float32 outputs through a
    # strided update a block (a tenth of a step's device time on the v5e:
    # PERF.md, PR 44)
    h, ys, states = h0.astype(f32), [], {}
    for n, xs in enumerate(zip(blocks(x), blocks(dt.astype(f32)),
                               blocks(Bm), blocks(Cm))):
        cuts = [t - n * Q for t in at if n * Q < t < (n + 1) * Q]
        inside, h, y = block(h, xs, cuts)
        ys.append(y)
        states.update(zip((n * Q + c for c in cuts), inside))
        states[(n + 1) * Q] = h
    y = ys[0] if nc == 1 else jnp.concatenate(ys, axis=1)
    y = y + D.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype), h, [states[t] for t in at]


def gate_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
              eps: float) -> jax.Array:
    """rms_norm, over each of `groups` equal groups of the last axis, of
    y * silu(z): the gate FIRST, then the norm.  y, z [..., d]; w [d]."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(*g.shape[:-1], groups, -1)
    var = jnp.mean(parts * parts, axis=-1, keepdims=True)
    out = (parts * jax.lax.rsqrt(var + eps)).reshape(g.shape)
    return (out * w.astype(jnp.float32)).astype(y.dtype)


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, h0: jax.Array, at: tuple = (),
                   kernel: bool | None = None, interpret: bool = False):
    """Mamba-1's selective scan over S tokens from state `h0`: per channel
    c and state index n

        H_t[n, c] = exp(dt_t[c] A[n, c]) H_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
        y_t[c]    = sum_n C_t[n] H_t[n, c]

    x [B, S, C]; dt [B, S, C] float32, already softplus'd and ZERO at the
    positions that must not move the state; A [N, C] float32 (negative: the
    state's own order, the channels under the lanes); Bm, Cm [B, S, N]; h0
    [B, N, C] float32.  `at`: static token counts short of S.
    -> (y [B, S, C] float32, WITHOUT the `D x` skip, which is the caller's;
    h [B, N, C] float32; and the state after each of `at`'s token counts, a
    list), the contract `scan` has.

    Every token's decay is [N, C] numbers of its own, so there is no block
    form: a loop over the tokens with the state resident, nothing of the [S,
    N, C] kind written.  On a TPU that loop is ONE kernel (`ops.pallas_ssm`),
    its blocks of tokens ending where a state is handed out, and a C its
    tiles do not hold is refused, never sent another way.  Elsewhere (the
    CPU's tests) it is a `lax.scan` over the tokens, cut at the same places.
    `kernel` and `interpret` are a check's: the other form on this backend,
    and the kernel interpreted where no TPU is (`scripts/
    check_selective_scan.py`)."""
    f32 = jnp.float32
    S, C = x.shape[1:]
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    dt, dtx = dt.astype(f32), dt.astype(f32) * x.astype(f32)
    if kernel:
        if not pallas_ssm.fits(C):
            raise ValueError(
                f"selective_scan: {C} channels are no whole tiles of "
                f"{pallas_ssm.GROUP}, which is what the TPU's kernel holds")
        block = math.gcd(S, *at, pallas_ssm.MAX_BLOCK)
        y, hs = pallas_ssm.selective_scan_pallas(
            dt, dtx, A.astype(f32), Bm, Cm, h0.astype(f32), block,
            interpret=interpret)
        return y, hs[:, -1], [hs[:, t // block - 1] for t in at]
    # time-major: a row's token is one leading index
    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (dt, dtx, Bm, Cm))

    def token(h, tok):
        dt_t, dtx_t, b_t, c_t = tok  # [B, C] [B, C] [B, N] [B, N]
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + b_t[:, :, None] * dtx_t[:, None, :])
        return h, jnp.sum(c_t[:, :, None] * h, axis=1)

    h, ys, states = h0.astype(f32), [], []
    for a, b in zip((0, *at), (*at, S)):
        h, y = jax.lax.scan(token, h, tuple(v[a:b] for v in xs))
        ys.append(y)
        states.append(h)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=0)
    return jnp.moveaxis(y, 0, 1), h, states[:-1]
