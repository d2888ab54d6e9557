"""Pallas TPU kernel for Mamba-1's selective scan (`ops.ssm.selective_scan`
says what it computes): the loop over the tokens INSIDE one kernel, the state
resident in vector registers.

Why a kernel: every token's decay is [state, channels] numbers of its own, so
the recurrence has no block form, and a loop over the tokens in XLA is a
`while` of several small fusions a token: 4,608 iterations a 512-token step
of nine layers, so many device ops that a profiler trace of a serving window
drops its buffers and holds nothing (PERF.md, PR 48).  Here a layer's scan is
ONE device op, 0.10 ms a 512-token chunk of 5,120 channels in the cell's
traced window.

Layout.  A group of 1,024 channels is ONE [8, 128] float32 tile a state
index: the channels fill sublanes and lanes, and `B_t[n]`, `C_t[n]` are
scalars read from SMEM, so every vector operation runs on whole registers
and nothing is broadcast along an axis.  The grid is (row, channel group,
block of tokens); the last axis is sequential and carries the state in a
VMEM scratch.  A block's end is where a state can be handed out: every
block writes its state, and the caller keeps the ones it wants.  Per token
and group: 16 exponentials, 16 x (3 products, 2 sums).

The second kernel here is Mamba-2's blocked scan (`ops.ssm.scan` says what
it computes; `scan_pallas`, at the end): ONE kernel a layer over a prefill
chunk.  Why a kernel: as plain `jnp` a block of 128 tokens wrote its decays
`exp(L_t - L_s)`, the scores `C B^T` REPEATED to every head of a group, and
their product in float32 and again in the served dtype through HBM, four to
five [heads, 128, 128] float32 arrays a block (4 MB each at nemotron_h's 64
heads), 1.8 GB a 512-token step of 23 layers where the scan's own inputs,
output and state are 19 MB a layer (PERF.md finding 40).

Layout.  The grid is (row, head group, block of tokens), the last axis
sequential; a grid step holds the group's x [block, heads x head_dim] as the
convolution left it (a token a sublane row, the group's heads side by side
along the lanes), the group's B and C [block, state], and the group's state
[heads x head_dim, state] float32 in a VMEM scratch from block to block.
`C B^T` is one product a GROUP; a head's decays, their product with the
scores and with dt, and that product with x live in VMEM and vector
registers only.  A head narrower than a lane tile (nemotron_h's 64) shares
its tile with its neighbour: each multiplies the tile with the other's
lanes zeroed, so no slice starts inside a tile.  The step sizes and their
running log decays are [tokens, heads] float32, a sixtieth of x: the
wrapper hands them in both ways up (a token a sublane row for what scales x
and y, a token a lane for the decays' rows), so nothing is transposed
inside.  Every block writes its state; the caller keeps the ones it wants.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_moe

LANES, SUBLANES = 128, 8
GROUP = LANES * SUBLANES  # channels a tile
# most tokens a grid step: what its blocks of dt, dt * x and y hold in VMEM
# (3 x 2 buffers x 128 x 4 KB)
MAX_BLOCK = 128


def fits(channels: int) -> bool:
    """Whether the kernel's layout holds this many channels: whole tiles."""
    return channels % GROUP == 0


def _kernel(b_ref, c_ref, dt_ref, dtx_ref, a_ref, h0_ref, y_ref, hs_ref,
            h_scr, *, tokens: int, states: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = h0_ref[...]

    a = [a_ref[n] for n in range(states)]

    def token(t, h):
        dt, dtx = dt_ref[t], dtx_ref[t]  # [8, 128] each
        h = tuple(jnp.exp(dt * a[n]) * h[n] + b_ref[t, n] * dtx
                  for n in range(states))
        y = c_ref[t, 0] * h[0]
        for n in range(1, states):
            y = y + c_ref[t, n] * h[n]
        y_ref[t] = y
        return h

    h = jax.lax.fori_loop(0, tokens, token,
                          tuple(h_scr[n] for n in range(states)))
    for n in range(states):
        h_scr[n] = h[n]
        hs_ref[n] = h[n]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def selective_scan_pallas(dt: jax.Array, dtx: jax.Array, A: jax.Array,
                          Bm: jax.Array, Cm: jax.Array, h0: jax.Array,
                          block: int, interpret: bool = False):
    """dt, dtx (= dt * x) [B, S, C] float32; A [N, C]; Bm, Cm [B, S, N]; h0
    [B, N, C], all float32; `block` tokens a grid step, dividing S.
    -> (y [B, S, C], the state after every block [B, S / block, N, C])."""
    B, S, C = dt.shape
    N, G, nb = A.shape[0], C // GROUP, S // block
    tile = (SUBLANES, LANES)
    f32 = jnp.float32

    def tiles(a):  # [..., C] -> [..., G, 8, 128]
        return a.astype(f32).reshape(*a.shape[:-1], G, *tile)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    y, hs = pl.pallas_call(
        functools.partial(_kernel, tokens=block, states=N),
        grid=(B, G, nb),
        in_specs=[
            smem((None, block, N), lambda b, g, s: (b, s, 0)),
            smem((None, block, N), lambda b, g, s: (b, s, 0)),
            pl.BlockSpec((None, block, None, *tile),
                         lambda b, g, s: (b, s, g, 0, 0)),
            pl.BlockSpec((None, block, None, *tile),
                         lambda b, g, s: (b, s, g, 0, 0)),
            pl.BlockSpec((N, None, *tile), lambda b, g, s: (0, g, 0, 0)),
            pl.BlockSpec((None, N, None, *tile),
                         lambda b, g, s: (b, 0, g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block, None, *tile),
                         lambda b, g, s: (b, s, g, 0, 0)),
            pl.BlockSpec((None, None, N, None, *tile),
                         lambda b, g, s: (b, s, 0, g, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, G, *tile), f32),
                   jax.ShapeDtypeStruct((B, nb, N, G, *tile), f32)],
        scratch_shapes=[pltpu.VMEM((N, *tile), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(Bm.astype(f32), Cm.astype(f32), tiles(dt), tiles(dtx), tiles(A),
      tiles(h0))
    return y.reshape(B, S, C), hs.reshape(B, nb, N, C)


# ---- Mamba-2's blocked scan -------------------------------------------------

# fewest tokens a block the kernel takes (`scan_lowering`): a block ends
# where a state is handed out, so a short row of the served path (64 tokens
# and fewer, a state every 16-token page, four rows a step) would be blocks
# of 16, a grid step each, 128 a nemotron_h layer.  Measured on the v5e
# (PERF.md finding 40; `scripts/time_ssm_scan.py`, ms a layer of convolution
# + scan + norm, kernel / `jnp`): four rows of 64 tokens in blocks of 16,
# nemotron_h 0.264 / 0.155 (the kernel alone 0.139), falcon_h1 0.308 / 0.456
# (32 grid steps a layer); one row of 64, 0.067 / 0.054 and 0.053 / 0.091.
# In blocks of 128: 1 x 128 0.040 / 0.059 and 0.038 / 0.058, 1 x 512 0.098 /
# 0.134 and 0.080 / 0.130, 4 x 512 0.595 / 1.125 and 0.696 / 1.218.  The
# claimed cell's short rows lose by 1.7, so they stay `jnp`.
MIN_SCAN_BLOCK = 128
SCAN_VMEM_LIMIT_BYTES = 64 << 20


def scan_block(S: int, chunk: int, at: tuple) -> int:
    """Tokens a block of a scan over S tokens that hands its state out
    after each of `at`: the model's own block where every hand-out falls on
    a block's end, else the largest that ends at each."""
    return math.gcd(min(chunk, S), *at)


def scan_lowering(x: jax.Array, G: int, N: int, chunk: int,
                  at: tuple) -> Tuple[Optional[bool], str]:
    """Is this trace's blocked scan over x [B, S, heads, head_dim] (G groups
    of B and C, N state values) the kernel?  -> (its `interpret`, or None:
    the trace keeps the `jnp` form; why).  Static shapes only: a
    single-device TPU program (`pallas_moe.single_device`: no mesh, or a
    check's say-so), blocks of `MIN_SCAN_BLOCK` tokens or more, and widths
    its tiles hold (a group's heads x head_dim and N in whole lanes, a head
    a whole number of lane tiles or a whole share of one)."""
    _, S, nh, hp = x.shape
    block = scan_block(S, chunk, at)
    if block < MIN_SCAN_BLOCK:
        return None, f"blocks of {block} tokens, under {MIN_SCAN_BLOCK}"
    if block % LANES:
        return None, f"blocks of {block} tokens in no whole lanes"
    if (nh // G * hp) % LANES or N % LANES or (hp % LANES and LANES % hp):
        return None, (f"{nh // G} heads of {hp} a group, state {N}: "
                      "no whole lane tiles")
    interpret = pallas_moe.single_device(x)
    return interpret, ("no single-device TPU trace" if interpret is None
                       else f"blocks of {block} tokens on one TPU device")


def _scan_kernel(x_ref, b_ref, c_ref, lc_ref, dc_ref, lr_ref, dr_ref, d_ref,
                 h0_ref, *rest, per: int, hp: int, eps: Optional[float]):
    """One block of one group: x_ref [Q, per x hp]; b_ref, c_ref [Q, N];
    lc_ref, dc_ref [Q, per] the running log decays L and the step sizes, a
    token a sublane row; lr_ref, dr_ref [per, Q] the same, a token a lane;
    d_ref [1, per x hp]; h0_ref, hs_ref, h_scr [per x hp, N] float32.  With
    `eps`, the gated norm's z_ref [Q, per x hp] and w_ref [1, per x hp]
    follow h0_ref and a float32 scratch g_scr [Q, per x hp] follows h_scr:
    y leaves as `ops.ssm.gate_norm` of it."""
    f32 = jnp.float32
    if eps is None:
        y_ref, hs_ref, h_scr = rest
    else:
        z_ref, w_ref, y_ref, hs_ref, h_scr, g_scr = rest
    Q, mxu = x_ref.shape[0], x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = h0_ref[...]

    nt = (((1,), (1,)), ((), ()))  # a @ b^T
    bq, cq = b_ref[...], c_ref[...]
    cb = jax.lax.dot_general(cq, bq, nt, preferred_element_type=f32)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
           <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    W = max(hp, LANES)  # columns a unit: whole lane tiles, whole heads
    k = W // hp
    units = per * hp // W
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // hp

    def lanes(column):
        """A value a head and token, [.., 1] by head -> along the unit's
        lanes, each head's over its own."""
        out = column(0)
        for j in range(1, k):
            out = jnp.where(lane_head == j, column(j), out)
        return out

    squares = None
    for u in range(units):
        cols = slice(u * W, (u + 1) * W)
        heads = [slice(u * k + j, u * k + j + 1) for j in range(k)]
        xu = x_ref[:, cols]
        y = None
        for j, h in enumerate(heads):
            # exp(L_t - L_s), s <= t: never above 1
            seg = lc_ref[:, h] - lr_ref[h, :]
            m = cb * jnp.where(tri, jnp.exp(seg), 0.0) * dr_ref[h, :]
            xj = xu if k == 1 else jnp.where(lane_head == j, xu,
                                             jnp.zeros_like(xu))
            part = jnp.dot(m.astype(mxu), xj, preferred_element_type=f32)
            y = part if y is None else y + part
        L = lanes(lambda j: lc_ref[:, heads[j]])
        last = lanes(lambda j: lc_ref[Q - 1:Q, heads[j]])
        dt = lanes(lambda j: dc_ref[:, heads[j]])
        # what the carried state adds: exp(L_t) C_t . H
        ch = jax.lax.dot_general(cq, h_scr[cols, :].astype(mxu), nt,
                                 preferred_element_type=f32)
        xf = xu.astype(f32)
        y = y + jnp.exp(L) * ch
        y = (y + d_ref[:, cols] * xf).astype(y_ref.dtype)
        if eps is None:
            y_ref[:, cols] = y
        else:  # the gate first, from y as the served dtype rounds it
            g = y.astype(f32) * jax.nn.silu(z_ref[:, cols].astype(f32))
            g_scr[:, cols] = g
            # the squares lane by lane: ONE sum across the lanes a block
            # (one a unit took as long as the scan: PERF.md finding 40)
            for c in range(W // LANES):
                part = g[:, c * LANES:(c + 1) * LANES]
                squares = part * part if squares is None else (
                    squares + part * part)
        # H' = exp(L_Q) H + (w x)^T B, w_s = exp(L_Q - L_s) dt_s
        xw = (xf * (jnp.exp(last - L) * dt)).astype(mxu)
        add = jax.lax.dot_general(xw, bq, (((0,), (0,)), ((), ())),
                                  preferred_element_type=f32)
        for j, h in enumerate(heads):
            rows = slice(u * W + j * hp, u * W + (j + 1) * hp)
            # along the lanes first: one value to a whole tile is refused
            e = jnp.exp(jnp.broadcast_to(lc_ref[Q - 1:Q, h],
                                         (1, h_scr.shape[1])))
            h_scr[rows, :] = e * h_scr[rows, :] + add[j * hp:(j + 1) * hp]
    hs_ref[...] = h_scr[...]
    if eps is not None:  # the group's columns are one group of the norm
        r = jax.lax.rsqrt(
            jnp.sum(squares, axis=-1, keepdims=True) / (per * hp) + eps)
        for u in range(units):
            cols = slice(u * W, (u + 1) * W)
            y_ref[:, cols] = ((g_scr[:, cols] * r)
                              * w_ref[:, cols]).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "eps", "interpret"))
def scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, D: jax.Array, h0: jax.Array, block: int,
                gate: Optional[Tuple[jax.Array, jax.Array]] = None,
                eps: Optional[float] = None, interpret: bool = False):
    """`ops.ssm.scan`'s operands, `block` tokens a grid step, dividing S;
    `gate` (z [B, S, nh x hp or more: the gate is its FIRST nh x hp columns,
    read where they lie], w [nh x hp]) and `eps` of the gated norm: both,
    or neither.
    -> (y [B, S, nh x hp] in x's dtype, the state after every block [S /
    block, B, nh, hp, N] float32)."""
    B, S, nh, hp = x.shape
    G, N = Bm.shape[2:]
    per, nb, f32 = nh // G, S // block, jnp.float32
    P = per * hp
    # the step sizes and their running log decays L_t, a block at a time, a
    # token a lane [B, G, nb, per, block] (the sum runs along the lanes), and
    # the same a token a sublane row
    step = jnp.transpose(dt.astype(f32).reshape(B, nb, block, G, per),
                         (0, 3, 1, 4, 2))
    # (a product with a triangle of ones at full precision: as a
    # `reduce_window` the TPU compiler's running sum of these 128 KB took
    # longer than the kernel, 45 of a layer's 158 us: PERF.md finding 40)
    logs = jnp.einsum(
        "bgcks,st->bgckt", step * A.astype(f32).reshape(G, 1, per, 1),
        jnp.triu(jnp.ones((block, block), f32)),
        precision=jax.lax.Precision.HIGHEST)
    rows = (logs, step)
    cols = tuple(jnp.swapaxes(a, 3, 4) for a in rows)

    def by_tokens(width):  # [B, S, G x width]: a block's, a group's columns
        return pl.BlockSpec((None, block, width), lambda b, g, c: (b, c, g))

    def by_heads(*dims):  # [B, G, nb, *dims]
        return pl.BlockSpec((None, None, None, *dims),
                            lambda b, g, c: (b, g, c, 0, 0))

    by_group = pl.BlockSpec((1, P), lambda b, g, c: (0, g))  # [1, G x P]
    gated = () if gate is None else (
        gate[0], gate[1].astype(f32).reshape(1, nh * hp))
    y, hs = pl.pallas_call(
        functools.partial(_scan_kernel, per=per, hp=hp, eps=eps),
        grid=(B, G, nb),
        in_specs=[
            by_tokens(P), by_tokens(N), by_tokens(N),
            by_heads(block, per), by_heads(block, per),
            by_heads(per, block), by_heads(per, block), by_group,
            pl.BlockSpec((None, P, N), lambda b, g, c: (b, g, 0)),
            *((by_tokens(P), by_group) if gated else ()),
        ],
        out_specs=[
            by_tokens(P),
            pl.BlockSpec((None, None, P, N), lambda b, g, c: (c, b, g, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, nh * hp), x.dtype),
                   jax.ShapeDtypeStruct((nb, B, nh * hp, N), f32)],
        scratch_shapes=[pltpu.VMEM((P, N), f32),
                        *((pltpu.VMEM((block, P), f32),) if gated else ())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SCAN_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssm.scan",
    )(x.reshape(B, S, nh * hp), Bm.reshape(B, S, G * N),
      Cm.reshape(B, S, G * N), *cols, *rows,
      jnp.repeat(D.astype(f32), hp).reshape(1, nh * hp),
      h0.astype(f32).reshape(B, nh * hp, N), *gated)
    return y, hs.reshape(nb, B, nh, hp, N)
