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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
