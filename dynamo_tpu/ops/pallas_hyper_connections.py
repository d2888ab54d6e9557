"""Pallas TPU kernels for the stream mixers of `ops/hyper_connections.py`:
a half of a layer reads the residual's n streams ONCE to mix and read them
(`read`: `mix` + `pre`) and once more to write them back (`write`: `post`).

Why kernels: as plain `jnp` the TPU compiler stored the residual [T, n, h]
with the n streams under the sublanes of a 4-row tile, re-laid it out to
stream planes three times over for the mixer's product (one copy for each
bf16 piece of phi), wrote `post`'s result three times in that layout for the
next half's products, and made a float32 copy of the streams for `pre`:
eleven stream-sized results a layer where two reads and one write are needed
(AOT compile for a described v5e, PERF.md finding 38).

Layout.  The residual is carried [T, n x h]: a token's streams side by side
along the lanes, stream j the columns [j h, (j + 1) h).  A kernel's block is
a tile of `TOKEN_TILE` tokens, all n x h columns of it (128 x 4 x 3584 bf16
= 3.7 MB), and a stream plane is a lane-aligned slice of the block: no
relayout exists inside or outside.

`read`, per tile: the mean square of a token's n x h values (lane-partial
sums, transposed so the tokens lie along the lanes) and the mixer's logits
`phi^T X^T` [3 x rows, tile] as ONE product a stream plane, phi's three bf16
pieces side by side along the rows and accumulated in float32 (the
arithmetic `hyper_connections._mix_logits` documents); then, with the
tokens on the lanes, the sigmoids and the Sinkhorn steps
(`hyper_connections.sinkhorn_rows`, its 19 row-and-column steps a
`fori_loop`); the weights transposed back to one row a token; and `u = sum_j
pre_j X_j` from the tile still in VMEM, rounded once.  -> u [T, h] and the
tile's weights [T, 128] float32 (`columns`).

`write`, per tile: `X'_k = post_k y + sum_j R[j, k] X_j` in float32, the
sums in `hyper_connections.post`'s order, rounded once; elementwise, so bit
for bit what XLA computes from the same weights.  The streams are updated in
place (the output aliases them).

A trace takes the kernels where `lowering` says so and `hyper_connections`'
`jnp` forms elsewhere; those stay the reference the kernels are tested
against (tests/test_hc_kernels.py, interpreted on the CPU).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hyper_connections as hc
from . import pallas_moe

LANES = pallas_moe.LANES
# tokens a tile: the lanes of one vector register, so that a tile's weights
# are [rows, 128] with the tokens on the lanes and transpose as one square
TOKEN_TILE = 128
MIN_TOKENS = 16
# rows `write` holds at a time: one packed bf16 register
ROWS = 16
# a bf16 piece of phi is padded to whole (16, 128) tiles of rows
PIECE_ALIGN = 16
# where the normalised logits lie among a token's 128 weight columns (after
# pre, post, R and err: `columns`), for the tests
LOGITS_AT = 64
VMEM_LIMIT_BYTES = 64 << 20


def columns(n: int):
    """(pre, post, R row-major [j, k], err, logits): where each lies among
    the 128 float32 columns `read` returns for a token."""
    M = n * n + 2 * n
    return (slice(0, n), slice(n, 2 * n), slice(2 * n, M), M,
            slice(LOGITS_AT, LOGITS_AT + M))


def lowering(x: jax.Array, n: int) -> Tuple[Optional[bool], str]:
    """Are the mixers of this trace the kernels, for streams x [..., n x h]?
    -> (their `interpret`, or None: the trace keeps the `jnp` forms; why).
    The kernels are a single-device TPU program (`pallas_moe.single_device`:
    no mesh, or a check's say-so) over bf16 streams of a hidden size in
    whole lanes, 16 tokens or more (a decode step of fewer rows is XLA's),
    a token's weights in its 128 columns."""
    h = x.shape[-1] // n
    if x.dtype != jnp.bfloat16:
        return None, f"{x.dtype} streams"
    if h % LANES:
        return None, f"hidden size {h} in no whole lanes"
    if x.size // x.shape[-1] < MIN_TOKENS:
        return None, f"fewer than {MIN_TOKENS} tokens"
    if n * n + 2 * n >= LOGITS_AT:
        return None, f"{n} streams' weights in no {LANES} columns"
    interpret = pallas_moe.single_device(x)
    return interpret, ("no single-device TPU trace" if interpret is None
                       else "bf16 streams on one TPU device")


def _piece_rows(M: int) -> int:
    return -(-M // PIECE_ALIGN) * PIECE_ALIGN


def _pieces(phi: jax.Array) -> jax.Array:
    """phi [n, h, M] float32 -> [n, 3 rows, h] bf16: its three bf16 pieces
    (`hyper_connections.bf16_pieces`), each transposed and padded to whole
    tiles of rows."""
    M = phi.shape[-1]
    return jnp.concatenate(
        [jnp.pad(jnp.swapaxes(piece, 1, 2),
                 ((0, 0), (0, _piece_rows(M) - M), (0, 0)))
         for piece in hc.bf16_pieces(phi)], axis=1)


def _padded(a: jax.Array) -> jax.Array:
    """Whole tiles of rows: a step of fewer tokens than a tile is one, and a
    count that is no whole number of tiles pays a copy (a step's tokens are
    a power of two: 16 to 64, or tiles)."""
    return jnp.pad(a, ((0, -a.shape[0] % TOKEN_TILE), (0, 0)))


def _read_kernel(x_ref, phi_ref, affine_ref, u_ref, w_ref, rows_ref, *,
                 n: int, h: int, iters: int, eps: float,
                 clamp: Tuple[float, float], rms_eps: float):
    f32 = jnp.float32
    M = n * n + 2 * n
    mp = _piece_rows(M)
    tm = x_ref.shape[0]

    # one pass over the tile: the logits' product and the squares
    logits = jnp.zeros((3 * mp, tm), f32)
    squares = jnp.zeros((tm, LANES), f32)
    for j in range(n):
        xj = x_ref[:, j * h:(j + 1) * h]
        logits = logits + jax.lax.dot_general(
            phi_ref[j], xj, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        xf = xj.astype(f32)
        sq = xf * xf
        for c in range(h // LANES):
            squares = squares + sq[:, c * LANES:(c + 1) * LANES]
    # tokens onto the lanes
    total = jnp.sum(squares.T, axis=0, keepdims=True)  # [1, tm]
    r = jax.lax.rsqrt(total / (n * h) + rms_eps)
    m = ((logits[:M] + logits[mp:mp + M]) + logits[2 * mp:2 * mp + M]) * r
    z = affine_ref[0] * m + affine_ref[1]  # scale and base by row

    rows_ref[...] = jnp.zeros_like(rows_ref)
    rows_ref[0:n, :] = jax.nn.sigmoid(z[0:n]) + eps
    rows_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    res = hc.sinkhorn_rows(
        [[z[2 * n + j * n + k:2 * n + j * n + k + 1] for k in range(n)]
         for j in range(n)], iters, eps, clamp, rolled=True)
    for j in range(n):
        for k in range(n):
            at = 2 * n + j * n + k
            rows_ref[at:at + 1, :] = res[j][k]
    rows_ref[M:M + 1, :] = hc.sinkhorn_err(res)
    rows_ref[LOGITS_AT:LOGITS_AT + M, :] = m
    w = rows_ref[...].T  # [tm, 128]: one row a token
    w_ref[...] = w

    acc = None
    for j in range(n):
        term = w[:, j:j + 1] * x_ref[:, j * h:(j + 1) * h].astype(f32)
        acc = term if acc is None else acc + term
    u_ref[...] = acc.astype(u_ref.dtype)


def read(x: jax.Array, phi: jax.Array, scale: jax.Array, base: jax.Array, *,
         iters: int, eps: float, clamp: Tuple[float, float], rms_eps: float,
         interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """A half's read of the streams x [T, n x h] (bf16) under its mixer
    (phi [n, h, M], scale [3], base [M]) -> (u [T, h]: `pre` of `mix`'s
    weights; w [T, 128] float32: the weights by `columns`).

    Not jitted on its own: the compiler names the call after the caller's
    named scope (`%hc.mix.N`), which is how a trace's walks place it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, M = phi.shape
    T = x.shape[0]
    tm = TOKEN_TILE
    by_row = jnp.repeat(scale.astype(jnp.float32),
                        np.array([n, n, n * n]), total_repeat_length=M)
    affine = jnp.broadcast_to(
        jnp.stack([by_row, base.astype(jnp.float32)])[:, :, None],
        (2, M, tm))
    xp = _padded(x)
    Tp = xp.shape[0]
    u, w = pl.pallas_call(
        functools.partial(_read_kernel, n=n, h=h, iters=iters, eps=eps,
                          clamp=clamp, rms_eps=rms_eps),
        grid=(Tp // tm,),
        in_specs=[pl.BlockSpec((tm, n * h), lambda i: (i, 0)),
                  pl.BlockSpec((n, 3 * _piece_rows(M), h),
                               lambda i: (0, 0, 0)),
                  pl.BlockSpec((2, M, tm), lambda i: (0, 0, 0))],
        out_specs=[pl.BlockSpec((tm, h), lambda i: (i, 0)),
                   pl.BlockSpec((tm, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Tp, h), x.dtype),
                   jax.ShapeDtypeStruct((Tp, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((LANES, tm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp, _pieces(phi), affine)
    return u[:T], w[:T]


def _write_kernel(x_ref, y_ref, w_ref, out_ref, *, n: int, h: int):
    """Sixteen tokens (one bf16 register of rows) and `lane_chunk` columns
    at a time, a token's 20 weights held across the columns: as whole-plane
    expressions the same sums took half as long again (0.139 against 0.091
    ms over 512 tokens from HBM: my chip runs, PR 58)."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    post, res = n, 2 * n  # `columns`
    c = pallas_moe.lane_chunk(h)

    def rows(r, _):
        at = pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)
        w = w_ref[at, :]
        cols = [w[:, i:i + 1] for i in range(res + n * n)]
        for c0 in range(0, h, c):
            yf = y_ref[at, c0:c0 + c].astype(f32)
            xs = [x_ref[at, j * h + c0:j * h + c0 + c].astype(f32)
                  for j in range(n)]
            for k in range(n):
                out = cols[post + k] * yf
                for j in range(n):
                    out = out + cols[res + j * n + k] * xs[j]
                out_ref[at, k * h + c0:k * h + c0 + c] = out.astype(
                    out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0] // ROWS, rows, 0)


def write(x: jax.Array, y: jax.Array, w: jax.Array, *, n: int,
          interpret: bool = False) -> jax.Array:
    """A half's write back: streams x [T, n x h], the half's output y [T,
    h] and `read`'s weights w [T, 128] -> the new streams [T, n x h], in
    place of the old."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh = x.shape
    h = nh // n
    tm = TOKEN_TILE
    xp, yp, wp = _padded(x), _padded(y), _padded(w)
    out = pl.pallas_call(
        functools.partial(_write_kernel, n=n, h=h),
        grid=(xp.shape[0] // tm,),
        in_specs=[pl.BlockSpec((tm, nh), lambda i: (i, 0)),
                  pl.BlockSpec((tm, h), lambda i: (i, 0)),
                  pl.BlockSpec((tm, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tm, nh), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xp, yp, wp)
    return out[:T]
