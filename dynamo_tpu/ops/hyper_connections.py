"""Manifold-constrained hyper-connections (mHC): a residual of `n` streams
mixed around each half of a layer (xing4_0's `hc_mult`).

The residual is X [..., n, h] in the served dtype.  Each half s of a layer
(attention, feed-forward) has a mixer (phi [n, h, n*n + 2n], scale [3],
base [n*n + 2n]), float32 whatever the checkpoint's dtype.  Per token, in
float32 (M = n*n + 2n):

    v    = vec(X);  r = (mean(v^2) + rms_eps)^-1/2
    m    = r (v phi)                      an RMS norm with no learned scale,
                                          applied after the product
    pre  = sigmoid(scale[0] m[:n]   + base[:n])   + eps          [n]
    post = 2 sigmoid(scale[1] m[n:2n] + base[n:2n])              [n]
    R~   = clip(scale[2] mat(m[2n:]) + mat(base[2n:]), clamp)    [n, n]
    R    = softmax over the last index of R~, + eps
    R    = R / (column sums + eps), then iters - 1 times:
           R = R / (row sums + eps);  R = R / (column sums + eps)
    u    = sum_j pre_j X_j                the half's input, h wide
    X'_k = post_k y + sum_j R[j, k] X_j   y = F_s(norm(u))

A column sum runs over the first index j, a row sum over the last, so the
last step leaves every new stream X'_k a convex combination of the old ones
and the row sums say how far the iteration is from doubly stochastic.  The
head reduces the streams with `head_reduce` before the final norm.

These are the plain `jnp` forms, over X [..., n, h]: what runs on the CPU,
under a mesh, for float32 streams and for a decode step's few rows, and the
reference that the kernels of `ops/pallas_hyper_connections.py` are tested
against (on a single-device TPU trace a half's `mix` + `pre` and its `post`
are ONE Pallas kernel each over the residual carried [T, n x h]:
`models/llama.py` `_residual` chooses).  `mix` carries the Sinkhorn steps
with the TOKENS on the minor axis (every sum is an add of token vectors,
where [tokens, n, n] would reduce over 4-wide minor axes), and `pre` /
`post` are written as sums over the n streams and not as batched 4 x 4
products, which the TPU compiler turns into a convolution over the tokens.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Mix(NamedTuple):
    """A half's mixing weights of every token: pre [..., n], post [..., n],
    res [..., n, n] (R[j, k]: old stream j into new stream k), float32, and
    err [...]: the largest |row or column sum of R - 1|."""

    pre: jax.Array
    post: jax.Array
    res: jax.Array
    err: jax.Array


def bf16_pieces(phi: jax.Array):
    """phi in float32 as three bf16 arrays that sum to it (8 + 8 + 8 bits
    of mantissa)."""
    pieces, rest = [], phi.astype(jnp.float32)
    for _ in range(3):
        pieces.append(rest.astype(jnp.bfloat16))
        rest = rest - pieces[-1].astype(jnp.float32)
    return pieces


def _mix_logits(x: jax.Array, phi: jax.Array, rms_eps: float) -> jax.Array:
    """r (v phi) of X [..., n, h] -> [M, tokens], tokens minor, float32
    products and sums.  bf16 streams are exact bf16 operands already, so
    phi alone is cut into three bf16 pieces (24 bits of mantissa between
    them) and each piece takes ONE pass with float32 accumulation: what
    `Precision.HIGHEST` computes in six, without a float32 copy of the
    streams.  Any other dtype takes the float32 product at the highest
    precision (one bf16 pass would round both operands)."""
    n, h = x.shape[-2:]
    xt = x.reshape(-1, n, h)
    xf = xt.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(-2, -1)) + rms_eps)  # [T]
    if x.dtype == jnp.bfloat16:
        m = sum(jnp.einsum("tnh,nhm->mt", xt, piece,
                           preferred_element_type=jnp.float32)
                for piece in bf16_pieces(phi))
    else:
        m = jnp.einsum("tnh,nhm->mt", xf, phi.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return m * r[None, :]


def sinkhorn_rows(rows, iters: int, eps: float, clamp: Tuple[float, float],
                  rolled: bool = False):
    """The n x n logits as a list of n lists of n arrays of one shape (rows[j]
    [k]: first index j, second k) -> R the same way, driven towards the
    doubly stochastic matrices.  Every sum is an add of such arrays and the
    whole iteration is elementwise, so XLA makes ONE fusion of it (as
    reductions over an [n, n, T] array it made some sixty small ones a mix:
    AOT, PR 37) and a Pallas kernel runs it on token vectors as it is
    written (`rolled`: the iters - 1 row-and-column steps as a `fori_loop`,
    where a trace unrolls them)."""
    n = len(rows)
    res = []
    for row in rows:  # softmax over the last index k, + eps
        row = [jnp.clip(v, clamp[0], clamp[1]) for v in row]
        top = functools.reduce(jnp.maximum, row)
        row = [jnp.exp(v - top) for v in row]
        total = sum(row)
        res.append([v / total + eps for v in row])

    def by_columns(res):
        sums = [sum(res[j][k] for j in range(n)) + eps for k in range(n)]
        return [[res[j][k] / sums[k] for k in range(n)] for j in range(n)]

    def by_rows(res):
        sums = [sum(row) + eps for row in res]
        return [[v / sums[j] for v in row] for j, row in enumerate(res)]

    res = by_columns(res)
    if rolled:
        return jax.lax.fori_loop(
            0, iters - 1, lambda _, res: by_columns(by_rows(res)), res)
    for _ in range(iters - 1):
        res = by_columns(by_rows(res))
    return res


def sinkhorn_err(res) -> jax.Array:
    """The largest |row or column sum of R - 1| of `sinkhorn_rows`' R."""
    n = len(res)
    sums = [sum(row) for row in res] + [
        sum(res[j][k] for j in range(n)) for k in range(n)]
    return functools.reduce(jnp.maximum, [jnp.abs(s - 1.0) for s in sums])


def sinkhorn(logits: jax.Array, iters: int, eps: float,
             clamp: Tuple[float, float]) -> jax.Array:
    """[n, n, ...] float32 logits (first index j, second k) -> R of the
    same shape: `sinkhorn_rows` over the n x n entries one by one, each a
    vector over the tokens."""
    return _stacked(sinkhorn_rows(_entries(logits), iters, eps, clamp))


def _entries(logits: jax.Array):
    n = logits.shape[0]
    return [[logits[j, k] for k in range(n)] for j in range(n)]


def _stacked(res) -> jax.Array:
    return jnp.stack([jnp.stack(row) for row in res])


@jax.named_scope("hc.mix")
def mix(x: jax.Array, phi: jax.Array, scale: jax.Array, base: jax.Array, *,
        iters: int, eps: float, clamp: Tuple[float, float],
        rms_eps: float) -> Mix:
    """The mixing weights of one half for every token of X [..., n, h]."""
    n = x.shape[-2]
    lead = x.shape[:-2]
    m = _mix_logits(x, phi, rms_eps)  # [n*n + 2n, T]
    scale = scale.astype(jnp.float32)
    base = base.astype(jnp.float32)[:, None]
    pre = jax.nn.sigmoid(scale[0] * m[:n] + base[:n]) + eps
    post = 2.0 * jax.nn.sigmoid(scale[1] * m[n:2 * n] + base[n:2 * n])
    res = sinkhorn_rows(
        _entries((scale[2] * m[2 * n:] + base[2 * n:]).reshape(n, n, -1)),
        iters, eps, clamp)
    return Mix(pre.T.reshape(*lead, n), post.T.reshape(*lead, n),
               jnp.moveaxis(_stacked(res), -1, 0).reshape(*lead, n, n),
               sinkhorn_err(res).reshape(lead))


def _weighted_sum(x: jax.Array, w: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    return sum(w[..., j, None] * xf[..., j, :]
               for j in range(x.shape[-2])).astype(x.dtype)


@jax.named_scope("hc.pre")
def pre(x: jax.Array, w: jax.Array) -> jax.Array:
    """sum_j w_j X_j: X [..., n, h], w [..., n] -> [..., h] in X's dtype."""
    return _weighted_sum(x, w)


@jax.named_scope("hc.post")
def post(x: jax.Array, y: jax.Array, w: jax.Array,
         res: jax.Array) -> jax.Array:
    """X'_k = w_k y + sum_j res[j, k] X_j: X [..., n, h], y [..., h], w
    [..., n], res [..., n, n] -> [..., n, h] in X's dtype."""
    xf = x.astype(jnp.float32)
    out = w[..., :, None] * y.astype(jnp.float32)[..., None, :]
    for j in range(x.shape[-2]):
        out = out + res[..., j, :, None] * xf[..., j, None, :]
    return out.astype(x.dtype)


@jax.named_scope("hc.head")
def head_reduce(x: jax.Array, phi: jax.Array, scale: jax.Array,
                base: jax.Array, *, eps: float, rms_eps: float) -> jax.Array:
    """The head's reduction of X [..., n, h] -> [..., h]: w = sigmoid(scale
    r (v phi) + base) + eps, sum_j w_j X_j.  phi [n, h, n], scale [1],
    base [n]."""
    n = x.shape[-2]
    m = _mix_logits(x, phi, rms_eps)  # [n, T]
    w = jax.nn.sigmoid(scale.astype(jnp.float32)[0] * m
                       + base.astype(jnp.float32)[:, None]) + eps
    return _weighted_sum(x, w.T.reshape(*x.shape[:-2], n))
