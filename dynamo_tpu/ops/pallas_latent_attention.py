"""Pallas TPU kernel: a prefill chunk's attention over LATENT pages.

The XLA form (`latent_attention.prefill_parts` + `latent_attention`)
gathers every page of the bucketed table into a fresh array a layer, scores
all of it and the whole chunk, masks, and keeps the [B, H, S, keys] float32
scores in HBM between its two einsums.  This kernel reads the pool's pages
in place through the page table, scores only the key tiles a query tile can
see (prefix tiles below `prefix_len`, the chunk's own tiles up to the
diagonal), and keeps scores, softmax state and accumulator in VMEM (online
softmax), so attention costs the context that is there and writes nothing
but its result.

Why it is simpler than the per-head kernel (`pallas_attention`): in the
absorbed form every head attends the SAME rows, `[c_kv | k_pe]` as keys and
`c_kv` as values (multi-query attention), so the heads fold into query rows.
`q_abs` [B, S, H, rank] is [B, S x H, rank] without a copy, one score
product is [M, rank + pe] x [rank + pe, T] with M = (tokens of the query
tile) x H, and there is no head loop to unroll: the MXU sees tall tiles and
the traced body is a few dozen equations whatever H is.

Layout: the pools are read as they are stored, [L, P, page, tiles, 128]
(`models.config.CacheSpec`: `k` the shared rotary key in the first `pe`
lanes of its first tile, `v` the latent over `rank / 128` tiles), by (layer,
page): the layer index is a scalar operand, nothing is sliced out of the
pool.  A streamed key tile's pages are put side by side once, [T, 128] and
[T, rank], the form both matmuls read.  How: a token's tiles are the
SECOND-MINOR axis of a page, so in VMEM (as in HBM) a 32-bit row holds one
lane-tile of a float32 pool, or two of a bf16 pool, packed low half first,
and tile j of all T tokens is ONE sublane-strided load of the page buffer
viewed as 32-bit rows (`ref.bitcast`, stride = 32-bit rows a token) and a
shift: bf16 is the high half of its float32.  Indexing the tile axis
(`scr[buf, :, :, j, :]`) is the same data gathered a row at a time, and
took 37% of the first version's time (PERF.md, PR 43).

Precision is the XLA form's: operands in the rows' dtype, float32 scores,
float32 max / sum / accumulator, probabilities cast to the rows' dtype for
the value product.  The scale multiplies the float32 scores.

Tests run it with ``interpret=True`` on the CPU against the XLA form
(`tests/test_pallas_latent_attention.py`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import NEG_INF, _vmem_bytes, page_plane

# Tile sizes, from one layer's attention timed alone on a v5e at both
# deployments' head counts (PERF.md, PR 43, has the table).
# Query rows (tokens x heads) one grid step works on: H 64 -> 32 tokens,
# H 32 -> 64.  Every query tile streams the prefix again, so taller tiles
# stream it less often: 1024 rows cost 10% more time than 2048 under a
# 3584-token prefix, 4096 rows give 4% back and double the VMEM.
_QUERY_ROWS = 2048

# Prefix keys streamed per inner step (whole pages): the accumulator [M,
# rank] f32 is read and written once a tile, so 512 keys a step take 12%
# less than 256.  Keys of the chunk itself per inner step: 256 scores a
# quarter more of the masked triangle than 128 and still takes 30% less
# (fewer passes over the accumulator).
_PREFIX_TILE = 512
_SELF_TILE = 256

# What a grid step may hold in VMEM (`latent_resident_bytes`, an upper
# bound: both pipeline buffers of every blocked operand, the scratch, and
# the float32 score tile with its exponentials: about 30 MiB at 2048 rows
# and 512 keys), and the scoped limit the compiler is given (the default,
# 16 MiB, refuses half of that; a v5e core has 128 MiB).
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def _geometry(S: int, H: int, page: int, rows: int, prefix_tile: int,
              self_tile: int):
    """(TQ query tokens a grid step, C pages a streamed tile, TS own keys
    an inner step) for a chunk of S tokens: TQ the largest power of two
    that divides S with TQ x H <= rows (at least one token)."""
    tq = 1
    while tq * 2 * H <= rows and S % (tq * 2) == 0:
        tq *= 2
    ts = self_tile if S % self_tile == 0 else S
    return tq, max(1, prefix_tile // page), ts


def _residents(TQ, S, H, rank, pe, page, C, k_planes, v_planes, dtype,
               pool_dtype):
    M, T = TQ * H, C * page
    f32 = jnp.float32
    blocked = [
        ((1, M, rank), dtype),  # q_abs
        ((1, M, pe), dtype),  # q_pe
        ((1, S, pe), dtype),  # the chunk's own rotary keys
        ((1, S, rank), dtype),  # its latents
        ((1, M, rank), dtype),  # o
    ]
    scratch = [
        ((2, C, page, *k_planes), pool_dtype),  # k_scr
        ((2, C, page, *v_planes), pool_dtype),  # v_scr
        ((T, k_planes[1]), pool_dtype),  # kf_scr
        ((T, -(-rank // 128) * 128), pool_dtype),  # lf_scr
        ((M, 128), f32),  # m_scr
        ((M, 128), f32),  # l_scr
        ((M, rank), f32),  # acc_scr
    ]
    return blocked, scratch


def latent_resident_bytes(TQ, S, H, rank, pe, page, C, TS, k_planes, v_planes,
                          dtype, pool_dtype) -> int:
    blocked, scratch = _residents(TQ, S, H, rank, pe, page, C, k_planes,
                                  v_planes, dtype, pool_dtype)
    # scores, their exponentials and the cast probabilities of one tile
    temps = 3 * _vmem_bytes((TQ * H, max(C * page, TS)), jnp.float32)
    return (2 * sum(_vmem_bytes(*a) for a in blocked)
            + sum(_vmem_bytes(*a) for a in scratch) + temps)


def latent_query_tile(S: int, H: int, rank: int, pe: int, page: int,
                      k_planes, v_planes, dtype, pool_dtype=None, *,
                      rows: int = _QUERY_ROWS,
                      prefix_tile: int = _PREFIX_TILE,
                      self_tile: int = _SELF_TILE):
    """Query tokens a grid step of the kernel takes of a chunk of S, or
    None where the kernel has no tile for the shape: a chunk that is no
    whole number of sublane tiles (a verify step's few tokens: the compiler
    refuses its row slices), a pool it cannot read as 32-bit rows, or rows
    that do not fit its VMEM.  The caller keeps that shape off the kernel."""
    pool_dtype = jnp.dtype(pool_dtype or dtype)
    if S % 8 or pool_dtype.itemsize not in (2, 4) or {
            k_planes[1], v_planes[1]} != {128}:
        return None  # `page_plane` reads 32-bit rows of one or two lane tiles
    tq, C, ts = _geometry(S, H, page, rows, prefix_tile, self_tile)
    while tq >= 1:
        if latent_resident_bytes(tq, S, H, rank, pe, page, C, ts, k_planes,
                                 v_planes, dtype, pool_dtype) <= _VMEM_BUDGET:
            return tq
        tq //= 2
    return None


def _kernel(
    # scalar prefetch
    pt_ref,  # [B, padded_pages] int32
    pre_ref,  # [B] int32 prefix lengths (tokens already in the cache)
    cl_ref,  # [B] int32 chunk lengths (valid tokens of the new chunk)
    layer_ref,  # [1] int32: which layer's pages of the pools to read
    # inputs
    qa_ref,  # [1, M, rank]: this grid step's queries, heads folded in
    qp_ref,  # [1, M, pe]
    kn_ref,  # [1, S, pe]: the chunk's own rotary keys, whole
    ln_ref,  # [1, S, rank]: its latents
    k_hbm,  # [L, P, page, tiles, 128] HBM: the pools as they are stored
    v_hbm,
    # outputs
    o_ref,  # [1, M, rank]
    # scratch
    k_scr,  # [2, C, page, tiles, 128]: double-buffered pages
    v_scr,
    kf_scr,  # [T, 128]: the streamed tile's rotary keys, a row a token
    lf_scr,  # [T, rank up to a lane tile]: its latents
    m_scr,  # [M, 128] f32: running max a row (lane-replicated)
    l_scr,  # [M, 128] f32: running denominator
    acc_scr,  # [M, rank] f32
    sems,  # DMA [2 buffers, 2 pools, C]
    *,
    H: int,
    TQ: int,
    C: int,
    page: int,
    TS: int,
    scale: float,
):
    b = pl.program_id(0)
    q0 = pl.program_id(1) * TQ  # this step's first token of the chunk
    M, rank = acc_scr.shape
    pe = qp_ref.shape[-1]
    T = C * page
    prefix_len = pre_ref[b]
    chunk_len = cl_ref[b]
    layer = layer_ref[0]

    def attend(kpe, lat, valid):
        """Online-softmax update of all M rows against one key tile: kpe
        [Tk, pe], lat [Tk, rank], valid broadcastable to [M, Tk]."""
        nt = (((1,), (1,)), ((), ()))
        s = scale * (
            jax.lax.dot_general(qa_ref[0], lat, nt,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qp_ref[0], kpe, nt,
                                  preferred_element_type=jnp.float32))
        s = jnp.where(valid, s, NEG_INF)  # [M, Tk]
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        pv = jax.lax.dot_general(p.astype(lat.dtype), lat,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    def pages(tile, buf, wait):
        """Start (or await) the 2C async copies that bring key tile `tile`'s
        pages into buffer `buf`.  A loop in the kernel, not in Python: the
        copies of a tile are one traced body whatever C is (unrolled, the
        descriptors of 32 pages at five sites were nine tenths of the
        kernel's trace, and a program's trace runs on the step thread)."""
        def page(i, carry):
            pid = pt_ref[b, tile * C + i]
            for pool, (hbm, scr) in enumerate(((k_hbm, k_scr),
                                               (v_hbm, v_scr))):
                copy = pltpu.make_async_copy(
                    hbm.at[layer, pid], scr.at[buf, i], sems.at[buf, pool, i])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, C, page, 0)

    # a query tile wholly past the chunk's length is padding: no key is
    # fetched or scored for it
    @pl.when(q0 >= chunk_len)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(q0 < chunk_len)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        # ---- the prefix: pages streamed in place, below prefix_len only ---- #
        n_pre = (prefix_len + T - 1) // T

        @pl.when(n_pre > 0)
        def _():
            pages(0, 0, wait=False)

        def prefix_tile(c, carry):
            buf = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_pre)
            def _():
                pages(c + 1, 1 - buf, wait=False)

            pages(c, buf, wait=True)
            kf_scr[...] = page_plane(k_scr, buf, 0, T)
            for j in range(lf_scr.shape[1] // 128):
                lf_scr[:, j * 128:(j + 1) * 128] = page_plane(
                    v_scr, buf, j, T)
            # the last tile may end inside the prefix (one traced body for
            # whole tiles and that one: the select is 1% of a tile's time,
            # a second body a third of the kernel's trace)
            kpos = c * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            attend(kf_scr[:, :pe], lf_scr[:, :rank], kpos < prefix_len)
            return carry

        jax.lax.fori_loop(0, n_pre, prefix_tile, 0)

        # ---- the chunk itself, causal: tiles up to the diagonal ---- #
        row = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
        tok = q0 + (row >> int(math.log2(H)) if H & (H - 1) == 0
                    else jax.lax.div(row, jnp.int32(H)))  # [M, 1]
        n_self = jnp.minimum((q0 + TQ + TS - 1) // TS,
                             (chunk_len + TS - 1) // TS)

        def self_tile(j, carry):
            j0 = pl.multiple_of(j * TS, TS)
            kpos = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, TS), 1)
            attend(kn_ref[0, pl.ds(j0, TS), :], ln_ref[0, pl.ds(j0, TS), :],
                   (kpos <= tok) & (kpos < chunk_len))
            return carry

        jax.lax.fori_loop(0, n_self, self_tile, 0)

        inv = 1.0 / jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype)


def prefill_latent_attention_pallas(
    q_abs: jax.Array,  # [B, S, H, rank]: queries with W_uk folded in
    q_pe: jax.Array,  # [B, S, H, pe]: their rotated rotary part
    kpe_new: jax.Array,  # [B, S, pe]: the chunk's own rotary keys
    lat_new: jax.Array,  # [B, S, rank]: its latents
    k_pool: jax.Array,  # [P, page, tiles, 128], or [L, P, ...] with `layer`
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    prefix_lens: jax.Array,  # [B]
    chunk_lens: jax.Array,  # [B]
    scale: float,
    *,
    layer=None,  # scalar layer index (traced OK) into whole pools
    interpret: bool = False,
    rows: int = _QUERY_ROWS,
    prefix_tile: int = _PREFIX_TILE,
    self_tile: int = _SELF_TILE,
) -> jax.Array:
    """The chunk attends to its cached prefix and itself (causal), as
    `latent_attention(q_abs, q_pe, prefill_parts(...), scale)`.  Returns
    [B, S, H, rank] in q's dtype; rows at or past `chunk_lens` are padding
    and hold no meaning (as in the XLA form)."""
    B, S, H, rank = q_abs.shape
    pe = q_pe.shape[-1]
    if layer is None:  # one layer's pools: pools of one layer
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    page = k_pool.shape[2]
    k_planes, v_planes = k_pool.shape[3:], v_pool.shape[3:]
    sizes = dict(rows=rows, prefix_tile=prefix_tile, self_tile=self_tile)
    TQ = latent_query_tile(S, H, rank, pe, page, k_planes, v_planes,
                           q_abs.dtype, k_pool.dtype, **sizes)
    if TQ is None:
        raise ValueError(
            f"no query tile for a {S}-token chunk (H={H}, rank={rank}, "
            f"pe={pe}, page={page}, {k_pool.dtype} pool) within "
            f"{_VMEM_BUDGET} B of VMEM")
    _, C, TS = _geometry(S, H, page, **sizes)
    maxp = page_table.shape[1]
    padded = -(-maxp // C) * C
    if padded != maxp:
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - maxp)))
    M = TQ * H
    blocked, scratch = _residents(TQ, S, H, rank, pe, page, C, k_planes,
                                  v_planes, q_abs.dtype, k_pool.dtype)
    qa_blk, qp_blk, kn_blk, ln_blk, o_blk = (blk for blk, _ in blocked)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, S // TQ),
        in_specs=[
            pl.BlockSpec(qa_blk, lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec(qp_blk, lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec(kn_blk, lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(ln_blk, lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(o_blk, lambda b, i, *_: (b, i, 0)),
        scratch_shapes=[
            *(pltpu.VMEM(shape, dtype) for shape, dtype in scratch),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ],
    )
    kernel = functools.partial(_kernel, H=H, TQ=TQ, C=C, page=page, TS=TS,
                               scale=float(scale))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S * H, rank), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        page_table,
        prefix_lens.astype(jnp.int32),
        chunk_lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_abs.reshape(B, S * H, rank), q_pe.reshape(B, S * H, pe),
        kpe_new, lat_new, k_pool, v_pool,
    )
    return out.reshape(B, S, H, rank)
