"""Pallas TPU kernels for paged attention.

Why a kernel at all: the XLA path (`paged_attention.gather_kv`) materializes
each sequence's KV into a fresh ``[B, max_pages*page, n_kv, hd]`` array in
HBM every step — the pool is read, written, and read again (3x traffic),
and the intermediate grows with the page-table bucket, not the true context.
The kernels here stream KV pages HBM→VMEM exactly once per step with
double-buffered async DMA and accumulate flash-attention style (online
softmax), so attention traffic is the true KV footprint and nothing else.

Layout notes:
- The page pool is ``[L, P, page, n_kv, hd]`` (see
  ``paged_attention.write_kv_layers``), and the head loops want a streamed
  chunk as ``[T, n_kv*hd]``: heads side by side on lanes, so a head is a
  static lane slice and the scratch tile is exactly (16, 128) for bf16.
  That is NOT how the pool is stored: in HBM one token's ``[n_kv, hd]`` is
  its own tile, so ``reshape(P, page, n_kv*hd)`` outside the kernel is a
  relayout copy of the whole slab (1.7 s of a 40 s window, PERF.md PR 26).
- The PREFILL kernel therefore takes the whole pool and a layer index,
  fetches page ``pid`` of layer ``l`` as it is stored (``k_hbm.at[l,
  pid]``, a page comes whole: Mosaic slices no single head out of a tile)
  and puts the heads side by side in VMEM once per streamed chunk.  Heads
  narrower than 128 lanes cannot be fetched that way (the minor dimension
  is padded in HBM) and keep one slice + relayout of the layer's slab.
- The DECODE kernel still gets one layer's slab in the ``[P, page,
  n_kv*hd]`` view from the scanned decode loop.
- Prefill flattens the query heads onto lanes the same way (``[S, H*hd]``)
  and walks the chunk in QUERY BLOCKS on a grid axis of its own: q, o, the
  online-softmax scalars (``[QB, H]``) and the f32 accumulator are QB rows
  whatever the chunk, so the VMEM footprint stops growing with it.  QB
  comes from ``prefill_query_block``, the one function that also sizes the
  scratch.

The reference delegates attention kernels to vLLM/TRT-LLM (SURVEY.md §2.6);
this module is the TPU-native equivalent of their CUDA paged-attention
kernels.

Tests run these with ``interpret=True`` on CPU against the einsum path;
the engine selects them on real TPU (``EngineConfig.attention_impl``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _sink_arr(sink, H: int) -> jax.Array:
    """[1, H] f32 sink logits for the kernels; the no-sink sentinel is
    NEG_INF — exp(sink - m) == 0 exactly, bit-identical to no sink."""
    if sink is None:
        return jnp.full((1, H), NEG_INF, jnp.float32)
    return sink.astype(jnp.float32).reshape(1, H)


def _page_dmas(pt_ref, b, chunk_idx, buf, k_hbm, v_hbm, k_scr, v_scr, sems, C,
               layer=None):
    """The 2C async copies bringing chunk `chunk_idx`'s pages into buffer
    `buf`. Returned (not started) so callers can .start() or .wait().
    With `layer`, the HBM refs are the whole pool [L, P, ...] and page
    `pid` of that layer is fetched."""
    copies = []
    for i in range(C):
        pid = pt_ref[b, chunk_idx * C + i]
        at = (pid,) if layer is None else (layer, pid)
        copies.append(
            pltpu.make_async_copy(k_hbm.at[at], k_scr.at[buf, i], sems.at[buf, 0, i])
        )
        copies.append(
            pltpu.make_async_copy(v_hbm.at[at], v_scr.at[buf, i], sems.at[buf, 1, i])
        )
    return copies


# --------------------------------------------------------------------------- #
# decode: one query token per sequence over its page table
# --------------------------------------------------------------------------- #


def _decode_kernel(
    # scalar prefetch
    pt_ref,  # [B, padded_pages] int32 page table
    len_ref,  # [B] int32 sequence lengths (incl. the new token)
    win_ref,  # [1] int32 sliding window (0 = full attention)
    # inputs
    q_ref,  # [1, H, hd] VMEM — this sequence's query (pre-scaled)
    sink_ref,  # [1, H] f32 — per-head sink logits (NEG_INF = no sink)
    k_hbm,  # [P, page, n_kv*hd] HBM
    v_hbm,
    # outputs
    o_ref,  # [1, H, hd] VMEM
    # scratch
    k_scr,  # [2, C, page, n_kv*hd] VMEM — double-buffered chunk
    v_scr,
    m_scr,  # [H, 128] f32 — running max (lane-replicated scalar per head)
    l_scr,  # [H, 128] f32 — running denominator
    acc_scr,  # [H, hd] f32 — running numerator
    sems,  # DMA sems [2 buf, 2 kv, C]
    *,
    C: int,
    page: int,
    n_kv: int,
    groups: int,
    hd: int,
    nc: int,
):
    b = pl.program_id(0)
    c = pl.program_id(1)
    T = C * page
    seq_len = len_ref[b]
    window = win_ref[0]
    # sliding window: chunks entirely before seq_len - window hold no
    # attended keys — remap the grid to start at the first relevant
    # chunk, so streamed bandwidth AND compute scale with the window,
    # not the full context
    first = jnp.where(
        window > 0, jnp.maximum(seq_len - window, 0) // T, 0
    )
    ch = c + first
    chunk_start = ch * T

    def dmas(chunk_idx, buf):
        return _page_dmas(
            pt_ref, b, chunk_idx, buf, k_hbm, v_hbm, k_scr, v_scr, sems, C
        )

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        for cp in dmas(first, 0):
            cp.start()

    @pl.when(chunk_start < seq_len)
    def _():
        buf = jax.lax.rem(c, 2)

        # overlap: start the next chunk's DMAs before waiting on this one
        @pl.when((c + 1 < nc) & ((ch + 1) * T < seq_len))
        def _():
            for cp in dmas(ch + 1, 1 - buf):
                cp.start()

        for cp in dmas(ch, buf):
            cp.wait()

        q = q_ref[0]  # [H, hd]
        k = k_scr[buf].reshape(T, n_kv * hd)
        v = v_scr[buf].reshape(T, n_kv * hd)
        tpos = chunk_start + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = tpos < seq_len  # [1, T]
        valid &= (window <= 0) | (tpos >= seq_len - window)

        for kh in range(n_kv):
            hs = slice(kh * groups, (kh + 1) * groups)
            ds = slice(kh * hd, (kh + 1) * hd)
            s = jax.lax.dot_general(
                q[hs, :], k[:, ds],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [g, T]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[hs, :1]  # [g, 1]
            l_prev = l_scr[hs, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # [g, T]
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v[:, ds],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [g, hd]
            acc_scr[hs, :] = acc_scr[hs, :] * corr + pv
            m_scr[hs, :] = jnp.broadcast_to(m_new, (groups, m_scr.shape[1]))
            l_scr[hs, :] = jnp.broadcast_to(l_new, (groups, l_scr.shape[1]))

    @pl.when(c == nc - 1)
    def _():
        # attention sinks (GPT-OSS): a virtual no-value key whose logit
        # joins the denominator — exactly exp(sink - m) under the online
        # softmax's running max (NEG_INF sink → plain softmax)
        sink = sink_ref[0, :].reshape(-1, 1)  # [H, 1]
        l_fin = l_scr[:, :1] + jnp.exp(sink - m_scr[:, :1])
        denom = jnp.maximum(l_fin, 1e-30)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,  # [B, H, hd]
    k_pages: jax.Array,  # [P, page, n_kv, hd]
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, max_pages] int32
    seq_lens: jax.Array,  # [B] int32 (incl. the new token)
    *,
    window=None,  # scalar int; None/<=0 → full attention
    sink=None,  # [H] per-head sink logits; None → plain softmax
    interpret: bool = False,
) -> jax.Array:
    """Flash paged-attention decode step. Returns [B, H, hd]."""
    B, H, hd = q.shape
    P, page, n_kv, _ = k_pages.shape
    groups = H // n_kv
    # ~128 tokens per streamed chunk keeps the score matmul MXU-sized
    C = max(1, 128 // page)
    maxp = page_table.shape[1]
    padded = -(-maxp // C) * C
    if padded != maxp:
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - maxp)))
    nc = padded // C

    scale = 1.0 / math.sqrt(hd)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    k_r = k_pages.reshape(P, page, n_kv * hd)
    v_r = v_pages.reshape(P, page, n_kv * hd)
    win = jnp.full((1,), 0 if window is None else window, jnp.int32)
    sink_arr = _sink_arr(sink, H)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, c, *_: (b, 0, 0)),
            pl.BlockSpec((1, H), lambda b, c, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, c, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, C, page, n_kv * hd), k_pages.dtype),
            pltpu.VMEM((2, C, page, n_kv * hd), v_pages.dtype),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        C=C, page=page, n_kv=n_kv, groups=groups, hd=hd, nc=nc,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(page_table, seq_lens.astype(jnp.int32), win, qs, sink_arr, k_r, v_r)


# --------------------------------------------------------------------------- #
# prefill: a new chunk attends to cached prefix pages + itself (causal)
# --------------------------------------------------------------------------- #


# query rows per unrolled head tile in the prefill kernel (a chunk that is
# not a multiple of it — the short buckets — runs as one block)
_PREFILL_ROW_BLOCK = 128

# prefix tokens streamed per grid step of the prefill kernel: the key tile
# every unrolled head works on.  Whole `prefill_step` programs on the chip
# (PERF.md, PR 34): 256 takes a third off the kernel's time over the prefix
# against 128 (one read-modify-write of a head's accumulator per 256 keys),
# 512 no more.
_PREFILL_STREAM_TOKENS = 256

# What one grid step of the prefill kernel may keep in VMEM: the compiler's
# scoped limit on a v5e.  `prefill_resident_bytes` counts the pipeline's
# second buffer of EVERY blocked operand, so it is an upper bound: the
# compiler's own figure (read from forced failures) is the scratch alone
# where XLA has put q, o and the chunk's K and V into VMEM itself (batch
# 1), and the scratch plus both buffers of q, kn and vn at batch 2, where
# the chunk whole (17-18 MiB) is refused on the chip and two blocks pass.
_PREFILL_VMEM_BUDGET = 16 * 1024 * 1024


def _prefill_pages_per_step(page: int) -> int:
    return max(1, _PREFILL_STREAM_TOKENS // page)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes an array takes in VMEM, as the compiler's scoped figures
    show them: lanes padded to 128, rows to what shares a 32-bit sublane
    (two bf16 rows; a [page, 4, 128] bf16 page is not padded to a tile)."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = max(1, 4 // itemsize)
    *lead, rows, lanes = shape
    return (math.prod(lead) * -(-rows // sub) * sub * -(-lanes // 128) * 128
            * itemsize)


def _prefill_residents(QB: int, S: int, H: int, n_kv: int, hd: int,
                       page: int, dtype, pool_dtype=None):
    """(blocked, scratch): shape and dtype of everything one grid step of
    the prefill kernel holds in VMEM at a query block of QB rows, in the
    order `prefill_attention_pallas` hands them to `pallas_call`.  Only q,
    o, m / l and the accumulator follow QB; the chunk's own K and V stay
    whole and the page buffers follow the page (and the pool's dtype, where
    that is not the chunk's)."""
    C = _prefill_pages_per_step(page)
    f32 = jnp.float32
    pool_dtype = pool_dtype or dtype
    as_stored = hd % 128 == 0
    page_shape = (page, n_kv, hd) if as_stored else (page, n_kv * hd)
    blocked = [
        ((1, QB, H * hd), dtype),  # q
        ((1, H), f32),  # sink
        ((1, S, n_kv * hd), dtype),  # kn
        ((1, S, n_kv * hd), dtype),  # vn
        ((1, QB, H * hd), dtype),  # o
    ]
    scratch = [
        ((2, C, *page_shape), pool_dtype),  # k_scr
        ((2, C, *page_shape), pool_dtype),  # v_scr
        *([((C * page, n_kv * hd), pool_dtype)] * 2 if as_stored else []),
        ((QB, H), f32),  # m_scr
        ((QB, H), f32),  # l_scr
        ((QB, H * hd), f32),  # acc_scr
    ]
    return blocked, scratch


def prefill_resident_bytes(QB: int, *geom) -> int:
    """VMEM bytes of `_prefill_residents(QB, *geom)`: every blocked operand
    twice (the pipeline fetches the next block while this one is computed
    on)."""
    blocked, scratch = _prefill_residents(QB, *geom)
    return (2 * sum(_vmem_bytes(*a) for a in blocked)
            + sum(_vmem_bytes(*a) for a in scratch))


def prefill_query_block(S: int, H: int, n_kv: int, hd: int, page: int,
                        dtype, pool_dtype=None):
    """Query rows per grid step of the prefill kernel for a chunk of S
    tokens: the largest multiple of the row block that divides S and whose
    residents fit the budget; a chunk that is no multiple of the row block
    (the short buckets) has itself as its only candidate.  None where
    nothing fits: the caller keeps that shape off the kernel."""
    RB = _PREFILL_ROW_BLOCK
    blocks = [S]
    if S % RB == 0:
        blocks = [q for q in range(S, 0, -RB) if S % q == 0]
    for QB in blocks:
        if prefill_resident_bytes(QB, S, H, n_kv, hd, page, dtype,
                                  pool_dtype) <= _PREFILL_VMEM_BUDGET:
            return QB
    return None


def _prefill_kernel(
    # scalar prefetch
    pt_ref,  # [B, padded_pages] int32
    pre_ref,  # [B] int32 prefix lengths (tokens already in cache)
    cl_ref,  # [B] int32 chunk lengths (valid tokens in the new chunk)
    win_ref,  # [1] int32 sliding window (0 = full attention)
    layer_ref,  # [1] int32 — which layer's pages of the pool to read
    # inputs (heads flattened onto lanes)
    q_ref,  # [1, QB, H*hd] VMEM (pre-scaled) — this grid row's query block
    sink_ref,  # [1, H] f32 — per-head sink logits (NEG_INF = no sink)
    kn_ref,  # [1, S, n_kv*hd] VMEM — the chunk's own K, whole
    vn_ref,
    k_hbm,  # [L, P, page, n_kv, hd] HBM — the whole pool, as it is stored
    v_hbm,  # (not `as_stored`: one layer's [1, P, page, n_kv*hd])
    # outputs
    o_ref,  # [1, QB, H*hd]
    # scratch
    k_scr,  # [2, C, *page shape] — double-buffered pages
    v_scr,
    *scratch,  # as_stored: kf_scr, vf_scr [T, n_kv*hd] — the current chunk
    # with heads side by side; then always:
    # m_scr [QB, H] f32 — running max per (query row, head), l_scr [QB, H]
    # f32, acc_scr [QB, H*hd] f32, sems
    as_stored: bool,
    C: int,
    page: int,
    n_kv: int,
    groups: int,
    hd: int,
    nc: int,
    QB: int,
    RB: int,
):
    m_scr, l_scr, acc_scr, sems = scratch[-4:]
    b = pl.program_id(0)
    q0 = pl.program_id(1) * QB  # this query block's first row of the chunk
    c = pl.program_id(2)
    T = C * page
    prefix_len = pre_ref[b]
    chunk_len = cl_ref[b]
    window = win_ref[0]
    # sliding window: the block's earliest query row (global position
    # prefix_len + q0) attends keys > prefix_len + q0 - window, so prefix
    # chunks wholly before that are skipped — stream and compute scale
    # with the window
    first = jnp.where(
        window > 0,
        jnp.maximum(prefix_len + q0 - window + 1, 0) // T,
        0,
    )
    ch = c + first
    chunk_start = ch * T

    def dmas(chunk_idx, buf):
        return _page_dmas(
            pt_ref, b, chunk_idx, buf, k_hbm, v_hbm, k_scr, v_scr, sems, C,
            layer=layer_ref[0],
        )

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        # guard on the FIRST COMPUTE CHUNK being real, not just on having
        # a prefix: with a tiny window first*T can reach prefix_len (no
        # prefix chunk attended at all) and a started-but-never-awaited
        # DMA would leak its semaphore signals into the next grid row
        @pl.when(first * T < prefix_len)
        def _():
            for cp in dmas(first, 0):
                cp.start()

    # The head loop below is unrolled in Python (heads live on lanes, and
    # Mosaic takes no dynamic lane slice), so what bounds the kernel's
    # code size — and its compile time — is the tile each unrolled head
    # works on.  Query rows therefore go through a `fori_loop` in blocks
    # of RB: every head touches [RB, T] / [RB, RB] score tiles whatever
    # the chunk length S, and the program no longer grows with S.  Rows
    # `rs` are the block's own (0..QB); masks use the row's place in the
    # chunk, q0 + row.
    nrb = QB // RB

    def attend_rows(rs, k, v, valid):
        """Online-softmax update of rows `rs` (all heads) against one key
        tile.  k, v: [Tk, n_kv*hd]; valid: [RB, Tk] bool."""
        for kh in range(n_kv):
            ds = slice(kh * hd, (kh + 1) * hd)
            k_h, v_h = k[:, ds], v[:, ds]
            for g in range(groups):
                h = kh * groups + g
                hl = slice(h * hd, (h + 1) * hd)
                s = jax.lax.dot_general(
                    q_ref[0, rs, hl], k_h,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [RB, Tk]
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_scr[rs, h:h + 1]  # [RB, 1]
                l_prev = l_scr[rs, h:h + 1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                pv = jax.lax.dot_general(
                    p.astype(v_h.dtype), v_h,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [RB, hd]
                acc_scr[rs, hl] = acc_scr[rs, hl] * corr + pv
                m_scr[rs, h:h + 1] = m_new
                l_scr[rs, h:h + 1] = (
                    l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
                )

    # ---- streamed prefix pages ---- #
    @pl.when(chunk_start < prefix_len)
    def _():
        buf = jax.lax.rem(c, 2)

        @pl.when((c + 1 < nc) & ((ch + 1) * T < prefix_len))
        def _():
            for cp in dmas(ch + 1, 1 - buf):
                cp.start()

        for cp in dmas(ch, buf):
            cp.wait()

        if as_stored:
            # a page arrives as it is stored, [page, n_kv, hd]: one
            # [n_kv, hd] per token.  Put the heads side by side on lanes
            # ONCE per streamed chunk (not per row block and head), in the
            # form the head loop slices; the pool itself is never
            # re-laid-out in HBM.
            kf_scr, vf_scr = scratch[:2]
            for kh in range(n_kv):
                ds = slice(kh * hd, (kh + 1) * hd)
                kf_scr[:, ds] = k_scr[buf, :, :, kh, :].reshape(T, hd)
                vf_scr[:, ds] = v_scr[buf, :, :, kh, :].reshape(T, hd)

        def tiles():  # this chunk's K and V, [T, n_kv*hd] each
            if as_stored:
                return kf_scr[...], vf_scr[...]
            return (k_scr[buf].reshape(T, n_kv * hd),
                    v_scr[buf].reshape(T, n_kv * hd))

        def row_block(r, carry):
            r0 = pl.multiple_of(r * RB, RB)
            # per-row mask: key position validity + sliding window around
            # the row's global query position (prefix_len + row)
            rows = q0 + r0 + jax.lax.broadcasted_iota(jnp.int32, (RB, T), 0)
            tpos = chunk_start + jax.lax.broadcasted_iota(
                jnp.int32, (RB, T), 1)
            valid = tpos < prefix_len
            valid &= (window <= 0) | (tpos > prefix_len + rows - window)
            attend_rows(pl.ds(r0, RB), *tiles(), valid)
            return carry

        jax.lax.fori_loop(0, nrb, row_block, 0)

    # ---- the chunk itself (causal), then finalize ---- #
    @pl.when(c == nc - 1)
    def _():
        def row_block(r, carry):
            r0 = pl.multiple_of(r * RB, RB)
            rs = pl.ds(r0, RB)

            def key_block(j, carry2):
                j0 = pl.multiple_of(j * RB, RB)
                i = q0 + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (RB, RB), 0)
                jj = j0 + jax.lax.broadcasted_iota(jnp.int32, (RB, RB), 1)
                causal = (jj <= i) & (jj < chunk_len)
                causal &= (window <= 0) | (jj > i - window)
                js = pl.ds(j0, RB)
                attend_rows(rs, kn_ref[0, js, :], vn_ref[0, js, :], causal)
                return carry2

            # causal: key blocks past the row block's diagonal hold nothing
            jax.lax.fori_loop(0, q0 // RB + r + 1, key_block, 0)

            for h in range(n_kv * groups):
                hl = slice(h * hd, (h + 1) * hd)
                # attention sink: one extra denominator term per row
                # (NEG_INF sink → exp == 0 → plain softmax)
                l_fin = l_scr[rs, h:h + 1] + jnp.exp(
                    sink_ref[0, h] - m_scr[rs, h:h + 1])
                denom = jnp.maximum(l_fin, 1e-30)
                o_ref[0, rs, hl] = (acc_scr[rs, hl] / denom).astype(
                    o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, nrb, row_block, 0)


def prefill_attention_pallas(
    q: jax.Array,  # [B, S, H, hd]
    k_new: jax.Array,  # [B, S, n_kv, hd]
    v_new: jax.Array,
    k_pages: jax.Array,  # [P, page, n_kv, hd], or [L, P, ...] with `layer`
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    prefix_lens: jax.Array,  # [B]
    chunk_lens: jax.Array,  # [B]
    *,
    window=None,  # scalar int; None/<=0 → full attention
    sink=None,  # [H] per-head sink logits; None → plain softmax
    layer=None,  # scalar layer index (traced OK) into a whole pool
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill flash attention: streamed prefix pages + causal self
    block. Returns [B, S, H, hd]."""
    B, S, H, hd = q.shape
    if layer is None:  # one layer's pool: a pool of one layer
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    _, P, page, n_kv, _ = k_pages.shape
    # A page can be fetched as it is stored only if a head fills whole
    # lanes: HBM pads the minor dimension to 128, and a DMA takes no part
    # of a tile.  Narrower heads (hd 64) keep ONE slice + relayout of the
    # layer's slab per layer, read-only (the stored layout: ROADMAP D3).
    as_stored = hd % 128 == 0
    if not as_stored:
        k_pages = k_pages[layer].reshape(1, P, page, n_kv * hd)
        v_pages = v_pages[layer].reshape(1, P, page, n_kv * hd)
        layer = 0
    groups = H // n_kv
    C = _prefill_pages_per_step(page)
    maxp = page_table.shape[1]
    padded = -(-maxp // C) * C
    if padded != maxp:
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - maxp)))
    nc = padded // C

    scale = 1.0 / math.sqrt(hd)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, S, H * hd)
    kn = k_new.reshape(B, S, n_kv * hd)
    vn = v_new.reshape(B, S, n_kv * hd)

    win = jnp.full((1,), 0 if window is None else window, jnp.int32)
    sink_arr = _sink_arr(sink, H)
    # the grid's middle axis walks the chunk in query blocks: q, o and the
    # softmax state are QB rows whatever S is; the prefix is streamed once
    # per query block, the chunk's own K and V once per row of the batch
    geom = (S, H, n_kv, hd, page, q.dtype, k_pages.dtype)
    QB = prefill_query_block(*geom)
    if QB is None:
        raise ValueError(
            f"no query block of a {S}-token chunk (H={H}, n_kv={n_kv}, "
            f"hd={hd}, page={page}) fits {_PREFILL_VMEM_BUDGET} B of VMEM")
    blocked, scratch = _prefill_residents(QB, *geom)
    q_blk, sink_blk, kn_blk, vn_blk, o_blk = (blk for blk, _ in blocked)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, S // QB, nc),
        in_specs=[
            pl.BlockSpec(q_blk, lambda b, i, c, *_: (b, i, 0)),
            pl.BlockSpec(sink_blk, lambda b, i, c, *_: (0, 0)),
            pl.BlockSpec(kn_blk, lambda b, i, c, *_: (b, 0, 0)),
            pl.BlockSpec(vn_blk, lambda b, i, c, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(o_blk, lambda b, i, c, *_: (b, i, 0)),
        scratch_shapes=[
            *(pltpu.VMEM(shape, dtype) for shape, dtype in scratch),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        C=C, page=page, n_kv=n_kv, groups=groups, hd=hd, nc=nc, QB=QB,
        RB=_PREFILL_ROW_BLOCK if S % _PREFILL_ROW_BLOCK == 0 else S,
        as_stored=as_stored,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * hd), q.dtype),
        interpret=interpret,
    )(
        page_table,
        prefix_lens.astype(jnp.int32),
        chunk_lens.astype(jnp.int32),
        win,
        jnp.asarray(layer, jnp.int32).reshape(1),
        qs, sink_arr, kn, vn, k_pages, v_pages,
    )
    return out.reshape(B, S, H, hd)
