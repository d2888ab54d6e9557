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
  ``paged_attention.write_kv_layers``): in HBM one token's ``[n_kv, hd]``
  is its own tile, so ``reshape(P, page, n_kv*hd)`` outside the kernel is a
  relayout copy of the whole slab (1.7 s of a 40 s window, PERF.md PR 26).
- The PREFILL kernel therefore takes the whole pool and a layer index,
  fetches page ``pid`` of layer ``l`` as it is stored (``k_hbm.at[l,
  pid]``, a page comes whole: Mosaic slices no single head out of a tile)
  and reads a KV head's ``[T, hd]`` out of the streamed tile with one
  strided load (``page_plane``).  Heads narrower than 128 lanes cannot be
  fetched that way (the minor dimension is padded in HBM) and keep one
  slice + relayout of the layer's slab.
- The DECODE kernel still gets one layer's slab in the ``[P, page,
  n_kv*hd]`` view from the scanned decode loop, heads side by side on
  lanes, a head a static lane slice.
- Prefill takes q and o with the heads on lanes the same way (``[S,
  H*hd]``, no relayout in HBM) and walks the chunk in QUERY BLOCKS on a
  grid axis.  Inside a block the ``groups = H / n_kv`` query heads that
  share a KV head are FOLDED into the rows of one query tile ``[M, hd]``,
  M = groups x QB, so a key tile costs ONE score product and ONE value
  product a KV head, and the traced body has ``n_kv`` of them, not ``H``.
  Scores are kept TRANSPOSED, ``[keys, M]``: keys on sublanes, folded rows
  on lanes.  A row's running max and sum are then one LANE each of a ``[1,
  M]`` array and a reduction ACROSS vregs (elementwise), where the
  row-major form reduces every vreg along its lanes and carries ``[M, 1]``
  columns that cost a vreg a row group (PERF.md, PR 50: that, not the
  MXU, was the parent's time); the accumulator is ``[hd, M]`` and is
  transposed back once a query block.  QB comes from
  ``prefill_query_block``, the one function that also sizes the scratch.

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


def _page_dmas(pt_ref, b, chunk_idx, buf, k_hbm, v_hbm, k_scr, v_scr, sems, C):
    """The 2C async copies bringing chunk `chunk_idx`'s pages of one
    layer's slab into buffer `buf` (the decode kernel's). Returned (not
    started) so callers can .start() or .wait()."""
    copies = []
    for i in range(C):
        pid = pt_ref[b, chunk_idx * C + i]
        copies.append(
            pltpu.make_async_copy(k_hbm.at[pid], k_scr.at[buf, i], sems.at[buf, 0, i])
        )
        copies.append(
            pltpu.make_async_copy(v_hbm.at[pid], v_scr.at[buf, i], sems.at[buf, 1, i])
        )
    return copies


def page_plane(scr, buf, j: int, T: int):
    """Lane tile j of the T tokens in buffer `buf` of a page scratch
    [2, C, page, tiles, 128], as [T, 128] rows.  A token's tiles are the
    SECOND-MINOR axis of a page, so in VMEM (as in HBM) a 32-bit row holds
    one lane tile of a float32 pool, or two of a bf16 pool, packed low half
    first, and tile j of all T tokens is ONE sublane-strided load of the
    scratch viewed as 32-bit rows (`ref.bitcast`, stride = 32-bit rows a
    token) and a shift: bf16 is the high half of its float32.  Indexing the
    tile axis (`scr[buf, :, :, j, :]`) is the same data gathered a row at a
    time (37% of the latent kernel's first version, PERF.md, PR 43; 15% of
    the per-head kernel's time under 6,144 tokens of prefix, PR 50)."""
    pack = 4 // scr.dtype.itemsize  # lane tiles a 32-bit row
    words = scr.shape[3] // pack  # 32-bit rows a token
    u = scr if pack == 1 else scr.bitcast(jnp.uint32)
    u = u.reshape(2 * T * words, 128)
    start = pl.multiple_of(buf * (T * words), T * words) + j // pack
    x = (u[pl.ds(start, T), :] if words == 1
         else u[pl.ds(start, T, stride=words), :])
    if pack == 1:
        return x
    x = x << 16 if j % 2 == 0 else x & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(x, jnp.float32).astype(scr.dtype)


def page_planes_readable(tiles: int, lanes: int, pool_dtype) -> bool:
    """Whether `page_plane` can read a pool whose token is [tiles, lanes]:
    whole 32-bit rows of one or two lane tiles."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    return (lanes == 128 and itemsize in (2, 4)
            and tiles % (4 // itemsize) == 0)


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


# Tile sizes, from one layer's kernel timed alone on a v5e (PERF.md, PR 50,
# has the tables; "us" below: a 512-token chunk at 28 query heads over 4 KV
# heads of 128 under 0 / 1,536 / 6,144 tokens of prefix, where the parent's
# row-major kernel with a Python loop over the heads took 177 / 421 / 1,157).

# A QUERY BLOCK (the tokens of the chunk one grid step works on) is a
# multiple of this many tokens that divides the chunk; a chunk that is no
# multiple of it (the short buckets) runs as one block.  (The parent's
# loop over 128-token row tiles inside a block is gone: a block's folded
# rows are the lanes of ONE product.)
_PREFILL_ROW_BLOCK = 128

# Folded query rows (a query block's tokens x the query heads of one KV
# head) one score product may span: `prefill_query_block` takes the
# largest block within it.  Wider is faster as far as it was tried: 512
# rows a product (one head of a 512-token block a time, a rolled loop over
# the heads) 148 / 310 / 812 us, 1,792 (256 tokens x 7 heads, two query
# blocks) 117 / 255 / 677, 3,584 (the chunk whole) 136 / 238 / 589; at 32
# heads over 2, under 1,536: 325, 299 (2,048), 286 (4,096).  Only the
# prefix-free chunk likes two blocks better (the second key tile of its
# own triangle is then scored for half the rows).
_PREFILL_QUERY_ROWS = 4096

# prefix tokens streamed per inner step of the prefill kernel (whole
# pages): the key tile every KV head's product works on, and how often a
# KV head's accumulator is read and written.  512 against 256 at 3,584
# rows: 160 / 271 / 615 us against 136 / 238 / 589 (a 512 x 3,584 float32
# score tile is 7 MB of VMEM traffic an elementwise pass).
_PREFILL_STREAM_TOKENS = 256

# keys of the chunk itself per inner step (a chunk that is no multiple of
# it is one tile): 512 against 256, at 512 rows a product and 512 prefix
# keys a step, 126 / 296 / 741 against 148 / 278 / 751 us: within the
# noise between two compiles.
_PREFILL_SELF_TOKENS = 256

# What one grid step of the prefill kernel may keep in VMEM
# (`prefill_resident_bytes`, an upper bound: both pipeline buffers of every
# blocked operand, the scratch, and the float32 score tile with its
# exponentials: 40 MB for a whole 512-token block of 28 heads over 4), and
# the scoped limit the compiler is given (its default, 16 MiB, was the
# parent's budget and refuses that block; a v5e core has 128 MiB).
_PREFILL_VMEM_BUDGET = 56 * 1024 * 1024
_PREFILL_VMEM_LIMIT = 96 * 1024 * 1024


def _prefill_pages_per_step(page: int) -> int:
    return max(1, _PREFILL_STREAM_TOKENS // page)


def _prefill_self_tile(S: int) -> int:
    return _PREFILL_SELF_TOKENS if S % _PREFILL_SELF_TOKENS == 0 else S


def _prefill_folded_rows(QB: int, groups: int) -> int:
    """Rows of one KV head's folded query tile: its `groups` query heads'
    QB tokens one under the other, up to whole lane tiles (the rows are
    the LANES of the scores; rows past the last head are scored and never
    read)."""
    return -(-groups * QB // 128) * 128


def _vmem_bytes(shape, dtype) -> int:
    """Bytes an array takes in VMEM, as the compiler's scoped figures
    show them: lanes padded to 128, rows to what shares a 32-bit sublane
    (two bf16 rows; a [page, 4, 128] bf16 page is not padded to a tile)."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = max(1, 4 // itemsize)
    *lead, rows, lanes = shape
    return (math.prod(lead) * -(-rows // sub) * sub * -(-lanes // 128) * 128
            * itemsize)


def packed_plane(n_kv: int, hd: int):
    """[tiles, 128]: a token's `n_kv` heads of `hd` values side by side as
    whole lane tiles, several heads a tile, where a head is narrower than a
    tile and a token fills whole ones (8 heads of 64: [4, 128], head j the
    lanes 64 (j % 2).. of tile j // 2).  The same values in the same order
    as [n_kv, hd].  A pool stored so is read by the kernel as it is stored
    (`page_plane`); stored [.., n_kv, 64] the compiler pads every head to a
    tile, twice the bytes, and copies the whole pool into that form a step
    (AOT compile for a v5e, PR 55: a 2.0 GB plane held 4.0 GB again)."""
    return (n_kv * hd // 128, 128)


def _prefill_residents(QB: int, S: int, H: int, n_kv: int, hd: int,
                       page: int, dtype, pool_dtype=None,
                       packed: bool = False):
    """(blocked, scratch, temps): shape and dtype of everything one grid
    step of the prefill kernel holds in VMEM at a query block of QB tokens:
    the blocked operands and the scratch in the order
    `prefill_attention_pallas` hands them to `pallas_call`, and the values
    of one product the compiler keeps there (scores, their exponentials,
    the cast probabilities).  q, o and the folded queries, softmax state
    and accumulator follow QB; the chunk's own K and V stay whole and the
    page buffers follow the page (and the pool's dtype, where that is not
    the chunk's).  `packed`: the pool's token is `packed_plane`."""
    C = _prefill_pages_per_step(page)
    f32 = jnp.float32
    pool_dtype = pool_dtype or dtype
    as_stored = hd % 128 == 0
    page_shape = ((page, *packed_plane(n_kv, hd)) if packed else
                  (page, n_kv, hd) if as_stored else (page, n_kv * hd))
    M = _prefill_folded_rows(QB, H // n_kv)
    blocked = [
        ((1, QB, H * hd), dtype),  # q
        ((n_kv, 1, M), f32),  # sink, a folded row's own
        ((1, S, n_kv * hd), dtype),  # kn
        ((1, S, n_kv * hd), dtype),  # vn
        ((1, QB, H * hd), dtype),  # o
    ]
    scratch = [
        ((2, C, *page_shape), pool_dtype),  # k_scr
        ((2, C, *page_shape), pool_dtype),  # v_scr
        ((n_kv, M, hd), dtype),  # qf_scr
        ((n_kv, 1, M), f32),  # m_scr
        ((n_kv, 1, M), f32),  # l_scr
        ((n_kv, hd, M), f32),  # acc_scr
    ]
    temps = [((max(C * page, _prefill_self_tile(S)), M), f32)] * 3
    return blocked, scratch, temps


def prefill_resident_bytes(QB: int, *geom) -> int:
    """VMEM bytes of `_prefill_residents(QB, *geom)`: every blocked operand
    twice (the pipeline fetches the next block while this one is computed
    on)."""
    blocked, scratch, temps = _prefill_residents(QB, *geom)
    return (2 * sum(_vmem_bytes(*a) for a in blocked)
            + sum(_vmem_bytes(*a) for a in scratch + temps))


def prefill_query_block(S: int, H: int, n_kv: int, hd: int, page: int,
                        dtype, pool_dtype=None, packed: bool = False):
    """Query tokens per grid step of the prefill kernel for a chunk of S
    tokens: the largest multiple of the row block that divides S, folds to
    no more than `_PREFILL_QUERY_ROWS` rows (the smallest candidate may)
    and whose residents fit the budget; a chunk that is no multiple of the
    row block (the short buckets) has itself as its only candidate.  None
    where nothing fits: the caller keeps that shape off the kernel."""
    RB = _PREFILL_ROW_BLOCK
    blocks = [S]
    if S % RB == 0:
        blocks = [q for q in range(S, 0, -RB) if S % q == 0]
    for QB in blocks:
        if QB != blocks[-1] and (_prefill_folded_rows(QB, H // n_kv)
                                 > _PREFILL_QUERY_ROWS):
            continue
        if prefill_resident_bytes(QB, S, H, n_kv, hd, page, dtype,
                                  pool_dtype, packed) <= _PREFILL_VMEM_BUDGET:
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
    sink_ref,  # [n_kv, 1, M] f32 — each folded row's head's sink logit
    # (NEG_INF = no sink)
    kn_ref,  # [1, S, n_kv*hd] VMEM — the chunk's own K, whole
    vn_ref,
    k_hbm,  # [L, P, page, n_kv, hd] HBM — the whole pool, as it is stored
    v_hbm,  # (not `as_stored`: one layer's [1, P, page, n_kv*hd])
    # outputs
    o_ref,  # [1, QB, H*hd]
    # scratch
    k_scr,  # [2, C, *page shape] — double-buffered pages
    v_scr,
    qf_scr,  # [n_kv, M, hd] — the block's queries FOLDED: the `groups`
    # query heads of a KV head one under the other, QB rows each
    m_scr,  # [n_kv, 1, M] f32 — running max per folded row, on lanes
    l_scr,  # [n_kv, 1, M] f32 — running sum
    acc_scr,  # [n_kv, hd, M] f32 — the accumulator, transposed
    sems,  # DMA [2 buffers, 2 pools, C]
    *,
    as_stored: bool,
    planes: bool,
    tile_heads: int,  # KV heads a lane tile of the pool (`packed_plane`)
    C: int,
    page: int,
    n_kv: int,
    groups: int,
    hd: int,
    nc: int,
    QB: int,
    TS: int,
):
    b = pl.program_id(0)
    q0 = pl.program_id(1) * QB  # this query block's first row of the chunk
    T = C * page
    M = qf_scr.shape[1]
    prefix_len = pre_ref[b]
    chunk_len = cl_ref[b]
    window = win_ref[0]
    layer = layer_ref[0]

    def attend(kh, k_h, v_h, valid):
        """Online-softmax update of KV head `kh`'s M folded rows against
        one key tile, k_h and v_h [Tk, hd]: ONE score product and ONE value
        product for all its query heads.  Scores are kept TRANSPOSED, keys
        on sublanes and folded rows on lanes ([Tk, M]; valid broadcasts to
        it): a row's max and sum are then reductions ACROSS vregs, its
        state one lane, and nothing is reduced or broadcast along lanes."""
        s = jax.lax.dot_general(k_h, qf_scr[kh], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)  # [Tk, M]
        m_prev = m_scr[kh]  # [1, M]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        pv = jax.lax.dot_general(v_h.T, p.astype(v_h.dtype),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[kh] = acc_scr[kh] * corr + pv  # [hd, M]
        l_scr[kh] = l_scr[kh] * corr + jnp.sum(p, axis=0, keepdims=True)
        m_scr[kh] = m_new

    def pages(tile, buf, wait):
        """Start (or await) the 2C async copies that bring key tile `tile`'s
        pages into buffer `buf`: a loop in the kernel, so the copies of a
        tile are one traced body whatever C is."""
        def one(i, carry):
            pid = pt_ref[b, tile * C + i]
            for pool, (hbm, scr) in enumerate(((k_hbm, k_scr),
                                               (v_hbm, v_scr))):
                copy = pltpu.make_async_copy(
                    hbm.at[layer, pid], scr.at[buf, i], sems.at[buf, pool, i])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, C, one, 0)

    def head_rows(scr, buf, kh):
        """KV head `kh`'s [T, hd] of the tile in buffer `buf`.  A page
        arrives as it is stored, [page, n_kv, hd], one [n_kv, hd] per
        token; the pool itself is never re-laid-out in HBM.  (Narrow heads:
        the slab view has the heads side by side on lanes.)"""
        if planes and tile_heads > 1:  # several heads a lane tile
            at = kh % tile_heads * hd
            return page_plane(scr, buf, kh // tile_heads, T)[:, at:at + hd]
        if planes:  # a head is one lane tile: one strided load
            return page_plane(scr, buf, kh, T)
        if as_stored:
            return scr[buf, :, :, kh, :].reshape(T, hd)
        return scr[buf].reshape(T, n_kv * hd)[:, kh * hd:(kh + 1) * hd]

    # a query block wholly past the chunk's length is padding (a short row
    # of a shared step, a pad row): no key is fetched or scored for it
    @pl.when(q0 >= chunk_len)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(q0 < chunk_len)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # fold: heads live on lanes in q (a head is a static lane slice), a
        # KV head's query heads go one under the other, once a query block
        for h in range(n_kv * groups):
            kh, g = divmod(h, groups)
            qf_scr[kh, g * QB:(g + 1) * QB, :] = (
                q_ref[0, :, h * hd:(h + 1) * hd])
        if M > groups * QB:  # rows past the last head: scored, never read
            qf_scr[:, groups * QB:, :] = jnp.zeros(
                (n_kv, M - groups * QB, hd), qf_scr.dtype)
        # a folded row's place in the chunk: rows are (head of the group,
        # token), so the token is the row modulo a head's rows
        row = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
        tok = q0 + (row & (QB - 1) if QB & (QB - 1) == 0
                    else jax.lax.rem(row, jnp.int32(QB)))  # [1, M]

        # ---- the prefix: pages streamed in place, below prefix_len ---- #
        # sliding window: the block's earliest query row (global position
        # prefix_len + q0) attends keys > prefix_len + q0 - window, so
        # prefix tiles wholly before that are skipped — stream and compute
        # scale with the window
        first = jnp.where(
            window > 0,
            jnp.maximum(prefix_len + q0 - window + 1, 0) // T,
            0,
        )
        n_tiles = jnp.maximum(
            jnp.minimum((prefix_len + T - 1) // T, nc) - first, 0)

        @pl.when(n_tiles > 0)
        def _():
            pages(first, 0, wait=False)

        def prefix_tile(i, carry):
            c = first + i
            buf = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                pages(c + 1, 1 - buf, wait=False)

            pages(c, buf, wait=True)
            # per-row mask: the key is in the prefix, and inside the
            # sliding window around the row's global position
            tpos = c * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
            valid = tpos < prefix_len
            valid &= (window <= 0) | (tpos > prefix_len + tok - window)
            for kh in range(n_kv):
                attend(kh, head_rows(k_scr, buf, kh),
                       head_rows(v_scr, buf, kh), valid)
            return carry

        jax.lax.fori_loop(0, n_tiles, prefix_tile, 0)

        # ---- the chunk itself (causal): tiles up to the diagonal ---- #
        n_self = jnp.minimum((q0 + QB + TS - 1) // TS,
                             (chunk_len + TS - 1) // TS)

        def self_tile(j, carry):
            j0 = pl.multiple_of(j * TS, TS)
            kpos = j0 + jax.lax.broadcasted_iota(jnp.int32, (TS, 1), 0)
            valid = (kpos <= tok) & (kpos < chunk_len)
            valid &= (window <= 0) | (kpos > tok - window)
            for kh in range(n_kv):
                ds = slice(kh * hd, (kh + 1) * hd)
                attend(kh, kn_ref[0, pl.ds(j0, TS), ds],
                       vn_ref[0, pl.ds(j0, TS), ds], valid)
            return carry

        jax.lax.fori_loop(0, n_self, self_tile, 0)

        # finish in the folded form, then unfold: a head's rows go back to
        # its lanes of o
        for kh in range(n_kv):
            # attention sink: one extra denominator term per row
            # (NEG_INF sink → exp == 0 → plain softmax)
            l_fin = l_scr[kh] + jnp.exp(sink_ref[kh] - m_scr[kh])
            out = acc_scr[kh] / jnp.maximum(l_fin, 1e-30)  # [hd, M]
            tiles = {}  # lane tile -> its [128, hd] transpose

            def head_rows_out(g):
                """[QB, hd]: the rows of the group's head g."""
                lo = g * QB
                if QB % 128 == 0:
                    return out[:, lo:lo + QB].T
                t, off = divmod(lo, 128)  # a short bucket: part of a tile
                if t not in tiles:
                    tiles[t] = out[:, t * 128:(t + 1) * 128].T
                return tiles[t][off:off + QB]

            for g in range(groups):
                h = kh * groups + g
                o_ref[0, :, h * hd:(h + 1) * hd] = head_rows_out(g).astype(
                    o_ref.dtype)


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
    packed: bool = False,  # the pool's token is `packed_plane`
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill flash attention: streamed prefix pages + causal self
    block. Returns [B, S, H, hd]; rows at or past `chunk_lens` are padding
    and hold no meaning (as in the XLA form)."""
    B, S, H, hd = q.shape
    if layer is None:  # one layer's pool: a pool of one layer
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    _, P, page = k_pages.shape[:3]
    n_kv = k_new.shape[2]
    # A page can be fetched as it is stored only if a head fills whole
    # lanes: HBM pads the minor dimension to 128, and a DMA takes no part
    # of a tile.  Narrower heads (hd 64) are read as stored from a pool whose
    # token is whole lane tiles, several heads a tile (`packed`: the
    # family's `CacheSpec` says so); stored [.., n_kv, hd] they keep ONE
    # slice + relayout of the layer's slab per layer, read-only, which
    # grows with the POOL (ROADMAP D3).
    if packed and (k_pages.shape[3:] != packed_plane(n_kv, hd)
                   or not page_planes_readable(*k_pages.shape[3:],
                                               k_pages.dtype)):
        raise ValueError(
            f"a pool of {k_pages.shape[3:]} a token is not {n_kv} heads of "
            f"{hd} in lane tiles the kernel reads")
    as_stored = hd % 128 == 0 or packed
    planes = as_stored and page_planes_readable(*k_pages.shape[3:],
                                                k_pages.dtype)
    if not as_stored:
        k_pages = k_pages[layer].reshape(1, P, page, n_kv * hd)
        v_pages = v_pages[layer].reshape(1, P, page, n_kv * hd)
        layer = 0
    C = _prefill_pages_per_step(page)
    maxp = page_table.shape[1]
    padded = -(-maxp // C) * C
    if padded != maxp:
        page_table = jnp.pad(page_table, ((0, 0), (0, padded - maxp)))

    scale = 1.0 / math.sqrt(hd)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, S, H * hd)
    kn = k_new.reshape(B, S, n_kv * hd)
    vn = v_new.reshape(B, S, n_kv * hd)

    win = jnp.full((1,), 0 if window is None else window, jnp.int32)
    # the grid's second axis walks the chunk in query blocks: q, o and the
    # folded state are QB tokens whatever S is; the prefix is streamed once
    # per query block, the chunk's own K and V once per row of the batch
    geom = (S, H, n_kv, hd, page, q.dtype, k_pages.dtype, packed)
    QB = prefill_query_block(*geom)
    if QB is None:
        raise ValueError(
            f"no query block of a {S}-token chunk (H={H}, n_kv={n_kv}, "
            f"hd={hd}, page={page}) fits {_PREFILL_VMEM_BUDGET} B of VMEM")
    blocked, scratch, _ = _prefill_residents(QB, *geom)
    q_blk, sink_blk, kn_blk, vn_blk, o_blk = (blk for blk, _ in blocked)
    # a folded row's sink is its head's: [n_kv, 1, M], rows (head, token)
    groups, M = H // n_kv, sink_blk[2]
    sink_rows = jnp.repeat(_sink_arr(sink, H).reshape(n_kv, groups), QB,
                           axis=1)
    sink_rows = jnp.pad(sink_rows, ((0, 0), (0, M - groups * QB)),
                        constant_values=NEG_INF)[:, None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, S // QB),
        in_specs=[
            pl.BlockSpec(q_blk, lambda b, i, *_: (b, i, 0)),
            pl.BlockSpec(sink_blk, lambda b, i, *_: (0, 0, 0)),
            pl.BlockSpec(kn_blk, lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(vn_blk, lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(o_blk, lambda b, i, *_: (b, i, 0)),
        scratch_shapes=[
            *(pltpu.VMEM(shape, dtype) for shape, dtype in scratch),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        C=C, page=page, n_kv=n_kv, groups=groups, hd=hd, nc=padded // C,
        QB=QB, TS=_prefill_self_tile(S), as_stored=as_stored,
        planes=planes, tile_heads=128 // hd if packed else 1,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT),
        interpret=interpret,
    )(
        page_table,
        prefix_lens.astype(jnp.int32),
        chunk_lens.astype(jnp.int32),
        win,
        jnp.asarray(layer, jnp.int32).reshape(1),
        qs, sink_rows, kn, vn, k_pages, v_pages,
    )
    return out.reshape(B, S, H, hd)
