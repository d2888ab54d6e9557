"""Paged attention for continuous batching on TPU.

Design (TPU-first, not a CUDA translation):

- The KV cache is a pool of fixed-size *pages* per layer:
  ``[num_pages, page_size, n_kv_heads, head_dim]``, stacked over layers
  (``[L, ...]``; the functions below take one layer's pool, or the whole
  pool with a layer index, and never cut a slab out).  A sequence owns an
  ordered list of page ids (its *page table* row).  Page id 0 is reserved as
  the trash page: padding tokens scatter there, so every shape stays static
  and no masking is needed on the write path.

- Everything here is shape-static and jit-friendly: the engine buckets
  ``pages_per_seq`` and chunk lengths to a handful of power-of-two sizes so
  XLA compiles a few variants and reuses them (no dynamic shapes inside jit).

- ``prefill_attention`` computes the general form "new chunk attends to
  cached prefix pages + itself (causal)".  With ``prefix_len == 0`` it is
  plain causal prefill; with a populated page table it covers chunked
  prefill and prefix-cache hits.  ``decode_attention`` is the single-token
  step over the page table.

The reference framework never implements attention (it delegates to
vLLM/TRT-LLM, see SURVEY.md §2.6); this module is the TPU-native equivalent
of those engines' paged attention + vLLM's slot-mapping KV writes.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..analysis import xla_ledger

NEG_INF = -1e30


# DECODE contexts at least this wide use the Pallas kernel under
# "adaptive".  r5 re-measured the crossover AFTER the deferred-write decode
# fix (the old per-layer scatter+gather pool copy had been taxing the xla
# path): at ctx 2272/batch 4 on v5e the xla+deferred path runs 9.6ms/step
# vs the kernel's 15.8 (the kernel still requires write-first), so the
# decode crossover moved out past 4k; each table-width bucket is its own
# jit trace, so the choice is static per compiled step.
PALLAS_MIN_CTX_TOKENS = 4096

# PREFILL steps whose XLA attention would materialise at least this many
# f32 scores a head, chunk x (table + chunk), use the Pallas kernel under
# "adaptive".  Whole `prefill_step` programs timed on a v5e, both forms
# forced, every table full (PERF.md, PR 50: `scripts/time_prefill_steps.py`
# at 28 query heads over 4 KV heads of 128, 14 layers, and at 32 over 2, 6
# attention layers of 52; 1, 2 and 4 rows, chunks 16-512, tables of 32-512
# pages): XLA's cost follows the TABLE (the gather and the scores), the
# kernel's the prefix that is there.  At the first geometry the kernel wins
# all 49 shapes, from 2^17 scores by 0.3-256 ms a step (a 64-token row under
# 128 pages by 0.3 of 13.7, four such rows by 1.9 of 17.2, a 512-token chunk
# under 32 / 128 / 512 pages by 1.0 / 9.3 / 65 of 24.7 / 34.1 / 95.3) and
# below it by 0.1-0.6 (1-4%).  At the second a step on the kernel carries
# about 1.8 ms that a step on XLA does not (not understood: the kernel's own
# time there is 0.2 ms a layer; PERF.md 7 (l)), so XLA wins a lone 64-256
# -token chunk under 32-128 pages by 0.5-1.9 ms of 19.4-23.1 up to 2^18.2
# scores, and the kernel wins 64-token rows under 128 pages (by 1.2 of 22.7,
# four of them by 2.7 of 31.6), a 256-token chunk under 128 pages (0.8) and
# everything larger (a 512-token chunk under 512 pages by 32 of 78).  2^17
# is where the shared 64-token rows of a 128-page table, the cached path of
# every document cell, cross to the kernel at both geometries; it costs the
# second geometry its lone 128-token chunks (1.8 ms a step), and its cell
# reads the same end to end (169.2 -> 168.7 ms).  It was 2^19 for the
# parent's kernel (PR 34), which lost four 64-token rows under 128 and 256
# pages by 1.7 / 0.9 ms (PR 36).
PALLAS_MIN_PREFILL_SCORES = 1 << 17


def resolve_attention_impl(impl: str = "auto", meshed: bool = False) -> str:
    """Pick the attention implementation.

    "adaptive" — per-trace choice: the Pallas streaming kernels
    (``ops.pallas_attention``) where they were measured to win (decode:
    a page-table bucket of at least ``PALLAS_MIN_CTX_TOKENS``; prefill:
    ``PALLAS_MIN_PREFILL_SCORES``), the einsum path elsewhere.
    Chosen on real TPU when the engine is single-device (the kernels are
    per-shard programs; under a GSPMD mesh the einsum path lets XLA
    partition freely).
    "xla" — the einsum path below (and everywhere in interpret-free CPU
    tests). Kernel/einsum equivalence is covered by
    tests/test_pallas_attention.py in interpret mode.
    """
    if impl not in ("auto", "adaptive", "pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl != "auto":
        if meshed and impl != "xla":
            raise ValueError(
                "the Pallas attention kernels are per-shard programs; a "
                "GSPMD-meshed engine must use attention_impl='xla'"
            )
        return impl
    backend = jax.default_backend()
    if meshed:
        choice, why = "xla", "GSPMD mesh: XLA partitions the einsum path"
    elif backend == "tpu":
        choice, why = "adaptive", "single-device tpu backend"
    else:
        choice, why = "xla", (
            f"backend is {backend!r}: the Pallas kernels are TPU programs")
    xla_ledger.note_path_choice("attention_impl", choice, why, requested=impl)
    return choice


def _decode_rule(ctx: int):
    if ctx >= PALLAS_MIN_CTX_TOKENS:
        return "pallas", f"table {ctx} tokens >= {PALLAS_MIN_CTX_TOKENS}"
    return "xla", f"table {ctx} tokens < {PALLAS_MIN_CTX_TOKENS}"


def _prefill_rule(chunk: int, ctx: int, query_block):
    """(choice, why) for a prefill step of `chunk` tokens under `ctx` tokens
    of table; `query_block` is the kernel's own answer to whether any
    block of this chunk fits its VMEM (None: none does)."""
    scores = chunk * (ctx + chunk)
    if query_block is None:
        return "xla", f"no query block of a {chunk}-token chunk fits VMEM"
    if scores >= PALLAS_MIN_PREFILL_SCORES:
        return "pallas", (f"{chunk} x ({ctx} + {chunk}) scores a head >= "
                          f"{PALLAS_MIN_PREFILL_SCORES}")
    return "xla", (f"{chunk} x ({ctx} + {chunk}) scores a head < "
                   f"{PALLAS_MIN_PREFILL_SCORES}")


# A prefill chunk over LATENT pages takes the kernel
# (`ops.pallas_latent_attention`) from this many tokens of TABLE on, and a
# chunk of at least `LATENT_PALLAS_MIN_CHUNK` tokens under any table.  Whole
# `prefill_step` programs of both latent configurations (64 and 32 heads)
# timed on a v5e, both forms forced, every table full (PERF.md, PR 43:
# chunks 16-512, 1 and 4 rows, tables of 32-256 pages): XLA gathers and
# scores the TABLE whatever the prefix, the kernel streams the prefix that
# is there, so from 2048 tokens of table the kernel wins or ties every shape
# (a 512-token chunk by 4.9 / 2.9 ms a step of 54 / 44 at 2048, 8.7 / 4.3 at
# 4096; four 64-token rows by 2.7 / 1.1 and 5.2 / 3.7; a lone 16-token chunk
# by 0.05-0.5), and a 512-token chunk wins under the short tables too (1.6
# -4.1 / 1.8-3.1 ms: half of the chunk's own scores lie above the diagonal).
# Below both, a 64-256-token chunk under 512-1024 tokens of table, XLA wins
# or ties by 0.0-0.7 ms (a streamed tile is 512 keys, half of them masked
# there).
LATENT_PALLAS_MIN_TABLE_TOKENS = 2048
LATENT_PALLAS_MIN_CHUNK = 512


def _latent_prefill_rule(batch: int, chunk: int, ctx: int, query_tile):
    """(choice, why) for a prefill step of `batch` rows of `chunk` tokens
    under `ctx` tokens of latent table; `query_tile` is the kernel's own
    answer to whether it has a tile for this chunk (None: none: a chunk of
    no whole sublane tiles, a pool dtype it cannot read, too much VMEM)."""
    if query_tile is None:
        return "xla", f"the kernel has no query tile for a {chunk}-token chunk"
    shape = f"latent pages: {batch} x {chunk} queries under a {ctx}-token table"
    if chunk >= LATENT_PALLAS_MIN_CHUNK:
        return "pallas", f"{shape}: chunk >= {LATENT_PALLAS_MIN_CHUNK}"
    if ctx >= LATENT_PALLAS_MIN_TABLE_TOKENS:
        return "pallas", f"{shape}: table >= {LATENT_PALLAS_MIN_TABLE_TOKENS}"
    return "xla", (f"{shape}: chunk < {LATENT_PALLAS_MIN_CHUNK} and table < "
                   f"{LATENT_PALLAS_MIN_TABLE_TOKENS}")


# Why every DECODE trace over latent pages is XLA's, whatever was asked for:
# `_adapt(..., only_xla=...)` notes it a trace.
LATENT_DECODE_XLA = ("latent pages: no kernel of the absorbed decode form "
                     "yet (prefill has one)")


def _adapt(impl: str, page_table: jax.Array, page_size: int,
           rule=_decode_rule, site: str = "decode_attention",
           chunk: int = 1, only_xla: str = "") -> str:
    """Resolve "adaptive" for one trace: `rule(table tokens)` says which
    program and why.  Runs at trace time only, so the ledger note below is
    once per compiled variant: which attention program a (batch, chunk,
    table-width) step got, and why.  `only_xla`: why this trace has no
    kernel to choose, whatever `impl` says."""
    ctx = page_table.shape[1] * page_size
    if only_xla:
        choice, why = "xla", only_xla
    elif impl != "adaptive":
        choice, why = impl, f"attention_impl={impl}"
    else:
        choice, why = rule(ctx)
    xla_ledger.note_path_choice(
        site, choice, why, batch=page_table.shape[0], chunk=chunk,
        table_tokens=ctx)
    return choice


_SUBLANES = 8


def _layers_would_move_to_sublanes(n_layers: int, plane: tuple) -> bool:
    """Whether the TPU compiler re-lays a pool [L, slots, *plane] out around
    `write_kv_layers`' scatter over the layer axis: where a token's plane
    has fewer rows than a tile has sublanes and the LAYERS fill them (a
    multiple of 8), it moves the layer axis under the lanes, and copies the
    whole pool there before the scatter and back after it, in every step
    (AOT compile for a described v5e, PR 37: a latent pool of 8 layers,
    planes [2, 128] and [4, 128], 2.4 GB copied twice a prefill step; at 7
    layers, and at the 12 and 14 of the per-head pools, it leaves the pool
    where it is).  By shapes alone, so every other pool traces the scatter
    it traced."""
    return n_layers % _SUBLANES == 0 and plane[0] < _SUBLANES


# Scope names (jax.named_scope) are what a profiler trace's device ops are
# found by: docs/observability.md lists them.
@jax.named_scope("kv.write")
def write_kv_layers(
    k_pool: jax.Array,  # [L, P, page, n_kv, hd] — every layer's pool
    v_pool: jax.Array,  # its own [n_kv, hd] (a latent pool's planes differ)
    k_new: jax.Array,  # [L, B, S, n_kv, hd] — every layer's new tokens
    v_new: jax.Array,
    page_table: jax.Array,  # [B, max_pages] int32
    write_pos: jax.Array,  # [B] int32 — seq offset of each row's first token
    valid: jax.Array,  # [B, S] bool — tokens that are not padding
) -> Tuple[jax.Array, jax.Array]:
    """ONE scatter lands every layer's new tokens in the (donated) pool, in
    place: a step writes its own tokens, never a layer's slab.  The layer
    loops attend to the OLD pool plus the new tokens themselves and call
    this once, after the loop.  Row b's token s sits at sequence position
    write_pos[b] + s; padding goes to slot 0 inside trash page 0, so every
    shape stays static (duplicate trash slots may race, by design)."""
    L, P, page_size, n_kv, hd = k_pool.shape
    pos = write_pos[:, None] + jnp.arange(valid.shape[1])[None, :]  # [B, S]
    page_idx = jnp.clip(pos // page_size, 0, page_table.shape[1] - 1)
    page_ids = jnp.take_along_axis(page_table, page_idx, axis=1)
    slot = jnp.where(valid, page_ids * page_size + pos % page_size,
                     0).reshape(-1)  # [B*S] flat slots of a layer's pool

    def land(pool, new):
        dims = pool.shape[3:]
        if _layers_would_move_to_sublanes(L, dims):
            # the same slots as rows of ONE axis, a layer after the other:
            # no layer axis is left for the compiler to re-lay out
            rows = pool.reshape(L * P * page_size, *dims)
            at = (jnp.arange(L)[:, None] * (P * page_size)
                  + slot[None, :]).reshape(-1)
            rows = rows.at[at].set(
                new.reshape(-1, *dims).astype(pool.dtype), mode="drop")
            return rows.reshape(pool.shape)
        flat = pool.reshape(L, P * page_size, *dims)
        flat = flat.at[:, slot].set(
            new.reshape(L, -1, *dims).astype(pool.dtype), mode="drop")
        return flat.reshape(pool.shape)

    return land(k_pool, k_new), land(v_pool, v_new)


def write_kv_pages(
    k_pages: jax.Array,  # [P, page, n_kv, hd]
    v_pages: jax.Array,
    k_new: jax.Array,  # [B, S, n_kv, hd]
    v_new: jax.Array,
    page_table: jax.Array,  # [B, max_pages] int32
    write_pos: jax.Array,  # [B] int32 — seq offset where this chunk starts
    chunk_lens: jax.Array,  # [B] int32 — valid tokens in this chunk
) -> Tuple[jax.Array, jax.Array]:
    """`write_kv_layers` for ONE layer's pool. Padding → trash page 0."""
    valid = jnp.arange(k_new.shape[1])[None, :] < chunk_lens[:, None]
    k_pool, v_pool = write_kv_layers(
        k_pages[None], v_pages[None], k_new[None], v_new[None], page_table,
        write_pos, valid)
    return k_pool[0], v_pool[0]


@jax.named_scope("kv.gather")
def gather_kv(
    k_pages: jax.Array,  # [P, page, n_kv, hd], or [L, P, ...] with `layer`
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    layer=None,  # scalar layer index (traced OK) into a whole pool
) -> Tuple[jax.Array, jax.Array]:
    """Materialize each sequence's KV: [B, max_pages*page, n_kv, hd].  With
    `layer`, ONE gather indexed by (layer, page) reads the table's pages
    straight out of the whole pool: no slab is sliced out first."""
    at = page_table if layer is None else (layer, page_table)
    k, v = k_pages[at], v_pages[at]  # [B, max_pages, page, n_kv, hd]
    B, mp, page, n_kv, hd = k.shape
    return k.reshape(B, mp * page, n_kv, hd), v.reshape(B, mp * page, n_kv, hd)


def _mqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B, Sq, n_heads, hd] x k [B, Sk, n_kv, hd] -> [B, n_heads, Sq, Sk]
    with GQA head grouping."""
    B, Sq, n_heads, hd = q.shape
    n_kv = k.shape[2]
    groups = n_heads // n_kv
    qg = q.reshape(B, Sq, n_kv, groups, hd)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    )
    return scores.reshape(B, n_kv * groups, Sq, k.shape[1])


def _mqa_out(weights: jax.Array, v: jax.Array, dtype) -> jax.Array:
    """weights [B, n_heads, Sq, Sk] x v [B, Sk, n_kv, hd] -> [B, Sq, n_heads, hd]."""
    B, n_heads, Sq, Sk = weights.shape
    n_kv = v.shape[2]
    groups = n_heads // n_kv
    wg = weights.reshape(B, n_kv, groups, Sq, Sk)
    out = jnp.einsum("bkgqs,bskd->bqkgd", wg, v.astype(jnp.float32))
    return out.reshape(B, Sq, n_heads, v.shape[3]).astype(dtype)


def _sink_softmax(scores: jax.Array, sink) -> jax.Array:
    """Softmax with optional per-head attention-sink logits (GPT-OSS):
    the sink joins the denominator as one extra virtual key but
    contributes no value — some attention mass drains into it."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    col_shape = (*scores.shape[:-1], 1)
    col = jnp.broadcast_to(
        sink.astype(jnp.float32).reshape(
            (1, -1) + (1,) * (scores.ndim - 3) + (1,)
        ),
        col_shape,
    )
    return jax.nn.softmax(
        jnp.concatenate([scores, col], axis=-1), axis=-1
    )[..., :-1]


@jax.named_scope("attn.core")
def prefill_attention(
    q: jax.Array,  # [B, S, n_heads, hd] — the new chunk
    k_new: jax.Array,  # [B, S, n_kv, hd]
    v_new: jax.Array,
    k_pages: jax.Array,  # [P, page, n_kv, hd] — pool (already containing
    # prefix), or every layer's [L, P, page, n_kv, hd] with `layer`
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    prefix_lens: jax.Array,  # [B] — tokens already in cache before this chunk
    chunk_lens: jax.Array,  # [B] — valid tokens in this chunk
    impl: str = "xla",
    window=None,  # scalar int (traced OK); <= 0 → full attention
    sink=None,  # [n_heads] learnable sink logits; None → plain softmax
    layer=None,  # scalar layer index (traced OK) into a whole pool
    packed: bool = False,  # the pool's token is lane tiles (`CacheSpec`)
) -> jax.Array:
    """Chunk attends to cached prefix + itself (causal; optionally only
    the last `window` positions). Returns [B,S,H,hd].  The pool is only
    read, and only by page: given the whole pool and `layer` (the layer
    loops), layer `layer`'s pages are fetched by (layer, page) and no slab
    is cut out of the pool."""
    B, S, n_heads, hd = q.shape
    # (`packed`: a token's heads are whole lane tiles, several narrow heads
    # a tile, `pallas_attention.packed_plane`: the same values in the same
    # order, so the heads are counted on the chunk's own keys)
    page, n_kv = k_pages.shape[-3], k_new.shape[2]

    def rule(ctx):
        from .pallas_attention import prefill_query_block

        return _prefill_rule(S, ctx, prefill_query_block(
            S, n_heads, n_kv, hd, page, q.dtype, k_pages.dtype, packed))

    impl = _adapt(impl, page_table, page, rule, site="prefill_attention",
                  chunk=S)
    if impl == "pallas":
        from .pallas_attention import prefill_attention_pallas

        return prefill_attention_pallas(
            q, k_new, v_new, k_pages, v_pages, page_table, prefix_lens,
            chunk_lens, window=window, sink=sink, layer=layer,
            packed=packed,
        )
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    # [B, Lp, n_kv, hd]
    k_pre, v_pre = gather_kv(k_pages, v_pages, page_table, layer)
    Lp = k_pre.shape[1]
    if packed:
        k_pre = k_pre.reshape(B, Lp, n_kv, hd)
        v_pre = v_pre.reshape(B, Lp, n_kv, hd)
    i = jnp.arange(S)[None, None, :, None]
    # global query positions: prefix + row index within the chunk
    q_pos = prefix_lens[:, None, None, None] + i

    # scores over prefix (global key positions 0..Lp)
    s_pre = _mqa_scores(q, k_pre) * scale  # [B, H, S, Lp]
    p = jnp.arange(Lp)[None, None, None, :]
    pre_valid = p < prefix_lens[:, None, None, None]
    if window is not None:
        pre_valid &= (p > q_pos - window) | (window <= 0)
    s_pre = jnp.where(pre_valid, s_pre, NEG_INF)

    # scores over the chunk itself (causal within chunk)
    s_new = _mqa_scores(q, k_new) * scale  # [B, H, S, S]
    j = jnp.arange(S)[None, None, None, :]
    new_valid = (j <= i) & (j < chunk_lens[:, None, None, None])
    if window is not None:
        new_valid &= (j > i - window) | (window <= 0)
    s_new = jnp.where(new_valid, s_new, NEG_INF)

    scores = jnp.concatenate([s_pre, s_new], axis=-1)  # [B, H, S, Lp+S]
    weights = _sink_softmax(scores, sink)
    w_pre, w_new = weights[..., :Lp], weights[..., Lp:]
    out = _mqa_out(w_pre, v_pre, q.dtype) + _mqa_out(w_new, v_new, q.dtype)
    return out


@jax.named_scope("attn.core")
def decode_attention(
    q: jax.Array,  # [B, n_heads, hd] — one new token per sequence
    k_pages: jax.Array,  # [P, page, n_kv, hd] (new token already written,
    # UNLESS self_kv is given — see below)
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] — context length incl. the new token
    impl: str = "xla",
    window=None,  # scalar int (traced OK); <= 0 → full attention
    sink=None,  # [n_heads] learnable sink logits; None → plain softmax
    self_kv=None,  # ([B, n_kv, hd], same): the NEW token's k/v, NOT yet
    # in the pool — it joins the softmax as an explicit self column.
    # This is the deferred-write decode path: a per-layer pool scatter
    # followed by a pool read makes XLA copy the pool every layer-step;
    # attending to the OLD pool + self lets the caller land ONE batched
    # scatter per step.  Token-identical in tier-1; what it saves is not
    # measured: no benchmark cell decodes
) -> jax.Array:
    """Single-token attention over the page table. Returns [B, n_heads, hd]."""
    impl = _adapt(impl, page_table, k_pages.shape[1])
    if impl == "pallas":
        assert self_kv is None, "self_kv is an xla-path feature"
        from .pallas_attention import decode_attention_pallas

        return decode_attention_pallas(
            q, k_pages, v_pages, page_table, seq_lens, window=window,
            sink=sink,
        )
    B, n_heads, hd = q.shape
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    k, v = gather_kv(k_pages, v_pages, page_table)  # [B, L, n_kv, hd]
    L = k.shape[1]
    scores = _mqa_scores(q[:, None], k)[:, :, 0, :] * scale  # [B, H, L]
    pos = jnp.arange(L)[None, None, :]
    cached = seq_lens[:, None, None] - (0 if self_kv is None else 1)
    valid = pos < cached
    if window is not None:
        valid &= (pos >= seq_lens[:, None, None] - window) | (window <= 0)
    scores = jnp.where(valid, scores, NEG_INF)
    if self_kv is not None:
        k_self, v_self = self_kv
        n_kv = k_self.shape[1]
        groups = n_heads // n_kv
        s_self = jnp.einsum(
            "bkgd,bkd->bkg",
            q.reshape(B, n_kv, groups, hd), k_self,
            preferred_element_type=jnp.float32,
        ).reshape(B, n_heads, 1) * scale
        weights = _sink_softmax(
            jnp.concatenate([scores, s_self], axis=-1), sink)
        w_cached, w_self = weights[..., :-1], weights[..., -1:]
        out = _mqa_out(w_cached[:, :, None, :], v, q.dtype)[:, 0]
        v_top = jnp.repeat(v_self, groups, axis=1)  # [B, n_heads, hd]
        return out + (w_self * v_top.astype(jnp.float32)).astype(q.dtype)
    weights = _sink_softmax(scores, sink)
    out = _mqa_out(weights[:, :, None, :], v, q.dtype)  # [B, 1, H, hd]
    return out[:, 0]
