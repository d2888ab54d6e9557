"""Attention over LATENT pages (deepseek_v3's multi-head latent attention).

A latent pool holds, a token a layer, two rows and no per-head key or value
(`models.config.CacheSpec`): in `k` the rotated rotary key `k_pe` that all
heads share (`pe` values, and zeros up to a lane tile), in `v` the
normalised key/value latent `c_kv` (`rank` = kv_lora_rank values).  Every
head's key is `[c_kv W_uk[h] | k_pe]` and its value `c_kv W_uv[h]`, so

    q_h . k_h = (q_nope_h W_uk[h]^T) . c_kv + q_pe_h . k_pe
    o_h       = (sum_j p_hj c_kv_j) W_uv[h]

and attention over the cache is multi-QUERY attention of `rank + pe`
products a score against the stored rows themselves, with the latent rows
as the values (the "absorbed" form: the caller folds `W_uk` into the queries
before and `W_uv` into the result after, `models/llama.py` `_latent_qkv` /
`_latent_out`).  The same numbers as up-projecting every cached token to
per-head keys and values, without the [tokens, heads, nope + v]
up-projection of the whole table every step and layer.

One core serves every step kind: `parts` are the key sets a step attends
to, in order (prefill: the table's pages, the chunk itself; a decode step:
the pages, its own token; a decode block: the pages, the block's ring, its
own token), each with the mask of what a query may see of it.  That XLA
form is the oracle and the path of every decode trace, of the CPU and of a
mesh.  A PREFILL chunk on a single-device TPU engine takes the streaming
Pallas kernel instead (`pallas_latent_attention`: pages read in place, no
scores in HBM, no key tile scored that the chunk cannot see), chosen a trace
by `prefill_attention` below as `paged_attention.prefill_attention` chooses
the per-head kernel; the absorbed decode kernel is not written (ROADMAP M6).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from .paged_attention import NEG_INF, _adapt, _latent_prefill_rule

# f32 scores one head block may materialise ([B, heads, S, keys]): past it
# the heads are walked in blocks (the keys are shared by all heads, so a
# head block re-reads nothing but its own queries).  64 heads x 512 queries
# x 4608 keys would be 604 MB of scores, and their exponentials again.
_SCORE_BLOCK_BYTES = 96 << 20

Part = Tuple[jax.Array, jax.Array, jax.Array]  # (k_pe rows [B, K, >= pe],
# latent rows [B, K, rank], visible [B, S, K] bool)


def rows_of(plane: jax.Array) -> jax.Array:
    """[..., tiles, 128] as stored -> [..., tiles * 128] rows."""
    return plane.reshape(*plane.shape[:-2], -1)


@jax.named_scope("kv.gather")
def gather_latent(k_pages, v_pages, page_table, layer=None):
    """(k_pe rows, latent rows) [B, max_pages * page, *] of each row's
    table, out of one layer's pools [P, page, tiles, 128] or, with `layer`,
    the whole pools' (as `gather_kv`; the stored lane tiles join into
    rows)."""
    at = page_table if layer is None else (layer, page_table)
    B, mp = page_table.shape

    def rows(pages):
        got = pages[at]  # [B, mp, page, tiles, 128]
        return got.reshape(B, mp * got.shape[2], -1)

    return rows(k_pages), rows(v_pages)


def _attend(q_abs, q_pe, parts, scale: float) -> jax.Array:
    """q_abs [B, S, H, rank], q_pe [B, S, H, pe] against every part's rows
    under its mask: one softmax over all parts' keys.  -> [B, S, H, rank]
    f32."""
    rank, pe = q_abs.shape[-1], q_pe.shape[-1]
    # a stored row ends in zeros where its width is no whole lane tile
    parts = [(kpe, lat[..., :rank], valid) for kpe, lat, valid in parts]
    scores = [
        jnp.where(valid[:, None],
                  (jnp.einsum("bqhr,bkr->bhqk", q_abs, lat,
                              preferred_element_type=jnp.float32)
                   + jnp.einsum("bqhp,bkp->bhqk", q_pe, kpe[..., :pe],
                                preferred_element_type=jnp.float32)) * scale,
                  NEG_INF)
        for kpe, lat, valid in parts]
    w = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = 0.0, 0
    for _, lat, _ in parts:
        n = lat.shape[1]
        # weights in the rows' dtype, float32 sums: no float32 copy of the
        # gathered rows
        out = out + jnp.einsum("bhqk,bkr->bqhr",
                               w[..., at:at + n].astype(lat.dtype), lat,
                               preferred_element_type=jnp.float32)
        at += n
    return out


@jax.named_scope("attn.core")
def latent_attention(
    q_abs: jax.Array,  # [B, S, H, rank]: queries with W_uk folded in
    q_pe: jax.Array,  # [B, S, H, pe]: their rotated rotary part
    parts: Sequence[Part],
    scale: float,
) -> jax.Array:
    """Softmax attention of every query over all parts' rows; the value of
    a row is its latent.  Returns [B, S, H, rank] in q's dtype."""
    B, S, H, _ = q_abs.shape
    keys = sum(lat.shape[1] for _, lat, _ in parts)
    per_head = B * S * keys * 4
    block = H
    while block > 1 and block * per_head > _SCORE_BLOCK_BYTES:
        block //= 2
    if block == H or H % block:
        return _attend(q_abs, q_pe, parts, scale).astype(q_abs.dtype)

    def blocks(q):
        return jnp.moveaxis(
            q.reshape(B, S, H // block, block, q.shape[-1]), 2, 0)

    out = jax.lax.map(lambda qs: _attend(*qs, parts, scale),
                      (blocks(q_abs), blocks(q_pe)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, H, -1).astype(q_abs.dtype)


def prefill_parts(k_pool, v_pool, kpe_new, lat_new, page_table, prefix_lens,
                  chunk_lens, layer=None):
    """The key sets of a prefill chunk: the table's cached rows below each
    row's prefix, then the chunk itself, causal."""
    kpe_pre, lat_pre = gather_latent(k_pool, v_pool, page_table, layer)
    B, S = lat_new.shape[:2]
    p = jnp.arange(lat_pre.shape[1])[None, None, :]
    pre_ok = jnp.broadcast_to(p < prefix_lens[:, None, None],
                              (B, S, lat_pre.shape[1]))
    i = jnp.arange(S)[None, :, None]
    j = jnp.arange(S)[None, None, :]
    new_ok = (j <= i) & (j < chunk_lens[:, None, None])
    return [(kpe_pre, lat_pre, pre_ok), (kpe_new, lat_new, new_ok)]


def prefill_attention(q_abs, q_pe, kpe_new, lat_new, k_pool, v_pool,
                      page_table, prefix_lens, chunk_lens, scale: float,
                      impl: str = "xla", layer=None) -> jax.Array:
    """A prefill chunk's attention over its cached prefix and itself:
    queries [B, S, H, rank] / [B, S, H, pe], the chunk's own rows [B, S, pe]
    / [B, S, rank], one layer's pools or the whole pools with `layer`.
    `impl` as `paged_attention.prefill_attention` takes it; the choice is
    noted a trace at the same site, so a step's slice says which program
    its shape got.  Returns [B, S, H, rank] in q's dtype."""
    B, S, H, rank = q_abs.shape
    page = k_pool.shape[-3]

    def rule(ctx):
        from .pallas_latent_attention import latent_query_tile

        return _latent_prefill_rule(B, S, ctx, latent_query_tile(
            S, H, rank, q_pe.shape[-1], page, k_pool.shape[-2:],
            v_pool.shape[-2:], q_abs.dtype, k_pool.dtype))

    impl = _adapt(impl, page_table, page, rule, site="prefill_attention",
                  chunk=S)
    if impl == "pallas":
        from .pallas_latent_attention import prefill_latent_attention_pallas

        with jax.named_scope("attn.core"):
            return prefill_latent_attention_pallas(
                q_abs, q_pe, kpe_new, lat_new, k_pool, v_pool, page_table,
                prefix_lens, chunk_lens, scale, layer=layer)
    return latent_attention(
        q_abs, q_pe, prefill_parts(k_pool, v_pool, kpe_new, lat_new,
                                   page_table, prefix_lens, chunk_lens, layer),
        scale)


def decode_parts(k_pages, v_pages, kpe_self, lat_self, page_table, seq_lens):
    """The key sets of one decode step whose own token ([B, 1, *] rows) is
    NOT in the pool yet: the table's rows below it, then itself."""
    kpe, lat = gather_latent(k_pages, v_pages, page_table)
    B = lat.shape[0]
    pos = jnp.arange(lat.shape[1])[None, None, :]
    ok = pos < (seq_lens[:, None, None] - 1)
    return [(kpe, lat, ok), (kpe_self, lat_self, jnp.ones((B, 1, 1), bool))]
