"""Sequence-parallel prefill for the serving engine.

The reference has no sequence/context parallelism (SURVEY.md §2.6 —
absent; long context is delegated to engines).  Here long-prompt prefill
is sharded over an `sp` mesh axis: each device holds S/sp of the prompt,
attention runs as ring attention (K/V blocks rotate over ICI while the
flash accumulator runs), so prefill FLOPs and activation memory scale
down by sp while attention stays exact.

Composes with tensor parallelism: on a dp×sp×tp mesh each device holds
S/sp of the sequence AND heads/tp of every projection (megatron
convention, the same `param_pspecs` the GSPMD decode path uses).  Ring
attention is per-head, so the ring rotates only the local head slice
over `sp` while `tp` psums reduce the attention/MLP outputs — the two
axes never talk to each other.

Design constraints (enforced by the engine):
- whole-REMAINDER prefill (no chunking): a row's uncached tokens are
  planned as one chunk; cached prefixes are supported — the ring starts
  at the prefix boundary and the prefix KV is flash-accumulated from the
  pool first (not with kv_partition: prefix pages are owner-shard-local);
- the KV pool is REPLICATED over sp and dp but SHARDED on kv-heads over
  tp (the same layout decode uses): each device all-gathers the new
  chunk's K/V over sp/dp and scatters its own head slice, keeping every
  sp/dp replica bit-identical without a pool-sized collective;
- the sequence bucket must divide by sp, the batch by dp, and the
  q/kv head counts by tp;
- MoE under sp×tp uses the ragged dispatch with experts sharded over
  tp (`_moe_ragged_ep`): the globally-sorted assignment list is rotated
  so each shard's contiguous expert slice sits at the front for
  `ragged_dot`, and a tp psum combines the per-expert partials.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import KVCache, ModelConfig, kv_cache_pspec, param_pspecs
from ..models.llama import _lm_logits, _moe, _proj, _qk_norm
from ..models.quantization import matmul_any, quantize_pspecs
from ..ops import apply_rope, rms_norm, rope_attention_scale, rope_frequencies, write_kv_pages
from ._compat import shard_map
from .ring_attention import ring_attention_local


def _embed_sp(embed_local: jax.Array, tokens: jax.Array) -> jax.Array:
    """Embedding lookup with the vocab sharded over tp: each shard
    gathers the rows it owns, the psum fills in the rest (the manual
    form of what GSPMD does for a sharded gather)."""
    v_local = embed_local.shape[0]
    off = jax.lax.axis_index("tp") * v_local
    idx = jnp.clip(tokens - off, 0, v_local - 1)
    x = embed_local[idx]
    mine = (tokens >= off) & (tokens < off + v_local)
    return jax.lax.psum(jnp.where(mine[..., None], x, 0), "tp")


def _layer_sp(lp, kv_layer, x, positions, table_full, chunk_full, cfg, inv_freq,
              tp: int, owner_l=None, table_l=None, chunk_l=None,
              prefix_l=None, prefix_full=None, window=None,
              prefix_table_l=None, rope_pos3=None):
    """One decoder layer on a [Bl, Sl] shard holding heads/tp: ring
    attention over sp on the local heads, KV head-slice written to the
    tp-sharded pool from the sp/dp-gathered chunk, tp psums after the
    attention and MLP output projections.

    With `owner_l` (partitioned pool): each (dp, sp) shard owns its own
    page range, so the write gathers the chunk over sp ONLY and each
    shard scatters just the rows it owns (non-owned rows write the
    shard's local trash page 0) — no dp gather, no replication.

    With a non-empty `prefix_table_l`: rows may carry a cached prefix
    (prefix_l tokens already in the pool); the ring starts at the prefix
    boundary and the prefix KV is flash-accumulated from those pages
    first.  Per-layer sliding `window`s and sink logits follow
    ops.paged_attention."""
    Bl, Sl, h = x.shape
    nh = cfg.num_attention_heads // tp
    nkv = cfg.num_key_value_heads // tp
    hd = cfg.head_dim_
    k_pages, v_pages = kv_layer
    dt = x.dtype

    attn_in = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = _proj(attn_in, lp, "wq", "bq").astype(dt).reshape(Bl, Sl, nh, hd)
    k = _proj(attn_in, lp, "wk", "bk").astype(dt).reshape(Bl, Sl, nkv, hd)
    v = _proj(attn_in, lp, "wv", "bv").astype(dt).reshape(Bl, Sl, nkv, hd)
    q, k = _qk_norm(lp, q, k, cfg)
    if rope_pos3 is not None:
        # mrope (qwen2_vl): the (t, h, w) streams' local S-slice rides in
        # with the shard; text rows carry equal streams
        from ..ops import apply_mrope

        q = apply_mrope(q, rope_pos3, inv_freq, cfg.mrope_section)
        k = apply_mrope(k, rope_pos3, inv_freq, cfg.mrope_section)
    else:
        rs = rope_attention_scale(cfg.rope_scaling)
        q = apply_rope(q, positions, inv_freq, scale=rs)
        k = apply_rope(k, positions, inv_freq, scale=rs)

    pk = pv = None
    use_prefix = prefix_table_l is not None and prefix_table_l.shape[1] > 0
    if use_prefix:
        # gather this shard's rows' cached pages (pool replicated over
        # sp/dp, head-sharded over tp — matches the local head slice).
        # prefix_table_l is width-bucketed to the batch's LONGEST prefix
        # host-side, so cache-miss batches (width 0) skip this entirely
        page = k_pages.shape[1]
        Wp = prefix_table_l.shape[1]
        pk = k_pages[prefix_table_l].reshape(Bl, Wp * page, nkv, hd)
        pv = v_pages[prefix_table_l].reshape(Bl, Wp * page, nkv, hd)
    attn = ring_attention_local(
        q, k, v, axis_name="sp", causal=True,
        q_offset=prefix_l if use_prefix else None,
        window=window, sink=lp.get("sinks"),
        prefix_k=pk, prefix_v=pv,
        prefix_lens=prefix_l if use_prefix else None,
    )

    k_full = jax.lax.all_gather(k, "sp", axis=1, tiled=True)
    v_full = jax.lax.all_gather(v, "sp", axis=1, tiled=True)
    if owner_l is not None:
        # partitioned pool: local rows only, owner-masked local tables
        mine = (owner_l == jax.lax.axis_index("sp"))[:, None]
        masked = jnp.where(mine, table_l, 0)
        zeros = jnp.zeros((Bl,), jnp.int32)
        k_pages, v_pages = write_kv_pages(
            k_pages, v_pages, k_full, v_full, masked, zeros, chunk_l
        )
    else:
        # replicated pool: the write must be identical on every sp/dp
        # replica (the pool is head-sharded over tp, so each tp shard
        # scatters its own slice): gather the full chunk (sp → sequence
        # axis, dp → batch axis) and scatter all rows — at the row's
        # prefix offset (cached-prefix rows append after their prefix)
        k_full = jax.lax.all_gather(k_full, "dp", axis=0, tiled=True)
        v_full = jax.lax.all_gather(v_full, "dp", axis=0, tiled=True)
        k_pages, v_pages = write_kv_pages(
            k_pages, v_pages, k_full, v_full, table_full, prefix_full,
            chunk_full,
        )

    attn_out = matmul_any(
        attn.reshape(Bl, Sl, nh * hd), lp["wo"], "bsd,dh->bsh"
    )
    attn_out = jax.lax.psum(attn_out, "tp").astype(dt)
    if "bo" in lp:  # gpt-oss o_proj bias — AFTER the tp psum (the bias
        # is replicated; adding pre-psum would scale it by tp)
        attn_out = attn_out + lp["bo"].astype(dt)
    x = x + attn_out
    mlp_in = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.is_moe:
        if tp > 1 and cfg.moe_impl == "a2a":
            # wide-EP: local routing + expert all-to-all (the DeepEP
            # analog — scales past the replicated-routing ragged path)
            mlp_out = _moe_a2a_tp(lp, mlp_in, cfg)
        elif tp > 1:
            mlp_out = _moe_ragged_ep(lp, mlp_in, cfg)
        else:
            mlp_out = _moe(lp, mlp_in, cfg)
    else:
        mlp_out = jax.lax.psum(_mlp_partial(lp, mlp_in), "tp")
    return x + mlp_out.astype(dt), (k_pages, v_pages)


def _mlp_partial(lp, x):
    """`models.llama._mlp` without the implicit full-width assumption:
    returns the PARTIAL down-projection (summed over the local ffn
    shard) for the caller to psum over tp."""
    gate = matmul_any(x, lp["w_gate"], "bsh,hf->bsf")
    up = matmul_any(x, lp["w_up"], "bsh,hf->bsf")
    act = jax.nn.silu(gate) * up
    return matmul_any(act.astype(x.dtype), lp["w_down"], "bsf,fh->bsh")


def _moe_a2a_tp(lp, x, cfg):
    """wide_ep.moe_all_to_all_ep adapted to the sp×tp layer body, where
    tokens arrive TP-REPLICATED (attention/psum outputs): each tp shard
    routes a disjoint 1/tp slice of the tokens — without the slice every
    shard would ship identical peer blocks and the owners would compute
    each assignment tp times — and an all-gather re-replicates the
    result for the residual add."""
    from .wide_ep import moe_all_to_all_ep

    B, S, h = x.shape
    i = jax.lax.axis_index("tp")
    tp = jax.lax.psum(1, "tp")
    T = B * S
    Tp = -(-T // tp) * tp
    xf = x.reshape(T, h)
    if Tp != T:
        xf = jnp.pad(xf, ((0, Tp - T), (0, 0)))
    xl = jax.lax.dynamic_slice(xf, (i * (Tp // tp), 0), (Tp // tp, h))
    out_l = moe_all_to_all_ep(
        lp, xl[None], cfg, axis="tp",
        capacity_factor=cfg.moe_capacity_factor or 2.0,
    )[0]  # [Tp/tp, h]
    out = jax.lax.all_gather(out_l, "tp", axis=0, tiled=True)  # [Tp, h]
    return out[:T].reshape(B, S, h)


def _moe_ragged_ep(lp, x, cfg):
    """Dropless ragged-dot MoE with the EXPERTS sharded over the tp axis
    (expert parallelism inside the sp shard_map).

    Tokens are already sequence-sharded (sp) and replicated across tp;
    each tp shard owns a contiguous expert slice [e0, e0+El).  Routing
    is computed in full (router weights replicated), assignments are
    sorted by expert globally, and the local slice — contiguous after
    the sort — is rotated to the front so `jax.lax.ragged_dot` computes
    exactly the local experts' rows.  A psum over tp combines the
    per-expert partial outputs (non-local assignments contribute zero).
    """
    B, S, h = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    El = lp["w_gate"].shape[0]  # local experts (static, from the shard)
    e0 = jax.lax.axis_index("tp") * El
    T = B * S
    A = T * k

    xf = x.reshape(T, h)
    from ..models.llama import moe_act, moe_router_logits

    router_logits = moe_router_logits(lp, xf, "th,he->te")
    weights, selected = jax.lax.top_k(router_logits, k)  # [T, k]
    weights = jax.nn.softmax(weights, axis=-1)

    expert_of = selected.reshape(A)
    order = jnp.argsort(expert_of, stable=True)
    sorted_experts = expert_of[order]
    # rotate the (contiguous) local expert segment to the front
    offset = jnp.searchsorted(sorted_experts, e0)
    rolled = jnp.roll(order, -offset)
    tok_rolled = rolled // k
    xs = xf[tok_rolled]  # [A, h] — local segment first
    gs_full = jnp.bincount(expert_of, length=E)
    gs_local = jax.lax.dynamic_slice(gs_full, (e0,), (El,))

    gate = jax.lax.ragged_dot(
        xs, lp["w_gate"], gs_local, preferred_element_type=jnp.float32
    )
    up = jax.lax.ragged_dot(
        xs, lp["w_up"], gs_local, preferred_element_type=jnp.float32
    )
    exp_rolled = expert_of[rolled]
    if "b_gate" in lp:  # gpt-oss: per-LOCAL-expert ffn biases (rows of
        # other shards' experts get a clipped bias, masked out below)
        safe_e = jnp.clip(exp_rolled - e0, 0, El - 1)
        gate = gate + lp["b_gate"][safe_e]
        up = up + lp["b_up"][safe_e]
    act = moe_act(cfg, gate, up).astype(x.dtype)
    ys = jax.lax.ragged_dot(
        act, lp["w_down"], gs_local, preferred_element_type=jnp.float32
    )  # [A, h] — rows past the local assignment count are garbage
    if "b_down" in lp:
        ys = ys + lp["b_down"][safe_e]

    local = (exp_rolled >= e0) & (exp_rolled < e0 + El)
    wf = weights.reshape(A)[rolled].astype(jnp.float32)
    # where(), not multiply-by-zero: rows past sum(gs_local) are
    # UNSPECIFIED ragged_dot output and may be non-finite on TPU —
    # NaN * 0 would poison the scatter-add and spread via the psum
    contrib = jnp.where(local[:, None], ys * wf[:, None], 0.0)
    out = jnp.zeros((T, h), jnp.float32).at[tok_rolled].add(contrib)
    out = jax.lax.psum(out, "tp")
    return out.reshape(B, S, h).astype(x.dtype)


def forward_prefill_sp(
    params,
    cfg: ModelConfig,
    kv: KVCache,
    tokens: jax.Array,  # [B, S] — S divisible by sp, B by dp
    page_table: jax.Array,  # [B, max_pages]
    chunk_lens: jax.Array,  # [B] valid tokens (prompt starts at position 0)
    mesh: Mesh,
    owner: jax.Array = None,  # [B] sp-slot owning each row's pages
    pool_axes=None,  # e.g. ("dp","sp") — partitioned-pool kv layout
    prefix_lens: jax.Array = None,  # [B] cached-prefix tokens per row
    prefix_table: jax.Array = None,  # [B, Wp] pages covering the batch's
    # longest prefix (width-bucketed host-side; Wp == 0 → no cached
    # prefixes this step, the prefix path compiles out)
    extra_embeds: jax.Array = None,  # [B, S, h] vision-tower patches
    extra_mask: jax.Array = None,  # [B, S] bool — both shard their S
    # axis over sp exactly like the tokens (vision × sp)
    mm_positions: jax.Array = None,  # [B, 3, S] mrope (t, h, w) streams,
    # S sharded over sp; None on an mrope model ropes text-style
) -> Tuple[jax.Array, KVCache]:
    """Whole-prompt prefill with the sequence sharded over `sp` and heads
    over `tp`.

    Returns (last-position logits [B, V], updated KVCache).  Without
    `owner` the pool comes back in the replicated decode layout (sp/dp-
    replicated, head-sharded over tp).  With `owner`/`pool_axes` the pool
    is PARTITIONED over (dp, sp): `page_table` carries LOCAL ids and each
    row's KV is written only on the (dp, sp) shard that owns it — HBM
    capacity scales with the mesh (engine kv_partition).
    """
    tp = mesh.shape.get("tp", 1)
    if cfg.is_moe and tp > 1:
        if cfg.moe_impl not in ("auto", "ragged", "a2a"):
            raise NotImplementedError(
                "sp×tp MoE implements the ragged and a2a dispatches only "
                f"(moe_impl={cfg.moe_impl!r})"
            )
        if cfg.num_experts % tp:
            raise ValueError(
                f"tp={tp} must evenly divide num_experts={cfg.num_experts}"
            )
    if cfg.num_attention_heads % tp or cfg.num_key_value_heads % tp:
        raise ValueError(
            f"tp={tp} must divide the head counts "
            f"({cfg.num_attention_heads} q / {cfg.num_key_value_heads} kv)"
        )
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)

    pooled = owner is not None
    with_embeds = extra_embeds is not None

    mrope = bool(cfg.mrope_section)

    def body(params, kv_k, kv_v, tokens_l, table_l, chunk_l, owner_l,
             prefix_l, prefix_table_l, *mm):
        sp_i = jax.lax.axis_index("sp")
        Bl, Sl = tokens_l.shape
        # the ring starts at each row's prefix boundary (0 with no cache)
        positions = (prefix_l[:, None] + sp_i * Sl
                     + jnp.arange(Sl)[None, :] + jnp.zeros((Bl, 1), jnp.int32))
        rope_pos3 = None
        if mrope:
            # mm rows carry precomputed streams; otherwise text-style
            # (all three streams equal the scalar positions)
            rope_pos3 = (mm[2] if with_embeds and len(mm) > 2
                         else jnp.broadcast_to(positions[:, None, :],
                                               (Bl, 3, Sl)))
        if pooled:
            table_full = chunk_full = prefix_full = None
        else:
            table_full = jax.lax.all_gather(table_l, "dp", axis=0, tiled=True)
            chunk_full = jax.lax.all_gather(chunk_l, "dp", axis=0, tiled=True)
            prefix_full = jax.lax.all_gather(prefix_l, "dp", axis=0, tiled=True)

        x = _embed_sp(params["embed"], tokens_l)
        if with_embeds:
            # the local S slice of embeds/mask lines up with tokens_l
            x = jnp.where(mm[1][..., None], mm[0].astype(x.dtype), x)
        from ..models.llama import _window_xs

        wins = _window_xs(cfg)

        def layer(carry, xs):
            h = carry
            lp, k_pages, v_pages = xs[:3]
            h, (k_pages, v_pages) = _layer_sp(
                lp, (k_pages, v_pages), h, positions, table_full,
                chunk_full, cfg, inv_freq, tp,
                owner_l=owner_l if pooled else None,
                table_l=table_l, chunk_l=chunk_l,
                prefix_l=prefix_l, prefix_full=prefix_full,
                window=xs[3] if wins else None,
                prefix_table_l=prefix_table_l,
                rope_pos3=rope_pos3,
            )
            return h, (k_pages, v_pages)

        x, (k_new, v_new) = jax.lax.scan(
            layer, x, (params["layers"], kv_k, kv_v, *wins)
        )
        # the row's last valid hidden state lives on ONE sp shard: each
        # shard contributes its masked candidate and a psum combines them
        # — an O(h) collective instead of gathering the whole [Bl, S, h]
        last = jnp.maximum(chunk_l - 1, 0)  # global position per row
        owner = (last // Sl) == sp_i  # [Bl]
        local_idx = jnp.clip(last - sp_i * Sl, 0, Sl - 1)
        cand = jnp.take_along_axis(x, local_idx[:, None, None], axis=1)[:, 0]
        x_last = jax.lax.psum(
            jnp.where(owner[:, None], cand, jnp.zeros_like(cand)), "sp"
        ).astype(x.dtype)
        logits = _lm_logits(params, cfg, x_last)  # [Bl, V/tp] (vocab-sharded)
        return logits, k_new, v_new

    pspec = quantize_pspecs(params, param_pspecs(cfg))
    kv_spec = kv_cache_pspec(pool_axes=pool_axes).k
    if owner is None:
        owner = jnp.zeros(tokens.shape[:1], jnp.int32)
    if prefix_lens is None:
        prefix_lens = jnp.zeros(tokens.shape[:1], jnp.int32)
    if prefix_table is None:
        prefix_table = jnp.zeros((tokens.shape[0], 0), jnp.int32)
    mm_args = ()
    mm_specs = ()
    if with_embeds:
        mm_args = (extra_embeds, extra_mask)
        mm_specs = (P("dp", "sp", None), P("dp", "sp"))
        if mrope and mm_positions is not None:
            mm_args += (mm_positions,)
            mm_specs += (P("dp", None, "sp"),)
    logits, k_new, v_new = shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, kv_spec, kv_spec, P("dp", "sp"), P("dp", None),
                  P("dp"), P("dp"), P("dp"), P("dp", None), *mm_specs),
        out_specs=(P("dp", "tp"), kv_spec, kv_spec),
    )(params, kv.k, kv.v, tokens, page_table, chunk_lens, owner,
      prefix_lens, prefix_table, *mm_args)
    return logits, KVCache(k_new, v_new)
