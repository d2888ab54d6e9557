"""Model correctness: prefill vs decode consistency, paged KV, MoE.

The key invariant: running a sequence through chunked prefill + decode must
produce the same logits as one full prefill — this is what guarantees
prefix-cache hits, chunked prefill, and disaggregated prefill/decode all
preserve model output.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import (
    KVCache,
    forward_decode,
    forward_prefill,
    init_params,
    tiny_config,
    tiny_moe_config,
)


def make_table(num_seqs, pages_per_seq, start=1):
    """Disjoint page tables (page 0 is the trash page)."""
    ids = np.arange(start, start + num_seqs * pages_per_seq, dtype=np.int32)
    return jnp.asarray(ids.reshape(num_seqs, pages_per_seq))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def full_prefill_logits(cfg, params, tokens):
    """Prefill the whole prompt in one chunk; return last-token logits."""
    B, S = tokens.shape
    page_size = 8
    pages = (S + page_size - 1) // page_size + 1
    kv = KVCache.create(cfg, num_pages=1 + B * pages, page_size=page_size, dtype=jnp.float32)
    table = make_table(B, pages)
    logits, kv = forward_prefill(
        params, cfg, kv, tokens, table,
        jnp.zeros(B, jnp.int32), jnp.full((B,), S, jnp.int32),
    )
    return logits, kv, table


def test_chunked_prefill_matches_full(setup):
    cfg, params = setup
    B, S = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    ref_logits, _, _ = full_prefill_logits(cfg, params, tokens)

    # same prompt in two chunks of 12
    page_size = 8
    pages = (S + page_size - 1) // page_size + 1
    kv = KVCache.create(cfg, num_pages=1 + B * pages, page_size=page_size, dtype=jnp.float32)
    table = make_table(B, pages)
    half = S // 2
    _, kv = forward_prefill(
        params, cfg, kv, tokens[:, :half], table,
        jnp.zeros(B, jnp.int32), jnp.full((B,), half, jnp.int32),
    )
    logits2, kv = forward_prefill(
        params, cfg, kv, tokens[:, half:], table,
        jnp.full((B,), half, jnp.int32), jnp.full((B,), half, jnp.int32),
    )
    np.testing.assert_allclose(ref_logits, logits2, rtol=2e-4, atol=2e-4)


def test_decode_matches_prefill(setup):
    cfg, params = setup
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0, cfg.vocab_size)

    # reference: prefill all S+1 tokens at once
    ref_logits, _, _ = full_prefill_logits(cfg, params, tokens)

    # prefill S then decode token S
    _, kv, table = full_prefill_logits(cfg, params, tokens[:, :S])
    dec_logits, kv = forward_decode(
        params, cfg, kv, tokens[:, S], jnp.full((B,), S, jnp.int32), table
    )
    np.testing.assert_allclose(ref_logits, dec_logits, rtol=2e-4, atol=2e-4)


def test_padding_does_not_leak(setup):
    """Tokens beyond chunk_lens must not affect output (they go to page 0)."""
    cfg, params = setup
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    valid = 10

    logits_a, _, _ = full_prefill_logits(cfg, params, tokens[:, :valid])

    page_size = 8
    pages = (S + page_size - 1) // page_size + 1
    kv = KVCache.create(cfg, num_pages=1 + B * pages, page_size=page_size, dtype=jnp.float32)
    table = make_table(B, pages)
    garbage = jnp.concatenate(
        [tokens[:, :valid], jnp.full((B, S - valid), 7, jnp.int32)], axis=1
    )
    logits_b, _ = forward_prefill(
        params, cfg, kv, garbage, table,
        jnp.zeros(B, jnp.int32), jnp.full((B,), valid, jnp.int32),
    )
    np.testing.assert_allclose(logits_a, logits_b, rtol=2e-4, atol=2e-4)


def test_moe_forward_runs(setup):
    cfg = tiny_moe_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab_size)
    logits, _, _ = full_prefill_logits(cfg, params, tokens)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_moe_dispatch_matches_dense(setup):
    """Capacity-bounded expert dispatch == dense all-experts compute when
    capacity covers every assignment (cf = E/k => C = G, no drops)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.llama import _moe, _moe_dense, init_params

    cfg = tiny_moe_config(moe_impl="capacity", moe_capacity_factor=2.0,
                          moe_group_size=16)
    # cf=2.0 with E=4, k=2: C = ceil(G*2*2/4) = G — capacity can never drop
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0 weights
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, cfg.hidden_size), jnp.float32)

    dense = _moe_dense(lp, x, cfg)
    dispatched = _moe(lp, x, cfg)
    np.testing.assert_allclose(
        np.asarray(dispatched), np.asarray(dense), atol=2e-5, rtol=2e-5
    )

    # tight capacity (cf small): still runs, bounded error on dropped tokens
    tight = dataclasses.replace(cfg, moe_capacity_factor=0.5)
    out = _moe(lp, x, tight)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()

    # the default dropless ragged path must also equal dense — and unlike
    # capacity dispatch it must be batch-composition independent
    ragged_cfg = dataclasses.replace(cfg, moe_impl="ragged")
    ragged = _moe(lp, x, ragged_cfg)
    np.testing.assert_allclose(
        np.asarray(ragged), np.asarray(dense), atol=2e-5, rtol=2e-5
    )
    solo = _moe(lp, x[:1], ragged_cfg)
    np.testing.assert_allclose(
        np.asarray(solo), np.asarray(ragged[:1]), atol=2e-5, rtol=2e-5
    )


def test_moe_dispatch_shards_on_ep_axis(setup):
    """The dispatched MoE under a dp x ep GSPMD mesh computes the same
    result as single-device (XLA inserts the expert all-to-all)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.models.llama import _moe, init_params

    cfg = tiny_moe_config(moe_impl="capacity", moe_capacity_factor=2.0,
                          moe_group_size=16)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 16, cfg.hidden_size), jnp.float32)
    want = _moe(lp, x, cfg)

    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("dp", "ep"))
    lp_sharded = {
        k: jax.device_put(v, NamedSharding(
            mesh, P("ep", None, None) if k in ("w_gate", "w_up", "w_down")
            else P(None, None)))
        for k, v in lp.items() if k in ("router", "w_gate", "w_up", "w_down")
    }
    x_sharded = jax.device_put(x, NamedSharding(mesh, P("dp", None, None)))

    got = jax.jit(lambda l, xx: _moe(l, xx, cfg))(lp_sharded, x_sharded)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_deferred_write_attention_equals_write_first():
    """decode_attention with self_kv (the deferred-write fast path: the
    new token joins as an explicit softmax column, the pool scatter
    happens later) must equal write-first + full-table attention — with
    GQA, sliding windows, and sink logits."""
    from dynamo_tpu.ops.paged_attention import (
        decode_attention,
        write_kv_pages,
    )

    rng = np.random.RandomState(11)
    B, NH, NKV, HD, PAGES, PAGE, W = 3, 8, 2, 16, 17, 4, 3
    k_pages = jnp.asarray(rng.randn(PAGES, PAGE, NKV, HD), jnp.float32)
    v_pages = jnp.asarray(rng.randn(PAGES, PAGE, NKV, HD), jnp.float32)
    table = make_table(B, W)
    q = jnp.asarray(rng.randn(B, NH, HD), jnp.float32)
    k_new = jnp.asarray(rng.randn(B, 1, NKV, HD), jnp.float32)
    v_new = jnp.asarray(rng.randn(B, 1, NKV, HD), jnp.float32)
    positions = jnp.asarray([5, 9, 2], jnp.int32)
    seq_lens = positions + 1
    sink = jnp.asarray(rng.randn(NH), jnp.float32)

    for window, snk in ((None, None), (4, None), (None, sink), (6, sink)):
        kp, vp = write_kv_pages(
            k_pages, v_pages, k_new, v_new, table, positions,
            jnp.ones((B,), jnp.int32))
        want = decode_attention(q, kp, vp, table, seq_lens,
                                window=window, sink=snk)
        got = decode_attention(q, k_pages, v_pages, table, seq_lens,
                               window=window, sink=snk,
                               self_kv=(k_new[:, 0], v_new[:, 0]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"window={window} sink={snk is not None}")


# The decode forward-path feature matrix: every entry must behave
# identically through the per-step path (forward_decode), the
# block-materialized path (decode_block_scan) and the fused verify path
# (forward_verify) — a model feature landing in only one of them is a
# silent-drift CI failure, not a review finding.
FEATURE_CFGS = {
    "plain": lambda: tiny_config(),
    "swa": lambda: tiny_config(sliding_window=8, model_type="mistral"),
    "moe_sinks_windows": lambda: tiny_moe_config(
        attention_sinks=True, sliding_window=8,
        layer_types=("sliding_attention", "full_attention"),
        attention_bias=True, attention_out_bias=True,
        moe_bias=True, moe_act="gpt_oss_glu", model_type="gpt_oss"),
    "mrope": lambda: tiny_config(mrope_section=(2, 3, 3),
                                 attention_bias=True,
                                 model_type="qwen2_vl"),
}


def _prefilled(cfg, params, B=3):
    """Prefill a small ragged batch; returns (tok0, lens, table, kv)."""
    pages_per = 4
    kv = KVCache.create(cfg, 1 + B * pages_per, 8, jnp.float32)
    table = make_table(B, pages_per)
    prompts = jnp.asarray(
        np.random.RandomState(5).randint(1, cfg.vocab_size, (B, 9)),
        jnp.int32)
    lens = jnp.asarray([9, 6, 4], jnp.int32)
    logits, kv = forward_prefill(
        params, cfg, kv, prompts, table,
        jnp.zeros((B,), jnp.int32), lens)
    return jnp.argmax(logits, -1).astype(jnp.int32), lens, table, kv


@pytest.mark.parametrize("feature", sorted(FEATURE_CFGS))
@pytest.mark.parametrize("sampling", ["greedy", "penalized"])
def test_block_scan_equals_per_step_decode(feature, sampling):
    """decode_block_scan (block-materialized KV: one gather, ring
    buffers, one scatter) must match T iterations of the per-step
    forward_decode path exactly — greedy tokens AND the resulting pool
    contents — across the full model-feature matrix (sinks+windows+MoE,
    mrope, SWA) and with frequency/presence penalties in the sampling
    tail.  This is the drift tripwire between the two decode forward
    paths (models/llama.py); the per-step deferred-vs-write-first
    equivalence is pinned separately above."""
    from dynamo_tpu.models.llama import decode_block_scan, forward_decode
    from dynamo_tpu.ops import apply_penalties

    cfg = FEATURE_CFGS[feature]()
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    T, B = 6, 3
    tok0, lens, table, kv_a = _prefilled(cfg, params, B)
    rope_off = (jnp.asarray([0, 3, 11], jnp.int32)
                if cfg.mrope_section else None)
    fp = jnp.asarray([1.5, 0.0, 0.7], jnp.float32)
    pp = jnp.asarray([0.0, 0.9, 0.4], jnp.float32)
    penalized = sampling == "penalized"
    kv_b = KVCache(kv_a.k, kv_a.v)

    # per-step write-first reference (host loop, host-side counts)
    toks_ref, kv_r, tok = [], kv_a, tok0
    counts = np.zeros((B, cfg.vocab_size), np.float32)
    pos = lens
    for _ in range(T):
        lg, kv_r = forward_decode(params, cfg, kv_r, tok, pos, table,
                                  attn_impl="xla", rope_offset=rope_off)
        if penalized:
            lg = apply_penalties(lg, jnp.asarray(counts), fp, pp)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        if penalized:
            counts[np.arange(B), np.asarray(tok)] += 1.0
        toks_ref.append(np.asarray(tok))
        pos = pos + 1

    def sample_step(eng, logits, tok_prev, t):
        cts = eng
        if penalized:
            logits = apply_penalties(logits, cts, fp, pp)
        out = jnp.argmax(logits, -1).astype(jnp.int32)
        if penalized:
            cts = cts.at[jnp.arange(B), out].add(1.0)
        return cts, out, out

    cts0 = (jnp.zeros((B, cfg.vocab_size), jnp.float32) if penalized
            else jnp.zeros(()))
    _, ys, tok_b, pos_b, kv_blk = decode_block_scan(
        params, cfg, kv_b, tok0, lens, table, T,
        max_valid_pos=10_000, sample_step=sample_step, carry_init=cts0,
        rope_offset=rope_off,
    )
    np.testing.assert_array_equal(
        np.asarray(ys), np.stack(toks_ref))
    np.testing.assert_array_equal(np.asarray(tok_b), toks_ref[-1])
    # the two paths sum the same f32 terms in different orders, so the KV
    # they write may differ by reassociation (observed: 1 element of 6656
    # at rel 2.5e-5); the tokens above stay exactly equal
    np.testing.assert_allclose(
        np.asarray(kv_blk.k), np.asarray(kv_r.k), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(kv_blk.v), np.asarray(kv_r.v), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("feature", sorted(FEATURE_CFGS))
@pytest.mark.parametrize("k", [0, 2, 4])
def test_verify_matches_per_step_decode(feature, k):
    """forward_verify (the fused k+1-position draft-verify forward of
    self-speculative decoding, riding the prefill layer path) must
    produce the same per-position logits AND pool contents as feeding
    the identical tokens through k+1 per-step forward_decode calls —
    over the same feature matrix as the block tripwire, including
    off-distribution draft tokens (rejected drafts still score
    identically).  k=0 pins the degenerate single-position chunk."""
    from dynamo_tpu.models.llama import forward_decode, forward_verify

    cfg = FEATURE_CFGS[feature]()
    params = init_params(cfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    B = 3
    tok0, lens, table, kv_a = _prefilled(cfg, params, B)
    rope_off = (jnp.asarray([0, 3, 11], jnp.int32)
                if cfg.mrope_section else None)
    # fed chunk: last sampled token + k arbitrary "draft" tokens
    drafts = jnp.asarray(
        np.random.RandomState(17).randint(1, cfg.vocab_size, (B, k)),
        jnp.int32)
    fed = jnp.concatenate([tok0[:, None], drafts], axis=1)  # [B, k+1]
    kv_b = KVCache(kv_a.k, kv_a.v)

    # per-step reference: feed the same tokens sequentially
    logits_ref, kv_r, pos = [], kv_a, lens
    for j in range(k + 1):
        lg, kv_r = forward_decode(
            params, cfg, kv_r, fed[:, j], pos, table,
            attn_impl="xla", rope_offset=rope_off)
        logits_ref.append(np.asarray(lg))
        pos = pos + 1

    logits_v, kv_v = forward_verify(
        params, cfg, kv_b, fed, table, lens,
        jnp.full((B,), k + 1, jnp.int32), rope_offset=rope_off)
    np.testing.assert_allclose(
        np.asarray(logits_v), np.stack(logits_ref, axis=1),
        rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(kv_v.k), np.asarray(kv_r.k), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(kv_v.v), np.asarray(kv_r.v), rtol=1e-5, atol=1e-6)


# -- the prefill layer loop leaves the pool where it is (ISSUE 26) -------------- #

def _layer_by_layer_prefill(params, cfg, kv, tokens, table, prefix_lens,
                            chunk_lens):
    """The semantics the scan over the pool had, as a plain Python loop:
    layer l attends, then its slab of the pool is rewritten BEFORE layer
    l+1 runs."""
    from dynamo_tpu.models.llama import _layer_prefill, _lm_logits
    from dynamo_tpu.ops import (rope_attention_scale, rope_frequencies,
                                write_kv_pages)

    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta,
                                cfg.rope_scaling)
    positions = prefix_lens[:, None] + jnp.arange(tokens.shape[1])[None, :]
    wins = cfg.layer_windows() if cfg.sliding_window else None
    x = params["embed"][tokens]
    for l in range(cfg.num_hidden_layers):
        lp = jax.tree.map(lambda w: w[l], params["layers"])
        x, (k, v) = _layer_prefill(
            lp, kv, l, x, positions, table, prefix_lens, chunk_lens, cfg,
            inv_freq, window=None if wins is None else jnp.int32(wins[l]),
            rope_scale=rope_attention_scale(cfg.rope_scaling))
        k_l, v_l = write_kv_pages(kv.k[l], kv.v[l], k, v, table,
                                  prefix_lens, chunk_lens)
        kv = KVCache(kv.k.at[l].set(k_l), kv.v.at[l].set(v_l))
    last = jnp.maximum(chunk_lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return _lm_logits(params, cfg, x_last), kv


@pytest.mark.parametrize("feature",
                         ["plain", "swa", "moe_sinks_windows"])
def test_prefill_loop_equals_layer_by_layer_writes(feature):
    """Chunked prefill through `prefill_layers` (pool read by (layer,
    page) inside the scan, ONE scatter after it) against a reference that
    writes layer by layer: same logits, same pool.  Two rows with
    different prefix and chunk lengths; row 0's padding goes to trash
    page 0 and nowhere else."""
    cfg = FEATURE_CFGS[feature]()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    B, S, page, pages_per = 2, 8, 4, 5
    P = 1 + B * pages_per + 2  # two pages no table names
    table = make_table(B, pages_per)
    toks = jnp.asarray(np.random.RandomState(9).randint(
        1, cfg.vocab_size, (2, B, S)), jnp.int32)
    chunks = [  # (prefix_lens, chunk_lens) of two consecutive chunks
        (jnp.asarray([0, 0], jnp.int32), jnp.asarray([7, 3], jnp.int32)),
        (jnp.asarray([7, 3], jnp.int32), jnp.asarray([5, 8], jnp.int32)),
    ]
    kv_a = KVCache.create(cfg, P, page, jnp.float32)
    kv_b = KVCache(kv_a.k, kv_a.v)
    for t, (pre, cl) in zip(toks, chunks):
        logits_a, kv_a = forward_prefill(params, cfg, kv_a, t, table, pre, cl)
        logits_b, kv_b = _layer_by_layer_prefill(params, cfg, kv_b, t, table,
                                                 pre, cl)
        np.testing.assert_allclose(logits_a, logits_b, rtol=1e-5, atol=1e-5)
    for a, b in zip(kv_a, kv_b):
        a, b = np.asarray(a), np.asarray(b)
        # page 0 takes the padding (duplicate slots may land in any order)
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], rtol=1e-6, atol=1e-6)
        assert np.abs(a[:, 0]).max() > 0  # padding did land in the trash
        assert not a[:, -2:].any()  # pages of no table stay untouched
        # row 0 holds 12 tokens and row 1 holds 11: nothing past them
        flat = a.reshape(a.shape[0], P * page, *a.shape[3:])
        for row, n in ((0, 12), (1, 11)):
            first = int(table[row, 0]) * page
            assert np.abs(flat[:, first:first + n]).min(axis=(-1, -2)).all()
            assert not flat[:, first + n:first + pages_per * page].any()


def test_prefill_loop_does_not_scan_the_pool():
    """In the jaxpr of a prefill the layer scan may CLOSE over the pool
    (a read-only constant of the loop) but neither scans it, carries it nor
    returns anything of its size: no xs, carry or ys has the page-count
    dimension.  The pool's only writer is the scatter after the loop."""
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S, P, page = 2, 8, 37, 4  # 37: no other dimension has that size
    kv = KVCache.create(cfg, P, page, jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda kv, t: forward_prefill(
            params, cfg, kv, t, make_table(B, 3), jnp.zeros(B, jnp.int32),
            jnp.full((B,), S, jnp.int32)))(kv, jnp.zeros((B, S), jnp.int32))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    (scan,) = scans
    moving = scan.invars[scan.params["num_consts"]:] + scan.outvars
    assert all(P not in v.aval.shape for v in moving), [
        v.aval for v in moving if P in v.aval.shape]
    # the scan does read the pool: it is among the loop's constants
    assert any(v.aval.shape == kv.k.shape
               for v in scan.invars[:scan.params["num_consts"]])
    # and the one thing written back is a scatter outside the loop
    assert [e.primitive.name for e in jaxpr.jaxpr.eqns].count("scatter") == 2
