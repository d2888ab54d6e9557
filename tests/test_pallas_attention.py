"""Pallas paged-attention kernels vs the XLA einsum path.

Runs the kernels in interpret mode on the CPU test platform (conftest
forces jax_platforms=cpu) and checks numerical equivalence against
ops.paged_attention's reference implementation on ragged batches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.paged_attention import (
    decode_attention,
    prefill_attention,
    write_kv_pages,
)
from dynamo_tpu.ops import pallas_attention
from dynamo_tpu.ops.pallas_attention import (
    _vmem_bytes,
    decode_attention_pallas,
    prefill_attention_pallas,
    prefill_query_block,
    prefill_resident_bytes,
)


def _make_pool(key, P, page, n_kv, hd, dtype):
    k1, k2 = jax.random.split(key)
    k_pages = (jax.random.normal(k1, (P, page, n_kv, hd), jnp.float32) * 0.3).astype(dtype)
    v_pages = (jax.random.normal(k2, (P, page, n_kv, hd), jnp.float32) * 0.3).astype(dtype)
    return k_pages, v_pages


def _page_table(B, maxp, seq_lens, page):
    """Distinct live pages per row; unused entries point at trash page 0."""
    table = np.zeros((B, maxp), np.int32)
    nxt = 1
    for b in range(B):
        used = -(-int(seq_lens[b]) // page)
        for i in range(used):
            table[b, i] = nxt
            nxt += 1
    return jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_matches_xla(dtype):
    B, H, n_kv, hd, page, maxp = 4, 8, 2, 64, 16, 20
    seq_lens = jnp.array([1, 17, 100, 320 - 1], jnp.int32)
    P = 1 + int(sum(-(-int(s) // page) for s in seq_lens))
    key = jax.random.PRNGKey(0)
    k_pages, v_pages = _make_pool(key, P, page, n_kv, hd, dtype)
    table = _page_table(B, maxp, seq_lens, page)
    q = (jax.random.normal(jax.random.PRNGKey(7), (B, H, hd), jnp.float32) * 0.5).astype(dtype)

    ref = decode_attention(q, k_pages, v_pages, table, seq_lens)
    out = decode_attention_pallas(
        q, k_pages, v_pages, table, seq_lens, interpret=True
    )
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


# (query heads, KV heads, head_dim): the folded kernel's geometries. 28/4 and
# 32/2 fold 7 and 16 query heads into one product, 8/8 folds nothing, hd 64
# reads the slab view (a head is no whole lane tile: not `as_stored`)
GEOMETRIES = [(8, 4, 64), (28, 4, 128), (32, 2, 128), (8, 8, 128), (8, 2, 64)]
GEOMETRY_IDS = ["8/4/64", "28/4/128", "32/2/128", "8/8/128", "8/2/64"]


def _assert_rows_close(out, ref, chunk_lens, tol):
    """Rows past chunk_len attend to garbage in both forms: compare the
    valid ones."""
    for b, n in enumerate(np.asarray(chunk_lens)):
        np.testing.assert_allclose(
            np.asarray(out[b, :n], np.float32),
            np.asarray(ref[b, :n], np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("geometry,dtype", [
    *((g, jnp.float32) for g in GEOMETRIES),
    ((28, 4, 128), jnp.bfloat16), ((8, 2, 64), jnp.bfloat16),
], ids=[*GEOMETRY_IDS, "28/4/128-bf16", "8/2/64-bf16"])
@pytest.mark.parametrize("prefix", [0, 48])
def test_prefill_matches_xla(prefix, geometry, dtype):
    """Chunked prefill: rows with and without a cached prefix, ragged
    chunk lengths, every geometry the kernel folds, a float32 and a bf16
    pool (the chunk in the pool's dtype)."""
    H, n_kv, hd = geometry
    B, page, maxp, S = 3, 16, 12, 64
    prefix_lens = jnp.array([prefix, 0, max(prefix - 16, 0)], jnp.int32)
    chunk_lens = jnp.array([S, S - 13, 1], jnp.int32)
    P = 1 + B * maxp
    key = jax.random.PRNGKey(1)
    k_pages, v_pages = _make_pool(key, P, page, n_kv, hd, dtype)
    table = _page_table(B, maxp, jnp.full((B,), maxp * page), page)

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = (jax.random.normal(ks[0], (B, S, H, hd)) * 0.5).astype(dtype)
    k_new = (jax.random.normal(ks[1], (B, S, n_kv, hd)) * 0.3).astype(dtype)
    v_new = (jax.random.normal(ks[2], (B, S, n_kv, hd)) * 0.3).astype(dtype)

    ref = prefill_attention(
        q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens
    )
    out = prefill_attention_pallas(
        q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens,
        interpret=True,
    )
    _assert_rows_close(out, ref, chunk_lens,
                       2e-5 if dtype == jnp.float32 else 2e-2)


def test_decode_under_jit_and_scan():
    """The engine calls the kernel inside lax.scan inside jit — make sure
    that composes (interpret mode)."""
    B, H, n_kv, hd, page, maxp, L = 2, 4, 2, 64, 16, 4, 3
    seq_lens = jnp.array([5, 33], jnp.int32)
    P = 8
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(2), P, page, n_kv, hd, jnp.float32)
    table = _page_table(B, maxp, seq_lens, page)
    q = jax.random.normal(jax.random.PRNGKey(5), (L, B, H, hd), jnp.float32)

    @jax.jit
    def run(q_all):
        def body(_, qt):
            out = decode_attention_pallas(
                qt, k_pages, v_pages, table, seq_lens, interpret=True
            )
            return None, out

        _, outs = jax.lax.scan(body, None, q_all)
        return outs

    outs = run(q)
    for i in range(L):
        ref = decode_attention(q[i], k_pages, v_pages, table, seq_lens)
        np.testing.assert_allclose(
            np.asarray(outs[i]), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("window", [8, 64, 1000])
def test_decode_windowed_matches_xla(window):
    """Sliding-window decode: the kernel's chunk-grid remapping (skip
    chunks before seq_len - window) must equal the XLA masked path,
    including window >= context (full attention)."""
    B, H, n_kv, hd, page, maxp = 4, 8, 2, 64, 16, 20
    seq_lens = jnp.array([1, 17, 100, 320 - 1], jnp.int32)
    P = 1 + int(sum(-(-int(s) // page) for s in seq_lens))
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(0), P, page, n_kv, hd,
                                  jnp.float32)
    table = _page_table(B, maxp, seq_lens, page)
    q = jax.random.normal(jax.random.PRNGKey(7), (B, H, hd), jnp.float32) * 0.5

    ref = decode_attention(q, k_pages, v_pages, table, seq_lens,
                           window=jnp.int32(window))
    out = decode_attention_pallas(
        q, k_pages, v_pages, table, seq_lens, window=jnp.int32(window),
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("window", [8, 40, 1000])
def test_prefill_windowed_matches_xla(window):
    """Sliding-window chunked prefill: per-row window over the streamed
    prefix (global positions) + within-chunk band, vs the XLA mask."""
    B, H, n_kv, hd, page, maxp, S = 3, 8, 4, 64, 16, 12, 64
    prefix_lens = jnp.array([48, 0, 32], jnp.int32)
    chunk_lens = jnp.array([S, S - 13, 1], jnp.int32)
    P = 1 + B * maxp
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(1), P, page, n_kv, hd,
                                  jnp.float32)
    table = _page_table(B, maxp, jnp.full((B,), maxp * page), page)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    k_new = jax.random.normal(ks[1], (B, S, n_kv, hd), jnp.float32) * 0.3
    v_new = jax.random.normal(ks[2], (B, S, n_kv, hd), jnp.float32) * 0.3

    ref = prefill_attention(
        q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens,
        window=jnp.int32(window),
    )
    out = prefill_attention_pallas(
        q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens,
        window=jnp.int32(window), interpret=True,
    )
    for b in range(B):
        n = int(chunk_lens[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n], np.float32),
            np.asarray(ref[b, :n], np.float32),
            atol=2e-5, rtol=2e-5,
        )


@pytest.mark.parametrize("geometry", [(8, 4, 64), (28, 4, 128), (32, 2, 128)],
                         ids=["8/4/64", "28/4/128", "32/2/128"])
def test_prefill_windowed_remap_skips_leading_chunks(geometry):
    """Exercise the prefill kernel's prefix-tile REMAP (first > 0): a long
    cached prefix with a small window must skip whole leading tiles and
    still match the XLA mask.  Tolerance is looser: flash accumulation
    vs one-shot einsum differ by f32 noise (~3e-4), masks are exact."""
    H, n_kv, hd = geometry
    B, page, S = 2, 16, 64
    maxp = 40  # 640 tokens >= prefix + chunk
    prefix_lens = jnp.array([520, 200], jnp.int32)  # first = 1 at window 64
    chunk_lens = jnp.array([S, S - 7], jnp.int32)
    P = 1 + B * maxp
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(5), P, page, n_kv, hd,
                                  jnp.float32)
    table = _page_table(B, maxp, jnp.full((B,), maxp * page), page)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    k_new = jax.random.normal(ks[1], (B, S, n_kv, hd), jnp.float32) * 0.3
    v_new = jax.random.normal(ks[2], (B, S, n_kv, hd), jnp.float32) * 0.3

    for window in (64, 1):  # window=1 also hits the zero-prefix-tile DMA guard
        ref = prefill_attention(
            q, k_new, v_new, k_pages, v_pages, table, prefix_lens,
            chunk_lens, window=jnp.int32(window),
        )
        out = prefill_attention_pallas(
            q, k_new, v_new, k_pages, v_pages, table, prefix_lens,
            chunk_lens, window=jnp.int32(window), interpret=True,
        )
        _assert_rows_close(out, ref, chunk_lens, 5e-4)


@pytest.mark.parametrize("geometry", [(8, 2, 64), (28, 4, 128)],
                         ids=["8/2/64", "28/4/128"])
def test_sinks_match_xla(geometry):
    """Attention-sink logits in the kernels (denominator-only virtual
    key, folded into the flash finalization) vs the XLA sink softmax —
    decode and windowed prefill."""
    H, n_kv, hd = geometry
    B, page, maxp = 3, 16, 12
    sink = jnp.linspace(-2.0, 3.0, H).astype(jnp.float32)

    seq_lens = jnp.array([5, 60, 150], jnp.int32)
    P = 1 + int(sum(-(-int(s) // page) for s in seq_lens))
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(9), P, page, n_kv, hd,
                                  jnp.float32)
    table = _page_table(B, maxp, seq_lens, page)
    q = jax.random.normal(jax.random.PRNGKey(10), (B, H, hd), jnp.float32) * 0.5
    ref = decode_attention(q, k_pages, v_pages, table, seq_lens, sink=sink)
    out = decode_attention_pallas(
        q, k_pages, v_pages, table, seq_lens, sink=sink, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    S = 64
    prefix_lens = jnp.array([48, 0, 96], jnp.int32)
    chunk_lens = jnp.array([S, S - 9, 3], jnp.int32)
    P2 = 1 + B * maxp
    k2, v2 = _make_pool(jax.random.PRNGKey(11), P2, page, n_kv, hd, jnp.float32)
    table2 = _page_table(B, maxp, jnp.full((B,), maxp * page), page)
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    qp = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    kn = jax.random.normal(ks[1], (B, S, n_kv, hd), jnp.float32) * 0.3
    vn = jax.random.normal(ks[2], (B, S, n_kv, hd), jnp.float32) * 0.3
    for window in (None, jnp.int32(16)):
        ref = prefill_attention(qp, kn, vn, k2, v2, table2, prefix_lens,
                                chunk_lens, window=window, sink=sink)
        out = prefill_attention_pallas(
            qp, kn, vn, k2, v2, table2, prefix_lens, chunk_lens,
            window=window, sink=sink, interpret=True,
        )
        for b in range(B):
            n = int(chunk_lens[b])
            np.testing.assert_allclose(
                np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
                atol=2e-5, rtol=2e-5,
            )


def _force_query_block(monkeypatch, QB, *geom):
    """Make `prefill_query_block` choose QB for this geometry: the budget
    becomes exactly what QB's residents take, so any larger block is over
    it (no switch in the kernel: the budget is what sizes it)."""
    monkeypatch.setattr(pallas_attention, "_PREFILL_VMEM_BUDGET",
                        prefill_resident_bytes(QB, *geom))
    assert prefill_query_block(*geom) == QB


@pytest.mark.parametrize("S,QB,prefix,hd,window,sinks,heads", [
    (256, None, 160, 64, None, False, (4, 2)),
    (256, None, 160, 64, 40, True, (4, 2)),
    (256, None, 160, 64, 200, False, (4, 2)),
    # across QUERY blocks: the chunk's grid axis, the prefix streamed once
    # per block, the causal loop reaching back into earlier blocks' keys
    (512, 256, 0, 128, None, False, (4, 2)),
    (512, 256, 500, 128, None, False, (4, 2)),
    (512, 256, 1536, 128, None, False, (4, 2)),
    (512, 128, 500, 128, None, False, (4, 2)),
    (512, 128, 1536, 128, 40, True, (4, 2)),
    (512, 256, 500, 128, 40, True, (4, 2)),
    (512, 256, 500, 64, 40, True, (4, 2)),
    (512, 128, 0, 64, None, False, (4, 2)),
    # the cells' folds (7 and 16 query heads a KV head), no fold, and the
    # slab view, a query block smaller than the chunk; one block of 128
    (512, 256, 500, 128, None, False, (28, 4)),
    (512, 128, 300, 128, 40, True, (32, 2)),
    (512, 256, 500, 128, None, False, (8, 8)),
    (512, 128, 500, 64, None, True, (8, 2)),
    (128, None, 300, 128, None, False, (28, 4)),
    (128, None, 300, 128, 40, True, (32, 2)),
])
def test_prefill_row_blocks_match_xla(monkeypatch, S, QB, prefix, hd, window,
                                      sinks, heads):
    """A chunk longer than one key tile of itself (S = 256 or 512): the
    causal key-tile loop under the diagonal and the finalize, with a prefix
    spanning several streamed tiles, a window that masks whole key tiles,
    and sinks.  With QB the chunk also crosses query blocks (two of 256,
    four of 128), one row of the batch shorter than a query block (its
    later blocks are skipped), heads fetched as stored (hd 128) and through
    the slab view (hd 64)."""
    (H, n_kv), B, page = heads, 2, 16
    maxp = (prefix + S) // page + 2
    prefix_lens = jnp.array([prefix, prefix // 3], jnp.int32)
    chunk_lens = jnp.array([S, S - 37] if QB is None else [S, 100],
                           jnp.int32)
    P = 1 + B * maxp
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(5), P, page, n_kv, hd,
                                  jnp.float32)
    table = _page_table(B, maxp, jnp.full((B,), maxp * page), page)
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    k_new = jax.random.normal(ks[1], (B, S, n_kv, hd), jnp.float32) * 0.3
    v_new = jax.random.normal(ks[2], (B, S, n_kv, hd), jnp.float32) * 0.3
    kw = {}
    if window is not None:
        kw["window"] = jnp.int32(window)
    if sinks:
        kw["sink"] = jax.random.normal(ks[3], (H,), jnp.float32)
    if QB is not None:
        _force_query_block(monkeypatch, QB, S, H, n_kv, hd, page,
                           jnp.float32)

    ref = prefill_attention(
        q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens,
        **kw,
    )
    out = prefill_attention_pallas(
        q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens,
        interpret=True, **kw,
    )
    _assert_rows_close(out, ref, chunk_lens, 2e-5)


def _call_residents(S, H, n_kv, hd, page, dtype):
    """What the `pallas_call` the wrapper really builds holds in VMEM:
    (grid, bytes of every blocked operand twice and every VMEM scratch
    once, the shape of the widest score tile its body computes)."""
    B, maxp = 1, 8
    q = jnp.zeros((B, S, H, hd), dtype)
    new = jnp.zeros((B, S, n_kv, hd), dtype)
    pool = jnp.zeros((4, page, n_kv, hd), dtype)
    table = jnp.zeros((B, maxp), jnp.int32)
    lens = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda: prefill_attention_pallas(
        q, new, new, pool, pool, table, lens, lens, interpret=True))()
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    blocked = [bm.block_aval for bm in gm.block_mappings
               if "<any>" not in str(bm.block_aval)]
    scratch = [v.aval for v in call.params["jaxpr"].invars[
        -gm.num_scratch_operands:] if "<vmem>" in str(v.aval)]
    assert len(blocked) == 5 and len(scratch) == 6
    scores = max((e.outvars[0].aval.shape for e in _eqns(call.params["jaxpr"])
                  if e.primitive.name == "dot_general"
                  and e.outvars[0].aval.dtype == jnp.float32),
                 key=lambda shape: shape[0] * shape[1])
    return gm.grid, (
        2 * sum(_vmem_bytes(a.shape, a.dtype) for a in blocked)
        + sum(_vmem_bytes(a.shape, a.dtype) for a in scratch)), scores


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs (loops,
    conditionals) among them."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("H,n_kv,hd,dtype", [
    (28, 4, 128, jnp.bfloat16),  # the benchmark cells' attention
    (32, 2, 128, jnp.bfloat16),  # Nemotron-3-Nano's: 16 query heads a KV head
    (32, 8, 128, jnp.bfloat16),  # Llama-3.1-8B
    (32, 8, 64, jnp.bfloat16),  # Llama-3.2-1B: not fetched as stored
    (4, 2, 64, jnp.float32),
], ids=["qwen7b", "nemotron3", "llama8b", "llama1b", "tiny-f32"])
def test_prefill_query_block_sizes_what_the_call_holds(H, n_kv, hd, dtype):
    """ONE function sizes the kernel: for every chunk bucket it returns a
    divisor of the chunk (a multiple of the row block where the chunk is
    one), its byte count IS the blocked operands (twice: the pipeline's
    second buffer) and VMEM scratch of the call the wrapper builds from
    it plus three float32 tiles of the widest score product that call's
    body traces (scores, exponentials, cast probabilities), that count is
    within the budget, and the next larger block is over it or folds to
    more rows than a product may span."""
    page = 16
    groups = H // n_kv
    geom = lambda S: (S, H, n_kv, hd, page, dtype)  # noqa: E731
    for S in (16, 32, 64, 128, 256, 512):
        QB = prefill_query_block(*geom(S))
        assert QB is not None and S % QB == 0
        assert QB == S or QB % pallas_attention._PREFILL_ROW_BLOCK == 0
        grid, held, scores = _call_residents(*geom(S))
        assert grid[1] == S // QB
        # the state is lane-dense: one folded row a lane, a head on whole
        # lane tiles
        assert scores[1] == -(-groups * QB // 128) * 128
        held += 3 * _vmem_bytes(scores, jnp.float32)
        assert held == prefill_resident_bytes(QB, *geom(S))
        assert held <= pallas_attention._PREFILL_VMEM_BUDGET
        larger = [q for q in range(QB + 128, S + 1, 128) if S % q == 0]
        assert all(prefill_resident_bytes(q, *geom(S))
                   > pallas_attention._PREFILL_VMEM_BUDGET
                   or groups * q > pallas_attention._PREFILL_QUERY_ROWS
                   for q in larger)
    # the cells' 512-token chunk runs as one block; 16 query heads a KV
    # head fold 256 tokens into as many rows
    if (H, n_kv, hd) == (28, 4, 128):
        assert prefill_query_block(*geom(512)) == 512
    if (H, n_kv, hd) == (32, 2, 128):
        assert prefill_query_block(*geom(512)) == 256


def _kernel_eqns(H, n_kv, hd=128, S=256):
    """(equations, products) traced for the prefill kernel's body."""
    q = jnp.zeros((1, S, H, hd), jnp.bfloat16)
    new = jnp.zeros((1, S, n_kv, hd), jnp.bfloat16)
    pool = jnp.zeros((4, 16, n_kv, hd), jnp.bfloat16)
    table = jnp.zeros((1, 32), jnp.int32)
    lens = jnp.zeros((1,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda: prefill_attention_pallas(
        q, new, new, pool, pool, table, lens, lens, interpret=True))()
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    eqns = list(_eqns(call.params["jaxpr"]))
    return len(eqns), sum(e.primitive.name == "dot_general" for e in eqns)


def test_prefill_kernel_body_does_not_grow_with_the_query_heads():
    """The query heads of a KV head are rows of ONE product: the traced
    body holds two products (scores, values) a KV head for the prefix and
    two for the chunk itself whatever the number of query heads, and what
    a further query head adds is its fold into the query tile and its way
    back out (a few copies a query block, no product, no softmax), so
    nobody can quietly unroll the heads again."""
    n28, dots28 = _kernel_eqns(28, 4)
    n56, dots56 = _kernel_eqns(56, 4)
    assert dots28 == dots56 == 4 * 4
    assert _kernel_eqns(28, 2)[1] == 4 * 2
    per_head = (n56 - n28) / 28
    assert per_head <= 16, per_head
    # the softmax bodies: what is left once the per-head copies are taken
    # off is the same at both head counts
    assert n56 - 56 * per_head == pytest.approx(n28 - 28 * per_head)


def test_adaptive_falls_back_only_where_no_query_block_fits(monkeypatch):
    """`_adapt` asks the sizing function whether ANY block fits: under a
    budget that holds no 128-row block a 512-token chunk is traced into
    XLA attention with that reason; under the real one it is not."""
    from dynamo_tpu.analysis import xla_ledger

    B, S, H, n_kv, hd, page, maxp = 1, 512, 4, 2, 64, 16, 128
    q = jnp.zeros((B, S, H, hd), jnp.float32)
    new = jnp.zeros((B, S, n_kv, hd), jnp.float32)
    pool = jnp.zeros((4, page, n_kv, hd), jnp.float32)
    table = jnp.zeros((B, maxp), jnp.int32)
    lens = jnp.zeros((B,), jnp.int32)
    dims = dict(batch=B, chunk=S, table_tokens=maxp * page)

    def trace():
        jax.make_jaxpr(lambda: prefill_attention(
            q, new, new, pool, pool, table, lens, lens, impl="adaptive"))()
        return xla_ledger.path_choice("prefill_attention", **dims)

    assert trace() == "pallas"
    monkeypatch.setattr(pallas_attention, "_PREFILL_VMEM_BUDGET", 1 << 16)
    assert prefill_query_block(S, H, n_kv, hd, page, jnp.float32) is None
    assert trace() == "xla"
    (why,) = [c["reason"] for c in xla_ledger.summary()["path_choices"]
              if c["site"] == "prefill_attention" and c["choice"] == "xla"
              and c["dims"] == ",".join(
                  f"{k}={v}" for k, v in sorted(dims.items()))]
    assert "no query block" in why


@pytest.mark.parametrize("window,sinks", [(None, False), (40, True)],
                         ids=["plain", "window-sinks"])
def test_prefill_reads_the_whole_pool_by_layer_and_page(window, sinks):
    """The layer loops hand the kernel every layer's pool [L, P, ...] and
    a TRACED layer index: it must fetch page `pid` of that layer itself
    (pages of a layer other than 0, inside jit, as the scan calls it) and
    equal the XLA path's (layer, page) gather AND the per-slab kernel."""
    L, layer = 3, 2
    B, H, n_kv, hd, page, S, maxp = 2, 4, 2, 64, 16, 32, 10
    prefix_lens = jnp.array([130, 5], jnp.int32)  # two streamed chunks, one
    chunk_lens = jnp.array([S, S - 9], jnp.int32)
    P = 1 + B * maxp
    pools = [_make_pool(jax.random.PRNGKey(20 + i), P, page, n_kv, hd,
                        jnp.float32) for i in range(L)]
    k_pool = jnp.stack([k for k, _ in pools])
    v_pool = jnp.stack([v for _, v in pools])
    table = _page_table(B, maxp, jnp.full((B,), maxp * page), page)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    k_new = jax.random.normal(ks[1], (B, S, n_kv, hd), jnp.float32) * 0.3
    v_new = jax.random.normal(ks[2], (B, S, n_kv, hd), jnp.float32) * 0.3
    kw = {}
    if window is not None:
        kw["window"] = jnp.int32(window)
    if sinks:
        kw["sink"] = jax.random.normal(ks[3], (H,), jnp.float32)

    @jax.jit
    def both(lyr):
        return (
            prefill_attention(q, k_new, v_new, k_pool, v_pool, table,
                              prefix_lens, chunk_lens, layer=lyr, **kw),
            prefill_attention_pallas(q, k_new, v_new, k_pool, v_pool, table,
                                     prefix_lens, chunk_lens, layer=lyr,
                                     interpret=True, **kw),
        )

    ref, out = both(jnp.int32(layer))
    slab = prefill_attention_pallas(
        q, k_new, v_new, k_pool[layer], v_pool[layer], table, prefix_lens,
        chunk_lens, interpret=True, **kw)
    other = prefill_attention(q, k_new, v_new, k_pool[0], v_pool[0], table,
                              prefix_lens, chunk_lens, **kw)
    for b in range(B):
        n = int(chunk_lens[b])
        np.testing.assert_allclose(np.asarray(out[b, :n]),
                                   np.asarray(ref[b, :n]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(out[b, :n]),
                                      np.asarray(slab[b, :n]))
        # and it IS that layer's pages: layer 0's give another answer
        assert np.abs(np.asarray(ref[b, :n] - other[b, :n])).max() > 1e-3
