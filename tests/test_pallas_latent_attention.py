"""The Pallas prefill kernel over latent pages against the XLA form
(`ops/latent_attention.py`), in interpret mode on the CPU, at tiny widths:
the two deployments' head counts (64 and 32) by a factor of 8, tiles small
enough that a chunk walks several of them; and the rule that says which
traces take the kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import xla_ledger
from dynamo_tpu.models.config import CacheSpec
from dynamo_tpu.ops import latent_attention as la
from dynamo_tpu.ops import paged_attention as pa
from dynamo_tpu.ops.pallas_latent_attention import (
    latent_query_tile,
    prefill_latent_attention_pallas,
)

PAGE, PE = 16, 32
# a query tile of 64 rows (16 tokens at H 4, 8 at H 8), two pages a streamed
# tile, 32 own keys an inner step: every loop of the kernel runs more than
# once at these sizes
TILES = dict(rows=64, prefix_tile=32, self_tile=32)


def _case(H, S, B, prefix, chunk=None, pages=8, L=1, layer=None, rank=256,
          dtype="float32", **tiles):
    return dict(H=H, S=S, B=B, prefix=prefix, chunk=chunk, pages=pages, L=L,
                layer=layer, rank=rank, dtype=dtype, tiles=tiles or TILES)


CASES = {
    # both head counts, every chunk bucket, one row
    "h8-c16-prefix0": _case(8, 16, 1, [0]),
    "h4-c16-in-tile": _case(4, 16, 1, [40]),
    "h8-c64-whole-tiles": _case(8, 64, 1, [64]),
    "h4-c64-full-table": _case(4, 64, 1, [128]),
    "h8-c128-in-tile": _case(8, 128, 1, [17]),
    "h4-c128-full-table": _case(4, 128, 1, [128]),
    "h4-c512-prefix0": _case(4, 512, 1, [0], self_tile=128, rows=128,
                             prefix_tile=32),
    "h8-c512-in-tile": _case(8, 512, 1, [100], self_tile=128, rows=128,
                             prefix_tile=64),
    # four rows, each its own table, prefix and length
    "h8-c64-b4": _case(8, 64, 4, [0, 40, 64, 128], [64, 3, 17, 48]),
    "h4-c16-b4": _case(4, 16, 4, [128, 1, 0, 33], [16, 16, 0, 5]),
    "h4-c128-b4": _case(4, 128, 4, [32, 0, 100, 128], [128, 1, 77, 100]),
    # a chunk shorter than its bucket: whole query tiles of padding
    "h8-c128-short": _case(8, 128, 1, [48], [9]),
    "h4-c512-short": _case(4, 512, 1, [64], [130], self_tile=128, rows=128,
                           prefix_tile=32),
    # whole pools read at a layer index: 7 layers, and the 8 whose scatter
    # writes flat rows (`paged_attention._layers_would_move_to_sublanes`)
    "h8-c64-pool7": _case(8, 64, 1, [50], L=7, layer=5),
    "h4-c64-pool8": _case(4, 64, 1, [128], L=8, layer=7),
    "h4-c16-pool8-b4": _case(4, 16, 4, [16, 0, 90, 128], [16, 7, 16, 2],
                             L=8, layer=3),
    # a latent of one lane tile (the pool stores two) and of four
    "h4-c64-rank128": _case(4, 64, 1, [40], rank=128),
    "h4-c64-rank512": _case(4, 64, 1, [70], rank=512),
    # bf16 as served; the module's own tile sizes at a short chunk
    "h8-c64-bf16": _case(8, 64, 1, [40], dtype="bfloat16"),
    "h4-c128-bf16-b4": _case(4, 128, 4, [0, 128, 31, 64], [128, 64, 5, 127],
                             dtype="bfloat16"),
    "h8-c16-bf16-pool8": _case(8, 16, 1, [128], L=8, layer=6,
                               dtype="bfloat16"),
    "h4-c64-default-tiles": _case(4, 64, 4, [0, 300, 512, 256],
                                  [64, 64, 10, 33], pages=32, rows=2048,
                                  prefix_tile=512, self_tile=256),
}


def _inputs(c, seed=0):
    dt = jnp.dtype(c["dtype"])
    H, S, B, rank, L = c["H"], c["S"], c["B"], c["rank"], c["L"]
    spec = CacheSpec("latent", 1, rank, PE)
    kd, vd = spec.plane_dims
    P = B * c["pages"] + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def pool(key, dims, width):
        rows = jax.random.normal(key, (L, P, PAGE, width), jnp.float32)
        rows = jnp.pad(rows, [(0, 0)] * 3 + [(0, dims[0] * dims[1] - width)])
        return rows.reshape(L, P, PAGE, *dims).astype(dt)

    k_pool, v_pool = pool(ks[0], kd, PE), pool(ks[1], vd, rank)
    # every row its own pages, in a shuffled order; page 0 is the trash page
    ids = np.random.RandomState(seed).permutation(np.arange(1, P))
    table = jnp.asarray(ids.reshape(B, c["pages"]), jnp.int32)
    q_abs = jax.random.normal(ks[2], (B, S, H, rank), jnp.float32).astype(dt)
    q_pe = jax.random.normal(ks[3], (B, S, H, PE), jnp.float32).astype(dt)
    kpe = jax.random.normal(ks[4], (B, S, PE), jnp.float32).astype(dt)
    lat = jax.random.normal(ks[5], (B, S, rank), jnp.float32).astype(dt)
    prefix = jnp.asarray(c["prefix"], jnp.int32)
    chunk = jnp.asarray(c["chunk"] or [S] * B, jnp.int32)
    return q_abs, q_pe, kpe, lat, k_pool, v_pool, table, prefix, chunk


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_is_the_xla_form(name):
    c = CASES[name]
    q_abs, q_pe, kpe, lat, k_pool, v_pool, table, prefix, chunk = _inputs(c)
    scale = (c["rank"] // 4 + PE) ** -0.5
    layer = c["layer"]
    if layer is None:
        k_pool, v_pool = k_pool[0], v_pool[0]

    @jax.jit
    def both(layer):
        want = la.latent_attention(
            q_abs, q_pe, la.prefill_parts(k_pool, v_pool, kpe, lat, table,
                                          prefix, chunk, layer), scale)
        got = prefill_latent_attention_pallas(
            q_abs, q_pe, kpe, lat, k_pool, v_pool, table, prefix, chunk,
            scale, layer=layer, interpret=True, **c["tiles"])
        return want, got

    # the layer index is a traced scalar, as in the layer loop
    want, got = both(None if layer is None else jnp.int32(layer))
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    tol = 2e-2 if c["dtype"] == "bfloat16" else 2e-5
    for b, n in enumerate(np.asarray(chunk)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=tol,
                                   atol=tol)


def _planes(rank=512, pe=64):
    return CacheSpec("latent", 1, rank, pe).plane_dims


@pytest.mark.parametrize("H,S,want", [(64, 512, 32), (32, 512, 64),
                                      (64, 16, 16), (32, 16, 16),
                                      (128, 64, 16)])
def test_query_tiles_follow_the_head_count(H, S, want):
    assert latent_query_tile(S, H, 512, 64, 16, *_planes(),
                             jnp.bfloat16) == want


def test_a_shape_that_fits_no_tile_stays_off_the_kernel():
    # a chunk whose own rows alone pass the budget
    assert latent_query_tile(1 << 16, 64, 512, 64, 16, *_planes(),
                             jnp.bfloat16) is None
    # a verify step's five tokens; a pool of one-byte values
    assert latent_query_tile(5, 64, 512, 64, 16, *_planes(),
                             jnp.bfloat16) is None
    assert latent_query_tile(64, 64, 512, 64, 16, *_planes(), jnp.bfloat16,
                             jnp.int8) is None
    choice, why = pa._latent_prefill_rule(4, 5, 4096, None)
    assert choice == "xla" and "no query tile" in why


def _noted(site, **has):
    """The path choices noted at `site` whose fields contain `has`."""
    return [c for c in xla_ledger.summary()["path_choices"]
            if c["site"] == site
            and all(v in c[k] for k, v in has.items())]


def _tiny():
    from test_deepseek_v3 import TINY

    from dynamo_tpu.models.config import ModelConfig
    return ModelConfig.from_hf_config(TINY, name="tiny-deepseek-v3")


@pytest.mark.parametrize("batch,chunk,ctx,want", [
    (1, 512, 512, "pallas"), (1, 512, 1024, "pallas"),
    (1, 512, 4096, "pallas"), (1, 256, 2048, "pallas"),
    (1, 128, 2048, "pallas"), (4, 64, 2048, "pallas"),
    (4, 64, 4096, "pallas"), (1, 16, 2048, "pallas"),
    (1, 16, 4096, "pallas"), (1, 256, 1024, "xla"), (1, 128, 512, "xla"),
    (4, 64, 1024, "xla"), (1, 16, 512, "xla")])
def test_the_rule_by_shapes(batch, chunk, ctx, want):
    """The kernel for a 512-token chunk under any table and for every chunk
    from 2048 tokens of table on (where the whole steps crossed on the
    chip); XLA's form below both."""
    choice, why = pa._latent_prefill_rule(batch, chunk, ctx, 16)
    assert choice == want, why
    assert str(ctx) in why and str(chunk) in why


def test_which_traces_note_the_kernel():
    """A latent prefill trace notes its program for its shape at the site
    the step slice's `attn` is read from: forced "xla" says so, adaptive
    notes the rule's answer; every decode trace of a latent model notes
    "xla" and why, whatever was asked."""
    c = _case(4, 16, 1, [40], rank=128)
    q_abs, q_pe, kpe, lat, k_pool, v_pool, table, prefix, chunk = _inputs(c)
    args = (q_abs, q_pe, kpe, lat, k_pool, v_pool, table, prefix, chunk)
    dims = dict(batch=1, chunk=16, table_tokens=128)
    jax.eval_shape(lambda *a: la.prefill_attention(*a, 0.1, impl="xla", layer=0),
                   *args)
    assert xla_ledger.path_choice("prefill_attention", **dims) == "xla"
    got = jax.eval_shape(
        lambda *a: la.prefill_attention(*a, 0.1, "adaptive", 0), *args)
    assert got.shape == q_abs.shape and got.dtype == q_abs.dtype
    choice, why = pa._latent_prefill_rule(1, 16, 128, 16)
    assert xla_ledger.path_choice("prefill_attention", **dims) == choice
    assert _noted("prefill_attention", reason=why, choice=choice)
    # under a table of 2048 tokens the adaptive trace takes the kernel
    wide = _inputs(_case(4, 16, 1, [40], rank=128, pages=128))
    jax.eval_shape(lambda *a: la.prefill_attention(*a, 0.1, "adaptive", 0),
                   *wide)
    assert xla_ledger.path_choice("prefill_attention", batch=1, chunk=16,
                                  table_tokens=2048) == "pallas"
    for impl in ("adaptive", "pallas"):
        assert pa._adapt(impl, jnp.zeros((2, 512), jnp.int32), PAGE,
                         only_xla=pa.LATENT_DECODE_XLA) == "xla"
    assert xla_ledger.path_choice("decode_attention", batch=2, chunk=1,
                                  table_tokens=512 * PAGE) == "xla"
    assert _noted("decode_attention", reason=pa.LATENT_DECODE_XLA)


@pytest.mark.parametrize("asked", ["auto", "pallas", "adaptive"])
def test_a_latent_engine_off_the_chip_keeps_xla_and_says_why(asked):
    """On the CPU a latent model's layout runs "xla" attention whatever was
    asked (the kernel is a TPU program), and no longer refuses "pallas"."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.layout import Layout

    layout = Layout.resolve(_tiny(), EngineConfig(
        page_size=PAGE, num_pages=16, attention_impl=asked))[0]
    assert layout.attn_impl == "xla"
    assert _noted("attention_impl", choice="xla", reason="cpu",
                  dims=f"requested={asked}")


def test_a_latent_decode_trace_runs_xla_under_a_forced_kernel():
    """`decode_layers` of a latent model no longer refuses "pallas": it
    notes XLA's form and why, and traces it."""
    from dynamo_tpu.models import KVCache, init_params
    from dynamo_tpu.models.llama import forward_decode

    cfg = _tiny()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                                jnp.float32))
    kv = jax.eval_shape(lambda: KVCache.create(cfg, 16, PAGE, jnp.float32))
    i32 = jax.ShapeDtypeStruct((3,), jnp.int32)
    table = jax.ShapeDtypeStruct((3, 4), jnp.int32)
    logits, _ = jax.eval_shape(
        lambda p, kv, t, pos, tab: forward_decode(p, cfg, kv, t, pos, tab,
                                                  attn_impl="pallas"),
        params, kv, i32, i32, table)
    assert logits.shape == (3, cfg.vocab_size)
    assert xla_ledger.path_choice("decode_attention", batch=3, chunk=1,
                                  table_tokens=4 * PAGE) == "xla"


def test_a_latent_engine_under_a_mesh_keeps_xla_and_says_why():
    assert pa.resolve_attention_impl("auto", meshed=True) == "xla"
    assert _noted("attention_impl", choice="xla", reason="mesh")
    with pytest.raises(ValueError, match="per-shard"):
        pa.resolve_attention_impl("pallas", meshed=True)
