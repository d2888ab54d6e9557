"""Parallelism on the virtual 8-device CPU mesh: TP-sharded model steps
equal single-device results; ring attention equals full attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import (
    KVCache,
    forward_decode,
    forward_prefill,
    init_params,
    tiny_config,
)
from dynamo_tpu.parallel import (
    ParallelConfig,
    make_mesh,
    ring_attention,
    shard_kv_cache,
    shard_params,
)


@pytest.fixture(scope="module")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, "conftest must force 8 CPU devices"
    return devs


def test_mesh_construction(devices):
    mesh = make_mesh(ParallelConfig(dp=2, tp=4))
    assert mesh.shape == {"dp": 2, "tp": 4}
    # a smaller mesh takes the first dp*tp devices; a larger one cannot be
    assert list(make_mesh(ParallelConfig(dp=3, tp=2)).devices.flat) == (
        devices[:6])
    with pytest.raises(ValueError):
        make_mesh(ParallelConfig(dp=3, tp=4))


def test_tp_sharded_prefill_matches_single_device(devices):
    cfg = tiny_config()  # 4 heads, 2 kv heads → tp=2 divides both
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S, page_size = 2, 16, 8
    pages = S // page_size + 1
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    table = jnp.arange(1, 1 + B * pages, dtype=jnp.int32).reshape(B, pages)
    prefix = jnp.zeros(B, jnp.int32)
    chunk = jnp.full((B,), S, jnp.int32)

    def run(params_in, kv_in):
        logits, kv = forward_prefill(
            params_in, cfg, kv_in, tokens, table, prefix, chunk
        )
        out2, _ = forward_decode(
            params_in, cfg, kv,
            jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.full((B,), S, jnp.int32), table,
        )
        return logits, out2

    kv = KVCache.create(cfg, 1 + B * pages, page_size, jnp.float32)
    ref_logits, ref2 = jax.jit(run)(params, kv)

    mesh = make_mesh(ParallelConfig(dp=4, tp=2), devices)
    with mesh:
        sp = shard_params(params, cfg, mesh)
        skv = shard_kv_cache(KVCache.create(cfg, 1 + B * pages, page_size,
                                            jnp.float32), mesh)
        got_logits, got2 = jax.jit(run)(sp, skv)
    np.testing.assert_allclose(ref_logits, got_logits, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(ref2, got2, rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_full(devices):
    mesh = Mesh(np.array(devices), axis_names=("sp",))
    B, S, H, Hkv, D = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)

    # reference: plain causal attention with GQA
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / np.sqrt(D)
    s = s.reshape(B, H, S, S)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    wg = w.reshape(B, Hkv, g, S, S)
    ref = jnp.einsum("bkgqs,bskd->bqkgd", wg, v).reshape(B, S, H, D)

    got = ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
    np.testing.assert_allclose(ref, got, rtol=2e-4, atol=2e-4)


def test_ring_attention_noncausal(devices):
    mesh = Mesh(np.array(devices), axis_names=("sp",))
    B, S, H, D = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(D)
    ref = jnp.einsum(
        "bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v
    )
    got = ring_attention(q, k, v, mesh, axis_name="sp", causal=False)
    np.testing.assert_allclose(ref, got, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# pipeline parallelism
# --------------------------------------------------------------------------- #


def _mlp_block(lp, h):
    """One residual MLP block (stand-in layer for pipeline tests)."""
    y = jnp.tanh(h @ lp["w1"]) @ lp["w2"]
    return h + y


@pytest.mark.parametrize("stages,microbatches", [(2, 4), (4, 2), (8, 8)])
def test_pipeline_matches_sequential(devices, stages, microbatches):
    """GPipe-scheduled pipeline over the pp axis == sequential layer scan."""
    from dynamo_tpu.parallel import microbatch, pipeline_forward

    L, B, h = 8, 16, 32
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "w1": jax.random.normal(k1, (L, h, h * 2), jnp.float32) * 0.1,
        "w2": jax.random.normal(k2, (L, h * 2, h), jnp.float32) * 0.1,
    }
    x = jax.random.normal(k3, (B, h), jnp.float32)

    def seq(params, x):
        def lay(carry, lp):
            return _mlp_block(lp, carry), None

        out, _ = jax.lax.scan(lay, x, params)
        return out

    want = seq(params, x)

    mesh = Mesh(np.array(jax.devices()).reshape(stages, 8 // stages)[:, 0]
                if stages < 8 else np.array(jax.devices()),
                axis_names=("pp",))
    x_mb = microbatch(x, microbatches)
    got = jax.jit(
        lambda p, xx: pipeline_forward(mesh, _mlp_block, p, xx)
    )(params, x_mb)
    np.testing.assert_allclose(
        np.asarray(got).reshape(B, h), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_pipeline_rejects_bad_microbatch():
    from dynamo_tpu.parallel import microbatch

    with pytest.raises(ValueError):
        microbatch(jnp.zeros((10, 4)), 3)
