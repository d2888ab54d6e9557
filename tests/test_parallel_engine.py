"""Sharded serving engine: a dp×tp-meshed JaxEngine must produce the same
greedy tokens as the single-device engine (the reference gets TP from vLLM's
`tensor_parallel_size`, /root/reference/components/src/dynamo/vllm/args.py:250;
here the engine itself shards over the serving mesh, SURVEY.md §7 M3)."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config, tiny_moe_config
from dynamo_tpu.parallel import ParallelConfig


def _ecfg(**over):
    base = dict(
        page_size=8,
        num_pages=128,
        max_num_seqs=8,
        max_prefill_tokens=32,
        max_model_len=128,
    )
    base.update(over)
    return EngineConfig(**base)


async def _collect(engine, prompts, max_tokens=8):
    async def one(p):
        req = {
            "token_ids": p,
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        }
        toks = []
        async for out in engine.generate(req):
            toks += out["token_ids"]
        return toks

    return await asyncio.gather(*[one(p) for p in prompts])


def _prompts(cfg, n=5):
    out = [[(i * 13 + j) % cfg.vocab_size for j in range(5 + 3 * i)]
           for i in range(n)]
    # one long prompt exercises chunked prefill (> max_prefill_tokens)
    out.append([(j * 7) % cfg.vocab_size for j in range(70)])
    return out


async def test_engine_dp_tp_greedy_matches_single_device():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompts = _prompts(cfg)

    ref = JaxEngine(cfg, params, _ecfg(), kv_dtype=jnp.float32)
    out_ref = await _collect(ref, prompts)
    await ref.shutdown()

    par = JaxEngine(
        cfg, params, _ecfg(), kv_dtype=jnp.float32,
        parallel=ParallelConfig(dp=4, tp=2),
    )
    out_par = await _collect(par, prompts)
    await par.shutdown()

    assert out_par == out_ref


async def test_engine_dp_only_matches():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    prompts = _prompts(cfg, n=3)

    ref = JaxEngine(cfg, params, _ecfg(), kv_dtype=jnp.float32)
    out_ref = await _collect(ref, prompts)
    await ref.shutdown()

    par = JaxEngine(
        cfg, params, _ecfg(), kv_dtype=jnp.float32,
        parallel=ParallelConfig(dp=8, tp=1),
    )
    out_par = await _collect(par, prompts)
    await par.shutdown()

    assert out_par == out_ref


async def test_engine_moe_ep_sharded():
    """MoE engine on the mesh: experts shard over the tp axis (EP)."""
    cfg = tiny_moe_config()
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    prompts = _prompts(cfg, n=3)

    ref = JaxEngine(cfg, params, _ecfg(), kv_dtype=jnp.float32)
    out_ref = await _collect(ref, prompts)
    await ref.shutdown()

    par = JaxEngine(
        cfg, params, _ecfg(), kv_dtype=jnp.float32,
        parallel=ParallelConfig(dp=4, tp=2),
    )
    out_par = await _collect(par, prompts)
    await par.shutdown()

    assert out_par == out_ref


async def test_engine_sp_sequence_parallel_prefill():
    """sp engine: whole-prompt ring-attention prefill over a dp×sp mesh,
    greedy continuation identical to single-device (the sequence-parallel
    serving path the reference lacks entirely, SURVEY.md §2.6)."""
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    prompts = _prompts(cfg, n=3)

    def ecfg():
        return _ecfg(
            enable_prefix_caching=False,
            max_prefill_tokens=256,
            max_model_len=256,
        )

    ref = JaxEngine(cfg, params, ecfg(), kv_dtype=jnp.float32)
    out_ref = await _collect(ref, prompts)
    await ref.shutdown()

    par = JaxEngine(
        cfg, params, ecfg(), kv_dtype=jnp.float32,
        parallel=ParallelConfig(dp=2, sp=4),
    )
    assert par.layout.sp == 4
    out_par = await _collect(par, prompts)
    await par.shutdown()

    assert out_par == out_ref


def test_engine_sp_validation():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    # sp + prefix caching is supported (the ring starts at the prefix
    # boundary) — EXCEPT with a partitioned pool, whose prefix pages are
    # owner-shard-local
    with pytest.raises(ValueError, match="prefix_caching"):
        JaxEngine(
            cfg, params,
            _ecfg(enable_prefix_caching=True, max_prefill_tokens=256,
                  max_model_len=256, kv_partition=True),
            parallel=ParallelConfig(dp=2, sp=4),
        )
    with pytest.raises(ValueError, match="max_prefill_tokens"):
        JaxEngine(
            cfg, params,
            _ecfg(enable_prefix_caching=False, max_prefill_tokens=64,
                  max_model_len=256),
            parallel=ParallelConfig(dp=2, sp=4),
        )
    # sp×tp MoE is allowed for ragged dispatch with E % tp == 0; an
    # uneven expert split still fails fast
    from dynamo_tpu.models import tiny_moe_config

    odd = tiny_moe_config(num_experts=3, num_experts_per_tok=2)
    with pytest.raises(ValueError, match="ragged|divisible"):
        JaxEngine(
            odd,
            init_params(odd, jax.random.PRNGKey(0), dtype=jnp.float32),
            _ecfg(enable_prefix_caching=False, max_prefill_tokens=256,
                  max_model_len=256),
            parallel=ParallelConfig(dp=2, sp=2, tp=2),
        )


async def test_engine_sp_tp_composed():
    """sp×tp engine: ring-attention prefill over sp with heads sharded
    over tp on a dp×sp×tp mesh — greedy continuation identical to
    single-device."""
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    prompts = _prompts(cfg, n=3)

    def ecfg():
        return _ecfg(
            enable_prefix_caching=False,
            max_prefill_tokens=256,
            max_model_len=256,
        )

    ref = JaxEngine(cfg, params, ecfg(), kv_dtype=jnp.float32)
    out_ref = await _collect(ref, prompts)
    await ref.shutdown()

    par = JaxEngine(
        cfg, params, ecfg(), kv_dtype=jnp.float32,
        parallel=ParallelConfig(dp=2, sp=2, tp=2),
    )
    out_par = await _collect(par, prompts)
    await par.shutdown()

    assert out_par == out_ref


async def test_engine_sp_tp_moe():
    """sp×tp MoE: ring-attention prefill over sp with EXPERTS sharded
    over tp (ragged dispatch rotated to the local expert slice inside
    the shard_map) — greedy equal to single-device."""
    cfg = tiny_moe_config()  # 4 experts, ragged dispatch
    params = init_params(cfg, jax.random.PRNGKey(6), dtype=jnp.float32)
    prompts = _prompts(cfg, n=3)

    def ecfg():
        return _ecfg(
            enable_prefix_caching=False,
            max_prefill_tokens=256,
            max_model_len=256,
        )

    ref = JaxEngine(cfg, params, ecfg(), kv_dtype=jnp.float32)
    out_ref = await _collect(ref, prompts)
    await ref.shutdown()

    par = JaxEngine(
        cfg, params, ecfg(), kv_dtype=jnp.float32,
        parallel=ParallelConfig(dp=2, sp=2, tp=2),
    )
    out_par = await _collect(par, prompts)
    await par.shutdown()

    assert out_par == out_ref

    # capacity-dispatch MoE stays rejected under sp×tp
    import dataclasses

    cap = dataclasses.replace(cfg, moe_impl="capacity")
    with pytest.raises(ValueError, match="ragged"):
        JaxEngine(
            cap, params, ecfg(), kv_dtype=jnp.float32,
            parallel=ParallelConfig(dp=2, sp=2, tp=2),
        )
