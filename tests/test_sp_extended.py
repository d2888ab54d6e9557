"""sp ring-prefill exclusions lifted (VERDICT r2 item 8): sliding-window
and attention-sink models run under sp, and cached prefixes start the
ring at the prefix boundary.  All greedy-equal to single-device."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig


def ecfg(**over):
    defaults = dict(
        page_size=8, num_pages=96, max_num_seqs=8,
        max_prefill_tokens=8 * 128,
        max_model_len=128, enable_prefix_caching=False,
    )
    defaults.update(over)
    return EngineConfig(**defaults)


def req(tokens, max_tokens=6):
    return {
        "token_ids": tokens,
        "sampling_options": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }


async def collect(engine, request):
    out = []
    async for d in engine.generate(request):
        assert d.get("finish_reason") != "error", d
        out.extend(d["token_ids"])
    return out


PROMPTS = [
    [(7 * j) % 101 + 1 for j in range(30)],
    [1, 2, 3, 4, 5],
    [(3 * j) % 97 + 1 for j in range(45)],
    [9, 8, 7, 6],
]


async def _run_all(engine):
    return await asyncio.gather(*[collect(engine, req(p)) for p in PROMPTS])


async def _sp_equals_ref(cfg, **cfg_over):
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ref = JaxEngine(cfg, params, ecfg(**cfg_over), eos_token_ids=[],
                    kv_dtype=jnp.float32)
    want = await _run_all(ref)
    await ref.shutdown()
    sp = JaxEngine(cfg, params, ecfg(**cfg_over), eos_token_ids=[],
                   kv_dtype=jnp.float32,
                   parallel=ParallelConfig(dp=2, sp=2, tp=2))
    got = await _run_all(sp)
    await sp.shutdown()
    assert got == want


async def test_sp_sliding_window():
    """Mistral-class SWA model prefills under sp ring attention."""
    await _sp_equals_ref(tiny_config(
        sliding_window=16, model_type="mistral", name="tiny-swa",
    ))


async def test_sp_attention_sinks_and_mixed_windows():
    """GPT-OSS-class model (sinks + alternating full/window layers)
    prefills under sp ring attention."""
    await _sp_equals_ref(tiny_config(
        sliding_window=16, attention_sinks=True,
        layer_types=["sliding_attention", "full_attention"],
        model_type="gpt_oss", name="tiny-oss",
    ))


async def test_sp_with_prefix_cache():
    """Cached-prefix sp prefill: the ring starts at the prefix boundary;
    a repeated prompt reuses its pages and stays greedy-equal."""
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ref = JaxEngine(cfg, params, ecfg(enable_prefix_caching=True),
                    eos_token_ids=[], kv_dtype=jnp.float32)
    sp = JaxEngine(cfg, params, ecfg(enable_prefix_caching=True),
                   eos_token_ids=[], kv_dtype=jnp.float32,
                   parallel=ParallelConfig(dp=2, sp=2, tp=2))
    shared = [(11 * j) % 89 + 1 for j in range(32)]
    tails = [[5, 6, 7], [42] * 9]
    for eng in (ref, sp):
        # seed the cache, then hit it with extended prompts
        await collect(eng, req(shared))
    outs = []
    for eng in (ref, sp):
        got = await asyncio.gather(
            *[collect(eng, req(shared + t)) for t in tails]
        )
        # the second run must actually have prefix hits
        hits = eng.pool.peek(eng.scheduler._seq_hashes(
            type("S", (), {"prompt": shared, "prompt_len": len(shared),
                           "cache_salt": ""})()
        ))
        assert hits > 0, "prefix cache never hit"
        outs.append(got)
    await ref.shutdown()
    await sp.shutdown()
    assert outs[0] == outs[1]


async def test_sp_prefix_cache_with_swa():
    """SWA + cached prefix + sp all at once (the Mistral/GPT-OSS class
    that most wants long-context prefill)."""
    cfg = tiny_config(sliding_window=16, model_type="mistral",
                      name="tiny-swa2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ref = JaxEngine(cfg, params, ecfg(enable_prefix_caching=True),
                    eos_token_ids=[], kv_dtype=jnp.float32)
    sp = JaxEngine(cfg, params, ecfg(enable_prefix_caching=True),
                   eos_token_ids=[], kv_dtype=jnp.float32,
                   parallel=ParallelConfig(dp=2, sp=2, tp=2))
    shared = [(13 * j) % 91 + 1 for j in range(24)]
    want = await collect(ref, req(shared))
    got = await collect(sp, req(shared))
    assert got == want
    want2 = await collect(ref, req(shared + [3, 1, 4]))
    got2 = await collect(sp, req(shared + [3, 1, 4]))
    await ref.shutdown()
    await sp.shutdown()
    assert got2 == want2
