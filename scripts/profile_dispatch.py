#!/usr/bin/env python
"""Profile ONE headline decode dispatch end-to-end (VERDICT r5 item 2).

Phases timed on the real chip:
  - raw primitives: device_put/device_get/no-op-dispatch latency
    (calibrates what a host↔device round trip costs),
  - a headline round (8 req, prompt 128, gen 64) with per-phase timers
    monkeypatched into the engine: plan build, operand upload, dispatch
    call, result fetch, host unpack/deliver,
  - per-phase device share of a decode step via jax profiling
    (attention vs FFN vs sampling) when --phases is passed.

Usage: python scripts/profile_dispatch.py [--phases] [--quant int8]
"""

import argparse
import asyncio
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

TIMES = defaultdict(list)


def timed(name):
    def deco(fn):
        def wrap(*a, **kw):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            TIMES[name].append(time.perf_counter() - t0)
            return out
        return wrap
    return deco


def report(title):
    print(f"--- {title}")
    for k in sorted(TIMES):
        v = TIMES[k]
        print(f"{k:28s} n={len(v):3d} total={sum(v)*1e3:9.1f}ms "
              f"mean={sum(v)/len(v)*1e3:8.2f}ms max={max(v)*1e3:8.2f}ms")
    TIMES.clear()


def raw_primitives():
    import jax
    import jax.numpy as jnp

    x = np.zeros((16,), np.int32)
    big = np.zeros((1024, 1024), np.float32)  # 4MB
    f = jax.jit(lambda a: a + 1)
    g = jax.jit(lambda a: a * 2)
    # warm
    r = f(jnp.asarray(x)); jax.block_until_ready(r)
    r = g(jnp.asarray(big)); jax.block_until_ready(r)
    for _ in range(20):
        t0 = time.perf_counter()
        d = jnp.asarray(x)
        TIMES["put_small_enqueue"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(d)
        TIMES["put_small_sync"].append(time.perf_counter() - t0)
    for _ in range(5):
        t0 = time.perf_counter()
        d = jnp.asarray(big)
        jax.block_until_ready(d)
        TIMES["put_4mb_sync"].append(time.perf_counter() - t0)
    d = jnp.asarray(x)
    jax.block_until_ready(d)
    for _ in range(20):
        t0 = time.perf_counter()
        out = f(d)
        TIMES["dispatch_enqueue"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(out)
        TIMES["dispatch_sync"].append(time.perf_counter() - t0)
    for _ in range(20):
        out = f(d); jax.block_until_ready(out)
        t0 = time.perf_counter()
        np.asarray(jax.device_get(out))
        TIMES["get_small"].append(time.perf_counter() - t0)
    # chained dispatch+get (the decode chain shape): enqueue 4, get 4
    for _ in range(10):
        t0 = time.perf_counter()
        o = d
        outs = []
        for _ in range(4):
            o = f(o)
            outs.append(o)
        TIMES["chain4_enqueue"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for o in outs:
            np.asarray(jax.device_get(o))
        TIMES["chain4_get"].append(time.perf_counter() - t0)
    report("raw primitives (round-trip calibration)")


async def headline(quant, gen=64, rounds=2):
    import jax
    import jax.numpy as jnp

    from bench import BATCH, GEN_TOKENS, PROMPT_LEN, SUSTAINED_GEN, run_round
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import init_params
    from dynamo_tpu.models.config import LLAMA_3_2_1B

    cfg = LLAMA_3_2_1B
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    pages_per_seq = (PROMPT_LEN + SUSTAINED_GEN) // 16 + 2
    ecfg = EngineConfig(
        page_size=16, num_pages=1 + 2 * BATCH * pages_per_seq + 32,
        max_num_seqs=2 * BATCH, max_prefill_tokens=BATCH * PROMPT_LEN,
        prefill_batch_size=BATCH, max_model_len=PROMPT_LEN + SUSTAINED_GEN + 16,
        decode_batch_buckets=[BATCH, 2 * BATCH], chunk_buckets=[PROMPT_LEN],
        decode_steps=64, decode_chain=4, mixed_prefill_tokens=0,
        enable_prefix_caching=False, quantization=quant,
        fuse_projections=True,
    )
    engine = JaxEngine(cfg, params, ecfg, eos_token_ids=[])

    # instrument
    for name in ("_plan_step", "_run_prefill", "_run_decode",
                 "_decode_arrays", "_samp_arrays", "_table_array",
                 "_consume_decode", "_unpack_rows", "_dispatch_decode",
                 "_maybe_fuse_decode"):
        if hasattr(engine, name):
            setattr(engine, name, timed(name)(getattr(engine, name)))
    orig_put = engine._put

    def put_t(arr, *axes):
        t0 = time.perf_counter()
        out = orig_put(arr, *axes)
        TIMES["_put(enqueue)"].append(time.perf_counter() - t0)
        return out
    engine._put = put_t

    import dynamo_tpu.engine.engine as em
    orig_get = em.jax.device_get

    t0 = time.perf_counter()
    await run_round(engine, 0, gen_tokens=gen)  # compile
    print(f"compile round: {time.perf_counter()-t0:.1f}s")
    TIMES.clear()

    def get_t(x):
        t0 = time.perf_counter()
        out = orig_get(x)
        TIMES["device_get"].append(time.perf_counter() - t0)
        return out
    em.jax.device_get = get_t
    try:
        for r in range(rounds):
            t0 = time.perf_counter()
            total, dt, ttft, itl = await run_round(
                engine, 5000 + r, gen_tokens=gen)
            wall = time.perf_counter() - t0
            print(f"round {r}: {total} tok in {dt:.3f}s = {total/dt:.1f} "
                  f"tok/s (wall {wall:.3f}s, ttft_p50 {ttft*1e3:.0f}ms)")
        report(f"headline round breakdown ({quant})")
    finally:
        em.jax.device_get = orig_get
    await engine.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quant", default="none")
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--skip-raw", action="store_true")
    args = ap.parse_args()
    if not args.skip_raw:
        raw_primitives()
    asyncio.run(headline(args.quant, gen=args.gen, rounds=args.rounds))


if __name__ == "__main__":
    main()
