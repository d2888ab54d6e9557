#!/usr/bin/env python
"""Ablate one 1B decode step to locate the fixed per-step cost.

The dispatch profile shows: marginal HBM bandwidth ~750GB/s (near peak)
but a ~4ms FIXED cost per decode step at batch 8 — the lever for the
bf16/int8 headline (VERDICT r5 items 2/4). Variants, all as a
64-iteration lax.scan on the real llama-3.2-1b shapes:

  full       — embed + layers + norm + lm_head + argmax (forward_decode)
  no_head    — stop at the final hidden state (skips lm_head + sampling)
  no_attn    — attention replaced by identity (skips KV gather/write)
  head_only  — just lm_head + argmax on a fixed hidden state
  attn_only  — KV gather + attention + write, no matmuls
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import KVCache, init_params
from dynamo_tpu.models.config import LLAMA_3_2_1B

B = 8
T = 64
PAGES = 1 + 2 * B * 22 + 32
PAGE = 16
TABLE_W = 32


RTT_S = 0.0


def _sync(out):
    jax.block_until_ready(out)


def bench(name, fn, *args, iters=3):
    out = fn(*args)
    _sync(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        times.append(time.perf_counter() - t0)
    dt = min(times) - RTT_S  # subtract the measured fetch round-trip
    print(f"{name:12s}: {dt*1e3:8.2f}ms total  {dt/T*1e3:6.3f}ms/step")
    return dt


def main():
    cfg = LLAMA_3_2_1B
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    kv = KVCache.create(cfg, PAGES, PAGE, jnp.bfloat16)
    tokens = jnp.arange(B, dtype=jnp.int32) + 5
    positions = jnp.full((B,), 130, jnp.int32)
    table = jnp.tile(jnp.arange(1, TABLE_W + 1, dtype=jnp.int32), (B, 1))

    from dynamo_tpu.models.llama import (
        _lm_logits,
        decode_layers,
        forward_decode,
    )

    def scan_full(params, kv, tokens, positions, table):
        def body(carry, _):
            kv, tok, pos = carry
            logits, kv = forward_decode(params, cfg, kv, tok, pos, table)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (kv, nxt, pos + 1), ()
        (kv, tok, _), _ = jax.lax.scan(
            body, (kv, tokens, positions), None, length=T)
        return tok

    def scan_no_head(params, kv, tokens, positions, table):
        def body(carry, _):
            kv, tok, pos = carry
            x = params["embed"][tok]
            x, kv = decode_layers(params["layers"], cfg, kv, x, pos, table,
                                  "xla")
            nxt = (tok + x[:, :8].sum(-1).astype(jnp.int32)) % 128
            return (kv, nxt, pos + 1), ()
        (kv, tok, _), _ = jax.lax.scan(
            body, (kv, tokens, positions), None, length=T)
        return tok

    def scan_head_only(params, x0, tokens):
        def body(carry, _):
            tok = carry
            logits = _lm_logits(params, cfg, x0)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32) + tok
            return nxt, ()
        tok, _ = jax.lax.scan(body, tokens, None, length=T)
        return tok

    x0 = jnp.ones((B, cfg.hidden_size), jnp.bfloat16)

    def scan_full_pallas(params, kv, tokens, positions, table):
        def body(carry, _):
            kv, tok, pos = carry
            logits, kv = forward_decode(params, cfg, kv, tok, pos, table,
                                        attn_impl="pallas")
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (kv, nxt, pos + 1), ()
        (kv, tok, _), _ = jax.lax.scan(
            body, (kv, tokens, positions), None, length=T)
        return tok

    def scan_matmuls(params, x, tokens):
        """Just the 7 per-layer matmuls over the stacked weights (no
        attention, norms, rope, KV) — the weight-stream + MXU floor."""
        lp = params["layers"]

        def body(carry, _):
            x, tok = carry

            def layer(h, w):
                q = h @ w["wq"]
                k = h @ w["wk"]
                v = h @ w["wv"]
                o = (q + jnp.pad(k, ((0, 0), (0, q.shape[1] - k.shape[1])))
                     + jnp.pad(v, ((0, 0), (0, q.shape[1] - v.shape[1]))))
                h = h + o @ w["wo"]
                g = h @ w["w_gate"]
                u = h @ w["w_up"]
                h = h + (g * u) @ w["w_down"]
                return h.astype(x.dtype), ()

            x, _ = jax.lax.scan(layer, x, lp)
            tok = tok + x[:, :8].sum(-1).astype(jnp.int32)
            return (x, tok), ()
        (x, tok), _ = jax.lax.scan(body, (x, tokens), None, length=T)
        return tok

    def scan_stream(params, tokens):
        """Force a full read of every layer weight per step (sums) — the
        pure HBM streaming ceiling for this layout."""
        lp = params["layers"]

        def body(tok, _):
            def layer(acc, w):
                s = sum(jnp.sum(v, dtype=jnp.float32) for v in w.values())
                return acc + s, ()
            acc, _ = jax.lax.scan(layer, jnp.float32(0), lp)
            return tok + acc.astype(jnp.int32) % 3, ()
        tok, _ = jax.lax.scan(body, tokens, None, length=T)
        return tok

    print(f"model {cfg.name}: B={B} T={T} "
          f"params={cfg.num_params()/1e9:.2f}B")
    # calibrate the fetch RTT on a trivial program
    global RTT_S
    triv = jax.jit(lambda t: t + 1)
    _sync(triv(tokens))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        _sync(triv(tokens))
        rtts.append(time.perf_counter() - t0)
    RTT_S = min(rtts)
    print(f"fetch RTT: {RTT_S*1e3:.1f}ms (subtracted from every variant)")
    from dynamo_tpu.ops import compute_logprobs, sample_tokens
    from dynamo_tpu.ops.sampling import SamplingParams as SP

    samp = SP.make(
        temperature=jnp.zeros((B,), jnp.float32),
        top_k=jnp.zeros((B,), jnp.int32),
        top_p=jnp.ones((B,), jnp.float32),
    ) if hasattr(SP, "make") else None
    seeds = jnp.zeros((B,), jnp.uint32)

    def scan_engine_like(params, kv, tokens, positions, table, samp, seeds):
        def body(carry, _):
            kv, tok, pos, ctr = carry
            logits, kv = forward_decode(params, cfg, kv, tok, pos, table)
            out = sample_tokens(logits, samp, seeds, ctr)
            logp = compute_logprobs(logits, out)
            packed = jnp.concatenate(
                [jax.lax.bitcast_convert_type(out, jnp.float32), logp])
            return (kv, out, pos + 1, ctr + 1), packed
        (kv, tok, _, _), packed = jax.lax.scan(
            body, (kv, tokens, positions, jnp.zeros((B,), jnp.int32)),
            None, length=T)
        return packed

    jf = jax.jit(scan_full)
    t_full = bench("full", jf, params, kv, tokens, positions, table)
    def scan_greedy_logp(params, kv, tokens, positions, table):
        def body(carry, _):
            kv, tok, pos = carry
            logits, kv = forward_decode(params, cfg, kv, tok, pos, table)
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logp = compute_logprobs(logits, out)
            packed = jnp.concatenate(
                [jax.lax.bitcast_convert_type(out, jnp.float32), logp])
            return (kv, out, pos + 1), packed
        (kv, tok, _), packed = jax.lax.scan(
            body, (kv, tokens, positions), None, length=T)
        return packed

    if samp is not None:
        bench("engine_like", jax.jit(scan_engine_like), params, kv,
              tokens, positions, table, samp, seeds)
    bench("greedy+logp", jax.jit(scan_greedy_logp), params, kv, tokens,
          positions, table)
    t_fp = bench("full_pallas", jax.jit(scan_full_pallas), params, kv,
                 tokens, positions, table)
    jn = jax.jit(scan_no_head)
    t_nohead = bench("no_head", jn, params, kv, tokens, positions, table)
    t_mm = bench("matmuls", jax.jit(scan_matmuls), params, x0, tokens)
    t_st = bench("stream", jax.jit(scan_stream), params, tokens)
    body_gb = (cfg.num_params() - cfg.vocab_size * cfg.hidden_size) * 2 / 1e9
    head_gb = cfg.vocab_size * cfg.hidden_size * 2 / 1e9
    print(f"\nbody weights {body_gb:.2f}GB:")
    for name, t in (("no_head", t_nohead), ("matmuls", t_mm),
                    ("stream", t_st)):
        print(f"  {name:8s} eff BW {body_gb / (t / T):6.0f} GB/s "
              f"({t/T*1e3:6.3f} ms/step)")
    print(f"head share of full: {(t_full - t_nohead) / t_full:.1%} "
          f"(head {head_gb:.2f}GB)")
    print(f"pallas vs xla attention: {t_fp/T*1e3:.3f} vs "
          f"{t_full/T*1e3:.3f} ms/step")
    print(f"attention+norms cost: {(t_nohead - t_mm)/T*1e3:.3f} ms/step")


if __name__ == "__main__":
    main()
