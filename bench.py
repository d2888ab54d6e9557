#!/usr/bin/env python
"""Benchmark: engine serving on the real chip, two model scales.

1. Llama-3.2-1B shapes (bf16 + int8, random weights): the headline
   `value` keeps round 1/2's protocol (8 concurrent requests, prompt 128,
   64 generated, decode 64x4) so `vs_baseline` stays comparable across
   rounds; `sustained` re-measures at 192 generated tokens where the
   decode blocks amortize (the realistic serving regime).
2. Llama-3.1-8B shapes, weight-only int8 (random int8 initialized
   DIRECTLY on device — ~8 GB of weights, no host transfer): throughput,
   TTFT/ITL, and the sustained HBM weight-read bandwidth.

Goodput under SLO (BASELINE.md's metric): a Poisson-arrival phase on the
1B engine measures per-request TTFT and mean ITL while prefills and
decodes genuinely interleave (mixed scheduling); goodput counts only
tokens from requests meeting the SLO.  Token delivery is block-bucketed
(decode_steps-token device blocks), so ITL here is each request's MEAN
inter-token latency; `itl_p99` is the p99 of that across requests.

Prints ONE JSON line {metric, value, unit, vs_baseline, ...}.
"""

import asyncio
import glob
import json
import os
import random
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BATCH = 8
PROMPT_LEN = 128
GEN_TOKENS = 64
SUSTAINED_GEN = 192

# explicit SLO for the goodput phases (BASELINE publishes no numbers;
# these are the TTFT/ITL classes interactive serving targets at this
# scale on one chip; chosen on a machine that is gone, re-derived in
# ROADMAP S0/S1 with a ledger — not before)
SLO_1B = {"ttft_ms": 800.0, "itl_ms": 15.0}
SLO_8B = {"ttft_ms": 1500.0, "itl_ms": 40.0}


async def run_round(engine, seed_base, *, batch=BATCH, prompt_len=PROMPT_LEN,
                    gen_tokens=GEN_TOKENS, stride=7):
    async def one(i):
        req = {
            "token_ids": [((i * stride + j) % 1000) + seed_base
                          for j in range(prompt_len)],
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": gen_tokens, "ignore_eos": True},
        }
        n = 0
        t_submit = time.perf_counter()
        t_first = t_last = None
        async for out in engine.generate(req):
            if out["token_ids"]:
                t_last = time.perf_counter()
                if t_first is None:
                    t_first = t_last
                n += len(out["token_ids"])
        ttft = (t_first - t_submit) if t_first else 0.0
        itl = ((t_last - t_first) / max(n - 1, 1)) if t_first else 0.0
        return n, ttft, itl

    t0 = time.perf_counter()
    results = await asyncio.gather(*[one(i) for i in range(batch)])
    dt = time.perf_counter() - t0
    total = sum(r[0] for r in results)
    ttfts = sorted(r[1] for r in results)
    itls = sorted(r[2] for r in results)
    return total, dt, ttfts[len(ttfts) // 2], itls[len(itls) // 2]


async def median_of(engine, rounds=3, gen_tokens=GEN_TOKENS,
                    with_samples=False):
    """A run can have whole slow phases (the host is shared); the MEDIAN
    of several rounds is robust without inflating like a best-of.
    `with_samples` additionally returns the per-round tok/s (for spread
    reporting)."""
    await run_round(engine, seed_base=0, gen_tokens=gen_tokens)  # compile
    results = [
        await run_round(engine, seed_base=5000 + 999 * r,
                        gen_tokens=gen_tokens)
        for r in range(rounds)
    ]
    results.sort(key=lambda res: res[0] / res[1])
    median = results[len(results) // 2]
    if with_samples:
        return median, sorted(r[0] / r[1] for r in results)
    return median


async def interleaved_ab(engines, rounds=3, gen_tokens=SUSTAINED_GEN):
    """A/B-interleave measurement rounds across engines within ONE run:
    a slow phase of the machine shifts every engine's rounds together, so
    per-engine medians stay comparable and the reported SPREAD separates
    environment noise from real regressions (a sequential design lets a
    phase land on one engine only and silently move the ratio).
    Returns per-engine (median_tok_s, all_round_tok_s, median_round)."""
    for e in engines:  # compile everything off the clock
        await run_round(e, seed_base=0, gen_tokens=gen_tokens)
    samples = {id(e): [] for e in engines}
    for r in range(rounds):
        for e in engines:  # one round each, alternating
            res = await run_round(e, seed_base=5000 + 999 * r,
                                  gen_tokens=gen_tokens)
            samples[id(e)].append(res)
    out = []
    for e in engines:
        rs = samples[id(e)]
        rates = sorted(r[0] / r[1] for r in rs)
        rs_sorted = sorted(rs, key=lambda res: res[0] / res[1])
        out.append((rates[len(rates) // 2], rates,
                    rs_sorted[len(rs_sorted) // 2]))
    return out


async def _goodput_pass(engine, *, rates, n_req, prompt_len, gen, slo,
                        min_fraction, rep):
    """One rate-ladder pass: sweep Poisson offered rates until the SLO
    breaks; returns (sweep_points, knee_rate).

    Each rate point ALSO runs through a live frontend SLO window
    (frontend/slo.py — the exact accounting the serving fleet exposes on
    /metrics and /fleet.json) and asserts the live slo_met/goodput agree
    with this offline computation; both land in BENCH_full.json."""
    from dynamo_tpu.frontend.slo import SLOAccountant, SLOTargets

    sweep, knee, broken = [], None, False
    for i, rate in enumerate(rates):
        live_acc = SLOAccountant(window_s=1800.0, slots=60)
        # set_targets, NOT the constructor default: the default passes
        # through SLOTargets.from_env, and a fleet-wide DYN_TPU_SLO_*
        # override would silently diverge the live predicate from the
        # offline `slo` dict this pass scores against
        live_acc.set_targets("bench", SLOTargets(
            ttft_ms=slo["ttft_ms"], itl_ms=slo["itl_ms"]))
        g = await poisson_goodput(
            engine, n_req=n_req, rate_rps=rate, prompt_len=prompt_len,
            gen=gen, slo=slo, seed=17 + 31 * rep + i,
            accountant=live_acc,
        )
        live = live_acc.snapshot()["bench"]
        # identical request log + identical SLO predicate → the MET
        # fraction must match exactly; the rates may differ only by the
        # covered-duration offset (the first arrival's Poisson wait,
        # ~1/(n_req·rate) of the phase)
        assert abs((live["slo_met"] if live["slo_met"] is not None
                    else -1.0) - g[4]) < 1e-6, (live["slo_met"], g[4])
        if g[0] > 0:
            # the acceptance bar: live within 5% of offline (the window
            # is anchored at phase t0, so agreement is near-exact)
            drift = abs(live["goodput_tok_s"] - g[0]) / g[0]
            assert drift < 0.05, (
                f"live window goodput {live['goodput_tok_s']:.1f} vs "
                f"offline {g[0]:.1f} ({drift:.1%} apart)"
            )
        sweep.append({
            "rate_rps": rate,
            "goodput_tok_s": round(g[0], 2),
            "attained_tok_s": round(g[1], 2),
            "ttft_p50_ms": round(g[2], 1),
            "itl_p99_ms": round(g[3], 2),
            "slo_met_fraction": round(g[4], 3),
            "live_window": {
                "slo_met": live["slo_met"],
                "goodput_tok_s": round(live["goodput_tok_s"], 2),
                "attained_tok_s": round(live["attained_tok_s"], 2),
                "ttft_p50_ms": live["ttft"]["p50_ms"],
                "itl_p99_ms": live["itl"]["p99_ms"],
            },
        })
        if g[4] >= min_fraction and not broken:
            # knee = top of the CONTIGUOUS passing prefix
            knee = rate
        else:
            broken = True
            if g[4] < 0.5:
                break  # far past the knee — stop burning chip time
    return sweep, knee


async def goodput_knee(engine, *, rates, n_req, prompt_len, gen, slo,
                       min_fraction=0.9, repeats=2):
    """Sweep Poisson offered rates up a ladder until the SLO breaks:
    reports the max goodput observed under the SLO-met threshold and the
    knee rate (the reference harness's concurrency sweeps,
    benchmarking.md:70-75 — one point where attained ≈ offered measures
    light-load SLO compliance, not capacity).

    VERDICT r4 weak #5 hardening: the whole ladder runs `repeats` times
    with distinct arrival seeds; a knee is only a number when the passes
    agree within one rung (otherwise knee_rate_rps is null and the
    disagreement rides the JSON), and max_goodput is the max over ALL
    SLO-passing points of the reported sweep — never contradicting it."""
    return (await goodput_knee_ab(
        [engine], rates=rates, n_req=n_req, prompt_len=prompt_len,
        gen=gen, slo=slo, min_fraction=min_fraction, repeats=repeats,
    ))[0]


async def goodput_knee_ab(engines, *, rates, n_req, prompt_len, gen, slo,
                          min_fraction=0.9, repeats=2):
    """A/B-interleave whole goodput-ladder passes across engines within
    ONE run (same rationale as `interleaved_ab`: a slow phase of the
    machine shifts every engine's passes together, so the reported deltas
    — e.g. block ladder on vs off — are real, not environment).
    Returns one `goodput_knee`-shaped summary per engine."""
    passes = {id(e): [] for e in engines}
    for rep in range(repeats):
        for e in engines:
            passes[id(e)].append(await _goodput_pass(
                e, rates=rates, n_req=n_req, prompt_len=prompt_len,
                gen=gen, slo=slo, min_fraction=min_fraction, rep=rep,
            ))
    return [
        _knee_summary(passes[id(e)], rates, n_req, min_fraction, slo)
        for e in engines
    ]


def _knee_summary(passes, rates, n_req, min_fraction, slo):
    """Aggregate ladder passes into the reported knee record (repeat
    agreement, conservative representative pass, max SLO-passing
    goodput)."""
    knees = [k for _, k in passes]
    # agreement: all passes found a knee within one rung of each other,
    # or none did — a zero-capacity pass vs any real knee is DISagreement
    rungs = [rates.index(k) for k in knees if k in rates]
    if len(rungs) == len(knees):
        agreement = max(rungs) - min(rungs) <= 1
    else:
        agreement = not rungs  # some passes kneeless: agree only if all
    # report the pass whose knee is the more conservative (lower) one
    order = [rates.index(k) if k in rates else -1 for k in knees]
    rep_idx = order.index(min(order))
    sweep = passes[rep_idx][0]
    best = max(
        (p["goodput_tok_s"] for p in sweep
         if p["slo_met_fraction"] >= min_fraction),
        default=0.0,
    )
    return {
        "sweep": sweep,
        "knee_rate_rps": knees[rep_idx] if agreement else None,
        **({} if agreement else {"knee_disagreement": knees}),
        "knees_per_pass": knees,
        "n_req": n_req,
        "repeat_agreement": agreement,
        "max_goodput_at_slo_tok_s": round(best, 2),
        "slo": slo,
    }


async def poisson_goodput(engine, *, n_req, rate_rps, prompt_len, gen,
                          slo, seed=17, accountant=None):
    """Poisson arrivals; returns (goodput_tok_s, attained_tok_s,
    ttft_p50_ms, itl_p99_ms, slo_met_fraction).

    With `accountant` (a frontend SLOAccountant), every request ALSO
    flows through the live sliding-window path — the cross-check that
    the serving fleet's /metrics numbers and this offline computation
    are the same definitions (`_goodput_pass` asserts agreement)."""
    rng = random.Random(seed)
    waits, acc = [], 0.0
    for _ in range(n_req):
        acc += rng.expovariate(rate_rps)
        waits.append(acc)

    if accountant is not None:
        # anchor the live window at phase t0: its covered duration must
        # be the same interval the offline goodput divides by, not
        # offset by the first arrival's Poisson wait (an Exp(rate) tail
        # that would otherwise flake the cross-check ~e^-(0.1·n_req))
        accountant.window("bench").mark()

    async def one(i):
        await asyncio.sleep(waits[i])
        req = {
            "token_ids": [((i * 13 + j) % 997) + 1 for j in range(prompt_len)],
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": gen, "ignore_eos": True},
        }
        n = 0
        t_submit = time.perf_counter()
        if accountant is not None:
            accountant.observe_start("bench")
        t_first = t_last = None
        async for out in engine.generate(req):
            if out["token_ids"]:
                t_last = time.perf_counter()
                if t_first is None:
                    t_first = t_last
                n += len(out["token_ids"])
        ttft_ms = (t_first - t_submit) * 1e3 if t_first else float("inf")
        itl_ms = ((t_last - t_first) / max(n - 1, 1) * 1e3
                  if t_first else float("inf"))
        if accountant is not None:
            accountant.observe("bench", ttft_ms, itl_ms, n,
                               prompt_tokens=prompt_len)
        return n, ttft_ms, itl_ms

    t0 = time.perf_counter()
    results = await asyncio.gather(*[one(i) for i in range(n_req)])
    dt = time.perf_counter() - t0
    ok = [r for r in results
          if r[1] <= slo["ttft_ms"] and r[2] <= slo["itl_ms"]]
    ttfts = sorted(r[1] for r in results)
    itls = sorted(r[2] for r in results)
    return (
        sum(r[0] for r in ok) / dt,
        sum(r[0] for r in results) / dt,
        ttfts[len(ttfts) // 2],
        itls[min(len(itls) - 1, int(len(itls) * 0.99))],
        len(ok) / max(len(results), 1),
    )


async def warm_mixed(engine, prompt_len=PROMPT_LEN) -> bool:
    """Warm prefill/decode/MIXED programs off the clock: solo request
    first, then overlap a prefill with a LIVE decode until the mixed
    program has actually compiled (a non-empty "mixed" entry in
    `engine.compiled_variants`) — a racy warmup leaks a compile
    of seconds into measured TTFTs."""
    await run_round(engine, 0, batch=1, prompt_len=prompt_len,
                    gen_tokens=40)

    async def _mixed_warm(seed):
        first = asyncio.Event()

        async def bg():
            req = {"token_ids": [(seed + j) % 997 + 1
                                 for j in range(prompt_len)],
                   "sampling_options": {"temperature": 0.0},
                   "stop_conditions": {"max_tokens": 160,
                                       "ignore_eos": True}}
            async for out in engine.generate(req):
                if out["token_ids"]:
                    first.set()
            first.set()  # errored/empty streams must not hang the bench

        task = asyncio.get_running_loop().create_task(bg())
        try:
            await asyncio.wait_for(first.wait(), timeout=120)
            # decode is live; the next prefill mixes
            await run_round(engine, seed + 7, batch=1,
                            prompt_len=prompt_len, gen_tokens=8)
        finally:
            await task

    for attempt in range(4):
        if engine.compiled_variants["mixed"]:
            return True
        await _mixed_warm(300 + 40 * attempt)
    ok = bool(engine.compiled_variants["mixed"])
    if not ok:
        print("WARNING: mixed-step warmup never compiled; goodput "
              "TTFTs include an on-clock XLA compile",
              file=sys.stderr, flush=True)
    return ok


async def warm_ladder(engine, prompt_len=PROMPT_LEN) -> bool:
    """Compile every block-ladder rung's decode program off the clock:
    a burst (short prompt landing on a live decode) resets the
    scheduler's ramp to the bottom rung, and the quiet tail climbs back
    up one rung per dispatch — so one long generation with a mid-stream
    burst walks the whole ladder.  Checked against
    `engine.compiled_decode_rungs`; a rung compiling ON the clock puts
    a compile of seconds inside a measured TTFT."""
    ladder = list(engine.cfg.block_ladder)
    if len(ladder) <= 1:
        return True
    for attempt in range(4):
        if set(ladder) <= engine.compiled_decode_rungs:
            return True
        first = asyncio.Event()

        async def bg(seed):
            req = {"token_ids": [(seed + j) % 997 + 1
                                 for j in range(prompt_len)],
                   "sampling_options": {"temperature": 0.0},
                   # enough tokens past the burst to climb every rung
                   "stop_conditions": {"max_tokens": 3 * sum(ladder) + 32,
                                       "ignore_eos": True}}
            async for out in engine.generate(req):
                if out["token_ids"]:
                    first.set()
            first.set()  # errored/empty streams must not hang the bench

        task = asyncio.get_running_loop().create_task(bg(500 + 40 * attempt))
        try:
            await asyncio.wait_for(first.wait(), timeout=120)
            # decode is live: this burst forces the bottom rung, then
            # the bg request's tail ramps back through the ladder
            await run_round(engine, 600 + 40 * attempt, batch=1,
                            prompt_len=prompt_len, gen_tokens=4)
        finally:
            await task
    ok = set(ladder) <= engine.compiled_decode_rungs
    if not ok:
        print(f"WARNING: ladder warmup missed rungs "
              f"{sorted(set(ladder) - engine.compiled_decode_rungs)}; "
              f"an XLA compile may land on the clock",
              file=sys.stderr, flush=True)
    return ok


def _ttft_attr_means(engine, m0=None):
    """Mean per-request TTFT attribution (ms) — block-wait vs
    queue-wait vs prefill, the split that proves where a goodput/TTFT
    win came from.  `m0` is a post-warmup metrics() snapshot: the
    engine totals are lifetime, and warmup traffic differs per A/B arm
    (warm_ladder only runs on laddered engines), so the measured means
    must be diffs."""

    m = engine.metrics()  # ONE snapshot: fields must be consistent

    def d(field):
        return getattr(m, field) - (getattr(m0, field) if m0 is not None
                                    else 0)

    n = max(d("ttft_attributed_total"), 1)
    return {
        "requests": d("ttft_attributed_total"),
        "block_wait_ms_mean": round(d("ttft_block_wait_ms_total") / n, 2),
        "queue_wait_ms_mean": round(d("ttft_queue_wait_ms_total") / n, 2),
        "prefill_ms_mean": round(d("ttft_prefill_ms_total") / n, 2),
    }


def _rung_delta(engine, h0=None):
    """Chosen-rung dispatch counts since the `h0` snapshot (warmup
    walks the whole ladder by design — exclude it from the reported
    mix)."""
    h0 = h0 or {}
    return {k: v - h0.get(k, 0) for k, v in engine.rung_histogram.items()
            if v - h0.get(k, 0)}


def _p50(xs):
    s = sorted(xs)
    return s[len(s) // 2]


def _p99(xs):
    s = sorted(xs)
    return s[min(len(s) - 1, int(len(s) * 0.99))]


async def disagg_phase(cfg, params, n=8, prompt_len=512, gen=8):
    """Prefill engine → data-plane KV transfer → decode engine, on-chip.
    Returns per-lane transfer percentiles + the TTFT cost of disagg vs
    local prefill (reference: disagg_serving.md:95-108 measures exactly
    this overhead)."""
    import jax.numpy as jnp  # noqa: F401

    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferSource
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    pages_per = prompt_len // 16 + 2

    def mk():
        return JaxEngine(cfg, params, EngineConfig(
            page_size=16, num_pages=1 + 4 * pages_per + 16, max_num_seqs=4,
            max_prefill_tokens=prompt_len, prefill_batch_size=1,
            max_model_len=prompt_len + gen + 16,
            decode_batch_buckets=[1], chunk_buckets=[prompt_len],
            decode_steps=8, enable_prefix_caching=False,
        ), eos_token_ids=[])

    pre, dec = mk(), mk()
    source = await KvTransferSource(pre).start()

    def req_for(i):
        return {
            "token_ids": [((i * 31 + j) % 997) + 1 for j in range(prompt_len)],
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": gen, "ignore_eos": True},
        }

    async def local(i):
        t0 = time.perf_counter()
        t_first = None
        async for d in dec.generate(req_for(i)):
            if d["token_ids"] and t_first is None:
                t_first = time.perf_counter()
        return (t_first - t0) * 1e3

    async def disagg(i, lanes):
        req = req_for(i)
        t0 = time.perf_counter()
        r = await pre.prefill_remote(dict(req), transfer_source=source)
        if "kv_descriptor" not in r:
            raise RuntimeError(f"prefill_remote failed: {r}")
        ttft_ms = (time.perf_counter() - t0) * 1e3  # first token exists
        t1 = time.perf_counter()
        pages, stats = await KvTransferClient(dec, lanes=lanes).fetch(
            r["kv_descriptor"])
        handoff_ms = (time.perf_counter() - t1) * 1e3
        async for d in dec.generate_imported(req, r["token_ids"][0], pages):
            if d.get("finish_reason") == "error":
                raise RuntimeError(f"generate_imported failed: {d}")
        return stats, ttft_ms, handoff_ms

    out = {}
    try:
        await local(0)  # compile prefill+decode on dec, off the clock
        await disagg(0, ("colocated",))  # compile export/import paths
        locals_ms = [await local(100 + i) for i in range(n)]
        out["ttft_local_p50_ms"] = round(_p50(locals_ms), 1)
        for key, lanes in (("lane_device", ("colocated",)),
                           ("lane_host", ("host",))):
            stats, ttfts, handoffs = [], [], []
            for i in range(n):
                s, t, h = await disagg(200 + i, lanes)
                stats.append(s)
                ttfts.append(t)
                handoffs.append(h)
            out[key] = {
                "kv_transfer_p50_ms": round(_p50([s.ms for s in stats]), 2),
                "kv_transfer_p99_ms": round(_p99([s.ms for s in stats]), 2),
                "bytes_per_req": stats[0].bytes,
                "lane": stats[0].lane,
                "handoff_p50_ms": round(_p50(handoffs), 2),
                "n": n,
            }
            out.setdefault("ttft_disagg_p50_ms", round(_p50(ttfts), 1))
        out["ttft_delta_ms"] = round(
            out["ttft_disagg_p50_ms"] - out["ttft_local_p50_ms"], 1)
    finally:
        await source.stop()
        await pre.shutdown()
        await dec.shutdown()
    return out


async def spec_decode_phase(cfg, params, prompt_len=128, gen=96, k=4,
                            rounds=2):
    """Batch-1 self-speculative decoding on a REPETITIVE workload (the
    prompt is a repeated 16-token cycle — the case prompt-lookup
    drafting exists for): ITL with speculation on vs off, plus the
    engine's own tokens-per-dispatch and acceptance telemetry.  Batch-1
    ITL is steps-per-token on a bandwidth-bound chip (8 GB of weights
    per step at 8B-int8 no matter how few tokens come out), which is
    exactly what the accepted drafts compress."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    period = 16
    prompt = [((i % period) * 31 + 7) % 997 + 1 for i in range(prompt_len)]
    pages_per = (prompt_len + gen) // 16 + 2

    def mk(spec_k):
        return JaxEngine(cfg, params, EngineConfig(
            page_size=16, num_pages=1 + 2 * pages_per + 16, max_num_seqs=2,
            max_prefill_tokens=prompt_len, prefill_batch_size=1,
            max_model_len=prompt_len + gen + 16,
            decode_batch_buckets=[1, 2], chunk_buckets=[prompt_len],
            # the spec engine pays one dispatch per <=k+1 tokens (drafts
            # come from the fetched history), so it runs unblocked;
            # the plain engine keeps a block shape of the same order so
            # the comparison is dispatch-for-dispatch honest
            decode_steps=1 if spec_k else k + 1, decode_chain=1,
            enable_prefix_caching=False, quantization="int8",
            speculative_ngram_k=spec_k,
        ), eos_token_ids=[])

    async def one(engine):
        req = {
            "token_ids": prompt,
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": gen, "ignore_eos": True},
        }
        n = 0
        t_first = t_last = None
        async for out in engine.generate(req):
            if out["token_ids"]:
                t_last = time.perf_counter()
                if t_first is None:
                    t_first = t_last
                n += len(out["token_ids"])
        return ((t_last - t_first) / max(n - 1, 1)) * 1e3 if t_first else 0.0

    plain, spec = mk(0), mk(k)
    out = {}
    try:
        for e in (plain, spec):  # compile off the clock
            await one(e)
        # the engine counters are lifetime: snapshot after warmup so the
        # reported acceptance/dispatch numbers cover exactly the
        # ITL-measured rounds
        m0 = spec.metrics()
        itl_plain, itl_spec = [], []
        for _ in range(rounds):  # interleave so a slow phase moves both
            itl_plain.append(await one(plain))
            itl_spec.append(await one(spec))
        m = spec.metrics()
        dispatches = m.spec_dispatches_total - m0.spec_dispatches_total
        accepted = m.spec_accepted_tokens_total - m0.spec_accepted_tokens_total
        drafted = m.spec_draft_tokens_total - m0.spec_draft_tokens_total
        out = {
            "k": k,
            "prompt_period": period,
            "batch": 1,
            "itl_plain_p50_ms": round(_p50(itl_plain), 2),
            "itl_spec_p50_ms": round(_p50(itl_spec), 2),
            "itl_ratio": round(
                _p50(itl_plain) / max(_p50(itl_spec), 1e-9), 3),
            "tokens_per_dispatch": round(
                (accepted + dispatches) / max(dispatches, 1), 3),
            "acceptance_rate": round(accepted / max(drafted, 1), 4),
            "spec_dispatches": dispatches,
        }
    finally:
        await plain.shutdown()
        await spec.shutdown()
    return out


async def continuous_phase(cfg, params, prompt_len=128, gen=192, rounds=3):
    """Device-resident decode loop A/B (ISSUE 6): the r05 serving shape
    (64-step int8 blocks) with the FIXED 4-block decode chain vs
    CONTINUOUS chaining (open-ended device-side chaining, on-device stop
    detection, async double-buffered drain), rounds interleaved within
    one run so a slow phase moves both arms.  Also derives the
    inter-block HOST gap from the continuous engine's step-event ring
    (runtime.timeline.decode_host_gaps — ROADMAP target: p50 < 0.1 ms
    on-chip between consecutive decode blocks)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.runtime.timeline import decode_host_gaps

    pages_per = (prompt_len + gen) // 16 + 2

    def mk(continuous):
        return JaxEngine(cfg, params, EngineConfig(
            page_size=16, num_pages=1 + BATCH * pages_per + 16,
            max_num_seqs=BATCH, max_prefill_tokens=BATCH * prompt_len,
            prefill_batch_size=BATCH, max_model_len=prompt_len + gen + 16,
            decode_batch_buckets=[BATCH], chunk_buckets=[prompt_len],
            decode_steps=64, decode_chain=4, decode_continuous=continuous,
            enable_prefix_caching=False, quantization="int8",
            fuse_projections=True,
        ), eos_token_ids=[])

    chained, cont = mk(False), mk(True)
    try:
        (ch_tok, ch_rates, ch_med), (cc_tok, cc_rates, cc_med) = (
            await interleaved_ab([chained, cont], rounds=rounds,
                                 gen_tokens=gen))
        m = cont.metrics()
        # host-gap measurement on ONE dedicated round with a cleared
        # ring: the A/B-interleaved rounds leave seconds-long idle
        # boundaries between the cont engine's blocks (the chained arm
        # was running), which would masquerade as p99 host gaps
        cont.events.clear()
        await run_round(cont, seed_base=12345, gen_tokens=gen)
        gaps = decode_host_gaps(cont.events.dump(), continuous_only=True)
        return {
            "batch": BATCH, "gen": gen,
            "tok_s_chained": round(ch_tok, 2),
            "tok_s_continuous": round(cc_tok, 2),
            "itl_p50_chained_ms": round(ch_med[3] * 1e3, 3),
            "itl_p50_continuous_ms": round(cc_med[3] * 1e3, 3),
            "itl_ratio": round(ch_med[3] / max(cc_med[3], 1e-9), 3),
            "cc_chains": m.decode_cc_chains_total,
            "cc_blocks": m.decode_cc_blocks_total,
            "host_gap_ms": gaps,
            "samples_tok_s": {
                "chained": [round(r, 1) for r in ch_rates],
                "continuous": [round(r, 1) for r in cc_rates],
            },
        }
    finally:
        await chained.shutdown()
        await cont.shutdown()


async def bursty_phase(cfg, params, *, prompt_len=128, gen=1024,
                       residents=4, bursts=5, burst_n=3,
                       arrival_prompt=96, arrival_gen=8, quiet_s=1.0,
                       rounds=2):
    """Bursty-arrival A/B on the device-resident loop (ISSUE 15):
    `residents` long decode streams hold a live chain while short-prompt
    bursts arrive — the UNIFIED arm splices each arrival into the chain
    as chunk rows (`prefill_chunk_tokens` prompt tokens per block inside
    the same compiled program), the FALL-OUT arm
    (`prefill_chunk_tokens=0`) ends the chain and replans per admission.

    Measured per arm, rounds interleaved within one run:
    - the residents' decode ITL p99 INSIDE burst windows vs quiet
      windows (the number splicing exists to flatten — admission work
      that ends the chain lands as resident ITL spikes);
    - chain fall-outs PER ADMITTED request, split by reason (from the
      engine's own `decode_cc_fallout_total{reason}` counters)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    pages_per = (prompt_len + gen) // 16 + 2
    nseqs = residents + burst_n
    bucket = 1 << (nseqs - 1).bit_length()

    def mk(chunk_tokens):
        return JaxEngine(cfg, params, EngineConfig(
            page_size=16, num_pages=1 + nseqs * pages_per + 16,
            max_num_seqs=nseqs, max_prefill_tokens=residents * prompt_len,
            prefill_batch_size=residents, max_model_len=prompt_len + gen + 16,
            decode_batch_buckets=[bucket],
            chunk_buckets=[arrival_prompt, prompt_len],
            decode_steps=64, decode_chain=4, decode_continuous=True,
            prefill_chunk_tokens=chunk_tokens,
            enable_prefix_caching=False, quantization="int8",
            fuse_projections=True,
        ), eos_token_ids=[])

    def _req(tokens, max_tokens):
        return {
            "token_ids": tokens,
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": max_tokens,
                                "ignore_eos": True},
        }

    async def _stream(engine, req, stamps=None):
        async for out in engine.generate(req):
            if out["token_ids"] and stamps is not None:
                stamps.append((time.perf_counter(), len(out["token_ids"])))

    async def _pass(engine, seed_base, *, n_bursts=bursts,
                    res_gen=gen):
        m0 = engine.metrics()
        f0 = dict(m0.decode_cc_fallout_total)
        stamps = [[] for _ in range(residents)]
        res = [asyncio.ensure_future(_stream(
            engine,
            _req([((i * 7 + j) % 1000) + seed_base
                  for j in range(prompt_len)], res_gen),
            stamps[i])) for i in range(residents)]
        await asyncio.sleep(quiet_s)  # settle into the steady chain
        windows, admitted = [], 0
        for b in range(n_bursts):
            t0 = time.perf_counter()
            burst = [asyncio.ensure_future(_stream(
                engine,
                _req([((b * 31 + j * 13 + k) % 997) + 1
                      for j in range(arrival_prompt)], arrival_gen)))
                for k in range(burst_n)]
            await asyncio.gather(*burst)
            windows.append((t0, time.perf_counter()))
            admitted += burst_n
            await asyncio.sleep(quiet_s)
        end = time.perf_counter()
        await asyncio.gather(*res)
        burst_gaps, quiet_gaps = [], []
        for per in stamps:
            for (ta, _ka), (tb, kb) in zip(per, per[1:]):
                if ta > end:
                    break  # bursts over: tail gaps classify as nothing
                g = (tb - ta) / max(kb, 1) * 1e3
                in_burst = any(ta <= w1 and tb >= w0
                               for w0, w1 in windows)
                (burst_gaps if in_burst else quiet_gaps).append(g)
        f1 = dict(engine.metrics().decode_cc_fallout_total)
        dfall = {k: v - f0.get(k, 0) for k, v in f1.items()
                 if v - f0.get(k, 0)}
        admit_attr = sum(dfall.get(k, 0)
                         for k in ("admit", "admission", "pending_work"))
        p99_b = _p99(burst_gaps) if burst_gaps else 0.0
        p99_q = _p99(quiet_gaps) if quiet_gaps else 0.0
        return {
            "itl_p99_burst_ms": round(p99_b, 3),
            "itl_p99_quiet_ms": round(p99_q, 3),
            "burst_vs_quiet": round(p99_b / max(p99_q, 1e-9), 3),
            "gaps_burst": len(burst_gaps), "gaps_quiet": len(quiet_gaps),
            "admitted": admitted,
            "fallouts": dfall,
            "fallout_per_admit": round(
                sum(dfall.values()) / max(admitted, 1), 3),
            "admission_fallout_per_admit": round(
                admit_attr / max(admitted, 1), 3),
        }

    unified, split = mk(64), mk(0)
    try:
        for e in (unified, split):  # compile off the clock, incl. the
            # chunk-row splice variant (one resident + one burst)
            await _pass(e, seed_base=0, n_bursts=1, res_gen=96)
        samples = {"unified": [], "split": []}
        for r in range(rounds):
            samples["unified"].append(
                await _pass(unified, seed_base=5000 + 999 * r))
            samples["split"].append(
                await _pass(split, seed_base=5000 + 999 * r))
        med = {arm: sorted(s, key=lambda p: p["itl_p99_burst_ms"])
               [len(s) // 2] for arm, s in samples.items()}
        return {
            "residents": residents, "bursts": bursts, "burst_n": burst_n,
            "arrival_prompt": arrival_prompt,
            "unified": med["unified"], "split": med["split"],
            "burst_p99_split_vs_unified": round(
                med["split"]["itl_p99_burst_ms"]
                / max(med["unified"]["itl_p99_burst_ms"], 1e-9), 3),
            "samples": samples,
        }
    finally:
        await unified.shutdown()
        await split.shutdown()


async def kvbm_zipf_phase(cfg, params, *, tenants=512, sys_len=384,
                          user_len=64, gen=48, n_req=96, rate_rps=6.0,
                          zipf_a=1.1, rounds=2, slo=SLO_1B):
    """Zipf-distributed multi-tenant prefix workload (ISSUE 8): `tenants`
    distinct system prompts whose popularity follows a Zipf law, each
    request = tenant system prefix + fresh user suffix, Poisson arrivals.
    The HBM page pool holds only ~32 tenants' prefixes BY DESIGN (the hot
    prefix set dwarfs HBM — the millions-of-users regime), so the
    offload arm keeps evicted prefixes in the DRAM tier and onboards
    them at admission while the no-offload arm re-prefills cold.

    Waves interleave offload-off/on within one run (same arrival seeds)
    so a slow phase moves both arms; reports per-arm goodput under the
    1B SLO, per-tier hit counters from the engine's own KVBM metrics,
    and the warm-prefix TTFT ladder (cold vs HBM-hit vs DRAM-hit — the
    acceptance ratios: DRAM ≤ 2× HBM, cold ≥ 5× DRAM)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.kvbm import HostBlockPool, TieredKvCache

    page = 16
    prompt_len = sys_len + user_len
    pages_per = (prompt_len + gen) // page + 2
    hot_tenants = 32  # HBM-resident tenant budget

    def mk(offload):
        tiered = (TieredKvCache(HostBlockPool(capacity_bytes=8 << 30))
                  if offload else None)
        return JaxEngine(cfg, params, EngineConfig(
            page_size=page,
            num_pages=1 + hot_tenants * (sys_len // page) + 16 * pages_per,
            max_num_seqs=16,
            max_prefill_tokens=2 * prompt_len, prefill_batch_size=2,
            max_model_len=prompt_len + gen + 16,
            decode_batch_buckets=[16], chunk_buckets=[prompt_len],
            decode_steps=32, decode_chain=2,
            mixed_prefill_tokens=2 * prompt_len,
            enable_prefix_caching=True, quantization="int8",
            fuse_projections=True,
        ), eos_token_ids=[], tiered=tiered)

    def tenant_sys(t):
        return [((t * 131 + j * 7) % 997) + 1 for j in range(sys_len)]

    def zipf_schedule(seed):
        rng = random.Random(seed)
        weights = [1.0 / (r + 1) ** zipf_a for r in range(tenants)]
        acc, reqs = 0.0, []
        for i in range(n_req):
            acc += rng.expovariate(rate_rps)
            t = rng.choices(range(tenants), weights=weights)[0]
            user = [((i * 31 + j * 3) % 997) + 1 for j in range(user_len)]
            reqs.append((acc, tenant_sys(t) + user))
        return reqs

    async def wave(engine, seed):
        reqs = zipf_schedule(seed)

        async def one(at, tokens):
            await asyncio.sleep(at)
            r = {"token_ids": tokens,
                 "sampling_options": {"temperature": 0.0},
                 "stop_conditions": {"max_tokens": gen, "ignore_eos": True}}
            n, t_first, t_last = 0, None, None
            t_submit = time.perf_counter()
            async for out in engine.generate(r):
                if out["token_ids"]:
                    t_last = time.perf_counter()
                    if t_first is None:
                        t_first = t_last
                    n += len(out["token_ids"])
            ttft = (t_first - t_submit) * 1e3 if t_first else float("inf")
            itl = ((t_last - t_first) / max(n - 1, 1) * 1e3
                   if t_first else float("inf"))
            return n, ttft, itl

        t0 = time.perf_counter()
        results = await asyncio.gather(*[one(a, p) for a, p in reqs])
        dt = time.perf_counter() - t0
        ok = [r for r in results
              if r[1] <= slo["ttft_ms"] and r[2] <= slo["itl_ms"]]
        return (sum(r[0] for r in ok) / dt,
                sum(r[0] for r in results) / dt,
                sorted(r[1] for r in results)[len(results) // 2])

    async def drain(tiered):
        deadline = time.perf_counter() + 30
        while tiered.offload_backlog and time.perf_counter() < deadline:
            await asyncio.sleep(0.05)

    e_off, e_on = mk(False), mk(True)
    try:
        # warm programs off the clock (prefill/mixed/decode + import)
        for e in (e_off, e_on):
            await wave(e, seed=1)
        await drain(e_on.tiered)
        m0 = e_on.metrics()
        goodput = {"no_offload": [], "offload": []}
        attained = {"no_offload": [], "offload": []}
        ttft = {"no_offload": [], "offload": []}
        for r in range(rounds):
            for name, e in (("no_offload", e_off), ("offload", e_on)):
                g, a, t = await wave(e, seed=100 + 7 * r)
                goodput[name].append(g)
                attained[name].append(a)
                ttft[name].append(t)
        m1 = e_on.metrics()

        def med(xs):
            return sorted(xs)[len(xs) // 2]

        # warm-prefix TTFT ladder on the offload engine: one fresh tenant
        async def one_ttft(tokens):
            r = {"token_ids": tokens,
                 "sampling_options": {"temperature": 0.0},
                 "stop_conditions": {"max_tokens": 2, "ignore_eos": True}}
            t0 = time.perf_counter()
            first = None
            async for out in e_on.generate(r):
                if out["token_ids"] and first is None:
                    first = time.perf_counter() - t0
            # token-less stream (engine error + recovery) scores inf like
            # the goodput phases' one() — never crash the bench run
            return float("inf") if first is None else first * 1e3

        cold, hbm, dram = [], [], []
        for i in range(3):
            probe = tenant_sys(tenants + 7 + i) + [7] * user_len
            e_on.clear_kv_blocks()
            cold.append(await one_ttft(probe))
            hbm.append(await one_ttft(probe))
            await drain(e_on.tiered)
            e_on.clear_kv_blocks()  # only copy left is DRAM-tier
            dram.append(await one_ttft(probe))

        gp_on, gp_off = med(goodput["offload"]), med(goodput["no_offload"])
        stats = {k: getattr(m1, k, 0) - getattr(m0, k, 0) for k in (
            "kvbm_offload_total", "kvbm_onboard_total", "kvbm_evict_total",
            "kvbm_host_hits_total", "kvbm_host_misses_total")}
        looked_up = (stats["kvbm_host_hits_total"]
                     + stats["kvbm_host_misses_total"])
        return {
            "tenants": tenants, "sys_len": sys_len, "gen": gen,
            "rate_rps": rate_rps, "zipf_a": zipf_a, "n_req": n_req,
            "goodput_tok_s": {"offload": round(gp_on, 2),
                              "no_offload": round(gp_off, 2)},
            "goodput_ratio": round(gp_on / max(gp_off, 1e-9), 3),
            "attained_tok_s": {
                "offload": round(med(attained["offload"]), 2),
                "no_offload": round(med(attained["no_offload"]), 2)},
            "ttft_p50_ms": {
                "offload": round(med(ttft["offload"]), 1),
                "no_offload": round(med(ttft["no_offload"]), 1)},
            "tier_hits": {**{k: int(v) for k, v in stats.items()},
                          "host_hit_rate": round(
                              stats["kvbm_host_hits_total"]
                              / max(looked_up, 1), 3)},
            "ttft_ladder_ms": {
                "cold": round(med(cold), 1),
                "hbm_hit": round(med(hbm), 1),
                "dram_hit": round(med(dram), 1),
                "dram_vs_hbm": round(med(dram) / max(med(hbm), 1e-9), 3),
                "cold_vs_dram": round(med(cold) / max(med(dram), 1e-9), 3),
            },
        }
    finally:
        await e_off.shutdown()
        await e_on.shutdown()


def phase_breakdown(cfg, params, T=32, B=8, table_w=32):
    """Per-phase decode-step shares measured ON DEVICE (VERDICT r5 item
    4): full forward vs no-lm-head vs matmuls-only scans at the serving
    shapes.  attention+norms = no_head - matmuls; head+sampling = full -
    no_head; the matmuls time IS the weight-stream floor.  Interleaved
    iterations + a trivial-program round-trip baseline keep the
    host↔device round trip out of the numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import KVCache
    from dynamo_tpu.models.llama import forward_decode
    from dynamo_tpu.models.quantization import matmul_any

    kv = KVCache.create(cfg, 1 + B * table_w + 8, 16, jnp.bfloat16)
    tokens = jnp.arange(B, dtype=jnp.int32) + 5
    positions = jnp.full((B,), 130, jnp.int32)
    table = jnp.tile(jnp.arange(1, table_w + 1, dtype=jnp.int32), (B, 1))
    x0 = jnp.ones((B, cfg.hidden_size), jnp.bfloat16)

    def scan_full(params, kv, tokens, positions, table):
        def body(carry, _):
            kv, tok, pos = carry
            logits, kv = forward_decode(params, cfg, kv, tok, pos, table)
            nxt = jnp.argmax(logits.astype(jnp.float32), -1).astype(
                jnp.int32)
            return (kv, nxt, pos + 1), ()
        (kv, tok, _), _ = jax.lax.scan(
            body, (kv, tokens, positions), None, length=T)
        return tok

    def scan_no_head(params, kv, tokens, positions, table):
        from dynamo_tpu.models.llama import decode_layers

        def body(carry, _):
            kv, tok, pos = carry
            x = params["embed"][tok] if not isinstance(
                params["embed"], dict) else params["embed"]["q"][tok]
            x, kv = decode_layers(params["layers"], cfg, kv,
                                  x.astype(jnp.bfloat16), pos, table, "xla")
            nxt = (tok + x[:, :8].sum(-1).astype(jnp.int32)) % 97
            return (kv, nxt, pos + 1), ()
        (kv, tok, _), _ = jax.lax.scan(
            body, (kv, tokens, positions), None, length=T)
        return tok

    def scan_matmuls(params, x, tokens):
        lp = params["layers"]

        def body(carry, _):
            x, tok = carry

            def layer(h, w):
                q = matmul_any(h, w["wq"], "bh,hd->bd")
                k = matmul_any(h, w["wk"], "bh,hd->bd")
                v = matmul_any(h, w["wv"], "bh,hd->bd")
                o = (q + jnp.pad(k, ((0, 0), (0, q.shape[1] - k.shape[1])))
                     + jnp.pad(v, ((0, 0), (0, q.shape[1] - v.shape[1]))))
                h = (h + matmul_any(o.astype(h.dtype), w["wo"],
                                    "bd,dh->bh")).astype(h.dtype)
                g = matmul_any(h, w["w_gate"], "bh,hf->bf")
                u = matmul_any(h, w["w_up"], "bh,hf->bf")
                h = (h + matmul_any((g * u).astype(h.dtype), w["w_down"],
                                    "bf,fh->bh")).astype(h.dtype)
                return h, ()

            x, _ = jax.lax.scan(layer, x, lp)
            tok = tok + x[:, :8].sum(-1).astype(jnp.int32)
            return (x, tok), ()
        (x, tok), _ = jax.lax.scan(body, (x, tokens), None, length=T)
        return tok

    def sync(o):
        np.asarray(jax.device_get(o))

    triv = jax.jit(lambda t: t + 1)
    fns = {
        "full": (jax.jit(scan_full),
                 (params, kv, tokens, positions, table)),
        "no_head": (jax.jit(scan_no_head),
                    (params, kv, tokens, positions, table)),
        "matmuls": (jax.jit(scan_matmuls), (params, x0, tokens)),
    }
    for f, a in fns.values():
        sync(f(*a))  # compile off the clock
    sync(triv(tokens))
    times = {k: [] for k in fns}
    rtts = []
    for _ in range(4):
        for k, (f, a) in fns.items():
            t0 = time.perf_counter()
            sync(f(*a))
            times[k].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        sync(triv(tokens))
        rtts.append(time.perf_counter() - t0)
    rtt = min(rtts)
    ms = {k: (min(v) - rtt) / T * 1e3 for k, v in times.items()}
    return {
        "matmul_weight_stream_ms": round(ms["matmuls"], 3),
        "attention_norms_ms": round(max(ms["no_head"] - ms["matmuls"], 0.0),
                                    3),
        "head_sampling_ms": round(max(ms["full"] - ms["no_head"], 0.0), 3),
        "full_step_ms": round(ms["full"], 3),
        "fetch_rtt_ms": round(rtt * 1e3, 1),
        "steps": T,
        "batch": B,
    }


def init_params_int8(cfg, key):
    """Random already-quantized params on device (layout =
    models.quantization.quantize_params; see random_int8_params there —
    shared with the planner profiler's llama-8b mode)."""
    from dynamo_tpu.models.quantization import random_int8_params

    return random_int8_params(cfg, key)


def quantized_param_bytes(cfg):
    """Weight bytes per decode step for an int8-quantized model (q int8 +
    bf16 embed read is a lookup, excluded)."""
    h, hd = cfg.hidden_size, cfg.head_dim_
    nh, nkv, L = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.num_hidden_layers)
    f, V = cfg.intermediate_size, cfg.vocab_size
    per_layer = h * (nh + 2 * nkv) * hd + nh * hd * h + 3 * h * f
    return L * per_layer + h * V


async def main_async():
    import jax
    import jax.numpy as jnp

    from dynamo_tpu import chip, compile_cache
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import init_params
    from dynamo_tpu.models.config import LLAMA_3_1_8B, LLAMA_3_2_1B

    # no chip, no numbers: every phase below runs in THIS process (no
    # child needs the device), and the record names what it ran on
    out = {"device": chip.require_tpu("bench.py")}
    compile_cache.configure()
    # frontend egress saturation (docs/frontend_dataplane.md): ramp
    # concurrent mock SSE streams against the REAL frontend write path
    # for streams-at-knee + per-delta p99, then A/B the batched
    # zero-copy writer against the legacy per-delta writer for
    # CPU-per-token.  Pure asyncio — no device, so it runs before any
    # model phase and survives a device-phase failure.
    from dynamo_tpu.frontend.loadgen import frontend_saturation

    out["frontend_saturation"] = await frontend_saturation(
        log=lambda m: print(m, flush=True)
    )

    # overload control (docs/overload_control.md): mixed-class Poisson
    # load at 2x the knee, with vs without priority classes + shedding +
    # decode preemption — interactive SLO protection and the recovered
    # attained-vs-goodput gap.  MockEngine (real scheduler), no device.
    from dynamo_tpu.frontend.overload import overload_phase

    out["overload"] = await overload_phase(
        log=lambda m: print(m, flush=True)
    )

    cfg = LLAMA_3_2_1B
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    pages_per_seq = (PROMPT_LEN + SUSTAINED_GEN) // 16 + 2

    def ecfg(quant, steps, chain, gen=SUSTAINED_GEN, mixed=0):
        return EngineConfig(
            page_size=16,
            num_pages=1 + 2 * BATCH * pages_per_seq + 32,
            max_num_seqs=2 * BATCH,
            max_prefill_tokens=BATCH * PROMPT_LEN,
            prefill_batch_size=BATCH,
            max_model_len=PROMPT_LEN + gen + 16,
            decode_batch_buckets=[BATCH, 2 * BATCH],
            chunk_buckets=[PROMPT_LEN],
            # sweeps measured on a machine that is gone: r3 (pre-block-KV)
            # preferred int8 96x4 (1724 > 64x4's 1593); r5's
            # block-materialized KV flipped it — ring-buffer attention
            # reads scale with the block length, so 64x4 now wins
            # (interleaved: 2130 vs 96x4's 1861) and both engines run
            # the SAME 64x4 dispatch shape
            decode_steps=steps,
            decode_chain=chain,
            mixed_prefill_tokens=mixed,
            enable_prefix_caching=False,  # raw compute, not cache hits
            quantization=quant,
            fuse_projections=True,
        )

    # headline (round-1/2 protocol for vs_baseline comparability) — the
    # per-round samples ride the JSON so a slow-phase dip is visible
    # as spread rather than a silent regression
    engine = JaxEngine(cfg, params, ecfg("none", 64, 4, gen=GEN_TOKENS),
                       eos_token_ids=[])
    (total, dt, ttft_p50, itl_p50), head_rates = await median_of(
        engine, with_samples=True
    )
    await engine.shutdown()
    out["value"] = round(total / dt, 2)
    out["ttft_p50_ms"] = round(ttft_p50 * 1000, 1)
    out["itl_p50_ms"] = round(itl_p50 * 1000, 2)
    out["headline_samples_tok_s"] = [round(r, 1) for r in head_rates]
    out["headline_spread"] = round(
        max(head_rates) / max(min(head_rates), 1e-9), 3
    )
    out["measurement_notes"] = (
        "in-run spreads are tight (<2-8%); cross-RUN deltas come from "
        "slow phases of the machine (the fetch round trip drifts) that "
        "shift whole runs together — interleaved A/B phases + per-round "
        "samples bound what environment can hide. r5 profiling "
        "(scripts/ablate_{decode,attention}.py): the decode ceiling was "
        "a per-layer KV-scatter + pool-read interaction forcing XLA to "
        "copy the page pool every layer-step (~1.8ms/step at 1B/b8) — "
        "fixed by deferred writes (attend to old pool + self column, "
        "one batched scatter per step); matmul weight streams run at "
        "~720-760 GB/s of the 819 peak; a STATIC greedy sampling "
        "variant replaces the runtime all-greedy cond (~0.1ms/step); "
        "block-materialized KV decode (gather once per 64-step block, "
        "ring buffers, one batched scatter) removed the per-step paged "
        "gather (~1.2ms/step of scattered DMA). step_breakdown_* "
        "fields carry the on-device phase shares."
    )

    # sustained (192-token generations, tuned dispatch): bf16 and int8
    # rounds INTERLEAVE within one run so a slow phase moves both —
    # per-phase samples + spread ride the JSON (a headline that can
    # silently lose 12% to environment is not a measurement)
    e_bf = JaxEngine(cfg, params, ecfg("none", 64, 4), eos_token_ids=[])
    e_q = JaxEngine(cfg, params, ecfg("int8", 64, 4), eos_token_ids=[])
    (bf16_sus, bf_rates, bf_med), (int8_sus, q_rates, _) = (
        await interleaved_ab([e_bf, e_q], rounds=3)
    )
    itl_idle = bf_med[3]
    await e_bf.shutdown()
    await e_q.shutdown()
    del e_bf, e_q  # drop the fused weight copies before the 8B phases
    # on-device per-phase decode-step breakdown (1B bf16): where a step's
    # time goes — the weight-stream floor vs attention vs head/sampling
    out["step_breakdown_1b_bf16"] = phase_breakdown(cfg, params)
    out["int8_tok_s"] = round(int8_sus, 2)
    out["phase_samples_tok_s"] = {
        "bf16": [round(r, 1) for r in bf_rates],
        "int8": [round(r, 1) for r in q_rates],
        "spread_bf16": round(max(bf_rates) / max(min(bf_rates), 1e-9), 3),
        "spread_int8": round(max(q_rates) / max(min(q_rates), 1e-9), 3),
        "int8_vs_bf16_sustained": round(int8_sus / max(bf16_sus, 1e-9), 3),
    }

    # goodput under SLO, 1B: Poisson arrivals over the mixed scheduler
    # (prefills ride decode dispatches — ITL stays flat under load).
    # Every bucket is pinned to ONE shape (prefill batch 1, decode batch
    # 16, chunk 128) so exactly three programs compile — all warmed off
    # the clock; a mid-phase XLA compile costs seconds and would swamp
    # every TTFT.
    engine = JaxEngine(cfg, params, EngineConfig(
        page_size=16, num_pages=1 + 24 * 16 + 32, max_num_seqs=16,
        # up to FOUR prompts ride one mixed dispatch: Poisson bursts
        # clear in one pump iteration instead of queueing one prompt per
        # ~200ms dispatch+fetch cycle (r5: burst-tail TTFTs broke the
        # SLO while ITL had margin); 32-step decode blocks amortize the
        # fetch round trip
        max_prefill_tokens=4 * PROMPT_LEN, prefill_batch_size=4,
        max_model_len=PROMPT_LEN + 96 + 16,
        decode_batch_buckets=[16], chunk_buckets=[PROMPT_LEN],
        table_width_buckets=[16], decode_steps=32, decode_chain=2,
        mixed_prefill_tokens=4 * PROMPT_LEN, enable_prefix_caching=False,
        quantization="int8", fuse_projections=True,
        # block ladder (ISSUE 2): full 32-step blocks while the queue is
        # idle, 1-step blocks (chaining suppressed) the moment prompts
        # are pending — a Poisson arrival's first chunk rides the next
        # dispatch instead of waiting out a 2×32-step chained run
        decode_block_ladder=[1, 4, 8],
    ), eos_token_ids=[])
    # warmup: solo request (prefill + decode programs), then overlap a
    # prefill with a LIVE decode until the mixed program has actually
    # compiled (compiled_variants["mixed"] non-empty) — a racy warmup
    # here leaks a compile of seconds into the measured TTFTs — then
    # walk the block ladder so every rung's program is warm too
    mixed_warm_ok = await warm_mixed(engine)
    mixed_warm_ok = (await warm_ladder(engine)) and mixed_warm_ok
    m0_1b, rungs0_1b = engine.metrics(), engine.rung_histogram
    # rate LADDER up to the knee: one light-load point where attained ≈
    # offered measures SLO compliance, not capacity (VERDICT r3 item 3).
    # Intermediate rungs (6, 12) make repeat_agreement load-bearing —
    # r5's passes disagreed by a full 2x rung ([4.0, 8.0]) and the
    # coarse ladder let the gate pass anyway (VERDICT r5 weak #4)
    k1 = await goodput_knee(
        engine, rates=[2.0, 4.0, 6.0, 8.0, 12.0, 16.0], n_req=50,
        prompt_len=PROMPT_LEN, gen=96, slo=SLO_1B,
    )
    # the rate-4 point keeps round-3 field compatibility
    g1 = next((
        (p["goodput_tok_s"], p["attained_tok_s"], p["ttft_p50_ms"],
         p["itl_p99_ms"], p["slo_met_fraction"])
        for p in k1["sweep"] if p["rate_rps"] == 4.0
    ), None) or (0.0, 0.0, 0.0, 0.0, 0.0)
    # chosen-rung histogram + TTFT attribution over the goodput phases
    # (post-warmup deltas: warmup walks the ladder by design)
    rungs_1b = _rung_delta(engine, rungs0_1b)
    ttft_attr_1b = _ttft_attr_means(engine, m0_1b)
    await engine.shutdown()
    del engine  # fused 1B copy — free before the 8B weights arrive
    import gc

    gc.collect()

    # batch-1 self-speculative decode ITL on a repetitive workload (the
    # VERDICT r5 item-5 lever: steps-per-token, not FLOPs, gates batch-1
    # ITL on a bandwidth-bound chip); reports tokens-per-dispatch and
    # acceptance from the engine's own SpecDecodeStats counters
    out["spec_decode_1b_int8"] = await spec_decode_phase(cfg, params)
    gc.collect()

    # device-resident decode loop A/B (ISSUE 6): continuous chaining vs
    # the fixed chain on the same int8 serving shape, same run — plus
    # the inter-block host-gap percentiles off the step-event timeline
    out["continuous_decode_1b"] = await continuous_phase(cfg, params)
    gc.collect()

    # unified serving loop A/B (ISSUE 15): bursty arrivals splice into
    # the live chain as chunk rows vs falling the chain out per
    # admission — residents' burst-window vs quiet ITL p99 + chain
    # fall-outs per admitted request
    out["bursty_1b"] = await bursty_phase(cfg, params)
    gc.collect()

    # KVBM multi-tier A/B (ISSUE 8): Zipf multi-tenant prefix workload
    # where the hot prefix set dwarfs HBM — offload-on keeps evicted
    # prefixes in the DRAM tier (onboard at admission) vs cold re-prefill;
    # plus the warm-prefix TTFT ladder (cold / HBM-hit / DRAM-hit)
    out["kvbm_zipf"] = await kvbm_zipf_phase(cfg, params)
    gc.collect()

    # disaggregated prefill→decode KV-transfer latency (the missing half
    # of BASELINE.json's metric — VERDICT r5 item 3): a prefill engine
    # exports pages through the real data plane (disagg/transfer.py), a
    # decode engine fetches and continues.  Both lanes measured: the
    # colocated device lane (one-chip reality) and the host TCP lane
    # (what a cross-host deployment rides while the DMA lane stays
    # gated — docs/ROADMAP.md).  TTFT delta vs local prefill rides along.
    out["disagg"] = await disagg_phase(cfg, params)
    out["disagg_kv_transfer_p50_ms"] = (
        out["disagg"]["lane_host"]["kv_transfer_p50_ms"]
    )
    gc.collect()

    # 8B int8 on the chip (~8 GB of weights initialized on device)
    cfg8 = LLAMA_3_1_8B
    params8 = jax.jit(lambda k: init_params_int8(cfg8, k))(
        jax.random.PRNGKey(1)
    )
    jax.block_until_ready(params8)
    e8 = EngineConfig(
        page_size=16, num_pages=1 + BATCH * pages_per_seq + 16,
        max_num_seqs=BATCH, max_prefill_tokens=BATCH * PROMPT_LEN,
        prefill_batch_size=BATCH, max_model_len=PROMPT_LEN + SUSTAINED_GEN + 16,
        decode_batch_buckets=[BATCH], chunk_buckets=[PROMPT_LEN],
        decode_steps=64, decode_chain=4, enable_prefix_caching=False,
        # no fusion at 8B: concatenating ~8GB of resident weights doubles
        # peak HBM (OOM), and the 4096-wide kernels are already large
        # enough to run bandwidth-bound
    )
    engine8 = JaxEngine(cfg8, params8, e8, eos_token_ids=[])
    t8, dt8, ttft8, itl8 = await median_of(engine8,
                                           gen_tokens=SUSTAINED_GEN)
    await engine8.shutdown()
    tps8 = t8 / dt8
    breakdown8 = phase_breakdown(cfg8, params8)
    # drop the throughput engine's KV pool before building TWO goodput
    # engines (ladder A/B) — ~1 GB of pages each beside 8 GB of weights
    del engine8
    import gc

    gc.collect()

    # 8B goodput: REAL Poisson arrivals over the mixed scheduler (the
    # round-3 batch-burst proxy is gone), swept up a rate ladder to the
    # knee.  Shapes pinned to one prefill/decode/chunk bucket each so
    # the programs all warm off the clock.  Run as an interleaved A/B —
    # block ladder ON vs fixed 32-step blocks — so the ISSUE 2 win
    # (prompts admitted within one short rung instead of a chained
    # 2×32-step run) is measured against environment drift, not
    # inferred (VERDICT #1)
    def ecfg8g(ladder):
        return EngineConfig(
            page_size=16, num_pages=1 + 12 * 16 + 32, max_num_seqs=8,
            # two prompts per mixed dispatch (burst handling, see the 1B
            # goodput engine); 32-step decode blocks amortize the fetch
            # round trip when the queue is idle
            max_prefill_tokens=2 * PROMPT_LEN, prefill_batch_size=2,
            max_model_len=PROMPT_LEN + 96 + 16,
            decode_batch_buckets=[8], chunk_buckets=[PROMPT_LEN],
            table_width_buckets=[16], decode_steps=32, decode_chain=2,
            mixed_prefill_tokens=2 * PROMPT_LEN,
            enable_prefix_caching=False,
            decode_block_ladder=ladder,
        )

    engine8g = JaxEngine(cfg8, params8, ecfg8g([1, 4, 8]), eos_token_ids=[])
    engine8f = JaxEngine(cfg8, params8, ecfg8g(None), eos_token_ids=[])
    mixed_warm_ok8 = (await warm_mixed(engine8g)) & (await warm_mixed(engine8f))
    mixed_warm_ok8 = (await warm_ladder(engine8g)) and mixed_warm_ok8
    # post-warmup snapshots: the arms warm asymmetrically (warm_ladder
    # only runs on the laddered engine), so the reported attribution
    # means must cover the measured traffic only
    m0_8g, rungs0_8g = engine8g.metrics(), engine8g.rung_histogram
    m0_8f = engine8f.metrics()
    # half-rungs (1.5, 3) for the same repeat-agreement reason as the 1B
    # ladder — r5's 8B passes disagreed 2.0 vs 1.0 (VERDICT r5 weak #4)
    k8, k8_fixed = await goodput_knee_ab(
        [engine8g, engine8f], rates=[1.0, 1.5, 2.0, 3.0, 4.0], n_req=50,
        prompt_len=PROMPT_LEN, gen=64, slo=SLO_8B,
    )
    rungs_8b = _rung_delta(engine8g, rungs0_8g)
    ttft_attr_8b = _ttft_attr_means(engine8g, m0_8g)
    ttft_attr_8b_fixed = _ttft_attr_means(engine8f, m0_8f)
    await engine8g.shutdown()
    await engine8f.shutdown()
    # release the ~8GB of 8B weights before the remaining 1B phases —
    # holding them through the ISL-2000 + prefix-cache engines OOMs HBM
    del engine8g, engine8f, params8
    gc.collect()

    gb_1b_bf16 = cfg.num_params() * 2 / 1e9
    gb_1b_int8 = quantized_param_bytes(cfg) / 1e9
    gb_8b_int8 = quantized_param_bytes(cfg8) / 1e9
    out["weight_read_gbps"] = round(max(
        bf16_sus / BATCH * gb_1b_bf16,
        int8_sus / BATCH * gb_1b_int8,
        tps8 / BATCH * gb_8b_int8,
    ), 1)
    out["models"] = {
        "llama-3.2-1b": {
            **({} if mixed_warm_ok else {"goodput_warmup_failed": True}),
            "bf16_tok_s": round(total / dt, 2),
            "bf16_sustained_tok_s": round(bf16_sus, 2),
            "int8_sustained_tok_s": round(int8_sus, 2),
            "goodput_at_slo_tok_s": round(g1[0], 2),
            "attained_tok_s": round(g1[1], 2),
            "slo": SLO_1B,
            "slo_met_fraction": round(g1[4], 3),
            "ttft_p50_under_load_ms": round(g1[2], 1),
            "itl_p99_under_prefill_ms": round(g1[3], 2),
            "itl_p50_idle_ms": round(itl_idle * 1e3, 2),
            "max_goodput_at_slo_tok_s": k1["max_goodput_at_slo_tok_s"],
            "knee_rate_rps": k1["knee_rate_rps"],
            "n_req": k1["n_req"],
            "repeat_agreement": k1["repeat_agreement"],
            "knees_per_pass": k1["knees_per_pass"],
            **({} if "knee_disagreement" not in k1
               else {"knee_disagreement": k1["knee_disagreement"]}),
            "goodput_sweep": k1["sweep"],
            # block-ladder telemetry over the goodput phases: which rungs
            # actually dispatched, and where each request's TTFT went
            "rung_dispatches": {str(k): v for k, v in rungs_1b.items()},
            "ttft_attribution_ms": ttft_attr_1b,
        },
        "llama-3.1-8b-int8": {
            **({} if mixed_warm_ok8 else {"goodput_warmup_failed": True}),
            "tok_s": round(tps8, 2),
            "ttft_p50_ms": round(ttft8 * 1e3, 1),
            "itl_p50_ms": round(itl8 * 1e3, 2),
            "weight_read_gbps": round(tps8 / BATCH * gb_8b_int8, 1),
            # which kernel eats the roofline gap (VERDICT r5 item 4)
            "step_breakdown_ms": breakdown8,
            "max_goodput_at_slo_tok_s": k8["max_goodput_at_slo_tok_s"],
            "knee_rate_rps": k8["knee_rate_rps"],
            "n_req": k8["n_req"],
            "repeat_agreement": k8["repeat_agreement"],
            "knees_per_pass": k8["knees_per_pass"],
            **({} if "knee_disagreement" not in k8
               else {"knee_disagreement": k8["knee_disagreement"]}),
            "goodput_sweep": k8["sweep"],
            "slo": SLO_8B,
            # interleaved A/B: block ladder on (the headline above) vs
            # fixed 32-step blocks, same run, alternating passes
            "ladder_ab": {
                "ladder": {
                    "max_goodput_at_slo_tok_s":
                        k8["max_goodput_at_slo_tok_s"],
                    "knee_rate_rps": k8["knee_rate_rps"],
                    "knees_per_pass": k8["knees_per_pass"],
                    "rung_dispatches":
                        {str(k): v for k, v in rungs_8b.items()},
                    "ttft_attribution_ms": ttft_attr_8b,
                },
                "fixed": {
                    "max_goodput_at_slo_tok_s":
                        k8_fixed["max_goodput_at_slo_tok_s"],
                    "knee_rate_rps": k8_fixed["knee_rate_rps"],
                    "knees_per_pass": k8_fixed["knees_per_pass"],
                    "ttft_attribution_ms": ttft_attr_8b_fixed,
                    "goodput_sweep": k8_fixed["sweep"],
                },
            },
        },
    }

    # reference-protocol operating point: ISL 2000 / OSL 256 swept over a
    # concurrency grid (benchmarking.md:70-75 sweeps concurrency; the
    # single fixed point was VERDICT r4 weak #9) on the 1B bf16 engine
    PI, GI = 2000, 256
    CONC = [1, 2, 4, 8]
    pages_i = (PI + GI) // 16 + 2
    engine_i = JaxEngine(cfg, params, EngineConfig(
        page_size=16, num_pages=1 + CONC[-1] * pages_i + 16,
        max_num_seqs=CONC[-1], max_prefill_tokens=2048,
        prefill_batch_size=1, max_model_len=PI + GI + 16,
        decode_batch_buckets=list(CONC), chunk_buckets=[2048],
        decode_steps=64, decode_chain=4,
        # explicit prefill-first policy for the batch-throughput phase:
        # at 2000-token prompts every mixed slice drags a 64-step decode
        # block (TTFT balloons) and each (decode bucket x chunk) mixed
        # shape is its own compile — the goodput phases
        # already measure mixed ITL-flatness; prompts go first here, and
        # r5's chain gating stops fused chains starving them
        mixed_prefill_tokens=0,
        # ONE table-width bucket: the default pow2 ladder crosses
        # 128->142 pages mid-generation, compiling a fresh decode program
        # ON THE CLOCK — the r5 itl/tok_s collapse
        table_width_buckets=[pages_i],
        enable_prefix_caching=False, fuse_projections=True,
    ), eos_token_ids=[])
    for b in CONC:  # warm every decode bucket off the clock
        await run_round(engine_i, 0, batch=b, prompt_len=PI, gen_tokens=8)
    sweep_i = []
    for b in CONC:
        ti, dti, ttft_i, itl_i = await run_round(
            engine_i, 9000 + b, batch=b, prompt_len=PI, gen_tokens=GI,
        )
        sweep_i.append({
            "concurrency": b,
            "tok_s": round(ti / dti, 2),
            "ttft_p50_ms": round(ttft_i * 1e3, 1),
            "itl_p50_ms": round(itl_i * 1e3, 2),
        })
    await engine_i.shutdown()
    p4 = next(p for p in sweep_i if p["concurrency"] == 4)
    out["isl2000_osl256"] = {
        # batch-4 flat fields keep round-over-round comparability
        "tok_s": p4["tok_s"], "ttft_p50_ms": p4["ttft_p50_ms"],
        "itl_p50_ms": p4["itl_p50_ms"], "batch": 4,
        "concurrency_sweep": sweep_i,
    }

    # prefix-cache TTFT win (the reference headlines a 40% TTFT
    # improvement from KV reuse, architecture.md:95)
    P2, B2 = 1024, 4
    pages2 = P2 // 16 + 2
    engine = JaxEngine(cfg, params, EngineConfig(
        page_size=16, num_pages=1 + 2 * B2 * pages2 + 32, max_num_seqs=B2,
        max_prefill_tokens=B2 * P2, prefill_batch_size=B2,
        max_model_len=P2 + 32, decode_batch_buckets=[B2],
        chunk_buckets=[16, P2], enable_prefix_caching=True,
    ), eos_token_ids=[])

    async def long_round(base):
        _, _, t, _ = await run_round(
            engine, base, batch=B2, prompt_len=P2, gen_tokens=2, stride=11
        )
        return t

    await long_round(0)
    await long_round(0)
    cold = await long_round(7000)
    warm = await long_round(7000)
    await engine.shutdown()
    out["prefix_cache_ttft_ms"] = {
        "cold": round(cold * 1000, 1), "warm": round(warm * 1000, 1),
    }
    return out


def previous_round_value():
    best = None

    def round_num(p):
        m = re.search(r"BENCH_r(\d+)\.json", p)
        return int(m.group(1)) if m else -1

    for path in sorted(glob.glob("BENCH_r*.json"), key=round_num):
        try:
            with open(path) as f:
                d = json.load(f)
            # the driver wraps the bench line as {"parsed": {...}, ...}
            if "parsed" in d and isinstance(d["parsed"], dict):
                d = d["parsed"]
            if d.get("unit") == "tok/s":
                best = d.get("value")
        except (OSError, ValueError):
            pass
    return best


def _compact_summary(full):
    """The flagship numbers as a handful of scalars: headline, sustained
    A/B, goodput knees, disagg p50, spec-decode phase.  Small enough
    that no artifact tail can truncate it away (VERDICT r5 weak #2)."""
    m1 = full.get("models", {}).get("llama-3.2-1b", {})
    m8 = full.get("models", {}).get("llama-3.1-8b-int8", {})
    spec = full.get("spec_decode_1b_int8", {})
    cc = full.get("continuous_decode_1b", {})
    bb = full.get("bursty_1b", {})
    kz = full.get("kvbm_zipf", {})
    fs = full.get("frontend_saturation", {})
    ov = full.get("overload", {})
    phase = full.get("phase_samples_tok_s", {})
    return {
        "headline_bf16_tok_s": full.get("value"),
        "ttft_p50_ms": full.get("ttft_p50_ms"),
        "itl_p50_ms": full.get("itl_p50_ms"),
        "bf16_sustained_tok_s": m1.get("bf16_sustained_tok_s"),
        "int8_sustained_tok_s": m1.get("int8_sustained_tok_s"),
        "int8_vs_bf16_sustained": phase.get("int8_vs_bf16_sustained"),
        "goodput_1b_max_tok_s": m1.get("max_goodput_at_slo_tok_s"),
        "goodput_1b_knee_rps": m1.get("knee_rate_rps"),
        "goodput_1b_knees_per_pass": m1.get("knees_per_pass"),
        "goodput_8b_max_tok_s": m8.get("max_goodput_at_slo_tok_s"),
        "goodput_8b_knee_rps": m8.get("knee_rate_rps"),
        "goodput_8b_knees_per_pass": m8.get("knees_per_pass"),
        # ladder A/B headline: fixed-block arm + the TTFT share the
        # ladder exists to shrink (block-wait), both arms
        "goodput_8b_fixed_max_tok_s": m8.get("ladder_ab", {})
        .get("fixed", {}).get("max_goodput_at_slo_tok_s"),
        "ttft_block_wait_8b_ladder_ms": m8.get("ladder_ab", {})
        .get("ladder", {}).get("ttft_attribution_ms", {})
        .get("block_wait_ms_mean"),
        "ttft_block_wait_8b_fixed_ms": m8.get("ladder_ab", {})
        .get("fixed", {}).get("ttft_attribution_ms", {})
        .get("block_wait_ms_mean"),
        "tok_s_8b": m8.get("tok_s"),
        "weight_read_gbps": full.get("weight_read_gbps"),
        "disagg_kv_transfer_p50_ms": full.get("disagg_kv_transfer_p50_ms"),
        "disagg_ttft_delta_ms": full.get("disagg", {}).get("ttft_delta_ms"),
        "isl2000_c4_tok_s": full.get("isl2000_osl256", {}).get("tok_s"),
        "prefix_cache_ttft_ms": full.get("prefix_cache_ttft_ms"),
        "spec_itl_plain_p50_ms": spec.get("itl_plain_p50_ms"),
        "spec_itl_spec_p50_ms": spec.get("itl_spec_p50_ms"),
        "spec_itl_ratio": spec.get("itl_ratio"),
        "spec_tokens_per_dispatch": spec.get("tokens_per_dispatch"),
        "spec_acceptance_rate": spec.get("acceptance_rate"),
        # device-resident decode loop A/B (ISSUE 6): fixed-chain vs
        # continuous ITL + the inter-block host-gap percentiles
        "itl_1b_chained_ms": cc.get("itl_p50_chained_ms"),
        "itl_1b_continuous_ms": cc.get("itl_p50_continuous_ms"),
        "cc_itl_ratio": cc.get("itl_ratio"),
        "host_gap_ms_p50": (cc.get("host_gap_ms") or {}).get("p50_ms"),
        "host_gap_ms_p99": (cc.get("host_gap_ms") or {}).get("p99_ms"),
        # unified serving loop A/B (ISSUE 15): burst-window decode ITL
        # p99 split-vs-unified + fall-outs per admitted arrival
        "bursty_itl_p99_burst_unified_ms": (bb.get("unified") or {})
        .get("itl_p99_burst_ms"),
        "bursty_itl_p99_burst_split_ms": (bb.get("split") or {})
        .get("itl_p99_burst_ms"),
        "bursty_burst_p99_split_vs_unified": bb.get(
            "burst_p99_split_vs_unified"),
        "bursty_fallout_per_admit_unified": (bb.get("unified") or {})
        .get("fallout_per_admit"),
        "bursty_fallout_per_admit_split": (bb.get("split") or {})
        .get("fallout_per_admit"),
        # KVBM Zipf multi-tenant prefix A/B (ISSUE 8): aggregate goodput
        # offload-on vs no-offload + the warm-prefix TTFT tier ladder
        "kvbm_zipf_goodput_ratio": kz.get("goodput_ratio"),
        "kvbm_zipf_goodput_offload_tok_s": (kz.get("goodput_tok_s") or {})
        .get("offload"),
        "kvbm_zipf_goodput_no_offload_tok_s": (kz.get("goodput_tok_s") or {})
        .get("no_offload"),
        "kvbm_ttft_dram_vs_hbm": (kz.get("ttft_ladder_ms") or {})
        .get("dram_vs_hbm"),
        "kvbm_ttft_cold_vs_dram": (kz.get("ttft_ladder_ms") or {})
        .get("cold_vs_dram"),
        "kvbm_host_hit_rate": (kz.get("tier_hits") or {})
        .get("host_hit_rate"),
        # frontend egress data plane (ISSUE 16): concurrent-stream knee
        # + batched-vs-legacy writer CPU-per-token A/B
        "frontend_streams_at_knee": fs.get("streams_at_knee"),
        "frontend_delta_p99_ms_at_knee": fs.get("delta_p99_ms_at_knee"),
        "frontend_cpu_us_per_token": fs.get("cpu_us_per_token"),
        "frontend_cpu_us_per_token_legacy": fs.get(
            "cpu_us_per_token_legacy"),
        "frontend_cpu_per_token_ratio": fs.get("cpu_per_token_ratio"),
        # overload control (ISSUE 18): per-class SLO at 2x knee +
        # attained-vs-goodput gap recovered by shedding/preemption
        "overload_interactive_slo_met": ov.get("interactive_slo_met"),
        "overload_batch_slo_met": ov.get("batch_slo_met"),
        "overload_gap_cut": ov.get("gap_cut"),
        "overload_gap_on_tok_s": (ov.get("on") or {}).get("gap_tok_s"),
        "overload_gap_off_tok_s": (ov.get("off") or {}).get("gap_tok_s"),
        "overload_batch_shed": ((ov.get("on") or {}).get("classes") or {})
        .get("batch", {}).get("shed"),
    }


def main():
    out = asyncio.run(main_async())
    prev = previous_round_value()
    vs = round(out["value"] / prev, 3) if prev else 1.0
    record = {
        "metric": "llama1b_serve_decode_throughput",
        "value": out["value"],
        "unit": "tok/s",
        "vs_baseline": vs,
        **{k: v for k, v in out.items() if k != "value"},
    }
    # the FULL record goes to a committed file: the driver's stdout tail
    # repeatedly truncated the head of this (large) JSON line and the
    # round's flagship numbers survived only in prose (VERDICT r5
    # weak #2)
    with open("BENCH_full.json", "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps(record))
    # …and the compact summary prints LAST so any tail keeps it.  It is
    # itself a valid {metric, value, unit, vs_baseline} record, so a
    # parser that takes the final JSON line still gets the headline.
    print(json.dumps({
        "metric": "llama1b_serve_decode_throughput",
        "value": out["value"],
        "unit": "tok/s",
        "vs_baseline": vs,
        "full_results": "BENCH_full.json",
        "summary": _compact_summary(record),
    }))


if __name__ == "__main__":
    main()
