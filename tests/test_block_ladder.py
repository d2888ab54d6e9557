"""Adaptive decode-block sizing ("block ladder", docs/adaptive_dispatch.md):
the scheduler picks the decode-block rung per dispatch — full blocks while
the prompt queue is empty, the shortest rung (chaining suppressed) while
prompts are pending — so a waiting prompt rides the next mixed dispatch
within one short block instead of a full chained run.

Correctness claims pinned here:
- tokens are schedule-independent: any mix of rung sizes produces the
  SAME stream as fixed blocks, for greedy AND seeded sampling AND the
  speculative-verify path (per-row PRNG counters are a function of the
  tokens emitted, never of block boundaries);
- rung selection + chain suppression follow the queue state;
- a prompt arriving mid-decode is admitted within one short-rung block
  (the dispatch-trace test — the CPU-verifiable half of ISSUE 2's
  acceptance criterion);
- the compiled-variant count is bounded by ladder size × variant keys
  (the compile-blowup tripwire).
"""

import asyncio
import itertools

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.page_pool import PagePool
from dynamo_tpu.engine.scheduler import SamplingOptions, Scheduler, Sequence
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.testing import dispatches


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_engine(setup, **over):
    cfg, params = setup
    defaults = dict(
        page_size=8, num_pages=128, max_num_seqs=4,
        max_prefill_tokens=16, max_model_len=256, decode_steps=8,
    )
    defaults.update(over)
    return JaxEngine(cfg, params, EngineConfig(**defaults),
                     eos_token_ids=[], kv_dtype=jnp.float32)


def req(tokens, max_tokens=10, **so):
    return {
        "token_ids": tokens,
        "sampling_options": {"temperature": 0.0, **so},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }


async def collect(engine, request):
    out, deltas = [], []
    async for delta in engine.generate(request):
        assert delta.get("finish_reason") != "error", delta
        out.extend(delta["token_ids"])
        deltas.append(delta)
    return out, deltas


PROMPTS = [
    [1, 2, 3],                                 # short: decoding early
    [(7 * j) % 101 + 1 for j in range(60)],    # long: chunked prefill
    [(3 * j) % 97 + 1 for j in range(45)],     # long: chunked prefill
    [9, 8, 7, 6, 5],
]


async def _staggered(engine, reqs, stagger=0.05):
    async def one(i, r):
        await asyncio.sleep(stagger * i)
        return (await collect(engine, r))[0]

    return await asyncio.gather(*[one(i, r) for i, r in enumerate(reqs)])


# -- config ----------------------------------------------------------------- #


def test_ladder_config_normalized():
    cfg = EngineConfig(decode_steps=8, decode_block_ladder=[4, 1, 4, 2])
    # sorted, deduped, decode_steps appended as the top rung
    assert cfg.decode_block_ladder == [1, 2, 4, 8]
    assert cfg.block_ladder == (1, 2, 4, 8)
    assert EngineConfig(decode_steps=8).block_ladder == (8,)


def test_ladder_config_rejects_bad_rungs():
    with pytest.raises(ValueError, match="positive"):
        EngineConfig(decode_steps=8, decode_block_ladder=[0, 4])
    with pytest.raises(ValueError, match="exceed decode_steps"):
        EngineConfig(decode_steps=8, decode_block_ladder=[1, 16])


# -- scheduler policy ------------------------------------------------------- #


def _sched(**over):
    cfg = EngineConfig(page_size=8, num_pages=64, decode_steps=8,
                       decode_block_ladder=[1, 2, 4], **over)
    return Scheduler(cfg, PagePool(64, 8)), cfg


def test_rung_ramps_up_while_quiet():
    sched, _ = _sched()
    got = [sched.select_decode_rung() for _ in range(5)]
    # climbs one rung per quiet dispatch; chaining only at the top rung
    assert got == [(1, False), (2, False), (4, False), (8, True), (8, True)]


def test_rung_drops_and_suppresses_chain_when_waiting():
    sched, _ = _sched()
    for _ in range(4):
        sched.select_decode_rung()  # reach the top rung
    seq = Sequence("r1", [1, 2, 3], SamplingOptions(max_tokens=4))
    sched.add(seq)
    # non-empty waiting queue: shortest rung, chaining suppressed, and
    # the ramp restarts from the bottom once the queue drains
    assert sched.select_decode_rung() == (1, False)
    assert seq.t_seen is not None
    sched.waiting.clear()
    assert sched.select_decode_rung() == (1, False)
    assert sched.select_decode_rung() == (2, False)


def test_rung_short_while_prefill_pending():
    sched, _ = _sched()
    seq = Sequence("r1", list(range(1, 40)), SamplingOptions(max_tokens=4))
    seq.status = "running"
    sched.running.append(seq)  # mid-chunked-prefill
    assert sched.prompts_pending()
    assert sched.select_decode_rung() == (1, False)
    seq.num_computed = seq.prompt_len  # prefill done
    assert not sched.prompts_pending()
    assert sched.select_decode_rung() == (1, False)  # ramp climbs from 0
    assert sched.select_decode_rung() == (2, False)


def test_starved_waiting_prompt_does_not_pin_short_rung():
    """A waiting prompt that CANNOT be admitted (slots or pages
    exhausted) must not pin every decode to 1-step unchained dispatches
    — short rungs buy a capacity-blocked prompt nothing, and its wait
    is queue-wait, not block-wait."""
    sched, cfg = _sched(max_num_seqs=1)
    runner = Sequence("r0", [1, 2], SamplingOptions(max_tokens=99))
    runner.status = "running"
    runner.num_computed = 2  # prefill done, decoding
    sched.running.append(runner)
    sched.add(Sequence("r1", [3, 4], SamplingOptions(max_tokens=4)))
    assert not sched.prompts_pending()  # no free slot: not admissible
    assert sched.select_decode_rung() == (1, False)  # ramp, not forced
    assert sched.select_decode_rung() == (2, False)
    # capacity frees -> the same waiting prompt forces the short rung
    sched.running.clear()
    assert sched.prompts_pending()
    assert sched.select_decode_rung() == (1, False)
    assert sched.select_decode_rung() == (1, False)  # stays pinned


def test_no_ladder_keeps_full_blocks_and_chaining():
    cfg = EngineConfig(page_size=8, num_pages=64, decode_steps=8)
    sched = Scheduler(cfg, PagePool(64, 8))
    sched.add(Sequence("r1", [1, 2], SamplingOptions(max_tokens=4)))
    # ladder off: fixed decode_steps blocks, chaining allowed — the
    # pre-ladder behavior, bit for bit
    assert sched.select_decode_rung() == (8, True)


# -- token identity across rung schedules ----------------------------------- #


def _scripted_rungs(engine, schedule):
    """Replace the engine's rung policy with a scripted cycle (mixed
    rung sizes on demand, independent of queue state)."""
    it = itertools.cycle(schedule)
    engine.scheduler.select_decode_rung = lambda: (next(it), False)


async def test_scripted_rungs_match_fixed_blocks(setup):
    """A decode stream cut 8,1,2,4,... produces the SAME tokens as 8,8:
    greedy and seeded sampling (PRNG counters are per emitted token,
    never per block boundary)."""
    def reqs():
        return [
            req(PROMPTS[0], max_tokens=21),
            req(PROMPTS[3], max_tokens=21, temperature=0.9, seed=7),
            req(PROMPTS[1], max_tokens=15, temperature=0.7, seed=123),
        ]

    fixed = make_engine(setup, decode_chain=1)
    want = await _staggered(fixed, reqs())
    await fixed.shutdown()

    laddered = make_engine(setup, decode_block_ladder=[1, 2, 4],
                           decode_chain=1)
    _scripted_rungs(laddered, [8, 1, 2, 4])
    got = await _staggered(laddered, reqs())
    await laddered.shutdown()
    assert got == want


async def test_ladder_policy_matches_fixed_blocks(setup):
    """The real policy (rungs driven by live queue state) under
    staggered concurrent traffic is token-identical to fixed blocks,
    greedy AND seeded sampling."""
    def reqs():
        out = [req(p, max_tokens=10) for p in PROMPTS]
        out[2] = req(PROMPTS[2], max_tokens=10, temperature=0.8, seed=31)
        return out

    a = make_engine(setup, decode_block_ladder=[1, 2, 4], decode_chain=2)
    got = await _staggered(a, reqs())
    hist = a.rung_histogram
    await a.shutdown()
    assert sum(hist.values()) > 0 and min(hist) < 8, hist

    b = make_engine(setup, decode_chain=2)
    want = await _staggered(b, reqs())
    await b.shutdown()
    assert got == want


async def test_spec_decode_with_ladder_matches_plain(setup):
    """Speculative decoding composes with the ladder: the draft-verify
    path samples every position from the same (seed, counter) stream
    regardless of how the surrounding decode blocks were cut, so seeded
    streams stay token-identical with the ladder on and off."""
    period = [13 + (i % 4) for i in range(40)]

    def reqs():
        return [
            req(period, max_tokens=24),
            req(period[1:], max_tokens=24, temperature=0.9, seed=5),
        ]

    a = make_engine(setup, speculative_ngram_k=2,
                    decode_block_ladder=[1, 2])
    got = await _staggered(a, reqs())
    spec_dispatches = a.metrics().spec_dispatches_total
    await a.shutdown()
    assert spec_dispatches > 0  # the spec path actually ran

    b = make_engine(setup, speculative_ngram_k=2)
    want = await _staggered(b, reqs())
    await b.shutdown()
    assert got == want


# -- dispatch trace: admission within one short rung ------------------------ #


async def test_prompt_admitted_within_one_short_rung(setup):
    """ISSUE 2 acceptance: a prompt arriving mid-decode is admitted
    within one short-rung block — never behind a full decode_steps
    block or a chained run — and the decoded tokens match the
    fixed-block schedule."""
    async def drive(engine):
        first = asyncio.Event()
        outs = {}

        async def decoder():
            outs["a"], _ = await collect(
                engine, req([1, 2, 3], max_tokens=40))

        async def watcher():
            # wait until the decode stream is genuinely running
            while not any(e["kind"] in ("decode", "fused")
                          for e in dispatches(engine)):
                await asyncio.sleep(0.002)
            first.set()

        async def prefiller():
            await first.wait()
            outs["b"], _ = await collect(
                engine, req(list(range(1, 25)), max_tokens=4))

        await asyncio.gather(decoder(), watcher(), prefiller())
        await engine.shutdown()
        return outs, dispatches(engine)

    laddered = make_engine(setup, decode_block_ladder=[1],
                           decode_chain=4, max_prefill_tokens=32)
    got, trace = await drive(laddered)
    ladder = laddered.cfg.block_ladder
    # the prompt rode a prefill-bearing dispatch...
    assert any(e["kind"] in ("mixed", "prefill") for e in trace)
    # ...and every decode-bearing dispatch planned while it (or any
    # prompt) was pending used the SHORTEST rung — the full-block /
    # chained commitment the ladder exists to avoid never happened
    pending_decodes = [e for e in trace
                       if e["kind"] in ("decode", "mixed") and e["pending"]]
    assert pending_decodes, trace
    assert all(e["n_steps"] == ladder[0] for e in pending_decodes), trace
    # admitted within ONE short-rung block: between the scheduler first
    # seeing the prompt (the first pending dispatch) and the prompt's
    # prefill-bearing dispatch, at most ladder[0] decode steps ran.
    # (The second request only launches after a decode dispatch exists,
    # so its prefill is the first prefill-bearing entry after one.)
    t_decode0 = min(e["t"] for e in trace
                    if e["kind"] in ("decode", "fused"))
    t_admit = min(e["t"] for e in trace
                  if e["kind"] in ("mixed", "prefill")
                  and e["t"] > t_decode0)
    steps_between = sum(
        e["n_steps"] * e["blocks"] for e in trace
        if e["kind"] in ("decode", "fused") and e["pending"]
        and t_decode0 <= e["t"] < t_admit
    )
    assert steps_between <= ladder[0], (steps_between, trace)

    fixed = make_engine(setup, decode_chain=4, max_prefill_tokens=32)
    want, _ = await drive(fixed)
    assert got == want


# -- continuous chaining (device-resident decode loop, ISSUE 6) ------------- #


async def test_continuous_chain_composes_with_ladder(setup):
    """The device-resident loop engages at the ladder's top rung only
    (rungs stay the scan lengths; short rungs keep the per-dispatch
    path for admission latency) and stays token-identical to the fixed
    engine under the live policy, greedy AND seeded."""
    def reqs():
        out = [req(p, max_tokens=12) for p in PROMPTS]
        out[2] = req(PROMPTS[2], max_tokens=12, temperature=0.8, seed=31)
        return out

    cc = make_engine(setup, decode_block_ladder=[1, 2, 4],
                     decode_chain=2, decode_continuous=True)
    got = await _staggered(cc, reqs())
    m = cc.metrics()
    await cc.shutdown()
    assert m.decode_cc_chains_total > 0  # the loop actually engaged

    fixed = make_engine(setup, decode_block_ladder=[1, 2, 4],
                        decode_chain=2)
    want = await _staggered(fixed, reqs())
    await fixed.shutdown()
    assert got == want


async def test_continuous_chain_falls_out_on_mid_chain_admission(setup):
    """ISSUE 6 satellite: a prompt arriving while an open-ended chain is
    in flight makes the chain FALL OUT (the scheduler's pending-add /
    `_admit_check` signals) and the prompt rides the next mixed/prefill
    dispatch instead of waiting for a fixed horizon to drain."""
    engine = make_engine(setup, decode_continuous=True, decode_chain=2,
                         fuse_prefill_decode=False,
                         max_prefill_tokens=32, max_model_len=512,
                         num_pages=256)

    async def long_decode():
        return (await collect(
            engine, req([1, 2, 3], max_tokens=400)))[0]

    task = asyncio.ensure_future(long_decode())
    # wait until the continuous chain is genuinely in flight
    while not any(e["kind"] == "decode" for e in dispatches(engine)):
        await asyncio.sleep(0.005)
    toks_b, _ = await collect(engine, req(list(range(1, 25)), max_tokens=4))
    assert len(toks_b) == 4
    task.cancel()  # generate()'s finally aborts the long stream
    try:
        await task
    except asyncio.CancelledError:
        pass
    fallouts = [e[3]["fallout"] for e in engine.events.snapshot()
                if e[2] == "decode_chain"]
    # the in-flight chain fell out on the admission-side signal...
    assert any(f in ("pending_work", "admit") for f in fallouts), fallouts
    # ...and the prompt rode a prefill-bearing dispatch
    trace = dispatches(engine)
    assert any(e["kind"] in ("mixed", "prefill") for e in trace), trace
    await engine.shutdown()


async def test_splice_composes_with_ladder(setup):
    """ISSUE 15 × ladder composition: chunk rows ride the TOP rung's
    open-ended chain (the only rung where chaining engages), a batch
    with a free padding slot splices the arrival instead of falling
    out, and every stream — greedy and seeded co-residents plus the
    long-prompt arrival — is byte-identical to the fall-out engine
    (prefill_chunk_tokens=0) under the same mid-chain admission."""
    def base_reqs():
        # long budgets: the chain must still be LIVE (several top-rung
        # blocks to go) when the arrival lands, or the admission takes
        # the ordinary between-chains path and nothing splices
        out = [req(PROMPTS[0], max_tokens=96),
               req(PROMPTS[3], max_tokens=96, temperature=0.8),
               req([4, 5, 6], max_tokens=96)]
        out[1]["sampling_options"]["seed"] = 17
        return out

    async def drive(engine):
        top = engine.cfg.block_ladder[-1]
        futs = [asyncio.ensure_future(collect(engine, r))
                for r in base_reqs()]
        # wait for a top-rung decode dispatch: chaining (and therefore
        # the splice window) only exists there
        while not any(e["kind"] == "decode" and e["n_steps"] == top
                      for e in dispatches(engine)):
            await asyncio.sleep(0.005)
        late = (await collect(engine, req(PROMPTS[1], max_tokens=6)))[0]
        rest = [r[0] for r in await asyncio.gather(*futs)]
        return rest + [late]

    unified = make_engine(setup, decode_block_ladder=[1, 2, 4],
                          decode_chain=2, decode_continuous=True)
    got = await drive(unified)
    ev = unified.events.snapshot()
    await unified.shutdown()
    fed = [e[3] for e in ev if e[2] == "decode_block"
           and e[3].get("chunk_rows", 0) > 0]
    assert fed, "chunk rows never rode the chain"
    # chunk blocks ran at the ladder's top rung — rungs stayed the
    # scan lengths, chunking didn't add a rung
    top = unified.cfg.block_ladder[-1]
    assert all(e["rung"] == top for e in fed), fed

    split = make_engine(setup, decode_block_ladder=[1, 2, 4],
                        decode_chain=2, decode_continuous=True,
                        prefill_chunk_tokens=0)
    want = await drive(split)
    await split.shutdown()
    assert got == want


# -- compile-count tripwire ------------------------------------------------- #


async def test_compile_count_bounded_by_ladder(setup):
    """Compiled decode/mixed variants stay bounded by ladder size ×
    the variant keys actually exercised — a silent recompile blowup
    (each one a stall of seconds inside some request) fails here first."""
    engine = make_engine(setup, decode_block_ladder=[1, 2, 4])
    reqs = [req(p, max_tokens=10) for p in PROMPTS]
    reqs[1] = req(PROMPTS[1], max_tokens=10, temperature=0.9, seed=3)
    reqs[2] = req(PROMPTS[2], max_tokens=10, frequency_penalty=0.5)
    await _staggered(engine, reqs)
    variants = engine.compiled_variants
    ladder = engine.cfg.block_ladder
    await engine.shutdown()

    for fam in ("decode", "mixed"):
        keys = [k for k in variants[fam]
                if isinstance(k, tuple) and len(k) == 4]
        flag_combos = {k[:3] for k in keys}
        assert len(keys) <= len(flag_combos) * len(ladder), variants
        assert {k[3] for k in keys} <= set(ladder), variants


async def test_compiled_variants_property(setup):
    """`compiled_variants` is the public view benches key off (the
    engine._mixed_steps noqa sites are gone)."""
    engine = make_engine(setup)
    assert engine.compiled_variants == {
        "prefill": [], "decode": [], "mixed": []}
    await collect(engine, req([1, 2, 3], max_tokens=4))
    variants = engine.compiled_variants
    rungs = engine.compiled_decode_rungs
    await engine.shutdown()
    assert variants["prefill"] and variants["decode"]
    assert rungs == {8}  # no ladder: only the full block compiles


# -- TTFT attribution ------------------------------------------------------- #


async def test_ttft_attribution_delta_and_metrics(setup):
    """The first delivered delta carries the one-shot TTFT attribution
    (block-wait / queue-wait / prefill), later deltas don't, and the
    engine's lifetime totals line up with the per-request dicts."""
    engine = make_engine(setup, decode_block_ladder=[1, 2])
    _, deltas = await collect(engine, req(PROMPTS[1], max_tokens=6))
    _, deltas2 = await collect(engine, req([4, 5, 6], max_tokens=6))
    m = engine.metrics()
    await engine.shutdown()

    for ds in (deltas, deltas2):
        attr = ds[0].get("ttft")
        assert attr is not None and set(attr) == {
            "block_wait_ms", "queue_wait_ms", "prefill_ms"}
        assert all(v >= 0 for v in attr.values())
        assert not any(d.get("ttft") for d in ds[1:])
    assert m.ttft_attributed_total == 2
    total = (m.ttft_block_wait_ms_total + m.ttft_queue_wait_ms_total
             + m.ttft_prefill_ms_total)
    per_req = sum(v for ds in (deltas, deltas2)
                  for v in ds[0]["ttft"].values())
    assert total == pytest.approx(per_req)


def test_frontend_ttft_attribution_metrics():
    """FrontendMetrics turns the per-request attribution dict into the
    dynamo_frontend_ttft_{block_wait,queue_wait,prefill}_seconds
    histograms (seconds, like every other frontend latency series)."""
    from dynamo_tpu.frontend.metrics import FrontendMetrics

    fm = FrontendMetrics()
    fm.observe_ttft_attr("m", {"block_wait_ms": 120.0,
                               "queue_wait_ms": 5.0,
                               "prefill_ms": 80.0})
    text = fm.exposition().decode()
    for name in ("ttft_block_wait", "ttft_queue_wait", "ttft_prefill"):
        assert f"dynamo_frontend_{name}_seconds_count" in text
    assert 'dynamo_frontend_ttft_block_wait_seconds_sum{model="m"} 0.12' \
        in text


def test_worker_metrics_counts_rung_and_ttft_series():
    """The worker Prometheus collector exports the dynamic per-rung
    dispatch counters and the TTFT attribution totals as counters."""
    from dynamo_tpu.runtime.metrics import EngineStatsCollector

    stats = {
        "decode_rung8_dispatches_total": 5,
        "decode_rung1_dispatches_total": 2,
        "ttft_block_wait_ms_total": 42.5,
        "kv_usage": 0.5,
    }
    fams = {f.name: f for f in
            EngineStatsCollector(lambda: stats, "ns", "c").collect()}
    assert fams["dynamo_tpu_worker_decode_rung8_dispatches"].type == "counter"
    assert fams["dynamo_tpu_worker_ttft_block_wait_ms"].type == "counter"
    assert fams["dynamo_tpu_worker_kv_usage"].type == "gauge"
