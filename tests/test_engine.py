"""Engine tests: streaming generation, continuous batching, prefix cache,
preemption, cancellation, determinism.

These run the real JaxEngine with the tiny model on CPU — the same code
path as TPU, just small.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.page_pool import PagePool
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.testing import dispatches


@pytest.fixture(scope="module")
def engine_setup():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_engine(engine_setup, **over):
    cfg, params = engine_setup
    defaults = dict(
        page_size=8,
        num_pages=64,
        max_num_seqs=4,
        max_prefill_tokens=32,
        max_model_len=256,
    )
    defaults.update(over)
    ecfg = EngineConfig(**defaults)
    return JaxEngine(cfg, params, ecfg, eos_token_ids=[], kv_dtype=jnp.float32)


def req(tokens, max_tokens=8, temperature=0.0):
    return {
        "token_ids": tokens,
        "sampling_options": {"temperature": temperature},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }


async def collect(engine, request, context=None):
    out = []
    async for delta in engine.generate(request, context):
        out.extend(delta["token_ids"])
        reason = delta["finish_reason"]
    return out, reason


async def test_single_generation(engine_setup):
    engine = make_engine(engine_setup)
    tokens, reason = await collect(engine, req([1, 2, 3, 4, 5], max_tokens=6))
    assert len(tokens) == 6
    assert reason == "length"
    await engine.shutdown()


async def test_concurrent_generations_match_solo(engine_setup):
    """Continuous batching must not change greedy outputs."""
    engine = make_engine(engine_setup)
    prompts = [[1, 2, 3], [9, 8, 7, 6], [42] * 10, [5, 5, 5, 5, 5]]
    solo = []
    for p in prompts:
        toks, _ = await collect(engine, req(p, max_tokens=5))
        solo.append(toks)
    results = await asyncio.gather(
        *[collect(engine, req(p, max_tokens=5)) for p in prompts]
    )
    for (got, _), want in zip(results, solo):
        assert got == want
    await engine.shutdown()


async def test_prefix_cache_hit(engine_setup):
    engine = make_engine(engine_setup)
    prompt = list(range(1, 33))  # 4 full pages
    t1, _ = await collect(engine, req(prompt, max_tokens=4))
    m = engine.metrics()
    assert engine.pool.evictable_pages > 0  # finished seq left cached pages
    t2, _ = await collect(engine, req(prompt, max_tokens=4))
    assert t1 == t2  # cache hit preserves greedy output
    await engine.shutdown()


async def test_preemption_under_pressure(engine_setup):
    """Tiny pool forces preemption; all requests must still finish."""
    engine = make_engine(
        engine_setup, num_pages=14, max_num_seqs=4, max_model_len=96
    )
    prompts = [[i] * 20 for i in range(1, 5)]
    results = await asyncio.gather(
        *[collect(engine, req(p, max_tokens=10)) for p in prompts]
    )
    for toks, reason in results:
        assert len(toks) == 10
        assert reason == "length"
    await engine.shutdown()


async def test_kill_cancels(engine_setup):
    engine = make_engine(engine_setup)
    ctx = Context()

    async def run():
        out = []
        async for delta in engine.generate(req([1, 2, 3], max_tokens=200), ctx):
            out.append(delta)
            if len(out) == 2:
                ctx.kill()
        return out

    out = await asyncio.wait_for(run(), timeout=60)
    assert len(out) >= 2
    # scheduler must be drained
    await asyncio.sleep(0.2)
    running, waiting = engine.scheduler.num_requests()
    assert (running, waiting) == (0, 0)
    await engine.shutdown()


async def test_shutdown_reaps_cancelled_stream_pages(engine_setup):
    """A stream cancelled right before shutdown queues its abort with the
    pump, but the pump exits as soon as shutdown() sets _closed — the
    reap in shutdown() must still run the abort and free the sequence's
    pages, or the pool leaks refs forever (the leak-ledger page account)."""
    engine = make_engine(engine_setup)
    gen = engine.generate(req([1, 2, 3], max_tokens=200))
    await gen.__anext__()  # sequence admitted, pages allocated
    await gen.aclose()  # generate()'s finally queues the abort
    await engine.shutdown()
    assert sum(engine.pool._refs.values()) == 0


async def test_stop_token(engine_setup):
    cfg, params = engine_setup
    engine = make_engine(engine_setup)
    # find what greedy emits first, then use it as a stop token
    toks, _ = await collect(engine, req([3, 1, 4], max_tokens=3))
    first = toks[0]
    request = req([3, 1, 4], max_tokens=10)
    request["stop_conditions"]["stop_token_ids"] = [first]
    toks2, reason = await collect(engine, request)
    assert toks2 == [first]
    assert reason == "stop"
    await engine.shutdown()


async def test_seeded_sampling_reproducible(engine_setup):
    """Same seed → same tokens, regardless of batching context."""
    engine = make_engine(engine_setup)
    r = req([1, 2, 3], max_tokens=6, temperature=0.9)
    r["sampling_options"]["seed"] = 42
    solo, _ = await collect(engine, r)
    # again, but batched with other traffic
    other = req([7, 7, 7], max_tokens=6, temperature=0.9)
    results = await asyncio.gather(
        collect(engine, dict(r)), collect(engine, other)
    )
    assert results[0][0] == solo
    await engine.shutdown()


async def test_generation_beyond_pool_errors_not_hangs(engine_setup):
    """Prompt fits but prompt+generation exceeds the whole pool: the engine
    must error the request out, not livelock on self-preemption."""
    engine = make_engine(engine_setup, num_pages=7, max_model_len=200)
    # pool: 6 usable pages * 8 = 48 tokens; request wants 20 + 100
    out = []
    async for delta in engine.generate(req([1] * 20, max_tokens=100)):
        out.append(delta)
    assert out[-1]["finish_reason"] == "error"
    # a small request afterwards must still work
    toks, reason = await collect(engine, req([1, 2, 3], max_tokens=4))
    assert len(toks) == 4
    await engine.shutdown()


async def test_default_max_tokens_generates_to_window(engine_setup):
    """No max_tokens → clamp to context window, not 16."""
    engine = make_engine(engine_setup, max_model_len=64)
    r = {"token_ids": [1, 2, 3], "sampling_options": {"temperature": 0.0},
         "stop_conditions": {"ignore_eos": True}}
    toks, reason = await collect(engine, r)
    assert len(toks) == 64 - 3
    assert reason == "length"
    await engine.shutdown()


async def test_prompt_too_long_rejected(engine_setup):
    engine = make_engine(engine_setup, max_model_len=64)
    out = []
    async for delta in engine.generate(req([1] * 100, max_tokens=4)):
        out.append(delta)
    assert out[-1]["finish_reason"] == "error"
    await engine.shutdown()


def test_page_pool_lru_eviction():
    events = []
    pool = PagePool(8, 4, event_sink=events.append)
    a = pool.allocate(3)
    for i, p in enumerate(a):
        pool.commit(p, 100 + i, 99 + i if i else None)
    pool.free(a)
    assert pool.evictable_pages == 3
    assert [e.kind for e in events] == ["stored"] * 3
    # exhaust: 4 free left (7 usable - 3 cached), ask for 6 → evicts 2 LRU
    b = pool.allocate(6)
    assert len(b) == 6
    removed = [e for e in events if e.kind == "removed"]
    assert len(removed) == 2
    assert removed[0].block_hashes == [100]  # oldest first
    # a prefix lookup starting at the evicted parent finds nothing...
    assert pool.lookup([100, 101, 102]) == []
    # ...but the youngest block survived eviction
    assert 102 in pool._cached


async def test_decode_chain_matches_unchained(engine_setup):
    """Chained decode dispatches (block k+1 issued before block k's results
    are fetched) must produce the same greedy tokens as unchained decode."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [3, 3, 3, 3, 3, 3, 3, 3]]
    plain = make_engine(engine_setup)
    want = [await collect(plain, req(p, max_tokens=13)) for p in prompts]
    await plain.shutdown()

    chained = make_engine(engine_setup, decode_steps=4, decode_chain=3)
    got = await asyncio.gather(
        *[collect(chained, req(p, max_tokens=13)) for p in prompts]
    )
    await chained.shutdown()
    assert [g[0] for g in got] == [w[0] for w in want]
    assert all(g[1] == "length" for g in got)


async def test_decode_chain_stop_token_mid_chain(engine_setup):
    """A stop token hit inside an early chained block must end the request
    and free its pages even though later blocks were already dispatched."""
    chained = make_engine(engine_setup, decode_steps=2, decode_chain=4)
    # discover the greedy continuation, then stop on its 3rd token
    probe, _ = await collect(chained, req([5, 6, 7], max_tokens=10))
    r = req([5, 6, 7], max_tokens=10)
    r["stop_conditions"]["stop_token_ids"] = [probe[2]]
    tokens, reason = await collect(chained, r)
    assert tokens == probe[:3]
    assert reason == "stop"
    # pool fully released once the in-flight chain drains (frees are
    # deferred past the last dispatched block, so poll briefly)
    for _ in range(100):
        if (chained.pool.free_pages + chained.pool.evictable_pages
                == chained.pool.num_pages - 1):
            break
        await asyncio.sleep(0.05)
    assert chained.pool.free_pages + chained.pool.evictable_pages == \
        chained.pool.num_pages - 1
    await chained.shutdown()


async def test_frequency_penalty_changes_output(engine_setup):
    """A strong frequency penalty must suppress token repetition relative
    to the unpenalized greedy continuation (reference maps penalties into
    engine sampling options, preprocessor.rs:102)."""
    engine = make_engine(engine_setup)
    base = req([2, 2, 2, 2], max_tokens=16)
    plain, _ = await collect(engine, base)

    pen = req([2, 2, 2, 2], max_tokens=16)
    pen["sampling_options"]["frequency_penalty"] = 2.0
    penalized, _ = await collect(engine, pen)

    assert penalized != plain
    # penalty makes repeats strictly rarer
    def max_repeat(toks):
        from collections import Counter
        return max(Counter(toks).values())
    assert max_repeat(penalized) <= max_repeat(plain)
    await engine.shutdown()


async def test_top_logprobs_delivered(engine_setup):
    engine = make_engine(engine_setup)
    r = req([1, 2, 3], max_tokens=4)
    r["sampling_options"]["logprobs"] = True
    r["sampling_options"]["top_logprobs"] = 3
    seen = []
    async for out in engine.generate(r):
        if out["token_ids"]:
            assert "top_logprobs" in out, out
            tops = out["top_logprobs"][0]
            assert len(tops) == 3
            # ranked descending, and the greedy token leads
            lps = [lp for _, lp in tops]
            assert lps == sorted(lps, reverse=True)
            assert tops[0][0] == out["token_ids"][0]  # greedy = argmax
            seen.append(tops)
    assert len(seen) == 4
    await engine.shutdown()


def test_ngram_draft_semantics():
    """The host drafter: longest trailing m-gram wins, the MOST RECENT
    earlier occurrence supplies the continuation, short continuations
    pad by repeating their last token, and no match falls back to
    repeating the sequence's last token."""
    from dynamo_tpu.engine.engine import _ngram_draft

    # trailing [1, 2] occurred twice; most recent earlier occurrence is
    # at index 4 → continuation [9, 1, 2]
    assert _ngram_draft([1, 2, 7, 8, 1, 2, 9, 1, 2], 3, 1) == [9, 1, 2]
    # longest match preferred: trailing [5, 1, 2] has an occurrence, so
    # its continuation [6] beats the shorter [1, 2] match's
    assert _ngram_draft([5, 1, 2, 6, 0, 5, 1, 2], 1, 1) == [6]
    # continuation shorter than k pads with its last token
    assert _ngram_draft([4, 4, 7, 4, 4], 4, 2) == [7, 4, 4, 4]
    # no repetition at all: repeat the last token
    assert _ngram_draft([10, 20, 30], 2, 2) == [30, 30]
    # degenerate histories never raise
    assert _ngram_draft([3], 2, 1) == [3, 3]
    assert _ngram_draft([], 2, 1) == [0, 0]


async def test_spec_decode_matches_plain(engine_setup):
    """Self-speculative decoding (n-gram draft + fused verify) must be
    output-invisible: token-identical streams with speculation on and
    off, across prompt shapes incl. repetitive ones (where drafts
    actually get accepted), (a) under greedy sampling, (b) under
    SEEDED temperature>0 sampling — the verify tail samples each
    position from the same (seed, counter) PRNG stream plain decode
    would use, the strongest form of 'rejection verification preserves
    the sampling distribution' — and (c) a stop token landing INSIDE
    an accepted draft run must end the request there with later
    accepted tokens discarded and pages freed."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [5, 6, 5, 6, 5, 6, 5, 6]]

    def seeded():
        out = req([1, 2, 3], max_tokens=10, temperature=0.9)
        out["sampling_options"]["seed"] = 42
        return out

    plain = make_engine(engine_setup)
    want = [await collect(plain, req(p, max_tokens=13)) for p in prompts]
    want_seeded, _ = await collect(plain, seeded())
    await plain.shutdown()

    spec = make_engine(engine_setup, speculative_ngram_k=4)
    got = await asyncio.gather(
        *[collect(spec, req(p, max_tokens=13)) for p in prompts]
    )
    assert [g[0] for g in got] == [w[0] for w in want]
    assert all(g[1] == "length" for g in got)
    got_seeded, _ = await collect(spec, seeded())
    assert got_seeded == want_seeded
    m = spec.metrics()
    assert m.spec_draft_tokens_total > 0  # the verify path actually ran

    # stop token mid-acceptance: reuse the greedy continuation as probe
    probe = want[0][0]
    r = req(prompts[0], max_tokens=13)
    r["stop_conditions"]["stop_token_ids"] = [probe[2]]
    tokens, reason = await collect(spec, r)
    assert tokens == probe[:3]
    assert reason == "stop"
    assert spec.pool.free_pages + spec.pool.evictable_pages == \
        spec.pool.num_pages - 1
    await spec.shutdown()


async def test_spec_decode_tokens_per_dispatch(engine_setup):
    """On a repetitive stream with k=4 the accepted drafts must compress
    dispatches: > 1.5 tokens per verify dispatch, with the acceptance
    telemetry visible in ForwardPassMetrics.  Uses a zeroed-parameter
    model (constant greedy output) so acceptance is deterministic."""
    cfg, params = engine_setup
    zero = jax.tree.map(jnp.zeros_like, params)
    engine = JaxEngine(
        cfg, zero,
        EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                     max_prefill_tokens=32, max_model_len=256,
                     speculative_ngram_k=4),
        eos_token_ids=[], kv_dtype=jnp.float32,
    )
    toks, reason = await collect(engine, req([7, 9, 11, 13], max_tokens=40))
    m = engine.metrics()
    dispatches = engine._spec_dispatch_total  # noqa: SLF001
    await engine.shutdown()
    assert len(toks) == 40 and reason == "length"
    assert dispatches > 0
    # tokens per verify dispatch = accepted drafts + the per-dispatch
    # bonus/corrected token
    tpd = (m.spec_accepted_tokens_total + dispatches) / dispatches
    assert tpd > 1.5, (tpd, dispatches, m.spec_accepted_tokens_total)
    assert m.spec_draft_tokens_total == 4 * dispatches
    assert 0.0 < m.spec_acceptance_rate <= 1.0


def make_cc_engine(engine_setup, **over):
    """A device-resident (continuous-chain) engine: open-ended decode
    chaining, on-device stop detection, async double-buffered drain."""
    over.setdefault("decode_steps", 4)
    over.setdefault("decode_chain", 2)
    over.setdefault("decode_continuous", True)
    return make_engine(engine_setup, **over)


async def test_continuous_decode_matches_per_step(engine_setup):
    """ISSUE 6 equivalence matrix: the device-resident decode loop
    (continuous chaining + on-device stop detection + async drain) must
    be output-invisible vs the per-step engine — greedy, SEEDED
    temperature sampling, and penalized rows, concurrent and solo."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [3, 3, 3, 3, 3, 3, 3, 3]]

    def reqs():
        out = [req(p, max_tokens=13) for p in prompts]
        out[1] = req(prompts[1], max_tokens=13, temperature=0.9)
        out[1]["sampling_options"]["seed"] = 42
        out[2] = req(prompts[2], max_tokens=13)
        out[2]["sampling_options"]["frequency_penalty"] = 1.5
        return out

    plain = make_engine(engine_setup)
    want = [await collect(plain, r) for r in reqs()]
    await plain.shutdown()

    cc = make_cc_engine(engine_setup)
    got = await asyncio.gather(*[collect(cc, r) for r in reqs()])
    m = cc.metrics()
    released = cc.pool.free_pages + cc.pool.evictable_pages
    await cc.shutdown()
    assert list(got) == want
    # the continuous path actually engaged (chains + per-chain blocks)
    assert m.decode_cc_chains_total > 0
    assert m.decode_cc_blocks_total >= m.decode_cc_chains_total
    assert released == cc.pool.num_pages - 1


async def test_continuous_decode_device_stop_detection(engine_setup):
    """A stop token inside an open-ended chain is latched ON DEVICE:
    the stream ends exactly at the stop with the right reason, the
    finished row's pages free without waiting for chain fall-out, and
    host-only stop SEQUENCES still work (they force fall-out)."""
    cc = make_cc_engine(engine_setup)
    probe, _ = await collect(cc, req([5, 6, 7], max_tokens=20))

    r = req([5, 6, 7], max_tokens=20)
    r["stop_conditions"]["stop_token_ids"] = [probe[2]]
    toks, reason = await collect(cc, r)
    assert toks == probe[:3] and reason == "stop"

    r = req([5, 6, 7], max_tokens=20)
    r["stop_conditions"]["stop_sequences"] = [[probe[2], probe[3]]]
    toks, reason = await collect(cc, r)
    assert toks == probe[:4] and reason == "stop"
    # a host-detected stop fell the chain out; device-detected stops
    # free early — either way the pool fully drains
    for _ in range(100):
        if (cc.pool.free_pages + cc.pool.evictable_pages
                == cc.pool.num_pages - 1):
            break
        await asyncio.sleep(0.05)
    assert cc.pool.free_pages + cc.pool.evictable_pages == \
        cc.pool.num_pages - 1
    fallouts = [e[3]["fallout"] for e in cc.events.snapshot()
                if e[2] == "decode_chain"]
    assert fallouts and set(fallouts) <= {
        "stop", "pending_work", "admit"}, fallouts
    await cc.shutdown()


async def test_continuous_decode_per_step_fallback_path(engine_setup):
    """The continuous loop's per-step scan fallback (Pallas / giant-KV
    engines that cannot materialize the block) stays token-identical:
    force it by zeroing the block-KV byte budget."""
    import dynamo_tpu.engine.steps as eng_mod

    plain = make_engine(engine_setup)
    want = [await collect(plain, req([1, 2, 3, 4, 5], max_tokens=13))]
    await plain.shutdown()

    saved = eng_mod._BLOCK_KV_BYTE_BUDGET
    eng_mod._BLOCK_KV_BYTE_BUDGET = 0
    try:
        cc = make_cc_engine(engine_setup)
        got = [await collect(cc, req([1, 2, 3, 4, 5], max_tokens=13))]
        assert cc.metrics().decode_cc_blocks_total > 0
        await cc.shutdown()
    finally:
        eng_mod._BLOCK_KV_BYTE_BUDGET = saved
    assert got == want


async def test_continuous_decode_top_logprobs(engine_setup):
    """top-logprobs ride the continuous packed layout (flags slot
    between logp and the top-TOPLP block)."""
    cc = make_cc_engine(engine_setup)
    r = req([1, 2, 3], max_tokens=6)
    r["sampling_options"]["logprobs"] = True
    r["sampling_options"]["top_logprobs"] = 3
    n_toks = n_tops = 0
    async for out in cc.generate(r):
        n_toks += len(out["token_ids"])
        for tops in out.get("top_logprobs", []):
            assert len(tops) == 3
            lps = [lp for _, lp in tops]
            assert lps == sorted(lps, reverse=True)
            n_tops += 1
    await cc.shutdown()
    assert n_toks == 6 and n_tops == 6


async def _drive_mid_chain_arrival(engine, base_reqs, arrival_req):
    """Start `base_reqs`, wait until a continuous decode dispatch is in
    flight, then submit `arrival_req`; returns every stream's (tokens,
    reason) in submission order.  The arrival deterministically lands
    mid-chain — the splice (unified engine) or fall-out (split engine)
    path is exercised on every run, not just when timing cooperates."""
    before = len(dispatches(engine))
    base = [asyncio.ensure_future(collect(engine, r)) for r in base_reqs]
    while not any(e["kind"] == "decode"
                  for e in dispatches(engine)[before:]):
        await asyncio.sleep(0.005)
    late = await collect(engine, arrival_req)
    out = list(await asyncio.gather(*base))
    out.append(late)
    return out


def _splice_reqs():
    """Three co-resident rows covering the device-variant matrix
    (greedy / seeded temperature / penalized+top-logprobs) plus a
    long-prompt greedy arrival whose chunked prefill spans several
    decode blocks AND a page boundary."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [3, 3, 3, 3, 3, 3, 3, 3]]
    out = [req(p, max_tokens=24) for p in prompts]
    out[1] = req(prompts[1], max_tokens=24, temperature=0.9)
    out[1]["sampling_options"]["seed"] = 42
    out[2] = req(prompts[2], max_tokens=24)
    out[2]["sampling_options"]["frequency_penalty"] = 1.5
    out[2]["sampling_options"]["logprobs"] = True
    out[2]["sampling_options"]["top_logprobs"] = 2
    arrival = req([(5 * j) % 101 + 1 for j in range(24)], max_tokens=8)
    return out, arrival


async def test_chunked_prefill_splice_matches_fallout_engine(engine_setup):
    """ISSUE 15 tentpole identity: a prompt admitted MID-CHAIN via the
    chunk-row splice (prefill chunks riding the running decode chain)
    yields byte-identical streams — for every co-resident row and the
    admitted request itself — to the fall-out engine
    (prefill_chunk_tokens=0), which ends the chain and prefills the
    prompt the PR 6 way.  Greedy, seeded, penalized and top-logprobs
    rows all share the spliced chain."""
    base, arrival = _splice_reqs()

    unified = make_cc_engine(engine_setup)
    got = await _drive_mid_chain_arrival(unified, base, arrival)
    ev = unified.events.snapshot()
    m = unified.metrics()
    # a fused prefill→decode step keeps its finished sequences' pages until
    # its chain is consumed (`deferred_free`), after the last delta is
    # posted: wait for the step thread to return them
    for _ in range(400):
        released = unified.pool.free_pages + unified.pool.evictable_pages
        if released == unified.pool.num_pages - 1:
            break
        await asyncio.sleep(0.005)
    await unified.shutdown()

    # the chunk rows actually rode the chain: splice-tagged decode
    # blocks with a nonzero chunk-row count...
    fed = [e[3].get("chunk_rows", 0) for e in ev
           if e[2] == "decode_block" and e[3].get("splice")]
    assert fed and max(fed) > 0, [e[3] for e in ev
                                  if e[2] == "decode_block"]
    # ...and the admission did NOT end a chain: no admission-side
    # fall-out reasons (stop/pending_work remain legitimate)
    assert m.decode_cc_chains_total > 0
    assert not {"admit", "admission"} & set(m.decode_cc_fallout_total), \
        m.decode_cc_fallout_total
    assert released == unified.pool.num_pages - 1

    split = make_cc_engine(engine_setup, prefill_chunk_tokens=0)
    want = await _drive_mid_chain_arrival(split, base, arrival)
    m_split = split.metrics()
    await split.shutdown()
    # the split engine really took the fall-out path for the arrival
    assert "admit" in m_split.decode_cc_fallout_total or \
        "pending_work" in m_split.decode_cc_fallout_total, \
        m_split.decode_cc_fallout_total
    assert got == want


async def test_chunked_prefill_splice_seeded_arrival(engine_setup):
    """A SEEDED sampled arrival spliced mid-chain: (a) the co-resident
    rows — greedy, seeded AND penalized — stay byte-identical to the
    fall-out engine (the chunk rows' prologue overlay and emit gating
    never perturb running rows), and (b) the spliced stream itself is
    reproducible run-to-run: its PRNG stream starts at counter 0 no
    matter which mid-chain block fed the chunks.  (The spliced row's
    picks are NOT asserted against the fall-out engine: prefill
    computes [B,T,D] matmuls where the chunk feed runs T per-step
    [B,1,D] ones, and the last-ulp logits differences that argmax
    absorbs can flip a temperature>0 gumbel pick.)"""
    base, _ = _splice_reqs()
    arrival = req([(5 * j) % 101 + 1 for j in range(11)], max_tokens=8,
                  temperature=0.7)
    arrival["sampling_options"]["seed"] = 1234

    async def run_unified():
        eng = make_cc_engine(engine_setup)
        out = await _drive_mid_chain_arrival(eng, base, arrival)
        ev = eng.events.snapshot()
        await eng.shutdown()
        assert any(e[3].get("chunk_rows", 0) > 0 for e in ev
                   if e[2] == "decode_block"), "splice never engaged"
        return out

    got = await run_unified()
    again = await run_unified()
    assert got == again  # seeded splice is reproducible

    split = make_cc_engine(engine_setup, prefill_chunk_tokens=0)
    want = await _drive_mid_chain_arrival(split, base, arrival)
    await split.shutdown()
    # co-resident rows are bit-identical across the two engines
    assert got[:3] == want[:3]
    # the seeded arrival emits the same SHAPE of stream either way
    assert len(got[3][0]) == len(want[3][0]) == 8
    assert got[3][1] == want[3][1] == "length"


async def test_fused_prefill_decode_matches_unfused():
    """The fused prefill→decode dispatch (first decode chain fed by the
    prefill's device-side sampled token) must be output-invisible:
    identical streams with the fusion on and off, including EOS stops
    landing on the prefill-sampled token and max_tokens cutoffs."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import init_params, tiny_config

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)

    def ecfg(fuse):
        return EngineConfig(
            page_size=8, num_pages=128, max_num_seqs=4,
            max_prefill_tokens=64, max_model_len=128,
            decode_steps=4, decode_chain=2,
            decode_batch_buckets=[1, 2, 4],
            fuse_prefill_decode=fuse,
        )

    async def collect(engine):
        outs = []
        for i in range(4):
            prompt = [(i * 17 + j) % cfg.vocab_size for j in range(5 + 6 * i)]
            req = {
                "token_ids": prompt,
                "sampling_options": {"temperature": 0.0},
                # one request stops on an early max_tokens, others run long
                "stop_conditions": {"max_tokens": 2 if i == 1 else 11,
                                    "ignore_eos": True},
            }
            toks = []
            async for out in engine.generate(req):
                assert out.get("finish_reason") != "error", out
                toks += out["token_ids"]
            outs.append(toks)
        await engine.shutdown()
        return outs

    fused = await collect(JaxEngine(cfg, params, ecfg(True),
                                    kv_dtype=jnp.float32))
    plain = await collect(JaxEngine(cfg, params, ecfg(False),
                                    kv_dtype=jnp.float32))
    assert fused == plain
    assert len(fused[1]) == 2 and len(fused[0]) == 11


# --------------------------------------------------------------------------- #
# Overload control: decode preemption with KV park/resume + class-aware
# admission (docs/overload_control.md)
# --------------------------------------------------------------------------- #


async def _wait_for(cond, timeout=30.0, what=""):
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond():
        assert asyncio.get_event_loop().time() < deadline, f"timeout: {what}"
        await asyncio.sleep(0.01)


@pytest.mark.parametrize("variant", ["greedy", "seeded", "penalized"])
async def test_park_resume_token_identity(engine_setup, variant):
    """A batch victim preempted mid-decode (KV parked host-side, pages
    freed) and resumed through ordinary admission must emit exactly the
    tokens of an uncontended oracle run — greedy, seeded, and with a
    penalized interactive co-resident (penalty state rides the victim's
    own token history, not its slot)."""

    def victim_req():
        r = req([3, 1, 4, 1, 5, 9, 2, 6], max_tokens=12,
                temperature=0.0 if variant == "greedy" else 0.9)
        if variant != "greedy":
            r["sampling_options"]["seed"] = 7
        r["priority"] = "batch"
        return r

    # oracle: same request, no contention, no preemption
    oracle_engine = make_engine(engine_setup, max_num_seqs=1)
    oracle, oracle_reason = await collect(oracle_engine, victim_req())
    assert oracle_engine.scheduler.preempted_total == 0
    await oracle_engine.shutdown()

    # storm: one decode slot, so an interactive arrival can only be
    # admitted by parking the running batch victim
    engine = make_engine(engine_setup, max_num_seqs=1)
    got: list = []
    reason: list = []

    async def run_victim():
        async for delta in engine.generate(victim_req()):
            got.extend(delta["token_ids"])
            reason.append(delta["finish_reason"])

    vt = asyncio.create_task(run_victim())
    await _wait_for(lambda: len(got) >= 2, what="victim mid-decode")

    inter = req([8, 8, 8], max_tokens=4, temperature=0.0)
    if variant == "penalized":
        inter["sampling_options"]["frequency_penalty"] = 2.0
    it = asyncio.create_task(collect(engine, inter))
    await _wait_for(lambda: engine.scheduler.preempted_total >= 1,
                    what="victim parked")
    # the victim's KV is host-side while the interactive runs
    assert len(engine.parking) <= 1  # resumed entries leave the lot
    await it
    await vt

    assert got == oracle, (variant, got, oracle)
    assert reason[-1] == oracle_reason == "length"
    sched = engine.scheduler
    assert sched.preempted_total == sched.resumed_total >= 1
    assert len(engine.parking) == 0 and engine.parking.pages_held == 0
    await engine.shutdown()


def _mkseq(rid, priority="interactive", prompt_len=8, parked=False):
    from dynamo_tpu.engine.scheduler import SamplingOptions, Sequence

    seq = Sequence(rid, list(range(1, prompt_len + 1)), SamplingOptions())
    seq.priority = priority
    seq.parked = parked
    return seq


def test_enqueue_class_order():
    """Interactive rides ahead of batch; FIFO within a class; front=True
    inserts at the head of the sequence's OWN class region."""
    from dynamo_tpu.engine.scheduler import Scheduler

    cfg = EngineConfig(page_size=8, num_pages=16, max_num_seqs=4,
                       max_prefill_tokens=32, max_model_len=256)
    sched = Scheduler(cfg, PagePool(16, 8))
    for rid, prio in [("b1", "batch"), ("i1", "interactive"),
                      ("b2", "batch"), ("i2", "interactive")]:
        sched.add(_mkseq(rid, prio))
    assert [s.request_id for s in sched.waiting] == ["i1", "i2", "b1", "b2"]
    # a preemption victim re-admits before later arrivals of its class
    # but never jumps the other class
    sched._enqueue(_mkseq("b0", "batch"), front=True)
    sched._enqueue(_mkseq("i0", "interactive"), front=True)
    assert [s.request_id for s in sched.waiting] == [
        "i0", "i1", "i2", "b0", "b1", "b2"]
    # only b2 arrived behind existing work (b1 found an empty queue);
    # direct _enqueue calls (preemption re-inserts) never count
    assert sched.queued_total == 1


def test_admit_check_interactive_claims_reserve():
    """The watermark reserve is waived for interactive admission only
    while batch work is present; batch always respects the reserve."""
    from dynamo_tpu.engine.scheduler import Scheduler

    cfg = EngineConfig(page_size=8, num_pages=16, max_num_seqs=4,
                       max_prefill_tokens=32, max_model_len=256,
                       watermark=0.5)  # reserve = 7 of 15 usable pages
    pool = PagePool(16, 8)
    sched = Scheduler(cfg, pool)
    held = pool.allocate(8)  # 7 free: covers need(1) but not need+reserve
    seq_i = _mkseq("i", "interactive")
    seq_b = _mkseq("b", "batch")
    # no batch present: interactive respects the reserve like anyone
    ok, _ = sched._admit_check(seq_i)
    assert not ok
    # batch present (waiting): interactive may claim the reserve...
    sched.add(seq_b)
    ok, _ = sched._admit_check(seq_i)
    assert ok
    # ...but batch itself still cannot
    ok, _ = sched._admit_check(seq_b)
    assert not ok
    pool.free(held)


def test_overloaded_needs_depth_and_headroom():
    """overloaded() trips only when BOTH the queue is deep enough and
    the watermark headroom is exhausted; depth 0 disables it."""
    from dynamo_tpu.engine.scheduler import Scheduler

    def make(depth, headroom):
        cfg = EngineConfig(page_size=8, num_pages=16, max_num_seqs=4,
                           max_prefill_tokens=32, max_model_len=256,
                           watermark=0.0, overload_queue_depth=depth,
                           overload_headroom_pages=headroom)
        return Scheduler(cfg, PagePool(16, 8)), cfg

    sched, _ = make(depth=2, headroom=4)
    assert not sched.overloaded()  # queue empty
    sched.add(_mkseq("a", "batch"))
    sched.add(_mkseq("b", "batch"))
    assert not sched.overloaded()  # deep enough, but 15 pages headroom
    held = sched.pool.allocate(12)  # headroom 3 <= 4
    assert sched.overloaded()
    sched.pool.free(held)

    sched0, _ = make(depth=0, headroom=10**6)
    sched0.add(_mkseq("a", "batch"))
    assert not sched0.overloaded()  # depth 0 = shedding disabled
