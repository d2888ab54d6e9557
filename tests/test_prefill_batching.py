"""Short prefill chunks that are ready together share one step (ISSUE 36):
when the chunk at the head of the line is a whole remaining prompt no longer
than the short bucket, the other such chunks ready at that plan ride in the
same `prefill_step` as further rows.  Same tokens as one sequence a step;
the rule; a closed set of shared programs, each run before two rows first
meet; abort and a failed fetch under a shared step in flight; what the
slice, the counters and `first_token` say."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine import engine as eng
from dynamo_tpu.engine.config import SHARED_PREFILL_ROWS
from dynamo_tpu.engine.layout import Layout
from dynamo_tpu.engine.page_pool import PagePool
from dynamo_tpu.engine.scheduler import (
    SamplingOptions, Scheduler, Sequence)
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig
from dynamo_tpu.runtime.engine import Context


def tiny_engine(**over):
    """Chunk buckets 8-128: four rows share the 16-token bucket."""
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ecfg = dict(page_size=8, num_pages=256, max_num_seqs=8,
                max_prefill_tokens=128, max_model_len=256)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32)


async def generate(engine, prompt, n=1, rid=None, sampling=None):
    toks, logps, finish = [], [], None
    async for d in engine.generate({
        "token_ids": prompt,
        "sampling_options": dict(sampling or {"temperature": 0.0},
                                 logprobs=True),
        "stop_conditions": {"max_tokens": n, "ignore_eos": True},
    }, Context(rid) if rid else None):
        toks.extend(d.get("token_ids", []))
        logps.extend(d.get("log_probs", []))
        finish = d.get("finish_reason") or finish
    return toks, logps, finish


def refs_held(engine):
    return sum(engine.pool._refs.values())  # noqa: SLF001


def events_of(engine, kind):
    return [e for e in engine.events.dump()["events"] if e["kind"] == kind]


def doc_of(client, n, salt=0):
    return [1 + (7 * client + 3 * i + salt) % 250 for i in range(n)]


async def session(engine, client, sampling, doc_len=140, fresh=9, salt=0):
    """A document (a 128-token chunk and a 12-token remainder, which is
    short) and three questions on it, one after the other: each hits the
    document's pages and prefills `fresh` + at most 7 tokens."""
    doc = doc_of(client, doc_len, salt)
    out = [await generate(engine, doc, 1, f"c{client}-doc", sampling)]
    for q in range(3):
        ask = doc + [(200 + client + i + 11 * q) % 250 for i in range(fresh)]
        out.append(await generate(engine, ask, 1, f"c{client}-q{q}",
                                  sampling))
    return out


async def served(engine, clients, sampling, **kw):
    try:
        return await asyncio.wait_for(asyncio.gather(*(
            session(engine, c, sampling, **kw) for c in range(clients))), 180)
    finally:
        await engine.shutdown()


# -- the same work ------------------------------------------------------------- #

@pytest.mark.parametrize("clients", [1, 4])
@pytest.mark.parametrize("sampling", [
    {"temperature": 0.0},
    {"temperature": 0.8, "top_k": 20, "top_p": 0.9, "seed": 1234},
], ids=["greedy", "seeded"])
async def test_tokens_and_logprobs_are_those_of_one_sequence_a_step(
        clients, sampling):
    shared = tiny_engine()
    assert (shared.cfg.prefill_batch_size,
            shared.cfg.short_chunk_bucket) == (SHARED_PREFILL_ROWS, 16)
    got = await served(shared, clients, sampling)
    want = await served(tiny_engine(prefill_batch_size=1), clients, sampling)
    for g, w in zip(sum(got, []), sum(want, [])):
        # the same token; the logprob of another program shape (four rows
        # of 16 tokens, not one of 8 or 16) to float32's rounding
        assert g[0] == w[0] and g[2] == w[2] == "length"
        assert g[1] == pytest.approx(w[1], abs=1e-4)
    hits = [e for e in events_of(shared, "admit") if e["cached"] > 0]
    assert len(hits) == 3 * clients  # every question hit its document
    chunks = events_of(shared, "prefill_chunk")
    assert sum(e["batch"] for e in chunks) == 5 * clients  # 2 + 3 a session
    if clients == 1:
        assert all(e["batch"] == 1 for e in chunks)  # nobody to meet
    else:
        assert len(chunks) < 5 * clients
        assert all(e["bucket"] == 16 and e["tokens"] <= 16 * e["batch"]
                   for e in chunks if e["batch"] > 1)
        # a lone short chunk runs at the short bucket too
        assert all(e["bucket"] == 16 for e in chunks if e["tokens"] <= 16)
        # a 128-token chunk never shares a step
        assert all(e["batch"] == 1 for e in chunks if e["tokens"] > 64)
    assert refs_held(shared) == 0


# -- the rule ------------------------------------------------------------------ #

def scheduler_of(num_pages=1024, **over):
    ecfg = dict(page_size=16, num_pages=num_pages, max_num_seqs=16,
                max_prefill_tokens=512, max_model_len=4096, watermark=0.0,
                prefill_batch_size=SHARED_PREFILL_ROWS)
    ecfg.update(over)
    cfg = EngineConfig(**ecfg)
    return Scheduler(cfg, PagePool(cfg.num_pages, cfg.page_size))


def seq_of(rid, n_prompt, start=1, **opts):
    return Sequence(rid, list(range(start, start + n_prompt)),
                    SamplingOptions(max_tokens=1, ignore_eos=True, **opts))


def running(sched, *seqs):
    for s in seqs:
        sched.add(s)
    return sched


def planned(sched):
    """[(rid, chunk_start, chunk_len)] of the next plan, committed and
    consumed as the engine does it: a row that sampled has its one token
    and is finished."""
    plan = sched.schedule()
    for it in plan.prefill:
        it.seq.num_computed += it.chunk_len
        if it.samples and it.seq.opts.max_tokens == 1:
            it.seq.output_tokens.append(7)
            sched.finish(it.seq, "length")
    return plan.kind, [(it.seq.request_id, it.chunk_start, it.chunk_len)
                       for it in plan.prefill]


def test_the_short_bucket_holds_rows_inside_half_the_step_budget():
    for tokens, rows, bucket in ((512, 4, 64), (256, 4, 32), (128, 4, 16),
                                 (64, 4, 0), (512, 2, 128), (512, 8, 32),
                                 (512, 1, 0)):
        cfg = EngineConfig(page_size=16, max_prefill_tokens=tokens,
                           prefill_batch_size=rows)
        assert cfg.short_chunk_bucket == bucket
        assert rows * bucket <= tokens // 2
    assert EngineConfig().prefill_batch_size == SHARED_PREFILL_ROWS
    assert EngineConfig(max_prefill_tokens=512).short_chunk_bucket == 64


@pytest.mark.parametrize("first", [512, 256, 128, 65])
def test_a_long_chunk_at_the_head_runs_alone(first):
    sched = running(scheduler_of(), seq_of("doc", first),
                    seq_of("q1", 40, 1000), seq_of("q2", 20, 2000))
    assert planned(sched) == ("prefill", [("doc", 0, first)])
    # and the questions behind it share the next step
    assert planned(sched) == ("prefill", [("q1", 0, 40), ("q2", 0, 20)])


def test_short_chunks_join_a_short_head_and_pass_a_long_chunk_between():
    sched = running(scheduler_of(), seq_of("q1", 40), seq_of("doc", 1500, 500),
                    seq_of("q2", 64, 3000), seq_of("q3", 16, 4000))
    assert planned(sched) == ("prefill", [
        ("q1", 0, 40), ("q2", 0, 64), ("q3", 0, 16)])
    # nothing passed the head; the document is the head now, alone
    assert planned(sched) == ("prefill", [("doc", 0, 512)])
    assert planned(sched) == ("prefill", [("doc", 512, 512)])


def test_no_more_rows_than_the_cap_and_the_rest_keep_their_order():
    sched = running(scheduler_of(), *(
        seq_of(f"q{i}", 20 + i, 100 * i + 1) for i in range(6)))
    kind, first = planned(sched)
    assert [r for r, _, _ in first] == ["q0", "q1", "q2", "q3"]
    assert planned(sched)[1] == [("q4", 0, 24), ("q5", 0, 25)]
    assert SHARED_PREFILL_ROWS * sched.cfg.short_chunk_bucket \
        <= sched.cfg.max_prefill_tokens // 2


def test_a_document_s_short_remainder_shares_a_step_with_a_question():
    sched = running(scheduler_of(), seq_of("doc", 1054), seq_of("q", 33, 5000),
                    seq_of("doc2", 542, 7000))
    assert planned(sched)[1] == [("doc", 0, 512)]
    assert planned(sched)[1] == [("doc", 512, 512)]
    # 30 tokens are left of the document: as short as the question
    assert planned(sched)[1] == [("doc", 1024, 30), ("q", 0, 33)]
    # and a remainder joins a short head as well as it leads one
    assert planned(sched)[1] == [("doc2", 0, 512)]
    sched.add(seq_of("q'", 17, 9000))
    assert planned(sched)[1] == [("doc2", 512, 30), ("q'", 0, 17)]


def test_a_65_token_prompt_is_not_short_and_joins_nothing():
    sched = running(scheduler_of(), seq_of("q1", 30), seq_of("mid", 65, 500),
                    seq_of("q2", 30, 900))
    assert planned(sched)[1] == [("q1", 0, 30), ("q2", 0, 30)]
    assert planned(sched)[1] == [("mid", 0, 65)]


@pytest.mark.parametrize("other", [
    {"temperature": 0.7, "seed": 3}, {"top_logprobs": 2},
], ids=["sampled", "top-logprobs"])
def test_rows_share_a_step_only_with_their_own_program_variant(other):
    sched = running(scheduler_of(), seq_of("g1", 30), seq_of("o1", 30, 500,
                                                             **other),
                    seq_of("g2", 30, 900), seq_of("o2", 30, 1300, **other))
    assert planned(sched)[1] == [("g1", 0, 30), ("g2", 0, 30)]
    assert planned(sched)[1] == [("o1", 0, 30), ("o2", 0, 30)]


def test_a_vision_prompt_keeps_its_own_step():
    sched = scheduler_of()
    img = seq_of("img", 30, 500)
    img.mm_embeds = object()
    running(sched, seq_of("q1", 30), img, seq_of("q2", 30, 900))
    assert planned(sched)[1] == [("q1", 0, 30), ("q2", 0, 30)]
    assert planned(sched)[1] == [("img", 0, 30)]


def test_a_row_joins_without_taking_anybody_s_pages():
    # 6 usable pages: the head's 3, and 3 for ONE of the two behind it
    sched = running(scheduler_of(num_pages=7), seq_of("q1", 40),
                    seq_of("q2", 40, 500), seq_of("q3", 40, 900))
    assert planned(sched)[1] == [("q1", 0, 40), ("q2", 0, 40)]
    q3 = sched.running[-1]
    assert (q3.status, q3.pages, sched.preempted_total) == ("running", [], 0)
    assert not sched.errored


def test_a_mixed_step_keeps_one_prefill_sequence():
    sched = scheduler_of()
    dec = Sequence("dec", list(range(1, 31)),
                   SamplingOptions(max_tokens=8, ignore_eos=True))
    running(sched, dec)
    planned(sched)
    dec.output_tokens.append(7)  # its first token is here: it decodes
    running(sched, seq_of("q1", 30, 500), seq_of("q2", 30, 900))
    plan = sched.schedule()
    assert plan.kind == "mixed" and plan.decode == [dec]
    assert [(it.seq.request_id, it.short) for it in plan.prefill] == [
        ("q1", False)]  # at its own bucket: a mixed program is a product


def test_the_plan_says_which_steps_run_at_the_short_bucket():
    """`PrefillItem.short` is the scheduler's decision, made once: the
    engine picks the step's bucket and programs from it and asks nobody."""
    sched = running(scheduler_of(), seq_of("q1", 40), seq_of("doc", 600, 500),
                    seq_of("q2", 20, 3000))
    plan = sched.schedule()
    assert [(it.seq.request_id, it.short) for it in plan.prefill] == [
        ("q1", True), ("q2", True)]
    for it in plan.prefill:
        it.seq.num_computed += it.chunk_len
        it.seq.output_tokens.append(7)
        sched.finish(it.seq, "length")
    (head,) = sched.schedule().prefill  # 512 of the document's 600 tokens
    assert (head.chunk_len, head.short) == (512, False)
    head.seq.num_computed += 512
    (rest,) = sched.schedule().prefill  # its 88-token remainder is not short
    assert (rest.chunk_len, rest.short) == (88, False)
    # one row a step: nothing is short, a lone 40-token prompt keeps its own
    # bucket
    (lone,) = running(scheduler_of(prefill_batch_size=1),
                      seq_of("q", 40)).schedule().prefill
    assert not lone.short


@pytest.mark.parametrize("parallel,over", [
    (ParallelConfig(dp=2, tp=2), {}),
    (ParallelConfig(dp=2), {"kv_partition": True}),
    (ParallelConfig(pp=2, dp=2), {}),
    (ParallelConfig(sp=2, dp=2), {}),
], ids=["dpxtp", "pooled", "pp", "sp"])
def test_every_layout_nobody_has_timed_keeps_one_sequence_a_step(
        parallel, over):
    layout, cfg = Layout.resolve(tiny_config(), EngineConfig(
        page_size=8, num_pages=64, max_num_seqs=4, max_prefill_tokens=128,
        max_model_len=128, **over), parallel)
    assert (cfg.prefill_batch_size, cfg.short_chunk_bucket) == (1, 0)
    sched = running(Scheduler(cfg, PagePool(cfg.num_pages, cfg.page_size)),
                    seq_of("q1", 12), seq_of("q2", 12, 500))
    assert planned(sched)[1] == [("q1", 0, 12)]


def test_a_multihost_leader_keeps_one_sequence_a_step():
    given = EngineConfig(max_prefill_tokens=512, max_model_len=256)
    _, cfg = Layout.resolve(tiny_config(), given,
                            ParallelConfig(dp=4, tp=2), multihost=True)
    assert (cfg.prefill_batch_size, cfg.short_chunk_bucket) == (1, 0)
    _, flat = Layout.resolve(tiny_config(), given)
    assert (flat.prefill_batch_size,
            flat.short_chunk_bucket) == (SHARED_PREFILL_ROWS, 64)


# -- a closed set of programs, none found by two rows meeting --------------------- #

async def test_both_short_programs_are_run_when_the_first_short_chunk_is():
    """The docqa cell's sizes (512-token steps, 16-token pages, 1-2 k-token
    documents, 16-48 fresh tokens a question).  One client first: no two
    rows ever meet, yet every program the rule can pick is there
    afterwards, so four clients on other tokens compile nothing; and four
    clients first leave the lone programs behind for one client."""
    greedy = {"temperature": 0.0}
    docs = (1040, 1500, 2040, 1300)  # tables of 128, 128, 256, 128 pages
    fresh = (16, 48, 30, 40)

    def engine_of():
        return tiny_engine(page_size=16, num_pages=1536, max_model_len=4096,
                           max_prefill_tokens=512, max_num_seqs=16)

    async def alone(engine, salt):
        for c in range(4):
            await session(engine, c, greedy, docs[c], fresh[c], salt)

    async def together(engine, salt):
        await asyncio.wait_for(asyncio.gather(*(
            session(engine, c, greedy, docs[c], fresh[c], salt)
            for c in range(4))), 180)

    runs = {}
    for first, then in ((alone, together), (together, alone)):
        engine = engine_of()
        step = engine.layout.prefill_step(False, greedy=True)
        try:
            await first(engine, 0)
            met = set(engine._short_prefill_met)  # noqa: SLF001
            programs = step._cache_size()  # noqa: SLF001
            seen = len(events_of(engine, "prefill_chunk"))
            await then(engine, 5)
        finally:
            await engine.shutdown()
        chunks = events_of(engine, "prefill_chunk")
        runs[first.__name__] = chunks[:seen]
        assert met == {(False, True, 128), (False, True, 256)}
        # nothing compiled in the second phase, whoever met or did not
        assert step._cache_size() == programs  # noqa: SLF001
        assert engine._short_prefill_met == met  # noqa: SLF001
        long = {(e["bucket"], e["pages"]) for e in chunks if e["bucket"] > 64}
        # a program a long shape, and two a table width for the short steps
        assert programs == len(long) + 2 * len(met)
        # what the rule emitted: ONE bucket; one row or ONE row count
        short = [e for e in chunks if e["bucket"] <= 64]
        assert {(e["bucket"], e["pages"]) for e in short} == {
            (64, w) for _, _, w in met}
        assert all(e["batch"] <= SHARED_PREFILL_ROWS
                   and e["tokens"] <= 64 * e["batch"] for e in short)
        assert all(e["batch"] == 1 for e in chunks if e["bucket"] > 64)
        assert refs_held(engine) == 0
    assert all(e["batch"] == 1 for e in runs["alone"])
    assert any(e["batch"] > 1 for e in runs["together"]), (
        "four clients in a closed loop were meant to meet")


# -- what the slice, the counters and first_token say ------------------------------ #

async def four_short_prompts(engine, n=1):
    return await asyncio.wait_for(asyncio.gather(*(
        generate(engine, list(range(1 + 20 * i, 13 + 20 * i + i)), n, f"r{i}")
        for i in range(4))), 120)


async def test_a_shared_step_is_one_slice_and_every_row_s_own_time():
    engine = tiny_engine()
    try:
        done = await four_short_prompts(engine)
    finally:
        await engine.shutdown()
    assert all(d[2] == "length" and len(d[0]) == 1 for d in done)
    (chunk,) = events_of(engine, "prefill_chunk")
    assert (chunk["batch"], chunk["tokens"], chunk["bucket"]) == (
        4, 12 + 13 + 14 + 15, 16)
    assert "rid" not in chunk and chunk["overlapped"] == 0
    m = vars(engine.metrics())
    assert (m["prefill_steps_total"], m["prefill_rows_total"]) == (1, 4)
    firsts = events_of(engine, "first_token")
    assert sorted(e["rid"] for e in firsts) == ["r0", "r1", "r2", "r3"]
    for e in firsts:
        assert e["queue_us"] + e["wait_us"] + e["own_us"] == e["total_us"]
        assert min(e["queue_us"], e["wait_us"], e["own_us"]) >= 0
        # the one step is each row's own working time, whole: a first
        # token is noted inside the slice's delivery, so what the slice
        # has and the row has not is at most `deliver_us` (and 2 for the
        # truncations to whole microseconds); no stretch of time is named
        assert e["steps"] == 1
        assert (chunk["dur_ns"] / 1000 - chunk["deliver_us"] - 2
                <= e["own_us"] <= chunk["dur_ns"] / 1000), (e, chunk)
    assert refs_held(engine) == 0


async def test_rows_over_steps_in_metrics_is_the_slices_batch():
    engine = tiny_engine()
    try:
        await asyncio.wait_for(asyncio.gather(*(
            session(engine, c, {"temperature": 0.0}) for c in range(3))), 120)
    finally:
        await engine.shutdown()
    chunks, m = events_of(engine, "prefill_chunk"), vars(engine.metrics())
    assert m["prefill_steps_total"] == len(chunks)
    assert m["prefill_rows_total"] == sum(e["batch"] for e in chunks) == 15


# -- a shared step in flight: abort of one row, a failed fetch ---------------------- #

async def test_an_abort_of_one_row_in_flight_lets_the_other_rows_deliver():
    engine = tiny_engine()
    loop = asyncio.get_running_loop()
    tasks, seen = {}, []
    real_dispatch = engine._prefill_dispatch  # noqa: SLF001
    real_abort = engine.scheduler.abort

    def dispatch_then_lose_a_client(items):
        step = real_dispatch(items)
        if len(items) > 1 and "r1" in [it.seq.request_id for it in items]:
            loop.call_soon_threadsafe(tasks["r1"].cancel)
        return step

    def abort(rid):
        victim = [s for s in engine.scheduler.running if s.request_id == rid]
        free = engine.pool.free_pages
        real_abort(rid)
        if victim:
            seen.append({
                "rows_in_flight": len(engine.scheduler.in_flight),
                "in_flight": victim[0] in engine.scheduler.in_flight,
                "deferred": len(engine.scheduler.deferred_free or ()),
                "freed_at_once": engine.pool.free_pages - free})

    engine._prefill_dispatch = dispatch_then_lose_a_client  # noqa: SLF001
    engine.scheduler.abort = abort
    try:
        for i in range(4):
            tasks[f"r{i}"] = asyncio.ensure_future(generate(
                engine, list(range(1 + 20 * i, 13 + 20 * i)), 4, f"r{i}"))
        # a document behind them: the plan made while the shared step is
        # in flight (the one that runs the abort) is a further prefill step
        doc = asyncio.ensure_future(generate(engine, doc_of(9, 200), 1, "doc"))
        done = await asyncio.wait_for(asyncio.gather(
            *tasks.values(), doc, return_exceptions=True), 120)
        want = [await generate(engine, list(range(1 + 20 * i, 13 + 20 * i)),
                               4) for i in range(4)]
    finally:
        await engine.shutdown()
    assert isinstance(done[1], asyncio.CancelledError)
    for i in (0, 2, 3):  # the other rows: the tokens they get when alone
        assert done[i][0] == want[i][0] and done[i][2] == "length"
    assert done[4][2] == "length"
    assert seen and seen[0]["in_flight"] and seen[0]["rows_in_flight"] > 1
    # the victim's two pages waited for the step's fetch
    assert (seen[0]["freed_at_once"], seen[0]["deferred"]) == (0, 2)
    assert not [e for e in events_of(engine, "first_token")
                if e["rid"] == "r1"]
    assert refs_held(engine) == 0


async def test_a_failed_fetch_of_a_shared_step_ends_every_row_and_recovers(
        monkeypatch):
    engine = tiny_engine()
    state = {"armed": False, "fired": 0}
    real_consume = engine._prefill_consume  # noqa: SLF001
    real_get = jax.device_get

    def consume(step):
        if not state["fired"] and len(step.items) > 1:
            state["armed"] = True
        return real_consume(step)

    def device_get(x):
        if state["armed"]:
            state["armed"] = False
            state["fired"] += 1
            raise RuntimeError("fetch failed")
        return real_get(x)

    engine._prefill_consume = consume  # noqa: SLF001
    monkeypatch.setattr(eng.jax, "device_get", device_get)
    try:
        done = await four_short_prompts(engine)
        assert state["fired"] == 1
        assert engine._inflight is None  # noqa: SLF001
        assert engine.scheduler.in_flight == ()
        assert engine.scheduler.deferred_free is None
        assert [d[2] for d in done] == ["error"] * 4
        assert all(d[0] == [] for d in done)
        after = await four_short_prompts(engine)
    finally:
        await engine.shutdown()
    fresh = tiny_engine(prefill_batch_size=1)
    try:
        want = [await generate(fresh, list(range(1 + 20 * i, 13 + 20 * i + i)),
                               1) for i in range(4)]
    finally:
        await fresh.shutdown()
    assert [a[0] for a in after] == [w[0] for w in want]
    assert all(a[2] == "length" for a in after)
    assert refs_held(engine) == 0
