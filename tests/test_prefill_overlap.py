"""One prefill step in flight ahead of the fetch (ISSUE 32): the next prefill
step is planned, built and dispatched before the last one's result is
fetched and delivered.  Same programs, same tokens; the scheduler plans
around the step in flight; abort, page pressure and a failed fetch while a
step is in flight leave the pool balanced and every stream ended."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine import engine as eng
from dynamo_tpu.engine.page_pool import PagePool
from dynamo_tpu.engine.scheduler import (
    SamplingOptions, Scheduler, Sequence, StepPlan)
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.runtime.engine import Context


def tiny_engine(**over):
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ecfg = dict(page_size=8, num_pages=128, max_num_seqs=8,
                max_prefill_tokens=16, max_model_len=128, decode_steps=2)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32)


def consume_at_once(engine):
    """The same engine with no step ever left in flight: the parent's order
    (build, dispatch, fetch, deliver, then plan)."""
    engine.layout.holds_step_in_flight = False
    return engine


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
    """The account `DYN_TPU_LEAKCHECK` reads at shutdown: page refs held."""
    return sum(engine.pool._refs.values())  # noqa: SLF001


def chunks_of(engine):
    return [e for e in engine.events.dump()["events"]
            if e["kind"] == "prefill_chunk"]


async def session(engine, client, sampling, n=1):
    """A document (three 16-token chunks) and two questions on it, one after
    the other: the questions hit the document's pages in the prefix cache."""
    doc = [1 + (7 * client + 3 * i) % 250 for i in range(44)]
    out = [await generate(engine, doc, n, f"c{client}-doc", sampling)]
    for q in range(2):
        ask = doc + [200 + client, 10 + q, 20 + q, 5]
        out.append(await generate(engine, ask, n, f"c{client}-q{q}", sampling))
    return out


async def served(engine, clients, sampling, n=1):
    try:
        return await asyncio.wait_for(asyncio.gather(*(
            session(engine, c, sampling, n) for c in range(clients))), 120)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("clients", [1, 4])
@pytest.mark.parametrize("sampling", [
    {"temperature": 0.0},
    {"temperature": 0.8, "top_k": 20, "top_p": 0.9, "seed": 1234},
], ids=["greedy", "seeded"])
async def test_tokens_and_logprobs_are_those_of_consume_at_once(
        clients, sampling):
    held = tiny_engine()
    got = await served(held, clients, sampling)
    want = await served(consume_at_once(tiny_engine()), clients, sampling)
    assert got == want  # token ids and float logprobs, bit for bit
    assert all(finish == "length" and len(toks) == 1
               for s in got for toks, _, finish in s)
    chunks = chunks_of(held)
    hits = [e for e in held.events.dump()["events"]
            if e["kind"] == "admit" and e["cached"] > 0]
    assert len(hits) == 2 * clients  # every question hit its document
    # a document's second and third chunk go out behind its first
    assert sum(e["overlapped"] for e in chunks) >= 2 * clients
    assert refs_held(held) == 0


async def test_decoding_streams_are_those_of_consume_at_once():
    """With answers of several tokens the plans pass through prefill, mixed
    and decode steps; a step in flight is fetched before any but the
    first."""
    held = tiny_engine()
    seen = []
    for name in ("_run_mixed", "_run_decode"):
        def spy(arg, run=getattr(held, name)):
            seen.append((held._inflight, held.scheduler.in_flight))  # noqa: SLF001
            return run(arg)
        setattr(held, name, spy)
    got = await served(held, 4, {"temperature": 0.0}, n=4)
    want = await served(consume_at_once(tiny_engine()), 4,
                        {"temperature": 0.0}, n=4)
    assert [[toks for toks, _, _ in s] for s in got] == [
        [toks for toks, _, _ in s] for s in want]
    assert seen and all(step is None and flight == ()
                        for step, flight in seen)
    assert refs_held(held) == 0


def small_scheduler(num_pages=64, **over):
    ecfg = dict(page_size=8, num_pages=num_pages, max_num_seqs=4,
                max_prefill_tokens=16, max_model_len=128, decode_steps=2,
                watermark=0.0)
    ecfg.update(over)
    cfg = EngineConfig(**ecfg)
    return Scheduler(cfg, PagePool(cfg.num_pages, cfg.page_size))


def seq_of(rid, n_prompt, max_tokens=4, start=1):
    return Sequence(rid, list(range(start, start + n_prompt)),
                    SamplingOptions(max_tokens=max_tokens, ignore_eos=True))


def dispatch(sched, plan):
    """What the engine does when it commits a prefill plan and leaves the
    step in flight."""
    assert plan.kind == "prefill"
    for it in plan.prefill:
        it.seq.num_computed += it.chunk_len
    sched.in_flight = tuple(it.seq for it in plan.prefill)


def test_a_sampling_chunk_in_flight_is_no_reason_to_decode_or_mix():
    sched = small_scheduler()
    a = seq_of("a", 12)
    sched.add(a)
    plan = sched.schedule()
    assert [it.samples for it in plan.prefill] == [True]
    dispatch(sched, plan)
    assert a.prefill_done and not a.output_tokens
    # nothing else to do: not a decode plan over a token that is not here
    assert sched.schedule().kind == "idle"
    # a prompt arrives: a plain prefill step, not a mixed one
    b = seq_of("b", 40, start=100)
    sched.add(b)
    plan = sched.schedule()
    assert plan.kind == "prefill" and not plan.decode
    assert [it.seq for it in plan.prefill] == [b]
    # the step is consumed: the same state plans a's decode beside b
    sched.in_flight = ()
    a.output_tokens.append(7)
    plan = sched.schedule()
    assert plan.kind == "mixed" and plan.decode == [a]


def test_a_mid_prompt_chunk_in_flight_has_its_next_chunk_planned():
    sched = small_scheduler()
    a = seq_of("a", 40)
    sched.add(a)
    dispatch(sched, sched.schedule())
    plan = sched.schedule()
    assert plan.kind == "prefill"
    (it,) = plan.prefill
    assert (it.seq, it.chunk_start, it.chunk_len) == (a, 16, 16)


def test_no_sequence_loses_its_pages_while_a_step_is_in_flight():
    # 7 usable pages: a's 40 tokens take 5, b's first chunk 2
    sched = small_scheduler(num_pages=8)
    a, b = seq_of("a", 40, max_tokens=1), seq_of("b", 40, start=100)
    sched.add(a)
    for _ in range(2):
        for it in sched.schedule().prefill:
            it.seq.num_computed += it.chunk_len
    dispatch(sched, sched.schedule())  # a's sampling chunk
    assert a.prefill_done and len(a.pages) == 5
    sched.add(b)
    dispatch_b = sched.schedule()
    assert dispatch_b.kind == "prefill"  # b's first chunk fits
    for it in dispatch_b.prefill:
        it.seq.num_computed += it.chunk_len
    # b's second chunk needs 2 more pages and 0 are free.  Today's plan
    # would preempt the youngest other sequence; with a's step in flight
    # the plan is abandoned, a keeps its pages and nobody is errored
    held = list(a.pages)
    plan = sched.schedule()
    assert plan.kind == "idle"
    assert a.status == "running" and a.pages == held
    assert b.status == "running" and not sched.errored
    assert sched.preempted_total == 0 and a.preemptions == 0
    # consumed: a finishes at its one token, and b's chunk fits
    sched.in_flight = ()
    a.output_tokens.append(3)
    sched.finish(a, "length")
    plan = sched.schedule()
    assert plan.kind == "prefill" and plan.prefill[0].seq is b
    assert plan.prefill[0].chunk_start == 16


def test_an_interactive_head_parks_its_victim_after_the_fetch_not_later():
    # two slots: a batch sequence decoding, a prompt whose chunk is in flight
    sched = small_scheduler(max_num_seqs=2)
    parked = []
    sched.park_fn = lambda seq: parked.append(seq.request_id) or True
    v, p = seq_of("v", 12, max_tokens=8), seq_of("p", 40, start=100)
    v.priority = "batch"
    sched.add(v)
    for it in sched.schedule().prefill:
        it.seq.num_computed += it.chunk_len
    v.output_tokens.append(5)
    sched.add(p)
    plan = sched.schedule()  # p's first chunk (v rides or waits: no matter)
    dispatch(sched, StepPlan("prefill", prefill=[
        it for it in plan.prefill if it.seq is p]))
    head = seq_of("head", 12, start=200)
    sched.add(head)
    # the head could have v's slot, but not under the step in flight: the
    # plan is abandoned (the engine consumes the step and plans again)
    # instead of going on to p's further chunks with the head left waiting
    assert sched.schedule().kind == "idle"
    assert not parked and v.status == "running" and head.status == "waiting"
    sched.in_flight = ()
    assert sched.schedule().kind == "prefill"
    assert parked == ["v"] and v.parked and head.status == "running"
    # with nobody to park the head just waits, and the prefills go on
    sched2 = small_scheduler(max_num_seqs=1)
    sched2.park_fn = lambda seq: True
    q = seq_of("q", 40)
    sched2.add(q)
    dispatch(sched2, sched2.schedule())
    sched2.add(seq_of("head", 12, start=200))
    assert sched2.schedule().kind == "prefill"


def test_a_step_s_pages_are_committed_no_further_than_it_wrote():
    sched = small_scheduler()
    a = seq_of("a", 40)
    sched.add(a)
    first = sched.schedule().prefill[0]
    a.num_computed += first.chunk_len  # chunk 0-16 dispatched
    sched.in_flight = (a,)
    second = sched.schedule().prefill[0]
    a.num_computed += second.chunk_len  # 16-32 dispatched behind it
    sched.commit_full_pages(a, first.chunk_start + first.chunk_len)
    assert a.committed_pages == 2  # 16 tokens, not the 32 accounted
    sched.commit_full_pages(a, second.chunk_start + second.chunk_len)
    assert a.committed_pages == 4


async def test_overlapped_steps_are_counted_on_the_slice_and_in_metrics():
    engine = tiny_engine()
    try:
        await asyncio.gather(*(
            generate(engine, list(range(1 + i, 41 + i)), 1, f"r{i}")
            for i in range(4)))
    finally:
        await engine.shutdown()
    # read after the shutdown: a step's record is written after its last
    # delta went out
    chunks, m = chunks_of(engine), vars(engine.metrics())
    assert len(chunks) == 12  # four prompts of 16 + 16 + 8 tokens
    assert m["prefill_steps_total"] == 12
    assert m["prefill_steps_overlapped_total"] == sum(
        e["overlapped"] for e in chunks) >= 8
    for e in chunks:
        assert e["overlapped"] in (0, 1) and e["overlap_us"] >= 0
        parts = sum(e[p] for p in ("build_us", "dispatch_us", "overlap_us",
                                   "fetch_us", "deliver_us"))
        assert e["dur_ns"] // 1000 - 100 <= parts <= e["dur_ns"] // 1000
    # depth one: a slice holds no other step's slice whole, and opens after
    # the slice before the last one has closed
    ordered = sorted(chunks, key=lambda e: e["t_ns"])
    ends = [e["t_ns"] + e["dur_ns"] for e in ordered]
    assert ends == sorted(ends)
    for i in range(2, len(ordered)):
        assert ordered[i]["t_ns"] >= ends[i - 2]
    firsts = [e for e in engine.events.dump()["events"]
              if e["kind"] == "first_token"]
    assert len(firsts) == 4
    for e in firsts:
        assert e["queue_us"] + e["wait_us"] + e["own_us"] == e["total_us"]
        assert min(e["queue_us"], e["wait_us"], e["own_us"]) >= 0


async def test_a_lone_request_is_consumed_at_once():
    engine = tiny_engine()
    try:
        await generate(engine, list(range(1, 13)), 1, "lone")
        await generate(engine, list(range(50, 90)), 1, "lone-3-chunks")
    finally:
        await engine.shutdown()
    chunks, m = chunks_of(engine), vars(engine.metrics())
    by = {}
    for e in sorted(chunks, key=lambda e: e["t_ns"]):
        by.setdefault(e["rid"], []).append(e["overlapped"])
    # one chunk: nothing to put behind it, fetched as soon as the plan says
    # so.  Three chunks: the request's own next chunk goes behind each
    assert by == {"lone": [0], "lone-3-chunks": [0, 1, 1]}
    assert (m["prefill_steps_total"],
            m["prefill_steps_overlapped_total"]) == (4, 2)


async def test_an_abort_while_its_step_is_in_flight_keeps_the_pool_balanced():
    engine = tiny_engine()
    loop = asyncio.get_running_loop()
    tasks, seen = {}, []
    real_dispatch = engine._prefill_dispatch  # noqa: SLF001
    real_abort = engine.scheduler.abort

    def dispatch_then_lose_the_client(items):
        step = real_dispatch(items)
        if items[0].seq.request_id == "victim" and items[0].samples:
            loop.call_soon_threadsafe(tasks["victim"].cancel)
        return step

    def abort(rid):
        victim = [s for s in engine.scheduler.running if s.request_id == rid]
        free = engine.pool.free_pages
        real_abort(rid)
        if victim:
            seen.append({
                "in_flight": victim[0] in engine.scheduler.in_flight,
                "deferred": list(engine.scheduler.deferred_free or ()),
                "pages": list(victim[0].pages), "freed_at_once":
                    engine.pool.free_pages - free})

    engine._prefill_dispatch = dispatch_then_lose_the_client  # noqa: SLF001
    engine.scheduler.abort = abort
    try:
        tasks["victim"] = asyncio.ensure_future(
            generate(engine, list(range(1, 41)), 4, "victim"))
        others = [asyncio.ensure_future(generate(
            engine, list(range(60 + i, 100 + i)), 1, f"o{i}"))
            for i in range(3)]
        done = await asyncio.wait_for(
            asyncio.gather(*others, tasks["victim"],
                           return_exceptions=True), 120)
        want = [await generate(engine, list(range(60 + i, 100 + i)), 1)
                for i in range(3)]
    finally:
        await engine.shutdown()
    assert isinstance(done[3], asyncio.CancelledError)
    # every other stream delivered, with the tokens it gets when alone
    assert [d[:2] for d in done[:3]] == [w[:2] for w in want]
    # the abort met the victim's sampling chunk in flight: its pages waited
    # in `deferred_free` for the step's fetch and went back then
    assert seen and seen[0]["in_flight"] and seen[0]["freed_at_once"] == 0
    assert seen[0]["pages"] == [] and len(seen[0]["deferred"]) == 5
    assert refs_held(engine) == 0
    assert not [e for e in engine.events.dump()["events"]
                if e["kind"] == "first_token" and e["rid"] == "victim"]


async def test_page_pressure_preempts_only_between_steps():
    prompts = [list(range(1 + 50 * i, 41 + 50 * i)) for i in range(4)]
    roomy = tiny_engine()
    try:
        want = [await generate(roomy, p, 12) for p in prompts]
    finally:
        await roomy.shutdown()
    # 11 usable pages for four streams that grow to 7 pages each
    engine = tiny_engine(num_pages=12, watermark=0.0)
    at_preempt = []
    real = engine.scheduler._preempt  # noqa: SLF001

    def preempt(seq):
        at_preempt.append((engine._inflight,  # noqa: SLF001
                           engine.scheduler.in_flight))
        real(seq)

    engine.scheduler._preempt = preempt  # noqa: SLF001
    try:
        got = await asyncio.wait_for(asyncio.gather(*(
            generate(engine, p, 12, f"p{i}")
            for i, p in enumerate(prompts))), 120)
    finally:
        await engine.shutdown()
    assert [g[0] for g in got] == [w[0] for w in want]
    assert all(g[2] == "length" for g in got)
    assert at_preempt, "the pool was meant to be too small"
    assert all(step is None and flight == ()
               for step, flight in at_preempt)
    assert refs_held(engine) == 0


async def test_a_failed_fetch_takes_the_step_behind_it_down_and_recovers(
        monkeypatch):
    engine = tiny_engine()
    state = {"armed": False, "fired": 0}
    real_consume = engine._prefill_consume  # noqa: SLF001
    real_get = jax.device_get

    def consume(step):
        newer = engine._inflight  # noqa: SLF001
        if not state["fired"] and newer is not None and newer is not step:
            state["armed"] = True  # an older step, fetched under a newer
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
        done = await asyncio.wait_for(asyncio.gather(*(
            generate(engine, list(range(1 + i, 41 + i)), 1, f"r{i}")
            for i in range(4))), 120)
        assert state["fired"] == 1
        assert engine._inflight is None  # noqa: SLF001
        assert engine.scheduler.in_flight == ()
        assert engine.scheduler.deferred_free is None
        # every stream ended: with its token, or with the error
        assert sorted({d[2] for d in done}) in (["error"],
                                                ["error", "length"])
        assert all(d[0] == [] for d in done if d[2] == "error")
        # and the engine serves on, with the tokens of an engine that
        # never failed
        after = await generate(engine, list(range(1, 41)), 1, "after")
    finally:
        await engine.shutdown()
    fresh = tiny_engine()
    try:
        want = await generate(fresh, list(range(1, 41)), 1)
    finally:
        await fresh.shutdown()
    assert after == want and after[2] == "length"
    assert refs_held(engine) == 0


def test_a_multihost_leader_consumes_at_once():
    from dynamo_tpu.engine.layout import Layout

    engine = tiny_engine()
    assert engine.layout.holds_step_in_flight
    leader = Layout(engine.model_cfg, engine.cfg, multihost=True)
    assert not leader.holds_step_in_flight
