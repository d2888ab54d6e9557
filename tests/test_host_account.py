"""The step loop's account on a CPU engine (ISSUE 57): the phases on the ring
tile the loop's time, every program a step hands to the device takes the next
`seq`, `dry` says whether the device had run dry at the dispatch, and a shared
prefill step names its rows' own chunks and contexts.  Every assertion is a
ratio or a count of the ring's own records: no CPU clock decides a case.  The
reader is the benchmark's (`benchmark/lib/hostline.py`, loaded by path as
`benchmark/run.py` would): program to reader, end to end."""

import asyncio
import os
import sys

import pytest

from test_prefill_overlap import generate, session, tiny_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
STEPS = ("prefill_chunk", "decode_block", "mixed_step", "spec_round")


@pytest.fixture(scope="module")
def hostline():
    sys.path.insert(0, BENCH)
    try:
        from lib import hostline as mod
    finally:
        sys.path.remove(BENCH)
    return mod


@pytest.fixture(scope="module")
def ring():
    """Two rounds of four clients (a document in three chunks, two questions,
    four tokens each: overlapped prefill steps, mixed steps, decode blocks)
    on ONE engine; the events of the second, warm round."""
    async def rounds():
        engine = tiny_engine()
        try:
            for _ in range(2):
                mark = engine.events.total
                await asyncio.wait_for(asyncio.gather(*(
                    session(engine, c, {"temperature": 0.0}, 4)
                    for c in range(4))), 120)
        finally:
            await engine.shutdown()
        # read after shutdown: a step's slice is written after its tokens
        # are handed on, so a stream can end before its last step's record
        dump = engine.events.dump()
        assert dump["dropped_total"] == 0
        return dump["events"][mark:], engine.metrics()

    return asyncio.run(rounds())


def assert_tiles(hostline, events):
    """From the first plan to the last phase: no two phases overlap, and
    under 1% of the loop's time lies under none.  Returns the timeline."""
    line = hostline.timeline(events)
    a = min(e["t_ns"] for e in events if e["kind"] == "plan")
    b = max(end for _, end, _ in line)
    covered, overlapped = hostline.coverage(line, a, b)
    assert overlapped == 0
    assert (b - a) - covered < 0.01 * (b - a), _holes(line, events, a)
    return line


def _holes(line, events, a):
    """What a failed tiling shows: the holes of a millisecond and more, and
    the loop's records around them (milliseconds from the first plan)."""
    out, at = [], a
    for s, e, phase in line:
        if s - at > 1_000_000:
            out.append(f"hole {(at - a) / 1e6:.3f}-{(s - a) / 1e6:.3f} "
                       f"before {phase}")
        at = max(at, e)
    for e in sorted(events, key=lambda e: e["t_ns"]):
        if e["dur_ns"]:
            out.append(f"{e['kind']} {(e['t_ns'] - a) / 1e6:.3f} "
                       f"+{e['dur_ns'] / 1e6:.3f} " + " ".join(
                           f"{k}={v}" for k, v in e.items()
                           if k.endswith("_us") or k in ("seq", "op")))
    return "\n".join(out)


def test_the_loops_account_tiles(ring, hostline):
    events, _ = ring
    kinds = {e["kind"] for e in events}
    assert {"prefill_chunk", "mixed_step", "decode_block"} <= kinds
    assert any(e.get("overlapped") for e in events)
    line = assert_tiles(hostline, events)
    assert {"hop", "build", "dispatch", "fetch", "deliver", "plan",
            "loop_yield"} <= {phase for _, _, phase in line}
    steps = [e for e in events if e["kind"] in STEPS]
    assert all("hop_us" in e and e["hop_us"] >= 0 for e in steps)


def test_seq_rises_by_one_a_program(ring):
    events, _ = ring
    steps = sorted((e for e in events if e["kind"] in STEPS),
                   key=lambda e: e["seq"])
    assert len({e["seq"] for e in steps}) == len(steps)
    for e, nxt in zip(steps, steps[1:]):
        took = {"prefill_chunk": 1 + e.get("fused_blocks", 0),
                "decode_block": e.get("blocks", 1)}.get(e["kind"], 1)
        assert nxt["seq"] - e["seq"] == took, (e, nxt)
    # the order of the ordinals is the order of the jitted calls
    calls = [e["t_ns"] + e["build_us"] * 1000 for e in steps]
    assert calls == sorted(calls)


def test_a_chained_decode_block_and_a_fused_chain_say_how_many_they_took():
    async def run():
        engine = tiny_engine(decode_steps=2, decode_chain=3)
        try:
            await generate(engine, [1, 2, 3, 4, 5], n=16)
        finally:
            await engine.shutdown()
        return [e for e in engine.events.dump()["events"]
                if e["kind"] in STEPS]

    steps = sorted(asyncio.run(run()), key=lambda e: e["seq"])
    took = [nxt["seq"] - e["seq"] for e, nxt in zip(steps, steps[1:])]
    assert steps[0]["seq"] == 0 and max(took) > 1
    for e, n in zip(steps, took):
        assert n == (1 + e["fused_blocks"] if e["kind"] == "prefill_chunk"
                     else e["blocks"])


class _Unfinished:
    """Stands in for a result whose program is still running."""

    @staticmethod
    def is_ready():
        return False


@pytest.mark.parametrize("finished", [True, False])
def test_dry_says_whether_the_step_in_flight_had_finished(finished):
    """A step dispatched after the one in flight was ready is dry; one
    dispatched behind an unfinished one is not.  `overlapped` is 1 for both:
    it says the older step was unfetched, not that it was running."""
    async def run():
        engine = tiny_engine()
        real = engine._prefill_dispatch  # noqa: SLF001

        def dispatch(items):
            older = engine._inflight  # noqa: SLF001
            if older is None:
                return real(items)
            result = older.packed_d
            result.block_until_ready()
            if not finished:
                older.packed_d = _Unfinished
            try:
                return real(items)
            finally:
                older.packed_d = result

        engine._prefill_dispatch = dispatch  # noqa: SLF001
        try:
            doc = [1 + (3 * i) % 250 for i in range(44)]   # three chunks
            await generate(engine, doc)
        finally:
            await engine.shutdown()
        chunks = [e for e in engine.events.dump()["events"]
                  if e["kind"] == "prefill_chunk"]
        return chunks, engine.metrics()

    chunks, metrics = asyncio.run(run())
    chunks.sort(key=lambda e: e["seq"])
    assert [e["overlapped"] for e in chunks] == [0, 1, 1]
    assert [e["dry"] for e in chunks] == [1, int(finished), int(finished)]
    assert metrics.steps_dry_total == sum(e["dry"] for e in chunks)


def test_every_step_kind_is_counted_dry_or_not(ring):
    events, metrics = ring
    steps = [e for e in events if e["kind"] in STEPS]
    assert all(e["dry"] in (0, 1) for e in steps)
    # a step that is not overlapped follows `_consume_inflight`: dry
    assert all(e["dry"] == 1 for e in steps if e["kind"] != "prefill_chunk")
    assert all(e["dry"] == 1 for e in steps
               if e["kind"] == "prefill_chunk" and not e["overlapped"])
    assert metrics.steps_dry_total >= sum(e["dry"] for e in steps)


def test_a_shared_step_names_its_rows_chunks_and_contexts():
    """Four clients' questions on their cached documents meet in shared
    steps: `toks` and `ctxs` in row order, `tokens` their sum and `ctx` the
    longest row's, as before."""
    import test_prefill_batching as batching

    async def run():
        engine = batching.tiny_engine()
        await batching.served(engine, 4, {"temperature": 0.0})
        return batching.events_of(engine, "prefill_chunk")

    chunks = asyncio.run(run())
    shared = [e for e in chunks if e["batch"] > 1]
    assert shared
    for e in chunks:
        if e["batch"] == 1:
            assert "ctxs" not in e and "toks" not in e
            continue
        assert len(e["toks"]) == len(e["ctxs"]) == e["batch"]
        assert sum(e["toks"]) == e["tokens"]
        assert max(e["ctxs"]) == e["ctx"]
        assert all(c >= t > 0 for t, c in zip(e["toks"], e["ctxs"]))
    # a question's row sees its cached document: its context is longer
    # than its chunk
    assert any(c > t for e in shared for t, c in zip(e["toks"], e["ctxs"]))


def test_the_pump_records_what_it_does_between_plans(hostline):
    """A queued device op runs on the step thread under a `pump_op` slice,
    and the step in flight before it is fetched with its hand-off on the
    slice (`fetch_hop_us`); the account still tiles."""
    async def run():
        engine = tiny_engine()
        try:
            doc = [1 + (3 * i) % 250 for i in range(44)]
            task = asyncio.ensure_future(generate(engine, doc, n=3))
            ran = await engine._device_op(lambda: 7)  # noqa: SLF001
            await task
        finally:
            await engine.shutdown()
        return ran, engine.events.dump()["events"]

    ran, events = asyncio.run(run())
    assert ran == 7
    ops = [e for e in events if e["kind"] == "pump_op"]
    assert [e["op"] for e in ops] == ["device_op"]
    assert_tiles(hostline, events)
