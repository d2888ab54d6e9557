"""Engine step-event recorder: ring semantics, the hot path's call
budget, the crash-surviving flight-recorder spill, and the
engine/status-server integration (docs/observability.md event schema)."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.runtime.events import (
    FLIGHT_HEADER_SIZE,
    FLIGHT_RECORD_SIZE,
    FlightRecorder,
    StepEventRecorder,
    load_flight_dir,
    load_flight_segment,
)
from dynamo_tpu.testing import call_ceiling, counted_calls


def test_ring_basics():
    rec = StepEventRecorder(capacity=4)
    rec.record("a", x=1)
    t0 = rec.now()
    rec.record("b", t0_ns=t0, rung=8)
    events = rec.snapshot()
    assert [e[2] for e in events] == ["a", "b"]
    assert events[0][1] == 0          # instant
    assert events[1][1] >= 0          # duration slice
    assert events[1][3] == {"rung": 8}
    assert len(rec) == 2 and rec.total == 2


def test_ring_wraps_oldest_first():
    rec = StepEventRecorder(capacity=3)
    for i in range(5):
        rec.record("e", i=i)
    events = rec.snapshot()
    assert [e[3]["i"] for e in events] == [2, 3, 4]
    assert rec.total == 5 and len(rec) == 3
    assert rec.dump()["dropped_total"] == 2


def test_disabled_recorder_is_inert():
    rec = StepEventRecorder(capacity=0)
    rec.record("a")
    assert rec.snapshot() == [] and len(rec) == 0
    assert rec.dump()["events"] == []


def test_dump_carries_time_anchors(monkeypatch):
    """wall/mono anchors let offline tools rebase monotonic event times
    onto the wall clock: they must describe the same instant, so `dump`
    reads the two clocks back to back, once each, and hands on what it
    read.  (Two real clocks agree to within what the thread was held
    between the readings: 50 ms was the allowance, and a busy machine's
    to take.)"""
    import types

    from dynamo_tpu.runtime import events

    reads = []

    def clock(name, value):
        def read():
            reads.append(name)
            return value
        return read

    monkeypatch.setattr(events, "time", types.SimpleNamespace(
        monotonic_ns=clock("mono", 5_000),
        time_ns=clock("wall", 1_700_000_000_000_005_000)))
    rec = StepEventRecorder(capacity=8)
    rec.record("a")
    del reads[:]
    dump = rec.dump()
    assert reads == ["mono", "wall"]
    assert dump["wall_ns"] - dump["mono_ns"] == 1_700_000_000_000_000_000
    ev = dump["events"][0]
    assert ev == {"kind": "a", "dur_ns": 0, "t_ns": 5_000}


def test_from_env_capacity(monkeypatch):
    monkeypatch.setenv("DYN_TPU_STEP_EVENTS", "16")
    assert StepEventRecorder.from_env().capacity == 16
    monkeypatch.setenv("DYN_TPU_STEP_EVENTS", "0")
    assert StepEventRecorder.from_env().enabled is False


def test_record_is_four_calls_an_event():
    """What `record()` costs on the decode hot path with exporters off,
    as work and not as time: itself, one clock read, the per-kind
    count's `dict.get` and the lock's release.  The ceiling IS today's
    count, with no margin, because a count does not vary: one more call
    an event fails this, one fewer passes."""
    rec = StepEventRecorder(capacity=4096)
    n = 2_000
    with counted_calls() as c:
        for i in range(n):
            rec.record("decode_block", rung=8, batch=4, chain=1)
    assert rec.total == n
    assert c.total // n <= call_ceiling(4), dict(c.names)


def test_dump_since_ns_cursor():
    """`dump(since_ns=watermark)` returns only events committed after the
    watermark — the /events.json poller contract.  Commit time is
    t_ns + dur_ns (record order), so a long slice recorded after the
    watermark is included even though it STARTED before it."""
    rec = StepEventRecorder(capacity=16)
    t_early = rec.now()
    rec.record("a", i=0)
    d1 = rec.dump()
    assert d1["watermark_ns"] > 0
    # nothing new: the cursor returns an empty delta, watermark unchanged
    d2 = rec.dump(since_ns=d1["watermark_ns"])
    assert d2["events"] == [] and d2["watermark_ns"] == d1["watermark_ns"]
    # a slice that STARTED before the watermark but committed after
    rec.record("b", t0_ns=t_early, i=1)
    rec.record("c", i=2)
    d3 = rec.dump(since_ns=d1["watermark_ns"])
    assert [e["kind"] for e in d3["events"]] == ["b", "c"]
    assert d3["watermark_ns"] > d1["watermark_ns"]


# -- flight recorder (crash-surviving spill) -------------------------------- #


def test_flight_round_trip(tmp_path):
    rec = StepEventRecorder(
        capacity=64,
        flight=FlightRecorder(str(tmp_path), service="worker-x",
                              segment_slots=64),
    )
    t0 = rec.now()
    rec.record("decode_block", t0_ns=t0, rung=8, batch=4, chain=1)
    rec.record("preempt_park", seq=7)
    dumps = load_flight_dir(str(tmp_path))
    assert len(dumps) == 1
    d = dumps[0]
    assert d["pid"] == os.getpid() and d["service"] == "worker-x"
    assert [e["kind"] for e in d["events"]] == ["decode_block",
                                                "preempt_park"]
    assert d["events"][0]["rung"] == 8 and d["events"][0]["dur_ns"] >= 0
    assert d["events"][1]["seq"] == 7
    # the spill carries the same time anchors as a ring dump
    ring = rec.dump()
    assert d["events"][0]["t_ns"] == ring["events"][0]["t_ns"]


def test_flight_rotation_and_keep(tmp_path):
    fr = FlightRecorder(str(tmp_path), service="s", segment_slots=16,
                        keep=2)
    rec = StepEventRecorder(capacity=16, flight=fr)
    for i in range(16 * 5 + 3):  # 6 segments written, 2 kept
        rec.record("e", i=i)
    segs = sorted(n for n in os.listdir(tmp_path) if n.endswith(".seg"))
    assert len(segs) == 2, segs
    dumps = load_flight_dir(str(tmp_path))
    assert len(dumps) == 1 and dumps[0]["segments"] == 2
    # the survivors are the NEWEST events, contiguous through the end
    idxs = [e["i"] for e in dumps[0]["events"]]
    assert idxs == list(range(16 * 4, 16 * 5 + 3)), idxs[:4]


def test_flight_torn_segment_is_clean_prefix(tmp_path):
    fr = FlightRecorder(str(tmp_path), service="s", segment_slots=32)
    rec = StepEventRecorder(capacity=32, flight=fr)
    for i in range(10):
        rec.record("e", i=i)
    (seg,) = [os.path.join(tmp_path, n) for n in os.listdir(tmp_path)]
    # tear the file mid-record-6 (a SIGKILL before the page hit disk):
    # the reader must stop at the 5-record clean prefix, never raise
    size = FLIGHT_HEADER_SIZE + 5 * FLIGHT_RECORD_SIZE + 17
    with open(seg, "r+b") as f:
        f.truncate(size)
    d = load_flight_segment(seg)
    assert [e["i"] for e in d["events"]] == [0, 1, 2, 3, 4]
    # ... and a zeroed commit byte mid-file also ends the prefix
    with open(seg, "r+b") as f:
        f.truncate(FLIGHT_HEADER_SIZE + 32 * FLIGHT_RECORD_SIZE)
        f.seek(FLIGHT_HEADER_SIZE + 3 * FLIGHT_RECORD_SIZE - 1)
        f.write(b"\x00")
    d = load_flight_segment(seg)
    assert [e["i"] for e in d["events"]] == [0, 1]


def test_flight_garbage_and_foreign_files_skipped(tmp_path):
    (tmp_path / "flight-999-00000000.seg").write_bytes(b"not a segment")
    (tmp_path / "notes.txt").write_text("hi")
    assert load_flight_dir(str(tmp_path)) == []
    with pytest.raises(ValueError):
        load_flight_segment(str(tmp_path / "flight-999-00000000.seg"))


def test_flight_oversized_attrs_truncate_not_fail(tmp_path):
    fr = FlightRecorder(str(tmp_path), service="s", segment_slots=16)
    rec = StepEventRecorder(capacity=16, flight=fr)
    rec.record("big", blob="x" * 500)
    rec.record("after", i=1)
    (d,) = load_flight_dir(str(tmp_path))
    assert d["events"][0]["kind"] == "big"
    assert d["events"][0].get("truncated") is True
    assert d["events"][1]["i"] == 1


def test_flight_holds_a_step_slice_whole(tmp_path):
    """The longest step slice the engine writes (an expert model's shared
    `prefill_chunk` with its phases, ordinal, hand-offs and rows) is read
    back from the flight recorder attribute for attribute."""
    fr = FlightRecorder(str(tmp_path), service="s", segment_slots=16)
    rec = StepEventRecorder(capacity=16, flight=fr)
    attrs = dict(
        batch=4, tokens=256, fused_blocks=0, ctx=7168, pages=512, bucket=64,
        attn="pallas", overlapped=1, head=1, seq=1234567, dry=0, hop_us=312,
        toks=[64, 64, 64, 64], ctxs=[7168, 7104, 6400, 2048],
        moe_assignments=24576, experts_hit=485, moe_max_load=502,
        moe_form="dispatched", moe_rows="kernel", moe_local=3072,
        hc_res_err_ppm=21000, fetch_hop_us=250, build_us=2576,
        dispatch_us=3351, overlap_us=32645, fetch_us=23514, deliver_us=2672)
    rec.record("prefill_chunk", t0_ns=StepEventRecorder.now() - 1000, **attrs)
    single = dict(attrs, batch=1, rid="08c4a2c2f8b441cab1881fb56b957f95")
    del single["toks"], single["ctxs"]
    rec.record("prefill_chunk", t0_ns=StepEventRecorder.now() - 1000,
               **single)
    (d,) = load_flight_dir(str(tmp_path))
    for got, want in zip(d["events"], (attrs, single)):
        assert "truncated" not in got
        assert {k: got[k] for k in want} == want


def test_flight_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("DYN_TPU_FLIGHT_DIR", raising=False)
    assert FlightRecorder.from_env() is None
    monkeypatch.setenv("DYN_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("DYN_TPU_FLIGHT_SEGMENT_SLOTS", "128")
    monkeypatch.setenv("DYN_TPU_FLIGHT_KEEP", "2")
    fr = FlightRecorder.from_env()
    assert fr is not None and fr.segment_slots == 128 and fr.keep == 2
    rec = StepEventRecorder.from_env()
    assert rec.flight is not None
    rec.record("e")
    assert load_flight_dir(str(tmp_path))


def test_record_with_the_flight_spill_armed_writes_no_file(tmp_path):
    """The hot path with the mmap spill armed (it flies in production,
    not only in postmortems): 19 calls an event (the ring's 4, `append`,
    the attribute encoder's 11, one `Struct.pack`, two `len`), none of
    them a write, a flush or a sync, and none opens a segment: the
    record lands by two slice stores into the mapping.  Every event is
    on the ring and in the segment."""
    rec = StepEventRecorder(
        capacity=4096,
        flight=FlightRecorder(str(tmp_path), service="bench",
                              segment_slots=4096),
    )
    rec.record("decode_block", rung=8, batch=4, chain=1)  # the kind's bytes
    n = 2_000
    with counted_calls() as c:
        for i in range(n):
            rec.record("decode_block", rung=8, batch=4, chain=1)
    assert rec.total == rec.flight.records_written == n + 1
    assert rec.flight.segments_written == 1 and rec.flight.ok
    assert c.total // n <= call_ceiling(19), dict(c.names)
    io = [name for name in c.names
          if any(w in name.lower() for w in
                 ("write", "flush", "sync", "open", "truncate", "mmap"))]
    assert io == []
    # and what a segment's end costs is paid once a segment, not an event
    for i in range(4096 - (n + 1)):
        rec.record("decode_block", rung=8, batch=4, chain=1)
    with counted_calls() as c:
        rec.record("decode_block", rung=8, batch=4, chain=1)
    assert c.names["FlightRecorder._open_segment"] == 1
    assert rec.flight.segments_written == 2


def test_slice_timing_accuracy():
    rec = StepEventRecorder(capacity=8)
    t0 = rec.now()
    time.sleep(0.01)
    rec.record("work", t0_ns=t0)
    (_, dur_ns, _, _) = rec.snapshot()[0]
    assert dur_ns >= 8_000_000  # ~10ms slice measured as such


async def test_status_events_json_since_ns_cursor():
    """`GET /events.json?since_ns=` threads the cursor to the recorder:
    pollers fetch only the delta since their last watermark; a bad
    cursor is a 400, and a cursor-unaware events_fn still serves."""
    import asyncio
    import json
    import urllib.error
    import urllib.request

    from dynamo_tpu.runtime.status import SystemStatusServer

    rec = StepEventRecorder(capacity=16)
    rec.record("a")
    status = await SystemStatusServer(
        events_fn=lambda since_ns=None: rec.dump(since_ns=since_ns),
        host="127.0.0.1",
    ).start()
    try:
        def fetch(query=""):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{status.port}/events.json{query}",
                timeout=10,
            ) as r:
                return json.loads(r.read())

        loop = asyncio.get_running_loop()
        full = await loop.run_in_executor(None, fetch)
        assert len(full["events"]) == 1 and full["watermark_ns"] > 0
        empty = await loop.run_in_executor(
            None, fetch, f"?since_ns={full['watermark_ns']}")
        assert empty["events"] == []
        rec.record("b")
        delta = await loop.run_in_executor(
            None, fetch, f"?since_ns={full['watermark_ns']}")
        assert [e["kind"] for e in delta["events"]] == ["b"]

        def fetch_bad():
            try:
                fetch("?since_ns=banana")
            except urllib.error.HTTPError as e:
                return e.code
            return 200

        assert await loop.run_in_executor(None, fetch_bad) == 400
    finally:
        await status.stop()


async def test_engine_records_step_events_and_status_dump():
    """A served generation leaves admit/plan/rung/decode/pool events
    on the engine ring, and the worker debug endpoint dumps them."""
    import urllib.request

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import init_params, tiny_config
    from dynamo_tpu.runtime.status import SystemStatusServer

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = JaxEngine(
        cfg, params,
        EngineConfig(page_size=8, num_pages=64, max_num_seqs=2,
                     max_prefill_tokens=64, max_model_len=128,
                     decode_steps=4, decode_block_ladder=[1, 4]),
        eos_token_ids=[], kv_dtype=jnp.float32,
    )
    try:
        out = []
        async for d in engine.generate({
            "token_ids": list(range(1, 20)),
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": 8, "ignore_eos": True},
        }):
            out.extend(d.get("token_ids", []))
        assert len(out) == 8
        kinds = {e[2] for e in engine.events.snapshot()}
        assert {"admit", "plan", "loop_yield", "first_token", "rung_select",
                "decode_block", "prefill_chunk", "pool_alloc"} <= kinds, kinds
        assert "dispatch" not in kinds  # its n_steps/blocks ride the slice
        decode = [e for e in engine.events.snapshot()
                  if e[2] == "decode_block"]
        assert decode and all("rung" in e[3] and "batch" in e[3]
                              and e[1] > 0 for e in decode)

        status = await SystemStatusServer(
            events_fn=lambda: {"engine": engine.events.dump()},
            host="127.0.0.1",
        ).start()
        try:
            import asyncio
            import json

            def fetch():
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{status.port}/events.json",
                    timeout=10,
                ) as r:
                    return json.loads(r.read())

            # sync client off-loop: the server runs on this test's loop
            body = await asyncio.get_running_loop().run_in_executor(
                None, fetch
            )
            assert body["engine"]["recorded_total"] == engine.events.total
            assert {e["kind"] for e in body["engine"]["events"]} >= {
                "decode_block"}
        finally:
            await status.stop()
    finally:
        await engine.shutdown()


# -- host events: the rare, long things, on the ring's clock ----------------- #


@pytest.fixture
def host_events(monkeypatch):
    """The module's host-event state, fresh for one test and put back."""
    from dynamo_tpu.runtime import events

    monkeypatch.setattr(events, "_host_ring", None)
    monkeypatch.setattr(events, "_host_buffer", [])
    events._gc_pending.clear()  # pauses of the tests before this one
    return events


def test_events_before_a_ring_are_adopted_with_their_own_times(host_events):
    ev = host_events
    t_a = time.monotonic_ns()
    ev.host_event("startup.imports", t_a - 5_000_000, t_a)
    ev.host_event("startup.backend", t_a, bytes=3)       # slice to now
    ev.host_event("ready")                                # an instant
    born = time.monotonic_ns()
    rec = StepEventRecorder(capacity=16)                  # the ring's birth
    assert rec.snapshot() == []
    assert ev.attach_host_events(rec) is True
    got = rec.snapshot()
    assert [e[2] for e in got] == ["startup.imports", "startup.backend",
                                   "ready"]
    assert got[0][:2] == (t_a - 5_000_000, 5_000_000)
    assert got[1][0] == t_a and got[1][3] == {"bytes": 3}
    assert got[2][1] == 0
    assert all(e[0] + e[1] <= born for e in got)   # older than the ring
    assert ev._host_buffer == []                   # adopted once
    assert rec.kind_totals["ready"] == 1
    # from now on straight to the ring
    ev.host_event("gc_pause", time.monotonic_ns() - 1000, gen=2, collected=0)
    assert rec.snapshot()[-1][2] == "gc_pause" and rec.total == 4


def test_since_ns_poll_neither_repeats_nor_loses_adopted_events(host_events):
    ev = host_events
    t = time.monotonic_ns()
    ev.host_event("startup.imports", t - 9_000_000, t - 6_000_000)
    ev.host_event("startup.weights", t - 6_000_000, t - 1_000_000)
    rec = StepEventRecorder(capacity=32)
    ev.attach_host_events(rec)
    rec.record("plan", t0_ns=rec.now())
    d1 = rec.dump()                        # a poller's first, without a cursor
    assert [e["kind"] for e in d1["events"]] == [
        "startup.imports", "startup.weights", "plan"]
    assert d1["watermark_ns"] >= t - 1_000_000
    assert rec.dump(since_ns=d1["watermark_ns"])["events"] == []
    # a long host slice that began before the watermark commits after it
    ev.host_event("startup.engine", t - 1_000_000)
    rec.record("plan", t0_ns=rec.now())
    d2 = rec.dump(since_ns=d1["watermark_ns"])
    assert [e["kind"] for e in d2["events"]] == ["startup.engine", "plan"]
    d3 = rec.dump(since_ns=d2["watermark_ns"])
    assert d3["events"] == [] and d3["watermark_ns"] == d2["watermark_ns"]
    # commit times are monotone in record order: the cursor's premise
    commits = [e["t_ns"] + e["dur_ns"] for e in rec.dump()["events"]]
    assert commits == sorted(commits)


def test_one_ring_a_process_takes_the_host_events(host_events):
    """`--dp-ranks`: the first engine's ring, while it lives."""
    import gc

    ev = host_events
    first, second = StepEventRecorder(capacity=8), StepEventRecorder(capacity=8)
    assert ev.attach_host_events(first) is True
    assert ev.attach_host_events(second) is False
    ev.host_event("lease_renew", time.monotonic_ns() - 10, late_us=1, rtt_us=2)
    assert first.total == 1 and second.total == 0
    del first
    gc.collect()
    assert ev.attach_host_events(second) is True


def test_step_events_0_turns_host_events_off(host_events, monkeypatch):
    ev = host_events
    monkeypatch.setenv("DYN_TPU_STEP_EVENTS", "0")
    ev.host_event("ready")
    assert ev._host_buffer == []
    rec = StepEventRecorder.from_env()
    ev.attach_host_events(rec)
    ev.host_event("ready")
    assert rec.snapshot() == [] and rec.total == 0


def test_host_buffer_is_bounded(host_events):
    ev = host_events
    for i in range(ev._HOST_BUFFER_MAX + 50):
        ev.host_event("gc_pause", 1, 2, gen=2, collected=i)
    assert len(ev._host_buffer) == ev._HOST_BUFFER_MAX
    assert ev._host_buffer[-1][3]["collected"] == ev._HOST_BUFFER_MAX - 1


def test_gc_collect_is_one_gen2_pause_and_a_young_collection_none(
        host_events):
    import gc

    ev = host_events
    rec = StepEventRecorder(capacity=64)
    ev.attach_host_events(rec)              # installs the callback too
    assert ev._on_gc in gc.callbacks
    was = gc.isenabled()
    gc.disable()  # no collection of the interpreter's own between the two
    try:
        ev._gc_pending.clear()
        n0 = rec.total
        t0 = time.monotonic_ns()
        gc.collect()
        t1 = time.monotonic_ns()
        (pause,) = rec.snapshot()[n0:]
        assert pause[2] == "gc_pause" and pause[3]["gen"] == 2
        assert "collected" in pause[3]
        assert t0 <= pause[0] and pause[0] + pause[1] <= t1 and pause[1] > 0
        n1 = rec.total
        gc.collect(0)  # generation 0: under a millisecond, not recorded
        young = [e for e in rec.snapshot()[n1:] if e[1] < 1_000_000]
        assert young == []
    finally:
        if was:
            gc.enable()


def test_collection_inside_the_rings_lock_waits_for_a_safe_point(
        host_events):
    """A collection starts between any two bytecodes, inside `record()`'s
    lock too: the callback takes no lock (it would wait for its own
    thread), and the pause reaches the ring with the next record or dump."""
    ev = host_events
    rec = StepEventRecorder(capacity=16)
    ev.attach_host_events(rec)
    done = []

    def collect_under_the_locks():
        with ev._host_lock, rec._lock:
            ev._on_gc("start", {})
            ev._on_gc("stop", {"generation": 2, "collected": 7})
        done.append(True)

    t = threading.Thread(target=collect_under_the_locks, daemon=True)
    t0 = time.monotonic_ns()
    t.start()
    t.join(timeout=10)
    assert done, "the collector's callback waited for a lock its thread held"
    assert rec.total == 0 and len(ev._gc_pending) == 1
    rec.record("plan", waiting=0)           # the next record moves it on,
    kinds = [e[2] for e in rec.snapshot()]  # before its own event
    assert kinds == ["gc_pause", "plan"]
    pause = rec.snapshot()[0]
    assert pause[3] == {"gen": 2, "collected": 7}
    assert t0 <= pause[0] <= pause[0] + pause[1] <= time.monotonic_ns()
    # a poll from a watermark taken BEFORE the pause still gets it
    w = rec.dump()["watermark_ns"]
    ev._on_gc("start", {})
    ev._on_gc("stop", {"generation": 2, "collected": 0})
    assert [e["kind"] for e in rec.dump(since_ns=w)["events"]] == ["gc_pause"]
    assert not ev._gc_pending


def test_record_budget_holds_on_a_ring_that_took_host_events(host_events):
    """`record()`'s four calls an event, on the ring the host events land
    on: a warm step pays one truth test of an empty `_gc_pending` for
    them, which is no call at all."""
    ev = host_events
    rec = StepEventRecorder(capacity=4096)
    ev.attach_host_events(rec)
    ev.host_event("program", rec.now() - 1000, fn="x")
    n = 2_000
    t = rec.now()
    with counted_calls() as c:
        for i in range(n):
            rec.record("decode_block", t0_ns=t, rung=8, batch=4,
                       build_us=3, dispatch_us=4)
    assert rec.total == n + 1
    assert c.total // n <= call_ceiling(4), dict(c.names)
    assert "monotonic_ns" in c.names and "_flush_gc" not in c.names


async def test_warm_step_records_what_it_recorded_and_a_cold_one_compiled(
        host_events):
    """The first request's steps bear their programs (`program` events on
    the engine's ring, `compiled` on the slices they fell into); an
    identical second request adds no program and its step slices carry
    exactly the attributes a step carried before host events existed."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import init_params, tiny_config

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = JaxEngine(
        cfg, params,
        EngineConfig(page_size=8, num_pages=64, max_num_seqs=2,
                     max_prefill_tokens=64, max_model_len=128,
                     enable_prefix_caching=False),
        eos_token_ids=[], kv_dtype=jnp.float32,
    )
    assert host_events._host_ring() is engine.events

    async def ask():
        async for _ in engine.generate({
            "token_ids": list(range(1, 20)),
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": 3, "ignore_eos": True},
        }):
            pass

    try:
        await ask()
        await ask()
    finally:
        await engine.shutdown()
    # a request's last step slice is recorded after its last token is
    # handed on, but before the pump admits the next request: split there
    events = engine.events.snapshot()
    first, second = [i for i, e in enumerate(events) if e[2] == "admit"]
    cold, warm = events[first:second], events[second:]
    programs = [e for e in cold if e[2] == "program"]
    assert {"prefill_step", "decode_step"} <= {e[3]["fn"] for e in programs}
    assert all(e[3]["role"] == "step" for e in programs if e[3]["fn"])
    steps = [e for e in cold if e[2] in ("prefill_chunk", "decode_block")]
    assert sum(e[3].get("compiled", 0) for e in steps) == len(programs)
    # each program lies inside the slice that says it compiled
    first = next(e for e in steps if "compiled" in e[3])
    inside = [p for p in programs
              if first[0] <= p[0] and p[0] + p[1] <= first[0] + first[1]]
    assert len(inside) == first[3]["compiled"]
    # warm: nothing born, and the slices are what they were
    assert [e for e in warm if e[2] == "program"] == []
    step_attrs = {"build_us", "dispatch_us", "fetch_us", "deliver_us",
                  "seq", "dry", "hop_us"}
    expected = {
        "prefill_chunk": step_attrs | {
            "batch", "tokens", "fused_blocks", "ctx", "pages", "bucket",
            "attn", "overlapped", "head", "rid", "n_steps", "overlap_us",
            "fetch_hop_us"},
        "decode_block": step_attrs | {
            "rung", "n_steps", "blocks", "batch", "chain", "ctx", "pages",
            "bucket", "attn", "rid"},
    }
    seen = {k: set() for k in expected}
    for e in warm:
        if e[2] in expected:
            seen[e[2]] |= set(e[3])
            assert set(e[3]) <= expected[e[2]], (e[2], set(e[3]))
    # `fetch_hop_us` is absent where the hand-off took under a microsecond
    seen["prefill_chunk"].add("fetch_hop_us")
    assert seen == expected


async def test_late_lease_renewal_is_a_host_event(host_events):
    import asyncio

    from dynamo_tpu.runtime import DistributedRuntime

    ev = host_events
    rec = StepEventRecorder(capacity=64)
    ev.attach_host_events(rec)
    rt = await DistributedRuntime.detached(lease_ttl=0.3)  # renews at 0.1 s
    try:
        await asyncio.sleep(0.25)             # two renewals on time
        assert rec.kind_totals.get("lease_renew", 0) == 0
        await asyncio.sleep(0.02)
        time.sleep(0.2)                       # something holds the loop
        await asyncio.sleep(0.05)
        late = [e for e in rec.snapshot() if e[2] == "lease_renew"]
        assert late, rec.snapshot()
        _, dur, _, attrs = late[0]
        assert attrs["late_us"] >= 50_000 or attrs["rtt_us"] >= 50_000
        assert dur >= (attrs["late_us"] + attrs["rtt_us"]) * 1000 * 0.99
    finally:
        await rt.shutdown()


def test_merged_timeline_draws_host_events_on_their_own_track(host_events):
    from dynamo_tpu.runtime import timeline

    ev = host_events
    t = time.monotonic_ns()
    ev.host_event("startup.weights", t - 8_000_000, t - 2_000_000, bytes=1)
    rec = StepEventRecorder(capacity=8)
    ev.attach_host_events(rec)
    rec.record("prefill_chunk", t0_ns=t - 1_000_000, batch=1)
    pids = {}
    out = timeline.ring_to_chrome(rec.dump(), "worker", pids)
    by = {e["name"]: e for e in out}
    assert by["startup.weights"]["tid"] == timeline._HOST_TID
    assert by["prefill_chunk"]["tid"] == timeline._RING_TID
    # older than the ring, and still placed by its own time
    assert by["startup.weights"]["ts"] < by["prefill_chunk"]["ts"]
    assert by["startup.weights"]["dur"] == pytest.approx(6000.0)


def test_host_event_kinds_are_the_kinds_the_package_records():
    """`HOST_EVENT_KINDS` (the timeline's track choice, the readers' test
    of a ring that has none) is every literal `host_event("kind", ...)`."""
    import ast

    from dynamo_tpu.runtime import events as ev

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(ev.__file__)))
    called = set()
    for dirpath, _, files in os.walk(pkg):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call) and node.args
                        and getattr(node.func, "id", None) == "host_event"
                        and isinstance(node.args[0], ast.Constant)):
                    called.add(node.args[0].value)
    assert called == set(ev.HOST_EVENT_KINDS)
