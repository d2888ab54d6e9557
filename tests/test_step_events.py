"""Engine step-event recorder: ring semantics, the <5µs/event hot-path
budget, the crash-surviving flight-recorder spill, and the
engine/status-server integration (docs/observability.md event schema)."""

import os
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


def test_dump_carries_time_anchors():
    rec = StepEventRecorder(capacity=8)
    rec.record("a")
    dump = rec.dump()
    # wall/mono anchors let offline tools rebase monotonic event times
    # onto the wall clock; they must describe the same instant
    assert abs((time.time_ns() - dump["wall_ns"])
               - (time.monotonic_ns() - dump["mono_ns"])) < 50_000_000
    ev = dump["events"][0]
    assert ev["kind"] == "a" and ev["dur_ns"] == 0 and "t_ns" in ev


def test_from_env_capacity(monkeypatch):
    monkeypatch.setenv("DYN_TPU_STEP_EVENTS", "16")
    assert StepEventRecorder.from_env().capacity == 16
    monkeypatch.setenv("DYN_TPU_STEP_EVENTS", "0")
    assert StepEventRecorder.from_env().enabled is False


def test_record_under_5us_per_event():
    """The acceptance micro-benchmark: ring recording with exporters
    disabled must cost < 5 µs/event (it sits on the decode hot path).
    The budget is a claim about the PRODUCTION build: under
    DYN_TPU_LOCKCHECK/DYN_TPU_CHECKS the ring's lock is a TrackedLock
    with order/hold-time bookkeeping, so the bound is relaxed to a
    sanity ceiling there."""
    from dynamo_tpu.analysis import contracts

    budget = 5e-6 if contracts.checks_mode() == "off" else 100e-6
    rec = StepEventRecorder(capacity=4096)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        rec.record("decode_block", rung=8, batch=4, chain=1)
    per_event = (time.perf_counter() - t0) / n
    assert rec.total == n
    assert per_event < budget, f"{per_event * 1e6:.2f}µs/event"


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


def test_record_under_5us_per_event_with_flight_spill(tmp_path):
    """The hot-path budget HOLDS with the mmap spill armed — the flight
    recorder is designed to fly in production, not only in postmortems.
    Same checks-mode relaxation as the bare-ring bench."""
    from dynamo_tpu.analysis import contracts

    budget = 5e-6 if contracts.checks_mode() == "off" else 100e-6
    rec = StepEventRecorder(
        capacity=4096,
        flight=FlightRecorder(str(tmp_path), service="bench",
                              segment_slots=4096),
    )
    # best of several short batches: the budget is the code's cost, and
    # one mean over a long run also times whatever else shares the core
    # (five other xdist workers, in the driver's run)
    batches, n = 10, 2_000
    per_event = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for i in range(n):
            rec.record("decode_block", rung=8, batch=4, chain=1)
        per_event = min(per_event, (time.perf_counter() - t0) / n)
    assert rec.total == batches * n
    assert rec.flight.records_written == batches * n
    assert per_event < budget, f"{per_event * 1e6:.2f}µs/event"


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
