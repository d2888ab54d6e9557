"""The engine step from the inside (ISSUE 25): the phases on every step
slice, the pump's own slices, one `first_token` a request that separates
waiting from working, the on-demand profiler capture, the int32 result
packing, the pool's cache counters and the program and scope names."""

import asyncio
import glob
import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine import engine as eng
from dynamo_tpu.engine import steps
from dynamo_tpu.engine.layout import Layout
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.runtime.events import (
    FlightRecorder,
    StepEventRecorder,
    _encode_attrs,
)
from dynamo_tpu.testing import call_ceiling, counted_calls

STEP_KINDS = ("prefill_chunk", "decode_block", "mixed_step", "spec_round")
PUMP_KINDS = ("plan", "loop_yield", "idle_wait")
PHASES = ("build_us", "dispatch_us", "fetch_us", "deliver_us")
# a prefill step's slice has `overlap_us` between dispatch and fetch besides


def tiny_engine(**over):
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ecfg = dict(page_size=8, num_pages=64, max_num_seqs=4,
                max_prefill_tokens=16, max_model_len=128, decode_steps=2)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32)


async def generate(engine, prompt, n, rid=None):
    from dynamo_tpu.runtime.engine import Context

    out = []
    async for d in engine.generate({
        "token_ids": prompt,
        "sampling_options": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": n, "ignore_eos": True},
    }, Context(rid) if rid else None):
        out.extend(d.get("token_ids", []))
    return out


async def served_ring(n_requests=3, **over):
    """Events of a tiny engine after `n_requests` concurrent requests whose
    prompts take several prefill chunks each."""
    engine = tiny_engine(**over)
    try:
        outs = await asyncio.gather(*(
            generate(engine, list(range(1 + i, 41 + i)), 4, rid=f"req-{i}")
            for i in range(n_requests)))
        assert all(len(o) == 4 for o in outs)
        metrics = engine.metrics()
    finally:
        await engine.shutdown()
    # read after the pump has gone: a step posts its last delta before it
    # writes its record, so a stream may close ahead of its last step's slice
    return engine.events.dump()["events"], metrics


async def test_step_slices_carry_phases_that_sum_to_the_slice():
    events, _ = await served_ring()
    steps = [e for e in events if e["kind"] in STEP_KINDS]
    chunks = [e for e in steps if e["kind"] == "prefill_chunk"]
    # 40 tokens in 16-token chunks, three requests: chunks alone, then
    # riding mixed steps once a request decodes
    assert len(chunks) >= 3 and len(steps) >= 9
    for e in steps:
        # a prefill step may stay in flight while the next one is planned,
        # built and dispatched: `overlap_us`, between its dispatch and its
        # own fetch, is among its parts; the other kinds have no such part
        assert ("overlap_us" in e) == (e["kind"] == "prefill_chunk"), e
        phases = PHASES + ("overlap_us",) * (e["kind"] == "prefill_chunk")
        assert all(type(e[p]) is int and e[p] >= 0 for p in phases), e
        parts = sum(e[p] for p in phases)
        # contiguous parts up to the record call: a little under the slice
        assert e["dur_ns"] // 1000 - 100 <= parts <= e["dur_ns"] // 1000, e
        assert e["pages"] >= 1 and e["bucket"] >= 1 and e["ctx"] >= 1
    for e in chunks:
        assert e["overlapped"] in (0, 1)
        assert e["attn"] in ("pallas", "xla")
        assert e["ctx"] >= e["tokens"] and e["bucket"] >= e["tokens"]
        if e["batch"] == 1:
            assert e["rid"].startswith("req-")
    # three requests arrive together: some chunk was dispatched behind an
    # unfetched one, and spent time in flight that the host gave to others
    assert any(e["overlapped"] and e["overlap_us"] > 0 for e in chunks)
    total = sum(e["dur_ns"] for e in steps) / 1000
    assert abs(sum(e[p] for e in steps for p in PHASES)
               + sum(e["overlap_us"] for e in chunks) - total) <= (
        0.02 * total + 100 * len(steps))
    assert not [e for e in events if e["kind"] == "dispatch"]
    decode = [e for e in steps if e["kind"] == "decode_block"]
    assert decode and all(e["n_steps"] == e["rung"] and e["blocks"] >= 1
                          for e in decode)


async def test_pump_slices_tile_the_time_between_steps():
    events, _ = await served_ring()
    steps = [e for e in events if e["kind"] in STEP_KINDS]
    pump = [e for e in events if e["kind"] in PUMP_KINDS]
    assert {"plan", "loop_yield"} <= {e["kind"] for e in pump}
    inside = 0
    for e in pump:
        # a pump slice lies outside every step slice, or inside a prefill
        # step that was in flight meanwhile: then wholly inside the part of
        # the slice between its dispatch and its own fetch (`overlap_us`)
        a, b = e["t_ns"], e["t_ns"] + e["dur_ns"]
        for s in steps:
            if not (s["t_ns"] < b and a < s["t_ns"] + s["dur_ns"]):
                continue
            assert s["kind"] == "prefill_chunk", (e, s)
            sent = s["t_ns"] + 1000 * (s["build_us"] + s["dispatch_us"])
            assert sent <= a and b <= sent + 1000 * (s["overlap_us"] + 1), (
                e, s)
            inside += 1
    assert inside  # the plan behind a step in flight
    ordered = sorted(pump, key=lambda e: e["t_ns"])
    for x, y in zip(ordered, ordered[1:]):  # nor another pump slice
        assert x["t_ns"] + x["dur_ns"] <= y["t_ns"]
    plans = [e for e in pump if e["kind"] == "plan"]
    assert sum(e["admitted"] for e in plans) == 3
    assert all(e["waiting"] >= 0 and e["running"] >= 0 for e in plans)
    # one `loop_yield` for each return of the step thread, a plan after each:
    # a step, or the fetch of a step in flight on its own
    yields = [e for e in pump if e["kind"] == "loop_yield"]
    assert len(yields) <= len(plans) and len(yields) <= 2 * len(steps)


async def test_first_token_separates_waiting_from_working():
    events, metrics = await served_ring()
    firsts = {e["rid"]: e for e in events if e["kind"] == "first_token"}
    assert sorted(firsts) == ["req-0", "req-1", "req-2"]
    for e in firsts.values():
        assert e["queue_us"] + e["wait_us"] + e["own_us"] == e["total_us"]
        assert min(e["queue_us"], e["wait_us"], e["own_us"]) >= 0
        assert e["prompt_len"] == 40 and e["steps"] >= 3  # 16+16+8 tokens
        chunks = [c for c in events if c["kind"] == "prefill_chunk"
                  and c.get("rid") == e["rid"]]
        if len(chunks) == e["steps"]:  # every step was this request's alone
            whole = sum(c["dur_ns"] for c in chunks) // 1000
            assert e["own_us"] <= whole + 1
    # one prefill sequence a step by default: someone waited for a turn
    assert max(e["wait_us"] for e in firsts.values()) > 0
    assert metrics.ttft_attributed_total == 3
    assert metrics.ttft_turn_wait_ms_total == pytest.approx(
        sum(e["wait_us"] for e in firsts.values()) / 1e3)
    assert metrics.ttft_turn_wait_ms_total <= metrics.ttft_prefill_ms_total


async def test_turn_wait_span_sits_between_queue_wait_and_prefill(
        monkeypatch):
    from dynamo_tpu.runtime import tracing

    seen = []
    monkeypatch.setattr(
        tracing, "export_span",
        lambda name, trace, t0, t1, **attrs: seen.append((name, attrs)))
    monkeypatch.setattr(tracing, "current_trace", lambda: object())
    engine = tiny_engine()
    try:
        await generate(engine, list(range(1, 30)), 2)
    finally:
        await engine.shutdown()
    names = [n for n, _ in seen]
    i = names.index("engine.turn_wait")
    assert names[i - 1] == "engine.queue_wait"
    assert names[i + 1] == "engine.prefill"
    assert seen[i][1]["steps"] >= 2 and seen[i][1]["turn_wait_ms"] >= 0


async def test_debug_xprof_writes_a_trace_and_refuses_a_second_arming(
        tmp_path):
    from dynamo_tpu.runtime.status import SystemStatusServer

    engine = tiny_engine()
    where = str(tmp_path / "xprof")

    def arm(steps, directory=None):
        return where if engine.arm_xprof(steps, directory or where) else None

    status = await SystemStatusServer(xprof_fn=arm, host="127.0.0.1").start()

    def post(query):
        req = urllib.request.Request(
            f"http://127.0.0.1:{status.port}/debug/xprof{query}",
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    off_loop = asyncio.get_running_loop().run_in_executor
    try:
        assert (await off_loop(None, post, ""))[0] == 400
        code, body = await off_loop(None, post, "?steps=3")
        assert code == 200 and body == {"steps": 3, "dir": where}
        assert (await off_loop(None, post, "?steps=3"))[0] == 409
        await generate(engine, list(range(1, 40)), 6)  # > 3 steps
        files = glob.glob(os.path.join(where, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        assert len(files) == 1 and os.path.getsize(files[0]) > 0
        marks = {e["kind"]: e for e in engine.events.dump()["events"]
                 if e["kind"].startswith("xprof_")}
        start, stop = marks["xprof_start"], marks["xprof_stop"]
        assert stop["steps"] == 3 and start["steps"] == 3
        # the anchors place the file on the ring's clock: wall - mono is
        # the same offset at both ends, and the stop lies after the start
        off = start["wall_ns"] - start["mono_ns"]
        assert abs((stop["wall_ns"] - stop["mono_ns"]) - off) < 50_000_000
        assert stop["mono_ns"] > start["mono_ns"]
        # the host line carries each step's annotation and no Python tracer
        pd = jax.profiler.ProfileData.from_file(files[0])
        names = [e.name for p in pd.planes for ln in p.lines
                 for e in ln.events]
        assert 1 <= names.count("prefill_chunk") + names.count(
            "decode_block") <= 3
        assert not [n for n in names if n.startswith("$")]  # Python frames
        # done: it can be armed again
        code, _ = await off_loop(None, post, f"?steps=1&dir={where}")
        assert code == 200
    finally:
        await status.stop()
        await engine.shutdown()


async def test_debug_xprof_without_an_engine_is_404():
    from dynamo_tpu.runtime.status import SystemStatusServer

    status = await SystemStatusServer(host="127.0.0.1").start()
    try:
        def post():
            req = urllib.request.Request(
                f"http://127.0.0.1:{status.port}/debug/xprof?steps=1",
                method="POST")
            try:
                urllib.request.urlopen(req, timeout=10)
            except urllib.error.HTTPError as e:
                return e.code
        assert await asyncio.get_running_loop().run_in_executor(
            None, post) == 404
    finally:
        await status.stop()


def test_step_slice_record_stays_on_the_fast_path(tmp_path):
    """A step slice's attributes (integers and two plain identifiers) stay
    on the fast path of `_encode_attrs`: with the flight spill armed, no
    event reaches the JSON encoder, and the calls an event are the ring's
    4, the spill's 12, a list append for each of the 14 attributes and 4
    more for each of the 2 identifiers (is it ASCII, is it alphanumeric
    without its dashes and underscores).  The ceiling is today's count."""
    attrs = dict(build_us=1234, dispatch_us=2345, overlap_us=9876,
                 fetch_us=61234, deliver_us=345, batch=1, tokens=512,
                 fused_blocks=0, ctx=2048, pages=128, bucket=512,
                 attn="pallas", overlapped=1,
                 rid="0a1b2c3d-e5f6-7890-abcd-ef0123456789")
    encoded = _encode_attrs(attrs)
    assert json.loads(encoded) == attrs
    assert b" " not in encoded  # the fast path's compact form
    assert json.loads(_encode_attrs({"why": 'a "quoted" reason'})) == {
        "why": 'a "quoted" reason'}
    rec = StepEventRecorder(
        capacity=4096,
        flight=FlightRecorder(str(tmp_path), segment_slots=4096))
    rec.record("prefill_chunk", t0_ns=1, **attrs)
    n = 500
    with counted_calls() as c:
        for _ in range(n):
            rec.record("prefill_chunk", t0_ns=1, **attrs)
    assert rec.flight.records_written == n + 1
    assert not any("JSONEncoder" in name for name in c.names), dict(c.names)
    assert c.total // n <= call_ceiling(4 + 12 + 14 + 2 * 4), dict(c.names)


IDS = np.array([0, 1, 7, 260, 151_000, (1 << 23) - 1], np.int32)
LOGP = np.array([-9.5, -1e-30, -0.0, -3.25, -1e-3, -17.125], np.float32)


@pytest.mark.parametrize("pair", ["out", "out_top", "cc", "cc_top", "spec"])
def test_packed_results_are_int32_and_round_trip_bit_exact(pair):
    """Finding 1: ids rode a float32 array as denormals and the TPU flushed
    them to zero.  Every pack is int32 now; ids below 2^23 and logprobs
    come back bit for bit."""
    b = len(IDS)
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(b, 64)), jnp.float32)
    top = "top" in pair
    if pair == "spec":
        toks = np.stack([IDS, IDS[::-1]], axis=1)  # [B, S=2]
        lps = np.stack([LOGP, LOGP[::-1]], axis=1)
        n_acc = np.arange(b, dtype=np.int32) % 2
        packed = jax.jit(lambda t, l, a: steps._pack(
            t.reshape(-1), l.reshape(-1), a))(toks, lps, n_acc)
        assert packed.dtype == jnp.int32
        t, l, a = steps._unpack_spec(np.asarray(packed), b, 2)
        assert (t == toks).all() and (a == n_acc).all()
        assert l.tobytes() == lps.tobytes()
        return
    if pair.startswith("cc"):
        act = np.array([1, 0, 1, 1, 0, 1], bool)
        packed = jax.jit(lambda o, l, a, x: steps._pack_out_cc(
            o, l, a, x if top else None))(IDS, LOGP, act, logits)
        toks, logp, flags, tids, tlps = steps._unpack_out_cc(
            np.asarray(packed), b, top)
        assert (flags == act).all()
    else:
        packed = jax.jit(lambda o, l, x: steps._pack_out(
            o, l, x if top else None))(IDS, LOGP, logits)
        toks, logp, tids, tlps = steps._unpack_out(np.asarray(packed), b, top)
    assert packed.dtype == jnp.int32
    assert toks.dtype == np.int32 and (toks == IDS).all()
    assert logp.dtype == np.float32 and logp.tobytes() == LOGP.tobytes()
    if top:
        ids, lps = steps.top_logprobs(logits, steps.TOPLP)
        assert (tids == np.asarray(ids)).all()
        assert tlps.tobytes() == np.asarray(lps).tobytes()
    else:
        assert tids is None and tlps is None


async def test_pool_occupancy_counts_cached_pages():
    engine = tiny_engine(num_pages=16, max_num_seqs=1)

    async def settled():
        """Metrics once the finished request's pages are back (the step
        thread frees them after the last delta is delivered)."""
        for _ in range(200):
            if engine.metrics().kv_usage == 0.0:
                break
            await asyncio.sleep(0.01)
        return engine.metrics()

    try:
        m0 = engine.metrics()
        assert (m0.kv_pages_cached, m0.prefix_evictions_total) == (0, 0)
        assert m0.kv_pages_free == 15  # page 0 is the trash page
        await generate(engine, list(range(1, 41)), 2)
        m1 = await settled()
        assert m1.kv_pages_cached == 5  # 40 tokens in full 8-token pages
        assert m1.kv_pages_free + m1.kv_pages_cached == 15
        assert m1.kv_usage == 0.0  # cached pages count as free there
        for i in range(3):  # other prompts push the cached pages out
            await generate(engine, list(range(100 + 50 * i, 140 + 50 * i)), 2)
        m2 = await settled()
        assert m2.prefix_evictions_total > 0
        assert m2.kv_pages_free + m2.kv_pages_cached == 15
    finally:
        await engine.shutdown()


def test_programs_and_scopes_are_found_by_name():
    """The step programs are named by kind and the model's parts sit under
    `jax.named_scope`s: a profiler trace's ops are found by these names."""
    from dynamo_tpu.models.llama import KVCache

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    kv = KVCache.create(cfg, num_pages=8, page_size=8, dtype=jnp.float32)
    samp = eng.SamplingParams.make([0.0], [0], [1.0], [0.0], [0.0])
    one = np.zeros((1,), np.int32)
    layout, _ = Layout.resolve(
        cfg, EngineConfig(decode_steps=2, max_model_len=128))
    step = layout.prefill_step(False, greedy=True)
    lowered = step.lower(
        params, kv, np.zeros((1, 16), np.int32), np.ones((1, 2), np.int32),
        one, one + 16, samp, one.astype(np.uint32), one)
    text = lowered.as_text(debug_info=True)
    assert "jit_prefill_step" in text or "@prefill_step" in text
    for scope in ("embed", "attn.qkv", "kv.write", "kv.gather", "attn.core",
                  "attn.out", "mlp", "head", "sample", "pack"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    assert steps.decode_name(1) == "decode_step"
    assert steps.decode_name(8) == "decode_block"
    mixed = layout.mixed_step(False, False, greedy=True, n_steps=2)
    assert mixed.__name__ == "mixed_step"
