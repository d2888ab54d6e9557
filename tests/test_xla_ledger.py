"""The runtime JAX contracts (dynamo_tpu/analysis/xla_ledger.py): the
compile ledger attributes every jit cache miss, the steady-state
tripwire fires with readable attribution, the thread-role transfer
guard blocks implicit device→host syncs on step/drain threads, and the
engine holds ZERO steady-state compiles across the rung ladder and the
continuous-decode chain.

Tests that deliberately provoke trips or violations MUST
``xla_ledger.reset()`` before returning — the conftest session gate
requires both empty.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import xla_ledger
from dynamo_tpu.testing import dispatches

from test_block_ladder import PROMPTS, collect, make_engine, req, setup  # noqa: F401

pytestmark = pytest.mark.skipif(
    not xla_ledger.ledger_enabled(),
    reason="DYN_TPU_XLALEDGER=0: ledger disabled for this run",
)


# -- compile ledger ---------------------------------------------------------- #


def test_probe_records_on_miss_not_on_hit():
    def stepfn(x):
        return x * 2

    g = xla_ledger.ledgered_jit(stepfn, tags={"rung": 3})
    name = stepfn.__qualname__

    def count():
        return xla_ledger.compiles_by_fn().get(name, 0)

    n0 = count()
    g(jnp.ones((4,), jnp.float32))
    assert count() == n0 + 1          # miss: traced + recorded
    g(jnp.zeros((4,), jnp.float32))
    assert count() == n0 + 1          # same signature: cache hit, no record
    g(jnp.ones((8,), jnp.float32))
    assert count() == n0 + 2          # new shape: second compile

    mine = [e for e in xla_ledger.entries() if e.fn == name]
    assert [e.signature for e in mine[-2:]] == ["f32[4]", "f32[8]"]
    assert all(e.tags == {"rung": 3} for e in mine)
    assert xla_ledger.last_entry().fn == name


def test_signature_formats_pytrees_and_scalars():
    def stepfn(tree, n):
        return tree["a"] + n

    g = xla_ledger.ledgered_jit(stepfn)
    g({"a": jnp.ones((2, 4), jnp.int32)}, jnp.float32(1.0))
    e = [x for x in xla_ledger.entries() if x.fn == stepfn.__qualname__][-1]
    assert "i32[2,4]" in e.signature and "f32[]" in e.signature
    assert stepfn.__qualname__ in e.format()


def test_steady_scope_trip_has_readable_attribution():
    def coldfn(x):
        return x + 1

    g = xla_ledger.ledgered_jit(coldfn, tags={"rung": 8})
    try:
        with xla_ledger.steady_scope("after-warmup"):
            g(jnp.ones((3,), jnp.float32))
        trips = xla_ledger.trips()
        assert len(trips) == 1
        t = trips[0]
        assert t.in_steady and t.scope == "after-warmup"
        # the attribution a human debugs from: function + arg signature
        assert "coldfn" in t.format() and "f32[3]" in t.format()
        assert "rung" in t.format()
    finally:
        xla_ledger.reset()  # session gate requires trips empty


def test_warm_function_does_not_trip_in_steady_scope():
    def warmfn(x):
        return x - 1

    g = xla_ledger.ledgered_jit(warmfn)
    g(jnp.ones((5,), jnp.float32))  # warm outside the scope
    before = xla_ledger.trips()
    with xla_ledger.steady_scope():
        g(jnp.zeros((5,), jnp.float32))
    assert xla_ledger.trips() == before


def test_disabled_ledger_degrades_to_plain_jit(monkeypatch):
    monkeypatch.setattr(xla_ledger, "_LEDGER_ON", False)

    def offfn(x):
        return x * 3

    g = xla_ledger.ledgered_jit(offfn, tags={"rung": 1})
    out = g(jnp.full((2,), 2.0, jnp.float32))
    assert np.array_equal(np.asarray(out), [6.0, 6.0])
    assert offfn.__qualname__ not in xla_ledger.compiles_by_fn()


def test_summary_and_reset_roundtrip():
    def sumfn(x):
        return x

    xla_ledger.ledgered_jit(sumfn)(jnp.ones((1,)))
    xla_ledger.note_decode_block(3)
    s = xla_ledger.summary()
    assert s["compiles_total"] >= 1 and s["decode_blocks"] >= 3
    assert set(s) >= {"by_fn", "backend_compiles", "trips",
                      "transfer_violations"}
    xla_ledger.reset()
    s2 = xla_ledger.summary()
    assert s2["compiles_total"] == 0 and s2["decode_blocks"] == 0
    assert xla_ledger.entries() == [] and xla_ledger.last_entry() is None


# -- a program's birth: stages on the ring's clock, handed to the sinks ------- #


@pytest.fixture
def program_sink():
    got = []

    def sink(t0_ns, t1_ns, attrs):
        got.append((t0_ns, t1_ns, attrs))

    was = xla_ledger._program_sink
    xla_ledger.set_program_sink(sink)
    yield got
    xla_ledger.set_program_sink(was)


def _births(got, fn):
    return [g for g in got if g[2]["fn"] == fn]


def test_first_call_leaves_one_timed_entry_and_reaches_the_sink_once(
        program_sink):
    import time

    def bornfn(x):
        return jnp.where(x > 0, x * 2, x).sum()

    g = xla_ledger.ledgered_jit(bornfn, tags={"rung": 5}, name="born_prog")
    t_before = time.monotonic_ns()
    g(jnp.ones((6,), jnp.float32))
    t_after = time.monotonic_ns()
    (e,) = [x for x in xla_ledger.entries() if x.program == "born_prog"]
    assert t_before <= e.t_ns <= t_after          # the ring's clock
    assert e.trace_us > 0 and e.lower_us > 0 and e.compile_us >= 0
    assert e.thread == threading.current_thread().name
    ((t0, t1, attrs),) = _births(program_sink, "born_prog")  # exactly once
    assert t0 == e.t_ns and t_before <= t0 < t1 <= t_after
    assert attrs["tags"] == "rung=5" and len(attrs["sig"]) == 8
    assert {k: attrs[k] for k in e.stages()} == e.stages()
    # the stages are spans of one thread between the probe and the sink
    assert sum(attrs[k] for k in ("trace_us", "lower_us", "compile_us")) \
        * 1000 <= (t1 - t0) * 1.01 + 1_000_000
    # a slot of the flight recorder is 256 bytes: the event fits
    from dynamo_tpu.runtime.events import _REC_PAYLOAD_MAX, _encode_attrs

    assert len(_encode_attrs(attrs)) + len("program") <= _REC_PAYLOAD_MAX


def test_sig_tells_programs_apart_behind_a_long_common_head(program_sink):
    """A step program's first leaves are the model's parameters: the
    signature kept for reading is cut inside them, the hash is not."""
    def headfn(params, x):
        return sum(p.sum() for p in params) + x.sum()

    g = xla_ledger.ledgered_jit(headfn, name="head_prog")
    params = [jnp.ones((3, 3), jnp.float32)] * 40
    g(params, jnp.ones((4,), jnp.float32))
    g(params, jnp.ones((8,), jnp.float32))
    a, b = [x for x in xla_ledger.entries() if x.program == "head_prog"]
    assert a.signature == b.signature and "more" in a.signature
    assert a.sig != b.sig
    assert [g[2]["sig"] for g in _births(program_sink, "head_prog")] == [
        a.sig, b.sig]


def test_second_call_of_the_same_shapes_leaves_nothing(program_sink):
    def warmfn2(x):
        return x + 3

    g = xla_ledger.ledgered_jit(warmfn2, name="warm_prog")
    g(jnp.ones((5,), jnp.float32))
    n_entries, n_sink = len(xla_ledger.entries()), len(program_sink)
    events0 = xla_ledger.summary()["program_events"]
    g(jnp.zeros((5,), jnp.float32))
    assert len(xla_ledger.entries()) == n_entries
    assert len(program_sink) == n_sink
    assert xla_ledger.summary()["program_events"] == events0
    assert len(_births(program_sink, "warm_prog")) == 1


def test_disabled_ledger_leaves_no_entry_and_no_named_program(
        monkeypatch, program_sink):
    monkeypatch.setattr(xla_ledger, "_LEDGER_ON", False)

    def offfn2(x):
        return x * 5

    n = len(xla_ledger.entries())
    xla_ledger.ledgered_jit(offfn2, name="off_prog")(
        jnp.ones((3,), jnp.float32))
    assert len(xla_ledger.entries()) == n
    assert _births(program_sink, "off_prog") == []


def test_compile_outside_a_ledgered_function_is_an_unnamed_program(
        program_sink):
    s0 = xla_ledger.summary()
    jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((7, 3), jnp.float32))
    s1 = xla_ledger.summary()
    born = s1["program_events"] - s0["program_events"]
    small = s1["programs_sub_ms"] - s0["programs_sub_ms"]
    # every backend compile is an event or in the count of small ones
    assert born + small == s1["backend_compiles"] - s0["backend_compiles"]
    assert born + small >= 1
    assert len(_births(program_sink, "")) == born
    for _, _, attrs in _births(program_sink, ""):
        assert "sig" not in attrs and "compile_us" in attrs
        assert "trace_us" not in attrs  # its birth opens at its lowering


def test_birth_under_a_millisecond_is_counted_not_handed_on(program_sink):
    n0 = xla_ledger.summary()["programs_sub_ms"]
    b = xla_ledger._open_birth()
    b.lower_us = 300
    xla_ledger._born(b, 0.0005)            # 0.8 ms in all, unledgered
    assert xla_ledger.summary()["programs_sub_ms"] == n0 + 1
    assert program_sink == []
    b = xla_ledger._open_birth()
    b.lower_us, b.load_us, b.hit = 200, 300, 1
    xla_ledger._born(b, 0.0009)            # 1.1 ms: an event
    ((_, _, attrs),) = program_sink
    assert attrs == {"fn": "", "role": attrs["role"], "lower_us": 200,
                     "compile_us": 600, "load_us": 300, "hit": 1}


def test_function_traced_inside_another_reaches_no_sink(program_sink):
    def innerfn(x):
        return x * 2

    inner = xla_ledger.ledgered_jit(innerfn, name="inner_prog")

    def outerfn(x):
        return inner(x) + 1

    xla_ledger.ledgered_jit(outerfn, name="outer_prog")(
        jnp.ones((9,), jnp.float32))
    assert len(_births(program_sink, "outer_prog")) == 1
    assert _births(program_sink, "inner_prog") == []
    (e,) = [x for x in xla_ledger.entries() if x.program == "inner_prog"]
    assert e.t_ns > 0 and e.compile_us is None  # inlined: never compiled


def test_traced_but_never_compiled_entry_does_not_name_the_next_program(
        program_sink):
    def shapefn(x):
        return x.sum()

    jax.eval_shape(xla_ledger.ledgered_jit(shapefn, name="shape_prog"),
                   jnp.ones((4,), jnp.float32))
    jax.jit(lambda x: jnp.cos(x) * 11)(jnp.ones((4,), jnp.float32))
    assert _births(program_sink, "shape_prog") == []
    (e,) = [x for x in xla_ledger.entries() if x.program == "shape_prog"]
    assert e.trace_us is not None and e.compile_us is None


def test_births_between_counts_the_calling_threads_programs_once(
        program_sink):
    """A step slice asks for the programs its thread bore in its build and
    dispatch.  A prefill step in flight (N) is recorded after the NEXT
    step (N+1) was dispatched: N must not take N+1's programs."""
    ms = 1_000_000
    xla_ledger._tls.born = []
    t0 = 50 * ms
    for t in (9, 31):                       # born in N+1's dispatch (8-60)
        b = xla_ledger._open_birth()
        b.t_ns, b.lower_us = t0 + t * ms, 2000
        xla_ledger._born(b, 0.01)
    assert len(program_sink) == 2
    assert xla_ledger.births_between(t0, t0 + 5 * ms) == 0        # N
    assert xla_ledger.births_between(t0 + 6 * ms, t0 + 60 * ms) == 2  # N+1
    assert xla_ledger.births_between(t0 + 6 * ms, t0 + 60 * ms) == 0  # once
    assert xla_ledger._tls.born == []
    # another thread's programs are its own: never counted here, and no
    # thread that never asks holds anything another thread looks at
    seen = []

    def other():
        b = xla_ledger._open_birth()
        b.t_ns, b.lower_us = t0 + 70 * ms, 2000
        xla_ledger._born(b, 0.01)
        seen.append(xla_ledger.births_between(t0 + 61 * ms, t0 + 80 * ms))

    th = threading.Thread(target=other)
    th.start()
    th.join(timeout=10)
    assert seen == [1]
    assert xla_ledger.births_between(t0 + 61 * ms, t0 + 80 * ms) == 0
    # a program born before the span asked for is forgotten, not kept
    b = xla_ledger._open_birth()
    b.t_ns, b.lower_us = t0 + 81 * ms, 2000
    xla_ledger._born(b, 0.01)
    assert xla_ledger.births_between(t0 + 90 * ms, t0 + 95 * ms) == 0
    assert xla_ledger._tls.born == []
    # bounded on a thread that never asks
    for i in range(xla_ledger._BORN_MAX + 10):
        b = xla_ledger._open_birth()
        b.lower_us = 2000
        xla_ledger._born(b, 0.01)
    assert len(xla_ledger._tls.born) == xla_ledger._BORN_MAX
    xla_ledger._tls.born = []


def test_format_shows_the_stages():
    def fmtfn(x):
        return x - 1

    xla_ledger.ledgered_jit(fmtfn)(jnp.ones((2,), jnp.float32))
    text = [x for x in xla_ledger.entries()
            if x.fn == fmtfn.__qualname__][-1].format()
    assert " trace=" in text and " lower=" in text and " compile=" in text
    assert text.count("ms") >= 3


def test_summary_keeps_the_keys_the_benchmark_reads():
    s = xla_ledger.summary()
    assert {"cache_hits", "cache_misses", "backend_compiles",
            "backend_compile_seconds", "program_events",
            "programs_sub_ms"} <= set(s)
    assert not hasattr(xla_ledger, "backend_compiles_total")


# -- transfer guard ---------------------------------------------------------- #


def _on_named_thread(name, fn):
    """Run fn on a thread with the given name; re-raise its exception."""
    box = {}

    def body():
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to the caller
            box["error"] = e

    t = threading.Thread(target=body, name=name, daemon=True)
    t.start()
    t.join(30)
    assert not t.is_alive(), f"thread {name} wedged"
    if "error" in box:
        raise box["error"]
    return box["result"]


@pytest.fixture
def xfercheck(monkeypatch):
    monkeypatch.setattr(xla_ledger, "_XFERCHECK", True)
    if not xla_ledger.install_transfer_guard():
        pytest.skip("ArrayImpl not patchable on this jaxlib")
    yield
    xla_ledger.reset()  # drop any violations so the session gate stays green


def test_step_thread_implicit_sync_raises(xfercheck):
    x = jnp.ones(())
    with pytest.raises(xla_ledger.HostSyncError, match="step"):
        _on_named_thread("jax-engine-step_t", lambda: float(x))
    with pytest.raises(xla_ledger.HostSyncError):
        _on_named_thread("jax-engine-step_t", x.item)
    kinds = xla_ledger.transfer_violations_total()
    assert kinds.get("float", 0) >= 1 and kinds.get("item", 0) >= 1
    v = xla_ledger.transfer_violations()[0]
    assert v["role"] == "step" and v["thread"].startswith("jax-engine-step")


def test_drain_thread_is_also_guarded(xfercheck):
    x = jnp.ones(())
    with pytest.raises(xla_ledger.HostSyncError, match="drain"):
        _on_named_thread("kvbm-offload_t", lambda: int(x))


def test_unknown_thread_is_exempt(xfercheck):
    x = jnp.ones(())
    assert _on_named_thread("user-thread", lambda: float(x)) == 1.0


def test_allow_scope_sanctions_the_sync(xfercheck):
    x = jnp.full((), 7.0)

    def body():
        with xla_ledger.allow_host_sync("test says so"):
            return float(x)

    assert _on_named_thread("jax-engine-step_t", body) == 7.0


def test_device_get_is_the_sanctioned_sync(xfercheck):
    x = jnp.arange(4)
    got = _on_named_thread("jax-engine-step_t",
                           lambda: jax.device_get(x))
    assert np.array_equal(got, [0, 1, 2, 3])


def test_patches_inert_when_xfercheck_off(monkeypatch):
    # install_transfer_guard() is process-global and may outlive a test
    # that enabled it; with the flag off the role check must not fire
    # even on a step-named thread
    xla_ledger.install_transfer_guard()
    monkeypatch.setattr(xla_ledger, "_XFERCHECK", False)
    x = jnp.ones(())
    assert _on_named_thread("jax-engine-step_t", lambda: float(x)) == 1.0


def test_thread_role_init_records_guard_state(xfercheck):
    _on_named_thread("jax-engine-step_guardinit", xla_ledger.thread_role_init)
    _on_named_thread("unrelated-pool_t", xla_ledger.thread_role_init)
    state = xla_ledger.guard_state()
    assert "d2h=disallow" in state["jax-engine-step_guardinit"]
    assert "exempt" in state["unrelated-pool_t"]


# -- /metrics export --------------------------------------------------------- #


def test_xla_ledger_collector_families():
    from dynamo_tpu.runtime.metrics import XlaLedgerCollector

    def mfn(x):
        return x

    xla_ledger.ledgered_jit(mfn)(jnp.ones((2,)))
    xla_ledger.note_transfer_violation("float", "step")
    try:
        fams = {f.name: f for f in XlaLedgerCollector().collect()}
        compiles = fams["dynamo_tpu_worker_xla_compiles"]
        by_fn = {s.labels["fn"]: s.value for s in compiles.samples
                 if s.name.endswith("_total")}
        assert by_fn.get(mfn.__qualname__) == 1
        viol = fams["dynamo_tpu_worker_xla_transfer_guard_violations"]
        kinds = {s.labels["kind"]: s.value for s in viol.samples
                 if s.name.endswith("_total")}
        assert kinds.get("float") == 1
    finally:
        xla_ledger.reset()  # the provoked violation must not reach the gate


# -- engine steady-state regression ------------------------------------------ #
#
# Warmup must cover every (rung × page-table-width-bucket) pair: the
# rung ladder's state persists across requests, so the SAME request can
# reach a rung at a different position — a different width bucket — on
# its second run.  That is the bounded bucket_for design, not a leak
# (docs/jax_contracts.md), so steady-state starts after two identical
# warmup passes.


async def test_rung_sweep_zero_steady_state_compiles(setup):  # noqa: F811
    engine = make_engine(setup, decode_block_ladder=[1, 2, 4])
    try:
        r = req([1, 2, 3], max_tokens=12)
        want, _ = await collect(engine, r)
        await collect(engine, req([1, 2, 3], max_tokens=12))
        with xla_ledger.steady_scope("rung-sweep"):
            got, _ = await collect(engine, req([1, 2, 3], max_tokens=12))
        bad = xla_ledger.trips()
        assert bad == [], "\n".join(t.format() for t in bad)
        assert got == want  # steady run is also token-identical
    finally:
        await engine.shutdown()
        xla_ledger.reset()


async def test_continuous_chain_zero_steady_state_compiles(setup):  # noqa: F811
    engine = make_engine(setup, decode_continuous=True, decode_chain=2)
    try:
        r = req(PROMPTS[0], max_tokens=20)
        await collect(engine, r)
        await collect(engine, req(PROMPTS[0], max_tokens=20))
        with xla_ledger.steady_scope("cc-chain"):
            await collect(engine, req(PROMPTS[0], max_tokens=20))
        bad = xla_ledger.trips()
        assert bad == [], "\n".join(t.format() for t in bad)
        assert engine.metrics().decode_cc_chains_total > 0
    finally:
        await engine.shutdown()
        xla_ledger.reset()


async def test_splice_admission_zero_steady_state_compiles(setup):  # noqa: F811
    """ISSUE 15 acceptance: an admission SPLICED into the running chain
    (chunk rows feeding the prompt through decode blocks) rides the
    already-compiled chain program — zero steady-state compiles across
    repeated mid-chain admissions.  Warmup is two identical passes
    (rung × table-width buckets persist across requests, same rule as
    the rung sweep above)."""
    import asyncio

    engine = make_engine(setup, decode_continuous=True, decode_chain=2)

    async def one_pass():
        before = len(dispatches(engine))
        # long base budgets keep the chain live across the arrival's
        # whole chunked admission — the splice must happen mid-chain
        # even on a warm pass where a block is a few ms
        base = [asyncio.ensure_future(
            collect(engine, req(PROMPTS[i], max_tokens=120)))
            for i in (0, 3)] + [asyncio.ensure_future(
            collect(engine, req([4, 5, 6], max_tokens=120)))]
        while not any(e["kind"] == "decode"
                      for e in dispatches(engine)[before:]):
            await asyncio.sleep(0.005)
        await collect(engine, req(PROMPTS[1], max_tokens=4))
        await asyncio.gather(*base)

    try:
        await one_pass()
        await one_pass()
        with xla_ledger.steady_scope("cc-splice"):
            await one_pass()
        bad = xla_ledger.trips()
        assert bad == [], "\n".join(t.format() for t in bad)
        # the steady pass really spliced: chunk rows rode tagged blocks
        assert any(e[3].get("chunk_rows", 0) > 0
                   for e in engine.events.snapshot()
                   if e[2] == "decode_block"), "splice never engaged"
    finally:
        await engine.shutdown()
        xla_ledger.reset()


def test_decode_blocks_counted_by_engine_hook():
    n0 = xla_ledger.summary()["decode_blocks"]
    xla_ledger.note_decode_block(2)
    assert xla_ledger.summary()["decode_blocks"] == n0 + 2


# -- the step-path fix this PR landed (regression) ---------------------------- #


async def test_import_dev_fetches_both_planes_in_one_device_get(setup, monkeypatch):  # noqa: F811
    """PR 12's first-run triage found the multihost import staging two
    sequential ``jax.device_get`` round-trips (k, then v); the fix
    batches both planes into ONE fetch.  A revert doubles this count."""
    engine = make_engine(setup)
    calls = []
    real_get = jax.device_get

    def counting_get(x):
        calls.append(x)
        return real_get(x)

    try:
        monkeypatch.setattr(engine.layout, "lockstep", True)
        monkeypatch.setattr(engine, "_stage_blob",
                            lambda k, v: ("tid", ("127.0.0.1", 1)))
        monkeypatch.setattr(engine, "_lockstep_send", lambda msg: None)
        monkeypatch.setattr(engine, "_import_fetch_replay",
                            lambda *a, **kw: None)
        monkeypatch.setattr(jax, "device_get", counting_get)
        kpad = jnp.ones((2, 4, 8, 1, 2), jnp.float32)
        vpad = jnp.zeros_like(kpad)
        engine._import_dev([0, 1], kpad, vpad)
    finally:
        monkeypatch.setattr(jax, "device_get", real_get)
        await engine.shutdown()

    assert len(calls) == 1, f"expected one batched fetch, saw {len(calls)}"
    assert isinstance(calls[0], tuple) and len(calls[0]) == 2
