"""The program store (`dynamo_tpu/compile_cache.py` `ProgramStore`, asked by
`analysis/xla_ledger.py` `ledgered_jit`'s probe): a step program's lowered
module is written once and read by every later start, keyed without tracing
the body.  A second start over one directory traces nothing and serves what
the first served, bit for bit; whatever the module depends on misses when it
changes; a damaged file is a miss and is rewritten; writers of one key race
to one whole file; a body that cannot be carried runs as before and says
why; the prefill kernel's module survives the round trip for the TPU.
"""

import dataclasses
import importlib.util
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from dynamo_tpu.analysis import xla_ledger
from dynamo_tpu.compile_cache import ProgramStore
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.layout import Layout
from dynamo_tpu.models import KVCache, init_params, tiny_config
from dynamo_tpu.ops.sampling import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    not xla_ledger.ledger_enabled(),
    reason="DYN_TPU_XLALEDGER=0: no probe, so no store")

ENGINE = dict(page_size=8, num_pages=64, max_num_seqs=2,
              max_prefill_tokens=16, max_model_len=128, decode_steps=2)


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store of this test's own, installed as `compile_cache.configure`
    installs the process's, over a ledger that starts empty."""
    xla_ledger.reset()
    s = ProgramStore(str(tmp_path / "programs"))
    monkeypatch.setattr(xla_ledger, "_program_store", s)
    yield s
    xla_ledger.reset()


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def files(store):
    return sorted(os.listdir(store.root)) if os.path.isdir(store.root) else []


# -- an engine, started twice -------------------------------------------------- #

async def start_and_serve(model, monkeypatch):
    """One start: a fresh ledger, ring and engine (new jitted functions, so
    every program is born again); a short prompt and one of three chunks,
    greedy, with logprobs.  What it served and what it left behind."""
    from dynamo_tpu.runtime import events

    xla_ledger.reset()
    monkeypatch.setattr(events, "_host_ring", None)
    monkeypatch.setattr(events, "_host_buffer", [])
    cfg, params = model
    engine = JaxEngine(cfg, params, EngineConfig(**ENGINE), eos_token_ids=[],
                       kv_dtype=jnp.float32)
    served = []
    try:
        for prompt in ([1, 2, 3], [(7 * j) % 101 + 1 for j in range(40)]):
            toks, logps = [], []
            async for d in engine.generate({
                    "token_ids": prompt,
                    "sampling_options": {"temperature": 0.0,
                                         "logprobs": True},
                    "stop_conditions": {"max_tokens": 4,
                                        "ignore_eos": True}}):
                toks += d["token_ids"]
                logps += d.get("log_probs") or []
            served.append((toks, logps))
    finally:
        await engine.shutdown()
    ring = engine.events.snapshot()
    summary = xla_ledger.summary()
    return {
        "served": served,
        "programs": [e[3] for e in ring if e[2] == "program" and e[3]["fn"]],
        "attn": [(e[2], e[3]["attn"]) for e in ring if "attn" in e[3]],
        "choices": sorted((c["site"], c["dims"], c["choice"], c["reason"])
                          for c in summary["path_choices"]),
        "summary": summary,
    }


async def test_a_second_start_traces_nothing_and_serves_the_same(
        store, model, monkeypatch):
    first = await start_and_serve(model, monkeypatch)
    second = await start_and_serve(model, monkeypatch)
    n = len(first["programs"])
    assert n >= 4 and len(second["programs"]) == n
    assert [p["stored"] for p in first["programs"]] == [0] * n
    assert [p["stored"] for p in second["programs"]] == [1] * n
    assert first["summary"]["programs_store_writes"] == n == len(files(store))
    assert (second["summary"]["programs_stored"],
            second["summary"]["programs_store_writes"]) == (n, 0)

    def cost(run, *stages):
        return sum(p[k] for p in run["programs"] for k in stages)

    # the body's trace is gone.  What is left is the call's own lowering,
    # which on this 2-layer model on the CPU is a floor of 20-50 ms a
    # program and most of what a cold birth costs here: the tenth that holds
    # for trace + lowering at a cell's size (PERF.md) is a quarter here
    assert cost(second, "trace_us") * 10 < cost(first, "trace_us")
    assert (cost(second, "trace_us", "lower_us") * 4
            < cost(first, "trace_us", "lower_us"))
    # tokens and logprobs bit for bit
    assert second["served"] == first["served"]
    assert all(len(t) == 4 and len(lp) == 4 for t, lp in first["served"])
    # the trace-time notes were replayed: every step shape has its answer,
    # so the slices' `attn` is what it was
    assert second["choices"] == first["choices"]
    assert any(site == "prefill_attention" for site, *_ in first["choices"])
    assert second["attn"] == first["attn"] and first["attn"]


def prefill_operands(cfg, params, chunk=16, pages=4):
    kv = KVCache.create(cfg, 16, 8, jnp.float32)
    one = jnp.ones((1,), jnp.float32)
    zero = jnp.zeros((1,), jnp.int32)
    return (params, kv, jnp.ones((1, chunk), jnp.int32),
            jnp.arange(1, 1 + pages, dtype=jnp.int32)[None], zero,
            jnp.full((1,), chunk, jnp.int32),
            SamplingParams(one, zero, one, one, one),
            jnp.zeros((1,), jnp.uint32), zero, jnp.ones((1,), bool))


def test_a_stored_step_still_donates_its_pool_and_is_born_once(store, model):
    """The outer `jit` keeps its `donate_argnums`: after a step through a
    module that came from the store the pool handed in is gone, and the
    step's results are those of the start that derived it.  Its results are
    as uncommitted as a traced step's (jax commits what a function that
    holds `call_exported` returns), so the step fed its own pool back is the
    program it was: one lowering, one executable."""
    cfg, params = model
    results = []
    for stored in (0, 1):
        layout = Layout.resolve(cfg, EngineConfig(**ENGINE))[0]
        step = layout.prefill_step(False, greedy=True)
        ops = prefill_operands(cfg, params)
        packed, _, kv = step(*ops)
        assert xla_ledger.last_entry().stored == stored
        assert ops[1].k.is_deleted() and ops[1].v.is_deleted()
        assert not kv.k.committed and not packed.committed
        results.append((np.array(packed), np.array(kv.k)))  # copies
        again = step(ops[0], kv, *prefill_operands(cfg, params)[2:])
        assert kv.k.is_deleted() and step._cache_size() == 1  # noqa: SLF001
        np.testing.assert_array_equal(again[0], packed)
    np.testing.assert_array_equal(results[0][0], results[1][0])
    np.testing.assert_array_equal(results[0][1], results[1][1])
    assert [e.program for e in xla_ledger.entries()] == ["prefill_step"] * 2


# -- what the key holds ------------------------------------------------------------ #

def born(model_cfg, engine_cfg, donate=(0,)):
    """A toy body through `Layout.wrap` of a fresh layout, traced once: the
    birth's `stored`."""
    layout = Layout.resolve(model_cfg, engine_cfg)[0]
    program = layout.wrap(lambda a, b: (a + 1, b * 2), "toy",
                          variant=("toy",), donate=donate)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    program.trace(x, x)
    return xla_ledger.last_entry().stored


def another_model(store, cfg, ecfg):
    return born(dataclasses.replace(cfg, rms_norm_eps=1e-6), ecfg)


def another_engine(store, cfg, ecfg):
    return born(cfg, dataclasses.replace(ecfg, max_model_len=64))


def another_donation(store, cfg, ecfg):
    return born(cfg, ecfg, donate=(1,))


def other_sources(store, cfg, ecfg):
    store.environment["sources"] = "0" * 32
    return born(cfg, ecfg)


def another_jax(store, cfg, ecfg):
    store.environment["jax"] = jax.__version__ + ".post1"
    return born(cfg, ecfg)


@pytest.mark.parametrize("change", [
    another_model, another_engine, another_donation, other_sources,
    another_jax], ids=lambda f: f.__name__)
def test_what_a_module_depends_on_misses_when_it_changes(store, change):
    cfg, ecfg = tiny_config(), EngineConfig(**ENGINE)
    assert born(cfg, ecfg) == 0        # derived and written
    assert born(cfg, ecfg) == 1        # known again, untraced
    assert len(files(store)) == 1
    assert change(store, cfg, ecfg) == 0      # another program
    assert len(files(store)) == 2
    assert xla_ledger.summary()["programs_store_writes"] == 2


def test_the_environment_names_versions_device_and_sources(store):
    env = store.environment
    assert env["jax"] == jax.__version__ and env["platform"] == "cpu"
    assert {"jaxlib", "platform_version", "device_kind"} <= set(env)
    from dynamo_tpu.compile_cache import source_digest

    assert env["sources"] == source_digest() and len(env["sources"]) == 32


# -- files ---------------------------------------------------------------------- #

def cut_in_half(path):
    with open(path, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(raw[:len(raw) // 2])


def not_a_module(path):
    """A whole file (its digest holds) whose module is of no serialisation
    this jax reads."""
    import hashlib

    body = (2).to_bytes(4, "big") + b"[]" + b"not a serialised program"
    with open(path, "wb") as f:
        f.write(hashlib.blake2b(body, digest_size=32).digest() + body)


def emptied(path):
    os.truncate(path, 0)


@pytest.mark.parametrize("damage", [cut_in_half, not_a_module, emptied],
                         ids=["truncated", "unreadable", "empty"])
def test_a_damaged_file_is_a_miss_and_is_rewritten(store, damage):
    cfg, ecfg = tiny_config(), EngineConfig(**ENGINE)
    assert born(cfg, ecfg) == 0
    (name,) = files(store)
    path = os.path.join(store.root, name)
    size = os.path.getsize(path)
    damage(path)
    assert store.load(name[:-len(".jaxprog")]) is None
    assert born(cfg, ecfg) == 0        # derived again
    assert files(store) == [name] and os.path.getsize(path) == size
    assert born(cfg, ecfg) == 1


def test_writers_of_one_key_leave_one_whole_file(store):
    """More writers than cores on one key (four ranks of a host start
    together), each with notes of its own, switching often: whatever is
    read meanwhile and afterwards is one writer's whole file."""
    exported = export.export(jax.jit(lambda x: x * 2))(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    writers, rounds = 4 * (os.cpu_count() or 2), 8
    seen, failures = [], []
    go = threading.Barrier(writers + 1)

    def write(i):
        go.wait(timeout=60)
        for r in range(rounds):
            if not store.save("k", exported, [["w", str(i), str(r), ""]]):
                failures.append(i)

    def read():
        go.wait(timeout=60)
        while any(t.is_alive() for t in threads):
            got = store.load("k")
            if got is not None:
                seen.append(got[1])

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(i,))
                   for i in range(writers)]
        reader = threading.Thread(target=read)
        for t in (*threads, reader):
            t.start()
        for t in (*threads, reader):
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert failures == []
    assert files(store) == ["k.jaxprog"]      # no temporary name left
    module, notes = store.load("k")
    assert len(notes) == 1 and notes[0][0] == "w"
    assert all(len(n) == 1 and n[0][0] == "w" for n in seen)
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(module.call(x), x * 2)


# -- a program that cannot be carried ----------------------------------------------- #

def test_a_body_whose_export_raises_runs_as_before_and_says_why_once(
        store, caplog):
    from typing import NamedTuple

    class Unknown(NamedTuple):  # a result tree `jax.export` cannot write
        doubled: jax.Array

    program = xla_ledger.ledgered_jit(
        lambda x: Unknown(x * 2), name="uncarried", closes_over=("toy",))
    with caplog.at_level("INFO", logger=xla_ledger.__name__):
        for n in (4, 8):   # two signatures: two births, one note
            out = program(jnp.arange(n, dtype=jnp.float32))
            assert isinstance(out, Unknown)
            np.testing.assert_array_equal(out.doubled, 2.0 * np.arange(n))
            assert xla_ledger.last_entry().stored is None
    (why,) = [c for c in xla_ledger.summary()["path_choices"]
              if c["site"] == "program_store"]
    assert (why["choice"], why["dims"], why["traces"]) == (
        "traced", "program=uncarried", 2)
    assert "unregistered type" in why["reason"]
    said = [r for r in caplog.records if "program_store" in r.getMessage()]
    assert len(said) == 1
    s = xla_ledger.summary()
    assert (s["programs_stored"], s["programs_store_writes"]) == (0, 0)
    assert files(store) == []


def test_without_a_description_or_a_store_a_program_is_traced(store,
                                                             monkeypatch):
    plain = xla_ledger.ledgered_jit(lambda x: x + 1, name="undescribed")
    plain(jnp.ones((2,)))
    assert xla_ledger.last_entry().stored is None and files(store) == []
    monkeypatch.setattr(xla_ledger, "_program_store", None)
    cfg, ecfg = tiny_config(), EngineConfig(**ENGINE)
    assert born(cfg, ecfg) is None and files(store) == []


# -- the kernel's module, for the chip, from here ----------------------------------- #

def test_the_prefill_kernel_exported_for_the_tpu_survives_the_round_trip():
    """`prefill_attention_pallas` under a donated pool, exported for
    `platforms=["tpu"]` from the CPU: the serialised module deserialises,
    holds the kernel's `tpu_custom_call`, keeps the donated operands'
    aliasing, and lowers for the TPU under an outer `jit`."""
    from dynamo_tpu.ops.pallas_attention import prefill_attention_pallas

    heads, kv_heads, hd, page, chunk, pages = 4, 2, 128, 16, 128, 16

    def body(k, v, q, kn, vn, table, prefix, lens):
        out = prefill_attention_pallas(q, kn, vn, k, v, table, prefix, lens,
                                       layer=jnp.int32(0))
        return out, k.at[0, 1].set(kn[0, :page]), v

    def f32(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16)

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    pool = f32(2, 64, page, kv_heads, hd)
    avals = (pool, pool, f32(1, chunk, heads, hd), f32(1, chunk, kv_heads, hd),
             f32(1, chunk, kv_heads, hd), i32(1, pages), i32(1), i32(1))
    exported = export.export(jax.jit(body, donate_argnums=(0, 1)),
                             platforms=["tpu"])(*avals)
    back = export.deserialize(exported.serialize())
    assert back.platforms == ("tpu",)
    text = back.mlir_module()
    assert text.count("tpu_custom_call") >= 1
    assert text.count("tf.aliasing_output") == 2
    lowered = jax.jit(back.call, donate_argnums=(0, 1)).trace(
        *avals).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in lowered
    assert lowered.count("tf.aliasing_output") >= 2


# -- the benchmark's reader ----------------------------------------------------------- #

def reader():
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "setup.programs_stored_pct.py")
    spec = importlib.util.spec_from_file_location("programs_stored_pct", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program(t_ms, fn="prefill_step", **attrs):
    return {"kind": "program", "t_ns": t_ms * 1_000_000,
            "dur_ns": 1_000_000, "fn": fn, **attrs}


@pytest.mark.parametrize("events,expected", [
    ([], None),
    ([program(1), program(2, hit=1)], None),             # the parent's ring
    ([program(1, stored=1), program(2, stored=1)], 100.0),
    ([program(1, stored=0), program(2, stored=0)], 0.0),  # a first start
    ([program(1, stored=1), program(2)], 50.0),           # one declined
    ([program(1, stored=1), program(2, fn=""),            # unnamed: not the
      program(3, stored=1)], 100.0),                      # store's
    ([program(1, stored=1), program(20_000, stored=0)], 100.0),  # in window
    ([program(1, fn="", stored=1), {"kind": "ready", "t_ns": 0}], None),
], ids=["empty", "no-attribute", "all-stored", "first-start", "declined",
        "unnamed", "after-t0", "only-unnamed"])
def test_the_stored_share_reader(events, expected):
    assert reader()({"t0": 10.0, "t1": 50.0, "events": events}) == expected


# -- a worker process, started twice over one cache directory ------------------------- #

def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def serve_once(tmp, cache, name):
    """Control plane + worker + frontend as processes on the tiny model, the
    cache directory given as an operator gives it; one chat; the worker's
    `STARTUP` line, `/metrics.json` `runtime.xla`, its ring and the answer."""
    import json
    import subprocess
    import time
    import urllib.request

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": cache}
    procs, logs = [], {}

    def spawn(what, args):
        logs[what] = str(tmp / f"{name}-{what}.log")
        with open(logs[what], "w") as f:
            p = subprocess.Popen([sys.executable, "-u", *args], stdout=f,
                                 stderr=subprocess.STDOUT, env=env, cwd=ROOT)
        procs.append(p)
        deadline = time.time() + 180
        while "READY" not in open(logs[what]).read():
            assert p.poll() is None and time.time() < deadline, (
                what + " did not come up:\n" + open(logs[what]).read()[-3000:])
            time.sleep(0.2)

    def get(port, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", body,
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.load(r)

    cp, status, http = _free_port(), _free_port(), _free_port()
    try:
        spawn("control", ["-m", "dynamo_tpu.runtime", "--port", str(cp),
                          "--host", "127.0.0.1"])
        spawn("worker", [
            "-m", "dynamo_tpu.worker", "--control", f"127.0.0.1:{cp}",
            "--model", "tiny", "--dtype", "float32", "--platform", "cpu",
            "--page-size", "8", "--num-pages", "96",
            "--max-prefill-tokens", "64", "--max-model-len", "128",
            "--no-prefix-caching", "--status-port", str(status)])
        spawn("frontend", ["-m", "dynamo_tpu.frontend", "--control",
                           f"127.0.0.1:{cp}", "--host", "127.0.0.1",
                           "--port", str(http)])
        body = json.dumps({
            "model": "tiny-chat", "max_tokens": 4, "temperature": 0,
            "logprobs": True, "nvext": {"ignore_eos": True},
            "messages": [{"role": "user", "content": "hello there"}]}).encode()
        deadline = time.time() + 60
        while True:
            try:
                answer = get(http, "/v1/chat/completions", body)
                break
            except Exception:  # noqa: BLE001 — may still be registering
                assert time.time() < deadline, open(logs["worker"]).read()[-3000:]
                time.sleep(0.3)
        t0 = time.monotonic()
        (line,) = [ln for ln in open(logs["worker"]).read().splitlines()
                   if ln.startswith("STARTUP ")]
        return {"answer": answer["choices"][0],
                "startup": json.loads(line[len("STARTUP "):]),
                "xla": get(status, "/metrics.json")["runtime"]["xla"],
                "run": {"t0": t0, "t1": t0 + 1, "events": get(
                    status, "/events.json")["engine"]["events"]}}
    finally:
        for p in procs[::-1]:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_a_worker_restarted_on_its_cache_directory_reads_its_programs(
        tmp_path):
    """Whoever owns the persistent cache's place owns the store's: the
    directory `JAX_COMPILATION_CACHE_DIR` names gets `programs/`; the first
    worker writes there what the second reads; both say so on the
    `STARTUP` line, in `/metrics.json` and on their `program` events, which
    the benchmark's reader turns into 0 and 100 %; and the second serves
    what the first served."""
    cache = str(tmp_path / "cache")
    first = serve_once(tmp_path, cache, "first")
    second = serve_once(tmp_path, cache, "second")
    written = os.listdir(os.path.join(cache, "programs"))
    n = first["xla"]["programs_store_writes"]
    assert n >= 2 and first["xla"]["programs_stored"] == 0
    assert len(written) == n and all(
        f.endswith(".jaxprog") for f in written)
    assert (second["xla"]["programs_stored"],
            second["xla"]["programs_store_writes"]) == (n, 0)
    for run in (first, second):   # as `runtime.xla` stood at READY
        assert set(run["startup"]) >= {"programs_stored",
                                       "programs_store_writes"}
    assert first["startup"]["programs_stored"] == 0
    assert second["startup"]["programs_store_writes"] == 0
    assert reader()(first["run"]) == 0.0
    assert reader()(second["run"]) == 100.0
    assert second["answer"]["message"] == first["answer"]["message"]
    assert second["answer"]["logprobs"] == first["answer"]["logprobs"]
    assert first["answer"]["logprobs"]["content"]
