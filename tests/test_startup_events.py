"""A worker PROCESS on the tiny model, from its start to a served request:
the host events it leaves on the step ring (`startup.*` slices that tile
process start -> `ready`, a `program` slice for every program born, the
`STARTUP` line and `/metrics.json` `runtime.startup` beside them), and the
benchmark's six start-up readers, loaded by path, on that worker's own
dump: program to reader, end to end (docs/observability.md, "Start-up and
compiles on the timeline")."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
PHASES = ("imports", "backend", "weights", "engine", "serve")
READERS = ("setup.worker_ready_s", "setup.weights_s",
           "setup.ready_to_window_s", "setup.programs_s",
           "compile.in_window", "host.pause_ms")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.load(r)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Control plane + worker + frontend as processes; three identical
    requests, the third inside a `window`; the worker's ring, its
    `/metrics.json` and its log."""
    tmp = tmp_path_factory.mktemp("startup")
    flight = str(tmp / "flight")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "DYN_TPU_FLIGHT_DIR": flight}
    procs, logs = [], {}

    def spawn(name, args):
        logs[name] = str(tmp / f"{name}.log")
        with open(logs[name], "w") as f:
            p = subprocess.Popen([sys.executable, "-u", *args], stdout=f,
                                 stderr=subprocess.STDOUT, env=env, cwd=ROOT)
        procs.append(p)
        deadline = time.time() + 180
        while "READY" not in open(logs[name]).read():
            assert p.poll() is None and time.time() < deadline, (
                name + " did not come up:\n" + open(logs[name]).read()[-3000:])
            time.sleep(0.2)

    def chat(port):
        body = json.dumps({
            "model": "tiny-chat",
            "messages": [{"role": "user", "content": "hello there"}],
            "max_tokens": 3, "temperature": 0,
            "nvext": {"ignore_eos": True}}).encode()
        deadline = time.time() + 60
        while True:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/chat/completions", body,
                    {"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    return json.load(r)
            except Exception:  # noqa: BLE001 — may still be registering
                assert time.time() < deadline, open(logs["worker"]).read()[-3000:]
                time.sleep(0.3)

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
        chat(http)
        first = _get(status, "/events.json")["engine"]
        chat(http)
        second = _get(
            status, f"/events.json?since_ns={first['watermark_ns']}")["engine"]
        t0 = time.monotonic()           # the worker's clock: one machine
        metrics0 = _get(status, "/metrics.json")
        chat(http)
        t1 = time.monotonic()
        ring = _get(status, "/events.json")["engine"]
        yield {"first": first, "second": second, "ring": ring, "t0": t0,
               "t1": t1, "metrics0": metrics0, "flight": flight,
               "metrics1": _get(status, "/metrics.json"),
               "log": open(logs["worker"]).read()}
    finally:
        for p in procs[::-1]:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def _by_kind(dump):
    out = {}
    for e in dump["events"]:
        out.setdefault(e["kind"], []).append(e)
    return out


def test_startup_slices_tile_process_start_to_ready(served):
    kinds = _by_kind(served["first"])
    slices = [kinds["startup." + p][0] for p in PHASES]
    (ready,) = kinds["ready"]
    for a, b in zip(slices, slices[1:]):
        assert a["t_ns"] + a["dur_ns"] == b["t_ns"]     # no gap, no overlap
    assert slices[-1]["t_ns"] + slices[-1]["dur_ns"] == ready["t_ns"]
    assert ready["dur_ns"] == 0 and ready["model"] == "tiny-chat"
    total = ready["t_ns"] - slices[0]["t_ns"]
    assert abs(sum(s["dur_ns"] for s in slices) - total) <= 0.02 * total
    # process start comes from /proc: before the interpreter's first line,
    # and the imports (jax among them) take a second or more from there
    assert slices[0]["dur_ns"] > 500_000_000
    assert all(s["dur_ns"] > 0 for s in slices)
    # older than the ring they are on, and first on it, in order
    assert [e["kind"] for e in served["first"]["events"]
            if e["kind"].startswith("startup.")] == [
                "startup." + p for p in PHASES]
    assert kinds["startup.backend"][0]["platform"] == "cpu"
    weights = kinds["startup.weights"][0]
    assert weights["bytes"] > 0 and weights["tensors"] > 0
    assert weights["read_us"] + weights["put_us"] == weights["dur_ns"] // 1000
    assert kinds["startup.engine"][0]["pool_bytes"] > 0


def test_metrics_json_and_the_startup_line_say_the_same_phases(served):
    kinds = _by_kind(served["first"])
    startup = served["metrics1"]["runtime"]["startup"]
    (line,) = [ln for ln in served["log"].splitlines()
               if ln.startswith("STARTUP ")]
    assert json.loads(line[len("STARTUP "):]) == startup
    for p in PHASES:
        assert startup[p + "_s"] == pytest.approx(
            kinds["startup." + p][0]["dur_ns"] / 1e9, abs=0.0011)
    assert startup["t0_ns"] == kinds["startup.imports"][0]["t_ns"]
    assert startup["ready_s"] == pytest.approx(
        (kinds["ready"][0]["t_ns"] - startup["t0_ns"]) / 1e9, abs=0.0011)
    assert startup["ready_s"] == pytest.approx(
        sum(startup[p + "_s"] for p in PHASES), abs=0.006)
    # the line stands beside READY, after DEVICE
    lines = served["log"].splitlines()
    at = {k: next(i for i, ln in enumerate(lines) if ln.startswith(k))
          for k in ("DEVICE ", "STARTUP ", "READY worker")}
    assert at["DEVICE "] < at["STARTUP "] < at["READY worker"]


def test_first_request_bears_programs_and_an_identical_second_none(served):
    kinds = _by_kind(served["first"])
    ready = kinds["ready"][0]["t_ns"]
    served_programs = [e for e in kinds["program"] if e["t_ns"] > ready]
    names = {e["fn"] for e in served_programs}
    assert {"prefill_step", "decode_step"} <= names
    for e in served_programs:
        if e["fn"]:
            assert e["role"] == "step" and len(e["sig"]) == 8
            assert e["trace_us"] > 0 and e["lower_us"] > 0
            assert e["dur_ns"] >= 1000 * (e["trace_us"] + e["lower_us"])
    steps = [e for e in served["first"]["events"]
             if e["kind"] in ("prefill_chunk", "decode_block")]
    assert sum(e.get("compiled", 0) for e in steps) >= 2
    assert "program" not in _by_kind(served["second"])
    assert all("compiled" not in e for e in served["second"]["events"])
    assert "first_token" in _by_kind(served["second"])   # it was served


def test_every_program_the_ledger_counts_is_an_event_or_counted_small(served):
    xla = served["metrics1"]["runtime"]["xla"]
    assert xla["program_events"] + xla["programs_sub_ms"] == (
        xla["backend_compiles"])
    assert xla["cache_hits"] + xla["cache_misses"] <= (
        xla["program_events"] + xla["programs_sub_ms"])
    assert served["ring"]["dropped_total"] == 0
    assert len(_by_kind(served["ring"])["program"]) == xla["program_events"]
    # nothing was born between the window's ends
    assert served["metrics0"]["runtime"]["xla"]["backend_compiles"] == (
        xla["backend_compiles"])


def test_flight_recorder_holds_the_startup_events_too(served):
    from dynamo_tpu.runtime.events import load_flight_dir

    dumps = load_flight_dir(served["flight"])
    kinds = [e["kind"] for d in dumps for e in d["events"]]
    assert ["startup." + p for p in PHASES] == [
        k for k in kinds if k.startswith("startup.")]
    assert "ready" in kinds and "program" in kinds
    (imports,) = [e for d in dumps for e in d["events"]
                  if e["kind"] == "startup.imports"]
    assert imports["t_ns"] == _by_kind(served["first"])[
        "startup.imports"][0]["t_ns"]


def test_six_readers_loaded_by_path_read_the_workers_own_dump(served):
    """Program to reader: the files `benchmark/run.py` would load, on the
    ring this worker served, with the third request as the window."""
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint as ckpt

        read = {n: ckpt.load_module("layer_metrics", n).read for n in READERS}
    finally:
        sys.path.remove(BENCH)
    events = [dict(e, ring="engine") for e in served["ring"]["events"]]
    run = {"t0": served["t0"], "t1": served["t1"], "events": events,
           "records": [], "trace": None, "metrics0": served["metrics0"],
           "metrics1": served["metrics1"]}
    got = {n: read[n](run) for n in READERS}
    startup = served["metrics1"]["runtime"]["startup"]
    assert got["setup.worker_ready_s"] == pytest.approx(
        startup["ready_s"], abs=0.0011)
    assert got["setup.weights_s"] == pytest.approx(
        startup["weights_s"], abs=0.0011)
    kinds = _by_kind(served["ring"])
    assert got["setup.ready_to_window_s"] == pytest.approx(
        served["t0"] - kinds["ready"][0]["t_ns"] / 1e9)
    assert got["setup.ready_to_window_s"] > 0
    stages = ("trace_us", "lower_us", "compile_us", "load_us")
    assert got["setup.programs_s"] == pytest.approx(
        sum(e.get(k, 0) for e in kinds["program"] for k in stages) / 1e6)
    assert got["setup.programs_s"] > 0
    assert got["compile.in_window"] == 0
    assert got["host.pause_ms"] >= 0.0
    # the three-way split stays under what a harness would call set-up
    assert got["setup.worker_ready_s"] + got["setup.ready_to_window_s"] < (
        served["t0"] - startup["t0_ns"] / 1e9 + 1e-6)
    # a program that records none of this (the parent): left out, not wrong
    from dynamo_tpu.runtime.events import HOST_EVENT_KINDS

    bare = dict(run, events=[e for e in events
                             if e["kind"] not in HOST_EVENT_KINDS])
    assert {n: read[n](bare) for n in READERS} == dict.fromkeys(READERS)


def test_the_host_account_readers_read_the_workers_own_dump(served):
    """ISSUE 57's readers on the same worker's ring (REHEARSAL.json is an
    existing file and cannot name them): the loop's own two need no trace,
    the account tiles between the worker's `ready` and its last record, and
    the readers that charge device idle time read nothing without one."""
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint as ckpt
        from lib import hostline

        names = ["host.cycle_ms_per_step", "engine.dry_dispatch_pct",
                 "host.exposed_ms_per_step", "host.clock_slack_us"] + [
            f"host.exposed_{s}_pct" for s in hostline.SHARE_NAMES]
        read = {n: ckpt.load_module("layer_metrics", n).read for n in names}
    finally:
        sys.path.remove(BENCH)
    events = [dict(e, ring="engine") for e in served["ring"]["events"]]
    run = {"t0": served["t0"], "t1": served["t1"], "events": events,
           "records": [], "trace": None}
    got = {n: read[n](run) for n in names}
    steps = [e for e in events if e["kind"] in hostline.STEP_KINDS
             and served["t0"] * 1e9 <= e["t_ns"] + e["dur_ns"]
             <= served["t1"] * 1e9]
    assert steps and all(k in e for e in steps
                         for k in ("seq", "dry", "hop_us"))
    assert got["host.cycle_ms_per_step"] > 0
    assert got["engine.dry_dispatch_pct"] == pytest.approx(
        100.0 * sum(e["dry"] for e in steps) / len(steps))
    assert {n: v for n, v in got.items() if n.startswith(
        ("host.exposed", "host.clock"))} == dict.fromkeys(names[2:])
    # between `ready` and the ring's last record every instant of the loop
    # lies under a phase but the pump's start-up before its first plan
    line = hostline.timeline(events)
    a = min(e["t_ns"] for e in events if e["kind"] == "plan")
    b = max(s[1] for s in line)
    covered, overlapped = hostline.coverage(line, a, b)
    assert overlapped == 0 and (b - a) - covered < 0.01 * (b - a)
    m1 = served["metrics1"]
    assert m1["steps_dry_total"] >= sum(e["dry"] for e in steps)


def test_the_spec_lists_the_six_readers_under_one_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    mine = [m for m in spec["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    # appended together, after everything PR 41 had (a later PR appends its
    # own after them: their place is held by name, PERF.md 7 (k))
    first = spec["per_layer"].index(mine[0])
    assert spec["per_layer"][first:first + len(READERS)] == mine
    assert "step.prefill_head_steps_pct" in [
        m["name"] for m in spec["per_layer"][:first]]
    for m in mine:
        assert (m["layer"], m["source"], m["better"]) == (
            "compile; start-up", "program_span", "lower")
        assert m["workloads"] == cells
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
    assert {m["name"]: m["moves"] for m in mine} == {
        **dict.fromkeys(READERS[:4], "setup_s"),
        "compile.in_window": "ttft_p95_ms", "host.pause_ms": "ttft_p95_ms"}
