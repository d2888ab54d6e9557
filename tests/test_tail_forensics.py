"""Tail-latency forensics: per-request waterfalls, /debug/tail.json,
OpenMetrics exemplars on the TTFT/ITL histograms, and the postmortem
tool's smoke test (docs/observability.md "Tail forensics")."""

import asyncio
import json
import os
import subprocess
import sys
import time

import aiohttp

from dynamo_tpu.frontend import HttpService, ModelManager
from dynamo_tpu.frontend.metrics import FrontendMetrics
from dynamo_tpu.frontend.service import ModelEntry
from dynamo_tpu.frontend.waterfall import build_waterfall
from dynamo_tpu.llm import ModelDeploymentCard
from dynamo_tpu.testing import tiny_tokenizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- waterfall stage math --------------------------------------------------- #


def test_waterfall_prefill_bottleneck():
    wf = build_waterfall(
        trace_id="t1", model="m", t0=100.0, t_end=100.5, t_first=100.4,
        t_last_tok=100.48,
        ttft_attr={"block_wait_ms": 5.0, "queue_wait_ms": 10.0,
                   "prefill_ms": 380.0},
        ntokens=8,
    )
    assert wf["bottleneck"] == "prefill"
    assert wf["stages"]["prefill_ms"] == 380.0
    assert abs(wf["ttft_ms"] - 400.0) < 1e-6
    assert abs(wf["total_ms"] - 500.0) < 1e-6
    assert wf["tokens"] == 8 and wf["status"] == 200
    # residual: 500 - (5+10+380+80) = 25ms of egress/unattributed
    assert abs(wf["stages"]["egress_ms"] - 25.0) < 1e-6


def test_waterfall_decode_and_queue_bottlenecks():
    decode = build_waterfall(
        trace_id="t2", model="m", t0=0.0, t_end=1.0, t_first=0.05,
        t_last_tok=0.99, ttft_attr={"prefill_ms": 40.0}, ntokens=64,
    )
    assert decode["bottleneck"] == "decode"
    queue = build_waterfall(
        trace_id="t3", model="m", t0=0.0, t_end=0.5, t_first=0.45,
        t_last_tok=0.48,
        ttft_attr={"queue_wait_ms": 400.0, "prefill_ms": 30.0},
    )
    assert queue["bottleneck"] == "queue"


def test_waterfall_incident_stalls_compete_as_stages():
    """A parked or migrated request blames preempt/migration, not an
    inflated decode (the stall happened INSIDE the token gap)."""
    wf = build_waterfall(
        trace_id="t4", model="m", t0=0.0, t_end=1.0, t_first=0.1,
        t_last_tok=0.95, ttft_attr={"prefill_ms": 80.0},
        incidents=[{"kind": "preempt", "stall_ms": 600.0},
                   {"kind": "onboard", "pages": 3, "stall_ms": 4.0}],
        ntokens=16,
    )
    assert wf["bottleneck"] == "preempt"
    assert wf["stages"]["preempt_ms"] == 600.0
    assert wf["stages"]["onboard_ms"] == 4.0
    assert wf["stages"]["decode_ms"] == 850.0  # raw gap, undiminished
    assert wf["incidents"][0]["kind"] == "preempt"
    mig = build_waterfall(
        trace_id="t5", model="m", t0=0.0, t_end=1.0, t_first=0.1,
        t_last_tok=0.95, ttft_attr={"prefill_ms": 80.0},
        incidents=[{"kind": "migration", "attempt": 1, "stall_ms": 700.0}],
    )
    assert mig["bottleneck"] == "migration"


def test_waterfall_shed_classifies_queue():
    wf = build_waterfall(trace_id="t6", model="m", t0=0.0, t_end=0.002,
                         status=429)
    assert wf["bottleneck"] == "queue" and wf["status"] == 429
    assert any(i["kind"] == "shed" for i in wf["incidents"])


def test_waterfall_no_tokens_never_negative():
    wf = build_waterfall(trace_id="t7", model="m", t0=10.0, t_end=9.0)
    assert wf["total_ms"] == 0.0
    assert all(v >= 0 for v in wf["stages"].values())


# -- e2e: a slow request shows up in /debug/tail.json ----------------------- #


class _EngineMovedClock:
    """`openai_http`'s `time`, with a `monotonic` that only the ENGINE
    moves (and a microsecond a reading, so stamps stay ordered): what the
    frontend cannot attribute is then a count of its own clock reads,
    not what six busy test workers did to its event loop."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        self.now += 1e-6
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


class _SlowPrefillEngine:
    """Mock engine with a deliberate prefill delay: TTFT ~250ms, nearly
    all attributed to prefill — the tail must blame `prefill`.  With a
    `clock` the delay is put ON it and nobody sleeps."""

    def __init__(self, char_id, prefill_s=0.25, clock=None):
        self.char_id = char_id
        self.prefill_s = prefill_s
        self.clock = clock

    async def generate(self, request, context):
        if self.clock is None:
            await asyncio.sleep(self.prefill_s)
        else:
            self.clock.now += self.prefill_s
        max_tokens = request["stop_conditions"]["max_tokens"]
        yield {"token_ids": [self.char_id],
               "ttft": {"block_wait_ms": 0.5, "queue_wait_ms": 1.0,
                        "prefill_ms": self.prefill_s * 1e3}}
        for _ in range(max_tokens - 1):
            yield {"token_ids": [self.char_id]}
        yield {"token_ids": [], "finish_reason": "length"}


async def _tail_stack(clock=None):
    tok = tiny_tokenizer()
    mdc = ModelDeploymentCard(name="tiny",
                              tokenizer_json=tok.to_json_str(),
                              eos_token_ids=list(tok.eos_token_ids))
    char_id = next(i for i in range(tok.vocab_size)
                   if len(tok.decode([i])) == 1)
    metrics = FrontendMetrics()
    manager = ModelManager()
    manager.add("tiny", ModelEntry.local(
        mdc, tok, _SlowPrefillEngine(char_id, clock=clock), metrics=metrics))
    http = await HttpService(manager, host="127.0.0.1", port=0,
                             metrics=metrics).start()
    return http, metrics


async def test_slow_request_named_in_tail_json(monkeypatch):
    """The verdict used to turn on a race of two clocks' worth: the
    injected 250 ms against `egress_ms`, which is whatever of the
    request's wall time the engine did not attribute, a loaded event
    loop's stalls included (ROADMAP D0: "comes and goes").  The delay is
    injected into the frontend's clock instead, and every figure below
    is exact."""
    from dynamo_tpu.frontend import openai_http

    clock = _EngineMovedClock()
    monkeypatch.setattr(openai_http, "time", clock)
    http, _metrics = await _tail_stack(clock)
    base = f"http://127.0.0.1:{http.port}"
    try:
        async with aiohttp.ClientSession() as session:
            body = {"model": "tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 4, "stream": True,
                    "nvext": {"ignore_eos": True}}
            async with session.post(
                f"{base}/v1/chat/completions", json=body,
                headers={"x-request-id": "slow-trace-0001"},
            ) as r:
                assert r.status == 200, await r.text()
                await r.read()
            async with session.get(f"{base}/debug/tail.json") as r:
                assert r.status == 200
                tail = await r.json()
    finally:
        await http.stop()
    assert tail["window_s"] > 0
    worst = tail["models"]["tiny"]
    assert worst, tail
    assert worst[0]["trace_id"] == "slow-trace-0001"
    assert worst[0]["bottleneck"] == "prefill"
    stages = worst[0]["stages"]
    assert stages["prefill_ms"] == 250.0        # carried, not measured
    # the frontend's own share: a few readings of its clock, 1 µs each
    assert 250.0 < worst[0]["ttft_ms"] < worst[0]["total_ms"] < 250.1
    assert 0 < stages["decode_ms"] < 0.1
    # the exemplar also reaches the fleet window snapshot
    async with aiohttp.ClientSession() as _s:
        pass  # session closed above; snapshot read is in-process
    snap = _metrics.slo.snapshot()["tiny"]
    assert snap["tail"][0]["trace_id"] == "slow-trace-0001"


async def test_metrics_openmetrics_exemplars():
    """`Accept: application/openmetrics-text` exposes `# {trace_id=...}`
    exemplars on the TTFT/ITL histograms; the default text format stays
    byte-compatible (no exemplar syntax)."""
    http, _metrics = await _tail_stack()
    base = f"http://127.0.0.1:{http.port}"
    try:
        async with aiohttp.ClientSession() as session:
            body = {"model": "tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 4, "stream": True,
                    "nvext": {"ignore_eos": True}}
            async with session.post(
                f"{base}/v1/chat/completions", json=body,
                headers={"x-request-id": "exemplar-trace-42"},
            ) as r:
                assert r.status == 200, await r.text()
                await r.read()
            async with session.get(
                f"{base}/metrics",
                headers={"Accept": "application/openmetrics-text"},
            ) as r:
                assert r.status == 200
                assert "openmetrics" in r.headers["Content-Type"]
                om = await r.text()
            async with session.get(f"{base}/metrics") as r:
                classic = await r.text()
    finally:
        await http.stop()
    ttft_lines = [ln for ln in om.splitlines()
                  if ln.startswith("dynamo_frontend_time_to_first_token_"
                                   "seconds_bucket") and "# {" in ln]
    assert any('trace_id="exemplar-trace-42"' in ln for ln in ttft_lines), (
        ttft_lines or om[-1500:])
    itl_lines = [ln for ln in om.splitlines()
                 if ln.startswith("dynamo_frontend_inter_token_latency_"
                                  "seconds_bucket") and "# {" in ln]
    assert any('trace_id="exemplar-trace-42"' in ln for ln in itl_lines)
    # classic exposition: unchanged surface, no exemplar syntax
    assert "# {" not in classic
    assert "dynamo_frontend_time_to_first_token_seconds_bucket" in classic


# -- postmortem tool smoke -------------------------------------------------- #


def test_postmortem_smoke_over_synthetic_dump(tmp_path):
    """scripts/postmortem.py over a synthetic dead-process dump dir:
    flight segments + an OTLP span file + a lockcheck ledger in, ONE
    summary JSON line and a valid merged timeline out."""
    from dynamo_tpu.runtime.events import FlightRecorder, StepEventRecorder

    rec = StepEventRecorder(
        capacity=64,
        flight=FlightRecorder(str(tmp_path), service="worker-dead",
                              segment_slots=64),
    )
    t0 = rec.now()
    rec.record("decode_block", t0_ns=t0, rung=8, batch=2, chain=1)
    rec.record("preempt_park", seq=3)
    rec.flight.close()
    wall = time.time_ns()
    span = {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name", "value": {"stringValue": "frontend"}}]},
        "scopeSpans": [{"spans": [{
            "name": "http.request", "traceId": "ab" * 16,
            "spanId": "cd" * 8,
            "startTimeUnixNano": str(wall - 10**9),
            "endTimeUnixNano": str(wall)}]}]}]}
    (tmp_path / "spans.jsonl").write_text(json.dumps(span) + "\n{torn")
    (tmp_path / "lockcheck-42.json").write_text(
        json.dumps({"cycles": [["a", "b"]], "self_deadlocks": []}))

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "postmortem.py"),
         "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["ok"] and summary["timeline_violations"] == 0
    assert summary["processes"] == 1 and summary["flight_events"] == 2
    assert summary["spans"] == 1 and summary["ledger_issues"] == 1
    doc = json.load(open(summary["timeline"]))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"decode_block", "preempt_park", "http.request"} <= names
    report = open(summary["report"]).read()
    assert "last 5s" in report or "last 5" in report
    # import-safe next to _verify_harness.py
    probe = subprocess.run(
        [sys.executable, "-c",
         f"import sys; sys.path.insert(0, {os.path.join(ROOT, 'scripts')!r}); "
         "import postmortem; assert callable(postmortem.run)"],
        capture_output=True, text=True, timeout=60,
    )
    assert probe.returncode == 0, probe.stderr


def test_postmortem_report_takes_events_that_end_at_one_instant():
    """The worker's `startup.serve` slice ends at the nanosecond of its
    `ready` instant: the report orders by time and never compares events."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import postmortem
    finally:
        sys.path.remove(os.path.join(ROOT, "scripts"))
    t = 5 * 10**9
    dump = {"wall_ns": 0, "mono_ns": 0, "events": [
        {"t_ns": t - 10**6, "dur_ns": 10**6, "kind": "startup.serve"},
        {"t_ns": t, "dur_ns": 0, "kind": "ready", "model": "m"}]}
    spans = [{"endTimeUnixNano": str(t), "service": "s", "name": n}
             for n in ("a", "b")]
    report, n = postmortem.last_seconds_report({"worker:1": dump}, spans)
    assert "startup.serve" in report and "ready" in report
    assert "worker:1: 2 event(s)" in report
