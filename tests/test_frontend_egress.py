"""Frontend egress data plane (frontend/egress.py + the rewritten
_stream_response drain loop, docs/frontend_dataplane.md):

- ChunkTemplate zero-copy frames are BYTE-identical to the legacy
  json.dumps round trip,
- the batched writer's wire output with coalescing off is byte-identical
  to the legacy per-delta writer through the real HTTP stack (and
  token-identical with coalescing on),
- keepalive pings key off time-since-last-WRITE,
- the per-delta frame-building budget (tier-1 micro-gate: calls counted,
  as the StepEventRecorder's),
- SO_REUSEPORT frontend sharding.
"""

import asyncio
import json
import re

import aiohttp
import pytest

from dynamo_tpu.frontend import HttpService, ModelManager
from dynamo_tpu.frontend.egress import (
    CONTENT_SENTINEL,
    ChunkTemplate,
    StreamEgress,
    sse_frame,
)
from dynamo_tpu.frontend.loadgen import SimStreamEngine, single_char_token_ids
from dynamo_tpu.frontend.service import ModelEntry
from dynamo_tpu.llm import ModelDeploymentCard
from dynamo_tpu.testing import call_ceiling, counted_calls, tiny_tokenizer


# --------------------------------------------------------------------------- #
# ChunkTemplate: zero-copy frame == json.dumps frame, byte for byte
# --------------------------------------------------------------------------- #

def _chat_chunk(text):
    return {
        "id": "chatcmpl-0123456789abcdef", "object": "chat.completion.chunk",
        "created": 1700000000, "model": "tiny",
        "choices": [{"index": 2, "delta": {"content": text},
                     "finish_reason": None}],
    }


def _completion_chunk(text):
    return {
        "id": "cmpl-0123456789abcdef", "object": "text_completion",
        "created": 1700000000, "model": "tiny",
        "choices": [{"index": 0, "text": text, "finish_reason": None}],
    }


@pytest.mark.parametrize("make", [_chat_chunk, _completion_chunk])
@pytest.mark.parametrize("text", [
    "hello", "", "with \"quotes\" and \\backslash\\",
    "newline\nand\ttab", "controls \x00\x1f", "café ☃ \U0001f600",
])
def test_template_frame_byte_identical(make, text):
    tmpl = ChunkTemplate(make(CONTENT_SENTINEL))
    assert tmpl.frame(text) == sse_frame(make(text))


def test_template_rejects_missing_or_repeated_sentinel():
    with pytest.raises(ValueError):
        ChunkTemplate(_chat_chunk("no sentinel here"))
    chunk = _chat_chunk(CONTENT_SENTINEL)
    chunk["model"] = CONTENT_SENTINEL  # two slots: ambiguous splice
    with pytest.raises(ValueError):
        ChunkTemplate(chunk)


# --------------------------------------------------------------------------- #
# StreamEgress: batching, coalescing, counters
# --------------------------------------------------------------------------- #

class _SinkResp:
    def __init__(self):
        self.writes = []

    async def write(self, data):
        self.writes.append(data)


async def test_burst_drains_into_one_write():
    resp = _SinkResp()
    eg = StreamEgress(resp)
    tmpl = ChunkTemplate(_chat_chunk(CONTENT_SENTINEL))
    for ch in "abc":
        eg.add_fast(tmpl, ch)
    await eg.flush()
    assert len(resp.writes) == 1 and eg.writes == 1
    assert resp.writes[0] == b"".join(sse_frame(_chat_chunk(c))
                                      for c in "abc")
    assert eg.frames == 3 and eg.deltas == 3 and eg.coalesced == 0


async def test_coalescing_merges_same_template_runs():
    resp = _SinkResp()
    eg = StreamEgress(resp, coalesce=True, coalesce_max=4)
    tmpl = ChunkTemplate(_chat_chunk(CONTENT_SENTINEL))
    other = ChunkTemplate(_completion_chunk(CONTENT_SENTINEL))
    for ch in "abcdef":          # run of 6, max 4 → frames "abcd" + "ef"
        eg.add_fast(tmpl, ch)
    eg.add_fast(other, "x")      # template switch seals the run
    eg.add_obj({"done": 1})      # full-serialization frame seals too
    await eg.flush()
    assert len(resp.writes) == 1
    assert resp.writes[0] == (
        sse_frame(_chat_chunk("abcd")) + sse_frame(_chat_chunk("ef"))
        + sse_frame(_completion_chunk("x")) + sse_frame({"done": 1})
    )
    assert eg.frames == 4 and eg.deltas == 8
    assert eg.coalesced == 4     # 3 merged into "abcd", 1 into "ef"


async def test_flush_without_frames_writes_nothing():
    resp = _SinkResp()
    eg = StreamEgress(resp)
    await eg.flush()
    assert resp.writes == [] and eg.writes == 0 and eg.bytes_out == 0


# --------------------------------------------------------------------------- #
# wire-level golden: legacy writer vs batched writer through the stack
# --------------------------------------------------------------------------- #

_NORM = [
    (re.compile(rb"chatcmpl-[0-9a-f]{24}"), b"chatcmpl-RID"),
    (re.compile(rb"cmpl-[0-9a-f]{24}"), b"cmpl-RID"),
    (re.compile(rb'"created": \d+'), b'"created": 0'),
]


def _normalize(body: bytes) -> bytes:
    for pat, sub in _NORM:
        body = pat.sub(sub, body)
    return body


async def _start_service(tok, mdc, char_ids, **service_kw):
    manager = ModelManager()
    manager.add(mdc.name, ModelEntry.local(
        mdc, tok, SimStreamEngine(char_ids, interval_s=0.0)))
    port = service_kw.pop("port", 0)
    return await HttpService(manager, host="127.0.0.1", port=port,
                             **service_kw).start()


async def _fetch(port, path, payload):
    async with aiohttp.ClientSession() as s:
        async with s.post(f"http://127.0.0.1:{port}{path}",
                          json=payload) as r:
            assert r.status == 200, await r.text()
            return await r.read()


def _sse_contents(body: bytes, kind: str):
    """Per-choice reassembled content from a raw SSE body."""
    out = {}
    for frame in body.split(b"\n\n"):
        if not frame.startswith(b"data: {"):
            continue
        chunk = json.loads(frame[6:])
        for ch in chunk["choices"]:
            text = (ch.get("delta", {}).get("content", "")
                    if kind == "chat" else ch.get("text", ""))
            out[ch["index"]] = out.get(ch["index"], "") + (text or "")
    return out


async def test_sse_golden_legacy_vs_fast_and_coalesced():
    """Coalescing OFF → byte-identical to the legacy writer on the wire
    (modulo request id / created timestamp); coalescing ON → identical
    per-choice token sequence.  Chat + completions, streaming + unary,
    n>1."""
    tok = tiny_tokenizer()
    char_ids = single_char_token_ids(tok)
    mdc = ModelDeploymentCard(name="tiny", tokenizer_json=tok.to_json_str(),
                              eos_token_ids=list(tok.eos_token_ids))
    requests = [
        ("chat", "/v1/chat/completions",
         {"model": "tiny", "messages": [{"role": "user", "content": "hi"}],
          "max_tokens": 6, "n": 3, "seed": 7, "stream": True}),
        ("completions", "/v1/completions",
         {"model": "tiny", "prompt": "hi", "max_tokens": 6, "n": 2,
          "seed": 40, "stream": True}),
    ]
    arms = {}
    for arm, kw in (
        ("legacy", dict(sse_legacy=True)),
        ("fast", dict(sse_coalesce=False)),
        ("coalesce", dict(sse_coalesce=True)),
    ):
        http = await _start_service(tok, mdc, char_ids, **kw)
        try:
            arms[arm] = {
                kind: await _fetch(http.port, path, payload)
                for kind, path, payload in requests
            }
            # unary rides the same arms: byte-identical JSON response
            arms[arm]["unary"] = await _fetch(
                http.port, "/v1/chat/completions",
                {"model": "tiny",
                 "messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 4, "n": 2, "seed": 90})
        finally:
            await http.stop()
    for kind in ("chat", "completions", "unary"):
        assert _normalize(arms["legacy"][kind]) == \
            _normalize(arms["fast"][kind]), kind
    for kind in ("chat", "completions"):
        want = _sse_contents(arms["legacy"][kind], kind)
        got = _sse_contents(arms["coalesce"][kind], kind)
        assert got == want and len(want) > 1, kind
        assert all(len(v) == 6 for v in want.values()), kind
        # and coalescing actually merged something on this burst shape
        assert arms["coalesce"][kind].count(b"data: ") < \
            arms["legacy"][kind].count(b"data: "), kind
    assert arms["legacy"]["chat"].endswith(b"data: [DONE]\n\n")


# --------------------------------------------------------------------------- #
# keepalive: time-since-last-WRITE, not time-since-last-queue-item
# --------------------------------------------------------------------------- #

class _GappyEngine:
    """One token, a long silence, one finishing token."""

    def __init__(self, char_ids, gap_s):
        self.char_ids = char_ids
        self.gap_s = gap_s

    async def generate(self, request, context=None):
        yield {"token_ids": [self.char_ids[0]], "finish_reason": None}
        await asyncio.sleep(self.gap_s)
        yield {"token_ids": [self.char_ids[1]], "finish_reason": "length"}


class _SteadyEngine:
    """Tokens at a steady trickle — every delta produces a write."""

    def __init__(self, char_ids, n, spacing_s):
        self.char_ids = char_ids
        self.n = n
        self.spacing_s = spacing_s

    async def generate(self, request, context=None):
        for k in range(self.n):
            await asyncio.sleep(self.spacing_s)
            yield {"token_ids": [self.char_ids[k % len(self.char_ids)]],
                   "finish_reason": "length" if k == self.n - 1 else None}


async def _stream_with(engine, monkeypatch, keepalive_s):
    from dynamo_tpu.frontend import openai_http

    monkeypatch.setattr(openai_http, "SSE_KEEPALIVE_S", keepalive_s)
    tok = tiny_tokenizer()
    mdc = ModelDeploymentCard(name="tiny", tokenizer_json=tok.to_json_str(),
                              eos_token_ids=list(tok.eos_token_ids))
    manager = ModelManager()
    manager.add("tiny", ModelEntry.local(mdc, tok, engine))
    http = await HttpService(manager, host="127.0.0.1", port=0).start()
    try:
        return await _fetch(
            http.port, "/v1/chat/completions",
            {"model": "tiny", "messages": [{"role": "user", "content": "x"}],
             "max_tokens": 16, "stream": True})
    finally:
        await http.stop()


async def test_keepalive_pings_during_engine_silence(monkeypatch):
    char_ids = single_char_token_ids(tiny_tokenizer())
    body = await _stream_with(_GappyEngine(char_ids, gap_s=0.7),
                              monkeypatch, keepalive_s=0.2)
    # ~0.7s of silence at a 0.2s keepalive → at least 2 pings, and they
    # land BETWEEN the two token frames (split[1] = after frame 1's
    # payload, before frame 2's "data: " marker)
    gap = body.split(b"data: ", 2)[1]
    assert gap.count(b": keep-alive\n\n") >= 2
    assert body.count(b": keep-alive\n\n") <= 4


async def test_keepalive_quiet_while_writes_flow(monkeypatch):
    """Steady token writes reset the write-anchored timer: a stream
    that is never silent for the keepalive interval gets NO pings (the
    old per-queue-item reset would also have passed here — the
    regression case is the silence test above, where markers/token-less
    items must not suppress pings)."""
    char_ids = single_char_token_ids(tiny_tokenizer())
    body = await _stream_with(
        _SteadyEngine(char_ids, n=8, spacing_s=0.05),
        monkeypatch, keepalive_s=0.4)
    assert b": keep-alive" not in body
    # 8 token frames (finish rides on the last content frame) + [DONE]
    assert body.count(b"data: ") == 8 + 1


# --------------------------------------------------------------------------- #
# tier-1 micro-gate: per-delta frame-building cost
# --------------------------------------------------------------------------- #

async def test_egress_call_budget_per_burst():
    """The frame-building hot path (template splice + burst buffering,
    null sink) as work, not time: a burst of 8 deltas and its flush is 56
    Python-level calls, 7 a delta (the per-token frontend cost the
    saturation bench banks on), ONE of them the sink's `write`; the 8
    deltas coalesce into one frame.  The ceiling is today's count."""
    sink = _SinkResp()
    eg = StreamEgress(sink, coalesce=True)
    tmpl = ChunkTemplate(_chat_chunk(CONTENT_SENTINEL))
    bursts = 500
    with counted_calls() as c:
        for _ in range(bursts):
            for _ in range(8):      # a modest burst
                eg.add_fast(tmpl, "hello")
            await eg.flush()
    assert eg.deltas == 8 * bursts and eg.coalesced == 7 * bursts
    assert eg.writes == eg.frames == len(sink.writes) == bursts
    assert c.names["_SinkResp.write"] == bursts
    assert c.total // bursts <= call_ceiling(56), dict(c.names)


# --------------------------------------------------------------------------- #
# SO_REUSEPORT sharding
# --------------------------------------------------------------------------- #

async def test_reuse_port_shares_one_address():
    tok = tiny_tokenizer()
    mdc = ModelDeploymentCard(name="tiny", tokenizer_json=tok.to_json_str(),
                              eos_token_ids=list(tok.eos_token_ids))
    char_ids = single_char_token_ids(tok)
    a = await _start_service(tok, mdc, char_ids, reuse_port=True)
    b = await _start_service(tok, mdc, char_ids, reuse_port=True,
                             port=a.port)
    try:
        assert b.port == a.port
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{a.port}/health") as r:
                assert r.status == 200
    finally:
        await b.stop()
        await a.stop()


async def test_without_reuse_port_rebind_fails():
    tok = tiny_tokenizer()
    mdc = ModelDeploymentCard(name="tiny", tokenizer_json=tok.to_json_str(),
                              eos_token_ids=list(tok.eos_token_ids))
    char_ids = single_char_token_ids(tok)
    a = await _start_service(tok, mdc, char_ids)
    try:
        with pytest.raises(OSError):
            await _start_service(tok, mdc, char_ids, port=a.port)
    finally:
        await a.stop()


# --------------------------------------------------------------------------- #
# egress_stream events on the step-event ring (/events.json)
# --------------------------------------------------------------------------- #

async def test_stream_records_egress_event():
    tok = tiny_tokenizer()
    mdc = ModelDeploymentCard(name="tiny", tokenizer_json=tok.to_json_str(),
                              eos_token_ids=list(tok.eos_token_ids))
    char_ids = single_char_token_ids(tok)
    http = await _start_service(tok, mdc, char_ids)
    try:
        await _fetch(http.port, "/v1/chat/completions",
                     {"model": "tiny",
                      "messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 3, "stream": True})
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{http.port}/events.json") as r:
                dump = await r.json()
    finally:
        await http.stop()
    ev = [e for e in dump["events"] if e["kind"] == "egress_stream"]
    assert ev and ev[-1]["deltas"] >= 3 and ev[-1]["writes"] >= 1
    assert ev[-1]["frames"] >= 3 and ev[-1]["bytes"] > 0
    assert http.events.totals().get("egress_stream", 0) == len(ev)
