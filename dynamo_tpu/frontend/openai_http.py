"""OpenAI-compatible HTTP service (aiohttp).

The analog of the reference's axum service
(/root/reference/lib/llm/src/http/service/service_v2.rs:135 `HttpService`,
openai.rs:504 `handler_chat_completions`, :280 completions, :434 embeddings,
:767 responses, :1048 models):

- POST /v1/chat/completions, /v1/completions — SSE streaming and unary,
  n>1 choices, OpenAI logprobs/top_logprobs shapes
- POST /v1/embeddings — decoder-as-embedder path
- POST /v1/responses — Responses API over the chat pipeline
- GET  /v1/models
- GET  /health, /live, /metrics (prometheus exposition)
- POST /clear_kv_blocks — broadcast cache clear to workers

Client disconnects kill the request context so workers stop generating
(reference http/service/disconnect.rs).
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
import uuid
from typing import Any, AsyncIterator, Dict, Optional

from aiohttp import web

from ..analysis import leak_ledger
from ..llm import RequestError
from ..runtime import Context
from ..runtime.config import env_bool, env_int
from ..runtime.events import StepEventRecorder
from ..runtime.transport.service import RemoteStreamError, ServiceUnavailable
from .egress import CONTENT_SENTINEL, ChunkTemplate, StreamEgress, sse_frame
from .metrics import FrontendMetrics
from .service import ModelManager, ModelWatcher

logger = logging.getLogger(__name__)

# idle SSE connections get a comment ping this often (seconds), measured
# from the last bytes actually WRITTEN to the connection (not the last
# queue item — a token-less drain marker must not reset the timer)
SSE_KEEPALIVE_S = 10.0

# max queue items drained into one resp.write (bounds frame batch size
# and keeps a badly backed-up stream from starving its siblings)
_MAX_BURST = 256

# queue sentinel the rearming keepalive timer drops in when the
# time-since-last-write deadline passes (never a real delta tuple)
_KEEPALIVE = object()

# how long a BATCH-class stream peeks at the engine queue for an intake
# shed before committing the 200/SSE preamble: an engine shed is its
# very first yield, so this resolves in one scheduler hop normally; the
# timeout only bites when the first delta is slower than the probe, in
# which case the stream proceeds as usual (interactive never probes)
_SHED_PROBE_S = 0.25


class _ChoiceParsers:
    """Per-choice output parsing: reasoning split first, then tool-call
    extraction on the content stream (reference: parsers crate wired into
    the chat response path)."""

    def __init__(self, mdc):
        from ..parsers import get_reasoning_parser, get_tool_parser

        self.reasoning = get_reasoning_parser(
            getattr(mdc, "reasoning_parser", "") or "")
        self.tools = get_tool_parser(
            getattr(mdc, "tool_call_parser", "") or "")
        self.n_tool_calls = 0

    @staticmethod
    def active(mdc) -> bool:
        return bool(getattr(mdc, "reasoning_parser", "")
                    or getattr(mdc, "tool_call_parser", ""))

    def push(self, text: str) -> dict:
        rd = self.reasoning.push(text)
        td = self.tools.push(rd.content)
        return {"content": td.content, "reasoning": rd.reasoning,
                "tool_calls": td.tool_calls}

    def finish(self) -> dict:
        rd = self.reasoning.finish()
        td = self.tools.push(rd.content)
        fd = self.tools.finish()
        return {"content": td.content + fd.content, "reasoning": rd.reasoning,
                "tool_calls": td.tool_calls + fd.tool_calls}

    def push_final(self, text: str) -> dict:
        """push + finish merged — the single place that defines how the
        flush combines with the last fragment (used by both the streaming
        finish branch and the unary path)."""
        parsed = self.push(text)
        fin = self.finish()
        return {
            "content": parsed["content"] + fin["content"],
            "reasoning": parsed["reasoning"] + fin["reasoning"],
            "tool_calls": parsed["tool_calls"] + fin["tool_calls"],
        }

    def delta_fields(self, parsed: dict) -> dict:
        """OpenAI chat delta fields for one parsed fragment."""
        delta = {}
        if parsed["content"]:
            delta["content"] = parsed["content"]
        if parsed["reasoning"]:
            delta["reasoning_content"] = parsed["reasoning"]
        if parsed["tool_calls"]:
            delta["tool_calls"] = [
                tc.to_openai(self.n_tool_calls + j)
                for j, tc in enumerate(parsed["tool_calls"])
            ]
            self.n_tool_calls += len(parsed["tool_calls"])
        return delta

    def map_finish(self, reason):
        return "tool_calls" if (self.n_tool_calls and reason == "stop") else reason


class HttpService:
    def __init__(self, manager: ModelManager, host: str = "0.0.0.0",
                 port: int = 8000, metrics: Optional[FrontendMetrics] = None,
                 audit=None, tls_cert: str = "", tls_key: str = "",
                 enabled_routes: Optional[set] = None, fleet=None,
                 reuse_port: bool = False,
                 sse_coalesce: Optional[bool] = None,
                 sse_legacy: Optional[bool] = None,
                 events: Optional[StepEventRecorder] = None):
        from ..llm.audit import AuditBus

        self.manager = manager
        # egress data plane knobs (frontend/egress.py has the semantics;
        # explicit args win over the environment)
        self.reuse_port = reuse_port  # SO_REUSEPORT: per-core sharding
        self.sse_coalesce = (env_bool("DYN_TPU_SSE_COALESCE")
                             if sse_coalesce is None else bool(sse_coalesce))
        self.sse_legacy = (env_bool("DYN_TPU_SSE_LEGACY")
                           if sse_legacy is None else bool(sse_legacy))
        self.sse_coalesce_max = env_int("DYN_TPU_SSE_COALESCE_MAX", 64)
        # per-stream egress summaries land on this ring (kind
        # "egress_stream"; /events.json dumps it)
        self.events = events if events is not None else StepEventRecorder.from_env()
        # optional planner.telemetry.FleetTelemetryWatcher: /fleet.json
        # then joins worker capacity snapshots to the local SLO windows
        self.fleet = fleet
        self.host = host
        self.port = port
        # TLS (reference service_v2.rs:222): both paths or neither
        if bool(tls_cert) != bool(tls_key):
            raise ValueError("tls_cert and tls_key must be given together")
        self._ssl = None
        if tls_cert:
            import ssl

            self._ssl = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            self._ssl.load_cert_chain(tls_cert, tls_key)
        self.metrics = metrics or FrontendMetrics()
        # request/response audit bus (DYN_AUDIT_SINK or explicit)
        self.audit = audit if audit is not None else AuditBus.from_env()
        self.app = web.Application()
        # per-route enable flags (reference service_v2.rs per-route
        # builder flags); health/live/metrics/models always serve.
        # ONE table drives both route registration and the OpenAPI doc
        # so the two can never drift.
        optional = {
            "chat": ("/v1/chat/completions", self.chat_completions,
                     "OpenAI chat completion (set 'stream' for SSE)"),
            "completions": ("/v1/completions", self.completions,
                            "OpenAI legacy completion"),
            "embeddings": ("/v1/embeddings", self.embeddings,
                           "OpenAI embeddings"),
            "responses": ("/v1/responses", self.responses,
                          "OpenAI responses"),
        }
        if enabled_routes is not None:
            unknown = set(enabled_routes) - set(optional)
            if unknown:
                raise ValueError(f"unknown routes {sorted(unknown)}; "
                                 f"known: {sorted(optional)}")
        enabled = {
            name: spec for name, spec in optional.items()
            if enabled_routes is None or name in enabled_routes
        }
        routes = [web.post(path, handler)
                  for path, handler, _ in enabled.values()]
        routes += [
            web.get("/v1/models", self.list_models),
            web.get("/health", self.health),
            web.get("/live", self.live),
            web.get("/metrics", self.prometheus),
            web.get("/fleet.json", self.fleet_json),
            web.get("/debug/tail.json", self.tail_json),
            web.get("/events.json", self.events_json),
            web.get("/openapi.json", self.openapi),
            web.post("/clear_kv_blocks", self.clear_kv_blocks),
        ]
        self.app.add_routes(routes)
        self._openapi_doc = self._build_openapi(enabled)
        self._runner: Optional[web.AppRunner] = None

    # -- lifecycle ----------------------------------------------------------- #

    async def start(self) -> "HttpService":
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port,
                           ssl_context=self._ssl,
                           reuse_port=self.reuse_port or None)
        await site.start()
        # resolve the real port when 0 was requested
        for s in site._server.sockets:  # noqa: SLF001
            self.port = s.getsockname()[1]
            break
        logger.info("http service on %s:%d", self.host, self.port)
        return self

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()
        leak_ledger.assert_balanced(f"frontend:{id(self):x}")

    # -- handlers ------------------------------------------------------------ #

    async def health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"status": "healthy", "models": self.manager.names()}
        )

    async def live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    @staticmethod
    def _build_openapi(enabled: dict) -> dict:
        """OpenAPI 3.1 description of the ENABLED surface (reference:
        http/service/openapi_docs.rs), built once from the same table
        that registered the routes so the document always matches what
        this process actually serves."""
        paths = {}
        for path, _handler, summary in enabled.values():
            paths[path] = {"post": {
                "summary": summary,
                "requestBody": {"content": {"application/json": {
                    "schema": {"type": "object"}}}},
                "responses": {"200": {"description": "completion"},
                              "400": {"description": "invalid request"},
                              "404": {"description": "unknown model"},
                              "503": {"description": "all workers busy"}},
            }}
        for path, summary in [
            ("/v1/models", "list served models"),
            ("/health", "aggregate health"),
            ("/live", "liveness"),
            ("/metrics", "Prometheus exposition"),
            ("/fleet.json", "live SLO windows + fleet capacity snapshots"),
            ("/debug/tail.json", "N worst windowed requests with trace "
                                 "ids + bottleneck classes"),
            ("/events.json", "egress step-event ring dump"),
            ("/openapi.json", "this document"),
        ]:
            paths[path] = {"get": {
                "summary": summary,
                "responses": {"200": {"description": "ok"}},
            }}
        paths["/clear_kv_blocks"] = {"post": {
            "summary": "evict every model's cached KV blocks",
            "responses": {"200": {"description": "pages cleared per model"}},
        }}
        return {
            "openapi": "3.1.0",
            "info": {"title": "dynamo_tpu frontend", "version": "0.1"},
            "paths": paths,
        }

    async def openapi(self, request: web.Request) -> web.Response:
        return web.json_response(self._openapi_doc)

    async def events_json(self, request: web.Request) -> web.Response:
        """Egress step-event ring: one `egress_stream` event per served
        stream (frames/deltas/coalesced/bytes), same dump schema as the
        worker's engine ring (docs/observability.md).  `?since_ns=` (the
        `watermark_ns` of a previous dump) returns only newer events —
        pollers fetch deltas instead of the whole ring each scrape."""
        since = request.query.get("since_ns")
        try:
            since_ns = int(since) if since is not None else None
        except ValueError:
            return _error_response(400, f"bad since_ns {since!r}")
        return web.json_response(self.events.dump(since_ns=since_ns))

    async def tail_json(self, request: web.Request) -> web.Response:
        """Tail forensics: per-model N worst requests in the live SLO
        window, each a waterfall summary with `trace_id` + `bottleneck`
        (docs/observability.md "Tail forensics" documents the schema)."""
        try:
            n = max(1, min(int(request.query.get("n", 10)), 100))
        except ValueError:
            return _error_response(400,
                                   f"bad n {request.query.get('n')!r}")
        return web.json_response({
            "ts": time.time(),
            "window_s": self.metrics.slo.window_s,
            "models": self.metrics.slo.tail(n),
        })

    async def prometheus(self, request: web.Request) -> web.Response:
        # content negotiation: OpenMetrics carries histogram exemplars
        # (`# {trace_id=...}`); the classic text format stays the
        # default so existing scrapers see an unchanged surface
        accept = request.headers.get("Accept", "")
        if "openmetrics" in accept:
            return web.Response(
                body=self.metrics.exposition(openmetrics=True),
                content_type="application/openmetrics-text",
            )
        return web.Response(
            body=self.metrics.exposition(),
            content_type="text/plain",
        )

    async def fleet_json(self, request: web.Request) -> web.Response:
        """Debug surface for the live telemetry plane: this frontend's
        per-model SLO windows (`frontend/slo.py`: a request meets its SLO
        iff TTFT and mean ITL are both under the target) plus, when a fleet watcher is attached, the joined
        worker capacity snapshots and online knee estimates
        (docs/observability.md documents the schema)."""
        body = {
            "ts": time.time(),
            "models": self.metrics.slo.snapshot(),
        }
        if self.fleet is not None:
            try:
                body["fleet"] = self.fleet.snapshot().to_dict()
            except Exception as e:  # noqa: BLE001 — debug surface
                body["fleet"] = {"error": repr(e)}
        return web.json_response(body)

    async def list_models(self, request: web.Request) -> web.Response:
        now = int(time.time())
        data = [
            {"id": name, "object": "model", "created": now, "owned_by": "dynamo-tpu"}
            for name in self.manager.names()
        ]
        return web.json_response({"object": "list", "data": data})

    async def clear_kv_blocks(self, request: web.Request) -> web.Response:
        results = {}
        for name in self.manager.names():
            entry = self.manager.get(name)
            try:
                async for out in entry.route(
                    {"control": "clear_kv_blocks"}, Context()
                ):
                    results[name] = out
                    break
            except (ServiceUnavailable, RemoteStreamError) as e:
                results[name] = {"error": str(e)}
        return web.json_response(results)

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve(request, kind="chat")

    async def completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve(request, kind="completion")

    async def embeddings(self, request: web.Request) -> web.Response:
        """OpenAI /v1/embeddings (reference openai.rs:434)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error_response(400, "invalid JSON body")
        model_name = body.get("model", "")
        entry = self.manager.get(model_name)
        if entry is None:
            self.metrics.requests.labels(model_name or "?", "embedding", "404").inc()
            return _error_response(
                404, f"model '{model_name}' not found", code="model_not_found"
            )
        if not entry.mdc.supports("embedding"):
            return _error_response(
                400, f"model '{model_name}' does not support embeddings"
            )
        try:
            preq = await asyncio.get_running_loop().run_in_executor(
                None, entry.preprocessor.preprocess_embedding, body
            )
        except RequestError as e:
            self.metrics.requests.labels(model_name, "embedding", "400").inc()
            return _error_response(400, str(e))
        try:
            result = None
            async for out in entry.route(preq, Context()):
                result = out
                break
        except ServiceUnavailable as e:
            self.metrics.requests.labels(model_name, "embedding", "503").inc()
            return _error_response(503, str(e))
        except RemoteStreamError as e:
            self.metrics.requests.labels(model_name, "embedding", "502").inc()
            return _error_response(502, str(e))
        if not result or result.get("error"):
            self.metrics.requests.labels(model_name, "embedding", "500").inc()
            return _error_response(
                500, (result or {}).get("error", "embedding failed")
            )
        self.metrics.requests.labels(model_name, "embedding", "200").inc()
        data = [
            {"object": "embedding", "index": i, "embedding": vec}
            for i, vec in enumerate(result.get("embeddings", []))
        ]
        ptoks = int(result.get("prompt_tokens", 0))
        return web.json_response({
            "object": "list",
            "data": data,
            "model": model_name,
            "usage": {"prompt_tokens": ptoks, "total_tokens": ptoks},
        })

    async def responses(self, request: web.Request) -> web.StreamResponse:
        """OpenAI /v1/responses (reference openai.rs:767): adapt the
        Responses request onto the chat pipeline."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error_response(400, "invalid JSON body")
        messages = []
        if body.get("instructions"):
            messages.append({"role": "system", "content": body["instructions"]})
        inp = body.get("input")
        if isinstance(inp, str):
            messages.append({"role": "user", "content": inp})
        elif isinstance(inp, list):
            for item in inp:
                if isinstance(item, dict) and item.get("type") in (None, "message"):
                    content = item.get("content", "")
                    if isinstance(content, list):
                        # Responses content parts use input_text/output_text;
                        # map onto the chat template's plain-text parts
                        content = [
                            {"type": "text", "text": p.get("text", "")}
                            if isinstance(p, dict)
                            and p.get("type") in ("input_text", "output_text")
                            else p
                            for p in content
                        ]
                    messages.append({
                        "role": item.get("role", "user"),
                        "content": content,
                    })
        if not messages:
            return _error_response(400, "'input' is required")
        chat_body = {
            "model": body.get("model", ""),
            "messages": messages,
            "stream": False,
            "temperature": body.get("temperature"),
            "top_p": body.get("top_p"),
            "max_tokens": body.get("max_output_tokens"),
        }
        model_name = chat_body["model"]
        entry = self.manager.get(model_name)
        if entry is None:
            return _error_response(
                404, f"model '{model_name}' not found", code="model_not_found"
            )
        try:
            preq = await asyncio.get_running_loop().run_in_executor(
                None, entry.preprocessor.preprocess_chat, chat_body
            )
        except RequestError as e:
            return _error_response(400, str(e))
        try:
            choice = await self._collect_choice(entry, preq, Context())
        except ServiceUnavailable as e:
            self.metrics.requests.labels(model_name, "responses", "503").inc()
            return _error_response(503, str(e))
        except RemoteStreamError as e:
            self.metrics.requests.labels(model_name, "responses", "502").inc()
            return _error_response(502, str(e))
        if choice.get("error"):
            self.metrics.requests.labels(model_name, "responses", "500").inc()
            return _error_response(500, choice["error"])
        rid = "resp_" + uuid.uuid4().hex[:24]
        prompt_tokens = len(preq.get("token_ids", []))
        self.metrics.requests.labels(model_name, "responses", "200").inc()
        return web.json_response({
            "id": rid,
            "object": "response",
            "created_at": int(time.time()),
            "status": "completed",
            "model": model_name,
            "output": [{
                "type": "message",
                "id": "msg_" + uuid.uuid4().hex[:24],
                "role": "assistant",
                "status": "completed",
                "content": [{
                    "type": "output_text",
                    "text": choice["text"],
                    "annotations": [],
                }],
            }],
            "output_text": choice["text"],
            "usage": {
                "input_tokens": prompt_tokens,
                "output_tokens": choice["token_count"],
                "total_tokens": prompt_tokens + choice["token_count"],
            },
        })

    # -- core serving path --------------------------------------------------- #

    async def _serve(self, request: web.Request, kind: str) -> web.StreamResponse:
        # every HTTP request gets a trace; x-request-id joins an existing
        # one (propagated to workers via wire-frame headers); the span
        # lands in the DYN_OTEL_FILE sink when configured
        from ..runtime.tracing import new_trace, set_trace, span

        set_trace(new_trace(request.headers.get("x-request-id")))
        with span(f"http.{kind}", path=request.path):
            return await self._serve_inner(request, kind)

    async def _serve_inner(self, request: web.Request, kind: str) -> web.StreamResponse:
        t0 = time.monotonic()
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error_response(400, "invalid JSON body")
        model_name = body.get("model", "")
        entry = self.manager.get(model_name)
        if entry is None:
            self.metrics.requests.labels(model_name or "?", kind, "404").inc()
            return _error_response(
                404, f"model '{model_name}' not found", code="model_not_found"
            )
        required = "chat" if kind == "chat" else "completions"
        if not entry.mdc.supports(required):
            return _error_response(
                400, f"model '{model_name}' does not support {required}"
            )
        from ..runtime.compute import run_compute
        from ..runtime.tracing import span

        try:
            # the preprocessor hop (template render + tokenize) gets its
            # own span under http.* so prompt-side TTFT cost is visible
            with span("frontend.preprocess", model=model_name, kind=kind):
                if kind == "chat":
                    preprocessed = await run_compute(
                        entry.preprocessor.preprocess_chat, body
                    )
                else:
                    preprocessed = await run_compute(
                        entry.preprocessor.preprocess_completion, body
                    )
        except RequestError as e:
            self.metrics.requests.labels(model_name, kind, "400").inc()
            return _error_response(400, str(e))

        n = preprocessed["sampling_options"].get("n", 1)
        rid = ("chatcmpl-" if kind == "chat" else "cmpl-") + uuid.uuid4().hex[:24]
        streaming = bool(body.get("stream", False))
        if self.audit is not None:
            self.audit.request(rid, model_name, kind, body)
        # shed 429s count toward offered load (observe_start) but are
        # never scored as window failures — overload control refusing
        # work cleanly is not a latency breach (docs/overload_control.md)
        self.metrics.slo.observe_start(
            model_name, priority=preprocessed.get("priority"))
        self.metrics.inflight.labels(model_name).inc()
        try:
            if streaming:
                return await self._stream_response(
                    request, entry, preprocessed, n, rid, kind, model_name, t0
                )
            return await self._unary_response(
                entry, preprocessed, n, rid, kind, model_name, t0
            )
        finally:
            self.metrics.inflight.labels(model_name).dec()

    def _observe_slo_failure(self, model_name, preprocessed,
                             output_tokens=0):
        """Score a FAILED/abandoned request into the live SLO window:
        never SLO-met (infinite latency), delivered tokens attained-only.
        The requests clients saw fail are the ones that must drag
        slo_met down during incidents — shared by every error path so
        the failure scoring can't drift between them.  Overload SHEDS do
        not come through here: a clean 429 is load control working, not
        a latency breach (docs/overload_control.md)."""
        self.metrics.slo.observe(
            model_name, float("inf"), float("inf"), output_tokens,
            prompt_tokens=len(preprocessed.get("token_ids") or []),
            priority=preprocessed.get("priority"),
        )

    def _choice_requests(self, preprocessed, n):
        """n independent engine requests; explicit seeds offset per choice
        so n>1 with a seed still yields distinct-but-reproducible choices."""
        out = []
        for i in range(n):
            preq = {
                **preprocessed,
                "sampling_options": dict(preprocessed["sampling_options"]),
            }
            seed = preq["sampling_options"].get("seed")
            if seed is not None and i:
                preq["sampling_options"]["seed"] = seed + i
            out.append(preq)
        return out

    async def _stream_response(
        self, request, entry, preprocessed, n, rid, kind, model_name, t0
    ) -> web.StreamResponse:
        ntokens = 0
        t_first = t_last_tok = None
        status = "200"
        spec_seen: list = [None] * n  # last cumulative spec stats per choice
        contexts = [Context() for _ in range(n)]
        parsers = (
            [_ChoiceParsers(entry.mdc) for _ in range(n)]
            if kind == "chat" and _ChoiceParsers.active(entry.mdc) else None
        )
        queue: asyncio.Queue = asyncio.Queue()

        async def pump_choice(i, preq, ctx):
            try:
                async for out in entry.generate(preq, ctx):
                    await queue.put((i, out, None))
            except (ServiceUnavailable, RemoteStreamError) as e:
                await queue.put((i, None, e))
            finally:
                await queue.put((i, None, None))  # choice drained

        tasks = [
            leak_ledger.tracked_task(pump_choice(i, preq, ctx),
                                     owner="frontend.stream")
            for i, (preq, ctx) in enumerate(
                zip(self._choice_requests(preprocessed, n), contexts)
            )
        ]
        # Batch-class shed probe (docs/overload_control.md): an intake
        # shed is the FIRST thing the engine yields, so peek at the
        # queue before committing the 200/SSE preamble — a shed batch
        # stream becomes a real HTTP 429 + Retry-After instead of a
        # status-200 SSE error frame.  Interactive streams skip the
        # probe entirely (zero added latency); a probe that surfaces a
        # normal first delta just hands it to the drain loop below.
        first_item = None
        try:
            if preprocessed.get("priority") == "batch":
                try:
                    first_item = await asyncio.wait_for(
                        queue.get(), _SHED_PROBE_S)
                except asyncio.TimeoutError:
                    first_item = None
                shed = (first_item is not None
                        and first_item[1] is not None
                        and first_item[1].get("finish_reason") == "error"
                        and _shed_error(first_item[1].get("error")))
                if shed:
                    for ctx in contexts:
                        ctx.kill()
                    for t in tasks:
                        t.cancel()
                    await asyncio.gather(*tasks, return_exceptions=True)
                    self.metrics.requests.labels(
                        model_name, kind, "429").inc()
                    self.metrics.shed.labels(model_name, "batch").inc()
                    if self.audit is not None:
                        self.audit.response(rid, model_name, kind, "429")
                    return _shed_response(shed)
            resp = web.StreamResponse(
                status=200,
                headers={
                    "Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                    "Connection": "keep-alive",
                },
            )
            await resp.prepare(request)
        except BaseException:
            # prepare/probe failed with pumps already running: settle
            # them before propagating (leak-ledger task invariant)
            for ctx in contexts:
                ctx.kill()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        created = int(time.time())
        # egress writer (frontend/egress.py): frame building + write
        # batching live there; this loop does queue drain + IO only.
        # The legacy arm reproduces the pre-optimization writer (one
        # dict + json.dumps + resp.write per delta) for A/B benching.
        eg = StreamEgress(resp, coalesce=self.sse_coalesce,
                          coalesce_max=self.sse_coalesce_max)
        legacy = self.sse_legacy
        max_burst = 1 if legacy else _MAX_BURST
        templates: dict = {}  # choice index -> ChunkTemplate
        stamps: list = []     # delta arrival times (batch-observed later)
        ttft_attrs: list = []  # engine TTFT attributions (ditto)
        incidents: list = []   # engine/migration stalls riding deltas

        def process(item):
            """One queue item → frames/bookkeeping. No awaits: delivery
            work happens here; scoring/annotation is deferred to the
            post-stream accounting block."""
            nonlocal live, status, ntokens, t_first, t_last_tok
            i, out, err = item
            if err is not None:
                status = "502"
                eg.add_obj(_sse_error_chunk(rid, str(err)))
                return
            if out is None:
                live -= 1
                return
            if out.get("finish_reason") == "error":
                err = out.get("error", "engine error")
                if _shed_error(err):
                    # a deadline shed landing after the SSE preamble
                    # (queued batch stream expired): too late for a real
                    # 429 status line, but account it as a shed, not a
                    # server error
                    status = "429"
                    self.metrics.shed.labels(
                        model_name, preprocessed.get("priority") or "batch"
                    ).inc()
                else:
                    status = "500"
                eg.add_obj(_sse_error_chunk(rid, err))
                return
            now = time.monotonic()
            stamps.append(now)
            ids = out.get("token_ids")
            if ids:
                # SLO scoring keys off TOKEN-bearing deltas only —
                # bench's definition; a token-less finish/role delta
                # must not make a zero-token stream look served
                t_last_tok = now
                if t_first is None:
                    t_first = now
                ntokens += len(ids)
            spec = out.get("spec")
            if spec:  # cumulative: the last delta seen carries totals
                spec_seen[i] = spec
            attr = out.get("ttft")
            if attr:  # one-shot, first-token delta only
                ttft_attrs.append(attr)
            inc = out.get("incidents")
            if inc:  # preempt/onboard/migration stalls (waterfall input)
                incidents.extend(inc)
            finish = out.get("finish_reason")
            if parsers is not None:
                if finish:
                    parsed = parsers[i].push_final(out.get("text", ""))
                else:
                    parsed = parsers[i].push(out.get("text", ""))
                delta = parsers[i].delta_fields(parsed)
                eg.add_obj(_make_chunk(
                    rid, kind, model_name, created, {**out, "text": ""},
                    parsers[i].map_finish(finish),
                    index=i, entry=entry, delta_override=delta,
                ))
                return
            if not legacy and finish is None and not out.get("log_probs"):
                # fast path: splice the text into the pre-serialized
                # skeleton — byte-identical to the json.dumps frame
                text = out.get("text", "")
                # chat deltas with EMPTY text serialize as `delta: {}`,
                # a different shape the skeleton can't splice
                if text or kind != "chat":
                    tmpl = templates.get(i)
                    if tmpl is None:
                        tmpl = templates[i] = ChunkTemplate(_make_chunk(
                            rid, kind, model_name, created,
                            {"text": CONTENT_SENTINEL}, None, index=i,
                        ))
                    eg.add_fast(tmpl, text)
                    return
            eg.add_obj(_make_chunk(rid, kind, model_name, created, out,
                                   finish, index=i, entry=entry))

        live = n
        # Keepalive keys off time-since-last-WRITE (a steady stream that
        # stops producing writes still pings on schedule, and proxies
        # stay open through long prefills — reference: SSE keep-alive
        # pings, openai.rs).  It's armed as ONE rearming loop.call_later
        # that drops a sentinel into the queue when the deadline passes:
        # the drain loop below stays a plain queue.get() with no
        # per-delta wait_for timer churn on the delivery path.
        loop = asyncio.get_running_loop()
        ka_handle = None

        def rearm_keepalive():
            nonlocal ka_handle
            wait = SSE_KEEPALIVE_S - (time.monotonic() - eg.last_write)
            if wait <= 0:
                queue.put_nowait(_KEEPALIVE)
                wait = SSE_KEEPALIVE_S
            ka_handle = loop.call_later(wait, rearm_keepalive)

        ka_handle = loop.call_later(SSE_KEEPALIVE_S, rearm_keepalive)
        try:
            if first_item is not None:  # delta the shed probe pulled
                process(first_item)
                await eg.flush()
            while live:
                item = await queue.get()
                if item is _KEEPALIVE:
                    if (time.monotonic() - eg.last_write
                            >= SSE_KEEPALIVE_S):
                        await eg.ping()
                    continue
                process(item)
                depth = queue.qsize()
                if depth and max_burst > 1:
                    # the pumps outran the writer: drain the backlog in
                    # one burst → ONE resp.write (and, when enabled,
                    # coalesced same-choice frames)
                    eg.note_backpressure(depth)
                    for _ in range(min(depth, max_burst - 1)):
                        it = queue.get_nowait()
                        if it is not _KEEPALIVE:
                            process(it)
                await eg.flush()
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            logger.info("client disconnected; killing %d choice(s)", n)
            for ctx in contexts:
                ctx.kill()
            self._observe_slo_failure(model_name, preprocessed, ntokens)
            if self.audit is not None:
                self.audit.response(rid, model_name, kind, "disconnected")
            raise
        finally:
            ka_handle.cancel()
            for t in tasks:
                t.cancel()
            # settle before returning: a cancelled-but-pending pump must
            # not outlive its request (or the loop, at server shutdown)
            await asyncio.gather(*tasks, return_exceptions=True)
            # accounting moved OFF the delivery path: per-delta latency
            # observes, TTFT attribution, egress counters and the ring
            # event all land here in one post-stream batch (runs on the
            # disconnect path too, so partial streams still count)
            from ..runtime.tracing import current_trace

            _tr = current_trace()
            trace_id = _tr.trace_id if _tr is not None else ""
            ex = {"trace_id": trace_id[:64]} if trace_id else None
            if stamps:
                self.metrics.ttft.labels(model_name).observe(
                    stamps[0] - t0, ex)
                observe_itl = self.metrics.itl.labels(model_name).observe
                prev = stamps[0]
                # one ITL exemplar per stream, on its LARGEST gap — the
                # observation a tail bucket would surface anyway
                worst_gap = max((b - a for a, b in zip(stamps, stamps[1:])),
                                default=None)
                tagged = False
                for t_delta in stamps[1:]:
                    gap = t_delta - prev
                    if not tagged and gap == worst_gap:
                        observe_itl(gap, ex)
                        tagged = True
                    else:
                        observe_itl(gap)
                    prev = t_delta
            for attr in ttft_attrs:
                self.metrics.observe_ttft_attr(model_name, attr)
            self.metrics.observe_egress(model_name, eg)
            self.events.record(
                "egress_stream", model=model_name, frames=eg.frames,
                deltas=eg.deltas, coalesced=eg.coalesced,
                writes=eg.writes, bytes=eg.bytes_out,
            )
        self.metrics.requests.labels(model_name, kind, status).inc()
        self.metrics.output_tokens.labels(model_name).inc(ntokens)
        t_end = time.monotonic()
        self.metrics.duration.labels(model_name).observe(t_end - t0)
        # tail forensics: assemble the request's stage waterfall (post-
        # stream, off the delivery path) — it becomes the SLO window's
        # exemplar so /debug/tail.json can answer "why was this slow"
        from .waterfall import build_waterfall

        waterfall = build_waterfall(
            trace_id=trace_id, model=model_name, t0=t0, t_end=t_end,
            t_first=t_first, t_last_tok=t_last_tok,
            ttft_attr=ttft_attrs[0] if ttft_attrs else None,
            incidents=incidents, ntokens=ntokens, status=int(status),
        )
        # live SLO window: the whole HTTP request is one accounting unit
        # (the per-request TTFT + mean-ITL predicate of frontend/slo.py,
        # applied post-hoc in slo.observe_stream — never on the delivery
        # loop). A stream the client saw FAIL can never be SLO-met.
        if status != "429":  # sheds are offered-only, never window failures
            self.metrics.slo.observe_stream(
                model_name, t0=t0, t_first=t_first, t_last_tok=t_last_tok,
                ntokens=ntokens, n_choices=n, errored=status != "200",
                prompt_tokens=len(preprocessed.get("token_ids") or []),
                priority=preprocessed.get("priority"),
                exemplar=waterfall,
            )
        for spec in spec_seen:
            if spec:  # a stop string may cut the stream before the
                self.metrics.observe_spec(model_name, spec)  # final delta
        if self.audit is not None:
            self.audit.response(
                rid, model_name, kind, status,
                usage={"completion_tokens": ntokens},
            )
        await resp.write_eof()
        return resp

    async def _collect_choice(self, entry, preq, context) -> Dict[str, Any]:
        """Drain one engine stream into an aggregated choice."""
        text_parts = []
        token_ids: list = []
        logprobs: list = []
        tops: list = []
        finish_reason = None
        spec = None
        ttft = None
        incidents: list = []
        async for out in entry.generate(preq, context):
            if out.get("finish_reason") == "error":
                return {"error": out.get("error", "engine error")}
            text_parts.append(out.get("text", ""))
            token_ids.extend(out.get("token_ids", []))
            logprobs.extend(out.get("log_probs", []))
            tops.extend(out.get("top_logprobs", []))
            spec = out.get("spec") or spec
            ttft = out.get("ttft") or ttft
            inc = out.get("incidents")
            if inc:
                incidents.extend(inc)
            finish_reason = out.get("finish_reason") or finish_reason
        return {
            "text": "".join(text_parts),
            "token_ids": token_ids,
            "token_count": len(token_ids),
            "log_probs": logprobs,
            "top_logprobs": tops,
            "finish_reason": finish_reason or "stop",
            "spec": spec,
            "ttft": ttft,
            "incidents": incidents,
        }

    async def _unary_response(
        self, entry, preprocessed, n, rid, kind, model_name, t0
    ) -> web.Response:
        contexts = [Context() for _ in range(n)]
        tasks = [
            leak_ledger.tracked_task(self._collect_choice(entry, preq, ctx),
                                     owner="frontend.unary")
            for preq, ctx in zip(
                self._choice_requests(preprocessed, n), contexts
            )
        ]
        try:
            results = await asyncio.gather(*tasks)
        except asyncio.CancelledError:
            # unary client disconnect: same invariant as streaming
            for ctx in contexts:
                ctx.kill()
            for t in tasks:
                t.cancel()
            self._observe_slo_failure(model_name, preprocessed)
            if self.audit is not None:
                self.audit.response(rid, model_name, kind, "disconnected")
            raise
        except (ServiceUnavailable, RemoteStreamError) as e:
            # one choice failed: stop its siblings instead of letting them
            # decode unattended to max_tokens
            for ctx in contexts:
                ctx.kill()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            status = "503" if isinstance(e, ServiceUnavailable) else "502"
            self.metrics.requests.labels(model_name, kind, status).inc()
            self._observe_slo_failure(model_name, preprocessed)
            if self.audit is not None:
                self.audit.response(rid, model_name, kind, status)
            return _error_response(int(status), str(e))
        for r in results:
            if r.get("error"):
                shed = _shed_error(r["error"])
                status = "429" if shed else "500"
                self.metrics.requests.labels(model_name, kind, status).inc()
                if shed:
                    # shed hygiene: counted in offered load (observe_start
                    # already ran) and on its own counter, but NOT scored
                    # as an SLO-window failure — the 429 is load control
                    # working, not a breach
                    self.metrics.shed.labels(
                        model_name, preprocessed.get("priority") or "batch"
                    ).inc()
                else:
                    self._observe_slo_failure(model_name, preprocessed)
                if self.audit is not None:
                    self.audit.response(rid, model_name, kind, status)
                if shed:
                    return _shed_response(shed)
                return _error_response(500, r["error"])
        created = int(time.time())
        prompt_tokens = len(preprocessed.get("token_ids", []))
        for r in results:
            if r.get("spec"):
                self.metrics.observe_spec(model_name, r["spec"])
            if r.get("ttft"):
                self.metrics.observe_ttft_attr(model_name, r["ttft"])
        token_count = sum(r["token_count"] for r in results)
        usage = {
            "prompt_tokens": prompt_tokens,
            "completion_tokens": token_count,
            "total_tokens": prompt_tokens + token_count,
        }
        want_lp = preprocessed["sampling_options"].get("logprobs")
        parse = kind == "chat" and _ChoiceParsers.active(entry.mdc)
        choices = []
        for i, r in enumerate(results):
            if kind == "chat":
                message = {"role": "assistant", "content": r["text"]}
                finish = r["finish_reason"]
                if parse:
                    parsed = _ChoiceParsers(entry.mdc).push_final(r["text"])
                    content = parsed["content"]
                    reasoning = parsed["reasoning"]
                    calls = parsed["tool_calls"]
                    message = {"role": "assistant",
                               "content": content or (None if calls else "")}
                    if reasoning:
                        message["reasoning_content"] = reasoning
                    if calls:
                        message["tool_calls"] = [
                            tc.to_openai(j) for j, tc in enumerate(calls)
                        ]
                        if finish == "stop":
                            finish = "tool_calls"
                choice = {
                    "index": i,
                    "message": message,
                    "finish_reason": finish,
                }
                if want_lp:
                    choice["logprobs"] = _chat_logprobs(entry, r)
            else:
                choice = {
                    "index": i,
                    "text": r["text"],
                    "finish_reason": r["finish_reason"],
                }
                if want_lp:
                    choice["logprobs"] = _completions_logprobs(entry, r)
            choices.append(choice)
        payload = {
            "id": rid,
            "object": "chat.completion" if kind == "chat" else "text_completion",
            "created": created,
            "model": model_name,
            "choices": choices,
            "usage": usage,
        }
        # live SLO window: unary delivery has no observable per-token
        # timing, so TTFT comes from the engine's attribution when it
        # rode the stream and the remainder amortizes as per-STREAM ITL
        # (choices run concurrently — divide by one choice's share of
        # the tokens, same as the streaming path)
        t_end = time.monotonic()
        dur_ms = (t_end - t0) * 1e3
        ttft_attr = next((r["ttft"] for r in results if r.get("ttft")), None)
        ttft_ms = (sum(v for v in ttft_attr.values()
                       if isinstance(v, (int, float)))
                   if ttft_attr else dur_ms)
        from ..runtime.tracing import current_trace

        from .waterfall import build_waterfall

        _tr = current_trace()
        trace_id = _tr.trace_id if _tr is not None else ""
        waterfall = build_waterfall(
            trace_id=trace_id, model=model_name, t0=t0, t_end=t_end,
            t_first=(t0 + min(ttft_ms, dur_ms) / 1e3
                     if token_count else None),
            t_last_tok=t_end if token_count else None,
            ttft_attr=ttft_attr,
            incidents=[i for r in results
                       for i in (r.get("incidents") or [])],
            ntokens=token_count, status=200,
        )
        self.metrics.slo.observe(
            model_name,
            ttft_ms=min(ttft_ms, dur_ms),
            itl_ms=(max(dur_ms - ttft_ms, 0.0)
                    / max(token_count / max(n, 1) - 1, 1)
                    if token_count else float("inf")),
            output_tokens=token_count,
            prompt_tokens=prompt_tokens,
            priority=preprocessed.get("priority"),
            exemplar=waterfall,
        )
        self.metrics.requests.labels(model_name, kind, "200").inc()
        self.metrics.output_tokens.labels(model_name).inc(token_count)
        self.metrics.duration.labels(model_name).observe(time.monotonic() - t0)
        if self.audit is not None:
            self.audit.response(
                rid, model_name, kind, "200", usage=usage,
                finish_reasons=[c.get("finish_reason") for c in choices],
            )
        return web.json_response(payload)


def _token_str(entry, tid: int) -> str:
    try:
        return entry.tokenizer.decode([tid])
    except Exception:  # noqa: BLE001
        return ""


def _chat_logprobs(entry, r) -> Dict[str, Any]:
    """OpenAI chat `logprobs` shape: {"content": [{token, logprob, bytes,
    top_logprobs: [...]}]} (reference perf/logprobs.rs + openai.rs)."""
    content = []
    tops = r.get("top_logprobs") or []
    for j, tid in enumerate(r["token_ids"]):
        lp = r["log_probs"][j] if j < len(r.get("log_probs", [])) else None
        tok = _token_str(entry, tid)
        item = {
            "token": tok,
            "logprob": lp,
            "bytes": list(tok.encode()),
        }
        if j < len(tops) and tops[j]:
            item["top_logprobs"] = [
                {
                    "token": _token_str(entry, t),
                    "logprob": l,
                    "bytes": list(_token_str(entry, t).encode()),
                }
                for t, l in tops[j]
            ]
        content.append(item)
    return {"content": content}


def _completions_logprobs(entry, r) -> Dict[str, Any]:
    """Legacy completions `logprobs` shape: parallel arrays + top-k maps."""
    tokens = [_token_str(entry, t) for t in r["token_ids"]]
    offsets = []
    pos = 0
    for t in tokens:
        offsets.append(pos)
        pos += len(t)
    tops = r.get("top_logprobs") or []
    top_maps = []
    for j in range(len(tokens)):
        if j < len(tops) and tops[j]:
            top_maps.append(
                {_token_str(entry, t): l for t, l in tops[j]}
            )
        else:
            top_maps.append(None)
    return {
        "tokens": tokens,
        "token_logprobs": list(r.get("log_probs", [])),
        "top_logprobs": top_maps,
        "text_offset": offsets,
    }


def _make_chunk(rid, kind, model, created, out, finish_reason, index=0,
                entry=None, delta_override=None):
    want_lp = entry is not None and out.get("log_probs")
    lp_args = {
        "token_ids": out.get("token_ids", []),
        "log_probs": out.get("log_probs", []),
        "top_logprobs": out.get("top_logprobs", []),
    }
    if kind == "chat":
        if delta_override is not None:
            delta = delta_override
        else:
            delta = {"content": out.get("text", "")} if out.get("text") else {}
        choice = {"index": index, "delta": delta, "finish_reason": finish_reason}
        if want_lp:
            choice["logprobs"] = _chat_logprobs(entry, lp_args)
        return {
            "id": rid,
            "object": "chat.completion.chunk",
            "created": created,
            "model": model,
            "choices": [choice],
        }
    choice = {"index": index, "text": out.get("text", ""),
              "finish_reason": finish_reason}
    if want_lp:
        choice["logprobs"] = _completions_logprobs(entry, lp_args)
    return {
        "id": rid,
        "object": "text_completion",
        "created": created,
        "model": model,
        "choices": [choice],
    }


def _sse_error_chunk(rid, message):
    return {"id": rid, "error": {"message": message, "type": "internal_error"}}


async def _write_sse(resp, obj) -> None:
    """Serialize + write one SSE object frame directly.

    The single seam for any write site outside the batched StreamEgress
    path (the two error branches used to carry near-duplicate f-string
    serializations); both paths produce bytes via egress.sse_frame, so
    the wire format is defined in exactly one place."""
    await resp.write(sse_frame(obj))


def _error_response(status: int, message: str, code: str = "invalid_request_error"):
    return web.json_response(
        {"error": {"message": message, "type": code, "code": status}},
        status=status,
    )


def _shed_error(err):
    """The structured overload-shed dict the engine attaches to a shed
    stream ({code: "overloaded", message, retry_after_s} — engine intake
    shed or queued-deadline expiry, docs/overload_control.md), else None."""
    if isinstance(err, dict) and err.get("code") == "overloaded":
        return err
    return None


def _shed_response(err: dict) -> web.Response:
    """HTTP 429 for an overload shed: Retry-After header plus the same
    hint in the structured body so clients can back off without parsing
    headers."""
    retry = max(1, int(err.get("retry_after_s") or 1))
    return web.json_response(
        {"error": {"message": err.get("message", "overloaded"),
                   "type": "overloaded", "code": 429,
                   "retry_after_s": retry}},
        status=429,
        headers={"Retry-After": str(retry)},
    )
