"""System status server: /health, /live, /metrics per process.

Reference: /root/reference/lib/runtime/src/system_status_server.rs:74.
"""

from __future__ import annotations

import asyncio
import json
from typing import Awaitable, Callable

from aiohttp import web

from .metrics import MetricsScope


class SystemStatusServer:
    def __init__(
        self,
        metrics: MetricsScope | None = None,
        health_fn: Callable[[], Awaitable[dict]] | None = None,
        stats_fn: Callable[[], dict] | None = None,
        events_fn: Callable[..., dict] | None = None,
        xprof_fn: Callable[[int, str | None], str | None] | None = None,
        host: str = "0.0.0.0",
        port: int = 0,
    ):
        self.metrics = metrics
        self.health_fn = health_fn
        self.stats_fn = stats_fn
        self.events_fn = events_fn
        self.xprof_fn = xprof_fn
        self.host = host
        self.port = port
        self._runner: web.AppRunner | None = None

    async def start(self) -> "SystemStatusServer":
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/metrics.json", self._metrics_json)
        app.router.add_get("/events.json", self._events_json)
        app.router.add_post("/debug/xprof", self._debug_xprof)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
        return self

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    async def _health(self, request: web.Request) -> web.Response:
        body = {"status": "healthy"}
        if self.health_fn:
            body = await self.health_fn()
        status = 200 if body.get("status") in ("healthy", "ready") else 503
        return web.Response(
            text=json.dumps(body), status=status, content_type="application/json"
        )

    async def _live(self, request: web.Request) -> web.Response:
        return web.Response(
            text=json.dumps({"status": "live"}), content_type="application/json"
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        data = self.metrics.render() if self.metrics else b""
        return web.Response(body=data, content_type="text/plain")

    async def _metrics_json(self, request: web.Request) -> web.Response:
        """Component stats as JSON (engine ForwardPassMetrics incl. KV
        transfer counters on disagg decode workers)."""
        body = self.stats_fn() if self.stats_fn else {}
        return web.Response(
            text=json.dumps(body), content_type="application/json"
        )

    async def _events_json(self, request: web.Request) -> web.Response:
        """Engine step-event ring dump (runtime.events.StepEventRecorder
        — the worker debug endpoint `scripts/trace_stack.py` and the
        timeline merger read; {} when no recorder is wired).
        `?since_ns=` (the previous dump's `watermark_ns`) returns only
        newer events so pollers fetch deltas, not the whole ring."""
        since = request.query.get("since_ns")
        try:
            since_ns = int(since) if since is not None else None
        except ValueError:
            return web.Response(
                text=json.dumps({"error": f"bad since_ns {since!r}"}),
                status=400, content_type="application/json",
            )
        body = {}
        if self.events_fn:
            if since_ns is None:
                body = self.events_fn()
            else:
                try:
                    body = self.events_fn(since_ns)
                except TypeError:
                    # cursor-unaware events_fn (older wiring): serve the
                    # full dump rather than failing the poller
                    body = self.events_fn()
        return web.Response(
            text=json.dumps(body), content_type="application/json"
        )

    async def _debug_xprof(self, request: web.Request) -> web.Response:
        """Arm a profiler capture of the engine's next `?steps=N` steps
        (`&dir=` overrides the directory) while serving; answers with the
        directory the `.xplane.pb` lands in, 409 while a capture is armed
        or running, 404 when the process has no engine to trace."""
        def reply(status, **body):
            return web.Response(text=json.dumps(body), status=status,
                                content_type="application/json")

        if self.xprof_fn is None:
            return reply(404, error="no engine to trace in this process")
        try:
            steps = int(request.query.get("steps", ""))
        except ValueError:
            steps = 0
        if steps <= 0:
            return reply(400, error="steps=N (a positive integer) is needed")
        directory = self.xprof_fn(steps, request.query.get("dir"))
        if directory is None:
            return reply(409, error="a capture is armed or running already")
        return reply(200, steps=steps, dir=directory)
