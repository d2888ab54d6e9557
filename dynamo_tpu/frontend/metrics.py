"""Frontend Prometheus metrics (reference `dynamo_frontend_*` family,
/root/reference/lib/llm/src/http/service/metrics.rs)."""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

_TTFT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0
)
_ITL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)


class FrontendMetrics:
    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        self.requests = Counter(
            "dynamo_frontend_requests_total",
            "Completed HTTP requests",
            ["model", "kind", "status"],
            registry=self.registry,
        )
        self.inflight = Gauge(
            "dynamo_frontend_inflight_requests",
            "Requests currently being served",
            ["model"],
            registry=self.registry,
        )
        self.ttft = Histogram(
            "dynamo_frontend_time_to_first_token_seconds",
            "Time to first token",
            ["model"],
            buckets=_TTFT_BUCKETS,
            registry=self.registry,
        )
        self.itl = Histogram(
            "dynamo_frontend_inter_token_latency_seconds",
            "Inter-token latency",
            ["model"],
            buckets=_ITL_BUCKETS,
            registry=self.registry,
        )
        # TTFT attribution (block ladder, docs/adaptive_dispatch.md):
        # the engine splits each request's TTFT into block-wait (the
        # in-flight decode block the pump was committed to at arrival),
        # queue-wait (scheduler admission) and prefill, and ships the
        # split on the first delivered delta — so a TTFT regression is
        # attributable from /metrics alone, not inferred
        self.ttft_block_wait = Histogram(
            "dynamo_frontend_ttft_block_wait_seconds",
            "TTFT share spent behind the in-flight decode block",
            ["model"],
            buckets=_TTFT_BUCKETS,
            registry=self.registry,
        )
        self.ttft_queue_wait = Histogram(
            "dynamo_frontend_ttft_queue_wait_seconds",
            "TTFT share spent waiting for scheduler admission",
            ["model"],
            buckets=_TTFT_BUCKETS,
            registry=self.registry,
        )
        self.ttft_prefill = Histogram(
            "dynamo_frontend_ttft_prefill_seconds",
            "TTFT share spent prefilling the prompt",
            ["model"],
            buckets=_TTFT_BUCKETS,
            registry=self.registry,
        )
        self.duration = Histogram(
            "dynamo_frontend_request_duration_seconds",
            "Whole-request duration",
            ["model"],
            registry=self.registry,
        )
        self.output_tokens = Counter(
            "dynamo_frontend_output_tokens_total",
            "Generated tokens",
            ["model"],
            registry=self.registry,
        )
        # speculative decoding (cumulative per-request stats ride the
        # engine stream's deltas; the last one seen carries the totals,
        # even when a frontend-side stop string ends the stream early):
        # draft/accept counters plus a rolling per-model acceptance
        # rate over recent requests
        self.spec_draft_tokens = Counter(
            "dynamo_frontend_spec_draft_tokens",
            "Speculative draft tokens proposed",
            ["model"],
            registry=self.registry,
        )
        self.spec_accepted_tokens = Counter(
            "dynamo_frontend_spec_accepted_tokens",
            "Speculative draft tokens accepted",
            ["model"],
            registry=self.registry,
        )
        self.spec_acceptance_rate = Gauge(
            "dynamo_frontend_spec_acceptance_rate",
            "Rolling speculative acceptance rate (recent requests)",
            ["model"],
            registry=self.registry,
        )
        self._spec_windows: dict = {}  # model -> deque[(draft, accepted)]
        # fault tolerance: migration counters incremented straight from
        # migrating_stream (frontend/service.py wires the callback), and
        # per-endpoint worker health as published to the control plane by
        # each worker's HealthCheckManager (frontend/service.py
        # HealthWatcher keeps the gauge in sync)
        self.migrations = Counter(
            "dynamo_frontend_migrations_total",
            "Streams transparently re-issued to another worker",
            ["model"],
            registry=self.registry,
        )
        self.migration_exhausted = Counter(
            "dynamo_frontend_migration_exhausted_total",
            "Streams that hit the migration limit (client saw an error)",
            ["model"],
            registry=self.registry,
        )
        # overload control (docs/overload_control.md): batch-class
        # requests the engine shed (intake 429 or queued-deadline expiry)
        # — these count in offered_rps but are excluded from SLO-window
        # failure scoring; the client got a clean 429+Retry-After
        self.shed = Counter(
            "dynamo_frontend_requests_shed_total",
            "Requests shed by overload control (HTTP 429)",
            ["model", "priority"],
            registry=self.registry,
        )
        self.endpoint_health = Gauge(
            "dynamo_frontend_endpoint_healthy",
            "Worker-reported endpoint health (1 healthy, 0 unhealthy)",
            ["endpoint", "instance"],
            registry=self.registry,
        )
        # egress data plane (frontend/egress.py): per-stream counters
        # flushed in ONE post-stream batch by observe_egress — nothing
        # here rides the per-delta delivery path
        self.egress_frames = Counter(
            "dynamo_frontend_egress_frames_total",
            "SSE frames written (coalescing merges deltas into fewer)",
            ["model"],
            registry=self.registry,
        )
        self.egress_writes = Counter(
            "dynamo_frontend_egress_writes_total",
            "resp.write calls (a burst drain sends many frames per write)",
            ["model"],
            registry=self.registry,
        )
        self.egress_coalesced = Counter(
            "dynamo_frontend_egress_coalesced_deltas_total",
            "Token deltas merged into a preceding frame under backpressure",
            ["model"],
            registry=self.registry,
        )
        self.egress_backpressure = Counter(
            "dynamo_frontend_egress_backpressure_events_total",
            "Queue drains that began with deltas already backed up",
            ["model"],
            registry=self.registry,
        )
        self.egress_cpu = Counter(
            "dynamo_frontend_egress_cpu_seconds_total",
            "Frontend CPU spent building + writing SSE frames "
            "(divide by output tokens for per-token cost)",
            ["model"],
            registry=self.registry,
        )
        self.egress_bytes = Counter(
            "dynamo_frontend_egress_bytes_total",
            "SSE bytes written (frames + keepalive pings)",
            ["model"],
            registry=self.registry,
        )
        self.egress_queue_depth = Histogram(
            "dynamo_frontend_egress_queue_depth",
            "Write-queue backlog observed at each backpressure drain",
            ["model"],
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
            registry=self.registry,
        )
        # span-exporter visibility: a full OTLP push queue drops spans —
        # dynamo_tracing_spans_sent_total/_dropped_total make that loss a
        # counter on /metrics instead of a silent trace gap
        from ..runtime.metrics import TracingSpanCollector

        self.registry.register(TracingSpanCollector())
        # live SLO window (frontend/slo.py): per-request goodput/slo_met
        # accounting (SLO-met iff TTFT and mean ITL are under the target;
        # goodput counts SLO-met requests' tokens), exposed as gauges at
        # scrape time and published to the fleet telemetry plane
        from .slo import SLOAccountant, SLOWindowCollector

        self.slo = SLOAccountant(exemplars=True)
        self.registry.register(SLOWindowCollector(self.slo))
        # process-level CPU/fd/RSS (runtime/metrics.py): the saturation
        # story needs frontend CPU per token to be attributable against
        # whole-process burn from the same scrape
        from ..runtime.metrics import ProcessStatsCollector

        self.registry.register(ProcessStatsCollector())

    def observe_egress(self, model: str, eg) -> None:
        """Flush one stream's egress counters (a StreamEgress) — called
        once per stream from the post-stream accounting block."""
        self.egress_frames.labels(model).inc(eg.frames)
        if eg.writes:
            self.egress_writes.labels(model).inc(eg.writes)
        if eg.coalesced:
            self.egress_coalesced.labels(model).inc(eg.coalesced)
        if eg.backpressure_events:
            self.egress_backpressure.labels(model).inc(eg.backpressure_events)
        self.egress_cpu.labels(model).inc(eg.cpu_ns / 1e9)
        self.egress_bytes.labels(model).inc(eg.bytes_out)
        if eg.depth_samples:
            observe = self.egress_queue_depth.labels(model).observe
            for depth in eg.depth_samples:
                observe(depth)

    def observe_migration(self, model: str, event: str) -> None:
        """Account one migrating_stream event ('migrated'/'exhausted')."""
        if event == "exhausted":
            self.migration_exhausted.labels(model).inc()
        else:
            self.migrations.labels(model).inc()

    def set_endpoint_health(self, endpoint: str, instance: int,
                            healthy: bool | None) -> None:
        """Track (or forget, healthy=None) a worker endpoint's health."""
        if healthy is None:
            try:
                self.endpoint_health.remove(endpoint, str(instance))
            except KeyError:
                pass
            return
        self.endpoint_health.labels(endpoint, str(instance)).set(
            1.0 if healthy else 0.0
        )

    def observe_ttft_attr(self, model: str, ttft: dict) -> None:
        """Account one request's engine-side TTFT attribution ({
        block_wait_ms, queue_wait_ms, prefill_ms} — the one-shot dict
        riding the first-token delta)."""
        for hist, key in (
            (self.ttft_block_wait, "block_wait_ms"),
            (self.ttft_queue_wait, "queue_wait_ms"),
            (self.ttft_prefill, "prefill_ms"),
        ):
            v = ttft.get(key)
            if isinstance(v, (int, float)) and v >= 0:
                hist.labels(model).observe(v / 1e3)

    def observe_spec(self, model: str, spec: dict) -> None:
        """Account one request's speculative stats ({draft_tokens,
        accepted_tokens}) and refresh the rolling acceptance gauge."""
        from collections import deque

        draft = int(spec.get("draft_tokens", 0) or 0)
        accepted = int(spec.get("accepted_tokens", 0) or 0)
        if draft <= 0:
            return
        self.spec_draft_tokens.labels(model).inc(draft)
        self.spec_accepted_tokens.labels(model).inc(accepted)
        win = self._spec_windows.setdefault(model, deque(maxlen=256))
        win.append((draft, accepted))
        total = sum(d for d, _ in win)
        self.spec_acceptance_rate.labels(model).set(
            sum(a for _, a in win) / total if total else 0.0
        )

    def exposition(self, openmetrics: bool = False) -> bytes:
        """Render the registry; OpenMetrics format (content-negotiated
        by the /metrics handler) carries the histogram exemplars that
        the classic text format silently drops."""
        if openmetrics:
            from prometheus_client.openmetrics.exposition import (
                generate_latest as om_latest,
            )

            return om_latest(self.registry)
        return generate_latest(self.registry)
