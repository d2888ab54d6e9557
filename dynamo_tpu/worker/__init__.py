"""Worker glue: serve a JaxEngine (or any AsyncEngine) as a discovered,
routable model endpoint.

The analog of the reference's worker startup path
(/root/reference/components/src/dynamo/vllm/main.py:247 `init`:
create_service → endpoint → register_llm → serve_endpoint), with the engine
being first-party instead of vLLM.
"""

from __future__ import annotations

import logging
from typing import Any, AsyncIterator, Optional

from ..chaos.gate import gate_async_check
from ..engine import ForwardPassMetrics, JaxEngine
from ..frontend.service import register_llm
from ..llm import ModelDeploymentCard, RuntimeConfig
from ..runtime import Context, DistributedRuntime, ServedEndpoint

logger = logging.getLogger(__name__)


class DpRankEngine:
    """N independent engine replicas behind one endpoint — the engine
    data-parallel ranks of the reference (vLLM `data_parallel_size`
    with per-dp-rank KV events and `WorkerWithDpRank` routing,
    /root/reference/components/src/dynamo/vllm/main.py:120-143).

    Each rank has its own KV pool and scheduler; the KV router addresses
    (instance, dp_rank) via packed worker keys, and rank-less requests
    round-robin locally."""

    def __init__(self, engines):
        if not engines:
            raise ValueError("DpRankEngine needs at least one engine")
        self.engines = list(engines)
        self._rr = 0

    @property
    def dp_ranks(self) -> int:
        return len(self.engines)

    def _pick(self, request) -> Any:
        rank = request.get("dp_rank") if isinstance(request, dict) else None
        if rank is None:
            rank = self._rr % len(self.engines)
            self._rr += 1
        if not isinstance(rank, int) or not 0 <= rank < len(self.engines):
            raise ValueError(
                f"dp_rank {rank!r} outside [0, {len(self.engines)})"
            )
        return self.engines[rank]

    async def generate(self, request: Any, context: Optional[Context] = None
                       ) -> AsyncIterator[Any]:
        try:
            engine = self._pick(request)
        except ValueError as e:
            yield {"token_ids": [], "finish_reason": "error", "error": str(e)}
            return
        async for out in engine.generate(request, context):
            yield out

    async def embed(self, request: Any, context: Optional[Context] = None):
        try:
            engine = self._pick(request)
        except ValueError as e:  # structured error, like generate
            return {"error": str(e)}
        return await engine.embed(request, context)

    def metrics(self) -> ForwardPassMetrics:
        """Aggregate snapshot (per-rank states publish separately)."""
        per = [e.metrics() for e in self.engines]
        drafted = sum(m.spec_draft_tokens_total for m in per)
        agg = ForwardPassMetrics(
            active_seqs=sum(m.active_seqs for m in per),
            waiting_seqs=sum(m.waiting_seqs for m in per),
            kv_usage=sum(m.kv_usage for m in per) / len(per),
            kv_total_pages=sum(m.kv_total_pages for m in per),
            num_requests_total=sum(m.num_requests_total for m in per),
            spec_draft_tokens_total=drafted,
            spec_accepted_tokens_total=sum(
                m.spec_accepted_tokens_total for m in per
            ),
            spec_dispatches_total=sum(m.spec_dispatches_total for m in per),
            # lifetime ratio across ranks (the per-rank rolling windows
            # don't aggregate meaningfully)
            spec_acceptance_rate=(
                sum(m.spec_accepted_tokens_total for m in per) / drafted
                if drafted else 0.0
            ),
            ttft_block_wait_ms_total=sum(
                m.ttft_block_wait_ms_total for m in per
            ),
            ttft_queue_wait_ms_total=sum(
                m.ttft_queue_wait_ms_total for m in per
            ),
            ttft_prefill_ms_total=sum(m.ttft_prefill_ms_total for m in per),
            ttft_turn_wait_ms_total=sum(
                m.ttft_turn_wait_ms_total for m in per
            ),
            kv_pages_cached=sum(m.kv_pages_cached for m in per),
            kv_pages_free=sum(m.kv_pages_free for m in per),
            prefix_evictions_total=sum(
                m.prefix_evictions_total for m in per
            ),
            ttft_attributed_total=sum(m.ttft_attributed_total for m in per),
            decode_cc_blocks_total=sum(
                m.decode_cc_blocks_total for m in per
            ),
            decode_cc_chains_total=sum(
                m.decode_cc_chains_total for m in per
            ),
            # per-reason fall-out dict merges key-wise across ranks
            decode_cc_fallout_total={
                r: sum(m.decode_cc_fallout_total.get(r, 0) for m in per)
                for r in sorted({k for m in per
                                 for k in m.decode_cc_fallout_total})
            },
            # capacity gauges: occupancy of the FULLEST rank (admission
            # pins sequences to a rank, so the max is the binding
            # signal, same reasoning as kv_usage) and aggregate
            # watermark headroom (pages are capacity — they sum)
            batch_occupancy=max(m.batch_occupancy for m in per),
            kv_watermark_headroom_pages=sum(
                m.kv_watermark_headroom_pages for m in per
            ),
        )
        # per-rung dispatch counters are dynamic attrs — sum the union
        # across ranks so the block-ladder histogram survives dp>1
        for key in {k for m in per for k in vars(m)
                    if k.startswith("decode_rung")}:
            setattr(agg, key, sum(getattr(m, key, 0) for m in per))
        return agg

    def clear_kv_blocks(self) -> int:
        return sum(e.clear_kv_blocks() for e in self.engines)

    def cached_prefix_len(self, prompt) -> int:
        return max(e.cached_prefix_len(prompt) for e in self.engines)

    async def shutdown(self) -> None:
        import asyncio

        await asyncio.gather(*(e.shutdown() for e in self.engines))


class EngineWorker:
    """Wraps an engine with the endpoint handler protocol: request dicts in,
    token-delta dicts out; control requests served inline."""

    def __init__(self, engine: Any):
        self.engine = engine

    async def handle(self, request: Any, context: Context) -> AsyncIterator[Any]:
        # chaos "wedge": accept the request and never yield — the process
        # stays alive, so ONLY the through-the-request-path health check
        # can catch it (health probes run this same handler)
        await gate_async_check("worker.generate")
        if isinstance(request, dict) and "control" in request:
            async for out in self._control(request):
                yield out
            return
        if isinstance(request, dict) and "embed_token_ids" in request:
            if not hasattr(self.engine, "embed"):
                yield {"error": "engine does not support embeddings"}
                return
            yield await self.engine.embed(request, context)
            return
        async for out in self.engine.generate(request, context):
            yield out

    async def _control(self, request: dict) -> AsyncIterator[Any]:
        op = request["control"]
        if op == "clear_kv_blocks":
            cleared = 0
            if hasattr(self.engine, "clear_kv_blocks"):
                cleared = self.engine.clear_kv_blocks()
            yield {"status": "ok", "pages_cleared": cleared}
        elif op == "metrics":
            m = (
                self.engine.metrics()
                if hasattr(self.engine, "metrics")
                else ForwardPassMetrics()
            )
            yield vars(m) if not isinstance(m, dict) else m
        else:
            yield {"status": "error", "error": f"unknown control op {op}"}


async def serve_engine(
    runtime: DistributedRuntime,
    engine: Any,
    mdc: ModelDeploymentCard,
    namespace: str = "dynamo",
    component: str = "backend",
    endpoint: str = "generate",
    publish_kv_events: bool = True,
) -> ServedEndpoint:
    """Register the engine as `{namespace}.{component}.{endpoint}` and
    publish its model card. Returns the served endpoint handle."""
    worker = EngineWorker(engine)
    ep = runtime.namespace(namespace).component(component).endpoint(endpoint)
    served = await ep.serve_endpoint(
        worker.handle,
        health_check_payload={"control": "metrics"},
    )
    wid = served.instance.instance_id
    if publish_kv_events and isinstance(engine, DpRankEngine):
        # one event stream + one metrics publisher PER RANK, keyed by the
        # packed (instance, dp_rank) worker id (reference: per-dp-rank
        # ZMQ event ports, vllm/main.py:120-143)
        from ..router import KvEventPublisher, WorkerMetricsPublisher

        served.kv_publisher = []
        served.metrics_publisher = []
        for rank, eng in enumerate(engine.engines):
            # metrics publish for EVERY rank — the router discovers an
            # instance's dp ranks from published metrics, so a silent
            # rank would never take KV-routed traffic
            served.metrics_publisher.append(WorkerMetricsPublisher(
                runtime, eng, namespace, component, wid, dp_rank=rank
            ).start())
            if not hasattr(eng, "add_event_sink"):
                continue
            kv_pub = KvEventPublisher(
                runtime, namespace, component, wid, dp_rank=rank
            ).start()
            eng.add_event_sink(kv_pub.sink)
            served.kv_publisher.append(kv_pub)
    elif publish_kv_events and hasattr(engine, "add_event_sink"):
        from ..router import KvEventPublisher, WorkerMetricsPublisher

        kv_pub = KvEventPublisher(runtime, namespace, component, wid).start()
        engine.add_event_sink(kv_pub.sink)
        metrics_pub = WorkerMetricsPublisher(
            runtime, engine, namespace, component, wid
        ).start()
        served.kv_publisher = kv_pub
        served.metrics_publisher = metrics_pub
    # KVBM fleet-wide prefix reuse: a worker with KV tiers attached also
    # publishes its host/disk tier summary (lease-scoped) so routers can
    # score overlap against blocks that left this worker's device cache
    tiered_src = engine
    while (getattr(tiered_src, "tiered", None) is None
           and hasattr(tiered_src, "engine")):
        tiered_src = tiered_src.engine  # unwrap disagg/encode handlers
    if publish_kv_events and getattr(tiered_src, "tiered", None) is not None:
        from ..kvbm.summary import TierSummaryPublisher
        from ..router.worker_key import pack_worker

        served.tier_summary_publisher = TierSummaryPublisher(
            runtime, tiered_src.tiered, namespace, component,
            worker_id=pack_worker(wid, 0),
        ).start()
    ranks = engine.dp_ranks if isinstance(engine, DpRankEngine) else 1
    inner = engine.engines[0] if isinstance(engine, DpRankEngine) else engine
    # unwrap handler/offload wrappers (DisaggDecodeHandler, EncodeOffload
    # — each delegates to `.engine`) so the model card still advertises
    # the real engine's page size / context / runtime config
    while not isinstance(inner, JaxEngine) and hasattr(inner, "engine"):
        inner = inner.engine
    if isinstance(inner, JaxEngine):
        if "embedding" not in mdc.types:
            mdc.model_type = mdc.model_type + ",embedding"
        mdc.kv_cache_block_size = inner.cfg.page_size
        mdc.context_length = inner.cfg.max_model_len
        mdc.runtime_config = RuntimeConfig(
            total_kv_blocks=inner.cfg.usable_pages * ranks,
            max_num_seqs=inner.cfg.max_num_seqs * ranks,
            max_num_batched_tokens=inner.cfg.max_prefill_tokens,
        )
    await register_llm(runtime, served, mdc)
    return served
