"""MockEngine — a full engine simulator (no device).

The reference treats its mocker as load-bearing infrastructure
(/root/reference/lib/llm/src/mocker/: vLLM simulator with paged KV manager,
watermark scheduler, chunked prefill, preemption, realistic timing, real KV
events) because it is what makes router/disagg/planner logic testable at
scale without hardware.  Ours reuses the *real* scheduler and page pool from
the JAX engine — so the simulation exercises exactly the code that runs on
TPU — and only fakes the device step with a timing model:

    prefill_time = base + per_token * chunk + quadratic * chunk * context
    decode_time  = base + per_seq * batch_size        (all / speedup_ratio)

Generated tokens are a deterministic hash of (request seed, absolute
sequence position = prompt length + output index), so tests can assert
determinism across topologies — AND across request migration: a stream
re-issued with `prompt + generated` as the new prompt continues the exact
token sequence the original worker would have produced, mirroring how a
real engine's output is conditioned on the full context.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import struct
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Callable, Dict, List, Optional

from ..engine.config import EngineConfig
from ..engine.engine import ForwardPassMetrics, _opts_from_request
from ..engine.page_pool import KvEvent, PagePool
from ..engine.scheduler import PrefillItem, Scheduler, Sequence
from ..runtime.engine import Context
from ..runtime.events import StepEventRecorder, attach_host_events

logger = logging.getLogger(__name__)


@dataclass
class MockEngineArgs:
    """Timing + capacity knobs (reference mocker/protocols.rs MockEngineArgs)."""

    num_pages: int = 512
    page_size: int = 16
    max_num_seqs: int = 16
    max_prefill_tokens: int = 512
    max_model_len: int = 4096
    enable_prefix_caching: bool = True
    watermark: float = 0.05
    speedup_ratio: float = 1.0  # >1 → faster than real time
    # timing model (seconds)
    prefill_base: float = 0.002
    prefill_per_token: float = 0.00005
    prefill_quadratic: float = 1e-9
    decode_base: float = 0.004
    decode_per_seq: float = 0.0002
    vocab_size: int = 32000
    eos_token_id: int = 2
    eos_probability: float = 0.0  # chance a generated token is EOS
    # overload control (docs/overload_control.md) — same semantics as
    # the real engine's knobs; the mock reuses the real Scheduler so the
    # class-aware admission/shed/preemption logic is exercised verbatim
    default_priority: str = "interactive"
    overload_queue_depth: int = 0
    overload_headroom_pages: int = 0
    batch_deadline_s: float = 0.0
    park_max_pages: int = 0

    def engine_config(self) -> EngineConfig:
        return EngineConfig(
            page_size=self.page_size,
            num_pages=self.num_pages,
            max_num_seqs=self.max_num_seqs,
            max_prefill_tokens=self.max_prefill_tokens,
            max_model_len=self.max_model_len,
            enable_prefix_caching=self.enable_prefix_caching,
            watermark=self.watermark,
            default_priority=self.default_priority,
            overload_queue_depth=self.overload_queue_depth,
            overload_headroom_pages=self.overload_headroom_pages,
            batch_deadline_s=self.batch_deadline_s,
            park_max_pages=self.park_max_pages,
        )


def _mock_token(seed: int, position: int, vocab: int, eos: int,
                eos_prob: float) -> int:
    h = hashlib.blake2b(struct.pack("<QQ", seed, position), digest_size=8)
    v = struct.unpack("<Q", h.digest())[0]
    if eos_prob > 0 and (v % 10_000) < eos_prob * 10_000:
        return eos
    tok = v % vocab
    return tok if tok != eos else (tok + 1) % vocab


class MockEngine:
    """Drop-in AsyncEngine with the JaxEngine's exact scheduling behavior."""

    def __init__(self, args: Optional[MockEngineArgs] = None,
                 event_sink: Optional[Callable[[KvEvent], None]] = None):
        self.args = args or MockEngineArgs()
        self.cfg = self.args.engine_config()
        self._event_sinks: List[Callable[[KvEvent], None]] = (
            [event_sink] if event_sink else []
        )
        self.pool = PagePool(
            self.cfg.num_pages, self.cfg.page_size, event_sink=self._emit
        )
        self.scheduler = Scheduler(self.cfg, self.pool)
        # same step-event surface as the real engine (admit/preempt from
        # the shared Scheduler; prefill_chunk/decode_block recorded by
        # the mock pump) — so chaos workers running the mock leave the
        # same black box (`DYN_TPU_FLIGHT_DIR`) a real worker would
        self.events = StepEventRecorder.from_env()
        attach_host_events(self.events)  # as the real engine's ring does
        self.scheduler.events = self.events
        # decode preemption park/resume: the mock holds no KV bytes, so
        # parking is pure page accounting through a real ParkingLot
        # (leak-ledger `parked_pages` account included) — generated
        # tokens are position-keyed, so a resume is token-identical by
        # construction and only the page bookkeeping needs restoring
        from ..kvbm.park import ParkingLot

        self.parking = ParkingLot(max_pages=self.cfg.park_max_pages,
                                  owner=f"mock-engine:{id(self):x}")
        self.scheduler.park_fn = self._park_seq
        self.scheduler.resume_fn = self._resume_parked
        self.scheduler.unpark_fn = (
            lambda seq: self.parking.discard(seq.request_id)
        )
        self._queues: Dict[str, asyncio.Queue] = {}
        self._contexts: Dict[str, Context] = {}
        self._wake = asyncio.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._pump_task: Optional[asyncio.Task] = None
        self._closed = False
        self._requests_total = 0
        self.step_log: List[str] = []  # for tests: sequence of step kinds

    def _emit(self, ev: KvEvent) -> None:
        for sink in self._event_sinks:
            try:
                sink(ev)
            except Exception:  # noqa: BLE001
                logger.exception("kv event sink failed")

    def add_event_sink(self, sink: Callable[[KvEvent], None]) -> None:
        self._event_sinks.append(sink)

    def metrics(self) -> ForwardPassMetrics:
        running, waiting = self.scheduler.num_requests()
        return ForwardPassMetrics(
            active_seqs=running,
            waiting_seqs=waiting,
            kv_usage=self.pool.usage(),
            kv_total_pages=self.cfg.usable_pages,
            num_requests_total=self._requests_total,
            batch_occupancy=running / max(self.cfg.max_num_seqs, 1),
            kv_watermark_headroom_pages=max(
                0, self.pool.available_pages
                - self.scheduler._watermark_pages()  # noqa: SLF001
            ),
            shed_total=self.scheduler.shed_total,
            queued_total=self.scheduler.queued_total,
            preempted_total=self.scheduler.preempted_total,
            resumed_total=self.scheduler.resumed_total,
            parked_seqs=len(self.parking),
            parked_pages=self.parking.pages_held,
        )

    def clear_kv_blocks(self) -> int:
        return self.pool.clear_cache()

    # -- park/resume hooks (overload control) -------------------------------- #

    def _park_seq(self, seq: Sequence) -> bool:
        from ..kvbm.park import ParkedSeq

        n = -(-seq.num_computed // self.cfg.page_size)
        if n <= 0 or n > len(seq.pages):
            return False
        return self.parking.park(ParkedSeq(
            request_id=seq.request_id, k=None, v=None, n_pages=n,
            num_computed=seq.num_computed, kv_rank=seq.kv_rank,
            block_hashes=list(seq.block_hashes),
        ))

    def _resume_parked(self, seq: Sequence) -> None:
        entry = self.parking.take(seq.request_id)
        if entry is None:
            raise KeyError(f"{seq.request_id} has no parked entry")
        seq.pages = self.pool.allocate_on(entry.kv_rank, entry.n_pages)
        # re-commit the hash chain from scratch on the fresh pages (the
        # real engine re-imports bytes; here only accounting matters)
        seq.committed_pages = 0
        seq.block_hashes = seq.block_hashes[:0]
        seq.num_computed = entry.num_computed

    # -- AsyncEngine --------------------------------------------------------- #

    async def generate(self, request: Dict[str, Any],
                       context: Optional[Context] = None
                       ) -> AsyncIterator[Dict[str, Any]]:
        context = context or Context()
        if self._pump_task is None or self._pump_task.done():
            self._loop = asyncio.get_running_loop()
            self._pump_task = self._loop.create_task(self._pump())
        opts = _opts_from_request(request)
        prompt = list(request["token_ids"])
        max_prompt = min(
            self.cfg.max_model_len - 1,
            self.cfg.usable_pages * self.cfg.page_size - 1,
        )
        if not prompt or len(prompt) > max_prompt:
            yield {"token_ids": [], "finish_reason": "error",
                   "error": f"prompt length {len(prompt)} outside [1, {max_prompt}]"}
            return
        if opts.max_tokens <= 0:
            yield {"token_ids": [], "finish_reason": "length"}
            return
        priority = request.get("priority") or self.cfg.default_priority
        if priority not in ("interactive", "batch"):
            yield {"token_ids": [], "finish_reason": "error",
                   "error": f"priority must be interactive|batch, "
                            f"got {priority!r}"}
            return
        if priority == "batch" and self.scheduler.overloaded():
            # admission shed at the knee — same structured error the
            # real engine emits (the frontend turns it into a 429)
            self.scheduler.shed_total += 1
            retry = max(1, int(self.cfg.batch_deadline_s) or 1)
            yield {"token_ids": [], "finish_reason": "error",
                   "error": {"code": "overloaded",
                             "message": "batch admission shed: engine "
                                        "past the overload knee; retry "
                                        "later",
                             "retry_after_s": retry}}
            return
        seq = Sequence(context.id, prompt, opts)
        seq.priority = priority
        seq.seed = opts.seed if opts.seed is not None else (
            struct.unpack("<Q", hashlib.blake2b(
                context.id.encode(), digest_size=8).digest())[0]
        )
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[context.id] = queue
        self._contexts[context.id] = context
        self._requests_total += 1
        self.scheduler.add(seq)
        self._wake.set()
        killed = asyncio.create_task(context.killed())
        finished = False
        try:
            while True:
                get = asyncio.create_task(queue.get())
                done, _ = await asyncio.wait(
                    {get, killed}, return_when=asyncio.FIRST_COMPLETED
                )
                if get not in done:
                    get.cancel()
                    return
                # lint: allow(blocking-in-async): asyncio.Task already completed by wait(); result() is non-blocking
                out = get.result()
                if out is None:
                    return
                yield out
                if out.get("finish_reason"):
                    finished = True
                    return
        finally:
            killed.cancel()
            self._queues.pop(context.id, None)
            self._contexts.pop(context.id, None)
            if not finished:
                # mock steps run on the event loop, so direct abort is safe
                self.scheduler.abort(context.id)

    async def shutdown(self) -> None:
        self._closed = True
        self._wake.set()
        if self._pump_task:
            await asyncio.gather(self._pump_task, return_exceptions=True)
        # same shutdown contract as JaxEngine: reap everything still
        # scheduled (aborting a parked waiter credits the parking lot via
        # unpark_fn) and hold the leak-ledger gate — a preemption
        # bookkeeping bug fails here loudly instead of pinning pages
        for seq in list(self.scheduler.running):
            self.scheduler.abort(seq.request_id)
        for seq in list(self.scheduler.waiting):
            self.scheduler.abort(seq.request_id)
        from ..analysis import leak_ledger

        leak_ledger.assert_balanced(self.parking.owner)

    # -- pump ---------------------------------------------------------------- #

    async def _pump(self) -> None:
        while not self._closed:
            plan = self.scheduler.schedule()
            # deliver planning-time errors BEFORE the idle park, or an
            # out-of-capacity request hangs forever
            for seq in self.scheduler.drain_errored():
                q = self._queues.get(seq.request_id)
                if q is not None:
                    q.put_nowait(
                        {"token_ids": [], "finish_reason": "error",
                         "error": "out of kv capacity"}
                    )
            for seq in self.scheduler.drain_shed():
                q = self._queues.get(seq.request_id)
                if q is not None:
                    retry = max(1, int(self.cfg.batch_deadline_s) or 1)
                    q.put_nowait(
                        {"token_ids": [], "finish_reason": "error",
                         "error": {"code": "overloaded",
                                   "message": "batch request shed after "
                                              "queueing past the deadline "
                                              "without admission; retry "
                                              "later",
                                   "retry_after_s": retry}}
                    )
            if plan.kind == "idle":
                if not self.scheduler.has_work:
                    self._wake.clear()
                    await self._wake.wait()
                else:
                    await asyncio.sleep(0.001)
                continue
            self.step_log.append(plan.kind)
            if plan.kind == "prefill":
                await self._run_prefill(plan.prefill)
            elif plan.kind == "mixed":
                # one device dispatch runs both halves back to back; the
                # simulated duration is the serial sum, matching the real
                # engine's mixed program
                await self._run_prefill(plan.prefill)
                await self._run_decode(plan.decode)
            else:
                await self._run_decode(plan.decode)
            await asyncio.sleep(0)

    async def _run_prefill(self, items: List[PrefillItem]) -> None:
        a = self.args
        total = sum(it.chunk_len for it in items)
        ctx_tokens = sum(it.seq.num_computed for it in items)
        t = (
            a.prefill_base
            + a.prefill_per_token * total
            + a.prefill_quadratic * total * ctx_tokens
        ) / a.speedup_ratio
        t0_ev = self.events.now()
        await asyncio.sleep(t)
        self.events.record("prefill_chunk", t0_ns=t0_ev, batch=len(items),
                           tokens=total, fused_blocks=0)
        for it in items:
            s = it.seq
            if s.status != "running":
                continue
            s.num_computed += it.chunk_len
            self.scheduler.commit_full_pages(s)
            if it.samples:
                self._append(s, _mock_token(
                    s.seed, len(s.prompt) + len(s.output_tokens),
                    a.vocab_size, a.eos_token_id, a.eos_probability,
                ))

    async def _run_decode(self, seqs: List[Sequence]) -> None:
        a = self.args
        t = (a.decode_base + a.decode_per_seq * len(seqs)) / a.speedup_ratio
        t0_ev = self.events.now()
        await asyncio.sleep(t)
        self.events.record("decode_block", t0_ns=t0_ev, rung=1,
                           batch=len(seqs), chain=1)
        for s in seqs:
            if s.status != "running":
                continue
            s.num_computed += 1
            self.scheduler.commit_full_pages(s)
            self._append(s, _mock_token(
                s.seed, len(s.prompt) + len(s.output_tokens),
                a.vocab_size, a.eos_token_id, a.eos_probability,
            ))

    def _append(self, seq: Sequence, token: int) -> None:
        seq.output_tokens.append(token)
        eos = [] if seq.opts.ignore_eos else [self.args.eos_token_id]
        reason = self.scheduler.check_stop(seq, eos)
        if reason:
            self.scheduler.finish(seq, reason)
        queue = self._queues.get(seq.request_id)
        if queue is not None:
            out: Dict[str, Any] = {"token_ids": [token],
                                   "finish_reason": reason}
            if seq.incidents:
                # forensics: engine-side stalls ride the next delta
                # (same attach-and-clear contract as the real engine)
                out["incidents"] = seq.incidents
                seq.incidents = []
            queue.put_nowait(out)
