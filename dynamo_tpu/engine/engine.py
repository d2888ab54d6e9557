"""JaxEngine — the TPU-native LLM engine (the component the reference
delegates to vLLM/SGLang/TRT-LLM; here it is first-party).

Structure:
- jitted step functions (`_prefill_step`, `_decode_step`) fuse model forward
  + sampling in one XLA program; the KV cache is donated through, so pages
  update in place in HBM with no host round-trip;
- a python-side `Scheduler` (continuous batching, chunked prefill, prefix
  cache, preemption) plans statically-shaped batches;
- an asyncio pump runs the device step in a worker thread and streams
  sampled tokens into per-request queues (`generate` implements the
  runtime's AsyncEngine protocol, so the engine drops straight into a
  served endpoint).

Emits KV events (stored/removed) and ForwardPassMetrics for the KV-aware
router (reference: publisher.rs:92 KvEventPublisher, :691
WorkerMetricsPublisher).
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, AsyncIterator, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import affine, leak_ledger, xla_ledger
from ..models import ModelConfig
from ..ops import SamplingParams
from ..runtime.engine import Context
from ..tokens import compute_block_hash_for_seq
from .config import EngineConfig, bucket_for
from .layout import Layout
from .page_pool import KvEvent, NoPagesError
from .scheduler import PrefillItem, SamplingOptions, Scheduler, Sequence, StepPlan
from ..models.llama import (moe_form, moe_rows, moe_stats_columns,
                            moe_stats_width,
                            require_no_state, require_plain_cache)
from .steps import (
    _unpack_out,
    _unpack_out_cc,
    _unpack_spec,
)

# jax.jit with compile attribution (analysis/xla_ledger.py): every jit
# cache miss in the engine lands in the ledger as (fn, signature, rung)
_ljit = xla_ledger.ledgered_jit

logger = logging.getLogger(__name__)


@dataclass
class ForwardPassMetrics:
    """Load snapshot published to the router (reference
    kv_router/protocols.rs ForwardPassMetrics).

    The spec_* fields are the SpecDecodeStats analog (reference
    _core.pyi:428-435): lifetime draft/accept counters plus a rolling
    acceptance rate over the engine's recent verify dispatches.

    The ttft_* fields attribute time-to-first-token across the three
    host-side phases the block ladder acts on: block-wait (request
    enqueued → scheduler first saw it, i.e. the in-flight decode block
    the pump was committed to), queue-wait (seen → admitted to running)
    and prefill (admitted → first token).  Lifetime ms totals plus the
    attributed-request count, so dashboards can plot means and the
    bench can prove where a TTFT win came from.  Per-rung dispatch
    counts ride as dynamic `decode_rung{n}_dispatches_total` attrs."""

    active_seqs: int = 0
    waiting_seqs: int = 0
    kv_usage: float = 0.0
    kv_total_pages: int = 0
    num_requests_total: int = 0
    spec_draft_tokens_total: int = 0
    spec_accepted_tokens_total: int = 0
    spec_dispatches_total: int = 0
    spec_acceptance_rate: float = 0.0
    ttft_block_wait_ms_total: float = 0.0
    ttft_queue_wait_ms_total: float = 0.0
    ttft_prefill_ms_total: float = 0.0
    # of prefill_ms: admitted, but waiting for a turn at the device or
    # between its own chunks (the ring's `first_token.wait_us`)
    ttft_turn_wait_ms_total: float = 0.0
    ttft_attributed_total: int = 0
    # pool occupancy with the prefix cache counted: pages held ONLY by the
    # cache (evictable; `kv_usage` counts them as free), pages on the free
    # list, and cached pages evicted to make room so far
    kv_pages_cached: int = 0
    kv_pages_free: int = 0
    prefix_evictions_total: int = 0
    # plain prefill steps dispatched, the sequences in them (more than one
    # where short chunks shared a step), the steps dispatched while the
    # step before was still unfetched (`_run_prefill`), and the steps in
    # which no row sampled, which skipped the output head (`_sampling_rows`)
    prefill_steps_total: int = 0
    prefill_rows_total: int = 0
    prefill_steps_overlapped_total: int = 0
    prefill_steps_headless_total: int = 0
    # steps of every kind dispatched to a device that had run dry: no
    # earlier program of this engine was still running once the step's own
    # was handed over (`dry` 1 on the step's slice: `_dispatched_dry`)
    steps_dry_total: int = 0
    # device-resident decode loop: chains run and blocks dispatched by
    # the continuous path (blocks/chains >> decode_chain means the open
    # horizon is actually engaging)
    decode_cc_blocks_total: int = 0
    decode_cc_chains_total: int = 0
    # per-reason chain fall-out counts (dict → labeled counter
    # decode_cc_fallout_total{reason} on /metrics): "admission" means
    # the chain ended FOR a waiting prompt (splice impossible or the
    # watermark reserve refused horizon growth) — distinct from "pages"
    # (pool genuinely exhausted with nothing waiting)
    decode_cc_fallout_total: Dict[str, int] = field(default_factory=dict)
    # fleet telemetry capacity signals: running-batch occupancy of the
    # FULLEST rank (one full rank blocks admission, so max not mean
    # across dp ranks) and pages still available above the admission
    # watermark (summed across ranks — aggregate headroom is capacity)
    batch_occupancy: float = 0.0
    kv_watermark_headroom_pages: int = 0
    # overload control (docs/overload_control.md): lifetime counts of
    # batch-class sheds (intake + deadline), batch adds that had to
    # queue, mid-decode preemptions parked to host, and parked
    # sequences resumed — plus the parking lot's live page footprint
    shed_total: int = 0
    queued_total: int = 0
    preempted_total: int = 0
    resumed_total: int = 0
    parked_seqs: int = 0
    parked_pages: int = 0


@dataclass
class _PrefillStep:
    """A prefill step between its two halves (`JaxEngine._run_prefill`):
    what `_prefill_dispatch` committed and `_prefill_consume` needs."""

    items: List[PrefillItem]
    item_rows: List[Optional[PrefillItem]]
    seq_rows: List[Optional[Sequence]]
    seqs: List[Sequence]
    with_top: bool
    packed_d: Any  # the packed result, on the device
    fused: list  # decode dispatches fused behind it ([]: none)
    t0_ns: int  # ring clock: slice start, the jitted call, dispatch done
    t_call_ns: int
    t_sent_ns: int
    attrs: dict  # the slice's attributes known at dispatch


def _ngram_draft(tokens: List[int], k: int, min_match: int,
                 max_match: int = 4, history: int = 256) -> List[int]:
    """Prompt-lookup / n-gram draft (host side, no draft model): propose
    the k tokens that followed the MOST RECENT earlier occurrence of the
    sequence's trailing m-gram, preferring the longest m in
    [min_match, max_match].  No match falls back to repeating the last
    token — a wrong draft only costs acceptance, never correctness (the
    verify step emits the model's own sample at the first mismatch)."""
    hist = np.asarray(tokens[-history:], np.int64)
    n = len(hist)
    for m in range(min(max_match, n - 1), min_match - 1, -1):
        # all length-m windows whose continuation exists (start <= n-m-1),
        # compared against the trailing m-gram in one vectorized pass —
        # this runs per row ahead of every spec dispatch, so no Python
        # inner loop
        windows = np.lib.stride_tricks.sliding_window_view(hist, m)[:n - m]
        hits = np.nonzero((windows == hist[n - m:]).all(axis=1))[0]
        if hits.size:
            s = int(hits[-1])  # most recent earlier occurrence
            # s + m <= n - 1, so at least one continuation token exists
            cont = hist[s + m:s + m + k].tolist()
            return (cont + [cont[-1]] * k)[:k]
    last = int(tokens[-1]) if tokens else 0
    return [last] * k


# -- multihost lockstep plan codec ----------------------------------------- #
# The leader (rank 0) broadcasts one step descriptor per dispatch; follower
# ranks replay it so every process issues identical jitted steps in the same
# order (the SPMD contract of parallel/multihost.py).  msgpack with numpy
# leaves encoded as (dtype, shape, bytes) triples.


def _plan_pack(obj) -> bytes:
    import msgpack

    def enc(o):
        if isinstance(o, np.ndarray):
            return {"__nd__": [str(o.dtype), list(o.shape),
                               np.ascontiguousarray(o).tobytes()]}
        if isinstance(o, (np.integer, np.floating)):
            return o.item()
        raise TypeError(f"unserializable plan leaf: {type(o)}")

    return msgpack.packb(obj, default=enc, use_bin_type=True)


def _plan_unpack(data: bytes):
    import msgpack

    def hook(o):
        if "__nd__" in o:
            dtype, shape, buf = o["__nd__"]
            return np.frombuffer(buf, dtype=np.dtype(dtype)).reshape(shape)
        return o

    return msgpack.unpackb(data, raw=False, object_hook=hook)


class JaxEngine:
    """Continuous-batching engine over a paged KV cache.

    Single-host by default; on a multi-process JAX world (multihost —
    `jax.distributed.initialize` via `parallel.initialize_multihost`) the
    engine runs in LOCKSTEP: rank 0 owns the scheduler and serves
    requests, every other rank constructs the same engine and calls
    `follower_loop()`, and each device dispatch is preceded by a plan
    broadcast so all ranks issue identical steps (the reference reaches
    multi-node only through its engines' NCCL worlds — MultinodeSpec,
    dynamocomponentdeployment_types.go:108; here the engine itself spans
    hosts with dp/tp over ICI+DCN)."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        params: Any,
        engine_cfg: Optional[EngineConfig] = None,
        eos_token_ids: Optional[List[int]] = None,
        kv_dtype=jnp.bfloat16,
        event_sink: Optional[Callable[[KvEvent], None]] = None,
        tiered=None,  # kvbm.TieredKvCache — host/disk KV tiers
        parallel=None,  # parallel.ParallelConfig — dp×tp serving mesh
        devices=None,
        vision=None,  # (vision_params, models.vision.VisionConfig)
        multihost: Optional[bool] = None,  # override process-count
        # detection (a process-local auxiliary engine inside a multihost
        # job passes False and pins its devices)
    ):
        self.model_cfg = model_cfg
        self.eos_token_ids = eos_token_ids or []
        self._kv_dtype = kv_dtype
        # where everything lives, and the config this layout really runs
        self.layout, self.cfg = Layout.resolve(
            model_cfg, engine_cfg, parallel, devices, multihost, vision)
        # multihost blob staging (per-shard KV import fetch): lazy server
        # on the leader, cached fetch clients on followers
        self._blob_stage_srv = None
        self._blob_clients: Dict[tuple, Any] = {}
        self._blob_bytes_fetched = 0  # survive server/client close (stats)
        self._blob_bytes_staged = 0
        self._blob_bytes_served = 0
        # what an expert model's prefill-path steps append to their pack
        # (`Layout.carries_moe_stats`)
        self.moe_assignments_total = 0
        self.moe_local_assignments_total = 0  # that chose a HELD expert
        self.moe_experts_hit_total = 0
        self.moe_steps_total = 0
        # the same steps and their valid tokens by the form their expert
        # layers ran (`models.llama.moe_form`: the step's traced shape)
        self.moe_form_steps = {"all_experts": 0, "dispatched": 0}
        self.moe_form_tokens = {"all_experts": 0, "dispatched": 0}
        # of the dispatched ones, those whose rows moved inside the grouped
        # kernel (`models.llama.moe_rows`)
        self.moe_rows_kernel_steps = 0
        # hyper-connections: the largest distance of a step's mixing
        # matrices from doubly stochastic so far, parts per million
        self.hc_res_err_ppm_max = 0
        if self.cfg.quantization == "int8":
            from ..models.quantization import quantize_params

            params = quantize_params(params)
        if self.cfg.fuse_projections:
            from ..models.llama import fuse_projections

            params = fuse_projections(params)
        # vision tower (multimodal): embeds computed engine-side at first
        # prefill of the sequence, injected in place of placeholder tokens.
        # Composes with multihost (the tower runs leader-local and the
        # resulting embeds ride the lockstep prefill plan), with
        # kv_partition (embeds shard with the per-rank batch blocks), and
        # with sp (embeds/mask shard their sequence axis over the ring
        # exactly like the tokens)
        self.vision = vision
        self._encode_fn = None
        if self.cfg.park_max_pages:  # parked pages are (k, v) host blobs
            require_plain_cache(model_cfg, "preemption parking")
        self.params = self.layout.shard_params(params)
        self.kv = self.layout.make_kv(kv_dtype)
        logger.info("cache: %s", self.cache_report())
        self._extra_event_sinks: List[Callable[[KvEvent], None]] = []
        if event_sink:
            self._extra_event_sinks.append(event_sink)
        self.pool = self.layout.make_pool(self._emit_event)
        self.scheduler = Scheduler(self.cfg, self.pool,
                                   self.layout.make_state_pool())
        # preemption parking lot (overload control): batch-class victims
        # preempted mid-decode export byte-exact KV here and resume
        # through ordinary admission — docs/overload_control.md.  The
        # ledger owner matches shutdown's assert_balanced owner, so KV
        # pinned past shutdown fails tier-1 loudly.
        from ..kvbm.park import ParkingLot

        self.parking = ParkingLot(self.cfg.park_max_pages,
                                  owner=f"engine:{id(self):x}")
        self.scheduler.park_fn = self._park_seq
        self.scheduler.resume_fn = self._resume_parked
        self.scheduler.unpark_fn = self._unpark_seq
        # device ops queued by the loop thread, executed by the pump between
        # steps (self.kv is only ever touched between steps)
        self._pending_ops: List = []
        self.tiered = None
        if tiered is not None:
            self.attach_connector(tiered)
        import random as _random

        self._py_rng = _random.Random(0xD1A)
        self._queues: Dict[str, asyncio.Queue] = {}
        self._contexts: Dict[str, Context] = {}
        self._seq_by_rid: Dict[str, Sequence] = {}
        self._wake = asyncio.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._pump_task: Optional[asyncio.Task] = None
        self._executor = None  # dedicated device-step thread (see _ensure_pump)
        # async drain (device-resident decode loop): a second thread that
        # device_gets + unpacks block k while the step thread dispatches
        # block k+1 (lazy — only continuous-mode engines start it)
        self._drain_pool = None
        self._cc_blocks_total = 0
        self._cc_chains_total = 0
        # per-reason chain fall-out counter (decode_cc_fallout_total on
        # /metrics): single-writer by contract — only the
        # @affine("step") chain loop mutates it; metrics() snapshots a
        # dict() copy, so no lock (docs/concurrency.md thread roles)
        self._cc_fallout_by_reason: Dict[str, int] = {}
        self._closed = False
        # adds/aborts are deferred to the pump loop so ALL scheduler/pool
        # mutation happens strictly between device steps, on the pump's
        # executor thread (admission may touch disk/remote KV tiers, so
        # planning runs off the event loop — see _plan_step)
        self._pending_aborts: set[str] = set()
        self._pending_adds: List = []  # ("add"|"imported", Sequence)
        self._requests_total = 0
        self._step_count = 0
        # speculative decoding telemetry (SpecDecodeStats analog):
        # lifetime counters + a rolling per-dispatch window for the
        # acceptance rate surfaced in ForwardPassMetrics
        from collections import deque as _deque

        self._spec_draft_total = 0
        self._spec_accepted_total = 0
        self._spec_dispatch_total = 0
        self._spec_window = _deque(maxlen=128)  # (drafted, accepted)
        # block-ladder telemetry: dispatches per chosen rung, plus the
        # TTFT attribution accumulators (block-wait vs queue-wait vs
        # prefill — per-request values ride the first delivered delta,
        # lifetime totals surface in ForwardPassMetrics)
        self._rung_dispatches: Dict[int, int] = {}
        self._ttft_block_wait_ms_total = 0.0
        self._ttft_queue_wait_ms_total = 0.0
        self._ttft_prefill_ms_total = 0.0
        self._ttft_attributed_total = 0
        # step-event ring (runtime.events): admit/plan/step/rung/spec/pool
        # events with monotonic-ns stamps — dumped by the worker debug
        # endpoint and merged into the Perfetto timeline.  Scheduler and
        # pool record through the same ring so one dump is the whole
        # engine's step history
        from ..runtime.events import StepEventRecorder, attach_host_events

        self.events = StepEventRecorder.from_env()
        # the process's host events (start-up phases, program births,
        # collector pauses, late lease renewals) land on the FIRST
        # engine's ring: a `--dp-ranks` worker's rank 0
        attach_host_events(self.events)
        self.scheduler.events = self.events
        for p in getattr(self.pool, "pools", [self.pool]):
            p.events = self.events
        if self.scheduler.state is not None:
            self.scheduler.state.events = self.events
        # jax.profiler capture of the next N engine steps, armed by
        # DYN_TPU_XPROF_STEPS=N / DYN_TPU_XPROF_DIR (default profiles/) at
        # start-up or by `arm_xprof` while serving (the worker's
        # POST /debug/xprof); starts at the next dispatched step
        from ..runtime.config import env_int, env_str

        self._xprof_steps = env_int("DYN_TPU_XPROF_STEPS", 0)
        self._xprof_dir = env_str("DYN_TPU_XPROF_DIR", "profiles")
        self._xprof_started_at: Optional[int] = None
        self._xprof_done = self._xprof_steps <= 0
        # ring clock (monotonic ns): start of the step the step thread is
        # in; and whether a step has returned since the pump's last record
        # (what follows, up to the loop's top, is a `loop_yield`)
        self._step_t0_ns: Optional[int] = None
        self._stepped = False
        # the instant up to which the step loop's account is written: the
        # end of the last phase either thread recorded (the pump and the
        # step thread never run at once).  The next phase starts here, so
        # the phases tile (docs/observability.md, "The loop's account
        # tiles"); a step's `hop_us` is its slice's start less this
        self._acct_ns = self.events.now()
        # step programs handed to the device so far: the next one's `seq`
        self._dispatch_seq = 0
        self.steps_dry_total = 0
        # the end of the last step slice recorded: `first_token.own_us`
        # counts a step from here on where its slice opened earlier
        self._slice_end_ns = 0
        # the one prefill step dispatched and not fetched (`_run_prefill`).
        # Written on the step thread, read by the pump between executor
        # calls (the two never run at once)
        self._inflight: Optional[_PrefillStep] = None
        self.prefill_steps_total = 0
        self.prefill_rows_total = 0
        self.prefill_steps_overlapped_total = 0
        self.prefill_steps_headless_total = 0
        self.cross_rows_total = 0
        # (top logprobs, greedy, table width) of every short prefill step
        # met so far: `_meet_short_prefill`
        self._short_prefill_met: set = set()
        self._ttft_turn_wait_ms_total = 0.0
        self._evictions_before_reset = 0

    def cache_report(self) -> dict:
        """The page pool by its one description (`ModelConfig.cache_spec`):
        the kind of pages, what a token leaves a layer, what it is stored
        as, and the pool's size (the worker's `CACHE` start-up line)."""
        spec, L = self.model_cfg.cache_spec, self.model_cfg.num_kv_layers
        per_token = L * spec.bytes_per_token_layer(
            jnp.dtype(self._kv_dtype).itemsize)
        tokens = (self.layout.pool_ranks * self.cfg.num_pages
                  * self.cfg.page_size)
        return {"kind": spec.kind, "values_per_token_layer": spec.values,
                "planes": [list(d) for d in spec.plane_dims],
                "dtype": jnp.dtype(self._kv_dtype).name, "layers": L,
                "bytes_per_token": per_token, "pool_tokens": tokens,
                "pool_bytes": tokens * per_token}

    def state_report(self) -> Optional[dict]:
        """The state slots beside the pages by their one description
        (`ModelConfig.state_spec`): the worker's `STATE` start-up line.
        None for a model without state-space layers."""
        spec = self.model_cfg.state_spec
        if spec is None:
            return None
        per_slot = spec.bytes_per_slot(jnp.dtype(self._kv_dtype).itemsize)
        slots = self.cfg.num_state_slots
        # a state that is a window alone has no recurrent part: no `state`
        # key, and `bytes_per_slot` counts the window's bytes only
        recurrent = ({"state": list(spec.state_dims),
                      "state_dtype": "float32"} if spec.recurrent else {})
        return {"kind": "ssm" if spec.recurrent else "window",
                "layers": spec.layers,
                "window": list(spec.window_dims),
                "window_dtype": jnp.dtype(self._kv_dtype).name,
                **recurrent,
                "bytes_per_slot": per_slot, "slots": slots,
                "pool_bytes": slots * per_slot,
                "snapshot_every": self.scheduler.state.snapshot_every}

    def attach_connector(self, connector) -> None:
        """Attach a KVBM connector (kvbm.KvConnector shape: on_event /
        pump_offloads / onboard).  The engine pumps its offload queue and
        routes admission-time cache misses through it — the engine-facing
        equivalent of the reference's KVConnector protocol
        (block_manager/connector/protocol.rs).  Composes with multihost
        (offload/onboard device ops broadcast on the lockstep plan
        channel like every other device op; the host/disk tiers stay
        leader-local) and with kv_partition (onboarded pages land on
        the admitting sequence's pool rank)."""
        require_plain_cache(self.model_cfg, "a KVBM tier")
        self.tiered = connector
        self.add_event_sink(connector.on_event)

        # onboarding runs inside admission (pump loop thread, between
        # steps) — blocking device work, small and batched.  The wrapper
        # leaves the scheduler's watermark reserve untouched (onboarding
        # must not eat the pages `_admit_check` holds back for decode
        # growth), exports a `kvbm.onboard` span under the admitting
        # request's trace, and lands a ring event on the step timeline.
        def _onboard(hashes, rank=0):
            t0 = time.time_ns()
            ring_t0 = (self.events.now() if self.events is not None
                       else None)
            pages = connector.onboard(
                self, hashes, rank=rank,
                headroom=self.scheduler._watermark_pages() + 1,  # noqa: SLF001
            )
            if pages:
                from ..runtime.tracing import export_span

                export_span(
                    "kvbm.onboard",
                    getattr(self.scheduler, "onboard_trace", None),
                    t0, time.time_ns(),
                    blocks=len(pages), missed=len(hashes), rank=rank,
                )
                if self.events is not None:
                    self.events.record("kvbm_onboard", t0_ns=ring_t0,
                                       n=len(pages), rank=rank)
            return pages

        self.scheduler.onboard_fn = _onboard

    @affine("step", "loop")
    def export_cached_blocks_device(self, hashes):
        """Device half of the offload export (step thread in steady state;
        the planning loop may call it too, where dispatch ordering keeps it
        from racing a step's donated KV buffers — never the drain thread).
        Returns per-rank chunks ``[(hashes, k_dev, v_dev)]`` WITHOUT
        fetching: the outputs are fresh device buffers, so the blocking
        ``device_get`` can run on the KVBM drain thread concurrently
        with later steps.  Hashes no longer cached are skipped."""
        resolved, pages = [], []
        for h in hashes:
            page = self.pool.cached_page(h)
            if page is not None:
                resolved.append(h)
                pages.append(page)
        if not pages:
            return []
        if self.layout.pooled:
            # a batch of cached hashes may span pool ranks; the export
            # jit masks to ONE rank per call — group into chunks
            by_rank: Dict[int, List[tuple]] = {}
            for h, p in zip(resolved, pages):
                by_rank.setdefault(self.pool.rank_of(p), []).append((h, p))
            chunks = []
            for items in by_rank.values():
                pg = [p for _, p in items]
                k, v = self._export_dev(pg)
                chunks.append(([h for h, _ in items], k, v))
            return chunks
        k, v = self._export_dev(pages)
        return [(resolved, k, v)]

    def export_cached_blocks(self, hashes):
        """SYNC device->host export of committed blocks (pump/executor
        thread only — never concurrent with a step).  Returns
        (resolved_hashes, k, v) with k/v shaped [L, n, page, kv, hd];
        hashes no longer cached are skipped."""
        chunks = self.export_cached_blocks_device(hashes)
        if not chunks:
            return [], None, None
        out_h, ks, vs = [], [], []
        for hs, k, v in chunks:
            out_h.extend(hs)
            ks.append(np.asarray(jax.device_get(k))[:, : len(hs)])
            vs.append(np.asarray(jax.device_get(v))[:, : len(hs)])
        if len(ks) == 1:
            return out_h, ks[0], vs[0]
        return out_h, np.concatenate(ks, 1), np.concatenate(vs, 1)

    @affine("step", "loop")
    def import_committed_blocks(self, blocks, rank: Optional[int] = None
                                ) -> List[int]:
        """SYNC import of (hash, parent_hash, k, v) blocks into freshly
        allocated pages, committed to the prefix cache (pump/executor
        thread only).  Returns the page ids.  `rank` pins the pages to
        one pool partition (onboarding for an admitting sequence must
        land on ITS rank; None = allocator's choice)."""
        if not blocks:
            return []
        pages = (self.pool.allocate(len(blocks)) if rank is None
                 else self.pool.allocate_on(rank, len(blocks)))
        width = self._pow2_width(len(pages))
        k0 = blocks[0][2]
        kpad = np.zeros((k0.shape[0], width, *k0.shape[1:]), k0.dtype)
        vpad = np.zeros_like(kpad)
        for i, (_, _, k, v) in enumerate(blocks):
            kpad[:, i] = k
            vpad[:, i] = v
        self._import_dev(pages, kpad, vpad)
        for (h, parent, _, _), page in zip(blocks, pages):
            self.pool.commit(page, h, parent)
        return pages

    # -- preemption park/resume (overload control) --------------------------- #

    @affine("step", "loop")
    def _park_seq(self, seq: Sequence) -> bool:
        """Scheduler park hook: export the victim's live KV pages —
        including the partial tail page — device→host byte-exact into
        the parking lot.  Byte-exact restore (not recompute) is what
        makes the preempt→park→resume round trip token-identical: the
        resumed decode sees the same KV bytes at the same positions,
        the same ``output_tokens[-1]`` input, and PRNG counters derived
        from ``len(output_tokens)``.  Returns False (victim keeps
        running) when the lot is at budget."""
        from ..kvbm.park import ParkedSeq
        from ..runtime.tracing import export_span

        ps = self.cfg.page_size
        n_used = -(-seq.num_computed // ps)
        if n_used <= 0 or n_used > len(seq.pages):
            return False
        if not self.parking.can_park(n_used):
            return False
        t0 = time.time_ns()
        pages = seq.pages[:n_used]
        k, v = self._export_dev(pages)
        # parking IS a synchronous device→host export: the victim's pages
        # are freed the moment park_fn returns, so the fetch cannot move
        # to the drain side — one batched transfer for both planes
        k, v = jax.device_get((k, v))  # lint: allow(device-get): park must complete the export before the pages are freed; single batched fetch
        k = np.asarray(k)[:, :n_used]
        v = np.asarray(v)[:, :n_used]
        ok = self.parking.park(ParkedSeq(
            request_id=seq.request_id, k=k, v=v, n_pages=n_used,
            num_computed=seq.num_computed, kv_rank=seq.kv_rank,
            block_hashes=list(seq.block_hashes),
        ))
        if ok:
            export_span(
                "engine.park", seq.trace, t0, time.time_ns(),
                pages=n_used, tokens=seq.num_computed,
            )
        return ok

    @affine("step", "loop")
    def _resume_parked(self, seq: Sequence) -> None:
        """Scheduler resume hook (admission time): restore a parked
        sequence's KV into fresh pages — device prefix-cache hits first
        (full blocks committed at park time may still be cached), the
        remainder imported from the lot's host bytes.  Full blocks
        re-commit to the prefix cache; the partial tail page stays
        uncommitted (its block is incomplete).  Raises on a missing
        entry or allocation failure — the scheduler errors the request
        (a silent recompute here would break token identity)."""
        from ..runtime.tracing import export_span

        entry = self.parking.take(seq.request_id)
        if entry is None:
            raise KeyError(f"no parked KV for {seq.request_id}")
        t0 = time.time_ns()
        full = len(entry.block_hashes)
        hit: List[int] = []
        if self.cfg.enable_prefix_caching and entry.block_hashes:
            hit = self.pool.lookup_on(seq.kv_rank, entry.block_hashes)
        rest = entry.n_pages - len(hit)
        try:
            fresh = (self.pool.allocate_on(seq.kv_rank, rest)
                     if rest else [])
        except NoPagesError:
            self.pool.free(hit)
            raise
        if fresh:
            width = self._pow2_width(rest)
            k0 = entry.k
            kpad = np.zeros((k0.shape[0], width, *k0.shape[2:]), k0.dtype)
            vpad = np.zeros_like(kpad)
            for j, idx in enumerate(range(len(hit), entry.n_pages)):
                kpad[:, j] = entry.k[:, idx]
                vpad[:, j] = entry.v[:, idx]
            self._import_dev(fresh, kpad, vpad)
            if self.cfg.enable_prefix_caching:
                for off, page in enumerate(fresh):
                    idx = len(hit) + off
                    if idx >= full:
                        break  # partial tail page — never committed
                    parent = (entry.block_hashes[idx - 1] if idx > 0
                              else None)
                    self.pool.commit(page, entry.block_hashes[idx], parent)
        seq.pages = list(hit) + list(fresh)
        seq.committed_pages = full
        seq.num_computed = entry.num_computed
        seq.block_hashes = list(entry.block_hashes)
        export_span(
            "engine.resume", seq.trace, t0, time.time_ns(),
            pages=entry.n_pages, cached=len(hit), tokens=entry.num_computed,
        )

    def _unpark_seq(self, seq: Sequence) -> None:
        """Scheduler unpark hook: a parked request was aborted/shed —
        drop its lot entry (credits the ledger's parked_pages)."""
        self.parking.discard(seq.request_id)

    @property
    def compiled_variants(self) -> Dict[str, List]:
        """Public view of the compiled step-variant cache keys per step
        family (`Layout.compiled_variants`).  Benchmarks and warmup
        harnesses key off this instead of the private caches (e.g. "has
        the mixed program compiled yet", "is every ladder rung warm")."""
        return self.layout.compiled_variants

    @property
    def compiled_decode_rungs(self) -> set:
        """Block-ladder rungs with a compiled decode OR mixed program
        (ladder-aware warmup checks coverage against
        `cfg.block_ladder`)."""
        return self.layout.compiled_decode_rungs

    @property
    def rung_histogram(self) -> Dict[int, int]:
        """Dispatch count per chosen decode-block rung (decode, mixed
        and fused dispatches; chained blocks count once per block)."""
        return dict(self._rung_dispatches)

    # -- events -------------------------------------------------------------- #

    def _emit_event(self, ev: KvEvent) -> None:
        for sink in self._extra_event_sinks:
            try:
                sink(ev)
            except Exception:  # noqa: BLE001 — sinks must not break the engine
                logger.exception("kv event sink failed")

    def add_event_sink(self, sink: Callable[[KvEvent], None]) -> None:
        self._extra_event_sinks.append(sink)

    # -- metrics ------------------------------------------------------------- #

    def metrics(self) -> ForwardPassMetrics:
        running, waiting = self.scheduler.num_requests()
        m = ForwardPassMetrics(
            active_seqs=running,
            waiting_seqs=waiting,
            # busy/capacity signals key off the FULLEST partition: one
            # full rank blocks admission (sequences pin to a rank) even
            # when aggregate usage looks low — reporting the aggregate
            # here would skew router busy-shed and planner decisions
            kv_usage=self.pool.usage_max_rank(),
            # partitioned pools aggregate capacity across their ranks
            kv_total_pages=self.cfg.usable_pages * self.pool.ranks,
            num_requests_total=self._requests_total,
            spec_draft_tokens_total=self._spec_draft_total,
            spec_accepted_tokens_total=self._spec_accepted_total,
            spec_dispatches_total=self._spec_dispatch_total,
            spec_acceptance_rate=self._spec_acceptance_rate(),
            ttft_block_wait_ms_total=self._ttft_block_wait_ms_total,
            ttft_queue_wait_ms_total=self._ttft_queue_wait_ms_total,
            ttft_prefill_ms_total=self._ttft_prefill_ms_total,
            ttft_turn_wait_ms_total=self._ttft_turn_wait_ms_total,
            ttft_attributed_total=self._ttft_attributed_total,
            kv_pages_cached=self.pool.evictable_pages,
            kv_pages_free=self.pool.free_pages,
            prefix_evictions_total=(self._evictions_before_reset
                                    + self.pool.evictions_total),
            prefill_steps_total=self.prefill_steps_total,
            prefill_rows_total=self.prefill_rows_total,
            prefill_steps_overlapped_total=(
                self.prefill_steps_overlapped_total),
            prefill_steps_headless_total=self.prefill_steps_headless_total,
            steps_dry_total=self.steps_dry_total,
            decode_cc_blocks_total=self._cc_blocks_total,
            decode_cc_chains_total=self._cc_chains_total,
            decode_cc_fallout_total=dict(self._cc_fallout_by_reason),
            batch_occupancy=running / max(self.cfg.max_num_seqs, 1),
            kv_watermark_headroom_pages=max(
                0, self.pool.available_pages
                - self.scheduler._watermark_pages() * self.pool.ranks  # noqa: SLF001
            ),
            shed_total=self.scheduler.shed_total,
            queued_total=self.scheduler.queued_total,
            preempted_total=self.scheduler.preempted_total,
            resumed_total=self.scheduler.resumed_total,
            parked_seqs=len(self.parking),
            parked_pages=self.parking.pages_held,
        )
        # chosen-rung histogram (block ladder): one dynamic counter attr
        # per rung — bounded by the ladder size, picked up by vars()
        # consumers (/metrics.json, the worker Prometheus collector)
        for rung, n in sorted(self._rung_dispatches.items()):
            setattr(m, f"decode_rung{rung}_dispatches_total", n)
        if self.layout.carries_moe_stats:  # an expert model's prefill path
            m.moe_assignments_total = self.moe_assignments_total
            if self.model_cfg.moe_ep_size > 1:
                m.moe_local_assignments_total = (
                    self.moe_local_assignments_total)
            m.moe_experts_hit_total = self.moe_experts_hit_total
            m.moe_steps_total = self.moe_steps_total
            for form in self.moe_form_steps:
                setattr(m, f"moe_{form}_steps_total",
                        self.moe_form_steps[form])
                setattr(m, f"moe_{form}_tokens_total",
                        self.moe_form_tokens[form])
            m.moe_rows_kernel_steps_total = self.moe_rows_kernel_steps
            if self.model_cfg.hc_mult:
                m.hc_res_err_ppm_max = self.hc_res_err_ppm_max
        if self.model_cfg.cross_decoder:  # beside `prefill_rows_total`
            m.cross_rows_total = self.cross_rows_total
        st = self.scheduler.state
        if st is not None:  # state slots beside the pages
            m.state_slots_total = st.num_slots - 1
            m.state_slots_running = st.running
            m.state_snapshots = st.snapshots
            m.state_snapshot_stored_total = st.stored_total
            m.state_snapshot_hits_total = st.hits_total
            m.state_snapshot_evictions_total = st.evictions_total
            m.state_hit_tokens_shortened_total = (
                st.hit_tokens_shortened_total)
        if self.pool.ranks > 1:
            m.kv_usage_aggregate = self.pool.usage()
        if self.tiered is not None:
            # KVBM tier stats ride the same snapshot (dynamic attrs are
            # picked up by vars() consumers: /metrics.json, Prometheus,
            # the TelemetryPublisher capacity snapshots)
            t = self.tiered
            m.kvbm_host_blocks = len(t.host)
            m.kvbm_pending_offloads = t.pending_offloads
            m.kvbm_inflight_offloads = t.inflight_offloads
            m.kvbm_offload_total = t.offloaded_blocks
            m.kvbm_onboard_total = t.onboarded_blocks
            m.kvbm_evict_total = t.host.evicted
            m.kvbm_host_hits_total = t.host.hits
            m.kvbm_host_misses_total = t.host.misses
            m.kvbm_host_bytes = t.host.bytes_used
            m.kvbm_host_capacity_bytes = t.host.capacity_bytes
            if t.disk is not None:
                m.kvbm_disk_blocks = len(t.disk)
                m.kvbm_disk_hits_total = t.disk.hits
                m.kvbm_disk_misses_total = t.disk.misses
                m.kvbm_disk_bytes = t.disk.bytes_used
        return m

    def clear_kv_blocks(self) -> int:
        return self.pool.clear_cache()

    # -- AsyncEngine protocol ------------------------------------------------ #

    async def generate(
        self, request: Dict[str, Any], context: Optional[Context] = None
    ) -> AsyncIterator[Dict[str, Any]]:
        """request: {"token_ids": [...], "sampling_options": {...},
        "stop_conditions": {...}} → stream of {"token_ids": [...],
        "finish_reason": str|None} (the wire protocol of the reference's
        PreprocessedRequest → LLMEngineOutput,
        /root/reference/lib/llm/src/protocols/common/llm_backend.rs)."""
        context = context or Context()
        self._ensure_pump()
        opts = _opts_from_request(request)
        prompt = list(request["token_ids"])
        max_prompt = min(
            self.cfg.max_model_len - 1,
            self.cfg.max_pages_per_seq * self.cfg.page_size - 1,
            # must fit the pool even with everything else evicted
            self.cfg.usable_pages * self.cfg.page_size - 1,
        )
        if not prompt or len(prompt) > max_prompt:
            yield {
                "token_ids": [],
                "finish_reason": "error",
                "error": (
                    f"prompt length {len(prompt)} outside [1, {max_prompt}]"
                ),
            }
            return
        if opts.max_tokens <= 0:
            yield {"token_ids": [], "finish_reason": "length"}
            return
        priority = request.get("priority") or self.cfg.default_priority
        if priority not in ("interactive", "batch"):
            yield {
                "token_ids": [],
                "finish_reason": "error",
                "error": f"unknown priority class {priority!r}",
            }
            return
        if priority == "batch" and self.scheduler.overloaded():
            # admission shed at intake: past the pressure knee, batch work
            # is rejected up front (429 at the frontend) rather than
            # accepted-then-starved.  The structured error dict passes
            # verbatim through postprocess_stream to the HTTP layer.
            self.scheduler.shed_total += 1
            if self.scheduler.events is not None:
                self.scheduler.events.record(
                    "shed", rid=context.id, reason="intake")
            retry = max(1, int(self.cfg.batch_deadline_s) or 1)
            yield {
                "token_ids": [],
                "finish_reason": "error",
                "error": {
                    "code": "overloaded",
                    "message": "batch admission shed: engine past the "
                               "overload knee (queue depth + watermark "
                               "headroom); retry later",
                    "retry_after_s": retry,
                },
            }
            return
        seq = Sequence(context.id, prompt, opts)
        seq.priority = priority
        seq.t_arrival = time.monotonic()
        seq.seed = opts.seed if opts.seed is not None else self._py_rng.getrandbits(31)
        seq.hold_pages = bool(request.get("_hold_pages"))
        from ..runtime.tracing import current_trace

        seq.trace = current_trace()  # milestone spans join this trace
        if (request.get("mm_pixels") or request.get("mm_embeds")
                or request.get("mm_patches")):
            err = self._attach_mm(seq, request)
            if err:
                yield {"token_ids": [], "finish_reason": "error", "error": err}
                return
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[context.id] = queue
        self._contexts[context.id] = context
        self._seq_by_rid[context.id] = seq
        self._requests_total += 1
        self._pending_adds.append(("add", seq))
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
            self._seq_by_rid.pop(context.id, None)
            if not finished:
                # consumer went away (kill, disconnect, stop-sequence close):
                # make sure the scheduler drops the sequence
                self._abort(context.id)

    # -- pump ---------------------------------------------------------------- #

    def _abort(self, request_id: str) -> None:
        self._pending_aborts.add(request_id)
        self._wake.set()

    def _ensure_pump(self) -> None:
        if self._pump_task is None or self._pump_task.done():
            if self._executor is None:
                # One dedicated thread per engine: device steps are strictly
                # sequential anyway, and owning the thread means shutdown()
                # can JOIN it — with the loop's shared default executor a
                # timed-out caller leaks a running step thread that later
                # posts to a closed loop (the full-suite flake, VERDICT r4
                # weak #1).
                import concurrent.futures as _cf

                self._executor = _cf.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="jax-engine-step",
                    initializer=xla_ledger.thread_role_init,
                )
            self._loop = asyncio.get_running_loop()
            self._pump_task = self._loop.create_task(self._pump())

    async def shutdown(self) -> None:
        self._closed = True
        self._wake.set()
        if self._pump_task:
            # the pump consumes a step in flight on its way out
            await asyncio.gather(self._pump_task, return_exceptions=True)
        if self._xprof_started_at is not None and not self._xprof_done:
            self._xprof_stop()
        # the pump exits the moment _closed is set, so an abort queued
        # during teardown (generate()'s finally on a cancelled stream)
        # never reaches the scheduler and its sequence keeps its page
        # refs forever.  Nothing can step again — reap everything still
        # scheduled so the pool is balanced before the leak check below.
        while self._pending_aborts:
            self.scheduler.abort(self._pending_aborts.pop())
        for seq in list(self.scheduler.running):
            self.scheduler.abort(seq.request_id)
        for seq in list(self.scheduler.waiting):
            self.scheduler.abort(seq.request_id)
        if self.scheduler.deferred_free:
            self.pool.free(self.scheduler.deferred_free)
            self.scheduler.deferred_free = None
        if self.layout.lockstep and self.layout.is_leader:
            # release follower ranks blocked in follower_loop — even when
            # the engine never served a request (no step executor yet)
            await asyncio.get_running_loop().run_in_executor(
                self._executor, self._lockstep_send, {"kind": "shutdown"}
            )
        if self._executor is not None:
            # join the step thread so no engine work outlives shutdown()
            await asyncio.get_running_loop().run_in_executor(
                None, self._executor.shutdown, True
            )
            self._executor = None
        if self._drain_pool is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self._drain_pool.shutdown, True
            )
            self._drain_pool = None
        if self.tiered is not None:
            # join the kvbm-offload drain thread: no tier write (host
            # insert, demotion disk put) outlives shutdown(), and the
            # executor thread doesn't leak per engine lifecycle.  The
            # pump has exited, so nothing submits anymore; a tier shared
            # with a later engine reopens its drain lazily on submit.
            await asyncio.get_running_loop().run_in_executor(
                None, self.tiered.close
            )
        self._close_blob_channels()
        # every sequence is gone: outstanding page refs can never be
        # freed now — surface the leak at its owner, not session end
        leak_ledger.check_page_pool(self.pool, f"engine:{id(self):x}")
        leak_ledger.assert_balanced(f"engine:{id(self):x}")

    def _close_blob_channels(self) -> None:
        """Stop the lazily-started blob stage server / fetch clients
        (leaked listeners and sockets otherwise accumulate across engine
        lifecycles in one process)."""
        if self._blob_stage_srv is not None:
            self._blob_bytes_staged += self._blob_stage_srv.bytes_staged
            self._blob_bytes_served += self._blob_stage_srv.bytes_served
            self._blob_stage_srv.stop()
            self._blob_stage_srv = None
        for client in self._blob_clients.values():
            self._blob_bytes_fetched += client.bytes_fetched
            client.close()
        self._blob_clients.clear()

    @affine("loop")
    def _plan_step(self) -> StepPlan:
        """Apply deferred scheduler mutations and plan the next step.

        Runs on the pump's loop thread between device steps; deferring
        adds/aborts here keeps every scheduler/pool mutation in one place.
        Admission may touch the disk/remote KV tiers — those are bounded
        by short tier timeouts rather than moved off-loop (planning on an
        executor thread turned out to intermittently wedge XLA:CPU
        compilation issued from rotating worker threads)."""
        # adds strictly before aborts: an abort for a still-queued add must
        # see the sequence in the scheduler or it becomes a silent no-op
        # and the orphan decodes to max_tokens with no consumer
        while self._pending_adds:
            kind, seq = self._pending_adds.pop(0)
            if kind == "imported":
                self.scheduler.add_imported(seq)
            else:
                self.scheduler.add(seq)
        while self._pending_aborts:
            self.scheduler.abort(self._pending_aborts.pop())
        # honor graceful stop requests before planning
        for rid, ctx in list(self._contexts.items()):
            if ctx.is_stopped() and not ctx.is_killed():
                for seq in list(self.scheduler.running):
                    if seq.request_id == rid and seq.output_tokens:
                        self.scheduler.finish(seq, "cancelled")
                        self._deliver(seq, [], "cancelled")
        return self.scheduler.schedule()

    def _pump_span(self) -> dict:
        """`t0_ns` and `t1_ns` of a pump slice: from where the loop's
        account ends (`_acct_ns`) to now, which is where it ends next."""
        t0, self._acct_ns = self._acct_ns, self.events.now()
        return {"t0_ns": t0, "t1_ns": self._acct_ns}

    async def _consume_inflight(self, loop) -> None:
        """Fetch and deliver the prefill step in flight, if there is one:
        before anything that is not a further plain prefill step.  The
        hand-off to the step thread is on the slice as `fetch_hop_us`."""
        step = self._inflight
        if step is None:
            return
        try:
            await loop.run_in_executor(
                self._executor, self._run_step, "prefill_chunk",
                self._prefill_consume, step)
        except Exception:  # noqa: BLE001
            logger.exception("engine step failed; resetting KV state")
            self._recover_after_error()

    async def _pump(self) -> None:
        loop = asyncio.get_running_loop()
        self._acct_ns = self.events.now()
        while not self._closed:
            if self._stepped:
                # the executor hop back to this thread and the
                # asyncio.sleep(0) in which the worker's other coroutines ran
                self.events.record("loop_yield", **self._pump_span())
                self._stepped = False
            if self._pending_ops or (self.tiered is not None
                                     and self.tiered.pending_offloads):
                await self._consume_inflight(loop)
            # drain offload queue (device→host copies, KVBM)
            if self.tiered is not None and self.tiered.pending_offloads:
                try:
                    await loop.run_in_executor(
                        self._executor, self.tiered.pump_offloads, self
                    )
                except Exception:  # noqa: BLE001
                    logger.exception("kv offload failed")
                self.events.record("pump_op", **self._pump_span(),
                                   op="offload")
            # run queued device ops (KV export/import for disagg)
            while self._pending_ops:
                op, fut = self._pending_ops.pop(0)
                try:
                    result = await loop.run_in_executor(self._executor, op)
                    if not fut.done():
                        fut.set_result(result)
                except Exception as e:  # noqa: BLE001
                    if not fut.done():
                        fut.set_exception(e)
                self.events.record("pump_op", **self._pump_span(),
                                   op="device_op")
            admits = self.events.kind_totals.get("admit", 0)
            plan = self._plan_step()
            self.events.record(
                "plan", **self._pump_span(),
                waiting=len(self.scheduler.waiting),
                running=len(self.scheduler.running),
                admitted=self.events.kind_totals.get("admit", 0) - admits)
            for seq in self.scheduler.drain_errored():
                self._deliver(seq, [], "error")
            for seq in self.scheduler.drain_shed():
                # queued-with-deadline batch work that expired: same
                # structured overload error as the intake shed, so the
                # frontend's 429 path is uniform
                retry = max(1, int(self.cfg.batch_deadline_s) or 1)
                self._deliver(seq, [], "error", error={
                    "code": "overloaded",
                    "message": "batch request shed after "
                               f"{self.cfg.batch_deadline_s:g}s queued "
                               "without admission; retry later",
                    "retry_after_s": retry,
                })
            if self._inflight is not None and plan.kind != "prefill":
                # nothing to put behind the step in flight: its result
                # first, then a plan that knows it (this one was made
                # without the step's tokens and without the pages its
                # finished sequences give back; what it admitted or
                # reserved stays with the sequences)
                await self._consume_inflight(loop)
                await asyncio.sleep(0)
                continue
            if plan.kind == "idle":
                if not (self.scheduler.has_work or self._pending_adds
                        or self._pending_aborts):
                    if self.tiered is not None \
                            and self.tiered.pending_offloads:
                        # only offload work remains: keep pumping batches,
                        # but with a real sleep — when the dispatch is
                        # backpressured (drain thread busy) a sleep(0)
                        # loop would spin the step thread hot
                        await asyncio.sleep(0.002)
                        self.events.record("idle_wait", **self._pump_span())
                        continue
                    # shutdown() may have set _closed (and _wake) while this
                    # iteration was suspended in an executor await — e.g. the
                    # offload pump dispatch; clearing _wake here would eat
                    # that wakeup and park forever against a gather()ing
                    # shutdown
                    if self._closed:
                        break
                    self._wake.clear()
                    await self._wake.wait()
                    self.events.record("idle_wait", **self._pump_span())
                else:
                    # work that no step can take yet: the yield is one
                    self._stepped = True
                    await asyncio.sleep(0)
                continue
            if not self._xprof_done and self._xprof_started_at is None:
                # a capture starts between whole steps
                await self._consume_inflight(loop)
                # lint: allow(blocking-in-async): one-time profiler capture setup, not steady-state
                self._xprof_start()
            if plan.kind == "prefill":
                step = ("prefill_chunk", self._run_prefill, plan.prefill)
            elif plan.kind == "mixed":
                step = ("mixed_step", self._run_mixed, plan)
            else:
                step = ("decode_block", self._run_decode, plan.decode)
            try:
                await loop.run_in_executor(
                    self._executor, self._run_step, *step)
            except Exception:  # noqa: BLE001
                logger.exception("engine step failed; resetting KV state")
                self._recover_after_error()
            self._step_count += 1
            if not self._xprof_done and self._xprof_due():
                await self._consume_inflight(loop)  # and ends between them
                self._xprof_stop()
            await asyncio.sleep(0)
        await self._consume_inflight(loop)

    # -- xprof capture (DYN_TPU_XPROF_STEPS, POST /debug/xprof) --------------- #

    def arm_xprof(self, steps: int, directory: str) -> bool:
        """Arm a capture of the next `steps` engine steps into `directory`
        while serving.  False when one is armed or running already (the
        profiler is one per process)."""
        if not self._xprof_done or steps <= 0:
            return False
        self._xprof_steps, self._xprof_dir = int(steps), directory
        self._xprof_started_at = None
        self._xprof_done = False
        return True

    def _xprof_start(self) -> None:
        """First non-idle plan with capture armed: start the jax.profiler
        trace.  A failed start disables capture for the engine's lifetime
        (profiling must never take down serving).

        The options switch off JAX's Python tracer (it hooks every Python
        call of every thread: hundreds of MB a run and a host several
        times slower, so a traced run measured the tracer) and keep the
        host tracer at 1: the device planes and `profile_start_time` are
        written at 0 already, 1 adds the `TraceAnnotation` of each step."""
        if self._xprof_started_at is not None:
            return
        try:
            import os as _os

            _os.makedirs(self._xprof_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            wall_ns, mono_ns = time.time_ns(), time.monotonic_ns()
            jax.profiler.start_trace(self._xprof_dir,
                                     profiler_options=options)
            self._xprof_started_at = self._step_count
            self.events.record("xprof_start", wall_ns=wall_ns,
                               mono_ns=mono_ns, steps=self._xprof_steps)
            logger.info("xprof: tracing %d engine step(s) into %s",
                        self._xprof_steps, self._xprof_dir)
        except Exception:  # noqa: BLE001
            self._xprof_done = True
            logger.exception("xprof start failed; capture disabled")

    def _xprof_due(self) -> bool:
        return (self._xprof_started_at is not None
                and self._step_count - self._xprof_started_at
                >= self._xprof_steps)

    def _xprof_stop(self) -> None:
        self._xprof_done = True
        try:
            wall_ns, mono_ns = time.time_ns(), time.monotonic_ns()
            jax.profiler.stop_trace()
            self.events.record(
                "xprof_stop", wall_ns=wall_ns, mono_ns=mono_ns,
                steps=self._step_count - self._xprof_started_at)
            logger.info("xprof: capture complete (%d steps) in %s",
                        self._step_count - self._xprof_started_at,
                        self._xprof_dir)
        except Exception:  # noqa: BLE001
            logger.exception("xprof stop failed")

    # -- device steps (worker thread) ---------------------------------------- #

    def _unpack_rows(self, packed: np.ndarray, B: int, with_top: bool,
                     blocks: int = 1):
        """`_unpack_out` over a row layout.  Partitioned-pool steps emit
        the packed result as a concatenation of per-rank blocks (each
        rank packs its own rows), so unpack block-wise and stitch."""
        if blocks <= 1:
            return _unpack_out(packed, B, with_top)
        L = packed.shape[-1] // blocks
        Br = B // blocks
        pr = packed.reshape(*packed.shape[:-1], blocks, L)
        parts = [
            _unpack_out(pr[..., r, :], Br, with_top) for r in range(blocks)
        ]
        toks = np.concatenate([p[0] for p in parts], axis=-1)
        logp = np.concatenate([p[1] for p in parts], axis=-1)
        if not with_top:
            return toks, logp, None, None
        tids = np.concatenate([p[2] for p in parts], axis=-2)
        tlps = np.concatenate([p[3] for p in parts], axis=-2)
        return toks, logp, tids, tlps

    # Batch ROW LAYOUTS: every per-step array builder takes a `rows` list
    # (Sequence | None, None = padding row).  Unpartitioned engines use
    # the identity layout (live rows first, pad tail); a partitioned pool
    # lays rows out as R contiguous per-rank blocks of uniform width so
    # the batch axis shards over (dp, sp) with each row on the shard that
    # owns its pages.

    def _decode_rows(self, seqs: List[Sequence]) -> List[Optional[Sequence]]:
        if not self.layout.pooled:
            Bb = bucket_for(len(seqs), self.cfg.decode_batch_buckets)
            return list(seqs) + [None] * (Bb - len(seqs))
        by_rank: List[List[Sequence]] = [
            [] for _ in range(self.layout.pool_ranks)]
        for s in seqs:
            by_rank[s.kv_rank].append(s)
        widest = max([1] + [len(g) for g in by_rank])
        Br = bucket_for(widest, self.cfg.decode_batch_buckets)
        # bucket_for clamps to buckets[-1]; a clamped Br < widest would
        # silently misalign rows with their (dp, sp) pool shards (config
        # validation rejects such bucket overrides — this is the backstop)
        assert Br >= widest, (
            f"per-rank decode group ({widest}) exceeds the largest decode "
            f"batch bucket ({Br})"
        )
        rows: List[Optional[Sequence]] = []
        for g in by_rank:
            rows.extend(g)
            rows.extend([None] * (Br - len(g)))
        return rows

    def _prefill_rows(self, items: List[PrefillItem]) -> List[Optional[PrefillItem]]:
        if not self.layout.pooled:
            # one sequence, or the CONSTANT row count of a shared step:
            # each distinct row count is otherwise its own program (a
            # compile of seconds, landing mid-measurement on whichever
            # requests first meet in that number); padding rows run a
            # 1-token chunk into the trash page
            B = self.layout.pad_batch(
                1 if len(items) == 1 else self.cfg.prefill_batch_size)
            return list(items) + [None] * (B - len(items))
        by_rank: List[List[PrefillItem]] = [
            [] for _ in range(self.layout.prefill_groups)]
        for it in items:
            by_rank[self.layout.prefill_slot(it.seq.kv_rank)[0]].append(it)
        Br = max([1] + [len(g) for g in by_rank])
        rows: List[Optional[PrefillItem]] = []
        for g in by_rank:
            rows.extend(g)
            rows.extend([None] * (Br - len(g)))
        return rows

    def _seed_arrays(self, rows: List[Optional[Sequence]]):
        seeds = [getattr(s, "seed", 0) if s else 0 for s in rows]
        counters = [len(s.output_tokens) if s else 0 for s in rows]
        return (
            np.asarray(seeds, np.uint32),
            np.asarray(counters, np.int32),
        )

    @staticmethod
    def _is_greedy(rows: List[Optional[Sequence]]) -> bool:
        """True when every row is temperature-0: the dispatch compiles
        the STATIC greedy step variant (the runtime all-greedy cond
        still costs ~0.9ms/step at a 128k vocab — ops/sampling.py).
        Decided from the host's own values, rounded as `_samp_arrays`
        rounds them: reading the temperatures back from the device waits
        behind the program in flight (a whole step, where a prefill step
        stays in flight while the next one is built)."""
        return all(s is None or np.float32(s.opts.temperature) <= 0.0
                   for s in rows)

    def _rope_array(self, rows: List[Optional[Sequence]]):
        """Per-row mrope rope-offset operand ([B] int32), or None for
        non-mrope models."""
        if not self.model_cfg.mrope_section:
            return None
        out = np.zeros((len(rows),), np.int32)
        for i, s in enumerate(rows):
            if s is not None:
                out[i] = s.rope_delta
        return out

    def _rope_operand(self, rope_off, like: np.ndarray) -> tuple:
        """The trailing `rope_off` operand of a decode-side step: mrope
        models only (qwen2_vl)."""
        if not self.model_cfg.mrope_section:
            return ()
        return (self.layout.put_rows(
            np.zeros_like(like) if rope_off is None else rope_off),)

    @staticmethod
    def _start_host_copy(arr) -> None:
        """Start a result's device→host copy now, so that it rides back
        behind its own program and not behind what is dispatched next."""
        try:
            arr.copy_to_host_async()
        except Exception:  # lint: allow(swallowed-exception): copy_to_host_async optional; fetch path device_gets anyway
            pass

    def _table_array(self, rows: List[Optional[Sequence]]) -> np.ndarray:
        """Page-table batch, width bucketed to the longest sequence present
        (attention/gather cost scales with width, so short-context batches
        stay cheap).  Partitioned pools store LOCAL ids (each shard's page
        0 is its own trash page).  For a model with state-space layers
        `Layout.state_cols` more columns follow the pages': the state slots
        a row reads and writes (`models.hybrid`), so that whatever builds
        a table, pad rows and warm-up steps of zeros too, names them."""
        need = max((len(s.pages) for s in rows if s), default=1)
        width = bucket_for(max(need, 1), self.cfg.table_width_buckets)
        cols = self.layout.state_cols
        table = np.zeros((len(rows), width + cols), np.int32)
        npp = self.cfg.num_pages
        for i, s in enumerate(rows):
            if s is None:
                continue
            if cols:
                # [read, write, inside...]: the snapshot a sequence resumes
                # from, else its own slot, else (nothing computed yet) no
                # state at all; its own slot; the slots reserved for the
                # snapshots inside its next chunk
                table[i, width:width + 2] = (
                    s.state_src or (s.state_slot if s.num_computed else 0),
                    s.state_slot)
                table[i, width + 2:width + 2 + len(s.state_inside)] = (
                    s.state_inside)
            n = min(len(s.pages), width)
            if self.layout.pooled:
                table[i, :n] = [p % npp for p in s.pages[:n]]
            else:
                table[i, :n] = s.pages[:n]
        return table

    def _samp_arrays(self, rows: List[Optional[Sequence]]) -> SamplingParams:
        return SamplingParams.make(
            [s.opts.temperature if s else 0.0 for s in rows],
            [s.opts.top_k if s else 0 for s in rows],
            [s.opts.top_p if s else 1.0 for s in rows],
            [s.opts.frequency_penalty if s else 0.0 for s in rows],
            [s.opts.presence_penalty if s else 0.0 for s in rows],
        )

    def _prefill_arrays(self, item_rows: List[Optional[PrefillItem]],
                        short: bool = False):
        """(tokens [B, chunk_bucket], prefix [B], chunk [B]) for a prefill
        row layout.  Pad rows run a 1-token chunk into the trash page (a
        fully masked row would softmax over -inf only).  A `short` step
        (`PrefillItem.short`: every row could share it) runs at the ONE
        short bucket whatever its rows' lengths, so that its programs
        differ by row count and table width alone."""
        B = len(item_rows)
        chunk_bucket = (self.cfg.short_chunk_bucket if short else bucket_for(
            max(it.chunk_len for it in item_rows if it),
            self.cfg.chunk_buckets))
        tokens = np.zeros((B, chunk_bucket), np.int32)
        prefix = np.zeros((B,), np.int32)
        chunk = np.ones((B,), np.int32)
        for i, it in enumerate(item_rows):
            if it is None:
                continue
            toks = it.seq.prompt[it.chunk_start : it.chunk_start + it.chunk_len]
            tokens[i, : len(toks)] = toks
            prefix[i] = it.chunk_start
            chunk[i] = it.chunk_len
        return tokens, prefix, chunk, chunk_bucket

    def _decode_arrays(self, rows: List[Optional[Sequence]]):
        """(last tokens [B], positions [B]) for a decode row layout."""
        B = len(rows)
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        for i, s in enumerate(rows):
            if s is None:
                continue
            tokens[i] = s.output_tokens[-1] if s.output_tokens else (
                s.prompt[-1] if s.prompt else 0
            )
            positions[i] = s.num_computed
        return tokens, positions

    def _counts_array(self, rows: List[Optional[Sequence]]) -> np.ndarray:
        """Dense [B, vocab] output-token histograms (prompt tokens are
        not penalized)."""
        counts = np.zeros((len(rows), self.model_cfg.vocab_size), np.float32)
        for i, s in enumerate(rows):
            if s is not None and s.output_tokens:
                np.add.at(counts[i], s.output_tokens, 1.0)
        return counts

    def _encode_counts_sparse(self, rows: List[Optional[Sequence]]):
        """Sparse (flat token list + row offsets) form of `_counts_array`
        for the lockstep plan channel (inverse: `_counts_from_sparse`)."""
        flat, offs = [], [0]
        for s in rows:
            if s is not None:
                flat.extend(s.output_tokens)
            offs.append(len(flat))
        return [np.asarray(flat, np.int32), np.asarray(offs, np.int64)]

    def _note_dispatch(self, n_steps: int = 0, blocks: int = 1) -> int:
        """Account one device dispatch of `blocks` step programs: the rung
        histogram (decode-bearing kinds, `n_steps`; a chained run counts
        once per block) and the dispatch ordinal.  Returns the `seq` of
        its first program: the engine's step programs run on the device in
        this order, so the n-th one on a device trace's program line is
        the one that took ordinal n (the ring's record of the dispatch is
        the step slice that follows, which carries it)."""
        if n_steps:
            self._rung_dispatches[n_steps] = (
                self._rung_dispatches.get(n_steps, 0) + blocks
            )
            xla_ledger.note_decode_block(blocks)
        seq = self._dispatch_seq
        self._dispatch_seq += blocks
        return seq

    def _step_begins(self) -> Tuple[int, int]:
        """(t0, hop_us) at a step slice's start: the ring's clock, and the
        hand-off that brought the step here, from where the loop's account
        ended (the pump's last record) to t0."""
        t0 = self._step_t0_ns = self.events.now()
        return t0, (t0 - self._acct_ns) // 1000

    def _dispatched_dry(self, dry: bool) -> int:
        """`dry` of a step slice, counted: 1 if no earlier program of this
        engine was still running once the step's own was handed to the
        device, so that the device stood idle before it could start.  The
        caller asks its unfetched results `is_ready()` (a query, never a
        wait) when its jitted call has RETURNED: the call takes 3.5 ms on a
        v5e host and the device runs dry inside it twenty times as often as
        before it (PERF.md finding 37).  A step dispatched with nothing
        unfetched is dry by construction."""
        self.steps_dry_total += dry
        return int(dry)

    def _run_step(self, kind: str, run, arg) -> None:
        """One engine step on the step thread, under a profiler annotation
        named like its ring slice: the host line of a capture then shows
        the step that the device line's program belongs to."""
        try:
            with jax.profiler.TraceAnnotation(kind):
                run(arg)
        finally:
            self._step_t0_ns = None
            self._stepped = True

    def _decode_moe_form(self, rows: int) -> dict:
        """{"moe_form": ...} of a decode step over `rows` rows, one token
        each (an expert model's decode events; {} otherwise)."""
        if not self.model_cfg.is_moe:
            return {}
        return {"moe_form": moe_form(self.model_cfg, rows)}

    def _note_moe(self, packed: np.ndarray, traced: int, tokens: int) -> dict:
        """The moe stats an expert model's prefill-path step appended to
        its pack (`steps.carries_moe_stats`): added to the engine's counters,
        returned as the step slice's attributes.  {} for any other step.
        `traced` is the step program's rows x bucket, which chose the form
        of its expert layers (`moe_form`); `tokens` its valid tokens."""
        if not self.layout.carries_moe_stats:
            return {}
        stats = [int(v) for v in packed[-moe_stats_width(self.model_cfg):]]
        assigned, hit, load, *local = stats[:moe_stats_columns(self.model_cfg)]
        self.moe_assignments_total += assigned
        self.moe_experts_hit_total += hit
        self.moe_steps_total += 1
        form = moe_form(self.model_cfg, traced)
        if form in self.moe_form_steps:  # not "capacity": it carries none
            self.moe_form_steps[form] += 1
            self.moe_form_tokens[form] += tokens
        attrs = {"moe_assignments": assigned, "experts_hit": hit,
                 "moe_max_load": load, "moe_form": form}
        rows = moe_rows(traced) if form == "dispatched" else None
        if rows is not None:
            attrs["moe_rows"] = rows
            self.moe_rows_kernel_steps += rows == "kernel"
        if local:  # a share of each layer's experts is held here
            self.moe_local_assignments_total += local[0]
            attrs["moe_local"] = local[0]
        if self.model_cfg.hc_mult:  # the stats' last column
            attrs["hc_res_err_ppm"] = stats[-1]
            self.hc_res_err_ppm_max = max(self.hc_res_err_ppm_max, stats[-1])
        return attrs

    def _attn_of(self, site: str, batch: int, chunk: int,
                 width: int) -> str:
        """The attention program ("pallas" | "xla") the step of this shape
        was traced into (`ops.paged_attention._adapt` notes it per shape);
        the configured implementation where no choice was noted.  `width`
        is the step's table's, its state columns included."""
        pages = width - self.layout.state_cols
        return xla_ledger.path_choice(
            site, batch=batch, chunk=chunk,
            table_tokens=pages * self.cfg.page_size) or self.layout.attn_impl

    def _scan_of(self, batch: int, chunk: int) -> dict:
        """`scan`: the form ("pallas" | "xla") the Mamba-2 scan of a prefill
        step of this shape was traced into (`ops.ssm.scan` notes it per
        shape); no key for a model without such a layer."""
        cfg = self.model_cfg
        if not cfg.ssm_heads or cfg.ssm_dt_rank:  # none, or Mamba-1's
            return {}
        choice = xla_ledger.path_choice("ssm_scan", rows=batch, chunk=chunk)
        return {"scan": choice} if choice else {}

    @staticmethod
    def _credit_own(seqs, ns: int) -> None:
        """A step's slice goes to the working time of each of its sequences
        that still waits for its first token (`first_token.own_us`)."""
        for s in seqs:
            if s.t_first_token is None:
                s.own_ns += ns
                s.own_steps += 1

    def _step_phases(self, seqs, t0: int, t_call: Optional[int],
                     t_fetch: Optional[int], t_got: Optional[int],
                     t_sent: Optional[int] = None) -> dict:
        """What the host did inside the step slice [t0, now], for the ONE
        ring record of the step: integer microseconds on the ring's clock —
        `build_us` (t0 to the jitted call), `dispatch_us` (the call, its
        input transfers and what was dispatched behind it: a cache miss or
        a blocked transfer shows here), `fetch_us` (the device_get: device
        wait plus copy), `deliver_us` (unpack, token accounting, queues),
        and `compiled`, the programs this thread bore in build and dispatch
        (`xla_ledger.births_between`; absent when 0).
        Phases are attributes and not nested slices: a reader that labels
        a device gap by the slice spanning it would lose a nested one
        behind its parent.  A phase the step never reached reads 0.

        A prefill step gives `t_sent`, the end of its dispatch half, and
        gets `overlap_us` (t_sent to its own fetch) between `dispatch_us`
        and `fetch_us`: the time its program ran, or waited behind the
        one before it, while the step thread and the pump were busy with
        OTHER steps: the older step's fetch and delivery, the plan, the
        next step's build and dispatch.  About 0 for a step consumed at
        once.

        Also credits the slice to its sequences (`_credit_own`), from the
        end of the last slice on where the two overlap, so that the
        credits of consecutive steps never count an instant twice."""
        t_end = time.monotonic_ns()
        t_got = t_end if t_got is None else t_got
        t_fetch = t_got if t_fetch is None else t_fetch
        t_call = t_fetch if t_call is None else t_call
        self._credit_own(seqs, t_end - max(t0, self._slice_end_ns))
        self._slice_end_ns = self._acct_ns = t_end
        # the slice ends at this reading (`StepEventRecorder.record`), so
        # the parts add up to it: a step thread that loses the CPU between
        # here and the record call leaves no hole in the step's account
        phases = {"t1_ns": t_end,
                  "build_us": (t_call - t0) // 1000,
                  "dispatch_us": (t_fetch - t_call) // 1000,
                  "fetch_us": (t_got - t_fetch) // 1000,
                  "deliver_us": (t_end - t_got) // 1000}
        if t_sent is not None:
            phases["dispatch_us"] = (t_sent - t_call) // 1000
            phases["overlap_us"] = (t_fetch - t_sent) // 1000
        # programs this thread bore up to the end of the dispatch, where
        # a jitted call traces, compiles or loads (absent when 0)
        born = xla_ledger.births_between(
            t0, t_fetch if t_sent is None else t_sent)
        if born:
            phases["compiled"] = born
        return phases

    def _sampling_rows(self, item_rows) -> Tuple[Optional[np.ndarray], int]:
        """The `samples` operand of a prefill step (or of a mixed step's
        prefill side) and the step's `head` attribute: [B] bool, the rows
        whose chunk ends a prompt (a pad row's does not), and 1 if the
        step runs the output head, which it does where any row samples
        (`models.llama.forward_prefill`).  (None, 1) on a layout whose
        program runs the head on every step (`Layout.heads_by_rows`)."""
        if not self.layout.heads_by_rows:
            return None, 1
        rows = np.array([it is not None and it.samples for it in item_rows])
        return rows, int(rows.any())

    @affine("step")
    def _run_prefill(self, items: List[PrefillItem]) -> None:
        """One prefill step in two halves: `_prefill_dispatch` builds the
        inputs and commits the program, `_prefill_consume` fetches and
        delivers its result.  Between them the step may stay IN FLIGHT
        (`self._inflight`, at most one): the pump plans again, and if that
        plan is a further prefill step, its first half runs here before
        the older step's second half, so the device goes from one program
        to the next while the host fetches, delivers, plans and builds.

        One stream on one chip (or one program over a single-process mesh)
        runs programs in dispatch order and threads the KV pool through
        them (`self.kv`), and that order is what makes a step in flight
        safe for the PAGES: the next chunk of a sequence reads what the
        chunk in flight writes; a page freed under the program in flight
        and handed to a later step is written after the program is done
        with it; an export, an offload or an import is a later program on
        the same stream.  It covers nothing on the HOST: a sequence whose
        sampling chunk is in flight has no token (the scheduler keeps it
        out of decode, mixed and fused plans: `Scheduler.in_flight`); a
        result is applied only to a sequence still running (an abort or
        a preemption after planning drops the row); pages enter the prefix
        cache when the step that wrote them is fetched; the pages of a
        sequence aborted under its step in flight go back to the pool
        then too (`deferred_free`); and a failed fetch takes the newer
        step down with it (`_recover_after_error`)."""
        step = self._prefill_dispatch(items)
        older, self._inflight = self._inflight, step
        self.scheduler.in_flight = tuple(step.seqs)
        if older is not None:
            self._prefill_consume(older)
        if step.fused or not self.layout.holds_step_in_flight:
            self._prefill_consume(step)

    def _prefill_dispatch(self, items: List[PrefillItem]) -> "_PrefillStep":
        t0_ev, hop_us = self._step_begins()
        ordinal = self._note_dispatch()
        item_rows = self._prefill_rows(items)
        B = len(item_rows)
        seq_rows = [it.seq if it else None for it in item_rows]
        short = items[0].short  # the plan's: one row or several, all short
        tokens, prefix, chunk, chunk_bucket = self._prefill_arrays(
            item_rows, short)
        seqs = [it.seq for it in items]
        if (self.layout.sp > 1 and prefix.any()
                and not self.cfg.enable_prefix_caching):
            # cannot happen with prefix caching off + whole-prompt chunks;
            # guards scheduler regressions from silently corrupting sp runs
            raise RuntimeError("sp prefill requires prefix_lens == 0")
        with_top = any(s.opts.top_logprobs > 0 for s in seqs)
        table = self._table_array(seq_rows)
        seeds, counters = self._seed_arrays(seq_rows)
        samp = self._samp_arrays(seq_rows)
        for s in seqs:  # encode pending vision inputs (step thread)
            if s.mm_pixels is not None or s.mm_patches is not None:
                self._encode_mm(s)
        mm = ()
        if any(s.mm_embeds is not None for s in seqs):
            mm = self._mm_arrays(item_rows, B, chunk_bucket)
        owner = None
        if self.layout.names_owner:
            owner = np.zeros((B,), np.int32)
            for i, it in enumerate(item_rows):
                if it is not None:
                    owner[i] = self.layout.prefill_slot(it.seq.kv_rank)[1]
        greedy = self._is_greedy(seq_rows)
        samples, head = self._sampling_rows(item_rows)
        if self.layout.lockstep:
            self._lockstep_send({
                "kind": "prefill", "with_top": with_top,
                "arrays": [tokens, table, prefix, chunk,
                           *[np.asarray(a) for a in samp], seeds, counters],
                "owner": owner,
                # vision embeds (leader-computed) ride the plan so every
                # rank issues the identical with-embeds prefill variant
                "mm": [np.asarray(m) for m in mm] if mm else None,
                "greedy": greedy,
            })
        t_call = self.events.now()
        packed_d, tok_d = self._dispatch_prefill(
            tokens, table, prefix, chunk, samp, seeds, counters, with_top,
            mm=mm, owner=owner, greedy=greedy, samples=samples,
        )
        dry = self._dispatched_dry(self._inflight is None
                                   or self._inflight.packed_d.is_ready())
        # start the host copy of the prefill result BEFORE the fused
        # decode dispatches enqueue: on a FIFO-ish transfer path the copy
        # then rides right behind the prefill, keeping TTFT at prefill
        # latency instead of the whole fused chain's
        self._start_host_copy(packed_d)
        if short:
            self._meet_short_prefill(len(items), with_top, greedy,
                                     table.shape[1])
        # the dispatch is committed: account the computed tokens NOW so a
        # fused decode chain, and the next plan if this step stays in
        # flight, start from current positions (errors reset all state via
        # _recover_after_error anyway).  Planned items may
        # have been PREEMPTED by a later item's page reservation in the
        # same schedule() pass — those rows compute into the trash page
        # and must not be accounted (their num_computed was reset)
        for it in items:
            if it.seq.status == "running":
                self.scheduler.chunk_dispatched(it.seq, it.chunk_len)
        fused = self._maybe_fuse_decode(items, B, tok_d, samp, seeds,
                                        counters, with_top)
        # frees are deferred while this dispatch is unfetched: a sequence
        # aborted while its step is in flight keeps its pages until that
        # step is consumed (and a prefill-token EOS must not hand pages
        # back under a fused decode table: `_prefill_consume`)
        if self.scheduler.deferred_free is None:
            self.scheduler.deferred_free = []
        overlapped = int(self._inflight is not None)
        self.prefill_steps_total += 1
        self.prefill_rows_total += len(items)
        self.prefill_steps_overlapped_total += overlapped
        self.prefill_steps_headless_total += 1 - head
        # a decoder-hybrid-decoder runs the cross half of its layers with
        # the head, on every row of a step in which any row samples
        cross = ({"cross_rows": head * len(items)}
                 if self.model_cfg.cross_decoder else {})
        self.cross_rows_total += cross.get("cross_rows", 0)
        attrs = dict(
            batch=len(items),
            tokens=int(sum(it.chunk_len for it in items)),
            fused_blocks=len(fused) if fused else 0,
            ctx=int((prefix + chunk).max()),
            pages=table.shape[1] - self.layout.state_cols,
            bucket=chunk_bucket,
            attn=self._attn_of("prefill_attention", B, chunk_bucket,
                               table.shape[1]),
            overlapped=overlapped, head=head, seq=ordinal, dry=dry,
            hop_us=hop_us, **cross, **self._scan_of(B, chunk_bucket))
        if len(items) > 1:
            # a shared step: each row's own chunk and context, in row
            # order (`tokens` is their sum, `ctx` the longest)
            attrs["toks"] = [it.chunk_len for it in items]
            attrs["ctxs"] = [it.chunk_start + it.chunk_len for it in items]
        # the dispatch half ends at the reading the account goes on from
        t_sent = self._acct_ns = self.events.now()
        return _PrefillStep(
            items=items, item_rows=item_rows, seq_rows=seq_rows, seqs=seqs,
            with_top=with_top, packed_d=packed_d, fused=fused,
            t0_ns=t0_ev, t_call_ns=t_call, t_sent_ns=t_sent, attrs=attrs)

    def _meet_short_prefill(self, rows: int, with_top: bool, greedy: bool,
                            width: int) -> None:
        """Behind the FIRST short step of a (program variant, table width):
        run the short-step program of the OTHER row count, once, on pad
        rows alone (1-token chunks into the trash page).  A short step is
        one sequence's, or the constant row count of a step that several
        share (`_prefill_rows`), always at the short bucket
        (`_prefill_arrays`): two programs a variant and table width.  Which
        of the two a chunk gets is a matter of timing (who else is ready at
        its plan); that a short chunk of this width was served is not.  So
        whoever has served one has both, and none is compiled because two
        requests happened to arrive together, or happened not to."""
        key = (with_top, greedy, width)
        if key in self._short_prefill_met:
            return
        self._short_prefill_met.add(key)
        self._note_dispatch()  # the ordinal after the step's own
        pad = [None] * self.layout.pad_batch(
            self.cfg.prefill_batch_size if rows == 1 else 1)
        self._dispatch_prefill(
            np.zeros((len(pad), self.cfg.short_chunk_bucket), np.int32),
            np.zeros((len(pad), width), np.int32),
            np.zeros((len(pad),), np.int32), np.ones((len(pad),), np.int32),
            self._samp_arrays(pad), *self._seed_arrays(pad), with_top,
            greedy=greedy, samples=self._sampling_rows(pad)[0])

    @affine("step")
    def _prefill_consume(self, step: "_PrefillStep") -> None:
        """Second half of a prefill step: fetch, account, deliver, record.
        Its slice runs from the step's own `t0_ns` to here."""
        self._step_t0_ns = step.t0_ns
        B = len(step.item_rows)
        t_fetch = t_got = None
        moe_attrs = {}
        # what was deferred while the step was in flight (sequences aborted
        # by a plan made meanwhile) waits for the fetch below.  A sequence
        # that finishes ON this result frees its pages at once, before its
        # last delta goes out, as in a step that was never held: its own
        # program is done, and it is in no newer step's table.  Only a
        # fused decode chain keeps them back, as before
        aborted = self.scheduler.deferred_free or []
        self.scheduler.deferred_free = [] if step.fused else None
        fetch_hop_us = 0
        try:
            t_fetch = self.events.now()
            # what brought the fetch here since the loop's account ended:
            # the pump's hand-off (`_consume_inflight`), or the return from
            # the dispatch half of the step behind this one (its operands
            # are released there)
            fetch_hop_us = (t_fetch - self._acct_ns) // 1000
            # lint: allow(device-get): prefill results are consumed on-step by design — decode, not prefill, is the latency path
            packed = np.asarray(jax.device_get(step.packed_d))
            t_got = self.events.now()
            moe_attrs = self._note_moe(
                packed, B * step.attrs["bucket"], step.attrs["tokens"])
            out, logp, tids, tlps = self._unpack_rows(
                packed, B, step.with_top, blocks=self.layout.prefill_blocks,
            )
            for i, it in enumerate(step.item_rows):
                if it is None:
                    continue
                s = it.seq
                if s.status != "running":  # preempted after planning
                    continue
                # no further than this step wrote: the sequence's next
                # chunk may be dispatched already
                self.scheduler.commit_full_pages(
                    s, it.chunk_start + it.chunk_len)
                if it.samples:
                    self._append_token(
                        s, int(out[i]), float(logp[i]),
                        _tops_for(s, tids, tlps, i),
                    )
            if step.fused:
                self._consume_decode(step.fused, step.seq_rows, B,
                                     step.with_top)
        finally:
            if self._inflight is step:
                self._inflight = None
                self.scheduler.in_flight = ()
            # none of these is in the table of a newer step in flight: they
            # finished here, or were aborted before that step was planned
            deferred = aborted + (self.scheduler.deferred_free or [])
            self.scheduler.deferred_free = (
                None if self._inflight is None else [])
            if deferred:
                self.pool.free(deferred)
            attrs = dict(step.attrs, **moe_attrs)
            if len(step.items) == 1:
                attrs["rid"] = step.items[0].seq.request_id
            if step.fused:
                attrs["n_steps"] = int(step.fused[0].shape[0])
            if fetch_hop_us:
                attrs["fetch_hop_us"] = fetch_hop_us
            self.events.record(
                "prefill_chunk", t0_ns=step.t0_ns, **attrs,
                **self._step_phases(step.seqs, step.t0_ns, step.t_call_ns,
                                    t_fetch, t_got, t_sent=step.t_sent_ns),
            )

    def _maybe_fuse_decode(self, items, B, tok_d, samp, seeds, counters,
                           with_top):
        """Dispatch the first decode chain straight off the prefill's
        device-side sampled tokens, skipping the prefill fetch barrier
        (one device→host sync saved per request — the prefill result and
        the first decode block come back together).
        Returns the decode dispatches, or [] when the batch is not
        eligible."""
        seqs = [it.seq for it in items]
        hard_cap = self.cfg.hard_cap
        if (
            not self.cfg.fuse_prefill_decode
            or self.cfg.speculative_ngram_k > 0  # spec drafts need the
            # fetched prefill token; the verify path starts next dispatch
            or self.layout.lockstep  # followers replay from host arrays only
            or not items
            or not all(it.samples for it in items)
            or any(s.status != "running" for s in seqs)  # preempted rows
            or B not in self.cfg.decode_batch_buckets  # tok_d has B rows
            or any(s.opts.penalized for s in seqs)  # counts need the
            # prefill token; take the plain path
            or any(s.opts.max_tokens <= 1 for s in seqs)
            or any(s.num_computed >= hard_cap for s in seqs)
        ):
            return []
        # same gating as _chain_ok block 0: nothing else needs the pump,
        # and every sequence's pages extend without preemption.  Other
        # running sequences with PENDING prefills also veto fusion — the
        # scheduler should plan mixed dispatches so their TTFT doesn't
        # sit behind a committed decode chain (bench r5: a 4×64-step
        # fused chain cost concurrent ISL-2000 prompts seconds of TTFT)
        if (self._pending_aborts or self._pending_ops
                or self.scheduler.waiting):
            return []
        if any(not s.prefill_done for s in self.scheduler.running
               if s not in seqs):
            return []
        if self.tiered is not None and self.tiered.pending_offloads:
            return []
        # the fused chain is a decode dispatch for ladder purposes: it
        # rides the scheduler's ramp rung (eligibility above guarantees
        # no prompts are pending, so this is never the forced-short
        # case).  PEEK first — the page extension below may still abort
        # the fusion, and an aborted dispatch must not consume a rung
        T, allow_chain = self.scheduler.peek_decode_rung()
        if not all(
            self.scheduler.try_extend_pages(
                s, min(s.num_computed + T, hard_cap)
            )
            for s in seqs
        ):
            return []
        self.scheduler.commit_decode_rung()
        chain_len = 1
        while (allow_chain and chain_len < max(1, self.cfg.decode_chain)
               and self._chain_ok(seqs, chain_len, T, hard_cap)):
            chain_len += 1
        self._note_dispatch(T, blocks=chain_len)
        positions = np.zeros((B,), np.int32)
        decode_ctr = np.zeros((B,), np.int32)
        for i, s in enumerate(seqs):
            positions[i] = s.num_computed
            decode_ctr[i] = counters[i] + 1  # past the prefill sample
        # fusion runs only on identity row layouts (disabled when pooled),
        # so the prefill rows double as decode rows
        table = self._table_array(
            seqs + [None] * (B - len(seqs))
        )  # includes extended pages
        rope_off = self._rope_array(seqs + [None] * (B - len(seqs)))
        return self._dispatch_decode(
            tok_d, positions, decode_ctr, None, table, samp, seeds,
            False, with_top, chain_len, rope_off=rope_off,
            greedy=self._is_greedy(seqs), n_steps=T,
        )

    def _consume_decode(self, dispatches, rows, Bb, with_top,
                        clock=None) -> None:
        """Fetch + account a decode chain's outputs over a row layout
        (callers manage deferred frees around in-flight dispatches).
        `clock` (a list) receives the first block's fetch start and end on
        the ring's clock, even if accounting raises later; the further
        blocks of a chain count as delivery.

        Rows that provably cannot stop inside the block take a BATCH
        path: one extend + one page commit + one delivery for the whole
        T-token block instead of T Python iterations — at decode_steps
        64-96 × chain 4 a single plan carries thousands of tokens, and
        the per-token loop (check_stop + queue item each) was a
        measurable share of serving throughput on real chips."""
        for packed_d in dispatches:
            t_fetch = time.monotonic_ns()
            # lint: allow(device-get): per-block fetch overlaps host consume with the next in-flight block; the cc path drains async
            packed = np.asarray(jax.device_get(packed_d))
            if clock is not None and not clock:
                clock[:] = t_fetch, time.monotonic_ns()
            out, logp, tids, tlps = self._unpack_rows(
                packed, Bb, with_top, blocks=self.layout.decode_blocks,
            )  # [T, B] each
            T = out.shape[0]
            for i, s in enumerate(rows):
                if s is None or s.status != "running":
                    continue
                if (
                    s.opts.ignore_eos
                    and not s.opts.stop_token_ids
                    and not s.opts.stop_sequences
                    and len(s.output_tokens) + T < s.opts.max_tokens
                    and s.total_len + T < self.cfg.max_model_len
                    and s.num_computed + T <= self.cfg.hard_cap
                ):
                    first = not s.output_tokens
                    s.num_computed += T
                    s.output_tokens.extend(int(x) for x in out[:, i])
                    if first:  # a first token CAN ride a decode block
                        # (e.g. future paths without a prefill sample) —
                        # keep the TTFT attribution complete
                        self._note_first_token(s)
                    self.scheduler.commit_full_pages(s)
                    self._deliver_block(s, out[:, i], logp[:, i],
                                        tids, tlps, i, with_top)
                    continue
                for t in range(T):
                    s.num_computed += 1
                    self.scheduler.commit_full_pages(s)
                    self._append_token(
                        s, int(out[t, i]), float(logp[t, i]),
                        _tops_for(s, tids, tlps, (t, i)),
                    )
                    if s.status != "running":
                        break  # stop hit mid-block; rest discarded

    def _deliver_block(self, seq: Sequence, toks, logps, tids, tlps,
                       col: int, with_top: bool,
                       finish_reason: Optional[str] = None) -> None:
        """One queue item for a whole decode block (fast path: the block
        was appended without per-token stop checks — either none can hit,
        or the device-side mask already cut the block at the stop and
        `finish_reason` rides the same delta)."""
        queue = self._queues.get(seq.request_id)
        if queue is None:
            return
        out = {
            "token_ids": [int(x) for x in toks],
            "finish_reason": finish_reason,
        }
        if seq.opts.logprobs:
            out["log_probs"] = [float(x) for x in logps]
        k = seq.opts.top_logprobs
        if with_top and k and tids is not None:
            out["top_logprobs"] = [
                _tops_for(seq, tids, tlps, (t, col))
                for t in range(len(out["token_ids"]))
            ]
        if seq.ttft_attr is not None:
            # one-shot TTFT attribution (see _deliver)
            out["ttft"] = seq.ttft_attr
            seq.ttft_attr = None
        if seq.incidents:
            # forensics: engine-side stalls (preempt park/resume, KV
            # onboard) ride the next delta for the frontend's waterfall
            out["incidents"] = seq.incidents
            seq.incidents = []
        if finish_reason:
            self._close_decode_span(seq, finish_reason)
        self._post_threadsafe(queue, out)

    def _post_threadsafe(self, queue, out) -> None:
        """Hop a delta from the step thread back to the consumer's loop.
        The loop may already be closed when a caller timed out and tore
        down mid-step — swallow that instead of cascading (a straggler
        step's delivery has no consumer anyway)."""
        try:
            self._loop.call_soon_threadsafe(queue.put_nowait, out)
        except RuntimeError:
            if not self._loop.is_closed():
                raise

    @affine("step")
    def _run_mixed(self, plan: StepPlan) -> None:
        """One dispatch: bounded prefill chunk + decode block (the mixed
        plan).  Decode rows' pages were reserved preemptively at planning;
        prefill rows extended non-preemptively, so the two sides cannot
        invalidate each other."""
        t0_ev, hop_us = self._step_begins()
        items, dseqs = plan.prefill, plan.decode
        # prefill side (same array construction as _run_prefill)
        item_rows = self._prefill_rows(items)
        Bp = len(item_rows)
        pseq_rows = [it.seq if it else None for it in item_rows]
        p_tokens, p_prefix, p_chunk, chunk_bucket = self._prefill_arrays(
            item_rows)
        pseqs = [it.seq for it in items]
        p_table = self._table_array(pseq_rows)
        p_seeds, p_ctr = self._seed_arrays(pseq_rows)
        p_samp = self._samp_arrays(pseq_rows)
        # decode side (same as _run_decode, chain_len fixed at 1)
        d_rows = self._decode_rows(dseqs)
        Bd = len(d_rows)
        d_tokens, d_pos = self._decode_arrays(d_rows)
        d_seeds, d_ctr = self._seed_arrays(d_rows)
        d_table = self._table_array(d_rows)
        penalized = any(s.opts.penalized for s in dseqs)
        with_top = any(
            s.opts.top_logprobs > 0 for s in pseqs + dseqs
        )
        d_samp = self._samp_arrays(d_rows)
        counts = self._counts_array(d_rows) if penalized else None
        d_rope = self._rope_array(d_rows)
        greedy_m = self._is_greedy(pseq_rows) and self._is_greedy(d_rows)
        p_samples, head = self._sampling_rows(item_rows)
        # a mixed plan means prompts are pending by construction, so the
        # ladder policy picks the shortest rung — the prefill side's NEXT
        # chunk (or the next waiting prompt) rides the following dispatch
        # one short block from now
        T, _ = self.scheduler.select_decode_rung()
        ordinal = self._note_dispatch(T)
        if self.layout.lockstep:
            sparse = (self._encode_counts_sparse(d_rows)
                      if penalized else None)
            self._lockstep_send({
                "kind": "mixed", "penalized": penalized,
                "with_top": with_top,
                "arrays": [p_tokens, p_table, p_prefix, p_chunk,
                           *[np.asarray(a) for a in p_samp], p_seeds, p_ctr,
                           d_tokens, d_pos, d_ctr, d_table,
                           *[np.asarray(a) for a in d_samp], d_seeds],
                "counts_sparse": sparse,
                "rope_off": d_rope,
                "greedy": greedy_m,
                "n_steps": T,
            })
        t_call = self.events.now()
        p_packed_d, d_packed_d = self._dispatch_mixed(
            p_tokens, p_table, p_prefix, p_chunk, p_samp, p_seeds, p_ctr,
            d_tokens, d_pos, d_ctr, counts, d_table, d_samp, d_seeds,
            penalized, with_top, rope_off=d_rope, greedy=greedy_m,
            n_steps=T, p_samples=p_samples,
        )
        # dispatch committed: account prefill chunks now (consume order
        # below matches the device program: prefill first, then decode)
        for it in items:
            if it.seq.status == "running":
                self.scheduler.chunk_dispatched(it.seq, it.chunk_len)
        t_fetch = self.events.now()
        # lint: allow(device-get): mixed-step prefill half, consumed on-step like _run_prefill
        p_packed = np.asarray(jax.device_get(p_packed_d))
        t_got = self.events.now()
        moe_attrs = self._note_moe(
            p_packed, Bp * chunk_bucket,
            int(sum(it.chunk_len for it in items)))
        p_out, p_logp, p_tids, p_tlps = self._unpack_rows(
            p_packed, Bp, with_top, blocks=self.layout.prefill_blocks,
        )
        for i, it in enumerate(item_rows):
            if it is None:
                continue
            s = it.seq
            if s.status != "running":
                continue
            self.scheduler.commit_full_pages(s)
            if it.samples:
                self._append_token(
                    s, int(p_out[i]), float(p_logp[i]),
                    _tops_for(s, p_tids, p_tlps, i),
                )
        self._consume_decode([d_packed_d], d_rows, Bd, with_top)
        self.events.record(
            "mixed_step", t0_ns=t0_ev, rung=T, n_steps=T, blocks=1,
            prefill_batch=len(items), decode_batch=len(dseqs),
            prefill_tokens=int(sum(it.chunk_len for it in items)),
            decode_rows=len(dseqs),
            ctx=int(max((p_prefix + p_chunk).max(), d_pos.max() + T)),
            pages=(max(p_table.shape[1], d_table.shape[1])
                   - self.layout.state_cols),
            bucket=chunk_bucket,
            attn=self._attn_of("prefill_attention", Bp, chunk_bucket,
                               p_table.shape[1]),
            head=head, seq=ordinal, dry=self._dispatched_dry(True),
            hop_us=hop_us, **moe_attrs, **self._scan_of(Bp, chunk_bucket),
            **self._step_phases(pseqs + dseqs, t0_ev, t_call, t_fetch,
                                t_got),
        )

    def _dispatch_mixed(self, p_tokens, p_table, p_prefix, p_chunk, p_samp,
                        p_seeds, p_ctr, d_tokens, d_pos, d_ctr, d_counts,
                        d_table, d_samp, d_seeds, penalized, with_top,
                        rope_off=None, greedy=False, n_steps=None,
                        p_samples=None):
        """Issue the jitted mixed step (identical on leader and followers);
        returns the two packed device outputs.  `p_samples` is the prefill
        side's `_sampling_rows`; None: every row samples."""
        step = self.layout.mixed_step(penalized, with_top, greedy, n_steps)
        put = self.layout.put_rows
        cts_d = put(d_counts) if penalized else None
        rope = self._rope_operand(rope_off, d_pos)
        p_packed, d_packed, self.kv = step(
            self.params, self.kv,
            put(p_tokens), put(p_table), put(p_prefix), put(p_chunk),
            self.layout.put_samp(p_samp), put(p_seeds), put(p_ctr),
            None if p_samples is None else self.layout.put(p_samples),
            put(d_tokens), put(d_pos), put(d_ctr), cts_d, put(d_table),
            self.layout.put_samp(d_samp), put(d_seeds),
            *rope,
        )
        for a in (p_packed, d_packed):  # they ride back in fetch order
            self._start_host_copy(a)
        return p_packed, d_packed

    def _attach_mm(self, seq, request) -> Optional[str]:
        """Validate + attach multimodal pixels OR precomputed patch
        embeddings to a sequence; returns an error string instead of
        raising (engine errors are streamed).  The embeds path is the
        EPD split: a dedicated encode worker ran the tower
        (disagg/encode.py), so THIS worker needs no vision tower."""
        import hashlib

        if request.get("mm_embeds"):
            e = request["mm_embeds"]
            try:
                arr = np.frombuffer(
                    e["data"], np.float32
                ).reshape(e["shape"]).copy()
            except (KeyError, TypeError, ValueError):
                return "malformed mm_embeds payload"
            offsets = list(request.get("mm_offsets") or [])
            if arr.ndim != 3 or arr.shape[0] != len(offsets):
                return "mm_embeds/mm_offsets mismatch"
            if arr.shape[2] != self.model_cfg.hidden_size:
                return (
                    f"mm_embeds width {arr.shape[2]} != model hidden "
                    f"size {self.model_cfg.hidden_size}"
                )
            P = arr.shape[1]
            for off in offsets:
                if (not isinstance(off, int) or isinstance(off, bool)
                        or not 0 <= off <= len(seq.prompt) - P):
                    return "mm_offsets must be integer offsets inside the prompt"
            seq.mm_embeds = arr
            seq.mm_offsets = offsets
            salt = request.get("cache_salt")
            seq.cache_salt = salt if isinstance(salt, str) and salt else (
                hashlib.blake2b(arr.tobytes(), digest_size=8).hexdigest()
            )
            return None
        if request.get("mm_patches"):
            return self._attach_mm_qwen(seq, request)
        if self.vision is None:
            return "this worker has no vision tower attached"
        from ..llm.multimodal import unpack_pixels
        from ..models.vision import VisionConfig

        _, vcfg = self.vision
        if not isinstance(vcfg, VisionConfig):
            # e.g. mm_pixels sent to a qwen2_vl (dynamic-resolution)
            # tower — the fixed-shape checks below would AttributeError
            return ("this worker's vision tower takes mm_patches "
                    "(dynamic resolution), not mm_pixels")
        try:
            pixels = unpack_pixels(request["mm_pixels"])
        except Exception:  # noqa: BLE001 — wire payloads are untrusted
            return "malformed mm_pixels payload"
        offsets = list(request.get("mm_offsets") or [])
        if pixels.ndim != 4 or pixels.shape[0] != len(offsets):
            return "mm_pixels/mm_offsets mismatch"
        if pixels.shape[1:] != (vcfg.image_size, vcfg.image_size, 3):
            return (
                f"image shape {pixels.shape[1:]} != tower input "
                f"({vcfg.image_size}, {vcfg.image_size}, 3)"
            )
        P = vcfg.num_patches
        for off in offsets:
            if (not isinstance(off, int) or isinstance(off, bool)
                    or not 0 <= off <= len(seq.prompt) - P):
                return "mm_offsets must be integer offsets inside the prompt"
        seq.mm_pixels = pixels
        seq.mm_offsets = offsets
        # same tokens + same image bytes → same hashes (legal reuse);
        # different image → disjoint cache namespace.  Prefer the
        # preprocessor's salt (the router scored overlap with it); the
        # local hash is the fallback for direct engine callers
        salt = request.get("cache_salt")
        seq.cache_salt = salt if isinstance(salt, str) and salt else (
            hashlib.blake2b(pixels.tobytes(), digest_size=8).hexdigest()
        )
        return None

    def _attach_mm_qwen(self, seq, request) -> Optional[str]:
        """Dynamic-resolution (qwen2_vl) media: per-medium patch blobs +
        grids; M-RoPE positions/delta derive from the placeholder runs."""
        import hashlib

        from ..llm.multimodal import unpack_patches
        from ..models.qwen_vl import (
            Qwen2VLVisionConfig, merged_tokens, mrope_positions_from_runs,
        )

        if self.vision is None:
            return "this worker has no vision tower attached"
        _, vcfg = self.vision
        if not isinstance(vcfg, Qwen2VLVisionConfig):
            return "mm_patches requires a qwen2_vl vision tower"
        if not self.model_cfg.mrope_section:
            return "mm_patches requires an mrope language model"
        offsets = list(request.get("mm_offsets") or [])
        blobs = request["mm_patches"]
        if len(blobs) != len(offsets):
            return "mm_patches/mm_offsets mismatch"
        patches, grids, runs = [], [], []
        h = hashlib.blake2b(digest_size=8)
        try:
            for blob, off in zip(blobs, offsets):
                arr, grid = unpack_patches(blob)
                t, gh, gw = grid
                if arr.ndim != 2 or arr.shape[1] != vcfg.patch_dim:
                    return "patch width != tower patch_dim"
                if (arr.shape[0] != t * gh * gw
                        or gh % vcfg.spatial_merge_size
                        or gw % vcfg.spatial_merge_size):
                    return "patch count does not match the grid"
                n = merged_tokens(grid, vcfg)
                if (not isinstance(off, int) or isinstance(off, bool)
                        or not 0 <= off <= len(seq.prompt) - n):
                    return ("mm_offsets must be integer offsets inside "
                            "the prompt")
                patches.append(arr)
                grids.append(grid)
                runs.append((off, grid))
                h.update(np.ascontiguousarray(arr).tobytes())
        except (KeyError, TypeError, ValueError):
            return "malformed mm_patches payload"
        # runs must tile disjoint spans — an overlap would silently put
        # the position streams and the embeds at different indices
        spans = sorted(
            (off, off + merged_tokens(g, vcfg)) for off, g in runs
        )
        for (_, end), (nxt, _) in zip(spans, spans[1:]):
            if nxt < end:
                return "mm_offsets overlap"
        try:
            pos, delta = mrope_positions_from_runs(
                len(seq.prompt), runs, vcfg
            )
        except ValueError as e:
            return str(e)
        seq.mm_patches = patches
        seq.mm_grids = grids
        seq.mm_offsets = offsets
        seq.mm_positions = pos
        seq.rope_delta = delta
        salt = request.get("cache_salt")
        seq.cache_salt = salt if isinstance(salt, str) and salt else (
            h.hexdigest()
        )
        return None

    def _encode_mm(self, seq) -> None:
        """Run the vision tower for a sequence (step thread, between
        dispatches)."""
        from ..models.qwen_vl import Qwen2VLVisionConfig

        vparams, vcfg = self.vision
        if isinstance(vcfg, Qwen2VLVisionConfig):
            from ..models.qwen_vl import encode_patches

            if self._encode_fn is None:
                # one compiled program per grid shape (dynamic resolution
                # buckets naturally by smart-resized grid).  LRU-bounded:
                # real traffic produces a near-continuous grid space and
                # each novel grid costs a trace+compile on the step
                # thread — the cap keeps a long-lived worker's executable
                # set (and that stall frequency, via reuse) bounded
                from collections import OrderedDict

                self._encode_fn = OrderedDict()
            embeds = []
            for arr, grid in zip(seq.mm_patches, seq.mm_grids):
                fn = self._encode_fn.get(grid)
                if fn is None:
                    # lint: allow(jit-static-drift): cache keyed by grid in self._encode_fn (LRU 64) — the loop only builds on miss
                    fn = _ljit(
                        lambda p, px, g=grid: encode_patches(p, vcfg, px, g)
                    )
                    self._encode_fn[grid] = fn
                    if len(self._encode_fn) > 64:
                        self._encode_fn.popitem(last=False)
                else:
                    self._encode_fn.move_to_end(grid)
                embeds.append(np.asarray(
                    # lint: allow(device-get): mm encode is prefill-side onboarding; embeds must be host np before chunk packing
                    jax.device_get(fn(vparams, jnp.asarray(arr)))
                ))
            seq.mm_embeds = embeds
            seq.mm_patches = None
            return
        if self._encode_fn is None:
            from ..models.vision import encode_images

            self._encode_fn = _ljit(
                lambda p, px: encode_images(p, vcfg, px)
            )
        seq.mm_embeds = np.asarray(
            # lint: allow(device-get): mm encode is prefill-side onboarding; embeds must be host np before chunk packing
            jax.device_get(self._encode_fn(vparams, jnp.asarray(seq.mm_pixels)))
        )
        seq.mm_pixels = None

    def _mm_arrays(self, item_rows, B, chunk_bucket):
        """Build (extra_embeds [B,S,h], mask [B,S]) covering every media
        patch run intersecting this chunk (chunked prefill may slice
        through a run).  mm_embeds is [N, P, h] for fixed-resolution
        (clip) towers or a LIST of [P_i, h] for dynamic resolution.  For
        mrope models a third array carries the per-token (t, h, w) rope
        streams [B, 3, S] — text rows get their sequential positions so
        one with-mm program serves mixed batches exactly."""
        h = self.model_cfg.hidden_size
        mrope = bool(self.model_cfg.mrope_section)
        extra = np.zeros((B, chunk_bucket, h), np.float32)
        mask = np.zeros((B, chunk_bucket), bool)
        pos = np.zeros((B, 3, chunk_bucket), np.int32) if mrope else None
        for i, it in enumerate(item_rows):
            if it is None:
                continue
            s = it.seq
            if mrope:
                lo, hi = it.chunk_start, it.chunk_start + it.chunk_len
                if s.mm_positions is not None:
                    w = min(hi, s.mm_positions.shape[1]) - lo
                    if w > 0:
                        pos[i, :, :w] = s.mm_positions[:, lo:lo + w]
                    # rows may extend past the precomputed prompt span
                    # only via bucket padding; pad positions are inert
                else:
                    pos[i, :, :] = lo + np.arange(chunk_bucket)
            if s.mm_embeds is None:
                continue
            per_img = (
                [e for e in s.mm_embeds]
                if isinstance(s.mm_embeds, list)
                else [s.mm_embeds[n] for n in range(s.mm_embeds.shape[0])]
            )
            for emb, off in zip(per_img, s.mm_offsets):
                P = emb.shape[0]
                lo = max(off, it.chunk_start)
                hi = min(off + P, it.chunk_start + it.chunk_len)
                if hi > lo:
                    extra[i, lo - it.chunk_start : hi - it.chunk_start] = (
                        emb[lo - off : hi - off]
                    )
                    mask[i, lo - it.chunk_start : hi - it.chunk_start] = True
        if mrope:
            return extra, mask, pos
        return extra, mask

    def _dispatch_prefill(self, tokens, table, prefix, chunk, samp, seeds,
                          counters, with_top, mm=(), owner=None,
                          greedy=False, samples=None):
        """Issue the jitted prefill (identical on leader and followers).
        Returns (packed_d, tok_d): the packed host-fetchable result and
        the sampled tokens as a device int32 carry.  `owner` rides along
        only for partitioned-pool sp prefill (rows shard over dp; the
        owner array names each row's sp slot).  `samples` is
        `_sampling_rows`'s; None: every row samples."""
        put = partial(self.layout.put_rows, prefill=True)
        packed_d, tok_d, kv = self.layout.prefill_step(
            with_top, bool(mm), greedy)(
            self.params,
            self.kv,
            put(tokens),
            put(table),
            put(prefix),
            put(chunk),
            self.layout.put_samp(samp, prefill=True),
            put(seeds),
            put(counters),
            None if samples is None else self.layout.put(samples),
            *map(put, mm),
            *self.layout.prefill_tail(table, prefix, owner),
        )
        self.kv = kv
        return packed_d, tok_d

    def _chain_ok(self, seqs: List[Sequence], k: int, T: int, hard_cap: int) -> bool:
        """May decode block k be dispatched before block k-1's results are
        fetched?  Only when nothing else needs the pump, at least one
        sequence can still use the block, and every page can grow without
        preemption (preempting would invalidate in-flight tables).

        A RUNNING sequence with its prefill still pending blocks chaining
        too: a committed multi-block chain would starve that prompt for
        the whole chain (at ISL-2000 a 4×64-step chain held a concurrent
        prompt's TTFT hostage for seconds — bench r5); breaking the chain
        lets the scheduler plan a mixed dispatch instead."""
        if self._pending_aborts or self._pending_ops or self.scheduler.waiting:
            return False
        if any(not s.prefill_done for s in self.scheduler.running):
            return False
        if self.tiered is not None and self.tiered.pending_offloads:
            return False
        if all(
            min(s.opts.max_tokens - len(s.output_tokens),
                hard_cap - s.num_computed) <= k * T
            for s in seqs
        ):
            return False
        return all(
            self.scheduler.try_extend_pages(
                s, min(s.num_computed + (k + 1) * T, hard_cap)
            )
            for s in seqs
        )

    # -- speculative decoding (n-gram draft + fused verify) ------------------ #

    def _spec_acceptance_rate(self) -> float:
        """Rolling acceptance over the recent verify dispatches."""
        drafted = sum(d for d, _ in self._spec_window)
        if not drafted:
            return 0.0
        return sum(a for _, a in self._spec_window) / drafted

    def _spec_ok(self, seqs: List[Sequence]) -> bool:
        """May this decode batch take the draft-verify path?  Falls back
        to the plain block per dispatch: partitioned/pp/sp pools keep
        their own step layouts, penalties need sequential count updates
        the fused verify cannot thread, top-logprobs rows want the full
        packed layout, and rows within k+1 tokens of the context cap
        would write drafts past their page-table horizon."""
        k = self.cfg.speculative_ngram_k
        if k <= 0 or not self.layout.runs_spec:
            return False
        if any(s.opts.penalized or s.opts.top_logprobs > 0 for s in seqs):
            return False
        return all(
            s.num_computed + k + 1 <= self.cfg.hard_cap for s in seqs
        )

    def _run_spec_decode(self, seqs: List[Sequence]) -> None:
        """One draft-verify dispatch: host n-gram drafts feed the fused
        (k+1)-position verify forward; the accepted prefix plus the
        model's own sample at the first divergence come back in one
        fetch and are consumed through the ordinary per-token stop
        path (variable acceptance == variable tokens per dispatch)."""
        k = self.cfg.speculative_ngram_k
        t0_ev, hop_us = self._step_begins()
        ordinal = self._note_dispatch()
        rows = self._decode_rows(seqs)
        B = len(rows)
        tokens = np.zeros((B, k + 1), np.int32)
        positions = np.zeros((B,), np.int32)
        for i, s in enumerate(rows):
            if s is None:
                continue
            tokens[i, 0] = s.output_tokens[-1] if s.output_tokens else (
                s.prompt[-1] if s.prompt else 0
            )
            tokens[i, 1:] = _ngram_draft(
                s.all_tokens(), k, self.cfg.speculative_min_match,
                self.cfg.speculative_max_match, self.cfg.speculative_history,
            )
            positions[i] = s.num_computed
        seeds, counters = self._seed_arrays(rows)
        table = self._table_array(rows)
        samp = self._samp_arrays(rows)
        rope_off = self._rope_array(rows)
        greedy = self._is_greedy(rows)
        if self.layout.lockstep:
            self._lockstep_send({
                "kind": "spec", "greedy": greedy,
                "arrays": [tokens, positions, counters, table,
                           *[np.asarray(a) for a in samp], seeds],
                "rope_off": rope_off,
            })
        t_call = self.events.now()
        packed_d = self._dispatch_spec(
            tokens, positions, counters, table, samp, seeds, greedy,
            rope_off=rope_off,
        )
        t_fetch = self.events.now()
        # lint: allow(device-get): spec verify needs accept counts on host to commit tokens; one packed fetch per dispatch
        packed = np.asarray(jax.device_get(packed_d))
        t_got = self.events.now()
        out, logp, n_acc = _unpack_spec(packed, B, k + 1)
        moe_attrs = self._note_moe(packed, B * (k + 1), len(seqs) * (k + 1))
        self._spec_dispatch_total += 1
        drafted = accepted = 0
        live: List[tuple] = []
        for i, s in enumerate(rows):
            if s is None or s.status != "running":
                continue
            a = int(n_acc[i])
            drafted += k
            accepted += a
            s.spec_draft_tokens += k
            s.spec_accepted_tokens += a
            live.append((i, s, a))
        # totals are published BEFORE any token is appended: _append_token
        # hands the finishing token to the waiting generator, whose caller
        # may read metrics() the moment it wakes — the dispatch counter
        # above and these totals must never be observable half-updated
        self._spec_draft_total += drafted
        self._spec_accepted_total += accepted
        self._spec_window.append((drafted, accepted))
        for i, s, a in live:
            for t in range(a + 1):
                s.num_computed += 1
                self.scheduler.commit_full_pages(s)
                self._append_token(s, int(out[i, t]), float(logp[i, t]))
                if s.status != "running":
                    break  # stop hit inside the accepted run; rest discarded
        self.events.record(
            "spec_round", t0_ns=t0_ev, k=k,
            batch=len(seqs), drafted=drafted, accepted=accepted,
            ctx=int(positions.max()) + k + 1, pages=table.shape[1],
            bucket=B, seq=ordinal, dry=self._dispatched_dry(True),
            hop_us=hop_us, **moe_attrs,
            **self._step_phases(seqs, t0_ev, t_call, t_fetch, t_got))

    def _dispatch_spec(self, tokens, positions, counters, table, samp,
                       seeds, greedy, rope_off=None):
        """Issue the jitted draft-verify step (identical on leader and
        followers); returns the packed device output."""
        step = self.layout.spec_step(greedy)
        put = self.layout.put_rows
        rope = self._rope_operand(rope_off, positions)
        packed_d, self.kv = step(
            self.params, self.kv,
            put(tokens),
            put(positions),
            put(table),
            self.layout.put_samp(samp),
            put(seeds),
            put(counters),
            *rope,
        )
        self._start_host_copy(packed_d)
        return packed_d

    @affine("step")
    def _run_decode(self, seqs: List[Sequence]) -> None:
        # the planner (loop thread) pipelines against this executor: a
        # sequence it scheduled may have stopped during the step that was
        # in flight, and its pages may already be freed — dispatching such
        # a row would read recycled KV and skew per-dispatch telemetry
        seqs = [s for s in seqs if s.status == "running"]
        if not seqs:
            return
        if self._spec_ok(seqs):
            return self._run_spec_decode(seqs)
        # block ladder: the scheduler picks this dispatch's block size —
        # full blocks while the prompt queue is empty, the shortest rung
        # (chaining suppressed) while prompts are pending, so a waiting
        # prompt rides the next mixed dispatch within one short block
        t0_ev, hop_us = self._step_begins()
        T, allow_chain = self.scheduler.select_decode_rung()
        if (allow_chain and self.cfg.decode_continuous
                and self.layout.runs_continuous):
            # device-resident loop: rungs stay the scan lengths — the
            # ladder's quiet-ramp top rung is where open-ended chaining
            # engages; short rungs (prompts pending) keep the per-
            # dispatch path so admission latency is unchanged
            return self._run_decode_continuous(seqs, T, t0_ev, hop_us)
        hard_cap = self.cfg.hard_cap
        # decide the chain length upfront and pre-reserve pages for the
        # whole horizon, so ONE page table serves every block: chained
        # dispatches pipeline only when block k+1's varying inputs are
        # exactly block k's device-side outputs (any fresh host buffer
        # mid-chain serializes the chain on its upload)
        chain_len = 1
        while (allow_chain and chain_len < max(1, self.cfg.decode_chain)
               and self._chain_ok(seqs, chain_len, T, hard_cap)):
            chain_len += 1
        ordinal = self._note_dispatch(T, blocks=chain_len)
        rows = self._decode_rows(seqs)
        Bb = len(rows)
        tokens, positions = self._decode_arrays(rows)
        seeds, counters = self._seed_arrays(rows)
        table = self._table_array(rows)
        penalized = any(s.opts.penalized for s in seqs)
        with_top = any(s.opts.top_logprobs > 0 for s in seqs)
        samp = self._samp_arrays(rows)
        # histograms updated on-device within and across chained blocks
        counts = self._counts_array(rows) if penalized else None
        rope_off = self._rope_array(rows)
        if self.layout.lockstep:
            # penalized plans carry the output tokens SPARSELY (flat list +
            # row offsets) — broadcasting the dense [B, vocab] histogram
            # would put ~4MB/step on the plan channel at a 128k vocab
            sparse = (self._encode_counts_sparse(rows)
                      if penalized else None)
            self._lockstep_send({
                "kind": "decode", "penalized": penalized,
                "with_top": with_top, "chain_len": chain_len,
                "arrays": [tokens, positions, counters, table,
                           *[np.asarray(a) for a in samp], seeds],
                "counts_sparse": sparse,
                "rope_off": rope_off,
                "greedy": self._is_greedy(rows),
                "n_steps": T,
            })
        t_call = self.events.now()
        dispatches = self._dispatch_decode(
            tokens, positions, counters, counts, table, samp, seeds,
            penalized, with_top, chain_len, rope_off=rope_off,
            greedy=self._is_greedy(rows), n_steps=T,
        )
        # page frees deferred until the whole chain drains: an in-flight
        # dispatch must never see its table's pages reallocated (unchained
        # decode keeps the synchronous free — consumers may observe pool
        # state right after their finish_reason arrives)
        deferred = [] if len(dispatches) > 1 else None
        self.scheduler.deferred_free = deferred
        clock = []  # the first block's fetch: [start, end]
        try:
            self._consume_decode(dispatches, rows, Bb, with_top, clock)
        finally:
            self.scheduler.deferred_free = None
            if deferred:
                self.pool.free(deferred)
            self.events.record(
                "decode_block", t0_ns=t0_ev, rung=T, n_steps=T,
                blocks=chain_len, batch=len(seqs), chain=chain_len,
                ctx=int(positions.max()) + T * chain_len,
                pages=table.shape[1] - self.layout.state_cols, bucket=Bb,
                attn=self._attn_of("decode_attention", Bb, 1,
                                   table.shape[1]),
                seq=ordinal, dry=self._dispatched_dry(True),
                hop_us=hop_us,
                **self._decode_moe_form(Bb),
                **({"rid": seqs[0].request_id} if len(seqs) == 1 else {}),
                **self._step_phases(seqs, t0_ev, t_call,
                                    *(clock or (None, None))),
            )

    def _dispatch_decode(self, tokens, positions, counters, counts, table,
                         samp, seeds, penalized, with_top, chain_len,
                         rope_off=None, greedy=False, n_steps=None):
        """Issue the chained decode dispatches (identical on leader and
        followers); returns the per-block packed outputs."""
        step = self.layout.decode_step(penalized, with_top, greedy, n_steps)
        put = self.layout.put_rows
        tok_d = put(tokens)
        pos_d = put(positions)
        ctr_d = put(counters)
        table_d = put(table)
        samp_d = self.layout.put_samp(samp)
        seeds_d = put(seeds)
        rope = self._rope_operand(rope_off, positions)
        cts_d = put(counts) if penalized else None
        dispatches = []
        for _ in range(chain_len):
            packed_d, tok_d, pos_d, ctr_d, cts_d, self.kv = step(
                self.params, self.kv, tok_d, pos_d, ctr_d, cts_d,
                table_d, samp_d, seeds_d, *rope,
            )
            self._start_host_copy(packed_d)  # overlaps later blocks' compute
            dispatches.append(packed_d)
        return dispatches

    # -- device-resident decode loop (continuous chaining) -------------------- #

    def _ensure_drain_pool(self):
        if self._drain_pool is None:
            import concurrent.futures as _cf

            self._drain_pool = _cf.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="jax-engine-drain",
                initializer=xla_ledger.thread_role_init,
            )
        return self._drain_pool

    def _stop_arrays(self, rows: List[Optional[Sequence]]) -> np.ndarray:
        """Per-row device stop-token ids ([B, K] int32, -1-padded, K a
        pow2 bucket): the row's stop_token_ids plus the engine eos set
        unless ignore_eos.  Multi-token stop SEQUENCES are not here — the
        host detects those at consume and forces chain fall-out."""
        sets = []
        for s in rows:
            if s is None:
                sets.append([])
                continue
            ids = set(s.opts.stop_token_ids)
            if not s.opts.ignore_eos:
                ids.update(self.eos_token_ids)
            sets.append(sorted(ids))
        K = max(1, max((len(x) for x in sets), default=1))
        K = 1 << (K - 1).bit_length()
        out = np.full((len(rows), K), -1, np.int32)
        for i, ids in enumerate(sets):
            out[i, : len(ids)] = ids
        return out

    def _seq_budget(self, s: Sequence) -> int:
        """Tokens `s` may still emit before a LENGTH stop — the same
        bound `check_stop` enforces (max_tokens, model window,
        page-table horizon).  ONE definition, shared by the device
        budget operand and the horizon pre-reservation: a drift between
        the two desyncs the on-device stop mask from the reserved
        tables.  For a CHUNK row still mid-prompt (num_computed <
        prompt_len) emissions begin only after the prompt completes, so
        the page-table term counts from prompt_len — the exact budget
        the split engine would compute after its prefill."""
        return max(0, min(
            s.opts.max_tokens - len(s.output_tokens),
            self.cfg.max_model_len - s.total_len,
            self.cfg.hard_cap - max(s.num_computed, s.prompt_len),
        ))

    def _budget_array(self, rows: List[Optional[Sequence]]) -> np.ndarray:
        """Per-row `_seq_budget` ([B] int32), precomputed so the device
        can latch length stops without the host in the loop."""
        out = np.zeros((len(rows),), np.int32)
        for i, s in enumerate(rows):
            if s is not None:
                out[i] = self._seq_budget(s)
        return out

    def _cc_reserve(self, seqs: List[Sequence], T: int,
                    inflight_blocks: int = 0) -> int:
        """Watermark page pre-reservation: grow every running row's
        pages up to `cc_horizon_blocks` decode blocks ahead WITHOUT
        preemption and without dipping into the admission watermark,
        then return how many more whole blocks the resulting tables
        cover for every row (rows whose remaining budget already fits
        under their table never constrain).  `inflight_blocks` accounts
        for dispatched-but-undrained blocks whose tokens the host has
        not yet folded into num_computed."""
        ps = self.cfg.page_size
        hard_cap = self.cfg.hard_cap
        horizon = self.cfg.cc_horizon_blocks
        allowance = horizon
        for s in seqs:
            if s.status != "running":
                continue
            # chunk rows still owe prompt writes before their first
            # emission — reserving only against the emission budget
            # would starve a long prompt whose max_tokens is small
            remaining = (max(0, s.prompt_len - s.num_computed)
                         + self._seq_budget(s))
            target = min(s.num_computed + (inflight_blocks + horizon) * T,
                         s.num_computed + remaining, hard_cap)
            self.scheduler.try_extend_pages(s, target, keep_watermark=True)
            covered = (min(len(s.pages) * ps, hard_cap) - s.num_computed
                       - inflight_blocks * T)
            if remaining - inflight_blocks * T > covered:
                allowance = min(allowance, max(0, covered) // T)
        return allowance

    def _cc_fall_out(self, seqs: List[Sequence],
                     splice: bool = False) -> Optional[str]:
        """The chain's fall-out signals (None = keep feeding the loop):
        anything else needing the pump, an ADMISSIBLE waiting prompt
        (`_admit_check` via `admission_ready`), or any co-scheduled row
        having stopped (drained stop flags / host stop sequences) — a
        stop frees capacity and shrinks the batch, so replanning wins.
        With `splice` (chunked prefill in-chain enabled) plain "add"
        intake and admissible waiting prompts are NOT fall-outs — the
        step thread's `_cc_intake` handles both at the next block and
        falls the chain out itself only when it cannot splice."""
        if self._closed:
            return "shutdown"
        pending_adds = self._pending_adds
        if splice:
            pending_adds = [e for e in pending_adds if e[0] != "add"]
        if pending_adds or self._pending_aborts or self._pending_ops:
            return "pending_work"
        if (not splice and self.scheduler.waiting
                and self.scheduler.admission_ready()):
            return "admit"
        if self.scheduler.preempt_ready():
            # an interactive prompt is starved behind batch decodes:
            # fall out so the pump can park a victim and admit it —
            # parking (device→host export) only happens at plan time,
            # never mid-chain (splice is a chunk-row feed, resume is a
            # device KV import)
            return "preempted"
        if any(s.status != "running" for s in seqs):
            return "stop"
        if self.tiered is not None and self.tiered.pending_offloads:
            return "offload"
        # only the co-scheduled rows' contexts (O(batch), not O(every
        # live stream) — this check sits inside the sub-0.1ms-target
        # inter-block host gap); other streams' graceful stops are
        # _plan_step's job after fall-out anyway
        for s in seqs:
            ctx = self._contexts.get(s.request_id)
            if ctx is not None and ctx.is_stopped() and not ctx.is_killed():
                return "cancel"
        return None

    @affine("drain")
    def _fetch_packed_cc(self, packed_d, Bb: int, with_top: bool):
        """Drain-thread half of the double buffer: block device_get +
        numpy unpack off the step thread, so block k's host fetch rides
        under block k+1's compute.  Scheduler state is NOT touched here
        — consumption stays on the step thread."""
        return _unpack_out_cc(
            np.asarray(jax.device_get(packed_d)), Bb, with_top
        )

    @affine("step")
    def _cc_intake(self, rows: List[Optional[Sequence]],
                   seqs: List[Sequence], penalized: bool, with_top: bool,
                   greedy: bool) -> Tuple[List[int], Optional[str]]:
        """Step-thread admission intake for the running chain: drain
        LEADING plain "add" entries from `_pending_adds` into the
        scheduler (legal — `Scheduler.add` is @affine("step","loop"),
        and the pump never plans while the chain's step task runs;
        non-"add" entries stay for the pump and trip "pending_work"),
        then splice every admissible waiting prompt into a free padding
        slot of the current batch bucket.  Returns (spliced slot
        indices, fall-out reason): "admit" when an admissible prompt
        exists but cannot ride this chain — no free slot in the bucket,
        or its sampling needs a different compiled variant (penalized /
        top-logprobs / greedy are compile-time booleans of the running
        program) — so the pump re-plans with the right shape."""
        while (self._pending_adds
               and self._pending_adds[0][0] == "add"):
            _, seq = self._pending_adds.pop(0)
            self.scheduler.add(seq)
        spliced: List[int] = []
        while self.scheduler.waiting and self.scheduler.admission_ready():
            head = self.scheduler.waiting[0]
            if head.parked:
                # resuming needs a device KV import at plan time — it
                # cannot ride the chain as a chunk-row splice
                return spliced, "admit"
            so = head.opts
            if ((greedy and so.temperature > 0)
                    or (not penalized and so.penalized)
                    or (not with_top and so.top_logprobs > 0)):
                return spliced, "admit"
            try:
                slot = rows.index(None)
            except ValueError:
                return spliced, "admit"
            seq = self.scheduler.splice_admit()
            if seq is None:  # raced an abort / capacity change
                break
            rows[slot] = seq
            seqs.append(seq)
            spliced.append(slot)
        return spliced, None

    def _cc_plan_feed(self, rows: List[Optional[Sequence]], T: int,
                      needs_reset, fed_complete):
        """Plan this block's chunk-row feeds: every mid-prompt row gets
        up to T prompt tokens from the shared per-block
        `prefill_chunk_tokens` budget, clamped to its (watermark-
        respecting) page coverage.  Fed tokens are committed into
        `num_computed` AT DISPATCH (the `_run_prefill` contract) —
        except the prompt-COMPLETING token, whose write is accounted by
        the first emission's drain exactly like the split engine's
        prefill→decode handoff (prefill leaves its sampled token's KV
        to the first decode step).  Rows in `needs_reset` carry their
        splice reset (init pos/budget) on their first fed block.
        Returns None on a quiet block (nothing to feed, no reset
        pending) so the steady path re-puts no host arrays."""
        ps = self.cfg.page_size
        hard_cap = self.cfg.hard_cap
        budget = int(self.cfg.prefill_chunk_tokens)
        Bb = len(rows)
        toks = rem = smp = None
        rst = ipos = ibud = None
        for i, s in enumerate(rows):
            if s is None or s.status != "running" or id(s) in fed_complete:
                continue
            left = s.prompt_len - s.num_computed
            if left <= 0 or budget <= 0:
                continue
            n = min(T, left, budget)
            # pages must cover every position this block can write for
            # the row: fed tokens plus a completing row's same-block
            # decode tail — one block is at most T writes from here
            self.scheduler.try_extend_pages(
                s, min(s.num_computed + T, hard_cap), keep_watermark=True)
            covered = len(s.pages) * ps - s.num_computed
            n = min(n, max(0, covered))
            if n <= 0:
                continue
            if toks is None:
                toks = np.zeros((Bb, T), np.int32)
                rem = np.zeros((Bb,), np.int32)
                smp = np.zeros((Bb,), bool)
                rst = np.zeros((Bb,), bool)
                ipos = np.zeros((Bb,), np.int32)
                ibud = np.zeros((Bb,), np.int32)
            toks[i, :n] = s.prompt[s.num_computed:s.num_computed + n]
            rem[i] = n
            completing = n == left
            smp[i] = completing
            if i in needs_reset:
                # first fed block after the splice: reset the slot's
                # carried pos/ctr/counts/budget in-step
                rst[i] = True
                ipos[i] = s.num_computed
                ibud[i] = self._seq_budget(s)
                needs_reset.discard(i)
            budget -= n
            if completing:
                # the last prompt token's write rides the first
                # emission's drain (split-engine prefill handoff);
                # guard re-feeding it until that drain lands
                s.num_computed += n - 1
                fed_complete.add(id(s))
            else:
                s.num_computed += n
        if toks is None:
            return None
        return toks, rem, smp, rst, ipos, ibud

    @affine("step")
    def _run_decode_continuous(self, seqs: List[Sequence], T: int,
                               t0_ev: int, hop_us: int) -> None:
        """The device-resident decode inner loop (docs/device_loop.md):
        an OPEN-ENDED chain of decode blocks whose varying inputs (last
        token, positions, counters, active mask, budgets, penalty
        counts) live on device — the host's only per-block work is
        issuing the next dispatch, handing the previous block to the
        drain thread, and checking the fall-out signals.  Stops are
        detected on device (active-row mask), so the host never
        re-checks per token; pages are pre-reserved `cc_horizon_blocks`
        ahead so one page table serves the rolling horizon; the chain
        ends only on a fall-out signal or when every row finishes.

        In the loop's account the chain is its `decode_chain` slice, from
        `_run_decode`'s start (`t0_ev`; `hop_us` is the hand-off before
        it): inside it, the time before an iteration's slice is that
        iteration's build, and what follows the last one is delivery."""
        from collections import deque as _deque

        rows = self._decode_rows(seqs)
        seqs = list(seqs)  # chain-local: splices append without
        # aliasing the caller's plan list
        Bb = len(rows)
        tokens, positions = self._decode_arrays(rows)
        seeds, counters = self._seed_arrays(rows)
        penalized = any(s.opts.penalized for s in seqs)
        with_top = any(s.opts.top_logprobs > 0 for s in seqs)
        samp = self._samp_arrays(rows)
        counts = self._counts_array(rows) if penalized else None
        rope_off = self._rope_array(rows)
        greedy = self._is_greedy(rows)
        budget = self._budget_array(rows)
        active = np.array([s is not None and budget[i] > 0
                           for i, s in enumerate(rows)])
        step = self.layout.cc_step(penalized, with_top, greedy, T)
        put = self.layout.put_rows
        drain = self._ensure_drain_pool()
        splice_on = self.cfg.prefill_chunk_tokens > 0
        # _plan_decode reserved decode_advance (>= T) preemptively, so
        # the first block always fits even when the watermark blocks
        # further growth
        allowance = max(1, self._cc_reserve(seqs, T))
        table_d = put(self._table_array(rows))
        tok_d = put(tokens)
        pos_d = put(positions)
        ctr_d = put(counters)
        act_d = put(active)
        budget_d = put(budget)
        stops_d = put(self._stop_arrays(rows))
        samp_d = self.layout.put_samp(samp)
        seeds_d = put(seeds)
        cts_d = put(counts) if penalized else None
        rope = self._rope_operand(rope_off, positions)
        # quiet-block chunk operands, put ONCE and reused: a steady
        # block ships no fresh host buffer (a fresh buffer mid-chain
        # serializes the chain on its upload)
        z_toks_d = put(np.zeros((Bb, T), np.int32))
        z_i32_d = put(np.zeros((Bb,), np.int32))
        z_bool_d = put(np.zeros((Bb,), bool))
        quiet_chunk = (z_toks_d, z_i32_d, z_bool_d, z_bool_d, z_i32_d,
                       z_i32_d)
        needs_reset: set = set()  # guarded-by: step thread (chain-local)
        fed_complete: set = set()  # guarded-by: step thread (chain-local)
        inflight: Any = _deque()
        deferred: List[int] = []
        self.scheduler.deferred_free = deferred
        blocks = 0
        # None until a fall-out signal fires: a chain that dies before
        # its first check records "error", never a clean reason
        fallout = None
        # counted at ENTRY (like the per-dispatch block counter): a
        # reader polling metrics() mid-chain sees the engaged loop
        # instead of zero until the teardown drain finishes
        self._cc_chains_total += 1
        try:
            while True:
                # -- splice intake + chunk feed (host work BEFORE the
                # slice's t0, so it lands in the inter-block gap the
                # timeline attributes to the tagged splice slice) ----- #
                splice_fall = None
                spliced: List[int] = []
                if splice_on:
                    spliced, splice_fall = self._cc_intake(
                        rows, seqs, penalized, with_top, greedy)
                    for i in spliced:
                        needs_reset.add(i)
                    if spliced:
                        # per-row operands now cover the new rows; the
                        # carried device state is reset in-step by the
                        # reset overlay on their first fed block
                        samp_d = self.layout.put_samp(self._samp_arrays(rows))
                        seeds_d = put(self._seed_arrays(rows)[0])
                        stops_d = put(self._stop_arrays(rows))
                        rope = self._rope_operand(
                            self._rope_array(rows), positions)
                feed = (self._cc_plan_feed(rows, T, needs_reset,
                                           fed_complete)
                        if splice_on else None)
                if feed is not None:
                    toks, rem, smp, rst, ipos, ibud = feed
                    chunk_ops = (
                        put(toks),
                        put(rem),
                        put(smp),
                        put(rst),
                        put(ipos),
                        put(ibud),
                    )
                    chunk_rows = int((rem > 0).sum())
                else:
                    chunk_ops = quiet_chunk
                    chunk_rows = 0
                if spliced or feed is not None:
                    # splices/feeds may have grown page lists
                    table_d = put(self._table_array(rows))
                t_iter = self._step_t0_ns = self.events.now()
                (packed_d, tok_d, pos_d, ctr_d, act_d, budget_d, cts_d,
                 self.kv) = step(
                    self.params, self.kv, tok_d, pos_d, ctr_d, cts_d,
                    act_d, budget_d, stops_d, table_d, samp_d, seeds_d,
                    *chunk_ops, *rope,
                )
                # one query a block still undrained, never a wait
                dry = self._dispatched_dry(
                    all(p.is_ready() for _, _, p in inflight))
                self._start_host_copy(packed_d)
                t_sent = self.events.now()
                blocks += 1
                allowance -= 1
                # live per-dispatch count: a reader polling metrics()
                # mid-chain (or right after its tokens arrive, before
                # the chain's trailing blocks drain) sees the blocks
                # already issued instead of zero
                self._cc_blocks_total += 1
                ordinal = self._note_dispatch(T, blocks=1)
                # pair every drain future with the rows it was
                # dispatched against: pre-splice blocks must consume
                # against the row set that produced them
                inflight.append(
                    (list(rows),
                     drain.submit(self._fetch_packed_cc, packed_d, Bb,
                                  with_top), packed_d))
                # double buffer: with two blocks undrained, consume the
                # older one (its device_get overlapped this dispatch)
                wait_ns = 0
                while len(inflight) >= 2:
                    rows_snap, fut, _ = inflight.popleft()
                    t_wait = self.events.now()
                    fetched = fut.result()
                    wait_ns += self.events.now() - t_wait
                    self._consume_cc_block(fetched, rows_snap, with_top)
                fallout = splice_fall or self._cc_fall_out(
                    seqs, splice=splice_on)
                # one decode_block slice per ITERATION (dispatch + drain
                # handoff + fall-out checks): the gap to the next slice
                # is the host's non-overlapped inter-block time — the
                # quantity runtime.timeline.decode_host_gaps derives.
                # Splice/feed iterations are tagged so the timeline can
                # separate the handshake from true host gaps.
                attrs = {}
                if spliced or chunk_rows:
                    attrs["splice"] = True
                if chunk_rows:
                    attrs["chunk_rows"] = chunk_rows
                born = xla_ledger.births_between(t_iter, t_sent)
                if born:
                    attrs["compiled"] = born
                # phases as on every step slice; the splice and feed work
                # above t_iter is outside the slice, so build_us is 0, and
                # fetch_us is the wait for the drain thread's device_get
                t_end = self.events.now()
                self._credit_own(seqs, t_end - t_iter)
                self.events.record(
                    "decode_block", t0_ns=t_iter, rung=T, n_steps=T,
                    blocks=1, batch=len(seqs), chain=blocks,
                    continuous=True, build_us=0,
                    dispatch_us=(t_sent - t_iter) // 1000,
                    fetch_us=wait_ns // 1000,
                    deliver_us=(t_end - t_sent - wait_ns) // 1000,
                    pages=table_d.shape[1], bucket=Bb, seq=ordinal,
                    dry=dry,
                    **self._decode_moe_form(Bb), **attrs)
                if fallout is not None:
                    break
                if allowance < 1:
                    # rolling horizon exhausted: re-reserve and push a
                    # fresh table (the one host input a long chain ever
                    # rebuilds, once per cc_horizon_blocks blocks)
                    allowance = self._cc_reserve(
                        seqs, T, inflight_blocks=len(inflight))
                    if allowance < 1:
                        # the watermark reserve held back for waiting
                        # prompts is what the extension refused for:
                        # record the trigger, not the symptom
                        fallout = ("admission" if self.scheduler.waiting
                                   else "pages")
                        break
                    table_d = put(self._table_array(rows))
        finally:
            err = None
            while inflight:
                rows_snap, fut, _ = inflight.popleft()
                try:
                    self._consume_cc_block(fut.result(), rows_snap,
                                           with_top)
                except Exception as e:  # noqa: BLE001 — drain the window
                    # before surfacing (later futures must not leak)
                    err = err or e
            self.scheduler.deferred_free = None
            if deferred:
                self.pool.free(deferred)
            reason = fallout or "error"
            self._cc_fallout_by_reason[reason] = (
                self._cc_fallout_by_reason.get(reason, 0) + 1)
            t_end = self._acct_ns = self.events.now()
            self.events.record("decode_chain", t0_ns=t0_ev, t1_ns=t_end,
                               rung=T, batch=len(seqs), blocks=blocks,
                               fallout=reason, hop_us=hop_us)
            if err is not None:
                raise err

    def _consume_cc_block(self, fetched, rows: List[Optional[Sequence]],
                          with_top: bool) -> None:
        """Account one drained continuous block: the emitted flags say
        exactly which tokens are real and where each row stopped, so
        rows without host-only stop SEQUENCES take a batch path — one
        extend + one stop check + one delivery per block.  A stop
        detected here was latched ON DEVICE in the same step (the mask
        froze the row before any later block wrote its pages), so the
        row's pages free immediately instead of waiting for chain
        fall-out."""
        out, logp, flags, tids, tlps = fetched  # [T, B] each
        for i, s in enumerate(rows):
            if s is None or s.status != "running":
                continue
            # the emitted steps are NOT always a block prefix: a chunk
            # row's feeding steps emit nothing, so a prompt completing
            # MID-block emits on the tail only (completing step + its
            # same-block decode steps) — index by the flags, never by
            # an assumed [0, emitted) range
            steps = np.nonzero(flags[:, i])[0]
            emitted = int(steps.size)
            if emitted == 0:
                continue
            if s.opts.stop_sequences:
                # multi-token stops are invisible to the device mask:
                # per-token host path; a hit finishes the row (pages
                # deferred — in-flight blocks still write them) and the
                # finished status trips chain fall-out
                for t in steps:
                    s.num_computed += 1
                    self.scheduler.commit_full_pages(s)
                    self._append_token(
                        s, int(out[t, i]), float(logp[t, i]),
                        _tops_for(s, tids, tlps, (t, i)),
                    )
                    if s.status != "running":
                        break
                continue
            first = not s.output_tokens
            s.num_computed += emitted
            s.output_tokens.extend(int(x) for x in out[steps, i])
            if first:
                self._note_first_token(s)
            self.scheduler.commit_full_pages(s)
            reason = self.scheduler.check_stop(s, self.eos_token_ids)
            if reason:
                # device-latched stop (eos/stop-id via the mask, length
                # via the budget): no in-flight or future block writes
                # these pages — free NOW, not at chain fall-out
                saved = self.scheduler.deferred_free
                self.scheduler.deferred_free = None
                try:
                    self.scheduler.finish(s, reason)
                finally:
                    self.scheduler.deferred_free = saved
            self._deliver_block(s, out[steps, i], logp[steps, i],
                                tids[steps] if tids is not None else None,
                                tlps[steps] if tlps is not None else None,
                                i, with_top, finish_reason=reason)

    # -- multihost lockstep --------------------------------------------------- #

    def _counts_from_sparse(self, sparse, b: int):
        """Rebuild the [B, vocab] penalty histogram a penalized plan
        broadcasts sparsely (flat token list + row offsets)."""
        if sparse is None:
            return None
        flat, offs = sparse
        counts = np.zeros((b, self.model_cfg.vocab_size), np.float32)
        for i in range(b):
            np.add.at(counts[i], flat[offs[i]:offs[i + 1]], 1.0)
        return counts

    def _lockstep_send(self, desc: Dict[str, Any]) -> None:
        self.layout.broadcast(_plan_pack(desc))

    def follower_loop(self) -> None:
        """Replay the leader's dispatches on this follower rank (blocking;
        returns when the leader broadcasts shutdown).  Every rank of a
        multihost group except rank 0 runs this instead of serving."""
        if not self.layout.lockstep or self.layout.is_leader:
            raise RuntimeError("follower_loop is for multihost ranks > 0")
        samp_n = len(SamplingParams._fields)
        # a follower-local dispatch failure leaves this rank's KV shards
        # diverged from the leader's; the ONLY consistent continuation is
        # the leader's own "recover" plan (it failed too and everyone
        # rebuilds).  Any other plan while poisoned must crash the process
        # rather than stream silently-wrong collectives.
        poisoned = False
        while True:
            desc = _plan_unpack(self.layout.broadcast(b""))
            kind = desc["kind"]
            if kind == "shutdown":
                self._close_blob_channels()
                return
            if kind == "recover":
                self.kv = self.layout.make_kv(self._kv_dtype)
                poisoned = False
                continue
            if poisoned:
                raise RuntimeError(
                    "follower state diverged from the leader (local "
                    "dispatch failed but the leader kept going) — the "
                    "multihost group must restart together"
                )
            try:
                if kind == "prefill":
                    a = desc["arrays"]
                    mm = tuple(desc["mm"]) if desc.get("mm") else ()
                    self._dispatch_prefill(
                        a[0], a[1], a[2], a[3],
                        SamplingParams(*a[4:4 + samp_n]),
                        a[4 + samp_n], a[5 + samp_n], desc["with_top"],
                        mm=mm, owner=desc.get("owner"),
                        greedy=desc.get("greedy", False),
                    )
                elif kind == "decode":
                    a = desc["arrays"]
                    counts = self._counts_from_sparse(
                        desc.get("counts_sparse"), a[0].shape[0]
                    )
                    self._dispatch_decode(
                        a[0], a[1], a[2], counts, a[3],
                        SamplingParams(*a[4:4 + samp_n]), a[4 + samp_n],
                        desc["penalized"], desc["with_top"],
                        desc["chain_len"], rope_off=desc.get("rope_off"),
                        greedy=desc.get("greedy", False),
                        n_steps=desc.get("n_steps"),
                    )
                elif kind == "mixed":
                    a = desc["arrays"]
                    i = 4
                    p_samp = SamplingParams(*a[i:i + samp_n]); i += samp_n
                    p_seeds, p_ctr = a[i], a[i + 1]; i += 2
                    d_tokens, d_pos, d_ctr, d_table = a[i:i + 4]; i += 4
                    d_samp = SamplingParams(*a[i:i + samp_n]); i += samp_n
                    d_seeds = a[i]
                    counts = self._counts_from_sparse(
                        desc.get("counts_sparse"), d_tokens.shape[0]
                    )
                    self._dispatch_mixed(
                        a[0], a[1], a[2], a[3], p_samp, p_seeds, p_ctr,
                        d_tokens, d_pos, d_ctr, counts, d_table, d_samp,
                        d_seeds, desc["penalized"], desc["with_top"],
                        rope_off=desc.get("rope_off"),
                        greedy=desc.get("greedy", False),
                        n_steps=desc.get("n_steps"),
                    )
                elif kind == "spec":
                    a = desc["arrays"]
                    self._dispatch_spec(
                        a[0], a[1], a[2], a[3],
                        SamplingParams(*a[4:4 + samp_n]), a[4 + samp_n],
                        desc["greedy"], rope_off=desc.get("rope_off"),
                    )
                elif kind == "kv_export":
                    self._export_replay(desc["padded"], desc["rank"])
                elif kind == "kv_import":
                    self._import_replay(
                        desc["padded"], desc["rank"], desc["k"], desc["v"]
                    )
                elif kind == "kv_import_fetch":
                    self._import_fetch_replay(
                        desc["padded"], desc["rank"], desc
                    )
                elif kind == "embed":
                    self._embed_replay(desc["tokens"], desc["lens"])
            except Exception:  # noqa: BLE001
                logger.exception(
                    "follower dispatch failed; awaiting leader recover"
                )
                poisoned = True

    # -- disaggregation: KV export / import ---------------------------------- #

    async def embed(self, request: Dict[str, Any],
                    context: Optional[Context] = None) -> Dict[str, Any]:
        """Embedding request: {"embed_token_ids": [[...], ...]} →
        {"embeddings": [[...], ...], "prompt_tokens": N}. Runs between
        engine steps on its own cache-free forward."""
        batches = request.get("embed_token_ids") or []
        if not batches:
            return {"error": "no inputs"}
        try:  # the cache-free forward threads no recurrent state
            require_no_state(self.model_cfg, "the embedding forward")
        except ValueError as e:
            return {"error": str(e)}
        max_len = min(
            max(len(t) for t in batches), self.cfg.max_model_len
        )
        S = bucket_for(max_len, self.cfg.chunk_buckets + [self.cfg.max_model_len])
        B = len(batches)
        tokens = np.zeros((B, S), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, t in enumerate(batches):
            t = t[:S]
            tokens[i, : len(t)] = t
            lens[i] = len(t)

        def op():
            if self.layout.lockstep:
                self._lockstep_send(
                    {"kind": "embed", "tokens": tokens, "lens": lens}
                )
            return self._embed_replay(tokens, lens)

        vecs = await self._device_op(op)
        return {
            "embeddings": [vecs[i].tolist() for i in range(B)],
            "prompt_tokens": int(lens.sum()),
        }

    async def encode_mm(self, request: Dict[str, Any],
                        context: Optional[Context] = None) -> Dict[str, Any]:
        """EPD encode-worker surface: {"mm_pixels": {...}} → patch
        embeddings {"mm_embeds": {shape, data}, "cache_salt": ...}.
        A dedicated encode worker runs the vision tower so serving
        workers don't carry it (reference: trtllm encode_helper /
        sglang encode_worker_handler — SURVEY §2.4)."""
        del context
        if self.vision is None:
            return {"error": "this worker has no vision tower attached"}
        from ..llm.multimodal import unpack_pixels

        import hashlib

        _, vcfg = self.vision
        try:
            pixels = unpack_pixels(request["mm_pixels"])
        except Exception:  # noqa: BLE001 — wire payloads are untrusted
            return {"error": "malformed mm_pixels payload"}
        if (pixels.ndim != 4
                or pixels.shape[1:] != (vcfg.image_size, vcfg.image_size, 3)):
            return {
                "error": f"image shape {pixels.shape[1:]} != tower input "
                         f"({vcfg.image_size}, {vcfg.image_size}, 3)"
            }
        vparams = self.vision[0]

        def op():
            if self._encode_fn is None:
                from ..models.vision import encode_images

                self._encode_fn = _ljit(
                    lambda p, px: encode_images(p, vcfg, px)
                )
            return np.asarray(jax.device_get(
                self._encode_fn(vparams, jnp.asarray(pixels))
            )).astype(np.float32)

        emb = await self._device_op(op)
        return {
            "mm_embeds": {"shape": list(emb.shape), "data": emb.tobytes()},
            # same image bytes → same salt: cache isolation keys match
            # whether the tower ran here or on the serving worker
            "cache_salt": hashlib.blake2b(
                pixels.tobytes(), digest_size=8
            ).hexdigest(),
        }

    def _embed_replay(self, tokens: np.ndarray, lens: np.ndarray) -> np.ndarray:
        """The device half of an embed op (leader and followers run this
        identically; multihost gathers the result to every process)."""
        out = self.layout.embed_step()(
            self.params, self.layout.put(tokens), self.layout.put(lens)
        )
        return np.asarray(jax.device_get(out))

    async def _device_op(self, op):
        """Run a device op between pump steps (never concurrent with
        them).  Under multihost lockstep the typed device ops (KV
        export/import, embed) broadcast themselves on the plan channel
        from inside the op; pool-only ops stay leader-local (followers
        hold no scheduler/pool state)."""
        self._ensure_pump()
        fut = self._loop.create_future()
        self._pending_ops.append((op, fut))
        self._wake.set()
        return await fut

    async def _release_held(self, seq) -> None:
        """Free pages a failed/cancelled remote prefill left held (pool
        mutation goes through the pump like every other page op)."""
        if seq is None or not seq.pages:
            return
        pages, seq.pages = list(seq.pages), []

        def op():
            self.pool.free(pages)

        try:
            await self._device_op(op)
        except Exception:  # noqa: BLE001
            logger.exception("failed to release held pages")

    # -- data-plane helpers (block-ID KV transfer, disagg/transfer.py) ------ #

    @staticmethod
    def _pow2_width(n: int) -> int:
        return 1 << max(0, n - 1).bit_length()

    def _export_dev(self, pages: List[int], width: Optional[int] = None):
        """jit export of page ids → (k, v) device arrays [L, width, ...].
        Partitioned pools take LOCAL ids + the owning rank (a sequence's
        pages always share one rank).  Under multihost lockstep the op is
        broadcast so every rank issues the same jit (disagg composes with
        multihost — reference: disagg_serving.md:110-120)."""
        width = width or self._pow2_width(len(pages))
        padded, rank = self._local_pages(pages, width)
        if self.layout.lockstep:
            self._lockstep_send(
                {"kind": "kv_export", "padded": padded, "rank": rank}
            )
        return self._export_replay(padded, rank)

    def _local_pages(self, pages: List[int], width: int):
        """(ids padded to `width`, owning rank): a partitioned pool takes
        LOCAL ids + the rank (a sequence's pages always share one), any
        other pool the ids as they are and no rank."""
        padded = np.zeros((width,), np.int32)
        if not self.layout.pooled:
            padded[: len(pages)] = pages
            return padded, None
        padded[: len(pages)] = [p % self.cfg.num_pages for p in pages]
        return padded, self.pool.rank_of(pages[0]) if pages else 0

    def _rank_operand(self, rank: Optional[int]) -> tuple:
        return () if rank is None else (self.layout.put(np.int32(rank)),)

    def _export_replay(self, padded: np.ndarray, rank: Optional[int]):
        """The device half of an export (leader and followers run this
        identically)."""
        return self.layout.export_fn(
            self.kv, self.layout.put(padded), *self._rank_operand(rank))

    def _import_dev(self, pages: List[int], kpad, vpad) -> None:
        """jit import of padded (k, v) blobs into the given page ids
        (padding rows hit the trash page).  Multihost: the blob is
        STAGED on the leader and the plan carries only a fetch
        descriptor — each host pulls the byte ranges its devices' KV
        shards need (per-shard fetch, engine/blob_stage.py) instead of
        every host receiving the whole blob."""
        padded, rank = self._local_pages(pages, kpad.shape[1])
        if self.layout.lockstep:
            if isinstance(kpad, jax.Array):
                # lint: allow(device-get): lockstep blob staging needs host bytes; one batched fetch for both planes
                kpad, vpad = map(np.asarray, jax.device_get((kpad, vpad)))
            kpad = np.ascontiguousarray(kpad)
            vpad = np.ascontiguousarray(vpad)
            tid, addr = self._stage_blob(kpad, vpad)
            desc = {"tid": tid, "addr": addr,
                    "shape": list(kpad.shape), "dtype": str(kpad.dtype)}
            self._lockstep_send({
                "kind": "kv_import_fetch", "padded": padded, "rank": rank,
                **desc,
            })
            self._import_fetch_replay(padded, rank, desc,
                                      local=(kpad, vpad))
            return
        self._import_replay(padded, rank, kpad, vpad)

    # -- per-shard blob fetch (multihost imports) ----------------------------- #

    def _stage_blob(self, kpad: np.ndarray, vpad: np.ndarray):
        from .blob_stage import BlobStage

        if self._blob_stage_srv is None:
            self._blob_stage_srv = BlobStage().start()
        import uuid

        tid = uuid.uuid4().hex
        self._blob_stage_srv.stage(
            tid, {"k": kpad, "v": vpad}, acks=jax.process_count() - 1
        )
        return tid, self._blob_stage_srv.address

    def _blob_client(self, addr):
        from .blob_stage import BlobClient

        key = (addr[0], int(addr[1]))
        if key not in self._blob_clients:
            self._blob_clients[key] = BlobClient(addr)
        return self._blob_clients[key]

    def _import_fetch_replay(self, padded: np.ndarray, rank: Optional[int],
                             desc: Dict[str, Any], local=None) -> None:
        """Build the sharded global import blob from per-device slices —
        the leader reads local memory, followers TCP-fetch ONLY the
        ranges their devices own (a non-owner host of a pooled rank
        fetches nothing) — then run the import jit.  Aggregate DCN
        traffic is O(1× blob) instead of O(hosts × blob)."""
        shape = tuple(desc["shape"])  # [L, width, page, kvh, hd]
        client = None if local is not None else self._blob_client(desc["addr"])
        cache: Dict[tuple, np.ndarray] = {}

        def src_slice(name: str, lo: int, hi: int) -> np.ndarray:
            key = (name, lo, hi)
            if key not in cache:
                if local is not None:
                    arr = local[0] if name == "k" else local[1]
                    cache[key] = np.ascontiguousarray(arr[:, :, :, lo:hi])
                else:
                    cache[key] = client.fetch(desc["tid"], name, lo, hi)
            return cache[key]

        k_blob, v_blob = (
            self.layout.import_blob(shape, np.dtype(desc["dtype"]), rank,
                                    partial(src_slice, name))
            for name in ("k", "v"))
        # pp×kv_partition: the KV layer axis is pp-sharded, and the
        # layout's import slices the blob by stage (a dp-only pooled import
        # would reshard every stage's cache to full layers: pp× HBM spike)
        self.kv = self.layout.import_fn(sharded_blob=self.layout.pooled)(
            self.kv, k_blob, v_blob, self.layout.put(padded),
            *self._rank_operand(rank))
        if client is not None:
            client.ack(desc["tid"])

    def _import_replay(self, padded: np.ndarray, rank: Optional[int],
                       kpad, vpad) -> None:
        if isinstance(kpad, jax.Array):
            k_d, v_d = kpad, vpad  # colocated device lane (single-process)
        else:
            k_d, v_d = self.layout.put(kpad), self.layout.put(vpad)
        self.kv = self.layout.import_fn()(
            self.kv, k_d, v_d, self.layout.put(padded),
            *self._rank_operand(rank))

    async def export_pages(self, pages: List[int]):
        """Copy the given pages device->host: ([L,n,page,kv,hd], same) —
        one jit variant per pow2 width."""
        def op():
            k, v = self._export_dev(pages)
            return (
                np.asarray(jax.device_get(k))[:, : len(pages)],
                np.asarray(jax.device_get(v))[:, : len(pages)],
            )

        return await self._device_op(op)

    async def alloc_pages(self, n: int) -> List[int]:
        def op():
            return self.pool.allocate(n)

        return await self._device_op(op)

    async def free_pages(self, pages: List[int]) -> None:
        def op():
            self.pool.free(pages)

        await self._device_op(op)

    async def import_page_chunk(self, pages: List[int], k_chunk, v_chunk) -> None:
        """Write KV pages into the pool at the given page ids (padding
        rows go to trash page 0).  Chunks may be host numpy (the TCP data
        plane) or device arrays (the colocated device lane — padding then
        happens on device and the data never visits the host)."""
        def op():
            n = len(pages)
            width = self._pow2_width(n)
            if isinstance(k_chunk, jax.Array):
                pad = ((0, 0), (0, width - n), (0, 0), (0, 0), (0, 0))
                kpad = jnp.pad(k_chunk, pad)
                vpad = jnp.pad(v_chunk, pad)
                # colocated transfers may arrive sharded over ANOTHER
                # engine's mesh (disagg roles on disjoint device sets in
                # one process — the resharding transfer NIXL performs);
                # device_put moves shards device-to-device (ICI on TPU),
                # never staging through host numpy
                mine = set(self.kv.k.devices())
                if set(kpad.devices()) != mine:
                    target = self.layout.foreign_blob_target(mine)
                    kpad = jax.device_put(kpad, target)
                    vpad = jax.device_put(vpad, target)
                self._import_dev(pages, kpad, vpad)
                return
            kpad = np.zeros((k_chunk.shape[0], width, *k_chunk.shape[2:]),
                            k_chunk.dtype)
            vpad = np.zeros_like(kpad)
            kpad[:, :n] = k_chunk
            vpad[:, :n] = v_chunk
            self._import_dev(pages, kpad, vpad)

        await self._device_op(op)

    def cached_prefix_len(self, prompt: List[int]) -> int:
        """Tokens of this prompt already in the device prefix cache (no
        references taken) — feeds the disagg-router decision."""
        if not self.cfg.enable_prefix_caching or not prompt:
            return 0
        ps = self.cfg.page_size
        hashes = compute_block_hash_for_seq(prompt, ps, self.cfg.block_hash_salt)
        if len(prompt) % ps == 0 and hashes:
            hashes = hashes[:-1]
        return self.pool.peek(hashes) * ps

    async def prefill_remote(self, request: Dict[str, Any],
                             context: Optional[Context] = None,
                             transfer_source=None) -> Dict[str, Any]:
        """Prefill-only: compute the prompt, sample the first token, hand
        the KV pages over.  With `transfer_source` (disagg/transfer.py
        KvTransferSource) the response carries only a block-ID transfer
        descriptor — the data plane moves the pages.  Without it, the KV
        rides inline (legacy/fallback).  The prefill-worker side of
        disaggregation (the reference's remote-prefill handler,
        /root/reference/components/src/dynamo/vllm/handlers.py:236)."""
        request = dict(request)
        request["stop_conditions"] = {
            **(request.get("stop_conditions") or {}), "max_tokens": 1,
        }
        request["_hold_pages"] = True
        context = context or Context()
        first_token = None
        seq = None
        async for out in self.generate(request, context):
            seq = self._seq_by_rid.get(context.id) or seq
            if out.get("finish_reason") == "error":
                await self._release_held(seq)
                return {"error": out.get("error", "prefill failed")}
            if out.get("token_ids"):
                first_token = out["token_ids"][0]
        if seq is None or first_token is None:
            await self._release_held(seq)
            return {"error": "prefill produced no token"}
        if transfer_source is not None:
            pages, seq.pages = list(seq.pages), []
            tid = await transfer_source.register(pages, seq.prompt_len)
            return {
                "token_ids": [first_token],
                "kv_descriptor": transfer_source.descriptor(tid),
            }
        pages = list(seq.pages)
        width = bucket_for(max(len(pages), 1), self.cfg.table_width_buckets)

        def export_op():
            k, v = self._export_dev(pages, width=width)
            k = np.asarray(jax.device_get(k))[:, : len(pages)]
            v = np.asarray(jax.device_get(v))[:, : len(pages)]
            # release the held pages now that the copy is out
            self.pool.free(pages)
            seq.pages = []
            return k, v

        k, v = await self._device_op(export_op)
        return {
            "token_ids": [first_token],
            "kv": {
                "k": k.tobytes(),
                "v": v.tobytes(),
                "dtype": str(k.dtype),
                "shape": list(k.shape),
                "prompt_len": seq.prompt_len,
                "page_size": self.cfg.page_size,
            },
        }

    async def generate_with_kv(
        self, request: Dict[str, Any], first_token: int, kv_blob: Dict[str, Any],
        context: Optional[Context] = None,
    ) -> AsyncIterator[Dict[str, Any]]:
        """Decode-side, inline-blob fallback: import a full KV blob then
        continue decoding. The block-ID path is `generate_imported` fed by
        disagg/transfer.py's KvTransferClient."""
        context = context or Context()
        self._ensure_pump()
        prompt = list(request["token_ids"])
        shape = kv_blob["shape"]
        dtype = np.dtype(kv_blob["dtype"])
        k = np.frombuffer(kv_blob["k"], dtype).reshape(shape)
        v = np.frombuffer(kv_blob["v"], dtype).reshape(shape)
        if kv_blob["page_size"] != self.cfg.page_size:
            yield {"token_ids": [], "finish_reason": "error",
                   "error": "kv import rejected: page_size mismatch on the "
                            "inline path (use the transfer service)"}
            return
        n_pages = shape[1]
        width = bucket_for(max(n_pages, 1), self.cfg.table_width_buckets)

        def import_op():
            pages = self.pool.allocate(n_pages)
            kpad = np.zeros((shape[0], width, *shape[2:]), dtype)
            vpad = np.zeros_like(kpad)
            kpad[:, :n_pages] = k
            vpad[:, :n_pages] = v
            self._import_dev(pages, kpad, vpad)
            return pages

        try:
            pages = await self._device_op(import_op)
        except NoPagesError as e:
            # pool too full to accept the imported prefix right now — the
            # caller falls back to local prefill (which queues normally)
            yield {"token_ids": [], "finish_reason": "error",
                   "error": f"kv import rejected: {e}"}
            return
        async for out in self.generate_imported(
            request, first_token, pages, context
        ):
            yield out

    async def generate_imported(
        self, request: Dict[str, Any], first_token: int, pages: List[int],
        context: Optional[Context] = None,
    ) -> AsyncIterator[Dict[str, Any]]:
        """Adopt pages already written into the pool (by the transfer
        service or the blob path) as a decoded-elsewhere prompt and stream
        the continuation (the reference decode handler's
        post-remote-prefill path, handlers.py:221-231)."""
        context = context or Context()
        self._ensure_pump()
        opts = _opts_from_request(request)
        prompt = list(request["token_ids"])
        seq = Sequence(context.id, prompt, opts)
        seq.seed = opts.seed if opts.seed is not None else self._py_rng.getrandbits(31)
        from ..runtime.tracing import current_trace

        seq.trace = current_trace()  # the disagg handoff's adopted trace
        seq.pages = pages
        if self.layout.pooled and pages:
            seq.kv_rank = self.pool.rank_of(pages[0])
        seq.num_computed = len(prompt)
        seq.num_cached = len(prompt)
        seq.output_tokens = [first_token]
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[context.id] = queue
        self._contexts[context.id] = context
        self._requests_total += 1
        # the remote first token counts toward stop conditions
        reason = self.scheduler.check_stop(seq, self.eos_token_ids)
        yield {"token_ids": [first_token], "finish_reason": reason}
        if reason:
            self.pool.free(seq.pages)
            self._queues.pop(context.id, None)
            self._contexts.pop(context.id, None)
            return
        self._pending_adds.append(("imported", seq))
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
                self._abort(context.id)

    def _recover_after_error(self) -> None:
        """A failed jitted step may have consumed the donated KV buffers;
        rebuild device state so the engine survives (reference behavior:
        engine death → watchdog restart; we recover in-process)."""
        # a step in flight goes with everything else: its sequences are
        # among the running ones, its result is never fetched, and what
        # was deferred for it is freed with the rest
        self._inflight = None
        self.scheduler.in_flight = ()
        deferred, self.scheduler.deferred_free = (
            self.scheduler.deferred_free, None)
        if deferred:
            self.pool.free(deferred)
        for seq in list(self.scheduler.running):
            self.scheduler.finish(seq, "error")
            self._deliver(seq, [], "error")
        if self.layout.lockstep:
            # keep followers lockstep: they rebuild their KV shards too
            self._lockstep_send({"kind": "recover"})
        self.kv = self.layout.make_kv(self._kv_dtype)
        self._evictions_before_reset += self.pool.evictions_total
        self.pool = self.layout.make_pool(self._emit_event)
        for p in getattr(self.pool, "pools", [self.pool]):
            p.events = self.events
        self._emit_event(KvEvent("cleared", []))
        self.scheduler.pool = self.pool
        self.scheduler.state = self.layout.make_state_pool()
        if self.scheduler.state is not None:
            self.scheduler.state.events = self.events
        for seq in self.scheduler.waiting:
            seq.pages = []
            seq.state_slot = seq.state_src = 0
            seq.num_cached = seq.kv_cached = 0
            seq.num_computed = 0
            seq.committed_pages = 0
            seq.block_hashes = []

    def _append_token(self, seq: Sequence, token: int, logprob: float,
                      tops=None) -> None:
        seq.output_tokens.append(token)
        if len(seq.output_tokens) == 1:
            self._note_first_token(seq)
        reason = self.scheduler.check_stop(seq, self.eos_token_ids)
        if reason:
            self.scheduler.finish(seq, reason)
        self._deliver(seq, [token], reason, logprob, tops)

    def _note_first_token(self, seq: Sequence) -> None:
        """Attribute this request's TTFT (block-wait / queue-wait /
        prefill) into the engine totals and stage the per-request dict
        on the sequence — the next delivered delta carries it to the
        frontend (one-shot, unlike the cumulative spec stats: the first
        delta of a stream is always consumed)."""
        if seq.t_first_token is not None or seq.t_arrival is None:
            return
        now_ns = time.monotonic_ns()
        now = now_ns / 1e9
        seq.t_first_token = now
        seen = seq.t_seen if seq.t_seen is not None else seq.t_arrival
        admitted = seq.t_admitted if seq.t_admitted is not None else seen
        # waiting apart from working: the request's own steps (the ones
        # that computed its tokens, the running one up to now) against
        # the rest of admission-to-first-token, in which it was admitted
        # and waited for its turn or between its own chunks
        total_us = max(0, int((now - seq.t_arrival) * 1e6))
        queue_us = min(total_us, max(0, int((admitted - seq.t_arrival) * 1e6)))
        own_ns, steps = seq.own_ns, seq.own_steps
        if self._step_t0_ns is not None:
            own_ns += now_ns - max(self._step_t0_ns, self._slice_end_ns)
            steps += 1
        own_us = min(own_ns // 1000, total_us - queue_us)
        wait_us = total_us - queue_us - own_us
        self.events.record(
            "first_token", rid=seq.request_id, prompt_len=seq.prompt_len,
            cached=seq.num_cached, total_us=total_us, queue_us=queue_us,
            own_us=own_us, steps=steps, wait_us=wait_us)
        attr = {
            "block_wait_ms": max(0.0, (seen - seq.t_arrival) * 1e3),
            "queue_wait_ms": max(0.0, (admitted - seen) * 1e3),
            "prefill_ms": max(0.0, (now - admitted) * 1e3),
        }
        seq.ttft_attr = attr
        self._ttft_block_wait_ms_total += attr["block_wait_ms"]
        self._ttft_queue_wait_ms_total += attr["queue_wait_ms"]
        self._ttft_prefill_ms_total += attr["prefill_ms"]
        self._ttft_turn_wait_ms_total += wait_us / 1e3
        self._ttft_attributed_total += 1
        # milestone spans reconstructed from the attribution timestamps,
        # exported under the request's adopted trace so the engine's TTFT
        # anatomy nests inside the caller's service.handle span
        if seq.trace is not None:
            from ..runtime.tracing import export_span, wall_ns_from_monotonic

            wall = wall_ns_from_monotonic
            export_span("engine.block_wait", seq.trace,
                        wall(seq.t_arrival), wall(seen),
                        block_wait_ms=round(attr["block_wait_ms"], 3))
            export_span("engine.queue_wait", seq.trace,
                        wall(seen), wall(admitted),
                        queue_wait_ms=round(attr["queue_wait_ms"], 3))
            # placed at admission; its length is the SUM of the waits (for
            # the first turn and between the request's own chunks)
            export_span("engine.turn_wait", seq.trace, wall(admitted),
                        wall(admitted + wait_us / 1e6),
                        turn_wait_ms=wait_us / 1e3,
                        own_ms=own_us / 1e3, steps=steps)
            export_span("engine.prefill", seq.trace,
                        wall(admitted), wall(now),
                        prefill_ms=round(attr["prefill_ms"], 3),
                        prompt_len=seq.prompt_len, cached=seq.num_cached)

    def _deliver(
        self,
        seq: Sequence,
        tokens: List[int],
        finish_reason: Optional[str],
        logprob: Optional[float] = None,
        tops=None,
        error: Any = None,
    ) -> None:
        queue = self._queues.get(seq.request_id)
        if queue is None:
            return
        out = {
            "token_ids": tokens,
            "finish_reason": finish_reason,
        }
        if error is not None:
            out["error"] = error
        if logprob is not None and seq.opts.logprobs:
            out["log_probs"] = [logprob]
        if tops is not None:
            out["top_logprobs"] = [tops]  # aligned with token_ids
        if seq.spec_draft_tokens:
            # per-request speculative stats (CUMULATIVE) ride every
            # delta so the frontend can aggregate per-model acceptance
            # on /metrics from the last delta it saw — a stop STRING is
            # detected frontend-side mid-stream, so the engine's final
            # delta may never be consumed
            out["spec"] = {
                "draft_tokens": seq.spec_draft_tokens,
                "accepted_tokens": seq.spec_accepted_tokens,
            }
        if seq.ttft_attr is not None:
            # one-shot TTFT attribution on the first-token delta
            out["ttft"] = seq.ttft_attr
            seq.ttft_attr = None
        if seq.incidents:
            # forensics: engine-side stalls (preempt park/resume, KV
            # onboard) ride the next delta for the frontend's waterfall
            out["incidents"] = seq.incidents
            seq.incidents = []
        if finish_reason:
            self._close_decode_span(seq, finish_reason)
        # may be called from the executor thread — hop back to the loop
        self._post_threadsafe(queue, out)

    def _close_decode_span(self, seq: Sequence, finish_reason: str) -> None:
        """Close the request's engine timeline: one decode-phase span
        (first token → finish) carrying the stream's totals + the TTFT
        attribution, so a single slice answers "where did this request's
        time go" without cross-referencing."""
        if seq.trace is None or seq.t_first_token is None:
            return
        from ..runtime.tracing import export_span, wall_ns_from_monotonic

        attrs = {
            "finish_reason": finish_reason,
            "output_tokens": len(seq.output_tokens),
            "preemptions": seq.preemptions,
        }
        if seq.spec_draft_tokens:
            attrs["spec_draft_tokens"] = seq.spec_draft_tokens
            attrs["spec_accepted_tokens"] = seq.spec_accepted_tokens
        export_span(
            "engine.decode", seq.trace,
            wall_ns_from_monotonic(seq.t_first_token),
            wall_ns_from_monotonic(time.monotonic()), **attrs,
        )


def _tops_for(seq: Sequence, tids, tlps, idx):
    """Slice this sequence's requested top-k (id, logprob) pairs out of the
    packed TOPLP-wide arrays; None when the request didn't ask."""
    k = seq.opts.top_logprobs
    if not k or tids is None:
        return None
    ids = tids[idx] if not isinstance(idx, tuple) else tids[idx[0], idx[1]]
    lps = tlps[idx] if not isinstance(idx, tuple) else tlps[idx[0], idx[1]]
    k = min(k, len(ids))
    return [[int(ids[j]), float(lps[j])] for j in range(k)]


def _opts_from_request(request: Dict[str, Any]) -> SamplingOptions:
    so = request.get("sampling_options", {}) or {}
    sc = request.get("stop_conditions", {}) or {}
    max_tokens = sc.get("max_tokens")
    temperature = so.get("temperature")
    return SamplingOptions(
        # OpenAI default is 1.0 (sampled); explicit 0 means greedy
        temperature=1.0 if temperature is None else temperature,
        top_k=so.get("top_k") or 0,
        top_p=so.get("top_p") if so.get("top_p") is not None else 1.0,
        frequency_penalty=so.get("frequency_penalty") or 0.0,
        presence_penalty=so.get("presence_penalty") or 0.0,
        # None → generate to the context window (Scheduler.add clamps);
        # the legacy-completions 16-token default is the preprocessor's job
        max_tokens=(1 << 30) if max_tokens is None else max_tokens,
        stop_token_ids=sc.get("stop_token_ids") or [],
        stop_sequences=sc.get("stop_sequences") or [],
        ignore_eos=sc.get("ignore_eos") or False,
        logprobs=bool(so.get("logprobs")),
        top_logprobs=int(so.get("top_logprobs") or 0),
        seed=so.get("seed"),
    )
