"""Worker CLI: `python -m dynamo_tpu.worker --control HOST:PORT --model ...`.

The analog of `python -m dynamo.vllm`
(/root/reference/components/src/dynamo/vllm/main.py), except the engine is
first-party JAX.  `--model tiny` builds the deterministic test model +
tokenizer in-process (no downloads); `--mock` runs the MockEngine simulator
(the analog of `python -m dynamo.mocker`).
"""

import time

# the fallback for the process's start, where /proc does not say (the
# package's imports, jax among them, are done by this line)
_T_MAIN_NS = time.monotonic_ns()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402

from ..runtime.events import host_event, install_host_probes  # noqa: E402

# Start-up as slices on the step ring's clock that tile process start ->
# READY (`startup.*`, then the instant `ready`: docs/observability.md),
# and the same phases in seconds: `/metrics.json` `runtime.startup` and the
# `STARTUP {...}` line
_STARTUP: dict = {}
_phase_end_ns = [0]


def _process_start_ns() -> int:
    """This process's start on the monotonic clock: `/proc/self/stat`'s
    start time (field 22: clock ticks since boot, which is where Linux's
    CLOCK_MONOTONIC counts from) or, where that cannot be this process's
    (no /proc, another clock), the first line of this module."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        t = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        if 0 <= _T_MAIN_NS - t < 600 * 1_000_000_000:
            return t
    except (OSError, ValueError, IndexError):
        pass
    return _T_MAIN_NS


def _phase_done(name: str) -> tuple:
    """(start, end) of the start-up phase that ends now: it began where
    the one before it ended, so the phases leave no gap and no overlap."""
    t0, t1 = _phase_end_ns[0], time.monotonic_ns()
    _phase_end_ns[0] = t1
    _STARTUP[name + "_s"] = round((t1 - t0) / 1e9, 3)
    return t0, t1


def _ladder_arg(s: str):
    """Comma-separated rung list for --decode-block-ladder (empty →
    None, i.e. fixed blocks); a clean usage error on malformed input."""
    if not s:
        return None
    try:
        return [int(r) for r in s.split(",") if r.strip()] or None
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"invalid ladder {s!r}: expected comma-separated ints, "
            f"e.g. 1,4,8"
        )


def _chain_arg(s: str):
    """--decode-chain takes an int (fixed chain depth) or the literal
    `continuous` (device-resident open-ended chaining, DYN-style
    continuous-mode toggle — docs/device_loop.md)."""
    if s.strip().lower() == "continuous":
        return "continuous"
    try:
        return int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"invalid --decode-chain {s!r}: expected an int or "
            f"'continuous'"
        )


def build_parser() -> argparse.ArgumentParser:
    """The worker's argparse surface, exposed so deployment graphs and
    recipe tests can validate worker argv without starting a worker."""
    ap = argparse.ArgumentParser(description="dynamo-tpu JAX worker")
    from ..runtime.config import RuntimeConfig

    _env_control = RuntimeConfig.from_env().control
    ap.add_argument("--control", required=not _env_control, default=_env_control)
    ap.add_argument("--model", default="tiny",
                    help="HF checkpoint dir, or 'tiny' for the test model")
    ap.add_argument("--model-name", default=None)
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--component", default="backend")
    ap.add_argument("--endpoint", default="generate")
    ap.add_argument("--mock", action="store_true", help="MockEngine simulator")
    ap.add_argument("--mock-speedup", type=float, default=10.0,
                    help="MockEngine speedup_ratio (with --mock): <1 slows "
                         "the simulator down — chaos scenarios use this to "
                         "make mid-stream kills deterministic")
    ap.add_argument("--vision", default="", choices=["", "tiny"],
                    help="attach a vision tower (multimodal chat); 'tiny' "
                         "pairs the test tower with --model tiny")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=2048)
    ap.add_argument("--num-state-slots", type=int, default=32,
                    help="state slots beside the pages, for a model with "
                         "state-space layers (unused otherwise), trash slot "
                         "0 included: one a running sequence, the rest "
                         "hash-addressed snapshots of a sequence's state")
    ap.add_argument("--max-num-seqs", type=int, default=16)
    ap.add_argument("--max-prefill-tokens", type=int, default=512)
    ap.add_argument("--max-model-len", type=int, default=4096)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    # engine tuning (mirrors EngineConfig; defaults match the dataclass
    # so unchanged launch commands keep their behavior)
    ap.add_argument("--quantization", default="none",
                    choices=["none", "int8"],
                    help="weight-only int8 halves decode's weight reads")
    ap.add_argument("--attention-impl", default="auto",
                    choices=["auto", "adaptive", "pallas", "xla"])
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="tokens decoded per device dispatch (lax.scan); "
                         "stops are applied after the block, so up to N-1 "
                         "tokens past a stop are computed and discarded")
    ap.add_argument("--decode-chain", type=_chain_arg, default=1,
                    help="decode dispatches in flight before fetching, "
                         "or 'continuous' for the device-resident decode "
                         "loop: open-ended chaining with on-device stop "
                         "detection and an async drain — the chain only "
                         "falls back to the host on admission/stop "
                         "events (docs/device_loop.md).  Equivalent to "
                         "--decode-continuous with the default horizon")
    ap.add_argument("--decode-continuous", action="store_true",
                    help="device-resident decode loop (see "
                         "--decode-chain continuous); with an integer "
                         "--decode-chain N, N becomes the page "
                         "pre-reservation horizon in blocks")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="chunked prefill INSIDE the continuous decode "
                         "chain: per-block token budget shared by chunk "
                         "rows, so an admission splices into the running "
                         "chain instead of falling it out "
                         "(docs/device_loop.md).  Default: "
                         "max_prefill_tokens; 0 disables (admissions "
                         "fall the chain out)")
    ap.add_argument("--decode-block-ladder", type=_ladder_arg, default=None,
                    help="adaptive decode-block sizing: comma-separated "
                         "rung sizes (e.g. 1,4,16) compiled alongside "
                         "--decode-steps; the scheduler runs full blocks "
                         "while the prompt queue is empty and drops to "
                         "the shortest rung (chaining suppressed) the "
                         "moment prompts are pending, so a waiting "
                         "prompt's first chunk rides the next dispatch. "
                         "Empty disables (fixed blocks)")
    ap.add_argument("--speculative-ngram-k", type=int, default=0,
                    help="self-speculative decoding: draft K tokens per "
                         "decode dispatch from the sequence's own history "
                         "(n-gram prompt lookup, no draft model) and "
                         "verify them in one fused forward; 0 disables. "
                         "Output is token-identical to plain decode; "
                         "acceptance telemetry lands on /metrics")
    ap.add_argument("--mixed-prefill-tokens", type=int, default=None,
                    help="prefill token budget inside a mixed "
                         "(prefill+decode) dispatch; default = "
                         "max_prefill_tokens, 0 disables mixing "
                         "(prefill-first scheduling)")
    ap.add_argument("--no-prefix-caching", action="store_true")
    ap.add_argument("--fuse-projections", action="store_true",
                    help="fuse qkv + gate/up weight reads (single-device "
                         "engines; numerically identical, faster decode "
                         "at small hidden sizes)")
    ap.add_argument("--kv-partition", action="store_true",
                    help="partition the KV pool across the mesh's dp*sp "
                         "shards (num_pages becomes per-shard; aggregate "
                         "capacity scales with the mesh)")
    ap.add_argument("--disagg-role", default="both",
                    choices=["both", "prefill", "decode", "encode"],
                    help="'encode' serves a dedicated vision-encode "
                         "worker (EPD split; requires --vision)")
    ap.add_argument("--encode-component", default="", metavar="COMPONENT",
                    help="offload image encoding to the encode worker "
                         "registered at this component (this worker "
                         "then needs no vision tower)")
    # distributed KVBM: shared host/disk/object-store KV tiers
    ap.add_argument("--kvbm", action="store_true",
                    help="attach shared KV tiers via the kvbm bootstrap")
    ap.add_argument("--kvbm-leader", type=int, default=0, metavar="WORLD",
                    help="also run the kvbm leader, barriering WORLD workers")
    ap.add_argument("--kvbm-disk-root", default=None)
    ap.add_argument("--kvbm-g4-bucket", default=None)
    ap.add_argument("--kvbm-host-bytes", type=int, default=1 << 30)
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"],
                    help="force the JAX backend (cpu for tests/CI)")
    ap.add_argument("--local-devices", type=int, default=0,
                    help="with --platform cpu: virtual CPU devices per "
                         "process (0 = backend default) — lets a "
                         "multihost group form a real global mesh "
                         "without TPU chips")
    ap.add_argument("--status-port", type=int, default=0,
                    help="system status server port (0 = ephemeral, "
                         "-1 = disabled); serves /health /live /metrics")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="TTFT SLO target carried on the model card "
                         "(frontend live windows + planner knee "
                         "estimation score against it; 0 = frontend "
                         "default class, DYN_TPU_SLO_TTFT_MS overrides)")
    ap.add_argument("--slo-itl-ms", type=float, default=0.0,
                    help="mean-ITL SLO target carried on the model card "
                         "(0 = frontend default class, "
                         "DYN_TPU_SLO_ITL_MS overrides)")
    # overload control (docs/overload_control.md): priority classes +
    # the shed / queue-deadline / preemption-parking knobs
    ap.add_argument("--priority-class", default="interactive",
                    choices=["interactive", "batch"],
                    help="default priority class for requests that don't "
                         "set one (carried on the model card; per-request "
                         "`priority` / `nvext.priority` win)")
    ap.add_argument("--overload-queue-depth", type=int, default=0,
                    help="shed NEW batch-class requests once the waiting "
                         "queue is this deep AND watermark headroom is at "
                         "or under --overload-headroom-pages (0 disables)")
    ap.add_argument("--overload-headroom-pages", type=int, default=0,
                    help="watermark-headroom floor (pages) below which "
                         "the queue-depth threshold counts as pressure")
    ap.add_argument("--batch-deadline-s", type=float, default=0.0,
                    help="shed a batch request queued this long without "
                         "ever being admitted (never accepted-then-"
                         "starved; 0 disables)")
    ap.add_argument("--park-max-pages", type=int, default=0,
                    help="cap on KV pages the decode-preemption parking "
                         "lot may hold host-side (0 = unbounded)")
    # serving mesh: dp*tp*sp devices (all local devices by default); on a
    # multihost group this spans the GLOBAL device set
    ap.add_argument("--dp", type=int, default=1, help="data-parallel degree")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel degree")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel degree (ring-attention prefill)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel degree (layer stack + KV layer "
                         "axis staged over pp; composes with dp)")
    ap.add_argument("--dp-ranks", type=int, default=1,
                    help="independent engine replicas behind this endpoint "
                         "(per-rank KV pools + events; the router targets "
                         "(instance, dp_rank))")
    # multihost (jax.distributed): every host in the group runs this CLI
    # with the same flags and a unique --host-id; see parallel/multihost.py.
    # Rank 0 serves the endpoint; other ranks replay its dispatches in
    # lockstep (JaxEngine.follower_loop)
    ap.add_argument("--coordinator", default="",
                    help="rank-0 coordinator host:port (DYN_COORDINATOR)")
    ap.add_argument("--num-hosts", type=int, default=None)
    ap.add_argument("--host-id", type=int, default=None)
    ap.add_argument("--prefill-router", default="", metavar="COMPONENT",
                    help="route remote prefills through a standalone "
                         "router service registered at this component "
                         "(decode role only)")
    ap.add_argument("--reasoning-parser", default="",
                    help="split reasoning_content from content "
                         "(deepseek_r1|qwen3|granite|gpt_oss)")
    ap.add_argument("--tool-call-parser", default="",
                    help="extract tool calls (hermes|mistral|json|pythonic)")
    ap.add_argument("--log-level", default="")
    ap.add_argument("--log-jsonl", action="store_true", default=None)
    return ap


def check_args(ap: argparse.ArgumentParser, args) -> None:
    """Cross-flag validation (calls ap.error on conflict) — shared by
    main() and the recipe-validation tests."""
    # fail fast on typo'd parser names (otherwise every request 500s)
    from ..parsers import get_reasoning_parser, get_tool_parser

    try:
        get_reasoning_parser(args.reasoning_parser)
        get_tool_parser(args.tool_call_parser)
    except ValueError as e:
        ap.error(str(e))
    if args.kvbm and getattr(args, "mock", False):
        ap.error("--kvbm requires a real JAX engine (incompatible with --mock)")
    if args.disagg_role == "encode" and not args.vision:
        ap.error("--disagg-role encode requires --vision (the encode "
                 "worker IS the vision tower)")
    if args.encode_component and args.vision:
        ap.error("--encode-component offloads encoding — drop --vision "
                 "on this worker")
    if args.encode_component and args.disagg_role in ("prefill", "encode"):
        ap.error("--encode-component composes with --disagg-role "
                 "both|decode (prefill workers receive pre-encoded "
                 "requests from their decode side; encode workers ARE "
                 "the encoder)")
    if args.mock and (args.quantization != "none"
                      or args.attention_impl != "auto"
                      or args.decode_steps != 1 or args.decode_chain != 1
                      or args.decode_block_ladder
                      or getattr(args, "decode_continuous", False)
                      or getattr(args, "prefill_chunk_tokens", None)
                      is not None
                      or args.speculative_ngram_k
                      or args.no_prefix_caching or args.vision
                      or args.encode_component):
        ap.error("engine-tuning/vision flags require a real JAX engine "
                 "(incompatible with --mock)")
    if args.dp_ranks > 1:
        # DpRankEngine serves the plain generate/embed surface only; the
        # disagg handlers, KVBM worker, mock branch, and multihost
        # follower all require the single-JaxEngine API
        for bad, flag in [
            (args.disagg_role != "both", "--disagg-role"),
            (args.kvbm, "--kvbm"),
            (args.mock, "--mock"),
            (bool(args.coordinator), "--coordinator (multihost)"),
            (bool(args.encode_component), "--encode-component"),
        ]:
            if bad:
                ap.error(f"--dp-ranks > 1 is incompatible with {flag}")


def engine_config_from_args(args):
    """EngineConfig from parsed worker argv (raises ValueError on bad
    combinations — the same construction the live worker performs)."""
    from ..engine import EngineConfig

    continuous = (getattr(args, "decode_continuous", False)
                  or args.decode_chain == "continuous")
    chain = (args.decode_chain if isinstance(args.decode_chain, int)
             else 2)  # 'continuous' keyword: default double-buffer horizon
    return EngineConfig(
        page_size=args.page_size,
        num_pages=args.num_pages,
        num_state_slots=getattr(args, "num_state_slots", 32),
        max_num_seqs=args.max_num_seqs,
        max_prefill_tokens=args.max_prefill_tokens,
        max_model_len=args.max_model_len,
        quantization=args.quantization,
        attention_impl=args.attention_impl,
        decode_steps=args.decode_steps,
        decode_chain=chain,
        decode_continuous=continuous,
        prefill_chunk_tokens=getattr(args, "prefill_chunk_tokens", None),
        decode_block_ladder=args.decode_block_ladder,
        speculative_ngram_k=args.speculative_ngram_k,
        mixed_prefill_tokens=args.mixed_prefill_tokens,
        kv_partition=args.kv_partition,
        enable_prefix_caching=not args.no_prefix_caching,
        fuse_projections=args.fuse_projections,
        default_priority=getattr(args, "priority_class", "interactive"),
        overload_queue_depth=getattr(args, "overload_queue_depth", 0),
        overload_headroom_pages=getattr(args, "overload_headroom_pages", 0),
        batch_deadline_s=getattr(args, "batch_deadline_s", 0.0),
        park_max_pages=getattr(args, "park_max_pages", 0),
    )


def main() -> None:
    install_host_probes()
    _phase_end_ns[0] = _STARTUP["t0_ns"] = _process_start_ns()
    ap = build_parser()
    args = ap.parse_args()
    check_args(ap, args)
    from ..runtime.tracing import setup_logging

    setup_logging(args.log_level, args.log_jsonl)
    if args.platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
        if args.local_devices:
            jax.config.update("jax_num_cpu_devices", args.local_devices)
    if not args.mock:
        from .. import compile_cache

        compile_cache.configure()
    if args.mock and args.coordinator:
        # a MOCK multinode group never joins a jax world (there are no
        # device dispatches to replay): rank 0 serves the simulator,
        # other ranks just hold their group slot so controllers exercise
        # real group lifecycle (spawn / any-rank-death / respawn)
        if (args.host_id or 0) > 0:
            print("READY mock-follower", flush=True)
            # block first or sigwait never consumes them (SIGTERM would
            # take the kernel default and exit 143; SIGINT would hang)
            signal.pthread_sigmask(
                signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGINT}
            )
            signal.sigwait({signal.SIGTERM, signal.SIGINT})
            return
    else:
        from ..parallel import initialize_multihost

        initialize_multihost(args.coordinator, args.num_hosts, args.host_id)
    import jax

    host_event("startup.imports", *_phase_done("imports"))
    n_devices = len(jax.devices())  # the first touch: the client, the chip
    host_event("startup.backend", *_phase_done("backend"),
               platform=jax.default_backend(), devices=n_devices)
    if jax.process_count() > 1 and jax.process_index() != 0:
        # follower rank: same engine, no endpoint — replay rank 0's steps
        if args.mock:
            raise SystemExit("--mock cannot run multihost")
        engine, _ = _build_engine(args)
        print("READY follower", flush=True)
        engine.follower_loop()
        return
    asyncio.run(_run(args))


async def _run(args) -> None:
    from ..analysis import leak_ledger
    from ..llm import ModelDeploymentCard
    from ..runtime import DistributedRuntime
    from . import serve_engine

    # attribute every task on the serving loop (no-op unless
    # DYN_TPU_LEAKCHECK=1) — feeds the LeakLedgerCollector families
    leak_ledger.install_loop(asyncio.get_running_loop(), owner="worker")
    # build the engine BEFORE taking a lease: model load / first compile can
    # block for longer than the lease TTL
    # lint: allow(blocking-in-async): one-time startup before serving; model load dwarfs it
    engine, mdc = _build_engine(args)
    runtime = await DistributedRuntime.connect(args.control)
    if args.kvbm:
        from ..kvbm import KvbmConfig, KvbmLeader, KvbmWorker

        leader_task = None
        if args.kvbm_leader > 0:
            leader_task = asyncio.ensure_future(KvbmLeader(
                runtime,
                KvbmConfig(
                    disk_root=args.kvbm_disk_root,
                    g4_bucket=args.kvbm_g4_bucket,
                    host_bytes=args.kvbm_host_bytes,
                ),
                world=args.kvbm_leader, namespace=args.namespace,
            ).start())
        await KvbmWorker(runtime, engine, namespace=args.namespace).start()
        if leader_task is not None:
            await leader_task
    def wrap_encode(inner):
        """Outermost wrapper: image requests swap pixels for encoder
        embeds BEFORE the disagg handler routes them, so remote
        prefills already carry mm_embeds."""
        if not args.encode_component:
            return inner
        from ..disagg import EncodeOffload

        return EncodeOffload(
            inner, runtime, namespace=args.namespace,
            component=args.encode_component,
        )

    if args.disagg_role == "encode":
        from ..disagg import serve_encode_worker
        from ..disagg.encode import ENCODE_COMPONENT

        # registers at --component ("encoder" when left at the worker
        # default) — serving workers point --encode-component at it
        await serve_encode_worker(
            runtime, engine, mdc, namespace=args.namespace,
            component=(args.component if args.component != "backend"
                       else ENCODE_COMPONENT),
        )
    elif args.disagg_role == "prefill":
        from ..disagg import serve_prefill_worker

        await serve_prefill_worker(runtime, engine, mdc, namespace=args.namespace)
    elif args.disagg_role == "decode":
        from ..disagg import DisaggDecodeHandler
        from ..disagg.handler import RemoteRouterClient

        prefill_router = (
            RemoteRouterClient(runtime, args.namespace, args.prefill_router)
            if args.prefill_router else None
        )
        engine = wrap_encode(DisaggDecodeHandler(
            engine, runtime, namespace=args.namespace,
            prefill_router=prefill_router,
        ))
        await serve_engine(
            runtime, engine, mdc,
            namespace=args.namespace, component=args.component,
            endpoint=args.endpoint,
        )
    else:
        engine = wrap_encode(engine)
        await serve_engine(
            runtime, engine, mdc,
            namespace=args.namespace, component=args.component,
            endpoint=args.endpoint,
        )
    import os as _os

    chaos_injector = None
    if _os.environ.get("DYN_TPU_CHAOS"):
        # chaos-enabled deployment: arm/disarm gate faults in this process
        # via /chaos control-plane keys (chaos/injector.py)
        from ..chaos import FaultInjector

        chaos_injector = await FaultInjector(
            runtime, namespace=args.namespace,
            ident=f"{args.component}:{runtime.primary_lease}",
        ).start()
    # per-process observability: /health probes the engine through its real
    # request path (reference system_status_server.rs:74, health_check.rs:353)
    status = health = None
    if args.status_port >= 0:
        from ..runtime.health import HealthCheckManager
        from ..runtime.status import SystemStatusServer

        from ..runtime.metrics import MetricsScope

        def _self_evict(name, st):
            # the liveness-kill analog: a wedged engine (alive process,
            # dead request path) exits nonzero so the operator's reconcile
            # loop replaces it; in-flight streams migrate to survivors
            logging.getLogger(__name__).error(
                "endpoint %s unhealthy (%d consecutive failures) — "
                "self-evicting", name, st.consecutive_failures,
            )
            _os._exit(3)  # noqa: SLF001 — hard exit IS the semantics

        health = HealthCheckManager(
            runtime, publish=True,
            on_unhealthy=(
                _self_evict if _os.environ.get("DYN_TPU_HEALTH_SELF_EVICT")
                else None
            ),
        ).start()

        def _stats():
            try:
                return {k: v for k, v in vars(engine.metrics()).items()
                        if isinstance(v, (int, float, str))}
            except Exception:  # noqa: BLE001
                return {}

        def _stats_json():
            """/metrics.json: the flat engine stats plus, for a real
            engine, what it runs on (`device_report`)."""
            body = _stats()
            if not args.mock:
                body["runtime"] = device_report()
            return body

        # Prometheus worker metrics (reference dynamo_component_*): the
        # shared EngineStatsCollector builds metric families from live
        # engine ForwardPassMetrics on every scrape — counters for
        # monotonic fields (incl. the spec_decode draft/accept pair) so
        # rate() is well-typed, gauges for the rest
        from ..runtime.metrics import (
            EngineStatsCollector,
            LeakLedgerCollector,
            TracingSpanCollector,
            XlaLedgerCollector,
        )

        scope = MetricsScope(
            namespace=args.namespace, component=args.component,
        )
        scope.registry.register(EngineStatsCollector(
            _stats, namespace=args.namespace, component=args.component,
        ))
        # span-exporter sent/dropped counters (silent span loss -> visible)
        scope.registry.register(TracingSpanCollector())
        # compile ledger: per-function XLA compiles + transfer-guard
        # violations (a climbing compile curve after warmup = recompile leak)
        scope.registry.register(XlaLedgerCollector())
        # lifecycle ledger: pending/orphaned tasks + resource-account
        # imbalances (absent unless DYN_TPU_LEAKCHECK=1)
        scope.registry.register(LeakLedgerCollector())
        # process-level CPU/fd/RSS — the same dynamo_process_* families
        # the frontend exports, so fleet dashboards see worker host
        # pressure from the worker's own /metrics
        from ..runtime.metrics import ProcessStatsCollector

        scope.registry.register(ProcessStatsCollector())

        def _inner_engine():
            inner = engine
            while not hasattr(inner, "events") and hasattr(inner, "engine"):
                inner = inner.engine  # unwrap disagg/encode handlers
            return inner

        def _events(since_ns=None):
            """Step-event ring dump(s) for /events.json — the engine(s)
            behind this endpoint, keyed so the timeline merger can place
            each ring on its own track (dp ranks dump separately).
            `since_ns` is the poller's cursor (dump watermark_ns)."""
            inner = _inner_engine()
            if hasattr(inner, "engines"):  # DpRankEngine
                return {
                    f"rank{r}": e.events.dump(since_ns=since_ns)
                    for r, e in enumerate(inner.engines)
                    if hasattr(e, "events")
                }
            if hasattr(inner, "events"):
                return {"engine": inner.events.dump(since_ns=since_ns)}
            return {}

        def _xprof(steps, directory=None):
            """POST /debug/xprof: arm a capture on the first engine behind
            this endpoint (the profiler is one per process, so one rank's
            step count bounds it); the directory, or None when busy."""
            inner = _inner_engine()
            inner = getattr(inner, "engines", [inner])[0]
            if not hasattr(inner, "arm_xprof"):
                return None
            directory = _os.path.abspath(
                directory or _os.environ.get("DYN_TPU_XPROF_DIR")
                or "profiles")
            return directory if inner.arm_xprof(steps, directory) else None

        status = await SystemStatusServer(
            metrics=scope,
            health_fn=lambda: _async_health(health),
            stats_fn=_stats_json,
            events_fn=_events,
            xprof_fn=None if args.mock else _xprof,
            port=args.status_port,
        ).start()
        print(f"STATUS http://0.0.0.0:{status.port}", flush=True)
    # capacity snapshots for the fleet telemetry plane: periodic compact
    # engine state (queue depth, batch occupancy, kv headroom, *_total
    # counters — the publisher derives per-interval rates — and decode
    # host-gap p50 when the step-event ring is wired) published
    # lease-scoped under /telemetry/{ns}/{component}/{lease}; the
    # planner's FleetTelemetryWatcher joins them with frontend windows
    from ..runtime.metrics import TelemetryPublisher

    _hg_cache = {"decode_blocks": -1}

    def _capacity_snapshot():
        try:
            src = engine
            while not hasattr(src, "metrics") and hasattr(src, "engine"):
                src = src.engine  # unwrap offload/handler wrappers
            m = src.metrics()
            snap = {k: v for k, v in (m if isinstance(m, dict)
                                      else vars(m)).items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}
        except Exception:  # noqa: BLE001
            return {}
        snap["model"] = mdc.name
        snap["disagg_role"] = args.disagg_role
        snap["queue_depth"] = snap.get("waiting_seqs", 0)
        try:
            inner = engine
            while not hasattr(inner, "events") and hasattr(inner, "engine"):
                inner = inner.engine
            events = getattr(inner, "events", None)
            # dump+sort of a full 4096-event ring is not free on the
            # serving loop: the per-kind counter gates it, so ticks
            # under prefill/alloc-only traffic never dump, and nothing
            # is published while decode is idle (a gap p50 recomputed
            # from minutes-old decode slices would be wrong-but-fresh-
            # looking — the staleness design's no-no)
            n_decode = (events.kind_totals.get("decode_block", 0)
                        if events is not None else 0)
            if n_decode and n_decode != _hg_cache["decode_blocks"]:
                from ..runtime.timeline import decode_host_gaps

                _hg_cache["decode_blocks"] = n_decode
                gaps = decode_host_gaps(events.dump())
                if gaps["p50_ms"] is not None:
                    snap["decode_host_gap_p50_ms"] = gaps["p50_ms"]
        except Exception:  # lint: allow(swallowed-exception): the gap stat is best-effort telemetry
            pass
        return snap

    telemetry = TelemetryPublisher(
        runtime, _capacity_snapshot,
        namespace=args.namespace, component=args.component,
    ).start()
    if not args.mock:
        import json as _json

        from .. import chip

        # what this worker really runs on, beside READY: a launcher that
        # must stay off JAX (one process per chip) reads it from here
        if hasattr(engine, "cache_report"):  # page kind, bytes a token
            print("CACHE " + _json.dumps(engine.cache_report()), flush=True)
            # the second pool, where the model has state-space layers: the
            # slots of recurrent state beside the pages
            if engine.state_report() is not None:
                print("STATE " + _json.dumps(engine.state_report()),
                      flush=True)
            # what the layer loops carry: x + f(x), or the streams of
            # hyper-connections
            print("RESIDUAL " + _json.dumps(
                engine.model_cfg.residual_report), flush=True)
        print("DEVICE " + _json.dumps(chip.device_identity()), flush=True)
        # of the programs born so far, how many the program store answered
        # and how many it was given (the rest are born after READY)
        from ..analysis import xla_ledger

        xla = xla_ledger.summary()
        _STARTUP.update({k: xla[k] for k in (
            "programs_stored", "programs_store_writes")})
    t0_serve, t_ready = _phase_done("serve")
    host_event("startup.serve", t0_serve, t_ready)
    host_event("ready", t_ready, t_ready, model=mdc.name)
    _STARTUP["ready_s"] = round((t_ready - _STARTUP["t0_ns"]) / 1e9, 3)
    # an operator's cold-start number, the planner's scale-up delay
    print("STARTUP " + json.dumps(_STARTUP), flush=True)
    print(f"READY worker {mdc.name}", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await telemetry.stop()
    if status:
        await status.stop()
    if health:
        await health.stop()
    if chaos_injector:
        await chaos_injector.stop()
    await runtime.shutdown()
    if hasattr(engine, "shutdown"):
        await engine.shutdown()
    # flush + close the span exporter LAST: engine shutdown may still
    # deliver final deltas whose spans must make the flush
    from ..runtime.tracing import close_exporter

    close_exporter()


async def _async_health(health) -> dict:
    return health.system_health()


def device_report() -> dict:
    """The device this process holds, per-device memory, the compile
    ledger (compile seconds, persistent-cache hits and misses, which
    attention program and decode path each compiled step took) and
    whether the native libs or their Python twins are loaded."""
    import jax

    from .. import chip, native
    from ..analysis import xla_ledger

    def memory(d):
        stats = d.memory_stats() or {}
        return {"id": d.id, **{k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}}

    return {
        "device": chip.device_identity(),
        "memory": [memory(d) for d in jax.local_devices()],
        "xla": xla_ledger.summary(),
        "startup": dict(_STARTUP),
        "native": native.status(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


def _weights_done(params) -> None:
    """`startup.weights`: from the backend's end through the LAST array on
    the device (configuration, tokenizer and the engine's imports are in
    it).  `bytes` and `tensors` are what sits on the device; `read_us` is
    the time inside the checkpoint readers (`models/loader.py`), `put_us`
    the rest: stacking on the host, the copies, the casts."""
    import jax

    from ..models import loader

    jax.block_until_ready(params)
    leaves = jax.tree_util.tree_leaves(params)
    t0, t1 = _phase_done("weights")
    read_us = loader.READ_STATS["read_ns"] // 1000
    host_event("startup.weights", t0, t1,
               bytes=int(sum(x.nbytes for x in leaves)), tensors=len(leaves),
               read_us=read_us, put_us=(t1 - t0) // 1000 - read_us)


def _build_engine(args):
    from ..llm import ModelDeploymentCard

    ecfg = engine_config_from_args(args)
    if args.mock:
        from ..mocker import MockEngine, MockEngineArgs
        from ..testing import tiny_tokenizer

        tok = tiny_tokenizer()
        margs = MockEngineArgs(
            num_pages=args.num_pages,
            page_size=args.page_size,
            max_num_seqs=args.max_num_seqs,
            max_prefill_tokens=args.max_prefill_tokens,
            max_model_len=args.max_model_len,
            speedup_ratio=args.mock_speedup,
            # generate INSIDE the tokenizer's vocab: the simulated tokens
            # detokenize to visible text, so e2e clients (and the chaos
            # harness's stream-identity checks) see real content.  The eos
            # id must come from the same tokenizer — the 32000-vocab
            # default of 2 is a special token here, and _mock_token avoids
            # emitting whatever id is designated eos
            vocab_size=tok.vocab_size,
            eos_token_id=list(tok.eos_token_ids)[0],
            # overload control rides the real scheduler inside the mock,
            # so graph-deployed mock workers (chaos scenarios) honor the
            # same class/shed/park knobs as real ones
            default_priority=args.priority_class,
            overload_queue_depth=args.overload_queue_depth,
            overload_headroom_pages=args.overload_headroom_pages,
            batch_deadline_s=args.batch_deadline_s,
            park_max_pages=args.park_max_pages,
        )
        engine = MockEngine(margs)
        host_event("startup.engine", *_phase_done("engine"))
        mdc = ModelDeploymentCard(
            name=args.model_name or "mock-model",
            tokenizer_json=tok.to_json_str(),
            eos_token_ids=[margs.eos_token_id],
            context_length=args.max_model_len,
            disagg_role=args.disagg_role,
            reasoning_parser=args.reasoning_parser,
            tool_call_parser=args.tool_call_parser,
            slo_ttft_ms=args.slo_ttft_ms,
            slo_itl_ms=args.slo_itl_ms,
            priority_class=args.priority_class,
        )
        return engine, mdc

    import jax.numpy as jnp

    from ..engine import JaxEngine

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    loaded_vision = None
    if args.model == "tiny":
        import jax

        from ..models import init_params, tiny_config
        from ..testing import tiny_tokenizer

        tok = tiny_tokenizer()
        cfg = tiny_config(vocab_size=tok.vocab_size)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
        name = args.model_name or "tiny-chat"
        tokenizer_json = tok.to_json_str()
        eos = list(tok.eos_token_ids)
    else:
        from ..llm import HuggingFaceTokenizer
        from ..models import ModelConfig
        from ..models.loader import load_params

        from ..models.hub import resolve_model

        model_dir = resolve_model(args.model)
        cfg = ModelConfig.from_pretrained(model_dir)
        if cfg.model_type in ("qwen2_vl", "qwen2_5_vl"):
            # qwen-vl checkpoints carry their own tower + mrope config
            from ..models.vlm import load_qwen_vl

            params, cfg, vparams, vcfg = load_qwen_vl(model_dir, dtype=dtype)
            loaded_vision = (vparams, vcfg)
        else:
            params = load_params(model_dir, cfg, dtype=dtype)
        tok = HuggingFaceTokenizer.from_pretrained(model_dir)
        name = args.model_name or cfg.name
        tokenizer_json = tok.to_json_str()
        eos = list(tok.eos_token_ids)
    _weights_done(params)

    parallel = None
    if args.dp * args.tp * args.sp * args.pp > 1:
        from ..parallel import ParallelConfig

        parallel = ParallelConfig(dp=args.dp, tp=args.tp, sp=args.sp,
                                  pp=args.pp)
    vision = None
    mm_fields = {}
    if loaded_vision is not None:
        # qwen2-vl checkpoint: the tower + geometry came with the model
        import json as _json
        import os as _os

        vision = loaded_vision
        vcfg = loaded_vision[1]
        with open(_os.path.join(model_dir, "config.json")) as f:
            hf = _json.load(f)
        img_id = hf.get("image_token_id", 151655)
        # id -> literal token string: decode() skips special tokens (the
        # placeholder IS one), so keep them for this lookup
        img_tok = tok.decode([img_id], skip_special_tokens=False)
        if not img_tok or tok.encode(img_tok)[-1:] != [img_id]:
            raise SystemExit(
                f"image_token_id {img_id} does not round-trip through "
                f"the tokenizer (got {img_tok!r})"
            )
        mm_fields = dict(
            image_token=img_tok,
            image_token_id=img_id,
            mm_arch="qwen2_vl",
            mm_config=dict(
                depth=vcfg.depth, embed_dim=vcfg.embed_dim,
                num_heads=vcfg.num_heads, mlp_ratio=vcfg.mlp_ratio,
                patch_size=vcfg.patch_size,
                temporal_patch_size=vcfg.temporal_patch_size,
                spatial_merge_size=vcfg.spatial_merge_size,
                hidden_size=vcfg.out_hidden_size,
                min_pixels=vcfg.min_pixels, max_pixels=vcfg.max_pixels,
            ),
        )
    elif args.vision or args.encode_component:
        import jax

        from ..models.vision import init_vision_params, tiny_vision_config

        vcfg = tiny_vision_config(out_hidden_size=cfg.hidden_size)
        if args.vision:
            vision = (
                init_vision_params(vcfg, jax.random.PRNGKey(7), dtype=dtype),
                vcfg,
            )
        # --encode-component: no local tower, but the model card still
        # advertises the image surface (preprocessor geometry must match
        # the encode worker's tower)
        image_ids = tok.encode("<image>")
        if len(image_ids) != 1:
            raise SystemExit("tokenizer has no single-token <image> marker")
        mm_fields = dict(
            image_token="<image>",
            image_token_id=image_ids[0],
            image_patches=vcfg.num_patches,
            image_size=vcfg.image_size,
        )
    if args.dp_ranks > 1 and ecfg.quantization == "int8":
        # quantize ONCE before constructing replicas: each JaxEngine would
        # otherwise quantize independently, materializing dp_ranks distinct
        # weight copies in HBM instead of sharing one
        import dataclasses as _dc

        from ..models.quantization import quantize_params

        params = quantize_params(params)
        ecfg = _dc.replace(ecfg, quantization="none")

    def make_engine(devices=None):
        return JaxEngine(cfg, params, ecfg, eos_token_ids=eos,
                         kv_dtype=dtype, parallel=parallel, vision=vision,
                         devices=devices)

    if args.dp_ranks > 1:
        import jax

        from . import DpRankEngine

        # each flat replica takes its own local device, round-robin:
        # parameters are copied there and the KV pool is allocated there.
        # Replicas that land on one device (a one-chip host) share that
        # device's parameter buffers; every replica has its own KV pool
        local = jax.local_devices()
        engine = DpRankEngine([
            make_engine(None if parallel is not None
                        else [local[r % len(local)]])
            for r in range(args.dp_ranks)
        ])
    else:
        engine = make_engine()
    host_event("startup.engine", *_phase_done("engine"),
               pool_bytes=sum(e.cache_report()["pool_bytes"]
                              for e in getattr(engine, "engines", [engine])))
    mdc = ModelDeploymentCard(
        name=name,
        tokenizer_json=tokenizer_json,
        eos_token_ids=eos,
        context_length=args.max_model_len,
        disagg_role=args.disagg_role,
        reasoning_parser=args.reasoning_parser,
        tool_call_parser=args.tool_call_parser,
        slo_ttft_ms=args.slo_ttft_ms,
        slo_itl_ms=args.slo_itl_ms,
        priority_class=args.priority_class,
        **mm_fields,
    )
    return engine, mdc


if __name__ == "__main__":
    main()
