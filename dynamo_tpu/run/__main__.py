"""Unified launcher: `python -m dynamo_tpu.run --in X --out Y`.

The in×out matrix of the reference's `dynamo-run` CLI
(/root/reference/launch/dynamo-run/src/main.rs:29):

  --in   http      OpenAI HTTP frontend (default)
         text      interactive terminal chat
         batch     JSONL file in → JSONL out (--input-file/--output-file)
         endpoint  serve the engine as a worker endpoint only
  --out  engine    first-party JaxEngine (--model tiny|<checkpoint dir>)
         mock      the scheduler-faithful mock engine
         echo      trivial echo engine (wiring tests)
         dyn       no local engine — attach to workers already registered
                   on an existing control plane (--control required)

Unless --control is given, an embedded control plane runs in-process
(DistributedRuntime.detached), so `dynamo_tpu.run` is a single-command
local deployment.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import signal
import sys

logger = logging.getLogger(__name__)


class EchoEngine:
    """Echoes the prompt tokens back (reference dynamo-run out=echo)."""

    async def generate(self, request, context=None):
        toks = list(request.get("token_ids") or [])
        maxt = (request.get("stop_conditions") or {}).get("max_tokens") or len(toks)
        for i, t in enumerate(toks[:maxt]):
            last = i == min(len(toks), maxt) - 1
            yield {"token_ids": [t], "finish_reason": "stop" if last else None}
        if not toks:
            yield {"token_ids": [], "finish_reason": "stop"}

    def metrics(self):
        from ..engine.engine import ForwardPassMetrics

        return ForwardPassMetrics()


def parse_args(argv=None):
    ap = argparse.ArgumentParser("dynamo_tpu.run")
    ap.add_argument("--in", dest="in_mode", default="http",
                    choices=["http", "text", "batch", "endpoint"])
    ap.add_argument("--out", dest="out_mode", default="engine",
                    choices=["engine", "mock", "echo", "dyn"])
    ap.add_argument("--model", default="tiny",
                    help="'tiny' or a checkpoint directory (out=engine)")
    ap.add_argument("--model-name", default="")
    ap.add_argument("--control", default="",
                    help="existing control plane address (required for out=dyn)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--max-model-len", type=int, default=1024)
    ap.add_argument("--max-tokens", type=int, default=64,
                    help="generation cap for text/batch modes")
    ap.add_argument("--input-file", default="", help="JSONL (batch mode)")
    ap.add_argument("--output-file", default="", help="JSONL (batch mode)")
    ap.add_argument("--router-mode", default="round_robin",
                    choices=["round_robin", "random", "kv"])
    ap.add_argument("--log-level", default="info")
    args = ap.parse_args(argv)
    if args.out_mode == "dyn" and not args.control:
        ap.error("--out dyn requires --control")
    if args.in_mode == "batch" and not args.input_file:
        ap.error("--in batch requires --input-file")
    return args


def _build_engine(args):
    """Engine + MDC for the chosen --out (None for dyn)."""
    from ..llm import ModelDeploymentCard

    if args.out_mode == "dyn":
        return None, None
    if args.out_mode == "echo":
        from ..testing import tiny_tokenizer

        tok = tiny_tokenizer()
        return EchoEngine(), ModelDeploymentCard(
            name=args.model_name or "echo",
            tokenizer_json=tok.to_json_str(),
            eos_token_ids=[],
            context_length=args.max_model_len,
        )
    if args.out_mode == "mock":
        from ..mocker import MockEngine, MockEngineArgs
        from ..testing import tiny_tokenizer

        tok = tiny_tokenizer()
        margs = MockEngineArgs(max_model_len=args.max_model_len)
        return MockEngine(margs), ModelDeploymentCard(
            name=args.model_name or "mock-model",
            tokenizer_json=tok.to_json_str(),
            eos_token_ids=[margs.eos_token_id],
            context_length=args.max_model_len,
        )
    # out=engine
    import jax
    import jax.numpy as jnp

    from .. import compile_cache
    from ..engine import EngineConfig, JaxEngine

    compile_cache.configure()

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.model == "tiny":
        from ..models import init_params, tiny_config
        from ..testing import tiny_tokenizer

        tok = tiny_tokenizer()
        cfg = tiny_config(vocab_size=tok.vocab_size)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
        name = args.model_name or "tiny-chat"
    else:
        from ..llm import HuggingFaceTokenizer
        from ..models import ModelConfig
        from ..models.loader import load_params

        cfg = ModelConfig.from_pretrained(args.model)
        params = load_params(args.model, cfg, dtype=dtype)
        tok = HuggingFaceTokenizer.from_pretrained(args.model)
        name = args.model_name or cfg.name
    eos = list(tok.eos_token_ids)
    engine = JaxEngine(
        cfg, params,
        EngineConfig(max_model_len=args.max_model_len),
        eos_token_ids=eos, kv_dtype=dtype,
    )
    return engine, ModelDeploymentCard(
        name=name,
        tokenizer_json=tok.to_json_str(),
        eos_token_ids=eos,
        context_length=args.max_model_len,
    )


async def _start_stack(args):
    """Runtime (+embedded control plane unless --control), local engine
    endpoint (unless dyn), frontend manager+watcher."""
    from ..frontend import ModelManager, ModelWatcher
    from ..runtime import DistributedRuntime
    from ..worker import serve_engine

    engine, mdc = _build_engine(args)
    if args.control:
        runtime = await DistributedRuntime.connect(args.control)
    else:
        runtime = await DistributedRuntime.detached()
    if engine is not None:
        await serve_engine(runtime, engine, mdc, namespace=args.namespace)
    manager = ModelManager()
    watcher = await ModelWatcher(
        runtime, manager, router_mode=args.router_mode
    ).start()
    if mdc is not None:
        await watcher.wait_for_model(mdc.name)
    return runtime, engine, manager, watcher


async def _amain(args):
    runtime, engine, manager, watcher = await _start_stack(args)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # pragma: no cover
            pass
    try:
        if args.in_mode == "endpoint":
            print(f"READY endpoint {args.namespace}", flush=True)
            await stop.wait()
        elif args.in_mode == "http":
            from ..frontend import HttpService

            http = await HttpService(
                manager, host=args.host, port=args.port
            ).start()
            print(f"READY http://{args.host}:{http.port}", flush=True)
            await stop.wait()
            await http.stop()
        elif args.in_mode == "text":
            await _run_text(manager, args, stop)
        else:
            await _run_batch(manager, args)
    finally:
        await watcher.stop()
        if engine is not None and hasattr(engine, "shutdown"):
            await engine.shutdown()
        await runtime.shutdown(graceful=False)


def _pick_entry(manager, args):
    names = manager.names()
    if not names:
        raise SystemExit("no models registered")
    return manager.get(args.model_name or names[0])


async def _generate_text(entry, messages, args):
    """One chat turn through preprocessor → route → detokenized stream."""
    from ..runtime import Context

    body = {
        "model": entry.mdc.name,
        "messages": messages,
        "max_tokens": args.max_tokens,
        "temperature": 0.0,
    }
    pre = entry.preprocessor.preprocess_chat(body)
    parts = []
    async for out in entry.generate(pre, Context()):
        if out.get("finish_reason") == "error":
            raise RuntimeError(out.get("error", "engine error"))
        piece = out.get("text", "")
        parts.append(piece)
        yield piece
    return


async def _run_text(manager, args, stop) -> None:
    """Interactive chat (reference dynamo-run in=text)."""
    entry = _pick_entry(manager, args)
    print(f"chatting with {entry.mdc.name!r} — empty line or ^D quits",
          flush=True)
    messages = []
    loop = asyncio.get_running_loop()
    while not stop.is_set():
        try:
            line = await loop.run_in_executor(None, input, "you> ")
        except (EOFError, KeyboardInterrupt):
            break
        if not line.strip():
            break
        messages.append({"role": "user", "content": line})
        sys.stdout.write("assistant> ")
        reply = []
        async for piece in _generate_text(entry, messages, args):
            sys.stdout.write(piece)
            sys.stdout.flush()
            reply.append(piece)
        sys.stdout.write("\n")
        messages.append({"role": "assistant", "content": "".join(reply)})


async def _run_batch(manager, args) -> None:
    """JSONL batch: lines with {"prompt"} or {"messages"} → completions
    (reference dynamo-run in=batch)."""
    entry = _pick_entry(manager, args)
    out_path = args.output_file or (args.input_file + ".out")
    n = 0
    # lint: allow(blocking-in-async): offline batch CLI, not the serving loop
    with open(args.input_file) as fin, open(out_path, "w") as fout:
        for line in fin:
            line = line.strip()
            if not line:
                continue
            item = json.loads(line)
            messages = item.get("messages") or [
                {"role": "user", "content": item.get("prompt", "")}
            ]
            reply = []
            async for piece in _generate_text(entry, messages, args):
                reply.append(piece)
            fout.write(json.dumps({**item, "response": "".join(reply)}) + "\n")
            n += 1
    print(f"batch done: {n} requests -> {out_path}", flush=True)


def main(argv=None):
    args = parse_args(argv)
    logging.basicConfig(level=args.log_level.upper(),
                        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
