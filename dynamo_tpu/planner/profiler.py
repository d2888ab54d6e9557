"""Profiling sweep harness: measure a worker's TTFT-vs-prefill-load and
ITL-vs-concurrency curves and write the `PerfProfile` npz the planner
sizes deployments from.

Reference: the planner's pre-swept npz grids
(/root/reference/components/src/dynamo/planner/utils/pre_swept_results/)
produced by benchmark sweeps (docs/benchmarks/benchmarking.md: ISL/OSL +
concurrency sweeps) — here the sweep is first-party and drives any
AsyncEngine: the JaxEngine on a real chip, or the mock engine in CI.

CLI: ``python -m dynamo_tpu.planner.profiler --out profile.npz
[--model tiny|DIR] [--mock] [--isl 512] [--osl 64] ...``
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from .perf_model import PerfProfile


def _prompt(isl: int, salt: int, vocab: int = 1000) -> List[int]:
    return [((salt * 131 + j * 7) % vocab) + 1 for j in range(isl)]


@dataclass
class SweepConfig:
    isl: int = 512  # input sequence length (reference default 2000, scaled)
    osl: int = 64  # output tokens for decode measurements
    concurrencies: Sequence[int] = (1, 2, 4, 8)
    # prefill offered-load points as fractions of measured serial capacity
    load_fractions: Sequence[float] = (0.25, 0.5, 0.75, 0.9, 1.1)
    prefill_window_s: float = 6.0  # open-loop window per load point
    vocab: int = 1000


async def _gen(engine, req, on_first=None):
    t0 = time.perf_counter()
    t_first = t_last = None
    n = 0
    async for out in engine.generate(req):
        if out.get("finish_reason") == "error":
            raise RuntimeError(out.get("error", "engine error"))
        if out.get("token_ids"):
            t_last = time.perf_counter()
            if t_first is None:
                t_first = t_last
                if on_first:
                    on_first(t_first - t0)
            n += len(out["token_ids"])
    return n, (t_first - t0 if t_first else 0.0), (t_last or t0) - (t_first or t0)


def _req(tokens, max_tokens):
    return {
        "token_ids": tokens,
        "sampling_options": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }


async def sweep_decode(engine, cfg: SweepConfig):
    """Closed-loop: c concurrent streams; per-point median ITL + aggregate
    output throughput."""
    conc, itls, thpts = [], [], []
    for c in cfg.concurrencies:
        async def one(i):
            return await _gen(
                engine, _req(_prompt(cfg.isl, i, cfg.vocab), cfg.osl)
            )

        # warmup pass: each concurrency point compiles its own batch
        # bucket — measuring the compile would poison the curve
        await asyncio.gather(*[one(i + c * 1000) for i in range(c)])
        t0 = time.perf_counter()
        rows = await asyncio.gather(*[one(i + c * 100) for i in range(c)])
        dt = time.perf_counter() - t0
        total = sum(r[0] for r in rows)
        per_itl = sorted(
            r[2] / max(r[0] - 1, 1) for r in rows
        )
        conc.append(float(c))
        itls.append(per_itl[len(per_itl) // 2])
        thpts.append(total / dt)
    return conc, itls, thpts


async def sweep_prefill(engine, cfg: SweepConfig):
    """Open-loop: offer prompts at a fixed token rate for a window, record
    median TTFT (max_tokens=1 → pure prefill)."""
    # serial capacity estimate (warm the prefill buckets, then measure)
    await _gen(engine, _req(_prompt(cfg.isl, 1, cfg.vocab), 1))
    await _gen(engine, _req(_prompt(cfg.isl, 3, cfg.vocab), 1))
    t0 = time.perf_counter()
    await _gen(engine, _req(_prompt(cfg.isl, 2, cfg.vocab), 1))
    serial_s = time.perf_counter() - t0
    capacity = cfg.isl / max(serial_s, 1e-6)

    loads, ttfts = [], []
    for frac in cfg.load_fractions:
        rate = capacity * frac  # tokens/s offered
        interval = cfg.isl / rate
        window_ttfts: List[float] = []
        tasks = []
        t_end = time.perf_counter() + cfg.prefill_window_s
        salt = int(frac * 1000)
        while time.perf_counter() < t_end:
            salt += 1
            req = _req(_prompt(cfg.isl, salt, cfg.vocab), 1)
            tasks.append(asyncio.ensure_future(_gen(engine, req)))
            await asyncio.sleep(interval)
        rows = await asyncio.gather(*tasks)
        window_ttfts = sorted(r[1] for r in rows)
        loads.append(rate)
        ttfts.append(window_ttfts[len(window_ttfts) // 2])
    # interpolators need monotone x
    order = np.argsort(loads)
    return (
        [loads[i] for i in order],
        [ttfts[i] for i in order],
    )


async def sweep_engine(engine, cfg: Optional[SweepConfig] = None) -> PerfProfile:
    cfg = cfg or SweepConfig()
    conc, itls, thpts = await sweep_decode(engine, cfg)
    loads, ttfts = await sweep_prefill(engine, cfg)
    return PerfProfile(
        prefill_load=loads, ttft_s=ttfts,
        decode_concurrency=conc, itl_s=itls, decode_throughput=thpts,
    )


# -- disaggregated role sweeps (VERDICT r5 item 10) ------------------------- #
# The reference pre-sweeps prefill and decode roles SEPARATELY
# (pre_swept_results/.../prefill_tp*, decode_tp*); aggregated-engine
# grids mis-plan disagg graphs because the prefill role pays the KV
# handoff and the decode role never prefills.


async def sweep_disagg(pre_engine, dec_engine,
                       cfg: Optional[SweepConfig] = None):
    """(prefill_role, decode_role) PerfProfiles measured through the REAL
    data plane: the prefill role's TTFT includes the KV transfer +
    import into the decode engine (host TCP lane — what a cross-host
    deployment rides); the decode role's ITL is measured on sequences
    that START from imported KV (it never prefills)."""
    from ..disagg.transfer import KvTransferClient, KvTransferSource

    cfg = cfg or SweepConfig()
    source = await KvTransferSource(pre_engine).start()
    client = KvTransferClient(dec_engine, lanes=("host",))

    async def handoff(salt, max_tokens):
        """prefill on the prefill role → transfer → continue on the
        decode role; returns (ttft_incl_handoff_s, gen_fn)."""
        req = _req(_prompt(cfg.isl, salt, cfg.vocab), max_tokens)
        t0 = time.perf_counter()
        r = await pre_engine.prefill_remote(dict(req),
                                            transfer_source=source)
        if "kv_descriptor" not in r:
            raise RuntimeError(f"prefill_remote failed: {r}")
        pages, _stats = await client.fetch(r["kv_descriptor"], timeout=60.0)
        ttft = time.perf_counter() - t0  # decode-able: KV handed off

        async def continue_on_decode():
            n = 0
            t_first = t_last = None
            async for out in dec_engine.generate_imported(
                req, r["token_ids"][0], pages
            ):
                if out.get("finish_reason") == "error":
                    raise RuntimeError(out.get("error"))
                if out.get("token_ids"):
                    t_last = time.perf_counter()
                    if t_first is None:
                        t_first = t_last
                    n += len(out["token_ids"])
            return n, (t_last or 0.0) - (t_first or 0.0)

        return ttft, continue_on_decode

    try:
        # decode role: c concurrent imported-KV streams → ITL
        conc, itls, thpts = [], [], []
        for c in cfg.concurrencies:
            async def one(i):
                _, cont = await handoff(i, cfg.osl)
                return await cont()

            await asyncio.gather(*[one(i + c * 1000) for i in range(c)])
            t0 = time.perf_counter()
            rows = await asyncio.gather(
                *[one(i + c * 100) for i in range(c)])
            dt = time.perf_counter() - t0
            per_itl = sorted(r[1] / max(r[0] - 1, 1) for r in rows)
            conc.append(float(c))
            itls.append(per_itl[len(per_itl) // 2])
            thpts.append(sum(r[0] for r in rows) / dt)
        decode_role = PerfProfile(
            prefill_load=[0.0], ttft_s=[0.0],
            decode_concurrency=conc, itl_s=itls, decode_throughput=thpts,
        )

        # prefill role: offered prompt-token rate → TTFT incl. handoff
        t0 = time.perf_counter()
        _, cal_cont = await handoff(7, 1)
        serial_s = time.perf_counter() - t0
        await cal_cont()  # consume: frees the KV imported into the decode role
        capacity = cfg.isl / max(serial_s, 1e-6)
        loads, ttfts = [], []
        for frac in cfg.load_fractions:
            rate = capacity * frac
            interval = cfg.isl / rate
            tasks = []
            t_end = time.perf_counter() + cfg.prefill_window_s
            salt = int(frac * 10_000)
            while time.perf_counter() < t_end:
                salt += 1

                async def one(s):
                    ttft, cont = await handoff(s, 1)
                    await cont()  # frees the imported pages
                    return ttft

                tasks.append(asyncio.ensure_future(one(salt)))
                await asyncio.sleep(interval)
            rows = sorted(await asyncio.gather(*tasks))
            loads.append(rate)
            ttfts.append(rows[len(rows) // 2])
        order = np.argsort(loads)
        prefill_role = PerfProfile(
            prefill_load=[loads[i] for i in order],
            ttft_s=[ttfts[i] for i in order],
            decode_concurrency=[1.0], itl_s=[0.0], decode_throughput=[0.0],
        )
        return prefill_role, decode_role
    finally:
        await source.stop()


def _build_engine(args):
    """The engine a sweep measures.  `prefill_batch_size` 4 changed meaning
    in PR 36: it used to co-plan up to four prompts of ANY length under the
    step budget and pad every prefill step to four rows; it now caps the
    whole remaining prompts of at most `EngineConfig.short_chunk_bucket`
    tokens that share one step, and every other step runs one row.  So a
    sweep's prefill points at `--isl` above that bucket (an eighth of
    `--isl`: 64 tokens at 512) are taken at one row, not four
    padded ones: a profile saved before PR 36 reads its prefill TTFT
    higher than one taken now, and the two are not comparable there; the
    decode (ITL) tables are untouched."""
    if args.mock:
        from ..mocker import MockEngine, MockEngineArgs

        return MockEngine(MockEngineArgs(
            max_model_len=args.isl + args.osl + 16,
            max_num_seqs=max(args.concurrency),
        ))
    import jax
    import jax.numpy as jnp

    from .. import chip, compile_cache
    from ..engine import EngineConfig, JaxEngine
    from ..models import init_params, tiny_config
    from ..models.config import LLAMA_3_2_1B
    from ..models.loader import load_params

    # a profile is a table of device times: no chip, no table
    chip.require_tpu("planner.profiler sweep")
    compile_cache.configure()

    maxc = max(args.concurrency)
    if args.model == "tiny":
        cfg = tiny_config()
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        dtype = jnp.float32
    elif args.model == "llama-1b":
        cfg = LLAMA_3_2_1B
        dtype = jnp.bfloat16
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    elif args.model == "llama-8b":
        # 8B fits a 16GB chip only as int8 (~8GB weights); init the
        # quantized tree directly on device — a bf16 intermediate would
        # OOM
        from ..models.config import LLAMA_3_1_8B
        from ..models.quantization import random_int8_params

        if getattr(args, "quantization", "none") != "int8":
            raise SystemExit("--model llama-8b requires --quantization int8")
        cfg = LLAMA_3_1_8B
        dtype = jnp.bfloat16
        # lint: allow(jit-static-drift): one-shot init compile at bench setup; the cache's lifetime is irrelevant
        params = jax.jit(lambda k: random_int8_params(cfg, k))(
            jax.random.PRNGKey(1)
        )
        jax.block_until_ready(params)
        # params are already quantized; the engine must not re-quantize
        args.quantization = "none"
    else:
        from ..llm import HuggingFaceTokenizer  # noqa: F401 — config check
        from ..models import ModelConfig

        cfg = ModelConfig.from_pretrained(args.model)
        dtype = jnp.bfloat16
        params = load_params(args.model, cfg, dtype=dtype)
    pages = -(-(args.isl + args.osl) // 16) + 1
    return JaxEngine(cfg, params, EngineConfig(
        page_size=16,
        num_pages=1 + (maxc + 2) * pages + 32,
        max_num_seqs=maxc,
        max_prefill_tokens=args.isl,
        prefill_batch_size=4,
        max_model_len=args.isl + args.osl + 16,
        decode_steps=8,
        quantization=getattr(args, "quantization", "none"),
        enable_prefix_caching=False,
    ), eos_token_ids=[], kv_dtype=dtype)


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser("dynamo_tpu.planner.profiler")
    ap.add_argument("--out", required=True, help="output npz path")
    ap.add_argument("--model", default="tiny",
                    help="tiny | llama-1b | llama-8b (int8 only) | "
                         "checkpoint dir")
    ap.add_argument("--mock", action="store_true")
    ap.add_argument("--quantization", default="none",
                    choices=["none", "int8"],
                    help="profile the weight-only int8 serving path")
    ap.add_argument("--isl", type=int, nargs="+", default=[512],
                    help="one value sweeps a single cell; several sweep "
                         "a grid (one npz per cell, reference "
                         "pre_swept_results layout)")
    ap.add_argument("--osl", type=int, nargs="+", default=[64])
    ap.add_argument("--concurrency", type=int, nargs="+",
                    default=[1, 2, 4, 8])
    ap.add_argument("--window", type=float, default=6.0)
    ap.add_argument("--disagg", action="store_true",
                    help="sweep the prefill and decode ROLES separately "
                         "through two engine instances + the real KV "
                         "data plane; writes <out>_disagg_prefill.npz "
                         "and <out>_disagg_decode.npz (reference "
                         "pre-sweeps roles separately)")
    args = ap.parse_args(argv)

    if args.disagg:
        if args.mock:
            raise SystemExit(
                "--disagg needs the real engine's data-plane API "
                "(prefill_remote / generate_imported) — not --mock")
        if len(args.isl) != 1 or len(args.osl) != 1:
            raise SystemExit("--disagg sweeps a single (isl, osl) cell")
        isl, osl = args.isl[0], args.osl[0]
        pre = _build_engine(argparse.Namespace(
            **{**vars(args), "isl": isl, "osl": osl}))
        dec = _build_engine(argparse.Namespace(
            **{**vars(args), "isl": isl, "osl": osl}))
        cfg = SweepConfig(isl=isl, osl=osl,
                          concurrencies=args.concurrency,
                          prefill_window_s=args.window)

        async def run_disagg():
            roles = await sweep_disagg(pre, dec, cfg)
            for e in (pre, dec):
                if hasattr(e, "shutdown"):
                    await e.shutdown()
            return roles

        prefill_role, decode_role = asyncio.run(run_disagg())
        base = args.out[:-4] if args.out.endswith(".npz") else args.out
        for role, prof in (("prefill", prefill_role),
                           ("decode", decode_role)):
            path = f"{base}_disagg_{role}.npz"
            prof.save_npz(path)
            print(f"disagg {role}-role profile written to {path}")
        for c, itl, t in zip(decode_role.decode_concurrency,
                             decode_role.itl_s,
                             decode_role.decode_throughput):
            print(f"  decode-role c={c:5.0f}: itl={itl*1000:7.2f}ms "
                  f"{t:9.1f} tok/s")
        for load, ttft in zip(prefill_role.prefill_load,
                              prefill_role.ttft_s):
            print(f"  prefill-role {load:9.1f} tok/s offered: "
                  f"ttft(+handoff)={ttft*1000:7.1f}ms")
        return

    grid = [(i, o) for i in args.isl for o in args.osl]

    def cell_path(isl, osl):
        if len(grid) == 1:
            return args.out
        import os

        os.makedirs(args.out, exist_ok=True)
        return os.path.join(args.out, f"isl{isl}_osl{osl}.npz")

    index = {}
    for isl, osl in grid:
        cell_args = argparse.Namespace(**{**vars(args), "isl": isl, "osl": osl})
        engine = _build_engine(cell_args)
        cfg = SweepConfig(
            isl=isl, osl=osl,
            concurrencies=args.concurrency,
            prefill_window_s=args.window,
        )

        async def run():
            profile = await sweep_engine(engine, cfg)
            if hasattr(engine, "shutdown"):
                await engine.shutdown()
            return profile

        profile = asyncio.run(run())
        path = cell_path(isl, osl)
        profile.save_npz(path)
        index[f"{isl}x{osl}"] = path
        print(f"profile [isl={isl} osl={osl}] written to {path}:")
        for c, itl, t in zip(profile.decode_concurrency, profile.itl_s,
                             profile.decode_throughput):
            print(f"  decode c={c:5.0f}: itl={itl*1000:7.2f}ms {t:9.1f} tok/s")
        for load, ttft in zip(profile.prefill_load, profile.ttft_s):
            print(f"  prefill {load:9.1f} tok/s offered: ttft={ttft*1000:7.1f}ms")
    if len(grid) > 1:
        import json
        import os

        with open(os.path.join(args.out, "index.json"), "w") as f:
            json.dump(index, f, indent=2)
        print(f"grid index written to {args.out}/index.json")


if __name__ == "__main__":
    main()
