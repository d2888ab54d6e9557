"""Whole `prefill_step` programs of one configuration, timed on the chip
this process holds: for each row count, table width and chunk size, the
flat engine's jitted step over random weights at the configuration's
widths, every row a full table of its own pages, median of N runs after a
warm-up.  One JSON line per (moe_impl, attention, batch, table, chunk,
head): `head` 1 is a step in which a row samples (a prompt's last chunk),
0 one in which none does, which skips the output head; `attn` is the
attention program the trace of that shape noted ("pallas" | "xla": under
`--attention auto` the rule's answer, and what a forced form came to, as on
the CPU, where a latent model keeps XLA's).

    python scripts/time_prefill_steps.py benchmark/configs/<config>.json \\
        [--chunks 512,256,64,16] [--table-pages 256[,128,64]] \\
        [--batch 1[,2,4]] [--impls auto,dense,ragged] \\
        [--attention auto[,pallas,xla]] [--head 1[,0]] \\
        [--max-model-len 4096] [--num-pages N]

Single process, no children; a measurement needs a TPU (refuses the CPU)."""

import argparse
import dataclasses
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--chunks", default="512,256,64,16")
    ap.add_argument("--table-pages", default="256")
    ap.add_argument("--batch", default="1",
                    help="row counts: the rows of a step that short chunks "
                    "share (engine/config.py SHARED_PREFILL_ROWS)")
    ap.add_argument("--impls", default="auto,dense,ragged")
    ap.add_argument("--attention", default="auto",
                    help="attention_impl: auto is the engine's own rule by "
                    "shape (ops/paged_attention.py _adapt), pallas and xla "
                    "force one form")
    ap.add_argument("--head", default="1",
                    help="1: the step's first row samples; 0: no row does "
                    "(the same program: `samples` is an operand)")
    ap.add_argument("--runs", type=int, default=15)
    ap.add_argument("--max-model-len", type=int, default=4096,
                    help="the context the layout is resolved for (a cell's "
                    "own: 8192 under 512-page tables)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool pages in place of the configuration's (a form "
                    "whose temporaries do not fit beside the cell's pool)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the control flow on the CPU in float32; "
                    "its times mean nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.analysis import xla_ledger
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.layout import Layout
    from dynamo_tpu.models import KVCache, ModelConfig, init_params
    from dynamo_tpu.ops.sampling import SamplingParams

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit(f"no TPU here ({dev.platform}): nothing to time")
    dtype = jnp.float32 if args.rehearse_cpu else jnp.bfloat16
    with open(args.config) as f:
        run = json.load(f)
    base = ModelConfig.from_hf_config(run["model"])
    pages = args.num_pages or run["worker_flags"]["--num-pages"]
    # a model with state-space layers: its state slots beside the pages
    # (every row reads and writes a slot of its own)
    slots = run["worker_flags"].get("--num-state-slots", 32)
    params = jax.jit(lambda: init_params(base, jax.random.PRNGKey(0),
                                         dtype))()
    shapes = [(int(b), int(t), int(c), int(h)) for b in args.batch.split(",")
              for t in args.table_pages.split(",")
              for c in args.chunks.split(",") for h in args.head.split(",")]
    for impl, attention in itertools.product(args.impls.split(","),
                                             args.attention.split(",")):
        cfg = dataclasses.replace(base, moe_impl=impl)
        layout = Layout.resolve(cfg, EngineConfig(
            num_pages=pages, num_state_slots=slots,
            max_model_len=args.max_model_len,
            attention_impl=attention))[0]
        step = layout.prefill_step(False, greedy=True)
        kv = KVCache.create(cfg, pages, 16, dtype, state_slots=slots)
        for batch, table_pages, chunk, head in shapes:
            ones = jnp.ones((batch,), jnp.float32)
            zeros = jnp.zeros((batch,), jnp.int32)
            samp = SamplingParams(ones, zeros, ones, ones, ones)
            table = jnp.arange(1, 1 + batch * table_pages,
                               dtype=jnp.int32).reshape(batch, table_pages)
            if layout.state_cols:  # [read, write, no snapshot inside]
                own = 1 + jnp.arange(batch, dtype=jnp.int32)[:, None]
                none = jnp.zeros((batch, layout.state_cols - 2), jnp.int32)
                table = jnp.concatenate([table, own, own, none], axis=1)
            prefix = jnp.full((batch,), table_pages * 16 - chunk, jnp.int32)
            toks = jnp.asarray(np.random.default_rng(0).integers(
                4, 260, (batch, chunk)), jnp.int32)
            lens = jnp.full((batch,), chunk, jnp.int32)
            samples = jnp.arange(batch) < head
            shape = {"config": run["name"], "moe_impl": impl,
                     "attention": attention, "batch": batch,
                     "chunk": chunk, "table_tokens": table_pages * 16,
                     "head": head}
            times = []
            try:
                for i in range(args.runs + 2):
                    t0 = time.perf_counter()
                    packed, _, kv = step(
                        params, kv, toks, table, prefix, lens, samp,
                        jnp.zeros((batch,), jnp.uint32), zeros, samples)
                    packed.block_until_ready()
                    if i >= 2:
                        times.append((time.perf_counter() - t0) * 1e3)
            except Exception as e:  # noqa: BLE001 — a form that does not fit
                print(json.dumps(dict(shape, error=str(e)[:300])), flush=True)
                kv = KVCache.create(cfg, pages, 16, dtype,
                                    state_slots=slots)  # was donated
                continue
            print(json.dumps(dict(
                shape, device=dev.device_kind, prefix=int(prefix[0]),
                attn=xla_ledger.path_choice(
                    "prefill_attention", batch=batch, chunk=chunk,
                    table_tokens=table_pages * 16),
                ms_median=statistics.median(times), ms_min=min(times),
                runs=len(times))), flush=True)
        del kv


if __name__ == "__main__":
    main()
