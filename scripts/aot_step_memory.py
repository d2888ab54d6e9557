#!/usr/bin/env python3
"""What a configuration's `prefill_step` programs take of a v5e's memory,
compiled HERE for a described chip (no chip, no weights: shapes alone):

    python3 scripts/aot_step_memory.py benchmark/configs/falcon-h1-34b-h6.json \\
        --num-pages 6400 --num-state-slots 137 --max-model-len 8192 \\
        --steps 1x512x512,4x64x512,1x16x512

A step is rows x chunk x table pages.  One line a step: arguments (weights
and both pools), temporaries, and what is left under the 16,911,433,728 B
the compiler allows; a step that does not fit prints the compiler's own
words.  This is where a cell's `--num-pages` / `--num-state-slots` come
from (PR 24's rule: the largest step program 0.5 GB under the limit), and
what showed that a gathered read of a state pool whose last axis is two
lane tiles copies the pool (`hybrid.read_state`; PERF.md finding 39)."""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from dynamo_tpu.engine import EngineConfig  # noqa: E402
from dynamo_tpu.engine.layout import Layout  # noqa: E402
from dynamo_tpu.models import KVCache, ModelConfig, init_params  # noqa: E402
from dynamo_tpu.ops.sampling import SamplingParams  # noqa: E402

LIMIT = 16911433728  # what the TPU compiler allows a program on a v5e
PAGE = 16


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--num-pages", type=int, required=True)
    ap.add_argument("--num-state-slots", type=int, default=0)
    ap.add_argument("--max-model-len", type=int, default=8192)
    ap.add_argument("--steps", default="1x512x512,4x64x512")
    ap.add_argument("--layers", type=int, help="instead of the file's depth")
    args = ap.parse_args()
    with open(args.config) as f:
        model = json.load(f)["model"]
    if args.layers:
        model = dict(model, num_hidden_layers=args.layers)
    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cfg = ModelConfig.from_hf_config(model)
    layout = Layout.resolve(cfg, EngineConfig(
        attention_impl="adaptive", num_pages=args.num_pages,
        num_state_slots=args.num_state_slots,
        max_model_len=args.max_model_len))[0]

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    def rep(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    kv = shapes(jax.eval_shape(lambda: KVCache.create(
        cfg, args.num_pages, PAGE, jnp.bfloat16,
        state_slots=args.num_state_slots)))
    for tree, name in ((params, "weights"), (kv, "pools")):
        print(name, f"{sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)):,} B")
    step = layout.prefill_step(False, greedy=True)
    for rows, chunk, pages in (map(int, s.split("x"))
                               for s in args.steps.split(",")):
        one_i, f32 = rep(jnp.int32, rows), rep(jnp.float32, rows)
        operands = (params, kv, rep(jnp.int32, rows, chunk),
                    rep(jnp.int32, rows, pages + layout.state_cols), one_i,
                    one_i, SamplingParams(f32, one_i, f32, f32, f32),
                    rep(jnp.uint32, rows), one_i, rep(jnp.bool_, rows))
        try:
            m = step.lower(*operands).compile().memory_analysis()
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the answer
            print(f"{rows}x{chunk}x{pages}: DOES NOT FIT: {str(e)[:600]}")
            continue
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"{rows}x{chunk}x{pages}: arguments "
              f"{m.argument_size_in_bytes:,} temporaries "
              f"{m.temp_size_in_bytes:,} total {total:,} under the limit by "
              f"{LIMIT - total:,}")


if __name__ == "__main__":
    main()
