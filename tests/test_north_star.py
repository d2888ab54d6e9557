"""The north-star composition, miniaturized (VERDICT r3 item 1b).

`recipes/llama-3-70b-v5e-64.yaml` prescribes: a MULTIHOST decode group
(dp×tp, `--kv-partition`) fed by an sp×tp ring-prefill group over the
disagg KV handoff, with mixed scheduling keeping decode ITL flat.  This
test runs that exact composition scaled to the CI mesh: 2 OS processes
× 4 CPU devices = a dp=4×tp=2 lockstep decode group with the KV pool
partitioned over dp, plus a process-local sp=2×tp=2 ring-prefill
engine, driving disagg prefill→decode handoffs THROUGH the partitioned
multihost engine while local prefills force MIXED dispatches on it.
Greedy outputs must equal a plain single-device engine.

Reference: /root/reference/docs/architecture/disagg_serving.md:110-120.
"""

import os
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NS_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)  # 4 local x 2 hosts = 8 global

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)
assert jax.device_count() == 8

import asyncio
import jax.numpy as jnp
from dynamo_tpu.deploy import GraphSpec
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig

# the miniature IS recipe-derived: same roles, same flag kinds, scaled
spec = GraphSpec.load(os.path.join(%(root)r, "recipes",
                                   "llama-3-70b-v5e-64.yaml"))
by_name = {c.name: c for c in spec.components}
dec_args, pre_args = by_name["decode"].args, by_name["prefill"].args
assert dec_args.get("kv-partition") is True
assert dec_args.get("disagg-role") == "decode"
assert pre_args.get("disagg-role") == "prefill" and int(pre_args["sp"]) > 1

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

# decode group: multihost dp=4 x tp=2, pool partitioned over dp
mh = JaxEngine(
    cfg, params,
    EngineConfig(page_size=8, num_pages=96, max_num_seqs=8,
                 max_prefill_tokens=16, max_model_len=128, decode_steps=2,
                 kv_partition=True),
    kv_dtype=jnp.float32, parallel=ParallelConfig(dp=4, tp=2),
)
assert mh.layout.pooled and mh.cfg.mixed_prefill_tokens > 0

def req(p, n=8):
    return {"token_ids": p, "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}

PROMPTS = [
    [1, 2, 3],
    [(7 * j) %% 101 + 1 for j in range(60)],
    [9, 8, 7, 6, 5],
    [(3 * j) %% 97 + 1 for j in range(45)],
]
HANDOFF = [(11 * j) %% 89 + 1 for j in range(20)]

if rank == 0:
    # prefill group: process-local sp x tp ring prefill (the recipe's
    # prefill role, scaled) — local devices only, no lockstep
    pre = JaxEngine(
        cfg, params,
        EngineConfig(page_size=8, num_pages=96, max_num_seqs=8,
                     max_prefill_tokens=8 * 128,
                     max_model_len=128, enable_prefix_caching=False),
        kv_dtype=jnp.float32, parallel=ParallelConfig(dp=1, sp=2, tp=2),
        multihost=False, devices=jax.local_devices()[:4],
    )
    assert pre.layout.sp == 2

    plans = []
    orig = mh.scheduler.schedule
    def spy():
        plan = orig()
        plans.append(plan.kind)
        return plan
    mh.scheduler.schedule = spy

    async def run():
        async def direct(i, p):
            # local prefills + decodes on the decode group — these are
            # what mixed dispatches interleave
            await asyncio.sleep(0.05 * i)
            toks = []
            async for d in mh.generate(req(p)):
                assert d.get("finish_reason") != "error", d
                toks += d["token_ids"]
            return toks

        async def handoff():
            # the disagg path: sp ring prefill -> partitioned multihost
            # decode (kv_import rides the lockstep plan channel)
            await asyncio.sleep(0.1)
            out = await pre.prefill_remote(req(HANDOFF))
            assert "kv" in out, out
            toks = []
            async for d in mh.generate_with_kv(req(HANDOFF),
                                               out["token_ids"][0],
                                               out["kv"]):
                assert d.get("finish_reason") != "error", d
                toks += d["token_ids"]
            return toks

        outs = await asyncio.gather(
            *[direct(i, p) for i, p in enumerate(PROMPTS)], handoff()
        )
        await pre.shutdown()
        await mh.shutdown()
        return outs

    outs = asyncio.run(run())
    assert "mixed" in plans, (
        "no mixed dispatch on the partitioned multihost pool: "
        f"{set(plans)}"
    )
    print("TOKENS", repr(outs), flush=True)
else:
    mh.follower_loop()
    print("FOLLOWER DONE", flush=True)
"""

NS_REFERENCE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import asyncio
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
engine = JaxEngine(
    cfg, params,
    EngineConfig(page_size=8, num_pages=96, max_num_seqs=8,
                 max_prefill_tokens=16, max_model_len=128, decode_steps=2),
    kv_dtype=jnp.float32,
)

def req(p, n=8):
    return {"token_ids": p, "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}

PROMPTS = [
    [1, 2, 3],
    [(7 * j) % 101 + 1 for j in range(60)],
    [9, 8, 7, 6, 5],
    [(3 * j) % 97 + 1 for j in range(45)],
]
HANDOFF = [(11 * j) % 89 + 1 for j in range(20)]

async def run():
    async def one(i, p):
        await asyncio.sleep(0.05 * i)
        toks = []
        async for d in engine.generate(req(p)):
            toks += d["token_ids"]
        return toks

    outs = await asyncio.gather(
        *[one(i, p) for i, p in enumerate(PROMPTS)], one(2, HANDOFF)
    )
    await engine.shutdown()
    return outs

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


def _tokens_from(out: str):
    for line in out.splitlines():
        if line.startswith("TOKENS "):
            return eval(line[len("TOKENS "):])  # noqa: S307 — our own output
    raise AssertionError(f"no TOKENS line in:\n{out}")


@pytest.mark.timeout(600)
def test_north_star_composition():
    """multihost × kv_partition × disagg × mixed, in one deployment."""
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    worker_src = NS_WORKER % {"root": ROOT}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", worker_src, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=540)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", NS_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)
