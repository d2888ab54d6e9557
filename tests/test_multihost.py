"""Multi-host: 2 OS processes joined via jax.distributed, a global dp×tp
mesh spanning both, SPMD model steps producing tokens identical to
single-process — the TPU-native counterpart of the reference's
multi-node engine worlds (MultinodeSpec nodeCount)."""

import os
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)  # 2 local x 2 hosts = 4 global

from dynamo_tpu.parallel.multihost import (
    broadcast_plan, global_mesh, host_array_to_global, initialize_multihost,
)

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)
assert jax.device_count() == 4 and jax.local_device_count() == 2

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.models import KVCache, forward_decode, forward_prefill, init_params, tiny_config
from dynamo_tpu.models.llama import kv_cache_pspec, param_pspecs

cfg = tiny_config()
params_host = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
mesh = global_mesh(dp=2, tp=2)

specs = param_pspecs(cfg)
params = jax.tree.map(
    lambda a, s: host_array_to_global(mesh, s, np.asarray(a)), params_host, specs
)
page_size, pages_per_seq, B, S = 8, 6, 4, 16
kv_spec = kv_cache_pspec()
kv_host = KVCache.create(cfg, 1 + B * pages_per_seq, page_size, jnp.float32)
kv = KVCache(
    host_array_to_global(mesh, kv_spec.k, np.asarray(kv_host.k)),
    host_array_to_global(mesh, kv_spec.v, np.asarray(kv_host.v)),
)

tokens = np.arange(B * S, dtype=np.int32).reshape(B, S) % cfg.vocab_size
table = np.arange(1, 1 + B * pages_per_seq, dtype=np.int32).reshape(B, pages_per_seq)
put = lambda arr, *ax: host_array_to_global(mesh, P(*ax), np.asarray(arr))

# sampled tokens come back REPLICATED so every host can fetch them
# (cross-process shards are not addressable locally)
rep = NamedSharding(mesh, P())
kv_out = KVCache(NamedSharding(mesh, kv_spec.k), NamedSharding(mesh, kv_spec.v))

@lambda f: jax.jit(f, out_shardings=(rep, kv_out))
def prefill_step(p, k, t, tb, pre, ch):
    logits, k = forward_prefill(p, cfg, k, t, tb, pre, ch)
    return jnp.argmax(logits, -1).astype(jnp.int32), k

@lambda f: jax.jit(f, out_shardings=(rep, kv_out))
def decode_step(p, k, t, po, tb):
    logits, k = forward_decode(p, cfg, k, t, po, tb)
    return jnp.argmax(logits, -1).astype(jnp.int32), k

last_d, kv = prefill_step(
    params, kv,
    put(tokens, "dp", None), put(table, "dp", None),
    put(np.zeros(B, np.int32), "dp"), put(np.full(B, S, np.int32), "dp"),
)
toks = []
positions = np.full(B, S, np.int32)
for step in range(4):
    last = np.asarray(jax.device_get(last_d)).astype(np.int32)
    toks.append(last.tolist())
    last_d, kv = decode_step(
        params, kv, put(last, "dp"), put(positions, "dp"), put(table, "dp", None),
    )
    positions = positions + 1

# lockstep plan broadcast: every rank must see rank 0's bytes
plan = broadcast_plan(b"plan-from-rank-0" if rank == 0 else b"overwritten")
assert plan == b"plan-from-rank-0", plan
print("TOKENS", repr(toks), flush=True)
"""

REFERENCE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from dynamo_tpu.models import KVCache, forward_decode, forward_prefill, init_params, tiny_config

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
page_size, pages_per_seq, B, S = 8, 6, 4, 16
kv = KVCache.create(cfg, 1 + B * pages_per_seq, page_size, jnp.float32)
tokens = jnp.asarray(np.arange(B * S, dtype=np.int32).reshape(B, S) % cfg.vocab_size)
table = jnp.asarray(np.arange(1, 1 + B * pages_per_seq, dtype=np.int32).reshape(B, pages_per_seq))
logits, kv = forward_prefill(params, cfg, kv, tokens, table,
                             jnp.zeros(B, jnp.int32), jnp.full(B, S, jnp.int32))
toks = []
last = np.asarray(logits).argmax(-1).astype(np.int32)
positions = np.full(B, S, np.int32)
for step in range(4):
    toks.append(last.tolist())
    logits, kv = forward_decode(params, cfg, kv, jnp.asarray(last),
                                jnp.asarray(positions), table)
    last = np.asarray(logits).argmax(-1).astype(np.int32)
    positions = positions + 1
print("TOKENS", repr(toks), flush=True)
"""


def _tokens_from(out: str):
    for line in out.splitlines():
        if line.startswith("TOKENS "):
            return eval(line[len("TOKENS "):])  # noqa: S307 — our own output
    raise AssertionError(f"no TOKENS line in:\n{out}")


@pytest.mark.timeout(300)
def test_two_host_spmd_matches_single_process():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)  # workers set their own device counts
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    ref = subprocess.run(
        [sys.executable, "-c", REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr

    want = _tokens_from(ref.stdout)
    for out in outs:
        assert _tokens_from(out) == want

# -- lockstep serving engine across 2 processes ----------------------------- #
# Rank 0 serves requests through the real JaxEngine (scheduler + pump);
# rank 1 constructs the same engine and replays rank 0's broadcast plans
# (JaxEngine.follower_loop).  Greedy output must equal a single-process
# single-device engine.

LOCKSTEP_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)  # 2 local x 2 hosts = 4 global

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)
assert jax.device_count() == 4

import asyncio
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
ecfg = EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                    max_prefill_tokens=32, max_model_len=64)
engine = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32,
                   parallel=ParallelConfig(dp=2, tp=2))

if rank == 0:
    async def run():
        outs = []
        for i in range(3):
            p = [(i * 13 + j) % cfg.vocab_size for j in range(5 + 3 * i)]
            # request 1 is penalized: exercises the sparse counts
            # broadcast + follower-side histogram rebuild
            so = {"temperature": 0.0}
            if i == 1:
                so["frequency_penalty"] = 0.7
            req = {"token_ids": p,
                   "sampling_options": so,
                   "stop_conditions": {"max_tokens": 6, "ignore_eos": True}}
            toks = []
            async for out in engine.generate(req):
                assert out.get("finish_reason") != "error", out
                toks += out["token_ids"]
            outs.append(toks)
        await engine.shutdown()
        return outs

    print("TOKENS", repr(asyncio.run(run())), flush=True)
else:
    engine.follower_loop()
    print("FOLLOWER DONE", flush=True)
"""

LOCKSTEP_REFERENCE = r"""
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
ecfg = EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                    max_prefill_tokens=32, max_model_len=64)
engine = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32)

async def run():
    outs = []
    for i in range(3):
        p = [(i * 13 + j) % cfg.vocab_size for j in range(5 + 3 * i)]
        so = {"temperature": 0.0}
        if i == 1:
            so["frequency_penalty"] = 0.7
        req = {"token_ids": p,
               "sampling_options": so,
               "stop_conditions": {"max_tokens": 6, "ignore_eos": True}}
        toks = []
        async for out in engine.generate(req):
            assert out.get("finish_reason") != "error", out
            toks += out["token_ids"]
        outs.append(toks)
    await engine.shutdown()
    return outs

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


@pytest.mark.timeout(300)
def test_lockstep_engine_two_hosts_matches_single_process():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", LOCKSTEP_WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", LOCKSTEP_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)


# -- disaggregation composed with multihost lockstep ------------------------ #
# The multihost engine group acts as BOTH disagg roles: (a) decode side —
# a process-local prefill engine hands KV over and the lockstep group
# imports + continues (the "kv_import" plan); (b) prefill side — the group
# prefills, exports the pages via the "kv_export" plan, and the local
# engine decodes.  Embeddings ride the "embed" plan.  Greedy outputs must
# match a plain single-process engine (VERDICT r2 item 1a).

DISAGG_MH_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)

import asyncio
import numpy as np
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
ecfg = lambda: EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                            max_prefill_tokens=64, max_model_len=64)
mh = JaxEngine(cfg, params, ecfg(), kv_dtype=jnp.float32,
               parallel=ParallelConfig(dp=2, tp=2))

def req(p, n=6):
    return {"token_ids": p, "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}

if rank == 0:
    local = JaxEngine(cfg, params, ecfg(), kv_dtype=jnp.float32,
                      multihost=False)

    async def run():
        p = [(7 * j) % cfg.vocab_size for j in range(20)]
        # (a) local prefill -> multihost decode (lockstep kv_import)
        out = await local.prefill_remote(req(p))
        assert "kv" in out, out
        toks_a = []
        async for d in mh.generate_with_kv(req(p), out["token_ids"][0],
                                           out["kv"]):
            assert d.get("finish_reason") != "error", d
            toks_a.extend(d["token_ids"])
        # (b) multihost prefill (lockstep kv_export) -> local decode
        out2 = await mh.prefill_remote(req(p))
        assert "kv" in out2, out2
        toks_b = []
        async for d in local.generate_with_kv(req(p), out2["token_ids"][0],
                                              out2["kv"]):
            assert d.get("finish_reason") != "error", d
            toks_b.extend(d["token_ids"])
        # (c) embeddings through the lockstep embed plan
        emb = await mh.embed({"embed_token_ids": [p[:8], p[:5]]})
        assert len(emb["embeddings"]) == 2 and emb["prompt_tokens"] == 13
        n = float(np.linalg.norm(emb["embeddings"][0]))
        assert abs(n - 1.0) < 1e-3, n
        await local.shutdown()
        await mh.shutdown()
        return [toks_a, toks_b]

    print("TOKENS", repr(asyncio.run(run())), flush=True)
else:
    mh.follower_loop()
    print("FOLLOWER DONE", flush=True)
"""

DISAGG_MH_REFERENCE = r"""
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
engine = JaxEngine(cfg, params,
                   EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                                max_prefill_tokens=64, max_model_len=64),
                   kv_dtype=jnp.float32)

async def run():
    p = [(7 * j) % cfg.vocab_size for j in range(20)]
    req = {"token_ids": p, "sampling_options": {"temperature": 0.0},
           "stop_conditions": {"max_tokens": 6, "ignore_eos": True}}
    toks = []
    async for out in engine.generate(req):
        toks += out["token_ids"]
    await engine.shutdown()
    return [toks, toks]

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


@pytest.mark.timeout(300)
def test_disagg_composes_with_multihost_lockstep():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", DISAGG_MH_WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", DISAGG_MH_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)


# -- KVBM tiering + per-shard KV import under multihost lockstep ------------ #
# The decode group runs kv_partition over dp; KV imports are no longer
# broadcast whole on the plan channel — the leader stages the blob and
# each host fetches only the byte ranges its devices' shards need
# (engine/blob_stage.py).  A host that owns no part of the target pool
# rank fetches NOTHING, so aggregate DCN traffic for R-rank pools drops
# from O(hosts x blob) toward O(1x).  KVBM offload/onboard rides the
# same lockstep channel (VERDICT r3 item 5).

KVBM_MH_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)

import asyncio
import numpy as np
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.kvbm import HostBlockPool, TieredKvCache
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
ecfg = EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=64,
                    kv_partition=True)
tiered = TieredKvCache(HostBlockPool(capacity_bytes=64 << 20)) if rank == 0 else None
mh = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32,
               parallel=ParallelConfig(dp=2, tp=2), tiered=tiered)
assert mh.layout.pooled and mh.layout.pool_ranks == 2

def req(p, n=6):
    return {"token_ids": p, "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}

if rank == 0:
    local = JaxEngine(cfg, params,
                      EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                                   max_prefill_tokens=64, max_model_len=64),
                      kv_dtype=jnp.float32, multihost=False)

    async def run():
        p1 = [(7 * j) % cfg.vocab_size for j in range(20)]
        p2 = [(5 * j + 3) % cfg.vocab_size for j in range(20)]
        outs = []
        # two CONCURRENT equal-size disagg handoffs: the second import
        # sees the first's pages still held, so the allocator spreads
        # them over BOTH partitions — one lands on the rank the
        # follower owns no part of (fetches zero bytes), the other on
        # the follower's rank (fetches that blob once)
        async def handoff(p):
            out = await local.prefill_remote(req(p))
            assert "kv" in out, out
            toks = []
            async for d in mh.generate_with_kv(req(p), out["token_ids"][0],
                                               out["kv"]):
                assert d.get("finish_reason") != "error", d
                toks.extend(d["token_ids"])
            return toks

        outs.extend(await asyncio.gather(handoff(p1), handoff(p2)))
        # KVBM under multihost: the handoffs above committed pages; the
        # offload pump exports them (kv_export plans), then a cache
        # clear forces onboarding (kv_import_fetch plans)
        deadline = asyncio.get_running_loop().time() + 10
        while tiered.offload_backlog or len(tiered.host) == 0:
            assert asyncio.get_running_loop().time() < deadline, "no offload"
            await asyncio.sleep(0.05)
        mh.clear_kv_blocks()
        toks3 = []
        async for d in mh.generate(req(p1)):
            assert d.get("finish_reason") != "error", d
            toks3.extend(d["token_ids"])
        assert tiered.onboarded_blocks >= 1, tiered.onboarded_blocks
        outs.append(toks3)
        await local.shutdown()
        await mh.shutdown()
        return outs

    outs = asyncio.run(run())
    print("STAGED", mh._blob_bytes_staged, mh._blob_bytes_served,
          flush=True)
    print("TOKENS", repr(outs), flush=True)
else:
    mh.follower_loop()
    print("FETCHED", mh._blob_bytes_fetched, flush=True)
    print("FOLLOWER DONE", flush=True)
"""

KVBM_MH_REFERENCE = r"""
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
engine = JaxEngine(cfg, params,
                   EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                                max_prefill_tokens=64, max_model_len=64),
                   kv_dtype=jnp.float32)

def req(p, n=6):
    return {"token_ids": p, "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}

async def run():
    p1 = [(7 * j) % cfg.vocab_size for j in range(20)]
    p2 = [(5 * j + 3) % cfg.vocab_size for j in range(20)]
    outs = []
    for p in (p1, p2, p1):
        toks = []
        async for out in engine.generate(req(p)):
            toks += out["token_ids"]
        outs.append(toks)
    await engine.shutdown()
    return outs

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


@pytest.mark.timeout(300)
def test_kvbm_and_per_shard_import_under_multihost():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", KVBM_MH_WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", KVBM_MH_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)

    # per-shard fetch accounting: one handoff targeted the pool rank the
    # follower owns no part of (zero bytes), so the follower pulled
    # strictly less than the staged total — the broadcast design moved
    # 100% to every host
    fetched = staged = None
    for line in outs[1].splitlines():
        if line.startswith("FETCHED "):
            fetched = int(line.split()[1])
    for line in outs[0].splitlines():
        if line.startswith("STAGED "):
            staged = int(line.split()[1])
    assert fetched is not None and staged is not None and staged > 0
    assert fetched > 0, "follower fetched nothing — imports never ran?"
    # the old design broadcast 100% of every blob to every host; at
    # least one import here targeted the pool rank the follower owns no
    # part of, so it pulled strictly less than the staged total
    assert fetched <= 0.8 * staged, (fetched, staged)


# -- vision tower composed with multihost lockstep --------------------------- #
# The tower runs leader-local; the resulting patch embeddings ride the
# lockstep prefill plan so every rank issues the identical with-embeds
# prefill (VERDICT r3 item 10).

VISION_MH_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)

import asyncio
import numpy as np
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.multimodal import pack_pixels
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.models.vision import init_vision_params, tiny_vision_config
from dynamo_tpu.parallel import ParallelConfig

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
vcfg = tiny_vision_config(out_hidden_size=cfg.hidden_size)
vparams = init_vision_params(vcfg, jax.random.PRNGKey(7), dtype=jnp.float32)
mh = JaxEngine(cfg, params,
               EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                            max_prefill_tokens=64, max_model_len=64),
               kv_dtype=jnp.float32, parallel=ParallelConfig(dp=2, tp=2),
               vision=(vparams, vcfg))

P = vcfg.num_patches
rng = np.random.default_rng(3)
pixels = rng.uniform(0, 1, (1, vcfg.image_size, vcfg.image_size, 3)).astype(np.float32)
prompt = [5, 9] + [250] * P + [17, 23]
req = {"token_ids": prompt,
       "sampling_options": {"temperature": 0.0},
       "stop_conditions": {"max_tokens": 6, "ignore_eos": True},
       "mm_pixels": pack_pixels(pixels), "mm_offsets": [2]}

if rank == 0:
    async def run():
        toks = []
        async for d in mh.generate(dict(req)):
            assert d.get("finish_reason") != "error", d
            toks += d["token_ids"]
        await mh.shutdown()
        return toks

    print("TOKENS", repr(asyncio.run(run())), flush=True)
else:
    mh.follower_loop()
    print("FOLLOWER DONE", flush=True)
"""

VISION_MH_REFERENCE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import asyncio
import numpy as np
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.multimodal import pack_pixels
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.models.vision import init_vision_params, tiny_vision_config

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
vcfg = tiny_vision_config(out_hidden_size=cfg.hidden_size)
vparams = init_vision_params(vcfg, jax.random.PRNGKey(7), dtype=jnp.float32)
engine = JaxEngine(cfg, params,
                   EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                                max_prefill_tokens=64, max_model_len=64),
                   kv_dtype=jnp.float32, vision=(vparams, vcfg))

P = vcfg.num_patches
rng = np.random.default_rng(3)
pixels = rng.uniform(0, 1, (1, vcfg.image_size, vcfg.image_size, 3)).astype(np.float32)
prompt = [5, 9] + [250] * P + [17, 23]
req = {"token_ids": prompt,
       "sampling_options": {"temperature": 0.0},
       "stop_conditions": {"max_tokens": 6, "ignore_eos": True},
       "mm_pixels": pack_pixels(pixels), "mm_offsets": [2]}

async def run():
    toks = []
    async for d in engine.generate(req):
        assert d.get("finish_reason") != "error", d
        toks += d["token_ids"]
    await engine.shutdown()
    return toks

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


@pytest.mark.timeout(300)
def test_vision_composes_with_multihost():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", VISION_MH_WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", VISION_MH_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)


# -- pipeline parallelism composed with multihost lockstep ------------------ #
# The GPipe-staged serving engine spans 2 processes: a dp=1 x pp=2 x tp=2
# mesh over 4 global devices, rank 0 serving and rank 1 replaying plans
# (round 4: the 70B recipe needs tp*pp >= 8 ACROSS hosts — 16GB/chip
# v5e holds no 70B stack on one host's chips).  Greedy + penalized +
# top-logprobs outputs must equal a plain single-device engine.

PP_MH_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)  # 2 local x 2 hosts = 4 global

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)
assert jax.device_count() == 4

import asyncio
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig

cfg = tiny_config()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
ecfg = EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                    max_prefill_tokens=32, max_model_len=64)
engine = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32,
                   parallel=ParallelConfig(tp=2, pp=2))

if rank == 0:
    async def run():
        outs = []
        for i in range(3):
            p = [(i * 13 + j) % cfg.vocab_size for j in range(5 + 3 * i)]
            so = {"temperature": 0.0}
            sc = {"max_tokens": 6, "ignore_eos": True}
            if i == 1:  # penalized: last-stage histogram + sparse plan
                so["frequency_penalty"] = 0.7
            if i == 2:  # top-logprobs ride the ring's last stage
                so["top_logprobs"] = 3
            req = {"token_ids": p, "sampling_options": so,
                   "stop_conditions": sc}
            toks = []
            async for out in engine.generate(req):
                assert out.get("finish_reason") != "error", out
                toks += out["token_ids"]
            outs.append(toks)
        await engine.shutdown()
        return outs

    print("TOKENS", repr(asyncio.run(run())), flush=True)
else:
    engine.follower_loop()
    print("FOLLOWER DONE", flush=True)
"""

PP_MH_REFERENCE = r"""
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
ecfg = EngineConfig(page_size=8, num_pages=64, max_num_seqs=4,
                    max_prefill_tokens=32, max_model_len=64)
engine = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32)

async def run():
    outs = []
    for i in range(3):
        p = [(i * 13 + j) % cfg.vocab_size for j in range(5 + 3 * i)]
        so = {"temperature": 0.0}
        sc = {"max_tokens": 6, "ignore_eos": True}
        if i == 1:
            so["frequency_penalty"] = 0.7
        if i == 2:
            so["top_logprobs"] = 3
        req = {"token_ids": p, "sampling_options": so,
               "stop_conditions": sc}
        toks = []
        async for out in engine.generate(req):
            assert out.get("finish_reason") != "error", out
            toks += out["token_ids"]
        outs.append(toks)
    await engine.shutdown()
    return outs

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


@pytest.mark.timeout(300)
def test_pp_engine_composes_with_multihost():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", PP_MH_WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", PP_MH_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)


# -- wide-EP all-to-all composed with multihost lockstep -------------------- #
# The 64-expert a2a MoE dispatch runs on a 2-process sp=2 x tp=2 mesh:
# expert all-to-alls cross the host boundary (the reference's wide-EP
# story is multi-node 16-way — recipes/deepseek-r1/sglang-wideep).
# Greedy output must equal a plain single-process engine.

WIDEEP_MH_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)  # 2 local x 2 hosts = 4 global

from dynamo_tpu.parallel.multihost import initialize_multihost

rank = int(sys.argv[1])
assert initialize_multihost(sys.argv[2], num_hosts=2, host_id=rank)
assert jax.device_count() == 4

import asyncio
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_moe_config
from dynamo_tpu.parallel import ParallelConfig

cfg = tiny_moe_config(num_experts=64, num_experts_per_tok=4,
                      moe_impl="a2a", moe_capacity_factor=8.0)
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
ecfg = EngineConfig(page_size=8, num_pages=96, max_num_seqs=4,
                    max_prefill_tokens=4 * 128, prefill_batch_size=1,
                    max_model_len=128, enable_prefix_caching=False)
engine = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32,
                   parallel=ParallelConfig(sp=2, tp=2))

if rank == 0:
    async def run():
        outs = []
        for i in range(3):
            p = [(7 * j + i) % cfg.vocab_size for j in range(20 + 4 * i)]
            req = {"token_ids": p,
                   "sampling_options": {"temperature": 0.0},
                   "stop_conditions": {"max_tokens": 5, "ignore_eos": True}}
            toks = []
            async for out in engine.generate(req):
                assert out.get("finish_reason") != "error", out
                toks += out["token_ids"]
            outs.append(toks)
        await engine.shutdown()
        return outs

    print("TOKENS", repr(asyncio.run(run())), flush=True)
else:
    engine.follower_loop()
    print("FOLLOWER DONE", flush=True)
"""

WIDEEP_MH_REFERENCE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import asyncio
import jax.numpy as jnp
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_moe_config

cfg = tiny_moe_config(num_experts=64, num_experts_per_tok=4,
                      moe_impl="a2a", moe_capacity_factor=8.0)
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
ecfg = EngineConfig(page_size=8, num_pages=96, max_num_seqs=4,
                    max_prefill_tokens=4 * 128, prefill_batch_size=1,
                    max_model_len=128, enable_prefix_caching=False)
engine = JaxEngine(cfg, params, ecfg, kv_dtype=jnp.float32)

async def run():
    outs = []
    for i in range(3):
        p = [(7 * j + i) % cfg.vocab_size for j in range(20 + 4 * i)]
        req = {"token_ids": p,
               "sampling_options": {"temperature": 0.0},
               "stop_conditions": {"max_tokens": 5, "ignore_eos": True}}
        toks = []
        async for out in engine.generate(req):
            assert out.get("finish_reason") != "error", out
            toks += out["token_ids"]
        outs.append(toks)
    await engine.shutdown()
    return outs

print("TOKENS", repr(asyncio.run(run())), flush=True)
"""


@pytest.mark.timeout(300)
def test_wide_ep_a2a_composes_with_multihost():
    env = {**os.environ, "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", WIDEEP_MH_WORKER, str(rank), coordinator],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out
        outs.append(out)
    assert "FOLLOWER DONE" in outs[1]

    ref = subprocess.run(
        [sys.executable, "-c", WIDEEP_MH_REFERENCE], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=240,
    )
    assert ref.returncode == 0, ref.stdout + ref.stderr
    assert _tokens_from(outs[0]) == _tokens_from(ref.stdout)
