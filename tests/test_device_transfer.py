"""Device-path KV transfer: colocated engines move pages device-to-device
through a jitted re-page (no host staging, no sockets) while remote
sources keep the TCP host lane — same handle/page protocol either way
(reference: NIXL device transfers with registered metadata,
/root/reference/docs/architecture/disagg_serving.md:95-108)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.disagg.device_transfer import (
    device_repage,
    local_source,
    probe_jax_transfer,
    process_token,
)
from dynamo_tpu.models import KVCache, init_params, tiny_config


def test_jax_transfer_probe_on_this_platform():
    """The real CPU backend implements the PJRT transfer API (the test
    mesh), so the probe passes here; on a backend that raises
    UNIMPLEMENTED for CreateBuffersForAsyncHostToDevice the probe gates
    the lane off instead of letting fetches crash.  Either way the
    result is cached."""
    first = probe_jax_transfer()
    assert first is True  # CPU mesh in tests
    assert probe_jax_transfer() is first  # cached


def test_local_source_requires_matching_process_token():
    assert local_source({"proc": "someone-else", "transfer_id": "x"}) is None
    assert local_source({"proc": process_token(), "transfer_id": "nope"}) is None


def test_device_repage_matches_host_restaging():
    """The jitted re-pager must produce exactly what the host-staged
    path produces: token-major truncation at prompt_len, zero padding,
    page-size change, dtype cast."""
    cfg = tiny_config()
    src_ps, dst_ps = 8, 16
    n_src, prompt_len = 4, 27  # ragged: crosses both page sizes
    kv = KVCache.create(cfg, 1 + n_src + 2, src_ps, jnp.float32)
    rng = np.random.RandomState(0)
    k_host = rng.randn(*kv.k.shape).astype(np.float32)
    v_host = rng.randn(*kv.v.shape).astype(np.float32)
    kv = KVCache(jnp.asarray(k_host), jnp.asarray(v_host))
    pages = [3, 1, 4, 2]  # deliberately unordered

    k_out, v_out = device_repage(kv, pages, src_ps, dst_ps, prompt_len,
                                 jnp.bfloat16)
    n_dst = -(-prompt_len // dst_ps)

    def host_ref(pool):
        L = pool.shape[0]
        toks = pool[:, pages].reshape(L, n_src * src_ps, *pool.shape[3:])
        toks = toks[:, :prompt_len]
        pad = n_dst * dst_ps - prompt_len
        toks = np.pad(toks, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return toks.reshape(L, n_dst, dst_ps, *pool.shape[3:])

    np.testing.assert_array_equal(
        np.asarray(k_out[:, :n_dst].astype(jnp.float32)),
        host_ref(k_host).astype(jnp.bfloat16).astype(np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(v_out[:, :n_dst].astype(jnp.float32)),
        host_ref(v_host).astype(jnp.bfloat16).astype(np.float32),
    )


async def test_colocated_device_lane_reshards_across_meshes():
    """The resharding transfer NIXL performs, device-side: a tp=2 MESHED
    prefill engine hands pages to (a) a single-device engine and (b) a
    tp=2 engine on a DISJOINT device set — different meshes, different
    page sizes, no host staging (stats lane == "device"), outputs equal
    a local run (VERDICT r2 item 7)."""
    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferSource
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.parallel import ParallelConfig
    from dynamo_tpu.runtime import Context

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    devices = jax.devices()

    def make(page_size, parallel=None, devs=None):
        return JaxEngine(
            cfg, params,
            EngineConfig(page_size=page_size, num_pages=64, max_num_seqs=2,
                         max_prefill_tokens=64, max_model_len=128,
                         enable_prefix_caching=False),
            kv_dtype=jnp.float32, parallel=parallel, devices=devs,
        )

    prompt = list(range(2, 39))
    req = {"token_ids": prompt,
           "sampling_options": {"temperature": 0.0},
           "stop_conditions": {"max_tokens": 6, "ignore_eos": True}}

    ref = make(16)
    want = []
    async for d in ref.generate(dict(req)):
        want.extend(d["token_ids"])
    await ref.shutdown()

    src = make(8, parallel=ParallelConfig(tp=2), devs=devices[0:2])
    source = await KvTransferSource(src).start()
    try:
        pre_req = {**req, "stop_conditions": {"max_tokens": 1,
                                              "ignore_eos": True}}
        descs = []
        for _ in range(2):
            r = await src.prefill_remote(dict(pre_req), Context(),
                                         transfer_source=source)
            assert "kv_descriptor" in r, r
            descs.append((r["token_ids"][0], r["kv_descriptor"]))

        for dst, (tok0, desc) in zip(
            (make(16),  # tp=2 → single-device
             make(16, parallel=ParallelConfig(tp=2),
                  devs=devices[2:4])),  # tp=2 → tp=2, disjoint devices
            descs,
        ):
            pages, stats = await KvTransferClient(dst).fetch(desc)
            assert stats.lane == "device", stats
            toks = []
            async for d in dst.generate_imported(dict(req), tok0, pages):
                assert d.get("finish_reason") != "error", d
                toks.extend(d["token_ids"])
            await dst.shutdown()
            assert toks == want, (toks, want)
    finally:
        await source.stop()
        await src.shutdown()


async def test_colocated_fetch_uses_device_lane(monkeypatch):
    """An in-process source/client pair must take the device lane (stats
    lane == "device") and produce pages whose contents equal the host
    lane's, page-size mismatch included."""
    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferSource
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def make(page_size):
        return JaxEngine(
            cfg, params,
            EngineConfig(page_size=page_size, num_pages=64, max_num_seqs=2,
                         max_prefill_tokens=64, max_model_len=128,
                         # three separate prefills must be bit-identical;
                         # cache hits would leave each run a different
                         # recomputed tail page
                         enable_prefix_caching=False),
            kv_dtype=jnp.float32,
        )

    from dynamo_tpu.runtime import Context

    # the DMA lane is opt-in (jaxlib's cross-process same-host pull
    # CHECK-crashes the source; in-process pulls — this test — work)
    monkeypatch.setenv("DYN_DMA_LANE", "1")

    src_engine = make(8)
    dst_dev = make(16)
    dst_host = make(16)
    source = await KvTransferSource(src_engine).start()
    try:
        # two remote prefills of the same prompt (prefix cache shares the
        # pages; each holds its own reference) — one descriptor per lane
        prompt = list(range(2, 39))  # 37 tokens
        req = {"token_ids": prompt,
               "sampling_options": {"temperature": 0.0},
               "stop_conditions": {"max_tokens": 1, "ignore_eos": True}}
        descs = []
        for _ in range(3):
            r = await src_engine.prefill_remote(
                dict(req), Context(), transfer_source=source)
            assert "kv_descriptor" in r, r
            descs.append(r["kv_descriptor"])
        assert descs[0]["proc"] == process_token()

        dev_pages, dev_stats = await KvTransferClient(dst_dev).fetch(descs[0])
        assert dev_stats.lane == "device"
        assert dev_stats.bytes > 0

        # host lane over the second hold
        host_pages, host_stats = await KvTransferClient(
            dst_host, allow_device_lane=False
        ).fetch(descs[1])
        assert host_stats.lane == "host"

        # cross-process device pull (PJRT transfer server; exercised
        # in-process — the socket path is identical) on the third hold
        dst_dma = make(16)
        assert descs[2]["dma_addr"], "dma lane not armed on CPU backend"
        dma_pages, dma_stats = await KvTransferClient(
            dst_dma, lanes=("dma", "host")
        ).fetch(descs[2])
        assert dma_stats.lane == "dma"

        # identical destination page contents across all three lanes
        kd, vd = await dst_dev.export_pages(dev_pages)
        kh, vh = await dst_host.export_pages(host_pages)
        km, vm = await dst_dma.export_pages(dma_pages)
        np.testing.assert_array_equal(kd, kh)
        np.testing.assert_array_equal(vd, vh)
        np.testing.assert_array_equal(km, kh)
        np.testing.assert_array_equal(vm, vh)
        await dst_dma.shutdown()
    finally:
        await source.stop()
        for e in (src_engine, dst_dev, dst_host):
            await e.shutdown()


async def test_disagg_handler_counts_device_lane(model_setup=None):
    """Full disagg flow in one process: the decode handler's fetch rides
    the device lane and the metric surfaces it."""
    from dynamo_tpu.disagg import DisaggDecodeHandler, DisaggRouter, serve_prefill_worker
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm import ModelDeploymentCard
    from dynamo_tpu.runtime import Context, ControlPlaneServer, DistributedRuntime
    from dynamo_tpu.testing import tiny_tokenizer

    tok = tiny_tokenizer()
    cfg = tiny_config(vocab_size=tok.vocab_size)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)

    def make(page_size):
        return JaxEngine(
            cfg, params,
            EngineConfig(page_size=page_size, num_pages=128, max_num_seqs=4,
                         max_prefill_tokens=128, max_model_len=256),
            kv_dtype=jnp.float32, eos_token_ids=[],
        )

    control = await ControlPlaneServer().start()
    rt_p = await DistributedRuntime.connect(control.address)
    rt_d = await DistributedRuntime.connect(control.address)
    prefill_engine = make(8)
    decode_engine = make(16)
    mdc = ModelDeploymentCard(name="m", tokenizer_json=tok.to_json_str())
    await serve_prefill_worker(rt_p, prefill_engine, mdc)
    handler = DisaggDecodeHandler(
        decode_engine, rt_d,
        router=DisaggRouter(max_local_prefill_length=8),
    )
    try:
        req = {"token_ids": list(range(3, 70)),
               "sampling_options": {"temperature": 0.0},
               "stop_conditions": {"max_tokens": 4, "ignore_eos": True}}
        toks = []
        async for out in handler.generate(req, Context()):
            assert out.get("finish_reason") != "error", out
            toks += out["token_ids"]
        assert len(toks) == 4
        assert handler.kv_transfer_count == 1
        assert handler.kv_transfer_device_count == 1  # same process
    finally:
        await decode_engine.shutdown()
        await prefill_engine.shutdown()
        await rt_d.shutdown(graceful=False)
        await rt_p.shutdown(graceful=False)
        await control.stop()
