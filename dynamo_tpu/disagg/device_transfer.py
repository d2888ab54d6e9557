"""Device-path KV transfer — the ICI/DMA lane of the data plane.

The block-ID transfer service (disagg/transfer.py) stages pages through
host memory over TCP — always correct, works across hosts and mismatched
layouts.  This module adds two faster lanes with the SAME handle/page
protocol (reference design: NIXL device-to-device transfer with metadata
registered once, /root/reference/docs/architecture/disagg_serving.md:95-108):

1. **Colocated lane** (implemented, tested): when the prefill and decode
   engines live in the same process — single-process disagg graphs from
   the `dynamo_tpu.run` launcher, and every in-process test — pages move
   device-to-device through a jitted gather→re-page→scatter with no host
   staging and no sockets.  Handles register in a process-local registry;
   the descriptor carries a process token so a client can tell colocated
   sources from remote ones.

2. **Cross-process device lane** (probed, gated): `jax.experimental.
   transfer` exposes PJRT's DMA transfer server (pull-based, address
   registered like NIXL metadata).  A backend may lack
   `PJRT_Client_CreateBuffersForAsyncHostToDevice`, so `probe_jax_transfer`
   caches a real round-trip attempt and the host lane stays the fallback
   where the platform does not support it.
"""

from __future__ import annotations

import logging
import os
import uuid
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

# process-local registry of live KvTransferSource objects: transfer_id →
# source.  A descriptor whose process token matches ours refers to a
# source whose device buffers we can touch directly.
_PROCESS_TOKEN = f"{os.getpid()}-{uuid.uuid4().hex[:8]}"
_LOCAL_SOURCES: Dict[str, object] = {}


def process_token() -> str:
    return _PROCESS_TOKEN


def register_local(tid: str, source) -> None:
    _LOCAL_SOURCES[tid] = source


def unregister_local(tid: str) -> None:
    _LOCAL_SOURCES.pop(tid, None)


def local_source(descriptor: dict):
    """The colocated source for a descriptor, or None."""
    if descriptor.get("proc") != _PROCESS_TOKEN:
        return None
    return _LOCAL_SOURCES.get(descriptor.get("transfer_id", ""))


# -- colocated device copy ---------------------------------------------------- #


def _repage_jit():
    """Module-cached jitted re-pagers; XLA fuses the gather, mask, and
    cast — data never leaves HBM.  Static dims are pow2-bucketed by the
    caller so compile count stays logarithmic; `prompt_len` is dynamic
    (positions past it are zeroed, matching the host stager's padding).
    Returns (from_pool, from_blocks): the colocated lane gathers straight
    out of the source pool; the DMA lane re-pages blocks it pulled."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    def _blocks_to_pages(blocks, prompt_len, n_dst, dst_page_size, dst_dtype):
        target = n_dst * dst_page_size
        L, n, ps, kvh, hd = blocks.shape
        toks = blocks.reshape(L, n * ps, kvh, hd)
        if n * ps < target:
            toks = jnp.pad(
                toks, ((0, 0), (0, target - n * ps), (0, 0), (0, 0))
            )
        toks = toks[:, :target]
        keep = (jnp.arange(target) < prompt_len)[None, :, None, None]
        toks = jnp.where(keep, toks, 0)
        return toks.reshape(L, n_dst, dst_page_size, kvh, hd).astype(dst_dtype)

    @partial(jax.jit, static_argnums=(4, 5, 6))
    def from_pool(k_pool, v_pool, pages, prompt_len, n_dst, dst_page_size,
                  dst_dtype):
        return (
            _blocks_to_pages(k_pool[:, pages], prompt_len, n_dst,
                             dst_page_size, dst_dtype),
            _blocks_to_pages(v_pool[:, pages], prompt_len, n_dst,
                             dst_page_size, dst_dtype),
        )

    @partial(jax.jit, static_argnums=(3, 4, 5))
    def from_blocks(k_blocks, v_blocks, prompt_len, n_dst, dst_page_size,
                    dst_dtype):
        return (
            _blocks_to_pages(k_blocks, prompt_len, n_dst, dst_page_size,
                             dst_dtype),
            _blocks_to_pages(v_blocks, prompt_len, n_dst, dst_page_size,
                             dst_dtype),
        )

    return from_pool, from_blocks


_REPAGE = None


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _repagers():
    global _REPAGE
    if _REPAGE is None:
        _REPAGE = _repage_jit()
    return _REPAGE


def device_repage(src_kv, src_pages, src_page_size: int,
                  dst_page_size: int, prompt_len: int, dst_dtype):
    """Gather `src_pages` from the source pool and re-page to the
    destination layout entirely on device: [L, n_src, ps, kv, hd] →
    token-major (zero past prompt_len) → [L, n_dst_pow2, pd, kv, hd].
    Callers slice the leading ceil(prompt_len / pd) destination pages."""
    import jax.numpy as jnp

    from_pool, _ = _repagers()
    # pow2-pad the page list AND the destination page count so compile
    # count stays logarithmic; padding source pages point at trash page 0
    # whose tokens sit past prompt_len and are zero-masked anyway
    n = len(src_pages)
    width = _pow2(n)
    padded = np.zeros((width,), np.int32)
    padded[:n] = src_pages
    n_dst = _pow2(-(-prompt_len // dst_page_size))
    return from_pool(
        src_kv.k, src_kv.v, jnp.asarray(padded),
        jnp.int32(prompt_len), n_dst, dst_page_size, jnp.dtype(dst_dtype),
    )


def device_repage_blocks(k_blocks, v_blocks, dst_page_size: int,
                         prompt_len: int, dst_dtype):
    """Re-page already-gathered blocks (the DMA lane's pulled arrays)."""
    import jax.numpy as jnp

    _, from_blocks = _repagers()
    n_dst = _pow2(-(-prompt_len // dst_page_size))
    return from_blocks(
        k_blocks, v_blocks, jnp.int32(prompt_len), n_dst, dst_page_size,
        jnp.dtype(dst_dtype),
    )


async def fetch_colocated(client, source, descriptor) -> Tuple[list, object]:
    """Device-path fetch for a colocated source: returns
    (dest_page_ids, stats-like dict).  Runs both engines' device ops
    through their pumps so nothing races a step."""
    src_engine = source.engine
    dst_engine = client.engine
    held = source._held.get(descriptor["transfer_id"])  # noqa: SLF001
    if held is None:
        raise RuntimeError(f"unknown transfer {descriptor['transfer_id']}")
    prompt_len = held.prompt_len
    src_ps = source.layout.page_size
    dst_ps = client.dest_layout.page_size
    n_dst = -(-prompt_len // dst_ps)

    dest_pages = await dst_engine.alloc_pages(n_dst)
    try:
        def src_op():
            return device_repage(
                src_engine.kv, held.pages, src_ps, dst_ps, prompt_len,
                dst_engine._kv_dtype,  # noqa: SLF001
            )

        k_chunk, v_chunk = await src_engine._device_op(src_op)  # noqa: SLF001
        # repage pow2-buckets its page-count output; keep the real pages
        await dst_engine.import_page_chunk(
            dest_pages, k_chunk[:, :n_dst], v_chunk[:, :n_dst]
        )
    except BaseException:
        await dst_engine.free_pages(dest_pages)
        raise
    # release the source's hold now (same semantics as the wire release)
    await source._release(descriptor["transfer_id"])  # noqa: SLF001
    return dest_pages, n_dst


# -- cross-process device (DMA) lane ------------------------------------------ #
# PJRT's transfer server (jax.experimental.transfer) is the NIXL analog:
# the source arms a pull (uuid → device arrays), registers its address in
# the descriptor, and the destination pulls straight into its own device
# buffers — ICI/DCN on TPU pods, sockets on CPU.  A backend without the
# API fails the probe, which gates the lane; the host-staged TCP path
# remains the fallback.

_DMA_SERVER = None


def dma_enabled() -> bool:
    """The DMA lane is OPT-IN (DYN_DMA_LANE=1): jaxlib 0.9's transfer
    server fatally CHECK-crashes the SOURCE process when a same-host
    peer in another process pulls (aux::LocalBulkTransportFactory::
    RecvBulkTransport, streaming.cc:193) — a dead prefill worker is far
    worse than host-staged copies.  In-process pulls work (covered by
    tests); deployments on platforms where the cross-process path is
    proven enable the flag."""
    from ..runtime.config import env_bool

    return env_bool("DYN_DMA_LANE", False)


def dma_server(host: str = "127.0.0.1"):
    """Process-global transfer server (created on first use; None when
    the lane is disabled or the platform lacks the PJRT transfer API)."""
    global _DMA_SERVER
    if _DMA_SERVER is None and dma_enabled() and probe_jax_transfer():
        import jax
        from jax.experimental import transfer

        _DMA_SERVER = transfer.start_transfer_server(
            jax.devices()[0].client, f"{host}:0"
        )
    return _DMA_SERVER


def dma_uid(tid: str) -> int:
    return int(tid[:15], 16)


def arm_dma(tid: str, arrays) -> Optional[str]:
    """Schedule device arrays for remote pull under the transfer id;
    returns the server address (None → lane unavailable)."""
    srv = dma_server()
    if srv is None:
        return None
    srv.await_pull(dma_uid(tid), list(arrays))
    return srv.address()


# connections are cached per peer address: a TransferConnection must stay
# alive while its pulled arrays stream (dropping it mid-transfer poisons
# the destination buffers with a closed-socket error), and reuse skips a
# handshake per fetch
_CONNS: Dict[str, object] = {}


def _connect(addr: str):
    srv = dma_server()
    if srv is None:
        raise RuntimeError("jax transfer unavailable on this platform")
    conn = _CONNS.get(addr)
    if conn is None:
        conn = srv.connect(addr)
        _CONNS[addr] = conn
    return conn


def dma_pull(addr: str, tid: str, structs):
    """Pull armed arrays from a remote transfer server into local device
    buffers; blocks until they materialize so transport failures surface
    HERE (where callers fall back to the host lane) instead of poisoning
    a later engine step."""
    import jax

    got = _connect(addr).pull(dma_uid(tid), list(structs))
    jax.block_until_ready(got)
    return got


def drain_dma_arm(tid: str, layout, num_pages: int) -> None:
    """Consume an UNCLAIMED arm by pulling it locally and dropping the
    result: the transfer API has no cancel, and an armed await_pull pins
    its device arrays in the server until someone pulls them."""
    srv = dma_server()
    if srv is None:
        return
    try:
        import jax
        import jax.numpy as jnp

        shape = (layout.layers, num_pages, layout.page_size,
                 layout.n_kv_heads, layout.head_dim)
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        structs = [jax.ShapeDtypeStruct(shape, jnp.dtype(layout.dtype),
                                        sharding=sharding)] * 2
        got = _connect(srv.address()).pull(dma_uid(tid), structs)
        jax.block_until_ready(got)
        for a in got:
            a.delete()
    except Exception:  # noqa: BLE001 — draining is best-effort cleanup
        logger.exception("dma drain for %s failed", tid)


_JAX_TRANSFER: Optional[bool] = None


def probe_jax_transfer() -> bool:
    """True when `jax.experimental.transfer` can actually move an array
    on this platform (cached).  A real pull round-trip is attempted —
    merely importing the module proves nothing (a backend can import
    fine and still raise UNIMPLEMENTED for
    PJRT_Client_CreateBuffersForAsyncHostToDevice)."""
    global _JAX_TRANSFER
    if _JAX_TRANSFER is not None:
        return _JAX_TRANSFER
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import transfer

        client = jax.devices()[0].client
        srv = transfer.start_transfer_server(client, "127.0.0.1:0")
        x = jnp.arange(4, dtype=jnp.float32)
        srv.await_pull(1, [x])
        conn = srv.connect(srv.address())
        got = conn.pull(1, [jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=x.sharding)])
        _JAX_TRANSFER = bool(np.array_equal(np.asarray(got[0]), np.asarray(x)))
    except Exception as e:  # noqa: BLE001 — any failure means "unavailable"
        logger.info("jax.experimental.transfer unavailable: %s", e)
        _JAX_TRANSFER = False
    return _JAX_TRANSFER
