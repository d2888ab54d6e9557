"""Block-ID KV transfer service — the TPU-native NIXL equivalent.

Matches the reference's data-plane design
(/root/reference/docs/architecture/disagg_serving.md:95-108,
lib/llm/src/block_manager/storage/nixl.rs): KV *layout* metadata is
registered once per worker in the control plane; per-request messages carry
only a transfer handle + page count; the data itself moves over a dedicated
data-plane socket in page-sized chunks with streaming overlap (the source
exports chunk k+1 from HBM while chunk k is on the wire, the destination
imports chunk k into its pool while reading chunk k+1); and a *layout
transpose* re-pages the token stream when prefill and decode engines use
different page sizes (the analog of the reference's TP-mismatch
layout-transpose kernel, disagg_serving.md:100).

On TPU hardware within a slice this host-staged path could be replaced by
ICI device-to-device DMA (`jax.experimental.transfer`); the protocol —
handles + page ids, never bulk blobs on the request path — is what carries
over either way.  Host staging also makes prefill-TP != decode-TP free:
`jax.device_get` of a sharded KV gathers full kv-heads, so the transposed
import reshards under the destination's own mesh.
"""

from __future__ import annotations

import asyncio
import logging
import time
import uuid
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..runtime.transport.wire import (
    Frame,
    K_CTRL,
    K_DATA,
    K_END,
    K_ERR,
    K_REQ,
    pack,
    read_frame,
    unpack,
    write_frame,
)

logger = logging.getLogger(__name__)

LAYOUT_PREFIX = "/kv_layouts"

# target bytes per streamed chunk (whole source pages)
_CHUNK_BYTES = 2 << 20
# unclaimed transfers are released after this many seconds
_DEFAULT_TTL = 120.0


@dataclass
class KvLayout:
    """KV pool geometry, registered once per worker (reference: NIXL
    layout registration, block_manager/layout/nixl.rs)."""

    layers: int
    page_size: int
    n_kv_heads: int
    head_dim: int
    dtype: str  # numpy dtype name

    @classmethod
    def of_engine(cls, engine) -> "KvLayout":
        from ..models.llama import require_plain_cache

        mc = engine.model_cfg
        # two planes of [heads, width] a page: what `bytes_per_page` and
        # the wire format below assume
        require_plain_cache(mc, "disagg KV transfer")
        spec = mc.cache_spec
        return cls(
            layers=mc.num_hidden_layers,
            page_size=engine.cfg.page_size,
            n_kv_heads=spec.heads,
            head_dim=spec.width,
            dtype=np.dtype(engine._kv_dtype).name,
        )

    @property
    def bytes_per_page(self) -> int:
        return (
            2 * self.layers * self.page_size * self.n_kv_heads * self.head_dim
            * np.dtype(self.dtype).itemsize
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "layers": self.layers,
            "page_size": self.page_size,
            "n_kv_heads": self.n_kv_heads,
            "head_dim": self.head_dim,
            "dtype": self.dtype,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KvLayout":
        return cls(**d)

    def compatible_heads(self, other: "KvLayout") -> bool:
        return (
            self.layers == other.layers
            and self.n_kv_heads == other.n_kv_heads
            and self.head_dim == other.head_dim
        )


@dataclass
class _Held:
    pages: List[int]
    prompt_len: int
    deadline: float
    dma_addr: Optional[str] = None  # transfer-server address when armed


class KvTransferSource:
    """Prefill-side data-plane server: holds exported-to-be pages under a
    transfer handle, streams them by block id on request, frees on release
    or TTL."""

    def __init__(self, engine, host: str = "127.0.0.1", ttl: float = _DEFAULT_TTL):
        self.engine = engine
        self.layout = KvLayout.of_engine(engine)
        self.host = host
        self.ttl = ttl
        self.port: int = 0
        self._held: Dict[str, _Held] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._reaper: Optional[asyncio.Task] = None

    @property
    def address(self) -> List[Any]:
        return [self.host, self.port]

    async def start(self) -> "KvTransferSource":
        self._server = await asyncio.start_server(self._on_conn, self.host, 0)
        self.port = self._server.sockets[0].getsockname()[1]
        self._reaper = asyncio.create_task(self._reap_loop())
        return self

    async def stop(self) -> None:
        if self._reaper:
            self._reaper.cancel()
            await asyncio.gather(self._reaper, return_exceptions=True)
        if self._server:
            self._server.close()
            await self._server.wait_closed()
        for tid in list(self._held):
            await self._release(tid)

    async def register_layout(self, runtime, namespace: str, component: str) -> None:
        """Publish the pool layout + data-plane address once, lease-scoped
        (the reference registers NIXL metadata in etcd)."""
        key = f"{LAYOUT_PREFIX}/{namespace}/{component}/{runtime.primary_lease}"
        value = pack({"layout": self.layout.to_dict(), "addr": self.address})
        # lint: allow(leaked-acquire): lease-scoped registration — lease revoke/expiry deletes the key
        await runtime.put_leased(key, value)

    # -- handle lifecycle --------------------------------------------------- #

    async def register(self, pages: List[int], prompt_len: int) -> str:
        """Hold the pages under a fresh transfer handle.  When the PJRT
        transfer API is available (device DMA — ICI/DCN on pods), the
        page blocks are gathered device-side and armed for remote pull;
        the gather is a device op, hence async."""
        from .device_transfer import arm_dma, register_local

        tid = uuid.uuid4().hex
        held = _Held(
            pages=list(pages), prompt_len=prompt_len,
            deadline=time.monotonic() + self.ttl,
        )
        self._held[tid] = held
        register_local(tid, self)
        if self.engine.layout.mesh is None:
            from .device_transfer import _pow2, dma_server

            if dma_server(self.host) is not None:
                import jax.numpy as jnp

                engine = self.engine
                n = len(pages)
                padded = np.zeros((_pow2(n),), np.int32)
                padded[:n] = pages

                def gather():
                    k, v = engine.layout.export_fn(
                        engine.kv, jnp.asarray(padded)
                    )
                    return k[:, :n], v[:, :n]

                try:
                    k_blocks, v_blocks = await engine._device_op(gather)  # noqa: SLF001
                    held.dma_addr = arm_dma(tid, [k_blocks, v_blocks])
                except Exception:  # noqa: BLE001 — host lane still works
                    logger.exception("dma arming failed; host lane only")
        return tid

    def descriptor(self, tid: str) -> Dict[str, Any]:
        """What rides the request path: a handle, page count, and where the
        data plane lives — never the data."""
        from .device_transfer import process_token

        held = self._held[tid]
        return {
            "transfer_id": tid,
            "addr": self.address,
            # colocated clients (same process) skip the socket and move
            # the pages device-to-device (device_transfer.py)
            "proc": process_token(),
            # armed PJRT transfer-server address (cross-process device
            # pull) — None when the platform lacks the API
            "dma_addr": held.dma_addr,
            "num_pages": len(held.pages),
            "prompt_len": held.prompt_len,
            "layout": self.layout.to_dict(),  # also in the registry; carried
            # inline so a fetch can proceed before the watcher catches up
        }

    async def _release(self, tid: str, dma_claimed: bool = False) -> None:
        from .device_transfer import drain_dma_arm, unregister_local

        unregister_local(tid)
        held = self._held.pop(tid, None)
        if held is None:
            return
        if held.dma_addr and not dma_claimed:
            # nothing can cancel an armed await_pull: self-pull the arrays
            # so the transfer server drops its references (otherwise every
            # unclaimed arm — TTL expiry, colocated/host-lane consumption —
            # leaks a full prompt-KV device copy)
            await asyncio.get_running_loop().run_in_executor(
                None, drain_dma_arm, tid, self.layout, len(held.pages),
            )
        if not held.pages:
            return
        pages = held.pages

        def op():
            self.engine.pool.free(pages)

        try:
            await self.engine._device_op(op)
        except Exception:  # noqa: BLE001
            logger.exception("failed to free transfer %s pages", tid)

    async def _reap_loop(self) -> None:
        while True:
            await asyncio.sleep(self.ttl / 4)
            now = time.monotonic()
            for tid, held in list(self._held.items()):
                if held.deadline < now:
                    logger.warning("kv transfer %s expired unclaimed", tid)
                    await self._release(tid)

    # -- wire protocol ------------------------------------------------------ #

    async def _on_conn(self, reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                frame = await read_frame(reader)
                if frame.kind == K_REQ and frame.header.get("op") == "fetch":
                    await self._serve_fetch(frame, writer)
                elif frame.kind == K_CTRL and frame.header.get("op") == "release":
                    await self._release(
                        frame.header.get("transfer_id", ""),
                        dma_claimed=bool(frame.header.get("dma_claimed")),
                    )
                    write_frame(writer, Frame(K_END, frame.stream_id, {}, b""))
                    await writer.drain()
                elif frame.kind == K_CTRL and frame.header.get("op") == "layout":
                    write_frame(writer, Frame(
                        K_DATA, frame.stream_id, {},
                        pack(self.layout.to_dict()),
                    ))
                    await writer.drain()
                else:
                    write_frame(writer, Frame(
                        K_ERR, frame.stream_id,
                        {}, pack({"message": "bad request"}),
                    ))
                    await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    async def _serve_fetch(self, frame: Frame, writer: asyncio.StreamWriter) -> None:
        tid = frame.header.get("transfer_id", "")
        held = self._held.get(tid)
        if held is None:
            write_frame(writer, Frame(
                K_ERR, frame.stream_id, {},
                pack({"message": f"unknown transfer {tid}"}),
            ))
            await writer.drain()
            return
        from ..runtime.tracing import trace_from_headers

        trace = trace_from_headers(frame.header)
        t0_wall = time.time_ns()
        held.deadline = time.monotonic() + self.ttl  # claimed; re-arm
        chunk_pages = max(1, _CHUNK_BYTES // max(self.layout.bytes_per_page, 1))
        pages = held.pages
        # Export in LARGE strides (16MB), not per 2MB wire frame: every
        # export is a device op with a fixed device→host sync cost, so
        # per-frame exports multiply that cost by the frame count.  The
        # stride stays bounded so a long-sequence transfer neither
        # allocates a whole-sequence pow2-padded gather buffer in HBM
        # nor compiles a fresh export width class per prompt length; the
        # wire still streams 2MB frames for incremental import.
        export_pages_n = max(
            chunk_pages,
            (16 << 20) // max(self.layout.bytes_per_page, 1),
        )
        seq = 0
        for estart in range(0, len(pages), export_pages_n):
            ids = pages[estart:estart + export_pages_n]
            k_all, v_all = await self.engine.export_pages(ids)
            for start in range(0, len(ids), chunk_pages):
                n = min(chunk_pages, len(ids) - start)
                kb = np.ascontiguousarray(
                    k_all[:, start:start + n]).tobytes()
                vb = np.ascontiguousarray(
                    v_all[:, start:start + n]).tobytes()
                write_frame(writer, Frame(
                    K_DATA, frame.stream_id,
                    {"seq": seq, "n": n, "klen": len(kb)},
                    kb + vb,
                ))
                seq += 1
                await writer.drain()
        write_frame(writer, Frame(K_END, frame.stream_id, {}, b""))
        await writer.drain()
        if trace is not None:
            # the source side of the data-plane hop on the request's
            # trace — adopted from the fetch frame's headers
            from ..runtime.tracing import export_span

            export_span("transfer.serve_fetch", trace, t0_wall,
                        time.time_ns(), transfer_id=tid,
                        pages=len(pages), seq_frames=seq)


@dataclass
class TransferStats:
    bytes: int = 0
    ms: float = 0.0
    src_pages: int = 0
    dest_pages: int = 0
    lane: str = "host"  # "host" (TCP staging) | "device" (colocated DMA)


class KvTransferClient:
    """Decode-side: fetch a registered transfer into the local engine's
    pool, re-paging between source and destination layouts on the fly.

    Three lanes, tried in order:
    - "colocated": source in the same process (single-process disagg
      graphs) — jitted device re-page, no host staging, no sockets;
    - "dma": the source armed a PJRT transfer-server pull (the NIXL
      analog; ICI/DCN on pods) — pages land in local device buffers;
    - "host": TCP page-chunk streaming with host staging (always works,
      any layout, any platform).
    `lanes` restricts the order (tests pin single lanes);
    `allow_device_lane=False` is shorthand for host-only."""

    def __init__(self, engine, allow_device_lane: bool = True,
                 lanes: Optional[Tuple[str, ...]] = None):
        self.engine = engine
        self.dest_layout = KvLayout.of_engine(engine)
        if lanes is None:
            lanes = (("colocated", "dma", "host") if allow_device_lane
                     else ("host",))
        self.lanes = lanes

    async def fetch(self, descriptor: Dict[str, Any],
                    timeout: Optional[float] = 60.0,
                    ) -> Tuple[List[int], TransferStats]:
        """Returns (dest page ids holding the prompt KV, stats).  Raises on
        incompatibility or transport failure — callers fall back to local
        prefill.  Allocated pages are freed on failure.

        ``timeout`` bounds the whole transfer (a partitioned source must
        not wedge the caller); on expiry the in-flight lane is cancelled,
        which runs the same settle-free-release path as any other failure.
        ``None`` disables the deadline (profiling harnesses)."""
        if timeout is not None:
            return await asyncio.wait_for(self._fetch(descriptor), timeout)
        return await self._fetch(descriptor)

    async def _fetch(self, descriptor: Dict[str, Any]) -> Tuple[List[int], TransferStats]:
        t0 = time.perf_counter()
        src = KvLayout.from_dict(descriptor["layout"])
        dst = self.dest_layout
        if not src.compatible_heads(dst):
            raise ValueError(
                f"incompatible KV layouts: src {src} vs dst {dst}"
            )
        if "colocated" in self.lanes:
            from .device_transfer import fetch_colocated, local_source

            source = local_source(descriptor)
            if source is not None and (
                getattr(self.engine, "_multihost", False)
                or getattr(source.engine, "_multihost", False)
            ):
                # a multihost engine's device ops must ride its lockstep
                # plan channel; the colocated lane's raw jits would run
                # on one rank of a multi-process array — host lane instead
                source = None
            if source is not None:
                dest_pages, n_dst = await fetch_colocated(
                    self, source, descriptor
                )
                return dest_pages, TransferStats(
                    # logical bytes moved (in HBM; nothing crossed the host)
                    bytes=n_dst * dst.bytes_per_page,
                    ms=(time.perf_counter() - t0) * 1000.0,
                    src_pages=int(descriptor["num_pages"]),
                    dest_pages=n_dst,
                    lane="device",
                )
        if "dma" in self.lanes and descriptor.get("dma_addr"):
            pages_stats = await self._fetch_dma(descriptor, src, dst, t0)
            if pages_stats is not None:
                return pages_stats
        prompt_len = int(descriptor["prompt_len"])
        n_dest = -(-prompt_len // dst.page_size)
        dest_pages = await self.engine.alloc_pages(n_dest)
        stats = TransferStats(dest_pages=n_dest)
        pending_box: List[Optional[asyncio.Task]] = [None]
        try:
            await self._fetch_into(descriptor, src, dst, prompt_len,
                                   dest_pages, stats, pending_box)
        except BaseException:
            # settle any in-flight import BEFORE freeing: its device op
            # must not land after the pages are reallocated to someone else
            task = pending_box[0]
            if task is not None:
                try:
                    await task
                except Exception:  # lint: allow(swallowed-exception): original error wins; task settled either way
                    pass
            await self.engine.free_pages(dest_pages)
            await self._release_remote(descriptor)
            raise
        stats.ms = (time.perf_counter() - t0) * 1000.0
        return dest_pages, stats

    async def _fetch_dma(self, descriptor, src: KvLayout, dst: KvLayout,
                         t0: float):
        """Cross-process device pull (PJRT transfer server): pull the
        armed page blocks into local device buffers, re-page on device,
        import.  Returns None to fall through to the host lane."""
        import asyncio as _asyncio

        from .device_transfer import (
            device_repage_blocks,
            dma_pull,
            probe_jax_transfer,
        )

        if not probe_jax_transfer() or self.engine.layout.mesh is not None:
            return None
        import jax
        import jax.numpy as jnp

        prompt_len = int(descriptor["prompt_len"])
        n = int(descriptor["num_pages"])
        shape = (src.layers, n, src.page_size, src.n_kv_heads, src.head_dim)
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        structs = [
            jax.ShapeDtypeStruct(shape, jnp.dtype(src.dtype),
                                 sharding=sharding)
        ] * 2
        try:
            k_blocks, v_blocks = await _asyncio.get_running_loop().run_in_executor(
                None, dma_pull, descriptor["dma_addr"],
                descriptor["transfer_id"], structs,
            )
        except Exception as e:  # noqa: BLE001 — host lane still works
            logger.warning("dma pull failed (%s); host lane", e)
            return None
        n_dst = -(-prompt_len // dst.page_size)
        dest_pages = await self.engine.alloc_pages(n_dst)
        try:
            engine = self.engine

            def op():
                return device_repage_blocks(
                    k_blocks, v_blocks, dst.page_size, prompt_len,
                    engine._kv_dtype,  # noqa: SLF001
                )

            kc, vc = await engine._device_op(op)  # noqa: SLF001
            await engine.import_page_chunk(
                dest_pages, kc[:, :n_dst], vc[:, :n_dst]
            )
        except BaseException:
            await self.engine.free_pages(dest_pages)
            raise
        await self._release_remote(descriptor, dma_claimed=True)
        return dest_pages, TransferStats(
            bytes=2 * int(np.prod(shape)) * np.dtype(src.dtype).itemsize,
            ms=(time.perf_counter() - t0) * 1000.0,
            src_pages=n,
            dest_pages=n_dst,
            lane="dma",
        )

    async def _release_remote(self, descriptor: Dict[str, Any],
                              dma_claimed: bool = False) -> None:
        """Best-effort: tell the source to drop its hold now rather than
        waiting out the TTL (failed fetches would otherwise park pages on
        the prefill worker for minutes).  `dma_claimed` tells the source
        its armed DMA pull was consumed (no self-drain needed)."""
        try:
            host, port = descriptor["addr"]
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, port), timeout=2.0
            )
            write_frame(writer, Frame(
                K_CTRL, 1,
                {"op": "release", "transfer_id": descriptor["transfer_id"],
                 "dma_claimed": dma_claimed},
                b"",
            ))
            await asyncio.wait_for(writer.drain(), timeout=2.0)
            writer.close()
        except Exception:  # lint: allow(swallowed-exception): remote TTL is the backstop for a lost release
            pass

    async def _fetch_into(self, descriptor, src: KvLayout, dst: KvLayout,
                          prompt_len: int, dest_pages: List[int],
                          stats: TransferStats,
                          pending_box: List[Optional[asyncio.Task]]) -> None:
        host, port = descriptor["addr"]
        reader, writer = await asyncio.open_connection(host, port)
        sdtype = np.dtype(src.dtype)
        ddtype = np.dtype(dst.dtype)
        L, kvh, hd = src.layers, src.n_kv_heads, src.head_dim
        try:
            from ..runtime.tracing import trace_headers

            # the data plane is a trace hop too: the source side adopts
            # these headers so its serve-side span joins the request's
            # trace (every egress point propagates, not just service.call)
            write_frame(writer, Frame(
                K_REQ, 1,
                {"op": "fetch", "transfer_id": descriptor["transfer_id"],
                 **trace_headers()},
                b"",
            ))
            await writer.drain()

            stage = _TokenStager(L, kvh, hd, ddtype)
            next_dest = 0  # index into dest_pages
            # import stride: each flush is a device op with a fixed
            # host→device cost, and per-wire-frame flushes serialize one
            # such cost per 2MB.  Accumulate to a 16MB stride: small
            # transfers import ONCE, large ones still stream with
            # bounded host memory.
            flush_tokens = max(
                dst.page_size,
                (16 << 20) // max(2 * L * kvh * hd * ddtype.itemsize, 1),
            )

            async def flush(final: bool) -> None:
                """Cut whole destination pages off the stage and import
                them; pipeline depth 1 so the import of chunk k overlaps
                reading chunk k+1 off the wire."""
                nonlocal next_dest
                if not final and stage.tokens < flush_tokens:
                    return
                n_whole = stage.tokens // dst.page_size
                if final and stage.tokens % dst.page_size:
                    stage.pad_to(n_whole * dst.page_size + dst.page_size)
                    n_whole += 1
                if n_whole == 0:
                    return
                k_chunk, v_chunk = stage.pop(n_whole * dst.page_size)
                k_chunk = k_chunk.reshape(L, n_whole, dst.page_size, kvh, hd)
                v_chunk = v_chunk.reshape(L, n_whole, dst.page_size, kvh, hd)
                ids = dest_pages[next_dest:next_dest + n_whole]
                if len(ids) != n_whole:
                    raise RuntimeError("transfer longer than prompt_len")
                next_dest += n_whole
                if pending_box[0] is not None:
                    await pending_box[0]
                pending_box[0] = asyncio.ensure_future(
                    self.engine.import_page_chunk(ids, k_chunk, v_chunk)
                )

            while True:
                frame = await read_frame(reader)
                if frame.kind == K_ERR:
                    raise RuntimeError(
                        unpack(frame.payload).get("message", "fetch failed")
                    )
                if frame.kind == K_END:
                    break
                n = frame.header["n"]
                klen = frame.header["klen"]
                stats.bytes += len(frame.payload)
                stats.src_pages += n
                kb = np.frombuffer(frame.payload[:klen], sdtype)
                vb = np.frombuffer(frame.payload[klen:], sdtype)
                stage.push(
                    kb.reshape(L, n * src.page_size, kvh, hd).astype(ddtype, copy=False),
                    vb.reshape(L, n * src.page_size, kvh, hd).astype(ddtype, copy=False),
                )
                # keep only prompt_len tokens (source pages are page-padded)
                stage.truncate_total(prompt_len)
                await flush(final=False)

            stage.truncate_total(prompt_len)
            await flush(final=True)
            if pending_box[0] is not None:
                await pending_box[0]
                pending_box[0] = None
            if next_dest != len(dest_pages):
                raise RuntimeError(
                    f"transfer filled {next_dest}/{len(dest_pages)} pages"
                )

            # release the source's hold (best effort — TTL covers failure)
            write_frame(writer, Frame(
                K_CTRL, 2,
                {"op": "release", "transfer_id": descriptor["transfer_id"]},
                b"",
            ))
            await writer.drain()
        finally:
            writer.close()


class _TokenStager:
    """Token-major staging between mismatched page sizes: frames push
    [L, t, kv, hd] slabs; pop() cuts an exact token count off the front."""

    def __init__(self, L: int, kvh: int, hd: int, dtype):
        self._shape = (L, kvh, hd)
        self._dtype = dtype
        self._k: List[np.ndarray] = []
        self._v: List[np.ndarray] = []
        self.tokens = 0  # tokens currently staged
        self.popped = 0

    def push(self, k: np.ndarray, v: np.ndarray) -> None:
        self._k.append(k)
        self._v.append(v)
        self.tokens += k.shape[1]

    def truncate_total(self, limit: int) -> None:
        """Drop staged tokens beyond stream position `limit`."""
        excess = (self.popped + self.tokens) - limit
        while excess > 0 and self._k:
            tail = self._k[-1].shape[1]
            cut = min(tail, excess)
            if cut == tail:
                self._k.pop(); self._v.pop()
            else:
                self._k[-1] = self._k[-1][:, :tail - cut]
                self._v[-1] = self._v[-1][:, :tail - cut]
            self.tokens -= cut
            excess -= cut

    def pad_to(self, n: int) -> None:
        L, kvh, hd = self._shape
        if n > self.tokens:
            z = np.zeros((L, n - self.tokens, kvh, hd), self._dtype)
            self._k.append(z)
            self._v.append(z)
            self.tokens = n

    def pop(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        assert n <= self.tokens
        out_k, out_v, got = [], [], 0
        while got < n:
            k, v = self._k[0], self._v[0]
            take = min(k.shape[1], n - got)
            out_k.append(k[:, :take])
            out_v.append(v[:, :take])
            if take == k.shape[1]:
                self._k.pop(0); self._v.pop(0)
            else:
                self._k[0] = k[:, take:]
                self._v[0] = v[:, take:]
            got += take
        self.tokens -= n
        self.popped += n
        return np.concatenate(out_k, axis=1), np.concatenate(out_v, axis=1)


async def lookup_layouts(runtime, namespace: str, component: str
                         ) -> Dict[str, Dict[str, Any]]:
    """Read registered layouts for a component from the control plane."""
    rows = await runtime.control.get_prefix(
        f"{LAYOUT_PREFIX}/{namespace}/{component}/"
    )
    out = {}
    for key, value in rows:
        out[key.rsplit("/", 1)[-1]] = unpack(value)
    return out
