"""Device KV page allocator with prefix caching and KV events.

This is the engine-resident sibling of the reference's KVBM device pool
(/root/reference/lib/llm/src/block_manager/pool.rs `ManagedBlockPool`:
active/inactive registries, reuse, reset) fused with vLLM-style prefix
caching, because our engine owns its own pages:

- pages move free → active (owned by a sequence) → cached (full, hashed,
  shareable, refcounted) → evicted (LRU) → free
- full pages are *committed* under their chained block hash; later
  sequences with the same prefix reuse them without recompute
- commits/evictions emit KV events (stored/removed) consumed by the
  KV-aware router (reference events.rs → publisher.rs)

Page 0 is reserved (trash page for padding writes) and never allocated.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence


@dataclass
class KvEvent:
    """stored/removed event, the unit the router's indexer consumes
    (reference kv_router/protocols.rs KvCacheEvent)."""

    kind: str  # "stored" | "removed" | "cleared"
    block_hashes: List[int]
    parent_hash: Optional[int] = None
    ts: float = field(default_factory=time.monotonic)


class NoPagesError(RuntimeError):
    pass


class PagePool:
    """Free-list page allocator + hash-addressed prefix cache."""

    ranks = 1  # partition count (ShardedPagePool overrides)

    def __init__(self, num_pages: int, page_size: int,
                 event_sink: Optional[Callable[[KvEvent], None]] = None):
        self.page_size = page_size
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))  # pop() → 1,2,...
        # block_hash → page id (full committed pages)
        self._cached: Dict[int, int] = {}
        self._page_hash: Dict[int, int] = {}  # page id → block hash
        self._refs: Dict[int, int] = {}  # page id → refcount (active users)
        # unreferenced cached pages in LRU order (evictable)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._event_sink = event_sink
        self.evictions_total = 0  # cached pages evicted to make room
        # optional StepEventRecorder (runtime.events): alloc/free land on
        # the engine step timeline; None-checked so the hot path stays a
        # single attribute load when unwired
        self.events = None

    # -- stats --------------------------------------------------------------- #

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def evictable_pages(self) -> int:
        return len(self._lru)

    @property
    def available_pages(self) -> int:
        return self.free_pages + self.evictable_pages

    def usage(self) -> float:
        """Fraction of the pool that is NOT reclaimable (pages held by
        running sequences).  Cached-but-evictable pages count as free —
        they are capacity, not load; counting them as used would make
        the router/busy-threshold systematically penalize cache-rich
        workers (vLLM v1 semantics: cached blocks sit in the free
        queue)."""
        usable = self.num_pages - 1
        return 1.0 - (self.available_pages / usable) if usable else 1.0

    def usage_max_rank(self) -> float:
        """Usage of the FULLEST partition — the admission-binding signal
        (a single pool has one partition, so this equals `usage`)."""
        return self.usage()

    # -- allocation ---------------------------------------------------------- #

    def allocate(self, n: int) -> List[int]:
        """Take n pages, evicting cached pages LRU-first if needed."""
        if self.available_pages < n:
            raise NoPagesError(f"need {n} pages, have {self.available_pages}")
        out: List[int] = []
        while len(out) < n:
            if self._free:
                out.append(self._free.pop())
            else:
                out.append(self._evict_one())
        for p in out:
            self._refs[p] = self._refs.get(p, 0) + 1
        if self.events is not None:
            self.events.record("pool_alloc", n=n,
                               available=self.available_pages)
        return out

    def _evict_one(self) -> int:
        page, _ = self._lru.popitem(last=False)
        self.evictions_total += 1
        h = self._page_hash.pop(page)
        del self._cached[h]
        self._emit(KvEvent("removed", [h]))
        return page

    def free(self, pages: Sequence[int]) -> None:
        """Release a sequence's hold. Cached pages become evictable; others
        return to the free list."""
        if self.events is not None and pages:
            self.events.record("pool_free", n=len(pages))
        for p in pages:
            refs = self._refs.get(p, 0) - 1
            if refs > 0:
                self._refs[p] = refs
                continue
            self._refs.pop(p, None)
            if p in self._page_hash:
                self._lru[p] = None  # still cached, now evictable
            else:
                self._free.append(p)

    # -- prefix cache -------------------------------------------------------- #

    def lookup(self, block_hashes: Sequence[int]) -> List[int]:
        """Longest cached prefix: page ids for the leading run of hits.
        Takes a reference on each returned page."""
        out: List[int] = []
        for h in block_hashes:
            page = self._cached.get(h)
            if page is None:
                break
            if page in self._lru:
                del self._lru[page]
            self._refs[page] = self._refs.get(page, 0) + 1
            out.append(page)
        return out

    def cached_page(self, block_hash: int) -> Optional[int]:
        """Page currently committed under this hash, or None — no reference
        taken (KVBM offload resolves hashes to live pages through this)."""
        return self._cached.get(block_hash)

    def peek(self, block_hashes: Sequence[int]) -> int:
        """Length of the leading cached run WITHOUT taking references
        (disagg-router costing: `cached_prefix_len`)."""
        n = 0
        for h in block_hashes:
            if h not in self._cached:
                break
            n += 1
        return n

    def commit(self, page: int, block_hash: int, parent_hash: Optional[int]) -> int:
        """Register a now-full page under its chain hash.

        If an identical block is already cached (another sequence filled the
        same prefix concurrently), the existing page wins: the caller keeps
        using its own copy (it holds a ref) but the cache dedups to one.
        Returns the canonical page id for the hash.
        """
        existing = self._cached.get(block_hash)
        if existing is not None:
            return existing
        self._cached[block_hash] = page
        self._page_hash[page] = block_hash
        self._emit(KvEvent("stored", [block_hash], parent_hash))
        return page

    def clear_cache(self) -> int:
        """Drop every evictable cached page (the reference's
        `clear_kv_blocks` endpoint). Returns pages reclaimed."""
        n = 0
        while self._lru:
            self._free.append(self._evict_one())
            n += 1
        self._emit(KvEvent("cleared", []))
        return n

    # rank-aware surface (trivial on the single pool; the Scheduler always
    # goes through these so a ShardedPagePool drops in unchanged)

    def available_on(self, rank: int) -> int:
        return self.available_pages

    def allocate_on(self, rank: int, n: int) -> List[int]:
        return self.allocate(n)

    def lookup_on(self, rank: int, block_hashes: Sequence[int]) -> List[int]:
        return self.lookup(block_hashes)

    def best_rank(self, block_hashes: Sequence[int]):
        """(rank, cached-prefix-hits) of the best partition to admit a
        sequence with this hash chain."""
        return 0, self.peek(block_hashes)

    def _emit(self, ev: KvEvent) -> None:
        if self._event_sink:
            self._event_sink(ev)


class StatePool:
    """Slots of recurrent state beside the pages (`ModelConfig.state_spec`):
    what a SEQUENCE leaves the state-space layers, whatever its length.

    - a running sequence owns one slot: its steps read it and write it;
    - a chunk that starts at a page boundary hands its state out every
      `every_of(bucket)` tokens from its start (`models.hybrid.
      handout_every`: `snapshot_every`, or a page in a short row) and at its
      end; where that position is a page boundary the state is *committed*
      under the chained block hash of the position: inside a chunk it is
      written to a slot reserved for it, at a chunk's end the sequence's own
      slot is committed and the sequence goes on in a fresh one (a step
      reads slot a and writes slot b: no copy);
    - a snapshot is refcounted while a reader is admitted on it, evictable
      (LRU) otherwise, and evicted to make room like a cached page;
    - a prefix hit is as deep as the deepest snapshot at or under the cached
      pages (`Scheduler._apply_prefix_cache`).

    Slot 0 is reserved: read, it is the zeros before a sequence; written,
    it is trash (pad rows)."""

    def __init__(self, num_slots: int, snapshot_every: int,
                 inside: int = 0,
                 every_of: Optional[Callable[[int], int]] = None):
        self.num_slots = num_slots
        self.snapshot_every = snapshot_every
        # a step's bucket -> the tokens between the states it hands out
        self.every_of = every_of or (lambda tokens: snapshot_every)
        # most snapshots a step writes INSIDE a row's chunk
        # (`models.hybrid.SNAP_COLS`)
        self.inside = inside
        self._free: List[int] = list(range(num_slots - 1, 0, -1))
        self._snap: Dict[int, int] = {}  # block hash → slot
        self._slot_hash: Dict[int, int] = {}
        self._refs: Dict[int, int] = {}  # snapshot slot → readers
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.stored_total = 0
        self.hits_total = 0
        self.evictions_total = 0
        # tokens by which prefix hits were shortened: pages were cached
        # past the deepest snapshot
        self.hit_tokens_shortened_total = 0
        self.events = None  # StepEventRecorder, as `PagePool.events`

    @property
    def available(self) -> int:
        return len(self._free) + len(self._lru)

    @property
    def snapshots(self) -> int:
        return len(self._snap)

    @property
    def running(self) -> int:
        """Slots owned by running sequences: their own, and those reserved
        for the snapshots their next step writes."""
        return self.num_slots - 1 - len(self._free) - len(self._snap)

    def allocate(self) -> int:
        """A slot for a running sequence, evicting the least recently used
        unread snapshot if none is free; 0 if there is none."""
        if self._free:
            return self._free.pop()
        if not self._lru:
            return 0
        slot, _ = self._lru.popitem(last=False)
        del self._snap[self._slot_hash.pop(slot)]
        self.evictions_total += 1
        if self.events is not None:
            self.events.record("state_evict", slot=slot)
        return slot

    def release(self, slot: int) -> None:
        """A running sequence gives its slot back."""
        if slot:
            self._free.append(slot)

    def commit(self, slot: int, block_hash: int, tokens: int) -> None:
        """A running sequence's slot becomes the snapshot under
        `block_hash` (which has none: `has`), its committer the first
        reader (`unref`: at once, where it does not go on from it)."""
        self._snap[block_hash] = slot
        self._slot_hash[slot] = block_hash
        self._refs[slot] = 1
        self.stored_total += 1
        if self.events is not None:
            self.events.record("state_store", slot=slot, tokens=tokens)

    def has(self, block_hash: int) -> bool:
        return block_hash in self._snap

    def lookup(self, block_hash: int) -> int:
        """The snapshot under this hash with a reference taken, or 0."""
        slot = self._snap.get(block_hash, 0)
        if slot:
            self._lru.pop(slot, None)
            self._refs[slot] = self._refs.get(slot, 0) + 1
            self.hits_total += 1
        return slot

    def unref(self, slot: int) -> None:
        """A reader is done with a snapshot (its step is dispatched: what
        is dispatched later runs later)."""
        refs = self._refs.get(slot, 0) - 1
        if refs > 0:
            self._refs[slot] = refs
            return
        self._refs.pop(slot, None)
        if slot in self._slot_hash:
            self._lru[slot] = None


class ShardedPagePool:
    """KV pool partitioned into R independent per-device-shard pools
    (the dp/sp-sharded pool: on a dp×sp×tp serving mesh each (dp, sp)
    shard owns its own page range, so aggregate HBM KV capacity scales
    with the mesh instead of replicating — the TPU-native analog of the
    reference engines sharding KV across their TP/DP ranks,
    /root/reference/docs/architecture/disagg_serving.md:110-120).

    Page ids are GLOBAL: id = rank * num_pages + local_id, so sequences,
    transfer descriptors, and the scheduler carry plain ints; the engine
    derives (rank, local) with divmod when building per-shard tables.
    Each rank's local page 0 is its trash page.

    Prefix caches are per-rank (a block cached on rank 2 is invisible to
    rank 3's attention); `best_rank` steers admission toward the rank
    holding the longest cached run.  KV events deduplicate across ranks:
    "stored" fires when a hash first appears on ANY rank, "removed" when
    it leaves the LAST one — the router's per-worker view stays a set of
    hashes, matching the single-pool contract."""

    def __init__(self, ranks: int, num_pages: int, page_size: int,
                 event_sink: Optional[Callable[[KvEvent], None]] = None):
        self.ranks = ranks
        self.num_pages = num_pages  # PER RANK (per-shard HBM is fixed)
        self.page_size = page_size
        self._event_sink = event_sink
        self._hash_ranks: Dict[int, int] = {}  # hash → #ranks caching it
        self.pools = [
            PagePool(num_pages, page_size,
                     event_sink=self._make_sink(r))
            for r in range(ranks)
        ]

    def _make_sink(self, rank: int) -> Callable[[KvEvent], None]:
        del rank  # events carry hashes, not pages — all ranks dedup here

        def sink(ev: KvEvent) -> None:
            if self._event_sink is None:
                return
            if ev.kind == "stored":
                fresh = [h for h in ev.block_hashes
                         if self._hash_ranks.get(h, 0) == 0]
                for h in ev.block_hashes:
                    self._hash_ranks[h] = self._hash_ranks.get(h, 0) + 1
                if fresh:
                    self._event_sink(KvEvent("stored", fresh, ev.parent_hash))
            elif ev.kind == "removed":
                gone = []
                for h in ev.block_hashes:
                    left = self._hash_ranks.get(h, 0) - 1
                    if left <= 0:
                        self._hash_ranks.pop(h, None)
                        gone.append(h)
                    else:
                        self._hash_ranks[h] = left
                if gone:
                    self._event_sink(KvEvent("removed", gone))
            # "cleared" is suppressed per-rank: a rank-0 clear while ranks
            # 1..R-1 still hold cached hashes would transiently wipe the
            # router's view of hashes still onboard — clear_cache() emits
            # ONE pool-wide event after every sub-pool has cleared

        return sink

    # -- global-id helpers --------------------------------------------------- #

    def rank_of(self, page: int) -> int:
        return page // self.num_pages

    def local_id(self, page: int) -> int:
        return page % self.num_pages

    def _split(self, pages: Sequence[int]):
        by_rank: Dict[int, List[int]] = {}
        for p in pages:
            by_rank.setdefault(p // self.num_pages, []).append(
                p % self.num_pages
            )
        return by_rank

    # -- stats --------------------------------------------------------------- #

    @property
    def free_pages(self) -> int:
        return sum(p.free_pages for p in self.pools)

    @property
    def evictable_pages(self) -> int:
        return sum(p.evictable_pages for p in self.pools)

    @property
    def available_pages(self) -> int:
        return sum(p.available_pages for p in self.pools)

    @property
    def evictions_total(self) -> int:
        return sum(p.evictions_total for p in self.pools)

    def usage(self) -> float:
        usable = self.ranks * (self.num_pages - 1)
        return 1.0 - (self.available_pages / usable) if usable else 1.0

    def usage_max_rank(self) -> float:
        """One full rank blocks admission even when aggregate usage looks
        low (sequences pin to a rank) — busy/capacity signals key off the
        fullest partition, not the average."""
        return max(p.usage() for p in self.pools)

    def available_on(self, rank: int) -> int:
        return self.pools[rank].available_pages

    # -- allocation ---------------------------------------------------------- #

    def allocate_on(self, rank: int, n: int) -> List[int]:
        base = rank * self.num_pages
        return [base + p for p in self.pools[rank].allocate(n)]

    def allocate(self, n: int) -> List[int]:
        """Rank-less allocation (transfer-service staging): picks the
        emptiest rank that can hold all n pages — a single transfer's
        pages must be co-resident for its adopter."""
        rank = max(range(self.ranks), key=lambda r: self.pools[r].available_pages)
        return self.allocate_on(rank, n)

    def free(self, pages: Sequence[int]) -> None:
        for rank, local in self._split(pages).items():
            self.pools[rank].free(local)

    # -- prefix cache -------------------------------------------------------- #

    def lookup_on(self, rank: int, block_hashes: Sequence[int]) -> List[int]:
        base = rank * self.num_pages
        return [base + p for p in self.pools[rank].lookup(block_hashes)]

    def best_rank(self, block_hashes: Sequence[int]):
        """Rank with the longest cached prefix run; ties break toward
        the most available pages (load spreading)."""
        best, best_hits = 0, -1
        for r, pool in enumerate(self.pools):
            hits = pool.peek(block_hashes) if block_hashes else 0
            if hits > best_hits or (
                hits == best_hits
                and pool.available_pages > self.pools[best].available_pages
            ):
                best, best_hits = r, hits
        return best, max(best_hits, 0)

    def cached_page(self, block_hash: int) -> Optional[int]:
        for r, pool in enumerate(self.pools):
            p = pool.cached_page(block_hash)
            if p is not None:
                return r * self.num_pages + p
        return None

    def peek(self, block_hashes: Sequence[int]) -> int:
        return max(pool.peek(block_hashes) for pool in self.pools)

    def commit(self, page: int, block_hash: int, parent_hash: Optional[int]) -> int:
        rank = page // self.num_pages
        local = self.pools[rank].commit(
            page % self.num_pages, block_hash, parent_hash
        )
        return rank * self.num_pages + local

    def clear_cache(self) -> int:
        # per-rank "cleared" events are suppressed in the sink (see
        # _make_sink); the removed-event bookkeeping keeps _hash_ranks
        # consistent for hashes that survive (referenced cached pages)
        n = sum(pool.clear_cache() for pool in self.pools)
        if self._event_sink is not None:
            self._event_sink(KvEvent("cleared", []))
        return n
