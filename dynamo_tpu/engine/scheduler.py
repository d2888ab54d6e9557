"""Continuous-batching scheduler with chunked prefill, prefix caching and
preemption.

Modeled on the behavior the reference *simulates* in its mocker
(/root/reference/lib/llm/src/mocker/scheduler.rs:240 watermark scheduler,
chunked prefill, preemption) and vLLM's real scheduler — but designed for
XLA: every step produces a statically-shaped batch (bucketed chunk lengths /
batch sizes), so the jitted prefill/decode functions compile a handful of
variants and then never retrace.

Policy (vLLM-style):
- prefills first: any running sequence with unprefilled prompt tokens gets
  the next chunk (up to `max_prefill_tokens` across the step);
- otherwise one decode step over all running sequences;
- admission holds back `watermark` fraction of pages; allocation failure on
  a running sequence preempts the youngest sequence (pages freed, sequence
  returns to the head of the waiting queue and re-prefills — prefix cache
  makes the recompute cheap).
"""

from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional, Sequence as Seq, Tuple

from ..analysis import affine
from ..tokens import chain_seed, compute_block_hash_for_seq, next_block_hash
from .config import EngineConfig, bucket_for
from .page_pool import NoPagesError, PagePool, StatePool

logger = logging.getLogger(__name__)


@dataclass
class SamplingOptions:
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    max_tokens: int = 16
    stop_token_ids: List[int] = field(default_factory=list)
    stop_sequences: List[List[int]] = field(default_factory=list)
    ignore_eos: bool = False
    logprobs: bool = False
    top_logprobs: int = 0  # top-k logprobs per token (OpenAI max 20)
    seed: Optional[int] = None

    @property
    def penalized(self) -> bool:
        return bool(self.frequency_penalty or self.presence_penalty)


class Sequence:
    """One in-flight request inside the engine."""

    def __init__(self, request_id: str, prompt: List[int], opts: SamplingOptions):
        self.request_id = request_id
        self.prompt = list(prompt)
        self.opts = opts
        self.seed = 0  # per-request sampling seed (engine assigns)
        self.hold_pages = False  # finish() keeps pages (disagg KV export)
        # overload-control class: "interactive" rides ahead of "batch" in
        # the waiting queue and may claim the watermark reserve; "batch"
        # absorbs overload (queued with a deadline, shed, or preempted
        # mid-decode with its KV parked)
        self.priority = "interactive"
        # True while this sequence's KV lives in the engine's parking lot
        # (preempted mid-decode); num_computed / output_tokens /
        # block_hashes are preserved so resume is byte-exact
        self.parked = False
        # multimodal: processed pixels arrive with the request; the engine
        # encodes them at first prefill.  cache_salt isolates the prefix
        # cache per image content — image placeholder tokens are identical
        # across different images, so token-only hashes would alias
        self.mm_pixels = None  # np [N, H, W, 3] float32 (clip towers)
        self.mm_offsets: List[int] = []
        self.mm_embeds = None  # np [N, patches, h] — or, for dynamic-
        # resolution (qwen2_vl) media, a LIST of [P_i, h] arrays
        # qwen2_vl: per-medium (patches [L_i, patch_dim], grid (t, h, w))
        self.mm_patches = None
        self.mm_grids: List[tuple] = []
        # M-RoPE: per-token (temporal, height, width) streams for the
        # prompt, and the delta every later rope position shifts by
        self.mm_positions = None  # np [3, prompt_len] int32
        self.rope_delta = 0
        self.cache_salt = ""
        self.pages: List[int] = []
        self.kv_rank = 0  # pool partition this sequence's pages live on
        self._admit_hashes: Optional[List[int]] = None  # scheduler cache
        self.num_cached = 0  # prompt tokens satisfied from prefix cache
        # prompt tokens whose PAGES were found cached: more than
        # `num_cached` where a model with state-space layers had no
        # snapshot that deep (`Scheduler._shorten_to_snapshot`)
        self.kv_cached = 0
        # state slots (`page_pool.StatePool`; 0: none): the one this
        # sequence's steps write, a snapshot its NEXT step reads in its
        # place (a prefix hit, or its own slot just committed), and the
        # slots reserved for the snapshots that step hands out inside its
        # chunk, after 1, 2, ... intervals of `state_every` tokens (0: none
        # there)
        self.state_slot = 0
        self.state_src = 0
        self.state_inside: Tuple[int, ...] = ()
        self.state_every = 0
        self.num_computed = 0  # tokens whose KV is written
        self.output_tokens: List[int] = []
        self.block_hashes: List[int] = []  # chained, full blocks only
        self.committed_pages = 0
        self.status = "waiting"
        self.finish_reason: Optional[str] = None
        self.preemptions = 0
        # speculative decoding telemetry: drafts proposed for / accepted
        # by this sequence (ride the final delivery so the frontend can
        # aggregate per-model acceptance)
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        # TTFT attribution timestamps (time.monotonic): request enqueued
        # at the engine; first seen by the scheduler (the gap is the
        # in-flight decode block the pump was committed to — what the
        # block ladder shortens); admitted to running; first token
        # sampled.  `ttft_attr` is the one-shot attribution dict the
        # first delivered delta carries to the frontend.
        self.t_arrival: Optional[float] = None
        self.t_seen: Optional[float] = None
        self.t_admitted: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.ttft_attr: Optional[dict] = None
        # until the first token: the summed slices (ns) and the count of
        # the engine steps that computed this request's tokens
        self.own_ns = 0
        self.own_steps = 0
        # forensics: mid-stream incidents (preemption park/resume, prefix
        # onboard) accumulated here and attached to the next delivered
        # delta, so the frontend's per-request waterfall sees stalls that
        # happened inside the engine (attach-and-clear in _deliver)
        self.incidents: List[dict] = []
        self.t_parked: Optional[float] = None  # preempt_park stamp
        # the request's TraceContext, captured at generate() where the
        # transport's contextvar is still live — the pump thread exports
        # per-request milestone spans (block-wait/queue-wait/prefill/
        # decode) under it so engine time joins the caller's trace
        self.trace = None

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output_tokens)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def prefill_done(self) -> bool:
        return self.num_computed >= self.prompt_len

    def all_tokens(self) -> List[int]:
        return self.prompt + self.output_tokens

    def pages_needed(self, upto_tokens: int, page_size: int) -> int:
        return -(-upto_tokens // page_size)


class _StepInFlight(Exception):
    """Raised inside planning where the plan would take pages from a
    running sequence (preempt it, or park it for an interactive head)
    while a step is in flight (`Scheduler.in_flight`)."""


@dataclass
class PrefillItem:
    seq: Sequence
    chunk_start: int
    chunk_len: int
    samples: bool  # True when this chunk completes the prompt
    # True when the chunk may share its step (`Scheduler.shares_step`, in a
    # plain prefill plan): the step then runs at the short bucket and holds
    # such rows alone, one or several
    short: bool = False


@dataclass
class StepPlan:
    kind: str  # "prefill" | "decode" | "mixed" | "idle"
    # (a plan abandoned for the step in flight is "idle" too: see
    # `Scheduler.in_flight`)
    prefill: List[PrefillItem] = field(default_factory=list)
    decode: List[Sequence] = field(default_factory=list)


class Scheduler:
    def __init__(self, cfg: EngineConfig, pool: PagePool,
                 state: Optional[StatePool] = None):
        self.cfg = cfg
        self.pool = pool
        # slots of recurrent state beside the pages (a model with
        # state-space layers; None otherwise)
        self.state = state
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        # sequences errored inside planning (e.g. out of KV capacity with
        # nothing left to evict) — the engine drains and notifies
        self.errored: List[Sequence] = []
        # when set (decode-chain processing), _finish parks pages here
        # instead of freeing — freed pages must not be reallocated while
        # chained dispatches referencing them are still in flight
        self.deferred_free: Optional[List[int]] = None
        # the sequences of the ONE prefill step the engine has dispatched
        # and not fetched yet (engine `_run_prefill`; () at any other
        # time).  Each is either mid-prompt (its `num_computed` counts the
        # chunk in flight, so its next chunk can be planned behind it) or
        # has its sampling chunk in flight: `prefill_done` with no token,
        # so NOT decodable, no reason for a mixed or decode plan, and not
        # to be fused or drafted for.  While the tuple is non-empty the
        # plan takes no pages from a running sequence either: a plan that
        # would have to preempt (or give up on) a sequence is abandoned
        # and made again once the step's result is known, which may well
        # finish sequences and free the pages
        self.in_flight: Tuple[Sequence, ...] = ()
        # optional multi-tier onboarding hook (KVBM): called with the hash
        # run missed by the device cache, returns onboarded page ids.
        # `onboard_trace` carries the admitting request's TraceContext
        # across the hook call (set/cleared by _apply_prefix_cache)
        self.onboard_fn = None
        self.onboard_trace = None
        # overload-control hooks (engine-set; all None on the mock path,
        # which falls back to recompute preemption):
        #   park_fn(seq) -> bool    exports the victim's live KV pages into
        #                           the parking lot (False = lot full)
        #   resume_fn(seq)          restores parked KV into fresh pages at
        #                           admission time (raises on failure)
        #   unpark_fn(seq)          releases a parked entry without resuming
        #                           (abort / shutdown while parked)
        self.park_fn = None
        self.resume_fn = None
        self.unpark_fn = None
        # batch-class sequences shed from the waiting queue (deadline
        # expiry under pressure) — the engine drains and notifies with a
        # structured `overloaded` error
        self.shed: List[Sequence] = []
        # overload counters (exported as dynamo_engine_*_total)
        self.preempted_total = 0
        self.resumed_total = 0
        self.shed_total = 0
        self.queued_total = 0
        # block-ladder ramp position: 0 = shortest rung.  Reset whenever
        # prompts are pending; climbs one rung per quiet dispatch so the
        # engine eases back into full blocks instead of jumping (a burst
        # straggler arriving right after the queue drains still finds a
        # short block in flight)
        self._rung_idx = 0
        # optional StepEventRecorder (runtime.events): admissions and rung
        # selections land on the engine step timeline
        self.events = None

    @affine("step", "loop")
    def drain_errored(self) -> List[Sequence]:
        out, self.errored = self.errored, []
        return out

    # -- intake -------------------------------------------------------------- #

    @affine("step", "loop")
    def add(self, seq: Sequence) -> None:
        if seq.prompt_len + seq.opts.max_tokens > self.cfg.max_model_len:
            # clamp generation budget to the model window
            seq.opts.max_tokens = max(0, self.cfg.max_model_len - seq.prompt_len)
        if seq.t_seen is None:
            seq.t_seen = time.monotonic()
        if seq.priority == "batch" and (
            self.waiting or len(self.running) >= self.cfg.max_num_seqs
        ):
            # a batch request enqueued behind existing work (the
            # "queued" arm of the shed-or-queue policy)
            self.queued_total += 1
        self._enqueue(seq)

    def _class_rank(self, seq: Sequence) -> int:
        return 0 if seq.priority == "interactive" else 1

    def _enqueue(self, seq: Sequence, front: bool = False) -> None:
        """Class-ordered queue insert: interactive rides ahead of batch,
        FIFO within a class.  `front` inserts at the head of the
        sequence's OWN class region (preemption victims re-admit before
        later arrivals of the same class — the anti-starvation property
        the old `appendleft` provided, now class-scoped)."""
        rank = self._class_rank(seq)
        idx = len(self.waiting)
        for i, s in enumerate(self.waiting):
            r = self._class_rank(s)
            if (r >= rank) if front else (r > rank):
                idx = i
                break
        self.waiting.insert(idx, seq)

    @affine("step", "loop")
    def abort(self, request_id: str) -> None:
        for seq in list(self.waiting):
            if seq.request_id == request_id:
                self.waiting.remove(seq)
                self._release_parked(seq)
                seq.status = "finished"
                seq.finish_reason = "cancelled"
        for seq in self.running:
            if seq.request_id == request_id:
                self._finish(seq, "cancelled")

    def _release_parked(self, seq: Sequence) -> None:
        """Credit the parking lot for a parked sequence that will never
        resume (abort / shed / shutdown) — parked KV must never outlive
        its request (the leak ledger's `parked_pages` account)."""
        if seq.parked:
            if self.unpark_fn is not None:
                self.unpark_fn(seq)
            seq.parked = False

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def num_requests(self) -> Tuple[int, int]:
        return len(self.running), len(self.waiting)

    # -- admission ----------------------------------------------------------- #

    def _watermark_pages(self) -> int:
        return int(self.cfg.watermark * self.cfg.usable_pages)

    def _admit_check(self, seq: Sequence) -> Tuple[bool, int]:
        """(admissible, rank): the non-mutating capacity half of
        admission — the single source of truth shared by `_try_admit`
        and `prompts_pending`, so the block-ladder policy can never
        desynchronize from real admissibility.

        Class-aware (overload control): an interactive request may claim
        the watermark reserve when batch-class work is present to absorb
        the resulting pressure (the reserve's churn-prevention role is
        taken over by batch preemption); batch requests always respect
        the full reserve.  A parked sequence's need is its restore
        footprint (the parked pages plus the next decode position), not
        a first prefill chunk."""
        if seq.parked:
            need = seq.pages_needed(seq.num_computed + 1, self.cfg.page_size)
        else:
            first_chunk = min(seq.prompt_len, self.cfg.max_prefill_tokens)
            need = seq.pages_needed(first_chunk, self.cfg.page_size)
        if seq.num_computed > 0 or self.pool.ranks == 1:
            # imported KV keeps the rank its pages live on; single
            # pools skip partition scoring entirely
            rank = seq.kv_rank
        else:
            # pick the pool partition: longest cached prefix wins,
            # ties spread by availability
            rank, _ = self.pool.best_rank(self._seq_hashes(seq))
        ok = self.pool.available_on(rank) >= need + self._reserve_pages(seq)
        if self.state is not None and not self.state.available:
            ok = False  # waits for a slot as for a page
        return ok, rank

    def _reserve_pages(self, seq: Sequence) -> int:
        """Admission reserve this sequence must leave untouched."""
        wm = self._watermark_pages()
        if wm and seq.priority == "interactive" and self._batch_present():
            return 0
        return wm

    def _batch_present(self) -> bool:
        return any(s.priority == "batch" for s in self.running) or any(
            s.priority == "batch" for s in self.waiting
        )

    def overloaded(self) -> bool:
        """Past the configured pressure threshold: the waiting queue is
        at least `overload_queue_depth` deep AND the live watermark
        headroom (the PR 7 capacity gauge) is at or under
        `overload_headroom_pages`.  Scheduler-side source of truth for
        batch admission shedding; 0 depth disables shedding."""
        depth = self.cfg.overload_queue_depth
        if depth <= 0 or len(self.waiting) < depth:
            return False
        headroom = (self.pool.available_pages
                    - self._watermark_pages() * self.pool.ranks)
        return headroom <= self.cfg.overload_headroom_pages

    def _try_admit(self) -> None:
        self._shed_expired()
        while self.waiting:
            seq = self.waiting[0]
            if len(self.running) >= self.cfg.max_num_seqs:
                ok, rank = False, seq.kv_rank
            else:
                ok, rank = self._admit_check(seq)
            if not ok:
                # an interactive head may evict batch-class decodes
                # (park, not recompute) to make room for itself
                if not self._preempt_for_head(seq):
                    break
                if len(self.running) >= self.cfg.max_num_seqs:
                    break
                ok, rank = self._admit_check(seq)
                if not ok:
                    break
            seq.kv_rank = rank
            self.waiting.popleft()
            if seq.parked:
                if not self._resume(seq):
                    continue  # errored out; next head may still admit
            else:
                self._admit_cache(seq)
            seq.status = "running"
            if seq.t_admitted is None:  # keep the FIRST admission:
                # re-admission after preemption is not queue wait
                seq.t_admitted = time.monotonic()
            self.running.append(seq)
            if self.events is not None:
                self.events.record(
                    "admit", rid=seq.request_id, rank=rank,
                    prompt_len=seq.prompt_len, cached=seq.num_cached,
                    kv_cached=seq.kv_cached,
                )

    def _resume(self, seq: Sequence) -> bool:
        """Restore a parked sequence's KV through the engine hook; on
        failure the request errors out (never silently recomputed — a
        recompute here would break token identity)."""
        try:
            self.resume_fn(seq)
        except Exception:  # noqa: BLE001 — surfaced as a request error
            logger.exception("park/resume restore failed for %s",
                             seq.request_id)
            self._release_parked(seq)
            seq.status = "finished"
            seq.finish_reason = "error"
            self.errored.append(seq)
            return False
        seq.parked = False
        self.resumed_total += 1
        if seq.t_parked is not None:
            # forensics: the park→resume stall rides the next delivered
            # delta so the frontend's waterfall can blame `preempt`
            stall_ms = (time.monotonic() - seq.t_parked) * 1e3
            seq.incidents.append(
                {"kind": "preempt", "stall_ms": round(stall_ms, 3)})
            seq.t_parked = None
        if self.events is not None:
            self.events.record(
                "preempt_resume", rid=seq.request_id, rank=seq.kv_rank,
                tokens=seq.num_computed,
            )
        return True

    @affine("step", "loop")
    def splice_admit(self) -> Optional[Sequence]:
        """Admit the head-of-queue prompt WITHOUT the pump: the
        continuous decode chain's step thread calls this mid-chain so
        an arriving request becomes a chunk row spliced into the
        running block (docs/device_loop.md "splice protocol") instead
        of a chain fall-out.  Exactly `_try_admit`'s per-sequence body
        — same `_admit_check` capacity gate (watermark-respecting),
        same prefix-cache application, same admit event (tagged
        ``spliced``) — so splice admission and pump admission can never
        diverge.  Returns the admitted sequence, or None when the head
        is not admissible right now.  A parked head never splices: its
        resume is a device KV import, not a chunk-row feed — the chain
        falls out (``admit``) and the pump resumes it."""
        if not self._head_admissible():
            return None
        if self.waiting[0].parked:
            return None
        seq = self.waiting[0]
        ok, rank = self._admit_check(seq)
        if not ok:
            return None
        seq.kv_rank = rank
        self.waiting.popleft()
        self._admit_cache(seq)
        seq.status = "running"
        if seq.t_admitted is None:
            seq.t_admitted = time.monotonic()
        self.running.append(seq)
        if self.events is not None:
            self.events.record(
                "admit", rid=seq.request_id, rank=rank,
                prompt_len=seq.prompt_len, cached=seq.num_cached,
                kv_cached=seq.kv_cached, spliced=True,
            )
        return seq

    def _seq_hashes(self, seq: Sequence) -> List[int]:
        """Block-hash chain for admission-time cache scoring (never hits
        the whole-prompt block — its last token must be recomputed).
        Cached on the sequence: the prompt never changes, and a waiting
        head-of-queue sequence is re-examined every pump tick."""
        if not self.cfg.enable_prefix_caching:
            return []
        if getattr(seq, "_admit_hashes", None) is None:
            hashes = self._prompt_hashes(seq)
            if seq.prompt_len % self.cfg.page_size == 0 and hashes:
                hashes = hashes[:-1]
            seq._admit_hashes = hashes
        return seq._admit_hashes

    def _prompt_hashes(self, seq: Sequence) -> List[int]:
        """The chained hash of every full block of the prompt, computed once
        a sequence: entry i is that of its first (i + 1) pages of tokens."""
        if getattr(seq, "_block_hashes", None) is None:
            seq._block_hashes = compute_block_hash_for_seq(
                seq.prompt, self.cfg.page_size,
                self.cfg.block_hash_salt + seq.cache_salt)
        return seq._block_hashes

    def _admit_cache(self, seq: Sequence) -> None:
        """What an admitted sequence takes from the caches: a state slot of
        its own first (which may evict the oldest unread snapshot), then the
        cached prefix."""
        if self.state is not None and not seq.state_slot:
            seq.state_slot = self.state.allocate()
        if self.cfg.enable_prefix_caching:
            self._apply_prefix_cache(seq)

    def _shorten_to_snapshot(self, seq: Sequence, hashes: List[int],
                             hit_pages: List[int]) -> List[int]:
        """A model with state-space layers resumes where it has BOTH the
        pages and the state: at the deepest committed snapshot at or under
        the cached pages.  Takes that snapshot as the sequence's next read
        (`state_src`) and gives the pages past it back: their tokens are
        computed again, into pages of the sequence's own (the cached ones
        may be another reader's).  A snapshot may stand at any page."""
        keep = 0
        for n in range(len(hit_pages), 0, -1):
            seq.state_src = self.state.lookup(hashes[n - 1])
            if seq.state_src:
                keep = n
                break
        self.state.hit_tokens_shortened_total += (
            (len(hit_pages) - keep) * self.cfg.page_size)
        self.pool.free(hit_pages[keep:])
        return hit_pages[:keep]

    @affine("step", "loop")
    def add_imported(self, seq: Sequence) -> None:
        """Admit a sequence whose KV was injected externally (disagg decode
        side): pages and num_computed are already set; skip prefix cache."""
        if seq.t_seen is None:
            seq.t_seen = time.monotonic()
        self.waiting.append(seq)

    def _apply_prefix_cache(self, seq: Sequence) -> None:
        if seq.num_computed > 0:  # imported KV — already placed
            return
        ps = self.cfg.page_size
        # never cache-hit the *entire* prompt: the last token must be
        # recomputed so prefill produces logits to sample from.
        hashes = self._seq_hashes(seq)
        hit_pages = self.pool.lookup_on(seq.kv_rank, hashes)
        if self.onboard_fn is not None and len(hit_pages) < len(hashes):
            # onboard() returns pages already holding this sequence's
            # ref, allocated on the sequence's pool rank (a sequence's
            # pages must share one partition).  The admitting request's
            # trace rides an attribute (not the hook signature, which
            # tests spy on) so the engine can export a kvbm.onboard span
            # under it.
            self.onboard_trace = seq.trace
            t_onboard = time.monotonic()
            try:
                onboarded = self.onboard_fn(
                    hashes[len(hit_pages):], seq.kv_rank)
                if onboarded:
                    # forensics: host→device KV onboarding stalled this
                    # request's admission; ride the first delta
                    seq.incidents.append({
                        "kind": "onboard",
                        "pages": len(onboarded),
                        "stall_ms": round(
                            (time.monotonic() - t_onboard) * 1e3, 3),
                    })
                hit_pages.extend(onboarded)
            finally:
                # a raising hook must not leave the dead request's trace
                # attached — the next admission's span would join it
                self.onboard_trace = None
        seq.kv_cached = len(hit_pages) * ps
        if hit_pages and self.state is not None:
            hit_pages = self._shorten_to_snapshot(seq, hashes, hit_pages)
        if hit_pages:
            seq.pages = list(hit_pages)
            seq.num_cached = len(hit_pages) * ps
            seq.num_computed = seq.num_cached
            seq.block_hashes = hashes[: len(hit_pages)]
            seq.committed_pages = len(hit_pages)

    # -- planning ------------------------------------------------------------ #

    def _head_admissible(self) -> bool:
        """Could the head-of-queue prompt be admitted right now?  The
        same `_admit_check` `_try_admit` runs, minus the mutation."""
        if not self.waiting or len(self.running) >= self.cfg.max_num_seqs:
            return False
        return self._admit_check(self.waiting[0])[0]

    def prompts_pending(self) -> bool:
        """True when a prompt could make progress next plan — a running
        sequence still mid-chunked-prefill, or an ADMISSIBLE waiting
        prompt — i.e. the states whose TTFT a committed full decode
        block would hold hostage.  A waiting prompt that CANNOT be
        admitted (pages/slots exhausted) is excluded on purpose: short
        rungs buy it nothing (it is blocked on capacity, not on the
        in-flight block — that wait lands in queue-wait, not
        block-wait), and pinning every decode to 1-step unchained
        dispatches for its whole wait would tax the running streams'
        ITL indefinitely.  `_chain_ok` still refuses chaining while
        anything waits, so once capacity frees the prompt is admitted
        within at most one (full) block."""
        return any(
            not s.prefill_done for s in self.running
        ) or self._head_admissible()

    @affine("step", "loop")
    def select_decode_rung(self) -> Tuple[int, bool]:
        """(n_steps, allow_chain) for the next decode-bearing dispatch
        (pure decode, mixed, or the fused prefill→decode chain).

        Policy (the block ladder, ISSUE 2 / Sarathi-Serve's stall-free
        property in host-side form): while prompts are pending, dispatch
        the SHORTEST rung with chaining suppressed, so the pump replans
        — and the waiting prompt rides a mixed dispatch — within one
        short block instead of `chain × decode_steps` steps.  Once the
        queue drains, climb one rung per quiet dispatch back to the full
        block; chaining is only allowed at the top rung (a chain is a
        commitment of chain × n_steps steps, exactly what short rungs
        exist to avoid).

        Page reservation is unaffected: `decode_advance` covers the
        worst case (`decode_steps`, or the 1+k speculative chunk) and
        every rung is <= decode_steps, so a rung switch never outgrows
        the reserved tables — including under speculative-verify
        reservations."""
        ladder = self.cfg.block_ladder
        if len(ladder) == 1:
            return ladder[-1], True
        # ONE pending evaluation per call: prompts_pending walks the
        # running list and scores head-of-queue admissibility — pump
        # hot-path work the ladder exists to keep short
        pending = self.prompts_pending()
        rung = self._rung_for(pending)
        self._rung_idx = (0 if pending
                          else min(self._rung_idx + 1, len(ladder) - 1))
        if self.events is not None:
            self.events.record("rung_select", rung=rung[0],
                               chain=rung[1], pending=pending)
        return rung

    def peek_decode_rung(self) -> Tuple[int, bool]:
        """`select_decode_rung` without the ramp advance — for callers
        that may still abort the dispatch (the fused path's page
        extension): a rung is only consumed when a block actually
        dispatches."""
        ladder = self.cfg.block_ladder
        if len(ladder) == 1:
            return ladder[-1], True
        return self._rung_for(self.prompts_pending())

    def _rung_for(self, pending: bool) -> Tuple[int, bool]:
        ladder = self.cfg.block_ladder
        if pending:
            return ladder[0], False
        idx = min(self._rung_idx, len(ladder) - 1)
        return ladder[idx], idx == len(ladder) - 1

    @affine("step", "loop")
    def commit_decode_rung(self) -> None:
        """Advance the ramp for a dispatch whose rung was taken via
        `peek_decode_rung` (the fused path: its eligibility already
        guaranteed no prompts were pending, so this is always the
        quiet-ramp advance — no second pending evaluation, and the
        committed rung is exactly the peeked one)."""
        ladder = self.cfg.block_ladder
        if len(ladder) > 1:
            self._rung_idx = min(self._rung_idx + 1, len(ladder) - 1)

    @affine("step", "loop")
    def schedule(self) -> StepPlan:
        try:
            return self._schedule()
        except _StepInFlight:
            return StepPlan("idle")

    def _schedule(self) -> StepPlan:
        self._try_admit()
        if not self.running:
            return StepPlan("idle")

        # mixed scheduling: when decodes are already running AND prompts
        # are pending, plan BOTH into one dispatch — decodes keep their
        # ITL, the prefill side advances by a bounded chunk budget.
        # Decode rows get page priority (preemptive); the mixed prefill
        # side allocates non-preemptively (it must not invalidate a
        # decode row planned into the same dispatch).  Multimodal
        # prompts take the pure-prefill path (their embed injection
        # arrays only exist there).
        has_pending_prefill = any(
            not s.prefill_done for s in self.running
        )
        mixed_budget = self.cfg.mixed_prefill_tokens
        if has_pending_prefill and mixed_budget > 0 and any(
            s.prefill_done and s not in self.in_flight for s in self.running
        ) and not any(
            s.mm_embeds is not None or s.mm_pixels is not None
            or s.mm_patches is not None
            for s in self.running if not s.prefill_done
        ):
            decodable = self._plan_decode()
            if decodable:
                items = self._plan_prefill(mixed_budget, preempt=False)
                if items:
                    return StepPlan("mixed", prefill=items, decode=decodable)
                return StepPlan("decode", decode=decodable)

        items = self._plan_prefill(self.cfg.max_prefill_tokens, preempt=True)
        if items:
            return StepPlan("prefill", prefill=items)
        decodable = self._plan_decode()
        if decodable:
            return StepPlan("decode", decode=decodable)
        return StepPlan("idle")

    def shares_step(self, seq: Sequence, chunk: int) -> bool:
        """May this chunk run in a prefill step beside other sequences'?
        Only a WHOLE remaining prompt no longer than the short bucket
        (`EngineConfig.short_chunk_bucket`: a cached question's fresh
        tokens, a document's last remainder): every row of a step is padded
        to the step's chunk bucket, so a short row beside a long one would
        be computed at the long one's length.  Vision inputs keep their own
        step (their embeds are one more program variant)."""
        return (0 < chunk <= self.cfg.short_chunk_bucket
                and chunk == seq.prompt_len - seq.num_computed
                and seq.mm_embeds is None and seq.mm_pixels is None
                and seq.mm_patches is None)

    @staticmethod
    def step_variant(seq: Sequence) -> Tuple[bool, bool]:
        """(top logprobs, greedy): the `prefill_step` program a sequence's
        row asks for.  Rows share a step only with their own kind, so the
        short steps' programs are two per variant and table width (one
        sequence, or the shared row count), both run at the first short
        step of their kind (`JaxEngine._meet_short_prefill`) and neither
        found by two rows meeting."""
        return seq.opts.top_logprobs > 0, seq.opts.temperature <= 0.0

    def _plan_prefill(self, budget: int, preempt: bool) -> List[PrefillItem]:
        """Plan one prefill step under a token budget (iterate a copy:
        preemptive page growth may preempt members).  The first sequence in
        `running` whose prompt is not done is always in it: first in first
        out.  Where its chunk is short (`shares_step`), further sequences
        in `running` order whose chunk is short too, and of the head's
        `step_variant`, ride in the same step as further rows, up to
        `prefill_batch_size`: such a step is bound by reading the weights,
        which it then reads once for all of them.  A row that joins may
        pass a long chunk that stands before it; nothing passes the head.
        A row joins without preempting anyone.  A mixed step (`preempt`
        False) keeps one prefill sequence: its programs are a product of
        buckets already."""
        items: List[PrefillItem] = []
        for seq in list(self.running):
            if seq.prefill_done or budget <= 0 or seq.status != "running":
                continue  # (the head's page growth may have preempted it)
            chunk = min(seq.prompt_len - seq.num_computed, budget)
            if self.state is not None:
                chunk = self._state_chunk(seq, chunk)
            short = preempt and self.shares_step(seq, chunk)
            if items and not (
                    short and self.step_variant(seq)
                    == self.step_variant(items[0].seq)):
                continue
            if self.state is not None and not self._ensure_state(
                    seq, chunk, short):
                continue  # no slot: its turn comes when one is given up
            if preempt and not items:
                if not self._ensure_pages(seq, seq.num_computed + chunk):
                    continue  # seq may have been preempted/errored
            elif preempt:
                if not self.try_extend_pages(seq, seq.num_computed + chunk):
                    continue  # pool tight: its own turn will come
            else:
                need = seq.pages_needed(
                    seq.num_computed + chunk, self.cfg.page_size
                ) - len(seq.pages)
                # a mixed prefill chunk must not drain the watermark
                # reserve admission maintains for decode growth — doing so
                # forces the next decode growth to preempt this very
                # prefill (churn the watermark exists to prevent).  Chunks
                # needing no new pages always proceed: they cost the
                # reserve nothing
                if need > 0:
                    headroom = self._watermark_pages()
                    if seq.preemptions >= 2:
                        # anti-thrash: a sequence decode growth has
                        # evicted twice only re-prefills with real
                        # headroom (enough pages that the running
                        # decodes' next growth will not immediately
                        # evict it again)
                        headroom += sum(
                            1 for s in self.running
                            if s.prefill_done and s.kv_rank == seq.kv_rank
                        )
                    if self.pool.available_on(seq.kv_rank) < need + headroom:
                        continue
                if not self.try_extend_pages(seq, seq.num_computed + chunk):
                    continue  # pool tight — decode-only this round
            items.append(
                PrefillItem(
                    seq,
                    seq.num_computed,
                    chunk,
                    samples=(seq.num_computed + chunk >= seq.prompt_len),
                    short=short,
                )
            )
            budget -= chunk
            # (a long head is alone; whoever joined a short one is short)
            if not short or len(items) >= self.cfg.prefill_batch_size:
                break
        return items

    def _plan_decode(self) -> List[Sequence]:
        """Every prefill-done running sequence advances up to
        `decode_advance` tokens — decode_steps on the block path, or the
        1+k draft-verify chunk when speculation is on; reservation
        covers the worst case of whichever path the engine dispatches
        (page reservation clamped to the model window so the table
        never outgrows its largest bucket).  Variable multi-token
        acceptance is handled at consume time: `check_stop` runs per
        appended token, so a stop inside an accepted run discards the
        tail exactly like a stop inside a decode block."""
        hard_cap = self.cfg.hard_cap
        decodable: List[Sequence] = []
        for seq in list(self.running):
            if (seq.status != "running" or not seq.prefill_done
                    or seq in self.in_flight):  # its token is not here yet
                continue
            target = min(seq.num_computed + self.cfg.decode_advance, hard_cap)
            if not self._ensure_pages(seq, target):
                continue
            decodable.append(seq)
        return decodable[: self.cfg.max_num_seqs]

    def _prompt_hash(self, seq: Sequence, tokens: int) -> int:
        """The chained block hash of the prompt's first `tokens` tokens (a
        multiple of the page size)."""
        return self._prompt_hashes(seq)[tokens // self.cfg.page_size - 1]

    def _tail_start(self, seq: Sequence) -> int:
        """Where the tail row of `seq`'s prompt starts; 0: it has none.  A
        prompt whose uncached part is longer than a snapshot interval ends
        its prefill with one short row, the last page boundary under its
        last token and as many whole pages before it as a step hands out
        states (`StatePool.inside`), and that row hands its state out at
        every page: the tail of a prompt is where a follow-up parts from it
        (a document, then another question), so the follow-up finds a
        snapshot at its last shared page and is a short row itself, which
        shares a step.  A shorter uncached part stays one chunk: it resumed
        from a snapshot that near.  None either where such a row's step
        would not hand out by pages (another page size or short bucket)."""
        st, ps = self.state, self.cfg.page_size
        row = (st.inside + 1) * ps
        if (not self.cfg.enable_prefix_caching
                or seq.prompt_len - seq.num_cached <= st.snapshot_every
                or self._every(row, row <= self.cfg.short_chunk_bucket) != ps):
            return 0
        return max((seq.prompt_len - 1) // ps * ps - st.inside * ps, 0)

    def _every(self, chunk: int, short: bool) -> int:
        """The tokens between the states a chunk's step hands out, by the
        bucket its program runs at (`JaxEngine._prefill_arrays`)."""
        return self.state.every_of(
            self.cfg.short_chunk_bucket if short
            else bucket_for(chunk, self.cfg.chunk_buckets))

    def _state_chunk(self, seq: Sequence, chunk: int) -> int:
        """The chunk of a model with state-space layers: it starts at a page
        boundary (its step hands the state out at whole intervals from
        there, and a snapshot is addressed by its page's hash), so one that
        does not, after a budget that is no multiple, stops at the next; and
        it does not reach into the prompt's tail row (`_tail_start`)."""
        ps, n = self.cfg.page_size, seq.num_computed
        if n % ps:
            return min(chunk, ps - n % ps)
        tail = self._tail_start(seq)
        return min(chunk, tail - n) if n < tail else chunk

    def _ensure_state(self, seq: Sequence, chunk: int, short: bool) -> bool:
        """Before a prefill chunk of `seq` is planned: the slot its steps
        write, and the snapshots (`StatePool`), each if a slot is to be had
        and its position has none.  Where the computed tokens stand at a
        page boundary with the sequence's own slot holding the state there:
        that slot is committed, stays the next step's read, and the sequence
        goes on in a fresh one.  For the positions INSIDE the chunk, every
        `_every(chunk, short)` tokens from its start, a slot each is
        reserved, which the step writes and `chunk_dispatched` commits; a
        short row hands out by pages, and keeps them only where it is a
        prompt's tail row (a follow-up's own short row leaves nothing worth
        a slot).  False: the sequence has no slot."""
        st = self.state
        if not seq.state_slot:
            seq.state_slot = st.allocate()
            if not seq.state_slot:
                return False
        for slot in seq.state_inside:  # of a plan that was not dispatched
            st.release(slot)
        seq.state_inside = ()
        n, every = seq.num_computed, self._every(chunk, short)
        if n % self.cfg.page_size or not self.cfg.enable_prefix_caching:
            return True
        here = n and self._prompt_hash(seq, n)
        if n and not seq.state_src and not st.has(here) and st.available:
            seq.state_src, seq.state_slot = seq.state_slot, st.allocate()
            st.commit(seq.state_src, here, n)
        if every < st.snapshot_every and not 0 < self._tail_start(seq) <= n:
            return True
        seq.state_every = every
        seq.state_inside = tuple(
            st.allocate() if (at < n + chunk and st.available and not st.has(
                self._prompt_hash(seq, at))) else 0
            for at in range(n + every, n + every * (st.inside + 1), every))
        return True

    @affine("step", "loop")
    def chunk_dispatched(self, seq: Sequence, tokens: int) -> None:
        """A prefill chunk of `seq` is committed to the device: the
        snapshots it writes inside the chunk are committed (evictable at
        once: nobody reads them yet), its tokens count as computed, and the
        snapshot it read is no longer held for it (what is dispatched later
        runs later)."""
        if self.state is not None:
            every = seq.state_every
            for j, slot in enumerate(seq.state_inside):
                if not slot:
                    continue
                at = seq.num_computed + (j + 1) * every
                if self.state.has(self._prompt_hash(seq, at)):
                    self.state.release(slot)  # committed meanwhile
                else:
                    self.state.commit(slot, self._prompt_hash(seq, at), at)
                    self.state.unref(slot)
            seq.state_inside = ()
        seq.num_computed += tokens
        if seq.state_src:
            self.state.unref(seq.state_src)
            seq.state_src = 0

    def _release_state(self, seq: Sequence) -> None:
        if self.state is None:
            return
        if seq.state_src:
            self.state.unref(seq.state_src)
        for slot in (seq.state_slot, *seq.state_inside):
            self.state.release(slot)
        seq.state_src = seq.state_slot = 0
        seq.state_inside = ()

    def _ensure_pages(self, seq: Sequence, upto_tokens: int) -> bool:
        """Grow seq's page list to cover `upto_tokens`, preempting others
        (youngest-first) if the pool is dry. Returns False if seq itself got
        preempted."""
        need = seq.pages_needed(upto_tokens, self.cfg.page_size) - len(seq.pages)
        if need <= 0:
            return True
        while True:
            try:
                seq.pages.extend(self.pool.allocate_on(seq.kv_rank, need))
                return True
            except NoPagesError:
                if self.in_flight:
                    raise _StepInFlight() from None
                victim = self._pick_victim(exclude=seq, rank=seq.kv_rank)
                if victim is None:
                    # nothing left to evict: with the pool to itself the
                    # sequence can never fit — error it out instead of the
                    # preempt/re-admit livelock
                    self._finish(seq, "error")
                    self.errored.append(seq)
                    return False
                # park mid-decode victims (byte-exact resume) when the
                # engine provides a lot; recompute-preempt otherwise
                if not self.preempt_park(victim):
                    self._preempt(victim)

    @affine("step", "loop")
    def try_extend_pages(self, seq: Sequence, upto_tokens: int,
                         keep_watermark: bool = False) -> bool:
        """Grow seq's page list WITHOUT preemption (cached-page eviction is
        fine).  Used by decode-chaining, where preempting a running sequence
        would invalidate tables already captured by in-flight dispatches.
        `keep_watermark` additionally refuses to dip into the admission
        reserve — the continuous decode loop's horizon pre-reservation
        must not starve waiting prompts of the pages `_admit_check`
        holds back for them."""
        need = seq.pages_needed(upto_tokens, self.cfg.page_size) - len(seq.pages)
        if need <= 0:
            return True
        reserve = self._watermark_pages() if keep_watermark else 0
        if self.pool.available_on(seq.kv_rank) < need + reserve:
            return False
        seq.pages.extend(self.pool.allocate_on(seq.kv_rank, need))
        return True

    def admission_ready(self) -> bool:
        """Public face of `_head_admissible` (`_admit_check` minus the
        mutation): True when the head-of-queue prompt could be admitted
        right now — the continuous decode chain's admission fall-out
        signal."""
        return self._head_admissible()

    def _pick_victim(self, exclude: Sequence, rank: int = 0) -> Optional[Sequence]:
        """Youngest running sequence on the SAME pool partition (evicting
        another rank's pages cannot unblock this allocation); batch-class
        victims are preferred over interactive ones."""
        for want_batch in (True, False):
            for seq in reversed(self.running):  # youngest first
                if (seq is not exclude and seq.kv_rank == rank
                        and (seq.priority == "batch") == want_batch):
                    return seq
        return None

    def _park_candidate(self, rank: int) -> Optional[Sequence]:
        """Youngest batch-class mid-decode sequence on `rank` — the only
        legal park victims (a mid-prefill victim has no output KV worth
        preserving; recompute preemption handles it)."""
        for seq in reversed(self.running):
            if (seq.priority == "batch" and seq.kv_rank == rank
                    and seq.prefill_done and seq.output_tokens):
                return seq
        return None

    @affine("step", "loop")
    def preempt_park(self, seq: Sequence) -> bool:
        """Preempt `seq` mid-decode by PARKING its KV (byte-exact resume)
        instead of recomputing: commit full blocks to the device cache
        (feeding the tier offload pump), export the live pages through the
        engine's park hook, free them, and requeue at the head of the
        victim's class region.  Returns False (no state change) when the
        hook is absent, the victim is not mid-decode, or the lot refuses
        (budget) — callers fall back to recompute preemption."""
        if (self.park_fn is None or not seq.prefill_done
                or not seq.output_tokens or seq.hold_pages):
            return False
        self.commit_full_pages(seq)
        if not self.park_fn(seq):
            return False
        logger.info("parking %s (%d tokens)", seq.request_id,
                    seq.num_computed)
        self.pool.free(seq.pages)
        seq.pages = []
        seq.committed_pages = 0
        seq.parked = True
        seq.status = "waiting"
        seq.preemptions += 1
        seq.t_parked = time.monotonic()  # forensics: resume stamps stall
        self.preempted_total += 1
        if seq in self.running:
            self.running.remove(seq)
        self._enqueue(seq, front=True)
        if self.events is not None:
            self.events.record(
                "preempt_park", rid=seq.request_id, rank=seq.kv_rank,
                tokens=seq.num_computed, outputs=len(seq.output_tokens),
            )
        return True

    def _rank_for(self, seq: Sequence) -> int:
        if seq.num_computed > 0 or self.pool.ranks == 1:
            return seq.kv_rank
        return self.pool.best_rank(self._seq_hashes(seq))[0]

    def _preempt_for_head(self, seq: Sequence) -> bool:
        """Park batch-class victims until the interactive head `seq`
        becomes admissible (pages or a slot).  Returns True if at least
        one victim was parked; never touches interactive victims and
        never recomputes (a recompute preemption of a mid-decode victim
        is not token-safe on the real engine)."""
        if self.park_fn is None or seq.priority != "interactive":
            return False
        rank = self._rank_for(seq)
        if self.in_flight:
            # answered like a plan that runs out of pages: the step's
            # result first, then the victim is parked by a plan that
            # knows it (the head waits one fetch, not a run of prefills)
            if self._park_candidate(rank) is not None:
                raise _StepInFlight()
            return False
        parked_any = False
        for _ in range(len(self.running)):
            if (len(self.running) < self.cfg.max_num_seqs
                    and self._admit_check(seq)[0]):
                break
            victim = self._park_candidate(rank)
            if victim is None or not self.preempt_park(victim):
                break
            parked_any = True
        return parked_any

    def preempt_ready(self) -> bool:
        """True when an interactive head could be admitted if a batch
        victim were parked — the continuous decode chain's preemption
        fall-out signal (reason ``preempted``): the chain exits, the pump
        replans, `_try_admit` parks the victim and admits the head."""
        if self.park_fn is None or not self.waiting:
            return False
        head = self.waiting[0]
        if head.priority != "interactive":
            return False
        if len(self.running) < self.cfg.max_num_seqs:
            if self._admit_check(head)[0]:
                return False  # ordinary admission handles it
        return self._park_candidate(self._rank_for(head)) is not None

    def _shed_expired(self) -> None:
        """Deadline shed: a batch-class request that has waited past
        `batch_deadline_s` without ever being admitted is shed (the
        queued-with-a-deadline half of the admission policy — never
        accepted-then-starved).  Parked sequences and sequences that
        already produced tokens are exempt: the client has state."""
        deadline = self.cfg.batch_deadline_s
        if deadline <= 0 or not self.waiting:
            return
        now = time.monotonic()
        for seq in list(self.waiting):
            if (seq.priority == "batch" and not seq.parked
                    and not seq.output_tokens and seq.t_seen is not None
                    and now - seq.t_seen > deadline):
                self.waiting.remove(seq)
                seq.status = "finished"
                seq.finish_reason = "shed"
                self.shed_total += 1
                self.shed.append(seq)
                if self.events is not None:
                    self.events.record(
                        "shed", rid=seq.request_id,
                        waited_s=round(now - seq.t_seen, 3),
                    )

    @affine("step", "loop")
    def drain_shed(self) -> List[Sequence]:
        out, self.shed = self.shed, []
        return out

    def _preempt(self, seq: Sequence) -> None:
        logger.info("preempting %s", seq.request_id)
        self.pool.free(seq.pages)
        self._release_state(seq)
        seq.pages = []
        seq.num_cached = seq.kv_cached = 0
        seq.num_computed = 0
        seq.committed_pages = 0
        seq.block_hashes = seq.block_hashes[:0]
        seq.status = "waiting"
        seq.preemptions += 1
        if seq in self.running:
            self.running.remove(seq)
        self._enqueue(seq, front=True)

    # -- completion ---------------------------------------------------------- #

    @affine("step", "loop")
    def commit_full_pages(self, seq: Sequence,
                          upto_tokens: Optional[int] = None) -> None:
        """Register newly-filled pages in the prefix cache (emits KV
        events).  `upto_tokens` bounds it to what a fetched step wrote:
        `num_computed` may already count the sequence's next chunk, which
        is dispatched and not fetched."""
        if not self.cfg.enable_prefix_caching:
            return
        ps = self.cfg.page_size
        computed = seq.num_computed
        if upto_tokens is not None:
            computed = min(computed, upto_tokens)
        full = computed // ps
        if full <= seq.committed_pages:
            return
        tokens = seq.all_tokens()
        # extend the hash chain incrementally (O(new blocks), not O(n^2))
        while len(seq.block_hashes) < full:
            i = len(seq.block_hashes)
            parent = (
                seq.block_hashes[-1]
                if seq.block_hashes
                else chain_seed(self.cfg.block_hash_salt + seq.cache_salt)
            )
            seq.block_hashes.append(
                next_block_hash(parent, tokens[i * ps : (i + 1) * ps])
            )
        for i in range(seq.committed_pages, full):
            parent = seq.block_hashes[i - 1] if i > 0 else None
            self.pool.commit(seq.pages[i], seq.block_hashes[i], parent)
        seq.committed_pages = full

    @affine("step", "loop")
    def check_stop(self, seq: Sequence, eos_token_ids: Seq[int]) -> Optional[str]:
        out = seq.output_tokens
        if not seq.opts.ignore_eos and out and out[-1] in eos_token_ids:
            return "stop"
        if out and out[-1] in seq.opts.stop_token_ids:
            return "stop"
        for stop in seq.opts.stop_sequences:
            if stop and out[-len(stop):] == stop:
                return "stop"
        if len(out) >= seq.opts.max_tokens:
            return "length"
        if seq.total_len >= self.cfg.max_model_len:
            return "length"
        return None

    def _finish(self, seq: Sequence, reason: str) -> None:
        seq.status = "finished"
        seq.finish_reason = reason
        self._release_state(seq)
        if not seq.hold_pages:
            if self.deferred_free is not None:
                self.deferred_free.extend(seq.pages)
            else:
                self.pool.free(seq.pages)
            seq.pages = []
        if seq in self.running:
            self.running.remove(seq)

    @affine("step", "loop")
    def finish(self, seq: Sequence, reason: str) -> None:
        self.commit_full_pages(seq)
        self._finish(seq, reason)
