"""`Layout` — the one owner of "where things live".

Whether the engine is flat on one chip, GSPMD over dp×tp, a partitioned
pool (`kv_partition`: pages sharded over the mesh's (dp, sp) shards),
pipeline stages (pp), a sequence-parallel ring (sp) or a multihost lockstep
group is decided ONCE, in `Layout.resolve`, which also returns the
`EngineConfig` the engine will really run.  From then on the layout places
parameters, the KV pool and every host array, wraps the layout-free bodies
of `steps.py` into the jitted programs (`wrap`), caches them per variant,
and answers the engine's questions by name (`holds_step_in_flight`,
`runs_continuous`, `prefill_blocks`, ...) so that `engine.py` never asks
which mesh it is on.  A new layout kind, or a new operand on a step, is an
edit here and in `steps.py` only.

`..parallel` is imported where a meshed layout needs it, never at module
level: a flat engine imports nothing of it.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis import xla_ledger
from ..models import KVCache, ModelConfig, forward_prefill, kv_cache_pspec
from ..models.llama import (require_no_state, require_one_layer_shape,
                            require_plain_cache)
from ..ops import SamplingParams
from ..ops.paged_attention import resolve_attention_impl
from . import steps
from .config import EngineConfig, bucket_for
from .page_pool import PagePool, StatePool

# jax.jit with compile attribution (analysis/xla_ledger.py): every jit
# cache miss in the engine lands in the ledger as (fn, signature, rung)
_ljit = xla_ledger.ledgered_jit


def _round_buckets(buckets, to: int) -> List[int]:
    return sorted({-(-b // to) * to for b in buckets})


class Layout:
    def __init__(self, model_cfg: ModelConfig, cfg: EngineConfig, *,
                 mesh=None, parallel=None, device=None,
                 multihost: bool = False):
        self.model_cfg = model_cfg
        self.cfg = cfg
        # -- serving mesh (M3): params TP-sharded, KV sharded on kv-heads,
        # batch sharded over dp.  XLA/GSPMD inserts the ICI collectives
        # (the TPU-native replacement for the reference's engine-delegated
        # `--tp/--dp` flags, SURVEY.md §2.6).
        self.mesh = mesh
        self.dp, self.sp, self.pp = (
            (parallel.dp, parallel.sp, parallel.pp) if mesh is not None
            else (1, 1, 1))
        # a flat engine given a device lives on it: parameters, the KV
        # pool and every host→device put are committed there (replicas of
        # one process each take their own chip — worker --dp-ranks)
        self.device = device
        # multihost lockstep: rank 0 leads and every dispatch goes out on
        # the plan channel first, the others replay (follower_loop)
        self.lockstep = multihost
        self.is_leader = jax.process_index() == 0
        # may a dispatched prefill step wait for its fetch while the next
        # one is planned?  Every single-process engine (flat, pooled, sp,
        # pp): one process issues every program, so dispatch order is the
        # devices' order.  Not a multihost leader: its next plan reaches the
        # followers through `broadcast_plan`, a device collective that
        # queues behind the program in flight on every rank, so the leader
        # would wait out the step inside the broadcast and the followers
        # (which replay host arrays in order and never fetch) would gain
        # nothing; a recover must also find leader and followers agreeing on
        # which steps ran
        self.holds_step_in_flight = not multihost
        # does a prefill step (and a mixed step's prefill side) take which
        # of its rows sample as an operand, and run the output head only
        # where one does (`steps.prefill_body`)?  Where the forward is
        # `forward_prefill` and the engine's own arrays reach the program:
        # the pp and sp forwards keep an unconditional head, and a lockstep
        # follower replays a step from the plan's host arrays, which do not
        # carry the rows
        self.heads_by_rows = self.pp == 1 and self.sp == 1 and not multihost
        # kv_partition: pool pages sharded over the mesh's (dp, sp)
        # shards — capacity scales with the mesh (engine.page_pool
        # ShardedPagePool); steps run manual-over-(dp,sp) via shard_map
        self.pooled = bool(cfg.kv_partition) and self.mesh is not None
        self.pool_axes = ("dp", "sp") if self.sp > 1 else ("dp",)
        self.pool_ranks = self.dp * self.sp if self.pooled else 1
        self.attn_impl = resolve_attention_impl(
            cfg.attention_impl, meshed=self.mesh is not None)
        if (model_cfg.is_latent and self.attn_impl != "xla"
                and jax.default_backend() != "tpu"):
            # a latent model's PREFILL has a kernel over its pages
            # (`ops.pallas_latent_attention`), chosen a trace by shapes on
            # a single-device TPU engine; it is a TPU program, so an engine
            # elsewhere keeps XLA's form whatever was asked for.  Its
            # decode steps and blocks are XLA's everywhere
            # (`ops.paged_attention.LATENT_DECODE_XLA`, noted a trace)
            self.attn_impl = "xla"
            xla_ledger.note_path_choice(
                "attention_impl", "xla",
                f"latent pages: backend is {jax.default_backend()!r}, the "
                "kernel over them is a TPU program",
                requested=cfg.attention_impl)
        # trailing columns of a row's page table that carry its state slots
        # (`models.hybrid.STATE_COLS`); 0 for a model without state-space
        # layers
        self.state_cols = 0
        if model_cfg.state_spec is not None:
            from ..models.hybrid import STATE_COLS

            self.state_cols = STATE_COLS
        # what every program of this layout closes over besides its own
        # variant key: the program store's half of a program's identity
        # (`wrap`), the call's signature being the other
        self._described = (
            model_cfg, cfg, parallel, self.attn_impl, multihost,
            jax.process_index(), jax.process_count(),
            None if mesh is None else (
                mesh.axis_names, mesh.devices.shape,
                [d.id for d in mesh.devices.flat]))
        self._prefill_steps: Dict[tuple, Callable] = {}
        self._decode_steps: Dict[tuple, Callable] = {}
        self._mixed_steps: Dict[tuple, Callable] = {}
        self._kv_fns: Dict[tuple, Callable] = {}
        self._embed_fn = None

    # -- the decision -------------------------------------------------------- #

    @classmethod
    def resolve(cls, model_cfg: ModelConfig,
                engine_cfg: Optional[EngineConfig] = None, parallel=None,
                devices=None, multihost: Optional[bool] = None,
                vision=None) -> Tuple["Layout", EngineConfig]:
        """The layout of an engine and the `EngineConfig` it will really
        run: what the layout cannot run is refused here, and what it runs
        differently (buckets rounded to the mesh, the fused and mixed fast
        paths where the dispatch shape is not the flat one) is rewritten
        here and nowhere else.  `multihost` overrides the process-count
        detection (a process-local auxiliary engine inside a multihost job
        passes False and pins its devices)."""
        cfg = engine_cfg or EngineConfig()
        if multihost is None:
            multihost = jax.process_count() > 1
        meshed = parallel is not None and parallel.world > 1
        if meshed or multihost:
            # short prefill chunks that are ready at one plan share a step
            # (`Scheduler._plan_prefill`) on the flat single-process
            # engine, where whole steps were timed.  GSPMD, pooled, pp, sp
            # and multihost layouts keep one sequence a step until someone
            # times them
            cfg = dataclasses.replace(cfg, prefill_batch_size=1)
        if multihost and not meshed:
            raise ValueError(
                "multihost requires a ParallelConfig spanning the global "
                "device set (dp*tp*sp == jax.device_count())"
            )
        mesh = device = None
        for what, on in (("a serving mesh (--tp/--dp/--sp/--pp)", meshed),
                         ("kv_partition", cfg.kv_partition),
                         ("fuse_projections", cfg.fuse_projections),
                         ("int8 quantization",
                          cfg.quantization == "int8")):
            if on:
                require_plain_cache(model_cfg, what)
                require_one_layer_shape(model_cfg, what)
        continuous = ("--decode-continuous (the device-resident decode "
                      "loop)")
        for what, on in (("--speculative-ngram-k (the draft-verify step)",
                          cfg.speculative_ngram_k),
                         (continuous, cfg.decode_continuous)):
            if on:
                require_no_state(model_cfg, what)
        if cfg.decode_continuous:  # its decode block scans ONE layer stack
            require_one_layer_shape(model_cfg, continuous)
        if model_cfg.state_spec is not None:
            from ..models.hybrid import snapshot_tokens

            every = snapshot_tokens(model_cfg)
            if every % cfg.page_size or cfg.max_prefill_tokens % every:
                raise ValueError(
                    f"page_size {cfg.page_size} must divide the state "
                    f"snapshot interval {every}, and that "
                    f"max_prefill_tokens {cfg.max_prefill_tokens} "
                    f"({model_cfg.model_type}: a snapshot is addressed by "
                    "the block hash of its position, and a chunk starts at "
                    "one)")
            if cfg.num_state_slots < 3:
                raise ValueError(
                    f"num_state_slots {cfg.num_state_slots}: a model with "
                    "state-space layers needs the trash slot, a running "
                    "sequence's and one to go on in past a snapshot")
        if meshed:
            from ..parallel import make_mesh

            mesh = make_mesh(parallel, devices)
            cfg = cls._resolve_meshed(model_cfg, cfg, parallel, vision)
        elif cfg.kv_partition:
            raise ValueError(
                "kv_partition requires a serving mesh (ParallelConfig "
                "with dp*sp > 1)"
            )
        elif devices is not None:
            device = list(devices)[0]
        layout = cls(model_cfg, cfg, mesh=mesh, parallel=parallel,
                     device=device, multihost=multihost)
        if cfg.fuse_projections and meshed:
            raise ValueError(
                "fuse_projections is single-device only (the fused "
                "output axis does not carry the megatron tp specs)"
            )
        # M-RoPE (qwen2_vl): decode ropes at slot + per-seq delta.  The
        # rope-offset operand threads through the fused, mixed, pooled
        # (kv_partition) and sp-ring step variants, so qwen2-vl serves on
        # meshed engines with mixed scheduling on.  pp stages don't carry
        # it yet.
        if model_cfg.mrope_section and layout.pp > 1:
            raise ValueError("mrope models do not serve under pp yet")
        return layout, cfg

    @staticmethod
    def _resolve_meshed(model_cfg, cfg, parallel, vision) -> EngineConfig:
        dp, sp, pp, tp = parallel.dp, parallel.sp, parallel.pp, parallel.tp
        if pp > 1 or sp > 1:
            from ..models.llama import require_flat_layer_scan

            require_flat_layer_scan(model_cfg, f"pp={pp}, sp={sp}")
        if pp > 1:
            if model_cfg.num_hidden_layers % pp:
                raise ValueError(
                    f"pp={pp} must divide num_hidden_layers="
                    f"{model_cfg.num_hidden_layers}"
                )
            if cfg.kv_partition and sp > 1:
                raise ValueError(
                    "pp×kv_partition partitions pages over dp only "
                    "(sp within a stage is future work)"
                )
            if vision is not None:
                raise ValueError(
                    "pp does not support the vision tower yet"
                )
            if tp > 1:
                bad = [k for k, v in {
                    "q heads": model_cfg.num_attention_heads,
                    "kv heads": model_cfg.cache_spec.heads,
                    "vocab_size": model_cfg.vocab_size,
                }.items() if v % tp]
                if bad:
                    raise ValueError(
                        f"tp={tp} must evenly divide "
                        f"{', '.join(bad)} for pp×tp serving"
                    )
            # decode microbatches the batch into pp groups, and the
            # fused/mixed fast paths assume the flat dispatch shape.
            # kv_partition buckets are PER-RANK (rows arrive as dp
            # blocks), so they round to pp only; global buckets round
            # to dp*pp
            cfg = dataclasses.replace(
                cfg,
                fuse_prefill_decode=False,
                mixed_prefill_tokens=0,
                decode_batch_buckets=_round_buckets(
                    cfg.decode_batch_buckets,
                    pp if cfg.kv_partition else dp * pp),
            )
        if sp > 1:
            # sp prefill is whole-remainder ring attention: no
            # chunking (mixed dispatches would chunk), buckets
            # divisible by sp.  Cached prefixes ARE supported (the
            # ring starts at the prefix boundary) — except with a
            # partitioned pool, whose prefix pages live on one
            # (dp, sp) shard only and cannot feed the other shards'
            # ring blocks
            cfg = dataclasses.replace(cfg, mixed_prefill_tokens=0)
            if cfg.enable_prefix_caching and cfg.kv_partition:
                raise ValueError(
                    "sp > 1 with kv_partition requires "
                    "enable_prefix_caching=False (prefix pages are "
                    "owner-shard-local)"
                )
            if cfg.max_prefill_tokens < cfg.max_model_len:
                raise ValueError(
                    "sp > 1 requires max_prefill_tokens >= max_model_len "
                    "— no prompt may be split into chunks"
                )
            bad = [b for b in cfg.chunk_buckets if b % sp]
            if bad:
                raise ValueError(
                    f"chunk buckets {bad} not divisible by sp={sp}"
                )
            if (tp > 1 and model_cfg.is_moe
                    and (model_cfg.moe_impl not in ("auto", "ragged", "a2a")
                         or model_cfg.num_experts % tp)):
                raise ValueError(
                    "sp×tp MoE requires moe_impl='auto'|'ragged'|'a2a' and "
                    "num_experts divisible by tp"
                )
            # moe_impl='a2a' composes with prefix caching: capacity
            # drops are per-token-per-peer (a pure function of the
            # token's own routing — parallel/wide_ep.py), so cached
            # KV is reproducible across batch compositions
            # the sp shard_map's param specs shard heads, the vocab,
            # and (dense models) the ffn dim over tp — catch uneven
            # splits here with a clear message instead of an opaque
            # shard_map shape error at first prefill.  MoE shards the
            # EXPERT dim instead (checked above), so its ffn width
            # need not divide
            uneven = {
                "q heads": model_cfg.num_attention_heads,
                "kv heads": model_cfg.cache_spec.heads,
                "vocab_size": model_cfg.vocab_size,
            }
            if not model_cfg.is_moe:
                uneven["intermediate_size"] = model_cfg.intermediate_size
            bad_dims = [k for k, v in uneven.items() if v % tp]
            if bad_dims:
                raise ValueError(
                    f"tp={tp} must evenly divide "
                    f"{', '.join(bad_dims)} for sp×tp prefill"
                )
        if cfg.kv_partition:
            # sharded pool: one partition per (dp, sp) shard; batches
            # are laid out as R uniform per-rank blocks (buckets stay
            # PER-RANK, so no dp-divisibility rounding).  The FUSED
            # fast path stays off (it reuses prefill rows as decode
            # rows, which only works on the identity layout) but
            # MIXED dispatches run: the pooled mixed step takes the
            # same per-rank block layouts both sides already use
            cfg = dataclasses.replace(cfg, fuse_prefill_decode=False)
            if max(cfg.decode_batch_buckets) < cfg.max_num_seqs:
                # bucket_for clamps to buckets[-1]: a per-rank decode
                # group wider than the largest bucket would break the
                # R-uniform-blocks layout and land rows on the wrong
                # pool shard — reject the config instead
                raise ValueError(
                    f"kv_partition requires max(decode_batch_buckets)"
                    f"={max(cfg.decode_batch_buckets)} >= "
                    f"max_num_seqs={cfg.max_num_seqs}"
                )
            return cfg
        # every batch shape must divide dp (rows beyond the real
        # batch are trash-page padding)
        return dataclasses.replace(
            cfg,
            decode_batch_buckets=_round_buckets(
                cfg.decode_batch_buckets, dp),
        )

    # -- what the engine asks by name ---------------------------------------- #

    @property
    def _plain(self) -> bool:
        """Rows in the identity order, sampled by the step's own program:
        flat on one chip, or GSPMD over dp×tp."""
        return self.pp == 1 and self.sp == 1 and not self.pooled

    @property
    def runs_continuous(self) -> bool:
        """The device-resident continuous decode loop: flat single-process
        engines only — the pooled/pp/sp step layouts and the multihost
        plan channel keep their chained paths (and stay token-identical —
        the loop is output-invisible)."""
        return self.mesh is None and not self.lockstep

    @property
    def runs_spec(self) -> bool:
        """The draft-verify step: partitioned/pp/sp pools keep their own
        step layouts."""
        return self._plain

    @property
    def carries_moe_stats(self) -> bool:
        """An expert model's prefill-path steps append their moe stats to
        the pack on flat and GSPMD engines (`steps.carries_moe_stats`);
        pp, sp and the partitioned pool carry none yet (ROADMAP D14)."""
        return steps.carries_moe_stats(self.model_cfg) and self._plain

    @property
    def prefill_blocks(self) -> int:
        """Per-rank blocks in a prefill step's pack (`_unpack_rows`): sp
        and pp sample at the jit level, so their pack is flat."""
        return self.pool_ranks if self.sp == 1 and self.pp == 1 else 1

    @property
    def decode_blocks(self) -> int:
        """pp packs [T, B] at the jit level (global row order), so its
        pack is flat even on a partitioned pool."""
        return self.pool_ranks if self.pp == 1 else 1

    @property
    def prefill_groups(self) -> int:
        """Row blocks of a partitioned pool's prefill batch."""
        return self.pool_ranks // self.sp

    def prefill_slot(self, kv_rank: int) -> Tuple[int, int]:
        """(row block, sp slot) of a prefill row on a partitioned pool:
        the sp ring shards ROWS over dp only (the sequence axis rides sp),
        so its rows group by dp shard and each row's sp slot goes in the
        per-row `owner` operand instead of the row layout."""
        return divmod(kv_rank, self.sp)

    @property
    def names_owner(self) -> bool:
        """Does a prefill step take the per-row `owner` operand?"""
        return self.pooled and self.sp > 1

    def pad_batch(self, n: int) -> int:
        """Round a batch size up to a dp multiple (pad rows hit the trash
        page)."""
        return -(-n // self.dp) * self.dp

    def broadcast(self, payload: bytes) -> bytes:
        """The lockstep plan channel: the leader's payload, on every
        rank."""
        from ..parallel.multihost import broadcast_plan

        return broadcast_plan(payload)

    # -- placement ----------------------------------------------------------- #

    def shard_params(self, params):
        if self.mesh is None:
            if self.device is not None:
                return jax.device_put(params, self.device)
            return params
        if self.pp > 1:
            from ..parallel.pp_engine import shard_params_pp

            return shard_params_pp(params, self.model_cfg, self.mesh)
        from ..parallel import shard_params

        return shard_params(params, self.model_cfg, self.mesh)

    def make_pool(self, event_sink):
        if self.pooled:
            from .page_pool import ShardedPagePool

            return ShardedPagePool(
                self.pool_ranks, self.cfg.num_pages, self.cfg.page_size,
                event_sink=event_sink,
            )
        return PagePool(
            self.cfg.num_pages, self.cfg.page_size, event_sink=event_sink
        )

    def make_state_pool(self) -> Optional[StatePool]:
        """The allocator of the state slots beside the pages; None for a
        model without state-space layers."""
        if self.model_cfg.state_spec is None:
            return None
        from ..models.hybrid import (SNAP_COLS, handout_every,
                                     snapshot_tokens)

        return StatePool(
            self.cfg.num_state_slots, snapshot_tokens(self.model_cfg),
            SNAP_COLS, every_of=lambda tokens: handout_every(
                self.model_cfg, tokens, self.cfg.page_size))

    @property
    def kv_pspec(self) -> KVCache:
        """Where the KV pool lives on the mesh."""
        if self.pp > 1:
            from ..parallel.pp_engine import kv_pspec_pp

            return kv_pspec_pp(pooled=self.pooled)
        return kv_cache_pspec(
            pool_axes=self.pool_axes if self.pooled else None)

    def make_kv(self, dtype) -> KVCache:
        # a pinned flat engine allocates its pool on its own device from
        # the start (never a transient copy on the default device)
        with (jax.default_device(self.device) if self.device is not None
              else contextlib.nullcontext()):
            kv = KVCache.create(
                self.model_cfg, self.pool_ranks * self.cfg.num_pages,
                self.cfg.page_size, dtype,
                state_slots=self.cfg.num_state_slots,
            )
        if self.mesh is None:
            if self.device is not None:
                kv = jax.device_put(kv, self.device)
            return kv
        if self.pp > 1:
            from ..parallel.multihost import host_array_to_global

            return jax.tree.map(
                lambda x, s: host_array_to_global(self.mesh, s, x),
                kv, self.kv_pspec,
            )
        from ..parallel import shard_kv_cache

        return shard_kv_cache(
            kv, self.mesh,
            pool_axes=self.pool_axes if self.pooled else None,
        )

    def _put(self, arr, *axes):
        """Host array → device under spec `axes`.  Multihost: every process
        passes the same logical array and contributes the shards its local
        devices own."""
        if self.mesh is None:
            if self.device is not None:
                return jax.device_put(arr, self.device)
            return jnp.asarray(arr)
        if self.lockstep:
            from ..parallel.multihost import host_array_to_global

            return host_array_to_global(self.mesh, P(*axes), np.asarray(arr))
        return jax.device_put(arr, NamedSharding(self.mesh, P(*axes)))

    def put(self, arr):
        """Host array → device, replicated."""
        return self._put(arr)

    def _bax(self, prefill: bool):
        # sp prefill shards batch ROWS over dp only (the sequence axis
        # rides sp), so pooled-sp prefill arrays must not demand a
        # (dp, sp)-divisible batch
        if self.pooled and self.sp > 1 and not prefill:
            return ("dp", "sp")
        return "dp"

    def put_rows(self, arr, prefill: bool = False):
        """Host array with one row per batch row → device, rows sharded
        over the layout's batch axis (`prefill`: a prefill step's)."""
        return self._put(arr, self._bax(prefill),
                         *[None] * (np.ndim(arr) - 1))

    def put_samp(self, samp: SamplingParams,
                 prefill: bool = False) -> SamplingParams:
        if self.mesh is None:
            return samp
        axes = self._bax(prefill)
        if self.lockstep:
            return jax.tree.map(lambda a: self._put(np.asarray(a), axes), samp)
        return jax.device_put(samp, NamedSharding(self.mesh, P(axes)))

    def prefill_tail(self, table: np.ndarray, prefix: np.ndarray,
                     owner: Optional[np.ndarray]) -> tuple:
        """The one trailing operand an sp prefill step takes after the
        `mm` triple: each row's sp slot on a partitioned pool, the cached
        prefix's pages otherwise."""
        if self.sp == 1:
            return ()
        if self.pooled:
            return (self._put(owner, "dp"),)
        # cached-prefix pages, width-bucketed to the batch's LONGEST
        # prefix (width 0 → the prefix path compiles out entirely)
        maxp = int(prefix.max()) if prefix.size else 0
        wp = (0 if maxp == 0 else bucket_for(
            -(-maxp // self.cfg.page_size),
            self.cfg.table_width_buckets,
        ))
        wp = min(wp, table.shape[1])
        return (self._put(np.ascontiguousarray(table[:, :wp]), "dp", None),)

    def foreign_blob_target(self, mine: set):
        """Where a KV blob that arrives on ANOTHER engine's devices is
        moved before an import: kv-heads sharded like the pool, so the
        cross-mesh copy moves 1/tp of the blob per device."""
        if self.mesh is None:
            return next(iter(mine))
        spec = (P(None, None, None, "tp", None)
                if "tp" in self.mesh.axis_names else P())
        return NamedSharding(self.mesh, spec)

    def import_blob(self, shape, dtype, rank: Optional[int], src_slice):
        """The sharded global import blob of a multihost import, built from
        per-device slices: `src_slice(lo, hi)` gives kv-heads [lo, hi) of
        the [L, width, page, kvh, hd] source, and is asked only for what
        this process's devices own (a non-owner host of a pooled rank
        asks for nothing)."""
        L, width, ps, kvh, hd = shape
        if self.pooled:
            gshape = (L, self.pool_ranks * width, ps, kvh, hd)
            spec = P(None, self.pool_axes, None, "tp", None)
        else:
            gshape = shape
            spec = P(None, None, None, "tp", None)
        sharding = NamedSharding(self.mesh, spec)
        arrays = []
        for dev, index in sharding.addressable_devices_indices_map(
                gshape).items():
            pg, hds = index[1], index[3]
            pg_lo = pg.start or 0
            pg_hi = gshape[1] if pg.stop is None else pg.stop
            h_lo = hds.start or 0
            h_hi = kvh if hds.stop is None else hds.stop
            if not self.pooled:
                data = src_slice(h_lo, h_hi)
            else:
                data = np.zeros((L, pg_hi - pg_lo, ps, h_hi - h_lo, hd),
                                dtype)
                blk_lo, blk_hi = rank * width, (rank + 1) * width
                if pg_lo <= blk_lo and pg_hi >= blk_hi:
                    data[:, blk_lo - pg_lo: blk_hi - pg_lo] = (
                        src_slice(h_lo, h_hi))
                elif not (pg_hi <= blk_lo or pg_lo >= blk_hi):
                    # non-owner shards keep their zeros, nothing fetched;
                    # shards are width-aligned by construction
                    raise AssertionError("unaligned pool shard")
            arrays.append(jax.device_put(data, dev))
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, arrays)

    # -- body → program ------------------------------------------------------ #

    def _rows(self, *trail) -> P:
        """Spec of a per-row operand of a step."""
        return P(self.pool_axes if self.pooled else "dp", *trail)

    @property
    def _kv_manual(self) -> KVCache:
        """The pool's spec inside `wrap`'s shard_map (manual axes only)."""
        s = (P("pp", "dp", None, None, None) if self.pp > 1
             else P(None, self.pool_axes, None, None, None))
        return KVCache(s, s)

    def _lockstep(self, lead: int, *mids, kv: bool = True) -> dict:
        """jit out_shardings for multihost lockstep ({} otherwise): the
        first `lead` outputs (the packs the leader must read) come back
        REPLICATED — cross-process shards are not addressable, so the
        leader could not read a dp-sharded result — the `mids` keep their
        stated specs, the trailing KV keeps its serving layout."""
        if not self.lockstep:
            return {}

        def shard(s):
            return jax.tree.map(lambda sp: NamedSharding(self.mesh, sp), s)

        rep = NamedSharding(self.mesh, P())
        return {"out_shardings": (
            *[rep] * lead, *[shard(s) for s in mids],
            *([shard(self.kv_pspec)] if kv else []))}

    def wrap(self, body, name: Optional[str] = None, *, variant: tuple,
             donate=(), tags: Optional[dict] = None, manual=None,
             **lockstep):
        """A body of `steps.py` → the jitted program of this layout.

        `variant` is the key this layout caches the program under, family
        first: with what the layout holds (`_described`) it is everything
        the body closes over, which is how the program store
        (`compile_cache.ProgramStore`) knows the program's lowered module
        again in a later process without tracing the body.

        `manual=(in_specs, out_specs)` (partitioned pool): the pool's page
        axis is sharded over the mesh's (dp, sp) shards and batches arrive
        as R contiguous per-rank row blocks with LOCAL page tables, so the
        body runs under a shard_map that is MANUAL over the pool axes and
        AUTO (GSPMD) over tp — every page gather/scatter stays device-local
        while tp keeps its megatron collectives (scaling-book layout;
        reference capability: engines shard KV over their ranks,
        disagg_serving.md:110).  `lockstep` is `_lockstep`'s."""
        if manual is not None:
            from ..parallel._compat import shard_map

            in_specs, out_specs = manual
            body = shard_map(
                body, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs,
                axis_names=({"pp", "dp"} if self.pp > 1
                            else set(self.pool_axes)),
            )
        return _ljit(body, name=name, donate_argnums=donate, tags=tags,
                     closes_over=(variant, manual, lockstep, self._described),
                     **lockstep)

    # -- the step programs, compiled lazily and cached per variant ----------- #

    def prefill_step(self, with_top: bool, with_mm: bool = False,
                     greedy: bool = False):
        key = (with_top, with_mm, greedy)
        if key not in self._prefill_steps:
            self._prefill_steps[key] = self._build_prefill(*key)
        return self._prefill_steps[key]

    def _forward_prefill(self):
        """`forward_prefill` as `steps.prefill_body` calls it."""
        return partial(forward_prefill, attn_impl=self.attn_impl)

    def _build_prefill(self, with_top, with_mm, greedy):
        name, tail, manual = "prefill_step", None, None
        if self.sp > 1:
            # sequence-parallel whole-prompt prefill
            # (parallel/sp_prefill.py): the prompt is sharded over the sp
            # axis and attention runs as ring attention; sampling happens
            # on the gathered last-position logits.  On a partitioned pool
            # the tables carry local ids and `owner` names each row's slot
            from ..parallel.sp_prefill import forward_prefill_sp

            def forward(params, cfg, kv, tokens, table, prefix, chunk, **kw):
                if self.pooled:
                    kw["pool_axes"] = self.pool_axes
                else:
                    kw["prefix_lens"] = prefix
                return forward_prefill_sp(params, cfg, kv, tokens, table,
                                          chunk, self.mesh, **kw)

            name += "_sp"
            tail = "owner" if self.pooled else "prefix_table"
            lock = self._lockstep(2)
        elif self.pp > 1:
            # the GPipe-staged pipeline (parallel/pp_engine.py); sampling
            # happens at the jit level on the replicated last-position
            # logits (dp-sharded when the pool is partitioned)
            from ..parallel.pp_engine import forward_prefill_pp

            forward = partial(forward_prefill_pp, mesh=self.mesh,
                              attn_impl=self.attn_impl, pooled=self.pooled)
            name += "_pp"
            lock = self._lockstep(2)
        elif self.pooled:
            # the packed result is 1-D PER SHARD ([tok|logp|...] over
            # local rows), so the global array is a concatenation of
            # per-rank blocks — the host unpacks with
            # `_unpack_rows(..., blocks=R)`
            forward = self._forward_prefill()
            bx, bx2 = self._rows(), self._rows(None)
            mm_specs = ()
            if with_mm:
                # vision embeds shard over the same per-rank batch blocks
                # as the tokens (vision × kv_partition); mrope's [B, 3,
                # chunk] rope streams ride as mm[2]
                mm_specs = (self._rows(None, None), bx2)
                if self.model_cfg.mrope_section:
                    mm_specs += (self._rows(None, None),)
            name += "_pooled"
            manual = ((P(), self._kv_manual, bx2, bx2, bx, bx, bx, bx, bx,
                       P(), *mm_specs), (bx, bx, self._kv_manual))
            lock = self._lockstep(1, bx)
        else:
            forward = self._forward_prefill()
            lock = self._lockstep(1, P())
        body = steps.prefill_body(
            self.model_cfg, forward, with_top=with_top, greedy=greedy,
            moe_stats=self.carries_moe_stats, with_mm=with_mm, tail=tail)
        return self.wrap(body, name, variant=("prefill", with_top, with_mm,
                                              greedy),
                         donate=(1,), manual=manual, **lock)

    def _decode_key(self, penalized, with_top, greedy, n_steps):
        return (penalized, with_top, greedy, n_steps or self.cfg.decode_steps)

    def decode_step(self, penalized: bool, with_top: bool,
                    greedy: bool = False, n_steps: Optional[int] = None):
        """The decode-block step for one (variant, n_steps) key.
        `n_steps` is the block-ladder rung (None → `decode_steps`): each
        rung is its own compiled program, cached alongside the variant
        flags, so the scheduler can switch block sizes per dispatch with
        zero retraces after warmup.  Called as (params, kv, tokens,
        positions, counters, counts, table, samp, seeds, *rope) →
        (packed, tok, pos, ctr, counts, kv), `counts` None in and out of
        an unpenalized variant."""
        key = self._decode_key(penalized, with_top, greedy, n_steps)
        if key not in self._decode_steps:
            self._decode_steps[key] = self._build_decode(*key)
        return self._decode_steps[key]

    def _build_decode(self, penalized, with_top, greedy, n_steps):
        cfg, cap = self.model_cfg, self.cfg.hard_cap
        name = steps.decode_name(n_steps)
        donate = (1, 5) if penalized else (1,)
        tags = {"rung": n_steps}
        variant = ("decode", penalized, with_top, greedy, n_steps)
        if self.pp > 1:
            from ..parallel.pp_engine import forward_decode_pp

            body = steps.decode_body_pp(partial(
                forward_decode_pp, cfg=cfg, n_steps=n_steps,
                max_valid_pos=cap, mesh=self.mesh, attn_impl=self.attn_impl,
                pooled=self.pooled, greedy=greedy), n_steps, with_top)
            return self.wrap(body, name + "_pp", variant=variant,
                             donate=donate, tags=tags, **self._lockstep(5))
        body = steps.decode_body(cfg, n_steps, cap, penalized, with_top,
                                 self.attn_impl, greedy)
        bx, bx2 = self._rows(), self._rows(None)
        # an unpenalized variant's counts are None: any spec is a prefix
        # of the empty tree
        cts = bx2 if penalized else P()
        if not self.pooled:
            return self.wrap(body, name, variant=variant, donate=donate,
                             tags=tags, **self._lockstep(1, bx, bx, bx, cts))
        # per-step packed results are 1-D per shard → [T, R * local] global
        outs = (P(None, self.pool_axes), bx, bx, bx, cts)
        rope = (bx,) if cfg.mrope_section else ()  # +rope_off (qwen2_vl)
        return self.wrap(
            body, name + "_pooled", variant=variant, donate=donate, tags=tags,
            manual=((P(), self._kv_manual, bx, bx, bx, cts, bx2, bx, bx,
                     *rope), (*outs, self._kv_manual)),
            **self._lockstep(1, *outs[1:]))

    def spec_step(self, greedy: bool = False):
        """The draft-verify decode variant, cached beside the plain
        variants under a `spec` key (one compile per greedy flag; jit
        shape-caches the batch/table buckets)."""
        key = ("spec", greedy)
        if key not in self._decode_steps:
            self._decode_steps[key] = self.wrap(
                steps.verify_body(
                    self.model_cfg, greedy=greedy, attn_impl=self.attn_impl,
                    moe_stats=self.carries_moe_stats),
                "verify_step", variant=key, donate=(1,), **self._lockstep(1))
        return self._decode_steps[key]

    def cc_step(self, penalized: bool, with_top: bool, greedy: bool = False,
                n_steps: Optional[int] = None):
        """The continuous-chain decode variant (`runs_continuous` gates
        dispatch), cached beside the plain rung programs under a "cc" key:
        one compiled program per (penalized, with_top, greedy, rung) like
        the plain variants, with the stop mask / budget carries riding as
        device arrays so an open-ended chain never rebuilds host inputs."""
        key = ("cc", *self._decode_key(penalized, with_top, greedy, n_steps))
        if key not in self._decode_steps:
            self._decode_steps[key] = self.wrap(
                steps.decode_body_cc(
                    self.model_cfg, key[4], self.cfg.hard_cap, penalized,
                    with_top, self.attn_impl, greedy),
                "decode_block_cc", variant=key,
                donate=(1, 5) if penalized else (1,), tags={"rung": key[4]})
        return self._decode_steps[key]

    def mixed_step(self, penalized: bool, with_top: bool,
                   greedy: bool = False, n_steps: Optional[int] = None):
        key = self._decode_key(penalized, with_top, greedy, n_steps)
        if key not in self._mixed_steps:
            self._mixed_steps[key] = self._build_mixed(*key)
        return self._mixed_steps[key]

    def _build_mixed(self, penalized, with_top, greedy, n_steps):
        """Over a PARTITIONED pool the whole program runs
        manual-over-(dp, sp) — both sides' batches arrive as R uniform
        per-rank row blocks with LOCAL page tables, so every page
        gather/scatter stays on the shard owning the row's pages while tp
        stays auto/GSPMD.  This is what lets the north-star decode
        topology (dp×tp, kv_partition) keep its ITL flat under concurrent
        prefills instead of falling back to prefill-stalls-decode."""
        body = steps.mixed_body(
            self.model_cfg, self._forward_prefill(), n_steps,
            self.cfg.hard_cap, penalized, with_top, self.attn_impl, greedy,
            moe_stats=self.carries_moe_stats)
        name, manual, lock = "mixed_step", None, self._lockstep(1, P())
        if self.pooled:
            name += "_pooled"
            bx, bx2 = self._rows(), self._rows(None)
            rope = (bx,) if self.model_cfg.mrope_section else ()
            manual = ((P(), self._kv_manual,
                       bx2, bx2, bx, bx, bx, bx, bx, P(),
                       bx, bx, bx, bx2 if penalized else P(), bx2, bx, bx,
                       *rope),
                      (bx, P(None, self.pool_axes), self._kv_manual))
            lock = self._lockstep(2)
        return self.wrap(body, name, variant=("mixed", penalized, with_top,
                                              greedy, n_steps),
                         donate=(1,), tags={"rung": n_steps}, manual=manual,
                         **lock)

    @property
    def compiled_variants(self) -> Dict[str, List]:
        """The compiled step-variant cache keys per step family.  Prefill
        keys are (with_top, with_mm, greedy); decode/mixed keys are
        (penalized, with_top, greedy, n_steps) — plus ("spec", greedy) for
        the draft-verify variant and ("cc", ...) for the continuous one."""
        return {
            "prefill": sorted(self._prefill_steps, key=repr),
            "decode": sorted(self._decode_steps, key=repr),
            "mixed": sorted(self._mixed_steps, key=repr),
        }

    @property
    def compiled_decode_rungs(self) -> set:
        """Block-ladder rungs with a compiled decode OR mixed program."""
        return {
            k[3] for k in (*self._decode_steps, *self._mixed_steps)
            if isinstance(k, tuple) and len(k) == 4
        }

    def embed_step(self):
        """The cache-free embedding forward (multihost gathers the result
        to every process)."""
        if self._embed_fn is None:
            from ..models.llama import forward_embed

            cfg = self.model_cfg
            kw = ({"out_shardings": NamedSharding(self.mesh, P())}
                  if self.lockstep else {})
            self._embed_fn = self.wrap(
                lambda p, tok, ln: forward_embed(p, cfg, tok, ln),
                variant=("embed",), **kw)
        return self._embed_fn

    # -- KV pages in and out ------------------------------------------------- #

    def _pool_index(self):
        """This shard's pool rank, inside `wrap`'s shard_map."""
        idx = jax.lax.axis_index(self.pool_axes[0])
        for ax in self.pool_axes[1:]:
            idx = idx * self.mesh.shape[ax] + jax.lax.axis_index(ax)
        return idx

    def _own(self, x, rank):
        """Every shard gathered its local candidates: the owner's survive
        a mask + psum, and the result is replicated over the pool axes
        (still tp-sharded on kv-heads).  Under pp the owner's gathers are
        stage-local layer SLICES: an all_gather over pp stitches them back
        into the full-layer blobs every consumer (disagg transfer, KVBM
        host pool) expects."""
        x = jax.lax.psum(jnp.where(self._pool_index() == rank, x, 0),
                         self.pool_axes)
        if self.pp > 1:
            x = jax.lax.all_gather(x, "pp", axis=0, tiled=True)
        return x

    def _mine(self, blob, plane, pages, rank):
        """Only the owning rank's pages change; other ranks rewrite their
        current values (padding rows hit each rank's local trash page 0).
        Under pp each stage slices its layer range out of the blob."""
        if self.pp > 1:
            n = plane.shape[0]
            blob = jax.lax.dynamic_slice_in_dim(
                blob, jax.lax.axis_index("pp") * n, n, 0)
        return jnp.where(self._pool_index() == rank,
                         blob.astype(plane.dtype), plane[:, pages])

    @property
    def export_fn(self):
        """(kv, pages[, rank]) → (k, v) [L, N, page, n_kv, hd].  A
        partitioned pool exports LOCAL page ids from ONE pool rank.
        Multihost lockstep gathers the result to every process (tp too) —
        the leader could not read an export whose shards live on other
        hosts."""
        if "export" not in self._kv_fns:
            manual = None
            if self.pooled:
                manual = ((self._kv_manual, P(), P()), (P(), P()))
            self._kv_fns["export"] = self.wrap(
                steps.gather_pages(self._own if self.pooled else None),
                variant=("export",), manual=manual,
                **self._lockstep(2, kv=False))
        return self._kv_fns["export"]

    def import_fn(self, sharded_blob: bool = False):
        """(kv, k_blob, v_blob, pages[, rank]) → kv.  `sharded_blob` takes
        the blob's page axis SHARDED over the pool axes (global [L,
        R*width, ...], real data only in the owner rank's block) — the
        multihost per-shard-fetch layout where non-owner hosts contribute
        zeros they never fetched; the default replicated layout serves
        single-process imports."""
        key = ("import", sharded_blob)
        if key not in self._kv_fns:
            manual = None
            if self.pooled:
                blob = (P(None, self.pool_axes, None, None, None)
                        if sharded_blob else P())
                manual = ((self._kv_manual, blob, blob, P(), P()),
                          self._kv_manual)
            self._kv_fns[key] = self.wrap(
                steps.set_pages(self._mine if self.pooled else None),
                variant=key, donate=(0,), manual=manual)
        return self._kv_fns[key]
