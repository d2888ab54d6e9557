"""The traced bodies of the engine's device steps: ONE per step kind,
layout-free.

A body is a plain function of device operands (forward → sample →
logprobs → pack); `engine/layout.py` shards it and wraps it into the jitted
program a serving layout runs, and `engine/engine.py` builds the host
arrays and reads the packs back (`_unpack_*`).  Optional trailing operands
(the mrope `rope_off`, the prefill `mm` triple) are `*rest`, and the penalty
`counts` is one positional operand that is None on an unpenalized variant
(an empty pytree: no argument of the compiled program).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import xla_ledger
from ..models import ModelConfig, forward_decode
from ..ops import apply_penalties, compute_logprobs, top_logprobs
from ..ops.sampling import sample_tokens_maybe_greedy

# static top-k width for OpenAI `top_logprobs` responses (API max is 20)
TOPLP = 20

# materialized-KV HBM cap for the decode BLOCK path (plain and
# continuous scans read the SAME constant, so the block/per-step
# crossover can never drift between them; module-level so tests can
# force the per-step fallback): kg+vg live across the whole step scan
# (~2*L*B*S*nkv*hd bytes) — past ~2GB the per-step path's
# layer-at-a-time gathers are the safer footprint
_BLOCK_KV_BYTE_BUDGET = 2 << 30


def _decode_path(attn_impl: str, kv, page_table, n_steps: int,
                 latent: bool = False, own_loop: bool = False) -> str:
    """"block" (`decode_block_scan`: one pool gather per block) or
    "per-step" (a scan of `forward_decode`) for one traced decode step,
    noted in the compile ledger with the reason — the Pallas decode kernel
    reads pages itself and needs the per-step write-first layout, a
    block whose gathered KV would pass the byte budget stays per-step, and
    so does a model with state slots beside its pages (the block's layer
    body carries no recurrent state) and one with a layer loop of its own
    (`own_loop`: layers of two shapes, which the block's one scan cannot
    walk)."""
    from ..ops.paged_attention import LATENT_DECODE_XLA, _adapt

    if own_loop:
        xla_ledger.note_path_choice(
            "decode_step", "per-step", "head counts by layer: the one layer "
            "loop walks both shapes", batch=page_table.shape[0],
            n_steps=n_steps)
        return "per-step"

    if hasattr(kv, "ssm"):
        xla_ledger.note_path_choice(
            "decode_step", "per-step", "state slots beside the pages: the "
            "one layer loop carries the recurrent state",
            batch=page_table.shape[0], n_steps=n_steps)
        return "per-step"

    # both planes' rows (a latent pool's differ in width)
    blk_bytes = sum(p.shape[0] * page_table.shape[0]
                    * page_table.shape[1] * p.shape[2]
                    * p.shape[3] * p.shape[4] * p.dtype.itemsize
                    for p in (kv.k, kv.v))
    if _adapt(attn_impl, page_table, kv.k.shape[2],
              only_xla=LATENT_DECODE_XLA if latent else "") == "pallas":
        path, why = "per-step", "pallas decode kernel (write-first layout)"
    elif blk_bytes > _BLOCK_KV_BYTE_BUDGET:
        path, why = "per-step", (
            f"block KV {blk_bytes} B > {_BLOCK_KV_BYTE_BUDGET} B budget")
    else:
        path, why = "block", (
            f"block KV {blk_bytes} B <= {_BLOCK_KV_BYTE_BUDGET} B budget")
    xla_ledger.note_path_choice(
        "decode_step", path, why, batch=page_table.shape[0],
        n_steps=n_steps,
        table_tokens=page_table.shape[1] * kv.k.shape[2])
    return path


def decode_name(n_steps: int) -> str:
    """Program name of a decode dispatch: a scan of several steps is a
    block."""
    return "decode_step" if n_steps == 1 else "decode_block"


# -- the packs: one int32 array a step, one fetch --------------------------- #

@jax.named_scope("pack")
def _pack(*parts: jax.Array) -> jax.Array:
    """Concatenate int32 ids and float32 logprobs into ONE int32 array
    along the last axis (floats ride as their bit patterns).  Integers and
    not floats on purpose: an id below 2^23 viewed as float32 is a denormal
    and the TPU flushes denormals to zero, so a float32 pack delivered
    every token id as 0 on the chip (PERF.md finding 1)."""
    return jnp.concatenate(
        [p if p.dtype == jnp.int32
         else jax.lax.bitcast_convert_type(p, jnp.int32) for p in parts],
        axis=-1)


def _as_f32(bits: np.ndarray) -> np.ndarray:
    """Host-side inverse of `_pack` for a float32 column range."""
    return np.ascontiguousarray(bits).view(np.float32)


def carries_moe_stats(cfg: ModelConfig) -> bool:
    """The steps that run the prefill layer path (prefill, the prefill
    side of a mixed step, speculative verify) of an expert model append
    `models.llama.moe_step_stats` (assignments, experts touched summed
    over layers, largest per-expert row count) to the int32 pack they
    already return: no second fetch.  The capacity dispatch routes per
    group and carries none."""
    return cfg.is_moe and cfg.moe_impl != "capacity"


def _pack_out(out: jax.Array, logp: jax.Array, logits=None,
              moe=None) -> jax.Array:
    """Pack sampled tokens (int32) + logprobs (float32) — plus top-TOPLP
    (ids, logprobs) when `logits` is given — into ONE int32 array along
    the last axis (`_pack`): every host fetch is a device→host sync with a
    fixed cost whatever its size, so results come back in a single transfer.

    Layout: [tok(B) | logp(B) | top_ids(B*TOPLP) | top_lps(B*TOPLP)
    | moe(`models.llama.moe_stats_width`)], the last only from an expert
    model's step.
    """
    parts = _out_parts(out, logp, logits)
    if moe is not None:
        parts.append(moe)
    return _pack(*parts)


def _out_parts(out: jax.Array, logp: jax.Array, logits=None) -> list:
    """What a sampled step packs for its rows, in `_pack_out`'s order."""
    parts = [out, logp]
    if logits is not None:
        ids, lps = top_logprobs(logits, TOPLP)  # [B, TOPLP] each
        parts += [ids.reshape(-1), lps.reshape(-1)]
    return parts


def _unpack_out(packed: np.ndarray, b: int, with_top: bool = False):
    """Inverse of `_pack_out`; returns (toks, logp, top_ids, top_lps)."""
    toks = packed[..., :b]
    logp = _as_f32(packed[..., b : 2 * b])
    if not with_top:
        return toks, logp, None, None
    ids = packed[..., 2 * b : 2 * b + b * TOPLP]
    lps = _as_f32(packed[..., 2 * b + b * TOPLP : 2 * b + 2 * b * TOPLP])
    return (
        toks, logp,
        ids.reshape(*packed.shape[:-1], b, TOPLP),
        lps.reshape(*packed.shape[:-1], b, TOPLP),
    )


def _pack_out_cc(out: jax.Array, logp: jax.Array, act: jax.Array,
                 logits=None) -> jax.Array:
    """`_pack_out` plus the device-resident loop's per-row EMITTED flag
    (1 where the row was still active when this step sampled): the
    drained buffer is then self-describing — the host learns each row's
    real token count and stop position from the flags instead of
    re-running per-token stop checks.

    Layout: [tok(B) | logp(B) | act(B) | top_ids(B*TOPLP) | top_lps]."""
    parts = [out, logp, act.astype(jnp.int32)]
    if logits is not None:
        ids, lps = top_logprobs(logits, TOPLP)
        parts += [ids.reshape(-1), lps.reshape(-1)]
    return _pack(*parts)


def _unpack_out_cc(packed: np.ndarray, b: int, with_top: bool = False):
    """Inverse of `_pack_out_cc`; returns (toks, logp, flags, top_ids,
    top_lps) — `flags` is a bool emitted-mask aligned with toks."""
    toks = packed[..., :b]
    logp = _as_f32(packed[..., b : 2 * b])
    flags = packed[..., 2 * b : 3 * b] > 0
    if not with_top:
        return toks, logp, flags, None, None
    ids = packed[..., 3 * b : 3 * b + b * TOPLP]
    lps = _as_f32(packed[..., 3 * b + b * TOPLP :])
    return (
        toks, logp, flags,
        ids.reshape(*packed.shape[:-1], b, TOPLP),
        lps.reshape(*packed.shape[:-1], b, TOPLP),
    )


def _unpack_spec(packed: np.ndarray, b: int, s: int):
    """Inverse of the spec verify step's packing: (tokens [B, S] int32,
    logprobs [B, S] float32, accepted draft count [B] int32)."""
    n = b * s
    toks = packed[:n].reshape(b, s)
    logp = _as_f32(packed[n:2 * n]).reshape(b, s)
    n_acc = packed[2 * n:2 * n + b]
    return toks, logp, n_acc


# -- prefill ----------------------------------------------------------------- #

def prefill_body(cfg: ModelConfig, forward, *, with_top: bool = False,
                 greedy: bool = False, moe_stats: bool = False,
                 with_mm: bool = False, tail=None):
    """The ONE prefill step: forward → sample → logprobs → pack.

    `forward(params, cfg, kv, tokens, page_table, prefix_lens, chunk_lens,
    **kw) -> (logits, kv, *moe)` is the layout's: `forward_prefill` (flat,
    partitioned pool), `forward_prefill_pp`, `forward_prefill_sp`.  After
    `counters` comes `samples`, then the optional operands: the `mm` triple
    (vision embeds, their mask and, on an mrope model, the (t, h, w)
    streams) and ONE trailing operand the layout names (`tail`: sp's
    "prefix_table" or "owner"), each passed to `forward` under its keyword.

    `samples` ([B] bool: the rows whose chunk ends a prompt) goes to the
    forward together with the sampling below, and `forward_prefill` runs
    the output head and them under one conditional on "any row samples": a
    step in which none does packs zeros where its rows' results were.
    None (where `Layout.heads_by_rows` is false: pp, sp, lockstep) means
    every row samples: the head is unconditional and the forward sees
    neither."""

    def body(params, kv, tokens, page_table, prefix_lens, chunk_lens, samp,
             seeds, counters, samples=None, *rest):
        def sample(logits):
            out = sample_tokens_maybe_greedy(logits, samp, seeds, counters,
                                             greedy)
            logp = compute_logprobs(logits, out)
            return _out_parts(out, logp, logits if with_top else None)

        kw = {}
        if tail is not None:
            kw[tail], rest = rest[-1], rest[:-1]
        if with_mm:
            kw.update(extra_embeds=rest[0], extra_mask=rest[1],
                      mm_positions=rest[2] if len(rest) > 2 else None)
        if moe_stats:
            kw["moe_stats"] = True
        if samples is not None:
            kw.update(samples=samples, then=sample)
        parts, kv, *moe = forward(
            params, cfg, kv, tokens, page_table, prefix_lens, chunk_lens,
            **kw)
        if samples is None:
            parts = sample(parts)  # the forward gave logits
        # `out` rides back as a separate device int32 so a fused decode
        # chain can consume it without waiting for the packed host fetch
        return _pack(*parts, *moe), parts[0], kv

    return body


# -- decode ------------------------------------------------------------------ #

def decode_body(cfg: ModelConfig, n_steps: int, max_valid_pos: int,
                penalized: bool, with_top: bool, attn_impl: str,
                greedy: bool = False):
    """Decode `n_steps` tokens per dispatch: lax.scan keeps the whole block
    on-device, so host→device latency is paid once per block, not per
    token (the TPU analog of multi-step scheduling).  Also the decode side
    of the mixed step.

    Steps whose position reaches `max_valid_pos` (the model window) write
    to the trash page instead of clamping into a real page — those tokens
    are discarded host-side anyway.

    Returns (packed [T, ...], tok, pos, ctr, counts, kv): the carries come
    back so a chained dispatch can consume block k's device-side outputs
    directly — introducing any fresh host buffer between chained dispatches
    serializes the pipeline on the host→device transfer.  `penalized`
    threads the [B, V] output-token counts through the scan for frequency/
    presence penalties (None in and out otherwise); `with_top` packs
    top-TOPLP logprobs per step.

    On the xla/deferred path the whole block runs through
    `decode_block_scan` (models/llama.py): the pool gathers ONCE per
    block, in-block tokens ride ring buffers, and one batched scatter
    lands the block's KV — per-step paged gathers were ~1.2ms/step of
    scattered-DMA at 1B/batch-8 (r5 ablations).  The Pallas long-context
    path keeps the per-step layout (the kernel reads pages directly)."""
    from ..models.llama import decode_block_scan

    def sample_tail(logits, cts, samp, seeds, ctr):
        """ONE sampling tail for both the per-step and block paths:
        penalties → sample → counts update → logprobs → pack."""
        if penalized:
            logits = apply_penalties(
                logits, cts, samp.frequency_penalty, samp.presence_penalty)
        out = sample_tokens_maybe_greedy(logits, samp, seeds, ctr, greedy)
        if penalized:
            cts = cts.at[jnp.arange(out.shape[0]), out].add(1.0)
        logp = compute_logprobs(logits, out)
        packed = _pack_out(out, logp, logits if with_top else None)
        return out, cts, packed

    def block_scan(params, kv, tokens, positions, counters, counts,
                   page_table, samp, seeds, rope_off):
        def sample_step(eng, logits, tok_prev, t):
            ctr, cts = eng
            out, cts, packed = sample_tail(logits, cts, samp, seeds, ctr)
            return (ctr + 1, cts), out, packed

        cts0 = counts if penalized else jnp.zeros((), jnp.float32)
        (ctr, cts), packed, tok, pos, kv = decode_block_scan(
            params, cfg, kv, tokens, positions, page_table, n_steps,
            max_valid_pos, sample_step, (counters, cts0),
            rope_offset=rope_off,
        )
        return packed, tok, pos, ctr, cts if penalized else None, kv

    def scan(params, kv, tokens, positions, counters, counts, page_table,
             samp, seeds, *rope):
        rope_off = rope[0] if rope else None
        if not penalized:
            counts = None
        if _decode_path(attn_impl, kv, page_table, n_steps, cfg.is_latent,
                        cfg.layer_kinds is not None) == "block":
            return block_scan(params, kv, tokens, positions, counters,
                              counts, page_table, samp, seeds, rope_off)

        def body(carry, _):
            kv, tok, pos, ctr, cts = carry
            ok = pos < max_valid_pos
            safe_pos = jnp.where(ok, pos, 0)
            # out-of-window rows use an all-trash table row
            table = jnp.where(ok[:, None], page_table, 0)
            logits, kv = forward_decode(
                params, cfg, kv, tok, safe_pos, table, attn_impl=attn_impl,
                rope_offset=rope_off,
            )
            out, cts, packed = sample_tail(logits, cts, samp, seeds, ctr)
            return (kv, out, pos + 1, ctr + 1, cts), packed

        (kv, tok, pos, ctr, cts), packed = jax.lax.scan(
            body, (kv, tokens, positions, counters, counts),
            None, length=n_steps,
        )
        return packed, tok, pos, ctr, cts, kv

    return scan


def decode_body_pp(forward, n_steps: int, with_top: bool):
    """`decode_body` for pipeline stages: `forward` is the layout's
    `forward_decode_pp` (the ring schedule that keeps the pipeline full,
    penalty histograms threaded through its last stage) with everything but
    the operands bound; the per-step rows are packed here in the
    `_unpack_out` layout ([T, 2B], or [T, B*(2+2*TOPLP)] with
    top-logprobs)."""

    def body(params, kv, tokens, positions, counters, counts, page_table,
             samp, seeds):
        toks, logp, tops, counts, kv = forward(
            params, kv=kv, tokens=tokens, positions=positions,
            page_table=page_table, samp=samp, seeds=seeds,
            counters=counters, counts=counts,
            top_k=TOPLP if with_top else 0)
        parts = [toks, logp]
        if tops is not None:
            ids, lps = tops  # [T, B, TOPLP] each
            T = ids.shape[0]
            parts += [ids.reshape(T, -1), lps.reshape(T, -1)]
        return (_pack(*parts), toks[-1], positions + n_steps,
                counters + n_steps, counts, kv)

    return body


def decode_body_cc(cfg: ModelConfig, n_steps: int, max_valid_pos: int,
                   penalized: bool, with_top: bool, attn_impl: str,
                   greedy: bool = False):
    """The device-resident decode-block body (`decode_body` with
    ON-DEVICE stop detection): an active-row mask rides the scan carry —
    each step a row emits only while active, and the mask latches off at
    the first stop/eos-token hit or when its token budget (max-token +
    model-window headroom, computed host-side) runs out.  Frozen rows
    stop advancing their position and PRNG counter, write KV only to the
    trash page, and stay inert for every later block of an open-ended
    chain, so their pool pages may be freed as soon as the stop drains.

    Extra operands vs the plain scan: `act [B]` bool (active at block
    start), `budget [B]` int32 (tokens the row may still emit), `stops
    [B, K]` int32 (-1-padded per-row stop/eos ids).  The packed output
    carries the per-step emitted flags (`_pack_out_cc`); the carries
    (tok, pos, ctr, act, budget, counts) all return as device arrays so
    block k+1 consumes block k's outputs with zero host round-trip.

    CHUNK ROWS (docs/device_loop.md "chunk rows"): prefill chunks ride
    the same block as extra operands — `chunk_toks [B, T]` (prompt
    tokens to feed, row-major from the row's resume point), `chunk_rem
    [B]` (how many of them this block feeds; 0 = pure decode row) and
    `chunk_samples [B]` (True when the last fed token completes the
    prompt, so that step samples the first output).  While a row feeds
    it is ACTIVE (KV written, position advancing) but emits nothing:
    its PRNG counter, penalty counts and budget are untouched, so the
    sampled stream is token-identical to a split prefill+decode.  A row
    whose chunk runs out mid-prompt goes dormant until the next block's
    operands feed it again.  `reset [B]` + `init_pos [B]` +
    `init_budget [B]` splice a NEW request into a slot in-step (a
    `jnp.where` overlay on the carried pos/ctr/counts/budget), so
    admission rides the SAME compiled program — zero steady-state
    compiles.  Within a block, active steps stay a contiguous prefix
    per row (dormancy only at chunk end, revival only in the prologue),
    which is what keeps `decode_block_scan`'s uniform KV scatter and
    ring-attention masks exact.

    DRIFT TRIPWIRE: this deliberately forks `decode_body`'s
    sample tail / per-step body / block-path gate (the mask threading
    touches every line, and the meshed variants must stay untouched) —
    any fix to the plain scan (penalty order, the blk_bytes HBM budget,
    the pallas `_adapt` gate) MUST be mirrored here, and vice versa; the
    continuous-vs-per-step equivalence matrix in tests/test_engine.py +
    tests/test_block_ladder.py is what catches a drift."""
    from ..models.llama import decode_block_scan

    def sample_tail(logits, cts, samp, seeds, ctr, act, budget, stops,
                    cidx, chunk_toks, chunk_rem, chunk_samples):
        """Sample + freeze + feed: counters/penalty counts/budget
        advance only for rows that EMIT this step (active decode rows,
        plus a chunk row's prompt-completing step); feeding steps
        discard the sample and load the next prompt token instead.  The
        returned mask governs the NEXT step."""
        if penalized:
            logits = apply_penalties(
                logits, cts, samp.frequency_penalty, samp.presence_penalty)
        out = sample_tokens_maybe_greedy(logits, samp, seeds, ctr, greedy)
        feeding = cidx < chunk_rem
        completing = feeding & (cidx + 1 == chunk_rem) & chunk_samples
        emit = act & (~feeding | completing)
        emitf = emit.astype(jnp.float32)
        ctr = ctr + emit.astype(ctr.dtype)
        if penalized:
            cts = cts.at[jnp.arange(out.shape[0]), out].add(emitf)
        logp = compute_logprobs(logits, out)
        packed = _pack_out_cc(out, logp, emit,
                              logits if with_top else None)
        hit = (out[:, None] == stops).any(axis=-1)
        budget = budget - emit.astype(budget.dtype)
        cidx_next = cidx + feeding.astype(cidx.dtype)
        tok_next = jnp.where(
            cidx_next < chunk_rem,
            jnp.take_along_axis(
                chunk_toks,
                jnp.clip(cidx_next, 0, chunk_toks.shape[1] - 1)[:, None],
                axis=1)[:, 0],
            out)
        # emitting rows follow the stop/budget latch; feeding rows stay
        # active while prompt tokens remain this block, then go dormant
        # until the next block's operands feed them again
        act_next = jnp.where(emit, act & ~hit & (budget > 0),
                             act & (cidx_next < chunk_rem))
        return tok_next, ctr, cts, packed, act_next, budget, cidx_next

    def block_scan(params, kv, tokens, positions, counters, counts, act,
                   budget, stops, page_table, samp, seeds, chunk_toks,
                   chunk_rem, chunk_samples, rope_off):
        def sample_step(eng, logits, tok_prev, t, act_in):
            ctr, cts, bud, cidx, _ = eng
            tok_next, ctr, cts, packed, act_next, bud, cidx = sample_tail(
                logits, cts, samp, seeds, ctr, act_in, bud, stops,
                cidx, chunk_toks, chunk_rem, chunk_samples)
            # act duplicated into the engine carry so the final mask
            # returns as a chainable device array
            return (ctr, cts, bud, cidx, act_next), tok_next, packed, act_next

        cts0 = counts if penalized else jnp.zeros((), jnp.float32)
        cidx0 = jnp.zeros_like(chunk_rem)
        (ctr, cts, bud, _, act_out), packed, tok, pos, kv = decode_block_scan(
            params, cfg, kv, tokens, positions, page_table, n_steps,
            max_valid_pos, sample_step, (counters, cts0, budget, cidx0, act),
            rope_offset=rope_off, active_init=act,
        )
        return (packed, tok, pos, ctr, act_out, bud,
                cts if penalized else None, kv)

    def scan(params, kv, tokens, positions, counters, counts, act, budget,
             stops, page_table, samp, seeds, chunk_toks, chunk_rem,
             chunk_samples, reset, init_pos, init_budget, *rope):
        rope_off = rope[0] if rope else None
        # splice/chunk prologue: spliced rows reset their carried
        # pos/ctr/counts/budget in-step (a jnp.where overlay, so
        # admission rides the SAME compiled program), and rows with
        # prompt tokens to feed this block load their first chunk token
        # and (re)activate.  Runs before the block/per-step fork so both
        # paths see identical row state.
        positions = jnp.where(reset, init_pos, positions)
        counters = jnp.where(reset, 0, counters)
        budget = jnp.where(reset, init_budget, budget)
        if penalized:
            counts = jnp.where(reset[:, None], 0.0, counts)
        act = act | (chunk_rem > 0)
        tokens = jnp.where(chunk_rem > 0, chunk_toks[:, 0], tokens)

        if _decode_path(attn_impl, kv, page_table, n_steps, cfg.is_latent,
                        cfg.layer_kinds is not None) == "block":
            return block_scan(params, kv, tokens, positions, counters,
                              counts, act, budget, stops, page_table,
                              samp, seeds, chunk_toks, chunk_rem,
                              chunk_samples, rope_off)

        def body(carry, _):
            kv, tok, pos, ctr, cts, a, bud, cidx = carry
            ok = (pos < max_valid_pos) & a
            safe_pos = jnp.where(pos < max_valid_pos, pos, 0)
            # frozen and out-of-window rows write through an all-trash table
            table = jnp.where(ok[:, None], page_table, 0)
            logits, kv = forward_decode(
                params, cfg, kv, tok, safe_pos, table, attn_impl=attn_impl,
                rope_offset=rope_off,
            )
            tok_next, ctr, cts, packed, a_next, bud, cidx = sample_tail(
                logits, cts, samp, seeds, ctr, a, bud, stops, cidx,
                chunk_toks, chunk_rem, chunk_samples)
            return (kv, tok_next, pos + a.astype(pos.dtype), ctr, cts,
                    a_next, bud, cidx), packed

        cts0 = counts if penalized else jnp.zeros((), jnp.float32)
        cidx0 = jnp.zeros_like(chunk_rem)
        (kv, tok, pos, ctr, cts, act, budget, _), packed = jax.lax.scan(
            body, (kv, tokens, positions, counters, cts0, act, budget,
                   cidx0),
            None, length=n_steps,
        )
        return (packed, tok, pos, ctr, act, budget,
                cts if penalized else None, kv)

    return scan


# -- speculative verify ------------------------------------------------------ #

def verify_body(cfg: ModelConfig, *, greedy: bool = False,
                attn_impl: str = "xla", moe_stats: bool = False):
    """Fused draft-verify decode step (self-speculative decoding): one
    forward scores k+1 positions — the last accepted token plus k
    host-drafted tokens — through the PREFILL layer path
    (`forward_verify`), then an on-device verify tail samples every
    position from its own (seed, counter) PRNG stream and counts the
    accepted draft prefix.  One weight read buys up to k+1 tokens.

    KV pages for all k+1 positions are written; rejected positions are
    logically rolled back by position masking (never attended,
    overwritten as decode advances) — the same trash-page/table
    discipline every other step relies on.  Packed result:
    [tok(B*(k+1)) | logp(B*(k+1)) | n_accepted(B)] in one fetch."""
    from ..models import forward_verify
    from ..ops.sampling import sample_tokens_block, speculative_accept

    def body(params, kv, tokens, positions, page_table, samp, seeds,
             counters, *rope):
        B, S = tokens.shape  # S == k + 1
        logits, kv, *moe = forward_verify(
            params, cfg, kv, tokens, page_table, positions,
            jnp.full((B,), S, jnp.int32), attn_impl=attn_impl,
            rope_offset=rope[0] if rope else None, moe_stats=moe_stats,
        )  # [B, S, V]
        out, logp = sample_tokens_block(logits, samp, seeds, counters,
                                        greedy)
        n_acc = speculative_accept(out, tokens)
        packed = _pack(out.reshape(-1), logp.reshape(-1), n_acc, *moe)
        return packed, kv

    return body


# -- mixed ------------------------------------------------------------------- #

def mixed_body(cfg: ModelConfig, forward, n_steps: int, max_valid_pos: int,
               penalized: bool, with_top: bool, attn_impl: str,
               greedy: bool = False, moe_stats: bool = False):
    """One dispatch = one bounded prefill chunk + one decode block
    (chunked-prefill interleave, the TPU form: both forwards live in one
    XLA program, so running decodes pay zero extra host round-trips for
    a concurrent prompt's prefill — reference behavior: vLLM mixed
    batches / mocker watermark scheduler, scheduler.rs:240).  The prefill
    side runs first (its page writes are disjoint from the decode rows'),
    then the decode scan; both packed outputs return in one fetch."""
    # the scheduler excludes mm-carrying sequences from mixed plans, so
    # the prefill side ropes text-style (no mm operands) even on mrope
    # models; the decode side still needs each row's delta (`*d_rope`)
    prefill = prefill_body(cfg, forward, with_top=with_top, greedy=greedy,
                           moe_stats=moe_stats)
    decode = decode_body(cfg, n_steps, max_valid_pos, penalized, with_top,
                         attn_impl, greedy)

    def body(params, kv, p_tokens, p_table, p_prefix, p_chunk, p_samp,
             p_seeds, p_ctr, p_samples, d_tokens, d_pos, d_ctr, d_counts,
             d_table, d_samp, d_seeds, *d_rope):
        p_packed, _, kv = prefill(params, kv, p_tokens, p_table, p_prefix,
                                  p_chunk, p_samp, p_seeds, p_ctr, p_samples)
        d_packed, *_, kv = decode(params, kv, d_tokens, d_pos, d_ctr,
                                  d_counts, d_table, d_samp, d_seeds,
                                  *d_rope)
        return p_packed, d_packed, kv

    return body


# -- KV pages in and out (disaggregation, offload, parking) ------------------ #

def gather_pages(own=None):
    """Export: pages [N] int32 → (k, v) [L, N, page, n_kv, hd] (a latent
    pool's planes: the shared rotary key, the latent).  On a partitioned pool the ids are LOCAL to ONE rank, a trailing `rank`
    operand names it, and the layout's `own(x, rank)` keeps the owner's
    gather and drops every other shard's."""

    def body(kv, pages, *rank):
        blobs = tuple(plane[:, pages] for plane in (kv.k, kv.v))
        if own is not None:
            blobs = tuple(own(b, *rank) for b in blobs)
        return blobs

    return body


def set_pages(mine=None):
    """Import: write (k, v) blobs into the given pages (padding rows point
    at trash page 0 — harmless overwrite).  On a partitioned pool the
    layout's `mine(blob, plane, pages, rank)` gives what each shard writes:
    the blob on the owning rank, the pages' current values elsewhere."""

    def body(kv, k_blob, v_blob, pages, *rank):
        def put(plane, blob):
            if mine is not None:
                blob = mine(blob, plane, pages, *rank)
            return plane.at[:, pages].set(blob)

        return kv._replace(k=put(kv.k, k_blob), v=put(kv.v, v_blob))

    return body
