"""The layer loop of a model whose layers have several SHAPES (`ModelConfig.
layer_kinds`: laguna, lfm2_moe).  A layer is `llama._layer_prefill`'s (GQA
attention, then a feed-forward, each around a plain residual) or, under the
layer type "conv" (lfm2_moe), a gated short convolution in attention's place
(`_short_conv`: described after the loop, below); what differs by layer:

  the attention kind (`layer_types`): "full_attention" layers have 48 query
    heads, see every earlier key and rotate the FIRST HALF of each head with
    yarn's table; "sliding_attention" layers have 64, see the last
    `sliding_window` keys and rotate the whole head with a plain table
    (Laguna-XS.2's numbers; `rope_parameters` has a rope for each kind and
    `layer_heads` each layer's heads).  Both leave [n_kv, head_dim] keys and
    values a token: ONE page pool, one `CacheSpec`.
  the feed-forward kind (`mlp_layer_types`): a dense SwiGLU, or the experts
    behind a softmax router beside one shared expert.

Two shapes of `wq` / `wo` cannot share a stack, so the params hold one stack
a KIND (`stack_of`: "full_dense_layers", "sliding_layers", "full_layers",
...), each the layers of its kind in model order at their own widths: no
head the config lacks is stored or computed.  The loop (`layers`) walks the
published order in a BOUNDED number of traced bodies whatever the depth
(`plan`): the order is cut into runs of one kind, and a stretch of runs that
repeats (F, S S S, F, S S S, ...) is ONE `lax.scan` over its periods whose
body holds one layer body a run (a run of several layers an inner scan).
Laguna-XS.2's 40 layers are [dense F] + 9 x [S S S, F] + [S S S]: four
bodies.  The kind is static in a body, so the window and the rope table are
too: no operand selects between two rotations.  No `lax.cond` anywhere, and a
layer's matrices are indexed out of the WHOLE stacks (closed over, as
`models/hybrid.py` does) by the loops' counters, so the product a matrix
feeds reads it in place (PERF.md finding 29).

A "conv" layer (lfm2_moe: `ModelConfig.short_conv_kernel`) keeps no keys and
has no heads: `[B, C, u] = in_proj(n)`, a causal depthwise convolution of K
taps over `B * u` with neither bias nor activation, `out_proj(C * conv)`.
What a sequence leaves it is the convolution's last K - 1 inputs, a window
[K - 1, hidden] and nothing recurrent: it lives in the state SLOTS beside
the pages (`llama.StateCache` with `ssm` None, `ModelConfig.state_spec`), a
row's slots ride in the last `hybrid.STATE_COLS` columns of its page table
as nemotron_h's do, and the window is handed out inside a chunk at the same
token counts (`hybrid._inside`), so snapshots, hash addresses and the tail
row are the scheduler's as they are.  The page pool holds the attention
layers alone and the slot pool the conv layers alone: a layer's row in
either is its rank among the layers of its mixer (`_rows`).

Every flat step kind rides this one loop: a prefill chunk, the verify step
(every position's logits; not with conv layers, whose window cannot be
rolled back) and a decode step (a chunk of one: `llama.forward_decode`).
The decode block over gathered pages, the embedding forward and every layout
with a layer body of its own refuse the family by name (`llama.
require_one_layer_shape`).

WHAT THE PUBLISHED CONFIG LEAVES UNSAID, and the reading taken (each one
line here and one keyword of `benchmark/reference/laguna.py`):
  1. `gating: true` is a sigmoid gate a HEAD on attention's output, from the
     layer's normed input (`llama._head_gate`; the sibling Laguna-S-2.1
     spells it "per-head", and only that count gives 33.4 B parameters).
  2. The router scores by softmax over the logits, the top 8 renormalised,
     times `moe_routed_scaling_factor` (`llama._route`).
  3. The shared expert is added as it is, without a gate of its own.
  4. Queries and keys are not normalised.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import rms_norm, rope_by_kind, ssm, write_kv_layers
from .config import ModelConfig
from .hybrid import (_as_tiles, _inside, read_window, split_table,
                     write_states)
from .llama import (KVCache, Params, _feed_forward, _layer_prefill,
                    _valid_rows, merge_moe_stats, moe_stats_columns)
from .quantization import matmul_any


def stack_of(kind: tuple) -> str:
    """The params' key of a kind's stack: kind = (layer type, feed-forward
    type, query heads); one head count a layer type (`_laguna_fields`)."""
    layer_type, mlp, _ = kind
    return (layer_type.split("_")[0] + ("_dense" if mlp == "dense" else "")
            + "_layers")


def stacks_of(cfg: ModelConfig) -> dict:
    """{stack: (kind, its layers' indices in the model)}, in order of first
    appearance: what `init_params` and the loader build."""
    out = {}
    for l, kind in enumerate(cfg.layer_kinds):
        out.setdefault(stack_of(kind), (kind, []))[1].append(l)
    return out


class Run(NamedTuple):
    """`count` consecutive layers of one kind inside a period."""

    kind: tuple
    count: int
    first: int  # index in its stack of the run's first layer, period 0
    stride: int  # layers of its stack a period
    offset: int  # the run's first layer within the period


class Segment(NamedTuple):
    """`periods` repeats of the runs, from model layer `first_layer` on."""

    periods: int
    runs: Tuple[Run, ...]
    first_layer: int

    @property
    def period_len(self) -> int:
        return sum(r.count for r in self.runs)


def plan(kinds: tuple) -> Tuple[Segment, ...]:
    """The layers' kinds in model order -> the segments the loop walks.
    Runs of one kind; then, from the front, the stretch of runs that repeats
    most becomes one segment of that many periods (a run that repeats
    nothing is a segment of its own)."""
    rle = []
    for kind in kinds:
        if rle and rle[-1][0] == kind:
            rle[-1][1] += 1
        else:
            rle.append([kind, 1])
    rle = [tuple(r) for r in rle]
    seen, layer, segments, i = {}, 0, [], 0
    while i < len(rle):
        p, n = 1, 1
        for width in range(2, (len(rle) - i) // 2 + 1):
            reps = 1
            while rle[i + reps * width:i + (reps + 1) * width] == (
                    rle[i:i + width]):
                reps += 1
            if reps > 1 and reps * width > n * p:
                p, n = width, reps
        unit = rle[i:i + p]
        per_period = {}
        for kind, count in unit:
            per_period[kind] = per_period.get(kind, 0) + count
        runs, offset, inside = [], 0, {}
        for kind, count in unit:
            runs.append(Run(kind, count, seen.get(kind, 0)
                            + inside.get(kind, 0), per_period[kind], offset))
            inside[kind] = inside.get(kind, 0) + count
            offset += count
        segments.append(Segment(n, tuple(runs), layer))
        for kind, count in per_period.items():
            seen[kind] = seen.get(kind, 0) + n * count
        layer += n * offset
        i += n * p
    return tuple(segments)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random init (tests): one stack a kind, each at its own widths."""
    h, hd, f = cfg.hidden_size, cfg.head_dim_, cfg.intermediate_size
    kvw = cfg.num_key_value_heads * hd
    E, fm, fs = (cfg.num_experts, cfg.moe_intermediate_size,
                 cfg.shared_expert_width)
    ks = iter(jax.random.split(key, 64))

    def w(*shape, scale=None):
        scale = scale or shape[-2] ** -0.5
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    params = {"embed": w(cfg.vocab_size, h, scale=0.02),
              "final_norm": jnp.ones((h,), dtype)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(h, cfg.vocab_size)
    for stack, ((kind, mlp, nh), ids) in stacks_of(cfg).items():
        n = len(ids)
        layer = {"attn_norm": jnp.ones((n, h), dtype),
                 "mlp_norm": jnp.ones((n, h), dtype)}
        if kind == "conv":  # `attn_norm` is the mixer's norm
            layer.update({"in_proj": w(n, h, 3 * h),
                          "conv_w": w(n, cfg.short_conv_kernel, h, scale=0.5),
                          "out_proj": w(n, h, h)})
        else:
            layer.update({"wq": w(n, h, nh * hd), "wk": w(n, h, kvw),
                          "wv": w(n, h, kvw), "wo": w(n, nh * hd, h)})
        if cfg.qk_norm and kind != "conv":  # weights a test can tell apart
            layer.update({"q_head_norm": 1.0 + w(n, hd, scale=0.2),
                          "k_head_norm": 1.0 + w(n, hd, scale=0.2)})
        if cfg.attention_gate:
            layer["w_head_gate"] = w(n, h, nh)
        if mlp == "dense":
            layer.update({"w_gate": w(n, h, f), "w_up": w(n, h, f),
                          "w_down": w(n, f, h)})
        else:
            layer.update({
                "router": w(n, h, cfg.router_width),
                "w_gate": w(n, E, h, fm), "w_up": w(n, E, h, fm),
                "w_down": w(n, E, fm, h)})
            if cfg.moe_scoring == "sigmoid":  # the choosing bias
                layer["router_bias"] = 0.02 * jax.random.normal(
                    next(ks), (n, cfg.router_width), jnp.float32)
            if cfg.n_shared_experts:
                layer.update({"ws_gate": w(n, h, fs), "ws_up": w(n, h, fs),
                              "ws_down": w(n, fs, h)})
        params[stack] = layer
    return params


def _rows(cfg: ModelConfig):
    """(row in the page pool, row in the slot pool) of each layer, as two
    int32 tables [L]: a layer's rank among the layers of its mixer.  (None,
    None) where every layer has pages and none a state: a layer's row is its
    index."""
    if not cfg.conv_layers:
        return None, None
    conv = np.asarray([k[0] == "conv" for k in cfg.layer_kinds])
    return (np.cumsum(~conv, dtype=np.int32) - 1,
            np.cumsum(conv, dtype=np.int32) - 1)


def _row(table, layer):
    """`table[layer]` for a layer index that is a loop's counter or a
    number; `layer` itself without a table."""
    if table is None:
        return layer
    return int(table[layer]) if isinstance(layer, int) else jnp.asarray(
        table)[layer]


def _short_conv(lp: Params, u: jax.Array, window: jax.Array,
                chunk_lens: jax.Array, at: tuple):
    """The gated short convolution over u [B, S, h] (normed) from a row's
    carried `window` [B, K-1, h] (the inputs `B * u` of the K-1 positions
    before the chunk) -> (out [B, S, h], window', [window after each of
    `at`'s token counts]).  Positions at or past `chunk_lens` move no
    window."""
    dt = u.dtype
    with jax.named_scope("sconv.in_proj"):
        b, c, g = jnp.split(matmul_any(
            u, lp["in_proj"], "bsh,hd->bsd").astype(dt), 3, axis=-1)
    with jax.named_scope("sconv.conv"):
        y, window, inside = ssm.conv(b * g, window, lp["conv_w"], None,
                                     chunk_lens, at, act=None)
        y = c * y
    with jax.named_scope("sconv.out_proj"):
        return (matmul_any(y, lp["out_proj"], "bsd,dh->bsh").astype(dt),
                window, inside)


def layers(params: Params, cfg: ModelConfig, kv: KVCache, x: jax.Array,
           positions: jax.Array, page_table: jax.Array,
           prefix_lens: jax.Array, chunk_lens: jax.Array,
           attn_impl: str = "xla", moe_stats: bool = False):
    """Every layer over an embedded chunk x [B, S, h], as `llama.
    prefill_layers`: -> (x, kv, *stats).  The pools stay where they are: the
    bodies read them by (row, page) and (row, slot), and ONE scatter lands
    every attention layer's keys and values after the loop, one every conv
    layer's windows.  With conv layers `page_table` carries the rows' state
    slots (`hybrid.split_table`).  A decode step is a chunk of one."""
    ropes = rope_by_kind(cfg.head_dim_, cfg.rope_parameters)
    B, S, _ = x.shape
    spec = cfg.state_spec
    kv_rows, state_rows = _rows(cfg)
    table, at = page_table, ()
    if spec is not None:
        table, slot_in, slot_out, slot_inside = split_table(page_table)
        at = _inside(cfg, S, kv.page_size)

    def put(buf, a, row):
        return jax.lax.dynamic_update_index_in_dim(buf, a, row, 0)

    def conv_layer(lp, h, row, stack, idx):
        """A conv layer: h + short_conv(norm(h)), then the feed-forward;
        -> (h, the window at the chunk's end and inside it as the pool
        stores them, *stats)."""
        with jax.named_scope("state.read"):
            win = read_window(kv, spec, row, slot_in, slot_in == 0)
        out, win, inside = _short_conv(
            lp, rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps), win,
            chunk_lens, at)
        h = h + out
        y, *st = _feed_forward(lp, h, h, cfg, chunk_lens, moe_stats,
                               (stack, idx))
        return h + y, tuple(_as_tiles(w, spec.window_dims)
                            for w in (win, *inside)), st

    def one(carry, run, idx, layer):
        """Layer `layer` of the model, `idx` in its kind's stack: the
        residual moves on, and what the layer leaves (the chunk's keys and
        values or its windows, its moe stats) lands in the layer's row of
        the carry's buffers."""
        h, pages, windows, stats = carry
        stack = params[stack_of(run.kind)]
        lp = jax.tree.map(lambda a: a[idx], stack)
        if run.kind[0] == "conv":
            row = _row(state_rows, layer)
            h, wins, st = conv_layer(lp, h, row, stack, idx)
            windows = tuple(put(buf, w, row) for buf, w in zip(windows, wins))
        else:
            row = _row(kv_rows, layer)
            windowed = "sliding" in run.kind[0]
            inv_freq, amplitude = ropes[run.kind[0]]
            h, (k, v, *st) = _layer_prefill(
                lp, kv, row, h, positions, table, prefix_lens, chunk_lens,
                cfg, inv_freq, attn_impl,
                window=cfg.sliding_window if windowed else None,
                rope_scale=amplitude, moe_stats=moe_stats,
                stacks=(stack, idx))
            # (as the pool stores a token: `CacheSpec.plane_dims`)
            pages = tuple(put(buf, a.reshape(buf.shape[1:]), row)
                          for buf, a in zip(pages, (k, v)))
        if moe_stats:
            stats = put(stats, st[0], layer)
        return h, pages, windows, stats

    def period(carry, seg, t):
        """Period `t` of a segment: one body a run, a run of several layers
        an inner scan."""
        for run in seg.runs:
            idx = run.first + t * run.stride
            layer = seg.first_layer + t * seg.period_len + run.offset
            if run.count == 1:
                carry = one(carry, run, idx, layer)
            else:
                carry, _ = jax.lax.scan(
                    lambda c, j, run=run, idx=idx, layer=layer: (
                        one(c, run, idx + j, layer + j), None),
                    carry, jnp.arange(run.count, dtype=jnp.int32))
        return carry

    # [rows, ...] buffers in the carry, a row a layer of the pool they land
    # in: each layer's rows are written where they belong by the loops'
    # counters (ys joined from runs and periods of different lengths took
    # reshapes and concatenates, and a step of several rows then failed a
    # check of the TPU compiler: AOT for a v5e, PR 52)
    L = cfg.num_hidden_layers
    carry = (
        x,
        tuple(jnp.zeros((cfg.num_kv_layers, B, S, *pool.shape[3:]), x.dtype)
              for pool in (kv.k, kv.v)),
        tuple(jnp.zeros((spec.layers, B, *spec.window_dims), kv.conv.dtype)
              for _ in (None, *at)) if spec is not None else (),
        jnp.zeros((L, moe_stats_columns(cfg)), jnp.int32) if moe_stats
        else None)
    for seg in plan(cfg.layer_kinds):
        if seg.periods == 1:
            carry = period(carry, seg, 0)
        else:
            carry, _ = jax.lax.scan(
                lambda c, t, seg=seg: (period(c, seg, t), None), carry,
                jnp.arange(seg.periods, dtype=jnp.int32))
    x, (k_new, v_new), windows, stats = carry
    pages = write_kv_layers(kv.k, kv.v, k_new, v_new, table, prefix_lens,
                            _valid_rows(x, chunk_lens))
    if spec is None:
        kv = KVCache(*pages)
    else:
        win, *inside = windows
        conv, _ = write_states(kv, (win, None, *((w, None) for w in inside)),
                               slot_out, slot_inside)
        kv = kv._replace(k=pages[0], v=pages[1], conv=conv)
    return (x, kv, merge_moe_stats(stats)) if moe_stats else (x, kv)
