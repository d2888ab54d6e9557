"""Pallas TPU kernel for an expert layer's rows sorted by expert: a grouped
matmul that reads the layer's expert stacks where they lie.

Why a kernel: `jax.lax.ragged_dot` (and megablox's `gmm`) take a layer's
expert matrices [E, h, f] as materialised operands, and inside a layer loop
a layer's slice of the layer-stacked [L, E, h, f] is no buffer of its own, so
XLA copies all three stacks out every layer, 2.5 times the weights' own read
before a token is multiplied (PERF.md, findings 11 and 32).  Here the weight
operands are the WHOLE stacks, viewed [L x E, h, f] (a free reshape) and left
in HBM; the layer's index is a prefetched scalar and the block index map adds
`layer x E` to the expert.  No [E, h, f] slice is ever an operand.

Layout.  The rows are the step's assignments (token, expert) sorted by
expert, expert e's `sizes[e]` rows after expert e - 1's; rows past
`sizes.sum()` belong to no expert (an expert held on another rank of the
layer's share).  A VISIT is one (row tile, expert) pair that holds rows: an
expert nobody chose has no visit and its weights are never read, and a tile
that several experts share is visited once by each, which keeps only its own
rows (megablox's scheme); a tile past the last group has no visit at all, so
a share's rows of experts held elsewhere cost nothing.  The grid is (visits,
blocks of the expert width f), the visits counted at run time; consecutive
visits of one expert or of one row tile find their block resident.  One
visit computes the whole expert for its rows: gate and up from the same row
tile, the activation, and the down product accumulated over the blocks of f
in float32, so the activations never leave VMEM.

Rows in and out (PR 56).  The kernel is handed the step's activations [T, h]
as they are and the sorted order (`rows`: the token of every sorted row and
its routing weight), and returns float32 [T, h], every token's weighted sum:
no [A, h] array exists outside it.  The activations are copied into VMEM
once, at the first visit, and stay; so do the sums, written back once at the
last.  A tile's FIRST visit gathers its 128 rows by a one-hot product [128,
T] x [T, h] (exact: one term a row) on an MXU that waits for the weights'
read anyway; every visit multiplies its own rows by their routing weights in
float32 (`ys * wf[:, None]`, as XLA did) and adds them to their tokens' sums
by the transposed one-hot product, 32 rows at a time (only the chunks its
rows lie in), the float32 rows split into three bfloat16 pieces (8 + 8 + 8
bits of mantissa) stacked along the contraction: the placement is exact, and
because a visit's rows are ONE expert's no token has two of them, so each of
a token's k additions is one float32 addition, made in the sorted order.
That is the order XLA's scatter-add walks (row by row): the sums are those
of the form before, bit for bit (tier-1, interpreted; on the chip PERF.md,
finding 36).  A first build placed a whole TILE at its last visit (a third of
the products); a token's rows in one tile were then added inside the MXU,
which rounds otherwise, and one probe step of the Laguna cell read 0.067
where the limit is 0.055: a flipped expert downstream of a last bit.
This is out-form (2) of ISSUE 56; form (1), a DMA a row to assignment order,
is refused as written (Mosaic: "slice shape along dimension 0 must be
aligned to tiling (8), but is 1") and, with every row a tile of its own, lost
on the chip (PERF.md, finding 36).  A step whose [T, h] activations and sums
do not fit `ROWS_VMEM_BYTES` (`rows_inside`: by the shape, no flag) keeps
the form before it: XLA gathers `x[token]` [A, h], the kernel takes and
returns sorted rows by block, and XLA multiplies and scatter-adds.

Axis order.  An expert's `w_up` (and `w_gate`) is [h, f] in the params and
its `w_down` [f, h].  Where f is in whole lanes the kernel takes the first
two viewed [L x E, h, f], blocked (h, block of f), and contracts the row tile
[tm, h] with a block on its FIRST axis.  Where f is no whole number of lanes
and h is one (nemotron_h's 2688 x 1856), the TPU compiler stores that stack
with h under the lanes, and the kernel takes it as stored: viewed [L x E, f,
h] (a transpose that is a bitcast there), blocked (f, h) like `w_down`, all
of f in one block, the row tile contracted with it on the LAST axis of both
(`f_major`).  One kernel, the operand's axis order its parameter; `w_down`,
the biases and the arithmetic are the same either way.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# Pallas itself is imported where a kernel is built: `models/llama.py` imports
# this module for `lowering`, and every process that imports the model would
# pay 2-7 s of Pallas at its start (4-6 s of a worker's `setup_s`, my chip
# runs, PR 53).
LANES = 128
# rows a tile: the MXU's height; fewer for a step of fewer rows (bf16 packs
# 16 rows a sublane tile)
ROW_TILE, ROW_ALIGN = 128, 16
# what one visit's blocks of the expert matrices may hold in VMEM, both
# buffers of each; the whole limit asked of the compiler (a v5e has 128 MiB)
WEIGHT_VMEM_BYTES = 48 << 20
VMEM_LIMIT_BYTES = 100 << 20
# what a step's activations [tokens, h] and their float32 sums may hold
# beside them while the rows move inside the kernel (512 tokens of 7168:
# 7.3 + 14.7 MB), and the lanes of h one placement product writes
ROWS_VMEM_BYTES = 24 << 20
PLACE_LANES = 512
# rows of a tile one placement product takes: a visit places the chunks its
# own rows lie in
PLACE_ROWS = 32


# A check's (`checked`): the kernel on this backend whatever it is, and
# whether interpreted.
_CHECK: Optional[bool] = None


@contextlib.contextmanager
def checked(interpret: bool):
    """Inside, every trace's dispatched experts (and its stream mixers:
    `ops/pallas_hyper_connections.py`) are the kernels, interpreted or not: a
    test on the CPU, a compile for a TPU that is not attached."""
    global _CHECK
    was, _CHECK = _CHECK, interpret
    try:
        yield
    finally:
        _CHECK = was


def f_major(h: int, f: int) -> bool:
    """Does the kernel take the stacks of experts [h, f] (`w_up`, `w_gate`)
    viewed [L x E, f, h]?  Where f is no whole number of lanes and h is
    (nemotron_h's 2688 x 1856), the TPU compiler STORES such a stack with h
    under the lanes, so that view is the stored bytes (a transpose that is a
    bitcast) where [L x E, h, f] would be a re-laid-out copy of the whole
    stack, 3.7 GB a step (AOT compile for a v5e, PR 53 and PR 54).  Then the
    visit's block is [f, h] like `w_down`'s, all of f, and the row tile is
    contracted with it on the last axis of both."""
    return f % LANES != 0 and h % LANES == 0


def lowering(w_up: jax.Array, gated: bool) -> Optional[bool]:
    """Is the kernel this trace's grouped product over experts like `w_up`
    [..., E, h, f] (`gated`: beside a `w_gate` of that shape)?  -> its
    `interpret`, or None: the trace keeps `ragged_dot`.  The kernel is a
    single-device TPU program: a Pallas call does not partition, so a trace
    whose operands lie on a mesh (GSPMD's, or a `shard_map`'s) is not its,
    nor one on another backend.  Compiled it wants h in whole lanes, and f
    either in whole lanes or all of it in one block (`width_block` gives
    such an f no other), the stack taken as it is stored (`f_major`)."""
    h, f = w_up.shape[-2:]
    if width_block(h, f, 2 + gated, w_up.dtype.itemsize) is None:
        return None
    interpret = single_device(w_up)
    if interpret is False and h % LANES:
        return None
    return interpret


def single_device(operand: jax.Array) -> Optional[bool]:
    """May this trace hold a Pallas call over `operand`?  -> the call's
    `interpret`: a check's (`checked`), else False on a TPU where the
    operand lies on no mesh; None elsewhere."""
    if _CHECK is not None:
        return _CHECK
    if jax.default_backend() == "tpu" and (
            jax.typeof(operand).sharding.mesh.empty):
        return False
    return None


def row_tile(rows: int) -> int:
    """Rows a tile of a step of `rows` sorted rows."""
    return min(ROW_TILE, -(-rows // ROW_ALIGN) * ROW_ALIGN)


def width_block(h: int, f: int, matrices: int, itemsize: int) -> Optional[int]:
    """Columns of the expert width f a grid step holds: all of f where the
    visit's `matrices` blocks of [h, block] fit `WEIGHT_VMEM_BYTES` twice
    over, else the largest divisor of f in whole lanes that does; None
    where none does (the caller keeps `ragged_dot`)."""
    def fits(block):
        return 2 * matrices * h * block * itemsize <= WEIGHT_VMEM_BYTES

    if fits(f):
        return f
    blocks = [b for b in range(LANES, f, LANES) if f % b == 0 and fits(b)]
    return max(blocks) if blocks else None


def visits_of(sizes: jax.Array, tiles: int, tm: int):
    """The grid's visits from the experts' row counts `sizes` [E]: ->
    (offsets [E + 1], the rows each expert starts at; expert [V] and tile [V]
    of every visit, V = tiles + E - 1 the most there can be; how many visits
    there are)."""
    E = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    V = tiles + E - 1
    last = jnp.cumsum(n)  # one past an expert's last visit
    # visit v is the expert's whose visits it falls among: as many experts
    # end at or before it (compare and sum: `jnp.repeat` is a scatter)
    expert = jnp.minimum((jnp.arange(V, dtype=jnp.int32)[:, None]
                          >= last).sum(1, dtype=jnp.int32), E - 1)
    visit0 = last - n  # an expert's first visit
    tile = first[expert] + jnp.arange(V, dtype=jnp.int32) - visit0[expert]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, expert, jnp.clip(tile, 0, tiles - 1), n.sum()


def rows_inside(tokens: int, h: int, itemsize: int) -> bool:
    """Do a step's rows move INSIDE the kernel (`grouped_experts` with
    `rows`)?  While the step's activations [tokens, h] and their float32
    sums fit `ROWS_VMEM_BYTES` beside the weight blocks; past that the rows
    are gathered and scattered by XLA around it."""
    return -(-tokens // ROW_TILE) * ROW_TILE * h * (itemsize + 4) <= (
        ROWS_VMEM_BYTES)


def lane_chunk(h: int) -> int:
    """Columns of h one placement product writes: the widest whole lanes up
    to `PLACE_LANES` that divide h (all of an h of fewer)."""
    chunks = [c for c in range(LANES, PLACE_LANES + 1, LANES) if h % c == 0]
    return max(chunks) if chunks else h


def _kernel(layer_ref, offsets_ref, expert_ref, tile_ref, *refs,
            act: Callable, gated: bool, biased: bool, blocks: int, tm: int,
            f_first: bool, inside: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del layer_ref
    refs = list(refs)
    x_ref = refs.pop(0)
    col_ref, row_ref, weight_ref = (refs.pop(0), refs.pop(0), refs.pop(0)
                                    ) if inside else (None,) * 3
    wg_ref = refs.pop(0) if gated else None
    wu_ref, wd_ref = refs.pop(0), refs.pop(0)
    bg_ref, bu_ref, bd_ref = (refs.pop(0), refs.pop(0), refs.pop(0)
                              ) if biased else (None,) * 3
    out_ref = refs.pop(0)
    acc_ref = refs.pop(0) if blocks > 1 else None
    if inside:  # the step's rows, a tile's gathered rows and every token's
        # sum: resident across the visits
        all_ref, xs_ref, sum_ref = refs
    v, j = pl.program_id(0), pl.program_id(1)
    f32, bf16 = jnp.float32, jnp.bfloat16
    this = tile_ref[v]

    if inside:
        T = all_ref.shape[0]

        @pl.when((v == 0) & (j == 0))
        def _():
            pltpu.sync_copy(x_ref, all_ref)
            sum_ref[...] = jnp.zeros_like(sum_ref)

        # a tile's first visit gathers its rows
        @pl.when(((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != this))
                 & (j == 0))
        def _():
            # row r of the tile is token col[r]'s: a one-hot product, exact
            # (one term a row), on an MXU that waits for weights anyway
            dt = all_ref.dtype
            pick = (col_ref[...] == jax.lax.broadcasted_iota(
                jnp.int32, (tm, T), 1)).astype(dt)
            xs_ref[...] = jnp.dot(
                pick, all_ref[...], preferred_element_type=f32,
                precision=(jax.lax.Precision.HIGHEST if dt == f32 else None)
            ).astype(dt)

        x = xs_ref[...]
    else:
        x = x_ref[...]

    def into(w_ref):  # x [tm, h] into the expert's [h, tf], or its [tf, h]
        return jax.lax.dot_general(
            x, w_ref[...], (((1,), (1 if f_first else 0,)), ((), ())),
            preferred_element_type=f32)

    up = into(wu_ref)
    gate = into(wg_ref) if gated else None
    if biased:
        gate, up = gate + bg_ref[...], up + bu_ref[...]
    y = jnp.dot(act(gate, up).astype(x.dtype), wd_ref[...],
                preferred_element_type=f32)

    def place(ys, tokens):
        """sum[tokens] += ys [R, h], float32 exactly: a row is three
        bfloat16 pieces (8 + 8 + 8 bits of its mantissa), each placed by a
        one-hot product accumulated in float32, the three stacked along the
        contraction (`tokens` [1, 3 R]: the rows' tokens three times).  The
        rows of ONE visit are one expert's, so no token has two of them:
        the product places, and the one addition a token is float32's."""
        hi = ys.astype(bf16)
        rest = ys - hi.astype(f32)
        mid = rest.astype(bf16)
        pieces = jnp.concatenate(
            [hi, mid, (rest - mid.astype(f32)).astype(bf16)], axis=0)
        put = (jax.lax.broadcasted_iota(jnp.int32, (T, pieces.shape[0]), 0)
               == tokens).astype(bf16)
        h = ys.shape[1]
        c = lane_chunk(h)
        for at in range(0, h, c):
            sum_ref[:, at:at + c] += jnp.dot(
                put, pieces[:, at:at + c], preferred_element_type=f32)

    def store(y):
        if biased:
            y = y + bd_ref[...]
        e = expert_ref[v]
        # the visit's own rows of the tile: [first, last)
        first = offsets_ref[e] - this * tm
        last = offsets_ref[e + 1] - this * tm
        row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        own = (row >= first) & (row < last)
        if not inside:
            out_ref[...] = jnp.where(own, y, out_ref[...])
            return
        y = jnp.where(own, y * weight_ref[...], 0.0)
        for c in range(tm // PLACE_ROWS):
            at = c * PLACE_ROWS

            @pl.when((first < at + PLACE_ROWS) & (last > at))
            def _(c=c, at=at):
                place(y[at:at + PLACE_ROWS], row_ref[c])

        @pl.when(v == pl.num_programs(0) - 1)
        def _():
            pltpu.sync_copy(sum_ref, out_ref)

    if blocks == 1:
        store(y)
        return

    @pl.when(j == 0)
    def _():
        acc_ref[...] = y

    @pl.when(j > 0)
    def _():
        acc_ref[...] += y

    @pl.when(j == blocks - 1)
    def _():
        store(acc_ref[...])


def grouped_experts(x: jax.Array, sizes: jax.Array, layer: jax.Array,
                    w_gate: Optional[jax.Array], w_up: jax.Array,
                    w_down: jax.Array, biases=None, *, act: Callable,
                    interpret: bool = False, rows=None) -> jax.Array:
    """`sizes` [E] rows an expert; `w_up` (and `w_gate`, None for an expert
    without a gate matrix) [L, E, h, f] and `w_down` [L, E, f, h]: the WHOLE
    stacks as the params hold them (the view of them the kernel takes is
    chosen here: `f_major`), `layer` the scalar index of the layer whose
    experts these rows chose; `biases` None or (b_gate, b_up [L, E, f],
    b_down [L, E, h]).  `act(gate, up)` is the expert's nonlinearity over
    float32.  The products are accumulated in float32 and the activations
    rounded to x's dtype between them (`ragged_dot`'s arithmetic).

    With `rows` = (token [A], weight [A] float32), the step's rows move
    inside the kernel: x [T, h] is the step's activations, sorted row a is
    token `token[a]`'s and its expert's output counts `weight[a]` times.
    -> float32 [T, h], every token's weighted sum over its rows in a group;
    rows past `sizes.sum()` are never fetched and count for nothing.

    Without, x [A, h] is the rows themselves, sorted by expert -> float32
    [A, h]: row a's expert's output; rows past `sizes.sum()` are NOT written
    (whatever the buffer held).

    Not jitted on its own: the compiler names the call after the caller's
    named scope (`%moe.experts.N`), which is how a trace's walks place it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, E, h, f = w_up.shape
    gated, biased, inside = (w_gate is not None, biases is not None,
                             rows is not None)
    A = rows[0].shape[0] if inside else x.shape[0]
    # inside, a tile is the MXU's height whatever the step holds: the
    # one-hot products want whole lanes of rows
    tm = ROW_TILE if inside else row_tile(A)
    tf = width_block(h, f, 2 + gated, w_up.dtype.itemsize)
    if tf is None:
        raise ValueError(f"no block of experts [{h}, {f}] fits VMEM")
    tiles, blocks = -(-A // tm), f // tf

    def padded(a, n):  # along its first axis
        return jnp.pad(a, ((0, n - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))

    # the grid's integers are the sort's: a walk of a trace places them by
    # the LAST scope of their path, beside the rest of it
    with jax.named_scope("moe.dispatch"):
        offsets, expert, tile, visits = visits_of(sizes, tiles, tm)
        if inside:
            token = padded(rows[0].astype(jnp.int32), tiles * tm)
            # the tokens of a tile's rows down the sublanes (to gather by)
            # and, a chunk of `PLACE_ROWS` rows at a time and three times
            # over, along the lanes (to place the three pieces by); a step
            # none of whose rows is held still writes its zeros
            order = [token[:, None],
                     jnp.tile(token.reshape(tiles, tm // PLACE_ROWS, 1,
                                            PLACE_ROWS), (1, 1, 1, 3)),
                     padded(rows[1].astype(jnp.float32),
                            tiles * tm)[:, None]]
            visits = jnp.maximum(visits, 1)

    # index maps over (visit, block of f) and the prefetched scalars: the
    # visit's row tile, and blocks of ITS layer's expert in the whole stacks
    def tile_rows(v, j, layer, offsets, expert, tile):
        return tile[v], 0

    def tile_lanes(v, j, layer, offsets, expert, tile):
        return tile[v], 0, 0, 0

    def columns(v, j, layer, offsets, expert, tile):  # of [h | 1, f]
        return layer[0] * E + expert[v], 0, j

    def down(v, j, layer, offsets, expert, tile):  # rows of [f, h]
        return layer[0] * E + expert[v], j, 0

    def whole(v, j, layer, offsets, expert, tile):  # [1, h]
        return layer[0] * E + expert[v], 0, 0

    down_spec = pl.BlockSpec((None, tf, h), down)
    up_spec = pl.BlockSpec((None, h, tf), columns)
    f_first = f_major(h, f)
    if f_first:  # [f, h] like `w_down`: the stack as it is stored, no copy
        w_up = jnp.swapaxes(w_up, -1, -2)
        w_gate = jnp.swapaxes(w_gate, -1, -2) if gated else None
        up_spec = down_spec

    def flat(w):  # [L, E, ...] -> [L x E, ...]
        return w.reshape(L * E, *w.shape[2:])

    scratch = [pltpu.VMEM((tm, h), jnp.float32)] if blocks > 1 else []
    if inside:
        T = x.shape[0]
        Tp = -(-T // ROW_TILE) * ROW_TILE
        operands = [padded(x, Tp), *order]
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((tm, 1), tile_rows),
                    pl.BlockSpec((None, tm // PLACE_ROWS, 1, 3 * PLACE_ROWS),
                                 tile_lanes),
                    pl.BlockSpec((tm, 1), tile_rows)]
        out_specs = pl.BlockSpec(memory_space=pl.ANY)
        out_shape = jax.ShapeDtypeStruct((Tp, h), jnp.float32)
        scratch += [pltpu.VMEM((Tp, h), x.dtype), pltpu.VMEM((tm, h), x.dtype),
                    pltpu.VMEM((Tp, h), jnp.float32)]
    else:
        operands = [padded(x, tiles * tm)]
        in_specs = [pl.BlockSpec((tm, h), tile_rows)]
        out_specs = pl.BlockSpec((tm, h), tile_rows)
        out_shape = jax.ShapeDtypeStruct((tiles * tm, h), jnp.float32)
    if gated:
        operands.append(flat(w_gate))
        in_specs.append(up_spec)
    operands += [flat(w_up), flat(w_down)]
    in_specs += [up_spec, down_spec]
    if biased:
        operands += [b.reshape(L * E, 1, b.shape[-1]) for b in biases]
        in_specs += [pl.BlockSpec((None, 1, tf), columns)] * 2
        in_specs.append(pl.BlockSpec((None, 1, h), whole))
    out = pl.pallas_call(
        functools.partial(_kernel, act=act, gated=gated, biased=biased,
                          blocks=blocks, tm=tm, f_first=f_first,
                          inside=inside),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits, blocks),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, expert, tile,
      *operands)
    return out[:x.shape[0]]
