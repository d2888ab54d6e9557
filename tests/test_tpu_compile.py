"""The main path's device programs, given to the TPU compiler for a DESCRIBED
v5e (2x2), not an attached one: what the chip's compiler would refuse — a
misaligned slice, too much VMEM, a program that does not fit HBM, a kernel
that cannot be partitioned — fails here, at no chip time.  Nothing runs, so
this says nothing about results or speed.

The topology is described inside a module-scoped fixture (never at import:
only one process may hold libtpu, and every xdist worker imports every test
file), nothing here starts a child process, and the persistent compile
cache is off around the compiles (such an executable is written but cannot
be read back without a chip).  All of it stays in this one file: a second
file could land on another worker, whose fixture would then skip.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.models import KVCache, init_params, kv_cache_pspec, param_pspecs
from dynamo_tpu.models.config import LLAMA_3_1_8B, LLAMA_3_2_1B, QWEN2_5_7B
from dynamo_tpu.ops.pallas_attention import (
    decode_attention_pallas,
    prefill_attention_pallas,
)
from dynamo_tpu.ops.sampling import SamplingParams

PAGE = 16
POOL_PAGES = 4096
# what chip_smoke.py's long prompts drive at the worker's default flags
SMOKE_CHUNK = 512
SMOKE_TABLE_PAGES = 128  # 2048 tokens of table under a 512-token chunk
DECODE_TABLE_PAGES = 256  # 4096 tokens: where "adaptive" takes the kernel


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """The executable and the bytes of its text, the kernel's serialized
    body included.  What once made a kernel take 36-49 s to compile was
    head loops unrolled into that body: a SIZE, which reads the same on a
    busy machine, where the seconds (16 of a 60 s budget under six test
    workers) do not.  Each ceiling is today's size and a tenth."""
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, len(compiled.as_text())


def _kernel_args(cfg, sh, batch, table_pages, chunk=None):
    H, KVH, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim_)
    pool = _sds((POOL_PAGES, PAGE, KVH, hd), jnp.bfloat16, sh)
    table = _sds((batch, table_pages), jnp.int32, sh)
    lens = _sds((batch,), jnp.int32, sh)
    if chunk is None:
        return [_sds((batch, H, hd), jnp.bfloat16, sh), pool, pool, table,
                lens]
    new = _sds((batch, chunk, KVH, hd), jnp.bfloat16, sh)
    return [_sds((batch, chunk, H, hd), jnp.bfloat16, sh), new, new, pool,
            pool, table, lens, lens]


@pytest.mark.parametrize("cfg,extras", [
    (LLAMA_3_2_1B, False), (LLAMA_3_1_8B, False), (LLAMA_3_2_1B, True),
], ids=["1b", "8b", "1b-window-sink"])
def test_decode_kernel_compiles(one_chip, cfg, extras):
    args = _kernel_args(cfg, one_chip, 8, DECODE_TABLE_PAGES)
    if extras:
        args.append(_sds((cfg.num_attention_heads,), jnp.float32, one_chip))

        def fn(q, k, v, t, n, sink):
            return decode_attention_pallas(q, k, v, t, n, window=128,
                                           sink=sink)
    else:
        fn = decode_attention_pallas
    compiled, size = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    assert size < 42_000    # 38,096 / 36,997 / 37,681 today


@pytest.mark.parametrize("extras", [False, True], ids=["1b", "1b-window-sink"])
def test_prefill_kernel_compiles_at_the_smoke_chunk(one_chip, extras):
    """The worker's default --max-prefill-tokens chunk, its head loops on
    row blocks (unrolled they took 36-49 s to compile, 4 s after)."""
    cfg = LLAMA_3_2_1B
    args = _kernel_args(cfg, one_chip, 1, SMOKE_TABLE_PAGES,
                        chunk=SMOKE_CHUNK)
    if extras:
        args.append(_sds((cfg.num_attention_heads,), jnp.float32, one_chip))

        def fn(q, kn, vn, k, v, t, pre, cl, sink):
            return prefill_attention_pallas(q, kn, vn, k, v, t, pre, cl,
                                            window=128, sink=sink)
    else:
        fn = prefill_attention_pallas
    compiled, size = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    assert size < 71_000    # 63,459 / 64,870 today


@pytest.mark.parametrize("table_pages", [32, 64, 128, 256])
def test_prefill_kernel_compiles_at_the_cells_chunk(one_chip, table_pages):
    """The benchmark cells' attention (28 heads over 4 of 128, bf16) at the
    512-token chunk, reading the whole 14-layer pool by (layer, page), under
    every table a 512-token step meets there: the chunk goes through the
    kernel as ONE query block, a KV head's seven query heads folded into
    3,584 rows of one product, under the scoped VMEM limit the call asks
    for."""
    from dynamo_tpu.ops.pallas_attention import prefill_query_block

    cfg = QWEN2_5_7B
    q, new, _, pool, _, table, lens, _ = _kernel_args(
        cfg, one_chip, 1, table_pages, chunk=512)
    pool = _sds((CELL_LAYERS, *pool.shape), pool.dtype, one_chip)
    assert prefill_query_block(512, q.shape[2], new.shape[2], q.shape[3],
                               PAGE, q.dtype) == 512

    def fn(q, kn, vn, k, v, t, pre, cl, layer):
        return prefill_attention_pallas(q, kn, vn, k, v, t, pre, cl,
                                        layer=layer)

    compiled, size = _compile(fn, q, new, new, pool, pool, table, lens, lens,
                              _sds((), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert size < 58_000    # 53,107 to 53,130 today, by table


def test_prefill_kernel_reads_narrow_heads_from_lane_tiles(one_chip):
    """LFM2-24B-A2B's attention (32 heads over 8 of 64, bf16) at the
    512-token chunk under its cell's 512-page table, reading a two-layer
    pool by (layer, page).  Stored as whole lane tiles, two heads a tile
    ([4, 128] a token: `packed_plane`), the pool is the kernel's operand as
    it stands: nothing pool-sized is made.  (Stored [8, 64] the compiler
    slices the layer's slab out and pads every head to a tile, a temporary
    of twice the slab every layer and step, read on PR 55's compiles: no
    test holds the compiler to that.)"""
    from dynamo_tpu.ops.pallas_attention import packed_plane

    pages, H, KVH, hd = 16384, 32, 8, 64
    assert packed_plane(KVH, hd) == (4, 128)
    pool = _sds((2, pages, PAGE, 4, 128), jnp.bfloat16, one_chip)
    q = _sds((1, 512, H, hd), jnp.bfloat16, one_chip)
    new = _sds((1, 512, KVH, hd), jnp.bfloat16, one_chip)
    table = _sds((1, 512), jnp.int32, one_chip)
    lens = _sds((1,), jnp.int32, one_chip)

    def fn(q, kn, vn, k, v, t, pre, cl, layer):
        return prefill_attention_pallas(q, kn, vn, k, v, t, pre, cl,
                                        layer=layer, packed=True)

    compiled, _ = _compile(fn, q, new, new, pool, pool, table, lens, lens,
                           _sds((), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    slab = pages * PAGE * KVH * hd * 2  # one layer of k (or of v), bytes
    assert compiled.memory_analysis().temp_size_in_bytes < slab // 8


# -- whole steps ---------------------------------------------------------------- #

def step_shapes(cfg, batch, table_pages, shardings, pool_pages=POOL_PAGES):
    """Abstract operands of the engine's decode step (`Layout.decode_step`;
    no penalty counts) for `cfg`, placed by `shardings(kind)` — kind is a PartitionSpec tree
    for "params"/"kv" and None for the replicated batch operands."""
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    kv = jax.eval_shape(
        lambda: KVCache.create(cfg, pool_pages, PAGE, jnp.bfloat16))

    def place(tree, specs):
        return jax.tree.map(
            lambda x, s: _sds(x.shape, x.dtype, shardings(s)), tree, specs)

    def rep(shape, dtype):
        return _sds(shape, dtype, shardings(P()))

    samp = SamplingParams(
        rep((batch,), jnp.float32), rep((batch,), jnp.int32),
        rep((batch,), jnp.float32), rep((batch,), jnp.float32),
        rep((batch,), jnp.float32))
    return (place(params, param_pspecs(cfg)), place(kv, kv_cache_pspec()),
            rep((batch,), jnp.int32), rep((batch,), jnp.int32),
            rep((batch,), jnp.int32), None,
            rep((batch, table_pages), jnp.int32),
            samp, rep((batch,), jnp.uint32))


def flat_layout(cfg, attn_impl):
    """The layout of a flat engine (GSPMD partitions its programs by their
    operands' shardings) with blocks of 4 steps under a 4096-token cap."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.layout import Layout

    return Layout.resolve(cfg, EngineConfig(
        attention_impl=attn_impl, decode_steps=4, max_model_len=4096,
        max_pages_per_seq=4096 // PAGE))[0]


def two_layers(cfg):
    import dataclasses

    return dataclasses.replace(cfg, num_hidden_layers=2)


def test_decode_block_step_compiles_on_one_chip(one_chip):
    """A full-width (two-layer) decode step, both programs `adaptive`
    picks: the block path under 4096 tokens of table, and the per-step
    scan around the Pallas kernel from there on."""
    cfg = two_layers(LLAMA_3_2_1B)
    for table_pages, kernel in ((64, False), (DECODE_TABLE_PAGES, True)):
        step = flat_layout(cfg, "adaptive").decode_step(
            False, False, greedy=True)
        compiled = step.lower(
            *step_shapes(cfg, 8, table_pages, lambda spec: one_chip)
        ).compile()
        assert ("tpu_custom_call" in compiled.as_text()) is kernel
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 4 << 30


def test_decode_block_step_compiles_on_a_tp4_mesh(topo):
    """The same step as GSPMD partitions it over the four chips (what
    `worker --tp 4` runs): heads and the KV pool sharded four ways."""
    cfg = two_layers(LLAMA_3_2_1B)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("dp", "tp"))
    step = flat_layout(cfg, "xla").decode_step(False, False, greedy=True)
    compiled = step.lower(
        *step_shapes(cfg, 8, 64, lambda spec: NamedSharding(mesh, spec))
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(
            step_shapes(cfg, 8, 64, lambda spec: None)[:2]))
    assert per_chip < 0.4 * whole, (per_chip, whole)


# -- a prefill step leaves the KV pool where it is (ISSUE 26) ------------------- #

# the benchmark's one configuration (benchmark/configs/qwen2.5-7b-h14.json)
CELL_LAYERS = 14
CELL_POOL_PAGES = 6912
# ops that move an array without computing on it
_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice", "reshape",
           "transpose")


def prefill_step_shapes(cfg, chunk, table_pages, shardings, pool_pages):
    """Operands of the engine's `prefill_step` (`Layout.prefill_step`):
    the decode step's, with a [B, chunk] token block, prefix and chunk
    lengths, seeds, counters and which rows sample."""
    params, kv, _, _, _, _, table, samp, seeds = step_shapes(
        cfg, 1, table_pages, shardings, pool_pages)
    lens = _sds((1,), jnp.int32, shardings(P()))
    return (params, kv, _sds((1, chunk), jnp.int32, shardings(P())), table,
            lens, lens, samp, seeds, lens,
            _sds((1,), jnp.bool_, shardings(P())))


def pool_sized_movers(hlo_text, kv_shape):
    """(op, result shape) of every data-moving op of the optimised HLO
    (fused computations included) whose result has as many elements as
    the pool or as one layer's slab of it."""
    import math
    import re

    sizes = (math.prod(kv_shape), math.prod(kv_shape[1:]))
    pat = re.compile(r"= \(?\w+\[([\d,]+)\]\S* (%s)\(" % "|".join(_MOVERS))
    found = []
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if m and math.prod(map(int, m.group(1).split(","))) in sizes:
            found.append((m.group(2), m.group(1)))
    return found


@pytest.mark.parametrize("chunk,table_pages,impl,choice", [
    (512, 128, "xla", "xla"), (512, 256, "xla", "xla"),
    (64, 128, "pallas", "pallas"), (512, 128, "adaptive", "pallas"),
    (512, 256, "adaptive", "pallas"), (64, 128, "adaptive", "pallas"),
    (64, 64, "adaptive", "xla"),
], ids=["xla-512x128", "xla-512x256", "pallas-64x128", "pallas-512x128",
        "pallas-512x256", "pallas-64x128-by-rule", "xla-64x64-by-rule"])
def test_prefill_step_leaves_the_pool_where_it_is(one_chip, chunk,
                                                  table_pages, impl, choice):
    """The benchmark cell's `prefill_step` programs (Qwen2.5-7B widths, 14
    layers, 6912 pages of 16, batch 1, pool donated) on both attention
    paths: the compiler's temporaries stay under ONE of k or v (they held
    the pool again: 3.6-3.7 GB), and nothing in the optimised HLO copies,
    slices, updates or re-lays-out the pool or a layer's slab of it.
    "adaptive" is what the cells run: by `_adapt`'s measured rule it takes
    the kernel at the 512-token chunk (then no score-shaped
    f32[1,28,chunk,*] temporary is left in the program) and at a 64-token
    one under 128 pages (2^17 scores a head), XLA attention at a 64-token
    one under 64.  Counts and bytes, never a time."""
    import dataclasses
    import re

    from dynamo_tpu.analysis import xla_ledger

    cfg = dataclasses.replace(QWEN2_5_7B, num_hidden_layers=CELL_LAYERS)
    step = flat_layout(cfg, impl).prefill_step(False, greedy=True)
    args = prefill_step_shapes(cfg, chunk, table_pages, lambda spec: one_chip,
                               CELL_POOL_PAGES)
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    kernel = choice == "pallas"
    assert xla_ledger.path_choice(
        "prefill_attention", batch=1, chunk=chunk,
        table_tokens=table_pages * PAGE) == choice
    assert ("tpu_custom_call" in text) is kernel
    scores = re.findall(r"f32\[1,28,%d,\d+\]" % chunk, text)
    assert bool(scores) is not kernel, scores[:3]
    k = args[1].k
    one_of_kv = k.size * k.dtype.itemsize  # 1.585 GB
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < one_of_kv, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * one_of_kv  # written in place
    assert pool_sized_movers(text, k.shape) == []


def test_prefill_step_keeps_the_head_behind_its_conditional(one_chip):
    """Qwen2.5-7B widths, two layers, chunk 512 under 128 pages: given which
    rows sample, the compiled step still holds ONE conditional (the compiler
    did not turn it into a select, which would run the head on every step)
    and nothing copies, slices or re-lays-out an array the size of the
    `[3584, 152064]` matrix on its way in.  Without the operand the same
    step compiles to no conditional.  Counts, never a time."""
    import math
    import re

    cfg = two_layers(QWEN2_5_7B)
    step = flat_layout(cfg, "adaptive").prefill_step(False, greedy=True)
    args = prefill_step_shapes(cfg, 512, 128, lambda spec: one_chip, 1024)
    text = step.lower(*args).compile().as_text()
    conds = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert len(conds) == 1, conds
    head = cfg.hidden_size * cfg.vocab_size
    moved = []
    for ln in text.splitlines():
        m = re.search(r"= \(?\w+\[([\d,]+)\]\S* (%s)\(" % "|".join(_MOVERS),
                      ln)
        if m and math.prod(map(int, m.group(1).split(","))) == head:
            moved.append(ln.strip()[:120])
    assert moved == []
    bare = step.lower(*args[:-1]).compile().as_text()
    assert " conditional(" not in bare


def test_prefill_step_partitions_on_a_tp4_mesh(topo):
    """The (layer, page) gather and the all-layer scatter under GSPMD with
    the pool sharded on the kv-head axis (`worker --tp 4`): each chip
    holds a quarter of the pool, and no chip copies its share.  (8B
    widths: a pool of 64-wide heads is STORED pages-minor by the TPU
    compiler and is re-laid-out around any access by page, before this
    loop and after it: ROADMAP D3.)"""
    cfg = two_layers(LLAMA_3_1_8B)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("dp", "tp"))
    step = flat_layout(cfg, "xla").prefill_step(False, greedy=True)
    args = prefill_step_shapes(
        cfg, SMOKE_CHUNK, SMOKE_TABLE_PAGES,
        lambda spec: NamedSharding(mesh, spec), POOL_PAGES)
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    k = args[1].k
    shard = (*k.shape[:3], k.shape[3] // 4, k.shape[4])
    assert pool_sized_movers(text, shard) == []
    assert "scatter" in text


def _smallthinker_step(one_chip, impl):
    """SmallThinker widths (64 experts of 2560 x 768, top 6, a rotary and a
    position-free layer, one of them windowed), chunk 512 under 128 pages."""
    import dataclasses

    from dynamo_tpu.models import ModelConfig

    cfg = dataclasses.replace(ModelConfig.from_hf_config({
        "model_type": "smallthinker", "head_dim": 128, "hidden_size": 2560,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64, "num_attention_heads": 28,
        "num_hidden_layers": 2, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": [0, 1], "rope_theta": 1500000,
        "sliding_window_layout": [0, 1], "sliding_window_size": 4096,
        "vocab_size": 151936}), moe_impl=impl)
    step = flat_layout(cfg, "adaptive").prefill_step(False, greedy=True)
    return cfg, step, prefill_step_shapes(cfg, 512, 128, lambda spec: one_chip,
                                          1024)


def _published_step(one_chip, impl, config, chunk, slots=0, rows=1, **over):
    """(cfg, the flat engine's `prefill_step`, its operands' shapes) of
    `benchmark/configs/<config>.json`'s model with the keys `over` replaced:
    `rows` rows of `chunk` tokens under 128 pages and `slots` state slots."""
    import dataclasses
    import json

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.layout import Layout
    from dynamo_tpu.models import ModelConfig

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            config + ".json")) as f:
        model = json.load(f)["model"]
    cfg = dataclasses.replace(ModelConfig.from_hf_config(dict(
        model, **{k: v(model) if callable(v) else v
                  for k, v in over.items()})), moe_impl=impl)
    layout = Layout.resolve(cfg, EngineConfig(
        attention_impl="adaptive", num_pages=1024, num_state_slots=slots,
        max_model_len=4096))[0]

    def shapes(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    def rep(dtype, *shape):
        return _sds(shape, dtype, one_chip)

    params = shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    kv = shapes(jax.eval_shape(lambda: KVCache.create(
        cfg, 1024, PAGE, jnp.bfloat16, state_slots=slots)))
    one, f32 = rep(jnp.int32, rows), rep(jnp.float32, rows)
    samp = SamplingParams(f32, one, f32, f32, f32)
    return cfg, layout.prefill_step(False, greedy=True), (
        params, kv, rep(jnp.int32, rows, chunk),
        rep(jnp.int32, rows, 128 + layout.state_cols), one, one, samp,
        rep(jnp.uint32, rows), one, rep(jnp.bool_, rows))


def _nemotron_share_step(one_chip, impl):
    """The Nemotron-3-Nano cell's share at its published widths (16 held of
    128 experts of 2688 x 1856, top 6, relu(up)^2 without a gate matrix; a
    state-space, an attention and three expert layers of its 52), chunk 512
    under 128 pages and 8 state slots."""
    return _published_step(
        one_chip, impl, "nemotron3-nano-30b-ep8", 512, slots=8,
        num_hidden_layers=7, hybrid_override_pattern="MEM*EME")


def _laguna_step(one_chip, impl):
    """The Laguna-XS.2 cell at its published widths (256 experts of 2048 x
    512, top 8, a shared expert; a full layer over a dense MLP and two
    windowed 64-head layers over experts, of its 7), chunk 256 under 128
    pages: the smallest step its rule dispatches."""
    def first(key):
        return lambda model: model[key][:3]

    return _published_step(
        one_chip, impl, "laguna-xs2-33b-h7", 256, num_hidden_layers=3,
        layer_types=first("layer_types"),
        mlp_layer_types=first("mlp_layer_types"),
        num_attention_heads_per_layer=first("num_attention_heads_per_layer"))


@pytest.mark.parametrize("family,impl,kernels,temp_bytes", [
    (_smallthinker_step, "dense", 1, 1 << 30),
    (_smallthinker_step, "ragged", 2, 1 << 26),
    (_nemotron_share_step, "ragged", 3, 1 << 27),
    (_laguna_step, "ragged", 3, 1 << 27)],
    ids=["all-experts", "dispatched", "nemotron-share-dispatched",
         "laguna-256-dispatched"])
def test_expert_prefill_step_reads_the_expert_stacks_in_place(
        one_chip, family, impl, kernels, temp_bytes):
    """The step compiles for the chip under both forms of `_moe`, and nothing
    copies a layer's expert stack out of the layer-stacked params, as sort
    + `ragged_dot` did (2.3 ms a layer on the chip: PERF.md, PR 31), nor
    re-lays the whole stack out.  The all-experts form's products fuse their
    slice; the dispatched form is the grouped kernel (`ops/pallas_moe.py`:
    a second custom call beside attention's, named after its scope), whose
    operands are the whole stacks, and its temporaries are rows, not
    [experts, tokens, hidden]: under 64 MB at SmallThinker's widths where
    the all-experts form's are 630.  Nemotron's expert width 1,856 is no
    whole number of lanes and the compiler stores its `w_up` with the hidden
    size under the lanes: the kernel takes that stack viewed [L x E, f, h],
    which is those bytes (`pallas_moe.f_major`); viewed [L x E, h, f] its
    operand was a copy of the stack, 3.7 GB at 23 layers (PR 53).  Laguna's
    two kinds of layers have an attention kernel each, and Nemotron's
    state-space layers the blocked scan's (`ssm.scan`, PR 60: the same check
    switches it on)."""
    import math
    import re

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import pallas_moe

    cfg, step, args = family(one_chip, impl)
    with pallas_moe.checked(interpret=False):  # no TPU is attached here
        compiled = step.lower(*args).compile()
    text = compiled.as_text()
    stack = cfg.num_experts * cfg.hidden_size * cfg.moe_intermediate_size
    stacks = {stack, cfg.num_moe_layers * stack}
    # results of that size OUTSIDE fused computations are materialised: a
    # slice fused into the matmul that reads it is not, nor is a bitcast
    made, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = "fused_computation" in line.split("(")[0]
        m = re.search(r"= \(?\w+\[([\d,]+)\]", line)
        if m and not fused and not re.search(
                r" (parameter|bitcast|get-tuple-element)\(", line) and (
                math.prod(map(int, m.group(1).split(","))) in stacks):
            made.append(line.strip()[:120])
    assert made == []
    assert compiled.memory_analysis().temp_size_in_bytes < temp_bytes
    calls = re.findall(r"(%[\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert len(calls) == kernels
    assert any(c.startswith("%moe.experts") for c in calls) == (
        impl == "ragged")
    if impl != "ragged":
        return
    # the step's rows move INSIDE the kernel (PR 56): nothing under the
    # expert layer's scopes scatters (XLA's scatter goes row by row: 92 ns a
    # row, 6.5 ms of a Nemotron step), and no op of the program results in
    # [assignments, hidden], be it a gather of the sorted rows, their product
    # with the routing weights or a kernel's output
    tokens = args[2].shape[1]
    assert llama.moe_rows(tokens) == "kernel"
    rows = "[%d,%d]" % (tokens * cfg.num_experts_per_tok, cfg.hidden_size)
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(r"= \(?\w+" + re.escape(rows), ln)
             or (" scatter(" in ln and re.search(
                 r'op_name="[^"]*moe\.(dispatch|experts|combine)', ln))]
    assert moved == []


# -- latent pages (the deepseek_v3 and xing4_0 cells) ------------------------------- #

@pytest.mark.parametrize("heads,layers,batch,chunk,table_pages", [
    (64, 7, 1, 512, 128), (64, 7, 4, 64, 256), (64, 7, 1, 16, 64),
    (32, 8, 1, 512, 128), (32, 8, 4, 64, 256), (32, 8, 1, 16, 64),
], ids=["h64-512x128", "h64-4x64x256", "h64-16x64", "h32-512x128",
        "h32-4x64x256", "h32-16x64"])
def test_latent_prefill_kernel_compiles_at_the_cells_shapes(
        one_chip, heads, layers, batch, chunk, table_pages):
    """The latent cells' attention at both deployments' head counts (rank
    512, rotary key 64, bf16), the whole 7- and 8-layer pools read by
    (layer, page) as they are stored, [2, 128] and [4, 128] planes: the
    512-token chunk, the `[4, 64]` shared step and a lone 16-token chunk.
    The query tile is 2048 rows at both (32 and 64 tokens), and nothing
    outside the kernel touches the pools: no temporary at all."""
    from dynamo_tpu.models.config import CacheSpec
    from dynamo_tpu.ops.pallas_latent_attention import (
        latent_query_tile,
        prefill_latent_attention_pallas,
    )

    kd, vd = CacheSpec("latent", 1, 512, 64).plane_dims
    bf = jnp.bfloat16
    assert latent_query_tile(chunk, heads, 512, 64, PAGE, kd, vd, bf) == min(
        chunk, 2048 // heads)
    lens = _sds((batch,), jnp.int32, one_chip)

    def fn(qa, qp, kn, ln, k, v, t, pre, cl, layer):
        return prefill_latent_attention_pallas(
            qa, qp, kn, ln, k, v, t, pre, cl, 0.1447, layer=layer)

    compiled, size = _compile(
        fn, _sds((batch, chunk, heads, 512), bf, one_chip),
        _sds((batch, chunk, heads, 64), bf, one_chip),
        _sds((batch, chunk, 64), bf, one_chip),
        _sds((batch, chunk, 512), bf, one_chip),
        _sds((layers, POOL_PAGES, PAGE, *kd), bf, one_chip),
        _sds((layers, POOL_PAGES, PAGE, *vd), bf, one_chip),
        _sds((batch, table_pages), jnp.int32, one_chip), lens, lens,
        _sds((), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert size < 35_000    # 29,401 to 31,603 today, by shape


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_latent_prefill_step_leaves_the_pool_where_it_is(one_chip,
                                                         monkeypatch, impl):
    """GigaChat's cell at two layers (one dense, one of experts), chunk 512
    under 128 pages, the 12288-page pool donated: with the kernel no op
    copies, slices or re-lays-out either pool or a layer's slab of it, no
    score-shaped f32[1,64,512,*] array and no gathered table is left in the
    program, and its temporaries are smaller than the XLA form's.  The
    layout is told it sits on a TPU (off the chip a latent model keeps
    "xla"); the test steers that, not an option of the program."""
    import dataclasses
    import json
    import re

    from dynamo_tpu.analysis import xla_ledger
    from dynamo_tpu.models import ModelConfig

    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "gigachat3.1-702b-ep16.json")) as f:
        run = json.load(f)
    cfg = dataclasses.replace(ModelConfig.from_hf_config(run["model"]),
                              num_hidden_layers=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = flat_layout(cfg, impl).prefill_step(False, greedy=True)
    monkeypatch.undo()
    # `step_shapes` places by the mesh's specs, which refuse the family
    on_chip = lambda x: _sds(x.shape, x.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    kv = jax.tree.map(on_chip, jax.eval_shape(lambda: KVCache.create(
        cfg, run["worker_flags"]["--num-pages"], PAGE, jnp.bfloat16)))
    lens, f32 = _sds((1,), jnp.int32, one_chip), _sds((1,), jnp.float32,
                                                      one_chip)
    args = (params, kv, _sds((1, 512), jnp.int32, one_chip),
            _sds((1, 128), jnp.int32, one_chip), lens, lens,
            SamplingParams(f32, lens, f32, f32, f32),
            _sds((1,), jnp.uint32, one_chip), lens,
            _sds((1,), jnp.bool_, one_chip))
    # the 512-token step's experts are dispatched (16 held, top 8: PR 53),
    # on the chip by the grouped kernel: traced as there, not as on this CPU
    from dynamo_tpu.ops import pallas_moe

    with pallas_moe.checked(interpret=False):
        compiled = step.lower(*args).compile()
    text = compiled.as_text()
    kernel = impl == "pallas"
    assert xla_ledger.path_choice("prefill_attention", batch=1, chunk=512,
                                  table_tokens=128 * PAGE) == impl
    calls = re.findall(
        r"(%[\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert calls and all(c.startswith(
        ("%moe.experts", "%attn.core") if kernel else "%moe.experts")
        for c in calls)
    assert any(c.startswith("%attn.core") for c in calls) is kernel
    scores = re.findall(r"f32\[1,\d+,512,\d{4}\]", text)
    gathered = re.findall(r"bf16\[1,2048,512\]", text)
    assert bool(scores) is not kernel and bool(gathered) is not kernel
    assert (pool_sized_movers(text, kv.k.shape)
            + pool_sized_movers(text, kv.v.shape)) == []
    mem = compiled.memory_analysis()
    pools = sum(p.size * p.dtype.itemsize for p in (kv.k, kv.v))
    assert mem.alias_size_in_bytes >= pools  # written in place
    assert mem.temp_size_in_bytes < (300 << 20 if kernel else 400 << 20)


def _loop_bodies(text):
    """{computation: its lines} of the optimised HLO, fused computations'
    bodies left out."""
    bodies, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            name = line.split("(")[0].strip()
        elif "fused_computation" not in name:
            bodies.setdefault(name, []).append(line)
    return bodies


# -- states wider than a lane tile (the falcon_h1 cell) ---------------------------- #

def test_a_shared_step_slices_wide_states_out_of_the_pool_row_by_row(one_chip):
    """The Falcon-H1 cell's `[4, 64]` shared step (published widths, two of
    its layers, 64 state slots: a pool of 2 x 64 x [32, 128, 256] float32,
    537 MB): its temporaries stay under the pool's own size.  With the rows'
    states gathered as `pool[layer, slots]` the compiler reads a pool whose
    last axis is two lane tiles through a COPY OF THE POOL in 128-lane
    halves: 819 MB of temporaries here where `hybrid.read_state`'s slice a
    row leaves 206 (AOT, PR 59), and at the cell's 137 slots and six layers
    the step did not fit the chip (16.99 GB).  A size, never a time."""
    cfg, step, args = _published_step(
        one_chip, "dense", "falcon-h1-34b-h6", 64, slots=64, rows=4,
        num_hidden_layers=2)
    pool = args[1].ssm
    assert pool.shape[-1] == 256  # N: wider than a lane tile
    mem = step.lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < pool.size * pool.dtype.itemsize


# -- Mamba-2's blocked scan (the nemotron_h and falcon_h1 cells) ------------------- #

@pytest.mark.parametrize("config,impl,over,layers", [
    ("nemotron3-nano-30b-ep8", "ragged",
     dict(num_hidden_layers=7, hybrid_override_pattern="MEM*EME"), 3),
    ("falcon-h1-34b-h6", "dense", dict(num_hidden_layers=2), 2),
], ids=["nemotron", "falcon-h1"])
def test_the_blocked_scan_is_one_kernel_a_layer_and_no_block_reaches_hbm(
        one_chip, config, impl, over, layers):
    """The two cells' 1 x 512 `prefill_step` (published widths, a few
    layers) with the scan as its kernel (ISSUE 60): the layer loop's body
    holds exactly ONE `ssm.scan` custom call (one a state-space layer: the
    loop runs a unit a turn), and nowhere in the program is there a float32
    array that ends in [heads, Q, Q] or [groups, Q, Q], the blocks' decays
    and scores that the `jnp` form wrote four to five times a block (AOT,
    PR 60: `f32[64,128,128]`, 4 MB each, at nemotron_h's widths).  The
    four-row short step keeps the `jnp` form (its states are handed out
    inside a block) and is the parent's program: no kernel, the same
    temporaries.  Counts and shapes, never a time."""
    import re

    from dynamo_tpu.analysis import xla_ledger
    from dynamo_tpu.ops import pallas_moe

    cfg, step, args = _published_step(one_chip, impl, config, 512, slots=8,
                                      **over)
    assert cfg.layer_pattern.count("M") + cfg.layer_pattern.count("P") == (
        layers)
    with pallas_moe.checked(interpret=False):
        text = step.lower(*args).compile().as_text()
    assert xla_ledger.path_choice("ssm_scan", rows=1, chunk=512) == "pallas"
    call = re.compile(r"(%[\w.]+) = [^\n]*custom_call_target="
                      r"\"tpu_custom_call\"")
    scans = [[c for c in call.findall("\n".join(body))
              if c.startswith("%ssm.scan")]
             for body in _loop_bodies(text).values()]
    assert sorted(len(s) for s in scans if s) == [1]
    nh, G, Q = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_chunk
    assert not re.findall(r"f32\[(?:\d+,)*(?:%d|%d),%d,%d\]" % (nh, G, Q, Q),
                          text)
    # and as `jnp` they are there: the pattern finds what it looks for
    _, step, args = _published_step(one_chip, impl, config, 512, slots=8,
                                    **over)
    assert re.findall(r"f32\[(?:\d+,)*(?:%d|%d),%d,%d\]" % (nh, G, Q, Q),
                      step.lower(*args).compile().as_text())
    # the short shared step: the `jnp` form, with or without the check
    _, step, args = _published_step(one_chip, impl, config, 64, slots=8,
                                    rows=4, **over)
    with pallas_moe.checked(interpret=False):
        checked = step.lower(*args).compile()
    assert xla_ledger.path_choice("ssm_scan", rows=4, chunk=64) == "xla"
    assert "%ssm.scan" not in checked.as_text()
    if impl == "dense":  # no other kernel rides the check: one program
        assert (checked.memory_analysis().temp_size_in_bytes
                == step.lower(*args).compile().memory_analysis(
                ).temp_size_in_bytes)


# -- a residual of several streams (the xing4_0 cell) ------------------------------ #

# ops that hand an array on between HBM and VMEM as it is laid out: the
# compiler's own prefetches and evictions (DMA beside other work), whole or in
# slices that a `ConcatBitcast` joins
_HANDED_ON = ("copy-start", "copy-done", "slice-start", "slice-done")


@pytest.mark.parametrize("rows,chunk", [(1, 512), (4, 64)],
                         ids=["512", "4x64"])
def test_stream_mixers_are_two_kernels_a_half_and_nothing_else(one_chip, rows,
                                                               chunk):
    """The Xing cell's `prefill_step` (published widths, its two dense
    layers and two of its expert layers; the 512-token chunk and the `[4,
    64]` shared step) with the mixers as kernels (ISSUE 58): each layer loop's body
    holds exactly two `hc.mix` and two `hc.post` custom calls, and NO other
    op that computes a result of n x tokens x hidden values.  As `jnp` the
    body held eleven: the carry bf16[1,512,4,3584] under 4-row tiles, three
    re-laid-out copies of it for the mixer's product, `post`'s result three
    times in that layout, a float32 copy for `pre` (AOT, PR 58).  What is
    left at that size is `hc.post`'s result and the compiler handing it on
    between HBM and VMEM in the SAME layout (tokens under the sublanes, the
    n x hidden values along the lanes), bf16.  Counts and shapes, never a
    time."""
    import math
    import re

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import pallas_moe

    cfg, step, args = _published_step(
        one_chip, "ragged", "xing4.0-29b-h8", chunk, rows=rows,
        num_hidden_layers=4)
    with pallas_moe.checked(interpret=False):
        compiled = step.lower(*args).compile()
    assert llama.hc_mixers(rows * chunk) == "kernel"
    values = rows * chunk * cfg.hc_mult * cfg.hidden_size
    loops = {k: v for k, v in _loop_bodies(compiled.as_text()).items()
             if any("= " in ln and ln.split("= ")[0].strip().startswith(
                 "%hc.") for ln in v)}
    assert len(loops) == 2  # the dense layers' loop and the expert layers'
    result = re.compile(
        r"(%[\w.-]+) = \(?(\w+)\[([\d,]+)\](\{[^ ]*\})? ([\w-]+)\(")
    for body in loops.values():
        calls = re.findall(r"(%[\w.]+) = [^\n]*custom_call_target="
                           r"\"tpu_custom_call\"", "\n".join(body))
        mixers = sorted(c.rsplit(".", 1)[0] for c in calls
                        if c.startswith("%hc."))
        assert mixers == ["%hc.mix", "%hc.mix", "%hc.post", "%hc.post"], calls
        made = []
        for m in map(result.search, body):
            if not m or m.group(5) in ("parameter", "bitcast", "tuple",
                                       "get-tuple-element", "while"):
                continue
            if math.prod(map(int, m.group(3).split(","))) != values:
                continue
            dtype, layout, op = m.group(2), m.group(4) or "", m.group(5)
            assert dtype == "bf16" and "T(8,128)(2,1)" in layout, m.string
            if not (m.group(1).startswith("%hc.post") or op in _HANDED_ON
                    or "ConcatBitcast" in m.string):
                made.append(m.string.strip()[:160])
        assert made == []
