"""phi4flash on the served path (ISSUE 48): the family's config keys, its
checkpoint names through the loader, both halves of the layers against the
plain reference (`benchmark/reference/phi4flash.py`: the recurrence, token by
token, the four attention products written out, the cross half at every
position), the cross half on sampled rows alone, the selective scan against
the loop, the state slots with their snapshots, each fault the reference can
plant, the layouts that refuse the family, and the benchmark's count and
trace readers.  Tiny sizes, float32 and bfloat16, seeded weights DRAWN AS THE
FAMILY INITIALISES THEM (A = -(1..N) by state index, steps in 0.001-0.1, D
1: a state that REMEMBERS, a decay that differs by state index), CPU.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import KVCache, ModelConfig, init_params
from dynamo_tpu.models import llama, phi4flash
from dynamo_tpu.models.loader import load_params
from dynamo_tpu.ops import layer_norm, ssm
from test_nemotron_h import TINY as NEMOTRON_TINY
from test_nemotron_h import (BENCH, PAGE, PEAKS, ROOT, TOL, bench_module,
                             engine_of, generate, logp, prompt, table_for,
                             with_slots)

CELL = "phi4-mini-flash-3.8b"

# "SWSWSFGC": three Mamba-1 layers, two under a window of 16, the full one,
# and one unit of the cross half
TINY = {
    "model_type": "phi4flash", "vocab_size": 300, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "layer_norm_eps": 1e-5, "mb_per_layer": 2, "sliding_window": 16,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "hidden_act": "silu", "max_position_embeddings": 512,
    "embd_pdrop": 0, "resid_pdrop": 0,
}
# bfloat16 weights, residual and pages against the float32 reference, every
# token of the vocabulary (logprobs down to -40 under the test's unit-scale
# embedding): the case below reads 0.41; float32 reads 3e-5, so a program
# that rounds to bfloat16 where float32 is stated fails TOL a thousand times
BF16_TOL = 1.0


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "phi4flash")


@pytest.fixture(scope="module")
def cfg():
    # a state handed out every 16 tokens inside a chunk (128 at full size)
    return dataclasses.replace(
        ModelConfig.from_hf_config(TINY, name="tiny-phi4flash"), ssm_chunk=16)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(48), dtype=jnp.float32)


def reader_of(params, cfg):
    """`read(name)` over a param tree, under the family's tensor names (the
    loader's mapping, backwards)."""
    flat = {"model.embed_tokens.weight": params["embed"],
            "model.final_layernorm.weight": params["final_norm"],
            "model.final_layernorm.bias": params["final_norm_bias"]}
    at = dict.fromkeys(phi4flash.STACKS.values(), 0)
    for i, kind in enumerate(cfg.layer_pattern):
        p, stack = f"model.layers.{i}.", phi4flash.STACKS[kind]
        a, lay, j = p + "attn.", params[stack], at[stack]
        at[stack] += 1
        flat.update({
            p + "input_layernorm.weight": lay["norm"][j],
            p + "input_layernorm.bias": lay["norm_b"][j],
            p + "post_attention_layernorm.weight": lay["mlp_norm"][j],
            p + "post_attention_layernorm.bias": lay["mlp_norm_b"][j],
            p + "mlp.fc1.weight": lay["w_gateup"][j].T,
            p + "mlp.fc2.weight": lay["w_down"][j].T})
        if kind == "S":
            flat.update({
                a + "in_proj.weight": lay["in_proj"][j].T,
                a + "conv1d.weight": np.asarray(lay["conv_w"][j]).T[:, None],
                a + "conv1d.bias": lay["conv_b"][j],
                a + "x_proj.weight": lay["x_proj"][j].T,
                a + "dt_proj.weight": lay["dt_proj"][j].T,
                a + "dt_proj.bias": lay["dt_bias"][j],
                a + "A_log": np.asarray(lay["A_log"][j]).T,
                a + "D": lay["D"][j],
                a + "out_proj.weight": lay["out_proj"][j].T})
        elif kind == "G":
            flat.update({a + "in_proj.weight": lay["w_in"][j].T,
                         a + "out_proj.weight": lay["w_out"][j].T})
        else:
            inner = a + "inner_cross_attn."
            for n, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2",
                                      "lambda_k2")):
                flat[inner + name] = lay["lambda"][j][n]
            w, b = ("wq", "bq") if kind == "C" else ("wqkv", "bqkv")
            flat.update({inner + "subln.weight": lay["subln"][j],
                         a + "out_proj.weight": lay["wo"][j].T,
                         a + "out_proj.bias": lay["bo"][j],
                         a + "Wqkv.weight": lay[w][j].T,
                         a + "Wqkv.bias": lay[b][j]})
    return lambda name: np.asarray(flat[name], np.float32)


def fresh_cache(cfg, tokens=128, slots=6, dtype=jnp.float32):
    return KVCache.create(cfg, 2 + -(-tokens // PAGE), PAGE, dtype,
                          state_slots=slots)


# one compile a (config, shape), not a trace a call
forward_prefill = jax.jit(llama.forward_prefill, static_argnums=(1,))
forward_decode = jax.jit(llama.forward_decode, static_argnums=(1,))


def prefill_all(cfg, params, tokens, chunk=None, kv=None, slot=1,
                dtype=jnp.float32):
    """Chunked prefill of one prompt through both pools (its state in slot
    `slot`): [(position, next-token logprobs)] a chunk, the cache."""
    T = len(tokens)
    chunk = chunk or T
    kv = kv if kv is not None else fresh_cache(cfg, T + 8 * PAGE, dtype=dtype)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        logits, kv = forward_prefill(
            params, cfg, kv, jnp.asarray([part], jnp.int32),
            table_for(T + 8 * PAGE, [slot if s else 0, slot]),
            jnp.asarray([s], jnp.int32), jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv


def ref_logp(ref, cfg, params, tokens, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), TINY, [np.asarray([tokens])],
                       len(tokens), **controls)[0][0]


def published():
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        return json.load(f)


# -- configuration ------------------------------------------------------------- #

def test_from_hf_config_reads_the_published_keys():
    """The catalog row's keys as published and as run (nothing is cut): the
    layout by published index, 9 layers with pages and 9 with a state, and
    the parameter count that is the model's name."""
    run = published()
    c = ModelConfig.from_hf_config(run["model"])
    assert c.layer_pattern == "SW" * 8 + "SF" + "GC" * 7 and c.cross_decoder
    assert (c.num_kv_layers, c.state_spec.layers) == (9, 9)
    assert (c.ssm_inner, c.ssm_state, c.ssm_dt_rank, c.ssm_conv_kernel) == (
        5120, 16, 160, 4)
    assert (c.sliding_window, c.diff_attention, c.attention_rope) == (
        512, True, False)
    assert c.num_params() == 3_852_562_944 == sum(
        int(np.prod(shape)) for _, shape, _ in bench_module(
            "checkpoints", "phi4flash").tensors(run["model"]))
    mem = run["memory"]
    # bf16, but A_log, dt_proj's bias and D (9 layers) and the four lambdas
    # (16 layers) held in float32
    assert mem["weights_bytes"] - c.num_params() * 2 == 2 * (
        9 * (5120 * 16 + 2 * 5120) + 16 * 4 * 64)
    spec = c.state_spec
    assert spec.bytes_per_slot(2) == mem["state_bytes_per_slot"] == 3_225_600
    assert spec.window_dims == (120, 128) and spec.state_dims == (16, 5120)
    assert 9 * c.cache_spec.bytes_per_token_layer(2) == (
        mem["kv_bytes_per_token"]) == 46_080
    flags = run["worker_flags"]
    assert mem["kv_pool_tokens"] == flags["--num-pages"] * 16
    assert mem["kv_pool_bytes"] == mem["kv_pool_tokens"] * 46_080
    assert mem["state_pool_bytes"] == flags["--num-state-slots"] * 3_225_600
    shapes = jax.eval_shape(lambda: KVCache.create(c, 64, 16, state_slots=8))
    assert shapes.k.shape == shapes.v.shape == (9, 64, 16, 2, 640)
    assert shapes.conv.shape == (9, 8, 120, 128)
    assert shapes.ssm.shape == (9, 8, 16, 5120)
    assert shapes.ssm.dtype == jnp.float32


def test_the_file_states_each_published_key_once_for_each_reader():
    """Top-level keys (what the driver's check reads) equal `model` (what the
    program gets) and the catalog row's values; `reduced` is empty."""
    run = published()
    model = dict(run["model"])
    assert model.pop("architectures") == ["Phi4FlashForCausalLM"]
    assert model.pop("torch_dtype") == "bfloat16"
    assert {k: run[k] for k in model} == model
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [c for c in spec["configs"] if c["name"] == CELL]
    assert entry["reduced"] == [] and run["reduced"] == {}
    assert entry["source"] == run["source"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = [r for r in map(json.loads, f)
                if r["source_url"] == run["source"]]
    assert row["config"] == model
    cell, = [w for w in spec["workloads"] if w["config"] == CELL]
    assert (cell["traffic"], cell["chips"]) == ("longdoc-1tok", 1)
    assert not os.path.exists(os.path.join(BENCH, "cells",
                                           cell["name"] + ".json"))
    assert run["worker_flags"]["--max-model-len"] == 8192
    listed = [m["name"] for m in spec["per_layer"]
              if m.get("workloads") == [cell["name"]]]
    assert listed == ["step.selective_scan_device_pct",
                      "kernel.selective_scan_roofline",
                      "step.cross_half_device_pct", "engine.cross_rows_pct"]


@pytest.mark.parametrize("bad,key", [
    ({"mb_per_layer": 4}, "mb_per_layer"),
    ({"num_hidden_layers": 6}, "num_hidden_layers"),
    ({"num_hidden_layers": 4}, "num_hidden_layers"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"sliding_window": None}, "sliding_window"),
    ({"sliding_window": [16, 0]}, "sliding_window"),
    ({"num_key_value_heads": 1}, "num_key_value_heads"),
    ({"rope_scaling": {"type": "longrope"}}, "rope_scaling"),
    ({"model_type": "phi4flush"}, "mb_per_layer"),
], ids=["every-fourth", "six-layers", "no-cross-half", "act", "mlp-bias",
        "head-bias", "untied", "no-window", "window-list", "odd-kv-heads",
        "rope-scaling", "another-family"])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(dict(TINY, **bad))


# -- checkpoint names through the loader ----------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """`benchmark/lib/checkpoint.py` + `checkpoints/phi4flash.py` write the
    family's tensors; `models/loader.py` reads them; a chunked prefill over
    the loaded tree agrees with the reference reading the same file."""
    from safetensors import safe_open

    ckpt = bench_module("lib", "checkpoint")
    names = [n for n, _, _ in bench_module(
        "checkpoints", "phi4flash").tensors(TINY)]
    assert "model.layers.0.attn.A_log" in names
    assert "model.layers.1.attn.inner_cross_attn.lambda_q1" in names
    assert "model.layers.6.attn.in_proj.weight" in names
    assert "model.layers.6.attn.x_proj.weight" not in names
    assert "model.layers.7.attn.Wqkv.bias" in names
    assert not any("lm_head" in n for n in names)
    model = dict(TINY, architectures=["Phi4FlashForCausalLM"],
                 torch_dtype="bfloat16")
    ckpt.write({"model": model, "weights_seed": 5,
                "checkpoint": "phi4flash"}, str(tmp_path))
    c = ModelConfig.from_pretrained(str(tmp_path))
    p = load_params(str(tmp_path), c, dtype=jnp.float32)
    assert p["ssm_layers"]["conv_w"].shape == (3, 4, 128)
    assert p["ssm_layers"]["A_log"].shape == (3, 16, 128)
    assert p["ssm_layers"]["A_log"].dtype == jnp.float32
    assert p["attn_layers"]["lambda"].shape == (3, 4, 8)
    assert p["cross_layers"]["wq"].shape == (1, 64, 64)
    assert "lm_head" not in p
    reader = safe_open(str(tmp_path / "model.safetensors"), framework="np")
    toks = prompt(40, 1)
    want = ref.forward(
        lambda n: reader.get_tensor(n).astype(np.float32), TINY,
        [np.asarray([toks])], len(toks))[0][0]
    for pos, got in prefill_all(c, p, toks, chunk=16)[0]:
        assert np.abs(got - want[pos]).max() < TOL


# -- both halves against the reference --------------------------------------------- #

@pytest.mark.parametrize("chunk", [None, 32, 13],
                         ids=["one-chunk", "chunks-of-32", "chunks-of-13"])
def test_chunked_prefill_agrees_with_the_reference(cfg, params, ref, chunk):
    """70 tokens in one chunk, in chunks that cross the window of 16 and a
    state handed out inside (every 16 of a 32-token chunk), and in chunks
    no page or block divides: the last position of every chunk against the
    reference's full forward pass."""
    toks = prompt(70, 3)
    want = ref_logp(ref, cfg, params, toks)
    for pos, got in prefill_all(cfg, params, toks, chunk)[0]:
        assert np.abs(got - want[pos]).max() < TOL, pos


def test_bfloat16_reads_far_from_float32_and_near_the_reference(cfg, ref):
    """The served dtype: bfloat16 weights, residual and pages (the state,
    `A_log`, the step's bias, `D` and the lambdas float32, as the loader
    leaves them) against the float32 reference over the same rounded
    weights.  It passes a tolerance of its own and fails the float32 one by
    two orders: TOL would catch a program that rounds where float32 is
    stated."""
    half = init_params(cfg, jax.random.PRNGKey(48), dtype=jnp.bfloat16)
    assert half["ssm_layers"]["A_log"].dtype == jnp.float32
    toks = prompt(70, 3)
    want = ref_logp(ref, cfg, half, toks)
    worst = max(np.abs(got - want[pos]).max() for pos, got in prefill_all(
        cfg, half, toks, 32, dtype=jnp.bfloat16)[0])
    assert 100 * TOL < worst < BF16_TOL, worst


def test_the_cross_half_runs_where_a_row_samples(cfg, params, ref):
    """Two rows of one step: logits at a row's last position are the same
    numbers whether every row samples, no conditional (`samples` None), or
    the first alone; a step in which none samples returns zeros and still
    writes pages and states.  And the reference's two forms agree: the
    cross half at every position, or at the returned ones alone."""
    a, b = prompt(24, 4), prompt(24, 5)
    want = [ref_logp(ref, cfg, params, t)[-1] for t in (a, b)]
    tail = ref.tail_logprobs(reader_of(params, cfg), TINY,
                             [np.asarray([a])], 3)[0][0]
    assert np.abs(tail - ref_logp(ref, cfg, params, a)[-3:]).max() < 5e-5

    def step(samples):
        return llama.forward_prefill(
            params, cfg, fresh_cache(cfg), jnp.asarray([a, b], jnp.int32),
            table_for(32, [[0, 1], [0, 2]], batch=2),
            jnp.zeros((2,), jnp.int32), jnp.full((2,), 24, jnp.int32),
            samples=samples)

    every, kv_every = step(None)
    first, kv_first = step(jnp.asarray([True, False]))
    none, kv_none = step(jnp.asarray([False, False]))
    for i in (0, 1):
        assert np.abs(logp(every)[i] - want[i]).max() < TOL
    # (another program: the conditional is compiled in; sums may reorder)
    assert np.abs(np.asarray(every)[0] - np.asarray(first)[0]).max() < 1e-4
    assert not np.asarray(none).any()
    for got in (kv_first, kv_none):
        for x, y in zip(got, kv_every):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_a_shared_short_step_with_pad_rows(cfg, params, ref):
    """Four rows of a 16-token bucket: two prompts' last chunks of unequal
    length, resumed from their own slots, and two pad rows (no tokens, slot
    0, page 0).  The real rows read as the reference; the pad rows move no
    state but trash."""
    a, b = prompt(27, 6), prompt(21, 7)
    kv = fresh_cache(cfg, 4 * 40, slots=6)
    pages = np.zeros((4, 5), np.int32)
    pages[0], pages[1] = np.arange(1, 6), np.arange(6, 11)
    zero = jnp.zeros((4,), jnp.int32)
    toks = np.zeros((4, 16), np.int32)
    toks[0], toks[1] = a[:16], b[:16]
    _, kv = llama.forward_prefill(
        params, cfg, kv, jnp.asarray(toks),
        with_slots(pages, [[0, 1], [0, 2], [0, 0], [0, 0]]), zero,
        jnp.asarray([16, 16, 0, 0], jnp.int32))
    toks[:] = 0
    toks[0, :11], toks[1, :5] = a[16:], b[16:]
    before = np.asarray(kv.ssm[:, 3:])
    logits, kv = llama.forward_prefill(
        params, cfg, kv, jnp.asarray(toks),
        with_slots(pages, [[1, 1], [2, 2], [0, 0], [0, 0]]),
        jnp.asarray([16, 16, 0, 0], jnp.int32),
        jnp.asarray([11, 5, 0, 0], jnp.int32),
        samples=jnp.asarray([True, True, False, False]))
    for i, text in enumerate((a, b)):
        assert np.abs(logp(logits)[i]
                      - ref_logp(ref, cfg, params, text)[-1]).max() < TOL
    np.testing.assert_array_equal(np.asarray(kv.ssm[:, 3:]), before)


def test_decode_goes_through_pages_and_slots(cfg, params, ref):
    """A prompt in two chunks, then five greedy decode steps of one token
    through all 8 layers: each step's logprobs against the reference's full
    forward pass over the text so far."""
    text = prompt(37, 8)
    out, kv = prefill_all(cfg, params, text, chunk=24)
    table = table_for(37 + 8 * PAGE, [1, 1])
    nxt = int(out[-1][1].argmax())
    for _ in range(5):
        text.append(nxt)
        logits, kv = forward_decode(
            params, cfg, kv, jnp.asarray([nxt], jnp.int32),
            jnp.asarray([len(text) - 1], jnp.int32), table)
        want = ref_logp(ref, cfg, params, text)[-1]
        assert np.abs(logp(logits)[0] - want).max() < TOL, len(text)
        nxt = int(want.argmax())


@pytest.mark.parametrize("how", ["decode", "chunk_of_one"])
def test_the_last_position_of_a_full_table_sees_each_key_once(cfg, params,
                                                               ref, how):
    """The token at position `pages * PAGE - 1` of a table with NO spare
    page, as many pages as a window's gather holds and more (3 here): the
    page the gather would read past the table's end is no second copy of its
    last one (its keys weighed twice in every windowed layer)."""
    pages = 4
    text = prompt(pages * PAGE, 9)
    kv = fresh_cache(cfg, pages * PAGE)
    table = table_for(pages * PAGE, [0, 1])
    assert table.shape[1] - phi4flash.split_table(table)[0].shape[1] > 0
    assert phi4flash.split_table(table)[0].shape[1] == pages
    _, kv = forward_prefill(
        params, cfg, kv, jnp.asarray([text[:-1]], jnp.int32), table,
        jnp.asarray([0], jnp.int32), jnp.asarray([len(text) - 1], jnp.int32))
    table = table_for(pages * PAGE, [1, 1])
    if how == "decode":
        logits, _ = forward_decode(
            params, cfg, kv, jnp.asarray(text[-1:], jnp.int32),
            jnp.asarray([len(text) - 1], jnp.int32), table)
    else:
        logits, _ = forward_prefill(
            params, cfg, kv, jnp.asarray([text[-1:]], jnp.int32), table,
            jnp.asarray([len(text) - 1], jnp.int32),
            jnp.asarray([1], jnp.int32))
    want = ref_logp(ref, cfg, params, text)[-1]
    assert np.abs(logp(logits)[0] - want).max() < TOL


CONTROLS = ["lower_precision", "ignore_window", "no_diff", "no_subln",
            "cross_reads_layer_15", "memory_after_gate", "memory_shifted",
            "layernorm_as_rmsnorm", "scalar_decay", "no_decay",
            "state_not_carried", "window_not_carried"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_comparison_catches(cfg, params, ref, control):
    """Each keyword of the reference's `forward` computes ONE thing wrong (the
    chunk-boundary ones every 16 tokens, where the served chunks end), and
    the served path, which agrees with the plain reference to TOL, reads
    at least 30 times TOL away from it."""
    assert set(CONTROLS) == set(ref.FAULTS) | {"lower_precision"}
    toks = prompt(70, 3)
    wrong = ref_logp(ref, cfg, params, toks, fault_chunk=16,
                     **{control: True})
    worst = max(np.abs(got - wrong[pos]).max()
                for pos, got in prefill_all(cfg, params, toks, 16)[0])
    assert worst > 30 * TOL, worst


# -- the selective scan ------------------------------------------------------------- #

@pytest.mark.parametrize("tokens,at,kernel,C", [
    (64, (16, 32, 48), False, 24), (40, (8, 16, 24), False, 24),
    (24, (), False, 24), (1, (), False, 24),
    (64, (16, 32, 48), True, 2048), (40, (8, 16, 24), True, 1024),
    (1, (), True, 1024)],
    ids=["three-inside", "inside-a-short-row", "none-inside", "one-token",
         "kernel-three-inside", "kernel-short-row", "kernel-one-token"])
def test_the_selective_scan_is_the_recurrence(tokens, at, kernel, C):
    """`ops.ssm.selective_scan`, XLA's loop and the kernel (interpret mode:
    whole tiles of 1,024 channels), against the loop, token by token:
    outputs, the final state and the states handed out inside the chunk,
    from a carried state, with two rows of unequal length (a zero step at
    pads)."""
    rng = np.random.default_rng(tokens)
    B, N = 2, 4
    x = rng.standard_normal((B, tokens, C)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (B, tokens, C))
                ).astype(np.float32)
    lens = [tokens, max(tokens - 5, 1)]
    for b, n in enumerate(lens):
        dt[b, n:] = 0.0
    A = -np.broadcast_to(np.arange(1, N + 1, dtype=np.float32)[:, None],
                         (N, C)) * rng.uniform(0.5, 2.0, (1, C)).astype(
                             np.float32)
    Bm = rng.standard_normal((B, tokens, N)).astype(np.float32)
    Cm = rng.standard_normal((B, tokens, N)).astype(np.float32)
    h0 = rng.standard_normal((B, N, C)).astype(np.float32)
    y, h, hs = ssm.selective_scan(
        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A), jnp.asarray(Bm),
        jnp.asarray(Cm), jnp.asarray(h0), at, kernel=kernel, interpret=True)
    assert len(hs) == len(at)
    H, states = h0.copy(), {}
    for t in range(tokens):
        H = (np.exp(dt[:, t, None, :] * A) * H
             + Bm[:, t, :, None] * (dt[:, t] * x[:, t])[:, None, :])
        assert np.abs(np.asarray(y[:, t]) - (Cm[:, t, :, None] * H).sum(1)
                      ).max() < 1e-4
        states[t + 1] = H.copy()
    assert np.abs(np.asarray(h) - states[tokens]).max() < 1e-5
    # a row's state stops where its real tokens end
    assert np.abs(np.asarray(h)[1] - states[lens[1]][1]).max() < 1e-5
    for t, got in zip(at, hs):
        assert np.abs(np.asarray(got) - states[t]).max() < 1e-5


def test_the_kernel_refuses_channels_that_are_no_whole_tiles():
    """Asked for the kernel (what a TPU always is), a channel count its
    tiles of 1,024 do not hold raises: no second form stands in unseen."""
    z = jnp.zeros
    with pytest.raises(ValueError, match="whole tiles of 1024"):
        ssm.selective_scan(z((1, 8, 24)), z((1, 8, 24)), -jnp.ones((4, 24)),
                           z((1, 8, 4)), z((1, 8, 4)), z((1, 4, 24)),
                           kernel=True, interpret=True)


def test_check_selective_scan_walks_at_a_small_size():
    """The chip's single-process check of the scan and a chunk boundary, at
    its `--small` size on this backend: exit 0, the scan at the loop, both
    planted faults far from it."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "check_selective_scan.py"), "--small"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["handed_out_at"] == [8, 16, 24]
    assert out["auto"]["y_err_over_max"] < out["tol"]
    assert min(out["faults_over_max"].values()) > 0.1


def test_layer_norm_has_its_mean_and_its_bias():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 32)) + 2.0,
                    jnp.float32)
    w, b = jnp.full((32,), 1.5), jnp.full((32,), 0.25)
    got = np.asarray(layer_norm(x, w, b, 1e-5))
    xn = np.asarray(x)
    want = ((xn - xn.mean(-1, keepdims=True))
            / np.sqrt(xn.var(-1, keepdims=True) + 1e-5) * 1.5 + 0.25)
    assert np.abs(got - want).max() < 1e-5


# -- the engine: slots, snapshots, the cross half's counters ------------------------ #

async def agrees(engine, ref, cfg, params, toks, n=3):
    got, lps = await generate(engine, toks, n)
    text = list(toks)
    for t, lp_t in zip(got, lps):
        want = ref_logp(ref, cfg, params, text)[-1]
        assert t == int(want.argmax()), len(text)
        assert abs(lp_t - want.max()) < 5 * TOL, len(text)
        text.append(t)


def events(engine, kind):
    return [e for e in engine.events.dump()["events"] if e["kind"] == kind]


@pytest.mark.parametrize("how", [{}, {"decode_steps": 4}],
                         ids=["default", "block-of-4"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         how):
    """Chunked prefill, a question that resumes from a document's snapshot
    and decode through pages and slots: the logprob of every greedy token
    against the reference's full forward pass over the text so far.  The
    cross half ran on the rows of the steps that sampled, and nowhere else."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(40, 6)
        for tail in (prompt(5, 7), prompt(9, 8)):
            await agrees(engine, ref, cfg, params, shared + tail, 4)
        first, second = events(engine, "admit")
        assert (first["cached"], first["kv_cached"]) == (0, 0)
        # 40 shared tokens: 5 pages cached, and the first prompt's tail row
        # left a snapshot at every page of it
        assert (second["cached"], second["kv_cached"]) == (40, 40)
        chunks = events(engine, "prefill_chunk")
        assert all(c["cross_rows"] == c["head"] * c["batch"] for c in chunks)
        assert sum(c["cross_rows"] for c in chunks) == 2
        assert sum(c["tokens"] for c in chunks) == 45 + 9
        m = vars(engine.metrics())
        assert m["cross_rows_total"] == 2
        assert m["state_snapshot_hits_total"] == 1
        assert m["state_hit_tokens_shortened_total"] == 0
    finally:
        await engine.shutdown()


def test_another_family_carries_no_cross_rows():
    from dynamo_tpu.models import tiny_config

    c = tiny_config()
    engine = JaxEngine(c, init_params(c, jax.random.PRNGKey(0), jnp.float32),
                       EngineConfig(page_size=PAGE, num_pages=16),
                       eos_token_ids=[], kv_dtype=jnp.float32)
    assert "cross_rows_total" not in vars(engine.metrics())


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "serving mesh"),
    ({"parallel": {"pp": 2}, "max_prefill_tokens": 160}, "serving mesh"),
    ({"fuse_projections": True}, "fuse_projections"),
    ({"quantization": "int8"}, "int8"),
    ({"park_max_pages": 8}, "parking"),
    ({"tiered": object()}, "KVBM"),
    ({"speculative_ngram_k": 3}, "speculative-ngram-k"),
    ({"decode_continuous": True, "decode_steps": 2}, "decode-continuous"),
    ({"num_state_slots": 2}, "num_state_slots"),
], ids=["tp", "pp", "fused-projections", "int8", "parking", "kvbm-tier",
        "speculative", "continuous", "too-few-slots"])
def test_paths_that_cannot_carry_the_family_refuse_it(cfg, params, how,
                                                      match):
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how)
    if "parallel" in how:
        how["parallel"] = ParallelConfig(**how["parallel"])
    with pytest.raises(ValueError, match=match):
        engine_of(cfg, params, **how)


def test_step_kinds_that_want_every_position_refuse_the_family(cfg, params):
    """The draft-verify step and the embedding forward would need the cross
    half at every position, the decode block a state rolled back: refused by
    name."""
    kv = fresh_cache(cfg)
    toks = jnp.zeros((1, 4), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="draft-verify.*phi4flash"):
        llama.forward_verify(params, cfg, kv, toks, table_for(8, [0, 1]),
                             one, one + 4)
    with pytest.raises(ValueError, match="embedding forward.*phi4flash"):
        llama.forward_embed(params, cfg, toks, one + 4)
    with pytest.raises(ValueError, match="decode block.*phi4flash"):
        llama.decode_block_scan(params, cfg, kv, one, one,
                                table_for(8, [0, 1]), 2, 64, None, ())


# -- what the layer loop's conditionals take ------------------------------------- #

_CALLED = re.compile(r"\w+=(?:\{([^}]*)\}|%?([\w.\-]+))")
_ARRAY = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]")


def loop_conditional_operands(text):
    """The shapes of the arrays that are operands of a `conditional` inside
    a `while` body of an HLO module's text, the computations the body calls
    among it: a set of tuples, the members of an operand tuple each for
    itself.  An operand of a conditional is a buffer of its own, so what is
    listed here is materialised every trip of the loop."""
    comps, lines = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            lines = comps.setdefault(line.split()[-2].lstrip("%"), [])
        elif lines is not None and line.startswith("  "):
            lines.append(line.strip().removeprefix("ROOT "))
    todo = [b for ls in comps.values() for l in ls if " while(" in l
            for b in re.findall(r"body=%?([\w.\-]+)", l)]
    inside, shapes = set(), set()
    while todo:
        comp = todo.pop()
        if comp in inside:
            continue
        inside.add(comp)
        defs = dict(l.lstrip("%").split(" = ", 1)
                    for l in comps[comp] if " = " in l)
        for rest in defs.values():
            todo += [n for many, one in _CALLED.findall(rest)
                     for n in (x.strip().lstrip("%")
                               for x in (many or one).split(","))
                     if n in comps]
            cond = re.search(r"\bconditional\(([^)]*)\)", rest)
            for operand in cond.group(1).split(", ")[1:] if cond else ():
                made = defs[operand.split()[-1].lstrip("%")]
                # its type: everything before the instruction's own name
                kind = re.split(r" [\w\-]+\(", made, maxsplit=1)[0]
                shapes |= {tuple(int(d) for d in dims.split(",") if d)
                           for dims in _ARRAY.findall(kind)}
    return shapes


FAMILIES = {  # the tiny model, how its tests run it, its attention matrices
    "phi4flash": (TINY, {"ssm_chunk": 16},
                  ("wqkv", "wo", "w_gateup", "w_down")),
    "nemotron_h": (NEMOTRON_TINY, {"moe_impl": "ragged"},
                   ("wq", "wk", "wv", "wo")),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_no_layer_matrix_is_an_operand_of_a_conditional_in_the_layer_loop(
        family):
    """A conditional's operand is a buffer of its own, so ONE layer's matrix
    (a slice of its stack) handed to a `lax.cond` inside the layer loop is
    copied out of the stack every unit: 197 MB a unit and a fifth of the
    device at phi4flash's published widths (ISSUE 49).  Whole stacks and
    pools may pass (they are passed as they stand, and the head's
    conditional, outside the loop, takes them so); a layer's slice may not.
    The guard for the next family that puts a layer under a conditional."""
    model, how, names = FAMILIES[family]
    cfg = dataclasses.replace(
        ModelConfig.from_hf_config(model, name="tiny-" + family), **how)
    params = init_params(cfg, jax.random.PRNGKey(49), dtype=jnp.float32)
    text = forward_prefill.lower(
        params, cfg, fresh_cache(cfg, 64), jnp.zeros((1, 32), jnp.int32),
        table_for(64, [0, 1]), jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 32, jnp.int32), samples=jnp.asarray([True]),
    ).compiler_ir("hlo").as_hlo_text()
    operands = loop_conditional_operands(text)
    # the walk found the loop's conditional: a pool is among its operands
    assert fresh_cache(cfg, 64).k.shape in operands
    one_layer = {n: params["attn_layers"][n].shape[1:] for n in names}
    assert not {n: s for n, s in one_layer.items() if s in operands}


# -- the benchmark's count and its trace readers ------------------------------------ #

@pytest.fixture(scope="module")
def bench_lib():
    sys.path.insert(0, BENCH)
    try:
        from lib import cross_trace, opwalk, roofline
    finally:
        sys.path.remove(BENCH)
    return cross_trace, opwalk, roofline


def test_the_roofline_counts_what_every_step_must(bench_lib):
    """The self half's 1,964 M parameters (18 layers: nine Mamba-1 mixers,
    nine attention layers, their feed-forwards), not the cross half's, the
    head's or the scans': 10 ms of operations a 512-token step; the scans'
    floor from what a fused kernel must move; attention's from the keys a
    token can see, 512 at most under the window."""
    _, _, roofline = bench_lib
    config = published()
    fam, model = roofline.family(config), config["model"]
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2560 * 5120 + 2560 * 2560
    ffn = 3 * 2560 * 10240
    assert fam.every_step_params(model) == 9 * (mamba + ffn) + 9 * (
        attn + ffn) == 1_962_639_360
    secs, which = fam.prefill_step_floor_s(model, PEAKS, 512)
    assert which == "compute" and abs(secs * 1e3 - 10.202) < 0.001
    secs, which = fam.prefill_step_floor_s(model, PEAKS, 16)
    assert which == "memory" and abs(secs * 1e3 - 4.793) < 0.001
    secs, which = fam.selective_scan_floor_s(model, PEAKS, 512, 1)
    per_token = 2 * (4 * 5120 + 2 * 16)
    state = 2 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert which == "memory" and abs(
        secs - 9 * (512 * per_token + state) / 819e9) < 1e-12
    assert abs(secs * 1e3 - 0.2387) < 0.0001
    assert 9 * 512 * 6 * 5120 * 16 / 197e12 < secs  # 0.0115 ms of operations
    four = fam.selective_scan_floor_s(model, PEAKS, 256, 4)[0]
    assert abs(four - 9 * (256 * per_token + 4 * state) / 819e9) < 1e-12
    # a 512-token chunk behind 5,120 tokens: a windowed layer's tokens see
    # 512 keys each, layer 17's every key before them
    secs, _ = fam.prefill_attn_floor_s(model, PEAKS, 512, 5632)
    windowed = max(6 * 64 * 40 * 512 * 512 / 197e12,
                   2 * 1023 * 20 * 64 * 2 / 819e9)
    full = max(6 * 64 * 40 * (512 * 5120 + 512 * 513 // 2) / 197e12,
               2 * 5632 * 20 * 64 * 2 / 819e9)
    assert abs(secs - (8 * windowed + full)) < 1e-12


PLACED = [
    ("%fusion.12 = f32[1,16,5120] fusion(%p)",
     "jit(prefill_step)/while/body/ssm.scan/while/body/mul", "scan"),
    ("%ssm.conv.3 = bf16[1,512,5120] fusion(%p)", "", "scan"),
    ("%fusion.7 = bf16[1,512,5120] fusion(%p)",
     "jit(prefill_step)/while/body/ssm.gate/mul", "scan"),
    ("%fusion.8 = bf16[1,512,10240] fusion(%p)",
     "jit(prefill_step)/while/body/ssm.in_proj/dot_general", None),
    ("%fusion.9 = f32[1,1,5120] fusion(%p)",
     "jit(prefill_step)/cond/branch_1_fun/cross/while/body/gmu/mul", "cross"),
    ("%fusion.10 = f32[1,10,2,1,8192] fusion(%p)",
     "jit(prefill_step)/cond/branch_1_fun/cross/while/body/attn.core/dot",
     "cross"),
    ("%while.4 = (s32[]) while(%t)",
     "jit(prefill_step)/cond/branch_1_fun/cross/while", None),
    ("%fusion.11 = f32[1,10,2,512,1040] fusion(%p)",
     "jit(prefill_step)/while/body/cond/branch_1_fun/attn.core/dot", None),
]


@pytest.mark.parametrize("name,scope,group", PLACED,
                         ids=[f"{i}-{g}" for i, (_, _, g) in enumerate(PLACED)])
def test_trace_ops_are_placed_by_their_scopes(bench_lib, name, scope, group):
    assert bench_lib[0].place(name, scope) == group


def test_the_new_readers_reduce_a_traced_window(bench_lib, tmp_path,
                                                monkeypatch):
    """The four new readers over a hand-made window: a 512-token mid-prompt
    step whose program runs 50 ms, 20 of them in the scans, and a sampling
    step of 40 tokens and 10 ms, 4 of them in the cross half.  A program
    without the scopes and the counter (the parent's) returns None."""
    cross_trace, opwalk, _ = bench_lib
    config = published()
    ms = 1_000_000
    names = [n for n, _, _ in PLACED]
    scopes = [s for _, s, _ in PLACED]
    t0, t1 = 100 * ms, 200 * ms
    ops = [[0, t0 + 1 * ms, 15 * ms], [2, t0 + 17 * ms, 5 * ms],
           [3, t0 + 23 * ms, 20 * ms], [7, t0 + 44 * ms, 5 * ms],
           [0, t1 + 1 * ms, 2 * ms], [4, t1 + 4 * ms, 3 * ms],
           [5, t1 + 8 * ms, 1 * ms]]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"names": names, "scopes": scopes, "planes": [{
        "name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]}]}))
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint, moe_trace

        monkeypatch.setattr(moe_trace, "trace_path", lambda: str(path))
        steps = [{"kind": "prefill_chunk", "t_ns": t0 - 3 * ms,
                  "dur_ns": 60 * ms, "batch": 1, "tokens": 512, "ctx": 5632,
                  "head": 0, "cross_rows": 0},
                 {"kind": "prefill_chunk", "t_ns": t1 - 3 * ms,
                  "dur_ns": 20 * ms, "batch": 1, "tokens": 40, "ctx": 5672,
                  "head": 1, "cross_rows": 1}]
        run = {"t0": 0.0, "t1": 1.0, "events": steps, "config": config,
               "peaks": PEAKS, "metrics0": {}, "metrics1": {},
               "trace": {"modules": [[(t0, t0 + 50 * ms, "jit_prefill_step"),
                                      (t1, t1 + 10 * ms,
                                       "jit_prefill_step")]]}}
        read = {n: checkpoint.load_module("layer_metrics", n).read
                for n in ("step.selective_scan_device_pct",
                          "kernel.selective_scan_roofline",
                          "step.cross_half_device_pct",
                          "engine.cross_rows_pct")}
        assert abs(read["step.selective_scan_device_pct"](run)
                   - 100 * 22 / 60) < 1e-6
        assert abs(read["step.cross_half_device_pct"](run)
                   - 100 * 4 / 60) < 1e-6
        fam = bench_lib[2].family(config)
        floor = sum(fam.selective_scan_floor_s(config["model"], PEAKS, n, 1)[0]
                    for n in (512, 40))
        assert abs(read["kernel.selective_scan_roofline"](run)
                   - 100 * floor / 0.022) < 1e-6
        assert abs(read["engine.cross_rows_pct"](run) - 100 / 552) < 1e-9
        # the parent's program: no scopes in its trace, no `cross_rows`
        opwalk._MEMO.clear()  # noqa: SLF001
        opwalk._COMPACT.clear()  # noqa: SLF001
        path.write_text(json.dumps({
            "names": names, "scopes": [""] * len(names), "planes": [{
                "name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops",
                     "events": [o for o in ops if o[0] != 1]}]}]}))
        bare = dict(run, events=[
            {k: v for k, v in s.items() if k != "cross_rows"}
            for s in steps])
        bare_names = [n.replace("%ssm.conv.3", "%fusion.3") for n in names]
        path.write_text(json.dumps({
            "names": bare_names, "scopes": [""] * len(names), "planes": [{
                "name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": ops}]}]}))
        assert all(r(bare) is None for r in read.values())
        assert all(r(dict(bare, trace=None)) is None for r in read.values())
    finally:
        sys.path.remove(BENCH)


# -- ops that only move data (ISSUE 49) ---------------------------------------------- #

MOVED = [
    ("%dynamic-slice_bitcast_fusion.13 = bf16[2560,20480]{1,0} fusion(%p)",
     "copy"),
    ("%copy.7 = bf16[1,512,2560]{2,1,0} copy(%p)", "copy"),
    ("%dynamic-slice.3 = bf16[2560,5120]{1,0} dynamic-slice(%p, %i)", "copy"),
    ("%slice_copy_fusion = f32[1,512,640]{2,1,0} fusion(%p)", "copy"),
    ("%bitcast_add_fusion.15 = bf16[1,512,2560]{2,1,0} fusion(%p)", None),
    ("%dynamic-update-slice_fusion.2 = bf16[9,321,16,2,640] fusion(%p)",
     None),
    ("%while.4 = (s32[], bf16[1,512,2560]) while(%t)", None),
    ("%attn.core.8 = f32[1,512,5,2,128] custom-call(%q, %k)", None),
    ("%fusion.439 = f32[1,512,20480]{2,1,0} fusion(%p)", None),
    ("%copy-start.1 = (bf16[8], bf16[8], u32[]) copy-start(%p)", None),
]


@pytest.fixture(scope="module")
def copy_reader(bench_lib):
    return bench_module("layer_metrics", "step.copy_device_pct")


@pytest.mark.parametrize("name,group", MOVED, ids=[
    n.split(" = ")[0].strip("%") for n, _ in MOVED])
def test_an_op_that_only_moves_data_is_told_by_its_name(copy_reader, name,
                                                        group):
    # the scope says nothing: XLA's own copies carry none
    assert copy_reader.place(name, "") == group
    assert copy_reader.place(name, "jit(prefill_step)/while/body/mlp") == group


def test_the_copy_share_of_a_traced_window(bench_lib, copy_reader, tmp_path,
                                           monkeypatch):
    """Two steps of 50 and 10 ms; the first holds a weight copy of 8 ms and a
    plain copy of 2 ms inside a loop (the loop's own time is nobody's), the
    second a dynamic-slice of 0.5 ms: 10.5 of 60 ms.  A window without such
    ops reads 0.0, a run without a trace None."""
    _, opwalk, _ = bench_lib
    ms = 1_000_000
    names = [n for n, _ in MOVED]
    t0, t1 = 100 * ms, 200 * ms
    ops = [[6, t0, 50 * ms],  # the layer loop, its body's ops inside it
           [0, t0 + 1 * ms, 8 * ms], [8, t0 + 10 * ms, 20 * ms],
           [1, t0 + 31 * ms, 2 * ms], [4, t0 + 34 * ms, 10 * ms],
           [5, t0 + 45 * ms, 3 * ms],
           [7, t1 + 1 * ms, 6 * ms], [2, t1 + 8 * ms, ms // 2]]
    path = tmp_path / "trace.json"

    def window(events):
        opwalk._MEMO.clear()  # noqa: SLF001
        opwalk._COMPACT.clear()  # noqa: SLF001
        path.write_text(json.dumps({
            "names": names, "scopes": [""] * len(names), "planes": [{
                "name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": events}]}]}))

    monkeypatch.setattr(opwalk.moe_trace, "trace_path", lambda: str(path))
    steps = [{"kind": "prefill_chunk", "t_ns": t - 3 * ms, "dur_ns": 60 * ms,
              "tokens": n} for t, n in ((t0, 512), (t1, 40))]
    run = {"t0": 0.0, "t1": 1.0, "events": steps,
           "trace": {"modules": [[(t0, t0 + 50 * ms, "jit_prefill_step"),
                                  (t1, t1 + 10 * ms, "jit_prefill_step")]]}}
    window(ops)
    assert abs(copy_reader.read(run) - 100 * 10.5 / 60) < 1e-6
    window([o for o in ops if MOVED[o[0]][1] is None])
    assert copy_reader.read(run) == 0.0
    assert copy_reader.read(dict(run, trace=None)) is None
