"""falcon_h1 on the served path (ISSUE 59): the family's config keys and its
multipliers, its checkpoint names through the loader, the layer of BOTH
mixers (a Mamba-2 mixer and attention side by side from one norm, then a
dense feed-forward) against the plain reference (`benchmark/reference/
falcon_h1.py`: the recurrence, token by token), chunks of every bucket,
decode through pages AND slots, snapshots at a page boundary and inside a
chunk, pad rows, every fault of the reference past the test's limit, bf16,
both pools' reports, the layouts that refuse the family, and the older
caller of `hybrid._mamba` bit for bit.  Tiny sizes, float32, seeded weights:
`A_log`, `dt_bias` drawn as the family initialises them (a state that
REMEMBERS) and multipliers under which attention is SHARP, so that what the
benchmark's one draw hides from `correct` (PERF.md section 7) shows here.
"""

import contextlib
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import xla_ledger
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import KVCache, ModelConfig, init_params
from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.loader import load_params
from dynamo_tpu.models.quantization import matmul_any
from dynamo_tpu.ops import pallas_moe, ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
PAGE = 8
CONFIG = "falcon-h1-34b-h6"

# no multiplier a power of two: a fold into a weight would round
TINY = {
    "model_type": "falcon_h1", "vocab_size": 300, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_ssm": 64,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "attn_layer_indices": None, "hidden_act": "silu", "rope_theta": 10000.0,
    "rope_scaling": None, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "embedding_multiplier": 3.3, "lm_head_multiplier": 0.3,
    "key_multiplier": 2.5, "attention_in_multiplier": 1.2,
    "attention_out_multiplier": 0.7, "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 0.6, "ssm_multipliers": [0.9, 0.7, 1.3, 0.5, 1.1],
    "mlp_multipliers": [0.6, 0.4], "mlp_expansion_factor": 8,
    "num_logits_to_keep": 1,
}


def bench_module(kind_dir, name):
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint
    finally:
        sys.path.remove(BENCH)
    return checkpoint.load_module(kind_dir, name)


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "falcon_h1")


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.from_hf_config(TINY, name="tiny-falcon-h1")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(59), dtype=jnp.float32)


def reader_of(params, cfg):
    """`read(name)` over a param tree, under the family's tensor names (the
    loader's mapping, backwards)."""
    lay = params["par_layers"]
    flat = {"model.embed_tokens.weight": params["embed"],
            "model.final_layernorm.weight": params["final_norm"],
            "lm_head.weight": params["lm_head"].T}
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        m = p + "mamba."
        flat.update({
            p + "input_layernorm.weight": lay["norm"][i],
            p + "pre_ff_layernorm.weight": lay["mlp_norm"][i],
            m + "in_proj.weight": lay["in_proj"][i].T,
            m + "conv1d.weight": np.asarray(lay["conv_w"][i]).T[:, None],
            m + "conv1d.bias": lay["conv_b"][i],
            m + "dt_bias": lay["dt_bias"][i], m + "A_log": lay["A_log"][i],
            m + "D": lay["D"][i], m + "norm.weight": lay["gate_norm"][i],
            m + "out_proj.weight": lay["out_proj"][i].T})
        for n in "qkvo":
            flat[p + f"self_attn.{n}_proj.weight"] = lay["w" + n][i].T
        for n in ("gate", "up", "down"):
            flat[p + f"feed_forward.{n}_proj.weight"] = lay["w_" + n][i].T
    return lambda name: np.asarray(flat[name], np.float32)


def with_slots(pages, slots):
    """A table of pages [B, W] with each row's state columns behind it:
    [read, write] and, 0 where not given, the slots inside the chunk."""
    cols = np.zeros((len(pages), hybrid.STATE_COLS), np.int32)
    for row, given in zip(cols, slots):
        row[:len(given)] = given
    return jnp.asarray(np.concatenate(
        [np.asarray(pages, np.int32), cols], axis=1))


def table_for(n_tokens, slots, batch=1):
    """Pages 1.. a row, then the row's state slots."""
    pages = -(-n_tokens // PAGE)
    t = np.arange(1, 1 + batch * pages, dtype=np.int32).reshape(batch, pages)
    return with_slots(t, np.asarray(slots).reshape(batch, -1))


def logp(logits):
    return np.asarray(jax.nn.log_softmax(
        jnp.asarray(logits, jnp.float32), axis=-1))


def fresh_cache(cfg, tokens=128, slots=6, dtype=jnp.float32):
    return KVCache.create(cfg, 2 + -(-tokens // PAGE), PAGE, dtype,
                          state_slots=slots)


def prefill_all(cfg, params, tokens, chunk=None, kv=None, slot=1, bucket=None,
                dtype=jnp.float32):
    """Chunked prefill of one prompt through both pools (its state in slot
    `slot`), each chunk padded to `bucket` tokens: [(position, next-token
    logprobs)] a chunk, the cache."""
    T = len(tokens)
    chunk = chunk or T
    kv = kv if kv is not None else fresh_cache(cfg, T + 8 * PAGE, dtype=dtype)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        row = part + [0] * ((bucket or len(part)) - len(part))
        logits, kv = llama.forward_prefill(
            params, cfg, kv, jnp.asarray([row], jnp.int32),
            table_for(T + 8 * PAGE, [slot if s else 0, slot]),
            jnp.asarray([s], jnp.int32), jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv


def ref_logp(ref, cfg, params, tokens, model=TINY, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), model,
                       [np.asarray([tokens])], len(tokens), **controls)[0][0]


def prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(4, 290, n)]


TOL = 3e-4  # float32 on both sides; sums in another order


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- configuration ------------------------------------------------------------- #

def test_from_hf_config_reads_the_published_keys():
    """The catalog row's keys as published (72 layers) and as run (6): the
    model's name is its parameter count, every layer is in BOTH pools'
    counts, and the multipliers are fields, not weights."""
    run = published()
    model = dict(run["model"])
    model.update({k: v["published"] for k, v in run["reduced"].items()})
    c = ModelConfig.from_hf_config(model)
    assert c.layer_pattern == "P" * 72
    assert (c.num_kv_layers, c.state_spec.layers) == (72, 72)
    assert c.num_params() == 33_642_516_224
    assert (c.ssm_inner, c.ssm_conv_dim, c.ssm_groups, c.ssm_state,
            c.ssm_heads, c.ssm_head_dim, c.ssm_chunk) == (
        4096, 5120, 2, 256, 32, 128, 128)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim_) == (
        20, 4, 128)
    assert c.rope_theta == 1e11 and c.attention_rope and not c.is_moe
    assert (c.embedding_multiplier, c.lm_head_multiplier, c.key_multiplier,
            c.attention_in_multiplier, c.attention_out_multiplier,
            c.ssm_in_multiplier, c.ssm_out_multiplier) == (
        5.656854249492381, 0.0078125, 0.011048543456039804, 1.0, 0.0375,
        0.25, 0.08838834764831845)
    assert c.mlp_multipliers == (0.1767766952966369, 0.011160714285714284)
    # in_proj's 9248 outputs [z | x | B | C | dt], ssm_in_multiplier folded in
    assert [n for _, n in c.ssm_mup_vector] == [4096, 4096, 512, 512, 32]
    assert [m for m, _ in c.ssm_mup_vector] == [
        0.25 * m for m in model["ssm_multipliers"]]
    cut = ModelConfig.from_hf_config(run["model"])
    assert cut.num_params() == 5_254_594_112 == sum(
        int(np.prod(shape)) for _, shape, _ in bench_module(
            "checkpoints", "falcon_h1").tensors(run["model"]))
    # bf16, but dt_bias, A_log and D held in float32
    assert run["memory"]["weights_bytes"] - cut.num_params() * 2 == (
        6 * 3 * 32 * 2)
    spec = cut.state_spec
    assert spec.bytes_per_slot(2) == run["memory"]["state_bytes_per_slot"] == (
        6 * (32 * 128 * 256 * 4 + 120 * 128 * 2)) == 25_350_144
    assert spec.window_dims == (120, 128)
    assert spec.state_dims == (32, 128, 256)
    assert 6 * cut.cache_spec.bytes_per_token_layer(2) == (
        run["memory"]["kv_bytes_per_token"]) == 12_288
    shapes = jax.eval_shape(lambda: KVCache.create(cut, 64, 16,
                                                   state_slots=8))
    # all four arrays lead with the SAME layers
    assert shapes.k.shape == shapes.v.shape == (6, 64, 16, 4, 128)
    assert shapes.conv.shape == (6, 8, 120, 128)
    assert shapes.ssm.shape == (6, 8, 32, 128, 256)
    assert shapes.ssm.dtype == jnp.float32
    units = hybrid.units_of(cut.layer_pattern)
    assert units.has.tolist() == [[True]] * 6
    assert units.idx[:, 0].tolist() == list(range(6))
    flags = run["worker_flags"]
    assert run["memory"]["state_slots"] == flags["--num-state-slots"]
    assert run["memory"]["kv_pool_tokens"] == flags["--num-pages"] * 16
    assert run["memory"]["state_pool_bytes"] == (
        flags["--num-state-slots"] * 25_350_144)
    assert run["memory"]["kv_pool_bytes"] == flags["--num-pages"] * 16 * 12_288


def test_the_file_states_each_published_key_once_for_each_reader():
    """Top-level keys (what the driver's check reads) equal `model` (what
    the program gets); `num_hidden_layers` alone differs from the source; no
    width among the reduced."""
    run = published()
    model = dict(run["model"])
    assert model.pop("architectures") == ["FalconH1ForCausalLM"]
    assert model.pop("torch_dtype") == "bfloat16"
    assert {k: run[k] for k in model} == model
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [c for c in json.load(f)["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(run["reduced"]) == ["num_hidden_layers"]
    assert entry["source"] == run["source"]
    cut = run["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["run"], run["num_hidden_layers"]) == (
        72, 6, 6)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = [r for r in map(json.loads, f)
                if r["source_url"] == run["source"]]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert run[key] == value, key
    assert set(model) == set(row["config"])
    for key in ("weights", "multipliers", "tensor_names", "in_proj_order",
                "unread_keys"):
        assert run["assumed"][key]
    assert run["stands_for"] and run["memory"]["count"]


@pytest.mark.parametrize("bad,key", [
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"projectors_bias": True}, "projectors_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attn_layer_indices": [0, 2]}, "attn_layer_indices"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"mamba_rms_norm": False}, "mamba_rms_norm"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"sliding_window": 128}, "sliding_window"),
    ({"mamba_d_ssm": 128}, "mamba_d_ssm"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"ssm_multipliers": [1.0, 2.0]}, "ssm_multipliers"),
    ({"mlp_multipliers": [1.0, 2.0, 3.0]}, "mlp_multipliers"),
], ids=["attention-bias", "mamba-bias", "mlp-bias", "projectors-bias",
        "no-conv-bias", "act", "layer-list", "norm-before-gate", "no-norm",
        "rope-scaling", "window", "inner-width", "uneven-groups",
        "uneven-kv-heads", "two-mup-factors", "three-mlp-factors"])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    with pytest.raises(ValueError, match=f"falcon_h1: {key}"):
        ModelConfig.from_hf_config(dict(TINY, **bad))


@pytest.mark.parametrize("keys", [("mamba_d_ssm",), ("ssm_multipliers",)])
def test_another_model_type_with_the_family_s_keys_is_refused_by_key(keys):
    """Before this PR such a config.json reached the llama branch and died
    in the loader on a missing `post_attention_layernorm`."""
    other = {k: v for k, v in TINY.items()
             if k in keys or not k.startswith(("mamba_", "ssm_"))}
    with pytest.raises(ValueError, match="mamba_d_ssm, ssm_multipliers.*the "
                       "llama branch would build another model"):
        ModelConfig.from_hf_config(dict(other, model_type="falcon_h2"))
    plain = {k: v for k, v in other.items() if k not in keys}
    assert ModelConfig.from_hf_config(
        dict(plain, model_type="llama")).layer_pattern is None


def test_a_layer_of_both_stands_beside_no_layer_of_one(cfg):
    with pytest.raises(ValueError, match="beside no layer of one"):
        dataclasses.replace(cfg, layer_pattern="PMP")
    assert hybrid.kinds_of("PPP") == "P" and hybrid.kinds_of("M*E") == "M*E"


# -- checkpoint names through the loader ----------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """`benchmark/lib/checkpoint.py` + `checkpoints/falcon_h1.py` write the
    family's tensors; `models/loader.py` reads them into ONE stack, no
    multiplier folded into any; a chunked prefill over the loaded tree
    agrees with the reference reading the same file."""
    from safetensors import safe_open

    ckpt = bench_module("lib", "checkpoint")
    names = [n for n, _, _ in bench_module(
        "checkpoints", "falcon_h1").tensors(TINY)]
    for want in ("model.layers.2.mamba.conv1d.weight",
                 "model.layers.0.self_attn.k_proj.weight",
                 "model.layers.1.feed_forward.gate_proj.weight",
                 "model.layers.1.pre_ff_layernorm.weight", "lm_head.weight"):
        assert want in names
    assert not any("post_attention_layernorm" in n for n in names)
    # the convolution's taps and D are ONES beside the norm scales: at the
    # draw's 0.014 the taps left the whole half under the gated norm's eps
    # and the cell's `correct` blind to it (REVIEW of PR 59)
    assert {n.rsplit(".", 2)[-2 if n.endswith(".weight") else -1]
            for n, _, kind in bench_module(
                "checkpoints", "falcon_h1").tensors(TINY)
            if kind == "ones" and ".mamba." in n} == {"conv1d", "D", "norm"}
    model = dict(TINY, architectures=["FalconH1ForCausalLM"],
                 torch_dtype="bfloat16")
    ckpt.write({"model": model, "weights_seed": 5,
                "checkpoint": "falcon_h1"}, str(tmp_path))
    c = ModelConfig.from_pretrained(str(tmp_path))
    assert c.model_type == "falcon_h1" and c.layer_pattern == "PPP"
    p = load_params(str(tmp_path), c, dtype=jnp.float32)
    lay = p["par_layers"]
    assert set(p) == {"embed", "final_norm", "lm_head", "par_layers"}
    assert lay["conv_w"].shape == (3, 4, 128)
    assert lay["in_proj"].shape == (3, 64, 64 + 128 + 8)
    assert lay["A_log"].dtype == lay["dt_bias"].dtype == jnp.float32
    reader = safe_open(str(tmp_path / "model.safetensors"), framework="np")

    def read(n):
        return reader.get_tensor(n).astype(np.float32)

    assert (np.asarray(lay["conv_w"]) == 1).all() and (
        np.asarray(lay["D"]) == 1).all()
    # the checkpoint's bits, transposed and stacked: nothing multiplied in
    assert np.array_equal(np.asarray(lay["wk"][1]),
                          read("model.layers.1.self_attn.k_proj.weight").T)
    assert np.array_equal(np.asarray(p["lm_head"]), read("lm_head.weight").T)
    toks = prompt(40, 1)
    want = ref.forward(read, TINY, [np.asarray([toks])], len(toks))[0][0]
    for pos, got in prefill_all(c, p, toks, chunk=16)[0]:
        assert np.abs(got - want[pos]).max() < TOL


# -- the forward paths against the recurrence ---------------------------------------- #

def test_attention_is_sharp_and_the_state_remembers(cfg, params, ref):
    """What the benchmark's one draw lacks, this file's weights have: the
    last query's attention entropy is under half of log n, and a state lost
    64 tokens back still moves the answer."""
    toks = prompt(96, 5)
    entropy = []
    ref.forward(reader_of(params, cfg), TINY, [np.asarray([toks])], 1,
                entropy=entropy)
    assert len(entropy) == 3
    assert all(h < 0.5 * log_n for h, log_n in entropy)
    want = ref_logp(ref, cfg, params, toks)[-1]
    lost = ref_logp(ref, cfg, params, toks, fault_chunk=32,
                    state_not_carried=True)[-1]
    assert np.abs(want - lost).max() > 10 * TOL


@pytest.mark.parametrize("chunk,bucket", [
    (None, None), (32, None), (13, 16), (8, 8), (16, 16), (24, 32), (64, 64)],
    ids=["one-chunk", "two-chunks", "13-of-16", "bucket-8", "bucket-16",
         "24-of-32", "bucket-64"])
def test_chunked_prefill_agrees_with_the_reference(cfg, params, ref, chunk,
                                                   bucket):
    """Prefill in chunks of every bucket (13 of 16 and 24 of 32: a bucket's
    padding), then 8 decode steps through pages AND slots, against the
    reference's full forward over the text: logits, not tokens."""
    toks = prompt(72, 2)
    P = 64
    want = ref_logp(ref, cfg, params, toks)
    out, kv = prefill_all(cfg, params, toks[:P], chunk, bucket=bucket)
    for pos, got in out:
        assert np.abs(got - want[pos]).max() < TOL, pos
    table = table_for(P + 8 * PAGE, [1, 1])
    for i in range(8):
        logits, kv = llama.forward_decode(
            params, cfg, kv, jnp.asarray([toks[P + i]], jnp.int32),
            jnp.asarray([P + i], jnp.int32), table)
        assert np.abs(logp(logits)[0] - want[P + i]).max() < TOL, i


def test_bf16_serving_stays_near_the_reference(cfg, params, ref):
    """The served dtype: bf16 weights, residual, pages and windows, float32
    state, accumulation and multipliers; against the float32 reference over
    the SAME (bf16-rounded) weights, in two chunks and 4 decode steps."""
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim > 2 or a.ndim == 2 and a.shape[0] > 8 else a, params)
    rounded = jax.tree.map(lambda a: a.astype(jnp.float32), low)
    toks = prompt(40, 4)
    want = ref_logp(ref, cfg, rounded, toks)
    out, kv = prefill_all(cfg, low, toks[:32], 16, dtype=jnp.bfloat16)
    assert kv.k.dtype == kv.conv.dtype == jnp.bfloat16
    assert kv.ssm.dtype == jnp.float32
    diffs = [np.abs(got - want[pos]).max() for pos, got in out]
    table = table_for(32 + 8 * PAGE, [1, 1])
    for i in range(4):
        logits, kv = llama.forward_decode(
            low, cfg, kv, jnp.asarray([toks[32 + i]], jnp.int32),
            jnp.asarray([32 + i], jnp.int32), table)
        diffs.append(np.abs(logp(logits)[0] - want[32 + i]).max())
    assert TOL < max(diffs) < 0.15, diffs


def test_both_halves_read_the_same_normed_input(cfg, params):
    """x + s + a with s and a from ONE u: the layer's output less its two
    halves computed apart is the residual it came in with."""
    lp = jax.tree.map(lambda a: a[0], params["par_layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64), jnp.float32)
    kv = fresh_cache(cfg, 16)
    spec = cfg.state_spec
    u = llama.rms_norm(x, lp["norm"], cfg.rms_norm_eps)
    lens, zero = jnp.asarray([16]), jnp.zeros((1,), jnp.int32)
    s, *_ = hybrid._mamba(  # noqa: SLF001
        lp, u, cfg, jnp.zeros((1, 3, spec.conv_dim)),
        jnp.zeros((1, *spec.state_dims)), lens, PAGE)
    table = table_for(16, [0, 1])
    a, _, _ = hybrid._attention(  # noqa: SLF001
        lp, u, cfg, kv, 0, jnp.arange(16)[None], table[:, :-5], zero, lens,
        "xla")
    h = x + s + a
    (y,) = llama._feed_forward(lp, h, h, cfg)  # noqa: SLF001
    one = dataclasses.replace(cfg, num_hidden_layers=1, layer_pattern="P")
    got, _ = hybrid.layers(
        {**params, "par_layers": jax.tree.map(lambda a: a[:1],
                                              params["par_layers"])},
        one, kv._replace(**{f: getattr(kv, f)[:1] for f in kv._fields}), x,
        table, zero, lens)
    assert np.abs(np.asarray(got - (h + y))).max() < 1e-5


def test_pad_positions_leave_the_state_where_the_last_real_token_left_it(
        cfg, params):
    """The shared short step (4 rows of unequal lengths, one of them an
    empty pad row: the rows' states are sliced out of the pool ROW BY ROW,
    `hybrid.read_state`): each row gets its lone answer and writes back its
    lone run's state and pages, and what the pad positions HOLD moves
    nothing: with other tokens there the same program writes the same
    bits."""
    lens = [16, 5, 11]
    rows = [prompt(n, 20 + n) for n in lens]
    kv0 = fresh_cache(cfg, 4 * 16, slots=8)

    def pad(r, fill=0):
        return r + [fill] * (16 - len(r))

    table = with_slots([[1, 2], [3, 4], [5, 6], [0, 0]],
                       [[0, 1], [0, 2], [0, 3], [0, 0]])

    def shared(fill):
        return llama.forward_prefill(
            params, cfg, kv0,
            jnp.asarray([pad(r, fill) for r in rows] + [[fill] * 16],
                        jnp.int32),
            table, jnp.zeros((4,), jnp.int32),
            jnp.asarray(lens + [1], jnp.int32))

    logits, kv = shared(0)
    logits_b, kv_b = shared(123)
    assert np.array_equal(np.asarray(logits[:3]), np.asarray(logits_b[:3]))
    for a, b in ((kv.ssm, kv_b.ssm), (kv.conv, kv_b.conv)):
        assert np.array_equal(np.asarray(a[:, 1:]), np.asarray(b[:, 1:]))
    for i, r in enumerate(rows):
        lone, kv1 = llama.forward_prefill(
            params, cfg, kv0, jnp.asarray([pad(r)], jnp.int32),
            table[i:i + 1], jnp.zeros((1,), jnp.int32),
            jnp.asarray([len(r)], jnp.int32))
        assert np.abs(np.asarray(logits[i] - lone[0])).max() < 1e-5
        for both, own in ((kv.ssm, kv1.ssm), (kv.conv, kv1.conv)):
            assert np.abs(np.asarray(both[:, i + 1] - own[:, i + 1])
                          ).max() < 1e-5
        # the row's pages too: every layer owns both
        page = 2 * i + 1
        assert np.abs(np.asarray(kv.k[:, page] - kv1.k[:, page])).max() < 1e-5
    # an 11-token row padded to 16 holds what 11 decode steps leave, read
    # back from a slot with three rows in the step
    kvd = kv0
    for t, tok in enumerate(rows[2]):
        _, kvd = llama.forward_decode(
            params, cfg, kvd, jnp.asarray([tok], jnp.int32),
            jnp.asarray([t], jnp.int32),
            with_slots([[5, 6]], [[3 if t else 0, 3]]))
    assert np.abs(np.asarray(kv.ssm[:, 3] - kvd.ssm[:, 3])).max() < 1e-5
    assert np.abs(np.asarray(kv.conv[:, 3] - kvd.conv[:, 3])).max() < 1e-5
    # a pad row reads no state and writes the trash slot alone
    assert not np.asarray(kv.ssm[:, 4:]).any()


def test_states_wider_than_a_lane_tile_are_sliced_out_row_by_row():
    """`read_state`: N 256 (this family's) takes a slice a row, N 128 and a
    single row the gather they took; both read the same values."""
    pool = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 2, 4, 256))
    slots = jnp.asarray([3, 1, 4])
    for p in (pool, pool[..., :128]):
        got = hybrid.read_state(p, 1, slots)
        assert np.array_equal(np.asarray(got), np.asarray(p[1, slots]))
    text = str(jax.make_jaxpr(lambda p, s: hybrid.read_state(p, 1, s))(
        pool, slots))
    assert text.count("dynamic_slice") == 3 and "gather" not in text
    narrow = str(jax.make_jaxpr(lambda p, s: hybrid.read_state(p, 1, s))(
        pool[..., :128], slots))
    assert "gather" in narrow and "dynamic_slice" not in narrow


@pytest.fixture
def every16(monkeypatch):
    """Snapshots every 16 tokens, two of the tiny model's scan blocks of 8:
    as many as the engines' chunk here."""
    monkeypatch.setattr(hybrid, "SNAPSHOT_BLOCKS", 2)


# the tiny model at widths the scan's kernel holds: 2 heads of 128, one
# group, 256 state values, blocks of 128 tokens
KERNEL_TINY = dict(TINY, mamba_n_heads=2, mamba_d_head=128, mamba_d_ssm=256,
                   mamba_d_state=256, mamba_n_groups=1, mamba_chunk_size=128)


@pytest.mark.parametrize("form,tokens,at", [
    ("jnp", 64, (16, 32, 48)), ("jnp", 32, (8, 16, 24)),
    ("kernel", 512, (128, 256, 384))],
    ids=["every-interval", "short-row-every-page", "kernel-block-ends"])
def test_the_scan_hands_out_the_state_inside_a_chunk(cfg, params, every16,
                                                     monkeypatch, form,
                                                     tokens, at):
    """The slots named in the table's last columns take the state after each
    of `at`'s token counts, each what a prefill of that many tokens alone
    leaves, and the chunk's own slot its state after all of them; through
    the `jnp` blocks, and through the scan's kernel (interpreted; a model at
    widths its tiles hold, handing out at its blocks' ends)."""
    if form == "kernel":
        monkeypatch.setattr(hybrid, "SNAPSHOT_BLOCKS", 1)
        cfg = ModelConfig.from_hf_config(KERNEL_TINY, name="tiny-kernel")
        params = init_params(cfg, jax.random.PRNGKey(60), dtype=jnp.float32)
    assert hybrid._inside(cfg, tokens, PAGE) == at  # noqa: SLF001
    toks = prompt(tokens, 9)
    kv0 = fresh_cache(cfg, tokens, slots=8)

    def prefill(toks, slots):
        with pallas_moe.checked(interpret=True) if form == "kernel" else (
                contextlib.nullcontext()):
            return llama.forward_prefill(
                params, cfg, kv0, jnp.asarray([toks], jnp.int32),
                table_for(tokens, slots), jnp.zeros((1,), jnp.int32),
                jnp.asarray([len(toks)], jnp.int32))[1]

    kv = prefill(toks, [0, 1, 2, 3, 4])
    assert xla_ledger.path_choice("ssm_scan", rows=1, chunk=tokens) == (
        "pallas" if form == "kernel" else "xla")
    for slot, n in (*zip((2, 3, 4), at), (1, tokens)):
        alone = prefill(toks[:n], [0, 5])
        for pool, want in ((kv.ssm, alone.ssm), (kv.conv, alone.conv)):
            assert np.abs(np.asarray(pool[:, slot] - want[:, 5])).max() < 1e-5
    assert not np.asarray(kv.ssm[:, 5:]).any()


@pytest.mark.parametrize("rows,tokens", [(1, 128), (4, 200), (1, 512)],
                         ids=["1-block", "4-rows-2-blocks", "4-blocks"])
def test_the_scan_kernel_is_the_recurrence_at_this_family_s_widths(rows,
                                                                   tokens):
    """`ops.ssm.scan` through its kernel (interpreted) at this family's
    geometry, 16 heads of 128 a group and 256 state values, from a carried
    state, rows that end at unequal lengths (a zero step size past each):
    against the token-by-token loop and against the `jnp` blocks, every
    handed-out state compared."""
    from test_nemotron_h import recurrence, scan_in_form, scan_operands

    rng = np.random.default_rng(tokens)
    ops, S = scan_operands(rng, "kernel", (16, 128, 1, 256), rows, 128,
                           tokens)
    at = tuple(range(128, S, 128))
    y, h, hs = scan_in_form("kernel", ops, 128, at)
    assert len(hs) == len(at)
    recurrence(ops, tokens, at, y, h, hs)
    for got, want in zip((y, h, *hs), jax.tree.leaves(
            ssm.scan_blocks(*map(jnp.asarray, ops), 128, at))):
        assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_the_gated_norm_rides_the_scan_kernel():
    """`scan(.., gate=(z, w, eps))`: the kernel's epilogue is `gate_norm`
    of the scan's y over the groups (a grid step holds one whole group of
    the norm), as the `jnp` form applies it after its blocks; float32 to
    rounding, bf16 to the served dtype's last bit.  The gate may come with
    the array it is the first columns of (`in_proj`'s output)."""
    from test_nemotron_h import scan_in_form, scan_operands

    for dtype, tol in ((np.float32, 2e-5), (jnp.bfloat16, 1 / 64)):
        rng = np.random.default_rng(3)
        ops, S = scan_operands(rng, "kernel", (32, 128, 2, 256), 2, 128, 256,
                               dtype)
        z = jnp.asarray(rng.standard_normal((2, S, 4096)), dtype)
        w = jnp.asarray(1 + 0.1 * rng.standard_normal(4096), dtype)
        ops = tuple(map(jnp.asarray, ops))
        want, want_h, _ = ssm.scan(*ops, 128, (128,), gate=(z, w, 1e-5))
        with pallas_moe.checked(interpret=True):
            got, got_h, _ = ssm.scan(*ops, 128, (128,), gate=(z, w, 1e-5))
        assert got.shape == want.shape == (2, S, 4096)
        assert got.dtype == want.dtype == ops[0].dtype
        scale = float(jnp.abs(want.astype(jnp.float32)).max())
        assert np.abs(np.asarray(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))).max() < (
            tol * max(scale, 1.0))
        assert np.abs(np.asarray(got_h - want_h)).max() < (
            2e-5 if dtype is np.float32 else 0.05)
        # and z read where it lies, the first columns of a wider array (no
        # whole number of the kernel's blocks): the same bits
        whole = jnp.concatenate(
            [z, jnp.asarray(rng.standard_normal((2, S, 1056)), dtype)], -1)
        with pallas_moe.checked(interpret=True):
            there, _, _ = ssm.scan(*ops, 128, (128,),
                                   gate=(z, w, 1e-5, whole))
        assert np.array_equal(np.asarray(there.astype(jnp.float32)),
                              np.asarray(got.astype(jnp.float32)))


def test_every_fault_is_a_keyword_of_forward(ref):
    assert len(ref.FAULTS) == 13 and ref.CONTROLS == (
        "lower_precision", *ref.FAULTS)
    with pytest.raises(TypeError, match="no control"):
        ref.forward(None, TINY, [], 1, no_such_fault=True)
    assert ref.FAULT_CHUNK == 512 and ref.LOGPROB_TOL > 0 < ref.TIE_MARGIN


@pytest.mark.parametrize("control", [
    "lower_precision", "no_ssm_half", "no_attention_half",
    "halves_in_sequence", "no_mup", "no_key_multiplier", "norm_before_gate",
    "norm_ungrouped", "wrong_group", "no_lm_head_multiplier",
    "state_not_carried", "window_dropped", "pad_advances_state",
    "ignore_rope"])
def test_the_comparison_catches(cfg, params, ref, control):
    """What the benchmark's `correct` rests on, at the tiny size and with
    weights under which BOTH halves show: against the reference computed
    with one thing wrong (the chunk-boundary faults 8 tokens before the
    compared position), the model is out of the tolerance that it meets
    against the reference as written."""
    assert control in ref.CONTROLS
    toks = prompt(48, 3)
    (_, got), = prefill_all(cfg, params, toks)[0]
    assert np.abs(got - ref_logp(ref, cfg, params, toks)[-1]).max() < TOL
    wrong = ref_logp(ref, cfg, params, toks, fault_chunk=40,
                     **{control: True})
    assert np.abs(got - wrong[-1]).max() > 10 * TOL, control


# -- the older caller of the shared mixer ---------------------------------------------- #

def _mamba_before(lp, u, cfg, window, h0, chunk_lens, page_size):
    """`hybrid._mamba` as it stood before the multipliers (PR 58's tree), to
    the letter."""
    B, S, _ = u.shape
    d, nh, hp = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_head_dim
    G, N, dt_ = cfg.ssm_groups, cfg.ssm_state, u.dtype
    with jax.named_scope("ssm.in_proj"):
        zxd = matmul_any(u, lp["in_proj"], "bsh,hd->bsd").astype(dt_)
        z, xbc, dt = (zxd[..., :d], zxd[..., d:d + cfg.ssm_conv_dim],
                      zxd[..., d + cfg.ssm_conv_dim:])
    at = hybrid._inside(cfg, S, page_size)  # noqa: SLF001
    with jax.named_scope("ssm.conv"):
        xbc, window, wins = ssm.conv(xbc, window, lp["conv_w"], lp["conv_b"],
                                     chunk_lens, at)
    with jax.named_scope("ssm.scan"):
        step = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        step = jnp.where(llama._valid_rows(u, chunk_lens)[..., None],  # noqa: SLF001
                         step, 0.0)
        y, h, hs = ssm.scan(
            xbc[..., :d].reshape(B, S, nh, hp), step, -jnp.exp(lp["A_log"]),
            xbc[..., d:d + G * N].reshape(B, S, G, N),
            xbc[..., d + G * N:].reshape(B, S, G, N), lp["D"], h0,
            cfg.ssm_chunk, at)
        inside = list(zip(wins, hs))
    with jax.named_scope("ssm.gate_norm"):
        y = ssm.gate_norm(y.reshape(B, S, d), z, lp["gate_norm"], G,
                          cfg.rms_norm_eps)
    with jax.named_scope("ssm.out_proj"):
        return (matmul_any(y, lp["out_proj"], "bsd,dh->bsh").astype(dt_),
                window, h, inside)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_older_caller_of_the_mixer_gets_the_bits_it_got(dtype):
    """nemotron_h's layers call `hybrid._mamba` without a multiplier:
    output, window, state and hand-outs are bit for bit the old function's,
    and the jaxprs are the same text."""
    from test_nemotron_h import TINY as NEMOTRON

    c = ModelConfig.from_hf_config(NEMOTRON)
    assert c.ssm_mup_vector is None and c.ssm_out_multiplier == 1.0
    p = init_params(c, jax.random.PRNGKey(7), dtype=dtype)
    lp = jax.tree.map(lambda a: a[1], p["ssm_layers"])
    spec = c.state_spec
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    u = jax.random.normal(ks[0], (2, 32, 64), jnp.float32).astype(dtype)
    win = jax.random.normal(ks[1], (2, 3, spec.conv_dim)).astype(dtype)
    h0 = jax.random.normal(ks[2], (2, *spec.state_dims), jnp.float32)
    args = (lp, u, c, win, h0, jnp.asarray([32, 9]), PAGE)
    got, want = hybrid._mamba(*args), _mamba_before(*args)  # noqa: SLF001
    for g, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == v.dtype and np.array_equal(np.asarray(g),
                                                     np.asarray(v))

    def text(fn):
        return str(jax.make_jaxpr(
            lambda lp, u, w, h, n: fn(lp, u, c, w, h, n, PAGE))(
                lp, u, win, h0, jnp.asarray([32, 9])))

    assert text(hybrid._mamba) == text(_mamba_before)  # noqa: SLF001
    # and this family's call is another program: the multiply is there
    f = ModelConfig.from_hf_config(TINY)
    fp = jax.tree.map(lambda a: a[0], init_params(
        f, jax.random.PRNGKey(7), dtype=dtype)["par_layers"])
    fs = f.state_spec
    mine = str(jax.make_jaxpr(lambda lp, u, w, h, n: hybrid._mamba(  # noqa: SLF001
        lp, u, f, w, h, n, PAGE))(
            fp, u, jnp.zeros((2, 3, fs.conv_dim), dtype),
            jnp.zeros((2, *fs.state_dims)), jnp.asarray([32, 9])))
    assert mine.count(" mul ") > text(hybrid._mamba).count(" mul ")  # noqa: SLF001


# -- the engine: pages and a slot in every layer ------------------------------------- #

def engine_of(cfg, params, **over):
    ecfg = dict(page_size=PAGE, num_pages=96, max_num_seqs=4,
                max_prefill_tokens=16, max_model_len=160, num_state_slots=8)
    parallel = over.pop("parallel", None)
    tiered = over.pop("tiered", None)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32, parallel=parallel, tiered=tiered)


async def generate(engine, toks, n):
    out, lps = [], []
    async for d in engine.generate({
            "token_ids": toks,
            "sampling_options": {"temperature": 0.0, "logprobs": True},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}):
        assert d.get("finish_reason") != "error", d
        out += d.get("token_ids", [])
        lps += d.get("log_probs", [])
    return out, lps


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


@pytest.mark.parametrize("how", [{}, {"mixed_prefill_tokens": 16},
                                 {"decode_steps": 2, "decode_chain": 2}],
                         ids=["default", "mixed", "chained"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         every16, how):
    """Chunked prefill, a prefix hit at a snapshot AND at pages of the same
    depth, and the decode paths the family serves: the logprob of every
    greedy token against the reference's full forward pass over the text so
    far."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(40, 6)
        for tail in (prompt(5, 7), prompt(9, 8), prompt(5, 7)):
            await agrees(engine, ref, cfg, params, shared + tail, 5)
        first, second, third = events(engine, "admit")
        assert (first["cached"], first["kv_cached"]) == (0, 0)
        # 40 shared tokens: 5 pages cached, and the first prompt's tail row
        # left a snapshot at every page of it: both reach 40
        assert (second["cached"], second["kv_cached"]) == (40, 40)
        assert (third["cached"], third["kv_cached"]) == (40, 40)
        m = vars(engine.metrics())
        assert m["state_snapshot_hits_total"] == 2
        assert m["state_hit_tokens_shortened_total"] == 0
        assert m["state_slots_total"] == 7 and m["state_slots_running"] == 0
    finally:
        await engine.shutdown()
    chunks = events(engine, "prefill_chunk")
    assert chunks and all(
        {"tokens", "ctx", "batch", "bucket"} <= set(e) for e in chunks)


async def test_a_hit_is_as_deep_as_both_pools_reach(cfg, params, ref,
                                                    every16):
    """Pages reach past every snapshot (another document's snapshots evicted
    them all under the cached pages): a cold start, not the pages' 40, and
    it equals the uncached run."""
    engine = engine_of(cfg, params, num_state_slots=4)  # 3 slots
    try:
        doc = prompt(40, 31)
        await agrees(engine, ref, cfg, params, doc + prompt(4, 1), 1)
        st = engine.scheduler.state
        assert st.snapshots == 2 and st.running == 0
        await agrees(engine, ref, cfg, params, prompt(48, 32), 1)
        assert st.evictions_total >= 2
        await agrees(engine, ref, cfg, params, doc + prompt(6, 2), 2)
        last = events(engine, "admit")[-1]
        assert (last["kv_cached"], last["cached"]) == (40, 0)
        assert vars(engine.metrics())[
            "state_hit_tokens_shortened_total"] == 40
    finally:
        await engine.shutdown()


async def test_snapshots_at_a_page_boundary_and_inside_a_chunk_are_hit(
        cfg, params, ref, every16):
    """64-token chunks, a state handed out every 16 tokens and, in a prompt's
    tail row, every page of 8 (`tests/test_nemotron_h.py` has the positions'
    arithmetic).  A request that shares 40 tokens resumes at 32, INSIDE the
    first request's first chunk; one that shares 116 at 112, a page boundary
    of its tail row: both pools agree on the depth the state allows, and
    each answer is the reference's, as the cold run's was."""
    engine = engine_of(cfg, params, max_prefill_tokens=64, num_state_slots=16)
    try:
        doc = prompt(118, 61)
        await agrees(engine, ref, cfg, params, doc, 1)
        assert [e["tokens"] for e in events(engine, "state_store")] == [
            16, 32, 48, 64, 88, 96, 104, 112]
        await agrees(engine, ref, cfg, params, doc[:40] + prompt(9, 1), 2)
        await agrees(engine, ref, cfg, params, doc[:116] + prompt(20, 2), 2)
        assert [(a["cached"], a["kv_cached"])
                for a in events(engine, "admit")] == [
            (0, 0), (32, 40), (112, 112)]
    finally:
        await engine.shutdown()


async def test_the_engine_reports_both_pools_from_their_descriptions(cfg,
                                                                     params):
    engine = engine_of(cfg, params)
    try:
        cache, state = engine.cache_report(), engine.state_report()
        # every layer in BOTH: 3 layers of pages, 3 of slots
        assert (cache["kind"], cache["layers"]) == ("kv", 3)
        assert cache["bytes_per_token"] == 3 * 2 * 2 * 16 * 4
        assert cache["pool_bytes"] == 96 * PAGE * cache["bytes_per_token"]
        spec = cfg.state_spec
        assert (state["kind"], state["layers"], state["slots"]) == (
            "ssm", 3, 8)
        # a [3, 96] window as 3 tiles of 128 and an [8, 8, 16] state
        assert state["window"] == [3, 128] and state["state"] == [8, 8, 16]
        assert state["bytes_per_slot"] == spec.bytes_per_slot(4) == 3 * (
            3 * 128 * 4 + 8 * 8 * 16 * 4)
        assert state["pool_bytes"] == 8 * state["bytes_per_slot"]
        assert state["snapshot_every"] == hybrid.snapshot_tokens(cfg) == 8
        assert engine.kv.ssm.shape == (3, 8, 8, 8, 16)
        assert engine.kv.k.shape[0] == engine.kv.conv.shape[0] == 3
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "serving mesh.*falcon_h1"),
    ({"parallel": {"pp": 2}, "max_prefill_tokens": 160},
     "serving mesh.*falcon_h1"),
    ({"parallel": {"sp": 2}, "max_prefill_tokens": 160},
     "serving mesh.*falcon_h1"),
    ({"parallel": {"dp": 2}, "kv_partition": True}, "serving mesh"),
    ({"fuse_projections": True}, "fuse_projections"),
    ({"quantization": "int8"}, "int8"),
    ({"park_max_pages": 8}, "parking"),
    ({"tiered": object()}, "KVBM.*falcon_h1"),
    ({"speculative_ngram_k": 3}, "speculative-ngram-k.*falcon_h1"),
    ({"decode_continuous": True, "decode_steps": 2},
     "decode-continuous.*falcon_h1"),
    ({"page_size": 6}, "snapshot interval"),
    ({"num_state_slots": 2}, "num_state_slots"),
], ids=["tp", "pp", "sp", "partitioned-pool", "fused-projections", "int8",
        "parking", "kvbm-tier", "speculative", "continuous", "page-size",
        "too-few-slots"])
def test_paths_that_cannot_carry_a_state_refuse_the_family(cfg, params, how,
                                                           match):
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how)
    if "parallel" in how:
        how["parallel"] = ParallelConfig(**how["parallel"])
    with pytest.raises(ValueError, match=match):
        engine_of(cfg, params, **how)


def test_step_kinds_without_a_state_refuse_the_family_by_name(cfg, params):
    kv = fresh_cache(cfg)
    toks = jnp.zeros((1, 4), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="draft-verify.*falcon_h1"):
        llama.forward_verify(params, cfg, kv, toks, table_for(8, [0, 1]),
                             one, one + 4)
    with pytest.raises(ValueError, match="embedding forward.*falcon_h1"):
        llama.forward_embed(params, cfg, toks, one + 4)
    with pytest.raises(ValueError, match="decode block.*falcon_h1"):
        llama.decode_block_scan(params, cfg, kv, one, one,
                                table_for(8, [0, 1]), 2, 64, None, ())
    # expert counters of a pattern with no expert layer: refused by name (it
    # was an IndexError behind the engine's own `carries_moe_stats`)
    with pytest.raises(ValueError, match="moe_stats.*no expert layer"):
        hybrid.layers(params, cfg, kv, jnp.zeros((1, 4, cfg.hidden_size)),
                      table_for(8, [0, 1]), one, one + 4, moe_stats=True)
    from dynamo_tpu.disagg.transfer import KvLayout

    stub = type("Stub", (), {"model_cfg": cfg, "_kv_dtype": jnp.bfloat16,
                             "cfg": EngineConfig(page_size=PAGE)})
    with pytest.raises(ValueError, match="disagg KV transfer.*falcon_h1"):
        KvLayout.of_engine(stub)
