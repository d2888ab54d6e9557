"""lfm2_moe on the served path (ISSUE 55): the family's config keys and what
is refused by key, a state that is a window ALONE in the state slots (no
second array), layers of (mixer kind, feed-forward kind) walked by the one
loop of `models/laguna.py` in a bounded number of bodies, QK-norm before the
rope, the sigmoid router's choosing bias and its denominator, `ops/ssm.conv`
with and without its bias and activation, the served path against the plain
reference (`benchmark/reference/lfm2_moe.py`) through chunked prefill,
snapshots, decode through pages and slots, every fault of the reference, the
layouts that refuse the family, and the benchmark's count.  Tiny sizes
(hidden 64, 4 heads over 2 KV heads of 16, 8 experts top 2, 3 taps; 9 layers
= the cell's cut of published layers 1-9), float32, seeded weights, CPU.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import KVCache, ModelConfig, init_params
from dynamo_tpu.models import hybrid, laguna, llama
from dynamo_tpu.models.loader import load_params
from dynamo_tpu.ops import ssm
from test_nemotron_h import (BENCH, PEAKS, TOL, bench_module, logp, prompt,
                             with_slots)

PAGE = 8
CELL = "lfm2-24b-a2b-h9"
# the published list: two conv layers, then [attention, conv, conv, conv]
# with the last period cut after its first conv
PUBLISHED = ["conv", "conv"] + (["full_attention"] + ["conv"] * 3) * 9 + [
    "full_attention", "conv"]


def tiny(n_layers=9, first=1, dense=1, **over):
    """`n_layers` of the published list from layer `first` on (the cell:
    layers 1-9), at tiny widths."""
    model = {
        "model_type": "lfm2_moe", "vocab_size": 300, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": n_layers,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "max_position_embeddings": 512, "norm_eps": 1e-5, "conv_L_cache": 3,
        "conv_bias": False, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "num_dense_layers": dense,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "layer_types": PUBLISHED[first:first + n_layers],
    }
    model.update(over)
    return model


TINY = tiny()


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "lfm2_moe")


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.from_hf_config(TINY, name="tiny-lfm2")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(55), dtype=jnp.float32)


FFN = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
        ("wo", "out_proj"))


def tensors_of(params, cfg):
    """The param tree under the family's tensor names (the loader's mapping,
    backwards)."""
    flat = {"model.embed_tokens.weight": params["embed"],
            "model.embedding_norm.weight": params["final_norm"]}
    for stack, ((kind, mlp, _), ids) in laguna.stacks_of(cfg).items():
        lay = params[stack]
        for j, i in enumerate(ids):
            p = f"model.layers.{i}."
            flat[p + "operator_norm.weight"] = lay["attn_norm"][j]
            flat[p + "ffn_norm.weight"] = lay["mlp_norm"][j]
            if kind == "conv":
                flat[p + "conv.in_proj.weight"] = lay["in_proj"][j].T
                flat[p + "conv.conv.weight"] = lay["conv_w"][j].T[:, None, :]
                flat[p + "conv.out_proj.weight"] = lay["out_proj"][j].T
            else:
                for k, n in ATTN:
                    flat[p + f"self_attn.{n}.weight"] = lay[k][j].T
                flat[p + "self_attn.q_layernorm.weight"] = (
                    lay["q_head_norm"][j])
                flat[p + "self_attn.k_layernorm.weight"] = (
                    lay["k_head_norm"][j])
            f = p + "feed_forward."
            if mlp == "dense":
                flat.update({f + n + ".weight": lay[k][j].T for k, n in FFN})
                continue
            flat[f + "gate.weight"] = lay["router"][j].T
            flat[f + "expert_bias"] = lay["router_bias"][j]
            for e in range(cfg.num_experts):
                flat.update({f + f"experts.{e}.{n}.weight": lay[k][j][e].T
                             for k, n in FFN})
    return {k: np.asarray(v, np.float32) for k, v in flat.items()}


def reader_of(params, cfg):
    flat = tensors_of(params, cfg)
    return lambda name: flat[name]


def table_for(n_tokens, slots, batch=1):
    """Pages 1.. a row, then the row's state slots."""
    pages = -(-n_tokens // PAGE)
    t = np.arange(1, 1 + batch * pages, dtype=np.int32).reshape(batch, pages)
    return with_slots(t, np.asarray(slots).reshape(batch, -1))


def fresh_cache(cfg, tokens=128, slots=8):
    return KVCache.create(cfg, 2 + -(-tokens // PAGE), PAGE, jnp.float32,
                          state_slots=slots)


# one compile a (config, shape), not a trace a call
forward_prefill = jax.jit(llama.forward_prefill, static_argnums=(1,))
forward_decode = jax.jit(llama.forward_decode, static_argnums=(1,))


def prefill_all(cfg, params, tokens, chunk=None, kv=None, slot=1,
                inside=()):
    """Chunked prefill of one prompt through both pools (its state in slot
    `slot`; `inside`: slots for the windows handed out inside a chunk):
    [(position, next-token logprobs)] a chunk, the cache."""
    T = len(tokens)
    chunk = chunk or T
    kv = kv if kv is not None else fresh_cache(cfg, T + 8 * PAGE)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        logits, kv = forward_prefill(
            params, cfg, kv, jnp.asarray([part], jnp.int32),
            table_for(T + 8 * PAGE, [slot if s else 0, slot, *inside]),
            jnp.asarray([s], jnp.int32), jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv


def ref_logp(ref, cfg, params, tokens, model=TINY, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), model,
                       [np.asarray([tokens])], len(tokens), **controls)[0][0]


def published():
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    return row


# -- the configuration ---------------------------------------------------------- #

def test_from_hf_config_counts_the_published_model_and_the_cut():
    """The catalog row whole: 40 layers, 30 conv and 10 attention, 2 dense,
    23.9 B parameters; the cell's cut: 9 layers, 7 conv, 2 attention, 1
    dense, 5,178 M (ISSUE 55's arithmetic); the slot is 7 x [2, 2048] bf16."""
    row = catalog_row()["config"]
    assert row["layer_types"] == PUBLISHED
    whole = ModelConfig.from_hf_config(row)
    assert (whole.conv_layers, whole.num_kv_layers) == (30, 10)
    assert whole.num_moe_layers == 38 and whole.qk_norm
    assert whole.tie_word_embeddings and whole.head_dim_ == 64
    assert 23.8e9 < whole.num_params() < 24.0e9
    cut = ModelConfig.from_hf_config(published()["model"])
    assert (cut.num_hidden_layers, cut.conv_layers, cut.num_kv_layers,
            cut.num_moe_layers) == (9, 7, 2, 8)
    assert cut.layer_types == tuple(PUBLISHED[1:10])
    assert 5.17e9 < cut.num_params() < 5.19e9
    spec = cut.state_spec
    assert (spec.layers, spec.state_dims, spec.conv_dim, spec.conv_kernel,
            spec.window_dims) == (7, (), 2048, 3, (32, 128))
    assert not spec.recurrent and spec.bytes_per_slot(2) == 57344
    assert cut.cache_spec.bytes_per_token_layer(2) * cut.num_kv_layers == 4096
    assert (cut.moe_scoring, cut.moe_n_group, cut.moe_norm_eps,
            cut.moe_routed_scale) == ("sigmoid", 1, 1e-6, 1.0)
    assert cut.rope_theta == 1e6 and cut.rms_norm_eps == 1e-5


def test_the_file_states_each_published_key_once_for_each_reader():
    """The configuration's file holds the catalog row's keys at its top
    level (the benchmark's check reads them there) and under `model` (what
    the program loads): the two are equal, and only `reduced`'s keys differ
    from the row."""
    row, conf = catalog_row()["config"], published()
    model = conf["model"]
    for key, value in row.items():
        assert conf[key] == model[key], key
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert set(conf["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                    "layer_types"}
    assert conf["layer_types"] == row["layer_types"][1:10]
    assert (conf["num_hidden_layers"], conf["num_dense_layers"]) == (9, 1)
    extra = set(model) - set(row)
    assert extra == {"architectures", "torch_dtype"}
    cfg = ModelConfig.from_hf_config(model)
    mem = conf["memory"]
    assert mem["weights_bytes"] == 2 * cfg.num_params()
    assert mem["kv_bytes_per_token"] == 4096
    assert mem["state_bytes_per_slot"] == 57344


@pytest.mark.parametrize("bad,key", [
    ({"layer_types": ["conv"] * 8}, "layer_types"),
    ({"layer_types": ["conv"] * 8 + ["sliding_attention"]}, "layer_types"),
    ({"num_dense_layers": 10}, "num_dense_layers"),
    ({"conv_L_cache": None}, "conv_L_cache"),
    ({"conv_bias": True}, "conv_bias"),
    ({"num_experts": 0}, "num_experts"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rms_norm_eps": 1e-6}, "rms_norm_eps"),
    ({"sliding_window": 128}, "sliding_window"),
    ({"rope_scaling": {"rope_type": "yarn"}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_parameters"),
    ({"rope_parameters": {"rope_theta": 1e6, "partial_rotary_factor": 0.5}},
     "rope_parameters"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    with pytest.raises(ValueError, match=f"lfm2_moe: {key}"):
        ModelConfig.from_hf_config(tiny(**bad))


def test_another_family_with_conv_layers_is_refused_by_key():
    with pytest.raises(ValueError, match="conv_L_cache"):
        ModelConfig.from_hf_config(dict(TINY, model_type="lfm2"))


# -- the layer loop --------------------------------------------------------------- #

@pytest.mark.parametrize("n_layers,first,dense,segments,conv,attn", [
    (40, 0, 2, 4, 3, 2), (9, 1, 1, 2, 2, 1), (13, 1, 1, 2, 2, 1),
], ids=["published-40", "the-cut-9", "three-periods"])
def test_the_loop_walks_the_list_in_a_bounded_number_of_bodies(
        n_layers, first, dense, segments, conv, attn):
    """The published 40 layers: the leading dense run of two conv layers
    (one inner scan), ONE scan over the nine periods [attention, conv x 3],
    and the cut last period (attention, conv): four segments, three conv
    bodies and two attention bodies, whatever the depth.  The 9 kept layers:
    the dense conv layer and one scan over two periods."""
    model = tiny(n_layers, first, dense)
    cfg = ModelConfig.from_hf_config(model)
    assert len(laguna.plan(cfg.layer_kinds)) == segments
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    kv = jax.eval_shape(lambda: fresh_cache(cfg))
    jaxpr = jax.make_jaxpr(
        lambda p, kv, x, t: laguna.layers(
            p, cfg, kv, x, jnp.arange(16)[None], t, jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 16, jnp.int32)))(
        shapes, kv, jnp.zeros((1, 16, 64)), table_for(64, [0, 1]))
    assert "cond[" not in str(jaxpr)
    from test_laguna import layer_bodies

    assert (layer_bodies(jaxpr.jaxpr, "sconv.out_proj"),
            layer_bodies(jaxpr.jaxpr, "attn.out")) == (conv, attn)
    # every layer keeps its place and its index in its kind's stack
    order = [s.first_layer + t * s.period_len + r.offset + j
             for s in laguna.plan(cfg.layer_kinds) for t in range(s.periods)
             for r in s.runs for j in range(r.count)]
    assert order == list(range(n_layers))


def test_each_layer_lands_in_its_own_row_of_its_pool(cfg):
    """The page pool holds the attention layers alone and the slot pool the
    conv layers alone: a layer's row is its rank among its mixer's."""
    kv_rows, state_rows = laguna._rows(cfg)
    assert list(kv_rows[[1, 5]]) == [0, 1]
    assert list(state_rows[[0, 2, 3, 4, 6, 7, 8]]) == list(range(7))
    from test_laguna import TINY as LAGUNA

    assert laguna._rows(ModelConfig.from_hf_config(LAGUNA)) == (None, None)
    kv = fresh_cache(cfg)
    assert kv.k.shape[0] == 2 and kv.conv.shape[:2] == (7, 8)
    assert kv.ssm is None and len(jax.tree.leaves(kv)) == 3


# -- ops/ssm.conv ------------------------------------------------------------------ #

def _conv_before(xbc, window, w, b, lens, at=()):
    """`ops.ssm.conv` as it stood before the bias and the activation became
    arguments (PR 54's tree), to the letter."""
    k1 = window.shape[1]
    S = xbc.shape[1]
    padded = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
    acc = b.astype(jnp.float32)
    for j in range(k1 + 1):
        acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                     * w[j].astype(jnp.float32))
    new = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, k1, 0))(
        padded, lens)
    inside = [padded[:, t:t + k1].astype(window.dtype) for t in at]
    return (jax.nn.silu(acc).astype(xbc.dtype), new.astype(window.dtype),
            inside)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("caller", ["mamba2", "mamba1"])
def test_the_older_callers_of_conv_get_the_bits_they_got(caller, dtype):
    """Mamba-2's caller (`models/hybrid.py`: x, B and C, 4 taps) and
    Mamba-1's (`models/phi4flash.py`: x alone) pass a bias and take the
    default silu: output, window and hand-outs are bit for bit the old
    function's, and the jaxprs are the same text."""
    C, K = (48, 4) if caller == "mamba2" else (32, 4)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 24, C), jnp.float32).astype(dtype)
    win = jax.random.normal(ks[1], (2, K - 1, C), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[2], (K, C), jnp.float32).astype(dtype)
    b = jax.random.normal(ks[3], (C,), jnp.float32).astype(dtype)
    lens = jnp.asarray([24, 9], jnp.int32)
    got = ssm.conv(x, win, w, b, lens, (8, 16))
    want = _conv_before(x, win, w, b, lens, (8, 16))
    for g, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == v.dtype and np.array_equal(np.asarray(g),
                                                     np.asarray(v))
    assert str(jax.make_jaxpr(lambda *a: ssm.conv(*a, (8, 16)))(
        x, win, w, b, lens)) == str(jax.make_jaxpr(
            lambda *a: _conv_before(*a, (8, 16)))(x, win, w, b, lens))


def test_conv_without_bias_and_activation_is_the_plain_sum():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (1, 10, 8), jnp.float32)
    win = jax.random.normal(ks[1], (1, 2, 8), jnp.float32)
    w = jax.random.normal(ks[2], (3, 8), jnp.float32)
    out, new, inside = ssm.conv(x, win, w, None, jnp.asarray([7]), (4,),
                                act=None)
    seq = np.concatenate([np.asarray(win), np.asarray(x)], axis=1)[0]
    want = np.stack([sum(seq[t + j] * np.asarray(w)[j] for j in range(3))
                     for t in range(10)])
    assert np.allclose(np.asarray(out)[0], want, atol=1e-6)
    assert np.array_equal(np.asarray(new)[0], seq[7:9])  # the 7 real tokens'
    assert np.array_equal(np.asarray(inside[0])[0], seq[4:6])


# -- the served path against the reference ----------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """The benchmark's checkpoint layout (`benchmark/checkpoints/
    lfm2_moe.py`), written at tiny size, loads through the loader under the
    family's tensor names, and serves what the reference computes from the
    same file."""
    from safetensors.numpy import save_file

    layout = bench_module("checkpoints", "lfm2_moe")
    cfg = ModelConfig.from_hf_config(TINY, name="tiny-lfm2")
    rng = np.random.default_rng(55)
    flat = {}
    for name, shape, kind in layout.tensors(TINY):
        flat[name] = (np.ones(shape, np.float32) if kind == "ones" else
                      (rng.standard_normal(shape) * 0.15).astype(np.float32))
    assert set(flat) == set(tensors_of(init_params(
        cfg, jax.random.PRNGKey(0), jnp.float32), cfg))
    save_file(flat, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as f:
        json.dump(TINY, f)
    params = load_params(str(tmp_path), cfg, dtype=jnp.float32)
    assert params["conv_layers"]["conv_w"].shape == (6, 3, 64)
    assert params["full_layers"]["router_bias"].dtype == jnp.float32
    assert "lm_head" not in params
    toks = prompt(40, 3)
    want = ref.forward(lambda n: flat[n], TINY, [np.asarray([toks])],
                       len(toks))[0][0]
    got, _ = prefill_all(cfg, params, toks, 16)
    for pos, lp in got:
        assert np.abs(lp - want[pos]).max() < TOL, pos


@pytest.mark.parametrize("chunk", [None, 32, 16, 13, 8],
                         ids=["one-chunk", "32s", "16s", "13s", "8s"])
def test_chunked_prefill_agrees_with_the_reference(cfg, params, ref, chunk):
    """One chunk, and chunks of every bucket (and of no bucket: 13), each
    handing the window on through the slot: the logits after every chunk
    against the reference's full forward pass."""
    toks = prompt(61, 1)
    want = ref_logp(ref, cfg, params, toks)
    got, kv = prefill_all(cfg, params, toks, chunk)
    for pos, lp in got:
        assert np.abs(lp - want[pos]).max() < TOL, pos
    assert kv.ssm is None


def test_prefill_then_decode_through_pages_and_slots(cfg, params, ref):
    """A prompt that ends inside a chunk, then 6 tokens one at a time
    (`forward_decode`: a chunk of one through the same loop, the window read
    from and written to the row's slot): each step's logits against the
    reference over the text so far."""
    toks = prompt(27, 2)
    got, kv = prefill_all(cfg, params, toks, 16)
    text = list(toks)
    lp = got[-1][1]
    for _ in range(6):
        want = ref_logp(ref, cfg, params, text)[-1]
        assert np.abs(lp - want).max() < TOL, len(text)
        text.append(int(want.argmax()))
        logits, kv = forward_decode(
            params, cfg, kv, jnp.asarray(text[-1:], jnp.int32),
            jnp.asarray([len(text) - 1], jnp.int32),
            table_for(27 + 8 * PAGE, [1, 1]))
        lp = logp(logits)[0]


def test_a_snapshot_at_a_page_boundary_gives_the_cold_runs_bits(cfg, params):
    """A chunk of 32 hands its window out after 8, 16 and 24 tokens (pages)
    into slots 2-4 and leaves its own in slot 1.  A second sequence that
    shares the 32 tokens (its pages, and slot 1 as its snapshot) runs the
    cold run's last chunk from the same window: the cold run's logits BIT
    FOR BIT.  One that resumes INSIDE the chunk, from the snapshot at 16
    (slot 3), runs steps of other shapes than the cold run's, whose sums
    round otherwise: the cold run's logits to rounding, from a window that
    is the one a cold 16-token chunk leaves, to rounding."""
    toks = prompt(45, 5)
    pages = 45 + 8 * PAGE

    def step(kv, s, n, slots):
        logits, kv = forward_prefill(
            params, cfg, kv, jnp.asarray([toks[s:s + n]], jnp.int32),
            table_for(pages, slots), jnp.asarray([s], jnp.int32),
            jnp.asarray([n], jnp.int32))
        return logp(logits)[0], kv

    _, shared = step(fresh_cache(cfg, pages), 0, 32, [0, 1, 2, 3, 4])
    cold, kv = step(shared, 32, 13, [1, 5])
    assert kv.ssm is None
    warm, _ = step(shared, 32, 13, [1, 6])  # a reader of the same snapshot
    assert np.array_equal(warm, cold)
    # the window handed out after 16 tokens is a 16-token chunk's own
    _, kv16 = prefill_all(cfg, params, toks[:16], 16)
    assert np.allclose(np.asarray(shared.conv[:, 3]),
                       np.asarray(kv16.conv[:, 1]), atol=1e-5)
    assert not np.allclose(np.asarray(shared.conv[:, 3]),
                           np.asarray(shared.conv[:, 4]), atol=1e-3)
    _, kv3 = step(shared, 16, 16, [3, 7])
    inside, _ = step(kv3, 32, 13, [7, 7])
    assert np.abs(inside - cold).max() < TOL


def test_narrow_heads_are_stored_as_whole_lane_tiles(ref):
    """4 KV heads of 64 are two lane tiles a token, the cell's 8 four: the
    pool's plane is [tiles, 128], the same values in the same order as
    [heads, 64]; heads that fill no whole tiles (the tiny 2 x 16) stay as
    they are.  Chunked prefill through such a pool agrees with the
    reference, and the prefill kernel (interpreted) reads it as stored, bit
    for bit what it reads from [heads, 64]."""
    from dynamo_tpu.ops.paged_attention import prefill_attention
    from dynamo_tpu.ops.pallas_attention import (packed_plane,
                                                 prefill_attention_pallas)

    spec = ModelConfig.from_hf_config(published()["model"]).cache_spec
    assert (spec.heads, spec.width, spec.packed) == (8, 64, True)
    assert spec.plane_dims == ((4, 128),) * 2
    spec = ModelConfig.from_hf_config(TINY).cache_spec
    assert not spec.packed and spec.plane_dims == ((2, 16),) * 2
    model = tiny(hidden_size=256, num_key_value_heads=4)
    wide = ModelConfig.from_hf_config(model)
    assert wide.head_dim_ == 64 and wide.cache_spec.plane_dims == (
        (2, 128),) * 2
    # it turns on the head width and the one loop that carries the plane,
    # not on the family: a dense model of the same heads keeps [heads, 64]
    dense = ModelConfig(hidden_size=256, num_attention_heads=4,
                        num_key_value_heads=4, num_hidden_layers=2,
                        intermediate_size=64, vocab_size=64)
    assert dense.head_dim_ == 64 and dense.layer_kinds is None
    assert dense.cache_spec.plane_dims == ((4, 64),) * 2
    p = init_params(wide, jax.random.PRNGKey(5), jnp.float32)
    toks = prompt(40, 13)
    want = ref_logp(ref, wide, p, toks, model)
    got, kv = prefill_all(wide, p, toks, 16)
    assert kv.k.shape[3:] == (2, 128)
    for pos, lp in got:
        assert np.abs(lp - want[pos]).max() < TOL, pos
    B, S, H, nkv, hd, P = 2, 32, 8, 4, 64, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(key, (B, S, n, hd), jnp.float32)
               for key, n in zip(ks, (H, nkv, nkv)))
    kp, vp = (jax.random.normal(key, (2, P, 16, nkv, hd), jnp.float32)
              for key in ks[3:])
    args = (jnp.asarray(np.arange(1, 17).reshape(B, 8), jnp.int32),
            jnp.asarray([48, 35], jnp.int32), jnp.asarray([32, 20], jnp.int32))
    tiles = (2, P, 16, *packed_plane(nkv, hd))
    pk, pv = kp.reshape(tiles), vp.reshape(tiles)
    real = (np.arange(S)[None] < np.asarray(args[2])[:, None])[..., None, None]
    xla = prefill_attention(q, k, v, kp, vp, *args, impl="xla", layer=1)
    assert np.array_equal(np.asarray(prefill_attention(
        q, k, v, pk, pv, *args, impl="xla", layer=1, packed=True)),
        np.asarray(xla))
    stored = prefill_attention_pallas(q, k, v, pk, pv, *args, layer=1,
                                      packed=True, interpret=True)
    heads = prefill_attention_pallas(q, k, v, kp, vp, *args, layer=1,
                                     interpret=True)
    assert np.array_equal(np.where(real, stored, 0), np.where(real, heads, 0))
    assert np.abs(np.where(real, stored - xla, 0)).max() < 1e-5
    with pytest.raises(ValueError, match="lane tiles the kernel reads"):
        prefill_attention_pallas(q, k, v, kp.reshape(2, P, 16, 1, 256),
                                 vp.reshape(2, P, 16, 1, 256), *args, layer=1,
                                 packed=True, interpret=True)


def test_pad_rows_and_pad_positions_move_no_state(cfg, params):
    """A step of two rows, the second a pad row (no tokens, slots 0): the
    real row's logits and window are those of the step alone, slot 0 apart
    no slot is written, and positions past a row's length leave the window
    where its last real token left it."""
    toks = prompt(13, 8)
    alone, kv_a = prefill_all(cfg, params, toks)
    kv = fresh_cache(cfg, 64)
    before = np.asarray(kv.conv)
    pages = np.zeros((2, 8), np.int32)
    pages[0] = np.arange(1, 9)
    padded = np.zeros((2, 16), np.int32)
    padded[0, :13] = toks
    logits, kv = forward_prefill(
        params, cfg, kv, jnp.asarray(padded), with_slots(pages, [[0, 1], []]),
        jnp.zeros((2,), jnp.int32), jnp.asarray([13, 0], jnp.int32))
    assert np.abs(logp(logits)[0] - alone[0][1]).max() < TOL
    after = np.asarray(kv.conv)
    assert np.array_equal(after[:, 2:], before[:, 2:])
    assert np.allclose(after[:, 1], np.asarray(kv_a.conv)[:, 1], atol=1e-6)
    # the window is g at positions 11 and 12, not at the padded 14 and 15
    assert np.abs(after[:, 1]).sum() > 0


def test_every_fault_of_the_reference_fails_the_limit(cfg, params, ref,
                                                      monkeypatch):
    """Each of the reference's `FAULTS`, and its lower precision, moves the
    top-1 logprob of some position of three 48-token prompts past
    LOGPROB_TOL at the tiny size; the reference against itself reads 0, and
    `bf16_routing`, the reading that is no fault, stays under the limit."""
    monkeypatch.setattr(ref, "FAULT_CHUNK", 16)
    assert set(ref.CONTROLS) == {"lower_precision", *ref.FAULTS}
    assert {"no_qk_norm", "norm_after_rope", "silu_on_conv", "taps_reversed",
            "window_not_carried", "bias_in_weights", "embedding_norm_first",
            "gate_before_conv"} <= set(ref.FAULTS)
    prompts = [prompt(48, seed) for seed in (9, 10, 11)]
    plain = [ref_logp(ref, cfg, params, toks) for toks in prompts]

    def reads(control):
        worst = 0.0
        for toks, want in zip(prompts, plain):
            top = want.argmax(-1)[:, None]
            bad = ref_logp(ref, cfg, params, toks, **{control: True})
            worst = max(worst, np.abs(np.take_along_axis(bad, top, -1)
                                      - np.take_along_axis(want, top, -1)
                                      ).max())
        return worst

    for control in ref.CONTROLS:
        assert reads(control) > ref.LOGPROB_TOL, control
    assert reads("bf16_routing") < ref.LOGPROB_TOL
    with pytest.raises(TypeError, match="no control"):
        ref_logp(ref, cfg, params, prompts[0], no_such_fault=True)


def test_the_reference_holds_a_routing_it_is_given(cfg, params, ref):
    """The two readings that told a flipped expert from a drift (PERF.md
    finding 35): `picks` receives every expert layer's choices, `routing`
    puts given ones in their place, and `bf16_stream` rounds what the served
    path rounds.  The plain reference under its own choices is itself, bit
    for bit; under another token's choices it is not; the rounded stream
    under the plain reference's choices picks what it was given and stays
    under the limit."""
    toks = prompt(48, 9)
    picks = {}
    plain = ref_logp(ref, cfg, params, toks, picks=picks)
    layers = [l for l in range(cfg.num_hidden_layers)
              if l >= TINY["num_dense_layers"]]
    assert sorted(picks) == [(l, 0) for l in layers]
    assert all(a.shape == (1, 48, TINY["num_experts_per_tok"])
               for a in picks.values())
    assert np.array_equal(ref_logp(ref, cfg, params, toks, routing=picks),
                          plain)
    other = {k: a[:, ::-1] for k, a in picks.items()}
    assert np.abs(ref_logp(ref, cfg, params, toks, routing=other)
                  - plain).max() > 1e-3
    held = {}
    rounded = ref_logp(ref, cfg, params, toks, bf16_stream=True,
                       routing=picks, picks=held)
    assert all(np.array_equal(held[k], picks[k]) for k in picks)
    top = plain.argmax(-1)[:, None]
    moved = np.abs(np.take_along_axis(rounded, top, -1)
                   - np.take_along_axis(plain, top, -1)).max()
    assert 0 < moved < ref.LOGPROB_TOL


def test_the_served_path_has_each_mechanism_a_fault_takes_out(cfg, params,
                                                              ref):
    """The served logits stand nearer the reference than any fault by three
    orders of magnitude: QK-norm before the rope, taps in order, the window
    carried, no activation, the unbiased weights, the norm at the end."""
    toks = prompt(40, 10)
    want = ref_logp(ref, cfg, params, toks)
    got, _ = prefill_all(cfg, params, toks, 16)
    assert max(np.abs(lp - want[pos]).max() for pos, lp in got) < TOL


def test_bfloat16_reads_near_the_reference(cfg, ref):
    """bf16 weights, residual, pages and windows: the window pool is bf16 and
    the served top-1 logprob stays inside the reference's limit."""
    p16 = init_params(cfg, jax.random.PRNGKey(55), dtype=jnp.bfloat16)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p16)
    toks = prompt(40, 11)
    want = ref_logp(ref, cfg, p32, toks)
    kv = KVCache.create(cfg, 16, PAGE, jnp.bfloat16, state_slots=4)
    assert kv.conv.dtype == jnp.bfloat16 and kv.ssm is None
    logits, _ = forward_prefill(
        p16, cfg, kv, jnp.asarray([toks], jnp.int32), table_for(48, [0, 1]),
        jnp.asarray([0], jnp.int32), jnp.asarray([40], jnp.int32))
    lp = logp(logits)[0]
    top = int(want[-1].argmax())
    assert abs(lp[top] - want[-1][top]) < 0.25


def test_the_router_divides_by_the_sum_plus_its_epsilon(cfg, params):
    """`_route_grouped_sigmoid` at one group: the 2 largest of s + bias are
    chosen, their weights the unbiased s over (their sum + 1e-6)."""
    lp = jax.tree.map(lambda a: a[0], params["full_layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 64), jnp.float32)
    weights, selected = llama._route(lp, x, cfg)
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))[0]
    biased = s + np.asarray(lp["router_bias"])
    for t in range(5):
        idx = np.argsort(-biased[t])[:2]
        assert set(idx) == set(np.asarray(selected)[0, t])
        chosen = s[t][np.asarray(selected)[0, t]]
        assert np.allclose(np.asarray(weights)[0, t],
                           chosen / (chosen.sum() + 1e-6), rtol=1e-6)
    exact = ModelConfig.from_hf_config(TINY)
    assert exact.moe_norm_eps == 1e-6


def test_qk_norm_serves_any_family_that_sets_it(ref):
    """A plain llama-like model with `qk_norm`: both decode forwards and the
    prefill apply the per-head norm (the prefill's logits move when the
    weights do, and decode continues the prefill)."""
    from dynamo_tpu.models import tiny_config

    base = tiny_config(qk_norm=True)
    p = init_params(base, jax.random.PRNGKey(2), jnp.float32)
    assert p["layers"]["q_head_norm"].shape == (2, 16)
    plain = {**p, "layers": {k: v for k, v in p["layers"].items()
                             if "head_norm" not in k}}
    toks = prompt(12, 12)
    kv = KVCache.create(base, 8, PAGE, jnp.float32)
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32)[None])
    args = (jnp.asarray([toks], jnp.int32), table,
            jnp.asarray([0], jnp.int32), jnp.asarray([12], jnp.int32))
    with_norm, kv1 = llama.forward_prefill(p, base, kv, *args)
    without, _ = llama.forward_prefill(plain, base, kv, *args)
    assert np.abs(np.asarray(with_norm) - np.asarray(without)).max() > 1e-3
    # decode continues the prefill: one more token either way
    full, _ = llama.forward_prefill(
        p, base, kv, jnp.asarray([toks + [7]], jnp.int32), table,
        jnp.asarray([0], jnp.int32), jnp.asarray([13], jnp.int32))
    step, _ = llama.forward_decode(p, base, kv1, jnp.asarray([7], jnp.int32),
                                   jnp.asarray([12], jnp.int32), table)
    assert np.abs(np.asarray(step) - np.asarray(full)).max() < TOL
    assert ModelConfig.from_hf_config(
        {"model_type": "qwen3", "vocab_size": 64, "hidden_size": 32,
         "num_hidden_layers": 1, "num_attention_heads": 2}).qk_norm


# -- the engine ---------------------------------------------------------------------- #

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


@pytest.fixture
def every16(monkeypatch):
    """Snapshots every 16 tokens: as many as the engines' chunk here."""
    monkeypatch.setattr(hybrid, "snapshot_tokens", lambda cfg: 16)


async def agrees(engine, ref, cfg, params, toks, n=3):
    got, lps = await generate(engine, toks, n)
    text = list(toks)
    for t, lp_t in zip(got, lps):
        want = ref_logp(ref, cfg, params, text)[-1]
        assert t == int(want.argmax()), len(text)
        assert abs(lp_t - want.max()) < 5 * TOL, len(text)
        text.append(t)


def admits(engine):
    return [e for e in engine.events.dump()["events"] if e["kind"] == "admit"]


@pytest.mark.parametrize("how", [
    {}, {"decode_steps": 4}, {"mixed_prefill_tokens": 16},
], ids=["default", "block-of-4", "mixed"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         every16, how):
    """Chunked prefill, a prefix hit through a snapshot and each decode path
    the family serves, through pages and slots: the logprob of every greedy
    token against the reference's full forward pass over the text so far."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(40, 6)
        for tail in (prompt(5, 7), prompt(9, 8), prompt(5, 7)):
            await agrees(engine, ref, cfg, params, shared + tail, 5)
        first, second, third = admits(engine)
        assert (first["cached"], first["kv_cached"]) == (0, 0)
        assert (second["cached"], second["kv_cached"]) == (40, 40)
        assert (third["cached"], third["kv_cached"]) == (40, 40)
        m = vars(engine.metrics())
        assert m["state_snapshot_hits_total"] == 2
        assert m["state_hit_tokens_shortened_total"] == 0
        assert m["state_slots_total"] == 7 and m["state_slots_running"] == 0
        assert m["state_snapshots"] == m["state_snapshot_stored_total"] >= 2
    finally:
        await engine.shutdown()


async def test_the_engine_reports_both_pools_in_bytes_that_are_true(
        cfg, params, every16):
    """`STATE {...}` of a window-only slot: no `state` key, and the bytes of
    a slot are the windows' alone: 7 layers x a [2, 64] window as one tile
    of 128 float32 values."""
    engine = engine_of(cfg, params)
    try:
        cache, state = engine.cache_report(), engine.state_report()
        assert (cache["kind"], cache["layers"]) == ("kv", 2)
        assert cache["bytes_per_token"] == 2 * 2 * 2 * 16 * 4
        assert (state["kind"], state["layers"], state["slots"]) == (
            "window", 7, 8)
        assert "state" not in state and "state_dtype" not in state
        assert state["window"] == [1, 128]
        assert state["bytes_per_slot"] == 7 * 128 * 4
        assert state["pool_bytes"] == 8 * 7 * 128 * 4 == engine.kv.conv.nbytes
        assert engine.kv.ssm is None and engine.kv.k.shape[0] == 2
        assert sum(a.nbytes for a in jax.tree.leaves(engine.kv)) == (
            engine.kv.k.nbytes * 2 + state["pool_bytes"])
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "serving mesh"),
    ({"parallel": {"pp": 3}, "max_prefill_tokens": 160}, "serving mesh"),
    ({"parallel": {"sp": 2}, "max_prefill_tokens": 160}, "serving mesh"),
    ({"parallel": {"dp": 2}, "kv_partition": True}, "serving mesh"),
    ({"fuse_projections": True}, "fuse_projections"),
    ({"quantization": "int8"}, "int8"),
    ({"park_max_pages": 8}, "parking"),
    ({"tiered": object()}, "KVBM"),
    ({"speculative_ngram_k": 3}, "speculative-ngram-k"),
    ({"decode_continuous": True, "decode_steps": 2}, "decode-continuous"),
    ({"page_size": 6}, "snapshot interval"),
    ({"num_state_slots": 2}, "num_state_slots"),
], ids=["tp", "pp", "sp", "partitioned-pool", "fused-projections", "int8",
        "parking", "kvbm-tier", "speculative", "continuous", "page-size",
        "too-few-slots"])
def test_layouts_that_cannot_carry_the_family_refuse_it_by_name(
        cfg, params, every16, how, match):
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how)
    if "parallel" in how:
        how["parallel"] = ParallelConfig(**how["parallel"])
    with pytest.raises(ValueError, match=match) as err:
        engine_of(cfg, params, **how)
    if match not in ("snapshot interval", "num_state_slots"):
        assert "lfm2_moe" in str(err.value)


def test_step_kinds_without_a_state_refuse_the_family_by_name(cfg, params):
    kv = fresh_cache(cfg)
    toks = jnp.zeros((1, 4), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="draft-verify.*lfm2_moe"):
        llama.forward_verify(params, cfg, kv, toks, table_for(8, [0, 1]),
                             one, one + 4)
    with pytest.raises(ValueError, match="embedding forward.*lfm2_moe"):
        llama.forward_embed(params, cfg, toks, one + 4)
    with pytest.raises(ValueError, match="decode block.*lfm2_moe"):
        llama.decode_block_scan(params, cfg, kv, one, one,
                                table_for(8, [0, 1]), 2, 64, None, ())
    from dynamo_tpu.disagg.transfer import KvLayout

    stub = type("Stub", (), {"model_cfg": cfg, "_kv_dtype": jnp.bfloat16,
                             "cfg": EngineConfig(page_size=PAGE)})
    with pytest.raises(ValueError, match="disagg KV transfer.*lfm2_moe"):
        KvLayout.of_engine(stub)


# -- the benchmark's count ------------------------------------------------------------ #

def test_the_roofline_counts_what_every_step_must():
    roof = bench_module("roofline", "lfm2_moe")
    model = published()["model"]
    cfg = ModelConfig.from_hf_config(model)
    H, F = 2048, 1536
    conv = 4 * H * H
    attn = 2 * H * 32 * 64 + 2 * H * 8 * 64
    want = (7 * conv + 2 * attn + 3 * H * 11776
            + 8 * (H * 64 + 4 * 3 * H * F))
    assert roof.every_step_params(model) == want
    # under the 5,178 M the chip holds: 4 of 64 experts a layer are charged
    assert want < cfg.num_params()
    t, bound = roof.prefill_step_floor_s(model, PEAKS, 512)
    assert bound == "compute" and t == 2 * 512 * want / PEAKS[
        "bf16_flops_per_s"]
    t1, bound1 = roof.prefill_step_floor_s(model, PEAKS, 16)
    assert bound1 == "memory" and t1 == 2 * want / PEAKS["hbm_bytes_per_s"]
    # a conv layer: 16.8 M parameters read once against 2 x 16.8 M x tokens
    t, bound = roof.short_conv_floor_s(model, PEAKS, 512)
    assert bound == "compute"
    assert t == 7 * 2 * 512 * conv / PEAKS["bf16_flops_per_s"]
    t, bound = roof.short_conv_floor_s(model, PEAKS, 16)
    assert (t, bound) == (7 * 2 * conv / PEAKS["hbm_bytes_per_s"], "memory")
    # the routed experts alone, from what a step did
    t, bound = roof.routed_experts_floor_s(model, PEAKS, 8 * 512 * 4, 8 * 64)
    assert bound == "memory"
    assert t == 2 * 8 * 64 * 3 * H * F / PEAKS["hbm_bytes_per_s"]
    # attention over the context: the 2 attention layers alone
    t, bound = roof.prefill_attn_floor_s(model, PEAKS, 512, 6144)
    pairs = 512 * 5632 + 512 * 513 // 2
    assert bound == "compute"
    assert t == pytest.approx(2 * 4 * 64 * 32 * pairs
                              / PEAKS["bf16_flops_per_s"])
