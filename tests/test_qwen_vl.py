"""Qwen2-VL family: dynamic-resolution 2D-rope vision tower, M-RoPE
language model, video frames, and the serving path (reference: qwen-vl
multimodal handlers in the sglang backend, SURVEY §2.4).

The golden tests pin numerics to HF transformers' Qwen2VL built in-test
with seeded random weights — the same discipline as tests/test_golden.py
but without committed fixtures (transformers is part of the image)."""

import base64
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm import ModelDeploymentCard
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor, RequestError
from dynamo_tpu.models import KVCache, init_params, tiny_config
from dynamo_tpu.models.llama import forward_decode, forward_prefill
from dynamo_tpu.models.qwen_vl import (
    Qwen2VLVisionConfig,
    encode_patches,
    frames_to_patches,
    init_qwen_vl_vision_params,
    merged_tokens,
    mrope_positions,
    mrope_positions_from_runs,
    smart_resize,
    tiny_qwen_vl_vision_config,
)
from dynamo_tpu.testing import tiny_tokenizer

torch = pytest.importorskip("torch")

IMG_ID, VS_ID, VE_ID = 5, 3, 4


def _hf_model(vocab=128):
    from transformers.models.qwen2_vl.configuration_qwen2_vl import (
        Qwen2VLConfig,
    )
    from transformers.models.qwen2_vl.modeling_qwen2_vl import (
        Qwen2VLForConditionalGeneration,
    )

    torch.manual_seed(0)
    hf_cfg = Qwen2VLConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False,
        image_token_id=IMG_ID, video_token_id=6,
        vision_start_token_id=VS_ID, vision_end_token_id=VE_ID,
        rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]},
        vision_config=dict(
            depth=2, embed_dim=32, num_heads=2, mlp_ratio=2.0,
            in_channels=3, patch_size=4, temporal_patch_size=2,
            spatial_merge_size=2, hidden_size=64,
        ),
    )
    return Qwen2VLForConditionalGeneration(hf_cfg).eval().float(), hf_cfg


def _t2n(x):
    return np.asarray(x.detach().numpy(), np.float32)


def _map_llm(sd, L=2, prefix="model.language_model."):
    def ls(fmt):
        return np.stack([_t2n(sd[prefix + fmt.format(i)]) for i in range(L)])

    return jax.tree.map(jnp.asarray, {
        "embed": _t2n(sd[prefix + "embed_tokens.weight"]),
        "final_norm": _t2n(sd[prefix + "norm.weight"]),
        "lm_head": _t2n(sd["lm_head.weight"]).T,
        "layers": {
            "attn_norm": ls("layers.{}.input_layernorm.weight"),
            "mlp_norm": ls("layers.{}.post_attention_layernorm.weight"),
            **{f"w{n}": np.stack([
                _t2n(sd[prefix + f"layers.{i}.self_attn.{n}_proj.weight"]).T
                for i in range(L)]) for n in "qkvo"},
            **{f"b{n}": ls(f"layers.{{}}.self_attn.{n}_proj.bias")
               for n in "qkv"},
            "w_gate": np.stack([
                _t2n(sd[prefix + f"layers.{i}.mlp.gate_proj.weight"]).T
                for i in range(L)]),
            "w_up": np.stack([
                _t2n(sd[prefix + f"layers.{i}.mlp.up_proj.weight"]).T
                for i in range(L)]),
            "w_down": np.stack([
                _t2n(sd[prefix + f"layers.{i}.mlp.down_proj.weight"]).T
                for i in range(L)]),
        },
    })


def _map_tower(sd, L=2, prefix="model.visual."):
    def vs(key):
        return np.stack([_t2n(sd[prefix + f"blocks.{i}.{key}"])
                         for i in range(L)])

    return jax.tree.map(jnp.asarray, {
        "patch_proj": _t2n(sd[prefix + "patch_embed.proj.weight"])
        .reshape(32, -1).T,
        "layers": {
            "ln1_scale": vs("norm1.weight"), "ln1_bias": vs("norm1.bias"),
            "wqkv": np.stack([
                _t2n(sd[prefix + f"blocks.{i}.attn.qkv.weight"]).T
                for i in range(L)]),
            "bqkv": vs("attn.qkv.bias"),
            "wo": np.stack([
                _t2n(sd[prefix + f"blocks.{i}.attn.proj.weight"]).T
                for i in range(L)]),
            "bo": vs("attn.proj.bias"),
            "ln2_scale": vs("norm2.weight"), "ln2_bias": vs("norm2.bias"),
            "w1": np.stack([
                _t2n(sd[prefix + f"blocks.{i}.mlp.fc1.weight"]).T
                for i in range(L)]),
            "b1": vs("mlp.fc1.bias"),
            "w2": np.stack([
                _t2n(sd[prefix + f"blocks.{i}.mlp.fc2.weight"]).T
                for i in range(L)]),
            "b2": vs("mlp.fc2.bias"),
        },
        "merge_ln_scale": _t2n(sd[prefix + "merger.ln_q.weight"]),
        "merge_ln_bias": _t2n(sd[prefix + "merger.ln_q.bias"]),
        "merge_w1": _t2n(sd[prefix + "merger.mlp.0.weight"]).T,
        "merge_b1": _t2n(sd[prefix + "merger.mlp.0.bias"]),
        "merge_w2": _t2n(sd[prefix + "merger.mlp.2.weight"]).T,
        "merge_b2": _t2n(sd[prefix + "merger.mlp.2.bias"]),
    })


_VCFG = Qwen2VLVisionConfig(
    embed_dim=32, depth=2, num_heads=2, mlp_ratio=2.0, patch_size=4,
    temporal_patch_size=2, spatial_merge_size=2, out_hidden_size=64,
)


def test_tower_matches_hf_image_and_video():
    model, _ = _hf_model()
    vparams = _map_tower(model.state_dict())
    rng = np.random.default_rng(0)
    for T, name in [(1, "image"), (4, "video")]:
        frames = rng.random((T, 16, 24, 3), np.float32)
        patches, grid = frames_to_patches(frames, _VCFG)
        hf_out = model.visual(torch.from_numpy(patches),
                              grid_thw=torch.tensor([list(grid)]))
        ours = np.asarray(
            encode_patches(vparams, _VCFG, jnp.asarray(patches), grid)
        )
        diff = np.abs(ours - _t2n(hf_out)).max()
        assert diff < 2e-4, f"{name}: {diff}"


def _hf_model_25(vocab=128):
    from transformers.models.qwen2_5_vl.configuration_qwen2_5_vl import (
        Qwen2_5_VLConfig,
    )
    from transformers.models.qwen2_5_vl.modeling_qwen2_5_vl import (
        Qwen2_5_VLForConditionalGeneration,
    )

    torch.manual_seed(1)
    hf_cfg = Qwen2_5_VLConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False,
        image_token_id=IMG_ID, video_token_id=6,
        vision_start_token_id=VS_ID, vision_end_token_id=VE_ID,
        rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]},
        vision_config=dict(
            depth=2, hidden_size=32, out_hidden_size=64, num_heads=2,
            intermediate_size=48, in_channels=3, patch_size=4,
            temporal_patch_size=2, spatial_merge_size=2,
            window_size=16, fullatt_block_indexes=[1],
        ),
    )
    return (Qwen2_5_VLForConditionalGeneration(hf_cfg).eval().float(),
            hf_cfg)


def _map_tower_25(sd, L=2, prefix="model.visual."):
    def vs(key):
        return np.stack([_t2n(sd[prefix + f"blocks.{i}.{key}"])
                         for i in range(L)])

    def vst(key):
        return np.stack([_t2n(sd[prefix + f"blocks.{i}.{key}"]).T
                         for i in range(L)])

    return jax.tree.map(jnp.asarray, {
        "patch_proj": _t2n(sd[prefix + "patch_embed.proj.weight"])
        .reshape(32, -1).T,
        "layers": {
            "ln1_scale": vs("norm1.weight"),
            "wqkv": vst("attn.qkv.weight"), "bqkv": vs("attn.qkv.bias"),
            "wo": vst("attn.proj.weight"), "bo": vs("attn.proj.bias"),
            "ln2_scale": vs("norm2.weight"),
            "w_gate": vst("mlp.gate_proj.weight"),
            "b_gate": vs("mlp.gate_proj.bias"),
            "w_up": vst("mlp.up_proj.weight"),
            "b_up": vs("mlp.up_proj.bias"),
            "w_down": vst("mlp.down_proj.weight"),
            "b_down": vs("mlp.down_proj.bias"),
        },
        "merge_ln_scale": _t2n(sd[prefix + "merger.ln_q.weight"]),
        "merge_w1": _t2n(sd[prefix + "merger.mlp.0.weight"]).T,
        "merge_b1": _t2n(sd[prefix + "merger.mlp.0.bias"]),
        "merge_w2": _t2n(sd[prefix + "merger.mlp.2.weight"]).T,
        "merge_b2": _t2n(sd[prefix + "merger.mlp.2.bias"]),
    })


_VCFG25 = Qwen2VLVisionConfig(
    embed_dim=32, depth=2, num_heads=2, patch_size=4,
    temporal_patch_size=2, spatial_merge_size=2, out_hidden_size=64,
    intermediate_size=48, window_size=16, fullatt_block_indexes=(1,),
    rms_norm=True,
)


def test_tower_25_matches_hf_windowed():
    """qwen2.5-vl tower (RMSNorm, gated SiLU MLP, WINDOWED attention
    with full-attention exceptions): our mask-equivalent of HF's
    window_index permutation matches Qwen2_5 numerics on grids whose
    window tiling truncates at the borders."""
    model, _ = _hf_model_25()
    vparams = _map_tower_25(model.state_dict())
    rng = np.random.default_rng(7)
    # 40x24 px -> 10x6 patch grid -> 5x3 merged -> ragged 2x2 windows
    for T, hw, name in [(1, (40, 24), "image-ragged"),
                        (1, (16, 16), "image-exact"),
                        (4, (24, 16), "video")]:
        frames = rng.random((T, *hw, 3), np.float32)
        patches, grid = frames_to_patches(frames, _VCFG25)
        hf_out = model.visual(torch.from_numpy(patches),
                              grid_thw=torch.tensor([list(grid)]))
        ours = np.asarray(
            encode_patches(vparams, _VCFG25, jnp.asarray(patches), grid)
        )
        diff = np.abs(ours - _t2n(hf_out)).max()
        assert diff < 2e-4, f"{name}: {diff}"


def test_mrope_positions_25_video_match_hf():
    """qwen2.5 video temporal rope: frames advance tokens_per_second *
    second_per_grid positions (assumed 1.0s/grid), not 1 — parity with
    HF Qwen2_5 get_rope_index including the post-video delta."""
    model, _ = _hf_model_25()
    grid = (4, 4, 4)
    n = merged_tokens(grid, _VCFG25)
    VID_ID = 6
    prompt = [10, VS_ID] + [VID_ID] * n + [VE_ID, 12, 13]
    hf_pos, hf_delta = model.model.get_rope_index(
        torch.tensor([prompt]), video_grid_thw=torch.tensor([list(grid)]),
        second_per_grid_ts=torch.tensor([1.0]),
    )
    vcfg = Qwen2VLVisionConfig(
        **{**_VCFG25.__dict__, "tokens_per_second": 4.0})
    pos, delta = mrope_positions(prompt, VID_ID, [grid], vcfg)
    assert np.array_equal(pos.astype(np.int64),
                          _t2n(hf_pos[:, 0]).astype(np.int64))
    assert delta == int(hf_delta[0])
    pos2, delta2 = mrope_positions_from_runs(len(prompt), [(2, grid)], vcfg)
    assert np.array_equal(pos, pos2) and delta == delta2


def test_full_splice_25_matches_hf():
    """qwen2.5-vl end to end: windowed tower embeds spliced into the
    mrope LLM — prefill logits and a rope-offset decode step match HF."""
    model, hf_cfg = _hf_model_25()
    sd = model.state_dict()
    vparams = _map_tower_25(sd)
    params = _map_llm(sd)
    cfg = tiny_config(vocab_size=128, mrope_section=(2, 3, 3),
                      model_type="qwen2_5_vl", name="tiny-qwen25-vl",
                      num_hidden_layers=2, hidden_size=64,
                      intermediate_size=128, num_attention_heads=4,
                      num_key_value_heads=2, rms_norm_eps=1e-6)
    rng = np.random.default_rng(9)
    frames = rng.random((1, 40, 24, 3), np.float32)
    patches, grid = frames_to_patches(frames, _VCFG25)
    n = merged_tokens(grid, _VCFG25)
    prompt = [10, 11, VS_ID] + [IMG_ID] * n + [VE_ID, 12, 13]
    S = len(prompt)
    with torch.no_grad():
        hf_out = model(
            input_ids=torch.tensor([prompt]),
            pixel_values=torch.from_numpy(patches),
            image_grid_thw=torch.tensor([list(grid)]),
        )
    hf_logits = _t2n(hf_out.logits)[0]

    embeds = np.asarray(
        encode_patches(vparams, _VCFG25, jnp.asarray(patches), grid))
    pos, delta = mrope_positions(prompt, IMG_ID, [grid], _VCFG25)
    extra = np.zeros((1, S, cfg.hidden_size), np.float32)
    mask = np.zeros((S,), bool)
    extra[0, 3:3 + n] = embeds
    mask[3:3 + n] = True
    n_pages = S // 8 + 3
    kv = KVCache.create(cfg, 1 + n_pages, 8, jnp.float32)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    logits, kv = forward_prefill(
        params, cfg, kv, jnp.asarray([prompt], jnp.int32), table,
        jnp.zeros((1,), jnp.int32), jnp.asarray([S], jnp.int32),
        extra_embeds=jnp.asarray(extra), extra_mask=jnp.asarray(mask[None]),
        mm_positions=jnp.asarray(pos[None]),
    )
    d = np.abs(np.asarray(logits)[0] - hf_logits[-1]).max()
    assert d < 3e-3, f"prefill diff {d}"
    nxt = int(hf_logits[-1].argmax())
    with torch.no_grad():
        hf2 = model(
            input_ids=torch.tensor([prompt + [nxt]]),
            pixel_values=torch.from_numpy(patches),
            image_grid_thw=torch.tensor([list(grid)]),
        )
    logits2, kv = forward_decode(
        params, cfg, kv, jnp.asarray([nxt], jnp.int32),
        jnp.asarray([S], jnp.int32), table,
        rope_offset=jnp.asarray([delta], jnp.int32),
    )
    d2 = np.abs(np.asarray(logits2)[0] - _t2n(hf2.logits)[0, -1]).max()
    assert d2 < 3e-3, f"decode diff {d2}"


def test_mrope_positions_match_hf():
    model, _ = _hf_model()
    grid = (1, 4, 6)
    n = merged_tokens(grid, _VCFG)
    prompt = [10, 11, VS_ID] + [IMG_ID] * n + [VE_ID, 12, 13, 14]
    hf_pos, hf_delta = model.model.get_rope_index(
        torch.tensor([prompt]), image_grid_thw=torch.tensor([list(grid)])
    )
    pos, delta = mrope_positions(prompt, IMG_ID, [grid], _VCFG)
    assert np.array_equal(pos.astype(np.int64), _t2n(hf_pos[:, 0]).astype(np.int64))
    assert delta == int(hf_delta[0])
    # the offset+grid variant (what the engine uses) agrees exactly
    pos2, delta2 = mrope_positions_from_runs(len(prompt), [(3, grid)], _VCFG)
    assert np.array_equal(pos, pos2) and delta == delta2


def test_full_splice_matches_hf_prefill_and_decode():
    """Tower embeds spliced into the mrope LLM: prefill logits and a
    rope-offset decode step both match HF to float32 noise."""
    model, hf_cfg = _hf_model()
    from dynamo_tpu.models import ModelConfig

    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), name="tiny-qwen2vl")
    assert cfg.mrope_section == (2, 3, 3) and cfg.attention_bias
    params = _map_llm(model.state_dict())
    vparams = _map_tower(model.state_dict())

    rng = np.random.default_rng(1)
    frames = rng.random((1, 16, 24, 3), np.float32)
    patches, grid = frames_to_patches(frames, _VCFG)
    n = merged_tokens(grid, _VCFG)
    prompt = [10, 11, VS_ID] + [IMG_ID] * n + [VE_ID, 12, 13, 14]
    S = len(prompt)
    with torch.no_grad():
        hf_out = model(input_ids=torch.tensor([prompt]),
                       pixel_values=torch.from_numpy(patches),
                       image_grid_thw=torch.tensor([list(grid)]))
    hf_logits = _t2n(hf_out.logits)[0]

    pos, delta = mrope_positions(prompt, IMG_ID, [grid], _VCFG)
    embeds = np.asarray(
        encode_patches(vparams, _VCFG, jnp.asarray(patches), grid)
    )
    mask = np.array([t == IMG_ID for t in prompt])
    extra = np.zeros((1, S, 64), np.float32)
    extra[0, mask] = embeds
    n_pages = S // 8 + 2
    kv = KVCache.create(cfg, 1 + n_pages, 8, jnp.float32)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    logits, kv = forward_prefill(
        params, cfg, kv, jnp.asarray([prompt], jnp.int32), table,
        jnp.zeros((1,), jnp.int32), jnp.asarray([S], jnp.int32),
        extra_embeds=jnp.asarray(extra), extra_mask=jnp.asarray(mask[None]),
        mm_positions=jnp.asarray(pos[None]),
    )
    assert np.abs(np.asarray(logits)[0] - hf_logits[-1]).max() < 2e-3

    nxt = int(hf_logits[-1].argmax())
    with torch.no_grad():
        hf2 = model(input_ids=torch.tensor([prompt + [nxt]]),
                    pixel_values=torch.from_numpy(patches),
                    image_grid_thw=torch.tensor([list(grid)]))
    logits2, kv = forward_decode(
        params, cfg, kv, jnp.asarray([nxt], jnp.int32),
        jnp.asarray([S], jnp.int32), table,
        rope_offset=jnp.asarray([delta], jnp.int32),
    )
    assert np.abs(
        np.asarray(logits2)[0] - _t2n(hf2.logits)[0, -1]
    ).max() < 2e-3


def test_patchify_matches_hf_processor():
    """frames_to_patches + smart_resize reproduce the HF image
    processor's pixel_values and grid exactly (patch ordering is the
    easiest thing to silently get wrong)."""
    from transformers.models.qwen2_vl.image_processing_qwen2_vl import (
        Qwen2VLImageProcessor,
    )
    from PIL import Image

    proc = Qwen2VLImageProcessor(
        patch_size=4, temporal_patch_size=2, merge_size=2,
        min_pixels=8 * 8, max_pixels=64 * 64, do_resize=True,
    )
    vcfg = tiny_qwen_vl_vision_config()
    rng = np.random.default_rng(2)
    img = Image.fromarray(
        (rng.random((30, 45, 3)) * 255).astype(np.uint8)
    )
    out = proc(images=[img], return_tensors="np")
    hf_patches = out["pixel_values"]
    hf_grid = tuple(int(g) for g in out["image_grid_thw"][0])

    h1, w1 = smart_resize(img.height, img.width, vcfg)
    frames = (np.asarray(
        img.resize((w1, h1), Image.BICUBIC), np.float32
    ) / 255.0)[None]
    patches, grid = frames_to_patches(frames, vcfg)
    assert grid == hf_grid
    assert patches.shape == hf_patches.shape
    # resampling differs slightly (HF rescales then resizes); compare
    # loosely on values but EXACTLY on layout via a synthetic array
    assert np.abs(patches - hf_patches).max() < 0.2
    # layout check: feed the smart-resized frame through HF with
    # do_resize off — byte-identical patch ordering required
    out2 = proc(images=[Image.fromarray((frames[0] * 255).astype(np.uint8))],
                return_tensors="np", do_resize=False)
    assert np.abs(patches - out2["pixel_values"]).max() < 1e-5


# -- serving path ------------------------------------------------------------ #


def _gif_data_uri(colors, size=(24, 20)):
    from PIL import Image

    frames = [Image.new("RGB", size, c) for c in colors]
    buf = io.BytesIO()
    frames[0].save(buf, format="GIF", save_all=True,
                   append_images=frames[1:], duration=100)
    return "data:image/gif;base64," + base64.b64encode(buf.getvalue()).decode()


def _png_data_uri(color, size=(40, 32)):
    from PIL import Image

    img = Image.new("RGB", size, color)
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    return "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()


def _qwen_setup():
    tok = tiny_tokenizer()
    cfg = tiny_config(vocab_size=tok.vocab_size, mrope_section=(2, 3, 3),
                      model_type="qwen2_vl", name="tiny-qwen-vl")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    vcfg = tiny_qwen_vl_vision_config(out_hidden_size=cfg.hidden_size)
    vparams = init_qwen_vl_vision_params(vcfg, jax.random.PRNGKey(7),
                                         dtype=jnp.float32)
    image_id = tok.encode("<image>")
    assert len(image_id) == 1
    mdc = ModelDeploymentCard(
        name="tiny-qwen-vl",
        tokenizer_json=tok.to_json_str(),
        eos_token_ids=list(tok.eos_token_ids),
        image_token="<image>",
        image_token_id=image_id[0],
        mm_arch="qwen2_vl",
        mm_config=dict(depth=2, embed_dim=32, num_heads=2, mlp_ratio=2.0,
                       patch_size=4, temporal_patch_size=2,
                       spatial_merge_size=2, hidden_size=cfg.hidden_size,
                       min_pixels=8 * 8, max_pixels=64 * 64),
    )
    return tok, cfg, params, vcfg, vparams, mdc


def _engine(cfg, params, vcfg, vparams, **over):
    kw = dict(
        page_size=8, num_pages=128, max_num_seqs=4,
        max_prefill_tokens=96, max_model_len=256,
    )
    kw.update(over)
    return JaxEngine(
        cfg, params, EngineConfig(**kw), kv_dtype=jnp.float32,
        vision=(vparams, vcfg),
    )


async def _gen(engine, pre_out, max_tokens=8):
    req = dict(pre_out)
    req["sampling_options"] = {"temperature": 0.0}
    req["stop_conditions"] = {"max_tokens": max_tokens, "ignore_eos": True}
    toks = []
    async for out in engine.generate(req):
        assert out.get("finish_reason") != "error", out
        toks += out["token_ids"]
    return toks


async def test_engine_serves_qwen_vl_images_and_video():
    """The full serving path: preprocessor smart-resizes + patchifies,
    engine encodes per-grid, splices embeds, ropes with M-RoPE streams
    and decodes at slot+delta.  Outputs are deterministic per content,
    different across contents, and text-only prompts still serve."""
    tok, cfg, params, vcfg, vparams, mdc = _qwen_setup()
    pre = OpenAIPreprocessor(mdc, tok)

    def img_req(color, size=(40, 32)):
        return pre.preprocess_chat({
            "messages": [{"role": "user", "content": [
                {"type": "text", "text": "describe "},
                {"type": "image_url",
                 "image_url": {"url": _png_data_uri(color, size)}},
            ]}],
        })

    def vid_req(colors):
        return pre.preprocess_chat({
            "messages": [{"role": "user", "content": [
                {"type": "text", "text": "what happens? "},
                {"type": "video_url",
                 "video_url": {"url": _gif_data_uri(colors)}},
            ]}],
        })

    engine = _engine(cfg, params, vcfg, vparams)
    red = await _gen(engine, img_req((200, 30, 30)))
    red2 = await _gen(engine, img_req((200, 30, 30)))
    blue = await _gen(engine, img_req((30, 30, 200)))
    wide = await _gen(engine, img_req((200, 30, 30), size=(64, 24)))
    vid = await _gen(engine, vid_req([(250, 0, 0), (0, 250, 0),
                                      (0, 0, 250), (250, 250, 0)]))
    vid2 = await _gen(engine, vid_req([(250, 0, 0), (0, 250, 0),
                                       (0, 0, 250), (250, 250, 0)]))
    text = await _gen(engine, pre.preprocess_chat({
        "messages": [{"role": "user", "content": "just text"}],
    }))
    await engine.shutdown()
    assert red == red2 and vid == vid2  # deterministic per content
    assert red != blue  # image content reaches the model
    assert red != wide  # dynamic resolution: aspect changes the grid
    assert vid and text  # video + text-only both serve


async def test_engine_qwen_vl_greedy_matches_forward_reference():
    """Engine output == a hand-rolled forward_prefill/forward_decode
    loop with the same mm positions and rope delta (covers the engine's
    position bookkeeping, not just 'something decoded')."""
    tok, cfg, params, vcfg, vparams, mdc = _qwen_setup()
    pre = OpenAIPreprocessor(mdc, tok)
    out = pre.preprocess_chat({
        "messages": [{"role": "user", "content": [
            {"type": "image_url",
             "image_url": {"url": _png_data_uri((120, 180, 60))}},
            {"type": "text", "text": " ok"},
        ]}],
    })
    prompt = out["token_ids"]
    S = len(prompt)
    from dynamo_tpu.llm.multimodal import unpack_patches

    runs, embeds_list = [], []
    for blob, off in zip(out["mm_patches"], out["mm_offsets"]):
        arr, grid = unpack_patches(blob)
        runs.append((off, grid))
        embeds_list.append((off, np.asarray(
            encode_patches(vparams, vcfg, jnp.asarray(arr), grid)
        )))
    pos, delta = mrope_positions_from_runs(S, runs, vcfg)

    engine = _engine(cfg, params, vcfg, vparams)
    got = await _gen(engine, out, max_tokens=6)
    await engine.shutdown()

    mask = np.zeros((S,), bool)
    extra = np.zeros((1, S, cfg.hidden_size), np.float32)
    for off, emb in embeds_list:
        extra[0, off:off + emb.shape[0]] = emb
        mask[off:off + emb.shape[0]] = True
    n_pages = S // 8 + 3
    kv = KVCache.create(cfg, 1 + n_pages, 8, jnp.float32)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    logits, kv = forward_prefill(
        params, cfg, kv, jnp.asarray([prompt], jnp.int32), table,
        jnp.zeros((1,), jnp.int32), jnp.asarray([S], jnp.int32),
        extra_embeds=jnp.asarray(extra), extra_mask=jnp.asarray(mask[None]),
        mm_positions=jnp.asarray(pos[None]),
    )
    want = [int(np.asarray(logits)[0].argmax())]
    for step in range(5):
        logits, kv = forward_decode(
            params, cfg, kv, jnp.asarray([want[-1]], jnp.int32),
            jnp.asarray([S + step], jnp.int32), table,
            rope_offset=jnp.asarray([delta], jnp.int32),
        )
        want.append(int(np.asarray(logits)[0].argmax()))
    assert got == want


async def test_engine_qwen_vl_pooled_and_sp_match_flat():
    """qwen2-vl (mrope) serves on MESHED engines (VERDICT r4 item 5):
    pooled dp×tp kv_partition with mixed scheduling ON, and the
    dp×sp×tp ring-prefill engine — greedy-equal to the flat engine for
    images, video, and text, sequential AND concurrently staggered."""
    import asyncio

    from dynamo_tpu.parallel import ParallelConfig

    tok, _, _, vcfg, vparams, mdc = _qwen_setup()
    # tp=2 needs vocab % tp == 0; the tiny tokenizer's 261 ids stay
    # valid under a padded 264 vocab (ids only ever compared, never
    # detokenized here)
    cfg = tiny_config(vocab_size=264, mrope_section=(2, 3, 3),
                      model_type="qwen2_vl", name="tiny-qwen-vl")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    pre = OpenAIPreprocessor(mdc, tok)

    reqs = [
        pre.preprocess_chat({"messages": [{"role": "user", "content": [
            {"type": "text", "text": "describe "},
            {"type": "image_url",
             "image_url": {"url": _png_data_uri((200, 30, 30))}},
        ]}]}),
        pre.preprocess_chat({"messages": [{"role": "user", "content": [
            {"type": "image_url",
             "image_url": {"url": _png_data_uri((30, 30, 200),
                                                size=(64, 24))}},
            {"type": "text", "text": " ok"},
        ]}]}),
        pre.preprocess_chat({"messages": [{"role": "user", "content": [
            {"type": "video_url",
             "video_url": {"url": _gif_data_uri([(250, 0, 0),
                                                 (0, 250, 0)])}},
        ]}]}),
        pre.preprocess_chat({"messages": [
            {"role": "user", "content": "just text please"}]}),
    ]
    base = dict(page_size=8, num_pages=128, max_num_seqs=4,
                max_prefill_tokens=256, max_model_len=128,
                prefill_batch_size=1, enable_prefix_caching=False)

    flat = JaxEngine(cfg, params, EngineConfig(**base),
                     kv_dtype=jnp.float32, vision=(vparams, vcfg))
    want = [await _gen(flat, r) for r in reqs]
    await flat.shutdown()

    pooled = JaxEngine(
        cfg, params, EngineConfig(**base, kv_partition=True),
        kv_dtype=jnp.float32, vision=(vparams, vcfg),
        parallel=ParallelConfig(dp=4, tp=2),
    )
    assert pooled.layout.pooled and pooled.cfg.mixed_prefill_tokens > 0, (
        "mrope no longer zeroes mixed scheduling")
    got = [await _gen(pooled, r) for r in reqs]
    assert got == want, "pooled dp×tp diverged from flat"

    # concurrent staggered submission through the SAME pooled engine:
    # mixed/fused dispatch must not change greedy outputs
    async def one(i, r):
        await asyncio.sleep(0.03 * i)
        return await _gen(pooled, r)

    got_cc = await asyncio.gather(*[one(i, r) for i, r in enumerate(reqs)])
    await pooled.shutdown()
    assert list(got_cc) == want, "staggered pooled run diverged"

    sp = JaxEngine(
        cfg, params, EngineConfig(**base, kv_partition=True),
        kv_dtype=jnp.float32, vision=(vparams, vcfg),
        parallel=ParallelConfig(dp=2, sp=2, tp=2),
    )
    got_sp = [await _gen(sp, r) for r in reqs]
    await sp.shutdown()
    assert got_sp == want, "sp ring prefill diverged from flat"


async def test_engine_rejects_mismatched_patches():
    tok, cfg, params, vcfg, vparams, mdc = _qwen_setup()
    engine = _engine(cfg, params, vcfg, vparams)
    bad = {
        "token_ids": [1, 2, 3, 4, 5, 6, 7, 8],
        "sampling_options": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": 2},
        "mm_patches": [{"shape": [8, vcfg.patch_dim], "data": b"\x00" * (
            8 * vcfg.patch_dim * 4), "grid": [1, 4, 4]}],  # 16 != 8
        "mm_offsets": [0],
    }
    outs = [o async for o in engine.generate(bad)]
    await engine.shutdown()
    assert outs[-1].get("finish_reason") == "error"
    assert "grid" in outs[-1].get("error", "")


def test_preprocessor_rejects_video_for_clip_models():
    tok = tiny_tokenizer()
    from dynamo_tpu.models.vision import tiny_vision_config

    vcfg = tiny_vision_config()
    mdc = ModelDeploymentCard(
        name="clip-vlm", tokenizer_json=tok.to_json_str(),
        image_token="<image>", image_token_id=tok.encode("<image>")[0],
        image_patches=vcfg.num_patches, image_size=vcfg.image_size,
    )
    pre = OpenAIPreprocessor(mdc, tok)
    with pytest.raises(RequestError, match="video"):
        pre.preprocess_chat({
            "messages": [{"role": "user", "content": [
                {"type": "video_url",
                 "video_url": {"url": _gif_data_uri([(1, 2, 3)])}},
            ]}],
        })


def test_qwen_25_vl_checkpoint_round_trip(tmp_path):
    """A qwen2.5-vl-layout checkpoint (window config, RMS tower, gated
    MLP) loads through load_qwen_vl with the 2.5 key mapping and
    reproduces the hand-mapped params bit-exactly."""
    safetensors_np = pytest.importorskip("safetensors.numpy")
    import json
    import os

    from dynamo_tpu.models.vlm import load_qwen_vl

    model, hf_cfg = _hf_model_25()
    sd = model.state_dict()
    from dynamo_tpu.testing import export_vl_state_dict

    tensors = export_vl_state_dict(model)
    safetensors_np.save_file(
        tensors, os.path.join(tmp_path, "model.safetensors"))
    cfg_d = hf_cfg.to_dict()
    cfg_d["model_type"] = "qwen2_5_vl"
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump(cfg_d, f)

    llm_params, llm_cfg, vparams, vcfg = load_qwen_vl(
        str(tmp_path), dtype=jnp.float32)
    assert llm_cfg.mrope_section == (2, 3, 3)
    assert vcfg.rms_norm and vcfg.window_size == 16
    assert vcfg.fullatt_block_indexes == (1,)
    want_llm = _map_llm(sd)
    want_tower = _map_tower_25(sd)
    for got, want in [(llm_params, want_llm), (vparams, want_tower)]:
        flat_w = dict(jax.tree_util.tree_leaves_with_path(want))
        for path, leaf in jax.tree_util.tree_leaves_with_path(got):
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(flat_w[path]),
                err_msg=str(path),
            )
    # the loaded tower runs and matches the HF forward
    rng = np.random.default_rng(3)
    frames = rng.random((1, 24, 16, 3), np.float32)
    patches, grid = frames_to_patches(frames, vcfg)
    hf_out = model.visual(torch.from_numpy(patches),
                          grid_thw=torch.tensor([list(grid)]))
    ours = np.asarray(
        encode_patches(vparams, vcfg, jnp.asarray(patches), grid))
    assert np.abs(ours - _t2n(hf_out)).max() < 2e-4


def test_qwen_vl_checkpoint_round_trip(tmp_path):
    """A qwen2-vl-layout safetensors checkpoint (published key naming:
    `visual.*` + `model.*` + `lm_head.weight`) loads through
    load_qwen_vl and reproduces the hand-mapped params bit-exactly."""
    safetensors_np = pytest.importorskip("safetensors.numpy")
    import json
    import os

    from dynamo_tpu.models.vlm import load_qwen_vl

    model, hf_cfg = _hf_model()
    sd = model.state_dict()
    tensors = {}
    for k, v in sd.items():
        if k.startswith("model.visual."):
            k2 = k[len("model."):]  # visual.*
        elif k.startswith("model.language_model."):
            k2 = "model." + k[len("model.language_model."):]
        else:
            k2 = k  # lm_head.weight
        tensors[k2] = _t2n(v)
    safetensors_np.save_file(
        tensors, os.path.join(tmp_path, "model.safetensors")
    )
    cfg_d = hf_cfg.to_dict()
    cfg_d["model_type"] = "qwen2_vl"
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump(cfg_d, f)

    llm_params, llm_cfg, vparams, vcfg = load_qwen_vl(
        str(tmp_path), dtype=jnp.float32
    )
    assert llm_cfg.mrope_section == (2, 3, 3)
    assert (vcfg.patch_size, vcfg.spatial_merge_size) == (4, 2)
    want_llm = _map_llm(sd)
    want_tower = _map_tower(sd)
    for got, want in [(llm_params, want_llm), (vparams, want_tower)]:
        flat_g = jax.tree_util.tree_leaves_with_path(got)
        flat_w = dict(jax.tree_util.tree_leaves_with_path(want))
        for path, leaf in flat_g:
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(flat_w[path]),
                err_msg=str(path),
            )
