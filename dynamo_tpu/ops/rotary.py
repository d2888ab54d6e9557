"""Rotary position embeddings (RoPE), including Llama-3 frequency scaling.

Functional, shape-polymorphic over leading dims; applied in float32 then cast
back (precision matters for long context).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rope_frequencies(
    head_dim: int,
    theta: float = 10000.0,
    scaling: Optional[dict] = None,
) -> jax.Array:
    """Inverse frequencies [head_dim//2], with optional llama3-style scaling."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling and scaling.get("rope_type", scaling.get("type")) == "yarn":
        # YaRN (gpt-oss ships factor=32 over 4096 original): interpolate
        # the long-wavelength frequencies by 1/factor, keep the short
        # ones, linear-ramp between — HF _compute_yarn_parameters.  The
        # companion amplitude factor is `rope_attention_scale`.
        factor = float(scaling["factor"])
        orig = float(scaling.get("original_max_position_embeddings", 4096))
        beta_fast = float(scaling.get("beta_fast", 32.0))
        beta_slow = float(scaling.get("beta_slow", 1.0))

        def dim_for(rotations: float) -> float:
            return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                    ) / (2 * math.log(theta))

        # HF only floor/ceils the correction range when truncate (default
        # true) — gpt-oss ships truncate:false and expects the fractional
        # band (ADVICE r4: floored bounds drift inv_freq ~3% in the ramp
        # band at head_dim=64/theta=150000, growing with position).
        low, high = dim_for(beta_fast), dim_for(beta_slow)
        if scaling.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0.0), min(high, float(head_dim - 1))
        if low == high:
            high += 0.001  # HF linear_ramp_factor degenerate-band guard
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
            / (high - low),
            0.0, 1.0,
        )
        extrapolation_mask = 1.0 - ramp  # 1 → keep original frequency
        return (inv_freq / factor) * (1.0 - extrapolation_mask) \
            + inv_freq * extrapolation_mask
    if scaling and scaling.get("rope_type", scaling.get("type")) == "llama3":
        factor = scaling["factor"]
        low = scaling["low_freq_factor"]
        high = scaling["high_freq_factor"]
        orig = scaling["original_max_position_embeddings"]
        wavelen = 2 * math.pi / inv_freq
        # three bands: long wavelengths scaled by 1/factor, short kept,
        # middle smoothly interpolated.
        smooth = (orig / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        inv_freq = (1 - smooth) * scaled + smooth * inv_freq
    return inv_freq


def rope_attention_scale(scaling: Optional[dict]) -> float:
    """YaRN's amplitude factor: HF multiplies cos AND sin by it, which
    equals scaling the roped q and k by the factor (score scale f²).
    1.0 for every other rope flavor."""
    if scaling and scaling.get("rope_type", scaling.get("type")) == "yarn":
        explicit = scaling.get("attention_factor")
        if explicit is not None:
            return float(explicit)
        factor = float(scaling["factor"])

        def get_mscale(scale: float, mscale: float = 1.0) -> float:
            if scale <= 1.0:
                return 1.0
            return 0.1 * mscale * math.log(scale) + 1.0

        # deepseek-style yarn configs set BOTH mscale and mscale_all_dim;
        # HF then uses the ratio of the two mscales (ADVICE r4).  A lone
        # mscale is IGNORED by HF — the fallback is get_mscale(factor).
        mscale = scaling.get("mscale")
        mscale_all_dim = scaling.get("mscale_all_dim")
        if mscale and mscale_all_dim:
            return get_mscale(factor, float(mscale)) / get_mscale(
                factor, float(mscale_all_dim))
        return get_mscale(factor)
    return 1.0


def rope_by_kind(head_dim: int, rope_parameters: tuple) -> dict:
    """{layer type: (inv_freq, amplitude)} of a model with a rope of its own
    for each kind of layer (`ModelConfig.rope_parameters`: laguna).  A
    kind's `partial_rotary_factor` is the share of a head it rotates: its
    table has half that many frequencies, computed (yarn's ramp too) over
    the rotated width as HF does, and `apply_rope` leaves the rest of the
    head as it is."""
    tables = {}
    for kind, items in rope_parameters:
        rp = dict(items)
        rotated = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
        tables[kind] = (
            rope_frequencies(rotated, float(rp.get("rope_theta", 10000.0)),
                             rp),
            rope_attention_scale(rp))
    return tables


def apply_rope(
    x: jax.Array,  # [..., seq, heads, head_dim]
    positions: jax.Array,  # [..., seq]
    inv_freq: jax.Array,  # [rotated//2]: head_dim//2, or fewer (partial)
    scale: float = 1.0,  # yarn attention factor (rope_attention_scale)
) -> jax.Array:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) — HF llama convention.
    `inv_freq` shorter than half the head (a partial rotary factor): the
    head's FIRST 2 x len(inv_freq) values are rotated so, among themselves,
    and the rest pass as they are."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    d2 = inv_freq.shape[0]
    partial = 2 * d2 < x.shape[-1]
    rot = x[..., :2 * d2] if partial else x
    x1, x2 = rot[..., :d2].astype(jnp.float32), rot[..., d2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if scale != 1.0:
        out = out * scale
    if partial:
        return jnp.concatenate([out.astype(x.dtype), x[..., 2 * d2:]], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,  # [B, seq, heads, head_dim]
    positions: jax.Array,  # [B, 3, seq] — (temporal, height, width) ids
    inv_freq: jax.Array,  # [head_dim//2]
    section,  # 3 ints summing to head_dim//2 (HF mrope_section)
) -> jax.Array:
    """Multimodal rotary embedding (Qwen2-VL): the head_dim//2 rotary
    frequencies split into three contiguous sections that read their
    angle from the temporal / height / width position stream
    respectively.  Text tokens carry identical (t, h, w) ids, for which
    this reduces exactly to `apply_rope` — decode therefore never needs
    the 3-stream form, only a scalar position shifted by the sequence's
    mrope delta.  Reference semantics: HF Qwen2VL
    `apply_multimodal_rotary_pos_emb` (modeling_qwen2_vl.py)."""
    t, h, w = section
    assert t + h + w == inv_freq.shape[0], (section, inv_freq.shape)
    sec_of = jnp.concatenate([
        jnp.zeros((t,), jnp.int32),
        jnp.ones((h,), jnp.int32),
        jnp.full((w,), 2, jnp.int32),
    ])  # [d/2] → which stream each frequency reads
    # angles[b, s, i] = positions[b, sec_of[i], s] * inv_freq[i]
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(sec_of[None, :, None],
                         (positions.shape[0], inv_freq.shape[0],
                          positions.shape[2])),
        axis=1,
    )  # [B, d/2, seq]
    angles = pos.transpose(0, 2, 1) * inv_freq  # [B, seq, d/2]
    cos = jnp.cos(angles)[..., None, :]  # [B, seq, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
