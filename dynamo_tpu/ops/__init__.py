"""TPU compute ops: attention over paged KV, RoPE, norms, sampling.

Reference impls are pure jnp (XLA fuses them well); Pallas kernels live in
``dynamo_tpu.ops.pallas`` and are selected at engine build time when running
on real TPU hardware.
"""

from .norm import layer_norm, rms_norm
from .paged_attention import (
    decode_attention,
    gather_kv,
    prefill_attention,
    write_kv_layers,
    write_kv_pages,
)
from .rotary import (apply_mrope, apply_rope, rope_attention_scale,
                     rope_by_kind, rope_frequencies)
from .sampling import (
    SamplingParams,
    apply_penalties,
    compute_logprobs,
    sample_tokens,
    top_logprobs,
)

__all__ = [
    "SamplingParams",
    "apply_penalties",
    "apply_mrope",
    "apply_rope",
    "compute_logprobs",
    "decode_attention",
    "gather_kv",
    "layer_norm",
    "prefill_attention",
    "rms_norm",
    "rope_attention_scale",
    "rope_by_kind",
    "rope_frequencies",
    "sample_tokens",
    "top_logprobs",
    "write_kv_layers",
    "write_kv_pages",
]
