"""Tensor names and shapes of the xing4_0 family (Xing4.0-29B-A4B:
`model_type` "xing4_0"): deepseek_v3's (`checkpoints/deepseek_v3.py`, whose
generator this file runs: latent attention, `first_k_dense_replace` dense
layers, then router, experts and shared expert) plus the hyper-connection
mixers of the `hc_mult`-stream residual: two a layer, `hc_attn` and `hc_ffn`,
each `.fn` [M, hc_mult x hidden] (M = hc_mult^2 + 2 hc_mult: the streams'
concatenation in, as a Linear stores it), `.scale` [3] and `.base` [M], and
the head's `model.hc_head.{fn [hc_mult, hc_mult x hidden], scale [1], base
[hc_mult]}`.  The mixers' names are ASSUMED (the catalog row carries no
tensor names); the configuration's file says so.  The multi-token-prediction
module is not written, as for deepseek_v3.

Yields (name, shape, kind).  A mixer's `scale` gets the kind "ones" and its
`fn` and `base` the kind "weight": `lib/checkpoint.py` has one draw (std
about 0.014), under which a scale drawn as a weight would leave every mixer
at pre 0.5, post 1, R 0.25 whatever the stream, and a transposed R
invisible to `correct`; at scale 1 the mixer's logits r (fn v) have a
spread near 0.014 x sqrt(hc_mult x hidden) = 1.7 and the Sinkhorn steps do
real work.  The file stores them as bf16 like every tensor; the program
computes with them in float32."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "checkpoints_deepseek_v3",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "deepseek_v3.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)


def mixer(prefix, n, H, width, scales):
    yield prefix + "fn", (width, n * H), "weight"
    yield prefix + "scale", (scales,), "ones"
    yield prefix + "base", (width,), "weight"


def tensors(model):
    n, H = model["hc_mult"], model["hidden_size"]
    M = n * n + 2 * n
    yield from base.tensors(model)
    for i in range(model["num_hidden_layers"]):
        for half in ("hc_attn", "hc_ffn"):
            yield from mixer(f"model.layers.{i}.{half}.", n, H, M, 3)
    yield from mixer("model.hc_head.", n, H, n, 1)
