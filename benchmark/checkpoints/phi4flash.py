"""Tensor names and shapes of Phi-4-mini-flash (`model_type` "phi4flash") as
the family's Hugging Face checkpoints carry them: every layer is
`model.layers.{l}.{input_layernorm, post_attention_layernorm}.{weight, bias}`,
`.mlp.{fc1, fc2}.weight` (fc1 the gate and the up halves in one matrix, the
gate first) and ONE mixer under `.attn`, whatever its kind
(`reference/phi4flash.py` `layer_kinds`): a Mamba-1 mixer (`in_proj`,
`conv1d` with its bias, `x_proj`, `dt_proj` with its bias, `A_log` [d, N],
`D`, `out_proj`), differential attention (`Wqkv` and `out_proj` with biases,
`inner_cross_attn.{lambda_q1, lambda_k1, lambda_q2, lambda_k2, subln.weight}`),
a Gated Memory Unit (`in_proj` [d, h] and `out_proj` [h, d] alone) or
differential cross-attention (as attention, `Wqkv` the queries' rows alone).
The embedding is tied: no `lm_head`.  `model` is the configuration's `model`
object (config.json keys).  The names are ASSUMED from the family's published
modelling code as known (the catalog row carries no tensor names); the
configuration's file says so.

Yields (name, shape, kind); kind is "weight" (random) or "ones"
(`benchmark/lib/checkpoint.py` has these two).  "ones": the norms' scales,
and in ONE Mamba-1 mixer, layer L/2's (16: the mixer whose scan output is the
memory every Gated Memory Unit reads), `D` (the family initialises it so) and
the convolution's four taps (a moving sum of the last four inputs).  The draw
has two settings for a mixer and PR 48 read both at full size (PERF.md,
finding 28):

  - every tensor drawn (|w| about 0.014): a mixer's input is about 0.01, its
    scan output 5e-6 and its output 1e-5 of the residual.  With all nine so,
    the state-space layers and the memory are invisible to `correct`
    (`memory_after_gate` and `memory_shifted` read 0.0002 where the served
    path read 0.037);
  - `D` and the taps ones: the mixer's input, its scan output and the memory
    are of order one and its output, 1.5 a layer, leads the residual.  With
    all nine so, bfloat16's own rounding is amplified through nine mixers,
    each cubic in its input (the served path read 0.29 where the 3-bit
    control read 0.79).

Nothing lies between: two kinds make a magnitude of 0.01 or of 1, never of
0.3.  So ONE mixer is on, the one the cross half hears: the eight windowed
units before it keep attention's and the feed-forward's share of the
residual, layer 16's scan, its memory and the seven Gated Memory Units are of
order one, and the served path's rounding reads 0.22 where the faults of the
window, the difference, the norms, the scan and the memory read 0.40-0.87.
The other eight mixers run the same scanned body on inputs of 0.01: `correct`
does not see THEIR arithmetic (tests/test_phi4flash.py and scripts/
check_selective_scan.py hold the scan at every size).  What no setting of
the two kinds shows is WHICH attention layer's pages the cross half reads
(`cross_reads_layer_15` 0.23): with the memory of order one the seven Gated
Memory Units add 1.0 a layer beside cross-attention's 0.14, and with it at
0.01 the memory's own faults vanish (PERF.md, finding 28, has the five draws
read).  `A_log`,
`dt_proj` and its bias, `x_proj`, the four lambda vectors and the LayerNorms'
biases get the "weight" draw in every layer: so `A_log` comes out near 0, A
near -1 for every channel and state index, a step size near 0.69, lambda at
its `lambda_init(l)` (the configuration's `assumed.weights`)."""

import importlib.util
import os


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference", "phi4flash.py")
    spec = importlib.util.spec_from_file_location("reference_phi4flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tensors(model):
    ref = _reference()
    z = ref.sizes(model)
    h, f, d, N, K, r = (z[k] for k in ("h", "f", "d", "N", "K", "r"))
    q, kv = z["nq"] * z["hd"], z["nkv"] * z["hd"]
    yield "model.embed_tokens.weight", (model["vocab_size"], h), "weight"
    kinds = ref.layer_kinds(model)
    for l, kind in enumerate(kinds):
        p = f"model.layers.{l}."
        a = p + "attn."
        # the mixer whose scan output is the memory: the one that is on
        lit = "ones" if l == len(kinds) // 2 else "weight"
        yield p + "input_layernorm.weight", (h,), "ones"
        yield p + "input_layernorm.bias", (h,), "weight"
        if kind == "S":
            yield a + "in_proj.weight", (2 * d, h), "weight"
            yield a + "conv1d.weight", (d, 1, K), lit
            yield a + "conv1d.bias", (d,), "weight"
            yield a + "x_proj.weight", (r + 2 * N, d), "weight"
            yield a + "dt_proj.weight", (d, r), "weight"
            yield a + "dt_proj.bias", (d,), "weight"
            yield a + "A_log", (d, N), "weight"
            yield a + "D", (d,), lit
            yield a + "out_proj.weight", (h, d), "weight"
        elif kind == "G":
            yield a + "in_proj.weight", (d, h), "weight"
            yield a + "out_proj.weight", (h, d), "weight"
        else:
            rows = q if kind == "C" else q + 2 * kv
            yield a + "Wqkv.weight", (rows, h), "weight"
            yield a + "Wqkv.bias", (rows,), "weight"
            inner = a + "inner_cross_attn."
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                yield inner + name, (z["hd"],), "weight"
            yield inner + "subln.weight", (2 * z["hd"],), "ones"
            yield a + "out_proj.weight", (h, q), "weight"
            yield a + "out_proj.bias", (h,), "weight"
        yield p + "post_attention_layernorm.weight", (h,), "ones"
        yield p + "post_attention_layernorm.bias", (h,), "weight"
        yield p + "mlp.fc1.weight", (2 * f, h), "weight"
        yield p + "mlp.fc2.weight", (h, f), "weight"
    yield "model.final_layernorm.weight", (h,), "ones"
    yield "model.final_layernorm.bias", (h,), "weight"
