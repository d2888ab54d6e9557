"""Tensor names and shapes of the Falcon-H1 family (TII Falcon-H1-34B-
Instruct: `model_type` "falcon_h1") as its Hugging Face checkpoints carry
them: every layer is `model.layers.{i}.input_layernorm`, a `mamba` mixer
(`in_proj`, `conv1d` with its bias, `dt_bias`, `A_log`, `D`, the gated
`norm`, `out_proj`) AND `self_attn` (`q_proj`, `k_proj`, `v_proj`, `o_proj`,
no biases) side by side, then `pre_ff_layernorm` and `feed_forward.{gate,
up, down}_proj`; `model.final_layernorm` and an untied `lm_head`.  `model` is
the configuration's `model` object (config.json keys).  The names are
ASSUMED from the family's published modelling code (the catalog row carries
no tensor names); the configuration's file says so.  No multiplier of the
family is in any tensor: they scale activations.

Yields (name, shape, kind); kind is "weight" (random, std 0.014) or "ones"
(`benchmark/lib/checkpoint.py` has these two kinds).  Norm scales are ones.
So are the convolution's TAPS and `D`: four taps of 1.0 are a moving sum of
the last four inputs and D = 1 is the family's own initialisation.  Drawn
as "weight" (PR 59's first checkpoint) the taps were 0.014, x, B and C left
the convolution at its bias, y * silu(z) had a mean square a thousand times
UNDER `rms_norm_eps`, the gated norm divided by sqrt(eps), and the whole
state-space half added 1e-4 to a stream of 0.08: `correct` could not see it
deleted (REVIEW of PR 59).  With taps of 1 that mean square is 7e-6 beside
the eps of 1e-5 and the half adds 0.050 to a stream of 0.078: nine of the
reference's thirteen faults fail `correct` at full size, `no_ssm_half` by
twenty times the limit (`benchmark/reference/falcon_h1.py`).
`conv1d.bias`, `dt_bias`, `A_log` keep the "weight" draw: `A_log` and
`dt_bias` come out near 0, A near -1 and a step size near 0.7: a state that
forgets in about ten tokens (the configuration's `assumed.weights`)."""


def tensors(model):
    H, F = model["hidden_size"], model["intermediate_size"]
    nh, K = model["mamba_n_heads"], model.get("mamba_d_conv", 4)
    d = model.get("mamba_d_ssm") or nh * model["mamba_d_head"]
    cd = d + 2 * model.get("mamba_n_groups", 1) * model["mamba_d_state"]
    hd = model.get("head_dim") or H // model["num_attention_heads"]
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    yield "model.embed_tokens.weight", (model["vocab_size"], H), "weight"
    for i in range(model["num_hidden_layers"]):
        p = f"model.layers.{i}."
        m, a, f = p + "mamba.", p + "self_attn.", p + "feed_forward."
        yield p + "input_layernorm.weight", (H,), "ones"
        yield m + "in_proj.weight", (d + cd + nh, H), "weight"
        yield m + "conv1d.weight", (cd, 1, K), "ones"
        yield m + "conv1d.bias", (cd,), "weight"
        yield m + "dt_bias", (nh,), "weight"
        yield m + "A_log", (nh,), "weight"
        yield m + "D", (nh,), "ones"
        yield m + "norm.weight", (d,), "ones"
        yield m + "out_proj.weight", (H, d), "weight"
        yield a + "q_proj.weight", (q, H), "weight"
        yield a + "k_proj.weight", (kv, H), "weight"
        yield a + "v_proj.weight", (kv, H), "weight"
        yield a + "o_proj.weight", (H, q), "weight"
        yield p + "pre_ff_layernorm.weight", (H,), "ones"
        yield f + "gate_proj.weight", (F, H), "weight"
        yield f + "up_proj.weight", (F, H), "weight"
        yield f + "down_proj.weight", (H, F), "weight"
    yield "model.final_layernorm.weight", (H,), "ones"
    if not model.get("tie_word_embeddings", False):
        yield "lm_head.weight", (model["vocab_size"], H), "weight"
