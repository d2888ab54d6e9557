"""Kernels: the least time latent attention's operations of the window's
one-sequence `prefill_chunk` steps can take at this chip's bf16 peak, over
the device time of latent attention's ops (lib/latent_trace.py, the group
`step.latent_attn_device_pct` reads: projections, gather of latent pages and
attention core together, so the two cannot disagree about which ops count).

Operations, a step and layer: the five projections' (q_a, q_b, kv_a, kv_b,
o: the family's own count of their weights, benchmark/roofline/<family>.py,
two operations a weight a token) plus the attention core's over the pairs
the step's own `tokens` and `ctx` say a causal chunk can see (prefix =
`ctx` - `tokens`; pairs = `tokens` x prefix + `tokens` x (`tokens` + 1) /
2), as the LESSER of the two forms that compute it, so no implementation
passes 100% by choosing the other: absorbed, 2 x pairs x heads x (2 x rank
+ pe) (scores against the stored rows, values the latents); up-projected,
2 x pairs x heads x (nope + pe + v) and the keys and values of `ctx` tokens
made from their latents first, 2 x `ctx` x heads x (nope + v) x rank.
Keys masked, padded or scored twice are the implementation's own and are
not counted.  %."""

from lib import latent_trace, roofline


def core_ops(model, tokens, ctx):
    """Operations of one layer's attention core over `tokens` queries whose
    last sees `ctx` keys: the cheaper form's."""
    nh, r = model["num_attention_heads"], model["kv_lora_rank"]
    nope, pe, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    pairs = tokens * (ctx - tokens) + tokens * (tokens + 1) // 2
    absorbed = 2 * pairs * nh * (2 * r + pe)
    up_projected = (2 * pairs * nh * (nope + pe + vd)
                    + 2 * ctx * nh * (nope + vd) * r)
    return min(absorbed, up_projected)


def read(run):
    found = latent_trace.prefill_group_seconds(run)
    if found is None or not found[1]["latent_attn"]:
        return None
    _, by_group, timed = found
    dims = getattr(roofline.family(run["config"]), "_dims", None)
    steps = [e for e, _ in timed if "ctx" in e]
    if dims is None or not steps or len(steps) != len(timed):
        return None
    model = run["config"]["model"]
    projections = dims(model)[0]  # the five projections' weights, a layer
    ops = model["num_hidden_layers"] * sum(
        2 * e["tokens"] * projections + core_ops(model, e["tokens"], e["ctx"])
        for e in steps)
    return (100.0 * ops / run["peaks"]["bf16_flops_per_s"]
            / by_group["latent_attn"])
