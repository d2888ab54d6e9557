"""Device time of a deepseek_v3 model's prefill programs by what the ops
belong to: latent attention (scopes `attn.q_lora`, `attn.kv_latent`,
`attn.kv_up`, `attn.core`, `attn.out` and the gather of latent pages:
dynamo_tpu/models/llama.py, ops/latent_attention.py), the expert layer's
share (`moe.router`, `moe.dispatch`, `moe.experts`, `moe.shared`,
`moe.combine`), and within that the held experts' matmuls alone, for the
readers `step.latent_attn_device_pct`, `step.expert_share_device_pct` and
`kernel.expert_share_roofline`.  It reads this family's own config.json
keys; `lib/moe_trace.py` reads SmallThinker's.

Where it reads: the compact trace `lib/trace.py` wrote for this run (found
as `lib/moe_trace.py` finds it).  Nothing there (a run without a trace, a
program without the family): the readers return None and their metrics are
left out.

How an op is placed.  A named scope is metadata and the compact form keeps
names only (PERF.md 7 (a)), so an op is placed by its kernel name where the
compiler named it after a scope (`%moe.experts.3 = ... custom-call`,
`%attn.core.7`), and else by the arrays its HLO line lists, whose shapes
nothing else in this model has:
  latent attention  a weight [.., hidden, q_rank], [.., q_rank, heads x
      (nope + pe)], [.., hidden, rank + pe], [.., heads, nope, rank], [..,
      heads, rank, v] or [.., heads x v, hidden]; rows of rank + pe values
      (the latent pages and what is gathered from them, absorbed queries);
      per-head latents [.., heads, rank]; scores [batch, heads, chunk,
      keys] of a chunk's queries
  experts           a held stack [.., held, hidden, width] or [.., held,
      width, hidden]; activations [held, batch, tokens, width]
  expert share      the experts, and: the router [.., hidden, W] and rows
      over its W outputs [.., W] or [.., groups, W / groups]; the shared
      expert [.., hidden, shared width] / [.., shared width, hidden] (2 or
      3 axes: a layer's slice or the stack of layers); outputs [held,
      batch, tokens, hidden]; one-hot rows [.., k, held] and the combine
      weights [batch, tokens, held]
A `while` (the layer loop) is nobody's: only self time is counted."""

import json
import re

from . import moe_trace, runview, trace

GROUPS = ("latent_attn", "experts", "expert_share_rest")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def is_family(model):
    return "kv_lora_rank" in model and "n_routed_experts" in model


def group_of(name, model):
    """"latent_attn" | "experts" | "expert_share_rest" | None for the op of
    this HLO line."""
    head = name.split(" = ", 1)[0]
    if head.startswith("%while"):
        return None
    if head.startswith("%moe."):
        return ("experts" if head.startswith("%moe.experts")
                else "expert_share_rest")
    if head.startswith(("%attn.", "%kv.gather")):
        return "latent_attn"
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    nh, qr, r = (model["num_attention_heads"], model["q_lora_rank"],
                 model["kv_lora_rank"])
    nope, pe, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    E, k = model["n_routed_experts"], model["num_experts_per_tok"]
    W = E * model.get("ep_size", 1)
    G = model["n_group"]
    S = F * model["n_shared_experts"]
    dims = [tuple(int(d) for d in m.split(","))
            for m in _ARRAY.findall(name)]
    two = [d[-2:] for d in dims if len(d) >= 2]
    three = [d[-3:] for d in dims if len(d) >= 3]
    if any(t in ((E, H, F), (E, F, H)) for t in three):
        return "experts"
    if any(len(d) >= 3 and d[0] == E and d[-1] == F for d in dims):
        return "experts"
    if any(t in ((H, W), (G, W // G), (k, E), (k, W)) for t in two):
        return "expert_share_rest"
    if any(d[-1] == W and len(d) <= 3 for d in dims if len(d) >= 2):
        return "expert_share_rest"
    if any(t in ((H, S), (S, H)) for t in two):
        return "expert_share_rest"
    if any(len(d) >= 3 and d[0] == E and d[-1] == H for d in dims):
        return "expert_share_rest"
    if any(len(d) == 3 and d[-1] == E for d in dims):
        return "expert_share_rest"
    if any(t in ((H, qr), (qr, nh * (nope + pe)), (H, r + pe),
                 (nh * vd, H), (nh, r)) for t in two):
        return "latent_attn"
    if any(t in ((nh, nope, r), (nh, r, vd)) for t in three):
        return "latent_attn"
    if any(d[-1] in (r, r + pe) and len(d) >= 3 for d in dims):
        return "latent_attn"
    if any(len(d) == 5 and d[-2:] in ((-(-r // 128), 128), (1, 128))
           for d in dims):
        return "latent_attn"  # latent pages [.., page, tiles, 128]
    if any(len(d) == 4 and d[1] <= nh and nh % d[1] == 0 and d[1] > 1
           and d[2] >= 16 and d[3] >= d[2] for d in dims):
        return "latent_attn"  # scores [batch, head block, chunk, keys]
    return None


_MEMO = {}


def prefill_group_seconds(run):
    """(program seconds, {group: seconds}, [(step event, program seconds)])
    over the window's one-sequence `prefill_chunk` steps: self time of the
    device ops inside each step's program execution, by `group_of`.  None
    without a trace, without the compact file, or for another family."""
    key = (moe_trace.trace_path(), id(run))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = (run, _prefill_group_seconds(run, key[0]))
    return _MEMO[key][1]


def _prefill_group_seconds(run, path):
    model = run["config"]["model"]
    timed = runview.prefill_steps(run)
    if not timed or path is None or not is_family(model):
        return None
    with open(path) as f:
        compact = json.load(f)
    slices = [(e["t_ns"], e["t_ns"] + e["dur_ns"]) for e, _ in timed]
    programs = []  # the execution inside each slice: the longest one
    mods = run["trace"]["modules"][0]
    for s, e in slices:
        inside = [(m[1] - m[0], m[0], m[1]) for m in mods
                  if s <= m[0] <= e and m[1] <= e + 1_000_000]
        if inside:
            programs.append(max(inside)[1:])
    programs.sort()
    ops = trace.line_of(compact["planes"][0], trace.OPS_LINE)
    if ops is None or not programs:
        return None
    by_group, j, group_of_index = dict.fromkeys(GROUPS, 0), 0, {}
    events = sorted(ops["events"], key=lambda ev: ev[1])
    for a, b in programs:
        while j < len(events) and events[j][1] < a:
            j += 1
        inside = []
        while j < len(events) and events[j][1] < b:
            i, s, d = events[j]
            inside.append((s, min(s + d, b), i))
            j += 1
        for i, ns in trace.self_times(inside).items():
            if i not in group_of_index:
                group_of_index[i] = group_of(compact["names"][i], model)
            if group_of_index[i]:
                by_group[group_of_index[i]] += ns
    return (sum(secs for _, secs in timed),
            {g: v / 1e9 for g, v in by_group.items()}, timed)
