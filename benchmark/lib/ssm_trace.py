"""Device time of a nemotron_h model's prefill programs by mechanism: the
state-space layers (scopes `ssm.in_proj`, `ssm.conv`, `ssm.scan`,
`ssm.gate_norm`, `ssm.out_proj`: dynamo_tpu/models/hybrid.py), the state
slots beside the pages (`state.read`, `state.write`) and the expert layer
(`moe.*`), for the readers `step.ssm_device_pct`, `kernel.ssm_scan_roofline`
and `step.expert_layer_device_pct`.  It reads the family's own config.json
keys (`hybrid_override_pattern`, `mamba_num_heads`); on a program or a
configuration without them the readers return None and their metrics are left
out.

Where it reads: the compact trace `lib/trace.py` wrote for this run (found as
`lib/moe_trace.py` finds it), over EVERY `prefill_chunk` slice of the window
(`lib/rowsview.py`: the steps that several sequences share among them,
PERF.md 7 (r)).

How an op is placed (as `lib/hc_trace.py` places its own: a named scope is
metadata and the compact form keeps names only).  A kernel the compiler named
after a scope (`%ssm.scan.3 = ...`) is that scope's.  Else an op is placed by
the arrays its HLO line lists, first match, with H = hidden_size, d = heads x
head_dim, cd = d + 2 G N, Wi = d + cd + heads, E the experts held, R the
router's width, F and S the expert's and the shared expert's widths:
  "state"     a slot pool [Lm, slots, ..] or every layer's new rows for it
              [Lm, rows, ..] (Lm the "M" layers), ending in the window's
              tiles [tiles, 128] or a state's [head_dim, N]
  nobody's    attention's `o_proj` where the line shows its STACK [La, d, H]
              (La the "*" layers: see below)
  "ssm.proj"  `in_proj` [.., H, Wi] or `out_proj` [.., d, H]
  "moe"       an expert stack [.., E, H, F] or [.., E, F, H], the router
              [.., H, R], the shared expert [.., H, S] or [.., S, H]
  nobody's    another weight matrix: attention's q, k, v, the head, the
              embedding: the product owns whatever is fused into it
  "moe"       activations [E, .., F], outputs [E, .., H] of three or more
              axes, the shared expert's [.., S], rows over the experts
              [.., k, E] or [tokens.., E] of at most three axes
  "ssm.scan"  (the convolution, the scan and the gated norm together, which
              `kernel.ssm_scan_roofline` sets against `ssm_scan_floor_s`) the
              in_proj's output and its parts [.., Wi], [.., cd]; and, axes of
              size 1 apart (the compiler drops a batch of one), arrays of
              three or more axes that end in heads [heads, head_dim], groups
              [G, N], a state [heads, head_dim, N] or [G, heads / G,
              head_dim, N], or a block's decays and scores [heads, Q, Q],
              [G, Q, Q] (Q the scan's block, or a shorter chunk)
A `while` (the layer loop) and a `conditional` (a unit's attention) are
nobody's: only self time is counted.

What the shapes cannot tell apart at this family's published widths:
attention's `o_proj` is [4096, 2688] as `out_proj` is (32 heads of 128 = 64
heads of 64).  Where the product's line shows the stack it slices, [6, ..]
against [23, ..], it is told; where the compiler cut the layer's matrix out
first, the line shows [4096, 2688] alone and the product is placed with
"ssm.proj" (`kernel.ssm_scan_roofline` does not read "ssm.proj").  The
router's scores [.., R] (R = 128 = N = a lane tile), per-head vectors
[heads] and the norms' row sums [tokens] are left unplaced: microseconds.
"""

import json
import re

from . import moe_trace, rowsview, trace

KINDS = ("ssm.proj", "ssm.scan", "state", "moe")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def is_family(model):
    return bool(model.get("hybrid_override_pattern")
                and model.get("mamba_num_heads"))


def _sizes(model):
    nh, hp, N = (model["mamba_num_heads"], model["mamba_head_dim"],
                 model["ssm_state_size"])
    G, d = model["n_groups"], nh * hp
    cd = d + 2 * G * N
    return {
        "H": model["hidden_size"], "nh": nh, "hp": hp, "N": N, "G": G,
        "d": d, "cd": cd, "Wi": d + cd + nh, "K": model["conv_kernel"],
        "Q": model.get("chunk_size", 128),
        "Lm": model["hybrid_override_pattern"].count("M"),
        "E": model["n_routed_experts"],
        "R": model["n_routed_experts"] * model.get("ep_size", 1),
        "k": model["num_experts_per_tok"],
        "F": model["moe_intermediate_size"],
        "S": model["moe_shared_expert_intermediate_size"],
        "La": model["hybrid_override_pattern"].count("*"),
        "q": model["num_attention_heads"] * model["head_dim"],
        "kv": model["num_key_value_heads"] * model["head_dim"],
        "V": model["vocab_size"]}


def _tails(dims, *patterns):
    """Whether an array's axes, those of size 1 apart, end in one of the
    patterns (the compiler drops a batch of one and adds unit axes)."""
    a = tuple(x for x in dims if x != 1)
    return any(a[-len(p):] == p for p in patterns if len(a) >= len(p))


def place(name, model):
    """The mechanism (of `KINDS`) the op of this HLO line belongs to, or
    None."""
    head = name.split(" = ", 1)[0]
    if head.startswith(("%while", "%conditional", "%attn.", "%kv.")):
        return None
    for prefix, kind in (("%ssm.in_proj", "ssm.proj"),
                         ("%ssm.out_proj", "ssm.proj"), ("%ssm.", "ssm.scan"),
                         ("%state.", "state"), ("%moe.", "moe")):
        if head.startswith(prefix):
            return kind
    z = _sizes(model)
    H, nh, hp, N, G, d, cd = (z[k] for k in ("H", "nh", "hp", "N", "G", "d",
                                            "cd"))
    E, F, S, per = z["E"], z["F"], z["S"], z["nh"] // z["G"]
    dims = [tuple(int(x) for x in m.split(","))
            for m in _ARRAY.findall(name)]
    tiles = -(-(z["K"] - 1) * cd // 128)
    if any(len(a) >= 3 and a[0] == z["Lm"]
           and a[-2:] in ((tiles, 128), (hp, N)) for a in dims):
        return "state"  # a slot pool, or every layer's new rows for it
    if any(_tails(a, (z["La"], d, H)) for a in dims):
        return None  # attention's o_proj, told by its stack of La layers
    if any(_tails(a, (H, z["Wi"]), (d, H)) for a in dims):
        return "ssm.proj"
    if any(_tails(a, (E, H, F), (E, F, H), (H, z["R"]), (H, S), (S, H))
           for a in dims):
        return "moe"
    if any(_tails(a, (H, z["q"]), (H, z["kv"]), (H, z["V"]), (z["V"], H))
           for a in dims):
        return None
    for a in dims:
        if len(a) >= 3 and a[0] == E and a[-1] in (F, H):
            return "moe"
        if len(a) >= 2 and (a[-1] == S or a[-2:] == (z["k"], E)
                            or (len(a) <= 3 and a[-1] == E)):
            return "moe"
    blocks = [(x, x) for x in (16, 32, 64, 128) if x <= z["Q"]]
    for a in dims:
        if len(a) >= 2 and a[-1] in (z["Wi"], cd):
            return "ssm.scan"
        if len(a) >= 3 and _tails(
                a, (nh, hp), (G, N), (nh, hp, N), (G, per, hp, N),
                (G, per, hp), *((h, *b) for b in blocks for h in (nh, G))):
            return "ssm.scan"
    return None


_MEMO = {}


def prefill_seconds(run):
    """(program seconds, {kind: seconds}, [(step event, program seconds)])
    over EVERY `prefill_chunk` step of the window: self time of the device
    ops inside each step's program execution, by `place`.  None without a
    trace, without the compact file, or for another family's
    configuration."""
    key = (moe_trace.trace_path(), id(run))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = (run, _prefill_seconds(run, key[0]))
    return _MEMO[key][1]


def _prefill_seconds(run, path):
    model = run["config"]["model"]
    if path is None or not is_family(model):
        return None
    timed = rowsview.prefill_steps(run)
    if not timed:
        return None
    with open(path) as f:
        compact = json.load(f)
    programs = []  # the execution inside each slice: the longest one
    mods = run["trace"]["modules"][0]
    for e, _ in timed:
        s, end = e["t_ns"], e["t_ns"] + e["dur_ns"]
        inside = [(m[1] - m[0], m[0], m[1]) for m in mods
                  if s <= m[0] <= end and m[1] <= end + 1_000_000]
        if inside:
            programs.append(max(inside)[1:])
    programs.sort()
    ops = trace.line_of(compact["planes"][0], trace.OPS_LINE)
    if ops is None or not programs:
        return None
    by_kind, j, placed = dict.fromkeys(KINDS, 0), 0, {}
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
            if i not in placed:
                placed[i] = place(compact["names"][i], model)
            if placed[i]:
                by_kind[placed[i]] += ns
    return (sum(secs for _, secs in timed),
            {k: v / 1e9 for k, v in by_kind.items()}, timed)
