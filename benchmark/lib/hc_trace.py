"""Device time of a hyper-connection model's prefill programs that the
stream mixers take (scopes `hc.mix`, `hc.pre`, `hc.post`, `hc.head`:
dynamo_tpu/ops/hyper_connections.py), for the readers
`step.hyper_conn_device_pct` and `kernel.hyper_conn_roofline`.  It reads the
family's own config.json keys (`hc_mult`); on a program or a configuration
without them the readers return None and their metrics are left out.

Where it reads: the compact trace `lib/trace.py` wrote for this run (found
as `lib/moe_trace.py` finds it), over EVERY `prefill_chunk` slice of the
window (`lib/rowsview.py`: the steps that several sequences share among
them, PERF.md 7 (r)).

How an op is placed (as `lib/latent_trace.py` places its own: a named scope
is metadata and the compact form keeps names only).  A kernel the compiler
named after a scope (`%hc.mix.3 = ...`) is the mixers'.  Else an op is the
mixers' where its HLO line lists an array only this mechanism has, with n =
hc_mult, H = hidden_size, M = n^2 + 2n:
  the residual's streams     [.., n, H] of three or more axes
  their concatenation        [.., n x H]
  a mixer's matrix           [.., n, H, M] or [.., H, M]; the head's [.., n,
                             H, n]
  a mixer's logits           [.., M], or [M, tokens] (the program keeps the
                             tokens on the minor axis through the Sinkhorn
                             steps)
  the mixing matrices        [n, n, tokens] or [.., tokens, n, n]
UNLESS the line also lists a weight matrix of one of the layer's halves
(attention's projections, the dense feed-forward, the router, the shared
expert, an expert stack, the output head): that op is a matrix product with
a mixer's read or write fused into it, its time is the product's, and it
stays with whoever owns the product (`lib/latent_trace.py`).  So a fusion
that spans the boundary is counted like this: the half's own norm fused into
`pre` (it reads the streams and writes the normalised input: no weight
matrix) is the MIXERS'; `post` fused into the next half's `mix` is the
mixers' on both sides; a product's epilogue that writes the streams is the
PRODUCT's.  A `while` (the layer loop) is nobody's: only self time is
counted."""

import json
import re

from . import moe_trace, rowsview, trace

_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def is_family(model):
    return bool(model.get("hc_mult"))


def _weights(model):
    """Last two (or three) axes of the weight matrices of a layer's halves
    and of the head."""
    H, F, I = (model["hidden_size"], model["moe_intermediate_size"],
               model["intermediate_size"])
    nh, qr, r = (model["num_attention_heads"], model["q_lora_rank"],
                 model["kv_lora_rank"])
    nope, pe, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    E = model["n_routed_experts"]
    W = E * model.get("ep_size", 1)
    S = F * model["n_shared_experts"]
    V = model["vocab_size"]
    two = {(H, qr), (qr, nh * (nope + pe)), (H, r + pe), (nh * vd, H),
           (H, I), (I, H), (H, W), (H, S), (S, H), (H, V), (V, H)}
    three = {(E, H, F), (E, F, H), (nh, nope, r), (nh, r, vd)}
    return two, three


def is_mixer_op(name, model):
    """Whether the op of this HLO line is the stream mixers'."""
    head = name.split(" = ", 1)[0]
    if head.startswith("%while"):
        return False
    if head.startswith("%hc."):
        return True
    n, H = model["hc_mult"], model["hidden_size"]
    M = n * n + 2 * n
    dims = [tuple(int(d) for d in m.split(","))
            for m in _ARRAY.findall(name)]
    two, three = _weights(model)
    if any(d[-2:] in two for d in dims if len(d) >= 2):
        return False
    if any(d[-3:] in three for d in dims if len(d) >= 3):
        return False
    for d in dims:
        if len(d) >= 3 and d[-2:] == (n, H):
            return True
        if len(d) >= 2 and d[-1] == n * H:
            return True
        if len(d) >= 2 and d[-2:] in ((H, M), (H, n)) and (
                len(d) == 2 or d[-3] == n):
            return True
        if len(d) >= 2 and (d[-1] == M or (len(d) == 2 and d[0] == M)):
            return True
        if len(d) == 3 and d[:2] == (n, n) and d[2] >= 16:
            return True
        if len(d) >= 3 and d[-2:] == (n, n) and d[-3] >= 16:
            return True
    return False


_MEMO = {}


def prefill_mixer_seconds(run):
    """(program seconds, mixer seconds, [(step event, program seconds)])
    over EVERY `prefill_chunk` step of the window: self time of the device
    ops inside each step's program execution that `is_mixer_op` places.
    None without a trace, without the compact file, or for a configuration
    without hyper-connections."""
    key = (moe_trace.trace_path(), id(run))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = (run, _prefill_mixer_seconds(run, key[0]))
    return _MEMO[key][1]


def _prefill_mixer_seconds(run, path):
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
    mixer_ns, j, placed = 0, 0, {}
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
                placed[i] = is_mixer_op(compact["names"][i], model)
            if placed[i]:
                mixer_ns += ns
    return sum(secs for _, secs in timed), mixer_ns / 1e9, timed
