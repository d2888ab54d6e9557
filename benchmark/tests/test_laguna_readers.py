"""The five readers PR 52 adds for the cell `laguna-xs2-33b.longdoc-1tok`, on a
hand-made traced window and event list, and the family's roofline file against
hand counts at one shape (48 / 64 heads, a 512 window, a 6k context).  A
program without the scopes and the counters (the parent's) reads None."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import moe_scopes, moe_trace, opwalk, peaks, roofline  # noqa: E402

MS = 1_000_000
CELL = "laguna-xs2-33b.longdoc-1tok"
NEW = {
    "step.routed_experts_device_pct": ("model step", "device_trace", "lower"),
    "step.moe_dispatch_device_pct": ("model step", "device_trace", "lower"),
    "kernel.routed_experts_roofline": ("kernels", "device_trace", "higher"),
    "engine.moe_touched_pct": ("engine", "program_counter", "higher"),
    "engine.moe_dispatched_token_pct": ("engine", "program_counter",
                                        "higher"),
}
# (kernel name, path of scopes, part)
PLACED = [
    ("%fusion.12 = bf16[1,512,256]", "jit(prefill_step)/while/body/mlp/"
     "moe.router/...h,he->...e/dot_general", "router"),
    ("%sort.3 = (s32[4096], s32[4096])", "jit(prefill_step)/while/body/mlp/"
     "moe.dispatch/sort", "dispatch"),
    ("%moe.experts.7 = f32[4096,512] custom-call", "", "experts"),
    ("%fusion.40 = f32[4096,2048]", "jit(prefill_step)/while/body/mlp/"
     "moe.experts/ragged_dot", "experts"),
    ("%scatter.2 = f32[512,2048]", "jit(prefill_step)/while/body/mlp/"
     "moe.combine/scatter-add", "combine"),
    ("%fusion.41 = bf16[1,512,2048]", "jit(prefill_step)/while/body/mlp/"
     "moe.shared/bsf,fh->bsh/dot_general", "shared"),
    ("%attn.core.5 = bf16[1,512,64,128] custom-call", "jit(prefill_step)/"
     "while/body/attn.core", None),
    ("%while.4 = (s32[], bf16[1,512,2048])", "jit(prefill_step)/while", None),
    ("%fusion.9 = bf16[1,512,2048]", "jit(prefill_step)/while/body/mlp/"
     "moe.unknown/x", None),
]


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def laguna():
    with open(os.path.join(BENCH, "configs", "laguna-xs2-33b-h7.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,scope,part", PLACED)
def test_an_op_is_placed_by_its_last_moe_scope(name, scope, part):
    assert moe_scopes.place(name, scope) == part


@pytest.fixture
def window(tmp_path, monkeypatch):
    """Two prefill steps: a 512-token one whose program runs 60 ms (router 2,
    dispatch 8, experts 30, combine 4, shared 1, attention 10) under the
    dispatched form, and a shared step of 4 rows (120 tokens, 20 ms, experts
    12 of them) under the all-experts form."""
    names = [n for n, _, _ in PLACED]
    scopes = [s for _, s, _ in PLACED]
    t0, t1 = 100 * MS, 200 * MS
    ops = [[7, t0, 60 * MS],  # the loop itself: its self time is nobody's
           [0, t0 + 1 * MS, 2 * MS], [1, t0 + 3 * MS, 8 * MS],
           [2, t0 + 11 * MS, 10 * MS], [3, t0 + 21 * MS, 20 * MS],
           [4, t0 + 41 * MS, 4 * MS], [5, t0 + 45 * MS, 1 * MS],
           [6, t0 + 46 * MS, 10 * MS],
           [3, t1 + 1 * MS, 12 * MS], [6, t1 + 14 * MS, 3 * MS]]
    path = tmp_path / "trace.json"

    def write(scopes_, names_=names):
        opwalk._MEMO.clear()  # noqa: SLF001
        opwalk._COMPACT.clear()  # noqa: SLF001
        path.write_text(json.dumps({
            "names": names_, "scopes": scopes_, "planes": [{
                "name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": ops}]}]}))

    write(scopes)
    monkeypatch.setattr(moe_trace, "trace_path", lambda: str(path))
    steps = [
        {"kind": "prefill_chunk", "t_ns": t0 - 3 * MS, "dur_ns": 70 * MS,
         "batch": 1, "tokens": 512, "bucket": 512, "ctx": 6144,
         "moe_form": "dispatched", "moe_assignments": 6 * 512 * 8,
         "experts_hit": 600, "moe_max_load": 300},
        {"kind": "prefill_chunk", "t_ns": t1 - 3 * MS, "dur_ns": 30 * MS,
         "batch": 4, "tokens": 120, "bucket": 64, "ctx": 6000,
         "moe_form": "all_experts", "moe_assignments": 6 * 120 * 8,
         "experts_hit": 168, "moe_max_load": 90}]
    run = {"t0": 0.0, "t1": 1.0, "events": steps, "records": [],
           "config": laguna(), "peaks": peaks.peaks_for("TPU v5 lite"),
           "metrics0": {}, "metrics1": {},
           "trace": {"modules": [[(t0, t0 + 60 * MS, "jit_prefill_step"),
                                  (t1, t1 + 20 * MS, "jit_prefill_step")]]}}
    return run, write, names


def test_the_scope_readers_reduce_a_traced_window(window):
    run, _, _ = window
    moe = (2 + 8 + 30 + 4 + 1) + 12
    assert reader("step.routed_experts_device_pct")(run) == pytest.approx(
        100 * moe / 80)
    assert reader("step.moe_dispatch_device_pct")(run) == pytest.approx(
        100 * (8 + 4) / 80)
    fam, model = roofline.family(run["config"]), run["config"]["model"]
    floor = sum(fam.routed_experts_floor_s(
        model, run["peaks"], e["moe_assignments"], e["experts_hit"])[0]
        for e in run["events"])
    got = reader("kernel.routed_experts_roofline")(run)
    assert got == pytest.approx(100 * floor / 0.042)
    assert 0 < got <= 100


def test_the_counter_readers_read_the_step_events(window):
    run, _, _ = window
    assert reader("engine.moe_touched_pct")(run) == pytest.approx(
        100 * (600 + 168) / 2 / (256 * 6))
    assert reader("engine.moe_dispatched_token_pct")(run) == pytest.approx(
        100 * 512 / 632)


def test_a_program_without_the_scopes_and_counters_reads_none(window):
    """The parent's side of a traced run: no `moe.*` scope in the trace, no
    `moe_form` / `moe_assignments` on the events; and a run without a
    trace."""
    run, write, names = window
    bare_names = [n.replace("%moe.experts.7", "%fusion.7") for n in names]
    write([""] * len(names), bare_names)
    bare = dict(run, events=[
        {k: v for k, v in e.items()
         if k not in ("moe_form", "moe_assignments")} for e in run["events"]])
    for name in NEW:
        assert reader(name)(bare) is None, name
    for name in ("step.routed_experts_device_pct",
                 "step.moe_dispatch_device_pct",
                 "kernel.routed_experts_roofline"):
        assert reader(name)(dict(run, trace=None)) is None, name


def test_the_roofline_file_counts_each_layer_at_its_own_heads_and_reach():
    """48 heads over the whole context in the two full layers, 64 over at
    most 512 keys in the five windowed ones; the step's floor charges each
    layer's own projections and 8 experts a sparse layer."""
    cfg, p = laguna(), peaks.peaks_for("TPU v5 lite")
    fam, model = roofline.family(cfg), cfg["model"]
    tokens, ctx = 512, 6144
    full = tokens * (ctx - tokens) + tokens * (tokens + 1) // 2
    windowed = tokens * 512
    flops = 4 * 128 * (2 * 48 * full + 5 * 64 * windowed)
    got, which = fam.prefill_attn_floor_s(model, p, tokens, ctx)
    assert which == "compute"
    assert got == pytest.approx(flops / p["bf16_flops_per_s"])
    # a short context: every token still under 512 keys, both kinds causal
    pairs = 64 * 65 // 2
    got, _ = fam.prefill_attn_floor_s(model, p, 64, 64)
    mem = 2 * 64 * 8 * 128 * 2 / p["hbm_bytes_per_s"]
    assert got == pytest.approx(sum(
        max(mem, 4 * 128 * nq * pairs / p["bf16_flops_per_s"])
        for nq in (48, 64, 64, 64, 48, 64, 64)))
    attn = {nq: 2 * 2048 * nq * 128 + 2 * 2048 * 1024 + 2048 * nq
            for nq in (48, 64)}
    expert = 3 * 2048 * 512
    sparse = 2048 * 256 + expert + 8 * expert
    n = (attn[48] + 3 * 2048 * 8192) + (attn[48] + sparse) + 5 * (
        attn[64] + sparse)
    assert fam.every_step_params(model) == n == 471_662_592
    secs, which = fam.prefill_step_floor_s(model, p, 512)
    assert which == "compute"
    assert secs == pytest.approx(2 * 512 * n / p["bf16_flops_per_s"])
    secs, which = fam.prefill_step_floor_s(model, p, 64)
    assert which == "memory"
    assert secs == pytest.approx(2 * n / p["hbm_bytes_per_s"])


def test_the_spec_lists_the_new_readers_for_the_new_cell_alone():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name, (layer, source, better) in NEW.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"], m["moves"],
                m["unit"]) == (layer, source, better, "ttft_p95_ms", "%")
        assert m["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py"))
    for name in ("step.attn_device_pct", "kernel.prefill_attn_roofline",
                 "step.copy_device_pct", "kernel.prefill_rows_step_roofline"):
        assert by_name[name]["workloads"][-1] == CELL
