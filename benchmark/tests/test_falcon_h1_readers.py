"""The readers PR 59 adds for the cell `falcon-h1-34b.longdoc-1tok`, on a
hand-made traced window, and the family's roofline file against hand counts.
A program without the `ssm.*` scopes (the parent's) and another family's
configuration read None."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import halves_trace, moe_trace, opwalk, peaks, roofline  # noqa: E402

MS = 1_000_000
CELL = "falcon-h1-34b.longdoc-1tok"
NEW = {"step.ssm_half_device_pct": ("model step", "lower"),
       "kernel.ssm_half_scan_roofline": ("kernels", "higher"),
       "step.feed_forward_device_pct": ("model step", "lower")}
APPENDED = ("engine.state_hit_depth_pct", "step.attn_device_pct",
            "kernel.prefill_attn_roofline",
            "kernel.prefill_rows_attn_roofline",
            "step.prefill_rows_device_ms_per_ktok", "host.cycle_ms_per_step",
            "setup.programs_s", "compile.in_window")
LOOP = "jit(prefill_step)/while/body/closed_call/"
# (kernel name, path of scopes, group)
PLACED = [
    ("%fusion.306 = bf16[1,512,9248]", LOOP + "ssm.in_proj/bsh,hd->bsd/"
     "dot_general", "ssm.proj"),
    ("%fusion.310 = bf16[1,512,5120]", LOOP + "ssm.out_proj/mul", "ssm.proj"),
    ("%multiply_convert_fusion.13 = bf16[1,512,5120]", LOOP + "ssm.conv/mul",
     "ssm.scan"),
    ("%fusion.337 = f32[1,128,32,128]", LOOP + "ssm.scan/bhts,bshp->bthp/"
     "dot_general", "ssm.scan"),
    ("%fusion.295 = bf16[512,4096]", LOOP + "ssm.gate_norm/mul", "ssm.scan"),
    ("%ssm.scan.3 = f32[1,32,128,256] custom-call", "", "ssm.scan"),
    ("%dynamic-slice.7 = f32[1,1,32,128,256]", LOOP + "state.read/"
     "dynamic_slice", "state"),
    ("%fusion.233 = f32[6,137,32,128,256]", "jit(prefill_step)/state.write/"
     "scatter", "state"),
    ("%fusion.400 = bf16[1,512,21504]", LOOP + "mlp/bsh,hf->bsf/dot_general",
     "mlp"),
    # a product whose scope the compiler dropped: told by its weight
    ("%fusion.401 = bf16[1,512,5120]{2,1,0} fusion(bf16[6,21504,5120]{2,1,0}"
     " %w, s32[] %i, bf16[1,512,21504]{2,1,0} %a)", "", "mlp"),
    ("%fusion.307 = bf16[1,512,9248]{2,1,0} fusion(bf16[6,5120,9248]{2,1,0} "
     "%w, s32[] %i, bf16[1,512,5120]{2,1,0} %x)", "", "ssm.proj"),
    ("%fusion.311 = bf16[1,512,5120]{2,1,0} fusion(bf16[6,4096,5120]{2,1,0} "
     "%w, s32[] %i, bf16[1,512,4096]{2,1,0} %y)", "", "ssm.proj"),
    ("%fusion.38 = bf16[1,512,5120]{2,1,0} fusion(bf16[6,2560,5120]{2,1,0} "
     "%wo, s32[] %i, bf16[1,512,2560]{2,1,0} %a)", "", None),
    ("%fusion.29 = bf16[1,512,2560]{2,1,0} fusion(bf16[6,5120,2560]{2,1,0} "
     "%wq, s32[] %i, bf16[1,512,5120]{2,1,0} %x)", LOOP + "attn.qkv/"
     "dot_general", None),
    ("%attn.core.10 = bf16[1,512,2560] custom-call", LOOP + "attn.core",
     None),
    ("%while.4 = (s32[], bf16[1,512,5120])", "jit(prefill_step)/while", None),
]


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def falcon():
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-h6.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,scope,group", PLACED,
                         ids=[n.split(" = ")[0] + "-" + str(i)
                              for i, (n, _, _) in enumerate(PLACED)])
def test_an_op_is_placed_by_its_scope_or_its_weight(name, scope, group):
    assert halves_trace.placer(falcon()["model"])(name, scope) == group


@pytest.fixture
def window(tmp_path, monkeypatch):
    """Two prefill steps: a 512-token one whose program runs 30 ms (the
    state-space half's products 2 + 1, its convolution, scan and norm 0.5 +
    2 + 0.5, the slots 0.2 + 0.3, the feed-forward 15 + 5, attention 1) and
    a shared step of 3 rows (96 tokens, 10 ms: the scan 1, the feed-forward
    6)."""
    names = [n for n, _, _ in PLACED]
    scopes = [s for _, s, _ in PLACED]
    t0, t1, us = 100 * MS, 200 * MS, 1000
    ops = [[15, t0, 30 * MS],  # the loop itself: its self time is nobody's
           [0, t0 + 1 * MS, 2 * MS], [1, t0 + 3 * MS, 1 * MS],
           [2, t0 + 4 * MS, 500 * us], [3, t0 + 5 * MS, 2 * MS],
           [4, t0 + 7 * MS, 500 * us], [6, t0 + 8 * MS, 200 * us],
           [8, t0 + 9 * MS, 15 * MS], [9, t0 + 24 * MS, 5 * MS],
           [14, t0 + 29 * MS, 1 * MS], [7, t0 + 30 * MS, 300 * us],
           [3, t1 + 1 * MS, 1 * MS], [8, t1 + 2 * MS, 6 * MS]]
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
        {"kind": "prefill_chunk", "t_ns": t0 - 3 * MS, "dur_ns": 36 * MS,
         "batch": 1, "tokens": 512, "bucket": 512, "ctx": 6144},
        {"kind": "prefill_chunk", "t_ns": t1 - 3 * MS, "dur_ns": 15 * MS,
         "batch": 3, "tokens": 96, "bucket": 64, "ctx": 6000}]
    run = {"t0": 0.0, "t1": 1.0, "events": steps, "records": [],
           "config": falcon(), "peaks": peaks.peaks_for("TPU v5 lite"),
           "metrics0": {}, "metrics1": {},
           "trace": {"modules": [[(t0, t0 + 30.4 * MS, "jit_prefill_step"),
                                  (t1, t1 + 10 * MS, "jit_prefill_step")]]}}
    return run, write, scopes, names


def test_the_readers_reduce_a_traced_window(window):
    run, _, _, _ = window
    half = (2 + 1) + (0.5 + 2 + 0.5) + (0.2 + 0.3) + 1
    assert reader("step.ssm_half_device_pct")(run) == pytest.approx(
        100 * half / 40.4)
    assert reader("step.feed_forward_device_pct")(run) == pytest.approx(
        100 * (15 + 5 + 6) / 40.4)
    fam, model = roofline.family(run["config"]), run["config"]["model"]
    floor = sum(fam.ssm_scan_floor_s(model, run["peaks"], n, rows)[0]
                for n, rows in ((512, 1), (96, 3)))
    got = reader("kernel.ssm_half_scan_roofline")(run)
    assert got == pytest.approx(100 * floor / (4 / 1e3))
    assert 0 < got <= 100


def test_a_program_without_the_scopes_and_another_family_read_none(window):
    """The parent's side of a traced run (no `ssm.*` scope or kernel name:
    the products told by their weights alone place no scan), a run without a
    trace, and a nemotron_h configuration, whose own readers
    (`lib/ssm_trace.py`) read it."""
    run, write, scopes, names = window
    write([""] * len(scopes), [n.replace("%ssm.scan", "%fusion")
                               for n in names])
    for name in NEW:
        assert reader(name)(run) is None, name
    write(scopes)
    for name in NEW:
        assert reader(name)(dict(run, trace=None)) is None, name
    with open(os.path.join(BENCH, "configs",
                           "nemotron3-nano-30b-ep8.json")) as f:
        other = dict(run, config=json.load(f))
    for name in NEW:
        assert reader(name)(other) is None, name


def test_the_roofline_file_counts_what_every_step_must():
    cfg, p = falcon(), peaks.peaks_for("TPU v5 lite")
    fam, model = roofline.family(cfg), cfg["model"]
    layer = (5120 * 9248 + 4096 * 5120 + 2 * 5120 * 2560 + 2 * 5120 * 512
             + 3 * 5120 * 21504)
    assert fam.layer_weight_params(model) == layer == 430_080_000
    secs, which = fam.prefill_step_floor_s(model, p, 512)
    assert (which, secs) == ("compute", pytest.approx(
        2 * 512 * 6 * layer / p["bf16_flops_per_s"]))
    secs, which = fam.prefill_step_floor_s(model, p, 16)
    assert (which, secs) == ("memory", pytest.approx(
        2 * 6 * layer / p["hbm_bytes_per_s"]))
    per_token = 2 * (5120 + 32 + 4096 + 4096)  # values in bf16
    state = 2 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    secs, which = fam.ssm_scan_floor_s(model, p, 512, 1)
    assert (which, secs) == ("memory", pytest.approx(
        6 * (512 * per_token + state) / p["hbm_bytes_per_s"]))
    four = fam.ssm_scan_floor_s(model, p, 256, 4)[0]
    assert four == pytest.approx(
        6 * (256 * per_token + 4 * state) / p["hbm_bytes_per_s"])
    pairs = 512 * 5632 + 512 * 513 // 2
    secs, which = fam.prefill_attn_floor_s(model, p, 512, 6144)
    assert (which, secs) == ("compute", pytest.approx(
        6 * 4 * 128 * 20 * pairs / p["bf16_flops_per_s"]))


def test_the_spec_lists_the_new_readers_for_the_new_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name, (layer, better) in NEW.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"],
                m["workloads"]) == (layer, "device_trace", better,
                                    "ttft_p95_ms", "%", [CELL])
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py"))
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL, name
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-h6", "longdoc-1tok", 1)
    assert spec["workloads"][-1] == cell and len(cell["why"]) <= 200
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert spec["configs"][-1] == entry
