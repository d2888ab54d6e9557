"""The readers PR 55 adds for the cell `lfm2-24b-a2b.longdoc-1tok`, on a
hand-made traced window, and the family's roofline file against hand counts.
A program without the `sconv.*` scopes (the parent's, and a state-space
family's, whose `state.*` scopes then count for nothing) reads None."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import moe_trace, opwalk, peaks, roofline, sconv_trace  # noqa: E402

MS = 1_000_000
CELL = "lfm2-24b-a2b.longdoc-1tok"
NEW = {"step.short_conv_device_pct": ("model step", "lower"),
       "kernel.short_conv_roofline": ("kernels", "higher")}
LOOP = "jit(prefill_step)/while/body/closed_call/while/body/closed_call/"
# (kernel name, path of scopes, group)
PLACED = [
    ("%fusion.450 = bf16[1,512,6144]", LOOP + "sconv.in_proj/bsh,hd->bsd/"
     "dot_general", "sconv"),
    ("%slice_multiply_fusion.3 = bf16[1,512,2048]", LOOP + "sconv.conv/mul",
     "sconv"),
    ("%fusion.449 = bf16[1,512,2048]", LOOP + "sconv.out_proj/bsd,dh->bsh/"
     "dot_general", "sconv"),
    ("%gather.7 = bf16[1,32,128]", LOOP + "state.read/gather", "state"),
    ("%fusion.233 = bf16[7,2049,32,128]", "jit(prefill_step)/state.write/"
     "scatter", "state"),
    ("%attn.core.10 = bf16[1,512,2048] custom-call", LOOP + "attn.core",
     None),
    ("%fusion.12 = bf16[1,512,8,64]", LOOP + "attn.qkv/attn.qk_norm/mul",
     None),
    ("%moe.experts.21 = f32[2048,2048] custom-call", "", None),
    ("%while.4 = (s32[], bf16[1,512,2048])", "jit(prefill_step)/while", None),
]


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def lfm2():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-h9.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,scope,group", PLACED)
def test_an_op_is_placed_by_its_scope(name, scope, group):
    assert sconv_trace.place(name, scope) == group


@pytest.fixture
def window(tmp_path, monkeypatch):
    """Two prefill steps: a 512-token one whose program runs 24 ms (the conv
    mixers 0.5 + 0.3 + 0.2, the windows 0.1 + 0.1, attention 1, experts 18)
    and a shared step of 3 rows (96 tokens, 10 ms, the mixers 0.4 of
    them)."""
    names = [n for n, _, _ in PLACED]
    scopes = [s for _, s, _ in PLACED]
    t0, t1, us = 100 * MS, 200 * MS, 1000
    ops = [[8, t0, 24 * MS],  # the loop itself: its self time is nobody's
           [0, t0 + 1 * MS, 500 * us], [1, t0 + 2 * MS, 300 * us],
           [2, t0 + 3 * MS, 200 * us], [3, t0 + 4 * MS, 100 * us],
           [5, t0 + 5 * MS, 1 * MS], [7, t0 + 6 * MS, 18 * MS],
           [4, t0 + 24 * MS, 100 * us],
           [0, t1 + 1 * MS, 400 * us], [7, t1 + 2 * MS, 8 * MS]]
    path = tmp_path / "trace.json"

    def write(scopes_):
        opwalk._MEMO.clear()  # noqa: SLF001
        opwalk._COMPACT.clear()  # noqa: SLF001
        path.write_text(json.dumps({
            "names": names, "scopes": scopes_, "planes": [{
                "name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": ops}]}]}))

    write(scopes)
    monkeypatch.setattr(moe_trace, "trace_path", lambda: str(path))
    steps = [
        {"kind": "prefill_chunk", "t_ns": t0 - 3 * MS, "dur_ns": 30 * MS,
         "batch": 1, "tokens": 512, "bucket": 512, "ctx": 6144},
        {"kind": "prefill_chunk", "t_ns": t1 - 3 * MS, "dur_ns": 15 * MS,
         "batch": 3, "tokens": 96, "bucket": 64, "ctx": 6000}]
    run = {"t0": 0.0, "t1": 1.0, "events": steps, "records": [],
           "config": lfm2(), "peaks": peaks.peaks_for("TPU v5 lite"),
           "metrics0": {}, "metrics1": {},
           "trace": {"modules": [[(t0, t0 + 24.1 * MS, "jit_prefill_step"),
                                  (t1, t1 + 10 * MS, "jit_prefill_step")]]}}
    return run, write, scopes


def test_the_readers_reduce_a_traced_window(window):
    run, _, _ = window
    ms = (0.5 + 0.3 + 0.2 + 0.1 + 0.1) + 0.4
    assert reader("step.short_conv_device_pct")(run) == pytest.approx(
        100 * ms / 34.1)
    fam, model = roofline.family(run["config"]), run["config"]["model"]
    floor = sum(fam.short_conv_floor_s(model, run["peaks"], n)[0]
                for n in (512, 96))
    got = reader("kernel.short_conv_roofline")(run)
    assert got == pytest.approx(100 * floor / (ms / 1e3))
    assert 0 < got <= 100


def test_a_program_without_the_scopes_reads_none(window):
    """The parent's side of a traced run, a state-space family's (its
    `state.*` scopes alone place nothing), and a run without a trace."""
    run, write, scopes = window
    for kept in ([""] * len(scopes),
                 [s if "state." in s else "" for s in scopes]):
        write(kept)
        for name in NEW:
            assert reader(name)(run) is None, name
    for name in NEW:
        assert reader(name)(dict(run, trace=None)) is None, name


def test_the_touched_share_counts_the_layers_after_the_dense_ones(window):
    """8 expert layers of 64: a step that touches 500 of their 512 experts
    and one that touches 140; steps without `moe_form`, outside the window
    or of a model without `num_dense_layers` count for nothing."""
    run, _, _ = window
    read = reader("engine.experts_touched_pct")
    assert read(run) is None  # no step carries the counters
    a, b = run["events"]
    events = [dict(a, moe_form="dispatched", experts_hit=500),
              dict(b, moe_form="dispatched", experts_hit=140),
              dict(b, experts_hit=512),
              dict(b, t_ns=2_000_000_000, moe_form="dispatched",
                   experts_hit=512)]
    assert read(dict(run, events=events)) == pytest.approx(
        100 * (500 + 140) / 2 / 512)
    model = {k: v for k, v in run["config"]["model"].items()
             if k != "num_dense_layers"}
    assert read(dict(run, events=events,
                     config=dict(run["config"], model=model))) is None


def test_the_roofline_file_counts_what_every_step_must():
    cfg, p = lfm2(), peaks.peaks_for("TPU v5 lite")
    fam, model = roofline.family(cfg), cfg["model"]
    conv, expert = 4 * 2048 * 2048, 3 * 2048 * 1536
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    n = 7 * conv + 2 * attn + 3 * 2048 * 11776 + 8 * (2048 * 64 + 4 * expert)
    assert fam.every_step_params(model) == n == 513_802_240
    secs, which = fam.prefill_step_floor_s(model, p, 512)
    assert which == "compute"
    assert secs == pytest.approx(2 * 512 * n / p["bf16_flops_per_s"])
    secs, which = fam.short_conv_floor_s(model, p, 512)
    assert (which, secs) == ("compute", pytest.approx(
        7 * 2 * 512 * conv / p["bf16_flops_per_s"]))
    secs, which = fam.short_conv_floor_s(model, p, 16)
    assert (which, secs) == ("memory", pytest.approx(
        7 * 2 * conv / p["hbm_bytes_per_s"]))
    secs, which = fam.routed_experts_floor_s(model, p, 8 * 2048, 8 * 40)
    assert (which, secs) == ("memory", pytest.approx(
        2 * 8 * 40 * expert / p["hbm_bytes_per_s"]))
    pairs = 512 * 5632 + 512 * 513 // 2
    secs, which = fam.prefill_attn_floor_s(model, p, 512, 6144)
    assert (which, secs) == ("compute", pytest.approx(
        2 * 4 * 64 * 32 * pairs / p["bf16_flops_per_s"]))


def test_the_spec_lists_the_new_readers_for_the_new_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name, (layer, better) in NEW.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"], m["moves"],
                m["unit"]) == (layer, "device_trace", better, "ttft_p95_ms",
                               "%")
        assert CELL in m["workloads"]
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py"))
    assert CELL in by_name["engine.state_hit_depth_pct"]["workloads"]
    m = by_name["engine.experts_touched_pct"]
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"],
            m["workloads"]) == ("engine", "program_counter", "higher",
                                "ttft_p95_ms", "%", [CELL])
