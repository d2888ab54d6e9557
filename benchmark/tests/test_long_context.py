"""A context past 4,096 tokens as a size (ISSUE 46): a cell's own
`worker_flags` laid over its configuration's, the mix held against the
context before any child starts, `probe_lens` and its default, the plain
reference's attention in blocks of queries with its `ignore_window` control,
the span a trace really holds, scopes read from a profile's bytes, and the
three readers a long context moves, on small hand-made runs."""

import json
import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import checkpoint, opwalk, probes, traffic  # noqa: E402
from lib import trace as trace_lib  # noqa: E402
from lib.procs import RunFailure  # noqa: E402

MS = 1_000_000
NEW = "smallthinker-21b.longdoc-1tok"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def cell_config_mix(name):
    (cell,) = [w for w in SPEC["workloads"] if w["name"] == name]
    (entry,) = [c for c in SPEC["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        cell["traffic"] + ".json"))
    return cell, config, mix


# -- the cell's sizes ----------------------------------------------------------- #

def parents_flags(config):
    """`worker_flags` as the parent of ISSUE 46 built them."""
    flags = []
    for flag, value in config.get("worker_flags", {}).items():
        flags += [flag, str(value)]
    return flags


@pytest.mark.parametrize("name", [c for c in CELLS if c != NEW])
def test_a_cell_without_sizes_starts_its_worker_as_before(name):
    cell, config, _ = cell_config_mix(name)
    sizes = bench_run.cell_sizes(cell, False)
    assert sizes == {}
    assert bench_run.worker_flags(cell, config, sizes, False) == (
        parents_flags(config))


def test_the_new_cell_lays_its_context_over_the_configurations_pool():
    cell, config, mix = cell_config_mix(NEW)
    sizes = bench_run.cell_sizes(cell, False)
    assert bench_run.worker_flags(cell, config, sizes, False) == [
        "--num-pages", "5888", "--max-model-len", "8192"]
    assert bench_run.check_context(cell, config, sizes, mix) == 8192
    # the configuration's other cell is served at the flags it had
    short, _, _ = cell_config_mix("smallthinker-21b.docqa-1tok")
    assert bench_run.worker_flags(short, config, {}, False) == [
        "--num-pages", "5888"]


def test_a_cells_flag_takes_the_place_of_the_configurations():
    cell, config = {"name": "c.m"}, {"name": "c", "worker_flags": {
        "--num-pages": 5888, "--tp": 1}}
    sizes = {"worker_flags": {"--num-pages": 4096, "--max-model-len": 8192}}
    assert bench_run.worker_flags(cell, config, sizes, True) == [
        "--num-pages", "4096", "--tp", "1", "--max-model-len", "8192",
        "--platform", "cpu", "--dtype", "float32"]


@pytest.mark.parametrize("where", ["cell", "configuration"])
def test_a_policy_flag_is_refused_by_owner_and_key(where):
    cell, config, sizes = {"name": "c.m"}, {"name": "c"}, {}
    flags = {"--max-prefill-tokens": 1024}
    if where == "cell":
        sizes = {"worker_flags": flags}
    else:
        config["worker_flags"] = flags
    with pytest.raises(RunFailure) as e:
        bench_run.worker_flags(cell, config, sizes, False)
    assert "--max-prefill-tokens" in str(e.value)
    assert ("cell c.m" if where == "cell" else "configuration c") in str(
        e.value)


def test_the_context_is_a_size_and_its_default_is_the_workers():
    assert "--max-model-len" not in bench_run.POLICY_FLAGS
    with open(os.path.join(ROOT, "dynamo_tpu", "worker", "__main__.py")) as f:
        (default,) = re.findall(
            r'"--max-model-len", type=int, default=(\d+)', f.read())
    assert int(default) == bench_run.DEFAULT_MAX_MODEL_LEN


MIX = {"prefix_len": {"dist": "uniform", "min": 100, "max": 5000},
       "fresh_len": {"dist": "lognormal", "median": 30, "sigma": 1,
                     "min": 4, "max": 90},
       "output_len": {"dist": "fixed", "value": 10}}


@pytest.mark.parametrize("sizes,limit,mix,says", [
    ({}, 16384, MIX, ["cell c.m", "5100", "prefix_len + fresh_len",
                      "default --max-model-len 4096"]),
    ({"worker_flags": {"--max-model-len": 5099}}, 16384, MIX,
     ["cell c.m", "5100", "worker_flags --max-model-len 5099"]),
    ({"worker_flags": {"--max-model-len": 32768}}, 16384, MIX,
     ["cell c.m", "max_position_embeddings 16384", "--max-model-len 32768"]),
    ({"worker_flags": {"--max-model-len": 6000}}, 16384,
     dict(MIX, probe_lens=[48, 6400]), ["cell c.m", "6408", "probe_lens"]),
], ids=["mix-past-default", "mix-past-cell", "context-past-model",
        "probe-past-context"])
def test_a_cell_that_cannot_hold_its_mix_fails_before_any_child(
        sizes, limit, mix, says):
    cell = {"name": "c.m", "traffic": "m"}
    config = {"name": "c", "model": {"max_position_embeddings": limit}}
    with pytest.raises(RunFailure) as e:
        bench_run.check_context(cell, config, sizes, mix)
    for text in says:
        assert text in str(e.value)


def test_a_cell_that_holds_its_mix_passes():
    cell = {"name": "c.m", "traffic": "m"}
    config = {"name": "c", "model": {"max_position_embeddings": 16384}}
    sizes = {"worker_flags": {"--max-model-len": 5100}}
    assert bench_run.check_context(cell, config, sizes, MIX) == 5100
    assert traffic.longest_request(MIX) == 5100
    assert traffic.max_len(None) == 0


# -- probes --------------------------------------------------------------------- #

def test_probe_lens_default_and_a_mix_that_appends_one():
    assert probes.lens_of({}) == probes.PROBE_LENS == (48, 48, 48, 48, 1200)
    longer = probes.lens_of({"probe_lens": [48, 48, 48, 48, 1200, 6400]})
    old = probes.probe_texts(31, (4, 260))
    new = probes.probe_texts(31, (4, 260), longer)
    assert new[:5] == old and len(new[5]) == 6400 + probes.PROBE_STEPS - 1
    assert probes.longest_request(longer) == 6408
    with pytest.raises(ValueError):
        probes.lens_of({"probe_lens": []})
    _, _, mix = cell_config_mix(NEW)
    assert probes.lens_of(mix) == longer
    _, _, short = cell_config_mix("smallthinker-21b.docqa-1tok")
    assert probes.lens_of(short) == probes.PROBE_LENS


def test_compare_forced_says_which_probe_is_over_and_by_how_much():
    ref = [[{"logprob": -1.0, "gap": 1.0}] * 2, [{"logprob": -2.0,
                                                  "gap": 0.01}] * 2]
    ok, d = probes.compare_forced([[-1.01, -1.02], [-2.0, -2.035]], ref,
                                  0.03, 0.03)
    assert ok and d["steps_over_by_probe"] == [0, 0]
    assert d["max_past_allowed"] == pytest.approx(-0.005)  # tol + the gap
    ok, d = probes.compare_forced([[-1.01, -1.02], [-2.0, -2.1]], ref,
                                  0.03, 0.03)
    assert not ok and d["steps_over_by_probe"] == [0, 1]
    assert d["max_past_allowed"] == pytest.approx(0.06)
    numbers = bench_run.compared_numbers(
        {"forced": d, "tolerance": 0.03}, 4)
    assert numbers["logprob_past_allowed_max"]["value"] > 0
    assert numbers["forced_steps_over"] == {"value": 1, "limit": 0}
    assert numbers["forced_steps_compared"] == {"value": 4, "at_least": 4}


# -- the plain reference at a long context -------------------------------------- #

TINY = {"head_dim": 16, "hidden_size": 64, "moe_ffn_hidden_size": 32,
        "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
        "rope_layout": [0, 1, 1, 1], "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1],
        "sliding_window_size": 24, "tie_word_embeddings": False,
        "vocab_size": 300}


@pytest.fixture(scope="module")
def tiny_reference():
    ref = checkpoint.load_module("reference", "smallthinker")
    rng = np.random.default_rng(46)
    layout = checkpoint.load_module("checkpoints", "smallthinker")
    weights = {name: (np.ones(shape, np.float32) if kind == "ones" else
                      rng.standard_normal(shape).astype(np.float32)
                      * shape[-1] ** -0.5)
               for name, shape, kind in layout.tensors(TINY)}
    texts = [rng.integers(4, 260, size=(1, n)) for n in (16, 70)]
    return ref, weights.__getitem__, texts


def test_attention_in_blocks_is_attention(tiny_reference, monkeypatch):
    ref, read, texts = tiny_reference
    whole = ref.tail_logprobs(read, TINY, texts, 8)
    monkeypatch.setattr(ref, "ATTN_QUERY_BLOCK", 7)  # 70 tokens: 10 blocks
    blocks = ref.tail_logprobs(read, TINY, texts, 8)
    for a, b in zip(whole, blocks):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_window_control_fails_the_probe_past_the_window_alone(
        tiny_reference):
    """The control kept as a test: the plain reference's own answers stand
    where the served path's would; against a reference whose windowed layers
    see every key, the 70-token probe (window 24) is over its limit and the
    16-token one agrees to the bit."""
    ref, read, texts = tiny_reference

    def top1(lp):
        order = np.sort(lp, axis=-1)
        return [{"logprob": float(order[k, -1]),
                 "gap": float(order[k, -1] - order[k, -2])}
                for k in range(lp.shape[0])]

    plain = [top1(lp[0]) for lp in ref.tail_logprobs(read, TINY, texts, 8)]
    fault = [top1(lp[0]) for lp in ref.forward(read, TINY, texts, 8,
                                               ignore_window=True)]
    served = [[s["logprob"] for s in steps] for steps in plain]
    ok, d = probes.compare_forced(served, plain, ref.LOGPROB_TOL,
                                  ref.TIE_MARGIN)
    assert ok and d["max_abs_logprob_diff"] == 0.0
    ok, d = probes.compare_forced(served, fault, 1e-4, 0.0)
    assert not ok
    assert d["steps_over_by_probe"][0] == 0 < d["steps_over_by_probe"][1]
    assert fault[0] == plain[0]


# -- the roofline's count of attention over the context ------------------------- #

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def seen_pairs(tokens, ctx, window):
    """(pairs, keys any token sees) by counting, one token at a time."""
    first, pairs = ctx, 0
    for p in range(ctx - tokens + 1, ctx + 1):  # the token at position p
        n = min(p, window) if window else p
        pairs += n
        first = min(first, p - n)
    return pairs, ctx - first


@pytest.mark.parametrize("tokens,ctx", [(512, 512), (512, 4096), (512, 4300),
                                        (512, 7168), (32, 6000), (64, 64),
                                        (512, 4607)])
def test_attn_floor_counts_the_keys_a_token_can_see(tokens, ctx):
    st = checkpoint.load_module("roofline", "smallthinker")
    model = {"num_attention_heads": 28, "num_key_value_heads": 4,
             "head_dim": 128, "sliding_window_size": 4096,
             "sliding_window_layout": [0, 1, 1, 1]}
    want = 0.0
    for windowed in model["sliding_window_layout"]:
        pairs, keys = seen_pairs(tokens, ctx, 4096 if windowed else None)
        want += max(4 * 128 * 28 * pairs / 197e12,
                    2 * keys * 4 * 128 * 2 / 819e9)
    got, bound = st.prefill_attn_floor_s(model, PEAKS, tokens, ctx)
    assert got == pytest.approx(want, rel=1e-12)
    assert bound == ("compute" if tokens >= 64 and ctx > 64 else "memory")
    dense = checkpoint.load_module("roofline", "llama_like")
    full = dict(model, num_hidden_layers=4, hidden_size=3584)
    pairs, keys = seen_pairs(tokens, ctx, None)
    assert dense.prefill_attn_floor_s(full, PEAKS, tokens, ctx)[0] == (
        pytest.approx(4 * max(4 * 128 * 28 * pairs / 197e12,
                              2 * keys * 4 * 128 * 2 / 819e9), rel=1e-12))
    if ctx > 4096 + tokens:  # the window clips: a windowed layer costs less
        assert got < dense.prefill_attn_floor_s(full, PEAKS, tokens, ctx)[0]


# -- the span a trace holds ----------------------------------------------------- #

def step(t_ms, dur_ms, **attrs):
    return {"kind": "prefill_chunk", "t_ns": int(t_ms * MS),
            "dur_ns": int(dur_ms * MS), "ring": "engine", "batch": 1,
            **attrs}


def plane_of(ops, modules):
    return {"clock": "mono_ns", "names": ["%fusion.1 = bf16[8]{0} fusion()",
                                          "jit_prefill_step(1)"],
            "planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": modules},
                {"name": "XLA Ops", "events": ops}]}]}


def test_busy_and_idle_are_taken_over_the_span_the_plane_holds():
    """The profiler stopped 23 ms into a 40 ms window while the host went
    on running steps: busy, the gaps and `window_s` are the captured span's;
    with no step after the plane's end the device did fall idle and the
    window stays whole."""
    ops = [[0, t * MS, 9 * MS] for t in (1, 11, 14)]  # the last ends at 23
    # the line of program executions goes on: it is the ops line, some
    # thousands of events a step, that the profiler's limit cuts
    mods = [[1, s, d] for _, s, d in ops] + [[1, 26 * MS, 9 * MS],
                                              [1, 36 * MS, 3 * MS]]
    steps = [step(0, 10), step(10, 4), step(13, 11), step(25, 10),
             step(35, 4)]
    cut = trace_lib.reduce(plane_of(ops, mods), 0, 40 * MS, steps)
    assert cut["capture_ended_early"] is True
    assert cut["window_s"] == pytest.approx(0.023)
    assert cut["busy_s"] == pytest.approx(0.021)  # 1-10 and 11-23
    assert sum(s for _, s in cut["idle_gaps"]) == pytest.approx(0.002)
    idle = trace_lib.reduce(plane_of(ops, mods[:3]), 0, 40 * MS, steps[:3])
    assert idle["capture_ended_early"] is False
    assert idle["window_s"] == pytest.approx(0.040)
    assert idle["busy_s"] == pytest.approx(0.021)
    assert sum(s for _, s in idle["idle_gaps"]) == pytest.approx(0.019)


def test_a_plane_that_reaches_the_windows_end_reads_as_before():
    with open(os.path.join(BENCH, "tests", "data",
                           "recorded_trace.json")) as f:
        rec = json.load(f)
    t0, t1 = rec["window_ns"]
    out = trace_lib.reduce(rec, t0, t1, rec["step_events"])
    assert out["busy_s"] == pytest.approx(rec["expected"]["busy_s"])
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert out["capture_ended_early"] is False


def test_scopes_are_read_from_the_profiles_own_bytes(tmp_path):
    space = trace_lib._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "not a device op"
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[7].name = "tf_op"
    dev.stat_metadata[8].name = "flops"
    dev.stat_metadata[9].name = "jit(prefill_step)/while/body/attn.core/dot"
    a = dev.event_metadata[1]
    a.name, a.display_name = "%fusion.3 = f32[4]{0} fusion()", "fusion.3"
    a.stats.add(metadata_id=8, ref_value=5)
    a.stats.add(metadata_id=7, str_value="jit(prefill_step)/mlp/dot_general")
    b = dev.event_metadata[2]
    b.name = "%attn.core.8 = bf16[1,512,3584]{2,1,0} custom-call()"
    b.stats.add(metadata_id=7, ref_value=9)
    dev.event_metadata[3].name = "%copy.1 = f32[2]{0} copy()"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert trace_lib.op_scopes(str(path)) == {
        a.name: "jit(prefill_step)/mlp/dot_general",
        "fusion.3": "jit(prefill_step)/mlp/dot_general",
        b.name: "jit(prefill_step)/while/body/attn.core/dot"}


# -- the three readers ---------------------------------------------------------- #

NAMES = ["jit_prefill_step(1)",
         "%while.2 = (s32[]) while()",
         "%attn.core.8 = bf16[1,512,3584]{2,1,0} custom-call()",
         "%fusion.9 = bf16[512,2560]{1,0} fusion()",
         "%fusion.11 = f32[4,4,7,64,8192]{4,3,2,1,0} fusion()",
         "%fusion.12 = bf16[8192,12,4,4,128]{4,3,2,1,0} fusion()"]
SCOPES = ["", "", "jit(prefill_step)/while/body/closed_call/attn.core/"
          "pallas_call", "jit(prefill_step)/while/body/closed_call/mlp/dot",
          "jit(prefill_step)/while/body/closed_call/attn.core/dot_general",
          "jit(prefill_step)/while/body/closed_call/kv.gather/gather"]


def traced_run(tmp_path, monkeypatch, scopes=True):
    """Three steps in a 100 ms window: a 512-token chunk at ctx 6144 (20 ms:
    a layer loop of 18 holding the kernel 6 and the MLP 10), a shared short
    step of three rows at ctx 7000 (10 ms: XLA attention 3 + gather 1, MLP
    5), a 512-token chunk at ctx 512 (8 ms: kernel 1, MLP 7)."""
    ev = lambda i, t, d: [i, int(t * MS), int(d * MS)]  # noqa: E731
    ops = [ev(1, 1, 18), ev(2, 1.5, 6), ev(3, 8, 10),
           ev(4, 31, 3), ev(5, 34, 1), ev(3, 35, 5),
           ev(2, 51, 1), ev(3, 52, 7)]
    mods = [ev(0, 0.5, 20), ev(0, 30.5, 10), ev(0, 50.5, 8)]
    compact = {"clock": "mono_ns", "names": NAMES,
               "planes": [{"name": "/device:TPU:0", "lines": [
                   {"name": "XLA Modules", "events": mods},
                   {"name": "XLA Ops", "events": ops}]}]}
    if scopes:
        compact["scopes"] = SCOPES
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(compact))
    monkeypatch.setattr(opwalk.moe_trace, "trace_path", lambda: str(path))
    events = [step(0, 25, tokens=512, ctx=6144),
              step(30, 15, tokens=96, ctx=7000, batch=3),
              step(50, 12, tokens=512, ctx=512),
              step(200, 12, tokens=512, ctx=9000)]  # outside the window
    _, config, mix = cell_config_mix(NEW)
    return {"t0": 0.0, "t1": 0.1, "events": events, "records": [],
            "config": config, "mix": mix, "peaks": PEAKS,
            "trace": trace_lib.reduce(compact, 0, 100 * MS, events)}


def test_the_readers_of_a_long_context_on_a_traced_window(tmp_path,
                                                           monkeypatch):
    run = traced_run(tmp_path, monkeypatch)
    read = {n: bench_run.load_reader("layer_metrics", n) for n in (
        "step.attn_device_pct", "kernel.prefill_attn_roofline",
        "engine.ctx_tokens_p50")}
    # attention 6 + (3 + 1) + 1 of the programs' 20 + 10 + 8 ms
    assert read["step.attn_device_pct"](run) == pytest.approx(
        100 * 11 / 38)
    assert read["engine.ctx_tokens_p50"](run) == 6144.0
    # the floor of the two one-sequence steps over their 7 ms; the shared
    # step says its longest row's context alone and stays out
    st = checkpoint.load_module("roofline", "smallthinker")
    model = run["config"]["model"]
    floor = sum(st.prefill_attn_floor_s(model, PEAKS, 512, c)[0]
                for c in (6144, 512))
    assert read["kernel.prefill_attn_roofline"](run) == pytest.approx(
        100 * floor / 0.007)
    assert read["kernel.prefill_attn_roofline"](run) < 100


def test_without_scopes_the_kernel_is_still_placed_by_its_name(
        tmp_path, monkeypatch):
    run = traced_run(tmp_path, monkeypatch, scopes=False)
    read = bench_run.load_reader("layer_metrics", "step.attn_device_pct")
    assert read(run) == pytest.approx(100 * 7 / 38)


def test_the_readers_find_nothing_without_a_trace():
    run = {"t0": 0.0, "t1": 0.1, "events": [step(0, 25, tokens=512)],
           "records": [], "trace": None, "config": {"checkpoint": "llama_like"}}
    for name in ("step.attn_device_pct", "kernel.prefill_attn_roofline",
                 "engine.ctx_tokens_p50"):
        assert bench_run.load_reader("layer_metrics", name)(run) is None


def test_the_readers_on_a_recorded_slice_of_the_long_document_cell(
        monkeypatch):
    """0.28 s of the cell's own traced window (tests/data/
    recorded_longdoc_slice.json: five 512-token chunks at contexts of
    4,608-6,656 tokens, a step two questions share, the next document's
    first chunk), with the scopes `lib/trace.py` read from the profile: the
    readings are the ones taken when the slice was cut, the kernel is found
    without scopes too, and the count stays under the measured time."""
    path = os.path.join(BENCH, "tests", "data", "recorded_longdoc_slice.json")
    with open(path) as f:
        rec = json.load(f)
    monkeypatch.setattr(opwalk.moe_trace, "trace_path", lambda: path)
    _, config, mix = cell_config_mix(NEW)
    t0, t1 = rec["window_ns"]
    run = {"t0": t0 / 1e9, "t1": t1 / 1e9, "events": rec["step_events"],
           "records": [], "config": config, "mix": mix, "peaks": PEAKS,
           "trace": trace_lib.reduce(rec, t0, t1, rec["step_events"])}
    assert run["trace"]["busy_s"] == pytest.approx(rec["expected"]["busy_s"])
    assert run["trace"]["capture_ended_early"] is False
    for name in ("step.attn_device_pct", "kernel.prefill_attn_roofline",
                 "engine.ctx_tokens_p50"):
        got = bench_run.load_reader("layer_metrics", name)(run)
        assert got == pytest.approx(rec["expected"][name]), name
    steps = opwalk.attention_seconds(run)
    assert [(e["batch"], e["tokens"], e["ctx"]) for e, _, _ in steps] == [
        (1, 512, 4608), (1, 512, 5120), (1, 512, 5632), (1, 512, 6144),
        (1, 512, 6656), (2, 68, 6693), (1, 512, 512)]
    # attention grows with the context and is the smaller part of a step
    attn = [a for _, _, a in steps]
    assert attn[:5] == sorted(attn[:5]) and attn[6] < attn[0] / 4
    assert all(0 < a < prog / 2 for _, prog, a in steps)
    st = checkpoint.load_module("roofline", "smallthinker")
    for e, _, a in steps:
        if e["batch"] == 1:
            floor = st.prefill_attn_floor_s(config["model"], PEAKS,
                                            e["tokens"], e["ctx"])[0]
            assert 0.05 * a < floor < a
