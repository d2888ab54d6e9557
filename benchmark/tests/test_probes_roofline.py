import json
import os

import pytest

from lib import peaks, probes, roofline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def ref(steps):
    return [[{"logprob": lp, "gap": gap} for lp, gap in s] for s in steps]


def test_greedy_probes_agree_within_tolerance():
    ok, d = probes.compare_greedy(
        [[-1.0, -2.0], [-3.0, -1.5]],
        ref([[(-1.02, 0.5), (-1.95, 0.4)], [(-3.0, 0.9), (-1.52, 0.3)]]),
        0.06, 0.06)
    assert ok and d["steps_compared"] == 4
    assert d["max_abs_logprob_diff"] == pytest.approx(0.05)


def test_a_greedy_probe_off_by_more_than_the_tolerance_fails():
    ok, d = probes.compare_greedy(
        [[-1.0, -2.0]], ref([[(-1.0, 0.5), (-2.3, 0.5)]]), 0.06, 0.06)
    assert not ok and "step 1" in d["problems"][0]


def test_a_tie_ends_a_greedy_probe_and_widens_that_step_by_the_gap_only():
    # step 0 is a near tie (gap 0.05): served may have taken the other token,
    # so up to tol + gap is allowed there and later steps are not compared
    r = ref([[(-1.0, 0.05), (-2.0, 0.5)]])
    ok, d = probes.compare_greedy([[-1.10, -9.0]], r, 0.06, 0.06)
    assert ok and d["steps_compared"] == 1
    assert not probes.compare_greedy([[-1.12, -9.0]], r, 0.06, 0.06)[0]


@pytest.mark.parametrize("served,ok_wanted", [
    ([[-1.03, -2.05]], True),    # the second step is a tie: 0.06 + 0.02 allowed
    ([[-1.03, -2.09]], False),   # ... and no more than the gap on top
    ([[-1.07, -2.0]], False),    # no tie: the tolerance alone
])
def test_forced_probes_compare_every_step(served, ok_wanted):
    r = ref([[(-1.0, 0.5), (-2.0, 0.02)]])
    ok, d = probes.compare_forced(served, r, 0.06, 0.06)
    assert ok == ok_wanted and d["steps_compared"] == 2
    assert ok or "prefix +" in d["problems"][0]


def test_a_missing_step_fails():
    r = ref([[(-1.0, 0.5), (-2.0, 0.5)]])
    assert not probes.compare_greedy([[-1.0]], r, 0.06, 0.06)[0]
    assert not probes.compare_forced([[-1.0]], r, 0.06, 0.06)[0]
    assert not probes.compare_forced([], r, 0.06, 0.06)[0]


def test_probe_texts_follow_the_weights_seed_only():
    a = probes.probe_texts(7, (4, 260))
    assert a == probes.probe_texts(7, (4, 260))
    assert a != probes.probe_texts(8, (4, 260))
    assert [len(t) for t in a] == [n + probes.PROBE_STEPS - 1
                                   for n in probes.PROBE_LENS]
    assert all(min(t) >= 4 and max(t) < 260 for t in a)
    assert max(probes.PROBE_LENS) > 1024  # one probe runs the long-prompt path


@pytest.mark.parametrize("asked,depth", [(1, 1), (5, 5), (256, 8)])
def test_greedy_depth_follows_the_mix(asked, depth):
    assert probes.greedy_depth(asked) == depth


def test_reference_tolerance_is_tight():
    from lib import checkpoint

    r = checkpoint.load_module("reference", "llama_like")
    assert r.LOGPROB_TOL <= 0.06 and r.TIE_MARGIN <= r.LOGPROB_TOL


def test_reference_tail_matches_one_position_at_a_time():
    """tail_logprobs over the last n positions equals n passes that each
    ask for the last position of a shorter text; batches of different
    lengths share one pass."""
    import numpy as np

    from lib import checkpoint

    r = checkpoint.load_module("reference", "llama_like")
    with open(os.path.join(BENCH, "tests", "data", "tiny-qwen.json")) as f:
        m = json.load(f)["model"]
    layout = checkpoint.load_module("checkpoints", "llama_like")
    rng = np.random.default_rng(3)
    weights = {name: (np.ones(shape, np.float32) if kind == "ones" else
                      rng.normal(0, 0.05, shape).astype(np.float32))
               for name, shape, kind in layout.tensors(m)}
    a = rng.integers(4, 260, (2, 20))
    b = rng.integers(4, 260, (1, 33))
    ta, tb = r.tail_logprobs(weights.__getitem__, m, [a, b], 3)
    assert ta.shape == (2, 3, m["vocab_size"]) and tb.shape[:2] == (1, 3)
    for k in range(3):
        one = r.tail_logprobs(weights.__getitem__, m, [a[:, :18 + k]], 1)[0]
        assert np.allclose(one[:, 0], ta[:, k], atol=1e-5)
    assert np.allclose(np.exp(tb).sum(-1), 1.0, atol=1e-4)


def old_prefill_step_floor_s(model, peaks, tokens):
    """lib/roofline.py's count as it stood before the count moved behind
    benchmark/roofline/<family>.py (PR 29), frozen here."""
    H, I = model["hidden_size"], model["intermediate_size"]
    hd = model.get("head_dim") or H // model["num_attention_heads"]
    q = model["num_attention_heads"] * hd
    kv = model["num_key_value_heads"] * hd
    params = model["num_hidden_layers"] * (
        H * q + 2 * H * kv + q * H + 3 * H * I)
    t_mem = 2 * params / peaks["hbm_bytes_per_s"]
    t_flop = 2 * tokens * params / peaks["bf16_flops_per_s"]
    return max(t_mem, t_flop), ("memory" if t_mem >= t_flop else "compute")


def test_layer_weights_from_shapes():
    cfg = model("qwen2.5-7b-h14")
    m = cfg["model"]
    assert roofline.family(cfg).layer_weight_params(m) == 233046016
    # the checkpoint = the layers' matrices + their small vectors + embedding
    # and head; the floor counts the matrices alone
    vectors = 14 * (2 * 3584 + 3584 + 2 * 512) + 3584
    assert cfg["memory"]["weights_bytes"] == 2 * (
        14 * 233046016 + vectors + 2 * 152064 * 3584)


@pytest.mark.parametrize("tokens,bound", [(16, "memory"), (64, "memory"),
                                          (512, "compute")])
def test_prefill_floor_from_shapes(tokens, bound):
    cfg = model("qwen2.5-7b-h14")
    m = cfg["model"]
    p = peaks.peaks_for("TPU v5 lite")
    secs, which = roofline.family(cfg).prefill_step_floor_s(m, p, tokens)
    params = 14 * 233046016
    assert which == bound
    assert secs == pytest.approx(max(2 * params / 819e9,
                                     2 * tokens * params / 197e12))


def test_the_familys_file_gives_the_old_float_for_every_step():
    """The count moved, the number did not: for every chunk the cell's
    steps can have (1..4096 tokens) the family's file returns the float the
    old lib/roofline.py returned, and the reader, over a run made of the
    recorded v5e trace's program executions taken as prefill steps of every
    bucket, the same share to the last bit."""
    cfg = model("qwen2.5-7b-h14")
    m, p = cfg["model"], peaks.peaks_for("TPU v5 lite")
    assert roofline.family_name(cfg) == "llama_like"
    new = roofline.family(cfg).prefill_step_floor_s
    for tokens in range(1, 4097):
        assert new(m, p, tokens) == old_prefill_step_floor_s(m, p, tokens)

    from lib import checkpoint, trace
    with open(os.path.join(BENCH, "tests", "data",
                           "recorded_trace.json")) as f:
        rec = json.load(f)
    t0, t1 = rec["window_ns"]
    steps = [e for e in rec["step_events"] if t0 <= e["t_ns"]
             and e["t_ns"] + e["dur_ns"] <= t1]
    buckets = (16, 32, 64, 128, 256, 512)
    events = [dict(e, kind="prefill_chunk", batch=1,
                   tokens=buckets[i % len(buckets)] - (i % 3))
              for i, e in enumerate(steps)]
    run = {"t0": t0 / 1e9, "t1": t1 / 1e9, "events": events, "config": cfg,
           "peaks": p, "trace": trace.reduce(rec, t0, t1, events)}
    read = checkpoint.load_module(
        "layer_metrics", "kernel.prefill_step_roofline").read
    from lib import runview
    timed = runview.prefill_steps(run)
    assert len(timed) >= 10
    want = 100.0 * sum(old_prefill_step_floor_s(m, p, e["tokens"])[0]
                       for e, _ in timed) / sum(s for _, s in timed)
    assert read(run) == want
