import json
import os

import pytest

import run as bench_run
from lib import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))
VOCAB = (4, 260)


def mix(name):
    return traffic.load_mix(os.path.join(BENCH, "traffic", name + ".json"))


def sizes(m, seed, cycle=0):
    return [tuple((len(t["prompt"]), t["max_tokens"]) for t in s)
            for s in traffic.sessions(m, seed, VOCAB, cycle)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = mix(name)
    a = traffic.sessions(m, 3000000019, VOCAB)
    assert a == traffic.sessions(m, 3000000019, VOCAB)
    assert a != traffic.sessions(m, 5, VOCAB)
    assert a != traffic.sessions(m, 3000000019, VOCAB, cycle=1)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_set_of_sizes_in_another_order(name):
    m = mix(name)
    a, b = sizes(m, 1), sizes(m, 2 ** 31 + 7)
    assert len(a) == m["set_size"] and len(set(a)) > 1
    assert sorted(a) == sorted(b) and a != b
    assert sorted(a) == sorted(sizes(m, 1, cycle=3)) and a != sizes(m, 1, 3)


def contexts(name):
    """The context `run.py` `check_context` gives each cell that sends the
    mix (it fails a cell whose mix can pass it); the worker's default where
    no cell of BENCHMARK.json sends it."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    found = []
    for cell in spec["workloads"]:
        if cell["traffic"] != name:
            continue
        entry = bench_run.by_name(spec["configs"], cell["config"], "config")
        with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
            config = json.load(f)
        found.append(bench_run.check_context(
            cell, config, bench_run.cell_sizes(cell, False), mix(name)))
    return found or [bench_run.DEFAULT_MAX_MODEL_LEN]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_the_context_a_mix_is_served_at(name):
    m = mix(name)
    context = min(contexts(name))
    for s in traffic.sessions(m, 1, VOCAB):
        for turn in s:
            assert len(turn["prompt"]) + turn["max_tokens"] < context
            assert all(VOCAB[0] <= t < VOCAB[1] for t in turn["prompt"])
            assert turn["max_tokens"] >= 1
    assert traffic.max_output_len(m) == max(
        t["max_tokens"] for s in traffic.sessions(m, 1, VOCAB) for t in s)


def test_sessions_share_their_document_and_nothing_else():
    m = mix("docqa-1tok")
    plan = traffic.sessions(m, 4, VOCAB)
    for s in plan:
        assert len(s) == 3
        doc = min(len(t["prompt"]) for t in s) - 48
        assert 1024 - 48 <= doc
        n = len(s[0]["prompt"]) - 48
        assert s[0]["prompt"][:n] == s[1]["prompt"][:n] == s[2]["prompt"][:n]
        assert all(t["max_tokens"] == 1 for t in s)
    assert plan[0][0]["prompt"][:64] != plan[1][0]["prompt"][:64]


def test_length_specs():
    import random

    rng = random.Random(1)
    assert traffic.draw_len(rng, {"dist": "fixed", "value": 7}) == 7
    xs = [traffic.draw_len(rng, {"dist": "lognormal", "median": 384,
                                 "sigma": 0.8, "min": 32, "max": 2048})
          for _ in range(2000)]
    assert min(xs) >= 32 and max(xs) <= 2048
    assert 330 < sorted(xs)[1000] < 440
    with pytest.raises(ValueError):
        traffic.draw_len(rng, {"dist": "zipf"})


def test_a_mix_file_is_data_and_names_a_loop_that_exists():
    for name in MIXES:
        with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
            m = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "loops", m["loop"] + ".py"))
