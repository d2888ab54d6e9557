"""A configuration, a mix, a per-layer metric, a checkpoint layout and a
reference are found by file name alone: adding one is adding files and
entries, never an edit."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import checkpoint, peaks  # noqa: E402


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_entry_of_the_spec_has_its_files():
    s = spec()
    for c in s["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for kind, key in (("checkpoints", "checkpoint"),
                          ("reference", "reference"),
                          ("roofline", "checkpoint")):
            assert os.path.exists(
                os.path.join(BENCH, kind, cfg.get(kind, cfg[key]) + ".py"))
        bench_run.family_files(cfg)
        assert not set(cfg["worker_flags"]) & set(bench_run.POLICY_FLAGS)
    for w in s["workloads"]:
        assert os.path.exists(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in s["per_layer"]:
        assert callable(bench_run.load_reader("layer_metrics", m["name"]))
    for m in s["end_to_end"]:
        if m["name"] != "setup_s":
            assert callable(bench_run.load_reader("end_to_end", m["name"]))


def test_each_cell_reports_setup_one_more_and_a_layer_metric():
    s = spec()
    for w in s["workloads"]:
        e2e = [m["name"] for m in s["end_to_end"]
               if bench_run.applies(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in s["per_layer"] if bench_run.applies(m, w["name"])]
        assert layer
        for m in layer:  # what it moves is reported wherever it is
            assert m["moves"] in e2e, (m["name"], w["name"])


def test_a_policy_flag_in_a_configuration_is_refused():
    with pytest.raises(bench_run.RunFailure):
        bench_run.worker_flags(
            {"name": "x.m"},
            {"name": "x", "worker_flags": {"--decode-steps": 8}}, {}, False)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_a_new_config_mix_loop_metric_and_cell_need_no_edit(tmp_path):
    """In a scratch copy: add a configuration, a mix, a loop, an end-to-end
    metric, a per-layer metric and a cell as new files plus entries of
    BENCHMARK.json; the harness finds them.  Then a second FAMILY: a
    configuration without `intermediate_size` (an expert model has only an
    expert width) with its own checkpoints/, reference/ and roofline/ file,
    read by the roofline reader that is there."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    before = {p: checkpoint.file_sha(os.path.join(dp, p))
              for dp, _, fs in os.walk(bench) for p in fs}
    with open(bench / "configs" / "qwen2.5-7b-h14.json") as f:
        cfg = json.load(f)
    cfg["name"] = "scratch-model"
    (bench / "configs" / "scratch-model.json").write_text(json.dumps(cfg))
    with open(bench / "traffic" / "docqa-1tok.json") as f:
        mix = json.load(f)
    mix.update(loop="trickle", set_size=20)
    (bench / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (bench / "loops" / "trickle.py").write_text(
        "async def run(ctx):\n    return {'sent': ctx['mix']['set_size']}\n")
    (bench / "layer_metrics" / "loadgen.sends.py").write_text(
        "def read(run):\n    return float(len(run['records']))\n")
    (bench / "end_to_end" / "requests_per_s.py").write_text(
        "def read(w):\n    return len(w['ok']) / (w['t1'] - w['t0'])\n")
    expert = json.loads(json.dumps(cfg))
    expert.update(name="scratch-experts", checkpoint="expert_like",
                  reference="expert_like")
    del expert["model"]["intermediate_size"]
    expert["model"].update(model_type="scratch_moe", moe_intermediate_size=768,
                           num_experts=64, num_experts_per_tok=6)
    (bench / "configs" / "scratch-experts.json").write_text(json.dumps(expert))
    (bench / "checkpoints" / "expert_like.py").write_text(
        "def tensors(model):\n"
        "    yield ('model.embed_tokens.weight', (model['vocab_size'],"
        " model['hidden_size']), 'random')\n")
    (bench / "reference" / "expert_like.py").write_text(
        "LOGPROB_TOL, TIE_MARGIN = 0.06, 0.06\n\n"
        "def tail_logprobs(read, model, batches, n_last):\n"
        "    raise NotImplementedError\n")
    (bench / "roofline" / "expert_like.py").write_text(
        "def prefill_step_floor_s(model, peaks, tokens):\n"
        "    # the experts a token uses, the experts a step can touch\n"
        "    H, E = model['hidden_size'], model['moe_intermediate_size']\n"
        "    used = min(model['num_experts'],"
        " tokens * model['num_experts_per_tok'])\n"
        "    t_mem = 2 * model['num_hidden_layers'] * used * 3 * H * E"
        " / peaks['hbm_bytes_per_s']\n"
        "    t_flop = 2 * tokens * model['num_hidden_layers']"
        " * model['num_experts_per_tok'] * 3 * H * E"
        " / peaks['bf16_flops_per_s']\n"
        "    return max(t_mem, t_flop), 'memory' if t_mem >= t_flop"
        " else 'compute'\n")
    s = spec()
    s["configs"].append({"name": "scratch-experts", "source": cfg["source"],
                         "file": "benchmark/configs/scratch-experts.json",
                         "reduced": ["num_hidden_layers"], "why": "scratch"})
    s["workloads"].append({"name": "scratch-experts.docqa-1tok",
                           "config": "scratch-experts",
                           "traffic": "docqa-1tok", "chips": 1,
                           "why": "scratch"})
    s["configs"].append({"name": "scratch-model", "source": cfg["source"],
                         "file": "benchmark/configs/scratch-model.json",
                         "reduced": ["num_hidden_layers"], "why": "scratch"})
    s["workloads"].append({"name": "scratch.trickle", "config": "scratch-model",
                           "traffic": "trickle", "chips": 1, "why": "scratch"})
    s["end_to_end"].append({"name": "requests_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["scratch.trickle"]})
    s["per_layer"].append({"name": "loadgen.sends", "unit": "count",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "requests_per_s",
                           "workloads": ["scratch.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    code = (
        "import asyncio, json, sys; sys.path.insert(0, 'benchmark'); import run\n"
        "from lib import checkpoint, traffic\n"
        "s = json.load(open('BENCHMARK.json'))\n"
        "cell = run.by_name(s['workloads'], 'scratch.trickle', 'workload')\n"
        "entry = run.by_name(s['configs'], cell['config'], 'configuration')\n"
        "cfg = json.load(open(entry['file']))\n"
        "mix = traffic.load_mix('benchmark/traffic/' + cell['traffic'] + '.json')\n"
        "n = len(traffic.sessions(mix, 1, tuple(cfg['prompt_vocab'])))\n"
        "loop = checkpoint.load_module('loops', mix['loop'])\n"
        "sent = asyncio.run(loop.run({'mix': mix}))['sent']\n"
        "v = run.load_reader('layer_metrics', 'loadgen.sends')({'records': [0] * n})\n"
        "e = run.load_reader('end_to_end', 'requests_per_s')({'ok': [0] * n, 't0': 0, 't1': 10})\n"
        "print(json.dumps([cfg['name'], n, sent, v, e]))\n"
        # the second family: every per-layer metric without a `workloads`
        # list is read in its cell too, the roofline one from its own count
        "from lib import peaks, roofline\n"
        "cell = run.by_name(s['workloads'], 'scratch-experts.docqa-1tok', 'w')\n"
        "entry = run.by_name(s['configs'], cell['config'], 'configuration')\n"
        "cfg = json.load(open(entry['file']))\n"
        "run.family_files(cfg)\n"
        "mods = [[(10, 50_000_010, 'jit_prefill_step')]]\n"
        "fake = {'t0': 0.0, 't1': 1.0, 'config': cfg, 'records': [],\n"
        "        'peaks': peaks.peaks_for('TPU v5 lite'),\n"
        "        'trace': {'modules': mods}, 'events': [\n"
        "    {'kind': 'prefill_chunk', 't_ns': 0, 'dur_ns': 60_000_000,\n"
        "     'batch': 1, 'tokens': 512, 'ring': 'engine'}]}\n"
        "read = [m['name'] for m in s['per_layer']\n"
        "        if run.applies(m, cell['name'])\n"
        "        and run.load_reader('layer_metrics', m['name'])(fake) is not None]\n"
        "share = run.load_reader('layer_metrics', 'kernel.prefill_step_roofline')(fake)\n"
        "print(json.dumps([cfg['name'], roofline.family_name(cfg), read, share]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, check=True)
    lines = out.stdout.splitlines()
    assert json.loads(lines[-2]) == ["scratch-model", 20, 20, 20.0, 2.0]
    name, fam, read, share = json.loads(lines[-1])
    assert (name, fam) == ("scratch-experts", "expert_like")
    assert "kernel.prefill_step_roofline" in read
    # 512 tokens x 6 experts each can touch all 64: 14 layers x 64 experts
    # x 3 x 3584 x 768 weights in bf16 over 819 GB/s (18.1 ms; the operations
    # of 6 experts a token take 3.6), against 50 ms on the device
    want = 2 * 14 * 64 * 3 * 3584 * 768 / 819e9
    assert 0 < share < 100 and share == pytest.approx(100 * want / 50e-3)
    after = {p: checkpoint.file_sha(os.path.join(dp, p))
             for dp, _, fs in os.walk(bench) for p in fs if p in before}
    assert after == before  # no existing file was touched


def test_a_bare_directory_gives_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: exit code other than
    0 and no result line."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    name = spec()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", name, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""
