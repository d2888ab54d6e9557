"""However `run.py` ends, it leaves one failure line and no process: two
walks of `run.py --rehearse-cpu` (CPU backend, the tiny stand-ins) and the
parts of `main()` that need no stack.  Every process of a walk inherits
`BENCH_TEST_TAG` from the test, which then looks for it in /proc/*/environ."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

from proctags import alive, gone_within, new_tag

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "qwen2.5-7b.docqa-1tok"

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import procs  # noqa: E402

# A family the program cannot load, as a later `model_config` PR's parent
# side meets it: a `model_type` the program has never heard of, a layout
# without `mlp.*` tensors, no `intermediate_size`; beside it a reference
# that is still computing when the worker gives up.
NO_MLP_LAYOUT = """
import importlib.util, os
_spec = importlib.util.spec_from_file_location(
    "llama_like", os.path.join(os.path.dirname(__file__), "llama_like.py"))
_llama = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_llama)


def tensors(model):
    for name, shape, kind in _llama.tensors(dict(model, intermediate_size=8)):
        if ".mlp." not in name:
            yield name, shape, kind
"""
SLOW_REFERENCE = """
import time
LOGPROB_TOL, TIE_MARGIN = 0.06, 0.06


def tail_logprobs(read, model, batches, n_last):
    time.sleep(120)  # a float32 pass at published widths takes minutes
"""
EXPERT_ROOFLINE = """
def prefill_step_floor_s(model, peaks, tokens):
    return 1e-3, "memory"
"""


def scratch_checkout(tmp_path):
    """benchmark/ copied, the program linked: a checkout whose benchmark
    gets new files and whose files that are there are not touched."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for name in ("dynamo_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    return tmp_path / "benchmark"


def add_unloadable_family(bench):
    (bench / "checkpoints" / "no_mlp.py").write_text(NO_MLP_LAYOUT)
    (bench / "reference" / "slow.py").write_text(SLOW_REFERENCE)
    (bench / "roofline" / "no_mlp.py").write_text(EXPERT_ROOFLINE)
    with open(bench / "tests" / "data" / "tiny-qwen.json") as f:
        cfg = json.load(f)
    cfg.update(name="tiny-unloadable", checkpoint="no_mlp", reference="slow")
    cfg["model"].update(model_type="never_heard_of",
                        architectures=["NeverHeardOfForCausalLM"])
    del cfg["model"]["intermediate_size"]
    (bench / "tests" / "data" / "tiny-unloadable.json").write_text(
        json.dumps(cfg))
    with open(bench / "tests" / "data" / "REHEARSAL.json") as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-unloadable", "source": "none",
        "file": "benchmark/tests/data/tiny-unloadable.json", "reduced": [],
        "why": "a family the program cannot load"})
    spec["workloads"].append({
        "name": "unloadable.docqa-1tok", "config": "tiny-unloadable",
        "traffic": "docqa-1tok", "chips": 1, "why": "parent-side failure"})
    (bench / "tests" / "data" / "REHEARSAL.json").write_text(json.dumps(spec))


def rehearse(root, cell, tag, *more):
    return subprocess.Popen(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--rehearse-cpu", "--seed", "3", *more], cwd=root,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, BENCH_TEST_TAG=tag, JAX_PLATFORMS="cpu"))


def failure_lines(err):
    return [ln for ln in err.splitlines()
            if ln.startswith("BENCHMARK RUN FAILED:")]


def test_a_worker_that_cannot_load_beside_a_reference_still_computing(
        tmp_path):
    bench = scratch_checkout(tmp_path)
    add_unloadable_family(bench)
    tag = new_tag()
    t0 = time.monotonic()
    p = rehearse(tmp_path, "unloadable.docqa-1tok", tag, "--seconds", "4")
    try:
        out, err = p.communicate(timeout=240)
    finally:
        p.kill()
    took = time.monotonic() - t0
    assert p.returncode == 1, err[-2000:]
    lines = failure_lines(err)
    assert len(lines) == 1, err[-2000:]
    assert "cell unloadable.docqa-1tok" in lines[0]
    assert "configuration tiny-unloadable" in lines[0]
    assert "process died" in lines[0]          # and the worker's last lines
    assert not any('"correct"' in ln for ln in out.splitlines())
    assert took < 100, "the run did not wait for the 120 s reference"
    assert gone_within(tag), f"left running: {alive(tag)}"
    ref_dir = bench / ".cache" / "reference"
    assert [f for f in os.listdir(ref_dir) if f.endswith(".log")]
    assert not [f for f in os.listdir(ref_dir) if f.endswith(".json")], (
        "a killed reference child leaves nothing a later run would trust")


def test_sigterm_in_the_window(tmp_path):
    tag = new_tag()
    p = rehearse(ROOT, CELL, tag, "--seconds", "600")
    try:
        for line in p.stdout:  # the probes are the last note before the loop
            if '"note": "probes"' in line:
                break
        else:
            pytest.fail("no probes note: " + p.stderr.read()[-2000:])
        time.sleep(8)  # the loop is sending: warm-up, then the 600 s window
        assert len(alive(tag)) >= 5  # run.py, control, frontend, worker, dog
        p.send_signal(signal.SIGTERM)
        out, err = p.communicate(timeout=150)
    finally:
        p.kill()
    assert p.returncode == 128 + signal.SIGTERM
    assert len(failure_lines(err)) == 1 and "SIGTERM" in err
    assert '"rehearsal"' not in out and '"correct"' not in out
    assert gone_within(tag), f"left running: {alive(tag)}"


def test_any_other_exception_is_one_failure_line_and_no_child(
        tmp_path, monkeypatch, capsys):
    """A reader's KeyError after the window, say: exit code 1, one line,
    the traceback in a file, and the children gone."""
    tag = new_tag()

    def broken(args, ps):
        ps.spawn([sys.executable, "-c", "import time; time.sleep(300)"],
                 "beside", str(tmp_path / "beside.log"))
        assert alive(tag)
        return {}["intermediate_size"]

    monkeypatch.setenv("BENCH_TEST_TAG", tag)
    monkeypatch.setattr(bench_run, "run_cell", broken)
    monkeypatch.setattr(bench_run, "CACHE", str(tmp_path / "cache"))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    assert bench_run.main() == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert len(failure_lines(captured.err)) == 1
    assert "KeyError: 'intermediate_size'" in captured.err
    assert "Traceback" not in captured.err
    with open(tmp_path / "cache" / "logs" / "last_failure.txt") as f:
        assert "Traceback" in f.read()
    assert not alive(tag)


@pytest.mark.parametrize("key,kind_dir", [
    ("checkpoint", "checkpoints"), ("reference", "reference"),
    ("roofline", "roofline")])
def test_a_family_without_one_of_its_files_fails_before_any_child(
        key, kind_dir):
    with open(os.path.join(BENCH, "configs", "qwen2.5-7b-h14.json")) as f:
        cfg = json.load(f)
    bench_run.family_files(cfg)  # as committed: every file is there
    cfg[key] = "never_written"
    with pytest.raises(procs.RunFailure,
                       match=f"benchmark/{kind_dir}/never_written.py"):
        bench_run.family_files(cfg)
