"""Two walks of `run.py --rehearse-cpu` over the long-document cell's tiny
stand-in (tests/data/: a configuration with a 64-token window, a mix whose
last probe is 600 tokens, a cell file that lays `--max-model-len` over the
configuration's pool): the laid-over flags reach the worker, the long probe
is compared, and a fault that only a context past the window shows, planted
in a scratch checkout's copy of the reference, turns `correct` false on the
long probe alone.  The look for a chip is the one thing a rehearsal skips."""

import json
import os
import sys

from proctags import alive, gone_within, new_tag
from test_run_ends import rehearse, scratch_checkout

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
CELL = "smallthinker-21b.longdoc-1tok"

# At the stand-in's size the fault reads 0.003-0.028 on the long probe (its
# output is near uniform over 640 tokens) where the float32 CPU path agrees
# with the reference to 5e-7, so the scratch copy also states the limit that
# path can be held to; at the cell's own size the fault reads 0.08-0.10
# against 0.03 (PERF.md, PR 46: benchmark/tests/control_answers.py on the
# chip's host).
WINDOW_IGNORED = '''

LOGPROB_TOL, TIE_MARGIN = 0.002, 0.0
_forward = forward


def tail_logprobs(read, model, batches, n_last):  # the fault, planted
    return _forward(read, model, batches, n_last, ignore_window=True)
'''


def walk(root):
    tag = new_tag()
    p = rehearse(root, CELL, tag, "--seconds", "4")
    try:
        out, err = p.communicate(timeout=400)
    finally:
        p.kill()
    assert p.returncode == 2, err[-2000:]
    assert gone_within(tag), f"left running: {alive(tag)}"
    notes = {}
    for line in out.splitlines():
        note = json.loads(line)
        notes[note["note"]] = note
    return notes, err


def test_the_stand_in_walks_the_laid_over_flags_and_the_long_probe():
    notes, _ = walk(ROOT)
    assert notes["worker"]["flags"][:4] == [
        "--num-pages", "1024", "--max-model-len", "1024"]
    assert notes["probes"]["probe_lens"] == [12, 12, 600]
    assert notes["probes"]["forced"]["steps_compared"] == 24
    assert notes["window"]["context_tokens"] == 1024
    would = notes["rehearsal"]["would_print"]
    assert would["correct"] is True and would["failed"] == 0
    assert list(would)[-1] == "compared"  # last: the record keeps ends
    assert would["compared"]["logprob_past_allowed_max"]["value"] <= 0
    assert would["compared"]["forced_steps_over"] == {"value": 0, "limit": 0}
    assert set(would["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}


def test_a_window_fault_turns_correct_false_on_the_long_probe_alone(
        tmp_path):
    bench = scratch_checkout(tmp_path)
    with open(bench / "reference" / "smallthinker.py", "a") as f:
        f.write(WINDOW_IGNORED)
    notes, err = walk(tmp_path)
    would = notes["rehearsal"]["would_print"]
    assert would["correct"] is False
    over = notes["probes"]["forced"]["steps_over_by_probe"]
    assert over[:2] == [0, 0] and over[2] > 0
    assert would["compared"]["logprob_past_allowed_max"]["value"] > 0
    assert would["compared"]["forced_steps_over"]["value"] == over[2]
