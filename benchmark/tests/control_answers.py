#!/usr/bin/env python3
"""A control of `correct` at a cell's own size, on the machine that ran it:

    python3 benchmark/tests/control_answers.py <cell> <control> <stdout of a run>

`<control>` is a keyword of the family's reference `forward`
(`lower_precision`, `ignore_window`, `drop_last_expert`, ...).  The plain
reference is run once more with it switched on (numpy, the host CPU; the
checkpoint the run left under benchmark/.cache/ is read, nothing is
served), and two comparisons are printed, each by `lib/probes.py`
`compare_forced` at the family's own limits:

  control_as_program    the control's answers where the served path's would
                        stand, against the plain reference's: what a lower
                        precision, put in the program's place, reads
  served_vs_control     the answers the chip served in that run (its
                        `probes` note) against the control's as if they
                        were the reference: what a fault planted in the
                        reference reads

Either has to come out not `ok` for the control to have failed, and
`steps_over_by_probe` says on which probes.  Not part of a benchmark run."""

import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import probes, reference_child, traffic  # noqa: E402


def main(cell_name, control, stdout_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == cell_name]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    config_path = os.path.join(ROOT, entry["file"])
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        cell["traffic"] + ".json"))
    lens = probes.lens_of(mix)
    (ckpt_dir,) = glob.glob(os.path.join(BENCH, ".cache", "ckpt",
                                         cell["config"] + "-*"))
    note = None
    with open(stdout_path) as f:
        for line in f:
            if line.startswith('{"note": "probes"'):
                note = json.loads(line)
    if note is None or note["probe_lens"] != list(lens):
        raise SystemExit(f"no probes note of this mix in {stdout_path}")
    out = os.path.join(BENCH, ".cache", "reference",
                       f"control-{cell['config']}-{control}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    reference_child.main(config_path, ckpt_dir, out, 1, lens, control)
    with open(out) as f:
        ctl = json.load(f)
    tol, margin = note["tolerance"], note["tie_margin"]
    plain = None  # the run's own reference answers, with their top-2 gaps
    for path in glob.glob(os.path.join(BENCH, ".cache", "reference",
                                       cell["config"] + "-*.json")):
        with open(path) as f:
            found = json.load(f)
        if found.get("probe_lens") == list(lens) and not found.get("control"):
            plain = found["forced"]
    if plain is None:
        raise SystemExit("the run's reference answers are not in the cache")
    as_program = probes.compare_forced(
        [[s["logprob"] for s in steps] for steps in ctl["forced"]],
        plain, tol, margin)
    vs_control = probes.compare_forced(note["served"], ctl["forced"], tol,
                                       margin)
    print(json.dumps({"cell": cell_name, "control": control,
                      "probe_lens": list(lens), "tolerance": tol,
                      "control_as_program": {"ok": as_program[0],
                                             **as_program[1]},
                      "served_vs_control": {"ok": vs_control[0],
                                            **vs_control[1]}}))


if __name__ == "__main__":
    main(*sys.argv[1:4])
