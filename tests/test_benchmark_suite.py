"""The benchmark's own tests, seen by tier-1 (ROADMAP D13): `benchmark/tests`
runs by `python -m pytest benchmark/tests` and by nothing the driver runs, so
a reader's arithmetic or the spec's walk could turn red unseen.  ONE
subprocess runs the files below; each is a case here and fails with what its
file printed.  Nothing under `benchmark/` is edited or imported.

Left out for the suite's time (48, 33 and 7 s of the directory's 93 on one
worker): the two walks of a whole stand-in run and the process handling.
They run by hand, as before."""

import glob
import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEFT_OUT = {"test_walk_long_context.py", "test_run_ends.py", "test_procs.py"}
# ONE case left out since PR 55, by name: it holds eight `workloads` lists of
# `BENCHMARK.json` to END in the Laguna cell (and four to hold it alone),
# while `test_rows_readers.py` holds three of the same lists to name EVERY
# cell: no cell after Laguna's can satisfy both, and a PR that is not a
# `benchmark` PR may edit neither file (PERF.md section 7 (bh): the repair is
# to assert by name, as 7 (k) did for the two before it)
DESELECT = ("benchmark/tests/test_laguna_readers.py::"
            "test_the_spec_lists_the_new_readers_for_the_new_cell_alone")
FILES = sorted(
    os.path.basename(p)
    for p in glob.glob(os.path.join(ROOT, "benchmark", "tests", "test_*.py"))
    if os.path.basename(p) not in LEFT_OUT)


@pytest.fixture(scope="module")
def suite(tmp_path_factory):
    """{module name: {"passed": n, "failed": [...]}} of one run of the files,
    and the end of what the run printed."""
    xml = str(tmp_path_factory.mktemp("benchmark_suite") / "junit.xml")
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--junitxml", xml, "--deselect", DESELECT,
         *(os.path.join("benchmark", "tests", f) for f in FILES)],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert os.path.exists(xml), run.stdout[-3000:] + run.stderr[-3000:]
    by_file = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        ran = by_file.setdefault(
            case.get("classname").rsplit(".", 1)[-1],
            {"passed": 0, "failed": []})
        outcome = {c.tag: c.get("message") for c in case}
        if "failure" in outcome or "error" in outcome:
            ran["failed"].append(f"{case.get('name')}: {outcome}")
        elif "skipped" not in outcome:
            ran["passed"] += 1
    return by_file, run.stdout[-3000:]


@pytest.mark.parametrize("name", FILES)
def test_the_benchmarks_own_tests_pass(suite, name):
    by_file, tail = suite
    ran = by_file.get(name[:-len(".py")], {"passed": 0, "failed": []})
    assert not ran["failed"] and ran["passed"] > 0, (
        "\n".join(ran["failed"] or ["no case ran"]) + "\n" + tail)
