"""BENCHMARK.json walked without a chip: what a cell names exists, every
listed metric has its reader, every `workloads` name is a cell, every cell's
and configuration's `worker_flags` pass `run.py`'s check and every mix's
longest request and probe fit the context its cell gives the worker.  A case
a cell, so that a new cell is held to it from its first PR.  (ISSUE 46 asked
for this file under tests/, where every PR runs it; a `benchmark` PR adds no
file there: PERF.md, Open questions.)"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]


def parts(name):
    cell = bench_run.by_name(SPEC["workloads"], name, "workload")
    entry = bench_run.by_name(SPEC["configs"], cell["config"],
                              "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(bench_run.mix_path(cell, False))
    return cell, entry, config, mix


@pytest.mark.parametrize("name", CELLS)
def test_what_a_cell_names_exists(name):
    cell, entry, config, mix = parts(name)
    assert config["name"] == entry["name"]
    bench_run.family_files(config)  # checkpoint, reference, roofline
    assert os.path.exists(os.path.join(BENCH, "loops", mix["loop"] + ".py"))
    assert cell["chips"] == config["chips"]
    assert len(traffic.session_sizes(mix)) == mix["set_size"]


@pytest.mark.parametrize("name", CELLS)
def test_a_cells_sizes_pass_and_hold_its_mix(name):
    cell, _, config, mix = parts(name)
    sizes = bench_run.cell_sizes(cell, False)
    flags = bench_run.worker_flags(cell, config, sizes, False)
    assert flags[:2] == ["--num-pages",
                         str(config["worker_flags"]["--num-pages"])] or (
        "--num-pages" in sizes.get("worker_flags", {}))
    context = bench_run.check_context(cell, config, sizes, mix)
    longest = max(s["prefix_len"] + fresh + out
                  for s in traffic.session_sizes(mix)
                  for fresh, out in s["turns"])
    assert longest <= traffic.longest_request(mix) <= context
    pool = sizes.get("memory", config["memory"])["kv_pool_tokens"]
    assert int(mix["clients"]) * traffic.longest_request(mix) <= pool


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_reports_set_up_another_end_to_end_and_a_layer(name):
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if bench_run.applies(m, name)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(bench_run.applies(m, name) for m in SPEC["per_layer"])


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_has_its_reader_and_names_cells(name):
    metric = bench_run.by_name(SPEC["end_to_end"] + SPEC["per_layer"], name,
                               "metric")
    if name != "setup_s":  # the harness's own
        kind = ("end_to_end" if metric in SPEC["end_to_end"]
                else "layer_metrics")
        assert callable(bench_run.load_reader(kind, name))
    assert set(metric.get("workloads", ())) <= set(CELLS)
    if "moves" in metric:
        moved = bench_run.by_name(SPEC["end_to_end"], metric["moves"],
                                  "end-to-end metric")
        for cell in metric.get("workloads", CELLS):
            assert bench_run.applies(moved, cell)


def test_a_cells_file_belongs_to_a_cell():
    for f in os.listdir(os.path.join(BENCH, "cells")):
        assert f.endswith(".json") and f[:-len(".json")] in CELLS


@pytest.mark.parametrize("name", CELLS)
def test_an_entry_of_workloads_has_the_five_keys_the_driver_takes(name):
    """The builder's contract for BENCHMARK.json: "Each entry has just the
    keys shown, and a metric may add `workloads`: any other key [...] is
    refused", before a single run.  So a cell's `worker_flags` cannot ride
    on its entry (as ISSUE 46 had them) and lie in benchmark/cells/."""
    cell = bench_run.by_name(SPEC["workloads"], name, "workload")
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
