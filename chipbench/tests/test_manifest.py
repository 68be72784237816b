"""``BENCHMARK.json`` and the files it names agree, so that a cell, a
configuration or a metric is found by its name."""

import importlib
import json
import os

import pytest

from chipbench import family, run as bench_run

ROOT = bench_run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def metric_cells(entry):
    return sorted(entry.get("workloads", CELLS))


@pytest.mark.parametrize("name", CELLS)
def test_cell_file_matches_its_entry(name):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    cell = bench_run.find_cell(name)
    bench_run.validate(cell)
    assert (cell["config"], cell["traffic"], cell["why"]) == (
        entry["config"], entry["traffic"], entry["why"])
    assert name == f"{cell['config']}.{cell['traffic']}"
    config = bench_run.load_json(cell["config_file"])["chipbench"]
    assert config["chips"] == entry["chips"]
    assert cell.get("platform", "tpu") == "tpu"
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if name in metric_cells(m)}
    assert reported == set(cell["end_to_end"])
    reported = {m["name"] for m in MANIFEST["per_layer"]
                if name in metric_cells(m)}
    assert reported == set(cell["per_layer"])


@pytest.mark.parametrize("entry", MANIFEST["per_layer"],
                         ids=lambda e: e["name"])
def test_reader_matches_its_entry(entry):
    reader = importlib.import_module(
        f"chipbench.layer_metrics.{entry['name']}")
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    for cell in metric_cells(entry):
        assert entry["moves"] in bench_run.find_cell(cell)["end_to_end"]


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda e: e["name"])
def test_config_file_matches_its_entry(entry):
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    bench = config["chipbench"]
    assert (bench["name"], bench["source"], bench["reduced"]) == (
        entry["name"], entry["source"], entry["reduced"])
    family.name_of(config)
    if "page-size" in bench["server_flags"]:
        # The Pallas kernels serve no smaller page (PERF.md section 4); a
        # model whose state is not paged has no reason to give the flag.
        assert bench["server_flags"]["page-size"] == 128
    assert {"max_abs_logprob_diff", "mean_abs_logprob_diff", "why"} <= set(
        bench["reference_tolerance"])


def test_every_per_layer_metric_lists_its_cells():
    """Without the list a metric belongs to every cell that reports
    what it moves, those of later PRs too; with it a new cell lists
    itself where it has something to read."""
    for entry in MANIFEST["per_layer"]:
        assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)


def test_end_to_end_units_are_the_harness_own():
    for entry in MANIFEST["end_to_end"]:
        assert bench_run.E2E_UNITS[entry["name"]] == entry["unit"]
    assert MANIFEST["command"] == ["python3", "chipbench/run.py"]
