"""Every cell, configuration, traffic mix, limit and per-layer metric
that BENCHMARK.json names is found by name."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BM = json.load(f)


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_cell_files_found_by_name(name):
    cell = harness.find_cell(name)
    wl = next(w for w in BM["workloads"] if w["name"] == name)
    cfg = next(c for c in BM["configs"] if c["name"] == wl["config"])
    assert cfg["file"] == f"bench/configs/{wl['config']}.json"
    assert cell.config["name"] == wl["config"]
    assert cell.config["reduced"] == cfg["reduced"]
    assert set(cell.limits) >= {"loss_gap", "update_gap"}
    assert ("moment_gap" in cell.limits) == (cell.config["opt"] == "adam")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("name", [m["name"] for m in BM["per_layer"]])
def test_metric_reader_found_by_name(name):
    assert callable(harness.metric_reader(name))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.find_cell("no_such_cell")


def test_peaks_table_names_its_source():
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
