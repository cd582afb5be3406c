"""Every cell end to end at a tiny size on the CPU, through the functions
the chip run uses: sound runs are correct and report the cell's metrics;
the control and each fault the cell can have make ``correct`` false.
The four-chip cell runs on four CPU devices in a subprocess (see
``test_cells_x4.py``)."""
import json

import pytest

from bench import harness
from bench.tests import cpu_cell

ONE_CHIP = ["delaunay_n20.solve_1col", "delaunay_n20.partition_topo"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct_and_reports_its_metrics(workload, monkeypatch):
    r = cpu_cell.run(workload, patch=monkeypatch.setattr)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    cell = harness.load_cell(harness.ROOT, workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(r)[-1] == "checks"
    assert r["diagnostics"]["compiles_in_window"] == 0
    json.dumps(r)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(workload, monkeypatch):
    r = cpu_cell.run(workload, control=True, patch=monkeypatch.setattr)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("workload, fault", [
    ("delaunay_n20.solve_1col", "unchanged"),
    ("delaunay_n20.solve_1col", "altered"),
    ("delaunay_n20.partition_topo", "unchanged"),
    ("delaunay_n20.partition_topo", "altered"),
])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    r = cpu_cell.run(workload, fault=fault, patch=monkeypatch.setattr)
    assert not r["correct"], r["checks"]


def test_traced_run_reports_no_device_metric_off_the_chip(monkeypatch):
    r = cpu_cell.run("delaunay_n20.solve_1col", trace=True,
                     patch=monkeypatch.setattr)
    assert r["correct"]
    assert "busy_s" not in r["device"] and "breakdown" not in r
    assert not r["metrics"]
