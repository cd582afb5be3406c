"""The ``make bench-diff`` regression gate (ISSUE 9 satellite).

``diff_payloads`` is the pure classifier (no git): >20% increases on
modeled objectives / makespans / round counts fail, wall-clock drift
only warns, agreement/noise bookkeeping never gates, and decreases are
always fine.  The tracked baselines themselves must parse and carry the
structural keys the gate fails on.
"""
import json

import pytest

from benchmarks.common import BASELINES
from benchmarks.diff import THRESHOLD, diff_payloads


def test_fail_on_modeled_objective_and_rounds():
    old = {"modeled_makespan": {"cut": 100.0},
           "tree_objective": {"cut": 50.0},
           "rounds": {"bottleneck": [1, 2, 1]}}
    new = {"modeled_makespan": {"cut": 130.0},       # +30% -> fail
           "tree_objective": {"cut": 55.0},          # +10% -> within band
           "rounds": {"bottleneck": [1, 2, 2]}}      # +100% -> fail
    failures, warnings = diff_payloads(old, new)
    paths = sorted(p for p, *_ in failures)
    assert paths == ["modeled_makespan.cut", "rounds.bottleneck[2]"]
    assert warnings == []


def test_latency_only_warns():
    old = {"per_iter_us": 1000.0, "spmv_us": 500.0, "wall_s": 10.0}
    new = {"per_iter_us": 2000.0, "spmv_us": 540.0, "wall_s": 30.0}
    failures, warnings = diff_payloads(old, new)
    assert failures == []
    assert sorted(p for p, *_ in warnings) == ["per_iter_us", "wall_s"]


def test_noise_keys_and_decreases_never_gate():
    old = {"agreement": {"max_rel_between": 1e-9},
           "modeled_makespan": 100.0, "per_iter_us": 1000.0,
           "win": {"per_iter": True}}
    new = {"agreement": {"max_rel_between": 1e-3},   # skip-classed
           "modeled_makespan": 40.0,                 # improvement
           "per_iter_us": 700.0,
           "win": {"per_iter": False}}               # bool: not numeric
    assert diff_payloads(old, new) == ([], [])


def test_new_and_missing_metrics_are_skipped():
    # a metric only on one side has no baseline to regress against
    failures, warnings = diff_payloads(
        {"modeled_makespan": {"cut": 100.0}},
        {"modeled_makespan": {"bottleneck": 400.0}})
    assert (failures, warnings) == ([], [])


def test_threshold_is_relative_increase():
    old = {"rounds": [10]}
    at = {"rounds": [round(10 * (1 + THRESHOLD), 6)]}   # exactly +20%
    over = {"rounds": [10 * (1 + THRESHOLD) + 0.1]}
    assert diff_payloads(old, at) == ([], [])
    failures, _ = diff_payloads(old, over)
    assert len(failures) == 1


@pytest.mark.parametrize("path", sorted(BASELINES.glob("BENCH_*.json")),
                         ids=lambda p: p.name)
def test_tracked_baselines_parse_and_self_diff_clean(path):
    payload = json.loads(path.read_text())
    assert isinstance(payload, dict) and payload
    # identical payloads never regress against themselves
    assert diff_payloads(payload, payload) == ([], [])


@pytest.mark.parametrize("ok", [True, False])
def test_bench_child_failure_exits_nonzero(ok, capsys):
    """A bench child that fails stops the bench (nonzero exit); it is
    never recorded as an ``"error"`` result row."""
    from benchmarks.common import run_child

    script = ('print(\'{"x": 1}\')' if ok
              else 'import sys; sys.exit("child broke")')
    if ok:
        assert run_child(["-c", script], timeout=60) == {"x": 1}
    else:
        with pytest.raises(SystemExit, match="exit code 1"):
            run_child(["-c", script], timeout=60)
        assert "child broke" in capsys.readouterr().err
