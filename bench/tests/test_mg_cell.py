"""``hpcg_104.mg_1col`` end to end at a small grid on the CPU, through the
functions the chip run uses: the sound run is correct and reports the
cell's metrics; plain CG fails ``trips_max``, the bfloat16 control fails
``residual_max``, an answer left at x = 0 fails too."""
import json

from bench import harness
from bench.tests import cpu_cell

CELL = "hpcg_104.mg_1col"
# an 8^3 grid over 3 levels (8, 4, 2): the shapes of the configuration
# (27-point stencil, halved grids, 8 colors), at a size the CPU compiles
# in seconds
TINY = {"config": {"generator_params": {"nx": 8, "ny": 8, "nz": 8,
                                        "levels": 3}, "n": 512}}


def _plain_cg():
    config = dict(TINY["config"])
    config["service"] = {"precondition": None}
    return {"config": config}


def test_sound_run_is_correct_and_reports_its_metrics(monkeypatch):
    r = cpu_cell.run(CELL, overrides=TINY, patch=monkeypatch.setattr)
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == {"residual_max", "trips_max"}
    assert r["attempted"] >= 1 and r["failed"] == 0
    cell = harness.load_cell(harness.ROOT, CELL)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert r["diagnostics"]["compiles_in_window"] == 0
    json.dumps(r)


def test_plain_cg_fails_trips_max(monkeypatch):
    r = cpu_cell.run(CELL, overrides=_plain_cg(), patch=monkeypatch.setattr)
    checks = r["checks"]
    assert not r["correct"]
    assert checks["residual_max"]["value"] <= checks["residual_max"]["limit"]
    assert checks["trips_max"]["value"] > checks["trips_max"]["limit"]


def test_bfloat16_control_fails_residual_max(monkeypatch):
    r = cpu_cell.run(CELL, overrides=TINY, control=True,
                     patch=monkeypatch.setattr)
    assert not r["correct"]
    assert (r["checks"]["residual_max"]["value"]
            > r["checks"]["residual_max"]["limit"])


def test_unchanged_answer_is_not_correct(monkeypatch):
    cpu_cell._break_solve(monkeypatch.setattr, cpu_cell._unchanged)
    r = cpu_cell.run(CELL, overrides=TINY, patch=monkeypatch.setattr)
    assert not r["correct"], r["checks"]


def test_program_sizes_pass_the_roofline_cross_check():
    """The level sizes the kind reads off the program's hierarchy are the
    ones ``mg_roofline.solve`` accepts for the configuration's grid."""
    import jax

    cell = harness.load_cell(harness.ROOT, CELL, TINY)
    kind = harness.load_module(harness.ROOT / "bench" / "kinds"
                               / "mg_solve.py")
    metric = harness.load_module(harness.ROOT / "bench" / "metrics"
                                 / "mg_roofline.solve.py")
    inputs = harness.load_inputs(harness.ROOT, cell.config)
    system = kind.setup(cell.config, cell.traffic, inputs, jax.devices(), 1)
    sizes = system.sizes()
    assert sizes["levels"] == metric.expected_sizes(
        cell.config["generator_params"])
    assert sizes["colors"] == [8, 8, 8]
