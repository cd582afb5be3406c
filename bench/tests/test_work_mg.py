"""``bench/work_mg.py`` against a hand count at a 16^3 grid, and the
``mg_roofline.solve`` reader on made-up runs."""
import types

import numpy as np

from bench import harness, work_mg

# HPCG's hierarchy over 16^3: rows g^3, stored entries (3g - 2)^3
SIZES_16 = [(4096, 97336), (512, 10648), (64, 1000), (8, 64)]


def test_trip_bytes_match_a_hand_count_at_16():
    # matrix passes: fine 6 (CG matvec, 2 pre sweeps, residual, 2 post),
    # middle levels 5, coarsest 2:
    # 6 * 97336 + 5 * 10648 + 5 * 1000 + 2 * 64 = 642,384 values
    # vectors: 5 at the fine level (x, r, p, Ap, z), 2 a coarser level:
    # 5 * 4096 + 2 * (512 + 64 + 8) = 21,648 a column
    assert work_mg.passes(4) == [6, 5, 5, 2]
    assert work_mg.trip_bytes(SIZES_16, 1, 4) == (642_384 + 21_648) * 4
    assert work_mg.trip_bytes(SIZES_16, 3, 4) == (642_384 + 3 * 21_648) * 4
    assert work_mg.trip_bytes(SIZES_16, 1, 8) == (642_384 + 21_648) * 8


def _run(sizes, params, busy_s=1.0, trips=10):
    mod = harness.load_module(harness.ROOT / "bench" / "metrics"
                              / "mg_roofline.solve.py")
    cell = types.SimpleNamespace(config={"dtype": "float32",
                                         "generator_params": params})
    run = types.SimpleNamespace(
        cell=cell, trace=types.SimpleNamespace(busy_s=busy_s),
        sizes={"n": sizes[0][0], "levels": [list(s) for s in sizes]},
        requests=[{"trips": trips, "units": 1}] * 2,
        device_kind="TPU v5 lite", chips=1)
    return mod.read(run)


def test_roofline_reads_the_program_sizes_checked_against_the_grid():
    params = {"nx": 16, "ny": 16, "nz": 16, "levels": 4}
    value = _run(SIZES_16, params)
    least = 2 * 10 * work_mg.trip_bytes(SIZES_16, 1, 4)
    assert np.isclose(value, 100 * least / 819e9)
    # sizes that disagree with the grid, or none at all: no reading
    assert _run(SIZES_16[:3], params) is None
    assert _run([(4096, 97335)] + SIZES_16[1:], params) is None
    assert _run(SIZES_16, {"log2n": 20}) is None
