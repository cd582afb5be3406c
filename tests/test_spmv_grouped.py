"""The ``coo`` backend's row-group layout (``spmv.csr_to_row_groups`` /
``spmv_grouped``): rows grouped by length, each group's products summed
over a slot-major axis, no scatter.  Held to the padded-COO scatter-add
(``spmv_coo``) and to SciPy, on the benchmark's Delaunay meshes at small
sizes and on hand-made matrices with an empty row, a dense row and
float64 values."""
from pathlib import Path

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from repro.sparse import CooOperator, cg_solve_global
from repro.sparse.spmv import (MAX_GROUPS, csr_diagonal, csr_to_padded_coo,
                               row_groups, spmv_coo)

ROOT = Path(__file__).resolve().parents[1]

# The shifted Laplacian of the benchmark's delaunay_n20 mesh (2^20 uniform
# points of generator seed 1): row length (degree + 1) -> rows.  nnz
# 7,339,960; lengths 13-23 hold 445 rows and 6,113 entries, spread here
# as a plausible tail.
DELAUNAY_N20_ROW_LENGTHS = {
    3: 1, 4: 11760, 5: 112088, 6: 271887, 7: 309684, 8: 208468, 9: 93910,
    10: 30944, 11: 7788, 12: 1601, 13: 269, 14: 100, 15: 36, 16: 25, 17: 7,
    18: 3, 19: 2, 20: 1, 22: 1, 23: 1}


def _delaunay(log2n):
    """The benchmark's Delaunay Laplacian (seed 1, shift 0.1), from its
    input cache."""
    from bench import harness

    inputs = harness.load_inputs(ROOT, {
        "generator": "delaunay",
        "generator_params": {"seed": 1, "log2n": log2n, "shift": 0.1}})
    return inputs["l_indptr"], inputs["l_indices"], inputs["l_data"]


def _from_dense(a, dtype=np.float32):
    m = sp.csr_matrix(a)
    return m.indptr.astype(np.int64), m.indices.astype(np.int32), \
        m.data.astype(dtype)


def _empty_row():
    a = np.diag(np.arange(1.0, 7.0))
    a[0, 3] = a[3, 0] = -0.5
    a[2, 2] = 0.0                       # row 2 stores nothing
    return _from_dense(a)


def _dense_row():
    n = 40
    a = 4 * np.eye(n) - np.eye(n, k=1) - np.eye(n, k=-1)
    a[0, :] = np.linspace(-1.0, 1.0, n)
    a[0, 0] = 50.0                      # one row holds every column
    return _from_dense(a)


def _float64():
    rng = np.random.default_rng(7)
    a = sp.random(60, 60, density=0.1, random_state=8).toarray()
    a = a + a.T + 12 * np.eye(60) + rng.normal(size=(60, 60)) * (a != 0)
    return _from_dense(a, np.float64)


SYSTEMS = {
    "delaunay_n10": lambda: _delaunay(10),
    "delaunay_n14": lambda: _delaunay(14),
    "empty_row": _empty_row,
    "dense_row": _dense_row,
    "float64": _float64,
}


@pytest.mark.parametrize("nb", [None, 3])
@pytest.mark.parametrize("name", list(SYSTEMS))
def test_matvec_matches_spmv_coo_and_scipy(name, nb):
    with jax.enable_x64(name == "float64"):
        indptr, indices, data = SYSTEMS[name]()
        n = len(indptr) - 1
        A = sp.csr_matrix((data.astype(np.float64), indices, indptr),
                          shape=(n, n))
        shape = (n,) if nb is None else (n, nb)
        x = np.random.default_rng(1).normal(size=shape).astype(data.dtype)
        op = CooOperator.from_csr(indptr, indices, data)
        y = op.gather(op.matvec(op.scatter(x)))
        rows, cols, vals = csr_to_padded_coo(indptr, indices, data)
        ref = np.asarray(spmv_coo(rows, cols, vals, x, n=n))
    assert y.dtype == data.dtype and y.shape == shape
    rtol = 64 * np.finfo(data.dtype).eps
    scale = np.abs(A) @ np.abs(x)       # bounds each entry's rounding
    np.testing.assert_array_less(np.abs(y - ref), rtol * scale + 1e-30)
    np.testing.assert_array_less(np.abs(y - A @ x), rtol * scale + 1e-30)


@pytest.mark.parametrize("name", list(SYSTEMS))
def test_diag_equals_csr_diagonal(name):
    with jax.enable_x64(name == "float64"):
        indptr, indices, data = SYSTEMS[name]()
        op = CooOperator.from_csr(indptr, indices, data)
        d = op.gather(op.diag())
    np.testing.assert_array_equal(d, csr_diagonal(indptr, indices, data))


def test_diag_sums_duplicate_diagonal_entries():
    indptr = np.array([0, 2, 3, 5])
    indices = np.array([0, 0, 1, 2, 2])
    data = np.array([1.0, 2.0, 5.0, 0.5, 0.25], np.float32)
    op = CooOperator.from_csr(indptr, indices, data)
    np.testing.assert_array_equal(op.gather(op.diag()), [3.0, 5.0, 0.75])


@pytest.mark.parametrize("nb", [None, 3])
def test_scatter_then_gather_restores_the_order(nb):
    indptr, indices, data = _delaunay(10)
    n = len(indptr) - 1
    op = CooOperator.from_csr(indptr, indices, data)
    assert not np.array_equal(op.perm, np.arange(n))   # rows were moved
    x = np.random.default_rng(2).normal(
        size=(n,) if nb is None else (n, nb)).astype(np.float32)
    np.testing.assert_array_equal(op.gather(op.scatter(x)), x)


def test_grouping_rule_on_the_delaunay_n20_histogram():
    lengths = np.repeat(list(DELAUNAY_N20_ROW_LENGTHS),
                        list(DELAUNAY_N20_ROW_LENGTHS.values()))
    perm, counts, widths = row_groups(lengths)
    nnz = int(lengths.sum())
    assert nnz == 7_339_960 and sum(counts) == len(lengths) == 1 << 20
    pad_share = sum(c * w for c, w in zip(counts, widths)) / nnz - 1
    assert len(widths) <= MAX_GROUPS and pad_share < 0.001
    # the one row of length 3 joins the 4s; the 445 rows past 12 share one
    # group padded to 23: 4,123 padded slots
    assert widths == [4, 5, 6, 7, 8, 9, 10, 11, 12, 23]
    assert sum(c * w for c, w in zip(counts, widths)) - nnz == 4_123
    np.testing.assert_array_equal(np.diff(lengths[perm]) >= 0, True)


def test_grouping_caps_the_number_of_groups():
    """200 distinct lengths, each held by many rows: merges that add the
    fewest padded slots bring them down to MAX_GROUPS, and every row
    lies in a group at least as wide as it."""
    lengths = np.random.default_rng(3).integers(0, 200, size=50_000)
    perm, counts, widths = row_groups(lengths)
    assert len(widths) == MAX_GROUPS
    assert widths == sorted(widths) and sum(counts) == len(lengths)
    group_width = np.repeat(widths, counts)
    assert np.all(lengths[perm] <= group_width)


def test_batched_cg_answers_to_tol():
    indptr, indices, data = _delaunay(10)
    n = len(indptr) - 1
    A = sp.csr_matrix((data.astype(np.float64), indices, indptr),
                      shape=(n, n))
    b = np.random.default_rng(4).normal(size=(n, 4)).astype(np.float32)
    b[:, 3] = 0.0
    op = CooOperator.from_csr(indptr, indices, data)
    x, iters, res = cg_solve_global(op, b, tol=1e-6, max_iters=500)
    rel = (np.linalg.norm(A @ x - b, axis=0)
           / np.maximum(np.linalg.norm(b, axis=0), 1e-30))
    assert np.all(rel[:3] < 1e-5), rel
    assert np.all(iters[:3] < 500) and iters[3] == 0
    assert np.all(x[:, 3] == 0)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("nb", [None, 3])
def test_matvec_holds_no_scatter(nb):
    indptr, indices, data = _delaunay(10)
    op = CooOperator.from_csr(indptr, indices, data)
    prims = set(_primitives(jax.make_jaxpr(op.matvec)(
        op.operand_spec(nb)).jaxpr))
    assert "gather" in prims
    assert not any(p.startswith("scatter") for p in prims), prims


def test_plan_build_span_records_the_layout(tmp_path):
    """``plan.build`` carries the admitted layout's ``groups`` and
    ``pad_share`` as stats on the profiler's host plane."""
    from jax.profiler import ProfileData

    from repro.launch.serve import SolverService

    csr = _delaunay(10)
    svc = SolverService(backend="coo", buckets=(1,), tol=1e-6,
                        max_iters=200)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, op, _ = svc.operator_for(*csr)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    (stats,) = [dict(e.stats) for p in ProfileData.from_file(str(path)).planes
                for line in p.lines for e in line.events
                if e.name == "repro.plan.build"]
    assert stats["groups"] == op.groups == len(op.cols)
    assert stats["pad_share"] == pytest.approx(op.pad_share)
    assert 0 <= op.pad_share < 0.02
