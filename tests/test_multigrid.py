"""HPCG's multigrid preconditioner (``sparse/multigrid.py``) on the ``coo``
operator, held to the plain float64 MG-PCG of ``bench/reference_mg.py``
(SciPy, importing nothing of the program), on HPCG's 27-point hierarchy
with seeded random values: the coloring, one V-cycle, MG-PCG's trip
counts, batched against single-column solves, and the service path."""
import functools

import jax
import numpy as np
import pytest

from bench import reference_mg as ref
from repro.launch.serve import SolverService
from repro.sparse import CooOperator, cg_solve
from repro.sparse.generators import stencil27, stencil27_levels
from repro.sparse.multigrid import greedy_colors

from test_spmv_diagonals import _parity

SEED = 7


def _hierarchy(g, depth=4):
    fine, levels = stencil27_levels(g, g, g, depth=depth, seed=SEED)
    return fine, levels, ref.hierarchy(fine, levels, (g, g, g))


@pytest.fixture(scope="module")
def h16():
    fine, levels, href = _hierarchy(16)
    return fine, levels, href, CooOperator.from_csr(*fine, levels=levels)


@pytest.mark.parametrize("dims", [(16, 16, 16), (6, 5, 7), (13, 13, 13)])
def test_greedy_colors_are_the_parity_classes(dims):
    """First-fit in row order gives the 27-point stencil 8 colors, and
    they are the parity classes the reference uses, on odd grids too."""
    indptr, indices, _ = stencil27(*dims, seed=1)
    np.testing.assert_array_equal(greedy_colors(indptr, indices),
                                  _parity(*dims))


def test_greedy_colors_match_the_sequential_loop():
    """The rounds give what the row-by-row loop gives, on a Delaunay
    mesh, whose pattern is irregular."""
    from test_spmv_grouped import _delaunay

    indptr, indices, _ = _delaunay(10)
    colors = greedy_colors(indptr, indices)
    loop = np.full(len(indptr) - 1, -1)
    for i in range(len(loop)):
        nb = indices[indptr[i]:indptr[i + 1]]
        used = set(loop[nb[nb < i]].tolist())
        loop[i] = min(set(range(len(used) + 1)) - used)
    np.testing.assert_array_equal(colors, loop)


def test_greedy_colors_refuse_an_unsymmetric_pattern():
    indptr = np.array([0, 2, 3, 4])
    indices = np.array([0, 1, 1, 2])          # (0, 1) without (1, 0)
    with pytest.raises(ValueError, match="symmetric"):
        greedy_colors(indptr, indices)


def test_hierarchy_levels_and_layouts(h16):
    _, levels, _, op = h16
    assert [o.n for o in (op,) + op.mg.ops] == [4096, 512, 64, 8]
    assert op.mg.sizes == ((4096, 46 ** 3), (512, 22 ** 3), (64, 10 ** 3),
                           (8, 4 ** 3))
    assert op.mg.colors == (8, 8, 8, 8)
    # even grids by parity blocks: 27 diagonals in each of 8 blocks
    assert op.layout == "diagonals" and op.diagonals == 216
    # f2c in operator order: the coarse points' fine rows are the fine
    # level's first color block, the points with even coordinates
    f2c = np.asarray(op.mg.f2c[0])
    assert sorted(f2c) == list(range(512))


@pytest.mark.parametrize("g, depth", [(16, 4), (24, 4), (20, 3)])
def test_one_vcycle_matches_the_float64_reference(g, depth):
    """At 20^3 over 3 levels the coarsest (5^3) is an odd grid whose
    colors fail the diagonal layout's test: it keeps the row groups, so
    both layouts run in one V-cycle."""
    fine, levels, href = _hierarchy(g, depth)
    op = CooOperator.from_csr(*fine, levels=levels)
    assert op.mg.ops[-1].layout == ("row groups" if g == 20
                                    else "diagonals")
    r = np.random.default_rng(g).standard_normal(g ** 3).astype(np.float32)
    apply = jax.jit(lambda o, v: o.mg_preconditioner()(v))
    z = op.gather(apply(op, op.scatter(r)))
    zr = ref.vcycle(href, r.astype(np.float64))
    # float32 against float64 through the same color updates, matvecs
    # and injections: the V-cycle damps what each step rounds, and the
    # three cases read 0.69-0.72 float32 eps of |z|; 16 eps leaves 20x
    # room, and a bfloat16 V-cycle (eps 2^-8, 32768 float32 eps) fails it
    rel = np.linalg.norm(z - zr) / np.linalg.norm(zr)
    assert rel < 16 * np.finfo(np.float32).eps, rel


@pytest.fixture(scope="module")
def solved16(h16):
    fine, levels, href, op = h16
    b = np.random.default_rng(3).standard_normal((op.n, 3)).astype(
        np.float32)
    solve = jax.jit(functools.partial(cg_solve, tol=1e-6, max_iters=200,
                                      precondition="mg", batched=True))
    res = solve(op, op.scatter(b))
    return b, op.gather(res.x), np.asarray(res.iters)


def test_mg_pcg_trips_match_the_reference(h16, solved16):
    fine, _, href, _ = h16
    b, x, iters = solved16
    for j in range(b.shape[1]):
        _, trips = ref.mgpcg(href, b[:, j], 1e-6, 200)
        # float32 against float64: the last trip's residual can land on
        # either side of tol, so one trip either way
        assert abs(int(iters[j]) - trips) <= 1, (j, iters[j], trips)
    a = href[0][0]
    rel = np.linalg.norm(b - a @ x, axis=0) / np.linalg.norm(b, axis=0)
    assert np.all(rel < 1e-5), rel          # 10 tol, as the cells check


def test_batched_columns_equal_single_column_solves(h16, solved16):
    _, _, _, op = h16
    b, x, iters = solved16
    solve = jax.jit(functools.partial(cg_solve, tol=1e-6, max_iters=200,
                                      precondition="mg"))
    for j in range(b.shape[1]):
        res = solve(op, op.scatter(b[:, j]))
        assert int(res.iters) == int(iters[j])
        # the same float32 arithmetic on one column or three: the
        # answers differ by rounding only, well inside CG's tol
        xj = op.gather(res.x)
        assert np.linalg.norm(xj - x[:, j]) <= 1e-5 * np.linalg.norm(xj)


def test_plain_operator_refuses_mg():
    op = CooOperator.from_csr(*stencil27(4, 4, 4))
    with pytest.raises(ValueError, match="levels="):
        op.mg_preconditioner()


def test_service_answers_with_mg_within_tol(tmp_path):
    """Width 1 and a batch of 3 through ``SolverService(precondition=
    'mg')``; admission records ``mg.build`` inside ``plan.build``, and a
    request stays at 7 spans."""
    from bench.tests.test_program_spans import record

    g = 8
    fine, levels = stencil27_levels(g, g, g, depth=3, seed=SEED)
    href = ref.hierarchy(fine, levels, (g, g, g))
    svc = SolverService(backend="coo", buckets=(1, 4), tol=1e-6,
                        max_iters=200, precondition="mg")
    b = np.random.default_rng(5).standard_normal((g ** 3, 3)).astype(
        np.float32)
    out = {}

    def admit():
        out["fp"], out["op"], _ = svc.operator_for(*fine, levels=levels)

    events = record(tmp_path / "admit", admit)
    names = [e[0] for e in events]
    assert names == ["repro.plan.build", "repro.mg.build"]
    (stats,) = [e[3] for e in events if e[0] == "repro.mg.build"]
    assert stats["levels"] == 3 and stats["colors"] == 8
    assert (stats["n0"], stats["nnz0"], stats["n2"]) == (512, 22 ** 3, 8)
    (build,) = [e[3] for e in events if e[0] == "repro.plan.build"]
    assert build["layout"] == "diagonals" and build["diagonals"] == 216

    for cols in (b[:, 0], b):
        resp = svc.solve(*fine, cols, fingerprint=out["fp"])
        x = resp.x.reshape(g ** 3, -1)
        a = href[0][0]
        bb = cols.reshape(g ** 3, -1).astype(np.float64)
        rel = np.linalg.norm(bb - a @ x, axis=0) / np.linalg.norm(bb, axis=0)
        assert np.all(rel < 1e-5), rel      # 10 tol, as the cells check
        assert np.all(resp.iters < 30)
    events = record(tmp_path / "solve",
                    lambda: svc.solve(*fine, b, fingerprint=out["fp"]))
    assert len(events) <= 8, [e[0] for e in events]
    assert "repro.mg.build" not in [e[0] for e in events]
