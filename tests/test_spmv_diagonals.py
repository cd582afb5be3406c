"""The ``coo`` backend's diagonal layout (``spmv.diagonal_offsets`` /
``csr_to_diagonals`` / ``spmv_diagonals``) and the choice between it and
the row groups, made from the CSR alone.  Held to the padded-COO
scatter-add (``spmv_coo``) and to a dense NumPy product, on HPCG's
27-point pattern with seeded random values (no layout may read the
values as constant), with and without row blocks."""
import jax
import numpy as np
import pytest

from repro.sparse import CooOperator
from repro.sparse.generators import stencil27
from repro.sparse.spmv import (MAX_DIAGONALS, MIN_FILL, csr_diagonal,
                               csr_to_padded_coo, dense_from_coo,
                               diagonal_offsets, spmv_coo)
from test_spmv_grouped import _delaunay, _primitives


def _parity(nx, ny, nz):
    z, y, x = np.unravel_index(np.arange(nx * ny * nz), (nz, ny, nx))
    return x % 2 + 2 * (y % 2) + 4 * (z % 2)


def _check(op, csr, nb):
    n = len(csr[0]) - 1
    shape = (n,) if nb is None else (n, nb)
    x = np.random.default_rng(1).normal(size=shape).astype(np.float32)
    y = op.gather(op.matvec(op.scatter(x)))
    rows, cols, vals = csr_to_padded_coo(*csr)
    a = dense_from_coo(rows, cols, vals, n)
    # float32 sums of up to 27 products in another order: each entry is
    # within 27 eps of the exact sum of its products' magnitudes
    bound = 27 * np.finfo(np.float32).eps * (np.abs(a) @ np.abs(x)) + 1e-30
    assert y.dtype == np.float32 and y.shape == shape
    np.testing.assert_array_less(np.abs(y - a @ x), bound)
    ref = np.asarray(spmv_coo(rows, cols, vals, x, n=n))
    np.testing.assert_array_less(np.abs(y - ref), 2 * bound)


@pytest.mark.parametrize("nb", [None, 1, 4])
@pytest.mark.parametrize("blocked", [False, True])
def test_diagonal_matvec_equals_the_csr_matvec(nb, blocked):
    csr = stencil27(6, 4, 8, seed=11)
    op = CooOperator.from_csr(*csr, blocks=_parity(6, 4, 8) if blocked
                              else None)
    assert op.layout == "diagonals" and op.groups == 0
    # 27 offsets in natural order; by the parity blocks of an even grid
    # (each a 3 x 2 x 4 grid), 27 in each of 8
    assert op.diagonals == (8 if blocked else 1) * 27
    assert (op.perm is None) != blocked
    _check(op, csr, nb)


def test_diagonal_layout_reads_the_stored_values():
    """Values are data: two matrices on one pattern give two products."""
    a, b = stencil27(4, 4, 4), stencil27(4, 4, 4, seed=3)
    x = np.ones(64, np.float32)
    ya = CooOperator.from_csr(*a).matvec(x)
    yb = CooOperator.from_csr(*b).matvec(x)
    assert not np.allclose(ya, yb)
    _check(CooOperator.from_csr(*b), b, None)


def test_diagonal_diag_and_blocks():
    csr = stencil27(6, 6, 2, seed=5)
    op = CooOperator.from_csr(*csr, blocks=_parity(6, 6, 2))
    np.testing.assert_array_equal(op.gather(op.diag()), csr_diagonal(*csr))
    x = np.random.default_rng(2).normal(size=(op.n, 3)).astype(np.float32)
    xo = op.scatter(x)
    lo, hi = op.pad
    xp = np.pad(np.asarray(xo), ((lo, hi), (0, 0)))
    full = np.asarray(op.matvec(xo))
    for b in range(len(op.row_blocks) - 1):
        s, e = op.row_blocks[b], op.row_blocks[b + 1]
        np.testing.assert_allclose(np.asarray(op.block_rows(b, xp)),
                                   full[s:e], rtol=1e-6, atol=1e-5)


def test_the_choice_follows_offsets_and_fill():
    csr = stencil27(8, 8, 8)
    offsets = diagonal_offsets(*csr[:2])
    assert len(offsets) == 1 and len(offsets[0]) == 27 <= MAX_DIAGONALS
    fill = len(csr[1]) / (27 * 512)          # 22^3 / (27 * 8^3) = 0.77
    assert fill >= MIN_FILL
    # an odd grid by parity blocks: the blocks differ in shape, so each
    # holds hundreds of offsets; it keeps the row groups
    op = CooOperator.from_csr(*stencil27(5, 5, 5), blocks=_parity(5, 5, 5))
    assert op.layout == "row groups" and op.diagonals == 0


def test_delaunay_keeps_row_groups():
    indptr, indices, data = _delaunay(10)
    assert diagonal_offsets(indptr, indices) is None
    assert CooOperator.from_csr(indptr, indices, data).layout == "row groups"


def test_delaunay_n20_keeps_row_groups():
    """The benchmark's delaunay_n20 matrix, which ``solve_1col`` and
    ``solve_batched`` serve: its offsets j - i number in the hundreds of
    thousands, far past MAX_DIAGONALS."""
    indptr, indices, _ = _delaunay(20)
    assert len(indptr) - 1 == 1 << 20
    assert diagonal_offsets(indptr, indices) is None


def test_batched_matvec_has_no_gather_on_diagonals():
    op = CooOperator.from_csr(*stencil27(4, 4, 4, seed=1))
    prims = set(_primitives(jax.make_jaxpr(op.matvec)(
        op.operand_spec(4)).jaxpr))
    assert "slice" in prims
    assert "gather" not in prims and not any(
        p.startswith("scatter") for p in prims), prims
