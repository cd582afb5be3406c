"""``hpcg_<n>``: HPCG 3.1's problem on one rank's ``nx x ny x nz`` grid.

``GenerateProblem``: the 27-point stencil, 26 on the diagonal and -1 for
each neighbour inside the grid, rows in lexicographic ``(z, y, x)``
order, built here as a Kronecker product of three 1-D patterns.
``GenerateCoarseProblem``, ``levels - 1`` times: the same stencil on the
grid halved in every dimension, and ``f2c``, which sends coarse point
``(i, j, k)`` to fine point ``(2i, 2j, 2k)``.

Returns the fine matrix as ``l_indptr`` (int64), ``l_indices`` (int32)
and ``l_data`` (float32), columns sorted, and each coarser level ``c``
(1 = the first below the fine one) as ``c<c>_indptr``, ``c<c>_indices``,
``c<c>_data`` and ``c<c>_f2c`` (int32).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def stencil(nx: int, ny: int, nz: int):
    """CSR ``(indptr, indices, data)`` of the 27-point matrix."""
    def line(m):
        return sp.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(m, m))

    pattern = sp.kron(line(nz), sp.kron(line(ny), line(nx)), format="csr")
    a = (27.0 * sp.identity(nx * ny * nz, format="csr") - pattern).tocsr()
    a.sort_indices()
    return (a.indptr.astype(np.int64), a.indices.astype(np.int32),
            a.data.astype(np.float32))


def make(nx: int, ny: int, nz: int, levels: int) -> dict[str, np.ndarray]:
    out = dict(zip(("l_indptr", "l_indices", "l_data"), stencil(nx, ny, nz)))
    for c in range(1, levels):
        if nx % 2 or ny % 2 or nz % 2:
            raise ValueError(f"cannot halve a {nx}x{ny}x{nz} grid")
        fine_nx, fine_ny = nx, ny
        nx, ny, nz = nx // 2, ny // 2, nz // 2
        k, j, i = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                              indexing="ij")
        out[f"c{c}_f2c"] = ((2 * k * fine_ny + 2 * j) * fine_nx
                            + 2 * i).ravel().astype(np.int32)
        for key, a in zip(("indptr", "indices", "data"), stencil(nx, ny, nz)):
            out[f"c{c}_{key}"] = a
    return out
