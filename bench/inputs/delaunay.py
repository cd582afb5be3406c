"""``delaunay_n<log2n>``: Delaunay triangulation of 2^log2n uniform points.

The DIMACS10 ``delaunay_n*`` family (the paper's Sec. VI meshes): points
uniform in the unit square, made from the seed, triangulated by SciPy.
Returns the graph as symmetric CSR adjacency with the points, and its
Laplacian with the diagonal shifted by ``shift`` (Sec. VI-a shifts it
"slightly") as float32 CSR with rows sorted by column.
"""
from __future__ import annotations

import numpy as np
from scipy.spatial import Delaunay


def _csr(n: int, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """CSR (indptr int64, indices int32) of sorted ``row * n + col`` keys."""
    rows, cols = np.divmod(keys, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return indptr, cols.astype(np.int32)


def make(seed: int, log2n: int, shift: float) -> dict[str, np.ndarray]:
    n = 1 << log2n
    pts = np.random.default_rng(seed).random((n, 2))
    tri = Delaunay(pts).simplices.astype(np.int64)
    e = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    e.sort(axis=1)
    und = np.unique(e[:, 0] * n + e[:, 1])
    u, v = np.divmod(und, n)
    adj = np.sort(np.concatenate([u * n + v, v * n + u]))
    indptr, indices = _csr(n, adj)
    deg = np.diff(indptr).astype(np.float64)
    diag = np.arange(n, dtype=np.int64) * (n + 1)
    keys = np.concatenate([adj, diag])
    vals = np.concatenate([-np.ones(len(adj)), deg + shift])
    order = np.argsort(keys, kind="stable")
    l_indptr, l_indices = _csr(n, keys[order])
    return {"indptr": indptr, "indices": indices,
            "coords": pts.astype(np.float32),
            "l_indptr": l_indptr, "l_indices": l_indices,
            "l_data": vals[order].astype(np.float32)}
