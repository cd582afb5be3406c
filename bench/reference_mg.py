"""A plain float64 MG-PCG, the reference that decides ``trips_max``.  It
imports nothing of the program and takes nothing it made.

The same V-cycle as HPCG's ``ComputeMG`` with 1 pre- and 1 post-smoothing
symmetric Gauss-Seidel step a level and one on the coarsest, injection at
the ``f2c`` points, and the multicolor order the program states: a level
of grid ``(nx, ny, nz)`` is colored by parity, ``(x % 2) + 2 (y % 2) +
4 (z % 2)``, and each symmetric step updates the colors 0..7 and then
7..0, a color's rows at once.  CG is HPCG's, from x = 0, stopping when
``||r|| <= tol ||b||``; a trip is one matvec and one V-cycle.

:func:`trips` spreads the columns over processes, one a core at most
(SciPy's sparse products hold the interpreter lock), from matrices it
leaves on disk for them.
"""
from __future__ import annotations

import multiprocessing
import os
import tempfile

import numpy as np
import scipy.sparse as sp


def _level(indptr, indices, data, dims):
    n = len(indptr) - 1
    a = sp.csr_matrix((np.asarray(data, np.float64), indices, indptr),
                      shape=(n, n))
    nx, ny, nz = dims
    z, y, x = np.unravel_index(np.arange(n), (nz, ny, nx))
    color = x % 2 + 2 * (y % 2) + 4 * (z % 2)
    d = a.diagonal()
    sweeps = []
    for c in range(8):
        rows = np.flatnonzero(color == c)
        sweeps.append((rows, a[rows], 1.0 / d[rows]))
    return a, sweeps


def hierarchy(fine, coarse, dims) -> list:
    """Per level ``(A, sweeps, f2c)``: ``fine`` the CSR ``(indptr,
    indices, data)``, ``coarse`` the coarser levels' ``(indptr, indices,
    data, f2c)``, ``dims`` the fine grid's ``(nx, ny, nz)``."""
    out = []
    f2c = None
    for lvl, csr in enumerate([tuple(fine)] + [c[:3] for c in coarse]):
        a, sweeps = _level(*csr, tuple(d >> lvl for d in dims))
        out.append((a, sweeps, f2c))
        f2c = coarse[lvl][3] if lvl < len(coarse) else None
    return out


def symgs(level, r, x):
    _, sweeps, _ = level
    for rows, a_rows, dinv in sweeps + sweeps[::-1]:
        x[rows] += (r[rows] - a_rows @ x) * dinv.reshape(
            dinv.shape + (1,) * (x.ndim - 1))
    return x


def vcycle(levels, r):
    a = levels[0][0]
    x = symgs(levels[0], r, np.zeros_like(r))
    if len(levels) == 1:
        return x
    f2c = levels[1][2]
    x[f2c] += vcycle(levels[1:], (r - a @ x)[f2c])
    return symgs(levels[0], r, x)


def mgpcg(levels, b, tol: float, max_iters: int):
    """``(x, trips)`` for the columns of ``b`` ((n,) or (n, m)), each
    its own CG: a column stops (and its trips with it) once its residual
    is within tol; trips is an (m,) array, or an int for (n,)."""
    a = levels[0][0]
    b = np.asarray(b, np.float64)
    b2 = b.reshape(len(b), -1)
    x = np.zeros_like(b2)
    r = b2.copy()
    stop = tol * np.linalg.norm(b2, axis=0)
    trips = np.zeros(b2.shape[1], np.int64)
    rz, p = np.ones(b2.shape[1]), np.zeros_like(b2)
    while True:
        live = (np.linalg.norm(r, axis=0) > stop) & (trips < max_iters)
        if not live.any():
            break
        z = vcycle(levels, r)
        rz, old = np.sum(r * z, axis=0), rz
        p = np.where(trips > 0, z + (rz / old) * p, z)
        ap = a @ p
        alpha = np.where(live, rz / np.sum(p * ap, axis=0), 0.0)
        x += alpha * p
        r -= alpha * ap
        trips += live
    if b.ndim == 1:
        return x[:, 0], int(trips[0])
    return x, trips


def _solve_columns(folder, keys, dims, cols, tol, max_iters):
    arrays = {k: np.load(os.path.join(folder, k + ".npy"), mmap_mode="r")
              for k in keys}
    coarse = []
    while f"c{len(coarse) + 1}_f2c" in arrays:
        c = f"c{len(coarse) + 1}_"
        coarse.append(tuple(arrays[c + k] for k in
                            ("indptr", "indices", "data", "f2c")))
    fine = tuple(arrays["l_" + k] for k in ("indptr", "indices", "data"))
    b = np.array(arrays["b"][:, cols])
    return mgpcg(hierarchy(fine, coarse, dims), b, tol, max_iters)[1]


def trips(inputs: dict, dims, b: np.ndarray, tol: float,
          max_iters: int) -> list[int]:
    """The reference's trips for each column of ``b`` (n, m), on the
    matrices of ``inputs`` (the ``bench/inputs/hpcg.py`` keys) over a
    fine grid ``dims``: the columns split over one process a core, at
    most one a column (8 columns at 64^3 on 8 cores: 7.3 s, against
    22.2 s in one process)."""
    keys = [k for k in inputs if k.startswith(("l_", "c"))] + ["b"]
    parts = np.array_split(np.arange(b.shape[1]),
                           min(b.shape[1], os.cpu_count() or 1))
    with tempfile.TemporaryDirectory(prefix="reference-mg-") as folder:
        for k in keys:
            np.save(os.path.join(folder, k + ".npy"),
                    b if k == "b" else inputs[k])
        ctx = multiprocessing.get_context("spawn")
        with ctx.Pool(len(parts)) as pool:
            out = pool.starmap(_solve_columns, [
                (folder, keys, tuple(dims), part.tolist(), tol, max_iters)
                for part in parts])
    return [int(t) for t in np.concatenate(out)]
