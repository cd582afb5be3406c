"""Graph generators mirroring the paper's instances (Table II).

  * rgg_2d / rgg_3d — random geometric graphs (KaGen-style): n points uniform
    in the unit square/cube, edge iff dist <= r, r chosen for avg degree ~6.
  * rdg_2d — random Delaunay triangulation graphs.
  * grid_2d / grid_3d — structured meshes (stand-in for the DIMACS hugeX
    triangle meshes, same family: planar, bounded degree).
  * aniso_grid — grid with direction-dependent edge weights (anisotropic
    diffusion; the block-Jacobi preconditioner's model problem).
  * refined_mesh — adaptively refined triangular mesh (refinetrace family):
    start from a coarse Delaunay mesh and refine cells near an attractor
    curve, giving strongly non-uniform density.
  * stencil27 / stencil27_levels — HPCG's matrix (``GenerateProblem``)
    and its multigrid hierarchy (``GenerateCoarseProblem``), as CSR.

The graph generators are deterministic given seed and return Graph with
coords; the stencil generators return CSR arrays.
"""
from __future__ import annotations

import numpy as np
from scipy.spatial import Delaunay, cKDTree

from .graph import Graph, from_edges


def rgg(n: int, dim: int = 2, avg_degree: float = 6.0,
        seed: int = 0) -> Graph:
    """Random geometric graph in [0,1]^dim with expected avg degree."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, dim)).astype(np.float32)
    # avg_degree = n * V_d(r)  =>  r = (avg_degree / (n c_d))^(1/d)
    c_d = {1: 2.0, 2: np.pi, 3: 4.0 * np.pi / 3.0}[dim]
    r = (avg_degree / (n * c_d)) ** (1.0 / dim)
    tree = cKDTree(pts)
    pairs = tree.query_pairs(r, output_type="ndarray")
    if len(pairs) == 0:
        pairs = np.zeros((0, 2), dtype=np.int64)
    return from_edges(n, pairs[:, 0], pairs[:, 1], symmetrize=True,
                      coords=pts)


def rdg(n: int, seed: int = 0) -> Graph:
    """Random Delaunay graph: Delaunay triangulation of uniform points."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2)).astype(np.float64)
    tri = Delaunay(pts)
    edges = _tri_edges(tri.simplices)
    return from_edges(n, edges[:, 0], edges[:, 1], symmetrize=True,
                      coords=pts.astype(np.float32))


def _tri_edges(simplices: np.ndarray) -> np.ndarray:
    e = np.concatenate([simplices[:, [0, 1]], simplices[:, [1, 2]],
                        simplices[:, [0, 2]]])
    e.sort(axis=1)
    return np.unique(e, axis=0)


def grid(shape: tuple[int, ...]) -> Graph:
    """Structured grid mesh (2D or 3D), 4/6-point stencil."""
    dims = len(shape)
    n = int(np.prod(shape))
    idx = np.arange(n).reshape(shape)
    src, dst = [], []
    for axis in range(dims):
        a = np.take(idx, np.arange(shape[axis] - 1), axis=axis).ravel()
        b = np.take(idx, np.arange(1, shape[axis]), axis=axis).ravel()
        src.append(a)
        dst.append(b)
    src, dst = np.concatenate(src), np.concatenate(dst)
    coords = np.stack(np.unravel_index(np.arange(n), shape),
                      axis=1).astype(np.float32)
    coords /= np.maximum(1, np.array(shape, dtype=np.float32) - 1)
    return from_edges(n, src, dst, symmetrize=True, coords=coords)


def aniso_grid(shape: tuple[int, ...], weights: tuple[float, ...] = None,
               eps: float = 0.01) -> Graph:
    """Structured grid with direction-dependent edge weights — the
    anisotropic-diffusion model problem.  ``weights[d]`` is the coupling
    along axis d (default ``(1, eps, eps, ...)``: strong along axis 0).
    Its shifted Laplacian is the classic case where point-Jacobi stalls
    but per-block preconditioners that keep whole strong lines inside a
    block (e.g. axis-0 stripes + block-Jacobi) stay effective.
    """
    dims = len(shape)
    if weights is None:
        weights = (1.0,) + (eps,) * (dims - 1)
    n = int(np.prod(shape))
    idx = np.arange(n).reshape(shape)
    src, dst, w = [], [], []
    for axis in range(dims):
        a = np.take(idx, np.arange(shape[axis] - 1), axis=axis).ravel()
        b = np.take(idx, np.arange(1, shape[axis]), axis=axis).ravel()
        src.append(a)
        dst.append(b)
        w.append(np.full(len(a), weights[axis], dtype=np.float32))
    src, dst, w = (np.concatenate(src), np.concatenate(dst),
                   np.concatenate(w))
    coords = np.stack(np.unravel_index(np.arange(n), shape),
                      axis=1).astype(np.float32)
    coords /= np.maximum(1, np.array(shape, dtype=np.float32) - 1)
    return from_edges(n, src, dst, w, symmetrize=True, coords=coords)


def refined_mesh(n_coarse: int = 2000, refine_rounds: int = 3,
                 seed: int = 0) -> Graph:
    """Adaptive mesh a la 'refinetrace': density concentrates near a moving
    front (a circle arc), produced by iterative point insertion + re-Delaunay.
    """
    rng = np.random.default_rng(seed)
    pts = rng.random((n_coarse, 2))
    center = np.array([0.5, 0.5])
    for _ in range(refine_rounds):
        d = np.abs(np.linalg.norm(pts - center, axis=1) - 0.3)
        hot = pts[d < 0.08]
        if len(hot) == 0:
            break
        jitter = rng.normal(scale=0.01, size=(len(hot), 2))
        pts = np.concatenate([pts, np.clip(hot + jitter, 0, 1)])
    pts = np.unique(np.round(pts, 7), axis=0)
    tri = Delaunay(pts)
    edges = _tri_edges(tri.simplices)
    return from_edges(len(pts), edges[:, 0], edges[:, 1], symmetrize=True,
                      coords=pts.astype(np.float32))


# the 27 offsets (dz, dy, dx) of the stencil, in the order of their linear
# offsets, so that each row's columns come out sorted
_STENCIL27 = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
              for dx in (-1, 0, 1)]


def stencil27(nx: int, ny: int, nz: int, seed: int | None = None):
    """HPCG's ``GenerateProblem`` matrix on an ``nx x ny x nz`` grid as CSR
    ``(indptr, indices, data)``: row ``(z * ny + y) * nx + x`` (lexicographic
    ``(z, y, x)``) holds 26 on the diagonal and -1 for each of its up to 26
    neighbours inside the grid, columns sorted, data float32.

    ``seed`` draws other values on the same pattern for tests: symmetric
    off-diagonals in [-1.5, -0.5] and a diagonal that exceeds its row's
    absolute off-diagonal sum by a value in [0.5, 1.5], so the matrix
    stays symmetric positive definite and no layout can rely on constant
    values."""
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    z, rem = np.divmod(idx, nx * ny)
    y, x = np.divmod(rem, nx)
    ok = np.empty((n, 27), bool)
    cols = np.empty((n, 27), np.int64)
    for k, (dz, dy, dx) in enumerate(_STENCIL27):
        ok[:, k] = ((0 <= x + dx) & (x + dx < nx) & (0 <= y + dy)
                    & (y + dy < ny) & (0 <= z + dz) & (z + dz < nz))
        cols[:, k] = idx + (dz * ny + dy) * nx + dx
    if seed is None:
        vals = np.full((n, 27), -1.0)
        vals[:, 13] = 26.0
    else:
        rng = np.random.default_rng(seed)
        vals = np.zeros((n, 27))
        for k in range(13):          # below the diagonal, mirrored above
            v = -rng.uniform(0.5, 1.5, n)
            vals[:, k] = v
            rows = idx[ok[:, k]]
            vals[cols[rows, k], 26 - k] = v[rows]
        vals[:, 13] = (np.abs(np.where(ok, vals, 0)).sum(axis=1)
                       + rng.uniform(0.5, 1.5, n))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(ok.sum(axis=1), out=indptr[1:])
    return indptr, cols[ok].astype(np.int32), vals[ok].astype(np.float32)


def stencil27_levels(nx: int, ny: int, nz: int, depth: int = 4,
                     seed: int | None = None):
    """HPCG's multigrid hierarchy (``GenerateCoarseProblem``): the fine
    ``stencil27`` matrix and, for each of the ``depth - 1`` coarser levels,
    ``(indptr, indices, data, f2c)``: ``stencil27`` on the grid halved in
    every dimension, and ``f2c`` (int32), which sends coarse point
    ``(i, j, k)`` to fine point ``(2i, 2j, 2k)`` of the level above.
    ``seed`` draws each level's values as :func:`stencil27` does."""
    fine = stencil27(nx, ny, nz, seed)
    levels = []
    for lvl in range(1, depth):
        if nx % 2 or ny % 2 or nz % 2:
            raise ValueError(f"level {lvl}: cannot halve a {nx}x{ny}x{nz} "
                             f"grid")
        cx, cy, cz = nx // 2, ny // 2, nz // 2
        z, y, x = np.unravel_index(np.arange(cx * cy * cz), (cz, cy, cx))
        f2c = ((2 * z * ny + 2 * y) * nx + 2 * x).astype(np.int32)
        levels.append(stencil27(cx, cy, cz, None if seed is None
                                else seed + lvl) + (f2c,))
        nx, ny, nz = cx, cy, cz
    return fine, levels


GENERATORS = {
    "rgg_2d": lambda n, seed=0: rgg(n, 2, seed=seed),
    "rgg_3d": lambda n, seed=0: rgg(n, 3, seed=seed),
    "rdg_2d": lambda n, seed=0: rdg(n, seed=seed),
    "grid_2d": lambda n, seed=0: grid((int(np.sqrt(n)),) * 2),
    "grid_3d": lambda n, seed=0: grid((max(2, round(n ** (1 / 3))),) * 3),
    "refined": lambda n, seed=0: refined_mesh(n, seed=seed),
}
