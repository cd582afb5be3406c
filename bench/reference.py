"""Plain references that decide ``correct``.  Nothing here imports the
program or takes anything it made.

* ``residuals``: ``||b - A x|| / ||b||`` per column, in float64 (SciPy).
* ``waterfill``: Algorithm 1 of the paper (target block sizes for speeds
  under memory caps), straight from its pseudocode.
* ``sfc_partition`` / ``edge_cut``: a Morton-order split of the points at
  given block sizes, and the number of edges a partition cuts.
* ``EllCG``: CG on the matrix in ELL form, in a dtype of the caller's
  choice; in bfloat16 it is the control that must fail the float32 limit.
"""
from __future__ import annotations

import numpy as np


def residuals(indptr, indices, data, b: np.ndarray,
              x: np.ndarray) -> np.ndarray:
    """Per-column relative residual of ``x`` for ``A x = b`` in float64."""
    import scipy.sparse as sp

    n = len(indptr) - 1
    a = sp.csr_matrix((np.asarray(data, np.float64), indices, indptr),
                      shape=(n, n))
    b2 = np.asarray(b, np.float64).reshape(n, -1)
    r = b2 - a @ np.asarray(x, np.float64).reshape(n, -1)
    return np.linalg.norm(r, axis=0) / np.linalg.norm(b2, axis=0)


def waterfill(load: float, speeds, caps) -> np.ndarray:
    """Algorithm 1: visit PUs by decreasing speed / memory, give each its
    speed's share of the load still unplaced, capped at its memory."""
    speeds = np.asarray(speeds, np.float64)
    caps = np.asarray(caps, np.float64)
    if load > caps.sum():
        raise ValueError(f"load {load} exceeds total memory {caps.sum()}")
    tw = np.zeros(len(speeds))
    rest_load, rest_speed = float(load), float(speeds.sum())
    for i in np.argsort(-(speeds / caps), kind="stable"):
        tw[i] = min(speeds[i] * rest_load / rest_speed, caps[i])
        rest_load -= tw[i]
        rest_speed -= speeds[i]
    return tw


def _spread_bits(v: np.ndarray) -> np.ndarray:
    """Insert a zero bit above each of the low 16 bits of ``v``."""
    v = v.astype(np.uint64) & 0xFFFF
    v = (v | (v << 8)) & 0x00FF00FF
    v = (v | (v << 4)) & 0x0F0F0F0F
    v = (v | (v << 2)) & 0x33333333
    return (v | (v << 1)) & 0x55555555


def sfc_partition(coords: np.ndarray, sizes) -> np.ndarray:
    """Blocks of ``sizes`` (rounded, summing to n) along the Morton order
    of 2-D points."""
    c = np.asarray(coords, np.float64)
    lo, hi = c.min(axis=0), c.max(axis=0)
    q = ((c - lo) / np.where(hi > lo, hi - lo, 1.0) * 0xFFFF).astype(
        np.uint64)
    order = np.argsort(_spread_bits(q[:, 0]) | (_spread_bits(q[:, 1]) << 1),
                       kind="stable")
    bounds = np.round(np.cumsum(sizes)).astype(np.int64)
    bounds[-1] = len(c)
    part = np.empty(len(c), np.int32)
    part[order] = np.searchsorted(bounds, np.arange(len(c)), side="right")
    return np.minimum(part, len(bounds) - 1)


def edge_cut(indptr, indices, part: np.ndarray) -> int:
    """Undirected edges of a symmetric CSR graph whose ends lie in
    different blocks."""
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return int(np.count_nonzero(part[src] != part[indices]) // 2)


class EllCG:
    """CG on ``A`` stored as ELL rows (every row padded to the longest),
    all of it, matrix and vectors, in ``dtype``: the reference put in the
    program's place, one precision below the configuration's."""

    def __init__(self, indptr, indices, data, dtype, device=None,
                 tol: float = 1e-6, max_iters: int = 500):
        import functools

        import jax
        import jax.numpy as jnp

        n = len(indptr) - 1
        deg = np.diff(indptr)
        width = int(deg.max())
        slot = np.arange(len(indices)) - np.repeat(indptr[:-1], deg)
        rows = np.repeat(np.arange(n), deg)
        cols = np.zeros((n, width), np.int32)
        vals = np.zeros((n, width), np.float32)
        cols[rows, slot] = indices
        vals[rows, slot] = data
        put = functools.partial(jax.device_put, device=device)
        self.n, self.dtype = n, dtype
        self.cols = put(cols)
        self.vals = put(jnp.asarray(vals, dtype))
        self._solve = jax.jit(functools.partial(_ell_cg, tol=tol,
                                                max_iters=max_iters))
        self._put = put

    def solve(self, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """``(x, iters)`` for ``b`` of shape (n,) or (n, nb), on the host."""
        import jax.numpy as jnp

        b2 = np.asarray(b).reshape(self.n, -1)
        x, it = self._solve(self.cols, self.vals,
                            self._put(jnp.asarray(b2, self.dtype)))
        x = np.asarray(x.astype(jnp.float32))
        return x.reshape(np.shape(b)), np.asarray(it)


def _ell_cg(cols, vals, b, tol: float, max_iters: int):
    import jax
    import jax.numpy as jnp

    def matvec(p):                       # (n, nb): gather rows, sum slots
        return jnp.einsum("ns,nsb->nb", vals, p[cols])

    def dot(u, v):
        return jnp.sum(u * v, axis=0)

    r = b
    x = jnp.zeros_like(b)
    p = r
    rr = dot(r, r)
    tol2 = (tol * tol) * dot(b, b)
    it = jnp.zeros(b.shape[1], jnp.int32)

    def cond(s):
        _, _, _, rr, it = s
        return jnp.any((rr > tol2) & (it < max_iters))

    def body(s):
        x, r, p, rr, it = s
        act = (rr > tol2) & (it < max_iters)
        ap = matvec(p)
        alpha = jnp.where(act, rr / dot(p, ap), 0).astype(b.dtype)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = dot(r, r)
        beta = jnp.where(act, rr_new / rr, 0).astype(b.dtype)
        p = jnp.where(act, r + beta * p, p)
        return x, r, p, jnp.where(act, rr_new, rr), it + act

    x, _, _, _, it = jax.lax.while_loop(cond, body, (x, r, p, rr, it))
    return x, it
