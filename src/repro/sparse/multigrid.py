"""HPCG's multigrid preconditioner (``ComputeMG``) on the ``coo`` operator.

The hierarchy is what HPCG's ``GenerateCoarseProblem`` gives: one matrix
a level, each the same stencil on a grid halved in every dimension (not a
Galerkin product), and an ``f2c`` map from each coarse point to the fine
point it injects from.  :func:`build` takes those as CSR arrays and maps,
so it assumes no grid: every level is colored from its own sparsity
pattern (:func:`greedy_colors`) and stored by ``CooOperator.from_csr`` in
the layout its CSR calls for, with the rows of each color together.

One V-cycle (:func:`vcycle`), as ``ComputeMG`` does it from a zero guess:

* one symmetric Gauss-Seidel step (:func:`symgs`) on the level;
* the residual ``r - A x`` injected at the ``f2c`` points;
* the V-cycle of the coarser level on it, added back at those points;
* one more symmetric Gauss-Seidel step;
* on the coarsest level, one symmetric Gauss-Seidel step alone.

The one departure from HPCG: its Gauss-Seidel sweeps rows in their
lexicographic order, one after another; here the sweep goes color by
color (forward over the colors, then backward), and a color's rows,
which share no entry, are updated at once.  HPCG's optimised submissions
reorder the same way; HPCG charges the iterations the ordering costs.
A color's update reads only that color's rows of the matrix and the
entries of ``x`` they touch (``CooOperator.block_rows``).  Everything
carries a trailing right-hand-side axis.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..spans import span
from .spmv import csr_diagonal


def _row_entries(ptr: np.ndarray, rows: np.ndarray):
    """Positions of the entries of ``rows`` in a CSR with row pointers
    ``ptr``, and for each the index into ``rows`` it belongs to."""
    start = ptr[rows]
    length = ptr[rows + 1] - start
    owner = np.repeat(np.arange(len(rows)), length)
    first = np.cumsum(length) - length
    return np.arange(length.sum()) - first[owner] + start[owner], owner


def greedy_colors(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """First-fit greedy coloring of a structurally symmetric pattern in
    row order: row i takes the smallest color none of its earlier
    neighbours holds.  It gives the 27-point stencil its 8 parity colors
    (``(x % 2) + 2 (y % 2) + 4 (z % 2)``).

    Computed in rounds, not row by row: a round colors every row whose
    earlier neighbours all have colors, which gives the same colors as
    the sequential loop.  Raises where the pattern is not symmetric, as
    then two rows of one color could share an entry."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    n = len(indptr) - 1
    rows = np.repeat(np.arange(n), np.diff(indptr))
    before, after = indices < rows, indices > rows

    def csr(mask):
        ptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(rows[mask], minlength=n), out=ptr[1:])
        return ptr, indices[mask]

    (e_ptr, e_idx), (l_ptr, l_idx) = csr(before), csr(after)
    pending = np.diff(e_ptr)
    width = int(pending.max(initial=0)) + 1
    color = np.full(n, -1, np.int64)
    front = np.flatnonzero(pending == 0)
    while front.size:
        pos, owner = _row_entries(e_ptr, front)
        used = np.zeros((len(front), width), bool)
        used[owner, color[e_idx[pos]]] = True
        color[front] = np.argmin(used, axis=1)
        pos, _ = _row_entries(l_ptr, front)
        later, count = np.unique(l_idx[pos], return_counts=True)
        pending[later] -= count
        front = later[pending[later] == 0]
    off = rows != indices
    if np.any(color < 0) or np.any(color[rows[off]] == color[indices[off]]):
        raise ValueError("greedy_colors needs a structurally symmetric "
                         "pattern")
    return color.astype(np.int32)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["ops", "dinv", "f2c"], meta_fields=[])
@dataclasses.dataclass
class Hierarchy:
    """The levels below a fine ``CooOperator``: ``ops`` the coarser level
    operators (coarsest last), ``dinv`` each level's inverse diagonal in
    its operator order (the fine level first), ``f2c`` for each coarser
    level the finer level's operator rows of its points, in its own
    operator order.  ``sizes`` ((n, nnz) a level, fine first) and
    ``colors`` (a level) stay on the host."""

    ops: tuple
    dinv: tuple
    f2c: tuple
    sizes: tuple = dataclasses.field(default=(), init=False)
    colors: tuple = dataclasses.field(default=(), init=False)


def _order(op) -> np.ndarray:
    return np.arange(op.n) if op.perm is None else op.perm


def build(fine: tuple, levels, operator):
    """The fine level's operator (``operator.from_csr``, e.g. the
    ``CooOperator`` class) with its :class:`Hierarchy` as ``.mg``.

    ``fine`` is its CSR ``(indptr, indices, data)``; ``levels`` the
    coarser levels, finest first, each ``(indptr, indices, data, f2c)``
    with ``f2c[i]`` the point of the level above that coarse point ``i``
    injects from.  Records the span ``mg.build`` (host: coloring,
    layouts, copies) with stats ``levels``, ``colors`` and ``n<l>`` /
    ``nnz<l>`` a level."""
    with span("mg.build") as mg_span:
        csrs = [fine] + [tuple(lvl[:3]) for lvl in levels]
        ops, colors = [], []
        for indptr, indices, data in csrs:
            c = greedy_colors(indptr, indices)
            ops.append(operator.from_csr(indptr, indices, data, blocks=c))
            colors.append(int(c.max(initial=-1)) + 1)
        f2c = []
        for upper, lower, lvl in zip(ops, ops[1:], levels):
            m = np.asarray(lvl[3], np.int64)
            if (len(m) != lower.n or len(np.unique(m)) != len(m)
                    or m.min(initial=0) < 0 or m.max(initial=0) >= upper.n):
                raise ValueError("each f2c must send every coarse point to "
                                 "its own point of the level above")
            inv = np.argsort(_order(upper))
            f2c.append(jnp.asarray(inv[m[_order(lower)]].astype(np.int32)))
        dinv = []
        for op, (indptr, indices, data) in zip(ops, csrs):
            d = csr_diagonal(indptr, indices, data)[_order(op)]
            dinv.append(jnp.asarray(np.where(d != 0, 1 / np.where(
                d != 0, d, 1), 0).astype(d.dtype)))
        top = ops[0]
        top.mg = Hierarchy(ops=tuple(ops[1:]), dinv=tuple(dinv),
                           f2c=tuple(f2c))
        top.mg.sizes = tuple((len(c[0]) - 1, len(c[1])) for c in csrs)
        top.mg.colors = tuple(colors)
        stats = {"levels": len(ops), "colors": max(colors)}
        for lvl, (n, nnz) in enumerate(top.mg.sizes):
            stats[f"n{lvl}"], stats[f"nnz{lvl}"] = n, nnz
        mg_span.set_metadata(**stats)
    return top


def _col(v, like):
    return v.reshape(v.shape + (1,) * (like.ndim - 1))


def symgs(op, dinv, rhs, x=None):
    """One symmetric Gauss-Seidel step on ``op``'s row blocks (its
    colors): forward over the blocks, then backward; a block's rows take
    ``x += (rhs - A x) / diag`` at once.  ``x`` None is zero."""
    lo, hi = op.pad
    bounds = op.row_blocks
    pad = ((lo, hi),) + ((0, 0),) * (rhs.ndim - 1)
    xp = jnp.pad(jnp.zeros_like(rhs) if x is None else x, pad)
    blocks = range(len(bounds) - 1)
    for b in [*blocks, *reversed(blocks)]:
        s, e = bounds[b], bounds[b + 1]
        y = op.block_rows(b, xp)
        xp = xp.at[lo + s:lo + e].add((rhs[s:e] - y) * _col(dinv[s:e], y))
    return xp[lo:lo + op.n]


def vcycle(ops, dinv, f2c, r):
    """z = M^-1 r: one V-cycle of ``ComputeMG`` from a zero guess over
    the levels ``ops`` (finest first; see the module docstring)."""
    op = ops[0]
    x = symgs(op, dinv[0], r)
    if len(ops) == 1:
        return x
    rc = (r - op.matvec(x))[f2c[0]]
    xc = vcycle(ops[1:], dinv[1:], f2c[1:], rc)
    x = x.at[f2c[0]].add(xc, unique_indices=True)
    return symgs(op, dinv[0], r, x)
