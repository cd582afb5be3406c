"""Single-device SpMV and CG building blocks (pure JAX).

Formats:
  * row groups  — the ``coo`` backend's layout (``csr_to_row_groups`` /
    ``spmv_grouped``): rows stable-sorted by length and cut into groups of
    one length; each group stores its columns and values slot-major,
    ``(L, n_L)``, so its rows lie on the minor (lane) axis.  The matvec
    sums each group's products over the slot axis: a gather of ``x`` and
    dense row sums, no scatter.
  * diagonals   — the ``coo`` backend's layout for a matrix with few,
    densely filled diagonals (``csr_to_diagonals`` / ``spmv_diagonals``):
    each row block stores its values one diagonal at a time, ``(D, n_b)``,
    and the matvec adds ``D`` products of a contiguous slice of ``x``: no
    gather, no column index read.  ``diagonal_offsets`` decides from the
    CSR alone whether a matrix takes it (``MAX_DIAGONALS``, ``MIN_FILL``).
  * padded-COO  — (rows, cols, vals) each (nnz_pad,); padding entries have
    val 0.  ``spmv_coo`` scatter-adds the products; it is the plain
    reference the tests hold the row groups to.
  * block-ELL   — see kernels/spmv_bell.py (the Pallas TPU kernel).

All converters preserve the input dtype (a float64 CSR yields float64
arrays — the old hard-coded ``float32`` silently downcast float64
systems); both matvecs additionally carry a trailing RHS-batch axis
through natively (``x`` of shape ``(n, nb)`` yields ``(n, nb)``), which is
the single-device half of the multi-RHS batched CG path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _value_dtype(data: np.ndarray):
    return data.dtype if np.issubdtype(data.dtype, np.floating) \
        else np.dtype(np.float32)


def csr_to_padded_coo(indptr: np.ndarray, indices: np.ndarray,
                      data: np.ndarray, nnz_pad: int | None = None):
    """CSR -> padded COO (rows, cols, vals); padded entries have val 0.
    ``vals`` keeps the dtype of ``data`` (float dtypes pass through;
    anything non-float is promoted to float32)."""
    n = len(indptr) - 1
    nnz = len(indices)
    nnz_pad = nnz_pad or nnz
    data = np.asarray(data)
    vdt = _value_dtype(data)
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    out_r = np.zeros(nnz_pad, dtype=np.int32)
    out_c = np.zeros(nnz_pad, dtype=np.int32)
    out_v = np.zeros(nnz_pad, dtype=vdt)
    out_r[:nnz], out_c[:nnz], out_v[:nnz] = rows, indices, data
    return out_r, out_c, out_v


@functools.partial(jax.jit, static_argnames=("n",))
def spmv_coo(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
             x: jnp.ndarray, n: int | None = None) -> jnp.ndarray:
    """y = A @ x for padded COO.  ``n`` (the output size) must be static:
    it shapes the segment-sum target, so it is a ``static_argnames`` entry
    rather than a traced operand.  ``x`` may carry a trailing RHS-batch
    axis (``(n, nb)``); the scatter-add batches natively."""
    n = n if n is not None else x.shape[0]
    contrib = vals.reshape(vals.shape + (1,) * (x.ndim - 1)) * x[cols]
    return jnp.zeros((n,) + x.shape[1:], vals.dtype).at[rows].add(contrib)


# The grouping rule of the row-group layout, fixed in code: a length held
# by fewer than n / FEW_ROWS rows goes up into the next longer group
# while the padded slots stay within PAD_BUDGET of the stored entries
# (rare short lengths join their neighbour; the long tail ends in one
# group padded to its longest row); then at most MAX_GROUPS groups, by
# the merges that add the fewest slots.
FEW_ROWS = 1024
PAD_BUDGET = 0.02
MAX_GROUPS = 16


def row_groups(lengths: np.ndarray):
    """Group rows by length: ``(perm, counts, widths)``.  ``perm`` stable-
    sorts the rows by length; group g holds the next ``counts[g]`` rows of
    ``perm``, each padded to ``widths[g]`` slots (ascending)."""
    lengths = np.asarray(lengths, np.int64)
    n, nnz = len(lengths), int(lengths.sum())
    perm = np.argsort(lengths, kind="stable")
    w, c = np.unique(lengths, return_counts=True)
    widths, counts = w.tolist(), c.tolist()
    pad, i = 0, 0

    def merge_up(g):
        counts[g + 1] += counts[g]
        del widths[g], counts[g]

    while i < len(widths) - 1:
        cost = counts[i] * (widths[i + 1] - widths[i])
        if counts[i] * FEW_ROWS < n and pad + cost <= PAD_BUDGET * nnz:
            pad += cost
            merge_up(i)
        else:
            i += 1
    while len(widths) > MAX_GROUPS:
        costs = [counts[j] * (widths[j + 1] - widths[j])
                 for j in range(len(widths) - 1)]
        merge_up(int(np.argmin(costs)))
    return perm, counts, widths


def csr_to_row_groups(indptr: np.ndarray, indices: np.ndarray,
                      data: np.ndarray, bounds: tuple | None = None):
    """CSR -> the row-group layout ``(perm, inv, cols, vals)`` in operator
    order: operator row i is CSR row ``perm[i]`` (CSR row r is operator
    row ``inv[r]``), and so for columns: A' = P A P^T.  ``cols`` and
    ``vals`` hold one slot-major ``(L, n_L)`` array per group; a padded
    slot has col 0 and val 0.  ``vals`` keeps the dtype of ``data``
    (non-float data becomes float32).  With ``bounds`` (row blocks
    ``bounds[b]:bounds[b + 1]``) each block is grouped on its own and
    keeps its rows: no group holds rows of two blocks.  Vectorised
    NumPy, O(nnz)."""
    indptr = np.asarray(indptr, np.int64)
    data = np.asarray(data)
    vdt = _value_dtype(data)
    lengths = np.diff(indptr)
    bounds = bounds or (0, len(lengths))
    perm, counts, widths = [], [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        p, c, w = row_groups(lengths[lo:hi])
        perm.append(lo + p)
        counts += c
        widths += w
    perm = np.concatenate(perm)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    cols_op = inv[np.asarray(indices)]
    cols, vals, start = [], [], 0
    for count, width in zip(counts, widths):
        rows = perm[start:start + count]
        slot = np.arange(width)[:, None]
        live = slot < lengths[rows][None, :]
        src = np.where(live, indptr[rows][None, :] + slot, 0)
        cols.append(np.where(live, cols_op[src], 0).astype(np.int32))
        vals.append(np.where(live, data[src], 0).astype(vdt))
        start += count
    return perm, inv, tuple(cols), tuple(vals)


@jax.jit
def spmv_grouped(cols, vals, x: jnp.ndarray) -> jnp.ndarray:
    """y = A' @ x for the row-group layout, in operator order: each
    group's products summed over its slot axis, the groups' rows
    concatenated.  ``x`` may carry a trailing RHS-batch axis."""
    tail = (1,) * (x.ndim - 1)
    return jnp.concatenate(
        [jnp.sum(v.reshape(v.shape + tail) * x[c], axis=0)
         for c, v in zip(cols, vals)], axis=0)


# The layout choice, fixed in code: a matrix is stored by diagonals where
# each row block holds at most MAX_DIAGONALS distinct offsets j - i and
# the stored entries fill at least MIN_FILL of those diagonals' slots;
# otherwise by row groups.  On one TPU v5e chip a row-group slot costs a
# gather, ~6.8 ns (a CG trip on delaunay_n20's 7.34M slots: 50.6 ms), a
# diagonal slot a streamed read, ~0.044 ns (a CG trip on the 104^3
# 27-point stencil's 30.4M diagonal slots: 1.33 ms); so diagonals win down
# to a fill near 1%, and MIN_FILL bounds the memory instead: the diagonals
# hold at most twice the stored entries.  MAX_DIAGONALS bounds the
# program: one slice and one product a diagonal and block, unrolled
# (HPCG's 27-point stencil holds 27 a block; a Delaunay mesh holds ~n).
MAX_DIAGONALS = 64
MIN_FILL = 0.5


def diagonal_offsets(indptr: np.ndarray, indices: np.ndarray,
                     bounds: tuple | None = None):
    """The distinct offsets ``j - i`` of each row block
    ``bounds[b]:bounds[b + 1]`` (default one block), sorted, or ``None``
    where the CSR fails the layout choice above.  O(nnz + n) a block."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    bounds = bounds or (0, n)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    off = np.asarray(indices, np.int64) - rows
    out, slots = [], 0
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        seen = np.bincount(off[indptr[lo]:indptr[hi]] + n,
                           minlength=2 * n)
        o = np.flatnonzero(seen) - n
        if len(o) > MAX_DIAGONALS:
            return None
        # a block that stores nothing keeps one (zero) diagonal
        out.append(tuple(int(v) for v in o) or (0,))
        slots += len(out[-1]) * (hi - lo)
    if len(off) < MIN_FILL * slots:
        return None
    return tuple(out)


def csr_to_diagonals(indptr: np.ndarray, indices: np.ndarray,
                     data: np.ndarray, offsets: tuple,
                     bounds: tuple | None = None):
    """CSR -> the diagonal layout, rows in CSR order: for each row block
    b, a ``(len(offsets[b]), n_b)`` array whose ``[d, k]`` is
    ``A[i, i + offsets[b][d]]`` at row ``i = bounds[b] + k`` (0 where the
    CSR stores nothing; duplicates summed).  ``vals`` keeps the dtype of
    ``data`` (non-float data becomes float32)."""
    indptr = np.asarray(indptr, np.int64)
    data = np.asarray(data)
    n = len(indptr) - 1
    bounds = bounds or (0, n)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    off = np.asarray(indices, np.int64) - rows
    vals = []
    for (lo, hi), o in zip(zip(bounds[:-1], bounds[1:]), offsets):
        a, b = indptr[lo], indptr[hi]
        slot = (np.searchsorted(o, off[a:b]) * (hi - lo)
                + rows[a:b] - lo)
        v = np.bincount(slot, weights=data[a:b],
                        minlength=len(o) * (hi - lo))
        vals.append(v.reshape(len(o), hi - lo).astype(_value_dtype(data)))
    return tuple(vals)


def diagonal_padding(offsets: tuple, bounds: tuple) -> tuple[int, int]:
    """Zeros ``(lo, hi)`` to put around ``x`` so that every diagonal's
    slice of it lies inside: row block b reads ``x[i + o]`` for its rows
    ``i`` and offsets ``o``."""
    lo = max(0, max(-(s + min(o)) for s, o in zip(bounds, offsets)))
    hi = max(0, max(e + max(o) - bounds[-1]
                    for e, o in zip(bounds[1:], offsets)))
    return lo, hi


def diagonal_rows(offsets, start: int, vals, xp, lo: int):
    """Rows ``start:start + n_b`` of A @ x for one row block of the
    diagonal layout: ``xp`` is ``x`` padded by ``lo`` zeros in front (and
    enough behind), with any trailing RHS-batch axis."""
    n_b = vals.shape[1]
    tail = (1,) * (xp.ndim - 1)
    acc = None
    for d, o in enumerate(offsets):
        s = lo + start + o
        term = vals[d].reshape((n_b,) + tail) * xp[s:s + n_b]
        acc = term if acc is None else acc + term
    return acc


@functools.partial(jax.jit, static_argnames=("offsets", "bounds"))
def spmv_diagonals(vals, x: jnp.ndarray, offsets: tuple,
                   bounds: tuple) -> jnp.ndarray:
    """y = A @ x for the diagonal layout (rows in CSR order): each row
    block's diagonals, the blocks' rows concatenated.  ``x`` may carry a
    trailing RHS-batch axis."""
    lo, hi = diagonal_padding(offsets, bounds)
    xp = jnp.pad(x, ((lo, hi),) + ((0, 0),) * (x.ndim - 1))
    return jnp.concatenate(
        [diagonal_rows(o, s, v, xp, lo)
         for o, s, v in zip(offsets, bounds, vals)], axis=0)


def csr_diagonal(indptr: np.ndarray, indices: np.ndarray,
                 data: np.ndarray) -> np.ndarray:
    """(n,) diagonal of a CSR matrix (duplicates summed) — feeds the
    Jacobi preconditioner of ``cg.cg_solve``.  Keeps the dtype of
    ``data``.  Vectorized NumPy."""
    n = len(indptr) - 1
    data = np.asarray(data)
    vdt = _value_dtype(data)
    src = np.repeat(np.arange(n), np.diff(indptr))
    on_diag = src == np.asarray(indices)
    d = np.zeros(n, dtype=vdt)
    np.add.at(d, src[on_diag], data[on_diag])
    return d


def dense_from_coo(rows, cols, vals, n):
    a = np.zeros((n, n), dtype=np.float64)
    np.add.at(a, (rows, cols), vals)
    return a
