"""Single-device SpMV and CG building blocks (pure JAX).

Formats:
  * row groups  — the ``coo`` backend's layout (``csr_to_row_groups`` /
    ``spmv_grouped``): rows stable-sorted by length and cut into groups of
    one length; each group stores its columns and values slot-major,
    ``(L, n_L)``, so its rows lie on the minor (lane) axis.  The matvec
    sums each group's products over the slot axis: a gather of ``x`` and
    dense row sums, no scatter.
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


def csr_to_padded_coo(indptr: np.ndarray, indices: np.ndarray,
                      data: np.ndarray, nnz_pad: int | None = None):
    """CSR -> padded COO (rows, cols, vals); padded entries have val 0.
    ``vals`` keeps the dtype of ``data`` (float dtypes pass through;
    anything non-float is promoted to float32)."""
    n = len(indptr) - 1
    nnz = len(indices)
    nnz_pad = nnz_pad or nnz
    data = np.asarray(data)
    vdt = data.dtype if np.issubdtype(data.dtype, np.floating) \
        else np.float32
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
                      data: np.ndarray):
    """CSR -> the row-group layout ``(perm, inv, cols, vals)`` in operator
    order: operator row i is CSR row ``perm[i]`` (CSR row r is operator
    row ``inv[r]``), and so for columns: A' = P A P^T.  ``cols`` and
    ``vals`` hold one slot-major ``(L, n_L)`` array per group; a padded
    slot has col 0 and val 0.  ``vals`` keeps the dtype of ``data``
    (non-float data becomes float32).  Vectorised NumPy, O(nnz)."""
    indptr = np.asarray(indptr, np.int64)
    data = np.asarray(data)
    vdt = data.dtype if np.issubdtype(data.dtype, np.floating) \
        else np.float32
    lengths = np.diff(indptr)
    perm, counts, widths = row_groups(lengths)
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


def csr_diagonal(indptr: np.ndarray, indices: np.ndarray,
                 data: np.ndarray) -> np.ndarray:
    """(n,) diagonal of a CSR matrix (duplicates summed) — feeds the
    Jacobi preconditioner of ``cg.cg_solve``.  Keeps the dtype of
    ``data``.  Vectorized NumPy."""
    n = len(indptr) - 1
    data = np.asarray(data)
    vdt = data.dtype if np.issubdtype(data.dtype, np.floating) \
        else np.float32
    src = np.repeat(np.arange(n), np.diff(indptr))
    on_diag = src == np.asarray(indices)
    d = np.zeros(n, dtype=vdt)
    np.add.at(d, src[on_diag], data[on_diag])
    return d


def dense_from_coo(rows, cols, vals, n):
    a = np.zeros((n, n), dtype=np.float64)
    np.add.at(a, (rows, cols), vals)
    return a
