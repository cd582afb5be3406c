"""Least bytes one CG loop trip must move, from n, nnz and the width only.

The count never comes from a compiled program, so a roofline share built
on it reads the same work whatever implements the trip:

* the matrix is read once per trip, whatever the width: its values and
  column indices (one each per stored entry) and its row pointers
  (n + 1 of them);
* each of the four CG vectors (x, r, p and A p) crosses HBM once per
  trip, one value per row and column of the right-hand side.

Values are of the configuration's dtype (``value_bytes``); column indices
and row pointers are int32.
"""
from __future__ import annotations

CG_VECTORS = 4
INDEX_BYTES = 4


def matrix_bytes(n: int, nnz: int, value_bytes: int) -> int:
    """Bytes of one pass over a CSR matrix (values, columns, row pointers)."""
    return nnz * (value_bytes + INDEX_BYTES) + (n + 1) * INDEX_BYTES


def vector_bytes(n: int, width: int, value_bytes: int) -> int:
    """Bytes of the CG vectors of one trip at ``width`` columns."""
    return CG_VECTORS * n * width * value_bytes


def trip_bytes(n: int, nnz: int, width: int, value_bytes: int) -> int:
    """Least bytes of one CG loop trip over ``width`` right-hand sides."""
    return (matrix_bytes(n, nnz, value_bytes)
            + vector_bytes(n, width, value_bytes))
