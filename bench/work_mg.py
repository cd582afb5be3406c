"""Least bytes one MG-PCG loop trip must move, from the level sizes and
the width only (``work.py`` counts a plain CG trip).

A trip of CG preconditioned by HPCG's ``ComputeMG`` (1 pre- and 1
post-smoothing symmetric Gauss-Seidel step a level) makes these passes
over each level's matrix:

* the fine level: the CG matvec (1), the pre-smoothing step's forward
  and backward sweeps (2), the residual's matvec before restriction (1),
  the post-smoothing step's sweeps (2): 6;
* every coarser level but the coarsest: the same but the CG matvec: 5;
* the coarsest level: one symmetric step, 2.

A pass reads the level's stored values once, ``nnz`` of them at the
configuration's dtype, and no index: a layout that stores the values
(and so reads at least them) can never read more than 100% of this.
Each vector crosses HBM once a trip, one value per row and column of the
right-hand side: at the fine level CG's x, r, p, A p and z = M r; at each
coarser level its injected residual and its correction.
"""
from __future__ import annotations

FINE_PASSES, LEVEL_PASSES, COARSEST_PASSES = 6, 5, 2
FINE_VECTORS, LEVEL_VECTORS = 5, 2


def passes(levels: int) -> list[int]:
    """Matrix passes a trip makes over each of ``levels`` levels, fine
    first."""
    if levels == 1:
        return [1 + COARSEST_PASSES]
    return ([FINE_PASSES] + [LEVEL_PASSES] * (levels - 2)
            + [COARSEST_PASSES])


def trip_bytes(sizes, width: int, value_bytes: int) -> int:
    """Least bytes of one trip; ``sizes`` is ``(n, nnz)`` a level, fine
    first."""
    matrix = sum(p * nnz for p, (_, nnz) in zip(passes(len(sizes)), sizes))
    vectors = (FINE_VECTORS * sizes[0][0]
               + LEVEL_VECTORS * sum(n for n, _ in sizes[1:]))
    return (matrix + vectors * width) * value_bytes
