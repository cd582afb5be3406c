"""Sparse matrix / graph substrate.

Public surface:

  * ``graph`` / ``generators`` — CSR graphs and the paper's instance
    families (Table II);
  * ``spmv`` / ``kernels.spmv_bell`` — single-device SpMV backends;
  * ``distributed`` — partition-aware shard_map SpMV/CG (halo exchange);
  * ``operator``   — the Operator protocol unifying every backend behind
    ``make_operator`` + ``cg_solve_global`` (see its module docstring);
  * ``cg``         — the one CG solver all backends share;
  * ``multigrid``  — HPCG's multigrid preconditioner on the ``coo``
    operator (``make_operator(..., levels=...)``, ``precondition='mg'``);
  * ``replan``     — O(delta) incremental plan patching for streaming
    graphs (``EdgeDelta`` / ``apply_edge_delta`` / ``migrate_state``).
"""
from .cg import CGResult, cg_solve, jacobi_preconditioner
from .distributed import (DistPlan, HierPlan, TreePlan, build_plan,
                          build_plan_hier, build_plan_tree)
from .operator import (BACKENDS, BlockEllOperator, CooOperator,
                       DistributedOperator, Operator, make_operator,
                       cg_solve_global)
from .replan import (EdgeDelta, apply_delta_csr, apply_edge_delta,
                     migrate_state)

__all__ = ["CGResult", "cg_solve", "jacobi_preconditioner", "BACKENDS",
           "Operator", "CooOperator", "BlockEllOperator",
           "DistributedOperator", "make_operator", "cg_solve_global",
           "DistPlan", "HierPlan", "TreePlan", "build_plan",
           "build_plan_hier", "build_plan_tree", "EdgeDelta",
           "apply_delta_csr", "apply_edge_delta", "migrate_state"]
