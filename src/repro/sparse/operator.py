"""The Operator protocol — one interface over every SpMV backend.

The paper's phase-2 evaluation (Sec. VI-a) runs the *same* SpMV/CG
application against matrices distributed by different partitioners; this
module is the code shape of that idea: a backend-agnostic linear-operator
interface so that one ``cg_solve`` and one benchmark harness drive

  * ``coo``            — single-device local SpMV in the layout the CSR
                         calls for: diagonals where few offsets j - i
                         hold the entries densely, else row groups (rows
                         grouped by length, each group's products summed
                         over a dense slot axis, no scatter; spmv.py);
                         with ``levels=`` it carries HPCG's multigrid
                         (multigrid.py, ``precondition='mg'``);
  * ``bell``           — the Pallas block-ELL TPU kernel
                         (kernels/spmv_bell.py), compiled on TPU and
                         interpreted on the CPU backend;
  * ``dist_halo``      — shard_map, edge-colored ppermute halo exchange,
                         *overlapped*: the interior matvec (rows touching
                         no halo slot) is issued before the ppermute
                         rounds so compute hides communication;
  * ``dist_halo_seq``  — the sequential halo schedule (exchange all
                         rounds, then one full matvec) — the
                         non-overlapped reference;
  * ``dist_bell``      — overlapped halo exchange with the interior
                         matvec in the Pallas block-ELL kernel (ROADMAP's
                         third comm/format combination);
  * ``dist_allgather`` — shard_map, all_gather baseline;
  * ``dist_hier``      — the per-tree-level hierarchical schedule
                         (``build_plan_tree``; two-level multi-pod is the
                         ``h == 2`` instance): interior matvec, then one
                         ppermute round class per tree level over that
                         level's axis suffix, issued outermost-first so
                         every slower exchange overlaps all faster-level
                         work.  Needs ``pods=`` / ``fanouts=`` / ``tree=``
                         and a hierarchical mesh
                         (``launch.mesh.make_test_mesh(k, pods=...)`` /
                         ``make_test_mesh(k, fanouts=...)`` or
                         ``make_production_mesh(multi_pod=True)``);
  * ``dist_hier_bell`` — the same tree schedule with the interior matvec
                         in the Pallas block-ELL kernel (the hier
                         counterpart of ``dist_bell``).

Protocol
--------
An Operator is any object with

  ``n``             — true global dimension;
  ``matvec(x)``     — y = A @ x in *operator space* (the backend's native
                      layout: (n,) for single-device, (k, B) padded
                      block-major for distributed);
  ``dot(u, v)``     — inner product in operator space (plain vdot is exact
                      for the distributed layout because padding rows stay
                      zero under matvec and scatter);
  ``diag()``        — diagonal of A in operator space (on-device; feeds
                      the Jacobi preconditioner in ``cg_solve``);
  ``scatter(x)``    — (n,) global numpy vector -> operator space;
  ``gather(y)``     — operator space -> (n,) global numpy vector.

``cg.cg_solve`` accepts an Operator directly; :func:`cg_solve_global` adds the
scatter/solve/gather round trip so callers never touch layouts.  Both take
``precondition='jacobi'`` to run preconditioned CG off the operator's
diagonal.  ``make_operator`` is the single factory the benchmark harness
uses.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .cg import CGResult, cg_solve
from .distributed import (DistPlan, build_plan, build_plan_tree,
                          make_dist_cg, make_dist_spmv, place_blocks,
                          shard_plan)
from .spmv import (csr_diagonal, csr_to_diagonals, csr_to_row_groups,
                   diagonal_offsets, diagonal_padding, diagonal_rows,
                   spmv_diagonals, spmv_grouped)


@runtime_checkable
class Operator(Protocol):
    """Structural protocol — see module docstring for the contract."""

    n: int

    def matvec(self, x): ...

    def dot(self, u, v): ...

    def diag(self): ...

    def scatter(self, x): ...

    def gather(self, y): ...


# --------------------------------------------------------------------------
# Single-device backends
# --------------------------------------------------------------------------
#
# Both are pytrees over their device arrays (``n`` and the kernel switch
# are static), so a jitted solve takes the operator as an argument: the
# matrix is an operand of the compiled program, never a constant in it.

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["cols", "vals", "mg"],
                   meta_fields=["n", "bounds", "offsets"])
@dataclasses.dataclass
class CooOperator:
    """The one-chip local SpMV (any sparsity; ``spmv.py``), in the layout
    the CSR calls for (``spmv.MAX_DIAGONALS`` / ``MIN_FILL``):

    * diagonals (``offsets`` non-empty): each row block's values one
      diagonal at a time, ``vals[b]`` of shape ``(D_b, n_b)``; the matvec
      adds ``D_b`` products of contiguous slices of ``x``, reading no
      index.  ``cols`` is empty.
    * row groups: rows stable-sorted by length and cut into groups of one
      length (``spmv.row_groups``); each group's ``cols``/``vals`` are
      slot-major ``(L, n_L)`` arrays, and the matvec sums each group's
      products over its slot axis: a gather and dense row sums, no
      scatter.

    ``bounds`` cuts operator space into row blocks (block b is rows
    ``bounds[b]:bounds[b + 1]``; ``()`` is one block): ``from_csr(...,
    blocks=labels)`` puts the rows of each label together, as the
    multigrid's colors need, and each block keeps its rows in either
    layout.  Operator space is that row order, A' = P A P^T: ``scatter``
    applies ``perm`` on the host before the copy to the device and
    ``gather`` undoes it after the copy back.  ``perm``, ``inv`` and
    ``pad_share`` (padded slots over stored entries) stay on the host,
    out of the pytree (``init=False``); ``perm`` is None for arrays
    already in operator order.  ``mg`` is the multigrid hierarchy below
    this matrix (``multigrid.Hierarchy``) where it was built with
    ``levels=``, else None.

    ``batch_native``: the matvec carries a trailing RHS-batch axis through
    natively, so the batched CG path needs no vmap."""

    n: int
    cols: tuple
    vals: tuple
    bounds: tuple = ()
    offsets: tuple = ()
    mg: object = None
    perm: np.ndarray | None = dataclasses.field(default=None, init=False)
    inv: np.ndarray | None = dataclasses.field(default=None, init=False)
    pad_share: float = dataclasses.field(default=0.0, init=False)

    batch_native = True

    @classmethod
    def from_csr(cls, indptr, indices, data, blocks=None, levels=None):
        """``blocks``: a label per row; rows of one label become one row
        block, labels in ascending order.  ``levels``: the coarser levels
        of a multigrid below this matrix, ``(indptr, indices, data, f2c)``
        each (``multigrid.build``)."""
        if levels is not None:
            from .multigrid import build
            return build((indptr, indices, data), levels, cls)
        n = len(indptr) - 1
        order, bounds = None, ()
        if blocks is not None:
            order = np.argsort(blocks, kind="stable")
            counts = np.unique(blocks, return_counts=True)[1]
            bounds = tuple(int(b) for b in np.concatenate(
                [[0], np.cumsum(counts)]))
            indptr, indices, data = permute_csr(indptr, indices, data,
                                                order)
        offsets = diagonal_offsets(indptr, indices, bounds)
        if offsets is not None:
            vals = csr_to_diagonals(indptr, indices, data, offsets, bounds)
            op = cls(n=n, cols=(), vals=tuple(map(jnp.asarray, vals)),
                     bounds=bounds, offsets=offsets)
            perm, slots = order, sum(v.size for v in vals)
        else:
            perm, _, cols, vals = csr_to_row_groups(indptr, indices, data,
                                                    bounds)
            op = cls(n=n, cols=tuple(map(jnp.asarray, cols)),
                     vals=tuple(map(jnp.asarray, vals)), bounds=bounds)
            perm = perm if order is None else order[perm]
            slots = sum(c.size for c in cols)
        if perm is not None and not np.array_equal(perm, np.arange(n)):
            op.perm = perm
            op.inv = np.empty_like(perm)
            op.inv[perm] = np.arange(n)
        op.pad_share = slots / max(len(indices), 1) - 1
        return op

    @property
    def layout(self) -> str:
        return "diagonals" if self.offsets else "row groups"

    @property
    def groups(self) -> int:
        return len(self.cols)

    @property
    def diagonals(self) -> int:
        return sum(len(o) for o in self.offsets)

    @property
    def dtype(self):
        return self.vals[0].dtype

    @property
    def row_blocks(self) -> tuple:
        """Bounds of the row blocks, one block when none was asked for."""
        return self.bounds or (0, self.n)

    @property
    def pad(self) -> tuple[int, int]:
        """Zeros ``(lo, hi)`` that :meth:`block_rows` wants around x."""
        if not self.offsets:
            return 0, 0
        return diagonal_padding(self.offsets, self.row_blocks)

    def matvec(self, x):
        if self.offsets:
            return spmv_diagonals(self.vals, x, offsets=self.offsets,
                                  bounds=self.row_blocks)
        return spmv_grouped(self.cols, self.vals, x)

    def block_rows(self, b: int, xp):
        """Rows of row block ``b`` of A @ x, ``xp`` being x padded by
        :attr:`pad`: they read only that block's values."""
        start = self.row_blocks[b]
        if self.offsets:
            return diagonal_rows(self.offsets[b], start, self.vals[b], xp,
                                 self.pad[0])
        tail = (1,) * (xp.ndim - 1)
        out, first = [], 0
        for c, v in zip(self.cols, self.vals):
            if start <= first < self.row_blocks[b + 1]:
                out.append(jnp.sum(v.reshape(v.shape + tail) * xp[c],
                                   axis=0))
            first += c.shape[1]
        return jnp.concatenate(out, axis=0)

    def mg_preconditioner(self):
        """z = M^-1 r, one V-cycle of HPCG's ComputeMG over the hierarchy
        built with ``levels=`` (``multigrid.vcycle``); carries the batch
        axis natively."""
        if self.mg is None:
            raise ValueError("precondition='mg' needs a CooOperator built "
                             "with levels= (the multigrid hierarchy)")
        from .multigrid import vcycle
        ops = (self,) + tuple(self.mg.ops)

        def apply(r):
            return vcycle(ops, self.mg.dinv, self.mg.f2c, r)

        apply.batch_native = True
        return apply

    def operand_spec(self, nb: int | None = None):
        """``ShapeDtypeStruct`` of the matvec operand — the abstract input
        the trace auditor (``repro.analysis.trace``) feeds to
        ``jax.make_jaxpr``; ``nb`` adds the trailing RHS-batch axis."""
        shape = (self.n,) if nb is None else (self.n, nb)
        return jax.ShapeDtypeStruct(shape, self.dtype)

    def dot(self, u, v):
        return jnp.vdot(u, v)

    def diag(self):
        """On-device diagonal in operator order: the offset-0 diagonal of
        each row block, or each group's slots whose column is their own
        row, summed (padded slots hold 0)."""
        out, start = [], 0
        if self.offsets:
            for o, v in zip(self.offsets, self.vals):
                out.append(v[o.index(0)] if 0 in o
                           else jnp.zeros(v.shape[1], v.dtype))
            return jnp.concatenate(out)
        for c, v in zip(self.cols, self.vals):
            row = start + jnp.arange(c.shape[1], dtype=c.dtype)
            out.append(jnp.sum(jnp.where(c == row, v, 0), axis=0))
            start += c.shape[1]
        return jnp.concatenate(out)

    def scatter(self, x):
        x = _as_float(x)
        return jnp.asarray(x if self.perm is None
                           else np.take(x, self.perm, axis=0))

    def gather(self, y):
        y = np.asarray(y)
        return y if self.inv is None else np.take(y, self.inv, axis=0)


def permute_csr(indptr, indices, data, perm):
    """P A P^T as CSR: row i is row ``perm[i]`` of A, with its columns
    renumbered the same way (left unsorted).  O(nnz)."""
    indptr = np.asarray(indptr, np.int64)
    lengths = np.diff(indptr)[perm]
    new_ptr = np.zeros(len(perm) + 1, np.int64)
    np.cumsum(lengths, out=new_ptr[1:])
    src = (np.arange(new_ptr[-1])
           - np.repeat(new_ptr[:-1] - indptr[perm], lengths))
    inv = np.empty(len(perm), np.int64)
    inv[perm] = np.arange(len(perm))
    return (new_ptr, inv[np.asarray(indices)[src]].astype(np.int32),
            np.asarray(data)[src])


def _as_float(x):
    """Host vector -> float ndarray, preserving float dtypes (float64
    systems stay float64 under JAX_ENABLE_X64; the old hard-coded
    ``astype(np.float32)`` silently downcast them)."""
    x = np.asarray(x)
    if not np.issubdtype(x.dtype, np.floating):
        x = x.astype(np.float32)
    return x


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["blocks", "cols", "diag_"],
                   meta_fields=["n"])
@dataclasses.dataclass
class BlockEllOperator:
    """Pallas block-ELL SpMV (compiled with Mosaic on TPU; interpreted
    on the CPU backend)."""

    n: int
    blocks: jnp.ndarray
    cols: jnp.ndarray
    diag_: jnp.ndarray | None = None

    @classmethod
    def from_csr(cls, indptr, indices, data, bm: int = 8, bk: int = 128,
                 nnzb: int | None = None):
        from ..kernels.spmv_bell import csr_to_block_ell
        n = len(indptr) - 1
        blocks, cols, _meta = csr_to_block_ell(indptr, indices, data, n,
                                               bm=bm, bk=bk, nnzb=nnzb)
        return cls(n=n, blocks=jnp.asarray(blocks), cols=jnp.asarray(cols),
                   diag_=jnp.asarray(csr_diagonal(indptr, indices, data)))

    def matvec(self, x):
        from ..kernels.spmv_bell import spmv_block_ell
        return spmv_block_ell(self.blocks, self.cols, x)

    def operand_spec(self, nb: int | None = None):
        """Abstract matvec operand for device-free tracing (the Pallas
        kernel is single-RHS, so ``nb`` is rejected like in matvec)."""
        if nb is not None:
            raise ValueError("BlockEllOperator is single-RHS")
        return jax.ShapeDtypeStruct((self.n,), self.blocks.dtype)

    def dot(self, u, v):
        return jnp.vdot(u, v)

    def diag(self):
        if self.diag_ is None:
            raise ValueError("BlockEllOperator built without a diagonal; "
                             "construct via from_csr for Jacobi support")
        return self.diag_

    def scatter(self, x):
        return jnp.asarray(_as_float(x))

    def gather(self, y):
        return np.asarray(y)


# --------------------------------------------------------------------------
# Distributed backend
# --------------------------------------------------------------------------

@dataclasses.dataclass
class DistributedOperator:
    """shard_map SpMV over a partition plan.

    ``comm`` picks the exchange schedule — ``'halo'`` (overlapped
    interior/boundary, the default), ``'halo_seq'`` (sequential
    reference), ``'allgather'`` (partitioner-oblivious baseline) or
    ``'hier'`` (the three-stage multi-pod schedule; needs a ``HierPlan``
    and a tuple ``axis``, see :meth:`from_csr`); ``local_format`` picks
    the interior matvec kernel — ``'coo'`` scatter-add or ``'bell'``
    (Pallas block-ELL, comm='halo' or 'hier').

    Operator space is the (k, B) padded block-major layout; ``dot`` is a
    plain vdot because ghost rows are zero in both vectors.  ``solve``
    exposes the fused whole-CG-in-shard_map program (one dispatch total)
    next to the composable ``cg_solve(op, ...)`` path (one dispatch per
    matvec) — both converge identically; the fused one is faster when
    dispatch overhead dominates.

    ``batch_native``: the halo/hier exchange schedules carry a trailing
    RHS-batch axis through natively (vmap cannot cross their ppermute
    rounds on every supported JAX), so batched CG hands them the full
    (k, B, nb) operand.  ``local_format='bell'`` stays single-RHS (the
    Pallas kernel is a vector kernel) and raises on a batched operand.
    """

    plan: DistPlan
    mesh: object
    axis: str | tuple = "pu"
    comm: str = "halo"
    local_format: str = "coo"

    batch_native = True

    def __post_init__(self):
        self.n = self.plan.n
        # placed once, one block per device (build_plan* leave the plan
        # on the default device)
        self.plan = shard_plan(self.plan, self.mesh, self.axis)
        self._spmv = make_dist_spmv(self.plan, self.mesh, axis=self.axis,
                                    comm=self.comm,
                                    local_format=self.local_format)
        self._fused = {}   # (tol, max_iters, precondition) -> compiled CG

    @classmethod
    def from_csr(cls, indptr, indices, data, part, k, mesh,
                 axis: str | tuple = "pu", comm: str = "halo",
                 local_format: str = "coo", pods=None, fanouts=None,
                 tree=None, validate: bool | None = None):
        """``comm='hier'`` builds the hierarchical plan — ``pods`` (pod
        count or explicit (k,) pod-of-block array) for the two-level
        instance, ``fanouts``/``tree`` ((k_1, ..., k_h) tuple / explicit
        (h-1, k) ancestor table) for arbitrary depth — and defaults
        ``axis`` to the mesh's full axis tuple, outermost level first —
        e.g. ``('pod', 'pu')`` on ``make_test_mesh(k, pods=...)``,
        ``('pod', 'host', 'pu')`` on ``make_test_mesh(k,
        fanouts=(2, 2, 2))`` and ``('pod', 'data', 'model')`` on
        ``make_production_mesh(multi_pod=True)``."""
        if comm == "hier":
            if pods is None and fanouts is None and tree is None:
                raise ValueError(
                    "comm='hier' needs pods= (pod count or (k,) "
                    "pod-of-block array), fanouts= ((k_1, ..., k_h) "
                    "tree shape) or tree= ((h-1, k) ancestor table)")
            if pods is not None and tree is not None:
                raise ValueError("pass either pods= or tree=, not both")
            plan = build_plan_tree(indptr, indices, data, part,
                                   pods if pods is not None else tree,
                                   k, fanouts=fanouts, validate=validate)
            if axis == "pu":                    # default -> full mesh tuple
                axis = tuple(mesh.axis_names)
        else:
            if pods is not None or fanouts is not None or tree is not None:
                raise ValueError("pods=/fanouts=/tree= only apply to "
                                 "comm='hier'")
            plan = build_plan(indptr, indices, data, part, k,
                              validate=validate)
        return cls(plan=plan, mesh=mesh, axis=axis, comm=comm,
                   local_format=local_format)

    def matvec(self, x):
        return self._spmv(x)

    def operand_spec(self, nb: int | None = None):
        """Abstract (k, B[, nb]) operator-space operand for device-free
        tracing: together with :func:`distributed.abstract_mesh_for` this
        lets ``repro.analysis.trace`` audit the staged program without
        any of the target topology present."""
        shape = (self.plan.k, self.plan.B)
        if nb is not None:
            shape = shape + (nb,)
        return jax.ShapeDtypeStruct(shape, self.plan.vals.dtype)

    def fused_solver(self, tol: float = 1e-6, max_iters: int = 500,
                     precondition: str | None = None):
        """The cached fused whole-CG program on *operator-space* operands
        ((k, B[, nb]) -> (x, res, iters)) — what :meth:`solve` runs after
        scattering, exposed so the trace auditor can ``make_jaxpr`` it."""
        key = (tol, max_iters, precondition)
        fused = self._fused.get(key)
        if fused is None:
            fused = self._fused[key] = make_dist_cg(
                self.plan, self.mesh, axis=self.axis,
                tol=tol, max_iters=max_iters, comm=self.comm,
                local_format=self.local_format, precondition=precondition)
        return fused

    def dot(self, u, v):
        return jnp.vdot(u, v)

    def diag(self):
        """(k, B) diagonal of A — extracted at plan build, already on
        device; ghost rows carry zero (handled by the preconditioner)."""
        return self.plan.diag

    def block_jacobi_preconditioner(self):
        """z = M^-1 r with M = blockdiag(A_bb), the per-PU diagonal blocks
        the plan already extracted (``plan.block_jacobi_inv``).  Operator-
        space application: one batched (B, B) matmul per block; ghost rows
        are identity in M^-1 and their residuals exactly zero, so padding
        stays out of the Krylov space."""
        minv = place_blocks(self.plan.block_jacobi_inv(), self.mesh,
                            self.axis)                 # (k, B, B)

        def apply(r):
            return jnp.einsum("kij,kj->ki", minv, r)

        return apply

    def scatter(self, x):
        return place_blocks(self.plan.scatter_vec(np.asarray(x)), self.mesh,
                            self.axis)

    def gather(self, y):
        return self.plan.gather_vec(np.asarray(y))

    def solve(self, b, tol: float = 1e-6, max_iters: int = 500,
              precondition: str | None = None) -> CGResult:
        """Fused distributed CG on a (n,) global right-hand side — or an
        (n, nb) RHS batch, which runs the multi-RHS masked loop inside the
        same shard_map program and returns per-column iters/residual.  The
        traced program is cached per (tol, max_iters, precondition);
        ``jax.jit`` retraces per operand shape under one cache entry, so
        repeated solves with new right-hand sides (same batch width) pay
        no re-trace."""
        fused = self.fused_solver(tol, max_iters, precondition)
        x, res, it = fused(self.scatter(b))
        return CGResult(x=x, iters=it, residual=res)


# --------------------------------------------------------------------------
# Factory + harness entry point
# --------------------------------------------------------------------------

BACKENDS = ("coo", "bell", "dist_halo", "dist_halo_seq", "dist_bell",
            "dist_allgather", "dist_hier", "dist_hier_bell")

_DIST_MODES = {
    "dist_halo": ("halo", "coo"),
    "dist_halo_seq": ("halo_seq", "coo"),
    "dist_bell": ("halo", "bell"),
    "dist_allgather": ("allgather", "coo"),
    "dist_hier": ("hier", "coo"),
    "dist_hier_bell": ("hier", "bell"),
}

_HIER_BACKENDS = ("dist_hier", "dist_hier_bell")


def make_operator(indptr, indices, data, backend: str = "coo", *,
                  part=None, k: int | None = None, mesh=None,
                  axis: str | tuple = "pu", **kw) -> Operator:
    """One factory for every SpMV backend (see BACKENDS).

    ``dist_hier`` / ``dist_hier_bell`` additionally need ``pods=`` (pod
    count or explicit (k,) pod-of-block array, e.g.
    ``core.topology.Topology.pod_assignment``), ``fanouts=`` or
    ``tree=`` (the arbitrary-depth forms) and a hierarchical mesh;
    ``axis`` defaults to the mesh's full axis tuple, outermost level
    first.

    ``part`` may also be a ``core.api.HierPartition`` (the tree-aware
    pipeline's output, duck-typed on ``.part``/``.pod_of``): the block
    partition, ``k``, and — for the hier backends — the
    partition-derived ancestor table are unpacked from it, so the
    partitioner output drives the runtime directly."""
    if part is not None and hasattr(part, "part") and hasattr(part,
                                                              "pod_of"):
        hp = part
        part = np.asarray(hp.part)
        if k is None:
            k = hp.k
        if backend in _HIER_BACKENDS and "pods" not in kw:
            kw.setdefault("tree", np.asarray(hp.anc)
                          if getattr(hp, "anc", None) is not None
                          else np.asarray(hp.pod_of))
    if backend == "coo":
        return CooOperator.from_csr(indptr, indices, data, **kw)
    if backend == "bell":
        return BlockEllOperator.from_csr(indptr, indices, data, **kw)
    if backend in _DIST_MODES:
        if part is None or k is None or mesh is None:
            raise ValueError(f"{backend} needs part=, k=, mesh=")
        comm, local_format = _DIST_MODES[backend]
        return DistributedOperator.from_csr(indptr, indices, data, part, k,
                                            mesh, axis=axis, comm=comm,
                                            local_format=local_format, **kw)
    raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")


def cg_solve_global(op: Operator, b: np.ndarray, tol: float = 1e-6,
             max_iters: int = 500,
             precondition: str | None = None) -> tuple[np.ndarray, int,
                                                       float]:
    """Scatter -> generic CG -> gather.  Returns (x_global, iters, res).

    A 2-D ``b`` of shape (n, nb) is an RHS batch: the multi-RHS masked
    loop runs all columns in one program and the returned iters/res are
    (nb,) arrays (the global vector is unambiguously 1-D, so the batch
    is inferred from ndim here — operator space needs the explicit
    ``batched=`` flag because a distributed single-RHS operand is
    already 2-D)."""
    batched = np.ndim(b) == 2
    res = cg_solve(op, op.scatter(b), tol=tol, max_iters=max_iters,
                   precondition=precondition, batched=batched)
    if batched:
        return (op.gather(res.x), np.asarray(res.iters),
                np.asarray(res.residual))
    return op.gather(res.x), int(res.iters), float(res.residual)
