"""Distributed SpMV / CG over a heterogeneous partition — shard_map version
of the paper's application layer (Sec. VI-a: SpMV and CG on the Laplacian,
distributed according to the partition produced by the respective tool).

MPI-rank-per-PU becomes one mesh index per block.  Because XLA SPMD shards
are uniform, each block is padded to B = max block size; `row_mask` marks
real rows.  The padding waste is exactly the heterogeneity spread: with
Algorithm-1 target sizes the fast PUs own the largest blocks, so B equals
the largest tw and slow PUs carry ghost rows.  (On a real heterogeneous
machine the fast PU also *is* faster, so wall-clock stays balanced — the
simulated-speed benchmark in benchmarks/bench_cg.py models this.)

Halo exchange: the quotient graph of the partition is edge-colored
(core.refinement.vizing_edge_coloring, Misra-Gries: <= Delta+1 rounds on
quotient degree Delta) and each color class becomes one
`lax.ppermute` round — at most one partner per device per round, the exact
communication schedule Geographer-R uses for its pairwise refinement.  The
halo buffer layout is (rounds, S) with stable slots, so column indices are
remapped once on the host.

Four exchange strategies are provided:
  * ``halo``       — ppermute rounds *overlapped* with compute: each
                     block's padded COO is split into interior rows (no
                     halo-slot columns) and boundary rows; the interior
                     matvec is issued before the ppermute rounds, so XLA
                     runs it concurrently with the exchange, and only the
                     boundary accumulation waits on halo data.  [default]
  * ``halo_seq``   — the sequential schedule (all rounds, then one full
                     matvec); same plan, kept as the non-overlapped
                     reference the benchmark compares against.
  * ``allgather``  — all_gather of the whole padded vector, comm volume
                     = O(n); the baseline a partitioner-oblivious system
                     would use.
  * ``hier``       — the per-tree-level schedule for hierarchical meshes
                     (:func:`build_plan_tree`; :func:`build_plan_hier` is
                     the two-level instance): halo edges are split by the
                     LCA level of their block pair, one segment per tree
                     level, each with its own Misra-Gries coloring over
                     that level's quotient graph.  The interior matvec is
                     issued first; each level's rounds ppermute over its
                     axis suffix (level 0 = the fast innermost axis,
                     firing in every subtree at once; the outermost level
                     = all axes combined), issued *outermost-level-first*
                     so every slower exchange is in flight while all
                     faster levels' rounds and accumulations run.  A
                     boundary row's class is the highest level it reads,
                     so only root-crossing rows wait on the slowest
                     links.

Orthogonally, ``local_format`` selects the interior matvec kernel:
padded-COO scatter-add (``'coo'``) or the Pallas block-ELL kernel of
kernels/spmv_bell.py (``'bell'``, TPU-compiled, interpreted on the CPU
backend).

Plan construction (:func:`build_plan`) is fully vectorized NumPy —
``searchsorted`` / ``unique`` / fancy-index scatter; the only Python loops
are over quotient-graph edges (O(k^2), k = #PUs), never over vertices or
matrix entries.  The seed's per-edge implementation is preserved as
:func:`build_plan_reference` and serves as the correctness oracle in
tests/test_dist_plan.py and the speedup baseline in benchmarks/bench_cg.py.

Both plan builders produce *identical* plans (bit-equal arrays), so the
ppermute schedule and halo slot layout are stable across the rewrite.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from ..compat import Mesh, NamedSharding, P, shard_map
from ..core.refinement import vizing_edge_coloring
from .cg import cg_solve, jacobi_preconditioner


@dataclasses.dataclass
class DistPlan:
    """Host-built plan + device arrays for the distributed operator.

    All arrays carry a leading block axis of size k and are sharded
    one-block-per-device by the shard_map programs below.
    """

    k: int
    B: int                      # padded rows per block
    S: int                      # padded halo slots per round
    n_rounds: int
    n: int                      # true global size
    perm: np.ndarray            # old vertex id -> padded new id (blk*B+rank)
    block_of: np.ndarray        # (k,) first padded id of each block
    sizes: np.ndarray           # (k,) true rows per block
    # device data
    rows: jnp.ndarray           # (k, nnz_pad) int32 local row
    cols: jnp.ndarray           # (k, nnz_pad) int32 local col in [0, B+R*S)
    vals: jnp.ndarray           # (k, nnz_pad) f32
    row_mask: jnp.ndarray       # (k, B) f32
    send_idx: jnp.ndarray       # (k, R, S) int32 local indices to send
    send_mask: jnp.ndarray      # (k, R, S) f32
    round_perms: tuple          # per round: tuple of (src, dst) pairs
    # interior/boundary split of the same nnz set (comm/compute overlap):
    # a row is *boundary* iff any of its edges reads a halo slot; interior
    # rows depend only on x_loc, so their matvec is issued before the
    # ppermute rounds and overlaps with the exchange.  Within each block
    # the packed edge order of rows/cols/vals is preserved, and
    # interior + boundary edges exactly tile the block's true nnz.
    rows_int: jnp.ndarray = None   # (k, nnz_int_pad) int32
    cols_int: jnp.ndarray = None   # (k, nnz_int_pad) int32, all < B
    vals_int: jnp.ndarray = None   # (k, nnz_int_pad) f32
    rows_bnd: jnp.ndarray = None   # (k, nnz_bnd_pad) int32
    cols_bnd: jnp.ndarray = None   # (k, nnz_bnd_pad) int32, in [0, B+R*S)
    vals_bnd: jnp.ndarray = None   # (k, nnz_bnd_pad) f32
    interior_mask: jnp.ndarray = None  # (k, B) f32: real AND interior rows
    diag: jnp.ndarray = None       # (k, B) f32 diagonal of A (Jacobi)
    nnz_blk: np.ndarray = None     # (k,) true nnz per block (host)
    # lazy allgather-mode columns: built on first access from the packing
    # order (only the allgather baseline needs them; halo mode never does)
    _pack_blk: np.ndarray = None      # (nnz,) owning block, packed order
    _pack_pos: np.ndarray = None      # (nnz,) slot within block
    _pack_dst: np.ndarray = None      # (nnz,) global dst vertex, packed order
    _cols_global: jnp.ndarray = None
    _bell: dict = dataclasses.field(default_factory=dict)
    _bj_inv: jnp.ndarray = None       # lazy (k, B, B) block-Jacobi inverses
    # host-side intermediates for O(delta) incremental replanning
    # (:mod:`repro.sparse.replan`); None on plans built without a cache.
    # Never compared by the bit-equality suites — pure bookkeeping.
    _replan: object = None

    @property
    def cols_global(self) -> jnp.ndarray:
        """(k, nnz_pad) int32 columns in padded global ids (blk*B + rank)."""
        if self._cols_global is None:
            out = np.zeros(self.rows.shape, dtype=np.int32)
            out[self._pack_blk, self._pack_pos] = \
                self.perm[self._pack_dst].astype(np.int32)
            self._cols_global = jnp.asarray(out)
        return self._cols_global

    def scatter_vec(self, x: np.ndarray) -> np.ndarray:
        """(n,) global vector -> (k, B) padded block-major layout.  An
        (n, nb) RHS batch scatters to (k, B, nb) — trailing axes ride
        along; padding rows stay zero in every column."""
        x = np.asarray(x)
        dt = x.dtype if np.issubdtype(x.dtype, np.floating) else np.float32
        out = np.zeros((self.k, self.B) + x.shape[1:], dtype=dt)
        out[self.perm // self.B, self.perm % self.B] = x
        return out

    def gather_vec(self, xb: np.ndarray) -> np.ndarray:
        """(k, B[, nb]) -> (n[, nb]) global order."""
        return np.asarray(xb)[self.perm // self.B, self.perm % self.B]

    def bell_local(self, bm: int = 8, bk: int = 128):
        """Block-ELL form of the *interior* edges, stacked over blocks.

        Returns (blocks, cols): (k, S_b, NNZB, bm, bk) f32 and
        (k, S_b, NNZB) int32 with uniform NNZB = max over blocks, so the
        stack shards cleanly one-block-per-device.  Interior columns are
        all < B, so the local Pallas block-ELL matvec needs no halo data —
        it is the interior half of the overlapped SpMV on TPU.  Cached per
        (bm, bk).
        """
        key = (bm, bk)
        cached = self._bell.get(key)
        if cached is not None:
            return cached
        from ..kernels.spmv_bell import padded_coo_to_block_ell
        ri = np.asarray(self.rows_int)
        ci = np.asarray(self.cols_int)
        vi = np.asarray(self.vals_int)
        per = [padded_coo_to_block_ell(ri[b], ci[b], vi[b], self.B,
                                       bm=bm, bk=bk)
               for b in range(self.k)]
        nnzb = max(blk.shape[1] for blk, _, _ in per)
        Sb = per[0][0].shape[0]
        blocks = np.zeros((self.k, Sb, nnzb, bm, bk), dtype=np.float32)
        cols = np.zeros((self.k, Sb, nnzb), dtype=np.int32)
        for b, (blk, col, _meta) in enumerate(per):
            blocks[b, :, :blk.shape[1]] = blk
            cols[b, :, :col.shape[1]] = col
        cached = (jnp.asarray(blocks), jnp.asarray(cols))
        self._bell[key] = cached
        return cached

    def block_jacobi_inv(self) -> jnp.ndarray:
        """(k, B, B) f32 inverses of the per-PU diagonal blocks of A.

        The diagonal block of PU b is assembled from the *local* edges the
        plan already extracted (cols < B — exactly the entries the interior
        + intra-block part of the matvec reads), so no second pass over the
        CSR input is needed.  Rows with no local entries (ghost padding
        rows, fully-halo rows) get an identity diagonal, which keeps their
        zero residuals out of the Krylov space — the same convention as
        :func:`cg.jacobi_preconditioner`.  Lazily computed and cached;
        dense O(k B^3) host inversion, intended for the benchmark/test
        scales this repo runs at (a production variant would sparse-
        Cholesky the local blocks instead).
        """
        if self._bj_inv is None:
            rows = np.asarray(self.rows)
            cols = np.asarray(self.cols)
            vals = np.asarray(self.vals, dtype=np.float64)
            k, nnz_pad = rows.shape
            per = np.asarray(self.nnz_blk, dtype=np.int64)
            valid = np.arange(nnz_pad)[None, :] < per[:, None]
            loc = valid & (cols < self.B)
            M = np.zeros((k, self.B, self.B), dtype=np.float64)
            bi, ei = np.nonzero(loc)
            np.add.at(M, (bi, rows[bi, ei], cols[bi, ei]), vals[bi, ei])
            zero_row = ~M.any(axis=2)                       # ghost + no-local
            zb, zr = np.nonzero(zero_row)
            M[zb, zr, zr] = 1.0
            self._bj_inv = jnp.asarray(np.linalg.inv(M).astype(np.float32))
        return self._bj_inv


def _edge_endpoints(indptr: np.ndarray, indices: np.ndarray):
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return src, np.asarray(indices)


def _pack_local_coo(indptr: np.ndarray, src: np.ndarray, data: np.ndarray,
                    part: np.ndarray, order: np.ndarray, k: int,
                    rows_l: np.ndarray, cols_l: np.ndarray,
                    per_blk: np.ndarray):
    """Pack edges per owning block into (k, nnz_pad) padded-COO arrays —
    scatter, no per-block loop.  The slot of edge e is derived from CSR
    structure in O(nnz) — no argsort: within a block, edges are laid out
    by (owner rank, CSR order), exactly the order a stable argsort over
    part[src] would give.  Shared by :func:`build_plan` and
    :func:`build_plan_hier` so the packed edge order (the invariant the
    bit-identity property tests guard) has one definition.

    Returns ``(rows_a, cols_a, vals_a, pos_edge)``.
    """
    n = len(indptr) - 1
    nnz_pad = max(int(per_blk.max()) if len(per_blk) else 1, 1)
    deg = np.diff(indptr)
    deg_o = deg[order]
    # edge start of each vertex inside its block's packed segment
    vstart = np.empty(n, dtype=np.int64)
    blk_edge_start = np.cumsum(per_blk) - per_blk
    vstart[order] = (np.cumsum(deg_o) - deg_o) - blk_edge_start[part[order]]
    pos_edge = (vstart[src]
                + (np.arange(len(src)) - np.repeat(indptr[:-1], deg)))
    own = part[src]
    rows_a = np.zeros((k, nnz_pad), dtype=np.int32)
    cols_a = np.zeros((k, nnz_pad), dtype=np.int32)
    vals_a = np.zeros((k, nnz_pad), dtype=np.float32)
    rows_a[own, pos_edge] = rows_l
    cols_a[own, pos_edge] = cols_l
    vals_a[own, pos_edge] = data
    return rows_a, cols_a, vals_a, pos_edge


def _pack_segment(rows_a: np.ndarray, cols_a: np.ndarray, vals_a: np.ndarray,
                  sel: np.ndarray):
    """Pack the edges selected by boolean mask ``sel`` (k, nnz_pad) into
    fresh (k, pad) arrays, preserving per-block packed edge order."""
    k = rows_a.shape[0]
    counts = sel.sum(axis=1)
    pad = max(int(counts.max()) if k else 0, 1)
    pos = np.cumsum(sel, axis=1) - 1
    b, e = np.nonzero(sel)
    r = np.zeros((k, pad), dtype=np.int32)
    c = np.zeros((k, pad), dtype=np.int32)
    v = np.zeros((k, pad), dtype=np.float32)
    p = pos[b, e]
    r[b, p] = rows_a[b, e]
    c[b, p] = cols_a[b, e]
    v[b, p] = vals_a[b, e]
    return r, c, v


def _derive_overlap_fields(rows_a: np.ndarray, cols_a: np.ndarray,
                           vals_a: np.ndarray, per_blk: np.ndarray,
                           B: int) -> dict:
    """Split each block's packed COO into interior/boundary row segments.

    A local row is *boundary* iff any of its edges has a halo-slot column
    (col >= B); every edge of a boundary row — including its local ones —
    goes to the boundary segment, so the interior matvec depends only on
    x_loc and can be issued before (and overlap with) the ppermute rounds.
    Within a block the original packed edge order is preserved in both
    segments, and interior + boundary exactly tile the true nnz set.

    Also extracts the (k, B) diagonal of A (rows == cols can only hold for
    local edges, and local ranks are unique, so rows == cols <=> src == dst)
    for Jacobi preconditioning.  Pure vectorized NumPy; derived only from
    the packed arrays, so both plan builders get bit-identical fields.
    """
    k, nnz_pad = rows_a.shape
    per_blk = np.asarray(per_blk, dtype=np.int64)
    valid = np.arange(nnz_pad)[None, :] < per_blk[:, None]     # (k, nnz_pad)
    halo_edge = valid & (cols_a >= B)
    bnd_row = np.zeros((k, B), dtype=bool)
    bi, ei = np.nonzero(halo_edge)
    bnd_row[bi, rows_a[bi, ei]] = True
    blk_col = np.arange(k)[:, None]
    edge_bnd = valid & bnd_row[blk_col, rows_a]
    edge_int = valid & ~edge_bnd

    pack = functools.partial(_pack_segment, rows_a, cols_a, vals_a)
    rows_int, cols_int, vals_int = pack(edge_int)
    rows_bnd, cols_bnd, vals_bnd = pack(edge_bnd)

    diag = np.zeros((k, B), dtype=np.float32)
    on_diag = valid & (rows_a == cols_a)
    db, de = np.nonzero(on_diag)
    np.add.at(diag, (db, rows_a[db, de]), vals_a[db, de])
    return dict(
        rows_int=jnp.asarray(rows_int), cols_int=jnp.asarray(cols_int),
        vals_int=jnp.asarray(vals_int), rows_bnd=jnp.asarray(rows_bnd),
        cols_bnd=jnp.asarray(cols_bnd), vals_bnd=jnp.asarray(vals_bnd),
        diag=jnp.asarray(diag), nnz_blk=per_blk.copy(),
        _bnd_row=bnd_row,
    )


# build_plan uses O(k*n) dense tables (counting sorts) up to this many
# cells.  The widest live table is the int32 halo-slot map (4 B/cell; the
# bool bitmaps are freed before it is allocated), so the single-shot dense
# path peaks at ~64 MiB of transient tables at this limit.  Beyond it the
# bitmap is *sharded by vertex range*: the same dedupe runs one
# O(k * chunk) chunk at a time (chunk sized so k * chunk stays at the
# limit), so production-scale k*n keeps the counting-sort extraction
# instead of falling back to O(E log E) comparison sorts.  Module-level so
# tests can force the sharded path.
DENSE_PLAN_LIMIT = 1 << 24


def _block_layout(part: np.ndarray, k: int, dense: bool = False):
    """Block-contiguous vertex layout shared by all plan builders.

    Returns ``(sizes, B, order, rank_in_block, perm, block_of)``.  With
    ``dense`` a (k, n) one-hot flatnonzero replaces the argsort — that is
    the counting sort for the (block, id) key directly, so both paths
    yield the identical ``order``.
    """
    n = len(part)
    sizes = np.bincount(part, minlength=k)
    B = int(sizes.max())
    if dense:
        onehot = np.zeros(k * n, dtype=bool)
        onehot[part.astype(np.int64) * n + np.arange(n)] = True
        order = np.flatnonzero(onehot) % n             # new (unpadded) -> old
        del onehot
    else:
        order = np.argsort(part, kind="stable")
    starts = np.zeros(k + 1, dtype=np.int64)
    np.cumsum(sizes, out=starts[1:])
    rank_in_block = np.empty(n, dtype=np.int32)
    rank_in_block[order] = np.arange(n, dtype=np.int64) - starts[part[order]]
    perm = part.astype(np.int64) * B + rank_in_block   # padded new id
    block_of = np.arange(k, dtype=np.int64) * B
    return sizes, B, order, rank_in_block, perm, block_of


def _ext_col_slots(flat_post: np.ndarray, flat_sorted, o2: np.ndarray,
                   slot_of_trip: np.ndarray, ext_keys: np.ndarray,
                   k: int, n: int, dense: bool) -> np.ndarray:
    """Halo slot per external edge, from the per-triple slots.

    Dense path: scatter the slots into a (k, n) table and gather by edge
    key.  Sharded path: no O(k*n) table — binary-search the sorted
    (recv, v) keys instead (``slot_at[p]`` = slot of the p-th sorted key).
    Shared by :func:`build_plan` and :func:`build_plan_hier`.
    """
    if dense:
        slot_arr = np.empty(k * n, dtype=np.int32)     # (recv, v) -> slot
        slot_arr[flat_post] = slot_of_trip
        return slot_arr[ext_keys]
    slot_at = np.empty(len(flat_sorted), dtype=np.int32)
    slot_at[o2] = slot_of_trip
    return slot_at[np.searchsorted(flat_sorted, ext_keys)]


def _halo_recv_v_pairs(part: np.ndarray, psrc: np.ndarray, dst: np.ndarray,
                       ext: np.ndarray, k: int, n: int, dense: bool):
    """Deduped (receiver, vertex) halo pairs, ascending by ``recv*n + v``.

    Two equivalent bitmap paths (identical output), shared by
    :func:`build_plan` and :func:`build_plan_hier`:

      dense   — O(nnz + k*n): one (k, n) needed-bitmap + flatnonzero.
                Used when the bitmap fits (k*n <= DENSE_PLAN_LIMIT cells).
      sharded — the same dedupe one vertex-range chunk at a time
                (k * chunk <= DENSE_PLAN_LIMIT cells live at once) for
                production-scale k*n; per chunk the flatnonzero gives
                (recv, v) ascending, and chunks partition the v range, so
                one stable radix pass on recv restores global order.

    Returns ``(flat, ext_keys)``: the sorted unique keys and the per-ext-
    edge key (int32 on the dense path — k*n fits — int64 on the sharded).
    """
    if dense:
        needed = np.zeros(k * n, dtype=bool)
        ext_keys = psrc[ext] * np.int32(n) + dst[ext]
        needed[ext_keys] = True
        flat = np.flatnonzero(needed)                  # sorted (recv, v)
        return flat, ext_keys
    e_recv, e_dst = psrc[ext].astype(np.int64), dst[ext].astype(np.int64)
    ext_keys = e_recv * n + e_dst
    cn = max(1, DENSE_PLAN_LIMIT // max(k, 1))
    chunk_of = e_dst // cn
    n_chunks = -(-n // cn)
    ord_c = np.argsort(chunk_of, kind="stable")
    bounds = np.searchsorted(chunk_of[ord_c], np.arange(n_chunks + 1))
    parts_flat = []
    for ci in range(n_chunks):
        sl = ord_c[bounds[ci]:bounds[ci + 1]]
        if not len(sl):
            continue
        v0 = ci * cn
        width = min(cn, n - v0)
        bm = np.zeros(k * width, dtype=bool)
        bm[e_recv[sl] * width + (e_dst[sl] - v0)] = True
        fz = np.flatnonzero(bm)                        # sorted (recv, v_loc)
        parts_flat.append((fz // width) * np.int64(n) + v0 + fz % width)
    flat = (np.concatenate(parts_flat) if parts_flat
            else np.zeros(0, dtype=np.int64))
    return flat[np.argsort(flat // n, kind="stable")], ext_keys


def _maybe_verify(plan, validate):
    """Run the structural verifier on a freshly built plan.

    ``validate=None`` defers to the ``REPRO_VALIDATE`` env var (the test
    suite turns it on via conftest; production builds skip the pass unless
    asked).  Raises ``analysis.PlanVerificationError`` (a ``ValueError``)
    with every violated invariant when the plan is corrupt.
    """
    if validate is None:
        validate = os.environ.get("REPRO_VALIDATE", "0") not in ("", "0")
    if validate:
        from ..analysis import verify_plan      # lazy: keep import acyclic
        verify_plan(plan).raise_for_errors()
    return plan


def build_plan(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray,
               part: np.ndarray, k: int,
               validate: bool | None = None) -> DistPlan:
    """Build the distributed plan for matrix (CSR) + partition — vectorized.

    O(nnz log nnz) in NumPy kernels (the log from sorts); no Python
    iteration over vertices, edges, or halo slots.  ``validate=`` runs the
    ``repro.analysis`` structural verifier on the result (default: the
    ``REPRO_VALIDATE`` env var).
    """
    n = len(indptr) - 1
    part = np.ascontiguousarray(part, dtype=np.int32)
    # dense-table mode: O(k*n) bitmaps replace O(x log x) sorts wherever a
    # small-range counting sort suffices; vertex-sharded bitmaps beyond
    dense = k * n <= DENSE_PLAN_LIMIT
    sizes, B, order, rank_in_block, perm, block_of = _block_layout(
        part, k, dense=dense)

    # ---- halo triples: (receiver, owner, vertex), deduped & sorted -------
    src, dst = _edge_endpoints(indptr, indices)
    psrc, pdst = part[src], part[dst]
    ext = psrc != pdst
    flat, ext_keys = _halo_recv_v_pairs(part, psrc, dst, ext, k, n, dense)
    flat_sorted = None if dense else flat              # ascending (recv, v)
    t_v = flat % n
    # small-range pair keys: 1-2 radix passes in the stable argsort below
    pair_t = np.int16 if k * k <= np.iinfo(np.int16).max else np.int32
    t_pair = ((flat // n).astype(pair_t) * pair_t(k)
              + part[t_v].astype(pair_t))              # recv*k + own
    o2 = np.argsort(t_pair, kind="stable")             # radix; keeps v asc
    t_pair, t_v, flat = t_pair[o2], t_v[o2], flat[o2]
    # triples sharing a (recv, own) pair are contiguous and sorted by v;
    # halo slot position = rank within the pair group.  t_pair is sorted,
    # so pair groups fall out of the boundary flags — no second unique/sort.
    m = len(t_pair)
    newp = np.empty(m, dtype=bool)
    if m:
        newp[0] = True
        np.not_equal(t_pair[1:], t_pair[:-1], out=newp[1:])
    grp_first = np.flatnonzero(newp)                   # triple idx per pair
    uniq_pairs = t_pair[grp_first]
    pair_counts = np.diff(np.append(grp_first, m))
    pair_of_trip = np.cumsum(newp) - 1
    t_pos = np.arange(m) - grp_first[pair_of_trip]
    S = int(pair_counts.max()) if len(pair_counts) else 1
    S = max(1, S)

    # ---- edge-color the undirected quotient graph ------------------------
    p_recv, p_own = uniq_pairs // k, uniq_pairs % k
    und_key = (np.minimum(p_recv, p_own) * k + np.maximum(p_recv, p_own))
    uniq_und = np.unique(und_key)
    und_a, und_b = uniq_und // k, uniq_und % k
    und_w = np.zeros(len(uniq_und), dtype=np.float64)
    np.add.at(und_w, np.searchsorted(uniq_und, und_key), pair_counts)
    qp = np.stack([und_a, und_b], axis=1).astype(np.int64)
    colors = (vizing_edge_coloring(qp, und_w) if len(qp)
              else np.zeros(0, np.int32))
    n_rounds = int(colors.max() + 1) if len(colors) else 1
    # (k, k) directed-pair -> round lookup (tiny), so per-triple color is a
    # single gather instead of min/max arithmetic over all triples
    color_dir = np.zeros(k * k, dtype=np.int32)
    color_dir[und_a * k + und_b] = colors
    color_dir[und_b * k + und_a] = colors
    t_color = color_dir[t_pair]

    # ---- send schedule (owner side) --------------------------------------
    # each color class is a matching, so an owner serves one receiver per
    # round: the (own, color, pos) scatter below has no collisions.
    send_idx = np.zeros((k, n_rounds, S), dtype=np.int32)
    send_mask = np.zeros((k, n_rounds, S), dtype=np.float32)
    t_own = (uniq_pairs % k)[pair_of_trip]        # owner of each triple
    send_idx[t_own, t_color, t_pos] = rank_in_block[t_v]
    send_mask[t_own, t_color, t_pos] = 1.0
    pair_color = color_dir[und_a * k + und_b]
    round_perms: list[list[tuple[int, int]]] = [[] for _ in range(n_rounds)]
    for a, b, c in zip(und_a.tolist(), und_b.tolist(), pair_color.tolist()):
        # o->r and r->o swap in the same round (bidirectional ppermute)
        round_perms[c].append((a, b))
        round_perms[c].append((b, a))

    # ---- local matrix in padded-COO with remapped columns ----------------
    rows_l = rank_in_block[src]
    # local rank everywhere, then overwrite external edges with halo slots
    cols_l = rank_in_block[dst]
    # halo slot of remote vertex u on receiver r: B + round*S + pos,
    # precomputed per triple so the per-edge remap is one gather
    slot_of_trip = (B + t_color * S + t_pos).astype(np.int32)
    cols_l[ext] = _ext_col_slots(flat, flat_sorted, o2, slot_of_trip,
                                 ext_keys, k, n, dense)
    own = psrc
    per_blk = np.bincount(own, minlength=k)
    rows_a, cols_a, vals_a, pos_edge = _pack_local_coo(
        indptr, src, data, part, order, k, rows_l, cols_l, per_blk)

    row_mask = (np.arange(B)[None, :] < sizes[:, None]).astype(np.float32)

    split = _derive_overlap_fields(rows_a, cols_a, vals_a, per_blk, B)
    bnd_row = split.pop("_bnd_row")
    interior_mask = row_mask * ~bnd_row

    return _maybe_verify(DistPlan(
        k=k, B=B, S=S, n_rounds=n_rounds, n=n, perm=perm, block_of=block_of,
        sizes=sizes,
        rows=jnp.asarray(rows_a), cols=jnp.asarray(cols_a),
        vals=jnp.asarray(vals_a), row_mask=jnp.asarray(row_mask),
        send_idx=jnp.asarray(send_idx), send_mask=jnp.asarray(send_mask),
        round_perms=tuple(tuple(r) for r in round_perms),
        interior_mask=jnp.asarray(interior_mask), **split,
        _pack_blk=own, _pack_pos=pos_edge, _pack_dst=dst,
    ), validate)


def build_plan_reference(indptr: np.ndarray, indices: np.ndarray,
                         data: np.ndarray, part: np.ndarray,
                         k: int) -> DistPlan:
    """The seed's per-edge plan builder, kept verbatim (modulo the removed
    dead ``loc`` placeholder) as the oracle for tests and the baseline for
    the vectorization speedup benchmark.  O(|halo|) Python iteration —
    do not use beyond toy meshes."""
    n = len(indptr) - 1
    part = np.asarray(part)
    sizes = np.bincount(part, minlength=k)
    B = int(sizes.max())
    order = np.argsort(part, kind="stable")
    starts = np.zeros(k + 1, dtype=np.int64)
    np.cumsum(sizes, out=starts[1:])
    rank_in_block = np.empty(n, dtype=np.int64)
    rank_in_block[order] = np.arange(n) - starts[part[order]]
    perm = part.astype(np.int64) * B + rank_in_block
    block_of = np.arange(k, dtype=np.int64) * B

    src, dst = _edge_endpoints(indptr, indices)
    ext = part[src] != part[dst]
    recv_blk = part[src][ext].astype(np.int64)
    own_blk = part[dst][ext].astype(np.int64)
    needed = dst[ext].astype(np.int64)
    pair_key = recv_blk * k + own_blk
    uniq_keys, inv = np.unique(pair_key, return_inverse=True)
    need_map: dict[tuple[int, int], np.ndarray] = {}
    for i, key in enumerate(uniq_keys):
        r, o = int(key // k), int(key % k)
        need_map[(r, o)] = np.unique(needed[inv == i])

    und_pairs = sorted({(min(r, o), max(r, o)) for (r, o) in need_map})
    qp = np.array(und_pairs, dtype=np.int64).reshape(-1, 2)
    qw = np.array([len(need_map.get((a, b), ())) +
                   len(need_map.get((b, a), ())) for a, b in und_pairs],
                  dtype=np.float64)
    colors = (vizing_edge_coloring(qp, qw) if len(qp)
              else np.zeros(0, np.int32))
    n_rounds = int(colors.max() + 1) if len(colors) else 1
    S = max(1, max((len(v) for v in need_map.values()), default=1))

    send_idx = np.zeros((k, n_rounds, S), dtype=np.int32)
    send_mask = np.zeros((k, n_rounds, S), dtype=np.float32)
    halo_slot: dict[tuple[int, int], int] = {}
    round_perms: list[list[tuple[int, int]]] = [[] for _ in range(n_rounds)]
    for e, (a, b) in enumerate(und_pairs):
        c = int(colors[e])
        for (o, r) in ((a, b), (b, a)):
            need = need_map.get((r, o))
            if need is None or len(need) == 0:
                continue
            loc = rank_in_block[need].astype(np.int32)
            send_idx[o, c, :len(need)] = loc
            send_mask[o, c, :len(need)] = 1.0
            for p, u in enumerate(need):
                halo_slot[(r, int(u))] = B + c * S + p
        round_perms[c].append((a, b))
        round_perms[c].append((b, a))

    rows_l = rank_in_block[src].astype(np.int32)
    cols_l = np.empty(len(dst), dtype=np.int32)
    same = ~ext
    cols_l[same] = rank_in_block[dst[same]].astype(np.int32)
    for i in np.nonzero(ext)[0]:
        cols_l[i] = halo_slot[(int(part[src[i]]), int(dst[i]))]
    own = part[src]
    per_blk = np.bincount(own, minlength=k)
    nnz_pad = max(int(per_blk.max()) if len(per_blk) else 1, 1)
    rows_a = np.zeros((k, nnz_pad), dtype=np.int32)
    cols_a = np.zeros((k, nnz_pad), dtype=np.int32)
    vals_a = np.zeros((k, nnz_pad), dtype=np.float32)
    ord2 = np.argsort(own, kind="stable")
    off = np.zeros(k + 1, dtype=np.int64)
    np.cumsum(per_blk, out=off[1:])
    for b in range(k):
        sl = ord2[off[b]:off[b + 1]]
        rows_a[b, :len(sl)] = rows_l[sl]
        cols_a[b, :len(sl)] = cols_l[sl]
        vals_a[b, :len(sl)] = data[sl]

    row_mask = np.zeros((k, B), dtype=np.float32)
    for b in range(k):
        row_mask[b, :sizes[b]] = 1.0

    split = _derive_overlap_fields(rows_a, cols_a, vals_a, per_blk, B)
    bnd_row = split.pop("_bnd_row")
    interior_mask = row_mask * ~bnd_row

    blk_e = own[ord2]
    return DistPlan(
        k=k, B=B, S=S, n_rounds=n_rounds, n=n, perm=perm, block_of=block_of,
        sizes=sizes,
        rows=jnp.asarray(rows_a), cols=jnp.asarray(cols_a),
        vals=jnp.asarray(vals_a), row_mask=jnp.asarray(row_mask),
        send_idx=jnp.asarray(send_idx), send_mask=jnp.asarray(send_mask),
        round_perms=tuple(tuple(r) for r in round_perms),
        interior_mask=jnp.asarray(interior_mask), **split,
        _pack_blk=blk_e,
        _pack_pos=np.arange(len(src)) - off[blk_e],
        _pack_dst=dst[ord2],
    )


# --------------------------------------------------------------------------
# hierarchical (arbitrary-depth tree) plans
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TreePlan(DistPlan):
    """Arbitrary-depth tree plan for hierarchical meshes
    (:func:`build_plan_tree`; the two-level :func:`build_plan_hier` is
    the ``h == 2`` instance).

    Blocks are *tree-major*: device position = the leaf slot of the
    ``fanouts`` mixed radix (outermost digit first), matching a
    ``P((axis_1, ..., axis_h))`` sharding of the leading block axis.
    Halo edges are split by the LCA level of their block pair (level 0 =
    siblings, level h-1 = root-crossing), one segment per level, each
    with its own Misra-Gries coloring over that level's quotient graph —
    nodes are *suffix* indices (the last ``level + 1`` radix digits), so
    one ppermute schedule over the level's axis suffix fires in every
    subtree at once (blocks without a given edge send masked zeros);
    the outermost level linearizes all axes, exactly PR 3's inter-pod
    class.

    The extended vector layout is ``[x_loc | lvl-0 slots | ... |
    lvl-(h-1) slots]``: a boundary row's class is the highest level it
    reads, so each class's accumulation waits only on its own and faster
    levels' exchanges.  The base-class flat schedule fields
    (``send_idx`` / ``send_mask`` / ``round_perms`` / ``rows_bnd``...)
    are *not populated* — a TreePlan only runs under ``comm='hier'``
    (enforced by the matvec builder).  The two-level field names of the
    PR 3-4 API (``S_intra`` / ``n_rounds_inter`` / ``send_idx_intra`` /
    ``rows_bnd_inter`` / ``pods`` / ``k_local`` / ``pod_of``...) remain
    available as read-only views of the level tuples.
    """

    fanouts: tuple = ()                 # (k_1, ..., k_h), prod == k
    anc: np.ndarray = None              # (h-1, k) canonical table, tree-major
    block_map: np.ndarray = None        # (k,) original block id -> device pos
    S_lvl: tuple = ()                   # per-level halo slots per round
    n_rounds_lvl: tuple = ()            # per-level colored round count
    send_idx_lvl: tuple = ()            # per level: (k, R_l, S_l) int32
    send_mask_lvl: tuple = ()           # per level: (k, R_l, S_l) f32
    round_perms_lvl: tuple = ()         # per level, per round:
    #                                     suffix-linearized (src, dst) pairs
    rows_bnd_lvl: tuple = ()            # per level: rows whose highest
    cols_bnd_lvl: tuple = ()            #   read is that level's slot range
    vals_bnd_lvl: tuple = ()

    # -- tree structure -----------------------------------------------------
    @property
    def h(self) -> int:
        return len(self.fanouts)

    def level_offsets(self) -> np.ndarray:
        """(h+1,) slot-range boundaries of the extended vector: level l
        slots live in ``[offs[l], offs[l+1])``; ``offs[0] == B``."""
        sizes = [r * s for r, s in zip(self.n_rounds_lvl, self.S_lvl)]
        return self.B + np.concatenate([[0], np.cumsum(sizes)]).astype(int)

    # -- two-level views (the PR 3-4 HierPlan API) --------------------------
    @property
    def pods(self) -> int:
        return self.fanouts[0] if self.h >= 2 else 1

    @property
    def k_local(self) -> int:
        return self.k // self.pods

    @property
    def pod_of(self) -> np.ndarray:
        """(k,) top-level group of each tree-major block."""
        return np.arange(self.k, dtype=np.int64) // self.k_local

    def _two_level(self, name: str, idx: int):
        if self.h > 2:
            raise AttributeError(
                f"{name} is the two-level view; this plan is depth "
                f"{self.h} — use the *_lvl tuples")
        return idx

    @property
    def S_intra(self) -> int:
        return self.S_lvl[self._two_level("S_intra", 0)]

    @property
    def S_inter(self) -> int:
        self._two_level("S_inter", 1)
        return self.S_lvl[1] if self.h >= 2 else 1

    @property
    def n_rounds_intra(self) -> int:
        return self.n_rounds_lvl[self._two_level("n_rounds_intra", 0)]

    @property
    def n_rounds_inter(self) -> int:
        self._two_level("n_rounds_inter", 1)
        return self.n_rounds_lvl[1] if self.h >= 2 else 0

    @property
    def send_idx_intra(self):
        return self.send_idx_lvl[self._two_level("send_idx_intra", 0)]

    @property
    def send_mask_intra(self):
        return self.send_mask_lvl[self._two_level("send_mask_intra", 0)]

    @property
    def send_idx_inter(self):
        return self.send_idx_lvl[self._two_level("send_idx_inter", 1)]

    @property
    def send_mask_inter(self):
        return self.send_mask_lvl[self._two_level("send_mask_inter", 1)]

    @property
    def round_perms_intra(self) -> tuple:
        return self.round_perms_lvl[self._two_level("round_perms_intra", 0)]

    @property
    def round_perms_inter(self) -> tuple:
        return self.round_perms_lvl[self._two_level("round_perms_inter", 1)]

    @property
    def rows_bnd_intra(self):
        return self.rows_bnd_lvl[self._two_level("rows_bnd_intra", 0)]

    @property
    def cols_bnd_intra(self):
        return self.cols_bnd_lvl[self._two_level("cols_bnd_intra", 0)]

    @property
    def vals_bnd_intra(self):
        return self.vals_bnd_lvl[self._two_level("vals_bnd_intra", 0)]

    @property
    def rows_bnd_inter(self):
        return self.rows_bnd_lvl[self._two_level("rows_bnd_inter", 1)]

    @property
    def cols_bnd_inter(self):
        return self.cols_bnd_lvl[self._two_level("cols_bnd_inter", 1)]

    @property
    def vals_bnd_inter(self):
        return self.vals_bnd_lvl[self._two_level("vals_bnd_inter", 1)]


# The two-level plan is the h == 2 TreePlan; the name is kept as the
# PR 3-4 API (isinstance checks and imports continue to work).
HierPlan = TreePlan


def _class_schedule(t_pair: np.ndarray, t_v: np.ndarray, k: int,
                    q_of: np.ndarray, nq: int, rank_in_block: np.ndarray):
    """Schedule one halo class (intra- or inter-pod) of directed-pair
    triples.

    ``t_pair`` (sorted ``recv*k + own`` keys; triples within a pair sorted
    by vertex) is grouped into pair runs; the class's quotient graph —
    nodes ``q_of[block]`` (local pu index for intra, global block id for
    inter), so intra edges from *different pods* with the same local
    endpoints merge into one colored edge and share a ppermute pair — is
    Misra-Gries edge-colored; the owner-side send schedule and per-triple
    halo slots fall out of (color, position-in-pair).

    Returns ``(S, n_rounds, send_idx, send_mask, round_pairs, slot)`` with
    ``slot`` the *relative* slot ``color * S + pos`` per triple and
    ``round_pairs[c]`` the bidirectional quotient-node pairs of round c.
    """
    m = len(t_pair)
    newp = np.empty(m, dtype=bool)
    if m:
        newp[0] = True
        np.not_equal(t_pair[1:], t_pair[:-1], out=newp[1:])
    grp_first = np.flatnonzero(newp)
    uniq_pairs = t_pair[grp_first].astype(np.int64)
    pair_counts = np.diff(np.append(grp_first, m))
    pair_of_trip = np.cumsum(newp) - 1
    t_pos = np.arange(m) - grp_first[pair_of_trip] if m else np.zeros(0, int)
    S = max(1, int(pair_counts.max()) if len(pair_counts) else 1)

    p_recv, p_own = uniq_pairs // k, uniq_pairs % k
    q_r, q_o = q_of[p_recv], q_of[p_own]
    und_key = np.minimum(q_r, q_o) * nq + np.maximum(q_r, q_o)
    uniq_und, und_inv = np.unique(und_key, return_inverse=True)
    und_a, und_b = uniq_und // nq, uniq_und % nq
    und_w = np.zeros(len(uniq_und), dtype=np.float64)
    np.add.at(und_w, und_inv, pair_counts)
    qp = np.stack([und_a, und_b], axis=1).astype(np.int64)
    colors = (vizing_edge_coloring(qp, und_w) if len(qp)
              else np.zeros(0, np.int32))
    n_rounds = int(colors.max() + 1) if len(colors) else 0
    color_dir = np.zeros(nq * nq, dtype=np.int32)
    color_dir[und_a * nq + und_b] = colors
    color_dir[und_b * nq + und_a] = colors
    t_color = (color_dir[q_of[(t_pair.astype(np.int64)) // k] * nq
                         + q_of[t_pair.astype(np.int64) % k]]
               if m else np.zeros(0, np.int32))

    send_idx = np.zeros((k, n_rounds, S), dtype=np.int32)
    send_mask = np.zeros((k, n_rounds, S), dtype=np.float32)
    t_own = (uniq_pairs % k)[pair_of_trip] if m else np.zeros(0, int)
    send_idx[t_own, t_color, t_pos] = rank_in_block[t_v]
    send_mask[t_own, t_color, t_pos] = 1.0
    round_pairs: list[list[tuple[int, int]]] = [[] for _ in range(n_rounds)]
    pair_color = color_dir[und_a * nq + und_b]
    for a, b, c in zip(und_a.tolist(), und_b.tolist(), pair_color.tolist()):
        round_pairs[c].append((a, b))
        round_pairs[c].append((b, a))
    slot = (t_color * S + t_pos).astype(np.int32)
    return (S, n_rounds, send_idx, send_mask,
            tuple(tuple(r) for r in round_pairs), slot)


def _derive_tree_fields_np(rows_a: np.ndarray, cols_a: np.ndarray,
                           vals_a: np.ndarray, per_blk: np.ndarray,
                           B: int, offs: np.ndarray) -> dict:
    """NumPy core of :func:`_derive_tree_fields` — host arrays only.

    Besides the packed segments it returns the per-edge segment
    bookkeeping (``seg_lvl``/``seg_pos``/``seg_counts``, ``row_lvl`` and
    the diagonal entry positions) that :mod:`repro.sparse.replan` uses to
    patch segments in place instead of re-deriving all blocks.
    """
    k, nnz_pad = rows_a.shape
    h = len(offs) - 1
    per_blk = np.asarray(per_blk, dtype=np.int64)
    valid = np.arange(nnz_pad)[None, :] < per_blk[:, None]
    # per-edge slot level: -1 local, l for cols in [offs[l], offs[l+1])
    edge_lvl = np.searchsorted(np.asarray(offs), cols_a, side="right") - 1
    edge_lvl = np.where(valid, edge_lvl, -1)
    # per-row highest level read
    row_lvl = np.full((k, B), -1, dtype=np.int64)
    bi, ei = np.nonzero(valid)
    np.maximum.at(row_lvl, (bi, rows_a[bi, ei]), edge_lvl[bi, ei])

    blk_col = np.arange(k)[:, None]
    row_lvl_of_edge = row_lvl[blk_col, rows_a]
    # per-edge segment (-2 padding, -1 interior, l = boundary level) and
    # the edge's packed position inside that segment
    seg_lvl = np.where(valid, row_lvl_of_edge, -2).astype(np.int8)
    seg_pos = np.zeros((k, nnz_pad), dtype=np.int32)
    seg_counts = np.zeros((h + 1, k), dtype=np.int64)
    segs = []
    for s in range(-1, h):
        sel = valid & (row_lvl_of_edge == s)
        counts = sel.sum(axis=1)
        seg_counts[s + 1] = counts
        pad = max(int(counts.max()) if k else 0, 1)
        pos = np.cumsum(sel, axis=1) - 1
        b, e = np.nonzero(sel)
        p = pos[b, e]
        seg_pos[b, e] = p.astype(np.int32)
        r = np.zeros((k, pad), dtype=np.int32)
        c = np.zeros((k, pad), dtype=np.int32)
        v = np.zeros((k, pad), dtype=np.float32)
        r[b, p] = rows_a[b, e]
        c[b, p] = cols_a[b, e]
        v[b, p] = vals_a[b, e]
        segs.append((r, c, v))

    diag = np.zeros((k, B), dtype=np.float32)
    on_diag = valid & (rows_a == cols_a)
    db, de = np.nonzero(on_diag)
    np.add.at(diag, (db, rows_a[db, de]), vals_a[db, de])
    return dict(
        int_seg=segs[0], lvl_segs=segs[1:], diag=diag,
        nnz_blk=per_blk.copy(), row_lvl=row_lvl,
        seg_lvl=seg_lvl, seg_pos=seg_pos, seg_counts=seg_counts,
        diag_b=db, diag_e=de,
    )


def _derive_tree_fields(rows_a: np.ndarray, cols_a: np.ndarray,
                        vals_a: np.ndarray, per_blk: np.ndarray,
                        B: int, offs: np.ndarray) -> dict:
    """(h+1)-way interior / per-level boundary split.

    A row's class is the *highest* slot level any of its edges reads
    (``offs`` are the level-range boundaries, ``offs[0] == B``; reads
    below B are local).  Every edge of a row goes to the row's segment,
    so the h+1 segments exactly tile the true nnz set and the PR 2
    boundary set is the union of the level segments.  The interior
    criterion (no halo reads at all) is identical to the flat plan's, so
    the interior segment is bit-equal to :func:`build_plan`'s on the
    same partition; at ``h == 2`` the level segments are exactly PR 3's
    intra-/inter-pod split.  The ``_host`` entry carries the NumPy core's
    raw output for the replan cache (popped by :func:`build_plan_tree`).
    """
    host = _derive_tree_fields_np(rows_a, cols_a, vals_a, per_blk, B, offs)
    rows_int, cols_int, vals_int = host["int_seg"]
    lvl_seg = host["lvl_segs"]
    return dict(
        rows_int=jnp.asarray(rows_int), cols_int=jnp.asarray(cols_int),
        vals_int=jnp.asarray(vals_int),
        rows_bnd_lvl=tuple(jnp.asarray(r) for r, _, _ in lvl_seg),
        cols_bnd_lvl=tuple(jnp.asarray(c) for _, c, _ in lvl_seg),
        vals_bnd_lvl=tuple(jnp.asarray(v) for _, _, v in lvl_seg),
        diag=jnp.asarray(host["diag"]), nnz_blk=host["nnz_blk"],
        _bnd_row=host["row_lvl"] >= 0,
        _host=host,
    )


def build_plan_tree(indptr: np.ndarray, indices: np.ndarray,
                    data: np.ndarray, part: np.ndarray,
                    tree, k: int, fanouts=None,
                    validate: bool | None = None,
                    cache: bool = True) -> TreePlan:
    """Build the arbitrary-depth distributed plan for a tree mesh.

    ``tree`` is anything ``core.topology.normalize_tree_of`` accepts: a
    pod count or (k,) pod array (the two-level instance), an explicit
    (h-1, k) ancestor table — e.g. the partition-derived table of
    ``core.api.partition_tree`` / ``tree_assignment_for`` (generally
    non-contiguous after the per-level sweeps) — or ``None`` with
    ``fanouts`` for the canonical contiguous grouping.  Every level must
    group blocks equally (the tree meshes are rectangular).  Blocks are
    relabeled tree-major (lexicographic by ancestor path); ``block_map``
    maps the caller's block ids to device positions (scatter/gather are
    unaffected — they go through ``perm``).

    Each tree level gets its own Misra-Gries coloring of its quotient
    graph over *suffix* indices (the last ``level + 1`` mixed-radix
    digits), so one ppermute schedule over the level's axis suffix fires
    in every subtree at once; the outermost level linearizes the full
    axis tuple.  Vectorized NumPy throughout; the only Python loops are
    over tree levels, quotient edges and chunks, as in
    :func:`build_plan`.
    """
    from ..core.topology import normalize_tree_of

    n = len(indptr) - 1
    part = np.ascontiguousarray(part, dtype=np.int32)
    # one validation definition shared with the partitioner side
    # (core.api.partition_tree produces what this consumes)
    anc_in = normalize_tree_of(tree, k, fanouts)
    h = anc_in.shape[0] + 1
    # tree-major relabeling: device position = leaf slot of the mixed
    # radix — stable lexicographic by ancestor path (top row primary),
    # the depth-h generalization of build_plan_hier's pod-major argsort
    order_blocks = (np.lexsort(tuple(anc_in[::-1])) if h > 1
                    else np.arange(k, dtype=np.int64))
    block_map = np.empty(k, dtype=np.int64)
    block_map[order_blocks] = np.arange(k)
    part = block_map[part].astype(np.int32)
    # canonical table / fanouts of the relabeled (device-position) blocks
    counts = [int(anc_in[t].max()) + 1 for t in range(h - 1)] + [k]
    fanouts_out, prev = [], 1
    for c in counts:
        fanouts_out.append(c // prev)
        prev = c
    fanouts_out = tuple(fanouts_out)
    # suffix size of level l = prod(fanouts[h-1-l:]): the range its
    # quotient nodes (and ppermute indices) live in
    suffix = [1] * (h + 1)
    for t in range(h - 1, -1, -1):
        suffix[h - 1 - t + 1] = suffix[h - 1 - t] * fanouts_out[t]
    dev = np.arange(k, dtype=np.int64)
    anc_dev = np.stack([dev // suffix[h - 1 - t]
                        for t in range(h - 1)]) if h > 1 else \
        np.zeros((0, k), dtype=np.int64)

    dense = k * n <= DENSE_PLAN_LIMIT
    sizes, B, order, rank_in_block, perm, block_of = _block_layout(
        part, k, dense=dense)

    # ---- halo triples, split by LCA level -------------------------------
    # same dense/vertex-sharded bitmap extraction as build_plan (one
    # definition, DENSE_PLAN_LIMIT respected), then triples ordered by
    # (directed pair, vertex) via the stable radix pass
    src, dst = _edge_endpoints(indptr, indices)
    psrc, pdst = part[src], part[dst]
    ext = psrc != pdst
    flat, ext_keys = _halo_recv_v_pairs(part, psrc, dst, ext, k, n, dense)
    flat_sorted = None if dense else flat              # ascending (recv, v)
    t_v_pre = flat % n
    t_pair_pre = ((flat // n).astype(np.int64) * k
                  + part[t_v_pre].astype(np.int64))    # recv*k + own
    o2 = np.argsort(t_pair_pre, kind="stable")         # keeps v ascending
    t_pair_all = t_pair_pre[o2]
    t_v_all = t_v_pre[o2]
    flat_post = flat[o2]
    # LCA level per triple: highest level whose suffix indices differ
    t_recv, t_own = t_pair_all // k, t_pair_all % k
    t_lvl = np.zeros(len(t_pair_all), dtype=np.int64)
    for l in range(h):
        differ = (t_recv // suffix[l]) != (t_own // suffix[l])
        t_lvl = np.where(differ, l, t_lvl)

    S_lvl, R_lvl, si_lvl, sm_lvl, perms_lvl = [], [], [], [], []
    slot_of_trip = np.empty(len(t_pair_all), dtype=np.int32)
    off = B
    for l in range(h):
        sel = t_lvl == l
        sz = suffix[l + 1]
        S_l, R_l, si, sm, perms, slot = _class_schedule(
            t_pair_all[sel], t_v_all[sel], k, dev % sz, sz, rank_in_block)
        slot_of_trip[sel] = off + slot
        off += R_l * S_l
        S_lvl.append(S_l)
        R_lvl.append(R_l)
        si_lvl.append(si)
        sm_lvl.append(sm)
        perms_lvl.append(perms)
    offs = B + np.concatenate(
        [[0], np.cumsum([r * s for r, s in zip(R_lvl, S_lvl)])]).astype(int)

    # ---- local matrix in padded-COO (same packing as build_plan) --------
    rows_l = rank_in_block[src]
    cols_l = rank_in_block[dst]
    cols_l[ext] = _ext_col_slots(flat_post, flat_sorted, o2, slot_of_trip,
                                 ext_keys, k, n, dense)
    own = psrc
    per_blk = np.bincount(own, minlength=k)
    rows_a, cols_a, vals_a, pos_edge = _pack_local_coo(
        indptr, src, data, part, order, k, rows_l, cols_l, per_blk)

    row_mask = (np.arange(B)[None, :] < sizes[:, None]).astype(np.float32)

    split = _derive_tree_fields(rows_a, cols_a, vals_a, per_blk, B, offs)
    bnd_row = split.pop("_bnd_row")
    host_split = split.pop("_host")
    interior_mask = row_mask * ~bnd_row

    # host-side intermediates for O(delta) patching (sparse/replan.py).
    # ``cache=False`` drops them (saves ~2x host memory for static
    # matrices); a canonical sorted CSR is required for patching, so a
    # non-canonical input simply gets no cache instead of failing.
    replan_cache = None
    if cache:
        from .replan import capture_replan_cache
        replan_cache = capture_replan_cache(
            indptr=np.asarray(indptr), indices=dst,
            data=np.asarray(data), src=src,
            part=part, order=order, rank_in_block=rank_in_block,
            sizes=sizes, B=B, k=k, n=n, fanouts=fanouts_out,
            suffix=tuple(suffix), flat=flat, o2=o2, ext=ext,
            ext_keys=ext_keys, psrc=psrc,
            t_pair=t_pair_all, t_v=t_v_all, t_lvl=t_lvl,
            slot_of_trip=slot_of_trip, offs=offs,
            rows_a=rows_a, cols_a=cols_a, vals_a=vals_a,
            per_blk=per_blk, pos_edge=pos_edge,
            row_mask=row_mask, host=host_split)

    return _maybe_verify(TreePlan(
        k=k, B=B, S=max(S_lvl), n_rounds=sum(R_lvl), n=n, perm=perm,
        block_of=block_of, sizes=sizes,
        rows=jnp.asarray(rows_a), cols=jnp.asarray(cols_a),
        vals=jnp.asarray(vals_a), row_mask=jnp.asarray(row_mask),
        send_idx=None, send_mask=None, round_perms=(),
        interior_mask=jnp.asarray(interior_mask), **split,
        fanouts=fanouts_out, anc=anc_dev, block_map=block_map,
        S_lvl=tuple(S_lvl), n_rounds_lvl=tuple(R_lvl),
        send_idx_lvl=tuple(jnp.asarray(a) for a in si_lvl),
        send_mask_lvl=tuple(jnp.asarray(a) for a in sm_lvl),
        round_perms_lvl=tuple(perms_lvl),
        _pack_blk=own, _pack_pos=pos_edge, _pack_dst=dst,
        _replan=replan_cache,
    ), validate)


def build_plan_hier(indptr: np.ndarray, indices: np.ndarray,
                    data: np.ndarray, part: np.ndarray,
                    pods, k: int, validate: bool | None = None) -> TreePlan:
    """Build the two-level distributed plan for a multi-pod mesh — the
    ``h == 2`` instance of :func:`build_plan_tree` (kept as the PR 3-4
    API).

    ``pods`` is either the pod count (blocks are grouped contiguously —
    block b goes to pod ``b // (k // pods)``, matching
    ``core.topology.Topology.pod_assignment``: Algorithm-1 orders fast PUs
    first, so the fast PUs that share the heaviest cut land in one pod) or
    an explicit (k,) pod id per block — e.g. the partition-derived
    assignment of ``core.api.partition_hier`` / ``pod_assignment_for``
    (generally non-contiguous after the pod-level sweep).  Pods must be
    equal-sized (the mesh is rectangular).
    """
    from ..core.topology import normalize_pod_of

    # one validation definition shared with the partitioner side
    pod_of_block = normalize_pod_of(pods, k)
    return build_plan_tree(indptr, indices, data, part,
                           pod_of_block[None, :], k, validate=validate)


# --------------------------------------------------------------------------
# shard_map programs
# --------------------------------------------------------------------------
#
# Every per-device function below is *rank-polymorphic* over a trailing
# RHS-batch axis: x_loc may be (B,) or (B, nb) and the same gather /
# scatter-add / ppermute schedule carries the extra axis through (vmap
# cannot cross the ppermute rounds on every supported JAX, so the batch
# axis is threaded natively instead).  Per-row weights ((S,) send masks,
# (nnz,) values, (B,) row masks) are aligned with a batched operand via
# :func:`_bcol`.


def _bcol(m, x):
    """Align a per-row weight/mask with ``x``'s trailing RHS-batch axes:
    (s,) against (s, nb) -> (s, 1) so NumPy broadcasting applies the
    weight to every column."""
    return m.reshape(m.shape + (1,) * (x.ndim - m.ndim))


def _halo_exchange(plan: DistPlan, x_loc, send_idx, send_mask, axis: str):
    """x_loc: (B,) or (B, nb).  Returns the (B + R*S[, nb]) extended
    vector."""
    bufs = []
    for c in range(plan.n_rounds):
        buf = x_loc[send_idx[c]] * _bcol(send_mask[c], x_loc)  # (S[, nb])
        perm = plan.round_perms[c]
        if perm:
            buf = jax.lax.ppermute(buf, axis, perm)
        else:
            buf = jnp.zeros_like(buf)
        bufs.append(buf)
    return jnp.concatenate([x_loc] + bufs)


def _hier_exchange(plan: HierPlan, x_loc, send_idx, send_mask, axes,
                   perms, n_rounds):
    """One class of hier rounds: returns the per-round (S[, nb]) buffers.

    ``axes`` is the ppermute axis spec — the intra-pod axes (fast links;
    the shared local-index schedule fires in every pod, masked zeros where
    a pod lacks the edge) or the full (pod, *intra) tuple with linearized
    device indices (inter-pod, slow links).
    """
    bufs = []
    for c in range(n_rounds):
        buf = x_loc[send_idx[c]] * _bcol(send_mask[c], x_loc)
        perm = perms[c]
        if perm:
            buf = jax.lax.ppermute(buf, axes, perm)
        else:
            buf = jnp.zeros_like(buf)
        bufs.append(buf)
    return bufs


COMM_MODES = ("halo", "halo_seq", "allgather", "hier")
LOCAL_FORMATS = ("coo", "bell")


def _validate_tree_axes(plan: "TreePlan", mesh: Mesh, axis) -> None:
    """Check that the mesh's trailing axes actually hold the plan's tree:
    level ``l`` ppermutes over ``axes[h-1-l:]`` with suffix-linearized
    indices, so the *product of those axis sizes* must equal the plan's
    level-``l`` suffix size ``prod(fanouts[h-1-l:])`` — an axis tuple
    that merely has enough entries but the wrong shape would deliver
    halo words to the wrong devices silently.

    Delegates to the reusable ``repro.analysis.check_mesh_axes`` pass
    (MESH0xx diagnostics) and raises ``ValueError`` on any violation, the
    historical contract of this hook.
    """
    from ..analysis import check_mesh_axes      # lazy: keep import acyclic
    check_mesh_axes(plan, mesh, tuple(axis)).raise_for_errors()


def abstract_mesh_for(plan: DistPlan, axis: str | tuple = "pu"):
    """Device-free mesh shaped for ``plan``'s schedule (trace entry hook).

    Returns a ``compat.abstract_mesh`` whose axis names/sizes match what
    :func:`make_dist_spmv` / :func:`make_dist_cg` expect for this plan, so
    the solver programs can be traced (``jax.make_jaxpr``) and audited on
    a machine with no devices — the entry point used by
    ``repro.analysis.trace``.

    Flat plans get a single ``axis`` of size ``k``.  Tree plans get one
    axis per level (``launch.mesh.tree_axis_names`` by default, or the
    explicit ``axis`` tuple), outermost first; when more axes than levels
    are named, the extra leading axes get size 1 — they fold into the
    outermost level exactly as on a concrete mesh.
    """
    from .. import compat
    if isinstance(plan, TreePlan):
        if axis == "pu":
            from ..launch.mesh import tree_axis_names
            names = tree_axis_names(max(plan.h, 2))
        else:
            names = tuple(axis)
        fanouts = plan.fanouts
        if len(names) > len(fanouts):
            fanouts = (1,) * (len(names) - len(fanouts)) + tuple(fanouts)
        return compat.abstract_mesh(dict(zip(names, fanouts)))
    name = axis if isinstance(axis, str) else tuple(axis)[0]
    return compat.abstract_mesh({name: plan.k})


def _local_matvec_builder(plan: DistPlan, comm: str, axis: str,
                          local_format: str = "coo"):
    """Shared per-device matvec for every comm/format combination.

    Returns ``(consts, fn)``: ``consts`` is a tuple of (k, ...) arrays to be
    sharded one-block-per-device, and ``fn(local_consts, x_loc)`` computes
    y_loc = (A @ x)_loc on already-squeezed per-device slices.  Both
    :func:`make_dist_spmv` and the fused :func:`make_dist_cg` build on it.
    ``consts`` always ends with ``plan.row_mask`` so the fused CG can read
    the mask for its psum dots without shipping a duplicate operand.

    ``comm='halo'`` is the *overlapped* schedule: the interior matvec
    (``plan.rows_int`` — rows touching no halo slot) is issued before the
    colored ppermute rounds, so XLA can run it concurrently with the
    exchange; boundary rows accumulate afterward from the extended vector.
    ``comm='halo_seq'`` keeps the PR-1 sequential schedule (exchange all
    rounds, then one full matvec) as the non-overlapped reference.
    ``local_format='bell'`` runs the interior matvec through the Pallas
    block-ELL kernel (kernels/spmv_bell.py) instead of the COO scatter-add
    — ROADMAP's third comm/format combination.

    ``comm='hier'`` is the three-stage multi-pod schedule and requires a
    :class:`HierPlan` plus a *tuple* ``axis`` ``(pod_axis, *intra_axes)``:
    interior matvec first, then intra-pod ppermute rounds over the fast
    intra axes and inter-pod rounds over the combined axes — the
    intra-pod boundary accumulation depends only on the fast rounds, so
    it overlaps with the slow inter-pod exchange.
    """
    if comm not in COMM_MODES:
        raise ValueError(f"unknown comm mode {comm!r}; choose {COMM_MODES}")
    if local_format not in LOCAL_FORMATS:
        raise ValueError(f"unknown local format {local_format!r}; "
                         f"choose {LOCAL_FORMATS}")
    if local_format == "bell" and comm not in ("halo", "hier"):
        raise ValueError("local_format='bell' requires comm='halo' or "
                         "'hier' (the interior/boundary split the kernel "
                         "is built from)")
    if isinstance(plan, TreePlan) != (comm == "hier"):
        raise ValueError(
            "comm='hier' requires a TreePlan (build_plan_tree / "
            "build_plan_hier) and a TreePlan only runs under comm='hier' "
            "— its halo layout has separate per-level slot ranges that "
            f"the flat schedules cannot address (got comm={comm!r}, "
            f"plan={type(plan).__name__})")
    B = plan.B

    if comm == "hier":
        h = plan.h
        if isinstance(axis, str) or len(tuple(axis)) < max(h, 2):
            raise ValueError(f"comm='hier' on a depth-{h} plan needs "
                             f"axis=(outer_axis, ..., inner_axis) with "
                             f">= {max(h, 2)} mesh axes; got {axis!r}")
        axes = tuple(axis)

        def level_axes(l: int):
            # level l ppermutes over the axis suffix holding its
            # mixed-radix digits; extra leading mesh axes fold into the
            # outermost level (axes[0:] for l == h-1)
            sub = axes[h - 1 - l:]
            return sub[0] if len(sub) == 1 else sub

        if local_format == "bell":
            head = plan.bell_local()
        else:
            head = (plan.rows_int, plan.cols_int, plan.vals_int)
        consts = head
        for l in range(h):
            consts = consts + (plan.rows_bnd_lvl[l], plan.cols_bnd_lvl[l],
                               plan.vals_bnd_lvl[l])
        for l in range(h):
            consts = consts + (plan.send_idx_lvl[l], plan.send_mask_lvl[l])
        consts = consts + (plan.row_mask,)

        n_head = len(head)

        def fn(c, x):
            bnd = c[n_head:n_head + 3 * h]
            sends = c[n_head + 3 * h:n_head + 5 * h]
            row_mask = c[-1]
            # stage 1: interior matvec — no halo dependence at all
            if local_format == "bell":
                if x.ndim > 1:
                    raise ValueError(
                        "local_format='bell' is single-RHS (the Pallas "
                        "block-ELL kernel is a vector kernel); use "
                        "local_format='coo' for batched solves")
                from ..kernels.spmv_bell import spmv_block_ell
                y = spmv_block_ell(c[0], c[1], x)
            else:
                ri, ci, vi = c[:3]
                y = jnp.zeros((B,) + x.shape[1:], x.dtype).at[ri].add(
                    _bcol(vi, x) * x[ci])
            # stage 2: issue every level's rounds, *outermost first* —
            # each slower exchange is in flight while all faster levels'
            # rounds and accumulations (and the interior matvec) run
            bufs: list = [None] * h
            for l in range(h - 1, -1, -1):
                bufs[l] = _hier_exchange(plan, x, sends[2 * l],
                                         sends[2 * l + 1], level_axes(l),
                                         plan.round_perms_lvl[l],
                                         plan.n_rounds_lvl[l])
            # stage 3: accumulate innermost first — a level's rows read
            # only its own and faster levels' slots, so each
            # accumulation waits on nothing slower than itself
            x_ext = x
            for l in range(h):
                if bufs[l]:
                    x_ext = jnp.concatenate([x_ext] + bufs[l])
                rl, cl, vl = bnd[3 * l:3 * l + 3]
                y = y.at[rl].add(_bcol(vl, x) * x_ext[cl])
            return y * _bcol(row_mask, y)

        return consts, fn

    if comm == "allgather":
        consts = (plan.rows, plan.cols_global, plan.vals, plan.row_mask)

        def fn(c, x):
            rows, cols, vals, row_mask = c
            x_all = jax.lax.all_gather(x, axis)               # (k, B[, nb])
            x_all = x_all.reshape((-1,) + x.shape[1:])        # (k*B[, nb])
            y = jnp.zeros((B,) + x.shape[1:], x.dtype).at[rows].add(
                _bcol(vals, x) * x_all[cols])
            return y * _bcol(row_mask, y)

        return consts, fn

    if comm == "halo_seq":
        consts = (plan.rows, plan.cols, plan.vals, plan.send_idx,
                  plan.send_mask, plan.row_mask)

        def fn(c, x):
            rows, cols, vals, send_idx, send_mask, row_mask = c
            x_ext = _halo_exchange(plan, x, send_idx, send_mask, axis)
            y = jnp.zeros((B,) + x.shape[1:], x.dtype).at[rows].add(
                _bcol(vals, x) * x_ext[cols])
            return y * _bcol(row_mask, y)

        return consts, fn

    # comm == "halo": overlapped interior/boundary schedule
    bnd = (plan.rows_bnd, plan.cols_bnd, plan.vals_bnd)
    tail = (plan.send_idx, plan.send_mask, plan.row_mask)
    if local_format == "coo":
        consts = (plan.rows_int, plan.cols_int, plan.vals_int) + bnd + tail

        def fn(c, x):
            ri, ci, vi, rb, cb, vb, send_idx, send_mask, row_mask = c
            # interior first: no halo dependence, overlaps the ppermutes
            y = jnp.zeros((B,) + x.shape[1:], x.dtype).at[ri].add(
                _bcol(vi, x) * x[ci])
            x_ext = _halo_exchange(plan, x, send_idx, send_mask, axis)
            y = y.at[rb].add(_bcol(vb, x) * x_ext[cb])
            return y * _bcol(row_mask, y)

        return consts, fn

    blocks, bcols = plan.bell_local()

    def fn(c, x):
        from ..kernels.spmv_bell import spmv_block_ell
        if x.ndim > 1:
            raise ValueError(
                "local_format='bell' is single-RHS (the Pallas block-ELL "
                "kernel is a vector kernel); use local_format='coo' for "
                "batched solves")
        blk, bc, rb, cb, vb, send_idx, send_mask, row_mask = c
        y = spmv_block_ell(blk, bc, x)                     # interior rows
        x_ext = _halo_exchange(plan, x, send_idx, send_mask, axis)
        y = y.at[rb].add(vb * x_ext[cb])
        return y * row_mask

    return (blocks, bcols) + bnd + tail, fn


def place_blocks(arrays, mesh, axis: str | tuple = "pu"):
    """A pytree of (k, ...) arrays (host or device) placed block ``b`` on
    mesh position ``b`` — a no-op for arrays already placed so.  A
    device-free ``AbstractMesh`` holds no arrays: they go to the default
    device."""
    if not isinstance(mesh, Mesh):
        return jax.tree.map(jnp.asarray, arrays)
    spec = P(axis if isinstance(axis, str) else tuple(axis))
    return jax.device_put(arrays, NamedSharding(mesh, spec))


def shard_plan(plan: DistPlan, mesh, axis: str | tuple = "pu") -> DistPlan:
    """``plan`` with every device array (all carry the leading block
    axis) placed one block per device of ``mesh``, so no device holds
    another's share; unchanged on an abstract mesh."""
    if not isinstance(mesh, Mesh):
        return plan
    upd = {}
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if isinstance(v, jax.Array) or (
                isinstance(v, tuple) and v
                and all(isinstance(a, jax.Array) for a in v)):
            upd[f.name] = place_blocks(v, mesh, axis)
    return dataclasses.replace(plan, **upd)


def make_dist_spmv(plan: DistPlan, mesh: Mesh, axis: str = "pu",
                   comm: str = "halo",
                   local_format: str = "coo") -> Callable:
    """Returns jit'd y = A @ x on (k, B) block-major vectors.  The plan's
    arrays are placed one block per device and bound as the program's
    first argument — operands, never compiled-in constants.

    ``comm='halo'`` (default) overlaps the interior matvec with the
    edge-colored ppermute rounds; ``comm='halo_seq'`` is the sequential
    reference schedule; ``comm='allgather'`` gathers the whole padded
    vector (the partitioner-oblivious baseline); ``comm='hier'`` is the
    per-tree-level schedule (needs a :class:`TreePlan` and
    ``axis=(outer_axis, ..., inner_axis)`` whose trailing-axis products
    match the plan's fanouts suffixes).  ``local_format='bell'`` runs
    the interior matvec through the Pallas block-ELL kernel.
    """
    consts, local_fn = _local_matvec_builder(plan, comm, axis, local_format)
    if comm == "hier":
        _validate_tree_axes(plan, mesh, axis)

    def prog(*args):
        *cs, x = args
        return local_fn(tuple(c[0] for c in cs), x[0])[None]

    spec = P(axis if isinstance(axis, str) else tuple(axis))
    fn = shard_map(prog, mesh=mesh,
                   in_specs=(spec,) * (len(consts) + 1), out_specs=spec)

    @jax.jit
    def spmv(consts, x):
        return fn(*consts, x)

    return functools.partial(spmv, place_blocks(consts, mesh, axis))


def make_dist_cg(plan: DistPlan, mesh: Mesh, axis: str = "pu",
                 tol: float = 1e-6, max_iters: int = 500,
                 comm: str = "halo", local_format: str = "coo",
                 precondition: str | None = None) -> Callable:
    """The fused CG of :func:`dist_cg_program` with the plan's arrays
    placed one block per device and bound: ``solve(b)`` on a (k, B[, nb])
    block-major right-hand side returns ``(x, residual, iters)``."""
    solve, consts = dist_cg_program(plan, mesh, axis, tol, max_iters, comm,
                                    local_format, precondition)
    return functools.partial(solve, place_blocks(consts, mesh, axis))


def dist_cg_program(plan: DistPlan, mesh: Mesh, axis: str = "pu",
                    tol: float = 1e-6, max_iters: int = 500,
                    comm: str = "halo", local_format: str = "coo",
                    precondition: str | None = None):
    """``(solve, consts)``: the jitted ``solve(consts, b)`` and the plan
    arrays it takes as operands.

    Whole-CG SPMD program: the while_loop runs inside shard_map; dot
    products are psum-reduced local dots; the matvec comes from
    :func:`_local_matvec_builder` — overlapped halo rounds (``'halo'``),
    the sequential schedule (``'halo_seq'``), or the full-vector
    all_gather baseline (``'allgather'``), with the interior matvec in
    padded-COO or Pallas block-ELL (``local_format``).

    ``precondition='jacobi'`` switches the body to preconditioned CG with
    M = diag(A); the diagonal is already on-device in ``plan.diag``,
    extracted when the plan was built.  ``precondition='block_jacobi'``
    uses the per-PU diagonal blocks instead (M = blockdiag(A_bb), applied
    as one dense (B, B) matmul per device from the plan's cached
    inverses).  Convergence is still tested on the unpreconditioned
    residual ||r||^2 <= tol^2 ||b||^2, so preconditioned and
    unpreconditioned solves stop at the same solution quality.

    This is the fused fast path; the composable path is
    ``operator.DistributedOperator`` + the generic ``cg.cg_solve``."""
    if precondition not in (None, "jacobi", "block_jacobi"):
        raise ValueError(f"unknown precondition {precondition!r}")
    consts, local_fn = _local_matvec_builder(plan, comm, axis, local_format)
    if comm == "hier":
        _validate_tree_axes(plan, mesh, axis)
    prec_tail = ()
    if precondition == "jacobi":
        prec_tail = (plan.diag,)
    elif precondition == "block_jacobi":
        prec_tail = (plan.block_jacobi_inv(),)
    all_consts = consts + prec_tail

    def cg_local(*args):
        # one CG implementation for every program shape: the generic
        # cg.cg_solve is pure lax, so tracing it here (with a psum dot and
        # the local matvec) yields the fused whole-CG SPMD program.  A 2-D
        # per-device b carries the trailing RHS-batch axis — the local
        # matvec is batch-native (rank-polymorphic schedule), the psum dot
        # stays single-column (cg_solve vmaps it over columns), and the
        # whole multi-RHS masked loop runs inside this one shard_map body.
        *cs, b = args
        cs = tuple(c[0] for c in cs)
        b = b[0]
        prec = None
        if precondition == "jacobi":
            prec = jacobi_preconditioner(cs[-1])
            cs = cs[:-1]
        elif precondition == "block_jacobi":
            minv = cs[-1]                 # (B, B); ghost rows identity, and
            cs = cs[:-1]                  # ghost residuals are exactly zero
            prec = lambda r: minv @ r
        row_mask = cs[-1]                 # builder contract: always last

        def dot(u, v):
            return jax.lax.psum(jnp.vdot(u * row_mask, v), axis)

        mv = lambda x: local_fn(cs, x)
        mv.batch_native = True
        res = cg_solve(mv, b, tol=tol,
                       max_iters=max_iters, dot=dot, precondition=prec,
                       batched=b.ndim == 2)
        return res.x[None], res.residual[None], res.iters[None]

    spec = P(axis if isinstance(axis, str) else tuple(axis))
    fn = shard_map(cg_local, mesh=mesh,
                   in_specs=(spec,) * (len(all_consts) + 1),
                   out_specs=(spec, spec, spec))

    @jax.jit
    def solve(consts, b):
        x, res, it = fn(*consts, b)
        return x, res[0], it[0]

    return solve, all_consts
