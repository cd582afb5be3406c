"""Pallas TPU kernel: block-ELL SpMV — the paper's HPC kernel (Sec. VI-a)
re-thought for the TPU memory hierarchy.

GPU SpMV is gather-heavy CSR; TPUs have no efficient per-lane gather, but an
MXU that eats dense (8x128-aligned) tiles.  We therefore re-tile the sparse
matrix into a *block-ELL* format:

  * rows grouped into stripes of BM rows,
  * columns grouped into panels of BK columns,
  * each stripe stores exactly NNZB dense (BM, BK) blocks (the densest
    panels; zero-padded if the stripe has fewer) plus their panel indices.

y[stripe] = sum_b  A_blocks[stripe, b] @ x[cols[stripe, b]]

The kernel walks grid (stripes, NNZB); the x panel for each step is selected
with a data-dependent BlockSpec index_map fed by scalar prefetch
(PrefetchScalarGridSpec), so the right (BK,) slice of x is already in VMEM
when the MXU needs it.  Output accumulates across the NNZB grid dimension.
The stripes run in chunks, one pallas_call each, so that every call's
prefetched column table fits in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_interpret


# --------------------------------------------------------------------------
# Format conversion (host-side, NumPy): CSR -> block-ELL
# --------------------------------------------------------------------------

def csr_to_block_ell(indptr: np.ndarray, indices: np.ndarray,
                     data: np.ndarray, n: int, bm: int = 8, bk: int = 128,
                     nnzb: int | None = None):
    """Convert CSR to block-ELL.

    Returns (blocks, cols, meta) where
      blocks: (S, NNZB, BM, BK) — dense blocks per stripe, in the dtype
              of ``data`` (float dtypes preserved, else float32)
      cols:   (S, NNZB) int32 — column-panel index of each block
      meta:   dict(n=n, bm=bm, bk=bk, fill=fraction of nonzero cells kept)
    If nnzb is None it is set to the max #panels touched by any stripe
    (lossless).  Smaller nnzb drops the sparsest panels (lossy — for
    preconditioner-style use; tests use lossless).
    """
    data = np.asarray(data)
    vdt = data.dtype if np.issubdtype(data.dtype, np.floating) \
        else np.float32
    S = -(-n // bm)
    P = -(-n // bk)
    per_stripe: list[dict[int, np.ndarray]] = [dict() for _ in range(S)]
    for i in range(n):
        s = i // bm
        row = slice(indptr[i], indptr[i + 1])
        for j, v in zip(indices[row], data[row]):
            p = int(j) // bk
            blk = per_stripe[s].get(p)
            if blk is None:
                blk = np.zeros((bm, bk), dtype=vdt)
                per_stripe[s][p] = blk
            blk[i % bm, int(j) % bk] += v
    max_panels = max((len(d) for d in per_stripe), default=1) or 1
    if nnzb is None:
        nnzb = max_panels
    blocks = np.zeros((S, nnzb, bm, bk), dtype=vdt)
    cols = np.zeros((S, nnzb), dtype=np.int32)
    kept = total = 0
    for s, panels in enumerate(per_stripe):
        items = sorted(panels.items(),
                       key=lambda kv: -np.count_nonzero(kv[1]))
        total += sum(np.count_nonzero(b) for _, b in items)
        for b, (p, blk) in enumerate(items[:nnzb]):
            blocks[s, b] = blk
            cols[s, b] = p
            kept += np.count_nonzero(blk)
    meta = dict(n=n, bm=bm, bk=bk, nnzb=nnzb,
                fill=kept / max(total, 1))
    return blocks, cols, meta


def padded_coo_to_block_ell(rows: np.ndarray, cols: np.ndarray,
                            vals: np.ndarray, n: int, bm: int = 8,
                            bk: int = 128, nnzb: int | None = None):
    """Convert padded COO (one device's local block) to block-ELL.

    Unlike :func:`csr_to_block_ell` this is fully vectorized NumPy — no
    per-row Python — so the distributed operator can convert every local
    block at plan-build time.  Zero-valued entries (the padding convention
    of the packed layouts in ``sparse.distributed``) are dropped before
    blocking, so padded slots never allocate a panel.

    Returns (blocks, cols, meta) with the same shapes/semantics as
    :func:`csr_to_block_ell`: blocks (S, NNZB, BM, BK) f32, cols (S, NNZB)
    int32, NNZB defaulting to the max #panels touched by any stripe
    (lossless).  Panels within a stripe are ordered by column-panel index
    (not by density): block-ELL SpMV is order-invariant, and the sorted
    order falls out of the radix sort for free.
    """
    rows = np.asarray(rows).ravel()
    cols = np.asarray(cols).ravel()
    vals = np.asarray(vals).ravel()
    if not np.issubdtype(vals.dtype, np.floating):
        vals = vals.astype(np.float32)
    live = vals != 0
    rows, cols, vals = rows[live], cols[live], vals[live]
    S = max(-(-n // bm), 1)
    stripe = rows // bm
    panel = cols // bk
    Pn = max(-(-int(cols.max() + 1) // bk), 1) if len(cols) else 1
    key = stripe.astype(np.int64) * Pn + panel
    uniq, inv = np.unique(key, return_inverse=True)
    u_stripe = (uniq // Pn).astype(np.int64)
    u_panel = (uniq % Pn).astype(np.int32)
    per_stripe = np.bincount(u_stripe, minlength=S)
    max_panels = max(int(per_stripe.max()) if len(per_stripe) else 0, 1)
    if nnzb is None:
        nnzb = max_panels
    # slot of each unique (stripe, panel) within its stripe: uniq is sorted
    # by (stripe, panel), so the slot is the rank inside the stripe group
    grp_start = np.repeat(np.cumsum(per_stripe) - per_stripe, per_stripe)
    slot = (np.arange(len(uniq)) - grp_start).astype(np.int64)
    blocks = np.zeros((S, nnzb, bm, bk), dtype=vals.dtype)
    colsb = np.zeros((S, nnzb), dtype=np.int32)
    u_keep = slot < nnzb
    colsb[u_stripe[u_keep], slot[u_keep]] = u_panel[u_keep]
    e_slot = slot[inv]
    keep = e_slot < nnzb
    np.add.at(blocks, (stripe[keep], e_slot[keep],
                       rows[keep] % bm, cols[keep] % bk), vals[keep])
    kept = int(keep.sum())
    meta = dict(n=n, bm=bm, bk=bk, nnzb=nnzb,
                fill=kept / max(len(vals), 1))
    return blocks, colsb, meta


# --------------------------------------------------------------------------
# Kernel
# --------------------------------------------------------------------------

# The scalar-prefetched column table lives in SMEM (1 MiB on a TPU v5e),
# flat (a 2-D (S, NNZB) table pads NNZB to 128 lanes).  Each pallas_call
# takes a chunk of stripes whose flat table is at most this many int32
# entries (128 KiB), so any stripe count fits.
SMEM_TABLE_ENTRIES = 1 << 15


def spmv_block_ell(blocks: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
                   interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x with A in block-ELL.  x: (n,); returns (n,) in the
    blocks' dtype (the kernel computes in the blocks' dtype — float64
    blocks keep float64 accumulation under the interpreter/CPU path).

    ``interpret=None`` resolves via :func:`repro.kernels.default_interpret`
    — the Pallas interpreter on the CPU backend, compiled Mosaic
    otherwise."""
    if interpret is None:
        interpret = default_interpret()
    return _spmv_block_ell(blocks, cols, x, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _spmv_block_ell(blocks: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
                    interpret: bool) -> jnp.ndarray:
    S, NNZB, BM, BK = blocks.shape
    dt = blocks.dtype
    n = x.shape[0]
    P = -(-n // BK)
    # x as (P, 1, BK) and y as (S, 1, BM): the last two block dims then
    # equal the array's (Mosaic's tiling rule for blocks below (8, 128))
    xp = jnp.pad(x.astype(dt), (0, P * BK - n)).reshape(P, 1, BK)
    flat = cols.reshape(-1)
    chunk = max(SMEM_TABLE_ENTRIES // NNZB, 1)
    ys = []
    for s0 in range(0, S, chunk):
        m = min(chunk, S - s0)
        ys.append(_stripe_chunk(blocks, flat[s0 * NNZB:(s0 + m) * NNZB], xp,
                                s0, m, interpret))
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    return y.reshape(-1)[:n]


def _stripe_chunk(blocks, cols_flat, xp, s0: int, m: int, interpret: bool):
    """Stripes ``[s0, s0 + m)`` of the product, (m, 1, BM)."""
    _, NNZB, BM, BK = blocks.shape
    dt = blocks.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, NNZB),
        in_specs=[
            pl.BlockSpec((1, 1, BM, BK),
                         lambda s, b, cols: (s0 + s, b, 0, 0)),
            pl.BlockSpec((1, 1, BK),
                         lambda s, b, cols: (cols[s * NNZB + b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BM), lambda s, b, cols: (s, 0, 0)),
    )

    def kernel(cols_ref, blocks_ref, x_ref, y_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            y_ref[...] = jnp.zeros_like(y_ref)

        y_ref[0] += jax.lax.dot_general(
            x_ref[0], blocks_ref[0, 0], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=dt)        # (1, BK) x (BM, BK)^T

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, BM), dt),
        interpret=interpret,
    )(cols_flat, blocks, xp)
