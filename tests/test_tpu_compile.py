"""Ahead-of-time compiles of the main path for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, from shapes alone (nothing runs, so these say
nothing about results or times).  What they catch is what interpret mode
cannot: block shapes Mosaic refuses, scalar tables that overflow SMEM,
programs that do not fit HBM or cannot be partitioned.  Every program is
compiled at the width the chip smoke runs it (``chip_smoke.py``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import NamedSharding, P, make_mesh
from repro.core.balanced_kmeans import _bkm_loop
from repro.kernels.pdist import pairwise_sqdist_pallas
from repro.kernels.spmv_bell import _spmv_block_ell
from repro.sparse import cg_solve
from repro.sparse.distributed import build_plan, dist_cg_program
from repro.sparse.generators import grid
from repro.sparse.graph import laplacian_csr
from repro.sparse.operator import CooOperator

N = 1 << 20                   # delaunay_n20
NNZ = 7 * N                   # Delaunay: ~3n edges, symmetric, + diagonal
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM, f"{total / 1e9:.2f} GB does not fit one chip"


def test_block_ell_kernel_compiles_at_smoke_width(one_chip):
    """The chip smoke's 512x512 grid in natural order: S = 32768 stripes
    of 8 rows; NNZB = the most 128-column panels any stripe touches."""
    g = grid((512, 512))
    indptr, indices, _ = laplacian_csr(g, shift=1e-2)
    rows = np.repeat(np.arange(g.n), np.diff(indptr))
    panels = -(-g.n // 128)
    touched = np.unique((rows // 8) * panels + indices // 128)
    S, nnzb = g.n // 8, int(np.bincount(touched // panels).max())
    compiled = _spmv_block_ell.lower(
        _sds((S, nnzb, 8, 128), jnp.float32, one_chip),
        _sds((S, nnzb), jnp.int32, one_chip),
        _sds((g.n,), jnp.float32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_pdist_kernel_compiles_at_n20(one_chip):
    compiled = pairwise_sqdist_pallas.lower(
        _sds((N, 2), jnp.float32, one_chip),
        _sds((8, 2), jnp.float32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_geokm_loop_compiles_at_n20(one_chip):
    compiled = _bkm_loop.lower(
        _sds((N, 2), jnp.float32, one_chip),
        _sds((6, 2), jnp.float32, one_chip),
        _sds((6,), jnp.float32, one_chip), iters=30, price_steps=12).compile()
    _fits(compiled)


@pytest.mark.parametrize("nb", [1, 16])
def test_batched_coo_cg_compiles_at_delaunay_n20(one_chip, nb):
    """The service's batched CG with the operator as an argument: the
    matrix must be an operand, not a constant baked into the program."""
    op = CooOperator(n=N, rows=_sds((NNZ,), jnp.int32, one_chip),
                     cols=_sds((NNZ,), jnp.int32, one_chip),
                     vals=_sds((NNZ,), jnp.float32, one_chip))
    solve = jax.jit(functools.partial(cg_solve, tol=1e-6, max_iters=2000,
                                      batched=True))
    compiled = solve.lower(op, _sds((N, nb), jnp.float32,
                                    one_chip)).compile()
    _fits(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 12 * NNZ          # matrix = operand
    assert mem.generated_code_size_in_bytes < 64 << 20     # no baked matrix


def test_fused_dist_halo_cg_compiles_on_four_chips(topo):
    """Fused CG over a 4-device mesh of described chips at n = 2^20 (a
    1024x1024 grid Laplacian in four stripes)."""
    g = grid((1024, 1024))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    k = 4
    part = ((np.arange(g.n) * k) // g.n).astype(np.int32)
    plan = build_plan(indptr, indices, data, part, k, validate=False)
    mesh = make_mesh((k,), ("pu",), topo.devices[:k])
    blocks = NamedSharding(mesh, P("pu"))
    solve, consts = dist_cg_program(plan, mesh, "pu", tol=1e-6,
                                    max_iters=2000, comm="halo")
    compiled = solve.lower(
        tuple(_sds(c.shape, c.dtype, blocks) for c in consts),
        _sds((k, plan.B, 8), jnp.float32, blocks)).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo and "all-reduce" in hlo
    _fits(compiled)
