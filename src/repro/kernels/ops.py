"""jit'd public wrappers for the Pallas kernels.

Kernel-path selection lives in ``kernels.default_interpret``: the Pallas
interpreter on the CPU backend, compiled Mosaic otherwise.
"""
from __future__ import annotations

import jax.numpy as jnp

from .pdist import pairwise_sqdist_pallas
from .spmv_bell import csr_to_block_ell, spmv_block_ell


def pairwise_sqdist(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """(n, d) x (k, d) -> (n, k) squared Euclidean distances (Pallas)."""
    return pairwise_sqdist_pallas(x, c)


def spmv(blocks: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray):
    """Block-ELL SpMV y = A @ x (Pallas)."""
    return spmv_block_ell(blocks, cols, x)


__all__ = ["pairwise_sqdist", "spmv", "csr_to_block_ell"]
