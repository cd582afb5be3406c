"""Pallas TPU kernel: pairwise squared distances, the hot loop of balanced
k-means (geoKM).  D[i, j] = ||X[i] - C[j]||^2.

TPU adaptation: `||x-c||^2 = ||x||^2 - 2 x.c + ||c||^2` turns the distance
computation into a matmul that runs on the MXU.  We tile X into (BN, D) and C
into (BK, D) VMEM blocks; D (the coordinate dim, 2 or 3 for meshes) is padded
to the 128-lane width once at the wrapper level so the MXU contraction is
aligned.  Grid is (n/BN, k/BK); each program computes one (BN, BK) output
tile entirely in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import default_interpret


def _pdist_kernel(x_ref, c_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # (BN, D)
    c = c_ref[...].astype(jnp.float32)          # (BK, D)
    xx = jnp.sum(x * x, axis=1, keepdims=True)  # (BN, 1)
    cc = jnp.sum(c * c, axis=1)[None, :]        # (1, BK)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    out_ref[...] = xx - 2.0 * xc + cc


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def pairwise_sqdist_pallas(x: jnp.ndarray, c: jnp.ndarray, bn: int = 256,
                           bk: int = 128, interpret: bool | None = None):
    """(n, d) x (k, d) -> (n, k) squared distances.

    ``interpret=None`` resolves via :func:`repro.kernels.default_interpret`
    (interpreted on the CPU backend, compiled Mosaic otherwise).
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = x.shape
    k, _ = c.shape
    # pad: lanes want multiples of 128 in the minor dim, sublanes 8.
    dp = max(8, -(-d // 8) * 8)
    npad = -(-n // bn) * bn
    kpad = -(-k // bk) * bk
    xp = jnp.zeros((npad, dp), x.dtype).at[:n, :d].set(x)
    cp = jnp.zeros((kpad, dp), c.dtype).at[:k, :d].set(c)

    out = pl.pallas_call(
        _pdist_kernel,
        grid=(npad // bn, kpad // bk),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, kpad), jnp.float32),
        interpret=interpret,
    )(xp, cp)
    return out[:n, :k]
