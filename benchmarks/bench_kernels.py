"""Pallas kernel microbenchmarks.  Each kernel takes its default path —
interpreted on the CPU backend (numbers reflect the reference
execution), compiled with Mosaic on a TPU."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.flash import flash_attention
from repro.kernels.pdist import pairwise_sqdist_pallas
from repro.kernels.ref import flash_attention_ref, pairwise_sqdist_ref
from repro.kernels.spmv_bell import csr_to_block_ell, spmv_block_ell

from .common import row, time_us


def run() -> list[str]:
    rows = []
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4096, 3)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(96, 3)), jnp.float32)
    us_p = time_us(lambda: pairwise_sqdist_pallas(
        x, c).block_until_ready(), reps=3)
    us_r = time_us(lambda: pairwise_sqdist_ref(
        x, c).block_until_ready(), reps=3)
    rows.append(row("pdist_pallas_4096x96", us_p, f"ref_us={us_r:.0f}"))

    from scipy.sparse import random as sprand
    n = 2048
    A = sprand(n, n, density=0.01, random_state=0, format="csr")
    A = (A + A.T).tocsr()
    blocks, cols, meta = csr_to_block_ell(
        A.indptr, A.indices, A.data.astype(np.float32), n)
    xb = jnp.asarray(rng.normal(size=n), jnp.float32)
    bj, cj = jnp.asarray(blocks), jnp.asarray(cols)
    us_s = time_us(lambda: spmv_block_ell(
        bj, cj, xb).block_until_ready(), reps=3)
    rows.append(row("spmv_bell_2048", us_s,
                    f"nnzb={meta['nnzb']};fill={meta['fill']:.2f}"))

    q = jnp.asarray(rng.normal(size=(1, 4, 512, 64)), jnp.float32)
    us_f = time_us(lambda: flash_attention(
        q, q, q, causal=True).block_until_ready(), reps=3)
    us_fr = time_us(lambda: flash_attention_ref(
        q, q, q, causal=True).block_until_ready(), reps=3)
    rows.append(row("flash_attn_512", us_f, f"ref_us={us_fr:.0f}"))
    return rows
