"""Pallas TPU kernel: flash attention (online softmax), the LM stack's
perf-critical hot spot.

Tiling: grid (B*H, Sq/BQ, Sk/BK).  Each (bh, qi) owns a (BQ, D) query tile
resident in VMEM; the innermost grid dimension walks key/value tiles of
shape (BK, D), maintaining the running max m, normalizer l and accumulator
acc in VMEM scratch (the classic FlashAttention-2 schedule).  The MXU sees
(BQ, D) x (D, BK) and (BQ, BK) x (BK, D) matmuls — both 128-aligned when
D, BQ, BK are multiples of 128 (D=64 also lowers fine: 8x128 tiles pack 2
rows).  Causal masking is applied in-kernel via block-local iota; fully
masked tiles short-circuit with @pl.when.

jnp oracle: kernels/ref.py::flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_interpret

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, bq: int, bk: int, scale: float,
                  n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (BQ, D)
        k = k_ref[0].astype(jnp.float32)              # (BK, D)
        v = v_ref[0].astype(jnp.float32)              # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        m_prev = m_ref[...]                           # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """q, k, v: (B, H, S, D) -> (B, H, S, D).  Softmax scale 1/sqrt(D).
    ``interpret=None`` resolves via :func:`repro.kernels.default_interpret`."""
    if interpret is None:
        interpret = default_interpret()
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = D ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, "pad sequence to tile multiples"
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    n_k = Sk // bk

    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, bq=bq, bk=bk,
                          scale=scale, n_k=n_k),
        grid=(B * H, Sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            # (BQ, 1) running max / normalizer, (BQ, D) accumulator — VMEM
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
