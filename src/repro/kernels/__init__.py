"""Pallas TPU kernels (compiled with Mosaic on TPU, interpreted on CPU).

  pdist.py     — pairwise squared distance (balanced k-means hot loop)
  spmv_bell.py — block-ELL SpMV (the paper's HPC kernel, TPU-native re-tile)
  flash.py     — flash attention (LM stack hot loop)
  ops.py       — jit'd wrappers;  ref.py — pure-jnp oracles
"""
import jax


def default_interpret() -> bool:
    """Kernel-path selection shared by every kernel: the Pallas
    interpreter on the CPU backend (tests, CI), compiled Mosaic on every
    other backend, so nothing runs interpreted on the chip."""
    return jax.default_backend() == "cpu"
