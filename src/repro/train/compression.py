"""Cross-pod gradient compression (beyond-paper distributed-optimization).

Multi-pod data parallelism reduces gradients across pods over the
(slower) inter-pod links.  XLA inserts that all-reduce implicitly at
bf16/f32 width.  Here the step runs in a manual shard_map region, so the
cross-pod reduction can be quantized:

  int8 symmetric quantization (per-tensor scale = pmax|g|/127)
  -> int8 all-gather over 'pod' (1 byte/elem on the wire vs 2 for bf16,
     4 for f32) -> local int32 sum -> dequantize.

For pod counts <= 128 the int32 accumulation is exact given int8 inputs,
so the only loss is the quantization itself (~0.4% RMS on typical grad
distributions; the per-tensor pmax scale makes it unbiased in sign).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..compat import shard_map


def compressed_psum(tree, axis: str, bits: int = 8):
    """Quantized sum over a (manual) mesh axis.  bits=8 only for now."""
    assert bits == 8

    def one(g):
        g32 = g.astype(jnp.float32)
        amax = jax.lax.pmax(jnp.max(jnp.abs(g32)), axis)
        scale = amax / 127.0 + 1e-30
        q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
        # int8 all-gather: 1 byte/elem on the wire; exact int32 local sum
        allq = jax.lax.all_gather(q, axis)              # (npods, ...)
        s = jnp.sum(allq.astype(jnp.int32), axis=0)
        return (s.astype(jnp.float32) * scale).astype(g.dtype)

    return jax.tree.map(one, tree)


def podwise_value_and_grad(loss_fn, mesh, batch_specs, *,
                           compression: str = "int8"):
    """Wrap ``value_and_grad(loss_fn)`` so the cross-pod gradient reduction
    goes through ``compressed_psum`` instead of XLA's implicit all-reduce.

    loss_fn: (params, batch) -> scalar loss.
    batch_specs: dict of PartitionSpecs for the batch *restricted to the
    pod axis* (other axes are auto).  Params are replicated across pods.
    """
    from ..compat import P

    def pod_spec(spec):
        # keep only the 'pod' component of each dim spec
        dims = []
        for d in spec:
            if d == "pod" or (isinstance(d, tuple) and "pod" in d):
                dims.append("pod")
            else:
                dims.append(None)
        return P(*dims)

    b_specs = {k: pod_spec(s) for k, s in batch_specs.items()}

    def local(params, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        g = compressed_psum(g, "pod")
        loss = jax.lax.pmean(loss, "pod")
        return loss, g

    # The program is manual over every mesh axis: a pod-only manual
    # region (data/model left auto) trips an XLA SPMD-partitioner CHECK on
    # the embedding gather.  The pod-axis wire traffic (int8 all-gather)
    # is the same; data/model recompute redundantly inside each pod, so
    # the int8 pod reduction is numerically validated (tests) but kept
    # OFF by default.
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(), b_specs),
        out_specs=(P(), P()),
        check_rep=False)
