"""Shared model building blocks — functional style, params as nested dicts.

Sharding: every parameter is created through ``param(...)`` with *logical*
axis names; ``logical_to_spec`` maps them to mesh axes (MaxText-style rules).
``init`` functions return ``(params, specs)`` twin trees so the launcher can
hand jit exact in/out shardings without tracing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from ..compat import P

# -- logical axis rules ------------------------------------------------------
# mesh axes: ("pod",) "data", "model".  FSDP shards the embed/d_model axis of
# weights over "data"; TP shards heads / ffn / vocab over "model"; "pod" is
# pure DP (params replicated across pods, gradients all-reduced).

DEFAULT_RULES: dict[str, Any] = {
    "embed": "data",        # d_model axis of weights -> FSDP
    "heads": "model",       # attention heads / q projection
    "kv": None,             # kv heads (small; replicate, see DESIGN)
    "mlp": "model",         # ffn hidden
    "vocab": "model",       # embedding/lm-head vocab axis
    "experts": "model",     # MoE expert axis (EP)
    "expert_mlp": None,     # per-expert hidden (already sharded via experts)
    "layers": None,         # scan axis — never sharded
    "conv": None,
    "state": None,          # SSM state axis
    "batch": ("pod", "data"),
    "seq": None,
    "cache_seq": "model",   # decode KV cache: shard sequence over model axis
    "act_embed": None,      # activation d_model axis
    "seq_sp": "model",      # sequence parallelism: residual stream S axis
                            # sharded over 'model' between TP blocks
                            # (Megatron-SP; halves TP collective bytes)
    "act_heads": "model",
    "act_mlp": "model",
    "act_vocab": "model",
}


_OVERRIDES: dict[str, Any] = {}


@contextlib.contextmanager
def rules_override(**kw):
    """Temporarily override logical-axis rules (e.g. batch=None when the
    global batch is smaller than the data-parallel degree)."""
    global _OVERRIDES
    old = dict(_OVERRIDES)
    _OVERRIDES.update(kw)
    try:
        yield
    finally:
        _OVERRIDES = old


def logical_to_spec(axes: tuple[str | None, ...],
                    rules: dict[str, Any] | None = None,
                    mesh_axes: tuple[str, ...] = ("data", "model")) -> P:
    """Map logical axis names to a PartitionSpec, dropping mesh axes that are
    absent from the target mesh (e.g. 'pod' on the single-pod mesh)."""
    rules = {**(rules or DEFAULT_RULES), **_OVERRIDES}
    out = []
    for ax in axes:
        r = rules.get(ax) if ax else None
        if isinstance(r, tuple):
            r = tuple(m for m in r if m in mesh_axes) or None
            if isinstance(r, tuple) and len(r) == 1:
                r = r[0]
        elif r is not None and r not in mesh_axes:
            r = None
        out.append(r)
    return P(*out)


# -- param creation ----------------------------------------------------------

class ParamCollector:
    """Accumulates twin (params, specs) trees during init."""

    def __init__(self, rng: jax.Array, dtype=jnp.float32,
                 mesh_axes: tuple[str, ...] = ("data", "model"),
                 rules: dict[str, Any] | None = None):
        self.rng = rng
        self.dtype = dtype
        self.mesh_axes = mesh_axes
        self.rules = rules or DEFAULT_RULES

    def next_rng(self) -> jax.Array:
        self.rng, sub = jax.random.split(self.rng)
        return sub

    def param(self, shape: tuple[int, ...], axes: tuple[str | None, ...],
              init: str = "normal", scale: float | None = None):
        spec = logical_to_spec(axes, self.rules, self.mesh_axes)
        if init == "zeros":
            w = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            w = jnp.ones(shape, self.dtype)
        else:
            fan_in = shape[0] if len(shape) > 1 else shape[-1]
            s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
            w = (jax.random.normal(self.next_rng(), shape, jnp.float32)
                 * s).astype(self.dtype)
        return w, spec


def maybe_constrain(x: jnp.ndarray, axes: tuple[str | None, ...]):
    """with_sharding_constraint via logical axis names against the ambient
    mesh (``compat.get_ambient_mesh``).

    No-op when no mesh is ambient (single-device tests).  Inside
    ``shard_map`` *manual* regions, constraining over a manual axis is an
    error, so manual axes are dropped from the candidate mesh axes — a
    fully-manual region (every mesh axis manual, e.g. the MoE dispatch
    body) skips the constraint entirely, while a partial-manual region
    (e.g. the pod-manual gradient-compression wrapper) still constrains
    over the remaining auto axes.  Genuine spec errors (rank mismatch,
    unknown mesh axis) are deliberately *not* swallowed.
    """
    from ..compat import get_ambient_mesh, manual_axis_names

    mesh = get_ambient_mesh()
    if mesh is None:
        return x
    axis_names = tuple(getattr(mesh, "axis_names", ()))
    if not axis_names:
        return x
    manual = manual_axis_names()
    avail = tuple(a for a in axis_names if a not in manual)
    if not avail:
        return x                       # fully-manual shard_map region
    spec = logical_to_spec(axes, mesh_axes=avail)
    return jax.lax.with_sharding_constraint(x, spec)


# -- norms --------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


def apply_norm(kind: str, x, p):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def init_norm(col: ParamCollector, d: int, kind: str):
    if kind == "rmsnorm":
        w, s = col.param((d,), ("act_embed",), init="ones")
        return {"scale": w}, {"scale": s}
    ws, ss = col.param((d,), ("act_embed",), init="ones")
    wb, sb = col.param((d,), ("act_embed",), init="zeros")
    return {"scale": ws, "bias": wb}, {"scale": ss, "bias": sb}


# -- RoPE ----------------------------------------------------------------------

def rope_table(seq: int, head_dim: int, theta: float = 10000.0,
               offset: int = 0):
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = pos[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: (..., S, H, hd).  cos/sin: (S, hd/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
                           ).astype(x.dtype)


# -- loss -----------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Mean CE, stable in f32; vocab axis may be model-sharded (XLA inserts
    the reductions)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
