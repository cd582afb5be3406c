"""The repo's one import path for JAX's sharding API.

The repo targets a single JAX, the installed 0.9 line, on the CPU (tests)
and on TPU v5e.  Every call site goes through this module instead of
touching the sharding namespace directly.  Rules for new code:

  1. Never call ``jax.set_mesh`` directly — use :func:`use_mesh`.
  2. Never call ``jax.shard_map`` directly — use :func:`shard_map`.
  3. Never call ``jax.sharding.get_abstract_mesh`` directly — use
     :func:`get_ambient_mesh` (returns ``None`` when no mesh is ambient).
  4. Build concrete meshes with :func:`make_mesh` (``Auto`` axes: sharding
     constraints and replicated ops keep their meaning without explicit
     ``out_sharding`` arguments).
  5. Never import from ``jax.sharding`` at all outside this module — the
     names the repo needs (``Mesh``, ``PartitionSpec``/``P``,
     ``NamedSharding``) are re-exported here.  Lint rule REPRO001
     (``repro.analysis.lint``) enforces this; this module is the single
     allowlisted file.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Sequence

import jax

Mesh = jax.sharding.Mesh
PartitionSpec = jax.sharding.PartitionSpec
P = PartitionSpec
NamedSharding = jax.sharding.NamedSharding


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    """Concrete mesh over ``devices`` (default: the first ``prod(shape)``
    of ``jax.devices()``) with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` asserts instead of constraining and ops
    such as ``jnp.repeat`` demand an ``out_sharding``; the repo's programs
    are written for ``Auto`` axes."""
    shape, names = tuple(int(s) for s in shape), tuple(axis_names)
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    return jax.make_mesh(shape, names, axis_types=auto, devices=devices)


def abstract_mesh(shape) -> Any:
    """Device-free mesh from ``{axis_name: size}`` (or (name, size) pairs).

    The result carries ``axis_names`` / ``shape`` like a concrete
    ``Mesh`` and is accepted by :func:`shard_map`, so solver programs can
    be abstractly traced for the jaxpr-level audit
    (``repro.analysis.trace``) without any devices.
    """
    pairs = tuple(shape.items()) if hasattr(shape, "items") else tuple(shape)
    return jax.sharding.AbstractMesh(tuple(s for _, s in pairs),
                                     tuple(n for n, _ in pairs))


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_rep: bool = False,
              axis_names: frozenset | set | None = None) -> Callable:
    """``jax.shard_map`` with the replication check off by default.

    ``check_rep`` (``check_vma`` upstream) defaults to False: the
    halo-exchange programs in ``sparse.distributed`` use ``ppermute``,
    whose outputs the check cannot type.  ``axis_names`` is the set of
    mesh axes the body is *manual* over (partial-manual when it is a
    strict subset)."""
    kwargs: dict[str, Any] = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep, **kwargs)


def manual_axis_names() -> frozenset:
    """Mesh axis names the *current trace* is manual over.

    Empty outside ``shard_map``; inside a (fully or partially) manual
    region the ambient abstract mesh types those axes ``Manual``.  Used by
    ``models.common.maybe_constrain`` to drop manual axes from sharding
    constraints (constraining over a manual axis is an error).
    """
    mesh = jax.sharding.get_abstract_mesh()
    return frozenset(
        name for name, t in zip(mesh.axis_names, mesh.axis_types)
        if t == jax.sharding.AxisType.Manual)


def use_mesh(mesh) -> contextlib.AbstractContextManager:
    """Context manager making ``mesh`` ambient for sharding decisions."""
    return jax.set_mesh(mesh)


def get_ambient_mesh() -> Any | None:
    """The abstract mesh made ambient by :func:`use_mesh`, or ``None``.
    It carries ``axis_names`` / ``shape`` and is accepted by
    :func:`shard_map`."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


__all__ = ["Mesh", "PartitionSpec", "P", "NamedSharding", "make_mesh",
           "shard_map", "use_mesh", "get_ambient_mesh",
           "manual_axis_names", "abstract_mesh"]
