"""Production mesh construction.

Kept as FUNCTIONS so importing this module never touches jax device state
(device count is frozen at first jax init; the dry-run sets
xla_force_host_platform_device_count=512 before importing anything).
"""
from __future__ import annotations

import jax
import numpy as np

from ..compat import make_mesh


def tree_axis_names(h: int) -> tuple[str, ...]:
    """Axis names for a depth-``h`` tree mesh, outermost first: the
    two-level ``("pod", "pu")`` of PR 3, ``("pod", "host", "pu")`` at
    depth 3 (the paper's chip < host < pod nesting), generic ``lv{i}``
    prefixes beyond."""
    if h == 1:
        return ("pu",)
    if h == 2:
        return ("pod", "pu")
    if h == 3:
        return ("pod", "host", "pu")
    return tuple(f"lv{i}" for i in range(h - 1)) + ("pu",)


def make_production_mesh(*, multi_pod: bool = False, fanouts=None):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    ``fanouts=(k_1, ..., k_h)`` overrides the shape with an arbitrary-
    depth tree mesh (one axis per tree level, outermost first) for the
    ``comm='hier'`` tree plans; a 3-tuple keeps the multi-pod
    ``("pod", "data", "model")`` axis names so existing specs map on."""
    if fanouts is not None:
        fanouts = tuple(int(f) for f in fanouts)
        axes = (("pod", "data", "model") if len(fanouts) == 3
                else tree_axis_names(len(fanouts)))
        return make_mesh(fanouts, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(k: int = 8, axes: tuple[str, ...] = ("data",),
                   pods: int | None = None, fanouts=None):
    """Small mesh for subprocess tests (host platform devices).

    ``pods=p`` builds the two-level ``(p, k // p)`` mesh with axes
    ``("pod", "pu")`` — the test-scale analogue of
    ``make_production_mesh(multi_pod=True)``'s ``("pod", "data", "model")``
    — for the hierarchical SpMV/CG plans (``sparse.distributed.
    build_plan_hier`` / backend ``dist_hier``).  ``fanouts=(k_1, ...,
    k_h)`` builds the arbitrary-depth tree mesh (one axis per level,
    outermost first — e.g. ``(2, 2, 2)`` is the depth-3
    ``("pod", "host", "pu")`` mesh of ``build_plan_tree``).
    """
    devs = jax.devices()[:k]
    if fanouts is not None:
        if pods is not None or axes != ("data",):
            raise ValueError("fanouts= fixes the axes to the tree levels; "
                             f"drop pods={pods!r} / axes={axes!r}")
        fanouts = tuple(int(f) for f in fanouts)
        if int(np.prod(fanouts)) != k:
            raise ValueError(f"prod(fanouts)={np.prod(fanouts)} != k={k}")
        return make_mesh(fanouts, tree_axis_names(len(fanouts)), devs)
    if pods is not None:
        if axes != ("data",):
            raise ValueError("pods= fixes the axes to ('pod', 'pu'); "
                             f"drop axes={axes!r}")
        if pods <= 0 or k % pods:
            raise ValueError(f"pods={pods} must divide k={k}")
        return make_mesh((pods, k // pods), ("pod", "pu"), devs)
    shape = (k,) if len(axes) == 1 else (k // 2, 2)
    return make_mesh(shape, axes, devs)


# Published per-chip peaks for the roofline analysis, keyed by
# ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" (per-chip specifications).
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": dict(
        peak_flops=197e12,      # bf16 FLOP/s
        hbm_bw=819e9,           # HBM B/s
        hbm_bytes=16e9,         # HBM capacity
        ici_bw=1600e9 / 8,      # chip-to-chip interconnect: 1,600 Gbit/s
    ),
}

# the chip this repo's static rooflines price against (a TPU v5e)
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str = TARGET_KIND) -> dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

# Deployment flags for real TPU pods: compute/communication overlap is
# XLA's latency-hiding scheduler — the collective schedule this framework
# emits (weight all-gathers ahead of their dots, grad reduce-scatters
# behind the backward) is what the scheduler overlaps.  The CPU dry-run
# backend runs collectives synchronously, so these are set at launch, not
# measured here.
TPU_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true "
)
