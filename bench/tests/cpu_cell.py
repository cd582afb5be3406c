"""Run a cell at a tiny size on the CPU, through the functions the chip
run uses, optionally with the timed path broken underneath.

    python -m bench.tests.cpu_cell <workload> [--devices N]

runs the cell sound, as its control and with each fault its kind can
have, and prints the results as one JSON object.  ``--devices 4`` gives
the CPU four devices (set before JAX starts), for the four-chip cell.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "delaunay_n20.solve_1col": {
        "config": {"generator_params": {"log2n": 10}, "n": 2 ** 10}},
    "delaunay_n20.partition_topo": {
        "config": {"generator_params": {"log2n": 14}, "n": 2 ** 14,
                   "pus": 12}},
    "delaunay_n20_x4.solve_mixed": {
        "config": {"generator_params": {"log2n": 10}, "n": 2 ** 10}},
}
SEED = 2 ** 33 + 7          # larger than 32 signed bits hold


def _break_solve(patch, edit):
    """Wrap ``SolverService._run`` so that ``edit`` rewrites the CG result's
    ``x`` (operator space, columns on the last axis)."""
    from repro.launch.serve import SolverService

    run = SolverService._run

    def broken(self, op, bcols):
        res = run(self, op, bcols)
        return res._replace(x=edit(res.x))

    patch(SolverService, "_run", broken)


def _break_partition(patch, edit):
    import repro.core

    partition = repro.core.partition

    def broken(g, topo, *a, **kw):
        part, tw = partition(g, topo, *a, **kw)
        return edit(part), tw

    patch(repro.core, "partition", broken)


def _unchanged(x):
    """CG hands back its starting state, x = 0."""
    import jax.numpy as jnp

    return jnp.zeros_like(x)


def _half_batch(x):
    """The second half of the columns is never solved."""
    import jax.numpy as jnp

    keep = -(-x.shape[-1] // 2)
    return jnp.where(jnp.arange(x.shape[-1]) < keep, x, 0)


def _altered(x):
    """One value changed where it is produced."""
    return x.at[(0,) * x.ndim].add(1.0)


def _no_exchange(patch):
    """The halo exchange between chips sends nothing."""
    import jax
    import jax.numpy as jnp

    patch(jax.lax, "ppermute", lambda x, axis_name, perm: jnp.zeros_like(x))


def _shuffled(part):
    import numpy as np

    part = np.asarray(part).copy()
    np.random.default_rng(0).shuffle(part)
    return part


# the faults each kind of cell can have; each takes ``patch(obj, name,
# value)`` (pytest's ``monkeypatch.setattr`` in-process)
FAULTS = {
    "solve": {
        "unchanged": lambda patch: _break_solve(patch, _unchanged),
        "half_batch": lambda patch: _break_solve(patch, _half_batch),
        "altered": lambda patch: _break_solve(patch, _altered),
        "no_exchange": _no_exchange,
    },
    "partition": {
        "unchanged": lambda patch: _break_partition(patch, lambda p: 0 * p),
        "altered": lambda patch: _break_partition(patch, _shuffled),
    },
}


def run(workload: str, fault: str | None = None, control: bool = False,
        trace: bool = False, seconds: float = 0.5, seed: int = SEED,
        root: Path = ROOT, overrides: dict | None = None,
        patch=setattr) -> dict:
    from bench import harness
    import repro.launch.compile_cache as cc

    patch(cc, "use_compile_cache", lambda: "not used on the CPU")
    if fault:
        kind = harness.load_cell(root, workload).traffic["kind"]
        FAULTS[kind][fault](patch)
    return harness.run_cell(workload, seed, seconds, trace, root=root,
                            accelerator=False, control=control,
                            overrides=TINY[workload] if overrides is None
                            else overrides)


class Patches:
    """``setattr`` that remembers, so that ``undo`` puts everything back."""

    def __init__(self):
        self.saved = []

    def __call__(self, obj, name, value):
        self.saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self.saved:
            setattr(*self.saved.pop())


def suite(workload: str) -> dict:
    """A sound run, the control and every fault of the cell's kind, one
    after another in this process: ``{"sound": result, "control": ...,
    "<fault>": ...}``."""
    from bench import harness

    kind = harness.load_cell(ROOT, workload).traffic["kind"]
    out = {}
    for name in ["sound", "control", *FAULTS[kind]]:
        patches = Patches()
        try:
            out[name] = run(workload, fault=name if name in FAULTS[kind]
                            else None, control=name == "control",
                            patch=patches)
        finally:
            patches.undo()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                               f"{args.devices}")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    print(json.dumps(suite(args.workload), default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
