"""Shared benchmark utilities — timing, CSV row emission, and the tracked
JSON baseline writer."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import Callable

BASELINES = Path(__file__).resolve().parent / "baselines"


def cpu_rehearsal() -> None:
    """Pin this process, and through the environment every child it
    starts, to JAX's CPU backend, and keep compiled programs in the
    persistent compile cache.

    The benches that force host devices are CPU rehearsals of the
    solver schedules, never chip measurements: their timings say how
    fast XLA's CPU backend is.  Call before the first JAX computation."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_platforms", "cpu")
    use_compile_cache()


def run_child(argv: list[str], timeout: int, env: dict | None = None) -> dict:
    """Run one bench child (``python <argv>``) and return the JSON object
    on the last line of its stdout.  A failed child fails the bench: its
    stderr is echoed and the process exits nonzero — a failure is never
    recorded as a result."""
    proc = subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise SystemExit(f"bench child {argv[:2]} failed with exit code "
                         f"{proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def write_bench_json(name: str, payload: dict) -> None:
    """``benchmarks/baselines/BENCH_<name>.json`` — the machine-readable
    counterpart of the CSV rows, committed per PR so the perf trajectory
    is diffable across the git history (the repo root's ``BENCH_*.json``
    scratch outputs stay ignored)."""
    BASELINES.mkdir(exist_ok=True)
    path = BASELINES / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"# wrote {path}", file=sys.stderr, flush=True)


def time_us(fn: Callable, *args, reps: int = 5, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn(*args)
    return (time.perf_counter() - t0) / reps * 1e6


def row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"
