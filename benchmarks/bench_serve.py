"""Solver-serving benchmark: multi-RHS batched CG behind the
``SolverService`` cache/admission layer, across the coo / dist_halo /
dist_hier backends.

Per backend (`make bench-serve`):

  * **cold vs warm latency** — the first request for a (matrix, size
    class) pays plan construction + format conversion + the jit trace;
    every repeat is an operator-cache hit landing on the compiled
    program.  ``speedup = cold / warm_p50`` is the serving headline (the
    acceptance bar is >= 5x).
  * **throughput** — solves/sec and p50/p95/max latency over warm
    repeat traffic with fresh RHS batches.
  * **batched vs sequential** — a mixed-difficulty nb=4 batch (hard /
    easy / zero / scaled columns) served in one masked batched solve must
    match the four single-column solves to < 1e-5, with per-column
    iteration counts summing to fewer matvec-equivalents than the naive
    ``nb x max(iters)`` (converged columns freeze instead of riding
    along).

A CPU rehearsal: the process and its children run on JAX's CPU backend
(``common.cpu_rehearsal``), so no number here is a chip measurement.
Distributed backends run in a subprocess with 8 forced host devices
(this process keeps the default 1); same caveat as bench_cg — host
devices show schedule overhead, not interconnect wins.  A failed child
exits the bench nonzero.  Results land in CSV rows on stdout and
``benchmarks/baselines/BENCH_serve.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from .common import cpu_rehearsal, row, run_child, write_bench_json

WARM_REQUESTS = 12
NB = 4


def _measure(backend: str) -> dict:
    """Runs under whatever device count the process was started with —
    in-process for coo, an 8-device subprocess for dist backends."""
    import jax
    import scipy.sparse as sp

    from repro.launch.serve import SolverService
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr

    g = grid((48, 32))
    indptr, indices, data = laplacian_csr(g, shift=0.05)
    n = g.n
    kw = {}
    if backend in ("dist_halo", "dist_hier"):
        part = (np.arange(n) * 8) // n      # locality-preserving stripes
        kw = dict(part=part, k=8)
        if backend == "dist_hier":
            from repro.launch.mesh import make_test_mesh
            kw.update(mesh=make_test_mesh(8, pods=2), pods=2)
        else:
            kw.update(mesh=jax.sharding.Mesh(np.array(jax.devices()),
                                             ("pu",)))
    svc = SolverService(backend=backend, tol=1e-6, max_iters=600, **kw)
    rng = np.random.default_rng(0)

    # standalone plan build + format conversion — the planning-path cost
    # a cache miss pays before any compilation (bench-diff gates it)
    from repro.sparse import make_operator
    t0 = time.perf_counter()
    make_operator(indptr, indices, data, backend, **kw)
    plan_build_s = time.perf_counter() - t0

    def fresh_batch():
        return rng.normal(size=(n, NB)).astype(np.float32)

    t0 = time.perf_counter()
    first = svc.solve(indptr, indices, data, fresh_batch())
    cold_ms = (time.perf_counter() - t0) * 1e3
    assert not first.cache_hit and not first.warm

    lat = []
    t_all = time.perf_counter()
    for _ in range(WARM_REQUESTS):
        t0 = time.perf_counter()
        r = svc.solve(indptr, indices, data, fresh_batch())
        np.asarray(r.x)
        lat.append((time.perf_counter() - t0) * 1e3)
    wall = time.perf_counter() - t_all
    assert r.cache_hit and r.warm
    lat = np.sort(np.array(lat))
    warm_p50 = float(np.percentile(lat, 50))

    # batched vs per-column sequential, mixed difficulty
    A = sp.csr_matrix((data, indices, indptr), shape=(n, n))
    hard = rng.normal(size=n).astype(np.float32)
    e = np.zeros(n, np.float32)
    e[n // 2] = 1.0
    easy = (A @ e).astype(np.float32)
    cols = [hard, easy, np.zeros(n, np.float32),
            (0.1 * hard).astype(np.float32)]
    resp = svc.solve(indptr, indices, data, np.stack(cols, axis=1))
    rel = 0.0
    seq_iters = []
    for j, col in enumerate(cols):
        single = svc.solve(indptr, indices, data, col)
        seq_iters.append(int(single.iters))
        scale = max(float(np.abs(single.x).max()), 1.0)
        rel = max(rel, float(np.abs(resp.x[:, j] - single.x).max()) / scale)
    iters = [int(i) for i in np.asarray(resp.iters)]
    s = svc.stats
    return {
        "n": n, "nb": NB,
        "plan_build_s": plan_build_s,
        "cold_ms": cold_ms,
        "warm_p50_ms": warm_p50,
        "warm_p95_ms": float(np.percentile(lat, 95)),
        "warm_max_ms": float(lat[-1]),
        "speedup_cold_over_warm": cold_ms / warm_p50,
        "solves_per_sec": WARM_REQUESTS / wall,
        "batched_vs_seq_rel": rel,
        "batched_iters": iters,
        "seq_iters": seq_iters,
        "matvec_equiv": int(sum(iters)),
        "matvec_equiv_naive": NB * max(iters),
        "operator_hits": s.operator_hits,
        "operator_misses": s.operator_misses,
        "bucket_hits": s.bucket_hits,
        "bucket_misses": s.bucket_misses,
        "padding_waste": s.padding_waste,
    }


def _subprocess_measure(backend: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return run_child(["-m", "benchmarks.bench_serve", "--inner", backend],
                     timeout=1200, env=env)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", metavar="BACKEND",
                    help="(internal) measure one backend and print JSON")
    ap.add_argument("--backends", default="coo,dist_halo,dist_hier",
                    help="comma-separated backends to bench")
    args = ap.parse_args()
    cpu_rehearsal()
    if args.inner:
        print(json.dumps(_measure(args.inner)))
        return

    rows = ["name,us,derived"]
    payload = {"bench": "serve", "warm_requests": WARM_REQUESTS,
               "backends": {}}
    for backend in args.backends.split(","):
        backend = backend.strip()
        out = (_measure(backend) if backend == "coo"
               else _subprocess_measure(backend))
        payload["backends"][backend] = out
        rows.append(row(f"serve_{backend}_cold", out["cold_ms"] * 1e3,
                        f"nb={out['nb']} n={out['n']}"))
        rows.append(row(
            f"serve_{backend}_warm_p50", out["warm_p50_ms"] * 1e3,
            f"speedup={out['speedup_cold_over_warm']:.1f}x "
            f"solves/s={out['solves_per_sec']:.1f}"))
        rows.append(row(
            f"serve_{backend}_batched", 0,
            f"rel={out['batched_vs_seq_rel']:.1e} "
            f"matvecs={out['matvec_equiv']}/"
            f"{out['matvec_equiv_naive']} naive"))
    write_bench_json("serve", payload)
    print("\n".join(rows))


if __name__ == "__main__":
    main()
