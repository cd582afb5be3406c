"""Smoke run of the paper's pipeline as a service on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip path only

Deployment: the paper's own application (Sec. VI-a), CG on a shifted graph
Laplacian of the DIMACS10 ``delaunay_n20`` family — 2^20 random points
(made from ``--seed``), Delaunay-triangulated.  One process drives every
phase through the entry points a user calls:

  partition  Algorithm-1 targets on a heterogeneous 6-PU topology with one
             memory-saturated PU, then ``partition(..., method="geoKM")``
             (its balanced k-means loop runs on the chip);
  serve      ``SolverService(backend="coo")`` answers batched requests of
             several widths; every column's true residual is recomputed on
             the host in float64 with SciPy;
  kernel     the Pallas block-ELL backend (``bell``) on a 512x512 grid
             Laplacian against ``coo``: one matvec and one CG solve, and
             the compiled program must hold the Mosaic kernel.

``--chips 4`` runs only the four-chip path: geoKM into k=4 blocks for four
equal chips, ``SolverService`` on ``dist_halo`` (1-D mesh) and on
``dist_hier`` (fanouts (2, 2)), each request checked against the one-chip
``coo`` solve of the same request and against SciPy residuals, and every
plan array and solver input checked to span the four devices.

Times printed here are smoke times of a cold process (compiles included),
not benchmark results.  The last stdout line is one JSON object naming the
device; the script exits nonzero, printing no such line, when JAX finds no
TPU or any check fails.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

# deployment: delaunay_n20, Laplacian diagonal shifted by SHIFT (the paper
# shifts "slightly" without a value; 0.1 is under 2% of the mean diagonal
# of ~6 and takes CG to 1e-6 in ~90 iterations)
LOG2_N = 20
SHIFT = 0.1
KERNEL_GRID = (512, 512)
# solver: relative residual target of the service's CG, and the bound on
# the float64 SciPy residual of every returned column (f32 recurrences
# drift from the true residual; measured ~6e-6 at n = 2^16)
CG_TOL = 1e-6
MAX_ITERS = 2000
RES_TOL = 1e-4
# agreement bounds: bell vs coo matvec (same f32 products, different
# summation order) and solutions of two backends (both at CG_TOL)
MATVEC_RTOL = 1e-5
SOLUTION_RTOL = 1e-3
# partition: largest block may exceed its Algorithm-1 target by EPS
EPS = 0.03
# request widths: several admission buckets, and the last request repeats
# the matrix and width of the second (must be a cache hit on a warm class)
WIDTHS = (1, 3, 8, 16, 2, 3)
BUCKETS = (1, 4, 8, 16)
# four chips: a few requests in one admission bucket (one compile each)
FOUR_CHIP_WIDTHS = (3, 2, 4)


def log(*parts) -> None:
    print(*parts, flush=True)


def smoke_time(name: str, t0: float) -> None:
    log(f"smoke time {name}: {time.perf_counter() - t0:.3f} s")


def deployment(log2n: int = LOG2_N, seed: int = 0):
    """``delaunay_n<log2n>`` graph and its shifted Laplacian (CSR)."""
    from repro.sparse.generators import rdg
    from repro.sparse.graph import laplacian_csr

    t0 = time.perf_counter()
    g = rdg(1 << log2n, seed=seed)
    csr = laplacian_csr(g, shift=SHIFT)
    nnz = len(csr[1])
    log(f"deployment delaunay_n{log2n}: n={g.n} edges={g.num_edges} "
        f"nnz={nnz} operator_bytes(padded COO)={12 * nnz}")
    smoke_time("generate", t0)
    return g, csr


def heterogeneous_topology(n: int):
    """Six PUs of speeds 4:4:2:2:1:1; the first may hold only 15% of the
    vertices, so Algorithm 1 saturates it."""
    from repro.core import PU, Topology

    caps = (0.15, 0.5, 0.5, 0.5, 0.5, 0.5)
    speeds = (4.0, 4.0, 2.0, 2.0, 1.0, 1.0)
    return Topology(tuple(PU(s, c * n, f"pu{i}")
                          for i, (s, c) in enumerate(zip(speeds, caps))))


def partition_phase(g, topo, seed: int = 0, eps: float = EPS):
    """Algorithm-1 targets + geoKM; checks every block against its
    target.  Returns the partition."""
    from repro.core import partition, target_block_sizes
    from repro.core.block_sizes import saturated_mask
    from repro.core.metrics import block_sizes_of, edge_cut, imbalance

    t0 = time.perf_counter()
    tw = target_block_sizes(g.n, topo)
    sat = saturated_mask(g.n, topo)
    log(f"partition: k={topo.k} targets={np.round(tw, 1).tolist()} "
        f"saturated={np.flatnonzero(sat).tolist()}")
    part, tw = partition(g, topo, method="geoKM", tw=tw, seed=seed,
                         eps=eps)
    sizes = block_sizes_of(part, topo.k)
    imb = imbalance(part, tw)
    log(f"partition geoKM: sizes={sizes.tolist()} cut={edge_cut(g, part)} "
        f"imbalance={imb:.6f}")
    smoke_time("partition", t0)
    check(bool(np.all(sizes <= (1 + eps) * tw)),
          f"a block exceeds its target by more than eps={eps}")
    return part


def scipy_residuals(csr, b: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Per-column ||b - A x|| / ||b|| in float64 (SciPy)."""
    import scipy.sparse as sp

    indptr, indices, data = csr
    n = len(indptr) - 1
    a = sp.csr_matrix((np.asarray(data, np.float64), indices, indptr),
                      shape=(n, n))
    b2 = b.reshape(n, -1).astype(np.float64)
    r = b2 - a @ x.reshape(n, -1).astype(np.float64)
    return np.linalg.norm(r, axis=0) / np.linalg.norm(b2, axis=0)


def check_solution(csr, b, resp, tag: str) -> np.ndarray:
    """Finite, right shape, every column within RES_TOL (SciPy)."""
    x = np.asarray(resp.x)
    check(x.shape == b.shape and bool(np.all(np.isfinite(x))),
          f"{tag}: bad solution shape/values {x.shape}")
    rel = scipy_residuals(csr, b, x)
    log(f"{tag}: bucket={resp.bucket} cache_hit={resp.cache_hit} "
        f"warm={resp.warm} iters={np.atleast_1d(resp.iters).tolist()} "
        f"scipy_rel_residual_max={rel.max():.3e}")
    check(bool(np.all(rel <= RES_TOL)),
          f"{tag}: residual {rel.max():.3e} > {RES_TOL}")
    return x


def serve_phase(csr, widths=WIDTHS, seed: int = 0):
    """``SolverService(backend="coo")`` answers one request per width."""
    from repro.launch.serve import SolverService

    svc = SolverService(backend="coo", buckets=BUCKETS, tol=CG_TOL,
                        max_iters=MAX_ITERS)
    n = len(csr[0]) - 1
    rng = np.random.default_rng(seed)
    seen = set()
    for i, nb in enumerate(widths):
        b = rng.normal(size=(n, nb)).astype(np.float32)
        t0 = time.perf_counter()
        resp = svc.solve(*csr, b)
        smoke_time(f"serve request {i} (nb={nb})", t0)
        check_solution(csr, b, resp, f"serve request {i} nb={nb}")
        if nb in seen:
            check(resp.cache_hit and resp.warm,
                  f"repeat request {i} (nb={nb}) was not a warm cache hit")
        seen.add(nb)
    s = svc.stats
    log(f"serve stats: operator hits={s.operator_hits} "
        f"misses={s.operator_misses} bucket hits={s.bucket_hits} "
        f"misses={s.bucket_misses} padding_waste={s.padding_waste:.3f}")
    return svc


def kernel_phase(shape=KERNEL_GRID, seed: int = 0, want_kernel: bool = True):
    """``bell`` vs ``coo`` on a naturally ordered grid Laplacian: one
    matvec and one single-RHS CG solve.  With ``want_kernel`` the compiled
    solve must hold the Mosaic kernel (``tpu_custom_call``)."""
    import jax

    from repro.sparse import cg_solve, make_operator
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr

    t0 = time.perf_counter()
    g = grid(shape)
    csr = laplacian_csr(g, shift=SHIFT)
    coo = make_operator(*csr, "coo")
    bell = make_operator(*csr, "bell")
    s, nnzb = bell.cols.shape
    log(f"kernel: grid {shape[0]}x{shape[1]} n={g.n} stripes={s} "
        f"nnzb={nnzb}")
    smoke_time("kernel build", t0)
    rng = np.random.default_rng(seed)
    x = jax.numpy.asarray(rng.normal(size=g.n).astype(np.float32))
    y_c, y_b = np.asarray(coo.matvec(x)), np.asarray(bell.matvec(x))
    rel = np.abs(y_b - y_c).max() / np.abs(y_c).max()
    log(f"kernel matvec bell vs coo: max rel diff {rel:.3e}")
    check(rel <= MATVEC_RTOL, f"bell matvec differs from coo by {rel:.3e}")

    b = rng.normal(size=g.n).astype(np.float32)
    solve = jax.jit(functools.partial(cg_solve, tol=CG_TOL,
                                      max_iters=MAX_ITERS))
    t0 = time.perf_counter()
    compiled = solve.lower(bell, b).compile()
    smoke_time("kernel compile (bell CG)", t0)
    has_kernel = "tpu_custom_call" in compiled.as_text()
    log(f"kernel: compiled bell CG holds tpu_custom_call: {has_kernel}")
    if want_kernel:
        check(has_kernel, "bell CG program holds no tpu_custom_call")
    t0 = time.perf_counter()
    r_b = compiled(bell, b)
    x_b = np.asarray(r_b.x)
    smoke_time("kernel bell CG solve", t0)
    x_c = np.asarray(solve(coo, b).x)
    diff = np.linalg.norm(x_b - x_c) / np.linalg.norm(x_c)
    rel_b = scipy_residuals(csr, b, x_b)[0]
    log(f"kernel CG: bell iters={int(r_b.iters)} "
        f"scipy_rel_residual={rel_b:.3e} |x_bell - x_coo|/|x_coo|="
        f"{diff:.3e}")
    check(rel_b <= RES_TOL, f"bell CG residual {rel_b:.3e} > {RES_TOL}")
    check(diff <= SOLUTION_RTOL, f"bell and coo CG differ by {diff:.3e}")


def _spans(arrays, devices) -> bool:
    import jax

    leaves = [a for a in jax.tree.leaves(arrays) if isinstance(a, jax.Array)]
    return bool(leaves) and all(a.sharding.device_set == set(devices)
                                for a in leaves)


def four_chip_phase(g, csr, devices, widths=FOUR_CHIP_WIDTHS,
                    seed: int = 0):
    """geoKM into four equal blocks; ``dist_halo`` and ``dist_hier``
    services against the one-chip ``coo`` solve of the same requests."""
    import jax

    from repro.compat import make_mesh
    from repro.core import Topology
    from repro.launch.serve import SolverService

    part = partition_phase(g, Topology.homogeneous(4, memory=g.n))
    services = {
        "coo": SolverService(backend="coo", buckets=BUCKETS, tol=CG_TOL,
                             max_iters=MAX_ITERS),
        "dist_halo": SolverService(
            backend="dist_halo", buckets=BUCKETS, tol=CG_TOL,
            max_iters=MAX_ITERS, part=part, k=4,
            mesh=make_mesh((4,), ("pu",), devices)),
        "dist_hier": SolverService(
            backend="dist_hier", buckets=BUCKETS, tol=CG_TOL,
            max_iters=MAX_ITERS, part=part, k=4, fanouts=(2, 2),
            mesh=make_mesh((2, 2), ("pod", "pu"), devices)),
    }
    n = g.n
    rng = np.random.default_rng(seed)
    for i, nb in enumerate(widths):
        b = rng.normal(size=(n, nb)).astype(np.float32)
        xs = {}
        for name, svc in services.items():
            t0 = time.perf_counter()
            resp = svc.solve(*csr, b)
            smoke_time(f"{name} request {i} (nb={nb})", t0)
            xs[name] = check_solution(csr, b, resp,
                                      f"{name} request {i} nb={nb}")
        for name in ("dist_halo", "dist_hier"):
            diff = (np.linalg.norm(xs[name] - xs["coo"], axis=0)
                    / np.linalg.norm(xs["coo"], axis=0)).max()
            log(f"{name} request {i}: |x - x_coo|/|x_coo| max {diff:.3e}")
            check(diff <= SOLUTION_RTOL,
                  f"{name} differs from the one-chip coo solve by {diff:.3e}")
    for name in ("dist_halo", "dist_hier"):
        _, op, _ = services[name].operator_for(*csr)
        plan_arrays = list(vars(op.plan).values())
        fused = op.fused_solver(CG_TOL, MAX_ITERS, None)
        b = op.scatter(np.ones((n, 1), np.float32))
        ok = (_spans(plan_arrays, devices) and _spans(fused.args, devices)
              and _spans(b, devices))
        log(f"{name}: plan arrays, solver operands and input span "
            f"{len(devices)} devices: {ok}")
        check(ok, f"{name}: an array does not span all {len(devices)} "
                  f"devices")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: partition, serve and kernel phases on one "
                         "chip; 4: only the four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {use_compile_cache()}")
    t_all = time.perf_counter()
    try:
        g, csr = deployment(seed=args.seed)
        if args.chips == 4:
            four_chip_phase(g, csr, devices, seed=args.seed)
        else:
            partition_phase(g, heterogeneous_topology(g.n), seed=args.seed)
            serve_phase(csr, seed=args.seed)
            kernel_phase(seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    smoke_time("total", t_all)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
