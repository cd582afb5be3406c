"""Operator protocol: single-device backends through the one cg_solve,
plus the cross-backend agreement matrix (promoted from benchmarks/
bench_cg.py): every backend/preconditioner combination solves the same
2-D grid Laplacian in an 8-device subprocess and must agree to < 1e-5.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.sparse import (BlockEllOperator, CooOperator, Operator,
                          cg_solve, make_operator, cg_solve_global)
from repro.sparse.generators import rdg
from repro.sparse.graph import laplacian_csr


@pytest.fixture(scope="module")
def system():
    # small instance: the interpreted Pallas kernel's grid is O(S * NNZB)
    # and a whole-CG trace multiplies it; shift=0.1 keeps the condition
    # number low enough for tight cross-backend agreement in f32
    g = rdg(300, seed=5)
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    import scipy.sparse as sp
    A = sp.csr_matrix((data, indices, indptr), shape=(g.n, g.n))
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)
    return (indptr, indices, data), A, b


def test_factory_and_protocol(system):
    (indptr, indices, data), A, b = system
    for backend in ("coo", "bell"):
        op = make_operator(indptr, indices, data, backend)
        assert isinstance(op, Operator)
        assert op.n == A.shape[0]
    assert isinstance(make_operator(indptr, indices, data, "coo"),
                      CooOperator)
    assert isinstance(make_operator(indptr, indices, data, "bell"),
                      BlockEllOperator)
    with pytest.raises(ValueError):
        make_operator(indptr, indices, data, "nope")
    with pytest.raises(ValueError):
        make_operator(indptr, indices, data, "dist_halo")   # missing part/k
    with pytest.raises(ValueError):
        make_operator(indptr, indices, data, "dist_hier")   # missing part/k


def test_block_jacobi_requires_distributed_backend(system):
    (indptr, indices, data), A, b = system
    import jax.numpy as jnp
    op = make_operator(indptr, indices, data, "coo")
    with pytest.raises(ValueError):
        cg_solve(op, jnp.asarray(b), precondition="block_jacobi")


@pytest.mark.parametrize("backend", ["coo", "bell"])
def test_matvec_matches_scipy(system, backend):
    (indptr, indices, data), A, b = system
    op = make_operator(indptr, indices, data, backend)
    x = np.random.default_rng(0).normal(size=op.n).astype(np.float32)
    y = op.gather(op.matvec(op.scatter(x)))
    np.testing.assert_allclose(y, A @ x, atol=1e-4, rtol=1e-4)


def test_cg_backends_agree(system):
    (indptr, indices, data), A, b = system
    sols = {}
    for backend in ("coo", "bell"):
        op = make_operator(indptr, indices, data, backend)
        x, iters, res = cg_solve_global(op, b, tol=1e-7, max_iters=2000)
        rel = np.linalg.norm(A @ x - b) / np.linalg.norm(b)
        assert rel < 1e-4, (backend, rel)
        sols[backend] = x
    scale = np.abs(sols["coo"]).max()
    assert np.abs(sols["coo"] - sols["bell"]).max() / scale < 1e-5


def test_cg_solve_accepts_operator_or_callable(system):
    (indptr, indices, data), A, b = system
    import jax.numpy as jnp
    op = make_operator(indptr, indices, data, "coo")
    r1 = cg_solve(op, jnp.asarray(b), tol=1e-6, max_iters=2000)
    r2 = cg_solve(op.matvec, jnp.asarray(b), tol=1e-6, max_iters=2000)
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x),
                               atol=1e-6)
    assert int(r1.iters) == int(r2.iters)


def test_jacobi_preconditioned_cg_single_device(system):
    (indptr, indices, data), A, b = system
    op = make_operator(indptr, indices, data, "coo")
    # diag() matches scipy
    np.testing.assert_allclose(op.gather(op.diag()), A.diagonal(),
                               atol=1e-5, rtol=1e-5)
    x_pl, it_pl, _ = cg_solve_global(op, b, tol=1e-7, max_iters=2000)
    x_pc, it_pc, _ = cg_solve_global(op, b, tol=1e-7, max_iters=2000,
                                     precondition="jacobi")
    # both stop on the same unpreconditioned tolerance => same quality
    for x in (x_pl, x_pc):
        rel = np.linalg.norm(A @ x - b) / np.linalg.norm(b)
        assert rel < 1e-4
    scale = np.abs(x_pl).max()
    assert np.abs(x_pl - x_pc).max() / scale < 1e-5


def test_jacobi_requires_operator():
    import jax.numpy as jnp
    with pytest.raises(ValueError):
        cg_solve(lambda x: x, jnp.ones(4), precondition="jacobi")


# -- cross-backend agreement matrix (one subprocess, 8 host devices) -------
# The dist_hier rows run on the two-level (pods=2, k=8) mesh from
# make_test_mesh(8, pods=2); the dist_tree3 rows on the depth-3
# (2, 2, 2) ("pod", "host", "pu") mesh from make_test_mesh(8,
# fanouts=(2, 2, 2)) — the depth-3 configuration, whose ppermutes run
# over suffix-combined axes.

CROSS_BACKENDS = ("coo", "coo+jacobi", "bell", "bell+jacobi",
                  "dist_halo", "dist_halo+jacobi",
                  "dist_halo+jacobi_fused", "dist_halo+block_jacobi",
                  "dist_halo_seq", "dist_bell",
                  "dist_allgather", "dist_hier", "dist_hier+jacobi",
                  "dist_hier+block_jacobi_fused", "dist_hier_podaware",
                  "dist_hier_bell", "dist_tree3", "dist_tree3_bell",
                  "dist_tree3_aware", "dist_tree3_bottleneck",
                  "dist_tree3+block_jacobi_fused",
                  "dist_hier_batched")

CROSS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr
    from repro.sparse import make_operator, cg_solve_global
    from repro.launch.mesh import make_test_mesh

    g = grid((24, 24))                       # the 2-D grid Laplacian
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    part = np.random.default_rng(0).integers(0, 8, g.n)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("pu",))
    mesh_hier = make_test_mesh(8, pods=2)    # ("pod", "pu") = (2, 4)
    mesh_tree = make_test_mesh(8, fanouts=(2, 2, 2))   # depth 3
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)

    # partition-derived (swept, generally non-contiguous) pod assignment
    # driving the hier runtime — the ISSUE 4 acceptance path
    from repro.core import (Topology, partition_tree, pod_assignment_for,
                            scale_to_load)
    topo8 = scale_to_load(Topology.homogeneous(8), g.n)
    pod_sw = pod_assignment_for(g, part, topo8, 2)
    # tree-aware depth-3 partition driving the runtime (ISSUE 5)
    topo_t = scale_to_load(Topology.homogeneous(8, fanouts=(2, 2, 2)), g.n)
    res_tree = partition_tree(g, topo_t, "greedyRef", seed=0)
    # bottleneck-refined depth-3 partition on the same mesh (ISSUE 9):
    # the makespan objective must only reshape the partition, never the
    # solution the runtime computes on it
    res_btree = partition_tree(g, topo_t, "greedyRef", seed=0,
                               objective="bottleneck")
    assert res_btree.objective == "bottleneck"

    sols = {}
    extra = {}
    for name in %r:
        backend, _, variant = name.partition("+")
        kw = {}
        if backend == "dist_hier_batched":
            # fused multi-RHS masked CG on the two-level mesh: column 0 is
            # the shared b (feeds the agreement matrix); the whole batch
            # must match per-column sequential fused solves, with
            # per-column iteration counts equal to the sequential ones
            op = make_operator(indptr, indices, data, "dist_hier",
                               part=part, k=8, mesh=mesh_hier, pods=2)
            rngb = np.random.default_rng(7)
            bb = np.stack(
                [b, rngb.normal(size=g.n).astype(np.float32),
                 0.01 * b + rngb.normal(
                     scale=0.1, size=g.n).astype(np.float32)], axis=1)
            resb = op.solve(bb, tol=1e-7, max_iters=2000)
            xb = op.gather(resb.x)
            sols[name] = xb[:, 0]
            seq = [op.solve(bb[:, j], tol=1e-7, max_iters=2000)
                   for j in range(3)]
            extra["batched_vs_seq"] = max(
                float(np.abs(xb[:, j] - op.gather(seq[j].x)).max())
                / max(float(np.abs(op.gather(seq[j].x)).max()), 1e-30)
                for j in range(3))
            extra["batched_iters"] = np.asarray(resb.iters).tolist()
            extra["seq_iters"] = [int(s.iters) for s in seq]
            continue
        if backend == "dist_hier_podaware":
            backend = "dist_hier"
            kw = dict(part=part, k=8, mesh=mesh_hier, pods=pod_sw)
        elif backend == "dist_tree3_aware":
            backend = "dist_hier"            # HierPartition unpack path
            kw = dict(part=res_tree, mesh=mesh_tree)
        elif backend == "dist_tree3_bottleneck":
            backend = "dist_hier"
            kw = dict(part=res_btree, mesh=mesh_tree)
        elif backend.startswith("dist_tree3"):
            backend = ("dist_hier_bell" if backend.endswith("bell")
                       else "dist_hier")
            kw = dict(part=part, k=8, mesh=mesh_tree, fanouts=(2, 2, 2))
        elif backend.startswith("dist"):
            kw = dict(part=part, k=8, mesh=mesh)
            if backend in ("dist_hier", "dist_hier_bell"):
                kw.update(mesh=mesh_hier, pods=2)
        op = make_operator(indptr, indices, data, backend, **kw)
        if variant.endswith("fused"):
            res = op.solve(b, tol=1e-7, max_iters=2000,
                           precondition=variant[:-6] or None)
            sols[name] = op.gather(res.x)
        else:
            x, _, _ = cg_solve_global(op, b, tol=1e-7, max_iters=2000,
                                      precondition=variant or None)
            sols[name] = x
    ref = sols["coo"]
    scale = float(np.abs(ref).max())
    rel = {name: float(np.abs(x - ref).max()) / scale
           for name, x in sols.items()}
    rel.update({"_" + key: v for key, v in extra.items()})
    print(json.dumps(rel))
""") % (CROSS_BACKENDS,)


@pytest.fixture(scope="module")
def cross_backend_rel():
    proc = subprocess.run([sys.executable, "-c", CROSS_SCRIPT],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CROSS_BACKENDS)
def test_cross_backend_agreement_2d_grid(cross_backend_rel, name):
    assert cross_backend_rel[name] < 1e-5, (name, cross_backend_rel)


def test_batched_dist_hier_matches_sequential(cross_backend_rel):
    """Fused multi-RHS CG on the two-level mesh: every column of the
    batched solve matches its per-column sequential fused solve."""
    assert cross_backend_rel["_batched_vs_seq"] < 1e-5, cross_backend_rel


def test_batched_dist_hier_per_column_iters(cross_backend_rel):
    """Per-column convergence masks do per-column work: each column's
    iteration count tracks its sequential solve (the masked loop freezes
    converged columns instead of running everyone to the max), and total
    work never exceeds nb * max(iters)."""
    batched = cross_backend_rel["_batched_iters"]
    seq = cross_backend_rel["_seq_iters"]
    assert len(batched) == len(seq) == 3
    for bi, si in zip(batched, seq):
        assert abs(bi - si) <= 2, (batched, seq)
    assert sum(batched) <= len(batched) * max(batched)


def test_spmv_coo_accepts_explicit_static_n():
    # regression: n was a traced arg under jit and crashed jnp.zeros(n)
    import jax.numpy as jnp
    from repro.sparse.spmv import spmv_coo
    rows = jnp.asarray([0, 1, 2])
    cols = jnp.asarray([0, 1, 0])
    vals = jnp.asarray([1.0, 2.0, 3.0])
    x = jnp.asarray([1.0, 1.0])
    y = spmv_coo(rows, cols, vals, x, n=3)
    np.testing.assert_allclose(np.asarray(y), [1.0, 2.0, 3.0])
