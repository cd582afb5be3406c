"""Distributed SpMV/CG over shard_map — run in a subprocess with 8 forced
host devices (the main pytest process must keep the default 1 device).

Exercises the Operator protocol end-to-end: dist_halo and dist_allgather
backends against the scipy oracle, the fused whole-CG shard_map program,
the generic cg_solve driving the distributed operator, and cross-backend
agreement with the single-device ``coo`` (row-group) operator."""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import Topology, scale_to_load, partition
    from repro.sparse.generators import rdg
    from repro.sparse.graph import laplacian_csr
    from repro.sparse import make_operator, cg_solve_global
    import scipy.sparse as sp

    g = rdg(2000, seed=11)
    topo = scale_to_load(Topology.topo1(8, 2/8, 8.0, 8.5), g.n)
    part, tw = partition(g, topo, "geoRef")
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    A = sp.csr_matrix((data, indices, indptr), shape=(g.n, g.n))
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("pu",))

    rng = np.random.default_rng(3)
    x = rng.normal(size=g.n).astype(np.float32)
    b = rng.normal(size=g.n).astype(np.float32)

    op_h = make_operator(indptr, indices, data, "dist_halo",
                         part=part, k=8, mesh=mesh)
    op_s = make_operator(indptr, indices, data, "dist_halo_seq",
                         part=part, k=8, mesh=mesh)
    op_a = make_operator(indptr, indices, data, "dist_allgather",
                         part=part, k=8, mesh=mesh)
    err_halo = float(np.abs(op_h.gather(op_h.matvec(op_h.scatter(x)))
                            - A @ x).max())
    err_seq = float(np.abs(op_s.gather(op_s.matvec(op_s.scatter(x)))
                           - A @ x).max())
    err_ag = float(np.abs(op_a.gather(op_a.matvec(op_a.scatter(x)))
                          - A @ x).max())
    # overlapped vs sequential halo schedule: same plan, same numbers
    ovl_vs_seq = float(np.abs(
        np.asarray(op_h.matvec(op_h.scatter(x)))
        - np.asarray(op_s.matvec(op_s.scatter(x)))).max()
        / max(np.abs(x).max(), 1e-30))

    # fused whole-CG shard_map program (halo and allgather comm modes)
    res = op_h.solve(b, tol=1e-6, max_iters=1500)
    xg = op_h.gather(res.x)
    rel = float(np.linalg.norm(A @ xg - b) / np.linalg.norm(b))
    res_a = op_a.solve(b, tol=1e-6, max_iters=1500)
    rel_ag = float(np.linalg.norm(A @ op_a.gather(res_a.x) - b)
                   / np.linalg.norm(b))

    # generic cg_solve driving the same operator (composable path)
    xg2, iters2, _ = cg_solve_global(op_h, b, tol=1e-6, max_iters=1500)
    rel2 = float(np.linalg.norm(A @ xg2 - b) / np.linalg.norm(b))

    # fused Jacobi-preconditioned CG off the on-device plan diagonal
    res_j = op_h.solve(b, tol=1e-6, max_iters=1500, precondition="jacobi")
    rel_j = float(np.linalg.norm(A @ op_h.gather(res_j.x) - b)
                  / np.linalg.norm(b))

    # cross-backend agreement: single-device COO on the same system
    xc, _, _ = cg_solve_global(make_operator(indptr, indices, data, "coo"), b,
                        tol=1e-6, max_iters=1500)
    cross = float(np.abs(np.asarray(xc) - xg2).max()
                  / max(np.abs(xc).max(), 1e-30))

    plan = op_h.plan
    rt = float(np.abs(plan.gather_vec(plan.scatter_vec(x)) - x).max())

    print(json.dumps({
        "err_halo": err_halo, "err_seq": err_seq, "err_ag": err_ag,
        "ovl_vs_seq": ovl_vs_seq, "cg_rel": rel,
        "iters": int(res.iters), "cg_rel_generic": rel2,
        "iters_generic": int(iters2), "cross_backend_rel": cross,
        "cg_rel_allgather_fused": rel_ag,
        "iters_allgather_fused": int(res_a.iters),
        "cg_rel_jacobi_fused": rel_j, "iters_jacobi_fused": int(res_j.iters),
        "roundtrip": rt, "rounds": plan.n_rounds, "halo_slots": plan.S,
    }))
""")


@pytest.fixture(scope="module")
def dist_results():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_halo_spmv_exact(dist_results):
    assert dist_results["err_halo"] < 1e-3


def test_sequential_halo_spmv_exact(dist_results):
    assert dist_results["err_seq"] < 1e-3


def test_overlapped_matches_sequential_schedule(dist_results):
    # same plan, reordered accumulation only — f32 rounding at most
    assert dist_results["ovl_vs_seq"] < 1e-5


def test_allgather_spmv_exact(dist_results):
    assert dist_results["err_ag"] < 1e-3


def test_distributed_cg_converges(dist_results):
    assert dist_results["cg_rel"] < 1e-3
    assert dist_results["iters"] < 1500


def test_generic_cg_drives_distributed_operator(dist_results):
    assert dist_results["cg_rel_generic"] < 1e-3
    assert dist_results["iters_generic"] < 1500


def test_fused_cg_allgather_comm_mode(dist_results):
    # regression: solve() must honor comm="allgather", not silently halo
    assert dist_results["cg_rel_allgather_fused"] < 1e-3
    assert dist_results["iters_allgather_fused"] < 1500


def test_fused_cg_jacobi_preconditioned(dist_results):
    # PCG off plan.diag converges to the same unpreconditioned tolerance
    assert dist_results["cg_rel_jacobi_fused"] < 1e-3
    assert dist_results["iters_jacobi_fused"] < 1500


def test_cross_backend_agreement(dist_results):
    # COO (single device) and halo shard_map CG agree on the solution
    assert dist_results["cross_backend_rel"] < 1e-3


def test_scatter_gather_roundtrip(dist_results):
    assert dist_results["roundtrip"] == 0.0


def test_edge_coloring_rounds_bounded(dist_results):
    # 8 blocks => quotient degree <= 7; Misra-Gries (Vizing) <= Delta+1 = 8
    assert 1 <= dist_results["rounds"] <= 8
