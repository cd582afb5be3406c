"""Solver-serving layer (`repro.launch.serve.SolverService`) plus the CG
dtype/epsilon bugfix sweep that rides along with it.

Deterministic coverage (the randomized property suite lives in
``test_cg_batched.py``):

  * matrix fingerprint: content-sensitive, structure-prefixed;
  * operator cache: hit/miss counters, LRU eviction purging warm classes;
  * bucketed admission: size classes, padding counters, shape round-trips;
  * served batched solves match per-column sequential solves, with
    per-column iteration counts (a zero column costs 0 iterations);
  * dtype-aware epsilon guards: float32 solves at ~1e-35 scale converge
    (the old additive ``1e-30`` guard drowned ``p^T A p`` and produced a
    garbage step), zero RHS short-circuits cleanly;
  * dtype preservation end to end, incl. a float64 agreement subprocess
    (``JAX_ENABLE_X64=1``);
  * ``--gen 0`` token-serving guard (used to divide by ``args.gen``).
"""
import argparse
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import scipy.sparse as sp

from repro.launch.serve import (SolverService, matrix_fingerprint,
                                _token_serving)
from repro.sparse import CooOperator, cg_solve
from repro.sparse.generators import grid
from repro.sparse.graph import laplacian_csr


def _system(side=10, shift=0.05):
    g = grid((side, side))
    return laplacian_csr(g, shift=shift)


# --------------------------------------------------------------------------
# fingerprint
# --------------------------------------------------------------------------

def test_fingerprint_deterministic_and_structure_prefixed():
    indptr, indices, data = _system()
    fp = matrix_fingerprint(indptr, indices, data)
    assert fp == matrix_fingerprint(indptr, indices, data)
    n, nnz, digest = fp.split(":")
    assert int(n) == len(indptr) - 1
    assert int(nnz) == len(indices)
    assert len(digest) == 32          # blake2b-16 hex


def test_fingerprint_is_content_sensitive():
    indptr, indices, data = _system()
    fp = matrix_fingerprint(indptr, indices, data)
    bumped = data.copy()
    bumped[0] += 1e-3
    # same sparsity structure, different values -> different key
    assert matrix_fingerprint(indptr, indices, bumped) != fp
    assert matrix_fingerprint(indptr, indices,
                              data.astype(np.float64)) != fp


# --------------------------------------------------------------------------
# admission + cache
# --------------------------------------------------------------------------

def test_bucket_classes():
    svc = SolverService(buckets=(1, 2, 4, 8, 16))
    assert [svc.bucket_for(nb) for nb in (1, 2, 3, 5, 16)] == [1, 2, 4, 8, 16]
    assert svc.bucket_for(40) == 40   # oversize: exact-width class


def test_service_validates_configuration():
    with pytest.raises(ValueError):
        SolverService(buckets=(4, 2, 1))
    with pytest.raises(ValueError):
        SolverService(buckets=())
    with pytest.raises(ValueError):
        SolverService(capacity=0)


def test_operator_cache_hits_and_lru_eviction():
    A = _system(8, 0.05)
    B = _system(8, 0.10)
    rng = np.random.default_rng(0)
    b = rng.normal(size=len(A[0]) - 1).astype(np.float32)

    svc = SolverService(capacity=1, max_iters=200)
    r1 = svc.solve(*A, b)
    assert not r1.cache_hit and not r1.warm
    r2 = svc.solve(*A, b)
    assert r2.cache_hit and r2.warm    # same matrix, same size class
    svc.solve(*B, b)                   # capacity 1: evicts A
    r4 = svc.solve(*A, b)
    assert not r4.cache_hit
    assert not r4.warm                 # eviction purged A's warm classes
    s = svc.stats
    assert (s.operator_hits, s.operator_misses, s.operator_evictions) == \
        (1, 3, 2)
    assert s.solves == 4
    # no stale warm entries for evicted fingerprints
    live = {fp for fp, _ in svc._warm}
    assert live <= set(svc._ops)


def test_padding_counters_and_shapes():
    indptr, indices, data = _system(8)
    n = len(indptr) - 1
    rng = np.random.default_rng(1)
    svc = SolverService(max_iters=200)

    resp = svc.solve(indptr, indices, data,
                     rng.normal(size=(n, 3)).astype(np.float32))
    assert resp.bucket == 4
    assert resp.x.shape == (n, 3)      # padding stripped
    assert resp.iters.shape == (3,)
    assert resp.residual.shape == (3,)
    assert svc.stats.real_cols == 3 and svc.stats.padded_cols == 1
    assert svc.stats.padding_waste == pytest.approx(0.25)

    single = svc.solve(indptr, indices, data,
                       rng.normal(size=n).astype(np.float32))
    assert single.bucket == 1
    assert single.x.shape == (n,)
    assert np.ndim(single.iters) == 0


# --------------------------------------------------------------------------
# served solves: correctness + per-column convergence
# --------------------------------------------------------------------------

def test_served_batch_matches_sequential_and_scipy():
    indptr, indices, data = _system(10)
    n = len(indptr) - 1
    A = sp.csr_matrix((data, indices, indptr), shape=(n, n))
    rng = np.random.default_rng(2)
    hard = rng.normal(size=n).astype(np.float32)
    easy = (A @ np.eye(n, dtype=np.float32)[:, 3]).astype(np.float32)
    zero = np.zeros(n, np.float32)
    b = np.stack([hard, easy, zero], axis=1)

    svc = SolverService(tol=1e-7, max_iters=1000)
    resp = svc.solve(indptr, indices, data, b)

    op = CooOperator.from_csr(indptr, indices, data)
    for j, col in enumerate((hard, easy, zero)):
        seq = cg_solve(op, op.scatter(col), tol=1e-7, max_iters=1000)
        xs = op.gather(seq.x)
        scale = max(float(np.abs(xs).max()), 1.0)
        assert np.abs(resp.x[:, j] - xs).max() / scale < 1e-5
        assert abs(int(resp.iters[j]) - int(seq.iters)) <= 2
    # columns converge at genuinely different counts; converged ones freeze
    assert int(resp.iters[2]) == 0                 # zero column is free
    assert int(resp.iters[1]) < int(resp.iters[0])  # b = A e_3 is easy
    dense = sp.linalg.spsolve(A.astype(np.float64),
                              hard.astype(np.float64))
    assert np.abs(resp.x[:, 0] - dense).max() / np.abs(dense).max() < 1e-4


# --------------------------------------------------------------------------
# dtype/epsilon bugfix sweep
# --------------------------------------------------------------------------

def test_float32_tiny_scale_converges():
    """A = 1e-35 * I in float32.  ``p^T A p ~ 1e-34`` is representable but
    far below the old additive ``1e-30`` guard, which dominated the
    denominator and shrank the step by ~1e4x.  The dtype-aware safe
    division takes the exact Newton step: one iteration."""
    n = 8
    s = np.float32(1e-35)
    indptr = np.arange(n + 1, dtype=np.int64)
    indices = np.arange(n, dtype=np.int32)
    data = np.full(n, s, dtype=np.float32)
    b = np.ones(n, np.float32)
    op = CooOperator.from_csr(indptr, indices, data)
    res = cg_solve(op, op.scatter(b), tol=1e-6, max_iters=50)
    x = np.asarray(res.x)
    assert int(res.iters) <= 2
    np.testing.assert_allclose(x, np.full(n, 1.0 / s), rtol=1e-5)


def test_zero_rhs_short_circuits():
    indptr, indices, data = _system(6)
    n = len(indptr) - 1
    op = CooOperator.from_csr(indptr, indices, data)
    res = cg_solve(op, op.scatter(np.zeros(n, np.float32)),
                   tol=1e-6, max_iters=50)
    assert int(res.iters) == 0
    assert np.all(np.asarray(res.x) == 0)
    assert np.isfinite(float(res.residual))


def test_operator_preserves_float32_and_promotes_ints():
    indptr, indices, data = _system(6)
    n = len(indptr) - 1
    op = CooOperator.from_csr(indptr, indices, data)
    assert op.dtype == np.float32
    assert np.asarray(op.diag()).dtype == np.float32
    x = np.ones(n, np.float32)
    assert np.asarray(op.matvec(op.scatter(x))).dtype == np.float32
    res = cg_solve(op, op.scatter(x), tol=1e-6, max_iters=200)
    assert np.asarray(res.x).dtype == np.float32
    # integer values promote to f32 rather than staying int
    op_i = CooOperator.from_csr(indptr, indices,
                                np.ones_like(data, dtype=np.int32))
    assert op_i.dtype == np.float32


F64_SCRIPT = textwrap.dedent("""
    import os
    os.environ["JAX_ENABLE_X64"] = "1"
    import json
    import numpy as np
    import scipy.sparse as sp
    from repro.sparse import CooOperator, cg_solve
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr

    g = grid((10, 10))
    indptr, indices, data = laplacian_csr(g, shift=0.05)
    data64 = data.astype(np.float64)
    A = sp.csr_matrix((data64, indices, indptr), shape=(g.n, g.n))
    rng = np.random.default_rng(5)
    b = rng.normal(size=g.n)

    op = CooOperator.from_csr(indptr, indices, data64)
    res = cg_solve(op, op.scatter(b), tol=1e-12, max_iters=2000)
    x64 = op.gather(res.x)
    dense = sp.linalg.spsolve(A, b)
    rel64 = float(np.abs(x64 - dense).max() / np.abs(dense).max())

    op32 = CooOperator.from_csr(indptr, indices, data)
    res32 = cg_solve(op32, op32.scatter(b.astype(np.float32)),
                     tol=1e-6, max_iters=2000)
    rel32 = float(np.abs(op32.gather(res32.x) - dense).max()
                  / np.abs(dense).max())
    print(json.dumps({"dtype": str(x64.dtype), "rel64": rel64,
                      "dtype32": str(np.asarray(res32.x).dtype),
                      "rel32": rel32}))
""")


def test_float64_agreement_subprocess():
    """With x64 enabled, float64 inputs stay float64 end to end (the old
    operator path forced f32) and CG reaches direct-solver accuracy."""
    proc = subprocess.run([sys.executable, "-c", F64_SCRIPT],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["dtype"] == "float64"
    assert out["rel64"] < 1e-10
    assert out["dtype32"] == "float32"
    assert out["rel32"] < 1e-4


# --------------------------------------------------------------------------
# streaming updates (update_matrix) — fingerprint motion under mutation
# --------------------------------------------------------------------------

def _reweight_pair(indptr, indices, data, i, j, val):
    """EdgeDelta setting the symmetric (i, j) off-diagonal pair plus the
    mutated CSR it should produce."""
    from repro.sparse.replan import EdgeDelta, apply_delta_csr

    n = len(indptr) - 1
    delta = EdgeDelta(n, set_rows=[i, j], set_cols=[j, i],
                      set_vals=[val, val])
    return delta, apply_delta_csr(indptr, indices, data, delta)


def test_update_matrix_moves_fingerprint():
    """A served delta retires the old fingerprint entirely: the mutated
    matrix hits, the *unmutated* one misses — never a stale hit."""
    indptr, indices, data = _system(8)
    n = len(indptr) - 1
    rng = np.random.default_rng(2)
    b = rng.normal(size=n).astype(np.float32)

    svc = SolverService(max_iters=400, tol=1e-7)
    r0 = svc.solve(indptr, indices, data, b)
    delta, (ip2, ix2, d2) = _reweight_pair(indptr, indices, data,
                                           0, 1, -0.5)
    resp = svc.update_matrix(r0.fingerprint, delta)
    assert resp.old_fingerprint == r0.fingerprint
    assert resp.fingerprint == matrix_fingerprint(ip2, ix2, d2)
    assert resp.fingerprint != r0.fingerprint
    # coo operators carry no plan/replan cache -> full rebuild path
    assert not resp.patched and not resp.repartitioned
    assert resp.drift is None and resp.state is None
    assert svc.stats.plan_rebuilds == 1 and svc.stats.plan_patches == 0

    r_new = svc.solve(ip2, ix2, d2, b)
    assert r_new.cache_hit and r_new.fingerprint == resp.fingerprint
    r_old = svc.solve(indptr, indices, data, b)
    assert not r_old.cache_hit            # old matrix: no stale operator

    A2 = sp.csr_matrix((d2, ix2, ip2), shape=(n, n))
    ref = sp.linalg.spsolve(A2.astype(np.float64), b.astype(np.float64))
    assert np.abs(np.asarray(r_new.x) - ref).max() \
        / np.abs(ref).max() < 1e-4


def test_update_matrix_moves_coo_state_to_the_new_row_order():
    """A rebuilt ``coo`` operator orders its rows by the mutated matrix's
    row lengths: operator-space state comes back in the new order.  An
    irregular mesh, which keeps the row groups (a grid's few diagonals
    would keep its rows in their own order)."""
    from repro.sparse.generators import rdg
    from repro.sparse.replan import EdgeDelta

    indptr, indices, data = laplacian_csr(rdg(64, seed=1), shift=0.05)
    n = len(indptr) - 1
    b = np.random.default_rng(5).normal(size=n).astype(np.float32)
    svc = SolverService(max_iters=400, tol=1e-7)
    r0 = svc.solve(indptr, indices, data, b)
    op = svc._ops[r0.fingerprint]
    # two new long-range edges lengthen rows 0 and n - 1
    delta = EdgeDelta(n, set_rows=[0, n - 1], set_cols=[n - 1, 0],
                      set_vals=[-0.5, -0.5])
    resp = svc.update_matrix(r0.fingerprint, delta,
                             state=(op.scatter(b),))
    new_op = svc._ops[resp.fingerprint]
    assert op.layout == new_op.layout == "row groups"
    assert not np.array_equal(new_op.perm, op.perm)
    np.testing.assert_array_equal(new_op.gather(resp.state[0]), b)


def test_update_matrix_unknown_or_evicted_fingerprint_raises():
    indptr, indices, data = _system(8)
    B = _system(8, 0.10)
    rng = np.random.default_rng(3)
    b = rng.normal(size=len(indptr) - 1).astype(np.float32)

    svc = SolverService(capacity=1, max_iters=200)
    with pytest.raises(KeyError):
        svc.update_matrix("0:0:deadbeef", _reweight_pair(
            indptr, indices, data, 0, 1, -0.5)[0])
    rA = svc.solve(indptr, indices, data, b)
    svc.solve(*B, b)                       # capacity 1: evicts A
    with pytest.raises(KeyError):          # evicted == unknown
        svc.update_matrix(rA.fingerprint, _reweight_pair(
            indptr, indices, data, 0, 1, -0.5)[0])


def test_eviction_purges_update_state():
    """LRU eviction of an updated matrix drops its CSR snapshot, drift
    monitor, warm classes and jit programs — no stale streaming state."""
    from repro.core.replan_policy import DriftPolicy

    indptr, indices, data = _system(8)
    B = _system(8, 0.10)
    rng = np.random.default_rng(4)
    b = rng.normal(size=len(indptr) - 1).astype(np.float32)

    n = len(indptr) - 1
    # part is a factory-level hint the coo backend ignores, but it lets
    # the drift monitor price plan-less operators
    svc = SolverService(capacity=1, max_iters=200,
                        part=((np.arange(n) * 4) // n).astype(np.int32),
                        drift=DriftPolicy(max_objective_ratio=1e6,
                                          max_imbalance_ratio=1e6))
    r0 = svc.solve(indptr, indices, data, b)
    delta, (ip2, ix2, d2) = _reweight_pair(indptr, indices, data,
                                           0, 1, -0.5)
    resp = svc.update_matrix(r0.fingerprint, delta)
    assert resp.drift is not None          # monitor priced the update
    assert resp.fingerprint in svc._monitors
    assert resp.old_fingerprint not in svc._csr
    assert resp.fingerprint in svc._csr and resp.fingerprint in svc._ops
    svc.solve(ip2, ix2, d2, b)

    svc.solve(*B, b)                       # capacity 1: evicts mutated A
    assert resp.fingerprint not in svc._ops
    assert resp.fingerprint not in svc._csr
    assert resp.fingerprint not in svc._monitors
    assert not any(fp == resp.fingerprint for fp, _ in svc._warm)
    assert not any(fp == resp.fingerprint for fp, _ in svc._cost)
    # every auxiliary table only references live operators
    assert set(svc._csr) == set(svc._ops)
    assert set(svc._monitors) <= set(svc._ops)
    assert {fp for fp, _ in svc._warm} <= set(svc._ops)


DELTA_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import scipy.sparse as sp
    from repro.core.replan_policy import DriftPolicy
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import SolverService
    from repro.sparse.replan import EdgeDelta, apply_delta_csr
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr

    g = grid((16, 16))
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    n, k = g.n, 8
    part = ((np.arange(n) * k) // n).astype(np.int32)
    mesh = make_test_mesh(8, fanouts=(2, 4))
    repart_calls = []

    def repartition(gs):
        repart_calls.append(gs.n)
        return part

    svc = SolverService(backend="dist_hier", capacity=4, max_iters=400,
                        tol=1e-7, part=part, k=k, mesh=mesh,
                        fanouts=(2, 4),
                        drift=DriftPolicy(max_objective_ratio=1.2),
                        repartition=repartition)
    rng = np.random.default_rng(0)
    b = rng.normal(size=n).astype(np.float32)
    r0 = svc.solve(indptr, indices, data, b)

    # 1) value delta -> O(delta) plan patch, not a rebuild
    dv = EdgeDelta(n, set_rows=[0, 1], set_cols=[1, 0],
                   set_vals=[-0.5, -0.5])
    ip2, ix2, d2 = apply_delta_csr(indptr, indices, data, dv)
    r1 = svc.update_matrix(r0.fingerprint, dv)
    assert r1.patched and not r1.repartitioned
    assert r1.drift is not None and not r1.drift.repartition
    hit = svc.solve(ip2, ix2, d2, b)
    assert hit.cache_hit and hit.fingerprint == r1.fingerprint
    miss = svc.solve(indptr, indices, data, b)
    assert not miss.cache_hit
    A2 = sp.csr_matrix((d2, ix2, ip2), shape=(n, n)).astype(np.float64)
    ref = sp.linalg.spsolve(A2, b.astype(np.float64))
    rel = float(np.abs(np.asarray(hit.x) - ref).max()
                / np.abs(ref).max())

    # 2) heavy cross-partition insertions -> drift trip -> repartition,
    #    with CG state migrated (not restarted)
    plan = svc._ops[r1.fingerprint].plan
    xs = plan.scatter_vec(b)
    u = np.arange(0, 30, dtype=np.int64)
    v = (n - 1 - u)
    ds = EdgeDelta(n, set_rows=np.concatenate([u, v]),
                   set_cols=np.concatenate([v, u]),
                   set_vals=np.full(60, -1.0))
    r2 = svc.update_matrix(r1.fingerprint, ds, state=(xs,))
    assert r2.drift.repartition and "objective" in r2.drift.reason
    assert r2.repartitioned and not r2.patched
    assert len(repart_calls) == 1
    new_plan = svc._ops[r2.fingerprint].plan
    migrated = np.asarray(new_plan.gather_vec(r2.state[0]))
    state_exact = bool(np.array_equal(migrated, b))

    s = svc.stats
    print(json.dumps({
        "rel": rel, "state_exact": state_exact,
        "patches": s.plan_patches, "rebuilds": s.plan_rebuilds,
        "trips": s.drift_trips,
        "fp_moved": r2.fingerprint != r1.fingerprint != r0.fingerprint,
    }))
""")


def test_update_matrix_patches_dist_plan_subprocess():
    """dist_hier serving: a value delta is an O(delta) plan patch; a
    drift trip forces repartition + exact CG-state migration (8 forced
    host devices, set before jax import)."""
    proc = subprocess.run([sys.executable, "-c", DELTA_SCRIPT],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rel"] < 1e-4
    assert out["state_exact"]
    assert (out["patches"], out["rebuilds"], out["trips"]) == (1, 1, 1)
    assert out["fp_moved"]


# --------------------------------------------------------------------------
# --gen 0 guard
# --------------------------------------------------------------------------

def test_token_serving_gen_zero(capsys):
    args = argparse.Namespace(arch="qwen1.5-0.5b", smoke=True, batch=1,
                              prompt_len=4, gen=0, temperature=0.8)
    _token_serving(args)      # used to raise ZeroDivisionError
    out = capsys.readouterr().out
    assert "decode skipped (--gen 0)" in out
