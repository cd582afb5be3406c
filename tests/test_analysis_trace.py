"""Trace-auditor tests (ISSUE 8 tentpole): per-rule mutation suite,
clean corpus over every backend, and the static cost model's acceptance
oracle against the metrics-side communication volumes.

The mutation pattern mirrors ``test_analysis_verify.py``: corrupt a
traced program *or its plan* and assert exactly the right TRACE code
fires.  The headline case is the seeded drift the PR 6 plan verifier
provably cannot catch — a fully self-consistent swap of two exchange
rounds (perms + send schedule + the halo slot ranges the edges read)
passes every PLAN0xx invariant, but the staged program still replays the
*original* round order, so only the jaxpr-level audit sees the mismatch.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import compat
from repro.analysis import TRACE_RULES, audit_backend, audit_jaxpr, \
    audit_operator, verify_plan
from repro.core.metrics import comm_volumes, tree_comm_volumes
from repro.core.topology import canonical_ancestors
from repro.launch.mesh import tree_axis_names
from repro.launch.roofline import static_roofline
from repro.sparse.generators import GENERATORS, grid
from repro.sparse.graph import laplacian_csr
from repro.sparse.operator import _HIER_BACKENDS, BACKENDS, make_operator



def _system(n=144, seed=0, generator="grid_2d"):
    g = GENERATORS[generator](n, seed=seed)
    nv = len(g.indptr) - 1
    return (g, nv) + laplacian_csr(g, shift=0.1)


def _rng_part(nv, k, seed=0):
    # a random partition gives every level several distinct non-empty
    # rounds — what the round-swap mutations need
    return np.random.default_rng(seed).integers(0, k, size=nv)


def _flat_op(comm="halo", k=4, seed=0):
    _, nv, indptr, indices, data = _system(seed=seed)
    backend = {"halo": "dist_halo", "halo_seq": "dist_halo_seq",
               "allgather": "dist_allgather"}[comm]
    mesh = compat.abstract_mesh({"pu": k})
    return make_operator(indptr, indices, data, backend,
                         part=_rng_part(nv, k, seed), k=k, mesh=mesh)


def _tree_op(fanouts=(2, 2), seed=0):
    _, nv, indptr, indices, data = _system(seed=seed)
    k = int(np.prod(fanouts))
    names = tree_axis_names(len(fanouts))
    mesh = compat.abstract_mesh(dict(zip(names, fanouts)))
    return make_operator(indptr, indices, data, "dist_hier",
                         part=_rng_part(nv, k, seed), k=k, mesh=mesh,
                         fanouts=fanouts)


def _matvec_jaxpr(op):
    return jax.make_jaxpr(op.matvec)(op.operand_spec())


# ------------------------------------------------------------ clean corpus

@pytest.mark.parametrize("backend", BACKENDS)
def test_clean_corpus_default_backends(backend):
    """Every backend of the cross-backend operator matrix traces with
    zero diagnostics on the default fixture (matvec AND fused CG)."""
    rep = audit_backend(backend, n=144, fanouts=(2, 2))
    assert rep.ok, str(rep)
    assert rep.info["cost_matvec"] is not None
    assert rep.info["cost_cg"] is not None


@pytest.mark.parametrize("backend", _HIER_BACKENDS)
def test_clean_corpus_depth3(backend):
    rep = audit_backend(backend, n=144, fanouts=(2, 2, 2))
    assert rep.ok, str(rep)


@pytest.mark.parametrize("backend", ["coo", "dist_halo", "dist_hier"])
def test_clean_corpus_batched(backend):
    rep = audit_backend(backend, n=144, fanouts=(2, 2), nb=3)
    assert rep.ok, str(rep)


@pytest.mark.parametrize("precondition", ["jacobi", "block_jacobi"])
def test_clean_corpus_preconditioned(precondition):
    rep = audit_backend("dist_hier", n=144, fanouts=(2, 2),
                        precondition=precondition)
    assert rep.ok, str(rep)


# ------------------------------------------------------------------ rules

def test_rule_table_is_complete():
    assert set(TRACE_RULES) == {"TRACE001", "TRACE002", "TRACE003",
                                "TRACE004", "TRACE005"}
    for code, desc in TRACE_RULES.items():
        assert desc and code.startswith("TRACE")


# --------------------------------------------------------------- TRACE001

def test_trace001_dropped_round():
    """Plan claims one round fewer than the program stages."""
    op = _flat_op()
    mut = dataclasses.replace(op.plan,
                              round_perms=tuple(op.plan.round_perms[:-1]))
    rep = audit_jaxpr(_matvec_jaxpr(op), plan=mut, axis="pu", comm="halo")
    assert rep.codes() == {"TRACE001"}, str(rep)


def test_trace001_level_with_no_rounds():
    """A level whose schedule was emptied still stages its ppermutes."""
    op = _tree_op()
    lvl = next(l for l in range(op.plan.h)
               if any(p for p in op.plan.round_perms_lvl[l]))
    rp = list(op.plan.round_perms_lvl)
    rp[lvl] = ((),) * len(rp[lvl])
    mut = dataclasses.replace(op.plan, round_perms_lvl=tuple(rp))
    rep = audit_jaxpr(_matvec_jaxpr(op), plan=mut, axis=op.axis,
                      comm="hier")
    assert rep.codes() == {"TRACE001"}, str(rep)
    assert any(f"level {lvl}" in d.where for d in rep.diagnostics)


# --------------------------------------------------------------- TRACE002

def _two_distinct_rounds(perms):
    """(c0, c1) of two non-empty rounds with different pair sets."""
    ne = [(c, frozenset(map(tuple, p))) for c, p in enumerate(perms) if p]
    for i, (c0, s0) in enumerate(ne):
        for c1, s1 in ne[i + 1:]:
            if s0 != s1:
                return c0, c1
    raise AssertionError("fixture has no two distinct rounds")


def test_trace002_swapped_permutation():
    op = _flat_op()
    c0, c1 = _two_distinct_rounds(op.plan.round_perms)
    pm = list(op.plan.round_perms)
    pm[c0], pm[c1] = pm[c1], pm[c0]
    mut = dataclasses.replace(op.plan, round_perms=tuple(pm))
    rep = audit_jaxpr(_matvec_jaxpr(op), plan=mut, axis="pu", comm="halo")
    assert rep.codes() == {"TRACE002"}, str(rep)
    assert len(rep.diagnostics) == 2        # both swapped rounds named


def _swap_rounds_consistently(plan, lvl, c0, c1):
    """Exchange rounds c0 and c1 of tree level ``lvl`` *consistently*:
    perms, send schedule columns, and the halo slot ranges every edge
    reads all move together, so the mutated plan satisfies every PLAN0xx
    invariant — it is simply a different (equally valid) schedule than
    the one the program was staged from."""
    offs = plan.level_offsets()
    S = int(plan.S_lvl[lvl])
    a0, a1 = int(offs[lvl]) + c0 * S, int(offs[lvl]) + c1 * S

    def remap(cols):
        cols = np.asarray(cols).copy()
        in0 = (cols >= a0) & (cols < a0 + S)
        in1 = (cols >= a1) & (cols < a1 + S)
        cols[in0] += a1 - a0
        cols[in1] += a0 - a1
        return jnp.asarray(cols)

    perms = list(plan.round_perms_lvl[lvl])
    perms[c0], perms[c1] = perms[c1], perms[c0]
    si = np.asarray(plan.send_idx_lvl[lvl]).copy()
    sm = np.asarray(plan.send_mask_lvl[lvl]).copy()
    si[:, [c0, c1]] = si[:, [c1, c0]]
    sm[:, [c0, c1]] = sm[:, [c1, c0]]
    rp = list(plan.round_perms_lvl)
    rp[lvl] = tuple(perms)
    sil = list(plan.send_idx_lvl)
    sil[lvl] = jnp.asarray(si)
    sml = list(plan.send_mask_lvl)
    sml[lvl] = jnp.asarray(sm)
    return dataclasses.replace(
        plan, round_perms_lvl=tuple(rp), send_idx_lvl=tuple(sil),
        send_mask_lvl=tuple(sml), cols=remap(plan.cols),
        cols_bnd_lvl=tuple(remap(c) for c in plan.cols_bnd_lvl))


def test_trace002_drift_the_plan_verifier_cannot_catch():
    """The acceptance-criterion drift: a consistent round swap passes the
    full PR 6 structural verifier (it IS a valid plan — just not the one
    the program was staged from), and only the trace auditor flags it."""
    op = _tree_op()
    lvl = next(l for l in range(op.plan.h)
               if sum(1 for p in op.plan.round_perms_lvl[l] if p) >= 2)
    c0, c1 = _two_distinct_rounds(op.plan.round_perms_lvl[lvl])
    mut = _swap_rounds_consistently(op.plan, lvl, c0, c1)

    vrep = verify_plan(mut)
    assert vrep.ok, "the plan verifier must be blind to this drift:\n" \
        + str(vrep)

    rep = audit_jaxpr(_matvec_jaxpr(op), plan=mut, axis=op.axis,
                      comm="hier")
    assert rep.codes() == {"TRACE002"}, str(rep)


# --------------------------------------------------------------- TRACE003

def test_trace003_wrong_axis_name():
    """Auditing the program against a different axis leaves its staged
    ppermutes underivable (TRACE003) and the expected axis empty-handed
    (TRACE001)."""
    op = _flat_op()
    rep = audit_jaxpr(_matvec_jaxpr(op), plan=op.plan, axis="data",
                      comm="halo")
    assert rep.codes() == {"TRACE001", "TRACE003"}, str(rep)


def test_trace003_collective_in_single_device_program():
    op = _flat_op()
    rep = audit_jaxpr(_matvec_jaxpr(op), plan=None, comm=None)
    assert rep.codes() == {"TRACE003"}, str(rep)


def test_trace003_allgather_not_in_schedule():
    op = _flat_op(comm="allgather")
    rep = audit_jaxpr(_matvec_jaxpr(op), plan=None, comm=None)
    assert "TRACE003" in rep.codes(), str(rep)


# --------------------------------------------------------------- TRACE004

def test_trace004_injected_bf16_roundtrip():
    _, _, indptr, indices, data = _system()
    op = make_operator(indptr, indices, data, "coo")

    def f(x):
        return op.matvec(x.astype(jnp.bfloat16).astype(jnp.float32))

    rep = audit_jaxpr(jax.make_jaxpr(f)(op.operand_spec()))
    assert rep.codes() == {"TRACE004"}, str(rep)
    dirs = {(d.details["src"], d.details["dst"]) for d in rep.diagnostics}
    assert dirs == {("float32", "bfloat16"), ("bfloat16", "float32")}


# --------------------------------------------------------------- TRACE005

def test_trace005_f64_leak_under_x64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * np.float64(2.0))(
            jax.ShapeDtypeStruct((8,), np.float32))
    rep = audit_jaxpr(closed, base_dtype=np.float32)
    assert "TRACE005" in rep.codes(), str(rep)


def test_trace005_silent_without_x64():
    # without x64 the same program stays f32: no leak, no diagnostic
    closed = jax.make_jaxpr(lambda x: x * np.float64(2.0))(
        jax.ShapeDtypeStruct((8,), np.float32))
    rep = audit_jaxpr(closed, base_dtype=np.float32)
    assert rep.ok, str(rep)


# ------------------------------------------------ static cost model oracle

def _stripes_fixture(shape, k):
    g = grid(shape)
    nv = g.n
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    part = (np.arange(nv) * k) // nv
    return g, indptr, indices, data, part


@pytest.mark.parametrize("fanouts", [(2, 2), (2, 2, 2)])
def test_payload_bytes_match_tree_comm_volumes(fanouts):
    """Acceptance oracle: per-level payload bytes equal the metrics-side
    deduplicated received-word volumes x itemsize exactly — counted
    elements x dtype size, no tolerance."""
    k = int(np.prod(fanouts))
    g, indptr, indices, data, part = _stripes_fixture((32, 64), k)
    names = tree_axis_names(len(fanouts))
    mesh = compat.abstract_mesh(dict(zip(names, fanouts)))
    op = make_operator(indptr, indices, data, "dist_hier", part=part,
                       k=k, mesh=mesh, fanouts=fanouts)
    rep = audit_operator(op, solver=False)
    assert rep.ok, str(rep)
    cost = rep.info["cost_matvec"]
    vols = tree_comm_volumes(g, part, k, canonical_ancestors(fanouts))
    itemsize = np.dtype(np.float32).itemsize
    expect = tuple(float(v.sum()) * itemsize for v in vols)
    assert cost.comm_payload_bytes_lvl == expect


def test_payload_bytes_match_flat_comm_volumes():
    k = 4
    g, indptr, indices, data, part = _stripes_fixture((32, 64), k)
    op = make_operator(indptr, indices, data, "dist_halo", part=part,
                       k=k, mesh=compat.abstract_mesh({"pu": k}))
    rep = audit_operator(op, solver=False)
    assert rep.ok, str(rep)
    cost = rep.info["cost_matvec"]
    expect = float(comm_volumes(g, part, k).sum()) * 4
    assert cost.comm_payload_bytes_lvl == (expect,)


def test_patched_plan_traces_like_fresh_build():
    """A delta-patched plan (ISSUE 10) drives the staged program through
    TRACE001-005 clean, and its static comm cost equals the fresh build's
    on the mutated matrix — the audit can't tell patch from rebuild."""
    import dataclasses

    from repro.sparse.replan import (EdgeDelta, apply_delta_csr,
                                     apply_edge_delta)

    _, nv, indptr, indices, data = _system()
    k, fanouts = 4, (2, 2)
    part = _rng_part(nv, k)
    mesh = compat.abstract_mesh(dict(zip(tree_axis_names(2), fanouts)))
    op = make_operator(indptr, indices, data, "dist_hier", part=part,
                       k=k, mesh=mesh, fanouts=fanouts)
    # structural mutation: a new symmetric corner-to-corner edge crosses
    # every tree level, so the patched schedules must re-trace cleanly
    delta = EdgeDelta(nv, set_rows=[0, nv - 1], set_cols=[nv - 1, 0],
                      set_vals=[-1.0, -1.0])
    op2 = dataclasses.replace(op, plan=apply_edge_delta(op.plan, delta))
    rep = audit_operator(op2, solver=False)
    assert rep.ok, str(rep)
    ip2, ix2, d2 = apply_delta_csr(indptr, indices, data, delta)
    fresh = make_operator(ip2, ix2, d2, "dist_hier", part=part, k=k,
                          mesh=mesh, fanouts=fanouts)
    ref = audit_operator(fresh, solver=False)
    assert ref.ok, str(ref)
    assert rep.info["cost_matvec"].comm_payload_bytes_lvl == \
        ref.info["cost_matvec"].comm_payload_bytes_lvl


def test_batched_payload_scales_with_nb():
    k = 4
    _, indptr, indices, data, part = _stripes_fixture((16, 16), k)
    op = make_operator(indptr, indices, data, "dist_halo", part=part,
                       k=k, mesh=compat.abstract_mesh({"pu": k}))
    one = audit_operator(op, solver=False).info["cost_matvec"]
    three = audit_operator(op, solver=False, nb=3).info["cost_matvec"]
    assert three.comm_payload_bytes_lvl == tuple(
        3 * b for b in one.comm_payload_bytes_lvl)


def test_cost_is_roofline_consumable():
    rep = audit_backend("dist_hier", n=144, fanouts=(2, 2))
    cost = rep.info["cost_cg"]
    for out in (cost.roofline(), static_roofline(cost)):
        assert {"compute_s", "memory_s", "collective_s",
                "dominant"} <= set(out)
        assert out["per_iteration"] is True
        assert out["n_devices"] == 4
        assert all(np.isfinite(out[t]) and out[t] >= 0
                   for t in ("compute_s", "memory_s", "collective_s"))
    assert cost.flops_per_iter > 0
    assert cost.hbm_bytes_per_iter > 0
    # the fused CG stages its dot-product psums: all-reduce bytes appear
    assert cost.collectives().get("all-reduce", 0) > 0
    with pytest.raises(ValueError, match="no published peaks"):
        static_roofline(cost, device_kind="cpu")


def test_cg_cost_separates_loop_body():
    """``flops_per_iter`` counts only the while-body; ``flops`` is the
    setup outside it (the initial residual's matvec etc.) — both must be
    populated for a CG program, and the loop body strictly exceeds one
    bare matvec (it adds the axpy/dot vector work)."""
    rep = audit_backend("dist_halo", n=144, fanouts=(2, 2))
    cg = rep.info["cost_cg"]
    mv = rep.info["cost_matvec"]
    assert cg.flops > 0 and cg.flops_per_iter > 0
    # one CG iteration does one matvec plus vector work
    assert cg.flops_per_iter > mv.flops_per_iter
    # the matvec program has no loop: per-iter == whole program
    assert mv.flops_per_iter == mv.flops


def test_cost_to_dict_is_jsonable():
    import json

    rep = audit_backend("dist_hier", n=144, fanouts=(2, 2))
    payload = json.dumps(rep.to_dict())
    back = json.loads(payload)
    assert back["ok"] is True
    assert back["info"]["cost_cg"]["n_devices"] == 4
    assert isinstance(back["info"]["cost_cg"]["comm_payload_bytes_lvl"],
                      list)


# ------------------------------------------------------- serving pricing

def test_solver_service_static_cost():
    from repro.launch.serve import SolverService

    g = grid((12, 12))
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    svc = SolverService(backend="coo", buckets=(1, 2, 4), max_iters=50)
    out = svc.static_cost(indptr, indices, data, nb=3)
    assert out["bucket"] == 4 and out["ok"]
    assert out["roofline"]["static_flops_per_iter"] > 0
    # same size class -> cached price object, no re-trace
    assert svc.static_cost(indptr, indices, data, nb=4) is out
    assert svc.static_cost(indptr, indices, data, nb=1) is not out


def test_solver_service_static_cost_distributed():
    from repro.launch.serve import SolverService

    g = grid((16, 16))
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    part = (np.arange(g.n) * 4) // g.n
    svc = SolverService(backend="dist_halo", part=part, k=4,
                        mesh=compat.abstract_mesh({"pu": 4}),
                        max_iters=50)
    out = svc.static_cost(indptr, indices, data, nb=2)
    assert out["ok"], out["diagnostics"]
    assert out["roofline"]["n_devices"] == 4
    assert out["cost"].comm_payload_bytes_lvl[0] > 0
