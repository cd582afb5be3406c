"""Fig. 5 analogue: CG time per iteration under different partitions
(TOPO3-style heterogeneity).

Two measurements per partitioner:
  * real: measured single-process SpMV+CG microseconds (CPU; homogeneous);
  * modeled heterogeneous step time, the paper's TOPO3 simulation —
        T_iter = max_i(|b_i| * c_nnz / speed_i) + alpha * maxCommVolume
    with c_nnz the measured per-row SpMV cost and alpha the per-word
    exchange cost (derived from the halo plan, not guessed).

Plus the Operator-era rows:
  * ``build_plan`` vectorization speedup vs the seed per-edge builder
    (256x256 grid Laplacian, k=8, random partition = maximal boundary);
  * cross-backend CG agreement (coo / bell / dist_halo (overlapped) /
    dist_halo_seq / dist_bell / dist_allgather, plus Jacobi-preconditioned
    variants, through the one ``make_operator`` + ``cg_solve_global``
    harness, the distributed ones on 8 forced host devices in a
    subprocess);
  * overlapped vs sequential halo SpMV microseconds.  Caveat: on forced
    host devices a ppermute is a same-process memcpy with no latency to
    hide, so the overlapped schedule's split (two scatter-adds instead of
    one) shows pure overhead here; the win appears on real interconnects
    where the interior matvec runs while the rounds are in flight.
"""
from __future__ import annotations

import textwrap
import time

import jax.numpy as jnp
import numpy as np

from repro.core import Topology, partition, scale_to_load, \
    target_block_sizes
from repro.core.metrics import block_sizes_of, max_comm_volume
from repro.sparse.cg import cg_solve
from repro.sparse.distributed import build_plan, build_plan_reference
from repro.sparse.generators import grid, rdg
from repro.sparse.graph import laplacian_csr
from repro.sparse.spmv import csr_to_padded_coo, spmv_coo

from .common import cpu_rehearsal, row, run_child, \
    write_bench_json as _write_bench_json

DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np
    import jax
    from repro.sparse.generators import rdg
    from repro.sparse.graph import laplacian_csr
    from repro.sparse import make_operator, cg_solve_global

    g = rdg(512, seed=9)
    indptr, indices, data = laplacian_csr(g, shift=0.1)
    part = np.random.default_rng(0).integers(0, 8, g.n)
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("pu",))
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)

    out = {}
    sols = {}
    for name in ("coo", "coo+jacobi", "bell", "dist_halo",
                 "dist_halo+jacobi", "dist_halo_seq", "dist_bell",
                 "dist_allgather"):
        backend, _, variant = name.partition("+")
        kw = (dict(part=part, k=8, mesh=mesh)
              if backend.startswith("dist") else {})
        op = make_operator(indptr, indices, data, backend, **kw)
        t0 = time.perf_counter()
        x, iters, res = cg_solve_global(op, b, tol=1e-7, max_iters=2000,
                                        precondition=variant or None)
        out[name] = {"iters": iters, "res": res,
                     "wall_us": (time.perf_counter() - t0) * 1e6}
        sols[name] = x
    scale = float(np.abs(sols["coo"]).max())
    out["max_pairwise_rel"] = max(
        float(np.abs(sols[a] - sols[b2]).max()) / scale
        for a in sols for b2 in sols if a < b2)

    # overlapped vs sequential halo vs allgather SpMV microseconds.
    # Locality-preserving stripes on a 64x32 grid: interior rows dominate
    # (the regime the overlap targets), unlike the worst-case random
    # partition above where nearly every row is boundary.
    from repro.sparse.generators import grid
    g = grid((64, 32))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    part = (np.arange(g.n) * 8) // g.n
    xb = None
    for backend in ("dist_halo", "dist_halo_seq", "dist_allgather"):
        op = make_operator(indptr, indices, data, backend,
                           part=part, k=8, mesh=mesh)
        xb = op.scatter(np.random.default_rng(3).normal(
            size=g.n).astype(np.float32))
        op.matvec(xb).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            y = op.matvec(xb)
        y.block_until_ready()
        out[backend + "_spmv_us"] = (time.perf_counter() - t0) / 20 * 1e6
    print(json.dumps(out))
""")


HIER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np
    import jax
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr
    from repro.sparse import make_operator, cg_solve_global
    from repro.sparse.distributed import build_plan, build_plan_hier
    from repro.launch.mesh import make_test_mesh

    # locality-preserving stripes on the 2-D grid Laplacian: the partition
    # spans 2 pods, so only the pod-crossing cut pays the slow links
    g = grid((64, 32))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    part = (np.arange(g.n) * 8) // g.n
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("pu",))
    mesh_hier = make_test_mesh(8, pods=2)            # ("pod", "pu")
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)

    out = {}
    fp = build_plan(indptr, indices, data, part, 8)
    hp = build_plan_hier(indptr, indices, data, part, 2, 8)
    out["rounds_flat"] = fp.n_rounds
    out["rounds_intra"] = hp.n_rounds_intra
    out["rounds_inter"] = hp.n_rounds_inter
    out["halo_slots_intra"] = hp.S_intra
    out["halo_slots_inter"] = hp.S_inter

    sols = {}
    for name, kw in (("dist_halo", dict(mesh=mesh)),
                     ("dist_hier", dict(mesh=mesh_hier, pods=2)),
                     ("dist_hier_bell", dict(mesh=mesh_hier, pods=2)),
                     ("dist_hier+block_jacobi", dict(mesh=mesh_hier,
                                                     pods=2))):
        backend, _, variant = name.partition("+")
        t0 = time.perf_counter()
        op = make_operator(indptr, indices, data, backend,
                           part=part, k=8, **kw)
        plan_build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        x, iters, res = cg_solve_global(op, b, tol=1e-7, max_iters=2000,
                                        precondition=variant or None)
        out[name] = {"iters": iters, "res": res,
                     "plan_build_s": plan_build_s,
                     "wall_us": (time.perf_counter() - t0) * 1e6}
        sols[name] = x
        xb = op.scatter(np.random.default_rng(3).normal(
            size=g.n).astype(np.float32))
        op.matvec(xb).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            y = op.matvec(xb)
        y.block_until_ready()
        out[name]["spmv_us"] = (time.perf_counter() - t0) / 20 * 1e6
    scale = float(np.abs(sols["dist_halo"]).max())
    out["max_rel_vs_halo"] = max(
        float(np.abs(x - sols["dist_halo"]).max()) / scale
        for x in sols.values())
    print(json.dumps(out))
""")


POD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np
    import jax
    from repro.core import (Topology, contiguous_pods, partition_hier,
                            scale_to_load)
    from repro.core.metrics import pod_comm_volumes
    from repro.sparse import make_operator, cg_solve_global
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr
    from repro.launch.mesh import make_test_mesh

    # stripes across the long axis: every stripe boundary (and the
    # contiguous-pod cut) is a full 128-wide grid line — the
    # pod-oblivious worst case the pipeline must beat
    g = grid((16, 128))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    topo = scale_to_load(Topology.homogeneous(8), g.n)
    mesh_hier = make_test_mesh(8, pods=2)            # ("pod", "pu")
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)

    part_s = ((np.arange(g.n) * 8) // g.n).astype(np.int32)
    pod_c = contiguous_pods(8, 2)
    res = partition_hier(g, topo, "geoRef", pods=2)

    out = {}
    for name, part, pods in (("oblivious", part_s, pod_c),
                             ("pod_aware", res.part, res.pod_of)):
        _, inter_v = pod_comm_volumes(g, part, 8, pods)
        t0 = time.perf_counter()
        if name == "pod_aware":      # partitioner output drives the runtime
            op = make_operator(indptr, indices, data, "dist_hier",
                               part=res, mesh=mesh_hier)
        else:
            op = make_operator(indptr, indices, data, "dist_hier",
                               part=part, k=8, mesh=mesh_hier, pods=pods)
        plan_build_s = time.perf_counter() - t0
        plan = op.plan               # the HierPlan the runtime executes
        t0 = time.perf_counter()
        x, iters, resid = cg_solve_global(op, b, tol=1e-7, max_iters=2000)
        wall = (time.perf_counter() - t0) * 1e6
        xb = op.scatter(np.random.default_rng(3).normal(
            size=g.n).astype(np.float32))
        op.matvec(xb).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            y = op.matvec(xb)
        y.block_until_ready()
        out[name] = {
            "inter_comm_volume": int(inter_v.sum()),
            "max_inter_comm_volume": int(inter_v.max()),
            "rounds_inter": plan.n_rounds_inter,
            "rounds_intra": plan.n_rounds_intra,
            "plan_build_s": plan_build_s,
            "iters": iters, "res": resid, "cg_wall_us": wall,
            "spmv_us": (time.perf_counter() - t0) / 20 * 1e6,
        }
        out[name + "_x"] = np.asarray(x).tolist()
    xa = np.array(out.pop("oblivious_x"))
    xb_ = np.array(out.pop("pod_aware_x"))
    out["max_rel_between"] = float(
        np.abs(xa - xb_).max() / np.abs(xa).max())
    print(json.dumps(out))
""")


TREE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np
    import jax
    from repro.core import (Topology, canonical_ancestors, partition_tree,
                            scale_to_load)
    from repro.core.metrics import tree_comm_volumes
    from repro.sparse import make_operator, cg_solve_global
    from repro.sparse.distributed import build_plan, build_plan_tree
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr
    from repro.launch.mesh import make_test_mesh

    # stripes across the long axis on the depth-3 (2, 2, 2) mesh: every
    # stripe boundary costs a full 128-wide grid line, and the flat plan
    # pays every one of its rounds at the slowest-link latency
    g = grid((16, 128))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    topo = scale_to_load(Topology.homogeneous(8, fanouts=(2, 2, 2)), g.n)
    mesh_tree = make_test_mesh(8, fanouts=(2, 2, 2))  # (pod, host, pu)
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)

    part_s = ((np.arange(g.n) * 8) // g.n).astype(np.int32)
    anc_c = canonical_ancestors((2, 2, 2))
    fp = build_plan(indptr, indices, data, part_s, 8)
    res = partition_tree(g, topo, "geoRef")

    out = {"rounds_flat": fp.n_rounds}
    for name, part, tree in (("oblivious", part_s, anc_c),
                             ("tree_aware", res.part, res.anc)):
        vols = tree_comm_volumes(g, part, 8, tree)
        t0 = time.perf_counter()
        if name == "tree_aware":     # partitioner output drives the runtime
            op = make_operator(indptr, indices, data, "dist_hier",
                               part=res, mesh=mesh_tree)
        else:
            op = make_operator(indptr, indices, data, "dist_hier",
                               part=part, k=8, mesh=mesh_tree, tree=tree)
        plan_build_s = time.perf_counter() - t0
        plan = op.plan               # the TreePlan the runtime executes
        t0 = time.perf_counter()
        x, iters, resid = cg_solve_global(op, b, tol=1e-7, max_iters=2000)
        wall = (time.perf_counter() - t0) * 1e6
        xb = op.scatter(np.random.default_rng(3).normal(
            size=g.n).astype(np.float32))
        op.matvec(xb).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            y = op.matvec(xb)
        y.block_until_ready()
        out[name] = {
            "rounds_by_level": list(plan.n_rounds_lvl),
            "volume_by_level": [int(v.sum()) for v in vols],
            "max_volume_by_level": [int(v.max()) for v in vols],
            "plan_build_s": plan_build_s,
            "iters": iters, "res": resid, "cg_wall_us": wall,
            "spmv_us": (time.perf_counter() - t0) / 20 * 1e6,
        }
        out[name + "_x"] = np.asarray(x).tolist()
    xa = np.array(out.pop("oblivious_x"))
    xb_ = np.array(out.pop("tree_aware_x"))
    out["max_rel_between"] = float(
        np.abs(xa - xb_).max() / np.abs(xa).max())
    print(json.dumps(out))
""")


BOTTLENECK_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np
    import jax
    from repro.core import Topology, partition_tree, scale_to_load
    from repro.core.costmodel import cost_model_for
    from repro.sparse import make_operator, cg_solve_global
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr
    from repro.launch.mesh import make_test_mesh

    # stripes grid on the depth-3 (2, 2, 2) mesh under a loose balance
    # cap (eps=0.5): the cut objective is oblivious to per-PU load below
    # the cap, so cut FM parks the biggest block ~17% over the mean —
    # and the padded SPMD runtime makes EVERY device pay that block as B
    # (plus the max per-level receive volume as S_lvl).  The bottleneck
    # objective prices exactly those maxima; on the measured machine
    # (forced host devices: homogeneous cores, every link a memcpy) the
    # honest model is flat lams=(1,1,1) with a compute-dominant c_comp.
    g = grid((16, 256))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    topo = scale_to_load(Topology.homogeneous(8, fanouts=(2, 2, 2)), g.n)
    mesh = make_test_mesh(8, fanouts=(2, 2, 2))
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)

    out = {}
    ops = {}
    for obj, kw in (("cut", {}),
                    ("bottleneck", dict(lams=(1.0, 1.0, 1.0),
                                        c_comp=8.0))):
        t0 = time.perf_counter()
        res = partition_tree(g, topo, "greedyRef", seed=0, objective=obj,
                             eps=0.5, passes=6, **kw)
        t_part = time.perf_counter() - t0
        t0 = time.perf_counter()
        op = make_operator(indptr, indices, data, "dist_hier",
                           part=res, mesh=mesh)
        plan_build_s = time.perf_counter() - t0
        ops[obj] = op
        plan = op.plan
        sizes = np.bincount(res.part, minlength=8)
        cm = cost_model_for("bottleneck", topo=topo,
                            lams=(1.0, 1.0, 1.0), c_comp=8.0)
        out[obj] = {
            "partition_s": t_part,
            "plan_build_s": plan_build_s,
            "B": int(plan.B),
            "S_lvl": [int(s) for s in plan.S_lvl],
            "rounds_by_level": list(plan.n_rounds_lvl),
            "block_sizes": sorted(int(s) for s in sizes),
            "modeled": cm.summary(g, res.part, res.anc),
            "tree_objective": float(cost_model_for("cut").price(
                g, res.part, np.atleast_2d(res.anc))),
        }
        x, iters, _res = cg_solve_global(op, b, tol=1e-7, max_iters=800)
        out[obj]["iters"] = iters
        out[obj + "_x"] = np.asarray(x).tolist()

    # interleaved min-of-5: host-device collectives jitter by ~10%, the
    # structural B/S_lvl/round gap is what the minima expose
    best = {obj: {"spmv_us": float("inf"), "per_iter_us": float("inf")}
            for obj in ops}
    for _trial in range(5):
        for obj, op in ops.items():
            xb = op.scatter(np.random.default_rng(3).normal(
                size=g.n).astype(np.float32))
            op.matvec(xb).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(50):
                y = op.matvec(xb)
            y.block_until_ready()
            spmv = (time.perf_counter() - t0) / 50 * 1e6
            t0 = time.perf_counter()
            x, iters, _res = cg_solve_global(op, b, tol=1e-7,
                                             max_iters=800)
            per = (time.perf_counter() - t0) * 1e6 / max(iters, 1)
            best[obj]["spmv_us"] = min(best[obj]["spmv_us"], spmv)
            best[obj]["per_iter_us"] = min(best[obj]["per_iter_us"], per)
    for obj in ops:
        out[obj].update(best[obj])
    xa = np.array(out.pop("cut_x"))
    xb_ = np.array(out.pop("bottleneck_x"))
    out["max_rel_between"] = float(np.abs(xa - xb_).max()
                                   / np.abs(xa).max())
    print(json.dumps(out))
""")


def _bench_bottleneck(rows: list[str]) -> None:
    """Bottleneck (makespan) vs cut refinement on the padded tree
    runtime (ISSUE 9).

    The headline numbers are structural — B (max padded block, the rows
    every device computes), S_lvl (max per-level receive volume, the
    halo slots every device pads to) and the per-level round split — and
    the measured per-CG-iteration / SpMV minima they drive.  The
    bottleneck objective prices exactly those maxima (max over PUs of
    modeled compute + per-level dedup receive volume), so its
    refinement must bring B and S_lvl below the cut-refined partition
    and the measured per-iteration time down with them."""
    t0 = time.perf_counter()
    out = run_child(["-c", BOTTLENECK_SCRIPT], timeout=1800)
    wall_s = time.perf_counter() - t0
    cut, bn = out["cut"], out["bottleneck"]
    _write_bench_json("bottleneck", {
        "bench": "bottleneck", "wall_s": wall_s,
        "mesh": "grid16x256;k=8;fanouts=(2,2,2);greedyRef;eps=0.5",
        "B": {"cut": cut["B"], "bottleneck": bn["B"]},
        "S_lvl": {"cut": cut["S_lvl"], "bottleneck": bn["S_lvl"]},
        "rounds": {"cut": cut["rounds_by_level"],
                   "bottleneck": bn["rounds_by_level"]},
        "modeled_makespan": {
            "cut": cut["modeled"]["makespan"],
            "bottleneck": bn["modeled"]["makespan"]},
        "tree_objective": {"cut": cut["tree_objective"],
                           "bottleneck": bn["tree_objective"]},
        "per_iter_us": {"cut": cut["per_iter_us"],
                        "bottleneck": bn["per_iter_us"]},
        "spmv_us": {"cut": cut["spmv_us"], "bottleneck": bn["spmv_us"]},
        "iters": {"cut": cut["iters"], "bottleneck": bn["iters"]},
        "win": {
            "per_iter": bool(bn["per_iter_us"] < cut["per_iter_us"]),
            "spmv": bool(bn["spmv_us"] < cut["spmv_us"]),
            "B": bool(bn["B"] < cut["B"]),
            "makespan": bool(bn["modeled"]["makespan"]
                             < cut["modeled"]["makespan"])},
        "agreement": {"max_rel_between": out["max_rel_between"],
                      "pass_1e-5": bool(out["max_rel_between"] < 1e-5)},
        "raw": out,
    })
    for obj in ("cut", "bottleneck"):
        r = out[obj]
        rows.append(row(
            f"cg_bottleneck__{obj}", r["per_iter_us"],
            f"B={r['B']};S0={r['S_lvl'][0]};"
            f"rounds={'/'.join(map(str, r['rounds_by_level']))};"
            f"makespan={r['modeled']['makespan']:.0f};"
            f"spmv_us={r['spmv_us']:.0f};iters={r['iters']}"))
    rows.append(row(
        "cg_bottleneck__per_iter_ratio",
        cut["per_iter_us"] / max(bn["per_iter_us"], 1e-9),
        f"bottleneck_faster="
        f"{int(bn['per_iter_us'] < cut['per_iter_us'])};"
        f"B_ratio={cut['B'] / max(bn['B'], 1):.2f};"
        f"agree_1e-5={int(out['max_rel_between'] < 1e-5)}"))


def _bench_tree(rows: list[str]) -> None:
    """Depth-3 (2, 2, 2) tree schedule: per-level round/volume split,
    tree-aware vs oblivious partition (ISSUE 5).

    The headline numbers are the *per-level* round split (the flat plan
    pays its whole total at the slowest-link latency; the tree plan pays
    only ``rounds_by_level[-1]`` there) and the outermost-level comm
    volume, which the tree-aware pipeline must bring strictly below the
    stripes baseline.  Same forced-host-device caveat as the other
    distributed rows: local memcpy collectives show schedule overhead,
    not the per-level-latency win the splits quantify.
    """
    t0 = time.perf_counter()
    out = run_child(["-c", TREE_SCRIPT], timeout=1200)
    wall_s = time.perf_counter() - t0
    _write_bench_json("tree", {
        "bench": "tree", "wall_s": wall_s,
        "rounds": {name: out[name]["rounds_by_level"]
                   for name in ("oblivious", "tree_aware")},
        "rounds_flat": out["rounds_flat"],
        "comm_volumes": {name: out[name]["volume_by_level"]
                         for name in ("oblivious", "tree_aware")},
        "cg_wall_us": {name: out[name]["cg_wall_us"]
                       for name in ("oblivious", "tree_aware")},
        "iters": {name: out[name]["iters"]
                  for name in ("oblivious", "tree_aware")},
        "agreement": {"max_rel_between": out["max_rel_between"],
                      "pass_1e-5": bool(out["max_rel_between"] < 1e-5)},
        "raw": out,
    })
    for name in ("oblivious", "tree_aware"):
        r = out[name]
        lv = ";".join(f"lv{l}={c}" for l, c in
                      enumerate(r["rounds_by_level"]))
        vv = ";".join(f"lv{l}CV={c}" for l, c in
                      enumerate(r["volume_by_level"]))
        rows.append(row(
            f"cg_tree__{name}", r["cg_wall_us"],
            f"{lv};{vv};flat_total={out['rounds_flat']};"
            f"iters={r['iters']};spmv_us={r['spmv_us']:.0f}"))
    ob, ta = out["oblivious"], out["tree_aware"]
    rows.append(row(
        "cg_tree__outer_volume_ratio",
        ob["volume_by_level"][-1] / max(ta["volume_by_level"][-1], 1),
        f"tree_aware_lower="
        f"{int(ta['volume_by_level'][-1] < ob['volume_by_level'][-1])};"
        f"outer_rounds_lt_flat="
        f"{int(ob['rounds_by_level'][-1] < out['rounds_flat'])};"
        f"agree_1e-5={int(out['max_rel_between'] < 1e-5)}"))


def _bench_pod(rows: list[str]) -> None:
    """Pod-aware vs pod-oblivious partitions of the same mesh (ISSUE 4).

    The headline number is ``inter_comm_volume`` — the words the hier
    schedule moves over the slow inter-pod links.  The pod-aware
    pipeline (pods-first geoRef + pod-level sweep + weighted FM) must
    come in strictly below the stripes-with-contiguous-pods baseline at
    <= inter-pod rounds.  Same forced-host-device caveat as the other
    distributed rows: local memcpy collectives show schedule overhead,
    not the slow-link win the volumes quantify.
    """
    t0 = time.perf_counter()
    out = run_child(["-c", POD_SCRIPT], timeout=1200)
    wall_s = time.perf_counter() - t0
    _write_bench_json("pod", {
        "bench": "pod", "wall_s": wall_s,
        "rounds": {name: {"inter": out[name]["rounds_inter"],
                          "intra": out[name]["rounds_intra"]}
                   for name in ("oblivious", "pod_aware")},
        "comm_volumes": {name: {
            "inter": out[name]["inter_comm_volume"],
            "max_inter": out[name]["max_inter_comm_volume"]}
            for name in ("oblivious", "pod_aware")},
        "cg_wall_us": {name: out[name]["cg_wall_us"]
                       for name in ("oblivious", "pod_aware")},
        "iters": {name: out[name]["iters"]
                  for name in ("oblivious", "pod_aware")},
        "agreement": {"max_rel_between": out["max_rel_between"],
                      "pass_1e-5": bool(out["max_rel_between"] < 1e-5)},
        "raw": out,
    })
    for name in ("oblivious", "pod_aware"):
        r = out[name]
        rows.append(row(
            f"cg_pod__{name}", r["cg_wall_us"],
            f"interCV={r['inter_comm_volume']};"
            f"maxInterCV={r['max_inter_comm_volume']};"
            f"rounds_inter={r['rounds_inter']};"
            f"rounds_intra={r['rounds_intra']};"
            f"iters={r['iters']};spmv_us={r['spmv_us']:.0f}"))
    ob, pa = out["oblivious"], out["pod_aware"]
    rows.append(row(
        "cg_pod__inter_volume_ratio",
        ob["inter_comm_volume"] / max(pa["inter_comm_volume"], 1),
        f"pod_aware_lower={int(pa['inter_comm_volume'] < ob['inter_comm_volume'])};"
        f"rounds_le={int(pa['rounds_inter'] <= ob['rounds_inter'])};"
        f"agree_1e-5={int(out['max_rel_between'] < 1e-5)}"))


def _bench_hier(rows: list[str]) -> None:
    """Multi-pod (pods=2, k=8) schedule vs the flat plan.

    The headline number is the *round split*: the flat plan pays every one
    of its colored rounds at inter-pod latency on a multi-pod machine,
    while the hier plan pays only ``rounds_inter`` there (the intra rounds
    ride the fast links and overlap the inter exchange).  Same
    forced-host-device caveat as the overlap rows: local memcpy collectives
    show the schedule's overhead, not its win.
    """
    t0 = time.perf_counter()
    out = run_child(["-c", HIER_SCRIPT], timeout=1200)
    wall_s = time.perf_counter() - t0
    _write_bench_json("hier", {
        "bench": "hier", "wall_s": wall_s,
        "rounds": {"inter": out["rounds_inter"],
                   "intra": out["rounds_intra"],
                   "flat_total": out["rounds_flat"]},
        "cg_wall_us": {name: out[name]["wall_us"]
                       for name in ("dist_halo", "dist_hier",
                                    "dist_hier_bell",
                                    "dist_hier+block_jacobi")},
        "iters": {name: out[name]["iters"]
                  for name in ("dist_halo", "dist_hier", "dist_hier_bell",
                               "dist_hier+block_jacobi")},
        "agreement": {"max_rel_vs_halo": out["max_rel_vs_halo"],
                      "pass_1e-5": bool(out["max_rel_vs_halo"] < 1e-5)},
        "raw": out,
    })
    rows.append(row(
        "dist_hier_rounds", out["rounds_inter"],
        f"inter={out['rounds_inter']};intra={out['rounds_intra']};"
        f"flat_total={out['rounds_flat']};"
        f"inter_lt_flat={int(out['rounds_inter'] < out['rounds_flat'])}"))
    for name in ("dist_halo", "dist_hier", "dist_hier_bell",
                 "dist_hier+block_jacobi"):
        r = out[name]
        rows.append(row(f"cg_hier__{name.replace('+', '_')}", r["wall_us"],
                        f"iters={r['iters']};spmv_us={r['spmv_us']:.0f}"))
    rows.append(row("cg_hier__max_rel_vs_halo",
                    out["max_rel_vs_halo"] * 1e6,   # in 1e-6 units
                    f"agree_1e-5={int(out['max_rel_vs_halo'] < 1e-5)}"))


def _bench_build_plan(rows: list[str]) -> None:
    g = grid((256, 256))
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    part = np.random.default_rng(0).integers(0, 8, g.n)
    build_plan(indptr, indices, data, part, 8)          # warm
    build_plan_reference(indptr, indices, data, part, 8)
    t_vec = min(_t(build_plan, indptr, indices, data, part) for _ in range(5))
    t_ref = min(_t(build_plan_reference, indptr, indices, data, part)
                for _ in range(3))
    rows.append(row("build_plan_vectorized", t_vec * 1e6,
                    "grid256x256;k=8;random_part"))
    rows.append(row("build_plan_seed_reference", t_ref * 1e6,
                    f"speedup={t_ref / t_vec:.1f}x"))


def _t(fn, *args):
    t0 = time.perf_counter()
    fn(*args, 8)
    return time.perf_counter() - t0


def _bench_operator_backends(rows: list[str]) -> None:
    out = run_child(["-c", DIST_SCRIPT], timeout=1200)
    for name in ("coo", "coo+jacobi", "bell", "dist_halo",
                 "dist_halo+jacobi", "dist_halo_seq", "dist_bell",
                 "dist_allgather"):
        r = out[name]
        rows.append(row(f"cg_operator__{name.replace('+', '_')}",
                        r["wall_us"],
                        f"iters={r['iters']};res={r['res']:.2e}"))
    rows.append(row("cg_operator__max_pairwise_rel",
                    out["max_pairwise_rel"] * 1e6,   # in 1e-6 units
                    f"agree_1e-5={int(out['max_pairwise_rel'] < 1e-5)}"))
    rows.append(row("dist_spmv_halo_overlapped", out["dist_halo_spmv_us"],
                    "grid64x32;k=8;stripes"))
    rows.append(row("dist_spmv_halo_sequential",
                    out["dist_halo_seq_spmv_us"],
                    f"overlap_speedup="
                    f"{out['dist_halo_seq_spmv_us'] / out['dist_halo_spmv_us']:.2f}x"))
    rows.append(row("dist_spmv_allgather", out["dist_allgather_spmv_us"],
                    "grid64x32;k=8;stripes"))


def run() -> list[str]:
    rows = []
    _bench_build_plan(rows)
    _bench_operator_backends(rows)
    _bench_hier(rows)
    _bench_pod(rows)
    _bench_tree(rows)
    g = rdg(30000, seed=4)
    indptr, indices, data = laplacian_csr(g, shift=1e-2)
    rows_a, cols_a, vals_a = (jnp.asarray(a) for a in
                              csr_to_padded_coo(indptr, indices, data))
    b = jnp.asarray(np.random.default_rng(0).normal(size=g.n), jnp.float32)

    # real single-device SpMV + CG cost
    y = spmv_coo(rows_a, cols_a, vals_a, b)
    y.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        y = spmv_coo(rows_a, cols_a, vals_a, b)
    y.block_until_ready()
    spmv_us = (time.perf_counter() - t0) / 20 * 1e6
    res = cg_solve(lambda x: spmv_coo(rows_a, cols_a, vals_a, x), b,
                   tol=1e-6, max_iters=300)
    res.x.block_until_ready()
    t0 = time.perf_counter()
    res = cg_solve(lambda x: spmv_coo(rows_a, cols_a, vals_a, x), b,
                   tol=1e-6, max_iters=300)
    res.x.block_until_ready()
    cg_total = (time.perf_counter() - t0) * 1e6
    iters = max(int(res.iters), 1)
    rows.append(row("cg_real_per_iter", cg_total / iters,
                    f"iters={iters};spmv_us={spmv_us:.0f}"))

    # modeled heterogeneous per-iteration time (paper's TOPO3 simulation)
    c_row = spmv_us / g.n                     # measured per-row cost, us
    alpha = 4 * c_row                         # per-halo-word exchange cost
    topo = scale_to_load(
        Topology.topo3(nodes=4, cores_per_node=6, fast_nodes=1), g.n)
    tw = target_block_sizes(g.n, topo)
    for m in ("sfc", "rcb", "geoKM", "geoRef"):
        part, _ = partition(g, topo, m, tw=tw)
        sizes = block_sizes_of(part, topo.k)
        t_comp = np.max(sizes / topo.speeds) * c_row
        t_comm = alpha * max_comm_volume(g, part, topo.k)
        rows.append(row(f"cg_model_topo3__{m}", t_comp + t_comm,
                        f"comp={t_comp:.0f};comm={t_comm:.0f}"))
    # uniform blocks (heterogeneity-oblivious) baseline: same model
    uni = np.round(np.full(topo.k, g.n / topo.k)).astype(int)
    part_u, _ = partition(g, topo, "geoKM",
                          tw=np.full(topo.k, g.n / topo.k))
    sizes = block_sizes_of(part_u, topo.k)
    t_comp = np.max(sizes / topo.speeds) * c_row
    t_comm = alpha * max_comm_volume(g, part_u, topo.k)
    rows.append(row("cg_model_topo3__uniform_oblivious", t_comp + t_comm,
                    f"comp={t_comp:.0f};comm={t_comm:.0f}"))
    return rows


def main() -> None:
    """``python -m benchmarks.bench_cg --hier`` (``make bench-hier``):
    only the multi-pod schedule section; ``--pod-aware``
    (``make bench-pod``): only the pod-aware vs pod-oblivious partition
    comparison; ``--tree`` (``make bench-tree``): the depth-3 (2, 2, 2)
    per-level round/volume split.  All on forced host devices: a CPU
    rehearsal (``common.cpu_rehearsal``), never a chip measurement."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--hier", action="store_true",
                    help="run only the multi-pod (dist_hier) benchmark")
    ap.add_argument("--pod-aware", action="store_true",
                    help="run only the pod-aware vs pod-oblivious "
                         "partition comparison")
    ap.add_argument("--tree", action="store_true",
                    help="run only the depth-3 tree schedule benchmark "
                         "(per-level round split on the (2,2,2) mesh)")
    ap.add_argument("--objective", choices=("cut", "bottleneck"),
                    default=None,
                    help="run only the refinement-objective comparison "
                         "(cut vs bottleneck partitions of the padded "
                         "tree runtime); the value picks the headline "
                         "row, both objectives always run")
    args = ap.parse_args()
    cpu_rehearsal()
    print("name,us_per_call,derived")
    rows: list[str] = []
    if args.hier:
        _bench_hier(rows)
    elif args.pod_aware:
        _bench_pod(rows)
    elif args.tree:
        _bench_tree(rows)
    elif args.objective is not None:
        _bench_bottleneck(rows)
    else:
        rows = run()
    for r in rows:
        print(r, flush=True)


if __name__ == "__main__":
    main()
