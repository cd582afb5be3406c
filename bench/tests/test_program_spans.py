"""The program's host spans, recorded on the CPU under the profiler and
read back as ``bench/spans.py`` reads them: one ``SolverService`` solve,
and one partition request of the partition cell's path (Algorithm 1,
then geoKM; geoRef adds the refinement)."""
import jax
import numpy as np

from bench import spans as sp
from bench import trace as tr


def record(log_dir, fn):
    """Run ``fn`` under the profiler inside a ``bench.window`` span; the
    program's spans in start order, as ``(name, start, end, stats)``."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            fn()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(log_dir)
    lines = sp.read_spans(path)
    assert len(lines) == 1                 # one host thread made them all
    events = sorted(((e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                     for p in ProfileData.from_file(str(path)).planes
                     if p.name == tr.HOST_PLANE for line in p.lines
                     for e in line.events if e.name.startswith("repro.")),
                    key=lambda e: (e[1], -e[2]))
    assert [e[:3] for e in events] == [
        s for s in lines[0] if s[0].startswith("repro.")]
    return events


def children(events, parent):
    """Names of the spans nested directly inside ``parent``, in order."""
    spans = [e[:3] for e in events]
    up = sp._parents(spans)
    i = spans.index(parent[:3])
    return [spans[j][0] for j in range(len(spans)) if up[j] == i]


def test_a_solve_request_records_its_phases_in_order(tmp_path):
    from repro.launch.serve import SolverService
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr

    csr = laplacian_csr(grid((8, 8)), shift=0.1)
    b = np.random.default_rng(0).standard_normal((64, 3)).astype(np.float32)
    svc = SolverService(backend="coo", buckets=(1, 4), tol=1e-6,
                        max_iters=200)
    resp = None

    def solve():
        nonlocal resp
        resp = svc.solve(*csr, b)

    events = record(tmp_path, solve)
    assert resp.x.shape == (64, 3) and resp.bucket == 4
    (root,) = [e for e in events if e[0] == "repro.serve.solve"]
    assert root[3] == {"request": 1, "width": 3, "bucket": 4}
    assert children(events, root) == [
        "repro.serve.admit", "repro.serve.pad", "repro.serve.scatter",
        "repro.serve.dispatch", "repro.serve.wait", "repro.serve.gather"]
    # the first request misses the operator cache and builds the plan
    (admit,) = [e for e in events if e[0] == "repro.serve.admit"]
    assert children(events, admit) == ["repro.plan.build"]
    assert len(events) <= 8


def test_a_warm_solve_request_builds_no_plan(tmp_path):
    from repro.launch.serve import SolverService
    from repro.sparse.generators import grid
    from repro.sparse.graph import laplacian_csr

    csr = laplacian_csr(grid((8, 8)), shift=0.1)
    b = np.ones((64, 1), np.float32)
    svc = SolverService(backend="coo", buckets=(1, 4), tol=1e-6,
                        max_iters=200)
    fp, _, _ = svc.operator_for(*csr)
    svc.solve(*csr, b, fingerprint=fp)
    events = record(tmp_path, lambda: svc.solve(*csr, b, fingerprint=fp))
    assert [e[0] for e in events] == [
        "repro.serve.solve", "repro.serve.admit", "repro.serve.pad",
        "repro.serve.scatter", "repro.serve.dispatch", "repro.serve.wait",
        "repro.serve.gather"]
    assert events[0][3] == {"request": 2, "width": 1, "bucket": 1}


def _request(method):
    """One partition request as the partition cell makes it: Algorithm-1
    targets for a heterogeneous PU set, then ``partition``."""
    from repro.core import PU, Topology, partition, target_block_sizes
    from repro.sparse.generators import rdg

    g = rdg(600, seed=3)
    topo = Topology(tuple(PU(s, m, f"pu{i}") for i, (s, m) in enumerate(
        [(4.0, 400.0), (1.0, 200.0), (1.0, 200.0), (2.0, 300.0)])))

    def run():
        part, _ = partition(g, topo, method=method,
                            tw=target_block_sizes(g.n, topo), seed=1)
        assert np.bincount(part, minlength=4).sum() == g.n

    return run


def test_a_geokm_partition_request_records_its_phases(tmp_path):
    events = record(tmp_path, _request("geoKM"))
    assert [e[0] for e in events] == [
        "repro.block_sizes", "repro.partition", "repro.kmeans.seed",
        "repro.kmeans.loop", "repro.kmeans.rebalance"]
    (root,) = [e for e in events if e[0] == "repro.partition"]
    assert root[3] == {"method": "geoKM", "k": 4}
    assert children(events, root) == [
        "repro.kmeans.seed", "repro.kmeans.loop", "repro.kmeans.rebalance"]


def test_a_georef_partition_request_adds_the_refinement(tmp_path):
    events = record(tmp_path, _request("geoRef"))
    (root,) = [e for e in events if e[0] == "repro.partition"]
    assert children(events, root) == [
        "repro.kmeans.seed", "repro.kmeans.loop", "repro.kmeans.rebalance",
        "repro.refine"]
    assert len(events) <= 6
