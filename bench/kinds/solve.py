"""Solve traffic: right-hand sides against one resident matrix.

The system under test is ``repro.launch.serve.SolverService`` on the
configuration's backend.  Set-up admits the matrix once (for a
distributed backend after Algorithm 1 and the configuration's
partitioner split it over the chips) and every request passes the
fingerprint that admission returned.  Warm-up solves a zero right-hand
side of each admission bucket the mix reaches: CG runs 0 iterations, so
it costs the compiles and nothing else.

Traffic parameters: ``widths`` (width -> probability), ``buckets`` (the
service's admission classes), ``pool`` (requests made in set-up and
cycled), ``check_sample``.  Every seed sends the same sequence of widths
(``sequence.balanced``) against the configuration's one matrix; the seed
draws the N(0, 1) right-hand sides.  The matrix's values and the
right-hand sides are of the configuration's ``dtype``.

Checked: the float64 residual ``||b - A x|| / ||b||`` of every column of a
sample of the answers, against the configuration's ``residual_max``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference, sequence


@dataclasses.dataclass
class Request:
    b: np.ndarray                 # (n, width), the configuration's dtype


@dataclasses.dataclass
class Answer:
    x: np.ndarray                 # (n, width), on the host
    iters: np.ndarray             # (width,) CG iterations per column


def laplacian(inputs: dict, dtype=None) -> tuple:
    data = inputs["l_data"]
    return (inputs["l_indptr"], inputs["l_indices"],
            data if dtype is None else data.astype(dtype))


def make_requests(traffic: dict, n: int, seed: int,
                  dtype) -> list[Request]:
    widths = sequence.balanced(
        {int(w): p for w, p in traffic["widths"].items()},
        int(traffic["pool"]))
    rng = np.random.default_rng([seed, 2])
    return [Request(rng.standard_normal((n, w), dtype=dtype))
            for w in widths]


def equal_partition(config: dict, inputs: dict, k: int, seed: int):
    """The matrix split over ``k`` equal PUs by the program: Algorithm 1,
    then the configuration's partitioner."""
    from repro.core import PU, Topology, partition, target_block_sizes
    from repro.sparse.graph import Graph

    n = len(inputs["indptr"]) - 1
    g = Graph(indptr=inputs["indptr"], indices=inputs["indices"],
              weights=np.ones(len(inputs["indices"]), np.float32),
              coords=inputs["coords"])
    topo = Topology(tuple(PU(1.0, float(n), f"chip{i}") for i in range(k)))
    p = config["partition"]
    part, _ = partition(g, topo, method=p["method"],
                        tw=target_block_sizes(n, topo),
                        seed=int(np.random.default_rng([seed, 3])
                                 .integers(2 ** 31)), eps=p["eps"])
    return part


class Service:
    """``SolverService`` with the matrix admitted."""

    def __init__(self, config, traffic, inputs, devices, seed):
        from repro.launch.serve import SolverService

        s = config["service"]
        kw = {}
        if s["backend"].startswith("dist_"):
            from repro.compat import make_mesh

            kw = dict(part=equal_partition(config, inputs, len(devices),
                                           seed),
                      k=len(devices),
                      mesh=make_mesh((len(devices),), ("pu",), devices))
        self.dtype = np.dtype(config["dtype"])
        self.csr = laplacian(inputs, self.dtype)
        self.svc = SolverService(backend=s["backend"],
                                 buckets=tuple(traffic["buckets"]),
                                 tol=s["tol"], max_iters=s["max_iters"],
                                 **kw)
        self.fp, _, _ = self.svc.operator_for(*self.csr)
        self.pool = make_requests(traffic, len(self.csr[0]) - 1, seed,
                                  self.dtype)

    def requests(self):
        while True:
            yield from self.pool

    def warm(self):
        n = len(self.csr[0]) - 1
        for b in sorted({self.svc.bucket_for(r.b.shape[1])
                         for r in self.pool}):
            self.svc.solve(*self.csr, np.zeros((n, b), self.dtype),
                           fingerprint=self.fp)

    def serve(self, req: Request) -> Answer:
        resp = self.svc.solve(*self.csr, req.b, fingerprint=self.fp)
        return Answer(x=np.asarray(resp.x), iters=np.asarray(resp.iters))

    @staticmethod
    def units(req: Request) -> int:
        return req.b.shape[1]

    @staticmethod
    def trips(answer: Answer) -> int:
        return int(answer.iters.max())

    def counters(self) -> dict:
        st = self.svc.stats
        return {"real_cols": st.real_cols, "padded_cols": st.padded_cols,
                "solves": st.solves}

    def sizes(self) -> dict:
        return {"n": len(self.csr[0]) - 1, "nnz": len(self.csr[1])}

    def release(self):
        self.svc = None


class Control(Service):
    """The reference's CG in bfloat16 in the service's place, on the
    first chip, against the same requests."""

    def __init__(self, config, traffic, inputs, devices, seed):
        import jax.numpy as jnp

        s = config["service"]
        self.dtype = np.dtype(config["dtype"])
        self.csr = laplacian(inputs, self.dtype)
        self.cg = reference.EllCG(*self.csr, dtype=jnp.bfloat16,
                                  device=devices[0], tol=s["tol"],
                                  max_iters=s["max_iters"])
        self.pool = make_requests(traffic, len(self.csr[0]) - 1, seed,
                                  self.dtype)

    def warm(self):
        for w in sorted({r.b.shape[1] for r in self.pool}):
            self.cg.solve(np.zeros((self.cg.n, w), self.dtype))

    def serve(self, req: Request) -> Answer:
        x, iters = self.cg.solve(req.b)
        return Answer(x=x, iters=iters)

    def counters(self) -> dict:
        return {}

    def release(self):
        self.cg = None


def setup(config, traffic, inputs, devices, seed, control=False):
    return (Control if control else Service)(config, traffic, inputs,
                                             devices, seed)


def check(config, traffic, inputs, kept) -> dict:
    worst = 0.0
    for req, ans in kept:
        if ans.x.shape != req.b.shape:
            worst = np.inf
            continue
        rel = reference.residuals(*laplacian(inputs), req.b, ans.x)
        worst = max(worst, float(np.max(np.nan_to_num(rel, nan=np.inf))))
    return {"residual_max": {"value": worst,
                             "limit": config["limits"]["residual_max"]}}
