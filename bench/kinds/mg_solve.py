"""Multigrid-preconditioned solve traffic: right-hand sides against one
resident matrix and the coarser levels of its multigrid.

The solve kind (``kinds/solve.py``), with the configuration's
``precondition`` given to ``SolverService`` and the input's coarser
levels (``c<c>_*`` of ``inputs/hpcg.py``) given to its admission, which
builds the hierarchy with the matrix.  Requests, warm-up, counters and
the control are the solve kind's.  ``sizes`` adds what the program
reports of its hierarchy: ``levels`` ((n, nnz) a level, fine first) and
``colors`` (a level).

Checked, beside the solve kind's ``residual_max``: ``trips_max``, the
largest CG trip count among the sampled answers' columns, against the
largest the float64 reference MG-PCG (``reference_mg.py``) takes on the
same columns plus the configuration's ``trips_slack``.
"""
from __future__ import annotations

import numpy as np

from bench import reference_mg
from bench.kinds import solve

LEVEL_KEYS = ("indptr", "indices", "data", "f2c")


def levels(inputs: dict, dtype=None) -> list[tuple]:
    """The coarser levels, finest first, as ``(indptr, indices, data,
    f2c)`` with ``data`` in ``dtype``."""
    out = []
    while f"c{len(out) + 1}_f2c" in inputs:
        ip, ix, data, f2c = (inputs[f"c{len(out) + 1}_{k}"]
                             for k in LEVEL_KEYS)
        out.append((ip, ix, data if dtype is None else data.astype(dtype),
                    f2c))
    return out


class Service(solve.Service):
    """``SolverService`` with the matrix and its levels admitted."""

    def __init__(self, config, traffic, inputs, devices, seed):
        from repro.launch.serve import SolverService

        s = config["service"]
        self.dtype = np.dtype(config["dtype"])
        self.csr = solve.laplacian(inputs, self.dtype)
        self.svc = SolverService(backend=s["backend"],
                                 buckets=tuple(traffic["buckets"]),
                                 tol=s["tol"], max_iters=s["max_iters"],
                                 precondition=s["precondition"])
        self.fp, self.op, _ = self.svc.operator_for(
            *self.csr, levels=levels(inputs, self.dtype))
        self.pool = solve.make_requests(traffic, len(self.csr[0]) - 1, seed,
                                        self.dtype)

    def sizes(self) -> dict:
        mg = self.op.mg
        return {**super().sizes(), "levels": [list(s) for s in mg.sizes],
                "colors": list(mg.colors)}

    def release(self):
        self.svc = self.op = None


def setup(config, traffic, inputs, devices, seed, control=False):
    return (solve.Control if control else Service)(config, traffic, inputs,
                                                   devices, seed)


def check(config, traffic, inputs, kept) -> dict:
    out = solve.check(config, traffic, inputs, kept)
    seen, cols, program = set(), [], 0
    for req, ans in kept:
        program = max(program, int(np.max(ans.iters)))
        if id(req) not in seen:
            seen.add(id(req))
            cols.append(req.b)
    p, s = config["generator_params"], config["service"]
    ref = reference_mg.trips(inputs, (p["nx"], p["ny"], p["nz"]),
                             np.concatenate(cols, axis=1), s["tol"],
                             s["max_iters"]) if cols else [0]
    out["trips_max"] = {"value": program,
                        "limit": max(ref) + config["limits"]["trips_slack"]}
    return out
