"""Least bytes of the window's MG-PCG loop trips (``work_mg.trip_bytes``
at each request's width, values of the configuration's dtype) over what
the chips' HBM moves at peak in the busy time: the multigrid-
preconditioned CG trip's share of its memory roofline, in percent.

The level sizes are those the program reports of the hierarchy it built
(the kind's ``sizes``: ``levels``), and are read only where they agree
with the configuration's grid: each level of an ``nx x ny x nz`` grid
halved ``l`` times holds ``prod(d >> l)`` rows and ``prod(3 (d >> l) -
2)`` stored entries.  None where either is missing or they disagree."""
import numpy as np

from bench import work_mg
from bench.peaks import peaks


def expected_sizes(params: dict) -> list[list[int]]:
    dims = [params["nx"], params["ny"], params["nz"]]
    return [[int(np.prod([d >> lvl for d in dims])),
             int(np.prod([3 * (d >> lvl) - 2 for d in dims]))]
            for lvl in range(params["levels"])]


def read(run):
    if not run.trace or run.trace.busy_s <= 0:
        return None
    sizes = run.sizes.get("levels")
    params = run.cell.config.get("generator_params", {})
    if not sizes or not {"nx", "ny", "nz", "levels"} <= set(params):
        return None
    if [list(s) for s in sizes] != expected_sizes(params):
        return None
    value_bytes = np.dtype(run.cell.config["dtype"]).itemsize
    least = sum((r["trips"] or 0)
                * work_mg.trip_bytes(sizes, r["units"], value_bytes)
                for r in run.requests)
    if not least:
        return None
    bw = peaks(run.device_kind)["hbm_bw"] * run.chips
    return 100.0 * least / (bw * run.trace.busy_s)
