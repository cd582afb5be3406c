"""Least bytes of the window's CG loop trips (``work.trip_bytes`` at each
request's width, values of the configuration's dtype) over what the
chips' HBM moves at peak in the busy time: the CG iteration's share of
its memory roofline, in percent."""
import numpy as np

from bench import work
from bench.peaks import peaks


def read(run):
    if not run.trace or run.trace.busy_s <= 0:
        return None
    n, nnz = run.sizes["n"], run.sizes["nnz"]
    value_bytes = np.dtype(run.cell.config["dtype"]).itemsize
    least = sum((r["trips"] or 0)
                * work.trip_bytes(n, nnz, r["units"], value_bytes)
                for r in run.requests)
    if not least:
        return None
    bw = peaks(run.device_kind)["hbm_bw"] * run.chips
    return 100.0 * least / (bw * run.trace.busy_s)
