"""95th percentile of request latency over every request of the window,
from the call to the service until ``x`` is on the host."""
import numpy as np


def read(run):
    lat = [r["end"] - r["start"] for r in run.requests]
    return float(np.percentile(lat, 95)) if lat else None
