"""Device busy time of the traced window (mean over the chips) over the
CG loop trips the window ran; a request's trips are the largest
iteration count among its columns."""


def read(run):
    trips = sum(r["trips"] or 0 for r in run.requests)
    if not run.trace or not trips:
        return None
    return run.trace.busy_s / trips * 1e3
