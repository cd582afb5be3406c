"""Right-hand-side columns answered over the whole window, first request
sent to last answer on the host."""


def read(run):
    cols = sum(r["units"] for r in run.requests if not r["failed"])
    return cols / run.window_s if run.window_s > 0 else None
