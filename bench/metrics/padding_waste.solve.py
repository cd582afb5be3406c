"""Share of the columns the service solved in the window that were
admission padding (``ServeStats`` padded over real plus padded)."""


def read(run):
    c = run.counters
    total = c.get("real_cols", 0) + c.get("padded_cols", 0)
    return c["padded_cols"] / total if total else None
