"""The window over the partitions completed in it (Algorithm-1 targets
and the partition, each)."""


def read(run):
    done = sum(1 for r in run.requests if not r["failed"])
    return run.window_s / done if done else None
