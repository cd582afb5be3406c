"""Share of the traced window in which the device ran no operation
(mean over the chips), under solve traffic."""


def read(run):
    t = run.trace
    return 1.0 - t.busy_s / t.window_s if t and t.window_s > 0 else None
