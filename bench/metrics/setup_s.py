"""Set-up: process start to the first request of the window (inputs,
admission, warm-up and, on a cold cache, compiles)."""


def read(run):
    return run.setup_s
