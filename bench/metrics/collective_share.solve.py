"""Share of device busy time spent in collective operations (halo
permutes, reductions, gathers; by opcode, each async pair's -start and
-done counted by its own device time), mean over the chips."""
from bench.trace import is_collective


def read(run):
    t = run.trace
    if not t or t.busy_s <= 0:
        return None
    coll = sum(s for op, s in t.op_seconds.items() if is_collective(op))
    return coll / t.busy_s
