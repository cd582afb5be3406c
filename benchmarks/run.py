"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  A CPU rehearsal: every bench
runs on JAX's CPU backend (``common.cpu_rehearsal``), so no number here
is a chip measurement.

  python -m benchmarks.run [--only block_sizes,partitioners,...]
"""
from __future__ import annotations

import argparse
import sys
import time

from .common import cpu_rehearsal

BENCHES = ("block_sizes", "hierarchical", "partitioners", "scaling",
           "cg", "kernels")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help=f"comma list from {BENCHES}")
    args = ap.parse_args()
    want = args.only.split(",") if args.only else BENCHES
    cpu_rehearsal()

    print("name,us_per_call,derived")
    failures = 0
    for name in want:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            for r in mod.run():
                print(r, flush=True)
        except Exception as e:     # keep the harness going
            failures += 1
            print(f"bench_{name}__ERROR,0,{type(e).__name__}:{e}",
                  flush=True)
        print(f"# bench_{name} done in {time.time() - t0:.1f}s",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
