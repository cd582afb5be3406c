"""Readings of the numbers that decide ``correct``, from which their limits
are set: the program's on many seeds, or the control's (``--control 1``),
in one process so that compiles are paid once.

    python bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 10

Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.run import finite

    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             control=bool(args.control))
        print(json.dumps(finite({
            "workload": args.workload, "seed": seed,
            "control": bool(args.control), "correct": r["correct"],
            "attempted": r["attempted"], "metrics": r["metrics"],
            "checks": r["checks"]})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
