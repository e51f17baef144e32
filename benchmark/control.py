#!/usr/bin/env python3
"""The readings a limit is set from; not part of a benchmark run.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 8

For each seed, in one process, the cell's runner gives the program's numbers
(the lower reading is their largest over a dozen seeds), the control's (the
reference in the precision below the configuration's, in the program's
place; the upper reading is its smallest), and, where the cell can have
them, each planted fault's.  One JSON line per seed on standard output, then
a summary.  Needs the chip like a run does.  ``PERF.md`` records what was
read and the limits set from it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", type=int, default=3,
                    help="on how many of the seeds, the first ones, the "
                         "control and the faults are read as well")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_benchmark(harness.ROOT), args.workload,
                        harness.ROOT)
    devs, compiles = harness.start(cell)
    lower, upper = {}, {}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        r = cell.runner().control(cell, seed, args.seconds, devs, compiles,
                                  with_control=n < args.controls)
        print(json.dumps({"seed": seed, **r}), flush=True)
        for k, v in r["program"].items():
            lower[k] = max(lower.get(k, float("-inf")), v)
        for name, nums in {**r.get("control", {}), **r.get("faults", {})}.items():
            for k, v in nums.items():
                if isinstance(v, float):
                    key = f"{name}:{k}"
                    upper[key] = min(upper.get(key, float("inf")), v)
    print(json.dumps({"largest_of_program": lower,
                      "smallest_of_control_and_faults": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
