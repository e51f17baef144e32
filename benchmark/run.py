#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It refuses to run (exit 2, no result) without a TPU or with
fewer chips than the cell asks for.  Set-up (weights or data from the seed,
warm-up of the cell's own shapes, compilation) ends where the measured window
starts; the window is measured by the host's clock from the client's side;
then the program's state is freed and the cell's plain reference decides
``correct``.  The last line of standard output is the result.  With
``--trace 1`` a part of the window runs under the profiler and the metrics are
the cell's per-layer metrics, each read by ``benchmark/metrics/<metric>.py``.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)          # the program, and ``benchmark`` as a package

from benchmark import harness  # noqa: E402


def build_result(cell: harness.Cell, out: dict, trace: bool, devs) -> dict:
    from benchmark import peaks, work

    device = harness.device_record(devs)
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"],
                                      int(out.get("memory_peak_bytes", 0)))
    values = dict(out["end_to_end"])
    values["setup_s"] = out["window_start"] - PROCESS_START
    metrics = {}
    result = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if not trace:
        for m in cell.end_to_end():
            if m["name"] not in values:
                raise SystemExit(f"the runner reported no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        reduced = out["trace"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        given = dict(trace=reduced, facts=out["facts"], cell=cell,
                     values=values, peak=peaks.peaks(device["kind"]),
                     work=work, chips=cell.chips)
        for m in cell.per_layer():
            v = cell.reader(m["name"]).read(**given)
            if v is not None:        # a reader that finds nothing says nothing
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        from benchmark import trace_reduce as tr
        result["breakdown"] = {"device_ops": tr.device_ops_top(reduced),
                               "idle_gaps": tr.idle_gaps_by_host(reduced)}
    result["metrics"] = metrics
    result["device"] = device
    result["info"] = out.get("info", {})
    result["compared"] = out["compared"]      # last: each number and its limit
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.Cell(harness.load_benchmark(ROOT), args.workload, ROOT)
    devs, compiles = harness.start(cell)
    harness.say(f"{cell.name}: seed {args.seed}, {args.seconds:g} s, trace "
                f"{args.trace}, {len(devs)} x {devs[0].device_kind}, compile "
                f"cache {os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    out = cell.runner().run(cell=cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), devs=devs[:cell.chips],
                            compiles=compiles)
    out["correct"] = harness.decide(out)
    result = build_result(cell, out, bool(args.trace), devs)
    harness.say(f"correct={result['correct']} attempted={result['attempted']} "
                f"failed={result['failed']}; each number compared, its limit:")
    for name, c in out["compared"].items():
        harness.say(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
