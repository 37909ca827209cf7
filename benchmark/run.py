#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: takes the chip(s) (fails off a TPU, prints no result), builds the cell's
state on the device from the seed, warms the cell's own programs, measures for
``--seconds``, checks its outputs outside the window, and prints as the last line of
stdout the one JSON object the contract fixes. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a traced window.
Everything that belongs to one cell is data or a file of its own, found by the names
in BENCHMARK.json: see benchmark/README.md.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import end_to_end, harness  # noqa: E402


def measure(run: harness.Run) -> dict:
    """Drive the cell's job kind and reduce the run to its metrics."""
    job = harness.load_by_path("jobs", run.cell.traffic["job"])
    job.run(run)
    if run.t_close is None:
        run.problem("the window never closed")
    misses = (run.compiles_in_window or {}).get("misses", 0)
    if misses:
        run.problem(f"{misses} compilation(s) missed the cache inside the window")
    metrics = {}
    if run.trace and run.trace_result is None and not run.rehearsal:
        run.problem("the traced run read no trace")
    if run.trace:
        for metric in run.cell.per_layer:
            value = harness.load_by_path("layer_metrics", metric["name"]).read(run)
            if value is not None:
                metrics[metric["name"]] = float(value)
    else:
        for metric in run.cell.end_to_end:
            value = getattr(end_to_end, metric["name"])(run)
            if value is None:
                run.problem(f"end-to-end metric {metric['name']} has nothing to report")
            else:
                metrics[metric["name"]] = float(value)
    return metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    try:
        run.take_devices()
        metrics = measure(run)
        result = run.result(metrics)
    finally:
        run.cleanup()
    print(json.dumps(result), flush=True)
    for number, row in run.compared.items():  # the last lines of standard error
        print(f"compared {number}: gap {row['gap']} limit {row['limit']}", file=sys.stderr)
    for problem in run.problems:
        print(f"problem: {problem}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
