#!/usr/bin/env python3
"""Rehearse a cell's control flow at a tiny size on the CPU. Never prints a result
line: a number from a CPU run is not a measurement.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py --workload mistral7b_steady [--seconds 3] [--trace 1]

The cell, its traffic file, its job kind and its readers are the real ones; only the
configuration's widths and batch are replaced by its family's ``TINY`` preset
(``families/<family>.py``). A four-chip cell runs on four virtual CPU devices.
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


def rehearse(workload: str, seed: int, seconds: float, trace: bool, manifest=None):
    """Returns (run, metrics) of a tiny run of the cell on whatever JAX finds."""
    from benchmark import harness
    from benchmark.run import measure

    cell = harness.load_cell(workload, manifest)
    cell.config = {**cell.config, **harness.load_family(cell.config).TINY}
    run = harness.Run(cell, seed, seconds, trace, T_PROCESS, rehearsal=True)
    try:
        run.take_devices()
        metrics = measure(run)
    finally:
        run.cleanup()
    return run, metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", help="another BENCHMARK.json (a cell not yet admitted)")
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    manifest = json.load(open(args.manifest)) if args.manifest else None
    chips = {w["name"]: w["chips"] for w in (manifest or json.load(
        open(os.path.join(ROOT, "BENCHMARK.json"))))["workloads"]}.get(args.workload, 1)
    if chips > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={chips}")
    run, metrics = rehearse(args.workload, args.seed, args.seconds, bool(args.trace), manifest)
    print(f"rehearsal of {args.workload} on {run.device}: metrics {metrics}; "
          f"problems {run.problems}. Not a measurement: no result line.", file=sys.stderr)
    return 1 if run.problems else 0


if __name__ == "__main__":
    sys.exit(main())
