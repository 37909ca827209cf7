#!/usr/bin/env python3
"""The control of ``correct`` at a configuration's own size, on the chip.

    python benchmark/control.py --config mistral-7b-l2 --seeds 101,102,103

For each seed: the float32 reference's first steps, then the same steps with the
reference computed in a lower precision and put in the program's place (``fp8``, the
precision below the configurations' bfloat16, must fail at least one number; ``bf16``,
the stated precision, shows what a sound program's rounding alone gives). Prints every
number beside the configuration's limit. A benchmark run never runs this; the limits in
the configuration files were set from its readings and from sound runs of the program
(PERF.md section 2). Needs one TPU chip: off one it prints nothing.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--precisions", default="fp8,bf16")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    import jax
    import numpy as np

    from benchmark.reference import train

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise harness.NoResult(f"the control is read on a TPU; JAX found {dev.platform!r}")
    cfg = harness.read_json(harness.HERE, "configs", f"{args.config}.json")
    cell = harness.Cell("control", 1, args.config, cfg, "", {}, [], [])
    for seed in (int(s) for s in args.seeds.split(",")):
        batches = [np.random.default_rng([seed, i]).integers(
            0, cfg["vocab_size"], cfg["batch"]).astype(np.int32)
            for i in range(harness.COMPARED_STEPS)]
        t0 = time.time()
        reference = train.follow(seed % (1 << 32), cfg, batches, "f32")
        print(json.dumps({"seed": seed, "reference_s": time.time() - t0,
                          "losses": reference["losses"]}), flush=True)
        for precision in args.precisions.split(","):
            run = harness.Run(cell, seed, 0.0, False, 0.0)
            lower = train.follow(seed % (1 << 32), cfg, batches, precision)
            rows = harness.compare_with_reference(run, lower, reference, cfg["limits"])
            print(json.dumps({"seed": seed, "precision": precision,
                              "fails": [r["number"] for r in rows if not r["ok"]],
                              "gaps": {r["number"]: r["gap"] for r in rows}}), flush=True)
            run.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
