"""Sweep the Pallas fused-median kernels vs XLA's sort lowering over (W, R) —
the measurement ``scoring_pallas`` auto-selection rests on.

Three kernel formulations are measured: ``loop`` (rank-counting, O(W²)),
``pairwise`` (all-pairs block, O(W²) VMEM-heavy; the product gate caps it at
the measured ``PAIRWISE_MAX_WINDOW`` = 32, but the sweep deliberately probes
up to W=64 so a different device generation that can compile it gets
measured rather than assumed — W>64 is skipped outright for its quadratic
VMEM temporaries), and ``radix`` (bit-select, O(32·W) — the scaling-safe
mode). The JSON tail derives the
auto-select boundary from the measurements:

- ``loop_max_window``: largest W where the loop kernel is the best variant at
  every tested R → export as ``$TPU_RESILIENCY_PALLAS_MAX_WINDOW`` (beyond it
  auto-select runs radix).
- ``pallas_beats_xla_at``: per-W verdict of best-Pallas vs XLA under the
  same noise tolerance as the cap (``TOL``), so the two exports cannot
  contradict each other on a sub-noise tie (the use_pallas gate
  justification).

Runs on a TPU or not at all (per-program device-plane times via the framework's
own DeviceTimeProfiler): off one it exits non-zero with no result line.

    python scripts/bench_pallas_sweep.py [--ws 32,64,128,256] [--rs 256,1024,4096]
"""

import argparse
import json
import sys

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

S = 64
ITERS = 20

#: Measurement-noise tolerance for BOTH exported decisions: a variant keeps
#: its "win" on a cell unless it is more than 2% slower than the alternative
#: (ties and sub-2% deficits count as wins — deliberately asymmetric toward
#: the Pallas path). On v5e, W=64 reads as an XLA "win" by 0.3-0.8% at small
#: R while loop wins 25% at R=4096 — a sub-noise tie must not flip either
#: export.
TOL = 1.02


def measure(r, w, variant):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_resiliency.telemetry import scoring
    from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler

    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.uniform(0.8, 1.2, (r, S, w)).astype(np.float32))
    counts = jnp.full((r, S), w, jnp.int32)
    ewma = jnp.ones((r,))
    hist = jnp.full((r, S), jnp.inf)

    if variant == "xla":
        def program(d, c, e, h):
            return scoring.score_round(d, c, e, h)
    else:
        from tpu_resiliency.ops.scoring_pallas import fused_median_weights

        mode = variant.removeprefix("pallas-")

        def program(d, c, e, h):
            mw = fused_median_weights(d, c, mode=mode)
            return scoring.score_round(d, c, e, h, medians_and_weights=mw)

    fn = jax.jit(program)
    out = fn(data, counts, ewma, hist)
    jax.block_until_ready(out)
    prof = DeviceTimeProfiler()  # a TPU trace without a device plane raises
    with prof:
        for _ in range(ITERS):
            out = fn(data, counts, out.ewma, hist)
        jax.block_until_ready(out)
    for name, st in prof.get_stats().items():
        if "program" in name:
            return st["med"] * 1e3
    raise RuntimeError(f"profiler missed program: {sorted(prof.get_stats())}")


VARIANTS = ("pallas-loop", "pallas-pairwise", "pallas-radix", "xla")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ws", default="32,64,128,256")
    ap.add_argument("--rs", default="256,1024,4096")
    args = ap.parse_args()
    ws = [int(x) for x in args.ws.split(",")]
    rs = [int(x) for x in args.rs.split(",")]

    import jax

    from tpu_resiliency.platform.device import apply_compile_cache_env

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench_pallas_sweep.py measures on a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). No result."
        )
    apply_compile_cache_env()
    backend = dev.platform
    print(f"backend: {backend} {jax.devices()}", file=sys.stderr)
    results = {}
    loop_best_by_w = {w: True for w in ws}
    pallas_wins_by_w = {w: True for w in ws}
    for r in rs:
        for w in ws:
            row = {}
            for variant in VARIANTS:
                if variant == "pallas-pairwise" and w > 64:
                    continue  # quadratic VMEM temporaries exceed budget
                try:
                    row[variant] = measure(r, w, variant)
                except Exception as e:
                    row[variant] = None
                    print(f"R={r} W={w} {variant}: FAILED {e!r}"[:4000], file=sys.stderr)
            results[f"{r}x{w}"] = row
            # Pairwise never auto-selects, so it votes in neither export —
            # a pairwise-only win would certify a path use_pallas can't run.
            pallas_times = {
                k: v
                for k, v in row.items()
                if k not in ("xla", "pallas-pairwise") and v is not None
            }
            best_pallas = min(pallas_times.values(), default=None)
            # THIS row's verdict; the *_by_w flags separately accumulate the
            # every-R requirement for the exported defaults. Same TOL as the
            # loop cap so the two exports cannot contradict each other on a
            # sub-noise tie.
            row_pallas_wins = (
                best_pallas is not None
                and row.get("xla") is not None
                and best_pallas <= TOL * row["xla"]
            )
            if not row_pallas_wins:
                pallas_wins_by_w[w] = False
            # The cap governs loop-vs-its-auto-alternatives (radix / XLA);
            # pairwise is never auto-selected, so it doesn't vote.
            loop_t = row.get("pallas-loop")
            loop_ok = (
                loop_t is not None
                and (row.get("pallas-radix") is None or loop_t <= TOL * row["pallas-radix"])
                and (row.get("xla") is None or loop_t <= TOL * row["xla"])
            )
            if not loop_ok:
                loop_best_by_w[w] = False
            cells = "  ".join(
                f"{k}={v:.3f}ms" if v is not None else f"{k}=FAIL"
                for k, v in row.items()
            )
            verdict = "pallas" if row_pallas_wins else "xla"
            print(f"R={r:5d} W={w:4d}: {cells}  -> {verdict}")
    # The loop cap must be safe for EVERY rank count: a window qualifies only
    # if the loop kernel was the best variant at every tested R, and only while
    # all smaller tested windows also qualified (one noise win past a loss must
    # not raise the cap).
    loop_max_window = 0
    for w in sorted(ws):
        if not loop_best_by_w[w]:
            break
        loop_max_window = w
    print(
        json.dumps(
            {
                "backend": backend,
                "device_kind": dev.device_kind,
                "device_count": len(jax.devices()),
                "signals": S,
                "results_ms": results,
                "loop_max_window": loop_max_window,
                "loop_tolerance": TOL,
                "pallas_beats_xla_at": {
                    str(w): pallas_wins_by_w[w] for w in sorted(ws)
                },
                "export": f"TPU_RESILIENCY_PALLAS_MAX_WINDOW={loop_max_window}",
            }
        )
    )


if __name__ == "__main__":
    main()
