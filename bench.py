"""North-star benchmark (BASELINE.json config 4): score 4096-rank heartbeat+perf fused
telemetry — per-rank per-signal timing windows reduced to straggler scores — on one TPU
chip, vs a host-side emulation of the reference's Python scoring path.

Baseline emulation re-implements, from the spec in SURVEY.md §2.5/§3.5 (NOT copied), what
the reference's ``ReportGenerator.generate_report`` does on host per report: per-rank
dicts of per-signal sample lists → per-signal medians + totals (Python loop over dict
entries), pack medians to a flat vector, min-reduce across ranks, unpack, weighted score
loop, straggler thresholding. The device path is ``telemetry.scoring.score_round`` (and
the Pallas fused-median variant) running as one compiled program.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "backend",
"device_kind", "device_count"}; details go to stderr. It measures on a TPU or not at
all: without one it exits non-zero and prints no result line. The parent never
imports JAX — a chip belongs to one process at a time — so every variant runs in a
child that has the chip to itself, and the device facts in the result are the
children's own.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

R, S, W = 4096, 64, 32
SLOW_FRACTION = 0.05
SLOWDOWN = 1.6
ITERS = 50


def make_telemetry(seed=0):
    rng = np.random.default_rng(seed)
    base = rng.uniform(0.8, 1.2, size=(1, S, 1)).astype(np.float32)
    data = base * (1.0 + 0.05 * rng.standard_normal((R, S, W)).astype(np.float32))
    n_slow = int(R * SLOW_FRACTION)
    slow_ranks = rng.choice(R, size=n_slow, replace=False)
    data[slow_ranks] *= SLOWDOWN
    counts = np.full((R, S), W, dtype=np.int32)
    truth = np.zeros(R, dtype=bool)
    truth[slow_ranks] = True
    return data, counts, truth


def baseline_host_scoring(data, counts, threshold=0.75):
    """Reference-style host scoring: dict-of-lists telemetry, Python pack/unpack loops."""
    # per-rank summaries as the reference holds them: dict rank -> {signal_name: samples}
    telemetry = {
        r: {f"sig{s}": data[r, s, : counts[r, s]].tolist() for s in range(S)} for r in range(R)
    }
    t0 = time.perf_counter()
    medians, totals = {}, {}
    for r, sigs in telemetry.items():
        med_r, tot_r = {}, {}
        for name, samples in sigs.items():
            arr = np.asarray(samples)
            med_r[name] = float(np.median(arr))
            tot_r[name] = float(arr.sum())
        medians[r] = med_r
        totals[r] = tot_r
    # pack → min-reduce across ranks → unpack (the all_reduce(MIN) emulation)
    names = sorted(medians[0])
    packed = np.array([[medians[r][n] for n in names] for r in range(R)])
    ref = packed.min(axis=0)
    # weighted per-rank score loop
    scores = {}
    for r in range(R):
        num = den = 0.0
        for j, n in enumerate(names):
            w = totals[r][n]
            num += w * (ref[j] / medians[r][n])
            den += w
        scores[r] = num / den
    stragglers = {r for r, sc in scores.items() if sc < threshold}
    elapsed = time.perf_counter() - t0
    return elapsed, scores, stragglers


def f1(pred_mask, truth):
    tp = int((pred_mask & truth).sum())
    fp = int((pred_mask & ~truth).sum())
    fn = int((~pred_mask & truth).sum())
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    return 2 * prec * rec / max(prec + rec, 1e-9)


def _program_ms(profiler, substring):
    """Median per-execution time (ms) of the profiled program whose name contains
    ``substring``; raises when the window captured no such program."""
    for name, st in profiler.get_stats().items():
        if substring in name:
            return st["med"] * 1e3
    raise RuntimeError(
        f"profiler window has no {substring!r} program: {sorted(profiler.get_stats())}"
    )


def require_tpu():
    """The device facts of this process, or exit: a number from any other
    backend is not a measurement of this system."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}). No result."
        )
    return {
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def device_scoring(data, counts, variant="xla"):
    """One scoring round's device time, from the framework's own XLA-profiler
    capture (``telemetry/device_profiler.py``): the executed program's duration on
    the device plane. A host clock around the call would time the enqueue — JAX
    returns before the device finishes — plus the dispatch path."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.telemetry import scoring
    from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler

    if variant in ("pallas", "pallas-pairwise", "pallas-radix"):
        from tpu_resiliency.ops.scoring_pallas import fused_median_weights

        mode = {"pallas": "loop", "pallas-pairwise": "pairwise",
                "pallas-radix": "radix"}[variant]

        def score_program(d, c, e, h):
            mw = fused_median_weights(d, c, mode=mode)
            return scoring.score_round(d, c, e, h, medians_and_weights=mw)

    else:
        def score_program(d, c, e, h):
            return scoring.score_round(d, c, e, h)

    fn = jax.jit(score_program)
    d = jnp.asarray(data)
    c = jnp.asarray(counts)
    ewma = jnp.ones((R,))
    hist = jnp.full((R, S), jnp.inf)
    out = fn(d, c, ewma, hist)
    jax.block_until_ready(out)
    prof = DeviceTimeProfiler()
    with prof:
        for _ in range(ITERS):
            out = fn(d, c, out.ewma, hist)
        jax.block_until_ready(out)
    return _program_ms(prof, "score_program") / 1e3, out


def device_ring_scoring(data, counts, report_interval=100):
    """The real north-star hot loop, decomposed the way a train loop pays for it:

    - **push**: every step appends its ``[R, S]`` timings to the device-resident
      sharded rings from inside the jitted step (donated carry) — paid per step;
    - **score**: the fused scoring program runs once per *report* (reference default
      cadence is minutes; ``report_interval`` steps here is conservative).

    The per-step cost is ``push + score / report_interval``, both device-plane
    program durations (see :func:`device_scoring`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler
    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("rank",))
    mt = MeshTelemetry(
        mesh, "rank", n_ranks=R,
        signal_names=tuple(f"sig{s}" for s in range(S)), window=W,
    )
    state = mt.init_state()
    # Pre-split step rows: indexing a device array with a fresh static index inside
    # the timed loop would compile a new slice program per index.
    rows = [jnp.asarray(data[:, :, i]) for i in range(W)]
    for i in range(W):
        state = mt.push(state, rows[i])
    # warm both programs
    state, out = mt.score(state)
    jax.block_until_ready((state, out))

    prof = DeviceTimeProfiler()
    with prof:
        for i in range(ITERS * 4):
            state = mt.push(state, rows[i % W])
        jax.block_until_ready(state)
        for i in range(5):
            state = mt.push(state, rows[i % W])  # keep counts alive between scores
            state, out = mt.score(state)
        jax.block_until_ready((state, out))
    per_push = _program_ms(prof, "_push_impl") / 1e3
    per_score = _program_ms(prof, "_score_reset_impl") / 1e3
    per_step = per_push + per_score / report_interval

    # Rebuild a full window so the F1 check sees real scores, not a 1-sample round.
    for i in range(W):
        state = mt.push(state, rows[i])
    _, out = mt.score(state)
    return per_step, per_push, per_score, out, mt.use_pallas


REPORT_INTERVAL = 100
VARIANTS = ("xla", "pallas", "pallas-pairwise", "pallas-radix", "rings")


def run_variant(variant: str) -> dict:
    """Measure one device variant in THIS process (a child of :func:`main`): it
    has the chip to itself, and variants cannot contaminate each other's
    dispatch latency (measuring the ring path after another compiled variant
    in one process inflated push dispatch ~30x)."""
    from tpu_resiliency.platform.device import apply_compile_cache_env

    res = require_tpu()
    apply_compile_cache_env()
    data, counts, truth = make_telemetry()
    if variant == "rings":
        per_step, per_push, per_score, out, use_pallas = device_ring_scoring(
            data, counts, REPORT_INTERVAL
        )
        res.update(per_push=per_push, per_score=per_score, use_pallas=use_pallas)
    else:
        per_step, out = device_scoring(data, counts, variant=variant)
    res.update(per_step=per_step, f1=f1(np.asarray(out.straggler), truth))
    return res


def run_variant_subprocess(variant: str, env: dict) -> dict:
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--variant", variant],
        stdout=subprocess.PIPE, text=True, timeout=900, env=env,
    )
    if r.returncode != 0:
        raise SystemExit(f"device[{variant}] failed (exit {r.returncode}); no result")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    from tpu_resiliency.platform import compile_cache  # imports no JAX

    env = dict(os.environ)
    # The children share one persistent compile cache: where the environment
    # names it, there; otherwise the checkout's one fixed directory.
    env.setdefault(
        compile_cache.CACHE_DIR_ENV,
        compile_cache.checkout_cache_dir(os.path.dirname(os.path.abspath(__file__))),
    )
    # First child first: without a chip it exits here, before the host baseline
    # (seconds of Python loops) is spent on a run that can print nothing.
    results = {"xla": run_variant_subprocess("xla", env)}

    data, counts, truth = make_telemetry()
    base_s, base_scores, base_stragglers = baseline_host_scoring(data, counts)
    base_mask = np.zeros(R, dtype=bool)
    base_mask[list(base_stragglers)] = True
    print(
        f"baseline host scoring: {base_s * 1e3:.1f} ms/report, "
        f"F1={f1(base_mask, truth):.3f}",
        file=sys.stderr,
    )
    for name in VARIANTS[1:]:
        results[name] = run_variant_subprocess(name, env)
    devices = {(r["backend"], r["device_kind"], r["device_count"]) for r in results.values()}
    if len(devices) != 1:
        raise SystemExit(f"variants ran on different devices {sorted(devices)}; no result")
    backend, device_kind, device_count = devices.pop()
    for name, r in results.items():
        print(
            f"device[{name}]: {r['per_step'] * 1e3:.4f} ms, F1={r['f1']:.3f}",
            file=sys.stderr,
        )
    rings = results["rings"]
    print(
        f"device[rings, hot loop]: push {rings['per_push'] * 1e3:.4f} ms/step + "
        f"score {rings['per_score'] * 1e3:.3f} ms/report / {REPORT_INTERVAL} steps "
        f"= {rings['per_step'] * 1e3:.4f} ms/step, F1={rings['f1']:.3f}, "
        f"use_pallas={rings['use_pallas']}",
        file=sys.stderr,
    )
    print(json.dumps({
        "metric": (
            f"telemetry hot-loop cost, {R} ranks x {S} signals x {W} window: in-jit "
            f"ring push/step + fused scoring/report amortized over {REPORT_INTERVAL} "
            f"steps (push {rings['per_push'] * 1e3:.4f} ms, score "
            f"{rings['per_score'] * 1e3:.3f} ms, F1={rings['f1']:.3f})"
        ),
        "value": round(rings["per_step"] * 1e3, 4),
        "unit": "ms/step",
        # The baseline pays its host report at the same cadence and nothing per
        # step: compare amortized report cost against the amortized device cost.
        "vs_baseline": round((base_s / REPORT_INTERVAL) / rings["per_step"], 2),
        "backend": backend,
        "device_kind": device_kind,
        "device_count": device_count,
    }))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default=None, choices=VARIANTS,
                    help="internal: measure one variant in this process")
    args = ap.parse_args()
    if args.variant:
        print(json.dumps(run_variant(args.variant)))
    else:
        main()
