"""BASELINE configs 1-3 replay harnesses (config 4 lives in bench.py).

The reference publishes no numbers (BASELINE.md), so these harnesses *measure* the
TPU-native path on replayed synthetic telemetry at the three scales BASELINE.json
names, against the same detection semantics the reference implements:

- **Config 1** — 64-rank single-process section-timing report (the reference
  ``examples/straggler`` semantics: per-section relative scores = min-of-medians /
  local-median, total-time weighting, 0.75 threshold). Scored by the real device
  pipeline (``ReportGenerator.generate_summary_report``).
- **Config 2** — 256-rank heartbeat replay with one injected hang, driven through
  the REAL monitor decision code (``RankMonitorServer._hb_timeout_elapsed``,
  reference ``rank_monitor_client.py:221-237`` / ``rank_monitor_server.py:349``)
  on a virtual clock: measures detection latency and F1.
- **Config 3** — 1024-rank kernel-style timing stream with 5% slow nodes, scored
  by the fused window pipeline (``scoring.score_round_jit``): report latency + F1.

Usage::

    python scripts/bench_configs.py [--out-dir DIR] [--iters N] [--configs 1,2,3]

Prints one JSON line per config and writes ``BENCH_config{N}.json`` to the out dir.
Run on CPU or TPU; CI runs it via ``tests/test_bench_configs.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def f1(pred: set, truth: set) -> float:
    tp = len(pred & truth)
    fp = len(pred - truth)
    fn = len(truth - pred)
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    return 2 * prec * rec / max(prec + rec, 1e-9)


# ---------------------------------------------------------------------------
# Config 1: 64-rank section-timing report parity
# ---------------------------------------------------------------------------

def config1(iters: int) -> dict:
    import jax.numpy as jnp

    from tpu_resiliency.telemetry.reporting import ReportGenerator

    ranks, sections = 64, 3
    names = ("sec/fwd", "sec/bwd", "sec/opt")
    slow = {17}
    rng = np.random.default_rng(1)
    base = rng.uniform(0.010, 0.030, size=(1, sections))
    medians = np.tile(base, (ranks, 1)) * (
        1.0 + 0.02 * rng.standard_normal((ranks, sections))
    )
    for r in slow:
        medians[r] *= 2.0
    weights = medians * 100.0  # total time over ~100 samples
    counts = np.full((ranks, sections), 100, np.int32)

    gen = ReportGenerator(world_size=ranks, max_signals=sections)
    m, w, c = jnp.asarray(medians), jnp.asarray(weights), jnp.asarray(counts)
    report = gen.generate_summary_report(m, w, c, names)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        report = gen.generate_summary_report(m, w, c, names)
    report_ms = (time.perf_counter() - t0) / iters * 1e3

    stragglers = report.identify_stragglers(perf_threshold=0.75)
    flagged = {s.rank for s in stragglers.by_perf}
    # Reference-semantics parity checks (examples/straggler): healthy ranks score
    # ~1.0, the slow rank scores ~min/median = ~0.5 and is flagged.
    healthy = [v for r, v in report.perf_scores.items() if r not in slow]
    parity = (
        min(healthy) > 0.9
        and max(healthy) <= 1.0 + 1e-6
        and report.perf_scores[17] < 0.6
    )
    return {
        "config": 1,
        "ranks": ranks,
        "report_ms": round(report_ms, 4),
        "f1": round(f1(flagged, slow), 4),
        "flagged": sorted(flagged),
        "parity_semantics_ok": bool(parity),
    }


# ---------------------------------------------------------------------------
# Config 2: 256-rank heartbeat replay, one injected hang
# ---------------------------------------------------------------------------

def config2(_: int) -> dict:
    from tpu_resiliency.watchdog.config import FaultToleranceConfig
    from tpu_resiliency.watchdog.data import RankInfo
    from tpu_resiliency.watchdog.monitor_server import RankMonitorServer, _RankSession

    ranks = 256
    hang_rank = 101
    hb_interval = 1.0
    hb_timeout = 3.0
    check_interval = 0.5
    hang_at = 30.0
    horizon = 60.0

    cfg = FaultToleranceConfig(
        initial_rank_heartbeat_timeout=10.0,
        rank_heartbeat_timeout=hb_timeout,
        workload_check_interval=check_interval,
    )
    servers = []
    for r in range(ranks):
        srv = RankMonitorServer(cfg, socket_path=f"/nonexistent/replay_{r}.sock")
        srv.session = _RankSession(
            info=RankInfo(global_rank=r, local_rank=r % 8, host=f"host{r // 8}", pid=0),
            connected_at=0.0,
        )
        servers.append(srv)

    detected: dict[int, float] = {}
    scan_times = []
    now = 0.0
    while now < horizon:
        now = round(now + check_interval, 6)
        # Replay heartbeats that arrived since the last tick (virtual clock).
        for r, srv in enumerate(servers):
            last_beat = None
            t = hb_interval
            while t <= now:
                if not (r == hang_rank and t >= hang_at):
                    last_beat = t
                t += hb_interval
            srv.session.last_hb = last_beat
        # The real decision code, timed: one full 256-rank scan per tick.
        t0 = time.perf_counter()
        for r, srv in enumerate(servers):
            if r in detected:
                continue
            reason = srv._hb_timeout_elapsed(now)
            if reason is not None:
                detected[r] = now
        scan_times.append(time.perf_counter() - t0)

    truth = {hang_rank}
    pred = set(detected)
    # Latency from the hang (last heartbeat the rank would have sent) to the tick
    # that flagged it. Expected: hb_timeout .. hb_timeout + hb_interval + tick.
    last_hb_sent = hang_at - hb_interval
    # None (JSON null), not inf: json.dumps would emit the non-standard Infinity.
    latency = (
        round(detected[hang_rank] - last_hb_sent, 3) if hang_rank in detected else None
    )
    return {
        "config": 2,
        "ranks": ranks,
        "hang_rank": hang_rank,
        "detection_latency_s": latency,
        "latency_budget_s": hb_timeout + hb_interval + check_interval,
        "f1": round(f1(pred, truth), 4),
        "scan_us_per_tick": round(float(np.mean(scan_times)) * 1e6, 2),
    }


# ---------------------------------------------------------------------------
# Config 3: 1024-rank kernel-timing stream, 5% slow nodes
# ---------------------------------------------------------------------------

def config3(iters: int) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.telemetry import scoring

    ranks, signals, window = 1024, 16, 32
    rng = np.random.default_rng(3)
    base = rng.uniform(0.8, 1.2, size=(1, signals, 1)).astype(np.float32)
    data = base * (1.0 + 0.05 * rng.standard_normal((ranks, signals, window)).astype(np.float32))
    n_slow = ranks // 20  # 5%
    slow = set(rng.choice(ranks, size=n_slow, replace=False).tolist())
    for r in slow:
        data[r] *= 1.6
    counts = np.full((ranks, signals), window, np.int32)

    d, c = jnp.asarray(data), jnp.asarray(counts)
    ewma = jnp.ones((ranks,))
    hist = jnp.full((ranks, signals), jnp.inf)
    out = scoring.score_round_jit(d, c, ewma, hist)  # warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = scoring.score_round_jit(d, c, out.ewma, hist)
    jax.block_until_ready(out)
    report_ms = (time.perf_counter() - t0) / iters * 1e3

    pred = set(np.nonzero(np.asarray(out.straggler))[0].tolist())
    return {
        "config": 3,
        "ranks": ranks,
        "slow_fraction": 0.05,
        "report_ms": round(report_ms, 4),
        "f1": round(f1(pred, slow), 4),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=REPO_ROOT)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--configs", default="1,2,3")
    args = ap.parse_args()

    os.makedirs(args.out_dir, exist_ok=True)
    runners = {1: config1, 2: config2, 3: config3}
    ok = True
    import jax

    combined_path = os.path.join(args.out_dir, "BENCH_configs.json")
    combined = {}
    if os.path.exists(combined_path):
        # A partial --configs rerun refreshes only its own entries; the other
        # configs' previously measured results stay in the artifact.
        try:
            with open(combined_path) as f:
                combined = json.load(f)
        except (OSError, ValueError):
            combined = {}
    combined["backend"] = jax.default_backend()
    combined["note"] = (
        "configs 1-3 are host-semantic detection benchmarks (section "
        "report, heartbeat replay, timing-stream scoring); latency figures "
        "are host-side, F1 is backend-independent"
    )
    for n in (int(x) for x in args.configs.split(",")):
        result = runners[n](args.iters)
        line = json.dumps(result)
        print(line)
        with open(os.path.join(args.out_dir, f"BENCH_config{n}.json"), "w") as f:
            f.write(line + "\n")
        combined[f"config{n}"] = result
        if result["f1"] < 1.0:
            ok = False
    with open(combined_path, "w") as f:
        json.dump(combined, f, indent=1)
        f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
