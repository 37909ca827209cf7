"""Recovery-latency harness sanity (scripts/bench_restart.py) plus the
slow-marked perf gates the ISSUE-9 acceptance criteria hang off: warm-path
respawn within 2.5x the in-process restart median, and fast-path rendezvous
at most half the full ladder's median — regressions fail CI, not a JSON
diff."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_restart_latency_harness(tmp_path):
    out = tmp_path / "BENCH_restart.json"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "bench_restart.py"),
            "--restarts", "2",
            "--out", str(out),
        ],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(out.read_text())
    inproc = summary["in_process"]["faulting_rank_ms"]["median"]
    injob = summary["in_job"]["respawn_ms"]
    assert 0 < inproc, summary
    assert 0 < injob, summary
    # The decomposition must be present and self-consistent on both in-job
    # legs: segments are non-negative and sum to no more than the total.
    for leg in ("in_job", "in_job_warm_spares"):
        d = summary[leg]
        segs = [d["detect_ms"], d["teardown_ms"], d["rendezvous_ms"]]
        segs.append(
            d["spawn_and_startup_ms"] if "spawn_and_startup_ms" in d
            else d["promote_ms"] + d["first_step_ready_ms"]
        )
        assert all(s >= 0 for s in segs), d
        assert sum(segs) <= d["respawn_ms"] * 1.05 + 1.0, d
    # The warm leg must actually have promoted (else it measured a cold run).
    assert "promote_ms" in summary["in_job_warm_spares"]
    # Structural acceptance: second-restart compile-cache hit recorded.
    assert summary["compile_cache"]["restart_hit"], summary["compile_cache"]
    # The entire point of the in-process layer: recovery without interpreter,
    # import, and rendezvous startup. That claim is about environments where
    # interpreter startup actually costs something (reaching a v5e chip took a
    # fresh process 8-12 s, chip run PR 21); in a featherweight env (measured
    # floor < 1 s, as under JAX_PLATFORMS=cpu) the event-driven in-job respawn
    # can legitimately beat the config-bound engine latency, so only
    # sanity-bound it.
    floor = summary["in_job"]["python_startup_floor_ms"]
    if floor > 1000:
        assert inproc < injob, summary
    else:
        assert inproc < 2000, summary


@pytest.mark.slow
def test_warm_respawn_within_2_5x_of_inprocess():
    """The ISSUE-9 headline gate: warm-path in-job respawn ≤ 2.5× the
    in-process restart median (and ≤ 400 ms absolute on loopback). Best of
    two attempts damps machine-load noise, same policy as the ckpt fg-ratio
    gate."""
    from scripts.bench_restart import bench_injob, bench_inprocess

    inproc = bench_inprocess(2)["faulting_rank_ms"]["median"]
    best = min(
        bench_injob(warm_spares=2)["respawn_ms"] for _ in range(2)
    )
    assert best <= 400.0, f"warm respawn {best:.0f} ms > 400 ms"
    assert best <= 2.5 * inproc, (
        f"warm respawn {best:.0f} ms > 2.5x in-process {inproc:.0f} ms"
    )


@pytest.mark.slow
def test_fastpath_rendezvous_at_most_half_the_ladder():
    """Replacement rounds with unchanged membership must close in ≤ 0.5× the
    full ladder's median (the committed 16-node loopback run shows ~3×)."""
    from scripts.bench_restart import bench_rendezvous_fastpath

    r = bench_rendezvous_fastpath(nodes=16, rounds=8)
    assert r["fast_path_ms"]["median"] <= 0.5 * r["full_ladder_ms"]["median"], r


@pytest.mark.slow
def test_compile_cache_restart_hit_and_cheaper_rejit():
    """Round N+1 must find the persistent compilation cache warm."""
    from scripts.bench_restart import bench_compile_cache

    r = bench_compile_cache()
    assert r["restart_hit"], r
    assert r["outcomes"][0] == "miss", r
    # The re-jit skips XLA compilation; allow generous slack for load noise.
    assert r["restart_jit_ms"] <= r["first_jit_ms"] * 1.5, r
