"""One in-job restart through the launcher, read back by ``tools/critpath``: the
milestone segments of the episode (detect, teardown, rendezvous, then spawn or
promote) are all there, none negative, and those stamped before the worker starts
fit in the episode; a parked spare is promoted where there is one; the replacement
start finds the persistent compile cache warm. Counts and order only: no duration
is judged."""

import json
import os
import subprocess
import sys

import pytest

from tpu_resiliency.tools.critpath import restart_decomposition

# Round 0: jit once under the launcher's compile cache, wait for a parked spare
# where one is due (so the promotion is certain, not a race with its warm-up),
# stamp the fault and exit 1. Round 1: stamp the re-entry, jit again.
WORKER = """
import glob, os, sys, time
stamp_dir, spares_glob = sys.argv[1], sys.argv[2]
count = int(os.environ.get("TPU_FT_RESTART_COUNT", "0"))
with open(os.path.join(stamp_dir, f"entry_{count}"), "w") as f:
    f.write(repr(time.time()))
from tpu_resiliency.platform import device
device.apply_compile_cache_env()  # the sweep and the compile_cache event
import jax, jax.numpy as jnp
jax.block_until_ready(jax.jit(lambda x: jnp.tanh(x @ x.T).sum())(jnp.ones((64, 64))))
if count == 0:
    deadline = time.monotonic() + 120
    while spares_glob and not [p for p in glob.glob(spares_glob) if not p.endswith(".tmp")]:
        if time.monotonic() > deadline:
            sys.exit(17)  # the spare never parked: fail loudly
        time.sleep(0.02)
    with open(os.path.join(stamp_dir, "exit_0"), "w") as f:
        f.write(repr(time.time()))
    sys.exit(1)
"""


@pytest.mark.parametrize("warm_spares", [0, 1], ids=["cold_spawn", "promoted_warm_spare"])
def test_one_restart_decomposes_into_its_milestones(tmp_path, warm_spares):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    stamps = tmp_path / "stamps"
    stamps.mkdir()
    events = tmp_path / "events.jsonl"
    run_dir = tmp_path / "run"
    env = dict(os.environ)
    # The cold start needs an empty cache: an outside directory would win over the flag.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "tpu_resiliency.launcher.launch",
            "--standalone", "--nproc-per-node", "1", "--max-restarts", "2",
            "--no-ft-monitors", "--monitor-interval", "0.1",
            "--events-file", str(events), "--run-dir", str(run_dir),
            "--compile-cache-dir", str(tmp_path / "compile_cache"),
            "--warm-spares", str(warm_spares), "--warm-spare-preload", "json",
            str(worker), str(stamps),
            str(run_dir / "spares" / "ready_*") if warm_spares else "",
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]

    records = [json.loads(line) for line in events.read_text().splitlines()]
    t_fault = float((stamps / "exit_0").read_text())
    t_resume = float((stamps / "entry_1").read_text())
    dec = restart_decomposition(records, fault_ts=t_fault, resume_ts=t_resume)
    assert dec is not None, "no restart episode in the event stream"

    last = ["promote", "first_step_ready"] if warm_spares else ["spawn_and_startup"]
    assert [s["name"] for s in dec["segments"]] == ["detect", "teardown", "rendezvous", *last]
    assert dec["promoted"] is bool(warm_spares)
    durations = [s["duration_ms"] for s in dec["segments"]]
    assert all(d >= 0 for d in durations), dec["segments"]
    assert dec["total_ms"] == pytest.approx((t_resume - t_fault) * 1e3, abs=0.01)
    # Detect, teardown and rendezvous are stamped before the worker has its spec,
    # so they fit in the episode; a cold spawn's one further segment closes it
    # exactly. A promoted shim's first statement can beat the launcher's own
    # `worker_promoted` stamp (by tens of ms on a loaded machine): that segment
    # is clamped, and may pass the end.
    assert sum(durations[:3]) <= dec["total_ms"] + 0.01, dec
    if not warm_spares:
        assert sum(durations) == pytest.approx(dec["total_ms"], abs=0.01), dec

    outcomes = [r["outcome"] for r in records if r["kind"] == "compile_cache"]
    assert outcomes[0] == "miss" and outcomes[-1] == "hit", outcomes
