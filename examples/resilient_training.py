"""End-to-end resilient training: launcher + callbacks + hierarchical checkpoints.

The full stack in one script (the analogue of the reference's
``examples/fault_tolerance/train_ddp_heartbeats_api.py`` + local-ckpt examples):

- launched by ``tpu-ft-launcher`` (in-job restart on worker death),
- FT heartbeats via :class:`FaultToleranceCallback` (hang detection),
- straggler section timing via :class:`StragglerDetectionCallback`,
- local checkpoints every 5 steps via :class:`HierarchicalCheckpointCallback`,
- resume-from-latest on every (re)start,
- a crash injected in round 0 at step 12 to demonstrate recovery.

Run::

    tpu-ft-launcher --nproc-per-node 1 --max-restarts 2 \\
        --warm-spares 1 \\
        --ft-param-initial_rank_heartbeat_timeout 60 \\
        --ft-param-rank_heartbeat_timeout 60 \\
        examples/resilient_training.py --steps 30 --ckpt-dir /tmp/resilient_ckpt

(``--warm-spares 1`` parks a pre-imported interpreter so the post-crash
respawn promotes it in tens of milliseconds instead of paying jax import.)

``$JAX_PLATFORMS`` decides where the worker runs; unset, it takes whatever JAX
finds (the chip on a TPU host). Keep ``--nproc-per-node 1`` on a TPU host: every
local worker is handed the same devices, and a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import os

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

import jax
import jax.numpy as jnp

from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
from tpu_resiliency.integrations import (
    FaultToleranceCallback,
    HierarchicalCheckpointCallback,
    LoopContext,
    StragglerDetectionCallback,
    run_training,
)
from tpu_resiliency.launcher.errors import record


@record
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--ckpt-dir", default="/tmp/resilient_ckpt")
    ap.add_argument("--crash-step", type=int, default=12)
    args = ap.parse_args()

    rank = int(os.environ.get("RANK", "0"))
    round_no = int(os.environ.get("TPU_FT_RESTART_COUNT", "0"))

    # -- model: tiny linear regression, jitted -----------------------------
    key = jax.random.PRNGKey(0)
    w0 = jax.random.normal(key, (16, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    y = x @ jax.random.normal(jax.random.PRNGKey(2), (16, 16))

    @jax.jit
    def train_step(w, _):
        def loss_fn(w):
            return jnp.mean((x @ w - y) ** 2)

        g = jax.grad(loss_fn)(w)
        return w - 0.05 * g

    def step_fn(state, i):
        state = train_step(state, i)
        if round_no == 0 and rank == 0 and i == args.crash_step:
            raise RuntimeError(f"injected crash at step {i} (round 0)")
        return state

    # -- resiliency stack --------------------------------------------------
    mgr = LocalCheckpointManager(args.ckpt_dir, rank=rank)
    ckpt_cb = HierarchicalCheckpointCallback(
        local_manager=mgr,
        local_every=5,
        to_state_dict=lambda s: {"w": s},
        from_state_dict=lambda s, loaded: loaded["w"],
    )
    callbacks = [
        FaultToleranceCallback(calc_timeouts=True),
        # Full telemetry stack: section timing every step, plus sampled
        # profiler windows feeding per-program (prog/...) and per-op/scope
        # (op/...) device times into the scored matrix.
        StragglerDetectionCallback(
            report_time_interval=2.0, profile_programs_every=10, profile_ops=True
        ),
        ckpt_cb,
    ]

    ctx = LoopContext(rank=rank, state=w0)
    if ckpt_cb.restore_latest(ctx):
        print(f"[rank {rank}] round {round_no}: resumed from step {ctx.start_step}")
    ctx = run_training(step_fn, ctx.state, args.steps, callbacks=callbacks, ctx=ctx)
    final_loss = float(jnp.mean((x @ ctx.state - y) ** 2))
    ckpt_cb.close()
    print(f"[rank {rank}] round {round_no}: done at step {ctx.step}, loss {final_loss:.5f}")


if __name__ == "__main__":
    main()
