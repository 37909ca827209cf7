"""Layered in-job + in-process restart: both restarters on one workload.

The TPU-native analogue of the reference's
``examples/fault_tolerance/in_job_and_in_process_example.py``: a jitted train loop
wrapped with :class:`tpu_resiliency.inprocess.Wrapper` runs under ``tpu-ft-launcher``,
sharing the launcher-hosted coordination store (``TPU_RESILIENCY_STORE_EXTERNAL`` is
set by the agent, ``launcher/agent.py``). Fault routing:

- an **exception** inside the wrapped fn is absorbed by the in-process layer — the
  function restarts without the launcher noticing (no respawn, no budget charge);
- a **process death** escalates to the in-job layer — the launcher respawns the
  round, and the respawned wrappers form a fresh in-process restart world scoped by
  the new launcher round (``TPU_FT_RESTART_COUNT``).

Both layers narrate their state machines via the machine-parseable
``[NestedRestarter] name=[InJob|InProcess] state=...`` log-line contract
(reference ``rank_monitor_state_machine.py:127-145``, ``nested_restarter.py:34-107``).

Run (CPU simulation, 2 ranks — export ``JAX_PLATFORMS=cpu`` yourself: the two
worker processes of this simulation cannot share one chip, and nothing here
picks a platform in code)::

    TPU_RESILIENCY_LOG_LEVEL=INFO JAX_PLATFORMS=cpu \\
        tpu-ft-launcher --nproc-per-node 2 --max-restarts 2 --no-ft-monitors \\
        examples/layered_restart.py --steps 20
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

from tpu_resiliency.inprocess import CallWrapper, Wrapper
from tpu_resiliency.inprocess.nested_restarter import NestedRestarter
from tpu_resiliency.launcher.errors import record


def build_train(args, rank: int, launcher_round: int):
    nr = NestedRestarter()

    @Wrapper(
        initialize=nr.on_initialize,
        abort=nr.on_abort,
        completion=nr.on_completion,
        terminate=nr.on_terminate,
        soft_timeout=30.0,
        hard_timeout=60.0,
    )
    def train(call: CallWrapper):
        @jax.jit
        def step(w, x):
            return w - 0.1 * jnp.tanh(w * x).mean(), (w * x).sum()

        w = jnp.ones(())
        for i in range(args.steps):
            # Fault (a): in round 0 the wrapper's first pass raises at --fail-step;
            # the in-process layer restarts the fn and iteration 1 runs clean.
            if (
                launcher_round == 0
                and call.iteration == 0
                and rank == 1
                and i == args.fail_step
            ):
                raise RuntimeError(f"transient fault at step {i}")
            # Fault (b): in round 0, the *restarted* fn dies hard at --die-step;
            # only the in-job layer can recover from a lost process.
            if (
                launcher_round == 0
                and call.iteration >= 1
                and rank == 1
                and i == args.die_step
            ):
                os._exit(17)
            w, loss = step(w, jnp.float32(i + 1))
            call.ping()
        return float(loss)

    return train


@record
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--fail-step", type=int, default=5)
    ap.add_argument("--die-step", type=int, default=9)
    args = ap.parse_args()

    rank = int(os.environ.get("RANK", "0"))
    launcher_round = int(os.environ.get("TPU_FT_RESTART_COUNT", "0"))
    train = build_train(args, rank, launcher_round)
    loss = train()
    print(f"rank {rank}: finished (launcher round {launcher_round}, loss {loss})")


if __name__ == "__main__":
    main()
