"""Mesh-sharded straggler telemetry as the product path: zero-gather report rounds.

The north-star configuration (BASELINE target 4/5): every worker is its own JAX
process (``jax.distributed``), and straggler report rounds ride the device mesh —
each process contributes its per-rank timing summary as a *shard* of a global mesh
array, the cross-rank reductions run as XLA collectives inside one compiled scoring
program, and the coordination store carries only the one-time column-name agreement.
No per-rank summary ever crosses the store (this script asserts that).

Contrast with the reference, which packs host dicts into tensors and runs
NCCL ``all_reduce`` + rank-0 ``gather`` with Python pack/unpack loops per report
(``straggler/reporting.py:255-296,338-419``).

Run (CPU simulation, 2 workers on one host)::

    TPU_RESILIENCY_LOG_LEVEL=INFO tpu-ft-launcher --nproc-per-node 2 \\
        --no-ft-monitors examples/mesh_telemetry_training.py \\
        --cpu --coord-port 29620 --steps 150

``--cpu`` is the explicit switch for this simulation: N worker processes on one
host cannot share one chip, so each becomes a 4-virtual-device CPU host. Without
it ``$JAX_PLATFORMS`` (or, unset, whatever JAX finds) decides — one worker per
TPU host, and the mesh rides ICI/DCN.
"""

from __future__ import annotations

import argparse
import os
import time

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

from tpu_resiliency.launcher.errors import record


@record
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--cpu", action="store_true",
        help="simulate: run this worker on 4 virtual CPU devices (N workers on "
        "one host cannot share one chip)",
    )
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--coord-port", type=int, required=True,
                    help="port for jax.distributed coordination (rank 0 hosts)")
    ap.add_argument("--slow-rank", type=int, default=1)
    ap.add_argument("--slow-ms", type=float, default=20.0)
    args = ap.parse_args()

    if args.cpu:
        # Before the first jax import: each worker process simulates a 4-device
        # host; the telemetry mesh uses one device per process (a row per rank).
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax
    import jax.numpy as jnp

    from tpu_resiliency.integrations import LoopContext, run_training
    from tpu_resiliency.integrations.straggler_callback import StragglerDetectionCallback
    from tpu_resiliency.platform.store import CoordStore, store_addr_from_env

    rank = int(os.environ["RANK"])
    world = int(os.environ["WORLD_SIZE"])
    jax.distributed.initialize(
        f"127.0.0.1:{args.coord_port}", num_processes=world, process_id=rank
    )

    host, port = store_addr_from_env()
    store = CoordStore(host, port)

    callback = StragglerDetectionCallback(
        report_time_interval=0.5,
        threshold=0.75,
        store=store.scoped("straggler/"),
        use_device_mesh=True,
    )

    @jax.jit
    def forward(w, x):
        return jnp.tanh(w @ x).sum()

    w = jnp.ones((64, 64))

    def step_fn(state, step):
        x = jnp.full((64, 8), 0.1 * (step % 7))
        loss = forward(w, x)
        loss.block_until_ready()
        # The injected straggler: this rank pays extra host time every step.
        if rank == args.slow_rank:
            time.sleep(args.slow_ms / 1e3)
        else:
            time.sleep(args.slow_ms / 4e3)
        return state

    ctx = run_training(
        step_fn,
        state=None,
        num_steps=args.steps,
        callbacks=[callback],
        ctx=LoopContext(rank=rank, world_size=world),
    )

    # --- the zero-gather proof -------------------------------------------------
    leaked = store.prefix_get("straggler/telemetry/round/")
    assert leaked == {}, f"per-rank summaries leaked through the store: {leaked}"
    report = callback.last_report
    if rank == 0:
        assert report is not None, "no report round elapsed; raise --steps"
        stragglers = report.identify_stragglers(perf_threshold=0.75)
        flagged = sorted(s.rank for s in stragglers.by_perf)
        assert flagged == [args.slow_rank], (flagged, report.perf_scores)
        print(
            f"ZERO-GATHER OK: report rounds rode the mesh; flagged ranks {flagged} "
            f"perf={report.perf_scores}",
            flush=True,
        )


if __name__ == "__main__":
    main()
