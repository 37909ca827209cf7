"""End-to-end example: fault-tolerant JAX training with in-process restart.

The TPU-native analogue of the reference's
``examples/fault_tolerance/in_job_and_in_process_example.py`` + ``tests/inprocess/app.py``:
N rank processes train a jitted MLP; one rank is killed mid-run; the survivors restart
in place — abort device state, re-mesh to the shrunken world, reload the latest local
checkpoint — and finish training.

Run (CPU simulation, 2 ranks):

    python examples/inprocess_restart_train.py --cpu --world 2 --kill-rank 1 --kill-step 6

Each rank process:
  - wraps ``train`` with :class:`tpu_resiliency.inprocess.Wrapper`
  - saves a local checkpoint every ``--ckpt-every`` steps via
    :class:`~tpu_resiliency.checkpoint.LocalCheckpointManager`
  - on restart: reloads the newest fully-covered checkpoint and continues
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import socket
import sys
import tempfile

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def rank_main(rank: int, world: int, port: int, args, result_q) -> None:
    os.environ.update(
        RANK=str(rank),
        WORLD_SIZE=str(world),
        TPU_RESILIENCY_STORE_HOST="127.0.0.1",
        TPU_RESILIENCY_STORE_PORT=str(port),
    )
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # Each rank process is a world of exactly --devices-per-rank devices (>1
    # certifies a surviving MULTI-device world re-entering, not just a lone
    # device). Must be pinned before the jax import below — and pinned even
    # for 1, since the caller's own XLA_FLAGS may force a different count.
    # Only the force-count flag is replaced; other inherited flags survive.
    kept = [
        t for t in os.environ.get("XLA_FLAGS", "").split()
        if not t.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={args.devices_per_rank}")
    os.environ["XLA_FLAGS"] = " ".join(kept)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_resiliency.checkpoint import LocalCheckpointManager, PyTreeStateDict
    from tpu_resiliency.inprocess import (
        AbortCompilationCache,
        CallWrapper,
        JaxHealthCheck,
        RetryController,
        Wrapper,
    )

    ckpt_root = args.ckpt_root

    @Wrapper(
        initialize=RetryController(max_iterations=5),
        abort=AbortCompilationCache(),
        health_check=JaxHealthCheck(timeout=60.0),
        monitor_interval=0.1,
        last_call_wait=0.1,
        soft_timeout=5.0,
        hard_timeout=10.0,
        heartbeat_interval=0.25,
        heartbeat_timeout=5.0,
        barrier_timeout=60.0,
        completion_timeout=60.0,
    )
    def train(call: CallWrapper):
        fs = call.frozen_state
        my_rank, active_world = fs.active_rank, fs.active_world_size
        # Per-rank local checkpoints; comm-less here (each rank loads its own shard;
        # see tests/checkpoint for the replicated multi-rank flow).
        mgr = LocalCheckpointManager(ckpt_root, rank=fs.initial_rank)

        key = jax.random.PRNGKey(0)
        params = {
            "w1": jax.random.normal(key, (16, 32)) * 0.1,
            "w2": jax.random.normal(jax.random.fold_in(key, 1), (32, 1)) * 0.1,
        }
        start_step = 0
        latest = mgr.find_latest()
        if latest >= 0:
            tree, meta = mgr.load_tree(latest)
            params = tree["params"]
            start_step = int(meta["iteration"]) + 1
            print(f"[rank {fs.initial_rank}] resumed from step {start_step}", flush=True)

        batch_sharding = None
        if args.devices_per_rank > 1:
            # Shard the batch over this rank's own device mesh: every step the
            # surviving world completes is a genuinely multi-device program
            # (XLA partitions the matmuls and inserts the loss reduction).
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            local_mesh = Mesh(np.asarray(jax.devices()), ("dp",))
            batch_sharding = NamedSharding(local_mesh, P("dp"))

        @jax.jit
        def step_fn(params, x, y):
            def loss_fn(p):
                h = jnp.tanh(x @ p["w1"])
                pred = h @ p["w2"]
                return jnp.mean((pred - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
            return new, loss

        rng = np.random.default_rng(123 + my_rank)
        loss = None
        for step in range(start_step, args.steps):
            if (
                fs.initial_rank == args.kill_rank
                and step == args.kill_step
                and fs.iteration == 0
            ):
                print(f"[rank {fs.initial_rank}] dying at step {step}", flush=True)
                os._exit(9)
            x = jnp.asarray(rng.standard_normal((8, 16)), dtype=jnp.float32)
            y = jnp.asarray(rng.standard_normal((8, 1)), dtype=jnp.float32)
            if batch_sharding is not None:
                x = jax.device_put(x, batch_sharding)
                y = jax.device_put(y, batch_sharding)
            params, loss = step_fn(params, x, y)
            call.ping()
            import time as _time

            _time.sleep(args.step_time)  # stand-in for a real training step
            if step % args.ckpt_every == 0:
                mgr.save(step, PyTreeStateDict({"params": params}), is_async=True)
                mgr.maybe_finalize()
        mgr.maybe_finalize(blocking=True)
        mgr.close()
        return {
            "rank": fs.initial_rank,
            "iteration": fs.iteration,
            "active_world": active_world,
            "local_devices": jax.local_device_count(),
            "final_loss": float(loss) if loss is not None else None,
            "resumed_from": start_step,
        }

    try:
        result = train()
        result_q.put((rank, result))
    except BaseException as e:  # noqa: BLE001
        result_q.put((rank, {"error": repr(e)}))
        raise


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--kill-rank", type=int, default=1)
    ap.add_argument("--kill-step", type=int, default=6)
    ap.add_argument("--step-time", type=float, default=0.25)
    ap.add_argument(
        "--cpu", action="store_true",
        help="simulate: every rank process runs on virtual CPU devices (N rank "
        "processes on one host cannot share one chip)",
    )
    ap.add_argument("--ckpt-root", default=None)
    ap.add_argument(
        "--devices-per-rank", type=int, default=1,
        help="virtual devices per rank process: >1 certifies a surviving "
        "MULTI-device world re-entering after the restart",
    )
    args = ap.parse_args()
    if args.ckpt_root is None:
        args.ckpt_root = tempfile.mkdtemp(prefix="inproc-example-")

    port = free_port()
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=rank_main, args=(r, args.world, port, args, q))
        for r in range(args.world)
    ]
    for p in procs:
        p.start()
    results = {}
    import queue as qmod

    deadline = 180.0
    import time

    t0 = time.monotonic()
    while len(results) < args.world and time.monotonic() - t0 < deadline:
        try:
            rank, payload = q.get(timeout=1.0)
            results[rank] = payload
        except qmod.Empty:
            if all(not p.is_alive() for p in procs):
                break
    for p in procs:
        p.join(timeout=10)
        if p.is_alive():
            p.terminate()

    survivors = {
        r: v for r, v in results.items() if isinstance(v, dict) and "error" not in v
    }
    print("results:", results, flush=True)
    ok = bool(survivors) and all(
        v["iteration"] >= 1
        and v["resumed_from"] > 0
        and v["local_devices"] == args.devices_per_rank
        for v in survivors.values()
    )
    n_surv = len(survivors)
    print(
        f"RESTART-RESUME {'OK' if ok else 'FAILED'} "
        f"devices {args.world}x{args.devices_per_rank} -> "
        f"{n_surv}x{args.devices_per_rank}",
        flush=True,
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
