"""Resilient MoE training over the full (dp, pp, ep) mesh with in-process restart.

Demonstrates the framework's restart engine protecting its most complex workload:
a top-k routed mixture-of-experts model (``models/moe.py``) whose layer stack is
pipelined over the ``pp`` mesh axis and whose experts are sharded over ``ep``
(``parallel/pipeline.py``). A fault is injected mid-training; the in-process
restart loop catches it, re-enters the train function, and the loop resumes from
the newest local checkpoint — the compiled pipeline (microbatch schedule,
``ppermute`` stage ring, expert all-to-alls) is simply re-jitted on re-entry.

Run (single process, 8 virtual CPU devices):

    python examples/moe_pipeline_training.py --cpu --steps 12 --fault-step 5

Prints ``RESUMED step=<n>`` after the restart and ``DONE loss=<x>`` on success.
"""

from __future__ import annotations

import argparse
import os
import tempfile

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--fault-step", type=int, default=5)
    p.add_argument("--ckpt-every", type=int, default=2)
    p.add_argument("--ckpt-root", default=None)
    p.add_argument("--n-micro", type=int, default=2)
    p.add_argument(
        "--cpu", action="store_true",
        help="simulate: run on 8 virtual CPU devices (the dp x pp x ep mesh "
        "needs at least 4 devices; without this $JAX_PLATFORMS / JAX decide)",
    )
    args = p.parse_args()

    if args.cpu:
        # Before the first jax import.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    os.environ.setdefault("RANK", "0")
    os.environ.setdefault("WORLD_SIZE", "1")
    # Standalone single-rank run: host the coordination store on an ephemeral
    # port — the fixed default can be transiently busy on a shared host
    # (concurrent jobs/CI instances), and this example needs no fixed address.
    os.environ.setdefault("TPU_RESILIENCY_STORE_PORT", "0")

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from tpu_resiliency.checkpoint import LocalCheckpointManager, PyTreeStateDict
    from tpu_resiliency.inprocess.initialize import RetryController
    from tpu_resiliency.inprocess.wrap import CallWrapper, Wrapper
    from tpu_resiliency.models import moe
    from tpu_resiliency.parallel import mesh as pmesh
    from tpu_resiliency.parallel import pipeline as pl

    ckpt_root = args.ckpt_root or tempfile.mkdtemp(prefix="moe-pp-ckpt-")
    cfg = moe.MoEConfig.tiny(dtype=jnp.float32)
    fault_armed = {"armed": True}

    @Wrapper(
        initialize=RetryController(max_iterations=4),  # a persistent fault must not loop forever
        monitor_interval=0.05,
        last_call_wait=0.1,
        # First compile of the pipelined step is tens of seconds on CPU and the
        # watchdog's auto-heartbeat cannot tick inside it.
        soft_timeout=300.0,
        hard_timeout=600.0,
        heartbeat_interval=0.2,
        heartbeat_timeout=60.0,
        barrier_timeout=600.0,
        completion_timeout=600.0,
    )
    def train(call: CallWrapper):
        n_dev = len(jax.devices())
        split = pmesh.moe_pipeline_split(n_dev)
        mesh = pmesh.build_mesh(devices=jax.devices()[:n_dev], **split)
        specs = pmesh.moe_param_specs(cfg)
        specs["layers"] = pmesh.pipeline_layer_specs(specs["layers"])
        shardings = pmesh.tree_shardings(mesh, specs)

        params = jax.device_put(moe.init_params(jax.random.PRNGKey(0), cfg), shardings)
        tokens = jax.device_put(
            jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (split["dp"] * args.n_micro * 2, 3))[:, :32],
            NamedSharding(mesh, pmesh.batch_spec()),
        )

        with mesh:
            step, init_opt = pl.make_pipelined_train_step(
                cfg, mesh, n_micro=args.n_micro, family="moe"
            )
            step_jit = jax.jit(step)

            mgr = LocalCheckpointManager(ckpt_root, rank=0)
            start = 0
            latest = mgr.find_latest()
            if latest < 0:
                opt = jax.jit(init_opt)(params)
            else:
                # Restore params AND optimizer state — resuming with fresh Adam
                # moments would silently change the training trajectory. The
                # shardings pytree mirrors the saved tree; opt leaves use default
                # placement (None) and jit re-shards them on entry.
                opt_spec = jax.tree.map(lambda _: None, jax.eval_shape(init_opt, params))
                tree, meta = mgr.load_tree(
                    latest, shardings={"params": shardings, "opt": opt_spec}
                )
                params, opt = tree["params"], tree["opt"]
                start = int(meta["iteration"]) + 1
                print(f"RESUMED step={start}", flush=True)

            loss = None
            for i in range(start, args.steps):
                if fault_armed["armed"] and i == args.fault_step and call.frozen_state.iteration == 0:
                    fault_armed["armed"] = False
                    raise RuntimeError(f"injected fault at step {i}")
                params, opt, loss = step_jit(params, opt, tokens)
                if i % args.ckpt_every == 0:
                    mgr.save(
                        i, PyTreeStateDict({"params": params, "opt": opt}), is_async=False
                    )
            mgr.maybe_finalize(blocking=True)
            mgr.close()
            return float(loss)

    final = train()
    print(f"DONE loss={final:.4f}", flush=True)


if __name__ == "__main__":
    main()
