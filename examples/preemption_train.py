"""Preemption-synchronized final saves across a multi-controller job.

N rank processes train under ``jax.distributed`` (recoverable client,
``platform/distributed.py``); mid-run ONE rank receives the preemption notice
(SIGTERM — what a TPU maintenance event or spot reclaim delivers). The
coordination service broadcasts it, every rank observes the SAME agreed step,
saves that step through its LocalCheckpointManager, and stops cleanly with a
coordinator-last teardown. Re-running resumes from the synchronized step.

No reference analogue — this is TPU-first lifecycle the reference lacks.

Run (CPU simulation, 2 ranks; the parent SIGTERMs rank 1 after ~3 s):

    python examples/preemption_train.py --cpu --world 2
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

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


CHILD = textwrap.dedent(
    """
    import json, os, sys, time

    rank = int(sys.argv[1]); world = int(sys.argv[2])
    jd_port = sys.argv[3]; ckpt_root = sys.argv[4]
    import jax

    from tpu_resiliency.platform import distributed as jdist

    jdist.initialize(
        f"127.0.0.1:{jd_port}", num_processes=world, process_id=rank,
        heartbeat_timeout=10.0,
    )
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_resiliency.checkpoint import LocalCheckpointManager, PyTreeStateDict
    from tpu_resiliency.integrations import PreemptionCheckpointCallback
    from tpu_resiliency.integrations.loop import LoopContext, run_training

    print(f"READY {rank}", flush=True)
    mgr = LocalCheckpointManager(ckpt_root, rank=rank)

    # The train state lives SHARDED on this rank's device mesh (the parent
    # exports 2 virtual devices per rank): the synchronized save captures a
    # mesh-sharded array, not a host scalar.
    local_mesh = Mesh(np.asarray(jax.local_devices()), ("dp",))
    shard = NamedSharding(local_mesh, P("dp"))

    def save(state, step):
        mgr.save(step, PyTreeStateDict({"w": state["w"]}), is_async=False)
        print(f"[rank {rank}] preemption save @ step {step}", flush=True)

    cb = PreemptionCheckpointCallback(on_preemption=save)

    @jax.jit
    def advance(w):
        return w + 1.0

    def step_fn(state, step):
        time.sleep(0.05)  # stand-in for a real train step
        return {"w": advance(state["w"])}

    ctx = LoopContext(rank=rank, world_size=world)
    ctx.state = {"w": jax.device_put(jnp.zeros((4, 2)), shard)}
    latest = mgr.find_latest()
    if latest >= 0:
        hollow, tensors, meta = mgr.load(latest)
        ctx.state = {"w": jax.device_put(jnp.asarray(tensors[0]), shard)}
        ctx.start_step = latest + 1
        print(f"[rank {rank}] resumed from step {ctx.start_step}", flush=True)
    ctx = run_training(step_fn, ctx.state, num_steps=400, callbacks=[cb], ctx=ctx)
    jdist.shutdown_graceful(rank, grace=3.0)  # coordinator-last teardown
    mgr.close()
    print(
        "PREEMPT " + json.dumps({"rank": rank, "stopped_at": ctx.step,
                                 "saved": cb.preempted_at}),
        flush=True,
    )
    """
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", type=int, default=2)
    ap.add_argument("--ckpt-root", default=None)
    ap.add_argument(
        "--cpu", action="store_true",
        help="simulate: every rank process runs on 2 virtual CPU devices (N "
        "rank processes on one host cannot share one chip)",
    )
    args = ap.parse_args()
    ckpt_root = args.ckpt_root or tempfile.mkdtemp(prefix="preempt-example-")
    print(f"[parent] checkpoints in {ckpt_root} (pass --ckpt-root here to resume)")
    jd_port = free_port()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child_env = {
        **os.environ,
        **(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            }
            if args.cpu else {}
        ),
        # uninstalled checkouts: children run from a temp dir
        "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }

    with tempfile.TemporaryDirectory(prefix="preempt-src-") as d:
        script = os.path.join(d, "child.py")
        with open(script, "w") as f:
            f.write(CHILD)
        import threading

        procs = []
        outputs: list[list[str]] = []
        readers: list[threading.Thread] = []
        for r in range(args.world):
            p = subprocess.Popen(
                [sys.executable, script, str(r), str(args.world), str(jd_port), ckpt_root],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                env=child_env,
            )
            buf: list[str] = []
            t = threading.Thread(target=lambda p=p, b=buf: b.extend(p.stdout),
                                 daemon=True)
            t.start()
            procs.append(p)
            outputs.append(buf)
            readers.append(t)
        # Deliver the notice only once every rank is PAST jdist.initialize (the
        # preemption handler exists) — a SIGTERM before that just kills the rank.
        deadline = time.monotonic() + 120.0
        ready = False
        while time.monotonic() < deadline:
            ready = all(any(ln.startswith("READY") for ln in b) for b in outputs)
            if ready or any(p.poll() is not None for p in procs):
                break
            time.sleep(0.2)
        if not ready:
            # Never deliver the notice before the handler exists: a pre-READY
            # SIGTERM just kills the rank.
            for r, p in enumerate(procs):
                state = p.returncode if p.poll() is not None else "hung in startup"
                print(f"[parent] rank {r} not READY ({state}):")
                print("".join(outputs[r])[-1500:])
                if p.poll() is None:
                    p.kill()
            return 1
        time.sleep(2.0)  # everyone stepping
        print("[parent] delivering preemption notice (SIGTERM) to rank 1")
        procs[min(1, args.world - 1)].send_signal(signal.SIGTERM)
        saved_steps = set()
        ok = True
        for r, p in enumerate(procs):
            try:
                p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                ok = False
            readers[r].join(5.0)  # drain the tail before parsing
            out = "".join(outputs[r])
            got = False
            for ln in out.splitlines():
                if ln.startswith("PREEMPT "):
                    payload = json.loads(ln[len("PREEMPT "):])
                    saved_steps.add(payload["saved"])
                    print(f"[parent] rank {r}: {payload}")
                    got = True
            if not got or p.returncode != 0:
                print(f"[parent] rank {r} FAILED (rc={p.returncode}):")
                print(out[-1500:])
            ok = ok and got and p.returncode == 0
    ok = ok and len(saved_steps) == 1 and None not in saved_steps
    print(
        f"PREEMPTION-SYNC {'OK' if ok else 'FAILED'}: "
        f"all ranks saved step {saved_steps}"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
