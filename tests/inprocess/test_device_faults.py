"""Device-fault injection kinds driving the device detectors end to end
(review round 3 item 9; reference analogue: GPU_ERROR / GPU_SLEEP in
``inprocess/tools/inject_fault.py:34-47``, which exist to test the device-health
detectors specifically):

- ``Fault.DEVICE_ERROR`` kills the XLA runtime (dead platform + dropped caches/
  backends): the liveness probe reports dead, ``JaxHealthCheck`` raises, and a
  faulted rank is EXCLUDED by the restart round's health chain rather than
  respun forever against a dead device.
- ``Fault.DEVICE_HANG`` parks the main thread in an uninterruptible device wait
  (compiled never-terminating ``while_loop``): async exceptions cannot land, so
  only the monitor process's hard-timeout ladder (progress stall → termination
  signal) gets the rank out; the survivor then shrinks the world.

Children are fresh interpreters: both faults wreck process-global jax state.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_children(child_src: str, world: int, args_fn, timeout: float = 180.0):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    import tempfile

    with tempfile.TemporaryDirectory(prefix="device-faults-") as tmp:
        script = os.path.join(tmp, "child.py")
        with open(script, "w") as f:
            f.write(child_src)
        procs = [
            subprocess.Popen(
                [sys.executable, script] + [str(a) for a in args_fn(r)],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=env,
                cwd=tmp,
            )
            for r in range(world)
        ]
        outs = {}
        try:
            for r, p in enumerate(procs):
                out, err = p.communicate(timeout=timeout)
                outs[r] = (p.returncode, out, err)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    return outs


PRIMITIVES_CHILD = textwrap.dedent(
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    from tpu_resiliency.inprocess.health_check import HealthCheckError, JaxHealthCheck
    from tpu_resiliency.inprocess.tools.inject_fault import (
        Fault,
        heal_device_error,
        inject_fault,
    )
    from tpu_resiliency.platform.device import device_liveness_probe

    assert device_liveness_probe(timeout=15.0), "device dead before injection"
    inject_fault(Fault.DEVICE_ERROR)
    assert not device_liveness_probe(timeout=15.0), "probe missed the dead runtime"
    try:
        JaxHealthCheck(timeout=5.0)(None)
        raise AssertionError("JaxHealthCheck passed on a dead runtime")
    except HealthCheckError:
        pass
    heal_device_error()
    assert device_liveness_probe(timeout=15.0), "heal did not restore the runtime"
    print("DEVICE-FAULT-PRIMITIVES OK")
    """
)


def test_device_error_primitives():
    """DEVICE_ERROR flips the liveness probe and JaxHealthCheck; heal restores."""
    outs = _run_children(PRIMITIVES_CHILD, 1, lambda r: [])
    rc, out, err = outs[0]
    assert rc == 0, f"child failed:\n{out}\n{err[-3000:]}"
    assert "DEVICE-FAULT-PRIMITIVES OK" in out


ERROR_LADDER_CHILD = textwrap.dedent(
    """
    import json, os, sys

    os.environ.update(
        RANK="0",
        WORLD_SIZE="1",
        TPU_RESILIENCY_STORE_HOST="127.0.0.1",
        TPU_RESILIENCY_STORE_PORT=sys.argv[1],
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from tpu_resiliency.inprocess import (
        CallWrapper,
        JaxHealthCheck,
        RetryController,
        Wrapper,
    )
    from tpu_resiliency.inprocess.health_check import HealthCheckError
    from tpu_resiliency.inprocess.tools.inject_fault import Fault, inject_fault

    attempts = []

    @Wrapper(
        initialize=RetryController(max_iterations=5),
        health_check=JaxHealthCheck(timeout=5.0),
        monitor_interval=0.05,
        last_call_wait=0.1,
        soft_timeout=10.0,
        hard_timeout=30.0,
        heartbeat_interval=0.2,
        heartbeat_timeout=10.0,
        barrier_timeout=30.0,
        completion_timeout=30.0,
    )
    def train(call: CallWrapper):
        attempts.append(call.iteration)
        if call.iteration == 0:
            inject_fault(Fault.DEVICE_ERROR)
        # The workload's own device use fails against the dead runtime.
        return float(jax.block_until_ready(jnp.ones((2,)).sum()))

    try:
        train()
        print("LADDER-RESULT " + json.dumps({"outcome": "completed (BAD)"}))
    except HealthCheckError as e:
        print(
            "LADDER-RESULT "
            + json.dumps({"outcome": "health_excluded", "attempts": attempts})
        )
    """
)


def test_device_error_excludes_rank_via_health_check():
    """Full escalation: device dies mid-iteration → fn fault → restart round's
    JaxHealthCheck finds the runtime dead → rank excluded (HealthCheckError),
    NOT respun forever against a dead device."""
    outs = _run_children(ERROR_LADDER_CHILD, 1, lambda r: [free_port()])
    rc, out, err = outs[0]
    line = [ln for ln in out.splitlines() if ln.startswith("LADDER-RESULT ")]
    assert line, f"no result line:\n{out}\n{err[-3000:]}"
    payload = json.loads(line[0][len("LADDER-RESULT "):])
    assert payload["outcome"] == "health_excluded", payload
    # One real attempt; the health check stopped iteration 1 from re-entering.
    assert payload["attempts"] == [0], payload


HANG_CHILD = textwrap.dedent(
    """
    import json, os, sys, time

    rank = sys.argv[1]
    os.environ.update(
        RANK=rank,
        WORLD_SIZE="2",
        TPU_RESILIENCY_STORE_HOST="127.0.0.1",
        TPU_RESILIENCY_STORE_PORT=sys.argv[2],
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from tpu_resiliency.inprocess import CallWrapper, RetryController, Wrapper
    from tpu_resiliency.inprocess.tools.inject_fault import Fault, inject_fault

    @Wrapper(
        initialize=RetryController(max_iterations=4),
        monitor_interval=0.1,
        last_call_wait=0.1,
        soft_timeout=1.5,
        hard_timeout=4.0,
        heartbeat_interval=0.2,
        heartbeat_timeout=15.0,
        barrier_timeout=60.0,
        completion_timeout=60.0,
    )
    def train(call: CallWrapper):
        fs = call.frozen_state
        for _ in range(3):
            jax.block_until_ready(jnp.ones((2,)) + 1)
            call.ping()
        if call.iteration == 0 and fs.initial_rank == 1:
            inject_fault(Fault.DEVICE_HANG)  # never returns: pings stop here
        if call.iteration == 0:
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                time.sleep(0.05)
            raise TimeoutError("restart never delivered")
        return {"iteration": call.iteration, "world": fs.active_world_size}

    result = train()
    print("HANG-RESULT " + json.dumps({"rank": rank, "result": result}), flush=True)
    """
)


def test_device_hang_killed_by_monitor_hard_timeout():
    """A rank wedged in an uninterruptible device wait stops reporting progress;
    its monitor PROCESS escalates (soft → hard → termination signal), and the
    survivor re-enters at world 1 — the only ladder that works when async
    exceptions cannot be delivered."""
    port = free_port()
    outs = _run_children(HANG_CHILD, 2, lambda r: [r, port], timeout=240.0)
    rc0, out0, err0 = outs[0]
    rc1, out1, err1 = outs[1]
    # The hung rank was killed by a signal (SIGTERM by default), not a clean exit.
    assert rc1 != 0, f"hung rank exited cleanly:\n{out1}\n{err1[-2000:]}"
    assert "HANG-RESULT" not in out1
    assert rc0 == 0, f"survivor failed:\n{out0}\n{err0[-3000:]}"
    line = [ln for ln in out0.splitlines() if ln.startswith("HANG-RESULT ")][0]
    payload = json.loads(line[len("HANG-RESULT "):])
    assert payload["result"] == {"iteration": 1, "world": 1}, payload
