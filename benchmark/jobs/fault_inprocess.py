"""Job kind ``fault_inprocess``: recovery from an exception inside the step function,
by the in-process restart engine, in one process.

Set-up trains ``warm_steps`` steps, makes one blocking save there, and goes through one
whole fault and recovery, so that every program of the recovery path is in the compile
cache before the window opens (at the re-entered incarnation's second step). In the
window an exception is raised inside the step function after every ``steps_per_fault``
steps an incarnation completed; ``inprocess.Wrapper`` (abort ladder and health check as
``chip_smoke.py:phase_inprocess`` wires them) re-enters the train function, which
restores the save, re-jits the step and trains on. No save is made in the window.
"""

from __future__ import annotations

import os
import time

from benchmark import harness


class InjectedFault(RuntimeError):
    pass


def run(run: harness.Run) -> None:
    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
    from tpu_resiliency.inprocess import (
        AbortCompilationCache,
        AbortJaxDistributed,
        CallWrapper,
        Compose,
        JaxHealthCheck,
        Wrapper,
    )
    from tpu_resiliency.integrations import LoopContext, run_training

    traffic = run.cell.traffic
    warm, per_fault = traffic["warm_steps"], traffic["steps_per_fault"]
    session = harness.Session(run)
    run.reference = harness.follow_reference(run, session)
    ckpt_dir = os.path.join(run.workdir, "ckpt")
    recoveries = run.notes.setdefault("recoveries", [])
    restores = run.notes.setdefault("restores", [])
    fault = {"t_raise": None, "span": None}

    @Wrapper(
        abort=Compose(AbortJaxDistributed(), AbortCompilationCache()),
        health_check=JaxHealthCheck(timeout=60.0),
        soft_timeout=240.0, hard_timeout=270.0, barrier_timeout=300.0,
        completion_timeout=300.0, heartbeat_timeout=120.0, store_port=0,
    )
    def train(call: CallWrapper):
        incarnation = call.iteration
        if fault["span"] is not None:
            fault["span"].__exit__(None, None, None)
            fault["span"] = None
        manager = LocalCheckpointManager(ckpt_dir, rank=0)
        ckpt_cb = harness.checkpoint_callback(manager, local_every=warm)
        ctx = LoopContext(rank=0, world_size=1)
        if incarnation == 0:
            state = session.build_state()
        else:
            # Restore BEFORE anything else is placed: one copy of the state at a time.
            session.new_step()
            restored = harness.restore(run, ckpt_cb, ctx)
            restores.append({**restored, "ts": time.time()})
            state, ctx.state = ctx.state, None
        done = {"n": 0}

        def on_completed(i: int) -> None:
            done["n"] += 1
            if fault["t_raise"] is not None:
                step = run.steps[-1]
                recoveries.append({"recover_s": step["t1"] - fault["t_raise"],
                                   "first_step_s": step["t1"] - step["t0"],
                                   "incarnation": incarnation})
                fault["t_raise"] = None
            if incarnation == 0 and i == warm:
                raise InjectedFault(f"set-up's fault, after step {i}")
            if run.t_open is not None and run.t_close is None and done["n"] % per_fault == 0:
                run.attempted += 1
                fault["t_raise"] = time.time()
                fault["span"] = run.annotate("restart")
                fault["span"].__enter__()
                raise InjectedFault(f"injected after step {i}")

        def checkpoint_fn(state, step: int) -> None:
            if incarnation == 0 and step + 1 == warm:
                ckpt_cb.save_now(state, step)
                manager.maybe_finalize(blocking=True)

        driver = harness.StepDriver(run, session, ctx, incarnation=incarnation,
                                    ready=lambda i: incarnation >= 1 and done["n"] >= 1,
                                    on_completed=on_completed)
        try:
            ctx = run_training(driver, state, 10 ** 9,
                               callbacks=[harness.straggler_callback(run), ckpt_cb], ctx=ctx,
                               checkpoint_every=1, checkpoint_fn=checkpoint_fn)
        finally:
            del state
            driver.finish()
            if run.t_close is None:
                ckpt_cb.close()
        # the window has closed: read the checkpoint back once more and prove it
        run.notes["state_bytes"] = session.state_bytes(ctx.state)
        ctx.state = None
        again = LoopContext(rank=0, world_size=1)
        harness.restore(run, ckpt_cb, again)
        if again.state is not None:
            harness.verify_restored(run, session, ckpt_dir, again, traffic["replay_steps"])
        ckpt_cb.close()
        return incarnation

    os.environ[harness.RUN_TOKEN_ENV] = str(os.getpid())
    try:
        last = train()
    finally:
        killed = harness.wait_for_descendants()
    run.say("incarnations", last=last, faults=len(recoveries) + (fault["t_raise"] is not None),
            restores=restores, monitor_killed=killed)
    if killed:
        run.problem(f"processes {killed} outlived the wrapper and were killed")
    if fault["t_raise"] is not None:
        run.problem("a fault raised in the window was never recovered from")
    # every re-entry's losses equal the first pass's (same seed, program and chip: exact)
    first = harness.first_pass_losses(run)
    for s in run.steps:
        if s["loss"] != first[s["i"]]:
            run.problem(f"incarnation {s['incarnation']} step {s['i']}: loss {s['loss']} "
                        f"!= first pass {first[s['i']]}")
    harness.compare_with_reference(run, run.program, run.reference,
                                   run.cell.config["limits"])
