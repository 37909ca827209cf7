"""Job kind ``save``: steady training under continuous local checkpointing.

Closed loop, driven from outside the program: ``run_training(checkpoint_every=1,
checkpoint_fn=...)`` asks the checkpoint callback for a save at the first step
boundary after the previous one finalized, so one save is outstanding at a time.

Traffic parameters: ``warm_steps``, ``warm_save_at`` (the step boundary of the one
whole save made in set-up: requested, detached under the next donating step, then
finalized blocking), ``tail_steps`` (steps after the window so that the last save's
next steps have a first pass to be compared with), ``replay_steps``.
"""

from __future__ import annotations

import os

from benchmark import harness


def run(run: harness.Run) -> None:
    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
    from tpu_resiliency.integrations import LoopContext, run_training

    traffic = run.cell.traffic
    session = harness.Session(run)
    run.reference = harness.follow_reference(run, session)
    state = session.build_state()
    ckpt_dir = os.path.join(run.workdir, "ckpt")
    manager = LocalCheckpointManager(ckpt_dir, rank=0)
    ckpt_cb = harness.checkpoint_callback(manager, local_every=1)
    ctx = LoopContext(rank=0, world_size=1)
    requested: list[int] = []
    warm = {"requested": False, "done": False}

    def checkpoint_fn(state, step: int) -> None:
        if run.t_close is not None or manager.queue.unfinalized_indices:
            if warm["requested"] and not warm["done"]:
                # set-up's save: the step just run pulled it off the device
                manager.maybe_finalize(blocking=True)
                warm["done"] = True
            return
        if run.t_open is None and (warm["requested"] or step + 1 < traffic["warm_save_at"]):
            return
        with run.annotate("save_request"):
            ckpt_cb.save_now(state, step)
        requested.append(step + 1)
        run.attempted += 1
        warm["requested"] = True

    driver = harness.StepDriver(
        run, session, ctx, tail_steps=traffic["tail_steps"],
        ready=lambda i: i >= traffic["warm_steps"] and warm["done"])
    try:
        # the callback's on_train_end finalizes the last save, blocking
        ctx = run_training(driver, state, 10 ** 9,
                           callbacks=[harness.straggler_callback(run), ckpt_cb], ctx=ctx,
                           checkpoint_every=1, checkpoint_fn=checkpoint_fn)
    finally:
        del state
        driver.finish()
    finalized = [e["iteration"] for e in run.events if e.get("kind") == "ckpt_saved"]
    run.say("saves", requested=requested, finalized=finalized, latest=manager.find_latest())
    if finalized != requested:
        run.problem(f"saves begun {requested} but finalized {finalized}")
    run.notes["state_bytes"] = session.state_bytes(ctx.state)
    ctx.state = None  # one copy of the state at a time
    again = LoopContext(rank=0, world_size=1)
    harness.restore(run, ckpt_cb, again)
    if again.state is not None:
        if again.start_step != requested[-1]:
            run.problem(f"restored step {again.start_step}, last save was {requested[-1]}")
        harness.verify_restored(run, session, ckpt_dir, again, traffic["replay_steps"])
    ckpt_cb.close()
    harness.compare_with_reference(run, run.program, run.reference,
                                   run.cell.config["limits"])
