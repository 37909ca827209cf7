"""Job kind ``steady``: train with the toolkit attached; no save, no fault.

Traffic parameters: ``warm_steps`` (set-up steps before the window opens),
``profile_programs_every`` (the straggler callback's profiler windows),
``trace_seconds`` (length of the traced window in a ``--trace 1`` run), ``bare_steps``
(steps without callbacks after a traced window, for ``loop.overhead``).
"""

from __future__ import annotations

import time

from benchmark import harness


def run(run: harness.Run) -> None:
    from tpu_resiliency.integrations import LoopContext, run_training

    traffic = run.cell.traffic
    session = harness.Session(run)
    run.reference = harness.follow_reference(run, session)
    state = session.build_state()
    ctx = LoopContext(rank=0, world_size=1)
    driver = harness.StepDriver(
        run, session, ctx, ready=lambda i: i >= traffic["warm_steps"])
    try:
        ctx = run_training(driver, state, 10 ** 9,
                           callbacks=[harness.straggler_callback(run)], ctx=ctx)
    finally:
        del state
        driver.finish()
    if run.trace and traffic.get("bare_steps"):
        # the same step and feed with no callback attached, after the window
        state, ms = ctx.state, []
        for i in range(ctx.step, ctx.step + traffic["bare_steps"]):
            t0 = time.time()
            params, opt_state, loss = session.step(*state, session.tokens(i))
            state = (params, opt_state)
            float(loss)
            ms.append((time.time() - t0) * 1e3)
        run.notes["bare_step_ms"] = ms
        ctx.state = state
    harness.compare_with_reference(run, run.program, run.reference,
                                   run.cell.config["limits"])
