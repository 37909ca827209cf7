"""The end-to-end metrics, one function each, found by the metric's name in
``BENCHMARK.json``. All are taken by the benchmark itself on the host's clock; none is
read from the program. Only a ``benchmark`` PR may add or change one."""

from __future__ import annotations

from . import harness


def tokens_per_s(run) -> float:
    """Steps completed in the window x batch x sequence / window seconds, every stall
    inside the window included; a step is complete when its loss is on the host."""
    batch, seq = run.cell.config["batch"]
    return run.completed_in_window() * batch * seq / run.seconds


def step_ms_p95(run) -> float:
    """95th percentile of the time from one step's completion to the next (feed, step,
    loss read-back and the loop's hooks between them)."""
    ms = [s["ms"] for s in run.window_steps()]
    run.say("step_ms", median=harness.median(ms), p95=harness.percentile(ms, 0.95),
            max=max(ms, default=None), samples=len(ms))
    return harness.percentile(ms, 0.95)


def save_stall_s(run) -> float:
    """Median over the saves begun in the window of the seconds the loop was blocked
    by that save (its ``ckpt_foreground_blocked`` records of both engines)."""
    stalls = [s["enqueue_s"] + s["detach_s"] for s in harness.saves(run)
              if run.in_window(s["ts"])]
    run.say("save_stalls", stalls=stalls)
    return harness.median(stalls)


def recover_s(run) -> float:
    """Median over the faults raised in the window of: exception raised -> first
    completed step after re-entry."""
    recoveries = [r["recover_s"] for r in run.notes.get("recoveries", [])]
    run.say("recoveries", recover_s=recoveries)
    return harness.median(recoveries)


def setup_s(run) -> float:
    """Process start -> window opens, less the reference's seconds."""
    return run.setup_s
