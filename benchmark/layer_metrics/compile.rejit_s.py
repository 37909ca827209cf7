"""Median over the re-entries of: the first step after re-entry (trace, load from the
persistent compile cache, run) minus the window's median step, in seconds."""

from benchmark import harness


def read(run):
    steady = harness.median(s["ms"] for s in run.window_steps())
    first = [r["first_step_s"] for r in run.notes.get("recoveries", [])]
    if steady is None or not first:
        return None
    return harness.median(first) - steady / 1e3
