"""The toolkit's share of a step: median step time of the window with the callbacks
attached against the median of the bare steps (same step and feed, no callback) that
the job ran after the traced window, in % of the bare step."""

from benchmark import harness


def read(run):
    bare = harness.median(run.notes.get("bare_step_ms", [])[1:])
    attached = harness.median(s["ms"] for s in run.window_steps())
    if bare is None or attached is None:
        return None
    return 100.0 * (attached - bare) / bare
