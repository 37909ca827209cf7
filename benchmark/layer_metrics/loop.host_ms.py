"""What the host adds to a step: median completion-to-completion step time of the
window minus the step program's median device time, in ms."""

from benchmark import harness


def read(run):
    device_ms = harness.load_by_path("layer_metrics", "model.step_device_ms").read(run)
    wall_ms = harness.median(s["ms"] for s in run.window_steps())
    if device_ms is None or wall_ms is None:
        return None
    return wall_ms - device_ms
