"""Median device time of one execution of the train step's program (``XLA Modules``
line of the device plane, program ``jit_train_step``), in ms."""

from benchmark import harness


def read(run):
    if run.trace_result is None:
        return None
    seconds = [s for name, v in run.trace_result.programs.items()
               if "train_step" in name for s in v]
    return harness.median(seconds) * 1e3 if seconds else None
