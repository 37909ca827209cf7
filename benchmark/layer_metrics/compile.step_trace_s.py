"""Seconds of set-up the model's Python cost: over the program's ``compile`` events
recorded before the window opened whose ``fun_name`` is the family contract's
``train_step`` (``jit(train_step)``), the sum of ``trace_s + lower_s``: the trace of the
step (the pattern models unroll their layers: a scan per period is what would shrink it)
and its lowering to StableHLO. The events are read by
``layer_metrics/compile.step_load_s.py``; ``None`` where the program wrote none."""

from benchmark import harness


def read(run):
    found = harness.load_by_path("layer_metrics", "compile.step_load_s").in_setup(run)
    return found["trace_s"] + found["lower_s"] if found else None
