"""Median over the window's re-entries of what the re-jit cost by JAX's own timers: the
``compile`` event of ``jit(train_step)`` recorded inside the window (one a re-entry: the
restored incarnation's first step), ``trace_s + lower_s + backend_s`` (trace, lowering,
and the load from the persistent compile cache), in seconds. ``compile.rejit_s`` is the
same re-jit timed from outside (first step minus the median step). ``None`` where the
program records no ``compile`` event or the window holds no re-entry."""

from benchmark import harness


def read(run):
    return harness.median(
        e["trace_s"] + e["lower_s"] + e["backend_s"]
        for e in harness.window_events(run, "compile", fun_name="jit(train_step)"))
