"""Per execution of ``jit_train_step``, the summed device time of its ``XLA Ops`` of
phase ``fwd`` (the linearized forward: ``op_name`` holds ``jvp(`` and no ``transpose(``); median
over the window's executions, in ms. The phase is read from the ``Hlo Proto`` the trace
embeds (``benchmark/program_spans.py``); containers (``while``, ``conditional``,
``call``) are skipped."""

from benchmark import program_spans


def read(run):
    return program_spans.phase_ms(run, "fwd")
