"""Per execution of ``jit_train_step``, the summed device time of its ``XLA Ops`` of
phase ``opt`` (AdamW and what autodiff does not touch, ops the compiler made without a name included); median
over the window's executions, in ms. The phase is read from the ``Hlo Proto`` the trace
embeds (``benchmark/program_spans.py``); containers (``while``, ``conditional``,
``call``) are skipped."""

from benchmark import program_spans


def read(run):
    return program_spans.phase_ms(run, "opt")
