"""Per step, the summed duration of the program's ``tpures/loop/on_step_start/*`` and
``tpures/loop/on_step_end/*`` annotations (one per callback and hook, host plane of the
traced window); median over the window's steps, in ms. Also prints the log line
``gaps_by_program_span``: the device's idle gaps of 1 ms or more by the ``tpures/``
annotation that owns each, beside the harness's ``hooks`` / ``step`` / ``feed``."""

from benchmark import program_spans


def read(run):
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    if spans.program and spans.busy:
        run.say("gaps_by_program_span", **program_spans.gaps_by_program_span(spans))
    return program_spans.hooks_ms(spans)
