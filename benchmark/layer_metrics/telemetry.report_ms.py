"""Median duration of the program's ``tpures/telemetry/report`` annotation (one whole
report round on the host: rings to medians, the puts and the scorer's dispatch, the
reads back), host plane of the traced window, in ms."""

from benchmark import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return program_spans.report_ms(spans) if spans is not None else None
