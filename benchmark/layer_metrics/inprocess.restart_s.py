"""Median seconds of the restart engine's transition (abort ladder, health check,
barrier, reassignment): the ``inprocess.restart`` span."""

from benchmark import harness


def read(run):
    return harness.median(e["duration_s"] for e in run.events
                          if e.get("kind") == "span_end" and e.get("span") == "inprocess.restart")
