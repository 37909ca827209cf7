"""Median seconds a save begun in the window kept the loop blocked at the next step
start, pulling the state off the device before the donating step deletes it
(``ckpt_foreground_blocked{engine=detach}``)."""

from benchmark import harness


def read(run):
    return harness.median(s["detach_s"] for s in harness.saves(run) if run.in_window(s["ts"]))
