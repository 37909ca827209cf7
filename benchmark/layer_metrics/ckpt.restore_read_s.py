"""Median over the window's restores of the seconds ``format.read_payload`` spent in
``f.read`` (the copy of every leaf out of the page cache): the ``timing`` record
``ckpt.load.read``, summed over the leaves by the program. ``None`` where the program
writes no such record (a commit from before the restore's phases)."""

from benchmark import harness


def read(run):
    return harness.median(e["duration_s"] for e in harness.window_events(
        run, "timing", name="ckpt.load.read", ok=True))
