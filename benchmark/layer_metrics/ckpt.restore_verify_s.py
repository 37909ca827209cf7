"""Median over the window's restores of the seconds ``format.read_payload`` spent in
``crc32c`` over every leaf: the ``timing`` record ``ckpt.load.verify``, summed over the
leaves by the program. ``None`` where the program writes no such record."""

from benchmark import harness


def read(run):
    return harness.median(e["duration_s"] for e in harness.window_events(
        run, "timing", name="ckpt.load.verify", ok=True))
