"""Median seconds of a save's background half (resolve, CRC, write) for the saves
that streamed inside the window (``timing`` event ``ckpt.save.stream``)."""

from benchmark import harness


def read(run):
    return harness.median(e["duration_s"] for e in harness.window_events(
        run, "timing", name="ckpt.save.stream", ok=True))
