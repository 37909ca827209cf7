"""Median over the window's restores of the seconds ``jax.device_put`` of every leaf held
the host for: the ``timing`` record ``ckpt.load.place`` (the wait for the device after it
is in ``ckpt.restore_s`` only). ``None`` where the program writes no such record."""

from benchmark import harness


def read(run):
    return harness.median(e["duration_s"] for e in harness.window_events(
        run, "timing", name="ckpt.load.place", ok=True))
