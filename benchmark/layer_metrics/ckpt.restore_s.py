"""Median seconds from ``restore_latest`` to ``block_until_ready`` on a re-entry (read,
verify, host-to-device)."""

from benchmark import harness


def read(run):
    return harness.median(r["restore_s"] for r in run.notes.get("restores", []))
