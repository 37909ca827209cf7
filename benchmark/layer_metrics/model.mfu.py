"""Model FLOP/s utilization: the operations forward and backward need per token
(``benchmark/flops.py``) x tokens per second of this run's window / (chips x the
chip's bf16 peak from ``benchmark/peaks.json``), in %."""

from benchmark import end_to_end, flops, harness


def read(run):
    peaks = harness.read_json(harness.HERE, "peaks.json")["device_kinds"].get(run.device["kind"])
    if peaks is None:  # only a rehearsal gets here: a run refuses a device not in the table
        return None
    return flops.mfu_percent(run.cell.config, run.cell.config["batch"][1],
                             end_to_end.tokens_per_s(run), run.cell.chips,
                             peaks["bf16_flops_per_s"])
