"""The attention products' share of their roofline: the larger of operations over the
chip's bf16 peak and bytes over its HBM bandwidth (``peaks.json``), for the QK^T and PV
products of every layer, forward and backward, at the least the algorithm needs (the band
of one window on sliding layers, the causal half on full ones, no recomputation:
``families/laguna.py:attention_core_cost``), over the device time of the ops under
``attn/<kind>/core`` in one step, in %."""

from benchmark import harness


def read(run):
    return harness.load_by_path("layer_metrics", "scope_times").roofline(
        run, "attn_core", "attention_core_cost")
