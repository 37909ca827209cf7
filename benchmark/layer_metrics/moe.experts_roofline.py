"""The grouped expert products' share of their roofline: the larger of operations over
the chip's bf16 peak and bytes over its HBM bandwidth (``peaks.json``) for the three
SwiGLU products of the pairs that land on the experts held, forward and backward, no
recomputation (``families/laguna.py:expert_products_cost``), over the device time of the
ops under ``moe/experts`` and of the compiler's grouped-product kernels in one step,
in %."""

from benchmark import harness


def read(run):
    return harness.load_by_path("layer_metrics", "scope_times").roofline(
        run, "moe_experts", "expert_products_cost")
