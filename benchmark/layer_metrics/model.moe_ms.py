"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``moe/*`` scopes (route, dispatch, experts, combine, shared) and of the
compiler's grouped-product kernels; forward, recomputed forward and backward; median
over the window's executions, in ms (``layer_metrics/scope_times.py``)."""

from benchmark import harness


def read(run):
    return harness.load_by_path("layer_metrics", "scope_times").ms(run, "moe")
