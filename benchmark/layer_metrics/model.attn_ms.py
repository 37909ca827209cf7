"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``attn/<kind>`` scopes (norm, projections, rotary, the products, gate and
output matrix; forward, recomputed forward and backward); median over the window's
executions, in ms (``layer_metrics/scope_times.py``)."""

from benchmark import harness


def read(run):
    return harness.load_by_path("layer_metrics", "scope_times").ms(run, "attn")
