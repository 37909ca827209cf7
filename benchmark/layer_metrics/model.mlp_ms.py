"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``mlp/dense`` scope: the dense MLP sublayer of every layer of every pass (the
norm before it, the three SwiGLU products, the norm after it under sandwich norms;
forward, made again for the backward pass where the layer is rematerialized, and
backward); median over the window's executions, in ms
(``layer_metrics/model.exit_ms.py:times``). Nothing where the program has no such scope or
there is no trace."""

from benchmark import harness


def read(run):
    found = harness.load_by_path("layer_metrics", "model.exit_ms").times(run)
    return found["mlp"] * 1e3 if found and found["mlp"] else None
