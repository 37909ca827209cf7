"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``attn/full/delta/rule/state`` scope: the scan that carries a delta-rule layer's
state from chunk to chunk, forward and backward, one chunk after another (128 in a row at
8,192 tokens and chunks of 64): the part of the rule that no width of the chip shortens,
since a chunk's step waits for the one before it; median over the window's executions, in
ms (``layer_metrics/attn.delta_ms.py:times``). Nothing where the program has no such scope
or there is no trace."""

from benchmark import harness


def read(run):
    found = harness.load_by_path("layer_metrics", "attn.delta_ms").times(run)
    return found["state"] * 1e3 if found and found["state"] else None
