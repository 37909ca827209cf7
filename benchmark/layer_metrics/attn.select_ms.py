"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``attn/full/select`` scope: turning a block of index scores into its rows of
the mask (the exact top-k of every query's scores: the counting passes that find the
``topk``-th largest, the tie rule, the count of keys selected); forward, and backward only
what of it a layer does not keep; median over the window's executions, in ms. The pass
over the trace is ``layer_metrics/attn.indexer_ms.py``'s; nothing where the program has
no such scope or there is no trace."""

from benchmark import harness


def read(run):
    found = harness.load_by_path("layer_metrics", "attn.indexer_ms").times(run)
    return found["select"] * 1e3 if found and found["select"] else None
