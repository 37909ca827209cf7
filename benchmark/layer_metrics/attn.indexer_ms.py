"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``attn/full/indexer`` scope: what an indexed layer's indexer does (its three
projections of the detached normed input, the norm and the rotary of its key and queries,
the index scores of every key for every query by blocks, and the divergence that teaches
it; forward, the scores made again for the backward pass, and backward); median over the
window's executions, in ms. :func:`times` makes one pass over the trace with
``layer_metrics/scope_times.py``'s join for this reader and for ``attn.select_ms``; where
the program has no such scope (a program from before indexed attention, or a
configuration without it) or there is no trace, nothing."""

import glob
import os
import re

from benchmark import harness

#: as ``scope_times.SCOPES`` writes a scope: bare in the recomputed forward and the
#: backward (``.../attn/full/indexer/...``), inside the transform in the first forward
#: (``jvp(attn/full)/indexer/...``)
SCOPES = {"indexer": re.compile(r"[/(]attn/full\)?/indexer[/)]"),
          "select": re.compile(r"[/(]attn/full\)?/select[/)]")}


def times(run) -> dict | None:
    """{scope: median seconds a step} of :data:`SCOPES`, read once a run."""
    if "indexed_scopes" in run.notes:
        return run.notes["indexed_scopes"]
    run.notes["indexed_scopes"] = None
    scope_times = harness.load_by_path("layer_metrics", "scope_times")
    files = glob.glob(os.path.join(run.workdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        return None
    from jax.profiler import ProfileData

    try:
        from tpu_resiliency.telemetry.device_profiler import device_ops, hlo_instructions
    except ImportError:  # a program from before the join
        return None
    with open(files[0], "rb") as f:
        hlo = hlo_instructions(f.read())
    steps: dict[tuple[int, int], dict] = {}
    for op in device_ops(ProfileData.from_file(files[0]), hlo):
        if scope_times.STEP_PROGRAM not in op.program or op.instruction is None \
                or op.instruction.is_container:
            continue
        step = steps.setdefault((op.plane, op.execution), dict.fromkeys(SCOPES, 0.0))
        for scope, mark in SCOPES.items():
            if mark.search(op.instruction.op_name):
                step[scope] += op.event.duration_ns * 1e-9
    medians = {scope: harness.median(step[scope] for step in steps.values()) for scope in SCOPES}
    run.say("indexed_scopes", executions=len(steps),
            **{f"{scope}_ms": value and value * 1e3 for scope, value in medians.items()})
    run.notes["indexed_scopes"] = medians
    return medians


def read(run):
    found = times(run)
    return found["indexer"] * 1e3 if found and found["indexer"] else None
