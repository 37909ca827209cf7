"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``attn/full/delta`` scope: what a delta-rule layer does beside its four large
projections (the three short convolutions, the maps of the log-decay, the write strength
and the output gate, the scaling of q and k and the heads' norm, and the chunked rule with
its scan of the state; forward, made again for the backward pass, and backward); median
over the window's executions, in ms. :func:`times` makes one pass over the trace with
``layer_metrics/scope_times.py``'s join for this reader, for ``attn.delta_state_ms`` and
for ``attn.delta_roofline``; where the program has no such scope (a program from before
the delta kind, or a configuration without it) or there is no trace, nothing."""

import glob
import os
import re

from benchmark import harness

#: as ``scope_times.SCOPES`` writes a scope: bare in the recomputed forward and the
#: backward (``.../attn/full/delta/rule/...``), inside the transform in the first forward
#: (``jvp(attn/full)/delta/rule/...``); the scan's own components (``while/body``) follow
#: the scope that is around it
SCOPES = {"delta": re.compile(r"[/(]attn/full\)?/delta[/)]"),
          "rule": re.compile(r"[/(]attn/full\)?/delta/rule[/)]"),
          "state": re.compile(r"[/(]attn/full\)?/delta/rule/state[/)]")}


def step_rows(op_names_by_step) -> dict:
    """{scope: median seconds a step} from ``{step: [(op_name, seconds), ...]}``."""
    steps = [{scope: sum(seconds for name, seconds in ops if mark.search(name))
              for scope, mark in SCOPES.items()} for ops in op_names_by_step.values()]
    return {scope: harness.median(step[scope] for step in steps) for scope in SCOPES}


def times(run) -> dict | None:
    """{scope: median seconds a step} of :data:`SCOPES`, read once a run."""
    if "delta_scopes" in run.notes:
        return run.notes["delta_scopes"]
    run.notes["delta_scopes"] = None
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
    steps: dict[tuple[int, int], list] = {}
    for op in device_ops(ProfileData.from_file(files[0]), hlo):
        if scope_times.STEP_PROGRAM not in op.program or op.instruction is None \
                or op.instruction.is_container:
            continue
        steps.setdefault((op.plane, op.execution), []).append(
            (op.instruction.op_name, op.event.duration_ns * 1e-9))
    medians = step_rows(steps) if steps else dict.fromkeys(SCOPES)
    run.say("delta_scopes", executions=len(steps),
            **{f"{scope}_ms": value and value * 1e3 for scope, value in medians.items()})
    run.notes["delta_scopes"] = medians
    return medians


def read(run):
    found = times(run)
    return found["delta"] * 1e3 if found and found["delta"] else None
