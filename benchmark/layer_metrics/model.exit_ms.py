"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``exit`` scope: what closes each pass of a looped decoder (the final norm, the
head's product, the float32 log-sum-exp and the target's logit, the exit gate) and the
mixture of the exits' losses; forward, made again for the backward pass (each exit is a
rematerialized unit) and backward; median over the window's executions, in ms.
:func:`times` makes one pass over the trace with ``layer_metrics/scope_times.py``'s join
for this reader and for ``model.mlp_ms``; where the program has no such scope (a program
from before the loop, or a configuration with one exit) or there is no trace, nothing."""

import glob
import os
import re

from benchmark import harness

#: as ``scope_times.SCOPES`` writes a scope: a path component, bare in the recomputed
#: forward and the backward (``.../exit/...``), inside the transform in the first forward
#: (``jvp(exit)/...``); the loops' own components (``while/body``) stand before it
SCOPES = {"exit": re.compile(r"[/(]exit[/)]"),
          "mlp": re.compile(r"[/(]mlp/dense[/)]")}


def step_rows(op_names_by_step) -> dict:
    """{scope: median seconds a step} from ``{step: [(op_name, seconds), ...]}``."""
    steps = [{scope: sum(seconds for name, seconds in ops if mark.search(name))
              for scope, mark in SCOPES.items()} for ops in op_names_by_step.values()]
    return {scope: harness.median(step[scope] for step in steps) for scope in SCOPES}


def times(run) -> dict | None:
    """{scope: median seconds a step} of :data:`SCOPES`, read once a run."""
    if "loop_scopes" in run.notes:
        return run.notes["loop_scopes"]
    run.notes["loop_scopes"] = None
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
    run.say("loop_scopes", executions=len(steps),
            **{f"{scope}_ms": value and value * 1e3 for scope, value in medians.items()})
    run.notes["loop_scopes"] = medians
    return medians


def read(run):
    found = times(run)
    return found["exit"] * 1e3 if found and found["exit"] else None
