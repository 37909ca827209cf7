"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``diffuse/`` scopes: what block-diffusion training adds around the stack
(``diffuse/noise``: the draws of a step, a key a sequence from its own ids, the levels, the
masked positions and the noised copy; ``diffuse/loss``: the float32 log-sum-exp and the
target's logit of the noised half, the weights ``1 / t`` and the counters; forward and
backward); median over the window's executions, in ms, by ``layer_metrics/scope_times.py``'s
join of the trace's ops to their ``op_name``. Where the program has no such scope (a
program from before the objective, or a configuration with next-token loss) or there is no
trace, nothing."""

import glob
import os
import re

from benchmark import harness

#: as ``scope_times.SCOPES`` writes a scope: a path component, bare in the backward pass
#: (``.../diffuse/loss/...``), inside the transform in the forward (``jvp(diffuse/loss)/...``)
SCOPE = re.compile(r"[/(]diffuse/(noise|loss)[/)]")


def read(run):
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
    steps: dict[tuple[int, int], float] = {}
    for op in device_ops(ProfileData.from_file(files[0]), hlo):
        if scope_times.STEP_PROGRAM not in op.program or op.instruction is None \
                or op.instruction.is_container:
            continue
        steps.setdefault((op.plane, op.execution), 0.0)
        if SCOPE.search(op.instruction.op_name):
            steps[op.plane, op.execution] += op.event.duration_ns * 1e-9
    seconds = harness.median(steps.values())
    run.say("diffuse_scopes", executions=len(steps), diffuse_ms=seconds and seconds * 1e3)
    return seconds * 1e3 if seconds else None
