"""Per execution of ``jit_train_step``, the summed device time of its ops under the
program's ``attn/full/latent`` scope: what latent attention does around its products
(the query matrix, the down-projection to the latent and the rotary key, the latent's
norm, the up-projection to every head's keys and values, the rotary of both parts and
the assembly of q and k; forward, recomputed forward and backward); median over the
window's executions, in ms. The join of the trace's ops to their ``op_name`` is
``layer_metrics/scope_times.py``'s; where the program has no such scope (a program from
before latent attention, or a configuration without it) or there is no trace, nothing."""

import glob
import os
import re

from benchmark import harness

#: as ``scope_times.SCOPES`` writes a scope: bare in the recomputed forward and the
#: backward (``.../attn/full/latent/...``), inside the transform in the first forward
#: (``jvp(attn/full)/latent/...``)
SCOPE = re.compile(r"[/(]attn/full\)?/latent/")


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
        step = (op.plane, op.execution)
        steps.setdefault(step, 0.0)
        if SCOPE.search(op.instruction.op_name):
            steps[step] += op.event.duration_ns * 1e-9
    median = harness.median(steps.values())
    run.say("latent_scope", executions=len(steps), latent_ms=median and median * 1e3)
    return median * 1e3 if median else None
