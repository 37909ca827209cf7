"""Device time of the train step by the program's own ``jax.named_scope``s: a helper of
the readers ``model.attn_ms``, ``model.moe_ms``, ``attn.roofline`` and
``moe.experts_roofline`` (not itself a metric: no entry of ``BENCHMARK.json`` names it).

Opens the traced window's ``*.xplane.pb`` once, joins every ``XLA Ops`` event of
``jit_train_step`` to its instruction's ``op_name`` in the ``Hlo Proto`` the trace embeds
(``telemetry/device_profiler.py:device_ops``; containers skipped, their bodies' ops are
events of their own) and sums, per execution of the step, the seconds of ops whose
``op_name`` holds each scope as a path component (forward, recomputed forward and
backward alike). The
compiler's grouped-product kernels carry no ``op_name`` of JAX's (``ragged-dot-none``):
they are found by their instruction name and counted under ``moe/experts``. A fusion is
put down to the scope of its own ``op_name`` (its root's). Where the program has no
such scope or the trace no ``train_step`` program, ``of_run`` returns ``None`` and the
readers leave their metric out.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark import harness

#: scope -> how an ``op_name`` names it: a scope is a path component, bare in the
#: recomputed forward and the backward (``.../checkpoint/attn/full/core/...``) and
#: inside the transform in the first forward (``jvp(attn/full)/core/...``)
SCOPES = {
    "attn": re.compile(r"[/(]attn/(full|sliding)[/)]"),
    "attn_core": re.compile(r"[/(]attn/(full|sliding)\)?/core/"),
    "moe": re.compile(r"[/(]moe/(route|dispatch|experts|combine|shared)[/)]"),
    "moe_experts": re.compile(r"[/(]moe/experts[/)]"),
}
#: instruction names of the compiler's grouped products (``jax.lax.ragged_dot`` on a TPU)
GROUPED_PRODUCT = "ragged-dot"
STEP_PROGRAM = "train_step"


def scopes_of(op_name: str, instruction: str) -> list[str]:
    if instruction.startswith(GROUPED_PRODUCT):
        return ["moe", "moe_experts"]
    return [scope for scope, mark in SCOPES.items() if mark.search(op_name)]


def read_file(path: str) -> list[dict] | None:
    """One ``{scope: seconds}`` per execution of the step, in time order."""
    from jax.profiler import ProfileData

    try:
        from tpu_resiliency.telemetry.device_profiler import (
            device_ops, hlo_instructions, instruction_name)
    except ImportError:  # a program from before the join
        return None
    with open(path, "rb") as f:
        hlo = hlo_instructions(f.read())
    rows: dict[tuple[int, int], dict] = {}
    for op in device_ops(ProfileData.from_file(path), hlo):
        if STEP_PROGRAM not in op.program or op.instruction is None \
                or op.instruction.is_container:
            continue
        row = rows.setdefault((op.plane, op.execution), dict.fromkeys(SCOPES, 0.0))
        for scope in scopes_of(op.instruction.op_name, instruction_name(op.event.name)):
            row[scope] += op.event.duration_ns * 1e-9
    return [row for _, row in sorted(rows.items())]


def of_run(run) -> dict | None:
    """{scope: median seconds a step}, read once; ``None`` where there is no trace,
    no step in it, or no op of any scope."""
    if "scope_times" not in run.notes:
        files = glob.glob(os.path.join(run.workdir, "**", "*.xplane.pb"), recursive=True)
        rows = read_file(files[0]) if len(files) == 1 else None
        times = None
        if rows and any(any(row.values()) for row in rows):
            times = {scope: harness.median(row[scope] for row in rows) for scope in SCOPES}
            run.say("scope_times", executions=len(rows),
                    **{f"{k}_ms": v * 1e3 for k, v in times.items()})
        run.notes["scope_times"] = times
    return run.notes["scope_times"]


def ms(run, scope: str) -> float | None:
    times = of_run(run)
    return times[scope] * 1e3 if times and times[scope] else None


def roofline(run, scope: str, cost: str) -> float | None:
    """The share of its roofline of the ops of ``scope``: the family's ``cost`` function
    gives the least operations and bytes of one step, ``peaks.json`` the chip's; the
    floor is the larger of operations over the bf16 peak and bytes over the bandwidth."""
    times = of_run(run)
    peaks = harness.read_json(harness.HERE, "peaks.json")["device_kinds"].get(run.device["kind"])
    count = getattr(harness.load_family(run.cell.config), cost, None)
    if not times or not times[scope] or peaks is None or count is None:
        return None
    ops, moved = count(run.cell.config, *run.cell.config["batch"])
    floor = max(ops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / times[scope]
