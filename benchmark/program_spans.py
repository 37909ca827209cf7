"""What the program says of its own time, read from the traced window's own file.

The program under test writes host annotations on the profiler's clock
(``tpures/<name>``: one per callback hook, one per telemetry report and its parts;
``tpu_resiliency/utils/tracing.py:annotate``), and its ``telemetry/device_profiler.py``
joins every ``XLA Ops`` event to the ``op_name`` of its instruction in the ``Hlo
Proto`` the trace file embeds, which gives the device step by forward / backward /
optimizer with the compiled step untouched. This helper opens the window's
``*.xplane.pb`` under ``run.workdir`` once, after the harness has closed the trace,
keeps what it read in ``run.notes``, and serves the five readers ``model.fwd_ms``,
``model.bwd_ms``, ``model.opt_ms``, ``loop.hooks_ms`` and ``telemetry.report_ms``. It
writes nothing into the trace directory and never calls ``run.problem``: where the
program has no such annotation or function (a commit from before them), or the trace no
``train_step`` program, a reader returns ``None`` and the line leaves the metric out.
"""

from __future__ import annotations

import dataclasses
import glob
import os

from benchmark import harness, xplane

PROGRAM_PREFIX = "tpures/"
HOOKS = (PROGRAM_PREFIX + "loop/on_step_start/", PROGRAM_PREFIX + "loop/on_step_end/")
REPORT = PROGRAM_PREFIX + "telemetry/report"
#: the program the split is taken of (``jit_train_step`` in the trace)
STEP_PROGRAM = "train_step"

Span = tuple[float, float, str]  # start_s, end_s, name


@dataclasses.dataclass
class ProgramSpans:
    phases: list[dict]  # per execution of the step: seconds by phase (device_profiler.step_phase_times)
    program: list[Span]  # the program's ``tpures/`` annotations, sorted by start
    bench: list[Span]  # the harness's ``bench/`` annotations, prefix stripped
    busy: list[tuple[float, float]]  # merged device-op intervals of the first device plane


def spans_with_prefix(data, prefix: str) -> list[Span]:
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    start = ev.start_ns * 1e-9
                    out.append((start, start + ev.duration_ns * 1e-9, ev.name))
    return sorted(out)


def busy_intervals(data) -> list[tuple[float, float]]:
    for plane in data.planes:
        if "/device:" in plane.name and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return xplane.merge([
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events])
    return []


def read_file(path: str) -> ProgramSpans:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    try:
        from tpu_resiliency.telemetry.device_profiler import hlo_instructions, step_phase_times
    except ImportError:  # a program from before the join: no split to read
        phases = []
    else:
        with open(path, "rb") as f:
            phases = step_phase_times(data, hlo_instructions(f.read()), STEP_PROGRAM)
    return ProgramSpans(phases=phases, program=spans_with_prefix(data, PROGRAM_PREFIX),
                        bench=xplane.host_spans(data), busy=busy_intervals(data))


def of_run(run) -> ProgramSpans | None:
    """The traced window's spans, read once; ``None`` where the run wrote no trace."""
    if "program_spans" not in run.notes:
        files = glob.glob(os.path.join(run.workdir, "**", "*.xplane.pb"), recursive=True)
        spans = read_file(files[0]) if len(files) == 1 else None
        run.notes["program_spans"] = spans
        if spans is not None and spans.phases:
            run.say("step_phases", **step_phases(spans))
    return run.notes["program_spans"]


def step_phases(spans: ProgramSpans) -> dict:
    """The log line beside the three metrics: medians in ms, and the shares of the
    step's device time in fusions of more than one phase and in ops without a name."""
    ms = {k: harness.median(row[k] for row in spans.phases) * 1e3
          for k in ("fwd", "bwd", "opt", "mixed", "unnamed", "module")}
    return {"executions": len(spans.phases), **{f"{k}_ms": v for k, v in ms.items()},
            "mixed_share": ms["mixed"] / ms["module"],
            "unnamed_share": ms["unnamed"] / ms["module"]}


def phase_ms(run, phase: str) -> float | None:
    """Median over the window's executions of the step of the summed device time of
    its ops of one phase, in ms."""
    spans = of_run(run)
    if spans is None or not spans.phases:
        return None
    return step_phases(spans)[f"{phase}_ms"]


def hooks_ms(spans: ProgramSpans) -> float | None:
    """Per step (the harness's ``bench/step`` and the one after it), the summed
    duration of the callbacks' ``on_step_end`` and ``on_step_start`` hooks between
    them; the median over the window's steps, in ms."""
    hooks = [s for s in spans.program if s[2].startswith(HOOKS)]
    steps = [s for s in spans.bench if s[2].startswith("step")]
    if not hooks or len(steps) < 2:
        return None
    return harness.median(
        sum(end - start for start, end, _ in hooks if before[1] <= start <= after[0]) * 1e3
        for before, after in zip(steps, steps[1:]))


def report_ms(spans: ProgramSpans) -> float | None:
    return harness.median((end - start) * 1e3 for start, end, name in spans.program
                          if name == REPORT)


def owner(gap: tuple[float, float], spans: list[Span]) -> str | None:
    """The innermost annotation that covers most of the gap: of those that overlap
    more than half of it the shortest, else the one that overlaps it most."""
    overlaps = [(min(end, gap[1]) - max(start, gap[0]), end - start, name)
                for start, end, name in spans if start < gap[1] and end > gap[0]]
    if not overlaps:
        return None
    most = [o for o in overlaps if o[0] > (gap[1] - gap[0]) / 2]
    if most:
        return min(most, key=lambda o: o[1])[2]
    return max(overlaps)[2]


def gaps_by_program_span(spans: ProgramSpans) -> dict:
    """The device's idle gaps of ``xplane.MIN_GAP_S`` or more, each put down to the
    program's annotation that owns it, beside the harness's own attribution."""
    by_harness: dict[str, float] = {}
    by_program: dict[str, float] = {}
    hooks_s = hooks_named_s = 0.0
    for (_, end), (start, _) in zip(spans.busy, spans.busy[1:]):
        seconds = start - end
        if seconds < xplane.MIN_GAP_S:
            continue
        bench = xplane.attribute((end, start), spans.bench)
        program = owner((end, start), spans.program)
        by_harness[bench] = by_harness.get(bench, 0.0) + seconds
        key = program or "none"
        by_program[key] = by_program.get(key, 0.0) + seconds
        if bench == "hooks":
            hooks_s += seconds
            hooks_named_s += seconds if program else 0.0
    return {"by_harness": by_harness, "by_program": by_program,
            "hooks_named_share": hooks_named_s / hooks_s if hooks_s else None}
