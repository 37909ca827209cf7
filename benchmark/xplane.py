"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

One place, kept with the benchmark, so that every PR computes the same number in the
same way. Checked against the small recorded trace beside it
(``tests/test_xplane.py``, ``tests/data/*.xplane.pb``).

What a v5e trace holds (jax 0.9, libtpu 0.0.34): one plane ``/device:TPU:<n>`` per
chip with the lines ``XLA Modules`` (one event per program execution) and ``XLA Ops``
(one per HLO instruction executed, named by the whole instruction text), and a
``/host:CPU`` plane whose thread lines carry the host's ``TraceAnnotation`` spans
(the harness's are named ``bench/<what>``). Times are nanoseconds on one clock for
host and device, equal to within a millisecond or so.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

_HASH_SUFFIX = re.compile(r"\(\d+\)$")
_INSTRUCTION = re.compile(r"^%([^\s=]+)\s*=")
_ID_SUFFIX = re.compile(r"\.\d+$")
HOST_PREFIX = "bench/"
#: an idle gap shorter than this is not attributed
MIN_GAP_S = 1e-3


def program_name(name: str) -> str:
    """``jit_train_step(123...)`` -> ``jit_train_step``."""
    return _HASH_SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: the instruction's own
    name without the numeric id, which changes from compile to compile."""
    m = _INSTRUCTION.match(name)
    return _ID_SUFFIX.sub("", m.group(1) if m else name)


_RESULT = re.compile(r"=\s*(\([^){]{0,60}|[a-z0-9]+\[[^\]]*\])")
#: instructions that only contain others (their time is their body's)
CONTAINERS = ("while", "conditional", "call")


def op_detail(name: str) -> str:
    """``%fusion.12 = f32[2,4096,14336]{2,1,0} fusion(...)`` -> ``fusion
    f32[2,4096,14336]``: what the breakdown lists, since XLA's generic names say
    nothing without the shape they produce."""
    m = _RESULT.search(name)
    return f"{op_name(name)} {m.group(1)}" if m else op_name(name)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The union of [start, end) intervals, sorted and disjoint."""
    out: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float  # first to last device event (see reduce)
    planes: int  # device planes found
    busy_s: float  # union of device-op intervals, averaged over the planes
    programs: dict[str, list[float]]  # program -> seconds of each execution
    ops: dict[str, list[float]]  # op -> seconds of each execution (all planes)
    gaps: list[tuple[str, float]]  # (what the host was doing, seconds), plane 0
    op_text: dict[str, str] = dataclasses.field(default_factory=dict)  # op -> one full text
    op_detail_s: dict[str, float] = dataclasses.field(default_factory=dict)  # name+shape -> s

    def kernels(self) -> dict[str, list[float]]:
        """The ops that are custom calls (Pallas kernels among them)."""
        return {k: v for k, v in self.ops.items() if "custom-call(" in self.op_text.get(k, "")}

    def breakdown(self) -> dict:
        ops = sorted(self.op_detail_s.items(), key=lambda kv: -kv[1])
        by_host: dict[str, float] = {}
        for what, seconds in self.gaps:
            by_host[what] = by_host.get(what, 0.0) + seconds
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:10]],
                "idle_gaps": [[k, v] for k, v in gaps[:10]]}


def _device_planes(data) -> list:
    return [p for p in data.planes if "/device:" in p.name and "CUSTOM" not in p.name]


def _line(plane, name: str):
    return next((line for line in plane.lines if line.name == name), None)


def host_spans(data) -> list[tuple[float, float, str]]:
    """(start_s, end_s, name) of the harness's host annotations, prefix stripped."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    start = ev.start_ns * 1e-9
                    out.append((start, start + ev.duration_ns * 1e-9, ev.name[len(HOST_PREFIX):]))
    return sorted(out)


def attribute(gap: tuple[float, float], spans: list[tuple[float, float, str]]) -> str:
    """The host annotation that covers most of the gap; ``unattributed`` if none
    overlaps it."""
    best, best_overlap = "unattributed", 0.0
    for start, end, name in spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(data, window_s: float) -> Reduced:
    """``data``: a ``jax.profiler.ProfileData``; ``window_s``: the host's seconds
    between starting and stopping the profiler. Raises where the trace has no device
    plane or no operation ran on it.

    The window reported is the extent of the device plane's events, never more than
    the host's: the first traced step can be held up for seconds while the profiler
    sets itself up (2.6 s of a 5.1 s window, chip run PR 23), which is the tracer's
    idle time, not the program's."""
    planes = _device_planes(data)
    if not planes:
        raise RuntimeError(f"no device plane in the trace: {[p.name for p in data.planes]}")
    programs: dict[str, list[float]] = {}
    ops: dict[str, list[float]] = {}
    op_text: dict[str, str] = {}
    op_detail_s: dict[str, float] = {}
    busy, first_busy, extents = [], None, []
    for plane in planes:
        modules, op_line = _line(plane, "XLA Modules"), _line(plane, "XLA Ops")
        for ev in (modules.events if modules is not None else ()):
            programs.setdefault(program_name(ev.name), []).append(ev.duration_ns * 1e-9)
        intervals = []
        for ev in (op_line.events if op_line is not None else ()):
            start = ev.start_ns * 1e-9
            intervals.append((start, start + ev.duration_ns * 1e-9))
            ops.setdefault(op_name(ev.name), []).append(ev.duration_ns * 1e-9)
            op_text.setdefault(op_name(ev.name), ev.name)
            if not op_name(ev.name).startswith(CONTAINERS):
                detail = op_detail(ev.name)
                op_detail_s[detail] = op_detail_s.get(detail, 0.0) + ev.duration_ns * 1e-9
        if not intervals and modules is not None:  # no op line: programs stand in
            intervals = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                         for ev in modules.events]
        merged = merge(intervals)
        busy.append(sum(end - start for start, end in merged))
        if merged:
            extents.append(merged[-1][1] - merged[0][0])
        if first_busy is None:
            first_busy = merged
    if not any(busy):
        raise RuntimeError("no operation ran on the device inside the traced window")
    spans = host_spans(data)
    gaps = []
    for (_, end), (start, _) in zip(first_busy, first_busy[1:]):
        if start - end >= MIN_GAP_S:
            gaps.append((attribute((end, start), spans), start - end))
    return Reduced(window_s=min(window_s, max(extents)), planes=len(planes),
                   busy_s=sum(busy) / len(busy),
                   programs=programs, ops=ops, gaps=gaps, op_text=op_text, op_detail_s=op_detail_s)


def reduce_dir(trace_dir: str, window_s: float) -> Reduced:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane trace under {trace_dir}, found {files}")
    return reduce(ProfileData.from_file(files[0]), window_s)
