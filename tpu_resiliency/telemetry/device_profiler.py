"""Per-compiled-program device timing from the XLA profiler: the CUPTI equivalent.

The reference's straggler detector feeds on CUPTI per-kernel wall times captured by a
C++ activity-buffer extension (``straggler/cupti_src/CuptiProfiler.cpp:96-203``) with a
``start/stop/get_stats/reset`` contract. Per-kernel timing does not exist under XLA —
kernels are fused into whole compiled programs — so the TPU-native signal is the
**per-XLA-module device time**: the profiler's device plane records one event per
program execution (``XLA Modules`` line) with the true on-device duration
(``device_duration_ps``), no host dispatch included. This is the deliberate semantic
change SURVEY §7 calls out ("matching CUPTI fidelity"): program-level granularity,
device-exact durations.

:class:`DeviceTimeProfiler` preserves the reference contract:

- ``start()`` / ``stop()`` bracket a capture window (run a window every Nth report
  interval, like CUPTI's ``profiling_interval`` — tracing is not free: on a v5e
  opening one takes 0.04 s and closing it 0.3 s). A loop that must not wait for
  the close calls ``stop_async()`` instead: the profiler's closer thread stops
  the session and parses the trace beside the next steps, and the next
  ``start()`` waits for it only if it is still at work;
- ``drain()`` yields the new per-program duration samples since the last drain
  (feed them to ``Detector.record_program_samples`` so programs join the scored
  telemetry matrix as ``prog/...`` signals);
- ``get_stats()`` returns per-program min/max/med/avg/std/count like the C++
  ``computeStats`` (``CuptiProfiler.cpp:44-74``); ``reset()`` clears.

Program names are stable across recompiles: the fingerprint hash suffix is stripped
(``jit_train_step(123...)`` → ``jit_train_step``). On the CPU backend, which has no
device plane, the capture reads the host trace's ``PjitFunction`` events —
host-inclusive dispatch durations, clearly a different signal, kept so the CPU tests
can exercise the whole pipeline. On a TPU backend a trace without a device plane is
an error (:class:`NoDevicePlane`), never a reason to report host times, and
``DeviceTimeProfiler.source`` tells a caller which of the two it got. Plane and line
names are the profiler's own; on jax 0.9 / libtpu 0.0.34 a v5e trace has one
``/device:TPU:0`` plane with ``XLA Modules`` and ``XLA Ops`` lines (opened by hand,
chip run PR 21; ``tests/telemetry/data/v5e_window.xplane.pb`` is that trace).

**One granularity below programs.** An ``XLA Ops`` event of such a trace is named
by its bare instruction text and has three stats (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``): no ``tf_op``, no ``hlo_op``.
The same file holds, on its ``/host:metadata`` plane, every traced program's
``Hlo Proto`` with each instruction's ``op_name`` as JAX wrote it
(``jit(train_step)/transpose(jvp())/while/body/mul``). :func:`hlo_instructions`
reads those (a short wire-format reader, no dependency beyond this file),
:func:`device_ops` joins every op event to its instruction by name, and
:func:`phase_of` reads forward / backward / optimizer from the names autodiff
itself writes — the compiled program is never touched.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import queue
import re
import shutil
import tempfile
import threading
import time
from collections import deque
from typing import Iterator, NamedTuple, Optional

import numpy as np

from tpu_resiliency.utils.logging import get_logger

log = get_logger(__name__)

_HASH_SUFFIX = re.compile(r"\((\d+)\)$")
_PJIT = re.compile(r"^PjitFunction\((.+)\)$")
_OP_ID_SUFFIX = re.compile(r"\.\d+$")
_HLO_INSTRUCTION = re.compile(r"^%([^\s=]+)\s*=")
_JIT_COMPONENT = re.compile(r"^(jit|pjit)\(.*\)$")

MAX_SAMPLES_PER_PROGRAM = 8192  # reference statsMaxLenPerKernel ring bound


def normalize_program_name(name: str) -> str:
    return _HASH_SUFFIX.sub("", name)


def op_scope_key(name: str, stats: dict) -> Optional[str]:
    """Aggregation key for one per-op trace event, or ``None`` for bookkeeping
    events. Pure so the TPU-plane mapping is testable without a TPU trace.

    Preference order:

    1. The ``tf_op`` stat — the framework op path XLA propagates from HLO
       metadata (``jax.named_scope`` contributes components). The key is the
       *scope* path: leading ``jit(...)``/``pjit(...)`` wrappers dropped, the
       trailing op component dropped, e.g. ``jit(step)/attn/dot_general`` →
       ``attn``. An unscoped op keys by its own base name.
    2. The ``hlo_op`` stat (or the event name), numeric instruction id
       stripped (``dot_general.2`` → ``dot_general``) — instruction ids are
       compile-order artifacts that would fragment signals across recompiles.
       A v5e ``XLA Ops`` event carries neither stat and is named by its whole
       HLO instruction (``%fused_median_weights.1 = (f32[...]) custom-call(...)``):
       the key is the instruction's own name, ``fused_median_weights``, unless
       :func:`extract_op_times` was given the trace's HLO and hands the
       instruction's ``op_name`` in as ``tf_op``.
    """
    if name.startswith("end: ") or "::" in name:
        return None
    tf_op = stats.get("tf_op")
    if tf_op:
        parts = [p for p in str(tf_op).split("/") if p]
        while parts and _JIT_COMPONENT.match(parts[0]):
            parts = parts[1:]
        if len(parts) >= 2:
            return "/".join(parts[:-1])
        if parts:
            return _OP_ID_SUFFIX.sub("", parts[0])
        return None
    base = str(stats.get("hlo_op") or name)
    instruction = _HLO_INSTRUCTION.match(base)
    if instruction:
        base = instruction.group(1)
    base = _OP_ID_SUFFIX.sub("", base)
    if not base or base.startswith("_"):
        return None
    return base


class NoDevicePlane(RuntimeError):
    """A trace that had to come from a device carries no device plane line."""


def _device_planes(profile_data) -> list:
    return [p for p in profile_data.planes
            if "/device:" in p.name and "CUSTOM" not in p.name]


def _device_lines(profile_data, line_name: str) -> list:
    return [
        line
        for plane in _device_planes(profile_data)
        for line in plane.lines
        if line.name == line_name
    ]


def _no_device_plane(line_name: str, profile_data) -> NoDevicePlane:
    return NoDevicePlane(
        f"no {line_name!r} line on a /device: plane; the trace has planes "
        f"{[p.name for p in profile_data.planes]}"
    )


def trace_source(profile_data) -> str:
    """``"device"`` when :func:`extract_program_times` reads true device times
    from this trace, ``"host"`` when all it has are host dispatch times."""
    return "device" if _device_lines(profile_data, "XLA Modules") else "host"


def _session_start_ns(profile_data) -> Optional[int]:
    """When the session began, on the host's wall clock (the ``Task Environment``
    plane's ``profile_start_time``); event times count from it. None where the
    trace does not say."""
    for plane in profile_data.planes:
        if plane.name == "Task Environment":
            return dict(plane.stats).get("profile_start_time")
    return None


def extract_program_times(
    profile_data, require_device: bool = False, closed_at_ns: Optional[int] = None
) -> dict[str, list[float]]:
    """Per-program device durations (seconds) from one xplane ProfileData.

    Source: device planes' ``XLA Modules`` line (true device time). Without one,
    ``require_device`` (a TPU backend) raises :class:`NoDevicePlane`; otherwise
    (the CPU tests) the host plane's ``PjitFunction`` events stand in
    (host-inclusive dispatch time). :func:`trace_source` says which it was.

    ``closed_at_ns`` is the wall-clock time (``time.time_ns()``) at which the
    session's close was requested: only executions that had ended by then are
    samples. A v5e goes on tracing for 24-35 ms after the request and records an
    execution it never saw the end of as an ordinary event, cut short where the
    trace ends (51 ms of a 101 ms program; chip run, PR 26), with nothing on the
    event to tell it by; an execution of a later step that ended in those
    milliseconds is left out with it. The trace's clock and the host's agree to
    0.1 ms there (a step's execution ends 1.1-2.9 ms before the request that
    follows its loss's read-back)."""
    out: dict[str, list[float]] = {}
    lines = _device_lines(profile_data, "XLA Modules")
    cutoff = None
    if closed_at_ns is not None and lines:
        session_start = _session_start_ns(profile_data)
        if session_start is not None:
            cutoff = closed_at_ns - session_start
    for line in lines:
        for ev in line.events:
            if cutoff is not None and ev.start_ns + ev.duration_ns > cutoff:
                continue
            name = normalize_program_name(ev.name)
            out.setdefault(name, []).append(float(ev.duration_ns) * 1e-9)
    if lines:
        return out
    if require_device:
        raise _no_device_plane("XLA Modules", profile_data)
    for plane in profile_data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name != "python":
                continue
            for ev in line.events:
                m = _PJIT.match(ev.name)
                if m:
                    name = f"pjit_{m.group(1)}"
                    out.setdefault(name, []).append(float(ev.duration_ns) * 1e-9)
    return out


def _event_stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


# --- the HLO the trace embeds: instruction name -> op_name -----------------------

#: HLO opcodes whose event only contains others (its time is its body's)
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})
#: instructions that compute nothing: they say nothing of their fusion's phase
_NO_WORK_OPCODES = frozenset(
    {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"})
PHASES = ("fwd", "bwd", "opt")


def phase_of(op_name: str) -> str:
    """Forward, backward or optimizer, from the names JAX's autodiff writes into
    every instruction's ``op_name``: ``transpose(`` marks the transposed (backward)
    computation, a recomputed forward under ``checkpoint`` included; else ``jvp(``
    the linearized forward; everything else (AdamW, loss scaling, what autodiff
    never saw, instructions the compiler added without a name) is ``opt``."""
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "opt"


@dataclasses.dataclass(frozen=True)
class HloInstruction:
    op_name: str  # metadata.op_name, "" where the compiler wrote none
    opcode: str
    #: the phases of the named, working instructions of a fusion's fused
    #: computation; more than one marks a fusion that no single phase owns
    fused_phases: frozenset = frozenset()

    @property
    def is_container(self) -> bool:
        return self.opcode in CONTAINER_OPCODES

    @property
    def phase(self) -> str:
        """:func:`phase_of` its own ``op_name``; a fusion the compiler left
        unnamed takes the phase of its fused computation where that is one."""
        if not self.op_name and len(self.fused_phases) == 1:
            return next(iter(self.fused_phases))
        return phase_of(self.op_name)


def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf) -> Iterator[tuple[int, int, object]]:
    """(field number, wire type, value) for each field of one protobuf message:
    an int for varints, a memoryview for length-delimited and fixed-width ones."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, pos = buf[pos:pos + size], pos + size
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not a protobuf message")
        yield number, wire, value


def _first(buf, number: int, default=None):
    return next((v for n, _, v in _fields(buf) if n == number), default)


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace") if value is not None else ""


def _ints(buf, number: int) -> list[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for n, wire, value in _fields(buf):
        if n != number:
            continue
        if wire == 0:
            out.append(value)
        else:
            pos = 0
            while pos < len(value):
                item, pos = _varint(value, pos)
                out.append(item)
    return out


def _module_instructions(hlo_proto) -> dict[str, HloInstruction]:
    """HloProto.hlo_module(1).computations(3).instructions(2): name(1), opcode(2),
    metadata(7).op_name(2), called_computation_ids(38); a computation's id is 5.
    Instruction names are unique in a module."""
    module = _first(hlo_proto, 1)
    if module is None:
        return {}
    raw: dict[int, list[tuple[str, str, str, list[int]]]] = {}
    for number, _, computation in _fields(module):
        if number != 3:
            continue
        rows = []
        for n, _, instruction in _fields(computation):
            if n == 2:
                metadata = _first(instruction, 7)
                rows.append((
                    _text(_first(instruction, 1)), _text(_first(instruction, 2)),
                    _text(_first(metadata, 2)) if metadata is not None else "",
                    _ints(instruction, 38),
                ))
        raw[_first(computation, 5, 0)] = rows

    def fused_phases(computation_id: int) -> frozenset:
        found = set()
        for _, opcode, op_name, called in raw.get(computation_id, ()):
            if op_name and opcode not in _NO_WORK_OPCODES:
                found.add(phase_of(op_name))
            for c in called:  # a fused reduce's own computation
                found |= fused_phases(c)
        return frozenset(found)

    return {
        name: HloInstruction(
            op_name, opcode,
            fused_phases(called[0]) if opcode == "fusion" and called else frozenset())
        for rows in raw.values() for name, opcode, op_name, called in rows
    }


def hlo_instructions(trace: bytes, known=()) -> dict[int, dict[str, HloInstruction]]:
    """{program id: {instruction name: :class:`HloInstruction`}} from the
    ``Hlo Proto`` stats of an ``.xplane.pb`` file's ``/host:metadata`` plane. The
    program id is the number in an ``XLA Modules`` event's name
    (``jit_train_step(<id>)``) and names one compiled program for good, so a
    caller that reads window after window passes the ids it has as ``known`` and
    gets only the others. Empty where the trace embeds no HLO.

    Wire format read: XSpace.planes(1); XPlane.name(2), event_metadata(4) and
    stat_metadata(5) maps (key 1, value 2); XEventMetadata.id(1), stats(5);
    XStat.metadata_id(1), bytes_value(6); XStatMetadata.name(2)."""
    out: dict[int, dict[str, HloInstruction]] = {}
    for number, _, plane in _fields(trace):
        if number != 1 or _text(_first(plane, 2)) != "/host:metadata":
            continue
        hlo_stat_ids = {
            _first(entry, 1) for n, _, entry in _fields(plane)
            if n == 5 and _text(_first(_first(entry, 2, b""), 2)) == "Hlo Proto"
        }
        for n, _, entry in _fields(plane):
            if n != 4:
                continue
            metadata = _first(entry, 2, b"")
            program_id = _first(metadata, 1, 0)
            if program_id in known:
                continue
            for m, _, stat in _fields(metadata):
                if m == 5 and _first(stat, 1) in hlo_stat_ids:
                    proto = _first(stat, 6)
                    if proto is not None:
                        out[program_id] = _module_instructions(proto)
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12`` (an ``XLA Ops`` event
    is named by its whole instruction); any other name is returned as it is."""
    m = _HLO_INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


class DeviceOp(NamedTuple):
    """One ``XLA Ops`` event joined to its program's execution and its instruction."""

    plane: int  # index among the device planes
    program: str  # the execution's name as the trace has it, ``jit_f(<id>)``
    execution: int  # index of the execution among the plane's ``XLA Modules`` events
    execution_s: float  # the execution's own device seconds
    event: object
    instruction: Optional[HloInstruction]  # None where the trace holds no HLO for it


def device_ops(profile_data, hlo: dict[int, dict[str, HloInstruction]]) -> Iterator[DeviceOp]:
    """Every ``XLA Ops`` event of the device planes joined to its program and its
    HLO instruction. An op belongs to the ``XLA Modules`` execution that holds its
    midpoint (one program runs at a time on a core); one outside every execution
    is not yielded."""
    for plane_index, plane in enumerate(_device_planes(profile_data)):
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        executions = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in lines["XLA Modules"].events)
        starts = [e[0] for e in executions]
        ids = [_HASH_SUFFIX.search(e[2]) for e in executions]
        instructions = [hlo.get(int(m.group(1)), {}) if m else {} for m in ids]
        for ev in lines["XLA Ops"].events:
            middle = ev.start_ns + ev.duration_ns / 2
            k = bisect.bisect_right(starts, middle) - 1
            if k < 0 or middle > executions[k][1]:
                continue
            start, end, program = executions[k]
            yield DeviceOp(plane_index, program, k, (end - start) * 1e-9, ev,
                           instructions[k].get(instruction_name(ev.name)))


def step_phase_times(profile_data, hlo, program: str = "train_step") -> list[dict]:
    """The device time of each execution of the step's program (the ``XLA Modules``
    events whose name holds ``program``), split by :func:`phase_of` of each op's own
    ``op_name``: one ``{"fwd", "bwd", "opt", "mixed", "unnamed", "module"}`` of
    seconds per execution, in time order. Containers are skipped (their body's ops
    are events of their own). ``mixed`` is the time of fusions whose fused
    computation holds named work of more than one phase and ``unnamed`` that of ops
    the compiler made without an ``op_name`` (both are also inside the three
    phases: :attr:`HloInstruction.phase`); ``module`` is the execution's own
    duration.
    Empty where the trace has no such program or embeds no HLO for it."""
    rows: dict[tuple[int, int], dict] = {}
    for op in device_ops(profile_data, hlo):
        instruction = op.instruction
        if program not in op.program or instruction is None or instruction.is_container:
            continue
        row = rows.setdefault((op.plane, op.execution), dict.fromkeys(
            (*PHASES, "mixed", "unnamed"), 0.0) | {"module": op.execution_s})
        seconds = op.event.duration_ns * 1e-9
        row[instruction.phase] += seconds
        if not instruction.op_name:
            row["unnamed"] += seconds
        if len(instruction.fused_phases) > 1:
            row["mixed"] += seconds
    return [row for _, row in sorted(rows.items())]


def extract_op_times(
    profile_data, require_device: bool = False,
    hlo: Optional[dict[int, dict[str, HloInstruction]]] = None,
) -> dict[str, list[float]]:
    """Per-op/scope device durations (seconds) from one xplane ProfileData —
    one granularity below :func:`extract_program_times`, the closest XLA gets
    to CUPTI's per-kernel stream (kernels themselves are fused away).

    Source: device planes' ``XLA Ops`` line (true device time, one event per
    HLO op execution). With ``hlo`` (:func:`hlo_instructions` of the same trace
    file) an event that carries no ``tf_op`` of its own, as a v5e's carry none,
    is keyed by the scope of its instruction's ``op_name``, and containers are
    left out; without it such an event keys by its instruction's bare name.
    Without a device line, ``require_device`` raises :class:`NoDevicePlane`;
    otherwise (the CPU tests) the PjRt CPU client's per-op thread line stands in
    (host-inclusive op durations — a different clock, same pipeline
    mechanics)."""
    out: dict[str, list[float]] = {}

    def add(ev, stats: dict) -> None:
        key = op_scope_key(ev.name, stats)
        if key is not None:
            out.setdefault(key, []).append(float(ev.duration_ns) * 1e-9)

    lines = _device_lines(profile_data, "XLA Ops")
    if lines and hlo:
        for op in device_ops(profile_data, hlo):
            stats, instruction = _event_stats(op.event), op.instruction
            if instruction is not None:
                if instruction.is_container:
                    continue
                if instruction.op_name:
                    stats.setdefault("tf_op", instruction.op_name)
            add(op.event, stats)
        return out
    for line in lines:
        for ev in line.events:
            add(ev, _event_stats(ev))
    if lines:
        return out
    if require_device:
        raise _no_device_plane("XLA Ops", profile_data)
    for plane in profile_data.planes:
        for line in plane.lines:
            if "XLAPjRt" in line.name:
                for ev in line.events:
                    add(ev, _event_stats(ev))
    return out


def _window_options(collect_ops: bool):
    """What a window asks the profiler to collect: on a TPU backend only what this
    module reads, the device planes (no host tracer, no Python tracer) and, for
    ``collect_ops``, the ``Hlo Proto`` its join needs. ``None``, the profiler's own
    defaults, anywhere else: the CPU fallback of :func:`extract_program_times`
    reads the Python tracer's ``PjitFunction`` events."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    options.enable_hlo_proto = collect_ops
    return options


class DeviceTimeProfiler:
    """Windowed per-program device-time capture with the CUPTI manager contract.

    Strict: a window that cannot start, wrote no trace, cannot be parsed, or —
    on a TPU backend — has no device plane RAISES. A caller that must not break
    a step on a profiling fault (``integrations/straggler_callback.py``)
    catches and counts; nothing here turns a fault into silence or into host
    times under a device name.

    **Closing a window is the expensive part** (stop the session, collect, write,
    read back, parse: 0.3 s on a v5e where opening takes 0.04 s). ``stop()`` does it
    on the caller's thread, so ``with prof:`` and every caller of ``stop()`` see a
    closed window and its faults as before. ``stop_async()`` hands it to the
    profiler's closer thread and returns: the caller's next steps run while the
    closer works, ``drain()`` yields the window's samples once it is done, and
    ``wait()`` raises what the close raised. One process holds one profiler
    session, so ``start()`` waits for a close still in flight; a window is late
    then, never skipped. The closer is one daemon thread for all of a profiler's
    deferred closes, started by the first and retired by ``stop()``, not one a
    window: libtpu's collect takes a second longer on every thread's first call
    (chip run, PR 26: 1.3 s a window from a new thread each, 0.3 s from one kept).

    **What a window's samples are**: the executions that ended between
    ``start()`` and the request to close it, by whichever of ``stop()`` and
    ``stop_async()``. The session itself lives on until the close is done, beside
    the caller's next steps after ``stop_async()``; what it records of those is
    not this window's (:func:`extract_program_times`, ``closed_at_ns``): neither
    an execution cut off where the trace ends nor a later one that ended before
    that. On a v5e the windows the loop closed this way held their own step's
    executions and nothing else (chip run, PR 26).

    Every window counts its own cost on the host in one ``profiler_window`` event,
    recorded when its close is done: ``start_s`` opening the profiler, ``wait_s``
    the seconds ``start()`` first waited for the previous window's close,
    ``stop_s`` closing the session and writing the trace, ``parse_s`` reading it
    back and folding it in, the trace's ``trace_bytes`` and its ``profile_source``
    (:attr:`source`; the event's own ``source`` is the envelope's,
    ``"telemetry"``). ``start_s + wait_s`` is what the caller of ``start()`` paid;
    after ``stop_async()`` the rest ran beside the caller."""

    def __init__(self, trace_root: Optional[str] = None, collect_ops: bool = False):
        self._root = trace_root
        self._window_dir: Optional[str] = None
        #: guards what the closer thread writes and the caller reads: the four
        #: sample stores, :attr:`source`, :attr:`windows`
        self._lock = threading.Lock()
        self._samples: dict[str, deque] = {}
        self._fresh: dict[str, list[float]] = {}
        #: opt-in per-op/scope granularity (extract_op_times) alongside the
        #: per-program default: parse cost, and on a TPU the ``Hlo Proto`` of
        #: every traced program in the trace.
        self.collect_ops = collect_ops
        self._op_samples: dict[str, deque] = {}
        self._op_fresh: dict[str, list[float]] = {}
        self._hlo: dict[int, dict[str, HloInstruction]] = {}  # of every program seen
        #: a window is open (``start()`` to the request to close it)
        self.active = False
        #: where the last parsed window's times came from (:func:`trace_source`):
        #: ``"device"`` | ``"host"``; None before the first window
        self.source: Optional[str] = None
        #: windows parsed into the stats so far
        self.windows = 0
        self._start_s = self._wait_s = 0.0
        self._closer: Optional[threading.Thread] = None
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()  # the closer's inbox
        self._idle = threading.Event()  # no deferred close is in flight
        self._idle.set()
        self._close_error: Optional[BaseException] = None

    # -- capture window ------------------------------------------------------

    def start(self) -> None:
        """Open a window, after the previous one's close where that is still in
        flight. Raises when the process-global profiler is already active
        (another window's leak, or user tracing)."""
        if self.active:
            return
        import jax

        t0 = time.perf_counter()
        self._idle.wait()  # the close's fault, if any, stays for wait()
        t_free = time.perf_counter()
        self._window_dir = tempfile.mkdtemp(prefix="devprof_", dir=self._root)
        try:
            jax.profiler.start_trace(
                self._window_dir, profiler_options=_window_options(self.collect_ops))
        except BaseException:
            shutil.rmtree(self._window_dir, ignore_errors=True)
            self._window_dir = None
            raise
        self.active = True
        self._wait_s, self._start_s = t_free - t0, time.perf_counter() - t_free

    def _take_window(self) -> tuple[str, float, float]:
        """The open window, handed to whoever closes it."""
        self.active = False
        window, self._window_dir = self._window_dir, None
        return window, self._start_s, self._wait_s

    def stop_async(self) -> None:
        """Hand the open window to the closer thread and return: it stops the
        session and folds the window's samples into the stats. ``drain()`` finds
        them when it is done; ``wait()`` raises what it raised."""
        if not self.active:
            return
        if self._closer is None:
            self._closer = threading.Thread(
                target=self._closer_loop, name="devprof-close",
                daemon=True)  # never holds a dying process
            self._closer.start()
        self._idle.clear()
        self._jobs.put(self._take_window())

    def _closer_loop(self) -> None:
        while (window := self._jobs.get()) is not None:
            try:
                self._close(*window)
            except BaseException as e:
                if self._close_error is not None:
                    log.warning("a profiler window's uncollected fault is replaced: "
                                f"{self._close_error!r}")
                self._close_error = e
            finally:
                self._idle.set()

    @property
    def closing(self) -> bool:
        """A deferred close is still in flight."""
        return not self._idle.is_set()

    def wait(self) -> None:
        """Wait for a deferred close in flight; raise, once, what the last one
        raised."""
        self._idle.wait()
        error, self._close_error = self._close_error, None
        if error is not None:
            raise error

    def stop(self) -> None:
        """End the window and fold its per-program samples into the stats, on
        the caller's thread; a deferred close in flight is waited for first.
        Afterwards no session is open and no closer thread is left."""
        self._idle.wait()
        if self._closer is not None:
            self._jobs.put(None)
            self._closer.join()
            self._closer = None
        if self.active:
            self._close(*self._take_window())
        self.wait()

    def _close(self, window_dir: str, start_s: float, wait_s: float) -> None:
        """Close one window: stop the session, read the trace back, fold it in."""
        import jax
        from jax.profiler import ProfileData

        from tpu_resiliency.utils.events import record

        t0 = t_stopped = time.perf_counter()
        closed_at_ns = time.time_ns()
        trace_bytes = 0
        try:
            jax.profiler.stop_trace()
            t_stopped = time.perf_counter()
            require_device = jax.default_backend() == "tpu"
            files = glob.glob(
                os.path.join(window_dir, "**", "*.xplane.pb"), recursive=True
            )
            if not files:
                raise RuntimeError("the profiler window wrote no xplane trace")
            for f in files:
                with open(f, "rb") as fh:
                    blob = fh.read()
                trace_bytes += len(blob)
                data = ProfileData.from_serialized_xspace(blob)
                times = extract_program_times(data, require_device, closed_at_ns)
                ops = {}
                if self.collect_ops:
                    self._hlo.update(hlo_instructions(blob, known=self._hlo))
                    ops = extract_op_times(data, require_device, self._hlo)
                with self._lock:
                    self.source = trace_source(data)
                    self._fold(times, self._samples, self._fresh)
                    self._fold(ops, self._op_samples, self._op_fresh)
            with self._lock:
                self.windows += 1
        finally:
            shutil.rmtree(window_dir, ignore_errors=True)
            record(
                "telemetry", "profiler_window", start_s=start_s, wait_s=wait_s,
                stop_s=t_stopped - t0, parse_s=time.perf_counter() - t_stopped,
                trace_bytes=trace_bytes, profile_source=self.source,
            )

    @staticmethod
    def _fold(times: dict[str, list[float]], rings: dict[str, deque],
              fresh: dict[str, list[float]]) -> None:
        for name, secs in times.items():
            rings.setdefault(name, deque(maxlen=MAX_SAMPLES_PER_PROGRAM)).extend(secs)
            fresh.setdefault(name, []).extend(secs)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- consumption ---------------------------------------------------------

    def drain(self) -> dict[str, list[float]]:
        """New samples since the last drain (seconds per execution): those of
        every window whose close is done."""
        with self._lock:
            fresh, self._fresh = self._fresh, {}
        return fresh

    def drain_ops(self) -> dict[str, list[float]]:
        """New per-op/scope samples since the last drain (collect_ops only);
        feed to ``Detector.record_op_samples``."""
        with self._lock:
            fresh, self._op_fresh = self._op_fresh, {}
        return fresh

    def _stats_over(self, samples: dict[str, deque]) -> dict[str, dict[str, float]]:
        with self._lock:
            arrays = {name: np.asarray(ring, dtype=np.float64)
                      for name, ring in samples.items() if ring}
        return {
            name: {
                "min": float(arr.min()),
                "max": float(arr.max()),
                "med": float(np.median(arr)),
                "avg": float(arr.mean()),
                "std": float(arr.std()),
                "count": int(arr.size),
            }
            for name, arr in arrays.items()
        }

    def get_stats(self) -> dict[str, dict[str, float]]:
        """Per-program stats over retained samples (reference ``computeStats``)."""
        return self._stats_over(self._samples)

    def get_op_stats(self) -> dict[str, dict[str, float]]:
        """Per-op/scope stats over retained samples (collect_ops only)."""
        return self._stats_over(self._op_samples)

    def reset(self) -> None:
        with self._lock:
            self._samples.clear()
            self._fresh.clear()
            self._op_samples.clear()
            self._op_fresh.clear()
