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
  interval, like CUPTI's ``profiling_interval`` — tracing is not free);
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
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import deque
from typing import Optional

import numpy as np

_HASH_SUFFIX = re.compile(r"\(\d+\)$")
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
       the key is the instruction's own name, ``fused_median_weights``.
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


def _device_lines(profile_data, line_name: str) -> list:
    return [
        line
        for plane in profile_data.planes
        if "/device:" in plane.name and "CUSTOM" not in plane.name
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


def extract_program_times(
    profile_data, require_device: bool = False
) -> dict[str, list[float]]:
    """Per-program device durations (seconds) from one xplane ProfileData.

    Source: device planes' ``XLA Modules`` line (true device time). Without one,
    ``require_device`` (a TPU backend) raises :class:`NoDevicePlane`; otherwise
    (the CPU tests) the host plane's ``PjitFunction`` events stand in
    (host-inclusive dispatch time). :func:`trace_source` says which it was.
    """
    out: dict[str, list[float]] = {}
    lines = _device_lines(profile_data, "XLA Modules")
    for line in lines:
        for ev in line.events:
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


def extract_op_times(
    profile_data, require_device: bool = False
) -> dict[str, list[float]]:
    """Per-op/scope device durations (seconds) from one xplane ProfileData —
    one granularity below :func:`extract_program_times`, the closest XLA gets
    to CUPTI's per-kernel stream (kernels themselves are fused away).

    Source: device planes' ``XLA Ops`` line (true device time, one event per
    HLO op execution, ``tf_op`` scope attribution when XLA carries it).
    Without one, ``require_device`` raises :class:`NoDevicePlane`; otherwise
    (the CPU tests) the PjRt CPU client's per-op thread line stands in
    (host-inclusive op durations — a different clock, same pipeline
    mechanics)."""
    out: dict[str, list[float]] = {}
    lines = _device_lines(profile_data, "XLA Ops")
    for line in lines:
        for ev in line.events:
            key = op_scope_key(ev.name, _event_stats(ev))
            if key is not None:
                out.setdefault(key, []).append(float(ev.duration_ns) * 1e-9)
    if lines:
        return out
    if require_device:
        raise _no_device_plane("XLA Ops", profile_data)
    for plane in profile_data.planes:
        for line in plane.lines:
            if "XLAPjRt" not in line.name:
                continue
            for ev in line.events:
                key = op_scope_key(ev.name, _event_stats(ev))
                if key is not None:
                    out.setdefault(key, []).append(float(ev.duration_ns) * 1e-9)
    return out


class DeviceTimeProfiler:
    """Windowed per-program device-time capture with the CUPTI manager contract.

    Strict: a window that cannot start, wrote no trace, cannot be parsed, or —
    on a TPU backend — has no device plane RAISES. A caller that must not break
    a step on a profiling fault (``integrations/straggler_callback.py``)
    catches and counts; nothing here turns a fault into silence or into host
    times under a device name."""

    def __init__(self, trace_root: Optional[str] = None, collect_ops: bool = False):
        self._root = trace_root
        self._window_dir: Optional[str] = None
        self._samples: dict[str, deque] = {}
        self._fresh: dict[str, list[float]] = {}
        #: opt-in per-op/scope granularity (extract_op_times) alongside the
        #: per-program default — parse cost only, no extra tracing overhead.
        self.collect_ops = collect_ops
        self._op_samples: dict[str, deque] = {}
        self._op_fresh: dict[str, list[float]] = {}
        self.active = False
        #: where the last parsed window's times came from (:func:`trace_source`):
        #: ``"device"`` | ``"host"``; None before the first window
        self.source: Optional[str] = None
        #: windows parsed into the stats so far
        self.windows = 0

    # -- capture window ------------------------------------------------------

    def start(self) -> None:
        """Open a window. Raises when the process-global profiler is already
        active (another window's leak, or user tracing)."""
        if self.active:
            return
        import jax

        self._window_dir = tempfile.mkdtemp(prefix="devprof_", dir=self._root)
        try:
            jax.profiler.start_trace(self._window_dir)
        except BaseException:
            shutil.rmtree(self._window_dir, ignore_errors=True)
            self._window_dir = None
            raise
        self.active = True

    def stop(self) -> None:
        """End the window and fold its per-program samples into the stats."""
        if not self.active:
            return
        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        self.active = False
        require_device = jax.default_backend() == "tpu"
        try:
            files = glob.glob(
                os.path.join(self._window_dir, "**", "*.xplane.pb"), recursive=True
            )
            if not files:
                raise RuntimeError("the profiler window wrote no xplane trace")
            for f in files:
                data = ProfileData.from_file(f)
                times = extract_program_times(data, require_device)
                self.source = trace_source(data)
                for name, secs in times.items():
                    ring = self._samples.setdefault(
                        name, deque(maxlen=MAX_SAMPLES_PER_PROGRAM)
                    )
                    ring.extend(secs)
                    self._fresh.setdefault(name, []).extend(secs)
                if self.collect_ops:
                    for name, secs in extract_op_times(data, require_device).items():
                        ring = self._op_samples.setdefault(
                            name, deque(maxlen=MAX_SAMPLES_PER_PROGRAM)
                        )
                        ring.extend(secs)
                        self._op_fresh.setdefault(name, []).extend(secs)
            self.windows += 1
        finally:
            if self._window_dir:
                shutil.rmtree(self._window_dir, ignore_errors=True)
                self._window_dir = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- consumption ---------------------------------------------------------

    def drain(self) -> dict[str, list[float]]:
        """New samples since the last drain (seconds per execution)."""
        fresh, self._fresh = self._fresh, {}
        return fresh

    def drain_ops(self) -> dict[str, list[float]]:
        """New per-op/scope samples since the last drain (collect_ops only);
        feed to ``Detector.record_op_samples``."""
        fresh, self._op_fresh = self._op_fresh, {}
        return fresh

    @staticmethod
    def _stats_over(samples: dict[str, deque]) -> dict[str, dict[str, float]]:
        out = {}
        for name, ring in samples.items():
            if not ring:
                continue
            arr = np.asarray(ring, dtype=np.float64)
            out[name] = {
                "min": float(arr.min()),
                "max": float(arr.max()),
                "med": float(np.median(arr)),
                "avg": float(arr.mean()),
                "std": float(arr.std()),
                "count": int(arr.size),
            }
        return out

    def get_stats(self) -> dict[str, dict[str, float]]:
        """Per-program stats over retained samples (reference ``computeStats``)."""
        return self._stats_over(self._samples)

    def get_op_stats(self) -> dict[str, dict[str, float]]:
        """Per-op/scope stats over retained samples (collect_ops only)."""
        return self._stats_over(self._op_samples)

    def reset(self) -> None:
        self._samples.clear()
        self._fresh.clear()
        self._op_samples.clear()
        self._op_fresh.clear()
