"""Per-compiled-program device timing (the CUPTI equivalent): xplane extraction,
the capture-window contract (start/stop/drain/get_stats/reset), and the Detector
integration that turns program times into scored ``prog/...`` signals."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.telemetry.detector import Detector
from tpu_resiliency.telemetry.device_profiler import (
    DeviceTimeProfiler,
    NoDevicePlane,
    extract_op_times,
    extract_program_times,
    normalize_program_name,
    trace_source,
)

#: one profiler window recorded on a v5e chip (jax 0.9.0, libtpu 0.0.34, PR 21):
#: five ``_push_impl`` executions and one ``_score_reset_impl`` with the Pallas
#: median kernel inside — the plane and line names the extraction depends on
V5E_TRACE = os.path.join(os.path.dirname(__file__), "data", "v5e_window.xplane.pb")


# --- xplane extraction on a stub object graph (device-plane case) -------------

@dataclasses.dataclass
class _Ev:
    name: str
    duration_ns: float


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


@dataclasses.dataclass
class _PD:
    planes: list


def test_extract_prefers_device_plane():
    pd = _PD(
        planes=[
            _Plane(
                "/device:TPU:0",
                [
                    _Line(
                        "XLA Modules",
                        [
                            _Ev("jit_train_step(123)", 1_500_000.0),
                            _Ev("jit_train_step(123)", 1_600_000.0),
                            _Ev("jit_eval(77)", 400_000.0),
                        ],
                    ),
                    _Line("XLA Ops", [_Ev("%fusion", 1.0)]),  # ignored
                ],
            ),
            _Plane("/host:CPU", [_Line("python", [_Ev("PjitFunction(train_step)", 9e9)])]),
        ]
    )
    times = extract_program_times(pd)
    assert set(times) == {"jit_train_step", "jit_eval"}  # host fallback NOT mixed in
    np.testing.assert_allclose(times["jit_train_step"], [1.5e-3, 1.6e-3])
    assert trace_source(pd) == "device"


def test_extract_falls_back_to_host_pjit_events():
    pd = _PD(
        planes=[
            _Plane("/host:CPU", [_Line("python", [
                _Ev("PjitFunction(step)", 2_000_000.0),
                _Ev("$profiler.py:101 start_trace", 1.0),  # non-pjit: ignored
            ])]),
        ]
    )
    times = extract_program_times(pd)
    assert set(times) == {"pjit_step"}
    np.testing.assert_allclose(times["pjit_step"], [2e-3])
    assert trace_source(pd) == "host"


@pytest.mark.parametrize("extract", [extract_program_times, extract_op_times])
def test_no_device_plane_is_an_error_where_a_device_is_required(extract):
    """What a TPU backend asks for: host events never stand in for device times."""
    pd = _PD(
        planes=[
            _Plane("/host:CPU", [_Line("python", [_Ev("PjitFunction(step)", 2e6)])]),
            _Plane("/device:CUSTOM:Megascale Trace", [_Line("XLA Modules", [])]),
        ]
    )
    with pytest.raises(NoDevicePlane, match="/host:CPU"):
        extract(pd, require_device=True)


def test_recorded_v5e_trace_reads_device_planes():
    """The real thing, not a stub: today's profiler names the chip's plane
    ``/device:TPU:0`` and its lines ``XLA Modules`` / ``XLA Ops``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(V5E_TRACE)
    assert trace_source(pd) == "device"
    times = extract_program_times(pd, require_device=True)
    assert {k: len(v) for k, v in times.items()} == {
        "jit__push_impl": 5, "jit__score_reset_impl": 1,
    }
    assert 1e-6 < min(times["jit__push_impl"]) < 1e-5
    ops = extract_op_times(pd, require_device=True)
    # XLA Ops events are named by whole HLO instructions and carry no tf_op.
    assert set(ops) == {"add", "copy", "fused_median_weights"}
    assert ops["fused_median_weights"][0] < times["jit__score_reset_impl"][0]


def test_normalize_strips_fingerprint():
    assert normalize_program_name("jit_f(18446744073709551615)") == "jit_f"
    assert normalize_program_name("jit_f") == "jit_f"


# --- real capture window (CPU backend: host-fallback signal) ------------------

def test_capture_window_end_to_end(tmp_path):
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))

    @jax.jit
    def work(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((128, 128))
    work(x)  # compile outside the window
    with prof:
        for _ in range(3):
            jax.block_until_ready(work(x))

    assert (prof.source, prof.windows) == ("host", 1)  # the CPU backend's answer
    fresh = prof.drain()
    assert fresh, "no program samples captured"
    name = next(iter(fresh))
    assert len(fresh[name]) >= 3
    assert all(s > 0 for s in fresh[name])
    assert prof.drain() == {}  # drained

    stats = prof.get_stats()
    st = stats[name]
    assert st["count"] >= 3
    assert st["min"] <= st["med"] <= st["max"]
    prof.reset()
    assert prof.get_stats() == {}
    # The window's trace dir is cleaned up.
    assert list(tmp_path.iterdir()) == []


def test_window_faults_raise_instead_of_vanishing(tmp_path, monkeypatch):
    """A window that cannot start, and a TPU-backend window without a device
    plane, are errors a caller sees — and neither leaks a trace or a dir."""
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))
    other = DeviceTimeProfiler(trace_root=str(tmp_path))
    prof.start()
    try:
        with pytest.raises(Exception):
            other.start()  # the process-global profiler is taken
        assert not other.active
    finally:
        prof.stop()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prof.start()
    jax.block_until_ready(jnp.ones((8,)) + 1)
    with pytest.raises(NoDevicePlane):
        prof.stop()  # a CPU trace under a backend that calls itself a TPU
    assert not prof.active and prof.windows == 1
    assert list(tmp_path.iterdir()) == []


# --- Detector integration ------------------------------------------------------

def test_program_samples_join_the_scored_matrix():
    Detector.initialize(rank=0, world_size=1, report_time_interval=3600.0)
    try:
        for _ in range(8):
            Detector.record_program_samples(
                {"jit_train_step": [1.0e-3], "jit_eval": [0.5e-3]}
            )
        report = Detector.generate_report()
        assert "prog/jit_train_step" in report.section_names
        assert "prog/jit_eval" in report.section_names
        # Single rank: both programs score 1.0 (their own median is the reference).
        assert report.relative_section_scores["prog/jit_train_step"] == 1.0
    finally:
        Detector.shutdown()


# --- per-op/scope granularity (the per-kernel-stream analogue) ----------------

def test_op_scope_key_mapping():
    """The pure event→key mapping both plane layouts share: tf_op scope paths
    win (jit wrappers dropped, trailing op dropped), hlo_op/event names fall
    back with compile-order instruction ids stripped, bookkeeping dies."""
    from tpu_resiliency.telemetry.device_profiler import op_scope_key

    # tf_op scope attribution (TPU "XLA Ops" events).
    assert op_scope_key("%fusion.3", {"tf_op": "jit(step)/attn/dot_general"}) == "attn"
    assert (
        op_scope_key("%fusion.9", {"tf_op": "jit(step)/decoder/mlp/dot_general"})
        == "decoder/mlp"
    )
    # Unscoped op: keys by its own de-numbered base name.
    assert op_scope_key("%reduce.1", {"tf_op": "jit(step)/reduce.1"}) == "reduce"
    assert op_scope_key("x", {"tf_op": "jit(step)"}) is None
    # hlo_op fallback (CPU client line events).
    assert op_scope_key("dot_general.2", {"hlo_op": "dot_general.2"}) == "dot_general"
    assert op_scope_key("wrapped_tanh", {}) == "wrapped_tanh"
    # v5e "XLA Ops" events: the whole HLO instruction is the name, no stats.
    assert (
        op_scope_key(
            "%fused_median_weights.1 = (f32[1024,64]{1,0:T(8,128)S(1)}, "
            "f32[1024,64]{1,0:T(8,128)S(1)}) custom-call(f32[1024,64,32] %copy)",
            {"device_duration_ps": 999151250},
        )
        == "fused_median_weights"
    )
    assert op_scope_key("%copy = f32[8]{0} copy(f32[8]{0} %d.1)", {}) == "copy"
    # Bookkeeping events are dropped.
    assert op_scope_key("end: dot_general.2", {}) is None
    assert op_scope_key("ThreadpoolListener::StartRegion", {}) is None


def test_extract_op_times_prefers_device_ops_line():
    @dataclasses.dataclass
    class _EvS:
        name: str
        duration_ns: float
        stats: list

    pd = _PD(
        planes=[
            _Plane(
                "/device:TPU:0",
                [
                    _Line("XLA Modules", [_Ev("jit_step(1)", 9e9)]),  # not ops
                    _Line(
                        "XLA Ops",
                        [
                            _EvS("%fusion.3", 1_000_000.0, [("tf_op", "jit(step)/attn/dot_general")]),
                            _EvS("%fusion.3", 1_200_000.0, [("tf_op", "jit(step)/attn/dot_general")]),
                            _EvS("%copy.1", 50_000.0, [("tf_op", "jit(step)/mlp/copy")]),
                        ],
                    ),
                ],
            ),
            # Host client line must NOT be mixed in when a device ops line exists.
            _Plane(
                "/host:CPU",
                [_Line("tf_XLAPjRtCpuClient/1", [_EvS("dot_general.2", 7e9, [])])],
            ),
        ]
    )
    times = extract_op_times(pd)
    assert set(times) == {"attn", "mlp"}
    np.testing.assert_allclose(times["attn"], [1e-3, 1.2e-3])


def test_op_capture_window_end_to_end(tmp_path):
    """collect_ops=True on a real CPU trace: the PjRt client per-op line feeds
    op/scope rings through the same window contract (drain_ops/get_op_stats),
    and the Detector turns them into scored op/... signals."""
    prof = DeviceTimeProfiler(trace_root=str(tmp_path), collect_ops=True)

    @jax.jit
    def work(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((128, 128))
    work(x)  # compile outside the window
    with prof:
        for _ in range(3):
            jax.block_until_ready(work(x))

    progs = prof.drain()
    assert progs, "program samples must still be captured alongside ops"
    ops = prof.drain_ops()
    assert ops, "no op samples captured from the client per-op line"
    assert all(all(s > 0 for s in v) for v in ops.values())
    # The matmul appears under its de-numbered hlo base name on CPU.
    assert any("dot" in k for k in ops), sorted(ops)
    assert prof.drain_ops() == {}
    st = prof.get_op_stats()
    k = next(iter(st))
    assert st[k]["count"] >= 1 and st[k]["min"] <= st[k]["max"]

    Detector.initialize(rank=0, world_size=1, report_time_interval=3600.0)
    try:
        Detector.record_op_samples({k: [1.0e-3, 1.1e-3]})
        report = Detector.generate_report()
        assert f"op/{k}" in report.section_names
    finally:
        Detector.shutdown()
    prof.reset()
    assert prof.get_op_stats() == {}
