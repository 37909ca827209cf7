"""Per-compiled-program device timing (the CUPTI equivalent): xplane extraction,
the capture-window contract (start/stop/drain/get_stats/reset), and the Detector
integration that turns program times into scored ``prog/...`` signals."""

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.telemetry.detector import Detector
from tpu_resiliency.telemetry.device_profiler import (
    PHASES,
    DeviceTimeProfiler,
    NoDevicePlane,
    device_ops,
    extract_op_times,
    extract_program_times,
    hlo_instructions,
    instruction_name,
    normalize_program_name,
    phase_of,
    step_phase_times,
    trace_source,
)

#: one profiler window recorded on a v5e chip (jax 0.9.0, libtpu 0.0.34, PR 21):
#: five ``_push_impl`` executions and one ``_score_reset_impl`` with the Pallas
#: median kernel inside — the plane and line names the extraction depends on
V5E_TRACE = os.path.join(os.path.dirname(__file__), "data", "v5e_window.xplane.pb")
#: a window recorded on a v5e chip by PR 25 (``benchmark/tools/record_step_trace.py``):
#: five executions of a ``value_and_grad`` + AdamW step jitted as ``train_step`` under
#: the product's loop, the straggler callback reporting on every step
V5E_STEP_TRACE = os.path.join(os.path.dirname(__file__), "data", "v5e_step.xplane.pb")


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


# --- the HLO a trace embeds: op_name, phase, scope keys --------------------------

@pytest.mark.parametrize("op_name, phase", [
    ("jit(train_step)/jvp()/while/body/closed_call/mul", "fwd"),
    ("jit(train_step)/jvp()/while/body/closed_call/jit(silu)/mul", "fwd"),
    ("jit(train_step)/jvp()/dot_general", "fwd"),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice", "bwd"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/add_any", "bwd"),
    # a forward recomputed under jax.checkpoint runs in, and counts as, the backward
    ("jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/tanh", "bwd"),
    ("jit(train_step)/div", "opt"),
    ("jit(train_step)/sqrt", "opt"),
    ("params['layers']['wq']", "opt"),  # a copy of an argument carries its name
    ("", "opt"),  # the compiler's own instructions carry none
])
def test_phase_is_read_from_the_names_autodiff_writes(op_name, phase):
    assert phase_of(op_name) == phase and phase in PHASES


def test_recorded_trace_joins_each_op_to_its_op_name():
    """The trace file embeds every program's ``Hlo Proto``: ``%add.1`` of the
    telemetry push is ``jit(_push_impl)/add``, with no stat on the event saying so."""
    from jax.profiler import ProfileData

    with open(V5E_TRACE, "rb") as f:
        hlo = hlo_instructions(f.read())
    assert {len(instructions) for instructions in hlo.values()} == {3, 10}
    ops = list(device_ops(ProfileData.from_file(V5E_TRACE), hlo))
    assert len(ops) == 10 and all(op.instruction is not None for op in ops)
    joined = {instruction_name(op.event.name): op.instruction for op in ops}
    assert joined["add.1"].op_name == "jit(_push_impl)/add"
    assert joined["add.1"].opcode == "add" and not joined["add.1"].is_container
    assert joined["fused_median_weights.1"].op_name.endswith(
        "jit(fused_median_weights)/pallas_call")
    pushes = [op for op in ops if "_push_impl" in op.program]
    assert [op.execution for op in pushes] == [0, 1, 2, 3, 4]
    assert all(0 < op.event.duration_ns * 1e-9 <= op.execution_s for op in pushes)
    assert hlo_instructions(b"") == {}  # a trace that embeds no HLO joins nothing
    with open(V5E_TRACE, "rb") as f:  # a program already read is not read again
        assert hlo_instructions(f.read(), known=hlo) == {}


def test_every_window_counts_its_own_cost(tmp_path):
    """``stop()`` records one ``profiler_window`` event a window; its three parts
    are the window's host time (what ``start()`` and ``stop()`` took together)."""
    from tpu_resiliency.utils import events

    seen = []
    events.add_sink(seen.append)
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))
    try:
        for _ in range(2):
            t0 = time.perf_counter()
            prof.start()
            t1 = time.perf_counter()
            jax.block_until_ready(jnp.ones((8,)) + 1)
            t2 = time.perf_counter()
            prof.stop()
            host_s = (t1 - t0) + (time.perf_counter() - t2)
    finally:
        events.remove_sink(seen.append)
    windows = [e.payload for e in seen if e.kind == "profiler_window"]
    assert len(windows) == 2 and [e.source for e in seen] == ["telemetry"] * 2
    last = windows[-1]
    assert set(last) >= {"start_s", "stop_s", "parse_s", "trace_bytes", "profile_source"}
    assert min(last["start_s"], last["stop_s"], last["parse_s"]) > 0
    parts = last["start_s"] + last["stop_s"] + last["parse_s"]
    assert parts <= host_s and parts == pytest.approx(host_s, rel=0.05, abs=2e-3)
    assert last["trace_bytes"] > 0 and last["profile_source"] == "host"


@pytest.fixture(scope="module")
def step_trace():
    from jax.profiler import ProfileData

    with open(V5E_STEP_TRACE, "rb") as f:
        hlo = hlo_instructions(f.read())
    return ProfileData.from_file(V5E_STEP_TRACE), hlo


def test_every_op_of_the_recorded_step_gets_its_op_name(step_trace):
    data, hlo = step_trace
    ops = [op for op in device_ops(data, hlo) if "train_step" in op.program]
    assert len({op.execution for op in ops}) == 5
    assert all(op.instruction is not None for op in ops)
    work = [op for op in ops if not op.instruction.is_container]
    assert len(work) < len(ops)  # the scanned layers are ``while`` containers
    # what the compiler made itself (copies, broadcasts of zeros) has no name: a
    # tenth of the step's time here, and by the rule ``opt``
    named = [op for op in work if op.instruction.op_name]
    seconds = lambda ops: sum(op.event.duration_ns for op in ops)  # noqa: E731
    assert seconds(named) > 0.85 * seconds(work)
    assert all(op.instruction.op_name.startswith("jit(train_step)/") for op in named
               if "(" in op.instruction.op_name)
    assert {op.instruction.phase for op in work} == set(PHASES)


def test_the_recorded_step_splits_into_three_phases_that_sum_to_it(step_trace):
    rows = step_phase_times(*step_trace)
    assert len(rows) == 5
    for row in rows:
        assert min(row[p] for p in PHASES) > 0
        assert sum(row[p] for p in PHASES) == pytest.approx(row["module"], rel=0.02)
        assert row["bwd"] > row["fwd"]  # two matmuls back for each one forward
        assert 0 < row["mixed"] < row["module"] and row["unnamed"] < row["opt"]
    assert step_phase_times(step_trace[0], {}) == []  # no HLO, no split
    assert step_phase_times(*step_trace, program="no_such_program") == []


def test_op_times_key_by_scope_where_the_hlo_is_given(step_trace):
    data, hlo = step_trace
    bare = extract_op_times(data, require_device=True)
    assert "fusion" in bare and "while" in bare  # what a v5e's events say of themselves
    scoped = extract_op_times(data, require_device=True, hlo=hlo)
    assert not any(k.startswith(("fusion", "while")) for k in scoped), sorted(scoped)
    assert {"jvp()/while/body/closed_call", "transpose(jvp())/while/body/closed_call",
            "jvp()", "transpose(jvp())"} <= set(scoped)
    # containers are left out, so no op's time is counted twice
    total = sum(sum(v) for v in scoped.values())
    programs = sum(sum(v) for v in extract_program_times(data, require_device=True).values())
    assert total <= programs


# --- the deferred close: stop_async / wait, and what a window asks for -----------

def _window_events(seen):
    return [e.payload for e in seen if e.kind == "profiler_window"]


@pytest.fixture
def gated_stop_trace(held_stop_trace):
    """``gate, hold = gated_stop_trace()``: ``jax.profiler.stop_trace`` waits for
    ``gate.set()`` (at most 5 s) before it closes the session."""
    import threading

    def arm():
        gate = threading.Event()
        return gate, held_stop_trace(lambda: gate.wait(5.0))

    return arm


def test_stop_async_returns_at_once_and_the_samples_follow(tmp_path, gated_stop_trace):
    import threading

    gate, hold = gated_stop_trace()
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))
    work = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    work(x)
    prof.start()
    jax.block_until_ready(work(x))
    prof.stop_async()  # returns while stop_trace is still held at the gate
    assert prof.closing and not prof.active
    assert prof.drain() == {} and prof.windows == 0
    closers = [t for t in threading.enumerate() if t.name == "devprof-close"]
    assert len(closers) == 1 and closers[0].daemon  # cannot hold a dying process
    prof.stop_async()  # no window open: nothing to request
    gate.set()
    prof.wait()
    assert not prof.closing and prof.windows == 1 and prof.source == "host"
    assert prof.drain()
    assert list(tmp_path.iterdir()) == []
    prof.wait()  # nothing in flight, nothing to raise
    assert closers[0].is_alive()  # one closer for all of a profiler's deferred closes
    prof.stop()  # ... until the profiler is stopped for good
    assert not closers[0].is_alive()


@pytest.mark.parametrize("close", ["stop", "with"])
def test_stop_and_with_are_still_synchronous(tmp_path, gated_stop_trace, close):
    """``stop()`` is "request the close, then wait": when it returns the session is
    closed, the samples are in, and another profiler can open a window at once."""
    import threading

    gate, hold = gated_stop_trace()
    threading.Timer(0.05, gate.set).start()
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))
    work = jax.jit(lambda x: jnp.tanh(x * 2.0).sum())
    x = jnp.ones((32,))
    work(x)
    if close == "with":
        with prof:
            jax.block_until_ready(work(x))
    else:
        prof.start()
        jax.block_until_ready(work(x))
        prof.stop()
    assert hold.calls == ["start_trace", "stop_trace"]
    assert not prof.closing and not prof.active and prof.windows == 1
    assert prof.drain()
    with DeviceTimeProfiler(trace_root=str(tmp_path)):
        pass


def test_start_waits_for_a_close_in_flight_and_wait_s_says_so(tmp_path, gated_stop_trace):
    """One process holds one profiler session: the next window opens after the last
    one's close, late and never skipped, and its event counts the wait."""
    import threading

    from tpu_resiliency.utils import events

    gate, hold = gated_stop_trace()
    seen = []
    events.add_sink(seen.append)
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))
    try:
        prof.start()
        jax.block_until_ready(jnp.ones((8,)) + 1)
        prof.stop_async()
        # open the gate only once start() is waiting for the closer, and 0.05 s
        # after that: the wait is then at least those 0.05 s
        waiting, real_wait = threading.Event(), prof._idle.wait

        def wait(*args):
            waiting.set()
            return real_wait(*args)

        prof._idle.wait = wait

        def release():
            assert waiting.wait(5.0)
            time.sleep(0.05)
            gate.set()

        threading.Thread(target=release, daemon=True).start()
        prof.start()
        jax.block_until_ready(jnp.ones((8,)) + 1)
        prof.stop()
    finally:
        events.remove_sink(seen.append)
    assert hold.calls == ["start_trace", "stop_trace", "start_trace", "stop_trace"]
    first, second = _window_events(seen)
    assert set(second) >= {"start_s", "wait_s", "stop_s", "parse_s", "trace_bytes",
                           "profile_source"}
    assert first["wait_s"] < 0.05 <= second["wait_s"]
    assert first["stop_s"] >= 0.05  # the held stop_trace is the close's, not the caller's
    assert prof.windows == 2


def test_a_deferred_closes_fault_is_raised_by_wait_once(tmp_path, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prof = DeviceTimeProfiler(trace_root=str(tmp_path))
    prof.start()
    jax.block_until_ready(jnp.ones((8,)) + 1)
    prof.stop_async()
    with pytest.raises(NoDevicePlane):
        prof.wait()  # a CPU trace under a backend that calls itself a TPU
    prof.wait()
    assert prof.windows == 0 and list(tmp_path.iterdir()) == []
    monkeypatch.undo()
    with prof:  # and the session was closed: the next window opens
        pass
    assert prof.windows == 1 and prof._closer is None


@pytest.mark.parametrize("backend, collect_ops, want", [
    ("cpu", False, None),
    ("cpu", True, None),
    ("tpu", False, (0, 0, False)),
    ("tpu", True, (0, 0, True)),
])
def test_a_window_asks_for_what_its_backend_and_collect_ops_read(
        tmp_path, monkeypatch, backend, collect_ops, want):
    """On a TPU only the device planes (and the ``Hlo Proto`` for ``collect_ops``);
    anywhere else the profiler's defaults, whose Python tracer the fallback reads."""
    captured = []
    real_start = jax.profiler.start_trace

    def start_trace(log_dir, **kwargs):
        captured.append(kwargs.get("profiler_options"))
        return real_start(log_dir, **kwargs)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    prof = DeviceTimeProfiler(trace_root=str(tmp_path), collect_ops=collect_ops)
    prof.start()
    jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.ones((8,))))
    try:
        prof.stop()
    except NoDevicePlane:
        assert backend == "tpu"  # the CPU's trace has none
    (options,) = captured
    if want is None:
        assert options is None and prof.drain()  # the fallback found its events
    else:
        assert (options.host_tracer_level, options.python_tracer_level,
                options.enable_hlo_proto) == want


@pytest.mark.parametrize("closed_after_ms, want", [
    (None, {"jit__push_impl": 5, "jit__score_reset_impl": 1}),  # no request time: all
    (60.0, {"jit__push_impl": 5, "jit__score_reset_impl": 1}),  # all ended before it
    (50.0, {"jit__push_impl": 5}),  # 0.7 ms into the 1.05 ms scorer: not a sample
    (48.2, {"jit__push_impl": 3}),  # between the third push and the fourth
    (10.0, {}),  # nothing had run yet
])
def test_only_executions_that_ended_before_the_close_are_samples(closed_after_ms, want):
    """The recorded v5e window (five pushes from 47.89 ms, the scorer from 49.28 to
    50.34 ms after the session's start) read as if its close had been requested at
    another time: what had not ended by then is left out, whole or cut."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(V5E_TRACE)
    start_ns = 1790439250044175170  # the trace's own ``profile_start_time``
    closed_at_ns = None if closed_after_ms is None else start_ns + int(closed_after_ms * 1e6)
    times = extract_program_times(pd, require_device=True, closed_at_ns=closed_at_ns)
    assert {k: len(v) for k, v in times.items()} == want
    if "jit__score_reset_impl" in want:
        assert times["jit__score_reset_impl"] == [pytest.approx(1.054546e-3)]
