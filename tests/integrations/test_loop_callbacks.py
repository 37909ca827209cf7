"""Integration-layer tests: loop protocol + the four callbacks, mirroring the
reference's ``tests/ptl_resiliency/unit`` pattern (fake trainer driving callbacks,
real monitor server behind an env-var socket)."""

import os
import time

import jax
import jax.numpy as jnp
import pytest

from tpu_resiliency.integrations import (
    Callback,
    FaultToleranceCallback,
    FaultToleranceSectionsCallback,
    HierarchicalCheckpointCallback,
    LoopContext,
    StopTraining,
    StragglerDetectionCallback,
    run_training,
)
from tpu_resiliency.platform import ipc
from tpu_resiliency.telemetry.detector import Detector
from tpu_resiliency.watchdog.config import FaultToleranceConfig
from tpu_resiliency.watchdog.monitor_server import RankMonitorServer


class Recorder(Callback):
    def __init__(self):
        self.events = []

    def __getattribute__(self, name):
        if name.startswith("on_"):
            events = object.__getattribute__(self, "events")

            def hook(ctx, *a):
                events.append(name)

            return hook
        return object.__getattribute__(self, name)


def test_loop_hook_order_and_state_threading():
    rec = Recorder()

    def step(state, i):
        return state + 1

    ctx = run_training(
        step,
        state=0,
        num_steps=3,
        callbacks=[rec],
        checkpoint_every=2,
        checkpoint_fn=lambda s, i: None,
        validate_every=3,
        validate_fn=lambda s, i: {"val": s},
    )
    assert ctx.state == 3
    assert rec.events[0] == "on_train_start"
    assert rec.events[-1] == "on_train_end"
    assert rec.events.count("on_step_start") == 3
    assert rec.events.count("on_step_end") == 3
    assert rec.events.count("on_checkpoint_start") == 1
    assert rec.events.count("on_validation_start") == 1
    assert ctx.metrics["val"] == 3


def test_loop_stop_training_cooperative():
    class Stopper(Callback):
        def on_step_end(self, ctx):
            if ctx.step == 1:
                raise StopTraining

    ctx = run_training(lambda s, i: s + 1, 0, 10, callbacks=[Stopper()])
    assert ctx.state == 2  # stopped after step 1 completed


def test_loop_exception_fires_hook_and_propagates():
    seen = []

    class Witness(Callback):
        def on_exception(self, ctx, exc):
            seen.append(repr(exc))

    def step(state, i):
        if i == 1:
            raise ValueError("boom")
        return state

    with pytest.raises(ValueError):
        run_training(step, 0, 5, callbacks=[Witness()])
    assert seen and "boom" in seen[0]


def test_each_callbacks_hook_is_an_annotation_on_the_profilers_clock(profiler_window):
    """``CallbackRunner.fire`` brackets every callback's hook in
    ``tpures/loop/<hook>/<CallbackClass>``; the annotation of a callback that raises
    is closed (the next one opens after it, not inside it) and the loop goes on."""

    class Quiet(Callback):
        pass

    class Faulty(Callback):
        def on_step_end(self, ctx):
            raise RuntimeError("a callback's fault is logged, never fatal")

    with profiler_window() as names:
        ctx = run_training(lambda s, i: s + 1, 0, 2, callbacks=[Faulty(), Quiet()])
    assert ctx.state == 2
    ours = [n for n in names if n.startswith("tpures/loop/")]
    per_step = [
        "tpures/loop/on_step_start/Faulty", "tpures/loop/on_step_start/Quiet",
        "tpures/loop/on_step_end/Faulty", "tpures/loop/on_step_end/Quiet",
    ]
    assert ours == (
        ["tpures/loop/on_train_start/Faulty", "tpures/loop/on_train_start/Quiet"]
        + per_step * 2
        + ["tpures/loop/on_train_end/Faulty", "tpures/loop/on_train_end/Quiet"])


def test_hooks_are_annotations_only_never_events():
    """With no profiler window open, a step through ``run_training`` with a callback
    attached records no event (an event per hook per step would flood an operator's
    stream): tracing off costs a null context per hook and nothing else."""
    from tpu_resiliency.utils import events

    seen = []
    events.add_sink(seen.append)
    try:
        run_training(lambda s, i: s + 1, 0, 3, callbacks=[Recorder()])
    finally:
        events.remove_sink(seen.append)
    assert seen == []


@pytest.fixture
def monitor(tmp_path):
    sock = str(tmp_path / "m.sock")
    cfg = FaultToleranceConfig(
        initial_rank_heartbeat_timeout=30.0,
        rank_heartbeat_timeout=30.0,
        workload_check_interval=0.5,
    )
    proc = RankMonitorServer.run_in_subprocess(cfg, sock, start_method="spawn")
    old = os.environ.get(ipc.MONITOR_SOCKET_ENV)
    os.environ[ipc.MONITOR_SOCKET_ENV] = sock
    yield sock
    if old is None:
        os.environ.pop(ipc.MONITOR_SOCKET_ENV, None)
    else:
        os.environ[ipc.MONITOR_SOCKET_ENV] = old
    proc.terminate()
    proc.join(timeout=10)


def test_ft_callback_heartbeats_and_finished_flag(monitor, tmp_path):
    from tpu_resiliency.utils import events

    flag = str(tmp_path / "finished.flag")
    sd_path = str(tmp_path / "ft_state.pkl")
    cb = FaultToleranceCallback(
        autoresume=True, finished_flag_path=flag, state_dict_path=sd_path
    )
    seen = []
    events.add_sink(seen.append)
    try:
        ctx = run_training(lambda s, i: s + 1, 0, 5, callbacks=[cb])
    finally:
        events.remove_sink(seen.append)
    assert ctx.state == 5
    assert cb.machine.heartbeats >= 5
    assert cb.machine.finished
    assert os.path.exists(flag)
    assert os.path.exists(sd_path)  # calculated timeouts persisted
    # Both FT milestones are on the structured event stream.
    kinds = {e.kind for e in seen if e.source == "ft"}
    assert {"timeouts_calculated", "training_finished"} <= kinds, kinds
    tc = next(e for e in seen if e.kind == "timeouts_calculated")
    assert tc.payload["initial_s"] > 0 and tc.payload["subsequent_s"] > 0

    # Second run: the finished flag short-circuits training (autoresume contract).
    cb2 = FaultToleranceCallback(autoresume=True, finished_flag_path=flag)
    ctx2 = run_training(lambda s, i: s + 1, 0, 5, callbacks=[cb2])
    assert ctx2.state == 0 and ctx2.should_stop


def test_ft_callback_simulated_fault(monitor):
    from tpu_resiliency.integrations.ft_callbacks import SimulatedFault

    cb = FaultToleranceCallback(simulated_fault_step=2)
    with pytest.raises(SimulatedFault, match="simulated fault"):
        run_training(lambda s, i: s + 1, 0, 5, callbacks=[cb])
    assert cb.machine.exception_seen and not cb.machine.finished


def test_ft_sections_callback(monitor):
    cb = FaultToleranceSectionsCallback()
    ctx = run_training(
        lambda s, i: s + 1,
        0,
        4,
        callbacks=[cb],
        checkpoint_every=2,
        checkpoint_fn=lambda s, i: None,
    )
    assert ctx.state == 4
    calc = cb.client.timeouts_calc
    assert set(calc.section_max_elapsed) >= {"setup", "step", "checkpointing"}
    assert all(v >= 0 for v in calc.section_max_elapsed.values())


def test_straggler_callback_reports(monkeypatch):
    if Detector.initialized:
        Detector.shutdown()
    cb = StragglerDetectionCallback(report_time_interval=0.0, threshold=0.75)

    def step(state, i):
        time.sleep(0.002)
        return state + 1

    ctx = run_training(step, 0, 20, callbacks=[cb])
    assert ctx.state == 20
    assert cb.last_report is not None
    assert any("train_step" in n for n in cb.last_report.section_names)
    assert not Detector.initialized  # shut down on train end


def test_straggler_callback_profiles_programs():
    """profile_programs_every wires the XLA-profiler capture into the loop: jitted
    programs executed inside profiled steps join the scored matrix as prog/
    signals (host-PjitFunction fallback on the CPU backend)."""
    import jax
    import jax.numpy as jnp

    if Detector.initialized:
        Detector.shutdown()
    cb = StragglerDetectionCallback(
        report_time_interval=0.0, profile_programs_every=2
    )

    @jax.jit
    def work(x):
        return jnp.tanh(x * 2.0).sum()

    def step(state, i):
        jax.block_until_ready(work(jnp.full((32,), float(i))))
        return state + 1

    ctx = run_training(step, 0, 24, callbacks=[cb])
    assert ctx.state == 24
    assert cb.last_report is not None
    assert any(n.startswith("prog/") for n in cb.last_report.section_names), (
        cb.last_report.section_names
    )
    # The window closed with training (no leaked process-global trace).
    assert cb._program_profiler is not None and not cb._program_profiler.active


def test_straggler_callback_profiles_ops():
    """profile_ops adds the per-op/scope granularity from the same windows:
    op/... signals join the scored matrix alongside prog/... (PjRt client
    per-op line on the CPU backend)."""
    import jax
    import jax.numpy as jnp

    if Detector.initialized:
        Detector.shutdown()
    cb = StragglerDetectionCallback(
        report_time_interval=0.0, profile_programs_every=2, profile_ops=True
    )

    @jax.jit
    def work(x):
        return jnp.tanh(x @ x).sum()

    def step(state, i):
        jax.block_until_ready(work(jnp.full((64, 64), float(i))))
        return state + 1

    ctx = run_training(step, 0, 24, callbacks=[cb])
    assert ctx.state == 24
    assert cb.last_report is not None
    names = cb.last_report.section_names
    assert any(n.startswith("prog/") for n in names), names
    assert any(n.startswith("op/") for n in names), names


def test_hierarchical_checkpoint_callback(tmp_path):
    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager

    mgr = LocalCheckpointManager(str(tmp_path / "local"), rank=0)
    cb = HierarchicalCheckpointCallback(
        local_manager=mgr,
        global_dir=str(tmp_path / "global"),
        local_every=2,
        global_every=4,
        to_state_dict=lambda s: {"w": s},
        from_state_dict=lambda s, loaded: loaded["w"],
    )
    os.makedirs(str(tmp_path / "global"), exist_ok=True)

    def step(state, i):
        return state + jnp.ones(())

    ctx = run_training(step, jnp.zeros(()), 8, callbacks=[cb])
    assert float(ctx.state) == 8.0
    # Local checkpoints exist for steps 2,4,6,8; global for 4,8.
    assert mgr.find_latest() == 8
    assert cb.latest_global_step() == 8

    # Restore path: local is newest → used.
    ctx2 = LoopContext()
    ctx2.state = jnp.zeros(())
    assert cb.restore_latest(ctx2)
    assert float(ctx2.state) == 8.0 and ctx2.start_step == 8
    cb.close()


def test_checkpoint_callback_prefers_newest_tier(tmp_path):
    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager

    mgr = LocalCheckpointManager(str(tmp_path / "local"), rank=0)
    cb = HierarchicalCheckpointCallback(
        local_manager=mgr,
        global_dir=str(tmp_path / "global"),
        local_every=3,
        global_every=4,
        to_state_dict=lambda s: {"w": s},
        from_state_dict=lambda s, loaded: loaded["w"],
    )
    os.makedirs(str(tmp_path / "global"), exist_ok=True)
    ctx = run_training(lambda s, i: s + jnp.ones(()), jnp.zeros(()), 4, callbacks=[cb])
    # local at step 3, global at step 4 → global wins.
    ctx2 = LoopContext()
    ctx2.state = jnp.zeros(())
    assert cb.restore_latest(ctx2)
    assert ctx2.start_step == 4 and float(ctx2.state) == 4.0
    cb.close()


def test_checkpoint_callback_rank_suffixed_global_restore(tmp_path):
    """Global checkpoints saved with a rank suffix must be discoverable again."""
    cb = HierarchicalCheckpointCallback(
        global_dir=str(tmp_path / "g"),
        global_every=2,
        rank=0,
        to_state_dict=lambda s: {"w": s},
        from_state_dict=lambda s, loaded: loaded["w"],
    )
    os.makedirs(str(tmp_path / "g"), exist_ok=True)
    run_training(lambda s, i: s + jnp.ones(()), jnp.zeros(()), 4, callbacks=[cb])
    assert cb.latest_global_step() == 4
    ctx = LoopContext()
    ctx.state = jnp.zeros(())
    assert cb.restore_latest(ctx)
    assert ctx.start_step == 4 and float(ctx.state) == 4.0
    cb.close()


def test_checkpoint_callback_driven_by_loop_brackets(monitor, tmp_path):
    """save_now wired as checkpoint_fn: saves happen inside the loop's checkpoint
    brackets so the sections callback attributes them to 'checkpointing'."""
    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager

    mgr = LocalCheckpointManager(str(tmp_path / "local"), rank=0)
    ckpt_cb = HierarchicalCheckpointCallback(
        local_manager=mgr,
        local_every=2,
        to_state_dict=lambda s: {"w": s},
        from_state_dict=lambda s, loaded: loaded["w"],
        driven_by_loop=True,
    )
    sections = FaultToleranceSectionsCallback()
    ctx = run_training(
        lambda s, i: s + jnp.ones(()),
        jnp.zeros(()),
        4,
        callbacks=[sections, ckpt_cb],
        checkpoint_every=ckpt_cb.cadence,
        checkpoint_fn=ckpt_cb.save_now,
    )
    assert float(ctx.state) == 4.0
    assert mgr.find_latest() == 4
    assert sections.client.timeouts_calc.section_max_elapsed.get("checkpointing", 0) > 0
    ckpt_cb.close()


def test_cooperative_stop_does_not_write_finished_flag(monitor, tmp_path):
    flag = str(tmp_path / "f.flag")

    class StopAtTwo(Callback):
        def on_step_end(self, ctx):
            if ctx.step == 2:
                raise StopTraining

    cb = FaultToleranceCallback(autoresume=True, finished_flag_path=flag)
    run_training(lambda s, i: s + 1, 0, 100, callbacks=[cb, StopAtTwo()])
    assert not os.path.exists(flag)  # job is NOT finished — must be rescheduled


def test_straggler_report_emits_structured_event():
    """Every report lands on the structured event stream as a machine-readable
    twin of the log lines (the reference's events/metrics-stream role)."""
    from tpu_resiliency.utils import events

    if Detector.initialized:
        Detector.shutdown()
    seen = []
    events.add_sink(seen.append)
    try:
        cb = StragglerDetectionCallback(report_time_interval=0.0)
        ctx = run_training(lambda s, i: s + 1, 0, 20, callbacks=[cb])
        assert ctx.state == 20
    finally:
        events.remove_sink(seen.append)
    reports = [e for e in seen if e.kind == "straggler_report"]
    assert reports, [e.kind for e in seen]
    ev = reports[-1]
    assert ev.source == "telemetry"
    assert set(ev.payload) >= {"step", "perf_scores", "stragglers_by_perf",
                               "stragglers_by_section"}
    assert ev.payload["perf_scores"].get("0") == 1.0  # single healthy rank (str keys: on-disk schema)
    assert ev.payload["stragglers_by_perf"] == []


@pytest.mark.parametrize("use_device_mesh", [True, False], ids=["mesh", "local"])
def test_one_worker_report_says_which_path_scored_it(use_device_mesh):
    """One worker is one JAX process per rank: asked for the mesh path, it gets
    the compiled mesh scorer (no store, nobody to agree columns with), and both
    the report and its event say so, beside the profiler windows' own source."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.utils import events

    if Detector.initialized:
        Detector.shutdown()
    seen = []
    events.add_sink(seen.append)
    try:
        cb = StragglerDetectionCallback(
            report_time_interval=0.0, profile_programs_every=3,
            use_device_mesh=use_device_mesh,
        )
        work = jax.jit(lambda x: jnp.tanh(x * 2.0).sum())

        def step(state, i):
            jax.block_until_ready(work(jnp.full((32,), float(i))))
            return state + 1

        run_training(step, 0, 24, callbacks=[cb])
    finally:
        events.remove_sink(seen.append)
    want = "mesh" if use_device_mesh else "local"
    assert cb.last_report.source == want
    assert cb.last_report.perf_scores == {0: 1.0}
    payloads = [e.payload for e in seen if e.kind == "straggler_report"]
    assert len(payloads) >= 2
    assert {p["report_source"] for p in payloads} == {want}
    last = payloads[-1]
    assert any(s.startswith("prog/") for s in last["signals"]), last["signals"]
    # The CPU backend's windows are host dispatch times, and say so.
    assert last["profile_source"] == "host" and last["profile_windows"] >= 2
    assert (last["profile_skipped"], last["profile_dropped"]) == (0, 0)


def test_profiler_faults_are_counted_not_fatal(monkeypatch):
    """A window that cannot start is skipped, one that cannot be parsed is
    dropped; training goes on and the counts reach the report event."""
    from tpu_resiliency.telemetry import device_profiler
    from tpu_resiliency.utils import events

    if Detector.initialized:
        Detector.shutdown()

    def no_plane(*a, **k):
        raise device_profiler.NoDevicePlane("no 'XLA Modules' line")

    monkeypatch.setattr(device_profiler, "extract_program_times", no_plane)
    seen = []
    events.add_sink(seen.append)
    try:
        cb = StragglerDetectionCallback(report_time_interval=0.0, profile_programs_every=4)
        ctx = run_training(lambda s, i: s + 1, 0, 20, callbacks=[cb])
    finally:
        events.remove_sink(seen.append)
    assert ctx.state == 20
    last = [e.payload for e in seen if e.kind == "straggler_report"][-1]
    # a window's fault is known once its deferred close is done: the fifth window's
    # (step 16) at the latest when training ends, the first four's by then for sure
    assert cb.profile_dropped == 5 and last["profile_dropped"] in (4, 5)
    assert last["profile_windows"] == 0
    assert not any(s.startswith("prog/") for s in last["signals"])
    assert not cb._program_profiler.active


def _profiled_training(cb, steps, step=None, extra=()):
    from tpu_resiliency.utils import events

    if Detector.initialized:
        Detector.shutdown()
    work = jax.jit(lambda x: jnp.tanh(x * 2.0).sum())

    def default_step(state, i):
        jax.block_until_ready(work(jnp.full((32,), float(i))))
        return state + 1

    seen = []
    events.add_sink(seen.append)
    try:
        run_training(step or default_step, 0, steps, callbacks=[cb, *extra])
    finally:
        events.remove_sink(seen.append)
    return seen


def test_a_slow_close_keeps_the_cadence(held_stop_trace):
    """24 steps at every 3 are 8 windows however slow the close: a window that finds
    the last close unfinished waits for it, and is never skipped or moved."""
    hold = held_stop_trace(lambda: time.sleep(0.02))
    cb = StragglerDetectionCallback(report_time_interval=0.0, profile_programs_every=3)
    seen = _profiled_training(cb, 24)
    windows = [e.payload for e in seen if e.kind == "profiler_window"]
    assert len(windows) == 8 and cb._program_profiler.windows == 8
    assert (cb.profile_skipped, cb.profile_dropped) == (0, 0)
    assert hold.calls == ["start_trace", "stop_trace"] * 8  # one session at a time
    assert all(w["stop_s"] >= 0.02 and w["wait_s"] >= 0 for w in windows)
    last = [e.payload for e in seen if e.kind == "straggler_report"][-1]
    assert any(s.startswith("prog/") for s in last["signals"]), last["signals"]
    assert (last["profile_skipped"], last["profile_dropped"]) == (0, 0)
    assert not cb._program_profiler.active and not cb._program_profiler.closing


def test_a_windows_samples_reach_the_detector_within_the_cadence(held_stop_trace):
    """The close of step 0's window is held until step 2 runs: nothing of it can be in
    the rings at the end of steps 0 and 1, and all of it is there by the end of step 3,
    whose window had to wait for it."""
    import threading

    step_2 = threading.Event()
    held_stop_trace(lambda: step_2.wait(5.0))
    work = jax.jit(lambda x: jnp.tanh(x * 2.0).sum())

    def step(state, i):
        if i == 2:
            step_2.set()
        jax.block_until_ready(work(jnp.full((32,), float(i))))
        return state + 1

    class RingsAfterEachStep(Callback):
        def __init__(self):
            self.programs = {}

        def on_step_end(self, ctx):
            self.programs[ctx.step] = {n for n in Detector._rings if n.startswith("prog/")}

    probe = RingsAfterEachStep()
    cb = StragglerDetectionCallback(report_time_interval=0.0, profile_programs_every=3)
    _profiled_training(cb, 24, step=step, extra=[probe])
    assert probe.programs[0] == set() and probe.programs[1] == set()
    assert probe.programs[3], probe.programs
    assert any(n.startswith("prog/") for n in cb.last_report.section_names)
    assert (cb.profile_skipped, cb.profile_dropped) == (0, 0)


@pytest.mark.parametrize("dies_at", [0, 1], ids=["window_open", "close_in_flight"])
def test_a_step_that_dies_leaves_no_profiler_session_open(held_stop_trace, tmp_path, dies_at):
    """An exception in a step, in the window's own step or while the closer is still
    at work on it, leaves no session and no closer behind: a restarted incarnation's
    fresh profiler opens its window at once."""
    import threading

    from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler

    raised = threading.Event()
    held_stop_trace(lambda: raised.wait(5.0))  # no close ends before the step has died

    def step(state, i):
        if i == dies_at:
            raised.set()
            raise RuntimeError("boom")
        return state + 1

    cb = StragglerDetectionCallback(report_time_interval=0.0, profile_programs_every=3)
    if Detector.initialized:
        Detector.shutdown()
    with pytest.raises(RuntimeError, match="boom"):
        run_training(step, 0, 6, callbacks=[cb])
    prof = cb._program_profiler
    assert not prof.active and not prof.closing and prof.windows == 1
    assert prof._closer is None  # the closer thread went with the loop
    assert cb.profile_dropped == 0
    fresh = DeviceTimeProfiler(trace_root=str(tmp_path))
    fresh.start()  # raises if the process-global session leaked
    fresh.stop()


def test_async_saves_survive_a_step_that_donates_the_state(tmp_path):
    """The loop a chip forces: the step donates its state (a second copy does
    not fit), and async saves of that state still finalize and restore."""
    import numpy as np

    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager

    step = jax.jit(
        lambda s: jax.tree.map(lambda x: x + 1.0, s), donate_argnums=(0,)
    )
    state = {"w": jnp.zeros((256, 1024)), "m": jnp.zeros((64, 1024))}
    mgr = LocalCheckpointManager(str(tmp_path / "ckpt"), rank=0)
    cb = HierarchicalCheckpointCallback(local_manager=mgr, local_every=2)
    ctx = run_training(lambda s, i: step(s), state, 7, callbacks=[cb])
    assert mgr.find_latest() == 6
    tree, _ = mgr.load_tree(6)
    np.testing.assert_array_equal(np.asarray(tree["w"]), 6.0)
    np.testing.assert_array_equal(np.asarray(ctx.state["m"]), 7.0)
    cb.close()
