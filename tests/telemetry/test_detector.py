import time

import numpy as np
import pytest

from tpu_resiliency.exceptions import ResiliencyError
from tpu_resiliency.telemetry import CallableId, Detector


@pytest.fixture(autouse=True)
def clean_detector():
    if Detector.initialized:
        Detector.shutdown()
    yield
    if Detector.initialized:
        Detector.shutdown()


def test_requires_initialize():
    with pytest.raises(ResiliencyError):
        with Detector.detection_section("x"):
            pass


def test_double_initialize_rejected():
    Detector.initialize()
    with pytest.raises(ResiliencyError):
        Detector.initialize()


def test_section_timing_and_report():
    Detector.initialize(report_time_interval=1e9)
    for _ in range(8):
        with Detector.detection_section("step", profile_device=False):
            time.sleep(0.002)
    summary = Detector.local_summary()
    assert "sec/step" in summary
    assert summary["sec/step"]["count"] == 8
    assert summary["sec/step"]["median"] >= 0.002
    report = Detector.generate_report()
    assert report is not None
    assert report.section_names == ("sec/step",)
    # single rank: relative score is 1.0 (it IS the reference)
    assert report.relative_section_scores["sec/step"] == pytest.approx(1.0)
    assert not report.identify_stragglers().any


def test_section_observe_device_timing():
    import jax.numpy as jnp

    Detector.initialize(profiling_interval=2)
    for i in range(4):
        with Detector.detection_section("jitted") as sec:
            sec.observe(jnp.ones((4, 4)) * i)
    summary = Detector.local_summary()
    assert summary["sec/jitted"]["count"] == 4
    # entries 0 and 2 profiled device time
    assert summary["dev/jitted"]["count"] == 2


def test_wrap_callables():
    import jax
    import jax.numpy as jnp

    class Trainer:
        def training_step(self, x):
            return jnp.sum(x * 2.0)

    trainer = Trainer()
    Detector.initialize()
    Detector.wrap_callables([CallableId(trainer, "training_step")])
    for _ in range(3):
        out = trainer.training_step(jnp.ones(8))
        assert float(out) == 16.0
    summary = Detector.local_summary()
    assert summary["sec/Trainer.training_step"]["count"] == 3
    assert summary["dev/Trainer.training_step"]["count"] == 3
    Detector.shutdown()
    # unwrapped after shutdown
    assert not hasattr(trainer.training_step, "__wrapped__")


def test_report_interval(monkeypatch):
    Detector.initialize(report_time_interval=0.0)  # report every iteration once locked
    from tpu_resiliency.telemetry import detector as det_mod

    # lock the tracker immediately
    Detector._interval_tracker.interval = 2
    with Detector.detection_section("s", profile_device=False):
        pass
    assert Detector.generate_report_if_interval_elapsed() is None  # iter 1
    assert Detector.generate_report_if_interval_elapsed() is not None  # iter 2


def test_multirank_aggregation_via_store(kv_server):
    """Three simulated ranks publish summaries; rank 0 scores globally."""
    import threading

    from tpu_resiliency.platform.store import CoordStore

    world = 3
    reports = {}

    def run_rank(rank):
        store = CoordStore("127.0.0.1", kv_server.port)
        # simulate per-rank Detector state without the singleton (store path unit)
        from tpu_resiliency.telemetry.detector import Detector as D

        local = {"sec/step": {"median": 0.1 * (4 if rank == 1 else 1), "total": 1.0, "count": 10}}
        ns = "telemetry/round/0"
        store.set_add(f"{ns}/names", ["sec/step"])
        store.set(f"{ns}/summary/{rank}", local)
        store.barrier(f"{ns}/publish", rank, world, 30.0)
        if rank == 0:
            import jax.numpy as jnp

            from tpu_resiliency.telemetry.reporting import ReportGenerator

            summaries = [store.get(f"{ns}/summary/{r}", timeout=30.0) for r in range(world)]
            medians = np.array([[s["sec/step"]["median"]] for s in summaries], np.float32)
            weights = np.array([[s["sec/step"]["total"]] for s in summaries], np.float32)
            counts = np.array([[s["sec/step"]["count"]] for s in summaries], np.int32)
            gen = ReportGenerator(world_size=world, max_signals=4)
            reports[0] = gen.generate_summary_report(
                jnp.asarray(medians), jnp.asarray(weights), jnp.asarray(counts),
                ("sec/step",), rank=0,
            )
        store.close()

    threads = [threading.Thread(target=run_rank, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    report = reports[0]
    stragglers = report.identify_stragglers()
    assert {s.rank for s in stragglers.by_perf} == {1}
    assert report.perf_scores[1] == pytest.approx(0.25, abs=0.01)


@pytest.mark.parametrize("mesh_path", [False, True])
def test_a_report_round_names_its_parts_on_the_profilers_clock(mesh_path, profiler_window):
    """Only a step that really generates a report writes ``tpures/telemetry/report``,
    with the round's parts nested in it in order: rings to medians, the puts and
    the scorer's dispatch, and on the mesh path the reads back (the store path's
    scorer materializes inside ``score``)."""
    device_telemetry = None
    if mesh_path:
        import jax
        from jax.sharding import Mesh

        from tpu_resiliency.telemetry.sharded import MeshTelemetry

        device_telemetry = MeshTelemetry(
            Mesh(np.array(jax.devices()[:1]), ("ranks",)), "ranks", n_ranks=1,
            signal_names=tuple(f"c{i}" for i in range(4)))
    Detector.initialize(report_time_interval=1e9, device_telemetry=device_telemetry)
    with Detector.detection_section("step", profile_device=False):
        pass
    assert Detector.generate_report() is not None  # every program compiled before the window
    with profiler_window() as names:
        with Detector.detection_section("step", profile_device=False):
            pass
        assert Detector.generate_report_if_interval_elapsed() is None  # no report, no annotation
        report = Detector.generate_report()
    assert report.source == ("mesh" if mesh_path else "local")
    ours = [n for n in names if n.startswith("tpures/")]
    assert ours == ["tpures/telemetry/report", "tpures/telemetry/report/summary",
                    "tpures/telemetry/report/score"] + (
                        ["tpures/telemetry/report/materialize"] if mesh_path else [])
