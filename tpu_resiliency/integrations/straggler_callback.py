"""Straggler-detection callback: per-step section timing + periodic scored reports.

Analogue of the reference's ``StragglerDetectionCallback``
(``ptl_resiliency/straggler_det_callback.py``): wraps the training step into a
detection section (``:91-98`` via ``Detector.wrap_callables``; here the loop hooks
bracket the step directly), calls ``generate_report_if_interval_elapsed`` each step,
logs best/worst scores, exports per-rank scores into ``ctx.metrics``, and optionally
requests a cooperative stop when stragglers are found (``trainer.should_stop``)."""

from __future__ import annotations

from typing import Optional

from tpu_resiliency.integrations.loop import Callback, LoopContext
from tpu_resiliency.telemetry.detector import Detector
from tpu_resiliency.utils.events import record as record_event
from tpu_resiliency.utils.logging import get_logger

log = get_logger(__name__)


class StragglerDetectionCallback(Callback):
    def __init__(
        self,
        report_time_interval: float = 300.0,
        calc_relative_scores: bool = True,
        calc_individual_scores: bool = False,
        threshold: float = 0.75,
        stop_if_detected: bool = False,
        export_metrics: bool = True,
        profiling_interval: int = 1,
        section_name: str = "train_step",
        store=None,
        use_pallas: bool = False,
        health_policy=None,
        use_device_mesh: bool = False,
        mesh_signal_capacity: int = 16,
        profile_programs_every: Optional[int] = None,
        profile_ops: bool = False,
    ):
        """``health_policy``: an optional
        :class:`~tpu_resiliency.telemetry.policy.HealthVectorPolicy` fed every
        report — its sinks close the loop to restart demotion / node exclusion /
        replication avoidance (BASELINE target 5).

        ``profile_programs_every``: every Nth step, bracket the step in an XLA
        profiler window and feed per-compiled-program device times into the scored
        matrix as ``prog/...`` signals (the CUPTI capture-every-Nth-entry analogue,
        reference ``profiling_interval``). What a window costs the step's path on
        a v5e (chip runs, PR 26): 0.04 s to open it, and of the 0.33 s its close
        takes only what the next N - 1 steps do not cover, because the close runs
        on the profiler's closer thread beside them and the next window waits for
        it: 0.12 s at N = 3 with 0.1 s steps, nothing once N - 1 steps take
        0.33 s. A window's samples join the rings when its close is done, at the
        end of one of the next N steps at the latest.

        ``profile_ops``: with ``profile_programs_every``, additionally feed
        per-op/scope device times from the same windows as ``op/...`` signals,
        keyed by the scope of each instruction's ``op_name`` in the HLO the
        trace embeds (``jvp()/while/body``, a ``jax.named_scope`` path where
        the model has one) — one granularity below programs, the closest XLA
        analogue of the reference's per-kernel CUPTI stream. Parse cost only
        (``parse_s`` of the ``profiler_window`` event, on the closer thread); no
        extra tracing overhead. With
        ``use_device_mesh`` the op signals count against
        ``mesh_signal_capacity`` like every other column — size it for
        sec/ + dev/ + prog/ + one op/<scope> per named scope, or the first
        over-capacity report permanently drops the mesh path for the run and
        falls back to the store gather (logged, training never interrupted).

        ``use_device_mesh``: route report rounds through the mesh-sharded scoring
        path (:class:`~tpu_resiliency.telemetry.sharded.MeshTelemetry`) instead of
        the per-rank store gather. Requires one JAX process per rank
        (``jax.process_count() == world_size``: each worker of a multi-rank job
        called ``jax.distributed.initialize``; a one-worker job qualifies as it
        is); outside that configuration the callback logs once and falls back to
        the store path. ``mesh_signal_capacity`` caps the number of distinct
        timed signals the compiled scorer carries. Which path scored a report is
        its ``source`` (``Report.source``), also on the ``straggler_report``
        event beside the profiler windows' own ``profile_source``."""
        self.threshold = threshold
        self.stop_if_detected = stop_if_detected
        self.export_metrics = export_metrics
        self.section_name = section_name
        self.health_policy = health_policy
        self.use_device_mesh = use_device_mesh
        self.mesh_signal_capacity = mesh_signal_capacity
        self.profile_programs_every = profile_programs_every
        self.profile_ops = profile_ops
        self._program_profiler = None
        #: profiler windows that could not start / could not be parsed: a
        #: profiling fault never breaks a step, and never goes uncounted
        self.profile_skipped = 0
        self.profile_dropped = 0
        self._step_count = 0
        self._init_kwargs = dict(
            scores_to_compute=(
                (["relative_perf_scores"] if calc_relative_scores else [])
                + (["individual_perf_scores"] if calc_individual_scores else [])
            ),
            report_time_interval=report_time_interval,
            profiling_interval=profiling_interval,
            store=store,
            use_pallas=use_pallas,
        )
        self._section = None
        self.last_report = None

    def _build_mesh_telemetry(self, ctx: LoopContext):
        """One telemetry row per rank on a one-device-per-process mesh — the
        configuration ``Detector._generate_mesh_report`` scores with zero per-rank
        store gathers (summaries travel as shards, reduced by XLA collectives)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from tpu_resiliency.telemetry.sharded import MeshTelemetry

        if jax.process_count() != ctx.world_size:
            log.info(
                "use_device_mesh requested but job is not one-JAX-process-per-rank "
                f"(process_count={jax.process_count()}, world={ctx.world_size}); "
                "falling back to the store summary path"
            )
            return None
        per_proc = [
            [d for d in jax.devices() if d.process_index == p][0]
            for p in range(ctx.world_size)
        ]
        mesh = Mesh(np.array(per_proc), ("ranks",))
        return MeshTelemetry(
            mesh,
            "ranks",
            n_ranks=ctx.world_size,
            signal_names=tuple(f"c{i}" for i in range(self.mesh_signal_capacity)),
        )

    def on_train_start(self, ctx: LoopContext) -> None:
        device_telemetry = (
            self._build_mesh_telemetry(ctx) if self.use_device_mesh else None
        )
        Detector.initialize(
            rank=ctx.rank,
            world_size=ctx.world_size,
            device_telemetry=device_telemetry,
            **self._init_kwargs,
        )

    def on_step_start(self, ctx: LoopContext) -> None:
        if self.profile_programs_every:
            if self._program_profiler is None:
                from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler

                self._program_profiler = DeviceTimeProfiler(
                    collect_ops=self.profile_ops
                )
            if self._step_count % self.profile_programs_every == 0:
                try:
                    self._program_profiler.start()
                except Exception:
                    # e.g. the process-global profiler is taken by user tracing
                    self.profile_skipped += 1
                    log.warning("profiler window skipped", exc_info=True)
        self._section = Detector.detection_section(self.section_name)
        self._section.__enter__()

    def on_step_end(self, ctx: LoopContext) -> None:
        if self._section is not None:
            self._section.__exit__(None, None, None)
            self._section = None
        self._step_count += 1
        prof = self._program_profiler
        if prof is not None:
            # The close runs on the profiler's closer thread, beside the next
            # steps; whatever it has finished by now joins the rings here, on the
            # loop's thread.
            if not prof.closing:
                self._counted(prof.wait)
            prof.stop_async()
            Detector.record_program_samples(prof.drain())
            if self.profile_ops:
                Detector.record_op_samples(prof.drain_ops())
        report = Detector.generate_report_if_interval_elapsed()
        if report is not None:
            self._handle_report(ctx, report)

    def _counted(self, close) -> bool:
        """Run the profiler's ``wait`` or ``stop``; False, and counted, when a
        window's trace had to be dropped (no device plane on a TPU backend,
        unparseable, no trace written)."""
        try:
            close()
        except Exception:
            self.profile_dropped += 1
            log.warning("profiler window dropped", exc_info=True)
            return False
        return True

    def _close_profiler_window(self) -> bool:
        """Leave no profiler session open and no closer thread behind: True
        unless a trace had to be dropped."""
        prof = self._program_profiler
        if prof is None:
            return True
        ok = self._counted(prof.wait)  # a deferred close's fault, before this one's
        return self._counted(prof.stop) and ok

    def on_exception(self, ctx: LoopContext, exc: BaseException) -> None:
        # A step that dies mid-window, or while the closer is still at work on
        # the last one, must not leak the process-global JAX trace: the
        # restarted loop's fresh profiler would find it active and crash.
        self._close_profiler_window()

    def on_train_end(self, ctx: LoopContext) -> None:
        if self._section is not None:
            self._section.__exit__(None, None, None)
            self._section = None
        self._close_profiler_window()
        Detector.shutdown()

    # -- report handling ---------------------------------------------------

    def _handle_report(self, ctx: LoopContext, report) -> None:
        self.last_report = report
        flat = dict(report.perf_scores or {})
        if flat:
            best = max(flat, key=flat.get)
            worst = min(flat, key=flat.get)
            log.info(
                f"straggler report: best rank {best}={flat[best]:.3f} "
                f"worst rank {worst}={flat[worst]:.3f}"
            )
            if self.export_metrics:
                ctx.metrics["straggler/best_score"] = float(flat[best])
                ctx.metrics["straggler/worst_score"] = float(flat[worst])
        stragglers = report.identify_stragglers(
            perf_threshold=self.threshold, section_threshold=self.threshold
        )
        if stragglers.by_perf or stragglers.by_section:
            log.warning(f"stragglers detected: {stragglers}")
            if self.export_metrics:
                ctx.metrics["straggler/detected"] = stragglers
            if self.stop_if_detected:
                ctx.should_stop = True
        # The machine-readable twin of the log lines above, on the same
        # structured JSONL stream the launcher narrates to ($TPU_RESILIENCY_
        # EVENTS_FILE) — the role the reference fills with its torchelastic
        # events/metrics streams + PTL logger export
        # (straggler_det_callback.py enable_ptl_logging, events/ metrics/).
        record_event(
            "telemetry",
            "straggler_report",
            step=ctx.step,
            # String keys: json.dumps would coerce int keys anyway, so use the
            # on-disk schema everywhere — in-process sinks and JSONL readers
            # index the same way.
            perf_scores={str(k): float(v) for k, v in flat.items()},
            stragglers_by_perf=sorted(s.rank for s in stragglers.by_perf),
            stragglers_by_section={
                name: sorted(s.rank for s in ids)
                for name, ids in stragglers.by_section.items()
            },
            # Which path scored it and what it scored: a job that asked for
            # the mesh path, or for device-plane program times, reads here
            # whether it got them.
            report_source=report.source,
            signals=list(report.section_names),
            **(
                {
                    "profile_source": self._program_profiler.source,
                    "profile_windows": self._program_profiler.windows,
                    "profile_skipped": self.profile_skipped,
                    "profile_dropped": self.profile_dropped,
                }
                if self._program_profiler is not None else {}
            ),
        )
        if self.health_policy is not None:
            self.health_policy.observe(report)
