"""Mesh-sharded, device-resident telemetry: the north-star ingestion + scoring path.

The reference aggregates straggler telemetry by packing host dicts into tensors and
running ``all_reduce``/``gather`` through NCCL with Python pack/unpack loops on every
report (``straggler/reporting.py:255-296,338-419``); round 1 of this framework still
gathered pickled summaries through the coordination store one rank at a time. This
module is the replacement: telemetry lives in HBM as a window-major ``[W, R, S]``
ring array **sharded over a mesh axis** (each device owns its ranks' rows), is appended to from
inside the jitted train step (donated carry — no host round-trip per step), and is
scored by the fused pipeline under ``jax.shard_map`` where the cross-rank reductions
are XLA collectives over ICI (``telemetry/scoring.py``). Host Python touches the data
exactly once per *report* — pulling the final [R]-sized score vectors to build a
:class:`~tpu_resiliency.telemetry.reporting.Report`.

Usage in a train loop::

    mt = MeshTelemetry(mesh, axis="dp", n_ranks=R, signal_names=("step", "ckpt"))
    tstate = mt.init_state()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(tstate, params, batch):
        ...
        tstate = mt.push(tstate, jnp.stack([step_ms, ckpt_ms], -1))  # in-jit
        return tstate, params, loss

    ...every report interval...
    tstate, report = mt.generate_report(tstate)   # one device->host transfer

Multi-host: every process holds the shard rows of its own local devices (standard JAX
global-array semantics), so "publishing" a host-measured timing means writing it into
the local shard of the next ``push`` values — the cross-host exchange happens inside
the compiled scoring program, not through a KV server.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import numpy as np

from tpu_resiliency.telemetry import scoring
from tpu_resiliency.telemetry.reporting import Report
from tpu_resiliency.utils.tracing import annotate

DEFAULT_WINDOW = 32


@dataclasses.dataclass
class TelemetryState:
    """The device-resident carry: rings + scoring state, sharded over the rank axis.

    Ring layout is ``[W, R, S]`` (window-major): one push writes the contiguous
    ``[1, R, S]`` slab at the cursor via ``dynamic_update_slice`` — O(R·S) bytes
    touched in-place on the donated buffer, where an ``[R, S, W]`` one-hot scatter
    re-materialized the whole O(R·S·W) ring every step (the round-2 push cost).
    The scorer consumes ``[R, S, W]``; the transpose happens once per *report*,
    amortized to noise."""

    data: Any  # f32 [W, R, S] timing windows, window-major
    counts: Any  # i32 [R, S] valid samples per window
    cursor: Any  # i32 [] scalar ring write position (ranks advance in lockstep)
    ewma: Any  # f32 [R] smoothed perf score, carried across reports
    hist_min: Any  # f32 [R, S] rank-historical best medians


def _register() -> None:
    import jax

    try:
        jax.tree_util.register_pytree_node(
            TelemetryState,
            lambda s: ((s.data, s.counts, s.cursor, s.ewma, s.hist_min), None),
            lambda _, c: TelemetryState(*c),
        )
    except ValueError:
        pass


_register()


class MeshTelemetry:
    """Owner of a sharded telemetry state and its compiled push/score programs.

    ``n_ranks`` is the number of telemetry rows (typically one per worker rank or one
    per device) and must divide evenly over ``mesh.shape[axis]``. Scores, EWMA, and
    historical minima carry across reports inside the state itself, so the whole
    report round is one compiled program: score → reset rings → new state.
    """

    def __init__(
        self,
        mesh,
        axis: str,
        *,
        n_ranks: Optional[int] = None,
        signal_names: Sequence[str] = ("step",),
        window: int = DEFAULT_WINDOW,
        threshold: float = scoring.DEFAULT_THRESHOLD,
        z_threshold: float = scoring.DEFAULT_Z_THRESHOLD,
        ewma_alpha: float = scoring.DEFAULT_EWMA_ALPHA,
        rank_to_host: Optional[dict[int, str]] = None,
        use_pallas: Optional[bool] = None,
    ):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis_size = mesh.shape[axis]
        self.mesh = mesh
        self.axis = axis
        self.n_ranks = int(n_ranks if n_ranks is not None else axis_size)
        if self.n_ranks % axis_size:
            raise ValueError(
                f"n_ranks={self.n_ranks} must divide over mesh axis "
                f"{axis!r} (size {axis_size})"
            )
        self.signal_names = tuple(signal_names)
        self.n_signals = len(self.signal_names)
        self.window = int(window)
        self.threshold = threshold
        self.z_threshold = z_threshold
        self.ewma_alpha = ewma_alpha
        self.rank_to_host = rank_to_host
        self.iteration = 0

        if use_pallas is None:
            # The fused Pallas window reduction beat XLA's sort lowering 2x on
            # TPU at the default window (2026-07-31 capture, BASELINE.md);
            # other backends can't run the kernel, and the kernel tiles the
            # rank axis so incompatible per-shard rank counts fall back to the
            # shape-generic XLA path, as do windows past the O(W²) crossover
            # (scoring_pallas.MAX_WINDOW).
            from tpu_resiliency.ops.scoring_pallas import pallas_supported

            use_pallas = (
                jax.default_backend() == "tpu"
                and pallas_supported(
                    self.n_ranks // axis_size,
                    window=self.window,
                    signals=self.n_signals,
                )
            )
        self.use_pallas = use_pallas
        self._row_sharding = NamedSharding(mesh, P(axis))
        self._scorer = scoring.make_sharded_scorer(
            mesh,
            axis,
            threshold=threshold,
            z_threshold=z_threshold,
            alpha=ewma_alpha,
            use_pallas=use_pallas,
        )
        self._push = jax.jit(self._push_impl, donate_argnums=(0,))
        self._score_reset = jax.jit(self._score_reset_impl, donate_argnums=(0,))
        # Report materialization must read every rank's scores from host Python, but
        # scorer outputs are sharded P(axis) — in a multi-process job each process
        # only holds its own rows and np.asarray on the rest is an error. This
        # jitted identity re-lays the score pytree out fully replicated (XLA inserts
        # the all-gather), making the report a legal single host transfer anywhere.
        replicated = NamedSharding(mesh, P())
        self._replicate = jax.jit(
            lambda s: s,
            out_shardings=scoring.TelemetryScores(*([replicated] * 7)),
        )
        self._summary_scorer = None
        self._summary_state = None  # (ewma [R], hist_min [R, S]) for the summary path

    # -- state lifecycle ---------------------------------------------------

    def init_state(self) -> TelemetryState:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        r, s, w = self.n_ranks, self.n_signals, self.window
        shard = self._row_sharding
        data_shard = NamedSharding(self.mesh, P(None, self.axis))
        replicated = NamedSharding(self.mesh, P())

        def init():
            return TelemetryState(
                data=jnp.zeros((w, r, s), jnp.float32),
                counts=jnp.zeros((r, s), jnp.int32),
                cursor=jnp.zeros((), jnp.int32),
                ewma=jnp.ones((r,), jnp.float32),
                hist_min=jnp.full((r, s), jnp.inf, jnp.float32),
            )

        out_shardings = TelemetryState(data_shard, shard, replicated, shard, shard)
        return jax.jit(init, out_shardings=out_shardings)()

    # -- in-jit ingestion --------------------------------------------------

    @staticmethod
    def _push_impl(state: TelemetryState, values) -> TelemetryState:
        import jax.numpy as jnp
        from jax import lax

        w = state.data.shape[0]
        values = jnp.asarray(values, state.data.dtype)
        idx = state.cursor % w
        # Contiguous [1, R, S] slab write at the cursor: with the donated carry this
        # lowers to an in-place dynamic-update-slice touching O(R·S) bytes; the
        # start offset is only in the unsharded window axis, so the update shards
        # over the rank axis with no collectives and no host sync.
        return TelemetryState(
            data=lax.dynamic_update_slice(state.data, values[None], (idx, 0, 0)),
            counts=jnp.minimum(state.counts + 1, w),
            cursor=state.cursor + 1,
            ewma=state.ewma,
            hist_min=state.hist_min,
        )

    def push(self, state: TelemetryState, values) -> TelemetryState:
        """Append one ``[R, S]`` sample row (one measurement per rank per signal).

        Jittable and donated — call it from inside the train step for
        device-computed signals, or standalone for host-measured timings.
        """
        return self._push(state, values)

    # -- scoring -----------------------------------------------------------

    def _score_reset_impl(self, state: TelemetryState):
        import jax.numpy as jnp

        # The scorer consumes [R, S, W]; this transpose is per-report, not per-step,
        # and stays local to each shard (the window axis is unsharded).
        data_rsw = jnp.transpose(state.data, (1, 2, 0))
        scores = self._scorer(data_rsw, state.counts, state.ewma, state.hist_min)
        new_state = TelemetryState(
            data=state.data,  # stale samples are masked by counts=0
            counts=jnp.zeros_like(state.counts),
            cursor=jnp.zeros_like(state.cursor),
            ewma=scores.ewma,
            hist_min=scores.historical_min,
        )
        return new_state, scores

    def score(self, state: TelemetryState):
        """One report round: returns ``(new_state, TelemetryScores)`` — rings reset,
        EWMA/historical-min carried, every output still sharded over the mesh."""
        self.iteration += 1
        return self._score_reset(state)

    # -- multi-host summary path ------------------------------------------

    def _build_summary_scorer(self):
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def body(medians, weights, counts, ewma, hist_min):
            dummy = jnp.zeros(medians.shape + (1,), medians.dtype)
            return scoring.score_round(
                dummy,
                counts,
                ewma,
                hist_min,
                threshold=self.threshold,
                z_threshold=self.z_threshold,
                alpha=self.ewma_alpha,
                medians_and_weights=(medians, weights),
                axis_name=self.axis,
            )

        spec = P(self.axis)
        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(spec,) * 5,
            out_specs=scoring.TelemetryScores(*([spec] * 7)),
        )
        return jax.jit(sharded)

    def score_local_summary(self, medians, weights, counts):
        """Score per-rank summaries fed process-locally — the multi-host Detector
        path with zero host gathers.

        Each process passes the ``[local_ranks, S]`` median/weight/count rows of the
        ranks it hosts; rows assemble into the global mesh-sharded array with
        ``jax.make_array_from_process_local_data`` (no cross-host transfer — each
        process donates its shard) and the cross-rank reductions run as ICI/DCN
        collectives inside the compiled scoring program. Replaces the reference's
        store/NCCL summary gather (``reporting.py:338-419``). EWMA and historical-min
        for this path are carried as sharded device arrays inside this object.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._summary_scorer is None:
            self._summary_scorer = self._build_summary_scorer()
        r, s = self.n_ranks, self.n_signals
        shard = self._row_sharding
        if self._summary_state is None:
            def init():
                return (
                    jnp.ones((r,), jnp.float32),
                    jnp.full((r, s), jnp.inf, jnp.float32),
                )

            self._summary_state = jax.jit(
                init, out_shardings=(shard, NamedSharding(self.mesh, P(self.axis)))
            )()
        ewma, hist_min = self._summary_state
        to_global = lambda x, dt: jax.make_array_from_process_local_data(  # noqa: E731
            shard, np.ascontiguousarray(x, dtype=dt)
        )
        scores = self._summary_scorer(
            to_global(medians, np.float32),
            to_global(weights, np.float32),
            to_global(counts, np.int32),
            ewma,
            hist_min,
        )
        self._summary_state = (scores.ewma, scores.historical_min)
        self.iteration += 1
        return scores

    # -- report materialization -------------------------------------------

    def generate_report(self, state: TelemetryState, *, rank: int = 0):
        """Score and build a host-side :class:`Report` (the single device→host hop).

        Returns ``(new_state, report)``.
        """
        new_state, scores = self.score(state)
        return new_state, self.materialize(scores, rank=rank)

    def report_from_summary(
        self, medians, weights, counts, *, rank: int = 0,
        signal_names: Optional[Sequence[str]] = None,
    ) -> Report:
        """Multi-host summary round: score process-local rows, build the Report.

        ``signal_names`` overrides the construction-time names (the Detector bridge
        passes the globally-agreed column list, which can be shorter than this
        object's column capacity — the tail columns carry counts=0 and score 1.0).
        """
        with annotate("tpures/telemetry/report/score"):
            scores = self.score_local_summary(medians, weights, counts)
        with annotate("tpures/telemetry/report/materialize"):
            return self.materialize(scores, rank=rank, signal_names=signal_names)

    def materialize(
        self, scores: scoring.TelemetryScores, *, rank: int = 0,
        signal_names: Optional[Sequence[str]] = None,
    ) -> Report:
        scores = self._replicate(scores)
        host = scoring.scores_to_host(scores)
        section = np.asarray(host.section_scores)
        indiv = np.asarray(host.individual_section_scores)
        perf = np.asarray(host.perf)
        z = np.asarray(host.z)
        ewma = np.asarray(host.ewma)
        names = tuple(signal_names) if signal_names is not None else self.signal_names
        return Report(
            rank=rank,
            world_size=self.n_ranks,
            iteration=self.iteration,
            section_names=names,
            relative_section_scores={
                n: float(section[rank, j]) for j, n in enumerate(names)
            },
            individual_section_scores={
                n: float(indiv[rank, j]) for j, n in enumerate(names)
            },
            perf_scores={r: float(perf[r]) for r in range(self.n_ranks)},
            z_scores={r: float(z[r]) for r in range(self.n_ranks)},
            ewma_scores={r: float(ewma[r]) for r in range(self.n_ranks)},
            global_section_scores=section[:, : len(names)],
            rank_to_host=self.rank_to_host,
            source="mesh",
        )
