"""Report objects + straggler identification over the on-device scoring results.

The user-facing contract mirrors the reference's ``straggler/reporting.py``:
``Report`` with relative/individual per-section scores and per-rank perf scores, and
``identify_stragglers`` thresholding (default 0.75, ``reporting.py:84-151``) — but the
numbers are produced by the fused device pipeline in ``telemetry/scoring.py`` rather
than host-side loops, and the report additionally carries robust-z and EWMA columns.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from tpu_resiliency.telemetry import scoring


@dataclasses.dataclass(frozen=True)
class StragglerId:
    """One flagged rank (reference ``reporting.py`` StragglerId)."""

    rank: int
    score: float
    z: float = float("nan")
    host: Optional[str] = None

    def __str__(self) -> str:
        host = f" host={self.host}" if self.host else ""
        return f"rank={self.rank}{host} score={self.score:.3f} z={self.z:+.2f}"


@dataclasses.dataclass
class Stragglers:
    """Result of ``Report.identify_stragglers``."""

    by_perf: frozenset[StragglerId]
    by_section: dict[str, frozenset[StragglerId]]

    @property
    def any(self) -> bool:
        return bool(self.by_perf) or any(self.by_section.values())


@dataclasses.dataclass
class Report:
    """One scoring round's results, as seen by one rank.

    ``perf_scores`` / ``z_scores`` / ``ewma_scores`` cover every rank when generated
    with ``gather_on_rank0``-style global visibility (the device pipeline always has
    the global matrix, so unlike the reference there is no extra gather cost).
    """

    rank: int
    world_size: int
    iteration: int
    section_names: tuple[str, ...]
    # this rank's per-section scores
    relative_section_scores: dict[str, float]
    individual_section_scores: dict[str, float]
    # global per-rank columns (None when running local-only)
    perf_scores: Optional[dict[int, float]] = None
    z_scores: Optional[dict[int, float]] = None
    ewma_scores: Optional[dict[int, float]] = None
    # per-rank per-section relative scores, [R, S], optional global view
    global_section_scores: Optional[np.ndarray] = None
    rank_to_host: Optional[dict[int, str]] = None
    #: which path scored it: ``"mesh"`` (per-rank summaries as shards of a mesh
    #: array, reduced by collectives — ``MeshTelemetry``), ``"store"`` (summaries
    #: gathered through the coordination store) or ``"local"`` (one process, its
    #: own summary). A job that asked for the mesh path can tell that it got it.
    source: str = "local"

    def identify_stragglers(
        self,
        perf_threshold: float = scoring.DEFAULT_THRESHOLD,
        section_threshold: float = scoring.DEFAULT_THRESHOLD,
        z_threshold: float = scoring.DEFAULT_Z_THRESHOLD,
    ) -> Stragglers:
        """Flag ranks whose perf score is below threshold OR whose robust-z is an
        outlier, and per-section slow ranks (reference ``identify_stragglers``,
        ``reporting.py:84-151``, extended with the z criterion)."""
        by_perf = set()
        if self.perf_scores:
            for r, s in self.perf_scores.items():
                z = (self.z_scores or {}).get(r, float("nan"))
                if s < perf_threshold or (not np.isnan(z) and z < -z_threshold):
                    by_perf.add(
                        StragglerId(r, s, z, (self.rank_to_host or {}).get(r))
                    )
        by_section: dict[str, frozenset] = {}
        if self.global_section_scores is not None:
            for j, name in enumerate(self.section_names):
                col = self.global_section_scores[:, j]
                flagged = {
                    StragglerId(
                        int(r),
                        float(col[r]),
                        host=(self.rank_to_host or {}).get(int(r)),
                    )
                    for r in np.nonzero(col < section_threshold)[0]
                }
                if flagged:
                    by_section[name] = frozenset(flagged)
        return Stragglers(by_perf=frozenset(by_perf), by_section=by_section)


class ReportGenerator:
    """Stateful scorer: carries EWMA and historical-min across rounds.

    Operates on the global telemetry matrix (``[R, S, W]`` windows or precomputed
    ``[R, S]`` medians+weights) and emits :class:`Report` objects. The device pipeline
    runs entirely under jit; only the final small score vectors are pulled to host to
    build the report (reference analogue: ``ReportGenerator.generate_report``,
    ``reporting.py:421``).
    """

    def __init__(
        self,
        world_size: int,
        max_signals: int,
        *,
        perf_threshold: float = scoring.DEFAULT_THRESHOLD,
        z_threshold: float = scoring.DEFAULT_Z_THRESHOLD,
        ewma_alpha: float = scoring.DEFAULT_EWMA_ALPHA,
        use_pallas: bool = False,
        rank_to_host: Optional[dict[int, str]] = None,
    ):
        import jax.numpy as jnp

        self.world_size = world_size
        self.max_signals = max_signals
        self.perf_threshold = perf_threshold
        self.z_threshold = z_threshold
        self.ewma_alpha = ewma_alpha
        self.use_pallas = use_pallas
        self.rank_to_host = rank_to_host
        self.iteration = 0
        self._ewma = jnp.ones((world_size,))
        self._hist_min = jnp.full((world_size, max_signals), jnp.inf)

    def reset(self) -> None:
        import jax.numpy as jnp

        self._ewma = jnp.ones((self.world_size,))
        self._hist_min = jnp.full((self.world_size, self.max_signals), jnp.inf)

    def _hist_slice(self, s: int):
        return self._hist_min[:, :s]

    def _carry(self, res: scoring.TelemetryScores, s: int) -> None:
        self._ewma = res.ewma
        self._hist_min = self._hist_min.at[:, :s].set(res.historical_min)
        self.iteration += 1

    def score(self, data, counts) -> scoring.TelemetryScores:
        """Run one scoring round on ``data [R,S,W]``/``counts [R,S]`` (device arrays)."""
        s = data.shape[1]
        mw = None
        if self.use_pallas:
            from tpu_resiliency.ops.scoring_pallas import fused_median_weights

            mw = fused_median_weights(data, counts)
        if mw is None:
            res = scoring.score_round_jit(
                data,
                counts,
                self._ewma,
                self._hist_slice(s),
                threshold=self.perf_threshold,
                z_threshold=self.z_threshold,
                alpha=self.ewma_alpha,
            )
        else:
            res = scoring.score_round(
                data,
                counts,
                self._ewma,
                self._hist_slice(s),
                threshold=self.perf_threshold,
                z_threshold=self.z_threshold,
                alpha=self.ewma_alpha,
                medians_and_weights=mw,
            )
        self._carry(res, s)
        return res

    def score_summary(self, medians, weights, counts) -> scoring.TelemetryScores:
        """Score precomputed per-(rank, signal) ``medians``/``weights`` summaries
        (the store-aggregated multi-host path; window reduction already done).
        One compiled program per shape (``score_summary_jit``), not dozens of
        eager dispatches per report."""
        s = medians.shape[1]
        res = scoring.score_summary_jit(
            medians,
            weights,
            counts,
            self._ewma,
            self._hist_slice(s),
            threshold=self.perf_threshold,
            z_threshold=self.z_threshold,
            alpha=self.ewma_alpha,
        )
        self._carry(res, s)
        return res

    def generate_summary_report(
        self, medians, weights, counts, section_names, *, rank: int = 0
    ) -> Report:
        res = self.score_summary(medians, weights, counts)
        return self._materialize(res, section_names, rank)

    def generate_report(
        self, data, counts, section_names, *, rank: int = 0
    ) -> Report:
        """Score and materialize a :class:`Report` for ``rank``."""
        res = self.score(data, counts)
        return self._materialize(res, section_names, rank)

    def _materialize(self, res: scoring.TelemetryScores, section_names, rank: int) -> Report:
        host = scoring.scores_to_host(res)
        section = np.asarray(host.section_scores)
        indiv = np.asarray(host.individual_section_scores)
        perf = np.asarray(host.perf)
        z = np.asarray(host.z)
        ewma = np.asarray(host.ewma)
        names = tuple(section_names)
        s = len(names)
        return Report(
            rank=rank,
            world_size=self.world_size,
            iteration=self.iteration,
            section_names=names,
            relative_section_scores={n: float(section[rank, j]) for j, n in enumerate(names)},
            individual_section_scores={n: float(indiv[rank, j]) for j, n in enumerate(names)},
            perf_scores={r: float(perf[r]) for r in range(self.world_size)},
            z_scores={r: float(z[r]) for r in range(self.world_size)},
            ewma_scores={r: float(ewma[r]) for r in range(self.world_size)},
            global_section_scores=section[:, :s],
            rank_to_host=self.rank_to_host,
        )
