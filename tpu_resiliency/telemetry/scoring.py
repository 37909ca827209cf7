"""On-device telemetry scoring: the north-star pipeline.

Re-implements the *scoring contract* of the reference's ``straggler/reporting.py`` as a
single jittable JAX pipeline over a ``[ranks, signals]`` telemetry matrix, instead of
host-side Python dict/tensor pack-unpack loops + ``all_reduce``/``gather``
(``reporting.py:196-296,338-419``):

- per-signal **relative score** = (min over ranks of the signal's median) / local median
  (reference ``reporting.py:196-217``), in (0, 1], 1.0 = fastest rank;
- **individual score** = rank-historical minimum median / current median
  (reference ``reporting.py:298``);
- per-rank **perf score** = total-time-weighted mean of relative scores over signals the
  rank observed (the reference's GPU score, ``reporting.py:219-253``);
- **robust-z** of perf scores across ranks (z = (x − median) / (1.4826·MAD)) and an
  **EWMA** over report rounds — the anomaly-scoring additions from BASELINE.json's
  north star, which the reference lacks (it only thresholds raw scores);
- **straggler mask** = perf score below threshold (reference default 0.75,
  ``reporting.py:84-151``) or robust-z below −z_threshold.

Two execution modes share this one pipeline:

- **single-program** (``axis_name=None``): the ``[R, ...]`` matrix lives on one chip
  (or is fully replicated) and the cross-rank reductions are plain axis-0 ops in one
  fused XLA program;
- **mesh-sharded** (``axis_name='rank axis'``): the matrix is sharded over a mesh axis
  and the function runs inside ``jax.shard_map`` — the same reductions become XLA
  collectives over ICI (``lax.pmin`` for the reference-min, a tiny ``all_gather`` of
  the [R] perf vector for the median/MAD), replacing the reference's host-side
  ``all_reduce``/``gather`` (``reporting.py:255-296,338-419``) with zero host hops.
  Use :func:`score_round_sharded` to apply it to mesh-sharded arrays.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-12
MAD_SCALE = 1.4826  # makes MAD a consistent sigma estimator under normality
# Perf scores live in (0, 1]; when every healthy rank scores identically the MAD
# degenerates to ~0 and float jitter (1e-7-ish) over EPS would z-flag the whole
# fleet. The floor says: deviations under ~3e-3 in score units are never outliers.
MAD_FLOOR = 1e-3
DEFAULT_THRESHOLD = 0.75  # reference identify_stragglers default (reporting.py:84)
DEFAULT_Z_THRESHOLD = 3.0
DEFAULT_EWMA_ALPHA = 0.5


def masked_median(data: jax.Array, counts: jax.Array) -> jax.Array:
    """Median over the last axis, honoring per-row valid-sample counts.

    ``data``: f32 [..., W] ring-buffer windows (insertion order irrelevant);
    ``counts``: i32 [...] number of valid samples in each window (0 ⇒ result inf).

    Invalid slots are sorted to +inf; the median of ``n`` valid samples is the mean of
    elements ``(n-1)//2`` and ``n//2`` of the sorted valid prefix.
    """
    w = data.shape[-1]
    pos = jnp.arange(w, dtype=jnp.int32)
    valid = pos < counts[..., None]
    padded = jnp.where(valid, data, jnp.inf)
    s = jnp.sort(padded, axis=-1)
    lo_idx = jnp.maximum(counts - 1, 0) // 2
    hi_idx = counts // 2
    lo = jnp.take_along_axis(s, lo_idx[..., None], axis=-1)[..., 0]
    hi = jnp.take_along_axis(s, hi_idx[..., None], axis=-1)[..., 0]
    med = 0.5 * (lo + hi)
    return jnp.where(counts > 0, med, jnp.inf)


def masked_total(data: jax.Array, counts: jax.Array) -> jax.Array:
    """Sum over the last axis honoring valid counts (the per-signal time weight)."""
    w = data.shape[-1]
    pos = jnp.arange(w, dtype=jnp.int32)
    valid = pos < counts[..., None]
    return jnp.where(valid, data, 0.0).sum(axis=-1)


def relative_scores(
    medians: jax.Array, valid: jax.Array, axis_name: Optional[str] = None
) -> jax.Array:
    """[R, S] relative scores vs the fastest rank per signal.

    The reference computes the reference-median as an all-reduce MIN over ranks of each
    signal's median (``reporting.py:255-296``); here that is a masked ``min`` along the
    rank axis — lowered to an ICI ``pmin`` collective when the rank axis is sharded
    over a mesh (``axis_name``).
    """
    ref = jnp.min(jnp.where(valid, medians, jnp.inf), axis=0, keepdims=True)
    if axis_name is not None:
        ref = lax.pmin(ref, axis_name)
    scores = ref / jnp.maximum(medians, EPS)
    # Signals nobody measured have ref=inf; signals this rank didn't measure score 1.
    scores = jnp.where(jnp.isfinite(ref), scores, 1.0)
    return jnp.clip(jnp.where(valid, scores, 1.0), 0.0, 1.0)


def individual_scores(
    medians: jax.Array, valid: jax.Array, historical_min: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Rank-local scores vs the rank's own best-ever median (reference
    ``_update_local_min_times``, ``reporting.py:298``). Returns (scores, new_min)."""
    new_min = jnp.where(valid, jnp.minimum(historical_min, medians), historical_min)
    scores = new_min / jnp.maximum(medians, EPS)
    return jnp.clip(jnp.where(valid, scores, 1.0), 0.0, 1.0), new_min


def perf_scores(section_scores: jax.Array, weights: jax.Array, valid: jax.Array) -> jax.Array:
    """[R] per-rank score: total-time-weighted mean over observed signals
    (the reference GPU score, ``reporting.py:219-253``)."""
    w = jnp.where(valid, weights, 0.0)
    denom = jnp.maximum(w.sum(axis=1), EPS)
    return (section_scores * w).sum(axis=1) / denom


def robust_z(x: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Median/MAD z-score along the rank axis.

    The median is not an associative reduction, so the sharded path all-gathers the per-
    rank perf vector — R floats over ICI, the one unavoidable full-exchange, and tiny
    (16 KB at 4096 ranks) next to the [R,S,W] telemetry it replaces on the host path.
    """
    full = x if axis_name is None else lax.all_gather(x, axis_name, tiled=True)
    med = jnp.median(full)
    mad = jnp.median(jnp.abs(full - med))
    return (x - med) / jnp.maximum(MAD_SCALE * mad, MAD_FLOOR)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TelemetryScores:
    """Result pytree of one scoring round."""

    section_scores: Any  # f32 [R, S] relative score per signal
    individual_section_scores: Any  # f32 [R, S] vs rank-historical best
    perf: Any  # f32 [R]   weighted per-rank score
    z: Any  # f32 [R]   robust-z of perf across ranks
    ewma: Any  # f32 [R]   smoothed perf score
    straggler: Any  # bool [R]
    historical_min: Any  # f32 [R, S] carried state

    def tree_flatten(self):
        return (
            (
                self.section_scores,
                self.individual_section_scores,
                self.perf,
                self.z,
                self.ewma,
                self.straggler,
                self.historical_min,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def score_round(
    data: jax.Array,
    counts: jax.Array,
    prev_ewma: jax.Array,
    historical_min: jax.Array,
    *,
    threshold: float = DEFAULT_THRESHOLD,
    z_threshold: float = DEFAULT_Z_THRESHOLD,
    alpha: float = DEFAULT_EWMA_ALPHA,
    medians_and_weights: Optional[tuple[jax.Array, jax.Array]] = None,
    axis_name: Optional[str] = None,
) -> TelemetryScores:
    """The fused scoring pipeline over raw telemetry windows.

    ``data``: f32 [R, S, W] per-rank per-signal timing windows;
    ``counts``: i32 [R, S] valid samples per window;
    ``prev_ewma``: f32 [R] (start with ones);
    ``historical_min``: f32 [R, S] (start with +inf).

    ``medians_and_weights`` short-circuits the reduction stage with precomputed
    ``(medians [R,S], weights [R,S])`` — the hook used by the Pallas kernel path.

    ``axis_name`` marks the rank axis as mesh-sharded: the function must then be
    called inside ``shard_map`` (see :func:`score_round_sharded`), R becomes the
    *local* shard size, and cross-rank reductions ride ICI collectives.
    """
    if medians_and_weights is None:
        medians = masked_median(data, counts)
        weights = masked_total(data, counts)
    else:
        medians, weights = medians_and_weights
    valid = counts > 0
    section = relative_scores(medians, valid, axis_name)
    indiv, new_min = individual_scores(medians, valid, historical_min)
    perf = perf_scores(section, weights, valid)
    z = robust_z(perf, axis_name)
    ewma = alpha * perf + (1.0 - alpha) * prev_ewma
    straggler = (perf < threshold) | (z < -z_threshold)
    return TelemetryScores(
        section_scores=section,
        individual_section_scores=indiv,
        perf=perf,
        z=z,
        ewma=ewma,
        straggler=straggler,
        historical_min=new_min,
    )


@functools.partial(jax.jit, static_argnames=("threshold", "z_threshold", "alpha"))
def score_round_jit(
    data,
    counts,
    prev_ewma,
    historical_min,
    threshold: float = DEFAULT_THRESHOLD,
    z_threshold: float = DEFAULT_Z_THRESHOLD,
    alpha: float = DEFAULT_EWMA_ALPHA,
):
    return score_round(
        data,
        counts,
        prev_ewma,
        historical_min,
        threshold=threshold,
        z_threshold=z_threshold,
        alpha=alpha,
    )


def scores_to_host(res: "TelemetryScores") -> "TelemetryScores":
    """ONE batched device->host transfer of a scores pytree. Report materializers
    must use this instead of per-array np.asarray: each per-array transfer is its
    own blocking round trip to the device, seven per report."""
    return jax.device_get(res)


@functools.partial(jax.jit, static_argnames=("threshold", "z_threshold", "alpha"))
def score_summary_jit(
    medians,
    weights,
    counts,
    prev_ewma,
    historical_min,
    threshold: float = DEFAULT_THRESHOLD,
    z_threshold: float = DEFAULT_Z_THRESHOLD,
    alpha: float = DEFAULT_EWMA_ALPHA,
):
    """One compiled program for the summary path (window reduction already done):
    eager dispatch here costs dozens of small device dispatches per report."""
    dummy = jnp.zeros(medians.shape + (1,), medians.dtype)
    return score_round(
        dummy,
        counts,
        prev_ewma,
        historical_min,
        threshold=threshold,
        z_threshold=z_threshold,
        alpha=alpha,
        medians_and_weights=(medians, weights),
    )


@functools.lru_cache(maxsize=16)
def make_sharded_scorer(
    mesh,
    axis: str,
    *,
    threshold: float = DEFAULT_THRESHOLD,
    z_threshold: float = DEFAULT_Z_THRESHOLD,
    alpha: float = DEFAULT_EWMA_ALPHA,
    use_pallas: bool = False,
):
    """Build a jitted scoring fn over a mesh-sharded rank axis. Cached per
    (mesh, axis, thresholds) so per-round callers don't re-trace.

    Input/output arrays are sharded ``P(axis)`` on dim 0; each device holds its own
    ranks' telemetry and the cross-rank reductions lower to collectives over the mesh
    (the north-star replacement for the reference's host gather,
    ``reporting.py:255-296``). Returns ``fn(data, counts, prev_ewma, historical_min)
    -> TelemetryScores`` with every leaf still sharded ``P(axis)``.

    ``use_pallas`` swaps the window reduction (masked median + totals) for the
    fused Pallas kernel, which runs per-shard before the cross-rank collectives
    (2.0x faster than the XLA sort lowering on v5e at 4096x64x32 in a 2026-07-31
    capture, BASELINE.md "Pallas verdict").
    """
    from jax.sharding import PartitionSpec as P

    spec = P(axis)
    if use_pallas:
        from tpu_resiliency.ops.scoring_pallas import fused_median_weights

        def body(data, counts, prev_ewma, historical_min):
            mw = fused_median_weights(data, counts)
            return score_round(
                data,
                counts,
                prev_ewma,
                historical_min,
                threshold=threshold,
                z_threshold=z_threshold,
                alpha=alpha,
                medians_and_weights=mw,
                axis_name=axis,
            )

    else:
        body = functools.partial(
            score_round,
            threshold=threshold,
            z_threshold=z_threshold,
            alpha=alpha,
            axis_name=axis,
        )
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=TelemetryScores(*([spec] * 7)),
        # pallas_call outputs carry no varying-mesh-axes metadata, so the vma
        # checker cannot validate the pallas branch.
        check_vma=not use_pallas,
    )
    return jax.jit(sharded)


def score_round_sharded(
    data,
    counts,
    prev_ewma,
    historical_min,
    *,
    mesh,
    axis: str,
    threshold: float = DEFAULT_THRESHOLD,
    z_threshold: float = DEFAULT_Z_THRESHOLD,
    alpha: float = DEFAULT_EWMA_ALPHA,
) -> TelemetryScores:
    """One mesh-sharded scoring round (see :func:`make_sharded_scorer`)."""
    fn = make_sharded_scorer(
        mesh, axis, threshold=threshold, z_threshold=z_threshold, alpha=alpha
    )
    return fn(data, counts, prev_ewma, historical_min)
