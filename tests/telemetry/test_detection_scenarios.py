"""Detection at the scales ``BASELINE.json`` names, on replayed synthetic telemetry
and with no clock: a 64-rank section-timing report scored with the reference's
relative-score semantics, and a 1,024-rank timing stream with 5% slow ranks scored
by the fused window pipeline. Each must find exactly the slow ranks."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_resiliency.telemetry import scoring
from tpu_resiliency.telemetry.reporting import ReportGenerator


@pytest.fixture
def section_timings():
    """64 ranks x 3 sections of per-section medians within 2% of one another,
    rank 17 at twice its time; total-time weights over 100 samples a section."""
    ranks, sections = 64, 3
    rng = np.random.default_rng(1)
    base = rng.uniform(0.010, 0.030, size=(1, sections))
    medians = np.tile(base, (ranks, 1)) * (
        1.0 + 0.02 * rng.standard_normal((ranks, sections))
    )
    medians[17] *= 2.0
    counts = np.full((ranks, sections), 100, np.int32)
    return medians, medians * 100.0, counts


@pytest.fixture
def timing_stream():
    """1,024 ranks x 16 signals x a window of 32 with 5% noise; 51 ranks (5%)
    drawn from the seed run 1.6x slow on every signal."""
    ranks, signals, window = 1024, 16, 32
    rng = np.random.default_rng(3)
    base = rng.uniform(0.8, 1.2, size=(1, signals, 1)).astype(np.float32)
    data = base * (
        1.0 + 0.05 * rng.standard_normal((ranks, signals, window)).astype(np.float32)
    )
    slow = rng.choice(ranks, size=ranks // 20, replace=False)
    data[slow] *= 1.6
    return data, np.full((ranks, signals), window, np.int32), set(slow.tolist())


def test_section_report_flags_the_slow_rank_alone(section_timings):
    """The reference's ``examples/straggler`` semantics: a rank's score is the
    best median over its own, weighted by total time, flagged under 0.75."""
    medians, weights, counts = section_timings
    gen = ReportGenerator(world_size=64, max_signals=3)
    report = gen.generate_summary_report(
        jnp.asarray(medians), jnp.asarray(weights), jnp.asarray(counts),
        ("sec/fwd", "sec/bwd", "sec/opt"),
    )
    flagged = {s.rank for s in report.identify_stragglers(perf_threshold=0.75).by_perf}
    assert flagged == {17}
    healthy = [v for r, v in report.perf_scores.items() if r != 17]
    assert min(healthy) > 0.9 and max(healthy) <= 1.0 + 1e-6
    assert report.perf_scores[17] < 0.6  # about min / median = 0.5


def test_window_pipeline_finds_every_slow_rank_of_1024_and_no_other(timing_stream):
    data, counts, slow = timing_stream
    ranks, signals, _ = data.shape
    out = scoring.score_round_jit(
        jnp.asarray(data), jnp.asarray(counts),
        jnp.ones((ranks,)), jnp.full((ranks, signals), jnp.inf),
    )
    assert len(slow) == 51
    assert set(np.nonzero(np.asarray(out.straggler))[0].tolist()) == slow
