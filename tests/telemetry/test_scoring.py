import numpy as np
import pytest

import jax.numpy as jnp

from tpu_resiliency.telemetry import scoring


def _mk_windows(rng, r, s, w, base=10.0):
    data = base + rng.standard_normal((r, s, w)).astype(np.float32) * 0.1
    counts = np.full((r, s), w, dtype=np.int32)
    return data, counts


def test_masked_median_matches_numpy():
    rng = np.random.default_rng(0)
    data = rng.uniform(1, 5, size=(4, 3, 9)).astype(np.float32)
    counts = np.array([[9, 5, 1], [2, 9, 4], [0, 3, 9], [9, 9, 9]], dtype=np.int32)
    med = np.asarray(scoring.masked_median(jnp.asarray(data), jnp.asarray(counts)))
    for i in range(4):
        for j in range(3):
            c = counts[i, j]
            if c == 0:
                assert np.isinf(med[i, j])
            else:
                np.testing.assert_allclose(med[i, j], np.median(data[i, j, :c]), rtol=1e-6)


def test_masked_total():
    data = jnp.asarray([[[1.0, 2.0, 100.0]]])
    counts = jnp.asarray([[2]], dtype=jnp.int32)
    assert float(scoring.masked_total(data, counts)[0, 0]) == 3.0


def test_relative_scores_flag_slow_rank():
    rng = np.random.default_rng(1)
    r, s, w = 8, 4, 16
    data, counts = _mk_windows(rng, r, s, w)
    data[3] *= 2.0  # rank 3 is 2x slower on every signal
    res = scoring.score_round(
        jnp.asarray(data),
        jnp.asarray(counts),
        prev_ewma=jnp.ones(r),
        historical_min=jnp.full((r, s), jnp.inf),
    )
    perf = np.asarray(res.perf)
    assert perf[3] == pytest.approx(0.5, abs=0.05)
    assert np.all(perf[np.arange(r) != 3] > 0.9)
    straggler = np.asarray(res.straggler)
    assert straggler[3]
    assert not straggler[np.arange(r) != 3].any()


def test_robust_z_detects_outlier_even_above_threshold():
    """A rank only mildly slow (score above 0.75) is still caught by robust-z."""
    rng = np.random.default_rng(2)
    r, s, w = 64, 4, 16
    data, counts = _mk_windows(rng, r, s, w)
    data[10] *= 1.15  # 15% slow: score ~0.87 > 0.75 threshold
    res = scoring.score_round(
        jnp.asarray(data),
        jnp.asarray(counts),
        prev_ewma=jnp.ones(r),
        historical_min=jnp.full((r, s), jnp.inf),
    )
    assert float(np.asarray(res.perf)[10]) > scoring.DEFAULT_THRESHOLD
    assert np.asarray(res.straggler)[10]  # caught by z
    assert np.asarray(res.straggler).sum() == 1


def test_individual_scores_track_historical_min():
    r, s, w = 2, 1, 4
    fast = np.full((r, s, w), 1.0, dtype=np.float32)
    counts = np.full((r, s), w, dtype=np.int32)
    res1 = scoring.score_round(
        jnp.asarray(fast),
        jnp.asarray(counts),
        prev_ewma=jnp.ones(r),
        historical_min=jnp.full((r, s), jnp.inf),
    )
    np.testing.assert_allclose(np.asarray(res1.individual_section_scores), 1.0)
    slow = fast * 4.0
    res2 = scoring.score_round(
        jnp.asarray(slow),
        jnp.asarray(counts),
        prev_ewma=res1.ewma,
        historical_min=res1.historical_min,
    )
    np.testing.assert_allclose(np.asarray(res2.individual_section_scores), 0.25)
    # relative scores see all ranks equally slow -> 1.0
    np.testing.assert_allclose(np.asarray(res2.section_scores), 1.0)


def test_empty_signals_score_neutral():
    r, s, w = 4, 3, 8
    rng = np.random.default_rng(3)
    data, counts = _mk_windows(rng, r, s, w)
    counts[:, 2] = 0  # nobody measured signal 2
    counts[1, 1] = 0  # rank 1 missed signal 1
    res = scoring.score_round(
        jnp.asarray(data),
        jnp.asarray(counts),
        prev_ewma=jnp.ones(r),
        historical_min=jnp.full((r, s), jnp.inf),
    )
    sec = np.asarray(res.section_scores)
    assert np.all(np.isfinite(np.asarray(res.perf)))
    np.testing.assert_allclose(sec[:, 2], 1.0)
    np.testing.assert_allclose(sec[1, 1], 1.0)
    assert not np.asarray(res.straggler).any()


def test_ewma_smoothing():
    r, s, w = 2, 1, 4
    data = np.ones((r, s, w), dtype=np.float32)
    counts = np.full((r, s), w, dtype=np.int32)
    res = scoring.score_round(
        jnp.asarray(data),
        jnp.asarray(counts),
        prev_ewma=jnp.zeros(r),
        historical_min=jnp.full((r, s), jnp.inf),
        alpha=0.5,
    )
    np.testing.assert_allclose(np.asarray(res.ewma), 0.5)


def test_pallas_kernel_matches_reference_pipeline():
    from tpu_resiliency.ops.scoring_pallas import fused_median_weights

    rng = np.random.default_rng(4)
    r, s, w = 16, 8, 16
    data, counts = _mk_windows(rng, r, s, w)
    counts[0, 0] = 5
    counts[2, 3] = 0
    counts[5, 1] = 1
    med_k, wt_k = fused_median_weights(
        jnp.asarray(data), jnp.asarray(counts), rank_tile=8, interpret=True
    )
    med_ref = scoring.masked_median(jnp.asarray(data), jnp.asarray(counts))
    wt_ref = scoring.masked_total(jnp.asarray(data), jnp.asarray(counts))
    np.testing.assert_allclose(np.asarray(med_k), np.asarray(med_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(wt_k), np.asarray(wt_ref), rtol=1e-5)


def test_pallas_kernel_with_duplicates():
    from tpu_resiliency.ops.scoring_pallas import fused_median_weights

    data = np.full((4, 2, 8), 3.0, dtype=np.float32)
    counts = np.full((4, 2), 8, dtype=np.int32)
    med, wt = fused_median_weights(
        jnp.asarray(data), jnp.asarray(counts), rank_tile=4, interpret=True
    )
    np.testing.assert_allclose(np.asarray(med), 3.0)
    np.testing.assert_allclose(np.asarray(wt), 24.0)


def _numpy_median_weights(data, counts):
    """NumPy's median over each window's valid prefix; +inf and 0 for an empty one."""
    r, s, _ = data.shape
    med = np.full((r, s), np.inf, np.float32)
    wt = np.zeros((r, s), np.float32)
    for i in range(r):
        for j in range(s):
            n = counts[i, j]
            wt[i, j] = data[i, j, :n].sum()
            if n:
                v = np.sort(data[i, j, :n])
                med[i, j] = np.float32(0.5 * (v[(n - 1) // 2] + v[n // 2]))
    return med, wt


def _edge_windows(seed, r, s, w):
    """Random windows with the edges the deleted kernels' tests carried: a short
    prefix, an empty window, a single sample, a whole window of ties and
    subnormal-adjacent magnitudes."""
    rng = np.random.default_rng(seed)
    data = rng.uniform(0.5, 2.0, (r, s, w)).astype(np.float32)
    counts = rng.integers(0, w + 1, (r, s)).astype(np.int32)
    counts[0, 0] = min(5, w)
    counts[1, s - 1] = 0
    counts[min(5, r - 1), 0] = 1
    data[r - 1, s // 2, :] = 1.5
    data[r // 2, 0, :] = np.float32(1e-30)
    return data, counts


@pytest.mark.parametrize(
    "r,s,w,tile",
    [
        (16, 8, 16, None),    # the shape the three kernels were compared at
        (4, 2, 8, 4),         # every window a run of ties (set below)
        (8, 64, 16, None),    # the production signal count
        (8, 48, 16, None),    # a signal count that is no power of two
        (2, 256, 16, None),   # S = 256
        (8, 4, 128, 8),       # W = 128, the cap
        (8, 4, 192, 8),       # a named kernel past the cap, W no power of two
        (24, 64, 512, None),  # R = 24: the budget tile of 16 snaps to 12
        (14, 64, 1024, None), # R = 14: the budget tile of 8 snaps to 7
        (32, 64, 256, None),  # the largest block that ran on v5e, whole
    ],
)
def test_pallas_kernel_matches_numpy_median(r, s, w, tile):
    """The one kernel against NumPy's masked median, bit for bit, over the
    shapes the deleted kernels' tests used."""
    from tpu_resiliency.ops import scoring_pallas as sp

    data, counts = _edge_windows(100 + r + s + w, r, s, w)
    if (r, s, w) == (4, 2, 8):
        data[:] = 3.0
        counts[:] = 8
    if tile is None:
        want = {(24, 64, 512): 12, (14, 64, 1024): 7, (32, 64, 256): 32}
        assert sp._snap_tile(r, s, w) == want.get((r, s, w), min(r, 32))
    med, wt = sp.fused_median_weights(
        jnp.asarray(data), jnp.asarray(counts), rank_tile=tile, interpret=True
    )
    exp_med, exp_wt = _numpy_median_weights(data, counts)
    np.testing.assert_array_equal(np.asarray(med), exp_med)
    np.testing.assert_allclose(np.asarray(wt), exp_wt, rtol=1e-5)


@pytest.mark.parametrize(
    "r,s,w,why",
    [
        (31, 64, 512, "shatter"),    # tile 16 has no divisor of 31 within 4x
        (2, 64, 16384, "exceeds"),   # one rank-row over the block budget
        (2, 4100, 128, "exceeds"),
    ],
)
def test_pallas_kernel_refuses_shapes_outside_its_budget(r, s, w, why):
    """Shapes that arrive from outside: the gate (when it knows S) and the
    kernel both say no, loudly, where a default tile would shatter the grid
    or no tile fits; an explicit tile is honoured or fails on divisibility."""
    from tpu_resiliency.ops import scoring_pallas as sp

    assert sp._snap_tile(r, s, w) is None
    assert not sp.pallas_supported(r, window=w, signals=s)
    with pytest.raises(ValueError, match=f"median kernel at window {w}.*{why}"):
        sp.fused_median_weights(
            jnp.zeros((r, s, w), jnp.float32), jnp.zeros((r, s), jnp.int32),
            interpret=True,
        )


def test_pallas_budget_tile_and_explicit_tile():
    from tpu_resiliency.ops import scoring_pallas as sp

    assert sp.budget_rank_tile(64, 256) == 32  # the proven block: no shrink
    assert sp.budget_rank_tile(64, 512) == 16  # one halving
    assert sp.budget_rank_tile(1, 32) == 32  # tiny shapes never shrink
    assert sp.budget_rank_tile(64, 2**20) == 1  # the halving floors at 1
    # Small worlds are not degenerate: one whole-R block is a single grid step.
    assert sp._snap_tile(4, 64, 256) == 4
    # The gate says what the kernel at its default tile will say.
    assert sp.pallas_supported(31, window=128, signals=64)  # one block of 31
    assert not sp.pallas_supported(31, window=128, signals=256)  # tile 16 -> 1
    # An explicit non-dividing tile hits the divisibility error: the snap
    # (which would have repaired 16 -> 12 at R = 24) must not touch it.
    with pytest.raises(ValueError, match="not divisible"):
        sp.fused_median_weights(
            jnp.zeros((24, 64, 256), jnp.float32), jnp.zeros((24, 64), jnp.int32),
            interpret=True, rank_tile=16,
        )


def test_loop_block_budget_at_many_signals():
    """The loop kernel's proven block is 32x64x256; a many-signal config at the
    raised W=128 cap would exceed it at the default tile, so the same shrink /
    loud-reject machinery applies (the cap raise must not re-open an unproven
    VMEM regime)."""
    from tpu_resiliency.ops import scoring_pallas as sp
    from tpu_resiliency.ops.scoring_pallas import fused_median_weights

    # 32*256*128 = 2x the proven loop block: tile halves to 16.
    assert sp._snap_tile(32, 256, 128) == 16
    assert sp.pallas_supported(32, window=128, signals=256)
    rng = np.random.default_rng(21)
    r, s, w = 32, 256, 128
    data = rng.uniform(0.5, 2.0, (r, s, w)).astype(np.float32)
    counts = rng.integers(0, w + 1, (r, s)).astype(np.int32)
    med, _ = fused_median_weights(
        jnp.asarray(data), jnp.asarray(counts), interpret=True
    )
    med = np.asarray(med)
    for i in range(0, r, 11):
        for j in range(0, s, 37):
            n = counts[i, j]
            if n:
                v = np.sort(data[i, j, :n])
                assert med[i, j] == np.float32(0.5 * (v[(n - 1) // 2] + v[n // 2]))
            else:
                assert med[i, j] == np.inf


@pytest.mark.parametrize("window", [32, 128, 129, 256, 512])
def test_pallas_window_gate(window, monkeypatch):
    """Auto-selection must not hand a large-window user an O(W^2) kernel: up
    to the cap the sweep set (128) the kernel, past it the XLA sort — and the
    scores of the path taken equal the host path's. No environment variable
    moves the cap (PR 47 deleted the two that did)."""
    import jax
    from jax.sharding import Mesh

    from tpu_resiliency.ops import scoring_pallas as sp
    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    monkeypatch.setenv("TPU_RESILIENCY_PALLAS_RADIX", "1")
    monkeypatch.setenv("TPU_RESILIENCY_PALLAS_MAX_WINDOW", "32")
    admitted = window <= 128
    assert sp.MAX_WINDOW == 128
    assert sp.pallas_supported(32, window=window, signals=4) is admitted
    assert sp.pallas_supported(33, window=window, signals=4) is admitted  # 3 x 11
    assert not sp.pallas_supported(37, window=window, signals=4)  # prime: shatters

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("rank",))
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        mt = MeshTelemetry(mesh, "rank", n_ranks=8, window=window,
                           signal_names=("a", "b", "c", "d"))
    assert mt.use_pallas is admitted

    rng = np.random.default_rng(window)
    r, s = 8, 4
    data = rng.uniform(0.5, 2.0, (r, s, window)).astype(np.float32)
    data[3] *= 2.0
    counts = rng.integers(1, window + 1, (r, s)).astype(np.int32)
    args = (jnp.asarray(data), jnp.asarray(counts), jnp.ones(r),
            jnp.full((r, s), jnp.inf))
    host = scoring.score_round(*args)
    scorer = scoring.make_sharded_scorer(mesh, "rank", use_pallas=mt.use_pallas)
    got = scorer(*args)
    np.testing.assert_allclose(np.asarray(got.perf), np.asarray(host.perf), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(got.straggler), np.asarray(host.straggler))
    assert np.asarray(got.straggler)[3]


def test_mesh_telemetry_autoselect_large_window(monkeypatch):
    """MeshTelemetry(use_pallas=None) on a TPU: the kernel at the default
    window, the XLA sort at a large one — whatever the environment says
    (``$TPU_RESILIENCY_PALLAS_RADIX`` opened the large window to a second
    kernel until PR 47)."""
    import jax
    from jax.sharding import Mesh

    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("rank",))
    picked = {}
    for env in (None, "1"):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            if env is not None:
                m.setenv("TPU_RESILIENCY_PALLAS_RADIX", env)
            picked[env] = [
                MeshTelemetry(mesh, "rank", n_ranks=32, window=w).use_pallas
                for w in (32, 256)
            ]
    assert picked[None] == [True, False]
    assert picked["1"] == picked[None]
