"""Property-based tests (hypothesis) for the scoring pipeline's invariants and the
native/python ring parity — randomized inputs catch the edge shapes (empty
windows, single samples, ties, wraps) that example-based tests miss."""

import numpy as np
from hypothesis import given, settings, strategies as st

from tpu_resiliency.telemetry import ring_buffer as rb
from tpu_resiliency.telemetry import scoring

settings.register_profile("ci", max_examples=40, deadline=None)
settings.load_profile("ci")


@st.composite
def telemetry_case(draw):
    r = draw(st.integers(2, 12))
    s = draw(st.integers(1, 5))
    w = draw(st.integers(1, 10))
    data = draw(
        st.lists(
            st.floats(np.float32(1e-4), np.float32(1e3), allow_nan=False, allow_subnormal=False, width=32),
            min_size=r * s * w,
            max_size=r * s * w,
        )
    )
    counts = draw(st.lists(st.integers(0, 10), min_size=r * s, max_size=r * s))
    counts = np.minimum(np.asarray(counts, np.int32).reshape(r, s), w)
    return np.asarray(data, np.float32).reshape(r, s, w), counts


@given(telemetry_case())
def test_masked_median_matches_numpy(case):
    import jax.numpy as jnp

    data, counts = case
    got = np.asarray(scoring.masked_median(jnp.asarray(data), jnp.asarray(counts)))
    r, s, _ = data.shape
    for i in range(r):
        for j in range(s):
            n = counts[i, j]
            if n == 0:
                assert got[i, j] == np.inf
            else:
                np.testing.assert_allclose(
                    got[i, j], np.median(data[i, j, :n]), rtol=1e-5
                )


@given(telemetry_case())
def test_score_round_invariants(case):
    import jax.numpy as jnp

    data, counts = case
    r, s, _ = data.shape
    res = scoring.score_round_jit(
        jnp.asarray(data),
        jnp.asarray(counts),
        jnp.ones((r,)),
        jnp.full((r, s), jnp.inf),
    )
    section = np.asarray(res.section_scores)
    perf = np.asarray(res.perf)
    valid = counts > 0
    # Relative scores are min-of-medians / own-median: bounded (0, 1] where valid.
    assert np.all(section[valid] <= 1.0 + 1e-5)
    assert np.all(section[valid] > 0.0)
    # Every signal someone measured has at least one rank at the reference (1.0).
    for j in range(s):
        if valid[:, j].any():
            assert section[valid[:, j], j].max() > 1.0 - 1e-4
    # Perf scores are weighted means of section scores: same bounds.
    has_any = valid.any(axis=1)
    assert np.all(perf[has_any] <= 1.0 + 1e-5)
    assert np.all(perf[has_any] > 0.0)
    assert np.all(np.isfinite(perf))


@given(
    st.integers(1, 24),
    st.lists(st.floats(np.float32(-1e6), np.float32(1e6), allow_nan=False, allow_subnormal=False, width=32), min_size=0, max_size=80),
)
def test_ring_backends_agree(capacity, samples):
    if rb._ringstats is None:
        import pytest

        pytest.skip("_ringstats extension not built")
    nat = rb.HostRingBuffer(capacity, native=True)
    py = rb.HostRingBuffer(capacity, native=False)
    for v in samples:
        nat.push(float(v))
        py.push(float(v))
    assert len(nat) == len(py)
    np.testing.assert_allclose(nat.linearize(), py.linearize())
    if len(py):
        sn, sp = nat.stats(), py.stats()
        for k in sp:
            np.testing.assert_allclose(sn[k], sp[k], rtol=1e-10, atol=1e-9, err_msg=k)


@st.composite
def window_case(draw):
    """Like telemetry_case but the window size also samples the LARGE regime
    (past the auto-selection cap), where a caller may still name the kernel."""
    r = draw(st.integers(2, 6))
    s = draw(st.integers(1, 3))
    w = draw(st.one_of(st.integers(1, 10), st.integers(129, 260)))
    data = draw(
        st.lists(
            st.floats(np.float32(1e-4), np.float32(1e3), allow_nan=False, allow_subnormal=False, width=32),
            min_size=r * s * w,
            max_size=r * s * w,
        )
    )
    counts = draw(st.lists(st.integers(0, w), min_size=r * s, max_size=r * s))
    return (
        np.asarray(data, np.float32).reshape(r, s, w),
        np.asarray(counts, np.int32).reshape(r, s),
    )


@given(window_case())
def test_median_kernel_matches_numpy(case):
    """Rank counting picks exactly NumPy's order statistics on arbitrary
    windows/counts (ties, empties, single samples, tiny/huge magnitudes, and
    windows past the cap): medians bit-identical to the mean of the two middle
    elements of the sorted valid prefix, weights the masked totals."""
    import jax.numpy as jnp

    from tpu_resiliency.ops.scoring_pallas import fused_median_weights

    data, counts = case
    r, s, _ = data.shape
    med, wt = fused_median_weights(
        jnp.asarray(data), jnp.asarray(counts), rank_tile=r, interpret=True
    )
    med, wt = np.asarray(med), np.asarray(wt)
    for i in range(r):
        for j in range(s):
            n = counts[i, j]
            if n == 0:
                assert med[i, j] == np.inf and wt[i, j] == 0.0
                continue
            v = np.sort(data[i, j, :n])
            assert med[i, j] == np.float32(0.5) * (v[(n - 1) // 2] + v[n // 2])
            np.testing.assert_allclose(wt[i, j], data[i, j, :n].sum(dtype=np.float64), rtol=1e-5)
