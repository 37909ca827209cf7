"""The delta kind of the pattern-of-layers model (models/pattern.py) and the heads a chip
holds, in a file of their own so that a test worker takes them beside ``test_pattern.py``
and not after it: the rule by chunks against the recurrence token by token (value and
every operand's gradient, at several chunks, and under a decay whose inverse overflows),
a chunk's decayed Gram matrices by sub-blocks against the sum over the differences of
whole chunks (value, gradients, every exponent it takes, what it lowers to), the inverse by
blocks of a chunk's triangular system against float64 and its own derivative,
causality through convolution and state, the three counters against their definitions,
``kept_residuals`` and ``attention_paths`` for the kind, the head shares of a delta and of
a softmax sublayer and the forty expert shares against the uncut reference, the seeding of
a share's ``wo``, and the example's events. The fourth description's cases of the
parametrised tests are in ``test_pattern.py``, whose helpers these tests use."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.models import pattern

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_pattern import DELTA_NAMES, ROOT, SEQ, share_of, sparse_layer, tiny_file_of  # noqa: E402

# ---------------------------------------------------------------------------------
# the delta rule, and the heads a chip holds
# ---------------------------------------------------------------------------------

RULE_OPERANDS = ("q", "k", "v", "g", "beta")


def rule_operands(seed=0, batch=2, seq=SEQ, heads=3, dk=8, dv=6, decay=3.0):
    """q, unit k, v, log-decays in ``[-decay, -0.01]`` and write strengths in (0, 2)."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    k = normal(batch, seq, heads, dk)
    return (normal(batch, seq, heads, dk), k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            normal(batch, seq, heads, dv),
            -jnp.asarray(rng.uniform(0.01, decay, (batch, seq, heads, dk)), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 2.0, (batch, seq, heads)), jnp.float32))


def token_by_token(q, k, v, g, beta):
    """The rule as its equations state it, one token at a time: (o, the final state)."""
    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state
        write = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, _, h, dk = k.shape
    state, o = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1])),
                            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def probe(rule):
    """A scalar of both results of a rule, so that every operand gets a gradient through
    the outputs and through the final state."""
    def value(*operands):
        o, state = rule(*operands)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.square(state))
    return value


@functools.cache
def rule_by_chunks_and_by_tokens(chunk: int):
    operands = rule_operands()
    with jax.default_matmul_precision("highest"):
        want = (token_by_token(*operands), jax.grad(probe(token_by_token), range(5))(*operands))
        by_chunks = lambda *xs: pattern.delta_rule(*xs, chunk)  # noqa: E731
        got = (by_chunks(*operands), jax.grad(probe(by_chunks), range(5))(*operands))
    return got, want


@pytest.mark.parametrize("chunk", [4, 16, SEQ])
@pytest.mark.parametrize("what", ["value", *RULE_OPERANDS])
def test_the_rule_by_chunks_equals_the_recurrence_token_by_token(chunk, what):
    """Outputs, final state and the gradient of every operand, in float32, at chunks of 4
    and 16 (neither divides the 37 tokens: the last chunk is padded) and at the whole
    sequence as one chunk: the result does not depend on the chunk. The gaps read 1e-6 of
    an output and 4e-6 of a gradient's largest element."""
    (got, got_grads), (want, want_grads) = rule_by_chunks_and_by_tokens(chunk)
    if what == "value":
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=2e-5)
        return
    i = RULE_OPERANDS.index(what)
    scale = float(jnp.max(jnp.abs(want_grads[i])))
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got_grads[i]), np.asarray(want_grads[i]),
                               atol=2e-5 * scale)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_rule_carries_its_state_in_float32(seed):
    """What the benchmark's three compared numbers cannot see (PERF.md section 2: a
    reference whose state is rounded to bfloat16 after each token reads ``correct``), held
    here: at a head of 128 x 128 under the seeded decays, bfloat16 q, k and v, the final
    state of the rule by chunks stands 0.0024-0.0030 of its norm from the float32 recurrence
    on the same operands (its products round their operands), and the same recurrence with
    its carry rounded to bfloat16 0.0052-0.0101: a rule, or a kernel in its place, that
    keeps the state in bfloat16 between chunks is over this limit."""
    rng = np.random.default_rng(seed)
    seq, heads, dk = 512, 2, 128
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q, k = (pattern._unit(normal(1, seq, heads, dk)) for _ in range(2))
    v = jax.nn.silu(normal(1, seq, heads, dk))
    rate = rng.uniform(1, 16, (1, 1, heads, 1))
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, 1, heads, dk)))
    bias = step + np.log(-np.expm1(-step))
    g = -jnp.asarray(rate * np.log1p(np.exp(bias + rng.normal(size=(1, seq, heads, dk)))),
                     jnp.float32)
    beta = 2 * jax.nn.sigmoid(normal(1, seq, heads))
    low = tuple(x.astype(jnp.bfloat16) for x in (q / np.sqrt(dk), k, v))
    same = tuple(x.astype(jnp.float32) for x in low)

    def rounded_carry(q, k, v, g, beta):
        def token(state, x):
            q_t, k_t, v_t, g_t, beta_t = x
            state = jnp.exp(g_t)[..., None] * state.astype(jnp.float32)
            write = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
            return (state + k_t[..., None] * write[..., None, :]).astype(jnp.bfloat16), None
        return jax.lax.scan(token, jnp.zeros((1, heads, dk, dk), jnp.bfloat16), tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))[0].astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = token_by_token(*same, g, beta)[1]
        rounded = rounded_carry(*same, g, beta)
    got = pattern.delta_rule(*low, g, beta, 64)[1]
    assert got.dtype == jnp.float32
    gap = lambda a: float(jnp.linalg.norm(a - want) / jnp.linalg.norm(want))  # noqa: E731
    assert gap(got) < 0.004 < gap(rounded), (gap(got), gap(rounded))


@pytest.mark.parametrize("chunk", [16, SEQ])
def test_the_rule_stays_finite_under_a_decay_whose_inverse_overflows(chunk):
    """Log-decays down to -300 a token: ``exp(-G)`` of a chunk's running sum is past
    float32 after one token, and the factored Gram matrix ``exp(G_i) x exp(-G_j)`` would be
    ``0 x inf``. Every exponent the rule takes is a difference ``G_i - G_j <= 0``: outputs,
    state and every gradient are finite, and equal the recurrence's as far as a float32
    running sum of thousands leaves a difference of two of them exact (1e-3 of a factor
    at sums of 10,000, where the recurrence multiplies a token's own ``exp(g)``)."""
    operands = rule_operands(seed=1, decay=300.0)
    assert float(jnp.min(jnp.cumsum(operands[3], axis=1))) < -1000  # exp(1000) is no float32
    with jax.default_matmul_precision("highest"):
        by_chunks = lambda *xs: pattern.delta_rule(*xs, chunk)  # noqa: E731
        got = by_chunks(*operands)
        grads = jax.grad(probe(by_chunks), range(5))(*operands)
        want = token_by_token(*operands)
        want_grads = jax.grad(probe(token_by_token), range(5))(*operands)
    for x in (*got, *grads):
        assert bool(jnp.all(jnp.isfinite(x)))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-3)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3 * max(float(jnp.max(jnp.abs(b))), 1.0))


GRAM_CASES = ("random decays", "no decay", "a decay of -40 a token on half the channels")


def gram_operands(case: str, c: int, dk=8):
    """q, k ``[2, 3, c, dk]`` float32 and log-decays a token of one chunk of ``c`` tokens."""
    rng = np.random.default_rng(c)
    q, k = (jnp.asarray(rng.normal(size=(2, 3, c, dk)), jnp.float32) for _ in range(2))
    g = -rng.uniform(0.01, 3.0, (2, 3, c, dk))
    if case == "no decay":
        g = np.zeros_like(g)
    elif case.startswith("a decay of -40"):
        g[..., ::2] = -40.0
    return q, k, jnp.asarray(g, jnp.float32)


def grams_by_differences(q, k, g):
    """The two decayed Gram matrices as sums over the ``[c, c, dk]`` differences of the
    running log-decays of a whole chunk: what ``pattern._within_chunks`` made until PR 45."""
    c = k.shape[-2]
    total = jnp.cumsum(g, axis=-2)
    lower = np.tril(np.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower[..., None], total[..., :, None, :] - total[..., None, :, :],
                              -jnp.inf))
    return (jnp.sum(k[..., :, None, :] * k[..., None, :, :] * decay, axis=-1),
            jnp.sum(q[..., :, None, :] * k[..., None, :, :] * decay, axis=-1))


def grams_by_sub_blocks(q, k, g):
    return pattern._decayed_grams(q, k, jnp.cumsum(g, axis=-2))


@pytest.mark.parametrize("c", [4, 16, 48, 64])
@pytest.mark.parametrize("case", GRAM_CASES)
def test_the_gram_matrices_by_sub_blocks_equal_the_sums_over_differences(case, c, monkeypatch):
    """``pattern._decayed_grams`` (sub-blocks of ``GRAM_ROWS`` rows: matrix products left of
    the diagonal blocks, sums over differences inside them) against the sum over the
    differences of the whole chunk, in value and in the gradients of ``q``, ``k`` and the
    log-decays, at chunks of 4 (shorter than a sub-block), 16, 48 and 64, and at sub-blocks
    of 8, 16 and 32 rows (48 is no multiple of 32: the last sub-block is short): to 2e-5 of
    each one's largest entry, and nothing above the diagonal. Under the strong decay half
    of the channels are gone after one token and the others carry the sum."""
    operands = gram_operands(case, c)
    weights = [jnp.asarray(np.random.default_rng(1).normal(size=(2, 3, c, c)), jnp.float32)
               for _ in range(2)]
    value = lambda grams: lambda *xs: sum(  # noqa: E731
        jnp.sum(w * jnp.sin(x)) for w, x in zip(weights, grams(*xs)))
    with jax.default_matmul_precision("highest"):
        want = grams_by_differences(*operands)
        want_grads = jax.grad(value(grams_by_differences), range(3))(*operands)
    for rows in (8, 16, 32):
        monkeypatch.setattr(pattern, "GRAM_ROWS", rows)
        got = grams_by_sub_blocks(*operands)
        got_grads = jax.grad(value(grams_by_sub_blocks), range(3))(*operands)
        for x, y in (*zip(got, want), *zip(got_grads, want_grads)):
            assert x.shape == y.shape and x.dtype == jnp.float32
            scale = float(jnp.max(jnp.abs(y)))
            assert scale > 0.1
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5 * scale)
        for x in got:
            np.testing.assert_array_equal(np.triu(np.asarray(x), 1), 0.0)


def exp_operands(jaxpr, consts, *args):
    """Every operand an ``exp`` of ``jaxpr`` is given when it runs on ``args``, through its
    nested jaxprs (``jit``, ``custom_vjp``)."""
    seen = []

    def run(jaxpr, consts, args):
        env = dict(zip(jaxpr.constvars, consts)) | dict(zip(jaxpr.invars, args))
        read = lambda v: v.val if hasattr(v, "val") else env[v]  # noqa: E731  (a literal)
        for eqn in jaxpr.eqns:
            values = [read(v) for v in eqn.invars]
            inner = next((eqn.params[name] for name in ("jaxpr", "call_jaxpr", "fun_jaxpr")
                          if name in eqn.params), None)
            if eqn.primitive.name == "exp":
                seen.append(values[0])
            if inner is not None:
                out = run(*((inner.jaxpr, inner.consts) if hasattr(inner, "consts")
                            else (inner, ())), values)
            else:
                out = eqn.primitive.bind(*values, **eqn.params)
                out = out if eqn.primitive.multiple_results else [out]
            env.update(zip(eqn.outvars, out))
        return [read(v) for v in jaxpr.outvars]

    run(jaxpr, consts, args)
    return seen


@pytest.mark.parametrize("c", [16, 48, 64])
def test_every_factor_of_the_gram_matrices_is_finite_and_at_most_one_under_a_strong_decay(c):
    """Log-decays of -40 a token on half the channels: the chunk's running sum reaches
    ``-40 c``, ``exp`` of its negative is no float32 from the third token on, and a factor
    ``exp(G_r) x exp(-G_j)`` would be ``0 x inf``. Every exponent that
    ``pattern._within_chunks`` takes on these operands, read off its jaxpr as it runs, is a
    number ``<= 0``, so every factor it forms is finite and in [0, 1]: the differences
    inside the sub-blocks (one ``exp`` for all), each sub-block's rows against its first and
    its first against the rows before it (two a sub-block, the first sub-block's second of
    no row), and the chunk's three own. Most of the strong channels' factors are zero: they
    stand for terms that are smaller still."""
    q, k, g = gram_operands(GRAM_CASES[2], c)
    assert float(jnp.min(jnp.cumsum(g, axis=-2))) <= -40.0 * c  # and exp(89) is no float32
    v = jnp.ones((2, 3, c, 6), jnp.float32)
    beta = jnp.ones((2, 3, c), jnp.float32)
    closed = jax.make_jaxpr(pattern._within_chunks)(q, k, v, g, beta)
    exponents = exp_operands(closed.jaxpr, closed.consts, q, k, v, g, beta)
    assert len(exponents) == 1 + 2 * -(-c // pattern.GRAM_ROWS) + 3
    assert [x.size for x in exponents].count(0) == 1
    for x in (x for x in exponents if x.size):
        assert float(jnp.max(x)) <= 0.0 and not bool(jnp.any(jnp.isnan(x)))
        factor = np.asarray(jnp.exp(x))
        assert np.all(np.isfinite(factor)) and factor.min() >= 0.0 and factor.max() <= 1.0
    strong = [x[..., ::2] for x in exponents if x.size and x.shape[-1] == 8]
    assert float(np.mean([float(jnp.mean(jnp.exp(x) == 0.0)) for x in strong])) > 0.5


def test_the_lowered_gram_matrices_are_products_and_hold_no_chunk_by_chunk_by_channel_array():
    """``pattern._within_chunks`` lowered at the cell's chunk (64 tokens, keys of 128):
    the columns left of each sub-block's diagonal block are a ``dot_general`` over the
    channels at ``Precision.HIGHEST``, ``[2 S, dk] x [dk, r] -> [2 S, r]`` (keys over
    queries) for ``r = S, 2 S, ...``, and no value of the program is ``[64, 64, 128]``: the
    largest array of differences is ``[S, S, 128]`` a sub-block. The sum over whole chunks'
    differences, lowered the same way, does hold one (the check can fail)."""
    c, dk, s = 64, 128, pattern.GRAM_ROWS
    shape = lambda *dims: jax.ShapeDtypeStruct((2, 8, *dims), jnp.float32)  # noqa: E731
    text = jax.jit(pattern._within_chunks).lower(
        shape(c, dk), shape(c, dk), shape(c, dk), shape(c, dk), shape(c)).as_text()
    products = [line for line in text.splitlines() if "dot_general" in line]
    for r in range(s, c, s):
        strips = [line for line in products
                  if f"(tensor<2x8x{2 * s}x{dk}xf32>, tensor<2x8x{dk}x{r}xf32>)" in line]
        assert len(strips) == 1 and f"-> tensor<2x8x{2 * s}x{r}xf32>" in strips[0], (r, products)
        assert "precision = [HIGHEST, HIGHEST]" in strips[0]
    assert f"{c}x{c}x{dk}x" not in text and f"x{s}x{s}x{dk}xf32" in text
    whole = jax.jit(grams_by_differences).lower(shape(c, dk), shape(c, dk), shape(c, dk)).as_text()
    assert f"{c}x{c}x{dk}x" in whole


def chunk_system(case: str, c: int):
    """``A = beta tril(decayed Gram matrix of the keys, -1)`` of one chunk of ``c`` tokens,
    float32, as :func:`pattern._within_chunks` makes it."""
    rng = np.random.default_rng(c)
    dk = 16
    keys = rng.normal(size=(c, dk))
    beta = rng.uniform(0.0, 2.0, c)
    g = -rng.uniform(0.01, 0.5, (c, dk))
    if case == "equal keys, beta 2, no decay":  # A = 2 tril(1, -1): the worst that
        keys = np.tile(keys[:1], (c, 1))        # ``kda_allow_neg_eigval`` allows
        beta, g = np.full(c, 2.0), np.zeros((c, dk))
    elif case == "positively correlated unit keys, beta 2":  # what a SiLU leaves
        keys = np.abs(keys) + 1.0
        beta, g = np.full(c, 2.0), np.zeros((c, dk))
    elif case == "strong decay":
        g = -rng.uniform(5.0, 300.0, (c, dk))
    keys = keys / np.linalg.norm(keys, axis=-1, keepdims=True)
    total = np.cumsum(g, axis=0)
    below = np.tril(np.ones((c, c), bool), -1)[..., None]
    decay = np.exp(np.where(below, total[:, None, :] - total[None, :, :], -np.inf))
    gram = np.sum(keys[:, None, :] * keys[None, :, :] * decay, axis=-1)
    return (beta[:, None] * gram).astype(np.float32)


@pytest.mark.parametrize("c", [4, 16, 48, 64])
@pytest.mark.parametrize("case", ["random", "equal keys, beta 2, no decay",
                                  "positively correlated unit keys, beta 2", "strong decay"])
def test_the_inverse_by_blocks_is_the_inverse(case, c):
    """``pattern._unit_lower_inverse`` against ``numpy.linalg.inv`` in float64, at chunks
    of 4, 16, 48 (no power of two: the last block of a level is short) and 64: under 1e-5
    of the inverse's largest entry in float32 (it reads 0 on equal keys, where every entry
    is a whole number, and up to 3e-6 elsewhere: keys that all point one way, written at
    full strength and never forgotten), and nothing above the diagonal. What lies on or
    above the diagonal of its operand is not read."""
    a = chunk_system(case, c)
    want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
    junk = np.triu(np.full((c, c), 7.0, np.float32))
    got = np.asarray(jax.jit(pattern._unit_lower_inverse)(jnp.asarray(a + junk)))
    assert got.dtype == np.float32
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    np.testing.assert_array_equal(np.triu(got, 1), 0.0)
    if case.startswith("equal"):
        assert np.abs(a).max() == 2.0 and np.abs(want).max() == 2.0


def test_the_solves_own_derivative_is_autodiffs_through_a_triangular_solve():
    """``pattern._solve_unit_lower`` keeps ``T`` and ``X`` and returns ``d rhs = T^T dX``
    and ``dA = -tril(d rhs X^T, -1)``: the same, to 1e-5 of each one's largest entry in
    float32, as differentiating ``jax.lax.linalg.triangular_solve`` on ``I + tril(a, -1)``,
    batched as the rule batches it, at a chunk of 48."""
    rng = np.random.default_rng(0)
    c, m = 48, 24
    a = jnp.asarray(np.stack([chunk_system("random", c),
                              chunk_system("positively correlated unit keys, beta 2", c)]))
    rhs = jnp.asarray(rng.normal(size=(2, c, m)), jnp.float32)
    weights = jnp.asarray(rng.normal(size=(2, c, m)), jnp.float32)

    def by_xla(a, rhs):
        system = jnp.eye(c, dtype=a.dtype) + jnp.tril(a, -1)
        return jax.lax.linalg.triangular_solve(system, rhs, left_side=True, lower=True,
                                               unit_diagonal=True)

    value = lambda solve: lambda a, rhs: jnp.sum(weights * jnp.sin(solve(a, rhs)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want_x, want = by_xla(a, rhs), jax.grad(value(by_xla), (0, 1))(a, rhs)
        got_x, got = pattern._solve_unit_lower(a, rhs), jax.grad(
            value(pattern._solve_unit_lower), (0, 1))(a, rhs)
    for x, y in ((got_x, want_x), *zip(got, want)):
        scale = float(jnp.max(jnp.abs(y)))
        assert scale > 0.1
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5 * scale)
    np.testing.assert_array_equal(np.triu(np.asarray(got[0])), 0.0)


def test_a_delta_model_is_causal_through_convolution_and_state():
    """Changing token ``t`` moves no logit before ``t`` (a convolution that read ahead, or
    a state that leaked backwards through a chunk's solve, would), and moves those from
    ``t`` on: at ``t`` itself, and beyond the convolution's four taps through the state."""
    cfg = pattern.PatternConfig.tiny_delta(dtype=jnp.float32)
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    t1 = jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, cfg.vocab_size)
    t2 = t1.at[0, 21].set((t1[0, 21] + 1) % cfg.vocab_size)  # inside the second chunk of 16
    forward = jax.jit(lambda p, t: pattern.forward(p, t, cfg)[0])
    with jax.default_matmul_precision("highest"):
        l1, l2 = forward(params, t1), forward(params, t2)
    np.testing.assert_array_equal(np.asarray(l1[0, :21]), np.asarray(l2[0, :21]))
    for position in (21, 26, SEQ - 1):
        assert float(jnp.max(jnp.abs(l1[0, position] - l2[0, position]))) > 1e-5, position


def delta_layer(cfg, seed=0, seq=SEQ):
    """One delta layer's weights (float32) and a stream to feed it."""
    lp = jax.tree.map(lambda w: w[1], pattern.init_params(
        jax.random.PRNGKey(seed), cfg)["attn"][pattern.DELTA])
    return lp, jax.random.normal(jax.random.PRNGKey(seed + 1), (2, seq, cfg.d_model))


@pytest.mark.parametrize("count", ["decay_mean", "beta_mean", "state_rms"])
def test_delta_counts_are_what_their_names_say(count):
    """``decay_mean`` is the mean over tokens, heads and channels of ``exp(g)``,
    ``beta_mean`` of the write strength, ``state_rms`` the root mean square of the state
    after the last token, all three from the reference's own formulas on the same layer;
    and the model reports one value a delta layer."""
    config, _, reference = tiny_file_of("delta")
    cfg = pattern.PatternConfig.tiny_delta(dtype=jnp.float32)
    lp, x = delta_layer(cfg)
    with jax.default_matmul_precision("highest"):
        _, counts = jax.jit(lambda x, lp: pattern._delta_block(cfg, x, lp))(x, lp)
        y = reference.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        step = jax.nn.softplus((y @ lp["wf_a"]) @ lp["wf_b"] + lp["dt_bias"])
        decay = jnp.exp(-jnp.repeat(jnp.exp(lp["a_log"]), cfg.delta.d_key) * step)
        beta = 2.0 * jax.nn.sigmoid(y @ lp["wb"])
        state = reference.delta_attention(y, lp, config, "f32")[1]
    want = {"decay_mean": jnp.mean(decay), "beta_mean": jnp.mean(beta),
            "state_rms": jnp.sqrt(jnp.mean(jnp.square(state)))}[count]
    assert 0 < float(want) < 2 and abs(float(counts[count]) - float(want)) < 1e-5 * float(want) + 1e-7
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    assert pattern.forward(params, tokens, cfg)[1][count].shape == (cfg.count(pattern.DELTA),)


def test_attention_paths_and_kept_residuals_know_the_delta_kind():
    """The delta kind goes by chunks in ``jax.numpy`` on any backend (no kernel of the rule
    exists), chunks no longer than the sequence; its layers keep the states and the rule's
    output as one group and q, k, v, the log-decays and the write strengths as another,
    sized by the heads held, and nothing under the names of the softmax products."""
    cfg = pattern.PatternConfig.tiny_delta()
    assert pattern.attention_paths(cfg, 64) == {
        "full": {"path": "blocks", "block": 16},
        "delta": {"path": "chunks", "chunk": 16, "solve": "blocks"}}
    assert pattern.attention_paths(cfg, 8)["delta"] == {
        "path": "chunks", "chunk": 8, "solve": "blocks"}
    kept = pattern.kept_residuals(cfg, 2 * 48, None, 48)
    heads, de = cfg.heads(pattern.DELTA), cfg.delta
    assert heads == 2 and cfg.kv_heads == 1  # of 8 and 4
    assert kept["per_layer"]["states"] == [0] + [
        heads * (2 * 3 * de.d_key * de.d_value * 4 + 96 * de.d_value * 2)] * 3
    assert kept["per_layer"]["delta"] == [0] + [
        96 * heads * (2 * (2 * de.d_key + de.d_value) + 4 * de.d_key + 4)] * 3
    assert kept["per_layer"]["attention"] == [96 * heads * 16 * 2, 0, 0, 0]
    assert kept["per_layer"]["qkv"] == [96 * 2 * 16 * (heads + 2 * cfg.kv_heads), 0, 0, 0]
    order = list(pattern.KEPT_GROUPS)
    assert order.index("attention") < order.index("states") < order.index("qkv") < order.index(
        "delta") < order.index("shared")


def head_share(cfg: pattern.PatternConfig, kind: str, lp: dict, share: int, ways: int) -> dict:
    """Of one layer's leaves over all heads, the ``share``-th of ``ways`` equal parts of
    every dimension the description marks as split by heads."""
    leaves = pattern.describe_params(dataclasses.replace(cfg, head_ways=1))["attn"][kind]
    out = {}
    for name, w in lp.items():
        axes = leaves[name].axes[1:]  # the description's leaves are stacked
        if "heads" in axes:
            dim = axes.index("heads")
            size = w.shape[dim] // ways
            w = jax.lax.slice_in_dim(w, share * size, (share + 1) * size, axis=dim)
        out[name] = w
    return out


def uncut_file(description: str, cfg: pattern.PatternConfig) -> dict:
    """The tiny configuration file with every head of ``cfg`` held."""
    config = tiny_file_of(description)[0]
    heads = cfg.layers[0].n_heads
    return {**config, "num_attention_heads": heads, "num_key_value_heads": cfg.n_kv_heads,
            "linear_attn_config": {**config["linear_attn_config"], "num_heads": heads},
            "deployment": {**config["deployment"], "num_attention_heads": heads,
                           "num_key_value_heads": cfg.n_kv_heads, "heads_held": [0, heads]}}


@pytest.mark.parametrize("kind,kv_heads,ways", [
    ("delta", 4, 1), ("delta", 4, 2), ("delta", 4, 4), ("delta", 8, 8),
    ("full", 4, 1), ("full", 4, 2), ("full", 4, 4), ("full", 8, 8)])
def test_head_shares_of_a_sublayer_add_up_to_the_uncut_reference_sublayer(kind, kv_heads, ways):
    """What the chips of a tensor-parallel group add to the stream, each from the heads it
    holds (8 heads over ``ways`` chips: the columns of ``wq``, ``wk``, ``wv``, of the gates
    and the convolutions and the rows of ``wo`` that are theirs), adds up to the reference's
    sublayer with every head held: a delta sublayer (its heads have keys of their own, so
    they go eight ways) and a softmax sublayer (whole KV groups: 8 heads over 4 KV heads go
    four ways at the most, over 8 eight)."""
    reference = tiny_file_of("delta")[2]
    whole = pattern.PatternConfig.tiny_delta(dtype=jnp.float32, n_kv_heads=kv_heads,
                                             head_ways=1)
    lp = jax.tree.map(lambda w: w[0], pattern.init_params(
        jax.random.PRNGKey(6), whole)["attn"][kind])
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, whole.d_model))
    config = uncut_file("delta", whole)
    with jax.default_matmul_precision("highest"):
        total = jnp.zeros_like(x)
        for share in range(ways):
            part = dataclasses.replace(whole, head_ways=ways)
            part_lp = head_share(whole, kind, lp, share, ways)
            if kind == "delta":
                out = pattern._delta_block(part, x, part_lp)[0]
            else:
                out = pattern._attn_block(part, kind, x, part_lp)
            total = total + (out - x)
        y = reference.rms_norm(x, lp["attn_norm"], whole.norm_eps)
        if kind == "delta":
            want = reference.delta_attention(y, lp, config, "f32")[0]
        else:
            want = reference.softmax_attention(y, lp, config, "f32")
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


def test_the_held_heads_seed_their_part_of_the_whole_output_matrix():
    """``wo`` of a share is seeded for the sum over all the heads of the deployment: its
    elements have the standard deviation of the uncut matrix's, not of a matrix of the
    held heads alone (which would make the part twice too large at four ways)."""
    cfg = pattern.PatternConfig.tiny_delta()
    leaves = pattern.describe_params(cfg)["attn"]
    assert leaves["delta"]["wo"].shape == (3, 2 * 16, 64) and leaves["delta"]["wo"].fan_in == 8 * 16
    assert leaves["full"]["wo"].shape == (1, 2 * 16, 64) and leaves["full"]["wo"].fan_in == 8 * 16
    assert leaves["full"]["wk"].shape == (1, 64, 1 * 16) and leaves["full"]["wg"].shape == (1, 64, 32)
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    assert abs(float(jnp.std(params["attn"]["delta"]["wo"])) * np.sqrt(8 * 16) - 1) < 0.05
    rate, step = params["attn"]["delta"]["a_log"], params["attn"]["delta"]["dt_bias"]
    assert 0 <= float(rate.min()) and float(rate.max()) <= np.log(16.0)
    assert 1e-3 - 1e-6 <= float(jax.nn.softplus(step).min()) and float(
        jax.nn.softplus(step).max()) <= 0.1 + 1e-6


@pytest.mark.parametrize("shares", [1, 8, 40])
def test_forty_expert_shares_add_up_to_the_uncut_sparse_mlp(shares):
    """The routed parts that all chips of a layer compute (40 experts over ``shares``
    chips, as the fourth configuration states its deployment: one of 320 is 8 of them a
    chip), plus the shared expert once, equal the reference's sparse MLP with every expert
    held."""
    config, _, reference = tiny_file_of("delta")
    cfg = pattern.PatternConfig.tiny_delta(dtype=jnp.float32, n_experts=40, experts_held=(0, 40))
    lp, y = sparse_layer(cfg)
    held = cfg.n_experts // shares
    with jax.default_matmul_precision("highest"):
        total = pattern._swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        for s in range(shares):
            part = dataclasses.replace(cfg, experts_held=(s * held, held))
            routed, counts, _ = pattern.routed_experts(part, y, share_of(lp, s * held, held))
            total = total + routed
            assert int(counts["dropped"]) == 0
        uncut = {**config, "n_routed_experts": 40,
                 "deployment": {**config["deployment"], "n_routed_experts": 40,
                                "experts_held": [0, 40]}}
        want = reference.sparse_mlp(y, lp, uncut, "f32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


def test_the_example_records_the_delta_kinds_path_its_kept_group_and_its_state(tmp_path):
    """``examples/pattern_training.py --description delta`` trains the fourth description
    under the toolkit's loop and records, before its first step, ``attention_path`` with
    the delta kind's ``{path: chunks, chunk}`` and ``kept_residuals`` with the two groups
    only a delta layer makes; and with every routing event a ``delta_state`` event: the
    three counts, one value a delta layer, none of them among the routing counts."""
    import json
    import subprocess

    events_file = tmp_path / "events.jsonl"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "pattern_training.py"), "--cpu",
         "--description", "delta", "--steps", "16", "--batch", "2", "32"],
        env={**os.environ, "TPU_RESILIENCY_EVENTS_FILE": str(events_file)},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    records = [json.loads(line) for line in events_file.read_text().splitlines()]
    (path,) = [r for r in records if r["kind"] == "attention_path"]
    assert path["delta"] == {"path": "chunks", "chunk": 16, "solve": "blocks"}
    assert path["full"] == {"path": "blocks", "block": 16}
    (kept,) = [r for r in records if r["kind"] == "kept_residuals"]
    assert DELTA_NAMES <= set(kept["names"])
    assert kept["per_layer"]["states"][0] == 0 and all(kept["per_layer"]["states"][1:])
    states = [r for r in records if r["kind"] == "delta_state"]
    routing = [r for r in records if r["kind"] == "moe_routing"]
    assert [r["step"] for r in states] == [r["step"] for r in routing] == [0, 5, 10, 15]
    for r in states:
        assert r["head_ways"] == 4
        assert all(0 < x < 1 for x in r["decay_mean"]) and len(r["decay_mean"]) == 3
        assert all(0 < x < 2 for x in r["beta_mean"]) and all(x > 0 for x in r["state_rms"])
    assert not {"decay_mean", "beta_mean", "state_rms"} & set(routing[0])
