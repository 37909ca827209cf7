"""The blocked attention kernels (ops/attention.py) under the Pallas interpreter on the
CPU: value and the three gradients against plain masked attention, and under a selection
against ``pattern._attend_summed`` with the heads' summed probabilities; which path
``models/pattern.py`` takes for which shapes; that every kernel of a layer's forward,
recomputed forward and backward carries the scope the benchmark's reader looks for; that
without a selection the two kernels lower to recorded operations; and that the backward
kernel's call fits the VMEM it is given at every cell's shapes."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.models import pattern
from tpu_resiliency.ops import attention

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HKV, DH = 2, 128

#: norm gaps (|got - want| / |want|) of bf16 operands against float32 plain attention.
#: Measured on these very cases under the interpreter (PR 29): the present ``jax.numpy``
#: blocks (``pattern.full_attention`` / ``sliding_attention`` in bf16) read 0.00323-0.00337
#: for the value and 0.00316-0.00475 for the gradients, the kernels 0.00323-0.00336 and
#: 0.00336-0.00460 (most of either is the operands' own rounding); the limits are the
#: blocks' largest and a fifth
BF16_VALUE_GAP, BF16_GRAD_GAP = 0.0040, 0.0057
F32_GAP = 2e-6
#: the kernels under the causal mask as a selection against the kernels with none (read:
#: 0.5e-7 to 1.5e-7 in float32, 0 to 4.2e-5 in bf16, where an output rounds the other way)
SAME_GAP = {jnp.float32: 5e-7, jnp.bfloat16: 2e-4}


def plain_attention(q, k, v, window=None):
    """Masked softmax attention with the whole T x T array (as
    ``tests/models/test_pattern.py:plain_attention``; the test directories are no
    packages, so it cannot be imported from there). The values' width is their own."""
    b, t, h, dh = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allowed = j <= i if window is None else (j <= i) & (j > i - window)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * v.shape[-1])


def inputs(seq: int, groups: int, score_width: int = DH):
    keys = jax.random.split(jax.random.PRNGKey(seq + groups), 4)
    q = jax.random.normal(keys[0], (1, seq, HKV * groups, score_width))
    k = jax.random.normal(keys[1], (1, seq, HKV, score_width))
    v = jax.random.normal(keys[2], (1, seq, HKV, DH))
    weight = jax.random.normal(keys[3], (1, seq, HKV * groups * DH))
    return q, k, v, weight


def value_and_grads(fn, q, k, v, weight):
    def weighed(*qkv):
        out = fn(*qkv).astype(jnp.float32)
        return jnp.sum(out * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(weighed, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, *grads


def gap(got, want) -> float:
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# window 512 at 256 rows: the band is the causal half
CASES = [(window, seq, groups) for window in (None, 128, 256) for seq in (256, 512)
         for groups in (1, 6, 8)] + [(512, 256, 6)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("window,seq,groups", CASES)
def test_kernels_equal_plain_masked_attention(window, seq, groups, dtype):
    q, k, v, weight = inputs(seq, groups)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(lambda *a: plain_attention(*a, window), q, k, v, weight)
    got = value_and_grads(lambda *a: attention.blocked_attention(*a, window=window),
                          *(x.astype(dtype) for x in (q, k, v)), weight)
    assert got[0].shape == (1, seq, HKV * groups * DH)
    for a, b in zip(got[1:], (q, k, v)):
        assert a.shape == b.shape and a.dtype == dtype
    limits = (F32_GAP,) * 4 if dtype == jnp.float32 else (BF16_VALUE_GAP,) + (BF16_GRAD_GAP,) * 3
    gaps = [gap(a, b) for a, b in zip(got, want)]
    assert all(g < limit for g, limit in zip(gaps, limits)), gaps


def selection(kind: str, seq: int, tile: int, top_k: int):
    """``[1, seq, seq]`` bool, query by key, each row with at least one key and none after
    its own position. ``random``: what ``pattern.select_keys`` keeps of random scores (the
    first ``top_k`` rows keep every key up to their own: a query with fewer than ``top_k``
    keys). ``late``: from the second tile on a query keeps keys of its own tile alone, so
    every tile the kernels visit before the diagonal holds none of its keys. ``causal``:
    the causal mask itself."""
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    causal = j <= i
    if kind == "causal":
        return causal[None]
    scores = jax.random.normal(jax.random.PRNGKey(seq + top_k), (1, seq, seq))
    chosen = pattern.select_keys(scores, 0, top_k)[0]
    if kind == "late":
        chosen = (chosen & (j // tile == i // tile)[None]) | (i == j)[None]
    return chosen


def summed_reference(q, k, v, selected):
    """``pattern._attend_summed`` under ``selected`` with every key at once: (output ``[B,
    T, H * dh]``, the probabilities summed over all heads ``[B, T, T]``)."""
    out, probs = pattern._attend_summed(*pattern._heads_first(q, k, v), selected)
    return pattern._heads_last(out, q.shape[1]), probs.sum(axis=0)


def plain_selected_attention(q, k, v, selected):
    b, t, h, dh = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    probs = jax.nn.softmax(jnp.where(selected[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * v.shape[-1])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("form", ["causal", "window", "selected", "noised"])
def test_two_batch_rows_of_two_kv_heads_each_start_from_an_empty_accumulator(
        form, dtype, monkeypatch):
    """The backward kernel holds dQ of a whole sequence in VMEM for one (batch row, KV head)
    and zeroes it at that pair's first step: with two rows of two KV heads, four tiles of
    128 rows each and groups of two, every pair but the first starts where another ended.
    Value and gradients of all four forms against plain attention under the whole mask,
    and each row's against the same row alone, bit for bit."""
    monkeypatch.setattr(attention, "FULL_TILE", 128)
    monkeypatch.setattr(attention, "WINDOW_TILE", 128)
    seq, groups = 512, 2
    keys = jax.random.split(jax.random.PRNGKey(49), 4)
    q = jax.random.normal(keys[0], (2, seq, HKV * groups, DH))
    k, v = (jax.random.normal(key, (2, seq, HKV, DH)) for key in keys[1:3])
    weight = jax.random.normal(keys[3], (2, seq, HKV * groups * DH))
    if form == "selected":
        selected = jnp.concatenate([selection("random", seq, 128, 96),
                                    selection("late", seq, 128, 96)])
        plain = lambda *a: plain_selected_attention(*a, selected)  # noqa: E731
        blocked = lambda *a: attention.blocked_attention(*a, selected=selected)[0]  # noqa: E731
    elif form == "noised":
        plain = lambda *a: plain_noised_attention(*a, 16, seq // 2)  # noqa: E731
        blocked = lambda *a: attention.blocked_attention(*a, noised=(16, seq // 2))  # noqa: E731
    else:
        window = 200 if form == "window" else None
        plain = lambda *a: plain_attention(*a, window)  # noqa: E731
        blocked = lambda *a: attention.blocked_attention(*a, window=window)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(plain, q, k, v, weight)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    got = value_and_grads(blocked, q, k, v, weight)
    limits = (F32_GAP,) * 4 if dtype == jnp.float32 else (BF16_VALUE_GAP,) + (BF16_GRAD_GAP,) * 3
    gaps = [gap(a, b) for a, b in zip(got, want)]
    assert all(g < limit for g, limit in zip(gaps, limits)), gaps
    if form == "selected":
        return  # a row alone takes its own selection: another closure, compared above
    for row in range(2):
        alone = value_and_grads(blocked, *(x[row:row + 1] for x in (q, k, v, weight)))
        for a, b in zip(got, alone):
            np.testing.assert_array_equal(np.asarray(a[row:row + 1]), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("window,seq,groups", [(128, 512, 2), (200, 512, 2), (129, 640, 1),
                                               (300, 1024, 6)])
def test_a_window_whose_reach_is_under_the_tile_count(window, seq, groups, dtype, monkeypatch):
    """Tiles of 128 rows, so that a query tile sees 1, 2 or 3 tiles back of 4, 5 or 8: a
    query tile's contributions to dQ end before the last key tile, and a key tile's walk
    runs past the last query tile (the steps not taken). At the cells' 256-row tiles the
    cases above have two tiles, where the reach is the whole sequence."""
    monkeypatch.setattr(attention, "WINDOW_TILE", 128)
    plan = attention._plan(jax.ShapeDtypeStruct((1, seq, HKV * groups, DH), dtype),
                           jax.ShapeDtypeStruct((1, seq, HKV, DH), dtype), window)
    assert plan.tile == 128 and 1 <= plan.reach < plan.n - 1
    q, k, v, weight = inputs(seq, groups)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(lambda *a: plain_attention(*a, window), q, k, v, weight)
    got = value_and_grads(lambda *a: attention.blocked_attention(*a, window=window),
                          *(x.astype(dtype) for x in (q, k, v)), weight)
    limits = (F32_GAP,) * 4 if dtype == jnp.float32 else (BF16_VALUE_GAP,) + (BF16_GRAD_GAP,) * 3
    gaps = [gap(a, b) for a, b in zip(got, want)]
    assert all(g < limit for g, limit in zip(gaps, limits)), gaps


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("kind", ["random", "late", "causal"])
def test_kernels_under_a_selection_equal_the_blocks_under_the_same_mask(
        kind, groups, dtype, monkeypatch):
    """Forward, the heads' summed probabilities, dQ, dK and dV of the kernels with the
    selection as an operand, against ``_attend_summed`` in float32: 4 x 4 tiles of 128
    rows, 10 of them visited. The probabilities are zero off the selection and in the tiles
    no kernel visits, and add up to the number of heads on every row; under the causal
    mask the kernels give what they give with no selection."""
    monkeypatch.setattr(attention, "FULL_TILE", 128)
    seq, top_k = 512, 96
    q, k, v, weight = inputs(seq, groups)
    selected = selection(kind, seq, 128, top_k)
    assert bool(jnp.all(selected.sum(-1) >= 1)) and not bool(jnp.any(jnp.triu(selected[0], 1)))
    if kind == "late":  # rows whose first visited tiles are empty, with far under top_k keys
        assert not bool(jnp.any(selected[0, 128:, :128]))
        assert 1 <= int(selected[0, 128:].sum(-1).max()) < top_k

    def with_probs(fn):
        def weighed(*qkv):
            out, probs = fn(*qkv)
            return jnp.sum(out.astype(jnp.float32) * weight), (out.astype(jnp.float32), probs)

        (_, (out, probs)), grads = jax.jit(jax.value_and_grad(
            weighed, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, probs, *grads

    with jax.default_matmul_precision("highest"):
        want = with_probs(lambda *a: summed_reference(*a, selected))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    got = with_probs(lambda *a: attention.blocked_attention(*a, selected=selected))
    assert got[1].shape == (1, seq, seq) and got[1].dtype == jnp.float32
    assert float(jnp.abs(jnp.where(selected, 0.0, got[1])).max()) == 0.0
    np.testing.assert_allclose(np.asarray(got[1].sum(-1)), HKV * groups, rtol=2e-3)
    for a, b in zip(got[2:], (q, k, v)):
        assert a.shape == b.shape and a.dtype == dtype
    # the probabilities are sums of float32 exponentials of scores from rounded operands
    limits = ((F32_GAP,) * 5 if dtype == jnp.float32 else
              (BF16_VALUE_GAP, BF16_VALUE_GAP) + (BF16_GRAD_GAP,) * 3)
    gaps = [gap(a, b) for a, b in zip(got, want)]
    assert all(g < limit for g, limit in zip(gaps, limits)), gaps
    if kind == "causal":  # the same arithmetic; the interpreter's two programs fuse apart
        positional = value_and_grads(attention.blocked_attention, q, k, v, weight)
        same = [gap(a, b) for a, b in zip((got[0], *got[2:]), positional)]
        assert all(g < SAME_GAP[dtype] for g in same), same


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_indexed_attention_on_the_kernels_reads_what_it_reads_on_the_blocks(dtype, monkeypatch):
    """``pattern.indexed_attention`` with ``kernels`` against itself without, four groups
    of 128 rows that keep 96 keys: the same mask row for row, so the same ``keys_selected``
    and ``select_ties`` to the digit; the output, each query's divergence (``index_kl``)
    and the gradients the two losses send to q, k, v and to the indexer's q, w and k to
    rounding."""
    monkeypatch.setattr(attention, "FULL_TILE", 128)
    seq, top_k, block = 512, 96, 128
    q, k, v, weight = inputs(seq, 2)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    qi = jax.random.normal(keys[0], (1, seq, 2, 64))
    wi = jax.random.normal(keys[1], (1, seq, 2)) / 2
    ki = jax.random.normal(keys[2], (1, seq, 64))
    q, k, v, qi, ki = (x.astype(dtype) for x in (q, k, v, qi, ki))

    def run(kernels):
        def losses(*operands):
            out, divergence, selected, tied, masks = pattern.indexed_attention(
                *operands, top_k, block, kernels=kernels)
            own = jnp.sum(out.astype(jnp.float32) * weight) + jnp.mean(divergence)
            return own, (out.astype(jnp.float32), divergence, selected, tied, masks)

        (_, aux), grads = jax.jit(jax.value_and_grad(
            losses, argnums=tuple(range(6)), has_aux=True))(q, k, v, qi, wi, ki)
        return aux, grads

    (out, divergence, selected, tied, masks), grads = run(True)
    (want_out, want_divergence, want_selected, want_tied, want_masks), want_grads = run(False)
    np.testing.assert_array_equal(np.asarray(selected), np.asarray(want_selected))
    np.testing.assert_array_equal(
        np.asarray(selected[0]), np.minimum(np.arange(seq) + 1, top_k))
    np.testing.assert_array_equal(np.asarray(tied), np.asarray(want_tied))
    for a, b in zip(masks, want_masks):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    value, grad = ((F32_GAP, F32_GAP) if dtype == jnp.float32 else
                   (BF16_VALUE_GAP, BF16_GRAD_GAP))
    assert gap(out, want_out) < value
    assert gap(divergence, want_divergence) < grad
    gaps = [gap(a, b) for a, b in zip(grads, want_grads)]
    assert all(g < grad for g in gaps), gaps


def test_a_selection_takes_no_window_and_its_own_shape():
    q, k, v, _ = inputs(256, 1)
    causal = selection("causal", 256, 256, 0)
    with pytest.raises(ValueError, match="takes no window"):
        attention.blocked_attention(q, k, v, window=128, selected=causal)
    with pytest.raises(ValueError, match="takes no window"):
        attention.blocked_attention(q, k, v, selected=causal[:, :128])


# latent attention's widths (a score 192 wide over values of 128), a score narrower than
# the values, and one of whole lane groups; a group of one, as latent attention has it, and
# of two. The kernels take one width, so these shapes go by ``pattern.full_attention``
UNEQUAL = [(width, seq, groups, block) for width in (192, 64, 256) for seq in (256, 512)
           for groups in (1, 2) for block in (128,)] + [(192, 512, 1, 512)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("score_width,seq,groups,block", UNEQUAL)
def test_blocks_at_a_score_width_that_differs_from_the_value_width(
        score_width, seq, groups, block, dtype):
    """Forward, dQ, dK and dV of the ``jax.numpy`` blocks against plain masked attention,
    whose scores are scaled by the score width; the cotangents of q and k come back at
    that width. The limits are the kernels' (the same rounding: bf16 operands, float32
    scores and sums)."""
    q, k, v, weight = inputs(seq, groups, score_width)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(plain_attention, q, k, v, weight)
        got = value_and_grads(lambda *a: pattern.full_attention(*a, block),
                              *(x.astype(dtype) for x in (q, k, v)), weight)
    assert got[0].shape == (1, seq, HKV * groups * DH)
    for a, b in zip(got[1:], (q, k, v)):
        assert a.shape == b.shape and a.dtype == dtype
    limits = (F32_GAP,) * 4 if dtype == jnp.float32 else (BF16_VALUE_GAP,) + (BF16_GRAD_GAP,) * 3
    gaps = [gap(a, b) for a, b in zip(got, want)]
    assert all(g < limit for g, limit in zip(gaps, limits)), gaps


def test_shapes_that_do_not_tile_are_refused():
    q, k, v, _ = inputs(256, 1)
    assert not attention.applies(200, DH, None) and not attention.applies(256, 64, None)
    assert not attention.applies(640, DH, None)  # over one tile, not whole tiles
    assert attention.applies(256, DH, 128) and attention.applies(8192, DH, 512)
    with pytest.raises(ValueError, match="does not tile"):
        attention.blocked_attention(q[:, :200], k[:, :200], v[:, :200])


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """``attention_paths`` asks ``jax.default_backend()``, which is the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def laguna_config(seq: int):
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", "laguna-xs2-l5-ep8.json")
    return harness.load_family(config).program_config(config, seq)


def kimi_config(seq: int):
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", "kimi-vl-a3b-l6-ep8.json")
    return harness.load_family(config).program_config(config, seq)


def test_attention_paths_of_the_latent_kind_off_the_tpu_are_the_blocks():
    assert pattern.attention_paths(pattern.PatternConfig.tiny_latent(), 40) == {
        "latent": {"path": "blocks", "block": 16, "score_width": 24, "value_width": 16}}
    assert pattern.attention_paths(kimi_config(8192), 8192) == {
        "latent": {"path": "blocks", "block": 1024, "score_width": 192, "value_width": 128}}


EQUAL_WIDTHS = pattern.Latent(kv_rank=32, d_nope=64, d_rope=64, d_value=128)


def test_attention_paths_of_the_latent_kind_on_a_tpu_follow_the_shapes(as_on_a_tpu):
    """The kernels take one width for queries, keys and values: a score of 192 over
    values of 128 goes by the blocks on a TPU too, a latent kind of equal widths by the
    kernels."""
    cfg = kimi_config(8192)
    assert pattern.attention_paths(cfg, 8192) == {"latent": {
        "path": "blocks", "block": 1024, "score_width": 192, "value_width": 128}}
    equal = pattern.PatternConfig.tiny_latent(head_dim=128, latent=EQUAL_WIDTHS)
    assert pattern.attention_paths(equal, 512) == {"latent": {
        "path": "kernel", "tile": attention.FULL_TILE, "score_width": 128, "value_width": 128}}
    assert pattern.attention_paths(equal, 512 + 128)["latent"]["path"] == "blocks"


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_attention_paths_of_the_indexed_kind_follow_the_backend_and_the_shapes(monkeypatch, backend):
    """An indexed layer's mask is data, which the kernels take as an operand: at heads of
    128 and 8,192 tokens, shapes the kernels tile, the kind takes them on a TPU (tiles of
    512 rows) and the blocks on the CPU, and says on both how many keys a query keeps; at
    the tests' tiny widths, or a sequence that is not whole tiles and whole blocks of the
    indexer's rows, the blocks on a TPU too."""
    from benchmark import harness

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = harness.read_json(harness.HERE, "configs", "keye-vl2-30b-a3b-l6-ep8.json")
    cfg = harness.load_family(config).program_config(config, 8192)
    assert attention.applies(8192, cfg.head_dim, None)
    path = {"cpu": {"path": "blocks", "block": 512, "scores": "blocks"},
            "tpu": {"path": "kernel", "tile": attention.FULL_TILE, "scores": "kernel"}}[backend]
    assert pattern.attention_paths(cfg, 8192) == {"indexed": {
        **path, "selected": 2048, "selection": "mask"}}
    assert pattern.attention_paths(pattern.PatternConfig.tiny_indexed(), 8) == {"indexed": {
        "path": "blocks", "block": 8, "selected": 8, "selection": "mask", "scores": "blocks"}}
    # 8,192 + 128 rows are no whole tiles; 384 rows are one tile of the kernels' and one
    # block of the indexer's, 1,024 rows are two tiles and, by blocks of 768, no whole blocks
    assert pattern.attention_paths(cfg, 8192 + 128)["indexed"]["path"] == "blocks"
    assert pattern.attention_paths(cfg, 384)["indexed"]["path"] == path["path"]
    assert pattern.attention_paths(
        dataclasses.replace(cfg, attn_block=768), 1024)["indexed"]["path"] == "blocks"


def test_attention_paths_off_the_tpu_are_the_blocks():
    tiny = pattern.PatternConfig.tiny()
    assert pattern.attention_paths(tiny, 40) == {
        "full": {"path": "blocks", "block": 16}, "sliding": {"path": "blocks", "block": 8}}
    cfg = laguna_config(8192)
    assert {p["path"] for p in pattern.attention_paths(cfg, 8192).values()} == {"blocks"}


def test_attention_paths_on_a_tpu_follow_the_shapes(as_on_a_tpu):
    cfg = laguna_config(8192)
    assert pattern.attention_paths(cfg, 8192) == {
        "full": {"path": "kernel", "tile": attention.FULL_TILE},
        "sliding": {"path": "kernel", "tile": attention.WINDOW_TILE}}
    # a sequence that is no multiple of the tile, and heads of no whole lane group
    assert pattern.attention_paths(cfg, 8192 + 128) == {
        "full": {"path": "blocks", "block": 1024}, "sliding": {"path": "blocks", "block": 512}}
    tiny = pattern.PatternConfig.tiny()
    assert {p["path"] for p in pattern.attention_paths(tiny, 512).values()} == {"blocks"}
    # a window as long as the sequence is the causal half, on the full layers' tile
    assert pattern.attention_paths(cfg, 512)["sliding"] == {"path": "kernel", "tile": 512}


@pytest.mark.parametrize("kept,forwards", [
    ((), 2),  # nothing kept: the layer's ``jax.checkpoint`` runs the forward kernel again
    ((attention.OUT_NAME,), 2),  # the output alone: again, for the log-sum-exp
    ((attention.OUT_NAME, attention.LSE_NAME), 1),
], ids=["nothing", "out", "out+lse"])
@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_every_kernel_of_a_layer_carries_the_core_scope(kind, kept, forwards, as_on_a_tpu):
    """Lowered for the TPU (nothing compiles or runs): the forward, the forward that the
    layer's ``jax.checkpoint`` recomputes unless its policy keeps both residuals the
    kernel names, and the backward kernel are custom calls whose ``op_name`` the
    benchmark's ``attn.roofline`` reader puts under ``attn/<kind>/core``."""
    from benchmark import harness

    mark = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    cfg = pattern.PatternConfig.tiny(head_dim=128, window=128)
    seq = 256
    assert pattern.attention_paths(cfg, seq)[kind]["path"] == "kernel"
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: jax.ShapeDtypeStruct(w.shape[1:], w.dtype), params["attn"][kind])
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), cfg.dtype)
    tables = pattern.rope_tables(cfg.rope(kind), cfg.head_dim, seq)

    def loss(x, lp):
        layer = jax.checkpoint(lambda x, lp: pattern._attn_block(cfg, kind, x, lp, *tables),
                               policy=jax.checkpoint_policies.save_only_these_names(*kept))
        return jnp.sum(layer(x, lp).astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(x, lp).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [names[ref] for ref in re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert sorted(name.rsplit("/", 2)[-2] for name in kernels) == [
        "blocked_attention_bwd", *["blocked_attention_fwd"] * forwards], kernels
    assert all(mark.search(name) and f"attn/{kind}" in name for name in kernels), kernels
    assert sum("rematted_computation" in name for name in kernels) == forwards - 1


def test_every_kernel_of_a_latent_layer_carries_the_core_scope_and_none_the_latent_one(
        as_on_a_tpu):
    """As above for latent attention of equal widths (the shapes the kernels take): the
    three kernels are under ``attn/full/core``, and the projections' products under
    ``attn/full/latent``, where the new reader looks."""
    from benchmark import harness

    core = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    latent = harness.load_by_path("layer_metrics", "attn.latent_ms").SCOPE
    cfg = pattern.PatternConfig.tiny_latent(head_dim=128, latent=EQUAL_WIDTHS)
    seq = 256
    assert pattern.attention_paths(cfg, seq)["latent"]["path"] == "kernel"
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: jax.ShapeDtypeStruct(w.shape[1:], w.dtype), params["attn"]["latent"])
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), cfg.dtype)
    tables = pattern.rope_tables(cfg.rope_latent, cfg.latent.d_rope, seq)

    def loss(x, lp):
        layer = jax.checkpoint(lambda x, lp: pattern._latent_block(cfg, x, lp, *tables))
        return jnp.sum(layer(x, lp).astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(x, lp).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [names[ref] for ref in re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert sorted(name.rsplit("/", 2)[-2] for name in kernels) == [
        "blocked_attention_bwd", "blocked_attention_fwd", "blocked_attention_fwd"], kernels
    assert all(core.search(name) and not latent.search(name) for name in kernels), kernels
    products = [n for n in names.values() if latent.search(n) and n.endswith("dot_general")]
    for pass_ in ("jvp(attn/full)", "rematted_computation/attn/full", "transpose("):
        assert any(pass_ in n for n in products), (pass_, products)


def mosaic_kernels(lowered_text: str) -> list:
    """(name, operands, sha256 of the module printed without locations) of each Mosaic
    kernel in a program lowered for the TPU. The payload of a kernel holds the file and
    the line of every operation of its source, so the bytes themselves move with any edit
    to the file; the operations do not."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    context = mlir.JaxIrContext()
    tpu.register_dialect(context)
    context.allow_unregistered_dialects = True
    kernels = []
    for operands, body in re.findall(
            r'stablehlo\.custom_call @tpu_custom_call\(([^)]*)\).*?\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
            lowered_text):
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            text = module.operation.get_asm(enable_debug_info=False)
        name = re.search(r"module @(\w+)", text).group(1)
        kernels.append((name, operands.count("%"), hashlib.sha256(text.encode()).hexdigest()))
    return kernels


#: the two kernels of a laguna layer at the cell's shapes (8,192 tokens, 8 KV heads of
#: 128). The forward is as it was lowered at the parent of PR 36, before the kernels took a
#: selection; the backward is PR 49's one kernel, where the parent of PR 36 (and of PR 49)
#: lowered two, in the full layers
#:   ("blocked_attention_dq", 6, "4db3f5b67eb15450abaeaf29e5336c43be6abd2c16df6d3418c6ee4cd4849fd5")
#:   ("blocked_attention_dkv", 6, "0d4d70a3f0b6a48b5eb7fd539ba6fda5da966af779beced8b44356ac7bc5a88e")
#: and in the sliding ones
#:   ("blocked_attention_dq", 6, "3b2b815c7b9372fb688df8df0f55f52b54142c9c3a4a66fee67dae604fd14897")
#:   ("blocked_attention_dkv", 6, "c2b41c4b520135fe03f80a8a24cf2364cd9f544f319b0f9ba74207e1c49f3cfc")
AS_BEFORE_A_SELECTION = {
    "full": [  # 48 query heads, tiles of 512 rows
        ("blocked_attention_fwd", 3, "49f06cc0ad5e668833dc7cbdf43ca7ee9f6d81b4088c48e2b354c4f3836f7b32"),
        ("blocked_attention_bwd", 6, "539089f52a8abc6499e00d4654b70fd0031b5e12642fbcf8cb2638ce7075603a")],
    "sliding": [  # 64 query heads, a window of 512 in tiles of 256 rows
        ("blocked_attention_fwd", 3, "9e8419de2dc59086f108baa1012abea51d065f9533f7e2c72245fc9c23f50c98"),
        ("blocked_attention_bwd", 6, "21ffb18976ba6ca74b9e4d2a0d0f44d0b727b87f047d1a6a80b0fcb8d737c184")],
}


@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_without_a_selection_the_kernels_lower_to_what_they_were(kind, as_on_a_tpu):
    """``blocked_attention(..., selected=None)`` at laguna's shapes, lowered for the TPU
    (nothing compiles or runs): two kernels by name, their operands (no selection among
    them) and, location for location aside, the forward's very operations at the parent of
    PR 36 and the backward's as PR 49 made it. An edit that reaches the kernels laguna runs
    shows here, at no chip time; one that is meant to changes these hashes with its own
    before and after."""
    cfg = laguna_config(8192)
    heads = {spec.attn: spec.n_heads for spec in cfg.layers}[kind]
    window = cfg.window if kind == "sliding" else None
    q = jax.ShapeDtypeStruct((1, 8192, heads, cfg.head_dim), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(attention.blocked_attention(q, k, v, window=window).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    assert mosaic_kernels(text) == AS_BEFORE_A_SELECTION[kind]


#: the two kernels at the Ouro cell's shapes (4,096 tokens, 16 query heads over 16 KV
#: heads of 128: one query head a KV head, tiles of 512 rows): the forward as PR 43 first
#: lowered and ran it on a v5e, the backward as PR 49 did, where PR 43 had
#:   ("blocked_attention_dq", 6, "e6f372feaa873fa6921dbd85e2833a24c2d81c21a3df13d4072ec826d44ba196")
#:   ("blocked_attention_dkv", 6, "48668e62b2f0cbcf183bd165470a9385cfb22018b6761c2e00b6a8d40c71e02f")
AT_ONE_QUERY_HEAD_A_KV_HEAD = [
    ("blocked_attention_fwd", 3, "fca5a722c604a31a10587058e580caf78f0daed4b38b9bd71f7beb16b6449515"),
    ("blocked_attention_bwd", 6, "651acce005584bad9a3f4ccb22239cfd30f8515bb0b5e3619dc93b6b946aa2fb")]


def test_the_kernels_lower_at_one_query_head_a_kv_head(as_on_a_tpu):
    """``blocked_attention`` at the shapes of ``ouro_2_6b_steady_noprof``
    (``[1, 4096, 16, 128]`` over 16 KV heads), lowered for the TPU (nothing compiles or
    runs): a group of one, so a row statistic is one value on the lanes, which no other
    cell has (laguna's groups are 6 and 8, the tests' interpreter cases 1, 6 and 8). The
    shapes are the configuration's own."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", "ouro-2.6b-l8.json")
    cfg = harness.load_family(config).program_config(config, config["batch"][1])
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (16, 16, 128)
    q = jax.ShapeDtypeStruct((1, config["batch"][1], cfg.n_heads, cfg.head_dim), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(attention.blocked_attention(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    assert mosaic_kernels(text) == AT_ONE_QUERY_HEAD_A_KV_HEAD


def cell_attention_shapes(name: str, kind: str):
    """(q, k as shapes, the keywords of ``attention.backward_vmem_bytes``) of one layer
    kind of an accepted configuration at its cell's batch, read from its file."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{name}.json")
    batch, seq = config["batch"]
    cfg = harness.load_family(config).program_config(config, seq)
    how = {}
    if kind == "dense":  # models/transformer.py: one kind, no pattern of layers
        heads = cfg.n_heads
    else:
        heads = {spec.attn: spec.n_heads for spec in cfg.layers}[kind]
        seq = cfg.stream(seq)
        path = pattern.attention_paths(cfg, seq)[kind]
        if path.get("walk") == "noised":
            how["noised"] = (path["block_length"], path["clean"])
        how["selection"] = kind == "indexed"
        if kind == "sliding":
            how["window"] = cfg.window
    q = jax.ShapeDtypeStruct((batch, seq, heads, cfg.head_dim), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((batch, seq, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    return q, k, how


#: (configuration, layer kind) -> (query heads a KV head, tiles, the float32 dQ of one
#: (batch row, KV head)'s whole sequence in bytes)
BACKWARD_IN_VMEM = {
    ("keye-vl2-30b-a3b-l6-ep8", "indexed"): (8, 16, 33_554_432),
    ("sdar-30b-a3b-l6-ep8", "full"): (8, 16, 33_554_432),
    ("laguna-xs2-l5-ep8", "full"): (6, 16, 25_165_824),
    ("laguna-xs2-l5-ep8", "sliding"): (8, 32, 33_554_432),
    ("ouro-2.6b-l8", "dense"): (1, 8, 2_097_152),
}


@pytest.mark.parametrize("name,kind", list(BACKWARD_IN_VMEM))
def test_the_backward_call_fits_the_vmem_it_is_given(name, kind, as_on_a_tpu):
    """Sizes alone, nothing lowered or run: the backward kernel keeps dQ of one (batch row,
    KV head)'s whole sequence in float32 scratch beside dK's and dV's tile, and Pallas holds
    two buffers of every block. At the shapes of every cell on the kernels, read from the
    configurations' own files, that is under ``VMEM_LIMIT_BYTES`` with room for Mosaic's own
    temporaries (eight ``[tile, tile]`` float32 arrays: a tile's scores, probabilities and
    their cotangents, twice), so a tile, a group or a sequence that would not fit fails
    here and not in Mosaic on the chip."""
    q, k, how = cell_attention_shapes(name, kind)
    plan = attention._plan(q, k, how.get("window"), how.get("noised"))
    groups, tiles, accumulator = BACKWARD_IN_VMEM[name, kind]
    assert (plan.groups, plan.n) == (groups, tiles)
    assert plan.n * plan.tile * plan.groups * plan.dh * 4 == accumulator
    asked = attention.backward_vmem_bytes(q, k, **how)
    width = plan.groups * plan.dh
    blocks = (2 * plan.tile * width * 2 + 2 * plan.tile * plan.dh * 2  # q, do; k, v
              + 2 * plan.groups * plan.tile * 4 + how.get("selection", False) * plan.tile ** 2
              + plan.tile * width * 2 + 2 * plan.tile * plan.dh * 2)  # dq; dk, dv
    assert asked == accumulator + 2 * plan.tile * plan.dh * 4 + 2 * blocks
    assert asked + 8 * plan.tile ** 2 * 4 < attention.VMEM_LIMIT_BYTES < 128 * 2 ** 20
    assert attention.applies(plan.t if "noised" not in how else how["noised"][1], plan.dh,
                             how.get("window"))


# -- the block-diffusion form: a doubled stream under a mask from positions alone ------

def noised_mask(block: int, clean: int) -> np.ndarray:
    """``[2 clean, 2 clean]`` bool, query by key, built whole from ``//`` and comparisons."""
    position = np.arange(2 * clean)
    late, of_block = position >= clean, position % clean // block
    q_late, k_late, q_block, k_block = late[:, None], late[None, :], of_block[:, None], of_block[None, :]
    return ((~q_late & ~k_late & (k_block <= q_block)) | (q_late & ~k_late & (k_block < q_block))
            | (q_late & k_late & (k_block == q_block)))


def plain_noised_attention(q, k, v, block: int, clean: int):
    b, t, h, dh = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    probs = jax.nn.softmax(jnp.where(noised_mask(block, clean), scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * dh)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("block,clean,groups", [
    (4, 256, 1), (4, 256, 8), (16, 256, 6), (16, 384, 1), (128, 256, 6), (4, 128, 8)])
def test_noised_kernels_equal_plain_attention_under_the_whole_mask(
        block, clean, groups, dtype, monkeypatch):
    """Value and the three gradients of the kernels' ``noised`` form against plain attention
    under the whole ``[2L, 2L]`` mask, at tiles of 128 rows: a stream of 4 and of 6 tiles
    (2 and 3 a half), blocks of 4 and of 16, a block that is a whole tile (every diagonal
    tile all or nothing), and a half that is one tile."""
    monkeypatch.setattr(attention, "FULL_TILE", 128)
    q, k, v, weight = inputs(2 * clean, groups)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(lambda *a: plain_noised_attention(*a, block, clean), q, k, v, weight)
    got = value_and_grads(lambda *a: attention.blocked_attention(*a, noised=(block, clean)),
                          *(x.astype(dtype) for x in (q, k, v)), weight)
    assert got[0].shape == (1, 2 * clean, HKV * groups * DH)
    for a, b in zip(got[1:], (q, k, v)):
        assert a.shape == b.shape and a.dtype == dtype
    limits = (F32_GAP,) * 4 if dtype == jnp.float32 else (BF16_VALUE_GAP,) + (BF16_GRAD_GAP,) * 3
    gaps = [gap(a, b) for a, b in zip(got, want)]
    assert all(g < limit for g, limit in zip(gaps, limits)), gaps


@pytest.mark.parametrize("path", ["kernel", "blocks"])
def test_no_clean_row_reads_a_noised_key_and_a_noised_row_of_block_0_reads_its_own_block_alone(
        path, monkeypatch):
    """Bit for bit: other keys and values in the noised half leave every clean row's
    output as it was; other keys and values everywhere but in block 0 of the noised half
    leave the noised rows of block 0 as they were (their first visited tile holds none of
    their keys: what it leaves in the running sums is wiped), and change every other
    noised row."""
    monkeypatch.setattr(attention, "FULL_TILE", 128)
    block, clean = 4, 256
    q, k, v, _ = inputs(2 * clean, 2)
    other_k, other_v = (x + 1.0 for x in (k, v))
    if path == "kernel":
        attend = jax.jit(lambda q, k, v: attention.blocked_attention(q, k, v, noised=(block, clean)))
    else:
        attend = jax.jit(lambda q, k, v: pattern.noised_attention(q, k, v, block, clean, 96))
    base = np.asarray(attend(q, k, v))
    late = jnp.arange(2 * clean)[None, :, None, None] >= clean
    noised_changed = np.asarray(attend(q, jnp.where(late, other_k, k), jnp.where(late, other_v, v)))
    np.testing.assert_array_equal(noised_changed[:, :clean], base[:, :clean])
    assert not np.array_equal(noised_changed[:, clean:], base[:, clean:])
    own = (jnp.arange(2 * clean) >= clean) & (jnp.arange(2 * clean) < clean + block)
    own = own[None, :, None, None]
    rest_changed = np.asarray(attend(q, jnp.where(own, k, other_k), jnp.where(own, v, other_v)))
    np.testing.assert_array_equal(rest_changed[:, clean:clean + block], base[:, clean:clean + block])
    differs = np.any(rest_changed != base, axis=(0, 2))
    assert differs[clean + block:].all() and differs[:clean].all()


def visited_tiles(half: int) -> dict:
    """The (query tile, key tile) pairs each kernel of the block-diffusion form takes at
    ``half`` tiles a half, by the kernels' own walks over their grids: the forward's
    ``(2 half, half + 1)`` steps by query tile, the backward's ``(2 half, 2 half)`` by key
    tile."""
    by_query = [(i, int(kj)) for i in range(2 * half) for j in range(half + 1)
                for kj, seen in [attention._noised_key_tile(np.int32(i), np.int32(j), half)]
                if seen]
    by_key = [(int(qi), j) for j in range(2 * half) for i in range(2 * half)
              for qi, seen in [attention._noised_query_tile(np.int32(j), np.int32(i), half)]
              if seen]
    return {"fwd": by_query, "bwd": by_key}


@pytest.mark.parametrize("half", [1, 2, 3, 8])
def test_the_noised_walk_visits_the_tile_pairs_the_mask_leaves_and_no_other(half):
    """The two kernels' walks over their grids, as the kernels compute them, against the
    tiles of the whole mask that hold a pair: ``half (half + 1) + half`` of the ``4
    half^2``, 80 of 256 at the cell's eight tiles a half (a causal walk over the doubled
    stream would visit 136), forward and backward alike; the tiles that skip the mask are
    the ones the mask leaves whole; and in the backward's walk a query tile's own key tile,
    after which its dQ leaves the kernel, is the last key tile that holds a pair of it."""
    tile, block = 8, 4  # the walk knows tiles, not rows
    mask = noised_mask(block, half * tile).reshape(2 * half, tile, 2 * half, tile)
    holds_a_pair = {(i, j) for i in range(2 * half) for j in range(2 * half)
                    if mask[i, :, j].any()}
    whole = {(i, j) for i in range(2 * half) for j in range(2 * half) if mask[i, :, j].all()}
    visited = visited_tiles(half)
    assert set(visited) == {"fwd", "bwd"}
    for kernel, pairs in visited.items():
        assert len(pairs) == len(set(pairs)) == half * (half + 1) + half, kernel
        assert set(pairs) == holds_a_pair, kernel
    # the backward walks the key tiles in ascending order, each from its own query tile
    assert [pair for pair in visited["bwd"] if pair[0] == pair[1]] == [
        (j, j) for j in range(2 * half)]
    assert all(j <= i for i, j in visited["bwd"])
    if half == 8:
        assert len(visited["fwd"]) == 80 and (2 * half) * (2 * half + 1) // 2 == 136
    uncut = {(i, j) for i, j in holds_a_pair
             if bool(attention._noised_uncut(np.int32(i), np.int32(j), half))}
    assert uncut == whole and len(uncut) == half * (half - 1)
    for i, j in holds_a_pair - whole:  # the three kinds of diagonal tile, from iotas
        for q_axis in (0, 1):
            keep = np.asarray(attention._noised_keep(np.int32(i), np.int32(j), tile, block,
                                                     half, q_axis))
            want = mask[i, :, j]
            np.testing.assert_array_equal(keep, want if q_axis == 0 else want.T)


def test_the_noised_form_takes_the_shapes_that_tile_and_refuses_the_rest():
    assert attention.applies_noised(8192, 128, 4, 4096)
    assert attention.applies_noised(256, 128, 16, 128)  # a half that is one short tile
    assert not attention.applies_noised(8192, 128, 3, 4096)  # a block's number is a shift
    assert not attention.applies_noised(8192, 64, 4, 4096)  # heads of no whole lane group
    assert not attention.applies_noised(8192 + 512, 128, 4, 4096)  # not two halves
    assert not attention.applies_noised(2 * 4000, 128, 4, 4000)  # halves of no whole tiles
    assert not attention.applies_noised(8192, 128, 1024, 4096)  # a block a tile would cut
    q = jnp.zeros((1, 512, 2, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 512, 1, 128), jnp.bfloat16)
    for kwargs in ({"window": 128}, {"selected": jnp.ones((1, 512, 512), bool)}):
        with pytest.raises(ValueError, match="doubled stream"):
            attention.blocked_attention(q, kv, kv, noised=(4, 256), **kwargs)
    with pytest.raises(ValueError, match="doubled stream"):
        attention.blocked_attention(q, kv, kv, noised=(3, 256))


def test_every_kernel_of_a_diffused_layer_carries_the_core_scope(as_on_a_tpu):
    """Lowered for the TPU (nothing compiles or runs): a full layer with head norms and no
    gate over a doubled stream, differentiated through a ``jax.checkpoint`` that keeps the
    kernel's two residuals: the forward once and the backward kernel, each a custom call
    under ``attn/full/core``, and no operand beside q, k, v and the backward's three more:
    the mask is no array."""
    from benchmark import harness

    mark = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    cfg = pattern.PatternConfig.tiny_diffusion(head_dim=128)
    stream = 2048
    assert pattern.attention_paths(cfg, stream)["full"] == {
        "path": "kernel", "tile": 512, "walk": "noised", "block_length": 4, "clean": 1024}
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: jax.ShapeDtypeStruct(w.shape[1:], w.dtype), params["attn"]["full"])
    x = jax.ShapeDtypeStruct((1, stream, cfg.d_model), cfg.dtype)
    tables = pattern._rope_tables(cfg, stream)["full"]

    def loss(x, lp):
        layer = jax.checkpoint(
            lambda x, lp: pattern._attn_block(cfg, "full", x, lp, *tables),
            policy=jax.checkpoint_policies.save_only_these_names(
                attention.OUT_NAME, attention.LSE_NAME))
        return jnp.sum(layer(x, lp).astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(x, lp).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [names[ref] for ref in re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert sorted(name.rsplit("/", 2)[-2] for name in kernels) == [
        "blocked_attention_bwd", "blocked_attention_fwd"], kernels
    assert all(mark.search(name) and "attn/full" in name for name in kernels), kernels
    assert [(name, operands) for name, operands, _ in mosaic_kernels(
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(x, lp).lower(
            lowering_platforms=("tpu",)).as_text())] == [
        ("blocked_attention_fwd", 3), ("blocked_attention_bwd", 6)]
    assert not re.search(r"tensor<(\d+x)*2048x2048x", text)  # no [2L, 2L] value of any type
