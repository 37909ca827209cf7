"""The index-score kernels (ops/index_scores.py) under the Pallas interpreter on the CPU:
the scores and the three cotangents against ``pattern.index_scores`` and ``jax.vjp`` of it,
a group of query rows against more keys than rows among the shapes; dead ReLUs and a head
weight of zero; the tiles past the diagonal; which shapes the kernels take; and the three
kernels as they are lowered for the TPU, by name, operands and results, each under the
scope the benchmark's reader looks for."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.models import pattern
from tpu_resiliency.ops import index_scores

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TILE, HEADS, DI = 128, 3, 64

#: norm gaps (|got - want| / |want|) against ``pattern.index_scores`` on the same operands.
#: Read on these very cases under the interpreter (PR 38): float32 operands 3.2e-8 to
#: 3.4e-8 for the scores and 0.5e-7 to 2.9e-7 for the cotangents (the heads are summed in
#: another order); bf16 operands the same for the scores and ``dwi`` (a bf16 product is
#: exact in float32 on both sides) and 0.00253-0.00256 for ``dqi`` and ``dki``, whose
#: cotangent of the products goes into the MXU in the operands' type here, as on a TPU,
#: and in float32 in XLA's CPU program
F32_GAP = 2e-6
BF16_GRAD_GAP = 0.004

#: (query rows, keys): a whole sequence of two and of three tiles, and a group of two
#: tiles and of one against three tiles of keys
SHAPES = [(256, 256), (384, 384), (256, 384), (128, 384)]


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(index_scores, "TILE", TILE)


def operands(queries: int, keys: int, dtype, batch: int = 2):
    rng = jax.random.split(jax.random.PRNGKey(queries + keys), 4)
    qi = jax.random.normal(rng[0], (batch, queries, HEADS, DI)).astype(dtype)
    wi = jax.random.normal(rng[1], (batch, queries, HEADS))
    ki = jax.random.normal(rng[2], (batch, keys, DI)).astype(dtype)
    g = jax.random.normal(rng[3], (batch, queries, keys))
    return qi, wi, ki, g


def seen(queries: int, keys: int):
    """``[queries, keys]`` bool: the key tiles up to each query tile's diagonal tile, the
    queries being the last of the keys' positions."""
    rows = (keys - queries + jnp.arange(queries)) // TILE
    return (jnp.arange(keys) // TILE)[None, :] <= rows[:, None]


def gap(got, want) -> float:
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("queries,keys", SHAPES)
def test_kernels_equal_the_jax_numpy_scores_and_their_vjp(queries, keys, dtype):
    qi, wi, ki, g = operands(queries, keys, dtype)
    visited = seen(queries, keys)
    want, want_vjp = jax.vjp(
        lambda *a: jnp.where(visited, pattern.index_scores(*a), 0.0), qi, wi, ki)
    got, got_vjp = jax.vjp(index_scores.index_scores, qi, wi, ki)
    assert got.shape == (2, queries, keys) and got.dtype == jnp.float32
    grads, want_grads = got_vjp(g), want_vjp(g)
    for a, b in zip(grads, (qi, wi, ki)):
        assert a.shape == b.shape and a.dtype == b.dtype
    product = F32_GAP if dtype == jnp.float32 else BF16_GRAD_GAP
    gaps = [gap(got, want)] + [gap(a, b) for a, b in zip(grads, want_grads)]
    assert all(x < limit for x, limit in zip(gaps, (F32_GAP, product, F32_GAP, product))), gaps


@pytest.mark.parametrize("queries,keys", SHAPES)
def test_tiles_past_the_diagonal_are_zeros_and_pass_no_gradient(queries, keys):
    qi, wi, ki, g = operands(queries, keys, jnp.bfloat16)
    visited = seen(queries, keys)
    got, vjp = jax.vjp(index_scores.index_scores, qi, wi, ki)
    assert float(jnp.abs(jnp.where(visited, 0.0, got)).max()) == 0.0
    # the diagonal tile is computed whole: its scores of keys after the query are there
    later = visited & (jnp.arange(keys)[None, :] > keys - queries + jnp.arange(queries)[:, None])
    assert float(jnp.abs(jnp.where(later, got, 0.0)).max()) > 0.0
    for a, b in zip(vjp(g), vjp(jnp.where(visited, g, 0.0))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_dead_relus_and_a_head_weight_of_zero_give_exact_zeros(dtype):
    """Query row 5 has only negative products (positive keys, a negative query in every
    head): its scores, ``dqi`` and ``dwi`` are exactly 0; head 1 weighs nothing: its
    ``dqi`` is exactly 0 everywhere, and it adds nothing to the scores or to ``dki``."""
    queries, keys = 256, 384
    qi, wi, ki, g = operands(queries, keys, dtype, batch=1)
    ki = jnp.abs(ki)
    qi = qi.at[:, 5].set(-jnp.abs(qi[:, 5]))
    wi = wi.at[:, :, 1].set(0.0)
    got, vjp = jax.vjp(index_scores.index_scores, qi, wi, ki)
    dqi, dwi, dki = vjp(g)
    assert float(jnp.abs(got[:, 5]).max()) == 0.0
    assert float(jnp.abs(dqi[:, 5].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(dwi[:, 5]).max()) == 0.0
    assert float(jnp.abs(dqi[:, :, 1].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(dwi[:, :, 1]).max()) > 0.0  # the weight's own gradient is alive
    without = [jnp.delete(x, 1, axis=2) for x in (qi, wi)]
    want, want_vjp = jax.vjp(index_scores.index_scores, *without, ki)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dki, np.float32),
                                  np.asarray(want_vjp(g)[2], np.float32))


@pytest.mark.parametrize("queries,keys,heads,head_dim,takes", [
    (2048, 8192, 16, 64, True),  # a group of the keye cell
    (2048, 2048, 16, 64, True),
    (8192, 8192, 16, 128, True),
    (256, 768, 4, 64, True),  # under one tile of queries: the tile is all of them
    (2048, 8192, 16, 8, False),  # the tests' tiny heads
    (2048, 8192, 16, 96, False),
    (2048 + 128, 8192, 16, 64, False),  # no whole tiles of queries
    (2048, 8192 + 128, 16, 64, False),  # no whole tiles of keys
    (48, 96, 16, 64, False),  # a tile of no whole lane groups
    (4096, 2048, 16, 64, False),  # more queries than keys
    (2048, 8192, 32, 64, False),  # all heads' products of a tile over what a kernel may hold
])
def test_which_shapes_the_kernels_take(monkeypatch, queries, keys, heads, head_dim, takes):
    monkeypatch.setattr(index_scores, "TILE", 512)
    assert index_scores.applies(queries, keys, heads, head_dim) == takes


@pytest.mark.parametrize("what", ["head_dim", "rows", "weights", "keys"])
def test_shapes_that_do_not_tile_are_refused(what):
    qi, wi, ki, _ = operands(256, 384, jnp.float32)
    if what == "head_dim":
        qi, ki = qi[..., :8], ki[..., :8]
    elif what == "rows":
        qi, wi = qi[:, :200], wi[:, :200]
    elif what == "weights":
        wi = wi[:, :, :2]
    else:
        ki = ki[:, :200]
    with pytest.raises(ValueError, match="does not tile"):
        index_scores.index_scores(qi, wi, ki)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The kernels ask ``jax.default_backend()`` whether to run under the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def custom_calls(lowered_text: str) -> list:
    """(kernel name, operand types, result types) of each Mosaic kernel of a program
    lowered for the TPU, in the program's order."""
    calls = []
    for line in lowered_text.splitlines():
        if "stablehlo.custom_call @tpu_custom_call" not in line:
            continue
        name = re.search(r'kernel_name = "(\w+)"', line).group(1)
        operands, results = re.search(
            r"\}\s*:\s*\((.*)\)\s*->\s*(.*?)(?:\s+loc\(.*)?$", line).groups()
        calls.append((name, re.findall(r"tensor<([^>]*)>", operands),
                      re.findall(r"tensor<([^>]*)>", results)))
    return calls


#: a group of the keye cell (2,048 query rows against 8,192 keys, 16 heads of 64, bf16):
#: the queries and the head weights heads first, ``dqi`` and ``dwi`` the same way
PINNED = {
    "index_scores_fwd": (["1x16x2048x64xbf16", "1x16x2048xf32", "1x8192x64xbf16"],
                         ["1x2048x8192xf32"]),
    "index_scores_dq": (["1x16x2048x64xbf16", "1x16x2048xf32", "1x8192x64xbf16",
                         "1x2048x8192xf32"], ["1x16x2048x64xbf16", "1x16x2048xf32"]),
    "index_scores_dk": (["1x16x2048x64xbf16", "1x16x2048xf32", "1x8192x64xbf16",
                         "1x2048x8192xf32"], ["1x8192x64xbf16"]),
}


@pytest.mark.parametrize("kernel", sorted(PINNED))
def test_the_three_kernels_by_name_operands_and_results(kernel, monkeypatch, as_on_a_tpu):
    """Lowered for the TPU at the keye cell's shapes (nothing compiles or runs): the
    forward and the two backward kernels are custom calls by these names, which the
    benchmark's ``attn.indexer_roofline`` finds them by, with these operands (the three
    residuals, and the cotangent ``g`` as it comes) and float32 scores."""
    monkeypatch.setattr(index_scores, "TILE", 512)
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, 2048, 16, 64), jnp.bfloat16), ((1, 2048, 16), jnp.float32),
        ((1, 8192, 64), jnp.bfloat16), ((1, 2048, 8192), jnp.float32))]

    def both(qi, wi, ki, g):
        out, vjp = jax.vjp(index_scores.index_scores, qi, wi, ki)
        return out, vjp(g)

    text = jax.jit(both).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    calls = {name: (operands, results) for name, operands, results in custom_calls(text)}
    assert sorted(calls) == sorted(PINNED)
    assert calls[kernel] == PINNED[kernel]


@pytest.mark.parametrize("kept,forwards", [(("index",), 2), (("index", "scores"), 1)],
                         ids=["operands", "operands+scores"])
def test_every_score_kernel_of_a_layer_carries_the_indexer_scope(kept, forwards, as_on_a_tpu):
    """One indexed layer lowered for the TPU through its ``jax.checkpoint`` with the
    indexer's operands kept, four groups of one tile: each group's forward kernel twice
    where the scores are not kept (the backward pass makes them again for the divergence's
    own backward) and once where they are, its ``dqi`` and its ``dki`` kernel once, every
    one under ``attn/full/indexer`` where ``attn.indexer_ms`` looks, and none of the
    blocks' loops."""
    from benchmark import harness

    mark = harness.load_by_path("layer_metrics", "attn.indexer_ms").SCOPES["indexer"]
    cfg = pattern.PatternConfig.tiny_indexed(
        indexer=pattern.Indexer(n_heads=4, head_dim=DI, top_k=96), attn_block=TILE)
    seq = 4 * TILE
    assert pattern.attention_paths(cfg, seq)["indexed"] == {
        "path": "blocks", "block": TILE, "selected": 96, "selection": "mask", "scores": "kernel"}
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: jax.ShapeDtypeStruct(w.shape[1:], w.dtype),
                      params["attn"]["indexed"])
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), cfg.dtype)
    tables = (pattern.rope_tables(cfg.rope_indexed, cfg.head_dim, seq)
              + pattern.rope_tables(cfg.rope_indexed, cfg.indexer.head_dim, seq))
    policy = jax.checkpoint_policies.save_only_these_names(
        *(name for group in kept for name in pattern.KEPT_GROUPS[group]))

    def loss(x, lp):
        layer = jax.checkpoint(lambda x, lp: pattern._indexed_block(cfg, x, lp, *tables)[:2],
                               policy=policy)
        out, counts = layer(x, lp)
        return jnp.sum(out.astype(jnp.float32) ** 2) + counts["index_kl"]

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(x, lp).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [names[ref] for ref in re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert sorted(name.rsplit("/", 2)[-2] for name in kernels) == [
        *["index_scores_dk"] * 4, *["index_scores_dq"] * 4,
        *["index_scores_fwd"] * 4 * forwards], kernels
    assert all(mark.search(name) for name in kernels), kernels
    assert sum("rematted_computation" in name for name in kernels) == 4 * (forwards - 1)
    under = [name for name in names.values() if mark.search(name)]
    assert not any("while" in name for name in under), under
