"""The blocked attention kernels (ops/attention.py) under the Pallas interpreter on the
CPU: value and the three gradients against plain masked attention, which path
``models/pattern.py`` takes for which shapes, and that every kernel of a layer's
forward, recomputed forward and backward carries the scope the benchmark's reader
looks for."""

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
def test_attention_paths_of_the_indexed_kind_are_the_blocks_everywhere(monkeypatch, backend):
    """The kernels compute their mask from positions and an indexed layer's is data: at
    heads of 128 and 8,192 tokens, shapes the kernels tile, the kind still takes the
    blocks, on a TPU too, and says how many keys a query keeps."""
    from benchmark import harness

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = harness.read_json(harness.HERE, "configs", "keye-vl2-30b-a3b-l6-ep8.json")
    cfg = harness.load_family(config).program_config(config, 8192)
    assert attention.applies(8192, cfg.head_dim, None)
    assert pattern.attention_paths(cfg, 8192) == {"indexed": {
        "path": "blocks", "block": 512, "selected": 2048, "selection": "mask"}}
    assert pattern.attention_paths(pattern.PatternConfig.tiny_indexed(), 8) == {"indexed": {
        "path": "blocks", "block": 8, "selected": 8, "selection": "mask"}}


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
    kernel names, and the two backward kernels are custom calls whose ``op_name`` the
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
        "blocked_attention_dkv", "blocked_attention_dq",
        *["blocked_attention_fwd"] * forwards], kernels
    assert all(mark.search(name) and f"attn/{kind}" in name for name in kernels), kernels
    assert sum("rematted_computation" in name for name in kernels) == forwards - 1


def test_every_kernel_of_a_latent_layer_carries_the_core_scope_and_none_the_latent_one(
        as_on_a_tpu):
    """As above for latent attention of equal widths (the shapes the kernels take): the
    four kernels are under ``attn/full/core``, and the projections' products under
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
        "blocked_attention_dkv", "blocked_attention_dq",
        "blocked_attention_fwd", "blocked_attention_fwd"], kernels
    assert all(core.search(name) and not latent.search(name) for name in kernels), kernels
    products = [n for n in names.values() if latent.search(n) and n.endswith("dot_general")]
    for pass_ in ("jvp(attn/full)", "rematted_computation/attn/full", "transpose("):
        assert any(pass_ in n for n in products), (pass_, products)
