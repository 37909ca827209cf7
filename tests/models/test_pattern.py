"""Pattern-of-layers model (models/pattern.py), in the four descriptions the benchmark
runs (window and full grouped-query layers with a gate; latent layers under a router
with a selection bias; indexed layers, whose keys an indexer selects and whose indexer
learns from a loss of its own, under a softmax router with no shared expert; a period of
one full layer with no rotary and three delta-rule layers that carry a state, a share of
the heads held): the program
against the benchmark's plain references on loss and every gradient leaf; each attention
kind against plain masked attention, the latent layer against attention written the long
way, the indexed layer against its reference's and its selection against a sort; the
experts' shares against the uncut layer; a bias that changes the choice and never a
weight; routing that drops nothing;
what the layers keep for the backward pass against keeping nothing, and the list by
tokens and memory; derived parameter specs on a mesh; the state through the local
checkpoint; the scopes the benchmark's readers look for in the lowered step. The delta
rule by chunks against the recurrence token by token and the heads' shares against the
uncut sublayers are in ``test_pattern_delta.py``."""

import collections
import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from tpu_resiliency.models import pattern
from tpu_resiliency.parallel import mesh as pmesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEQ = 37  # longer than the window (8), a multiple of neither block (8, 16)


#: the four descriptions: the configuration file whose family and reference go with
#: each, and the model's own tiny preset of it
DESCRIPTIONS = {"mixed": ("laguna-xs2-l5-ep8", pattern.PatternConfig.tiny),
                "latent": ("kimi-vl-a3b-l6-ep8", pattern.PatternConfig.tiny_latent),
                "indexed": ("keye-vl2-30b-a3b-l6-ep8", pattern.PatternConfig.tiny_indexed),
                "delta": ("solar-open2-250b-l4-ep40-tp8", pattern.PatternConfig.tiny_delta)}
#: the leaves of an indexed layer that only the indexer's own loss reaches
INDEXER_LEAVES = ("wq_index", "wk_index", "ww_index", "k_index_norm")


@functools.cache
def tiny_file_of(description: str):
    """A configuration at its family's tiny widths, as a configuration dict, with the
    family and the reference that go with it."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{DESCRIPTIONS[description][0]}.json")
    family = harness.load_family(config)
    config = {**config, **family.TINY}
    return config, family, harness.load_reference(config)


@pytest.fixture(scope="module")
def tiny_file():
    return tiny_file_of("mixed")


@functools.cache
def exact_of(description: str):
    """Program (float32 activations) and reference on the same seeded weights: value
    and gradient of the loss on one batch."""
    config, family, reference = tiny_file_of(description)
    cfg = dataclasses.replace(family.program_config(config, SEQ), dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, (2, SEQ)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        params = pattern.init_params(jax.random.PRNGKey(5), cfg)
        ref_params = reference.init_params(5, config)
        got = jax.jit(jax.value_and_grad(lambda p: pattern.loss_fn(p, tokens, cfg)))(params)
        # the reference on its own choices (family ``keye`` hands it the bfloat16
        # program's): in float32 on both sides they are the same
        want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, tokens, {**config, "choices": None}, "f32")))(ref_params)
    return params, ref_params, got, want


def leaf_paths() -> list[tuple[str, str]]:
    """(description, leaf path) of every parameter leaf of both tiny presets."""
    out = []
    for description, (_, preset) in DESCRIPTIONS.items():
        leaves = jax.tree_util.tree_flatten_with_path(
            pattern.describe_params(preset()), is_leaf=lambda x: isinstance(x, pattern.Leaf))[0]
        out += [(description, jax.tree_util.keystr(path)) for path, _ in leaves]
    return out


@pytest.mark.parametrize("description", list(DESCRIPTIONS))
def test_seeded_weights_and_loss_equal_the_reference(description):
    """The weights are bit-equal (one PRNG key a leaf in flatten order on both sides);
    the float32 losses differ by summation order alone: 1e-5 of a loss near 6."""
    params, ref_params, (loss, _), (ref_loss, _) = exact_of(description)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert abs(float(loss) - float(ref_loss)) < 1e-5


@pytest.mark.parametrize("description,path", leaf_paths())
def test_gradient_leaf_equals_the_reference(description, path):
    """Every element within 2e-5 of the leaf's largest (float32 under ``highest`` on both
    sides, other summation orders: the gaps read 2e-7 to 1.2e-6 of it). The selection
    bias enters a top-k and nothing else: the loss sends it nothing, and its gradient is
    the balancing rule's +-1 an expert, the same signs on both sides."""
    _, _, (_, grads), (_, ref_grads) = exact_of(description)
    got = {jax.tree_util.keystr(p): g for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    want = {jax.tree_util.keystr(p): g
            for p, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0]}
    scale = float(jnp.max(jnp.abs(want[path])))
    if path.endswith("['b_router']"):
        assert set(np.unique(np.asarray(want[path]))) == {-1.0, 1.0}
        np.testing.assert_array_equal(np.asarray(got[path]), np.asarray(want[path]))
        return
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got[path]), np.asarray(want[path]),
                               rtol=0, atol=2e-5 * max(scale, 1e-2))


def plain_attention(q, k, v, window=None):
    """Masked softmax attention with the whole T x T array."""
    b, t, h, dh = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allowed = j <= i if window is None else (j <= i) & (j > i - window)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * dh)


@pytest.mark.parametrize("kind,seq", [
    ("sliding", 37), ("sliding", 8), ("sliding", 5), ("full", 37), ("full", 16), ("full", 7)])
def test_attention_kind_equals_plain_masked_attention(kind, seq):
    """Value and gradient, at lengths over, at and under a block."""
    keys = jax.random.split(jax.random.PRNGKey(seq), 3)
    q = jax.random.normal(keys[0], (2, seq, 6, 16))
    k, v = (jax.random.normal(key, (2, seq, 2, 16)) for key in keys[1:])
    if kind == "sliding":
        blocked = lambda q, k, v: pattern.sliding_attention(q, k, v, 8)  # noqa: E731
        plain = lambda q, k, v: plain_attention(q, k, v, 8)  # noqa: E731
    else:
        blocked = lambda q, k, v: pattern.full_attention(q, k, v, 16)  # noqa: E731
        plain = plain_attention
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, 6 * 16))
    with jax.default_matmul_precision("highest"):
        got, got_grad = jax.value_and_grad(
            lambda *a: jnp.sum(blocked(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        want, want_grad = jax.value_and_grad(
            lambda *a: jnp.sum(plain(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(blocked(q, k, v)), np.asarray(plain(q, k, v)),
                                   atol=2e-5)
    assert abs(float(got) - float(want)) < 1e-3
    for a, b in zip(got_grad, want_grad):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def latent_layer(cfg, seed=0, seq=SEQ):
    """One latent layer's weights (float32), its input and its rotary table."""
    lp = jax.tree.map(lambda w: w[0], pattern.init_params(
        jax.random.PRNGKey(seed), cfg)["attn"][pattern.LATENT])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, seq, cfg.d_model))
    return lp, x, pattern.rope_tables(cfg.rope_latent, cfg.latent.d_rope, seq)


def latent_the_long_way(cfg, x, lp):
    """Latent attention with nothing shared and nothing blocked: every head gets keys of
    the whole score width (its own non-rotary part beside a copy of the rotary key), the
    rotation is written out per pair of dimensions, and the softmax is over the whole
    masked T x T array."""
    b, t, d = x.shape
    h, la = cfg.heads(pattern.LATENT), cfg.latent
    norm = lambda z, w, eps: z / jnp.sqrt(jnp.mean(z * z, -1, keepdims=True) + eps) * w  # noqa: E731
    y = norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (y @ lp["wq"]).reshape(b, t, h, la.d_score)
    down = y @ lp["wkv_a"]
    up = (norm(down[..., :la.kv_rank], lp["kv_norm"], la.norm_eps) @ lp["wkv_b"]).reshape(
        b, t, h, la.d_nope + la.d_value)

    def turn(z):  # [B, T, n, d_rope]: dimension i with dimension i + d_rope / 2
        half = la.d_rope // 2
        angle = jnp.arange(t)[:, None] / cfg.rope_latent.theta ** (jnp.arange(half) / half)[None]
        cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
        return jnp.concatenate([z[..., :half] * cos - z[..., half:] * sin,
                                z[..., half:] * cos + z[..., :half] * sin], -1)

    k_rope = jnp.repeat(turn(down[..., la.kv_rank:][:, :, None]), h, axis=2)
    q = jnp.concatenate([q[..., :la.d_nope], turn(q[..., la.d_nope:])], -1)
    k = jnp.concatenate([up[..., :la.d_nope], k_rope], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(la.d_score)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), up[..., la.d_nope:])
    return x + out.reshape(b, t, h * la.d_value) @ lp["wo"]


@pytest.mark.parametrize("seq", [37, 16, 7])
def test_latent_layer_equals_attention_written_the_long_way(seq):
    """Value and the gradient to the input and to each of the six leaves, at lengths
    over, at and under a query block; float32 under ``highest``: 5e-5 absolute."""
    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32)
    lp, x, tables = latent_layer(cfg, seq=seq)
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        got, got_grad = jax.value_and_grad(lambda x, lp: jnp.sum(
            pattern._latent_block(cfg, x, lp, *tables) * weight), argnums=(0, 1))(x, lp)
        want, want_grad = jax.value_and_grad(lambda x, lp: jnp.sum(
            latent_the_long_way(cfg, x, lp) * weight), argnums=(0, 1))(x, lp)
        np.testing.assert_allclose(np.asarray(pattern._latent_block(cfg, x, lp, *tables)),
                                   np.asarray(latent_the_long_way(cfg, x, lp)), atol=2e-5)
    assert abs(float(got) - float(want)) < 1e-3
    assert set(got_grad[1]) == {"attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def indexed_layer(cfg, seed=0, seq=SEQ):
    """One indexed layer's weights (float32), its input and its two rotary tables."""
    lp = jax.tree.map(lambda w: w[0], pattern.init_params(
        jax.random.PRNGKey(seed), cfg)["attn"][pattern.INDEXED])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, seq, cfg.d_model))
    tables = (pattern.rope_tables(cfg.rope_indexed, cfg.head_dim, seq)
              + pattern.rope_tables(cfg.rope_indexed, cfg.indexer.head_dim, seq))
    return lp, x, tables


@pytest.mark.parametrize("seq", [37, 16, 7])
def test_indexed_layer_equals_the_references_layer(seq):
    """The stream after attention and the indexer's own loss, and the gradient of each by
    the input and by each of the eleven leaves, against the reference's layer (index
    scores of every key, a sort, a mask, a softmax over the masked row) on seeded weights,
    at lengths over, at and under a query block and over and under the 12 keys kept. The
    indexer's four leaves get their gradient from its loss alone, and no other leaf does;
    the selection passes nothing."""
    config, _, reference = tiny_file_of("indexed")
    cfg = pattern.PatternConfig.tiny_indexed(dtype=jnp.float32)
    lp, x, tables = indexed_layer(cfg, seq=seq)
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def program(x, lp):
        out, counts, _ = pattern._indexed_block(cfg, x, lp, *tables)
        return jnp.sum(out * weight), counts["index_kl"]

    def plain(x, lp):
        added, divergence = reference.attention(x, lp, config, "f32")[:2]
        return jnp.sum((x + added) * weight), divergence

    with jax.default_matmul_precision("highest"):
        for term in (0, 1):
            got, got_grad = jax.jit(jax.value_and_grad(
                lambda x, lp: program(x, lp)[term], argnums=(0, 1)))(x, lp)  # noqa: B023
            want, want_grad = jax.jit(jax.value_and_grad(
                lambda x, lp: plain(x, lp)[term], argnums=(0, 1)))(x, lp)  # noqa: B023
            assert abs(float(got) - float(want)) < (1e-3 if term == 0 else 1e-6)
            for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
            reached = {name for name, g in got_grad[1].items() if float(jnp.max(jnp.abs(g))) > 0}
            assert reached == (set(INDEXER_LEAVES) if term else set(lp) - set(INDEXER_LEAVES))
            assert bool(jnp.any(got_grad[0] != 0)) == (term == 0)  # the indexer's input is detached


def selection_by_sort(scores: np.ndarray, start: int, top_k: int) -> np.ndarray:
    """Each row's ``top_k`` keys ``s <= t`` by (score descending, position ascending)."""
    mask = np.zeros(scores.shape, bool)
    for index in np.ndindex(scores.shape[:-1]):
        t = start + index[-1]
        row = scores[index][:t + 1]
        order = np.lexsort((np.arange(t + 1), -row))
        mask[index][order[:top_k]] = True
    return mask


@pytest.mark.parametrize("top_k", [5, 12, 37, 50])
@pytest.mark.parametrize("ties", ["none", "many", "zeros"])
def test_selection_equals_a_sorts_ties_included(top_k, ties):
    """:func:`select_keys` against a sort of every row, at a ``top_k`` smaller than, equal
    to and larger than the sequence of 37: continuous scores; scores of five values, so
    that nearly every row's ``top_k``-th is tied with its neighbours; and scores that are
    zero of either sign in two places of three (a ReLU's). Blocks of 16 rows against
    their causal prefix, as the layer runs them, and the rows whose ``top_k``-th and next
    scores are equal are counted as such."""
    rng = np.random.default_rng(top_k)
    scores = rng.normal(size=(2, 48, 48)).astype(np.float32)
    if ties == "many":
        scores = np.round(scores)
    if ties == "zeros":
        scores = np.where(rng.random(scores.shape) < 2 / 3,
                          np.where(rng.random(scores.shape) < 0.5, -0.0, 0.0), scores).astype(np.float32)
    for start in (0, 16, 32):
        end = start + 16
        block = scores[:, start:end, :end]
        mask, tied = jax.jit(lambda a: pattern.select_keys(a, start, top_k))(block)  # noqa: B023
        want = selection_by_sort(block, start, top_k)
        np.testing.assert_array_equal(np.asarray(mask), want)
        for index in np.ndindex(block.shape[:-1]):
            t = start + index[-1]
            row = np.sort(block[index][:t + 1])[::-1]
            assert bool(tied[index]) == (t + 1 > top_k and row[top_k - 1] == row[top_k]), index
    if ties == "many" and top_k < 37:
        assert int(jnp.sum(tied)) > 0


@pytest.mark.parametrize("description,terms", [("indexed", "lm"), ("indexed", "index")])
def test_the_two_losses_meet_on_no_leaf(description, terms):
    """The whole model's loss is the cross-entropy plus the mean of the layers'
    ``index_kl``: with the indexer's loss taken out, the indexer's four leaves get a
    gradient of exactly zero and every other leaf its whole gradient; the indexer's loss
    alone reaches those four and nothing else."""
    cfg = DESCRIPTIONS[description][1](dtype=jnp.float32)
    params = pattern.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab_size, (2, SEQ)), jnp.int32)

    def parts(p):
        loss, counts = pattern.loss_and_counts(p, tokens, cfg)
        own = jnp.mean(counts["index_kl"])
        return {"lm": loss - own, "index": own, "both": loss}

    grads = {name: jax.jit(jax.grad(lambda p: parts(p)[name]))(params)  # noqa: B023
             for name in (terms, "both")}
    flat = lambda tree: {jax.tree_util.keystr(path): g for path, g in  # noqa: E731
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    part, both = flat(grads[terms]), flat(grads["both"])
    for path, g in part.items():
        indexer = any(f"['{leaf}']" in path for leaf in INDEXER_LEAVES)
        if indexer == (terms == "index"):
            assert float(jnp.max(jnp.abs(g))) > 0, path
            np.testing.assert_allclose(np.asarray(g), np.asarray(both[path]), rtol=1e-5, atol=1e-9)
        else:
            assert float(jnp.max(jnp.abs(g))) == 0.0, path


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_shares_of_an_indexed_layer_add_up_to_the_uncut_reference_layer(shares):
    """A whole layer of the third description: attention under the indexer's selection,
    which every chip computes alike, counted once, plus the routed parts of all the shares
    (16 experts over 1, 2, 4 and 8 chips; no shared expert to count once), equal the
    reference's layer with every expert held; the router is a softmax over all 16."""
    config, _, reference = tiny_file_of("indexed")
    experts = 16
    cfg = pattern.PatternConfig.tiny_indexed(dtype=jnp.float32,
                                             experts_held=(0, experts // shares))
    whole = dataclasses.replace(cfg, experts_held=(0, experts))
    params = pattern.init_params(jax.random.PRNGKey(4), whole)
    lp = jax.tree.map(lambda w: w[0], params["mlp"][pattern.SPARSE])
    assert not any(name.startswith("ws_") for name in lp)
    attn_lp, x, tables = indexed_layer(whole, seed=4)
    held = experts // shares
    uncut = {**config, "num_experts": experts, "num_local_experts": experts,
             "deployment": {"num_experts": experts, "experts_held": [0, experts]}}
    with jax.default_matmul_precision("highest"):
        x1, *_ = pattern._indexed_block(cfg, x, attn_lp, *tables)
        y = pattern.tfm.rms_norm(x1, lp["mlp_norm"], cfg.norm_eps)
        total = x1
        for s in range(shares):
            part = dataclasses.replace(cfg, experts_held=(s * held, held))
            routed, counts, balance = pattern.routed_experts(
                part, y.reshape(-1, cfg.d_model), share_of(lp, s * held, held))
            total = total + routed.reshape(x.shape)
            assert int(counts["dropped"]) == 0 and balance is None
        x1_ref = x + reference.attention(x, attn_lp, uncut, "f32")[0]
        want = x1_ref + reference.sparse_mlp(
            reference.rms_norm(x1_ref, lp["mlp_norm"], cfg.norm_eps), lp, uncut, "f32")[0]
        np.testing.assert_allclose(
            np.asarray(pattern._mlp_block(whole, pattern.SPARSE, x1, lp)[0]), np.asarray(want),
            atol=5e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


def test_softmax_routing_is_the_renormalised_top_of_a_softmax_over_all_experts():
    cfg = pattern.PatternConfig.tiny_indexed(dtype=jnp.float32)
    lp, y = sparse_layer(cfg, seed=6)
    weights, experts, by_bias, balance = pattern.route(cfg, y, lp["w_router"])
    probs = np.asarray(jax.nn.softmax(jnp.matmul(y, lp["w_router"], precision="highest"), -1))
    want = np.argsort(-probs, axis=-1)[:, :cfg.top_k]
    assert by_bias is None and balance is None
    np.testing.assert_array_equal(np.asarray(experts), want)
    chosen = np.take_along_axis(probs, want, -1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
    sigmoid = pattern.route(dataclasses.replace(cfg, route_score=pattern.SIGMOID), y, lp["w_router"])
    assert not np.allclose(np.asarray(sigmoid[0]), np.asarray(weights))


def sparse_layer(cfg, seed=0):
    """One sparse layer's weights over ALL experts (float32), and normed tokens."""
    whole = dataclasses.replace(cfg, experts_held=(0, cfg.n_experts))
    lp = jax.tree.map(lambda w: w[0], pattern.init_params(
        jax.random.PRNGKey(seed), whole)["mlp"][pattern.SPARSE])
    y = jax.random.normal(jax.random.PRNGKey(seed + 1), (64, cfg.d_model))
    return lp, y


def share_of(lp: dict, first: int, count: int) -> dict:
    return {k: (w[first:first + count] if k.startswith("we_") else w) for k, w in lp.items()}


@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_shares_add_up_to_the_uncut_layer(tiny_file, shares):
    """The routed parts that all chips of a deployment compute (16 experts over
    ``shares`` chips), plus the shared expert once, equal the uncut reference layer."""
    config, _, reference = tiny_file
    cfg = pattern.PatternConfig.tiny(dtype=jnp.float32)
    lp, y = sparse_layer(cfg)
    held = cfg.n_experts // shares
    with jax.default_matmul_precision("highest"):
        total = pattern._swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        for s in range(shares):
            part = dataclasses.replace(cfg, experts_held=(s * held, held))
            routed, counts, _ = pattern.routed_experts(part, y, share_of(lp, s * held, held))
            total = total + routed
            assert int(counts["dropped"]) == 0
        uncut = {**config, "num_experts": cfg.n_experts,
                 "deployment": {"num_experts": cfg.n_experts, "experts_held": [0, cfg.n_experts]}}
        want = reference.sparse_mlp(y, lp, uncut, "f32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("experts,shares", [(64, 8), (16, 4), (16, 1)])
def test_shares_of_a_latent_layer_add_up_to_the_uncut_reference_layer(experts, shares):
    """A whole layer of the second description: attention and the shared experts, which
    every chip computes alike, counted once, plus the routed parts of all the shares
    (64 experts over eight chips, as the configuration states its deployment), equal the
    reference's layer with every expert held."""
    config, _, reference = tiny_file_of("latent")
    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32, n_experts=experts,
                                            experts_held=(0, experts // shares))
    whole = dataclasses.replace(cfg, experts_held=(0, experts))
    params = pattern.init_params(jax.random.PRNGKey(4), whole)
    attn_lp = jax.tree.map(lambda w: w[1], params["attn"][pattern.LATENT])
    lp = jax.tree.map(lambda w: w[0], params["mlp"][pattern.SPARSE])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.d_model))
    tables = pattern.rope_tables(cfg.rope_latent, cfg.latent.d_rope, SEQ)
    held = experts // shares
    uncut = {**config, "n_routed_experts": experts,
             "deployment": {"n_routed_experts": experts, "experts_held": [0, experts]}}
    with jax.default_matmul_precision("highest"):
        x1 = pattern._latent_block(cfg, x, attn_lp, *tables)
        y = pattern.tfm.rms_norm(x1, lp["mlp_norm"], cfg.norm_eps)
        total = x1 + pattern._swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        for s in range(shares):
            part = dataclasses.replace(cfg, experts_held=(s * held, held))
            routed, counts, _ = pattern.routed_experts(
                part, y.reshape(-1, cfg.d_model), share_of(lp, s * held, held))
            total = total + routed.reshape(x.shape)
            assert int(counts["dropped"]) == 0
        x1_ref = x + reference.attention(x, attn_lp, uncut, "f32")
        want = x1_ref + reference.sparse_mlp(
            reference.rms_norm(x1_ref, lp["mlp_norm"], cfg.norm_eps), lp, uncut, "f32")[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


def scores_of(y, w_router):
    return jax.nn.sigmoid(jnp.matmul(y, w_router, precision="highest"))


@pytest.mark.parametrize("std", [0.05, 0.1, 0.5])
def test_a_bias_changes_the_choice_and_never_a_weight(std):
    """The experts are the top-k of score + bias; the weights are the chosen experts'
    scores, normalised and scaled, with no trace of the bias; ``chosen_by_bias`` counts
    the pairs the scores alone would not have chosen."""
    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32)
    lp, y = sparse_layer(cfg, seed=6)
    bias = std * jax.random.normal(jax.random.PRNGKey(7), (cfg.n_experts,))
    weights, experts, by_bias, _ = pattern.route(  # the leaf is in units of 1 / gain
        cfg, y, lp["w_router"], bias / cfg.route_bias_gain)
    _, plain, none, _ = pattern.route(cfg, y, lp["w_router"])
    scores = np.asarray(scores_of(y, lp["w_router"]))
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :cfg.top_k]
    assert none is None
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1), np.sort(want, -1))
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(-1, keepdims=True)
                               * cfg.routed_scale, rtol=1e-6)
    differ = sum(len(set(a) - set(b)) for a, b in zip(np.asarray(experts), np.asarray(plain)))
    assert differ > 0 and int(by_bias) == differ


@pytest.mark.parametrize("std", [0.0, 0.1, 0.5])
def test_the_balancing_rule_is_a_term_of_no_value_whose_gradient_is_each_experts_sign(std):
    """``balance`` is zero whatever the bias; its gradient by the bias is +1 for every
    expert that more than the even share of the ``N x top_k`` choices fell on and -1 for
    every other, so a step of any optimizer that follows the sign lowers the crowded
    experts' bias and raises the rest: the loss-free rule."""
    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32)
    lp, y = sparse_layer(cfg, seed=6)
    bias = std / cfg.route_bias_gain * jax.random.normal(jax.random.PRNGKey(7), (cfg.n_experts,))
    term = lambda b: pattern.route(cfg, y, lp["w_router"], b)[3]  # noqa: E731
    value, grad = jax.value_and_grad(term)(bias)
    experts = np.asarray(pattern.route(cfg, y, lp["w_router"], bias)[1])
    load = np.bincount(experts.ravel(), minlength=cfg.n_experts)
    assert float(value) == 0.0 and load.sum() == y.shape[0] * cfg.top_k
    np.testing.assert_array_equal(
        np.asarray(grad), np.where(load > load.sum() / cfg.n_experts, 1.0, -1.0))
    assert pattern.route(cfg, y, lp["w_router"])[3] is None


@pytest.mark.parametrize("gain", [1.0, 100.0])
def test_adamw_applies_the_balancing_rule_and_the_load_evens_out(gain):
    """Trained on one batch with the router's weights held (only the bias moves, by
    ``lr x gain`` a step), the largest load of an expert falls towards the even share."""
    import optax

    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32, route_bias_gain=gain)
    lp, y = sparse_layer(cfg, seed=6)
    even = y.shape[0] * cfg.top_k / cfg.n_experts
    load = lambda b: np.bincount(np.asarray(  # noqa: E731
        pattern.route(cfg, y, lp["w_router"], b)[1]).ravel(), minlength=cfg.n_experts)
    bias = lp["b_router"]
    optimizer = optax.adamw(0.01 / gain, weight_decay=0.01)
    state = optimizer.init(bias)
    first = load(bias).max()
    for _ in range(60):
        grad = jax.grad(lambda b: pattern.route(cfg, y, lp["w_router"], b)[3])(bias)
        updates, state = optimizer.update(grad, state, bias)
        bias = optax.apply_updates(bias, updates)
    assert first > 2 * even and load(bias).max() < 1.3 * even, (first, load(bias).max(), even)


def test_a_zero_bias_is_the_plain_router():
    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32)
    lp, y = sparse_layer(cfg, seed=6)
    weights, experts, by_bias, _ = pattern.route(cfg, y, lp["w_router"], jnp.zeros(cfg.n_experts))
    plain_weights, plain_experts, _, _ = pattern.route(cfg, y, lp["w_router"])
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(plain_experts))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(plain_weights))
    assert int(by_bias) == 0
    part = share_of({**lp, "b_router": jnp.zeros(cfg.n_experts)}, *cfg.experts_held)
    without = {k: w for k, w in part.items() if k != "b_router"}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(np.asarray(pattern.routed_experts(cfg, y, part)[0]),
                                      np.asarray(pattern.routed_experts(cfg, y, without)[0]))


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4)])
def test_no_pair_is_dropped_under_a_bias_that_sends_everything_here(held):
    """A bias of +10 on the four experts held (top-k is four): all ``N x top_k`` pairs
    land here, over any bound, so the layer takes the full width; nothing is dropped, and
    the result is the dense weighted sum with weights that are the scores' own."""
    cfg = pattern.PatternConfig.tiny_latent(dtype=jnp.float32, experts_held=held)
    lp, y = sparse_layer(cfg, seed=8)
    lp["b_router"] = jnp.zeros(cfg.n_experts).at[held[0]:held[0] + held[1]].set(10.0)
    part = share_of(lp, *held)
    n = y.shape[0]
    with jax.default_matmul_precision("highest"):
        routed, counts, _ = jax.jit(lambda y, part: pattern.routed_experts(cfg, y, part))(y, part)
        scores = scores_of(y, lp["w_router"])[:, held[0]:held[0] + held[1]]
        gates = scores / jnp.sum(scores, -1, keepdims=True) * cfg.routed_scale
        want = sum(gates[:, e:e + 1] * pattern._swiglu(
            y, part["we_gate"][e], part["we_up"][e], part["we_down"][e]) for e in range(held[1]))
    assert int(counts["pairs_held"]) == int(counts["rows_carried"]) == n * cfg.top_k
    assert int(counts["dropped"]) == 0 and int(counts["max_load"]) == n
    assert pattern.dispatch_rows(cfg, n)["rows"] < n * cfg.top_k  # the bound was passed
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_no_pair_is_dropped_under_a_biased_router(held):
    """A router biased so that one held expert takes every token's first choice (half
    of all tokens and more): every pair that lands here is computed, the counts say
    so, and the result equals the dense weighted sum over the held experts."""
    cfg = pattern.PatternConfig.tiny(dtype=jnp.float32, experts_held=held)
    lp, y = sparse_layer(cfg, seed=3)
    y = jnp.abs(y)  # so that a positive router column wins for every token
    hot = held[0] + 1
    lp["w_router"] = lp["w_router"].at[:, hot].set(1.0)
    part = share_of(lp, *held)
    with jax.default_matmul_precision("highest"):
        routed, counts, _ = pattern.routed_experts(cfg, y, part)
        weights, experts, _, _ = pattern.route(cfg, y, lp["w_router"])
        want = jnp.zeros_like(y)
        for e in range(held[1]):
            gate = jnp.sum(jnp.where(experts == held[0] + e, weights, 0.0), -1, keepdims=True)
            want = want + gate * pattern._swiglu(
                y, part["we_gate"][e], part["we_up"][e], part["we_down"][e])
    n = y.shape[0]
    landed = int(jnp.sum((experts >= held[0]) & (experts < held[0] + held[1])))
    assert int(counts["max_load"]) == n  # the hot expert got every token
    assert int(counts["pairs_held"]) == landed and int(counts["dropped"]) == 0
    assert float(counts["mean_load"]) == pytest.approx(landed / held[1])
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want), atol=2e-5)


def biased(cfg, seed, always=(), never=()):
    """A sparse layer's share of the weights and positive tokens, the router biased so
    that every token chooses the experts ``always`` and none chooses ``never``; and
    the dense weighted sum over the held experts, which the routed part must equal."""
    lp, y = sparse_layer(cfg, seed=seed)
    y = jnp.abs(y)  # so that the sign of a router column decides for every token
    for experts, value in ((always, 1.0), (never, -1.0)):
        for e in experts:
            lp["w_router"] = lp["w_router"].at[:, e].set(value)
    first, held = cfg.experts_held
    part = share_of(lp, first, held)
    weights, experts, _, _ = pattern.route(cfg, y, lp["w_router"])
    want = jnp.zeros_like(y)
    for e in range(held):
        gate = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1, keepdims=True)
        want = want + gate * pattern._swiglu(
            y, part["we_gate"][e], part["we_up"][e], part["we_down"][e])
    return part, y, want


def equations(jaxpr):
    """Every equation of a jaxpr and of every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def primitives(jaxpr) -> set[str]:
    """Names of the primitives of a jaxpr and of every jaxpr inside it."""
    return {eqn.primitive.name for eqn in equations(jaxpr)}


@pytest.fixture(scope="module")
def both_widths():
    """Value and gradients of one routed layer (64 tokens, 256 pairs, 4 of 16 experts
    held: 128 rows carried) through the bounded dispatch and, with the static choice
    overridden, through the full width alone."""
    cfg = pattern.PatternConfig.tiny(dtype=jnp.float32)
    lp, y = sparse_layer(cfg, seed=11)
    part = share_of(lp, *cfg.experts_held)
    cot = jax.random.normal(jax.random.PRNGKey(12), y.shape)

    def value_and_grads():
        def f(y, part):
            routed, counts, _ = jax.checkpoint(
                lambda y, part: pattern.routed_experts(cfg, y, part))(y, part)
            return jnp.sum(routed * cot), (routed, counts)
        with jax.default_matmul_precision("highest"):
            (_, (routed, counts)), (dy, dpart) = jax.value_and_grad(
                f, argnums=(0, 1), has_aux=True)(y, part)
        return {"output": routed, "y": dy, **dpart}, counts

    bounded, counts = value_and_grads()
    assert int(counts["rows_carried"]) == 128 >= int(counts["pairs_held"])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pattern, "dispatch_rows", lambda cfg, n: {
            "path": "full", "rows": n * cfg.top_k, "pairs": n * cfg.top_k})
        full, counts = value_and_grads()
    assert int(counts["rows_carried"]) == 256
    return bounded, full


@pytest.mark.parametrize("what", ["output", "y", "w_router", "we_gate", "we_up", "we_down"])
def test_bounded_dispatch_equals_the_full_width(both_widths, what):
    """The output, and the gradient to the tokens, the router and each expert leaf."""
    bounded, full = both_widths
    scale = float(jnp.max(jnp.abs(full[what])))
    assert scale > 0
    np.testing.assert_allclose(np.asarray(bounded[what]), np.asarray(full[what]),
                               rtol=0, atol=2e-5 * max(scale, 1e-2))


@pytest.mark.parametrize("held,always,never,carried", [
    # three held experts take every token: 192 pairs over the 128 carried -> full width
    ((0, 4), (0, 1, 2), (), 256),
    ((4, 4), (5, 6, 7), (), 256),
    ((12, 4), (12, 14, 15), (), 256),
    # two take every token and two none: exactly the 128 carried -> bounded
    ((0, 4), (1, 2), (0, 3), 128),
    ((12, 4), (12, 15), (13, 14), 128),
    # one takes every token, the others what falls to them -> bounded
    ((4, 4), (5,), (), 128),
])
def test_a_router_over_its_bound_takes_the_full_width_and_drops_nothing(
        held, always, never, carried):
    cfg = pattern.PatternConfig.tiny(dtype=jnp.float32, experts_held=held)
    with jax.default_matmul_precision("highest"):
        part, y, want = biased(cfg, 3, always, never)
        routed, counts, _ = jax.jit(lambda y, part: pattern.routed_experts(cfg, y, part))(y, part)
    n = y.shape[0]
    assert int(counts["rows_carried"]) == carried
    assert int(counts["dropped"]) == 0
    if never:
        assert int(counts["pairs_held"]) == len(always) * n == carried
    else:
        assert (int(counts["pairs_held"]) > 128) == (carried == 256)
    assert int(counts["max_load"]) == n
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("held,tokens,path,rows", [
    ((0, 4), 64, "bounded", 128),     # twice the share of 64: 128 of 256
    ((0, 1), 64, "bounded", 128),     # twice the share is 32: rounded up to the row tile
    ((0, 4), 1000, "bounded", 2048),  # 2,000 rounded up
    ((0, 1), 1000, "bounded", 512),   # twice the share is 500, under the tokens
    ((0, 4), 32, "full", 128),        # the row tile is all 128 pairs
    ((0, 8), 64, "full", 256),        # twice a half share
    ((0, 16), 64, "full", 256),       # every expert held
])
def test_dispatch_rows_is_what_the_layer_carries_and_full_width_has_no_cond(
        held, tokens, path, rows):
    cfg = pattern.PatternConfig.tiny(dtype=jnp.float32, experts_held=held)
    assert pattern.dispatch_rows(cfg, tokens) == {
        "path": path, "rows": rows, "pairs": tokens * cfg.top_k}
    lp, _ = sparse_layer(cfg)
    part = share_of(lp, *held)
    y = jax.random.normal(jax.random.PRNGKey(2), (tokens, cfg.d_model))
    layer = lambda y, part: pattern.routed_experts(cfg, y, part)  # noqa: E731
    assert ("cond" in primitives(jax.make_jaxpr(layer)(y, part).jaxpr)) == (path == "bounded")
    counts = jax.jit(layer)(y, part)[1]
    assert int(counts["pairs_held"]) <= rows  # this router stays under twice its share
    assert int(counts["rows_carried"]) == rows and int(counts["dropped"]) == 0


#: the tiny indexed description at shapes the kernels tile: heads of 128, an indexer of 4
#: heads of 64, blocks and tiles of 128 rows, one sequence of 512 (four groups of one tile)
KERNELS = "indexed-kernels"
KEPT_CASES = [*DESCRIPTIONS, KERNELS]


def passes(grad, params) -> collections.Counter:
    """The matrix products in a differentiated program, and its kernel calls by name."""
    count = collections.Counter()
    for eqn in equations(jax.make_jaxpr(grad)(params).jaxpr):
        if eqn.primitive.name == "dot_general":
            count["products"] += 1
        elif eqn.primitive.name == "pallas_call":
            count[eqn.params["name"]] += 1
    return count


@functools.cache
def kept_and_not(description: str):
    """Loss, routing counts and gradient of one batch (float32 activations) with the
    list ``kept_residuals`` gives where no memory limit is stated, and with nothing kept
    (a device too small for any group): ``{kept: (value, gradient, the differentiated
    program's :func:`passes`)}``; for an indexed description also the passes with every
    group kept but the indexer's target, and but its scores (``"target"``, ``"scores"``).
    ``KERNELS`` sends the attention and the index scores to the kernels whatever
    ``attention_paths`` says of the CPU (they then run under the interpreter)."""
    from tpu_resiliency.ops import attention, index_scores

    if description == KERNELS:
        cfg = pattern.PatternConfig.tiny_indexed(
            dtype=jnp.float32, head_dim=128, attn_block=128,
            indexer=pattern.Indexer(n_heads=4, head_dim=64, top_k=96))
        shape = (1, 512)
    else:
        cfg, shape = DESCRIPTIONS[description][1](dtype=jnp.float32), (2, SEQ)
    params = pattern.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, shape), jnp.int32)
    # a function of its own for every list: a trace is cached by the function traced
    grad = lambda: jax.value_and_grad(  # noqa: E731
        lambda p: pattern.loss_and_counts(p, tokens, cfg), has_aux=True)
    paths, rule = pattern.attention_paths, pattern.kept_residuals
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        if description == KERNELS:
            patch.setattr(attention, "FULL_TILE", 128)
            patch.setattr(index_scores, "TILE", 128)
            patch.setattr(pattern, "attention_paths", lambda cfg, seq: {"indexed": {
                **paths(cfg, seq)["indexed"], "path": "kernel", "scores": "kernel"}})
        for kept, memory in ((True, None), (False, 0)):
            patch.setattr(pattern, "device_memory_bytes", lambda: memory)
            assert bool(pattern.kept_residuals(cfg, tokens.size, memory)["names"]) == kept
            out[kept] = (*jax.jit(grad())(params), passes(grad(), params))
        for group in ("target", "scores") if cfg.count(pattern.INDEXED) else ():
            def but_one(*args, group=group):
                kept = rule(*args)
                return {**kept, "names": [name for name in kept["names"]
                                          if name not in pattern.KEPT_GROUPS[group]]}

            patch.setattr(pattern, "device_memory_bytes", lambda: None)
            patch.setattr(pattern, "kept_residuals", but_one)
            out[group] = passes(grad(), params)
    return out


@pytest.mark.parametrize("description,path", leaf_paths() + [
    (KERNELS, path) for description, path in leaf_paths() if description == "indexed"])
def test_gradient_leaf_with_the_kept_list_equals_nothing_kept(description, path):
    both = kept_and_not(description)
    got, want = (dict(jax.tree_util.tree_flatten_with_path(both[kept][1])[0])
                 for kept in (True, False))
    key = next(k for k in want if jax.tree_util.keystr(k) == path)
    assert float(jnp.linalg.norm(want[key])) > 0
    # a delta layer's scan compiles to other float32 sums where its states are kept than
    # where they are made again: 4e-6 of a leaf's largest element, read
    atol = 2e-5 * float(jnp.max(jnp.abs(want[key]))) if description == "delta" else 1e-9
    np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]), rtol=1e-6, atol=atol)


@pytest.mark.parametrize("description", KEPT_CASES)
def test_the_kept_list_gives_the_same_loss_and_counts_with_fewer_products(description):
    both = kept_and_not(description)
    (kept, _, kept_passes), (bare, _, bare_passes) = both[True], both[False]
    assert float(kept[0]) == float(bare[0])
    for name, want in bare[1].items():
        np.testing.assert_array_equal(np.asarray(kept[1][name]), np.asarray(want), err_msg=name)
    # kept: the forward products of q, k, v, the gate, the output matrix, the router and
    # the gate and up of every SwiGLU outside the dispatch are not made a second time
    assert kept_passes["products"] < bare_passes["products"], (kept_passes, bare_passes)
    if description == "indexed":
        # three layers, three groups of query rows (37 rows in blocks of 16): without the
        # target a group scores its keys once more (``QK^T`` alone: the output is kept),
        # without the scores it makes the indexer's products once more
        assert both["target"] - kept_passes == {"products": 3 * 3}
        assert both["scores"] - kept_passes == {"products": 3 * 3}
    if description == KERNELS:
        # three layers, four groups: every kernel once a layer or a group, the kernel of the
        # summed probabilities and the index scores' forward twice where their value is not
        # kept, and the attention's forward twice where nothing is
        kernels = lambda passes: {k: n for k, n in passes.items() if k != "products"}  # noqa: E731
        once = {**dict.fromkeys(("blocked_attention_fwd", "blocked_attention_probs",
                                 "blocked_attention_bwd"), 3),
                **dict.fromkeys(("index_scores_fwd", "index_scores_dq", "index_scores_dk"), 12)}
        assert kernels(kept_passes) == once
        assert kernels(both["target"]) == {**once, "blocked_attention_probs": 6}
        assert kernels(both["scores"]) == {**once, "index_scores_fwd": 24}
        assert kernels(bare_passes) == {**once, "blocked_attention_fwd": 6,
                                        "blocked_attention_probs": 6, "index_scores_fwd": 24}


@pytest.mark.parametrize("description", list(DESCRIPTIONS))
def test_the_forward_names_what_the_groups_list(description):
    cfg = DESCRIPTIONS[description][1]()
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    jaxpr = jax.make_jaxpr(lambda p, t: pattern.loss_fn(p, t, cfg))(
        params, jax.ShapeDtypeStruct((2, SEQ), jnp.int32)).jaxpr
    named = {eqn.params["name"] for eqn in equations(jaxpr) if eqn.primitive.name == "name"}
    listed = set(pattern.kept_residuals(cfg, 2 * SEQ, None, SEQ)["names"])
    assert named == listed - {"attn_lse"}  # the blocks make no log-sum-exp
    indexer_makes = {"select_mask", "index_q", "index_w", "index_k", "index_target", "index_scores"}
    if description == "indexed":  # no shared expert, no dense layer
        assert listed == set(ALL_NAMES) - DELTA_NAMES - {
            "shared_gate", "shared_up", "dense_gate", "dense_up"}
    elif description == "delta":  # no dense layer; its full layer names q, k, v and the output
        assert listed == set(ALL_NAMES) - indexer_makes - {"dense_gate", "dense_up"}
    else:  # every group but those that only an indexer or a delta layer makes
        assert listed == set(ALL_NAMES) - indexer_makes - DELTA_NAMES


def cell_config(description: str, seq: int) -> pattern.PatternConfig:
    """The program's configuration of a benchmark cell, at its published widths."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{DESCRIPTIONS[description][0]}.json")
    return harness.load_family(config).program_config(config, seq)


ALL_NAMES = [name for names in pattern.KEPT_GROUPS.values() for name in names]
#: what only a delta layer names
DELTA_NAMES = {*pattern.KEPT_GROUPS["states"], *pattern.KEPT_GROUPS["delta"]}
V5E_BYTES = 16.9e9  # one v5e's ``bytes_limit``
#: float32 a (query, key) of the keye cell's four groups of 2,048 query rows against their keys
INDEX_ROWS_BYTES = 4 * 2048 * (2048 + 4096 + 6144 + 8192)
#: what the keye cell's six layers keep in the six groups before the indexer's target
INDEXED_SIX_BYTES = 6 * (37_748_736 + 5_242_880 + 33_554_432 + 67_108_864 + 83_886_080
                         + 18_350_080)


@pytest.mark.parametrize("description,tokens,memory,groups,kept_bytes", [
    # the two cells: every group, 1.93e9 and 2.05e9 B beside 12.6e9 and 12.8e9 of step
    ("mixed", 8192, V5E_BYTES, 6, 1_926_234_112),
    ("latent", 8192, V5E_BYTES, 6, 2_052_849_664),
    # the third: its eight groups (no shared expert, no dense layer), 3.49e9 B beside 12.6e9:
    # a layer keeps 38 MB of mask (three groups of 2,048 rows against 4,096, 6,144 and 8,192
    # keys; the first 2,048 rows keep every key), 5 of routing, 34 of stream, 67 of output,
    # 84 of q, k, v and 18 of the indexer's q, k and weights, and then, float32 a (query,
    # key) of the four groups' rows against their keys, 168 of the indexer's target and 168
    # of its scores
    ("indexed", 8192, V5E_BYTES, 8, INDEXED_SIX_BYTES + 6 * 2 * INDEX_ROWS_BYTES),
    # a chip's memory 1e9 B smaller: the target, and no room for the scores behind it
    ("indexed", 8192, V5E_BYTES - 1e9, 7, INDEXED_SIX_BYTES + 6 * INDEX_ROWS_BYTES),
    ("indexed", 8192, V5E_BYTES - 2e9, 6, INDEXED_SIX_BYTES),  # 2e9 B smaller: neither
    ("indexed", 8192, 12.9e9, 2, 6 * (37_748_736 + 5_242_880)),  # routing, then the selection
    # one sequence of 16,384: four groups of 4,096 rows, all of which select; the output no
    # longer fits, with one layer's target and scores (0.67e9 B each) in what the step holds
    ("indexed", 16384, V5E_BYTES, 3, 6 * (10_485_760 + 4096 * (4096 + 8192 + 12288 + 16384)
                                          + 67_108_864)),
    ("mixed", 8192, None, 6, 1_926_234_112),  # no limit stated (the CPU): as the chip
    # twice the tokens on the same chip: q, k, v and the SwiGLUs' products no longer fit
    # (laguna compiles to 15.08e9 B so, and to 16.89e9 of the chip's 16.91e9 with all kept)
    ("mixed", 16384, V5E_BYTES, 3, 1_637_875_712),
    ("latent", 16384, V5E_BYTES, 3, 834_142_208),
    # four times: the step's own state and logits are over the chip before anything is kept
    ("mixed", 32768, V5E_BYTES, 0, 0),
    ("latent", 32768, V5E_BYTES, 0, 0),
    ("mixed", 32768, 32e9, 6, 4 * 1_926_234_112),  # and all of it on a chip twice the size
    # a device that holds the step and the routing group's 38 MB, and one that holds neither
    ("mixed", 8192, 12.64e9, 1, 37_748_736),
    ("mixed", 8192, 12.0e9, 0, 0),
    ("latent", 8192, 1e9, 0, 0),
    # the fourth: 13.45e9 B of weights, moments and gradients, 1.6e9 of logits and 0.3e9 of
    # the rule's float32 values leave room for six groups of its seven: a layer keeps 12 MB
    # of routing and 67 of stream, the full layer 17 of output and log-sum-exp and 21 of q, k
    # and v (one KV head), a delta layer 67 of states (128 chunks x 8 heads x 128 x 128
    # float32), 17 of the rule's output and 84 of q, k, v, log-decays and write strengths;
    # the shared expert's products (42 MB a layer) do not fit
    ("delta", 8192, V5E_BYTES, 6, 4 * (11_534_336 + 67_108_864) + 17_039_360 + 20_971_520
                                  + 3 * (67_108_864 + 16_777_216 + 8192 * 8 * (2 * 384 + 512 + 4))),
    ("delta", 8192, 32e9, 7, 4 * (11_534_336 + 67_108_864 + 41_943_040) + 17_039_360 + 20_971_520
                             + 3 * (67_108_864 + 16_777_216 + 8192 * 8 * (2 * 384 + 512 + 4))),
    ("delta", 8192, 16.0e9, 1, 4 * 11_534_336),
    ("delta", 8192, 15.9e9, 0, 0),
])
def test_kept_residuals_by_tokens_and_memory(description, tokens, memory, groups, kept_bytes):
    cfg = cell_config(description, tokens)
    kept = pattern.kept_residuals(cfg, tokens, memory)
    # the groups that hold anything in this description, in the order of KEPT_GROUPS
    indexer = {"selection", "index", "target", "scores"}  # what only an indexed layer holds
    holds = {"mixed": set(pattern.KEPT_GROUPS) - indexer - {"states", "delta"},
             "latent": set(pattern.KEPT_GROUPS) - indexer - {"states", "delta"},
             "indexed": set(pattern.KEPT_GROUPS) - {"shared", "dense", "states", "delta"},
             "delta": set(pattern.KEPT_GROUPS) - indexer - {"dense"}}[description]
    taken = [(g, names) for g, names in pattern.KEPT_GROUPS.items() if g in holds][:groups]
    assert kept["names"] == [name for _, names in taken for name in names]
    assert list(kept["per_layer"]) == [group for group, _ in taken]
    assert all(len(layers) == len(cfg.layers) for layers in kept["per_layer"].values())
    assert kept["bytes"] == sum(map(sum, kept["per_layer"].values())) == kept_bytes
    if memory is not None and groups:
        assert kept["step_bytes"] + kept["bytes"] <= memory
    # what the step holds anyway: at least its weights, moments and gradients
    n_params = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(
        pattern.describe_params(cfg), is_leaf=pattern._is_leaf))
    assert kept["step_bytes"] > 16 * n_params


@pytest.mark.parametrize("backend,lse", [("cpu", 0), ("tpu", 4)])
def test_the_indexed_kind_keeps_a_log_sum_exp_on_the_kernel_path_alone(monkeypatch, backend, lse):
    """The "attention" group of ``keye-vl2-30b-a3b-l6-ep8`` at 8,192 tokens: 32 heads' output
    in bf16 on the ``jax.numpy`` blocks (the CPU), and four bytes a head and token more
    where ``attention_paths`` sends the kind to the kernels, which make a log-sum-exp (a
    TPU); the selection is a byte a (query, key) of the three groups that select, on both;
    the indexer's target and scores, float32, are the last two groups of the list."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = cell_config("indexed", 8192)
    assert pattern.attention_paths(cfg, 8192)["indexed"]["path"] == (
        "kernel" if lse else "blocks")
    kept = pattern.kept_residuals(cfg, 8192, V5E_BYTES)
    assert kept["per_layer"]["attention"] == [8192 * 32 * (128 * 2 + lse)] * 6
    assert kept["per_layer"]["selection"] == [2048 * (4096 + 6144 + 8192)] * 6
    assert "attn_lse" in kept["names"]  # the group's names are the same on both
    # the indexer's target and its scores are the groups' rows against their keys on both
    # paths, and fit at the chip's memory after the six groups before them
    assert kept["per_layer"]["target"] == kept["per_layer"]["scores"] == [INDEX_ROWS_BYTES] * 6
    assert list(kept["per_layer"])[-3:] == ["index", "target", "scores"]
    assert kept["names"][-2:] == ["index_target", "index_scores"]


def test_kept_residuals_of_the_laguna_cell_layer_by_layer():
    """The bytes of each group in each layer of ``laguna-xs2-l5-ep8`` at 8,192 tokens:
    a full layer (48 heads) keeps 102 MB of output and log-sum-exp and 134 MB of q, k
    and v, a sliding one (64 heads) 136 and 168; a sparse layer 9 MB of routing and 17 MB
    of the shared expert's products, the dense layer 268 MB of its own."""
    kept = pattern.kept_residuals(cell_config("mixed", 8192), 8192, V5E_BYTES)
    assert kept["names"] == [name for name in ALL_NAMES if "select" not in name
                             and "index" not in name and name not in DELTA_NAMES]
    assert kept["per_layer"] == {
        "routing": [0] + [8192 * 4 * (256 + 4 * 8)] * 4,
        "stream": [8192 * 2048 * 2] * 5,
        "attention": [8192 * 48 * (128 * 2 + 4)] + [8192 * 64 * (128 * 2 + 4)] * 3
                     + [8192 * 48 * (128 * 2 + 4)],
        "qkv": [8192 * 2 * 128 * (48 + 16)] + [8192 * 2 * 128 * (64 + 16)] * 3
               + [8192 * 2 * 128 * (48 + 16)],
        "shared": [0] + [2 * 8192 * 512 * 2] * 4,
        "dense": [2 * 8192 * 8192 * 2] + [0] * 4,
    }


@pytest.mark.parametrize("description", list(DESCRIPTIONS))
@pytest.mark.parametrize("axes", [{"dp": 4, "ep": 2}, {"dp": 2, "ep": 2, "tp": 2}])
def test_derived_specs_on_a_mesh_give_the_one_chip_loss(axes, description):
    # float32 activations: in bf16 another reduction order flips near-tied router choices
    cfg = DESCRIPTIONS[description][1](dtype=jnp.float32)
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    loss = jax.jit(lambda p, t: pattern.loss_fn(p, t, cfg))
    want = float(loss(params, tokens))
    mesh = pmesh.build_mesh(devices=jax.devices()[:8], **axes)
    specs = pmesh.pattern_param_specs(cfg)
    assert specs["mlp"]["sparse"]["we_gate"] == jax.sharding.PartitionSpec(
        None, "ep", None, "tp")
    assert specs["mlp"]["sparse"]["w_router"] == jax.sharding.PartitionSpec(None, None, None)
    if description == "latent":  # what all heads share replicates; a head's columns go together
        latent = specs["attn"]["latent"]
        assert latent["wkv_a"] == jax.sharding.PartitionSpec(None, None, None)
        assert latent["kv_norm"] == jax.sharding.PartitionSpec(None, None)
        assert latent["wq"] == latent["wkv_b"] == jax.sharding.PartitionSpec(None, None, "tp")
        assert latent["wo"] == jax.sharding.PartitionSpec(None, "tp", None)
        assert specs["mlp"]["sparse"]["b_router"] == jax.sharding.PartitionSpec(None, None)
    if description == "indexed":  # the indexer and the head norms replicate, like the router
        indexed = specs["attn"]["indexed"]
        for name in (*INDEXER_LEAVES, "q_norm", "k_norm"):
            assert all(axis is None for axis in indexed[name]), name
        assert indexed["wq"] == indexed["wk"] == jax.sharding.PartitionSpec(None, None, "tp")
        assert "ws_gate" not in specs["mlp"]["sparse"] and "dense" not in specs["mlp"]
    if description == "delta":  # what is split by heads goes by heads; the low-rank downs replicate
        delta = specs["attn"]["delta"]
        for name in ("wq", "wk", "wv", "wf_b", "wg_b", "wb"):
            assert delta[name] == jax.sharding.PartitionSpec(None, None, "tp"), name
        for name in ("conv_q", "conv_k", "conv_v", "wo"):
            assert delta[name] == jax.sharding.PartitionSpec(None, "tp", None), name
        assert delta["a_log"] == delta["dt_bias"] == jax.sharding.PartitionSpec(None, "tp")
        for name in ("wf_a", "wg_a", "o_norm", "attn_norm"):
            assert all(axis is None for axis in delta[name]), name
    sharded = jax.device_put(params, pmesh.tree_shardings(mesh, specs))
    assert len(sharded["mlp"]["sparse"]["we_up"].sharding.device_set) == 8
    with mesh:
        got = float(loss(sharded, jax.device_put(
            tokens, NamedSharding(mesh, pmesh.batch_spec()))))
    assert abs(got - want) < 1e-4


def test_forward_counts_and_causality():
    cfg = pattern.PatternConfig.tiny()
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    t1 = jax.random.randint(jax.random.PRNGKey(2), (1, SEQ), 0, cfg.vocab_size)
    t2 = t1.at[0, 30].set((t1[0, 30] + 1) % cfg.vocab_size)
    forward = jax.jit(lambda p, t: pattern.forward(p, t, cfg))
    l1, counts = forward(params, t1)
    l2, _ = forward(params, t2)
    assert l1.shape == (1, SEQ, cfg.vocab_size) and l1.dtype == jnp.float32
    assert counts["pairs_held"].shape == (cfg.count(pattern.SPARSE),)
    assert int(counts["dropped"].sum()) == 0
    assert int(counts["pairs_held"].max()) <= SEQ * cfg.top_k
    np.testing.assert_allclose(np.asarray(l1[0, :30]), np.asarray(l2[0, :30]), atol=2e-2)
    assert not np.allclose(np.asarray(l1[0, 30:]), np.asarray(l2[0, 30:]), atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_index_scores_from_the_kernels_give_what_the_blocks_give(monkeypatch, dtype):
    """The tiny indexed description with an indexer the kernels' layout takes (4 heads of
    64, blocks of 128 rows, four groups of one tile): with the scores from
    ``ops/index_scores.py`` the layers keep the same number of keys to the digit, and
    ``select_ties``, ``index_kl``, the loss and every gradient leaf are the blocks' to
    rounding (inside the tiny limits of the keye family by far: a bf16 product is exact in
    float32 either way, so the scores differ by the order of a float32 sum alone)."""
    from tpu_resiliency.ops import index_scores

    monkeypatch.setattr(index_scores, "TILE", 128)
    _, family, _ = tiny_file_of("indexed")
    cfg = pattern.PatternConfig.tiny_indexed(
        dtype=dtype, indexer=pattern.Indexer(n_heads=4, head_dim=64, top_k=96), attn_block=128)
    seq = 512
    assert pattern.attention_paths(cfg, seq)["indexed"]["scores"] == "blocks"
    params = pattern.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (1, seq)), jnp.int32)

    def run():
        value = lambda p: pattern.loss_and_counts(p, tokens, cfg)  # noqa: E731
        (loss, counts), grads = jax.jit(jax.value_and_grad(value, has_aux=True))(params)
        return float(loss), counts, grads

    want = run()
    # ``attention_paths`` asks the backend, which is the CPU here: send the scores to the
    # kernels whatever it says (they then run under the interpreter)
    paths = pattern.attention_paths
    monkeypatch.setattr(pattern, "attention_paths", lambda cfg, seq: {
        "indexed": {**paths(cfg, seq)["indexed"], "scores": "kernel"}})
    got = run()
    np.testing.assert_array_equal(np.asarray(got[1]["keys_selected"]),
                                  np.asarray(want[1]["keys_selected"]))
    assert int(got[1]["keys_selected"][0]) == sum(min(t + 1, 96) for t in range(seq))
    np.testing.assert_array_equal(np.asarray(got[1]["select_ties"]),
                                  np.asarray(want[1]["select_ties"]))
    rounding = 1e-5 if dtype == jnp.float32 else 4e-3
    np.testing.assert_allclose(np.asarray(got[1]["index_kl"]), np.asarray(want[1]["index_kl"]),
                               rtol=rounding)
    assert abs(got[0] - want[0]) < rounding < family.TINY["limits"]["loss_abs"]
    gaps = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
                        got[2], want[2])
    limit = 1e-4 if dtype == jnp.float32 else family.TINY["limits"]["grad_norm_gap"] / 4
    assert max(jax.tree.leaves(gaps)) < limit, gaps


@pytest.mark.parametrize("backend,indexer,block,seq,scores", [
    ("cpu", pattern.Indexer(16, 64, 2048), 512, 8192, "blocks"),
    ("tpu", pattern.Indexer(16, 64, 2048), 512, 8192, "kernel"),
    ("tpu", pattern.Indexer(4, 8, 12), 512, 8192, "blocks"),  # the tests' tiny heads
    ("tpu", pattern.Indexer(16, 64, 2048), 512, 8192 + 256, "blocks"),  # no whole blocks of rows
    ("tpu", pattern.Indexer(16, 64, 2048), 512, 384, "kernel"),  # one tile of all 384 rows
    ("tpu", pattern.Indexer(16, 64, 2048), 768, 3072, "blocks"),  # groups of 768 rows: 1.5 tiles
])
def test_attention_paths_say_which_way_the_index_scores_go(
        monkeypatch, backend, indexer, block, seq, scores):
    """``scores: "kernel"`` where the backend is a TPU, every group of query rows is whole
    tiles and the indexer's heads fit the kernels' layout, else ``"blocks"``; the products'
    own path is chosen apart (heads of 16 keep them on the blocks here)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = pattern.PatternConfig.tiny_indexed(indexer=indexer, attn_block=block)
    path = pattern.attention_paths(cfg, seq)["indexed"]
    assert path["scores"] == scores and path["path"] == "blocks"


def test_the_attention_path_event_says_which_way_the_index_scores_go(tmp_path):
    """``examples/pattern_training.py`` records ``attention_path`` before its first step:
    the indexed kind carries ``scores``, on the CPU ``"blocks"``."""
    import json
    import subprocess

    events_file = tmp_path / "events.jsonl"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "pattern_training.py"), "--cpu",
         "--description", "indexed", "--steps", "10", "--batch", "2", "32"],
        env={**os.environ, "TPU_RESILIENCY_EVENTS_FILE": str(events_file)},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    records = [json.loads(line) for line in events_file.read_text().splitlines()]
    (event,) = [r for r in records if r["kind"] == "attention_path"]
    assert event["seq"] == 32
    assert event["indexed"] == {"path": "blocks", "block": 16, "selected": 12,
                                "selection": "mask", "scores": "blocks"}


def test_indexed_counts_are_one_value_a_layer_and_the_keys_selected_are_what_the_shapes_fix():
    cfg = pattern.PatternConfig.tiny_indexed()
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, SEQ), 0, cfg.vocab_size)
    loss, counts = jax.jit(lambda p, t: pattern.loss_and_counts(p, t, cfg))(params, tokens)
    layers = cfg.count(pattern.INDEXED)
    for name in ("index_kl", "keys_selected", "select_ties", "pairs_held", "dropped"):
        assert counts[name].shape == (layers,), name
    assert "chosen_by_bias" not in counts
    want = 3 * sum(min(t + 1, cfg.indexer.top_k) for t in range(SEQ))
    assert [int(n) for n in counts["keys_selected"]] == [want] * layers
    assert bool(jnp.all(counts["index_kl"] > 0)) and bool(jnp.all(counts["select_ties"] >= 0))
    nll = float(loss) - float(jnp.mean(counts["index_kl"]))
    assert 5.0 < nll < 6.5  # near log(256): the indexer's loss is a term with a value


@pytest.mark.parametrize("description", list(DESCRIPTIONS))
def test_choices_are_what_the_forward_pass_chose(description):
    """``pattern.choices`` says which keys each indexed layer read and which experts each
    sparse layer took: a key of its result only where the pattern has such layers, as many
    keys a query as the counts say, and in float32 the reference's own choices on the
    same weights (which is what lets the reference be compared on the program's)."""
    config, family, reference = tiny_file_of(description)
    cfg = dataclasses.replace(family.program_config(config, SEQ), dtype=jnp.float32)
    params = pattern.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, (2, SEQ)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        chose = jax.jit(lambda p, t: pattern.choices(p, t, cfg))(params, tokens)
        counts = jax.jit(lambda p, t: pattern.forward(p, t, cfg)[1])(params, tokens)
    assert set(chose) == {"experts"} | ({"selected"} if cfg.count(pattern.INDEXED) else set())
    experts = np.asarray(chose["experts"])
    assert experts.shape == (cfg.count(pattern.SPARSE), 2, SEQ, cfg.top_k)
    first, held = cfg.experts_held
    np.testing.assert_array_equal(
        ((experts >= first) & (experts < first + held)).sum(axis=(1, 2, 3)),
        np.asarray(counts["pairs_held"]))
    if description != "indexed":
        return
    selected = np.asarray(chose["selected"])
    assert selected.shape == (cfg.count(pattern.INDEXED), 2, SEQ, SEQ) and selected.dtype == bool
    assert not np.triu(selected, 1).any()  # no key after its query
    np.testing.assert_array_equal(selected.sum(axis=(1, 2, 3)), np.asarray(counts["keys_selected"]))
    with jax.default_matmul_precision("highest"):
        own = jax.jit(lambda p, t: reference.forward(p, t, {**config, "choices": None}, "f32")[2])(
            reference.init_params(5, config), tokens)
    np.testing.assert_array_equal(selected, np.asarray(own["selected"]))
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(np.asarray(own["experts"]), -1))


def test_description_rejects_what_cannot_be_stacked():
    with pytest.raises(ValueError, match="head count"):
        pattern.PatternConfig.tiny(layers=(
            pattern.Layer("full", 6, "dense"), pattern.Layer("full", 4, "sparse")))
    with pytest.raises(ValueError, match="experts_held"):
        pattern.PatternConfig.tiny(experts_held=(14, 4))
    with pytest.raises(ValueError, match="indexer"):
        pattern.PatternConfig.tiny_indexed(indexer=None)
    with pytest.raises(ValueError, match="router score"):
        pattern.PatternConfig.tiny_indexed(route_score="tanh")
    with pytest.raises(ValueError, match="widths of `delta`"):
        pattern.PatternConfig.tiny_delta(delta=None)
    with pytest.raises(ValueError, match="whole KV groups"):  # 8 heads over 4 KV heads, 8 ways
        pattern.PatternConfig.tiny_delta(head_ways=8)
    with pytest.raises(ValueError, match="not held 3 ways"):
        pattern.PatternConfig.tiny_delta(head_ways=3)
    with pytest.raises(ValueError, match="not a count of shares"):
        pattern.PatternConfig.tiny_delta(head_ways=0)
    with pytest.raises(ValueError, match="output gate"):
        pattern.PatternConfig.tiny(gate="row")


@pytest.mark.parametrize("description,leaves", [("mixed", 27), ("latent", 22), ("indexed", 19),
                                                ("delta", 33)])
def test_state_through_the_local_checkpoint_replays_the_next_loss(tmp_path, description, leaves):
    """Per-kind stacks and the held experts' leaves through
    ``checkpoint/local_manager.py``: the restored state gives the next loss exactly."""
    from tpu_resiliency.checkpoint import LocalCheckpointManager, PyTreeStateDict

    cfg = DESCRIPTIONS[description][1]()
    train_step, init_opt = pattern.make_train_step(cfg)
    step = jax.jit(train_step)
    params = pattern.init_params(jax.random.PRNGKey(7), cfg)
    opt_state = init_opt(params)
    batch = lambda i: jnp.asarray(  # noqa: E731
        np.random.default_rng([7, i]).integers(0, cfg.vocab_size, (2, SEQ)), jnp.int32)
    for i in range(2):
        params, opt_state, _ = step(params, opt_state, batch(i))
    mgr = LocalCheckpointManager(str(tmp_path / "ckpt"), rank=0)
    mgr.save(2, PyTreeStateDict({"params": params, "opt": opt_state}), is_async=False)
    _, _, want = step(params, opt_state, batch(2))
    assert mgr.find_latest() == 2
    tree, _ = mgr.load_tree(2)
    assert jax.tree.structure(tree["params"]) == jax.tree.structure(params)
    assert len(jax.tree.leaves(tree["params"])) == leaves
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves({"params": params, "opt": opt_state})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, _, got = step(tree["params"], tree["opt"], batch(2))
    assert float(got) == float(want)
    mgr.close()


@pytest.mark.parametrize("description", ["latent", "indexed", "delta"])
def test_the_lowered_step_carries_the_scopes_the_readers_look_for(description):
    """The second, the third and the fourth description's train step, lowered (nothing
    compiles): its ops' names hold ``attn/full``, ``attn/full/core`` and ``moe/*`` as the
    accepted readers' patterns want them, and ``attn/full/latent``, or
    ``attn/full/indexer`` and ``attn/full/select``, or ``attn/full/delta`` with ``/rule``
    and ``/rule/state``, as the new readers' do; what is under a new scope is under
    ``attn`` too and never under ``core``."""
    from benchmark import harness

    scopes = harness.load_by_path("layer_metrics", "scope_times").SCOPES
    if description == "latent":
        own = {"latent": harness.load_by_path("layer_metrics", "attn.latent_ms").SCOPE}
    elif description == "delta":
        own = harness.load_by_path("layer_metrics", "attn.delta_ms").SCOPES
        assert list(own) == ["delta", "rule", "state"]
    else:
        own = harness.load_by_path("layer_metrics", "attn.indexer_ms").SCOPES
        assert set(own) == {"indexer", "select"}
    cfg = DESCRIPTIONS[description][1]()
    train_step, init_opt = pattern.make_train_step(cfg)
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    text = jax.jit(train_step).trace(
        params, jax.eval_shape(init_opt, params), jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    ).lower().as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    under = {key: {n for n in names if mark.search(n)} for key, mark in {**scopes, **own}.items()}
    if description == "indexed":  # no shared expert: the other moe scopes are all there
        assert not any("moe/shared" in n for n in names)
    assert all(under.values()), {k: len(v) for k, v in under.items()}
    for key in own:
        assert under[key] <= under["attn"] and not under[key] & under["attn_core"], key
    assert under["attn_core"] <= under["attn"] and under["moe_experts"] <= under["moe"]
    for phase in ("jvp(", "transpose("):  # the first forward and the backward alike
        assert any(phase in n for n in under[next(iter(own))]), phase
    assert any(n.endswith("dot_general") for n in under[next(iter(own))])
    if description == "indexed":  # the mask is kept: the selection runs in the first forward alone
        assert all("jvp(" in n and "transpose(" not in n for n in under["select"])
        assert not under["select"] & under["indexer"]
    if description == "delta":  # the scan is inside the rule, the rule inside the layer's own
        assert under["state"] < under["rule"] < under["delta"]
        assert any("/conv/" in n for n in under["delta"] - under["rule"])
        assert any("/gates/" in n for n in under["delta"] - under["rule"])
        for phase in ("jvp(", "transpose("):  # the scan forward, and the walk back over it
            assert any(phase in n and "while" in n for n in under["state"]), phase
