"""Block-diffusion training through the pattern-of-layers model (models/pattern.py,
``Diffusion``), the fifth description: the program against the benchmark's plain
reference (``benchmark/reference/sdar.py``) on the loss and every gradient leaf in
float32, and over three AdamW steps inside the tiny model's limits, with the bf16
reference inside them and the fp8 reference, a loss with the clean half left out and a
loss with the weights ``1 / t`` dropped outside; the attention by blocks of rows against
plain attention under the whole ``[2L, 2L]`` mask; the draws, which are a function of the
batch: the same in the program and the reference, the same twice, after a save and a
restore, and in a re-entered step; the experts' eight shares against the uncut layer;
what :func:`attention_paths`, :func:`dispatch_rows` and :func:`kept_residuals` say of a
doubled stream; the counters; the scopes the benchmark's readers look for; the example."""

import dataclasses
import functools
import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.models import pattern

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "sdar-30b-a3b-l6-ep8"
SEQ = 40  # ten blocks of 4; under three of the blocks of 16 rows the attention goes by
SEEDS = (11, 2147483659, 4000000007)


@functools.cache
def tiny_file():
    """The configuration at its family's tiny widths, with its family and its reference."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{CONFIG}.json")
    family = harness.load_family(config)
    config = {**config, **family.TINY}
    return config, family, harness.load_reference(config)


@functools.cache
def exact():
    """Program (float32 activations) and reference on the same seeded weights: value and
    gradient of the loss on one batch."""
    config, family, reference = tiny_file()
    cfg = dataclasses.replace(family.program_config(dict(config), SEQ), dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, (2, SEQ)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        params = pattern.init_params(jax.random.PRNGKey(5), cfg)
        ref_params = reference.init_params(5, config)
        got = jax.jit(jax.value_and_grad(lambda p: pattern.loss_fn(p, tokens, cfg)))(params)
        want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, tokens, {**config, "choices": None}, "f32")))(ref_params)
    return params, ref_params, got, want


def leaf_paths() -> list[str]:
    leaves = jax.tree_util.tree_flatten_with_path(
        pattern.describe_params(pattern.PatternConfig.tiny_diffusion()),
        is_leaf=lambda x: isinstance(x, pattern.Leaf))[0]
    return [jax.tree_util.keystr(path) for path, _ in leaves]


def test_the_description_has_head_norms_and_no_gate():
    assert leaf_paths() == [
        "['attn']['full']['attn_norm']", "['attn']['full']['k_norm']",
        "['attn']['full']['q_norm']", "['attn']['full']['wk']", "['attn']['full']['wo']",
        "['attn']['full']['wq']", "['attn']['full']['wv']", "['embed']", "['final_norm']",
        "['lm_head']", "['mlp']['sparse']['mlp_norm']", "['mlp']['sparse']['w_router']",
        "['mlp']['sparse']['we_down']", "['mlp']['sparse']['we_gate']",
        "['mlp']['sparse']['we_up']"]
    # the four descriptions with a gate and no head norms are what they were
    mixed = pattern.describe_params(pattern.PatternConfig.tiny())["attn"]["full"]
    assert sorted(mixed) == ["attn_norm", "wg", "wk", "wo", "wq", "wv"]


def test_seeded_weights_and_loss_equal_the_reference():
    """The weights are bit-equal; the float32 losses, a weighted sum with weights up to a
    thousand, differ by summation order alone."""
    params, ref_params, (loss, _), (ref_loss, _) = exact()
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * float(ref_loss)


@pytest.mark.parametrize("path", leaf_paths())
def test_gradient_leaf_equals_the_reference(path):
    """Every element within 2e-5 of the leaf's largest (float32 under ``highest`` on both
    sides, other summation orders)."""
    _, _, (_, grads), (_, ref_grads) = exact()
    got = {jax.tree_util.keystr(p): g for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    want = {jax.tree_util.keystr(p): g
            for p, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0]}
    scale = float(jnp.max(jnp.abs(want[path])))
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got[path]), np.asarray(want[path]),
                               rtol=0, atol=2e-5 * max(scale, 1e-2))


# -- three AdamW steps, inside and outside the tiny model's limits --------------------

def batches_of(seed: int, config: dict):
    return [np.random.default_rng([seed, i]).integers(
        0, config["vocab_size"], config["batch"]).astype(np.int32) for i in range(3)]


@functools.cache
def followed(seed: int, side: str) -> dict:
    """Three steps of one side from the seed's weights on the seed's batches: the float32
    reference (on the program's experts for ``"program"``'s comparison, else on its own),
    the program as the harness drives it, the reference in a lower precision, or a
    reference whose loss is wrong."""
    from benchmark import harness
    from benchmark.reference import train

    config, family, reference = tiny_file()
    config = dict(config)
    batches = batches_of(seed, config)
    if side == "program":
        cfg = family.program_config(config, config["batch"][1])
        train_step, init_opt = family.make_train_step(cfg, optimizer=config["optimizer"])
        step = jax.jit(train_step)
        seeded = pattern.init_params(jax.random.PRNGKey(seed % (1 << 32)), cfg)
        params, opt_state, out = seeded, init_opt(seeded), {"losses": []}
        for i, batch in enumerate(batches):
            params, opt_state, loss = step(params, opt_state, jnp.asarray(batch))
            out["losses"].append(float(loss))
            if i == 0:
                out["grad_norms"] = {k: v / (1 - train.B1)
                                     for k, v in train.leaf_norms(opt_state[0].mu).items()}
        out["change_norms"] = train.leaf_norms(jax.tree.map(lambda a, b: a - b, params, seeded))
        return out
    if side == "reference_on_the_programs_experts":
        family.program_config(config, config["batch"][1])  # leaves ``choices`` in the dict
        return train.follow(seed % (1 << 32), config, batches, "f32")
    if side in ("f32", "bf16", "fp8"):
        return train.follow(seed % (1 << 32), config, batches, side)

    def wrong_loss(params, tokens, cfg, precision):
        noised, masked, level = reference.draws(tokens, cfg)
        stream = jnp.concatenate([tokens, noised], axis=1)
        mask = None
        if side == "no_clean_half":  # a noised query reads its own block and nothing clean
            length = tokens.shape[1]
            mask = reference.stream_mask(length, cfg["diffusion"]["block_length"])
            mask = mask & ~((jnp.arange(2 * length) >= length)[:, None]
                            & (jnp.arange(2 * length) < length)[None, :])
        else:
            assert side == "no_weights"
            level = jnp.ones_like(level)
        logits = reference.forward(params, stream, cfg, precision, mask=mask)[0]
        return reference.weighted_nll(logits, tokens, masked, level)

    wrong = types.SimpleNamespace(init_params=reference.init_params, loss=wrong_loss)
    real = harness.load_reference
    harness.load_reference = lambda cfg: wrong
    try:
        return train.follow(seed % (1 << 32), config, batches, "f32")
    finally:
        harness.load_reference = real


def compared(seed: int, side: str) -> list[dict]:
    from benchmark import harness

    config = tiny_file()[0]
    against = "reference_on_the_programs_experts" if side == "program" else "f32"
    cell = harness.Cell("tiny", 1, "tiny", config, "", {}, [], [])
    run = harness.Run(cell, seed, 1.0, False, 0.0, rehearsal=True)
    try:
        return harness.compare_with_reference(
            run, followed(seed, side), followed(seed, against), config["limits"])
    finally:
        run.cleanup()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("side", ["program", "bf16"])
def test_three_steps_stay_inside_the_tiny_limits(side, seed):
    """The program (bfloat16 activations, the harness's own comparison: the loss of each
    step, the first gradient's and the parameter change's worst leaf) and the reference in
    the stated precision."""
    rows = compared(seed, side)
    assert len(rows) == 5 and all(row["ok"] for row in rows), rows


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("side", ["fp8", "no_clean_half", "no_weights"])
def test_a_lower_precision_or_a_wrong_loss_is_outside_the_tiny_limits(side, seed):
    """The reference in fp8 fails at least one number; a loss whose noised queries read no
    clean key, or whose masked positions all weigh one, fails the loss itself."""
    rows = compared(seed, side)
    assert not all(row["ok"] for row in rows), rows
    if side != "fp8":
        assert not rows[0]["ok"], rows[0]


# -- the attention by blocks of rows ------------------------------------------------

def plain_noised_attention(q, k, v, block: int, clean: int):
    """Softmax attention under the whole ``[2L, 2L]`` mask, built from ``//`` and
    comparisons."""
    b, t, h, dh = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    position = np.arange(t)
    late, of_block = position >= clean, position % clean // block
    mask = np.where(late[:, None], np.where(late[None, :], of_block[None, :] == of_block[:, None],
                                            of_block[None, :] < of_block[:, None]),
                    ~late[None, :] & (of_block[None, :] <= of_block[:, None]))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * dh)


@pytest.mark.parametrize("block,clean,rows", [(4, 40, 16), (16, 48, 16), (4, 38, 16), (4, 8, 16)])
def test_attention_by_blocks_of_rows_equals_plain_attention_under_the_whole_mask(
        block, clean, rows):
    """Value and the three gradients, at blocks of 4 and of 16, at a sequence that is no
    whole number of blocks or of rows, and at one shorter than a block of rows."""
    keys = jax.random.split(jax.random.PRNGKey(clean), 4)
    q = jax.random.normal(keys[0], (2, 2 * clean, 6, 16))
    k, v = (jax.random.normal(key, (2, 2 * clean, 2, 16)) for key in keys[1:3])
    weight = jax.random.normal(keys[3], (2, 2 * clean, 6 * 16))

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *qkv: jnp.sum(fn(*qkv) * weight), argnums=(0, 1, 2)))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = both(lambda *a: pattern.noised_attention(*a, block, clean, rows))
        want = both(lambda *a: plain_noised_attention(*a, block, clean))
    assert abs(float(got[0]) - float(want[0])) < 1e-3
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_no_clean_position_reads_the_noised_copy_and_no_position_a_later_block():
    """Through the whole model: ids swapped inside a later block (the same sum, so the same
    key and the same draws) leave the logits of every earlier block bit-equal; and the
    experts chosen on the clean half do not depend on the noise seed, which changes every
    noised position, while those of the noised half do."""
    cfg = pattern.PatternConfig.tiny_diffusion()
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 255, (1, SEQ)), jnp.int32)
    swapped = tokens.at[0, 29].set(tokens[0, 30]).at[0, 30].set(tokens[0, 29])
    assert int(tokens[0, 29]) != int(tokens[0, 30])
    forward = jax.jit(lambda t: pattern.forward(params, t, cfg)[0])
    a, b = np.asarray(forward(tokens)), np.asarray(forward(swapped))
    np.testing.assert_array_equal(a[0, :28], b[0, :28])  # blocks 0-6: positions 0-27
    assert not np.array_equal(a[0, 28:], b[0, 28:])
    other = dataclasses.replace(cfg, diffusion=dataclasses.replace(cfg.diffusion, noise_seed=1))
    chose = [np.asarray(jax.jit(lambda t, c=c: pattern.choices(params, t, c))(tokens)["experts"])
             for c in (cfg, other)]
    assert chose[0].shape == (3, 1, 2 * SEQ, cfg.top_k)
    np.testing.assert_array_equal(chose[0][:, :, :SEQ], chose[1][:, :, :SEQ])
    assert not np.array_equal(chose[0][:, :, SEQ:], chose[1][:, :, SEQ:])


# -- the draws -------------------------------------------------------------------------

def test_the_draws_are_the_references_and_a_function_of_the_batch():
    """The program's draws equal the reference's, which writes them again from the
    docstring, to the bit; the same batch gives the same masks twice, inside a compiled
    program and outside; another batch, or another noise seed, gives others; a level a block in [eps,
    1), a masked position wherever its uniform is under it, MASK there and the id
    elsewhere."""
    config, family, reference = tiny_file()
    cfg = family.program_config(dict(config), 64)
    tokens = jnp.asarray(batches_of(SEEDS[1], config)[0])
    got = pattern.draw_noise(tokens, cfg.diffusion)
    xt, masked, level = reference.draws(tokens, config)
    np.testing.assert_array_equal(np.asarray(got.noised), np.asarray(xt))
    np.testing.assert_array_equal(np.asarray(got.masked), np.asarray(masked))
    np.testing.assert_array_equal(np.asarray(got.level), np.asarray(level))
    # inside a compiled program, as the step and the reference's loss draw them: the two
    # sides again to the bit; against the values made op by op the masks are the same and a
    # level is within a rounding (a compiler may contract ``eps + (1 - eps) u`` into one)
    again = jax.jit(lambda t: pattern.draw_noise(t, cfg.diffusion))(tokens)
    for a, b in zip(again, jax.jit(lambda t: reference.draws(t, config))(tokens)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(again.masked), np.asarray(masked))
    np.testing.assert_allclose(np.asarray(again.level), np.asarray(level), rtol=2e-7)
    level = np.asarray(level)
    assert np.all(level >= 1e-3) and np.all(level < 1) and np.all(level[:, ::4] == level[:, 3::4])
    assert len(np.unique(level[0])) == 16 and not np.array_equal(level[0], level[1])
    np.testing.assert_array_equal(
        np.asarray(xt), np.where(np.asarray(masked), 255, np.asarray(tokens)))
    assert 0.3 < float(np.mean(masked)) < 0.7
    other_batch = pattern.draw_noise(tokens.at[0, 0].add(1), cfg.diffusion)
    assert not np.array_equal(np.asarray(other_batch.masked[0]), np.asarray(masked[0]))
    np.testing.assert_array_equal(np.asarray(other_batch.masked[1:]), np.asarray(masked[1:]))
    other_seed = pattern.draw_noise(tokens, dataclasses.replace(cfg.diffusion, noise_seed=1))
    assert not np.array_equal(np.asarray(other_seed.masked), np.asarray(masked))


def test_the_loss_counts_the_draws_masked_positions_and_not_the_mask_id():
    """A batch that is MASK throughout: ``xt == x0`` everywhere, and the loss still
    weighs the drawn positions alone."""
    cfg = pattern.PatternConfig.tiny_diffusion()
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.full((1, SEQ), cfg.diffusion.mask_id, jnp.int32)
    _, counts = jax.jit(lambda t: pattern.loss_and_counts(params, t, cfg))(tokens)
    masked = pattern.draw_noise(tokens, cfg.diffusion).masked
    assert float(counts["masked_share"]) == pytest.approx(float(np.mean(masked)))
    assert 0 < float(np.mean(masked)) < 1


def test_state_through_the_local_checkpoint_replays_the_next_loss_and_its_masks(tmp_path):
    """No generator state lives in the checkpoint: the restored state gives the next loss
    exactly, and so does a re-entered step (the train function made and jitted anew)."""
    from tpu_resiliency.checkpoint import LocalCheckpointManager, PyTreeStateDict

    cfg = pattern.PatternConfig.tiny_diffusion()
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
    tree, _ = mgr.load_tree(2)
    assert len(jax.tree.leaves(tree["params"])) == 15
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves({"params": params, "opt": opt_state})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, _, got = step(tree["params"], tree["opt"], batch(2))
    assert float(got) == float(want)
    reentered = jax.jit(pattern.make_train_step(pattern.PatternConfig.tiny_diffusion())[0])
    _, _, again = reentered(tree["params"], tree["opt"], batch(2))
    assert float(again) == float(want)
    mgr.close()


# -- the share --------------------------------------------------------------------------

@pytest.mark.parametrize("shares", [1, 2, 8])
def test_expert_shares_of_a_layer_add_up_to_the_uncut_reference_layer(shares):
    """A whole layer over the doubled stream: attention under the block-diffusion mask,
    which every chip computes alike, counted once (the router too: one softmax over all
    16), plus the routed parts of all the shares (16 experts over 1, 2 and 8 chips; no
    shared expert), equal the reference's layer with every expert held."""
    config, _, reference = tiny_file()
    experts = 16
    cfg = pattern.PatternConfig.tiny_diffusion(dtype=jnp.float32,
                                               experts_held=(0, experts // shares))
    whole = dataclasses.replace(cfg, experts_held=(0, experts))
    params = pattern.init_params(jax.random.PRNGKey(4), whole)
    attn_lp = jax.tree.map(lambda w: w[0], params["attn"][pattern.FULL])
    lp = jax.tree.map(lambda w: w[0], params["mlp"][pattern.SPARSE])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 2 * SEQ, cfg.d_model))
    held = experts // shares
    uncut = {**config, "num_experts": experts,
             "deployment": {"num_experts": experts, "experts_held": [0, experts]}}
    with jax.default_matmul_precision("highest"):
        x1 = pattern._attn_block(cfg, pattern.FULL, x, attn_lp,
                                 *pattern._rope_tables(cfg, 2 * SEQ)[pattern.FULL])
        y = pattern.tfm.rms_norm(x1, lp["mlp_norm"], cfg.norm_eps)
        total = x1
        for s in range(shares):
            part = dataclasses.replace(cfg, experts_held=(s * held, held))
            share = {k: (v[s * held:(s + 1) * held] if k.startswith("we_") else v)
                     for k, v in lp.items()}
            routed, counts, balance = pattern.routed_experts(
                part, y.reshape(-1, cfg.d_model), share)
            total = total + routed.reshape(x.shape)
            assert int(counts["dropped"]) == 0 and balance is None
        x1_ref = x + reference.attention(
            x, attn_lp, uncut, "f32", reference.stream_mask(SEQ, cfg.diffusion.block))
        want = x1_ref + reference.sparse_mlp(
            reference.rms_norm(x1_ref, lp["mlp_norm"], cfg.norm_eps), lp, uncut, "f32")[0]
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x1_ref), atol=5e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


# -- what the program says of itself ---------------------------------------------------

def test_paths_rows_and_residuals_are_told_the_positions_of_the_stream(monkeypatch):
    """A batch of 1 x 4,096 ids at the cell's widths is a stream of 8,192 positions: the
    walk and its tiles, the rows the dispatch carries, and the bytes a layer keeps are
    those of 8,192 positions, and the logits those of 4,096."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{CONFIG}.json")
    cfg = harness.load_family(config).program_config(config, 4096)
    assert cfg.stream(4096) == 8192 and pattern.PatternConfig.tiny().stream(4096) == 4096
    assert pattern.attention_paths(cfg, 8192) == {"full": {
        "path": "blocks", "block": 512, "walk": "noised", "block_length": 4, "clean": 4096}}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pattern.attention_paths(cfg, 8192) == {"full": {
        "path": "kernel", "tile": 512, "walk": "noised", "block_length": 4, "clean": 4096}}
    odd = dataclasses.replace(cfg, diffusion=dataclasses.replace(cfg.diffusion, block=3))
    assert pattern.attention_paths(odd, 8192)["full"]["path"] == "blocks"  # no power of two
    assert pattern.attention_paths(cfg, 2 * 4000)["full"]["path"] == "blocks"  # no whole tiles
    assert pattern.dispatch_rows(cfg, 8192) == {"path": "bounded", "rows": 16384, "pairs": 65536}
    limit = 16_909_336_064  # memory_stats()["bytes_limit"] on the chip
    kept = pattern.kept_residuals(cfg, 8192, limit, 8192)
    assert list(kept["per_layer"]) == ["routing", "stream", "attention", "qkv"]
    assert kept["per_layer"]["stream"] == [8192 * 2048 * 2] * 6
    assert kept["per_layer"]["attention"] == [8192 * 32 * (128 * 2 + 4)] * 6
    causal = dataclasses.replace(cfg, diffusion=None)
    assert (pattern.kept_residuals(causal, 8192, limit, 8192)["step_bytes"] - kept["step_bytes"]
            == 2 * 4096 * 18992 * 4)  # the head reads the noised half alone


def test_the_counters_are_what_their_names_say():
    config, family, reference = tiny_file()
    cfg = family.program_config(dict(config), 64)
    tokens = jnp.asarray(batches_of(SEEDS[0], config)[0])
    params = pattern.init_params(jax.random.PRNGKey(1), cfg)
    loss, counts = jax.jit(lambda t: pattern.loss_and_counts(params, t, cfg))(tokens)
    _, masked, level = (np.asarray(x) for x in reference.draws(tokens, config))
    weights = 1.0 / level[masked]
    assert float(counts["masked_share"]) == pytest.approx(masked.mean())
    assert float(counts["weight_mean"]) == pytest.approx(weights.mean(), rel=1e-5)
    assert float(counts["weight_max"]) == pytest.approx(weights.max(), rel=1e-6)
    assert float(counts["pairs_read"]) == (64 + 4) / 2
    # the mask itself reads as many: the mean row sum of the reference's whole mask
    assert float(np.asarray(reference.stream_mask(64, 4)).sum(-1).mean()) == (64 + 4) / 2
    logits = np.asarray(jax.jit(lambda t: pattern.forward(params, t, cfg)[0])(tokens))
    nll = (jax.scipy.special.logsumexp(logits, axis=-1)
           - np.take_along_axis(logits, np.asarray(tokens)[..., None], -1)[..., 0])
    assert float(counts["loss_unweighted"]) == pytest.approx(float(nll[masked].mean()), rel=1e-4)
    assert float(loss) == pytest.approx(float((nll[masked] * weights).sum() / tokens.size), rel=1e-4)
    assert counts["pairs_held"].shape == counts["rows_carried"].shape == (3,)
    assert int(counts["pairs_held"].max()) <= 2 * tokens.size * cfg.top_k


def test_a_description_that_cannot_be_diffused_is_rejected():
    with pytest.raises(ValueError, match="full layers"):
        pattern.PatternConfig.tiny_diffusion(
            layers=(pattern.Layer(pattern.SLIDING, 8, pattern.SPARSE),))
    with pytest.raises(ValueError, match="no noise"):
        pattern.PatternConfig.tiny_diffusion(
            diffusion=pattern.Diffusion(block=4, eps=1e-3, noise_seed=0, mask_id=256))
    with pytest.raises(ValueError, match="unknown output gate"):
        pattern.PatternConfig.tiny_diffusion(gate="row")


def test_derived_specs_on_a_mesh_give_the_one_chip_loss():
    """The head norms of a full layer replicate, like every norm; the loss on a mesh of
    eight is the one chip's."""
    from jax.sharding import NamedSharding, PartitionSpec

    from tpu_resiliency.parallel import mesh as pmesh

    cfg = pattern.PatternConfig.tiny_diffusion(dtype=jnp.float32)
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    loss = jax.jit(lambda p, t: pattern.loss_fn(p, t, cfg))
    want = float(loss(params, tokens))
    mesh = pmesh.build_mesh(devices=jax.devices()[:8], dp=2, ep=2, tp=2)
    specs = pmesh.pattern_param_specs(cfg)
    full = specs["attn"]["full"]
    assert full["q_norm"] == full["k_norm"] == PartitionSpec(None, None) and "wg" not in full
    assert full["wq"] == full["wk"] == PartitionSpec(None, None, "tp")
    sharded = jax.device_put(params, pmesh.tree_shardings(mesh, specs))
    with mesh:
        got = float(loss(sharded, jax.device_put(
            tokens, NamedSharding(mesh, pmesh.batch_spec()))))
    assert abs(got - want) < 1e-4 * want


def test_the_lowered_step_carries_the_scopes_the_readers_look_for():
    """The fifth description's train step, lowered (nothing compiles): its ops' names hold
    ``attn/full``, ``attn/full/core`` and ``moe/*`` as the accepted readers' patterns want
    them, and ``diffuse/noise`` and ``diffuse/loss`` as the new reader's does, outside every
    layer's scope; the loss in the first forward and in the backward pass, the draws (which
    nothing is differentiated through) in the forward alone."""
    from benchmark import harness

    scopes = harness.load_by_path("layer_metrics", "scope_times").SCOPES
    own = harness.load_by_path("layer_metrics", "model.diffuse_ms").SCOPE
    cfg = pattern.PatternConfig.tiny_diffusion()
    train_step, init_opt = pattern.make_train_step(cfg)
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    text = jax.jit(train_step).trace(
        params, jax.eval_shape(init_opt, params), jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    ).lower().as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    under = {key: {n for n in names if mark.search(n)} for key, mark in scopes.items()}
    assert all(under.values()), {k: len(v) for k, v in under.items()}
    assert not any("moe/shared" in n for n in names)
    diffuse = {n for n in names if own.search(n)}
    noise, loss = ({n for n in diffuse if f"diffuse/{part}" in n} for part in ("noise", "loss"))
    assert noise and loss and noise | loss == diffuse
    assert not diffuse & (under["attn"] | under["moe"])
    assert any("transpose(" in n for n in loss) and not any("transpose(" in n for n in noise)
    assert any("threefry" in n or "random" in n for n in noise), sorted(noise)[:5]


def test_the_example_trains_the_description_and_records_its_walk_and_its_counters(tmp_path):
    events = tmp_path / "events.jsonl"
    env = {**os.environ, "TPU_RESILIENCY_EVENTS_FILE": str(events)}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "pattern_training.py"), "--cpu",
         "--description", "diffusion", "--steps", "6", "--routing-every", "3"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DONE loss=" in out.stdout and out.stdout.count("DIFFUSION step=") == 2
    records = [json.loads(line) for line in events.read_text().splitlines()]
    path = next(r for r in records if r.get("kind") == "attention_path")
    assert path["seq"] == 128 and path["full"] == {
        "path": "blocks", "block": 16, "walk": "noised", "block_length": 4, "clean": 64}
    dispatch = next(r for r in records if r.get("kind") == "dispatch_path")
    assert dispatch["tokens"] == 2 * 2 * 64
    diffusion = [r for r in records if r.get("kind") == "diffusion"]
    assert [r["step"] for r in diffusion] == [0, 3]
    for r in diffusion:
        assert {"masked_share", "weight_mean", "weight_max", "loss_unweighted",
                "pairs_read"} <= set(r)
        assert 0 < r["masked_share"] < 1 and r["pairs_read"] == 34.0 and r["block"] == 4
    routing = [r for r in records if r.get("kind") == "moe_routing"]
    assert len(routing) == 2 and "masked_share" not in routing[0]
    assert routing[0]["pairs"] == 2 * 2 * 64 * 4
