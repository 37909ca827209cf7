"""The dense model as a looped decoder (``models/transformer.py`` with ``n_passes``,
``sandwich_norms``, ``exit_beta``): against the plain float32 reference
(``benchmark/reference/ouro.py``) on loss and every gradient leaf, the gradient of tied
weights as the sum over untied copies, the exit distribution, the counters, causality at
every pass, the rule of what a layer keeps, and that the default description is the
program it was. Tiny widths, seeded weights, CPU."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.models import transformer as tfm
from tpu_resiliency.parallel import mesh as pmesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ouro as reference  # noqa: E402

SEQ = 24


def looped(passes: int = 3, **kw):
    return tfm.TransformerConfig.tiny_looped(
        n_passes=passes, n_kv_heads=4, n_layers=3, dtype=jnp.float32, **kw)


def as_config(cfg) -> dict:
    """The description as a configuration file of family ``ouro`` states it."""
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "num_hidden_layers": cfg.n_layers,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "total_ut_steps": cfg.n_passes, "assumed": {"exit_beta": cfg.exit_beta}}


def batch(cfg, seed: int = 1, rows: int = 2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0, cfg.vocab_size)


def leaves(tree) -> dict:
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


REFERENCE_LEAVES = sorted(leaves(jax.eval_shape(
    lambda: tfm.init_params(jax.random.PRNGKey(0), looped()))))


@pytest.fixture(scope="module")
def against_reference():
    """{passes: (program's loss and gradient, reference's)} in float32, computed once."""
    out = {}
    for passes in (1, 2, 4):
        cfg = looped(passes)
        params, tokens = reference.init_params(7, as_config(cfg)), batch(cfg)
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(tfm.loss_fn)(params, tokens, cfg)
            want = jax.value_and_grad(reference.loss)(params, tokens, as_config(cfg), "f32")
        out[passes] = (got, want)
    return out


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_loss_equals_the_references(against_reference, passes):
    (got, _), (want, _) = against_reference[passes]
    assert abs(float(got) - float(want)) < 2e-5 * float(want)


@pytest.mark.parametrize("leaf", REFERENCE_LEAVES)
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_every_gradient_leaf_equals_the_references(against_reference, passes, leaf):
    (_, got), (_, want) = against_reference[passes]
    got, want = leaves(got)[leaf], leaves(want)[leaf]
    if passes == 1 and "exit_gate" in leaf:  # one pass takes everything: its gate enters nothing
        assert not got.any() and not want.any()
        return
    assert np.linalg.norm(got - want) < 2e-4 * np.linalg.norm(want)


def test_the_program_seeds_the_weights_the_reference_seeds():
    cfg = looped()
    ours, theirs = tfm.init_params(jax.random.PRNGKey(11), cfg), reference.init_params(11, as_config(cfg))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def unrolled_loss(copies: list, shared: dict, tokens, cfg):
    """The looped model's loss with a copy of the stack a pass: the same functions, the
    passes written out."""
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    cos, sin = tfm.rope_tables(cfg, tokens.shape[1])
    x = shared["embed"].astype(cfg.dtype)[tokens]
    yields = []
    for layers in copies:
        for l in range(cfg.n_layers):
            x = tfm._layer(cfg, x, jax.tree.map(lambda w: w[l], layers), cos, sin, tfm._attention)
        x = tfm.rms_norm(x, shared["final_norm"], cfg.norm_eps)
        yields.append(tfm._exit(x, shared["lm_head"], shared["exit_gate"], targets))
    nll, gate = (jnp.stack(a)[..., :-1] for a in zip(*yields))
    return tfm.mix_exits(nll, gate, cfg.exit_beta)[0]


def test_a_tied_leafs_gradient_is_the_sum_over_the_untied_copies():
    cfg = looped(3)
    params, tokens = tfm.init_params(jax.random.PRNGKey(3), cfg), batch(cfg)
    shared = {k: v for k, v in params.items() if k != "layers"}
    tied = jax.grad(tfm.loss_fn)(params, tokens, cfg)
    untied, of_shared = jax.grad(unrolled_loss, argnums=(0, 1))(
        [params["layers"]] * cfg.n_passes, shared, tokens, cfg)
    for name, leaf in tied["layers"].items():
        copies = [np.asarray(copy[name]) for copy in untied]
        assert all(np.linalg.norm(c) > 0 for c in copies), name
        np.testing.assert_allclose(np.asarray(leaf), sum(copies), rtol=2e-4, atol=1e-6, err_msg=name)
    for a, b in zip(jax.tree.leaves({k: tied[k] for k in shared}), jax.tree.leaves(of_shared)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6)


def exits_of(cfg, params, tokens):
    """(nll, gate logit) ``[passes, B, S - 1]`` as ``loss_and_counts`` mixes them."""
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))

    def close(x):
        x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, tfm._exit(x, params["lm_head"], params["exit_gate"], targets)

    _, (nll, gate) = tfm._passes(params, tokens, cfg, None, 0, close)
    return nll[..., :-1], gate[..., :-1]


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_what_is_left(passes):
    gate = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 2, SEQ))
    nll = jnp.zeros_like(gate).at[:].set(jnp.arange(1.0, passes + 1)[:, None, None])
    lam = np.asarray(jax.nn.sigmoid(gate), np.float64)
    want = [lam[t] * np.prod(1 - lam[:t], axis=0) for t in range(passes - 1)]
    want.append(np.prod(1 - lam[:passes - 1], axis=0))  # what is left; lam of the last: nothing
    assert np.allclose(np.sum(want, axis=0), 1.0)
    loss, counts = tfm.mix_exits(nll, gate, beta=0.0)
    np.testing.assert_allclose(np.asarray(counts["exit_share"]), np.mean(want, axis=(1, 2)), rtol=1e-5)
    np.testing.assert_allclose(float(np.sum(counts["exit_share"])), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(loss), np.mean(np.sum(
        np.asarray(want) * np.asarray(nll), axis=0)), rtol=1e-5)


@pytest.mark.parametrize("bias,exit_taken", [(-30.0, -1), (30.0, 0)])
def test_a_shut_gate_leaves_the_last_exits_loss_and_an_open_one_the_firsts(bias, exit_taken):
    cfg = looped(4, exit_beta=0.0)
    params, tokens = tfm.init_params(jax.random.PRNGKey(5), cfg), batch(cfg)
    params["exit_gate"]["b"] = jnp.full((1,), bias)
    (loss, counts), grads = jax.value_and_grad(tfm.loss_and_counts, has_aux=True)(params, tokens, cfg)
    nll, _ = exits_of(cfg, params, tokens)
    np.testing.assert_allclose(float(loss), float(jnp.mean(nll[exit_taken])), rtol=1e-6)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))


def test_the_counters_are_what_their_names_say():
    cfg = looped(4)
    params, tokens = tfm.init_params(jax.random.PRNGKey(6), cfg), batch(cfg)
    params["exit_gate"]["w"] = 8.0 * params["exit_gate"]["w"]  # gates that differ by position
    loss, counts = tfm.loss_and_counts(params, tokens, cfg)
    nll, gate = (np.asarray(a, np.float64) for a in exits_of(cfg, params, tokens))
    lam = 1 / (1 + np.exp(-gate))
    p = np.stack([lam[t] * np.prod(1 - lam[:t], axis=0) for t in range(3)]
                 + [np.prod(1 - lam[:3], axis=0)])
    entropy = -np.sum(p * np.log(p), axis=0)
    assert {k: v.shape for k, v in counts.items()} == {
        "exit_share": (4,), "exit_loss": (4,), "exit_entropy": (), "gate_mean": (3,)}
    np.testing.assert_allclose(np.asarray(counts["exit_share"]), p.mean(axis=(1, 2)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(counts["exit_loss"]), nll.mean(axis=(1, 2)), rtol=1e-6)
    np.testing.assert_allclose(float(counts["exit_entropy"]), entropy.mean(), rtol=1e-5)
    assert float(counts["exit_entropy"]) <= np.log(4)
    np.testing.assert_allclose(np.asarray(counts["gate_mean"]), lam[:3].mean(axis=(1, 2)), rtol=1e-5)
    np.testing.assert_allclose(
        float(loss), np.mean(np.sum(p * nll, axis=0) - cfg.exit_beta * entropy), rtol=1e-5)


def test_a_later_token_changes_no_exit_of_an_earlier_position_at_any_pass():
    cfg = looped(4)
    params, tokens = tfm.init_params(jax.random.PRNGKey(8), cfg), batch(cfg, rows=1)
    at = 15
    moved = tokens.at[0, at].set((tokens[0, at] + 1) % cfg.vocab_size)
    (nll, gate), (nll2, gate2) = exits_of(cfg, params, tokens), exits_of(cfg, params, moved)
    # position i's target is token i + 1: the NLLs before at - 1 and the gates before at
    np.testing.assert_allclose(np.asarray(nll[..., :at - 1]), np.asarray(nll2[..., :at - 1]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gate[..., :at]), np.asarray(gate2[..., :at]), atol=1e-5)
    assert all(not np.allclose(np.asarray(gate[t, 0, at:]), np.asarray(gate2[t, 0, at:]), atol=1e-4)
               for t in range(4))


def memory_for(cfg, n_groups: int) -> int:
    """A device's memory at which ``kept_residuals`` takes just the first ``n_groups``."""
    everything = tfm.kept_residuals(cfg, 2 * SEQ, None, SEQ)
    held = list(everything["groups"].values())[:n_groups]
    return int(everything["step_bytes"] + tfm.STACKING_LOSS * sum(held) + tfm.RESERVED_BYTES + 1)


def test_the_kept_list_shrinks_with_the_devices_memory():
    cfg = looped(3)
    # on the plain path, here: its scores carry no name, and only the kernels name their output
    groups = [g for g in tfm.KEPT_GROUPS if g not in ("attention", "scores")]
    everything = tfm.kept_residuals(cfg, 2 * SEQ, None, SEQ)
    assert everything["everything"] and list(everything["groups"]) == [*groups, "scores"]
    lists = []
    for n in range(len(groups), -1, -1):
        kept = tfm.kept_residuals(cfg, 2 * SEQ, memory_for(cfg, n), SEQ)
        assert not kept["everything"] and list(kept["groups"]) == groups[:n]
        assert kept["names"] == [name for g in groups[:n] for name in tfm.KEPT_GROUPS[g]]
        assert kept["bytes"] == sum(kept["groups"].values())
        lists.append(kept["names"])
    assert lists[-1] == [] and all(len(a) > len(b) for a, b in zip(lists, lists[1:]))


@pytest.mark.parametrize("n_groups", [0, 1, 4, 7])
def test_loss_and_gradients_do_not_depend_on_what_is_kept(monkeypatch, n_groups):
    cfg = looped(3)
    params, tokens = tfm.init_params(jax.random.PRNGKey(9), cfg), batch(cfg)
    want = jax.value_and_grad(tfm.loss_fn)(params, tokens, cfg)

    def remats() -> int:  # a fresh function: a trace is kept by the function's identity
        jaxpr = jax.make_jaxpr(lambda p, t: tfm.loss_fn(p, t, cfg))(params, tokens)
        return len(re.findall(r"\bremat2\b", str(jaxpr)))

    assert remats() == 1  # each exit is rematerialized, whatever is kept (one scan body)
    limit = memory_for(cfg, n_groups)
    monkeypatch.setattr(tfm, "device_memory_bytes", lambda: limit)
    assert remats() == 2  # and now the layer
    got = jax.value_and_grad(tfm.loss_fn)(params, tokens, cfg)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_the_default_description_has_the_parameter_tree_it_had():
    cfg = tfm.TransformerConfig.tiny()
    assert (cfg.n_passes, cfg.sandwich_norms, cfg.exit_beta, cfg.norm_eps, cfg.attention) == (
        1, False, None, 1e-5, "plain")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert sorted(params) == ["embed", "final_norm", "layers", "lm_head"]
    assert sorted(params["layers"]) == ["attn_norm", "mlp_norm", "w_down", "w_gate", "w_up",
                                        "wk", "wo", "wq", "wv"]
    assert tfm.loss_and_counts(params, batch(cfg), cfg)[1] == {}


def test_the_default_descriptions_lowered_step_holds_no_checkpoint_and_no_exit(monkeypatch):
    monkeypatch.setattr(tfm, "device_memory_bytes", lambda: 16_909_336_064)
    cfg = tfm.TransformerConfig.tiny()
    train_step, init_opt = tfm.make_train_step(cfg)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    lowered = jax.jit(train_step).lower(params, init_opt(params), batch(cfg))
    assert "remat" not in str(jax.make_jaxpr(train_step)(params, init_opt(params), batch(cfg)))
    text = lowered.as_text(debug_info=True)
    assert "attn/full" in text and "mlp/dense" in text  # the scopes are in the locations
    assert not re.search(r"[/(]checkpoint[/)]", text) and not re.search(r"[/(]exit[/)]", text)
    assert "optimization_barrier" not in text  # what a rematerialized layer lowers to


def test_mistral_on_the_chips_memory_keeps_everything():
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", "mistral-7b-l2.json")
    batch_, seq = config["batch"]
    cfg = harness.load_family(config).program_config(config, seq)
    kept = tfm.kept_residuals(cfg, batch_ * seq, 16_909_336_064, seq)
    assert kept["everything"] and "scores" in kept["groups"]
    assert not tfm.kept_residuals(cfg, 4 * batch_ * seq, 16_909_336_064, seq)["everything"]


def test_the_ouro_cell_on_the_chips_memory_keeps_four_groups(monkeypatch):
    from benchmark import harness

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = harness.read_json(harness.HERE, "configs", "ouro-2.6b-l8.json")
    batch_, seq = config["batch"]
    cfg = harness.load_family(config).program_config(config, seq)
    assert tfm.attention_path(cfg, seq) == {"path": "kernel", "tile": 512}
    kept = tfm.kept_residuals(cfg, batch_ * seq, 16_909_336_064, seq)
    assert list(kept["groups"]) == ["attention", "mlp_proj", "attn_proj", "v"]
    assert kept["names"] == ["attn_out", "attn_lse", "mlp_proj", "attn_proj", "attn_v"]


def test_the_kernels_are_taken_only_where_they_apply(monkeypatch):
    cfg = looped(attention="kernel")
    assert tfm.attention_path(cfg, 512) == {"path": "plain"}  # the CPU; heads of 16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm.attention_path(cfg, 512) == {"path": "plain"}  # heads of 16 are no lane group
    wide = tfm.TransformerConfig.tiny(d_model=256, n_heads=2, n_kv_heads=2, attention="kernel")
    assert tfm.attention_path(wide, 512) == {"path": "kernel", "tile": 512}
    assert tfm.attention_path(wide, 200) == {"path": "plain"}  # no whole tiles
    assert tfm.attention_path(tfm.TransformerConfig.tiny(d_model=256, n_heads=2), 512) == {
        "path": "plain"}  # the default asks for none


@pytest.mark.parametrize("cfg", [tfm.TransformerConfig.tiny(), looped()], ids=["default", "looped"])
def test_param_specs_cover_every_leaf(cfg):
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    specs = pmesh.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    flat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    assert all(len(spec) == leaf.ndim for spec, leaf in zip(flat, jax.tree.leaves(params)))


def test_a_description_that_is_none_is_refused():
    with pytest.raises(ValueError, match="n_passes"):
        tfm.TransformerConfig.tiny(n_passes=0)
    with pytest.raises(ValueError, match="attention"):
        tfm.TransformerConfig.tiny(attention="flash")


def test_the_example_records_its_three_events(tmp_path):
    events_file = tmp_path / "events.jsonl"
    env = {**os.environ, "TPU_RESILIENCY_EVENTS_FILE": str(events_file), "PYTHONPATH": ROOT}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "looped_training.py"), "--cpu",
         "--steps", "6", "--exits-every", "3"], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ATTENTION {'path': 'plain'}" in out.stdout and "KEPT " in out.stdout
    assert len(re.findall(r"^EXITS step=\d+ ", out.stdout, re.M)) == 2
    assert re.search(r"^DONE loss=\d", out.stdout, re.M)
    import json

    kinds = [json.loads(line).get("kind") for line in events_file.read_text().splitlines()]
    assert kinds.count("attention_path") == 1 and kinds.count("kept_residuals") == 1
    assert kinds.count("exit_state") == 2
