"""The training contract's optimizer, which every model's ``make_train_step`` shares
(``models/transformer.py:make_train_step_from_loss``): AdamW at 3e-4 where none is handed
in, and the AdamW a configuration's stated rate hands in (``optax.adamw(lr,
weight_decay=0.01)``, what ``benchmark/families/solar.py`` and ``ouro.py`` pass) is the
same optimizer at another rate: after one step from the same weights on the same batch
every leaf has moved by ``lr / 3e-4`` of what the default moves it, to float32's rounding,
and the optimizer's state has the default's tree structure (which
``benchmark/harness.py:Session.first_gradient_norms`` reads)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_resiliency.models import pattern
from tpu_resiliency.models import transformer as tfm

MODELS = {
    "transformer": (tfm, tfm.TransformerConfig.tiny),
    "looped": (tfm, tfm.TransformerConfig.tiny_looped),
    "pattern": (pattern, pattern.PatternConfig.tiny),
}


@pytest.fixture(scope="module")
def default_steps():
    """{model: (cfg, params, tokens, the default's parameters and state after a step)}."""
    out = {}
    for name, (module, preset) in MODELS.items():
        cfg = preset()
        params = module.init_params(jax.random.PRNGKey(2), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, cfg.vocab_size)
        train_step, init_opt = module.make_train_step(cfg)
        after, state, _ = jax.jit(train_step)(params, init_opt(params), tokens)
        out[name] = (cfg, params, tokens, after, state)
    return out


@pytest.mark.parametrize("lr", [3e-6, 1e-4, 1e-3])
@pytest.mark.parametrize("model", list(MODELS))
def test_a_stated_rate_moves_every_leaf_by_its_share_of_the_defaults_step(default_steps, model, lr):
    cfg, params, tokens, default_after, default_state = default_steps[model]
    train_step, init_opt = MODELS[model][0].make_train_step(
        cfg, optax.adamw(lr, weight_decay=0.01))
    after, state, _ = jax.jit(train_step)(params, init_opt(params), tokens)
    assert jax.tree.structure(state) == jax.tree.structure(default_state)
    for m, d in zip(jax.tree.leaves(state[0].mu), jax.tree.leaves(default_state[0].mu)):
        np.testing.assert_array_equal(np.asarray(m), np.asarray(d))  # the same first gradient
    eps = float(jnp.finfo(jnp.float32).eps)
    before, moved, by_default = (
        {jax.tree_util.keystr(path): np.asarray(leaf, np.float64)
         for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
        for tree in (params, after, default_after))
    for leaf, p in before.items():
        assert np.any(by_default[leaf] != p), leaf
        # each side rounds its new weight once: two roundings of a weight of this size
        np.testing.assert_allclose(
            moved[leaf] - p, (by_default[leaf] - p) * (lr / 3e-4), rtol=1e-5,
            atol=2 * eps * float(np.max(np.abs(p))) * max(1.0, lr / 3e-4), err_msg=leaf)
