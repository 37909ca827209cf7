"""The reference's own training steps: what ``correct`` holds the program to.

Follows the program's first steps on the same seeded weights and the same batches, with
the weights and the loss of the reference module the configuration's family names
(``harness.load_reference``: ``init_params(seed, config)`` and ``loss(params, tokens,
config, precision)``): float32 everywhere, ``highest`` matmul precision, AdamW written
out (betas 0.9/0.999, eps 1e-8, decoupled weight decay 0.01: the configurations'
``assumed`` optimizer; the learning rate is the configuration's ``optimizer.lr`` where
the file states one, 3e-4 where it does not). Returns, per step, the loss; after the
first step the norm of every gradient leaf; after the last the norm of every parameter
leaf's change.

It runs before the program's state exists. Only the parameters and one gradient live
on the device; AdamW's two moments stay on the host between steps and cross leaf by
leaf, so the peak stays well under the program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness

LR, B1, B2, EPS, WEIGHT_DECAY = 3e-4, 0.9, 0.999, 1e-8, 0.01


@functools.partial(jax.jit, donate_argnums=(0, 2, 3), static_argnums=(5, 6))
def _adamw_leaf(p, g, mu, nu, count, decay: bool, lr: float = LR):
    mu = B1 * mu + (1 - B1) * g
    nu = B2 * nu + (1 - B2) * g * g
    m_hat = mu / (1 - B1 ** count)
    v_hat = nu / (1 - B2 ** count)
    update = m_hat / (jnp.sqrt(v_hat) + EPS) + (WEIGHT_DECAY * p if decay else 0.0)
    return p - lr * update, mu, nu


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def leaf_norms(tree) -> dict[str, float]:
    """{leaf path: l2 norm}, as Python floats."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): float(_norm(leaf)) for path, leaf in flat}


def follow(seed: int, cfg: dict, batches, precision: str = "f32", params=None) -> dict:
    """Train ``len(batches)`` steps from the seeded weights. ``batches`` are int32
    arrays [B, T]. ``precision`` other than ``"f32"`` is the control's."""
    model = harness.load_reference(cfg)
    lr = float((cfg.get("optimizer") or {}).get("lr", LR))
    grad = jax.jit(jax.value_and_grad(
        lambda p, t: model.loss(p, t, cfg, precision)))
    if params is None:
        params = model.init_params(seed, cfg)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    moments = [None] * len(leaves)  # host copies of (mu, nu) between steps
    losses, grad_norms = [], None
    for step, tokens in enumerate(batches, start=1):
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        if precision == "f32":
            with jax.default_matmul_precision("highest"):
                value, grads = grad(params, jnp.asarray(tokens))
        else:
            value, grads = grad(params, jnp.asarray(tokens))
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        g_leaves = jax.tree_util.tree_leaves(grads)
        del params, grads
        for i in range(len(leaves)):
            p, g = leaves[i], g_leaves[i]
            leaves[i] = g_leaves[i] = None
            if moments[i] is None:
                mu, nu = jnp.zeros_like(p), jnp.zeros_like(p)
            else:
                mu, nu = (jnp.asarray(m) for m in moments[i])
            # AdamW as the program applies it decays every leaf, norms included.
            p, mu, nu = _adamw_leaf(p, g, mu, nu, jnp.float32(step), True, lr)
            leaves[i] = p
            moments[i] = (np.asarray(mu), np.asarray(nu)) if step < len(batches) else None
            del mu, nu
    # the change of every leaf against the seeded weights, regenerated leaf by leaf
    initial = jax.tree_util.tree_leaves(model.init_params(seed, cfg))
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(
                 jax.tree_util.tree_unflatten(treedef, leaves))[0]]
    change_norms = {}
    for i, path in enumerate(paths):
        change_norms[path] = float(_norm(leaves[i] - initial[i]))
        initial[i] = None
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change_norms}
