"""Plain reference: the Ouro looped decoder (LoopLM), as one pipeline stage's layers.

Written from the model's public ``config.json``
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json) and its published
description ("Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741, and
the ``modeling_ouro.py`` beside the config) in straightforward ``jax.numpy``, float32: a
Python loop over the passes around a Python loop over the layers, the exits each computed
in full, no scan, no kernel. It imports nothing of ``tpu_resiliency``; ``precision`` is
``reference/model.py``'s (``"f32"``, ``"bf16"``, and the control's ``"fp8"``).

Tokens ``x_1..x_S``, ``h^(0) = E[x]``.

- A layer ``f_l``: ``a = h + N2_l(Attn_l(N1_l(h)))``, ``f_l(h) = a + N4_l(SwiGLU_l(N3_l(a)))``:
  sandwich norms, an RMS norm on each sublayer's input and on its output, eps
  ``rms_norm_eps``. ``Attn``: ``q, k, v = y W_q, y W_k, y W_v`` (no bias), rotary on all
  ``head_dim`` dimensions of every head of ``q`` and ``k`` (``rope_theta``, the half-split
  form), causal softmax of ``q k^T / sqrt(head_dim)`` a head, times ``v``, times ``W_o``.
  ``SwiGLU(y) = (silu(y W_gate) * (y W_up)) W_down``.
- The loop: for ``t = 1..total_ut_steps``, ``h^(t) = N_f(F(h^(t-1)))``, ``F`` the
  ``num_hidden_layers`` layers in turn on the same weights: the final norm closes every
  pass and the normed stream is what the next pass reads.
- Exits: ``z^(t) = h^(t) W_head``, ``l_t[i] = logsumexp(z^(t)[i]) - z^(t)[i, x_{i+1}]``; a
  gate ``lam_t[i] = sigmoid(h^(t)[i] . w_g + b_g)``, the same at every pass. ``p_t = lam_t
  prod_{j<t} (1 - lam_j)`` for ``t`` before the last pass, which takes what is left.
- The loss (the report's first training stage): ``mean_i [sum_t p_t[i] l_t[i] - beta
  H(p[i])]``, ``H(p) = -sum_t p_t log p_t``, over the ``S - 1`` positions with a target,
  ``beta = assumed.exit_beta``. ``early_exit_threshold`` is inference's: not read.

**Memory, not mathematics:** attention goes by query blocks of ``QUERY_BLOCK`` rows, each
against all the keys under its rows of the mask, and each block, layer and exit is
recomputed in the backward pass (``jax.checkpoint``), so that 32 layer applications at
4,096 tokens and the four exits' float32 logits fit one chip beside the float32 weights
and their gradient. The probabilities are kept as their logarithms (``log sigmoid``), so
that a gate that is shut gives a probability of 0 and an entropy term of 0, not a NaN.

The parameter tree has the program's leaf paths: ``embed``, ``final_norm``, ``lm_head``,
``exit_gate/{w, b}`` and ``layers/<leaf>`` stacked on a leading ``[L]`` axis
(``attn_norm, attn_post_norm, mlp_norm, mlp_post_norm, wq, wk, wv, wo, w_gate, w_up,
w_down``). Weights: normal / sqrt(fan_in), norms at one, the gate's bias zero; keys as the
configuration's ``assumed.init`` states them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import model
from benchmark.reference.model import _round, matmul, rms_norm, rope, swiglu

QUERY_BLOCK = 256


def init_params(seed: int, cfg: dict) -> dict:
    """The dense block's seeded weights (``reference/model.py``: three keys from
    ``PRNGKey(seed)``, seven from the layers'), the two further norms a layer at one, and
    the gate: its weight from ``fold_in(PRNGKey(seed), 3)``, its bias zero."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    params = model.init_params(seed, cfg)
    params["layers"].update(attn_post_norm=jnp.ones((n, d), jnp.float32),
                            mlp_post_norm=jnp.ones((n, d), jnp.float32))
    gate_key = jax.random.fold_in(jax.random.PRNGKey(seed), 3)
    params["exit_gate"] = {"w": jax.random.normal(gate_key, (d, 1), jnp.float32) / np.sqrt(d),
                           "b": jnp.zeros((1,), jnp.float32)}
    return params


def attention(y, lp: dict, cfg: dict, precision: str):
    """Causal multi-head attention of the normed input ``y``, by blocks of query rows."""
    b, t, _ = y.shape
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rope(matmul(y, lp["wq"], precision).reshape(b, t, h, dh), cfg["rope_theta"])
    k = rope(matmul(y, lp["wk"], precision).reshape(b, t, hkv, dh), cfg["rope_theta"])
    v = matmul(y, lp["wv"], precision).reshape(b, t, hkv, dh)
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def block(rows):
        q_rows, causal_rows = rows  # [B, Q, H, dh], [Q, T]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q_rows, precision), _round(k, precision),
                            preferred_element_type=jnp.float32) / np.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(causal_rows[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                          preferred_element_type=jnp.float32)

    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(block, (q.reshape(b, t // rows, rows, h, dh).swapaxes(0, 1),
                              causal.reshape(t // rows, rows, t)))
    return matmul(out.swapaxes(0, 1).reshape(b, t, h * dh), lp["wo"], precision)


def layer(x, lp: dict, cfg: dict, precision: str):
    eps = cfg["rms_norm_eps"]
    a = x + rms_norm(attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg, precision),
                     lp["attn_post_norm"], eps)
    mlp = swiglu(rms_norm(a, lp["mlp_norm"], eps), lp["w_gate"], lp["w_up"], lp["w_down"],
                 precision)
    return a + rms_norm(mlp, lp["mlp_post_norm"], eps)


def exit_of(x, params: dict, targets, precision: str):
    """One exit from its normed stream: the NLL of every position with a target through
    the head, and ``log lam``, ``log (1 - lam)`` of the gate there."""
    logits = matmul(x[:, :-1], params["lm_head"], precision)
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    gate = jnp.sum(x[:, :-1] * params["exit_gate"]["w"][:, 0], axis=-1) + params["exit_gate"]["b"][0]
    return nll, jax.nn.log_sigmoid(gate), jax.nn.log_sigmoid(-gate)


def exits(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """(``l_t``, ``log p_t``) of every pass, ``[passes, B, S - 1]`` each."""
    targets = tokens[:, 1:]
    x = params["embed"][tokens]
    passes = cfg["total_ut_steps"]
    nlls, log_ps = [], []
    left = jnp.zeros(targets.shape, jnp.float32)  # log prod_{j<t} (1 - lam_j)
    for t in range(passes):
        for l in range(cfg["num_hidden_layers"]):
            lp = {name: leaf[l] for name, leaf in params["layers"].items()}
            x = jax.checkpoint(lambda x, lp: layer(x, lp, cfg, precision))(x, lp)
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        nll, log_lam, log_stay = jax.checkpoint(
            lambda x, params: exit_of(x, params, targets, precision))(x, params)
        nlls.append(nll)
        log_ps.append(left + log_lam if t < passes - 1 else left)  # the last takes what is left
        left = left + log_stay
    return jnp.stack(nlls), jnp.stack(log_ps)


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """``mean_i [sum_t p_t[i] l_t[i] - beta H(p[i])]`` over the positions with a target."""
    nll, log_p = exits(params, tokens, cfg, precision)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - cfg["assumed"]["exit_beta"] * entropy)
