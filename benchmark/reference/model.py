"""Plain references: the Mistral dense block and the Mixtral top-2 MoE block.

Written from the published descriptions (Mistral 7B, arXiv:2310.06825; Mixtral of
Experts, arXiv:2401.04088; the models' public ``config.json``), in straightforward
``jax.numpy``, float32, one Python loop over the layers, no scan, no expert capacity,
no kernels. It imports nothing of ``tpu_resiliency``.

``precision`` selects what a matrix multiplication sees:

- ``"f32"``: the reference proper. The caller runs it under
  ``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is one bf16
  pass otherwise).
- ``"bf16"``: inputs rounded to bfloat16, float32 accumulation: what the
  configurations state for activations.
- ``"fp8"``: inputs rounded to float8_e4m3fn under a per-tensor amax scale, cotangents
  left in bfloat16 (the usual fp8 training recipe: e4m3 forward, wider backward),
  float32 accumulation: the precision below the stated one, which the control of
  ``correct`` uses.

Parameters are the pytree the configurations describe: ``embed [V, D]``, ``lm_head
[D, V]``, ``final_norm [D]`` and per-layer leaves stacked on a leading ``[L]`` axis
(``attn_norm, wq, wk, wv, wo, mlp_norm`` and either ``w_gate, w_up, w_down`` or
``w_router, we_gate, we_up, we_down``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _fp8(x):
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(FP8).astype(jnp.float32) / scale


# A cast to fp8 differentiated as it stands would round the cotangent to e4m3 too, and
# gradients of 1e-4 vanish there: the recipe keeps the backward pass wider.
_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_bf16(g),))


def _round(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return _bf16(x)
    if precision == "fp8":
        return _fp8(x)
    raise ValueError(f"unknown precision {precision!r}")


def matmul(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      preferred_element_type=jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta: float):
    """Rotary embedding in the half-split ("rotate_half") convention of the published
    checkpoints. x: [B, T, H, dh]."""
    t, dh = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, lp, cfg: dict, precision: str):
    b, t, _ = x.shape
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = matmul(x, lp["wq"], precision).reshape(b, t, h, dh)
    k = matmul(x, lp["wk"], precision).reshape(b, t, hkv, dh)
    v = matmul(x, lp["wv"], precision).reshape(b, t, hkv, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision), _round(k, precision),
                        preferred_element_type=jnp.float32) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                     preferred_element_type=jnp.float32).reshape(b, t, h * dh)
    return matmul(out, lp["wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision: str):
    return matmul(jax.nn.silu(matmul(x, w_gate, precision)) * matmul(x, w_up, precision),
                  w_down, precision)


def moe(x, lp, cfg: dict, precision: str):
    """Softmax over all experts, top-k, renormalised; every token reaches its experts
    (no capacity). Each expert is computed for every token and weighted by its gate,
    which is zero where the token did not choose it. Returns (y, aux)."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.matmul(x, lp["w_router"], precision="highest"), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [B, T, K, E]
    gates = jnp.einsum("btk,btke->bte", top, chosen)
    y = jnp.zeros_like(x)
    for j in range(e):
        y = y + gates[..., j:j + 1] * swiglu(
            x, lp["we_gate"][j], lp["we_up"][j], lp["we_down"][j], precision)
    # load-balance loss of the Switch/Mixtral recipe: E * sum_e f_e * P_e, f_e the
    # share of (token, choice) pairs sent to e times k, P_e the mean router probability
    frac = jnp.mean(chosen, axis=(0, 1)).sum(0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))
    return y, aux


def forward(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """tokens [B, T] -> (logits [B, T, V] float32, mean router aux loss)."""
    x = params["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    aux = jnp.zeros((), jnp.float32)
    layers = params["layers"]
    for l in range(cfg["num_hidden_layers"]):
        lp = {name: leaf[l] for name, leaf in layers.items()}
        x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg, precision)
        y = rms_norm(x, lp["mlp_norm"], eps)
        if "w_router" in lp:
            out, layer_aux = moe(y, lp, cfg, precision)
            x, aux = x + out, aux + layer_aux
        else:
            x = x + swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"], precision)
    x = rms_norm(x, params["final_norm"], eps)
    return matmul(x, params["lm_head"], precision), aux / cfg["num_hidden_layers"]


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over [B, T-1] positions, plus the router's
    load-balance loss at the configuration's weight."""
    logits, aux = forward(params, tokens, cfg, precision)
    logits, targets = logits[:, :-1], tokens[:, 1:]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + cfg.get("router_aux_loss_coef", 0.0) * aux


def init_params(seed: int, cfg: dict) -> dict:
    """Seeded weights: normal / sqrt(fan_in), norms at one (the configurations'
    ``assumed`` initialisation). The key derivation is the one the configurations
    state, so the same seed gives the program and the reference the same weights
    without either handing the other an array."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, hkv, dh, n = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"], cfg["num_hidden_layers"])
    key = jax.random.PRNGKey(seed)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 7)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    layers = {
        "attn_norm": jnp.ones((n, d), jnp.float32),
        "wq": dense(ks[0], (n, d, h * dh), d),
        "wk": dense(ks[1], (n, d, hkv * dh), d),
        "wv": dense(ks[2], (n, d, hkv * dh), d),
        "wo": dense(ks[3], (n, h * dh, d), h * dh),
        "mlp_norm": jnp.ones((n, d), jnp.float32),
    }
    if "num_local_experts" in cfg:
        e = cfg["num_local_experts"]
        kr, kg, ku, kd = jax.random.split(jax.random.fold_in(key, 7), 4)
        layers["w_router"] = dense(kr, (n, d, e), d)
        layers["we_gate"] = dense(kg, (n, e, d, f), d)
        layers["we_up"] = dense(ku, (n, e, d, f), d)
        layers["we_down"] = dense(kd, (n, e, f, d), f)
    else:
        layers["w_gate"] = dense(ks[4], (n, d, f), d)
        layers["w_up"] = dense(ks[5], (n, d, f), d)
        layers["w_down"] = dense(ks[6], (n, f, d), f)
    return {
        "embed": dense(k_embed, (v, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": dense(k_head, (d, v), d),
    }
