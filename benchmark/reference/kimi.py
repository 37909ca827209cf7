"""Plain reference: the language decoder of Kimi-VL-A3B, as one chip of an
expert-parallel deployment holds it.

Written from the model's public ``config.json``
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json, the
``text_config`` of a DeepSeek-V3-style decoder) in straightforward ``jax.numpy``,
float32: one Python loop over the layers and a loop over the experts held, every held
expert computed for every token and weighted by its gate (zero where the token did not
choose it), the keys and values of every head decompressed and the rotary key repeated
for every head: no sort, no grouped product, no kernel, nothing absorbed. It imports
nothing of ``tpu_resiliency``; ``precision`` is ``reference/model.py``'s (``"f32"``,
``"bf16"``, and the control's ``"fp8"``).

Layer ``l`` (pre-norm residual, RMSNorm with ``rms_norm_eps``), for the normed input
``y``:

- Latent attention. ``q = y W_q`` (``num_attention_heads`` x (``qk_nope_head_dim`` +
  ``qk_rope_head_dim``); ``q_lora_rank`` is null, so the query has no low rank of its
  own), each head ``[q_nope | q_rope]``. ``[c | k_r] = y W_kv_a`` (``kv_lora_rank`` +
  ``qk_rope_head_dim``); ``c = rms_norm(c)`` with a weight of its own; ``[k_nope | v] =
  c W_kv_b``, a head at a time (``qk_nope_head_dim`` + ``v_head_dim``). Rotary positions
  (``rope_theta``, no scaling) on ``q_rope`` of every head and on the one ``k_r``, which
  every head shares. A head's score is ``(q_nope . k_nope + q_rope . k_r) /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, ``softmax @ v``, heads
  concatenated, ``W_o``. No gate, no bias, no QK norm.
- The MLP: a SwiGLU of ``intermediate_size`` in the first ``first_k_dense_replace``
  layers; after them ``s = sigmoid(y W_r)`` over all experts of the deployment, the
  ``num_experts_per_tok`` experts with the largest ``s + b`` (``b``, the leaf
  ``b_router`` times :func:`bias_gain`, enters the choice and nothing else; ``n_group`` =
  ``topk_group`` = 1, so ``noaux_tc``'s group step is the identity; the method's
  balancing rule moves ``b`` through the loss's gradient, :func:`sparse_mlp`), weights ``s[chosen] / sum(s[chosen]) x
  routed_scaling_factor`` (``norm_topk_prob``), the routed sum over the chosen experts
  held here, plus one shared SwiGLU of width ``n_shared_experts x
  moe_intermediate_size``. Embedding and head are not tied.

**The share.** ``n_routed_experts`` counts the experts held here,
``deployment.experts_held`` says which of the ``deployment.n_routed_experts`` the router
scores; a token's choices that fall on experts held elsewhere add nothing (they are
another chip's part of the sum). ``vocab_size`` is the slice of the vocabulary held
here: ids, logits and loss are over the slice.

**Memory and compile time, not mathematics:** attention goes by query blocks of
``QUERY_BLOCK`` rows, each against all the keys under its rows of the mask, and each
block, expert and layer is recomputed in the backward pass (``jax.checkpoint``), so that
8,192 tokens at the published widths fit one chip beside the float32 weights and their
gradient. The loops over the blocks and over the experts held are ``lax`` loops, one
compiled body each (the running sum is kept once an expert for the backward pass: 67 MB
each at 8,192 tokens).

The parameter tree is the one ``describe`` lists, with the program's leaf paths:
``embed``, ``final_norm``, ``lm_head``, ``attn/latent/<leaf>`` and
``mlp/<dense|sparse>/<leaf>``, the layers of one kind stacked on a leading axis in the
order they appear. Weights: normal / sqrt(fan_in), norms at one, ``b_router`` normal x
``assumed.router_bias_std``, one PRNG key a leaf, split from ``PRNGKey(seed)`` in the
order the tree flattens (sorted keys).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.model import _round, matmul, rms_norm, swiglu

QUERY_BLOCK = 256


def mlp_kinds(cfg: dict) -> list[str]:
    """The MLP kind of each layer that runs."""
    return ["dense" if layer < cfg["first_k_dense_replace"] else "sparse"
            for layer in range(cfg["num_hidden_layers"])]


def bias_gain(cfg: dict) -> float:
    """What one unit of ``b_router`` adds to a score in the choice: the balancing rule's
    step (``assumed.router_bias_step``) over AdamW's (``reference/train.py:LR``, 3e-4),
    which is what AdamW moves a leaf by on a gradient of +-1."""
    return cfg["assumed"]["router_bias_step"] / 3e-4


def describe(cfg: dict) -> dict:
    """{path: (shape, fan_in or None for a norm)} as a nested dict; ``b_router``'s third
    entry is the standard deviation it is drawn at."""
    d, h, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kinds = mlp_kinds(cfg)
    n = len(kinds)
    tree = {"embed": ((cfg["vocab_size"], d), d), "final_norm": ((d,), None),
            "lm_head": ((d, cfg["vocab_size"]), d), "mlp": {},
            "attn": {"latent": {
                "attn_norm": ((n, d), None), "wq": ((n, d, h * (nope + rope)), d),
                "wkv_a": ((n, d, rank + rope), d), "kv_norm": ((n, rank), None),
                "wkv_b": ((n, rank, h * (nope + dv)), rank), "wo": ((n, h * dv, d), h * dv)}}}
    n = kinds.count("dense")
    if n:
        f = cfg["intermediate_size"]
        tree["mlp"]["dense"] = {
            "mlp_norm": ((n, d), None), "w_gate": ((n, d, f), d),
            "w_up": ((n, d, f), d), "w_down": ((n, f, d), f)}
    n = kinds.count("sparse")
    if n:
        e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs, all_experts = cfg["n_shared_experts"] * f, cfg["deployment"]["n_routed_experts"]
        tree["mlp"]["sparse"] = {
            "mlp_norm": ((n, d), None), "w_router": ((n, d, all_experts), d),
            "b_router": ((n, all_experts), None, cfg["assumed"]["router_bias_std"] / bias_gain(cfg)),
            "we_gate": ((n, e, d, f), d), "we_up": ((n, e, d, f), d),
            "we_down": ((n, e, f, d), f),
            "ws_gate": ((n, d, fs), d), "ws_up": ((n, d, fs), d), "ws_down": ((n, fs, d), fs)}
    return tree


def init_params(seed: int, cfg: dict) -> dict:
    leaves, treedef = jax.tree_util.tree_flatten(
        describe(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    def seeded(key, shape, fan_in, std=None):
        if std is not None:
            return jax.random.normal(key, shape, jnp.float32) * std
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    return jax.tree_util.tree_unflatten(
        treedef, [seeded(key, *leaf) for key, leaf in zip(keys, leaves)])


def rotary(x, theta: float):
    """Rotary positions on every dimension of x ``[B, T, H, dr]``, half-split
    ("rotate_half") pairing: dimension ``i`` turns with dimension ``i + dr/2``."""
    t, dr = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, lp: dict, cfg: dict, precision: str):
    b, t, _ = x.shape
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    theta = float(cfg["rope_theta"])
    y = rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = matmul(y, lp["wq"], precision).reshape(b, t, h, nope + rope)
    down = matmul(y, lp["wkv_a"], precision)
    c = rms_norm(down[..., :rank], lp["kv_norm"], cfg["assumed"]["latent_norm_eps"])
    k_rope = rotary(down[..., rank:].reshape(b, t, 1, rope), theta)
    up = matmul(c, lp["wkv_b"], precision).reshape(b, t, h, nope + dv)
    # the long way: every head gets keys of the whole score width, the rotary part repeated
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([up[..., :nope], jnp.repeat(k_rope, h, axis=2)], axis=-1)
    v = up[..., nope:]
    allowed = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def block(rows):
        q_rows, allowed_rows = rows  # [B, Q, H, nope + rope], [Q, T]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q_rows, precision), _round(k, precision),
                            preferred_element_type=jnp.float32) / np.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(allowed_rows[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                          preferred_element_type=jnp.float32)

    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(block, (q.reshape(b, t // rows, rows, h, nope + rope).swapaxes(0, 1),
                              allowed.reshape(t // rows, rows, t)))
    out = out.swapaxes(0, 1).reshape(b, t, h * dv)
    return matmul(out, lp["wo"], precision)


def sparse_mlp(y, lp: dict, cfg: dict, precision: str):
    """The held experts' part of the routed sum plus the shared SwiGLU, and the balancing
    rule's term: zero in value, its gradient by ``b_router`` +1 for an expert that more
    than the even share of this batch's choices fell on and -1 for any other."""
    first, held = cfg["deployment"]["experts_held"]
    experts = cfg["deployment"]["n_routed_experts"]
    bias = lp["b_router"]
    scores = jax.nn.sigmoid(jnp.matmul(y, lp["w_router"], precision="highest"))
    _, chosen = jax.lax.top_k(
        scores + bias_gain(cfg) * bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    load = jnp.stack([jnp.sum(chosen == e) for e in range(experts)])
    over = jnp.where(load * experts > chosen.size, 1.0, -1.0)
    balance = jnp.sum((bias - jax.lax.stop_gradient(bias)) * over)
    out = swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"], precision)

    @jax.checkpoint
    def add_expert(out, expert):
        number, w_gate, w_up, w_down = expert
        gate = jnp.sum(jnp.where(chosen == first + number, top, 0.0), -1, keepdims=True)
        return out + gate * swiglu(y, w_gate, w_up, w_down, precision), None

    return jax.lax.scan(
        add_expert, out, (jnp.arange(held), lp["we_gate"], lp["we_up"], lp["we_down"]))[0], balance


def forward(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """tokens [B, T] -> (logits [B, T, V] float32 (V: the slice held here), the sum of the
    sparse layers' balancing terms)."""
    x = params["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    seen, balances = {"dense": 0, "sparse": 0}, 0.0

    def layer(x, attn_lp, mlp_lp, mlp):
        x = x + attention(x, attn_lp, cfg, precision)
        y = rms_norm(x, mlp_lp["mlp_norm"], eps)
        if mlp == "dense":
            return x + swiglu(y, mlp_lp["w_gate"], mlp_lp["w_up"], mlp_lp["w_down"], precision), 0.0
        out, balance = sparse_mlp(y, mlp_lp, cfg, precision)
        return x + out, balance

    for number, mlp in enumerate(mlp_kinds(cfg)):
        attn_lp = {name: leaf[number] for name, leaf in params["attn"]["latent"].items()}
        mlp_lp = {name: leaf[seen[mlp]] for name, leaf in params["mlp"][mlp].items()}
        seen[mlp] += 1
        x, balance = jax.checkpoint(layer, static_argnums=(3,))(x, attn_lp, mlp_lp, mlp)
        balances = balances + balance
    return matmul(rms_norm(x, params["final_norm"], eps), params["lm_head"], precision), balances


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over [B, T-1] positions, plus the balancing terms
    (nothing in value; what AdamW moves ``b_router`` by). No auxiliary loss: ``seq_aux``
    is true but the config carries no coefficient for one (``assumed``)."""
    logits, balance = forward(params, tokens, cfg, precision)
    logits, targets = logits[:, :-1], tokens[:, 1:]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + balance
