"""Plain reference: SDAR-30B-A3B-Chat trained by block diffusion, as one chip of an
expert-parallel deployment holds it.

Written from the model's public ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json: a Qwen3-MoE block,
``model_type`` ``sdar_moe``) and from the vectorised training of block diffusion that SDAR
("SDAR: A Synergistic Diffusion-AutoRegression Paradigm for Scalable Sequence Generation")
adopts from BD3-LM (arXiv:2503.09573), in straightforward ``jax.numpy``, float32: the
``[2L, 2L]`` mask built whole from ``//`` and comparisons, a softmax over the masked row, a
loop over the experts held with every held expert computed for every position: no tile
walk, no grouped product, no kernel. It imports nothing of ``tpu_resiliency``;
``precision`` is ``reference/model.py``'s (``"f32"``, ``"bf16"``, and the control's
``"fp8"``).

**The step.** A sequence ``x0`` of ``L`` ids is cut into blocks of ``block =
diffusion.block_length`` consecutive positions, ``b(i) = i // block``.

1. The draws, a function of the sequence's own ids: ``key = fold_in(jax.random.key(
   diffusion.noise_seed, impl="threefry2x32"), sum of the sequence's ids as uint32)``;
   ``key_b, key_i = jax.random.split(key)``; ``u_b = uniform(key_b, [ceil(L / block)],
   float32)``, ``u_i = uniform(key_i, [L], float32)``; the level of block ``b`` is ``t_b =
   eps + (1 - eps) u_b`` (``eps = diffusion.eps``); position ``i`` is masked where ``u_i <
   t_b(i)``; ``xt = where(masked, MASK, x0)`` with ``MASK = diffusion.mask_token_id``, the
   last row of the slice of the vocabulary held.
2. The stack runs once on the ``2 L`` positions ``[x0 ; xt]``, both halves at the rotary
   positions ``0 .. L - 1``. A clean query ``i`` reads the clean keys ``j`` with ``b(j) <=
   b(i)``; a noised query ``i`` reads the clean keys with ``b(j) < b(i)`` and the noised
   keys with ``b(j) == b(i)``; nothing else is read (:func:`stream_mask`).
3. The final norm and the head on the noised half only; ``loss = sum_i masked_i / t_b(i) x
   NLL(logits_i, x0_i) / (B L)``: position ``i``'s logits predict token ``i`` (no shift).
   Whether a position counts is the draw's ``masked_i``, never ``xt_i == MASK``.

**Layer** ``l`` (pre-norm residual, RMSNorm with ``rms_norm_eps``), input ``x [2L, D]``:

1. ``y = rms_norm(x)``. ``q = y W_q`` (``num_attention_heads`` x ``head_dim``), ``k = y
   W_k``, ``v = y W_v`` (``num_key_value_heads`` x ``head_dim``), no bias. Each head's
   ``q`` and ``k`` are normed over their ``head_dim`` dimensions (weights ``q_norm``,
   ``k_norm``, one vector each for all heads), then turned by the rotary positions over
   the whole head (``rope_theta``, half-split pairing), scale ``head_dim^-0.5``, a softmax
   over the keys the mask allows, no output gate; ``x <- x + concat(o) W_o``.
2. ``y2 = rms_norm(x)``; ``p = softmax(y2 W_r)`` in float32 over all experts of the
   deployment; the ``num_experts_per_tok`` largest; weights ``p_e / sum of the chosen``
   (``norm_topk_prob``); ``x <- x + sum over the chosen experts held here of weight_e x
   SwiGLU_e(y2)``. No shared expert, no bias, no auxiliary balance loss.

**Choices.** Step 2 of a layer chooses, and near a tie a rounding decides the choice; the
comparison that decides ``correct`` is made on the same experts on both sides, as
``reference/keye.py`` sets out: ``loss`` takes them from ``cfg["choices"]`` where the
caller put a function there (``choices(params, tokens) -> {"experts": [layers, B, 2L,
num_experts_per_tok] int32}`` over the positions of the stream,
``families/sdar.py:program_config``), still makes its own, and returns a loss that is not
a number if more than ``STRAYED`` of a layer's given experts are not among its own. With
no such function the choices are the float32 reference's own: a ``precision`` below
float32 (the control of ``correct``) first runs a float32 pass for them.

**The share.** ``num_experts`` counts the experts held here, ``deployment.experts_held``
says which of the ``deployment.num_experts`` the router scores; a position's choices that
fall on experts held elsewhere add nothing. ``vocab_size`` is the slice of the vocabulary
held here, ``MASK`` among its rows.

**Memory and compile time, not mathematics:** attention goes by query blocks of
``QUERY_BLOCK`` rows against all ``2L`` keys under the blocks' rows of the mask; each
block, expert and layer is recomputed in the backward pass (``jax.checkpoint``). The loops
over layers, blocks and experts are ``lax`` loops, one compiled body each.

The parameter tree is the one ``describe`` lists, with the program's leaf paths:
``embed``, ``final_norm``, ``lm_head``, ``attn/full/<leaf>`` and ``mlp/sparse/<leaf>``, the
layers stacked on a leading axis. Weights: normal / sqrt(fan_in), norms at one, one PRNG
key a leaf, split from ``PRNGKey(seed)`` in the order the tree flattens (sorted keys).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.model import _round, matmul, rms_norm, swiglu

QUERY_BLOCK = 256

#: the share of a layer's given experts that may be missing from the reference's own
#: before the loss is not a number
STRAYED = 0.2


def describe(cfg: dict) -> dict:
    """{path: (shape, fan_in or None for a norm)} as a nested dict."""
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    n, e, f = cfg["num_hidden_layers"], cfg["num_experts"], cfg["moe_intermediate_size"]
    return {
        "embed": ((cfg["vocab_size"], d), d), "final_norm": ((d,), None),
        "lm_head": ((d, cfg["vocab_size"]), d),
        "attn": {"full": {
            "attn_norm": ((n, d), None), "wq": ((n, d, h * dh), d), "wk": ((n, d, hkv * dh), d),
            "wv": ((n, d, hkv * dh), d), "wo": ((n, h * dh, d), h * dh),
            "q_norm": ((n, dh), None), "k_norm": ((n, dh), None)}},
        "mlp": {"sparse": {
            "mlp_norm": ((n, d), None),
            "w_router": ((n, d, cfg["deployment"]["num_experts"]), d),
            "we_gate": ((n, e, d, f), d), "we_up": ((n, e, d, f), d),
            "we_down": ((n, e, f, d), f)}},
    }


def init_params(seed: int, cfg: dict) -> dict:
    leaves, treedef = jax.tree_util.tree_flatten(
        describe(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def seeded(key, shape, fan_in):
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    return jax.tree_util.tree_unflatten(
        treedef, [seeded(key, *leaf) for key, leaf in zip(keys, leaves)])


def draws(tokens, cfg: dict):
    """Step 1 for tokens ``[B, L]``: (``xt [B, L]``, ``masked [B, L]`` bool, the level
    ``t_b(i)`` of every position ``[B, L]`` float32)."""
    noise, length = cfg["diffusion"], tokens.shape[1]
    block, eps = noise["block_length"], noise["eps"]
    base = jax.random.key(noise["noise_seed"], impl="threefry2x32")
    rows = []
    for ids in tokens:  # a key a sequence
        key = jax.random.fold_in(base, jnp.sum(ids.astype(jnp.uint32)))
        key_b, key_i = jax.random.split(key)
        u_b = jax.random.uniform(key_b, (-(-length // block),), jnp.float32)
        u_i = jax.random.uniform(key_i, (length,), jnp.float32)
        level = (eps + (1.0 - eps) * u_b)[jnp.arange(length) // block]
        rows.append((u_i < level, level))
    masked, level = (jnp.stack(x) for x in zip(*rows))
    return jnp.where(masked, noise["mask_token_id"], tokens), masked, level


def stream_mask(length: int, block: int):
    """``[2L, 2L]`` bool, query by key, over the stream ``[clean ; noised]``."""
    position = jnp.arange(2 * length)
    noised, of_block = position >= length, position % length // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_block, k_block = of_block[:, None], of_block[None, :]
    return ((~q_noised & ~k_noised & (k_block <= q_block))
            | (q_noised & ~k_noised & (k_block < q_block))
            | (q_noised & k_noised & (k_block == q_block)))


def rotary(x, theta: float, positions):
    """Rotary positions on every dimension of x ``[B, T, H, dr]`` at ``positions [T]``,
    half-split ("rotate_half") pairing: dimension ``i`` turns with dimension ``i + dr/2``."""
    dr = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, lp: dict, cfg: dict, precision: str, mask):
    """Step 1 of a layer: what attention adds to the stream ``[B, T, D]`` under ``mask [T,
    T]`` (``T = 2L``; the two halves at the same positions)."""
    b, t, _ = x.shape
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    positions = jnp.arange(t) % (t // 2)
    y = rms_norm(x, lp["attn_norm"], eps)
    q = matmul(y, lp["wq"], precision).reshape(b, t, h, dh)
    k = matmul(y, lp["wk"], precision).reshape(b, t, hkv, dh)
    v = matmul(y, lp["wv"], precision).reshape(b, t, hkv, dh)
    q = rotary(rms_norm(q, lp["q_norm"], eps), theta, positions)
    k = rotary(rms_norm(k, lp["k_norm"], eps), theta, positions)
    # the long way: every query head gets its KV head's keys and values, repeated
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)

    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = lambda a: a.reshape(b, t // rows, rows, *a.shape[2:]).swapaxes(0, 1)  # noqa: E731

    @jax.checkpoint
    def block(of_rows):
        q_rows, mask_rows = of_rows
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q_rows, precision), _round(k, precision),
                            preferred_element_type=jnp.float32) / np.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(mask_rows[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(block, (blocks(q), mask.reshape(t // rows, rows, t)))
    return matmul(out.swapaxes(0, 1).reshape(b, t, h * dh), lp["wo"], precision)


def sparse_mlp(y, lp: dict, cfg: dict, precision: str, given=None):
    """(The held experts' part of the routed sum: softmax over all experts of the
    deployment, the largest ``num_experts_per_tok``, renormalised; the experts chosen ``[B,
    T, num_experts_per_tok]``; the share of them that are not the largest). ``given``:
    experts to take in place of the largest."""
    first, held = cfg["deployment"]["experts_held"]
    scores = jax.nn.softmax(jnp.matmul(y, lp["w_router"], precision="highest"), axis=-1)
    top, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    strayed = jnp.float32(0.0)
    if given is not None:
        strayed = jnp.mean(jnp.all(given[..., :, None] != chosen[..., None, :], axis=-1))
        top, chosen = jnp.take_along_axis(scores, given, axis=-1), given
    top = top / jnp.sum(top, axis=-1, keepdims=True)

    @jax.checkpoint
    def add_expert(out, expert):
        number, w_gate, w_up, w_down = expert
        gate = jnp.sum(jnp.where(chosen == first + number, top, 0.0), -1, keepdims=True)
        return out + gate * swiglu(y, w_gate, w_up, w_down, precision), None

    out = jax.lax.scan(add_expert, jnp.zeros_like(y), (
        jnp.arange(held), lp["we_gate"], lp["we_up"], lp["we_down"]))[0]
    return out, chosen, strayed


def forward(params: dict, stream, cfg: dict, precision: str = "f32", given=None, mask=None):
    """The ids of the stream ``[B, 2L]`` (``[x0 ; xt]``) -> (logits ``[B, L, V]`` float32
    of the noised half (V: the slice held here), the experts chosen or taken ``[layers, B,
    2L, num_experts_per_tok]`` int32, the largest share of a layer's ``given`` experts that
    are not its own). ``given``: experts to take (see Choices above); ``mask``: another
    ``[2L, 2L]`` mask than step 2's (a test's: what a wrong mask reads)."""
    length = stream.shape[1] // 2
    if mask is None:
        mask = stream_mask(length, cfg["diffusion"]["block_length"])
    x = params["embed"][stream]
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def layer(x, attn_lp, mlp_lp, experts):
        x = x + attention(x, attn_lp, cfg, precision, mask)
        routed, experts, strayed = sparse_mlp(
            rms_norm(x, mlp_lp["mlp_norm"], eps), mlp_lp, cfg, precision, experts)
        return x + routed, experts, strayed

    def step(x, leaves):  # each layer with its row of ``given``
        attn_lp, mlp_lp, *taken = leaves
        x, chosen, strayed = layer(x, attn_lp, mlp_lp, taken[0] if taken else None)
        return x, (chosen, strayed)

    x, (experts, strayed) = jax.lax.scan(step, x, (
        params["attn"]["full"], params["mlp"]["sparse"], *(() if given is None else (given,))))
    logits = matmul(rms_norm(x[:, length:], params["final_norm"], eps), params["lm_head"],
                    precision)
    return logits, experts, jnp.max(strayed)


def weighted_nll(logits, tokens, masked, level):
    """Step 3: ``sum_i masked_i / t_b(i) x NLL(logits_i, x0_i) / (B L)``."""
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(masked, nll / level, 0.0)) / tokens.size


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """The block-diffusion loss of tokens ``[B, L]`` on the step's own draws, on the
    experts the caller gives or the float32 reference's own (see Choices above)."""
    noised, masked, level = draws(tokens, cfg)
    stream = jnp.concatenate([tokens, noised], axis=1)
    given = None
    if cfg.get("choices") is not None:
        given = jax.lax.stop_gradient(cfg["choices"](params, tokens))["experts"]
    elif precision != "f32":
        with jax.default_matmul_precision("highest"):
            given = forward(jax.lax.stop_gradient(params), stream, cfg, "f32")[1]
    logits, _, strayed = forward(params, stream, cfg, precision, given)
    return jnp.where(strayed > STRAYED, jnp.nan, weighted_nll(logits, tokens, masked, level))
