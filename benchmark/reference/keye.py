"""Plain reference: the language decoder of Keye-VL-2.0-30B-A3B, as one chip of an
expert-parallel deployment holds it.

Written from the model's public ``config.json``
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json: a
Qwen3-MoE-style block whose attention reads the keys a DeepSeek-Sparse-Attention indexer
selects, ``sa_config``) and from the sparse-training stage of that attention as its
report states it, in straightforward ``jax.numpy``, float32: one loop over the layers,
the index scores of every key for every query, a sort of each query's scores, a
mask, a softmax over the masked row, a loop over the experts held with every held expert
computed for every token: no bisection, no grouped product, no kernel. It imports nothing
of ``tpu_resiliency``; ``precision`` is ``reference/model.py``'s (``"f32"``, ``"bf16"``,
and the control's ``"fp8"``).

Layer ``l`` (pre-norm residual, RMSNorm with ``rms_norm_eps``), input ``x [T, D]``,
positions ``t = 0 .. T-1``, text only:

1. ``y = rms_norm(x)``. ``q = y W_q`` (``num_attention_heads`` x ``head_dim``), ``k = y
   W_k``, ``v = y W_v`` (``num_key_value_heads`` x ``head_dim``), no bias. Each head's
   ``q`` and ``k`` are normed over their ``head_dim`` dimensions (weights ``q_norm``,
   ``k_norm``, one vector each for all heads), then turned by the rotary positions over
   the whole head (``rope_theta``, half-split pairing).
2. The indexer, from ``stop_gradient(y)``: ``qI = y W_qI`` (``indexer_num_heads`` x
   ``indexer_head_dim``), ``kI = rms_norm(y W_kI)`` (one key of ``indexer_head_dim`` a
   token for all heads, weight ``k_index_norm``), ``wI = y W_wI`` (one number a head); the
   rotary over all dimensions of ``qI`` and ``kI``. The index score of query ``t`` for
   key ``s <= t``: ``I[t, s] = sum_j wI[t, j] J^-1/2 dI^-1/2 relu(qI[t, j] . kI[s])``.
3. ``S_t``: the ``topk`` keys ``s <= t`` of largest ``I[t, s]`` (all of them while ``t <
   topk``), equal scores to the lower ``s``: each row sorted by (score descending, position
   ascending), and the ``topk``-th entry of that order is the last one taken.
4. For head ``h`` with KV head ``h // (heads / KV heads)``: ``P[t, h, .] = softmax over
   S_t of q[t, h] . k[s] / sqrt(head_dim)``, ``o[t, h] = sum_s P[t, h, s] v[s]``;
   ``x <- x + concat(o) W_o``. No gate.
5. ``y2 = rms_norm(x)``; ``p = softmax(y2 W_r)`` over all experts of the deployment; the
   ``num_experts_per_tok`` largest; weights ``p_e / sum of the chosen``
   (``norm_topk_prob``); ``x <- x + sum over the chosen experts held here of weight_e x
   SwiGLU_e(y2)``. No shared expert, no bias, no auxiliary balance loss.
6. ``L_I = mean over t of KL(p_t || softmax over S_t of I[t, .])`` with ``p_t[s] = mean
   over heads of P[t, h, s]`` under ``stop_gradient``. The loss is the mean next-token
   cross-entropy over the slice of the vocabulary plus the mean over the layers of
   ``L_I``: the indexer's four leaves get their gradient from ``L_I`` alone and every
   other leaf from the cross-entropy alone.

**Choices.** Steps 3 and 5 choose, and near a tie a rounding decides the choice: another
arithmetic on the same weights then reads other keys and runs other experts for a few
tokens in a hundred, which moves every number downstream by far more than the rounding
did, so a comparison of the two no longer tells a lower precision from a sound run. The
comparison that decides ``correct`` is therefore made on the same choices on both sides.
``loss`` takes them from ``cfg["choices"]`` where the caller put a function there
(``choices(params, tokens) -> {"selected": [layers, B, T, T] bool, "experts": [layers, B,
T, num_experts_per_tok] int32}``: the program under test says what it chose on these
weights, ``families/keye.py:program_config``), still makes its own by steps 3 and 5, and
returns a loss that is not a number if more than ``STRAYED`` of a layer's given keys or
experts are not among its own (a rounding flips a few in a hundred; a wrong rule nearly
all). With no such function the choices are the float32 reference's own: a ``precision``
below float32 (the control of ``correct``) first runs a float32 pass for them. Nothing
differentiable passes through a choice either way.

**The share.** ``num_experts`` (and ``num_local_experts``, the same count under its second
key) counts the experts held here, ``deployment.experts_held`` says which of the
``deployment.num_experts`` the router scores; a token's choices that fall on experts held
elsewhere add nothing. ``vocab_size`` is the slice of the vocabulary held here.

**Memory and compile time, not mathematics:** a layer first makes its mask by query
blocks of ``QUERY_BLOCK`` rows (index scores, the sort), then attention and the
divergence by the same blocks against all the keys; each block, expert and layer is
recomputed in the backward pass (``jax.checkpoint``) but for the mask, which the layer
keeps (one byte a pair: 67 MB a layer at 8,192 tokens) so that no row is sorted twice.
The loops over layers, blocks and experts are ``lax`` loops, one compiled body each.

The parameter tree is the one ``describe`` lists, with the program's leaf paths:
``embed``, ``final_norm``, ``lm_head``, ``attn/indexed/<leaf>`` and ``mlp/sparse/<leaf>``,
the layers stacked on a leading axis. Weights: normal / sqrt(fan_in), norms at one, one
PRNG key a leaf, split from ``PRNGKey(seed)`` in the order the tree flattens (sorted
keys).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from benchmark.reference.model import _round, matmul, rms_norm, swiglu

QUERY_BLOCK = 256

#: the share of a layer's given keys or experts that may be missing from the reference's
#: own before the loss is not a number
STRAYED = 0.2


def describe(cfg: dict) -> dict:
    """{path: (shape, fan_in or None for a norm)} as a nested dict."""
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    sa, n = cfg["sa_config"], cfg["num_hidden_layers"]
    heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f, all_experts = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["deployment"]["num_experts"]
    return {
        "embed": ((cfg["vocab_size"], d), d), "final_norm": ((d,), None),
        "lm_head": ((d, cfg["vocab_size"]), d),
        "attn": {"indexed": {
            "attn_norm": ((n, d), None), "wq": ((n, d, h * dh), d), "wk": ((n, d, hkv * dh), d),
            "wv": ((n, d, hkv * dh), d), "wo": ((n, h * dh, d), h * dh),
            "q_norm": ((n, dh), None), "k_norm": ((n, dh), None),
            "wq_index": ((n, d, heads * di), d), "wk_index": ((n, d, di), d),
            "ww_index": ((n, d, heads), d), "k_index_norm": ((n, di), None)}},
        "mlp": {"sparse": {
            "mlp_norm": ((n, d), None), "w_router": ((n, d, all_experts), d),
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


def rotary(x, theta: float):
    """Rotary positions on every dimension of x ``[B, T, H, dr]``, half-split
    ("rotate_half") pairing: dimension ``i`` turns with dimension ``i + dr/2``."""
    t, dr = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def index_rows(q_rows, w_rows, keys, precision: str):
    """q_rows ``[B, Q, J, dI]``, w_rows ``[B, Q, J]`` (scaled), keys ``[B, T, dI]`` ->
    ``I [B, Q, T]``: each head's products, the ReLU, the weighted sum over the heads."""
    dots = jnp.einsum("bqjd,bkd->bqjk", _round(q_rows, precision), _round(keys, precision),
                      preferred_element_type=jnp.float32)
    return jnp.sum(w_rows[..., None] * jax.nn.relu(dots), axis=2)


def selected(scores, allowed, topk: int):
    """The mask of ``S_t`` for rows of index scores ``[B, Q, T]`` under ``allowed [Q, T]``
    (``s <= t``): a sort of each row by (score descending, position ascending); the
    ``topk``-th entry of the order is the last taken."""
    t = scores.shape[-1]
    if topk >= t:
        return jnp.broadcast_to(allowed, scores.shape)
    masked = jnp.where(allowed, scores, -jnp.inf)
    position = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), scores.shape)
    descending, order = jax.lax.sort((-masked, position), dimension=-1, num_keys=2)
    least, last = -descending[..., topk - 1: topk], order[..., topk - 1: topk]
    return allowed & ((masked > least) | ((masked == least) & (position <= last)))


def attention(x, lp: dict, cfg: dict, precision: str, given=None, check: bool = True):
    """Steps 1-4 and 6 of a layer: (what attention adds to the stream ``[B, T, D]``, the
    layer's ``L_I``, the mask ``[B, T, T]`` it ran under, the share of that mask's keys
    that step 3 would not have selected). ``given``: a mask to run under in place of step
    3's own (which is made all the same if ``check``)."""
    b, t, _ = x.shape
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    y = rms_norm(x, lp["attn_norm"], eps)
    q = matmul(y, lp["wq"], precision).reshape(b, t, h, dh)
    k = matmul(y, lp["wk"], precision).reshape(b, t, hkv, dh)
    v = matmul(y, lp["wv"], precision).reshape(b, t, hkv, dh)
    q = rotary(rms_norm(q, lp["q_norm"], eps), theta)
    k = rotary(rms_norm(k, lp["k_norm"], eps), theta)
    # the long way: every query head gets its KV head's keys and values, repeated
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)

    detached = jax.lax.stop_gradient(y)
    qi = rotary(matmul(detached, lp["wq_index"], precision).reshape(b, t, heads, di), theta)
    ki = rms_norm(matmul(detached, lp["wk_index"], precision), lp["k_index_norm"], eps)
    ki = rotary(ki[:, :, None], theta)[:, :, 0]
    wi = matmul(detached, lp["ww_index"], precision) * heads ** -0.5 * di ** -0.5
    allowed = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = lambda a: a.reshape(b, t // rows, rows, *a.shape[2:]).swapaxes(0, 1)  # noqa: E731
    allowed_blocks = allowed.reshape(t // rows, rows, t)

    def select(block):
        qi_rows, wi_rows, allowed_rows = block
        return selected(index_rows(qi_rows, wi_rows, ki, precision), allowed_rows, topk)

    strayed = jnp.float32(0.0)
    if given is None or check:
        mask = jax.lax.map(select, jax.lax.stop_gradient(
            (blocks(qi), blocks(wi), allowed_blocks)))  # [blocks, B, Q, T]
    if given is not None:
        given = blocks(given & allowed)
        if check:
            strayed = jnp.sum(given & ~mask) / jnp.sum(given)
        mask = given
    mask = checkpoint_name(mask, "mask")

    @jax.checkpoint
    def block(rows):
        q_rows, qi_rows, wi_rows, mask_rows = rows
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q_rows, precision), _round(k, precision),
                            preferred_element_type=jnp.float32) / np.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(mask_rows[:, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                         preferred_element_type=jnp.float32)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))  # p_t [B, Q, T]
        index = index_rows(qi_rows, wi_rows, ki, precision)
        log_index = jax.nn.log_softmax(jnp.where(mask_rows, index, -1e30), axis=-1)
        seen = mask_rows & (target > 0)
        log_target = jnp.log(jnp.where(seen, target, 1.0))
        divergence = jnp.sum(jnp.where(seen, target * (log_target - log_index), 0.0), axis=-1)
        return out, divergence

    out, divergence = jax.lax.map(block, (blocks(q), blocks(qi), blocks(wi), mask))
    out = out.swapaxes(0, 1).reshape(b, t, h * dh)
    return (matmul(out, lp["wo"], precision), jnp.mean(divergence),
            mask.swapaxes(0, 1).reshape(b, t, t), strayed)


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


def forward(params: dict, tokens, cfg: dict, precision: str = "f32", given=None,
            check: bool = True):
    """tokens [B, T] -> (logits [B, T, V] float32 (V: the slice held here), the mean over
    the layers of ``L_I``, the choices made or taken ``{"selected": [layers, B, T, T] bool,
    "experts": [layers, B, T, num_experts_per_tok] int32}``, the largest share of a
    layer's ``given`` choices that are not its own). ``given``: choices to take (see
    Choices above); ``check``: still make the own ones, to count that share."""
    x = params["embed"][tokens]
    eps = cfg["rms_norm_eps"]

    def layer(x, attn_lp, mlp_lp, selected, experts):
        added, divergence, selected, strayed_keys = attention(
            x, attn_lp, cfg, precision, selected, check)
        x = x + added
        routed, experts, strayed_experts = sparse_mlp(
            rms_norm(x, mlp_lp["mlp_norm"], eps), mlp_lp, cfg, precision, experts)
        return x + routed, divergence, selected, experts, jnp.maximum(strayed_keys, strayed_experts)

    keep_mask = jax.checkpoint_policies.save_only_these_names("mask")
    kept_layer = jax.checkpoint(layer, policy=keep_mask)
    taken = (None, None) if given is None else (given["selected"], given["experts"])
    x, (divergence, selected, experts, strayed) = jax.lax.scan(
        lambda x, leaves: (lambda x, *row: (x, row))(*kept_layer(x, *leaves)), x,
        (params["attn"]["indexed"], params["mlp"]["sparse"], *taken))
    logits = matmul(rms_norm(x, params["final_norm"], eps), params["lm_head"], precision)
    return (logits, jnp.mean(divergence), {"selected": selected, "experts": experts},
            jnp.max(strayed))


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over [B, T-1] positions plus the mean over the layers
    of the indexers' ``L_I`` (coefficient 1: the two share no leaf), on the choices the
    caller gives or the float32 reference's own (see Choices above)."""
    given, check = None, False
    if cfg.get("choices") is not None:
        given, check = jax.lax.stop_gradient(cfg["choices"](params, tokens)), True
    elif precision != "f32":
        with jax.default_matmul_precision("highest"):
            given = forward(jax.lax.stop_gradient(params), tokens, cfg, "f32")[2]
    logits, divergence, _, strayed = forward(params, tokens, cfg, precision, given, check)
    logits, targets = logits[:, :-1], tokens[:, 1:]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.where(strayed > STRAYED, jnp.nan, jnp.mean(nll) + divergence)
