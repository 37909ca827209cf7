"""Plain reference: the Laguna-XS.2 decoder, as one chip of an expert-parallel
deployment holds it.

Written from the model's public ``config.json``
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json) in straightforward
``jax.numpy``, float32: one Python loop over the layers and a loop over the experts held,
every held expert computed for every token and weighted by its gate (zero where the
token did not choose it): no sort, no grouped product, no kernel. It imports nothing of
``tpu_resiliency``; ``precision`` is ``reference/model.py``'s (``"f32"``, ``"bf16"``,
and the control's ``"fp8"``).

Layer ``l`` (pre-norm residual, RMSNorm): attention of kind ``layer_types[l]`` with
``num_attention_heads_per_layer[l]`` query heads over ``num_key_value_heads`` KV heads;
rotary positions by ``rope_parameters[kind]`` (YaRN frequencies and attention factor on
full layers, on the first ``partial_rotary_factor`` of each head); causal softmax
attention, on sliding layers over the last ``sliding_window`` keys; an output gate, one
sigmoid a head (``gating``); then the MLP of kind ``mlp_layer_types[l]``: a SwiGLU, or a
sigmoid router over all experts, the ``num_experts_per_tok`` largest scores normalised
to one and scaled by ``moe_routed_scaling_factor``, the routed experts plus one shared
expert. Embedding and head are not tied.

**The share.** ``num_experts`` counts the experts held here, ``deployment.experts_held``
says which of the ``deployment.num_experts`` the router scores; a token's choices that
fall on experts held elsewhere add nothing (they are another chip's part of the sum).
``vocab_size`` is the slice of the vocabulary held here: ids, logits and loss are over
the slice.

**Memory and compile time, not mathematics:** attention goes by query blocks of
``QUERY_BLOCK`` rows, each against all the keys under its rows of the mask, and each
block, expert and layer is recomputed in the backward pass (``jax.checkpoint``), so that
8,192 tokens at the published widths fit one chip beside the float32 weights and their
gradient. The loops over the blocks and over the experts held (eight a pass) are ``lax``
loops, one compiled body each, run in turn: unrolled in Python the 128 experts and 80
blocks took nine minutes to compile.

The parameter tree is the one ``describe`` lists, with the program's leaf paths:
``embed``, ``final_norm``, ``lm_head``, ``attn/<full|sliding>/<leaf>`` and
``mlp/<dense|sparse>/<leaf>``, the layers of one kind stacked on a leading axis in the
order they appear. Weights: normal / sqrt(fan_in), norms at one, one PRNG key a leaf,
split from ``PRNGKey(seed)`` in the order the tree flattens (sorted keys).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.model import _round, matmul, rms_norm, swiglu

QUERY_BLOCK = 256
#: experts a pass of the loop over the experts held computes (the loop keeps its running
#: sum once a pass for the backward pass: 67 MB each at 8,192 tokens)
EXPERT_CHUNK = 8
KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def layers_of(cfg: dict) -> list[tuple[str, int, str]]:
    """(attention kind, query heads, MLP kind) of each layer that runs: the first
    ``num_hidden_layers`` entries of the three published lists."""
    n = cfg["num_hidden_layers"]
    return [(KINDS[a], h, m) for a, h, m in zip(
        cfg["layer_types"][:n], cfg["num_attention_heads_per_layer"][:n],
        cfg["mlp_layer_types"][:n])]


def describe(cfg: dict) -> dict:
    """{path: (shape, fan_in or None for a norm)} as a nested dict."""
    d, dh, hkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    layers = layers_of(cfg)
    tree = {"embed": ((cfg["vocab_size"], d), d), "final_norm": ((d,), None),
            "lm_head": ((d, cfg["vocab_size"]), d), "attn": {}, "mlp": {}}
    for kind in ("full", "sliding"):
        heads = [h for a, h, _ in layers if a == kind]
        if not heads:
            continue
        n, h = len(heads), heads[0]
        tree["attn"][kind] = {
            "attn_norm": ((n, d), None), "wq": ((n, d, h * dh), d),
            "wk": ((n, d, hkv * dh), d), "wv": ((n, d, hkv * dh), d),
            "wg": ((n, d, h), d), "wo": ((n, h * dh, d), h * dh)}
    n = sum(1 for *_, m in layers if m == "dense")
    if n:
        f = cfg["intermediate_size"]
        tree["mlp"]["dense"] = {
            "mlp_norm": ((n, d), None), "w_gate": ((n, d, f), d),
            "w_up": ((n, d, f), d), "w_down": ((n, f, d), f)}
    n = sum(1 for *_, m in layers if m == "sparse")
    if n:
        e, f, fs = (cfg["num_experts"], cfg["moe_intermediate_size"],
                    cfg["shared_expert_intermediate_size"])
        tree["mlp"]["sparse"] = {
            "mlp_norm": ((n, d), None),
            "w_router": ((n, d, cfg["deployment"]["num_experts"]), d),
            "we_gate": ((n, e, d, f), d), "we_up": ((n, e, d, f), d),
            "we_down": ((n, e, f, d), f),
            "ws_gate": ((n, d, fs), d), "ws_up": ((n, d, fs), d), "ws_down": ((n, fs, d), fs)}
    return tree


def init_params(seed: int, cfg: dict) -> dict:
    leaves, treedef = jax.tree_util.tree_flatten(
        describe(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        jnp.ones(shape, jnp.float32) if fan_in is None
        else jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)
        for key, (shape, fan_in) in zip(keys, leaves)])


def rotary(x, rope: dict):
    """Rotary positions, half-split ("rotate_half") convention, on the first
    ``partial_rotary_factor`` of each head's dimensions. x: [B, T, H, dh]. With
    ``rope_type`` "yarn" (arXiv:2309.00071): frequencies below the dimension that
    turns ``beta_fast`` times in the original context stay, those above the one that
    turns ``beta_slow`` times are divided by ``factor``, a linear ramp between; cos and
    sin are multiplied by ``attention_factor``."""
    t, dh = x.shape[1], x.shape[-1]
    rot = int(dh * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    inv_freq = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if rope["rope_type"] == "yarn":
        original = rope["original_max_position_embeddings"]

        def dimension_turning(turns):
            return rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(dimension_turning(rope["beta_fast"])), 0)
        high = min(math.ceil(dimension_turning(rope["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        interpolated = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
        inv_freq = (inv_freq / rope["factor"]) * interpolated + inv_freq * (1.0 - interpolated)
        factor = rope["attention_factor"]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(x, lp: dict, cfg: dict, kind: str, heads: int, precision: str):
    b, t, _ = x.shape
    hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    rope = cfg["rope_parameters"][f"{kind}_attention"]
    h = rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = rotary(matmul(h, lp["wq"], precision).reshape(b, t, heads, dh), rope)
    k = rotary(matmul(h, lp["wk"], precision).reshape(b, t, hkv, dh), rope)
    v = matmul(h, lp["wv"], precision).reshape(b, t, hkv, dh)
    gate = jax.nn.sigmoid(matmul(h, lp["wg"], precision))  # [B, T, H]
    k = jnp.repeat(k, heads // hkv, axis=2)
    v = jnp.repeat(v, heads // hkv, axis=2)

    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allowed = j <= i
    if kind == "sliding":
        allowed &= j > i - cfg["sliding_window"]

    @jax.checkpoint
    def block(rows):
        q_rows, allowed_rows = rows  # [B, Q, H, dh], [Q, T]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q_rows, precision), _round(k, precision),
                            preferred_element_type=jnp.float32) / np.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(allowed_rows[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                          preferred_element_type=jnp.float32)

    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(block, (q.reshape(b, t // rows, rows, heads, dh).swapaxes(0, 1),
                              allowed.reshape(t // rows, rows, t)))
    out = out.swapaxes(0, 1).reshape(b, t, heads, dh) * gate[..., None]
    return matmul(out.reshape(b, t, heads * dh), lp["wo"], precision)


def sparse_mlp(h, lp: dict, cfg: dict, precision: str):
    """The held experts' part of the routed sum, plus the shared expert."""
    first = cfg["deployment"]["experts_held"][0]
    scores = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"], precision="highest"))
    top, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True) * cfg["moe_routed_scaling_factor"]
    y = swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], precision)

    @jax.checkpoint
    def add_experts(y, chunk):
        numbers, w_gate, w_up, w_down = chunk
        for i in range(numbers.shape[0]):
            gate = jnp.sum(jnp.where(chosen == first + numbers[i], top, 0.0), -1, keepdims=True)
            y = y + gate * swiglu(h, w_gate[i], w_up[i], w_down[i], precision)
        return y, None

    held = cfg["num_experts"]
    size = EXPERT_CHUNK if held % EXPERT_CHUNK == 0 else held
    chunks = jax.tree_util.tree_map(
        lambda w: w.reshape(held // size, size, *w.shape[1:]),
        (jnp.arange(held), lp["we_gate"], lp["we_up"], lp["we_down"]))
    return jax.lax.scan(add_experts, y, chunks)[0]


def forward(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """tokens [B, T] -> logits [B, T, V] float32 (V: the slice held here)."""
    x = params["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    seen = {"full": 0, "sliding": 0, "dense": 0, "sparse": 0}

    def layer(x, attn_lp, mlp_lp, kind, heads, mlp):
        x = x + attention(x, attn_lp, cfg, kind, heads, precision)
        h = rms_norm(x, mlp_lp["mlp_norm"], eps)
        if mlp == "dense":
            return x + swiglu(h, mlp_lp["w_gate"], mlp_lp["w_up"], mlp_lp["w_down"], precision)
        return x + sparse_mlp(h, mlp_lp, cfg, precision)

    for kind, heads, mlp in layers_of(cfg):
        attn_lp = {name: leaf[seen[kind]] for name, leaf in params["attn"][kind].items()}
        mlp_lp = {name: leaf[seen[mlp]] for name, leaf in params["mlp"][mlp].items()}
        seen[kind] += 1
        seen[mlp] += 1
        x = jax.checkpoint(layer, static_argnums=(3, 4, 5))(x, attn_lp, mlp_lp, kind, heads, mlp)
    return matmul(rms_norm(x, params["final_norm"], eps), params["lm_head"], precision)


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over [B, T-1] positions (no auxiliary loss: the
    configuration has no key for one)."""
    logits, targets = forward(params, tokens, cfg, precision)[:, :-1], tokens[:, 1:]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
