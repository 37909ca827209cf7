"""Plain reference: the Solar-Open2 decoder, as one chip of a deployment that holds the
heads and the vocabulary of a layer eight ways and its experts forty ways.

Written from the model's public ``config.json``
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json) and, for the linear
layers, from the published description their ``kda_*`` keys and ``linear_attn_config`` are
those of (Kimi Delta Attention: Kimi Linear, arXiv:2510.26692), in straightforward
``jax.numpy``, float32. It imports nothing of ``tpu_resiliency`` and shares no code with the
program's rule: the recurrence runs **token by token**, no chunk, no triangular system, no
Gram matrix. ``precision`` is ``reference/model.py``'s (``"f32"``, ``"bf16"``, and the
control's ``"fp8"``).

Layer ``l`` (pre-norm residual, RMSNorm of ``rms_norm_eps``, ``y`` the normed input) is a
softmax layer where ``l`` is in ``gqa_layers`` and a linear one elsewhere.

*Linear layer* (per head ``h``, ``dk = dv = linear_attn_config.head_dim``)::

    q~ = silu(conv(y W_q)), k~ = silu(conv(y W_k)), v = silu(conv(y W_v))
        conv(u)_t = sum_{i < taps} c[:, i] u_{t - (taps - 1) + i}   depthwise, causal, zeros before
    q_t = q~_t / |q~_t| / sqrt(dk), k_t = k~_t / |k~_t|     (|x| = sqrt(sum x^2 + 1e-6))
    g_t = -exp(A_h) softplus((y_t W_fa) W_fb + b_dt)         a number a key channel, <= 0
    beta_t = 2 sigmoid(y_t W_b)_h                             (kda_allow_neg_eigval)
    S'_t = Diag(exp(g_t)) S_{t-1};  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  S_0 = 0
    o_t = S_t^T q_t
    out_t = concat_h( rms_norm(o_t; w_o) sigmoid((y_t W_ga) W_gb)_h ) W_o

*Softmax layer*: grouped-query attention over the causal prefix with no rotary
(``use_rope false``), scale ``head_dim^-1/2``, times ``sigmoid(y W_g)`` a channel
(``use_gqa_gate``), then ``W_o``.

*MLP, every layer*: a sigmoid router over all experts, the ``num_experts_per_tok`` largest
scores normalised to one and scaled by ``routed_scaling_factor``, the routed experts plus one
shared expert.

**The share.** ``num_attention_heads``, ``num_key_value_heads`` and
``linear_attn_config.num_heads`` count the heads held here: ``W_q``, ``W_k``, ``W_v``, the
gates' up-projections, the convolutions, ``A``, ``b_dt`` and ``W_b`` are the held heads'
columns and ``W_o`` their rows, seeded for the sum over all ``deployment.num_attention_heads``
that the product here is a part of; what the other chips of the tensor-parallel group add
to the stream is not here. ``n_routed_experts`` counts the experts held,
``deployment.experts_held`` says which of the ``deployment.n_routed_experts`` the router
scores; ``vocab_size`` is the slice of the vocabulary held: ids, logits and loss are over it.

**A lower precision** rounds, beside every matrix product's operands, the state after each
token and the vectors that meet it: the configuration states float32 for the state, so below
it is the state in bfloat16 (or fp8), each rounded value behind an optimization barrier, or
the TPU's compiler carries the state through in float32 (``_round_kept``).

**Memory and compile time, not mathematics:** the scan over the positions is rematerialised
in blocks of ``TOKEN_BLOCK`` tokens (every ``S_t`` of a layer would be 4.3e9 B at 8,192
tokens), the softmax layer goes by query blocks of ``QUERY_BLOCK`` rows, and each block,
expert and layer is recomputed in the backward pass.

The parameter tree is the one ``describe`` lists, with the program's leaf paths. Weights:
normal / sqrt(fan_in), norms at one, ``a_log`` the log of a uniform draw in [1, 16],
``dt_bias`` the inverse softplus of a log-uniform draw in [0.001, 0.1]; one PRNG key a leaf,
split from ``PRNGKey(seed)`` in the order the tree flattens (sorted keys).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.model import FP8, FP8_MAX, _bf16, _round, matmul, rms_norm, swiglu

QUERY_BLOCK = 256
TOKEN_BLOCK = 64
#: experts a pass of the loop over the experts held computes
EXPERT_CHUNK = 8


def layer_kinds(cfg: dict) -> list[str]:
    return ["full" if i in cfg["gqa_layers"] else "delta" for i in range(cfg["num_hidden_layers"])]


def describe(cfg: dict) -> dict:
    """{path: (shape, how it is seeded)} as a nested dict: a fan-in, ``None`` for a norm,
    or the name of a seeding of its own."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ways = cfg["deployment"]["num_attention_heads"] // h
    kinds = layer_kinds(cfg)
    tree = {"embed": ((cfg["vocab_size"], d), d), "final_norm": ((d,), None),
            "lm_head": ((d, cfg["vocab_size"]), d), "attn": {}, "mlp": {}}
    n = kinds.count("full")
    if n:
        tree["attn"]["full"] = {
            "attn_norm": ((n, d), None), "wq": ((n, d, h * dh), d), "wk": ((n, d, hkv * dh), d),
            "wv": ((n, d, hkv * dh), d), "wg": ((n, d, h * dh), d),
            "wo": ((n, h * dh, d), ways * h * dh)}
    n = kinds.count("delta")
    if n:
        lin = cfg["linear_attn_config"]
        hl, dl, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
        c, r = hl * dl, dl
        tree["attn"]["delta"] = {
            "attn_norm": ((n, d), None), "wq": ((n, d, c), d), "wk": ((n, d, c), d),
            "wv": ((n, d, c), d), "conv_q": ((n, c, taps), taps), "conv_k": ((n, c, taps), taps),
            "conv_v": ((n, c, taps), taps), "wf_a": ((n, d, r), d), "wf_b": ((n, r, c), r),
            "a_log": ((n, hl), "decay_rate"), "dt_bias": ((n, c), "decay_step"),
            "wb": ((n, d, hl), d), "wg_a": ((n, d, r), d), "wg_b": ((n, r, c), r),
            "o_norm": ((n, dl), None), "wo": ((n, c, d), ways * c)}
    n, e, f = len(kinds), cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    tree["mlp"]["sparse"] = {
        "mlp_norm": ((n, d), None),
        "w_router": ((n, d, cfg["deployment"]["n_routed_experts"]), d),
        "we_gate": ((n, e, d, f), d), "we_up": ((n, e, d, f), d), "we_down": ((n, e, f, d), f),
        "ws_gate": ((n, d, fs), d), "ws_up": ((n, d, fs), d), "ws_down": ((n, fs, d), fs)}
    return tree


def _seeded(key, shape, how):
    if how is None:
        return jnp.ones(shape, jnp.float32)
    if how == "decay_rate":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if how == "decay_step":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(how)


def init_params(seed: int, cfg: dict) -> dict:
    leaves, treedef = jax.tree_util.tree_flatten(
        describe(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [_seeded(key, shape, how) for key, (shape, how) in zip(keys, leaves)])


def softmax_attention(y, lp: dict, cfg: dict, precision: str):
    """The held heads' part of a softmax layer's ``W_o`` product."""
    b, t, _ = y.shape
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = matmul(y, lp["wq"], precision).reshape(b, t, h, dh)
    k = jnp.repeat(matmul(y, lp["wk"], precision).reshape(b, t, hkv, dh), h // hkv, axis=2)
    v = jnp.repeat(matmul(y, lp["wv"], precision).reshape(b, t, hkv, dh), h // hkv, axis=2)
    gate = jax.nn.sigmoid(matmul(y, lp["wg"], precision))  # [B, T, H * dh]
    allowed = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def block(rows):
        q_rows, allowed_rows = rows  # [B, Q, H, dh], [Q, T]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q_rows, precision), _round(k, precision),
                            preferred_element_type=jnp.float32) / np.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(allowed_rows[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), _round(v, precision),
                          preferred_element_type=jnp.float32)

    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    out = jax.lax.map(block, (q.reshape(b, t // rows, rows, h, dh).swapaxes(0, 1),
                              allowed.reshape(t // rows, rows, t)))
    out = out.swapaxes(0, 1).reshape(b, t, h * dh) * gate
    return matmul(out, lp["wo"], precision)


def short_conv(u, c):
    """u [B, T, channels], c [channels, taps]: four shifted adds."""
    taps, t = c.shape[1], u.shape[1]
    out = jnp.zeros_like(u)
    for i in range(taps):
        back = taps - 1 - i  # tap i reads the token `back` positions earlier
        out = out + c[:, i] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return out


@jax.custom_vjp
def _fp8_kept(x):
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.optimization_barrier((x * scale).astype(FP8)).astype(jnp.float32) / scale


_fp8_kept.defvjp(lambda x: (_fp8_kept(x), None), lambda _, g: (_bf16(g),))


def _round_kept(x, precision: str):
    """``reference/model.py:_round`` for a value that no matrix product takes, with the
    rounded value behind an optimization barrier in its own type. Without one the TPU's
    compiler carries an elementwise chain through a convert there and back in float32
    (XLA's excess precision): on the chip a state "rounded" to fp8 after each token stood
    2.5e-7 of its norm from the float32 state, and one rounded to bfloat16 no further than
    the products' default precision puts it (chip run, PR 39, 8,192 tokens, 8 heads of
    128 x 128). On the CPU the values are ``_round``'s."""
    if precision == "bf16":
        return jax.lax.optimization_barrier(x.astype(jnp.bfloat16)).astype(jnp.float32)
    if precision == "fp8":
        return _fp8_kept(x)
    return _round(x, precision)


def recurrence(q, k, v, g, beta, precision: str):
    """The rule, one token at a time: q, k, g ``[B, T, H, dk]``, v ``[B, T, H, dv]``, beta
    ``[B, T, H]`` -> (o ``[B, T, H, dv]``, the state after the last token)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    low = lambda x: _round_kept(x, precision)  # noqa: E731

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x  # [B, H, dk] ..., beta [B, H]
        state = jnp.exp(g_t)[..., None] * state
        held = jnp.einsum("bhkv,bhk->bhv", state, low(k_t))  # what the state holds for k_t
        write = beta_t[..., None] * (low(v_t) - held)
        state = low(state + low(k_t)[..., None] * write[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, low(q_t))

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    size = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(t // size, size, *x.shape[:1], *x.shape[2:])
               for x in (q, k, v, g, beta))
    state, out = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out.reshape(t, b, h, dv), 0, 1), state


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_attention(y, lp: dict, cfg: dict, precision: str):
    """The held heads' part of a linear layer's ``W_o`` product, and the state it ends in."""
    b, t, _ = y.shape
    lin = cfg["linear_attn_config"]
    h, dh = lin["num_heads"], lin["head_dim"]
    q, k, v = (jax.nn.silu(short_conv(matmul(y, lp[w], precision), lp[c])).reshape(b, t, h, dh)
               for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k = unit(q) / np.sqrt(dh), unit(k)
    through = lambda a, b_: matmul(matmul(y, lp[a], precision), lp[b_], precision)  # noqa: E731
    step = jax.nn.softplus(through("wf_a", "wf_b") + lp["dt_bias"]).reshape(b, t, h, dh)
    g = -jnp.exp(lp["a_log"])[:, None] * step
    beta = 2.0 * jax.nn.sigmoid(matmul(y, lp["wb"], precision))
    gate = jax.nn.sigmoid(through("wg_a", "wg_b")).reshape(b, t, h, dh)
    o, state = recurrence(q, k, v, g, beta, precision)
    o = rms_norm(o, lp["o_norm"], cfg["rms_norm_eps"]) * gate
    return matmul(o.reshape(b, t, h * dh), lp["wo"], precision), state


def sparse_mlp(y, lp: dict, cfg: dict, precision: str):
    """The held experts' part of the routed sum, plus the shared expert."""
    first = cfg["deployment"]["experts_held"][0]
    scores = jax.nn.sigmoid(jnp.matmul(y, lp["w_router"], precision="highest"))
    top, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    out = swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"], precision)

    @jax.checkpoint
    def add_experts(out, chunk):
        numbers, w_gate, w_up, w_down = chunk
        for i in range(numbers.shape[0]):
            gate = jnp.sum(jnp.where(chosen == first + numbers[i], top, 0.0), -1, keepdims=True)
            out = out + gate * swiglu(y, w_gate[i], w_up[i], w_down[i], precision)
        return out, None

    held = cfg["n_routed_experts"]
    size = EXPERT_CHUNK if held % EXPERT_CHUNK == 0 else held
    chunks = jax.tree_util.tree_map(
        lambda w: w.reshape(held // size, size, *w.shape[1:]),
        (jnp.arange(held), lp["we_gate"], lp["we_up"], lp["we_down"]))
    return jax.lax.scan(add_experts, out, chunks)[0]


def forward(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """tokens [B, T] -> logits [B, T, V] float32 (V: the slice held here)."""
    x = params["embed"][tokens]
    eps = cfg["rms_norm_eps"]
    seen = {"full": 0, "delta": 0}

    def layer(x, attn_lp, mlp_lp, kind):
        y = rms_norm(x, attn_lp["attn_norm"], eps)
        if kind == "full":
            x = x + softmax_attention(y, attn_lp, cfg, precision)
        else:
            x = x + delta_attention(y, attn_lp, cfg, precision)[0]
        return x + sparse_mlp(rms_norm(x, mlp_lp["mlp_norm"], eps), mlp_lp, cfg, precision)

    for i, kind in enumerate(layer_kinds(cfg)):
        attn_lp = {name: leaf[seen[kind]] for name, leaf in params["attn"][kind].items()}
        mlp_lp = {name: leaf[i] for name, leaf in params["mlp"]["sparse"].items()}
        seen[kind] += 1
        x = jax.checkpoint(layer, static_argnums=(3,))(x, attn_lp, mlp_lp, kind)
    return matmul(rms_norm(x, params["final_norm"], eps), params["lm_head"], precision)


def loss(params: dict, tokens, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over [B, T-1] positions (no auxiliary loss: the
    configuration has no key for one)."""
    logits, targets = forward(params, tokens, cfg, precision)[:, :-1], tokens[:, 1:]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
