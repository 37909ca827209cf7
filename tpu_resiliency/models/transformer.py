"""Flagship model: a Llama-style decoder-only transformer, pure JAX, mesh-shardable.

The resiliency framework's exercise workload (the reference exercises NVRx against
NeMo/Lightning Llama-3 jobs, ``tests/ptl_resiliency/func/nemo20/``). Built TPU-first:

- parameters are a plain pytree with stacked layer weights, so the layer stack runs as
  one ``lax.scan`` (single trace/compile per layer body, MXU-sized matmuls),
- bfloat16 activations / float32 params + optimizer, RoPE, GQA, SwiGLU, RMSNorm,
- shardable over the canonical (dp, tp, sp) mesh via ``parallel/mesh.py`` specs; with
  ``sp > 1`` attention runs as ring attention over the sequence axis
  (``parallel/ring_attention.py``),
- no Python control flow on data inside jit; static shapes throughout.

The description (:class:`TransformerConfig`) also says how often the stack runs, which
norms a layer has, how the loss reads the passes and which path the attention products
take. The defaults are the plain decoder above (Mistral's: one pass, pre-norm, one exit,
eps 1e-5, plain attention), whose parameter tree and lowered step they leave as they were.

**A looped decoder** (``n_passes`` > 1, ``sandwich_norms``, ``exit_beta``: Ouro's LoopLM,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741). Tokens
``x_1..x_S``, ``h^(0) = E[x]``:

- a layer ``f_l``: ``a = h + N2_l(Attn_l(N1_l(h)))``, ``f_l(h) = a + N4_l(SwiGLU_l(N3_l(a)))``.
  ``N*`` are RMS norms with a weight vector each, eps ``norm_eps``, computed in float32
  (``N1 = attn_norm``, ``N2 = attn_post_norm``, ``N3 = mlp_norm``, ``N4 = mlp_post_norm``;
  without ``sandwich_norms`` there is no ``N2`` and no ``N4``). ``Attn``: ``q, k, v = y W_q,
  y W_k, y W_v``, no bias, rotary on every dimension of every head of ``q`` and ``k``
  (half-split, :func:`apply_rope`), causal softmax of ``q k^T / sqrt(head_dim)`` a head in
  float32, times ``v``, times ``W_o``. ``SwiGLU(y) = (silu(y W_gate) * (y W_up)) W_down``.
- a pass: ``F(h) = f_L(...f_1(h))``, one ``lax.scan`` over the stacked layers. The loop:
  for ``t = 1..n_passes``, ``h^(t) = N_f(F(h^(t-1)))`` on the same weights: the final norm
  closes every pass and the normed stream is what the next pass reads. A ``lax.scan`` over
  the passes around the one over the layers, so a leaf's gradient is the sum over its uses.
- exits (``exit_beta`` not ``None``): after every pass ``z^(t) = h^(t) W_head`` (float32
  logits), ``l_t[i] = logsumexp(z^(t)[i]) - z^(t)[i, x_{i+1}]``, and a gate ``lam_t[i] =
  sigmoid(h^(t)[i] . w_g + b_g)`` in float32, the same ``w_g, b_g`` at every pass. The exit
  distribution: ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for ``t < n_passes``, and the last
  pass takes what is left, ``prod_{j<n_passes} (1 - lam_j)`` (its own gate enters nothing).
  The loss is ``mean_i [sum_t p_t[i] l_t[i] - exit_beta H(p[i])]``, ``H(p) = -sum_t p_t log
  p_t``, over the ``S - 1`` positions with a target (:func:`mix_exits`). Each exit is a
  rematerialized unit of its own: it keeps its normed stream and yields ``[B, T]`` numbers,
  so the float32 logits of one exit live at a time. Without exits the loss is the last
  pass's mean NLL. (Stopping early at a threshold is inference's; no training path reads
  one.)
- **a layer keeps what its backward pass reads, as far as the device's memory goes**
  (:func:`kept_residuals`, the rule of ``models/pattern.py``): where everything a step
  keeps fits the device, nothing is rematerialized and the program is the plain one; else
  each layer of each pass runs under a ``jax.checkpoint`` that keeps its input and the
  named values that still fit, and recomputes the rest.
- ``attention="kernel"`` sends the products to the blocked kernels of ``ops/attention.py``
  where they apply (a TPU, heads of whole lane groups, a sequence of whole tiles:
  :func:`attention_path`); the default, ``"plain"``, holds the ``[B, H, T, T]`` scores.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from tpu_resiliency.ops import attention as kernels

PLAIN, KERNEL = "plain", "kernel"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1376
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    #: how often the stack runs on the same weights, the final norm closing every pass
    n_passes: int = 1
    #: an RMS norm on each sublayer's output before the residual takes it
    sandwich_norms: bool = False
    #: ``None``: one exit, after the last pass. A number: an exit through the head after
    #: every pass, a learned gate that mixes their losses, and this weight on the entropy
    #: of the exit distribution
    exit_beta: Optional[float] = None
    norm_eps: float = 1e-5
    #: the path of the attention products: ``"plain"``, or ``"kernel"`` for the blocked
    #: kernels of ``ops/attention.py`` where they apply (:func:`attention_path`)
    attention: str = PLAIN

    def __post_init__(self):
        if self.n_passes < 1:
            raise ValueError(f"n_passes {self.n_passes} is not a count of passes")
        if self.attention not in (PLAIN, KERNEL):
            raise ValueError(f"attention {self.attention!r} is not {PLAIN!r} or {KERNEL!r}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def tiny(**kw) -> "TransformerConfig":
        base = dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_looped(**kw) -> "TransformerConfig":
        """A looped decoder at tiny widths: three passes, sandwich norms, three exits."""
        return TransformerConfig.tiny(
            **{"n_passes": 3, "sandwich_norms": True, "exit_beta": 0.05, "norm_eps": 1e-6, **kw})


def init_params(rng: jax.Array, cfg: TransformerConfig, *, with_mlp: bool = True) -> dict:
    """Parameter pytree with layer weights stacked on a leading [L] axis.

    ``with_mlp=False`` skips the dense SwiGLU weights (the MoE family replaces
    them with expert stacks and must not materialize both). A description with
    sandwich norms has two more norms a layer, one with exits an ``exit_gate`` (its
    weights keyed by ``fold_in(rng, 3)``, its bias zero); the other leaves are seeded as
    they are without them."""
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    d, h, hkv, dh, f, L = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
    )

    def norm_init(*shape):
        return jnp.ones(shape, jnp.float32)

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in))

    ks = jax.random.split(k_layers, 7)
    layers = {
        "attn_norm": norm_init(L, d),
        "wq": dense_init(ks[0], (L, d, h * dh), d),
        "wk": dense_init(ks[1], (L, d, hkv * dh), d),
        "wv": dense_init(ks[2], (L, d, hkv * dh), d),
        "wo": dense_init(ks[3], (L, h * dh, d), h * dh),
        "mlp_norm": norm_init(L, d),
    }
    if with_mlp:
        layers["w_gate"] = dense_init(ks[4], (L, d, f), d)
        layers["w_up"] = dense_init(ks[5], (L, d, f), d)
        layers["w_down"] = dense_init(ks[6], (L, f, d), f)
    if cfg.sandwich_norms:
        layers["attn_post_norm"] = norm_init(L, d)
        if with_mlp:
            layers["mlp_post_norm"] = norm_init(L, d)
    params = {
        "embed": dense_init(k_embed, (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": norm_init(d),
        "lm_head": dense_init(k_head, (d, cfg.vocab_size), d),
    }
    if cfg.exit_beta is not None:
        params["exit_gate"] = {"w": dense_init(jax.random.fold_in(rng, 3), (d, 1), d),
                               "b": jnp.zeros((1,), jnp.float32)}
    return params


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def rope_tables(cfg: TransformerConfig, seq_len: int, offset: int = 0):
    dh = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dh, 2, jnp.float32) / dh))
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    angles = pos[:, None] * inv_freq[None, :]  # [T, dh/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, T, H, dh]; cos/sin: [T, dh/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _attention(q, k, v, causal_offset: int = 0):
    """Plain causal attention. q: [B, T, H, dh]; k/v: [B, T, Hkv, dh] with
    H % Hkv == 0 (GQA) — query heads are grouped per KV head in the einsum
    itself, so repeated K/V are never materialized in HBM."""
    dh = q.shape[-1]
    b, tq, h, _ = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    qg = q.reshape(b, tq, hkv, h // hkv, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) / np.sqrt(dh)
    qpos = jnp.arange(tq)[:, None] + causal_offset
    kpos = jnp.arange(tk)[None, :]
    mask = qpos >= kpos
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, tq, h, dh)


def attention_path(cfg: TransformerConfig, seq: int) -> dict:
    """Which path the attention products take at sequences of ``seq``, from what the code
    can see: ``{"path": "kernel", "tile": rows}`` for the blocked kernels of
    ``ops/attention.py`` where the description asks for them, the backend is a TPU and the
    shapes tile (heads of whole lane groups, a sequence of whole tiles); else ``{"path":
    "plain"}``, the ``[B, H, T, T]`` scores of :func:`_attention`."""
    if (cfg.attention == KERNEL and jax.default_backend() == "tpu"
            and kernels.applies(seq, cfg.head_dim, None)):
        return {"path": KERNEL, "tile": kernels.tile_of(seq, None)}
    return {"path": PLAIN}


def adapt_attn_fn(attn_fn, causal_offset: int = 0, kernel: bool = False):
    """Resolve the layer-level attention callable from a user override.

    The attention blocks hand ``attn_fn`` GQA-shaped tensors (q ``[B, T, H, dh]``,
    k/v ``[B, T, Hkv, dh]``). The default :func:`_attention` consumes those
    directly — grouped in the einsum, repeated K/V never hit HBM; with ``kernel`` (what
    :func:`attention_path` found for a description that asks for them) the default is
    ``ops/attention.py:blocked_attention``, which takes the same tensors. Custom fns
    (e.g. ring attention) keep their documented pre-repeated-full-heads
    contract, so they are wrapped with the repeat here, at the seam, where the
    repeat happens before any sharding decisions the custom fn makes.

    ``causal_offset`` only applies to the default dense attention; a custom fn
    owns its own position bookkeeping, so combining the two is rejected here
    rather than silently producing a mask anchored at 0. The kernels anchor theirs at 0
    too, so an offset takes the plain path."""
    if attn_fn is not None and causal_offset:
        raise ValueError(
            "position_offset is only applied to the default dense attention; "
            "a custom attn_fn must handle positions itself"
        )
    if attn_fn is None:
        if kernel and not causal_offset:
            return kernels.blocked_attention
        return functools.partial(_attention, causal_offset=causal_offset)

    def repeated(q, k, v):
        reps = q.shape[2] // k.shape[2]
        if reps > 1:
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
        return attn_fn(q, k, v)

    return repeated


def _named(x: jax.Array, name: str, remat: bool) -> jax.Array:
    """``x`` under a name of :data:`KEPT_GROUPS`, in a layer that is rematerialized. The
    plain program carries no names: each would be one more equation to lower, and the
    lowered step of a description that keeps everything is held to what it was."""
    return checkpoint_name(x, name) if remat else x


def _attn_block(cfg: TransformerConfig, x: jax.Array, lp: dict, cos, sin, attn_fn,
                remat: bool = False) -> jax.Array:
    """Pre-norm GQA attention with residual; shared by the dense and MoE layers. With
    sandwich norms the sublayer's output is normed before the residual takes it; ``remat``
    says that the layer runs under a ``jax.checkpoint`` that keeps values by name."""
    with jax.named_scope("attn/full"):
        b, t, d = x.shape
        h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (y @ lp["wq"].astype(y.dtype)).reshape(b, t, h, dh)
        k = (y @ lp["wk"].astype(y.dtype)).reshape(b, t, hkv, dh)
        v = (y @ lp["wv"].astype(y.dtype)).reshape(b, t, hkv, dh)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q, k, v = _named(q, "attn_q", remat), _named(k, "attn_k", remat), _named(v, "attn_v", remat)
        with jax.named_scope("core"):  # the kernels name their output and its log-sum-exp
            attn = attn_fn(q, k, v).reshape(b, t, h * dh)
        attn = _named(attn @ lp["wo"].astype(attn.dtype), "attn_proj", remat)
        if cfg.sandwich_norms:
            attn = rms_norm(attn, lp["attn_post_norm"], cfg.norm_eps)
        return x + attn


def _layer(cfg: TransformerConfig, x: jax.Array, lp: dict, cos, sin, attn_fn,
           remat: bool = False) -> jax.Array:
    x = _attn_block(cfg, x, lp, cos, sin, attn_fn, remat)

    # MLP block (SwiGLU)
    with jax.named_scope("mlp/dense"):
        y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(_named(y @ lp["w_gate"].astype(y.dtype), "dense_gate", remat))
        up = _named(y @ lp["w_up"].astype(y.dtype), "dense_up", remat)
        mlp = _named((gate * up) @ lp["w_down"].astype(y.dtype), "mlp_proj", remat)
        if cfg.sandwich_norms:
            mlp = rms_norm(mlp, lp["mlp_post_norm"], cfg.norm_eps)
        return x + mlp


# ---------------------------------------------------------------------------------
# what a layer keeps for its backward pass
# ---------------------------------------------------------------------------------

#: The named values (``jax.ad_checkpoint.checkpoint_name``) a layer may keep beside its
#: input, by group, in the order :func:`kept_residuals` takes them: most device time bought
#: for a byte kept first. The first six are one ``[tokens, d_model]`` array a layer each
#: (where every query head has a KV head), so the list can follow the memory closely.
KEPT_GROUPS = {
    # the attention products' forward, which the kernels name themselves (without the
    # log-sum-exp the forward kernel runs again); the plain path names neither and makes
    # its products again
    "attention": (kernels.OUT_NAME, kernels.LSE_NAME),
    # the down product of the SwiGLU and the output matrix's product, each the input of the
    # norm after it under sandwich norms (the stream after a sublayer is a sum away)
    "mlp_proj": ("mlp_proj",),
    "attn_proj": ("attn_proj",),
    # v, and k and q after the rotary: a projection each
    "v": ("attn_v",),
    "k": ("attn_k",),
    "q": ("attn_q",),
    # the up product of the SwiGLU, then its gate product (before the activation)
    "dense_up": ("dense_up",),
    "dense_gate": ("dense_gate",),
    # the plain path's float32 scores and its probabilities, ``[B, H, T, T]`` each: they
    # carry no name, and are kept only where everything is (no layer rematerialized)
    "scores": (),
}


#: of a device's memory, what its runtime keeps for itself (a v5e: 258 MiB of 15.75 GiB)
RESERVED_BYTES = 258 * 2 ** 20
#: what a kept value costs over its bytes: it lives in one buffer for all layers and passes,
#: and the compiler's allocator places such buffers with gaps between them. A compile for a
#: described v5e read 0.67e9 B of peak for each group of 0.54e9 B (32 uses of a layer at
#: 4,096 tokens, PR 43)
STACKING_LOSS = 1.25


def device_memory_bytes() -> Optional[int]:
    """The memory of the device this process computes on, or ``None`` where the backend
    states no limit (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _group_bytes(cfg: TransformerConfig, n_tokens: int, seq: int) -> dict:
    """Bytes of each group of :data:`KEPT_GROUPS` in one layer of one pass over
    ``n_tokens`` tokens in sequences of ``seq``."""
    act = jnp.dtype(cfg.dtype).itemsize
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kernel = attention_path(cfg, seq)["path"] == KERNEL
    return {
        "attention": n_tokens * h * (dh * act + 4) if kernel else 0,
        "mlp_proj": n_tokens * cfg.d_model * act,
        "attn_proj": n_tokens * cfg.d_model * act,
        "v": n_tokens * hkv * dh * act,
        "k": n_tokens * hkv * dh * act,
        "q": n_tokens * h * dh * act,
        "dense_up": n_tokens * cfg.d_ff * act,
        "dense_gate": n_tokens * cfg.d_ff * act,
        "scores": 0 if kernel else n_tokens * seq * h * (4 + act),
    }


def kept_residuals(cfg: TransformerConfig, n_tokens: int, memory_bytes: Optional[int],
                   seq: Optional[int] = None) -> dict:
    """What each layer of each pass keeps for its backward pass beside its input, at
    ``n_tokens`` tokens a step (in sequences of ``seq``; ``None``: one sequence) on a
    device of ``memory_bytes`` (``None``: no limit stated), from the description and the
    shapes alone: ``{"everything": nothing is rematerialized, "names": else the names the
    layers' ``jax.checkpoint`` keeps, "bytes": what they hold over all layers and passes,
    "groups": {group: its bytes over all layers and passes}, "step_bytes": what the step
    holds anyway}``.

    The groups of :data:`KEPT_GROUPS` are taken in order while the bytes kept (at
    :data:`STACKING_LOSS` times their size) and ``step_bytes`` stay inside the memory less
    :data:`RESERVED_BYTES`, and the first that does not fit ends the list. Where all fit,
    ``everything`` is true and the step is the plain program (no ``jax.checkpoint`` in it).
    ``step_bytes`` is 16 B a parameter (float32 weights, two moments, gradients), 2 B more
    where the stack runs more than once (the weights in the activations' type, which the
    compiler then makes once for all passes), the input of every layer of every pass, the
    float32 logits of one exit with their cotangent, and all the groups of one layer once
    (the layer whose backward pass runs holds them, kept or recomputed)."""
    seq = seq or n_tokens
    per_layer = _group_bytes(cfg, n_tokens, seq)
    virtual_layers = cfg.n_passes * cfg.n_layers
    n_params = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))))
    act = jnp.dtype(cfg.dtype).itemsize
    step_bytes = ((16 + (act if cfg.n_passes > 1 else 0)) * n_params
                  + virtual_layers * n_tokens * cfg.d_model * act
                  + 2 * n_tokens * cfg.vocab_size * 4
                  + sum(per_layer.values()))
    kept = {"everything": True, "names": [], "bytes": 0, "groups": {},
            "step_bytes": step_bytes}
    for group, names in KEPT_GROUPS.items():
        held = virtual_layers * per_layer[group]
        if not held:
            continue
        if (memory_bytes is not None and step_bytes + STACKING_LOSS * (kept["bytes"] + held)
                > memory_bytes - RESERVED_BYTES):
            kept["everything"] = False
            break
        kept["names"] += names
        kept["bytes"] += held
        kept["groups"][group] = held
    return kept


# ---------------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------------

def _passes(params: dict, tokens: jax.Array, cfg: TransformerConfig, attn_fn,
            position_offset: int, close):
    """The passes in turn. ``close(stream)`` closes a pass: it gives (the stream under the
    final norm, which the next pass reads, what this pass's exit yields). Returns the last
    pass's normed stream and the exits' yields stacked on a leading ``[n_passes]`` axis."""
    t = tokens.shape[1]
    attn_fn = adapt_attn_fn(attn_fn, position_offset,
                            kernel=attention_path(cfg, t)["path"] == KERNEL)
    x = params["embed"].astype(cfg.dtype)[tokens]
    cos, sin = rope_tables(cfg, t, position_offset)

    # each layer of each pass keeps its input and the values named here for its backward
    # pass, and recomputes the rest of its forward there; where everything fits, nothing
    kept = kept_residuals(cfg, tokens.size, device_memory_bytes(), t)
    remat = not kept["everything"]

    def body(x, lp):
        return _layer(cfg, x, lp, cos, sin, attn_fn, remat), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(*kept["names"]))

    def one_pass(x, _):
        x, _ = jax.lax.scan(body, x, params["layers"])
        return close(x)

    if cfg.n_passes == 1:
        x, out = one_pass(x, None)
        return x, jax.tree.map(lambda a: a[None], out)
    return jax.lax.scan(one_pass, x, None, length=cfg.n_passes)


def forward(
    params: dict,
    tokens: jax.Array,
    cfg: TransformerConfig,
    *,
    attn_fn=None,
    position_offset: int = 0,
) -> jax.Array:
    """tokens [B, T] int32 → logits [B, T, V] (float32), of the last pass.

    ``position_offset`` is applied to RoPE and to the DEFAULT dense attention's
    causal mask only; a custom ``attn_fn`` (e.g. ring attention) owns its own
    position bookkeeping, so combining the two is rejected (in
    :func:`adapt_attn_fn`) rather than silently producing a mask anchored
    at 0."""
    x, _ = _passes(params, tokens, cfg, attn_fn, position_offset,
                   lambda x: (rms_norm(x, params["final_norm"], cfg.norm_eps), None))
    return (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)


def token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position next-token NLL, ``logsumexp(logits) - logits[target]``.

    Equivalent to gathering from ``log_softmax`` but never materializes the
    ``[B, T, V]`` log-prob tensor — at vocab scale that array dominates the
    step's HBM traffic (B8 x T1024 x V32000 f32 is ~1 GB each way); logsumexp
    reduces to ``[B, T]`` and the backward pass recomputes softmax fused."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - picked


def _exit(x: jax.Array, lm_head: jax.Array, gate: dict, targets: jax.Array):
    """One pass's exit from its normed stream ``[B, T, D]``: (the per-position NLL through
    the head, the exit gate's logit), ``[B, T]`` float32 each. The logits leave the product
    in float32 (no rounding to the operands' type on the way: the gate learns from the small
    differences between the exits' NLLs); the gate is a float32 sum of products, not a
    matrix product (whose operands a TPU would round to bfloat16)."""
    logits = jnp.matmul(x, lm_head.astype(x.dtype), preferred_element_type=jnp.float32)
    gate_logit = jnp.sum(x.astype(jnp.float32) * gate["w"][:, 0], axis=-1) + gate["b"][0]
    return token_nll(logits, targets), gate_logit


def mix_exits(nll: jax.Array, gate_logit: jax.Array, beta: float):
    """The loss over the exits, and what they did: ``nll`` and ``gate_logit`` are
    ``[n_passes, ...]``, one entry a position with a target. ``lam_t = sigmoid(gate_logit_t)``;
    ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass taking what is left; the loss is
    the mean over the positions of ``sum_t p_t nll_t - beta H(p)``. In logarithms, so that
    a gate that is shut or wide open gives a probability of 0 and no NaN. Counts (float32):
    ``exit_share [n_passes]`` (the mean of ``p_t``: sums to one), ``exit_loss [n_passes]``
    (each exit's mean NLL), ``exit_entropy`` (the mean of ``H(p)``, at most ``log
    n_passes``), ``gate_mean [n_passes - 1]`` (the mean of ``lam_t``; the last pass's gate
    enters nothing)."""
    stay = jax.nn.log_sigmoid(-gate_logit[:-1])  # log(1 - lam_t), t < n_passes
    nothing = jnp.zeros_like(gate_logit[:1])
    stayed = jnp.concatenate([nothing, jnp.cumsum(stay, axis=0)])  # log prod_{j<t} (1 - lam_j)
    log_p = stayed + jnp.concatenate([jax.nn.log_sigmoid(gate_logit[:-1]), nothing])
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    loss = jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)
    positions = tuple(range(1, nll.ndim))
    counts = {
        "exit_share": jnp.mean(p, axis=positions),
        "exit_loss": jnp.mean(nll, axis=positions),
        "exit_entropy": jnp.mean(entropy),
        "gate_mean": jnp.mean(jax.nn.sigmoid(gate_logit[:-1]), axis=positions),
    }
    return loss, counts


def loss_and_counts(params: dict, tokens: jax.Array, cfg: TransformerConfig, *,
                    attn_fn=None, position_offset: int = 0):
    """Next-token cross-entropy over tokens [B, T], and the counts of :func:`mix_exits`
    (none without exits).

    The forward pass runs on the FULL sequence and the last position's logits are
    dropped afterwards (rather than slicing tokens first): a sequence-sharded
    batch keeps its ``T % sp == 0`` divisibility through attention, and the
    trailing slice is a local no-collective op on the logits. With exits, every pass's
    normed stream goes through the head inside a ``jax.checkpoint`` that keeps nothing:
    the backward pass makes one exit's logits again when it reaches that exit, and the
    last position, which has no target, is dropped from the ``[B, T]`` numbers.
    """
    if cfg.exit_beta is None:
        logits = forward(params, tokens, cfg, attn_fn=attn_fn,
                         position_offset=position_offset)[:, :-1]
        return token_nll(logits, tokens[:, 1:]).mean(), {}
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))  # the last position has none

    def close(x):
        with jax.named_scope("exit"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            return x, jax.checkpoint(_exit)(x, params["lm_head"], params["exit_gate"], targets)

    _, (nll, gate_logit) = _passes(params, tokens, cfg, attn_fn, position_offset, close)
    with jax.named_scope("exit"):
        return mix_exits(nll[..., :-1], gate_logit[..., :-1], cfg.exit_beta)


def loss_fn(params: dict, tokens: jax.Array, cfg: TransformerConfig, **kw) -> jax.Array:
    return loss_and_counts(params, tokens, cfg, **kw)[0]


def make_train_step_from_loss(bound_loss_fn, optimizer=None):
    """Shared factory behind every model family's ``make_train_step``:
    ``(train_step, init_opt_state)`` from a bound ``loss_fn(params, tokens)``.
    Changes to the training contract (optimizer default, grad transform) live here
    once."""
    import optax

    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.01)

    def init_opt_state(params):
        return optimizer.init(params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(bound_loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step, init_opt_state


def make_train_step(cfg: TransformerConfig, optimizer=None, attn_fn=None):
    """Returns ``(train_step, init_opt_state)`` — jit-ready pure functions.

    ``attn_fn`` overrides the dense attention (e.g.
    :func:`~tpu_resiliency.parallel.ring_attention.make_ring_attn_fn` for a
    sequence-sharded mesh)."""
    return make_train_step_from_loss(
        lambda params, tokens: loss_fn(params, tokens, cfg, attn_fn=attn_fn), optimizer
    )
