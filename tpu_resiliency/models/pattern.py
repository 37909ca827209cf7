"""Third model family: a decoder described as a *pattern of layers*.

Where ``transformer.py`` and ``moe.py`` scan one homogeneous stacked layer, this
model is a list of layers, each an attention kind and an MLP kind. Attention:
``full`` or ``sliding`` (grouped-query, with an output gate a head or a channel, each with
its own head count and, unless the description gives the full kind none, its rotary
table), ``latent`` (:class:`Latent`: keys and values of every head
decompressed from one normed low-rank latent, one rotary key part shared by all heads,
a score width that differs from the value width, no gate), or ``indexed``
(:class:`Indexer`: grouped-query heads with a norm on each head's q and k and no gate,
over the keys that a second, small set of heads chooses for each query from the data:
an exact top-k of their scores, the same set for all heads; the indexer is taught by a
loss of its own), or ``delta`` (:class:`Delta`: no softmax over keys at all, a state a
head carried along the sequence by the gated delta rule, :func:`delta_rule`). A
description may hold a share of every layer's heads, as it holds a share of the experts
(:attr:`PatternConfig.head_ways`); a description may give its ``full`` and ``sliding``
layers a norm on each head's q and k and no gate (:attr:`PatternConfig.head_norms`,
``gate=None``). MLP: ``dense`` SwiGLU, or ``sparse``: a float32 router over all
experts of the deployment (sigmoid or softmax scores; chosen by score, or by score plus
a selection bias that never enters a weight and that the loss-free balancing rule
moves), the top-k routed experts that this chip holds, and one shared SwiGLU where the
description has one (several shared experts are one SwiGLU of their summed width).
Parameters are
stacked per kind; the layers run in the order the description gives (a Python loop:
the kinds differ in shape, so there is no single body to scan).

A description also states the objective. Without more it is next-token cross-entropy over
a causal stream. With a :class:`Diffusion` it is block-diffusion training (SDAR-30B-A3B-Chat
is the description the benchmark runs: hidden 2048, layers all alike, ``x <- x +
Attn(norm(x))``, ``x <- x + MoE(norm(x))``, RMS norms with eps 1e-6, a final norm and an
untied head; 32 query heads over 4 KV heads of 128, no bias, a norm on each head's q and k
before the rotary, rotary theta 1e6 on the whole head, scale ``128^-0.5``, no output gate;
a float32 softmax over 128 router outputs, top 8 renormalised, SwiGLU experts of width 768,
no shared expert, no auxiliary loss): a step draws a noise level a block of ``block``
positions and masks each position with that probability, runs the stack once on the clean
copy beside the noised copy of each sequence (``2 L`` positions, the same rotary positions
twice) under a mask that is neither causal nor a band, reads the head on the noised half
alone, and weighs each masked position's cross-entropy against its own id by ``1 / t``.
The draws are a function of the sequence's ids, so a resume and a plain reference replay
them; :class:`Diffusion` states the step to the letter.

Built TPU-first, static shapes throughout:

- **Attention never holds a T x T array.** On a TPU, at heads of whole lane groups
  and sequences of whole tiles, a kind whose queries, keys and values have one width
  runs as the blocked kernels of ``ops/attention.py``: a tile of scores lives in VMEM,
  the key tiles a query tile cannot see are skipped (:func:`attention_paths` says which
  path a shape takes); under :class:`Diffusion` the walk over the doubled stream's tiles
  comes from positions alone, and the mask is no array of any size. Off the TPU, at
  shapes that do not tile, or where the score
  width differs from the value width (latent attention), the blocks below are plain
  ``jax.numpy``: sliding layers compute the band (query blocks of one window against
  their own and the previous key block), full, latent and indexed layers go by query
  blocks against the causal prefix of the keys, and all loop over the KV heads with the
  block's scores recomputed in the backward pass. An indexed layer's key sets are a
  mask that is an operand of its blocks (one block of index scores, one of the mask
  and one of the heads' mean probabilities at a time); on a TPU its index scores run
  as the blocked kernels of ``ops/index_scores.py`` where its groups of query rows are
  whole tiles, and never hold the per-head products of a block.
- **A state runs by chunks.** A delta layer's rule is a triangular system inside a chunk
  of tokens, solved for all of them at once, and a scan of the state across the chunks
  with a backward pass of its own that reads the states the forward kept; every exponent
  is of a difference of running log-decays that is ``<= 0`` (:func:`delta_rule`).
- **Routing drops nothing.** Every (token, choice) pair whose expert this chip holds
  is computed: the pairs are sorted by expert and the three SwiGLU products run as
  grouped products over the ragged groups (``jax.lax.ragged_dot``). Pairs for
  experts held elsewhere contribute nothing here, and nothing stands in for the
  chips that hold them or for the exchange with them. The rows moved are the head
  of the sorted order, twice the even-routing share of the experts held; a step
  whose router sends more here takes the full width (:func:`dispatch_rows`).
- **A layer keeps what its backward pass reads, as far as the device's memory goes.**
  Each layer runs under a ``jax.checkpoint`` whose policy keeps a list of named values
  beside the layer's input (the router's choices and the sort's indices, the keys an
  indexer selected, the stream after attention, the attention output and its
  log-sum-exp, a delta layer's states at each chunk's start, q, k and v, the gate and up
  products of the SwiGLUs, and last an indexer's target and its scores: float32 a (query,
  key), 168e6 B a layer each at 8,192 tokens) and recomputes the
  rest; :func:`kept_residuals` chooses
  the list from the configuration, the tokens of a step and the device's memory.
- **The description says how each leaf may be sharded** (:func:`describe_params`:
  logical axis names per dimension), so ``parallel/mesh.py`` derives the
  ``PartitionSpec`` tree and holds no key name of this model.

It reuses the dense model's ``rms_norm``, ``apply_rope``, ``token_nll`` and
``make_train_step_from_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from tpu_resiliency.models import transformer as tfm
from tpu_resiliency.ops import attention
from tpu_resiliency.ops import index_scores as index_score_kernels

FULL, SLIDING, LATENT, INDEXED, DELTA = "full", "sliding", "latent", "indexed", "delta"
ATTENTION_KINDS = (FULL, SLIDING, LATENT, INDEXED, DELTA)
DENSE, SPARSE = "dense", "sparse"
SIGMOID, SOFTMAX = "sigmoid", "softmax"
#: the output gate of a ``full`` or ``sliding`` layer: one sigmoid a head, or one a channel
HEAD_GATE, CHANNEL_GATE = "head", "channel"


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's frequency blend (Peng et al., arXiv:2309.00071) as the published
    configurations state it."""

    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


@dataclasses.dataclass(frozen=True)
class Rope:
    theta: float = 10000.0
    #: share of each head's dimensions that rotate (the first ones); the rest pass
    rotary_fraction: float = 1.0
    yarn: Optional[Yarn] = None


@dataclasses.dataclass(frozen=True)
class Latent:
    """Latent attention (DeepSeek-V2, arXiv:2405.04434, without the query's own low
    rank): a token's keys and values are ``kv_rank`` numbers, normed, from which every
    head's ``d_nope`` key dimensions and ``d_value`` values are decompressed; ``d_rope``
    more key dimensions carry the rotary positions and are one vector for all heads.
    A head's score is ``d_nope + d_rope`` wide."""

    kv_rank: int
    d_nope: int
    d_rope: int
    d_value: int
    #: the epsilon of the norm on the latent (the layers' own is ``norm_eps``)
    norm_eps: float = 1e-6

    @property
    def d_score(self) -> int:
        return self.d_nope + self.d_rope


@dataclasses.dataclass(frozen=True)
class Indexer:
    """Learned sparse attention (DeepSeek-V3.2's sparse attention in its sparse-training
    stage): ``n_heads`` small query heads of ``head_dim``, one key of ``head_dim`` a token
    for all of them and one weight a head score every earlier token for every query,
    ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``; the ``top_k`` keys of largest score
    (ties to the earlier key; all of them while there are no more) are the only keys the
    layer's attention heads see for that query. The selection passes no gradient: the
    indexer learns from the KL divergence between the heads' mean probabilities over the
    selected keys and the softmax of its own scores there, and reads the layer's normed
    input detached, so the two losses meet on no leaf."""

    n_heads: int
    head_dim: int
    top_k: int


@dataclasses.dataclass(frozen=True)
class Delta:
    """Gated delta-rule linear attention with a decay a key channel (Kimi Delta Attention,
    Kimi Linear, arXiv:2510.26692): a head carries a state ``S [d_key, d_value]`` along
    the sequence and no key of an earlier token. A token decays each key channel of the
    state by a factor of its own, takes out what the state already holds for its key and
    writes its value (:func:`delta_rule`). Around the rule: ``q``, ``k`` and ``v`` each
    pass a depthwise causal convolution of ``conv_taps`` taps and a SiLU, ``q`` and ``k``
    are scaled to unit length a head; the log-decay and the output gate are maps of the
    normed input through ``gate_rank`` numbers (two matrices each); the write strength
    ``beta`` is one sigmoid a head, doubled under ``neg_eigval`` so that a transition may
    have a negative eigenvalue; each head's output is normed (one weight vector for all
    heads) under the gate. No position enters: the order of the tokens is the state's."""

    d_key: int
    d_value: int
    gate_rank: int
    conv_taps: int = 4
    #: tokens the rule takes at a time: inside a chunk a triangular system, across chunks
    #: a scan of the state. A size of the computation: the result does not depend on it
    chunk: int = 64
    neg_eigval: bool = True


@dataclasses.dataclass(frozen=True)
class Diffusion:
    """Block-diffusion training (BD3-LM's vectorised form, arXiv:2503.09573, which SDAR
    adopts): the second objective beside next-token loss. A sequence ``x0`` of ``L`` ids is
    cut into blocks of ``block`` consecutive positions, ``b(i) = i // block``, and a step
    is one pass over ``2 L`` positions:

    - **the draws**, a function of the sequence's own ids (no generator state lives in a
      checkpoint: the same batch gives the same masks in a resumed run, in a re-entered
      ``train`` function and in the reference, which writes them again from these lines):
      ``key = fold_in(jax.random.key(noise_seed, impl="threefry2x32"), sum of the
      sequence's ids as uint32)``; ``key_b, key_i = jax.random.split(key)``; ``u_b =
      uniform(key_b, [ceil(L / block)], float32)``, ``u_i = uniform(key_i, [L],
      float32)``; the level of block ``b`` is ``t_b = eps + (1 - eps) u_b`` (the linear
      schedule of masked diffusion, a level a block); position ``i`` is masked where ``u_i
      < t_b(i)``; ``xt = where(masked, mask_id, x0)``;
    - **the stream** is ``[x0 ; xt]``, the clean copy beside the noised copy, both halves
      at the rotary positions ``0 .. L - 1``, through every layer;
    - **the mask**: a clean query ``i`` reads the clean keys ``j`` with ``b(j) <= b(i)``; a
      noised query ``i`` reads the clean keys with ``b(j) < b(i)`` and the noised keys with
      ``b(j) == b(i)``; nothing else is read (``ops/attention.py``'s ``noised`` form on a
      TPU at shapes that tile, :func:`noised_attention` elsewhere);
    - **the loss**: the final norm and the head on the noised half alone, and ``sum_i
      masked_i / t_b(i) x NLL(logits_i, x0_i) / (B L)``: position ``i``'s logits predict
      token ``i`` (no shift), and whether a position counts is the draw's ``masked_i``,
      never ``xt_i == mask_id`` (``mask_id`` may turn up as data)."""

    block: int
    eps: float
    noise_seed: int
    #: the id a masked position carries: a row of the vocabulary held here
    mask_id: int


@dataclasses.dataclass(frozen=True)
class Layer:
    attn: str  # FULL | SLIDING | LATENT | INDEXED | DELTA
    n_heads: int  # of the deployment (:attr:`PatternConfig.head_ways` says how many are here)
    mlp: str  # DENSE | SPARSE


@dataclasses.dataclass(frozen=True)
class PatternConfig:
    vocab_size: int
    d_model: int
    head_dim: int
    n_kv_heads: int
    layers: tuple[Layer, ...]
    d_ff: int  # the dense MLP's width
    d_expert: int  # a routed expert's width
    d_shared: int  # the shared expert's width; 0: the sparse layers have none
    n_experts: int  # the router's outputs: every expert of the deployment
    top_k: int
    #: (first, count): the contiguous range of the ``n_experts`` whose weights are here
    experts_held: tuple[int, int]
    #: how many equal shares every layer's heads are held in, one of them here: a chip
    #: of a tensor-parallel group holds ``n_heads / head_ways`` query heads of a layer with
    #: their ``n_kv_heads / head_ways`` KV heads, the columns of ``wq``, ``wk``, ``wv``
    #: and of the gates and the rows of ``wo`` that are theirs, and adds the held heads'
    #: part of ``wo``'s product to the stream. Nothing stands in for the other chips of
    #: the group or for the sum over them, and no computation reads which share this is
    #: (the weights are). ``1``: every head
    head_ways: int = 1
    routed_scale: float = 1.0
    window: int = 512
    #: ``None``: the full layers turn nothing (no position enters them)
    rope_full: Optional[Rope] = Rope()
    rope_sliding: Rope = Rope()
    #: HEAD_GATE: ``wg [d, heads]``; CHANNEL_GATE: ``wg [d, heads * head_dim]``; ``None``:
    #: the ``full`` and ``sliding`` layers have no output gate and no ``wg``
    gate: Optional[str] = HEAD_GATE
    #: the ``full`` and ``sliding`` layers norm each head's q and k before the rotary (one
    #: weight vector each for all heads, ``q_norm`` and ``k_norm``), as the indexed kind does
    head_norms: bool = False
    #: the objective: ``None`` is next-token loss over a causal stream; a :class:`Diffusion`
    #: is its masked-token loss over noised blocks on a doubled stream (every layer ``full``)
    diffusion: Optional[Diffusion] = None
    #: the widths of the ``latent`` layers, and the rotary table of their ``d_rope`` part
    latent: Optional[Latent] = None
    rope_latent: Rope = Rope()
    #: the small heads of the ``indexed`` layers, and the rotary table of those layers
    #: (made once for the attention heads' width and once for the indexer's)
    indexer: Optional[Indexer] = None
    rope_indexed: Rope = Rope()
    #: the widths of the ``delta`` layers, which have no rotary table
    delta: Optional[Delta] = None
    #: the router's scores over all experts: SIGMOID (each expert's own) or SOFTMAX
    route_score: str = SIGMOID
    #: the router chooses by score + a selection bias (seeded at this standard deviation)
    #: and weighs by the score alone; ``None``: no bias, it chooses by score
    route_bias_std: Optional[float] = None
    #: what one unit of the leaf ``b_router`` adds to a score in the choice. The balancing
    #: rule reaches the leaf as a gradient of +-1 (:func:`route`), so an optimizer of
    #: step ``lr`` moves the bias by ``lr * route_bias_gain`` a step
    route_bias_gain: float = 1.0
    norm_eps: float = 1e-6
    #: query rows a full, latent or indexed layer scores at a time (against all the keys
    #: before them) on the ``jax.numpy`` path; the kernel path has its own tiles and does
    #: not read it
    attn_block: int = 1024
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count > 0 and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} is not a range of "
                             f"the {self.n_experts} experts")
        for kind in ATTENTION_KINDS:
            heads = {l.n_heads for l in self.layers if l.attn == kind}
            if len(heads) > 1:
                raise ValueError(f"{kind} layers differ in head count {sorted(heads)}: "
                                 "their weights cannot be stacked")
        if self.route_score not in (SIGMOID, SOFTMAX):
            raise ValueError(f"unknown router score {self.route_score!r}")
        if self.gate not in (HEAD_GATE, CHANNEL_GATE, None):
            raise ValueError(f"unknown output gate {self.gate!r}")
        if self.diffusion is not None:
            noise = self.diffusion
            if any(l.attn != FULL for l in self.layers):
                raise ValueError("the block-diffusion mask is the full layers' alone")
            if noise.block < 1 or not 0 < noise.eps < 1 or not 0 <= noise.mask_id < self.vocab_size:
                raise ValueError(f"{noise} is no noise over a vocabulary of {self.vocab_size}")
        ways = self.head_ways
        if ways < 1:
            raise ValueError(f"head_ways {ways} is not a count of shares")
        for l in self.layers:
            if l.attn not in ATTENTION_KINDS or l.mlp not in (DENSE, SPARSE):
                raise ValueError(f"unknown layer kind in {l}")
            if l.n_heads % ways:
                raise ValueError(f"{l.n_heads} heads are not held {ways} ways")
            if l.attn == LATENT:  # every head has keys of its own
                if self.latent is None:
                    raise ValueError("latent layers need the widths of `latent`")
            elif l.attn == DELTA:  # and here
                if self.delta is None:
                    raise ValueError("delta layers need the widths of `delta`")
            elif l.n_heads % self.n_kv_heads:
                raise ValueError(f"{l.n_heads} heads do not group over {self.n_kv_heads}")
            elif self.n_kv_heads % ways:
                raise ValueError(f"a {ways}th of {l.n_heads} heads over {self.n_kv_heads} "
                                 "KV heads is not whole KV groups")
            if l.attn == INDEXED and self.indexer is None:
                raise ValueError("indexed layers need the heads of `indexer`")

    def rope(self, kind: str) -> Optional[Rope]:
        """The rotary table of the layers of ``kind``; ``None``: they have none."""
        return {FULL: self.rope_full, SLIDING: self.rope_sliding, LATENT: self.rope_latent,
                INDEXED: self.rope_indexed, DELTA: None}[kind]

    def rotary_width(self, kind: str) -> int:
        """The dimensions of a head that :meth:`rope`'s table of ``kind`` is made for."""
        return self.latent.d_rope if kind == LATENT else self.head_dim

    def held(self, heads: int) -> int:
        """Of ``heads`` of the deployment, how many are held here."""
        return heads // self.head_ways

    def heads(self, kind: str) -> int:
        """The query heads held here of a layer of ``kind``."""
        return self.held(next(l.n_heads for l in self.layers if l.attn == kind))

    @property
    def kv_heads(self) -> int:
        """The KV heads held here (of the kinds that group their query heads over them)."""
        return self.held(self.n_kv_heads)

    def count(self, kind: str) -> int:
        """Layers whose attention or MLP is of ``kind``."""
        return sum(1 for l in self.layers if kind in (l.attn, l.mlp))

    def stream(self, ids: int) -> int:
        """The positions that ``ids`` ids of a batch (or of a sequence) are in the stream
        the layers see: twice as many under :class:`Diffusion`. What
        :func:`attention_paths`, :func:`dispatch_rows` and :func:`kept_residuals` take."""
        return ids if self.diffusion is None else 2 * ids

    @staticmethod
    def tiny(**kw) -> "PatternConfig":
        base = dict(
            vocab_size=256, d_model=64, head_dim=16, n_kv_heads=2,
            layers=(Layer(FULL, 6, DENSE), Layer(SLIDING, 8, SPARSE), Layer(FULL, 6, SPARSE)),
            d_ff=128, d_expert=32, d_shared=32, n_experts=16, top_k=4,
            experts_held=(0, 4), routed_scale=2.5, window=8, attn_block=16,
            rope_full=Rope(500000.0, 0.5, Yarn(64.0, 4096, 64.0, 1.0, 1.4158883083359672)),
        )
        base.update(kw)
        return PatternConfig(**base)

    @staticmethod
    def tiny_latent(**kw) -> "PatternConfig":
        """Latent attention throughout, a router with a selection bias, two shared
        experts' width: the second description the tests and the example train."""
        base = dict(
            vocab_size=256, d_model=64, head_dim=16, n_kv_heads=4,
            layers=(Layer(LATENT, 4, DENSE), Layer(LATENT, 4, SPARSE), Layer(LATENT, 4, SPARSE)),
            latent=Latent(kv_rank=32, d_nope=16, d_rope=8, d_value=16),
            rope_latent=Rope(800000.0), route_bias_std=0.1, route_bias_gain=0.001 / 3e-4,
            d_ff=128, d_expert=32, d_shared=64, n_experts=16, top_k=4,
            experts_held=(0, 4), routed_scale=2.446, attn_block=16, norm_eps=1e-5,
        )
        base.update(kw)
        return PatternConfig(**base)

    @staticmethod
    def tiny_indexed(**kw) -> "PatternConfig":
        """Indexed attention throughout (8 heads over the 12 keys that 4 small heads
        select), softmax routing, no shared expert, no dense layer: the third
        description the tests train."""
        base = dict(
            vocab_size=256, d_model=64, head_dim=16, n_kv_heads=2,
            layers=(Layer(INDEXED, 8, SPARSE),) * 3,
            indexer=Indexer(n_heads=4, head_dim=8, top_k=12), rope_indexed=Rope(1e7),
            route_score=SOFTMAX, d_ff=128, d_expert=32, d_shared=0, n_experts=16, top_k=4,
            experts_held=(0, 4), attn_block=16,
        )
        base.update(kw)
        return PatternConfig(**base)

    @staticmethod
    def tiny_diffusion(**kw) -> "PatternConfig":
        """Block diffusion (:class:`Diffusion`) over blocks of 4: full attention throughout
        with a norm on each head's q and k and no gate, softmax routing, no shared expert,
        no dense layer; the last row of the vocabulary is the mask's: the fifth
        description the tests train."""
        base = dict(
            vocab_size=256, d_model=64, head_dim=16, n_kv_heads=2,
            layers=(Layer(FULL, 8, SPARSE),) * 3, gate=None, head_norms=True,
            rope_full=Rope(1e6), route_score=SOFTMAX,
            diffusion=Diffusion(block=4, eps=1e-3, noise_seed=0, mask_id=255),
            d_ff=128, d_expert=32, d_shared=0, n_experts=16, top_k=4,
            experts_held=(0, 4), attn_block=16,
        )
        base.update(kw)
        return PatternConfig(**base)

    @staticmethod
    def tiny_delta(**kw) -> "PatternConfig":
        """A period of four: one full layer with no rotary and a sigmoid a channel for a
        gate, then three delta layers; all sparse; 2 of 8 heads held (with 1 of the 4 KV
        heads of the full layer): the fourth description the tests train."""
        base = dict(
            vocab_size=256, d_model=64, head_dim=16, n_kv_heads=4,
            layers=(Layer(FULL, 8, SPARSE), *(Layer(DELTA, 8, SPARSE),) * 3),
            delta=Delta(d_key=16, d_value=16, gate_rank=8, chunk=16),
            rope_full=None, gate=CHANNEL_GATE, head_ways=4,
            d_ff=128, d_expert=32, d_shared=32, n_experts=16, top_k=4,
            experts_held=(0, 4), attn_block=16, norm_eps=1e-5,
        )
        base.update(kw)
        return PatternConfig(**base)


# ---------------------------------------------------------------------------------
# the parameters, described
# ---------------------------------------------------------------------------------

class Leaf(NamedTuple):
    """One parameter leaf: its shape, the logical name of each dimension (what
    ``parallel/mesh.py`` maps to mesh axes; ``None`` is never sharded) and how it is
    seeded: by the rule of :data:`SEEDINGS` that ``seeding`` names, else normal /
    sqrt(``fan_in``), or normal x ``std`` where that is given, or at one (a norm:
    none of them)."""

    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    fan_in: Optional[int]
    std: Optional[float] = None
    seeding: Optional[str] = None


def _decay_rate(key, shape):
    """``log A`` with ``A`` uniform in [1, 16]: a head's decay rate."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _decay_step(key, shape):
    """The inverse softplus of a step drawn log-uniformly in [0.001, 0.1]: the bias under
    a delta layer's softplus, so that at seeded weights a token's log-decay is about
    ``-A x step``."""
    step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


#: Seedings of their own (the published implementation's of a delta layer's decay: with a
#: normal draw here the state forgets either at once or never, and the rule does no work)
SEEDINGS = {"decay_rate": _decay_rate, "decay_step": _decay_step}


def describe_params(cfg: PatternConfig) -> dict:
    """The parameter tree as :class:`Leaf` descriptions. Layer weights are stacked on a
    leading axis per kind: ``attn/<full|sliding>`` and ``mlp/<dense|sparse>``, in the
    order the layers of that kind appear. A full or sliding layer has ``wg`` where the
    description has a gate, and ``q_norm`` and ``k_norm`` (one weight vector each for all
    heads, never sharded) where it norms the heads. A latent layer's down-projection
    ``wkv_a`` and
    its latent norm serve all heads and are never sharded; ``wq`` and the up-projection
    ``wkv_b`` have a head's columns together. An indexed layer's ``q_norm`` and ``k_norm``
    are one weight vector for all heads; its indexer (``wq_index``, ``wk_index``,
    ``ww_index``, ``k_index_norm``) is replicated like the router: every chip scores all
    the keys of its own tokens. ``b_router`` is there only where the
    router chooses by a bias, seeded so that the bias it stands for (``route_bias_gain``
    times it) has ``route_bias_std``. A delta layer's leaves: ``wq``, ``wk``, ``wv`` and
    a convolution for each (``conv_* [channels, taps]``), the two matrices of the log-decay
    (``wf_a``, the same on every chip of a tensor-parallel group, and ``wf_b``) with a rate
    a head (``a_log``) and a bias a channel (``dt_bias``), ``wb`` (the write strength),
    the two matrices of the output gate (``wg_a``, ``wg_b``), the heads' norm ``o_norm``
    and ``wo``.

    Every leaf that is split by heads has the heads held here
    (:attr:`PatternConfig.head_ways`); ``wo`` is seeded for the sum over all the heads
    of the deployment, which its product here is a part of."""
    d, dh, hkv = cfg.d_model, cfg.head_dim, cfg.kv_heads
    ways = cfg.head_ways
    tree: dict = {
        "embed": Leaf((cfg.vocab_size, d), ("vocab", None), d),
        "final_norm": Leaf((d,), (None,), None),
        "lm_head": Leaf((d, cfg.vocab_size), (None, "vocab"), d),
        "attn": {}, "mlp": {},
    }
    for kind in (FULL, SLIDING):
        n = cfg.count(kind)
        if not n:
            continue
        h = cfg.heads(kind)
        tree["attn"][kind] = {
            "attn_norm": Leaf((n, d), (None, None), None),
            "wq": Leaf((n, d, h * dh), (None, None, "heads"), d),
            "wk": Leaf((n, d, hkv * dh), (None, None, "heads"), d),
            "wv": Leaf((n, d, hkv * dh), (None, None, "heads"), d),
            "wo": Leaf((n, h * dh, d), (None, "heads", None), ways * h * dh),
        }
        if cfg.gate is not None:
            tree["attn"][kind]["wg"] = Leaf(
                (n, d, h * (dh if cfg.gate == CHANNEL_GATE else 1)), (None, None, "heads"), d)
        if cfg.head_norms:
            tree["attn"][kind].update(q_norm=Leaf((n, dh), (None, None), None),
                                      k_norm=Leaf((n, dh), (None, None), None))
    n = cfg.count(LATENT)
    if n:
        h, la = cfg.heads(LATENT), cfg.latent
        tree["attn"][LATENT] = {
            "attn_norm": Leaf((n, d), (None, None), None),
            "wq": Leaf((n, d, h * la.d_score), (None, None, "heads"), d),
            "wkv_a": Leaf((n, d, la.kv_rank + la.d_rope), (None, None, None), d),
            "kv_norm": Leaf((n, la.kv_rank), (None, None), None),
            "wkv_b": Leaf((n, la.kv_rank, h * (la.d_nope + la.d_value)),
                          (None, None, "heads"), la.kv_rank),
            "wo": Leaf((n, h * la.d_value, d), (None, "heads", None), ways * h * la.d_value),
        }

    n = cfg.count(INDEXED)
    if n:
        h, ix = cfg.heads(INDEXED), cfg.indexer
        tree["attn"][INDEXED] = {
            "attn_norm": Leaf((n, d), (None, None), None),
            "wq": Leaf((n, d, h * dh), (None, None, "heads"), d),
            "wk": Leaf((n, d, hkv * dh), (None, None, "heads"), d),
            "wv": Leaf((n, d, hkv * dh), (None, None, "heads"), d),
            "q_norm": Leaf((n, dh), (None, None), None),
            "k_norm": Leaf((n, dh), (None, None), None),
            "wo": Leaf((n, h * dh, d), (None, "heads", None), ways * h * dh),
            "wq_index": Leaf((n, d, ix.n_heads * ix.head_dim), (None, None, None), d),
            "wk_index": Leaf((n, d, ix.head_dim), (None, None, None), d),
            "ww_index": Leaf((n, d, ix.n_heads), (None, None, None), d),
            "k_index_norm": Leaf((n, ix.head_dim), (None, None), None),
        }

    n = cfg.count(DELTA)
    if n:
        h, de = cfg.heads(DELTA), cfg.delta
        keys, values, r = h * de.d_key, h * de.d_value, de.gate_rank
        conv = lambda channels: Leaf(  # noqa: E731
            (n, channels, de.conv_taps), (None, "heads", None), de.conv_taps)
        tree["attn"][DELTA] = {
            "attn_norm": Leaf((n, d), (None, None), None),
            "wq": Leaf((n, d, keys), (None, None, "heads"), d),
            "wk": Leaf((n, d, keys), (None, None, "heads"), d),
            "wv": Leaf((n, d, values), (None, None, "heads"), d),
            "conv_q": conv(keys), "conv_k": conv(keys), "conv_v": conv(values),
            "wf_a": Leaf((n, d, r), (None, None, None), d),
            "wf_b": Leaf((n, r, keys), (None, None, "heads"), r),
            "a_log": Leaf((n, h), (None, "heads"), None, seeding="decay_rate"),
            "dt_bias": Leaf((n, keys), (None, "heads"), None, seeding="decay_step"),
            "wb": Leaf((n, d, h), (None, None, "heads"), d),
            "wg_a": Leaf((n, d, r), (None, None, None), d),
            "wg_b": Leaf((n, r, values), (None, None, "heads"), r),
            "o_norm": Leaf((n, de.d_value), (None, None), None),
            "wo": Leaf((n, values, d), (None, "heads", None), ways * values),
        }

    def swiglu(prefix: str, lead: tuple, lead_axes: tuple, f: int) -> dict:
        return {
            f"{prefix}_gate": Leaf((*lead, d, f), (*lead_axes, None, "ff"), d),
            f"{prefix}_up": Leaf((*lead, d, f), (*lead_axes, None, "ff"), d),
            f"{prefix}_down": Leaf((*lead, f, d), (*lead_axes, "ff", None), f),
        }

    n = cfg.count(DENSE)
    if n:
        tree["mlp"][DENSE] = {"mlp_norm": Leaf((n, d), (None, None), None),
                              **swiglu("w", (n,), (None,), cfg.d_ff)}
    n = cfg.count(SPARSE)
    if n:
        held = cfg.experts_held[1]
        tree["mlp"][SPARSE] = {
            "mlp_norm": Leaf((n, d), (None, None), None),
            "w_router": Leaf((n, d, cfg.n_experts), (None, None, None), d),
            **swiglu("we", (n, held), (None, "experts"), cfg.d_expert),
        }
        if cfg.d_shared:
            tree["mlp"][SPARSE].update(swiglu("ws", (n,), (None,), cfg.d_shared))
        if cfg.route_bias_std is not None:
            tree["mlp"][SPARSE]["b_router"] = Leaf(
                (n, cfg.n_experts), (None, None), None,
                std=cfg.route_bias_std / cfg.route_bias_gain)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def init_params(rng: jax.Array, cfg: PatternConfig) -> dict:
    """Seeded weights: normal / sqrt(fan_in) or normal x std, norms at one, or by a rule
    of :data:`SEEDINGS` (:class:`Leaf`). One key a leaf, split from ``rng`` in the order the tree flattens
    (sorted keys), so that anything that knows the description makes the same weights."""
    leaves, treedef = jax.tree.flatten(describe_params(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(rng, len(leaves))

    def seeded(key, leaf: Leaf):
        if leaf.seeding is not None:
            return SEEDINGS[leaf.seeding](key, leaf.shape)
        if leaf.std is not None:
            return jax.random.normal(key, leaf.shape, jnp.float32) * leaf.std
        if leaf.fan_in is None:
            return jnp.ones(leaf.shape, jnp.float32)
        return jax.random.normal(key, leaf.shape, jnp.float32) / np.sqrt(leaf.fan_in)

    return jax.tree.unflatten(treedef, [seeded(key, leaf) for key, leaf in zip(keys, leaves)])


# ---------------------------------------------------------------------------------
# rotary tables
# ---------------------------------------------------------------------------------

def rope_tables(rope: Rope, head_dim: int, seq_len: int):
    """cos, sin ``[T, rot/2]`` over the ``rot = head_dim * rotary_fraction`` rotating
    dimensions. With YaRN the frequencies blend the interpolated and the original
    ones over a ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times in the original context, and cos and sin carry the attention factor."""
    rot = int(head_dim * rope.rotary_fraction)
    exponent = np.arange(0, rot, 2, dtype=np.float64) / rot
    inv_freq = 1.0 / (rope.theta ** exponent)
    scale = 1.0
    if rope.yarn is not None:
        y = rope.yarn

        def turns_to_dim(turns: float) -> float:
            return rot * math.log(y.original_max_position / (turns * 2 * math.pi)) / (
                2 * math.log(rope.theta))

        low = max(math.floor(turns_to_dim(y.beta_fast)), 0)
        high = min(math.ceil(turns_to_dim(y.beta_slow)), rot - 1)
        ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq / y.factor * ramp + inv_freq * (1.0 - ramp)
        scale = y.attention_factor
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotary positions on the first ``2 * cos.shape[-1]`` dimensions of each head."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return tfm.apply_rope(x, cos, sin)
    return jnp.concatenate([tfm.apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


# ---------------------------------------------------------------------------------
# attention: the blocked kernels where they apply; else a band, or query blocks over
# the causal prefix, in plain jax.numpy (everything down to full_attention)
# ---------------------------------------------------------------------------------

def _attend(q, k, v, mask):
    """Softmax attention of one KV head's query group. q ``[..., G, Q, dh]``, k / v
    ``[..., K, dh]``, mask broadcastable to ``[..., Q, K]`` -> ``[..., G, Q, dh]``.

    The softmax is written out so that neither row reduction is broadcast back over
    the keys inside one fusion: the row maximum (a constant of the softmax, so no
    gradient flows through it) crosses an optimization barrier, and the row sum
    divides the product with ``v``, not the scores. Left to ``jax.nn.softmax`` the
    TPU compiler turns reduce-and-broadcast into a ``reduce-window`` as wide as the
    key axis, which at 8,192 keys cost 2.4 s a step (chip run, PR 28)."""
    scores = jnp.einsum("...gqd,...kd->...gqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    scores = jnp.where(mask[..., None, :, :], scores, -1e30)
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True)))
    weights = jnp.exp(scores - top)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.einsum("...gqk,...kd->...gqd", weights.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out / total).astype(q.dtype)


def _attend_summed(q, k, v, mask):
    """:func:`_attend`, written the same way, and with it the group's summed probabilities
    ``[..., Q, K]`` in float32, which pass no gradient (what an indexer is taught to
    predict)."""
    scores = jnp.einsum("...gqd,...kd->...gqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    scores = jnp.where(mask[..., None, :, :], scores, -1e30)
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True)))
    weights = jnp.exp(scores - top)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.einsum("...gqk,...kd->...gqd", weights.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out / total).astype(q.dtype), jax.lax.stop_gradient(jnp.sum(weights / total, axis=-3))


def _over_kv_heads(q, k, v, mask):
    """:func:`_attend` for each KV head in turn (leading axis), its scores recomputed
    in the backward pass: one head's block of scores is all that is ever alive."""
    body = jax.checkpoint(lambda qkv: _attend(*qkv, mask))
    return jax.lax.map(body, (q, k, v))


def _over_heads_summed(q, k, v, mask):
    """:func:`_attend_summed` for each query head in turn, its scores recomputed in the backward
    pass, with the heads' probabilities summed on the way: q ``[Hkv, B, G, Q, dh]``, k / v
    ``[Hkv, B, K, dh]``, mask ``[B, Q, K]`` -> (``[Hkv, B, G, Q, dh]``, ``[B, Q, K]``
    float32). One head at a time, not one KV head's group: a head's ``[Q, K]`` float32
    scores are 16e6 B at 512 rows and 8,192 keys, a group of eight's 134e6 B, and the
    softmax and its backward pass go over them some twenty times. By groups the step of
    the 8,192-token cell read 1,867 ms on a v5e, 1,538 of it these products; by heads 549
    and 223 (chip runs, PR 35), as kimi's one-head blocks of 33e6 B had let expect: what
    passes between the fusions stays on the chip at the smaller size."""
    body = jax.checkpoint(lambda q1, k1, v1: _attend_summed(q1, k1, v1, mask))

    def kv_head(total, qkv):
        group, k1, v1 = qkv  # [B, G, Q, dh], [B, K, dh] twice

        def one_head(total, q1):
            out, probs = body(q1[:, None], k1, v1)
            return total + probs, out[:, 0]

        total, out = jax.lax.scan(one_head, total, group.swapaxes(0, 1))
        return total, out.swapaxes(0, 1)

    total, out = jax.lax.scan(kv_head, jnp.zeros(mask.shape, jnp.float32), (q, k, v))
    return out, total


def _pad_rows(x, axis: int, multiple: int):
    pad = -x.shape[axis] % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _heads_first(q, k, v):
    """q ``[B, T, H, dh]``, k / v ``[B, T, Hkv, dh]`` -> q ``[Hkv, B, G, T, dh]``,
    k / v ``[Hkv, B, T, dh]``."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, h // hkv, dh).transpose(2, 0, 3, 1, 4)
    return q, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)


def _heads_last(out, t: int):
    """``[Hkv, B, G, T', dh]`` -> ``[B, t, H * dh]`` (``T' >= t``: padding dropped)."""
    hkv, b, g, _, dh = out.shape
    return out[:, :, :, :t].transpose(1, 3, 0, 2, 4).reshape(b, t, hkv * g * dh)


def sliding_attention(q, k, v, window: int):
    """Causal attention over the last ``window`` keys (``i - window < j <= i``), as a
    band: the sequence is cut into blocks of ``window`` rows and each query block is
    scored against its own key block and the one before it."""
    b, t, _, dh = q.shape
    q, k, v = (_pad_rows(x, 1, window) for x in (q, k, v))
    q, k, v = _heads_first(q, k, v)
    hkv, _, g, tp, _ = q.shape
    nb = tp // window
    q = q.reshape(hkv, b, g, nb, window, dh).transpose(0, 1, 3, 2, 4, 5)  # [Hkv,B,nb,G,W,dh]

    def with_previous(x):  # [Hkv, B, T', dh] -> [Hkv, B, nb, 2W, dh]
        x = x.reshape(hkv, b, nb, window, dh)
        before = jnp.pad(x, ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))[:, :, :-1]
        return jnp.concatenate([before, x], axis=3)

    qpos = jnp.arange(nb)[:, None, None] * window + jnp.arange(window)[None, :, None]
    kpos = (jnp.arange(nb)[:, None, None] - 1) * window + jnp.arange(2 * window)[None, None, :]
    mask = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)  # [nb, W, 2W]
    out = _over_kv_heads(q, with_previous(k), with_previous(v), mask)  # [Hkv,B,nb,G,W,dh]
    out = out.transpose(0, 1, 3, 2, 4, 5).reshape(hkv, b, g, tp, dh)
    return _heads_last(out, t)


def full_attention(q, k, v, block: int):
    """Causal attention by query blocks of ``block`` rows, each against the keys up
    to its own last row: the rows of one block see all their keys at once, so no
    running softmax is carried, and the keys after a block are never scored. (The
    path taken off the TPU or at shapes that do not tile; the kernel path carries a
    running softmax over key tiles.)"""
    t = q.shape[1]
    block = min(block, t)
    q, k, v = (_pad_rows(x, 1, block) for x in (q, k, v))
    q, k, v = _heads_first(q, k, v)
    outs = []
    for start in range(0, q.shape[3], block):
        end = start + block
        mask = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        outs.append(_over_kv_heads(q[:, :, :, start:end], k[:, :, :end], v[:, :, :end], mask))
    return _heads_last(jnp.concatenate(outs, axis=3), t)


def noised_attention(q, k, v, block: int, clean: int, rows: int):
    """Attention over a doubled stream ``[clean ; noised]`` of ``2 clean`` rows under the
    block-diffusion mask (:class:`Diffusion`), by query blocks of ``rows`` rows of either
    half: a block of clean queries against the clean keys up to the end of its last
    block; a block of noised queries against those and the noised keys of its own blocks,
    side by side, so a row's softmax is over all its keys at once. The masks come from
    positions alone (numpy: constants of the program, a block's own ``[rows, keys]`` and
    never the stream's square). (The path taken off the TPU or at shapes that do not
    tile; the kernel path walks tiles, ``ops/attention.py``.)"""
    t = q.shape[1]
    q, k, v = _heads_first(q, k, v)
    of_block = np.arange(clean) // block
    outs = []
    for late in (False, True):
        for start in range(0, clean, rows):
            end = min(start + rows, clean)
            first, last = start // block * block, min(-(-end // block) * block, clean)
            own = of_block[start:end, None]
            if not late:
                mask = of_block[None, :last] <= own
                keys, values = k[:, :, :last], v[:, :, :last]
            else:
                mask = np.concatenate(
                    [of_block[None, :last] < own, of_block[None, first:last] == own], axis=1)
                keys, values = (jnp.concatenate(
                    [x[:, :, :last], x[:, :, clean + first:clean + last]], axis=2) for x in (k, v))
            at = clean * late
            outs.append(_over_kv_heads(q[:, :, :, at + start:at + end], keys, values,
                                       jnp.asarray(mask)))
    return _heads_last(jnp.concatenate(outs, axis=3), t)


def index_scores(q, w, k):
    """An indexer's score of every key for every query: q ``[B, Q, J, di]`` (``J`` small
    heads), w ``[B, Q, J]`` float32 (a head's weight for the query, scaled), k
    ``[B, K, di]`` (one key a token for all heads) -> ``[B, Q, K]`` float32, ``sum_j
    w[q, j] relu(q[q, j] . k[s])``: the products in the operands' type accumulated in
    float32, the ReLU and the weighted sum in float32."""
    dots = jnp.einsum("bqjd,bkd->bqjk", q, k, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' own (-0.0 is 0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x).astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _bisect(bits: int, dtype, rows: tuple, holds):
    """The largest ``bits``-bit number ``x`` (one for each of ``rows``) for which
    ``holds(x)`` is true, for a ``holds`` that is true at 0 and, once false, stays false
    as ``x`` grows: the bits are settled from the top, one pass over the rows each."""
    def settle(i, x):
        trial = x | jnp.left_shift(jnp.ones((), dtype), (bits - 1 - i).astype(dtype))
        return jnp.where(holds(trial), trial, x)

    return jax.lax.fori_loop(0, bits, settle, jnp.zeros(rows, dtype))


def select_keys(scores, start: int, top_k: int):
    """Each query's key set as a mask: scores ``[..., Q, K]`` float32 of the query rows at
    positions ``start .. start + Q - 1`` against the keys at ``0 .. K - 1`` -> (mask
    ``[..., Q, K]`` bool: the ``top_k`` keys ``s <= t`` of largest score, equal scores to
    the lower ``s``, all of them for a query with no more; ``[..., Q]`` bool: the queries
    whose ``top_k``-th and next scores are equal). Exact, with no sort: the ``top_k``-th
    largest score of a row is found bit by bit (32 counts of the row's scores at or above
    a trial value, in the order-preserving integer form of the floats), and only where
    some row's equal scores straddle it, the last position taken among them the same
    way. Rows that all end at or before ``top_k`` get the causal mask with no pass."""
    q, k = scores.shape[-2:]
    position = jnp.arange(k, dtype=jnp.int32)
    causal = position <= start + jnp.arange(q, dtype=jnp.int32)[:, None]
    if start + q <= top_k:
        return (jnp.broadcast_to(causal, scores.shape),
                jnp.zeros(scores.shape[:-1], bool))
    keys = jnp.where(causal, _ordered(scores), jnp.uint32(0))  # a real score is above 0
    count = lambda which: jnp.sum(which, axis=-1, dtype=jnp.int32)  # noqa: E731
    rows = keys.shape[:-1]
    level = _bisect(32, jnp.uint32, rows,
                    lambda x: count(keys >= x[..., None]) >= top_k)[..., None]
    above, equal = keys > level, keys == level
    wanted = top_k - count(above)  # of the keys at the level, lowest positions first
    # a row with no more than top_k keys has level 0 (the masked ones): all of it is taken
    straddles = (count(equal) > wanted) & (level[..., 0] > 0)

    def lowest(_):
        last = _bisect(max(k - 1, 1).bit_length(), jnp.int32, rows,
                       lambda x: count(equal & (position < x[..., None])) < wanted)
        return equal & (position <= last[..., None])

    equal = jax.lax.cond(jnp.any(straddles), lowest, lambda _: equal, None)
    return (above | equal) & causal, straddles


@jax.custom_vjp
def index_divergence(scores, mask, target):
    """``KL(target || softmax of scores over the mask)`` of each row, ``[..., Q]``
    float32: scores and target ``[..., Q, K]`` float32, the target zero off the mask.
    Differentiated by the scores alone, as ``sum(target) x softmax - target``; the row
    maximum and the row sum cross a barrier for :func:`_attend`'s reason."""
    return _index_divergence(scores, mask, target)[0]


def _index_softmax(scores, mask):
    scores = jnp.where(mask, scores, -1e30)
    top = jax.lax.optimization_barrier(jnp.max(scores, axis=-1, keepdims=True))
    weights = jnp.where(mask, jnp.exp(scores - top), 0.0)
    total = jax.lax.optimization_barrier(jnp.sum(weights, axis=-1, keepdims=True))
    return scores - top, weights, total


def _index_divergence(scores, mask, target):
    shifted, _, total = _index_softmax(scores, mask)
    seen = mask & (target > 0)
    log_target = jnp.log(jnp.where(seen, target, 1.0))
    rows = jnp.sum(jnp.where(seen, target * (log_target - shifted + jnp.log(total)), 0.0), axis=-1)
    return rows, (scores, mask, target)


def _index_divergence_bwd(res, g):
    scores, mask, target = res
    _, weights, total = _index_softmax(scores, mask)
    mass = jax.lax.optimization_barrier(jnp.sum(target, axis=-1, keepdims=True))
    return g[..., None] * (mass * (weights / total) - target), None, None


index_divergence.defvjp(_index_divergence, _index_divergence_bwd)


#: the query blocks of an indexed layer go in at most this many groups, each group against
#: the keys up to its own end: one compiled body a group and stage, mapped over its blocks
KEY_GROUPS = 4


def key_groups(seq: int, block: int) -> list[tuple[int, int]]:
    """(first query row, keys) of each group of query blocks of a sequence of ``seq``
    rows (whole blocks of ``block``): the rows from ``first`` to ``keys`` see the keys
    ``0 .. keys - 1``."""
    blocks = seq // block
    size = -(-blocks // KEY_GROUPS) * block
    return [(first, min(first + size, seq)) for first in range(0, seq, size)]


def _in_blocks(x, axis: int, n: int):
    """``[..., n * rows, ...]`` on ``axis`` as ``[n, ..., rows, ...]``: the operand of a
    ``jax.lax.map`` over ``n`` blocks of rows."""
    return jnp.moveaxis(x.reshape(*x.shape[:axis], n, -1, *x.shape[axis + 1:]), axis, 0)


def _whole(x, axis: int):
    """``[n, ..., rows, ...]`` back to ``[..., n * rows, ...]`` on ``axis``."""
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(*x.shape[:axis], -1, *x.shape[axis + 2:])


def indexed_attention(q, k, v, qi, wi, ki, top_k: int, block: int, kernels: bool = False,
                      score_kernels: bool = False):
    """Causal attention over the keys an indexer selects: q ``[B, T, H, dh]``, k / v ``[B,
    T, Hkv, dh]``, and the indexer's qi ``[B, T, J, di]``, wi ``[B, T, J]``, ki ``[B, T,
    di]`` -> (the attention output ``[B, T, H * dh]``; per query ``[B, T]``: the divergence
    of the indexer's softmax from the heads' mean probabilities over the selected keys, how
    many keys were selected, and whether the ``top_k``-th score was tied with the next; and
    each group's rows of the mask, ``[B, rows, keys]`` bool, for :func:`choices`).

    The query rows go in groups (:func:`key_groups`), each against the causal prefix of
    the keys up to its own end. A group computes its index scores by blocks of ``block``
    rows (:func:`index_scores`, one block's products of all the indexer's heads alive at
    a time; made again in the backward pass) or, with ``score_kernels`` (groups of whole
    tiles of an indexer the kernels' layout takes), by one call of
    ``ops/index_scores.py:index_scores``, which bounds its own temporaries and leaves the
    key tiles past a query tile's diagonal tile zero (named ``index_scores`` either way);
    and turns them into its rows of the mask
    (:func:`select_keys`; named ``select_mask``, so a layer may keep it). The products
    under the mask, with the heads' probabilities summed on the way, go one of two ways
    (:func:`attention_paths`). On the ``jax.numpy`` blocks each group runs
    :func:`_attend_summed` by the same blocks of rows, each head in turn, so the last rows
    of a group score up to a quarter of the sequence beyond their own position. With
    ``kernels`` (a sequence of whole blocks that the kernels tile) the groups' rows are put
    together into the sequence's selection, a byte a (query, key), and one call of
    ``ops/attention.py:blocked_attention`` takes it as an operand: no key past a query
    tile's diagonal tile is scored, the forward's output and log-sum-exp carry the names a
    layer keeps, and a second kernel gives the summed probabilities. Then each group takes
    its divergence from its rows of them (named ``index_target`` on either path). The loops
    over the blocks are ``jax.lax.map``s: a
    layer compiles one body a group and stage. Nothing differentiable passes through the
    mask or the probabilities. Scopes: ``indexer``, ``select``, ``core``, each around its
    loop or its kernels (an op's name holds a scope before the loop's own components)."""
    b, t, heads = q.shape[:3]
    block = min(block, t)
    q, k, v, qi, wi, ki = (_pad_rows(x, 1, block) for x in (q, k, v, qi, wi, ki))
    groups = key_groups(q.shape[1], block)
    score_block = jax.checkpoint(index_scores)
    scores, masks, rows = [], [], []
    for first, keys in groups:
        n = (keys - first) // block
        ki_seen = ki[:, :keys]
        with jax.named_scope("indexer"):
            if score_kernels:
                group_scores = index_score_kernels.index_scores(
                    qi[:, first:keys], wi[:, first:keys], ki_seen)
            else:
                group_scores = _whole(jax.lax.map(
                    lambda rows, ki_seen=ki_seen: score_block(*rows, ki_seen),
                    (_in_blocks(qi[:, first:keys], 1, n), _in_blocks(wi[:, first:keys], 1, n))), 1)
            scores.append(checkpoint_name(group_scores, KEPT_GROUPS["scores"][0]))
        with jax.named_scope("select"):
            mask, tied = select_keys(jax.lax.stop_gradient(scores[-1]), first, top_k)
            if keys > top_k:  # else the causal mask, which nothing needs to keep
                mask = checkpoint_name(mask, KEPT_GROUPS["selection"][0])
            rows.append((jnp.sum(mask, axis=-1, dtype=jnp.int32), tied))
        masks.append(mask)
    with jax.named_scope("core"):
        if kernels:
            selection = jnp.concatenate([jnp.pad(m.astype(jnp.int8), (
                (0, 0), (0, 0), (0, t - m.shape[2]))) for m in masks], axis=1)
            out, probs = attention.blocked_attention(q, k, v, selected=selection)
            # the name on each group's rows, not on the kernel's whole square: kept whole it
            # is copied whole (4.9 ms a step at 8,192 tokens), the rows come out of the
            # fusion that reads them first (3.6 ms), and hold 0.6 of its bytes (chip run, PR 46)
            probs = [checkpoint_name(probs[:, first:keys, :keys], KEPT_GROUPS["target"][0])
                     for first, keys in groups]
        else:
            q, k, v = _heads_first(q, k, v)
            outs, probs = [], []
            for (first, keys), mask in zip(groups, masks):
                n = (keys - first) // block
                group_out, group_probs = jax.lax.map(
                    lambda rows, seen=(k[:, :, :keys], v[:, :, :keys]): _over_heads_summed(
                        rows[0], *seen, rows[1]),
                    (_in_blocks(q[:, :, :, first:keys], 3, n), _in_blocks(mask, 1, n)))
                outs.append(_whole(group_out, 3))
                probs.append(checkpoint_name(_whole(group_probs, 1), KEPT_GROUPS["target"][0]))
            out = _heads_last(jnp.concatenate(outs, axis=3), t)
    with jax.named_scope("indexer"):
        divergence = [index_divergence(s, mask, p / heads)
                      for s, mask, p in zip(scores, masks, probs)]
    divergence, selected, tied = (jnp.concatenate(x, axis=1)[:, :t]
                                  for x in (divergence, *zip(*rows)))
    return out, divergence, selected, tied, masks


def _window(cfg: PatternConfig, kind: str) -> Optional[int]:
    return cfg.window if kind == SLIDING else None


def _widths(cfg: PatternConfig, kind: str) -> tuple[int, int]:
    """(score width, value width) of a head of ``kind``."""
    if kind == LATENT:
        return cfg.latent.d_score, cfg.latent.d_value
    return cfg.head_dim, cfg.head_dim


def attention_paths(cfg: PatternConfig, seq: int) -> dict:
    """Which path the attention products of each kind of layer take at sequences of
    ``seq`` positions in the stream (:meth:`PatternConfig.stream`), from what the code can
    see (the backend, the widths, whether the sequence
    is whole tiles): ``{kind: {"path": "kernel", "tile": rows}}`` for the blocked
    kernels of ``ops/attention.py``, ``{"path": "blocks", "block": rows}`` for the
    ``jax.numpy`` blocks. The kernels take one width for queries, keys and values, so a
    latent kind whose score width differs from its value width takes the blocks, and
    says both widths. An indexed kind's mask is data, which the kernels take as an
    operand (``selection: "mask"``: every key up to a query tile's diagonal tile is
    scored and the unselected ones masked, on either path) where the sequence is also
    whole blocks of the indexer's rows; it says how many keys a query keeps, and which way
    its index scores go: ``scores: "kernel"`` for the blocked kernels of
    ``ops/index_scores.py`` where the backend is a TPU, every group of query rows is whole
    tiles and the indexer's heads fit their layout, else ``scores: "blocks"``. A delta
    kind has no products over keys: ``{"path": "chunks", "chunk": tokens, "solve":
    "blocks"}``, the rule of :func:`delta_rule` in ``jax.numpy`` (``"kernel"`` is for a
    kernel of it, which there is not), every chunk's triangular system inverted by blocks
    as matrix products (:func:`_unit_lower_inverse`; on every backend, at any chunk). Under
    :class:`Diffusion` the full kind says the walk: ``{"walk": "noised",
    "block_length": b, "clean": L}`` beside its path, the kernels of the ``noised`` form
    where the backend is a TPU and both halves are whole tiles that no block crosses
    (``attention.applies_noised``), else :func:`noised_attention` by blocks of rows."""
    paths = {}
    for kind in ATTENTION_KINDS:
        if not cfg.count(kind):
            continue
        if cfg.diffusion is not None:  # every layer is full: one walk, from positions
            block, clean = cfg.diffusion.block, seq // 2
            if jax.default_backend() == "tpu" and attention.applies_noised(
                    seq, cfg.head_dim, block, clean):
                paths[kind] = {"path": "kernel", "tile": attention.tile_of(clean, None)}
            else:
                paths[kind] = {"path": "blocks", "block": min(cfg.attn_block, clean)}
            paths[kind].update(walk="noised", block_length=block, clean=clean)
            continue
        if kind == DELTA:  # no products over keys: the rule by chunks, in jax.numpy
            paths[kind] = {"path": "chunks", "chunk": min(cfg.delta.chunk, seq),
                           "solve": "blocks"}
            continue
        window = _window(cfg, kind)
        score, value = _widths(cfg, kind)
        block = cfg.window if kind == SLIDING else min(cfg.attn_block, seq)
        if (jax.default_backend() == "tpu" and score == value
                and attention.applies(seq, score, window)
                and not (kind == INDEXED and seq % block)):
            paths[kind] = {"path": "kernel", "tile": attention.tile_of(seq, window)}
        else:
            paths[kind] = {"path": "blocks", "block": block}
        if kind == LATENT:
            paths[kind].update(score_width=score, value_width=value)
        if kind == INDEXED:
            ix = cfg.indexer
            tiled = jax.default_backend() == "tpu" and not seq % block and all(
                index_score_kernels.applies(keys - first, keys, ix.n_heads, ix.head_dim)
                for first, keys in key_groups(seq, block))
            paths[kind].update(selected=min(ix.top_k, seq), selection="mask",
                               scores="kernel" if tiled else "blocks")
    return paths


def _products(cfg: PatternConfig, kind: str, q, k, v):
    """Softmax attention of one layer (causal; under :class:`Diffusion` its own mask over
    the doubled stream) by the path :func:`attention_paths` names:
    q ``[B, T, H, dk]``, k ``[B, T, Hkv, dk]``, v ``[B, T, Hkv, dv]`` -> ``[B, T, H * dv]``.
    The operands and the result carry the names of :data:`KEPT_GROUPS` (the kernel names
    its own output and log-sum-exp where it makes them)."""
    q, k, v = (checkpoint_name(x, name) for x, name in zip((q, k, v), KEPT_GROUPS["qkv"]))
    with jax.named_scope("core"):
        path = attention_paths(cfg, q.shape[1])[kind]
        if cfg.diffusion is not None:
            noised = (path["block_length"], path["clean"])
            if path["path"] == "kernel":
                return attention.blocked_attention(q, k, v, noised=noised)
            out = noised_attention(q, k, v, *noised, path["block"])
        elif path["path"] == "kernel":
            return attention.blocked_attention(q, k, v, window=_window(cfg, kind))
        elif kind == SLIDING:
            out = sliding_attention(q, k, v, cfg.window)
        else:
            out = full_attention(q, k, v, cfg.attn_block)
    return checkpoint_name(out, attention.OUT_NAME)


def _attn_block(cfg: PatternConfig, kind: str, x, lp: dict, cos=None, sin=None):
    """Pre-norm grouped-query attention of one kind with an output gate (from the normed
    input: one sigmoid a head, or one a channel where the description says so, or none)
    and the residual; each head's q and k normed where the description says so, then the
    rotary where the kind has a table (``cos`` given)."""
    with jax.named_scope(f"attn/{kind}"):
        b, t, _ = x.shape
        h, hkv, dh = cfg.heads(kind), cfg.kv_heads, cfg.head_dim
        y = tfm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (y @ lp["wq"].astype(y.dtype)).reshape(b, t, h, dh)
        k = (y @ lp["wk"].astype(y.dtype)).reshape(b, t, hkv, dh)
        v = (y @ lp["wv"].astype(y.dtype)).reshape(b, t, hkv, dh)
        if cfg.gate is not None:
            gate = jax.nn.sigmoid(y @ lp["wg"].astype(y.dtype))  # [B, T, H] or [B, T, H * dh]
        if cfg.head_norms:
            q = tfm.rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = tfm.rms_norm(k, lp["k_norm"], cfg.norm_eps)
        if cos is not None:
            q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        attn = _products(cfg, kind, q, k, v)
        if cfg.gate == CHANNEL_GATE:
            attn = attn * gate
        elif cfg.gate == HEAD_GATE:
            attn = (attn.reshape(b, t, h, dh) * gate[..., None]).reshape(b, t, h * dh)
        return x + attn @ lp["wo"].astype(attn.dtype)


def _latent_block(cfg: PatternConfig, x, lp: dict, cos, sin):
    """Pre-norm latent attention and the residual: no gate, no bias. Every head's keys
    are ``[k_nope | k_rope]`` with the one rotary ``k_rope`` of the token, its queries
    ``[q_nope | q_rope]``, so a score is ``(q_nope . k_nope + q_rope . k_rope) /
    sqrt(d_nope + d_rope)``; keys and values are decompressed for the products (the
    training form: nothing is absorbed into ``wq`` or ``wo``).

    The scope is ``attn/full``: it names the mask, which is what the benchmark's readers
    of ``attn/<mask>`` and ``attn/<mask>/core`` know; the projections, the latent norm
    and the rotary of both parts are under ``attn/full/latent``."""
    with jax.named_scope("attn/full"):
        b, t, _ = x.shape
        h, la = cfg.heads(LATENT), cfg.latent
        y = tfm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("latent"):
            q = (y @ lp["wq"].astype(y.dtype)).reshape(b, t, h, la.d_score)
            # [B, T, kv_rank + d_rope], left in float32 until it is normed and rotated:
            # every head's keys and values come from these few numbers, and a rounding
            # here is repeated in all of them (576 of a token's 5,000 activations)
            down = jnp.matmul(y, lp["wkv_a"].astype(y.dtype), preferred_element_type=jnp.float32)
            c = tfm.rms_norm(down[..., :la.kv_rank], lp["kv_norm"], la.norm_eps).astype(y.dtype)
            up = (c @ lp["wkv_b"].astype(c.dtype)).reshape(b, t, h, la.d_nope + la.d_value)
            k_rope = _rotate(down[..., la.kv_rank:].reshape(b, t, 1, la.d_rope), cos, sin)
            q = jnp.concatenate(
                [q[..., :la.d_nope], _rotate(q[..., la.d_nope:], cos, sin)], axis=-1)
            k = jnp.concatenate(
                [up[..., :la.d_nope],
                 jnp.broadcast_to(k_rope.astype(y.dtype), (b, t, h, la.d_rope))], axis=-1)
            v = up[..., la.d_nope:]
        attn = _products(cfg, LATENT, q, k, v)
        return x + attn @ lp["wo"].astype(attn.dtype)


def _indexed_block(cfg: PatternConfig, x, lp: dict, cos, sin, index_cos, index_sin):
    """Pre-norm grouped-query attention over the keys its indexer selects, and the
    residual: each head's q and k normed (one weight vector for all heads) before the
    rotary, no gate. The indexer reads the normed input detached: its queries, its one
    key a token (normed, with a weight) and its head weights, the rotary over all its
    dimensions. Returns the stream, the layer's counts: ``index_kl`` (the mean over
    the queries of :func:`index_divergence`, which is also the layer's term of the
    loss), ``keys_selected``, ``select_ties``; and :func:`indexed_attention`'s rows of the
    mask.

    The scope is ``attn/full`` as the latent kind's is (it names the causal mask the
    selection is made under), with ``/indexer`` (the indexer's projections, norm and
    rotary, the index scores, the divergence), ``/select`` and ``/core`` inside it."""
    with jax.named_scope("attn/full"):
        b, t, _ = x.shape
        h, hkv, dh, ix = cfg.heads(INDEXED), cfg.kv_heads, cfg.head_dim, cfg.indexer
        y = tfm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (y @ lp["wq"].astype(y.dtype)).reshape(b, t, h, dh)
        k = (y @ lp["wk"].astype(y.dtype)).reshape(b, t, hkv, dh)
        v = (y @ lp["wv"].astype(y.dtype)).reshape(b, t, hkv, dh)
        q = _rotate(tfm.rms_norm(q, lp["q_norm"], cfg.norm_eps), cos, sin)
        k = _rotate(tfm.rms_norm(k, lp["k_norm"], cfg.norm_eps), cos, sin)
        with jax.named_scope("indexer"):
            detached = jax.lax.stop_gradient(y)
            qi = (detached @ lp["wq_index"].astype(y.dtype)).reshape(b, t, ix.n_heads, ix.head_dim)
            ki = tfm.rms_norm(detached @ lp["wk_index"].astype(y.dtype), lp["k_index_norm"],
                              cfg.norm_eps)
            wi = jnp.matmul(detached, lp["ww_index"].astype(y.dtype),
                            preferred_element_type=jnp.float32) / np.sqrt(
                                ix.n_heads * ix.head_dim)
            qi = _rotate(qi, index_cos, index_sin)
            ki = _rotate(ki[:, :, None], index_cos, index_sin)[:, :, 0]
        names = (*KEPT_GROUPS["qkv"], *KEPT_GROUPS["index"])
        q, k, v, qi, wi, ki = (checkpoint_name(a, name)
                               for a, name in zip((q, k, v, qi, wi, ki), names))
        path = attention_paths(cfg, t)[INDEXED]
        attn, divergence, selected, tied, masks = indexed_attention(
            q, k, v, qi, wi, ki, ix.top_k, cfg.attn_block,
            kernels=path["path"] == "kernel", score_kernels=path["scores"] == "kernel")
        attn = checkpoint_name(attn, attention.OUT_NAME)
        out = x + attn @ lp["wo"].astype(attn.dtype)
        # The indexer's loss hangs off the layer to one side and its value is read at the
        # end of the step, so the compiler is free to leave a layer's divergence for much
        # later, with the sequence's float32 target (268e6 B at 8,192 tokens) held for it.
        # The stream leaves the layer with the divergence: 13.85e9 B for the step of the
        # 8,192-token cell against 15.45e9 (compile for a v5e, PR 36), and 469 ms a step
        # against 480 (chip run, PR 36).
        out, divergence = jax.lax.optimization_barrier((out, divergence))
        counts = {"index_kl": jnp.mean(divergence), "keys_selected": jnp.sum(selected),
                  "select_ties": jnp.sum(tied)}
        return out, counts, masks


# ---------------------------------------------------------------------------------
# the delta rule: a state along the sequence, by chunks
# ---------------------------------------------------------------------------------

#: chunks whose values one pass of :func:`delta_rule` makes together
#: (:func:`_within_chunks`): a pass's differences of running log-decays are ``[B, H,
#: GRAM_CHUNKS, chunk / GRAM_ROWS, GRAM_ROWS, GRAM_ROWS, d_key]`` float32 inside the
#: fusions that sum over them, and the factors of its products reach memory, 352 rows of
#: ``d_key`` float32 a chunk at chunks of 64 in sub-blocks of 8 (11.5e6 B a pass at 8 heads
#: and keys of 128). At 4 / 8 / 16 chunks a pass the rule alone, value and gradients of
#: one layer, took 11.4 / 11.2 / 12.1 ms (chip run, PR 45), and at 32 a quarter more: there
#: XLA splits the sums over the differences into a fusion an output, each with its own
#: exponentials
GRAM_CHUNKS = 8

#: rows of a sub-block of a chunk's decayed Gram matrices (:func:`_decayed_grams`)
GRAM_ROWS = 8

DELTA_STATES_NAME, DELTA_OUT_NAME = "delta_states", "delta_out"


def _product(a, b):  # in float32: a TPU's default rounds a product's operands to bfloat16
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _unit_lower_inverse(a):
    """``T = (I + A)^-1`` for ``A`` the strictly lower triangle of ``a [..., C, C]``
    float32 (what lies on or above the diagonal is not read): forward substitution by
    blocks, the block doubled each level. With ``D`` the inverse of the block diagonal of
    ``I + A`` at blocks of ``s`` rows (``D = I`` at ``s = 1``) and ``A_s`` the part of ``A``
    in the lower-left ``s`` rows and columns of each diagonal block of ``2 s``, ``D - D A_s
    D`` is the inverse of the block diagonal at ``2 s``: for one pair of blocks ``[[L11, 0],
    [A21, L22]]^-1 = [[L11^-1, 0], [-L22^-1 A21 L11^-1, L22^-1]]``, whatever the blocks'
    sizes, so a last block may be short and ``C`` is any length. ``ceil(log2 C)`` levels,
    two products of whole ``[..., C, C]`` arrays a level but for the first (``I - A_1``),
    every one at ``Precision.HIGHEST``: float32 is the precision the rule states, and a
    float32 product at a TPU's default rounds its operands to bfloat16. **Not** the finite
    product ``(I - A)(I + A^2)(I + A^4)...``, which is the same count of products and
    wrong in float32: the powers of ``A`` grow where the inverse does not (equal keys
    written at ``beta = 2``: ``A = 2 tril(1, -1)``, the inverse's entries are all 2 or
    under and ``A^32`` has entries of 1e20)."""
    c = a.shape[-1]
    row, col = np.arange(c)[:, None], np.arange(c)[None, :]

    def corner(s):  # the lower-left blocks of the diagonal blocks of 2 s
        return (row // (2 * s) == col // (2 * s)) & (row % (2 * s) >= s) & (col % (2 * s) < s)

    inverse = jnp.eye(c, dtype=a.dtype) - jnp.where(corner(1), a, 0.0)
    s = 2
    while s < c:
        below = jnp.where(corner(s), a, 0.0)
        inverse = inverse - _product(_product(inverse, below), inverse)
        s *= 2
    return inverse


@jax.custom_vjp
def _solve_unit_lower(a, rhs):
    """``X`` of ``(I + A) X = rhs`` for ``A`` the strictly lower triangle of ``a [..., C,
    C]`` and ``rhs [..., C, m]``, float32: ``T = (I + A)^-1`` by :func:`_unit_lower_inverse`
    and one product ``T rhs``. Its derivative is its own: with ``T`` and ``X`` kept, ``d rhs
    = T^T dX`` and ``dA = -tril(d rhs X^T, -1)``, two products and no pass through the
    inverse's levels."""
    return _solve_unit_lower_fwd(a, rhs)[0]


def _solve_unit_lower_fwd(a, rhs):
    inverse = _unit_lower_inverse(a)
    x = _product(inverse, rhs)
    return x, (inverse, x)


def _solve_unit_lower_bwd(res, d_x):
    inverse, x = res
    d_rhs = _product(jnp.swapaxes(inverse, -1, -2), d_x)
    return -jnp.tril(_product(d_rhs, jnp.swapaxes(x, -1, -2)), -1), d_rhs


_solve_unit_lower.defvjp(_solve_unit_lower_fwd, _solve_unit_lower_bwd)


def _decayed_grams(q, k, total):
    """The two decayed Gram matrices of a chunk, ``sum_d x_i[d] k_j[d] exp(G_i[d] -
    G_j[d])`` for ``j <= i`` and zero above the diagonal, with ``x = k`` and with ``x = q``:
    operands ``[..., C, dk]`` float32, ``total`` the running log-decays ``G``, which never
    rise along the rows -> two ``[..., C, C]`` float32. The decays differ by channel, and
    ``exp(G_i) x exp(-G_j)`` overflows where the decay is strong; around a row between the
    two it factors without. By sub-blocks of ``S =`` :data:`GRAM_ROWS` rows (a chunk that
    is no multiple of ``S`` is padded with rows without key, query or decay; one shorter
    than ``S`` is one sub-block), with ``r`` a sub-block's first row:

    - left of the sub-block's diagonal block, ``j < r <= i``: ``exp(G_i - G_j) = exp(G_i -
      G_r) x exp(G_r - G_j)``, both exponents ``<= 0`` and both factors in [0, 1], so
      those columns of both matrices are one product over the channels on the matrix unit,
      ``[k_i exp(G_i - G_r); q_i exp(G_i - G_r)] [2 S, dk]`` by ``k_j exp(G_r - G_j) [dk,
      r]``, float32 at ``Precision.HIGHEST``. A factor that underflows stands for a term
      that is smaller still. The result does not depend on ``G_r``: no gradient goes
      through it;
    - the ``C / S`` diagonal blocks: sums on the vector unit over ``[S, S, dk]``
      differences ``G_i - G_j <= 0``, ``S / C`` of what the whole chunk's would be.

    A sub-block's rows are its strip, its diagonal block and zeros, side by side."""
    c, dk = k.shape[-2:]
    s = min(GRAM_ROWS, c)
    n = -(-c // s)
    if n * s > c:
        rows = [(0, 0)] * (k.ndim - 2) + [(0, n * s - c), (0, 0)]
        q, k, total = jnp.pad(q, rows), jnp.pad(k, rows), jnp.pad(total, rows, mode="edge")
    qs, ks, totals = (x.reshape(*x.shape[:-2], n, s, dk) for x in (q, k, total))
    within = jnp.exp(jnp.where(np.tril(np.ones((s, s), bool))[..., None],
                               totals[..., :, None, :] - totals[..., None, :, :], -jnp.inf))
    diagonal = jnp.concatenate(
        [jnp.sum(x[..., :, None, :] * ks[..., None, :, :] * within, axis=-1) for x in (ks, qs)],
        axis=-2)  # [..., n, 2 S, S]
    rows = []
    for r in range(0, n * s, s):  # the sub-block of rows r .. r + S
        first = jax.lax.stop_gradient(total[..., r:r + 1, :])
        since = jnp.exp(total[..., r:r + s, :] - first)
        left = jnp.concatenate([k[..., r:r + s, :] * since, q[..., r:r + s, :] * since], axis=-2)
        until = jnp.exp(first - total[..., :r, :])
        strip = _product(left, jnp.swapaxes(k[..., :r, :] * until, -1, -2))  # [..., 2 S, r]
        rows.append(jnp.concatenate(
            [strip, diagonal[..., r // s, :, :],
             jnp.zeros((*k.shape[:-2], 2 * s, n * s - r - s), k.dtype)], axis=-1))
    rows = jnp.stack(rows, axis=-3)  # [..., n, 2 S, n S]: the keys' rows over the queries'
    return tuple(x.reshape(*k.shape[:-2], n * s, n * s)[..., :c, :c]
                 for x in (rows[..., :s, :], rows[..., s:, :]))


def _within_chunks(q, k, v, g, beta):
    """What the scan over the chunks needs of each chunk, from the chunk alone: operands
    ``[..., C, d]`` (a chunk of ``C`` tokens on the second-last axis), ``g`` float32 log-
    decays, ``beta [..., C]`` float32. With ``G`` the running sum of ``g`` inside the chunk,
    and every exponent a difference ``G_i - G_j <= 0`` of a later row and an earlier one:

    - ``A[i, j] = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` for ``j < i``, and
      ``T = (I + A)^-1`` (:func:`_solve_unit_lower`: inverted by blocks, as matrix products)
      applied to ``beta v`` and to ``beta k exp(G)``: a token's write as if the state at the
      chunk's start were zero (``w_v``), and what that state takes from it (``w_k``);
    - ``B[i, j] = sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])`` for ``j <= i``: what a query
      reads of the writes of its own chunk;
    - ``q exp(G)``: the query against the state at the chunk's start; ``k exp(G_C - G)``:
      a write as it stands in the state at the chunk's end; ``exp(G_C)``: what is left of
      the state by then.

    All float32; the two Gram matrices by sub-blocks (:func:`_decayed_grams`): matrix
    products but for the blocks on the diagonal."""
    f32 = jnp.float32
    c = q.shape[-2]
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    # the running sum as a product with a triangle of ones: a cumulative sum along an
    # axis lowers to a reduce-window on a TPU
    total = jnp.einsum("ij,...jd->...id", np.tril(np.ones((c, c), np.float32)), g,
                       precision=jax.lax.Precision.HIGHEST)
    gram_k, gram_q = _decayed_grams(q, k, total)
    seen = jnp.exp(total)
    rhs = jnp.concatenate([v, k * seen], axis=-1) * beta[..., None]
    solved = _solve_unit_lower(beta[..., None] * gram_k, rhs)
    w_v, w_k = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    last = total[..., -1:, :]
    return w_v, w_k, gram_q, q * seen, k * jnp.exp(last - total), jnp.exp(last[..., 0, :])


def _chunk_step(state, chunk, dtype):
    """One chunk of the scan: the state ``[B, H, dk, dv]`` float32 at the chunk's start and
    :func:`_within_chunks`'s values of the chunk -> (the state at its end, the outputs
    ``[B, H, C, dv]`` float32). The products take their operands in ``dtype`` and
    accumulate in float32."""
    w_v, w_k, gram_q, q_seen, k_left, left = chunk

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=jnp.float32)

    u = w_v - dot("bhck,bhkv->bhcv", w_k, state)  # the chunk's writes, the state known
    out = dot("bhck,bhkv->bhcv", q_seen, state) + dot("bhcj,bhjv->bhcv", gram_q, u)
    return left[..., None] * state + dot("bhck,bhcv->bhkv", k_left, u), out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scan_chunks(chunks, dtype):
    """The state carried over the chunks from zero: ``chunks`` are :func:`_within_chunks`'s
    values with the chunks leading (``[N, B, H, ...]``) -> (outputs ``[N, B, H, C, dv]``
    in ``dtype``, the final state ``[B, H, dk, dv]`` float32). The forward keeps the state at
    each chunk's start (``[N, B, H, dk, dv]`` float32, named, as the outputs are, so that a
    layer may keep both); the backward pass walks the chunks from the last with them, one
    chunk's step differentiated at a time: it does not run the forward scan again, and
    holds no chunk's intermediate but the one it is at."""
    return _scan_chunks_fwd(chunks, dtype)[0]


def _scan_chunks_fwd(chunks, dtype):
    w_v, w_k = chunks[0], chunks[1]
    zero = jnp.zeros((*w_k.shape[1:3], w_k.shape[-1], w_v.shape[-1]), jnp.float32)

    def step(state, chunk):
        after, out = _chunk_step(state, chunk, dtype)
        return after, (out, state)

    final, (out, states) = jax.lax.scan(step, zero, chunks)
    out = checkpoint_name(out.astype(dtype), DELTA_OUT_NAME)
    states = checkpoint_name(states, DELTA_STATES_NAME)
    return (out, final), (chunks, states)


def _scan_chunks_bwd(dtype, res, cotangents):
    chunks, states = res
    d_out, d_final = cotangents

    def step(d_after, x):
        chunk, state, d_out = x
        _, pull = jax.vjp(lambda state, chunk: _chunk_step(state, chunk, dtype), state, chunk)
        return pull((d_after, d_out.astype(jnp.float32)))

    _, d_chunks = jax.lax.scan(step, d_final, (chunks, states, d_out), reverse=True)
    return (d_chunks,)


_scan_chunks.defvjp(_scan_chunks_fwd, _scan_chunks_bwd)


def delta_rule(q, k, v, g, beta, chunk: int):
    """The gated delta rule with a decay a key channel, by chunks: q, k ``[B, T, H, dk]``,
    v ``[B, T, H, dv]``, g ``[B, T, H, dk]`` float32 (a token's log-decay of each key
    channel, ``<= 0``), beta ``[B, T, H]`` float32 -> (o ``[B, T, H, dv]`` in ``v``'s type,
    the final state ``[B, H, dk, dv]`` float32). Token by token, from ``S_0 = 0``::

        S'_t = Diag(exp(g_t)) S_{t-1}
        S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
        o_t  = S_t^T q_t

    Here ``chunk`` tokens at a time (a sequence is padded to whole chunks with tokens that
    leave the state as it is; the result does not depend on the chunk): inside a chunk the
    rule is a unit-lower-triangular system, solved for all its tokens at once
    (:func:`_within_chunks`, :data:`GRAM_CHUNKS` chunks a pass, made again in the backward
    pass; its decayed Gram matrices by sub-blocks of :data:`GRAM_ROWS` rows, matrix
    products but for the blocks on the diagonal, :func:`_decayed_grams`; the system
    inverted by blocks as matrix products, :func:`_solve_unit_lower`), and a scan carries
    the state from chunk to chunk (:func:`_scan_chunks`). The state, the Gram matrices, the
    system and the products of its inverse are float32 (``Precision.HIGHEST``); the
    products with the state and with the chunk's writes take their operands in ``q``'s
    type and accumulate in float32.
    No exponent is taken of anything but a difference of running log-decays that is ``<=
    0``, of a later row and an earlier one. Scopes: ``state`` around the scan; the
    caller's around the rest."""
    b, t, h, dk = k.shape
    chunk = min(chunk, t)
    dtype = q.dtype
    q, k, v, g, beta = (_pad_rows(x, 1, chunk) for x in (q, k, v, g, beta))
    n = q.shape[1] // chunk
    per_pass = math.gcd(n, GRAM_CHUNKS)

    def chunked(x):  # [B, T', H, ...] -> [passes, B, H, chunks a pass, C, ...]
        x = x.reshape(b, n // per_pass, per_pass, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 1), 2, 0)

    within = jax.lax.map(jax.checkpoint(lambda xs: _within_chunks(*xs)),
                         tuple(chunked(x) for x in (q, k, v, g, beta)))
    # [passes, B, H, chunks a pass, ...] -> [N, B, H, ...]
    chunks = tuple(jnp.moveaxis(x, 3, 1).reshape(n, b, h, *x.shape[4:]) for x in within)
    with jax.named_scope("state"):
        out, final = _scan_chunks(chunks, dtype)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, -1)[:, :, :t]
    return out.swapaxes(1, 2).astype(v.dtype), final


def _causal_conv(x, taps):
    """A depthwise causal convolution: x ``[B, T, channels]``, taps ``[channels, n]`` ->
    ``sum_i taps[:, i] x[t - (n - 1) + i]`` in float32, zeros before the sequence."""
    n = taps.shape[-1]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * taps[:, i].astype(jnp.float32) for i in range(n))


def _unit(x):
    """Each head's vector scaled to unit length (``x [..., d]``), in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta_block(cfg: PatternConfig, x, lp: dict):
    """Pre-norm delta-rule linear attention (:class:`Delta`, :func:`delta_rule`) and the
    residual. Returns the stream and the layer's counts: ``decay_mean`` (the mean over
    tokens, heads and channels of ``exp(g)``: how much of the state a token keeps),
    ``beta_mean`` and ``state_rms`` (of the state after the last token).

    The scope is ``attn/full``, as the latent and the indexed kinds' is: a delta layer sees
    the whole causal prefix, through its state. Everything but the four large projections
    is under ``attn/full/delta``: ``/conv`` (the three convolutions), ``/gates`` (the
    log-decay, the write strength and the output gate), ``/rule`` (the chunked rule) with
    ``/rule/state`` (the scan across the chunks) inside it; no ``/core``."""
    with jax.named_scope("attn/full"):
        b, t, _ = x.shape
        h, de = cfg.heads(DELTA), cfg.delta
        dt = x.dtype
        y = tfm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = (y @ lp[name].astype(dt) for name in ("wq", "wk", "wv"))
        with jax.named_scope("delta"):
            with jax.named_scope("conv"):
                q, k, v = (jax.nn.silu(_causal_conv(a, lp[name])) for a, name in (
                    (q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
            q = (_unit(q.reshape(b, t, h, de.d_key)) / np.sqrt(de.d_key)).astype(dt)
            k = _unit(k.reshape(b, t, h, de.d_key)).astype(dt)
            v = v.reshape(b, t, h, de.d_value).astype(dt)
            with jax.named_scope("gates"):
                through = lambda a, b_: jnp.matmul(  # noqa: E731
                    y @ lp[a].astype(dt), lp[b_].astype(dt), preferred_element_type=jnp.float32)
                step = jax.nn.softplus(through("wf_a", "wf_b") + lp["dt_bias"])
                g = -jnp.exp(lp["a_log"])[:, None] * step.reshape(b, t, h, de.d_key)
                beta = jax.nn.sigmoid(jnp.matmul(y, lp["wb"].astype(dt),
                                                 preferred_element_type=jnp.float32))
                if de.neg_eigval:
                    beta = 2.0 * beta
                gate = jax.nn.sigmoid(through("wg_a", "wg_b")).reshape(b, t, h, de.d_value)
            q, k, v, g, beta = (checkpoint_name(a, name) for a, name in zip(
                (q, k, v, g, beta), KEPT_GROUPS["delta"]))
            with jax.named_scope("rule"):
                o, final = delta_rule(q, k, v, g, beta, de.chunk)
            o = tfm.rms_norm(o, lp["o_norm"], cfg.norm_eps) * gate.astype(dt)
            counts = {"decay_mean": jnp.mean(jnp.exp(g)), "beta_mean": jnp.mean(beta),
                      "state_rms": jnp.sqrt(jnp.mean(jnp.square(final)))}
        o = o.reshape(b, t, h * de.d_value)
        return x + o @ lp["wo"].astype(dt), counts


# ---------------------------------------------------------------------------------
# the MLPs
# ---------------------------------------------------------------------------------

def _swiglu(y, w_gate, w_up, w_down, names: Optional[tuple[str, str]] = None):
    """SwiGLU of ``y``; with ``names``, the gate product (before its activation) and
    the up product carry them."""
    gate, up = y @ w_gate.astype(y.dtype), y @ w_up.astype(y.dtype)
    if names is not None:
        gate, up = checkpoint_name(gate, names[0]), checkpoint_name(up, names[1])
    return (jax.nn.silu(gate) * up) @ w_down.astype(y.dtype)


@jax.custom_vjp
def _take_rows(x, index, inverse):
    """``x[index]`` for a permutation ``index`` of the rows with inverse ``inverse``;
    the backward pass is the inverse gather, where autodiff would scatter."""
    return x[index]


_take_rows.defvjp(lambda x, index, inverse: (x[index], (index, inverse)),
                  lambda res, g: (g[res[1]], None, None))


def route(cfg: PatternConfig, y, w_router, bias=None):
    """Float32 routing of tokens ``y [N, D]`` over all ``n_experts``: sigmoid scores (or
    the softmax over all experts, as ``route_score`` says), the ``top_k`` largest, their
    weights normalised to one and scaled. With a ``bias
    [E]`` (Wang et al., arXiv:2408.15664, as DeepSeek-V3's ``noaux_tc`` with one group)
    the experts are the ``top_k`` largest of score + ``route_bias_gain`` x bias and the
    weights are their scores: the bias enters the choice and nothing else, so the loss
    sends it no gradient. What moves it is that paper's balancing rule, down where an
    expert got more than the even share of this batch's pairs and up where it got less:
    ``balance`` is a term that is always zero and whose gradient by the bias is that
    sign, +-1 an expert, so the optimizer that takes the loss's gradient applies the
    rule. Returns (weights ``[N, K]`` float32, experts ``[N, K]`` int32, and with a bias
    the count of chosen pairs that the scores alone would not have chosen and
    ``balance``, else ``None`` twice)."""
    logits = checkpoint_name(
        jnp.matmul(y.astype(jnp.float32), w_router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST), "route_logits")
    scores = jax.nn.sigmoid(logits) if cfg.route_score == SIGMOID else jax.nn.softmax(logits)
    if bias is None:
        weights, experts = jax.lax.top_k(scores, cfg.top_k)
        by_bias = balance = None
    else:
        bias = bias.astype(jnp.float32)
        _, experts = jax.lax.top_k(scores + cfg.route_bias_gain * bias, cfg.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        least = jax.lax.top_k(scores, cfg.top_k)[0][:, -1:]  # the unbiased choice's last
        by_bias = jnp.sum(weights < least)
        load = jnp.sum(experts[..., None] == jnp.arange(cfg.n_experts), axis=(0, 1))
        over = jnp.where(load * cfg.n_experts > experts.size, 1.0, -1.0)
        balance = jnp.sum((bias - jax.lax.stop_gradient(bias)) * over)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * cfg.routed_scale
    return (checkpoint_name(weights, "route_weights"), checkpoint_name(experts, "route_experts"),
            by_bias, balance)


def _rows_at(rows, place):
    """``rows [C, D]`` at ``place [N, K]``; a place of ``C`` (past the end) reads zeros."""
    return rows.at[place].get(mode="fill", fill_value=0)


@jax.custom_vjp
def _carry(y, token, place):
    """``y[token]``: for each of the ``C`` pairs carried, its token's row of ``y [N, D]``.
    ``place [N, K]`` is where each of a token's pairs went among the ``C`` (``C``: not
    carried); with it the backward pass is a gather too: each token sums its own
    pairs' cotangents, in float32, rounded once."""
    return y[token]


_carry.defvjp(
    lambda y, token, place: (y[token], place),
    lambda place, g: (
        jnp.sum(_rows_at(g, place).astype(jnp.float32), axis=1).astype(g.dtype), None, None))


@jax.custom_vjp
def _bring_back(out, weights, pair, place):
    """``[N, D]``: each token's weighted sum over its pairs of the rows of ``out [C, D]``,
    in float32, rounded once. ``weights [N, K]`` float32; ``pair [C]`` is which of the
    ``N * K`` pairs each row is, ``place [N, K]`` its inverse (``C``: not carried). The
    backward pass gathers ``C`` rows of the cotangent by token."""
    total = jnp.sum(_rows_at(out, place).astype(jnp.float32) * weights[..., None], axis=1)
    return total.astype(out.dtype)


def _bring_back_bwd(res, g):
    out, weights, pair, place = res
    rows = g[pair // weights.shape[1]].astype(jnp.float32)
    d_out = (rows * weights.reshape(-1)[pair][:, None]).astype(out.dtype)
    d_weight = jnp.sum(rows * out.astype(jnp.float32), axis=-1)  # [C]: one a pair carried
    return d_out, _rows_at(d_weight[:, None], place)[..., 0], None, None


_bring_back.defvjp(lambda *args: (_bring_back(*args), args), _bring_back_bwd)


#: the rows the dispatch carries are whole tiles of this many (the MXU's rows)
ROW_TILE = 128


def dispatch_rows(cfg: PatternConfig, n_tokens: int) -> dict:
    """How many (token, choice) pairs the expert dispatch of a sparse layer carries for
    ``n_tokens`` positions of the stream (:meth:`PatternConfig.stream` of a batch's ids),
    from the configuration and the shapes alone: ``{"path":
    "bounded", "rows": C, "pairs": n_tokens * top_k}`` where twice the even-routing
    share of the experts held, rounded up to :data:`ROW_TILE`, is under all the pairs
    (a step whose router sends more than ``C`` pairs here carries all of them, see
    :func:`routed_experts`), else ``{"path": "full", "rows": pairs, "pairs": pairs}``."""
    pairs = n_tokens * cfg.top_k
    tiles = -(-2 * pairs * cfg.experts_held[1] // (cfg.n_experts * ROW_TILE))
    if tiles * ROW_TILE < pairs:
        return {"path": "bounded", "rows": tiles * ROW_TILE, "pairs": pairs}
    return {"path": "full", "rows": pairs, "pairs": pairs}


def _expert_rows(rows, valid, group_sizes, lp: dict):
    """The held experts' SwiGLU of ``rows``, sorted by expert into groups of
    ``group_sizes``, with the weights ``lp`` in the rows' dtype; rows that are not
    ``valid`` give zero."""
    with jax.named_scope("moe/experts"):
        dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes)
        gate = jax.nn.silu(dot(rows, lp["we_gate"]))
        out = dot(gate * dot(rows, lp["we_up"]), lp["we_down"])
        # rows past the last group are whatever the grouped product left there
        return jnp.where(valid, out, 0)


def routed_experts(cfg: PatternConfig, y, lp: dict):
    """What the experts held here add for tokens ``y [N, D]``, and the routing counts.

    All ``N * top_k`` (token, choice) pairs are sorted by the local index of their
    expert, pairs for experts held elsewhere last; ``group_sizes`` counts the pairs
    of each held expert, so the grouped products compute every pair that landed here
    and no other row. The shapes are static and nothing is dropped: the rows gathered,
    multiplied and added back are the first ``C`` of the sorted order, room for twice
    the even-routing share of the experts held (:func:`dispatch_rows`); a step whose
    router sends more than ``C`` pairs here takes the full width, ``N * top_k`` rows,
    under the same ``jax.lax.cond``. Where ``C`` would not be under the full width
    (every expert held; tiny shapes) there is only the full width and no ``cond``.
    ``rows_carried`` among the counts says which it was; under a selection bias
    ``chosen_by_bias`` counts the pairs (of all ``N * top_k``) whose expert the scores
    alone would not have chosen."""
    n, d = y.shape
    k = cfg.top_k
    first, held = cfg.experts_held
    with jax.named_scope("moe/route"):
        weights, experts, by_bias, balance = route(
            cfg, y, lp["w_router"], lp.get("b_router"))
    with jax.named_scope("moe/dispatch"):
        local = experts - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(n * k)
        order = jnp.argsort(key)  # stable: the pairs of one expert stay in token order
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=order.dtype), unique_indices=True)
        order = checkpoint_name(order, "dispatch_order")
        inverse = checkpoint_name(inverse, "dispatch_inverse")
        sorted_key = key[order]
        ends = jnp.searchsorted(sorted_key, jnp.arange(held + 1), side="left")
        group_sizes = jnp.diff(ends).astype(jnp.int32)  # [held]
    landed = jnp.sum(here)
    bound = dispatch_rows(cfg, n)["rows"]
    with jax.named_scope("moe/experts"):
        experts_lp = {name: lp[name].astype(y.dtype) for name in ("we_gate", "we_up", "we_down")}

    def full(y, weights, order, inverse, sorted_key, group_sizes, experts_lp):
        with jax.named_scope("moe/dispatch"):
            valid = (sorted_key < held)[:, None]
            pairs = jnp.broadcast_to(y[:, None, :], (n, k, d)).reshape(n * k, d)
            rows = jnp.where(valid, _take_rows(pairs, order, inverse), 0)
        out = _expert_rows(rows, valid, group_sizes, experts_lp)
        with jax.named_scope("moe/combine"):
            out = _take_rows(out, inverse, order).reshape(n, k, d)
            return jnp.sum(out.astype(jnp.float32) * weights[..., None], axis=1).astype(y.dtype)

    def bounded(y, weights, order, inverse, sorted_key, group_sizes, experts_lp):
        with jax.named_scope("moe/dispatch"):
            pair = order[:bound]
            place = jnp.minimum(inverse, bound).reshape(n, k)
            valid = (sorted_key[:bound] < held)[:, None]
            rows = jnp.where(valid, _carry(y, pair // k, place), 0)
        out = _expert_rows(rows, valid, group_sizes, experts_lp)
        with jax.named_scope("moe/combine"):
            return _bring_back(out, weights, pair, place)

    operands = (y, weights, order, inverse, sorted_key, group_sizes)
    if bound < n * k:
        fits = landed <= bound
        # Each branch recomputes itself in the backward pass, so what crosses the ``cond``
        # is its operands and not the union of both branches' residuals. The weights are
        # fenced from it: left free, the compiler moves their gradients' widening and
        # padding to the stack of all layers into both branches (17 ms a step at
        # 8,192 tokens, chip run) where the sum over the layers reads each once.
        routed = jax.lax.cond(fits, jax.checkpoint(bounded), jax.checkpoint(full),
                              *operands, jax.lax.optimization_barrier(experts_lp))
        carried = jnp.where(fits, bound, n * k)
    else:
        routed, carried = full(*operands, experts_lp), jnp.asarray(n * k)
    counts = {
        "pairs_held": landed,
        "max_load": jnp.max(group_sizes),
        "mean_load": landed / held,
        "dropped": landed - jnp.sum(group_sizes),
        "rows_carried": carried,
    }
    if by_bias is not None:
        counts["chosen_by_bias"] = by_bias
    return routed, counts, balance


def _mlp_block(cfg: PatternConfig, kind: str, x, lp: dict):
    """Pre-norm MLP with the residual; a sparse one also returns its routing counts and,
    under a selection bias, :func:`route`'s ``balance``."""
    y = tfm.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if kind == DENSE:
        with jax.named_scope("mlp/dense"):
            mlp = _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"], KEPT_GROUPS["dense"])
            return x + mlp, None, None
    b, t, d = y.shape
    routed, counts, balance = routed_experts(cfg, y.reshape(b * t, d), lp)
    if not cfg.d_shared:
        return x + routed.reshape(b, t, d), counts, balance
    with jax.named_scope("moe/shared"):
        shared = _swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"], KEPT_GROUPS["shared"])
    return x + routed.reshape(b, t, d) + shared, counts, balance


# ---------------------------------------------------------------------------------
# what a layer keeps for its backward pass
# ---------------------------------------------------------------------------------

#: The named values (``jax.ad_checkpoint.checkpoint_name``) a layer may keep beside its
#: input, by group, in the order :func:`kept_residuals` takes them: most device time
#: bought for a byte kept first.
KEPT_GROUPS = {
    # the float32 router product, the sort of all pairs and the scatter that inverts it
    "routing": ("route_logits", "route_weights", "route_experts",
                "dispatch_order", "dispatch_inverse"),
    # the keys an indexer selected, as the mask its attention runs under: 32 counting
    # passes over every block of index scores (and the scores themselves, once)
    "selection": ("select_mask",),
    # x + attention @ wo: the output matrix's forward product
    "stream": ("attn_stream",),
    # the attention products' forward; without the log-sum-exp the kernel runs again
    "attention": (attention.OUT_NAME, attention.LSE_NAME),
    # the states a delta layer's scan left at each chunk's start, and the rule's output:
    # without them the backward pass runs the forward scan a second time
    "states": (DELTA_STATES_NAME, DELTA_OUT_NAME),
    # q and k after the rotary, v: the projections and the rotary
    "qkv": ("attn_q", "attn_k", "attn_v"),
    # a delta layer's q, k and v after the convolutions and the scaling, its log-decays
    # and its write strengths: three projections, the convolutions and the gates' maps
    "delta": ("delta_q", "delta_k", "delta_v", "delta_g", "delta_beta"),
    # an indexer's queries and key after the rotary, its head weights: the same of its own
    "index": ("index_q", "index_w", "index_k"),
    # the gate and up products of the shared expert, then of the dense MLP
    "shared": ("shared_gate", "shared_up"),
    "dense": ("dense_gate", "dense_up"),
    # the heads' summed probabilities an indexer is taught by: the kernel that gives them
    # (on the blocks, every head's ``QK^T`` and softmax) a second time. Float32 a (query,
    # key), each group's rows against its keys: 168e6 B a layer at 8,192 tokens
    "target": ("index_target",),
    # an indexer's scores, which its divergence reads again in the backward pass: their
    # forward kernel (or blocks) a second time. Float32 and of the target's shapes
    "scores": ("index_scores",),
}


#: the memory of the device this process computes on (the dense model's, whose rule is this one)
device_memory_bytes = tfm.device_memory_bytes


def _group_bytes(cfg: PatternConfig, spec: Layer, n_tokens: int, seq: int) -> dict:
    """Bytes of each group of :data:`KEPT_GROUPS` in one layer over ``n_tokens`` positions
    of the stream in sequences of ``seq`` of them."""
    act = jnp.dtype(cfg.dtype).itemsize
    heads = cfg.held(spec.n_heads)
    sparse = spec.mlp == SPARSE
    groups = dict.fromkeys(KEPT_GROUPS, 0)
    groups.update(
        routing=n_tokens * 4 * (cfg.n_experts + 4 * cfg.top_k) if sparse else 0,
        stream=n_tokens * cfg.d_model * act,
        shared=2 * n_tokens * cfg.d_shared * act if sparse else 0,
        dense=0 if sparse else 2 * n_tokens * cfg.d_ff * act)
    if spec.attn == DELTA:
        de = cfg.delta
        chunks = n_tokens // seq * -(-seq // min(de.chunk, seq))
        groups.update(
            states=heads * (chunks * de.d_key * de.d_value * 4 + n_tokens * de.d_value * act),
            delta=n_tokens * heads * (act * (2 * de.d_key + de.d_value) + 4 * de.d_key + 4))
        return groups
    score, value = _widths(cfg, spec.attn)
    kv_heads = heads if spec.attn == LATENT else cfg.kv_heads
    indexed = spec.attn == INDEXED
    # the kernels take one width of whole lane groups, and an indexed kind goes by them
    # where :func:`attention_paths` says so; only they make a log-sum-exp
    kernels = score == value and not score % attention.LANES and (
        not indexed or attention_paths(cfg, seq)[INDEXED]["path"] == "kernel")
    lse = 4 * heads if kernels else 0
    if indexed:
        ix, block = cfg.indexer, min(cfg.attn_block, seq)
        groups["index"] = n_tokens * (act * (ix.n_heads + 1) * ix.head_dim + 4 * ix.n_heads)
        # a byte a (query, key) of every group of query blocks that really selects,
        # against the group's keys (:func:`indexed_attention`)
        padded = -(-seq // block) * block
        rows = key_groups(padded, block)
        groups["selection"] = n_tokens // seq * sum(
            (keys - first) * keys for first, keys in rows if keys > ix.top_k)
        # float32 a (query, key) of every group's rows against the group's keys, each
        groups["target"] = groups["scores"] = 4 * n_tokens // seq * sum(
            (keys - first) * keys for first, keys in rows)
    groups.update(
        attention=n_tokens * (heads * value * act + lse),
        qkv=n_tokens * act * (heads * score + kv_heads * (score + value)))
    return groups


def _rule_bytes(cfg: PatternConfig, n_tokens: int, seq: int) -> int:
    """The float32 values a delta layer's rule holds beside what the layer keeps, forward
    as backward: :func:`_within_chunks`'s six values of every chunk and their cotangents,
    and the factors of one pass's Gram products with theirs (:func:`_decayed_grams`: of
    each chunk of the pass the keys and queries against their sub-block's first row, ``2
    chunk`` rows of ``d_key``, and the keys before each sub-block against its first row).
    A pass's differences of running log-decays live inside the fusions that sum over
    them: no ``[GRAM_ROWS, GRAM_ROWS, d_key]`` array reaches the device's memory (compile
    for a v5e, PR 45; PR 39 for whole chunks')."""
    de = cfg.delta
    chunk = min(de.chunk, seq)
    per_token = 4 * (de.d_value + 3 * de.d_key + chunk)
    blocks = -(-chunk // GRAM_ROWS)
    factors = 4 * GRAM_CHUNKS * (2 * chunk + GRAM_ROWS * blocks * (blocks - 1) // 2) * de.d_key
    return cfg.heads(DELTA) * 2 * (n_tokens * per_token + n_tokens // seq * factors)


def kept_residuals(cfg: PatternConfig, n_tokens: int, memory_bytes: Optional[int],
                   seq: Optional[int] = None) -> dict:
    """What each layer keeps for its backward pass beside its input, at ``n_tokens``
    positions of the stream a step (:meth:`PatternConfig.stream` of the batch's ids; in
    sequences of ``seq``; ``None``: one sequence) on a device of
    ``memory_bytes`` (``None``: no limit stated, everything is kept), from the
    configuration and the shapes alone: ``{"names": the names the
    layers' ``jax.checkpoint`` keeps, "bytes": what they hold over all layers,
    "per_layer": {group: [bytes in each layer]}, "step_bytes": what the step holds
    anyway}``.

    The groups of :data:`KEPT_GROUPS` that hold anything in some layer of this
    description are taken in order while the bytes kept and ``step_bytes`` stay inside
    the memory, and the first that does not fit ends the list: a longer step or a smaller
    device gets the shorter list, down to none (``names`` empty: every layer recomputes
    its whole forward). ``step_bytes`` is
    16 B a parameter (float32 weights, two moments, gradients), every layer's input,
    the float32 logits with their cotangent (over the noised half alone under
    :class:`Diffusion`), and all the groups of the largest layer
    once (the layer whose backward pass runs holds them, kept or recomputed), with the
    float32 values of the rule where that layer is a delta layer (:func:`_rule_bytes`)."""
    seq = seq or n_tokens
    per_layer = [_group_bytes(cfg, spec, n_tokens, seq) for spec in cfg.layers]
    n_params = sum(math.prod(leaf.shape) for leaf in
                   jax.tree.leaves(describe_params(cfg), is_leaf=_is_leaf))
    step_bytes = (16 * n_params
                  + len(cfg.layers) * n_tokens * cfg.d_model * jnp.dtype(cfg.dtype).itemsize
                  + 2 * (n_tokens // 2 if cfg.diffusion else n_tokens) * cfg.vocab_size * 4
                  + max(sum(groups.values())
                        + (_rule_bytes(cfg, n_tokens, seq) if spec.attn == DELTA else 0)
                        for spec, groups in zip(cfg.layers, per_layer)))
    kept = {"names": [], "bytes": 0, "per_layer": {}, "step_bytes": step_bytes}
    for group, names in KEPT_GROUPS.items():
        layers = [groups[group] for groups in per_layer]
        if not any(layers):
            continue
        if (memory_bytes is not None
                and step_bytes + kept["bytes"] + sum(layers) > memory_bytes):
            break
        kept["names"] += names
        kept["bytes"] += sum(layers)
        kept["per_layer"][group] = layers
    return kept


# ---------------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------------

def _rope_tables(cfg: PatternConfig, t: int) -> dict:
    """{kind: its layers' rotary tables at the ``t`` positions of the stream}, of the kinds
    that have one. Under :class:`Diffusion` the stream's two halves are the same positions:
    the table of ``t / 2`` positions, twice."""
    if cfg.diffusion is not None and cfg.rope(FULL) is not None:
        cos, sin = rope_tables(cfg.rope(FULL), cfg.rotary_width(FULL), t // 2)
        return {FULL: (jnp.concatenate([cos, cos]), jnp.concatenate([sin, sin]))}
    tables = {kind: rope_tables(cfg.rope(kind), cfg.rotary_width(kind), t)
              for kind in ATTENTION_KINDS if cfg.count(kind) and cfg.rope(kind) is not None}
    if cfg.count(INDEXED):  # the indexer's heads turn by the same table at their own width
        tables[INDEXED] += rope_tables(cfg.rope(INDEXED), cfg.indexer.head_dim, t)
    return tables


def _layer_params(params: dict, cfg: PatternConfig):
    """(the layer's description, its attention leaves, its MLP leaves) of each layer in
    turn, out of the per-kind stacks."""
    seen = dict.fromkeys((*ATTENTION_KINDS, DENSE, SPARSE), 0)
    for spec in cfg.layers:
        attn_lp = jax.tree.map(lambda w: w[seen[spec.attn]], params["attn"][spec.attn])
        mlp_lp = jax.tree.map(lambda w: w[seen[spec.mlp]], params["mlp"][spec.mlp])
        seen[spec.attn] += 1
        seen[spec.mlp] += 1
        yield spec, attn_lp, mlp_lp


class Noise(NamedTuple):
    """One step's draws (:class:`Diffusion`), each ``[B, L]``: the noised copy ``xt``
    (int32), which positions the draw masked (bool), and the level ``t_b`` of each
    position's block (float32)."""

    noised: jax.Array
    masked: jax.Array
    level: jax.Array


def draw_noise(tokens: jax.Array, noise: Diffusion) -> Noise:
    """The draws of :class:`Diffusion` for tokens ``[B, L]``, to its letter: a key a
    sequence from the sequence's own ids, a level a block, a uniform a position."""
    length = tokens.shape[1]
    base = jax.random.key(noise.noise_seed, impl="threefry2x32")

    def of_sequence(ids):
        key_b, key_i = jax.random.split(jax.random.fold_in(base, jnp.sum(ids.astype(jnp.uint32))))
        level = noise.eps + (1.0 - noise.eps) * jax.random.uniform(
            key_b, (-(-length // noise.block),), jnp.float32)
        level = jnp.repeat(level, noise.block)[:length]
        return jax.random.uniform(key_i, (length,), jnp.float32) < level, level

    masked, level = jax.vmap(of_sequence)(tokens)
    return Noise(jnp.where(masked, jnp.asarray(noise.mask_id, tokens.dtype), tokens), masked, level)


def _stream(tokens: jax.Array, cfg: PatternConfig):
    """(the ids of the stream the layers see, the step's :class:`Noise` or ``None``): the
    batch itself, or under :class:`Diffusion` ``[x0 ; xt]`` (scope ``diffuse/noise``)."""
    if cfg.diffusion is None:
        return tokens, None
    with jax.named_scope("diffuse/noise"):
        noise = draw_noise(tokens, cfg.diffusion)
        return jnp.concatenate([tokens, noise.noised], axis=1), noise


def _forward(params: dict, tokens: jax.Array, cfg: PatternConfig):
    """:func:`forward`, the sum of the sparse layers' ``balance`` (:func:`route`), and the
    step's :class:`Noise` (``None`` without :class:`Diffusion`)."""
    tokens, noise = _stream(tokens, cfg)
    x = params["embed"].astype(cfg.dtype)[tokens]
    t = tokens.shape[1]
    tables = _rope_tables(cfg, t)

    def layer(x, attn_lp, mlp_lp, spec: Layer):
        attn_counts = None  # an indexed or a delta layer's own
        if spec.attn == LATENT:
            x = _latent_block(cfg, x, attn_lp, *tables[LATENT])
        elif spec.attn == INDEXED:
            x, attn_counts, _ = _indexed_block(cfg, x, attn_lp, *tables[INDEXED])
        elif spec.attn == DELTA:
            x, attn_counts = _delta_block(cfg, x, attn_lp)
        else:
            x = _attn_block(cfg, spec.attn, x, attn_lp, *tables.get(spec.attn, ()))
        return (*_mlp_block(cfg, spec.mlp, checkpoint_name(x, "attn_stream"), mlp_lp),
                attn_counts)

    # each layer keeps its input and the values named here for its backward pass, and
    # recomputes the rest of its forward there
    kept = kept_residuals(cfg, tokens.size, device_memory_bytes(), t)
    policy = jax.checkpoint_policies.save_only_these_names(*kept["names"])

    counts, attn_counts, balance = [], {}, None
    for spec, attn_lp, mlp_lp in _layer_params(params, cfg):
        x, layer_counts, layer_balance, layer_attn = jax.checkpoint(
            functools.partial(layer, spec=spec), policy=policy)(x, attn_lp, mlp_lp)
        if layer_counts is not None:
            counts.append(layer_counts)
        if layer_balance is not None:
            balance = layer_balance if balance is None else balance + layer_balance
        if layer_attn is not None:
            attn_counts.setdefault(spec.attn, []).append(layer_attn)
    if noise is not None:  # the head reads the noised half alone
        x = x[:, t // 2:]
    x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *counts) if counts else {}
    for of_kind in attn_counts.values():
        stacked.update(jax.tree.map(lambda *xs: jnp.stack(xs), *of_kind))
    return logits, stacked, balance, noise


def forward(params: dict, tokens: jax.Array, cfg: PatternConfig):
    """tokens ``[B, T]`` -> (logits ``[B, T, V]`` float32 (under :class:`Diffusion` of the
    noised copy of the step's own draws), counts: a dict of ``[sparse
    layers]`` arrays of routing counts, see :func:`routed_experts`, and of ``[indexed
    layers]`` arrays ``index_kl``, ``keys_selected``, ``select_ties``, see
    :func:`_indexed_block`, and of ``[delta layers]`` arrays ``decay_mean``, ``beta_mean``,
    ``state_rms``, see :func:`_delta_block`)."""
    return _forward(params, tokens, cfg)[:2]


def choices(params: dict, tokens: jax.Array, cfg: PatternConfig) -> dict:
    """What the forward pass of tokens ``[B, T]`` chose where it chooses: ``{"selected":
    [indexed layers, B, T, T] bool`` (query by key: the keys each indexed layer's
    attention read), ``"experts": [sparse layers, B, T, top_k] int32`` (of all experts of
    the deployment, in :func:`route`'s order)}``, a key only where the pattern has such
    layers; under :class:`Diffusion` over the ``T = 2 L`` positions of the step's own stream.
    The arithmetic is the training step's own (the same blocks, the same types),
    with nothing kept and nothing differentiated: for a comparison with other arithmetic
    on the same choices, since near a tie a rounding decides the choice, and the choice
    then moves every number downstream by far more than the rounding did."""
    tokens = _stream(tokens, cfg)[0]
    b, t = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    tables = _rope_tables(cfg, t)
    chose = {"selected": [], "experts": []}
    for spec, attn_lp, mlp_lp in _layer_params(params, cfg):
        if spec.attn == LATENT:
            x = _latent_block(cfg, x, attn_lp, *tables[LATENT])
        elif spec.attn == INDEXED:
            x, _, masks = _indexed_block(cfg, x, attn_lp, *tables[INDEXED])
            rows = [jnp.pad(m, ((0, 0), (0, 0), (0, masks[-1].shape[2] - m.shape[2])))
                    for m in masks]
            chose["selected"].append(jnp.concatenate(rows, axis=1)[:, :t, :t])
        elif spec.attn == DELTA:
            x = _delta_block(cfg, x, attn_lp)[0]
        else:
            x = _attn_block(cfg, spec.attn, x, attn_lp, *tables.get(spec.attn, ()))
        if spec.mlp == SPARSE:
            y = tfm.rms_norm(x, mlp_lp["mlp_norm"], cfg.norm_eps).reshape(b * t, -1)
            experts = route(cfg, y, mlp_lp["w_router"], mlp_lp.get("b_router"))[1]
            chose["experts"].append(experts.reshape(b, t, cfg.top_k))
        x = _mlp_block(cfg, spec.mlp, x, mlp_lp)[0]
    return {name: jnp.stack(layers) for name, layers in chose.items() if layers}


def loss_and_counts(params: dict, tokens: jax.Array, cfg: PatternConfig):
    """Next-token cross-entropy over tokens ``[B, T]`` (the last position's logits
    dropped, as in the dense model) and the counts of each sparse, each indexed and each
    delta layer.
    Under a selection bias the loss carries the layers' ``balance``: nothing in value, the
    balancing rule in the gradient (:func:`route`). With indexed layers it carries the
    mean of their ``index_kl``, the indexers' own loss: by the two ``stop_gradient``s of
    :func:`_indexed_block` it is the only term the indexers' leaves get a gradient from,
    and they are the only leaves it reaches.

    Under :class:`Diffusion` the cross-entropy is its masked-token loss over the noised
    blocks, each position's logits against its own id with the weight ``1 / t`` of its
    block (scope ``diffuse/loss``), and the counts gain ``masked_share`` (of the
    positions), ``weight_mean`` and ``weight_max`` (of ``1 / t`` over the masked
    positions), ``loss_unweighted`` (the mean NLL over the masked positions) and
    ``pairs_read`` (the mean number of keys a query of the stream reads, from positions:
    ``(L + block) / 2`` at whole blocks)."""
    logits, counts, balance, noise = _forward(params, tokens, cfg)
    if noise is None:
        loss = tfm.token_nll(logits[:, :-1], tokens[:, 1:]).mean()
    else:
        with jax.named_scope("diffuse/loss"):
            nll = tfm.token_nll(logits, tokens)
            weight = jnp.where(noise.masked, 1.0 / noise.level, 0.0)
            loss = jnp.sum(weight * nll) / tokens.size
            n_masked = jnp.maximum(jnp.sum(noise.masked), 1)
            block = cfg.diffusion.block
            counts = {
                **counts, "masked_share": jnp.mean(noise.masked),
                "weight_mean": jnp.sum(weight) / n_masked, "weight_max": jnp.max(weight),
                "loss_unweighted": jnp.sum(jnp.where(noise.masked, nll, 0.0)) / n_masked,
                # a query of either half reads the positions up to the end of its block
                "pairs_read": jnp.float32(np.minimum(
                    (np.arange(tokens.shape[1]) // block + 1) * block, tokens.shape[1]).mean()),
            }
    if balance is not None:
        loss = loss + balance
    if "index_kl" in counts:
        loss = loss + jnp.mean(counts["index_kl"])
    return loss, counts


def loss_fn(params: dict, tokens: jax.Array, cfg: PatternConfig) -> jax.Array:
    return loss_and_counts(params, tokens, cfg)[0]


def make_train_step(cfg: PatternConfig, optimizer=None):
    """(train_step, init_opt_state) — jit-ready, the dense model's contract."""
    return tfm.make_train_step_from_loss(
        lambda params, tokens: loss_fn(params, tokens, cfg), optimizer)
