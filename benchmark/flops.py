"""Operations the algorithms need, counted from shapes.

The counts are the least the algorithm needs, so that a share of a peak computed from
them cannot pass 100%: matmul operations of the parameters a token really touches
(top-k experts only, no capacity padding, no recomputation, the embedding lookup not
counted), the causal half of the attention products, and nothing for norms, softmax,
the optimizer or casts.

Which parameters a token touches is the architecture's to say: every family file states
``train_flops_per_token(config, seq)`` (``benchmark/families/<family>.py``) from the
pieces below, which take sizes and read no configuration key.
"""

from __future__ import annotations


def gqa_projection_params(d: int, heads: int, kv_heads: int, head_dim: int) -> int:
    """Parameters of one grouped-query attention layer's q, k, v and output matrices."""
    return d * heads * head_dim + 2 * d * kv_heads * head_dim + heads * head_dim * d


def swiglu_params(d: int, f: int) -> int:
    """Parameters of one gate/up/down MLP."""
    return 3 * d * f


def causal_attention_train_flops(seq: int, heads: int, head_dim: int) -> float:
    """Forward and backward of one token's attention in one layer, in a sequence of
    ``seq``: the causal half of QK^T and PV (2 products x 2 operations x seq/2 keys x
    heads x head size forward, three times that with the backward pass)."""
    return 6.0 * seq * heads * head_dim


def matmul_train_flops(params_per_token: int) -> float:
    """Forward and backward of one token: 6 operations per parameter of the matrices
    it is multiplied with."""
    return 6.0 * params_per_token


def mfu_percent(cfg: dict, seq: int, tokens_per_s: float, chips: int, peak_flops: float) -> float:
    from . import harness

    per_token = harness.load_family(cfg).train_flops_per_token(cfg, seq)
    return 100.0 * per_token * tokens_per_s / (chips * peak_flops)
