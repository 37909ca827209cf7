"""Operations and bytes the algorithms need, counted from a configuration's shapes.

The counts are the least the algorithm needs, so that a share of a peak computed from
them cannot pass 100%: matmul operations of the parameters a token really touches
(top-k experts only, no capacity padding, no recomputation, the embedding lookup not
counted), the causal half of the attention products, and nothing for norms, softmax,
the optimizer or casts.
"""

from __future__ import annotations


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters of the matrices one token is multiplied with in a forward pass."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = d * h * dh + 2 * d * hkv * dh + h * dh * d
    if "num_local_experts" in cfg:
        mlp = d * cfg["num_local_experts"] + cfg["num_experts_per_tok"] * 3 * d * f
    else:
        mlp = 3 * d * f
    return cfg["num_hidden_layers"] * (attention + mlp) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``: 6 operations per
    matmul parameter, and for attention the causal half of QK^T and PV (2 products x
    2 operations x seq/2 keys x heads x head size forward, three times that with the
    backward pass)."""
    attention = 6 * seq * cfg["num_attention_heads"] * cfg["head_dim"] * cfg["num_hidden_layers"]
    return 6.0 * matmul_params_per_token(cfg) + attention


def mfu_percent(cfg: dict, seq: int, tokens_per_s: float, chips: int, peak_flops: float) -> float:
    return 100.0 * train_flops_per_token(cfg, seq) * tokens_per_s / (chips * peak_flops)
