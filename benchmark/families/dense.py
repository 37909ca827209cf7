"""Family ``dense``: grouped-query attention with rotary positions and a SwiGLU MLP,
one homogeneous layer scanned (``tpu_resiliency/models/transformer.py``).

Everything the benchmark knows of the architecture, and the only file that imports the
program's model: the program's side is imported inside the functions, so that loading
this file for the reference's name or the operation count imports nothing of it.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/<REFERENCE>.py``: ``init_params(seed, config)`` and
#: ``loss(params, tokens, config, precision)``, with the program's leaf paths
REFERENCE = "model"

#: overrides that shrink a configuration for the CPU rehearsal and the tests under
#: benchmark/tests; never used by a cell. The limits are the tiny model's own, set as the
#: configurations' are: above the largest gap of sound runs (bf16 reference and the
#: program, six seeds on the CPU: loss 0.0026, gradient 0.0027, change 0.0014) and below
#: the fp8 control's smallest (loss 0.0096, gradient 0.0134)
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "batch": [2, 32],
    "limits": {"loss_abs": 0.005, "grad_norm_gap": 0.006, "change_norm_gap": 0.004},
}


def transformer_keys(config: dict, seq: int) -> dict:
    """The published keys under the names ``TransformerConfig`` gives them."""
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_ff=config["intermediate_size"],
        max_seq_len=seq, rope_theta=float(config["rope_theta"]),
    )


def checked(cfg, config: dict):
    """``TransformerConfig`` derives the head size; the configuration file states it."""
    if cfg.head_dim != config["head_dim"]:
        raise harness.NoResult("head_dim of the configuration is not hidden_size / heads")
    return cfg


def program_config(config: dict, seq: int):
    from tpu_resiliency.models import transformer

    return checked(transformer.TransformerConfig(**transformer_keys(config, seq)), config)


def init_params(key, cfg):
    from tpu_resiliency.models import transformer

    return transformer.init_params(key, cfg)


def make_train_step(cfg):
    from tpu_resiliency.models import transformer

    return transformer.make_train_step(cfg)


def param_specs(cfg):
    from tpu_resiliency.parallel import mesh

    return mesh.param_specs(cfg)


def attention_params(config: dict) -> int:
    return flops.gqa_projection_params(
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"])


def block_train_flops(config: dict, seq: int, mlp_params: int) -> float:
    """Every layer's attention matrices, ``mlp_params`` (what one token touches of a
    layer's MLP), the head, and the causal attention products."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    matmul = layers * (attention_params(config) + mlp_params) + d * config["vocab_size"]
    attention = layers * flops.causal_attention_train_flops(
        seq, config["num_attention_heads"], config["head_dim"])
    return flops.matmul_train_flops(matmul) + attention


def train_flops_per_token(config: dict, seq: int) -> float:
    return block_train_flops(
        config, seq, flops.swiglu_params(config["hidden_size"], config["intermediate_size"]))
