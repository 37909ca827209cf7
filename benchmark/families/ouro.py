"""Family ``ouro``: a looped decoder (LoopLM, arXiv:2510.25741). A dense stack of
multi-head attention with rotary positions and a SwiGLU MLP under sandwich norms runs
``total_ut_steps`` times on the same weights, the final norm closing every pass; after
every pass the stream leaves through the head, and a learned gate mixes the passes' losses
(``tpu_resiliency/models/transformer.py``, the dense model, by its description).

Everything the benchmark knows of the architecture, and the only file that imports the
program's model (inside the functions). A configuration of this family states the
published ``config.json``; ``num_hidden_layers`` counts the layers one pipeline stage
holds and ``layer_types`` is cut to them; ``assumed.exit_beta`` is the weight of the
entropy of the exit distribution in the loss.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/ouro.py``
REFERENCE = "ouro"

#: the tiny preset: three layers, three passes, three exits. The limits are the tiny
#: model's own, from 12 seeds on the CPU (the program, and the reference in bf16 and in
#: fp8, against the float32 reference; a seed's worst loss of three, the first gradient's
#: worst leaf, the parameter change's): sound up to 0.0048 / 0.0171 (the next 0.0080) /
#: 0.0020, bf16 up to 0.0033 / 0.0064 / 0.0016, fp8 from 0.0067 / 0.0194 / 0.0056. The
#: parameter change separates every seed and is the limit the control fails by; the
#: other two bound sound runs. One pass fewer than stated reads a gradient gap of 0.048 or
#: more and a change of 0.0055 or more, a step that returns its state unchanged a change of
#: 1.0
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
    "num_hidden_layers": 3, "layer_types": ["full_attention"] * 3, "total_ut_steps": 3,
    "batch": [2, 32],
    "limits": {"loss_abs": 0.01, "grad_norm_gap": 0.03, "change_norm_gap": 0.0035},
}

_dense = harness.load_by_path("families", "dense")
init_params, param_specs = _dense.init_params, _dense.param_specs


def program_config(config: dict, seq: int):
    from tpu_resiliency.models import transformer

    # the program implements one reading of these keys
    layer_types = config["layer_types"]
    for key, got, want in (
            ("hidden_act", config["hidden_act"], "silu"),
            ("use_sliding_window", config["use_sliding_window"], False),
            ("rope_scaling", config["rope_scaling"], None),
            ("tie_word_embeddings", config["tie_word_embeddings"], False),
            ("layer_types", layer_types, ["full_attention"] * config["num_hidden_layers"])):
        if got != want:
            raise harness.NoResult(f"{key} = {got!r} is not what the program computes")
    try:
        cfg = transformer.TransformerConfig(
            **_dense.transformer_keys(config, seq), n_passes=config["total_ut_steps"],
            sandwich_norms=True, exit_beta=float(config["assumed"]["exit_beta"]),
            norm_eps=float(config["rms_norm_eps"]), attention="kernel")
    except TypeError as e:  # a program from before the loop
        raise harness.NoResult(f"this program's dense model runs no stack twice: {e}")
    return _dense.checked(cfg, config)


def make_train_step(cfg, optimizer=None):
    """``optimizer`` is a configuration's ``optimizer`` key, ``{"lr": <float>}``: AdamW as
    the training contract has it at that rate; none, the contract's own 3e-4."""
    from tpu_resiliency.models import transformer

    if optimizer is not None:
        import optax

        optimizer = optax.adamw(float(optimizer["lr"]), weight_decay=0.01)
    return transformer.make_train_step(cfg, optimizer)


# -- operations and bytes, the least the algorithm needs ---------------------------

def train_flops_per_token(config: dict, seq: int) -> float:
    """``total_ut_steps`` times what one pass and its exit need: every layer's attention
    and MLP matrices and the head (6 operations a parameter), and the causal half of every
    layer's attention products. Nothing for the recomputation, the gate's 2,048 products
    or the norms."""
    mlp = flops.swiglu_params(config["hidden_size"], config["intermediate_size"])
    return config["total_ut_steps"] * _dense.block_train_flops(config, seq, mlp)


def attention_core_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's attention products, forward and backward: every
    layer of every pass over the causal half; bytes as ``families/laguna.py`` counts them
    (q, k, v read and the output written once forward; those four and the output's
    cotangent read, and three cotangents written, backward; bf16)."""
    heads, kv_heads, dh = (config["num_attention_heads"], config["num_key_value_heads"],
                           config["head_dim"])
    uses = config["total_ut_steps"] * config["num_hidden_layers"] * batch * seq
    return (uses * flops.causal_attention_train_flops(seq, heads, dh),
            uses * dh * 2 * (5 * heads + 6 * kv_heads))
