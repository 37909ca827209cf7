"""Family ``sdar``: a Qwen3-MoE-style decoder trained by block diffusion (SDAR, after
BD3-LM's vectorised training, arXiv:2503.09573), as the pattern of layers of
``tpu_resiliency/models/pattern.py``: in every layer grouped-query heads with a norm on
each head's q and k and no gate, then a sparse MLP (a softmax router over all experts of
the deployment, the top-k renormalised, the routed experts this chip holds, no shared
expert); no dense layer. What differs from every other family is what a step is
(``pattern.Diffusion``): the stack runs once on the clean copy of each sequence beside its
noised copy, ``2 L`` positions under a mask that is neither causal nor a band, and the loss
is a masked-token loss over the noised blocks with a weight ``1 / t`` a block, on logits of
the same position.

Everything the benchmark knows of the architecture, and the only file that imports the
program's model (inside the functions). A configuration of this family states the
published ``config.json`` whole, and the objective's own numbers under ``diffusion``
(``block_length``, ``eps``, ``noise_seed``, ``mask_token_id``: the last row of the slice
held). ``num_experts`` and ``vocab_size`` count what is held here; ``deployment`` gives the
published counts and which experts these are. ``seq`` is a sequence's ids, ``L``; the
stream is ``2 L``.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/sdar.py``
REFERENCE = "sdar"

#: the tiny preset: three layers of eight heads over two KV heads, 16 experts of which 4
#: are held, sequences of 64 ids in blocks of 4 (a stream of 128, eight attention blocks
#: of 16 rows). The limits are the tiny model's own, from 12 seeds on the CPU (the program
#: against the float32 reference on the program's experts; the bf16 and the fp8 reference
#: against it on its own): loss sound up to 0.0031 and bf16 up to 0.0019, fp8 0.0012-0.0379
#: (over 0.0065 on 11 seeds of 12); gradient sound up to 0.0283 (``w_router``; next 0.0155)
#: and bf16 up to 0.0049, fp8 0.0101-0.0715 (over 0.04 on the seed whose loss is under);
#: parameter change sound up to 0.0099 (next 0.0041), bf16 up to 0.0034, fp8 0.0028-0.0077:
#: it only bounds a sound run. The weight ``1 / t`` has a heavy tail (a block whose level is
#: near ``eps`` and that still masks a position weighs it a thousandfold), and at 256 ids a
#: step a wrong loss (the clean half left out, the weights dropped) is off by tenths and more
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 4, "num_experts_per_tok": 4, "vocab_size": 256,
    "diffusion": {"block_length": 4, "eps": 1e-3, "noise_seed": 0, "mask_token_id": 255},
    "deployment": {"chips_per_layer": 4, "num_experts": 16, "experts_held": [0, 4]},
    "attention_block": 16,
    "batch": [4, 64],
    "limits": {"loss_abs": 0.0065, "grad_norm_gap": 0.04, "change_norm_gap": 0.02},
}


def program_config(config: dict, seq: int):
    try:
        from tpu_resiliency.models import pattern
    except ImportError as e:  # a program from before the model
        raise harness.NoResult(f"this program has no pattern-of-layers model: {e}")
    if not hasattr(pattern, "Diffusion"):  # a program from before the objective
        raise harness.NoResult(
            "this program's pattern-of-layers model has no block-diffusion objective")

    # the program implements one reading of these switches
    for key, got, want in (
            ("attention_bias", config["attention_bias"], False),
            ("hidden_act", config["hidden_act"], "silu"),
            ("norm_topk_prob", config["norm_topk_prob"], True),
            ("tie_word_embeddings", config["tie_word_embeddings"], False),
            ("decoder_sparse_step", config["decoder_sparse_step"], 1),
            ("mlp_only_layers", config["mlp_only_layers"], []),
            ("use_sliding_window", config["use_sliding_window"], False),
            ("sliding_window", config["sliding_window"], None),
            ("rope_scaling", config["rope_scaling"], None)):
        if got != want or type(got) is not type(want):
            raise harness.NoResult(f"{key} = {got!r} is not what the program computes")
    first, held = config["deployment"]["experts_held"]
    if held != config["num_experts"]:
        raise harness.NoResult("num_experts is not the count of deployment.experts_held")
    noise = config["diffusion"]
    if noise["mask_token_id"] != config["vocab_size"] - 1:
        raise harness.NoResult("diffusion.mask_token_id is not the last row of the slice held")
    if seq % noise["block_length"]:
        raise harness.NoResult(f"a sequence of {seq} ids is not whole blocks of "
                               f"{noise['block_length']}")
    heads = config["num_attention_heads"]
    program = pattern.PatternConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        head_dim=config["head_dim"], n_kv_heads=config["num_key_value_heads"],
        layers=(pattern.Layer(pattern.FULL, heads, pattern.SPARSE),) * config["num_hidden_layers"],
        gate=None, head_norms=True, rope_full=pattern.Rope(float(config["rope_theta"])),
        diffusion=pattern.Diffusion(
            block=noise["block_length"], eps=float(noise["eps"]),
            noise_seed=noise["noise_seed"], mask_id=noise["mask_token_id"]),
        route_score=pattern.SOFTMAX, d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"], d_shared=0,
        n_experts=config["deployment"]["num_experts"], top_k=config["num_experts_per_tok"],
        experts_held=(first, held), routed_scale=1.0, norm_eps=config["rms_norm_eps"],
        attn_block=config["attention_block"],
    )

    def choices(params, tokens):
        import jax

        # traced into the caller's program, but in the program's own matmul precision: the
        # reference's ``highest`` is no type of the program's bfloat16 grouped products
        with jax.default_matmul_precision(None):
            return pattern.choices(params, tokens, program)

    # ``correct`` compares the two sides on the experts the program chose over the stream
    # of the step's own draws (reference/sdar.py, "Choices"): the reference, which gets
    # this same dict, asks here
    config["choices"] = choices
    return program


# the program's side is the pattern-of-layers model's, as family ``laguna`` reaches it, and
# its AdamW at the file's rate, as family ``solar`` hands it over
_laguna = harness.load_by_path("families", "laguna")
init_params, param_specs = _laguna.init_params, _laguna.param_specs
make_train_step = harness.load_by_path("families", "solar").make_train_step


# -- operations and bytes, the least the algorithm needs ---------------------------

def pairs_per_id(config: dict, seq: int) -> float:
    """(query, key) pairs the mask lets the two queries of one id read, on average, in a
    sequence of ``seq`` ids of whole blocks: each reads the positions up to the end of its
    block, ``(seq + block) / 2`` (4,100 pairs an id at 4,096 ids in blocks of 4, where a
    causal half of the doubled stream would be 8,193)."""
    return float(seq + config["diffusion"]["block_length"])


def attention_product_flops(config: dict, seq: int) -> float:
    """Forward and backward of one id's QK^T and PV in one layer, over the pairs the mask
    allows: 2 products x 2 operations x pairs x heads x head size, three times with the
    backward."""
    return (12.0 * pairs_per_id(config, seq) * config["num_attention_heads"]
            * config["head_dim"])


def routed_share(config: dict) -> float:
    """Routed experts a position reaches *here*, under even routing: ``top-k`` of the
    published experts, of which this chip holds ``num_experts``."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["deployment"]["num_experts"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """Per id of the batch. Both halves of the stream go through every layer's four
    attention projections, the router's matrix and the routed experts a position reaches
    here (twice an id); the attention products over the pairs the mask allows; the head
    over the slice held, once an id (the noised half alone). Nothing for the draws, the
    norms or the softmax."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    per_position = layers * (
        flops.gqa_projection_params(d, config["num_attention_heads"],
                                    config["num_key_value_heads"], config["head_dim"])
        + d * config["deployment"]["num_experts"]
        + routed_share(config) * flops.swiglu_params(d, config["moe_intermediate_size"]))
    return (flops.matmul_train_flops(2 * per_position + d * config["vocab_size"])
            + layers * attention_product_flops(config, seq))


def attention_core_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's attention products over all layers, forward and
    backward: the pairs the mask allows, the same :func:`train_flops_per_token` counts, so
    ``attn.roofline`` reads the walk against what the mask needs and not against a causal
    half of the doubled stream. Bytes as ``families/laguna.py`` counts them (q, k, v, the
    output and the cotangents, bf16), over the ``2 x seq`` rows of the stream."""
    h, hkv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    layers = config["num_hidden_layers"]
    ops = layers * batch * seq * attention_product_flops(config, seq)
    moved = layers * batch * 2 * seq * dh * 2 * (5 * h + 6 * hkv)
    return ops, moved
