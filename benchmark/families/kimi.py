"""Family ``kimi``: a DeepSeek-V3-style decoder as the pattern of layers of
``tpu_resiliency/models/pattern.py``: latent attention in every layer (keys and values
decompressed from one normed latent, a rotary key part shared by all heads, a score width
that differs from the value width, no gate), ``first_k_dense_replace`` leading dense
SwiGLUs, then sparse layers: a sigmoid router over all experts of the deployment that
chooses by score + a selection bias and weighs by the score (the bias moved by the
loss-free balancing rule, which rides on the loss's gradient), the top-k routed experts
this chip holds, and the shared experts as one SwiGLU of their summed width.

Everything the benchmark knows of the architecture, and the only file that imports the
program's model (inside the functions). A configuration of this family states the
published ``config.json`` whole. ``n_routed_experts`` and ``vocab_size`` count what is
held here; ``deployment`` gives the published counts and which experts these are.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/kimi.py``
REFERENCE = "kimi"

#: of the training contract's AdamW (``models/transformer.py:make_train_step_from_loss``,
#: ``benchmark/reference/train.py:LR``)
LEARNING_RATE = 3e-4

#: the tiny preset: three latent layers of four heads (score width 24, value width 16,
#: latent 32), a dense first MLP, 16 experts of which 4 are held, sequences of 64 that are
#: whole attention blocks of 16. The limits are the tiny model's own, from 12 seeds on the
#: CPU (the program and the bf16 reference against the float32 one, and the fp8 control).
#: At 256 tokens a batch the first gradient separates every seed (sound and bf16 up to
#: 0.0134, fp8 from 0.0279) and is the limit the control fails by; a flipped router choice
#: moves the loss as much as fp8 does (sound and bf16 up to 0.0092, fp8 from 0.0077) and
#: the parameter change nearly so (0.0051 against 0.0066), so those two limits only bound
#: sound runs. At 80 tokens a batch, the other families' tiny size, none of the three
#: separated: four choices of sixteen under a selection bias flip too often
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 4, "vocab_size": 256,
    "deployment": {"chips_per_layer": 4, "n_routed_experts": 16, "experts_held": [0, 4]},
    "assumed": {"attention_block": 16, "latent_norm_eps": 1e-6, "router_bias_std": 0.1,
                "router_bias_step": 0.001},
    "batch": [4, 64],
    "limits": {"loss_abs": 0.02, "grad_norm_gap": 0.02, "change_norm_gap": 0.0075},
}


def mlp_kinds(config: dict) -> list[str]:
    """The MLP kind of each layer that runs."""
    return ["dense" if layer < config["first_k_dense_replace"] else "sparse"
            for layer in range(config["num_hidden_layers"])]


def program_config(config: dict, seq: int):
    try:
        from tpu_resiliency.models import pattern
    except ImportError as e:  # a program from before the model
        raise harness.NoResult(f"this program has no pattern-of-layers model: {e}")
    if not hasattr(pattern, "Latent"):  # a program from before the kind
        raise harness.NoResult("this program's pattern-of-layers model has no latent attention")

    # the program implements one reading of these switches
    for key, want in (("q_lora_rank", None), ("rope_scaling", None), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("moe_layer_freq", 1), ("hidden_act", "silu"),
                      ("attention_bias", False), ("tie_word_embeddings", False)):
        if config[key] != want or type(config[key]) is not type(want):
            raise harness.NoResult(f"{key} = {config[key]!r} is not what the program computes")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise harness.NoResult("latent attention gives every head keys of its own")
    first, held = config["deployment"]["experts_held"]
    if held != config["n_routed_experts"]:
        raise harness.NoResult("n_routed_experts is not the count of deployment.experts_held")
    heads = config["num_attention_heads"]
    return pattern.PatternConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        head_dim=config["v_head_dim"], n_kv_heads=heads,
        layers=tuple(pattern.Layer(pattern.LATENT, heads, mlp) for mlp in mlp_kinds(config)),
        latent=pattern.Latent(
            kv_rank=config["kv_lora_rank"], d_nope=config["qk_nope_head_dim"],
            d_rope=config["qk_rope_head_dim"], d_value=config["v_head_dim"],
            norm_eps=config["assumed"]["latent_norm_eps"]),
        rope_latent=pattern.Rope(float(config["rope_theta"])),
        route_bias_std=config["assumed"]["router_bias_std"],
        # AdamW's step on a gradient of +-1 is its learning rate: the leaf is kept in
        # units in which that step is the rule's
        route_bias_gain=config["assumed"]["router_bias_step"] / LEARNING_RATE,
        d_ff=config["intermediate_size"], d_expert=config["moe_intermediate_size"],
        d_shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        n_experts=config["deployment"]["n_routed_experts"], top_k=config["num_experts_per_tok"],
        experts_held=(first, held), routed_scale=config["routed_scaling_factor"],
        norm_eps=config["rms_norm_eps"], attn_block=config["assumed"]["attention_block"],
    )


# the program's side is the pattern-of-layers model's, as family ``laguna`` reaches it
_laguna = harness.load_by_path("families", "laguna")
init_params, make_train_step, param_specs = (
    _laguna.init_params, _laguna.make_train_step, _laguna.param_specs)


# -- operations and bytes, the least the algorithm needs ---------------------------

def latent_projection_params(config: dict) -> int:
    """Parameters of one latent attention layer's four matrices: the query's, the
    down-projection to latent + rotary key, the up-projection to every head's keys and
    values, and the output's."""
    d, h, rank = config["hidden_size"], config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + dv) + h * dv * d


def attention_product_flops(config: dict, seq: int) -> float:
    """Forward and backward of one token's QK^T and PV in one layer: 2 operations x the
    causal half of the keys x heads x (the score width for QK^T + the value width for
    PV), three times with the backward. The decompressed form: what training computes."""
    widths = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return 6.0 * (seq / 2) * config["num_attention_heads"] * widths


def routed_share(config: dict) -> float:
    """Routed experts a token reaches *here*, under even routing: ``top-k`` of the
    published experts, of which this chip holds ``n_routed_experts``."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["deployment"]["n_routed_experts"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """The four latent projections and the causal half of the attention products in
    every layer, the dense MLP, the router's matrix, the shared SwiGLU, the routed
    experts a token reaches here, and the head over the slice held."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    matmul = d * config["vocab_size"]
    for mlp in mlp_kinds(config):
        matmul += latent_projection_params(config)
        if mlp == "dense":
            matmul += flops.swiglu_params(d, config["intermediate_size"])
        else:
            matmul += (d * config["deployment"]["n_routed_experts"]
                       + flops.swiglu_params(d, config["n_shared_experts"] * f)
                       + routed_share(config) * flops.swiglu_params(d, f))
    return (flops.matmul_train_flops(matmul)
            + config["num_hidden_layers"] * attention_product_flops(config, seq))


def attention_core_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's attention products over all layers, forward
    and backward. Bytes, bf16: forward q and the keys read at the score width (every
    head's non-rotary part and the rotary key once, as the latent form shares it), v
    read and the output written at the value width; backward those four and the output's
    cotangent read, and the three cotangents written."""
    h = config["num_attention_heads"]
    nope, rope, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q, k, v = h * (nope + rope), h * nope + rope, h * dv
    layers = config["num_hidden_layers"]
    ops = layers * batch * seq * attention_product_flops(config, seq)
    moved = layers * batch * seq * 2 * ((q + k + v + v) + (q + k + v + v + v) + (q + k + v))
    return ops, moved


def expert_products_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's grouped expert products over all sparse layers,
    forward and backward, for the pairs that land here under even routing. Bytes: the
    three bf16 weight stacks read forward and backward and their gradient written once,
    and each pair's rows (``d`` in and out, ``f`` three times) both ways."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    sparse = mlp_kinds(config).count("sparse")
    pairs = batch * seq * routed_share(config)
    weights = config["n_routed_experts"] * flops.swiglu_params(d, f)
    ops = sparse * pairs * flops.matmul_train_flops(flops.swiglu_params(d, f))
    moved = sparse * 2 * (3 * weights + 2 * pairs * (2 * d + 3 * f))
    return ops, moved
