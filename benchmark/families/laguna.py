"""Family ``laguna``: a pattern of layers (``tpu_resiliency/models/pattern.py``): full and
sliding-window attention of different head counts with an output gate, a leading dense
SwiGLU, then sparse layers: a sigmoid router over all experts of the deployment, the
top-k routed experts this chip holds, one shared expert.

Everything the benchmark knows of the architecture, and the only file that imports the
program's model (inside the functions). A configuration of this family states the
published ``config.json`` whole: the three per-layer lists keep every published entry and
the first ``num_hidden_layers`` of each run. ``num_experts`` and ``vocab_size`` count
what is held here; ``deployment`` gives the published counts and which experts these are.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/laguna.py``
REFERENCE = "laguna"

#: the tiny preset: two kinds of attention layer, a dense first MLP, 16 experts of which
#: 4 are held, a window shorter than the sequence, a sequence that is a multiple of
#: neither block. The limits are the tiny model's own, from 11 seeds on the CPU (the
#: program and the bf16 reference against the float32 one, and the fp8 control). At 80
#: tokens a batch and four choices of sixteen a flipped router choice moves the loss as
#: much as fp8 does (sound up to 0.0228, fp8 from 0.0147) and the gradient nearly so
#: (0.0346 against 0.0360), so those two limits only bound sound runs; the parameter
#: change separates every seed (sound up to 0.0056, fp8 from 0.0096) and is the limit the
#: control fails by
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "head_dim": 16, "num_key_value_heads": 2,
    "num_hidden_layers": 3,
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "num_attention_heads_per_layer": [6, 8, 6],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "num_experts": 4, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "sliding_window": 8, "vocab_size": 256,
    "deployment": {"chips_per_layer": 4, "num_experts": 16, "experts_held": [0, 4]},
    "assumed": {"attention_block": 16},
    "batch": [2, 40],
    "limits": {"loss_abs": 0.03, "grad_norm_gap": 0.045, "change_norm_gap": 0.0075},
}

KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def layers_of(config: dict) -> list[tuple[str, int, str]]:
    """(attention kind, query heads, MLP kind) of the layers that run."""
    n = config["num_hidden_layers"]
    return [(KINDS[a], h, m) for a, h, m in zip(
        config["layer_types"][:n], config["num_attention_heads_per_layer"][:n],
        config["mlp_layer_types"][:n])]


def program_config(config: dict, seq: int):
    try:
        from tpu_resiliency.models import pattern
    except ImportError as e:  # a program from before the model
        raise harness.NoResult(f"this program has no pattern-of-layers model: {e}")

    # the program implements one reading of these switches
    for key, want in (("gating", True), ("moe_apply_router_weight_on_input", False),
                      ("tie_word_embeddings", False), ("attention_bias", False)):
        if config[key] is not want:
            raise harness.NoResult(f"{key} = {config[key]!r} is not what the program computes")
    first, held = config["deployment"]["experts_held"]
    if held != config["num_experts"]:
        raise harness.NoResult("num_experts is not the count of deployment.experts_held")

    def rope(p: dict):
        yarn = None
        if p["rope_type"] == "yarn":
            yarn = pattern.Yarn(
                factor=float(p["factor"]),
                original_max_position=p["original_max_position_embeddings"],
                beta_fast=float(p["beta_fast"]), beta_slow=float(p["beta_slow"]),
                attention_factor=p["attention_factor"])
        return pattern.Rope(float(p["rope_theta"]), p["partial_rotary_factor"], yarn)

    return pattern.PatternConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        head_dim=config["head_dim"], n_kv_heads=config["num_key_value_heads"],
        layers=tuple(pattern.Layer(*layer) for layer in layers_of(config)),
        d_ff=config["intermediate_size"], d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_experts=config["deployment"]["num_experts"], top_k=config["num_experts_per_tok"],
        experts_held=(first, held), routed_scale=config["moe_routed_scaling_factor"],
        window=config["sliding_window"],
        rope_full=rope(config["rope_parameters"]["full_attention"]),
        rope_sliding=rope(config["rope_parameters"]["sliding_attention"]),
        norm_eps=config["rms_norm_eps"], attn_block=config["assumed"]["attention_block"],
    )


def init_params(key, cfg):
    from tpu_resiliency.models import pattern

    return pattern.init_params(key, cfg)


def make_train_step(cfg):
    from tpu_resiliency.models import pattern

    return pattern.make_train_step(cfg)


def param_specs(cfg):
    from tpu_resiliency.parallel import mesh

    return mesh.pattern_param_specs(cfg)


# -- operations and bytes, the least the algorithm needs ---------------------------

def keys_seen(kind: str, seq: int, window: int) -> float:
    """Mean number of keys a query scores in a sequence of ``seq``: the causal half
    (as ``flops.causal_attention_train_flops`` counts it) or the band of ``window``,
    shorter for the first rows."""
    if kind == "full" or seq <= window:
        return seq / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def attention_product_flops(config: dict, seq: int, kind: str, heads: int) -> float:
    """Forward and backward of one token's QK^T and PV in one layer: 2 products x 2
    operations x the keys it sees x heads x head size, three times with the backward."""
    return 12.0 * keys_seen(kind, seq, config["sliding_window"]) * heads * config["head_dim"]


def routed_share(config: dict) -> float:
    """Routed experts a token reaches *here*, under even routing: ``top-k`` of the
    published experts, of which this chip holds ``num_experts``."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["deployment"]["num_experts"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """Per-kind projections with the gate's matrix, the band or the causal half of the
    attention products, the dense MLP, the router's matrix, the shared expert, the
    routed experts a token reaches here, and the head over the slice held."""
    d = config["hidden_size"]
    matmul, attention = d * config["vocab_size"], 0.0
    for kind, heads, mlp in layers_of(config):
        matmul += flops.gqa_projection_params(
            d, heads, config["num_key_value_heads"], config["head_dim"]) + d * heads
        attention += attention_product_flops(config, seq, kind, heads)
        if mlp == "dense":
            matmul += flops.swiglu_params(d, config["intermediate_size"])
        else:
            matmul += (d * config["deployment"]["num_experts"]
                       + flops.swiglu_params(d, config["shared_expert_intermediate_size"])
                       + routed_share(config) * flops.swiglu_params(
                           d, config["moe_intermediate_size"]))
    return flops.matmul_train_flops(matmul) + attention


def attention_core_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's attention products over all layers, forward
    and backward. Bytes: q, k, v read and the output written once forward; those four
    and the output's cotangent read, and three cotangents written, backward; bf16."""
    hkv, dh = config["num_key_value_heads"], config["head_dim"]
    ops = moved = 0.0
    for kind, heads, _ in layers_of(config):
        ops += batch * seq * attention_product_flops(config, seq, kind, heads)
        moved += batch * seq * dh * 2 * (5 * heads + 6 * hkv)
    return ops, moved


def expert_products_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's grouped expert products over all sparse
    layers, forward and backward, for the pairs that land here under even routing (at
    seeded weights a layer's own count read 7,775-8,779 against the 8,192 counted).
    Bytes: the three bf16 weight stacks read forward and backward and their gradient
    written once, and each pair's rows (``d`` in and out, ``f`` three times) both ways."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    sparse = sum(1 for *_, mlp in layers_of(config) if mlp == "sparse")
    pairs = batch * seq * routed_share(config)
    weights = config["num_experts"] * flops.swiglu_params(d, f)
    ops = sparse * pairs * flops.matmul_train_flops(flops.swiglu_params(d, f))
    moved = sparse * 2 * (3 * weights + 2 * pairs * (2 * d + 3 * f))
    return ops, moved
