"""Family ``solar``: a hybrid decoder, as the pattern of layers of
``tpu_resiliency/models/pattern.py``: a period of one softmax layer and ``gqa_interval``
linear ones. The linear layers are gated delta-rule attention with a decay a key channel
(Kimi Delta Attention, arXiv:2510.26692: a state of ``head_dim x head_dim`` a head carried
along the sequence, short depthwise convolutions on q, k and v, low-rank maps for the decay
and the output gate, a norm a head under the gate); the softmax layers (``gqa_layers``) are
grouped-query attention over the causal prefix with no position (``use_rope false``) and a
sigmoid a channel for an output gate. Every layer's MLP is sparse: a sigmoid router over
all experts of the deployment, the top-k renormalised, the routed experts this chip holds,
one shared expert.

Everything the benchmark knows of the architecture, and the only file that imports the
program's model (inside the functions). A configuration of this family states the
published ``config.json`` whole. ``n_routed_experts``, ``vocab_size``,
``num_attention_heads``, ``num_key_value_heads`` and ``linear_attn_config.num_heads`` count
what is held here; ``deployment`` gives the published counts and which experts, heads and
rows these are. ``gqa_layers`` keeps every published entry and the first
``num_hidden_layers`` layers run.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/solar.py``
REFERENCE = "solar"

#: the tiny preset: one period (a softmax layer, three delta layers), 2 of 8 heads held
#: with 1 of the 4 KV heads, 16 experts of which 4 are held, sequences of 48 that are three
#: chunks of 16. The limits are the tiny model's own, from 12 seeds on the CPU (the program
#: and the reference in bf16 and in fp8 against the float32 one). At 96 tokens a step and
#: widths of 16 one rounding moves a leaf's gradient by percents: sound up to 0.106 (a
#: seed's worst leaf 0.022-0.106), fp8 0.114-0.376, bf16 with the state rounded too
#: 0.017-0.199; loss sound up to 0.030, fp8 0.010-0.127; the parameter change separates
#: best: sound up to 0.0158, fp8 from 0.0147 (over 0.02 on 9 seeds of 12, the three the
#: tests run among them)
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "head_dim": 16,
    "num_hidden_layers": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 2,
                           "num_kv_heads": None},
    "n_routed_experts": 4, "num_experts_per_tok": 4, "vocab_size": 256,
    "deployment": {"chips_per_layer": 16, "n_routed_experts": 16, "experts_held": [0, 4],
                   "num_attention_heads": 8, "num_key_value_heads": 4, "heads_held": [0, 2]},
    "assumed": {"chunk": 16, "attention_block": 16},
    "batch": [2, 48],
    "limits": {"loss_abs": 0.04, "grad_norm_gap": 0.13, "change_norm_gap": 0.02},
}


def layer_kinds(config: dict) -> list[str]:
    """``"softmax"`` or ``"delta"`` for each layer that runs."""
    return ["softmax" if i in config["gqa_layers"] else "delta"
            for i in range(config["num_hidden_layers"])]


def program_config(config: dict, seq: int):
    try:
        from tpu_resiliency.models import pattern
    except ImportError as e:  # a program from before the model
        raise harness.NoResult(f"this program has no pattern-of-layers model: {e}")
    if not hasattr(pattern, "DELTA"):  # a program from before the kind
        raise harness.NoResult("this program's pattern-of-layers model has no delta-rule attention")

    # the program implements one reading of these switches
    linear = config["linear_attn_config"]
    for key, got, want in (
            ("kda_allow_neg_eigval", config["kda_allow_neg_eigval"], True),
            ("kda_use_full_proj", config["kda_use_full_proj"], False),
            ("use_rope", config["use_rope"], False),
            ("use_gqa_gate", config["use_gqa_gate"], True),
            ("first_k_dense_replace", config["first_k_dense_replace"], 0),
            ("n_shared_experts", config["n_shared_experts"], 1),
            ("norm_topk_prob", config["norm_topk_prob"], True),
            ("tie_word_embeddings", config["tie_word_embeddings"], False),
            ("linear_attn_config.num_kv_heads", linear["num_kv_heads"], None)):
        if got != want or type(got) is not type(want):
            raise harness.NoResult(f"{key} = {got!r} is not what the program computes")
    deployment = config["deployment"]
    first, held = deployment["experts_held"]
    if held != config["n_routed_experts"]:
        raise harness.NoResult("n_routed_experts is not the count of deployment.experts_held")
    first_head, heads = deployment["heads_held"]
    all_heads = deployment["num_attention_heads"]
    if not heads == config["num_attention_heads"] == linear["num_heads"] or all_heads % heads \
            or first_head % heads:
        raise harness.NoResult("num_attention_heads and linear_attn_config.num_heads are not "
                               "the count of deployment.heads_held, an equal share of the heads")
    ways = all_heads // heads
    if config["num_key_value_heads"] * ways != deployment["num_key_value_heads"]:
        raise harness.NoResult("num_key_value_heads is not the held heads' share of the KV heads")
    if linear["head_dim"] != config["head_dim"]:
        raise harness.NoResult("the stacks of one description share a head size")
    kinds = {"softmax": pattern.FULL, "delta": pattern.DELTA}
    return pattern.PatternConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        head_dim=config["head_dim"], n_kv_heads=deployment["num_key_value_heads"],
        layers=tuple(pattern.Layer(kinds[kind], all_heads, pattern.SPARSE)
                     for kind in layer_kinds(config)),
        head_ways=ways, rope_full=None, gate=pattern.CHANNEL_GATE,
        delta=pattern.Delta(
            d_key=linear["head_dim"], d_value=linear["head_dim"], gate_rank=linear["head_dim"],
            conv_taps=linear["short_conv_kernel_size"], chunk=config["assumed"]["chunk"],
            neg_eigval=True),
        d_ff=config["intermediate_size"], d_expert=config["moe_intermediate_size"],
        d_shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        n_experts=deployment["n_routed_experts"], top_k=config["num_experts_per_tok"],
        experts_held=(first, held), routed_scale=float(config["routed_scaling_factor"]),
        norm_eps=config["rms_norm_eps"], attn_block=config["assumed"]["attention_block"],
    )


# the program's side is the pattern-of-layers model's, as family ``laguna`` reaches it
_laguna = harness.load_by_path("families", "laguna")
init_params, param_specs = _laguna.init_params, _laguna.param_specs


def make_train_step(cfg, optimizer=None):
    """``optimizer`` is a configuration's ``optimizer`` key, ``{"lr": <float>}``: AdamW as
    the training contract has it (betas 0.9/0.999, eps 1e-8, decoupled weight decay 0.01 on
    every leaf) at that rate; none, the contract's own 3e-4."""
    from tpu_resiliency.models import pattern

    if optimizer is not None:
        import optax

        optimizer = optax.adamw(float(optimizer["lr"]), weight_decay=0.01)
    return pattern.make_train_step(cfg, optimizer)


# -- operations and bytes, the least the algorithm needs ---------------------------

def delta_projection_params(config: dict) -> int:
    """Parameters of one delta layer's matrices as held here: q, k, v and the output, the
    two low-rank maps (decay and output gate: down to ``head_dim``, up to the held heads'
    channels) and the write strength a head."""
    d, linear = config["hidden_size"], config["linear_attn_config"]
    channels, rank = linear["num_heads"] * linear["head_dim"], linear["head_dim"]
    return 4 * d * channels + 2 * (d * rank + rank * channels) + d * linear["num_heads"]


def delta_rule_flops(config: dict) -> float:
    """Forward and backward of one token's delta rule in one head of one layer, by chunks
    of ``assumed.chunk``: the two decayed Gram matrices over the causal half of the chunk
    (``k k^T`` for the system, ``q k^T`` for what a query reads of its own chunk), the solve
    applied to ``v`` and to the decayed ``k`` (a triangle, half the chunk a row), the Gram
    matrix's product with the chunk's writes, and three products of ``dk x dv`` with the
    state (what the state takes from a write, what a query reads of it, the writes added to
    it); three times with the backward. The decays themselves, the convolutions and the
    norms are no operations of this count's kind."""
    chunk, dk = config["assumed"]["chunk"], config["linear_attn_config"]["head_dim"]
    dv = dk
    forward = 2 * chunk * dk + chunk * (dv + dk) + chunk * dv + 6 * dk * dv
    return 3.0 * forward


def attention_product_flops(config: dict, seq: int) -> float:
    """Forward and backward of one token's QK^T and PV in one softmax layer, over the
    causal half, for the heads held."""
    return flops.causal_attention_train_flops(
        seq, config["num_attention_heads"], config["head_dim"])


def routed_share(config: dict) -> float:
    """Routed experts a token reaches *here*, under even routing."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["deployment"]["n_routed_experts"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """The projections and gates of the held heads, the softmax products over the causal
    half on the softmax layers, the rule's least work on the delta layers, and in every
    layer the router's matrix, the shared expert and the routed experts a token reaches
    here; and the head over the slice held."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    matmul, other = d * config["vocab_size"], 0.0
    for kind in layer_kinds(config):
        if kind == "softmax":
            matmul += flops.gqa_projection_params(d, heads, kv_heads, dh) + d * heads * dh
            other += attention_product_flops(config, seq)
        else:
            matmul += delta_projection_params(config)
            other += config["linear_attn_config"]["num_heads"] * delta_rule_flops(config)
        matmul += (d * config["deployment"]["n_routed_experts"]
                   + (config["n_shared_experts"] + routed_share(config))
                   * flops.swiglu_params(d, config["moe_intermediate_size"]))
    return flops.matmul_train_flops(matmul) + other


def attention_core_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's softmax attention products (the softmax layers
    alone: a delta layer has no ``core``), forward and backward, bytes as
    ``families/laguna.py`` counts them."""
    layers = layer_kinds(config).count("softmax")
    heads, kv_heads, dh = (config["num_attention_heads"], config["num_key_value_heads"],
                           config["head_dim"])
    ops = layers * batch * seq * attention_product_flops(config, seq)
    moved = layers * batch * seq * dh * 2 * (5 * heads + 6 * kv_heads)
    return ops, moved


def delta_rule_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's delta rule over all delta layers, forward and
    backward, whatever computes it: the operations of :func:`delta_rule_flops`; bytes a
    token a head: q, k, v (bf16), the log-decays (float32 a key channel) and the write
    strength read and the output written forward; read again with the output's cotangent
    and their five cotangents written backward; and the state at each chunk's start
    (float32, ``dk x dv`` a chunk) written once and read once. At the cell's shapes the
    bytes bound it: 1.26e9 B are 1.5 ms at the chip's bandwidth, the operations 0.4 ms at
    its peak."""
    linear, chunk = config["linear_attn_config"], config["assumed"]["chunk"]
    dk = dv = linear["head_dim"]
    layers = layer_kinds(config).count("delta")
    head_tokens = layers * batch * seq * linear["num_heads"]
    operands = 2 * (2 * dk + dv) + 4 * dk + 4
    moved = 3 * operands + 2 * 2 * dv + 2 * 4 * dk * dv / chunk
    return head_tokens * delta_rule_flops(config), head_tokens * moved
