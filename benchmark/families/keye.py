"""Family ``keye``: a Qwen3-MoE-style decoder whose attention reads only the keys a
learned indexer selects (DeepSeek Sparse Attention in its sparse-training stage), as the
pattern of layers of ``tpu_resiliency/models/pattern.py``: in every layer grouped-query
heads with a norm on each head's q and k and no gate, over the ``sa_config.topk`` keys of
largest index score for each query (``indexer_num_heads`` small heads, one key a token, a
weight a head; an exact top-k, the same set for all heads), the indexer taught by a loss
of its own (the KL divergence between the heads' mean probabilities and the softmax of
its scores over the selected keys); then a sparse MLP: a softmax router over all experts
of the deployment, the top-k renormalised, the routed experts this chip holds, no shared
expert. No dense layer.

Everything the benchmark knows of the architecture, and the only file that imports the
program's model (inside the functions). A configuration of this family states the
published ``config.json`` whole. ``num_experts`` (and ``num_local_experts``, the same
count under its second key) and ``vocab_size`` count what is held here; ``deployment``
gives the published counts and which experts these are.
"""

from __future__ import annotations

from benchmark import flops, harness

#: ``benchmark/reference/keye.py``
REFERENCE = "keye"

#: the tiny preset: three indexed layers of eight heads over two KV heads, an indexer of
#: four heads of 8 that keeps 12 keys a query, 16 experts of which 4 are held, sequences
#: of 64 that are whole attention blocks of 16 in four groups (three of which select). The
#: limits are the tiny model's own, from 12 seeds on the CPU (the program against the
#: float32 reference on the program's choices; the bf16 and the fp8 reference against it
#: on its own). At 256 tokens a step a sum over the tokens averages little, and the
#: program, whose stream is bfloat16, reads further from float32 than the bf16 reference
#: does: gradient up to 0.0289 (bf16 up to 0.006, fp8 0.0157-0.0446, over 0.035 on 4
#: seeds of 12, the three the tests run among them), loss up to 0.0353 (fp8 up to 0.029),
#: parameter change up to 0.0082 (fp8 up to 0.0127)
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 4, "num_local_experts": 4, "num_experts_per_tok": 4,
    "vocab_size": 256,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 16, "q_chunk_size": 16, "topk": 12},
    "deployment": {"chips_per_layer": 4, "num_experts": 16, "experts_held": [0, 4]},
    "batch": [4, 64],
    "limits": {"loss_abs": 0.05, "grad_norm_gap": 0.035, "change_norm_gap": 0.012},
}


def program_config(config: dict, seq: int):
    try:
        from tpu_resiliency.models import pattern
    except ImportError as e:  # a program from before the model
        raise harness.NoResult(f"this program has no pattern-of-layers model: {e}")
    if not hasattr(pattern, "Indexer"):  # a program from before the kind
        raise harness.NoResult("this program's pattern-of-layers model has no indexed attention")

    # the program implements one reading of these switches
    sa = config["sa_config"]
    for key, got, want in (
            ("attention_bias", config["attention_bias"], False),
            ("hidden_act", config["hidden_act"], "silu"),
            ("norm_topk_prob", config["norm_topk_prob"], True),
            ("tie_word_embeddings", config["tie_word_embeddings"], False),
            ("decoder_sparse_step", config["decoder_sparse_step"], 1),
            ("mlp_only_layers", config["mlp_only_layers"], []),
            ("use_sliding_window", config["use_sliding_window"], False),
            ("sliding_window", config["sliding_window"], None),
            ("rope_scaling.rope_type", config["rope_scaling"]["rope_type"], "default"),
            ("sa_config.indexer_num_kv_heads", sa["indexer_num_kv_heads"], 1)):
        if got != want or type(got) is not type(want):
            raise harness.NoResult(f"{key} = {got!r} is not what the program computes")
    if sa["q_chunk_size"] != sa["kv_chunk_size"]:
        raise harness.NoResult("the index scores are computed in square tiles")
    first, held = config["deployment"]["experts_held"]
    if not held == config["num_experts"] == config["num_local_experts"]:
        raise harness.NoResult(
            "num_experts and num_local_experts are not the count of deployment.experts_held")
    heads = config["num_attention_heads"]
    theta = float(config["rope_theta"])
    program = pattern.PatternConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        head_dim=config["head_dim"], n_kv_heads=config["num_key_value_heads"],
        layers=(pattern.Layer(pattern.INDEXED, heads, pattern.SPARSE),) * config["num_hidden_layers"],
        indexer=pattern.Indexer(n_heads=sa["indexer_num_heads"], head_dim=sa["indexer_head_dim"],
                                top_k=sa["topk"]),
        rope_indexed=pattern.Rope(theta), route_score=pattern.SOFTMAX,
        d_ff=config["intermediate_size"], d_expert=config["moe_intermediate_size"], d_shared=0,
        n_experts=config["deployment"]["num_experts"], top_k=config["num_experts_per_tok"],
        experts_held=(first, held), routed_scale=1.0, norm_eps=config["rms_norm_eps"],
        # the index scores, the mask and the attention under it go by the same query rows
        attn_block=sa["q_chunk_size"],
    )

    def choices(params, tokens):
        import jax

        # traced into the caller's program, but in the program's own matmul precision: the
        # reference's ``highest`` is no type of the program's bfloat16 grouped products
        with jax.default_matmul_precision(None):
            return pattern.choices(params, tokens, program)

    # ``correct`` compares the two sides on the keys and the experts the program chose
    # (reference/keye.py, "Choices"): the reference, which gets this same dict, asks here
    config["choices"] = choices
    return program


# the program's side is the pattern-of-layers model's, as family ``laguna`` reaches it
_laguna = harness.load_by_path("families", "laguna")
init_params, make_train_step, param_specs = (
    _laguna.init_params, _laguna.make_train_step, _laguna.param_specs)


# -- operations and bytes, the least the algorithm needs ---------------------------

def keys_selected(config: dict, seq: int) -> float:
    """Mean number of keys a query's attention reads in a sequence of ``seq``: ``min(t + 1,
    topk)`` over the positions (1,792 at 8,192 tokens and 2,048 kept, against the causal
    half's 4,096)."""
    topk = min(config["sa_config"]["topk"], seq)
    return (topk * (topk + 1) / 2 + (seq - topk) * topk) / seq


def indexer_params(config: dict) -> int:
    """Parameters of one indexer's three matrices: its queries, its key, its head weights."""
    sa = config["sa_config"]
    return config["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"] + sa["indexer_head_dim"]
        + sa["indexer_num_heads"])


def index_score_flops(config: dict, seq: int) -> float:
    """Forward and backward of one query's index scores in one layer: 2 operations x the
    causal half of the keys x the indexer's heads x their size, three times with the
    backward (the cotangent of the scores into the queries' and into the keys')."""
    sa = config["sa_config"]
    return 6.0 * ((seq + 1) / 2) * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def attention_product_flops(config: dict, seq: int) -> float:
    """Forward and backward of one token's QK^T and PV in one layer, over the keys
    selected for it: 2 products x 2 operations x keys x heads x head size, three times
    with the backward."""
    return (12.0 * keys_selected(config, seq) * config["num_attention_heads"]
            * config["head_dim"])


def routed_share(config: dict) -> float:
    """Routed experts a token reaches *here*, under even routing: ``top-k`` of the
    published experts, of which this chip holds ``num_experts``."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["deployment"]["num_experts"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """In every layer the four attention projections, the indexer's three (their input is
    detached, so forward and the weights' gradient: 4 operations a parameter), the index
    scores over the causal half, the attention products over the selected keys, the
    router's matrix and the routed experts a token reaches here; and the head over the
    slice held. Nothing for the selection itself (no operation of this count's kind) or
    for the divergence."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    matmul = d * config["vocab_size"] + layers * (
        flops.gqa_projection_params(d, config["num_attention_heads"],
                                    config["num_key_value_heads"], config["head_dim"])
        + d * config["deployment"]["num_experts"]
        + routed_share(config) * flops.swiglu_params(d, config["moe_intermediate_size"]))
    return (flops.matmul_train_flops(matmul) + layers * (
        4.0 * indexer_params(config) + index_score_flops(config, seq)
        + attention_product_flops(config, seq)))


def attention_core_cost(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) of one step's attention products over all layers, forward and
    backward, over the selected keys: the least work, the same products
    :func:`train_flops_per_token` counts, so a masked dense product reads well under what
    a gathered one would. The heads' mean probabilities cost the blocks nothing of this
    kind (they sum the probabilities they hold); the kernel path a TPU runs since PR 36
    keeps a log-sum-exp and not the scores, and pays one more ``QK^T`` a layer for them,
    twice (``blocked_attention_probs``, forward and again in the backward pass), which is
    not least work and is not counted. Bytes as ``families/laguna.py`` counts them (q, k,
    v, the output and the cotangents, bf16); the selection itself (a byte or four a selected
    key) is left out: the products are compute-bound by a factor of seven either way."""
    h, hkv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    layers = config["num_hidden_layers"]
    ops = layers * batch * seq * attention_product_flops(config, seq)
    moved = layers * batch * seq * dh * 2 * (5 * h + 6 * hkv)
    return ops, moved
