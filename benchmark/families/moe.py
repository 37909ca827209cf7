"""Family ``moe``: the dense family's block with the MLP replaced by a softmax router
and top-k SwiGLU experts under a capacity (``tpu_resiliency/models/moe.py``).

The only file that knows the program's MoE model; it builds on ``families/dense.py``
as ``MoEConfig`` builds on ``TransformerConfig``.
"""

from __future__ import annotations

from benchmark import flops, harness

dense = harness.load_by_path("families", "dense")

#: both references live in ``benchmark/reference/model.py`` (they share attention and
#: the SwiGLU); it takes the expert layer where the configuration counts experts
REFERENCE = "model"

#: the tiny preset, as ``families/dense.py`` says; limits from the same readings
TINY = {
    **dense.TINY, "batch": [4, 32],
    "assumed": {
        "capacity_factor": 8.0,
        "why": "at 32 tokens a row routing is far from balanced; the tiny model gets "
               "room for every token so that it can be held to the reference, which "
               "has no capacity",
    },
    "limits": {"loss_abs": 0.03, "grad_norm_gap": 0.02, "change_norm_gap": 0.02},
}


def program_config(config: dict, seq: int):
    from tpu_resiliency.models import moe

    return dense.checked(moe.MoEConfig(
        **dense.transformer_keys(config, seq),
        n_experts=config["num_local_experts"], top_k=config["num_experts_per_tok"],
        capacity_factor=config["assumed"]["capacity_factor"],
        router_aux_weight=config["router_aux_loss_coef"],
    ), config)


def init_params(key, cfg):
    from tpu_resiliency.models import moe

    return moe.init_params(key, cfg)


def make_train_step(cfg):
    from tpu_resiliency.models import moe

    return moe.make_train_step(cfg)


def param_specs(cfg):
    from tpu_resiliency.parallel import mesh

    return mesh.moe_param_specs(cfg)


def train_flops_per_token(config: dict, seq: int) -> float:
    """The router's matrix and the top-k experts a token reaches, no capacity padding."""
    d = config["hidden_size"]
    mlp = d * config["num_local_experts"] + config["num_experts_per_tok"] * flops.swiglu_params(
        d, config["intermediate_size"])
    return dense.block_train_flops(config, seq, mlp)
