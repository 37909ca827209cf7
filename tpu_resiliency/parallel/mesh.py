"""Mesh axis conventions and sharding-spec helpers for the framework's models.

Axis names used throughout:

- ``dp``: data parallel (batch axis; gradients all-reduced over ICI),
- ``tp``: tensor parallel (attention heads / MLP hidden sharded; activations
  all-gathered / reduce-scattered by XLA where needed),
- ``sp``: sequence/context parallel (long-context: sequence axis sharded, attention
  runs as a ring over ``sp`` — see ``parallel/ring_attention.py``),
- ``pp``: pipeline parallel (the stacked ``[L]`` layer axis sharded into stages;
  microbatches flow stage-to-stage as a ``ppermute`` ring — see
  ``parallel/pipeline.py``),
- ``ep``: expert parallel (MoE expert axis sharded; the dispatch einsums make XLA
  route tokens with an all-to-all — see ``models/moe.py``).

The reference implements no parallelism (SURVEY.md §2.7 checklist) — these exist because
a TPU-native resiliency framework must be *exercised* against real sharded workloads,
and its rank topology components (Tree layers, replication cliques) key off mesh axes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

DP, TP, SP, PP, EP = "dp", "tp", "sp", "pp", "ep"


def build_mesh(
    n_devices: Optional[int] = None,
    *,
    dp: int = 1,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence] = None,
):
    """Build a ``Mesh`` with the framework's canonical axes (dp, tp, sp, pp, ep).

    If ``n_devices`` is given without explicit axis sizes, all devices go to ``dp``.
    Axis order puts ``pp`` outermost (stage hops are the rarest, largest-grained
    transfers) and ``tp`` innermost (its collectives are per-matmul, so it gets the
    fastest ICI loops).
    """
    import jax

    from tpu_resiliency.platform.device import make_mesh

    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    total = dp * tp * sp * pp * ep
    if total == 1 and n_devices:
        dp, total = len(devs), len(devs)
    if total != len(devs):
        raise ValueError(f"dp*tp*sp*pp*ep = {total} != {len(devs)} devices")
    return make_mesh({PP: pp, DP: dp, EP: ep, SP: sp, TP: tp}, devices=devs)


def default_split(n_devices: int) -> dict[str, int]:
    """A sensible (dp, tp, sp) split for n devices (pp/ep left to dedicated configs —
    see :func:`moe_pipeline_split`).

    All three axes are real: 8 devices → (dp=2, tp=2, sp=2) — the training step
    runs tensor-parallel matmuls, a data-parallel gradient reduction, AND ring
    attention over the sequence axis (``parallel/ring_attention.py``)."""
    if n_devices % 8 == 0:
        return {"dp": n_devices // 4, "tp": 2, "sp": 2, "pp": 1, "ep": 1}
    tp = 2 if n_devices % 2 == 0 else 1
    return {"dp": n_devices // tp, "tp": tp, "sp": 1, "pp": 1, "ep": 1}


def moe_pipeline_split(n_devices: int) -> dict[str, int]:
    """A (dp, pp, ep) split exercising the pipeline + expert axes: 8 devices →
    (dp=2, pp=2, ep=2). The MoE training step then runs a data-parallel gradient
    reduction, a two-stage microbatch pipeline, AND expert-parallel dispatch."""
    if n_devices % 4 == 0:
        return {"dp": n_devices // 4, "tp": 1, "sp": 1, "pp": 2, "ep": 2}
    if n_devices % 2 == 0:
        return {"dp": n_devices // 2, "tp": 1, "sp": 1, "pp": 1, "ep": 2}
    return {"dp": n_devices, "tp": 1, "sp": 1, "pp": 1, "ep": 1}


def param_specs(cfg) -> dict:
    """PartitionSpecs for the transformer parameter pytree (see models/transformer.py).

    Layout follows the megatron-style convention: column-parallel then row-parallel —
    wq/wk/wv and w_gate/w_up shard their output dim over ``tp``; wo and w_down shard
    their input dim over ``tp``; embeddings shard vocab over ``tp``; norms replicate,
    as do the two further norms a layer of a description with sandwich norms and the
    exit gate of one with exits.
    """
    from jax.sharding import PartitionSpec as P

    specs = {
        "embed": P(TP, None),  # [V, D]
        "layers": {
            "attn_norm": P(None, None),  # [L, D]
            "wq": P(None, None, TP),  # [L, D, H*dh]
            "wk": P(None, None, TP),  # [L, D, Hkv*dh]
            "wv": P(None, None, TP),  # [L, D, Hkv*dh]
            "wo": P(None, TP, None),  # [L, H*dh, D]
            "mlp_norm": P(None, None),  # [L, D]
            "w_gate": P(None, None, TP),  # [L, D, F]
            "w_up": P(None, None, TP),  # [L, D, F]
            "w_down": P(None, TP, None),  # [L, F, D]
        },
        "final_norm": P(None),  # [D]
        "lm_head": P(None, TP),  # [D, V]
    }
    if cfg.sandwich_norms:
        specs["layers"]["attn_post_norm"] = P(None, None)  # [L, D]
        specs["layers"]["mlp_post_norm"] = P(None, None)  # [L, D]
    if cfg.exit_beta is not None:
        specs["exit_gate"] = {"w": P(None, None), "b": P(None)}  # [D, 1], [1]
    return specs


def moe_param_specs(cfg) -> dict:
    """PartitionSpecs for the MoE parameter pytree (see models/moe.py).

    The dense per-layer MLP is replaced by a replicated router and experts stacked
    on an ``[E]`` axis sharded over ``ep``; within each expert the SwiGLU weights
    keep the megatron column/row split over ``tp``. The stacked ``[L]`` layer axis
    shards over ``pp`` when the pipeline runs (``layer_axis="pp"``).
    """
    from jax.sharding import PartitionSpec as P

    specs = param_specs(cfg)
    layers = dict(specs["layers"])
    for k in ("w_gate", "w_up", "w_down"):
        del layers[k]
    layers["w_router"] = P(None, None, None)  # [L, D, E]
    layers["we_gate"] = P(None, EP, None, TP)  # [L, E, D, F]
    layers["we_up"] = P(None, EP, None, TP)  # [L, E, D, F]
    layers["we_down"] = P(None, EP, TP, None)  # [L, E, F, D]
    specs["layers"] = layers
    return specs


#: the mesh axis a parameter dimension of each logical name shards over
#: (``models/pattern.py:describe_params`` names the dimensions)
LOGICAL_AXES = {"vocab": TP, "heads": TP, "ff": TP, "experts": EP}


def pattern_param_specs(cfg) -> dict:
    """PartitionSpecs for the pattern-of-layers parameter pytree (see
    models/pattern.py), derived from the model's own description: every leaf names its
    dimensions, and this function holds no key of the tree. Vocabulary, heads and
    MLP widths shard over ``tp`` (column- then row-parallel, as :func:`param_specs`),
    the held experts' ``[E]`` axis over ``ep``; norms and the router replicate. A delta
    layer's convolutions, its decay's rate and bias, its write strength and the
    up-projections of its two low-rank maps are split by heads like ``wq``; the maps'
    down-projections and the heads' norm replicate. (This shards the heads a description
    holds over the devices of one program; a description that holds a share of the heads,
    ``PatternConfig.head_ways``, is one chip's part, and the sum over the parts is not
    built: docs/parallelism.md.)"""
    import jax
    from jax.sharding import PartitionSpec as P

    from tpu_resiliency.models import pattern

    return jax.tree.map(
        lambda leaf: P(*(LOGICAL_AXES.get(name) for name in leaf.axes)),
        pattern.describe_params(cfg), is_leaf=lambda x: isinstance(x, pattern.Leaf))


def pipeline_layer_specs(layer_specs: dict) -> dict:
    """Prepend ``pp`` to the leading stacked-``[L]`` dim of every per-layer spec, so
    each pipeline stage holds only its own layers."""
    from jax.sharding import PartitionSpec as P

    return {k: P(PP, *spec[1:]) for k, spec in layer_specs.items()}


def batch_spec():
    from jax.sharding import PartitionSpec as P

    return P(DP, SP)  # tokens [B, T]


def tree_shardings(mesh, specs):
    """Map a spec pytree to NamedShardings on ``mesh``."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


def opt_state_shardings(init_opt, params, param_shardings):
    """Shardings for the optimizer state ``init_opt(params)``: every params-shaped
    subtree (AdamW's moments) laid out like the params, the rest (step counts)
    replicated. Pass the result as ``out_shardings`` of ``jax.jit(init_opt)``:
    left to itself the compiler puts the all-zero moments, which depend on no
    sharded input, unsharded on device 0."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.tree.leaves(param_shardings)[0].mesh
    replicated = NamedSharding(mesh, PartitionSpec())
    return optax.tree_map_params(
        init_opt, lambda _, s: s, jax.eval_shape(init_opt, params), param_shardings,
        transform_non_params=lambda _: replicated,
    )


def axis_sizes(mesh) -> dict[str, int]:
    """``{axis name: size}`` of a Mesh — the form the elastic reshard layout
    (``checkpoint/reshard.py``) consumes."""
    return {str(n): int(s) for n, s in zip(mesh.axis_names, mesh.devices.shape)}


def checkpoint_layout(mesh, tree, spec_tree, ranks: Optional[Sequence[int]] = None):
    """A :class:`~tpu_resiliency.checkpoint.reshard.TreeLayout` for saving
    ``tree`` (this rank's LOCAL pytree) sharded per ``spec_tree`` on ``mesh``.

    This is the save-side half of elastic resharding: pass the result to
    ``LocalCheckpointManager.save(..., layout=...)`` and any later world —
    shrunk, grown, or re-split — can resume via ``load_resharded``. ``ranks``
    defaults to one rank per mesh device position (``range(n)``); pass the
    job's actual global rank order when it differs."""
    from tpu_resiliency.checkpoint.reshard import TreeLayout

    sizes = axis_sizes(mesh)
    if ranks is None:
        import numpy as _np

        ranks = range(int(_np.prod(mesh.devices.shape, dtype=_np.int64)))
    # Mesh axis order is authoritative (row-major rank grid follows it).
    axes = [(n, sizes[n]) for n in map(str, mesh.axis_names)]
    return TreeLayout.for_local_tree(tree, spec_tree, axes, list(ranks))
