"""Train the pattern-of-layers model (``models/pattern.py``) with the toolkit attached,
and report what its routing does.

``--description mixed`` (the default) mixes full and sliding-window attention layers of
different head counts with an output gate; ``--description latent`` has latent attention
in every layer (keys and values decompressed from one normed latent, a rotary key part
shared by all heads, scores wider than values), a router that chooses by score + a
selection bias and weighs by the score, and two shared experts' width;
``--description indexed`` has in every layer grouped-query attention over the keys a
small indexer selects for each query (an exact top-k of its scores; the indexer learns
from a loss of its own, ``index_kl``), a softmax router, no shared expert and no dense
layer; ``--description delta`` has a period of one full layer with no rotary and a
sigmoid a channel for a gate and three delta-rule layers (a state of ``d_key x d_value`` a
head carried along the sequence, a decay a key channel, short convolutions; this process
holds 2 of the 8 heads: one chip's share of a tensor-parallel group, whose sum is not
built); ``--description diffusion`` trains by block diffusion (``pattern.Diffusion``): full
attention throughout with a norm on each head's q and k and no gate, a softmax router,
no shared expert and no dense layer, on a stream twice the batch (the clean copy of each
sequence beside its noised copy) under a mask that is neither causal nor a band, with a
masked-token loss over the noised blocks in place of next-token loss. The first two have
a leading dense MLP; all have sparse layers whose router
scores every expert of a deployment
while this process holds a contiguous range of them (``--experts-held FIRST COUNT``:
one chip's share of an expert-parallel split; pairs routed to experts held elsewhere add
nothing here). Every ``--routing-every`` steps the script runs the model's forward once
more on the step's batch and emits its routing counts as a ``moe_routing`` event: for
each sparse layer the (token, choice) pairs that landed on held experts, the largest
and the mean load of a held expert, the pairs dropped (always 0) and the rows the
dispatch carried (twice the even share of the experts held, or every pair in a step
whose router sent more than that here), and under a selection bias ``chosen_by_bias``,
the pairs (of all of them) whose expert the scores alone would not have chosen; and for
each indexed layer ``index_kl`` (its indexer's loss), ``keys_selected`` and
``select_ties`` (queries whose last selected score equals the next). With delta layers the
same forward gives a ``delta_state`` event: for each delta layer ``decay_mean`` (the mean
of ``exp(g)``: how much of the state a token keeps), ``beta_mean`` (the write strength) and
``state_rms`` (of the state after the last token). Under block diffusion the same forward
gives a ``diffusion`` event: ``masked_share`` (of the batch's positions), ``weight_mean``
and ``weight_max`` (of ``1 / t`` over the masked positions), ``loss_unweighted`` (the mean
NLL over them) and ``pairs_read`` (the mean number of keys a query of the stream reads),
with the block length; the routing counts are then over the stream's positions, twice the
batch's. Once, before the
first step, it emits an
``attention_path`` event: for each kind of attention layer, whether its products run
as the blocked kernels of ``ops/attention.py`` (on a TPU, at shapes that tile) or as
the ``jax.numpy`` blocks (this script's tiny widths, anywhere), with the tile or block,
for the latent kind its ``score_width`` and ``value_width``, for the indexed kind how many
keys a query keeps and whether its index scores run as the kernels of
``ops/index_scores.py`` (``scores``: ``kernel`` or ``blocks``), for the delta kind
``{path: chunks, chunk, solve: blocks}`` (the rule by chunks in ``jax.numpy``, a chunk's
triangular system inverted by blocks as matrix products; ``kernel`` once one exists),
under block diffusion the walk (``walk: noised`` with ``block_length`` and ``clean``: the
tiles, or the blocks of rows, are visited from positions alone, at the stream's length);
and a ``dispatch_path`` event: the rows the expert dispatch carries at this batch
(``pattern.dispatch_rows``), ``bounded`` or ``full``; and a ``kept_residuals`` event:
the named values each layer keeps for its backward pass at this batch on this device's
memory, and their bytes (``pattern.kept_residuals``; ``names`` empty: every layer
recomputes its whole forward).

Run (CPU simulation)::

    python examples/pattern_training.py --cpu --steps 20

Prints ``ATTENTION {...}``, ``DISPATCH {...}``, ``KEPT {...}``, one ``ROUTING step=<n>
...`` line per routing event (and one ``DELTA step=<n> ...`` line with delta layers, one
``DIFFUSION step=<n> ...`` line under block diffusion) and ``DONE loss=<x>`` on success.
"""

from __future__ import annotations

import argparse
import os

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

#: the counts of a delta layer among what ``loss_and_counts`` returns: a ``delta_state``
#: event's, not a ``moe_routing`` event's
DELTA_COUNTS = ("decay_mean", "beta_mean", "state_rms")
#: the counts of the block-diffusion objective: a ``diffusion`` event's
DIFFUSION_COUNTS = ("masked_share", "weight_mean", "weight_max", "loss_unweighted", "pairs_read")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="simulate on the CPU (without it $JAX_PLATFORMS / JAX decide)")
    ap.add_argument("--description", choices=("mixed", "latent", "indexed", "delta", "diffusion"),
                    default="mixed",
                    help="which pattern of layers to train (see the module docstring)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, nargs=2, default=(2, 64), metavar=("B", "T"))
    ap.add_argument("--experts-held", type=int, nargs=2, default=(0, 4),
                    metavar=("FIRST", "COUNT"))
    ap.add_argument("--routing-every", type=int, default=5)
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_resiliency.integrations import LoopContext, StragglerDetectionCallback, run_training
    from tpu_resiliency.models import pattern
    from tpu_resiliency.utils import events

    preset = {"mixed": pattern.PatternConfig.tiny, "latent": pattern.PatternConfig.tiny_latent,
              "indexed": pattern.PatternConfig.tiny_indexed,
              "delta": pattern.PatternConfig.tiny_delta,
              "diffusion": pattern.PatternConfig.tiny_diffusion}
    cfg = preset[args.description](experts_held=tuple(args.experts_held))
    train_step, init_opt = pattern.make_train_step(cfg)
    step = jax.jit(train_step, donate_argnums=(0, 1))
    counts_of = jax.jit(lambda p, t: pattern.loss_and_counts(p, t, cfg)[1])
    params = pattern.init_params(jax.random.PRNGKey(0), cfg)
    losses = []
    # what the layers see: the batch, or under block diffusion a stream twice as long
    seq, n_tokens = cfg.stream(args.batch[1]), cfg.stream(args.batch[0] * args.batch[1])
    paths = pattern.attention_paths(cfg, seq)
    events.record("model", "attention_path", seq=seq, **paths)
    print(f"ATTENTION {paths}", flush=True)
    dispatch = pattern.dispatch_rows(cfg, n_tokens)
    events.record("model", "dispatch_path", tokens=n_tokens, **dispatch)
    print(f"DISPATCH {dispatch}", flush=True)
    memory = pattern.device_memory_bytes()
    kept = pattern.kept_residuals(cfg, n_tokens, memory, seq)
    events.record("model", "kept_residuals", tokens=n_tokens, memory_bytes=memory, **kept)
    print(f"KEPT {kept}", flush=True)

    def tokens(i: int):
        # two batches in turn, which the model learns by heart: on a fresh batch of
        # uniform ids every step a loss only settles near log(vocabulary)
        return jnp.asarray(np.random.default_rng([0, i % 2]).integers(
            0, cfg.vocab_size, tuple(args.batch)), jnp.int32)

    def step_fn(state, i: int):
        batch = tokens(i)
        if i % args.routing_every == 0:
            counts = {k: np.asarray(v).tolist() for k, v in counts_of(state[0], batch).items()}
            of_state = {k: counts.pop(k) for k in DELTA_COUNTS if k in counts}
            of_noise = {k: counts.pop(k) for k in DIFFUSION_COUNTS if k in counts}
            events.record("model", "moe_routing", step=i, experts_held=list(cfg.experts_held),
                          pairs=int(n_tokens * cfg.top_k), **counts)
            print(f"ROUTING step={i} {counts}", flush=True)
            if of_state:
                events.record("model", "delta_state", step=i, head_ways=cfg.head_ways,
                              **of_state)
                print(f"DELTA step={i} {of_state}", flush=True)
            if of_noise:
                events.record("model", "diffusion", step=i, block=cfg.diffusion.block,
                              **of_noise)
                print(f"DIFFUSION step={i} {of_noise}", flush=True)
        params, opt_state, loss = step(*state, batch)
        losses.append(float(loss))
        return params, opt_state

    run_training(step_fn, (params, init_opt(params)), args.steps,
                 callbacks=[StragglerDetectionCallback(report_time_interval=0.5)],
                 ctx=LoopContext(rank=0, world_size=1))
    assert losses[-1] < losses[0], losses
    print(f"DONE loss={losses[-1]:.4f}", flush=True)


if __name__ == "__main__":
    main()
