"""Train the dense model as a looped decoder (``models/transformer.py``: the stack run
``n_passes`` times on the same weights under sandwich norms, an exit through the head after
every pass, a learned gate that mixes the exits' losses) with the toolkit attached, and
report what its exits do.

Every ``--exits-every`` steps the script runs the model's loss once more on the step's
batch and emits its counts as an ``exit_state`` event: ``exit_share`` (the mean of the exit
distribution, a number a pass, summing to one), ``exit_loss`` (each exit's mean NLL),
``exit_entropy`` (the mean entropy of the exit distribution, at most ``log n_passes``) and
``gate_mean`` (the mean of each pass's gate; the last pass takes what is left). Once,
before the first step, it emits an ``attention_path`` event (whether the attention
products run as the blocked kernels of ``ops/attention.py``, on a TPU at shapes that tile,
or as the plain ``[B, H, T, T]`` scores: this script's tiny widths, anywhere) and a
``kept_residuals`` event: whether the step keeps everything its backward pass reads
(``everything``: nothing is rematerialized), else the named values each layer of each pass
keeps at this batch on this device's memory, and their bytes
(``transformer.kept_residuals``).

Run (CPU simulation)::

    python examples/looped_training.py --cpu --steps 20

Prints ``ATTENTION {...}``, ``KEPT {...}``, one ``EXITS step=<n> ...`` line per exit event
and ``DONE loss=<x>`` on success.
"""

from __future__ import annotations

import argparse
import os

# Allow running this file directly from a repo checkout (no pip install).
import os as _os, sys as _sys
_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="simulate on the CPU (without it $JAX_PLATFORMS / JAX decide)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch", type=int, nargs=2, default=(2, 64), metavar=("B", "T"))
    ap.add_argument("--exits-every", type=int, default=5)
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_resiliency.integrations import LoopContext, StragglerDetectionCallback, run_training
    from tpu_resiliency.models import transformer
    from tpu_resiliency.utils import events

    cfg = transformer.TransformerConfig.tiny_looped(n_passes=args.passes, attention="kernel")
    train_step, init_opt = transformer.make_train_step(cfg)
    step = jax.jit(train_step, donate_argnums=(0, 1))
    counts_of = jax.jit(lambda p, t: transformer.loss_and_counts(p, t, cfg)[1])
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    losses = []
    path = transformer.attention_path(cfg, args.batch[1])
    events.record("model", "attention_path", seq=args.batch[1], **path)
    print(f"ATTENTION {path}", flush=True)
    n_tokens = args.batch[0] * args.batch[1]
    memory = transformer.device_memory_bytes()
    kept = transformer.kept_residuals(cfg, n_tokens, memory, args.batch[1])
    events.record("model", "kept_residuals", tokens=n_tokens, memory_bytes=memory, **kept)
    print(f"KEPT {kept}", flush=True)

    def tokens(i: int):
        # two batches in turn, which the model learns by heart: on a fresh batch of
        # uniform ids every step a loss only settles near log(vocabulary)
        return jnp.asarray(np.random.default_rng([0, i % 2]).integers(
            0, cfg.vocab_size, tuple(args.batch)), jnp.int32)

    def step_fn(state, i: int):
        batch = tokens(i)
        if i % args.exits_every == 0:
            counts = {k: np.asarray(v).tolist() for k, v in counts_of(state[0], batch).items()}
            events.record("model", "exit_state", step=i, passes=cfg.n_passes, **counts)
            print(f"EXITS step={i} {counts}", flush=True)
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
