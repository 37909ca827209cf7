"""Flagship-model throughput on the local accelerator: tokens/sec for the
Llama-style transformer's full train step (fwd + bwd + adamw), bf16 activations.

Not the driver's headline metric (that's bench.py's telemetry hot loop) — this
validates the model/parallelism stack on real hardware and gives the resiliency
overhead a denominator: a telemetry push at ~0.03 ms/step is noise against a real
step. Prints one JSON line — on a TPU or not at all: off one it exits non-zero
with no result line, and MFU is printed only for a ``device_kind`` whose peak is
written down in :data:`PEAK_BF16_FLOPS`.

    python scripts/bench_model.py [--layers 8] [--d-model 1024] [--batch 8] [--seq 1024]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

#: bf16 peak FLOP/s of one chip, by ``device_kind`` as JAX reports it. Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s). A device that is not
#: here gets no MFU — never another device's peak.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=2816)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_resiliency.models import transformer as tfm
    from tpu_resiliency.platform.device import apply_compile_cache_env

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench_model.py measures on a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). No result.",
            file=sys.stderr,
        )
        return 1
    apply_compile_cache_env()

    cfg = tfm.TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.heads,
        n_kv_heads=args.kv_heads,
        d_ff=args.d_ff,
        max_seq_len=args.seq,
    )
    print(f"backend: {jax.default_backend()}, devices: {jax.devices()}", file=sys.stderr)

    train_step, init_opt = tfm.make_train_step(cfg)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = jax.jit(init_opt)(params)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, args.seq)), jnp.int32)

    step = jax.jit(train_step, donate_argnums=(0, 1))
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, tokens)
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0

    # Per-step device time from the framework's profiler (the program's
    # duration on the device plane; a TPU trace without one raises).
    from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler

    prof = DeviceTimeProfiler()
    with prof:
        for _ in range(args.iters):
            params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
    steps = [st["med"] for name, st in prof.get_stats().items() if "train_step" in name]
    if not steps:
        raise RuntimeError(
            f"profiler window has no train_step program: {sorted(prof.get_stats())}"
        )
    per_step = steps[-1]
    tokens_per_s = args.batch * args.seq / per_step

    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(
        json.dumps(
            {
                "metric": (
                    f"transformer train-step throughput ({n_params / 1e6:.0f}M params, "
                    f"{args.layers}L x {args.d_model}d, batch {args.batch} x seq "
                    f"{args.seq}, bf16, compile {compile_s:.1f}s)"
                ),
                "value": round(tokens_per_s, 1),
                "unit": "tokens/s",
                "ms_per_step": round(per_step * 1e3, 2),
                "final_loss": round(float(loss), 4),
                "backend": dev.platform,
                "device_kind": dev.device_kind,
                "device_count": len(jax.devices()),
                # 6*N*tokens/s FLOPs over the chip's written-down bf16 peak
                **(
                    {"mfu": round(
                        6 * n_params * tokens_per_s / PEAK_BF16_FLOPS[dev.device_kind], 4
                    )}
                    if dev.device_kind in PEAK_BF16_FLOPS else {}
                ),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
