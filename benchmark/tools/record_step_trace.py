#!/usr/bin/env python3
"""Record the small step trace the tests read (``tests/data/v5e_step.xplane.pb``, the same
file under ``tests/telemetry/data``). By hand, through the chip tool:

    python3 benchmark/tools/record_step_trace.py chiprun_out/v5e_step.xplane.pb

A ``value_and_grad`` + AdamW step over a two-layer scanned MLP, jitted under the name
``train_step`` and driven by the product's loop with the straggler callback attached as
a benchmark run attaches it (mesh report on every step, no profiler windows of its own),
between the harness's ``bench/feed``, ``bench/step`` and ``bench/hooks`` annotations. The
matrices are wide enough that the pauses between ops stay under 2% of the step, and the
program small enough that the file stays under 200 KB. Traced: four hook rounds, so five
executions, after the detector has locked its report interval.
"""

import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARM_STEPS = 24  # as the steady traffic: the detector reports on every step after 17
TRACED_ROUNDS = 4  # hook rounds inside the window: one execution of the step more
#: at 2048 x 2048 the pauses between ops are 0.2% of the step and the file is 194,720 bytes
#: (chip run PR 25); at 1024 the compiler emits three times the ops and 260 KB
WIDTH = BATCH = 2048


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from tpu_resiliency.integrations import LoopContext, StragglerDetectionCallback, run_training

    def loss_fn(params, x, y):
        def layer(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(layer, x, params["layers"])
        return jnp.mean(jnp.square(h @ params["out"] - y))

    def train_step(params, opt, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        count = opt["count"] + 1
        mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, opt["mu"], grads)
        nu = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, opt["nu"], grads)
        params = jax.tree.map(
            lambda p, m, v: p - 1e-3 * (
                (m / (1 - 0.9 ** count)) / (jnp.sqrt(v / (1 - 0.999 ** count)) + 1e-8)
                + 0.01 * p),
            params, mu, nu)
        return params, {"count": count, "mu": mu, "nu": nu}, loss

    d, b = WIDTH, BATCH
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"layers": jax.random.normal(keys[0], (2, d, d)) * d ** -0.5,
              "out": jax.random.normal(keys[1], (d, d)) * d ** -0.5}
    opt = {"count": jnp.zeros((), jnp.int32),
           "mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params)}
    x, y = jax.random.normal(keys[2], (b, d)), jax.random.normal(keys[3], (b, d))
    step = jax.jit(train_step, donate_argnums=(0, 1))

    annotate = jax.profiler.TraceAnnotation
    trace_dir = tempfile.mkdtemp(prefix="step_trace_")
    ctx = LoopContext(rank=0, world_size=1)
    hooks = []

    def step_fn(state, i):
        if hooks:
            hooks.pop().__exit__(None, None, None)
        if i == WARM_STEPS:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        with annotate("bench/feed"):
            batch = (x + 0.0, y)
        with annotate("bench/step"):
            new_params, new_opt, loss = step(*state, *batch)
            float(loss)
        if i == WARM_STEPS + TRACED_ROUNDS:
            jax.block_until_ready(jnp.zeros(()))
            jax.profiler.stop_trace()
            ctx.should_stop = True
        else:
            hooks.append(annotate("bench/hooks"))
            hooks[-1].__enter__()
        return new_params, new_opt

    callback = StragglerDetectionCallback(
        report_time_interval=0.0, use_device_mesh=True, use_pallas=True,
        profile_programs_every=0, mesh_signal_capacity=64)
    try:
        run_training(step_fn, (params, opt), 10 ** 9, callbacks=[callback], ctx=ctx)
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise SystemExit(f"expected one trace under {trace_dir}, found {files}")
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copyfile(files[0], args.out)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = jax.devices()[0]
    print({"out": args.out, "bytes": os.path.getsize(args.out),
           "platform": device.platform, "kind": device.device_kind})
    return 0


if __name__ == "__main__":
    sys.exit(main())
