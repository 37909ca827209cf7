"""The index-score kernels' share of their roofline: the least operations an indexer's
scores need in one step (forward and backward over the causal half of the keys, every
head, no recomputation: ``families/keye.py:index_score_flops`` a token a layer x the
step's tokens x the layers) over the chip's bf16 peak (``peaks.json``), over the device
time a step of the program's index-score kernels, in %. The kernels are found by name, as
``breakdown.device_ops`` prints them: the custom calls ``index_scores_fwd``,
``index_scores_dq`` and ``index_scores_dk`` of ``tpu_resiliency/ops/index_scores.py``. A
step's time is the sum of its calls (each kernel's events fall into as many equal runs as
the trace has executions of the train step); median over the executions. The contraction
is one head's 64 columns, half of the MXU's depth, so the share cannot pass 50%; bytes do
not bound it (the float32 scores of the cell's step written once and their cotangent
read once are 2.0e9 B, 2.5 ms at the chip's bandwidth, where the operations are 6.3 ms at
its peak). Nothing where the program has no
such kernels (a program from before them, or an indexer on the ``jax.numpy`` blocks),
where the family counts no index scores, or where there is no trace."""

from benchmark import harness

KERNELS = ("index_scores_fwd", "index_scores_dq", "index_scores_dk")
STEP_PROGRAM = "train_step"


def step_seconds(reduced) -> float | None:
    """Median over the step's executions of the summed seconds of :data:`KERNELS`."""
    steps = sum(len(v) for name, v in reduced.programs.items() if STEP_PROGRAM in name)
    calls = [reduced.ops[name] for name in KERNELS if reduced.ops.get(name)]
    if not steps or not calls or any(len(v) % steps for v in calls):
        return None
    return harness.median(
        sum(sum(v[i * len(v) // steps:(i + 1) * len(v) // steps]) for v in calls)
        for i in range(steps))


def read(run):
    if run.trace_result is None:
        return None
    config = run.cell.config
    peaks = harness.read_json(harness.HERE, "peaks.json")["device_kinds"].get(run.device["kind"])
    count = getattr(harness.load_family(config), "index_score_flops", None)
    seconds = step_seconds(run.trace_result)
    if peaks is None or count is None or not seconds:
        return None
    batch, seq = config["batch"]
    ops = count(config, seq) * batch * seq * config["num_hidden_layers"]
    run.say("index_score_kernels", step_ms=seconds * 1e3, operations=ops)
    return 100.0 * ops / peaks["bf16_flops_per_s"] / seconds
