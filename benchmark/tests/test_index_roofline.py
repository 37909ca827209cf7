"""The reader ``attn.indexer_roofline`` on a synthetic reduced trace, run by hand with the
others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- where the step's index-score kernels are in the trace, the share is the family's
  least-work count over the chip's bf16 peak over the median step's summed kernel time;
- where they are not (the parent of the PR that added them, an indexer on the blocks), where
  there is no trace, or where a kernel's calls do not divide into the step's executions,
  nothing;
- at the cell's shapes no time the kernels could reach reads over 50%: the contraction is
  one head's 64 columns, half of the MXU's depth.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, xplane  # noqa: E402

CELL = "keye_vl2_a3b_steady_noprof"
NAME = "attn.indexer_roofline"
#: calls a step of each kernel at the cell's shapes: six layers x four groups of query
#: rows, the forward kernel again in the backward pass
CALLS = {"index_scores_fwd": 48, "index_scores_dq": 24, "index_scores_dk": 24}


def reduced(steps: int, call_s: dict, slow_step: int | None = None) -> xplane.Reduced:
    """A trace of ``steps`` executions of the train step, every call of a kernel taking
    ``call_s[kernel]`` seconds (three times that in ``slow_step``), among other ops."""
    ops = {"fusion": [1e-3] * (40 * steps), "blocked_attention_fwd": [3.75e-3] * (6 * steps)}
    for kernel, seconds in call_s.items():
        ops[kernel] = [seconds * (3 if step == slow_step else 1)
                       for step in range(steps) for _ in range(CALLS[kernel])]
    return xplane.Reduced(window_s=5.0, planes=1, busy_s=4.9,
                          programs={"jit_train_step": [0.42] * steps, "jit_body": [4e-6] * steps},
                          ops=ops, gaps=[])


@pytest.fixture
def run():
    run = harness.Run(harness.load_cell(CELL), 1, 1.0, True, 0.0, rehearsal=True)
    run.device = {"kind": "TPU v5 lite"}
    yield run
    run.cleanup()


def read(run):
    return harness.load_by_path("layer_metrics", NAME).read(run)


def test_the_cell_reports_it_and_no_other_cell_does():
    manifest = harness.read_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "model", "moves": "tokens_per_s", "workloads": [CELL]}


def test_the_share_is_the_least_work_over_the_median_steps_kernel_time(run):
    config = run.cell.config
    family = harness.load_family(config)
    batch, seq = config["batch"]
    # 6 x 4,096.5 x 16 x 64 operations a token a layer, 1.24e12 a step: 6.3 ms at the peak
    ops = family.index_score_flops(config, seq) * batch * seq * config["num_hidden_layers"]
    assert ops == pytest.approx(6 * 4096.5 * 16 * 64 * 8192 * 6)
    call_s = {"index_scores_fwd": 0.2e-3, "index_scores_dq": 0.5e-3, "index_scores_dk": 0.45e-3}
    step_s = sum(CALLS[k] * s for k, s in call_s.items())  # 32.4 ms
    run.trace_result = reduced(11, call_s, slow_step=4)  # one slow step leaves the median
    assert read(run) == pytest.approx(100 * ops / 197e12 / step_s)
    assert 19 < read(run) < 20


@pytest.mark.parametrize("case", ["no_trace", "no_kernels", "no_step", "ragged", "unknown_chip"])
def test_nothing_to_read_is_nothing(run, case):
    call_s = dict.fromkeys(CALLS, 0.3e-3)
    if case == "no_kernels":  # the parent: the scores on the jax.numpy blocks
        run.trace_result = reduced(11, {})
    elif case == "no_step":
        run.trace_result = reduced(0, call_s)
    elif case == "ragged":  # calls that do not divide into the step's executions
        run.trace_result = reduced(11, call_s)
        run.trace_result.ops["index_scores_dq"].pop()
    elif case == "unknown_chip":
        run.trace_result = reduced(11, call_s)
        run.device = {"kind": "TPU v9"}
    assert read(run) is None


def test_a_family_that_counts_no_index_scores_reads_nothing(run, monkeypatch):
    run.trace_result = reduced(11, dict.fromkeys(CALLS, 0.3e-3))
    assert read(run) is not None
    laguna = harness.load_by_path("families", "laguna")
    monkeypatch.setattr(harness, "load_family", lambda config: laguna)
    assert read(run) is None


@pytest.mark.parametrize("mxu_share,products", [(0.1, 6), (0.25, 6), (0.5, 6), (0.5, 3)])
def test_no_time_the_kernels_can_reach_reads_over_half(run, mxu_share, products):
    """A contraction of 64 fills half of the MXU's depth, so the kernels' own products run
    at no more than half the peak; they do six products of the score's size where the
    least work is three (one recomputation in each backward kernel, the forward again for
    the backward pass). At ``mxu_share`` of the peak on ``products`` products the share of
    the least work's roofline is ``3 / products`` of it: 25% at best as built, 50% for a
    program that recomputed nothing."""
    config = run.cell.config
    batch, seq = config["batch"]
    least = (harness.load_family(config).index_score_flops(config, seq) * batch * seq
             * config["num_hidden_layers"])
    step_s = (products / 3) * least / (mxu_share * 197e12)
    run.trace_result = reduced(9, dict.fromkeys(CALLS, step_s / sum(CALLS.values())))
    assert read(run) == pytest.approx(100 * mxu_share * 3 / products)
    assert read(run) < 50.0 + 1e-9
