"""``correct`` can come out false. Two tests at a size a test run holds (the tiny
widths of the family's ``TINY`` preset, on the CPU), run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- the control: the reference computed in fp8, the precision below the configuration's
  bfloat16, put in the program's place, fails at least one compared number, while the
  reference in the stated precision (bf16) passes all;
- the harness with the timed path broken underneath (a step that returns its state
  unchanged) drives the rest of a run and reports ``correct`` false.

The limits these tests hold the tiny model to are the configuration's own.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_RESILIENCY_LOG_LEVEL", "WARNING")

from benchmark import harness, rehearse  # noqa: E402

SEEDS = (11, 2147483659, 4000000007)


def tiny_run(seed):
    cell = harness.load_cell("mistral7b_steady")
    cell.config = {**cell.config, **harness.load_family(cell.config).TINY}
    return harness.Run(cell, seed, 1.0, False, 0.0, rehearsal=True)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_in_fp8_fails_and_stated_precision_passes(seed, capsys):
    from benchmark.reference import train

    run = tiny_run(seed)
    cfg, limits = run.cell.config, run.cell.config["limits"]
    import numpy as np

    batches = [np.random.default_rng([seed, i]).integers(
        0, cfg["vocab_size"], cfg["batch"]).astype(np.int32) for i in range(3)]
    reference = train.follow(seed % (1 << 32), cfg, batches, "f32")
    stated = train.follow(seed % (1 << 32), cfg, batches, "bf16")
    control = train.follow(seed % (1 << 32), cfg, batches, "fp8")
    ok = harness.compare_with_reference(run, stated, reference, limits)
    assert all(row["ok"] for row in ok), ok
    run.problems.clear()
    bad = harness.compare_with_reference(run, control, reference, limits)
    assert not all(row["ok"] for row in bad), bad
    assert run.problems


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax

    real_build = harness.Session.build_state

    def broken_build(self):
        state = real_build(self)
        sound = jax.jit(self.train_step)  # no donation: the state handed in survives
        self.step = lambda params, opt_state, tokens: (
            params, opt_state, sound(params, opt_state, tokens)[2])
        return state

    monkeypatch.setattr(harness.Session, "build_state", broken_build)
    run, metrics = rehearse.rehearse("mistral7b_steady", SEEDS[1], 1.0, False)
    result = run.result(metrics)
    assert result["correct"] is False and result["failed"] >= 1
    assert any(row["gap"] > row["limit"] for row in result["compared"].values())
    assert any("change_norms" in p or "grad_norms" in p for p in run.problems), run.problems


def test_the_unbroken_harness_is_correct():
    run, metrics = rehearse.rehearse("mistral7b_steady", SEEDS[2], 1.0, False)
    result = run.result(metrics)
    assert result["correct"] is True, run.problems
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p95", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "compared" and len(result["compared"]) == 5
    assert all(row["gap"] <= row["limit"] for row in result["compared"].values())
