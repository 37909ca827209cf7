"""The five readers of ``benchmark/program_spans.py`` against two recorded v5e traces:
``data/v5e_step.xplane.pb`` (five executions of a ``value_and_grad`` + AdamW step jitted
as ``train_step`` under the product's loop with the straggler callback attached and the
harness's ``bench/`` annotations; recorded on the chip by PR 25 with
``benchmark/tools/record_step_trace.py``, the same file as tests/telemetry/data) and
``data/v5e_window.xplane.pb`` (PR 21: telemetry programs only, no annotation of the
program's). Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness, program_spans, xplane  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = ("model.fwd_ms", "model.bwd_ms", "model.opt_ms", "loop.hooks_ms", "telemetry.report_ms")


class StubRun:
    """What a reader may touch of a ``harness.Run``."""

    def __init__(self, workdir):
        self.workdir, self.notes, self.lines, self.problems = str(workdir), {}, {}, []

    def say(self, what, **facts):
        self.lines[what] = facts

    def problem(self, what):
        self.problems.append(what)


def run_on(tmp_path, trace: str | None) -> tuple[StubRun, dict]:
    if trace is not None:
        os.makedirs(tmp_path / "trace" / "plugins" / "profile" / "t")
        shutil.copyfile(os.path.join(DATA, trace),
                        tmp_path / "trace" / "plugins" / "profile" / "t" / "host.xplane.pb")
    run = StubRun(tmp_path)
    return run, {name: harness.load_by_path("layer_metrics", name).read(run) for name in NEW}


def test_the_five_readers_on_the_recorded_step(tmp_path):
    run, got = run_on(tmp_path, "v5e_step.xplane.pb")
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    phases = run.lines["step_phases"]
    assert phases["executions"] == 5
    split = got["model.fwd_ms"] + got["model.bwd_ms"] + got["model.opt_ms"]
    assert split == pytest.approx(phases["module_ms"], rel=0.02)
    assert 0 <= phases["mixed_share"] < 1 and 0 <= phases["unnamed_share"] < 1
    # a report is a part of one hook of one callback
    assert got["telemetry.report_ms"] <= got["loop.hooks_ms"]
    gaps = run.lines["gaps_by_program_span"]
    assert gaps["by_harness"].get("hooks", 0) > 0
    assert gaps["hooks_named_share"] >= 0.9
    assert all(k == "none" or k.startswith("tpures/") for k in gaps["by_program"])
    assert sum(gaps["by_program"].values()) == pytest.approx(sum(gaps["by_harness"].values()))
    assert run.problems == []
    assert os.listdir(tmp_path) == ["trace"]  # nothing written beside the trace


@pytest.mark.parametrize("trace", ["v5e_window.xplane.pb", None])
def test_nothing_to_read_is_none_and_no_problem(tmp_path, trace):
    """A program without ``tpures/`` annotations and a trace without a ``train_step``
    program (the parent commit's side of a check), and a run that wrote no trace."""
    run, got = run_on(tmp_path, trace)
    assert got == dict.fromkeys(NEW)
    assert run.problems == [] and "step_phases" not in run.lines


def test_a_program_from_before_the_join_has_no_split(tmp_path, monkeypatch):
    """The driver lays these files over the parent's checkout: its
    ``device_profiler`` has no ``hlo_instructions`` to import."""
    from tpu_resiliency.telemetry import device_profiler

    monkeypatch.delattr(device_profiler, "hlo_instructions")
    run, got = run_on(tmp_path, "v5e_step.xplane.pb")
    assert got["model.fwd_ms"] is None and got["model.opt_ms"] is None
    assert got["loop.hooks_ms"] > 0 and run.problems == []


def test_owner_is_the_innermost_annotation_that_covers_most_of_the_gap():
    spans = [(0.0, 10.0, "tpures/loop/on_step_end/Cb"), (1.0, 9.0, "tpures/telemetry/report"),
             (1.0, 2.0, "tpures/telemetry/report/summary"),
             (5.0, 9.0, "tpures/telemetry/report/materialize")]
    assert program_spans.owner((6.0, 8.0), spans) == "tpures/telemetry/report/materialize"
    assert program_spans.owner((3.0, 4.0), spans) == "tpures/telemetry/report"
    # 40% in materialize, all of it in the report: the report covers most of it
    assert program_spans.owner((2.0, 7.0), spans) == "tpures/telemetry/report"
    assert program_spans.owner((9.5, 12.0), spans) == "tpures/loop/on_step_end/Cb"
    assert program_spans.owner((11.0, 12.0), spans) is None
    assert xplane.attribute((11.0, 12.0), spans) == "unattributed"
