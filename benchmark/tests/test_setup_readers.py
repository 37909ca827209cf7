"""The six readers of PR 37 (``compile.step_trace_s``, ``compile.step_load_s``; and, kept
without an entry for the fault job kind, ``ckpt.restore_read_s``, ``ckpt.restore_verify_s``,
``ckpt.restore_place_s``, ``compile.rejit_load_s``) against a recorded event list:
``data/v5e_fault_events.jsonl`` holds the ``compile`` and ``timing`` records and the
window of one run of ``mistral7b_fault`` on a v5e (my chip run, PR 37; see the file's
first line). On a stream without the new records (the parent commit's side of a check)
every one of them returns ``None`` and raises nothing. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness, rehearse  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_fault_events.jsonl")
SETUP = ("compile.step_trace_s", "compile.step_load_s")
FAULT = ("ckpt.restore_read_s", "ckpt.restore_verify_s", "ckpt.restore_place_s",
         "compile.rejit_load_s")


class RecordedRun:
    """What a reader may touch of a ``harness.Run``, filled from a recorded stream."""

    def __init__(self, events, t_open, seconds, setup_s=None):
        self.events, self.t_open, self.seconds, self.setup_s = events, t_open, seconds, setup_s
        self.notes, self.lines, self.problems = {}, {}, []

    @property
    def deadline(self):
        return self.t_open + self.seconds

    def in_window(self, t):
        return self.t_open <= t <= self.deadline

    def say(self, what, **facts):
        self.lines[what] = facts

    def problem(self, what):
        self.problems.append(what)


def recorded(keep=lambda e: True) -> RecordedRun:
    with open(DATA) as f:
        head, *events = [json.loads(line) for line in f]
    return RecordedRun([e for e in events if keep(e)], head["t_open"], head["seconds"],
                       head["setup_s"])


def read_all(run, names=SETUP + FAULT) -> dict:
    return {name: harness.load_by_path("layer_metrics", name).read(run) for name in names}


def test_the_six_readers_on_the_recorded_fault_run():
    run = recorded()
    got = read_all(run)
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    assert run.problems == []
    # set-up cannot have spent more on the step's trace and load than it took
    assert got["compile.step_trace_s"] + got["compile.step_load_s"] <= run.setup_s
    line = run.lines["compile_in_setup"]
    step = line["step"]
    assert got["compile.step_trace_s"] == pytest.approx(step["trace_s"] + step["lower_s"])
    assert got["compile.step_load_s"] == pytest.approx(step["backend_s"])
    assert set(step["cache"]) <= {"hit", "miss", "uncached"} and len(step["cache"]) == 2
    assert len(line["largest"]) == 10
    seconds = [row["seconds"] for row in line["largest"]]
    assert seconds == sorted(seconds, reverse=True)
    assert line["seconds"] >= sum(seconds) and line["programs"] == sum(line["by_cache"].values())
    names = [row["fun_name"] for row in line["largest"]]
    assert "jit(train_step)" in names and "jit(<lambda>)" in names  # the reference's, by name


def test_the_restore_phases_fit_inside_the_restore_the_harness_timed():
    """The recorded run's ``restores`` (the harness's clock around ``restore_latest`` and
    ``block_until_ready``) are in the file's first line."""
    with open(DATA) as f:
        head = json.loads(f.readline())
    run = recorded()
    got = read_all(run, FAULT[:3])
    restore_s = harness.median(r["restore_s"] for r in head["restores"] if run.in_window(r["ts"]))
    assert sum(got.values()) <= restore_s


def test_the_rejit_is_the_window_s_compile_of_the_step_alone():
    run = recorded()
    inside = [e for e in run.events if e["kind"] == "compile" and run.in_window(e["ts"])]
    steps = [e for e in inside if e["fun_name"] == "jit(train_step)"]
    assert steps and len(steps) < len(inside)  # other programs load in the window too
    got = read_all(run, ("compile.rejit_load_s",))["compile.rejit_load_s"]
    assert got == harness.median(e["trace_s"] + e["lower_s"] + e["backend_s"] for e in steps)


@pytest.mark.parametrize("keep", [
    lambda e: e["kind"] != "compile" and not e.get("name", "").startswith("ckpt.load."),
    lambda e: False,
], ids=["the-parents-stream", "no-event"])
def test_a_stream_without_the_new_records_reads_none(keep):
    run = recorded(keep)
    assert read_all(run) == dict.fromkeys(SETUP + FAULT)
    assert run.problems == [] and "compile_in_setup" not in run.lines


def test_only_the_steps_own_programs_count():
    """Other programs of set-up are on the log line and in neither metric; a step that
    compiled inside the window is no part of set-up."""
    ev = lambda ts, name, backend: {  # noqa: E731
        "kind": "compile", "ts": ts, "fun_name": name, "trace_s": 1.0, "lower_s": 0.5,
        "backend_s": backend, "cache": "hit"}
    run = RecordedRun([ev(1.0, "jit(<lambda>)", 30.0), ev(2.0, "jit(train_step)", 4.0),
                       ev(3.0, "jit(norms)", 2.0), ev(11.0, "jit(train_step)", 9.0)],
                      t_open=10.0, seconds=51.0)
    got = read_all(run, SETUP)
    assert got == {"compile.step_trace_s": 1.5, "compile.step_load_s": 4.0}
    assert run.lines["compile_in_setup"]["programs"] == 3
    assert read_all(run, ("compile.rejit_load_s",)) == {"compile.rejit_load_s": 10.5}
    no_step = RecordedRun([ev(1.0, "jit(<lambda>)", 30.0)], t_open=10.0, seconds=51.0)
    assert read_all(no_step, SETUP) == dict.fromkeys(SETUP)
    assert "compile_in_setup" in no_step.lines  # what set-up did compile is still said


@pytest.mark.parametrize("trace", [False, True])
def test_a_rehearsed_cell_reports_both_within_its_setup(trace):
    """The real harness, the real program, tiny widths on the CPU: the two readers read
    the run's own events, and what they sum is no larger than the run's ``setup_s``."""
    run, metrics = rehearse.rehearse("mistral7b_steady_noprof", 2370000001, 1.0, trace)
    assert run.problems == []
    got = read_all(run, SETUP)
    assert all(v > 0 for v in got.values())
    assert sum(got.values()) <= run.setup_s
    if trace:
        assert {k: metrics[k] for k in SETUP} == got
    assert run.notes["compile_in_setup"]["backend_s"] == got["compile.step_load_s"]
