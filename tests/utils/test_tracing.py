"""Cross-process tracing: span pairing, parent chains, env propagation."""

import json
import os
import subprocess
import sys

import pytest

from tpu_resiliency.utils import events, tracing


@pytest.fixture(autouse=True)
def clean():
    events.clear_sinks()
    saved = {
        k: os.environ.pop(k, None)
        for k in (events.EVENTS_FILE_ENV, tracing.TRACE_ID_ENV, tracing.PARENT_SPAN_ENV)
    }
    yield
    events.clear_sinks()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _sink(tmp_path, name="t.jsonl"):
    path = str(tmp_path / name)
    events.add_sink(events.JsonlSink(path))
    return path


def test_span_pair_shares_envelope_span_id(tmp_path):
    path = _sink(tmp_path)
    tracing.ensure_trace_id()
    with tracing.span("launcher", "launcher.round", round=3):
        pass
    begin, end = events.read_events(path)
    assert begin["kind"] == "span_begin" and end["kind"] == "span_end"
    assert begin["span"] == end["span"] == "launcher.round"
    assert begin["span_id"] == end["span_id"]  # the pairing key
    assert begin["round"] == 3
    assert end["ok"] is True and end["duration_s"] >= 0
    assert begin["trace_id"] == end["trace_id"] == tracing.trace_id()


def test_nested_spans_form_a_parent_chain(tmp_path):
    path = _sink(tmp_path)
    with tracing.span("a", "outer"):
        with tracing.span("a", "inner"):
            pass
    recs = events.read_events(path)
    outer_b, inner_b, inner_e, outer_e = recs
    assert outer_b["parent_id"] is None
    assert inner_b["parent_id"] == outer_b["span_id"]
    assert inner_e["span_id"] == inner_b["span_id"]
    assert outer_e["span_id"] == outer_b["span_id"]


def test_plain_record_carries_the_active_span(tmp_path):
    path = _sink(tmp_path)
    with tracing.span("a", "outer"):
        events.record("worker", "ckpt_saved", iteration=7)
    recs = events.read_events(path)
    assert recs[1]["kind"] == "ckpt_saved"
    assert recs[1]["span_id"] == recs[0]["span_id"]
    # Outside any span (and with no env parent) events carry no span_id.
    events.record("worker", "bare")
    assert "span_id" not in events.read_events(path)[-1]


def test_span_failure_records_error_and_reraises(tmp_path):
    path = _sink(tmp_path)
    with pytest.raises(ValueError):
        with tracing.span("a", "boom"):
            raise ValueError("nope")
    end = events.read_events(path)[-1]
    assert end["kind"] == "span_end" and end["ok"] is False
    assert "ValueError" in end["error"]
    # The failed span was popped: no stale parent leaks onto later events.
    events.record("a", "after")
    assert "span_id" not in events.read_events(path)[-1]


def test_ensure_trace_id_mints_once_and_exports():
    tid = tracing.ensure_trace_id()
    assert os.environ[tracing.TRACE_ID_ENV] == tid
    assert tracing.ensure_trace_id() == tid  # idempotent


def test_annotate_is_a_null_context_until_jax_is_loaded(monkeypatch):
    """``annotate`` never imports JAX: without it in ``sys.modules`` (the launcher,
    the agents, the rank monitor) it is a null context; with it, the profiler's own
    ``TraceAnnotation``."""
    import contextlib

    import jax

    assert isinstance(tracing.annotate("tpures/x"), jax.profiler.TraceAnnotation)
    monkeypatch.delitem(sys.modules, "jax")
    null = tracing.annotate("tpures/x")
    assert isinstance(null, contextlib.nullcontext)
    with null:
        pass
    assert "jax" not in sys.modules


def test_a_span_leaves_a_process_off_jax():
    child = (
        "import sys\n"
        "from tpu_resiliency.utils.tracing import annotate, span\n"
        "with span('launcher', 'launcher.round'):\n"
        "    with annotate('tpures/inside'):\n"
        "        pass\n"
        "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') for m in sys.modules) else 0)\n"
    )
    r = subprocess.run([sys.executable, "-c", child], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stderr


def test_spans_and_annotations_are_on_the_profilers_host_plane(tmp_path, profiler_window):
    path = _sink(tmp_path)
    with profiler_window() as names:
        with tracing.span("ckpt", "ckpt.save.enqueue"):
            with tracing.annotate("tpures/inside"):
                pass
        with pytest.raises(ValueError):
            with tracing.span("a", "boom"):
                raise ValueError("nope")
    ours = [n for n in names if n.startswith(tracing.ANNOTATION_PREFIX)]
    assert ours == ["tpures/ckpt.save.enqueue", "tpures/inside", "tpures/boom"]
    # the event stream is what it was: the pair per span, nothing per annotation
    assert [r["kind"] for r in events.read_events(path)] == [
        "span_begin", "span_end", "span_begin", "span_end"]


def test_child_env_carries_trace_and_active_span():
    tracing.ensure_trace_id()
    with tracing.span("a", "round") as sid:
        env = tracing.child_env()
        assert env[tracing.TRACE_ID_ENV] == tracing.trace_id()
        assert env[tracing.PARENT_SPAN_ENV] == sid
    assert tracing.PARENT_SPAN_ENV not in tracing.child_env()


def test_env_propagation_across_a_spawned_subprocess(tmp_path):
    """The launcher pattern end to end: a child process spawned with
    ``child_env`` parents its spans/events to the spawner's active span and
    shares its trace id — with NO tracing code in the child beyond use."""
    path = str(tmp_path / "x.jsonl")
    os.environ[events.EVENTS_FILE_ENV] = path
    events.clear_sinks()  # child wires itself from the env var
    tid = tracing.ensure_trace_id()
    child = (
        "from tpu_resiliency.utils import events\n"
        "from tpu_resiliency.utils.tracing import span\n"
        "events.record('worker', 'hello')\n"
        "with span('worker', 'work'):\n"
        "    events.record('worker', 'inside')\n"
    )
    with tracing.span("launcher", "launcher.round") as round_sid:
        env = {**os.environ, **tracing.child_env()}
        r = subprocess.run(
            [sys.executable, "-c", child],
            env=env, capture_output=True, text=True, timeout=60,
        )
    assert r.returncode == 0, r.stderr
    recs = events.read_events(path)
    by_kind = {r["kind"]: r for r in recs if r.get("source") == "worker"}
    # Same trace end to end.
    assert all(r["trace_id"] == tid for r in recs if "trace_id" in r)
    # A bare record in the child parents to the spawner's round span...
    assert by_kind["hello"]["span_id"] == round_sid
    # ...the child's own span nests under it...
    worker_begin = next(r for r in recs if r.get("span") == "work"
                        and r["kind"] == "span_begin")
    assert worker_begin["parent_id"] == round_sid
    # ...and records inside the child's span carry the child span's id.
    assert by_kind["inside"]["span_id"] == worker_begin["span_id"]


def test_untraced_process_pays_no_envelope_bytes(tmp_path):
    path = _sink(tmp_path)
    events.record("a", "plain")
    line = open(path).read()
    assert "trace_id" not in line and "span_id" not in line
    rec = json.loads(line)
    assert rec["kind"] == "plain"
