"""Structured event stream: records, sinks, env wiring, @prof timing."""

import json
import os

import pytest

from tpu_resiliency.utils import events


@pytest.fixture(autouse=True)
def clean_sinks():
    events.clear_sinks()
    old = os.environ.pop(events.EVENTS_FILE_ENV, None)
    yield
    events.clear_sinks()
    if old is not None:
        os.environ[events.EVENTS_FILE_ENV] = old


def test_record_to_jsonl_sink(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    sink = events.JsonlSink(path)
    events.add_sink(sink)
    events.record("launcher", "rendezvous_round", round=3, world_size=8)
    events.record("inprocess", "restart_signalled", iteration=1)
    sink.close()
    recs = events.read_events(path)
    assert [r["kind"] for r in recs] == ["rendezvous_round", "restart_signalled"]
    assert recs[0]["source"] == "launcher" and recs[0]["round"] == 3
    assert recs[0]["pid"] == os.getpid()
    assert "ts" in recs[0]


def test_env_var_wires_sink(tmp_path):
    path = str(tmp_path / "env_ev.jsonl")
    os.environ[events.EVENTS_FILE_ENV] = path
    events.record("watchdog", "hang_detected", global_rank=5, reason="hb timeout")
    recs = events.read_events(path)
    assert len(recs) == 1 and recs[0]["global_rank"] == 5


def test_rank_from_env(tmp_path, monkeypatch):
    path = str(tmp_path / "r.jsonl")
    events.add_sink(events.JsonlSink(path))
    monkeypatch.setenv("RANK", "7")
    events.record("checkpoint", "ckpt_saved", iteration=40)
    assert events.read_events(path)[0]["rank"] == 7


def test_sink_failure_never_raises():
    def bad_sink(ev):
        raise RuntimeError("sink down")

    events.add_sink(bad_sink)
    events.record("launcher", "anything")  # must not raise


def test_reserved_payload_keys_do_not_collide(tmp_path):
    path = str(tmp_path / "c.jsonl")
    events.add_sink(events.JsonlSink(path))
    events.record("x", "y", ts=123, pid=-1)
    rec = events.read_events(path)[0]
    assert rec["source"] == "x" and rec["ts"] != 123  # envelope wins
    assert rec["p_ts"] == 123 and rec["p_pid"] == -1


def test_prof_decorator(tmp_path):
    path = str(tmp_path / "p.jsonl")
    events.add_sink(events.JsonlSink(path))

    @events.prof("checkpoint")
    def work(x):
        return x * 2

    @events.prof("checkpoint", name="explode")
    def bad():
        raise ValueError("nope")

    assert work(21) == 42
    with pytest.raises(ValueError):
        bad()
    recs = events.read_events(path)
    assert recs[0]["kind"] == "timing" and recs[0]["name"] == "work" and recs[0]["ok"]
    assert recs[1]["name"] == "explode" and not recs[1]["ok"]
    assert "ValueError" in recs[1]["error"]
    assert recs[0]["duration_s"] >= 0


def test_read_events_tolerates_torn_line(tmp_path):
    path = tmp_path / "torn.jsonl"
    path.write_text(json.dumps({"kind": "a"}) + "\n" + '{"kind": "b", "tru')
    assert [r["kind"] for r in events.read_events(str(path))] == ["a"]


def test_read_events_window_filters_at_read_time(tmp_path):
    path = tmp_path / "win.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in [
        {"kind": "old", "ts": 10.0},
        {"kind": "in_a", "ts": 20.0},
        {"kind": "no_ts"},
        {"kind": "in_b", "ts": 25.0},
        {"kind": "future", "ts": 99.0},
    ]) + "\n")
    recs = events.read_events(str(path), since=20.0, until=30.0)
    assert [r["kind"] for r in recs] == ["in_a", "in_b"]
    # Unbounded read keeps everything, ts-less records included.
    assert len(events.read_events(str(path))) == 5


def test_debug_time_nesting_and_event(tmp_path, caplog):
    import logging

    from tpu_resiliency.utils import events
    from tpu_resiliency.utils.timers import debug_time

    path = str(tmp_path / "t.jsonl")
    events.add_sink(events.JsonlSink(path))

    with caplog.at_level(logging.DEBUG, logger="tpu_resiliency"):
        with debug_time("outer", source="checkpoint"):
            with debug_time("inner", source="checkpoint"):
                pass

    lines = [r.message for r in caplog.records if "ms" in r.message]
    assert any(m.startswith("  inner:") for m in lines)  # nested → indented
    assert any(m.startswith("outer:") for m in lines)
    # Nested scopes reach the event stream too, each with where it sits.
    recs = [r for r in events.read_events(path) if r["kind"] == "timing"]
    assert [(r["name"], r["depth"], r["parent"]) for r in recs] == [
        ("inner", 1, "outer"), ("outer", 0, None)]
    assert recs[0]["duration_s"] <= recs[1]["duration_s"]


def test_debug_time_payload_failure_and_sibling_scopes(tmp_path):
    from tpu_resiliency.utils import events
    from tpu_resiliency.utils.timers import debug_time

    path = str(tmp_path / "t.jsonl")
    events.add_sink(events.JsonlSink(path))
    with pytest.raises(ValueError):
        with debug_time("root", source="checkpoint", bytes=7):
            with debug_time("first", source="checkpoint"):
                pass
            with debug_time("second", source="checkpoint", leaves=2):
                raise ValueError("nope")
    with debug_time("after", source="checkpoint"):  # the stack unwound: a root again
        pass
    recs = {r["name"]: r for r in events.read_events(path) if r["kind"] == "timing"}
    assert recs["root"]["bytes"] == 7 and recs["second"]["leaves"] == 2
    assert recs["first"]["ok"] is True and recs["first"]["parent"] == "root"
    assert recs["second"]["ok"] is False and "nope" in recs["second"]["error"]
    assert recs["root"]["ok"] is False and recs["root"]["depth"] == 0
    assert recs["after"]["depth"] == 0 and recs["after"]["parent"] is None


def test_debug_time_scopes_are_per_thread(tmp_path):
    import threading

    from tpu_resiliency.utils import events
    from tpu_resiliency.utils.timers import debug_time

    path = str(tmp_path / "t.jsonl")
    events.add_sink(events.JsonlSink(path))

    def other():
        with debug_time("elsewhere", source="checkpoint"):
            pass

    with debug_time("here", source="checkpoint"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    recs = {r["name"]: r for r in events.read_events(path) if r["kind"] == "timing"}
    assert recs["elsewhere"]["depth"] == 0 and recs["elsewhere"]["parent"] is None


def test_debug_time_enters_the_annotation(tmp_path, profiler_window):
    from tpu_resiliency.utils.timers import debug_time, SummedTime

    with profiler_window() as names:
        with debug_time("ckpt.outer", source="checkpoint"):
            with debug_time("ckpt.inner", source="checkpoint"):
                pass
            pieces = SummedTime("ckpt.pieces", source="checkpoint")
            for i in range(2):
                with pieces.piece(i, 10):
                    pass
            pieces.close()
    ours = [n for n in names if n.startswith("tpures/ckpt.")]
    assert ours == ["tpures/ckpt.outer", "tpures/ckpt.inner", "tpures/ckpt.pieces",
                    "tpures/ckpt.pieces"]


def test_summed_time_is_one_record_of_many_pieces(tmp_path):
    import time

    from tpu_resiliency.utils import events
    from tpu_resiliency.utils.timers import debug_time, SummedTime

    path = str(tmp_path / "t.jsonl")
    events.add_sink(events.JsonlSink(path))
    with debug_time("root", source="checkpoint"):
        reading = SummedTime("reading", source="checkpoint")
        checking = SummedTime("checking", source="checkpoint")
        never = SummedTime("never", source="checkpoint")
        with pytest.raises(RuntimeError):
            try:
                for i, nbytes in enumerate((10, 30, 20)):
                    with reading.piece(i, nbytes):
                        time.sleep(0.02 if i == 1 else 0.0)
                    with checking.piece(i, nbytes):
                        if i == 2:
                            raise RuntimeError("bad leaf")
            finally:
                reading.close()
                checking.close()
                never.close()
    recs = {r["name"]: r for r in events.read_events(path) if r["kind"] == "timing"}
    assert "never" not in recs  # no piece ran: nothing to say
    assert recs["reading"]["leaves"] == 3 and recs["reading"]["bytes"] == 60
    assert recs["reading"]["slowest_leaf"] == 1 and recs["reading"]["slowest_leaf_bytes"] == 30
    assert 0.02 <= recs["reading"]["slowest_leaf_s"] <= recs["reading"]["duration_s"]
    assert recs["reading"]["ok"] is True and recs["reading"]["parent"] == "root"
    assert recs["checking"]["ok"] is False and "bad leaf" in recs["checking"]["error"]
    assert recs["checking"]["leaves"] == 3 and recs["checking"]["depth"] == 1


def test_debug_time_as_decorator():
    from tpu_resiliency.utils.timers import debug_time

    @debug_time("work")
    def f(x):
        return x + 1

    @debug_time
    def g(x):
        return x * 2

    assert f(1) == 2 and g(3) == 6
