"""Goodput ledger: interval algebra, phase attribution, publish parity, CLI."""

import json

import pytest

from tpu_resiliency.utils import events
from tpu_resiliency.utils.goodput import (
    GoodputLedger,
    merge_intervals,
    render_table,
    subtract_intervals,
    total_seconds,
)
from tpu_resiliency.utils.metrics import MetricsRegistry, aggregate


@pytest.fixture(autouse=True)
def clean_sinks():
    events.clear_sinks()
    yield
    events.clear_sinks()


# -- interval algebra ---------------------------------------------------------


def test_interval_algebra():
    assert merge_intervals([]) == []
    assert merge_intervals([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert merge_intervals([(1, 1), (2, 1)]) == []  # empty/backward dropped
    assert subtract_intervals([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)
    ]
    assert subtract_intervals([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert subtract_intervals([(0, 5)], [(0, 10)]) == []
    assert subtract_intervals([(0, 5)], []) == [(0, 5)]
    assert total_seconds([(0, 2), (3, 4.5)]) == 3.5


# -- attribution --------------------------------------------------------------


T0 = 10_000.0


def _step(i, ts, pid=10, rank=0):
    return {"kind": "iteration_start", "iteration": i, "ts": ts,
            "pid": pid, "rank": rank}


def test_phases_partition_wall_clock_exactly():
    led = GoodputLedger()
    led.observe_many([
        {"kind": "span_end", "span": "rendezvous.round", "ts": T0 + 2,
         "duration_s": 2.0, "pid": 1},
        *[_step(i, T0 + 2 + i) for i in range(4)],       # train 2..5
        {"kind": "ckpt_foreground_blocked", "ts": T0 + 5.5,
         "duration_s": 1.0, "pid": 10, "rank": 0},       # stall 4.5..5.5
        {"kind": "incident_opened", "incident_id": "i1", "ts": T0 + 6, "pid": 1},
        {"kind": "incident_closed", "incident_id": "i1", "ts": T0 + 8, "pid": 1},
    ])
    s = led.summary()
    assert s["wall_clock_s"] == pytest.approx(8.0)
    assert sum(s["phases"].values()) == pytest.approx(s["wall_clock_s"])
    # The stall window [4.5, 5.5] outranks the train interval it overlaps.
    assert s["phases"]["train"] == pytest.approx(2.5)
    assert s["phases"]["ckpt_stall"] == pytest.approx(1.0)
    assert s["phases"]["restart"] == pytest.approx(2.0)
    assert s["phases"]["incident"] == pytest.approx(2.0)
    assert s["phases"]["unattributed"] == pytest.approx(0.5)
    assert s["goodput_ratio"] == pytest.approx(2.5 / 8.0)
    assert s["steps"] == 3
    assert s["ranks"]["0"]["steps"] == 3
    assert s["ranks"]["0"]["train_s"] == pytest.approx(3.0)  # raw, pre-overlap


def test_overlapping_evidence_never_double_counts():
    """A sync save emits BOTH ckpt_foreground_blocked and its per-phase
    timings over the same window: interval union must charge the window
    once."""
    led = GoodputLedger()
    led.observe_many([
        _step(0, T0),
        {"kind": "ckpt_foreground_blocked", "ts": T0 + 2.0, "duration_s": 2.0,
         "pid": 10, "rank": 0},
        {"kind": "timing", "name": "ckpt.save.serialize", "ts": T0 + 1.0,
         "duration_s": 1.0, "pid": 10, "rank": 0},
        {"kind": "timing", "name": "ckpt.save.write", "ts": T0 + 2.0,
         "duration_s": 1.0, "pid": 10, "rank": 0},
        {"kind": "span_end", "span": "ckpt.save.enqueue", "ts": T0 + 2.0,
         "duration_s": 2.0, "pid": 10, "rank": 0},
        _step(1, T0 + 3.0),
    ])
    s = led.summary()
    assert s["phases"]["ckpt_stall"] == pytest.approx(2.0)  # once, not 6s
    assert s["phases"]["train"] == pytest.approx(1.0)  # 0..3 minus the stall
    assert sum(s["phases"].values()) == pytest.approx(s["wall_clock_s"])


def test_step_gating_matches_metrics_bridge():
    """Repeated iterations (in-process restart) and over-cap gaps are not
    steps — the same rule observe_record applies to tpu_step_seconds."""
    led = GoodputLedger(max_step_s=10.0)
    led.observe_many([
        _step(0, T0), _step(1, T0 + 1),          # one step
        _step(1, T0 + 5),                        # repeat: not a step
        _step(2, T0 + 30),                       # 25s > cap: not a step
        _step(3, T0 + 31),                       # one step
    ])
    s = led.summary()
    assert s["steps"] == 2
    assert s["phases"]["train"] == pytest.approx(2.0)


def test_fault_to_resume_window_is_restart():
    """The operator-visible restart cost — failure detection, teardown,
    respawn, the new interpreter's imports — is the fault-evidence →
    training-resumed window, not just the instrumented spans."""
    led = GoodputLedger()
    led.observe_many([
        _step(0, T0), _step(1, T0 + 1),
        {"kind": "worker_failed", "ts": T0 + 1.5, "pid": 1},
        {"kind": "restart_requested", "ts": T0 + 1.6, "pid": 1},  # same window
        {"kind": "span_end", "span": "worker.spawn", "ts": T0 + 2.5,
         "duration_s": 0.1, "pid": 1},
        _step(0, T0 + 4.0, pid=11),  # respawned rank resumes: window closes
        _step(1, T0 + 5.0, pid=11),
    ])
    s = led.summary()
    assert s["phases"]["restart"] == pytest.approx(2.5)  # 1.5 -> 4.0
    assert s["phases"]["train"] == pytest.approx(1.0 + 1.0 - 0.0)
    assert sum(s["phases"].values()) == pytest.approx(s["wall_clock_s"])


def test_unresolved_restart_charged_to_end_of_stream():
    led = GoodputLedger()
    led.observe_many([
        _step(0, T0), _step(1, T0 + 1),
        {"kind": "worker_failed", "ts": T0 + 2, "pid": 1},
        {"kind": "budget_exhausted", "ts": T0 + 3, "pid": 1},
    ])
    s = led.summary()
    assert s["phases"]["restart"] == pytest.approx(1.0)  # 2 -> end (3)
    assert s["phases"]["train"] == pytest.approx(1.0)


def test_open_incident_charged_to_end_of_stream():
    led = GoodputLedger()
    led.observe_many([
        _step(0, T0),
        {"kind": "incident_opened", "incident_id": "i1", "ts": T0 + 1, "pid": 1},
        _step(1, T0 + 4),
    ])
    s = led.summary()
    assert s["phases"]["incident"] == pytest.approx(3.0)
    # train 0..4 loses the incident window 1..4
    assert s["phases"]["train"] == pytest.approx(1.0)


def test_incident_close_without_open_uses_time_to_recover():
    led = GoodputLedger()
    led.observe_many([
        _step(0, T0), _step(1, T0 + 10),
        {"kind": "incident_closed", "incident_id": "ix", "ts": T0 + 8,
         "time_to_recover_s": 3.0, "pid": 1},
    ])
    assert led.summary()["phases"]["incident"] == pytest.approx(3.0)


def test_empty_ledger_summary():
    s = GoodputLedger().summary()
    assert s["wall_clock_s"] == 0.0 and s["goodput_ratio"] == 0.0
    assert s["window"] is None and s["steps"] == 0


def test_publish_deltas_replay_to_identical_totals():
    """Live/post-hoc parity: aggregating the goodput_update records the
    ledger published reconstructs the same monotonic totals the final
    summary reports."""
    led = GoodputLedger()
    published = []
    rec = lambda src, kind, **p: published.append({"kind": kind, **p})

    led.observe_many([_step(i, T0 + i) for i in range(3)])
    led.publish(record=rec)
    led.observe_many([
        {"kind": "ckpt_foreground_blocked", "ts": T0 + 4, "duration_s": 1.0,
         "pid": 10, "rank": 0},
        _step(3, T0 + 5),
    ])
    led.publish(record=rec)
    led.publish(record=rec)  # no new evidence -> no new record
    assert len(published) == 2
    final = led.summary()
    reg = aggregate(published)
    totals = {
        e["labels"]["phase"]: e["value"]
        for e in reg.snapshot()["metrics"]["tpu_time_attributed_seconds_total"]
    }
    for phase, seconds in final["phases"].items():
        assert totals.get(phase, 0.0) == pytest.approx(seconds, abs=1e-5), phase
    assert reg.gauge("tpu_goodput_ratio").value == pytest.approx(
        final["goodput_ratio"]
    )


def test_publish_routes_through_events_by_default():
    led = GoodputLedger()
    led.observe_many([_step(0, T0), _step(1, T0 + 1)])
    seen = []
    events.add_sink(seen.append)
    led.publish()
    kinds = [e.kind for e in seen]
    assert kinds == ["goodput_update"]
    # And the ledger ignores its own narration when it comes back around.
    led.observe({"kind": "goodput_update", "ts": T0 + 999,
                 "phases": {"train": 1.0}})
    assert led.summary()["wall_clock_s"] == pytest.approx(1.0)


def test_render_table(capsys):
    led = GoodputLedger()
    led.observe_many([_step(i, T0 + i) for i in range(3)])
    render_table(led.summary())
    out = capsys.readouterr().out
    assert "goodput:" in out and "phase attribution" in out
    for phase in ("train", "ckpt_stall", "restart", "incident", "unattributed"):
        assert phase in out
    assert "per-rank:" in out and "rank 0:" in out


# -- CLI ----------------------------------------------------------------------


def test_metrics_dump_goodput_flag(tmp_path, capsys):
    from tpu_resiliency.tools import metrics_dump

    path = tmp_path / "ev.jsonl"
    with open(path, "w") as f:
        for rec in [
            _step(0, T0), _step(1, T0 + 1),
            {"kind": "span_end", "span": "worker.spawn", "ts": T0 + 0.2,
             "duration_s": 0.2, "pid": 1},
        ]:
            f.write(json.dumps(rec) + "\n")
    assert metrics_dump.main([str(path), "--goodput"]) == 0
    out = capsys.readouterr().out
    assert "goodput:" in out and "restart" in out
    assert metrics_dump.main([str(path), "--goodput", "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "tpu-goodput-1"
    # spawn span [T0, T0+0.2] outranks the train interval [T0, T0+1]
    assert doc["phases"]["restart"] == pytest.approx(0.2)
    assert doc["phases"]["train"] == pytest.approx(0.8)
    assert sum(doc["phases"].values()) == pytest.approx(doc["wall_clock_s"])


# -- compare (autoscale PR) ---------------------------------------------------


def _summary_for(records):
    led = GoodputLedger()
    led.observe_many(records)
    return led.summary()


def test_compare_summaries_and_ledgers():
    from tpu_resiliency.utils.goodput import compare

    # Run A: 4 clean steps. Run B: same steps plus a 2 s restart window.
    a_recs = [_step(i, T0 + i) for i in range(5)]
    b_recs = [_step(0, T0), _step(1, T0 + 1),
              {"kind": "worker_failed", "ts": T0 + 1.5, "pid": 10},
              _step(2, T0 + 3.5), _step(3, T0 + 4.5)]
    led_a, led_b = GoodputLedger(), GoodputLedger()
    led_a.observe_many(a_recs)
    led_b.observe_many(b_recs)
    cmp_doc = compare(led_a, led_b)  # ledger inputs
    assert cmp_doc["schema"] == "tpu-goodput-compare-1"
    assert cmp_doc["ratio_delta"] > 0  # A trained a larger share of its wall
    assert cmp_doc["phases"]["restart"] == pytest.approx(-2.0)
    # Summary-document inputs answer identically.
    assert compare(led_a.summary(), led_b.summary()) == cmp_doc
    assert cmp_doc["steps_delta"] == 1


def test_compare_normalizes_wall_clock():
    """A controlled run that finishes sooner must not look worse for being
    shorter: the fractional deltas are per-wall-clock shares."""
    from tpu_resiliency.utils.goodput import compare

    short = _summary_for([_step(i, T0 + i * 0.5) for i in range(5)])  # 2 s
    long = _summary_for([_step(i, T0 + i) for i in range(5)])         # 4 s
    cmp_doc = compare(short, long)
    assert cmp_doc["phases"]["train"] == pytest.approx(-2.0)  # absolute
    assert cmp_doc["phase_frac"]["train"] == pytest.approx(0.0)  # share
    assert cmp_doc["ratio_delta"] == pytest.approx(0.0)


def test_render_compare(capsys):
    from tpu_resiliency.utils.goodput import compare, render_compare

    a = _summary_for([_step(i, T0 + i) for i in range(4)])
    b = _summary_for([_step(0, T0),
                      {"kind": "worker_failed", "ts": T0 + 1.2, "pid": 10},
                      _step(1, T0 + 3)])
    render_compare(compare(a, b), labels=("controlled", "baseline"))
    out = capsys.readouterr().out
    assert "controlled" in out and "baseline" in out
    assert "per-phase delta" in out and "train" in out and "restart" in out


def test_metrics_dump_goodput_baseline_flag(tmp_path, capsys):
    from tpu_resiliency.tools import metrics_dump

    run = tmp_path / "run.jsonl"
    base = tmp_path / "base.jsonl"
    with open(run, "w") as f:
        for rec in [_step(i, T0 + i) for i in range(4)]:
            f.write(json.dumps(rec) + "\n")
    with open(base, "w") as f:
        for rec in [_step(0, T0),
                    {"kind": "worker_failed", "ts": T0 + 1.0, "pid": 10},
                    _step(1, T0 + 3)]:
            f.write(json.dumps(rec) + "\n")
    assert metrics_dump.main(
        [str(run), "--goodput", "--baseline", str(base)]
    ) == 0
    out = capsys.readouterr().out
    assert "vs" in out and "delta" in out
    assert metrics_dump.main(
        [str(run), "--goodput", "--baseline", str(base), "--format", "json"]
    ) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "tpu-goodput-compare-1"
    assert doc["ratio_delta"] > 0
    # --baseline without --goodput is a usage error.
    assert metrics_dump.main([str(run), "--baseline", str(base)]) == 2


# -- nested ``timing`` records (debug_time records every scope since PR 37) ----


def _recorded_stream():
    """A stream as the program wrote it before nested scopes were recorded: roots
    only, no ``depth``."""
    return [
        _step(0, T0), _step(1, T0 + 1.0),
        {"kind": "timing", "name": "ckpt.load", "ts": T0 + 4.0, "duration_s": 2.0,
         "ok": True, "pid": 10, "rank": 0},
        {"kind": "timing", "name": "ckpt.save.d2h", "ts": T0 + 5.0, "duration_s": 0.5,
         "ok": True, "pid": 10, "rank": 0},
        _step(2, T0 + 6.0), _step(3, T0 + 7.0),
    ]


def _summary(records):
    led = GoodputLedger()
    led.observe_many(records)
    return led.summary()


@pytest.mark.parametrize("nested", [
    # a listed name that ran inside a listed root, and the restore's new phases
    [{"kind": "timing", "name": "ckpt.local_load", "ts": T0 + 3.9, "duration_s": 1.8,
      "ok": True, "pid": 10, "rank": 0, "depth": 1, "parent": "ckpt.load"}],
    [{"kind": "timing", "name": "ckpt.load.read", "ts": T0 + 3.0, "duration_s": 0.7,
      "ok": True, "pid": 10, "rank": 0, "depth": 2, "parent": "ckpt.local_load"},
     {"kind": "timing", "name": "ckpt.local_load", "ts": T0 + 3.9, "duration_s": 1.8,
      "ok": True, "pid": 10, "rank": 0, "depth": 1, "parent": "ckpt.load"},
     {"kind": "timing", "name": "ckpt.load.place", "ts": T0 + 4.4, "duration_s": 0.2,
      "ok": True, "pid": 10, "rank": 0, "depth": 0, "parent": None}],
], ids=["listed-name-nested", "restore-phases"])
def test_nested_timings_leave_the_recorded_totals_unchanged(nested):
    """The ledger charges root scopes: a recorded stream gives what it gave, and the
    same stream with the nested records a newer program adds gives the same, per
    phase and per rank (a rank's ``ckpt_stall_s`` is a plain sum: a nested listed
    name counted beside its root would double it)."""
    before = _summary(_recorded_stream())
    assert before["phases"]["ckpt_stall"] == pytest.approx(2.5)
    assert before["ranks"]["0"]["ckpt_stall_s"] == pytest.approx(2.5)
    stream = sorted(_recorded_stream() + nested, key=lambda r: r["ts"])
    after = _summary(stream)
    assert after["phases"] == before["phases"]
    assert after["ranks"] == before["ranks"]
    assert after["goodput_ratio"] == before["goodput_ratio"]


def test_an_explicit_root_depth_counts_as_the_recorded_root_did():
    with_depth = [dict(r, depth=0, parent=None) if r["kind"] == "timing" else r
                  for r in _recorded_stream()]
    assert _summary(with_depth) == _summary(_recorded_stream())
