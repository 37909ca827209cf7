"""Critical-path analyzer: span collection, milestone decomposition,
the dominant chain, self-time, and
the tpu-critpath CLI with highlighted trace export."""

import json

import pytest

from tpu_resiliency.tools import critpath, trace_export

T = 1000.0


def _restart_stream():
    return [
        {"ts": T + 0.000, "kind": "worker_failed", "source": "launcher", "pid": 1},
        {"ts": T + 0.004, "kind": "failure_detected", "source": "launcher", "pid": 1},
        {"ts": T + 0.004, "kind": "span_begin", "span": "launcher.round",
         "source": "launcher", "pid": 1, "span_id": "aaa"},
        {"ts": T + 0.020, "kind": "restart_requested", "source": "launcher", "pid": 1},
        {"ts": T + 0.021, "kind": "span_begin", "span": "rendezvous.round",
         "source": "rendezvous", "pid": 1, "span_id": "bbb", "parent_id": "aaa"},
        {"ts": T + 0.050, "kind": "span_end", "span": "rendezvous.round",
         "source": "rendezvous", "pid": 1, "span_id": "bbb", "duration_s": 0.029},
        {"ts": T + 0.050, "kind": "rendezvous_round", "source": "launcher",
         "pid": 1, "round": 1},
        {"ts": T + 0.060, "kind": "worker_promoted", "source": "launcher",
         "pid": 1, "outcome": "promoted", "round": 1},
        {"ts": T + 0.061, "kind": "rendezvous_fast_path", "outcome": "reused",
         "source": "rendezvous", "pid": 1},
        {"ts": T + 0.090, "kind": "iteration_start", "source": "inprocess",
         "pid": 2, "iteration": 5},
        {"ts": T + 0.100, "kind": "span_end", "span": "launcher.round",
         "source": "launcher", "pid": 1, "span_id": "aaa", "duration_s": 0.096},
    ]


def test_collect_spans_pairs_and_flags_unfinished():
    recs = _restart_stream() + [
        {"ts": T + 0.05, "kind": "span_begin", "span": "worker.spawn",
         "source": "launcher", "pid": 3, "span_id": "ccc"},
    ]
    spans = critpath.collect_spans(recs)
    by_name = {s.name: s for s in spans}
    assert by_name["rendezvous.round"].finished
    assert by_name["rendezvous.round"].parent_id == "aaa"
    assert not by_name["worker.spawn"].finished
    assert by_name["worker.spawn"].t1 == pytest.approx(T + 0.100)


def test_restart_decomposition_matches_published_arithmetic():
    dec = critpath.restart_decomposition(_restart_stream())
    segs = {s["name"]: s["duration_ms"] for s in dec["segments"]}
    assert segs["detect"] == pytest.approx(4.0, abs=0.01)
    assert segs["teardown"] == pytest.approx(16.0, abs=0.01)
    assert segs["rendezvous"] == pytest.approx(30.0, abs=0.01)
    assert segs["promote"] == pytest.approx(10.0, abs=0.01)
    assert segs["first_step_ready"] == pytest.approx(30.0, abs=0.01)
    assert dec["fast_path"] and dec["promoted"]
    assert dec["total_ms"] == pytest.approx(90.0, abs=0.01)


def test_restart_decomposition_external_anchors():
    """The benchmark's stamp-file anchors override the stream's own fault/
    resume evidence — the published numbers and the pure-events view share
    one arithmetic with different endpoints."""
    dec = critpath.restart_decomposition(
        _restart_stream(), fault_ts=T - 0.002, resume_ts=T + 0.080
    )
    segs = {s["name"]: s["duration_ms"] for s in dec["segments"]}
    assert segs["detect"] == pytest.approx(6.0, abs=0.01)
    assert segs["first_step_ready"] == pytest.approx(20.0, abs=0.01)


def test_inverted_milestones_clamp_to_zero():
    dec = critpath.restart_decomposition(
        _restart_stream(), resume_ts=T + 0.059  # beats the promote stamp
    )
    segs = {s["name"]: s["duration_ms"] for s in dec["segments"]}
    assert segs["first_step_ready"] == 0.0


def test_cold_restart_reports_spawn_segment():
    recs = [r for r in _restart_stream() if r["kind"] != "worker_promoted"]
    dec = critpath.restart_decomposition(recs)
    segs = {s["name"] for s in dec["segments"]}
    assert "spawn_and_startup" in segs and "promote" not in segs
    assert not dec["promoted"]


def test_dominant_chain_descends_into_children_and_covers_window():
    doc = critpath.analyze(_restart_stream())
    ep = doc["episodes"][0]
    chain = ep["chain"]
    assert any(seg["span"] == "rendezvous.round" for seg in chain)
    # Contiguous cover of [t_fault, t_end], gaps explicit.
    assert chain[0]["start"] == pytest.approx(ep["t_fault"])
    for a, b in zip(chain, chain[1:]):
        assert a["end"] == pytest.approx(b["start"])
    assert chain[-1]["end"] == pytest.approx(ep["t_end"])
    assert chain[0]["span"] == "(gap)"  # nothing instrumented covers detect


def test_self_time_subtracts_children():
    spans = critpath.collect_spans(_restart_stream())
    parent = next(s for s in spans if s.name == "launcher.round")
    # 96 ms span minus the 29 ms rendezvous child.
    assert critpath.self_time(parent, spans) == pytest.approx(0.067, abs=1e-6)


def test_multiple_episodes_found():
    second = []
    for r in _restart_stream():
        r2 = dict(r)
        r2["ts"] = r["ts"] + 10.0
        for k in ("span_id", "parent_id"):
            if k in r2:
                r2[k] = r2[k] + "2"
        second.append(r2)
    eps = critpath.find_restart_episodes(_restart_stream() + second)
    assert len(eps) == 2
    assert eps[1]["t_fault"] == pytest.approx(T + 10.0)


def test_window_fallback_without_restart():
    recs = [
        {"ts": T, "kind": "span_begin", "span": "ckpt.save.enqueue",
         "source": "checkpoint", "pid": 1, "span_id": "s1"},
        {"ts": T + 0.5, "kind": "span_end", "span": "ckpt.save.enqueue",
         "source": "checkpoint", "pid": 1, "span_id": "s1", "duration_s": 0.5},
    ]
    doc = critpath.analyze(recs)
    assert doc["episodes"][0]["kind"] == "window"
    assert any(s["span"] == "ckpt.save.enqueue"
               for s in doc["episodes"][0]["chain"])


def test_reshard_decomposition():
    recs = [
        {"ts": T, "kind": "span_begin", "span": "reshard.plan",
         "source": "checkpoint", "pid": 1, "span_id": "p1"},
        {"ts": T + 0.01, "kind": "span_end", "span": "reshard.plan",
         "source": "checkpoint", "pid": 1, "span_id": "p1", "duration_s": 0.01},
        {"ts": T + 0.02, "kind": "span_begin", "span": "reshard.fetch",
         "source": "checkpoint", "pid": 1, "span_id": "f1"},
        {"ts": T + 0.10, "kind": "span_end", "span": "reshard.fetch",
         "source": "checkpoint", "pid": 1, "span_id": "f1", "duration_s": 0.08},
        {"ts": T + 0.10, "kind": "reshard_fetch", "via": "peer", "holder": 2,
         "bytes": 1024, "pid": 1},
        {"ts": T + 0.11, "kind": "reshard_fetch", "via": "local",
         "bytes": 2048, "pid": 1},
    ]
    d = critpath.reshard_decomposition(recs)
    assert d["plan_s"] == pytest.approx(0.01)
    assert d["fetch_s"] == pytest.approx(0.08)
    assert d["peer_bytes"] == 1024 and d["local_bytes"] == 2048
    assert d["peer_fetches"] == 1


def test_critical_span_ids_feed_trace_highlight():
    doc = critpath.analyze(_restart_stream())
    ids = critpath.critical_span_ids(doc)
    assert "bbb" in ids
    trace = trace_export.to_chrome_trace(_restart_stream(), critical_ids=ids)
    crit = [e for e in trace["traceEvents"]
            if e.get("args", {}).get("critical_path")]
    assert any(e["name"] == "rendezvous.round" for e in crit)
    assert all(e.get("cname") for e in crit)


# -- CLI ----------------------------------------------------------------------


def _write(tmp_path, recs):
    path = tmp_path / "ev.jsonl"
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_cli_table_names_segments_and_chain(tmp_path, capsys):
    path = _write(tmp_path, _restart_stream())
    assert critpath.main([path]) == 0
    out = capsys.readouterr().out
    for want in ("restart episode", "detect", "rendezvous", "promote",
                 "rendezvous.round", "fast-path rendezvous"):
        assert want in out, out


def test_cli_json_document(tmp_path, capsys):
    path = _write(tmp_path, _restart_stream())
    assert critpath.main([path, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "tpu-critpath-1"
    assert doc["episodes"][0]["kind"] == "restart"


def test_cli_trace_export_highlights(tmp_path, capsys):
    path = _write(tmp_path, _restart_stream())
    trace_path = tmp_path / "crit.trace.json"
    assert critpath.main([path, "--trace", str(trace_path)]) == 0
    doc = json.loads(trace_path.read_text())
    assert any(e.get("args", {}).get("critical_path")
               for e in doc["traceEvents"])


def test_cli_restart_mode_exits_1_without_episode(tmp_path, capsys):
    path = _write(tmp_path, [
        {"ts": T, "kind": "iteration_start", "pid": 1, "iteration": 0,
         "source": "inprocess"},
    ])
    assert critpath.main([path, "--episode", "restart"]) == 1


def test_cli_missing_file():
    assert critpath.main(["/nonexistent/ev.jsonl"]) == 1
