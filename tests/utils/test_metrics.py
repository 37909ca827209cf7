"""Metrics registry: primitives, quantiles, Prometheus exposition, events bridge."""

import json
import math
import os
import re
import threading

import pytest

from tpu_resiliency.utils import events
from tpu_resiliency.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    MetricsSink,
    aggregate,
    observe_record,
)


@pytest.fixture(autouse=True)
def clean_sinks():
    events.clear_sinks()
    old = os.environ.pop(events.EVENTS_FILE_ENV, None)
    yield
    events.clear_sinks()
    if old is not None:
        os.environ[events.EVENTS_FILE_ENV] = old


def test_counter_and_gauge():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(7)
    g.inc(3)
    g.dec()
    assert g.value == 9.0


def test_histogram_quantiles_exact_below_reservoir():
    h = Histogram()
    for v in range(1, 101):  # 0.01 .. 1.00
        h.observe(v / 100)
    assert h.count == 100 and abs(h.sum - 50.5) < 1e-9
    assert abs(h.quantile(0.5) - 0.50) < 1e-9
    assert abs(h.quantile(0.95) - 0.95) < 1e-9
    assert abs(h.quantile(1.0) - 1.00) < 1e-9
    assert abs(h.quantile(0.0) - 0.01) < 1e-9
    assert math.isnan(Histogram().quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_buckets_are_cumulative_in_exposition():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="10"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_count 5" in text
    assert "# TYPE lat_seconds histogram" in text


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    a = reg.counter("x_total", kind="a")
    assert reg.counter("x_total", kind="a") is a  # same series
    assert reg.counter("x_total", kind="b") is not a  # same family, new series
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # one family, one type


def test_prometheus_format_is_parseable():
    """Every sample line must match the exposition grammar (name{labels} value)."""
    reg = MetricsRegistry()
    reg.counter("tpu_restarts_total", "restarts", layer="injob").inc(2)
    reg.gauge("tpu_world_size").set(8)
    reg.histogram("tpu_span_seconds", span="rendezvous.round").observe(0.25)
    line_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+=\"[^\"]*\""
        r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? [0-9eE.+-]+$|^\+Inf$"
    )
    for line in reg.to_prometheus().splitlines():
        if line.startswith("#") or not line:
            continue
        assert line_re.match(line.replace("+Inf", "Inf")), line


def test_metric_name_sanitized():
    reg = MetricsRegistry()
    reg.counter("weird-name.total").inc()
    assert "weird_name_total 1" in reg.to_prometheus()


def test_label_values_escaped_per_exposition_format():
    """Regression: a backslash, double-quote, or newline in a label value
    (peer addresses, file paths) must render as valid 0.0.4 text — escaped,
    never raw."""
    reg = MetricsRegistry()
    reg.counter("x_total", path="C:\\tmp\\f").inc()
    reg.counter("x_total", peer='he said "hi"').inc()
    reg.counter("x_total", detail="line1\nline2").inc()
    text = reg.to_prometheus()
    assert 'path="C:\\\\tmp\\\\f"' in text
    assert 'peer="he said \\"hi\\""' in text
    assert 'detail="line1\\nline2"' in text
    # No sample line may span two physical lines.
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        assert line.count('"') % 2 == 0, line
    # HELP text gets backslash/newline escaping too.
    reg2 = MetricsRegistry()
    reg2.counter("y_total", "multi\nline \\help").inc()
    help_line = next(
        ln for ln in reg2.to_prometheus().splitlines() if ln.startswith("# HELP")
    )
    assert help_line == "# HELP y_total multi\\nline \\\\help"


def test_write_json_is_strict_json(tmp_path):
    """Snapshots are restricted to plain JSON types: NaN quantiles become
    null (not a repr string, not a bare NaN token) and the document parses
    under a strict-JSON reader."""
    reg = MetricsRegistry()
    reg.counter("c_total").inc(2)
    reg.histogram("h_seconds")  # zero observations -> NaN quantiles
    reg.gauge("g").set(1.5)
    path = str(tmp_path / "m.json")
    reg.write_json(path)

    def no_constants(name):
        raise AssertionError(f"non-JSON constant {name} leaked into snapshot")

    doc = json.loads(open(path).read(), parse_constant=no_constants)
    h = doc["metrics"]["h_seconds"][0]
    assert h["p50"] is None and h["count"] == 0
    assert doc["metrics"]["c_total"][0]["value"] == 2
    # Round-trip: the parsed document is byte-equivalent snapshot content.
    assert json.loads(json.dumps(doc)) == doc


def test_snapshot_drops_non_coercible_values():
    from tpu_resiliency.utils.metrics import _plain_json

    class Weird:
        pass

    doc = _plain_json({"ok": 1, "bad": Weird(), "nan": float("nan"),
                       "inf": float("inf"), "np_like": True})
    assert doc == {"ok": 1, "bad": None, "nan": None, "inf": None,
                   "np_like": True}


def test_iteration_start_feeds_step_histogram():
    """The satellite: iteration_start deltas land in tpu_step_seconds — but
    only strictly-consecutive iterations within the gap cap (a repeat after
    an in-process restart or a multi-minute stall is downtime, not a step)."""
    from tpu_resiliency.utils.metrics import STEP_GAP_MAX_S

    reg = MetricsRegistry()
    t0 = 1000.0
    recs = [
        {"kind": "iteration_start", "iteration": 0, "ts": t0, "pid": 7},
        {"kind": "iteration_start", "iteration": 1, "ts": t0 + 0.5, "pid": 7},
        {"kind": "iteration_start", "iteration": 2, "ts": t0 + 1.0, "pid": 7},
        # same iteration again (in-process restart): not a step
        {"kind": "iteration_start", "iteration": 2, "ts": t0 + 9.0, "pid": 7},
        # consecutive but beyond the gap cap: not a step
        {"kind": "iteration_start", "iteration": 3,
         "ts": t0 + 9.0 + STEP_GAP_MAX_S + 1, "pid": 7},
        # a different pid has its own chain
        {"kind": "iteration_start", "iteration": 0, "ts": t0, "pid": 8},
        {"kind": "iteration_start", "iteration": 1, "ts": t0 + 0.25, "pid": 8},
    ]
    aggregate(recs, reg)
    hists = reg.histograms("tpu_step_seconds")
    assert len(hists) == 1
    h = next(iter(hists.values()))
    assert h.count == 3  # 2 steps from pid 7 + 1 from pid 8
    assert abs(h.sum - 1.25) < 1e-9
    # Live sink parity: the same records through MetricsSink agree.
    live = MetricsRegistry()
    for r in recs:
        from tpu_resiliency.utils.metrics import observe_record as orec
        orec(r, live)
    lh = next(iter(live.histograms("tpu_step_seconds").values()))
    assert lh.count == h.count and lh.bucket_counts == h.bucket_counts


def test_snapshot_and_write_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total").inc(3)
    reg.histogram("h_seconds").observe(1.0)
    path = str(tmp_path / "sub" / "m.json")
    reg.write_json(path)
    doc = json.load(open(path))
    m = doc["metrics"]
    assert m["c_total"][0]["value"] == 3
    assert m["h_seconds"][0]["count"] == 1
    assert m["h_seconds"][0]["p95"] == 1.0
    assert not [f for f in os.listdir(tmp_path / "sub") if ".tmp." in f]


def test_counter_thread_safety():
    c = Counter()

    def work():
        for _ in range(10_000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 80_000


def test_observe_record_maps_compile_events():
    """One ``compile`` event per executable (platform/compile_cache.py:watch) becomes
    seconds and programs by cache outcome."""
    reg = MetricsRegistry()
    aggregate([
        {"kind": "compile", "fun_name": "jit(train_step)", "trace_s": 2.0, "lower_s": 1.0,
         "backend_s": 30.0, "cache": "miss"},
        {"kind": "compile", "fun_name": "jit(train_step)", "trace_s": 2.0, "lower_s": 1.0,
         "backend_s": 4.0, "retrieval_s": 3.5, "cache": "hit"},
        {"kind": "compile", "fun_name": "jit(init)", "trace_s": 0.25, "lower_s": 0.25,
         "backend_s": 0.5, "cache": "hit"},
        {"kind": "compile", "fun_name": "jit(add)", "backend_s": 0.125, "cache": "uncached"},
    ], reg)
    snap = reg.snapshot()["metrics"]
    seconds = {e["labels"]["cache"]: e["value"] for e in snap["tpu_compile_seconds_total"]}
    programs = {e["labels"]["cache"]: e["value"] for e in snap["tpu_compiles_total"]}
    assert seconds == {"miss": 33.0, "hit": 8.0, "uncached": 0.125}
    assert programs == {"miss": 1.0, "hit": 2.0, "uncached": 1.0}


def test_nested_timings_are_series_of_their_own_name():
    """Every scope is observed under its name; the recorded roots read what they
    read, whatever nested records a newer program adds beside them."""
    roots = [
        {"kind": "timing", "name": "ckpt.local_load", "duration_s": 2.0, "ok": True},
        {"kind": "timing", "name": "ckpt.local_load", "duration_s": 4.0, "ok": True},
    ]
    nested = [
        {"kind": "timing", "name": "ckpt.load.read", "duration_s": 1.0, "ok": True,
         "depth": 1, "parent": "ckpt.local_load"},
        {"kind": "timing", "name": "ckpt.load.verify", "duration_s": 0.5, "ok": False,
         "depth": 1, "parent": "ckpt.local_load"},
    ]
    before, after = MetricsRegistry(), MetricsRegistry()
    aggregate(roots, before)
    aggregate(roots + nested, after)
    key = (("name", "ckpt.local_load"),)
    for reg in (before, after):
        h = reg.histograms("tpu_timing_seconds")[key]
        assert (h.count, h.sum) == (2, 6.0)
    assert after.histograms("tpu_timing_seconds")[(("name", "ckpt.load.read"),)].count == 1
    failures = after.snapshot()["metrics"]["tpu_timing_failures_total"]
    assert [(e["labels"]["name"], e["value"]) for e in failures] == [("ckpt.load.verify", 1.0)]


def test_observe_record_mapping():
    reg = MetricsRegistry()
    recs = [
        {"kind": "rendezvous_round", "round": 1, "world_size": 4},
        {"kind": "restart_requested"},
        {"kind": "restart_signalled"},
        {"kind": "worker_failed"},
        {"kind": "hang_detected"},
        {"kind": "ckpt_saved", "bytes": 1024},
        {"kind": "timing", "name": "ckpt.save.write", "duration_s": 0.2, "ok": True},
        {"kind": "timing", "name": "ckpt.save.write", "duration_s": 0.4, "ok": False},
        {"kind": "span_end", "span": "rendezvous.round", "duration_s": 1.5, "ok": True},
        {"kind": "unmapped_novelty"},
        {"no_kind": True},
    ]
    aggregate(recs, reg)
    snap = reg.snapshot()["metrics"]
    total = sum(e["value"] for e in snap["tpu_events_total"])
    assert total == 10  # the kindless record is skipped, the novel kind counted
    by_layer = {
        tuple(sorted(e["labels"].items())): e["value"]
        for e in snap["tpu_restarts_total"]
    }
    assert by_layer == {(("layer", "injob"),): 1, (("layer", "inprocess"),): 1}
    assert snap["tpu_worker_failures_total"][0]["value"] == 1
    assert snap["tpu_rank_terminations_total"][0]["labels"] == {"cause": "hang"}
    assert snap["tpu_ckpt_saves_total"][0]["value"] == 1
    h = reg.histograms("tpu_timing_seconds")[(("name", "ckpt.save.write"),)]
    assert h.count == 2
    assert snap["tpu_timing_failures_total"][0]["value"] == 1
    rdzv = reg.histograms("tpu_span_seconds")[(("span", "rendezvous.round"),)]
    assert rdzv.quantile(0.95) == 1.5
    assert reg.gauge("tpu_world_size").value == 4


def test_metrics_sink_bridges_live_records(tmp_path):
    """One record() call feeds the JSONL stream AND the registry."""
    reg = MetricsRegistry()
    jsonl = str(tmp_path / "ev.jsonl")
    events.add_sink(events.JsonlSink(jsonl))
    events.add_sink(MetricsSink(reg, json_path=str(tmp_path / "m.json"),
                                snapshot_interval=0.0))
    events.record("launcher", "restart_requested", reason="test")
    events.record("checkpoint", "timing", name="ckpt.load", duration_s=0.1, ok=True)
    # payload keys colliding with the envelope get the same p_-rename as JSONL
    events.record("x", "y", ts=-1, pid=-1)
    recs = events.read_events(jsonl)
    assert len(recs) == 3
    assert recs[2]["p_ts"] == -1 and recs[2]["ts"] != -1
    snap = reg.snapshot()["metrics"]
    assert snap["tpu_restarts_total"][0]["value"] == 1
    kinds = {e["labels"]["kind"] for e in snap["tpu_events_total"]}
    assert kinds == {"restart_requested", "timing", "y"}
    # The piggybacked snapshot file landed and parses.
    doc = json.load(open(tmp_path / "m.json"))
    assert "tpu_events_total" in doc["metrics"]


def test_aggregate_matches_sink(tmp_path):
    """Live-bridged and post-hoc-aggregated registries agree on the same run."""
    jsonl = str(tmp_path / "ev.jsonl")
    live = MetricsRegistry()
    events.add_sink(events.JsonlSink(jsonl))
    events.add_sink(MetricsSink(live))
    for i in range(5):
        events.record("launcher", "rendezvous_round", round=i, world_size=2)
    events.record("launcher", "worker_failed", global_rank=0, exitcode=3)
    post = aggregate(events.read_events(jsonl))
    for reg in (live, post):
        snap = reg.snapshot()["metrics"]
        assert snap["tpu_rendezvous_rounds_total"][0]["value"] == 5
        assert snap["tpu_worker_failures_total"][0]["value"] == 1


def test_env_var_wires_metrics_bridge(tmp_path, monkeypatch):
    """$TPU_RESILIENCY_METRICS_FILE attaches a MetricsSink lazily, with the
    pid inserted so sibling processes never clobber each other's snapshot."""
    mpath = tmp_path / "m.json"
    monkeypatch.setenv(events.METRICS_FILE_ENV, str(mpath))
    events.record("launcher", "worker_failed", global_rank=0)
    expect = tmp_path / f"m.{os.getpid()}.json"
    assert expect.exists(), os.listdir(tmp_path)
    doc = json.load(open(expect))
    vals = [e["value"] for e in doc["metrics"]["tpu_worker_failures_total"]]
    assert vals and vals[0] >= 1


def test_step_gap_cap_is_env_tunable(monkeypatch):
    """$TPU_RESILIENCY_STEP_GAP_MAX retunes the consecutive-step cap per
    workload; garbage or non-positive values fall back to the 300s default
    rather than taking metrics down."""
    from tpu_resiliency.utils.metrics import (
        STEP_GAP_ENV, STEP_GAP_MAX_S, step_gap_max_s,
    )

    monkeypatch.delenv(STEP_GAP_ENV, raising=False)
    assert step_gap_max_s() == STEP_GAP_MAX_S == 300.0
    monkeypatch.setenv(STEP_GAP_ENV, "5")
    assert step_gap_max_s() == 5.0
    for bad in ("zero-ish", "", "0", "-3"):
        monkeypatch.setenv(STEP_GAP_ENV, bad)
        assert step_gap_max_s() == STEP_GAP_MAX_S
    # The knob reaches the step histogram: a 10s gap is a step under the
    # default cap but downtime under a 5s cap.
    recs = [
        {"kind": "iteration_start", "iteration": 0, "ts": 100.0, "pid": 7},
        {"kind": "iteration_start", "iteration": 1, "ts": 110.0, "pid": 7},
    ]
    monkeypatch.setenv(STEP_GAP_ENV, "5")
    reg = MetricsRegistry()
    aggregate(recs, reg)
    assert not reg.histograms("tpu_step_seconds")
    monkeypatch.delenv(STEP_GAP_ENV)
    reg = MetricsRegistry()
    aggregate(recs, reg)
    assert next(iter(reg.histograms("tpu_step_seconds").values())).count == 1


def test_alert_transitions_feed_alert_metrics():
    """alert_fired/alert_resolved drive the pair the watchtower exports:
    a by-rule/severity fired counter and a net active-alerts gauge."""
    reg = MetricsRegistry()
    aggregate([
        {"kind": "alert_fired", "rule": "goodput_burn", "severity": "page"},
        {"kind": "alert_fired", "rule": "ckpt_staleness", "severity": "warn"},
        {"kind": "alert_resolved", "rule": "goodput_burn", "severity": "page",
         "duration_s": 12.0},
    ], reg)
    snap = reg.snapshot()["metrics"]
    fired = {
        tuple(sorted(e["labels"].items())): e["value"]
        for e in snap["tpu_alerts_total"]
    }
    assert fired == {
        (("rule", "goodput_burn"), ("severity", "page")): 1,
        (("rule", "ckpt_staleness"), ("severity", "warn")): 1,
    }
    assert reg.gauge("tpu_alerts_active").value == 1  # 2 fired - 1 resolved
    prom = reg.to_prometheus()
    assert 'tpu_alerts_total{rule="goodput_burn",severity="page"} 1' in prom
    assert "tpu_alerts_active 1" in prom
