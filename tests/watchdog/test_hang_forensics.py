"""Hang-forensics plane units: location beacons, version-skew tolerance,
stack capture, the monitor's dump machinery, and the store barrier census."""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from tpu_resiliency.utils import events, location, stackdump
from tpu_resiliency.utils.metrics import MetricsRegistry, observe_record
from tpu_resiliency.watchdog.config import FaultToleranceConfig
from tpu_resiliency.watchdog.data import (
    DumpStacksMsg,
    HeartbeatMsg,
    InitMsg,
    OkMsg,
    RankInfo,
    SectionAction,
    SectionMsg,
    StatusMsg,
)
from tpu_resiliency.watchdog.monitor_server import RankMonitorServer


@pytest.fixture
def sink_events():
    captured = []
    events.add_sink(captured.append)
    yield captured
    events.remove_sink(captured.append)


# -- location beacon ----------------------------------------------------------


def test_location_beacon_snapshot_and_describe():
    b = location.LocationBeacon()
    assert b.snapshot() == {"v": 1}
    b.note_step(7)
    b.enter_section("step")
    snap = b.snapshot()
    assert snap["step"] == 7 and snap["section"] == "step"
    assert snap["section_age_s"] >= 0 and "entered_at" in snap
    with b.barrier("rdzv/round-3"):
        snap = b.snapshot()
        assert snap["barrier"] == "rdzv/round-3"
        frag = location.describe(snap)
        assert "section=step" in frag and "barrier=rdzv/round-3" in frag
        assert "for " in frag
    assert "barrier" not in b.snapshot()
    # Nesting pops innermost-first; unknown names are no-ops.
    b.enter_section("inner")
    b.exit_section("nope")
    assert b.snapshot()["section"] == "inner"
    b.exit_section(None)
    assert "section" not in b.snapshot()
    # describe() tolerates garbage.
    assert location.describe(None) == ""
    assert location.describe({"v": 1}) == ""


def test_blocking_barrier_join_tags_the_beacon(kv_server, coord_store):
    done = threading.Event()

    def join():
        coord_store.barrier_join("census/b", rank=0, world_size=2, timeout=30.0)
        done.set()

    t = threading.Thread(target=join, daemon=True)
    t.start()
    deadline = time.time() + 5
    while "barrier" not in location.snapshot() and time.time() < deadline:
        time.sleep(0.01)
    assert location.snapshot().get("barrier") == "census/b"
    coord_store.barrier_join("census/b", rank=1, world_size=2, timeout=10.0)
    assert done.wait(10.0)
    t.join(5.0)
    assert "barrier" not in location.snapshot()


# -- monitor server: beacons + skew ------------------------------------------


def _server(**cfg_overrides):
    cfg = FaultToleranceConfig(
        initial_rank_heartbeat_timeout=None, rank_heartbeat_timeout=None,
        **cfg_overrides,
    )
    srv = RankMonitorServer(cfg, socket_path="/tmp/unused-hang-forensics.sock")
    srv._dispatch(InitMsg(
        rank_info=RankInfo(global_rank=3, local_rank=0, host="h", pid=os.getpid()),
        capabilities={"dump_signal": False, "dump_poll": True},
    ))
    return srv


def test_heartbeat_and_section_carry_location():
    srv = _server()
    loc = {"v": 1, "section": "step", "section_age_s": 1.5, "step": 42}
    assert isinstance(srv._dispatch(HeartbeatMsg(rank=3, location=loc)), OkMsg)
    assert srv.session.location == loc
    loc2 = {"v": 1, "section": "checkpointing", "section_age_s": 0.1}
    srv._dispatch(SectionMsg(
        rank=3, action=SectionAction.OPEN, name="checkpointing", location=loc2,
    ))
    assert srv.session.location == loc2
    status = srv._dispatch(StatusMsg()).payload
    assert status["connected"] and status["rank"] == 3
    assert status["location"] == loc2
    assert status["location_age_s"] >= 0.1
    assert status["open_sections"].keys() == {"checkpointing"}


def test_version_skew_location_less_messages_tolerated():
    """A field-stripped (old-build) heartbeat/section must not poison the
    monitor: dispatch succeeds and the last good beacon is kept."""
    srv = _server()
    good = {"v": 1, "section": "step", "section_age_s": 0.5}
    srv._dispatch(HeartbeatMsg(rank=3, location=good))

    old_hb = HeartbeatMsg(rank=3)
    del old_hb.__dict__["location"]  # exactly what unpickling an old msg yields
    assert "location" not in old_hb.__dict__
    assert isinstance(srv._dispatch(old_hb), OkMsg)
    assert srv.session.location == good

    old_sec = SectionMsg(rank=3, action=SectionAction.OPEN, name="step")
    del old_sec.__dict__["location"]
    assert isinstance(srv._dispatch(old_sec), OkMsg)
    assert srv.session.location == good

    # The reverse skew: a NEW message with a malformed payload is no update.
    assert isinstance(
        srv._dispatch(HeartbeatMsg(rank=3, location="not-a-dict")), OkMsg
    )
    assert srv.session.location == good

    # Old-build InitMsg (no capabilities attr) re-inits cleanly too.
    old_init = InitMsg(
        rank_info=RankInfo(global_rank=3, local_rank=0, host="h", pid=os.getpid())
    )
    del old_init.__dict__["capabilities"]
    reply = srv._dispatch(old_init)
    assert reply.__class__.__name__ == "InitReplyMsg"
    assert srv.session.dump_signal_ok is False


def test_terminate_rank_folds_location_into_cause(sink_events):
    srv = _server(rank_termination_signal=signal.SIGTERM)
    victim = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        srv._dispatch(InitMsg(
            rank_info=RankInfo(global_rank=3, local_rank=0, host="h", pid=victim.pid),
        ))
        srv._dispatch(HeartbeatMsg(rank=3, location={
            "v": 1, "section": "step", "barrier": "rdzv/round-3",
            "barrier_age_s": 600.0, "step": 12,
        }))
        srv._terminate_rank("heartbeat gap exceeded 45.0s", "hang", "heartbeat")
        hang = [e for e in sink_events if e.kind == "hang_detected"]
        assert len(hang) == 1
        p = hang[0].payload
        assert "last seen in" in p["reason"]
        assert "barrier=rdzv/round-3" in p["reason"]
        assert "section=step" in p["reason"]
        assert p["location"]["barrier"] == "rdzv/round-3"
        assert p["blocked_s"] >= 0
        assert victim.wait(timeout=10) == -signal.SIGTERM
    finally:
        if victim.poll() is None:
            victim.kill()
        victim.wait()


# -- stack capture ------------------------------------------------------------


def _stuck_in_native_wait(ev):
    ev.wait(30.0)  # lock wait: a GIL-releasing native park


def test_capture_stacks_sees_other_threads():
    ev = threading.Event()
    # Early-sorting name: the capture cap keeps the first MAX_THREADS by
    # (main-first, name) and a full test session leaks scores of pool
    # threads; a real worker never carries that many.
    t = threading.Thread(
        target=_stuck_in_native_wait, args=(ev,), name="00-parked"
    )
    t.start()
    try:
        threads = stackdump.capture_stacks()
        assert threads[0]["main"] is True  # main thread sorts first
        parked = [d for d in threads if d["name"] == "00-parked"]
        assert parked, [d["name"] for d in threads]
        assert any("_stuck_in_native_wait" in f for f in parked[0]["frames"])
    finally:
        ev.set()
        t.join(5.0)


def test_capture_stacks_reads_no_file(monkeypatch):
    """A capture under a GIL-holding hang has one chunk gap to finish in, and
    every system call hands the GIL back to the holder for its next whole
    chunk: with a cold ``linecache`` the capture still names file, line and
    function of every frame, opens nothing, and leaves the cache cold; with
    the file cached it adds the source text."""
    import builtins
    import linecache
    import tokenize

    def refuse(*a, **kw):
        raise AssertionError(f"capture_stacks opened a file: {a}")

    here = __file__
    linecache.clearcache()
    with monkeypatch.context() as m:
        m.setattr(builtins, "open", refuse)
        m.setattr(tokenize, "open", refuse)
        m.setattr(linecache, "updatecache", refuse)
        cold = stackdump.capture_stacks()
    mine = [f for f in cold[0]["frames"] if "test_capture_stacks_reads_no_file" in f]
    assert mine and mine[0].startswith(f"{here}:") and " | " not in mine[0]
    assert here not in linecache.cache

    linecache.getlines(here)  # what rendering any earlier traceback does
    warm = stackdump.capture_stacks()
    mine = [f for f in warm[0]["frames"] if "test_capture_stacks_reads_no_file" in f]
    assert mine[0].endswith("| warm = stackdump.capture_stacks()")


def test_dump_stacks_records_event_and_counts(sink_events, tmp_path):
    from tpu_resiliency.utils import flight_recorder

    flight_recorder.install(str(tmp_path), install_handlers=False)
    try:
        stackdump.dump_stacks("hang: test", detail="rank 3")
        dumps = [e for e in sink_events if e.kind == "stack_dump"]
        assert len(dumps) == 1
        p = dumps[0].payload
        assert p["reason"] == "hang: test"
        assert p["thread_count"] == len(p["threads"]) >= 1
        assert any(
            "test_dump_stacks_records_event" in f
            for f in p["threads"][0]["frames"]
        )
        # The consolidated flight dump carries the capture (SIGKILL-proof:
        # the hot segment got it at record time already).
        dumped = flight_recorder.collect(str(tmp_path))
        assert any(
            r.get("kind") == "stack_dump"
            for recs in dumped.values() for r in recs
        )
        # Bridge: stack_dump -> tpu_stack_dumps_total{reason} (prefix only).
        reg = MetricsRegistry()
        observe_record(
            {"kind": "stack_dump", "reason": "hang: whatever detail"}, reg
        )
        assert reg.counter("tpu_stack_dumps_total", reason="hang").value == 1
    finally:
        flight_recorder.uninstall()


def test_hang_census_metrics_bridge():
    reg = MetricsRegistry()
    observe_record(
        {
            "kind": "hang_census",
            "suspects": [{"rank": 1, "score": 5.0, "reasons": ["missing"]}],
            "blocked": {"1": 12.5, "0": 0.2},
            "barrier_waiters": 3,
        },
        reg,
    )
    assert reg.counter("tpu_hang_suspects_total", rank="1").value == 1
    assert reg.gauge("tpu_rank_blocked_seconds", rank="1").value == 12.5
    assert reg.gauge("tpu_rank_blocked_seconds", rank="0").value == 0.2
    assert reg.gauge("tpu_barrier_waiters").value == 3


# -- dump request plumbing (real monitor subprocess) --------------------------


def test_dump_request_reaches_the_client(tmp_uds_path, sink_events):
    """Operator path end to end: a DumpStacksMsg at the monitor socket makes
    the connected client (this process) record a stack_dump event via its
    long-poll listener."""
    from tpu_resiliency.platform import ipc
    from tpu_resiliency.watchdog.monitor_client import RankMonitorClient

    cfg = FaultToleranceConfig(
        initial_rank_heartbeat_timeout=None, rank_heartbeat_timeout=None,
        workload_check_interval=0.2,
    )
    mon = RankMonitorServer.run_in_subprocess(cfg, tmp_uds_path, start_method="spawn")
    client = RankMonitorClient()
    try:
        client.init_workload_monitoring(
            socket_path=tmp_uds_path,
            rank_info=RankInfo(global_rank=0, local_rank=0, host="h", pid=os.getpid()),
        )
        client.send_heartbeat()
        # Give the listener a beat to complete its generation sync.
        time.sleep(0.3)
        sock = ipc.connect(tmp_uds_path, timeout=5.0)
        try:
            ipc.write_object(sock, DumpStacksMsg(reason="operator-test"))
            reply = ipc.read_object(sock)
            assert isinstance(reply, OkMsg) and reply.payload["gen"] >= 1
        finally:
            sock.close()
        # Two deliveries race: the long-poll listener ("operator-test") and
        # the SIGUSR1 nudge ("signal:SIGUSR1") — the long-poll one must land.
        deadline = time.time() + 10
        while time.time() < deadline:
            if any(
                e.kind == "stack_dump" and e.payload.get("reason") == "operator-test"
                for e in sink_events
            ):
                break
            time.sleep(0.05)
        reasons = [
            e.payload.get("reason") for e in sink_events if e.kind == "stack_dump"
        ]
        assert "operator-test" in reasons, reasons
    finally:
        client.shutdown_workload_monitoring()
        mon.terminate()
        mon.join(5.0)


# -- barrier census (store) ---------------------------------------------------


def test_barrier_census_arrived_missing_and_release(kv_server, coord_store):
    client = coord_store.client
    # Nobody joined yet: census is empty.
    assert client.barrier_census() == {}
    coord_store.barrier_join("iter/0", rank=0, world_size=3, timeout=0.0, wait=False)
    time.sleep(0.05)
    coord_store.barrier_join("iter/0", rank=2, world_size=3, timeout=0.0, wait=False)
    census = client.barrier_census()
    assert set(census) == {"iter/0"}
    b = census["iter/0"]
    assert set(b["arrived"]) == {0, 2}
    assert b["missing"] == [1]
    assert b["absent"] == []
    assert b["world_size"] == 3
    # Rank 0 arrived first: its waiter age is the oldest.
    assert b["arrived"][0] >= b["arrived"][2] >= 0
    assert b["open_age_s"] >= b["arrived"][0]
    # Proxy-absent ranks are reported as absent, not missing.
    coord_store.complete_barrier_for("iter/0", rank=1, world_size=3)
    # Covering rank 1 releases the round; the census clears.
    assert client.barrier_census() == {}
    # StoreView scoping: names come back view-relative.
    coord_store.barrier_join("iter/1", rank=0, world_size=2, timeout=0.0, wait=False)
    scoped = coord_store.barrier_census()
    assert set(scoped) == {"iter/1"}
    assert scoped["iter/1"]["missing"] == [1]
    # Prefix filter on the raw client.
    assert client.barrier_census(prefix="nope/") == {}
