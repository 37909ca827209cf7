"""ACCEPTANCE: hang forensics end to end under the real launcher.

A two-rank launch with FT monitors on. Rank 1 wedges (a GIL-holding sleep; a
compiled-device-hang variant rides the slow marker) while rank 0 blocks in a
store barrier waiting for it. The plane must prove, live and post-hoc:

- ``/hangz`` names the stuck rank, its section, and a stuck-duration while
  the job is still wedged (before the kill ladder completes);
- the watchdog's ``hang_detected`` cause carries the location beacon
  ("last seen in section=step ...");
- the incident artifact embeds (a) the barrier census with the victim listed
  missing and (b) the victim's multi-thread stack dump with the injected
  frame visible;
- ``tpu_rank_blocked_seconds`` and ``tpu_hang_suspects_total`` appear in the
  merged ``/metrics`` view, and ``tpu_stack_dumps_total`` aggregates from the
  events stream.
"""

import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

NPROC = 2

WORKER = textwrap.dedent(
    """
    import importlib, os, sys, threading, time
    from tpu_resiliency.platform.store import CoordStore
    from tpu_resiliency.utils import location
    from tpu_resiliency.utils.events import record
    from tpu_resiliency.watchdog.monitor_client import RankMonitorClient
    # importlib: the tools package re-exports the inject_fault FUNCTION as an
    # attribute, shadowing the module on plain `import ... as inj`.
    inj = importlib.import_module("tpu_resiliency.inprocess.tools.inject_fault")

    stop, fault_name = sys.argv[1], sys.argv[2]
    rank = int(os.environ["RANK"])
    round_no = int(os.environ["TPU_FT_RESTART_COUNT"])
    inj.GIL_SLEEP_CHUNK_S = 3.0  # > hb timeout: no beat can land mid-chunk

    client = RankMonitorClient()
    client.init_workload_monitoring()

    # Background heartbeats: a healthy rank parked in a barrier keeps
    # beating; the GIL_SLEEP victim's beats stop because the chunked hold
    # freezes every thread.
    def beats():
        while True:
            try:
                client.send_heartbeat()
            except Exception:
                return
            time.sleep(0.25)

    threading.Thread(target=beats, daemon=True).start()

    store = CoordStore(
        os.environ["TPU_RESILIENCY_STORE_HOST"],
        int(os.environ["TPU_RESILIENCY_STORE_PORT"]),
        prefix="hangtest/",
    )

    for i in range(3):
        location.note_step(i)
        record("inprocess", "iteration_start", iteration=i)
        client.start_section("step")
        store.barrier(f"step-{round_no}-{i}", rank, 2, timeout=120.0)
        client.end_section("step")
        time.sleep(0.05)

    if round_no == 0:
        location.note_step(3)
        record("inprocess", "iteration_start", iteration=3)
        if rank == 1:
            # The victim: opens its section, then wedges. The monitor must
            # detect, capture stacks, and run the kill ladder.
            client.start_section("step")
            # A starved thread that gets the GIL between two chunks keeps it
            # for one switch interval before the wedged main thread may force
            # it off again: 5 ms by default, which on a loaded machine can pass
            # before the dumper is even scheduled. Widen the gap, not the grace.
            sys.setswitchinterval(0.25)
            inj.inject_fault(getattr(inj.Fault, fault_name), duration=90.0)
            time.sleep(90)
            sys.exit(0)
        # Rank 0 blocks in the barrier the victim never reaches — the
        # census's "who never arrived" evidence. No section here: its own
        # watchdog must keep trusting the background heartbeats.
        try:
            store.barrier(f"step-0-3", rank, 2, timeout=300.0)
        except Exception:
            pass
        time.sleep(300)
        sys.exit(0)

    # Replacement round: hold until the test finishes scraping.
    deadline = time.time() + 120
    while not os.path.exists(stop) and time.time() < deadline:
        time.sleep(0.1)
    """
)


def _tail(tmp_path, n=3000):
    try:
        return (tmp_path / "launcher.out").read_text()[-n:]
    except OSError:
        return "<no launcher.out>"


def _get_json(port, path, timeout=5):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def _get_text(port, path, timeout=5):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return r.read().decode()


def _launch(tmp_path, fault_name):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    stop = tmp_path / "stop"
    events_file = tmp_path / "events.jsonl"
    run_dir = tmp_path / "run"
    incidents = tmp_path / "incidents"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "TPU_RESILIENCY_LOG_LEVEL": "INFO"})
    # File-backed output, NOT pipes: workers/monitors inherit the launcher's
    # stdio fds, so a PIPE would (a) never reach EOF for communicate() while
    # any child lives and (b) deadlock everything once full.
    out = open(tmp_path / "launcher.out", "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tpu_resiliency.launcher.launch",
         "--standalone", "--nproc-per-node", str(NPROC), "--max-restarts", "2",
         "--rdzv-last-call", "0.2", "--monitor-interval", "0.1",
         "--telemetry-port", "0",
         "--ft-param-initial_rank_heartbeat_timeout", "15",
         "--ft-param-rank_heartbeat_timeout", "2.0",
         "--ft-param-workload_check_interval", "0.25",
         "--ft-param-rank_section_timeouts", "{step: 4.0}",
         # Chunks of 3 s put the victim's gaps 3 s apart, and one gap serves
         # one starved thread (a beat, or one of the two dumpers): three gaps,
         # and not a multiple of the chunk, so none races the kill ladder.
         "--ft-param-stack_dump_grace", "10.0",
         "--events-file", str(events_file), "--run-dir", str(run_dir),
         "--incidents-dir", str(incidents),
         str(script), str(stop), fault_name],
        stdout=out, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=str(tmp_path),
    )
    out.close()
    return proc, stop, events_file, run_dir, incidents


def _hang_forensics_flow(tmp_path, fault_name, injected_frame):
    proc, stop, events_file, run_dir, incidents = _launch(tmp_path, fault_name)
    hangz = None
    try:
        # -- port-file handshake ------------------------------------------
        port_file = run_dir / "telemetry.port"
        deadline = time.time() + 60
        while not port_file.exists():
            assert proc.poll() is None, _tail(tmp_path)
            assert time.time() < deadline, "telemetry.port never appeared"
            time.sleep(0.2)
        port = int(port_file.read_text().strip())

        # -- (a) /hangz names the stuck rank while the job is wedged ------
        deadline = time.time() + 120
        while time.time() < deadline:
            assert proc.poll() is None, _tail(tmp_path)
            try:
                doc = _get_json(port, "/hangz")
            except OSError:
                time.sleep(0.2)
                continue
            suspects = {s["rank"]: s for s in doc.get("suspects", [])}
            victim = next(
                (r for r in doc.get("ranks", []) if r.get("rank") == 1), None
            )
            if (
                1 in suspects
                and victim is not None
                and (victim.get("location") or {}).get("section") == "step"
                and isinstance(victim.get("stuck_s"), (int, float))
                and victim["stuck_s"] > 0
                and any("missing" in why for why in suspects[1]["reasons"])
            ):
                hangz = doc
                break
            time.sleep(0.2)
        assert hangz is not None, "/hangz never identified the stuck rank"
        blocked_barriers = [
            b for b in hangz["barriers"] if 1 in b.get("missing", [])
        ]
        assert blocked_barriers, hangz["barriers"]
        assert blocked_barriers[0]["waiters"] >= 1  # rank 0 parked, waiting

        # -- (b) incident artifact: census + the victim's stack dump ------
        deadline = time.time() + 180
        artifact = None
        while time.time() < deadline and artifact is None:
            assert proc.poll() is None, _tail(tmp_path)
            names = sorted(
                n for n in (os.listdir(incidents) if incidents.exists() else [])
                if n.startswith("incident-") and n.endswith(".json")
            )
            for n in names:
                with open(incidents / n) as f:
                    doc = json.load(f)
                if doc.get("census"):
                    artifact = doc
                    break
            time.sleep(0.3)
        assert artifact is not None, "no incident artifact with a census"
        census = artifact["census"]
        assert any(
            1 in b.get("missing", []) for b in census.get("barriers", [])
        ), "census does not list the victim as missing"
        assert any(s["rank"] == 1 for s in census.get("suspects", []))
        # The victim's dump must be IN the artifact: normally in its flight
        # ring (the flight sink runs first, so even a SIGKILL racing the
        # capture persists it), with the shared-stream event window as the
        # belt-and-braces second copy.
        dumps = [
            r for ident, recs in (artifact.get("flight") or {}).items()
            if ident.startswith("1-") for r in recs
            if r.get("kind") == "stack_dump"
        ]
        dumps += [
            r for r in artifact.get("events", [])
            if r.get("kind") == "stack_dump" and r.get("rank") == 1
        ]
        assert dumps, (
            f"victim stack dump missing from the artifact (flight idents "
            f"{list((artifact.get('flight') or {}))})"
        )
        best = max(dumps, key=lambda d: len(d.get("threads") or []))
        assert len(best["threads"]) >= 2, "expected a multi-thread dump"
        all_frames = [
            f for t in best["threads"] for f in t.get("frames", [])
        ]
        assert any(injected_frame in f for f in all_frames), (
            f"injected frame {injected_frame!r} not visible in "
            + "\n".join(all_frames[:80])
        )

        # The hang_detected cause carries the location beacon.
        from tpu_resiliency.utils.events import read_events

        hang_evs = [
            e for e in read_events(str(events_file))
            if e.get("kind") == "hang_detected"
        ]
        assert hang_evs, "no hang_detected event"
        assert any(
            "last seen in" in e.get("reason", "")
            and "section=step" in e.get("reason", "")
            for e in hang_evs
        ), [e.get("reason") for e in hang_evs]

        # -- (c) merged /metrics carries the new families ------------------
        deadline = time.time() + 60
        prom = ""
        while time.time() < deadline:
            prom = _get_text(port, "/metrics")
            if "tpu_hang_suspects_total" in prom:
                break
            time.sleep(0.3)
        assert 'tpu_hang_suspects_total{rank="1"}' in prom, prom[-2000:]
        assert 'tpu_rank_blocked_seconds{rank="1"}' in prom
        assert "tpu_barrier_waiters" in prom

        # -- clean shutdown ------------------------------------------------
        stop.touch()
        rc = proc.wait(timeout=120)
        assert rc == 0, _tail(tmp_path)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    # -- post-hoc parity ---------------------------------------------------
    from tpu_resiliency.utils.events import read_events
    from tpu_resiliency.utils.metrics import aggregate

    reg = aggregate(read_events(str(events_file)))
    assert reg.counter("tpu_hang_suspects_total", rank="1").value >= 1
    assert reg.counter(
        "tpu_rank_terminations_total", cause="hang"
    ).value >= 1
    # At least the victim dumped (reason prefix "hang"); siblings usually too.
    total_dumps = sum(
        e.get("thread_count", 0) >= 1
        for e in read_events(str(events_file)) if e.get("kind") == "stack_dump"
    )
    assert total_dumps >= 1
    # tpu-incident-report renders the census table.
    r = subprocess.run(
        [sys.executable, "-m", "tpu_resiliency.tools.incident_report",
         str(tmp_path / "incidents")],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert "hang census" in r.stdout
    assert "never arrived [1]" in r.stdout
    assert "stack dump" in r.stdout
    return hangz


def test_hang_forensics_gil_sleep(tmp_path):
    """The GIL-holding stall: beats freeze, detection fires mid-chunk, the
    capture lands in a chunk gap before the kill ladder."""
    _hang_forensics_flow(tmp_path, "GIL_SLEEP", "_gil_sleep")


@pytest.mark.slow
def test_hang_forensics_device_hang(tmp_path):
    """The compiled-while-loop device hang: heartbeats keep flowing (the wait
    releases the GIL), so the SECTION timeout is the detector, and the dump
    listener captures immediately."""
    _hang_forensics_flow(tmp_path, "DEVICE_HANG", "_device_hang")
