"""End-to-end restart-loop tests: forked rank processes + injected faults.

Models the reference's ``tests/inprocess/test_wrap.py`` enumeration (fault in fn,
process death, restart to success) using the fork-N-subprocess harness of SURVEY §4.
Each child runs the real Wrapper against the shared KV store; the parent asserts on
results sent back over a queue.
"""

import multiprocessing as mp
import os
import signal
import socket
import time

import pytest

from tpu_resiliency.exceptions import RestartAbort


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def fast_wrapper(**kw):
    from tpu_resiliency.inprocess.wrap import Wrapper

    # Generous timeouts: fault detection in these tests rides socket EOF (instant),
    # and tight heartbeat windows false-positive under parallel-suite CPU contention.
    defaults = dict(
        monitor_interval=0.05,
        last_call_wait=0.1,
        soft_timeout=10.0,
        hard_timeout=20.0,
        heartbeat_interval=0.2,
        heartbeat_timeout=10.0,
        barrier_timeout=45.0,
        completion_timeout=45.0,
    )
    defaults.update(kw)
    return Wrapper(**defaults)


def run_world(world, body, timeout=90.0, expect_exit=None, after_start=None):
    """Fork `world` children; each runs body(rank, result_q). Returns rank→result.

    ``after_start(port)`` runs in the parent once all children are forked — for
    tests that inject store state mid-run (e.g. simulating a monitor's proxy
    joins)."""
    port = free_port()
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    procs = []
    for rank in range(world):
        def child(rank=rank):
            os.environ["RANK"] = str(rank)
            os.environ["WORLD_SIZE"] = str(world)
            os.environ["TPU_RESILIENCY_STORE_PORT"] = str(port)
            os.environ["TPU_RESILIENCY_STORE_HOST"] = "127.0.0.1"
            body(rank, q)

        p = ctx.Process(target=child, daemon=False)
        p.start()
        procs.append(p)
    if after_start is not None:
        after_start(port)
    results = {}
    deadline = time.monotonic() + timeout
    try:
        while len(results) < world and time.monotonic() < deadline:
            try:
                rank, payload = q.get(timeout=1.0)
                results[rank] = payload
            except Exception:
                if all(not p.is_alive() for p in procs) and q.empty():
                    break
    finally:
        for p in procs:
            p.join(timeout=15.0)
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
    return results, [p.exitcode for p in procs]


class TestSingleRank:
    def test_success_passthrough(self):
        def body(rank, q):
            @fast_wrapper()
            def train():
                return "done"

            q.put((rank, train()))

        results, codes = run_world(1, body)
        assert results == {0: "done"} and codes == [0]

    def test_restart_on_exception(self):
        def body(rank, q):
            from tpu_resiliency.inprocess.wrap import CallWrapper

            attempts = []

            @fast_wrapper()
            def train(call: CallWrapper):
                attempts.append(call.iteration)
                if len(attempts) < 3:
                    raise RuntimeError(f"boom {len(attempts)}")
                return ("ok", attempts)

            q.put((rank, train()))

        results, codes = run_world(1, body)
        assert results[0] == ("ok", [0, 1, 2])
        assert codes == [0]

    def test_retry_controller_aborts(self):
        def body(rank, q):
            from tpu_resiliency.inprocess.initialize import RetryController

            @fast_wrapper(initialize=RetryController(max_iterations=2))
            def train():
                raise RuntimeError("always fails")

            try:
                train()
                q.put((rank, "no-abort"))
            except RestartAbort:
                q.put((rank, "aborted"))

        results, codes = run_world(1, body)
        assert results == {0: "aborted"} and codes == [0]


class TestMultiRank:
    def test_peer_exception_restarts_everyone(self):
        def body(rank, q):
            from tpu_resiliency.inprocess.wrap import CallWrapper

            state = {"n": 0}

            @fast_wrapper()
            def train(call: CallWrapper):
                state["n"] += 1
                if call.iteration == 0 and rank == 1:
                    raise RuntimeError("rank1 fails round 0")
                # Survivors park until the restart signal arrives.
                deadline = time.monotonic() + 30.0
                while call.iteration == 0 and time.monotonic() < deadline:
                    time.sleep(0.05)
                return ("ok", call.iteration, call.frozen_state.active_world_size)

            q.put((rank, train()))

        results, codes = run_world(2, body)
        assert codes == [0, 0]
        # Both ranks completed on iteration 1 with the full world intact.
        assert results[0] == ("ok", 1, 2)
        assert results[1] == ("ok", 1, 2)

    def test_rank_death_shrinks_world(self):
        def body(rank, q):
            from tpu_resiliency.inprocess.wrap import CallWrapper

            @fast_wrapper()
            def train(call: CallWrapper):
                if call.iteration == 0 and rank == 1:
                    os._exit(7)  # hard death: monitor must report + proxy barriers
                deadline = time.monotonic() + 60.0
                while call.iteration == 0 and time.monotonic() < deadline:
                    time.sleep(0.05)
                return ("ok", call.iteration, call.frozen_state.active_world_size)

            q.put((rank, train()))

        results, codes = run_world(2, body, timeout=120.0)
        assert codes[1] == 7
        assert results[0] == ("ok", 1, 1)  # survivor re-entered with world 1

    def test_degraded_rank_demoted_without_dying(self):
        """The health-vector decisions loop (review round 1 item 2): a slow-but-alive
        rank recorded degraded is excluded from the active world on the next
        restart round — a healthy spare takes its slot — without the slow rank
        ever dying."""

        def body(rank, q):
            from tpu_resiliency.inprocess.rank_assignment import DemoteDegraded
            from tpu_resiliency.inprocess.wrap import CallWrapper

            @fast_wrapper(rank_assignment=DemoteDegraded(max_active_world_size=2))
            def train(call: CallWrapper):
                fs = call.frozen_state
                if call.iteration == 0:
                    if rank == 0:
                        # Telemetry policy publishes: rank 1 is degraded.
                        call.coord.set_degraded({1})
                        time.sleep(0.3)
                        raise RuntimeError("force a restart round")
                    deadline = time.monotonic() + 30.0
                    while time.monotonic() < deadline:
                        time.sleep(0.05)
                return ("ok", call.iteration, fs.mode.name, fs.active_rank,
                        fs.active_world_size)

            q.put((rank, train()))

        results, codes = run_world(3, body, timeout=120.0)
        assert codes == [0, 0, 0]
        # Iteration 1: ranks 0 and 2 active; degraded rank 1 is alive but spent the
        # round in reserve (a reserve rank's wrapper returns None on completion).
        assert results[1] is None
        assert results[0] == ("ok", 1, "ACTIVE", 0, 2)
        assert results[2] == ("ok", 1, "ACTIVE", 1, 2)

    def test_system_exit_terminates_rank_not_restart(self):
        """SystemExit must terminate the raising rank (re-raised, rank recorded
        terminated) while peers restart without it — not spin the raiser through
        restart rounds (ADVICE r1: reference restarts only on Exception)."""

        def body(rank, q):
            from tpu_resiliency.inprocess.wrap import CallWrapper

            attempts = []

            @fast_wrapper()
            def train(call: CallWrapper):
                attempts.append(call.iteration)
                if rank == 1:
                    raise SystemExit(5)
                deadline = time.monotonic() + 60.0
                while call.iteration == 0 and time.monotonic() < deadline:
                    time.sleep(0.05)
                return ("ok", call.iteration, call.frozen_state.active_world_size)

            try:
                q.put((rank, train()))
            except SystemExit as e:
                q.put((rank, ("exit", e.code, len(attempts))))

        results, codes = run_world(2, body, timeout=120.0)
        # Rank 1 left exactly once — no restart loop for BaseException.
        assert results[1] == ("exit", 5, 1)
        # Rank 0 restarted into a world of 1.
        assert results[0] == ("ok", 1, 1)

    def test_spare_rank_activates_on_failure(self):
        """3 ranks, active world capped at 2: rank 2 starts as a reserve spare and
        takes over when rank 1 dies."""

        def body(rank, q):
            from tpu_resiliency.inprocess.rank_assignment import MaxActiveWorldSize
            from tpu_resiliency.inprocess.wrap import CallWrapper

            @fast_wrapper(rank_assignment=MaxActiveWorldSize(2))
            def train(call: CallWrapper):
                fs = call.frozen_state
                if call.iteration == 0 and rank == 1:
                    os._exit(5)
                deadline = time.monotonic() + 60.0
                while call.iteration == 0 and time.monotonic() < deadline:
                    time.sleep(0.05)
                return ("ok", call.iteration, fs.active_rank, fs.active_world_size)

            q.put((rank, train()))

        results, codes = run_world(3, body, timeout=120.0)
        assert codes[1] == 5
        # Survivors 0 and 2 are both active in iteration 1 (spare promoted).
        assert results[0][0] == "ok" and results[2][0] == "ok"
        assert results[0][3] == 2 and results[2][3] == 2


class TestStandDown:
    def test_proxy_completed_straggler_stands_down(self):
        """A rank that was proxy-completed out of a finishing round (declared dead
        while starved, but actually alive) must stand down cleanly when it discovers
        the job finished without it — clean None return and exit 0, not a crash on
        the dead coordinator (wrap.py job_done pre-check + server_linger)."""
        from tpu_resiliency.platform.store import CoordStore

        def body(rank, q):
            @fast_wrapper(server_linger=10.0)
            def train():
                if rank == 0:
                    time.sleep(0.3)
                    return "ok"
                # The straggler: sleeps through the whole completion round, then
                # faults into the restart path.
                time.sleep(4.0)
                raise RuntimeError("late fault on the straggler")

            q.put((rank, train()))
            if rank == 0:
                # Keep the process (and with it the lingering server) alive for the
                # straggler's full rescue window: >= server_linger, so the job_done
                # check cannot race the server's death under CI load.
                time.sleep(12.0)

        def proxy_straggler(port):
            # Simulate the straggler's watcher declaring it dead: proxy rank 1 into
            # the iteration-0 completion barrier so rank 0 finishes without it.
            time.sleep(1.5)
            mon = CoordStore("127.0.0.1", port, prefix="inprocess/")
            mon.barrier_join(
                "barrier/completion/0", 1, 2, timeout=0.0, wait=False, on_behalf=True
            )
            mon.close()

        results, codes = run_world(2, body, timeout=90.0, after_start=proxy_straggler)
        assert results.get(0) == "ok", results
        assert 1 in results and results[1] is None, results  # stood down cleanly
        assert codes == [0, 0]


def test_monitor_long_poll_does_not_queue_the_main_threads_store_calls():
    """At the DEFAULT monitor_interval (1 s) the monitor thread's back-to-back
    long-poll must not hold the connection the wrapped fn's own coordination
    calls use: on a shared client each call queued behind a whole poll."""

    def body(rank, q):
        from tpu_resiliency.inprocess.wrap import CallWrapper

        @fast_wrapper(monitor_interval=1.0, enable_monitor_process=False)
        def fn(call: CallWrapper):
            assert call._monitor_coord.store is not call.coord.store
            time.sleep(0.3)  # let the monitor park in its poll
            t0 = time.monotonic()
            for _ in range(20):
                assert call.coord.is_interrupted(call.iteration) is False
            return time.monotonic() - t0

        q.put((rank, fn()))

    results, codes = run_world(1, body)
    # 20 round trips on loopback: milliseconds. Behind the poll: ~20 s.
    assert codes == [0] and results[0] < 3.0, (results, codes)
