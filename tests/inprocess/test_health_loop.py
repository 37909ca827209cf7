"""The health-vector decisions loop, closed end to end with REAL measurements
(BASELINE target 5): a slow-but-alive rank's section timings flow through the
Detector's scored report → ``HealthVectorPolicy`` debounce → the coordination
store's degraded set → ``DemoteDegraded`` benches the rank as a spare at the next
restart round — no hand-planted degraded state anywhere."""

import multiprocessing as mp
import os
import socket
import time


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


STEPS_PER_ROUND = 6
MAX_REPORT_ROUNDS = 8  # stop as soon as the demotion is agreed (patience 2)


def body(rank, world, port, q):
    # Spawned children do not run conftest, but they inherit the
    # JAX_PLATFORMS=cpu it exported: three rank processes cannot share one chip.
    os.environ.update(
        RANK=str(rank),
        WORLD_SIZE=str(world),
        TPU_RESILIENCY_STORE_PORT=str(port),
        TPU_RESILIENCY_STORE_HOST="127.0.0.1",
    )
    from tpu_resiliency.inprocess.rank_assignment import DemoteDegraded
    from tpu_resiliency.inprocess.wrap import CallWrapper, Wrapper
    from tpu_resiliency.platform.store import CoordStore
    from tpu_resiliency.telemetry.detector import Detector
    from tpu_resiliency.telemetry.policy import HealthVectorPolicy

    @Wrapper(
        rank_assignment=DemoteDegraded(max_active_world_size=2),
        monitor_interval=0.05,
        last_call_wait=0.1,
        soft_timeout=45.0,
        hard_timeout=90.0,
        heartbeat_interval=0.2,
        # Hang detection is NOT this test's subject (measured slowness → scored
        # demotion is); a tight heartbeat window false-positives under CI load
        # and ejects a healthy-but-starved rank mid-completion.
        heartbeat_timeout=60.0,
        barrier_timeout=90.0,
        completion_timeout=90.0,
    )
    def train(call: CallWrapper):
        fs = call.frozen_state
        if fs.iteration >= 1:
            # Post-demotion round: actives finish; the demoted rank idles in
            # reserve inside the wrapper and returns None.
            return ("ok", fs.iteration, fs.mode.name, fs.active_world_size)

        # Telemetry spans the ACTIVE world (the spare's fn never runs): with the
        # active world capped at 2, iteration 0 actives are ranks {0, 1}.
        me, active_world = fs.active_rank, fs.active_world_size
        store = CoordStore("127.0.0.1", int(os.environ["TPU_RESILIENCY_STORE_PORT"]))
        policy = HealthVectorPolicy(
            patience=2,
            recovery=100,
            sinks=[lambda decision: call.coord.set_degraded(decision.degraded)],
        )
        Detector.initialize(
            rank=me,
            world_size=active_world,
            store=store.scoped("telemetry/"),
            gather_on_rank0=False,
            report_time_interval=3600.0,
        )
        try:
            for _ in range(MAX_REPORT_ROUNDS):
                for _ in range(STEPS_PER_ROUND):
                    with Detector.detection_section("step", profile_device=False):
                        # Rank 1 is genuinely 10x slower, measured for real (wide
                        # margin: host scheduling noise under CI load must not
                        # compress the ratio past the 0.75 threshold).
                        time.sleep(0.080 if rank == 1 else 0.008)
                report = Detector.generate_report()  # collective (store barrier)
                decision = policy.observe(report)
                # Same global report on every rank -> same decision -> all ranks
                # break on the same round (generate_report stays collective).
                if 1 in decision.degraded:
                    break
            assert 1 in decision.degraded, decision
        finally:
            Detector.shutdown()
            store.close()
        if rank == 0:
            time.sleep(0.2)  # let peers reach their park loops
            raise RuntimeError("force the restart round that applies the demotion")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            time.sleep(0.02)
        return ("parked-forever", fs.iteration, fs.mode.name, fs.active_world_size)

    q.put((rank, train()))


def test_measured_slowness_demotes_through_the_full_loop():
    world = 3
    port = free_port()
    # Children call into JAX (Detector scoring); the pytest parent has a live,
    # multithreaded JAX backend, so fork()ed children can inherit a held lock and
    # deadlock under suite load. Spawn starts them clean.
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=body, args=(r, world, port, q)) for r in range(world)]
    for p in procs:
        p.start()
    results = {}
    deadline = time.monotonic() + 180
    try:
        while len(results) < world and time.monotonic() < deadline:
            try:
                r, payload = q.get(timeout=1.0)
                results[r] = payload
            except Exception:
                if all(not p.is_alive() for p in procs):
                    break
    finally:
        for p in procs:
            p.join(timeout=20.0)
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)

    # The measured-slow rank was demoted: it spent iteration 1 in reserve (a
    # reserve rank's wrapper returns None), while the healthy pair ran active.
    assert results[1] is None, results
    assert results[0] == ("ok", 1, "ACTIVE", 2), results
    assert results[2] == ("ok", 1, "ACTIVE", 2), results
