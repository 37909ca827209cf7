"""jax.distributed lifecycle for resilient jobs: initialize survivable, tear down
restartable, re-initialize with a new world.

The TPU-native analogue of the reference's NCCL abort + process-group destroy
(``inprocess/abort.py:58-105``): there, surviving ranks abort communicators so the
restarted iteration can rebuild collectives over a new group. Under JAX the
coordination layer is the distributed runtime client/service, and two facts
(measured on jax 0.9, CPU/Gloo backend — see tests/inprocess/test_abort_reinit.py)
shape this module:

- **Peer death is fatal by default.** The XLA distributed client LOG(FATAL)s the
  *surviving* process the moment the coordination service reports any peer dead
  ("Terminating process because the JAX distributed service detected fatal
  errors"). A resilient job must opt in to ``jax_enable_recoverability`` at
  initialize time — after the fault it is too late.
- **Re-initialize requires dead backends.** ``jax.distributed.initialize`` refuses
  to run once the XLA backends are live, so the restart teardown must also clear
  them (dropping device buffers — the restart loop reloads state from local
  checkpoints anyway, ``checkpoint/local_manager.py``).

A collective already in flight against a dead peer can still block indefinitely
(Gloo has no liveness timeout); that case is the monitor process's hard-timeout
ladder (``inprocess/monitor_process.py``), not this module's. This module makes the
*between-steps* fault — the overwhelmingly common case — restartable in-process.
"""

from __future__ import annotations

from tpu_resiliency.utils.logging import get_logger

log = get_logger(__name__)


def client_active() -> bool:
    """Is a jax.distributed client currently connected?"""
    import jax

    return jax._src.distributed.global_state.client is not None  # noqa: SLF001


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    *,
    heartbeat_timeout: float = 10.0,
    initialization_timeout: float = 60.0,
    recoverable: bool = True,
) -> None:
    """``jax.distributed.initialize`` with survivable-peer-death defaults.

    ``recoverable`` turns on ``jax_enable_recoverability`` so peer death surfaces
    as an error instead of terminating this process (required for any in-process
    restart); set it False only for jobs that prefer fail-fast semantics.
    """
    import jax

    if recoverable:
        jax.config.update("jax_enable_recoverability", True)
    jax.distributed.initialize(
        coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        # jax takes whole seconds; never truncate a sub-second request to 0
        # (0 would disable/instant-fire the heartbeat).
        heartbeat_timeout_seconds=max(1, round(heartbeat_timeout)),
        initialization_timeout=max(1, round(initialization_timeout)),
    )
    log.info(
        f"jax.distributed initialized: world={num_processes} rank={process_id} "
        f"coordinator={coordinator_address} recoverable={recoverable}"
    )


def clear_backends() -> None:
    """Tear down live XLA backends (jax 0.9 has no public API for it).

    Only the multi-process restart needs this (``initialize`` refuses while
    backends are live); a one-process restart keeps its backend, see
    :func:`shutdown_for_restart`. On one v5e chip the same process re-acquired
    the chip 0.16 s after this call, once nothing referenced the old client's
    arrays or executables (chip run, PR 21)."""
    import jax._src.xla_bridge as xb  # noqa: SLF001

    xb._clear_backends()  # noqa: SLF001


def shutdown_ordered(
    store,
    active_rank: int,
    active_world_size: int,
    *,
    iteration: int = 0,
    timeout: float = 30.0,
    key: str = "jd_shutdown_done",
) -> None:
    """Orderly END-OF-JOB teardown: coordinator's service outlives every peer.

    A recoverable client's shutdown barrier does not block (by design — see
    :func:`initialize`), so at job completion the coordinator (active rank 0,
    which hosts the coordination service) can exit before a peer's client sends
    its disconnect RPC; that late disconnect then LOG(FATAL)s the peer at
    interpreter exit. Here non-coordinator ranks shut down their clients first
    and announce on the job ``store``; the coordinator waits for every
    announcement (bounded by ``timeout``, best-effort beyond it) before tearing
    the service down. Call once per rank after the last collective, passing the
    restart ``iteration`` (stale announcements from an earlier, fault-aborted
    completion attempt must not satisfy this round's wait). Backends are left
    alive (nothing restarts after completion). Never raises: a completed job
    must not be re-classified as faulted because its teardown hiccuped.
    """
    import time as _time

    import jax

    if not client_active():
        return
    skey = f"{key}/{iteration}"
    if active_rank != 0:
        try:
            jax.distributed.shutdown()
        except Exception as e:
            log.warning(f"shutdown_ordered: client shutdown failed: {e!r}")
        try:
            store.set_add(skey, [int(active_rank)])
        except Exception as e:
            log.warning(f"shutdown_ordered: announcement failed: {e!r}")
        return
    expected = set(range(1, active_world_size))
    deadline = _time.monotonic() + timeout
    try:
        while _time.monotonic() < deadline:
            if set(store.set_get(skey)) >= expected:
                break
            _time.sleep(0.05)
        else:
            log.warning(
                f"shutdown_ordered: peers {expected - set(store.set_get(skey))} "
                f"never announced client shutdown within {timeout}s; tearing down "
                f"anyway"
            )
    except Exception as e:
        log.warning(f"shutdown_ordered: announcement wait failed: {e!r}")
    try:
        jax.distributed.shutdown()
    except Exception as e:
        log.warning(f"shutdown_ordered: coordinator shutdown failed: {e!r}")


def shutdown_graceful(process_id: int, grace: float = 5.0) -> None:
    """End-of-job teardown WITHOUT a coordination store: non-coordinator ranks
    disconnect immediately; the coordinator idles ``grace`` seconds before
    tearing its service down, so a peer's slightly-later disconnect RPC cannot
    LOG(FATAL) that peer at interpreter exit (recoverable clients have no
    synchronized shutdown barrier — see :func:`shutdown_ordered`, which is
    deterministic and preferred when a KV store is available). Typical use: the
    exit path after :class:`PreemptionCheckpointCallback` stops the loop.
    Never raises."""
    import time as _time

    import jax

    if not client_active():
        return
    try:
        # Only the coordinator waits, and only when peers exist whose late
        # disconnects its service must outlive (single-process worlds skip it).
        if process_id == 0 and jax.process_count() > 1:
            _time.sleep(grace)
        jax.distributed.shutdown()
    except Exception as e:
        log.warning(f"shutdown_graceful: {e!r}")


def shutdown_for_restart() -> bool:
    """Tear down the distributed client/service AND the XLA backends so a later
    :func:`initialize` with a different world is legal.

    Returns True when a distributed client was actually shut down (callers can
    skip backend-rebuild costs otherwise). Never raises: the restart loop must
    proceed no matter how broken the old world's state is.
    """
    import jax

    had_client = False
    try:
        had_client = client_active()
        if had_client:
            jax.distributed.shutdown()
            log.info("jax.distributed client/service shut down")
    except Exception as e:
        log.warning(f"jax.distributed.shutdown failed (continuing): {e!r}")
    if not had_client:
        return False
    try:
        jax.clear_caches()
        clear_backends()
        log.info("XLA backends cleared for re-initialize")
    except Exception as e:
        log.warning(f"backend teardown failed (continuing): {e!r}")
    return True
