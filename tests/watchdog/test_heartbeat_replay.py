"""One hang among 256 replayed ranks, on a virtual clock: the monitor's own decision
(``RankMonitorServer._hb_timeout_elapsed``) declares the hung rank within
``hb_timeout + hb_interval + tick`` of its last heartbeat, and no healthy rank."""

from tpu_resiliency.watchdog.config import FaultToleranceConfig
from tpu_resiliency.watchdog.data import RankInfo
from tpu_resiliency.watchdog.monitor_server import RankMonitorServer, _RankSession

RANKS, HANG_RANK = 256, 101
HB_INTERVAL, HB_TIMEOUT, TICK = 1.0, 3.0, 0.5
HANG_AT, HORIZON = 30.0, 60.0


def replay():
    """Every rank beats once a second from t=1; ``HANG_RANK`` sends nothing from
    ``HANG_AT`` on. Each tick hands every monitor the newest beat that has arrived
    and asks it the question its periodic check asks. Returns rank -> tick of the
    first verdict."""
    cfg = FaultToleranceConfig(
        initial_rank_heartbeat_timeout=10.0,
        rank_heartbeat_timeout=HB_TIMEOUT,
        workload_check_interval=TICK,
    )
    monitors = []
    for r in range(RANKS):
        srv = RankMonitorServer(cfg, socket_path=f"/nonexistent/replay_{r}.sock")
        srv.session = _RankSession(
            info=RankInfo(global_rank=r, local_rank=r % 8, host=f"host{r // 8}", pid=0),
            connected_at=0.0,
        )
        monitors.append(srv)

    declared: dict[int, float] = {}
    for tick in range(1, int(HORIZON / TICK) + 1):
        now = tick * TICK
        newest = float(int(now / HB_INTERVAL)) * HB_INTERVAL
        for r, srv in enumerate(monitors):
            last = min(newest, HANG_AT - HB_INTERVAL) if r == HANG_RANK else newest
            srv.session.last_hb = last if last >= HB_INTERVAL else None
            if r not in declared and srv._hb_timeout_elapsed(now) is not None:
                declared[r] = now
    return declared


def test_the_hung_rank_is_declared_within_the_budget_and_no_healthy_rank_is():
    declared = replay()
    assert set(declared) == {HANG_RANK}
    latency = declared[HANG_RANK] - (HANG_AT - HB_INTERVAL)  # from its last beat
    assert HB_TIMEOUT < latency <= HB_TIMEOUT + HB_INTERVAL + TICK
