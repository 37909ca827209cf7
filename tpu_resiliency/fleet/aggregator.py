"""Fleet aggregation: parallel scrape fan-out + tree-merge into fleet documents.

One :meth:`FleetAggregator.scrape` is one control-plane heartbeat: read the
live leases (:mod:`tpu_resiliency.fleet.registry`), fan out ONE bounded-timeout
HTTP GET per job (the launcher's consolidated ``/snapshot`` document — metrics
snapshot, goodput summary, health, hang census, incident feed in a single
round trip), and fold the per-job answers into the fleet view:

- **metrics** — every reachable job's snapshot merged under a ``job=`` label
  (``MetricsRegistry.merge(extra_labels=...)``), so two jobs'
  ``tpu_restarts_total`` stay distinct series; the same snapshots are also
  folded *unlabelled* into an explicit fleet-total view re-exposed as
  ``fleet:<name>`` families (the recording-rule namespace: ``fleet:``-prefixed
  series are cross-job sums by construction). fleetd's own operational
  metrics (``tpu_fleet_jobs``, ``tpu_fleet_scrape_seconds``,
  ``tpu_fleet_scrape_errors_total{job}``) ride the same registry.
- **goodput scoreboard** (``tpu-fleet-goodput-1``) — per-job rows ranked by
  goodput ratio, plus a fleet aggregate (train-seconds-weighted ratio).
- **SLO ranking** (``tpu-fleet-slo-1``) — jobs ranked worst-first by
  time-in-restart share, with time-to-detect / time-to-recover percentiles
  interpolated from the merged histogram buckets (:func:`bucket_quantile` —
  merged snapshots transport buckets, not quantile reservoirs).
- **incident feed** (``tpu-fleet-incidents-1``) and **hang census**
  (``tpu-fleet-hangz-1``) — cross-job, each entry stamped with its job.

Failure containment is per job by design: a crashed, hung, or mid-restart job
costs one timed-out GET and a ``status: unreachable`` row (+
``fleet_job_unreachable`` event); it never degrades a fleet endpoint and
never blocks the other jobs' scrapes (parallel fan-out — the wall clock of a
scrape is the slowest single job, not the sum, which is what keeps scrape
cost sub-linear in job count;
``tests/fleet/test_aggregator.py::test_dead_job_is_unreachable_never_fatal``
holds the containment).
"""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from tpu_resiliency.fleet.registry import DEFAULT_TTL_S, expire_stale, live_leases
from tpu_resiliency.utils import events as events_mod
from tpu_resiliency.utils.logging import get_logger
from tpu_resiliency.utils.metrics import MetricsRegistry, observe_record

log = get_logger(__name__)

GOODPUT_SCHEMA = "tpu-fleet-goodput-1"
SLO_SCHEMA = "tpu-fleet-slo-1"
INCIDENTS_SCHEMA = "tpu-fleet-incidents-1"
HANGZ_SCHEMA = "tpu-fleet-hangz-1"
ALERTS_SCHEMA = "tpu-fleet-alerts-1"
SNAPSHOT_SCHEMA = "tpu-fleet-snapshot-1"

#: cross-job alert sort: most urgent severity first (watchtower grades)
_SEVERITY_RANK = {"page": 0, "warn": 1, "info": 2}

#: family-name prefix of the explicit fleet-total series (Prometheus reserves
#: the ``:`` namespace for aggregated/recorded series — which these are)
FLEET_TOTAL_PREFIX = "fleet:"

#: fan-out breadth cap: enough to keep a hundreds-of-jobs scrape near
#: slowest-single-job wall clock without unbounded thread growth
MAX_FANOUT = 32


def bucket_quantile(bounds, counts, q: float) -> Optional[float]:
    """Nearest-rank quantile linearly interpolated inside Prometheus-style
    cumulative buckets (``counts`` has the +Inf tail, ``len(bounds) + 1``).

    Merged snapshots carry exact bucket counts but no sample reservoirs, so
    this is the fleet's only quantile path — same estimate
    ``histogram_quantile`` would give a real Prometheus. Returns None on an
    empty histogram; the +Inf bucket answers with the highest finite bound
    (quantiles beyond instrumented range are clamped, not invented)."""
    if not bounds or len(counts) != len(bounds) + 1:
        return None
    total = sum(counts)
    if total <= 0:
        return None
    target = q * total
    cum = 0.0
    for i, n in enumerate(counts):
        if n <= 0:
            continue
        if cum + n >= target:
            if i >= len(bounds):  # +Inf tail
                return float(bounds[-1])
            hi = float(bounds[i])
            lo = float(bounds[i - 1]) if i > 0 else min(0.0, hi)
            return lo + (hi - lo) * max(0.0, min(1.0, (target - cum) / n))
        cum += n
    return float(bounds[-1])


def _hist_stats(metrics: dict, family: str) -> dict:
    """count / p50 / p95 of one histogram family from a snapshot's ``metrics``
    dict, entries bucket-summed across label sets (matching-bounds only)."""
    bounds: Optional[tuple] = None
    counts: list = []
    total = 0
    for e in metrics.get(family) or []:
        if not isinstance(e, dict) or e.get("type") != "histogram":
            continue
        b = e.get("buckets") or {}
        eb, ec = tuple(b.get("bounds") or ()), list(b.get("counts") or [])
        if not eb or len(ec) != len(eb) + 1:
            continue
        if bounds is None:
            bounds, counts = eb, [0] * len(ec)
        elif eb != bounds:
            continue
        for i, n in enumerate(ec):
            counts[i] += int(n or 0)
        total += int(e.get("count") or 0)
    if bounds is None or total == 0:
        return {"count": 0, "p50": None, "p95": None}
    return {
        "count": total,
        "p50": bucket_quantile(bounds, counts, 0.50),
        "p95": bucket_quantile(bounds, counts, 0.95),
    }


def _counter_total(metrics: dict, family: str) -> float:
    return sum(
        e.get("value") or 0.0
        for e in (metrics.get(family) or [])
        if isinstance(e, dict) and e.get("type") == "counter"
        and isinstance(e.get("value"), (int, float))
    )


class FleetAggregator:
    """Stateless-per-scrape fold of N jobs' telemetry into one fleet view.

    ``registry`` holds fleetd's OWN operational metrics across scrapes (gauge
    of live jobs, scrape-latency histogram, per-job error counters); the
    per-job merged registry is rebuilt fresh each scrape so departed jobs'
    series age out with their leases instead of lingering forever.
    """

    def __init__(
        self,
        fleet_dir: str,
        *,
        lease_ttl: float = DEFAULT_TTL_S,
        timeout: float = 2.0,
        expire: bool = True,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.fleet_dir = fleet_dir
        self.lease_ttl = lease_ttl
        self.timeout = timeout
        self.expire = expire
        self.registry = registry if registry is not None else MetricsRegistry()
        # Scrape-cost flatness machinery: a persistent fan-out pool (thread
        # creation is a per-job linear cost otherwise) and one keep-alive
        # HTTP/1.1 connection per job (TCP handshake + server-side handler
        # thread spawn are per-request linear costs otherwise). Scrapes are
        # serialized — concurrent callers would race the connections.
        self._pool: Optional[ThreadPoolExecutor] = None
        self._conns: dict[str, http.client.HTTPConnection] = {}
        self._scrape_lock = threading.Lock()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        for conn in self._conns.values():
            try:
                conn.close()
            except Exception:
                pass
        self._conns.clear()

    # -- scrape fan-out ------------------------------------------------------

    def _ensure_pool(self, njobs: int) -> ThreadPoolExecutor:
        want = min(MAX_FANOUT, max(4, njobs))
        if self._pool is None or self._pool._max_workers < want:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
            self._pool = ThreadPoolExecutor(
                max_workers=want, thread_name_prefix="fleet-scrape"
            )
        return self._pool

    def _fetch_snapshot(self, url: str) -> dict:
        parsed = urllib.parse.urlsplit(url)
        # Up to two attempts, but only when the first used a kept-alive
        # connection the job has since closed (restart, idle teardown): that
        # one is re-dialed fresh. A job that is actually down fails its
        # fresh connect once — never a doubled timeout.
        for _ in (0, 1):
            conn = self._conns.pop(url, None)
            fresh = conn is None
            if fresh:
                conn = http.client.HTTPConnection(
                    parsed.hostname, parsed.port, timeout=self.timeout
                )
            try:
                conn.request("GET", "/snapshot")
                resp = conn.getresponse()
                body = resp.read()
                if resp.status != 200:
                    raise RuntimeError(f"/snapshot answered {resp.status}")
                if not resp.will_close:
                    self._conns[url] = conn  # keep alive for the next scrape
                else:
                    conn.close()
                doc = json.loads(body)
                if not isinstance(doc, dict):
                    raise ValueError("job snapshot is not a JSON object")
                return doc
            except Exception:
                try:
                    conn.close()
                except Exception:
                    pass
                if fresh:
                    raise
        raise AssertionError("unreachable")

    def _scrape_job(self, lease) -> dict:
        t0 = time.monotonic()
        state = {
            "job": lease.job,
            "url": lease.url,
            "node_id": lease.node_id,
            "pid": lease.pid,
            "started_at": lease.started_at,
            "heartbeat_ts": lease.heartbeat_ts,
            "reachable": False,
            "error": None,
            "scrape_s": None,
            "doc": None,
        }
        try:
            state["doc"] = self._fetch_snapshot(lease.url)
            state["reachable"] = True
        except Exception as e:
            state["error"] = repr(e)
        state["scrape_s"] = round(time.monotonic() - t0, 6)
        return state

    def scrape(self) -> "FleetView":
        """One full fleet scrape: discover, fan out, fold. Never raises for
        a job's sake — every per-job failure is a row, not an exception.
        Serialized (concurrent callers would race the kept-alive
        connections); the FleetServer's view cache already collapses scrape
        storms before they get here."""
        with self._scrape_lock:
            return self._scrape_locked()

    def _scrape_locked(self) -> "FleetView":
        t0 = time.monotonic()
        if self.expire:
            expire_stale(self.fleet_dir, self.lease_ttl)
        leases = live_leases(self.fleet_dir, self.lease_ttl)
        states: list[dict] = []
        if leases:
            pool = self._ensure_pool(len(leases))
            states = list(
                pool.map(self._scrape_job, [leases[j] for j in sorted(leases)])
            )
        duration = time.monotonic() - t0
        unreachable = [s for s in states if not s["reachable"]]
        # Audit + self-metrics through the one shared kind→metric mapping, so
        # fleetd's live registry and a post-hoc aggregate of its events agree.
        self._observe(
            "fleet_scrape",
            jobs=len(states),
            unreachable=len(unreachable),
            duration_s=round(duration, 6),
        )
        for s in unreachable:
            self._observe("fleet_job_unreachable", job=s["job"], error=s["error"])
        return FleetView(self, states, duration)

    def _observe(self, kind: str, **payload) -> None:
        events_mod.record("fleetd", kind, **payload)
        observe_record({"kind": kind, "ts": time.time(), **payload}, self.registry)


class FleetView:
    """One scrape's fold: the documents every ``/fleet/*`` endpoint serves."""

    def __init__(self, agg: FleetAggregator, states: list[dict], duration: float):
        self.ts = time.time()
        self.fleet_dir = agg.fleet_dir
        self.scrape_s = round(duration, 6)
        self.states = states
        self.registry = self._merged_registry(agg)

    # -- merged metrics ------------------------------------------------------

    def _merged_registry(self, agg: FleetAggregator) -> MetricsRegistry:
        merged = MetricsRegistry()
        totals = MetricsRegistry()
        for s in self.states:
            metrics = (s["doc"] or {}).get("metrics")
            if not isinstance(metrics, dict):
                continue
            try:
                # The federation fold: same-named series of different jobs
                # stay separate under the injected job label...
                merged.merge(metrics, extra_labels={"job": s["job"]})
                # ...and still sum in the explicit fleet-total families.
                totals.merge(metrics)
            except (ValueError, TypeError):
                log.debug(f"unmergeable metrics from job {s['job']!r}", exc_info=True)
        tot = totals.snapshot()
        merged.merge({
            "ts": tot.get("ts"),
            "metrics": {
                f"{FLEET_TOTAL_PREFIX}{name}": entries
                for name, entries in (tot.get("metrics") or {}).items()
            },
        })
        merged.merge(agg.registry.snapshot())
        return merged

    def to_prometheus(self) -> str:
        return self.registry.to_prometheus()

    # -- per-job helpers -----------------------------------------------------

    def _row_base(self, s: dict) -> dict:
        return {
            "job": s["job"],
            "status": "ok" if s["reachable"] else "unreachable",
            "url": s["url"],
            "node_id": s["node_id"],
            "error": s["error"],
            "scrape_s": s["scrape_s"],
        }

    # -- documents -----------------------------------------------------------

    def goodput_doc(self) -> dict:
        """The scoreboard: reachable jobs ranked by goodput ratio (best
        first), unreachable jobs listed after them — present, named, and
        explicitly degraded rather than silently missing."""
        rows = []
        train_sum = wall_sum = 0.0
        for s in self.states:
            row = self._row_base(s)
            gp = (s["doc"] or {}).get("goodput")
            if isinstance(gp, dict):
                phases = gp.get("phases") or {}
                row.update(
                    goodput_ratio=gp.get("goodput_ratio"),
                    wall_clock_s=gp.get("wall_clock_s"),
                    steps=gp.get("steps"),
                    phases=phases,
                )
                if isinstance(gp.get("wall_clock_s"), (int, float)):
                    wall_sum += gp["wall_clock_s"]
                    train = phases.get("train")
                    if isinstance(train, (int, float)):
                        train_sum += train
            rows.append(row)
        rows.sort(
            key=lambda r: (
                r["status"] != "ok",
                -(r.get("goodput_ratio") or 0.0),
                r["job"],
            )
        )
        return {
            "schema": GOODPUT_SCHEMA,
            "ts": self.ts,
            "jobs": rows,
            "fleet": {
                "jobs": len(rows),
                "reachable": sum(1 for r in rows if r["status"] == "ok"),
                "wall_clock_s": round(wall_sum, 6),
                "train_s": round(train_sum, 6),
                "goodput_ratio": (
                    round(train_sum / wall_sum, 6) if wall_sum > 0 else 0.0
                ),
            },
        }

    def slo_doc(self) -> dict:
        """Jobs ranked worst-first by time-in-restart share, with
        time-to-detect / time-to-recover percentiles from the merged
        incident histograms — the page an on-call reads top-down."""
        rows = []
        for s in self.states:
            row = self._row_base(s)
            doc = s["doc"] or {}
            gp = doc.get("goodput") if isinstance(doc.get("goodput"), dict) else {}
            phases = gp.get("phases") or {}
            wall = gp.get("wall_clock_s")
            restart_s = phases.get("restart")
            incident_s = phases.get("incident")
            row.update(
                wall_clock_s=wall,
                restart_s=restart_s,
                incident_s=incident_s,
                restart_share=(
                    round(restart_s / wall, 6)
                    if isinstance(restart_s, (int, float))
                    and isinstance(wall, (int, float)) and wall > 0 else None
                ),
                goodput_ratio=gp.get("goodput_ratio"),
            )
            metrics = doc.get("metrics")
            m = metrics.get("metrics") if isinstance(metrics, dict) else None
            if isinstance(m, dict):
                row.update(
                    restarts=int(_counter_total(m, "tpu_restarts_total")),
                    incidents=int(_counter_total(m, "tpu_incidents_total")),
                    time_to_detect_s=_hist_stats(
                        m, "tpu_incident_time_to_detect_seconds"
                    ),
                    time_to_recover_s=_hist_stats(
                        m, "tpu_incident_time_to_recover_seconds"
                    ),
                )
            rows.append(row)
        # Worst first: unreachable jobs lead (they ARE the incident), then by
        # restart share descending.
        rows.sort(
            key=lambda r: (
                r["status"] == "ok",
                -(r.get("restart_share") or 0.0),
                r["job"],
            )
        )
        return {"schema": SLO_SCHEMA, "ts": self.ts, "jobs": rows}

    def incidents_doc(self) -> dict:
        """The cross-job incident feed: every job's recent ``tpu-incident-1``
        summaries stamped with their job, newest first."""
        feed = []
        by_job: dict[str, int] = {}
        for s in self.states:
            incidents = (s["doc"] or {}).get("incidents")
            if not isinstance(incidents, list):
                continue
            for inc in incidents:
                if not isinstance(inc, dict):
                    continue
                feed.append({"job": s["job"], **inc})
                by_job[s["job"]] = by_job.get(s["job"], 0) + 1
        feed.sort(
            key=lambda i: (
                -(i.get("opened_ts") if isinstance(i.get("opened_ts"), (int, float))
                  else 0.0),
                i["job"],
            )
        )
        return {
            "schema": INCIDENTS_SCHEMA,
            "ts": self.ts,
            "incidents": feed,
            "jobs": dict(sorted(by_job.items())),
            "unreachable": sorted(
                s["job"] for s in self.states if not s["reachable"]
            ),
        }

    def hangz_doc(self) -> dict:
        """The fleet-wide hang census: each job's ``/hangz`` document plus a
        flattened cross-job suspect ranking."""
        jobs = []
        suspects = []
        for s in self.states:
            row = self._row_base(s)
            hz = (s["doc"] or {}).get("hangz")
            if isinstance(hz, dict):
                row["census"] = hz
                for sus in hz.get("suspects") or []:
                    if isinstance(sus, dict):
                        suspects.append({"job": s["job"], **sus})
            jobs.append(row)
        suspects.sort(key=lambda x: (-(x.get("score") or 0.0), x["job"]))
        return {
            "schema": HANGZ_SCHEMA,
            "ts": self.ts,
            "jobs": jobs,
            "suspects": suspects,
        }

    def alerts_doc(self) -> dict:
        """The severity-ranked cross-job alert feed: every job's active
        watchtower alerts stamped with their job, pages first. An unreachable
        job degrades to its row (status ``unreachable``) — its last-known
        alerts are gone with its endpoint, but the job itself never vanishes
        from the feed, and the endpoint never answers non-200 for it."""
        jobs = []
        active = []
        firing_jobs: dict[str, int] = {}
        for s in self.states:
            row = self._row_base(s)
            al = (s["doc"] or {}).get("alerts")
            if isinstance(al, dict):
                row.update(
                    active=len(al.get("active") or []),
                    rules=len(al.get("rules") or []),
                    alerts_error=al.get("error"),
                )
                for a in al.get("active") or []:
                    if isinstance(a, dict):
                        active.append({"job": s["job"], **a})
                        firing_jobs[s["job"]] = firing_jobs.get(s["job"], 0) + 1
            jobs.append(row)
        active.sort(
            key=lambda a: (
                _SEVERITY_RANK.get(a.get("severity"), 9),
                -(a.get("fire_ts") if isinstance(a.get("fire_ts"), (int, float))
                  else 0.0),
                a["job"],
                str(a.get("rule")),
            )
        )
        return {
            "schema": ALERTS_SCHEMA,
            "ts": self.ts,
            "active": active,
            "jobs": jobs,
            "firing_jobs": dict(sorted(firing_jobs.items())),
            "unreachable": sorted(
                s["job"] for s in self.states if not s["reachable"]
            ),
        }

    def snapshot_doc(self) -> dict:
        """The whole fold as one offline-renderable artifact (what
        ``tpu-fleetd --snapshot`` persists and ``tpu-fleet`` renders)."""
        return {
            "schema": SNAPSHOT_SCHEMA,
            "ts": self.ts,
            "fleet_dir": self.fleet_dir,
            "scrape_s": self.scrape_s,
            "goodput": self.goodput_doc(),
            "slo": self.slo_doc(),
            "incidents": self.incidents_doc(),
            "hangz": self.hangz_doc(),
            "alerts": self.alerts_doc(),
            "metrics": self.registry.snapshot(),
        }
