"""Span critical-path analysis over the structured event stream.

Phase decomposition over the events JSONL (the ``failure_detected`` /
``restart_requested`` / ``rendezvous_round`` timestamps of a restart, the
spans of a resharded resume) has ONE code path, this module, which the tests
and the operator tooling both use: it builds the span DAG of a
restart / save / reshard episode from the events JSONL (parenting already
env-propagated by ``utils/tracing.py``), computes the **dominant chain** — the
sequence of spans that actually gates the episode's wall clock — with
per-segment self-time vs overlap, and renders an operator table plus a
Chrome-trace export with the critical path highlighted
(``tools/trace_export.py`` colors the chain's spans distinctly).

Three layers of answer, cheapest first:

- **milestone decomposition** (:func:`restart_decomposition`): the
  detect / teardown / rendezvous / promote / first-step-ready split, computed
  from the launcher's own milestone events
  (``tests/launcher/test_restart_milestones.py`` drives it through one real
  restart);
- **dominant chain** (:func:`dominant_chain`): walk backward from the episode
  end, at each instant charging the wall clock to the most specific span
  covering it — the restart's critical path reads
  ``launcher.round → rendezvous.round → worker.spawn`` instead of "812 ms";
- **self-time** (:func:`self_time`): a chain span's duration minus its
  children's overlap — the part only THAT span can explain, which is where an
  optimization must land to move the episode.

Usage::

    tpu-critpath run_events.jsonl                       # auto: every episode
    tpu-critpath run_events.jsonl --format json
    tpu-critpath run_events.jsonl --trace run.trace.json  # highlighted trace
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Iterable, Optional

from tpu_resiliency.tools import SIGPIPE_EXIT, pipe_safe
from tpu_resiliency.utils.events import read_events
from tpu_resiliency.utils.goodput import (
    RESTART_EVIDENCE,
    merge_intervals,
    subtract_intervals,
    total_seconds,
)

SCHEMA = "tpu-critpath-1"


@dataclasses.dataclass
class Span:
    name: str
    source: str
    pid: int
    span_id: Optional[str]
    parent_id: Optional[str]
    t0: float
    t1: float
    finished: bool
    args: dict


def collect_spans(records: Iterable[dict]) -> list[Span]:
    """Pair ``span_begin``/``span_end`` records into :class:`Span` objects.

    Unmatched begins (the process died mid-span — the interesting case)
    become unfinished spans running to end-of-stream, same convention as
    ``trace_export``. Ends without begins are dropped here (they carry no
    interval)."""
    recs = [
        r for r in records
        if isinstance(r.get("ts"), (int, float)) and isinstance(r.get("kind"), str)
    ]
    recs.sort(key=lambda r: r["ts"])
    if not recs:
        return []
    t_last = recs[-1]["ts"]
    open_spans: dict[tuple, dict] = {}
    out: list[Span] = []
    for rec in recs:
        kind = rec["kind"]
        sid = rec.get("span_id")
        if kind == "span_begin" and sid:
            open_spans[(rec.get("pid"), sid)] = rec
        elif kind == "span_end" and sid:
            begin = open_spans.pop((rec.get("pid"), sid), None)
            if begin is None:
                continue
            out.append(Span(
                name=str(begin.get("span", "span")),
                source=str(begin.get("source", "?")),
                pid=begin.get("pid", 0),
                span_id=sid,
                parent_id=begin.get("parent_id"),
                t0=begin["ts"],
                t1=rec["ts"],
                finished=True,
                args={k: v for k, v in begin.items()
                      if k not in ("ts", "kind", "span", "pid", "source",
                                   "span_id", "parent_id", "trace_id")},
            ))
    for (pid, sid), begin in open_spans.items():
        out.append(Span(
            name=str(begin.get("span", "span")),
            source=str(begin.get("source", "?")),
            pid=pid or 0,
            span_id=sid,
            parent_id=begin.get("parent_id"),
            t0=begin["ts"],
            t1=t_last,
            finished=False,
            args={},
        ))
    out.sort(key=lambda s: (s.t0, s.t1))
    return out


def self_time(span: Span, spans: list[Span]) -> float:
    """Span duration minus the union of its children's overlap — the seconds
    only this span's own code can explain."""
    children = [
        (max(c.t0, span.t0), min(c.t1, span.t1))
        for c in spans
        if c.parent_id is not None and c.parent_id == span.span_id
        and c.t1 > span.t0 and c.t0 < span.t1
    ]
    if not children:
        return max(0.0, span.t1 - span.t0)
    own = subtract_intervals(
        merge_intervals([(span.t0, span.t1)]), merge_intervals(children)
    )
    return total_seconds(own)


# -- milestone decomposition ---------------------------------------------------


def _first_ts(recs: list[dict], kind: str, after: float = float("-inf"),
              pred=None) -> Optional[float]:
    for r in recs:
        if r.get("kind") == kind and r["ts"] >= after and (
            pred is None or pred(r)
        ):
            return r["ts"]
    return None


def find_restart_episodes(records: Iterable[dict]) -> list[dict]:
    """Every restart episode in the stream: fault evidence → training
    resumed, decomposed at the launcher's own milestone events. This segment
    arithmetic is the one definition."""
    recs = [
        r for r in records
        if isinstance(r.get("ts"), (int, float)) and isinstance(r.get("kind"), str)
    ]
    recs.sort(key=lambda r: r["ts"])
    episodes: list[dict] = []
    cursor = float("-inf")
    while True:
        t_fault = next(
            (r["ts"] for r in recs
             if r["kind"] in RESTART_EVIDENCE and r["ts"] > cursor),
            None,
        )
        if t_fault is None:
            return episodes
        ep = _decompose(recs, t_fault)
        episodes.append(ep)
        cursor = ep["t_end"]


def _decompose(
    recs: list[dict],
    t_fault: float,
    resume_ts: Optional[float] = None,
) -> dict:
    t_detect = _first_ts(recs, "failure_detected", t_fault)
    t_request = _first_ts(recs, "restart_requested", t_detect or t_fault)
    t_round = (
        _first_ts(recs, "rendezvous_round", t_request)
        if t_request is not None else None
    )
    t_promote = (
        _first_ts(
            recs, "worker_promoted", t_round,
            pred=lambda r: r.get("outcome", "promoted") == "promoted",
        )
        if t_round is not None else None
    )
    if resume_ts is None and t_round is not None:
        resume_ts = _first_ts(recs, "iteration_start", t_round)
    fast_path = t_request is not None and any(
        r.get("kind") == "rendezvous_fast_path" and r.get("outcome") == "reused"
        and r["ts"] >= t_request for r in recs
    )
    segments: list[dict] = []

    def seg(name: str, start: Optional[float], end: Optional[float]) -> None:
        # Clamped at zero: a milestone pair can invert by a fraction of a
        # millisecond (a promoted shim's first statement beating the
        # launcher's own promote stamp) — that is a 0-length segment, not a
        # missing one.
        if start is not None and end is not None:
            segments.append({
                "name": name, "start": start, "end": max(start, end),
                "duration_ms": round(max(0.0, end - start) * 1e3, 3),
            })

    seg("detect", t_fault, t_detect)
    seg("teardown", t_detect, t_request)
    seg("rendezvous", t_request, t_round)
    if t_promote is not None:
        seg("promote", t_round, t_promote)
        seg("first_step_ready", t_promote, resume_ts)
    else:
        seg("spawn_and_startup", t_round, resume_ts)
    t_end = next(
        (t for t in (resume_ts, t_promote, t_round, t_request, t_detect)
         if t is not None),
        t_fault,
    )
    return {
        "kind": "restart",
        "t_fault": t_fault,
        "t_detect": t_detect,
        "t_request": t_request,
        "t_round": t_round,
        "t_promote": t_promote,
        "t_resume": resume_ts,
        "t_end": t_end,
        "total_ms": round((t_end - t_fault) * 1e3, 3),
        "fast_path": fast_path,
        "promoted": t_promote is not None,
        "segments": segments,
    }


def restart_decomposition(
    records: Iterable[dict],
    *,
    fault_ts: Optional[float] = None,
    resume_ts: Optional[float] = None,
) -> Optional[dict]:
    """The first restart episode's decomposition, with optional external
    anchors: a caller that knows the exact fault/resume instants (worker
    stamp files, on the same wall clock as the stream) passes them so its
    numbers and the pure-events view share one arithmetic."""
    recs = [
        r for r in records
        if isinstance(r.get("ts"), (int, float)) and isinstance(r.get("kind"), str)
    ]
    recs.sort(key=lambda r: r["ts"])
    if fault_ts is None:
        fault_ts = next(
            (r["ts"] for r in recs if r["kind"] in RESTART_EVIDENCE), None
        )
    if fault_ts is None:
        return None
    return _decompose(recs, fault_ts, resume_ts=resume_ts)


def reshard_decomposition(records: Iterable[dict]) -> dict:
    """Phase split of a resharded resume from its own spans/events: plan
    build, ranged peer fetch (wall + bytes), local slice bytes."""
    recs = [r for r in records if isinstance(r, dict)]
    spans = collect_spans(recs)
    plan_s = sum(s.t1 - s.t0 for s in spans if s.name == "reshard.plan")
    fetch_spans = [s for s in spans if s.name == "reshard.fetch"]
    fetch_s = total_seconds(
        merge_intervals([(s.t0, s.t1) for s in fetch_spans])
    )
    local = peer = fetches = 0
    for r in recs:
        if r.get("kind") != "reshard_fetch":
            continue
        nbytes = r.get("bytes")
        if not isinstance(nbytes, (int, float)):
            continue
        if r.get("via") == "peer":
            peer += int(nbytes)
            fetches += 1
        else:
            local += int(nbytes)
    return {
        "plan_s": round(plan_s, 6),
        "fetch_s": round(fetch_s, 6),
        "local_bytes": local,
        "peer_bytes": peer,
        "peer_fetches": fetches,
    }


# -- dominant chain ------------------------------------------------------------


def dominant_chain(
    spans: list[Span], t0: float, t1: float, eps: float = 1e-9
) -> list[dict]:
    """The critical chain through ``[t0, t1]``: walking backward from the
    end, each instant is charged to the **most specific** span covering it
    (latest start wins — ``rendezvous.round`` beats the ``launcher.round``
    that contains it), then the walk jumps to that span's start. Instants no
    span covers become explicit ``(gap)`` segments — unexplained wall clock
    is a finding, not something to render around."""
    cands = [s for s in spans if s.t1 > t0 + eps and s.t0 < t1 - eps]
    chain: list[dict] = []
    cursor = t1
    while cursor > t0 + eps:
        covering = [s for s in cands if s.t0 < cursor - eps and s.t1 >= cursor - eps]
        if covering:
            pick = max(covering, key=lambda s: (s.t0, s.t1))
            # Charge `pick` only back to the latest end of a more specific
            # span inside its window — the walk then descends into THAT span
            # (the classic critical-path hop), instead of letting a parent
            # slice swallow its children's structure.
            inner_end = max(
                (s.t1 for s in cands
                 if s is not pick and pick.t0 + eps < s.t1 < cursor - eps
                 and s.t0 > pick.t0 - eps),
                default=pick.t0,
            )
            start = max(inner_end, pick.t0, t0)
            chain.append({
                "span": pick.name,
                "source": pick.source,
                "pid": pick.pid,
                "span_id": pick.span_id,
                "start": start,
                "end": cursor,
                "duration_ms": round((cursor - start) * 1e3, 3),
                "span_duration_ms": round((pick.t1 - pick.t0) * 1e3, 3),
                "self_time_ms": round(self_time(pick, spans) * 1e3, 3),
                "unfinished": not pick.finished,
            })
            cursor = start
        else:
            ended = [s for s in cands if s.t1 < cursor - eps]
            gap_start = max((s.t1 for s in ended), default=t0)
            gap_start = max(gap_start, t0)
            chain.append({
                "span": "(gap)", "source": "-", "pid": None, "span_id": None,
                "start": gap_start, "end": cursor,
                "duration_ms": round((cursor - gap_start) * 1e3, 3),
                "span_duration_ms": None, "self_time_ms": None,
                "unfinished": False,
            })
            cursor = gap_start
    chain.reverse()
    return chain


def analyze(records: Iterable[dict], episode: str = "auto") -> dict:
    """The full document (schema ``tpu-critpath-1``): every detected
    episode's milestone segments + dominant chain; when the stream holds no
    restart episode (or ``episode='window'``), one whole-window chain."""
    recs = [
        r for r in records
        if isinstance(r.get("ts"), (int, float)) and isinstance(r.get("kind"), str)
    ]
    recs.sort(key=lambda r: r["ts"])
    spans = collect_spans(recs)
    doc: dict = {"schema": SCHEMA, "episodes": []}
    if not recs:
        return doc
    lo, hi = recs[0]["ts"], recs[-1]["ts"]
    doc["window"] = [lo, hi]
    episodes = find_restart_episodes(recs) if episode in ("auto", "restart") else []
    if episode == "restart" and not episodes:
        return doc
    if not episodes:
        episodes = [{
            "kind": "window", "t_fault": lo, "t_end": hi,
            "total_ms": round((hi - lo) * 1e3, 3), "segments": [],
        }]
    for ep in episodes:
        start, end = ep["t_fault"], ep["t_end"]
        if end > start:
            ep["chain"] = dominant_chain(spans, start, end)
        else:
            ep["chain"] = []
        doc["episodes"].append(ep)
    return doc


def critical_span_ids(doc: dict) -> set[str]:
    """Every span id on any episode's dominant chain — what
    ``trace_export`` highlights."""
    out: set[str] = set()
    for ep in doc.get("episodes") or []:
        for seg in ep.get("chain") or []:
            if seg.get("span_id"):
                out.add(seg["span_id"])
    return out


def render(doc: dict, out=None) -> None:
    out = sys.stdout if out is None else out
    episodes = doc.get("episodes") or []
    if not episodes:
        print("no episodes found", file=out)
        return
    for i, ep in enumerate(episodes):
        head = f"{ep.get('kind', '?')} episode {i}: total {ep.get('total_ms', 0):.1f} ms"
        extras = []
        if ep.get("fast_path"):
            extras.append("fast-path rendezvous")
        if ep.get("promoted"):
            extras.append("warm-spare promotion")
        if extras:
            head += f" ({', '.join(extras)})"
        print(head, file=out)
        segments = ep.get("segments") or []
        total = ep.get("total_ms") or 0.0
        if segments:
            print("  segments:", file=out)
            for s in segments:
                share = 100.0 * s["duration_ms"] / total if total else 0.0
                print(
                    f"    {s['name']:<18} {s['duration_ms']:>10.1f} ms "
                    f"{share:5.1f}%",
                    file=out,
                )
        chain = ep.get("chain") or []
        if chain:
            print("  critical path (dominant chain):", file=out)
            for seg in chain:
                label = f"[{seg['source']}] {seg['span']}"
                line = f"    {label:<38} {seg['duration_ms']:>10.1f} ms"
                if seg.get("self_time_ms") is not None:
                    line += f"  (self {seg['self_time_ms']:.1f} ms)"
                if seg.get("unfinished"):
                    line += "  UNFINISHED"
                print(line, file=out)


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Critical-path analysis of restart/save/reshard episodes "
        "in a tpu-resiliency events JSONL: milestone decomposition + the "
        "dominant span chain, with optional highlighted Chrome-trace export"
    )
    ap.add_argument("events_file")
    ap.add_argument(
        "--episode", choices=("auto", "restart", "window"), default="auto",
        help="auto: restart episodes when present, else the whole window; "
        "restart: restart episodes only (exit 1 when none); window: one "
        "chain over the whole stream",
    )
    ap.add_argument(
        "--format", choices=("table", "json"), default="table",
    )
    ap.add_argument(
        "--trace", default=None, metavar="OUT",
        help="also write a Chrome trace with the critical-path spans "
        "highlighted (distinct color + critical_path arg; load in "
        "ui.perfetto.dev)",
    )
    ap.add_argument("-o", "--output", default=None)
    args = ap.parse_args(argv)
    try:
        with open(args.events_file):
            pass
    except OSError as e:
        print(f"cannot read events file: {e}", file=sys.stderr)
        return 1
    records = read_events(args.events_file)
    doc = analyze(records, episode=args.episode)
    if not doc.get("episodes"):
        print("no episodes found in the stream", file=sys.stderr)
        return 1
    if args.trace:
        from tpu_resiliency.tools import trace_export

        trace = trace_export.to_chrome_trace(
            records, critical_ids=critical_span_ids(doc)
        )
        with open(args.trace, "w") as f:
            f.write(json.dumps(trace, default=repr) + "\n")
        n_crit = sum(
            1 for e in trace["traceEvents"]
            if e.get("args", {}).get("critical_path")
        )
        print(
            f"wrote {args.trace}: {n_crit} critical-path spans highlighted",
            file=sys.stderr,
        )

    def emit() -> None:
        if args.format == "json":
            json.dump(doc, sys.stdout, indent=2)
            sys.stdout.write("\n")
        else:
            render(doc)

    if args.output:
        with open(args.output, "w") as f:
            old, sys.stdout = sys.stdout, f
            try:
                emit()
            finally:
                sys.stdout = old
        print(f"wrote {args.output}")
        return 0
    if pipe_safe(emit):
        return SIGPIPE_EXIT
    return 0


if __name__ == "__main__":
    sys.exit(main())
