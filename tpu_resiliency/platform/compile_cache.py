"""Persistent XLA compilation cache that survives in-job restarts.

Every restarted worker used to re-trace and re-compile its step function from
scratch — on real models that is the dominant residual cost of a warm-spare
respawn (the interpreter floor is already paid, the XLA compile is not). This
module puts an integrity sweep and an event around JAX's own persistent
compilation cache so round N+1's first step loads round N's executables
instead of recompiling. There is ONE rule for where the cache lives:

- ``$JAX_COMPILATION_CACHE_DIR`` (:data:`CACHE_DIR_ENV`, the variable jax itself
  reads when it is imported) names the directory. Where it is set — by the
  machine, the user, or an entry program — launcher and workers keep their
  cache there and nowhere else; where it is unset there is no persistent
  cache. The library never makes up a path and never sets another directory.
- ``tpu-ft-launcher --compile-cache-dir DIR`` exports the variable to its
  workers only when the environment does not already carry it (an outside
  setting wins, and is logged). The entry program ``chip_smoke.py``
  falls back to :func:`checkout_cache_dir`, one fixed git-ignored
  directory in the checkout: the path is part of the cache key's world, so a
  directory that moves between runs never hits.
- Workers apply it through :func:`apply_from_env` (called by
  ``inprocess/wrap.py`` at engine start and by
  ``platform/device.py:apply_compile_cache_env``), which records ONE
  ``compile_cache`` event per process — outcome ``hit`` (valid entries were
  waiting), ``miss`` (cold cache), or ``miss_corrupt`` (damaged entries were
  purged) — feeding ``tpu_compile_cache_total{outcome}`` and the goodput
  ledger's restart attribution.

Integrity posture (the ``ckpt`` plane's rule, applied here): a corrupt cache
entry costs a cold compile, NEVER a crash and never a wrong executable. JAX
itself degrades unreadable entries to a warning, but only at first use deep in
a compile path; the sweep here verifies entries against a CRC **manifest**
up front and deletes mismatches, so damage is detected, counted, and evented
at process start — the same posture as the checkpoint recovery ladder's
"quarantine, then recompute". Entries newer than the manifest (written after
the last manifest refresh, e.g. by a worker that was SIGKILLed) cannot be
judged and are left for JAX's own decode-failure fallback.

The manifest is refreshed by the launcher after every round (the one process
that survives worker churn) and at worker interpreter exit — both
best-effort: a missing or stale manifest only narrows detection, never
correctness.

**Which program compiled, and what it cost.** The ``compile_cache`` event is the
state of the directory at start-up, not a hit. What each compilation really did
is the ``compile`` event of the watcher below (:func:`watch`): one a program,
from JAX's own monitoring stream, with the seconds of its trace, its lowering
and its compile or load, and ``cache: hit | miss | uncached``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import zlib
from typing import Optional

from tpu_resiliency.utils.events import record as record_event
from tpu_resiliency.utils.logging import get_logger

log = get_logger(__name__)

#: the one variable that places the cache: jax's own (read at ``import jax``)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: directory name :func:`checkout_cache_dir` uses (listed in ``.gitignore``)
CHECKOUT_CACHE_NAME = ".jax_cache"

#: integrity manifest file kept inside the cache dir (never a cache entry:
#: JAX entry files end in ``-cache``)
MANIFEST_NAME = "MANIFEST.tpures.json"

#: only files with this suffix are cache entries (JAX writes ``<key>-cache``
#: payloads plus tiny ``-atime`` stamps we ignore)
_ENTRY_SUFFIX = "-cache"

#: process-level latch: the cache is applied (and its event recorded) once
_applied: Optional[dict] = None

#: JAX's monitoring names the compile watcher folds (jax 0.9.0): three duration
#: events a program, each with a ``fun_name``; the cache's two plain events and
#: its one duration between the lowering and the backend's
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"


class _CompileWatcher:
    """Folds JAX's monitoring stream into one ``compile`` event per executable.

    JAX calls a listener on the thread that compiles, in this order for one
    program: the trace durations of every function traced on the way (inner
    jitted functions and primitives first, the program's own last, each with the
    bare ``fun_name``), the lowering's duration (``jit(<fun_name>)``), the
    cache's request / hit / retrieval time where a cache is consulted, and the
    backend's duration (``jit(<fun_name>)``), which closes the program. What is
    pending on a thread is folded then: ``trace_s`` is the trace duration of the
    function of the program's own name. **Trace durations of inner functions
    that get no executable of their own are dropped, not summed:** the outer
    function's trace ran while they did and already contains them. ``backend_s``
    is the compile on a miss, and on a hit the fetch and deserialisation
    (``retrieval_s``, JAX's own reading of the fetch, rides along). JAX fires
    none of these on a cached dispatch, so nothing here is on a step's path."""

    def __init__(self) -> None:
        self._pending = threading.local()
        self._lock = threading.Lock()
        self._totals = {"requests": 0, "hits": 0, "misses": 0, "seconds": 0.0}
        self.calls = 0  # every call of either listener: a test holds a cached dispatch to none

    def _thread_state(self) -> dict:
        """What this thread's open program has told so far."""
        state = getattr(self._pending, "state", None)
        if state is None:
            state = self._pending.state = {
                "traces": {}, "lower_s": 0.0, "requested": False, "hit": False,
                "retrieval_s": None}
        return state

    def on_event(self, event: str, **_) -> None:
        self.calls += 1
        if event == _REQUEST:
            self._thread_state()["requested"] = True
        elif event == _HIT:
            self._thread_state()["hit"] = True

    def on_duration(self, event: str, seconds: float, fun_name: str = "", **_) -> None:
        self.calls += 1
        if event == _TRACE:
            self._thread_state()["traces"][fun_name] = seconds
        elif event == _LOWER:
            self._thread_state()["lower_s"] = seconds
        elif event == _RETRIEVAL:
            self._thread_state()["retrieval_s"] = seconds
        elif event == _BACKEND:
            self._close(fun_name, seconds)

    def _close(self, fun_name: str, backend_s: float) -> None:
        state = self._thread_state()
        bare = fun_name[fun_name.find("(") + 1:-1] if fun_name.endswith(")") else fun_name
        trace_s = state["traces"].get(bare, 0.0)
        lower_s = state["lower_s"]
        # JAX announces a request whenever caching is not switched off, directory
        # or none: with no directory nothing was consulted, and that is no miss.
        jax = sys.modules.get("jax")
        consulted = state["requested"] and jax and jax.config.jax_compilation_cache_dir
        cache = "hit" if state["hit"] else "miss" if consulted else "uncached"
        retrieval = {} if state["retrieval_s"] is None else {"retrieval_s": state["retrieval_s"]}
        self._pending.state = None
        with self._lock:
            totals = self._totals
            totals["requests"] += cache != "uncached"
            totals["hits"] += cache == "hit"
            totals["misses"] += cache == "miss"
            totals["seconds"] += trace_s + lower_s + backend_s
            snapshot = dict(totals)
        record_event(
            "platform", "compile", fun_name=fun_name, trace_s=trace_s, lower_s=lower_s,
            backend_s=backend_s, cache=cache, **retrieval, **snapshot,
        )

    def totals(self) -> dict:
        with self._lock:
            return dict(self._totals)


#: the process's one watcher, made by the first :func:`watch`
_watcher: Optional[_CompileWatcher] = None


def _entry_names(path: str) -> list[str]:
    try:
        return sorted(
            n for n in os.listdir(path) if n.endswith(_ENTRY_SUFFIX)
        )
    except OSError:
        return []


def _digest_file(p: str) -> tuple[int, int]:
    """(size, crc32) of a file, streamed."""
    crc = 0
    size = 0
    with open(p, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
            size += len(chunk)
    return size, crc


def scan(path: str) -> dict[str, list[int]]:
    """{entry_name: [size, crc32]} for every cache entry currently on disk."""
    out: dict[str, list[int]] = {}
    for name in _entry_names(path):
        try:
            size, crc = _digest_file(os.path.join(path, name))
        except OSError:
            continue  # racing writer/deleter: skip, never raise
        out[name] = [size, crc]
    return out


def write_manifest(path: str) -> int:
    """Atomically record the current entry digests; returns the entry count.
    Best-effort: an unwritable cache dir is a log line, not a failure."""
    entries = scan(path)
    doc = {"version": 1, "entries": entries}
    tmp = os.path.join(path, f"{MANIFEST_NAME}.tmp.{os.getpid()}")
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, os.path.join(path, MANIFEST_NAME))
    except OSError:
        log.debug("compile-cache manifest write failed", exc_info=True)
        try:
            os.unlink(tmp)
        except OSError:
            pass
    return len(entries)


def read_manifest(path: str) -> dict[str, list[int]]:
    try:
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            doc = json.load(f)
        entries = doc.get("entries")
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        return {}


def sweep(path: str) -> dict:
    """Verify manifest-covered entries; purge mismatches (truncated, bit-flipped,
    torn) so they cost a cold compile instead of a decode failure — or worse.

    Returns ``{"entries", "bytes", "purged", "unverified"}`` where ``entries``/
    ``bytes`` count the cache AFTER the purge and ``unverified`` counts entries
    newer than the manifest (left in place for JAX's own fallback).
    """
    manifest = read_manifest(path)
    purged = 0
    for name, want in sorted(manifest.items()):
        p = os.path.join(path, name)
        if not os.path.exists(p):
            continue  # evicted/cleaned: not corruption
        try:
            size, crc = _digest_file(p)
        except OSError:
            continue
        if [size, crc] != list(want):
            log.warning(
                f"compile cache entry {name} fails integrity "
                f"({size}B/crc{crc:08x} != manifest {want}); purging — "
                "this program will cold-compile"
            )
            for victim in (p, p[: -len(_ENTRY_SUFFIX)] + "-atime"):
                try:
                    os.unlink(victim)
                except OSError:
                    pass
            purged += 1
    entries = 0
    total = 0
    names = _entry_names(path)
    for name in names:
        try:
            total += os.path.getsize(os.path.join(path, name))
            entries += 1
        except OSError:
            continue
    unverified = sum(1 for n in names if n not in manifest)
    return {
        "entries": entries, "bytes": total,
        "purged": purged, "unverified": unverified,
    }


def outcome_of(stats: dict) -> str:
    """Classify a sweep for the ``compile_cache`` event / metric."""
    if stats.get("purged"):
        return "miss_corrupt"
    return "hit" if stats.get("entries") else "miss"


def watch() -> _CompileWatcher:
    """Register the compile watcher on JAX's monitoring stream, once per process
    (:func:`enable` and :func:`apply_from_env` call this, with or without a cache
    directory). From then on every program this process compiles or loads is a
    ``compile`` event: ``fun_name``, ``trace_s``, ``lower_s``, ``backend_s``,
    ``retrieval_s`` on a hit, ``cache`` = ``hit`` | ``miss`` | ``uncached`` (no
    persistent cache consulted), and the running totals of :func:`compile_totals`."""
    global _watcher
    if _watcher is None:
        import jax

        _watcher = _CompileWatcher()
        jax.monitoring.register_event_listener(_watcher.on_event)
        jax.monitoring.register_event_duration_secs_listener(_watcher.on_duration)
    return _watcher


def compile_totals() -> dict:
    """``{"requests", "hits", "misses", "seconds"}`` of this process since
    :func:`watch`: compilations that consulted the persistent cache, those it
    served, those the backend really made, and the summed seconds (trace, lowering,
    compile or load) of every program, cached or not. Zeros before any watcher."""
    if _watcher is None:
        return {"requests": 0, "hits": 0, "misses": 0, "seconds": 0.0}
    return _watcher.totals()


def checkout_cache_dir(checkout: str) -> str:
    """The one fixed cache directory of a checkout, for entry programs to export
    as :data:`CACHE_DIR_ENV` when the environment does not set it. Never made
    from ``tempfile``, a pid or the time: every run of the same checkout gets
    the same path, so the second run hits what the first compiled."""
    return os.path.join(os.path.abspath(checkout), CHECKOUT_CACHE_NAME)


def enable(path: str) -> dict:
    """Sweep ``path`` (the directory :data:`CACHE_DIR_ENV` names), lower jax's
    caching threshold, and register an exit-time manifest refresh. Returns the
    sweep stats.

    jax took the directory from the environment when it was imported; it is
    handed the same value again here only for a process that set the variable
    after its ``import jax``. No other directory is ever set. Every failure
    mode degrades to a cold compile: an unusable directory simply leaves
    caching off."""
    watch()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        log.warning(f"compile cache dir {path!r} unusable; caching disabled")
        return {"entries": 0, "bytes": 0, "purged": 0, "unverified": 0,
                "enabled": False}
    stats = sweep(path)
    stats["enabled"] = True
    import jax

    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    # Loopback/test programs compile in microseconds; without a zero
    # threshold nothing under 1 s would ever be cached and every restart
    # bench would read as a miss.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import atexit

    atexit.register(lambda: write_manifest(path))
    return stats


def apply_from_env(record: bool = True) -> Optional[dict]:
    """Sweep and announce the directory :data:`CACHE_DIR_ENV` names, once per
    process; None when unset or when already applied. On first application
    records the ``compile_cache`` event (hit / miss / miss_corrupt + entry
    count and bytes). Registers the compile watcher (:func:`watch`) whether or
    not a directory is set; with none set, only in a process that has imported
    JAX already (the library imports it into no process that may never use it:
    such an entry program calls :func:`watch` itself, after its ``import jax``)."""
    global _applied
    path = os.environ.get(CACHE_DIR_ENV, "")
    if path or "jax" in sys.modules:
        watch()
    if not path or _applied is not None:
        return None
    stats = enable(path)
    stats["outcome"] = outcome_of(stats)
    _applied = stats
    if record and stats.get("enabled"):
        record_event(
            "platform", "compile_cache",
            outcome=stats["outcome"], entries=stats["entries"],
            bytes=stats["bytes"], purged=stats["purged"],
            unverified=stats["unverified"], dir=path,
        )
    return stats


def refresh_manifest_from_env() -> None:
    """Launcher-side post-round manifest refresh: covers workers that died
    without their atexit hook (SIGKILL, OOM). Cheap — CRC of a few files."""
    path = os.environ.get(CACHE_DIR_ENV, "")
    if path and os.path.isdir(path):
        write_manifest(path)
