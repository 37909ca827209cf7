"""Nested debug timers: lightweight elapsed-time logging for slow paths.

Analogue of the reference's ``debug_time`` context/decorator
(``checkpointing/utils.py:35-83``), used across its checkpoint machinery: nested
scopes log at DEBUG with indentation showing the call tree, so a slow save
decomposes at a glance (serialize → replicate → write → finalize).

Every scope, root or nested, is three things at once:

- a DEBUG log line, indented by its depth;
- a ``timing`` record on the structured event stream (when a sink is attached)
  carrying ``name``, ``duration_s``, ``ok``, ``depth`` (0 for a root) and
  ``parent`` (the enclosing scope's name on this thread, ``None`` for a root),
  plus whatever the site passed as payload (``bytes``, ``leaves``, ...);
- the annotation ``tpures/<name>`` on the profiler's clock
  (``utils/tracing.py:annotate``: free with no window open, no JAX import), so
  an open ``jax.profiler`` window shows the scope beside the device's ops.

A consumer that sums ``timing`` records over a list of names on which a nested
name stands beside its root counts the roots (``depth == 0``; a record from
before ``depth`` existed is a root).

Work that one phase does in pieces interleaved with another phase's (a container
read leaf by leaf: read, verify, read, verify) is a :class:`SummedTime`: an
annotation a piece, one record a phase.

Usage::

    from tpu_resiliency.utils.timers import debug_time

    with debug_time("save"):
        with debug_time("serialize"):
            ...
        with debug_time("replicate"):
            ...

    @debug_time("finalize")
    def _finalize(...): ...
"""

from __future__ import annotations

import functools
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Optional

from tpu_resiliency.utils.events import record as record_event
from tpu_resiliency.utils.logging import get_logger
from tpu_resiliency.utils.tracing import ANNOTATION_PREFIX, annotate

log = get_logger(__name__)

_tls = threading.local()


def _open_scopes() -> list[str]:
    """Names of the scopes open on this thread, outermost first."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _record(source: str, name: str, seconds: float, depth: int,
            parent: Optional[str], failure: Optional[str], payload: dict) -> None:
    log.debug("%s%s: %.3f ms", "  " * depth, name, seconds * 1e3)
    record_event(
        source, "timing", name=name, duration_s=seconds, ok=failure is None,
        depth=depth, parent=parent,
        **({"error": failure} if failure else {}), **payload,
    )


@contextmanager
def _timed(name: str, source: str, payload: dict):
    stack = _open_scopes()
    depth, parent = len(stack), stack[-1] if stack else None
    stack.append(name)
    t0 = time.perf_counter()
    failure = None
    try:
        with annotate(ANNOTATION_PREFIX + name):
            yield
    except BaseException as e:
        # A raised block reports ok=False with the error (events.prof parity).
        failure = repr(e)
        raise
    finally:
        del stack[depth:]
        _record(source, name, time.perf_counter() - t0, depth, parent, failure, payload)


def debug_time(name: Optional[str] = None, source: str = "timer", **payload: Any):
    """Context manager when called with a name; decorator when applied to a fn.
    ``payload`` rides on the scope's ``timing`` record."""
    if callable(name):  # bare @debug_time
        fn = name
        return debug_time(fn.__name__, source)(fn)

    def as_decorator(fn: Callable):
        label = name or getattr(fn, "__name__", "block")

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with _timed(label, source, payload):
                return fn(*args, **kwargs)

        return wrapped

    class _Both:
        """Usable as ``with debug_time("x"):`` and ``@debug_time("x")``. Safe to
        share across threads: each ``with`` entry gets its own context manager
        (thread-local stack), so concurrent scopes never clobber each other."""

        def __init__(self):
            self._local = threading.local()

        def __call__(self, fn: Callable):
            return as_decorator(fn)

        def __enter__(self):
            cm = _timed(name or "block", source, payload)
            stack = getattr(self._local, "stack", None)
            if stack is None:
                stack = self._local.stack = []
            stack.append(cm)
            return cm.__enter__()

        def __exit__(self, *exc):
            return self._local.stack.pop().__exit__(*exc)

    return _Both()


class SummedTime:
    """A scope whose work comes in pieces, interleaved with another scope's:
    ``with scope.piece(i, nbytes):`` around each piece is the annotation
    ``tpures/<name>`` and adds to the sum; ``scope.close()`` (in a ``finally``)
    writes ONE ``timing`` record with the pieces' summed ``duration_s``,
    ``bytes``, ``leaves`` and the slowest piece (``slowest_leaf``,
    ``slowest_leaf_bytes``, ``slowest_leaf_s``), under the scope that was open
    when it was made (``depth`` / ``parent``). A piece that raises ends the
    record with ``ok=False``."""

    def __init__(self, name: str, source: str = "timer"):
        self.name, self.source = name, source
        stack = _open_scopes()
        self.depth, self.parent = len(stack), stack[-1] if stack else None
        self.seconds = 0.0
        self.pieces = self.bytes = 0
        self.slowest: Optional[tuple[float, int, int]] = None  # seconds, index, bytes
        self.failure: Optional[str] = None

    @contextmanager
    def piece(self, index: int, nbytes: int):
        """Time one piece (leaf ``index`` of ``nbytes`` bytes) of the phase."""
        t0 = time.perf_counter()
        try:
            with annotate(ANNOTATION_PREFIX + self.name):
                yield
        except BaseException as e:
            self.failure = repr(e)
            raise
        finally:
            seconds = time.perf_counter() - t0
            self.seconds += seconds
            self.pieces += 1
            self.bytes += nbytes
            if self.slowest is None or seconds > self.slowest[0]:
                self.slowest = (seconds, index, nbytes)

    def close(self) -> None:
        """Record the phase, if any piece of it ran."""
        if self.slowest is None:
            return
        seconds, index, nbytes = self.slowest
        _record(self.source, self.name, self.seconds, self.depth, self.parent,
                self.failure, {
                    "bytes": self.bytes, "leaves": self.pieces, "slowest_leaf": index,
                    "slowest_leaf_bytes": nbytes, "slowest_leaf_s": seconds,
                })
