"""Test harness configuration.

Forces JAX onto the host CPU platform with 8 virtual devices so multi-chip sharding,
mesh, and collective code paths run on any machine — the JAX analogue of the reference's
Gloo-on-CPU multi-process fixtures (``tests/straggler/unit/_utils.py:42-80``).
Must run before the first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

# Subprocess-spawning tests (launcher e2e, WorkerGroup, layered restart) must be able
# to import tpu_resiliency from a fresh clone without a pip install: put the repo root
# on PYTHONPATH for every child this test session spawns.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in os.environ.get("PYTHONPATH", "").split(os.pathsep):
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (_REPO_ROOT, os.environ.get("PYTHONPATH", "")) if p
    )
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TPU_RESILIENCY_LOG_LEVEL", "WARNING")

import pytest  # noqa: E402


@pytest.fixture
def kv_server():
    from tpu_resiliency.platform.store import KVServer

    server = KVServer(host="127.0.0.1", port=0)
    yield server
    server.close()


@pytest.fixture
def coord_store(kv_server):
    from tpu_resiliency.platform.store import CoordStore

    store = CoordStore("127.0.0.1", kv_server.port, timeout=30.0)
    yield store
    store.close()


@pytest.fixture
def tmp_uds_path(tmp_path):
    # Keep UDS paths short (108-byte sun_path limit).
    return str(tmp_path / "s.sock")


@pytest.fixture
def profiler_window(tmp_path):
    """``with profiler_window() as names: ...``: a CPU profiler window around the
    block; afterwards ``names`` holds the host plane's event names in time order
    (the ``tpures/`` annotations among them)."""
    import contextlib
    import glob

    import jax
    from jax.profiler import ProfileData

    @contextlib.contextmanager
    def window():
        names: list[str] = []
        trace_dir = str(tmp_path / "profiler_window")
        jax.profiler.start_trace(trace_dir)
        try:
            yield names
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        events = [
            (ev.start_ns, ev.name)
            for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
        ]
        names.extend(name for _, name in sorted(events))

    return window


@pytest.fixture
def held_stop_trace(monkeypatch):
    """``hold = held_stop_trace(until)``: every ``jax.profiler.stop_trace`` first waits
    for ``until()`` (a ``threading.Event``'s ``wait``, a ``time.sleep``), so a profiler
    window's close is in flight for as long as the test wants; ``hold.calls`` has
    ``"start_trace"`` / ``"stop_trace"`` in the order they went through."""
    import types

    import jax

    real_stop, real_start = jax.profiler.stop_trace, jax.profiler.start_trace

    def arm(until):
        hold = types.SimpleNamespace(calls=[])

        def stop_trace():
            until()
            real_stop()
            hold.calls.append("stop_trace")

        def start_trace(*args, **kwargs):
            hold.calls.append("start_trace")
            return real_start(*args, **kwargs)

        monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
        monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
        return hold

    return arm
