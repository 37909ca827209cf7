"""Persistent compilation cache: manifest integrity, the corrupt-entry →
cold-compile (never a crash) posture, env plumbing, and the compile_cache
event → tpu_compile_cache_total bridge."""

import json
import os
import subprocess
import sys

import pytest

from tpu_resiliency.platform import compile_cache
from tpu_resiliency.utils.metrics import MetricsRegistry, observe_record

JIT_SNIPPET = """
import json, os, sys, time
from tpu_resiliency.platform import device
device.apply_compile_cache_env()
import jax, jax.numpy as jnp
t0 = time.monotonic()
f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
val = float(jax.block_until_ready(f(jnp.ones((32, 32), jnp.float32))))
out = {"compile_ms": (time.monotonic() - t0) * 1e3, "val": val}
with open(sys.argv[1], "w") as fh:
    json.dump(out, fh)
"""


def _run_jit_worker(tmp_path, cache_dir, tag, extra_env=None):
    out = tmp_path / f"out_{tag}.json"
    events_file = tmp_path / f"events_{tag}.jsonl"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[compile_cache.CACHE_DIR_ENV] = str(cache_dir)
    env["TPU_RESILIENCY_EVENTS_FILE"] = str(events_file)
    env.update(extra_env or {})
    r = subprocess.run(
        [sys.executable, "-c", JIT_SNIPPET, str(out)],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    evs = [
        json.loads(ln) for ln in events_file.read_text().splitlines()
    ] if events_file.exists() else []
    cc = [e for e in evs if e.get("kind") == "compile_cache"]
    return json.loads(out.read_text()), cc


def _entries(cache_dir):
    return sorted(
        n for n in os.listdir(cache_dir) if n.endswith("-cache")
    )


def test_cold_then_warm_start_hits(tmp_path):
    cache = tmp_path / "cc"
    got0, cc0 = _run_jit_worker(tmp_path, cache, "cold")
    assert len(cc0) == 1 and cc0[0]["outcome"] == "miss", cc0
    assert _entries(cache), "no cache entries written"
    got1, cc1 = _run_jit_worker(tmp_path, cache, "warm")
    assert len(cc1) == 1 and cc1[0]["outcome"] == "hit", cc1
    assert cc1[0]["entries"] >= 1 and cc1[0]["bytes"] > 0
    assert got1["val"] == got0["val"]


def test_truncated_entry_is_purged_to_cold_compile(tmp_path):
    """The ckpt-style integrity posture: a truncated cache entry costs exactly
    one cold compile and an outcome=miss_corrupt event — never a crash."""
    cache = tmp_path / "cc"
    _run_jit_worker(tmp_path, cache, "seed")
    compile_cache.write_manifest(str(cache))
    victims = _entries(cache)
    assert victims
    for name in victims:
        p = cache / name
        with open(p, "r+b") as f:
            f.truncate(max(1, os.path.getsize(p) // 2))
    got, cc = _run_jit_worker(tmp_path, cache, "corrupt")
    assert len(cc) == 1 and cc[0]["outcome"] == "miss_corrupt", cc
    assert cc[0]["purged"] == len(victims)
    assert got["val"] == pytest.approx(got["val"])
    # The purged programs were re-compiled and re-cached.
    assert _entries(cache)


def test_sweep_leaves_unmanifested_entries_alone(tmp_path):
    cache = tmp_path / "cc"
    cache.mkdir()
    (cache / "newentry-cache").write_bytes(b"x" * 64)
    stats = compile_cache.sweep(str(cache))
    assert stats == {"entries": 1, "bytes": 64, "purged": 0, "unverified": 1}
    assert (cache / "newentry-cache").exists()


def test_manifest_roundtrip_and_mismatch_purge(tmp_path):
    cache = tmp_path / "cc"
    cache.mkdir()
    (cache / "a-cache").write_bytes(b"alpha")
    (cache / "b-cache").write_bytes(b"bravo")
    assert compile_cache.write_manifest(str(cache)) == 2
    # Flip a bit in one entry.
    (cache / "a-cache").write_bytes(b"alphA")
    stats = compile_cache.sweep(str(cache))
    assert stats["purged"] == 1
    assert not (cache / "a-cache").exists()
    assert (cache / "b-cache").exists()
    # A deleted (evicted) entry is NOT corruption.
    os.unlink(cache / "b-cache")
    compile_cache.write_manifest(str(cache))
    assert compile_cache.sweep(str(cache))["purged"] == 0


def test_corrupt_manifest_is_tolerated(tmp_path):
    cache = tmp_path / "cc"
    cache.mkdir()
    (cache / compile_cache.MANIFEST_NAME).write_text("{not json")
    (cache / "a-cache").write_bytes(b"alpha")
    stats = compile_cache.sweep(str(cache))
    assert stats["purged"] == 0 and stats["entries"] == 1


def test_observe_record_maps_compile_cache_events():
    reg = MetricsRegistry()
    observe_record(
        {"kind": "compile_cache", "outcome": "hit", "bytes": 4096}, reg
    )
    observe_record(
        {"kind": "compile_cache", "outcome": "miss_corrupt", "bytes": 0}, reg
    )
    snap = reg.snapshot()["metrics"]
    outcomes = {
        e["labels"]["outcome"]: e["value"]
        for e in snap["tpu_compile_cache_total"]
    }
    assert outcomes == {"hit": 1.0, "miss_corrupt": 1.0}
    assert snap["tpu_compile_cache_bytes"][0]["value"] == 0.0


def test_outcome_classification():
    assert compile_cache.outcome_of({"entries": 0, "purged": 0}) == "miss"
    assert compile_cache.outcome_of({"entries": 3, "purged": 0}) == "hit"
    assert compile_cache.outcome_of({"entries": 3, "purged": 1}) == "miss_corrupt"


# -- the placement rule: $JAX_COMPILATION_CACHE_DIR, set from outside, wins ----

LAUNCHED_WORKER = """
import json, os, sys
from tpu_resiliency.platform import device
device.apply_compile_cache_env()
import jax, jax.numpy as jnp
jax.block_until_ready(jax.jit(lambda x: jnp.tanh(x @ x.T).sum())(jnp.ones((16, 16))))
with open(sys.argv[1], "w") as fh:
    json.dump({"env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
               "jax": jax.config.jax_compilation_cache_dir}, fh)
"""


@pytest.mark.parametrize("outside_set", [True, False], ids=["outside-wins", "flag-fills"])
def test_launcher_and_worker_follow_the_one_variable(tmp_path, outside_set):
    """Set outside: launcher and worker keep the cache there and the flag's
    directory is never created. Unset: the flag supplies the variable."""
    worker = tmp_path / "worker.py"
    worker.write_text(LAUNCHED_WORKER)
    outside, flag = tmp_path / "outside", tmp_path / "flag"
    events, out = tmp_path / "events.jsonl", tmp_path / "out.json"
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["TPU_RESILIENCY_LOG_LEVEL"] = "INFO"
    if outside_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(outside)
    r = subprocess.run(
        [
            sys.executable, "-m", "tpu_resiliency.launcher.launch",
            "--standalone", "--nproc-per-node", "1", "--max-restarts", "0",
            "--no-ft-monitors", "--events-file", str(events),
            "--compile-cache-dir", str(flag),
            "--run-dir", str(tmp_path / "run"), str(worker), str(out),
        ],
        env=env, capture_output=True, text=True, timeout=240, cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    used, unused = (outside, flag) if outside_set else (flag, outside)
    got = json.loads(out.read_text())
    assert got == {"env": str(used), "jax": str(used)}
    cc = [
        json.loads(ln) for ln in events.read_text().splitlines()
        if '"compile_cache"' in ln
    ]
    assert [e["dir"] for e in cc] == [str(used)], cc
    assert _entries(used) and not unused.exists()
    assert ("is set outside and wins" in r.stderr) == outside_set


def test_unset_entry_programs_share_one_fixed_checkout_path(tmp_path):
    """Two processes derive the same in-checkout directory, it is git-ignored,
    and the library itself leaves caching off while the variable is unset."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    snippet = (
        "from tpu_resiliency.platform import compile_cache as cc;"
        f"print(cc.checkout_cache_dir({repo!r}));"
        "print(cc.apply_from_env())"
    )
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outs = [
        subprocess.run(
            [sys.executable, "-c", snippet], env=env, capture_output=True,
            text=True, timeout=60, cwd=str(tmp_path), check=True,
        ).stdout.split()
        for _ in range(2)
    ]
    assert outs[0] == outs[1] == [os.path.join(repo, ".jax_cache"), "None"]
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- the compile watcher: one ``compile`` event per executable -----------------

WATCH_SNIPPET = """
import json, sys
from tpu_resiliency.platform import compile_cache, device
import jax, jax.numpy as jnp
device.apply_compile_cache_env()      # registers the watcher, directory or none
device.apply_compile_cache_env()      # ... once
inner = jax.jit(lambda x: jnp.sin(x) * 2)
def train_step(x):
    return (inner(x) + jnp.tanh(x @ x.T)).sum()
step = jax.jit(train_step)
x = jnp.ones((32, 32), jnp.float32)
jax.block_until_ready(step(x))
calls = compile_cache._watcher.calls
for _ in range(100):
    out = step(x)
jax.block_until_ready(out)
cached_dispatch_calls = compile_cache._watcher.calls - calls
jax.clear_caches()
jax.block_until_ready(step(x))
from jax._src import monitoring
listeners = sum(getattr(cb, "__self__", None) is compile_cache._watcher
                for cb in monitoring.get_event_listeners()
                + monitoring.get_event_duration_listeners())
with open(sys.argv[1], "w") as fh:
    json.dump({"cached_dispatch_calls": cached_dispatch_calls, "listeners": listeners,
               "totals": compile_cache.compile_totals()}, fh)
"""


def _run_watch_worker(tmp_path, tag, cache_dir):
    out = tmp_path / f"watch_{tag}.json"
    events_file = tmp_path / f"watch_events_{tag}.jsonl"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop(compile_cache.CACHE_DIR_ENV, None)
    if cache_dir is not None:
        env[compile_cache.CACHE_DIR_ENV] = str(cache_dir)
    env["TPU_RESILIENCY_EVENTS_FILE"] = str(events_file)
    r = subprocess.run([sys.executable, "-c", WATCH_SNIPPET, str(out)],
                       capture_output=True, text=True, timeout=180, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    evs = [json.loads(ln) for ln in events_file.read_text().splitlines()]
    return json.loads(out.read_text()), [e for e in evs if e.get("kind") == "compile"]


@pytest.fixture(scope="module")
def watched(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("watch")
    return {"cached": _run_watch_worker(tmp, "cached", tmp / "cc"),
            "uncached": _run_watch_worker(tmp, "uncached", None)}


def _steps(compiles):
    return [e for e in compiles if e["fun_name"] == "jit(train_step)"]


@pytest.mark.parametrize("mode", ["cached", "uncached"])
def test_watcher_gives_one_compile_event_a_program(watched, mode):
    facts, compiles = watched[mode]
    steps = _steps(compiles)
    assert len(steps) == 2, [e["fun_name"] for e in compiles]  # the first call, and after clear_caches
    for e in steps:
        assert e["source"] == "platform"
        assert all(isinstance(e[k], float) and e[k] >= 0 for k in ("trace_s", "lower_s", "backend_s"))
        assert e["trace_s"] > 0 and e["backend_s"] > 0
    # the inner jitted function is inlined: traced on the way, no executable, no event
    assert not any("lambda" in e["fun_name"] for e in compiles)
    assert facts["listeners"] == 2  # one watcher: JAX keeps plain and duration listeners apart


def test_second_compile_after_clear_caches_is_a_hit(watched):
    _, compiles = watched["cached"]
    first, second = _steps(compiles)
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and 0 <= second["retrieval_s"] <= second["backend_s"]


def test_without_a_directory_programs_are_uncached(watched):
    facts, compiles = watched["uncached"]
    assert compiles and {e["cache"] for e in compiles} == {"uncached"}
    assert facts["totals"]["requests"] == facts["totals"]["hits"] == facts["totals"]["misses"] == 0
    assert facts["totals"]["seconds"] > 0


@pytest.mark.parametrize("mode", ["cached", "uncached"])
def test_a_cached_dispatch_calls_no_listener(watched, mode):
    """JAX fires none of the watched events when a compiled function is called again:
    100 calls of the step, no call of the watcher."""
    facts, _ = watched[mode]
    assert facts["cached_dispatch_calls"] == 0


def test_running_totals_follow_the_events(watched):
    facts, compiles = watched["cached"]
    last = compiles[-1]
    assert {k: last[k] for k in ("requests", "hits", "misses", "seconds")} == facts["totals"]
    assert facts["totals"]["requests"] == sum(e["cache"] != "uncached" for e in compiles)
    assert facts["totals"]["hits"] == sum(e["cache"] == "hit" for e in compiles) >= 1
    assert facts["totals"]["misses"] == facts["totals"]["requests"] - facts["totals"]["hits"]
    seconds = sum(e["trace_s"] + e["lower_s"] + e["backend_s"] for e in compiles)
    assert facts["totals"]["seconds"] == pytest.approx(seconds)
    assert [e["seconds"] for e in compiles] == sorted(e["seconds"] for e in compiles)


def test_watcher_folds_in_jaxs_order_and_drops_inner_traces():
    """The fold itself, fed what JAX 0.9.0 emits for one program on one thread: inner
    functions' trace durations first (dropped: the outer trace contains them), then
    the program's own, its lowering, the cache's events, the backend's duration."""
    from tpu_resiliency.utils import events

    seen = []
    sink = lambda ev: seen.append(ev.to_record())  # noqa: E731
    events.add_sink(sink)
    try:
        w = compile_cache._CompileWatcher()
        w.on_duration(compile_cache._TRACE, 0.25, fun_name="sin")
        w.on_duration(compile_cache._TRACE, 0.5, fun_name="inner")
        w.on_duration(compile_cache._TRACE, 2.0, fun_name="train_step")
        w.on_duration(compile_cache._LOWER, 1.0, fun_name="jit(train_step)")
        w.on_event(compile_cache._REQUEST)
        w.on_event(compile_cache._HIT)
        w.on_duration(compile_cache._RETRIEVAL, 3.0)
        w.on_duration(compile_cache._BACKEND, 4.0, fun_name="jit(train_step)")
        # the next program starts clean: no cache consulted, no trace of its name
        w.on_duration(compile_cache._BACKEND, 0.5, fun_name="jit(other)")
        w.on_event("/jax/some/other/event")
        w.on_duration("/jax/some/other/duration", 9.0)
    finally:
        events.remove_sink(sink)
    first, second = [e for e in seen if e["kind"] == "compile"]
    assert (first["fun_name"], first["trace_s"], first["lower_s"], first["backend_s"],
            first["retrieval_s"], first["cache"]) == ("jit(train_step)", 2.0, 1.0, 4.0, 3.0, "hit")
    assert (second["trace_s"], second["lower_s"], second["cache"]) == (0.0, 0.0, "uncached")
    assert "retrieval_s" not in second
    assert w.totals() == {"requests": 1, "hits": 1, "misses": 0, "seconds": 7.5}
    assert w.calls == 11
