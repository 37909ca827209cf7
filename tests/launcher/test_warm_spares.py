"""Warm-spare promotion: parked pre-imported interpreters serve restart rounds
without paying interpreter+import startup (the respawn tax the reference's
cold ``start_processes`` path pays on every round)."""

import json
import os
import subprocess
import sys
import textwrap
import time

from tpu_resiliency.launcher.park import (
    PROMOTED_ENV,
    WarmSparePool,
    spawn_spare,
)


class TestShim:
    def _spawn(self, tmp_path, preload="json"):
        return spawn_spare(str(tmp_path), 0, preload=preload)

    def _wait_warm(self, spare, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if spare.warm:
                return
            assert spare.alive, "spare died while parking"
            time.sleep(0.02)
        raise AssertionError("spare never became warm")

    def test_unpark_runs_script_with_env_argv_and_logs(self, tmp_path, monkeypatch):
        script = tmp_path / "w.py"
        out = tmp_path / "out.json"
        script.write_text(
            textwrap.dedent(
                f"""
                import json, os, sys
                print("hello-from-worker")
                with open({str(out)!r}, "w") as f:
                    json.dump({{"rank": os.environ["RANK"],
                               "promoted": os.environ.get({PROMOTED_ENV!r}),
                               "stale": os.environ.get("TPU_TEST_STALE_VAR"),
                               "argv": sys.argv[1:]}}, f)
                """
            )
        )
        # Present in the launcher env at park time but ABSENT from the round
        # env: must not leak into the promoted worker (Popen(env=...) parity).
        monkeypatch.setenv("TPU_TEST_STALE_VAR", "leaky")
        spare = self._spawn(tmp_path)
        try:
            self._wait_warm(spare)
            stdout_path = str(tmp_path / "stdout.log")
            round_env = {
                k: v for k, v in os.environ.items() if k != "TPU_TEST_STALE_VAR"
            }
            proc = spare.unpark(
                [str(script), "--flag", "v"],
                {**round_env, "RANK": "3"},
                stdout=stdout_path,
            )
            assert proc.wait(timeout=30) == 0
            got = json.loads(out.read_text())
            assert got == {
                "rank": "3", "promoted": "1", "stale": None, "argv": ["--flag", "v"],
            }
            assert "hello-from-worker" in open(stdout_path).read()
        finally:
            spare.kill()

    def test_promoted_script_is_registered_main(self, tmp_path):
        """Pickle parity: a script-level class in a promoted worker must
        resolve as __main__.<name> (runpy.run_path would leave the shim bound
        to __main__ and break pickling / multiprocessing-spawn)."""
        script = tmp_path / "w.py"
        out = tmp_path / "ok"
        script.write_text(
            textwrap.dedent(
                f"""
                import pickle, sys

                class Payload:
                    x = 41

                if __name__ == "__main__":
                    blob = pickle.dumps(Payload())
                    assert type(pickle.loads(blob)).x == 41
                    assert sys.modules["__main__"].__file__ == {str(script)!r}
                    open({str(out)!r}, "w").close()
                """
            )
        )
        spare = self._spawn(tmp_path)
        try:
            self._wait_warm(spare)
            proc = spare.unpark([str(script)], dict(os.environ))
            assert proc.wait(timeout=30) == 0
            assert out.exists()
        finally:
            spare.kill()

    def test_launcher_death_releases_parked_spare(self, tmp_path):
        """The pipe EOF tether: a launcher that dies without close() — even
        while the spare is still importing — must not leak a parked
        interpreter."""
        import tpu_resiliency

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(tpu_resiliency.__file__)))
        parent = tmp_path / "parent.py"
        parent.write_text(
            textwrap.dedent(
                f"""
                import os, sys
                sys.path.insert(0, {repo_root!r})
                from tpu_resiliency.launcher.park import spawn_spare
                s = spawn_spare({str(tmp_path / "spares")!r}, 0, preload="json")
                print(s.proc.pid, flush=True)
                os._exit(1)  # crash without any cleanup
                """
            )
        )
        r = subprocess.run(
            [sys.executable, str(parent)], capture_output=True, text=True,
            timeout=60, env=dict(os.environ), cwd=repo_root,
        )
        pid = int(r.stdout.strip())
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return  # spare exited cleanly on EOF
            time.sleep(0.1)
        os.kill(pid, 9)
        raise AssertionError(f"orphaned spare pid {pid} still parked after 30s")

    def test_unpark_module_mode_and_failure_exit(self, tmp_path):
        spare = self._spawn(tmp_path)
        try:
            self._wait_warm(spare)
            # `-m platform` prints the platform string and exits 0.
            proc = spare.unpark(["-m", "platform"], dict(os.environ))
            assert proc.wait(timeout=30) == 0
        finally:
            spare.kill()
        bad = tmp_path / "bad.py"
        bad.write_text("import sys\nsys.exit(7)\n")
        spare = self._spawn(tmp_path)
        try:
            self._wait_warm(spare)
            proc = spare.unpark([str(bad)], dict(os.environ))
            assert proc.wait(timeout=30) == 7
        finally:
            spare.kill()

    def test_acquire_never_spawns_and_replenish_tops_up(self, tmp_path, monkeypatch):
        """The promotion hot path: acquire() (even one that reaps a dead spare)
        must NEVER block on a replacement Popen — spawning is replenish()'s
        job, run off the critical path."""
        import tpu_resiliency.launcher.park as park_mod

        pool = WarmSparePool(2, str(tmp_path), preload="json")
        try:
            deadline = time.monotonic() + 30
            while pool.warm_count < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.warm_count == 2
            # One spare "dies" (warm, so it's not a startup death).
            pool._spares[0].proc.kill()
            pool._spares[0].proc.wait(timeout=10)

            def forbidden_spawn(*a, **k):
                raise AssertionError("acquire() spawned a replacement spare")

            monkeypatch.setattr(park_mod, "spawn_spare", forbidden_spawn)
            got = pool.acquire()  # would raise if it tried to spawn
            assert got is not None
            assert pool._spares == []  # reaped + promoted, nothing spawned
            got.kill()
            monkeypatch.undo()
            assert pool.replenish() == 2
            assert len(pool._spares) == 2
        finally:
            pool.close()

    def test_pool_disables_after_systematic_startup_failure(self, tmp_path):
        """Doomed preloads (typo'd module) must not respawn dying interpreters
        forever: the pool notices consecutive startup deaths and disables."""
        pool = WarmSparePool(1, str(tmp_path), preload="definitely_not_a_module")
        try:
            deadline = time.monotonic() + 60
            while pool.size > 0 and time.monotonic() < deadline:
                assert pool.acquire() is None
                pool.replenish()
                time.sleep(0.2)
            assert pool.size == 0
            assert pool.acquire() is None
            assert pool.replenish() == 0
            assert pool._spares == []
        finally:
            pool.close()

    def test_pool_acquire_replenish_cycle_and_close(self, tmp_path):
        pool = WarmSparePool(2, str(tmp_path), preload="json")
        try:
            deadline = time.monotonic() + 30
            while pool.warm_count < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.warm_count == 2
            s1 = pool.acquire()
            assert s1 is not None
            s1.kill()
            pool.replenish()
            # Replenished: back to 2 eventually.
            deadline = time.monotonic() + 30
            while pool.warm_count < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.warm_count == 2
        finally:
            pool.close()
        assert pool.warm_count == 0

    def test_pool_stats_shape_for_healthz(self, tmp_path):
        """The /healthz `warm_spares` block: size/parked/warm/deepest."""
        pool = WarmSparePool(1, str(tmp_path), preload="json")
        try:
            deadline = time.monotonic() + 30
            while pool.warm_count < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.stats() == {
                "size": 1, "parked": 1, "warm": 1, "deepest": 1,
            }
        finally:
            pool.close()
        assert pool.stats()["parked"] == 0

    def test_acquire_prefers_deepest_park_depth(self, tmp_path):
        """With a runtime-warmed and an imports-only spare both parked, the
        promotion must take the deeper one."""
        import json as json_mod

        pool = WarmSparePool(2, str(tmp_path), preload="json")
        try:
            deadline = time.monotonic() + 30
            while pool.warm_count < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.warm_count == 2
            # Simulate one spare having completed the runtime warmup phase.
            deep = pool._spares[1]
            with open(deep.ready_file + ".tmp", "w") as f:
                json_mod.dump({"pid": deep.proc.pid, "depth": 2}, f)
            os.replace(deep.ready_file + ".tmp", deep.ready_file)
            got = pool.acquire()
            assert got is deep
            assert got.park_depth == 2
            got.kill()
        finally:
            pool.close()


class TestWarmupPhase:
    """The optional park warmup phase: depth protocol, crash accounting, and
    the promotion parity contract (warmup must not leak env/sys.path drift
    into the promoted worker)."""

    def _wait_warm(self, spare, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if spare.warm:
                return
            assert spare.alive, "spare died while parking"
            time.sleep(0.02)
        raise AssertionError("spare never became warm")

    def test_ready_file_reports_park_depth(self, tmp_path):
        """imports-only parks at depth 1; a completed warmup parks at depth 2
        — the ready file is the protocol."""
        shallow = spawn_spare(str(tmp_path / "a"), 0, preload="json")
        deep = spawn_spare(
            str(tmp_path / "b"), 0, preload="json", warmup="os:getcwd"
        )
        try:
            self._wait_warm(shallow)
            self._wait_warm(deep)
            assert shallow.park_depth == 1
            assert deep.park_depth == 2
            body = json.loads(open(deep.ready_file).read())
            assert body == {"pid": deep.proc.pid, "depth": 2}
        finally:
            shallow.kill()
            deep.kill()

    def test_runtime_warmup_parks_at_depth_2(self, tmp_path):
        """The built-in platform-safe warmup (device.warm_runtime) completes
        under JAX_PLATFORMS=cpu and reports depth 2."""
        spare = spawn_spare(str(tmp_path), 0, preload="json", warmup="runtime")
        try:
            self._wait_warm(spare, timeout=120.0)
            assert spare.park_depth == 2
        finally:
            spare.kill()

    def test_warmup_crash_is_a_startup_death(self, tmp_path):
        """A warmup that raises must kill the spare BEFORE its ready file
        exists, so the pool counts a startup death (and a doomed warmup
        disables the pool) instead of promoting a half-warm interpreter."""
        spare = spawn_spare(
            str(tmp_path), 0, preload="json", warmup="definitely_not_a_module:boom"
        )
        try:
            assert spare.proc.wait(timeout=60) != 0
            assert not os.path.exists(spare.ready_file)
        finally:
            spare.kill()
        pool = WarmSparePool(
            1, str(tmp_path / "pool"), preload="json",
            warmup="definitely_not_a_module:boom",
        )
        try:
            deadline = time.monotonic() + 60
            while pool.size > 0 and time.monotonic() < deadline:
                assert pool.acquire() is None
                pool.replenish()
                time.sleep(0.2)
            assert pool.size == 0
        finally:
            pool.close()

    def test_promoted_worker_env_and_sys_path_match_cold_spawn(self, tmp_path):
        """Promotion parity THROUGH the warmup phase: a runtime-warmed spare's
        promoted worker must see byte-identical os.environ and sys.path to a
        cold `python script.py` that makes the same imports with the same round
        env (modulo the two promotion-marker vars, which exist by design) —
        what `import jax` itself writes into the environment included."""
        script = tmp_path / "dump.py"
        script.write_text(
            textwrap.dedent(
                """
                import json, os, sys
                import jax  # what the runtime warmup imported in the spare
                with open(sys.argv[1], "w") as f:
                    json.dump({"env": dict(os.environ), "path": sys.path}, f)
                """
            )
        )
        round_env = dict(os.environ)
        round_env["TPU_TEST_ROUND_VAR"] = "x"
        cold_out = tmp_path / "cold.json"
        r = subprocess.run(
            [sys.executable, str(script), str(cold_out)],
            env=round_env, timeout=60, cwd=os.getcwd(),
        )
        assert r.returncode == 0
        spare = spawn_spare(str(tmp_path), 0, preload="json", warmup="runtime")
        try:
            self._wait_warm(spare, timeout=120.0)
            warm_out = tmp_path / "warm.json"
            proc = spare.unpark([str(script), str(warm_out)], round_env)
            assert proc.wait(timeout=60) == 0
        finally:
            spare.kill()
        cold = json.loads(cold_out.read_text())
        warm = json.loads(warm_out.read_text())
        markers = {PROMOTED_ENV, "TPU_FT_WARM_SPARE_DEPTH"}
        assert {k: v for k, v in warm["env"].items() if k not in markers} == cold["env"]
        assert warm["path"] == cold["path"]

    def test_what_a_preload_writes_into_the_environment_survives_promotion(
        self, tmp_path, monkeypatch
    ):
        """`import jax` on a TPU host writes LIBTPU_INIT_ARGS and more into
        os.environ; a cold worker's own import does the same. A stand-in module
        makes that deterministic here: the promoted worker (whose import ran
        long before its environment was replaced) ends up where the cold one does."""
        (tmp_path / "envwriter.py").write_text(
            "import os\n"
            "os.environ['WRITTEN_BY_IMPORT'] = 'yes'\n"
            "os.environ['INIT_ARGS'] = os.environ.get('INIT_ARGS', '') + ' --flag'\n"
        )
        script = tmp_path / "dump.py"
        script.write_text(
            "import json, os, sys, envwriter\n"
            "json.dump(dict(os.environ), open(sys.argv[1], 'w'))\n"
        )
        round_env = dict(os.environ)
        round_env["PYTHONPATH"] = os.pathsep.join(
            [str(tmp_path), round_env.get("PYTHONPATH", "")])
        round_env["INIT_ARGS"] = "--users-own"
        cold_out, warm_out = tmp_path / "cold.json", tmp_path / "warm.json"
        subprocess.run([sys.executable, str(script), str(cold_out)],
                       env=round_env, timeout=60, check=True)
        for key in ("PYTHONPATH", "INIT_ARGS"):  # the spare inherits the launcher's
            monkeypatch.setenv(key, round_env[key])
        spare = spawn_spare(str(tmp_path), 0, preload="envwriter")
        try:
            self._wait_warm(spare, timeout=120.0)
            proc = spare.unpark([str(script), str(warm_out)], round_env)
            assert proc.wait(timeout=60) == 0
        finally:
            spare.kill()
        cold, warm = json.loads(cold_out.read_text()), json.loads(warm_out.read_text())
        assert cold["WRITTEN_BY_IMPORT"] == "yes" and cold["INIT_ARGS"] == "--users-own --flag"
        markers = {PROMOTED_ENV, "TPU_FT_WARM_SPARE_DEPTH"}
        assert {k: v for k, v in warm.items() if k not in markers} == cold

    def test_round_environ_replaces_but_keeps_what_imports_wrote(self):
        from tpu_resiliency.launcher.park import _round_environ

        park = {"KEEP": "1", "DROPPED": "x", "FLAGS": "a", "LAUNCHER_CHANGED": "old"}
        now = {**park, "FLAGS": "a --set-by-import", "WROTE": "jax",
               "LAUNCHER_CHANGED": "old --set-by-import"}
        round_env = {"KEEP": "1", "FLAGS": "a", "LAUNCHER_CHANGED": "new", "ROUND": "3"}
        assert _round_environ(round_env, park, now) == {
            "KEEP": "1", "ROUND": "3",
            "FLAGS": "a --set-by-import",  # as a cold worker's import would make it
            "WROTE": "jax",
            "LAUNCHER_CHANGED": "new",  # the launcher's newer word wins
        }  # and DROPPED is gone


def test_restart_round_promoted_from_warm_spare(tmp_path):
    """E2E through the real CLI: worker fails once, the restart round's worker
    is a PROMOTED spare (it sees $TPU_FT_WARM_SPARE), and the job succeeds."""
    script = tmp_path / "crash_once.py"
    marker = tmp_path / "crashed"
    result = tmp_path / "result.json"
    spares_dir = tmp_path / "run" / "spares"
    script.write_text(
        textwrap.dedent(
            f"""
            import glob, json, os, sys, time
            if not os.path.exists({str(marker)!r}):
                open({str(marker)!r}, "w").close()
                # Deterministic: crash only once a spare is parked-and-warm —
                # detection+rendezvous are now fast enough that an immediate
                # first-step crash can legitimately beat the spare's own
                # interpreter warm-up (the designed cold-spawn fallback).
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    ready = [p for p in
                             glob.glob(os.path.join({str(spares_dir)!r}, "ready_*"))
                             if not p.endswith(".tmp")]
                    if ready:
                        sys.exit(1)
                    time.sleep(0.05)
                sys.exit(17)  # never went warm: fail loudly, not flakily
            with open({str(result)!r}, "w") as f:
                json.dump({{"promoted": os.environ.get({PROMOTED_ENV!r}),
                           "restart": os.environ["TPU_FT_RESTART_COUNT"]}}, f)
            """
        )
    )
    env = dict(os.environ)
    env.setdefault("TPU_RESILIENCY_LOG_LEVEL", "INFO")
    events_file = tmp_path / "events.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "tpu_resiliency.launcher.launch",
         "--standalone", "--nproc-per-node", "1", "--max-restarts", "2",
         "--warm-spares", "1", "--warm-spare-preload", "json",
         "--no-ft-monitors", "--events-file", str(events_file),
         "--run-dir", str(tmp_path / "run"), str(script)],
        capture_output=True, text=True, timeout=180, env=env, cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(result.read_text())
    assert got["promoted"] == "1", (got, r.stderr[-2000:])
    assert int(got["restart"]) >= 1
    # The promotion is a first-class structured event for operators. Round 0
    # may legitimately promote too (a spare can warm before the first round on
    # a slow host) — the restart round's promotion is the one that must exist.
    promoted = [
        json.loads(ln) for ln in events_file.read_text().splitlines()
        if '"worker_promoted"' in ln
    ]
    restart_promos = [e for e in promoted if e["round"] >= 1]
    assert restart_promos, promoted
    assert restart_promos[0]["global_rank"] == 0
    assert restart_promos[0]["worker_pid"] > 0
    assert restart_promos[0]["worker_pid"] != restart_promos[0]["pid"]
