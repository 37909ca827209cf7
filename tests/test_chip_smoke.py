"""``chip_smoke.py`` rehearsed on the CPU: every phase's control flow at a tiny size
(``--tiny``), and the ways it must refuse to print a result. The chip check itself
runs on the chip; nothing here is one."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_smoke(tmp_path, *argv, devices=1, timeout=600):
    env = dict(os.environ)
    # The rehearsal writes nothing into the checkout: its compile cache goes here.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["TMPDIR"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, SMOKE, *argv], env=env, capture_output=True, text=True,
        timeout=timeout, cwd=str(tmp_path),
    )
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.startswith("{")]
    return r, {ln["phase"]: ln for ln in lines if "phase" in ln}, lines


def never_ok(lines):
    return not any(ln.get("ok") is True and "phase" not in ln for ln in lines)


def test_tiny_rehearsal_runs_every_phase_and_prints_no_result(tmp_path):
    r, phases, lines = run_smoke(tmp_path, "--tiny")
    assert r.returncode == 0, r.stderr[-4000:]
    assert never_ok(lines) and "prints no result" in r.stderr
    assert phases["setup"]["cache_dir"] == str(tmp_path / "jax_cache")
    assert phases["setup"]["cache_dir_from"] == "environment"
    assert phases["native"]["rings"] in ("native", "python")

    tel = phases["telemetry"]
    assert tel["ok"] and tel["use_pallas"] is True and tel["medians_bit_equal"]
    assert tel["f1"] >= 0.99 and tel["profiler_source"] == "host"  # the CPU's answer

    train = phases["train"]
    assert train["ok"], train["failed"]
    assert train["rounds"] == [0, 1] and train["steps"] == {"0": 22, "1": 24}
    assert train["restore"]["crc_equal"] is True and train["restore"]["step"] == 12
    assert train["report_source"] == ["mesh"] and train["reports"] >= 2
    assert train["compile_cache_by_round"][1] == "hit"
    assert train["cache_counts_by_round"]["1"]["cache_hits"] >= 1
    # the counts are the package's compile watcher's, and so is the step's own event
    assert train["step_cache_by_round"][0] == ["miss"]
    assert train["step_cache_by_round"][1] == ["hit"]
    assert train["cache_counts_by_round"]["1"]["cache_requests"] >= \
        train["cache_counts_by_round"]["1"]["cache_hits"]
    assert train["loss_first_last"][1] < train["loss_first_last"][0]
    # Round 1 is the parked spare, promoted; nobody but a worker has a backend.
    assert train["promotions"][-1] == "promoted"
    roles = {p["role"] for p in train["processes"]}
    assert {"launcher", "worker", "monitor"} <= roles
    assert all(not p["dev_fds"] for p in train["processes"])

    inproc = phases["inprocess"]
    assert inproc["ok"], inproc["failed"]
    assert inproc["chain"] == ["AbortJaxDistributed", "AbortCompilationCache", "JaxHealthCheck"]
    assert inproc["steps"] == {"0": 6, "1": 6} and inproc["restore"]["crc_equal"] is True
    assert [s["backend"] for s in inproc["abort_steps"]] == ["cpu"] * 3


def test_tiny_four_chip_path_on_virtual_devices(tmp_path):
    r, phases, lines = run_smoke(tmp_path, "--tiny", "--chips", "4", devices=4)
    assert r.returncode == 0, r.stderr[-4000:]
    assert never_ok(lines) and set(phases) == {"setup", "multichip"}  # no other phase
    m = phases["multichip"]
    assert m["ok"], m["failed"]
    assert m["device"]["count"] == 4 and m["shard_holders"] == [0, 1, 2, 3]
    assert m["mesh"] == {"dp": 2, "tp": 2}
    assert m["max_loss_diff"] <= m["loss_tolerance"]
    assert m["restored_byte_equal"] and m["same_straggler_set"] and m["score_max_diff"] <= 1e-6


def test_a_failing_phase_gives_a_non_zero_exit(tmp_path):
    """The four-chip path with one device: its phase fails, and so does the script."""
    r, phases, lines = run_smoke(tmp_path, "--tiny", "--chips", "4", devices=1)
    assert r.returncode != 0 and never_ok(lines)
    assert phases["multichip"]["ok"] is False
    assert "needs 4 devices, JAX found 1" in json.dumps(phases["multichip"]["failed"])
    assert "FAILED" in r.stderr


@pytest.mark.parametrize(
    "argv",
    [
        ["chip_smoke.py"],
        ["benchmark/run.py", "--workload", "mistral7b_steady_noprof", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
    ],
    ids=["chip_smoke.py", "benchmark/run.py"],
)
def test_without_a_chip_no_program_prints_a_result(tmp_path, argv):
    """Off a TPU the two programs that measure on the chip fail, say which platform
    they found, and print no result line — no CPU number under a device's name."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, argv[0]), *argv[1:]], env=env,
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
    )
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr and "no result" in r.stderr.lower()
    results = [
        ln for ln in r.stdout.splitlines()
        if ln.startswith("{") and {"ok", "value", "results_ms"} & set(json.loads(ln))
        and "phase" not in json.loads(ln)
    ]
    assert results == []
    assert not (tmp_path / "jax_cache").exists()  # stopped before it placed anything


def test_the_parent_never_imports_jax(tmp_path):
    """A chip belongs to one process: the parent, which outlives every phase,
    must not be able to hold it."""
    probe = (
        "import sys, runpy; sys.argv = ['chip_smoke.py', '--tiny', '--skip', 'train', "
        "'--skip', 'inprocess']\n"
        "try:\n    runpy.run_path(%r, run_name='__main__')\n"
        "except SystemExit as e:\n    code = e.code\n"
        "print('JAX_IN_PARENT', 'jax' in sys.modules, 'EXIT', code)\n" % SMOKE
    )
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run(
        [sys.executable, "-c", probe], env=env, capture_output=True, text=True,
        timeout=300, cwd=str(tmp_path),
    )
    assert "JAX_IN_PARENT False EXIT 0" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])
    assert '"phase": "telemetry", "ok": true' in r.stdout  # a phase really ran
