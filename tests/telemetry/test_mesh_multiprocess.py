"""The north-star configuration, tested for real: 2 JAX processes × 4 CPU devices,
``Detector`` reports riding the mesh (``_generate_mesh_report`` →
``MeshTelemetry.score_local_summary``) across genuine process boundaries.

This is the one configuration the sharded telemetry path exists for: each process
contributes its own summary rows as *shards* of a global mesh array
(``jax.make_array_from_process_local_data``), cross-rank reductions run as XLA
collectives inside the compiled scoring program, and the coordination store carries
only the column-name agreement — **zero per-rank summary traffic** (asserted below
against the store's key space).

Mirrors the reference's multi-process Gloo-on-CPU scoring tests
(``tests/straggler/unit/_utils.py:42-80``) at the JAX process level.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

from tpu_resiliency.platform.store import KVServer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


CHILD = textwrap.dedent(
    """
    import json, os, sys, time

    rank = int(sys.argv[1])
    kv_port = int(sys.argv[2])
    coord_port = int(sys.argv[3])

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{coord_port}", num_processes=2, process_id=rank
    )
    assert jax.process_count() == 2

    import numpy as np
    from jax.sharding import Mesh

    from tpu_resiliency.platform.store import CoordStore
    from tpu_resiliency.telemetry.detector import Detector
    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    # One telemetry row per Detector rank: a 2-device mesh, one device per process.
    per_proc = [[d for d in jax.devices() if d.process_index == p][0] for p in range(2)]
    mesh = Mesh(np.array(per_proc), ("ranks",))
    mt = MeshTelemetry(
        mesh, "ranks", n_ranks=2, signal_names=tuple(f"c{i}" for i in range(8))
    )

    store = CoordStore("127.0.0.1", kv_port)
    Detector.initialize(
        rank=rank,
        world_size=2,
        store=store,
        gather_on_rank0=False,
        report_time_interval=3600.0,
        device_telemetry=mt,
    )

    # Rank 1 is ~4x slower in the 'step' section; both ranks also time 'io'.
    for _ in range(6):
        with Detector.detection_section("step", profile_device=False):
            time.sleep(0.02 if rank == 1 else 0.005)
        with Detector.detection_section("io", profile_device=False):
            time.sleep(0.004)

    report = Detector.generate_report()
    assert report is not None

    # The mesh path must leave the per-rank summary namespace untouched: the store
    # carried column names only (plus the registry's own bookkeeping).
    leaked = store.prefix_get("telemetry/round/")
    assert leaked == {}, f"summary gather leaked through the store: {leaked}"

    stragglers = report.identify_stragglers(perf_threshold=0.75)
    out = {
        "rank": rank,
        "perf": {str(k): v for k, v in report.perf_scores.items()},
        "by_perf": sorted(s.rank for s in stragglers.by_perf),
        "sections": list(report.section_names),
        "rel_step": report.relative_section_scores.get("sec/step"),
    }

    # Second round: the column agreement is already settled; scores must keep
    # flowing through the same compiled program (EWMA carries across reports).
    for _ in range(4):
        with Detector.detection_section("step", profile_device=False):
            time.sleep(0.02 if rank == 1 else 0.005)
    report2 = Detector.generate_report()
    assert report2 is not None
    assert store.prefix_get("telemetry/round/") == {}
    out["perf2"] = {str(k): v for k, v in report2.perf_scores.items()}

    Detector.shutdown()
    print("RESULT " + json.dumps(out), flush=True)
    """
)


def test_mesh_report_across_process_boundaries(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    kv = KVServer(host="127.0.0.1", port=0)
    coord_port = free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(r), str(kv.port), str(coord_port)],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=env,
                cwd=str(tmp_path),
            )
            for r in range(2)
        ]
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, f"child failed:\n{out}\n{err}"
            outs.append(out)
    finally:
        kv.close()
        for p in procs:
            if p.poll() is None:
                p.kill()

    results = {}
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][0]
        r = json.loads(line[len("RESULT "):])
        results[r["rank"]] = r

    for rank in (0, 1):
        r = results[rank]
        # Global visibility on every rank (the device pipeline always has the
        # global matrix): rank 1 scores clearly below rank 0 and is flagged.
        assert r["perf"]["1"] < 0.6 < r["perf"]["0"], r
        assert r["by_perf"] == [1], r
        assert r["perf2"]["1"] < r["perf2"]["0"], r
        # The globally-agreed column list drove the report.
        assert "sec/step" in r["sections"] and "sec/io" in r["sections"]
    # Both processes computed identical global scores from their own shards.
    assert results[0]["perf"] == pytest.approx(results[1]["perf"])


def test_mesh_telemetry_example_under_launcher(tmp_path):
    """The shipped product path: ``examples/mesh_telemetry_training.py`` under
    ``tpu-ft-launcher`` — the example itself asserts its report rounds made zero
    per-rank store gets and that the injected slow rank was flagged."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["TPU_RESILIENCY_LOG_LEVEL"] = "INFO"
    r = subprocess.run(
        [
            sys.executable, "-m", "tpu_resiliency.launcher.launch",
            "--nproc-per-node", "2",
            "--no-ft-monitors",
            "--rdzv-endpoint", f"127.0.0.1:{free_port()}",
            "--rdzv-last-call", "0.2",
            "--monitor-interval", "0.1",
            "--run-dir", str(tmp_path / "run"),
            os.path.join(REPO_ROOT, "examples", "mesh_telemetry_training.py"),
            "--cpu",
            "--coord-port", str(free_port()),
            "--steps", "150",
        ],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ZERO-GATHER OK" in r.stdout
    assert "flagged ranks [1]" in r.stdout
