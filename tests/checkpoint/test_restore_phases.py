"""The restore's phases on the ``timing`` stream and the profiler's clock: a tiny tree
saved and restored through ``LocalCheckpointManager.load_tree`` gives ``find``, ``read``,
``verify``, ``unpickle`` and ``place`` under the root ``ckpt.local_load``, with the
payload's bytes and a sum no larger than the wall time around the call; a corrupted
leaf fails closed as before and ends ``ckpt.load.verify`` with ``ok=False``."""

import os
import time

import numpy as np
import pytest

from tpu_resiliency.checkpoint.local_manager import CkptID, LocalCheckpointManager
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.utils import events

PHASES = ("ckpt.load.find", "ckpt.load.read", "ckpt.load.verify", "ckpt.load.unpickle",
          "ckpt.load.place")


def tree():
    return {"a": np.arange(4096, dtype=np.float32), "b": np.ones((64, 33), np.int32),
            "c": np.full((7,), 2.5, np.float64), "step": 3}


PAYLOAD_BYTES = sum(v.nbytes for v in tree().values() if isinstance(v, np.ndarray))


@pytest.fixture
def timings():
    seen = []
    sink = lambda ev: seen.append(ev.to_record()) if ev.kind == "timing" else None  # noqa: E731
    events.add_sink(sink)
    yield seen
    events.remove_sink(sink)


@pytest.fixture
def saved(tmp_path):
    mgr = LocalCheckpointManager(str(tmp_path), rank=0)
    mgr.save(3, PyTreeStateDict(tree()), is_async=False)
    yield mgr, os.path.join(str(tmp_path), "s0", "r0", CkptID(3, 0).filename())
    mgr.close()


def by_name(records):
    return {r["name"]: r for r in records}


def test_load_tree_records_every_phase(saved, timings):
    mgr, _ = saved
    t0 = time.perf_counter()
    restored, meta = mgr.load_tree()
    wall = time.perf_counter() - t0
    np.testing.assert_array_equal(np.asarray(restored["a"]), tree()["a"])
    assert meta["iteration"] == 3
    got = by_name(timings)
    assert set(PHASES) | {"ckpt.local_load"} <= set(got), sorted(got)
    assert all(got[name]["ok"] and got[name]["duration_s"] >= 0 for name in PHASES)
    assert sum(got[name]["duration_s"] for name in PHASES) <= wall
    for name in ("ckpt.load.read", "ckpt.load.verify", "ckpt.load.place"):
        assert got[name]["bytes"] == PAYLOAD_BYTES and got[name]["leaves"] == 3, got[name]
    read = got["ckpt.load.read"]
    assert 0 <= read["slowest_leaf"] < 3 and read["slowest_leaf_s"] <= read["duration_s"]
    assert read["slowest_leaf_bytes"] in (4096 * 4, 64 * 33 * 4, 7 * 8)


def test_phases_nest_under_the_kept_root(saved, timings):
    """``ckpt.local_load`` is still the root the goodput ledger reads; placement
    happens outside ``load()`` and is a root of its own."""
    mgr, _ = saved
    mgr.load_tree()
    got = by_name(timings)
    assert got["ckpt.local_load"]["depth"] == 0 and got["ckpt.local_load"]["parent"] is None
    for name in ("ckpt.load.find", "ckpt.load.read", "ckpt.load.verify", "ckpt.load.unpickle"):
        assert got[name]["depth"] == 1 and got[name]["parent"] == "ckpt.local_load", got[name]
    assert got["ckpt.load.place"]["depth"] == 0
    inside = sum(got[n]["duration_s"] for n in PHASES if n != "ckpt.load.place")
    assert inside <= got["ckpt.local_load"]["duration_s"]


def test_an_asked_for_iteration_skips_the_find(saved, timings):
    mgr, _ = saved
    assert mgr.find_latest() == 3  # the caller's own ladder, as ``restore_latest`` runs it
    assert [r["depth"] for r in timings if r["name"] == "ckpt.load.find"] == [0]
    del timings[:]
    mgr.load_tree(3)
    assert "ckpt.load.find" not in by_name(timings)


def test_a_corrupted_leaf_still_fails_closed_and_ends_verify_not_ok(saved, timings):
    mgr, path = saved
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - 4096)  # inside leaf ``a`` or ``b``'s bytes
        byte = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([byte[0] ^ 0x10]))
    with pytest.raises(CheckpointError):
        mgr.load_tree()
    got = by_name(timings)
    verify = got["ckpt.load.verify"]
    assert verify["ok"] is False and "checksum mismatch" in verify["error"]
    assert verify["leaves"] >= 1 and got["ckpt.load.read"]["ok"] is True
    assert got["ckpt.local_load"]["ok"] is False
    assert "ckpt.load.place" not in got  # nothing was placed


def test_the_phases_are_annotations_on_the_profilers_clock(saved, profiler_window):
    mgr, _ = saved
    with profiler_window() as names:
        mgr.load_tree()
    ours = [n for n in names if n.startswith("tpures/ckpt.")]
    assert ours[0] == "tpures/ckpt.local_load" and ours[-1] == "tpures/ckpt.load.place"
    assert ours.count("tpures/ckpt.load.read") == 3  # an annotation a leaf
    assert ours.count("tpures/ckpt.load.verify") == 3
    assert "tpures/ckpt.load.find" in ours and "tpures/ckpt.load.unpickle" in ours
