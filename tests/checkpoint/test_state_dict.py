"""Tensor-aware state dict, container format, and whole-tree async checkpointer.

Models the reference's checkpointing unit tests (``tests/checkpointing/unit/``): tmp-dir
round-trips, async save + finalize, structure checks — no hardware assumptions.
"""

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.checkpoint import format as ckpt_format
from tpu_resiliency.checkpoint.async_ckpt import AsyncCheckpointer
from tpu_resiliency.checkpoint.async_core import (
    AsyncCallsQueue,
    AsyncRequest,
    ThreadAsyncCaller,
)
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict, TensorPlaceholder
from tpu_resiliency.exceptions import CheckpointError


def make_tree():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4), "b": jnp.ones(4)},
        "step": 7,
        "opt": [jnp.zeros((2, 2)), {"m": jnp.full((3,), 2.5)}],
        "name": "flagship",
    }


class TestPyTreeStateDict:
    def test_pop_insert_roundtrip(self):
        tree = make_tree()
        sd = PyTreeStateDict(tree)
        tensors = sd.pop_tensors()
        assert sd.is_hollow
        assert len(tensors) == 4
        # Hollow skeleton is picklable and contains placeholders.
        blob = pickle.dumps(sd.hollow_tree)
        hollow = pickle.loads(blob)
        leaves = jax.tree_util.tree_leaves(
            hollow, is_leaf=lambda x: isinstance(x, TensorPlaceholder)
        )
        assert sum(isinstance(leaf, TensorPlaceholder) for leaf in leaves) == 4
        sd.insert_tensors(tensors)
        assert not sd.is_hollow
        restored = sd.tree
        np.testing.assert_array_equal(restored["params"]["w"], tree["params"]["w"])
        assert restored["step"] == 7 and restored["name"] == "flagship"

    def test_host_copy_and_device_restore(self):
        sd = PyTreeStateDict(make_tree())
        sd.pop_tensors()
        sd.copy_tensors_to_host()
        assert all(isinstance(t, np.ndarray) for t in sd.tensors())
        sd.restore_tensor_device()
        assert all(isinstance(t, jax.Array) for t in sd.tensors())
        sd.insert_tensors(sd.tensors())
        np.testing.assert_array_equal(
            np.asarray(sd.tree["params"]["b"]), np.ones(4, dtype=np.float32)
        )

    def test_double_pop_raises(self):
        sd = PyTreeStateDict(make_tree())
        sd.pop_tensors()
        with pytest.raises(CheckpointError):
            sd.pop_tensors()

    def test_insert_wrong_count(self):
        sd = PyTreeStateDict(make_tree())
        sd.pop_tensors()
        with pytest.raises(CheckpointError):
            sd.insert_tensors([np.zeros(1)])

    def test_non_array_leaves_preserved(self):
        sd = PyTreeStateDict({"a": 1, "b": "x", "c": None})
        assert sd.pop_tensors() == []
        sd.insert_tensors([])
        assert sd.tree == {"a": 1, "b": "x", "c": None}


class TestContainerFormat:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "c.ckpt")
        arrays = [np.arange(6, dtype=np.float64).reshape(2, 3), np.ones(3, np.int32)]
        ckpt_format.write_payload(path, b"hollow", arrays, meta={"it": 3})
        hollow, tensors, meta = ckpt_format.read_payload(path)
        assert hollow == b"hollow" and meta == {"it": 3}
        np.testing.assert_array_equal(tensors[0], arrays[0])
        np.testing.assert_array_equal(tensors[1], arrays[1])
        assert not os.path.exists(path + ckpt_format.DIRTY_SUFFIX)

    def test_bytes_roundtrip(self):
        arrays = [np.random.default_rng(0).standard_normal((4, 4)).astype(np.float32)]
        blob = ckpt_format.serialize_to_bytes(b"h", arrays, meta={"k": 1})
        hollow, tensors, meta = ckpt_format.deserialize_from_bytes(blob)
        assert hollow == b"h" and meta == {"k": 1}
        np.testing.assert_array_equal(tensors[0], arrays[0])

    def test_bad_magic(self, tmp_path):
        path = str(tmp_path / "bad.ckpt")
        with open(path, "wb") as f:
            f.write(b"NOTMAGIC" + b"\0" * 32)
        with pytest.raises(CheckpointError):
            ckpt_format.read_payload(path)

    def test_bfloat16_roundtrip(self, tmp_path):
        path = str(tmp_path / "bf16.ckpt")
        arr = jnp.astype(jnp.arange(8), jnp.bfloat16)
        ckpt_format.write_payload(path, b"", [np.asarray(arr)])
        _, tensors, _ = ckpt_format.read_payload(path)
        np.testing.assert_array_equal(
            np.asarray(tensors[0], np.float32), np.arange(8, dtype=np.float32)
        )


class TestAsyncCore:
    def test_thread_caller_runs(self, tmp_path):
        marker = tmp_path / "done"
        caller = ThreadAsyncCaller()
        caller.schedule(AsyncRequest(async_fn=lambda: marker.write_text("ok")))
        assert caller.wait(10.0)
        caller.raise_if_failed()
        assert marker.read_text() == "ok"

    def test_thread_caller_error_surfaces(self):
        caller = ThreadAsyncCaller()

        def boom():
            raise RuntimeError("disk full")

        caller.schedule(AsyncRequest(async_fn=boom))
        caller.wait(10.0)
        with pytest.raises(CheckpointError, match="disk full"):
            caller.raise_if_failed()

    def test_queue_fifo_finalize(self):
        order = []
        q = AsyncCallsQueue(caller="thread")
        for i in range(3):
            q.schedule_async_request(
                AsyncRequest(
                    async_fn=lambda: None,
                    finalize_fns=(lambda i=i: order.append(i),),
                )
            )
            q.maybe_finalize_async_calls(blocking=True)
        assert order == [0, 1, 2]
        assert q.num_unfinalized_calls == 0
        q.close()

    def test_failed_save_never_finalizes(self):
        """Regression: a failed save must be dequeued when its error is raised —
        a later poll must not run its finalize_fns as if it succeeded."""
        q = AsyncCallsQueue(caller="thread")
        finalized = []

        def boom():
            raise RuntimeError("disk full")

        q.schedule_async_request(
            AsyncRequest(async_fn=boom, finalize_fns=(lambda: finalized.append(1),))
        )
        with pytest.raises(CheckpointError):
            q.maybe_finalize_async_calls(blocking=True)
        assert q.maybe_finalize_async_calls(blocking=True) == []
        assert finalized == [] and q.num_unfinalized_calls == 0
        q.close()

    def test_preload_runs_synchronously(self):
        events = []
        q = AsyncCallsQueue(caller="thread")
        q.schedule_async_request(
            AsyncRequest(
                async_fn=lambda: events.append("async"),
                preload_fn=lambda: events.append("preload"),
            )
        )
        assert events[0] == "preload"
        q.finalize_all()
        q.close()


class TestAsyncCheckpointer:
    def test_async_save_load_roundtrip(self, tmp_path):
        path = str(tmp_path / "model.ckpt")
        tree = make_tree()
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree, path, meta={"iteration": 11})
        ckpt.finalize_all()
        loaded, meta = AsyncCheckpointer.load(path)
        assert meta["iteration"] == 11
        np.testing.assert_array_equal(
            np.asarray(loaded["params"]["w"]), np.asarray(tree["params"]["w"])
        )
        assert loaded["step"] == 7
        assert isinstance(loaded["params"]["w"], jax.Array)
        ckpt.close()

    def test_changed_scalar_leaves_are_persisted(self, tmp_path):
        """Same treedef, different non-array leaf values: both must round-trip
        (regression: a structure-keyed hollow cache wrote stale step counters)."""
        ckpt = AsyncCheckpointer()
        tree = make_tree()
        ckpt.async_save(tree, str(tmp_path / "a.ckpt"))
        ckpt.finalize_all()
        tree2 = dict(tree, step=9999)
        ckpt.async_save(tree2, str(tmp_path / "b.ckpt"))
        ckpt.finalize_all()
        assert AsyncCheckpointer.load(str(tmp_path / "a.ckpt"))[0]["step"] == 7
        assert AsyncCheckpointer.load(str(tmp_path / "b.ckpt"))[0]["step"] == 9999
        ckpt.close()

    def test_per_rank_paths(self, tmp_path):
        ckpt = AsyncCheckpointer()
        ckpt.save({"x": jnp.ones(2)}, str(tmp_path / "s.ckpt"), rank=3)
        assert os.path.exists(tmp_path / "s.r3.ckpt")
        tree, _ = AsyncCheckpointer.load(str(tmp_path / "s.ckpt"), rank=3)
        np.testing.assert_array_equal(np.asarray(tree["x"]), np.ones(2, np.float32))
        ckpt.close()


def _roundtrip(tmp_path, arrays, meta=None):
    """The one sequential writer: written, verified, read back equal, and
    byte-identical to the in-memory serialization of the same leaves."""
    path = str(tmp_path / "c.ckpt")
    written = ckpt_format.write_payload(path, b"hollow", arrays, meta=meta)
    assert written == os.path.getsize(path)
    assert ckpt_format.verify_file(path)[0] == "ok"
    with open(path, "rb") as f:
        assert f.read() == ckpt_format.serialize_to_bytes(b"hollow", arrays, meta)
    hollow, tensors, got_meta = ckpt_format.read_payload(path)
    assert hollow == b"hollow" and got_meta == (meta or {})
    assert len(tensors) == len(arrays)
    for got, want in zip(tensors, arrays):
        np.testing.assert_array_equal(got, want)


class TestSequentialWriter:
    def test_mixed_leaves_roundtrip(self, tmp_path):
        rng = np.random.default_rng(0)
        arrays = [
            np.asarray(rng.standard_normal(s), np.float32)
            for s in [(64, 64), (7,), (128, 3), (1,), (33, 5), (256,)]
        ]
        _roundtrip(tmp_path, arrays, meta={"it": 1})

    @pytest.mark.parametrize(
        "name,value",
        [("TPU_RESILIENCY_CKPT_STRIPES", "4"), ("TPU_RESILIENCY_CKPT_CHUNK", "4096")],
    )
    def test_environment_does_not_steer_the_writer(
        self, tmp_path, monkeypatch, name, value
    ):
        """PR 47 deleted both variables: with either set, the container's
        bytes are those of a clean environment (at the parent the chunk
        variable changed the manifest and the trailer's size)."""
        arrays = [np.arange(1 << 16, dtype=np.float32), np.ones((3, 5), np.int32)]
        clean = str(tmp_path / "clean.ckpt")
        ckpt_format.write_payload(clean, b"h", arrays, meta={"it": 2})
        clean_trailer = ckpt_format.trailer_size_for([a.nbytes for a in arrays])
        monkeypatch.setenv(name, value)
        path = str(tmp_path / "set.ckpt")
        ckpt_format.write_payload(path, b"h", arrays, meta={"it": 2})
        with open(path, "rb") as f, open(clean, "rb") as g:
            assert f.read() == g.read()
        assert ckpt_format.read_trailer(path)[2].chunk_size == ckpt_format.DEFAULT_CHUNK
        assert ckpt_format.trailer_size_for([a.nbytes for a in arrays]) == clean_trailer

    def test_blob_roundtrip(self, tmp_path):
        blob = np.random.default_rng(1).integers(0, 255, 3 << 20, np.uint8).tobytes()
        path = str(tmp_path / "blob.bin")
        ckpt_format.write_blob(path, blob)
        with open(path, "rb") as f:
            assert f.read() == blob
        assert not os.path.exists(path + ckpt_format.DIRTY_SUFFIX)


class TestSeparationHint:
    def test_routed_file_and_merged_load(self, tmp_path):
        tree = {
            "params": {"w": np.ones((4, 4), np.float32)},
            "opt_state": {"m": np.full((4, 4), 2.0, np.float32)},
            "step": 11,
        }
        path = str(tmp_path / "model.ckpt")
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree, path, meta={"it": 11}, separation_hint="opt_state")
        ckpt.finalize_all()
        # Two container files: main (params+step) and the routed optimizer file
        # (named by the save's pair token).
        assert (tmp_path / "model.ckpt").exists()
        assert len(list(tmp_path.glob("model.opt_state.*.ckpt"))) == 1
        main_tree, _ = AsyncCheckpointer.load(path)
        assert "opt_state" not in main_tree
        merged, meta = AsyncCheckpointer.load(path, separation_hint="opt_state")
        assert meta == {"it": 11}
        assert merged["step"] == 11
        np.testing.assert_array_equal(merged["opt_state"]["m"], tree["opt_state"]["m"])
        np.testing.assert_array_equal(merged["params"]["w"], tree["params"]["w"])

    def test_hint_requires_mapping_key(self, tmp_path):
        import pytest

        from tpu_resiliency.exceptions import CheckpointError

        ckpt = AsyncCheckpointer()
        with pytest.raises(CheckpointError):
            ckpt.async_save({"a": 1}, str(tmp_path / "x.ckpt"), separation_hint="b")


class TestDominantLeaf:
    def test_single_huge_leaf_roundtrip(self, tmp_path):
        """One leaf of several chunks dominates the payload beside a tiny one."""
        rng = np.random.default_rng(2)
        arrays = [
            np.asarray(rng.standard_normal((1 << 20,)), np.float32),  # ~4 MiB
            np.asarray([1.0], np.float32),
        ]
        _roundtrip(tmp_path, arrays)


class TestTornPairDetection:
    def test_crash_between_renames_keeps_old_pair_loadable(self, tmp_path):
        """A crash after the new hint file landed but before the main file's
        commit rename must leave the PREVIOUS generation fully loadable (the
        r4 advisor's durability finding: fixed-name hints destroyed it)."""
        path = str(tmp_path / "m.ckpt")
        tree1 = {"params": {"w": np.ones((2,), np.float32)}, "opt": {"m": np.zeros((2,), np.float32)}}
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree1, path, separation_hint="opt")
        ckpt.finalize_all()
        # Simulate the torn window: generation 2's token-named hint file exists,
        # main never committed (writer died before its rename).
        ckpt_format.write_payload(
            str(tmp_path / ("m.opt." + "ab" * 8 + ".ckpt")),
            b"h",
            [np.full((2,), 9.0, np.float32)],
            meta={"_pair_token": "ab" * 8},
        )
        merged, _ = AsyncCheckpointer.load(path, separation_hint="opt")
        np.testing.assert_array_equal(merged["opt"]["m"], tree1["opt"]["m"])

    def test_token_mismatch_refused(self, tmp_path):
        path = str(tmp_path / "m.ckpt")
        tree = {"params": {"w": np.ones((2,), np.float32)}, "opt": {"m": np.zeros((2,), np.float32)}}
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree, path, separation_hint="opt")
        ckpt.finalize_all()
        import shutil

        # Corrupt: a file at the token-named path whose internal token differs
        # (take a different save's hint file and drop it on the expected name).
        (hint_file,) = tmp_path.glob("m.opt.*.ckpt")
        ckpt.async_save(tree, str(tmp_path / "other.ckpt"), separation_hint="opt")
        ckpt.finalize_all()
        (other_hint,) = tmp_path.glob("other.opt.*.ckpt")
        shutil.copy(str(other_hint), str(hint_file))
        import pytest as _pytest

        from tpu_resiliency.exceptions import CheckpointError

        with _pytest.raises(CheckpointError, match="torn"):
            AsyncCheckpointer.load(path, separation_hint="opt")

    def test_superseded_hint_files_pruned_after_commit(self, tmp_path):
        path = str(tmp_path / "m.ckpt")
        ckpt = AsyncCheckpointer()
        for step in range(3):
            tree = {"params": {"w": np.full((2,), float(step), np.float32)},
                    "opt": {"m": np.full((2,), float(step), np.float32)}}
            ckpt.async_save(tree, path, separation_hint="opt")
            ckpt.finalize_all()
        # Only the committed generation's hint file survives cleanup.
        assert len(list(tmp_path.glob("m.opt.*.ckpt"))) == 1
        merged, _ = AsyncCheckpointer.load(path, separation_hint="opt")
        np.testing.assert_array_equal(
            merged["opt"]["m"], np.full((2,), 2.0, np.float32)
        )

    def test_overlapping_saves_to_same_path_serialize(self, tmp_path):
        """Back-to-back async saves to one path without an intervening finalize
        must serialize: they share the .dirty tmp file AND the hint-file
        cleanup (one save would prune the other's just-written hint)."""
        path = str(tmp_path / "m.ckpt")
        ckpt = AsyncCheckpointer()
        for step in range(4):
            tree = {"params": {"w": np.full((64,), float(step), np.float32)},
                    "opt": {"m": np.full((64,), float(step), np.float32)}}
            ckpt.async_save(tree, path, separation_hint="opt")
        ckpt.finalize_all()
        merged, _ = AsyncCheckpointer.load(path, separation_hint="opt")
        np.testing.assert_array_equal(
            merged["opt"]["m"], np.full((64,), 3.0, np.float32)
        )
        assert len(list(tmp_path.glob("m.opt.*.ckpt"))) == 1

    def test_glob_metachars_in_path_still_pruned(self, tmp_path):
        sweep = tmp_path / "run[1]"
        sweep.mkdir()
        path = str(sweep / "m.ckpt")
        ckpt = AsyncCheckpointer()
        for step in range(2):
            tree = {"a": {"x": np.full((2,), float(step), np.float32)},
                    "b": {"y": np.full((2,), float(step), np.float32)}}
            ckpt.async_save(tree, path, separation_hint="b")
            ckpt.finalize_all()
        assert len(list(sweep.glob("m.b.*.ckpt"))) == 1
        merged, _ = AsyncCheckpointer.load(path, separation_hint="b")
        np.testing.assert_array_equal(merged["b"]["y"], np.full((2,), 1.0, np.float32))

    def test_numpy_meta_round_trips(self, tmp_path):
        """User meta holding numpy arrays must not break the pair check
        (dict != on arrays raises ValueError; tokens alone are compared)."""
        path = str(tmp_path / "m.ckpt")
        tree = {"a": {"x": np.ones((2,), np.float32)}, "b": {"y": np.ones((2,), np.float32)}}
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree, path, meta={"rng": np.arange(4)}, separation_hint="b")
        ckpt.finalize_all()
        merged, meta = AsyncCheckpointer.load(path, separation_hint="b")
        np.testing.assert_array_equal(meta["rng"], np.arange(4))
        assert "_pair_token" not in meta

    def test_single_d2h_pair_roundtrip_strips_token(self, tmp_path):
        path = str(tmp_path / "t.ckpt")
        tree = {"a": {"x": np.arange(4, dtype=np.float32)}, "b": {"y": np.arange(3, dtype=np.float32)}, "n": 7}
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree, path, meta={"it": 2}, separation_hint="b")
        ckpt.finalize_all()
        merged, meta = AsyncCheckpointer.load(path, separation_hint="b")
        assert meta == {"it": 2}  # token stripped
        assert merged["n"] == 7
        np.testing.assert_array_equal(merged["b"]["y"], tree["b"]["y"])
        np.testing.assert_array_equal(merged["a"]["x"], tree["a"]["x"])


class TestWriterEdgeCases:
    def test_single_leaf_payload(self, tmp_path):
        """A payload that is one fused-parameter leaf of four whole chunks."""
        _roundtrip(tmp_path, [np.arange(1 << 20, dtype=np.float32)])

    def test_all_empty_leaves(self, tmp_path):
        arrays = [np.zeros((0,), np.float32), np.zeros((0,), np.int32)]
        _roundtrip(tmp_path, arrays)
        _, tensors, _ = ckpt_format.read_payload(str(tmp_path / "c.ckpt"))
        assert [t.size for t in tensors] == [0, 0]

    def test_direct_load_strips_pair_token(self, tmp_path):
        path = str(tmp_path / "d.ckpt")
        tree = {"a": {"x": np.ones((2,), np.float32)}, "b": {"y": np.ones((2,), np.float32)}}
        ckpt = AsyncCheckpointer()
        ckpt.async_save(tree, path, meta={"it": 4}, separation_hint="b")
        ckpt.finalize_all()
        # Loading either file of the pair directly keeps user meta clean.
        _, meta_main = AsyncCheckpointer.load(path)
        (hint_file,) = tmp_path.glob("d.b.*.ckpt")
        _, meta_hint = AsyncCheckpointer.load(str(hint_file))
        assert meta_main == {"it": 4} and meta_hint == {"it": 4}


# -- async snapshots vs a step that donates the saved state --------------------

def _donating_step():
    return jax.jit(lambda t: jax.tree.map(lambda x: x + 1, t), donate_argnums=(0,))


def test_undetached_snapshot_dies_with_the_donated_arrays():
    """The hazard itself: donation deletes the arrays under a snapshot that has
    not copied them out yet, whatever ``copy_to_host_async`` had enqueued."""
    tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
    sd = PyTreeStateDict(tree)
    sd.pop_tensors()
    snapshot = sd.copy_tensors_to_host_async()
    _donating_step()(tree)
    with pytest.raises(RuntimeError, match="deleted"):
        snapshot.resolve_all()


def test_detach_device_makes_the_snapshot_survive_donation():
    from tpu_resiliency.checkpoint.staging import HostStagingPool
    from tpu_resiliency.utils import events

    tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
    # Built apart from the device arrays: a numpy view of one would pin its
    # buffer and keep the CPU backend from donating it. Leaves pop sorted: b, w.
    want = [np.ones((8,), np.float32), np.arange(64, dtype=np.float32).reshape(8, 8)]
    sd = PyTreeStateDict(tree)
    sd.pop_tensors()
    snapshot = sd.copy_tensors_to_host_async(pool=HostStagingPool())
    snapshot.resolve(0)  # the background writer got this far
    seen = []
    events.add_sink(seen.append)
    try:
        assert sd.detach_device() == 1  # only the leaf still on the device
        assert sd.detach_device() == 0  # idempotent, and silent
    finally:
        events.remove_sink(seen.append)
    stalls = [e.payload for e in seen if e.kind == "ckpt_foreground_blocked"]
    assert [(p["engine"], p["leaves"]) for p in stalls] == [("detach", 1)]
    new = _donating_step()(tree)
    assert all(x.is_deleted() for x in jax.tree.leaves(tree))
    for got, ref in zip(snapshot.resolve_all(), want):
        np.testing.assert_array_equal(got, ref)
    snapshot.release()
    np.testing.assert_array_equal(np.asarray(new["b"]), 2.0)
