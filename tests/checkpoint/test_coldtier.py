"""Cold tier: spill containment, manifest-gated visibility, restore-anywhere.

Same simulated multi-rank pattern as test_local.py: N "ranks" as threads, each
with its own store client + peer exchange against one KVServer. The cold tier
under test is a FilesystemStore in tmp_path — the artifact layout and manifest
schema are backend-independent, so everything proven here holds for any
ObjectStore implementation.
"""

import concurrent.futures as cf
import json
import os

import numpy as np
import pytest

from tpu_resiliency.checkpoint import format as ckpt_format
from tpu_resiliency.checkpoint import reshard as R
from tpu_resiliency.checkpoint.coldtier import (
    ColdTier,
    FilesystemStore,
    artifact_key,
    cold_from_env,
    manifest_key,
)
from tpu_resiliency.checkpoint.comm import PeerExchange, StoreComm
from tpu_resiliency.checkpoint.local_manager import CkptID, LocalCheckpointManager
from tpu_resiliency.checkpoint.replication import CliqueReplicationStrategy
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.platform import chaos
from tpu_resiliency.platform.store import CoordStore
from tpu_resiliency.utils import events


def run_ranks(world, fn, timeout=60.0):
    """Run fn(rank) on the given ranks as threads; raise the first failure."""
    ranks = world if isinstance(world, (list, tuple)) else range(world)
    with cf.ThreadPoolExecutor(max_workers=len(list(ranks))) as pool:
        futures = [pool.submit(fn, r) for r in ranks]
        return [f.result(timeout=timeout) for f in futures]


@pytest.fixture
def make_store(kv_server):
    stores = []

    def factory():
        s = CoordStore("127.0.0.1", kv_server.port, timeout=30.0)
        stores.append(s)
        return s

    yield factory
    for s in stores:
        s.close()


@pytest.fixture
def sink():
    seen = []
    events.add_sink(seen.append)
    yield seen
    events.remove_sink(seen.append)


@pytest.fixture(autouse=True)
def _no_chaos():
    yield
    chaos.clear_plan()


def _tree(rank):
    return {"w": np.full((8,), float(rank) + 0.5, dtype=np.float32), "step": rank}


def _cold(tmp_path, rank=0, **kw):
    return ColdTier(FilesystemStore(str(tmp_path / "cold")), rank=rank, **kw)


class TestFilesystemStore:
    def test_put_get_range_stat_list_delete(self, tmp_path):
        fs = FilesystemStore(str(tmp_path))
        n = fs.put("a/b.bin", [b"hello ", b"world"])
        assert n == 11
        assert fs.get("a/b.bin") == b"hello world"
        assert fs.get_range("a/b.bin", 6, 5) == b"world"
        assert fs.stat("a/b.bin") == 11
        assert fs.list() == ["a/b.bin"]
        fs.delete("a/b.bin")
        assert fs.list() == []

    def test_rejects_traversal_keys(self, tmp_path):
        fs = FilesystemStore(str(tmp_path))
        for bad in ("/abs", "../up", "a/../../b", ""):
            with pytest.raises(ValueError):
                fs.put(bad, [b"x"])

    def test_in_flight_uploads_invisible_to_list(self, tmp_path):
        fs = FilesystemStore(str(tmp_path))
        fs.put("k.bin", [b"x"])
        # A crashed uploader's leftover temp must never surface as an object.
        with open(os.path.join(str(tmp_path), "k2.bin.upload"), "wb") as f:
            f.write(b"partial")
        assert fs.list() == ["k.bin"]


class TestSpill:
    def test_spill_via_manager_and_manifest_schema(self, tmp_path, sink):
        cold = _cold(tmp_path)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        mgr.save(3, PyTreeStateDict(_tree(0)), is_async=False)
        assert cold.flush(timeout=30.0)
        mgr.close()

        assert cold.coverage() == {3: {0}}
        doc = cold.manifest(3, 0)
        assert doc["format"] == "tpu-coldtier-1"
        assert doc["iteration"] == 3 and doc["owner"] == 0
        assert doc["keyframe"] is True
        assert doc["prefix_len"] > 0 and doc["bytes"] > doc["prefix_len"]
        for leaf in doc["leaves"]:
            assert leaf["nbytes"] >= 0 and "crc32c" in leaf
            assert "chunks" in leaf  # v3 containers carry chunk manifests
        spilled = [e for e in sink if e.kind == "coldtier_spilled"]
        assert len(spilled) == 1 and spilled[0].payload["iteration"] == 3

    def test_every_keyframe_of_every_rank_spills_undegraded(self, tmp_path, sink):
        """World x rounds: two ranks' three asynchronous saves each put six
        artifacts in the object store, none degraded, every iteration covered
        by both ranks."""
        world, rounds = 2, 3

        def rank_saves(rank):
            cold = _cold(tmp_path, rank=rank)
            # keep=rounds: retention of one would prune a container the
            # low-priority spiller has not read yet, which it skips by design.
            mgr = LocalCheckpointManager(
                str(tmp_path / "work"), rank=rank, cold=cold, keep=rounds
            )
            for it in range(1, rounds + 1):
                mgr.save(it, PyTreeStateDict(_tree(rank)), is_async=True)
                mgr.maybe_finalize(blocking=True)
            assert cold.flush(timeout=30.0)
            mgr.close()

        run_ranks(world, rank_saves)
        spilled = [e.payload for e in sink if e.kind == "coldtier_spilled"]
        assert len(spilled) == world * rounds
        assert not [e for e in sink if e.kind == "coldtier_degraded"]
        assert all(p["bytes"] > 0 for p in spilled)
        assert _cold(tmp_path).coverage() == {it: {0, 1} for it in range(1, rounds + 1)}

    def test_non_keyframe_spills_are_skipped(self, tmp_path):
        cold = _cold(tmp_path)
        assert cold.spill(5, 0, "unused", keyframe=False) is False
        assert cold.coverage() == {}

    def test_torn_upload_leaves_no_visible_manifest(self, tmp_path, sink):
        """The commit-semantics satellite: a torn artifact commit must never
        be followed by a manifest — the iteration stays invisible."""
        akey = artifact_key(0, 1, 0)
        chaos.install_plan(
            chaos.ChaosPlan.parse(f"11:cold.commit.torn-rename@peer={akey}")
        )
        cold = _cold(tmp_path, retries=2, backoff_s=0.01)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
        assert cold.flush(timeout=30.0)
        mgr.close()

        assert cold.coverage() == {}
        assert cold.store.list() == []  # no manifest, no torn artifact kept
        degraded = [e for e in sink if e.kind == "coldtier_degraded"]
        assert degraded and degraded[-1].payload["reason"] == "upload-failed"
        # The save itself still succeeded locally.
        mgr2 = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=False)
        assert mgr2.find_latest() == 1
        mgr2.close()

    def test_enospc_degrades_to_local_only(self, tmp_path, sink):
        chaos.install_plan(chaos.ChaosPlan.parse("7:cold.write.enospc"))
        cold = _cold(tmp_path, retries=2, backoff_s=0.01)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
        assert cold.flush(timeout=30.0)

        assert cold.coverage() == {}
        assert [e.payload["reason"] for e in sink if e.kind == "coldtier_degraded"] \
            == ["upload-failed"]
        # Local tier is untouched: save landed and loads.
        hollow, tensors, meta = mgr.load(1)
        np.testing.assert_array_equal(
            np.asarray(tensors[0]), _tree(0)["w"]
        )
        mgr.close()

    def test_breaker_opens_after_repeated_failures(self, tmp_path, sink):
        chaos.install_plan(chaos.ChaosPlan.parse("7:cold.write.enospc"))
        cold = _cold(
            tmp_path, retries=1, backoff_s=0.01,
            breaker_threshold=1, breaker_cooldown_s=300.0,
        )
        src = str(tmp_path / "src.ckpt")
        ckpt_format.write_blob(
            src,
            ckpt_format.serialize_to_bytes(
                b"h", [np.zeros(4, np.float32)], meta={}
            ),
        )
        cold.spill(1, 0, src)
        assert cold.flush(timeout=30.0)
        cold.spill(2, 0, src)
        assert cold.flush(timeout=30.0)
        reasons = [e.payload["reason"] for e in sink if e.kind == "coldtier_degraded"]
        assert reasons == ["upload-failed", "breaker-open"]

    def test_slow_store_never_blocks_save_foreground(self, tmp_path):
        """fg regression for the degraded path: a pathologically slow backend
        must not stretch the save call — spilling is fully asynchronous."""

        class SlowStore(FilesystemStore):
            def put(self, key, slices):
                import time as _t
                _t.sleep(2.0)
                return super().put(key, slices)

        cold = ColdTier(SlowStore(str(tmp_path / "cold")), rank=0)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        import time as _t
        t0 = _t.monotonic()
        mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
        fg = _t.monotonic() - t0
        assert fg < 1.5, f"save foreground blocked on the cold tier ({fg:.2f}s)"
        assert cold.flush(timeout=30.0)
        assert cold.coverage() == {1: {0}}
        mgr.close()

    def test_unverifiable_container_is_refused(self, tmp_path, sink):
        cold = _cold(tmp_path, retries=1)
        bad = str(tmp_path / "bad.ckpt")
        with open(bad, "wb") as f:
            f.write(b"not a container at all")
        cold.spill(1, 0, bad)
        assert cold.flush(timeout=30.0)
        assert cold.coverage() == {}
        assert any(e.kind == "coldtier_degraded" for e in sink)


class TestRestore:
    def test_fresh_workdir_restores_from_cold(self, tmp_path):
        cold = _cold(tmp_path)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        mgr.save(2, PyTreeStateDict(_tree(0)), is_async=False)
        assert cold.flush(timeout=30.0)
        mgr.close()

        mgr2 = LocalCheckpointManager(
            str(tmp_path / "fresh"), rank=0, cold=_cold(tmp_path)
        )
        assert mgr2.find_latest() == 2
        hollow, tensors, meta = mgr2.load(2)
        assert meta["iteration"] == 2
        np.testing.assert_array_equal(np.asarray(tensors[0]), _tree(0)["w"])
        mgr2.close()

    def test_corrupt_cold_artifact_fails_closed(self, tmp_path, sink):
        cold = _cold(tmp_path)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
        assert cold.flush(timeout=30.0)
        mgr.close()

        # Flip a payload byte in the archived artifact, leaving the manifest.
        doc = cold.manifest(1, 0)
        apath = os.path.join(str(tmp_path / "cold"), artifact_key(0, 1, 0))
        with open(apath, "r+b") as f:
            f.seek(doc["prefix_len"] + 2)
            b = f.read(1)
            f.seek(doc["prefix_len"] + 2)
            f.write(bytes([b[0] ^ 0x40]))

        assert cold.verify(1, 0)[0] == "corrupt"
        with pytest.raises(CheckpointError):
            cold.fetch(1, 0, str(tmp_path / "out.ckpt"))
        assert not os.path.exists(str(tmp_path / "out.ckpt"))
        with pytest.raises(CheckpointError):
            cold.fetch_ranges(1, 0, [(0, 0, 8)])
        fetches = [e for e in sink if e.kind == "coldtier_fetch"]
        assert all(e.payload["outcome"] == "corrupt" for e in fetches)

    def test_ranged_fetch_is_partial_and_byte_exact(self, tmp_path):
        cold = _cold(tmp_path)
        arr = np.arange(4096, dtype=np.float32)
        src = str(tmp_path / "src.ckpt")
        ckpt_format.write_blob(
            src, ckpt_format.serialize_to_bytes(b"h", [arr], meta={})
        )
        cold.spill(1, 0, src)
        assert cold.flush(timeout=30.0)
        got = cold.fetch_ranges(1, 0, [(0, 16, 64)])
        assert bytes(got[0]) == arr.tobytes()[16:80]


class TestColdReshard:
    GLOBAL = np.arange(48, dtype=np.float32).reshape(12, 4)

    def _layout(self, ranks):
        return R.TreeLayout(
            [("dp", len(ranks))], list(ranks),
            [R.LeafSpec(self.GLOBAL.shape, "float32", ("dp",))],
        )

    def _save_world(self, make_store, tmp_path, ranks, iterations, gen=0):
        layout = self._layout(ranks)
        root = str(tmp_path / "work")

        def body(rank):
            comm = StoreComm(
                make_store(), rank, list(ranks), timeout=30.0, generation=gen
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                cold = _cold(tmp_path, rank=rank)
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat,
                    cold=cold, keep=len(iterations),
                )
                for it in iterations:
                    tree = {
                        "w": R.slice_local([self.GLOBAL], layout, rank)[0]
                        + float(it),
                        "step": it,
                    }
                    mgr.save(
                        it, PyTreeStateDict(tree), is_async=False,
                        layout=layout,
                    )
                assert cold.flush(timeout=30.0)
                mgr.close()
            finally:
                ex.close()

        run_ranks(list(ranks), body, timeout=120.0)
        return root

    def _cold_load(self, make_store, tmp_path, ranks, gen):
        def body(rank):
            comm = StoreComm(
                make_store(), rank, list(ranks), timeout=30.0, generation=gen
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                mgr = LocalCheckpointManager(
                    str(tmp_path / "fresh"), rank=rank, comm=comm,
                    cold=_cold(tmp_path, rank=rank),
                )
                hollow, tensors, meta = mgr.load_resharded()
                mgr.close()
                return meta, [np.asarray(t).copy() for t in tensors]
            finally:
                ex.close()

        return run_ranks(list(ranks), body, timeout=120.0)

    def test_fresh_world_resumes_from_cold_on_smaller_world(
        self, make_store, tmp_path
    ):
        """The tentpole restore-anywhere path: world-3 job dies, fresh world-2
        launcher with an EMPTY workdir assembles byte-identical state from
        the cold tier alone."""
        self._save_world(make_store, tmp_path, [0, 1, 2], [4])
        out = self._cold_load(make_store, tmp_path, [0, 1], gen=1)
        tgt = self._layout([0, 1])
        for rank, (meta, tensors) in zip([0, 1], out):
            assert meta["iteration"] == 4
            want = R.slice_local([self.GLOBAL], tgt, rank)[0] + 4.0
            np.testing.assert_array_equal(tensors[0], want)

    def test_cold_bitflip_climbs_to_older_iteration(
        self, make_store, tmp_path, sink
    ):
        """Seeded corruption of the newest cold iteration: the group must
        agree to discard it and climb to the next-older covered iteration —
        corrupt bytes are never restored, and no rank diverges."""
        self._save_world(make_store, tmp_path, [0, 1, 2], [1, 2])
        colddir = str(tmp_path / "cold")
        probe = ColdTier(FilesystemStore(colddir))
        # Corrupt EVERY owner's iter-2 artifact (inside the sharded "w" leaf,
        # the one every target rank must fetch) so no alternative copy heals it.
        for owner in (0, 1, 2):
            doc = probe.manifest(2, owner)
            off = doc["prefix_len"]
            for leaf in doc["leaves"]:
                if leaf["nbytes"] == max(l["nbytes"] for l in doc["leaves"]):
                    break
                off += leaf["nbytes"]
            apath = os.path.join(colddir, artifact_key(0, 2, owner))
            with open(apath, "r+b") as f:
                f.seek(off + 2)
                b = f.read(1)
                f.seek(off + 2)
                f.write(bytes([b[0] ^ 0x01]))

        out = self._cold_load(make_store, tmp_path, [0, 1], gen=1)
        tgt = self._layout([0, 1])
        for rank, (meta, tensors) in zip([0, 1], out):
            assert meta["iteration"] == 1, "must climb below the corrupt iter"
            want = R.slice_local([self.GLOBAL], tgt, rank)[0] + 1.0
            np.testing.assert_array_equal(tensors[0], want)


class TestVersionSkew:
    @pytest.mark.parametrize("head", [b"TPURES01", b"TPURES02"], ids=["v1", "v2"])
    def test_old_format_workdir_under_a_cold_iteration(self, tmp_path, sink, head):
        """A workdir whose one local container is of an old format beside a
        cold tier holding a newer iteration: coverage counts both names, the
        cold iteration restores cleanly, and the local one is refused and
        quarantined, never loaded. (At the parent it loaded.)"""
        from tests.checkpoint.test_integrity import _other_head

        cold = _cold(tmp_path)
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=cold)
        mgr.save(2, PyTreeStateDict(_tree(0)), is_async=False)
        assert cold.flush(timeout=30.0)
        mgr.close()

        mgr2 = LocalCheckpointManager(
            str(tmp_path / "old"), rank=0, cold=_cold(tmp_path)
        )
        old_path = mgr2._path(CkptID(1, 0))
        os.makedirs(os.path.dirname(old_path), exist_ok=True)
        with open(old_path, "wb") as f:
            f.write(_other_head(head))

        assert mgr2.find_latest() == 2
        hollow, tensors, meta = mgr2.load(2)
        np.testing.assert_array_equal(np.asarray(tensors[0]), _tree(0)["w"])
        with pytest.raises(CheckpointError, match="no intact"):
            mgr2.load(1)
        (q,) = [e for e in sink if e.kind == "ckpt_quarantined"]
        assert q.payload["iteration"] == 1 and "bad magic" in q.payload["error"]
        assert not [e for e in sink if e.kind == "ckpt_unverified"]
        assert not os.path.exists(old_path)
        mgr2.close()


class TestRetention:
    def _container(self, tmp_path, name="src.ckpt"):
        src = str(tmp_path / name)
        ckpt_format.write_blob(
            src,
            ckpt_format.serialize_to_bytes(
                b"h", [np.zeros(16, np.float32)], meta={}
            ),
        )
        return src

    def test_cold_keep_prunes_oldest_with_events(self, tmp_path, sink):
        cold = _cold(tmp_path, keep=2)
        src = self._container(tmp_path)
        for it in (1, 2, 3, 4):
            cold.spill(it, 0, src)
            assert cold.flush(timeout=30.0)
        assert sorted(cold.coverage()) == [3, 4]
        pruned = sorted(
            e.payload["iteration"] for e in sink if e.kind == "coldtier_pruned"
        )
        assert pruned == [1, 2]

    def test_delta_base_is_never_orphaned(self, tmp_path):
        unlimited = _cold(tmp_path)  # no retention while seeding
        src = self._container(tmp_path)
        for it in (1, 2):
            unlimited.spill(it, 0, src)
            assert unlimited.flush(timeout=30.0)
        # Iter 3 names iter 1 as its delta base — retention with keep=1 must
        # keep {3} plus its base {1}, pruning only 2.
        cold = _cold(tmp_path, keep=1)
        cold.spill(3, 0, src, delta_base=1)
        assert cold.flush(timeout=30.0)
        assert sorted(cold.coverage()) == [1, 3]


class TestEnvWiring:
    def test_cold_from_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv("TPU_RESILIENCY_COLD_DIR", raising=False)
        assert cold_from_env() is None
        monkeypatch.setenv("TPU_RESILIENCY_COLD_DIR", str(tmp_path / "cold"))
        monkeypatch.setenv("TPU_RESILIENCY_COLD_KEEP", "5")
        cold = cold_from_env(session=1, rank=2)
        assert cold is not None and cold.keep == 5 and cold.rank == 2
        assert "cold" in cold.store.describe()

    def test_manager_defaults_to_env_cold_tier(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_RESILIENCY_COLD_DIR", str(tmp_path / "cold"))
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0)
        try:
            assert mgr.cold is not None
            mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
            assert mgr.cold.flush(timeout=30.0)
            assert mgr.cold.coverage() == {1: {0}}
        finally:
            mgr.close()

    def test_cold_false_disables(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_RESILIENCY_COLD_DIR", str(tmp_path / "cold"))
        mgr = LocalCheckpointManager(str(tmp_path / "work"), rank=0, cold=False)
        assert mgr.cold is None
        mgr.close()
