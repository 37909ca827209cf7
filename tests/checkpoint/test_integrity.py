"""Checkpoint integrity plane: container checksums, heads of other formats
refused, quarantine, and the load() recovery ladder (local → peer retrieve → group
fallback)."""

import concurrent.futures as cf
import os
import pickle
import struct

import numpy as np
import pytest

from tpu_resiliency.checkpoint import format as ckpt_format
from tpu_resiliency.checkpoint.comm import PeerExchange, StoreComm
from tpu_resiliency.checkpoint.local_manager import CkptID, LocalCheckpointManager
from tpu_resiliency.checkpoint.replication import CliqueReplicationStrategy
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.platform.store import CoordStore
from tpu_resiliency.utils import events


def run_ranks(world, fn, timeout=60.0):
    with cf.ThreadPoolExecutor(max_workers=world) as pool:
        futures = [pool.submit(fn, r) for r in range(world)]
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


def _arrays():
    return [np.arange(256, dtype=np.float32), np.ones((3, 5), dtype=np.int32)]


def _flip(path, offset, mask=0x10):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ mask]))


def _other_head(head, hollow=None):
    """A whole, well-formed container under a head that is not ``TPURES03``,
    as bytes: ``TPURES01`` as pre-integrity code wrote it (head, header,
    payload, nothing after), ``TPURES02`` as PR 5's code did (leaf CRCs in a
    ``TPURESCK`` trailer, no chunk manifest) — both loaded before PR 47 — and
    any other eight bytes in front of a current container."""
    arr = np.arange(16, dtype=np.float32)
    hollow = pickle.dumps({"w": 0}) if hollow is None else hollow
    if head not in (b"TPURES01", b"TPURES02"):
        blob = ckpt_format.serialize_to_bytes(hollow, [arr], meta={"iteration": 5})
        return head + blob[len(head):]
    crc = ckpt_format.crc32c(arr.tobytes())
    spec = {"shape": (16,), "dtype": "float32", "nbytes": 64}
    if head == b"TPURES02":
        spec["crc32c"] = crc
    header = pickle.dumps(
        {"hollow": hollow, "leaves": [spec], "meta": {"iteration": 5}},
        protocol=pickle.HIGHEST_PROTOCOL,
    )
    prefix = head + struct.pack("<Q", len(header)) + header
    if head == b"TPURES01":
        return prefix + arr.tobytes()
    digest = ckpt_format.crc32c(struct.pack("<I", crc), ckpt_format.crc32c(prefix))
    trailer = (
        b"TPURESCK" + ckpt_format._ALGO_TAG
        + struct.pack("<3I", 1, crc, digest)
    )
    return prefix + arr.tobytes() + trailer


OTHER_HEADS = [b"TPURES01", b"TPURES02", b"\x00\xffNOTCKP"]


class TestFormat:
    def test_roundtrip_verifies_and_header_carries_crcs(self, tmp_path):
        path = str(tmp_path / "a.ckpt")
        written = ckpt_format.write_payload(path, b"hollow", _arrays(), meta={"it": 7})
        assert written == os.path.getsize(path)
        header = ckpt_format.read_header(path)
        assert all("crc32c" in s for s in header["leaves"])
        hollow, tensors, meta = ckpt_format.read_payload(path)
        assert hollow == b"hollow" and meta == {"it": 7}
        np.testing.assert_array_equal(tensors[0], _arrays()[0])
        assert ckpt_format.verify_file(path)[0] == "ok"

    def test_payload_bitflip_detected(self, tmp_path):
        path = str(tmp_path / "a.ckpt")
        ckpt_format.write_payload(path, b"hollow", _arrays())
        _flip(path, os.path.getsize(path) - 100)  # inside the payload
        assert ckpt_format.verify_file(path)[0] == "corrupt"
        with pytest.raises(CheckpointError, match="checksum mismatch"):
            ckpt_format.read_payload(path)

    def test_header_corruption_detected(self, tmp_path):
        path = str(tmp_path / "a.ckpt")
        ckpt_format.write_payload(path, b"hollow", _arrays())
        _flip(path, len(ckpt_format.MAGIC) + 12)  # inside the header pickle
        assert ckpt_format.verify_file(path)[0] == "corrupt"
        with pytest.raises(CheckpointError):
            ckpt_format.read_payload(path)

    def test_truncation_rejected_cleanly(self, tmp_path):
        """The satellite size-truncation check: a torn file fails with a
        classified CheckpointError naming the size delta, not a pickle/struct
        leak or a silently short tree."""
        path = str(tmp_path / "a.ckpt")
        ckpt_format.write_payload(path, b"hollow", _arrays())
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) - 7)
        status, detail = ckpt_format.verify_file(path)
        assert status == "corrupt" and "size mismatch" in detail
        with pytest.raises(CheckpointError, match="size mismatch"):
            ckpt_format.read_payload(path)

    @pytest.mark.parametrize("reader", ["file", "buffer", "ranges"])
    @pytest.mark.parametrize("head", OTHER_HEADS, ids=["v1", "v2", "arbitrary"])
    def test_other_head_refused_on_every_read_path(
        self, tmp_path, sink, head, reader
    ):
        """One format: a head that is not ``TPURES03`` is a corrupt head on
        every read path — the bad-magic :class:`CheckpointError`, the
        manager's ``ckpt_quarantined`` event, no ``ckpt_unverified`` event,
        nothing loaded. (At the parent the two old heads loaded.)"""
        blob = _other_head(head)
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        path = _shard_path(tmp_path, 0, 5, 0)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(blob)
        if reader == "buffer":
            with pytest.raises(CheckpointError, match="bad magic"):
                ckpt_format.deserialize_from_buffer(memoryview(blob))
            # to the receive side it is not a container at all
            assert ckpt_format.verify_container(memoryview(blob)) is False
        else:
            with pytest.raises(CheckpointError, match="bad magic"):
                if reader == "file":
                    ckpt_format.read_payload(path)
                else:
                    ckpt_format.read_trailer(path)
            assert ckpt_format.verify_file(path)[0] == "corrupt"
            with pytest.raises(
                CheckpointError,
                match="no intact" if reader == "file" else "corrupt container",
            ):
                if reader == "file":
                    mgr.load(5)
                else:
                    mgr._read_ranges(5, 0, [(0, 0, 4)])
            stage = "local-read" if reader == "file" else "reshard-verify"
            q = [e for e in sink if e.kind == "ckpt_quarantined"]
            assert [e.payload["stage"] for e in q] == [stage]
            assert "bad magic" in q[0].payload["error"]
            assert not os.path.exists(path)  # out of the inventory
        assert not [e for e in sink if e.kind == "ckpt_unverified"]
        mgr.close()

    @pytest.mark.parametrize("reader", ["file", "buffer", "receive", "ranges"])
    def test_foreign_algorithm_loads_unverified_with_event(
        self, tmp_path, sink, reader
    ):
        """The one load that skips a CRC comparison: a container signed by a
        checksum algorithm this host lacks (a mixed fleet) loads, and says so
        with one ``ckpt_unverified`` event whose reason names the tag."""
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        mgr.save(5, PyTreeStateDict(_tree(0, 5)), is_async=False)
        path = _shard_path(tmp_path, 0, 5, 0)
        blob = bytearray(open(path, "rb").read())
        at = blob.rindex(ckpt_format.TRAILER_MAGIC_V3) + len(ckpt_format.TRAILER_MAGIC_V3)
        assert bytes(blob[at : at + 4]) == ckpt_format._ALGO_TAG
        blob[at : at + 4] = b"xx99"
        with open(path, "wb") as f:
            f.write(blob)
        if reader == "file":
            _, tensors, meta = mgr.load(5)
            assert meta["iteration"] == 5
            np.testing.assert_array_equal(np.asarray(tensors[0]), _tree(0, 5)["w"])
        elif reader == "buffer":
            _, tensors, _ = ckpt_format.deserialize_from_buffer(blob)
            np.testing.assert_array_equal(tensors[0], _tree(0, 5)["w"])
        elif reader == "receive":
            assert ckpt_format.verify_container(blob) is False
        else:
            (part,) = mgr._read_ranges(5, 0, [(0, 8, 8)])
            assert part == _tree(0, 5)["w"].tobytes()[8:16]
            assert ckpt_format.verify_file(path)[0] == "unverified"
        if reader == "ranges":
            # the range reader's verdict is verify_file's, which records none
            assert not [e for e in sink if e.kind == "ckpt_unverified"]
        else:
            (e,) = [e for e in sink if e.kind == "ckpt_unverified"]
            assert e.payload["reason"] == "algo:b'xx99'"
        assert not [e for e in sink if e.kind == "ckpt_quarantined"]
        mgr.close()

    def test_serialize_parts_carries_trailer_and_verifies(self):
        prefix, views = ckpt_format.serialize_parts(b"h", _arrays(), meta={"k": 1})
        joined = b"".join([prefix, *[bytes(v) for v in views]])
        assert ckpt_format.verify_container(joined) is True
        blob = bytearray(joined)
        blob[len(prefix) + 9] ^= 0x40
        with pytest.raises(CheckpointError, match="checksum mismatch"):
            ckpt_format.verify_container(blob)
        with pytest.raises(CheckpointError, match="checksum mismatch"):
            ckpt_format.deserialize_from_buffer(blob)

    def test_verify_container_passes_non_containers_through(self):
        assert ckpt_format.verify_container(b"raw-blob-not-a-container") is False
        assert ckpt_format.verify_container(b"") is False

    def test_streamed_container_with_checksummer_verifies(self, tmp_path):
        """The pipelined-save shape: header_prefix from specs, leaves streamed
        one at a time through a Checksummer, trailer last."""
        path = str(tmp_path / "stream.ckpt")
        arrays = _arrays()
        specs = [
            {"shape": a.shape, "dtype": a.dtype.name, "nbytes": a.nbytes}
            for a in arrays
        ]
        prefix = ckpt_format.header_prefix(b"h", specs, {"it": 5})

        def chunks():
            ck = ckpt_format.Checksummer(prefix)
            yield prefix
            for a in arrays:
                view = ckpt_format._raw_view(a)
                ck.add_leaf(view)
                yield view
            yield ck.trailer()

        written = ckpt_format.write_stream(path, chunks())
        assert written == os.path.getsize(path)
        assert ckpt_format.verify_file(path)[0] == "ok"
        hollow, tensors, meta = ckpt_format.read_payload(path)
        assert meta == {"it": 5}
        np.testing.assert_array_equal(tensors[1], arrays[1])

    def test_zero_leaf_container(self, tmp_path):
        path = str(tmp_path / "z.ckpt")
        ckpt_format.write_payload(path, b"skeleton-only", [])
        assert ckpt_format.verify_file(path)[0] == "ok"
        hollow, tensors, _ = ckpt_format.read_payload(path)
        assert hollow == b"skeleton-only" and tensors == []


def _tree(rank, it):
    return {"w": np.full((512,), rank * 10.0 + it, np.float32), "step": it}


def _mgr(make_store, tmp_path, rank, world, gen, keep=2):
    comm = StoreComm(
        make_store(), rank, list(range(world)), timeout=30.0, generation=gen
    )
    ex = PeerExchange(make_store(), rank, timeout=30.0)
    ex.start()
    strat = CliqueReplicationStrategy(
        comm, ex, replication_jump=1, replication_factor=world
    )
    mgr = LocalCheckpointManager(
        str(tmp_path), rank=rank, comm=comm, replication=strat, keep=keep
    )
    return mgr, ex


def _shard_path(tmp_path, holder, it, owner):
    return os.path.join(
        str(tmp_path), "s0", f"r{holder}", CkptID(it, owner).filename()
    )


class TestRecoveryLadder:
    def _save_two_iters(self, make_store, tmp_path, world=2):
        def body(rank):
            mgr, ex = _mgr(make_store, tmp_path, rank, world, gen=0)
            try:
                mgr.save(1, PyTreeStateDict(_tree(rank, 1)), is_async=False)
                mgr.save(2, PyTreeStateDict(_tree(rank, 2)), is_async=False)
                mgr.close()
            finally:
                ex.close()

        run_ranks(world, body, timeout=120.0)

    def test_corrupt_shard_recovers_from_peer_byte_identical(
        self, make_store, tmp_path, sink
    ):
        world = 2
        self._save_two_iters(make_store, tmp_path)
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)

        def body(rank):
            mgr, ex = _mgr(make_store, tmp_path, rank, world, gen=1)
            try:
                hollow, tensors, meta = mgr.load()
                mgr.close()
                return meta["iteration"], np.asarray(tensors[0]).copy()
            finally:
                ex.close()

        results = run_ranks(world, body, timeout=120.0)
        for rank, (it, w) in enumerate(results):
            assert it == 2
            np.testing.assert_array_equal(
                w, np.full((512,), rank * 10.0 + 2, np.float32)
            )
        # Quarantined for forensics + recovered copy re-persisted and valid.
        rdir = os.path.join(str(tmp_path), "s0", "r0")
        assert any(".corrupt" in n for n in os.listdir(rdir))
        assert ckpt_format.verify_file(_shard_path(tmp_path, 0, 2, 0))[0] == "ok"
        assert any(e.kind == "ckpt_quarantined" for e in sink)

    def test_replica_also_corrupt_falls_back_to_older_iteration(
        self, make_store, tmp_path, sink
    ):
        world = 2
        self._save_two_iters(make_store, tmp_path)
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)  # rank 0's own copy
        _flip(_shard_path(tmp_path, 1, 2, 0), 150)  # the clique mirror

        def body(rank):
            mgr, ex = _mgr(make_store, tmp_path, rank, world, gen=1)
            try:
                hollow, tensors, meta = mgr.load()
                mgr.close()
                return meta["iteration"], np.asarray(tensors[0]).copy()
            finally:
                ex.close()

        results = run_ranks(world, body, timeout=120.0)
        # ALL ranks converge on the same older iteration — the StoreComm
        # agreement round, not per-rank improvisation.
        for rank, (it, w) in enumerate(results):
            assert it == 1, f"rank {rank} resumed from {it}"
            np.testing.assert_array_equal(
                w, np.full((512,), rank * 10.0 + 1, np.float32)
            )
        assert any(e.kind == "ckpt_fallback" for e in sink)
        assert any(
            e.kind == "ckpt_integrity_failure" for e in sink
        ), "verify-on-receive never fired for the corrupt mirror"

    def test_single_rank_falls_back_locally(self, tmp_path, sink):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)
        hollow, tensors, meta = mgr.load()
        assert meta["iteration"] == 1
        np.testing.assert_array_equal(
            np.asarray(tensors[0]), np.full((512,), 1.0, np.float32)
        )
        mgr.close()

    @pytest.mark.parametrize("head", OTHER_HEADS[:2], ids=["v1", "v2"])
    def test_newest_container_of_an_old_format_falls_to_next_rung(
        self, tmp_path, sink, head
    ):
        """``find_latest`` counts names, so it offers the newest iteration;
        ``load`` then refuses its head, quarantines it and says why, and the
        ladder lands on the next rung."""
        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        with open(_shard_path(tmp_path, 0, 2, 0), "wb") as f:
            f.write(_other_head(head))
        assert mgr.find_latest() == 2
        _, tensors, meta = mgr.load()
        assert meta["iteration"] == 1
        np.testing.assert_array_equal(np.asarray(tensors[0]), _tree(0, 1)["w"])
        (q,) = [e for e in sink if e.kind == "ckpt_quarantined"]
        assert q.payload["iteration"] == 2 and q.payload["stage"] == "local-read"
        assert "bad magic" in q.payload["error"]
        (fb,) = [e for e in sink if e.kind == "ckpt_fallback"]
        assert (fb.payload["from_iteration"], fb.payload["to_iteration"]) == (2, 1)
        assert not [e for e in sink if e.kind == "ckpt_unverified"]
        assert mgr.find_latest() == 1
        mgr.close()

    def test_single_rank_all_corrupt_raises_checkpoint_error(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        _flip(_shard_path(tmp_path, 0, 1, 0), 150)
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)
        with pytest.raises(CheckpointError, match="no intact checkpoint"):
            mgr.load()
        mgr.close()

    def test_pipelined_save_produces_verifiable_container(self, tmp_path):
        """The leaf-streaming save path (thread caller, async) must emit the
        same verifiable container as the materialized path."""
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        assert mgr.pipelined
        mgr.save(4, PyTreeStateDict(_tree(0, 4)), is_async=True)
        mgr.maybe_finalize(blocking=True)
        path = _shard_path(tmp_path, 0, 4, 0)
        assert ckpt_format.verify_file(path)[0] == "ok"
        hollow, tensors, meta = mgr.load(4)
        assert meta["iteration"] == 4
        mgr.close()


class TestQuarantineHousekeeping:
    def test_cleanup_sweeps_corrupt_keeping_newest_per_id(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.close()
        rdir = os.path.join(str(tmp_path), "s0", "r0")
        base = CkptID(9, 0).filename()
        older = os.path.join(rdir, base + ".corrupt-1")
        newer = os.path.join(rdir, base + ".corrupt-2")
        other = os.path.join(rdir, CkptID(8, 0).filename() + ".corrupt-1")
        for i, p in enumerate((older, newer, other)):
            with open(p, "wb") as f:
                f.write(b"forensics")
            os.utime(p, (1000.0 + i, 1000.0 + i))
        mgr2 = LocalCheckpointManager(str(tmp_path), rank=0)
        names = set(os.listdir(rdir))
        assert os.path.basename(newer) in names
        assert os.path.basename(older) not in names
        assert os.path.basename(other) in names  # newest of ITS id
        mgr2.close()

    def test_quarantined_files_never_count_as_inventory(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)
        assert mgr.find_latest() == 2  # not yet discovered
        mgr.load()  # quarantines iter 2, falls back
        assert mgr.find_latest() == 1  # quarantine removed it from coverage
        mgr.close()


class TestUniformErrorClassification:
    def test_read_blob_missing_file_raises_checkpoint_error(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        with pytest.raises(CheckpointError, match="unreadable shard"):
            mgr._read_blob(3, 0)
        mgr.close()

    def test_read_local_shard_wraps_all_damage_as_checkpoint_error(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        path = _shard_path(tmp_path, 0, 5, 0)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(b"garbage that is not a container at all")
        with pytest.raises(CheckpointError, match="bad magic"):
            mgr._read_local_shard(5, 0)
        mgr.close()

    def test_corrupt_hollow_pickle_classified(self, tmp_path):
        """A container that verifies but whose hollow bytes are no pickle must
        fail as CheckpointError naming the path (pickle raises half a dozen
        types)."""
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        path = _shard_path(tmp_path, 0, 6, 0)
        ckpt_format.write_payload(path, b"\x80\x04corrupt-pickle", [])
        assert ckpt_format.verify_file(path)[0] == "ok"
        with pytest.raises(CheckpointError, match="corrupt hollow skeleton"):
            mgr._read_local_shard(6, 0)
        mgr.close()

    def test_out_of_range_placeholder_index_classified(self):
        from tpu_resiliency.checkpoint.state_dict import (
            PyTreeStateDict,
            TensorPlaceholder,
        )

        sd = PyTreeStateDict.__new__(PyTreeStateDict)
        sd._tree = {"w": TensorPlaceholder(shape=(4,), dtype="float32", index=7)}
        sd._hollow = True
        sd._tensors = None
        sd._shardings = None
        with pytest.raises(CheckpointError, match="out of range"):
            sd.insert_tensors([np.zeros(4, np.float32)])


class TestKeepRetention:
    def test_default_keeps_only_newest(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        assert {i.iteration for i in mgr.local_ids()} == {2}
        mgr.close()

    def test_keep_two_retains_fallback_rung(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        for it in (1, 2, 3):
            mgr.save(it, PyTreeStateDict(_tree(0, it)), is_async=False)
        assert {i.iteration for i in mgr.local_ids()} == {2, 3}
        mgr.close()


class TestCkptInfoVerify:
    def test_verify_cli_flags_corruption(self, tmp_path, capsys):
        from tpu_resiliency.tools import ckpt_info

        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        mgr.close()
        assert ckpt_info.main([str(tmp_path), "--verify"]) == 0
        out = capsys.readouterr().out
        assert "[OK" in out and "[CORRUPT" not in out and "0 corrupt" in out
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)
        assert ckpt_info.main([str(tmp_path), "--verify"]) == 1
        out = capsys.readouterr().out
        assert "[CORRUPT" in out

    def test_scan_reports_quarantined_files(self, tmp_path, capsys):
        from tpu_resiliency.tools import ckpt_info

        mgr = LocalCheckpointManager(str(tmp_path), rank=0, keep=2)
        mgr.save(1, PyTreeStateDict(_tree(0, 1)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0, 2)), is_async=False)
        _flip(_shard_path(tmp_path, 0, 2, 0), 150)
        mgr.load()  # quarantines + falls back
        mgr.close()
        assert ckpt_info.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "quarantined corrupt container" in out
