"""Checkpoint byte-economy plane (checkpoint/coding/): RS codec algebra,
erasure replication + the reconstruct-from-parity recovery rung, delta
checkpoint chains, the TPURES03 chunk manifest, and format-version skew
(TPURES02 containers in a TPURES03 world)."""

import concurrent.futures as cf
import itertools
import os
import pickle

import numpy as np
import pytest

from tpu_resiliency.checkpoint import format as ckpt_format
from tpu_resiliency.checkpoint.coding import (
    DeltaTracker,
    ErasureReplicationStrategy,
    apply_delta,
    encode_delta,
    is_block,
    is_delta,
    replication_from_env,
)
from tpu_resiliency.checkpoint.coding import delta as delta_mod
from tpu_resiliency.checkpoint.coding import rs
from tpu_resiliency.checkpoint.coding import strategy as coding_mod
from tpu_resiliency.checkpoint.comm import PeerExchange, StoreComm
from tpu_resiliency.checkpoint.local_manager import (
    CkptID,
    LocalCheckpointManager,
    block_filename,
)
from tpu_resiliency.checkpoint.replication import CliqueReplicationStrategy
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.platform.store import CoordStore
from tpu_resiliency.utils import events


def run_ranks(ranks, fn, timeout=90.0):
    with cf.ThreadPoolExecutor(max_workers=len(ranks)) as pool:
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


# -- RS codec -----------------------------------------------------------------


class TestRS:
    @pytest.mark.parametrize("k,m", [(1, 1), (2, 1), (3, 1), (3, 2), (7, 3)])
    def test_any_k_of_n_reconstructs(self, k, m):
        rng = np.random.default_rng(k * 100 + m)
        data = rng.integers(0, 256, 997 * k + 13, dtype=np.uint8).tobytes()
        blocks, orig = rs.split(data, k)
        coded = {i: b for i, b in enumerate(blocks)}
        coded.update({k + j: p for j, p in enumerate(rs.encode(blocks, m))})
        for drop in itertools.islice(
            itertools.combinations(range(k + m), m), 10
        ):
            have = {i: b for i, b in coded.items() if i not in drop}
            rec = rs.reconstruct(k, m, have, want=list(range(k)))
            assert bytes(rs.join([rec[i] for i in range(k)], orig)) == data

    def test_too_few_blocks_raises(self):
        data = b"x" * 100
        blocks, orig = rs.split(data, 3)
        coded = {0: blocks[0]}  # 1 of 3 required
        with pytest.raises(CheckpointError, match="cannot reconstruct"):
            rs.reconstruct(3, 1, coded)

    def test_split_join_pads_and_strips(self):
        data = b"abcdefg"  # 7 bytes over k=3 -> 3-byte blocks, 2 pad bytes
        blocks, orig = rs.split(data, 3)
        assert orig == 7 and all(b.nbytes == 3 for b in blocks)
        assert bytes(rs.join(blocks, orig)) == data


# -- block artifacts ----------------------------------------------------------


class TestBlockArtifact:
    def test_roundtrip_and_magic_probe(self):
        block = np.frombuffer(b"B" * 64, dtype=np.uint8)
        parts = coding_mod.build_block_parts(2, 7, 3, 1, 1, block, 190, 0xABCD)
        blob = b"".join(bytes(p) for p in parts)
        assert is_block(blob) and not is_delta(blob)
        header, view = coding_mod.parse_block(blob)
        assert (header["owner"], header["iteration"]) == (2, 7)
        assert bytes(view) == b"B" * 64

    def test_corrupt_block_rejected(self):
        block = np.frombuffer(b"B" * 64, dtype=np.uint8)
        parts = coding_mod.build_block_parts(0, 1, 2, 1, 0, block, 128, 1)
        blob = bytearray(b"".join(bytes(p) for p in parts))
        blob[-5] ^= 0x20  # flip a payload byte
        with pytest.raises(CheckpointError, match="checksum mismatch"):
            coding_mod.parse_block(bytes(blob))

    def test_mixed_generation_reconstruction_rejected(self):
        data = os.urandom(300)
        blocks, orig = rs.split(data, 2)
        parity = rs.encode(blocks, 1)
        arts = [
            b"".join(bytes(p) for p in coding_mod.build_block_parts(
                0, 1, 2, 1, 0, blocks[0], orig, 111))
        ]
        arts.append(
            b"".join(bytes(p) for p in coding_mod.build_block_parts(
                0, 1, 2, 1, 2, parity[0], orig, 222))  # different digest
        )
        with pytest.raises(CheckpointError, match="mismatched generations"):
            coding_mod.reconstruct_container(arts)


# -- factory ------------------------------------------------------------------


def test_replication_from_env(monkeypatch, make_store):
    comm = None  # strategies tolerate comm=None at construction
    ex = object()
    monkeypatch.delenv("TPU_RESILIENCY_CKPT_CODING", raising=False)
    s = replication_from_env(comm, ex, 1, 2)
    assert type(s) is CliqueReplicationStrategy
    monkeypatch.setenv("TPU_RESILIENCY_CKPT_CODING", "erasure")
    s = replication_from_env(comm, ex, 1, 3)
    assert isinstance(s, ErasureReplicationStrategy) and s.parity == 1
    s = replication_from_env(comm, ex, 1, 4, coding="erasure:2")
    assert s.parity == 2
    with pytest.raises(CheckpointError):
        replication_from_env(comm, ex, 1, 2, coding="erasure:2")  # k < 1
    with pytest.raises(CheckpointError):
        replication_from_env(comm, ex, 1, 2, coding="banana")


# -- erasure e2e over real managers ------------------------------------------


WORLD3 = [0, 1, 2]


def _tree(rank, it, n=200_000):
    return {"w": np.full((n,), rank * 10.0 + it, np.float32), "step": it}


def _erasure_body(root, make_store, rank, gen, *, save_iters=(), wipe=False,
                  load=False, pipelined=False, world=WORLD3):
    comm = StoreComm(make_store(), rank, list(world), timeout=60.0,
                     generation=gen)
    ex = PeerExchange(make_store(), rank, timeout=30.0)
    ex.start()
    try:
        strat = ErasureReplicationStrategy(
            comm, ex, replication_jump=1, replication_factor=len(world),
            parity=1,
        )
        mgr = LocalCheckpointManager(
            root, rank=rank, comm=comm, replication=strat, keep=2,
            pipelined=pipelined,
        )
        if wipe:
            mgr.wipe()
        for it in save_iters:
            mgr.save(it, PyTreeStateDict(_tree(rank, it)),
                     is_async=pipelined)
            mgr.maybe_finalize(blocking=True)
        out = None
        if load:
            hollow, tensors, meta = mgr.load()
            out = (meta["iteration"], np.asarray(tensors[0]).copy())
        mgr.close()
        return out, sorted(mgr.block_ids())
    finally:
        ex.close()


class TestErasureE2E:
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_save_distributes_one_block_per_peer(
        self, tmp_path, make_store, sink, pipelined
    ):
        root = str(tmp_path / "ckpt")
        out = run_ranks(WORLD3, lambda r: _erasure_body(
            root, make_store, r, 0, save_iters=(1,), pipelined=pipelined))
        for rank, (_, blocks) in zip(WORLD3, out):
            # Each rank holds exactly one block of each peer's shard, and
            # the assigned index equals this rank's clique position.
            owners = sorted(b[1] for b in blocks)
            assert owners == sorted(set(WORLD3) - {rank})
            assert all(b[2] == rank and b[3] == 2 and b[4] == 1 for b in blocks)
        parity_events = [e for e in sink if e.kind == "ckpt_parity"]
        assert len(parity_events) == len(WORLD3)
        for e in parity_events:
            # Wire economy: k=2, m=1 -> sent ≤ (1 + 1/k) x payload.
            assert e.payload["sent_bytes"] <= 1.6 * e.payload["payload_bytes"]

    def test_lost_rank_reconstructs_byte_identical_no_mirror_fallback(
        self, tmp_path, make_store, sink
    ):
        """ACCEPTANCE: the recovery-ladder e2e — a lost rank's shard comes
        back from parity blocks byte-identically, with zero full-mirror
        transfers and zero iteration fallback."""
        root = str(tmp_path / "ckpt")
        run_ranks(WORLD3, lambda r: _erasure_body(
            root, make_store, r, 0, save_iters=(1,)))
        own = open(os.path.join(root, "s0", "r0",
                                CkptID(1, 0).filename()), "rb").read()
        out = run_ranks(WORLD3, lambda r: _erasure_body(
            root, make_store, r, 1, wipe=(r == 0), load=True))
        for rank, (loaded, _) in zip(WORLD3, out):
            it, w = loaded
            assert it == 1
            np.testing.assert_array_equal(
                w, np.full((200_000,), rank * 10.0 + 1, np.float32))
        # The reconstructed container was re-persisted byte-identically.
        assert open(os.path.join(root, "s0", "r0",
                                 CkptID(1, 0).filename()), "rb").read() == own
        recon = [e for e in sink if e.kind == "ckpt_parity_reconstruct"]
        assert [e.payload["outcome"] for e in recon] == ["ok"]
        assert not [e for e in sink if e.kind == "ckpt_fallback"]
        # Zero full-mirror fallback: no whole-container retrieve transfer —
        # every p2p payload in the recovery round is a block artifact
        # (retr/…/b/ tags), never a mirror (retr/…/m/ tags).
        mirror_sends = [
            e for e in sink
            if e.kind == "p2p_transfer" and "/m/" in str(e.payload.get("tag"))
        ]
        assert not mirror_sends

    def test_corrupt_parity_block_degrades_to_peer_retrieve(
        self, tmp_path, make_store, sink
    ):
        """A flipped bit in a parity block must NEVER reconstruct silently:
        reconstruction fails closed, and when a real mirror exists (mixed
        clique / previously recovered container) the ladder's peer-retrieve
        rung serves it byte-identically."""
        root = str(tmp_path / "ckpt")
        run_ranks(WORLD3, lambda r: _erasure_body(
            root, make_store, r, 0, save_iters=(1,)))
        own_path = os.path.join(root, "s0", "r0", CkptID(1, 0).filename())
        own = open(own_path, "rb").read()
        # Rank 1 also holds a REAL mirror of rank 0's shard (the shape a
        # mixed-version peer or an earlier recovery leaves behind).
        mirror_path = os.path.join(root, "s0", "r1", CkptID(1, 0).filename())
        with open(mirror_path, "wb") as f:
            f.write(own)
        # Corrupt one of the surviving blocks of rank 0's shard.
        for holder in (1, 2):
            d = os.path.join(root, "s0", f"r{holder}")
            for name in os.listdir(d):
                if name.endswith(".ecblk") and "_0_b" in name:
                    p = os.path.join(d, name)
                    blob = bytearray(open(p, "rb").read())
                    blob[-3] ^= 0x40
                    open(p, "wb").write(bytes(blob))
        out = run_ranks(WORLD3, lambda r: _erasure_body(
            root, make_store, r, 1, wipe=(r == 0), load=True))
        it, w = out[0][0]
        assert it == 1
        np.testing.assert_array_equal(
            w, np.full((200_000,), 1.0, np.float32))
        assert open(own_path, "rb").read() == own
        # The rung order is visible in the events: a failed reconstruction,
        # then a successful peer retrieve; never a fallback.
        recon = [e for e in sink if e.kind == "ckpt_parity_reconstruct"]
        assert recon and recon[0].payload["outcome"] == "failed"
        assert not [e for e in sink if e.kind == "ckpt_fallback"]

    def test_coverage_counts_reconstructible_shards(
        self, tmp_path, make_store
    ):
        """find_latest must agree with what the ladder can deliver: after
        the owner's disk is wiped, the iteration stays covered because the
        blocks reconstruct it."""
        root = str(tmp_path / "ckpt")
        run_ranks(WORLD3, lambda r: _erasure_body(
            root, make_store, r, 0, save_iters=(1,)))

        def probe(rank):
            comm = StoreComm(make_store(), rank, WORLD3, timeout=60.0,
                             generation=1)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = ErasureReplicationStrategy(
                    comm, ex, replication_jump=1,
                    replication_factor=len(WORLD3), parity=1)
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat)
                if rank == 0:
                    mgr.wipe()
                latest = mgr.find_latest()
                mgr.close()
                return latest
            finally:
                ex.close()

        assert run_ranks(WORLD3, probe) == [1, 1, 1]

    pass


# -- streaming erasure encode -------------------------------------------------


class TestStreamingEncode:
    @pytest.mark.parametrize("k,m", [(1, 0), (1, 1), (2, 1), (3, 1), (3, 2),
                                     (5, 3), (7, 2)])
    def test_blocks_byte_identical_to_copy_path(self, k, m):
        """Every coded block off the streaming path (multi-part payload,
        view-served data blocks, accumulated parity) matches the classic
        split-copy + encode path byte for byte — including the zero-pad
        tail of the last data block."""
        rng = np.random.default_rng(k * 31 + m)
        for total in (1, 13, 64 * 1024 + 7, 256 * 1024):
            payload = rng.integers(0, 256, total, dtype=np.uint8).tobytes()
            parts = [payload[: total // 3], payload[total // 3 : 2 * total // 3],
                     payload[2 * total // 3 :]]
            ref_blocks, ref_len = coding_mod._split_parts(parts, k)
            ref = ref_blocks + rs.encode(ref_blocks, m)
            views, tot, bl, parity = coding_mod.encode_payload(parts, k, m)
            assert tot == ref_len
            for i in range(k + m):
                got = coding_mod.coded_block(views, tot, bl, parity, k, i)
                gb = (b"".join(bytes(p) for p in got)
                      if isinstance(got, list) else bytes(memoryview(got)))
                assert gb == ref[i].tobytes(), (total, k, m, i)

    def test_prefed_encoder_reused_and_mismatch_falls_back(self):
        parts = [os.urandom(10_000), os.urandom(5_000)]
        enc = rs.StreamingEncoder(15_000, 2, 1, window=333)
        for p in parts:
            enc.update(p)
        views, tot, bl, parity = coding_mod.encode_payload(
            parts, 2, 1, encoder=enc)
        assert parity[0] is enc.parity[0]  # reused, no re-encode
        # Geometry mismatch (different k): silently re-streams.
        _, _, _, parity2 = coding_mod.encode_payload(parts, 3, 1, encoder=enc)
        ref_blocks, _ = coding_mod._split_parts(parts, 3)
        assert parity2[0].tobytes() == rs.encode(ref_blocks, 1)[0].tobytes()

    def test_parity1_is_pure_xor(self):
        """The RAID-5 fast path survives streaming: m=1 parity equals the
        XOR-reduce of the data blocks."""
        payload = os.urandom(4096 * 3)
        views, tot, bl, parity = coding_mod.encode_payload([payload], 3, 1)
        blocks, _ = rs.split(payload, 3)
        want = blocks[0] ^ blocks[1] ^ blocks[2]
        assert parity[0].tobytes() == want.tobytes()

    def test_streaming_alloc_stays_small(self):
        """Steady-state allocation gate: streaming a 32 MB payload through
        the encoder (m=1) must not allocate payload-sized scratch — the
        parity block plus O(window) temporaries only."""
        import tracemalloc

        total = 32 * (1 << 20)
        chunk = bytes(1 << 20)
        enc = rs.StreamingEncoder(total, 3, 1)
        enc.update(chunk)  # warm the code path before measuring
        tracemalloc.start()
        for _ in range(31):
            enc.update(chunk)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert enc.parity_blocks()[0].nbytes >= total // 3
        assert peak < 1 << 20, f"peak transient alloc {peak} >= 1 MB"

    def test_overfeed_and_early_parity_read_raise(self):
        enc = rs.StreamingEncoder(100, 2, 1)
        enc.update(b"x" * 60)
        with pytest.raises(CheckpointError, match="past the declared total"):
            enc.update(b"y" * 41)
        with pytest.raises(CheckpointError, match="parity read after"):
            enc.parity_blocks()


# -- delta x erasure composition ----------------------------------------------


def _delta_frame_fixture(tmp_path, dirty=128):
    """A (frame, base_path, want_container_bytes) triple: base container on
    disk, new container differing in a few chunks, encoded as a frame."""
    arr = np.zeros(1 << 21, dtype=np.uint8)
    arr[:] = 3
    prefix, views = ckpt_format.serialize_parts(
        b"hollow", [arr], meta={"iteration": 1})
    base_path = str(tmp_path / "base.ckpt")
    ckpt_format.write_parts(base_path, [prefix, *views])
    info = ckpt_format.parse_trailer(views[-1])
    base = {
        "iteration": 1,
        "leaf_sizes": [arr.nbytes],
        "chunk_size": info.chunk_size,
        "leaf_chunks": info.leaf_chunk_crcs([arr.nbytes]),
        "container_crc": info.container_crc,
    }
    new = arr.copy()
    new[:dirty] += 9
    p2, v2 = ckpt_format.serialize_parts(
        b"hollow", [new], meta={"iteration": 2})
    frame, _ = encode_delta(0, 2, base, p2, v2[:-1], bytes(v2[-1]))
    want = b"".join([p2, *[bytes(memoryview(v).cast("B")) for v in v2]])
    return frame, base_path, want


class TestDeltaErasureComposition:
    def test_k_of_n_frame_reconstruction_round_trips(self, tmp_path):
        """ACCEPTANCE: a delta frame erasure-coded into k+m blocks
        reconstructs byte-identically from any k of them, and the applied
        container round-trips byte-identically against the base."""
        frame, base_path, want = _delta_frame_fixture(tmp_path)
        k, m = 3, 2
        views, tot, bl, parity = coding_mod.encode_payload([frame], k, m)
        meta = coding_mod._payload_meta([frame])
        digest = meta.pop("digest")
        arts = {}
        for i in range(k + m):
            blk = coding_mod.coded_block(views, tot, bl, parity, k, i)
            arts[i] = b"".join(
                bytes(p) for p in coding_mod.build_block_parts(
                    0, 2, k, m, i, blk, tot, digest, **meta))
        for drop in itertools.islice(
            itertools.combinations(range(k + m), m), 6
        ):
            got = coding_mod.reconstruct_container(
                [a for i, a in arts.items() if i not in drop])
            assert got == frame
            assert is_delta(got)
        out_path = str(tmp_path / "applied.ckpt")
        apply_delta(frame, base_path, out_path)
        assert open(out_path, "rb").read() == want

    def test_corrupt_frame_block_fails_closed(self, tmp_path):
        frame, _, _ = _delta_frame_fixture(tmp_path)
        views, tot, bl, parity = coding_mod.encode_payload([frame], 2, 1)
        meta = coding_mod._payload_meta([frame])
        digest = meta.pop("digest")
        # Wrong digest in the artifacts: reconstruction must not return a
        # frame whose whole-frame CRC disagrees with the recorded identity.
        arts = []
        for i in (0, 1):
            blk = coding_mod.coded_block(views, tot, bl, parity, 2, i)
            arts.append(b"".join(
                bytes(p) for p in coding_mod.build_block_parts(
                    0, 2, 2, 1, i, blk, tot, digest ^ 1, **meta)))
        with pytest.raises(CheckpointError, match="digest mismatch"):
            coding_mod.reconstruct_container(arts)


def _delta_erasure_body(root, make_store, rank, gen, *, iters=(),
                        interval=4, load=False, world=WORLD3,
                        pipelined=False):
    comm = StoreComm(make_store(), rank, list(world), timeout=60.0,
                     generation=gen)
    ex = PeerExchange(make_store(), rank, timeout=30.0)
    ex.start()
    try:
        strat = ErasureReplicationStrategy(
            comm, ex, replication_jump=1, replication_factor=len(world),
            parity=1)
        mgr = LocalCheckpointManager(
            root, rank=rank, comm=comm, replication=strat, keep=2,
            delta_interval=interval, pipelined=pipelined)
        for it in iters:
            arr = np.full((1 << 21,), float(rank), np.float32)
            arr[:128] += it  # ~small dirty fraction between saves
            mgr.save(it, PyTreeStateDict({"w": arr, "step": it}),
                     is_async=pipelined)
            mgr.maybe_finalize(blocking=True)
        out = None
        if load:
            hollow, tensors, meta = mgr.load()
            out = (meta["iteration"], np.asarray(tensors[0]).copy())
        mgr.close()
        return out, sorted(mgr.block_ids())
    finally:
        ex.close()


class TestDeltaErasureE2E:
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_delta_round_codes_the_frame(
        self, tmp_path, make_store, sink, pipelined
    ):
        """Iteration 2 is a delta round under erasure: the parity exchange
        codes the FRAME (payload_bytes collapses), peers hold block
        artifacts for it, and the wire still moves ≤ (1+1/k)× the frame."""
        root = str(tmp_path / "ckpt")
        out = run_ranks(WORLD3, lambda r: _delta_erasure_body(
            root, make_store, r, 0, iters=(1, 2), pipelined=pipelined))
        for rank, (_, blocks) in zip(WORLD3, out):
            assert sorted({b[0] for b in blocks}) == [1, 2]
        deltas = [e for e in sink if e.kind == "ckpt_delta"]
        assert len(deltas) == len(WORLD3)  # iteration 2, every rank
        parity = {e.payload["payload_bytes"]: e for e in sink
                  if e.kind == "ckpt_parity"}
        small, big = min(parity), max(parity)
        # One dirty chunk of an 8-chunk container: the frame round's coded
        # payload collapses to ~prefix+trailer+1 chunk.
        assert small * 4 < big  # frame rounds vs keyframe rounds
        for e in parity.values():
            k = e.payload["k"]
            assert e.payload["sent_bytes"] <= 1.1 * (
                e.payload["payload_bytes"] * (1 + 1 / k)) + 4096 * k

    def test_lost_owner_delta_generation_reconstructs(
        self, tmp_path, make_store, sink
    ):
        """The owner loses its NEWEST (delta-generation) container but keeps
        the base: the ladder reconstructs the frame from peer blocks and
        applies it against the local base, byte-identically."""
        root = str(tmp_path / "ckpt")
        run_ranks(WORLD3, lambda r: _delta_erasure_body(
            root, make_store, r, 0, iters=(1, 2)))
        newest = os.path.join(root, "s0", "r0", CkptID(2, 0).filename())
        own = open(newest, "rb").read()
        os.unlink(newest)
        out = run_ranks(WORLD3, lambda r: _delta_erasure_body(
            root, make_store, r, 1, load=True))
        for rank, (loaded, _) in zip(WORLD3, out):
            it, w = loaded
            assert it == 2
            want = np.full((1 << 21,), float(rank), np.float32)
            want[:128] += 2
            np.testing.assert_array_equal(w, want)
        assert open(newest, "rb").read() == own
        applied = [e for e in sink if e.kind == "ckpt_delta_applied"]
        assert [e.payload["outcome"] for e in applied] == ["ok"]
        assert not [e for e in sink if e.kind == "ckpt_fallback"]

    def test_lost_base_breaks_chain_and_ladder_falls_back(
        self, tmp_path, make_store, sink
    ):
        """The owner loses its whole disk: iteration 2's frame reconstructs
        but cannot apply (no base), so the group agrees to fall back to the
        keyframe generation — never assembling from a wrong base."""
        root = str(tmp_path / "ckpt")
        run_ranks(WORLD3, lambda r: _delta_erasure_body(
            root, make_store, r, 0, iters=(1, 2)))
        import shutil
        shutil.rmtree(os.path.join(root, "s0", "r0"))
        out = run_ranks(WORLD3, lambda r: _delta_erasure_body(
            root, make_store, r, 1, load=True))
        for rank, (loaded, _) in zip(WORLD3, out):
            it, w = loaded
            assert it == 1  # keyframe generation
            want = np.full((1 << 21,), float(rank), np.float32)
            want[:128] += 1
            np.testing.assert_array_equal(w, want)
        broken = [e for e in sink if e.kind == "ckpt_delta_applied"
                  and e.payload["outcome"] == "broken"]
        assert broken
        assert [e for e in sink if e.kind == "ckpt_fallback"]


# -- delta chain --------------------------------------------------------------


class TestDeltaTracker:
    def test_interval_cadence(self):
        t = DeltaTracker(3)
        assert t.enabled
        sizes = [1024]
        assert t.eligible(sizes) is None  # no base yet
        t.note_saved(1, sizes, 256, [[1, 2, 3, 4]], 99, keyframe=True)
        assert t.eligible(sizes) is not None  # delta 1 of cycle
        t.note_saved(2, sizes, 256, [[1, 2, 3, 5]], 98, keyframe=False)
        assert t.eligible(sizes) is not None  # delta 2 of cycle
        t.note_saved(3, sizes, 256, [[1, 2, 3, 6]], 97, keyframe=False)
        assert t.eligible(sizes) is None  # keyframe due (interval=3)
        t.note_saved(4, sizes, 256, [[9, 2, 3, 6]], 96, keyframe=True)
        assert t.eligible(sizes) is not None
        assert t.eligible([2048]) is None  # signature moved
        t.reset()
        assert t.eligible(sizes) is None

    def test_env_default(self, monkeypatch):
        monkeypatch.setenv(delta_mod.DELTA_ENV, "5")
        assert DeltaTracker().interval == 5
        monkeypatch.setenv(delta_mod.DELTA_ENV, "bogus")
        assert DeltaTracker().interval == 0


class TestDeltaFrames:
    def _container(self, arr, it):
        prefix, views = ckpt_format.serialize_parts(
            b"hollow", [arr], meta={"iteration": it}
        )
        return prefix, views

    def test_encode_apply_roundtrip(self, tmp_path):
        cs = ckpt_format.DEFAULT_CHUNK
        base_arr = np.zeros(cs * 3 // 4, dtype=np.uint8)  # sub-chunk leaf
        base_arr[:] = 7
        p1, v1 = self._container(base_arr, 1)
        base_path = str(tmp_path / "base.ckpt")
        ckpt_format.write_parts(base_path, [p1, *v1])
        info = ckpt_format.parse_trailer(v1[-1])
        base = {
            "iteration": 1,
            "leaf_sizes": [base_arr.nbytes],
            "chunk_size": info.chunk_size,
            "leaf_chunks": info.leaf_chunk_crcs([base_arr.nbytes]),
            "container_crc": info.container_crc,
        }
        new_arr = base_arr.copy()
        new_arr[5] = 9
        p2, v2 = self._container(new_arr, 2)
        frame, stats = encode_delta(0, 2, base, p2, v2[:-1], bytes(v2[-1]))
        assert is_delta(frame)
        assert stats["chunks_changed"] == 1
        out_path = str(tmp_path / "applied.ckpt")
        apply_delta(frame, base_path, out_path)
        want = b"".join([p2, *[bytes(memoryview(v).cast("B")) for v in v2]])
        assert open(out_path, "rb").read() == want
        assert ckpt_format.verify_file(out_path)[0] == "ok"

    def test_broken_chain_fails_closed(self, tmp_path):
        arr = np.arange(4096, dtype=np.uint8)
        p1, v1 = self._container(arr, 1)
        base_path = str(tmp_path / "base.ckpt")
        ckpt_format.write_parts(base_path, [p1, *v1])
        info = ckpt_format.parse_trailer(v1[-1])
        base = {
            "iteration": 1,
            "leaf_sizes": [arr.nbytes],
            "chunk_size": info.chunk_size,
            "leaf_chunks": info.leaf_chunk_crcs([arr.nbytes]),
            "container_crc": info.container_crc,
        }
        new = arr.copy()
        new[0] ^= 1
        p2, v2 = self._container(new, 2)
        frame, _ = encode_delta(0, 2, base, p2, v2[:-1], bytes(v2[-1]))
        # A DIFFERENT base on disk (stale generation): digest mismatch.
        other = np.arange(4096, dtype=np.uint8)[::-1].copy()
        p3, v3 = self._container(other, 1)
        ckpt_format.write_parts(base_path, [p3, *v3])
        with pytest.raises(CheckpointError, match="stale or divergent"):
            apply_delta(frame, base_path, str(tmp_path / "out.ckpt"))
        # Missing base entirely.
        with pytest.raises(CheckpointError, match="unusable"):
            apply_delta(frame, str(tmp_path / "gone.ckpt"),
                        str(tmp_path / "out.ckpt"))


def _delta_body(root, make_store, rank, *, iters, interval, world=(0, 1),
                pipelined=False, skip_base_mirror=False):
    comm = StoreComm(make_store(), rank, list(world), timeout=60.0)
    ex = PeerExchange(make_store(), rank, timeout=30.0)
    ex.start()
    try:
        strat = CliqueReplicationStrategy(
            comm, ex, replication_jump=1, replication_factor=len(world))
        mgr = LocalCheckpointManager(
            root, rank=rank, comm=comm, replication=strat,
            delta_interval=interval, keep=2, pipelined=pipelined)
        for it in iters:
            arr = np.full((1 << 21,), float(rank), np.float32)
            arr[: 128] += it  # small dirty fraction
            mgr.save(it, PyTreeStateDict({"w": arr, "step": it}),
                     is_async=pipelined)
            mgr.maybe_finalize(blocking=True)
            if skip_base_mirror and it == iters[0] and rank == 1:
                # Simulate a joiner that missed the keyframe: drop the
                # mirror of rank 0's base before the delta round.
                p = os.path.join(root, "s0", "r1", CkptID(it, 0).filename())
                os.unlink(p)
        hollow, tensors, meta = mgr.load()
        mgr.close()
        return meta["iteration"], np.asarray(tensors[0]).copy()
    finally:
        ex.close()


class TestDeltaE2E:
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_chain_round_trips_byte_identical(
        self, tmp_path, make_store, sink, pipelined
    ):
        root = str(tmp_path / "ckpt")
        out = run_ranks([0, 1], lambda r: _delta_body(
            root, make_store, r, iters=(1, 2, 3), interval=4,
            pipelined=pipelined))
        for rank, (it, w) in zip([0, 1], out):
            assert it == 3
            want = np.full((1 << 21,), float(rank), np.float32)
            want[:128] += 3
            np.testing.assert_array_equal(w, want)
        deltas = [e for e in sink if e.kind == "ckpt_delta"]
        applied = [e for e in sink if e.kind == "ckpt_delta_applied"]
        assert len(deltas) == 4  # iters 2 and 3, both ranks
        assert all(e.payload["outcome"] == "ok" for e in applied)
        # Byte economy: the frame is a small fraction of the container.
        for e in deltas:
            assert e.payload["frame_bytes"] * 4 < e.payload["full_bytes"]
        # Mirrors are byte-identical to the sender's own container.
        for rank in (0, 1):
            own = open(os.path.join(
                root, "s0", f"r{rank}", CkptID(3, rank).filename()), "rb").read()
            mirror = open(os.path.join(
                root, "s0", f"r{1 - rank}", CkptID(3, rank).filename()),
                "rb").read()
            assert own == mirror, rank

    def test_keyframe_cadence_respected(self, tmp_path, make_store, sink):
        root = str(tmp_path / "ckpt")
        run_ranks([0, 1], lambda r: _delta_body(
            root, make_store, r, iters=(1, 2, 3, 4, 5), interval=3))
        deltas = sorted(
            e.payload["iteration"] for e in sink if e.kind == "ckpt_delta"
        )
        # interval=3: keyframes at 1 and 4; deltas at 2, 3 and 5 (per rank).
        assert deltas == [2, 2, 3, 3, 5, 5]

    def test_broken_chain_drops_mirror_and_ladder_survives(
        self, tmp_path, make_store, sink
    ):
        """A peer missing the base container cannot apply the delta: the
        mirror is skipped (ckpt_delta_applied{broken}), the owner's copy
        still covers the iteration, and load() serves everyone."""
        root = str(tmp_path / "ckpt")
        out = run_ranks([0, 1], lambda r: _delta_body(
            root, make_store, r, iters=(1, 2), interval=4,
            skip_base_mirror=True))
        for rank, (it, w) in zip([0, 1], out):
            assert it == 2
        broken = [
            e for e in sink
            if e.kind == "ckpt_delta_applied"
            and e.payload["outcome"] == "broken"
        ]
        assert broken and broken[0].payload["owner"] == 0
        # The dropped mirror really is absent; coverage rode the owner copy.
        assert not os.path.exists(
            os.path.join(root, "s0", "r1", CkptID(2, 0).filename()))


# -- the chunk manifest, and heads of other formats at the retrieve rung ------


class TestChunkManifest:
    def test_writers_and_chunk_manifest(self, tmp_path):
        path = str(tmp_path / "c.ckpt")
        arr = np.arange(ckpt_format.DEFAULT_CHUNK // 2, dtype=np.uint8)
        ckpt_format.write_payload(path, b"h", [arr, arr[: 100]])
        with open(path, "rb") as f:
            assert f.read(8) == b"TPURES03"
        header, prefix_len, info = ckpt_format.read_trailer(path)
        assert info.chunk_size == ckpt_format.DEFAULT_CHUNK
        assert len(info.chunk_crcs) == 2  # one per (sub-chunk) leaf
        rep = ckpt_format.chunk_report(path)
        assert rep["status"] == "ok" and not any(
            leaf["bad"] for leaf in rep["leaves"]
        )

    def test_chunk_corruption_located(self, tmp_path):
        path = str(tmp_path / "c.ckpt")
        cs = ckpt_format.DEFAULT_CHUNK
        ckpt_format.write_payload(path, b"h", [np.zeros(cs * 3, dtype=np.uint8)])
        header, prefix_len, info = ckpt_format.read_trailer(path)
        assert info.chunk_size == cs and len(info.chunk_crcs) == 3
        with open(path, "r+b") as f:
            f.seek(prefix_len + cs + 17)  # inside chunk 1
            f.write(b"\xff")
        status, detail = ckpt_format.verify_file(path)
        assert status == "corrupt" and "chunk 1" in detail
        rep = ckpt_format.chunk_report(path)
        assert rep["leaves"][0]["bad"] == [1]

    def test_streamed_chunk_size_rides_in_the_trailer(self, tmp_path):
        """A writer that names its chunk size (``Checksummer(chunk_size=)``)
        is read back at that size: the trailer, not the reader's default,
        decides the geometry."""
        path = str(tmp_path / "s.ckpt")
        cs = 4096
        arr = np.arange(cs * 3 + 5, dtype=np.uint8)
        prefix = ckpt_format.header_prefix(
            b"h", [{"shape": arr.shape, "dtype": "uint8", "nbytes": arr.nbytes}]
        )
        ck = ckpt_format.Checksummer(prefix, chunk_size=cs)
        ck.add_leaf(arr)
        trailer = ck.trailer()
        assert len(trailer) == ckpt_format.trailer_size_for([arr.nbytes], cs)
        ckpt_format.write_stream(path, [prefix, arr, trailer])
        assert ckpt_format.verify_file(path)[0] == "ok"
        _, _, info = ckpt_format.read_trailer(path)
        assert info.chunk_size == cs and len(info.chunk_crcs) == 4
        _, tensors, _ = ckpt_format.read_payload(path)
        np.testing.assert_array_equal(tensors[0], arr)

    @pytest.mark.parametrize("head", [b"TPURES01", b"TPURES02"], ids=["v1", "v2"])
    def test_old_format_mirror_is_refused_at_the_retrieve_rung(
        self, tmp_path, make_store, sink, head
    ):
        """Rank 1 holds rank 0's shard as a container of an old format; rank
        0's disk is empty. The retrieve rung moves the bytes (to the receive
        side they are no container), the parser refuses their head with the
        rung's ``ckpt_integrity_failure`` event, nothing is loaded or
        persisted, and with no older rung every rank's load raises. (At the
        parent ``TPURES02`` was served and re-persisted.)"""
        from tests.checkpoint.test_integrity import _other_head

        root = str(tmp_path / "ckpt")
        r1 = os.path.join(root, "s0", "r1")
        os.makedirs(r1, exist_ok=True)
        with open(os.path.join(r1, CkptID(5, 0).filename()), "wb") as f:
            f.write(_other_head(head))
        ckpt_format.write_payload(
            os.path.join(r1, CkptID(5, 1).filename()),
            pickle.dumps("own-skeleton"), [np.full((64,), 11.0, np.float32)],
            meta={"iteration": 5},
        )

        def body(rank):
            comm = StoreComm(make_store(), rank, [0, 1], timeout=60.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2)
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat)
                try:
                    with pytest.raises(CheckpointError, match="no intact"):
                        mgr.load()
                finally:
                    mgr.close()
            finally:
                ex.close()

        run_ranks([0, 1], body)
        failures = [e for e in sink if e.kind == "ckpt_integrity_failure"]
        assert [e.payload["stage"] for e in failures] == ["peer-retrieve"]
        assert "bad magic" in failures[0].payload["error"]
        assert not [e for e in sink if e.kind == "ckpt_unverified"]
        assert not os.path.exists(
            os.path.join(root, "s0", "r0", CkptID(5, 0).filename()))
