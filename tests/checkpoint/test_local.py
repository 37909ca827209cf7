"""Multi-rank local checkpointing: comm, replication cliques, manager coverage.

Simulated multi-rank pattern per SURVEY §4: N "ranks" as threads, each with its own
store client + peer exchange against one KVServer — the JAX-host analogue of the
reference's Gloo-on-CPU multi-process fixtures.
"""

import concurrent.futures as cf
import pickle

import numpy as np
import pytest
from hypothesis import given as hyp_given, settings as hyp_settings, strategies as hyp_st

from tpu_resiliency.checkpoint import format as ckpt_format
from tpu_resiliency.checkpoint.comm import PeerExchange, StoreComm
from tpu_resiliency.checkpoint.local_manager import CkptID, LocalCheckpointManager
from tpu_resiliency.checkpoint.replication import (
    CliqueReplicationStrategy,
    ExchangePlan,
    parse_group_sequence,
)
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.platform.store import CoordStore


def run_ranks(world, fn, timeout=60.0):
    """Run fn(rank) on `world` threads; raise the first failure."""
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


class TestParseGroupSequence:
    def test_adjacent(self):
        assert parse_group_sequence(1, 2, 4) == [[0, 1], [2, 3]]

    def test_jump_spans_hosts(self):
        # jump=2 (ranks per host), factor=2, world=8: mirrors on different hosts.
        assert parse_group_sequence(2, 2, 8) == [[0, 2], [1, 3], [4, 6], [5, 7]]

    def test_factor_one_identity(self):
        assert parse_group_sequence(1, 1, 3) == [[0], [1], [2]]

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            parse_group_sequence(2, 2, 6)


class TestExchangePlan:
    def test_balanced_holder_choice(self):
        # Ranks 0,1 lost their shards; both 2 and 3 hold both shards.
        plan = ExchangePlan.build(
            wanted={0: 0, 1: 1}, holders={2: {0, 1}, 3: {0, 1}}
        )
        senders = sorted(src for src in plan.sends)
        assert senders == [2, 3]  # load-balanced, not both from rank 2

    def test_no_holder_raises(self):
        with pytest.raises(CheckpointError):
            ExchangePlan.build(wanted={0: 0}, holders={1: {5}})


class TestStoreComm:
    def test_all_gather_ordered(self, make_store):
        world = 4

        def body(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            return comm.all_gather(rank * 10)

        for result in run_ranks(world, body):
            assert result == [0, 10, 20, 30]

    def test_broadcast(self, make_store):
        world = 3

        def body(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            return comm.broadcast({"cfg": 1} if rank == 1 else None, src=1)

        assert run_ranks(world, body) == [{"cfg": 1}] * world

    def test_all_reduce_and(self, make_store):
        world = 3

        def body(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            return comm.all_reduce_and(rank != 1)

        assert run_ranks(world, body) == [False] * world

    def test_rounds_do_not_collide(self, make_store):
        world = 2

        def body(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            out = [comm.all_gather(f"{rank}-{i}") for i in range(3)]
            return out

        for result in run_ranks(world, body):
            assert result == [["0-0", "1-0"], ["0-1", "1-1"], ["0-2", "1-2"]]


class TestPeerExchange:
    def test_send_recv(self, make_store):
        world = 2

        def body(rank):
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                ex.send(1 - rank, "t", f"hello-{rank}".encode())
                return bytes(ex.recv(1 - rank, "t")).decode()
            finally:
                ex.close()

        assert run_ranks(world, body) == ["hello-1", "hello-0"]

    def test_tag_isolation(self, make_store):
        world = 2

        def body(rank):
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                if rank == 0:
                    ex.send(1, "b", b"B")
                    ex.send(1, "a", b"A")
                    return None
                return (ex.recv(0, "a"), ex.recv(0, "b"))
            finally:
                ex.close()

        assert run_ranks(world, body)[1] == (b"A", b"B")

    def test_authenticated_exchange(self, make_store):
        """With an auth key, peers bind off-loopback and must pass the HMAC
        challenge; an unauthenticated client is rejected."""
        world = 2

        def body(rank):
            ex = PeerExchange(make_store(), rank, timeout=30.0, auth_key="s3cret")
            ex.start()
            try:
                ex.send(1 - rank, "t", f"auth-{rank}".encode())
                got = bytes(ex.recv(1 - rank, "t")).decode()
                if rank == 0:
                    # A keyless client cannot deliver to an authenticated peer.
                    bad = PeerExchange(make_store(), 7, timeout=5.0, auth_key=None)
                    try:
                        bad.send(1, "t", b"evil")
                        delivered = True
                    except Exception:
                        delivered = False
                    return (got, delivered)
                return got
            finally:
                ex.close()

        results = run_ranks(world, body)
        assert results[0] == ("auth-1", False)
        assert results[1] == "auth-0"

    def test_non_loopback_bind_requires_key(self, make_store):
        ex = PeerExchange(make_store(), 0, auth_key=None)
        with pytest.raises(ValueError):
            ex.start(host="0.0.0.0")


class TestCliqueReplication:
    def test_replicate_within_clique(self, make_store):
        world = 4

        def body(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                held = strat.replicate(f"shard-{rank}".encode())
                return {owner: bytes(blob).decode() for owner, blob in held.items()}
            finally:
                ex.close()

        results = run_ranks(world, body)
        assert results[0] == {0: "shard-0", 1: "shard-1"}
        assert results[3] == {2: "shard-2", 3: "shard-3"}


def _tree(rank):
    return {"w": np.full((4,), float(rank), dtype=np.float32), "step": rank}


class TestLocalCheckpointManager:
    def test_single_rank_roundtrip(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        sd = PyTreeStateDict(_tree(0))
        mgr.save(10, sd, is_async=True)
        mgr.maybe_finalize(blocking=True)
        assert mgr.find_latest() == 10
        hollow, tensors, meta = mgr.load(10)
        assert meta["iteration"] == 10
        restored = PyTreeStateDict.__new__(PyTreeStateDict)
        restored._tree, restored._hollow, restored._tensors = hollow, True, None
        restored._shardings = None
        restored.insert_tensors(tensors)
        np.testing.assert_array_equal(np.asarray(restored.tree["w"]), np.zeros(4))
        mgr.close()

    def test_prunes_old_iterations(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
        mgr.save(2, PyTreeStateDict(_tree(0)), is_async=False)
        assert {i.iteration for i in mgr.local_ids()} == {2}
        mgr.close()

    def test_dirty_files_cleaned_on_init(self, tmp_path):
        mgr = LocalCheckpointManager(str(tmp_path), rank=0)
        mgr.save(1, PyTreeStateDict(_tree(0)), is_async=False)
        dirty = mgr._path(CkptID(9, 0)) + ckpt_format.DIRTY_SUFFIX
        with open(dirty, "wb") as f:
            f.write(b"junk")
        mgr.close()
        mgr2 = LocalCheckpointManager(str(tmp_path), rank=0)
        import os

        assert not os.path.exists(dirty)
        assert mgr2.find_latest() == 1
        mgr2.close()

    def test_distributed_save_load_with_replication(self, tmp_path, make_store):
        world = 4

        def body(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    str(tmp_path), rank=rank, comm=comm, replication=strat
                )
                mgr.save(5, PyTreeStateDict(_tree(rank)), is_async=True)
                mgr.maybe_finalize(blocking=True)
                latest = mgr.find_latest()
                hollow, tensors, meta = mgr.load(latest)
                mgr.close()
                return latest, float(tensors[0][0])
            finally:
                ex.close()

        results = run_ranks(world, body, timeout=120.0)
        assert all(latest == 5 for latest, _ in results)
        assert [v for _, v in results] == [0.0, 1.0, 2.0, 3.0]

    def test_lost_rank_recovers_from_mirror(self, tmp_path, make_store):
        """Rank 1's storage is wiped after save; load must route from its clique peer."""
        world = 2

        def save_phase(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    str(tmp_path), rank=rank, comm=comm, replication=strat
                )
                mgr.save(3, PyTreeStateDict(_tree(rank)), is_async=False)
                mgr.close()
            finally:
                ex.close()

        run_ranks(world, save_phase)

        # Simulate rank 1 landing on a fresh host: wipe its directory.
        import shutil, os

        shutil.rmtree(os.path.join(str(tmp_path), "s0", "r1"))

        def load_phase(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    str(tmp_path), rank=rank, comm=comm, replication=strat
                )
                latest = mgr.find_latest()
                hollow, tensors, meta = mgr.load(latest)
                mgr.close()
                return latest, float(tensors[0][0])
            finally:
                ex.close()

        results = run_ranks(world, load_phase, timeout=120.0)
        assert results == [(3, 0.0), (3, 1.0)]


class TestForkCallerGuard:
    def test_refuses_fork_over_live_backend(self):
        """The suite's conftest initializes JAX, so a fork here duplicates runtime
        threads into the child — schedule must refuse (the documented hazard)."""
        import jax
        import pytest

        from tpu_resiliency.checkpoint.async_core import AsyncRequest, ForkAsyncCaller
        from tpu_resiliency.exceptions import CheckpointError

        jax.devices()  # ensure the backend client exists
        caller = ForkAsyncCaller()
        with pytest.raises(CheckpointError, match="initialized JAX backend"):
            caller.schedule(AsyncRequest(async_fn=lambda: None))

    def test_explicit_override_forks(self, tmp_path):
        import warnings

        import jax
        import pytest

        from tpu_resiliency.checkpoint.async_core import AsyncRequest, ForkAsyncCaller

        if jax.default_backend() != "cpu":
            pytest.skip("forking over a live accelerator client is the documented UB")

        marker = tmp_path / "wrote"
        caller = ForkAsyncCaller(unsafe_allow_fork_with_backend=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)  # multithreaded fork
            caller.schedule(AsyncRequest(async_fn=_touch_file, async_fn_args=(str(marker),)))
        assert caller.wait(timeout=30.0)
        caller.raise_if_failed()
        assert marker.exists()


def _touch_file(path):
    with open(path, "w") as f:
        f.write("ok")


class TestGroupSequenceFor:
    def test_divisible_matches_parse(self):
        from tpu_resiliency.checkpoint.replication import group_sequence_for

        assert group_sequence_for(range(8), 2, 2) == parse_group_sequence(2, 2, 8)

    def test_gapped_rank_ids_group_by_position(self):
        from tpu_resiliency.checkpoint.replication import group_sequence_for

        # Survivors [0,2,5,7] with jump 2: spacing follows placement ORDER.
        assert group_sequence_for([7, 0, 5, 2], 2, 2) == [[0, 5], [2, 7]]

    def test_remainder_merges_into_last_clique(self):
        from tpu_resiliency.checkpoint.replication import group_sequence_for

        assert group_sequence_for(range(3), 1, 2) == [[0, 1, 2]]
        assert group_sequence_for(range(5), 1, 2) == [[0, 1], [2, 3, 4]]

    def test_no_full_block_consecutive_cliques(self):
        from tpu_resiliency.checkpoint.replication import group_sequence_for

        # jump 4 x factor 2 needs 8 ranks; with 5 the spacing degrades rather
        # than leaving anyone unmirrored — a singleton tail folds into its
        # neighbor (a 1-clique would hold zero mirrors).
        assert group_sequence_for(range(5), 4, 2) == [[0, 1], [2, 3, 4]]

    def test_single_rank(self):
        from tpu_resiliency.checkpoint.replication import group_sequence_for

        assert group_sequence_for([3], 1, 2) == [[3]]


class TestRebuildAfterReassignment:
    def test_rebuild_remirrors_and_next_save_covers(self, tmp_path, make_store):
        """review round 3 item 7: world 4 saves with cliques [0,1],[2,3]; rank 3 dies;
        survivors rebuild over [0,1,2], the orphaned rank-2 shard gets re-mirrored,
        a wiped rank still recovers, and the next save is coverage-complete."""
        world = 4

        def save_phase(rank):
            comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    str(tmp_path), rank=rank, comm=comm, replication=strat
                )
                mgr.save(2, PyTreeStateDict(_tree(rank)), is_async=False)
                mgr.close()
            finally:
                ex.close()

        run_ranks(world, save_phase, timeout=120.0)

        # Rank 3 is dead. Survivors' managers (still configured for the old
        # world) adopt the new group.
        survivors = [0, 1, 2]

        def rebuild_phase(rank):
            import os

            stale_comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    stale_comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    str(tmp_path), rank=rank, comm=stale_comm, replication=strat
                )
                assert strat.my_group in ([0, 1], [2, 3])
                new_comm = StoreComm(
                    make_store(), rank, survivors, timeout=30.0, generation=1
                )
                mgr.rebuild_group(new_comm)
                # Remainder merged: one clique of all three survivors.
                assert strat.my_group == [0, 1, 2]
                # Rank 2's shard (old mirror lived only on dead rank 3) is now
                # mirrored on every new clique peer (rank 2 additionally still
                # holds the dead rank's stale mirror — harmless, pruned at the
                # next save's retention pass).
                held = {i.owner for i in mgr.local_ids() if i.iteration == 2}
                assert held >= {0, 1, 2}, held
                # The DEAD rank's shard (sole copy was rank 2's mirror) was
                # re-spread: every survivor can now serve the reshard path.
                assert 3 in held, held
                hollow3, t3, _ = mgr.load_shard(3, 2)
                assert float(t3[0][0]) == 3.0
                new_comm.barrier("post-remirror")
                if rank == 2:  # rank 2 lands on fresh storage
                    for name in os.listdir(mgr._dir):
                        os.unlink(os.path.join(mgr._dir, name))
                new_comm.barrier("post-wipe")
                latest = mgr.find_latest()
                assert latest == 2, latest
                hollow, tensors, meta = mgr.load(latest)
                val = float(tensors[0][0])
                # The next save must be coverage-complete over the NEW group
                # (finalize raises otherwise).
                mgr.save(5, PyTreeStateDict(_tree(rank + 10)), is_async=False)
                latest2 = mgr.find_latest()
                mgr.close()
                return val, latest2
            finally:
                ex.close()

        results = run_ranks(3, lambda r: rebuild_phase(survivors[r]), timeout=120.0)
        assert [v for v, _ in results] == [0.0, 1.0, 2.0]
        assert all(l == 5 for _, l in results)


class TestLazyCliqueReplication:
    def test_groups_bind_at_first_use(self, make_store):
        from tpu_resiliency.checkpoint.replication import LazyCliqueReplicationStrategy

        world = 2

        def body(rank):
            # The comm is only KNOWABLE after "rank assignment settles": the
            # factory defers its construction to first replicate().
            ex = PeerExchange(make_store(), rank, timeout=30.0)
            ex.start()
            try:
                strat = LazyCliqueReplicationStrategy(
                    lambda: StoreComm(make_store(), rank, [0, 1], timeout=30.0),
                    ex,
                    replication_jump=1,
                    replication_factor=2,
                )
                assert strat.comm is None and strat.groups is None
                held = strat.replicate(f"blob-{rank}".encode())
                assert strat.my_group == [0, 1]
                return {o: bytes(b).decode() for o, b in held.items()}
            finally:
                ex.close()

        results = run_ranks(world, body, timeout=60.0)
        assert results[0] == {0: "blob-0", 1: "blob-1"}
        assert results[1] == {0: "blob-0", 1: "blob-1"}


class TestGroupSequenceProperties:
    """Hypothesis invariants for the remainder-folding clique math — the logic a
    reassignment bug would corrupt silently."""

    @hyp_settings(max_examples=200, deadline=None)
    @hyp_given(
        ranks=hyp_st.sets(hyp_st.integers(0, 500), min_size=1, max_size=64),
        jump=hyp_st.integers(1, 8),
        factor=hyp_st.integers(1, 8),
    )
    def test_partition_and_no_singletons(self, ranks, jump, factor):
        from tpu_resiliency.checkpoint.replication import group_sequence_for

        groups = group_sequence_for(ranks, jump, factor)
        flat = [r for g in groups for r in g]
        # Exact partition: every active rank in exactly one clique.
        assert sorted(flat) == sorted(ranks)
        assert len(flat) == len(set(flat))
        # No unmirrored rank unless replication is off or world is 1.
        if factor >= 2 and len(ranks) >= 2:
            assert all(len(g) >= 2 for g in groups), groups
        # Full-spacing blocks never exceed jump*factor; folded tails are
        # bounded by one extra block's worth of members.
        assert all(len(g) <= jump * factor + factor for g in groups)
