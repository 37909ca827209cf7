"""Elastic resharding: plan algebra properties, the ranged-read wire op, and
end-to-end resumes across changed worlds (shrink, grow, changed DP/TP split)
with byte-identical reassembled global state."""

import concurrent.futures as cf
import os

import numpy as np
import pytest

from tpu_resiliency.checkpoint import reshard as R
from tpu_resiliency.checkpoint.comm import PeerExchange, StoreComm
from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
from tpu_resiliency.checkpoint.replication import CliqueReplicationStrategy
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.platform.store import CoordStore
from tpu_resiliency.utils import events


def run_ranks(world, fn, timeout=90.0):
    with cf.ThreadPoolExecutor(max_workers=len(world)) as pool:
        futures = [pool.submit(fn, r) for r in world]
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


def _mem_read(locals_by_rank):
    def read(owner, leaf, off, n):
        flat = locals_by_rank[owner][leaf].reshape(-1).view(np.uint8)
        return flat[off : off + n].tobytes()

    return read


def _reassemble_global(layout, locals_by_rank, leaf):
    spec = layout.leaves[leaf]
    out = np.zeros(spec.global_shape, dtype=np.dtype(spec.dtype))
    filled = np.zeros(spec.global_shape, dtype=np.int32)
    for r in layout.ranks:
        b = layout.box(leaf, r)
        sl = tuple(slice(o, o + s) for o, s in zip(b.offset, b.shape))
        out[sl] = locals_by_rank[r][leaf]
        filled[sl] += 1
    return out, filled


class TestPlanAlgebra:
    def _random_case(self, seed):
        rng = np.random.default_rng(seed)
        worlds = [(1, 1), (2, 1), (3, 1), (4, 1), (2, 2), (6, 1), (2, 3), (1, 2)]
        src_axes = list(zip(["dp", "tp"], worlds[rng.integers(0, len(worlds))]))
        tgt_axes = list(zip(["dp", "tp"], worlds[rng.integers(0, len(worlds))]))
        n = int(np.prod([s for _, s in src_axes]))
        m = int(np.prod([s for _, s in tgt_axes]))
        leaves, arrays = [], []
        for _ in range(int(rng.integers(1, 4))):
            ndim = int(rng.integers(1, 4))
            shape = tuple(int(rng.integers(2, 13)) for _ in range(ndim))
            options: list = [None, "dp", "tp"]
            spec_raw = [options[rng.integers(0, 3)] for _ in range(ndim)]
            # one axis per dim, no repeats across dims
            seen: set = set()
            spec = tuple(
                a if a is None or (a not in seen and not seen.add(a)) else None
                for a in spec_raw
            )
            leaves.append(R.LeafSpec(shape, "float32", spec))
            arrays.append(rng.standard_normal(shape).astype(np.float32))
        src = R.TreeLayout(src_axes, list(range(n)), leaves)
        tgt = R.TreeLayout(tgt_axes, list(range(m)), leaves)
        return src, tgt, arrays

    @pytest.mark.parametrize("seed", range(10))
    def test_round_trip_byte_identical_and_exact_cover(self, seed):
        """Property sweep: N→M→N round-trips byte-identically, and the M-world
        reassembly covers every global index exactly once."""
        src, tgt, arrays = self._random_case(seed)
        plan = R.build_plan(src, tgt)  # build_plan runs validate()
        locals_src = {r: R.slice_local(arrays, src, r) for r in src.ranks}
        locals_tgt = {
            r: R.assemble_rank(plan, r, _mem_read(locals_src))
            for r in tgt.ranks
        }
        for i, arr in enumerate(arrays):
            got, filled = _reassemble_global(tgt, locals_tgt, i)
            assert np.array_equal(got, arr), (seed, i)
            # every global index written by at least one target rank; replicas
            # write identical bytes so exact-once is proven per-rank by
            # validate() and globally by full coverage here
            assert (filled > 0).all(), (seed, i)
        back = R.build_plan(tgt, src)
        locals_rt = {
            r: R.assemble_rank(back, r, _mem_read(locals_tgt))
            for r in src.ranks
        }
        for r in src.ranks:
            for a, b in zip(locals_rt[r], locals_src[r]):
                assert a.tobytes() == b.tobytes(), (seed, r)

    def test_balanced_blocks_survive_non_divisible_shrink(self):
        src = R.TreeLayout(
            [("dp", 4)], [0, 1, 2, 3],
            [R.LeafSpec((10, 3), "float32", ("dp",))],
        )
        tgt = src.retarget([0, 1, 2])
        plan = R.build_plan(src, tgt)
        # 10 rows over 3 ranks: balanced 3/3/4 split
        assert [plan.target.box(0, r).shape[0] for r in (0, 1, 2)] == [3, 3, 4]
        g = [np.arange(30, dtype=np.float32).reshape(10, 3)]
        locals_src = {r: R.slice_local(g, src, r) for r in src.ranks}
        for r in tgt.ranks:
            out = R.assemble_rank(plan, r, _mem_read(locals_src))
            assert np.array_equal(out[0], R.slice_local(g, tgt, r)[0])

    def test_validate_catches_tampered_plan(self):
        src = R.TreeLayout(
            [("dp", 2)], [0, 1], [R.LeafSpec((8,), "float32", ("dp",))]
        )
        plan = R.build_plan(src, src.retarget([0, 1]))
        rp = plan.for_rank(0)
        rp.segments[0].ranges[0] = R.Range(0, 4, 8)  # shift → gap at 0
        with pytest.raises(CheckpointError, match="gap|overlap"):
            plan.validate()

    def test_missing_sources_named_in_error(self):
        src = R.TreeLayout(
            [("dp", 4)], [0, 1, 2, 3],
            [R.LeafSpec((8, 2), "float32", ("dp",))],
        )
        plan = R.build_plan(src, src.retarget([0, 1]))
        plan.require_available([0, 1, 2, 3])
        with pytest.raises(CheckpointError, match=r"\[2, 3\]"):
            plan.require_available([0, 1])

    def test_replicas_grouped_as_one_cell(self):
        # params sharded only over tp: the dp axis replicates them — each tp
        # cell lists BOTH dp ranks as interchangeable owners.
        src = R.TreeLayout(
            [("dp", 2), ("tp", 2)], [0, 1, 2, 3],
            [R.LeafSpec((4, 8), "float32", (None, "tp"))],
        )
        cells = src.cells(0)
        assert [owners for _, owners in cells] == [(0, 2), (1, 3)]
        # losing one dp replica of each cell still covers a shrink
        plan = R.build_plan(src, src.retarget([0, 1]))
        plan.require_available([2, 3])

    def test_layout_meta_roundtrip(self):
        src = R.TreeLayout(
            [("dp", 2), ("tp", 2)], [0, 1, 2, 3],
            [
                R.LeafSpec((8, 4), "float32", ("dp", "tp")),
                R.LeafSpec((3,), "int32", (None,)),
            ],
        )
        rt = R.TreeLayout.from_meta(src.to_meta())
        assert rt.to_meta() == src.to_meta()
        assert R.extract_layout({"layout": src.to_meta()}).to_meta() == src.to_meta()
        assert R.extract_layout({}) is None
        with pytest.raises(CheckpointError):
            R.TreeLayout.from_meta({"schema": "bogus"})

    def test_retarget_rescales_dp_and_rejects_impossible(self):
        src = R.TreeLayout(
            [("dp", 4), ("tp", 2)], list(range(8)),
            [R.LeafSpec((16,), "float32", ("dp",))],
        )
        tgt = src.retarget(list(range(6)))
        assert dict(tgt.axes) == {"dp": 3, "tp": 2}
        with pytest.raises(CheckpointError, match="non-dp"):
            src.retarget(list(range(5)))
        explicit = src.retarget(list(range(8)), axes={"dp": 2, "tp": 4})
        assert dict(explicit.axes) == {"dp": 2, "tp": 4}

    def test_layout_validation_errors(self):
        with pytest.raises(CheckpointError, match="unknown axis"):
            R.TreeLayout(
                [("dp", 2)], [0, 1], [R.LeafSpec((4,), "float32", ("tp",))]
            )
        with pytest.raises(CheckpointError, match="more than one dim"):
            R.TreeLayout(
                [("dp", 2)], [0, 1],
                [R.LeafSpec((4, 4), "float32", ("dp", "dp"))],
            )
        with pytest.raises(CheckpointError, match="describe"):
            R.TreeLayout(
                [("dp", 3)], [0, 1], [R.LeafSpec((4,), "float32", (None,))]
            )
        with pytest.raises(CheckpointError, match="geometry mismatch"):
            R.build_plan(
                R.TreeLayout(
                    [("dp", 1)], [0], [R.LeafSpec((4,), "float32", (None,))]
                ),
                R.TreeLayout(
                    [("dp", 1)], [0], [R.LeafSpec((5,), "float32", (None,))]
                ),
            )

    def test_for_local_tree_aligns_with_pop_order(self):
        import jax

        from tpu_resiliency.parallel.mesh import checkpoint_layout
        from tpu_resiliency.platform.device import make_mesh

        mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices("cpu")[:4])
        tree = {
            "a": np.ones((4, 3), np.float32),  # dp-sharded rows (local view)
            "step": 7,                          # non-array leaf: skipped
            "z": np.ones((2, 5), np.float32),  # tp-sharded cols
        }
        from jax.sharding import PartitionSpec as P

        specs = {"a": P("dp"), "step": None, "z": P(None, "tp")}
        layout = checkpoint_layout(mesh, tree, specs)
        assert dict(layout.axes) == {"dp": 2, "tp": 2}
        assert [l.global_shape for l in layout.leaves] == [(8, 3), (2, 10)]
        # pop order == tree order of array leaves
        sd = PyTreeStateDict(dict(tree))
        popped = sd.pop_tensors()
        assert [tuple(t.shape) for t in popped] == [(4, 3), (2, 5)]


class TestRangedReadOp:
    def _pair(self, make_store):
        exs = []
        for rank in (0, 1):
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            exs.append(ex)
        return exs

    def test_fetch_ranges_roundtrip_with_crcs(self, make_store):
        ex0, ex1 = self._pair(make_store)
        try:
            payload = bytes(range(256)) * 4
            served = []

            def handler(req):
                served.append(req)
                return {"tag": "extra"}, [
                    payload[off : off + n] for _, off, n in req["ranges"]
                ]

            ex1.serve_ranges(handler)
            header, parts = ex0.fetch_ranges(
                1, {"ranges": [[0, 16, 32], [0, 512, 64]]}
            )
            assert header["ok"] and header["tag"] == "extra"
            assert bytes(parts[0]) == payload[16:48]
            assert bytes(parts[1]) == payload[512:576]
            assert header["crc_algo"] and len(header["crc32c"]) == 2
            assert served and served[0]["ranges"] == [[0, 16, 32], [0, 512, 64]]
        finally:
            ex0.close()
            ex1.close()

    def test_unserved_peer_is_a_classified_error(self, make_store):
        ex0, ex1 = self._pair(make_store)
        try:
            with pytest.raises(CheckpointError, match="serves no ranged reads"):
                ex0.fetch_ranges(1, {"ranges": [[0, 0, 4]]}, timeout=10.0)
        finally:
            ex0.close()
            ex1.close()

    def test_handler_exception_becomes_error_reply(self, make_store):
        ex0, ex1 = self._pair(make_store)
        try:
            def handler(req):
                raise CheckpointError("no such shard on this rank")

            ex1.serve_ranges(handler)
            with pytest.raises(CheckpointError, match="no such shard"):
                ex0.fetch_ranges(1, {"ranges": [[0, 0, 4]]}, timeout=10.0)
        finally:
            ex0.close()
            ex1.close()

    def test_concurrent_fetches_use_distinct_reply_tags(self, make_store):
        ex0, ex1 = self._pair(make_store)
        try:
            ex1.serve_ranges(
                lambda req: ({}, [bytes([req["ranges"][0][1] % 251]) * 8])
            )
            with cf.ThreadPoolExecutor(4) as pool:
                futs = [
                    pool.submit(
                        ex0.fetch_ranges, 1, {"ranges": [[0, i, 8]]}
                    )
                    for i in range(4)
                ]
                for i, f in enumerate(futs):
                    _, parts = f.result(timeout=30)
                    assert bytes(parts[0]) == bytes([i % 251]) * 8
        finally:
            ex0.close()
            ex1.close()


GLOBAL = np.arange(24 * 6, dtype=np.float32).reshape(24, 6)


class TestReshardE2E:
    """ACCEPTANCE: a checkpoint saved at N ranks loads at M ranks — shrink,
    grow, AND a changed DP/TP split of the same N — with byte-identical
    reassembled global state, the peer path moving only newly-owned ranges."""

    def _save_world(self, make_store, tmp_path, layout, factor=2, gen=0):
        root = str(tmp_path / "ckpt")

        def body(rank):
            comm = StoreComm(
                make_store(), rank, list(layout.ranks), timeout=30.0,
                generation=gen,
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=factor
                )
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat
                )
                tree = {
                    "w": R.slice_local([GLOBAL], layout, rank)[0],
                    "step": 11,
                }
                mgr.save(
                    1, PyTreeStateDict(tree), is_async=False, layout=layout
                )
                mgr.close()
            finally:
                ex.close()

        run_ranks(list(layout.ranks), body)
        return root

    def _load_world(
        self, make_store, root, world, gen, axes=None, target=None,
        iteration=None,
    ):
        def body(rank):
            comm = StoreComm(
                make_store(), rank, world, timeout=30.0, generation=gen
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat
                )
                hollow, tensors, meta = mgr.load_resharded(
                    target=target, axes=axes, iteration=iteration
                )
                mgr.close()
                return hollow, [np.asarray(t).copy() for t in tensors], meta
            finally:
                ex.close()

        return run_ranks(world, body)

    def test_shrink_grow_and_resplit_byte_identical(
        self, make_store, tmp_path, sink
    ):
        src = R.TreeLayout(
            [("dp", 4)], [0, 1, 2, 3],
            [R.LeafSpec(GLOBAL.shape, "float32", ("dp",))],
        )
        root = self._save_world(make_store, tmp_path, src)

        # -- shrink 4 → 3 (rank 3 preempted; its state lives on in r2's
        # mirror), then the shrunken world checkpoints at ITS OWN layout —
        # the "shrink, keep training" half of the elastic story.
        tgt3 = src.retarget([0, 1, 2])

        def shrink_and_save(rank):
            comm = StoreComm(
                make_store(), rank, [0, 1, 2], timeout=30.0, generation=1
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat, keep=2
                )
                hollow, tensors, meta = mgr.load_resharded()
                resumed = [np.asarray(t).copy() for t in tensors]
                layout = R.TreeLayout.from_meta(meta["layout"])
                mgr.save(
                    2, PyTreeStateDict({"w": resumed[0], "step": 12}),
                    is_async=False, layout=layout,
                )
                mgr.close()
                return hollow, resumed, meta
            finally:
                ex.close()

        out = run_ranks([0, 1, 2], shrink_and_save)
        locals3 = {}
        for rank, (hollow, tensors, meta) in zip([0, 1, 2], out):
            want = R.slice_local([GLOBAL], tgt3, rank)[0]
            assert np.array_equal(tensors[0], want), rank
            assert hollow["step"] == 11
            assert meta["layout"]["ranks"] == [0, 1, 2]
            locals3[rank] = tensors
        got, _ = _reassemble_global(tgt3, locals3, 0)
        assert np.array_equal(got, GLOBAL)
        # The wire's count: every byte of the state moved once, and the peer
        # path moved less than the one source shard (a quarter of the state)
        # that a full-mirror retrieve of rank 2's copy would have moved.
        fetched = [e.payload for e in sink if e.kind == "reshard_fetch"]
        assert sum(f["bytes"] for f in fetched) == GLOBAL.nbytes
        peer_bytes = sum(f["bytes"] for f in fetched if f["via"] == "peer")
        assert 0 < peer_bytes < GLOBAL.nbytes // 4

        # -- grow 3 → 4 (rank 3 returns with a wiped disk; newest iteration
        # is the shrunken world's save, so the resume is a true grow)
        import shutil

        shutil.rmtree(os.path.join(root, "s0", "r3"))
        out4 = self._load_world(make_store, root, [0, 1, 2, 3], gen=2)
        for rank, (hollow, tensors, meta) in zip([0, 1, 2, 3], out4):
            want = R.slice_local([GLOBAL], src, rank)[0]
            assert np.array_equal(tensors[0], want), rank
            assert hollow["step"] == 12
            assert meta["iteration"] == 2

        # -- changed split, same N: iteration 1's dp4 layout → dp2·tp2 (leaf
        # stays dp-sharded; tp replicates it, so pairs hold identical halves)
        out_rs = self._load_world(
            make_store, root, [0, 1, 2, 3], gen=3, axes={"dp": 2, "tp": 2},
            iteration=1,
        )
        tgt_rs = src.retarget([0, 1, 2, 3], axes={"dp": 2, "tp": 2})
        for rank, (hollow, tensors, meta) in zip([0, 1, 2, 3], out_rs):
            want = R.slice_local([GLOBAL], tgt_rs, rank)[0]
            assert np.array_equal(tensors[0], want), rank

        plans = [e for e in sink if e.kind == "reshard_plan"]
        directions = {e.payload["direction"] for e in plans}
        assert {"shrink", "grow", "resplit"} <= directions
        fetches = [e for e in sink if e.kind == "reshard_fetch"]
        assert any(e.payload["via"] == "peer" for e in fetches)
        assert any(e.payload["via"] == "local" for e in fetches)

    def test_reshard_metrics_aggregate(self, make_store, tmp_path, sink):
        src = R.TreeLayout(
            [("dp", 2)], [0, 1], [R.LeafSpec((8, 3), "float32", ("dp",))]
        )
        root = self._save_world(make_store, tmp_path, src)
        self._load_world(make_store, root, [0], gen=1)
        from tpu_resiliency.utils.metrics import aggregate

        reg = aggregate([{"kind": e.kind, **e.payload} for e in sink])
        prom = reg.to_prometheus()
        assert "tpu_reshard_bytes_total" in prom
        assert 'direction="shrink"' in prom
        assert "tpu_reshard_ranks_total" in prom

    def test_uncoverable_shrink_names_missing_ranks(
        self, make_store, tmp_path
    ):
        src = R.TreeLayout(
            [("dp", 4)], [0, 1, 2, 3],
            [R.LeafSpec(GLOBAL.shape, "float32", ("dp",))],
        )
        root = self._save_world(make_store, tmp_path, src)
        # Destroy every copy of ranks 2 and 3 (own shards AND mirrors):
        import shutil

        shutil.rmtree(os.path.join(root, "s0", "r2"))
        shutil.rmtree(os.path.join(root, "s0", "r3"))

        def body(rank):
            comm = StoreComm(
                make_store(), rank, [0, 1], timeout=30.0, generation=1
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat
                )
                with pytest.raises(CheckpointError) as exc:
                    mgr.load_resharded()
                mgr.close()
                return str(exc.value)
            finally:
                ex.close()

        msgs = run_ranks([0, 1], body)
        for m in msgs:
            assert "[2, 3]" in m, m

    def test_save_rejects_layout_disagreeing_with_tensors(self, tmp_path):
        """REGRESSION (found by the forked-process verify driver): a layout
        whose leaves are listed in tree-insertion order while the pytree
        flattens sorted-key first must fail AT SAVE TIME with a geometry
        error — not surface later as an unexplainable reshard
        'no live holder'."""
        mgr = LocalCheckpointManager(str(tmp_path / "ckpt"), rank=0, comm=None)
        # tree flattens sorted: "a" (2,2) then "z" (4,); layout lists them
        # swapped — the classic insertion-order mistake.
        bad = R.TreeLayout(
            [("dp", 1)], [0],
            [R.LeafSpec((4,), "float32", (None,)),
             R.LeafSpec((2, 2), "float32", (None,))],
        )
        sd = PyTreeStateDict(
            {"z": np.zeros((4,), np.float32), "a": np.zeros((2, 2), np.float32)}
        )
        with pytest.raises(CheckpointError, match="sorted-key"):
            mgr.save(1, sd, is_async=False, layout=bad)
        # leaf-count mismatch is also a save-time error
        sd2 = PyTreeStateDict({"a": np.zeros((2, 2), np.float32)})
        with pytest.raises(CheckpointError, match="leaves"):
            mgr.save(1, sd2, is_async=False, layout=bad)
        mgr.close()

    def test_load_rejects_header_disagreeing_layout(self, tmp_path):
        """Metas written before save-time validation existed (or hand-edited)
        must be cross-checked against the container's own header at load."""
        import pickle

        root = str(tmp_path / "ckpt")
        mgr = LocalCheckpointManager(root, rank=0, comm=None)
        good = R.TreeLayout(
            [("dp", 1)], [0], [R.LeafSpec((4,), "float32", (None,))]
        )
        mgr.save(
            1, PyTreeStateDict({"w": np.zeros((4,), np.float32)}),
            is_async=False, layout=good,
        )
        # Corrupt the EMBEDDED layout only (shape lie), rewriting the
        # container so its checksums stay valid.
        from tpu_resiliency.checkpoint import format as ckpt_format

        path = os.path.join(root, "s0", "r0", "iter_0000001_0_local.ckpt")
        hollow, tensors, meta = ckpt_format.read_payload(path)
        meta["layout"]["leaves"][0]["global_shape"] = [400]
        ckpt_format.write_payload(path, hollow, tensors, meta=meta)
        with pytest.raises(CheckpointError, match="container holds"):
            mgr.load_resharded()
        mgr.close()

    def test_explicit_iteration_fails_hard_without_fallback(
        self, make_store, tmp_path
    ):
        src = R.TreeLayout(
            [("dp", 1)], [0], [R.LeafSpec((4, 6), "float32", ("dp",))]
        )
        root = self._save_world(make_store, tmp_path, src, factor=1)
        mgr = LocalCheckpointManager(root, rank=0, comm=None)
        with pytest.raises(CheckpointError, match="iteration 9"):
            mgr.load_resharded(iteration=9)
        mgr.close()

    def test_single_rank_local_only_reshard(self, make_store, tmp_path):
        """comm=None world of one: a 2-rank checkpoint whose containers all
        sit on rank 0's disk (own shard + mirror) reshards to one rank with
        zero network."""
        src = R.TreeLayout(
            [("dp", 2)], [0, 1], [R.LeafSpec((6, 2), "float32", ("dp",))]
        )
        root = self._save_world(make_store, tmp_path, src)
        mgr = LocalCheckpointManager(root, rank=0, comm=None)
        hollow, tensors, meta = mgr.load_resharded()
        assert tensors[0].shape == (6, 2)
        assert np.array_equal(
            tensors[0], R.slice_local([GLOBAL[:6, :2].copy()], src.retarget([0]), 0)[0]
        )
        mgr.close()

    def test_placeholder_shapes_synced_to_target_world(
        self, make_store, tmp_path
    ):
        """The mesh-aware restore contract: after a resharded load the hollow
        skeleton's placeholders describe the TARGET world's local blocks (the
        saving world's shapes would mislead shape-driven sharding specs), and
        ``load_resharded_tree`` rebuilds a full tree from them."""
        from tpu_resiliency.checkpoint.state_dict import TensorPlaceholder

        src = R.TreeLayout(
            [("dp", 2)], [0, 1], [R.LeafSpec((8, 4), "float32", ("dp",))]
        )
        root = self._save_world(make_store, tmp_path, src)
        mgr = LocalCheckpointManager(root, rank=0, comm=None)
        hollow, tensors, meta = mgr.load_resharded()  # dp2 -> dp1
        import jax

        phs = [
            l
            for l in jax.tree_util.tree_flatten(
                hollow, is_leaf=lambda x: isinstance(x, TensorPlaceholder)
            )[0]
            if isinstance(l, TensorPlaceholder)
        ]
        assert [p.shape for p in phs] == [(8, 4)]  # target-local, not (4, 4)
        tree, meta2 = mgr.load_resharded_tree()
        assert tree["step"] == 11
        assert np.asarray(tree["w"]).shape == (8, 4)
        assert np.array_equal(
            np.asarray(tree["w"]), np.asarray(GLOBAL[:8, :4])
        )
        mgr.close()

    def test_corrupt_local_copy_falls_to_peer(self, make_store, tmp_path, sink):
        """A survivor whose mirror went bad mid-life quarantines it and
        ranged-fetches from the other replica holder instead."""
        src = R.TreeLayout(
            [("dp", 2)], [0, 1], [R.LeafSpec((8, 4), "float32", ("dp",))]
        )
        root = self._save_world(make_store, tmp_path, src, factor=2)
        # Flip a payload byte in rank 0's OWN shard copy; the mirror in r1
        # stays intact, so rank 0's reshard must fetch from rank 1.
        path = os.path.join(root, "s0", "r0", "iter_0000001_0_local.ckpt")
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) - 40)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x20]))

        def body(rank):
            comm = StoreComm(
                make_store(), rank, [0, 1], timeout=30.0, generation=1
            )
            ex = PeerExchange(make_store(), rank, timeout=10.0)
            ex.start()
            try:
                strat = CliqueReplicationStrategy(
                    comm, ex, replication_jump=1, replication_factor=2
                )
                mgr = LocalCheckpointManager(
                    root, rank=rank, comm=comm, replication=strat
                )
                hollow, tensors, meta = mgr.load_resharded()
                mgr.close()
                return [np.asarray(t).copy() for t in tensors]
            finally:
                ex.close()

        out = run_ranks([0, 1], body)
        for rank, tensors in zip([0, 1], out):
            want = R.slice_local([GLOBAL[:8, :4].copy()], src, rank)[0]
            assert np.array_equal(tensors[0], want), rank
        # Chunked (TPURES03) containers verify lazily per touched chunk, so
        # the corruption surfaces at the chunk-verify stage; a pre-chunk
        # container would have been caught by the whole-file reshard-verify.
        assert any(
            e.kind == "ckpt_quarantined"
            and e.payload.get("stage") in ("reshard-verify", "chunk-verify")
            for e in sink
        )
