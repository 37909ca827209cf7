"""The hierarchical-tier recovery story, end to end (review round 4 item 5): train
with BOTH tiers, lose the entire local tier in the crash, restart — the same
callback seam restores from the Orbax global tier — and the rebuilt replication
group repopulates the local tier with coverage-complete saves.

Reference analogue: ``ptl_resiliency/local_checkpoint_callback.py:101-203``
(HierarchicalCheckpointIO's whole point is the global fallback) +
``base_manager.py:156-203`` coverage logic.
"""

import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from tests.checkpoint.test_local import run_ranks
from tpu_resiliency.checkpoint.comm import PeerExchange, StoreComm
from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
from tpu_resiliency.checkpoint.replication import CliqueReplicationStrategy
from tpu_resiliency.integrations import (
    HierarchicalCheckpointCallback,
    OrbaxCheckpointCallback,
)
from tpu_resiliency.integrations.loop import LoopContext, run_training
from tpu_resiliency.platform.store import CoordStore


def _step_fn(state, step):
    return {"w": state["w"] + 1.0, "step": jnp.asarray(step)}


def _init_state():
    return {"w": jnp.zeros((4,)), "step": jnp.asarray(0)}


def test_local_tier_lost_orbax_restores_replication_repopulates(tmp_path, kv_server):
    world = 4
    orbax_dir = str(tmp_path / "orbax")
    node_dir = lambda r: str(tmp_path / f"node{r}")  # per-rank "node-local disk"
    stores = []

    def make_store():
        s = CoordStore("127.0.0.1", kv_server.port, timeout=30.0)
        stores.append(s)
        return s

    # ---- phase 1: world 4, local saves every 2 steps (cliques [0,1],[2,3]),
    # rank 0 additionally writes the Orbax global tier every 3 steps.
    def train_phase(rank):
        comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
        ex = PeerExchange(make_store(), rank, timeout=30.0)
        ex.start()
        try:
            strat = CliqueReplicationStrategy(
                comm, ex, replication_jump=1, replication_factor=2
            )
            mgr = LocalCheckpointManager(
                node_dir(rank), rank=rank, comm=comm, replication=strat
            )
            local_cb = HierarchicalCheckpointCallback(
                local_manager=mgr, local_every=2
            )
            cbs = [local_cb]
            orbax_cb = None
            if rank == 0:
                orbax_cb = OrbaxCheckpointCallback(orbax_dir, every=3)
                cbs.append(orbax_cb)
            ctx = run_training(_step_fn, _init_state(), num_steps=4, callbacks=cbs)
            assert float(ctx.state["w"][0]) == 4.0
            assert mgr.find_latest() == 4  # iterations 2 and 4 saved, covered
            if orbax_cb is not None:
                assert orbax_cb.latest_step() == 2  # saved after step idx 2 (w=3)
                orbax_cb.close()
            mgr.close()
        finally:
            ex.close()

    run_ranks(world, train_phase, timeout=240.0)

    # ---- the crash: every node's local disk is lost (beyond any coverage),
    # and ranks 2/3 don't come back.
    for r in range(world):
        shutil.rmtree(node_dir(r))

    # ---- phase 2: survivors [0,1] restart with fresh processes. Managers come
    # up configured for the old world, adopt the survivor group through the
    # callback's rebuild seam, find the local tier unrestorable, fall back to
    # Orbax through the same seam, resume, and repopulate the local tier.
    survivors = [0, 1]

    def restart_phase(rank):
        stale_comm = StoreComm(make_store(), rank, list(range(world)), timeout=30.0)
        ex = PeerExchange(make_store(), rank, timeout=30.0)
        ex.start()
        try:
            strat = CliqueReplicationStrategy(
                stale_comm, ex, replication_jump=1, replication_factor=2
            )
            mgr = LocalCheckpointManager(
                node_dir(rank), rank=rank, comm=stale_comm, replication=strat
            )
            local_cb = HierarchicalCheckpointCallback(
                local_manager=mgr, local_every=2
            )
            new_comm = StoreComm(make_store(), rank, survivors, timeout=30.0, generation=1)
            local_cb.rebuild_group(new_comm)
            assert strat.my_group == survivors

            ctx = LoopContext()
            ctx.state = _init_state()
            # Local tier: gone beyond coverage — the seam must say so.
            assert local_cb.restore_latest(ctx) is False
            # Same seam, next tier down: Orbax restores step 2 (w=3).
            orbax_cb = OrbaxCheckpointCallback(
                orbax_dir, every=3 if rank == 0 else 0
            )
            assert orbax_cb.restore_latest(ctx) is True
            assert ctx.start_step == 3
            np.testing.assert_array_equal(np.asarray(ctx.state["w"]), np.full((4,), 3.0))

            cbs = [local_cb] + ([orbax_cb] if rank == 0 else [])
            ctx = run_training(_step_fn, ctx.state, num_steps=6, callbacks=cbs, ctx=ctx)
            assert float(ctx.state["w"][0]) == 6.0

            # The local tier is repopulated with coverage-complete saves over
            # the rebuilt group: find_latest agrees at 6 and every survivor
            # holds its own shard AND its clique peer's mirror.
            assert mgr.find_latest() == 6
            held = {i.owner for i in mgr.local_ids() if i.iteration == 6}
            assert held == set(survivors), held
            tree, _ = mgr.load_tree(6)
            np.testing.assert_array_equal(np.asarray(tree["w"]), np.full((4,), 6.0))
            orbax_cb.close()
            mgr.close()
        finally:
            ex.close()

    run_ranks(len(survivors), restart_phase, timeout=240.0)
    for s in stores:
        s.close()
