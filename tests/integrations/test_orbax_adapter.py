"""The loop protocol driving orbax (code this framework didn't write): save via
hooks, crash, rebuild the manager, restore, and finish — the ecosystem-adapter
proof (review round 3 item 10; reference analogue:
``ptl_resiliency/local_checkpoint_callback.py:101-203``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resiliency.integrations import OrbaxCheckpointCallback
from tpu_resiliency.integrations.loop import LoopContext, run_training


def _step_fn(state, step):
    return {"w": state["w"] + 1.0, "step": jnp.asarray(step)}


def _init_state():
    return {"w": jnp.zeros((4,)), "step": jnp.asarray(0)}


def test_save_restore_roundtrip(tmp_path):
    cb = OrbaxCheckpointCallback(str(tmp_path / "orbax"), every=2)
    ctx = run_training(_step_fn, _init_state(), num_steps=6, callbacks=[cb])
    assert float(ctx.state["w"][0]) == 6.0
    assert cb.latest_step() == 5  # saves after steps 1, 3, 5
    cb.close()

    # A fresh process/manager (post-crash) restores the newest step and resumes.
    cb2 = OrbaxCheckpointCallback(str(tmp_path / "orbax"), every=2)
    ctx2 = LoopContext()
    ctx2.state = _init_state()
    assert cb2.restore_latest(ctx2)
    assert ctx2.start_step == 6
    np.testing.assert_array_equal(np.asarray(ctx2.state["w"]), np.full((4,), 6.0))

    # Resume the loop from the restored step and run to 8.
    ctx3 = run_training(
        _step_fn, ctx2.state, num_steps=8, callbacks=[cb2], ctx=ctx2
    )
    assert float(ctx3.state["w"][0]) == 8.0
    assert cb2.latest_step() == 7
    cb2.close()


def test_restore_empty_returns_false(tmp_path):
    cb = OrbaxCheckpointCallback(str(tmp_path / "empty"), every=2)
    ctx = LoopContext()
    ctx.state = _init_state()
    assert not cb.restore_latest(ctx)
    assert ctx.start_step == 0
    cb.close()


def test_retention_prunes_old_steps(tmp_path):
    cb = OrbaxCheckpointCallback(str(tmp_path / "keep"), every=1, max_to_keep=2)
    run_training(_step_fn, _init_state(), num_steps=5, callbacks=[cb])
    cb.manager.wait_until_finished()
    steps = sorted(cb.manager.all_steps())
    assert steps == [3, 4], steps
    cb.close()


def test_composes_with_local_tier(tmp_path):
    """Both tiers on one loop: orbax global saves + the framework's local-manager
    saves, from independent callbacks."""
    from tpu_resiliency.checkpoint import LocalCheckpointManager, PyTreeStateDict
    from tpu_resiliency.integrations import HierarchicalCheckpointCallback

    local_mgr = LocalCheckpointManager(str(tmp_path / "local"), rank=0)
    local_cb = HierarchicalCheckpointCallback(
        local_manager=local_mgr, local_every=2
    )
    orbax_cb = OrbaxCheckpointCallback(str(tmp_path / "orbax"), every=3)
    run_training(
        _step_fn, _init_state(), num_steps=6, callbacks=[local_cb, orbax_cb]
    )
    local_mgr.queue.maybe_finalize_async_calls(blocking=True)
    # Local tier records steps-completed (6); orbax records the 0-based step (5).
    assert local_mgr.find_latest() == 6
    assert orbax_cb.latest_step() == 5
    orbax_cb.close()
    local_cb.close()
