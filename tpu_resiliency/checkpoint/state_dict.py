"""Tensor-aware state dicts: split a pytree into payload arrays and a hollow skeleton.

TPU-native re-design of the reference's ``TensorAwareStateDict`` contract
(``checkpointing/local/base_state_dict.py:29-115``) and its ``BasicTensorAwareStateDict``
implementation (``checkpointing/local/basic_state_dict.py:57-188``). The reference walks
nested torch dicts; here the natural unit is a **JAX pytree**: any nested structure of
params / optimizer state / step counters. ``pop_tensors`` swaps every array leaf for a
:class:`TensorPlaceholder`, leaving a picklable "hollow" skeleton that can ride the
control plane (replication metadata, IPC) while the payload arrays move through the fast
path (device→host DMA, raw file IO, peer sockets).

Device round-trip: ``copy_tensors_to_host`` performs one batched ``jax.device_get`` (a
single D2H DMA per leaf, queued together — the analogue of the reference's pinned-memory
``non_blocking=True`` D2H copies, ``checkpointing/utils.py:85``); shardings are recorded
so ``restore_tensor_device`` can ``jax.device_put`` each leaf back onto the same mesh
layout after a restart.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Optional, Sequence

import numpy as np

from tpu_resiliency.exceptions import CheckpointError
from tpu_resiliency.utils.events import record as record_event


@dataclasses.dataclass
class TensorPlaceholder:
    """Stands in for an array leaf inside a hollow pytree.

    Analogue of the reference's ``TensorPlaceholder``
    (``checkpointing/local/basic_state_dict.py:30-54``), extended with the leaf's
    sharding so the array can be restored to its mesh layout.
    """

    shape: tuple
    dtype: str
    index: int
    sharding: Any = None  # jax.sharding.Sharding | None; not pickled across hosts

    def __getstate__(self):
        # Shardings reference device objects that do not pickle across processes;
        # the restore side supplies shardings from its own mesh instead.
        return {
            "shape": self.shape,
            "dtype": self.dtype,
            "index": self.index,
            "sharding": None,
        }

    def __setstate__(self, state):
        for k, v in state.items():
            setattr(self, k, v)


def _is_array(leaf: Any) -> bool:
    import jax

    return isinstance(leaf, (jax.Array, np.ndarray)) and not np.isscalar(leaf)


def leaf_specs(tensors: Sequence[Any]) -> list[dict]:
    """Container-format leaf specs (shape/dtype/nbytes) straight from device
    arrays — no host copy, no blocking: the pipelined save pickles the header
    and sizes the staging lease before any D2H byte has landed."""
    specs = []
    for t in tensors:
        dt = np.dtype(t.dtype)
        nbytes = int(np.prod(t.shape, dtype=np.int64)) * dt.itemsize
        specs.append({"shape": tuple(t.shape), "dtype": dt.name, "nbytes": nbytes})
    return specs


class HostSnapshot:
    """Leaf-by-leaf D2H resolver: the handle the pipelined save's background
    half consumes.

    Created by :meth:`PyTreeStateDict.copy_tensors_to_host_async`, which has
    already enqueued every leaf's ``copy_to_host_async()`` — all DMAs are in
    flight before this object reaches the background thread. ``resolve(i)``
    blocks only until leaf ``i``'s transfer lands (the analogue of the
    reference's per-tensor pinned-memory D2H events), stages it into the
    pooled lease when one is attached, and drops the device reference so
    device memory frees as the pipeline advances. The background writer
    resolves leaves in order; :meth:`detach` lets the train loop pull whatever
    is still on the device before a step that donates those arrays, so
    ``resolve`` is serialized by a lock.
    """

    def __init__(self, tensors: Sequence[Any], pool: Any = None):
        self._tensors: list = list(tensors)
        self.specs = leaf_specs(self._tensors)
        self.nbytes = sum(s["nbytes"] for s in self.specs)
        #: Lease acquisition is LAZY (first resolve, i.e. on the background
        #: thread): the foreground enqueue path never pays the miss-path
        #: allocation, nor blocks when both double-buffer slots are still
        #: leased to earlier saves' background halves.
        self._pool = pool
        self._lease = None
        self._released = False
        self._resolved: list[Optional[np.ndarray]] = [None] * len(self._tensors)
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._resolved)

    def _ensure_lease(self):
        if self._lease is None and self._pool is not None and not self._released:
            self._lease = self._pool.acquire(self.specs)
        return self._lease

    def resolve(self, i: int) -> np.ndarray:
        """Materialize leaf ``i`` on host (blocking only on ITS transfer)."""
        with self._lock:
            out = self._resolved[i]
            if out is None:
                t = self._tensors[i]
                lease = self._ensure_lease()
                if lease is not None:
                    out = lease.fill(i, t)
                else:
                    out = np.asarray(t)
                self._resolved[i] = out
                self._tensors[i] = None
            return out

    def detach(self) -> int:
        """Resolve every leaf that still lives only on the device; returns how
        many there were. After it the snapshot holds no device array: a jitted
        step may donate (delete) the arrays this save was handed without
        failing it. Their transfers were enqueued with the save, so this waits
        for DMAs already in flight, not for the writer."""
        pending = [i for i, t in enumerate(self._tensors) if t is not None]
        for i in pending:
            self.resolve(i)
        return len(pending)

    def resolve_view(self, i: int) -> memoryview:
        """Leaf ``i`` as the flat uint8 window writers and senders consume."""
        self.resolve(i)
        if self._lease is not None:
            return self._lease.raw_views[i]
        from tpu_resiliency.checkpoint.format import _raw_view

        return _raw_view(self._resolved[i])

    def resolve_all(self) -> list[np.ndarray]:
        return [self.resolve(i) for i in range(len(self))]

    def __iter__(self):
        for i in range(len(self)):
            yield self.resolve(i)

    def release(self) -> None:
        """Return the staging lease to its pool (idempotent). Call only after
        every consumer (file writer, peer sends) is done with the views."""
        self._released = True
        if self._lease is not None:
            self._lease.release()
            self._lease = None


class PyTreeStateDict:
    """A pytree with pop/insert tensor semantics for local checkpointing.

    Contract (mirrors reference ``base_state_dict.py:29-115``):

    - ``pop_tensors()`` → list of array leaves; ``self`` becomes hollow (picklable).
    - ``insert_tensors(tensors)`` → re-inflates the hollow skeleton.
    - ``copy_tensors_to_host()`` → payload becomes numpy (one batched D2H).
    - ``restore_tensor_device(shardings=...)`` → payload becomes device arrays again.
    - ``tree`` → the underlying pytree (hollow or full).
    """

    def __init__(self, tree: Any):
        self._tree = tree
        self._hollow = False
        self._tensors: Optional[list] = None
        self._shardings: Optional[list] = None
        self._snapshots: list[HostSnapshot] = []

    @classmethod
    def from_hollow(
        cls,
        hollow_tree: Any,
        tensors: Sequence[Any],
        shardings: Optional[Sequence[Any]] = None,
        device: Any = None,
    ) -> "PyTreeStateDict":
        """Rebuild a full state dict from a loaded (hollow skeleton, payload) pair,
        placing tensors back on device — the standard restore path after
        ``LocalCheckpointManager.load`` / ``ckpt_format.read_payload``."""
        sd = cls.__new__(cls)
        sd._tree = hollow_tree
        sd._hollow = True
        sd._tensors = list(tensors)
        sd._shardings = None
        sd._snapshots = []
        sd.restore_tensor_device(shardings=shardings, device=device)
        sd.insert_tensors(sd._tensors)
        return sd

    # -- introspection -----------------------------------------------------

    @property
    def is_hollow(self) -> bool:
        return self._hollow

    @property
    def tree(self) -> Any:
        if self._hollow:
            raise CheckpointError("state dict is hollow; insert_tensors() first")
        return self._tree

    @property
    def hollow_tree(self) -> Any:
        if not self._hollow:
            raise CheckpointError("state dict is not hollow; pop_tensors() first")
        return self._tree

    def tensors(self) -> list:
        if self._tensors is None:
            raise CheckpointError("tensors were not popped")
        return self._tensors

    # -- pop / insert ------------------------------------------------------

    def pop_tensors(self) -> list:
        """Replace every array leaf with a placeholder; return the arrays in order."""
        import jax

        if self._hollow:
            raise CheckpointError("pop_tensors() on an already-hollow state dict")
        leaves, treedef = jax.tree_util.tree_flatten(self._tree)
        tensors: list = []
        hollow_leaves: list = []
        for leaf in leaves:
            if _is_array(leaf):
                sharding = getattr(leaf, "sharding", None)
                hollow_leaves.append(
                    TensorPlaceholder(
                        shape=tuple(leaf.shape),
                        dtype=str(leaf.dtype),
                        index=len(tensors),
                        sharding=sharding,
                    )
                )
                tensors.append(leaf)
            else:
                hollow_leaves.append(leaf)
        self._tree = jax.tree_util.tree_unflatten(treedef, hollow_leaves)
        self._tensors = tensors
        self._hollow = True
        return tensors

    def insert_tensors(self, tensors: Sequence[Any]) -> None:
        """Inverse of :meth:`pop_tensors`."""
        import jax

        if not self._hollow:
            raise CheckpointError("insert_tensors() on a non-hollow state dict")
        leaves, treedef = jax.tree_util.tree_flatten(
            self._tree, is_leaf=lambda x: isinstance(x, TensorPlaceholder)
        )
        n_ph = sum(isinstance(leaf, TensorPlaceholder) for leaf in leaves)
        if n_ph != len(tensors):
            raise CheckpointError(f"expected {n_ph} tensors, got {len(tensors)}")
        # A hollow skeleton that deserialized but carries out-of-range indices
        # (a corrupt-but-unpicklable-looking container, a hand-built tree)
        # must fail as a classified checkpoint error, not an IndexError.
        bad = [
            leaf.index
            for leaf in leaves
            if isinstance(leaf, TensorPlaceholder)
            and not 0 <= leaf.index < len(tensors)
        ]
        if bad:
            raise CheckpointError(
                f"hollow skeleton placeholder index(es) {sorted(bad)} out of "
                f"range for {len(tensors)} tensors (corrupt skeleton?)"
            )
        full = [
            tensors[leaf.index] if isinstance(leaf, TensorPlaceholder) else leaf
            for leaf in leaves
        ]
        self._tree = jax.tree_util.tree_unflatten(treedef, full)
        self._tensors = list(tensors)
        self._hollow = False

    # -- device movement ---------------------------------------------------

    def copy_tensors_to_host(self) -> None:
        """One batched D2H transfer; payload becomes numpy, shardings recorded."""
        import jax

        if self._tensors is None:
            raise CheckpointError("pop_tensors() before copy_tensors_to_host()")
        self._shardings = [getattr(t, "sharding", None) for t in self._tensors]
        # device_get on the whole list queues all transfers before blocking on any.
        self._tensors = [np.asarray(x) for x in jax.device_get(self._tensors)]

    def copy_tensors_to_host_async(self, pool: Any = None) -> HostSnapshot:
        """Non-blocking counterpart of :meth:`copy_tensors_to_host`: enqueue
        every leaf's D2H DMA and return a :class:`HostSnapshot` that resolves
        leaves as their transfers complete.

        The caller-visible cost is "enqueue": one ``copy_to_host_async()`` call
        per leaf (microseconds) instead of one barrier over the whole payload.
        ``pool`` (a :class:`~tpu_resiliency.checkpoint.staging.HostStagingPool`)
        stages resolved leaves into recycled buffers so steady-state saves
        allocate nothing large; the lease is acquired lazily at first resolve
        (on the background thread) and the snapshot owns it — ``release()``
        when the background half is done. ``self`` keeps its device tensors
        untouched (shardings are recorded for a later restore).

        Until a leaf resolves, the snapshot needs its device array alive. A
        train step that DONATES the saved state deletes those arrays under the
        background writer ("Array has been deleted", on the CPU and on a v5e
        alike — chip run, PR 21): call :meth:`detach_device` before such a
        step (``HierarchicalCheckpointCallback`` does, every step start)."""
        if self._tensors is None:
            raise CheckpointError("pop_tensors() before copy_tensors_to_host_async()")
        self._shardings = [getattr(t, "sharding", None) for t in self._tensors]
        for t in self._tensors:
            start = getattr(t, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()
                except Exception:
                    # Enqueue is an optimization; resolve() still blocks
                    # correctly on backends without the async entry point.
                    pass
        snapshot = HostSnapshot(self._tensors, pool=pool)
        self._snapshots.append(snapshot)
        return snapshot

    def detach_device(self) -> int:
        """Make every async snapshot taken from this state dict independent of
        the device arrays (:meth:`HostSnapshot.detach`); returns the number of
        leaves that had to be pulled. The wait is a train-loop stall caused by
        the save, so it is recorded as ``ckpt_foreground_blocked``."""
        t0 = time.perf_counter()
        pulled = sum(s.detach() for s in self._snapshots)
        self._snapshots.clear()
        if pulled:
            record_event(
                "checkpoint", "ckpt_foreground_blocked",
                duration_s=time.perf_counter() - t0, engine="detach",
                leaves=pulled,
            )
        return pulled

    def _align_shardings_pytree(self, shardings) -> list:
        """Flatten a shardings pytree that mirrors the saved tree's structure into a
        flat list aligned with the popped tensor order. Non-array leaves in the saved
        tree (e.g. a step counter) are allowed: their corresponding shardings-pytree
        entries are ignored."""
        import jax

        # None must count as a leaf on BOTH sides (it is jax's empty node by
        # default): in the saved tree it may be an optional field, in the
        # shardings pytree it means "default placement".
        is_ph = lambda x: isinstance(x, TensorPlaceholder) or x is None  # noqa: E731
        tree_leaves, tree_def = jax.tree_util.tree_flatten(self._tree, is_leaf=is_ph)
        sh_leaves, sh_def = jax.tree_util.tree_flatten(
            shardings, is_leaf=lambda x: x is None
        )
        if len(sh_leaves) != len(tree_leaves) or sh_def != tree_def:
            raise CheckpointError(
                f"shardings pytree does not mirror the saved tree — pass a pytree "
                f"with a Sharding/None at each saved-tree leaf, or a flat "
                f"per-tensor sequence.\n  shardings: {len(sh_leaves)} leaves, "
                f"{sh_def}\n  saved tree: {len(tree_leaves)} leaves, {tree_def}"
            )
        out: list = [None] * len(self._tensors)
        cursor = 0  # full-tree case: arrays appear in tree order == pop order
        for leaf, s in zip(tree_leaves, sh_leaves):
            if isinstance(leaf, TensorPlaceholder):
                out[leaf.index] = s
            elif _is_array(leaf):
                out[cursor] = s
                cursor += 1
        return out

    def restore_tensor_device(
        self,
        shardings: Optional[Sequence[Any]] = None,
        device: Any = None,
    ) -> None:
        """``jax.device_put`` the payload back (mesh shardings > explicit device > default).

        ``shardings`` may be a flat sequence of shardings (aligned with the popped
        tensor list) OR a pytree mirroring the saved tree's structure, with a
        ``Sharding`` or ``None`` (default placement) at each leaf."""
        import jax

        if self._tensors is None:
            raise CheckpointError("no tensors to restore")
        target = shardings if shardings is not None else self._shardings
        # Interpretation order for a list/tuple of placement-like entries
        # (Sharding, Device, None):
        #   1. length == popped-tensor count → the flat per-tensor form (exact);
        #   2. otherwise, a pytree mirroring a list-rooted saved tree → aligned
        #      structurally (handles non-array leaves interleaved with tensors);
        #   3. otherwise, the legacy flat form with prefix semantics (shorter
        #      lists pad the tail with default placement — the long-standing
        #      behavior of the `i < len(target)` guard below).
        # Any container with non-placement entries is always a mirrored pytree.
        if target is not None and not isinstance(target, (list, tuple)):
            target = self._align_shardings_pytree(target)
        elif target is not None:
            all_placement = all(
                s is None or isinstance(s, (jax.sharding.Sharding, jax.Device))
                for s in target
            )
            if not (all_placement and len(target) == len(self._tensors)):
                try:
                    target = self._align_shardings_pytree(target)
                except CheckpointError:
                    if not all_placement:
                        raise
                    # legacy flat prefix form; the guard below pads the tail
        out = []
        for i, t in enumerate(self._tensors):
            s = target[i] if target is not None and i < len(target) else None
            if s is not None:
                out.append(jax.device_put(t, s))
            elif device is not None:
                out.append(jax.device_put(t, device))
            else:
                out.append(jax.device_put(t))
        self._tensors = out
        if self._hollow:
            return
        # Payload already re-inserted: rebuild the tree with the new device arrays.
        self.insert_if_full()

    def insert_if_full(self) -> None:
        if not self._hollow and self._tensors is not None:
            # Re-thread device arrays through the tree by temporarily hollowing.
            tensors = self._tensors
            self.pop_tensors()
            self.insert_tensors(tensors)


def split_tree(tree: Any) -> tuple[PyTreeStateDict, list]:
    """Convenience: wrap + pop in one call. Returns (hollow wrapper, tensors)."""
    sd = PyTreeStateDict(tree)
    tensors = sd.pop_tensors()
    return sd, tensors


def tree_size_bytes(tensors: Sequence[Any]) -> int:
    total = 0
    for t in tensors:
        total += int(np.prod(t.shape)) * np.dtype(
            t.dtype if not hasattr(t.dtype, "name") else t.dtype.name
        ).itemsize
    return total


def sync_placeholder_shapes(hollow_tree: Any, tensors: Sequence[Any]) -> Any:
    """Update a hollow skeleton's placeholders to the ACTUAL payload geometry.

    After an elastic reshard (``local_manager.load_resharded``) the loaded
    skeleton's placeholders still describe the SAVING world's local blocks;
    the reassembled tensors are the TARGET world's. Shape-driven consumers —
    ``make_restore_shardings`` spec functions, shape assertions in user
    restore code — must see the target truth, so the reshard load path runs
    this before handing the skeleton out. In-place on the placeholders;
    returns ``hollow_tree`` for chaining."""
    import jax

    leaves = jax.tree_util.tree_flatten(
        hollow_tree, is_leaf=lambda x: isinstance(x, TensorPlaceholder)
    )[0]
    for leaf in leaves:
        if isinstance(leaf, TensorPlaceholder) and 0 <= leaf.index < len(tensors):
            t = tensors[leaf.index]
            leaf.shape = tuple(t.shape)
            leaf.dtype = np.dtype(getattr(t.dtype, "name", t.dtype)).name
    return hollow_tree


def make_restore_shardings(
    hollow: Any, spec_fn: Callable[[TensorPlaceholder], Any]
) -> list:
    """Build a sharding list for ``restore_tensor_device`` from a hollow skeleton."""
    import jax

    leaves = jax.tree_util.tree_flatten(
        hollow, is_leaf=lambda x: isinstance(x, TensorPlaceholder)
    )[0]
    placeholders = [leaf for leaf in leaves if isinstance(leaf, TensorPlaceholder)]
    placeholders.sort(key=lambda p: p.index)
    return [spec_fn(p) for p in placeholders]
