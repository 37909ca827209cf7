"""Pallas TPU kernel for the telemetry reduction stage: fused masked median + totals.

The hot part of a scoring round is reducing raw timing windows ``[R, S, W]`` to
per-(rank, signal) medians and time-weights — the work the reference does with Python
loops over per-kernel deques + ``torch`` stats on host (``straggler/straggler.py:172-197``,
``reporting.py``'s pack/unpack). Here it is one Pallas kernel, tiled over ranks, that:

1. masks invalid ring-buffer slots (slot index ≥ count) to +inf,
2. computes each element's *stable rank* within its window via W compare/accumulate
   passes on the VPU (no sort, no gather — selection by rank counting, which maps onto
   TPU vector units far better than a bitonic network),
3. selects the median as the mean of the ``(n-1)//2``-th and ``n//2``-th order
   statistics by masked summation,
4. computes the masked total (the weight) in the same pass over VMEM-resident data.

The downstream scoring math (cross-rank min, weighted perf score, robust-z, EWMA) is
plain ``jnp`` in ``telemetry/scoring.py`` — it is O(R·S) and XLA fuses it into a couple
of reductions.

**One kernel, chosen on the chip.** Rank counting is O(W²). Two other formulations
stood beside it until PR 47: one ``[RT, S, W, W]`` all-pairs comparison block, and a
selection of the two order statistics bit by bit in 64 passes whatever W. A sweep on
one v5e chip on 2026-07-31 (W in 32..256, R in 256..4096) had the all-pairs form
compiling at W = 32 alone and 4-5x slower there, and the bit-select form slower than
this kernel at every W <= 128 and slower than XLA's sort at W = 128. PR 47's probe on
today's jax 0.9 / libtpu 0.0.34 (R = 4096, S = 64; W = 32, 128, 256) is in PERF.md
section 6 with its command. Both forms were deleted with the switches that selected
them. This kernel is the **default window reduction on TPU** for the mesh scoring
path up to ``MAX_WINDOW`` (``MeshTelemetry(use_pallas=None)`` selects by backend and
shape via :func:`pallas_supported`); past it, and on other backends, the XLA sort
lowering of ``telemetry/scoring.py`` runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _median_weights_kernel(data_ref, counts_ref, med_ref, weight_ref):
    data = data_ref[:]  # [RT, S, W] f32
    counts = counts_ref[:]  # [RT, S] i32
    rt, s, w = data.shape

    pos = jax.lax.broadcasted_iota(jnp.int32, (rt, s, w), dimension=2)
    valid = pos < counts[:, :, None]
    x = jnp.where(valid, data, jnp.inf)

    # Stable rank of each element within its window:
    #   rank_i = #{j : x_j < x_i} + #{j < i : x_j == x_i}
    # computed with W VPU compare passes in a fori_loop (bounded live temps — a static
    # unroll blows the VMEM stack). The j-th element is extracted with a positional
    # mask + reduction rather than dynamic_slice, which this Pallas lowering lacks.
    rank = jnp.zeros((rt, s, w), jnp.int32)

    def body(j, rank):
        sel = pos == j
        xj = jnp.sum(jnp.where(sel, x, 0.0), axis=2, keepdims=True)  # [RT, S, 1]
        xj = jnp.where(j < counts[:, :, None], xj, jnp.inf)  # invalid slot ⇒ +inf
        less = (xj < x).astype(jnp.int32)
        eq_before = ((xj == x) & (j < pos)).astype(jnp.int32)
        return rank + less + eq_before

    rank = jax.lax.fori_loop(0, w, body, rank)

    # median = mean of the (n-1)//2-th and n//2-th order statistics picked by
    # rank equality; weight = masked total.
    n = jnp.maximum(counts, 1)
    lo_idx = ((n - 1) // 2)[:, :, None]
    hi_idx = (n // 2)[:, :, None]
    x_finite = jnp.where(valid, data, 0.0)
    lo = jnp.sum(jnp.where(rank == lo_idx, x_finite, 0.0), axis=2)
    hi = jnp.sum(jnp.where(rank == hi_idx, x_finite, 0.0), axis=2)
    med = 0.5 * (lo + hi)
    med_ref[:] = jnp.where(counts > 0, med, jnp.inf)
    weight_ref[:] = jnp.sum(x_finite, axis=2)


#: Largest window the kernel is auto-selected for: its work grows with W², and
#: the sweep above had it ahead of the XLA sort at every tested R for W <= 128
#: and behind at W = 256. Past the cap :func:`pallas_supported` says no and the
#: callers take the sort.
MAX_WINDOW = 128

#: Ranks a block holds unless the budget below shrinks it.
RANK_TILE = 32

#: Largest ``[RT, S, W]`` element count a default block may hold: the largest
#: block that Mosaic-compiled and ran on v5e (32·64·256, ≈2 MB an array).
#: Default tiles halve until the block fits. Halving preserves the gate-checked
#: divisibility only when 32 | R; for other admitted rank counts
#: :func:`_snap_tile` snaps to the largest divisor of R within budget (and both
#: the gate and the kernel reject the degenerate near-prime-R grids that snap
#: produces, as well as single rank-rows that already exceed the budget).
BLOCK_ELEMS = 32 * 64 * 256

#: Snapped tiles more than this factor below the budget tile mean a
#: near-prime rank count shattered the grid into many tiny blocks — a
#: pathological launch far slower than the XLA sort, rejected loudly.
#: Relative (not an absolute tile floor): a snapped tile of 7 on a budget of 8
#: is a fine 2-block grid at R=14, while a snapped tile of 1 on a budget of 16
#: is a 31-block shatter at R=31.
SNAP_SHATTER_FACTOR = 4


def budget_rank_tile(s: int, w: int) -> int:
    """``RANK_TILE`` halved until an ``[RT, s, w]`` block fits the budget."""
    tile = RANK_TILE
    while tile > 1 and tile * s * w > BLOCK_ELEMS:
        tile //= 2
    return tile


def _snap_tile(r: int, s: int, w: int) -> int | None:
    """Default tile for ``[r, s, w]``: the largest divisor of ``r`` within the
    VMEM budget. ``None`` marks the shapes callers must reject: a single
    rank-row already over budget (no tile can fit), or a degenerate divisor
    far below the budget tile (shattered grid)."""
    if s * w > BLOCK_ELEMS:
        return None
    shrunk = min(budget_rank_tile(s, w), r)
    snapped = next(d for d in range(shrunk, 0, -1) if r % d == 0)
    if snapped * SNAP_SHATTER_FACTOR < shrunk:
        return None
    return snapped


def pallas_supported(n_ranks: int, window: int, signals: int) -> bool:
    """Shape gate for auto-selection, for ``n_ranks`` per shard: True where
    :func:`fused_median_weights` takes ``[n_ranks, signals, window]`` at its
    default tile and the window is one the kernel wins at. Its work is
    quadratic in the window, so past ``MAX_WINDOW`` the XLA sort serves; it
    tiles the rank axis under a VMEM block budget, so a rank-row over the
    budget, or a near-prime rank count whose snapped tile degenerates, goes
    to the sort too."""
    return window <= MAX_WINDOW and _snap_tile(n_ranks, signals, window) is not None


@functools.partial(jax.jit, static_argnames=("rank_tile", "interpret"))
def fused_median_weights(
    data: jax.Array,
    counts: jax.Array,
    *,
    rank_tile: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(medians [R,S], weights [R,S])`` from windows ``data [R,S,W]``, ``counts [R,S]``.

    Tiled over the rank axis; each grid step holds a ``[rank_tile, S, W]`` block in
    VMEM (by default ``RANK_TILE`` ranks, fewer where the block budget asks).
    ``interpret`` defaults to True off-TPU so tests run on CPU. A caller that
    names this kernel gets it at any window the budget holds;
    :func:`pallas_supported` is what keeps auto-selection under ``MAX_WINDOW``.
    """
    r, s, w = data.shape
    if rank_tile is None:
        rank_tile = _snap_tile(r, s, w)
        if rank_tile is None:
            # Over-budget blocks fail Mosaic, shattered grids silently run far
            # slower than the XLA sort — both fail loudly here.
            detail = (
                f"a single rank-row ({s}x{w} elements) exceeds the VMEM "
                f"block budget ({BLOCK_ELEMS})"
                if s * w > BLOCK_ELEMS
                else f"rank count {r} has no divisor near the budget "
                f"tile {budget_rank_tile(s, w)} (within "
                f"{SNAP_SHATTER_FACTOR}x) — the grid would shatter"
            )
            raise ValueError(
                f"median kernel at window {w}: {detail}; pass rank_tile "
                f"explicitly or use the XLA path"
            )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rank_tile = min(rank_tile, r)
    if r % rank_tile != 0:
        raise ValueError(f"ranks {r} not divisible by rank_tile {rank_tile}")

    grid = (r // rank_tile,)
    return pl.pallas_call(
        _median_weights_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rank_tile, s, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((rank_tile, s), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rank_tile, s), lambda i: (i, 0)),
            pl.BlockSpec((rank_tile, s), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, s), data.dtype),
            jax.ShapeDtypeStruct((r, s), data.dtype),
        ],
        interpret=interpret,
    )(data, counts)
