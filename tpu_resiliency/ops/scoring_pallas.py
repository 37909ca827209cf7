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

Measured on one v5e chip (4096×64×32) by **on-device program duration**: a 2026-07-31
capture read this kernel's scoring round at 4.31 ms against 8.43 ms for XLA's
sort-based ``masked_median`` lowering, identical F1, and PR 21's chip run read 4.309 ms
for the same round on today's jax 0.9 / libtpu 0.0.34 (the XLA side was not measured
again). It is therefore the **default window reduction on TPU** for the mesh scoring
path (``MeshTelemetry(use_pallas=None)`` auto-selects by backend and shape via
:func:`pallas_supported`); non-TPU backends use the XLA lowering. Rank-counting is
O(W²), so auto-selection caps it at a window crossover and switches to the O(32·W)
radix-select kernel beyond it (``auto_mode``); ``BASELINE.md`` keeps the one sweep
of the three variants on a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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
    _write_median_and_weight(data, counts, valid, rank, med_ref, weight_ref)


def _write_median_and_weight(data, counts, valid, rank, med_ref, weight_ref):
    """Shared selection tail: median = mean of the (n-1)//2-th and n//2-th order
    statistics picked by rank equality; weight = masked total."""
    n = jnp.maximum(counts, 1)
    lo_idx = ((n - 1) // 2)[:, :, None]
    hi_idx = (n // 2)[:, :, None]
    x_finite = jnp.where(valid, data, 0.0)
    lo = jnp.sum(jnp.where(rank == lo_idx, x_finite, 0.0), axis=2)
    hi = jnp.sum(jnp.where(rank == hi_idx, x_finite, 0.0), axis=2)
    med = 0.5 * (lo + hi)
    med_ref[:] = jnp.where(counts > 0, med, jnp.inf)
    weight_ref[:] = jnp.sum(x_finite, axis=2)


def _median_weights_pairwise_kernel(data_ref, counts_ref, med_ref, weight_ref):
    """All-pairs variant: one [RT, S, W, W] comparison block instead of W
    sequential VPU passes — more VMEM (quadratic temporaries, so it runs at a
    smaller rank tile) but no serial loop. Which formulation wins is a question for
    a chip, not an assumption: ``BASELINE.md`` has the one sweep that asked it."""
    data = data_ref[:]  # [RT, S, W] f32
    counts = counts_ref[:]  # [RT, S] i32
    rt, s, w = data.shape

    pos = jax.lax.broadcasted_iota(jnp.int32, (rt, s, w), dimension=2)
    valid = pos < counts[:, :, None]
    x = jnp.where(valid, data, jnp.inf)

    xi = x[:, :, :, None]  # the element whose rank we compute
    xj = x[:, :, None, :]  # everything it is compared against
    pi = pos[:, :, :, None]
    pj = pos[:, :, None, :]
    rank = jnp.sum(
        (xj < xi).astype(jnp.int32) + ((xj == xi) & (pj < pi)).astype(jnp.int32),
        axis=3,
    )
    _write_median_and_weight(data, counts, valid, rank, med_ref, weight_ref)


def _radix_select(x, key, cand0, k):
    """Exact k-th smallest (0-indexed among ``cand0`` elements) per trailing-W
    group via MSB-first radix selection on the 32 sort-key bits: 32 masked
    count-and-narrow passes, O(32·W) — the O(W·log) formulation that keeps the
    Pallas path winning where rank-counting's O(W²) would hand large windows
    back to the XLA sort. All remaining candidates after 32 bits share the
    selected value bit-for-bit, so extraction is a masked min.

    Mosaic constraint (hit on real v5e, invisible in interpret mode): ``i1``
    vectors cannot be reshaped (``tpu.reshape vector<...xi1>`` is rejected), so
    the candidate mask and the branch predicate are carried as int32 0/1 and
    only compared elementwise — never broadcast with ``[..., None]`` as bools."""
    def body(i, carry):
        cand, k = carry  # cand: int32 0/1 mask [.., W]; k: int32 [..]
        bit = 31 - i
        # Bits of the UNSIGNED order key u = key ^ 0x80000000: bit 31 is the
        # inverted sign of the signed key (XOR with 1 exactly when bit == 31);
        # bits 30..0 coincide with key's.
        raw = jax.lax.shift_right_logical(key, bit) & 1
        bitval = raw ^ (bit == 31).astype(jnp.int32)
        c0 = jnp.sum(cand * (1 - bitval), axis=-1)
        go_zero = (k < c0).astype(jnp.int32)
        want = 1 - go_zero[..., None]  # desired bit value in the kept branch
        cand = cand * (bitval == want).astype(jnp.int32)
        k = k - (1 - go_zero) * c0
        return cand, k

    cand, _ = jax.lax.fori_loop(0, 32, body, (cand0.astype(jnp.int32), k))
    return jnp.min(jnp.where(cand == 1, x, jnp.inf), axis=-1)


def _median_weights_radix_kernel(data_ref, counts_ref, med_ref, weight_ref):
    """O(W·log)-class variant: radix-select both median order statistics
    instead of rank-counting. 64 VPU passes total regardless of W, so it is the
    auto-selected mode past the loop kernel's measured window cap. Assumes no
    NaNs (timing windows; invalid slots are masked before keying)."""
    data = data_ref[:]  # [RT, S, W] f32
    counts = counts_ref[:]  # [RT, S] i32
    rt, s, w = data.shape

    pos = jax.lax.broadcasted_iota(jnp.int32, (rt, s, w), dimension=2)
    valid = pos < counts[:, :, None]
    x = jnp.where(valid, data, jnp.inf)

    # Monotone float→int32 key: signed comparison of the key matches float
    # order (non-negatives keep their bits; negatives bit-complement then flip
    # the sign bit).
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(b >= 0, b, jnp.bitwise_xor(jnp.bitwise_not(b), jnp.int32(-(2**31))))

    n = jnp.maximum(counts, 1)
    lo = _radix_select(x, key, valid, (n - 1) // 2)
    hi = _radix_select(x, key, valid, n // 2)
    med = 0.5 * (lo + hi)
    med_ref[:] = jnp.where(counts > 0, med, jnp.inf)
    weight_ref[:] = jnp.sum(jnp.where(valid, data, 0.0), axis=2)


#: Largest window the O(W²) kernels (loop / pairwise) are auto-selected for;
#: beyond it auto-selection switches to the radix kernel (O(32·W), no cap)
#: instead of falling back to the XLA sort. The value comes from a sweep on
#: one v5e chip on 2026-07-31 (W∈{32..256} × R∈{256..4096}, an older jax and
#: compiler; its record is gone): the loop kernel beat both the XLA sort and
#: the radix kernel at every tested R for W≤128 and lost at W=256. Not measured
#: on today's installation — ROADMAP queues that (one probe on the chip).
#: ``$TPU_RESILIENCY_PALLAS_MAX_WINDOW`` overrides it.
DEFAULT_MAX_WINDOW = 128
MAX_WINDOW_ENV = "TPU_RESILIENCY_PALLAS_MAX_WINDOW"

#: Opt-in for AUTO-selecting the radix kernel past the loop cap (explicit
#: ``mode="radix"`` always works). Default off on that same 2026-07-31 sweep:
#: radix lost to the loop kernel at every W≤128 and to the XLA sort at W=128,
#: and at W=256 — the one regime it could win — it did not compile then. It
#: does now: at R=4096, S=64, W=256 it compiles to a ``tpu_custom_call`` for a
#: described v5e (``tests/platform/test_chip_compile.py``, PR 21). Whether it
#: beats the sort there is not measured; flip only once a sweep shows it.
RADIX_ENV = "TPU_RESILIENCY_PALLAS_RADIX"
DEFAULT_RADIX_AUTO = False

#: Modes whose work grows quadratically with the window (subject to the cap).
_QUADRATIC_MODES = ("loop", "pairwise")

#: Pairwise has its own, smaller bound: the 2026-07-31 sweep had it compiling
#: only at W=32 on v5e (S-folded; Mosaic rejected its 4-D blocks at W=64 even
#: folded) and losing to the loop kernel 4-5x where it ran — the shared
#: loop cap must not re-open a gate that measurement closed.
PAIRWISE_MAX_WINDOW = 32


def max_auto_window() -> int:
    import os

    try:
        return int(os.environ.get(MAX_WINDOW_ENV, DEFAULT_MAX_WINDOW))
    except ValueError:
        return DEFAULT_MAX_WINDOW


def radix_auto_enabled() -> bool:
    import os

    v = os.environ.get(RADIX_ENV)
    if v is None:
        return DEFAULT_RADIX_AUTO
    return v.strip().lower() in ("1", "on", "true", "yes")


def auto_mode(window: int) -> str:
    """Mode choice for an auto-selected Pallas path: the measured-winning
    quadratic ``loop`` kernel up to the window cap, the scaling-safe ``radix``
    kernel beyond it."""
    return "loop" if window <= max_auto_window() else "radix"


def default_rank_tile(mode: str) -> int:
    # pairwise materializes [RT, S, W, W] temporaries — quadratic VMEM, so it
    # runs at a much smaller rank tile.
    return 8 if mode == "pairwise" else 32


#: Largest [RT, S, W] element count a default block may hold, per mode —
#: each set to the largest block that Mosaic-compiled on v5e in the
#: 2026-07-31 sweep. The radix kernel carries more concurrent W-sized
#: temporaries than the loop kernel (x, int32 key, candidate mask, plus the
#: selection carries): then its compile failed at 32·64·256-element blocks
#: (≈2 MB/array, ~6 live arrays brushes VMEM) while every 32·64·128 block
#: passed, and the loop kernel compiled and ran at 32·64·256, so its budget
#: is 2× radix's. Under this budget radix at W=256 takes a 16-rank tile and
#: compiles on today's compiler (PR 21); whether the budget can grow is
#: queued with the window caps. Default tiles halve until the block fits the
#: budget. Halving preserves the gate-checked divisibility only when 32 | R;
#: for other admitted rank counts :func:`_snap_tile` snaps to the largest
#: divisor of R within budget (and both the gate and the kernel reject the
#: degenerate near-prime-R grids that snap produces, as well as single
#: rank-rows that already exceed the budget).
MODE_BLOCK_ELEMS = {
    "loop": 32 * 64 * 256,
    "radix": 32 * 64 * 128,
}

#: Snapped tiles more than this factor below the budget tile mean a
#: near-prime rank count shattered the grid into many tiny blocks — a
#: pathological launch far slower than the XLA sort, rejected loudly like
#: pairwise's near-prime S fold. Relative (not an absolute tile floor): a
#: snapped tile of 7 on a budget of 8 is a fine 2-block grid at R=14, while
#: a snapped tile of 1 on a budget of 16 is a 31-block shatter at R=31.
SNAP_SHATTER_FACTOR = 4


def mode_rank_tile(mode: str, s: int, w: int, base: int = 32) -> int:
    tile = base
    budget = MODE_BLOCK_ELEMS[mode]
    while tile > 1 and tile * s * w > budget:
        tile //= 2
    return tile


def _pairwise_fold_divisor(s: int) -> int:
    """Largest signal-group size ≤32 that divides ``s`` — the S-fold unit the
    pairwise kernel uses to stay under Mosaic's 4-D block limit. Shared by the
    kernel's fold path and the shape gate so both always agree on which
    near-prime signal counts are rejected (< 8 degenerates the grid)."""
    return next(d for d in range(32, 0, -1) if s % d == 0)


def _snap_tile(mode: str, r: int, s: int, w: int, base: int = 32) -> int | None:
    """Default tile for ``[r, s, w]`` in a budgeted mode: the largest divisor
    of ``r`` within the VMEM budget. ``None`` marks the shapes callers must
    reject: a single rank-row already over budget (no tile can fit), or a
    degenerate divisor far below the budget tile (shattered grid)."""
    if s * w > MODE_BLOCK_ELEMS[mode]:
        return None
    shrunk = min(mode_rank_tile(mode, s, w, base), r)
    snapped = next(d for d in range(shrunk, 0, -1) if r % d == 0)
    if snapped * SNAP_SHATTER_FACTOR < shrunk:
        return None
    return snapped


def pallas_supported(
    n_ranks: int,
    rank_tile: int | None = None,
    mode: str | None = None,
    window: int | None = None,
    signals: int | None = None,
) -> bool:
    """Shape gate for auto-selection: the kernel tiles the rank axis, so the
    per-shard rank count must be a whole number of tiles (or fit in one). Pass
    the same ``mode``/``rank_tile`` that will be given to
    :func:`fused_median_weights`; ``mode=None`` means :func:`auto_mode` (which
    needs ``window``). Pass ``signals`` too when known: the budgeted modes'
    (loop/radix) VMEM block budget can shrink their default tile, and only
    with the signal count can the gate mirror that shrink (and reject the
    near-prime rank counts whose snapped tile degenerates, or single
    rank-rows that exceed the budget outright).

    An explicitly quadratic ``mode`` is rejected past the measured window cap —
    auto-selection must not hand a W=128 user a silent O(W²) blowup. With mode
    auto, windows past the cap route to the radix kernel only once it is
    device-measured/opted-in (:func:`radix_auto_enabled`); until then they
    fall back to the XLA sort."""
    if mode is None:
        mode = auto_mode(window) if window is not None else "loop"
        if mode == "radix" and not radix_auto_enabled():
            return False
    elif window is not None and mode in _QUADRATIC_MODES:
        cap = PAIRWISE_MAX_WINDOW if mode == "pairwise" else max_auto_window()
        if window > cap:
            return False
    if signals is not None and mode == "pairwise" and signals > 32:
        # Mirror the kernel's S-fold rejection (Mosaic caps its 4-D block at
        # S<=32; a near-prime S has no usable fold divisor and raises there).
        if _pairwise_fold_divisor(signals) < 8:
            return False
    if rank_tile is None:
        rank_tile = default_rank_tile(mode)
        if mode in MODE_BLOCK_ELEMS and window is not None and signals is not None:
            snapped = _snap_tile(mode, n_ranks, signals, window, rank_tile)
            if snapped is None:
                return False
            rank_tile = snapped
    tile = min(rank_tile, n_ranks)
    return tile > 0 and n_ranks % tile == 0


_KERNELS = {
    "loop": _median_weights_kernel,
    "pairwise": _median_weights_pairwise_kernel,
    "radix": _median_weights_radix_kernel,
}


@functools.partial(jax.jit, static_argnames=("rank_tile", "interpret", "mode"))
def fused_median_weights(
    data: jax.Array,
    counts: jax.Array,
    *,
    rank_tile: int | None = None,
    interpret: bool | None = None,
    mode: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(medians [R,S], weights [R,S])`` from windows ``data [R,S,W]``, ``counts [R,S]``.

    Tiled over the rank axis; each grid step holds a ``[rank_tile, S, W]`` block in
    VMEM. ``interpret`` defaults to True off-TPU so tests run on CPU. ``mode``:
    ``"loop"`` (W rank-counting passes, O(W²), rank_tile 32), ``"pairwise"``
    (one [RT, S, W, W] comparison block, rank_tile 8 for the quadratic VMEM
    temporaries), ``"radix"`` (64 bit-select passes, O(32·W) — scales to large
    windows), or ``None`` for the measured :func:`auto_mode` by window size.
    """
    r, s, w = data.shape
    if mode is None:
        mode = auto_mode(w)
    if mode not in _KERNELS:
        raise ValueError(f"unknown mode {mode!r}; one of {sorted(_KERNELS)}")
    kernel = _KERNELS[mode]
    if rank_tile is None:
        rank_tile = default_rank_tile(mode)
        if mode in MODE_BLOCK_ELEMS:
            snapped = _snap_tile(mode, r, s, w, rank_tile)
            if snapped is None:
                # Mirror the pairwise near-prime-S rejection: over-budget
                # blocks fail Mosaic, shattered grids silently run far
                # slower than the XLA sort — both fail loudly here.
                detail = (
                    f"a single rank-row ({s}x{w} elements) exceeds the VMEM "
                    f"block budget ({MODE_BLOCK_ELEMS[mode]})"
                    if s * w > MODE_BLOCK_ELEMS[mode]
                    else f"rank count {r} has no divisor near the budget "
                    f"tile {mode_rank_tile(mode, s, w)} (within "
                    f"{SNAP_SHATTER_FACTOR}x) — the grid would shatter"
                )
                raise ValueError(
                    f"{mode} mode at window {w}: {detail}; pass rank_tile "
                    f"explicitly or use the XLA path"
                )
            rank_tile = snapped
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rank_tile = min(rank_tile, r)
    if r % rank_tile != 0:
        raise ValueError(f"ranks {r} not divisible by rank_tile {rank_tile}")

    # Mosaic rejects pairwise's 4-D all-pairs block once S reaches 64 (fine at
    # S≤32, measured on v5e). The kernel is independent per (rank, signal), so
    # large-S inputs are folded — signal groups moved onto the rank axis with
    # plain XLA reshapes outside the kernel — and each block sees S'≤32.
    # (Tiling S inside the grid instead is illegal: 2-D operand blocks must
    # keep their last dim full or 128-divisible.)
    if mode == "pairwise" and s > 32:
        st = _pairwise_fold_divisor(s)
        if st < 8:
            # A near-prime S would degenerate to single-signal blocks — a
            # pathological grid far slower than the XLA sort. Fail loudly.
            raise ValueError(
                f"pairwise mode needs a signal count with a divisor in [8, 32] "
                f"to fold S={s} under Mosaic's S<=32 limit (best divisor: {st})"
            )
        fold = s // st
        med, wt = fused_median_weights(
            data.reshape(r * fold, st, w),
            counts.reshape(r * fold, st),
            rank_tile=rank_tile,
            interpret=interpret,
            mode=mode,
        )
        return med.reshape(r, s), wt.reshape(r, s)

    grid = (r // rank_tile,)
    return pl.pallas_call(
        kernel,
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
