"""Causal grouped-query attention as blocked Pallas TPU kernels: the scores of one
(query tile, key tile) pair live in VMEM and nowhere else.

One algorithm for full causal attention, for a sliding window and for a selection of
keys that is data: the running-softmax recurrence over the key tiles a query tile can
see (Dao et al., FlashAttention-2, arXiv:2307.08691). The first two differ in which key
tiles are visited: the causal prefix, or the band ``i - window < j <= i``. The grid's
last axis walks only those tiles, tiles that the mask does not cut skip the mask, and
the ``G = H / Hkv`` query heads of one KV head ride the same K / V tile, so K and V are
read once a group and never repeated. The third (``selected``: a byte a (query, key), set
where the query reads the key, causal already) visits the causal prefix and reads one
tile of the selection beside each tile of scores: every visited tile takes the one masked
body, the bytes unpacked once for the ``G`` heads of the group, and no mask is made from
positions. A query's keys may all lie in its later tiles: the masked score is finite,
so what the first tiles leave in the running sums is wiped by the first real maximum.

A fourth form walks the tiles from positions again, under a mask that is neither causal
nor a band (``noised``: block diffusion over a doubled stream ``[clean ; noised]``, both
halves of ``clean`` rows cut into blocks of ``block`` positions, BD3-LM's vectorised
training, arXiv:2503.09573). With ``b(i) = i // block``: a clean query ``i`` reads the
clean keys ``j`` with ``b(j) <= b(i)``; a noised query ``i`` reads the clean keys with
``b(j) < b(i)`` and the noised keys with ``b(j) == b(i)``; nothing else is read. Query
tile ``r`` of the clean half visits the clean key tiles ``0 .. r``, query tile ``r`` of
the noised half the clean key tiles ``0 .. r`` and the noised key tile ``r``, and no
other: with ``n`` tiles a half, ``n (n + 1) + n`` of the ``4 n^2`` pairs (80 of 256 at
``n = 8``). Tiles wholly below a diagonal skip the mask; the three kinds of diagonal
tile (clean by clean: ``b(j) <= b(i)``; noised by clean: ``b(j) < b(i)``; noised by
noised: ``b(j) == b(i)``) make theirs from iotas. A noised row of a tile's first block
reads nothing of the clean tile on its diagonal, and a row of block 0 no clean key at
all: every row reads its own block of the noised tile, the last it visits, so no row is
empty (the masked score is finite, as under a selection).

Arithmetic, as ``models/pattern.py:_attend`` has it: the operands go into the MXU as
they come (bf16 in training), the scores, the row maximum, the row sum and every
accumulator are float32, the softmax scale multiplies the float32 scores, the
exponentials are cast to the operands' type only as the operand of the product with
``v``, and the division by the row sum is exact. The backward pass (a ``custom_vjp``)
keeps the output and each row's log-sum-exp, recomputes a tile's scores from them, and
runs as one kernel (PR 49; two until then, which made every tile's ``s``, ``p``, ``dp``
and ``ds`` twice: seven products a (tile, head) where this one has five). It walks the
query tiles that see a key tile, the key tiles on the grid's outer axis in ascending
order; in a visited tile each head of the group makes ``s = k q^T``, ``p = exp(s - lse)``,
``dp = v do^T`` and ``ds = p (dp - delta)`` once, with the keys on the sublanes, and takes
``dv += p do``, ``dk += ds q`` and ``dq[query tile] += ds^T k`` from them, ``p`` and
``ds`` rounded to the operands' type once, as operands of those products. ``dk`` and
``dv`` accumulate in a ``[tile, dh]`` float32 scratch for the key tile's walk. ``dq``
cannot: a query tile's contributions arrive one a key tile, across the outer axis. Its
float32 accumulator holds the whole sequence of one (batch row, KV head), ``[T, G * dh]``
(32 MiB at 8,192 rows and a group of 8), in VMEM from that pair's first grid step, where
it is zeroed, to its last; both tile axes of the grid are ``"arbitrary"`` for that. In all
four forms a query tile's own key tile is the last that holds a key of it and the first
step of that key tile's walk, so there ``dq`` of the tile is complete, and it is scaled,
rounded and written out then, a tile at a time. A tile's contributions arrive in ascending
key tile, the order in which the kernel that walked a query tile's keys added them, each
the float32 product of the same rounded ``ds`` and ``k``: ``dq`` is that kernel's to the
order of a float32 sum inside the products. Under a selection a third kernel gives a
second result, as ``models/pattern.py:_attend_summed`` does: the probabilities
``exp(score - lse)`` of every head summed in float32, ``[B, T, T]`` (one more ``QK^T`` a
tile, from the forward's log-sum-exp; zero off the selection and in the tiles past the
diagonal), which passes no gradient.

Layout: q, the output and their cotangents stay ``[B, T, H * dh]`` in HBM; a block is
``[tile, G * dh]``, the columns of one KV head's group, and a head is a lane-aligned
slice of it. Nothing is transposed on the way in or out. Row statistics are
``[B, Hkv, T, G]`` (a query row's ``G`` values on the lanes) where the scores have the
queries on the sublanes, and ``[B, Hkv, G, T]`` in the backward kernel, which has them on
the lanes and reads the selection key by query, from a transposed copy made once a call.

Off the TPU the same kernels run under the Pallas interpreter, which is what the tier-1
tests compare with plain masked attention.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a query tile and of a key tile, forward and backward alike, chosen on a v5e
#: (PERF.md, PR 29). A window's tiles are smaller: a 512-row tile against a 512-token
#: band scores twice the band, a 256-row one 1.5 times it.
FULL_TILE = 512
WINDOW_TILE = 256

LANES = 128
#: what a masked score is set to: finite, so that a row whose first visited tile is all
#: masked gives exp(0) there and is wiped by the first real maximum (exp(MASKED - m) = 0)
MASKED = -0.7 * float(np.finfo(np.float32).max)
VMEM_LIMIT_BYTES = 96 * 1024 * 1024

#: the names (``jax.ad_checkpoint.checkpoint_name``) of the two residuals the forward
#: kernel makes, for a caller whose ``jax.checkpoint`` policy keeps them: without the
#: log-sum-exp kept, the backward pass runs the whole forward kernel again for it
OUT_NAME, LSE_NAME = "attn_out", "attn_lse"

_NT = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]


def tile_of(seq: int, window: Optional[int]) -> int:
    """Rows of a tile for a sequence of ``seq``: the kind's constant, or the whole
    sequence where that is shorter. A window that covers the sequence is the causal
    half and takes the full layers' tile."""
    full = window is None or window >= seq
    return min(FULL_TILE if full else WINDOW_TILE, seq)


def applies(seq: int, head_dim: int, window: Optional[int]) -> bool:
    """Whether the kernels tile these shapes: heads of whole lane groups, a sequence of
    whole tiles."""
    tile = tile_of(seq, window)
    return head_dim % LANES == 0 and tile % LANES == 0 and seq % tile == 0


def applies_noised(stream: int, head_dim: int, block: int, clean: int) -> bool:
    """Whether the kernels tile a doubled stream of ``stream`` rows whose clean half ends
    at ``clean``, in blocks of ``block``: two halves of whole tiles, blocks that are a
    power of two (a position's block is a shift) and that no tile cuts."""
    tile = min(FULL_TILE, clean)
    return (stream == 2 * clean and applies(clean, head_dim, None)
            and block & (block - 1) == 0 and 0 < block <= tile)


# -- which tiles see each other: with square tiles, key tile kj is seen by query tile qi
# iff 0 <= qi - kj <= reach

def _reach(n: int, tile: int, window: Optional[int]) -> int:
    """How many tiles back a query tile sees, of ``n`` tiles in all."""
    return n - 1 if window is None else min((tile + window - 2) // tile, n - 1)


def _uncut(qi, kj, tile, window):
    """No score of tile (qi, kj) is masked. With square tiles the causal edge leaves
    the tiles below the diagonal whole; the window's edge cuts those its far side is in."""
    whole = kj < qi
    if window is not None:
        whole &= kj * tile >= qi * tile + tile - window
    return whole


def _keep(qi, kj, tile, window, q_axis: int):
    """The mask of tile (qi, kj), ``[tile, tile]`` with the queries on ``q_axis``."""
    ahead = (qi - kj) * tile + (
        jax.lax.broadcasted_iota(jnp.int32, (tile, tile), q_axis)
        - jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1 - q_axis))
    keep = ahead >= 0
    if window is not None:
        keep &= ahead < window
    return keep


def _set(sel_ref):
    """A tile of the selection as the mask of its scores."""
    return sel_ref[...].astype(jnp.int32) != 0


def _visit(sel, uncut, keep, tile_body):
    """Run ``tile_body(mask)`` for a visited tile: ``mask()`` gives its ``[tile, tile]``
    bool, ``None`` says nothing is cut. Under a selection (``sel``: its tile's ref in a
    list, or an empty one) every tile takes the one body under its tile of the selection;
    else the tiles the positions leave whole skip the mask that ``keep()`` makes."""
    if sel:
        tile_body(lambda: _set(sel[0]))
    else:
        pl.when(uncut)(lambda: tile_body(None))
        pl.when(jnp.logical_not(uncut))(lambda: tile_body(keep))


# -- the block-diffusion form: ``half`` tiles of clean rows, then ``half`` of noised rows

def _halves(i, half: int):
    """(tile ``i``'s place within its half, whether that is the noised half)."""
    late = i >= half
    return jnp.where(late, i - half, i), late


def _noised_key_tile(i, j, half: int):
    """Step ``j`` of query tile ``i``: (the key tile, whether the step is taken). Steps
    ``0 .. r`` are the clean key tiles up to the query tile's own place ``r``; a noised
    query tile takes one more, its own noised key tile. A step not taken names the tile
    of the last one taken, so nothing is fetched for it."""
    r, late = _halves(i, half)
    kj = jnp.where(j > r, jnp.where(late, half + r, r), j)
    return kj, (j <= r) | (late & (j == r + 1))


def _noised_query_tile(j, i, half: int):
    """Step ``i`` of key tile ``j``: (the query tile, whether the step is taken). A clean
    key tile at place ``c`` is seen by the clean query tiles ``c .. half - 1``, then by the
    noised ones at the same places; a noised key tile by the noised query tile it is."""
    c, late = _halves(j, half)
    qi = jnp.minimum(jnp.where(i < half - c, c + i, 2 * c + i), 2 * half - 1)
    return jnp.where(late, j, qi), jnp.where(late, i == 0, i < 2 * (half - c))


def _noised_uncut(qi, kj, half: int):
    """No score of tile (qi, kj) is masked: a clean key tile before the query tile's place."""
    return _halves(kj, half)[0] < _halves(qi, half)[0]


def _noised_keep(qi, kj, tile, block, half: int, q_axis: int):
    """The mask of a diagonal tile (qi, kj), ``[tile, tile]`` with the queries on
    ``q_axis``, from the blocks' numbers within the tile (``block`` is a power of two): a
    query's block is at or after the key's (clean by clean), after it (noised by clean),
    or the key's own (noised by noised)."""
    def of_block(axis):  # a row's or a column's block within the tile
        return jax.lax.shift_right_logical(
            jax.lax.broadcasted_iota(jnp.int32, (tile, tile), axis), block.bit_length() - 1)

    ahead = of_block(q_axis) - of_block(1 - q_axis)
    own = kj >= half
    least = jnp.where((qi >= half) & jnp.logical_not(own), 1, 0)
    return (ahead >= least) & (ahead <= jnp.where(own, 0, tile))


def _lanes(x, width: int):
    """A lane-replicated ``[rows, 128]`` statistic against ``width`` columns."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


def _query_walk(i, j, tile, window, reach, noised):
    """Step ``j`` of query tile ``i`` in the forward kernel, as three functions of no
    argument, each traced where the kernel calls it: whether the step is taken, whether
    its tile is whole, and the tile's mask with the queries on the sublanes."""
    if noised is None:
        kj = jnp.maximum(i - reach, 0) + j
        return (lambda: kj <= i, lambda: _uncut(i, kj, tile, window),
                lambda: _keep(i, kj, tile, window, 0))
    block, half = noised
    kj, taken = _noised_key_tile(i, j, half)
    return (lambda: taken, lambda: _noised_uncut(i, kj, half),
            lambda: _noised_keep(i, kj, tile, block, half, 0))


def _key_walk(j, i, tile, window, n, noised):
    """Step ``i`` of key tile ``j`` in the backward kernel: the query tile, then
    :func:`_query_walk`'s three functions, the masks with the queries on the lanes."""
    if noised is None:
        qi = j + i  # the first query tile that sees key tile j is the one on the diagonal
        return (qi, lambda: qi < n, lambda: _uncut(qi, j, tile, window),
                lambda: _keep(qi, j, tile, window, 1))
    block, half = noised
    qi, taken = _noised_query_tile(j, i, half)
    return (qi, lambda: taken, lambda: _noised_uncut(qi, j, half),
            lambda: _noised_keep(qi, j, tile, block, half, 1))


# -- forward ----------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, groups, dh, tile, window, scale, reach,
                noised=None):
    *sel, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    i, j = pl.program_id(2), pl.program_id(3)
    seen, uncut, keep = _query_walk(i, j, tile, window, reach, noised)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile_body(mask):
        k, v = k_ref[...], v_ref[...]
        keep = mask() if mask else None
        for g in range(groups):
            head = slice(g * dh, (g + 1) * dh)
            s = jax.lax.dot_general(q_ref[:, head], k, _NT,
                                    preferred_element_type=jnp.float32) * scale
            if mask:
                s = jnp.where(keep, s, MASKED)
            m_prev, l_prev = m_scr[g], l_scr[g]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, tile))
            alpha = jnp.exp(m_prev - m_next)
            l_scr[g] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[g] = m_next
            acc_scr[:, head] = acc_scr[:, head] * _lanes(alpha, dh) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(seen())
    def _():
        _visit(sel, uncut(), keep, tile_body)

    @pl.when(j == reach)
    def _():
        for g in range(groups):
            head = slice(g * dh, (g + 1) * dh)
            l = l_scr[g]
            o_ref[:, head] = (acc_scr[:, head] / _lanes(l, dh)).astype(o_ref.dtype)
            lse_ref[:, g:g + 1] = (m_scr[g] + jnp.log(l))[:, :1]


def _params(outer_tiles: str = "parallel"):
    """The grid's axes are (batch row, KV head, outer tile, inner tile): scratch lives
    across the inner tiles, and in the backward kernel across the outer ones too."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", outer_tiles, "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


class _Plan(NamedTuple):
    """What the kernels share for one call: sizes, the kernels' static arguments
    (``static``), and the blocks of a grid ``(B, Hkv, query tile, key tile it sees)``."""

    b: int
    t: int
    hkv: int
    groups: int
    dh: int
    tile: int
    n: int  # tiles in the sequence
    reach: int  # a query tile sees this many key tiles before its own
    window: Optional[int]  # None where it covers the sequence
    #: (block, tiles a half) of the block-diffusion form, whose query tile takes up to
    #: ``reach + 1 = n / 2 + 1`` steps: the clean key tiles up to its place and its own
    noised: Optional[tuple] = None

    @property
    def static(self) -> dict:
        static = dict(groups=self.groups, dh=self.dh, tile=self.tile, window=self.window,
                      scale=float(self.dh) ** -0.5, reach=self.reach)
        return static if self.noised is None else {**static, "noised": self.noised}

    @property
    def grid(self):
        return self.b, self.hkv, self.n, self.reach + 1

    def q_block(self):  # the columns of one KV head's group of query heads
        return pl.BlockSpec((None, self.tile, self.groups * self.dh),
                            lambda b, h, i, j: (b, i, h))

    def kv_block(self):  # the j-th key tile that query tile i sees
        if self.noised is not None:
            return pl.BlockSpec(
                (None, self.tile, self.dh),
                lambda b, h, i, j: (b, _noised_key_tile(i, j, self.noised[1])[0], h))
        return pl.BlockSpec(
            (None, self.tile, self.dh),
            lambda b, h, i, j: (b, jnp.minimum(jnp.maximum(i - self.reach, 0) + j, i), h))

    def sel_blocks(self, selected) -> list:
        """The selection's tile beside tile (i, j), where there is a selection (which
        takes no window: the j-th key tile a query tile sees is tile j)."""
        if selected is None:
            return []
        return [pl.BlockSpec((None, self.tile, self.tile),
                             lambda b, h, i, j: (b, i, jnp.minimum(j, i)))]

    def flat(self, *arrays):
        return tuple(x.reshape(self.b, self.t, -1) for x in arrays)


def _plan(q, k, window, noised=None) -> _Plan:
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    if noised is not None:
        block, clean = noised
        tile = tile_of(clean, None)
        half = clean // tile
        return _Plan(b, t, hkv, h // hkv, dh, tile, 2 * half, half, None, (block, half))
    tile = tile_of(t, window)
    if window is not None and window >= t:
        window = None
    n = t // tile
    return _Plan(b, t, hkv, h // hkv, dh, tile, n, _reach(n, tile, window), window)


def _given(selected) -> tuple:
    """The selection as an operand, where there is one."""
    return () if selected is None else (selected,)


def _forward(q, k, v, window, selected=None, noised=None):
    p = _plan(q, k, window, noised)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **p.static),
        grid=p.grid,
        in_specs=[p.q_block(), p.kv_block(), p.kv_block(), *p.sel_blocks(selected)],
        out_specs=[p.q_block(),
                   pl.BlockSpec((None, None, p.tile, p.groups), lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((p.b, p.t, p.groups * p.hkv * p.dh), q.dtype),
                   jax.ShapeDtypeStruct((p.b, p.hkv, p.t, p.groups), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p.groups, p.tile, LANES), jnp.float32),
                        pltpu.VMEM((p.groups, p.tile, LANES), jnp.float32),
                        pltpu.VMEM((p.tile, p.groups * p.dh), jnp.float32)],
        compiler_params=_params(), interpret=_interpret(), name="blocked_attention_fwd",
    )(*p.flat(q, k, v), *_given(selected))


def _probs_kernel(q_ref, k_ref, lse_ref, sel_ref, probs_ref, *, groups, dh, scale):
    i, j, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(h == 0)
    def _():
        probs_ref[...] = jnp.zeros_like(probs_ref)

    @pl.when(j <= i)
    def _():
        k, keep = k_ref[...], _set(sel_ref)
        total = probs_ref[...]
        for g in range(groups):
            s = jax.lax.dot_general(q_ref[:, g * dh:(g + 1) * dh], k, _NT,
                                    preferred_element_type=jnp.float32) * scale
            total += jnp.exp(jnp.where(keep, s, MASKED) - lse_ref[:, g:g + 1])
        probs_ref[...] = total


def _probs(q, k, lse, selected):
    """The heads' summed probabilities ``[B, T, T]`` float32 under ``selected``, from the
    forward's log-sum-exp. The grid is (B, query tile, key tile, KV head): a tile of the
    result stays in VMEM while the KV heads add their groups to it, and a tile past the
    diagonal is written as zeros with nothing read for it (its blocks are the diagonal's,
    which are there already)."""
    p = _plan(q, k, None)

    def seen(i, j):
        return jnp.minimum(j, i)

    return pl.pallas_call(
        functools.partial(_probs_kernel, groups=p.groups, dh=p.dh, scale=p.static["scale"]),
        grid=(p.b, p.n, p.n, p.hkv),
        in_specs=[pl.BlockSpec((None, p.tile, p.groups * p.dh), lambda b, i, j, h: (b, i, h)),
                  pl.BlockSpec((None, p.tile, p.dh), lambda b, i, j, h: (b, seen(i, j), h)),
                  pl.BlockSpec((None, None, p.tile, p.groups), lambda b, i, j, h: (b, h, i, 0)),
                  pl.BlockSpec((None, p.tile, p.tile), lambda b, i, j, h: (b, i, seen(i, j)))],
        out_specs=pl.BlockSpec((None, p.tile, p.tile), lambda b, i, j, h: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((p.b, p.t, p.t), jnp.float32),
        compiler_params=_params(), interpret=_interpret(), name="blocked_attention_probs",
    )(*p.flat(q, k), lse, selected)


# -- backward ---------------------------------------------------------------------

_TN = (((0,), (0,)), ((), ()))  # [n, m] x [n, d] -> [m, d]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                groups, dh, tile, window, scale, reach, n, noised=None):
    *sel, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    j, i = pl.program_id(2), pl.program_id(3)
    qi, seen, uncut, keep = _key_walk(j, i, tile, window, n, noised)
    last = reach if noised is None else n - 1  # the grid's last step for a key tile

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile_body(mask):
        # scores with the keys on the sublanes: [key, query]
        k, v = k_ref[...], v_ref[...]
        keep = mask() if mask else None
        for g in range(groups):
            head = slice(g * dh, (g + 1) * dh)
            q, do = q_ref[:, head], do_ref[:, head]
            s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
            if mask:
                s = jnp.where(keep, s, MASKED)
            p = jnp.exp(s - lse_ref[g:g + 1, :])
            dv_scr[...] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[g:g + 1, :])).astype(q.dtype)
            dk_scr[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            # ds^T k: the keys are the rows of both, and Mosaic turns the block for the MXU
            dq_scr[qi, :, head] += jax.lax.dot_general(
                ds, k, _TN, preferred_element_type=jnp.float32)

    @pl.when(seen())
    def _():
        _visit(sel, uncut(), keep, tile_body)

    @pl.when(i == 0)  # query tile j has taken its own key tile, the last that holds a key of it
    def _():
        dq_ref[...] = (dq_scr[j] * scale).astype(dq_ref.dtype)

    @pl.when(i == last)
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _backward_call(p: _Plan, dtype, selection: bool):
    """The backward kernel's call for plan ``p`` over operands of ``dtype``: (the grid, the
    blocks, the results and the scratch as ``pallas_call`` takes them, the dtype of each
    operand's block). The grid is (B, Hkv, key tile, query tile that sees it); the scores
    have the queries on the lanes, so the row statistics go in with the sequence last, and a
    selection key by query. In the block-diffusion form a clean key tile is seen by up to
    all the query tiles of both halves."""
    def seen(j, i):
        if p.noised is not None:
            return _noised_query_tile(j, i, p.noised[1])[0]
        return jnp.minimum(j + i, p.n - 1)

    width = p.groups * p.dh
    q_seen = pl.BlockSpec((None, p.tile, width), lambda b, h, j, i: (b, seen(j, i), h))
    q_own = pl.BlockSpec((None, p.tile, width), lambda b, h, j, i: (b, j, h))
    kv_own = pl.BlockSpec((None, p.tile, p.dh), lambda b, h, j, i: (b, j, h))
    stats = pl.BlockSpec((None, None, p.groups, p.tile), lambda b, h, j, i: (b, h, 0, seen(j, i)))
    sel_seen = [] if not selection else [
        pl.BlockSpec((None, p.tile, p.tile), lambda b, h, j, i: (b, j, seen(j, i)))]
    call = dict(
        grid=p.grid if p.noised is None else (p.b, p.hkv, p.n, p.n),
        in_specs=[q_seen, kv_own, kv_own, q_seen, stats, stats, *sel_seen],
        out_specs=[q_own, kv_own, kv_own],
        out_shape=[jax.ShapeDtypeStruct((p.b, p.t, p.hkv * width), dtype),
                   *[jax.ShapeDtypeStruct((p.b, p.t, p.hkv * p.dh), dtype)] * 2],
        scratch_shapes=[pltpu.VMEM((p.n, p.tile, width), jnp.float32),
                        *[pltpu.VMEM((p.tile, p.dh), jnp.float32)] * 2])
    return call, [dtype] * 4 + [jnp.float32] * 2 + [jnp.int8] * len(sel_seen)


def backward_vmem_bytes(q, k, *, window: Optional[int] = None, selection: bool = False,
                        noised=None) -> int:
    """What the backward kernel's call asks of VMEM for q and k of these shapes and dtype
    (arrays or ``jax.ShapeDtypeStruct``): its scratch, and two buffers (one in use, one in
    flight) of every operand's and every result's block. Mosaic's own temporaries (a tile's
    ``[tile, tile]`` float32 scores and their like) come on top."""
    call, dtypes = _backward_call(_plan(q, k, window, noised), q.dtype, selection)
    dtypes += [out.dtype for out in call["out_shape"]]
    blocks = sum(2 * int(np.prod([d for d in spec.block_shape if d is not None]))
                 * jnp.dtype(dtype).itemsize
                 for spec, dtype in zip(call["in_specs"] + call["out_specs"], dtypes))
    return blocks + sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                        for s in call["scratch_shapes"])


def _backward(q, k, v, out, lse, do, window, selected=None, noised=None):
    p = _plan(q, k, window, noised)
    # each row's sum of (output x its cotangent): what the softmax's backward subtracts
    delta = jnp.sum((out.astype(jnp.float32) * do.astype(jnp.float32))
                    .reshape(p.b, p.t, p.hkv, p.groups, p.dh), axis=-1).transpose(0, 2, 3, 1)
    call, _ = _backward_call(p, q.dtype, selected is not None)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, n=p.n, **p.static), **call,
        compiler_params=_params("arbitrary"), interpret=_interpret(),
        name="blocked_attention_bwd",
    )(*p.flat(q, k, v, do), lse.swapaxes(2, 3), delta,
      *(s.swapaxes(1, 2) for s in _given(selected)))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, window):
    return _forward(q, k, v, window)[0]


def _attention_fwd(q, k, v, window):
    out, lse = _forward(q, k, v, window)
    out, lse = checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return out, (q, k, v, out, lse)


def _attention_bwd(window, residuals, do):
    return _backward(*residuals, do, window)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _noised_attention(q, k, v, noised):
    return _forward(q, k, v, None, noised=noised)[0]


def _noised_attention_fwd(q, k, v, noised):
    out, lse = _forward(q, k, v, None, noised=noised)
    out, lse = checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return out, (q, k, v, out, lse)


def _noised_attention_bwd(noised, residuals, do):
    return _backward(*residuals, do, None, noised=noised)


_noised_attention.defvjp(_noised_attention_fwd, _noised_attention_bwd)


@jax.custom_vjp
def _selected_attention(q, k, v, selected):
    return _selected_attention_fwd(q, k, v, selected)[0]


def _selected_attention_fwd(q, k, v, selected):
    out, lse = _forward(q, k, v, None, selected)
    out, lse = checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return (out, _probs(q, k, lse, selected)), (q, k, v, out, lse, selected)


def _selected_attention_bwd(residuals, cotangents):
    *kept, selected = residuals
    return *_backward(*kept, cotangents[0], None, selected), None


_selected_attention.defvjp(_selected_attention_fwd, _selected_attention_bwd)


def blocked_attention(q, k, v, *, window: Optional[int] = None, selected=None, noised=None):
    """Causal attention of q ``[B, T, H, dh]`` over k / v ``[B, T, Hkv, dh]``
    (``H % Hkv == 0``: query head ``h`` reads KV head ``h // (H / Hkv)``) ->
    ``[B, T, H * dh]``. ``window=None`` sees every key up to the query's own;
    ``window=W`` sees the last ``W`` of them (``i - W < j <= i``). The shapes have to
    tile (:func:`applies`).

    With ``selected`` (``[B, T, T]`` bool or int8, query by key: the keys each query
    reads, at least one and none after the query) the result is a pair: the attention over
    those keys, and the heads' summed probabilities ``[B, T, T]`` float32, zero off the
    selection, which pass no gradient.

    With ``noised = (block, clean)`` the ``T = 2 clean`` rows are a doubled stream, the
    clean copy of a sequence beside its noised copy, under the block-diffusion mask of the
    module's docstring, from positions alone; it takes no window and no selection, and the
    shapes have to tile (:func:`applies_noised`).

    Every form differentiates through one backward kernel, which gives dQ, dK and dV from
    one making of each tile's scores and holds the float32 dQ of a (batch row, KV head)'s
    whole sequence in VMEM meanwhile (:func:`backward_vmem_bytes`; the module's docstring
    says why that leaves dQ's order of summation as it was)."""
    t, dh = q.shape[1], q.shape[3]
    if noised is not None:
        if (window is not None or selected is not None or q.shape[2] % k.shape[2]
                or not applies_noised(t, dh, *noised)):
            raise ValueError(f"blocked_attention does not tile q {q.shape}, k {k.shape} as a "
                             f"doubled stream {noised}: see attention.applies_noised")
        return _noised_attention(q, k, v, tuple(noised))
    if q.shape[2] % k.shape[2] or not applies(t, dh, window):
        raise ValueError(f"blocked_attention does not tile q {q.shape}, k {k.shape}, "
                         f"window {window}: see attention.applies")
    if selected is None:
        return _attention(q, k, v, window)
    if window is not None or selected.shape != (q.shape[0], t, t):
        raise ValueError(f"a selection is [B, T, T] of q {q.shape} and takes no window: "
                         f"{selected.shape}, window {window}")
    return _selected_attention(q, k, v, selected.astype(jnp.int8))
