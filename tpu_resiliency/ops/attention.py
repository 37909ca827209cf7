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

Arithmetic, as ``models/pattern.py:_attend`` has it: the operands go into the MXU as
they come (bf16 in training), the scores, the row maximum, the row sum and every
accumulator are float32, the softmax scale multiplies the float32 scores, the
exponentials are cast to the operands' type only as the operand of the product with
``v``, and the division by the row sum is exact. The backward pass (a ``custom_vjp``)
keeps the output and each row's log-sum-exp, recomputes a tile's scores from them, and
runs as two kernels: one walks the query tiles that see a key tile and accumulates
``dk`` and ``dv``, the other walks the key tiles a query tile sees and accumulates
``dq``. Under a selection a fourth kernel gives a second result, as
``models/pattern.py:_attend_summed`` does: the probabilities ``exp(score - lse)`` of
every head summed in float32, ``[B, T, T]`` (one more ``QK^T`` a tile, from the
forward's log-sum-exp; zero off the selection and in the tiles past the diagonal), which
passes no gradient.

Layout: q, the output and their cotangents stay ``[B, T, H * dh]`` in HBM; a block is
``[tile, G * dh]``, the columns of one KV head's group, and a head is a lane-aligned
slice of it. Nothing is transposed on the way in or out. Row statistics are
``[B, Hkv, T, G]`` (a query row's ``G`` values on the lanes) where the scores have the
queries on the sublanes, and ``[B, Hkv, G, T]`` where the dK/dV kernel has them on the
lanes; that kernel reads the selection key by query, from a transposed copy made once
a call.

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


def _lanes(x, width: int):
    """A lane-replicated ``[rows, 128]`` statistic against ``width`` columns."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


# -- forward ----------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, groups, dh, tile, window, scale, reach):
    *sel, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    i, j = pl.program_id(2), pl.program_id(3)
    kj = jnp.maximum(i - reach, 0) + j

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

    @pl.when(kj <= i)
    def _():
        _visit(sel, _uncut(i, kj, tile, window), lambda: _keep(i, kj, tile, window, 0),
               tile_body)

    @pl.when(j == reach)
    def _():
        for g in range(groups):
            head = slice(g * dh, (g + 1) * dh)
            l = l_scr[g]
            o_ref[:, head] = (acc_scr[:, head] / _lanes(l, dh)).astype(o_ref.dtype)
            lse_ref[:, g:g + 1] = (m_scr[g] + jnp.log(l))[:, :1]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
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

    @property
    def static(self) -> dict:
        return dict(groups=self.groups, dh=self.dh, tile=self.tile, window=self.window,
                    scale=float(self.dh) ** -0.5, reach=self.reach)

    @property
    def grid(self):
        return self.b, self.hkv, self.n, self.reach + 1

    def q_block(self):  # the columns of one KV head's group of query heads
        return pl.BlockSpec((None, self.tile, self.groups * self.dh),
                            lambda b, h, i, j: (b, i, h))

    def kv_block(self):  # the j-th key tile that query tile i sees
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


def _plan(q, k, window) -> _Plan:
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    tile = tile_of(t, window)
    if window is not None and window >= t:
        window = None
    n = t // tile
    return _Plan(b, t, hkv, h // hkv, dh, tile, n, _reach(n, tile, window), window)


def _given(selected) -> tuple:
    """The selection as an operand, where there is one."""
    return () if selected is None else (selected,)


def _forward(q, k, v, window, selected=None):
    p = _plan(q, k, window)
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

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               groups, dh, tile, window, scale, reach):
    *sel, dq_ref, dq_scr = rest
    i, j = pl.program_id(2), pl.program_id(3)
    kj = jnp.maximum(i - reach, 0) + j

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile_body(mask):
        k, v = k_ref[...], v_ref[...]
        keep = mask() if mask else None
        for g in range(groups):
            head = slice(g * dh, (g + 1) * dh)
            s = jax.lax.dot_general(q_ref[:, head], k, _NT,
                                    preferred_element_type=jnp.float32) * scale
            if mask:
                s = jnp.where(keep, s, MASKED)
            p = jnp.exp(s - lse_ref[:, g:g + 1])
            dp = jax.lax.dot_general(do_ref[:, head], v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[:, g:g + 1])
            dq_scr[:, head] += jnp.dot(ds.astype(k.dtype), k,
                                       preferred_element_type=jnp.float32)

    @pl.when(kj <= i)
    def _():
        _visit(sel, _uncut(i, kj, tile, window), lambda: _keep(i, kj, tile, window, 0),
               tile_body)

    @pl.when(j == reach)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                groups, dh, tile, window, scale, reach, n):
    *sel, dk_ref, dv_ref, dk_scr, dv_scr = rest
    j, i = pl.program_id(2), pl.program_id(3)
    qi = j + i  # the first query tile that sees key tile j is the one on the diagonal

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
            ds = p * (dp - delta_ref[g:g + 1, :])
            dk_scr[...] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32)

    @pl.when(qi < n)
    def _():
        _visit(sel, _uncut(qi, j, tile, window), lambda: _keep(qi, j, tile, window, 1),
               tile_body)

    @pl.when(i == reach)
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _backward(q, k, v, out, lse, do, window, selected=None):
    p = _plan(q, k, window)
    # each row's sum of (output x its cotangent): what the softmax's backward subtracts
    delta = jnp.sum((out.astype(jnp.float32) * do.astype(jnp.float32))
                    .reshape(p.b, p.t, p.hkv, p.groups, p.dh), axis=-1).transpose(0, 2, 1, 3)
    operands = p.flat(q, k, v, do)
    stats = pl.BlockSpec((None, None, p.tile, p.groups), lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **p.static),
        grid=p.grid,
        in_specs=[p.q_block(), p.kv_block(), p.kv_block(), p.q_block(), stats, stats,
                  *p.sel_blocks(selected)],
        out_specs=p.q_block(),
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((p.tile, p.groups * p.dh), jnp.float32)],
        compiler_params=_params(), interpret=_interpret(), name="blocked_attention_dq",
    )(*operands, lse, delta, *_given(selected))

    # the dK/dV kernel's grid is (B, Hkv, key tile, query tile that sees it); its scores
    # have the queries on the lanes, so the row statistics go in with the sequence last,
    # and a selection key by query
    def seen(j, i):
        return jnp.minimum(j + i, p.n - 1)

    q_seen = pl.BlockSpec((None, p.tile, p.groups * p.dh), lambda b, h, j, i: (b, seen(j, i), h))
    kv_own = pl.BlockSpec((None, p.tile, p.dh), lambda b, h, j, i: (b, j, h))
    stats = pl.BlockSpec((None, None, p.groups, p.tile), lambda b, h, j, i: (b, h, 0, seen(j, i)))
    sel_seen = [] if selected is None else [
        pl.BlockSpec((None, p.tile, p.tile), lambda b, h, j, i: (b, j, seen(j, i)))]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n=p.n, **p.static),
        grid=p.grid,
        in_specs=[q_seen, kv_own, kv_own, q_seen, stats, stats, *sel_seen],
        out_specs=[kv_own, kv_own],
        out_shape=[jax.ShapeDtypeStruct(operands[1].shape, k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((p.tile, p.dh), jnp.float32)] * 2,
        compiler_params=_params(), interpret=_interpret(), name="blocked_attention_dkv",
    )(*operands, lse.swapaxes(2, 3), delta.swapaxes(2, 3),
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


def blocked_attention(q, k, v, *, window: Optional[int] = None, selected=None):
    """Causal attention of q ``[B, T, H, dh]`` over k / v ``[B, T, Hkv, dh]``
    (``H % Hkv == 0``: query head ``h`` reads KV head ``h // (H / Hkv)``) ->
    ``[B, T, H * dh]``. ``window=None`` sees every key up to the query's own;
    ``window=W`` sees the last ``W`` of them (``i - W < j <= i``). The shapes have to
    tile (:func:`applies`).

    With ``selected`` (``[B, T, T]`` bool or int8, query by key: the keys each query
    reads, at least one and none after the query) the result is a pair: the attention over
    those keys, and the heads' summed probabilities ``[B, T, T]`` float32, zero off the
    selection, which pass no gradient."""
    t, dh = q.shape[1], q.shape[3]
    if q.shape[2] % k.shape[2] or not applies(t, dh, window):
        raise ValueError(f"blocked_attention does not tile q {q.shape}, k {k.shape}, "
                         f"window {window}: see attention.applies")
    if selected is None:
        return _attention(q, k, v, window)
    if window is not None or selected.shape != (q.shape[0], t, t):
        raise ValueError(f"a selection is [B, T, T] of q {q.shape} and takes no window: "
                         f"{selected.shape}, window {window}")
    return _selected_attention(q, k, v, selected.astype(jnp.int8))
