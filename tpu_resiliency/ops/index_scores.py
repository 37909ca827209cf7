"""An indexer's scores as blocked Pallas TPU kernels: the per-head products of one (query
tile, key tile) pair live in VMEM and nowhere else.

``index_scores(qi, wi, ki)[b, q, s] = sum_j wi[b, q, j] relu(qi[b, q, j] . ki[b, s])``, as
``models/pattern.py:index_scores`` states it: a score of every key for every query from
``J`` small heads, one key a token for all heads and one weight a (query, head). The
``jax.numpy`` form makes the float32 products of all heads, ``[B, Q, J, K]``, writes them,
reads them for the ReLU, the weights and the sum over heads, makes them again in the
backward pass and then a cotangent of the same size. Here a grid step holds one ``[tile,
tile]`` block of the result: the products of all ``J`` heads with the key tile (one ``[J x
tile, di] x [tile, di]^T`` product, float32 ``[J, tile, tile]`` in VMEM), the ReLU, each
head's weight, the sum over heads, and the block is written once. No loop over the heads:
on a v5e one product of all heads ran 15% faster than a loop of sixteen, and its kernel
is traced and lowered in a third of the time of sixteen unrolled ones (PERF.md, PR 38),
which a step that calls these kernels 96 times pays in set-up.

Arithmetic as that docstring has it and no lower: the operands go into the MXU as they
come (bf16 in training), every product is accumulated in float32, the ReLU, the head
weight and the sum over heads are float32. The backward pass (a ``custom_vjp`` whose
residuals are the three operands) recomputes a tile's products and runs as two kernels:
one walks the key tiles a query tile sees and accumulates ``dqi`` and ``dwi``, the other
walks the query tiles that see a key tile and accumulates ``dki``; each forms ``g[q, s] x
wi[q, j] x (product > 0)`` in float32, casts it to the operands' type as the operand of
its product, and accumulates in float32; ``dwi`` is the float32 row sum of ``g x
relu(product)``.

Causality: the ``Q`` queries are the last ``Q`` of the ``K`` positions (a group of query
rows against the causal prefix up to its own end; ``Q == K`` for a whole sequence). A key
tile wholly past a query tile's last row is not computed, is written as zeros and passes
no gradient; every other tile is computed whole, the diagonal tile's scores of later keys
among them, which every reader masks (``select_keys`` takes the causal mask first,
``index_divergence`` takes the selection).

Layout: a head is ``di = 64`` columns, half a lane tile, so a head of ``[B, Q, J * di]``
would start in the middle of a vector register every second time. The queries go heads
first, ``[B, J, Q, di]`` (one copy a call, 16 MB a layer at 8,192 tokens; ``dqi`` comes
back the same way): a block is ``[J, tile, di]``, a head a whole slab of it, and all heads
one operand of ``J x tile`` rows. The head weights go heads first too, ``[B, J, Q]``, the
queries on the lanes (``dwi`` comes back the same way, each head's row sums laid along the
lanes): where the products have the queries on the sublanes a block ``[J, tile]`` is
turned to ``[J, tile, 1]`` in VMEM, once for all heads, which ran 10% faster on a v5e and
traces in fewer operations than sixteen column slices of ``[tile, J]``. The ``dki`` kernel
has its products ``[J, key, query]``, one product a head, so that ``dki`` needs no
transposed operand; it turns its tile of ``g`` in VMEM, once for all heads.

Off the TPU the same kernels run under the Pallas interpreter, which is what the tier-1
tests compare with the ``jax.numpy`` form.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a query tile and of a key tile, the attention kernels' on full layers
TILE = 512
LANES = 128
#: a head's columns: whole halves of a lane tile (what was compiled and run)
HEAD_COLUMNS = 64
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
#: the float32 products of all heads with one key tile, of which a backward kernel holds
#: about five at a time under the limit above: 16 heads at tiles of 512 rows
PRODUCT_BYTES = 16 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]
_BATCHED_NT = (((2,), (2,)), ((0,), (0,)))  # [J, m, d] x [J, n, d] -> [J, m, n]
_BATCHED_NN = (((2,), (1,)), ((0,), (0,)))  # [J, m, n] x [J, n, d] -> [J, m, d]


def tile_of(queries: int) -> int:
    """Rows of a tile for ``queries`` query rows: the constant, or all of them."""
    return min(TILE, queries)


def applies(queries: int, keys: int, heads: int, head_dim: int) -> bool:
    """Whether the kernels tile these shapes: whole tiles of queries and of keys, the
    queries no more than the keys, a head of whole half lane tiles, and all heads' products
    of a tile within what a kernel may hold."""
    tile = tile_of(queries)
    return (tile % LANES == 0 and queries % tile == 0 and keys % tile == 0
            and queries <= keys and head_dim % HEAD_COLUMNS == 0
            and heads * tile * tile * 4 <= PRODUCT_BYTES)


class _Plan(NamedTuple):
    b: int
    heads: int
    di: int
    tile: int
    nq: int  # query tiles
    nk: int  # key tiles

    @property
    def offset(self) -> int:
        """Query tile ``i`` sees the key tiles ``0 .. i + offset``."""
        return self.nk - self.nq

    def seen(self, i, j):
        """The ``j``-th key tile that query tile ``i`` reads: a tile past its diagonal
        tile reads nothing new."""
        return jnp.minimum(j, i + self.offset)

    def seeing(self, j, i):
        """The ``i``-th query tile that sees key tile ``j``; past the last, the last."""
        return jnp.minimum(jnp.maximum(j - self.offset, 0) + i, self.nq - 1)


def _plan(qi, ki) -> _Plan:
    b, q, heads, di = qi.shape
    tile = tile_of(q)
    return _Plan(b, heads, di, tile, q // tile, ki.shape[1] // tile)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _heads_first(x):
    return x.transpose(0, 2, 1, 3)


# -- forward ----------------------------------------------------------------------

def _products(q_ref, k):
    """Every head's products of a query tile with a key tile, ``[J, tile, tile]`` float32:
    one ``[J x tile, di] x [tile, di]^T`` product (the heads are the leading rows)."""
    heads, tile, di = q_ref.shape
    dots = jax.lax.dot_general(q_ref[...].reshape(heads * tile, di), k, _NT,
                               preferred_element_type=jnp.float32)
    return dots.reshape(heads, tile, k.shape[0])


def _fwd_kernel(q_ref, w_ref, k_ref, o_ref, *, offset):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j <= i + offset)
    def _():
        dots = _products(q_ref, k_ref[...])
        o_ref[...] = jnp.sum(w_ref[...][:, :, None] * jnp.maximum(dots, 0.0), axis=0)

    @pl.when(j > i + offset)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _forward(qi, wi, ki):
    p = _plan(qi, ki)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, offset=p.offset),
        grid=(p.b, p.nq, p.nk),
        in_specs=[pl.BlockSpec((None, p.heads, p.tile, p.di), lambda b, i, j: (b, 0, i, 0)),
                  pl.BlockSpec((None, p.heads, p.tile), lambda b, i, j: (b, 0, i)),
                  pl.BlockSpec((None, p.tile, p.di), lambda b, i, j: (b, p.seen(i, j), 0))],
        out_specs=pl.BlockSpec((None, p.tile, p.tile), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((p.b, p.nq * p.tile, p.nk * p.tile), jnp.float32),
        compiler_params=_params(), interpret=_interpret(), name="index_scores_fwd",
    )(_heads_first(qi), wi.swapaxes(1, 2), ki)


# -- backward ---------------------------------------------------------------------

def _dq_kernel(q_ref, w_ref, k_ref, g_ref, dq_ref, dw_ref, dq_scr, dw_scr, *, offset, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(j <= i + offset)
    def _():
        k, g = k_ref[...], g_ref[...][None]
        heads, tile, di = q_ref.shape
        dots = _products(q_ref, k)
        d_dots = jnp.where(dots > 0, g * w_ref[...][:, :, None], 0.0).astype(k.dtype)
        dq_scr[...] += jnp.dot(d_dots.reshape(heads * tile, k.shape[0]), k,
                               preferred_element_type=jnp.float32).reshape(heads, tile, di)
        dw_scr[...] += jnp.sum(g * jnp.maximum(dots, 0.0), axis=-1)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_scr[...]


def _dk_kernel(q_ref, w_ref, k_ref, g_ref, dk_ref, dk_scr, *, offset, nq):
    j, i = pl.program_id(1), pl.program_id(2)
    seeing = jnp.maximum(j - offset, 0) + i  # the i-th query tile that sees key tile j

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)

    @pl.when(seeing < nq)
    def _():
        # products with the keys on the sublanes, [J, key, query]: a product a head
        q, k = q_ref[...], k_ref[...]
        dots = jax.lax.dot_general(jnp.broadcast_to(k[None], (q.shape[0], *k.shape)), q,
                                   _BATCHED_NT, preferred_element_type=jnp.float32)
        d_dots = jnp.where(dots > 0, g_ref[...].T[None] * w_ref[...][:, None, :], 0.0)
        dk_scr[...] += jnp.sum(jax.lax.dot_general(
            d_dots.astype(q.dtype), q, _BATCHED_NN, preferred_element_type=jnp.float32), axis=0)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)


def _backward(qi, wi, ki, g):
    p = _plan(qi, ki)
    q_first = _heads_first(qi)
    w_first = wi.swapaxes(1, 2)
    q_tiles = pl.BlockSpec((None, p.heads, p.tile, p.di), lambda b, i, j: (b, 0, i, 0))
    w_tiles = pl.BlockSpec((None, p.heads, p.tile), lambda b, i, j: (b, 0, i))
    dq, dw = pl.pallas_call(
        functools.partial(_dq_kernel, offset=p.offset, nk=p.nk),
        grid=(p.b, p.nq, p.nk),
        in_specs=[q_tiles, w_tiles,
                  pl.BlockSpec((None, p.tile, p.di), lambda b, i, j: (b, p.seen(i, j), 0)),
                  pl.BlockSpec((None, p.tile, p.tile), lambda b, i, j: (b, i, p.seen(i, j)))],
        out_specs=[q_tiles, w_tiles],
        out_shape=[jax.ShapeDtypeStruct(q_first.shape, qi.dtype),
                   jax.ShapeDtypeStruct(w_first.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p.heads, p.tile, p.di), jnp.float32),
                        pltpu.VMEM((p.heads, p.tile), jnp.float32)],
        compiler_params=_params(), interpret=_interpret(), name="index_scores_dq",
    )(q_first, w_first, ki, g)

    # the dki kernel's grid is (B, key tile, query tile that sees it)
    dk = pl.pallas_call(
        functools.partial(_dk_kernel, offset=p.offset, nq=p.nq),
        grid=(p.b, p.nk, p.nq),
        in_specs=[pl.BlockSpec((None, p.heads, p.tile, p.di),
                               lambda b, j, i: (b, 0, p.seeing(j, i), 0)),
                  pl.BlockSpec((None, p.heads, p.tile), lambda b, j, i: (b, 0, p.seeing(j, i))),
                  pl.BlockSpec((None, p.tile, p.di), lambda b, j, i: (b, j, 0)),
                  pl.BlockSpec((None, p.tile, p.tile), lambda b, j, i: (b, p.seeing(j, i), j))],
        out_specs=pl.BlockSpec((None, p.tile, p.di), lambda b, j, i: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct(ki.shape, ki.dtype),
        scratch_shapes=[pltpu.VMEM((p.tile, p.di), jnp.float32)],
        compiler_params=_params(), interpret=_interpret(), name="index_scores_dk",
    )(q_first, w_first, ki, g)
    return _heads_first(dq), dw.swapaxes(1, 2).astype(wi.dtype), dk


@jax.custom_vjp
def _index_scores(qi, wi, ki):
    return _forward(qi, wi, ki)


def _index_scores_fwd(qi, wi, ki):
    return _forward(qi, wi, ki), (qi, wi, ki)


def _index_scores_bwd(residuals, g):
    return _backward(*residuals, g)


_index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


def index_scores(qi, wi, ki):
    """qi ``[B, Q, J, di]``, wi ``[B, Q, J]`` float32, ki ``[B, K, di]`` -> ``[B, Q, K]``
    float32, ``sum_j wi[q, j] relu(qi[q, j] . ki[s])``, the queries being the last ``Q`` of
    the ``K`` positions: the key tiles wholly after a query tile are zeros. The shapes
    have to tile (:func:`applies`)."""
    b, q, heads, di = qi.shape
    if (wi.shape != (b, q, heads) or ki.shape[::2] != (b, di)
            or not applies(q, ki.shape[1], heads, di)):
        raise ValueError(f"index_scores does not tile qi {qi.shape}, wi {wi.shape}, "
                         f"ki {ki.shape}: see index_scores.applies")
    return _index_scores(qi, wi.astype(jnp.float32), ki)
