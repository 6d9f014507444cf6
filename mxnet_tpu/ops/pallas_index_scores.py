"""The sparse-attention selector's index scores, ``I[t, s] = sum_j w[t, j]
relu(qI[t, j] . kI[s])``, summed over their heads in VMEM, forward and
backward (``ops/decoder_ops.py::_index_scores`` is the composition they
stand in for, and stays the path of everything they cannot serve: it
writes each head's float32 product to HBM and reads it back to reduce
it, sixteen times the bytes of ``I``, and its pullback does so again).

One call a layer a pass, whatever the number of query blocks. The grid
is (batch, query tile, key tile); a step holds the query tile's index
queries for every head, ``(tile, heads x d)`` as they lie, and one key
tile. Key tiles wholly above the diagonal are not visited: their step
does nothing and moves nothing (its block indices are the diagonal
step's). For each head ``s_j = kI qI_j^T`` comes out of the MXU in
float32 and ``relu(s_j) * w_j`` is added to the tile's sum, heads in
ascending order; the tile of ``I`` is written once.

Layout. Tiles are computed keys x queries, like the attention kernels'
(``ops/pallas_causal_gqa.py``): a query's weight is then a dense ``(1,
tile)`` row that broadcasts along sublanes and the sum over keys for
``dw`` runs over sublanes. The scores of the whole sequence are one
array in ``pallas_sparse_gqa.mask_blocks``' shape, ``(batch, query
tiles, length, tile)`` float32, a query tile's column of key tiles
contiguous and nothing written past its diagonal tile; a query block's
scores (batch, queries, keys up to its end) are a prefix of its column
seen transposed (:func:`index_score_blocks`), the layout in which the
compiler kept a block's scores before, so thresholds, mask and index
loss read them as they read the composition's.

Heads of 64 lanes. Two heads share a 128-lane tile of the queries'
rows, and a 64-lane slice of it is not a tile. The index keys (one head)
are handed in twice instead: in lanes 0..63 with zeros above, and in
lanes 64..127 with zeros below. A product of a whole 128-lane tile of
queries with the first is the even head's scores, with the second the
odd head's: the contraction is half zeros, which costs the MXU what a
contraction of 64 costs it (half an array either way). The backward's
``d qI`` needs nothing more (each head adds into its own half of the
pair's lanes); ``d kI`` is summed in one accumulator a half and the
valid halves are added outside. Heads of 128 lanes take the same code
with one part.

Backward (``pallas_index_scores_bwd``, one kernel): from the cotangent
``dI`` in the scores' own layout it rebuilds ``s_j`` in VMEM, forms
``ds_j = where(s_j > 0, dI * w_j, 0)`` and, per head, ``d qI_j += ds_j .
kI`` (in a ``(heads x d, tile)`` float32 buffer over the key tiles,
written once a query tile), ``d kI += ds_j^T . qI_j`` (float32, the
whole length resident in VMEM across the grid: 4 MB a half at 8,192) and
``dw_j = sum_k dI * relu(s_j)``. The residuals are the three inputs.

What is exact, and what is not. The scores are a pure function of the
inputs, tile by tile in a fixed order: a second call (the backward's)
gives the first's bits, so a mask rebuilt from kept thresholds is the
forward's. Against the composition the sum over heads runs in another
order, so a score may differ in its last bit and a tie at the
``top_k``-th place may fall the other way: the set is ``lax.top_k``'s of
the scores this path computed.

Precision is the composition's: bf16 operands into the MXU with float32
accumulation (``Precision.DEFAULT`` pinned), relu, weight and the sum
over heads in float32, ``ds`` cast to bf16 for its two products (what a
TPU's default precision makes of the float32 cotangent in the
composition's pullback).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_common
from .pallas_causal_gqa import (BF16, F32, _LANE, _NN, _NT, _TN,
                                _VMEM_BUDGET, _dot)
from .pallas_sparse_gqa import mask_blocks

__all__ = ["index_scores_available", "index_score_blocks",
           "index_score_blocks_vjp"]


def _vmem_bytes(length, heads, d, tile):
    """The backward's working set: the resident ``d kI`` halves, the
    step's blocks (queries in, their gradient out, ``dI``) twice over
    (the pipeline's two buffers), the ``d qI`` buffer, a few tiles."""
    per = _LANE // d
    return (2 * per * length * _LANE * 4
            + 2 * (2 * tile * heads * d * 2 + tile * tile * 4)
            + heads * d * tile * 4 + 6 * tile * tile * 4)


def index_scores_available(iq, ik, iw, tile):
    """Whether the kernels may serve this call, from what the code can
    observe: one device in the mesh being traced for, bf16 index queries
    (batch, length, heads, d) and keys (batch, length, d), float32
    weights (batch, length, heads), heads of a lane tile or of half of
    one in whole pairs, a length of whole tiles, the backward's working
    set within the VMEM budget, and kernels that will be compiled (a TPU
    backend) or whose interpretation was asked for."""
    if iq.ndim != 4 or ik.ndim != 3 or iw.ndim != 3:
        return False
    length, heads, d = iq.shape[1:]
    return bool(
        pallas_common.kernels_allowed()
        and iq.dtype == BF16 and ik.dtype == BF16 and iw.dtype == F32
        and d in (_LANE, _LANE // 2) and heads % (_LANE // d) == 0
        and tile % _LANE == 0 and length > 0 and length % tile == 0
        and _vmem_bytes(length, heads, d, tile) <= _VMEM_BUDGET
        and (not pallas_common.interpret_mode()
             or pallas_common.interpret_asked()))


def _compiler_params(pltpu, length, heads, d, tile):
    nbytes = _vmem_bytes(length, heads, d, tile) + (16 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=min(nbytes, 110 << 20))


def _block_specs(pl, heads, d, tile):
    """(a query tile's index queries / their gradient, a key tile of the
    keys' parts, a query tile's weights / their gradient, a tile of the
    scores / of ``dI``) over the grid (batch, query tile, key tile); a
    step above the diagonal names the diagonal step's blocks."""
    per = _LANE // d
    return (pl.BlockSpec((None, tile, heads * d), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((None, per, tile, _LANE),
                         lambda n, i, j: (n, 0, jnp.minimum(j, i), 0)),
            pl.BlockSpec((None, heads, tile), lambda n, i, j: (n, 0, i)),
            pl.BlockSpec((None, None, tile, tile),
                         lambda n, i, j: (n, i, jnp.minimum(j, i), 0)))


def _heads(iq_ref, heads, d):
    """(head, which part of the keys it reads, its pair's 128 lanes of
    the step's queries) a head, in ascending order."""
    per = _LANE // d
    for h in range(heads):
        lanes = slice(h // per * _LANE, (h // per + 1) * _LANE)
        yield h, h % per, lanes, iq_ref[:, lanes]


@functools.lru_cache(maxsize=None)
def _fwd_call(b, length, heads, d, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = length // tile

    def pallas_index_scores_fwd(iq_ref, ik_ref, w_ref, out_ref):
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(j <= i)
        def _():
            acc = None
            for h, part, _, qg in _heads(iq_ref, heads, d):
                st = _dot(ik_ref[part], qg, _NT)            # keys x queries
                term = jnp.maximum(st, 0.0) * w_ref[h:h + 1, :]
                acc = term if acc is None else acc + term
            out_ref[...] = acc

    q_spec, k_spec, w_spec, tile_spec = _block_specs(pl, heads, d, tile)
    return pl.pallas_call(
        pallas_index_scores_fwd,
        grid=(b, nq, nq),
        in_specs=[q_spec, k_spec, w_spec],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, length, tile), F32),
        compiler_params=_compiler_params(pltpu, length, heads, d, tile),
        interpret=interpret,
        name="pallas_index_scores_fwd",
    )


@functools.lru_cache(maxsize=None)
def _bwd_call(b, length, heads, d, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    per, nq = _LANE // d, length // tile

    def pallas_index_scores_bwd(iq_ref, ik_ref, w_ref, di_ref,
                                diq_ref, dik_ref, dw_ref, diq_acc):
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when((i == 0) & (j == 0))
        def _():
            dik_ref[...] = jnp.zeros(dik_ref.shape, F32)

        @pl.when(j == 0)
        def _():
            diq_acc[...] = jnp.zeros(diq_acc.shape, F32)
            dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

        @pl.when(j <= i)
        def _():
            rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
            di = di_ref[...]                                # keys x queries
            for h, part, lanes, qg in _heads(iq_ref, heads, d):
                kj = ik_ref[part]
                st = _dot(kj, qg, _NT)
                dw_ref[h:h + 1, :] += jnp.sum(jnp.maximum(st, 0.0) * di,
                                              axis=0, keepdims=True)
                dst = jnp.where(st > 0, di * w_ref[h:h + 1, :], 0.0) \
                    .astype(BF16)
                diq_acc[lanes, :] += _dot(kj, dst, _TN)     # d x queries
                dik_ref[part, rows, :] += _dot(dst, qg, _NN)

        @pl.when(j == i)
        def _():
            for g in range(heads // per):
                lanes = slice(g * _LANE, (g + 1) * _LANE)
                diq_ref[:, lanes] = diq_acc[lanes, :].T.astype(diq_ref.dtype)

    q_spec, k_spec, w_spec, tile_spec = _block_specs(pl, heads, d, tile)
    return pl.pallas_call(
        pallas_index_scores_bwd,
        grid=(b, nq, nq),
        in_specs=[q_spec, k_spec, w_spec, tile_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((None, per, length, _LANE),
                                lambda n, i, j: (n, 0, 0, 0)),
                   w_spec],
        out_shape=[jax.ShapeDtypeStruct((b, length, heads * d), BF16),
                   jax.ShapeDtypeStruct((b, per, length, _LANE), F32),
                   jax.ShapeDtypeStruct((b, heads, length), F32)],
        scratch_shapes=[pltpu.VMEM((heads * d, tile), F32)],
        compiler_params=_compiler_params(pltpu, length, heads, d, tile),
        interpret=interpret,
        name="pallas_index_scores_bwd",
    )


def _key_parts(ik):
    """The index keys once a head of a lane tile: (batch, parts, length,
    128), part ``r`` holding them in lanes ``r d ..`` and zeros in the
    others."""
    d = ik.shape[-1]
    return jnp.stack([
        jnp.pad(ik, ((0, 0), (0, 0), (r * d, _LANE - (r + 1) * d)))
        for r in range(_LANE // d)], axis=1)


def _operands(iq, ik, iw):
    b, length, heads, d = iq.shape
    return (iq.reshape(b, length, heads * d), _key_parts(ik),
            jnp.swapaxes(iw, 1, 2))


def _blocks_of(whole, tile):
    """Each query block's (batch, queries, keys up to its end) of an
    array in the kernels' layout."""
    return tuple(jnp.swapaxes(whole[:, i, :(i + 1) * tile], 1, 2)
                 for i in range(whole.shape[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def index_score_blocks(iq, ik, iw, tile):
    """``decoder_ops._index_scores`` of every query block of ``tile``
    queries against the keys up to its end, float32 (batch, tile, keys),
    a tuple over the blocks: iq (batch, length, heads, d) and ik (batch,
    length, d) bf16, iw (batch, length, heads) float32 (check
    :func:`index_scores_available` first). One forward kernel;
    differentiated by one backward kernel over the blocks' cotangents
    together."""
    return _blocks_fwd(iq, ik, iw, tile)[0]


def _blocks_fwd(iq, ik, iw, tile):
    b, length, heads, d = iq.shape
    call = _fwd_call(b, length, heads, d, int(tile),
                     pallas_common.interpret_mode())
    return _blocks_of(call(*_operands(iq, ik, iw)), tile), (iq, ik, iw)


def _blocks_bwd(tile, res, dblocks):
    iq, ik, iw = res
    b, length, heads, d = iq.shape
    call = _bwd_call(b, length, heads, d, int(tile),
                     pallas_common.interpret_mode())
    flat, parts, w = _operands(iq, ik, iw)
    di = mask_blocks([jnp.swapaxes(t, 1, 2) for t in dblocks], length)
    diq, dik, dw = call(flat, parts, w, di)
    dik = sum(dik[:, r, :, r * d:(r + 1) * d] for r in range(_LANE // d))
    return (diq.reshape(iq.shape), dik.astype(ik.dtype),
            jnp.swapaxes(dw, 1, 2))


index_score_blocks.defvjp(_blocks_fwd, _blocks_bwd)


def index_score_blocks_vjp(iq, ik, iw, tile):
    """(:func:`index_score_blocks`, its pullback from a cotangent a block
    to ``(d iq, d ik, d iw)``) for a caller that is a backward rule
    itself: the two kernels called as they are, where ``jax.vjp`` would
    trace them under names of its own (``jvp_pallas_...``) that no sum
    over ``pallas_*`` calls finds."""
    blocks, res = _blocks_fwd(iq, ik, iw, tile)
    return blocks, functools.partial(_blocks_bwd, tile, res)
