"""Causal grouped-query attention over the keys a learned selector
keeps, as flash kernels that take the selection as a mask
(``ops/decoder_ops.py::_sparse_gqa`` is the composition they stand in
for, and stays the path of everything they cannot serve). The kernels
are handed the selected set, never the scores: thresholds, ties and
mask stay the XLA code of ``decoder_ops``, one code for every form, so
the set is exactly ``lax.top_k``'s of the index scores the form
computed, and the backward's mask, rebuilt from the kept thresholds and
the scores computed again by the forward's own code, is the forward's
bit for bit. The index scores themselves are ``decoder_ops.
_index_scores`` a block or, where they can serve, the kernels of
``ops/pallas_index_scores.py``, which sum the heads in VMEM in an order
of their own: against the composition a score may differ in its last
bit, and which of two keys an ulp apart is kept is not part of the
result (the float32 reference and the bf16 system differ there too).

The mask. ``int8``, keys x queries, a query tile's whole column of key
tiles one block: ``(batch, query tiles, length, tile)``, zero past the
tile's own end (:func:`mask_blocks`). The grid is (batch, key-value
head, query tile, head of the group), so a query tile's mask (4 MB at
8,192) and the group's k / v stay in VMEM across the group's heads.
A byte a pair against the four-byte score the composition writes and
reads about five times.

The kernels are ``ops/pallas_causal_gqa.py``'s (tiles computed keys x
queries, so the row statistics are dense ``(1, tile)`` rows; online
softmax; tiles above the diagonal never visited; one backward kernel of
five products a tile with dk / dv accumulated in VMEM over a group) with
``where(mask, s, -inf)`` on every tile, the diagonal's included (the
selected set lies inside the causal one). What is new beside the mask:

* A row may have no selected key in a tile, the first it visits
  included. Its running max is then ``-inf`` and ``exp(-inf - -inf)`` is
  NaN; the forward subtracts 0 instead of the max while that lasts, so
  such a tile adds exactly nothing. The backward and
  :func:`head_mean_probs` subtract the saved log-sum-exp, finite for
  every row (a row always selects a key: ``top_k`` is at least 1).
* ``pallas_sparse_gqa_probs``: the probabilities averaged over the
  heads, float32 keys x queries, which the index loss reads. One call a
  query block (the backward needs a block's at a time, between that
  block's index scores and their gradient); a grid step is a key tile
  and a group, whose heads are taken in turn: ``exp(s - lse)`` rebuilt
  from q, k, the mask and the saved log-sum-exp, summed in VMEM,
  written once a key tile: 1/32 of the score traffic it replaces.

Precision is the composition's: bf16 operands into the MXU with float32
accumulation, scale, mask, max, exp and sums in float32, probabilities
and ``dS`` cast to bf16 for their products.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_common
from .pallas_causal_gqa import (BF16, F32, _NN, _NT, _TN, _VMEM_BUDGET,
                                _bwd_vmem_bytes, _dot, _lanes,
                                causal_gqa_available)

__all__ = ["SCOPE", "sparse_gqa_available", "mask_blocks", "attend",
           "attend_bwd", "head_mean_probs"]

# the device-side scope of the attention over the selected keys, kernel
# or composition (``decoder_ops`` opens it around every call here)
SCOPE = "mx.attn.sparse"


def _vmem_bytes(length, d, tile):
    """The dense backward's working set and a query tile's mask, twice
    (the pipeline's two buffers)."""
    return _bwd_vmem_bytes(length, d, tile) + 2 * length * tile


def sparse_gqa_available(q, k, v, tile):
    """Whether the kernels may serve this call, from what the code can
    observe: all that ``causal_gqa_available`` asks (one device in the
    mesh being traced for, bf16 q / k / v, a head width of whole lane
    tiles, whole groups, a length of whole tiles), k / v and a mask
    column within the VMEM budget, and kernels that will be compiled (a
    TPU backend) or whose interpretation was asked for: an interpreted
    grid of 512-wide tiles is nothing to fall into on a CPU."""
    length, d = q.shape[1], q.shape[3]
    return bool(
        causal_gqa_available(q, k, v, tile)
        and _vmem_bytes(length, d, tile) <= _VMEM_BUDGET
        and (not pallas_common.interpret_mode()
             or pallas_common.interpret_asked()))


def mask_blocks(keeps, length):
    """The kernels' mask from each query block's selected set: ``keeps``
    one ``(batch, hi, tile)`` int8 a block (keys x queries, ``hi`` the
    block's end) -> ``(batch, blocks, length, tile)``, zero past
    ``hi``."""
    return jnp.stack([jnp.pad(kp, ((0, 0), (0, length - kp.shape[1]), (0, 0)))
                      for kp in keeps], axis=1)


def _compiler_params(pltpu, semantics, length, d, tile):
    nbytes = _vmem_bytes(length, d, tile) + (16 << 20)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=min(nbytes, 110 << 20))


def _block_specs(pl, length, d, tile, rep):
    """(a head's tile of q / o / do / dq, its group's whole k / v / dk /
    dv, a head's tile of a row statistic, a query tile's mask) over the
    grid (batch, key-value head, query tile, head of the group)."""
    return (pl.BlockSpec((None, tile, d),
                         lambda n, g, i, r: (n, i, g * rep + r)),
            pl.BlockSpec((None, length, d), lambda n, g, i, r: (n, 0, g)),
            pl.BlockSpec((None, None, 1, tile),
                         lambda n, g, i, r: (n, g * rep + r, 0, i)),
            pl.BlockSpec((None, None, length, tile),
                         lambda n, g, i, r: (n, i, 0, 0)))


def _masked(st, mask):
    """Scores of a tile with the pairs not selected at ``-inf``."""
    return jnp.where(mask.astype(jnp.int32) != 0, st, -jnp.inf)


@functools.lru_cache(maxsize=None)
def _fwd_call(b, length, heads, kv, d, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep, nq = heads // kv, length // tile
    scale = 1.0 / math.sqrt(d)

    def pallas_sparse_gqa_fwd(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                              m_ref, l_ref, acc_ref):
        i = pl.program_id(2)
        q = q_ref[...]
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        def key_tile(j, carry):
            rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
            st = _masked(_dot(k_ref[rows, :], q, _NT) * scale,
                         mask_ref[rows, :])                 # keys x queries
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            # a row that has met no selected key yet: subtract 0, not
            # -inf, and the tile adds nothing to it
            m_safe = jnp.where(m_next == -jnp.inf, 0.0, m_next)
            alpha = jnp.exp(m_prev - m_safe)
            pt = jnp.exp(st - m_safe)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(pt, axis=0,
                                                      keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + _dot(
                v_ref[rows, :], pt.astype(BF16), _TN)       # d x queries
            m_ref[...] = m_next
            return carry

        lax.fori_loop(0, i + 1, key_tile, None)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)

    q_spec, kv_spec, row_spec, mask_spec = _block_specs(pl, length, d, tile,
                                                        rep)
    return pl.pallas_call(
        pallas_sparse_gqa_fwd,
        grid=(b, kv, nq, rep),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b, length, heads * d), BF16),
                   jax.ShapeDtypeStruct((b, heads, 1, length), F32)],
        scratch_shapes=[pltpu.VMEM((1, tile), F32),
                        pltpu.VMEM((1, tile), F32),
                        pltpu.VMEM((d, tile), F32)],
        compiler_params=_compiler_params(
            pltpu, ("parallel", "parallel", "parallel", "arbitrary"),
            length, d, tile),
        interpret=interpret,
        name="pallas_sparse_gqa_fwd",
    )


@functools.lru_cache(maxsize=None)
def _bwd_call(b, length, heads, kv, d, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep, nq = heads // kv, length // tile
    scale = 1.0 / math.sqrt(d)

    def pallas_sparse_gqa_bwd(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                              delta_ref, dq_ref, dk_ref, dv_ref,
                              dq_acc, dk_acc, dv_acc):
        i, r = pl.program_id(2), pl.program_id(3)

        @pl.when((i == 0) & (r == 0))
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, F32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, F32)

        q, do = q_ref[...], do_ref[...]
        lse, delta = lse_ref[...], delta_ref[...]           # (1, tile)
        dq_acc[...] = jnp.zeros(dq_acc.shape, F32)

        def key_tile(j, carry):
            rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
            kj, vj = k_ref[rows, :], v_ref[rows, :]
            st = _masked(_dot(kj, q, _NT) * scale, mask_ref[rows, :])
            pt = jnp.exp(st - lse)                      # keys x queries
            dv_acc[rows, :] += _dot(pt.astype(BF16), do, _NN)
            dpt = _dot(vj, do, _NT)
            dst = (pt * (dpt - delta) * scale).astype(BF16)
            dk_acc[rows, :] += _dot(dst, q, _NN)
            dq_acc[...] += _dot(kj, dst, _TN)               # d x queries
            return carry

        lax.fori_loop(0, i + 1, key_tile, None)
        dq_ref[...] = dq_acc[...].T.astype(dq_ref.dtype)

        @pl.when((i == nq - 1) & (r == rep - 1))
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    q_spec, kv_spec, row_spec, mask_spec = _block_specs(pl, length, d, tile,
                                                        rep)
    kv_shape = jax.ShapeDtypeStruct((b, length, kv * d), BF16)
    return pl.pallas_call(
        pallas_sparse_gqa_bwd,
        grid=(b, kv, nq, rep),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec, q_spec, row_spec,
                  row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, length, heads * d), BF16),
                   kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((d, tile), F32),
                        pltpu.VMEM((length, d), F32),
                        pltpu.VMEM((length, d), F32)],
        compiler_params=_compiler_params(
            pltpu, ("parallel", "parallel", "arbitrary", "arbitrary"),
            length, d, tile),
        interpret=interpret,
        name="pallas_sparse_gqa_bwd",
    )


@functools.lru_cache(maxsize=None)
def _probs_call(b, length, heads, kv, d, tile, block, interpret):
    """Query block ``block`` (static) of ``length`` tokens' q, k and
    log-sum-exp against its own ``block + 1`` key tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep, nk = heads // kv, block + 1
    scale = 1.0 / math.sqrt(d)

    def pallas_sparse_gqa_probs(q_ref, k_ref, mask_ref, lse_ref, out_ref,
                                acc_ref):
        g = pl.program_id(2)

        @pl.when(g == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        kj, mask = k_ref[...], mask_ref[...]
        for r in range(rep):
            st = _masked(_dot(kj, q_ref[:, r * d:(r + 1) * d], _NT) * scale,
                         mask)                              # keys x queries
            acc_ref[...] += jnp.exp(st - lse_ref[r])

        @pl.when(g == kv - 1)
        def _():
            out_ref[...] = acc_ref[...] / heads

    return pl.pallas_call(
        pallas_sparse_gqa_probs,
        grid=(b, nk, kv),
        in_specs=[
            pl.BlockSpec((None, tile, rep * d), lambda n, j, g: (n, block, g)),
            pl.BlockSpec((None, tile, d), lambda n, j, g: (n, j, g)),
            pl.BlockSpec((None, tile, tile), lambda n, j, g: (n, j, 0)),
            pl.BlockSpec((None, rep, 1, tile),
                         lambda n, j, g: (n, g, 0, block))],
        out_specs=pl.BlockSpec((None, tile, tile), lambda n, j, g: (n, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nk * tile, tile), F32),
        scratch_shapes=[pltpu.VMEM((tile, tile), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="pallas_sparse_gqa_probs",
    )


def _sizes(q, k, tile):
    b, length, heads, d = q.shape
    return b, length, heads, k.shape[2], d, int(tile)


def attend(q, k, v, mask, tile):
    """``softmax`` over each query's selected keys of ``q . k / sqrt(d)``
    times v: q (batch, length, heads, d), k / v (batch, length,
    kv_heads, d), all bf16; ``mask`` as :func:`mask_blocks` makes it
    (check :func:`sparse_gqa_available` first). Returns the context and
    the rows' log-sum-exp, (batch, heads, 1, length) float32."""
    call = _fwd_call(*_sizes(q, k, tile), pallas_common.interpret_mode())
    o, lse = call(_lanes(q), _lanes(k), _lanes(v), mask)
    return o.reshape(q.shape), lse


def attend_bwd(q, k, v, mask, o, lse, do, tile):
    """(dq, dk, dv) of :func:`attend`'s context from its cotangent
    ``do``, the saved context and log-sum-exp."""
    call = _bwd_call(*_sizes(q, k, tile), pallas_common.interpret_mode())
    do = do.astype(BF16)
    delta = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1) \
        .transpose(0, 2, 1)[:, :, None, :]
    dq, dk, dv = call(_lanes(q), _lanes(k), _lanes(v), mask, _lanes(do), lse,
                      delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def head_mean_probs(q, k, keep, lse, block, tile):
    """Query block ``block``'s attention probabilities averaged over the
    heads, (batch, (block + 1) * tile, tile) float32, keys x queries
    like the mask (the layout in which the compiler keeps a block's
    index scores too, so the index loss reads them without a copy):
    q, k whole, ``keep`` the block's (batch, keys, tile) int8 mask,
    ``lse`` :func:`attend`'s."""
    call = _probs_call(*_sizes(q, k, tile), int(block),
                       pallas_common.interpret_mode())
    return call(_lanes(q), _lanes(k), keep, lse)
