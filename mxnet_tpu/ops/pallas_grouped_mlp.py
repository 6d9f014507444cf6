"""The expert buffer's products as grouped Pallas kernels that read each
expert's weights where they lie (``ops/decoder_ops.py::_blocks_product``
holds the composition they stand in for, and stays the path of
everything they cannot serve).

The rows arrive by XLA's gather (``decoder_ops._gather_rows``: at the
memory's pace, PERF.md section 6, PR 43) and leave, summed by token,
through the window kernel of ``ops/pallas_moe_rows.py``
(``decoder_ops._sum_slots``), which forks on its own predicate.

The buffer is ``blocks`` blocks of ``block`` rows sorted by expert, and
``expert_of_block`` (int32, non-decreasing: an expert's blocks are
contiguous, the empty blocks past the last expert's run are mapped to
the last expert) says whose weights a block multiplies. It is a scalar
prefetch: a ``BlockSpec``'s index map reads ``expert_of_block[b]`` and
picks the tile of ``w1`` (held, width or 2 x width, hidden) or ``w2``
(held, hidden, width) straight from the experts' own arrays, in the
MXNet ``(out, in)`` layout they have. No gathered or transposed copy of
a weight exists in HBM, and no per-block gradient.

``used`` (int32), the second scalar prefetch, is the number of blocks
that hold a routed row. The buffer is packed, so those are its first
``used`` blocks, and everything from there on is an empty tail of zero
rows. Five kernels, each computing the first ``used`` blocks and
skipping the tail: a skipped block's inputs are not fetched (the index
maps hold at the last computed block, whose tiles are in VMEM already),
no product runs for it, and what it would write is written as zeros.
The four that produce rows take the whole contraction in one step (a
block's rows and a weight tile sit in VMEM; no accumulator) on a grid
(weight tile, block) with the blocks innermost: over an expert's run of
blocks the weight tile's index does not change and the pipeline does
not fetch it again, the rows are read once a weight tile (megablox's
``gmm`` order; PERF.md section 6, PR 35, has what the other order read).

``w1`` holds ``pieces`` stacks of ``width`` rows (1 for ``relu2``; 2 for
``swiglu``, the gate's over the up projection's) and ``act`` maps the
``pieces`` float32 results, side by side along the last axis, to the
hidden layer: element ``c`` of its output from element ``c`` of each
piece, so a tile of the width can be activated alone. Which activation
it is nothing here knows: the kernels get the callable and trace it
(and ``jax.vjp`` of it) inside their bodies, on the float32
accumulators in VMEM.

* ``pallas_grouped_mlp_up``: ``h[b] = act(x[b] @ w1[e(b)]^T)``. A step
  fetches the ``pieces`` tiles of ``w1[e(b)]`` that one tile of the
  width reads, contracts each with the block's rows and writes the
  tile of ``h`` in bf16. Where the call is being differentiated it
  also writes the float32 products, ``pre``, piece-major (pieces,
  rows, width: every block of it whole lane tiles); the plain primal,
  which nothing reads ``pre`` of, does not.
* ``pallas_grouped_mlp_nt``: ``y[b] = (h[b] @ w2[e(b)]^T) *
  weight_of_row`` (the float32 slot weights multiply the float32
  result before it is cast): the second forward product.
* ``pallas_grouped_mlp_dh``: ``dh = g[b] @ w2[e(b)]`` stays in VMEM.
  The step reads the block's tiles of ``pre``, runs ``jax.vjp(act,
  pre)``, and writes ``dpre = pull(dh * weight_of_row)`` in bf16,
  piece-major, ``h`` rounded to bf16 as the forward rounds it (the down
  product's gradient reads it), and the tile's part of the slot
  weights' gradient, ``sum(h * dh)`` over the tile's columns from that
  ``h``: eight float32 partial sums a row a tile of the width, a row of
  the buffer along the lanes, which XLA adds up (rows x 32 bytes x
  tiles: the one fusion left between the products).
* ``pallas_grouped_mlp_nn``: ``dx[b] = dpre[b] @ w1[e(b)]``, the
  contraction running over the pieces.
* ``pallas_grouped_mlp_dw``: ``dW[e] = sum over the blocks b of e of
  g[b]^T x[b]``, grid (tile of dW's rows, tile of its columns, block),
  blocks innermost: a float32 accumulator in VMEM is set at an expert's
  first block and written (cast to the weights' dtype) at its last, so
  a tile of ``dW[e]`` goes to HBM once. (megablox's ``tgmm`` has this
  grid too, after a transposed copy of ``g`` in HBM that the
  contraction over sublanes here does without.) ``g`` is piece-major
  (``dpre`` as ``dh`` wrote it; the one piece of the output's
  cotangent), a tile of ``dW``'s rows within one piece. An optional
  scale a row multiplies ``g`` in VMEM. **An expert with no block is
  never visited**: the output is an array of zeros that the call takes
  over (``input_output_aliases``; the kernel never reads it), so such
  an expert's tiles stay the zeros they were, and no masked copy of the
  gradient is made after the call. The skipped blocks past the last run
  add nothing to the last expert's sum; where that expert (or every
  expert) has no computed block, the first skipped one sets its sum to
  zeros.

Between the six products of a step no XLA fusion runs over the buffer,
and the only float32 array of the buffer's length in HBM is the kept
``pre``, which these kernels alone read and write. ``h`` is not kept
beside it: ``dh`` has it in VMEM and writes it again (kept, it was one
store a step of that kernel less and 49 to 57 MB more of the Keye-VL
step's heap, none of them live: PERF.md section 6, PR 57).

Precision is the composition's: bf16 operands into the MXU, float32
accumulation (``Precision.DEFAULT`` pinned), float32 activation and
derivative, ``h``, ``dpre`` and the cotangent cast to bf16 for their
products.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

from . import pallas_common
from .pallas_causal_gqa import BF16, F32, _NN, _NT, _TN, _dot

__all__ = ["SCOPE", "grouped_mlp_available", "grouped_mlp"]

# the device-side scope of the expert buffer's work, kernel or
# composition (``decoder_ops._moe_experts`` opens it; the backward rule
# here opens it again, being traced after the caller's has closed)
SCOPE = "mx.moe.experts"

_LANE = 128
_ROWS = 16                      # a bf16 sublane tile
_SUBLANES = 8                   # a float32 one
_TILE = 1024                    # the widest tile of a weight's rows / columns
_VMEM_BUDGET = 64 * 1024 * 1024


def _tile(n):
    """The widest tile of whole lane tiles that divides ``n`` (a whole
    number of lane tiles) and is at most ``_TILE``."""
    return max(t for t in range(_LANE, min(n, _TILE) + 1, _LANE) if n % t == 0)


def _rows_vmem_bytes(block, k, n):
    tn = _tile(n)
    # rows (the whole contraction), weight tile and bf16 result twice
    # (the pipeline's two buffers), the float32 product once
    return 2 * (block * k * 2 + tn * k * 2 + block * tn * 2) + block * tn * 4


def _up_vmem_bytes(block, hidden, f, pieces):
    tn = _tile(f)
    pre = pieces * block * tn * 4
    # rows, the pieces' weight tiles, ``pre`` and ``h`` twice; the
    # accumulators and as much again for the activation's temporaries
    return 2 * (block * hidden * 2 + pieces * tn * hidden * 2 + pre
                + block * tn * 2) + 2 * pre


def _dh_vmem_bytes(block, hidden, f, pieces):
    tn = _tile(f)
    pre = pieces * block * tn * 4
    # the cotangent's rows, a weight tile, ``pre``, ``dpre``, ``h`` and
    # the slot weights' column (a lane tile wide in VMEM) twice; ``dh``
    # and three times ``pre`` for the derivative's temporaries
    return 2 * (block * hidden * 2 + tn * hidden * 2 + pre + pre // 2
                + block * tn * 2 + block * _LANE * 4) \
        + block * tn * 4 + 3 * pre


def _dw_vmem_bytes(block, m, n):
    tm, tn = _tile(m), _tile(n)
    return 2 * (block * (tm + tn) * 2 + tm * tn * 2) + 2 * tm * tn * 4


def _vmem_bytes(block, hidden, f, pieces):
    """The largest working set of the six products."""
    return max(_up_vmem_bytes(block, hidden, f, pieces),
               _rows_vmem_bytes(block, f, hidden),
               _dh_vmem_bytes(block, hidden, f, pieces),
               _rows_vmem_bytes(block, pieces * f, hidden),
               _dw_vmem_bytes(block, hidden, f),
               _dw_vmem_bytes(block, f, hidden))


def grouped_mlp_available(xr, up, down):
    """Whether the kernels may serve this buffer, from what the code
    can observe: one device in the mesh being traced for; bf16 rows and
    weights; blocks of whole bf16 sublane tiles; a hidden size and
    widths of whole lane tiles, ``w1`` a whole number of stacks of the
    width; rows, weight tiles and the activation's float32 tiles within
    the VMEM budget; and kernels that will be compiled (a TPU backend)
    or whose interpretation was asked for (a plain CPU keeps the
    composition). xr (blocks, rows, hidden), up (held, f1, hidden), down
    (held, hidden, f). (A width off the lane tiles, the Nemotron cell's
    1,856 = 14.5, Mosaic takes as one whole tile in every product, and
    the step ran 11% faster; but under ``ShardedTrainStep``'s AUTO
    layouts the compiler then lays the float32 masters of ``w2`` out
    minor-to-major (0, 2, 1), and the executable that comes back from
    the persistent compile cache asks for another layout than it
    reports: the second run of the cell fails. PERF.md section 6, PR 35;
    PR 28 met the same with ``lax.ragged_dot``.)"""
    block, hidden = xr.shape[1:]
    f1, f = up.shape[1], down.shape[2]
    return bool(
        pallas_common.kernels_allowed()
        and all(t.dtype == BF16 for t in (xr, up, down))
        and block % _ROWS == 0
        and all(n > 0 and n % _LANE == 0 for n in (hidden, f1, f))
        and f1 % f == 0
        and _vmem_bytes(block, hidden, f, f1 // f) <= _VMEM_BUDGET
        and (not pallas_common.interpret_mode()
             or pallas_common.interpret_asked()))


def _fetched(b, used):
    """Block ``b``, or the last computed block where ``b`` is skipped:
    an input's index map through it fetches nothing for a skipped
    block."""
    return jnp.minimum(b, jnp.maximum(used[0] - 1, 0))


def _compiler_params(pltpu, semantics, nbytes):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=min(nbytes + (16 << 20), 110 << 20))


def _total(parts):
    """Their sum (``sum`` starts from an int 0: one more add a tile)."""
    return functools.reduce(operator.add, parts)


def _side_by_side(pieces):
    """The pieces' tiles along the last axis, as ``act`` reads them."""
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=-1)


def _computed_or_zeros(pl, computed, outs, compute):
    """Run ``compute`` where the block holds a row; else write the
    zeros its products would come to."""
    pl.when(computed)(compute)

    @pl.when(jnp.logical_not(computed))
    def _():
        for o_ref in outs:
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _up_call(blocks, block, k, f, pieces, act, keeps, interpret):
    """``h[b] = act(x[b] @ W[e(b)]^T)`` in bf16 for the first ``used``
    blocks (W (held, pieces, f, k)), and where ``keeps`` the float32
    products ``pre`` (pieces, rows, f); zeros for the rest."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tn = _tile(f)

    def pallas_grouped_mlp_up(e_ref, used_ref, x_ref, w_ref, h_ref, *pre_ref):
        def compute():
            x = x_ref[...]
            pre = [_dot(x, w_ref[p], _NT) for p in range(pieces)]
            h_ref[...] = act(_side_by_side(pre)).astype(h_ref.dtype)
            for ref in pre_ref:
                for p in range(pieces):
                    ref[p] = pre[p]

        _computed_or_zeros(pl, pl.program_id(1) < used_ref[0],
                           (h_ref,) + pre_ref, compute)

    rows = jax.ShapeDtypeStruct((blocks * block, f), BF16)
    kept = jax.ShapeDtypeStruct((pieces, blocks * block, f), F32)
    return pl.pallas_call(
        pallas_grouped_mlp_up,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // tn, blocks),
            in_specs=[pl.BlockSpec((block, k),
                                   lambda j, b, e, u: (_fetched(b, u), 0)),
                      pl.BlockSpec((None, pieces, tn, k),
                                   lambda j, b, e, u: (e[_fetched(b, u)], 0,
                                                       j, 0))],
            out_specs=[pl.BlockSpec((block, tn), lambda j, b, e, u: (b, j))]
            + [pl.BlockSpec((pieces, block, tn),
                            lambda j, b, e, u: (0, b, j))] * keeps),
        out_shape=[rows] + [kept] * keeps,
        compiler_params=_compiler_params(
            pltpu, ("parallel", "arbitrary"),
            _up_vmem_bytes(block, k, f, pieces)),
        interpret=interpret,
        name="pallas_grouped_mlp_up",
    )


@functools.lru_cache(maxsize=None)
def _dh_call(blocks, block, k, f, pieces, act, interpret):
    """For the first ``used`` blocks, with ``dh = g[b] @ W[e(b)]`` (W
    (held, k, f)) and ``h, pull = vjp(act, pre[b])``: ``dpre =
    pull(dh * scale[b])`` in bf16 (pieces, rows, f); ``h`` in bf16
    (rows, f); and of ``sum(bf16(h) * dh)`` a row, for each tile of
    ``f``, eight partial sums (tiles, blocks, 8, block), float32; zeros
    for the rest."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tn = _tile(f)

    def pallas_grouped_mlp_dh(e_ref, used_ref, g_ref, w_ref, pre_ref,
                              scale_ref, dpre_ref, dot_ref, h_ref):
        def compute():
            dh = _dot(g_ref[...], w_ref[...], _NN)
            h, pull = jax.vjp(act, _side_by_side(
                [pre_ref[p] for p in range(pieces)]))
            h = h.astype(BF16)
            h_ref[...] = h
            # h . dh a row, lane-dense: the tile's columns summed a lane
            # tile at a time, the (rows, 128) sums turned, their 128
            # rows summed to 8, which XLA adds with the tiles' (a column
            # a row in HBM is padded to a lane tile, 128 times its bytes)
            part = h.astype(F32) * dh
            part = _total([part[:, c:c + _LANE]
                           for c in range(0, tn, _LANE)]).T
            dot_ref[...] = _total([part[r:r + _SUBLANES]
                                   for r in range(0, _LANE, _SUBLANES)])
            dpre, = pull(dh * scale_ref[...])
            for p in range(pieces):
                dpre_ref[p] = dpre[:, p * tn:(p + 1) * tn] \
                    .astype(dpre_ref.dtype)

        _computed_or_zeros(pl, pl.program_id(1) < used_ref[0],
                           (dpre_ref, dot_ref, h_ref), compute)

    return pl.pallas_call(
        pallas_grouped_mlp_dh,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // tn, blocks),
            in_specs=[pl.BlockSpec((block, k),
                                   lambda j, b, e, u: (_fetched(b, u), 0)),
                      pl.BlockSpec((None, k, tn),
                                   lambda j, b, e, u: (e[_fetched(b, u)], 0,
                                                       j)),
                      pl.BlockSpec((pieces, block, tn),
                                   lambda j, b, e, u: (0, _fetched(b, u), j)),
                      pl.BlockSpec((block, 1),
                                   lambda j, b, e, u: (_fetched(b, u), 0))],
            out_specs=[pl.BlockSpec((pieces, block, tn),
                                    lambda j, b, e, u: (0, b, j)),
                       pl.BlockSpec((None, None, _SUBLANES, block),
                                    lambda j, b, e, u: (j, b, 0, 0)),
                       pl.BlockSpec((block, tn), lambda j, b, e, u: (b, j))]),
        out_shape=[
            jax.ShapeDtypeStruct((pieces, blocks * block, f), BF16),
            jax.ShapeDtypeStruct((f // tn, blocks, _SUBLANES, block), F32),
            jax.ShapeDtypeStruct((blocks * block, f), BF16)],
        compiler_params=_compiler_params(
            pltpu, ("parallel", "arbitrary"),
            _dh_vmem_bytes(block, k, f, pieces)),
        interpret=interpret,
        name="pallas_grouped_mlp_dh",
    )


@functools.lru_cache(maxsize=None)
def _rows_call(blocks, block, k, n, pieces, transposed, scaled, interpret):
    """``out[b] = sum over the pieces p of a[p][b] @ W[e(b)][p]^T``
    (``transposed``: W (held, pieces, n, k)) or ``a[p][b] @
    W[e(b)][p]`` (W (held, pieces, k, n)), times ``scale[b]`` a row
    where ``scaled``, in bf16 for the first ``used`` blocks (a (pieces,
    rows, k)); zeros for the rest."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tn = _tile(n)
    dims = _NT if transposed else _NN

    def body(e_ref, used_ref, a_ref, w_ref, *rest):
        o_ref = rest[-1]

        def compute():
            acc = _total([_dot(a_ref[p], w_ref[p], dims)
                          for p in range(pieces)])
            if scaled:
                acc = acc * rest[0][...]
            o_ref[...] = acc.astype(o_ref.dtype)

        _computed_or_zeros(pl, pl.program_id(1) < used_ref[0], (o_ref,),
                           compute)

    body.__name__ = "pallas_grouped_mlp_" + ("nt" if transposed else "nn")
    rows = pl.BlockSpec((pieces, block, k),
                        lambda j, b, e, u: (0, _fetched(b, u), 0))
    weight = pl.BlockSpec((None, pieces, tn, k),
                          lambda j, b, e, u: (e[_fetched(b, u)], 0, j, 0)) \
        if transposed else \
        pl.BlockSpec((None, pieces, k, tn),
                     lambda j, b, e, u: (e[_fetched(b, u)], 0, 0, j))
    scale = pl.BlockSpec((block, 1), lambda j, b, e, u: (_fetched(b, u), 0))
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, blocks),
            in_specs=[rows, weight] + [scale] * scaled,
            out_specs=pl.BlockSpec((block, tn), lambda j, b, e, u: (b, j))),
        out_shape=jax.ShapeDtypeStruct((blocks * block, n), BF16),
        compiler_params=_compiler_params(
            pltpu, ("parallel", "arbitrary"),
            _rows_vmem_bytes(block, pieces * k, n)),
        interpret=interpret,
        name=body.__name__,
    )


@functools.lru_cache(maxsize=None)
def _dw_call(blocks, block, m, n, pieces, experts, scaled, interpret):
    """``dW[e] = sum_{b < used: e(b) = e} (scale[b] g[b])^T x[b]``: g
    (pieces, rows, m), x (rows, n) -> (held, pieces x m, n) bf16,
    written over the last input (zeros, unread); a tile of an expert
    that no block is mapped to is not written and keeps them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tn = _tile(m), _tile(n)
    tiles = m // tm             # of a piece's rows

    def pallas_grouped_mlp_dw(e_ref, used_ref, g_ref, x_ref, *rest):
        o_ref, acc_ref = rest[-2:]
        b = pl.program_id(2)
        e = e_ref[b]
        first = (b == 0) | (e_ref[jnp.maximum(b - 1, 0)] != e)
        last = (b == blocks - 1) | (e_ref[jnp.minimum(b + 1, blocks - 1)] != e)
        computed = b < used_ref[0]

        @pl.when(computed)
        def _():
            g = g_ref[...]
            if scaled:
                g = (g.astype(F32) * rest[0][...]).astype(BF16)
            part = _dot(g, x_ref[...], _TN)

            @pl.when(first)
            def _():
                acc_ref[...] = part

            @pl.when(jnp.logical_not(first))
            def _():
                acc_ref[...] += part

        # the last expert's run (or every expert's) holds no routed row
        @pl.when(first & jnp.logical_not(computed))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    scale = pl.BlockSpec((block, 1), lambda i, j, b, e, u: (_fetched(b, u), 0))
    return pl.pallas_call(
        pallas_grouped_mlp_dw,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pieces * tiles, n // tn, blocks),
            in_specs=[pl.BlockSpec((None, block, tm),
                                   lambda i, j, b, e, u: (
                                       i // tiles, _fetched(b, u), i % tiles)),
                      pl.BlockSpec((block, tn),
                                   lambda i, j, b, e, u: (_fetched(b, u), j))]
            + [scale] * scaled + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, tm, tn),
                                   lambda i, j, b, e, u: (e[b], i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32)]),
        out_shape=jax.ShapeDtypeStruct((experts, pieces * m, n), BF16),
        # (counted with the two scalar prefetches)
        input_output_aliases={4 + scaled: 0},
        compiler_params=_compiler_params(
            pltpu, ("parallel", "parallel", "arbitrary"),
            _dw_vmem_bytes(block, m, n)),
        interpret=interpret,
        name="pallas_grouped_mlp_dw",
    )


def _column(scale):
    """A row scale as the kernels' optional last input, (rows, 1)."""
    return () if scale is None else (scale[:, None],)


def _stacks(up, pieces):
    """``w1`` (held, pieces x f, hidden) as its ``pieces`` stacks of
    rows: (held, pieces, f, hidden), the same bytes."""
    held, f1, hidden = up.shape
    return up.reshape(held, pieces, f1 // pieces, hidden)


def _up(xr, up, expert_of_block, used, act, pieces, keeps):
    """``[h]``, and ``pre`` behind it where ``keeps``."""
    blocks = expert_of_block.shape[0]
    call = _up_call(blocks, xr.shape[0] // blocks, xr.shape[1],
                    up.shape[1] // pieces, pieces, act, keeps,
                    pallas_common.interpret_mode())
    return call(expert_of_block, used.reshape(1), xr, _stacks(up, pieces))


def _dh(g, down, pre, expert_of_block, used, weight_of_row, act):
    """``dpre`` (piece-major), the slot weights' gradient and ``h``."""
    blocks = expert_of_block.shape[0]
    call = _dh_call(blocks, g.shape[0] // blocks, g.shape[1], down.shape[2],
                    pre.shape[0], act, pallas_common.interpret_mode())
    dpre, parts, h = call(expert_of_block, used.reshape(1), g, down, pre,
                          *_column(weight_of_row))
    return dpre, jnp.sum(parts, axis=(0, 2)).reshape(-1), h


def _rows(a, w, expert_of_block, used, transposed, scale=None):
    """a (pieces, rows, k) with w (held, pieces, n, k) (``transposed``)
    or (held, pieces, k, n)."""
    blocks = expert_of_block.shape[0]
    pieces, rows, k = a.shape
    n = w.shape[2] if transposed else w.shape[3]
    call = _rows_call(blocks, rows // blocks, k, n, pieces, transposed,
                      scale is not None, pallas_common.interpret_mode())
    return call(expert_of_block, used.reshape(1), a, w, *_column(scale))


def _dw(g, x, expert_of_block, used, experts, scale=None):
    """g (pieces, rows, m), x (rows, n) -> (held, pieces x m, n)."""
    blocks = expert_of_block.shape[0]
    pieces, rows, m = g.shape
    call = _dw_call(blocks, rows // blocks, m, x.shape[1], pieces, experts,
                    scale is not None, pallas_common.interpret_mode())
    return call(expert_of_block, used.reshape(1), g, x, *_column(scale),
                jnp.zeros((experts, pieces * m, x.shape[1]), BF16))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def grouped_mlp(xr, expert_of_block, used, weight_of_row, up, down, act):
    """Each block of the sorted buffer ``xr`` (blocks x rows, hidden),
    bf16, through its expert's two products, each row times its slot's
    float32 weight: ``(act(xr[b] @ up[e(b)]^T) @ down[e(b)]^T) *
    weight_of_row`` in bf16 (check :func:`grouped_mlp_available`
    first). ``used`` (int32 scalar) is the number of blocks that hold a
    routed row, the buffer's first: only they are computed, here and in
    the backward; the blocks from ``used`` on must be zero rows of
    weight 0, and come out as the zeros their products would be.
    ``act`` maps the first product's float32 output (``up`` holding
    ``up.shape[1] // down.shape[2]`` stacks of rows, their results side
    by side) to the second's input, column by column. Differentiated by
    hand: kept are the rows and the first product's output; the backward
    runs two more grouped products and the two weight gradients summed
    by expert in VMEM, never the second forward product again."""
    return _forward(xr, expert_of_block, used, weight_of_row, up, down, act,
                    False)[0]


def _forward(xr, expert_of_block, used, weight_of_row, up, down, act, keeps):
    """``[y]``, and ``pre`` behind it where ``keeps``."""
    pieces = up.shape[1] // down.shape[2]
    h, *pre = _up(xr, up, expert_of_block, used, act, pieces, keeps)
    return [_rows(h[None], down[:, None], expert_of_block, used, True,
                  weight_of_row), *pre]


def _vjp_fwd(xr, expert_of_block, used, weight_of_row, up, down, act):
    y, pre = _forward(xr, expert_of_block, used, weight_of_row, up, down,
                      act, True)
    return y, (xr, expert_of_block, used, weight_of_row, up, down, pre)


def _vjp_bwd(act, res, g):
    xr, expert_of_block, used, weight_of_row, up, down, pre = res
    experts = up.shape[0]
    with jax.named_scope(SCOPE):
        g = g.astype(BF16)
        # d (y w) = w dy + y dw, and y . g = h . (g @ down): the slot
        # weights' gradient without the second forward product
        dpre, d_weight, h = _dh(g, down, pre, expert_of_block, used,
                                weight_of_row, act)
        d_down = _dw(g[None], h, expert_of_block, used, experts,
                     weight_of_row)
        d_up = _dw(dpre, xr, expert_of_block, used, experts)
        dx = _rows(dpre, _stacks(up, pre.shape[0]), expert_of_block, used,
                   False)
    return dx, None, None, d_weight, d_up, d_down


grouped_mlp.defvjp(_vjp_fwd, _vjp_bwd)
