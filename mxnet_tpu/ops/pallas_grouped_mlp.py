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
rows. Three kernels, each computing the first ``used`` blocks and
skipping the tail: a skipped block's inputs are not fetched (the index
maps hold at the last computed block, whose tiles are in VMEM already)
and no product runs for it:

* ``pallas_grouped_mlp_nt``: ``out[b] = a[b] @ W[e(b)]^T`` (``W``
  (held, n, k)): the two forward products.
* ``pallas_grouped_mlp_nn``: ``out[b] = a[b] @ W[e(b)]`` (``W`` (held,
  k, n)): the two input gradients. Same body, other contraction.
  Both take the whole contraction in one step (a block's rows and a
  weight tile sit in VMEM; no accumulator), the grid is (weight tile,
  block) with the blocks innermost: over an expert's run of blocks the
  weight tile's index does not change and the pipeline does not fetch
  it again, the rows are read once a weight tile (megablox's ``gmm``
  order; PERF.md section 6, PR 35, has what the other order read). An
  optional float32 scale a row multiplies the float32 result before it
  is cast (the slot weights, forward). A skipped block's output is
  written as zeros, which is what the product of its zero rows is.
* ``pallas_grouped_mlp_dw``: ``dW[e] = sum over the blocks b of e of
  g[b]^T x[b]``, grid (tile of dW's rows, tile of its columns, block),
  blocks innermost: a float32 accumulator in VMEM is set at an expert's
  first block and written (cast to the weights' dtype) at its last, so
  a tile of ``dW[e]`` goes to HBM once. (megablox's ``tgmm`` has this
  grid too, after a transposed copy of ``g`` in HBM that the
  contraction over sublanes here does without.) An optional scale a row
  multiplies ``g`` in VMEM. **An expert with no block is never
  visited**: the output is an array of zeros that the call takes over
  (``input_output_aliases``; the kernel never reads it), so such an
  expert's tiles stay the zeros they were, and no masked copy of the
  gradient is made after the call. The skipped blocks past the last run
  add nothing to the last expert's sum; where that expert (or every
  expert) has no computed block, the first skipped one sets its sum to
  zeros.

Between the products the activation and its derivative stay XLA fusions
on the float32 ``pre`` (any ``act``: nothing here knows which).

Precision is the composition's: bf16 operands into the MXU, float32
accumulation (``Precision.DEFAULT`` pinned), float32 activation, ``h``,
``dpre`` and the cotangent cast to bf16 for their products.
"""
from __future__ import annotations

import functools

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
_TILE = 1024                    # the widest tile of a weight's rows / columns
_VMEM_BUDGET = 64 * 1024 * 1024


def _tile(n):
    """The widest tile of whole lane tiles that divides ``n`` (a whole
    number of lane tiles) and is at most ``_TILE``."""
    return max(t for t in range(_LANE, min(n, _TILE) + 1, _LANE) if n % t == 0)


def _rows_vmem_bytes(block, k, n, out_bytes):
    tn = _tile(n)
    # rows, weight tile and result twice (the pipeline's two buffers),
    # the float32 product once
    return 2 * (block * k * 2 + tn * k * 2 + block * tn * out_bytes) \
        + block * tn * 4


def _dw_vmem_bytes(block, m, n):
    tm, tn = _tile(m), _tile(n)
    return 2 * (block * (tm + tn) * 2 + tm * tn * 2) + 2 * tm * tn * 4


def _vmem_bytes(block, hidden, f1, f):
    """The largest working set of the six products."""
    return max(_rows_vmem_bytes(block, hidden, f1, 4),
               _rows_vmem_bytes(block, f, hidden, 2),
               _rows_vmem_bytes(block, hidden, f, 4),
               _rows_vmem_bytes(block, f1, hidden, 2),
               _dw_vmem_bytes(block, hidden, f),
               _dw_vmem_bytes(block, f1, hidden))


def grouped_mlp_available(xr, up, down):
    """Whether the kernels may serve this buffer, from what the code
    can observe: one device in the mesh being traced for; bf16 rows and
    weights; blocks of whole bf16 sublane tiles; a hidden size and
    widths of whole lane tiles; rows and weight tiles within the VMEM
    budget; and kernels that will be compiled (a TPU backend) or whose
    interpretation was asked for (a plain CPU keeps the composition).
    xr (blocks, rows, hidden), up (held, f1, hidden), down (held,
    hidden, f). (A width off the lane tiles, the Nemotron cell's 1,856 =
    14.5, Mosaic takes as one whole tile in every product, and the step
    ran 11% faster; but under ``ShardedTrainStep``'s AUTO layouts the
    compiler then lays the float32 masters of ``w2`` out minor-to-major
    (0, 2, 1), and the executable that comes back from the persistent
    compile cache asks for another layout than it reports: the second
    run of the cell fails. PERF.md section 6, PR 35; PR 28 met the same
    with ``lax.ragged_dot``.)"""
    block, hidden = xr.shape[1:]
    f1, f = up.shape[1], down.shape[2]
    return bool(
        pallas_common.kernels_allowed()
        and all(t.dtype == BF16 for t in (xr, up, down))
        and block % _ROWS == 0
        and all(n > 0 and n % _LANE == 0 for n in (hidden, f1, f))
        and _vmem_bytes(block, hidden, f1, f) <= _VMEM_BUDGET
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


@functools.lru_cache(maxsize=None)
def _rows_call(blocks, block, k, n, transposed, scaled, out_dtype, interpret):
    """``out[b] = a[b] @ W[e(b)]^T`` (``transposed``: W (held, n, k)) or
    ``a[b] @ W[e(b)]`` (W (held, k, n)), times ``scale[b]`` a row where
    ``scaled``, for the first ``used`` blocks; zeros for the rest."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tn = _tile(n)
    dims = _NT if transposed else _NN

    def body(e_ref, used_ref, a_ref, w_ref, *rest):
        o_ref = rest[-1]
        computed = pl.program_id(1) < used_ref[0]

        @pl.when(computed)
        def _():
            acc = _dot(a_ref[...], w_ref[...], dims)
            if scaled:
                acc = acc * rest[0][...]
            o_ref[...] = acc.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(computed))
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    body.__name__ = "pallas_grouped_mlp_" + ("nt" if transposed else "nn")
    rows = pl.BlockSpec((block, k), lambda j, b, e, u: (_fetched(b, u), 0))
    weight = pl.BlockSpec((None, tn, k),
                          lambda j, b, e, u: (e[_fetched(b, u)], j, 0)) \
        if transposed else \
        pl.BlockSpec((None, k, tn),
                     lambda j, b, e, u: (e[_fetched(b, u)], 0, j))
    scale = pl.BlockSpec((block, 1), lambda j, b, e, u: (_fetched(b, u), 0))
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, blocks),
            in_specs=[rows, weight] + [scale] * scaled,
            out_specs=pl.BlockSpec((block, tn), lambda j, b, e, u: (b, j))),
        out_shape=jax.ShapeDtypeStruct((blocks * block, n), out_dtype),
        compiler_params=_compiler_params(
            pltpu, ("parallel", "arbitrary"),
            _rows_vmem_bytes(block, k, n, jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name=body.__name__,
    )


@functools.lru_cache(maxsize=None)
def _dw_call(blocks, block, m, n, experts, scaled, interpret):
    """``dW[e] = sum_{b < used: e(b) = e} (scale[b] g[b])^T x[b]``: g
    (rows, m), x (rows, n) -> (held, m, n) bf16, written over the last
    input (zeros, unread); a tile of an expert that no block is mapped
    to is not written and keeps them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tn = _tile(m), _tile(n)

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
            num_scalar_prefetch=2, grid=(m // tm, n // tn, blocks),
            in_specs=[pl.BlockSpec((block, tm),
                                   lambda i, j, b, e, u: (_fetched(b, u), i)),
                      pl.BlockSpec((block, tn),
                                   lambda i, j, b, e, u: (_fetched(b, u), j))]
            + [scale] * scaled + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, tm, tn),
                                   lambda i, j, b, e, u: (e[b], i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32)]),
        out_shape=jax.ShapeDtypeStruct((experts, m, n), BF16),
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


def _rows(a, w, expert_of_block, used, transposed, out_dtype, scale=None):
    blocks = expert_of_block.shape[0]
    k = a.shape[1]
    n = w.shape[1] if transposed else w.shape[2]
    call = _rows_call(blocks, a.shape[0] // blocks, k, n, transposed,
                      scale is not None, jnp.dtype(out_dtype),
                      pallas_common.interpret_mode())
    return call(expert_of_block, used.reshape(1), a, w, *_column(scale))


def _dw(g, x, expert_of_block, used, experts, scale=None):
    blocks = expert_of_block.shape[0]
    call = _dw_call(blocks, g.shape[0] // blocks, g.shape[1], x.shape[1],
                    experts, scale is not None,
                    pallas_common.interpret_mode())
    return call(expert_of_block, used.reshape(1), g, x, *_column(scale),
                jnp.zeros((experts, g.shape[1], x.shape[1]), BF16))


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
    ``act`` maps the first product's float32 output to the second's
    input. Differentiated by hand: kept are the rows and the first
    product's output; the backward runs two more grouped products and
    the two weight gradients summed by expert in VMEM, never the second
    forward product again."""
    return _forward(xr, expert_of_block, used, weight_of_row, up, down,
                    act)[0]


def _forward(xr, expert_of_block, used, weight_of_row, up, down, act):
    pre = _rows(xr, up, expert_of_block, used, True, F32)
    h = act(pre).astype(xr.dtype)
    return _rows(h, down, expert_of_block, used, True, xr.dtype,
                 weight_of_row), pre


def _vjp_fwd(xr, expert_of_block, used, weight_of_row, up, down, act):
    y, pre = _forward(xr, expert_of_block, used, weight_of_row, up, down, act)
    return y, (xr, expert_of_block, used, weight_of_row, up, down, pre)


def _vjp_bwd(act, res, g):
    xr, expert_of_block, used, weight_of_row, up, down, pre = res
    experts = up.shape[0]
    with jax.named_scope(SCOPE):
        g = g.astype(BF16)
        h, pull = jax.vjp(act, pre)
        h = h.astype(BF16)
        # d (y w) = w dy + y dw, and y . g = h . (g @ down): the slot
        # weights' gradient without the second forward product
        dh = _rows(g, down, expert_of_block, used, False, F32)
        d_weight = jnp.sum(h.astype(F32) * dh, axis=-1)
        dpre, = pull(dh * weight_of_row[:, None])
        dpre = dpre.astype(BF16)
        d_down = _dw(g, h, expert_of_block, used, experts, weight_of_row)
        d_up = _dw(dpre, xr, expert_of_block, used, experts)
        dx = _rows(dpre, up, expert_of_block, used, False, BF16)
    return dx, None, None, d_weight, d_up, d_down


grouped_mlp.defvjp(_vjp_fwd, _vjp_bwd)
