"""The expert buffer's rows summed back by token in a Pallas kernel that
reads the buffer in whole windows of rows and picks a token's rows out
of them on the MXU (``ops/decoder_ops.py::_sum_slots`` holds the XLA
form it stands in for, which stays the path of everything
:func:`sum_available` refuses).

Alone on the chip (PERF.md section 6, PR 43) XLA moves rows *into* the
sorted buffer at the memory's pace (``_gather_rows``: 0.66 ms for the
73,728 rows of a Mellum 2 layer; the (tokens, hidden) operand is small
enough for the compiler to hold on chip), and rows *out of* it at 59 ns
a slot (``_sum_slots``: 7.7 ms for 131,072 slots, three quarters of
them the zero row, read at random from a 340 MB buffer into a (tokens,
top_k, hidden) bf16 result whose ``top_k`` pads to a 16-row tile). A
kernel that issues one async copy a row does no better: Mosaic refuses
a slice of fewer rows than a tile, and a copy a row costs ~100 ns of
the scalar core (same section). What the code can see is that **the
buffer is sorted**: within an expert's run the rows lie in token order,
so the rows that a block of consecutive tokens needs are a few short
contiguous stretches, one an expert.

``pallas_moe_rows_sum``: ``out[t] = sum_j rows[row_of_slot[t, j]]``
(nothing added where the slot is one past the end), float32
accumulation, cast once. The buffer is cut into windows of ``_WINDOW``
rows (whole bf16 tiles: a window is one aligned, contiguous copy of the
(16, 128)-tiled array as it lies; no packed view, any whole number of
lane tiles). From ``token_of_row`` XLA lists, for each block of
``_TOKENS`` tokens, the windows that hold a row of one of its tokens,
ascending (:func:`_windows`: one comparison of every row's block with
every block and a stable sort of blocks x windows flags; the list and
its lengths are scalar prefetches). A grid step is a block of tokens;
it takes its windows ``_GROUP`` at a time: a group's copies HBM -> VMEM
fly while a 0/1 matrix ``P[t, c] = any_j (row_of_slot[t, j] == the
buffer row staged at c)`` is built (``top_k`` integer comparisons in
VMEM), then one product ``P @ staged`` ((tokens, group x window) x
(group x window, hidden), bf16 operands, float32 accumulation: each
product is 1 x a bf16, exact). A slot that is not held here matches no
staged row; a window that no token of the block needs is never copied;
the tail of the buffer that no slot fills is never read. The sum runs
in the buffer's row order (expert order), not slot order: a float32 sum
of at most ``top_k`` bf16 values, the same to the last bit unless their
exponents lie more than 16 apart. (A form that lists the windows in the
kernel from a stretch a segment and multiplies one group while the
next one's copies fly read 0.85 for 1.38 ms alone; the Keye-VL cell's
step no longer compiled with it, 34 MB over the chip: PERF.md section
6, PR 43.)

Nothing but the cost rests on the order: rows in any other order need
more windows, and the sums are the same. (A staged row that the
block does not select is multiplied by 0, so a NaN or an infinity in a
*neighbour's* row would reach the block's tokens: the buffer's rows are
finite wherever the step is.)

``decoder_ops._sum_slots`` calls :func:`sum_slots` where
:func:`sum_available` allows; it is ``_gather_rows``' transpose under
``custom_vjp`` (slots <-> rows is one-to-one on what is held): its
backward is XLA's gather, the gather's backward is this kernel, and
neither runs a scatter. ``token_of_row`` comes with the routing, made
once a layer call (``decoder_ops._rows_to_slots``: the layer's one
scatter, in which no two updates share a place); the windows' lists are
made from it at each of a layer's two calls (0.04 ms each in the LFM2
cell: PERF.md section 6, PR 60).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_common
from .pallas_causal_gqa import BF16, F32

__all__ = ["sum_available", "sum_slots"]

I32 = jnp.int32
_LANE = 128
_ROWS = 16                      # a bf16 sublane tile
_TOKENS = 512                   # the most tokens a grid step sums
_WINDOW = 32                    # rows a window of the buffer holds
_GROUP = 16                     # windows staged (and multiplied) together
_VMEM_BUDGET = 48 * 1024 * 1024


def _tokens(t):
    """Tokens a grid step: whole bf16 tiles, dividing ``t``, at most
    ``_TOKENS``; 0 where none does."""
    return max([n for n in range(_ROWS, min(t, _TOKENS) + 1, _ROWS)
                if t % n == 0], default=0)


def _vmem_bytes(tokens, d):
    staged = _GROUP * _WINDOW
    # a group's staged rows; the accumulator and the product in
    # float32; the output block and the slots' block twice (the
    # pipeline's buffers); P and the comparisons' masks
    return staged * d * 2 + 2 * tokens * d * 4 + 2 * tokens * d * 2 \
        + 2 * tokens * _LANE * 4 + 3 * tokens * staged * 4


def sum_available(rows, top_k, tokens):
    """Whether the kernel may sum this buffer's rows, from what the code
    can observe: one device in the mesh being traced for; bf16 rows of
    whole lane tiles; a buffer of whole windows; a token count of whole
    bf16 tiles; the working set within the VMEM budget; and a kernel
    that will be compiled (a TPU backend) or whose interpretation was
    asked for (a plain CPU keeps XLA's gather). rows (buffer rows,
    hidden), ``tokens`` the rows of the result."""
    cap, d = rows.shape
    step = _tokens(tokens)
    return bool(
        pallas_common.kernels_allowed()
        and rows.dtype == BF16
        and d > 0 and d % _LANE == 0
        and cap > 0 and cap % _WINDOW == 0
        and top_k > 0 and step > 0
        and _vmem_bytes(step, d) <= _VMEM_BUDGET
        and (not pallas_common.interpret_mode()
             or pallas_common.interpret_asked()))


def _windows(token_of_row, t, tokens):
    """For each block of ``tokens`` tokens the buffer's windows that
    hold a row of one of its tokens, ascending, then the others: (blocks
    x windows,) int32, and how many of each block's are needed."""
    cap = token_of_row.shape[0]
    # (an empty row's token is ``t``: the block one past the last)
    block_of_row = (token_of_row // tokens).reshape(cap // _WINDOW, _WINDOW)
    needed = jnp.any(block_of_row[:, :, None] == jnp.arange(t // tokens),
                     axis=1).T
    order = jnp.argsort(jnp.logical_not(needed), axis=1, stable=True)
    return order.astype(I32).reshape(-1), jnp.sum(needed, axis=1, dtype=I32)


@functools.lru_cache(maxsize=None)
def _sum_call(t, k, d, cap, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens = _tokens(t)
    windows = cap // _WINDOW
    staged = _GROUP * _WINDOW

    def pallas_moe_rows_sum(window_ref, count_ref, slot_ref, rows_hbm, o_ref,
                            stage, acc, sem):
        b = pl.program_id(0)
        count = count_ref[b]

        @pl.when(b == 0)
        def _():
            # (what a group's unused places hold is multiplied by 0)
            stage[...] = jnp.zeros(stage.shape, stage.dtype)

        acc[...] = jnp.zeros(acc.shape, F32)
        place = lax.broadcasted_iota(I32, (1, staged), 1)

        def copy(window, i):
            return pltpu.make_async_copy(
                rows_hbm.at[pl.ds(pl.multiple_of(window * _WINDOW, _WINDOW),
                                  _WINDOW), :],
                stage.at[pl.ds(i * _WINDOW, _WINDOW), :], sem.at[0])

        def group(g, carry):
            first = g * _GROUP
            # the buffer row that each staged row is (none: negative)
            row_of_place = jnp.full((1, staged), -1, I32)
            for i in range(_GROUP):
                live = first + i < count
                window = window_ref[
                    b * windows + jnp.minimum(first + i, windows - 1)]

                @pl.when(live)
                def _():
                    copy(window, i).start()

                start = jnp.where(live, window * _WINDOW, -2 * _WINDOW)
                row_of_place = jnp.where(
                    place // _WINDOW == i, start + place % _WINDOW,
                    row_of_place)
            slots = slot_ref[...]
            hit = slots[:, 0:1] == row_of_place
            for j in range(1, k):
                hit = hit | (slots[:, j:j + 1] == row_of_place)
            picks = jnp.where(hit, 1.0, 0.0).astype(BF16)
            for i in range(_GROUP):
                @pl.when(first + i < count)
                def _():
                    copy(0, i).wait()
            acc[...] += lax.dot_general(
                picks, stage[...], (((1,), (0,)), ((), ())),
                precision=lax.Precision.DEFAULT, preferred_element_type=F32)
            return carry

        lax.fori_loop(0, (count + _GROUP - 1) // _GROUP, group, 0)
        o_ref[...] = acc[...].astype(o_ref.dtype)

    return pl.pallas_call(
        pallas_moe_rows_sum,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(t // tokens,),
            in_specs=[pl.BlockSpec((tokens, k), lambda b, w, c: (b, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, d), lambda b, w, c: (b, 0)),
            scratch_shapes=[pltpu.VMEM((staged, d), BF16),
                            pltpu.VMEM((tokens, d), F32),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((t, d), BF16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET + (16 << 20)),
        interpret=interpret,
        name="pallas_moe_rows_sum",
    )


def sum_slots(rows, token_of_row, row_of_slot):
    """``out[t] = sum_j rows[row_of_slot[t, j]]``: ``rows`` (buffer
    rows, hidden) bf16 (check :func:`sum_available` first),
    ``token_of_row`` (buffer rows,) the token of each row (the token
    count where no slot fills it), ``row_of_slot`` (tokens, top_k) its
    inverse (the buffer's row count where the slot is not held here).
    Not differentiated here: ``decoder_ops._sum_slots`` is, by its
    transpose."""
    t, k = row_of_slot.shape
    cap, d = rows.shape
    window_of_block, count = _windows(token_of_row, t, _tokens(t))
    call = _sum_call(t, k, d, cap, pallas_common.interpret_mode())
    return call(window_of_block, count, row_of_slot, rows)
