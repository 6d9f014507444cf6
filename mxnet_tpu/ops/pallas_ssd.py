"""The Mamba-2 chunked scan (SSD) as two Pallas kernels, forward and
backward (``ops/decoder_ops.py::_ssd`` is the composition they stand in
for, and stays the path of everything they cannot serve: a mesh,
float32 inputs, widths off the lane tiles).

Per head, from a zero state, ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
B_t^T``, ``y_t = S_t C_t + d x_t``, computed ``chunk`` steps at a time:
products inside a chunk, one carried state between chunks.

Layout. x and y are viewed as ``(batch, length, heads * head_dim)``, B
and C as ``(batch, length, groups * state)`` (free row-major reshapes).
What a step has of its own, its ``dt`` and the cumulative log-decay
``s`` inside its chunk (``cumsum(dt a)``: float32, 2 MB a layer at the
Nemotron cell's call, XLA's, and so by autodiff the reverse sum of the
backward), is handed over twice: as columns, ``(batch, groups, length,
2 * heads a group)``, the steps on sublanes, for the tiles that
broadcast a step's number along lanes; and as rows, ``(batch, groups,
2 * heads a group, length)``, the steps on lanes, for the decays'
``s_i - s_j``. (Summing ``dt a`` and transposing a tile inside the
kernel puts a chain of latencies at the head of every grid step, which
nothing overlaps: a third of the forward's time when measured; PERF.md
section 6, PR 41.)

Grid ``(batch, group, chunk)``, the chunk axis sequential. One step
holds what the heads of a group share: the chunk's B and C tiles
``(chunk, state)`` once, x for the group's heads as one lane-dense tile
``(chunk, heads a group * head_dim)``, and the carried state of the
group, ``(state, heads a group * head_dim)`` float32 (head ``r``'s
``S_r^T`` in its ``head_dim`` lanes), in a VMEM scratch that lives
across the chunk axis. The products that all heads of a group share are
one product over the group's lanes (the read of the entering state, the
chunk's own state, their gradients); the products a head has alone (the
masked decays times the scores, times ``dt x``) are taken a 128-lane
window at a time, the heads of a window side by side along the
contraction with the other heads' lanes zeroed, so that every MXU
result is a whole lane tile.

Forward (``pallas_ssd_fwd``), a chunk: ``C B^T`` once a group; per head
``exp`` of the masked differences ``s_i - s_j`` times the scores, cast,
times ``dt x``; plus ``C`` times the entering state scaled by
``exp(s)``; plus ``d x``; then the state leaves as ``exp(s_last) S +
(B to_end)^T (dt x)``. Neither the decays, the mix, nor any
chunk-by-chunk carry exists in HBM. Inside a differentiated call
(``pallas_ssd_fwd_states``) each chunk's entering state is also
written, in the inputs' dtype (as it meets ``C``): the backward reads
it. A call that is not differentiated writes ``y`` only.

Backward (``pallas_ssd_bwd``): the chunks in reverse with the state's
cotangent in the same scratch; a chunk's decays, scores and output are
rebuilt in VMEM; written are ``dx``, ``dB`` and ``dC`` (summed over the
group's heads inside the step), the gradient of the columns (``ds`` and
the part of ``d dt`` that comes through ``dt x``; XLA's reverse sum
makes ``d dt`` and ``da`` of them), and ``dd`` as per-lane partial sums
accumulated over the chunk axis in a resident block (XLA sums them over
batch and lanes). With ``y^`` the output without its skip term, ``ds_i
= dy_i . y^_i - (dt x)_i . du_i``, plus at a chunk's last step ``<dS_out,
S_out>`` of the leaving state (the known identity of the SSD backward:
every path through the decays ends in the output or in the leaving
state). The two reductions over a head's lanes run on the MXU against a
0/1 matrix, the float32 operand split in two bf16 halves (16 bits of
mantissa a term).

Precision is the composition's or higher: log-decays, cumulative sums,
``exp`` and the carried state (and its cotangent) in float32; every
product takes the operands the composition gives it (the mix, ``dt x``,
the weighted ``x``, the entering state where it meets ``C``, all cast
to the inputs' dtype) and accumulates in float32
(``Precision.DEFAULT`` pinned). The state is carried in float32 across
chunks where the composition sums float32 chunk states once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_common
from .pallas_causal_gqa import BF16, F32, _NN, _NT, _TN, _dot

__all__ = ["SCOPE", "RESET", "log_decays", "ssd_available", "ssd_scan"]

# the device-side scope of the scan, kernel or composition
# (``decoder_ops._scan`` opens it; the backward rule here opens it
# again, being traced after the caller's has closed)
SCOPE = "mx.mamba2.ssd"

_LANE = 128
_ROWS = 8                       # a float32 sublane tile
_VMEM_BUDGET = 96 * 1024 * 1024

# A document's first step in a packed row decays what came before it by
# exp(-RESET) = 4.2e-18: under float32's resolution (6e-8) of anything
# the new document adds, by ten orders. Not larger: the log-decays are
# summed in float32 inside a chunk, a sum that has passed k starts is
# about -k RESET, and a decay is the exp of a difference of two such
# sums, so its relative error is their spacing there: 3e-5 at ten
# starts a chunk of 128 (documents of 16 tokens, the shortest a feed
# here draws, and a row's cut ends), where the sums of ``dt a`` alone
# already reach -200 and 1.5e-5
RESET = 40.0


def log_decays(dt, a_neg, reset=None):
    """(batch, length, heads) float32: a step's log-decay ``dt a``, and
    ``-RESET`` where ``reset`` (batch, length) bool says that a
    document starts (the state before it counts for nothing; no
    gradient goes to that step's ``dt`` or to ``a`` through its decay,
    as none is due). What both forms of the scan sum."""
    la = dt.astype(F32) * a_neg.astype(F32)
    if reset is None:
        return la
    return jnp.where(reset[..., None], -RESET, la)


def _bwd_vmem_bytes(q, w, n):
    """What a backward step holds: the pipeline's two buffers of each
    block (x, dy, dx and the entering state over the group's lanes; B,
    C, dB, dC; the per-step columns, their gradient and the rows, a
    lane tile wide in VMEM), the 0/1 matrix of the lane sums, the
    state's cotangent, and the step's float32 tiles (some twenty over
    the group's lanes, a window's decays and mixes)."""
    blocks = 2 * (3 * q * w * 2 + n * w * 2 + 4 * q * n * 2
                  + 3 * q * _LANE * 4)
    resident = 2 * (2 * w * _LANE * 2) + n * w * 4
    tiles = 20 * q * w * 4 + 12 * q * q * 4 + 4 * n * w * 4
    return blocks + resident + tiles


def ssd_available(x, bm, cm, chunk):
    """Whether the kernels may serve this call, from what the code can
    observe: one device in the mesh being traced for, bf16 x / B / C,
    whole groups of heads, a head width that divides a lane tile or is
    whole lane tiles, the group's ``heads * head_dim``, the state and
    the chunk whole 128-lane tiles, and a backward step that fits the
    VMEM limit the call states."""
    _, _, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    chunk = int(chunk)
    if groups <= 0 or heads % groups:
        return False
    rep = heads // groups
    return bool(
        pallas_common.kernels_allowed()
        and all(t.dtype == BF16 for t in (x, bm, cm))
        and bm.shape == cm.shape
        and (_LANE % p == 0 or p % _LANE == 0)
        and (rep * p) % _LANE == 0 and n % _LANE == 0
        and chunk > 0 and chunk % _LANE == 0
        and _bwd_vmem_bytes(chunk, rep * p, n) <= _VMEM_BUDGET)


def _compiler_params(pltpu, q, w, n):
    """The backward's working set bounds the forward's too."""
    nbytes = _bwd_vmem_bytes(q, w, n) + (16 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(nbytes, 110 << 20))


# ---------------------------------------------------------------------------
# what both kernels build of a chunk
# ---------------------------------------------------------------------------
def _spread(cols, first, rep, p):
    """(steps, rep * p) float32: head ``r``'s ``p`` lanes hold column
    ``first + r`` of ``cols`` (steps, columns)."""
    q = cols.shape[0]

    def column(r):
        return jnp.broadcast_to(cols[:, first + r:first + r + 1], (q, _LANE))

    if p >= _LANE:
        return jnp.concatenate([column(r) for r in range(rep)
                                for _ in range(p // _LANE)], axis=1)
    per = _LANE // p
    lane = lax.broadcasted_iota(jnp.int32, (q, _LANE), 1)
    tiles = []
    for first_head in range(0, rep, per):
        tile = column(first_head + per - 1)
        for i in range(per - 2, -1, -1):
            tile = jnp.where(lane < (i + 1) * p, column(first_head + i), tile)
        tiles.append(tile)
    return jnp.concatenate(tiles, axis=1)


def _windows(rep, p):
    """[(the window's first lane, its width, its heads)]: the group's
    lanes a 128-lane window (or a head of whole lane tiles) at a
    time."""
    width = max(p, _LANE)
    per = width // p
    return [(k * width, width, range(k * per, (k + 1) * per))
            for k in range(rep * p // width)]


def _by_head(tile, p):
    """A window's tile (steps, lanes) once a head of the window, with
    every lane but that head's zeroed, one under the other (the tile
    itself where the window is one head)."""
    if tile.shape[1] == p:
        return tile
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.concatenate(
        [jnp.where((lane >= i * p) & (lane < (i + 1) * p), tile,
                   jnp.zeros_like(tile))
         for i in range(tile.shape[1] // p)], axis=0)


def _decay(cols, rows, r, seen):
    """Head ``r``'s (steps i, steps j) float32 decays ``exp(s_i -
    s_j)``, 0 above the diagonal: ``s`` as column ``r`` of ``cols``
    (steps, ...) and as row ``r`` of ``rows`` (..., steps)."""
    q = rows.shape[1]
    return jnp.exp(jnp.where(
        seen, jnp.broadcast_to(cols[:, r:r + 1], (q, q)) - rows[r:r + 1, :],
        -jnp.inf))


def _chunk(cols_ref, rows_ref, x_ref, b_ref, c_ref, rep, p):
    """What both kernels build of a chunk first: (cols, rows, ``s`` and
    ``dt`` over the group's lanes, x in float32, ``dt x`` cast, B, C,
    the scores ``C B^T`` (steps i x steps j), the causal mask)."""
    cols, rows = cols_ref[...], rows_ref[...]
    s_w, dt_w = _spread(cols, 0, rep, p), _spread(cols, rep, rep, p)
    x, bm, cm = x_ref[...], b_ref[...], c_ref[...]
    xf = x.astype(F32)
    q = x.shape[0]
    seen = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return (cols, rows, s_w, dt_w, xf, (xf * dt_w).astype(x.dtype), bm, cm,
            _dot(cm, bm, _NT), seen)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _fwd_call(b, length, groups, rep, p, n, q, dtype, states, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, nc = rep * p, length // q

    def body(x_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref, y_ref, hin_ref,
             h_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            h_ref[...] = jnp.zeros(h_ref.shape, F32)

        cols, rows, s_w, dt_w, xf, u, bm, cm, scores, seen = _chunk(
            cols_ref, rows_ref, x_ref, b_ref, c_ref, rep, p)
        hin = h_ref[...]
        hb = hin.astype(dtype)
        if hin_ref is not None:
            hin_ref[...] = hb
        inside = []
        for lo, width, heads in _windows(rep, p):
            mix = jnp.concatenate(
                [(scores * _decay(cols, rows, r, seen)).astype(dtype)
                 for r in heads], axis=1)
            inside.append(_dot(mix, _by_head(u[:, lo:lo + width], p), _NN))
        last = s_w[q - 1:q, :]
        y = jnp.concatenate(inside, axis=1) \
            + _dot(cm, hb, _NN) * jnp.exp(s_w) + d_ref[...] * xf
        y_ref[...] = y.astype(dtype)
        xw = (u.astype(F32) * jnp.exp(last - s_w)).astype(dtype)
        h_ref[...] = jnp.exp(last) * hin + _dot(bm, xw, _TN)

    if states:
        def pallas_ssd_fwd_states(*refs):
            body(*refs)
        kernel = pallas_ssd_fwd_states
    else:
        def pallas_ssd_fwd(*refs):
            body(*refs[:7], None, refs[7])
        kernel = pallas_ssd_fwd

    lanes, cols, rows, shared, skip, state = _block_specs(
        pl, rep, p, n, q, lambda c: c)
    out_specs = [lanes] + ([state] if states else [])
    out_shape = [jax.ShapeDtypeStruct((b, length, groups * w), dtype)] + (
        [jax.ShapeDtypeStruct((b, groups, nc, n, w), dtype)] if states else [])
    return pl.pallas_call(
        kernel,
        grid=(b, groups, nc),
        in_specs=[lanes, cols, rows, shared, shared, skip],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, w), F32)],
        compiler_params=_compiler_params(pltpu, q, w, n),
        interpret=interpret,
        name=kernel.__name__,
    )


def _block_specs(pl, rep, p, n, q, chunk_of):
    """(x / y / dy / dx over the group's lanes, the per-step columns
    and their gradient, the per-step rows, B / C / dB / dC, the skip's
    lanes, a chunk's entering state) over the grid (batch, group,
    chunk); ``chunk_of`` maps the grid's chunk index to the chunk it
    works on."""
    w = rep * p
    return (
        pl.BlockSpec((None, q, w), lambda i, g, c: (i, chunk_of(c), g)),
        pl.BlockSpec((None, None, q, 2 * rep),
                     lambda i, g, c: (i, g, chunk_of(c), 0)),
        pl.BlockSpec((None, None, 2 * rep, q),
                     lambda i, g, c: (i, g, 0, chunk_of(c))),
        pl.BlockSpec((None, q, n), lambda i, g, c: (i, chunk_of(c), g)),
        pl.BlockSpec((None, 1, w), lambda i, g, c: (g, 0, 0)),
        pl.BlockSpec((None, None, None, n, w),
                     lambda i, g, c: (i, g, chunk_of(c), 0, 0)))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _lane_sums(rep, p, dtype):
    """(2 * rep * p, 128) 0/1, two matrices one over the other: a tile
    (steps, rep * p) against the first gives head ``r``'s lane sum in
    column ``r``, against the second in column ``rep + r``."""
    w = rep * p
    row = jnp.arange(2 * w)
    col = (row % w) // p + rep * (row // w)
    return (col[:, None] == jnp.arange(_LANE)[None, :]).astype(dtype)


def _halves(z, dtype):
    """A float32 tile as two tiles of the products' dtype whose sum is
    the tile to twice that dtype's mantissa."""
    hi = z.astype(dtype)
    return hi, (z - hi.astype(F32)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _bwd_call(b, length, groups, rep, p, n, q, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, nc = rep * p, length // q

    def pallas_ssd_bwd(x_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref,
                       dy_ref, hin_ref, sums_ref, dx_ref, dcols_ref, db_ref,
                       dc_ref, dd_ref, dh_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            dh_ref[...] = jnp.zeros(dh_ref.shape, F32)
            dd_ref[...] = jnp.zeros(dd_ref.shape, F32)

        # the chunk again, as the forward built it
        cols, rows, s_w, dt_w, xf, u, bm, cm, scores, seen = _chunk(
            cols_ref, rows_ref, x_ref, b_ref, c_ref, rep, p)
        hb = hin_ref[...]
        last = s_w[q - 1:q, :]
        into, to_end, whole = (jnp.exp(s_w), jnp.exp(last - s_w),
                               jnp.exp(last))
        xw = (u.astype(F32) * to_end).astype(dtype)

        dy = dy_ref[...]
        dyf = dy.astype(F32)
        dh = dh_ref[...]
        dhb = dh.astype(dtype)

        # the entering state's read, and the chunk's own state
        dz = (dyf * into).astype(dtype)
        y_hat = _dot(cm, hb, _NN) * into
        dc = _dot(dz, hb, _NT)
        dxw = _dot(bm, dhb, _NN)
        db = _dot(xw, dhb, _NT)

        # inside the chunk, a window of heads at a time
        dscores = jnp.zeros((q, q), F32)
        inside, du = [], []
        for lo, width, heads in _windows(rep, p):
            dy_win = dy[:, lo:lo + width]
            decays = [_decay(cols, rows, r, seen) for r in heads]
            mixes = [(scores * decay).astype(dtype) for decay in decays]
            u_heads = _by_head(u[:, lo:lo + width], p)
            inside.append(_dot(jnp.concatenate(mixes, axis=1), u_heads, _NN))
            for i, decay in enumerate(decays):
                dscores += _dot(dy_win, u_heads[i * q:(i + 1) * q], _NT) \
                    * decay
            du.append(_dot(jnp.concatenate(mixes, axis=0),
                           _by_head(dy_win, p), _TN))
        y_hat = y_hat + jnp.concatenate(inside, axis=1)
        du_inside = jnp.concatenate(du, axis=1)
        du = du_inside + to_end * dxw
        dsb = dscores.astype(dtype)
        dc_ref[...] = (dc + _dot(dsb, bm, _NN)).astype(dtype)
        db_ref[...] = (db + _dot(dsb, cm, _TN)).astype(dtype)

        # the log-decays: ds = dy . y^ - (dt x) . du, and at the last
        # step what the leaving state carries. Each term of the first
        # is one of the second at another step (the same rounded mix,
        # dt x and weighted x in both), so their roundings cancel in
        # the sums the cumulative sum takes, as the composition's do
        dux = du * xf
        spent = dxw * xw.astype(F32)
        ds = dyf * y_hat - u.astype(F32) * du_inside - spent
        leaving = jnp.sum(spent, axis=0, keepdims=True) \
            + whole * jnp.sum(dh * hb.astype(F32), axis=0, keepdims=True)
        tail = lax.broadcasted_iota(jnp.int32, (_ROWS, w), 0) == _ROWS - 1
        ds = jnp.concatenate(
            [ds[:q - _ROWS], ds[q - _ROWS:] + jnp.where(tail, leaving, 0.0)],
            axis=0)
        sums = sum(_dot(half, sums_ref[lo:lo + w, :], _NN)
                   for lo, z in ((0, ds), (w, dux))
                   for half in _halves(z, dtype))
        dcols_ref[...] = sums[:, :2 * rep]
        dd_ref[...] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        dx_ref[...] = (d_ref[...] * dyf + dt_w * du).astype(dtype)
        dh_ref[...] = whole * dh + _dot(cm, dz, _TN)

    lanes, cols, rows, shared, skip, state = _block_specs(
        pl, rep, p, n, q, lambda c: nc - 1 - c)
    return pl.pallas_call(
        pallas_ssd_bwd,
        grid=(b, groups, nc),
        in_specs=[lanes, cols, rows, shared, shared, skip, lanes, state,
                  pl.BlockSpec((2 * w, _LANE), lambda i, g, c: (0, 0))],
        out_specs=[lanes, cols, shared, shared,
                   pl.BlockSpec((None, None, 1, w),
                                lambda i, g, c: (i, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, length, groups * w), dtype),
                   jax.ShapeDtypeStruct((b, groups, length, 2 * rep), F32),
                   jax.ShapeDtypeStruct((b, length, groups * n), dtype),
                   jax.ShapeDtypeStruct((b, length, groups * n), dtype),
                   jax.ShapeDtypeStruct((b, groups, 1, w), F32)],
        scratch_shapes=[pltpu.VMEM((n, w), F32)],
        compiler_params=_compiler_params(pltpu, q, w, n),
        interpret=interpret,
        name="pallas_ssd_bwd",
    )


# ---------------------------------------------------------------------------
# the differentiable call
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, cols, rows, bm, cm, d, dims, q):
    """x (batch, length, heads * head_dim); cols (batch, groups,
    length, 2 * heads a group) float32, a step's cumulative log-decay
    ``s`` of each head of the group, then its ``dt``; rows (batch,
    groups, 2 * heads a group, length), the same numbers with the steps
    on lanes (the gradient goes to ``cols`` alone); bm / cm (batch,
    length, groups * state); d (groups, 1, heads a group * head_dim)
    float32; ``dims`` = (groups, heads a group, head_dim, state);
    length a whole number of chunks ``q``."""
    call = _fwd_call(x.shape[0], x.shape[1], *dims, q, x.dtype, False,
                     pallas_common.interpret_mode())
    return call(x, cols, rows, bm, cm, d)[0]


def _scan_fwd(x, cols, rows, bm, cm, d, dims, q):
    call = _fwd_call(x.shape[0], x.shape[1], *dims, q, x.dtype, True,
                     pallas_common.interpret_mode())
    y, entering = call(x, cols, rows, bm, cm, d)
    return y, (x, cols, rows, bm, cm, d, entering)


def _scan_bwd(dims, q, res, dy):
    x, cols, rows, bm, cm, d, entering = res
    _, rep, p, _ = dims
    call = _bwd_call(x.shape[0], x.shape[1], *dims, q, x.dtype,
                     pallas_common.interpret_mode())
    with jax.named_scope(SCOPE):
        dx, dcols, db, dc, dd = call(
            x, cols, rows, bm, cm, d, dy.astype(x.dtype), entering,
            _lane_sums(rep, p, x.dtype))
        dd = jnp.sum(dd, axis=0)
    return dx, dcols, jnp.zeros_like(rows), db, dc, dd


# under ``jax.checkpoint`` the forward pass runs the call that writes
# no states: a Pallas output is not dead-code eliminated
_scan.defvjp(_scan_fwd, _scan_bwd, optimize_remat=True)


def ssd_scan(x, dt, a_neg, bm, cm, d_skip, chunk, reset=None):
    """``decoder_ops._ssd``'s result by the kernels: x (batch, length,
    heads, head_dim), dt (batch, length, heads), a_neg (heads,), bm /
    cm (batch, length, groups, state), d_skip (heads,); check
    :func:`ssd_available` first. A length that is no whole number of
    chunks is padded here with dt = 0 (the state is carried unchanged)
    and y is cut. The log-decays ``dt a`` and their cumulative sum
    inside each chunk are XLA's, in float32 (2 MB a layer at the
    Nemotron cell's call), and so is the reverse sum of the backward,
    by autodiff. ``reset`` (batch, length) bool marks the steps before
    which the state counts for nothing (:func:`log_decays`): the
    kernels are handed those sums and know nothing of documents."""
    b, length, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    rep, q = heads // groups, int(chunk)
    pad = (-length) % q
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (v.ndim - 2)) for v in (x, dt, bm, cm))
        if reset is not None:
            reset = jnp.pad(reset, ((0, 0), (0, pad)))
    full = length + pad
    dt = dt.astype(F32)
    s = jnp.cumsum(log_decays(dt, a_neg, reset)
                   .reshape(b, full // q, q, heads), axis=2)
    steps = jnp.concatenate([s.reshape(b, full, groups, rep),
                             dt.reshape(b, full, groups, rep)], axis=-1)
    y = _scan(
        x.reshape(b, full, heads * p),
        steps.transpose(0, 2, 1, 3), steps.transpose(0, 2, 3, 1),
        bm.reshape(b, full, groups * n), cm.reshape(b, full, groups * n),
        jnp.repeat(d_skip.astype(F32), p).reshape(groups, 1, rep * p),
        (groups, rep, p, n), q)
    return y.reshape(b, full, heads, p)[:, :length]
