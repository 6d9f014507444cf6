"""Causal grouped-query attention as one flash kernel (forward and
backward), for the hybrid decoder's attention layer
(``ops/decoder_ops.py::_causal_gqa`` is the composition it stands in
for, and stays the path of everything this kernel cannot serve).

Layout. q and the context are viewed as ``(batch, length, heads * d)``
and k / v as ``(batch, length, kv_heads * d)`` (free row-major
reshapes); a ``BlockSpec`` picks query head ``h``'s ``d`` lanes and
key-value head ``h // (heads // kv_heads)``'s, so no transpose exists
before or after a call. One grid step is one query head and one tile of
``tile`` queries; the head's whole k and v sit in VMEM (their block
index changes once a group of heads), and the step loops over the key
tiles up to its own: tiles wholly above the diagonal are never visited,
the diagonal tile is masked, so the work is the composition's at
``QUERY_BLOCK`` granularity.

Heads of 64 lanes (half a lane tile; a ``BlockSpec`` cannot pick 64
lanes out of that view) are served two a grid step, where the groups
are even: a 128-lane block of q / o / do / dq holds two query heads of
one group, a 128-lane block of k / v / dk / dv the pair of key-value
heads of which the step's group reads one half. Each head's q (and
do) is moved to that half and zero in the other (:class:`_Pair`), so
every product is over whole 128-lane tiles: a score contracts 64 real
lanes and 64 zeros, dk / dv take a zero contribution for the other
key-value head, and the valid half of the context and of dq is read
back out of the accumulators. The MXU does a 128-lane head's work for
each 64-lane head (a contraction of 64 fills half the array either
way); q, k, v and the context move as they lie, with no padded copy.
On a v5e at (4, 8192, 32, 64) over 8 key-value heads, forward +
backward: 54.9 ms, against 62.7 for heads zero-padded to a lane tile
on the way in (the same kernel steps plus the copies and twice the
traffic) and 1,265 for the composition (PERF.md section 6, PR 47).

With a ``window`` (a sliding-window layer: query ``t`` sees the keys
``t - window < s <= t``) the same two kernels visit only the key tiles
that hold a key of the step's band: the diagonal tile first (every
query sees itself there, so the running max is finite before a tile
that hides all its keys from some query), then the tiles wholly inside
the band, then the one or two tiles that the band's far edge crosses,
masked below the edge as the diagonal tile is masked above it. At
``tile`` 512 and ``window`` 1,024 that is three tiles a query tile
whatever the length. Without ``window`` (or with one no shorter than
the length) the traced program is the causal one, unchanged. k and v
stay whole in VMEM either way: the window saves products, not
residency, and the length is bounded as before.

With ``segments`` (packed documents: one integer id a token, query
``t`` sees the keys ``s <= t`` *of its own document*) both kernels take
the ids twice, as a row ``(batch, 1, length)`` of which a step reads
its query tile's (queries on lanes, as the row statistics lie) and as a
column ``(batch, length, 1)`` that sits whole in VMEM beside k and v
(keys on sublanes; a lane tile wide there: 4 MB at 8,192 tokens, which
the budget counts), and every visited tile is masked by ``id_key ==
id_query`` on top of its causal or band mask. The diagonal tile is
visited first, as under a window and for the same reason: a query's
earlier tiles may hold no key of its document. Tiles that lie wholly in
other documents are visited and masked, not skipped: a step's time does
not follow its row's boundaries (PERF.md section 7). Without
``segments`` the traced program is the one it was.

Both kernels compute every tile transposed, keys x queries
(``S^T = K_j Q^T``): a query's running max and sum, the saved
log-sum-exp and ``delta = sum(dO * O)`` are then dense ``(1, tile)``
rows that broadcast along sublanes, and the reductions over keys run
over sublanes. (With queries on rows they are ``(tile, 1)`` columns,
one lane in use of a vreg's 128: the forward took 8.6 ms on a v5e where
it now takes 6.4; PERF.md section 6, PR 31.)

Forward (``pallas_causal_gqa_fwd``): online softmax; a ``tile x tile``
score tile lives in VMEM only; saved are the context and the row
log-sum-exp, ``(batch, heads, 1, length)`` float32.

Backward (``pallas_causal_gqa_bwd``, one kernel, five products a tile):
each probability tile is rebuilt from q, k and the log-sum-exp
and consumed on the spot (dv, dp, ds, dk, dq). dq of the step's query
tile accumulates over the key tiles in a small buffer; dk / dv
accumulate in float32 over the query tiles and over the heads of a
group in two ``(length, d)`` VMEM buffers and are written once a group.
That residency is what bounds the length this kernel takes
(``_VMEM_BUDGET``).

Precision is the composition's: bf16 operands into the MXU with float32
accumulation (``Precision.DEFAULT`` pinned: Mosaic refuses a float32
contraction of bf16), scale, mask, max, exp and sums in float32,
probabilities and ``dS`` cast to bf16 for their products.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from . import pallas_common

__all__ = ["SCOPE", "WINDOW_SCOPE", "causal_gqa_available",
           "flash_causal_gqa"]

# the device-side scopes of causal attention over every earlier key and
# over a window of them, kernel or composition (``decoder_ops._attend``
# opens one around either; the backward rule here opens it again,
# being traced after the caller's has closed)
SCOPE = "mx.attn.causal"
WINDOW_SCOPE = "mx.attn.window"

_LANE = 128
F32 = jnp.float32
BF16 = jnp.bfloat16

# what one backward step may hold in VMEM (of the v5e's 128 MiB): the
# group's k, v, dk, dv blocks twice over (the pipeline's two buffers),
# the two float32 accumulators, and a few score tiles
_VMEM_BUDGET = 96 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _bwd_vmem_bytes(length, d, tile, segmented=False):
    resident = length * d * (4 * 2 * 2 + 2 * 4)     # k v dk dv x2, 2 acc
    if segmented:       # the keys' ids, a lane tile wide, x2
        resident += length * _LANE * 4 * 2
    tiles = 6 * tile * tile * 4 + 8 * tile * d * 4
    return resident + tiles


def _heads_a_step(heads, kv, d):
    """Query heads one grid step takes (0: a shape the kernel does not
    serve): one head of whole 128-lane tiles; or two heads of 64 lanes
    that share a key-value head, side by side in one lane tile, where
    the groups are even (a pair never straddles two groups) and the
    key-value heads pair up likewise."""
    if kv <= 0 or heads % kv:
        return 0
    if d % _LANE == 0:
        return 1
    if 2 * d == _LANE and (heads // kv) % 2 == 0 and kv % 2 == 0:
        return 2
    return 0


def causal_gqa_available(q, k, v, tile, segments=None):
    """Whether the kernel may serve this call, from what the code can
    observe: one device in the mesh being traced for, bf16 q / k / v,
    a head width of whole 128-lane tiles or of half a tile (64 lanes,
    with even groups over an even number of key-value heads), whole
    groups of query heads, a length of whole tiles, and a k / v (and,
    with ``segments``, the keys' ids) that fit VMEM."""
    b, length, heads, d = q.shape
    pack = _heads_a_step(heads, k.shape[2], d)
    return bool(
        pallas_common.kernels_allowed()
        and all(t.dtype == BF16 for t in (q, k, v))
        and pack and tile % _LANE == 0
        and length > 0 and length % tile == 0
        and _bwd_vmem_bytes(length, d * pack, tile, segments is not None)
        <= _VMEM_BUDGET)


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=F32)


def _seen(tile):
    """(keys, queries) bool of the diagonal tile: key position <= query
    position."""
    return lax.broadcasted_iota(jnp.int32, (tile, tile), 0) \
        <= lax.broadcasted_iota(jnp.int32, (tile, tile), 1)


def _band(tile, dist, window):
    """(keys, queries) bool of the tile ``dist`` tiles before the
    diagonal one: 0 <= query position - key position < window."""
    ahead = dist * tile \
        + lax.broadcasted_iota(jnp.int32, (tile, tile), 1) \
        - lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    return (ahead >= 0) & (ahead < window)


def _key_tiles(pl, i, key_tile, tile, length, window, diagonal_first=False):
    """Run ``key_tile(j, mask)`` over the key tiles that query tile
    ``i`` sees; ``mask`` (keys x queries, or None for a tile seen
    whole) is static. ``diagonal_first``: the diagonal tile before the
    earlier ones (under a window it always is)."""
    if window is None or window >= length:
        if diagonal_first:
            key_tile(i, _seen(tile))
        lax.fori_loop(0, i, lambda j, c: key_tile(j, None), None)
        if not diagonal_first:
            key_tile(i, _seen(tile))
        return
    key_tile(i, _seen(tile) if window >= tile else _band(tile, 0, window))
    # before the diagonal: tile i - dist holds the pairs dist * tile -
    # (tile - 1) .. dist * tile + (tile - 1) positions apart
    whole = max(window // tile - 1, 0)
    lax.fori_loop(jnp.maximum(i - whole, 0), i,
                  lambda j, c: key_tile(j, None), None)
    for dist in range(whole + 1, length // tile):
        if (dist - 1) * tile + 1 >= window:
            break
        pl.when(i >= dist)(functools.partial(
            key_tile, i - dist, _band(tile, dist, window)))


def _compiler_params(pltpu, semantics, length, d, tile, segmented):
    """The backward's working set bounds the forward's too."""
    nbytes = _bwd_vmem_bytes(length, d, tile, segmented) + (16 << 20)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=min(nbytes, 110 << 20))


def _block_specs(pl, length, d, tile, rep, pack=1):
    """(a head's tile of q / o / do / dq, its group's whole k / v / dk /
    dv, a head's tile of a row statistic) over the grid (batch, head,
    query tile). With ``pack`` 2 a step is two 64-lane heads of one
    group: ``d`` is their lane tile (128), a k / v block the pair of
    key-value heads of which the step reads one half, and ``rep`` steps
    share it as ``rep`` heads share a 128-lane key-value head."""
    rows = (None, None, 1, tile) if pack == 1 else (None, pack, 1, tile)
    return (pl.BlockSpec((None, tile, d), lambda n, h, i: (n, i, h)),
            pl.BlockSpec((None, length, d), lambda n, h, i: (n, 0, h // rep)),
            pl.BlockSpec(rows, lambda n, h, i: (n, h, 0, i)))


def _segment_specs(pl, length, tile):
    """[the query tile's ids as a row, every key's id as a column] of
    the operands (batch, 1, length) and (batch, length, 1)."""
    return [pl.BlockSpec((None, 1, tile), lambda n, h, i: (n, 0, i)),
            pl.BlockSpec((None, length, 1), lambda n, h, i: (n, 0, 0))]


def _in_document(mask, sq_ref, sk_ref, rows):
    """``mask`` (keys x queries, or None) and ``id_key == id_query``."""
    same = sk_ref[rows, :] == sq_ref[...]
    return same if mask is None else mask & same


class _Pair:
    """What a step of two 64-lane query heads needs beside the
    one-head step's code. The step's q / do block holds head ``a`` in
    lanes ``64 a ..``; its k / v block holds two key-value heads, the
    group's own in half ``s`` (by the step's place among the ``rep``
    that share the block). ``to_kv`` gives each head ``a``'s lanes moved to
    half ``s`` and zeros in the other half, so a product over all 128
    lanes with a k / v / dk / dv block is head ``a``'s against its own
    key-value head and adds nothing to the other's. ``from_kv`` reads a
    (2 x 128, queries) float32 accumulator's valid rows (half ``s`` of
    each head's 128) as the (queries, 128) block of both heads."""

    def __init__(self, pl, pltpu, rep, d):
        self.pl, self.pltpu, self.d = pl, pltpu, d
        self.s = (pl.program_id(1) // (rep // 2)) % 2
        self.half = lax.broadcasted_iota(jnp.int32, (1, _LANE), 1) // d

    def to_kv(self, x):
        """[head 0's, head 1's] of a q / do block."""
        swapped = self.pltpu.roll(x.astype(F32), self.d, 1).astype(x.dtype)
        return [jnp.where(self.half == self.s,
                          jnp.where(self.s == a, x, swapped),
                          jnp.zeros_like(x)) for a in range(2)]

    def from_kv(self, acc_ref, scale_of=None):
        d, pl = self.d, self.pl
        rows = []
        for a in range(2):
            top = pl.multiple_of(a * _LANE + self.s * d, d)
            part = acc_ref[pl.ds(top, d), :]
            rows.append(part if scale_of is None else part / scale_of(a))
        return jnp.concatenate(rows, axis=0).T


@functools.lru_cache(maxsize=None)
def _fwd_call(b, length, heads, kv, d, tile, window, segmented, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep, nq = heads // kv, length // tile
    scale = 1.0 / math.sqrt(d)
    pack = _heads_a_step(heads, kv, d)
    lanes = d * pack

    def pallas_causal_gqa_fwd(q_ref, k_ref, v_ref, *refs):
        segs, (o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs[:-5], refs[-5:]
        i = pl.program_id(2)
        q = q_ref[...]
        if pack == 1:
            qs, stat, part = [q], [...], [...]
        else:
            pair = _Pair(pl, pltpu, rep, d)
            qs = pair.to_kv(q)
            stat = [pl.ds(a, 1) for a in range(pack)]
            part = [pl.ds(a * lanes, lanes) for a in range(pack)]
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        def key_tile(j, mask):
            rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
            if segmented:
                mask = _in_document(mask, *segs, rows)
            for a in range(pack):
                st = _dot(k_ref[rows, :], qs[a], _NT) * scale   # keys x queries
                if mask is not None:
                    st = jnp.where(mask, st, -jnp.inf)
                m_prev = m_ref[stat[a]]
                m_next = jnp.maximum(m_prev,
                                     jnp.max(st, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                pt = jnp.exp(st - m_next)
                l_ref[stat[a]] = alpha * l_ref[stat[a]] + jnp.sum(
                    pt, axis=0, keepdims=True)
                acc_ref[part[a]] = alpha * acc_ref[part[a]] + _dot(
                    v_ref[rows, :], pt.astype(BF16), _TN)   # d x queries
                m_ref[stat[a]] = m_next

        _key_tiles(pl, i, key_tile, tile, length, window, segmented)
        l = l_ref[...]
        if pack == 1:
            o_ref[...] = (acc_ref[...] / l).T.astype(o_ref.dtype)
            lse_ref[...] = m_ref[...] + jnp.log(l)
        else:
            o_ref[...] = pair.from_kv(
                acc_ref, lambda a: l_ref[stat[a]]).astype(o_ref.dtype)
            lse_ref[...] = (m_ref[...] + jnp.log(l))[:, None, :]

    q_spec, kv_spec, row_spec = _block_specs(pl, length, lanes, tile, rep,
                                             pack)
    return pl.pallas_call(
        pallas_causal_gqa_fwd,
        grid=(b, heads // pack, nq),
        in_specs=[q_spec, kv_spec, kv_spec] + (
            _segment_specs(pl, length, tile) if segmented else []),
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b, length, heads * d), BF16),
                   jax.ShapeDtypeStruct((b, heads, 1, length), F32)],
        scratch_shapes=[pltpu.VMEM((pack, tile), F32),
                        pltpu.VMEM((pack, tile), F32),
                        pltpu.VMEM((pack * lanes, tile), F32)],
        compiler_params=_compiler_params(
            pltpu, ("parallel", "parallel", "arbitrary"), length, lanes,
            tile, segmented),
        interpret=interpret,
        name="pallas_causal_gqa_fwd",
    )


@functools.lru_cache(maxsize=None)
def _bwd_call(b, length, heads, kv, d, tile, window, segmented, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep, nq = heads // kv, length // tile
    scale = 1.0 / math.sqrt(d)
    pack = _heads_a_step(heads, kv, d)
    lanes = d * pack

    def pallas_causal_gqa_bwd(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, *refs):
        segs, (dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = \
            refs[:-6], refs[-6:]
        h, i = pl.program_id(1), pl.program_id(2)

        @pl.when((h % rep == 0) & (i == 0))
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, F32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, F32)

        q, do = q_ref[...], do_ref[...]
        lse, delta = lse_ref[...], delta_ref[...]           # (1, tile)
        if pack == 1:
            qs, dos, lses, deltas, part = [q], [do], [lse], [delta], [...]
        else:
            pair = _Pair(pl, pltpu, rep, d)
            qs, dos = pair.to_kv(q), pair.to_kv(do)
            lses = [lse[a] for a in range(pack)]
            deltas = [delta[a] for a in range(pack)]
            part = [pl.ds(a * lanes, lanes) for a in range(pack)]
        dq_acc[...] = jnp.zeros(dq_acc.shape, F32)

        def key_tile(j, mask):
            rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
            kj, vj = k_ref[rows, :], v_ref[rows, :]
            if segmented:
                mask = _in_document(mask, *segs, rows)
            for a in range(pack):
                st = _dot(kj, qs[a], _NT) * scale           # keys x queries
                if mask is not None:
                    st = jnp.where(mask, st, -jnp.inf)
                pt = jnp.exp(st - lses[a])
                dv_acc[rows, :] += _dot(pt.astype(BF16), dos[a], _NN)
                dpt = _dot(vj, dos[a], _NT)
                dst = (pt * (dpt - deltas[a]) * scale).astype(BF16)
                dk_acc[rows, :] += _dot(dst, qs[a], _NN)
                dq_acc[part[a]] += _dot(kj, dst, _TN)       # d x queries

        _key_tiles(pl, i, key_tile, tile, length, window, segmented)
        if pack == 1:
            dq_ref[...] = dq_acc[...].T.astype(dq_ref.dtype)
        else:
            dq_ref[...] = pair.from_kv(dq_acc).astype(dq_ref.dtype)

        @pl.when((h % rep == rep - 1) & (i == nq - 1))
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    q_spec, kv_spec, row_spec = _block_specs(pl, length, lanes, tile, rep,
                                             pack)
    kv_shape = jax.ShapeDtypeStruct((b, length, kv * d), BF16)
    return pl.pallas_call(
        pallas_causal_gqa_bwd,
        grid=(b, heads // pack, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec] + (
            _segment_specs(pl, length, tile) if segmented else []),
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, length, heads * d), BF16),
                   kv_shape, kv_shape],
        scratch_shapes=[pltpu.VMEM((pack * lanes, tile), F32),
                        pltpu.VMEM((length, lanes), F32),
                        pltpu.VMEM((length, lanes), F32)],
        compiler_params=_compiler_params(
            pltpu, ("parallel", "arbitrary", "arbitrary"), length, lanes,
            tile, segmented),
        interpret=interpret,
        name="pallas_causal_gqa_bwd",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_causal_gqa(q, k, v, tile, window=None, keep=None, segments=None):
    """Causal ``softmax(Q K^T / sqrt(d)) V``: q (batch, length, heads,
    d), k / v (batch, length, kv_heads, d), all bf16, query head h
    reading key-value head ``h // (heads // kv_heads)``; ``tile``
    queries and keys a tile (check :func:`causal_gqa_available`
    first). With ``window`` query ``t`` sees the keys ``t - window < s
    <= t`` only, and only their tiles are visited. With ``segments``
    (batch, length), integers, it sees only the keys whose id is its
    own (packed documents; every tile the causal or band rule visits is
    visited and masked). ``keep`` names the context and the rows'
    log-sum-exp (``checkpoint_name``) for a caller whose
    ``jax.checkpoint`` policy saves them, so that its backward does not
    run the forward kernel again."""
    return _forward(q, k, v, tile, window, segments)[0]


def _lanes(x):
    """(batch, length, heads, d) -> (batch, length, heads * d)."""
    return x.reshape(x.shape[:2] + (-1,))


def _static(window):
    return None if window is None else int(window)


def _ids(segments):
    """The ids as the kernels take them: [] without, else [(batch, 1,
    length), (batch, length, 1)] int32."""
    if segments is None:
        return []
    ids = segments.astype(jnp.int32)
    return [ids[:, None, :], ids[:, :, None]]


def _forward(q, k, v, tile, window, segments):
    b, length, heads, d = q.shape
    call = _fwd_call(b, length, heads, k.shape[2], d, int(tile),
                     _static(window), segments is not None,
                     pallas_common.interpret_mode())
    o, lse = call(_lanes(q), _lanes(k), _lanes(v), *_ids(segments))
    return o.reshape(q.shape), lse


def _vjp_fwd(q, k, v, tile, window, keep, segments):
    o, lse = _forward(q, k, v, tile, window, segments)
    if keep is not None:
        o, lse = checkpoint_name(o, keep), checkpoint_name(lse, keep)
    return o, (q, k, v, o, lse, segments)


def _vjp_bwd(tile, window, keep, res, do):
    q, k, v, o, lse, segments = res
    b, length, heads, d = q.shape
    call = _bwd_call(b, length, heads, k.shape[2], d, int(tile),
                     _static(window), segments is not None,
                     pallas_common.interpret_mode())
    with jax.named_scope(SCOPE if window is None else WINDOW_SCOPE):
        do = do.astype(BF16)
        delta = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1) \
            .transpose(0, 2, 1)[:, :, None, :]
        dq, dk, dv = call(_lanes(q), _lanes(k), _lanes(v), _lanes(do), lse,
                          delta, *_ids(segments))
    # (an integer operand's cotangent is a float0 zero)
    none = None if segments is None else np.zeros(segments.shape,
                                                  jax.dtypes.float0)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), none


flash_causal_gqa.defvjp(_vjp_fwd, _vjp_bwd)
