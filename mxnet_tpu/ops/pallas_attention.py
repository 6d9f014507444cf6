"""Fused multi-head self-attention (flash-style) Pallas kernel for the
BERT path (ref: src/operator/contrib/transformer.cc ::
interleaved_matmul_selfatt_qk/valatt — the reference's hand-written
attention kernels exist for exactly this reason: stock composition
leaves perf on the table).

Round-7 rework (ISSUE 14; the round-6 builder's forecast residual
"transpose_jvp 1.76 ms"):
the kernel now consumes the reference-packed ``(L, N, heads*3*hd)``
QKV layout DIRECTLY. The r6 version reshaped to ``(N*heads, L, 3*hd)``
with an XLA transpose outside the kernel — cheap per call, but its jvp
shows up as the 1.76 ms/step ``transpose_jvp`` category on the BERT
breakdown. Here the head (de)interleave is index arithmetic in the
BlockSpecs plus an in-VMEM relayout inside the kernel: each grid step
``(n, j)`` loads the contiguous last-axis slice of batch element ``n``
covering head block ``j`` (``block_heads`` heads × ``3*hd`` lanes),
splits q/k/v off the minor axis, and writes the context back in the
packed output layout. No HLO transpose exists between the QKV
projection and the kernel call in either direction (the packed tests
assert this on the jaxpr), so the ``transpose_jvp`` category vanishes.

Ragged shapes stay on the kernel instead of silently falling back:

* sequence lengths that are not a sublane multiple are zero-padded to
  ``L_pad`` outside the kernel (a pad, not a transpose) and the padded
  KEY positions are masked to −∞ before the softmax, so probabilities
  on real positions are exactly those of the unpadded problem; padded
  query rows are sliced off after the call. (r6 rejected any
  ``L % 8`` — the L=127 regression.)
* head counts that the head-block size does not divide are zero-padded
  to a whole number of head blocks; a padded head attends uniformly to
  zero values, contributes exactly zero, and is sliced off.

Scores → softmax → dropout → context never materialize the ``[L, L]``
probabilities in HBM; the backward recomputes them flash-style from
the packed QKV block and the same per-block dropout seeds (TPU
hardware PRNG via ``pltpu.prng_*``; interpreter runs substitute a
deterministic integer-hash stream so the seed-recompute contract is
testable on the CPU mesh). ``block_heads`` is autotuned
(``MXNET_AUTOTUNE``, mxnet_tpu/autotune.py) with the hand-picked
default as the incumbent.

A plan for every length up to ``_MAX_L`` (ISSUE 39): a grid step holds
a head block's whole ``(L_pad, L_pad)`` scores, so its working set
grows with the square of the length, and the default scoped VMEM limit
(16 MiB) stopped serving 12 x 64 at 336 positions. A call reckoned
over that limit's budget now states its own ``vmem_limit_bytes``
(``_compiler_params``; the v5e has 128 MiB), and a block is planned
inside ``_VMEM_MAX``: at 12 x 64 six heads a step at 512 positions,
two at 1,024. The kernels' bodies are the ones they were.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["flash_selfatt", "flash_selfatt_available", "selfatt_plan"]

_MAX_L = 1024   # longest sequence a plan is made for
_BB = 16        # max heads per grid step (the r6 batch-head block size)
_SUBLANE = 16   # seq padding unit (bf16 sublane tile)
_LANE = 128     # minor-axis tile

# What the default scoped VMEM limit (16 MiB) leaves a kernel: a call
# reckoned inside it asks nothing of the compiler, one over it states
# its own limit (``_compiler_params``). No block is planned over
# ``_VMEM_MAX`` of the v5e's 128 MiB: two heads x 64 at ``_MAX_L``
# reckon 82 MiB.
_VMEM_BUDGET = 10 * 1024 * 1024
_VMEM_MAX = 96 * 1024 * 1024


def _interpret():
    from .pallas_common import interpret_mode
    return interpret_mode()


def _ceil_to(x, m):
    return -(-x // m) * m


def _block_bytes(bbh, L_pad, hd, esize, n_score_temps):
    """Estimated VMEM working set of one grid step: the qkv/out blocks
    plus n_score_temps live (bbh, L_pad, L_pad) f32 intermediates."""
    return bbh * (L_pad * 4 * hd * esize            # qkv + out blocks
                  + n_score_temps * L_pad * L_pad * 4)


def _lane_unit(hd):
    """Smallest head count whose ``hd`` lanes fill whole 128-lane
    tiles: a block's minor dimension (``bbh*hd`` out, ``bbh*3*hd`` in)
    must be a multiple of 128 for the Mosaic lowering."""
    return _LANE // math.gcd(_LANE, hd)


def _fits(bbh, L_pad, hd, esize):
    return _block_bytes(bbh, L_pad, hd, esize, 5) * 2 <= _VMEM_MAX


def _compiler_params(pltpu, nbytes):
    """``pallas_call`` keywords of a call reckoned at ``nbytes``: none
    inside the budget of the default scoped limit (the program is the
    one it was), else the reckoning as ``vmem_limit_bytes`` with 16 MiB
    for what it does not see."""
    if nbytes <= _VMEM_BUDGET:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=nbytes + (16 << 20))}


def _default_block_heads(heads, L_pad, hd, esize):
    """Heads per grid step: a multiple of the lane unit whose working
    set fits ``_VMEM_MAX`` (backward temp count = 5, the worse case),
    least head padding first, then fewest grid steps (on the chip at
    12 x 64, 512 positions: six heads a step 4.05 ms a layer forward +
    backward, two heads 4.13-4.20; PERF.md, PR 39); None when no such
    block exists."""
    unit = _lane_unit(hd)
    fits = [b for b in range(unit, _ceil_to(min(heads, _BB), unit) + 1,
                             unit)
            if _fits(b, L_pad, hd, esize)]
    if not fits:
        return None
    return min(fits, key=lambda b: (_ceil_to(heads, b), -b))


def selfatt_plan(L, heads, batch, dropout=0.0, dtype=None,
                 block_heads=None, head_dim=64):
    """Kernel launch geometry for one packed self-attention call — or
    None when the Pallas path cannot serve it (the caller then uses the
    unfused interleaved-matmul composition).

    Returns {"bbh", "L_pad", "heads_pad", "n_hblk", "n_blocks"}:
    ``bbh`` heads per grid step (autotuned unless ``block_heads``
    overrides), ``heads_pad = n_hblk * bbh`` (zero-padded final block
    when bbh does not divide heads), ``n_blocks = batch * n_hblk`` the
    per-block dropout-seed count. ``head_dim`` sizes the VMEM check and
    the lane alignment of ``bbh`` (BERT-family 64 when not given).

    In a program partitioned over a mesh the kernel runs once a shard
    (pallas_common.per_shard): the block is planned (and tuned) for a
    shard's share of the batch, and ``n_blocks`` still counts the whole
    batch's seeds, which split with it.
    """
    from ..config import get as _cfg
    from .pallas_common import split_of
    if not _cfg("MXNET_FLASH_ATTENTION"):
        return None
    if L < 1 or L > _MAX_L or heads < 1 or batch < 1 or head_dim < 1:
        return None
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        # the kernel computes in bf16 on the MXU; routing f32 inputs
        # through it would silently lose precision vs the unfused
        # composition (advisor r3) — f32 falls back
        return None
    split = split_of("pallas_selfatt_packed", (L, batch), dim=1)
    if not split:
        return None
    esize = 2 if dtype is None else jnp.dtype(dtype).itemsize
    L_pad = _ceil_to(L, _SUBLANE)
    plan = _resolve_plan(int(L), int(L_pad), int(heads),
                         int(batch) // split.shards, esize, block_heads,
                         int(head_dim))
    if plan is not None:
        plan["n_blocks"] *= split.shards
    return plan


def _resolve_plan(L, L_pad, heads, batch, esize, block_heads, hd):
    default = _default_block_heads(heads, L_pad, hd, esize)
    if default is None:
        return None
    if block_heads is not None:
        # explicit override (tests): taken as given — an unaligned one
        # is the interpreter's business and a compile error on the chip
        bbh = int(block_heads)
        if bbh < 1:
            return None
    else:
        bbh = _tuned_block_heads(L, L_pad, heads, batch, esize,
                                 default, hd)
    if not _fits(bbh, L_pad, hd, esize):
        bbh = default
    n_hblk = -(-heads // bbh)
    return {"bbh": bbh, "L_pad": L_pad, "heads_pad": n_hblk * bbh,
            "n_hblk": n_hblk, "n_blocks": batch * n_hblk}


def _tuned_block_heads(L, L_pad, heads, batch, esize, default, hd):
    """Consult the autotune table for the head-block size (off mode —
    the default — returns ``default`` untouched)."""
    from .. import autotune

    unit = _lane_unit(hd)

    def _candidates():
        cands = []
        # descending: every unpadded candidate has identical analytic
        # roofline features (heads_pad == heads), and _score_cost
        # breaks ties on candidate ORDER — larger head blocks mean
        # fewer grid steps, so they must be the preferred tie-winners
        for bbh in range(_ceil_to(min(heads, _BB), unit), 0, -unit):
            if not _fits(bbh, L_pad, hd, esize):
                continue
            n_hblk = -(-heads // bbh)
            # analytic roofline features: 4 batched matmuls of
            # (L, hd) x (hd, L) per (batch, head) pair fwd+bwd
            flops = 4.0 * batch * n_hblk * bbh * L_pad * L_pad * hd
            hbm = batch * n_hblk * bbh * L * 4 * hd * esize
            cands.append(autotune.Candidate(
                {"block_heads": bbh}, flops=flops, hbm_bytes=hbm,
                # gated by ``_fits`` above, not by the tuner's budget
                # (the default limit's): the call states its own limit
                vmem_bytes=0.0,
                build=_probe_builder(L, heads, batch, hd, bbh),
                opaque=True))
        return cands

    def _valid(params):
        bbh = params.get("block_heads")
        return (isinstance(bbh, int) and bbh >= 1 and bbh % unit == 0
                and _fits(bbh, L_pad, hd, esize))

    out = autotune.lookup(
        "pallas_selfatt_packed",
        {"L": L, "heads": heads, "batch": batch, "esize": esize,
         "hd": hd},
        {"block_heads": default}, candidates=_candidates,
        validate=_valid)
    return int(out.get("block_heads", default))


def _probe_builder(L, heads, batch, hd, bbh):
    def build():
        qkv = jnp.zeros((L, batch, heads * 3 * hd), jnp.bfloat16)
        n_blocks = batch * (-(-heads // bbh))
        seeds = jnp.zeros((n_blocks,), jnp.int32)

        def fn(qkv, seeds):
            return flash_selfatt(qkv, seeds, heads=heads, dropout=0.0,
                                 block_heads=bbh)
        return fn, (qkv, seeds)
    return build


def flash_selfatt_available(L, heads, batch, dropout=0.0, dtype=None):
    """True when the packed Pallas kernel can serve this call."""
    return selfatt_plan(L, heads, batch, dropout, dtype) is not None


# ---------------------------------------------------------------------------
# in-kernel PRNG (hardware stream on TPU; deterministic hash fallback in
# interpreter mode so fwd/bwd seed-recompute parity is testable on CPU)
# ---------------------------------------------------------------------------
def _keep_mask(pltpu, seed, shape, thresh, interpret):
    if not interpret:
        pltpu.prng_seed(seed)
        bits = pltpu.prng_random_bits(shape).astype(jnp.uint32)
    else:
        # splitmix/murmur3-finalizer hash of (seed, linear index) —
        # NOT the TPU PRNG stream, but the same bits every time the
        # same seed is presented, which is the contract the backward's
        # mask recompute relies on
        d0, d1, d2 = shape
        idx = (lax.broadcasted_iota(jnp.uint32, shape, 0)
               * jnp.uint32(d1 * d2)
               + lax.broadcasted_iota(jnp.uint32, shape, 1)
               * jnp.uint32(d2)
               + lax.broadcasted_iota(jnp.uint32, shape, 2))
        z = idx + seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        z = (z ^ (z >> 16)) * jnp.uint32(0x85EBCA6B)
        z = (z ^ (z >> 13)) * jnp.uint32(0xC2B2AE35)
        bits = z ^ (z >> 16)
    return bits >= jnp.uint32(thresh)


def _bdot(a, b, contract):
    """Head-batched in-kernel matmul, f32 accumulation. bf16 operands
    pin DEFAULT precision: an ambient jax_default_matmul_precision of
    "highest" would ask Mosaic for an fp32 contraction of bf16 vectors,
    which it refuses ("Bad lhs type")."""
    prec = lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return lax.dot_general(a, b, (contract, ((0,), (0,))), precision=prec,
                           preferred_element_type=jnp.float32)


def _attn_fwd_math(pltpu, q, k, seed, L, L_pad, p_drop, keep, thresh,
                   interpret):
    """Shared fwd math on (BBH, L_pad, d) operands: returns (p_raw,
    p_dropped, keep_mask). Padded key columns (>= L) are masked to −∞
    before the softmax so real positions see the unpadded problem."""
    s = _bdot(q, k, ((2,), (2,)))
    if L_pad != L:
        col = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col < L, s, -1e30)
    m = jnp.max(s, axis=2, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=2, keepdims=True)
    if p_drop > 0.0:
        keep_mask = _keep_mask(pltpu, seed, s.shape, thresh, interpret)
        return p, jnp.where(keep_mask, p / keep, 0.0), keep_mask
    return p, p, None


def _split_heads(ref, bbh, d, parts=1, part=0):
    """(L_pad, bbh*parts*d) packed block ref -> (bbh, L_pad, d): every
    head's ``part``-th d-wide field as a static lane slice, stacked on
    a new major axis — the (de)interleave that used to be an HLO
    transpose outside the kernel. (A minor-axis reshape to
    (L_pad, bbh, parts*d) is refused by Mosaic: "unsupported shape
    cast".)"""
    return jnp.stack([ref[:, (parts * h + part) * d:
                          (parts * h + part + 1) * d]
                      for h in range(bbh)], axis=0)


def _split_qkv_block(qkv_ref, bbh, d):
    """bf16 (bbh, L_pad, d) q, k, v of an interleaved [q|k|v] block."""
    return tuple(_split_heads(qkv_ref, bbh, d, 3, i) for i in range(3))


def _merge_heads(x):
    """(bbh, L_pad, w) -> (L_pad, bbh*w): heads back onto the lanes."""
    return jnp.concatenate([x[h] for h in range(x.shape[0])], axis=1)


@functools.lru_cache(maxsize=None)
def _fwd_call(L, L_pad, N, heads_pad, bbh, d, p_drop, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / float(d) ** 0.5
    keep = 1.0 - p_drop
    thresh = min(int(p_drop * 2 ** 32), 2 ** 32 - 1)
    n_hblk = heads_pad // bbh

    def pallas_selfatt_packed_fwd(seed_ref, qkv_ref, o_ref):
        n = pl.program_id(0)
        j = pl.program_id(1)
        q, k, v = _split_qkv_block(qkv_ref, bbh, d)
        q = q.astype(jnp.float32) * scale
        k = k.astype(jnp.float32)
        _, pd, _ = _attn_fwd_math(pltpu, q, k,
                                  seed_ref[n * n_hblk + j],
                                  L, L_pad, p_drop, keep, thresh,
                                  interpret)
        o = _bdot(pd.astype(jnp.bfloat16), v, ((2,), (1,)))
        # back to the packed (L_pad, bbh*d) output layout
        o_ref[:] = _merge_heads(o).astype(o_ref.dtype)

    return pl.pallas_call(
        pallas_selfatt_packed_fwd,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, n_hblk),
            in_specs=[
                pl.BlockSpec((L_pad, bbh * 3 * d),
                             lambda n, j, seeds: (0, n * n_hblk + j)),
            ],
            out_specs=pl.BlockSpec((L_pad, bbh * d),
                                   lambda n, j, seeds: (0, n * n_hblk + j)),
        ),
        out_shape=jax.ShapeDtypeStruct((L_pad, N * heads_pad * d),
                                       jnp.bfloat16),
        interpret=interpret,
        name="pallas_selfatt_packed_fwd",
        **_compiler_params(pltpu, _block_bytes(bbh, L_pad, d, 2, 2) * 2),
    )


@functools.lru_cache(maxsize=None)
def _bwd_call(L, L_pad, N, heads_pad, bbh, d, p_drop, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / float(d) ** 0.5
    keep = 1.0 - p_drop
    thresh = min(int(p_drop * 2 ** 32), 2 ** 32 - 1)
    n_hblk = heads_pad // bbh

    def pallas_selfatt_packed_bwd(seed_ref, qkv_ref, do_ref, dqkv_ref):
        n = pl.program_id(0)
        j = pl.program_id(1)
        q, k, v = _split_qkv_block(qkv_ref, bbh, d)
        q = q.astype(jnp.float32) * scale
        k = k.astype(jnp.float32)
        do = _split_heads(do_ref, bbh, d).astype(jnp.float32)
        p, pd, keep_mask = _attn_fwd_math(
            pltpu, q, k, seed_ref[n * n_hblk + j], L, L_pad, p_drop,
            keep, thresh, interpret)
        # dV (bbh,L,d) = Pdᵀ·dO : contract over query positions
        dv = _bdot(pd, do, ((1,), (1,)))
        # dPd (bbh,L,L) = dO·Vᵀ
        dpd = _bdot(do, v.astype(jnp.float32), ((2,), (2,)))
        if p_drop > 0.0:
            dp = jnp.where(keep_mask, dpd / keep, 0.0)
        else:
            dp = dpd
        ds = p * (dp - jnp.sum(dp * p, axis=2, keepdims=True))
        dsb = ds.astype(jnp.bfloat16)
        # dq (bbh,L,d) = dS·K ; dk (bbh,L,d) = dSᵀ·(Q·scale)
        dq = _bdot(dsb, k.astype(jnp.bfloat16), ((2,), (1,))) * scale
        dk = _bdot(dsb, q.astype(jnp.bfloat16), ((1,), (1,)))
        # re-pack [dq|dk|dv] into the interleaved minor axis
        out = jnp.concatenate([dq, dk, dv], axis=2)   # (bbh, L, 3d)
        dqkv_ref[:] = _merge_heads(out).astype(dqkv_ref.dtype)

    return pl.pallas_call(
        pallas_selfatt_packed_bwd,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, n_hblk),
            in_specs=[
                pl.BlockSpec((L_pad, bbh * 3 * d),
                             lambda n, j, seeds: (0, n * n_hblk + j)),
                pl.BlockSpec((L_pad, bbh * d),
                             lambda n, j, seeds: (0, n * n_hblk + j)),
            ],
            out_specs=pl.BlockSpec((L_pad, bbh * 3 * d),
                                   lambda n, j, seeds: (0, n * n_hblk + j)),
        ),
        out_shape=jax.ShapeDtypeStruct((L_pad, N * heads_pad * 3 * d),
                                       jnp.bfloat16),
        interpret=interpret,
        name="pallas_selfatt_packed_bwd",
        **_compiler_params(pltpu, _block_bytes(bbh, L_pad, d, 2, 5) * 2),
    )


def _pad_packed(qkv, L, L_pad, heads, heads_pad, d):
    """Zero-pad the packed array along seq (rows) and heads (whole
    trailing head slots) — pads, never transposes."""
    if heads_pad != heads:
        qkv = jnp.pad(qkv, ((0, 0), (0, 0),
                            (0, (heads_pad - heads) * 3 * d)))
    if L_pad != L:
        qkv = jnp.pad(qkv, ((0, L_pad - L), (0, 0), (0, 0)))
    return qkv


@functools.lru_cache(maxsize=None)
def _make_op(heads, p_drop, bbh):
    @jax.custom_vjp
    def f(qkv, seeds):
        L, N, thd = qkv.shape
        d = thd // (3 * heads)
        L_pad = _ceil_to(L, _SUBLANE)
        n_hblk = -(-heads // bbh)
        heads_pad = n_hblk * bbh
        x = _pad_packed(qkv.astype(jnp.bfloat16), L, L_pad, heads,
                        heads_pad, d)
        call = _fwd_call(L, L_pad, N, heads_pad, bbh, d, p_drop,
                         _interpret())
        o = call(seeds, x.reshape(L_pad, N * heads_pad * 3 * d))
        o = o.reshape(L_pad, N, heads_pad * d)
        return o[:L, :, :heads * d].astype(qkv.dtype)

    def fwd(qkv, seeds):
        return f(qkv, seeds), (qkv, seeds)

    def bwd(res, dout):
        qkv, seeds = res
        L, N, thd = qkv.shape
        d = thd // (3 * heads)
        L_pad = _ceil_to(L, _SUBLANE)
        n_hblk = -(-heads // bbh)
        heads_pad = n_hblk * bbh
        x = _pad_packed(qkv.astype(jnp.bfloat16), L, L_pad, heads,
                        heads_pad, d)
        do = dout.astype(jnp.bfloat16)
        if heads_pad != heads:
            do = jnp.pad(do, ((0, 0), (0, 0),
                              (0, (heads_pad - heads) * d)))
        if L_pad != L:
            do = jnp.pad(do, ((0, L_pad - L), (0, 0), (0, 0)))
        call = _bwd_call(L, L_pad, N, heads_pad, bbh, d, p_drop,
                         _interpret())
        dqkv = call(seeds, x.reshape(L_pad, N * heads_pad * 3 * d),
                    do.reshape(L_pad, N * heads_pad * d))
        dqkv = dqkv.reshape(L_pad, N, heads_pad * 3 * d)
        return (dqkv[:L, :, :heads * 3 * d].astype(qkv.dtype),
                jnp.zeros(seeds.shape, jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def flash_selfatt(qkv, seeds, *, heads, dropout=0.0, block_heads=None):
    """Fused self-attention on reference-packed QKV — consumed and
    produced in the packed layout, no outside transposes.

    qkv: (L, N, heads*3*hd), per-head interleaved [q|k|v]; seeds:
    int32 (N * n_hblk,) per-grid-block dropout seeds where n_hblk =
    ceil(heads/block_heads) — size it with :func:`selfatt_plan`
    (ignored when dropout=0). Returns context (L, N, heads*hd).
    Scores/softmax in f32, matmul operands bf16 — matching the unfused
    XLA path. ``block_heads`` overrides the autotuned head-block size
    (tests). In a program partitioned over a mesh ``N`` is split and
    the seeds, ``N``-major, with it."""
    from .pallas_common import per_shard, split_of
    heads = int(heads)
    L, N, thd = qkv.shape
    if block_heads is None:
        d = thd // (3 * heads)
        plan = selfatt_plan(L, heads, N, float(dropout), head_dim=d)
        if plan is None:
            raise ValueError(
                "flash_selfatt: shape (L=%d, heads=%d, batch=%d) is "
                "not servable (check selfatt_plan first)"
                % (L, heads, N))
        block_heads = plan["bbh"]
    f = _make_op(heads, float(dropout), int(block_heads))
    split = split_of("pallas_selfatt_packed", qkv.shape, dim=1)
    return per_shard("pallas_selfatt_packed", split, f, qkv, seeds)
