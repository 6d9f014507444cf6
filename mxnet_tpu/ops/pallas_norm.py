"""Pallas LayerNorm — fused forward and single-sweep backward (ref:
src/operator/nn/layer_norm.cc :: LayerNormCompute / LayerNormGradCompute,
whose hand-written CUDA kernels exist for exactly this reason).

Why this exists (round-6 perf work; round-5 builder figures): the
BERT-base step spends 5.27 ms/step in `convert_reduce_fusion` —
dominated by XLA's LayerNorm backward, which splits into a reduction island (dgamma/dbeta +
row moments) and an elementwise island, re-reading the activations and
the upstream gradient from HBM for each. LN is pure VPU/bandwidth work,
so the only fix is fewer HBM sweeps:

* forward: ONE kernel computes mean/var and normalizes in VMEM — x is
  read once, out written once (XLA's fwd is already close; the win is
  keeping the same code path and rounding for the backward).
* backward: ONE kernel re-derives the row statistics from the x block it
  already streams for dx, computes dgamma/dbeta partial sums and the
  row moments of dy·gamma in the same pass, and writes dx — x and dy
  are each read exactly once, dx written once. The XLA schedule reads
  each of them at least twice.

Numerics match ops/nn.py :: _ln_fused bit-for-bit-in-formula: f32
statistics, two-pass variance E[(x-mean)^2] (the uncentered form
catastrophically cancels for large-mean activations), f32 dgamma/dbeta.

Availability rules (clean XLA fallback otherwise, see
pallas_ln_available): normalized axis must be the last, the flattened
row count must split into whole aligned row-blocks that fit VMEM. On
CPU the kernels run in Pallas interpret mode (tier-1 exact-grad tests;
tests/test_pallas_norm.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pallas_layer_norm", "pallas_ln_available"]


def _interpret():
    from .pallas_common import interpret_mode
    return interpret_mode()


def _pick_rows(M, C, esize, n_streams):
    """Largest row-block keeping double-buffered streams under ~10 MB of
    the ~16 MB VMEM. n_streams counts [bm, C] arrays alive in the kernel
    (inputs + outputs + f32 temporaries). bf16 blocks keep the 16-row
    sublane alignment; interpret mode has no such constraint but uses
    the same choice so CPU tests exercise the TPU tiling."""
    per_row = C * (n_streams * esize + 4 * 4)   # + f32 working copies
    floor = 8 if esize >= 4 else 16
    for bm in (1024, 512, 256, 128, 64, 32, 16, 8):
        if bm < floor or M % bm:
            continue
        if bm * per_row * 2 + 8 * C * 4 <= 10 * 1024 * 1024:
            return bm
    return None


def pallas_ln_available(shape, dtype, axis):
    """True when the Pallas LN kernels can serve this call (the caller
    falls back to the XLA _ln_fused path otherwise)."""
    from ..config import get as _cfg
    from .pallas_common import kernels_allowed
    # no per-shard rule (pallas_common.per_shard), on purpose: in the
    # partitioned BERT-base step these kernels, a shard each, read
    # 4,507 samples/s where XLA's fusions of the composition (with the
    # residual add before it) read 4,620 (PERF.md section 6, PR 45), so
    # on a mesh they stand down. On one chip they earn their place:
    # off reads 1,244 -> 1,201 samples/s at 128 positions and 268.6 ->
    # 268.2 at 512 (PERF.md section 6, PR 48)
    if not _cfg("MXNET_PALLAS_LAYERNORM") or not kernels_allowed():
        return False
    if len(shape) < 2 or axis != len(shape) - 1:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float16)):
        return False
    C = shape[-1]
    M = 1
    for s in shape[:-1]:
        M *= s
    if M < 8 or C < 1:
        return False
    esize = jnp.dtype(dtype).itemsize
    return _pick_rows(M, C, esize, 3) is not None


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _fwd_call(M, C, bm, eps, dtype_name, interpret):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)

    def pallas_layer_norm_fwd(x_ref, gb_ref, o_ref):
        xf = x_ref[:].astype(jnp.float32)
        mean = jnp.mean(xf, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=1, keepdims=True)
        inv = lax.rsqrt(var + eps)
        out = (xf - mean) * inv * gb_ref[0, :] + gb_ref[1, :]
        o_ref[:] = out.astype(o_ref.dtype)

    return pl.pallas_call(
        pallas_layer_norm_fwd,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((8, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), dtype),
        interpret=interpret,
        name="pallas_layer_norm_fwd",
    )


@functools.lru_cache(maxsize=None)
def _bwd_call(M, C, bm, eps, dtype_name, interpret):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)

    def pallas_layer_norm_bwd(dy_ref, x_ref, gb_ref, dx_ref, sums_ref):
        i = pl.program_id(0)
        xf = x_ref[:].astype(jnp.float32)
        dyf = dy_ref[:].astype(jnp.float32)
        # re-derive the row stats from the x block already streaming for
        # dx — cheaper than a second HBM array of saved (mean, inv), and
        # identical values to the forward's (same block, same formula)
        mean = jnp.mean(xf, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=1, keepdims=True)
        inv = lax.rsqrt(var + eps)
        xhat = (xf - mean) * inv
        dyg = dyf * gb_ref[0, :]
        m1 = jnp.mean(dyg, axis=1, keepdims=True)
        m2 = jnp.mean(dyg * xhat, axis=1, keepdims=True)
        dx_ref[:] = (inv * (dyg - m1 - xhat * m2)).astype(dx_ref.dtype)
        # dgamma/dbeta partial sums over this row block, accumulated
        # across sequential grid steps
        dg = jnp.sum(dyf * xhat, axis=0)
        db = jnp.sum(dyf, axis=0)
        row = jnp.concatenate(
            [dg[None], db[None], jnp.zeros((6, C), jnp.float32)], axis=0)

        @pl.when(i == 0)
        def _():
            sums_ref[:] = row

        @pl.when(i > 0)
        def _():
            sums_ref[:] = sums_ref[:] + row

    return pl.pallas_call(
        pallas_layer_norm_bwd,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((8, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((8, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, C), dtype),
            jax.ShapeDtypeStruct((8, C), jnp.float32),
        ],
        interpret=interpret,
        name="pallas_layer_norm_bwd",
    )


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_op(M, C, bm_fwd, bm_bwd, eps, dtype_name, interpret):
    @jax.custom_vjp
    def f(x2, g, b):
        gb = jnp.concatenate(
            [g[None].astype(jnp.float32), b[None].astype(jnp.float32),
             jnp.zeros((6, C), jnp.float32)], axis=0)
        call = _fwd_call(M, C, bm_fwd, eps, dtype_name, interpret)
        return call(x2, gb)

    def fwd(x2, g, b):
        return f(x2, g, b), (x2, g, b)

    def bwd(res, dy):
        x2, g, b = res
        gb = jnp.concatenate(
            [g[None].astype(jnp.float32),
             jnp.zeros((7, C), jnp.float32)], axis=0)
        call = _bwd_call(M, C, bm_bwd, eps, dtype_name, interpret)
        dx, sums = call(dy, x2, gb)
        return dx, sums[0].astype(g.dtype), sums[1].astype(b.dtype)

    f.defvjp(fwd, bwd)
    return f


def _tuned_rows(M, C, esize, n_streams, default, eps, dtype_name):
    """Consult the autotune table for the LN row-block size via the
    shared row-block helper (MXNET_AUTOTUNE; off mode returns the
    _pick_rows default untouched — byte-identical to the pre-autotune
    behavior)."""
    from .. import autotune

    def _ln_probe(bm):
        def build():
            x = jnp.zeros((M, C), jnp.dtype(dtype_name))
            gb = jnp.zeros((8, C), jnp.float32)

            def fn(x, gb):
                return _fwd_call(M, C, bm, eps, dtype_name,
                                 _interpret())(x, gb)
            return fn, (x, gb)
        return build

    return autotune.tuned_rows(
        "pallas_layer_norm_%d" % n_streams, M, C, esize, default,
        C * (n_streams * esize + 4 * 4), extra_bytes=8 * C * 4,
        flops=8.0 * M * C,
        hbm_bytes=float((n_streams + 1) * M * C * esize),
        probe=_ln_probe)


def pallas_layer_norm(data, gamma, beta, *, eps=1e-5, block_rows=None):
    """Fused LayerNorm over the LAST axis via the Pallas kernels.

    data: (..., C); gamma/beta: (C,). Returns data-shaped output in
    data.dtype. Caller must have checked pallas_ln_available();
    block_rows overrides the autotuned / VMEM-budget row-block choice
    (tests)."""
    C = data.shape[-1]
    M = data.size // C
    x2 = data.reshape(M, C)
    esize = jnp.dtype(data.dtype).itemsize
    interp = _interpret()
    dname = jnp.dtype(data.dtype).name
    bm_fwd = block_rows or _tuned_rows(
        M, C, esize, 2, _pick_rows(M, C, esize, 2), float(eps), dname)
    bm_bwd = block_rows or _tuned_rows(
        M, C, esize, 3, _pick_rows(M, C, esize, 3), float(eps), dname)
    if bm_fwd is None or bm_bwd is None or M % bm_fwd or M % bm_bwd:
        raise ValueError(
            "pallas_layer_norm: no whole row-block tiling for shape %r "
            "(call pallas_ln_available first)" % (data.shape,))
    f = _make_op(M, C, bm_fwd, bm_bwd, float(eps),
                 jnp.dtype(data.dtype).name, interp)
    out = f(x2, gamma, beta)
    return out.reshape(data.shape)
