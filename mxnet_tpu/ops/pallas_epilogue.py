"""Pallas epilogue kernels — fused bias+GeLU and bias+residual-add
(round-7 kernel work, ISSUE 14; the round-6 builder's forecast residual
"fusion (misc) 5.43 ms": the unfused Dense epilogues of the BERT FFN/projection
paths).

XLA already fuses elementwise chains, but on the BERT-base step the
bias-add, exact GeLU and residual-add epilogues land in SEPARATE
fusions from each other and from their backward islands — each one an
extra HBM round-trip of the (seq*batch, hidden) activation. These two
kernels collapse each epilogue to one sweep per direction:

* **bias+GeLU** — forward: one kernel computes ``GeLU(x + b)`` (exact
  erf form, f32 internally) reading x once, writing out once.
  Backward: one kernel re-derives the pre-activation ``z = x + b``
  from the x block it already streams (cheaper than saving z — the
  pallas_norm recompute idiom), applies the analytic GeLU derivative
  ``Φ(z) + z·φ(z)``, writes dx and accumulates the db partial sums
  across sequential grid steps. x and dy are each read exactly once.
* **bias+residual** — forward: one kernel computes ``x + b + r`` in a
  single sweep (three separate XLA fusion boundaries collapse to one
  read each). The backward is trivially ``(dy, Σdy, dy)`` and stays on
  XLA — a Pallas kernel could not beat an identity plus one reduction.

Both ship behind ``MXNET_PALLAS_EPILOGUE`` (default on) with the
reference-idiomatic XLA composition as the fallback ladder (the
pallas_norm pattern): ineligible shapes/dtypes and the flag-off path
run exactly the ops the model ran before this module existed. Row
blocks are autotuned (``MXNET_AUTOTUNE``) with the VMEM-budget
heuristic as the incumbent default. Numerics: f32 internally (the XLA
fallback computes in the input dtype; parity is to fp tolerance, the
fallback is the reference — tests/test_pallas_epilogue.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["pallas_bias_gelu", "bias_gelu_available",
           "pallas_bias_residual", "bias_residual_available"]

_INV_SQRT2 = 1.0 / math.sqrt(2.0)
_INV_SQRT2PI = 1.0 / math.sqrt(2.0 * math.pi)

_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
           jnp.dtype(jnp.float16))


# erf(x) ~= x * P(x^2) / Q(x^2) on |x| <= 4 — the f32 rational
# approximation XLA and Eigen use. Mosaic has no lowering for lax.erf
# ("Unimplemented primitive ... erf"), so the kernels evaluate it from
# mul/add/div; within 1e-6 absolute of lax.erf over the f32 range
# (tests/test_pallas_epilogue.py::test_erf_matches_xla).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf(x):
    """f32 erf from primitives the Pallas TPU lowering has."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * _horner(x2, _ERF_P) / _horner(x2, _ERF_Q)


def _interpret():
    from .pallas_common import interpret_mode
    return interpret_mode()


def _pick_rows(M, C, esize, n_streams):
    """Largest whole row-block keeping double-buffered streams under
    ~10 MB of VMEM (the pallas_norm heuristic — the autotuner's
    incumbent default)."""
    per_row = C * (n_streams * esize + 4 * 4)
    floor = 8 if esize >= 4 else 16
    for bm in (1024, 512, 256, 128, 64, 32, 16, 8):
        if bm < floor or M % bm:
            continue
        if bm * per_row * 2 + 8 * C * 4 <= 10 * 1024 * 1024:
            return bm
    return None


def _tuned_rows(kernel, M, C, esize, n_streams, default, build_probe):
    """Shared-helper consult for the epilogue row-block sizes
    (MXNET_AUTOTUNE; off mode returns the _pick_rows default
    untouched). autotune.tuned_rows owns the candidate grid AND the
    cache-entry validation — a stale table entry must clear the same
    sublane-floor/VMEM rules as a fresh pick."""
    from .. import autotune
    return autotune.tuned_rows(
        kernel, M, C, esize, default,
        C * (n_streams * esize + 4 * 4), extra_bytes=8 * C * 4,
        flops=8.0 * M * C,
        hbm_bytes=float((n_streams + 1) * M * C * esize),
        probe=build_probe)


def _available(shape, dtype, n_streams):
    from ..config import get as _cfg
    from .pallas_common import kernels_allowed
    # no per-shard rule (pallas_common.per_shard), on purpose: in the
    # partitioned BERT-base step these two kernels, a shard each, read
    # 3,941 samples/s where XLA's own fusions of the compositions read
    # 4,503 (PERF.md section 6, PR 45), so on a mesh they stand down
    # (on one device too they lose: ROADMAP A2)
    if not _cfg("MXNET_PALLAS_EPILOGUE") or not kernels_allowed():
        return False
    if len(shape) < 2:
        return False
    if jnp.dtype(dtype) not in _DTYPES:
        return False
    C = shape[-1]
    M = 1
    for s in shape[:-1]:
        M *= s
    if M < 8 or C < 1:
        return False
    return _pick_rows(M, C, jnp.dtype(dtype).itemsize,
                      n_streams) is not None


def bias_gelu_available(shape, dtype, bias_dtype=None):
    """True when the fused bias+GeLU kernels can serve this call (the
    caller falls back to the ``gelu(x + b)`` XLA composition)."""
    if bias_dtype is not None and \
            jnp.dtype(bias_dtype) != jnp.dtype(dtype):
        return False
    return _available(shape, dtype, 3)


def bias_residual_available(shape, dtype, bias_dtype=None,
                            residual_dtype=None):
    """True when the fused bias+residual kernel can serve this call."""
    for dt in (bias_dtype, residual_dtype):
        if dt is not None and jnp.dtype(dt) != jnp.dtype(dtype):
            return False
    return _available(shape, dtype, 3)


# ---------------------------------------------------------------------------
# bias + GeLU
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _bias_gelu_fwd_call(M, C, bm, dtype_name, interpret):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)

    def pallas_bias_gelu_fwd(x_ref, b_ref, o_ref):
        z = x_ref[:].astype(jnp.float32) + b_ref[0, :]
        o = 0.5 * z * (1.0 + _erf(z * _INV_SQRT2))
        o_ref[:] = o.astype(o_ref.dtype)

    return pl.pallas_call(
        pallas_bias_gelu_fwd,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((8, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), dtype),
        interpret=interpret,
        name="pallas_bias_gelu_fwd",
    )


@functools.lru_cache(maxsize=None)
def _bias_gelu_bwd_call(M, C, bm, dtype_name, interpret):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)

    def pallas_bias_gelu_bwd(dy_ref, x_ref, b_ref, dx_ref, db_ref):
        i = pl.program_id(0)
        # re-derive the pre-activation from the x block already
        # streaming for dx — z is never saved to HBM
        z = x_ref[:].astype(jnp.float32) + b_ref[0, :]
        dyf = dy_ref[:].astype(jnp.float32)
        cdf = 0.5 * (1.0 + _erf(z * _INV_SQRT2))
        pdf = jnp.exp(-0.5 * z * z) * _INV_SQRT2PI
        dz = dyf * (cdf + z * pdf)
        dx_ref[:] = dz.astype(dx_ref.dtype)
        # db partial sums accumulated across sequential grid steps
        # (the pallas_norm dgamma/dbeta idiom)
        row = jnp.concatenate(
            [jnp.sum(dz, axis=0)[None],
             jnp.zeros((7, C), jnp.float32)], axis=0)

        @pl.when(i == 0)
        def _():
            db_ref[:] = row

        @pl.when(i > 0)
        def _():
            db_ref[:] = db_ref[:] + row

    return pl.pallas_call(
        pallas_bias_gelu_bwd,
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
        name="pallas_bias_gelu_bwd",
    )


def _b8(b, C):
    """(C,) bias -> the (8, C) f32 sublane-aligned sidecar block."""
    return jnp.concatenate(
        [b[None].astype(jnp.float32), jnp.zeros((7, C), jnp.float32)],
        axis=0)


def _gelu_probe(M, C, bm, dtype_name):
    def build():
        x = jnp.zeros((M, C), jnp.dtype(dtype_name))
        b = jnp.zeros((C,), jnp.dtype(dtype_name))

        def fn(x, b):
            call = _bias_gelu_fwd_call(M, C, bm, dtype_name,
                                       _interpret())
            return call(x, _b8(b, C))
        return fn, (x, b)
    return build


@functools.lru_cache(maxsize=None)
def _make_bias_gelu(M, C, bm, dtype_name, interpret):
    @jax.custom_vjp
    def f(x2, b):
        call = _bias_gelu_fwd_call(M, C, bm, dtype_name, interpret)
        return call(x2, _b8(b, C))

    def fwd(x2, b):
        return f(x2, b), (x2, b)

    def bwd(res, dy):
        x2, b = res
        call = _bias_gelu_bwd_call(M, C, bm, dtype_name, interpret)
        dx, sums = call(dy, x2, _b8(b, C))
        return dx, sums[0].astype(b.dtype)

    f.defvjp(fwd, bwd)
    return f


def pallas_bias_gelu(data, bias, *, block_rows=None):
    """Fused ``GeLU(data + bias)`` over the last axis.

    data: (..., C); bias: (C,). Caller must have checked
    bias_gelu_available(); ``block_rows`` overrides the autotuned
    row-block choice (tests)."""
    C = data.shape[-1]
    M = data.size // C
    esize = jnp.dtype(data.dtype).itemsize
    dtype_name = jnp.dtype(data.dtype).name
    default = _pick_rows(M, C, esize, 3)
    bm = block_rows or _tuned_rows(
        "pallas_bias_gelu", M, C, esize, 3, default,
        lambda b: _gelu_probe(M, C, b, dtype_name))
    if bm is None or M % bm:
        raise ValueError(
            "pallas_bias_gelu: no whole row-block tiling for shape %r "
            "(call bias_gelu_available first)" % (data.shape,))
    f = _make_bias_gelu(M, C, bm, dtype_name, _interpret())
    return f(data.reshape(M, C), bias).reshape(data.shape)


# ---------------------------------------------------------------------------
# bias + residual add
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _residual_fwd_call(M, C, bm, dtype_name, interpret):
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)

    def pallas_residual_fwd(x_ref, r_ref, b_ref, o_ref):
        o = (x_ref[:].astype(jnp.float32) + b_ref[0, :]
             + r_ref[:].astype(jnp.float32))
        o_ref[:] = o.astype(o_ref.dtype)

    return pl.pallas_call(
        pallas_residual_fwd,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec((8, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), dtype),
        interpret=interpret,
        name="pallas_residual_fwd",
    )


def _residual_probe(M, C, bm, dtype_name):
    def build():
        x = jnp.zeros((M, C), jnp.dtype(dtype_name))
        r = jnp.zeros((M, C), jnp.dtype(dtype_name))
        b = jnp.zeros((C,), jnp.dtype(dtype_name))

        def fn(x, r, b):
            call = _residual_fwd_call(M, C, bm, dtype_name,
                                      _interpret())
            return call(x, r, _b8(b, C))
        return fn, (x, r, b)
    return build


@functools.lru_cache(maxsize=None)
def _make_bias_residual(M, C, bm, dtype_name, interpret):
    @jax.custom_vjp
    def f(x2, b, r2):
        call = _residual_fwd_call(M, C, bm, dtype_name, interpret)
        return call(x2, r2, _b8(b, C))

    def fwd(x2, b, r2):
        return f(x2, b, r2), ()

    def bwd(res, dy):
        # identity fan-out plus one reduction — XLA's home turf
        # (availability pins bias dtype == data dtype, so dy.dtype is
        # the right db dtype)
        db = jnp.sum(dy.astype(jnp.float32), axis=0).astype(dy.dtype)
        return dy, db, dy

    f.defvjp(fwd, bwd)
    return f


def pallas_bias_residual(data, bias, residual, *, block_rows=None):
    """Fused ``data + bias + residual`` over the last axis.

    data/residual: (..., C) same shape; bias: (C,). Caller must have
    checked bias_residual_available()."""
    C = data.shape[-1]
    M = data.size // C
    esize = jnp.dtype(data.dtype).itemsize
    dtype_name = jnp.dtype(data.dtype).name
    default = _pick_rows(M, C, esize, 3)
    bm = block_rows or _tuned_rows(
        "pallas_residual", M, C, esize, 3, default,
        lambda b: _residual_probe(M, C, b, dtype_name))
    if bm is None or M % bm:
        raise ValueError(
            "pallas_bias_residual: no whole row-block tiling for shape "
            "%r (call bias_residual_available first)" % (data.shape,))
    f = _make_bias_residual(M, C, bm, dtype_name, _interpret())
    dxb = f(data.reshape(M, C), bias, residual.reshape(M, C))
    return dxb.reshape(data.shape)
