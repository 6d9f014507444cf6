"""Pallas LayerNorm kernels (mxnet_tpu/ops/pallas_norm): exact-gradient
parity vs the XLA fused-VJP reference (_ln_fused), odd shapes, bf16 +
fp32, the output_mean_var path, and the MXNET_PALLAS_LAYERNORM off-path.

Runs in Pallas interpret mode (pallas_interpret fixture): numerics are
checked against the interpreter wherever the suite runs. Whether the
kernels compile for the chip is tests/test_chip_compile_*.py's question.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.nn import _ln_fused
from mxnet_tpu.ops.pallas_norm import (pallas_layer_norm,
                                       pallas_ln_available)
from mxnet_tpu.test_utils import check_numeric_gradient
from numerics import jitted


def _data(rng, shape, dtype):
    # offset mean so the two-pass-variance property is actually load-
    # bearing (E[x^2]-mean^2 would cancel here)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 2.0 + 3.0)
    return x.astype(dtype)


@pytest.mark.parametrize("shape,dtype", [
    ((16, 33), jnp.float32),          # odd channel count
    ((24, 7), jnp.float32),           # tiny odd channels
    ((4, 8, 128), jnp.bfloat16),      # 3-D, aligned
    ((32, 768), jnp.bfloat16),        # BERT hidden width
    ((32, 768), jnp.float32),
])
def test_ln_kernel_matches_xla_reference(pallas_interpret, shape, dtype):
    rng = np.random.RandomState(0)
    x = _data(rng, shape, dtype)
    C = shape[-1]
    g = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(C).astype(np.float32))
    r = jnp.asarray(rng.randn(*shape).astype(np.float32))
    ax = len(shape) - 1
    assert pallas_ln_available(shape, dtype, ax)

    def f_pallas(x, g, b):
        return jnp.sum(pallas_layer_norm(x, g, b, eps=1e-5)
                       .astype(jnp.float32) * r)

    def f_xla(x, g, b):
        return jnp.sum(_ln_fused(ax, len(shape), 1e-5)(x, g, b)
                       .astype(jnp.float32) * r)

    # bf16 outputs can differ in the last mantissa bit between the two
    # schedules; f32 only by reduction order
    bf16 = jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
    (sum_p, g1), (sum_x, g2) = (
        jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(x, g, b)
        for f in (f_pallas, f_xla))
    np.testing.assert_allclose(float(sum_p), float(sum_x),
                               rtol=5e-3 if bf16 else 2e-4)
    out_p = np.asarray(jax.jit(lambda *a: pallas_layer_norm(*a, eps=1e-5))(
        x, g, b), np.float32)
    out_x = np.asarray(jitted(_ln_fused(ax, len(shape), 1e-5))(x, g, b),
                       np.float32)
    np.testing.assert_allclose(out_p, out_x,
                               rtol=1e-2 if bf16 else 2e-5,
                               atol=1e-2 if bf16 else 2e-5)
    for a, ref, nm in zip(g1, g2, "xgb"):
        a = np.asarray(a, np.float32)
        ref = np.asarray(ref, np.float32)
        denom = np.max(np.abs(ref)) + 1e-9
        assert np.max(np.abs(a - ref)) / denom < 2e-3, nm


def test_ln_kernel_multiblock_accumulation(pallas_interpret):
    """dgamma/dbeta accumulate across sequential grid steps: force a
    small row block so the reduction output is revisited 8 times."""
    rng = np.random.RandomState(1)
    x = _data(rng, (2048, 128), jnp.float32)
    g = jnp.asarray(rng.rand(128).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(128).astype(np.float32))

    def f_pallas(x, g, b):
        return jnp.sum(pallas_layer_norm(x, g, b, eps=1e-5,
                                         block_rows=256))

    def f_xla(x, g, b):
        return jnp.sum(_ln_fused(1, 2, 1e-5)(x, g, b))

    g1 = jax.jit(jax.grad(f_pallas, argnums=(0, 1, 2)))(x, g, b)
    g2 = jax.jit(jax.grad(f_xla, argnums=(0, 1, 2)))(x, g, b)
    for a, ref in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_ln_op_numeric_gradient(pallas_interpret):
    """check_numeric_gradient through the registered LayerNorm op with
    the Pallas path active (central differences vs the tape)."""
    rng = np.random.RandomState(2)
    from mxnet_tpu import nd

    def op(data, gamma, beta):
        return nd.LayerNorm(data, gamma, beta, axis=-1, eps=1e-5)

    check_numeric_gradient(
        op, [rng.randn(8, 16) * 2 + 1, rng.rand(16) + 0.5,
             rng.randn(16)], rtol=2e-2, atol=2e-3)


def test_ln_flag_off_reproduces_xla_path(pallas_interpret, monkeypatch):
    """Off-path parity: MXNET_PALLAS_LAYERNORM=0 must reproduce the
    current numerics exactly (it IS the _ln_fused path), and the on-path
    result agrees to fp tolerance."""
    rng = np.random.RandomState(3)
    from mxnet_tpu import nd
    x = nd.array((rng.randn(16, 64) * 2 + 3).astype(np.float32))
    g = nd.array((rng.rand(64) + 0.5).astype(np.float32))
    b = nd.array(rng.randn(64).astype(np.float32))

    monkeypatch.setenv("MXNET_PALLAS_LAYERNORM", "0")
    off = nd.LayerNorm(x, g, b, axis=-1, eps=1e-5).asnumpy()
    # the eager op path runs _ln_fused under jit — compare against the
    # identically-jitted reference for bitwise equality
    ref = np.asarray(jitted(_ln_fused(1, 2, 1e-5))(
        jnp.asarray(x.asnumpy()), jnp.asarray(g.asnumpy()),
        jnp.asarray(b.asnumpy())))
    np.testing.assert_array_equal(off, ref)

    monkeypatch.setenv("MXNET_PALLAS_LAYERNORM", "1")
    on = nd.LayerNorm(x, g, b, axis=-1, eps=1e-5).asnumpy()
    np.testing.assert_allclose(on, off, rtol=1e-6, atol=1e-6)


def test_ln_output_mean_var_unaffected(pallas_interpret):
    """output_mean_var stays on the reference path regardless of the
    flag and returns the exact reduced mean/std."""
    rng = np.random.RandomState(4)
    from mxnet_tpu import nd
    xn = (rng.randn(8, 32) * 1.5 + 2).astype(np.float32)
    x = nd.array(xn)
    g = nd.array(np.ones(32, np.float32))
    b = nd.array(np.zeros(32, np.float32))
    out, mean, std = nd.LayerNorm(x, g, b, axis=-1, eps=1e-5,
                                  output_mean_var=True)
    np.testing.assert_allclose(mean.asnumpy(), xn.mean(-1), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        std.asnumpy(), np.sqrt(xn.var(-1) + 1e-5), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        out.asnumpy(), (xn - xn.mean(-1, keepdims=True))
        / np.sqrt(xn.var(-1, keepdims=True) + 1e-5), rtol=1e-4, atol=1e-4)


def test_ln_ineligible_shape_falls_back(pallas_interpret):
    """Shapes with no whole row-block tiling (here M=5 rows) must fall
    back cleanly to the XLA path — never raise."""
    assert not pallas_ln_available((5, 33), jnp.float32, 1)
    rng = np.random.RandomState(5)
    from mxnet_tpu import nd
    x = nd.array(rng.randn(5, 33).astype(np.float32))
    g = nd.array(np.ones(33, np.float32))
    b = nd.array(np.zeros(33, np.float32))
    out = nd.LayerNorm(x, g, b, axis=-1, eps=1e-5).asnumpy()
    ref = np.asarray(_ln_fused(1, 2, 1e-5)(
        jnp.asarray(x.asnumpy()), jnp.ones(33, np.float32),
        jnp.zeros(33, np.float32)))
    np.testing.assert_array_equal(out, ref)


def test_ln_non_last_axis_falls_back(pallas_interpret):
    """axis != last is served by the XLA path (kernel is last-axis
    only); numerics must match the reference regardless."""
    assert not pallas_ln_available((16, 32), jnp.float32, 0)
    rng = np.random.RandomState(6)
    from mxnet_tpu import nd
    x = nd.array(rng.randn(16, 32).astype(np.float32))
    g = nd.array((rng.rand(16) + 0.5).astype(np.float32))
    b = nd.array(rng.randn(16).astype(np.float32))
    out = nd.LayerNorm(x, g, b, axis=0, eps=1e-5).asnumpy()
    xn = x.asnumpy()
    mean = xn.mean(0, keepdims=True)
    inv = 1.0 / np.sqrt(xn.var(0, keepdims=True) + 1e-5)
    ref = (xn - mean) * inv * g.asnumpy()[:, None] \
        + b.asnumpy()[:, None]
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
