"""Experimental Pallas fused bottleneck ops (mxnet_tpu/ops/pallas_fused):
numerics of the dual-matmul backward kernels vs plain-XLA references.
Runs in interpret mode on the CPU mesh; on a real chip the same code
Mosaic-compiles (exercised by tools/layout_exp.py modes 3-5)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_fused import (bottleneck_v1_block,
                                        bottleneck_v1_block_ref,
                                        conv1x1_bn_act, conv1x1_bn_act_ref,
                                        fused_stage)


def _mk(rng, i, o, k=1):
    if k == 1:
        return jnp.asarray(rng.randn(i, o).astype(np.float32)
                           * np.sqrt(2.0 / i))
    return jnp.asarray(rng.randn(k, k, i, o).astype(np.float32)
                       * np.sqrt(2.0 / (i * k * k)))


def _bnp(rng, c):
    return (jnp.asarray(rng.rand(c).astype(np.float32) + 0.5),
            jnp.asarray(rng.randn(c).astype(np.float32) * 0.1))


@pytest.mark.parametrize("relu", [True, False])
def test_conv1x1_bn_act_matches_ref(relu):
    rng = np.random.RandomState(0)
    N, H, W, I, O = 4, 8, 8, 32, 64
    x = jnp.asarray(rng.randn(N, H, W, I).astype(np.float32)) \
        .astype(jnp.bfloat16)
    w = _mk(rng, I, O)
    g, b = _bnp(rng, O)
    r = jnp.asarray(rng.randn(N, H, W, O).astype(np.float32))

    def f1(x, w, g, b):
        return jnp.sum(conv1x1_bn_act(x, w, g, b, relu=relu)[0]
                       .astype(jnp.float32) * r)

    def f2(x, w, g, b):
        return jnp.sum(conv1x1_bn_act_ref(x, w, g, b, relu=relu)[0]
                       .astype(jnp.float32) * r)

    np.testing.assert_allclose(float(f1(x, w, g, b)), float(f2(x, w, g, b)),
                               rtol=2e-2)
    g1 = jax.grad(f1, argnums=(0, 1, 2, 3))(x, w, g, b)
    g2 = jax.grad(f2, argnums=(0, 1, 2, 3))(x, w, g, b)
    for a, bb, nm in zip(g1, g2, "xwgb"):
        a = np.asarray(a, np.float32)
        bb = np.asarray(bb, np.float32)
        denom = np.max(np.abs(bb)) + 1e-9
        assert np.max(np.abs(a - bb)) / denom < 3e-2, nm


@pytest.mark.parametrize("has_ds", [False, True])
def test_bottleneck_block_matches_ref_f32(has_ds):
    """f32 + jnp fallback: the hand-scheduled block backward must agree
    with autodiff of the unfused composition to fp tolerance."""
    import mxnet_tpu.ops.pallas_fused as pf
    rng = np.random.RandomState(1)
    H, W, N, I, C, O = 8, 8, 4, 32, 8, 32
    x = jnp.asarray(rng.randn(H, W, N, I).astype(np.float32))
    params = [_mk(rng, I, C), *_bnp(rng, C), _mk(rng, C, C, 3),
              *_bnp(rng, C), _mk(rng, C, O), *_bnp(rng, O)]
    if has_ds:
        params += [_mk(rng, I, O), *_bnp(rng, O)]
    params = tuple(params)
    r = jnp.asarray(rng.randn(H, W, N, O).astype(np.float32))
    orig = pf._run_dual
    pf._run_dual = lambda *a, **k: None
    try:
        def f1(x, *ps):
            return jnp.sum(bottleneck_v1_block(
                x, ps, data_format="HWNC", has_ds=has_ds)[0] * r)

        def f2(x, *ps):
            return jnp.sum(bottleneck_v1_block_ref(
                x, ps, data_format="HWNC", has_ds=has_ds)[0] * r)

        np.testing.assert_allclose(float(f1(x, *params)),
                                   float(f2(x, *params)), rtol=1e-4)
        argnums = tuple(range(len(params) + 1))
        g1 = jax.grad(f1, argnums=argnums)(x, *params)
        g2 = jax.grad(f2, argnums=argnums)(x, *params)
        for i, (a, bb) in enumerate(zip(g1, g2)):
            denom = float(jnp.max(jnp.abs(bb))) + 1e-9
            err = float(jnp.max(jnp.abs(a - bb))) / denom
            assert err < 5e-3, (i, err)
    finally:
        pf._run_dual = orig


def test_block_kernel_matches_fallback_bf16():
    """kernel path vs jnp fallback on identical bf16 inputs: parameter
    grads must agree exactly (same math, same roundings)."""
    import mxnet_tpu.ops.pallas_fused as pf
    rng = np.random.RandomState(2)
    H, W, N, I, C, O = 8, 8, 4, 32, 8, 32
    x = jnp.asarray(rng.randn(H, W, N, I).astype(np.float32)) \
        .astype(jnp.bfloat16)
    params = tuple([_mk(rng, I, C), *_bnp(rng, C), _mk(rng, C, C, 3),
                    *_bnp(rng, C), _mk(rng, C, O), *_bnp(rng, O)])
    r = jnp.asarray(rng.randn(H, W, N, O).astype(np.float32))

    def f(x, *ps):
        return jnp.sum(bottleneck_v1_block(
            x, ps, data_format="HWNC")[0].astype(jnp.float32) * r)

    argnums = tuple(range(len(params) + 1))
    g_kernel = jax.grad(f, argnums=argnums)(x, *params)
    orig = pf._run_dual
    pf._run_dual = lambda *a, **k: None
    try:
        g_fb = jax.grad(f, argnums=argnums)(x, *params)
    finally:
        pf._run_dual = orig
    # parameter grads agree to accumulation-order tolerance (the
    # kernel reduces per-tile, the fallback in one einsum)
    for a, bb in zip(g_kernel[1:], g_fb[1:]):
        a = np.asarray(a, np.float32)
        bb = np.asarray(bb, np.float32)
        denom = np.max(np.abs(bb)) + 1e-9
        assert np.max(np.abs(a - bb)) / denom < 1e-3


def test_fused_stage_matches_chained_blocks_f32():
    import mxnet_tpu.ops.pallas_fused as pf
    rng = np.random.RandomState(3)
    H, W, N, I, C, O = 8, 8, 4, 32, 8, 32
    x = jnp.asarray(rng.randn(H, W, N, I).astype(np.float32))

    def mkblock(i, with_ds):
        ps = [_mk(rng, i, C), *_bnp(rng, C), _mk(rng, C, C, 3),
              *_bnp(rng, C), _mk(rng, C, O), *_bnp(rng, O)]
        if with_ds:
            ps += [_mk(rng, i, O), *_bnp(rng, O)]
        return tuple(ps)

    blocks = [mkblock(I, True), mkblock(O, False), mkblock(O, False)]
    flat = [v for b in blocks for v in b]
    r = jnp.asarray(rng.randn(H, W, N, O).astype(np.float32))
    orig = pf._run_dual
    pf._run_dual = lambda *a, **k: None
    try:
        def f1(x, *fl):
            b0, b1, b2 = fl[:12], fl[12:21], fl[21:30]
            out, _ = fused_stage(x, (b0, b1, b2), data_format="HWNC",
                                 ds_first=True)
            return jnp.sum(out * r)

        def f2(x, *fl):
            b0, b1, b2 = fl[:12], fl[12:21], fl[21:30]
            out, _ = bottleneck_v1_block_ref(x, b0, data_format="HWNC",
                                             has_ds=True)
            out, _ = bottleneck_v1_block_ref(out, b1, data_format="HWNC")
            out, _ = bottleneck_v1_block_ref(out, b2, data_format="HWNC")
            return jnp.sum(out * r)

        np.testing.assert_allclose(float(f1(x, *flat)), float(f2(x, *flat)),
                                   rtol=1e-4)
        argnums = tuple(range(len(flat) + 1))
        g1 = jax.grad(f1, argnums=argnums)(x, *flat)
        g2 = jax.grad(f2, argnums=argnums)(x, *flat)
        for i, (a, bb) in enumerate(zip(g1, g2)):
            denom = float(jnp.max(jnp.abs(bb))) + 1e-9
            assert float(jnp.max(jnp.abs(a - bb))) / denom < 5e-3, i
    finally:
        pf._run_dual = orig
