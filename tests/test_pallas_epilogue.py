"""Fused bias+GeLU / bias+residual epilogue kernels (round 7,
ISSUE 14; ops/pallas_epilogue.py). Interpret mode on CPU — the suite
pins MXNET_PALLAS_INTERPRET (the pallas_norm pattern)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_epilogue import (bias_gelu_available,
                                           bias_residual_available,
                                           pallas_bias_gelu,
                                           pallas_bias_residual)
from numerics import jitted


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    yield


def _gelu_ref(x, b):
    return jax.nn.gelu(x + b, approximate=False)


@pytest.mark.parametrize("M,C,dtype,tol", [
    (64, 32, jnp.float32, 5e-7),
    (128, 96, jnp.float32, 5e-7),
    (64, 128, jnp.bfloat16, 2e-2),
])
def test_bias_gelu_fwd_parity(M, C, dtype, tol):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, C).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.randn(C).astype(np.float32)).astype(dtype)
    assert bias_gelu_available((M, C), dtype, dtype)
    o1 = pallas_bias_gelu(x, b)
    o2 = _gelu_ref(x, b)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        rtol=tol, atol=tol)


def test_bias_gelu_exact_grads():
    """Analytic bwd (streamed-preactivation re-derivation) vs the XLA
    reference grads AND a central-difference probe (f32, clean)."""
    M, C = 64, 32
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(M, C).astype(np.float32))
    b = jnp.asarray(rng.randn(C).astype(np.float32))
    r = jnp.asarray(rng.randn(M, C).astype(np.float32))

    def s1(x, b):
        return jnp.sum(pallas_bias_gelu(x, b) * r)

    def s2(x, b):
        return jnp.sum(_gelu_ref(x, b) * r)

    g1 = jax.jit(jax.grad(s1, argnums=(0, 1)))(x, b)
    g2 = jax.jit(jax.grad(s2, argnums=(0, 1)))(x, b)
    s1 = jax.jit(s1)
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               rtol=1e-5, atol=1e-5)
    eps = 1e-3
    for idx in [(0, 0), (13, 17), (63, 31)]:
        e = jnp.zeros_like(x).at[idx].set(eps)
        num = (s1(x + e, b) - s1(x - e, b)) / (2 * eps)
        assert abs(float(num) - float(g1[0][idx])) < 1e-2


def test_bias_gelu_multiblock_db_accumulation():
    """db partial sums accumulate across sequential grid steps —
    force multiple blocks and compare against the single-block run."""
    M, C = 64, 32
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(M, C).astype(np.float32))
    b = jnp.asarray(rng.randn(C).astype(np.float32))

    def db_of(block_rows):
        def s(x, b):
            return jnp.sum(pallas_bias_gelu(x, b,
                                            block_rows=block_rows))
        return jax.jit(jax.grad(s, argnums=1))(x, b)

    np.testing.assert_allclose(np.asarray(db_of(8)),
                               np.asarray(db_of(64)),
                               rtol=1e-5, atol=1e-5)


def test_bias_residual_exact_and_grads():
    M, C = 48, 64
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(M, C).astype(np.float32))
    b = jnp.asarray(rng.randn(C).astype(np.float32))
    r = jnp.asarray(rng.randn(M, C).astype(np.float32))
    assert bias_residual_available((M, C), x.dtype, b.dtype, r.dtype)
    o = pallas_bias_residual(x, b, r)
    assert bool(jnp.all(o == x + b + r))
    w = jnp.asarray(rng.randn(M, C).astype(np.float32))
    g1 = jax.jit(jax.grad(lambda x, b, r: jnp.sum(
        pallas_bias_residual(x, b, r) * w), argnums=(0, 1, 2)))(x, b, r)
    g2 = jax.jit(jax.grad(lambda x, b, r: jnp.sum(
        (x + b + r) * w), argnums=(0, 1, 2)))(x, b, r)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-6, atol=1e-6)


def test_registered_ops_flag_off_bitwise(monkeypatch):
    """MXNET_PALLAS_EPILOGUE=0: the registered ops are byte-identical
    to the reference XLA compositions the model ran before this PR."""
    from mxnet_tpu.ops import get_op
    M, C = 32, 64
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(M, C).astype(np.float32))
    b = jnp.asarray(rng.randn(C).astype(np.float32))
    r = jnp.asarray(rng.randn(M, C).astype(np.float32))
    monkeypatch.setenv("MXNET_PALLAS_EPILOGUE", "0")
    assert not bias_gelu_available((M, C), x.dtype, b.dtype)
    assert not bias_residual_available((M, C), x.dtype)
    og = get_op("_contrib_bias_gelu").impl(x, b)
    assert bool(jnp.all(og == jax.nn.gelu(x + b, approximate=False)))
    orr = get_op("_contrib_bias_add_residual").impl(x, b, r)
    assert bool(jnp.all(orr == x + b + r))


def test_availability_ladder():
    assert not bias_gelu_available((32, 64), jnp.int32)
    assert not bias_gelu_available((64,), jnp.float32)        # 1-D
    assert not bias_gelu_available((32, 64), jnp.bfloat16,
                                   bias_dtype=jnp.float32)    # mixed
    assert not bias_residual_available(
        (32, 64), jnp.float32, residual_dtype=jnp.bfloat16)
    # mismatched residual shape falls back inside the op (no crash)
    from mxnet_tpu.ops import get_op
    x = jnp.zeros((4, 8, 16))
    r = jnp.zeros((1, 8, 16))
    b = jnp.zeros((16,))
    out = get_op("_contrib_bias_add_residual").impl(x, b, r)
    assert out.shape == (4, 8, 16)


def test_dense_epilogue_wiring_and_flag_off_parity(monkeypatch):
    """gluon Dense(epilogue=...) routes through the fused ops; with the
    flag off it reproduces the r6 composition bitwise (matmul -> bias
    add -> gelu / residual add in the same order)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn

    rng = np.random.RandomState(5)
    x = nd.array(rng.randn(16, 4, 32).astype(np.float32))

    d1 = nn.Dense(64, flatten=False, in_units=32, epilogue="gelu",
                  prefix="a_")
    d1.initialize()
    ref = nn.Dense(64, flatten=False, in_units=32, prefix="b_")
    ref.initialize()
    ref.weight.set_data(d1.weight.data())
    ref.bias.set_data(d1.bias.data())

    monkeypatch.setenv("MXNET_PALLAS_EPILOGUE", "0")
    o_off = d1(x).asnumpy()
    o_ref = nd.LeakyReLU(ref(x), act_type="gelu").asnumpy()
    assert np.array_equal(o_off, o_ref)

    monkeypatch.delenv("MXNET_PALLAS_EPILOGUE")
    o_on = d1(x).asnumpy()
    np.testing.assert_allclose(o_on, o_ref, rtol=1e-5, atol=1e-5)

    # residual epilogue: with and without the second input
    d2 = nn.Dense(32, flatten=False, epilogue="residual", prefix="c_")
    d2.initialize()
    plain = d2(x).asnumpy()
    fused = d2(x, x).asnumpy()
    np.testing.assert_allclose(fused, plain + x.asnumpy(),
                               rtol=1e-5, atol=1e-5)

    with pytest.raises(ValueError):
        nn.Dense(8, epilogue="gelu", use_bias=False)
    with pytest.raises(ValueError):
        nn.Dense(8, epilogue="nope")
    # a residual input on a non-residual Dense must raise, not be
    # silently dropped (review fix)
    with pytest.raises(ValueError):
        d1(x, x)
    d3 = nn.Dense(32, flatten=False, in_units=32, prefix="d_")
    d3.initialize()
    with pytest.raises(ValueError):
        d3(x, x)


def test_bert_ffn_and_cell_parity(monkeypatch):
    """The model-zoo BERT paths produce the same function with the
    epilogues on and off (tolerance: the kernels compute in f32), and
    the dropout=0 FFN routes the residual through ffn_2."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.bert import (BERTEncoderCell,
                                                PositionwiseFFN)
    rng = np.random.RandomState(6)
    x = nd.array(rng.randn(16, 4, 32).astype(np.float32))

    ffn = PositionwiseFFN(32, 64, dropout=0.0)
    ffn.initialize()
    on = ffn(x).asnumpy()
    monkeypatch.setenv("MXNET_PALLAS_EPILOGUE", "0")
    off = ffn(x).asnumpy()
    monkeypatch.delenv("MXNET_PALLAS_EPILOGUE")
    np.testing.assert_allclose(on, off, rtol=1e-4, atol=1e-4)

    cell = BERTEncoderCell(32, 64, 4, dropout=0.0)
    cell.initialize()
    on = cell(x).asnumpy()
    monkeypatch.setenv("MXNET_PALLAS_EPILOGUE", "0")
    off = cell(x).asnumpy()
    monkeypatch.delenv("MXNET_PALLAS_EPILOGUE")
    np.testing.assert_allclose(on, off, rtol=1e-4, atol=1e-4)


def test_erf_matches_xla():
    """The kernels evaluate erf from mul/add/div (Mosaic has no erf
    lowering): within 1e-6 absolute of lax.erf over the f32 range,
    saturation and the dense centre included."""
    from jax import lax
    from mxnet_tpu.ops.pallas_epilogue import _erf
    x = jnp.asarray(np.concatenate([
        np.linspace(-8.0, 8.0, 400001),
        np.random.RandomState(0).randn(100000) * 3.0,
        [0.0, -0.0, 1e-20, 4.0, -4.0, 1e30, -1e30]]).astype(np.float32))
    got = np.asarray(jitted(_erf)(x))
    np.testing.assert_allclose(got, np.asarray(lax.erf(x)), rtol=0,
                               atol=1e-6)
    assert np.all(np.abs(got) <= 1.0 + 1e-6)
