"""The row-wise work between BERT's products, as the ops a model names:
``_contrib_bias_gelu`` and ``_contrib_bias_add_residual`` (the Dense
epilogues; ``gluon.nn.Dense(epilogue=...)``, the zoo BERT). Each is the
plain composition XLA fuses into the products beside it (PR 48: the
Pallas kernels that served them lost to those fusions on the chip), so
what is held here is the op itself: its value against a float64 numpy
reference and its gradients against autodiff of the float32 formula, in
both dtypes and in every layout the models feed.
"""
import math

import numpy as np
import pytest

import jax

from numerics import BF, F32, close, jitted, near, rand, value_and_grads

from mxnet_tpu.ops import get_op

C = 128
LAYOUTS = {"BTC": (4, 16, C), "LNC": (16, 4, C), "2D": (64, C)}

_erf = np.vectorize(math.erf)


def _gelu64(x, b):
    z = x + b
    return 0.5 * z * (1.0 + _erf(z / math.sqrt(2.0)))


def _gelu32(x, b):
    z = x + b
    return 0.5 * z * (1.0 + jax.lax.erf(z * np.float32(1 / math.sqrt(2.0))))


# op -> (operands but the data and the bias, float64 reference, float32
# formula)
OPS = {
    "_contrib_bias_gelu": (0, _gelu64, _gelu32),
    "_contrib_bias_add_residual": (1, lambda x, b, r: x + b + r,
                                   lambda x, b, r: x + b + r),
}


def _case(op, dtype, layout):
    more, ref64, ref32 = OPS[op]
    shape = LAYOUTS[layout]
    args = rand(7, shape, (C,), *[shape] * more, scale=1.5, dtype=dtype)
    return get_op(op).impl, args, ref64, ref32


cases = pytest.mark.parametrize("op, dtype, layout", [
    pytest.param(op, dtype, layout, id="-".join((op, name, layout)))
    for op in sorted(OPS) for name, dtype in (("float32", F32),
                                              ("bfloat16", BF))
    for layout in sorted(LAYOUTS)])


@cases
def test_an_epilogue_op_is_its_formula_in_float64(op, dtype, layout):
    impl, args, ref64, _ = _case(op, dtype, layout)
    out = jitted(impl)(*args)
    assert out.dtype == dtype and out.shape == args[0].shape
    want = ref64(*[np.asarray(a, np.float64) for a in args])
    if dtype == F32:
        close(out, want, 1e-5)
    else:
        near(out, want, 2.0 ** -7)


@cases
def test_an_epilogue_op_has_its_float32_formulas_gradients(op, dtype,
                                                           layout):
    """To every operand, the bias's summed over the rows: what guards a
    backward rule of the op's own, should it get one again."""
    impl, args, _, ref32 = _case(op, dtype, layout)
    (cot,) = rand(8, args[0].shape)
    got = value_and_grads(impl, *args, cot=cot)
    # the formula at the same (rounded) inputs
    want = value_and_grads(ref32, *[a.astype(F32) for a in args], cot=cot)
    assert [g.shape for g in got[1:]] == [a.shape for a in args]
    if dtype == F32:
        close(got, want, 1e-5)
    else:
        near(got, want, 2e-2)



def test_a_residual_that_broadcasts_is_added():
    x, b, r = rand(9, (4, 8, 16), (16,), (1, 8, 16))
    out = jitted(get_op("_contrib_bias_add_residual").impl)(x, b, r)
    close(out, np.asarray(x) + np.asarray(b) + np.asarray(r), 1e-6)


def test_dense_routes_its_epilogue_through_the_ops():
    """``gluon.nn.Dense(epilogue=...)``: the product, then the op. What
    it computes is what a plain Dense and the activation (or the add)
    after it compute, to the bit; what it cannot fuse it refuses."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    x = nd.array(np.random.RandomState(5).randn(16, 4, 32)
                 .astype(np.float32))
    gelu = nn.Dense(64, flatten=False, in_units=32, epilogue="gelu",
                    prefix="a_")
    plain = nn.Dense(64, flatten=False, in_units=32, prefix="b_")
    for block in (gelu, plain):
        block.initialize()
    plain.weight.set_data(gelu.weight.data())
    plain.bias.set_data(gelu.bias.data())
    np.testing.assert_array_equal(
        gelu(x).asnumpy(),
        nd.LeakyReLU(plain(x), act_type="gelu").asnumpy())

    # the residual epilogue, with and without its second input
    res = nn.Dense(32, flatten=False, epilogue="residual", prefix="c_")
    res.initialize()
    close(res(x, x).asnumpy(), res(x).asnumpy() + x.asnumpy(), 1e-5)

    with pytest.raises(ValueError):
        nn.Dense(8, epilogue="gelu", use_bias=False)
    with pytest.raises(ValueError):
        nn.Dense(8, epilogue="nope")
    # a residual handed to a layer that cannot add it is an error, not
    # an input dropped in silence
    with pytest.raises(ValueError):
        gelu(x, x)
    with pytest.raises(ValueError):
        plain(x, x)


def test_the_zoo_ffn_without_dropout_adds_its_residual_in_ffn_2():
    """``PositionwiseFFN(dropout=0)``: ``ffn_2`` carries the residual
    epilogue, and the block is LayerNorm(ffn_2(GeLU(ffn_1 x)) + x)."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.bert import PositionwiseFFN
    x = nd.array(np.random.RandomState(6).randn(16, 4, 32)
                 .astype(np.float32))
    ffn = PositionwiseFFN(32, 64, dropout=0.0)
    ffn.initialize()
    assert ffn.ffn_2._epilogue == "residual"
    got = ffn(x).asnumpy()      # first: the call resolves the shapes
    w1, b1, w2, b2 = (p.data() for p in (ffn.ffn_1.weight, ffn.ffn_1.bias,
                                         ffn.ffn_2.weight, ffn.ffn_2.bias))
    hidden = nd.LeakyReLU(nd.FullyConnected(
        x, w1, b1, num_hidden=64, flatten=False), act_type="gelu")
    want = ffn.layer_norm(nd.FullyConnected(
        hidden, w2, b2, num_hidden=32, flatten=False) + x)
    close(got, want.asnumpy(), 1e-5)
    assert PositionwiseFFN(32, 64, dropout=0.1).ffn_2._epilogue is None
