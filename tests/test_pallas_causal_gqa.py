"""The causal grouped-query flash kernel (ops/pallas_causal_gqa.py) in
interpret mode on the CPU: values and gradients against the blocked
composition it stands in for (``decoder_ops._causal_gqa``) and against
the plain float32 reference (tests/numerics.py); causality; and the
ladder by which ``decoder_ops._attend`` picks a schedule, each rung
counted in ``mx_attn_causal_path_total``. What Mosaic makes of the
kernels at the published widths is tests/test_chip_compile_*.py's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mxnet_tpu import telemetry
from mxnet_tpu.ops import decoder_ops as D, get_op, pallas_causal_gqa as P
from mxnet_tpu.ops.pallas_common import auto_partitioned
from numerics import (BF, F32, attention_ref, near, qkv, value_and_grads,
                      window_ref)

COUNTER = "mx_attn_causal_path_total"


@pytest.mark.parametrize("heads, kv", [(2, 2), (4, 1), (16, 1)],
                         ids=["1to1", "4to1", "16to1"])
@pytest.mark.parametrize("length, tile", [(128, 128), (384, 128), (512, 256)],
                         ids=["one_tile", "three_tiles", "two_tiles_of_256"])
def test_kernel_matches_the_composition_and_the_reference(length, tile,
                                                          heads, kv):
    q, k, v, cot = qkv(length + heads, length, heads, kv, batch=2)
    got = value_and_grads(lambda *a: P.flash_causal_gqa(*a, tile),
                          q, k, v, cot=cot)
    # the composition on the same bf16 inputs: two roundings of one sum
    near(got, value_and_grads(lambda *a: D._causal_gqa(*a, tile),
                              q, k, v, cot=cot), 2e-2)
    # the plain float32 reference on the same values
    near(got, value_and_grads(
        attention_ref, *(t.astype(F32) for t in (q, k, v)), cot=cot), 2e-2)


@pytest.mark.parametrize("t", [0, 127, 128, 200, 382])
def test_a_key_after_position_t_never_reaches_output_t(t):
    q, k, v, _ = qkv(5, 384, 4, 2)
    later = (jnp.arange(384) > t)[None, :, None, None]
    kernel = jax.jit(lambda *a: P.flash_causal_gqa(*a, 128))
    out = kernel(q, k, v)
    moved = kernel(q, jnp.where(later, k + 3, k), jnp.where(later, v - 2, v))
    np.testing.assert_array_equal(np.asarray(out[:, :t + 1], F32),
                                  np.asarray(moved[:, :t + 1], F32))
    assert not np.array_equal(np.asarray(out[:, t + 1:], F32),
                              np.asarray(moved[:, t + 1:], F32))


# ---------------------------------------------------------------------------
# which schedule a call takes, through the registered ops
# ---------------------------------------------------------------------------
@pytest.fixture
def counted():
    """{path: count} of the calls counted since the fixture began."""
    was = telemetry.enabled()
    telemetry.enable(True)
    start = {p: telemetry.counter(COUNTER, path=p).get()
             for p in ("pallas", "xla")}
    yield lambda: {p: telemetry.counter(COUNTER, path=p).get() - n
                   for p, n in start.items()}
    telemetry.enable(was)


def _attention(q, k, v):
    return get_op("_contrib_causal_gqa_attention").impl(q, k, v)


def _mixer(q, k, v):
    """The mixer op on a hidden state whose projections give q, k, v's
    shapes (weights of ones: only the path is looked at; the state
    hangs on q, so that q's gradient runs the mixer's backward)."""
    b, length, heads, d = q.shape
    kv, hidden = k.shape[2], 16
    w = lambda rows: jnp.ones((rows, hidden), q.dtype)
    data = jnp.ones((b, length, hidden), q.dtype) * jnp.mean(q)
    return get_op("_contrib_gqa_mixer").impl(
        data, jnp.ones((hidden,), q.dtype),
        w(heads * d), w(kv * d), w(kv * d),
        jnp.ones((hidden, heads * d), q.dtype),
        num_heads=heads, num_kv_heads=kv, head_dim=d)


def _pallas_calls(fn, *args):
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    grad = jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32)), (0, 1, 2))
    return sum(name == "pallas_call"
               for name in walk(jax.make_jaxpr(grad)(*args).jaxpr))


def _two_devices():
    return auto_partitioned(Mesh(np.array(jax.devices()[:2]), ("dp",)))


RUNGS = {
    # name: (length, heads, kv, d, dtype, scope to trace in)
    "float32_inputs": (D.QUERY_BLOCK, 2, 1, 128, F32, None),
    "two_device_mesh": (D.QUERY_BLOCK, 2, 1, 128, BF, _two_devices),
    "ragged_length": (D.QUERY_BLOCK + 8, 2, 1, 128, BF, None),
    "head_width_off_the_lanes": (D.QUERY_BLOCK, 2, 1, 64, BF, None),
}


@pytest.mark.parametrize("op", [_attention, _mixer], ids=["op", "mixer"])
@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_each_rung_takes_the_composition_and_is_counted_xla(rung, op,
                                                            counted):
    length, heads, kv, d, dtype, scope = RUNGS[rung]
    q, k, v, _ = qkv(1, length, heads, kv, d, dtype=dtype)
    if scope is None:
        assert not P.causal_gqa_available(q, k, v, D.QUERY_BLOCK)
        calls = _pallas_calls(op, q, k, v)
    else:
        with scope():
            assert not P.causal_gqa_available(q, k, v, D.QUERY_BLOCK)
            calls = _pallas_calls(op, q, k, v)
    assert calls == 0
    assert counted() == {"pallas": 0, "xla": 1}


@pytest.mark.parametrize("op", [_attention, _mixer], ids=["op", "mixer"])
def test_an_eligible_call_takes_the_kernel_and_is_counted_pallas(op, counted):
    q, k, v, _ = qkv(2, D.QUERY_BLOCK, 2, 1)
    assert P.causal_gqa_available(q, k, v, D.QUERY_BLOCK)
    assert _pallas_calls(op, q, k, v) == 2      # forward, backward
    assert counted() == {"pallas": 1, "xla": 0}


def test_the_op_on_the_kernel_path_gives_the_composition_s_values():
    q, k, v, cot = qkv(3, 2 * D.QUERY_BLOCK, 2, 1)
    near(value_and_grads(_attention, q, k, v, cot=cot),
         value_and_grads(lambda *a: D._causal_gqa(*a, D.QUERY_BLOCK),
                         q, k, v, cot=cot), 2e-2)


def test_a_length_whose_keys_do_not_fit_vmem_takes_the_composition():
    shape = lambda heads: jax.ShapeDtypeStruct((1, 1 << 16, heads, 128), BF)
    assert not P.causal_gqa_available(shape(32), shape(2), shape(2),
                                      D.QUERY_BLOCK)
    shape = lambda heads: jax.ShapeDtypeStruct((1, 1 << 13, heads, 128), BF)
    assert P.causal_gqa_available(shape(32), shape(2), shape(2),
                                  D.QUERY_BLOCK)


# ---------------------------------------------------------------------------
# the sliding window: only the band's key tiles are visited
# ---------------------------------------------------------------------------
WINDOW_COUNTER = "mx_attn_window_path_total"


@pytest.mark.parametrize("length, tile, window", [
    (512, 128, 200),    # the band's edge inside a tile: two tiles masked
    (512, 128, 256),    # on a tile boundary: one tile masked, one whole
    (384, 128, 100),    # narrower than a tile: the diagonal tile banded too
    (256, 128, 256),    # the length: every key seen, the causal program
    (256, 128, 1000)],
    ids=["inside", "boundary", "narrow", "length", "beyond"])
def test_windowed_kernel_matches_a_whole_mask_and_the_composition(
        length, tile, window):
    q, k, v, cot = qkv(length + window, length, 2, 1)
    got = value_and_grads(
        lambda *a: P.flash_causal_gqa(*a, tile, window), q, k, v, cot=cot)
    near(got, value_and_grads(
        lambda *a: D._causal_gqa(*a, tile, window), q, k, v, cot=cot), 2e-2)
    near(got, value_and_grads(
        lambda *a: window_ref(*a, window),
        *(t.astype(F32) for t in (q, k, v)), cot=cot), 2e-2)


@pytest.mark.parametrize("t", [130, 255, 256, 383])
def test_a_key_before_the_band_never_reaches_output_t(t):
    """Keys at or before ``t - window`` do not move row ``t``; the
    band's first key does."""
    window = 130
    q, k, v, _ = qkv(6, 384, 4, 2)
    kernel = jax.jit(lambda *a: P.flash_causal_gqa(*a, 128, window))
    out = kernel(q, k, v)
    before = (jnp.arange(384) <= t - window)[None, :, None, None]
    moved = kernel(q, jnp.where(before, k + 3, k),
                   jnp.where(before, v - 2, v))
    np.testing.assert_array_equal(np.asarray(out[:, t:], F32),
                                  np.asarray(moved[:, t:], F32))
    first = (jnp.arange(384) == t - window + 1)[None, :, None, None]
    moved = kernel(q, k, jnp.where(first, v - 2, v))
    assert not np.array_equal(np.asarray(out[:, t], F32),
                              np.asarray(moved[:, t], F32))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_without_a_window_the_kernel_s_program_is_the_causal_one():
    """``window=None``, and a window no shorter than the length, trace
    the kernels they traced before the argument existed: one loop from
    tile 0 and the diagonal tile, no ``cond`` for a band's tile."""
    q, k, v, cot = qkv(7, 384, 2, 1)

    def grad_text(*window):
        fn = lambda *a: jnp.sum(P.flash_causal_gqa(*a, 128, *window)
                                .astype(F32))
        return str(jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(q, k, v))

    plain = grad_text()
    assert plain == grad_text(None) == grad_text(None, None)
    assert plain == grad_text(384) == grad_text(5000)
    banded = grad_text(130)
    assert banded != plain
    # beside the two that open and close a group's dk / dv: one for
    # each tile that the band's far edge crosses, forward and backward
    assert banded.count("cond[") == plain.count("cond[") + 4


def test_a_windowed_call_is_counted_in_a_series_of_its_own(counted):
    was = {p: telemetry.counter(WINDOW_COUNTER, path=p).get()
           for p in ("pallas", "xla")}
    q, k, v, _ = qkv(8, D.QUERY_BLOCK, 2, 1)
    assert _pallas_calls(lambda *a: D._attend(*a, window=100), q, k, v) == 2
    assert _pallas_calls(lambda *a: D._attend(
        *(t.astype(F32) for t in a), window=100), q, k, v) == 0
    now = {p: telemetry.counter(WINDOW_COUNTER, path=p).get() - n
           for p, n in was.items()}
    assert now == {"pallas": 1, "xla": 1}
    assert counted() == {"pallas": 0, "xla": 0}     # full-causal calls only


def test_the_scope_follows_the_kind_forward_and_backward():
    q, k, v, _ = qkv(9, D.QUERY_BLOCK, 2, 1)

    def text(**kw):
        fn = lambda *a: jnp.sum(D._attend(*a, **kw).astype(F32))
        return jax.jit(jax.grad(fn, (0, 1, 2))).lower(q, k, v).as_text(
            debug_info=True)

    windowed, causal = text(window=100), text()
    assert "mx.attn.window" in windowed and "mx.attn.causal" not in windowed
    assert "mx.attn.causal" in causal and "mx.attn.window" not in causal
