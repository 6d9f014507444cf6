"""The Mamba-2 scan's kernels (ops/pallas_ssd.py) in interpret mode on
the CPU: values and all six gradients against the step-by-step
recurrence of the benchmark's plain reference and against the
composition they stand in for (``decoder_ops._ssd``), in float32 (the
mathematics, to the tolerance tests/test_decoder_ops.py holds the
composition to) and in bf16 (what the predicate admits, to the tolerance
a bf16 path is held to there); a tail that is no whole chunk; the ladder
by which ``decoder_ops._scan`` picks a form, each rung counted in
``mx_mamba2_ssd_path_total``. What Mosaic makes of the kernels at the
published widths is tests/test_chip_compile_*.py's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mxnet_tpu import telemetry
from mxnet_tpu.ops import decoder_ops as D, get_op, pallas_ssd as P
from mxnet_tpu.ops.pallas_common import auto_partitioned
from numerics import (BF, F32, close, jitted, near, normal, reference,
                      value_and_grads)

REF = reference("nemotron_twotower_30b_a3b")
COUNTER = "mx_mamba2_ssd_path_total"
CHUNK = 128
# two chunks, and three with a tail of 37 that is padded with dt = 0
LENGTHS = [256, 384 + 37]


def _args(seed, length, batch=2, heads=8, p=64, groups=2, n=128, dtype=BF):
    """(x, dt, a, B, C, d, a cotangent of y): x / B / C in ``dtype``,
    the rest float32, as ``_mamba2`` hands them over."""
    keys = jax.random.split(jax.random.key(seed), 7)
    x, cot = (normal(k, (batch, length, heads, p), dtype) for k in keys[:2])
    dt = jax.nn.softplus(normal(keys[2], (batch, length, heads)) - 2.0)
    a = -jnp.exp(normal(keys[3], (heads,)))
    bm, cm = (normal(k, (batch, length, groups, n), dtype, 0.3)
              for k in keys[4:6])
    return [x, dt, a, bm, cm, normal(keys[6], (heads,)), cot]


def _kernels(*a):
    return P.ssd_scan(*a, CHUNK)


def _composition(*a):
    return D._ssd(*a, CHUNK)


@pytest.mark.parametrize("length", LENGTHS)
def test_float32_kernels_are_the_step_by_step_recurrence(length):
    """The mathematics alone (interpreted float32 products are exact):
    the kernels' forward and their hand-written backward against the
    recurrence and the composition, all six gradients."""
    *args, cot = _args(length, length, dtype=F32)
    # [y, dx, d dt, da, dB, dC, dd]
    got = value_and_grads(_kernels, *args, cot=cot)
    for want in (value_and_grads(REF.recurrence, *args, cot=cot),
                 value_and_grads(_composition, *args, cot=cot)):
        near(got, want, 5e-5)
        close(got[0], want[0], 5e-5)


@pytest.mark.parametrize("heads, p, groups, chunk", [
    (4, 128, 2, 128), (8, 32, 1, 128), (8, 64, 2, 256)],
    ids=["a_head_a_lane_tile", "four_heads_a_lane_tile", "chunk_256"])
def test_other_widths_the_predicate_admits(heads, p, groups, chunk):
    """A head of a whole lane tile (a window is one head), four heads
    of 32 lanes a window, and a chunk of two lane tiles: the same
    numbers as the composition, in float32."""
    *args, cot = _args(heads + p, 2 * chunk, 1, heads, p, groups, dtype=F32)
    bf = [t.astype(BF) for t in args]
    assert P.ssd_available(bf[0], bf[3], bf[4], chunk)
    near(value_and_grads(lambda *a: P.ssd_scan(*a, chunk), *args, cot=cot),
         value_and_grads(lambda *a: D._ssd(*a, chunk), *args, cot=cot), 5e-5)


@pytest.mark.parametrize("length", LENGTHS)
def test_bfloat16_kernels_stay_near_float32_and_the_composition(length):
    """What the predicate admits: bf16 x / B / C. Against the float32
    recurrence on the same rounded values, and against the composition
    on the same bf16 inputs (two roundings of one sum)."""
    *args, cot = _args(length + 1, length)
    assert P.ssd_available(args[0], args[3], args[4], CHUNK)
    got = value_and_grads(_kernels, *args, cot=cot)
    assert got[0].shape == args[0].shape
    exact = value_and_grads(REF.recurrence, *(t.astype(F32) for t in args),
                            cot=cot)
    composed = value_and_grads(_composition, *args, cot=cot)
    for want in (exact, composed):
        near(got[:1], want[:1], 2e-2)
        near(got[1:], want[1:], 3e-2)
    # no further from float32 than the composition is, by the norm
    for g, c, w in zip(got, composed, exact):
        assert jnp.linalg.norm(g - w) <= 2 * jnp.linalg.norm(c - w) \
            + 1e-3 * jnp.linalg.norm(w)


def test_a_step_after_position_t_never_reaches_output_t():
    x, dt, a, bm, cm, d, _ = _args(3, 384)
    t = 200
    later = (jnp.arange(384) > t)[None, :, None, None]
    kernels = jitted(_kernels)
    out = kernels(x, dt, a, bm, cm, d)
    moved = kernels(jnp.where(later, x + 3, x), dt, a,
                    jnp.where(later, bm - 2, bm), cm, d)
    np.testing.assert_array_equal(np.asarray(out[:, :t + 1], F32),
                                  np.asarray(moved[:, :t + 1], F32))
    assert not np.array_equal(np.asarray(out[:, t + 1:], F32),
                              np.asarray(moved[:, t + 1:], F32))


def _kernel_names(fn, *args, grad=True):
    """The names of the Pallas calls in ``fn``'s (gradient's) trace."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    if grad:
        fn = jax.grad(lambda *a, f=fn: jnp.sum(f(*a).astype(F32)),
                      tuple(range(len(args))))
    return sorted(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def test_only_a_differentiated_call_writes_the_entering_states():
    args = _args(4, 256)[:6]
    assert _kernel_names(_kernels, *args, grad=False) == ["pallas_ssd_fwd"]
    assert _kernel_names(_kernels, *args) == ["pallas_ssd_bwd",
                                              "pallas_ssd_fwd_states"]


# ---------------------------------------------------------------------------
# which form a call takes, through the registered ops
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


def _scan_op(x, dt, a, bm, cm, d):
    return get_op("_contrib_ssd_scan").impl(x, dt, a, bm, cm, d,
                                            chunk_size=CHUNK)


def _mixer(x, dt, a, bm, cm, d):
    """The mixer op at the widths of x / B / C (weights of ones: only
    the path is looked at)."""
    b, length, heads, p = x.shape
    groups, n = bm.shape[2:]
    hidden, inner, conv = 16, heads * p, heads * p + 2 * groups * n
    ones = lambda *shape: jnp.ones(shape, x.dtype)
    return get_op("_contrib_mamba2_mixer").impl(
        ones(b, length, hidden) * jnp.mean(x), ones(hidden),
        ones(inner + conv + heads, hidden) / hidden, ones(conv, 4),
        ones(conv), dt[0, 0], jnp.log(-a), d, ones(inner),
        ones(hidden, inner), num_heads=heads, head_dim=p, n_groups=groups,
        state_size=n, chunk_size=CHUNK)


def _four_devices():
    return auto_partitioned(Mesh(np.array(jax.devices()[:4]), ("dp",)))


RUNGS = {
    # name: (heads, head width, groups, state, dtype, scope to trace in)
    "float32_inputs": (8, 64, 2, 128, F32, None),
    "head_width_32_leaves_half_a_lane_tile_a_group": (4, 32, 2, 128, BF, None),
    "state_64": (8, 64, 2, 64, BF, None),
    "four_device_mesh": (8, 64, 2, 128, BF, _four_devices),
}


@pytest.mark.parametrize("op", [_scan_op, _mixer], ids=["op", "mixer"])
@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_each_rung_takes_the_composition_and_is_counted_xla(rung, op,
                                                            counted):
    heads, p, groups, n, dtype, scope = RUNGS[rung]
    args = _args(1, 256, 1, heads, p, groups, n, dtype)[:6]

    def look():
        assert not P.ssd_available(args[0], args[3], args[4], CHUNK)
        return _kernel_names(op, *args)

    if scope is None:
        names = look()
    else:
        with scope():
            names = look()
    assert names == []
    assert counted() == {"pallas": 0, "xla": 1}


@pytest.mark.parametrize("op, names", [
    (_scan_op, ["pallas_ssd_bwd", "pallas_ssd_fwd_states"]),
    # the mixer is recomputed whole: its forward, then the rule's pair
    (_mixer, ["pallas_ssd_bwd", "pallas_ssd_fwd", "pallas_ssd_fwd_states"]),
], ids=["op", "mixer"])
def test_an_eligible_call_takes_the_kernels_and_is_counted_pallas(
        op, names, counted):
    args = _args(2, 256, batch=1)[:6]
    assert P.ssd_available(args[0], args[3], args[4], CHUNK)
    assert _kernel_names(op, *args) == names
    assert counted() == {"pallas": 1, "xla": 0}


def test_the_op_on_the_kernel_path_gives_the_composition_s_values():
    *args, cot = _args(5, 384 + 37)
    got, want = (value_and_grads(fn, *args, cot=cot)
                 for fn in (_scan_op, _composition))
    near(got[:1], want[:1], 2e-2)
    near(got[1:], want[1:], 3e-2)
