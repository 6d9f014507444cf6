"""What Mellum 2 added to the decoder's ops (ops/decoder_ops.py), at toy
widths on the CPU: sliding-window attention by the blocked composition
against a whole-mask float32 reference (the band's edge inside a block,
on a block boundary, narrower than a block, no shorter than the
length), YaRN's angles against the written-out rule of the benchmark's
reference (mxbench/reference/mellum2_12b_a2_5b.py), the rotary
attention mixer of both kinds against that reference, what the traced
programs of the older callers keep, and the expert product over a
buffer of many blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import decoder_ops as D, get_op
from numerics import (attention_ref, close, highest, jitted,  # noqa: F401
                      rand, reference, remat_count, same_values_and_grads,
                      swiglu_experts, value_and_grads, window_ref)

KREF = reference("keye_vl2_30b_a3b")
MREF = reference("mellum2_12b_a2_5b")
pytestmark = pytest.mark.usefixtures("highest")
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


@pytest.mark.parametrize("length, block, window", [
    (40, 8, 11),        # the band's edge inside a block
    (40, 8, 16),        # on a block boundary
    (40, 8, 3),         # narrower than a block
    (21, 8, 21),        # the length, and beyond it: every key seen
    (21, 8, 64),
    (7, 16, 4)],
    ids=["inside", "boundary", "narrow", "length", "beyond", "one_block"])
def test_windowed_composition_against_a_whole_mask(length, block, window):
    q, k, v = rand(40, (2, length, 4, 8), (2, length, 2, 8),
                   (2, length, 2, 8))
    same_values_and_grads(lambda *a: D._causal_gqa(*a, block, window),
                          lambda *a: window_ref(*a, window), (q, k, v))
    if window >= length:
        close(jax.jit(lambda *a: D._causal_gqa(*a, block, window))(q, k, v),
              jitted(attention_ref)(q, k, v))


def test_a_window_leaves_the_keys_before_the_band_alone():
    """At 64 positions in blocks of 16 under a window of 8 no score
    block is wider than a block and its band, 16 + 7 keys."""
    q, k, v = rand(41, (1, 64, 2, 4), (1, 64, 1, 4), (1, 64, 1, 4))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(D._causal_gqa(*a, 16, 8)), (0, 1, 2)))(q, k, v)

    def walk(jp):
        for eqn in jp.eqns:
            for var in eqn.outvars:
                yield var.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    widths = {s[-1] for s in walk(jaxpr.jaxpr)
              if len(s) == 5 and s[-2] == 16 and s[-1] >= 16}
    assert widths == {16, 23}


def test_without_a_window_the_traced_program_is_the_one_before():
    """``window=None`` adds nothing to what is traced: the composition,
    the op and the NoPE mixer give the jaxpr they gave without the
    argument, and it holds no trace of a band."""
    q, k, v = rand(42, (1, 32, 4, 8), (1, 32, 2, 8), (1, 32, 2, 8))
    plain = str(jax.make_jaxpr(lambda *a: D._causal_gqa(*a, 8))(q, k, v))
    assert plain == str(jax.make_jaxpr(
        lambda *a: D._causal_gqa(*a, 8, None))(q, k, v))
    assert plain != str(jax.make_jaxpr(
        lambda *a: D._causal_gqa(*a, 8, 5))(q, k, v))
    attend = str(jax.make_jaxpr(D._attend)(q, k, v))
    assert attend == str(jax.make_jaxpr(
        lambda *a: D._attend(*a, window=None, keep=None))(q, k, v))
    assert "mx.attn.window" not in str(jitted(D._attend).lower(q, k, v)
                                       .as_text(debug_info=True))


def test_yarn_low_and_high_at_the_published_sizes():
    """c(32) = 18.08 and c(1) = 34.98 at head_dim 128, theta 5e5 and an
    original length of 8,192: pairs up to 18 keep their frequency,
    pairs from 35 on are slowed 16 times, linear between."""
    ramp = np.asarray(D._yarn_ramp(64, 5e5, 16.0, 8192.0, 32.0, 1.0))
    assert (ramp[:19] == 0).all() and ramp[19] > 0
    assert (ramp[35:] == 1).all() and ramp[34] < 1
    np.testing.assert_allclose(ramp[18:36], np.arange(18) / 17.0, rtol=1e-6)
    pos = jnp.arange(3)[None]
    angles = np.asarray(D._rotary_angles(pos, 64, 5e5,
                                         yarn=(16.0, 8192.0, 32.0, 1.0)))
    f = 5e5 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(angles[0, 1, :19], f[:19], rtol=1e-6)
    np.testing.assert_allclose(angles[0, 1, 35:], f[35:] / 16, rtol=1e-6)
    g = (26 - 18) / 17.0
    np.testing.assert_allclose(angles[0, 2, 26],
                               2 * (f[26] * (1 - g) + f[26] / 16 * g),
                               rtol=1e-6)


@pytest.mark.parametrize("original", [8192, 64])
def test_yarn_rotary_against_the_written_out_rule(original):
    """The op against the reference's table, written out from the
    configuration file's equations: the angles, and the attention
    factor on cos and sin."""
    rope = dict(YARN, original_max_position_embeddings=original)
    (x,) = rand(43, (2, 40, 3, 128))
    op = get_op("_contrib_rotary").impl
    attrs = dict(theta=5e5, yarn=(16, original, 32, 1))
    want = MREF.rotate(x, MREF.rope_table(rope, 128, 40))
    same_values_and_grads(
        lambda x: op(x, attention_factor=rope["attention_factor"], **attrs),
        lambda x: MREF.rotate(x, MREF.rope_table(rope, 128, 40)), (x,),
        tol=1e-4)
    # the factor is on cos and sin: a rotation times it
    unscaled = jax.jit(lambda x: op(x, **attrs))(x)
    close(unscaled * rope["attention_factor"], want, tol=1e-4)
    # and YaRN is not plain rotary at these positions
    assert float(jnp.max(jnp.abs(
        unscaled - jax.jit(lambda x: op(x, theta=5e5))(x)))) > 0.1


def test_plain_rotary_is_unchanged_by_the_new_arguments():
    (x,) = rand(44, (2, 9, 3, 16))
    op = get_op("_contrib_rotary").impl
    plain = jax.make_jaxpr(lambda x: op(x, theta=5e5))(x)
    assert str(plain) == str(jax.make_jaxpr(
        lambda x: op(x, theta=5e5, yarn=(), attention_factor=1.0))(x))
    close(op(x, theta=5e5),
          MREF.rotate(x, MREF.rope_table({"rope_theta": 5e5}, 16, 9)))


def _rotary_mixer_args(seed, hidden=24, heads=4, kv=2, d=8, length=21):
    x, norm_w, qw, kw, vw, ow, qn, kn = rand(
        seed, (2, length, hidden), (hidden,), (heads * d, hidden),
        (kv * d, hidden), (kv * d, hidden), (hidden, heads * d), (d,), (d,),
        scale=0.3)
    return (x, 1 + norm_w, qw, kw, vw, ow, 1 + qn, 1 + kn)


def _rotary_mixer_ref(args, kind, window, rope):
    x, norm_w, qw, kw, vw, ow, qn, kn = args
    w = {"q_weight": qw, "k_weight": kw, "v_weight": vw, "o_weight": ow,
         "q_norm_weight": qn, "k_norm_weight": kn}
    cfg = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=8,
               rms_norm_eps=1e-6, sliding_window=window,
               rope_parameters={kind: rope})
    return MREF.attention(w, "", MREF._rms(x, norm_w, 1e-6), kind, cfg)


@pytest.mark.parametrize("kind, window, rope", [
    ("sliding_attention", 6, {"rope_theta": 500000}),
    ("full_attention", 0, dict(YARN, original_max_position_embeddings=8))],
    ids=["sliding", "full_yarn"])
def test_rotary_gqa_mixer_against_the_reference(kind, window, rope):
    args = _rotary_mixer_args(45)
    attrs = dict(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=5e5,
                 window=window, eps=1e-6)
    if rope.get("rope_type") == "yarn":
        attrs.update(rope_yarn=(16, 8, 32, 1),
                     attention_factor=rope["attention_factor"])
    op = get_op("_contrib_rotary_gqa_mixer").impl
    same_values_and_grads(lambda *a: op(*a, **attrs),
                          lambda *a: _rotary_mixer_ref(a, kind, window, rope),
                          args, tol=1e-4)


def test_the_rotary_mixer_keeps_v_and_its_context_only(capsys):
    """Beside its arguments the mixer's checkpoint keeps the v
    projection's output and the context: no q, no k, normed or turned
    or neither, no score block."""
    args = _rotary_mixer_args(46)
    op = get_op("_contrib_rotary_gqa_mixer").impl

    def fn(*a):
        return jnp.sum(op(*a, num_heads=4, num_kv_heads=2, head_dim=8,
                          rope_theta=5e5, window=6))

    assert remat_count(jax.grad(fn), *args) > 0
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    kept = [line.split(" ")[0] for line in capsys.readouterr().out
            .splitlines() if "from the argument" not in line
            and "from a constant" not in line]
    assert kept == ["f32[2,21,16]", "f32[2,21,4,8]"]


@pytest.mark.parametrize("at_once, a_chunk, chunk", [(3, 3, 2), (20, 20, 17),
                                                     (34, 24, 0)])
def test_a_buffer_of_many_blocks_is_multiplied_in_chunks(monkeypatch, at_once,
                                                         a_chunk, chunk):
    """Beyond ``BLOCKS_AT_ONCE`` blocks the expert product is a loop
    over chunks of ``BLOCKS_A_CHUNK`` (a divisor of the buffer's blocks
    at most that): the reference's numbers and gradients either way,
    and up to it the program traced before the loop existed."""
    monkeypatch.setattr(D, "BLOCKS_AT_ONCE", at_once)
    monkeypatch.setattr(D, "BLOCKS_A_CHUNK", a_chunk)
    w, cfg = swiglu_experts(50)
    (x,) = rand(51, (2, 40, 12))
    names = sorted(w)

    def fn(x, *ws):     # 34 blocks of 8 rows: a buffer no routing overfills
        y, _ = D._moe_experts(
            x.reshape(-1, 12), ws[2], None, ws[1], ws[0], top_k=3, offset=4,
            scale=1.0, norm_topk=True, score_func="softmax",
            activation="swiglu", capacity_factor=100.0, block_rows=8)
        return y.reshape(x.shape)

    def ref(x, *ws):
        return KREF.experts(dict(zip(names, ws)), "", x, cfg)

    args = (x,) + tuple(w[n] for n in names)
    text = str(jax.make_jaxpr(fn)(*args))
    assert "i32[34]" in text            # the blocks' experts
    assert text.count("scan[") == (1 if chunk else 0)
    if chunk:
        assert "f32[%d,%d,8,12]" % (34 // chunk, chunk) in text
    (cot,) = rand(52, x.shape)
    got = value_and_grads(fn, *args, cot=cot)
    want = value_and_grads(ref, *args, cot=cot)
    close(got[0], want[0])
    close(got[1:], want[1:], 5e-5)
