"""The hybrid decoder's ops (ops/decoder_ops.py) against the plain
float32 reference of the benchmark's Nemotron-H configuration
(mxbench/reference/nemotron_twotower_30b_a3b.py), at toy widths on the
CPU: forward and gradients, the chunked scan against the step-by-step
recurrence at lengths that are and are not multiples of the chunk,
routing at its extremes, the expert-parallel share, recomputation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxbench import manifest
from mxnet_tpu.ops import decoder_ops as D, get_op

REF = manifest.load_module("reference", "nemotron_twotower_30b_a3b.py")
F32 = jnp.float32


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _rand(seed, *shapes, scale=1.0):
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return [scale * jax.random.normal(k, s, F32) for k, s in zip(keys, shapes)]


def _close(got, want, tol=2e-5):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol,
                                   atol=tol)


def _same_values_and_grads(fn, ref, args, tol=2e-5):
    _close(fn(*args), ref(*args), tol)
    cot = _rand(99, jnp.shape(ref(*args)))[0]
    argnums = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.sum(fn(*a) * cot), argnums)(*args)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * cot), argnums)(*args)
    _close(got, want, tol)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(3, 16), (2, 5, 24)])
def test_rms_norm(shape):
    x, w = _rand(0, shape, shape[-1:])
    op = get_op("_contrib_rms_norm").impl
    _same_values_and_grads(lambda x, w: op(x, w, eps=1e-5),
                           lambda x, w: REF._rms(x, w, 1e-5), (x, w))


def test_rms_norm_keeps_the_dtype_and_norms_in_float32():
    x = (100 * _rand(1, (4, 64))[0]).astype(jnp.bfloat16)
    y = get_op("_contrib_rms_norm").impl(x, jnp.ones((64,), jnp.bfloat16))
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(jnp.mean(jnp.square(y.astype(F32)), -1)), 1.0, rtol=2e-2)


@pytest.mark.parametrize("group", [8, 16, 32])
def test_gated_rms_norm(group):
    y, z, w = _rand(2, (2, 7, 32), (2, 7, 32), (32,))

    def ref(y, z, w):
        g = (y * jax.nn.silu(z)).reshape(2, 7, 32 // group, group)
        return REF._rms(g, 1.0, 1e-5).reshape(2, 7, 32) * w

    op = get_op("_contrib_gated_rms_norm").impl
    _same_values_and_grads(
        lambda y, z, w: op(y, z, w, group_size=group, eps=1e-5), ref,
        (y, z, w))


@pytest.mark.parametrize("length, k", [(9, 4), (3, 4), (12, 2)])
def test_causal_conv1d(length, k):
    x, w, b = _rand(3, (2, length, 6), (6, k), (6,))
    _same_values_and_grads(get_op("_contrib_causal_conv1d").impl, REF._conv,
                           (x, w, b))
    # causal: an input after t never reaches y[t]
    y0 = D._causal_conv1d(x, w, b)
    y1 = D._causal_conv1d(x.at[:, -1].add(5.0), w, b)
    _close(y0[:, :-1], y1[:, :-1], 0)


# ---------------------------------------------------------------------------
def _ssd_args(seed, length, batch=2, heads=4, p=8, groups=2, n=16):
    x, dt, a, bm, cm, d = _rand(
        seed, (batch, length, heads, p), (batch, length, heads), (heads,),
        (batch, length, groups, n), (batch, length, groups, n), (heads,))
    return x, jax.nn.softplus(dt - 2.0), -jnp.exp(a), bm, cm, d


@pytest.mark.parametrize("length", [16, 24, 21, 5, 1])
def test_chunked_scan_is_the_step_by_step_recurrence(length):
    """Chunk 8: lengths that are multiples of it, that are not (the
    tail is padded with dt = 0), and shorter than one chunk."""
    op = get_op("_contrib_ssd_scan").impl
    _same_values_and_grads(lambda *a: op(*a, chunk_size=8), REF.recurrence,
                           _ssd_args(4, length), tol=5e-5)


def test_scan_does_not_depend_on_the_chunk():
    args = _ssd_args(5, 24)
    y4, y8, y24 = (D._ssd(*args, c) for c in (4, 8, 24))
    _close(y4, y8)
    _close(y8, y24)


def test_scan_without_its_skip_term_is_another_function():
    args = _ssd_args(6, 16)
    y = D._ssd(*args, 8)
    no_d = D._ssd(*args[:5], jnp.zeros_like(args[5]), 8)
    assert float(jnp.max(jnp.abs(y - no_d))) > 0.1


def test_the_reference_recurrence_keeps_states_by_segment():
    """Lengths over SEGMENT that it divides take the nested scan:
    the same numbers."""
    args = _ssd_args(7, 2 * REF.SEGMENT, batch=1, heads=2, p=4, n=4)
    _close(REF.recurrence(*args), D._ssd(*args, 16), 5e-5)


# ---------------------------------------------------------------------------
def _attention_ref(q, k, v):
    heads, kv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    seen = jnp.tril(jnp.ones(s.shape[-2:], bool))
    att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", att, v)


@pytest.mark.parametrize("length, block", [(16, 4), (21, 8), (7, 16), (8, 8)])
def test_blocked_causal_gqa_attention(length, block):
    q, k, v = _rand(8, (2, length, 4, 8), (2, length, 2, 8),
                    (2, length, 2, 8))
    _same_values_and_grads(lambda *a: D._causal_gqa(*a, block),
                           _attention_ref, (q, k, v))
    # the op itself, at its own block size
    _close(get_op("_contrib_causal_gqa_attention").impl(q, k, v),
           _attention_ref(q, k, v))


def test_attention_never_builds_a_length_by_length_array():
    """At 64 positions in blocks of 16 the largest score array is
    16 x 64, not 64 x 64."""
    q, k, v = _rand(9, (1, 64, 2, 4), (1, 64, 1, 4), (1, 64, 1, 4))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(D._causal_gqa(*a, 16)), (0, 1, 2)))(q, k, v)
    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield v.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    sizes = list(walk(jaxpr.jaxpr))
    assert not [s for s in sizes if s[-2:] == (64, 64)]
    assert [s for s in sizes if s[-2:] == (16, 64)]


# ---------------------------------------------------------------------------
CFG = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
       "norm_topk_prob": True}


def _moe_weights(seed, hidden=12, routed=16, held=4, width=10, offset=4):
    r, b, up, down = _rand(seed, (routed, hidden), (routed,),
                           (held, width, hidden), (held, hidden, width))
    return {"router_weight": r, "e_score_correction_bias": 0.1 * b,
            "experts_up_weight": up, "experts_down_weight": down}, \
        dict(CFG, expert_offset=offset)


def _moe(x, w, cfg, capacity_factor=None):
    """The op; with a ``capacity_factor``, what the op runs with another
    buffer than its own (``D.CAPACITY_FACTOR``)."""
    if capacity_factor is None:
        return get_op("_contrib_moe_experts").impl(
            x, w["router_weight"], w["e_score_correction_bias"],
            jnp.zeros((2, w["experts_up_weight"].shape[0]), F32),
            w["experts_up_weight"], w["experts_down_weight"],
            top_k=cfg["num_experts_per_tok"],
            expert_offset=cfg["expert_offset"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"])
    y, rows = D._moe_experts(
        x.reshape(-1, x.shape[-1]), w["router_weight"],
        w["e_score_correction_bias"], w["experts_up_weight"],
        w["experts_down_weight"], top_k=cfg["num_experts_per_tok"],
        offset=cfg["expert_offset"], scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"], capacity_factor=capacity_factor)
    return y.reshape(x.shape), rows


@pytest.mark.parametrize("capacity_factor", [0.25, None, 100.0])
def test_routed_experts(capacity_factor):
    """Buffers too small for the routing (the dense path), the
    default, and buffers no routing can overfill: the same numbers and
    gradients as the reference's loop over the held experts."""
    w, cfg = _moe_weights(10)
    (x,) = _rand(11, (2, 20, 12))
    names = sorted(w)

    def fn(x, *ws):
        return _moe(x, dict(zip(names, ws)), cfg, capacity_factor)[0]

    def ref(x, *ws):
        return REF.experts(dict(zip(names, ws)), "", x, cfg, shared=False)

    args = (x,) + tuple(w[n] for n in names)
    _close(fn(*args), ref(*args))
    cot = _rand(12, x.shape)[0]
    nums = (0,) + tuple(1 + i for i, n in enumerate(names)
                        if n != "e_score_correction_bias")
    _close(jax.grad(lambda *a: jnp.sum(fn(*a) * cot), nums)(*args),
           jax.grad(lambda *a: jnp.sum(ref(*a) * cot), nums)(*args), 5e-5)


@pytest.mark.parametrize("capacity_factor", [0.25, None])
@pytest.mark.parametrize("favoured, rows", [
    ((4, 0, 1), [40, 0, 0, 0]),       # every token to one held expert
    ((0, 1, 2), [0, 0, 0, 0]),        # none to any
    ((4, 5, 6), [40, 40, 40, 0]),     # every choice held
])
def test_routing_at_its_extremes_drops_nothing(favoured, rows,
                                               capacity_factor):
    """A bias that decides the top-k outright: exact, counted, and every
    routed row computed, whatever the buffers hold."""
    w, cfg = _moe_weights(13)
    w["e_score_correction_bias"] = jnp.zeros((16,)).at[jnp.array(favoured)] \
        .set(10.0)
    (x,) = _rand(14, (40, 12))
    y, counts = _moe(x, w, cfg, capacity_factor)
    _close(y, REF.experts(w, "", x, cfg, shared=False))
    np.testing.assert_array_equal(np.asarray(counts[0]), rows)
    np.testing.assert_array_equal(np.asarray(counts[1]), rows)
    if not any(rows):
        assert float(jnp.max(jnp.abs(y))) == 0.0


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in shares of 4: the four shares' routed parts plus
    the shared expert once are the layer with all 16 held."""
    w, cfg = _moe_weights(15, held=16, offset=0)
    shared_up, shared_down, x = _rand(16, (14, 12), (12, 14), (30, 12))
    whole = dict(w, shared_up_weight=shared_up, shared_down_weight=shared_down)
    want = REF.experts(whole, "", x, cfg)
    got = REF._relu2_mlp(x, shared_up, shared_down)
    counts = []
    for offset in (0, 4, 8, 12):
        share = dict(w, experts_up_weight=w["experts_up_weight"]
                     [offset:offset + 4], experts_down_weight=w[
                         "experts_down_weight"][offset:offset + 4])
        part, rows = _moe(x, share, dict(cfg, expert_offset=offset))
        _close(part, REF.experts(share, "", x, dict(cfg, expert_offset=offset),
                                 shared=False))
        got = got + part
        counts.append(np.asarray(rows[0]))
    _close(got, want)
    assert int(np.sum(counts)) == 30 * 3        # every choice held once


def test_a_wrong_scaling_factor_is_seen():
    w, cfg = _moe_weights(17)
    (x,) = _rand(18, (20, 12))
    y = _moe(x, w, cfg)[0]
    off = _moe(x, w, dict(cfg, routed_scaling_factor=1.0))[0]
    _close(y, 2.5 * off)
    assert float(jnp.max(jnp.abs(y - off))) > 1e-2


# ---------------------------------------------------------------------------
def _remat_count(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return text.count("checkpoint") + text.count("remat")


def test_the_mamba2_mixer_recomputes_its_inside():
    hidden, heads, p, groups, n, k = 16, 4, 4, 2, 8, 4
    inner, conv = heads * p, heads * p + 2 * groups * n
    u, nw, inw, cw, cb, dtb, al, d, gw, ow = _rand(
        19, (2, 12, hidden), (hidden,), (inner + conv + heads, hidden),
        (conv, k), (conv,), (heads,), (heads,), (heads,), (inner,),
        (hidden, inner), scale=0.3)
    args = (u, nw, inw, cw, cb, dtb, al, d, gw, ow)
    attrs = dict(num_heads=heads, head_dim=p, n_groups=groups, state_size=n,
                 chunk_size=4, eps=1e-5)
    op = get_op("_contrib_mamba2_mixer").impl
    plain = lambda *a: D._mamba2(*a, heads=heads, head_dim=p, groups=groups,
                                 state=n, chunk=4, eps=1e-5)
    _same_values_and_grads(lambda *a: op(*a, **attrs), plain, args, tol=5e-5)
    w = {"in_proj_weight": inw, "conv_weight": cw, "conv_bias": cb,
         "dt_bias": dtb, "a_log": al, "d": d, "gate_norm_weight": gw,
         "out_proj_weight": ow}
    cfg = {"mamba_num_heads": heads, "mamba_head_dim": p, "n_groups": groups,
           "ssm_state_size": n, "layer_norm_epsilon": 1e-5}
    _close(op(*args, **attrs),
           REF.mamba2(w, "", REF._rms(u, nw, 1e-5), cfg), 5e-5)
    grad = jax.grad(lambda *a: jnp.sum(op(*a, **attrs)))
    assert _remat_count(grad, *args) > 0
    assert _remat_count(jax.grad(lambda *a: jnp.sum(plain(*a))), *args) == 0


def test_mixers_take_bfloat16_and_stay_near_float32():
    """What ShardedTrainStep feeds them: bf16 in, bf16 out, float32
    inside where it matters."""
    args = _ssd_args(20, 32)
    want = D._ssd(*args, 8)
    low = [a.astype(jnp.bfloat16) if a.ndim > 1 and i != 1 else a
           for i, a in enumerate(args)]
    got = D._ssd(*low, 8)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(F32) - want) / jnp.linalg.norm(want)
    assert float(err) < 2e-2
