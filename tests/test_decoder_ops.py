"""The hybrid decoder's ops (ops/decoder_ops.py) against the plain
float32 reference of the benchmark's Nemotron-H configuration
(mxbench/reference/nemotron_twotower_30b_a3b.py), at toy widths on the
CPU: forward and gradients, the chunked scan against the step-by-step
recurrence at lengths that are and are not multiples of the chunk,
routing at its extremes, the expert-parallel share, recomputation."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxbench import manifest
from mxnet_tpu import telemetry
from mxnet_tpu.ops import decoder_ops as D, get_op
from numerics import (F32, attention_ref, close, highest, jitted,  # noqa: F401
                      near, rand, reference, remat_count, same_values_and_grads,
                      swiglu_experts, value_and_grads)

REF = reference("nemotron_twotower_30b_a3b")
pytestmark = pytest.mark.usefixtures("highest")


@pytest.fixture(params=["xla", "pallas"])
def path(request, monkeypatch):
    """The expert buffer's two schedules: the composition as every test
    here runs it (float32, toy widths), and the grouped kernels,
    interpreted, on the calls they serve (bf16 rows and weights, hidden
    and width of a lane tile: :func:`_experts_on`)."""
    if request.param == "pallas":
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    return request.param


def _experts_on(path, weights, seed, matrices):
    """(weights, x, what the op is given, the tolerances of value and
    gradient) for a path: on ``pallas`` a lane tile wide and the rows
    and the experts' ``matrices`` rounded to bf16 (the reference reads
    the rounded values in float32, so both route alike)."""
    if path == "xla":
        w, cfg = weights()
        (x,) = rand(seed, (2, 20, 12))
        return w, cfg, x, lambda name, a: a, close, \
            lambda got, want: close(got, want, 5e-5)
    w, cfg = weights(hidden=128, width=128)
    (x,) = rand(seed, (2, 20, 128))
    x = x.astype(jnp.bfloat16).astype(F32)
    w = {n: a.astype(jnp.bfloat16).astype(F32) if n in matrices else a
         for n, a in w.items()}

    def given(name, a):
        return a.astype(jnp.bfloat16) if name in matrices + ("x",) else a

    return w, cfg, x, given, lambda got, want: near(got, want, 2e-2), \
        lambda got, want: near(got, want, 3e-2)


def _takes_the_kernels(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("shape", [(3, 16), (2, 5, 24)])
def test_rms_norm(shape):
    x, w = rand(0, shape, shape[-1:])
    op = get_op("_contrib_rms_norm").impl
    same_values_and_grads(lambda x, w: op(x, w, eps=1e-5),
                          lambda x, w: REF._rms(x, w, 1e-5), (x, w))


def test_rms_norm_keeps_the_dtype_and_norms_in_float32():
    x = (100 * rand(1, (4, 64))[0]).astype(jnp.bfloat16)
    y = get_op("_contrib_rms_norm").impl(x, jnp.ones((64,), jnp.bfloat16))
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(jnp.mean(jnp.square(y.astype(F32)), -1)), 1.0, rtol=2e-2)


@pytest.mark.parametrize("group", [8, 16, 32])
def test_gated_rms_norm(group):
    y, z, w = rand(2, (2, 7, 32), (2, 7, 32), (32,))

    def ref(y, z, w):
        g = (y * jax.nn.silu(z)).reshape(2, 7, 32 // group, group)
        return REF._rms(g, 1.0, 1e-5).reshape(2, 7, 32) * w

    op = get_op("_contrib_gated_rms_norm").impl
    same_values_and_grads(
        lambda y, z, w: op(y, z, w, group_size=group, eps=1e-5), ref,
        (y, z, w))


@pytest.mark.parametrize("length, k", [(9, 4), (3, 4), (12, 2)])
def test_causal_conv1d(length, k):
    x, w, b = rand(3, (2, length, 6), (6, k), (6,))
    same_values_and_grads(get_op("_contrib_causal_conv1d").impl, REF._conv,
                          (x, w, b))
    # causal: an input after t never reaches y[t]
    conv = jitted(D._causal_conv1d)
    y0, y1 = conv(x, w, b), conv(x.at[:, -1].add(5.0), w, b)
    close(y0[:, :-1], y1[:, :-1], 0)


# ---------------------------------------------------------------------------
def _ssd_args(seed, length, batch=2, heads=4, p=8, groups=2, n=16):
    x, dt, a, bm, cm, d = rand(
        seed, (batch, length, heads, p), (batch, length, heads), (heads,),
        (batch, length, groups, n), (batch, length, groups, n), (heads,))
    return x, jax.nn.softplus(dt - 2.0), -jnp.exp(a), bm, cm, d


@pytest.mark.parametrize("length", [16, 24, 21, 5, 1])
def test_chunked_scan_is_the_step_by_step_recurrence(length):
    """Chunk 8: lengths that are multiples of it, that are not (the
    tail is padded with dt = 0), and shorter than one chunk."""
    op = get_op("_contrib_ssd_scan").impl
    same_values_and_grads(lambda *a: op(*a, chunk_size=8), REF.recurrence,
                          _ssd_args(4, length), tol=5e-5)


def test_scan_does_not_depend_on_the_chunk():
    args = _ssd_args(5, 24)
    y4, y8, y24 = (jax.jit(lambda *a, c=c: D._ssd(*a, c))(*args)
                   for c in (4, 8, 24))
    close(y4, y8)
    close(y8, y24)


def test_scan_without_its_skip_term_is_another_function():
    args = _ssd_args(6, 16)
    scan = jax.jit(lambda *a: D._ssd(*a, 8))
    y, no_d = scan(*args), scan(*args[:5], jnp.zeros_like(args[5]))
    assert float(jnp.max(jnp.abs(y - no_d))) > 0.1


def test_the_reference_recurrence_keeps_states_by_segment():
    """Lengths over SEGMENT that it divides take the nested scan:
    the same numbers."""
    args = _ssd_args(7, 2 * REF.SEGMENT, batch=1, heads=2, p=4, n=4)
    close(jitted(REF.recurrence)(*args),
          jax.jit(lambda *a: D._ssd(*a, 16))(*args), 5e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("length, block", [(16, 4), (21, 8), (7, 16), (8, 8)])
def test_blocked_causal_gqa_attention(length, block):
    q, k, v = rand(8, (2, length, 4, 8), (2, length, 2, 8),
                   (2, length, 2, 8))
    same_values_and_grads(lambda *a: D._causal_gqa(*a, block),
                          attention_ref, (q, k, v))
    # the op itself, at its own block size
    close(jitted(get_op("_contrib_causal_gqa_attention").impl)(q, k, v),
          jitted(attention_ref)(q, k, v))


def test_attention_never_builds_a_length_by_length_array():
    """At 64 positions in blocks of 16 the largest score array is
    16 x 64, not 64 x 64."""
    q, k, v = rand(9, (1, 64, 2, 4), (1, 64, 1, 4), (1, 64, 1, 4))
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
    r, b, up, down = rand(seed, (routed, hidden), (routed,),
                          (held, width, hidden), (held, hidden, width))
    return {"router_weight": r, "e_score_correction_bias": 0.1 * b,
            "experts_up_weight": up, "experts_down_weight": down}, \
        dict(CFG, expert_offset=offset)


def _moe(x, w, cfg, capacity_factor=None):
    """The op; with a ``capacity_factor``, what the op runs with another
    buffer than its own (``D.CAPACITY_FACTOR``). Compiled, as every
    caller of the op runs it."""
    return jax.jit(lambda x, w: _moe_traced(x, w, cfg, capacity_factor))(x, w)


def _moe_traced(x, w, cfg, capacity_factor):
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


def _ref_experts(ref, w, x, cfg, **kw):
    """A reference's loop over the held experts, compiled."""
    return jax.jit(lambda w, x: ref.experts(w, "", x, cfg, **kw))(w, x)


@pytest.mark.parametrize("capacity_factor", [0.25, None, 100.0])
def test_routed_experts(capacity_factor, path):
    """Buffers too small for the routing (the dense path), the
    default, and buffers no routing can overfill: the same numbers and
    gradients as the reference's loop over the held experts, by the
    composition and by the grouped kernels."""
    w, cfg, x, given, value_close, grad_close = _experts_on(
        path, lambda **kw: _moe_weights(10, **kw), 11,
        ("experts_up_weight", "experts_down_weight"))
    # the score bias decides the choice and takes no gradient
    bias = {"e_score_correction_bias": w.pop("e_score_correction_bias")}
    names = sorted(w)

    def fn(x, *ws):
        ws = {n: given(n, a) for n, a in zip(names, ws)}
        return _moe_traced(given("x", x), dict(ws, **bias), cfg,
                           capacity_factor)[0].astype(F32)

    def ref(x, *ws):
        return REF.experts(dict(zip(names, ws), **bias), "", x, cfg,
                           shared=False)

    args = (x,) + tuple(w[n] for n in names)
    # (a quarter of the buffer is blocks of 8 rows: not a bf16 tile)
    assert _takes_the_kernels(fn, *args) == (
        path == "pallas" and capacity_factor != 0.25)
    (cot,) = rand(12, x.shape)
    got = value_and_grads(fn, *args, cot=cot)
    want = value_and_grads(ref, *args, cot=cot)
    value_close(got[0], want[0])
    grad_close(got[1:], want[1:])


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
    (x,) = rand(14, (40, 12))
    y, counts = _moe(x, w, cfg, capacity_factor)
    close(y, _ref_experts(REF, w, x, cfg, shared=False))
    np.testing.assert_array_equal(np.asarray(counts[0]), rows)
    np.testing.assert_array_equal(np.asarray(counts[1]), rows)
    if not any(rows):
        assert float(jnp.max(jnp.abs(y))) == 0.0


# ---------------------------------------------------------------------------
# the sorted buffer's row maps (made once a layer call since PR 60)
def _scatter_maps(row, w_slot, cap):
    """The plain reference, the maps as every trace of the buffer made
    them until PR 60: two scatters, every slot without a row writing the
    one place past the end."""
    tokens = jnp.broadcast_to(jnp.arange(row.shape[0])[:, None], row.shape)
    return (jnp.full((cap + 1,), row.shape[0], jnp.int32)
            .at[row.reshape(-1)].set(tokens.reshape(-1))[:-1],
            jnp.zeros((cap + 1,), F32)
            .at[row.reshape(-1)].set(w_slot.reshape(-1))[:-1])


def _plain_layout(held, local, n_held, cap, block):
    """(expert of each block, blocks that hold a row, whether the runs
    fit), counted one expert and one block at a time."""
    held, local = np.asarray(held), np.asarray(local)
    ends, end = [], 0
    for e in range(n_held):
        end += -(-int(np.sum(held & (local == e))) // block) * block
        ends.append(end)
    starts = range(0, cap, block)
    return ([min(sum(s >= e for e in ends), n_held - 1) for s in starts],
            sum(s < end for s in starts), end <= cap)


def _mixer_before_pr60(data, norm_gamma, router_w, bias, w1, w2, *, top_k,
                       offset, eps):
    """``_contrib_moe_mixer`` as it stood at PR 59 from the pieces that
    are left: the maps made inside the buffer's branch, by
    :func:`_scatter_maps`, the overflow ``cond`` differentiated as it
    stands, the checkpoint keeping its arguments alone."""
    def mixer(data, norm_gamma, router_w, w1, w2):
        x = D._rms(data, norm_gamma, eps).reshape(-1, data.shape[-1])
        t, n_held = x.shape[0], w1.shape[0]
        idx, w_slot = D._route(x, router_w, bias, top_k, 1.0, True)
        local = idx - offset
        held = (local >= 0) & (local < n_held)
        block, blocks, _ = D._buffer(t, top_k, n_held, router_w.shape[0])
        cap = blocks * block
        row, (counts, _), expert_of_block, used, fits = D._slots_to_rows(
            held, local, n_held, cap, block)
        kernel = D.pallas_grouped_mlp.grouped_mlp_available(
            jax.ShapeDtypeStruct((blocks, block, x.shape[1]), x.dtype), w1,
            w2)
        sums = D.pallas_moe_rows.sum_available(
            jax.ShapeDtypeStruct((cap, x.shape[1]), x.dtype), top_k, t)

        def buffer():
            token_of_row, weight_of_row = _scatter_maps(row, w_slot, cap)
            xr = D._gather_rows(x, token_of_row, row, sums) \
                .reshape(-1, block, x.shape[1])
            yr = D._blocks_product(xr, expert_of_block, used, weight_of_row,
                                   w1, w2, D._relu2, kernel)
            return D._sum_slots(yr, token_of_row, row, sums)

        y = jax.lax.cond(fits, buffer, lambda: D._experts_dense(
            x, held, local, w_slot, counts, w1, w2, D._relu2))
        return y.astype(data.dtype).reshape(data.shape)

    return jax.checkpoint(mixer)(data, norm_gamma, router_w, w1, w2)


# tokens, the experts a bias of 10 favours, what the routing is
_MAP_CASES = {
    "random": (80, ()),
    "one_held_expert": (80, (4, 0, 1)),
    "no_slot_held": (80, (0, 1, 2)),
    "a_run_ends_on_a_block": (32, (4, 0, 1)),
    "overfills_the_buffer": (80, (4, 5, 6)),
}


@pytest.mark.parametrize("case", sorted(_MAP_CASES))
def test_the_row_maps_are_the_scatter_s_and_the_layer_the_parent_s(case, path):
    """The buffer's maps by one collision-free scatter and a gather
    (``_rows_to_slots``, ``_weight_of_row``) are the maps the two
    colliding scatters made, the blocks' layout is the plain count's,
    and the mixer with the maps made once and kept across its
    checkpoint gives the output and the five gradients of the mixer
    that made them in every trace: by the composition to a millionth of
    each one's largest entry, within the kernels' tolerance where they
    are interpreted."""
    tokens, favoured = _MAP_CASES[case]
    w, cfg, _, given, _, grad_close = _experts_on(
        path, lambda **kw: _moe_weights(50, **kw), 51,
        ("experts_up_weight", "experts_down_weight"))
    hidden = w["router_weight"].shape[1]
    x, gamma = rand(52, (1, tokens, hidden), (hidden,))
    x = given("x", x.astype(jnp.bfloat16).astype(F32)) if path == "pallas" \
        else x
    bias = jnp.zeros((16,)).at[jnp.array(favoured, jnp.int32)].set(10.0)
    up, down = (given(n, w[n]) for n in ("experts_up_weight",
                                         "experts_down_weight"))
    top_k, offset, n_held = 3, cfg["expert_offset"], up.shape[0]

    @jax.jit
    def maps(x):
        idx, w_slot = D._route(x[0], w["router_weight"], bias, top_k, 1.0,
                               True)
        local = idx - offset
        held = (local >= 0) & (local < n_held)
        block, blocks, _ = D._buffer(tokens, top_k, n_held, 16)
        row, _, expert_of_block, used, fits = D._slots_to_rows(
            held, local, n_held, blocks * block, block)
        slot_of_row, token_of_row = D._rows_to_slots(row, blocks * block)
        return (token_of_row, D._weight_of_row(w_slot, slot_of_row)), \
            _scatter_maps(row, w_slot, blocks * block), \
            (expert_of_block, used, fits), (held, local, block, blocks)

    got, want, layout, (held, local, block, blocks) = maps(x)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    plain = _plain_layout(held, local, n_held, int(blocks * block),
                          int(block))
    for a, b in zip(layout, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert bool(layout[2]) == (case != "overfills_the_buffer")
    if case == "a_run_ends_on_a_block":
        assert tokens % int(block) == 0 and int(layout[1]) == tokens // block

    op = get_op("_contrib_moe_mixer").impl

    def now(x, gamma, r, up, down):
        return op(x, gamma, r, jnp.zeros((2, n_held), F32), up, down, bias,
                  top_k=top_k, expert_offset=offset, eps=1e-5)[0].astype(F32)

    def before(x, gamma, r, up, down):
        return _mixer_before_pr60(x, gamma, r, bias, up, down, top_k=top_k,
                                  offset=offset, eps=1e-5).astype(F32)

    args = (x, gamma, w["router_weight"], up, down)
    assert _takes_the_kernels(now, *args) == (path == "pallas")
    (cot,) = rand(53, x.shape)
    got = value_and_grads(now, *args, cot=cot)
    want = value_and_grads(before, *args, cot=cot)
    assert len(got) == 6
    if path == "xla":
        # to a float32's last bits: the same sums in the same order, but
        # two programs, and XLA's CPU code rounds a sigmoid or contracts
        # a product and a sum by the loop a fusion puts them in (with the
        # chosen scores gathered, as until PR 60, they agreed to the bit)
        near(got, want, 1e-6)
    else:
        grad_close(got, want)


def test_a_row_no_slot_fills_reaches_no_output_and_no_gradient(path):
    """A NaN in the last token, which no held expert is routed, in the
    rows and in the cotangent: the buffer's empty rows are true zeros
    (not the last token's row, which an index clamped to the tokens
    would read), so the outputs and the four gradients are what they
    are with that token zeroed, and finite."""
    w, _, _, given, _, _ = _experts_on(
        path, lambda **kw: _moe_weights(54, **kw), 55,
        ("experts_up_weight", "experts_down_weight"))
    (x,) = rand(55, (80, w["router_weight"].shape[1]))
    x = given("x", x)
    up, down = (given(n, w[n]) for n in ("experts_up_weight",
                                         "experts_down_weight"))
    tokens, n_held, top_k = x.shape[0], up.shape[0], 3
    local = np.random.default_rng(56).permuted(
        np.tile(np.arange(16) - 4, (tokens, 1)), axis=1)[:, :top_k]
    local[-1] = [-1, -2, 5]         # the last token: none of the four held
    local = jnp.asarray(local, jnp.int32)
    held = (local >= 0) & (local < n_held)
    block, blocks, _ = D._buffer(tokens, top_k, n_held, 16)
    (w_slot,) = rand(57, (tokens, top_k))
    # (both kernels where they are interpreted: the grouped products'
    # and the slot sum's windows)
    kernel = sums = path == "pallas"
    assert sums == D.pallas_moe_rows.sum_available(
        jax.ShapeDtypeStruct((blocks * block, x.shape[1]), x.dtype), top_k,
        tokens)

    def fn(x, w_slot, up, down):
        row, _, expert_of_block, used, fits = D._slots_to_rows(
            held, local, n_held, blocks * block, block)
        slot_of_row, token_of_row = D._rows_to_slots(row, blocks * block)
        return D._experts_sorted(
            x, row, token_of_row, D._weight_of_row(w_slot, slot_of_row),
            expert_of_block, used, up, down, block, D._relu2, kernel,
            sums).astype(F32)

    assert _takes_the_kernels(fn, x, w_slot, up, down) == kernel
    (cot,) = rand(58, x.shape)
    want = value_and_grads(fn, x.at[-1].set(0), w_slot, up, down,
                           cot=cot.at[-1].set(0))
    got = value_and_grads(fn, x.at[-1].set(jnp.nan), w_slot, up, down,
                          cot=cot.at[-1].set(jnp.nan))
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_mixer_s_gradient_makes_the_maps_once_and_sorts_nothing_again():
    """The lowered value and gradient of ``_contrib_moe_mixer``
    (StableHLO, before any compiler has its say): one scatter of the buffer's length, the
    collision-free one (six a layer until PR 60: two in each of the
    forward, the recomputation and the backward rule), and none of one
    place more; the backward holds no ``top_k``, sort or running count
    beyond the forward's, because the chosen experts, the slots' rows
    and the maps cross the checkpoint by name."""
    tokens, hidden, held, cap = 80, 12, 4, 256
    x, gamma, r, up, down = rand(59, (1, tokens, hidden), (hidden,),
                                 (16, hidden), (held, 10, hidden),
                                 (held, hidden, 10))
    op = get_op("_contrib_moe_mixer").impl

    def loss(*args):
        return jnp.sum(op(*args[:3], jnp.zeros((2, held), F32), *args[3:],
                          top_k=3, expert_offset=4)[0].astype(F32))

    def lowered(fn):
        return jax.jit(fn).lower(x, gamma, r, up, down).as_text()

    forward = lowered(loss)
    gradient = lowered(jax.value_and_grad(loss, range(5)))
    assert D._buffer(tokens, 3, held, 16)[:2] == (32, cap // 32)
    scattered = re.findall(r'"stablehlo\.scatter"\(.*?-> tensor<([0-9x]*)x[a-z]',
                           gradient, re.S)
    assert scattered.count(str(cap)) == 1, scattered
    assert str(cap + 1) not in scattered
    assert "unique_indices = true" in gradient
    for again in (r"chlo\.top_k", r"stablehlo\.sort", r"call @cumsum"):
        assert len(re.findall(again, gradient)) \
            == len(re.findall(again, forward)), again
    assert len(re.findall(r"chlo\.top_k", forward)) == 1


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in shares of 4: the four shares' routed parts plus
    the shared expert once are the layer with all 16 held."""
    w, cfg = _moe_weights(15, held=16, offset=0)
    shared_up, shared_down, x = rand(16, (14, 12), (12, 14), (30, 12))
    whole = dict(w, shared_up_weight=shared_up, shared_down_weight=shared_down)
    want = _ref_experts(REF, whole, x, cfg)
    got = REF._relu2_mlp(x, shared_up, shared_down)
    counts = []
    for offset in (0, 4, 8, 12):
        share = dict(w, experts_up_weight=w["experts_up_weight"]
                     [offset:offset + 4], experts_down_weight=w[
                         "experts_down_weight"][offset:offset + 4])
        part, rows = _moe(x, share, dict(cfg, expert_offset=offset))
        close(part, _ref_experts(REF, share, x,
                                 dict(cfg, expert_offset=offset),
                                 shared=False))
        got = got + part
        counts.append(np.asarray(rows[0]))
    close(got, want)
    assert int(np.sum(counts)) == 30 * 3        # every choice held once


def test_a_wrong_scaling_factor_is_seen():
    w, cfg = _moe_weights(17)
    (x,) = rand(18, (20, 12))
    y = _moe(x, w, cfg)[0]
    off = _moe(x, w, dict(cfg, routed_scaling_factor=1.0))[0]
    close(y, 2.5 * off)
    assert float(jnp.max(jnp.abs(y - off))) > 1e-2


# ---------------------------------------------------------------------------
def test_the_mamba2_mixer_recomputes_its_inside():
    hidden, heads, p, groups, n, k = 16, 4, 4, 2, 8, 4
    inner, conv = heads * p, heads * p + 2 * groups * n
    u, nw, inw, cw, cb, dtb, al, d, gw, ow = rand(
        19, (2, 12, hidden), (hidden,), (inner + conv + heads, hidden),
        (conv, k), (conv,), (heads,), (heads,), (heads,), (inner,),
        (hidden, inner), scale=0.3)
    args = (u, nw, inw, cw, cb, dtb, al, d, gw, ow)
    attrs = dict(num_heads=heads, head_dim=p, n_groups=groups, state_size=n,
                 chunk_size=4, eps=1e-5)
    op = get_op("_contrib_mamba2_mixer").impl
    plain = lambda *a: D._mamba2(*a, heads=heads, head_dim=p, groups=groups,
                                 state=n, chunk=4, eps=1e-5)
    same_values_and_grads(lambda *a: op(*a, **attrs), plain, args, tol=5e-5)
    w = {"in_proj_weight": inw, "conv_weight": cw, "conv_bias": cb,
         "dt_bias": dtb, "a_log": al, "d": d, "gate_norm_weight": gw,
         "out_proj_weight": ow}
    cfg = {"mamba_num_heads": heads, "mamba_head_dim": p, "n_groups": groups,
           "ssm_state_size": n, "layer_norm_epsilon": 1e-5}
    close(jax.jit(lambda *a: op(*a, **attrs))(*args),
          jax.jit(lambda u, nw, w: REF.mamba2(w, "", REF._rms(u, nw, 1e-5),
                                              cfg))(u, nw, w), 5e-5)
    grad = jax.grad(lambda *a: jnp.sum(op(*a, **attrs)))
    assert remat_count(grad, *args) > 0
    assert remat_count(jax.grad(lambda *a: jnp.sum(plain(*a))), *args) == 0


def test_mixers_take_bfloat16_and_stay_near_float32():
    """What ShardedTrainStep feeds them: bf16 in, bf16 out, float32
    inside where it matters."""
    args = _ssd_args(20, 32)
    scan = jax.jit(lambda *a: D._ssd(*a, 8))
    want = scan(*args)
    low = [a.astype(jnp.bfloat16) if a.ndim > 1 and i != 1 else a
           for i, a in enumerate(args)]
    got = scan(*low)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(F32) - want) / jnp.linalg.norm(want)
    assert float(err) < 2e-2


# ---------------------------------------------------------------------------
# rotary positions, the learned key selector and the attention over the
# keys it keeps, softmax / SwiGLU experts: against the plain reference
# of the benchmark's Keye-VL configuration
# ---------------------------------------------------------------------------
KREF = reference("keye_vl2_30b_a3b")
KTOY = manifest.load_json("configs", "keye_vl2_30b_a3b.json")["toy"]


def test_rotary_with_three_distinct_position_axes():
    (x,) = rand(30, (2, 9, 3, 16))
    pos = jnp.asarray(np.random.default_rng(0).integers(0, 5000, (3, 2, 9)),
                      jnp.int32)
    assert not np.array_equal(pos[0], pos[1])
    op = get_op("_contrib_rotary").impl
    same_values_and_grads(
        lambda x: op(x, pos, theta=1e7, sections=(2, 3, 3)),
        lambda x: KREF.rope(x, pos, 1e7, [2, 3, 3]), (x,))
    # one axis, and text: no positions given is every axis the index
    close(op(x, pos[1], theta=1e4), KREF.rope(x, pos[1], 1e4))
    index = jnp.broadcast_to(jnp.arange(9), (3, 2, 9))
    close(op(x, theta=1e7, sections=(2, 3, 3)),
          KREF.rope(x, index, 1e7, [2, 3, 3]))
    close(op(x, theta=1e7), KREF.rope(x, index[0], 1e7))
    # a section that reads another axis changes the result
    swapped = op(x, pos[jnp.array([0, 2, 1])], theta=1e7, sections=(2, 3, 3))
    assert float(jnp.max(jnp.abs(
        swapped - op(x, pos, theta=1e7, sections=(2, 3, 3))))) > 0.1
    with pytest.raises(ValueError):
        op(x, pos, theta=1e7, sections=(2, 3, 2))


def _program_set(scores, first, k):
    """The program's selected set for a block of query rows."""
    n = scores.shape[-1]
    seen = jnp.arange(n)[None, :] <= (first + jnp.arange(
        scores.shape[-2]))[:, None]
    return jax.jit(jax.vmap(lambda s: D._selected(
        s, seen, *D._thresholds(s, seen, k))))(scores)


def _reference_set(scores, first, k):
    return jax.jit(lambda s: KREF.selected(s, first, k))(scores)


@pytest.mark.parametrize("first, k", [(0, 8), (24, 8), (24, 40), (5, 1)])
def test_the_selected_set_is_top_ks_row_for_row(first, k):
    iq, ik, iw = rand(31, (2, 16, 4, 8), (2, first + 16, 8), (2, 16, 4))
    scores = jitted(D._index_scores)(iq, ik, iw)
    close(scores, jitted(KREF.index_scores)(iq, ik, iw), 1e-5)
    want = _reference_set(scores, first, k)
    got = _program_set(scores, first, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rows = np.asarray(got).sum(-1)
    np.testing.assert_array_equal(
        rows, np.broadcast_to(np.minimum(first + 1 + np.arange(16), k),
                              rows.shape))


def test_ties_go_to_the_lower_index():
    """Planted ties at a row's threshold (and a row of nothing but
    ties, zeros of both signs among them): exactly ``lax.top_k``'s set."""
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(1, 8, 32)).astype(np.float32)
    kth = np.sort(scores[0, 2])[::-1][5]
    scores[0, 2, [1, 9, 30]] = kth              # four keys at the threshold
    scores[0, 3] = rng.integers(-1, 2, 32)      # few distinct values
    scores[0, 4] = 0.0
    scores[0, 4, ::3] = -0.0
    scores[0, 5] = np.where(rng.random(32) < 0.5, 0.0, -0.0)
    scores[0, 5, 7] = 1.0
    scores = jnp.asarray(scores)
    for first in (24, 0):
        # zeros of either sign are one value, as the reference's own
        # index_scores hands them to lax.top_k
        want = _reference_set(jnp.where(scores == 0, 0.0, scores), first, 6)
        got = _program_set(scores, first, 6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    tied = np.flatnonzero(np.asarray(scores[0, 2]) == kth)
    kept = np.flatnonzero(np.asarray(_program_set(scores, 24, 6))[0, 2])
    assert len(tied) == 4 and len(kept) == 6
    assert set(tied[:1]) <= set(kept) and tied[-1] not in kept


def test_kth_largest_without_sorting():
    keys = jnp.asarray(np.random.default_rng(4).integers(
        1, 2 ** 32, (5, 50), dtype=np.uint64).astype(np.uint32))
    for k in (1, 7, 50):
        want = np.sort(np.asarray(keys), axis=-1)[:, -k]
        np.testing.assert_array_equal(np.asarray(D._kth_largest(keys, k)),
                                      want)
    assert (np.asarray(D._kth_largest(keys, 51)) == 0).all()
    # the order of the floats is the order of their keys
    x = jnp.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf],
                    F32)
    order = np.asarray(D._order_keys(x)).astype(np.int64)
    assert (np.diff(order) >= 0).all() and order[3] == order[4]
    assert order.min() > 0


def _attn_cfg():
    return dict(KTOY, rms_norm_eps=1e-6, rope_theta=1e7)


def _attn_weights(seed, cfg):
    u = cfg["hidden_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    names = ["q_weight", "k_weight", "v_weight", "o_weight", "q_norm_weight",
             "k_norm_weight", "index_q_weight", "index_k_weight",
             "index_w_weight", "index_k_norm_weight", "index_k_norm_bias"]
    shapes = [(h * d, u), (kv * d, u), (kv * d, u), (u, h * d), (d,), (d,),
              (ih * idim, u), (idim, u), (ih, u), (idim,), (idim,)]
    w = dict(zip(names, rand(seed, *shapes, scale=0.3)))
    for n in ("q_norm_weight", "k_norm_weight", "index_k_norm_weight"):
        w[n] = 1.0 + w[n]
    return names, w


def _mixer(x, norm_w, w, names, cfg, positions=None):
    sa = cfg["sa_config"]
    return get_op("_contrib_sparse_gqa_mixer").impl(
        x, norm_w, *[w[n] for n in names], jnp.zeros((2,), F32), positions,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], top_k=sa["topk"],
        rope_theta=cfg["rope_theta"],
        rope_sections=tuple(cfg["rope_scaling"]["mrope_section"]),
        eps=cfg["rms_norm_eps"])


@pytest.mark.parametrize("length, block", [(37, 16), (32, 512)])
def test_sparse_attention_mixer_against_the_reference(monkeypatch, length,
                                                      block):
    """Forward, both outputs and the state; the gradient of both outputs
    to every input; several query blocks and one, a length that is not
    whole blocks; three distinct position axes. (Traced here, under the
    patched block size.)"""
    monkeypatch.setattr(D, "QUERY_BLOCK", block)
    monkeypatch.setattr(KREF, "QUERY_BLOCK", block)
    cfg = _attn_cfg()
    names, w = _attn_weights(32, cfg)
    x, norm_w = rand(33, (2, length, cfg["hidden_size"]),
                     (cfg["hidden_size"],))
    norm_w = 1.0 + 0.1 * norm_w
    pos = jnp.asarray(np.random.default_rng(1).integers(
        0, 900, (3, 2, length)), jnp.int32)

    def fn(x, norm_w, *ws):
        return _mixer(x, norm_w, dict(zip(names, ws)), names, cfg, pos)

    def ref(x, norm_w, *ws):
        return KREF.attention(dict(zip(names, ws)), "",
                              KREF._rms(x, norm_w, cfg["rms_norm_eps"]), pos,
                              cfg)

    args = (x, norm_w) + tuple(w[n] for n in names)
    # one pullback of both outputs together (a cotangent on the mixer's
    # output, the index loss weighted 3) to every input
    (cot,) = rand(34, x.shape)
    y, loss, state, *got = value_and_grads(fn, *args, cot=(cot, 3.0, 0.0))
    want_y, want_loss, *want = value_and_grads(ref, *args, cot=(cot, 3.0))
    close(y, want_y, 1e-4)
    assert loss.shape == (1,) and float(loss[0]) > 0
    assert float(loss[0]) == pytest.approx(float(want_loss), rel=1e-4)
    k = cfg["sa_config"]["topk"]
    assert float(state[0]) == pytest.approx(
        sum(min(t + 1, k) for t in range(length)) / length)
    assert float(state[1]) == pytest.approx(float(loss[0]))
    close(got, want, 2e-4)


def test_each_loss_trains_its_own_parameters():
    """The index loss reaches the selector's parameters and nothing
    else; the language model's side reaches everything but them."""
    cfg = _attn_cfg()
    names, w = _attn_weights(35, cfg)
    x, norm_w = rand(36, (1, 12, cfg["hidden_size"]), (cfg["hidden_size"],))
    args = (x, 1.0 + 0.1 * norm_w) + tuple(w[n] for n in names)
    argnums = tuple(range(len(args)))

    def fn(*a):
        return _mixer(a[0], a[1], dict(zip(names, a[2:])), names, cfg)

    by_index = jax.jit(jax.grad(lambda *a: fn(*a)[1].sum(), argnums))(*args)
    by_lm = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.square(fn(*a)[0])),
                             argnums))(*args)
    for name, gi, gl in zip(("data", "norm") + tuple(names), by_index, by_lm):
        moved_i = float(jnp.max(jnp.abs(gi))) > 0
        moved_l = float(jnp.max(jnp.abs(gl))) > 0
        assert moved_i == name.startswith("index_"), name
        assert moved_l == (not name.startswith("index_")), name


def test_the_sparse_mixer_keeps_thresholds_and_context_only(capsys):
    """Beside its arguments the mixer's checkpoint keeps each row's
    threshold and tie count and the context: no score block, no
    projection."""
    fn, args, kept = _dsa_case()
    assert remat_count(jax.grad(fn), *args) > 0
    assert sorted(_saved(capsys, fn, *args)) == sorted(kept)


# ---------------------------------------------------------------------------
def _swiglu_moe(x, w, cfg, capacity_factor=None):
    return jax.jit(lambda x, w: _swiglu_moe_traced(
        x, w, cfg, capacity_factor))(x, w)


def _swiglu_moe_traced(x, w, cfg, capacity_factor):
    kwargs = {} if capacity_factor is None \
        else {"capacity_factor": capacity_factor}
    y, rows = D._moe_experts(
        x.reshape(-1, x.shape[-1]), w["router_weight"], None,
        w["experts_gate_up_weight"], w["experts_down_weight"],
        top_k=cfg["num_experts_per_tok"], offset=cfg["expert_offset"],
        scale=1.0, norm_topk=cfg["norm_topk_prob"], score_func="softmax",
        activation="swiglu", **kwargs)
    return y.reshape(x.shape), rows


@pytest.mark.parametrize("capacity_factor", [0.25, None, 100.0])
def test_softmax_swiglu_experts(capacity_factor, path):
    """A softmax router without a bias over gated experts, the dense
    path, the default and a buffer no routing overfills: the numbers
    and gradients of the reference's loop over the held experts, by the
    composition and by the grouped kernels."""
    w, cfg, x, given, value_close, grad_close = _experts_on(
        path, lambda **kw: swiglu_experts(40, **kw), 41,
        ("experts_gate_up_weight", "experts_down_weight"))
    names = sorted(w)

    def fn(x, *ws):
        ws = {n: given(n, a) for n, a in zip(names, ws)}
        return _swiglu_moe_traced(given("x", x), ws, cfg,
                                  capacity_factor)[0].astype(F32)

    def ref(x, *ws):
        return KREF.experts(dict(zip(names, ws)), "", x, cfg)

    args = (x,) + tuple(w[n] for n in names)
    assert _takes_the_kernels(fn, *args) == (
        path == "pallas" and capacity_factor != 0.25)
    (cot,) = rand(42, x.shape)
    got = value_and_grads(fn, *args, cot=cot)
    want = value_and_grads(ref, *args, cot=cot)
    value_close(got[0], want[0])
    grad_close(got[1:], want[1:])
    # through the registered op too, the bias left out
    op = get_op("_contrib_moe_experts").impl
    y, rows = jax.jit(lambda x, r, gate_up, down: op(
        x, r, None, jnp.zeros((2, 4), F32), gate_up, down, top_k=3,
        expert_offset=4, score_func="softmax", activation="swiglu"))(
        given("x", x), w["router_weight"],
        given("experts_gate_up_weight", w["experts_gate_up_weight"]),
        given("experts_down_weight", w["experts_down_weight"]))
    value_close(y, want[0])
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(rows[1]))


def test_the_moe_mixer_takes_its_optional_inputs_last():
    """Without a score bias and a shared expert, and with both: the
    same op, the routed part unchanged."""
    w, cfg = swiglu_experts(43)
    x, norm_w, bias, shared_up, shared_down = rand(
        44, (2, 10, 12), (12,), (16,), (2 * 6, 12), (12, 6))
    op = get_op("_contrib_moe_mixer").impl
    attrs = dict(top_k=3, expert_offset=4, score_func="softmax",
                 activation="swiglu", eps=1e-6)
    mixer = jax.jit(lambda *a: op(*a, **attrs))
    rows = jnp.zeros((2, 4), F32)
    bare, _ = mixer(x, norm_w, w["router_weight"], rows,
                    w["experts_gate_up_weight"], w["experts_down_weight"])
    h = KREF._rms(x, norm_w, 1e-6)
    close(bare, _ref_experts(KREF, w, h, cfg))
    full, _ = mixer(x, norm_w, w["router_weight"], rows,
                    w["experts_gate_up_weight"], w["experts_down_weight"],
                    0.0 * bias, shared_up, shared_down)
    close(full, bare + KREF.swiglu(h, shared_up[:6], shared_up[6:],
                                   shared_down))
    with pytest.raises(KeyError):
        op(x, norm_w, w["router_weight"], rows, w["experts_gate_up_weight"],
           w["experts_down_weight"], **dict(attrs, activation="gelu"))


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """16 experts in 8 shares of 2 (the cell: 128 in 8 shares of 16):
    the shares' parts, with no shared expert to count once, are the
    layer with all 16 held."""
    w, cfg = swiglu_experts(45, held=16, offset=0)
    (x,) = rand(46, (30, 12))
    want = _ref_experts(KREF, w, x, cfg)
    got, counts = 0.0, []
    for offset in range(0, 16, 2):
        share = dict(w, experts_gate_up_weight=w["experts_gate_up_weight"]
                     [offset:offset + 2], experts_down_weight=w[
                         "experts_down_weight"][offset:offset + 2])
        part, rows = _swiglu_moe(x, share, dict(cfg, expert_offset=offset))
        close(part, _ref_experts(KREF, share, x,
                                 dict(cfg, expert_offset=offset)))
        got = got + part
        counts.append(np.asarray(rows[0]))
    close(got, want)
    assert int(np.sum(counts)) == 30 * 3        # every choice held once


# ---------------------------------------------------------------------------
# what a recomputed mixer keeps (the module's rule): its input and the
# output of a product that reads its normed input, where the next stage
# reads that output as it lies (W_in's, in_proj's, the rotary mixer's v)
# ---------------------------------------------------------------------------
_B, _L, _U = 2, 21, 24                      # batch, length, hidden
_H, _KV, _HD = 4, 2, 8                      # the rotary mixer's heads


def _rotary_case(norms=True, gate=False, window=0):
    x, g, qw, kw, vw, ow, qn, kn, gw = rand(
        61, (_B, _L, _U), (_U,), (_H * _HD, _U), (_KV * _HD, _U),
        (_KV * _HD, _U), (_U, _H * _HD), (_HD,), (_HD,), (_H, _U), scale=0.3)
    args = [x, 1 + g, qw, kw, vw, ow] + ([1 + qn, 1 + kn] if norms else []) \
        + ([gw] if gate else [])

    def spread(a):
        # the op's order: ..., q_norm, k_norm, positions, gate_weight
        a = list(a)
        gate_w = a.pop() if gate else None
        return a + ([] if norms else [None, None]) + [None, gate_w]

    op = get_op("_contrib_rotary_gqa_mixer").impl
    attrs = dict(num_heads=_H, num_kv_heads=_KV, head_dim=_HD,
                 rope_theta=5e5, window=window, eps=1e-6)
    body = lambda *a: D._rotary_mixer(
        *spread(a), h=_H, kv=_KV, d=_HD, rotary_dim=_HD, theta=5e5, yarn=(),
        attention_factor=1.0, window=window or None, eps=1e-6)
    before = jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            D._CTX_KEPT))
    kept = ["f32[%d,%d,%d]" % (_B, _L, _KV * _HD),           # v
            "f32[%d,%d,%d,%d]" % (_B, _L, _H, _HD)]          # the context
    return (lambda *a: op(*spread(a), **attrs)), body, before, args, kept, 1


def _conv_case():
    x, g, w_in, w_c, w_out = rand(62, (_B, _L, _U), (_U,), (3 * _U, _U),
                                  (_U, 3), (_U, _U), scale=0.5)
    op = get_op("_contrib_short_conv_mixer").impl
    body = lambda *a: D._short_conv(*a, eps=1e-5)
    return (lambda *a: op(*a, eps=1e-5)), body, jax.checkpoint(body), \
        (x, 1 + g, w_in, w_c, w_out), ["f32[%d,%d,%d]" % (_B, _L, 3 * _U)], 1


def _mamba2_case():
    heads, p, groups, n, k = 4, 4, 2, 8, 4
    inner, conv = heads * p, heads * p + 2 * groups * n
    args = rand(63, (_B, 12, 16), (16,), (inner + conv + heads, 16),
                (conv, k), (conv,), (heads,), (heads,), (heads,), (inner,),
                (16, inner), scale=0.3)
    op = get_op("_contrib_mamba2_mixer").impl
    attrs = dict(num_heads=heads, head_dim=p, n_groups=groups, state_size=n,
                 chunk_size=4, eps=1e-5)
    body = lambda *a: D._mamba2(*a, heads=heads, head_dim=p, groups=groups,
                                state=n, chunk=4, eps=1e-5)
    return (lambda *a: op(*a, **attrs)), body, jax.checkpoint(body), args, \
        ["f32[%d,12,%d]" % (_B, inner + conv + heads)], 1


_KEEPERS = {
    "rotary": _rotary_case,
    "rotary_without_qk_norms": lambda: _rotary_case(norms=False),
    "rotary_gated": lambda: _rotary_case(norms=False, gate=True),
    "rotary_windowed": lambda: _rotary_case(window=6),
    "rotary_gated_windowed_normed": lambda: _rotary_case(gate=True, window=6),
    "conv": _conv_case,
    "mamba2": _mamba2_case,
}


def _saved(capsys, fn, *args):
    """What ``fn``'s checkpoints keep beside arguments and constants."""
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    return [line.split(" ")[0] for line in capsys.readouterr().out
            .splitlines() if "from the argument" not in line
            and "from a constant" not in line]


@pytest.mark.parametrize("case", sorted(_KEEPERS))
def test_a_keeping_mixer_keeps_its_in_product_and_nothing_else(case, capsys):
    """Beside its arguments: the named product's output (and, for the
    rotary mixer, the context); no q or k, no normed, turned or gated
    copy, no tap, no scan state."""
    op, _, _, args, kept, _ = _KEEPERS[case]()
    assert sorted(_saved(capsys, lambda *a: jnp.sum(op(*a)), *args)) \
        == sorted(kept)


@pytest.mark.parametrize("case", sorted(_KEEPERS))
def test_the_backward_runs_no_kept_product_again(case):
    """One ``dot_general`` fewer a kept product than the form that kept
    the input alone (the context too, for the rotary mixer), while the
    element-wise inside is still recomputed."""
    op, _, before, args, _, products = _KEEPERS[case]()

    def dots(mixer):
        return str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(mixer(*a))))(
            *args)).count("dot_general")

    assert dots(before) - dots(op) == products
    assert remat_count(jax.grad(lambda *a: jnp.sum(op(*a))), *args) > 0


@pytest.mark.parametrize("case", sorted(_KEEPERS))
def test_a_keeping_mixer_is_its_body_in_value_and_gradient(case):
    op, body, _, args, _, _ = _KEEPERS[case]()
    same_values_and_grads(op, body, tuple(args), tol=5e-5)


@pytest.mark.parametrize("case, mixer", [
    ("rotary_gated", "rotary"), ("conv", "conv"), ("mamba2", "mamba2")])
def test_a_keeping_mixer_is_counted_once_a_traced_call(case, mixer):
    op, _, _, args, _, _ = _KEEPERS[case]()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        count = telemetry.counter("mx_mixer_kept_total", mixer=mixer)
        before = count.value
        jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(op(*a))))(*args)
        assert count.value == before + 1
    finally:
        telemetry.enable(was)


def _mla_case():
    h, nope, rope, q_rank, kv_rank = 2, 8, 8, 12, 10
    vd = nope + rope
    args = rand(64, (_B, _L, _U), (_U,), (q_rank, _U), (q_rank,),
                (h * (nope + rope), q_rank), (kv_rank + rope, _U), (kv_rank,),
                (h * (nope + vd), kv_rank), (_U, h * vd), scale=0.3)
    op = get_op("_contrib_mla_mixer").impl
    return (lambda *a: jnp.sum(op(
        *a, num_heads=h, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=vd))), args, ["f32[%d,%d,%d,%d]" % (_B, _L, h, vd)]


def _dsa_case():
    cfg = _attn_cfg()
    names, w = _attn_weights(37, cfg)
    x, norm_w = rand(38, (1, 24, cfg["hidden_size"]), (cfg["hidden_size"],))
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    return (lambda *a: jnp.sum(_mixer(a[0], a[1], dict(zip(names, a[2:])),
                                      names, cfg)[0])), \
        (x, norm_w) + tuple(w[n] for n in names), \
        ["u32[1,24]", "i32[1,24]", "f32[1,24,%d,%d]" % (h, d)]


def _glu_case():
    args = rand(65, (_B, _L, _U), (_U,), (2 * 16, _U), (_U, 16), scale=0.3)
    op = get_op("_contrib_glu_mlp_mixer").impl
    return (lambda *a: jnp.sum(op(*a))), args, []


@pytest.mark.parametrize("case", [_mla_case, _dsa_case, _glu_case],
                         ids=["mla", "dsa", "glu_mlp"])
def test_the_other_mixers_keep_what_they_kept(case, capsys):
    """The rule stops at three mixers: the latent one keeps its context,
    the sparse one thresholds, tie counts and context, the dense gated
    one nothing, and none of them a projection (why: the module's
    docstring)."""
    fn, args, kept = case()
    assert sorted(_saved(capsys, fn, *args)) == sorted(kept)
    assert remat_count(jax.grad(fn), *args) > 0
