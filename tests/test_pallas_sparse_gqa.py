"""The sparse grouped-query flash kernels (ops/pallas_sparse_gqa.py) in
interpret mode on the CPU: context, head-averaged probabilities, index
loss, state and every gradient against the composition they stand in for
(``decoder_ops._sparse_gqa``) and against a plain float32 reference;
rows whose selected keys miss whole tiles; the causal limit; causality;
what the mixer keeps; and the ladder by which
``decoder_ops._sparse_attend`` picks a form, each rung counted in
``mx_attn_sparse_path_total``. And the selector's index scores summed
over their heads in VMEM (ops/pallas_index_scores.py), which the flash
form takes where it can: scores and gradients against the composition
(``decoder_ops._index_scores``) and the float32 reference, the mask the
backward rebuilds against the forward's, its own rungs, each counted in
``mx_attn_index_path_total``. What Mosaic makes of the kernels at the
published widths is tests/test_chip_compile_*.py's."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mxnet_tpu import telemetry
from mxnet_tpu.ops import (decoder_ops as D, get_op, pallas_causal_gqa as P,
                           pallas_common, pallas_index_scores as I,
                           pallas_sparse_gqa as S)
from mxnet_tpu.ops.pallas_common import auto_partitioned
from numerics import (BF, F32, close, jitted, near, normal, reference,
                      value_and_grads)

KREF = reference("keye_vl2_30b_a3b")
COUNTER = "mx_attn_sparse_path_total"
INDEX_COUNTER = "mx_attn_index_path_total"
PATHS = {COUNTER: ("pallas", "masked"), INDEX_COUNTER: ("pallas", "xla")}
TILE = 128
# index heads the kernels of ops/pallas_index_scores.py serve (the
# published 64 lanes, in pairs); ``_inputs``' own (2 x 8) they do not
SUMMED = dict(ih=2, idim=64)

pytestmark = pytest.mark.usefixtures("pallas_interpret")


@pytest.fixture(autouse=True)
def _tile(monkeypatch):
    """Query blocks of 128 (the op's 512 in interpret mode is minutes)."""
    monkeypatch.setattr(D, "QUERY_BLOCK", TILE)


def _inputs(seed, length, heads, kv, d=128, batch=1, dtype=BF, ih=2, idim=8):
    """q, k, v, index queries / keys / weights, a cotangent for the
    context."""
    keys = jax.random.split(jax.random.key(seed), 7)
    shapes = [(batch, length, heads, d), (batch, length, kv, d),
              (batch, length, kv, d), (batch, length, ih, idim),
              (batch, length, idim), (batch, length, ih),
              (batch, length, heads, d)]
    return [normal(key, s, F32 if i == 5 else dtype)
            for i, (key, s) in enumerate(zip(keys, shapes))]


def _reference(q, k, v, iq, ik, iw, top_k):
    """Plain float32: whole score rows, ``lax.top_k``'s set."""
    b, length, heads, d = q.shape
    k, v = (jnp.repeat(t, heads // k.shape[2], axis=2) for t in (k, v))
    scores = KREF.index_scores(iq, ik, iw)
    keep = KREF.selected(jax.lax.stop_gradient(scores), 0, top_k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    att = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
    target = jax.lax.stop_gradient(att.mean(1))
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    kl = jnp.sum(jax.scipy.special.xlogy(target, target)
                 - jnp.where(keep, target * logq, 0.0))
    return (jnp.einsum("bhqk,bkhd->bqhd", att, v), kl / (b * length),
            jnp.sum(keep, dtype=F32) / (b * length))


def _outputs_and_grads(form, args, cot, top_k):
    """[context, index loss, keys a query, six gradients] of the context
    under ``cot`` plus three times the index loss, float32."""
    return value_and_grads(lambda *a: form(*a, top_k), *args,
                           cot=(cot, 3.0, 0.0))


@pytest.mark.parametrize("heads, kv", [(2, 2), (8, 1), (16, 1)],
                         ids=["1to1", "8to1", "16to1"])
@pytest.mark.parametrize("length, top_k", [
    (TILE, 48), (3 * TILE, 48), (3 * TILE, TILE), (3 * TILE, 200)],
    ids=["one_tile", "three_tiles_k_below", "k_a_tile", "k_above"])
def test_kernels_match_the_composition_and_the_reference(length, top_k,
                                                         heads, kv):
    _flash_against_the_composition_and_the_reference(length, top_k, heads, kv)


@pytest.mark.parametrize("length, top_k, ih, idim", [
    (3 * TILE, 48, 4, 64), (2 * TILE, 200, 2, 128)],
    ids=["two_pairs_of_64", "two_heads_of_128"])
def test_the_flash_form_on_summed_index_scores_matches_them_too(
        length, top_k, ih, idim):
    """The same comparison where the index scores come from the kernels
    that sum them over the heads in VMEM (and their gradient from the
    backward kernel)."""
    assert I.index_scores_available(
        *_inputs(0, length, 4, 2, ih=ih, idim=idim)[3:6], TILE)
    _flash_against_the_composition_and_the_reference(length, top_k, 4, 2,
                                                     ih=ih, idim=idim)


def _flash_against_the_composition_and_the_reference(length, top_k, heads, kv,
                                                     **index):
    *args, cot = _inputs(length + heads + top_k, length, heads, kv, **index)
    got = _outputs_and_grads(D._sparse_gqa_flash, args, cot, top_k)
    # the composition on the same bf16 inputs: the same selected set bit
    # for bit, two roundings of one sum elsewhere
    want = _outputs_and_grads(D._sparse_gqa, args, cot, top_k)
    assert float(got[2]) == float(want[2]) == pytest.approx(
        sum(min(t + 1, top_k) for t in range(length)) / length)
    near(got, want, 2e-2)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-3)
    # the plain float32 reference on the same values
    ref = _outputs_and_grads(_reference, [t.astype(F32) for t in args], cot,
                           top_k)
    near(got, ref, 2e-2)
    assert float(got[1]) == pytest.approx(float(ref[1]), rel=2e-3)


# ---------------------------------------------------------------------------
# the index scores summed over their heads in VMEM
# ---------------------------------------------------------------------------
INDEX_SHAPES = {
    # name: (length, index heads, their width)
    "one_tile_a_pair": (TILE, 2, 64),
    "three_tiles_two_pairs": (3 * TILE, 4, 64),
    "two_tiles_heads_of_a_lane_tile": (2 * TILE, 2, 128),
}


def _composition_blocks(iq, ik, iw):
    return tuple(D._index_scores(*index_in)
                 for _, _, index_in in D._index_blocks(iq, ik, iw))


@pytest.mark.parametrize("shape", sorted(INDEX_SHAPES))
def test_summed_index_scores_match_the_composition_and_the_reference(shape):
    """Every query block's scores (the second and third blocks read keys
    of earlier tiles) and, under a cotangent a block, ``d qI``, ``d kI``
    and ``dw``."""
    length, ih, idim = INDEX_SHAPES[shape]
    iq, ik, iw = _inputs(17, length, 2, 1, batch=2, ih=ih, idim=idim)[3:6]
    assert I.index_scores_available(iq, ik, iw, TILE)
    n = length // TILE
    cot = tuple(normal(key, (2, TILE, (i + 1) * TILE)) for i, key in
                enumerate(jax.random.split(jax.random.key(18), n)))
    got = value_and_grads(lambda *a: I.index_score_blocks(*a, TILE),
                          iq, ik, iw, cot=cot)
    want = value_and_grads(_composition_blocks, iq, ik, iw, cot=cot)
    close(got[:n], want[:n], 1e-5)
    whole = jitted(KREF.index_scores)(iq.astype(F32), ik.astype(F32), iw)
    for i, block in enumerate(got[:n]):
        close(block, whole[:, i * TILE:(i + 1) * TILE, :(i + 1) * TILE], 1e-5)
    near(got[n:n + 2], want[n:n + 2], 1e-2)     # bf16 gradients
    near(got[n + 2:], want[n + 2:], 1e-5)


def _loss_gradient(form, inputs):
    """Run the gradient of both outputs of ``_sparse_gqa_flash`` or of
    the mixer op at ``inputs``."""
    *args, cot = inputs
    if form == "flash":
        return _outputs_and_grads(D._sparse_gqa_flash, args, cot, 48)
    fn, args = _mixer_form(args[0], args[1], args[3])

    def loss(*a):
        y, index_loss, _ = fn(*a)
        return jnp.sum(y.astype(F32)) + index_loss[0]

    return jax.block_until_ready(jax.jit(jax.grad(loss))(*args))


@pytest.mark.parametrize("form", ["flash", "mixer"])
def test_the_backward_rebuilds_the_forward_s_mask_bit_for_bit(form,
                                                              monkeypatch):
    """On the path of the summed index scores: the mask the backward
    kernel is handed (the scores computed again, the kept thresholds
    and tie counts) is the one the forward kernel was handed."""
    masks = {"attend": [], "attend_bwd": []}
    for name, kept in masks.items():
        def spy(q, k, v, mask, *rest, kernel=getattr(S, name), kept=kept):
            jax.debug.callback(lambda m: kept.append(np.asarray(m)), mask)
            return kernel(q, k, v, mask, *rest)
        monkeypatch.setattr(S, name, spy)
    inputs = _inputs(21, 3 * TILE, 4, 2, **SUMMED)
    assert I.index_scores_available(*inputs[3:6], TILE)
    _loss_gradient(form, inputs)
    jax.effects_barrier()
    # (the mixer's recomputation keeps the spy's callback, and so its
    # mask, though not the forward kernel: one more forward mask there)
    assert len(masks["attend"]) == (2 if form == "mixer" else 1)
    (backward,) = masks["attend_bwd"]
    for forward in masks["attend"]:
        # selection engaged: some seen pair of the last block is not kept
        assert 0 < forward[0, 2].sum() < 2.5 * TILE * TILE
        np.testing.assert_array_equal(forward, backward)


# ---------------------------------------------------------------------------
# the kernels under a mask handed to them
# ---------------------------------------------------------------------------
def _masked_reference(q, k, v, keep):
    """(context, head-averaged probabilities) of a softmax over the
    pairs ``keep`` (batch, queries, keys) names, float32."""
    heads, d = q.shape[2], q.shape[3]
    k, v = (jnp.repeat(t, heads // k.shape[2], axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    att = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", att, v), att.mean(1)


def _kernel_mask(keep):
    length = keep.shape[1]
    return [D._keys_by_queries(keep[:, lo:lo + TILE, :lo + TILE])
            for lo in range(0, length, TILE)]


def test_rows_that_select_nothing_in_a_tile_stay_finite_and_right():
    """Three tiles. Rows 260-299 select keys of the last tile they visit
    only (none in their first two); rows 300-339 none in their first;
    rows 340-383 the first tile only (none in their last but
    themselves); the rest a random half of what they see."""
    length, heads, kv = 3 * TILE, 4, 2
    q, k, v, _, _, _, cot = _inputs(11, length, heads, kv, batch=2)
    rows = jnp.arange(length)[:, None]
    cols = jnp.arange(length)[None, :]
    seen = cols <= rows
    keep = seen & (jax.random.uniform(jax.random.key(12), (length, length))
                   < 0.5)
    keep = jnp.where((rows >= 260) & (rows < 300), cols >= 2 * TILE, keep)
    keep = jnp.where((rows >= 300) & (rows < 340), cols >= TILE, keep)
    keep = jnp.where((rows >= 340), cols < TILE, keep)
    keep = jnp.broadcast_to((keep | (rows == cols)) & seen,
                            (2, length, length))

    @jax.jit
    def kernels(q, k, v, cot):
        blocks = _kernel_mask(keep)
        mask = S.mask_blocks(blocks, length)
        ctx, lse = S.attend(q, k, v, mask, TILE)
        probs = jnp.concatenate([
            jnp.pad(jnp.swapaxes(S.head_mean_probs(q, k, blk, lse, i, TILE),
                                 1, 2),
                    ((0, 0), (0, 0), (0, length - blk.shape[1])))
            for i, blk in enumerate(blocks)], axis=1)
        return (ctx, probs, *S.attend_bwd(q, k, v, mask, ctx, lse, cot, TILE),
                lse)

    ctx, probs, dq, dk, dv, lse = kernels(q, k, v, cot)
    near([t.astype(F32) for t in (ctx, probs, dq, dk, dv)],
         value_and_grads(lambda *a: _masked_reference(*a, keep),
                         *(t.astype(F32) for t in (q, k, v)),
                         cot=(cot, 0.0)), 2e-2)
    assert bool(jnp.all(jnp.isfinite(lse)))
    # a pair that is not selected has probability 0, exactly
    assert float(jnp.max(jnp.where(keep, 0.0, probs))) == 0.0


def test_rows_that_see_no_more_than_top_k_give_the_causal_kernel_s_values():
    """``top_k`` at the length: the selected set is the causal one and
    the kernels give ``flash_causal_gqa``'s values."""
    length = 3 * TILE
    *args, cot = _inputs(13, length, 4, 2)
    got = _outputs_and_grads(D._sparse_gqa_flash, args, cot, length)
    assert float(got[2]) == (length + 1) / 2
    # (one entry in 196,608 a bf16 rounding apart: XLA's CPU fusions of
    # the two kernels' tile code differ)
    near([got[0]] + got[3:6],
         value_and_grads(lambda *a: P.flash_causal_gqa(*a, TILE), *args[:3],
                         cot=cot), 1e-3)


@pytest.mark.parametrize("t", [0, 127, 128, 200, 300])
def test_a_key_after_position_t_never_reaches_output_t(t):
    q, k, v, iq, ik, iw, _ = _inputs(5, 3 * TILE, 4, 2)
    later = jnp.arange(3 * TILE) > t
    run = jax.jit(lambda *a: D._sparse_gqa_flash(*a, 48)[0])
    out = run(q, k, v, iq, ik, iw)
    moved = run(q, jnp.where(later[None, :, None, None], k + 3, k),
                jnp.where(later[None, :, None, None], v - 2, v), iq,
                jnp.where(later[None, :, None], ik + 1, ik), iw)
    np.testing.assert_array_equal(np.asarray(out[:, :t + 1], F32),
                                  np.asarray(moved[:, :t + 1], F32))
    assert not np.array_equal(np.asarray(out[:, t + 1:], F32),
                              np.asarray(moved[:, t + 1:], F32))


# ---------------------------------------------------------------------------
# which form a call takes, through the registered ops
# ---------------------------------------------------------------------------
@pytest.fixture
def counted():
    """{path: count} of the calls counted since the fixture began, in
    ``mx_attn_sparse_path_total`` or the counter named."""
    was = telemetry.enabled()
    telemetry.enable(True)
    start = {(c, p): telemetry.counter(c, path=p).get()
             for c, paths in PATHS.items() for p in paths}
    yield lambda counter=COUNTER: {
        p: telemetry.counter(c, path=p).get() - n
        for (c, p), n in start.items() if c == counter}
    telemetry.enable(was)


def _attention(q, k, v, iq, ik, iw):
    return get_op("_contrib_sparse_gqa_attention").impl(
        q, k, v, iq, ik, iw, jnp.zeros((2,), F32), top_k=48)


HIDDEN = 32


def _mixer_form(q, k, iq):
    """(the mixer op at q, k and the index queries' shapes, a hidden
    state and weights for it)."""
    b, length, heads, d = q.shape
    kv, (ih, idim) = k.shape[2], iq.shape[2:]
    keys = iter(jax.random.split(jax.random.key(7), 8))

    def w(*shape):
        return (0.3 * jax.random.normal(next(keys), shape, F32)) \
            .astype(q.dtype)

    ones = lambda n: jnp.ones((n,), q.dtype)
    args = (w(b, length, HIDDEN), ones(HIDDEN), w(heads * d, HIDDEN),
            w(kv * d, HIDDEN), w(kv * d, HIDDEN), w(HIDDEN, heads * d),
            ones(d), ones(d), w(ih * idim, HIDDEN), w(idim, HIDDEN),
            w(ih, HIDDEN), ones(idim), jnp.zeros((idim,), q.dtype))
    op = get_op("_contrib_sparse_gqa_mixer").impl
    return lambda *a: op(
        *a, jnp.zeros((2,), F32), num_heads=heads, num_kv_heads=kv,
        head_dim=d, index_heads=ih, index_head_dim=idim, top_k=48,
        rope_theta=1e7, rope_sections=(d // 8, 3 * d // 16, 3 * d // 16)), \
        args


def _forms(rung_inputs):
    """(the op, its arguments) for the attention op and for the mixer."""
    q, k, v, iq, ik, iw, _ = rung_inputs
    return {"op": (_attention, (q, k, v, iq, ik, iw)),
            "mixer": _mixer_form(q, k, iq)}


def _kernel_calls(fn, args):
    """How many of each kernel the gradient of both outputs holds, the
    recomputation included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    def loss(*a):
        out = fn(*a)
        return jnp.sum(out[0].astype(F32)) + out[1][0]

    floats = tuple(i for i, a in enumerate(args)
                   if jnp.issubdtype(a.dtype, jnp.floating))
    names = list(walk(jax.make_jaxpr(jax.grad(loss, floats))(*args).jaxpr))
    return {n: names.count(n) for n in set(names)}


def _two_devices():
    return auto_partitioned(Mesh(np.array(jax.devices()[:2]), ("dp",)))


RUNGS = {
    # name: (length, d, dtype, scope to trace in)
    "float32_inputs": (TILE, 128, F32, None),
    "two_device_mesh": (TILE, 128, BF, _two_devices),
    "ragged_length": (TILE + 8, 128, BF, None),
    "head_width_off_the_lanes": (TILE, 64, BF, None),
}


@pytest.mark.parametrize("form", ["op", "mixer"])
@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_each_rung_takes_the_composition_and_is_counted_masked(rung, form,
                                                               counted):
    length, d, dtype, scope = RUNGS[rung]
    inputs = _inputs(1, length, 2, 1, d, dtype=dtype, **SUMMED)
    fn, args = _forms(inputs)[form]
    # the index scores' kernels stand down on the same rungs (and go
    # with the flash form where only the attention's width refuses)
    index_too = rung != "head_width_off_the_lanes"
    with (scope or contextlib.nullcontext)():
        assert not S.sparse_gqa_available(*inputs[:3], TILE)
        assert I.index_scores_available(*inputs[3:6], TILE) != index_too
        calls = _kernel_calls(fn, args)
    assert calls == {}
    assert counted() == {"pallas": 0, "masked": 1}
    assert counted(INDEX_COUNTER) == {"pallas": 0, "xla": 1}


@pytest.mark.parametrize("form", ["op", "mixer"])
def test_no_chip_and_no_interpretation_asked_takes_the_composition(
        form, counted, monkeypatch):
    """Kernels that would be interpreted only because no chip is
    attached serve nothing: the call is the composition's, as on any
    CPU; where they will be compiled they serve."""
    inputs = _inputs(1, TILE, 2, 1, **SUMMED)
    fn, args = _forms(inputs)[form]
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET")
    assert pallas_common.interpret_mode()
    assert not S.sparse_gqa_available(*inputs[:3], TILE)
    assert not I.index_scores_available(*inputs[3:6], TILE)
    assert _kernel_calls(fn, args) == {}
    assert counted() == {"pallas": 0, "masked": 1}
    assert counted(INDEX_COUNTER) == {"pallas": 0, "xla": 1}
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    assert S.sparse_gqa_available(*inputs[:3], TILE)
    assert I.index_scores_available(*inputs[3:6], TILE)


# index heads the attention's kernels take with the scores' composition
INDEX_RUNGS = {"index_width_off_the_lanes": dict(ih=2, idim=8),
               "an_odd_head_of_64": dict(ih=3, idim=64)}


@pytest.mark.parametrize("form", ["op", "mixer"])
@pytest.mark.parametrize("index", ["summed", *sorted(INDEX_RUNGS)])
def test_an_eligible_call_takes_the_kernels_and_is_counted_pallas(
        index, form, counted):
    """Two query blocks: one forward kernel (the mixer's recomputation
    does not run it again), the probabilities a block in the forward
    and a block in the backward, one backward kernel. Index heads the
    scores' kernels serve: their forward once in the forward and once
    in the backward rule (the same kernel, so the same bits), one
    backward kernel, whatever the number of blocks; heads they cannot
    serve: the composition's scores under the same attention kernels,
    counted ``xla``."""
    summed = index == "summed"
    inputs = _inputs(2, 2 * TILE, 2, 1, **INDEX_RUNGS.get(index, SUMMED))
    fn, args = _forms(inputs)[form]
    assert S.sparse_gqa_available(*inputs[:3], TILE)
    assert I.index_scores_available(*inputs[3:6], TILE) == summed
    assert _kernel_calls(fn, args) == dict(
        {"pallas_sparse_gqa_fwd": 1, "pallas_sparse_gqa_probs": 4,
         "pallas_sparse_gqa_bwd": 1},
        **({"pallas_index_scores_fwd": 2, "pallas_index_scores_bwd": 1}
           if summed else {}))
    assert counted() == {"pallas": 1, "masked": 0}
    assert counted(INDEX_COUNTER) == {"pallas": int(summed),
                                      "xla": int(not summed)}


@pytest.mark.parametrize("index", [{}, SUMMED], ids=["index_xla", "summed"])
def test_the_op_on_the_kernel_path_gives_the_composition_s_values(
        index, monkeypatch):
    inputs = _inputs(3, 2 * TILE, 4, 2, **index)
    fn, args = _forms(inputs)["mixer"]

    def run():
        def loss(*a):
            y, index_loss, state = fn(*a)
            return jnp.sum(y.astype(F32)) + index_loss[0], (y, index_loss,
                                                            state)
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
        return [t.astype(F32) for t in out + grads]

    got = run()
    monkeypatch.setattr(S, "sparse_gqa_available", lambda *a: False)
    want = run()
    np.testing.assert_array_equal(got[2][0], want[2][0])   # keys a query
    near(got, want, 3e-2)


@pytest.mark.parametrize("index", [{}, SUMMED], ids=["index_xla", "summed"])
def test_the_mixer_on_the_kernel_path_keeps_thresholds_context_and_lse(
        index, capsys):
    """Beside its arguments the mixer's checkpoint keeps each row's
    threshold and tie count, the context and the rows' log-sum-exp: no
    mask, no probabilities, no projection, and no index score where the
    kernels sum them."""
    inputs = _inputs(4, 2 * TILE, 2, 1, **index)
    fn, args = _forms(inputs)["mixer"]

    def loss(*a):
        y, index_loss, _ = fn(*a)
        return jnp.sum(y.astype(F32)) + index_loss[0]

    jax.ad_checkpoint.print_saved_residuals(loss, *args)
    kept = [line.split(" ")[0] for line in capsys.readouterr().out
            .splitlines() if "from the argument" not in line
            and "from a constant" not in line]
    n = 2 * TILE
    assert sorted(kept) == sorted([
        "u32[1,%d]" % n, "i32[1,%d]" % n, "bf16[1,%d,2,128]" % n,
        "f32[1,2,1,%d]" % n])


def test_a_length_whose_mask_and_keys_do_not_fit_vmem_takes_the_composition(
        monkeypatch):
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    shape = lambda n, heads: jax.ShapeDtypeStruct((1, n, heads, 128), BF)
    for n, fits in ((1 << 13, True), (1 << 14, True), (1 << 15, False)):
        assert S.sparse_gqa_available(shape(n, 32), shape(n, 4), shape(n, 4),
                                      512) == fits
