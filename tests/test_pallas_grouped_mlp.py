"""The grouped kernels of the expert buffer (ops/pallas_grouped_mlp.py),
interpreted on the CPU at a lane tile's width: output and every gradient
(rows, slot weights, both weights, and the router's through the slot
weights) against the composition they stand in for and against a plain
float32 loop over the held experts (the benchmark's own references are
``tests/test_decoder_ops.py::test_routed_experts`` /
``test_softmax_swiglu_experts``, which run under both paths); the traps
of the weight-gradient kernel (an expert with no block, the empty blocks
past the last run, one expert drawing nearly every token); the empty
tail the kernels skip (``used``: any number of computed blocks gives the
all-blocks kernels' numbers bit for bit); the two kernels that hold the
activation and its derivative, each alone; the overflow path; the ladder
of what the kernels do not serve; the counter. What Mosaic makes of the
real widths is ``tests/test_chip_compile_*.py``'s to say."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import decoder_ops as D, pallas_common, pallas_moe_rows
from mxnet_tpu.ops import pallas_grouped_mlp as G
from numerics import BF, F32, near, normal, rand, value_and_grads

COUNTER = "mx_moe_experts_path_total"
HIDDEN = WIDTH = 128


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def _pallas_outs(jaxpr):
    """What each ``pallas_call`` of a jaxpr writes, in program order,
    those of the jaxprs its equations hold (a ``custom_vjp``'s, a
    ``jit``'s) among them."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([(v.aval.dtype, v.aval.shape) for v in eqn.outvars])
            continue
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                found += _pallas_outs(inner)
    return found


# ---------------------------------------------------------------------------
# the kernels alone: a buffer laid out by hand
# ---------------------------------------------------------------------------
ACTS = {"relu2": (D._relu2, 1), "swiglu": (D._swiglu, 2)}
# 8 blocks over 4 experts: a long run, an expert with no block, a run of
# one, and two empty blocks mapped to the last expert
EXPERT_OF_BLOCK = [0, 0, 0, 0, 1, 3, 3, 3]
USED = 6
BLOCK = 16


def _buffer(seed, act, eob=EXPERT_OF_BLOCK, used=USED):
    mul = ACTS[act][1]
    rows = len(eob) * BLOCK
    x, up, down = rand(seed, (rows, HIDDEN), (4, mul * WIDTH, HIDDEN),
                       (4, HIDDEN, WIDTH), scale=0.3, dtype=BF)
    filled = jnp.arange(rows) < used * BLOCK    # the blocks past: empty
    x = jnp.where(filled[:, None], x, 0).astype(BF)
    w = jnp.where(filled, jax.random.uniform(jax.random.key(seed + 1),
                                             (rows,), F32, 0.1, 1.0), 0.0)
    return x, jnp.array(eob, jnp.int32), w, up, down


def _by_hand(x, eob, w, up, down, act, experts=EXPERT_OF_BLOCK):
    """Block by block in float32, the casts where the paths make them."""
    out = []
    for b, e in enumerate(experts):
        xb = x[b * BLOCK:(b + 1) * BLOCK].astype(F32)
        h = ACTS[act][0](xb @ up[e].astype(F32).T).astype(BF).astype(F32)
        out.append(h @ down[e].astype(F32).T)
    return jnp.concatenate(out) * w[:, None]


@pytest.mark.parametrize("act", sorted(ACTS))
def test_kernels_against_the_composition_and_float32(interpreted, act):
    x, eob, w, up, down = _buffer(1, act)
    used = jnp.int32(USED)
    fn = ACTS[act][0]
    (cot,) = rand(3, x.shape, dtype=BF)
    cot = cot.astype(F32)

    def kernels(x, w, up, down):
        return jnp.sum(G.grouped_mlp(x, eob, used, w, up, down, fn)
                       .astype(F32) * cot)

    def composed(x, w, up, down):
        xr = x.reshape(len(EXPERT_OF_BLOCK), BLOCK, HIDDEN)
        y = (D._mm("bmf,bdf->bmd", fn(D._mm("bmd,bfd->bmf", xr, up[eob]))
                   .astype(BF), down[eob]).reshape(x.shape) * w[:, None])
        return jnp.sum(y.astype(BF).astype(F32) * cot)

    def plain(x, w, up, down):
        return jnp.sum(_by_hand(x, eob, w, up, down, act) * cot)

    assert G.grouped_mlp_available(
        x.reshape(len(EXPERT_OF_BLOCK), BLOCK, HIDDEN), up, down)
    assert _pallas_calls(jax.grad(kernels, (0, 1, 2, 3)), x, w, up, down) == 6
    near(jax.jit(lambda x, eob, *a: G.grouped_mlp(x, eob, used, *a, fn))(
        x, eob, w, up, down),
        jax.jit(lambda *a: _by_hand(*a, act))(x, eob, w, up, down), 2e-2)
    got, by_composition, by_hand = (
        jax.jit(jax.grad(loss, (0, 1, 2, 3)))(x, w, up, down)
        for loss in (kernels, composed, plain))
    near(got, by_composition, 2e-2)
    near(got, by_hand, 2e-2)
    # the expert no block is mapped to: exact zeros, not what the
    # kernel's unvisited tiles held; the empty blocks' rows likewise
    for dw in got[2:]:
        assert dw.dtype == BF
        assert float(jnp.max(jnp.abs(dw[2].astype(F32)))) == 0.0
        assert float(jnp.max(jnp.abs(dw[3].astype(F32)))) > 0.0
    assert float(jnp.max(jnp.abs(got[0][6 * BLOCK:].astype(F32)))) == 0.0


def test_weight_gradient_kernel_masks_what_it_never_visits(interpreted):
    """A tile of the output that no block is mapped to keeps the zeros
    of the array the call writes over: ``_dw`` returns zeros for that
    expert, and the last expert's sum includes the empty blocks'
    zeros."""
    g, x = rand(5, (8 * BLOCK, HIDDEN), (8 * BLOCK, WIDTH), dtype=BF)
    eob = jnp.array(EXPERT_OF_BLOCK, jnp.int32)
    dw = G._dw(g[None], x, eob, jnp.int32(len(EXPERT_OF_BLOCK)), 4)
    want = jnp.stack([
        sum((g[b * BLOCK:(b + 1) * BLOCK].astype(F32).T
             @ x[b * BLOCK:(b + 1) * BLOCK].astype(F32)
             for b, e in enumerate(EXPERT_OF_BLOCK) if e == held),
            jnp.zeros((HIDDEN, WIDTH), F32)) for held in range(4)])
    near(dw, want, 1e-2)
    assert float(jnp.max(jnp.abs(dw[2].astype(F32)))) == 0.0
    for held in (0, 1, 3):
        assert float(jnp.max(jnp.abs(dw[held].astype(F32)))) > 0.0


# (the expert of each block, the blocks that hold a row): what routing
# can leave in a packed buffer, the blocks past the last row mapped to
# the last expert
TAILS = {
    # every block skipped: dW all zeros, whatever the accumulator held
    "no_expert_routed_a_row": ([3] * 8, 0),
    # the tail's expert is not the last computed block's
    "one_block_and_a_last_expert_routed_nothing": ([1] + [3] * 7, 1),
    "a_tail_behind_the_last_experts_run": (EXPERT_OF_BLOCK, USED),
    # the accumulator holds expert 2's sum where expert 3's run begins
    "a_tail_and_a_last_expert_routed_nothing": ([0, 0, 0, 1, 2, 2, 3, 3], 6),
    "no_tail": (EXPERT_OF_BLOCK, len(EXPERT_OF_BLOCK)),
    # the tail begins inside the first expert's run: the blocks behind
    # it are that expert's and two more experts', all skipped
    "a_tail_from_inside_the_first_run": (EXPERT_OF_BLOCK, 3),
    # one block short of the whole buffer
    "all_but_the_last_block": ([0, 1, 1, 2, 2, 2, 3, 3], 7),
}


def _stacks(up, act):
    return G._stacks(up, ACTS[act][1])


def _plain_pre(x, eob_list, up):
    """The first product in float32, block by block: (rows, f1)."""
    return jnp.concatenate([
        x[b * BLOCK:(b + 1) * BLOCK].astype(F32) @ up[e].astype(F32).T
        for b, e in enumerate(eob_list)])


def _piece_major(a, act):
    """(rows, pieces x f) as the kernels keep it: (pieces, rows, f)."""
    return jnp.stack(jnp.split(a, ACTS[act][1], axis=-1))


@pytest.mark.parametrize("act", sorted(ACTS))
def test_up_kernel_alone_activates_in_vmem(interpreted, act):
    """``pallas_grouped_mlp_up`` against the float32 loop: ``h`` in
    bf16, and where it is asked to keep it the float32 ``pre``,
    piece-major; the call that keeps nothing has one output, the same
    ``h`` bit for bit, and no float32 array of the buffer's length."""
    x, eob, w, up, down = _buffer(31, act)
    fn, pieces = ACTS[act]
    used = jnp.int32(USED)
    h, pre = jax.jit(lambda x, up: G._up(x, up, eob, used, fn, pieces, True))(
        x, up)
    (alone,) = jax.jit(lambda x, up: G._up(x, up, eob, used, fn, pieces,
                                           False))(x, up)
    want = _plain_pre(x, EXPERT_OF_BLOCK, up)
    assert (h.dtype, pre.dtype) == (BF, F32)
    assert pre.shape == (pieces, x.shape[0], WIDTH)
    near(pre, _piece_major(want, act), 1e-5)
    near(h, fn(want), 1e-2)
    np.testing.assert_array_equal(np.asarray(h.astype(F32)),
                                  np.asarray(alone.astype(F32)))
    # the blocks past ``used``: zeros, written and not computed
    assert float(jnp.max(jnp.abs(pre[:, USED * BLOCK:]))) == 0.0
    assert float(jnp.max(jnp.abs(h[USED * BLOCK:].astype(F32)))) == 0.0


@pytest.mark.parametrize("act", sorted(ACTS))
def test_dh_kernel_alone_differentiates_in_vmem(interpreted, act):
    """``pallas_grouped_mlp_dh`` against XLA's ``jax.vjp`` of the
    activation on the float32 ``dh``: ``dpre`` in bf16, piece-major, the
    slot weights' gradient from the bf16-rounded ``h``, and that ``h``,
    the forward's bit for bit."""
    x, eob, w, up, down = _buffer(33, act)
    fn = ACTS[act][0]
    (g,) = rand(35, x.shape, dtype=BF)
    forward, kept = jax.jit(lambda x, up: G._up(
        x, up, eob, jnp.int32(USED), fn, ACTS[act][1], True))(x, up)
    pre = jnp.concatenate(list(kept), axis=-1)      # as ``act`` reads it
    dpre, d_weight, h = jax.jit(lambda g, down, pre, w: G._dh(
        g, down, pre, eob, jnp.int32(USED), w, fn))(g, down, kept, w)

    @jax.jit
    def plain(g, down, pre, w):
        dh = jnp.concatenate([
            g[b * BLOCK:(b + 1) * BLOCK].astype(F32) @ down[e].astype(F32)
            for b, e in enumerate(EXPERT_OF_BLOCK)])
        h, pull = jax.vjp(fn, pre)
        return (pull(dh * w[:, None])[0],
                jnp.sum(h.astype(BF).astype(F32) * dh, axis=-1))

    want_dpre, want_weight = plain(g, down, pre, w)
    filled = (jnp.arange(x.shape[0]) < USED * BLOCK)
    assert (dpre.dtype, d_weight.dtype, h.dtype) == (BF, F32, BF)
    np.testing.assert_array_equal(np.asarray(h.astype(F32)),
                                  np.asarray(forward.astype(F32)))
    near(dpre, _piece_major(jnp.where(filled[:, None], want_dpre, 0), act),
         1e-2)
    near(d_weight, jnp.where(filled, want_weight, 0), 1e-3)


@pytest.mark.parametrize("act", sorted(ACTS))
def test_the_primal_call_keeps_no_pre(interpreted, act):
    """The plain primal (what the mixer's forward pass runs: nothing
    there reads ``pre``) is two calls, neither with a float32 output;
    the differentiated forward is the same two, the first writing
    ``pre`` beside ``h``, and keeps ``pre`` alone of the two."""
    x, eob, w, up, down = _buffer(37, act)
    fn, pieces = ACTS[act]
    used = jnp.int32(USED)

    def primal(x, w, up, down):
        return G.grouped_mlp(x, eob, used, w, up, down, fn)

    def kept(x, w, up, down):
        return G._vjp_fwd(x, eob, used, w, up, down, fn)

    def outs(fn):
        return _pallas_outs(jax.make_jaxpr(fn)(x, w, up, down).jaxpr)

    rows = x.shape[0]
    h, y = (BF, (rows, WIDTH)), (BF, (rows, HIDDEN))
    assert _pallas_calls(primal, x, w, up, down) == 2
    assert outs(primal) == [[h], [y]]
    assert outs(kept) == [[h, (F32, (pieces, rows, WIDTH))], [y]]
    # (the rows of the buffer have ``h``'s shape at these sizes: one)
    kept_shapes = [(r.dtype, r.shape) for r in jax.eval_shape(
        kept, x, w, up, down)[1]]
    assert kept_shapes.count((F32, (pieces, rows, WIDTH))) == 1
    assert kept_shapes.count(h) == 1


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("tail", sorted(TAILS))
def test_skipping_the_empty_tail_changes_no_number(interpreted, tail, act):
    """Each kernel alone (``up`` keeping ``pre`` and not, ``nt`` with the
    row scale, ``dh``, ``nn``, ``dw`` with and without the scale) and
    ``grouped_mlp``'s value and four gradients, computing ``used``
    blocks of a buffer whose other blocks are zero rows of weight 0: the
    all-blocks kernels' numbers (``used`` = every block) exactly, and
    the float32 loop's within bf16."""
    experts, used = TAILS[tail]
    blocks = len(experts)
    x, eob, w, up, down = _buffer(21, act, experts, used)
    (g,) = rand(23, x.shape, dtype=BF)
    g = jnp.where((w > 0)[:, None], g, 0).astype(BF)    # no row, no cotangent
    fn, pieces = ACTS[act]

    @jax.jit
    def kernels(used):
        out, pull = jax.vjp(lambda x, w, up, down: G.grouped_mlp(
            x, eob, used, w, up, down, fn), x, w, up, down)
        h, pre = G._up(x, up, eob, used, fn, pieces, True)
        dpre, d_weight, again = G._dh(g, down, pre, eob, used, w, fn)
        return dict(
            up=(h, pre), up_alone=G._up(x, up, eob, used, fn, pieces, False),
            nt_scaled=G._rows(h[None], down[:, None], eob, used, True, w),
            dh=(dpre, d_weight, again),
            nn=G._rows(dpre, _stacks(up, act), eob, used, False),
            dw=G._dw(dpre, x, eob, used, 4),
            dw_scaled=G._dw(g[None], h, eob, used, 4, w),
            value=out, grads=pull(g))

    skipping, whole = kernels(jnp.int32(used)), kernels(jnp.int32(blocks))
    for got, want in zip(jax.tree_util.tree_leaves(skipping),
                         jax.tree_util.tree_leaves(whole), strict=True):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                      np.asarray(want.astype(F32)))
    if used:
        near([skipping["value"], *skipping["grads"]], value_and_grads(
            lambda x, w, up, down: _by_hand(x, eob, w, up, down, act,
                                            experts),
            x, w, up, down, cot=g), 2e-2)
    # an expert with no computed block: exact zeros from both weight
    # gradients and both bare kernels, not a neighbour's sum
    for e in sorted(set(range(4)) - set(experts[:used])):
        for dw in (skipping["dw"], skipping["dw_scaled"],
                   *skipping["grads"][2:]):
            assert float(jnp.max(jnp.abs(dw[e].astype(F32)))) == 0.0
    for e in set(experts[:used]):
        assert float(jnp.max(jnp.abs(skipping["dw"][e].astype(F32)))) > 0.0
    # and a skipped block's rows, the kept ``pre`` and ``dpre`` among them
    for rows in (skipping["up"][0], skipping["nt_scaled"], skipping["nn"],
                 skipping["dh"][1][:, None], skipping["dh"][2],
                 skipping["value"],
                 skipping["grads"][0], *skipping["up"][1],
                 *skipping["dh"][0]):
        assert float(jnp.max(jnp.abs(rows[used * BLOCK:].astype(F32)),
                             initial=0.0)) == 0.0


# ---------------------------------------------------------------------------
# through the op: routing decides the buffer
# ---------------------------------------------------------------------------
def _layer(seed, act, tokens=256, routed=16, held=4, offset=4):
    mul = ACTS[act][1]
    x, up, down = rand(seed, (tokens, HIDDEN), (held, mul * WIDTH, HIDDEN),
                       (held, HIDDEN, WIDTH), scale=0.3, dtype=BF)
    r = normal(jax.random.key(seed + 7), (routed, HIDDEN), scale=0.3)
    return x, r, up, down, offset


def _experts(x, r, bias, up, down, act, offset, **kw):
    score = "softmax" if act == "swiglu" else "sigmoid"
    y, rows = D._moe_experts(x, r, bias, up, down, top_k=2, offset=offset,
                             scale=1.5, norm_topk=True, score_func=score,
                             activation=act, **kw)
    return y.astype(F32), rows


def _reference(x, r, bias, up, down, act, offset):
    """A float32 loop over the held experts, each over every token,
    weighted by the slot that chose it, on the values the op was
    given."""
    score = "softmax" if act == "swiglu" else "sigmoid"
    x, up, down = (a.astype(F32) for a in (x, up, down))
    chosen, wk = D._route(x, r, bias, 2, 1.5, True, score)
    y = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        we = jnp.sum(jnp.where(chosen == offset + e, wk, 0.0), axis=-1)
        y = y + we[:, None] * jnp.matmul(
            ACTS[act][0](jnp.matmul(x, up[e].T, precision="highest")),
            down[e].T, precision="highest")
    return y


def _favouring(expert, strength, routed=16):
    return jnp.zeros((routed,), F32).at[expert].set(strength)


CASES = {
    # what the router alone decides: every held expert a few rows
    "even": lambda: jnp.zeros((16,), F32),
    # one held expert drawing nearly every token (the Mellum 2 cell's
    # layer 3: 14,016 of 16,384): a long run, short ones, empty blocks
    "one_draws_most": lambda: _favouring(5, 10.0),
    # a held expert no token is routed to
    "one_draws_none": lambda: _favouring(6, -10.0),
    # no token to any held expert: every block empty, mapped to the last
    "none_held": lambda: jnp.zeros((16,), F32).at[jnp.array([0, 1])]
    .set(10.0),
    # the last two held experts routed nothing, the first two a block
    # each: 2 of the buffer's 8 blocks hold a row, and the tail's expert
    # is not the last computed block's
    "quarter_full": lambda: jnp.zeros((16,), F32).at[jnp.array([6, 7])]
    .set(-10.0),
}


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_expert_layer_by_the_kernels(interpreted, monkeypatch, case, act):
    x, r, up, down, offset = _layer(11, act)
    bias = CASES[case]()

    (cot,) = rand(13, x.shape, dtype=BF)

    def loss(fn):
        def of(x, r, up, down):
            y, rows = fn(x, r, bias, up, down, act, offset)
            return jnp.sum(y * cot.astype(F32)), (y, rows)
        return jax.value_and_grad(of, (0, 1, 2, 3), has_aux=True)

    def run(fn):
        """(y, rows), the gradients: traced now, one compiled program."""
        (_, out), grads = jax.jit(loss(fn))(*args)
        return out, grads

    def op(*a):
        return _experts(*a)

    def ref(*a):
        return _reference(*a), None

    args = (x, r, up, down)
    assert _pallas_calls(loss(op), *args) > 0
    (y, rows), got = run(op)
    (y_ref, _), want = run(ref)
    near(y, y_ref, 2e-2)
    near(got, want, 3e-2)
    # the composition on the same call: the same rows counted, numbers
    # and gradients within bf16 of each other
    # (the slot sum's kernel, ops/pallas_moe_rows.py, forks on its own
    # predicate: it stands down with them here)
    monkeypatch.setattr(G, "grouped_mlp_available", lambda *a: False)
    monkeypatch.setattr(pallas_moe_rows, "sum_available", lambda *a: False)
    assert _pallas_calls(loss(op), *args) == 0
    (y_xla, rows_xla), got_xla = run(op)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_xla))
    near(y, y_xla, 2e-2)
    near(got, got_xla, 3e-2)
    counts = np.asarray(rows[0])
    if case == "one_draws_most":
        assert counts[1] > 0.8 * x.shape[0]
    if case == "none_held":
        assert not counts.any() and float(jnp.max(jnp.abs(y))) == 0.0
    if case == "quarter_full":
        block, blocks, _ = D._buffer(x.shape[0], 2, 4, 16)
        assert (blocks, np.sum(-(-counts // block))) == (8, 2)
        assert counts[:2].all() and not counts[2:].any()
    for dw in got[2:]:      # an expert routed no row: exact zeros
        for e in np.flatnonzero(counts == 0):
            assert float(jnp.max(jnp.abs(dw[e].astype(F32)))) == 0.0


@pytest.mark.parametrize("act", sorted(ACTS))
def test_overflowing_routing_still_takes_the_dense_product(interpreted, act):
    """A buffer a quarter of the default's with blocks of a bf16 tile:
    the kernels are in the program (the sorted branch), the routing
    overfills the buffer, the dense branch runs, and no row is
    dropped."""
    x, r, up, down, offset = _layer(17, act, tokens=512, held=8, offset=0)
    bias = _favouring(1, 10.0)
    kw = dict(capacity_factor=0.5)

    def fn(x):
        return _experts(x, r, bias, up, down, act, offset, **kw)[0]

    text = str(jax.make_jaxpr(fn)(x))
    assert "pallas_call" in text and "cond" in text
    y, rows = jax.jit(lambda x: _experts(x, r, bias, up, down, act, offset,
                                         **kw))(x)
    near(y, jax.jit(lambda x: _reference(x, r, bias, up, down, act,
                                         offset))(x), 2e-2)
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(rows[1]))
    assert float(rows[0][1]) > 384      # it did overflow the 384 rows


# ---------------------------------------------------------------------------
# what the kernels do not serve, and the counter
# ---------------------------------------------------------------------------
def _shapes(block=16, hidden=128, f1=256, f=128, dtype=BF, wdtype=BF):
    return (jax.ShapeDtypeStruct((4, block, hidden), dtype),
            jax.ShapeDtypeStruct((2, f1, hidden), wdtype),
            jax.ShapeDtypeStruct((2, hidden, f), wdtype))


LADDER = {
    "float32 rows": dict(dtype=F32),
    "float32 weights": dict(wdtype=F32),
    "a block off the bf16 tile": dict(block=8),
    "a toy hidden size": dict(hidden=32),
    "a toy width": dict(f1=64, f=32),
    "a width off the lane tiles": dict(f1=1856, f=1856),
    "tiles beyond the budget": dict(block=4096, hidden=8192),
}


@pytest.mark.parametrize("rung", sorted(LADDER))
def test_what_the_kernels_leave_to_the_composition(interpreted, rung):
    assert G.grouped_mlp_available(*_shapes())
    assert not G.grouped_mlp_available(*_shapes(**LADDER[rung]))


def test_the_cells_widths(interpreted):
    for block, hidden, f1, f in [(512, 2304, 1792, 896),     # Mellum 2
                                 (512, 2048, 1536, 768)]:    # Keye-VL
        assert G.grouped_mlp_available(*_shapes(block, hidden, f1, f))
    assert (G._tile(1792), G._tile(2304), G._tile(2048)) == (896, 768, 1024)
    # the Nemotron cell's 1,856 is 14.5 lane tiles: the composition
    # (Mosaic takes it whole, the compile cache's layouts do not)
    assert not G.grouped_mlp_available(*_shapes(512, 2688, 1856, 1856))


def test_a_plain_cpu_keeps_the_composition():
    assert pallas_common.interpret_mode() \
        and not pallas_common.interpret_asked()
    assert not G.grouped_mlp_available(*_shapes())


def test_a_mesh_of_several_devices_stands_the_kernels_down(interpreted):
    from jax.sharding import Mesh
    devices = np.array(jax.devices()[:2])
    with pallas_common.auto_partitioned(Mesh(devices, ("dp",))):
        assert not G.grouped_mlp_available(*_shapes())
    with pallas_common.auto_partitioned(Mesh(devices[:1], ("dp",))):
        assert G.grouped_mlp_available(*_shapes())


def _count(path):
    return telemetry.counter(COUNTER, path=path).get()


@pytest.fixture
def counting():
    was = telemetry.enabled()
    telemetry.enable(True)
    yield
    telemetry.enable(was)


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_a_traced_call_is_counted_once_under_its_path(monkeypatch, counting,
                                                      path):
    if path == "pallas":
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    x, r, up, down, offset = _layer(19, "swiglu")
    before = {p: _count(p) for p in ("pallas", "xla")}
    fn = jax.jit(jax.grad(lambda x: jnp.sum(
        _experts(x, r, None, up, down, "swiglu", offset)[0])))
    fn(x)
    fn(x)       # compiled: not traced, not counted again
    other = "xla" if path == "pallas" else "pallas"
    assert _count(path) == before[path] + 1
    assert _count(other) == before[other]
