"""The slot sum's window kernel (ops/pallas_moe_rows.py), interpreted on
the CPU at scaled-down shapes of each decoder cell: against the XLA form
it stands in for, bit for bit (float32 sums of a token's few bf16 rows
are exact in either order at these magnitudes); ``_gather_rows`` /
``_sum_slots`` as each other's transposes with the kernel on one side;
the expert layer end to end on both branches of ``fits``; what stands
the kernel down; the counter. What Mosaic makes of the real shapes is
``tests/test_chip_compile_*.py``'s to say."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import decoder_ops as D, pallas_common
from mxnet_tpu.ops import pallas_moe_rows as R
from numerics import BF, F32, jitted, near, normal, value_and_grads

COUNTER = "mx_moe_rows_path_total"


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


def _xla_sum(rows, token_of_row, row_of_slot):
    ext = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return jnp.sum(ext[row_of_slot].astype(F32), axis=1).astype(rows.dtype)


def _xla_gather(x, token_of_row, row_of_slot):
    return jnp.concatenate([x, jnp.zeros_like(x[:1])])[token_of_row]


def _routing(seed, tokens, hidden, routed, held, top_k, bias=None,
             capacity_factor=D.CAPACITY_FACTOR):
    """(x, token_of_row, row_of_slot, buffer rows, fits) as
    ``_moe_experts`` lays the buffer out for the first ``held`` of
    ``routed`` experts."""
    kx, kr = jax.random.split(jax.random.key(seed))
    x = normal(kx, (tokens, hidden), BF)
    r = normal(kr, (routed, hidden), scale=0.3)
    block, blocks, _ = D._buffer(tokens, top_k, held, routed, capacity_factor)
    cap = blocks * block

    @jax.jit
    def lay_out(x, r, bias):
        idx, _ = D._route(x, r, bias, top_k, 1.0, True, "softmax")
        row, _, _, _, fits = D._slots_to_rows(idx < held, idx, held, cap,
                                              block)
        slots = jnp.broadcast_to(jnp.arange(tokens)[:, None], row.shape)
        token_of_row = jnp.full((cap + 1,), tokens, jnp.int32) \
            .at[row.reshape(-1)].set(slots.reshape(-1))[:-1]
        return token_of_row, row, fits

    token_of_row, row, fits = lay_out(x, r, bias)
    return x, token_of_row, row, cap, bool(fits)


def _favouring(expert, strength, routed):
    return jnp.zeros((routed,), F32).at[expert].set(strength)


# tokens, hidden, routed, held, top_k, score bias: the cells' ratios of
# held to routed experts and their top_k, hidden sizes of 1 to 3 lane
# tiles (the Nemotron cell's 21 is odd too)
CASES = {
    "mellum2 (16 of 64, top 8)": (512, 256, 16, 4, 8, None),
    "laguna (32 of 256, top 8): a quarter filled": (512, 256, 64, 8, 8, None),
    "keye-vl (16 of 128, top 8)": (512, 128, 32, 4, 8, None),
    "glm (8 of 64, top 4)": (256, 256, 16, 2, 4, None),
    "nemotron (8 of 128, top 6), 3 lane tiles": (512, 384, 32, 2, 6, None),
    "all rows empty": (256, 128, 16, 4, 4,
                       _favouring(jnp.arange(8, 12), 10.0, 16)),
    "one expert draws most tokens": (512, 128, 16, 4, 4,
                                     _favouring(1, 10.0, 16)),
    "96 tokens, every expert held": (96, 128, 8, 8, 6, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_sums_what_xla_sums(interpreted, case):
    tokens, hidden, routed, held, top_k, bias = CASES[case]
    _, token_of_row, row, cap, fits = _routing(3, tokens, hidden, routed,
                                               held, top_k, bias)
    assert fits
    rows = normal(jax.random.key(5), (cap, hidden), BF)
    assert R.sum_available(rows, top_k, tokens)
    # slots one past the end: wherever not every expert is held
    assert (int(jnp.sum(row == cap)) > 0) == (held < routed)
    if case == "all rows empty":
        assert int(jnp.sum(row < cap)) == 0
    got = jitted(R.sum_slots)(rows, token_of_row, row)
    assert got.dtype == BF and got.shape == (tokens, hidden)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jitted(_xla_sum)(rows, token_of_row, row), np.float32))


@pytest.mark.parametrize("window, group", [(16, 4), (32, 2), (64, 3)])
def test_windows_and_groups_of_other_sizes(interpreted, monkeypatch, window,
                                           group):
    """Several groups a block of tokens, a last group partly used, and
    windows that straddle two experts' runs."""
    monkeypatch.setattr(R, "_WINDOW", window)
    monkeypatch.setattr(R, "_GROUP", group)
    monkeypatch.setattr(R, "_TOKENS", 128)
    R._sum_call.cache_clear()
    _, token_of_row, row, cap, _ = _routing(7, 512, 128, 16, 4, 8)
    rows = normal(jax.random.key(9), (cap, 128), BF)
    order, count = jax.jit(lambda t: R._windows(t, 512, 128))(token_of_row)
    assert order.shape == (4 * cap // window,)
    assert int(count.max()) > group and any(int(c) % group for c in count)
    got = jitted(R.sum_slots)(rows, token_of_row, row)
    # the kernel was built at this window and group, not found traced
    assert R._sum_call.cache_info().currsize == 1
    R._sum_call.cache_clear()
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jitted(_xla_sum)(rows, token_of_row, row), np.float32))


def test_the_windows_listed_are_the_windows_needed(interpreted):
    _, token_of_row, row, cap, _ = _routing(11, 512, 128, 16, 4, 8)
    tokens = 128
    order, count = jax.jit(lambda t: R._windows(t, 512, tokens))(token_of_row)
    order = np.asarray(order).reshape(512 // tokens, cap // R._WINDOW)
    row = np.asarray(row)
    for b in range(512 // tokens):
        mine = row[b * tokens:(b + 1) * tokens]
        np.testing.assert_array_equal(
            order[b, :int(count[b])],
            np.unique(mine[mine < cap] // R._WINDOW))


def test_rows_in_any_order_are_still_summed(interpreted):
    """Nothing but the cost rests on the buffer's order: with the
    buffer's rows shuffled (a block of tokens then needs many more
    windows) the sums are the same."""
    _, token_of_row, row, cap, _ = _routing(11, 512, 128, 16, 4, 8)
    shuffle = jax.random.permutation(jax.random.key(1), cap)
    where = jnp.argsort(shuffle)            # old row -> new row
    token_of_row = token_of_row[shuffle]
    row = jnp.where(row < cap, where[jnp.minimum(row, cap - 1)], cap)
    rows = normal(jax.random.key(9), (cap, 128), BF)
    np.testing.assert_array_equal(
        np.asarray(jitted(R.sum_slots)(rows, token_of_row, row), np.float32),
        np.asarray(jitted(_xla_sum)(rows, token_of_row, row), np.float32))


@pytest.mark.parametrize("case", ["mellum2 (16 of 64, top 8)",
                                  "glm (8 of 64, top 4)",
                                  "all rows empty"])
def test_each_is_the_other_s_transpose(interpreted, monkeypatch, case):
    """``_sum_slots``' pullback is XLA's gather, ``_gather_rows``' is
    the kernel: both against ``jax.vjp`` of the XLA forms in float32
    (which scatter-adds), cast once."""
    tokens, hidden, routed, held, top_k, bias = CASES[case]
    x, token_of_row, row, cap, _ = _routing(13, tokens, hidden, routed, held,
                                            top_k, bias)
    rows = normal(jax.random.key(15), (cap, hidden), BF)

    def same(got, want):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want.astype(BF), np.float32))

    def pulled(fn, primal, cot):
        return jax.vjp(fn, primal)[1](cot)[0]

    def sum_slots(r):
        return D._sum_slots(r, token_of_row, row, True)

    assert "pallas_call" in str(jax.make_jaxpr(sum_slots)(rows))
    y, pull = value_and_grads(sum_slots, rows, cot=x)
    same(y, jitted(_xla_sum)(rows, token_of_row, row))
    same(pull, value_and_grads(
        lambda r: _xla_sum(r, token_of_row, row).astype(F32),
        rows.astype(F32), cot=x)[1])

    def gather_rows(a):
        return D._gather_rows(a, token_of_row, row, True)

    assert "pallas_call" in str(jax.make_jaxpr(
        lambda a, c: pulled(gather_rows, a, c))(x, rows))
    xr, pull = value_and_grads(gather_rows, x, cot=rows)
    same(xr, jitted(_xla_gather)(x, token_of_row, row))
    same(pull, value_and_grads(lambda a: _xla_gather(a, token_of_row, row),
                               x.astype(F32), cot=rows)[1])


# ---------------------------------------------------------------------------
# through the op, on both branches of ``fits``
# ---------------------------------------------------------------------------
def _layer(seed, tokens=256, hidden=128, width=128, routed=16, held=4):
    keys = jax.random.split(jax.random.key(seed), 4)
    x = normal(keys[0], (tokens, hidden), BF, 0.3)
    up = normal(keys[1], (held, 2 * width, hidden), BF, 0.3)
    down = normal(keys[2], (held, hidden, width), BF, 0.3)
    r = normal(keys[3], (routed, hidden), scale=0.3)
    return x, r, up, down


@pytest.mark.parametrize("branch", ["sorted", "dense"])
@pytest.mark.parametrize("top_k", [4, 6, 8])
def test_expert_layer_with_the_kernel_in_it(interpreted, monkeypatch, branch,
                                            top_k):
    """The layer's output, its counted rows and every gradient with the
    window kernel in the program (interpreted) against the same call
    with it stood down; where the routing overfills the buffer the
    dense branch runs and the kernel, though in the program, does
    not."""
    x, r, up, down = _layer(17 + top_k)
    bias = _favouring(5, 10.0, 16) if branch == "dense" else None
    kw = dict(capacity_factor=0.5) if branch == "dense" else {}
    cot = normal(jax.random.key(19), x.shape)

    def loss(x, r, up, down):
        y, rows = D._moe_experts(
            x, r, bias, up, down, top_k=top_k, offset=4, scale=1.5,
            norm_topk=True, score_func="softmax", activation="swiglu", **kw)
        return jnp.sum(y.astype(F32) * cot), rows

    def grad():     # (a new function each time: traced again)
        return jax.value_and_grad(lambda *a: loss(*a), (0, 1, 2, 3),
                                  has_aux=True)

    args = (x, r, up, down)
    text = str(jax.make_jaxpr(grad())(*args))
    assert "pallas_moe_rows_sum" in text and "cond" in text
    (value, rows), got = jax.jit(grad())(*args)
    # nearly every token chooses the favoured expert, or about top_k / 16
    assert (float(rows[0].max()) > 0.9 * 256) == (branch == "dense")
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(rows[1]))
    monkeypatch.setattr(R, "sum_available", lambda *a: False)
    assert "pallas_moe_rows_sum" not in str(jax.make_jaxpr(grad())(*args))
    (value_xla, rows_xla), got_xla = jax.jit(grad())(*args)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_xla))
    near(value, value_xla, 1e-2)
    near(got, got_xla, 3e-2)


# ---------------------------------------------------------------------------
# what the kernel does not serve, and the counter
# ---------------------------------------------------------------------------
def _rows(cap=512, hidden=256, dtype=BF):
    return jax.ShapeDtypeStruct((cap, hidden), dtype)


LADDER = {
    "float32 rows": (_rows(dtype=F32), 8, 256),
    "a hidden size off the lane tiles": (_rows(hidden=192), 8, 256),
    "a toy hidden size": (_rows(hidden=32), 8, 256),
    "a buffer that is not whole windows": (_rows(cap=528), 8, 256),
    "no row in the buffer": (_rows(cap=0), 8, 256),
    "tokens off the bf16 tile": (_rows(), 8, 250),
    "a working set beyond the budget": (_rows(hidden=16384), 8, 512),
}


@pytest.mark.parametrize("rung", sorted(LADDER))
def test_what_the_kernel_leaves_to_xla(interpreted, rung):
    assert R.sum_available(_rows(), 8, 256)
    assert not R.sum_available(*LADDER[rung])


def test_a_plain_cpu_keeps_xla_s_gather():
    assert pallas_common.interpret_mode() \
        and not pallas_common.interpret_asked()
    assert not R.sum_available(_rows(), 8, 256)
    x, r, up, down = _layer(29)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda x: D._moe_experts(
            x, r, None, up, down, top_k=4, offset=4, scale=1.0,
            norm_topk=True, score_func="softmax",
            activation="swiglu")[0])(x))


def test_a_mesh_of_several_devices_stands_the_kernel_down(interpreted):
    from jax.sharding import Mesh
    devices = np.array(jax.devices()[:2])
    with pallas_common.auto_partitioned(Mesh(devices, ("dp",))):
        assert not R.sum_available(_rows(), 8, 256)
    with pallas_common.auto_partitioned(Mesh(devices[:1], ("dp",))):
        assert R.sum_available(_rows(), 8, 256)


@pytest.mark.parametrize("cell, cap, hidden, top_k, tokens", [
    ("mellum2", 73728, 2304, 8, 16384), ("laguna", 32768, 2048, 8, 8192),
    ("keye-vl", 24576, 2048, 8, 8192), ("glm", 12288, 2048, 4, 8192),
    # 21 lane tiles: no 32-bit view is taken, so an odd count serves
    ("nemotron", 10240, 2688, 6, 8192)])
def test_the_cells_buffers_are_served(interpreted, cell, cap, hidden, top_k,
                                      tokens):
    assert R.sum_available(_rows(cap, hidden), top_k, tokens)
    assert R._tokens(tokens) == 512 and cap % R._WINDOW == 0


@pytest.fixture
def counting():
    was = telemetry.enabled()
    telemetry.enable(True)
    yield
    telemetry.enable(was)


def _count(path):
    return telemetry.counter(COUNTER, path=path).get()


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_a_traced_layer_is_counted_once_under_its_path(monkeypatch, counting,
                                                       path):
    if path == "pallas":
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    x, r, up, down = _layer(23)
    before = {p: _count(p) for p in ("pallas", "xla")}
    fn = jax.jit(jax.grad(lambda x: jnp.sum(D._moe_experts(
        x, r, None, up, down, top_k=4, offset=4, scale=1.0, norm_topk=True,
        score_func="softmax", activation="swiglu")[0].astype(F32))))
    fn(x)
    fn(x)       # compiled: not traced, not counted again
    other = "xla" if path == "pallas" else "pallas"
    assert _count(path) == before[path] + 1
    assert _count(other) == before[other]
