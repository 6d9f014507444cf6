"""Packed-QKV flash attention (round 7, ISSUE 14): the Pallas kernel
consumes and produces the reference-packed (L, N, heads*3*hd) layout
directly — no reshape+transpose chain between the QKV projection and
the kernel (the r6 transpose_jvp residual). Interpret mode on CPU;
Mosaic-compiled on a real chip via tools/bert_bench.py.

Suite pins MXNET_PALLAS_INTERPRET so it runs identically everywhere
(the pallas_norm pattern)."""
import os

import numpy as np
import pytest

import jax
import jax.extend.core
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_attention
from mxnet_tpu.ops.pallas_attention import (_keep_mask, flash_selfatt,
                                            flash_selfatt_available,
                                            selfatt_plan)
from mxnet_tpu.ops.contrib_ops import (interleaved_matmul_selfatt_qk,
                                       interleaved_matmul_selfatt_valatt)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    yield


def _ref(qkv, heads, att_hook=None):
    sc = interleaved_matmul_selfatt_qk(qkv, heads=heads)
    att = jax.nn.softmax(sc, axis=-1)
    if att_hook is not None:
        att = att_hook(att)
    return interleaved_matmul_selfatt_valatt(qkv, att, heads=heads)


def _ref_chain(qkv, heads):
    """The kernel's exact dtype chain as plain jnp ops: bf16 operands,
    f32 scores/softmax, bf16 probability matmul operand, bf16 output —
    the bitwise forward reference."""
    L, N, thd = qkv.shape
    d = thd // (3 * heads)
    x = qkv.astype(jnp.bfloat16).reshape(L, N, heads, 3 * d)
    q = x[..., :d].astype(jnp.float32) * (1.0 / np.sqrt(d))
    k = x[..., d:2 * d].astype(jnp.float32)
    v = x[..., 2 * d:]
    s = jnp.einsum("lnhe,mnhe->nhlm", q, k,
                   preferred_element_type=jnp.float32)
    m = jnp.max(s, axis=3, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=3, keepdims=True)
    o = jnp.einsum("nhlm,mnhe->lnhe", p.astype(jnp.bfloat16), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(L, N, heads * d).astype(jnp.bfloat16) \
        .astype(qkv.dtype)


def _rand_qkv(rng, L, N, H, d):
    return jnp.asarray(rng.randn(L, N, H * 3 * d).astype(np.float32))


def _grad_gap(f, g, qkv, r):
    """The largest difference of the two gradients of sum(. * r), over
    the largest entry of ``g``'s: each traced and compiled once."""
    g1 = jax.jit(jax.grad(lambda q: jnp.sum(f(q) * r)))(qkv)
    g2 = jax.jit(jax.grad(lambda q: jnp.sum(g(q) * r)))(qkv)
    return float(jnp.max(jnp.abs(g1 - g2))) \
        / (float(jnp.max(jnp.abs(g2))) + 1e-9)


@pytest.mark.parametrize("L,N,H,d", [(16, 4, 4, 8), (32, 2, 8, 16)])
def test_packed_bitwise_fwd(L, N, H, d):
    """Forward is bitwise-equal to the unfused composition run through
    the kernel's exact dtype chain."""
    rng = np.random.RandomState(0)
    qkv = _rand_qkv(rng, L, N, H, d)
    plan = selfatt_plan(L, H, N, 0.0)
    assert plan is not None
    seeds = jnp.zeros((plan["n_blocks"],), jnp.int32)
    o1 = jax.jit(lambda q: flash_selfatt(
        q, seeds, heads=H, block_heads=plan["bbh"]))(qkv)
    o2 = jax.jit(lambda q: _ref_chain(q, H))(qkv)
    assert bool(jnp.all(o1 == o2))


@pytest.mark.parametrize("L,N,H,d", [(16, 4, 4, 8), (32, 2, 8, 16)])
def test_packed_matches_unfused(L, N, H, d):
    """Value and analytic-gradient parity with the true unfused
    composition (bf16-kernel tolerance, the r6 contract)."""
    rng = np.random.RandomState(0)
    qkv = _rand_qkv(rng, L, N, H, d)
    plan = selfatt_plan(L, H, N, 0.0)
    seeds = jnp.zeros((plan["n_blocks"],), jnp.int32)

    def f(q):
        return flash_selfatt(q, seeds, heads=H, block_heads=plan["bbh"])

    np.testing.assert_allclose(np.asarray(jax.jit(f)(qkv)),
                               np.asarray(jax.jit(lambda q: _ref(q, H))(qkv)),
                               rtol=2e-2, atol=2e-2)
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    assert _grad_gap(f, lambda q: _ref(q, H), qkv, r) < 3e-2


def test_ragged_seq_l127_stays_on_kernel():
    """r6 rejected any L % 8 and silently fell back; now the seq tail
    is padded at the kernel entry and the padded keys are masked out
    of the softmax — L=127 runs on the kernel with exact parity."""
    L, N, H, d = 127, 2, 4, 8
    assert flash_selfatt_available(L, H, N)
    rng = np.random.RandomState(1)
    qkv = _rand_qkv(rng, L, N, H, d)
    plan = selfatt_plan(L, H, N, 0.0)
    assert plan["L_pad"] == 128 and plan["n_blocks"] == N
    seeds = jnp.zeros((plan["n_blocks"],), jnp.int32)

    def f(q):
        return flash_selfatt(q, seeds, heads=H, block_heads=plan["bbh"])

    o1 = jax.jit(f)(qkv)
    assert o1.shape == (L, N, H * d)
    assert bool(jnp.all(o1 == jax.jit(lambda q: _ref_chain(q, H))(qkv)))
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    assert _grad_gap(f, lambda q: _ref(q, H), qkv, r) < 3e-2


@pytest.mark.parametrize("H,bbh", [(5, 5), (5, 4), (12, 8)])
def test_non_dividing_heads_and_padded_blocks(H, bbh):
    """Head counts the block size does not divide ride zero-padded
    final head blocks; a padded head contributes exactly zero and is
    sliced off (both directions)."""
    L, N, d = 24, 2, 8
    rng = np.random.RandomState(2)
    qkv = _rand_qkv(rng, L, N, H, d)
    n_hblk = -(-H // bbh)
    seeds = jnp.zeros((N * n_hblk,), jnp.int32)

    def f(q):
        return flash_selfatt(q, seeds, heads=H, block_heads=bbh)

    o1 = jax.jit(f)(qkv)
    assert o1.shape == (L, N, H * d)
    assert bool(jnp.all(o1 == jax.jit(lambda q: _ref_chain(q, H))(qkv)))
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    assert _grad_gap(f, lambda q: _ref(q, H), qkv, r) < 3e-2


def test_dropout_seed_recompute_parity():
    """The backward regenerates the forward's dropout mask from the
    same seeds. The interpreter PRNG is a deterministic function of
    (seed, position), so the test reconstructs the exact mask and
    checks value AND analytic-gradient parity against the unfused
    composition with that mask applied."""
    L, N, H, d, bbh, p = 16, 2, 4, 8, 4, 0.5
    rng = np.random.RandomState(3)
    qkv = _rand_qkv(rng, L, N, H, d)
    seeds = jnp.asarray(rng.randint(0, 2 ** 31 - 1, (N,))
                        .astype(np.int32))
    thresh = min(int(p * 2 ** 32), 2 ** 32 - 1)
    masks = jnp.stack([
        _keep_mask(None, seeds[n], (bbh, L, L), thresh, True)
        for n in range(N)]).reshape(N * H, L, L)
    # ~p of the probabilities must actually drop
    keep_frac = float(jnp.mean(masks))
    assert 0.4 < keep_frac < 0.6

    def ref_masked(q):
        return _ref(q, H, att_hook=lambda att: jnp.where(
            masks, att / (1.0 - p), 0.0).astype(att.dtype))

    def f(q, seeds=seeds):
        return flash_selfatt(q, seeds, heads=H, dropout=p,
                             block_heads=bbh)

    kernel = jax.jit(f)
    o1, o2 = kernel(qkv), kernel(qkv)
    assert bool(jnp.all(o1 == o2))            # same seeds, same mask
    np.testing.assert_allclose(np.asarray(o1),
                               np.asarray(jax.jit(ref_masked)(qkv)),
                               rtol=3e-2, atol=3e-2)
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    assert _grad_gap(f, ref_masked, qkv, r) < 3e-2
    # different seeds -> different mask -> different output
    assert not bool(jnp.all(o1 == kernel(qkv, seeds + 1)))


# (L, heads) at BERT's head width 64, past the 336 positions where the
# default scoped VMEM limit stopped serving: whole sublane tiles, a
# ragged length, two and four heads, a padded head block
@pytest.mark.parametrize("L,H", [(512, 2), (512, 4), (400, 2), (400, 4),
                                 (200, 3)])
def test_lengths_past_the_default_limit_match_unfused(L, H):
    """ISSUE 39: the plan's own call at a length whose block states its
    VMEM limit: forward within a bf16 step of the kernel's dtype chain,
    value and analytic gradient against the true unfused composition
    at p = 0."""
    N, d = 2, 64
    rng = np.random.RandomState(6)
    qkv = _rand_qkv(rng, L, N, H, d)
    plan = selfatt_plan(L, H, N, 0.0, head_dim=d)
    assert plan is not None and plan["L_pad"] == -(-L // 16) * 16
    seeds = jnp.zeros((plan["n_blocks"],), jnp.int32)

    def f(q):
        return flash_selfatt(q, seeds, heads=H, block_heads=plan["bbh"])

    o1 = jax.jit(f)(qkv)
    assert o1.shape == (L, N, H * d)
    # one bf16 step: long rows' sums are not ordered as the chain's
    np.testing.assert_allclose(
        np.asarray(o1), np.asarray(jax.jit(lambda q: _ref_chain(q, H))(qkv)),
        rtol=2 ** -7, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(o1), np.asarray(jax.jit(lambda q: _ref(q, H))(qkv)),
        rtol=2e-2, atol=2e-2)
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    assert _grad_gap(f, lambda q: _ref(q, H), qkv, r) < 3e-2


@pytest.mark.parametrize("L,H,bbh", [(256, 2, 2), (200, 4, 2)])
def test_dropout_at_the_configurations_rate(L, H, bbh):
    """p = 0.1, BERT's: the backward recomputes each block's mask from
    its seed, so value AND gradient agree with the composition under
    the reconstructed masks; the keep rate is 0.9 within sampling
    error; two head blocks never share a mask."""
    N, d, p = 2, 64, 0.1
    L_pad = -(-L // 16) * 16
    n_hblk = H // bbh
    rng = np.random.RandomState(7)
    qkv = _rand_qkv(rng, L, N, H, d)
    seeds = jnp.asarray(rng.randint(0, 2 ** 31 - 1, (N * n_hblk,))
                        .astype(np.int32))
    thresh = min(int(p * 2 ** 32), 2 ** 32 - 1)
    blocks = [_keep_mask(None, seeds[b], (bbh, L_pad, L_pad), thresh, True)
              for b in range(N * n_hblk)]
    assert abs(float(jnp.mean(blocks[0] != blocks[1]))
               - 2 * 0.9 * 0.1) < 0.01
    masks = jnp.stack(blocks).reshape(N * H, L_pad, L_pad)
    assert abs(float(jnp.mean(masks)) - 0.9) < 0.005     # 250k+ draws
    masks = masks[:, :L, :L]

    def ref_masked(q):
        return _ref(q, H, att_hook=lambda att: jnp.where(
            masks, att / (1.0 - p), 0.0).astype(att.dtype))

    def f(q):
        return flash_selfatt(q, seeds, heads=H, dropout=p,
                             block_heads=bbh)

    kernel = jax.jit(f)
    o1 = kernel(qkv)
    assert bool(jnp.all(o1 == kernel(qkv)))
    np.testing.assert_allclose(np.asarray(o1),
                               np.asarray(jax.jit(ref_masked)(qkv)),
                               rtol=3e-2, atol=3e-2)
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    assert _grad_gap(f, ref_masked, qkv, r) < 3e-2


def test_central_difference_grads_through_registered_op():
    """Directional central-difference through _contrib_sdp_selfatt's
    flash path on a bf16-exact input grid (pointwise differences drown
    in the kernel's bf16 output quantization; a directional probe
    averages it out)."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sdp_selfatt")
    L, N, H, d = 16, 2, 4, 8
    rng = np.random.RandomState(4)
    base = (rng.randint(-16, 17, (L, N, H * 3 * d)) / 16.0) \
        .astype(np.float32)
    qkv = jnp.asarray(base).astype(jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))

    def f(q):
        out = op.impl(key, q, heads=H, dropout=0.0, _train=True)
        return jnp.sum(out.astype(jnp.float32) * r)

    f = jax.jit(f)
    g = jax.jit(jax.grad(f))(qkv).astype(jnp.float32)
    gnorm = float(jnp.linalg.norm(g))
    checked = 0
    for trial in range(4):
        v = jnp.asarray(
            (np.random.RandomState(trial).randint(-2, 3, base.shape)
             / 16.0).astype(np.float32))
        eps = 0.5
        num = (f((qkv.astype(jnp.float32) + eps * v)
                 .astype(jnp.bfloat16))
               - f((qkv.astype(jnp.float32) - eps * v)
                   .astype(jnp.bfloat16))) / (2 * eps)
        ana = float(jnp.sum(g * v))
        vnorm = float(jnp.linalg.norm(v))
        if abs(ana) < 0.05 * gnorm * vnorm / np.sqrt(v.size):
            continue                       # direction ~orthogonal to g
        assert abs(float(num) - ana) / abs(ana) < 0.08, \
            (trial, float(num), ana)
        checked += 1
    assert checked >= 2


def _walk_transposes(jaxpr, out):
    """Collect transpose eqns, recursing through sub-jaxprs but NOT
    into Pallas kernels (in-VMEM relayouts are the design)."""
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            continue
        if eqn.primitive.name == "transpose":
            out.append([tuple(v.aval.shape) for v in eqn.invars])
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                _walk_transposes(sub, out)
            elif isinstance(v, jax.extend.core.Jaxpr):
                _walk_transposes(v, out)
    return out


@pytest.mark.parametrize("L", [16, 256])
def test_no_transpose_between_projection_and_kernel(L):
    """The static half of the transpose_jvp claim (ISSUE 14): trace
    QKV projection -> sdp_selfatt and assert NO transpose eqn touches
    the activation path — the only transpose in the whole trace is the
    projection's weight transpose. At a length whose call states its
    VMEM limit too (ISSUE 39)."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sdp_selfatt")
    N, H, d = 4, 4, 8
    U = H * d
    plan = selfatt_plan(L, H, N, 0.0, dtype=jnp.bfloat16, head_dim=d)
    reckoned = pallas_attention._block_bytes(
        plan["bbh"], plan["L_pad"], d, 2, 5) * 2
    assert (reckoned > pallas_attention._VMEM_BUDGET) == (L == 256)

    def fn(x, w, b, key):
        qkv = jnp.matmul(x, w.T) + b           # the Dense projection
        return op.impl(key, qkv.astype(jnp.bfloat16), heads=H,
                       dropout=0.0, _train=True)

    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros((L, N, U), jnp.bfloat16),
        jnp.zeros((3 * U, U), jnp.bfloat16),
        jnp.zeros((3 * U,), jnp.bfloat16),
        jax.random.PRNGKey(0))
    transposes = _walk_transposes(jaxpr.jaxpr, [])
    w_shape = (3 * U, U)
    for shapes in transposes:
        assert all(s == w_shape for s in shapes), \
            "activation-path transpose survived: %r" % (transposes,)
    # and the gradient trace is transpose-free on the activation path
    def loss(x, w, b, key):
        return jnp.sum(fn(x, w, b, key).astype(jnp.float32))

    jaxpr_g = jax.make_jaxpr(jax.grad(loss, argnums=0))(
        jnp.zeros((L, N, U), jnp.bfloat16),
        jnp.zeros((3 * U, U), jnp.bfloat16),
        jnp.zeros((3 * U,), jnp.bfloat16),
        jax.random.PRNGKey(0))
    for shapes in _walk_transposes(jaxpr_g.jaxpr, []):
        assert all(s in (w_shape, w_shape[::-1]) for s in shapes), \
            "activation-path transpose in the backward"


def test_flag_off_bitwise_fallback(monkeypatch):
    """MXNET_FLASH_ATTENTION=0: the registered op is byte-identical to
    the unfused composition — the packed kernel never engages."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sdp_selfatt")
    L, N, H, d = 16, 4, 4, 8
    rng = np.random.RandomState(5)
    qkv = _rand_qkv(rng, L, N, H, d).astype(jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    monkeypatch.setenv("MXNET_FLASH_ATTENTION", "0")
    assert selfatt_plan(L, H, N, 0.0, dtype=qkv.dtype) is None
    off = op.impl(key, qkv, heads=H, dropout=0.0, _train=True)
    ref = _ref(qkv, H)
    assert bool(jnp.all(off == ref))
    monkeypatch.delenv("MXNET_FLASH_ATTENTION")
    assert selfatt_plan(L, H, N, 0.0, dtype=qkv.dtype) is not None


def test_plan_eligibility_ladder():
    """f32 inputs, oversized L and zero-size axes fall back; the
    availability shim agrees with the plan."""
    assert selfatt_plan(16, 4, 4, 0.0, dtype=jnp.float32) is None
    assert selfatt_plan(2048, 4, 4, 0.0) is None
    assert selfatt_plan(16, 0, 4, 0.0) is None
    assert flash_selfatt_available(16, 4, 4)
    assert not flash_selfatt_available(16, 4, 4, dtype=jnp.float32)
    # block_heads override out of range resolves to the safe default
    plan = selfatt_plan(16, 4, 4, 0.0, block_heads=0)
    assert plan is None


# 12 heads x 64 (BERT-base): length -> heads a grid step
PLANS = {128: 12, 256: 12, 336: 12, 384: 12, 400: 12, 512: 6, 768: 4,
         1024: 2}


@pytest.mark.parametrize("L", sorted(PLANS))
def test_every_length_up_to_the_cap_has_a_plan(L):
    """The lengths people train BERT-base at: a plan exists, its
    reckoned working set is inside the budget it was planned under,
    the limit its calls state leaves the v5e's 128 MiB room, and 128
    resolves to the geometry it had (all twelve heads, nothing asked
    of the compiler)."""
    from jax.experimental.pallas import tpu as pltpu
    H, N, d = 12, 32768 // L, 64
    plan = selfatt_plan(L, H, N, 0.1, dtype=jnp.bfloat16, head_dim=d)
    assert plan is not None
    bbh, L_pad = plan["bbh"], plan["L_pad"]
    assert bbh == PLANS[L] and L_pad == -(-L // 16) * 16
    assert plan["n_blocks"] == N * plan["n_hblk"]
    assert plan["heads_pad"] == plan["n_hblk"] * bbh == H
    reckoned = pallas_attention._block_bytes(bbh, L_pad, d, 2, 5) * 2
    assert reckoned <= pallas_attention._VMEM_MAX
    params = pallas_attention._compiler_params(pltpu, reckoned)
    if L == 128:
        assert params == {}
    else:
        limit = params["compiler_params"].vmem_limit_bytes
        assert reckoned < limit <= 112 << 20


def test_the_budget_decides_the_plan(monkeypatch):
    """Under the default scoped limit's budget alone the plans are the
    ones before ISSUE 39: none at 512 positions (the composition ran),
    twelve heads at 128; 2,048 is refused under either."""
    assert selfatt_plan(2048, 12, 16, 0.1, dtype=jnp.bfloat16) is None
    monkeypatch.setattr(pallas_attention, "_VMEM_MAX",
                        pallas_attention._VMEM_BUDGET)
    assert selfatt_plan(512, 12, 64, 0.1, dtype=jnp.bfloat16) is None
    assert selfatt_plan(336, 12, 64, 0.1, dtype=jnp.bfloat16)["bbh"] == 2
    assert selfatt_plan(128, 12, 256, 0.1, dtype=jnp.bfloat16)["bbh"] == 12
    assert selfatt_plan(2048, 12, 16, 0.1, dtype=jnp.bfloat16) is None


@pytest.mark.parametrize("dtype, devices, path, other",
                         [(jnp.bfloat16, 1, "pallas", "xla"),
                          (jnp.float32, 1, "xla", "pallas"),
                          (jnp.bfloat16, 2, "pallas", "xla")],
                         ids=["bf16", "f32", "bf16-two-devices"])
def test_a_traced_call_counts_its_path(dtype, devices, path, other):
    """ISSUE 39: ``mx_attn_selfatt_path_total{path}`` counts one a
    traced call of ``_contrib_sdp_selfatt``: bf16 on one device the
    kernel (one ``pallas_call`` left in the gradient, its backward
    rule); float32 the composition (its six products); in a program
    GSPMD partitions over several devices, as the dp4 cell's, the
    kernel once a shard (ISSUE 45: the ``pallas_call`` inside the
    transposed ``shard_map``)."""
    from jax.sharding import Mesh
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import get_op, pallas_common
    op = get_op("_contrib_sdp_selfatt")
    qkv = jnp.ones((16, 2, 4 * 3 * 8), dtype)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))

    def loss(q):
        out = op.impl(jax.random.key(0), q, heads=4, dropout=0.1,
                      _train=True)
        return jnp.sum(out.astype(jnp.float32))

    def counts():
        return {p: telemetry.counter("mx_attn_selfatt_path_total",
                                     path=p).get() for p in (path, other)}

    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        start = counts()
        with pallas_common.auto_partitioned(mesh, batch=("dp", 2)):
            jaxpr = jax.make_jaxpr(jax.grad(loss))(qkv)
        got = {p: n - start[p] for p, n in counts().items()}
    finally:
        telemetry.enable(was)
    assert got == {path: 1, other: 0}

    def heavy(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name in ("pallas_call", "dot_general"):
                yield e.primitive.name
            elif e.primitive.name == "shard_map":
                yield from heavy(e.params["jaxpr"])

    assert list(heavy(jaxpr.jaxpr)) == (
        ["pallas_call"] if path == "pallas" else ["dot_general"] * 6)
