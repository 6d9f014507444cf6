"""Quantized gradient collectives (ISSUE 13, docs/QUANTIZE.md):
blockwise int8/fp8 kernels, the EQuARX RS/AG composition, error
feedback on every sync path (kvstore / hierarchical / ZeRO), guard
integration and the commwatch dtype-labeled byte accounting."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

pytestmark = pytest.mark.quant


def _jnp():
    import jax.numpy as jnp
    return jnp


def _cfg(**kw):
    from mxnet_tpu.parallel.quantize import QuantConfig
    return QuantConfig(**kw)


def _ctxs(n):
    import jax
    if len(jax.local_devices()) < n:
        pytest.skip("needs %d devices" % n)
    return [mx.Context("cpu", i) for i in range(n)]


def _grid_rows(rng, m, L, block, exp=-9):
    """Rows whose values sit EXACTLY on the int8 grid: every scale
    block's absmax is 127 * 2^exp (a power-of-two scale), all other
    entries integer multiples of 2^exp — quantize must round-trip
    bitwise."""
    s = 2.0 ** exp
    v = (rng.randint(-127, 128, (m, L)) * s).astype(np.float32)
    for b in range(0, L, block):
        blk = v[:, b:b + block]
        blk[:, 0] = 127 * s          # pin each block's absmax on-grid
    return v


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def test_kernel_grid_roundtrip_bitwise():
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32)
    v = _grid_rows(np.random.RandomState(0), 4, 96, 32)
    q, sc, err = qz.quantize_rows(jnp.asarray(v), cfg)
    assert float(jnp.abs(err).max()) == 0.0
    deq = np.asarray(qz.dequantize_rows(q, sc, cfg))[:, :96]
    np.testing.assert_array_equal(deq, v)


def test_kernel_zero_block_scale_guard():
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32)
    q, sc, err = qz.quantize_rows(jnp.zeros((2, 64)), cfg)
    assert int(jnp.abs(q.astype(jnp.int32)).sum()) == 0
    np.testing.assert_array_equal(np.asarray(sc), 1.0)  # guarded scale
    assert float(jnp.abs(err).max()) == 0.0


@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
def test_kernel_nonfinite_poisons_own_block_only(bad):
    """A non-finite element poisons its whole scale block in the
    DEQUANTIZED result (NaN scale sidecar) — the downstream guard
    check names it — while every other block stays clean."""
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32)
    v = np.ones((1, 64), np.float32)
    v[0, 5] = bad
    q, sc, _ = qz.quantize_rows(jnp.asarray(v), cfg)
    deq = np.asarray(qz.dequantize_rows(q, sc, cfg))
    assert not np.isfinite(deq[0, :32]).any(), "bad block must poison"
    assert np.isfinite(deq[0, 32:]).all(), "clean block must survive"


def test_kernel_bf16_input():
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32)
    rng = np.random.RandomState(1)
    v = jnp.asarray(rng.randn(2, 64), jnp.bfloat16)
    q, sc, err = qz.quantize_rows(v, cfg)
    assert q.dtype == jnp.int8 and sc.dtype == jnp.float32
    deq = qz.dequantize_rows(q, sc, cfg)
    rel = float(jnp.abs(deq - v.astype(jnp.float32)).max())
    assert rel < float(jnp.abs(v.astype(jnp.float32)).max()) * 0.01


def test_kernel_non_dividing_block_pads_wire_only():
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32)
    rng = np.random.RandomState(2)
    v = rng.randn(3, 50).astype(np.float32)       # 50 % 32 != 0
    q, sc, err = qz.quantize_rows(jnp.asarray(v), cfg)
    assert q.shape == (3, 64) and sc.shape == (3, 2)
    assert err.shape == (3, 50)
    # the pad region quantizes to exact zeros (never leaks into sums)
    np.testing.assert_array_equal(np.asarray(q)[:, 50:], 0)
    deq = np.asarray(qz.dequantize_rows(q, sc, cfg))[:, :50]
    assert np.abs(deq - v).max() < np.abs(v).max() * 0.01


def test_kernel_fp8_mode():
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no float8 in this jax")
    cfg = _cfg(mode="fp8", block=32)
    rng = np.random.RandomState(3)
    v = rng.randn(2, 64).astype(np.float32)
    q, sc, err = qz.quantize_rows(jnp.asarray(v), cfg)
    assert q.dtype == jnp.float8_e4m3fn
    deq = np.asarray(qz.dequantize_rows(q, sc, cfg))
    # e4m3: 3 mantissa bits -> <= ~6.25% relative per element
    assert np.abs(deq[:, :64] - v).max() < np.abs(v).max() * 0.07


def test_kernel_stochastic_rounding_unbiased():
    import jax
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32, stochastic=True)
    # a value exactly half way between two grid points: round-to-
    # nearest always picks one side; stochastic must hit both with
    # ~equal frequency and stay ON the grid
    v = np.full((1, 32), 0.5, np.float32)
    v[0, 0] = 127.0                                # scale = 1.0
    deqs = []
    for seed in range(200):
        q, sc, _ = qz.quantize_rows(jnp.asarray(v), cfg,
                                    key=jax.random.PRNGKey(seed))
        deqs.append(float(np.asarray(
            qz.dequantize_rows(q, sc, cfg))[0, 1]))
    vals = set(deqs)
    assert vals <= {0.0, 1.0}, vals
    mean = np.mean(deqs)
    assert 0.35 < mean < 0.65, mean                # unbiased-ish


def test_numpy_reference_matches_kernel():
    from mxnet_tpu.parallel import quantize as qz
    jnp = _jnp()
    cfg = _cfg(block=32)
    rng = np.random.RandomState(4)
    v = rng.randn(70).astype(np.float32)
    q, sc, err = qz.quantize_rows(jnp.asarray(v[None]), cfg)
    deq = np.asarray(qz.dequantize_rows(q, sc, cfg))[0, :70]
    ref_deq, ref_err = qz.np_reference_quantize(v, cfg)
    np.testing.assert_allclose(deq, ref_deq, rtol=0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(err)[0], ref_err,
                               rtol=0, atol=1e-7)


def test_config_validation():
    from mxnet_tpu.parallel.quantize import QuantConfig
    with pytest.raises(ValueError):
        QuantConfig(mode="int4")
    with pytest.raises(ValueError):
        QuantConfig(tier="ici")
    with pytest.raises(ValueError):
        QuantConfig(block=4)


def test_from_env_off_by_default(monkeypatch):
    from mxnet_tpu.parallel import quantize as qz
    monkeypatch.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
    assert qz.from_env() is None
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE_BLOCK", "64")
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE_TIER", "all")
    cfg = qz.from_env()
    assert cfg.mode == "int8" and cfg.block == 64 and cfg.tier == "all"


# ---------------------------------------------------------------------------
# error-feedback accumulation (shard_map level)
# ---------------------------------------------------------------------------
def _flat_ar(cfg, ndev=8):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel import quantize as qz
    from mxnet_tpu.parallel.collectives import shard_map
    devs = jax.devices()[:ndev]
    if len(devs) < ndev:
        pytest.skip("needs %d devices" % ndev)
    mesh = Mesh(np.array(devs), ("dp",))

    def f(g, r):
        out, nr = qz.quantized_allreduce(g[0], "dp", None, cfg,
                                         residual=r[0])
        return out[None], nr[None]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                             out_specs=(P("dp"), P("dp")),
                             check_vma=False))


def test_ef_accumulation_vs_numpy_reference():
    """One device's EF chain must match the NumPy reference run of the
    same scheme step for step (single participant: the collective sum
    is the identity, isolating the EF bookkeeping)."""
    from mxnet_tpu.parallel import quantize as qz
    cfg = _cfg(block=32)
    ar = _flat_ar(cfg, ndev=1)
    _jnp()
    rng = np.random.RandomState(5)
    S = 70
    res_np = np.zeros(S, np.float32)
    res = np.zeros((1, S), np.float32)
    for _ in range(4):
        g = rng.randn(S).astype(np.float32)
        out, res = ar(g[None].copy(), res)
        # reference: quantize(g+res) twice (RS wire + AG requant)
        deq1, err1 = qz.np_reference_quantize(g + res_np, cfg)
        deq2, err2 = qz.np_reference_quantize(deq1, cfg)
        res_np = (err1 + err2).astype(np.float32)
        np.testing.assert_allclose(np.asarray(out)[0], deq2,
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(res)[0], res_np,
                                   rtol=0, atol=1e-6)
        res = np.asarray(res)


def test_residual_carry_identity_flat_allreduce():
    """sum over K steps of the dequantized (wire) sums + the final
    residual sum == sum of the true gradients — the telescoping EF
    identity, at ulp-scaled tolerance."""
    cfg = _cfg(block=32)
    ar = _flat_ar(cfg)
    jnp = _jnp()
    rng = np.random.RandomState(6)
    S, K = 500, 5
    res = jnp.zeros((8, S), jnp.float32)
    tot_out = np.zeros(S, np.float64)
    tot_true = np.zeros(S, np.float64)
    for _ in range(K):
        g = rng.randn(8, S).astype(np.float32)
        out, res = ar(jnp.asarray(g), res)
        out = np.asarray(out)
        np.testing.assert_array_equal(out[0], out[7])  # replicated
        tot_out += out[0]
        tot_true += g.sum(0)
    carry = np.asarray(res).sum(0)
    scale = np.maximum(np.abs(tot_true), 1.0)
    assert (np.abs(tot_out + carry - tot_true) / scale).max() < 1e-5


def test_exact_grid_allreduce_bitwise():
    """On exact-grid gradients the quantized allreduce is BITWISE the
    f32 sum (the quant_micro parity gate's mechanism)."""
    cfg = _cfg(block=32)
    ar = _flat_ar(cfg)
    jnp = _jnp()
    rng = np.random.RandomState(7)
    # every replica contributes the SAME on-grid rows: the sum of 8
    # copies stays on a power-of-two grid (absmax 127*2^-6)
    row = _grid_rows(rng, 1, 256, 32)[0]
    g = np.tile(row, (8, 1))
    out, _ = ar(jnp.asarray(g), jnp.zeros((8, 256), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out)[0], g.sum(0))


def test_hierarchical_tiers():
    """Staged dcn x ici: tier='dcn' leaves ici f32 and the identity
    still holds; tier='all' quantizes both hops."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel import quantize as qz
    from mxnet_tpu.parallel.collectives import shard_map
    jnp = _jnp()
    devs = jax.devices()[:8]
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dcn", "dp"))
    spec = P(("dcn", "dp"))
    rng = np.random.RandomState(8)
    S, K = 400, 4
    for tier in ("dcn", "all"):
        cfg = _cfg(block=32, tier=tier)

        def f(g, r):
            out, nr = qz.quantized_allreduce(
                g.reshape(-1), "dp", "dcn", cfg,
                residual=r.reshape(-1))
            return out[None], nr[None]

        ar = jax.jit(shard_map(f, mesh=mesh, in_specs=(spec, spec),
                               out_specs=(spec, spec), check_vma=False))
        res = jnp.zeros((8, S), jnp.float32)
        tot_out = np.zeros(S, np.float64)
        tot_true = np.zeros(S, np.float64)
        for _ in range(K):
            g = rng.randn(8, S).astype(np.float32)
            out, res = ar(jnp.asarray(g), res)
            tot_out += np.asarray(out)[0]
            tot_true += g.sum(0)
        carry = np.asarray(res).sum(0)
        scale = np.maximum(np.abs(tot_true), 1.0)
        assert (np.abs(tot_out + carry - tot_true) / scale).max() \
            < 1e-5, tier


def test_hierarchical_grad_sync_quant_residual():
    """The pytree-level hierarchical sync: quantized wire, residual
    pytree carried, identity per leaf."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel import collectives as coll
    jnp = _jnp()
    devs = jax.devices()[:8]
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dcn", "dp"))
    spec = P(("dcn", "dp"))
    cfg = _cfg(block=32)

    def f(t, r):
        un = jax.tree_util.tree_map(lambda x: x[0], t)
        ur = jax.tree_util.tree_map(lambda x: x[0], r)
        s, nr = coll.hierarchical_grad_sync(un, "dp", "dcn", quant=cfg,
                                            residual=ur)
        pack = jax.tree_util.tree_map(lambda x: x[None], s)
        rpack = jax.tree_util.tree_map(lambda x: x[None], nr)
        return pack, rpack

    sync = jax.jit(coll.shard_map(f, mesh=mesh, in_specs=(spec, spec),
                                  out_specs=(spec, spec),
                                  check_vma=False))
    rng = np.random.RandomState(9)
    tree = {"w": rng.randn(8, 10, 7).astype(np.float32),
            "b": rng.randn(8, 5).astype(np.float32)}
    res = {"w": np.zeros((8, 10, 7), np.float32),
           "b": np.zeros((8, 5), np.float32)}
    tot = {k: np.zeros(v.shape[1:], np.float64) for k, v in tree.items()}
    true = {k: np.zeros(v.shape[1:], np.float64) for k, v in tree.items()}
    for _ in range(3):
        g = {k: rng.randn(*v.shape).astype(np.float32)
             for k, v in tree.items()}
        out, res = sync({k: jnp.asarray(v) for k, v in g.items()},
                        {k: jnp.asarray(v) for k, v in res.items()})
        res = {k: np.asarray(v) for k, v in res.items()}
        for k in g:
            tot[k] += np.asarray(out[k])[0]
            true[k] += g[k].sum(0)
    for k in tot:
        carry = res[k].sum(0)
        scale = np.maximum(np.abs(true[k]), 1.0)
        assert (np.abs(tot[k] + carry - true[k]) / scale).max() < 1e-5


# ---------------------------------------------------------------------------
# kvstore path
# ---------------------------------------------------------------------------
def test_kvstore_quant_off_bitwise_unchanged(monkeypatch):
    """MXNET_KVSTORE_QUANTIZE unset: the grouped reduce is the classic
    f32 collective, bitwise — and no quantized program or residual
    state exists."""
    monkeypatch.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
    ctxs = _ctxs(4)
    kv = mx.kvstore.create("device")
    rng = np.random.RandomState(10)
    gs = [rng.randn(31, 3).astype(np.float32) for _ in ctxs]
    kv.init("w", nd.zeros((31, 3), ctx=ctxs[0]))
    vals = [nd.array(a, ctx=c) for a, c in zip(gs, ctxs)]
    outs = [nd.zeros((31, 3), ctx=c) for c in ctxs]
    kv.pushpull_list(["w"], [vals], [outs])
    # numeric: the classic f32 collective sum (XLA's reduction order
    # differs from numpy's only at ulp level)
    np.testing.assert_allclose(outs[0].asnumpy(), np.sum(gs, axis=0),
                               rtol=1e-5, atol=1e-6)
    # structural: the quantized machinery was never instantiated —
    # byte-for-byte today's path
    assert not kv._quant_state
    assert not kv._reducer._quant_watched


def test_kvstore_residual_carry_identity(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE_BLOCK", "32")
    ctxs = _ctxs(8)
    kv = mx.kvstore.create("device")
    rng = np.random.RandomState(11)
    shapes = {"0": (40, 5), "1": (17,)}
    for k, s in shapes.items():
        kv.init(k, nd.zeros(s, ctx=ctxs[0]))
    tot_out = {k: np.zeros(s, np.float64) for k, s in shapes.items()}
    tot_true = {k: np.zeros(s, np.float64) for k, s in shapes.items()}
    for _ in range(5):
        gs = {k: [rng.randn(*s).astype(np.float32) for _ in ctxs]
              for k, s in shapes.items()}
        vals = [[nd.array(a, ctx=c) for a, c in zip(gs[k], ctxs)]
                for k in shapes]
        outs = [[nd.zeros(shapes[k], ctx=c) for c in ctxs]
                for k in shapes]
        kv.pushpull_list(list(shapes), vals, outs)
        for i, k in enumerate(shapes):
            tot_out[k] += outs[i][0].asnumpy()
            tot_true[k] += np.sum(gs[k], axis=0)
    res = kv.quant_residuals_export()
    for k, s in shapes.items():
        carry = res[k].reshape(s)
        scale = np.maximum(np.abs(tot_true[k]), 1.0)
        assert (np.abs(tot_out[k] + carry - tot_true[k])
                / scale).max() < 1e-5


def test_kvstore_quant_program_steady_state(monkeypatch):
    """The quantized grouped reduce compiles ONCE per group signature —
    steady-state steps are cache hits (compilewatch counters)."""
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    from mxnet_tpu import telemetry
    telemetry.refresh()
    try:
        telemetry.reset()
        ctxs = _ctxs(4)
        kv = mx.kvstore.create("device")
        kv.init("w", nd.zeros((64,), ctx=ctxs[0]))
        rng = np.random.RandomState(12)
        for _ in range(4):
            vals = [nd.array(rng.randn(64).astype(np.float32), ctx=c)
                    for c in ctxs]
            outs = [nd.zeros((64,), ctx=c) for c in ctxs]
            kv.pushpull_list(["w"], [vals], [outs])
        snap = telemetry.snapshot()
        compiles = snap["counters"].get(
            'mx_compile_total{fn="kv.quant_reduce"}', 0)
        recompiles = snap["counters"].get(
            'mx_recompiles_total{fn="kv.quant_reduce"}', 0)
        assert compiles == 1, compiles
        assert recompiles == 0, recompiles
    finally:
        telemetry.reset()
        telemetry.refresh()


def test_kvstore_commwatch_dtype_bytes(monkeypatch):
    """commwatch charges the TRUE low-precision wire bytes under the
    new dtype label: int8 payload bytes exact, f32 scale sidecar tiny,
    and no unlabeled f32 payload on the quantized axis."""
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE_BLOCK", "32")
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.delenv("MXNET_COMMWATCH", raising=False)
    from mxnet_tpu import commwatch, telemetry
    telemetry.refresh()
    try:
        telemetry.reset()
        commwatch.reset()
        ctxs = _ctxs(8)
        kv = mx.kvstore.create("device")
        S = 8 * 32 * 2          # pads to itself: C=64, 2 blocks/rank
        kv.init("w", nd.zeros((S,), ctx=ctxs[0]))
        rng = np.random.RandomState(13)
        vals = [nd.array(rng.randn(S).astype(np.float32), ctx=c)
                for c in ctxs]
        outs = [nd.zeros((S,), ctx=c) for c in ctxs]
        kv.pushpull_list(["w"], [vals], [outs])
        snap = telemetry.snapshot()
        a2a = snap["counters"][
            'mx_comm_bytes_total{axis="kv",dtype="int8",op="all_to_all"}']
        ag = snap["counters"][
            'mx_comm_bytes_total{axis="kv",dtype="int8",op="allgather"}']
        assert a2a == S          # (n, C) int8 = S bytes
        assert ag == S           # total gathered output, int8
        # scale sidecars: f32, S/32 each way
        scales = sum(v for k, v in snap["counters"].items()
                     if k.startswith("mx_comm_bytes_total")
                     and 'axis="kv"' in k and "dtype" not in k)
        assert scales == 2 * (S // 32) * 4
        rows = commwatch.report()
        int8_rows = [r for r in rows if r["dtype"] == "int8"]
        assert {r["axis"] for r in int8_rows} == {"kv"}
    finally:
        telemetry.reset()
        telemetry.refresh()


def test_trainer_kvstore_convergence_within_2pct(monkeypatch):
    """The flat data-parallel Trainer (kvstore path): 20 SGD steps,
    quantized-with-EF final loss within 2% of f32 (the acceptance
    criterion's kvstore leg)."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    ctxs = _ctxs(8)

    def run(mode):
        if mode:
            monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", mode)
        else:
            monkeypatch.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
        mx.random.seed(21)
        np.random.seed(21)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=16, activation="relu"),
                nn.Dense(8))
        net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
        net(nd.ones((2, 16), ctx=ctxs[0]))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore="device")
        rng = np.random.RandomState(22)
        X = rng.rand(16, 16).astype(np.float32)
        Y = (X[:, :8] * 2 - 0.5).astype(np.float32)
        last = None
        for _ in range(20):
            xs = gluon.utils.split_and_load(nd.array(X), ctxs)
            ys = gluon.utils.split_and_load(nd.array(Y), ctxs)
            with autograd.record():
                ls = [((net(x) - y) ** 2).mean()
                      for x, y in zip(xs, ys)]
            for l in ls:
                l.backward()
            tr.step(16)
            last = float(np.mean([l.asnumpy().item() for l in ls]))
        return last

    l_f32 = run(None)
    l_q = run("int8")
    assert abs(l_q - l_f32) / l_f32 < 0.02, (l_q, l_f32)


def test_trainer_checkpoint_carries_kv_residual(monkeypatch, tmp_path):
    """Trainer.save_states wraps the kvstore-path EF residuals; a new
    Trainer restores them (sum-preserving) and consumes them at its
    first reduce."""
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    ctxs = _ctxs(4)

    def build():
        mx.random.seed(31)
        net = nn.Dense(4, in_units=8)
        net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
        net(nd.ones((2, 8), ctx=ctxs[0]))
        return net, gluon.Trainer(net.collect_params(), "sgd",
                                  {"learning_rate": 0.05},
                                  kvstore="device")

    net, tr = build()
    rng = np.random.RandomState(32)
    for _ in range(3):
        xs = gluon.utils.split_and_load(
            nd.array(rng.rand(8, 8).astype(np.float32)), ctxs)
        ys = gluon.utils.split_and_load(
            nd.array(rng.rand(8, 4).astype(np.float32)), ctxs)
        with autograd.record():
            ls = [((net(x) - y) ** 2).sum() for x, y in zip(xs, ys)]
        for l in ls:
            l.backward()
        tr.step(8)
    saved = tr._kvstore.quant_residuals_export()
    assert saved and any(np.abs(v).max() > 0 for v in saved.values())
    f = str(tmp_path / "states")
    tr.save_states(f)
    net2, tr2 = build()
    tr2._contexts = tr2._check_contexts()
    tr2._init_kvstore()
    tr2.load_states(f)
    kv2 = tr2._kvstore
    assert set(kv2._quant_restore) == set(saved)
    # one step consumes the pending restore into live residual state
    xs = gluon.utils.split_and_load(
        nd.array(rng.rand(8, 8).astype(np.float32)), ctxs)
    ys = gluon.utils.split_and_load(
        nd.array(rng.rand(8, 4).astype(np.float32)), ctxs)
    with autograd.record():
        ls = [((net2(x) - y) ** 2).sum() for x, y in zip(xs, ys)]
    for l in ls:
        l.backward()
    tr2.step(8)
    assert not kv2._quant_restore and kv2._quant_state


# ---------------------------------------------------------------------------
# ZeRO path
# ---------------------------------------------------------------------------
def _zero_trainer(ctxs, opt="sgd", dcn=0, seed=41):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    os.environ["MXNET_ZERO"] = "1"
    os.environ["MXNET_ZERO_DCN"] = str(dcn)
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(48, in_units=24, activation="relu"), nn.Dense(6))
    net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
    net(nd.ones((2, 24), ctx=ctxs[0]))
    kw = {"learning_rate": 0.05}
    tr = gluon.Trainer(net.collect_params(), opt, kw, kvstore="device")
    return net, tr


def _zero_step(net, tr, ctxs, rng, batch=16):
    from mxnet_tpu import autograd, gluon
    xs = gluon.utils.split_and_load(
        nd.array(rng.rand(batch, 24).astype(np.float32)), ctxs)
    ys = gluon.utils.split_and_load(
        nd.array(rng.rand(batch, 6).astype(np.float32)), ctxs)
    with autograd.record():
        ls = [((net(x) - y) ** 2).mean() for x, y in zip(xs, ys)]
    for l in ls:
        l.backward()
    tr.step(batch)
    return float(np.mean([l.asnumpy().item() for l in ls]))


@pytest.fixture()
def zero_env(monkeypatch):
    yield monkeypatch
    os.environ.pop("MXNET_ZERO", None)
    os.environ.pop("MXNET_ZERO_DCN", None)


def test_zero_residual_carry_identity(zero_env):
    """The ZeRO leg of the carry identity, on the engine's own compiled
    'reduce' program: sum over steps of the dequant-accumulated shards
    + the final (replica-summed) grad residual == sum of true summed
    gradients, elementwise in the fragment layout."""
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE_BLOCK", "32")
    ctxs = _ctxs(8)
    from mxnet_tpu.gluon import zero as zero_mod
    net, tr = _zero_trainer(ctxs)
    rng = np.random.RandomState(42)
    _zero_step(net, tr, ctxs, rng)          # engine + layout build
    eng = tr._zero
    assert isinstance(eng, zero_mod.ZeroEngine) and eng._quant
    G = len(eng._groups)
    prog = eng._program("reduce")
    n = eng._n
    g0 = eng._groups[0]

    def gmat_of(grads_np):
        cols = []
        for it in g0.items:
            gg = np.zeros(it.frag * n, np.float32)
            flat = grads_np[it.pos].reshape(-1)
            gg[:flat.size] = flat
            cols.append(gg.reshape(n, it.frag))
        return np.concatenate(cols, axis=1)

    # the engine-build step above already advanced the residual: the
    # identity is sum(out) + res_K == sum(true) + res_0
    res0 = np.zeros((n, g0.C), np.float64)
    for p in range(n):
        res0 += np.asarray(eng._gres_nd[0][p].asnumpy(),
                           np.float64).reshape(n, g0.C)
    tot_sh = np.zeros((n, g0.C), np.float64)
    tot_true = np.zeros((n, g0.C), np.float64)
    for _ in range(4):
        per_replica = []
        for r, _ctx in enumerate(ctxs):
            grads_np = [rng.randn(*it.param.shape).astype(np.float32)
                        for it in eng._items]
            per_replica.append(grads_np)
        for it in eng._items:
            for r, g in enumerate(it.param.list_grad()):
                g[:] = nd.array(per_replica[r][it.pos],
                                ctx=ctxs[r])._jax()
        grad_args = [eng._stack_nd(it.param.list_grad())
                     for it in eng._items]
        gres_args, _ = eng._res_args()
        red = prog(*(grad_args + gres_args))
        shards, gres_new = list(red[:G]), list(red[G:2 * G])
        eng._write_res(gres_new, eng._gres_nd)
        # shard row j (device j's output) = reduced global fragment j
        sh = np.stack([np.asarray(s.data).reshape(-1)
                       for s in shards[0].addressable_shards])
        tot_sh += sh
        tot_true += sum(gmat_of(g) for g in per_replica)
    res_sum = np.zeros((n, g0.C), np.float64)
    for p in range(n):
        res_sum += np.asarray(eng._gres_nd[0][p].asnumpy(),
                              np.float64).reshape(n, g0.C)
    scale = np.maximum(np.abs(tot_true), 1.0)
    assert (np.abs(tot_sh + res_sum - (tot_true + res0))
            / scale).max() < 1e-5


@pytest.mark.parametrize("dcn", [0, 2])
def test_zero_quant_convergence(zero_env, dcn):
    """Flat AND hierarchical ZeRO: 20 quantized SGD steps land within
    2% of the f32 run's final loss."""
    ctxs = _ctxs(8)

    def run(mode):
        if mode:
            zero_env.setenv("MXNET_KVSTORE_QUANTIZE", mode)
        else:
            zero_env.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
        np.random.seed(51)
        net, tr = _zero_trainer(ctxs, dcn=dcn, seed=51)
        rng = np.random.RandomState(52)
        last = None
        for _ in range(20):
            last = _zero_step(net, tr, ctxs, rng)
        from mxnet_tpu.gluon import zero as zero_mod
        assert isinstance(tr._zero, zero_mod.ZeroEngine)
        return last

    l_q = run("int8")
    l_f = run(None)
    assert abs(l_q - l_f) / l_f < 0.02, (l_q, l_f)


def test_zero_guard_names_param_with_quantize(zero_env):
    """nan_grad faultinject + quantize on: the NaN crosses the int8
    wire as a poisoned scale block and the guard still NAMES the
    offending parameter (skip_step policy counts the step)."""
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    from mxnet_tpu import faultinject, guardrails
    ctxs = _ctxs(8)
    net, tr = _zero_trainer(ctxs)
    tr.grad_guard = guardrails.GradGuard(nonfinite="skip_step")
    rng = np.random.RandomState(61)
    _zero_step(net, tr, ctxs, rng)
    events = []
    unsub = guardrails.on_event(events.append)
    try:
        faultinject.set_fault("nan_grad", 1.0, max_fires=1)
        w_before = [p.data(ctxs[0]).asnumpy()
                    for p in net.collect_params().values()]
        _zero_step(net, tr, ctxs, rng)
    finally:
        unsub()
        faultinject.clear("nan_grad")
    assert tr.grad_guard.skipped_steps == 1
    first_param = tr._zero._items[0].param.name
    nonf = [e for e in events if e["kind"] == "nonfinite"]
    assert nonf and first_param in nonf[0]["params"]
    assert nonf[0].get("quantize") == "int8"
    w_after = [p.data(ctxs[0]).asnumpy()
               for p in net.collect_params().values()]
    for b, a in zip(w_before, w_after):
        np.testing.assert_array_equal(b, a)  # skipped: nothing moved


def test_zero_quant_checkpoint_cross_topology(zero_env, tmp_path):
    """Residual shards ride checkpoints like optimizer state: save on
    8 replicas, restore on 4, gathered residuals identical (sum
    preserved); quantize-off loads of the same blob also work."""
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    ctxs8 = _ctxs(8)
    net, tr = _zero_trainer(ctxs8, opt="adam")
    rng = np.random.RandomState(71)
    for _ in range(3):
        _zero_step(net, tr, ctxs8, rng)
    g8, w8 = tr._zero._gathered_residuals()
    assert any(np.abs(v).max() > 0 for v in g8.values())
    f = str(tmp_path / "states")
    tr.save_states(f)

    net4, tr4 = _zero_trainer(ctxs8[:4], opt="adam")
    tr4._contexts = tr4._check_contexts()
    tr4._init_kvstore()
    tr4.load_states(f)
    eng4 = tr4._zero_engine()
    g4, w4 = eng4._gathered_residuals()
    for k in g8:
        np.testing.assert_allclose(g4[k], g8[k], rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(w4[k], w8[k], rtol=1e-5, atol=1e-7)

    # quantize off: the wrapper still loads (states only, no residuals)
    zero_env.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
    net2, tr2 = _zero_trainer(ctxs8[:2], opt="adam")
    tr2._contexts = tr2._check_contexts()
    tr2._init_kvstore()
    tr2.load_states(f)
    assert tr2._zero_engine()._quant is None


def test_nonfinite_step_does_not_poison_residual(zero_env):
    """Review fix: a NaN gradient poisons the OUTPUT (guard names it,
    step skipped) but never the error-feedback carry — the very next
    clean step proceeds and the weights move again. Without the fix
    the NaN residual re-poisons every later step's input forever."""
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    from mxnet_tpu import faultinject, guardrails
    ctxs = _ctxs(8)
    net, tr = _zero_trainer(ctxs)
    tr.grad_guard = guardrails.GradGuard(nonfinite="skip_step")
    rng = np.random.RandomState(91)
    _zero_step(net, tr, ctxs, rng)
    try:
        faultinject.set_fault("nan_grad", 1.0, max_fires=1)
        _zero_step(net, tr, ctxs, rng)            # poisoned -> skipped
    finally:
        faultinject.clear("nan_grad")
    assert tr.grad_guard.skipped_steps == 1
    # residual stayed finite through the poisoned step
    for gi in range(len(tr._zero._groups)):
        for p in range(tr._zero._n):
            assert np.isfinite(
                tr._zero._gres_nd[gi][p].asnumpy()).all()
    w_before = [p.data(ctxs[0]).asnumpy()
                for p in net.collect_params().values()]
    _zero_step(net, tr, ctxs, rng)                # clean step
    assert tr.grad_guard.skipped_steps == 1       # NOT skipped again
    w_after = [p.data(ctxs[0]).asnumpy()
               for p in net.collect_params().values()]
    assert any(np.abs(a - b).max() > 0
               for a, b in zip(w_after, w_before)), "training resumed"
    for w in w_after:
        assert np.isfinite(w).all()


def test_kvstore_nonfinite_recovery(monkeypatch):
    """Same recovery contract on the kvstore path: a push with an inf
    gradient dequantizes non-finite (caught downstream), but the NEXT
    clean reduce is correct and the residual is finite."""
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE_BLOCK", "32")
    ctxs = _ctxs(4)
    kv = mx.kvstore.create("device")
    kv.init("w", nd.zeros((64,), ctx=ctxs[0]))
    bad = np.ones(64, np.float32)
    bad[3] = np.inf
    vals = [nd.array(bad, ctx=c) for c in ctxs]
    outs = [nd.zeros((64,), ctx=c) for c in ctxs]
    kv.pushpull_list(["w"], [vals], [outs])
    assert not np.isfinite(outs[0].asnumpy()).all()
    assert np.isfinite(kv.quant_residuals_export()["w"]).all()
    good = [np.random.RandomState(i).randn(64).astype(np.float32)
            for i in range(4)]
    vals = [nd.array(a, ctx=c) for a, c in zip(good, ctxs)]
    kv.pushpull_list(["w"], [vals], [outs])
    got = outs[0].asnumpy()
    true = np.sum(good, axis=0)
    assert np.isfinite(got).all()
    assert np.abs(got - true).max() < np.abs(true).max() * 0.05


def test_zero_stochastic_rounding_wired(zero_env):
    """Review fix: MXNET_KVSTORE_QUANTIZE_STOCHASTIC reaches the ZeRO
    programs (qseed arg threaded) — steps run, stay finite, and the
    per-step seed decorrelates consecutive identical-gradient steps."""
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    zero_env.setenv("MXNET_KVSTORE_QUANTIZE_STOCHASTIC", "1")
    ctxs = _ctxs(8)
    net, tr = _zero_trainer(ctxs)
    rng = np.random.RandomState(95)
    for _ in range(3):
        _zero_step(net, tr, ctxs, rng)
    eng = tr._zero
    assert eng._quant.stochastic
    assert eng._qstep == 3          # one seed per step
    for p in net.collect_params().values():
        assert np.isfinite(p.data(ctxs[0]).asnumpy()).all()


def test_grad_sync_env_does_not_auto_quantize(monkeypatch):
    """Review fix: hierarchical_grad_sync never quantizes implicitly —
    MXNET_KVSTORE_QUANTIZE in the env must NOT make the stateless
    helper lossy (a caller without a residual would silently drop
    rounding error); quant='env' is the explicit opt-in."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel import collectives as coll
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    jnp = _jnp()
    devs = jax.devices()[:8]
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dcn", "dp"))
    spec = P(("dcn", "dp"))

    def f(t):
        un = jax.tree_util.tree_map(lambda x: x[0], t)
        s = coll.hierarchical_grad_sync(un, "dp", "dcn")
        return jax.tree_util.tree_map(lambda x: x[None], s)

    sync = jax.jit(coll.shard_map(f, mesh=mesh, in_specs=(spec,),
                                  out_specs=spec, check_vma=False))
    rng = np.random.RandomState(96)
    g = rng.randn(8, 40).astype(np.float32)
    out = np.asarray(sync({"w": jnp.asarray(g)})["w"])[0]
    # f32 path: exact to summation-order ulps, NOT quantization error
    np.testing.assert_allclose(out, g.sum(0), rtol=1e-5, atol=1e-6)


def test_grad_sync_flushes_residual_when_quant_resolves_off(monkeypatch):
    """Review fix: a caller-carried residual is FLUSHED into the sync
    (entering the sum exactly once) when quant resolves to None mid-run
    — never silently dropped."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel import collectives as coll
    monkeypatch.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
    jnp = _jnp()
    devs = jax.devices()[:8]
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dcn", "dp"))
    spec = P(("dcn", "dp"))

    def f(t, r):
        un = jax.tree_util.tree_map(lambda x: x[0], t)
        ur = jax.tree_util.tree_map(lambda x: x[0], r)
        s, nr = coll.hierarchical_grad_sync(un, "dp", "dcn",
                                            quant="env", residual=ur)
        return (jax.tree_util.tree_map(lambda x: x[None], s),
                jax.tree_util.tree_map(lambda x: x[None], nr))

    sync = jax.jit(coll.shard_map(f, mesh=mesh, in_specs=(spec, spec),
                                  out_specs=(spec, spec),
                                  check_vma=False))
    rng = np.random.RandomState(97)
    g = rng.randn(8, 24).astype(np.float32)
    res = rng.randn(8, 24).astype(np.float32)   # a carried correction
    out, nres = sync({"w": jnp.asarray(g)}, {"w": jnp.asarray(res)})
    # the carry entered the sum once per replica and was cleared
    np.testing.assert_allclose(np.asarray(out["w"])[0],
                               (g + res).sum(0), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nres["w"]), 0.0)


def test_legacy_compression_guard_attribution(monkeypatch):
    """Review fix: quantization switched on through the LEGACY
    set_gradient_compression route (env unset) is still attributed on
    guard events (guardrails._active_quantize via quantize.active_mode
    — the kvstore reducer notes the mode it actually used)."""
    import warnings
    from mxnet_tpu import guardrails
    from mxnet_tpu.parallel import quantize as qz
    monkeypatch.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
    monkeypatch.setattr(qz, "_LAST_ACTIVE", None)
    assert qz.active_mode() is None
    ctxs = _ctxs(4)
    kv = mx.kvstore.create("device")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        kv.set_gradient_compression({"type": "2bit"})
    kv.init("w", nd.zeros((64,), ctx=ctxs[0]))
    rng = np.random.RandomState(98)
    vals = [nd.array(rng.randn(64).astype(np.float32), ctx=c)
            for c in ctxs]
    outs = [nd.zeros((64,), ctx=c) for c in ctxs]
    kv.pushpull_list(["w"], [vals], [outs])
    assert qz.active_mode() == "int8"
    assert guardrails._active_quantize() == "int8"


def test_kv_residual_export_restore_sum_preserved(monkeypatch):
    """Review fix: export sums the local per-device residuals and
    restore splits back over the SAME local device count — the round
    trip conserves the carried sum exactly."""
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "int8")
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE_BLOCK", "32")
    ctxs = _ctxs(4)
    kv = mx.kvstore.create("device")
    kv.init("w", nd.zeros((96,), ctx=ctxs[0]))
    rng = np.random.RandomState(99)
    vals = [nd.array(rng.randn(96).astype(np.float32), ctx=c)
            for c in ctxs]
    outs = [nd.zeros((96,), ctx=c) for c in ctxs]
    kv.pushpull_list(["w"], [vals], [outs])
    saved = kv.quant_residuals_export()
    kv2 = mx.kvstore.create("device")
    kv2.init("w", nd.zeros((96,), ctx=ctxs[0]))
    kv2.quant_residuals_restore(saved)
    # one zero-grad reduce consumes the pending restore; its residual
    # then carries exactly the restored sum minus what the wire moved
    zvals = [nd.zeros((96,), ctx=c) for c in ctxs]
    kv2.pushpull_list(["w"], [zvals], [outs])
    flushed = outs[0].asnumpy()
    carry2 = kv2.quant_residuals_export()["w"]
    np.testing.assert_allclose(flushed + carry2, saved["w"],
                               rtol=0, atol=1e-6)


def test_report_key_shared_helper():
    from mxnet_tpu import commwatch
    assert commwatch.report_key(
        {"op": "allreduce", "axis": "dp"}) == "allreduce/dp"
    assert commwatch.report_key(
        {"op": "all_to_all", "axis": "kv", "dtype": "int8"}) \
        == "all_to_all/kv/int8"


def test_fp8_unavailable_raises_at_config(monkeypatch):
    """Review fix: a jax without float8 rejects fp8 at from_env()
    (friendly ValueError), not mid-trace on the first step."""
    import types
    import mxnet_tpu.parallel.quantize as qz
    jnp = _jnp()
    monkeypatch.setenv("MXNET_KVSTORE_QUANTIZE", "fp8")
    if hasattr(jnp, "float8_e4m3fn"):
        assert qz.from_env().mode == "fp8"
    # simulate a float8-less jax: the module-level jnp loses the attr
    fake = types.SimpleNamespace(int8=jnp.int8, float32=jnp.float32)
    monkeypatch.setattr(qz, "jnp", fake)
    with pytest.raises(ValueError):
        qz.from_env()


def test_zero_quant_off_program_layout_unchanged(zero_env):
    """Quantize off: the engine builds the CLASSIC programs (no
    residual args, no extra outputs) — the arg layout is the
    pre-quantize one, so zero_micro's off-path parity holds."""
    zero_env.delenv("MXNET_KVSTORE_QUANTIZE", raising=False)
    ctxs = _ctxs(4)
    net, tr = _zero_trainer(ctxs)
    rng = np.random.RandomState(81)
    _zero_step(net, tr, ctxs, rng)
    eng = tr._zero
    assert eng._quant is None
    assert eng._gres_nd == [] and eng._wres_nd == []
