"""ZeRO-style weight-update sharding (MXNET_ZERO; gluon/zero.py,
docs/ZERO.md): on/off parity for SGD / SGD-momentum / Adam including
param counts that don't divide the replica count, GradGuard
skip/zero/clip on the scattered shards, topology-portable optimizer
checkpoints, the eligibility-ladder fallbacks, sharded-state memory
accounting and the single-watched-program contract. Tier-1 (8-device
CPU mesh)."""
import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, compilewatch, commwatch, gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import zero as zero_mod


def _ndev(n):
    import jax
    if jax.device_count() < n:
        pytest.skip("needs %d devices" % n)
    return [mx.tpu(i) for i in range(n)]


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXNET_ZERO", raising=False)
    monkeypatch.delenv("MXNET_ZERO_DCN", raising=False)
    monkeypatch.delenv("MXNET_ZERO_MIN_SIZE", raising=False)
    monkeypatch.delenv("MXNET_GUARD_NONFINITE", raising=False)
    monkeypatch.delenv("MXNET_GUARD_CLIP_NORM", raising=False)
    # a benchmark test earlier in this worker may have left commwatch
    # switched off for the process (mxbench.run.context)
    monkeypatch.delenv("MXNET_COMMWATCH", raising=False)
    telemetry.refresh()
    yield
    telemetry.refresh()
    telemetry.reset()
    commwatch.reset()


def _build(zero, ndev=4, opt="sgd", opt_kw=None, seed=5, dcn=0,
           layers=("dense0_", "dense1_")):
    os.environ["MXNET_ZERO"] = "1" if zero else "0"
    if dcn:
        os.environ["MXNET_ZERO_DCN"] = str(dcn)
    ctxs = _ndev(ndev)
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    # sizes 35, 5, 15, 3: none divisible by 4 or 8 replicas, and the
    # 3-element bias is SMALLER than the replica count (frag=1, most
    # replicas own pure padding for it) — the uneven-shard edge cases
    # (named here, dense0 and dense1 in every net: a checkpoint is
    # restored by the parameters' sorted names, and under the
    # process-wide counter two nets of one test can stand either side
    # of dense9 / dense10, which sort the other way: ROADMAP C3 and
    # test_zero_checkpoint_across_a_digit_boundary_of_the_layer_names)
    with net.name_scope():
        net.add(nn.Dense(5, in_units=7, prefix=layers[0]),
                nn.Dense(3, prefix=layers[1]))
    net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
    net(nd.ones((2, 7), ctx=ctxs[0]))
    tr = gluon.Trainer(net.collect_params(), opt,
                       opt_kw or {"learning_rate": 0.05},
                       kvstore="device")
    return net, tr, ctxs


def _run(net, tr, ctxs, steps, seed=11, poison_step=None):
    rng = np.random.RandomState(seed)
    for s in range(steps):
        x = rng.rand(8, 7).astype(np.float32)
        y = rng.rand(8, 3).astype(np.float32)
        xs = gluon.utils.split_and_load(nd.array(x), ctxs)
        ys = gluon.utils.split_and_load(nd.array(y), ctxs)
        with autograd.record():
            losses = [((net(a) - b) ** 2).sum() for a, b in zip(xs, ys)]
        for l in losses:
            l.backward()
        if s == poison_step:
            for g in list(net.collect_params().values())[0].list_grad():
                g[:] = float("nan")
        tr.step(8)


def _weights(net, ctx):
    return [p.data(ctx).asnumpy() for p in net.collect_params().values()]


def _assert_parity(net_a, ctx_a, net_b, ctx_b, rtol=1e-5, atol=1e-6):
    for (na, pa), (nb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        a = pa.data(ctx_a).asnumpy()
        b = pb.data(ctx_b).asnumpy()
        assert np.allclose(a, b, rtol=rtol, atol=atol), \
            (na, float(np.abs(a - b).max()))


# ---------------------------------------------------------------------------
# on/off parity (the acceptance suite)
# ---------------------------------------------------------------------------
@pytest.mark.zero
@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
], ids=["sgd", "sgd_momentum", "adam"])
def test_zero_on_off_parity(opt, kw):
    net_z, tr_z, ctx_z = _build(True, opt=opt, opt_kw=dict(kw))
    _run(net_z, tr_z, ctx_z, 4)
    assert isinstance(tr_z._zero, zero_mod.ZeroEngine), \
        "MXNET_ZERO=1 eligible Trainer did not shard"
    net_r, tr_r, ctx_r = _build(False, opt=opt, opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 4)
    _assert_parity(net_z, ctx_z[0], net_r, ctx_r[0])
    # update counters advance once per STEP on both paths
    assert tr_z._optimizer.num_update == 4
    assert tr_r._optimizer.num_update == 4


@pytest.mark.zero
def test_zero_replicas_stay_bit_identical():
    net, tr, ctxs = _build(True, opt="adam", opt_kw={"learning_rate": 0.01})
    _run(net, tr, ctxs, 3)
    for p in net.collect_params().values():
        ref = p.data(ctxs[0]).asnumpy()
        for c in ctxs[1:]:
            # the all-gathered weights are the SAME shard bytes on
            # every replica — bitwise, not just close
            assert np.array_equal(p.data(c).asnumpy(), ref), p.name


def test_replicated_adam_replicas_coherent():
    """Regression for the per-replica update-count drift: the N
    updaters share the optimizer, and before the Trainer._update
    rewind each replica saw a different Adam bias-correction t and the
    replicas silently diverged (~4e-3/step)."""
    net, tr, ctxs = _build(False, opt="adam", opt_kw={"learning_rate": 0.01})
    _run(net, tr, ctxs, 2)
    assert tr._optimizer.num_update == 2     # once per step, not per replica
    for p in net.collect_params().values():
        ref = p.data(ctxs[0]).asnumpy()
        for c in ctxs[1:]:
            assert np.allclose(p.data(c).asnumpy(), ref, rtol=0, atol=0), \
                p.name


# ---------------------------------------------------------------------------
# GradGuard on the scattered shards
# ---------------------------------------------------------------------------
@pytest.mark.zero
@pytest.mark.guard
@pytest.mark.parametrize("policy", ["skip_step", "zero"])
@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    # adam's bias correction is t-dependent: a skipped step must NOT
    # advance the update counters (review finding: hyperparams were
    # computed before the guard verdict, desyncing t after any skip)
    ("adam", {"learning_rate": 0.01}),
], ids=["sgd_momentum", "adam"])
def test_zero_guard_policy_parity(policy, opt, kw, monkeypatch):
    monkeypatch.setenv("MXNET_GUARD_NONFINITE", policy)
    net_z, tr_z, ctx_z = _build(True, opt=opt, opt_kw=dict(kw))
    _run(net_z, tr_z, ctx_z, 3, poison_step=1)
    net_r, tr_r, ctx_r = _build(False, opt=opt, opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 3, poison_step=1)
    _assert_parity(net_z, ctx_z[0], net_r, ctx_r[0])
    assert tr_z._optimizer.num_update == tr_r._optimizer.num_update
    gz, gr = tr_z.grad_guard, tr_r.grad_guard
    assert gz.nonfinite_steps == gr.nonfinite_steps == 1
    if policy == "skip_step":
        assert gz.skipped_steps == gr.skipped_steps == 1
    else:
        assert gz.zeroed_steps == gr.zeroed_steps == 1
    # one reduction sync per guarded step on both paths
    assert gz.sync_count == gr.sync_count == 3


@pytest.mark.zero
@pytest.mark.guard
def test_zero_guard_clip_parity(monkeypatch):
    monkeypatch.setenv("MXNET_GUARD_CLIP_NORM", "0.5")
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    net_z, tr_z, ctx_z = _build(True, opt="sgd", opt_kw=dict(kw))
    _run(net_z, tr_z, ctx_z, 3)
    net_r, tr_r, ctx_r = _build(False, opt="sgd", opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 3)
    _assert_parity(net_z, ctx_z[0], net_r, ctx_r[0])
    assert tr_z.grad_guard.clipped_steps == tr_r.grad_guard.clipped_steps > 0
    assert np.isclose(tr_z.grad_guard.last_norm, tr_r.grad_guard.last_norm,
                      rtol=1e-4)


# ---------------------------------------------------------------------------
# topology-portable checkpoints
# ---------------------------------------------------------------------------
@pytest.mark.zero
def test_zero_save_states_is_canonical(tmp_path):
    """A sharded Trainer's save_states must byte-match the replicated
    layout: same {index: state} pickle a replicated Trainer produces
    after the identical run."""
    kw = {"learning_rate": 0.01}
    net_z, tr_z, ctx_z = _build(True, opt="adam", opt_kw=dict(kw))
    _run(net_z, tr_z, ctx_z, 3)
    net_r, tr_r, ctx_r = _build(False, opt="adam", opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 3)
    fz, fr = str(tmp_path / "z.st"), str(tmp_path / "r.st")
    tr_z.save_states(fz)
    tr_r.save_states(fr)
    sz = pickle.load(open(fz, "rb"))
    sr = pickle.load(open(fr, "rb"))
    assert set(sz) == set(sr)
    for k in sz:
        tz = sz[k] if isinstance(sz[k], tuple) else (sz[k],)
        trp = sr[k] if isinstance(sr[k], tuple) else (sr[k],)
        for a, b in zip(tz, trp):
            assert a.shape == b.shape
            assert np.allclose(a.asnumpy(), b.asnumpy(),
                               rtol=1e-5, atol=1e-7)


def _round_trip(tmp_path, saved, restored):
    """sharded(4), its layers named ``saved`` -> save -> load on
    replicated(2) AND on sharded(8), their layers named ``restored``:
    both restored trainers continue bit-compatibly."""
    kw = {"learning_rate": 0.01}
    net_a, tr_a, ctx_a = _build(True, ndev=4, opt="adam", opt_kw=dict(kw),
                                layers=saved)
    _run(net_a, tr_a, ctx_a, 3)
    ckpt = str(tmp_path / "zero.states")
    tr_a.save_states(ckpt)
    w0 = _weights(net_a, ctx_a[0])

    net_b, tr_b, ctx_b = _build(False, ndev=2, opt="adam", opt_kw=dict(kw),
                                layers=restored)
    net_c, tr_c, ctx_c = _build(True, ndev=8, opt="adam", opt_kw=dict(kw),
                                layers=restored)
    for w, (_, pb), (_, pc) in zip(w0, net_b.collect_params().items(),
                                   net_c.collect_params().items()):
        pb.set_data(nd.array(w))
        pc.set_data(nd.array(w))
    tr_b.load_states(ckpt)
    tr_c.load_states(ckpt)
    assert isinstance(tr_c._zero, zero_mod.ZeroEngine)
    _run(net_b, tr_b, ctx_b, 2, seed=17)
    _run(net_c, tr_c, ctx_c, 2, seed=17)
    _assert_parity(net_b, ctx_b[0], net_c, ctx_c[0])


@pytest.mark.zero
def test_zero_checkpoint_round_trips_across_topologies(tmp_path):
    """Feeds ROADMAP item 5: resume on a different chip count."""
    _round_trip(tmp_path, ("dense0_", "dense1_"), ("dense0_", "dense1_"))


@pytest.mark.zero
@pytest.mark.xfail(strict=True, reason="ROADMAP C3: a checkpoint is "
                   "restored by the parameters' sorted names")
def test_zero_checkpoint_across_a_digit_boundary_of_the_layer_names(tmp_path):
    """The names the process-wide counter gives a net built after eight
    other ``Dense`` blocks: ``dense10_`` sorts before ``dense9_``, so
    the saved net's states stand second layer first, the restoring
    nets' first layer first, and a 3-element bias's state meets a
    5-element bias (``restore size mismatch``). Counts as neither a
    pass nor a failure while the program is as it is, and fails
    (strict) on the day the program restores by position: take the
    marker off then."""
    _round_trip(tmp_path, ("dense9_", "dense10_"), ("dense11_", "dense12_"))


@pytest.mark.zero
def test_zero_loads_step0_checkpoint(tmp_path):
    """A checkpoint saved BEFORE any optimizer step pickles empty
    states; loading it under MXNET_ZERO must mean 'fresh state', like
    the replicated path's lazy creation (review finding: it raised
    missing-parameter)."""
    kw = {"learning_rate": 0.01}
    net_r, tr_r, ctx_r = _build(False, opt="adam", opt_kw=dict(kw))
    ckpt = str(tmp_path / "step0.states")
    tr_r.save_states(ckpt)       # no step yet: empty {}
    net_z, tr_z, ctx_z = _build(True, opt="adam", opt_kw=dict(kw))
    tr_z.load_states(ckpt)       # must not raise
    _run(net_z, tr_z, ctx_z, 2)
    _run(net_r, tr_r, ctx_r, 2)
    _assert_parity(net_z, ctx_z[0], net_r, ctx_r[0])


# ---------------------------------------------------------------------------
# eligibility ladder / fallbacks
# ---------------------------------------------------------------------------
@pytest.mark.zero
def test_zero_fallback_unsupported_optimizer():
    """LAMB has no elementwise fragment form (layerwise norms): with
    MXNET_ZERO=1 the Trainer must fall back to the replicated path and
    still train correctly."""
    kw = {"learning_rate": 0.01}
    net_z, tr_z, ctx_z = _build(True, opt="lamb", opt_kw=dict(kw))
    _run(net_z, tr_z, ctx_z, 2)
    assert tr_z._zero is False and tr_z._zero_bailed
    net_r, tr_r, ctx_r = _build(False, opt="lamb", opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 2)
    _assert_parity(net_z, ctx_z[0], net_r, ctx_r[0])


@pytest.mark.zero
def test_zero_fallback_single_device():
    os.environ["MXNET_ZERO"] = "1"
    mx.random.seed(0)
    net = nn.Dense(4, in_units=4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    with autograd.record():
        loss = net(nd.ones((2, 4))).sum()
    loss.backward()
    tr.step(2)
    assert not isinstance(tr._zero, zero_mod.ZeroEngine)


@pytest.mark.zero
def test_zero_min_size_fallback(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_MIN_SIZE", "1000000")
    net, tr, ctxs = _build(True)
    _run(net, tr, ctxs, 1)
    assert tr._zero is False and tr._zero_bailed


@pytest.mark.zero
def test_zero_eligibility_reasons():
    os.environ["MXNET_ZERO"] = "1"
    ctxs = _ndev(2)
    mx.random.seed(0)
    net = nn.Dense(4, in_units=4)
    net.initialize(ctx=ctxs)
    tr = gluon.Trainer(net.collect_params(), "lamb",
                       {"learning_rate": 0.01}, kvstore="device")
    tr._contexts = tr._check_contexts()
    ok, reason = zero_mod.eligibility(tr)
    assert not ok and "fragment form" in reason
    tr2 = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.01}, kvstore="device",
                        compression_params={"type": "2bit",
                                            "threshold": 0.5})
    tr2._contexts = tr2._check_contexts()
    ok, reason = zero_mod.eligibility(tr2)
    assert not ok and "compression" in reason


# ---------------------------------------------------------------------------
# memory accounting + observability
# ---------------------------------------------------------------------------
@pytest.mark.zero
@pytest.mark.obs
def test_zero_state_memory_and_gauges(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh()
    telemetry.reset()
    kw = {"learning_rate": 0.01}
    ndev = 4
    net_z, tr_z, ctx_z = _build(True, ndev=ndev, opt="adam",
                                opt_kw=dict(kw))
    _run(net_z, tr_z, ctx_z, 1)
    net_r, tr_r, ctx_r = _build(False, ndev=ndev, opt="adam",
                                opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 1)
    zb, rb = tr_z.optimizer_state_bytes(), tr_r.optimizer_state_bytes()
    assert rb > 0 and zb > 0
    # >= (N-1)/N of the replicated state is gone, modulo the per-param
    # padding (the 3-element bias costs ndev-3 pad elements per kind)
    assert zb <= rb / ndev * 1.5, (zb, rb)
    assert zb < rb / 2
    # the shard gauges are exported per replica context
    snap = telemetry.snapshot()
    keys = [k for k in snap["gauges"] if k.startswith("mx_zero_state_bytes")]
    assert len(keys) == ndev, snap["gauges"]
    saved = [v for k, v in snap["gauges"].items()
             if k.startswith("mx_zero_state_saved_bytes")]
    assert all(v > 0 for v in saved)


@pytest.mark.zero
@pytest.mark.obs
def test_zero_single_watched_program_and_comm(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh()
    telemetry.reset()
    commwatch.reset()
    net, tr, ctxs = _build(True, opt="sgd",
                           opt_kw={"learning_rate": 0.05, "momentum": 0.9})
    _run(net, tr, ctxs, 3)
    snap = telemetry.snapshot()
    # RS -> shard-update -> AG compiled as ONE watched program, cached
    # across steps (no recompiles)
    assert snap["counters"].get('mx_compile_total{fn="zero.step"}') == 1, \
        {k: v for k, v in snap["counters"].items() if "zero" in k}
    assert 'mx_recompiles_total{fn="zero.step"}' not in snap["counters"]
    assert commwatch.program_execs("zero.step") == 3
    # the RS/AG path shows up on the dp axis with nonzero payloads
    rows = {(r["op"], r["axis"]): r for r in commwatch.report()}
    rs = rows.get(("reduce_scatter", "dp"))
    ag = rows.get(("allgather", "dp"))
    assert rs is not None and rs["bytes"] > 0 and rs["bus_bytes"] > 0
    assert ag is not None and ag["bytes"] > 0 and ag["bus_bytes"] > 0
    # RS+AG == AR in bus-traffic terms, on the PADDED payload exactly
    # (this model's tiny params carry ~10% pad — a pathological share
    # real models don't have; tools/zero_micro.py gates the realistic
    # <=1.1x against the UNpadded allreduce baseline)
    n = len(ctxs)
    padded_bytes = sum(g.C * n * np.dtype(g.dtype).itemsize
                       for g in tr._zero._groups)
    ar_bus = padded_bytes * 2 * (n - 1) / n
    per_step = (rs["bus_bytes"] + ag["bus_bytes"]) / 3
    assert abs(per_step - ar_bus) <= ar_bus * 0.01, (per_step, ar_bus)


@pytest.mark.zero
def test_zero_hierarchical_dcn_parity(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh()
    telemetry.reset()
    commwatch.reset()
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    net_z, tr_z, ctx_z = _build(True, ndev=8, opt="sgd", opt_kw=dict(kw),
                                dcn=2)
    _run(net_z, tr_z, ctx_z, 3)
    assert isinstance(tr_z._zero, zero_mod.ZeroEngine)
    assert tr_z._zero._n_dcn == 2
    net_r, tr_r, ctx_r = _build(False, ndev=8, opt="sgd", opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 3)
    _assert_parity(net_z, ctx_z[0], net_r, ctx_r[0])
    # both tiers of the hierarchy carried RS and AG traffic
    rows = {(r["op"], r["axis"]): r for r in commwatch.report()}
    for op in ("reduce_scatter", "allgather"):
        for axis in ("dp", "dcn"):
            assert rows.get((op, axis), {}).get("bytes", 0) > 0, (op, axis)


@pytest.mark.zero
def test_zero_hierarchical_checkpoint_permutation(tmp_path):
    """The dcn ownership permutation must be honored by the gather:
    a dcn=2-sharded save equals the replicated save."""
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    net_z, tr_z, ctx_z = _build(True, ndev=8, opt="sgd", opt_kw=dict(kw),
                                dcn=2)
    _run(net_z, tr_z, ctx_z, 2)
    net_r, tr_r, ctx_r = _build(False, ndev=8, opt="sgd", opt_kw=dict(kw))
    _run(net_r, tr_r, ctx_r, 2)
    fz, fr = str(tmp_path / "z.st"), str(tmp_path / "r.st")
    tr_z.save_states(fz)
    tr_r.save_states(fr)
    sz = pickle.load(open(fz, "rb"))
    sr = pickle.load(open(fr, "rb"))
    for k in sz:
        assert np.allclose(sz[k].asnumpy(), sr[k].asnumpy(),
                           rtol=1e-5, atol=1e-7), k


@pytest.mark.zero
def test_zero_grads_stay_local_documented_divergence():
    """Documented divergence (docs/ZERO.md): after a sharded step the
    per-replica gradient arrays keep their LOCAL pre-reduction values
    (the reduced grads only exist scattered inside the program)."""
    net, tr, ctxs = _build(True)
    rng = np.random.RandomState(0)
    x = rng.rand(8, 7).astype(np.float32)
    y = rng.rand(8, 3).astype(np.float32)
    xs = gluon.utils.split_and_load(nd.array(x), ctxs)
    ys = gluon.utils.split_and_load(nd.array(y), ctxs)
    with autograd.record():
        losses = [((net(a) - b) ** 2).sum() for a, b in zip(xs, ys)]
    for l in losses:
        l.backward()
    pre = [g.asnumpy() for g in
           list(net.collect_params().values())[0].list_grad()]
    tr.step(8)
    post = [g.asnumpy() for g in
            list(net.collect_params().values())[0].list_grad()]
    for a, b in zip(pre, post):
        assert np.array_equal(a, b)
