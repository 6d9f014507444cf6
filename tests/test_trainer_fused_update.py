"""Fused-update Trainer mode (MXNET_TRAINER_FUSED_UPDATE): the Gluon
hybridize+Trainer loop executes the SGD multi-tensor update inside the
compiled fwd+bwd program. Off-path parity, program accounting, the
deferral-safety flushes, and the fallback ladder. Tier-1 (CPU mesh)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu import autograd as ag
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _clean_arm_state():
    yield
    ag.disarm_fused_update()
    ag.flush_pending_step()


def _build(prefix, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(init=mx.initializer.Xavier(rnd_type="gaussian",
                                              magnitude=2.0))
    return net


def _data():
    rng = np.random.RandomState(0)
    return (nd.array(rng.randn(8, 12).astype(np.float32)),
            nd.array(rng.randint(0, 4, (8,)).astype(np.float32)))


def _run_loop(fused, monkeypatch, steps=4, momentum=0.9, wd=1e-4,
              prefix=None):
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE",
                       "1" if fused else "0")
    prefix = prefix or ("f_" if fused else "u_")
    net = _build(prefix)
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    opt_params = {"learning_rate": 0.1, "wd": wd}
    if momentum:
        opt_params["momentum"] = momentum
    tr = gluon.Trainer(net.collect_params(), "sgd", opt_params,
                       kvstore="device")
    x, y = _data()
    losses = []
    for _ in range(steps):
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        tr.step(8)
        losses.append(float(loss.mean().asnumpy().item()))
    params = {k.replace(prefix, ""): v.data().asnumpy()
              for k, v in net.collect_params().items()}
    states = {i: (s.asnumpy() if s is not None else None)
              for i, s in tr._updaters[0].states.items()}
    ag.disarm_fused_update()
    return losses, params, states, tr


@pytest.mark.parametrize("momentum", [0.9, 0.0])
def test_fused_update_off_path_parity(monkeypatch, momentum):
    """Flag on == flag off: losses, parameters and optimizer states are
    numerically identical after several steps (both momentum-SGD and
    plain SGD in-graph forms)."""
    l1, p1, s1, _ = _run_loop(True, monkeypatch, momentum=momentum)
    l2, p2, s2, _ = _run_loop(False, monkeypatch, momentum=momentum)
    np.testing.assert_allclose(l1, l2, rtol=1e-6)
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)
    for i in s1:
        if s1[i] is None:
            assert s2[i] is None
        else:
            np.testing.assert_allclose(s1[i], s2[i], rtol=1e-6,
                                       atol=1e-7)


def test_fused_step_engages_and_caches_one_program(monkeypatch):
    """After the first classic step the loop arms; every later step
    consumes a deferred plan through ONE cached fused-step program and
    never dispatches the separate multi-tensor optimizer kernel."""
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    net = _build("e_")
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore="device")
    x, y = _data()
    # the keys this loop adds, not the cache's length: other tests'
    # CachedOps are finalized (and their entries evicted) whenever the
    # collector gets to them
    before = set(ag._FUSED_STEP_CACHE)

    import mxnet_tpu.ops as ops_mod
    sep_calls = []
    orig = ops_mod.get_op("preloaded_multi_sgd_mom_update")

    stashed = []
    for s in range(4):
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        stashed.append(ag._PENDING[0] is not None)
        tr.step(8)
    assert stashed == [False, True, True, True]
    assert tr._fused_armed
    assert len(set(ag._FUSED_STEP_CACHE) - before) == 1
    # the fused-step program carries the update: optimizer counters
    # advanced once per step for every param
    assert tr._optimizer.num_update == 4


def test_grad_read_between_backward_and_step_flushes(monkeypatch):
    """Parameter.grad()/list_grad()/NDArray.grad in the deferral window
    execute the pending plan first — observed gradients match the
    unfused path exactly."""
    l_ref, _, _, _ = _run_loop(False, monkeypatch, steps=2, wd=0.0,
                               prefix="g1_")

    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    net = _build("g2_")
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore="device")
    x, y = _data()
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()
    tr.step(8)                      # classic + arm
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()
    assert ag._PENDING[0] is not None
    g = list(net.collect_params().values())[0].grad()
    assert ag._PENDING[0] is None   # flushed by the read
    assert np.isfinite(g.asnumpy()).all()
    tr.step(8)                      # falls back to the classic update
    # the flushed-then-classic step produced the same trajectory
    np.testing.assert_allclose(
        float(loss.mean().asnumpy().item()), l_ref[1], rtol=1e-6)


def test_unconsumed_plan_flushes_on_next_backward(monkeypatch):
    """A loop that breaks after backward() (no step) must not lose its
    gradients: the next backward flushes the stashed plan first."""
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    net = _build("h_")
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore="device")
    x, y = _data()
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()
    tr.step(8)
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()                 # stashed...
    assert ag._PENDING[0] is not None
    with autograd.record():         # ...loop "restarts" without step()
        loss = lf(net(x), y)
    loss.backward()
    # first plan executed by the entry flush, second one stashed
    assert ag._PENDING[0] is not None
    tr.step(8)


def test_guard_disables_fused_update(monkeypatch):
    """An active GradGuard needs host-visible gradients before the
    update — the fused path must never arm."""
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    monkeypatch.setenv("MXNET_GUARD_NONFINITE", "skip_step")
    net = _build("i_")
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore="device")
    x, y = _data()
    for _ in range(2):
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        tr.step(8)
    assert not tr._fused_armed


def test_guard_installed_mid_training_not_bypassed(monkeypatch):
    """Eligibility is re-validated at consume time: a GradGuard
    installed AFTER the loop armed must see the very next step (the
    stashed plan executes plainly; the classic guard path runs)."""
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    net = _build("k_")
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore="device")
    x, y = _data()
    for _ in range(2):
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        tr.step(8)
    assert tr._fused_armed
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()
    assert ag._PENDING[0] is not None   # stashed while armed
    from mxnet_tpu import guardrails
    monkeypatch.setenv("MXNET_GUARD_NONFINITE", "skip_step")
    tr.grad_guard = guardrails.from_env()
    checked = []
    orig_check = tr.grad_guard.check
    tr.grad_guard.check = lambda *a, **k: (checked.append(1),
                                           orig_check(*a, **k))[1]
    tr.step(8)                          # must route through the guard
    assert checked, "guard bypassed by the stashed fused plan"
    assert not tr._fused_armed


def test_non_sgd_optimizer_never_arms(monkeypatch):
    """Only optimizers with an implemented in-graph form (SGD) defer —
    Adam keeps the reference-idiomatic separate program."""
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    net = _build("j_")
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-3}, kvstore="device")
    x, y = _data()
    for _ in range(2):
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        tr.step(8)
    assert not tr._fused_armed
