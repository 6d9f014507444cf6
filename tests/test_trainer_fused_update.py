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


# ---------------------------------------------------------------------------
# the ladder: every rung of Trainer._fused_update_eligible() sends the
# step down the classic path (docs/TRAINING.md "Eligibility")
# ---------------------------------------------------------------------------
class _HalvedSGD(mx.optimizer.SGD):
    """A subclass may change the update math the in-graph form copies."""

    def _get_lr(self, index):
        return 0.5 * super()._get_lr(index)


def _two_ctx():
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 host devices")
    return [mx.cpu(0), mx.cpu(1)]


def _guard(**kw):
    from mxnet_tpu import guardrails
    return guardrails.GradGuard(**kw)


# rung -> what makes the Trainer ineligible: "env" is set before the
# optimizer is built, "opt" replaces the optimizer, "guard" is installed
# on the Trainer, "ctx" spreads the parameters, "add" accumulates into
# one parameter's gradient
_RUNGS = {
    "two_contexts": dict(ctx=True),
    "two_contexts_zero": dict(ctx=True, env={"MXNET_ZERO": "1"}),
    "guard_zero": dict(guard=dict(nonfinite="zero")),
    "guard_raise": dict(guard=dict(nonfinite="raise")),
    "guard_clip_norm": dict(guard=dict(nonfinite="off", clip_norm=0.5)),
    "guard_amp_scaler": dict(guard=dict(nonfinite="skip_step"), amp=True),
    "sgd_subclass": dict(
        opt=lambda: _HalvedSGD(learning_rate=0.1, momentum=0.9)),
    "multi_precision": dict(
        opt=lambda: mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                                     multi_precision=True)),
    "aggregate_num_1": dict(env={"MXNET_OPTIMIZER_AGGREGATION_SIZE": "1"}),
    "grad_req_add": dict(add=True),
}


def _ineligible_loop(rung, fused, monkeypatch, steps=3):
    from mxnet_tpu import telemetry
    from mxnet_tpu.contrib import amp
    spec = _RUNGS[rung]
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1" if fused else "0")
    monkeypatch.setenv("MXNET_ZERO", "0")
    for k, v in spec.get("env", {}).items():
        monkeypatch.setenv(k, v)
    ctxs = _two_ctx() if spec.get("ctx") else [mx.cpu(0)]
    prefix = "%s%d_" % (rung, fused)
    mx.random.seed(0)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=12))
        net.add(nn.Dense(4, in_units=16))
    net.initialize(init=mx.initializer.Xavier(), ctx=ctxs)
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    if len(ctxs) == 1:
        # (a hybridized loss pins its program to one device)
        lf.hybridize()
    params = net.collect_params()
    if spec.get("add"):
        list(params.values())[0].grad_req = "add"
    opt = spec["opt"]() if "opt" in spec else "sgd"
    tr = gluon.Trainer(params, opt,
                       None if "opt" in spec else
                       {"learning_rate": 0.1, "momentum": 0.9})
    if "guard" in spec:
        tr.grad_guard = _guard(**spec["guard"])
    if spec.get("amp"):
        amp.init(target_dtype="bfloat16")
        amp.init_trainer(tr)
        assert tr.grad_guard.scaler is tr._amp_loss_scaler
    x, y = _data()
    built = set(ag._FUSED_STEP_CACHE)
    counted = [telemetry.counter("mx_fused_step_total", donated=d).get()
               for d in "10"]
    try:
        for _ in range(steps):
            xs = gluon.utils.split_and_load(x, ctxs)
            ys = gluon.utils.split_and_load(y, ctxs)
            with autograd.record():
                ls = [lf(net(a), b) for a, b in zip(xs, ys)]
                if spec.get("amp"):
                    with amp.scale_loss(ls, tr) as scaled:
                        ls = scaled
            autograd.backward(ls)
            assert ag._PENDING[0] is None    # nothing was deferred
            tr.step(8)
    finally:
        if spec.get("amp"):
            amp.reset()
    assert not tr._fused_armed
    # what this loop added: other tests' entries go whenever the
    # collector finalizes their CachedOps
    assert not set(ag._FUSED_STEP_CACHE) - built
    assert counted == [
        telemetry.counter("mx_fused_step_total", donated=d).get()
        for d in "10"]
    assert tr._optimizer.num_update == steps
    return {k.replace(prefix, ""): v.data(ctxs[0]).asnumpy()
            for k, v in params.items()}


@pytest.fixture
def _telemetry_on(monkeypatch):
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh()
    telemetry.reset()
    yield telemetry
    monkeypatch.undo()
    telemetry.refresh()


@pytest.mark.parametrize("rung", sorted(_RUNGS))
def test_ineligible_trainer_runs_classic_and_matches(
        rung, monkeypatch, _telemetry_on):
    """One case a rung of ``_fused_update_eligible()`` (skip_step and
    Adam are held above): with the flag on, no backward is deferred, no
    ``autograd.fused_step`` program is built or counted, and three
    steps leave the weights of the flag-off run, bit for bit."""
    on = _ineligible_loop(rung, True, monkeypatch)
    off = _ineligible_loop(rung, False, monkeypatch)
    assert on.keys() == off.keys()
    for k in on:
        np.testing.assert_array_equal(on[k], off[k], err_msg=k)


# ---------------------------------------------------------------------------
# optimizer state through a checkpoint, between fused steps
# ---------------------------------------------------------------------------
def _ckpt_rig(prefix, momentum):
    net = _build(prefix)
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    opt = {"learning_rate": 0.1, "wd": 1e-4}
    if momentum:
        opt["momentum"] = momentum
    tr = gluon.Trainer(net.collect_params(), "sgd", opt, kvstore="device")
    rng = np.random.RandomState(3)
    xs = rng.randn(6, 8, 12).astype(np.float32)
    ys = rng.randint(0, 4, (6, 8)).astype(np.float32)

    def fwd_bwd(i):
        with autograd.record():
            loss = lf(net(nd.array(xs[i])), nd.array(ys[i]))
        loss.backward()

    def snapshot():
        return ({k.replace(prefix, ""): v.data().asnumpy()
                 for k, v in net.collect_params().items()},
                {i: (None if s is None else s.asnumpy())
                 for i, s in tr._updaters[0].states.items()})

    return net, tr, fwd_bwd, snapshot


def _same(a, b):
    (pa, sa), (pb, sb) = a, b
    assert pa.keys() == pb.keys() and sa.keys() == sb.keys()
    for k in pa:
        np.testing.assert_array_equal(pa[k], pb[k], err_msg=k)
    for i in sa:
        if sa[i] is None:
            assert sb[i] is None
        else:
            np.testing.assert_array_equal(sa[i], sb[i], err_msg=str(i))


@pytest.mark.parametrize("momentum", [0.9, 0.0])
def test_states_roundtrip_between_fused_steps_bitwise(
        monkeypatch, tmp_path, momentum):
    """``save_states`` / ``load_states`` between fused steps, and a
    ``save_states`` between ``backward()`` and ``step()`` while the
    plan is pending: the pending plan stays pending and its step stays
    fused, the run that saved and reloaded ends where the run that did
    neither ends, and a fresh net resumed from the pending-plan
    checkpoint ends there too, all bit for bit."""
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    tag = "m%d" % int(momentum * 10)
    _, tr, fwd_bwd, snapshot = _ckpt_rig("ck_a%s_" % tag, momentum)
    for i in range(6):
        fwd_bwd(i)
        tr.step(8)
    ref = snapshot()

    _, tr, fwd_bwd, snapshot = _ckpt_rig("ck_b%s_" % tag, momentum)
    for i in range(3):
        fwd_bwd(i)
        tr.step(8)
    assert tr._fused_armed
    f1, f2 = str(tmp_path / "between"), str(tmp_path / "pending")
    tr.save_states(f1)
    tr.load_states(f1)                  # new state arrays, same values
    fwd_bwd(3)
    plan = ag._PENDING[0]
    assert plan is not None
    tr.save_states(f2)
    assert ag._PENDING[0] is plan       # saved around it, not through it
    with open(f1, "rb") as a, open(f2, "rb") as b:
        assert a.read() == b.read()     # step 3's update is not in yet
    at_ckpt = snapshot()
    assert ag._PENDING[0] is plan       # reading weights forces nothing
    tapes = {k[0] for k in ag._FUSED_STEP_CACHE}
    tr.step(8)
    # the same tape and update: at most its variant that donates
    # nothing, for the step whose buffers the snapshot still held
    assert tr._fused_armed
    assert {k[0] for k in ag._FUSED_STEP_CACHE} <= tapes
    for i in range(4, 6):
        fwd_bwd(i)
        tr.step(8)
    _same(snapshot(), ref)

    net, tr, fwd_bwd, snapshot = _ckpt_rig("ck_c%s_" % tag, momentum)
    for k, p in net.collect_params().items():
        p.set_data(nd.array(at_ckpt[0][k.replace("ck_c%s_" % tag, "")]))
    tr.load_states(f2)
    for i in range(3, 6):
        fwd_bwd(i)
        tr.step(8)
    _same(snapshot(), ref)


# ---------------------------------------------------------------------------
# outputs a loop reads, and statistics the next forward reads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["loss_read", "batchnorm", "both",
                                  "loss_read_in_window",
                                  "both_in_window"])
def test_forced_outputs_keep_the_fused_program(
        case, monkeypatch, _telemetry_on):
    """A loop that reads its loss every step, and a net whose BatchNorm
    running statistics feed the next forward (the ResNet cells' own
    shape), stay on the fused path: one program for the tape, every
    step after the first counted ``mx_fused_step_total{donated="1"}``,
    losses and weights those of the classic run bit for bit. A read
    INSIDE the backward()..step() window forces the forward there and
    then: the step is still the fused one and still exact, and whether
    it donates is left open (with BatchNorm it does not today:
    docs/TRAINING.md)."""
    telemetry = _telemetry_on
    in_window = case.endswith("_in_window")
    case = case.replace("_in_window", "")

    def run(fused):
        monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE",
                           "1" if fused else "0")
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Conv2D(6, 3, padding=1))
        if case != "loss_read":
            net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"), nn.GlobalAvgPool2D(), nn.Dense(5))
        net.initialize()
        net.hybridize(static_alloc=True, static_shape=True)
        lf = gluon.loss.SoftmaxCrossEntropyLoss()
        lf.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
        rng = np.random.RandomState(0)
        x = nd.array(rng.randn(8, 3, 8, 8).astype(np.float32))
        y = nd.array(rng.randint(0, 5, (8,)).astype(np.float32))
        losses = []
        for _ in range(5):
            with autograd.record():
                loss = lf(net(x), y)
            loss.backward()
            if in_window:
                losses.append(loss.asnumpy().copy())
            tr.step(8)
            if case != "batchnorm" and not in_window:
                losses.append(loss.asnumpy().copy())
        ag.disarm_fused_update()
        return losses, [p.data().asnumpy()
                        for p in net.collect_params().values()]

    telemetry.reset()
    before = set(ag._FUSED_STEP_CACHE)
    losses, weights = run(True)
    assert len({k[0] for k in set(ag._FUSED_STEP_CACHE) - before}) == 1
    counts = [int(telemetry.counter("mx_fused_step_total", donated=d).get())
              for d in "10"]
    assert sum(counts) == 4 and (in_window or counts == [4, 0])
    ref_losses, ref_weights = run(False)
    assert len(losses) == len(ref_losses)
    for a, b in zip(losses + weights, ref_losses + ref_weights):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# one Trainer.step is one marked step, whatever became of its update
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["guard_skipped", "plan_flushed_by_a_read"])
def test_a_step_off_the_fused_path_is_still_one_marked_step(
        case, monkeypatch, _telemetry_on):
    """``Trainer.step`` marks exactly once: a step whose update a guard
    dropped (``useful=False``: its interval is debited from goodput)
    and a step whose pending plan a gradient read flushed (classic
    update, the loop re-arms after it) each count one
    ``mx_steps_total`` and close one record of ``step_log`` holding one
    ``step::update``."""
    from mxnet_tpu import faultinject
    telemetry = _telemetry_on
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    net = _build("mk_%s_" % case)
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    if case == "guard_skipped":
        tr.grad_guard = _guard(nonfinite="skip_step")
    x, y = _data()

    def fwd_bwd():
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()

    for _ in range(2):
        fwd_bwd()
        tr.step(8)
    telemetry.reset()
    fwd_bwd()
    tr.step(8)                          # opens the meters' window
    before = [p.data().asnumpy() for p in net.collect_params().values()]
    fwd_bwd()
    try:
        if case == "guard_skipped":
            faultinject.set_fault("nan_grad", 1.0, max_fires=1)
        else:
            assert ag._PENDING[0] is not None
            list(net.collect_params().values())[0].grad()
            assert ag._PENDING[0] is None
        tr.step(8)
    finally:
        faultinject.reset()
    after = [p.data().asnumpy() for p in net.collect_params().values()]
    moved = any(not np.array_equal(a, b) for a, b in zip(before, after))
    snap = telemetry.snapshot()
    assert snap["steps"] == 2
    log = telemetry.step_log()
    assert [r["step"] for r in log] == [0, 1]
    spans = log[1]["spans"]
    assert spans["step::update"]["count"] == 1
    assert "step::update.launch" not in spans
    if case == "guard_skipped":
        assert not moved and tr.grad_guard.skipped_steps == 1
        assert "step::guard" in spans and "step::optimizer" not in spans
        assert snap["gauges"]["mx_goodput"] == 0.0
    else:
        assert moved and spans["step::optimizer"]["count"] == 1
        assert snap["gauges"]["mx_goodput"] > 0.0
        assert tr._fused_armed          # the next backward defers again
