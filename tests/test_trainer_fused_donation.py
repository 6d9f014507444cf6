"""The Gluon fused step donates the buffers it overwrites (weights,
momenta, last step's gradients, BatchNorm's running statistics) when
nothing else holds them, and runs the program that donates nothing when
something does. What is held here: the donating program computes what
the other computes, bit for bit; no handle a caller took before a step
becomes unreadable or changes value because the step ran; an alias
costs one step without donation, not a mode; the compiled program
aliases what it was given; the arrays a loop steps on are never
donated. Tier-1 (CPU: jax honours donation there)."""
import numpy as np
import pytest

import jax
import mxnet_tpu as mx
from mxnet_tpu import autograd, compilewatch, gluon, modelwatch, nd, telemetry
from mxnet_tpu import autograd as ag
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _telemetry_on(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1")
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()
    yield
    ag.disarm_fused_update()
    ag.flush_pending_step()
    telemetry.refresh()


def _loop(momentum=0.9, batch=8, batch_norm=True):
    """A BatchNorm net in a hybridized Gluon loop with SGD, past its
    first (classic) step: every later step is a fused one."""
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(6, 3, padding=1))
    if batch_norm:
        net.add(nn.BatchNorm())
    net.add(nn.Activation("relu"), nn.GlobalAvgPool2D(), nn.Dense(5))
    net.initialize()
    net.hybridize(static_alloc=True, static_shape=True)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    lf.hybridize()
    opt = {"learning_rate": 0.1, "wd": 1e-4}
    if momentum:
        opt["momentum"] = momentum
    tr = gluon.Trainer(net.collect_params(), "sgd", opt)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(batch, 3, 8, 8).astype(np.float32))
    y = nd.array(rng.randint(0, 5, (batch,)).astype(np.float32))

    def step():
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        tr.step(batch)

    step()
    return net, tr, step, (x, y)


def _counts():
    return tuple(int(telemetry.counter("mx_fused_step_total",
                                       donated=d).get()) for d in "10")


def _state(net, tr):
    """Everything a step writes, on the host."""
    out = {}
    for i, (name, p) in enumerate(net.collect_params().items()):
        # by position: the names carry a counter that differs per net
        name = "%d%s" % (i, "_running" if "running" in name else "")
        out["w:" + name] = p.data().asnumpy()
        if p.grad_req != "null":
            out["g:" + name] = p.grad().asnumpy()
    for i, s in tr._updaters[0].states.items():
        if s is not None:
            out["m:%d" % i] = s.asnumpy()
    return out


def _trained(net):
    return [p for p in net.collect_params().values()
            if p.grad_req != "null"]


# ---------------------------------------------------------------------------
# (a) the donating program computes what the other computes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("momentum", [0.9, 0.0])
def test_donating_steps_match_the_undonated_program_bitwise(momentum):
    net, tr, step, _ = _loop(momentum)
    for _ in range(5):
        step()
    assert _counts() == (5, 0)
    donated = _state(net, tr)

    telemetry.reset()
    net, tr, step, _ = _loop(momentum)
    for _ in range(5):
        # a second holder of one weight: the whole step keeps its buffers
        held = _trained(net)[0].data().detach()
        step()
        del held
    assert _counts() == (0, 5)
    plain = _state(net, tr)

    assert donated.keys() == plain.keys()
    assert any(k.startswith("w:") and "running" in k for k in donated)
    for k in donated:
        np.testing.assert_array_equal(donated[k], plain[k], err_msg=k)


# ---------------------------------------------------------------------------
# (b) whoever took a handle before the step can read it after
# ---------------------------------------------------------------------------
def _weight(net, tr):
    return _trained(net)[0].data()


def _running_stat(net, tr):
    return [p for n, p in net.collect_params().items()
            if "running_mean" in n][0].data()


def _gradient(net, tr):
    return _trained(net)[0].data()._grad


def _momentum(net, tr):
    return next(s for s in tr._updaters[0].states.values() if s is not None)


def _session_outputs(sess, x):
    out = sess.infer(x.asnumpy())
    return np.asarray(out[0] if isinstance(out, (list, tuple)) else out)


def _mesh_session(net, x):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    return net.serve_session(x, mesh=mesh)


# name -> (the owner's handle, how the caller gets a second one over
# the same buffer)
_ALIASES = {
    "data.detach": (_weight, lambda h: h.detach()),
    "data.copy": (_weight, lambda h: h.copy()),
    "data.copyto_same_ctx": (_weight, lambda h: h.copyto(h.ctx)),
    "running_stat.detach": (_running_stat, lambda h: h.detach()),
    "grad.copy": (_gradient, lambda h: h.copy()),
    "momentum.copy": (_momentum, lambda h: h.copy()),
    "data.raw_jax_value": (_weight, lambda h: h._jax()),
    "kvstore.init": (_weight, None),
    "kvstore.pull": (_weight, None),
}


@pytest.mark.parametrize("how", sorted(_ALIASES))
def test_handle_taken_before_a_step_reads_its_old_values_after(how):
    net, tr, step, _ = _loop()
    step()
    assert _counts() == (1, 0)
    owner, take = _ALIASES[how]
    h = owner(net, tr)
    if how == "kvstore.init":
        kv = mx.kvstore.create("local")
        kv.init(0, h)
        alias = next(iter(kv._store.values()))
    elif how == "kvstore.pull":
        # the store's value lands in the parameter: one buffer, two
        # holders
        kv = mx.kvstore.create("local")
        kv.init(0, h * 2)
        kv.pull(0, out=h)
        alias = next(iter(kv._store.values()))
    else:
        alias = take(h)
    before = np.asarray(alias if how == "data.raw_jax_value"
                        else alias.asnumpy()).copy()

    step()                      # meets the alias: donates nothing
    assert _counts() == (1, 1)
    after = np.asarray(alias if how == "data.raw_jax_value"
                       else alias.asnumpy())
    np.testing.assert_array_equal(after, before)
    assert not np.array_equal(h.asnumpy(), before)   # the owner moved on

    step()                      # the owner's buffer is its own again
    assert _counts() == (2, 1)
    np.testing.assert_array_equal(
        np.asarray(alias if how == "data.raw_jax_value"
                   else alias.asnumpy()), before)


@pytest.mark.parametrize("owner", [_weight, _momentum, _gradient],
                         ids=["data.as_in_context_same_ctx",
                              "updater.state", "data.grad"])
def test_the_owners_own_handle_follows_the_step(owner):
    """``as_in_context`` on the same context, the updater's state and
    ``Parameter.grad()``'s array are the very handles the step rebinds,
    not second holders: the step donates, and they read the new values
    as they always did."""
    net, tr, step, _ = _loop()
    step()
    h = owner(net, tr)
    same = h.as_in_context(h.ctx)
    assert same is h
    before = same.asnumpy().copy()
    step()
    assert _counts() == (2, 0)
    assert not np.array_equal(same.asnumpy(), before)


def test_basic_index_view_reads_the_bases_new_values():
    net, tr, step, _ = _loop()
    step()
    w = _weight(net, tr)
    view = w[0:2]
    before = view.asnumpy().copy()
    step()
    assert _counts() == (2, 0)         # a view holds the handle, not the buffer
    np.testing.assert_array_equal(view.asnumpy(), w.asnumpy()[0:2])
    assert not np.array_equal(view.asnumpy(), before)


def test_modelwatch_sampling_keeps_its_pre_update_aliases(monkeypatch):
    monkeypatch.setenv("MXNET_MODELWATCH", "1")
    monkeypatch.setenv("MXNET_MODELWATCH_EVERY", "2")
    modelwatch.reset()
    try:
        net, tr, step, _ = _loop()          # step 0 (classic), sampled
        assert tr.modelwatch is not None
        for _ in range(4):                  # steps 1..4: 2 and 4 sampled
            step()
        assert _counts() == (2, 2)
        ring = modelwatch.ring()
        assert ring and all(np.isfinite(v) for r in ring
                            for v in r.get("update_ratio", {}).values())
    finally:
        modelwatch.reset()


def test_live_serving_session_sees_every_step():
    """A single-device session reads the parameters' buffers at each
    request and keeps none: it is no second holder."""
    net, tr, step, (x, _y) = _loop()
    sess = net.serve_session(x)
    first = _session_outputs(sess, x)
    step()
    second = _session_outputs(sess, x)
    step()
    assert _counts() == (2, 0)
    assert not np.array_equal(first, second)
    assert not np.array_equal(second, _session_outputs(sess, x))


def test_mesh_session_capture_survives_the_step():
    """Mesh mode ``device_put``s the weights once, replicated: copies
    over the parameters' own buffers that no reference count shows, so
    the session keeps the source arrays beside them."""
    net, tr, step, (x, _y) = _loop()
    step()
    sess = _mesh_session(net, x)
    captured = [np.asarray(w).copy() for w in sess._weight_args()]
    first = _session_outputs(sess, x)
    step()
    assert _counts() == (1, 1)
    for w, was in zip(sess._weight_args(), captured):
        np.testing.assert_array_equal(np.asarray(w), was)
    np.testing.assert_array_equal(_session_outputs(sess, x), first)
    step()
    assert _counts() == (2, 1)
    sess.refresh_weights()
    assert not np.array_equal(_session_outputs(sess, x), first)


# ---------------------------------------------------------------------------
# (c) the compiled program aliases what it was given, and is built once
# ---------------------------------------------------------------------------
def test_donating_program_aliases_its_outputs_and_compiles_once():
    keys_before = set(ag._FUSED_STEP_CACHE)
    net, tr, step, _ = _loop()
    step()
    n_trained = len(_trained(net))
    n_stats = sum(1 for p in net.collect_params().values()
                  if p.grad_req == "null")
    assert n_stats == 2
    aliased = telemetry.gauge("mx_fused_step_outputs", kind="aliased").get()
    total = telemetry.gauge("mx_fused_step_outputs", kind="all").get()
    # weight, momentum and gradient of every trained parameter, and
    # the running statistics; the loss and the logits have no input
    # to take
    assert aliased >= 3 * n_trained + n_stats
    assert aliased <= total == 3 * n_trained + n_stats + 2

    compiles = len(compilewatch.programs())
    for _ in range(6):
        step()
    assert len(compilewatch.programs()) == compiles
    assert _counts() == (7, 0)
    added = set(ag._FUSED_STEP_CACHE) - keys_before
    assert len(added) == 1 and next(iter(added))[2] is True

    held = _weight(net, tr).detach()
    step()                              # the second variant, once
    del held
    step()
    added = set(ag._FUSED_STEP_CACHE) - keys_before
    assert sorted(k[2] for k in added) == [False, True]
    compiles = len(compilewatch.programs())
    for _ in range(3):
        held = _weight(net, tr).detach()
        step()
        del held
        step()
    assert len(compilewatch.programs()) == compiles
    assert set(ag._FUSED_STEP_CACHE) - keys_before == added


# ---------------------------------------------------------------------------
# (d) what the loop only reads is never donated
# ---------------------------------------------------------------------------
def test_resident_inputs_step_ten_times():
    net, tr, step, (x, y) = _loop()
    xv, yv = x._jax(), y._jax()
    x0, y0 = x.asnumpy().copy(), y.asnumpy().copy()
    for _ in range(10):
        step()
    assert _counts() == (10, 0)
    assert x._jax() is xv and y._jax() is yv
    assert not xv.is_deleted() and not yv.is_deleted()
    np.testing.assert_array_equal(x.asnumpy(), x0)
    np.testing.assert_array_equal(y.asnumpy(), y0)


def test_step_log_closes_the_count_per_step():
    net, tr, step, _ = _loop()
    step()
    held = _weight(net, tr).detach()
    step()
    del held
    log = telemetry.step_log()
    assert [r["fused_steps"] for r in log[-3:]] == \
        [{}, {"1": 1.0}, {"0": 1.0}]


# ---------------------------------------------------------------------------
# readers on other threads: the step's gate
# ---------------------------------------------------------------------------
def test_reader_threads_never_meet_a_donated_buffer():
    """Handles read from other threads while the loop steps (a serving
    scheduler on the live parameters, a logging thread): between a
    donating launch and its write-back they wait at the step's gate
    and then read the new value; a value read just before the launch
    is a reference the step sees, so that step donates nothing. (No
    BatchNorm here: a running statistic read from another thread
    between forward and backward forces its deferred node there, and
    ``_Node.force`` is not safe against the stepping thread, on this
    tree as on its parent.)"""
    import threading
    net, tr, step, (x, _y) = _loop(batch_norm=False)
    step()
    handles = [_weight(net, tr), _gradient(net, tr), _momentum(net, tr)]
    sess = net.serve_session(x)
    stop, errors, reads = threading.Event(), [], [0]

    def reader(k):
        try:
            while not stop.is_set():
                h = handles[k % len(handles)]
                v = h.asnumpy()
                assert np.isfinite(v).all()
                if k == 0:
                    _session_outputs(sess, x)
                reads[0] += 1
        except Exception as e:        # noqa: BLE001 - reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=reader, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(60):
            step()
    finally:
        stop.set()
        for t in threads:
            t.join(60)
    assert not errors, errors[:3]
    assert reads[0] > 0
    donated, plain = _counts()
    assert donated + plain == 61 and donated > 0
