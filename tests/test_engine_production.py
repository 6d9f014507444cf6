"""Native dependency engine on PRODUCTION paths (VERDICT r4 task #3):
custom-op execution, async checkpoint writes, and the native-IO device
hand-off all flow through native/engine.cc from public API calls — not
just direct engine tests (ref: SURVEY §1 L2 "every mutation in the
system flows through it")."""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
import mxnet_tpu.operator as op_mod


class _SlowSquare(op_mod.CustomOp):
    def __init__(self, delay):
        self._delay = delay

    def forward(self, is_train, req, in_data, out_data, aux):
        time.sleep(self._delay)
        self.assign(out_data[0], req[0], in_data[0] * in_data[0])

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], out_grad[0] * 2 * in_data[0])


@op_mod.register("slow_square")
class _SlowSquareProp(op_mod.CustomOpProp):
    def __init__(self, delay="0.3"):
        super().__init__(need_top_grad=True)
        self._delay = float(delay)

    def create_operator(self, ctx, shapes, dtypes):
        return _SlowSquare(self._delay)


class _Exploding(op_mod.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        raise RuntimeError("boom in custom forward")

    def backward(self, *a, **kw):
        pass


@op_mod.register("exploding_op")
class _ExplodingProp(op_mod.CustomOpProp):
    def create_operator(self, ctx, shapes, dtypes):
        return _Exploding()


def test_custom_op_overlaps_main_thread():
    """nd.Custom returns immediately; the Python callback runs on an
    engine worker (MXNET_CUSTOM_OP_NUM_THREADS analogue) and the value
    materializes at wait_to_read."""
    x = nd.array(np.array([1.0, 2.0, 3.0], np.float32))
    # warm the output-alloc compile cache so the timed window measures
    # dispatch, not the first `zeros` XLA compile (solo-run flake)
    nd.Custom(x, op_type="slow_square", delay="0.0").wait_to_read()
    t0 = time.perf_counter()
    y = nd.Custom(x, op_type="slow_square", delay="0.4")
    dispatch_time = time.perf_counter() - t0
    # dispatch must NOT wait the 0.4s callback
    assert dispatch_time < 0.2, dispatch_time
    # main thread can do other work here; then the wait blocks
    t1 = time.perf_counter()
    got = y.asnumpy()
    waited = time.perf_counter() - t1
    np.testing.assert_allclose(got, [1.0, 4.0, 9.0], rtol=1e-6)
    assert dispatch_time + waited >= 0.3   # the work really happened async


def test_custom_op_error_at_wait():
    """An exception in the callback poisons the output's engine var and
    re-raises at wait_to_read — not at dispatch."""
    x = nd.ones((3,))
    y = nd.Custom(x, op_type="exploding_op")   # must NOT raise here
    with pytest.raises(Exception, match="boom in custom forward"):
        y.wait_to_read()


def test_custom_op_chain_dependencies():
    """A custom op consuming another custom op's gated output declares
    a read dependency — engine ordering keeps the chain correct."""
    x = nd.array(np.array([2.0], np.float32))
    y = nd.Custom(x, op_type="slow_square", delay="0.2")
    z = nd.Custom(y, op_type="slow_square", delay="0.0")
    np.testing.assert_allclose(z.asnumpy(), [16.0], rtol=1e-6)


def test_custom_op_still_differentiates():
    from mxnet_tpu import autograd
    x = nd.array(np.array([1.0, 2.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.Custom(x, op_type="slow_square", delay="0.0")
        loss = y.sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), [2.0, 4.0], rtol=1e-5)


def test_async_checkpoint_overlap_and_roundtrip(tmp_path):
    """model.save_checkpoint returns before the file lands; the write
    happens on an engine worker; load_params orders after it."""
    from mxnet_tpu import model
    prefix = str(tmp_path / "ck")
    args = {"w%d" % i: nd.array(np.full((256, 256), i, np.float32))
            for i in range(8)}
    t0 = time.perf_counter()
    model.save_checkpoint(prefix, 3, None, args, {})
    dispatch = time.perf_counter() - t0
    a2, _ = model.load_params(prefix, 3)     # waits for the write
    assert set(a2) == set(args)
    np.testing.assert_allclose(a2["w5"].asnumpy()[0, :3], 5.0)
    # snapshot semantics: post-save mutation must not leak into file
    args["w0"][:] = 99.0
    model.save_checkpoint(prefix, 4, None, {"w0": nd.array(
        np.zeros((2, 2), np.float32))}, {}, sync=True)
    assert dispatch < 5.0  # sanity: dispatch is not unboundedly slow


def test_async_checkpoint_error_at_wait(tmp_path):
    """A write failure (nonexistent directory) surfaces at the next
    checkpoint wait, not at dispatch."""
    from mxnet_tpu import model
    bad_prefix = str(tmp_path / "no" / "such" / "dir" / "ck")
    args = {"w": nd.ones((2, 2))}
    model.save_checkpoint(bad_prefix, 0, None, args, {})   # returns OK
    with pytest.raises(Exception):
        model.wait_checkpoints()
    # the error is delivered once; checkpointing keeps working after
    good = str(tmp_path / "ok")
    model.save_checkpoint(good, 0, None, args, {}, sync=True)
    a2, _ = model.load_params(good, 0)
    assert "w" in a2


def test_native_io_handoff_ungated(tmp_path):
    """ImageRecordIter batches are produced by an engine op
    (`io_batch_upload`) and handed over once it has completed; values
    are correct (production API: the BASELINE ResNet input
    pipeline)."""
    from mxnet_tpu import recordio
    from mxnet_tpu.io import ImageRecordIter
    rec = str(tmp_path / "d.rec")
    idx = str(tmp_path / "d.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.RandomState(0)
    imgs = []
    for i in range(8):
        raw = rng.randint(0, 255, (8, 8, 3)).astype(np.uint8)
        imgs.append(raw)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), raw.tobytes()))
    w.close()
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 8), batch_size=4,
                         shuffle=False)
    batch = it.next()
    d = batch.data[0]
    # handed over: no gate to force
    assert d._pending is None
    assert d.shape == (4, 3, 8, 8)
    vals = d.asnumpy()
    labels = batch.label[0].asnumpy()
    np.testing.assert_allclose(labels, [0, 1, 2, 3])
    np.testing.assert_allclose(vals[1], imgs[1].transpose(2, 0, 1),
                               rtol=1e-4)


def test_custom_op_input_snapshot():
    """Regression: mutating an input after nd.Custom returns must not
    change what the deferred callback computes."""
    x = nd.array(np.array([2.0], np.float32))
    y = nd.Custom(x, op_type="slow_square", delay="0.25")
    x[:] = 100.0
    np.testing.assert_allclose(y.asnumpy(), [4.0], rtol=1e-6)


def test_custom_op_may_read_own_output():
    """Reference CustomOp.forward may read out_data (pre-filled zeros)
    without deadlocking on its own engine var."""
    class ReadOut(op_mod.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            base = out_data[0].asnumpy()      # reads own gated output
            self.assign(out_data[0], req[0],
                        nd.array(base + in_data[0].asnumpy()))

        def backward(self, *a, **kw):
            pass

    @op_mod.register("readout_op")
    class ReadOutProp(op_mod.CustomOpProp):
        def create_operator(self, ctx, shapes, dtypes):
            return ReadOut()

    x = nd.array(np.array([5.0], np.float32))
    y = nd.Custom(x, op_type="readout_op")
    np.testing.assert_allclose(y.asnumpy(), [5.0], rtol=1e-6)


def test_waitall_covers_native_engine(tmp_path):
    """mx.nd.waitall() is a barrier over checkpoint writes too."""
    from mxnet_tpu import model
    prefix = str(tmp_path / "wa")
    model.save_checkpoint(prefix, 0, None, {"w": nd.ones((64, 64))}, {})
    nd.waitall()
    assert os.path.exists(prefix + "-0000.params")


def test_custom_op_gated_input_mutation_ordering():
    """ADVICE r4: an engine-gated input kept live by a deferred custom
    op must feed the op its record-time value even when the main thread
    mutates it in place right after nd.Custom returns — the mutation is
    a write-after-read that waits for the pinned reader (the reference
    engine's write-dep rule), instead of racing the worker."""
    x = nd.array(np.array([3.0], np.float32))
    # y is engine-gated for 0.4s; z records y's (future) value
    y = nd.Custom(x, op_type="slow_square", delay="0.4")
    z = nd.Custom(y, op_type="slow_square", delay="0.0")
    # mutate the gated intermediate IMMEDIATELY — before the worker
    # chain can possibly have run z's forward
    y += 100.0
    np.testing.assert_allclose(z.asnumpy(), [81.0], rtol=1e-6)
    np.testing.assert_allclose(y.asnumpy(), [109.0], rtol=1e-6)


def test_async_checkpoint_error_surfaces_at_exit(tmp_path):
    """ADVICE r4: a failed async checkpoint whose wait point never runs
    must still surface at interpreter exit via the registered atexit
    drain (no more silent exit-0 with a missing checkpoint)."""
    import subprocess
    import sys

    code = """
import numpy as np
from mxnet_tpu import nd, model
model.save_checkpoint("%s/nonexistent-dir/ck", 0, None,
                      {"w": nd.array(np.ones((2,), np.float32))}, {})
# exit WITHOUT waiting: the atexit drain must raise the write error
""" % "${TMP}"
    code = code.replace("${TMP}", str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    blob = r.stdout + r.stderr
    assert "nonexistent-dir" in blob or "No such file" in blob or \
        r.returncode != 0, \
        "checkpoint write failure vanished at exit: rc=%d out=%r" % (
            r.returncode, blob[-500:])


def test_native_io_batch_feeds_a_recorded_step_unwaited(tmp_path):
    """A batch from ImageRecordIter.next() is handed over, not gated:
    with no wait_to_read() it goes through autograd.record() -> a
    hybridized block -> backward() -> Trainer.step, and the step matches
    the same step on nd.array of the same pixels (on the parent the
    fused backward met `'EngineGate' object has no attribute
    'out_values'`)."""
    from mxnet_tpu import autograd, gluon, recordio
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.io import ImageRecordIter
    rec = str(tmp_path / "s.rec")
    idx = str(tmp_path / "s.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.RandomState(3)
    imgs = rng.randint(0, 255, (8, 8, 8, 3)).astype(np.uint8)
    for i in range(8):
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 3), i, 0), imgs[i].tobytes()))
    w.close()
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 8), batch_size=4,
                         std_r=255.0, std_g=255.0, std_b=255.0)

    def build():
        net = nn.HybridSequential()
        net.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
                nn.Activation("relu"), nn.Flatten(), nn.Dense(3))
        net.initialize(mx.initializer.Xavier(rnd_type="gaussian"))
        net.hybridize(static_alloc=True, static_shape=True)
        return net, gluon.Trainer(net.collect_params(), "sgd",
                                  {"learning_rate": 0.1, "momentum": 0.9},
                                  kvstore="device")

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    fed, fed_tr = build()
    ref, ref_tr = build()
    fed(nd.zeros((4, 3, 8, 8)))             # shapes known: copy the weights
    ref(nd.zeros((4, 3, 8, 8)))
    for a, b in zip(fed.collect_params().values(),
                    ref.collect_params().values()):
        b.set_data(a.data())
    losses = []
    for k in range(2):                      # the cold batch, then a look-ahead
        batch = it.next()
        x, y = batch.data[0], batch.label[0]
        assert x._pending is None and y._pending is None
        rx = nd.array(imgs[4 * k:4 * k + 4].astype(np.float32)
                      .transpose(0, 3, 1, 2) / 255.0)
        ry = nd.array(np.arange(4 * k, 4 * k + 4) % 3, dtype="float32")
        pair = []
        for net, tr, data, label in ((fed, fed_tr, x, y),
                                     (ref, ref_tr, rx, ry)):
            with autograd.record():
                loss = loss_fn(net(data), label)
            loss.backward()
            tr.step(4)
            pair.append(loss.asnumpy())
        np.testing.assert_allclose(pair[0], pair[1], rtol=1e-5, atol=1e-6)
        losses.append(pair[0])
    assert np.isfinite(losses).all()
    for a, b in zip(fed.collect_params().values(),
                    ref.collect_params().values()):
        np.testing.assert_allclose(a.data().asnumpy(), b.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)
