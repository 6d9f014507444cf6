"""The step spans and the per-step log (docs/OBSERVABILITY.md "Step
spans"): ``telemetry.span`` as the one primitive (parent from the
thread's open spans, ``jax.profiler.TraceAnnotation`` on enter, the
bounded step log on exit), ``mark_step`` closing a step,
``telemetry.step_log``, and the spans and the launch counter where the
work happens in the Gluon and sharded training paths."""
import glob
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, profiler, telemetry
from mxnet_tpu.gluon import nn

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.delenv("MXNET_TELEMETRY_HEARTBEAT", raising=False)
    telemetry.refresh()
    telemetry.reset()
    profiler.set_state("stop")
    yield
    profiler.set_state("stop")
    telemetry.refresh()
    telemetry.reset()


def _names(record):
    return [e[0] for e in record["events"]]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------
def test_span_nests_and_records_its_parent():
    with telemetry.span("outer"):
        with telemetry.span("mid"):
            with telemetry.span("leaf"):
                pass
        with telemetry.span("mid2"):
            pass
    telemetry.mark_step()
    (rec,) = telemetry.step_log()
    parents = {e[0]: e[3] for e in rec["events"]}
    assert parents == {"leaf": "mid", "mid": "outer", "mid2": "outer",
                       "outer": None}
    # in order of exit, each (name, start, end, parent, step)
    assert _names(rec) == ["leaf", "mid", "mid2", "outer"]
    assert all(e[2] >= e[1] and e[4] == 0 for e in rec["events"])


def test_parent_is_per_thread():
    import threading
    seen = []

    def worker():
        with telemetry.span("other-thread"):
            pass
        seen.append(True)

    with telemetry.span("main-thread"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    telemetry.mark_step()
    (rec,) = telemetry.step_log()
    assert seen and {e[0]: e[3] for e in rec["events"]} == {
        "other-thread": None, "main-thread": None}


def test_self_time_is_duration_minus_children(monkeypatch):
    clock = iter([0.0,          # parent enters
                  1.0, 3.0,     # child a: 2 s
                  4.0, 4.5,     # child b: 0.5 s
                  4.6, 4.7,     # grandchild's parent c enters, g enters
                  4.8, 5.0,     # g exits (0.1 s), c exits (0.4 s)
                  10.0])        # parent exits: 10 s

    class _Time:
        perf_counter = staticmethod(lambda: next(clock))

    monkeypatch.setattr(telemetry, "time", _Time)
    with telemetry.span("p"):
        with telemetry.span("a"):
            pass
        with telemetry.span("a"):
            pass
        with telemetry.span("c"):
            with telemetry.span("g"):
                pass
    monkeypatch.undo()
    telemetry.enable(True)
    telemetry.mark_step()
    spans = telemetry.step_log(1)[0]["spans"]
    assert spans["p"]["seconds"] == 10.0
    assert spans["a"] == {"count": 2, "seconds": 2.5, "self_seconds": 2.5}
    assert spans["c"]["seconds"] == pytest.approx(0.4)
    assert spans["c"]["self_seconds"] == pytest.approx(0.3)
    # the grandchild is c's, not p's: 10 - (2 + 0.5 + 0.4)
    assert spans["p"]["self_seconds"] == pytest.approx(7.1)


def test_mark_step_closes_a_step_and_the_ring_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(telemetry._StepLog, "STEP_LOG_STEPS", 4)
    telemetry.reset()                   # a ring of the patched size
    for i in range(7):
        with telemetry.span("work%d" % i):
            pass
        telemetry.count_launch("gluon")
        if i % 2:
            telemetry.count_launch("sharded")
        telemetry.mark_step()
    log = telemetry.step_log()
    assert [r["step"] for r in log] == [3, 4, 5, 6]
    assert [_names(r) for r in log] == [["work3"], ["work4"], ["work5"],
                                        ["work6"]]
    assert [r["launches"] for r in log] == [
        {"gluon": 1.0, "sharded": 1.0}, {"gluon": 1.0},
        {"gluon": 1.0, "sharded": 1.0}, {"gluon": 1.0}]
    assert [r["step"] for r in telemetry.step_log(2)] == [5, 6]
    assert telemetry.step_log(0) == []
    # the spans carry the step they ran in
    assert [r["events"][0][4] for r in log] == [3, 4, 5, 6]
    # a span after the last mark waits in the open step
    with telemetry.span("late"):
        pass
    assert [r["step"] for r in telemetry.step_log()] == [3, 4, 5, 6]
    snap = telemetry.snapshot()["counters"]
    assert snap['mx_program_launches_total{path="gluon"}'] == 7
    assert snap['mx_program_launches_total{path="sharded"}'] == 3


def test_a_step_that_never_closes_stops_at_its_cap(monkeypatch):
    monkeypatch.setattr(telemetry._StepLog, "OPEN_SPAN_CAP", 5)
    for _ in range(9):
        with telemetry.span("serving"):
            pass
    assert len(telemetry._STEPLOG.open) == 5
    telemetry.mark_step()
    (rec,) = telemetry.step_log()
    assert rec["spans"]["serving"]["count"] == 5 and rec["dropped"] == 4
    telemetry.mark_step()
    assert telemetry.step_log(1)[0]["dropped"] == 0


@pytest.mark.parametrize("how", ["same_name_folds", "cancel_drops"])
def test_spans_that_leave_no_record(how):
    if how == "same_name_folds":
        with telemetry.phase("forward"):
            with telemetry.phase("forward"):
                with telemetry.phase("forward"):
                    pass
        want = ["step::forward"]
    else:
        with telemetry.phase("data") as sp:
            sp.cancel()
        with telemetry.phase("forward"):
            pass
        want = ["step::forward"]
    assert telemetry._OPEN_SPANS.names == []
    telemetry.mark_step()
    assert _names(telemetry.step_log(1)[0]) == want
    hist = telemetry.snapshot()["histograms"]
    assert hist['mx_step_phase_seconds{phase="forward"}']["count"] == 1
    assert 'mx_step_phase_seconds{phase="data"}' not in hist


def test_gate_off_span_reads_the_gate_and_nothing_else(monkeypatch):
    """With telemetry and the profiler off a span takes no clock, enters
    no annotation and leaves no record: all it does is read the gate."""
    telemetry.enable(False)
    touched = []        # a span swallows what its instruments raise

    class _Annotation:
        def __init__(self, *a, **k):
            touched.append("annotation")

    class _Clock:
        @staticmethod
        def perf_counter():
            touched.append("clock")
            return 0.0

    monkeypatch.setattr(telemetry, "TraceAnnotation", _Annotation)
    monkeypatch.setattr(telemetry, "time", _Clock)
    for _ in range(3):
        with telemetry.phase("forward") as sp:
            with telemetry.span("inner", hist="h"):
                pass
        assert sp._live is False and sp._ann is None
    telemetry.count_launch("gluon")
    telemetry.mark_step()
    assert touched == []
    assert telemetry._STEPLOG.open == [] and telemetry.step_log() == []
    assert telemetry._OPEN_SPANS.names == []
    assert telemetry.snapshot()["counters"] == {}


def test_profiler_alone_makes_a_span_live(tmp_path):
    telemetry.enable(False)
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    with telemetry.phase("forward"):
        pass
    profiler.set_state("stop")
    assert [e[0] for e in telemetry._STEPLOG.open] == ["step::forward"]
    assert telemetry.snapshot()["histograms"] == {}


def test_a_failing_annotation_never_poisons_the_region(monkeypatch):
    class _Boom:
        def __init__(self, *a, **k):
            raise RuntimeError("no profiler here")

    monkeypatch.setattr(telemetry, "TraceAnnotation", _Boom)
    with telemetry.span("outer"):
        ran = True
    assert ran and telemetry._OPEN_SPANS.names == []
    assert telemetry._STEPLOG.open == []


def test_spans_land_in_a_jax_profiler_trace(tmp_path):
    """A live span is a TraceAnnotation: in a jax.profiler trace it sits
    on the host plane under its own name, nested as in the program."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.phase("update"):
            with telemetry.phase("update.launch"):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("step::"):
                        found[ev.name] = (ev.start_ns,
                                          ev.start_ns + ev.duration_ns)
    assert set(found) == {"step::update", "step::update.launch"}
    outer, inner = found["step::update"], found["step::update.launch"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


# ---------------------------------------------------------------------------
# the Gluon loop
# ---------------------------------------------------------------------------
def _gluon_loop(monkeypatch, fused, hybrid_loss, prefix):
    monkeypatch.setenv("MXNET_TRAINER_FUSED_UPDATE", "1" if fused else "0")
    mx.random.seed(0)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize(static_alloc=True, static_shape=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    if hybrid_loss:
        loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 12).astype(np.float32))
    y = nd.array(rng.randint(0, 4, (8,)).astype(np.float32))

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)
        return loss

    return step


def _launch_total():
    return telemetry.snapshot()["counters"].get(
        'mx_program_launches_total{path="gluon"}', 0.0)


def test_fused_gluon_loop_one_span_of_each_per_step(monkeypatch):
    step = _gluon_loop(monkeypatch, True, True, "sf_")
    step()                  # classic: arms the fused update
    step()                  # fused: compiles the step program
    telemetry.reset()
    before = _launch_total()
    for _ in range(2):
        step()
    log = telemetry.step_log()
    assert [r["step"] for r in log] == [0, 1]
    for rec in log:
        spans = rec["spans"]
        # one forward span for each hybridized block the step calls,
        # summed into one row
        assert spans["step::forward"]["count"] == 2     # net, loss block
        assert spans["step::backward"]["count"] == 1
        assert spans["step::update"]["count"] == 1
        parents = {e[0]: e[3] for e in rec["events"]}
        for child in ("prep", "launch", "writeback"):
            assert spans["step::update." + child]["count"] == 1
            assert parents["step::update." + child] == "step::update"
        for child in ("lookup", "call"):
            assert spans["step::update.launch." + child]["count"] == 1
            assert parents["step::update.launch." + child] \
                == "step::update.launch"
        assert parents["step::forward"] is None
        assert parents["step::backward"] is None
        assert parents["step::update"] is None
        kids = sum(spans["step::update." + c]["seconds"]
                   for c in ("prep", "launch", "writeback"))
        assert spans["step::update"]["self_seconds"] == pytest.approx(
            spans["step::update"]["seconds"] - kids)
        assert 0 <= spans["step::update"]["self_seconds"]
        # forward and backward only record: the one program of the step
        # is launched inside step::update.launch
        assert rec["launches"] == {"gluon": 1.0}
        assert "step::optimizer" not in spans
    assert _launch_total() - before == 2
    hist = telemetry.snapshot()["histograms"]
    # the fused path files step::update under its documented label
    assert hist['mx_step_phase_seconds{phase="fused_step"}']["count"] == 2
    assert 'mx_step_phase_seconds{phase="update"}' not in hist
    assert hist['mx_step_phase_seconds{phase="update.launch"}'][
        "count"] == 2
    assert telemetry.snapshot()["steps"] == 2


@pytest.mark.parametrize("why, fused, hybrid_loss, forwards, launches", [
    # the flag is off: the fused backward is the step's one program
    ("flag_off", False, True, 2, 1.0),
    # an eager loss keeps the tape out of the fused paths: the net's
    # forward-with-residuals and its backward are a program each
    ("eager_loss", True, False, 1, 2.0),
])
def test_classic_gluon_loop_spans_and_launches(monkeypatch, why, fused,
                                               hybrid_loss, forwards,
                                               launches):
    step = _gluon_loop(monkeypatch, fused, hybrid_loss, "sc_%s_" % why)
    step()
    telemetry.reset()
    before = _launch_total()
    for _ in range(2):
        step()
    log = telemetry.step_log()
    assert len(log) == 2
    for rec in log:
        spans = rec["spans"]
        assert spans["step::forward"]["count"] == forwards
        assert spans["step::backward"]["count"] == 1
        assert spans["step::update"]["count"] == 1
        parents = {e[0]: e[3] for e in rec["events"]}
        assert parents["step::allreduce"] == "step::update"
        assert parents["step::optimizer"] == "step::update"
        assert "step::update.launch" not in spans
        assert rec["launches"] == {"gluon": launches}
    assert _launch_total() - before == sum(
        r["launches"]["gluon"] for r in log)
    hist = telemetry.snapshot()["histograms"]
    assert hist['mx_step_phase_seconds{phase="update"}']["count"] == 2
    assert 'mx_step_phase_seconds{phase="fused_step"}' not in hist


def test_forward_outside_record_has_no_span(monkeypatch):
    net = nn.Dense(4, in_units=3)
    net.initialize()
    net.hybridize()
    net(nd.ones((2, 3)))
    telemetry.reset()
    net(nd.ones((2, 3)))
    assert telemetry._STEPLOG.open == []
    assert _launch_total() == 1         # an inference launch still counts


def test_estimator_with_a_hybridized_net_counts_one_forward_per_batch():
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    X = np.random.rand(8, 3).astype(np.float32)
    Y = (X @ np.ones((3, 1), np.float32)).astype(np.float32)
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(X, Y),
                                   batch_size=4)
    net = nn.Dense(1, in_units=3)
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01}, kvstore=None)
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[mx.metric.MSE()], trainer=trainer)
    est.fit(loader, epochs=2)
    hist = telemetry.snapshot()["histograms"]
    for ph in ("data", "forward", "backward"):
        assert hist['mx_step_phase_seconds{phase="%s"}' % ph]["count"] \
            == 4, ph
    log = telemetry.step_log()
    assert len(log) == 4
    for rec in log:
        assert rec["spans"]["step::forward"]["count"] == 1
        assert rec["spans"]["step::backward"]["count"] == 1
        assert rec["spans"]["step::update"]["count"] == 1


# ---------------------------------------------------------------------------
# the sharded step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("grad_accum", [1, 2])
def test_sharded_step_span_with_its_two_children(grad_accum):
    from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep, make_mesh
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=6), nn.Dense(2,
                                                                 in_units=8))
    net.initialize()
    mesh = make_mesh(MeshConfig(dp=2))
    step = ShardedTrainStep(net, gluon.loss.L2Loss(), mesh, optimizer="sgd",
                            lr=0.1, momentum=0.0, grad_accum=grad_accum)
    x = nd.array(np.random.rand(4, 6).astype(np.float32))
    y = nd.array(np.random.rand(4, 2).astype(np.float32))
    for _ in range(grad_accum):
        step.step(x, y)                 # compiles
    telemetry.reset()
    for _ in range(2 * grad_accum):
        step.step(x, y)
    log = telemetry.step_log()
    assert len(log) == 2                # a micro-step marks no step
    for rec in log:
        spans = rec["spans"]
        parents = {e[0]: e[3] for e in rec["events"]}
        assert spans["step::sharded"]["count"] == grad_accum
        assert parents["step::sharded"] is None
        for child in ("place", "launch"):
            assert spans["step::sharded." + child]["count"] == grad_accum
            assert parents["step::sharded." + child] == "step::sharded"
        assert rec["launches"] == {"sharded": float(grad_accum)}
    hist = telemetry.snapshot()["histograms"]
    assert hist['mx_step_phase_seconds{phase="sharded.launch"}'][
        "count"] == 2 * grad_accum
