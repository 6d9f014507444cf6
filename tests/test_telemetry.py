"""Observability-layer tests (docs/OBSERVABILITY.md): metrics registry
schema, Prometheus exposition, span tracing into the chrome-trace
profiler, engine/kvstore/step wiring, heartbeat, and the profiler /
monitor satellite fixes. All tier-1 (`obs` marker, not `slow`)."""
import json
import logging
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject, guardrails, profiler, telemetry

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    """Each test starts with telemetry ON, an empty registry, a clean
    profiler buffer and no armed faults."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.delenv("MXNET_TELEMETRY_HEARTBEAT", raising=False)
    telemetry.refresh()
    telemetry.reset()
    faultinject.reset()
    profiler.set_state("stop")
    profiler.dumps(reset=True)
    yield
    faultinject.reset()
    profiler.set_state("stop")
    profiler.dumps(reset=True)
    telemetry.refresh()
    telemetry.reset()


def _trace_events(tmp_path, reset=True):
    path = str(tmp_path / "trace.json")
    profiler.set_config(filename=path)
    profiler.dump(reset=reset)
    with open(path) as f:
        return json.load(f)["traceEvents"]


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------
def test_disabled_gate(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.refresh()
    assert not telemetry.enabled()
    telemetry.guard_event("skip")        # all hooks no-op when off
    telemetry.fault_event("nan_grad")
    telemetry.mark_step()
    assert telemetry.snapshot()["counters"] == {}
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    assert not telemetry.enabled(), "gate must be CACHED, not live"
    telemetry.refresh()
    assert telemetry.enabled()


def test_counter_gauge_histogram():
    telemetry.counter("c_total").inc()
    telemetry.counter("c_total").inc(2.5)
    assert telemetry.counter("c_total").get() == 3.5
    g = telemetry.gauge("g")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.get() == 5.0
    h = telemetry.histogram("h")
    for v in (0.001, 0.01, 0.01, 0.1):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    np.testing.assert_allclose(s["sum"], 0.121)
    assert s["min"] == 0.001 and s["max"] == 0.1
    # log-bucket percentile estimate: within one bucket (10^.25) of true
    assert 0.005 <= s["p50"] <= 0.02
    assert s["p99"] <= 0.1


def test_labels_make_distinct_series():
    telemetry.counter("ops", label="a").inc()
    telemetry.counter("ops", label="b").inc(2)
    snap = telemetry.snapshot()
    assert snap["counters"]['ops{label="a"}'] == 1
    assert snap["counters"]['ops{label="b"}'] == 2
    with pytest.raises(TypeError):
        telemetry.gauge("ops", label="a")   # kind mismatch caught


def test_counter_thread_safety():
    c = telemetry.counter("threaded_total")
    h = telemetry.histogram("threaded_hist")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert c.get() == 8000
    assert h.summary()["count"] == 8000


def test_snapshot_schema():
    telemetry.counter("a_total").inc()
    telemetry.gauge("b").set(1)
    telemetry.histogram("c_seconds").observe(0.5)
    snap = telemetry.snapshot()
    assert set(snap) == {"enabled", "steps", "counters", "gauges",
                         "histograms", "jit_cache"}
    assert snap["enabled"] is True
    assert isinstance(snap["jit_cache"], dict)   # ISSUE 4 cache sizes
    assert isinstance(snap["steps"], int)
    assert snap["counters"]["a_total"] == 1.0
    assert snap["gauges"]["b"] == 1.0
    hist = snap["histograms"]["c_seconds"]
    assert set(hist) == {"count", "sum", "min", "max", "p50", "p90",
                         "p99"}


def test_prometheus_label_escaping():
    telemetry.counter("esc_total", key='we"ird\\key\nx').inc()
    text = telemetry.render_prometheus()
    assert 'esc_total{key="we\\"ird\\\\key\\nx"} 1' in text
    assert "\nx" not in text.split("esc_total", 1)[1].split("\n", 1)[0]


def test_render_prometheus_exposition():
    telemetry.counter("mx_things_total", kind="x").inc(3)
    telemetry.gauge("mx_level").set(2)
    h = telemetry.histogram("mx_lat_seconds")
    h.observe(0.001)
    h.observe(10.0)
    text = telemetry.render_prometheus()
    lines = text.strip().split("\n")
    assert "# TYPE mx_things_total counter" in lines
    assert 'mx_things_total{kind="x"} 3' in lines
    assert "# TYPE mx_level gauge" in lines
    assert "mx_level 2" in lines
    assert "# TYPE mx_lat_seconds histogram" in lines
    assert 'mx_lat_seconds_bucket{le="+Inf"} 2' in lines
    assert "mx_lat_seconds_count 2" in lines
    # buckets are cumulative and non-decreasing
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines
              if l.startswith("mx_lat_seconds_bucket")]
    assert counts == sorted(counts) and counts[-1] == 2
    np.testing.assert_allclose(
        float([l for l in lines
               if l.startswith("mx_lat_seconds_sum")][0].rsplit(" ", 1)[1]),
        10.001)


# ---------------------------------------------------------------------------
# spans -> chrome trace + histograms
# ---------------------------------------------------------------------------
def test_span_feeds_profiler_and_histogram(tmp_path):
    profiler.set_state("run")
    with telemetry.span("region", "user", hist="region_seconds",
                        tag="t1"):
        time.sleep(0.002)
    profiler.set_state("stop")
    events = _trace_events(tmp_path)
    ev = [e for e in events if e["name"] == "region"]
    assert len(ev) == 1 and ev[0]["ph"] == "X" and ev[0]["cat"] == "user"
    assert ev[0]["dur"] >= 1500
    s = telemetry.snapshot()["histograms"]['region_seconds{tag="t1"}']
    assert s["count"] == 1 and s["min"] >= 0.0015


def test_span_records_histogram_without_profiler():
    assert profiler.state() == "stop"
    with telemetry.span("quiet", "user", hist="quiet_seconds"):
        pass
    assert telemetry.snapshot()["histograms"]["quiet_seconds"]["count"] == 1
    assert profiler.dumps() == json.dumps({"traceEvents": []}, indent=1)


def test_phase_span_naming(tmp_path):
    profiler.set_state("run")
    with telemetry.phase("forward"):
        pass
    profiler.set_state("stop")
    events = _trace_events(tmp_path)
    assert any(e["name"] == "step::forward" and e["cat"] == "step"
               for e in events)
    snap = telemetry.snapshot()
    assert snap["histograms"]['mx_step_phase_seconds{phase="forward"}'][
        "count"] == 1


# ---------------------------------------------------------------------------
# engine wiring
# ---------------------------------------------------------------------------
def test_engine_op_spans_and_metrics(tmp_path):
    from mxnet_tpu.engine import NativeDependencyEngine
    profiler.set_state("run")
    e = NativeDependencyEngine(num_workers=2)
    try:
        v = e.new_var()
        for _ in range(3):
            e.push_async(lambda: None, write_vars=(v,), label="work_op")
        e.wait_for_all()
    finally:
        e.close()
    profiler.set_state("stop")
    events = _trace_events(tmp_path)
    runs = [ev for ev in events if ev["name"] == "engine::work_op"]
    queued = [ev for ev in events
              if ev["name"] == "engine::work_op (queued)"]
    assert len(runs) == 3 and len(queued) == 3
    assert all(ev["cat"] == "engine" for ev in runs + queued)
    assert all("site" in ev["args"] for ev in runs)
    snap = telemetry.snapshot()
    assert snap["counters"]['mx_engine_ops_total{label="work_op"}'] == 3
    assert snap["histograms"]['mx_engine_op_seconds{label="work_op"}'][
        "count"] == 3
    assert snap["histograms"]['mx_engine_queue_seconds{label="work_op"}'][
        "count"] == 3
    assert snap["gauges"]["mx_engine_pending_ops"] == 0


def test_engine_error_counter_and_label_sanitization():
    from mxnet_tpu.engine import NativeDependencyEngine

    def boom():
        raise ValueError("kaboom")

    e = NativeDependencyEngine(num_workers=1)
    try:
        v = e.new_var()
        e.push_async(boom, write_vars=(v,),
                     label="ckpt_write:file-0001.params")
        with pytest.raises(ValueError):
            e.wait_for_var(v)
    finally:
        e.close()
    snap = telemetry.snapshot()
    # instance detail after ':' folds into one bounded series
    assert snap["counters"][
        'mx_engine_op_errors_total{label="ckpt_write"}'] == 1
    assert snap["counters"]['mx_engine_ops_total{label="ckpt_write"}'] == 1
    # the engine_error guard event became a counter too
    assert snap["counters"]['mx_guard_events_total{kind="engine_error"}'] == 1


# ---------------------------------------------------------------------------
# guard / fault / checkpoint / kvstore-deadline event counters
# ---------------------------------------------------------------------------
def test_guard_events_become_counters():
    guardrails.emit("skip", step=1)
    guardrails.emit("skip", step=2)
    guardrails.emit("clip", step=2)
    snap = telemetry.snapshot()["counters"]
    assert snap['mx_guard_events_total{kind="skip"}'] == 2
    assert snap['mx_guard_events_total{kind="clip"}'] == 1


def test_fault_fires_become_counters():
    faultinject.set_fault("nan_grad", 1.0, max_fires=2)
    assert faultinject.should_fail("nan_grad")
    assert faultinject.should_fail("nan_grad")
    assert not faultinject.should_fail("nan_grad")    # budget spent
    snap = telemetry.snapshot()["counters"]
    assert snap['mx_fault_injections_total{site="nan_grad"}'] == 2


def test_checkpoint_write_counters(tmp_path):
    from mxnet_tpu import model as model_mod
    a = mx.nd.array(np.ones((4,), np.float32))
    prefix = str(tmp_path / "ck")
    model_mod.save_checkpoint(prefix, 1, None, {"w": a}, {}, sync=True)
    faultinject.set_fault("ckpt_write", 1.0, max_fires=1)
    with pytest.raises(mx.MXNetError):
        model_mod.save_checkpoint(prefix, 2, None, {"w": a}, {},
                                  sync=True)
    snap = telemetry.snapshot()
    assert snap["counters"]["mx_checkpoint_writes_total"] == 1
    assert snap["counters"]["mx_checkpoint_errors_total"] == 1
    assert snap["histograms"]["mx_checkpoint_write_seconds"]["count"] >= 1


def test_comm_deadline_counters():
    from mxnet_tpu.dist import call_with_deadline
    calls = [0]

    def slow_then_ok():
        calls[0] += 1
        if calls[0] == 1:
            time.sleep(0.4)
        return 42

    assert call_with_deadline(slow_then_ok, 0.1, "push(test)",
                              retries=1, backoff=0.5) == 42
    snap = telemetry.snapshot()["counters"]
    assert snap['mx_kvstore_retries_total{call="push(test)"}'] == 1

    with pytest.raises(mx.MXNetError):
        call_with_deadline(lambda: time.sleep(0.5) or 1, 0.05,
                           "pull(test)", retries=0)
    snap = telemetry.snapshot()["counters"]
    assert snap['mx_kvstore_deadline_hits_total{call="pull(test)"}'] == 1


# ---------------------------------------------------------------------------
# step loop wiring
# ---------------------------------------------------------------------------
def _tiny_trainer():
    from mxnet_tpu import gluon
    mx.random.seed(0)
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01}, kvstore=None)
    return net, trainer


def test_trainer_step_marks_steps_and_phases(tmp_path):
    from mxnet_tpu import autograd, gluon
    net, trainer = _tiny_trainer()
    loss_fn = gluon.loss.L2Loss()
    X = mx.nd.array(np.random.rand(4, 3).astype(np.float32))
    Y = mx.nd.array(np.random.rand(4, 2).astype(np.float32))
    profiler.set_state("run")
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net(X), Y)
        l.backward()
        trainer.step(4)
    profiler.set_state("stop")
    snap = telemetry.snapshot()
    assert snap["counters"]["mx_steps_total"] == 3
    assert snap["steps"] == 3
    # inter-step time: first step has no predecessor
    assert snap["histograms"]["mx_step_seconds"]["count"] == 2
    phases = [k for k in snap["histograms"]
              if k.startswith("mx_step_phase_seconds")]
    assert 'mx_step_phase_seconds{phase="optimizer"}' in phases
    assert 'mx_step_phase_seconds{phase="allreduce"}' in phases
    events = _trace_events(tmp_path)
    assert any(e["name"] == "step::optimizer" for e in events)


def test_guarded_skip_still_marks_step():
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.guardrails import GradGuard
    net, trainer = _tiny_trainer()
    trainer.grad_guard = GradGuard(nonfinite="skip_step")
    loss_fn = gluon.loss.L2Loss()
    X = mx.nd.array(np.random.rand(4, 3).astype(np.float32))
    Y = mx.nd.array(np.random.rand(4, 2).astype(np.float32))
    faultinject.set_fault("nan_grad", 1.0)
    with autograd.record():
        l = loss_fn(net(X), Y)
    l.backward()
    trainer.step(4)
    snap = telemetry.snapshot()
    assert snap["counters"]["mx_steps_total"] == 1
    assert snap["counters"]['mx_guard_events_total{kind="skip"}'] == 1
    assert snap["histograms"]['mx_step_phase_seconds{phase="guard"}'][
        "count"] == 1


def test_dataloader_batch_histogram():
    from mxnet_tpu import gluon
    X = np.random.rand(16, 3).astype(np.float32)
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(X),
                                   batch_size=4)
    assert len(list(loader)) == 4
    snap = telemetry.snapshot()
    assert snap["histograms"]["mx_dataloader_batch_seconds"]["count"] == 4


def test_dataloader_traces_with_telemetry_off(tmp_path, monkeypatch):
    """Profiler-only workflow (MXNET_TELEMETRY unset): data-pipeline
    events must still land in the chrome trace, like every other
    instrumented site."""
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.refresh()
    from mxnet_tpu import gluon
    X = np.random.rand(8, 3).astype(np.float32)
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(X),
                                   batch_size=4)
    it = mx.io.NDArrayIter(X, batch_size=4)
    profiler.set_state("run")
    assert len(list(loader)) == 2
    assert len(list(it)) == 2
    profiler.set_state("stop")
    events = _trace_events(tmp_path)
    names = [e["name"] for e in events]
    assert names.count("dataloader::next") == 2
    assert names.count("io::NDArrayIter.next") == 2
    assert telemetry.snapshot()["histograms"] == {}  # registry was off


def test_span_cancel_drops_record():
    with telemetry.span("probe", "user", hist="probe_seconds") as sp:
        sp.cancel()
    assert "probe_seconds" not in telemetry.snapshot()["histograms"]


def test_span_swallows_instrument_conflict():
    telemetry.gauge("conflicted")          # wrong kind, registered first
    with telemetry.span("r", "user", hist="conflicted"):
        pass                               # kind conflict must not raise


def test_estimator_data_phase_excludes_epoch_probe():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    X = np.random.rand(8, 3).astype(np.float32)
    Y = (X @ np.ones((3, 1), np.float32)).astype(np.float32)
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(X, Y),
                                   batch_size=4)
    net = gluon.nn.Dense(1, in_units=3)
    net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01}, kvstore=None)
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[mx.metric.MSE()], trainer=trainer)
    est.fit(loader, epochs=2)
    snap = telemetry.snapshot()["histograms"]
    # 2 epochs x 2 batches: exactly 4 data-phase samples, not 6
    assert snap['mx_step_phase_seconds{phase="data"}']["count"] == 4
    assert snap['mx_step_phase_seconds{phase="forward"}']["count"] == 4


def test_dataiter_histogram():
    X = np.random.rand(8, 3).astype(np.float32)
    it = mx.io.NDArrayIter(X, batch_size=4)
    assert len(list(it)) == 2
    snap = telemetry.snapshot()
    key = 'mx_dataiter_batch_seconds{iter="NDArrayIter"}'
    assert snap["histograms"][key]["count"] >= 2


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------
def test_heartbeat_line_registers_nothing_when_off(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.refresh()
    line = telemetry.heartbeat_line()
    assert line.startswith("mx-heartbeat steps=0")
    snap = telemetry.snapshot()
    assert snap["histograms"] == {} and snap["gauges"] == {}, \
        "on-demand heartbeat must not register phantom instruments"


def test_heartbeat_line_contents():
    telemetry.counter("mx_guard_events_total", kind="skip").inc(4)
    telemetry.gauge("mx_engine_pending_ops").set(2)
    for dt in (0.01, 0.02, 0.03):
        telemetry.histogram("mx_step_seconds").observe(dt)
    line = telemetry.heartbeat_line()
    assert line.startswith("mx-heartbeat ")
    for field in ("steps=", "rate=", "step_p50=", "step_p99=",
                  "pending_engine_ops=2", "guard_events=4",
                  "ckpt_errors="):
        assert field in line, (field, line)


def test_heartbeat_thread_emits(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_TELEMETRY_HEARTBEAT", "0.05")
    telemetry.refresh()
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        telemetry.enable(True)      # starts the heartbeat thread
        deadline = time.time() + 3.0
        while time.time() < deadline:
            if any(r.message.startswith("mx-heartbeat")
                   for r in caplog.records):
                break
            time.sleep(0.02)
    lines = [r.message for r in caplog.records
             if r.message.startswith("mx-heartbeat")]
    assert lines, "heartbeat thread never emitted"
    telemetry.refresh()             # stops the thread


# ---------------------------------------------------------------------------
# acceptance: chaos --nan-inject under full telemetry
# ---------------------------------------------------------------------------
def test_chaos_nan_inject_full_telemetry(tmp_path, monkeypatch, caplog):
    """ISSUE 3 acceptance: a tools/chaos_run.py --nan-inject run with
    MXNET_TELEMETRY=1 produces a chrome trace with engine op spans AND
    step-phase spans, a Prometheus rendering with the step-time
    histogram + guard-event counters, and >=1 heartbeat line."""
    import tools.chaos_run as chaos_run
    monkeypatch.setenv("MXNET_TELEMETRY_HEARTBEAT", "0.2")
    telemetry.refresh()
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.set_state("run")
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        assert chaos_run.main(["--nan-inject", "--rounds", "1",
                               "--epochs", "2"]) == 0
        # a heartbeat period elapses even if the round was fast
        deadline = time.time() + 3.0
        while time.time() < deadline and not any(
                r.message.startswith("mx-heartbeat")
                for r in caplog.records):
            time.sleep(0.05)
    profiler.set_state("stop")
    events = _trace_events(tmp_path)
    names = {e["name"] for e in events}
    assert any(n.startswith("engine::checkpoint_write") for n in names), \
        sorted(names)
    for ph in ("data", "forward", "backward", "guard", "optimizer"):
        assert "step::%s" % ph in names
    prom = telemetry.render_prometheus()
    assert "# TYPE mx_step_seconds histogram" in prom
    assert 'mx_step_seconds_bucket{le="+Inf"}' in prom
    assert 'mx_guard_events_total{kind="skip"}' in prom
    assert 'mx_fault_injections_total{site="nan_grad"}' in prom
    snap = telemetry.snapshot()
    assert snap["counters"]["mx_steps_total"] >= 8
    assert snap["counters"]["mx_checkpoint_writes_total"] >= 1
    assert any(r.message.startswith("mx-heartbeat")
               for r in caplog.records), "no heartbeat line"


# ---------------------------------------------------------------------------
# satellite: profiler.dump atomicity + reset
# ---------------------------------------------------------------------------
def test_profiler_dump_atomic_and_reset(tmp_path):
    import os
    path = str(tmp_path / "prof.json")
    profiler.set_config(filename=path)
    profiler.set_state("run")
    with profiler.scope("alpha"):
        pass
    profiler.set_state("stop")
    profiler.dump(reset=True)
    assert [e["name"] for e in json.load(open(path))["traceEvents"]] \
        == ["alpha"]
    assert not [f for f in os.listdir(str(tmp_path))
                if ".tmp." in f], "temp file leaked"
    # buffer was cleared: second dump is empty
    profiler.dump()
    assert json.load(open(path))["traceEvents"] == []
    # a failed dump must not destroy the published file OR the buffer
    profiler.set_state("run")
    with profiler.scope("beta"):
        pass
    profiler.set_state("stop")
    profiler.dump(reset=True)
    profiler.set_state("run")
    with profiler.scope("gamma"):
        pass
    profiler.set_state("stop")
    profiler.set_config(filename=str(tmp_path / "nodir" / "x.json"))
    with pytest.raises(OSError):
        profiler.dump(reset=True)
    assert [e["name"] for e in json.loads(profiler.dumps())
            ["traceEvents"]] == ["gamma"], "failed dump lost events"
    assert [e["name"] for e in json.load(open(path))["traceEvents"]] \
        == ["beta"]


def test_profiler_counter_threaded_increment():
    c = profiler.Counter("hits")
    profiler.set_state("run")

    def work():
        for _ in range(2000):
            c.increment()
        for _ in range(500):
            c.decrement()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    profiler.set_state("stop")
    profiler.dumps(reset=True)
    assert c.value == 8 * (2000 - 500), \
        "increment/decrement lost updates under contention"


# ---------------------------------------------------------------------------
# satellite: monitor exception safety + telemetry routing
# ---------------------------------------------------------------------------
def test_monitor_stat_error_restores_invoke():
    from mxnet_tpu.monitor import Monitor
    from mxnet_tpu.ndarray import ndarray as nd_impl
    orig = nd_impl.invoke

    def bad_stat(arr):
        raise RuntimeError("stat exploded")

    mon = Monitor(stat_func=bad_stat)
    mon.install()
    mon.tic()
    assert nd_impl.invoke is not orig
    with pytest.raises(RuntimeError, match="stat exploded"):
        mx.nd.ones((2,)) + mx.nd.ones((2,))
    assert nd_impl.invoke is orig, \
        "a raising stat_func must restore ndarray.invoke"
    # ops keep working afterwards
    out = (mx.nd.ones((2,)) * 3).asnumpy()
    np.testing.assert_allclose(out, [3, 3])


def test_monitor_stats_reach_telemetry():
    from mxnet_tpu.monitor import Monitor
    mon = Monitor(pattern=".*")
    with mon:
        mx.nd.ones((2, 2)) + mx.nd.ones((2, 2))
    gauges = telemetry.snapshot()["gauges"]
    stats = {k: v for k, v in gauges.items()
             if k.startswith("mx_monitor_stat")}
    assert stats, "monitor stats never reached the registry"
    assert all(np.isfinite(v) for v in stats.values())


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------
def test_trace_summary_aggregates(tmp_path, capsys):
    import tools.trace_summary as ts
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": [
            {"name": "a", "cat": "engine", "ph": "X", "ts": 0, "dur": 10},
            {"name": "a", "cat": "engine", "ph": "X", "ts": 20, "dur": 30},
            {"name": "b", "cat": "step", "ph": "X", "ts": 0, "dur": 5},
            {"name": "m", "ph": "i", "ts": 0},          # no duration
        ]}, f)
    assert ts.main([path]) == 0
    out = capsys.readouterr().out
    assert "engine" in out and "step" in out
    per_name, per_cat = ts.summarize(json.load(open(path))["traceEvents"])
    assert per_name["a"]["count"] == 2
    assert per_name["a"]["total_us"] == 40
    assert per_cat["engine"]["max_us"] == 30
    assert "m" not in per_name
    # the legal array-form chrome trace (no traceEvents wrapper) works
    arr = str(tmp_path / "arr.json")
    with open(arr, "w") as f:
        json.dump([{"name": "a", "cat": "c", "ph": "X", "ts": 0,
                    "dur": 2}], f)
    assert ts.main([arr]) == 0
    assert "a" in capsys.readouterr().out


def test_telemetry_micro_runs():
    """Exercise the overhead tool end to end in report-only mode — the
    hard 5% gate is a benchmark-machine assertion; on a loaded CI box
    a 300-op trial can jitter past any sane bound (threshold<=0 turns
    the assert off, everything else still runs)."""
    import tools.telemetry_micro as tm
    assert tm.main(["--ops", "300", "--repeats", "2",
                    "--threshold", "0"]) == 0
    # the tool popped MXNET_TELEMETRY and refreshed: gate must be OFF
    # (a leaked enable(True) or cached stale gate would show here)
    assert telemetry.enabled() is False
