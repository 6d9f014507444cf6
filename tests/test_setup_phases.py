"""The start of a job on the program's own timeline
(docs/OBSERVABILITY.md "Start-up"): ``setup::*`` spans in a log of
their own, the sharded step's compile as one ``compilewatch`` record a
program with its three stages and the persistent cache's word, and
``telemetry.startup_phases`` merging both into exclusive seconds by
phase."""
import time

import jax
import numpy as np
import pytest

from mxnet_tpu import compilewatch, gluon, nd, profiler, telemetry
from mxnet_tpu.gluon import nn

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_COMMWATCH", "0")
    monkeypatch.delenv("MXNET_TELEMETRY_HEARTBEAT", raising=False)
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()
    profiler.set_state("stop")
    yield
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()


def _toy_step(**kwargs):
    from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep, make_mesh
    net = nn.HybridSequential(prefix="toy_")
    net.add(nn.Dense(8, activation="relu", in_units=6),
            nn.Dense(2, in_units=8))
    net.initialize()
    return ShardedTrainStep(net, gluon.loss.L2Loss(),
                            make_mesh(MeshConfig(dp=2)), optimizer="sgd",
                            lr=0.1, momentum=0.0, **kwargs)


def _batch(n):
    return (nd.array(np.random.rand(n, 6).astype(np.float32)),
            nd.array(np.random.rand(n, 2).astype(np.float32)))


def _record(fn, at, stages, cache):
    return {"site": "test", "fn": fn, "instance": fn, "kind": "compile",
            "stages": stages, "flops": None, "bytes": {}, "signature": [],
            "changed": [], "time": at, "persistent_cache": cache}


def _sharded_records():
    return [r for r in compilewatch.programs()
            if r["fn"].startswith("sharded_step:")]


# ---------------------------------------------------------------------------
# the sharded step's compile
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("commwatch_on", ["0", "1"])
def test_sharded_step_leaves_one_record_a_program(monkeypatch, commwatch_on):
    monkeypatch.setenv("MXNET_COMMWATCH", commwatch_on)
    telemetry.refresh()
    step = _toy_step()
    assert [s[0] for s in telemetry.setup_log()].count("setup::place") == 1
    assert "setup::graph" in [s[0] for s in telemetry.setup_log()]
    step.step(*_batch(4))
    (rec,) = _sharded_records()
    assert rec["fn"] == "sharded_step:fused_step"
    assert rec["site"] == "parallel.sharded" and rec["instance"] == "toy"
    assert list(rec["stages"]) == ["trace", "lower", "compile"]
    assert all(dt > 0 for dt in rec["stages"].values())
    assert rec["kind"] == "compile" and rec["signature"] == [
        "f32[4,6]", "f32[4,2]"]
    assert rec["persistent_cache"] in ("hit", "miss", None)
    hist = telemetry.snapshot()["histograms"]
    for stage in rec["stages"]:
        assert hist['mx_compile_seconds{fn="sharded_step:fused_step",'
                    'stage="%s"}' % stage]["count"] == 1
    assert not any('stage="total"' in key for key in hist)
    assert not hasattr(compilewatch, "note_external_compile")
    # the program's first call, and no later one, is a first launch
    first = [s for s in telemetry.setup_log()
             if s[0] == "setup::first_launch"
             and s[3] == "step::sharded.launch"]
    assert len(first) == 1
    # what the benchmark's late-compile guard counts: steady steps add
    # nothing, another data shape adds a record
    seen = len(compilewatch.programs())
    step.step(*_batch(4))
    assert len(compilewatch.programs()) == seen
    assert len([s for s in telemetry.setup_log()
                if s[3] == "step::sharded.launch"]) == 1
    step.step(*_batch(8))
    assert len(compilewatch.programs()) == seen + 1
    assert _sharded_records()[-1]["kind"] == "recompile"
    assert compilewatch.compile_seconds_total() >= sum(
        sum(r["stages"].values()) for r in _sharded_records())


def test_every_program_of_an_accumulating_step_is_recorded():
    step = _toy_step(grad_accum=2)
    x, y = _batch(4)
    step.step(x, y)
    step.step(x, y)
    assert sorted(r["fn"] for r in _sharded_records()) == [
        "sharded_step:apply_step", "sharded_step:micro_step"]


class _Stage:
    """A jitted function whose stages do nothing but say what the
    persistent cache would."""

    def __init__(self, events):
        self.events = events

    def trace(self, *args):
        return self

    def lower(self):
        return self

    def compile(self):
        for event in self.events:
            jax.monitoring.record_event(event)
        return "executable"


@pytest.mark.parametrize("events, word", [
    ((compilewatch._USES_CACHE, compilewatch._CACHE_HIT), "hit"),
    ((compilewatch._USES_CACHE,), "miss"),
    ((), None)])
def test_compile_stages_reads_the_persistent_caches_word(events, word):
    _, _, compiled, stages, got = compilewatch.compile_stages(
        _Stage(events), ())
    assert compiled == "executable" and got == word
    assert list(stages) == ["trace", "lower", "compile"]


def test_watched_site_records_carry_the_caches_word():
    f = compilewatch.watched_jit(lambda x: x + 1, "toy_fn", "test")
    f(np.ones(3, np.float32))
    (rec,) = [r for r in compilewatch.programs() if r["fn"] == "toy_fn"]
    assert "persistent_cache" in rec
    assert [s[0] for s in telemetry.setup_log()] == ["setup::first_launch"]
    f(np.ones(3, np.float32))           # a cache hit launches unwatched
    assert len(telemetry.setup_log()) == 1


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------
def test_setup_span_folds_its_own_name_and_keeps_its_parent():
    with telemetry.setup_phase("init"):
        with telemetry.setup_phase("init"):         # a child block's
            with telemetry.setup_phase("native"):
                pass
    log = telemetry.setup_log()
    assert [(s[0], s[3]) for s in log] == [
        ("setup::native", "setup::init"), ("setup::init", None)]
    hist = telemetry.snapshot()["histograms"]
    assert hist['mx_setup_phase_seconds{phase="init"}']["count"] == 1
    assert "mx_setup_phase_seconds" in telemetry.render_prometheus()


def test_backdated_span_starts_where_it_is_told():
    t0 = time.perf_counter() - 5.0
    with telemetry.setup_phase("import") as s:
        s.backdate(t0)
    ((name, start, end, parent),) = telemetry.setup_log()
    assert (name, start, parent) == ("setup::import", t0, None)
    assert end - start >= 5.0


def test_import_of_the_package_is_a_span():
    import os
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import mxnet_tpu as mx; log = mx.telemetry.setup_log(); "
         "print([s[0] for s in log], log[-1][2] - log[-1][1] > 0.05)"],
        env=dict(os.environ, MXNET_TELEMETRY="1", JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join(sys.path)),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split("]")[0].endswith("'setup::import'")
    assert out.stdout.strip().endswith("True")


def test_setup_span_enters_no_step_record():
    from mxbench import spans

    def window():
        for _ in range(3):
            with telemetry.phase("forward"):
                with telemetry.setup_phase("first_launch"):
                    pass
            telemetry.mark_step()
        return telemetry.step_log()

    with_setup = window()
    assert all(list(rec["spans"]) == ["step::forward"]
               for rec in with_setup)
    assert all(len(rec["events"]) == 1 for rec in with_setup)
    per = spans.per_step(with_setup)
    assert sorted(per) == ["launches", "step::forward", "step_log_steps"]
    assert len(telemetry.setup_log()) == 3
    # and the step log's reset leaves no set-up span behind
    telemetry.reset()
    assert telemetry.setup_log() == [] and telemetry.step_log() == []


def test_setup_log_is_bounded(monkeypatch):
    monkeypatch.setattr(telemetry._SetupLog, "CAP", 4)
    for _ in range(6):
        with telemetry.setup_phase("init"):
            pass
    assert len(telemetry.setup_log()) == 4
    assert telemetry._SETUPLOG.dropped == 2


class _CountingClock:
    def __init__(self):
        self.reads = 0

    def perf_counter(self):
        self.reads += 1
        return time.perf_counter()

    def __getattr__(self, name):
        return getattr(time, name)


def test_with_telemetry_off_nothing_is_timed_or_kept(monkeypatch):
    step = _toy_step()
    x, y = _batch(4)
    telemetry.enable(False)
    telemetry.reset()
    compilewatch.reset()
    clock = _CountingClock()
    monkeypatch.setattr(telemetry, "time", clock)
    monkeypatch.setattr(compilewatch, "time", clock)

    def refuse(*args, **kwargs):
        raise AssertionError("the gate is off")

    monkeypatch.setattr(compilewatch, "compile_stages", refuse)
    monkeypatch.setattr(compilewatch, "publish", refuse)
    with telemetry.setup_phase("init") as s:
        assert s._ann is None
    loss = step.step(x, y)              # compiles, unwatched
    assert np.isfinite(float(jax.device_get(loss)))
    assert clock.reads == 0
    assert telemetry.setup_log() == [] and compilewatch.programs() == []
    (compiled, _), = step._programs.values()
    assert step._executable("fused_step", None, next(iter(
        step._programs))[1], ())[0] is compiled


# ---------------------------------------------------------------------------
# the timeline
# ---------------------------------------------------------------------------
def _timeline(monkeypatch, spans, records):
    monkeypatch.setattr(telemetry._SETUPLOG, "spans", [
        ("setup::" + name, start, end, None) for name, start, end in spans])
    for rec in records:
        compilewatch.publish(rec)


def test_phases_are_exclusive_and_sum_to_covered(monkeypatch):
    _timeline(monkeypatch, [
        ("import", 0.0, 10.0),
        ("native", 2.0, 3.0),               # inside the import
        ("init", 12.0, 20.0),
        ("graph", 21.0, 22.0),
        ("first_launch", 16.5, 17.0),       # inside init, after a compile
        ("place", 30.0, 34.0),
        ("place", 40.0, 41.0),              # the re-layout
        ("first_launch", 41.0, 41.5),
    ], [
        # a miss inside init, a hit between place and its re-layout
        _record("eager", 13.0, {"trace": 0.5, "lower": 1.0, "compile": 2.0},
                "miss"),
        _record("step", 35.0, {"trace": 1.0, "lower": 1.0, "compile": 3.0},
                "hit"),
        _record("plain", 50.0, {"total": 1.0}, None),
    ])
    got = telemetry.startup_phases(until=100.0)
    assert got == pytest.approx({
        "import": 9.0, "native": 1.0, "init": 8.0 - 3.5 - 0.5,
        "graph": 1.0, "place": 5.0, "trace_lower": 1.5 + 2.0,
        "compile_miss": 2.0 + 1.0, "cache_load": 3.0,
        "first_launch": 1.0, "covered": 10 + 8 + 1 + 4 + 5 + 1.5 + 1})
    assert sum(v for k, v in got.items() if k != "covered") \
        == pytest.approx(got["covered"])
    assert tuple(k for k in got if k != "covered") \
        == telemetry.STARTUP_PHASES


def test_until_cuts_the_timeline(monkeypatch):
    _timeline(monkeypatch, [("init", 0.0, 4.0), ("place", 6.0, 10.0),
                            ("graph", 20.0, 21.0)],
              [_record("late", 30.0, {"trace": 1.0, "lower": 1.0,
                                      "compile": 1.0}, "miss")])
    got = telemetry.startup_phases(until=8.0)
    assert got["init"] == 4.0 and got["place"] == 2.0       # cut at 8
    assert got["graph"] == 0.0 and got["trace_lower"] == 0.0
    assert got["covered"] == 6.0
    assert telemetry.startup_phases(until=100.0)["covered"] == 12.0


def test_default_cut_is_the_first_step(monkeypatch):
    with telemetry.setup_phase("init"):
        pass
    telemetry.mark_step()
    first = telemetry._STEP["t0"]
    with telemetry.setup_phase("place"):
        time.sleep(0.01)
    telemetry.mark_step()
    got = telemetry.startup_phases()
    assert got["init"] > 0 and got["place"] == 0.0
    assert got == telemetry.startup_phases(until=first)


def test_report_prints_the_phases_and_the_misses(monkeypatch):
    _timeline(monkeypatch, [("init", 0.0, 4.0)], [
        _record("slow_step", 5.0, {"trace": 1.0, "lower": 1.0,
                                   "compile": 7.0}, "miss"),
        _record("warm_step", 20.0, {"trace": 1.0, "lower": 1.0,
                                    "compile": 0.5}, "hit")])
    assert [r["fn"] for r in compilewatch.cache_misses()] == ["slow_step"]
    monkeypatch.setitem(telemetry._STEP, "t0", 100.0)
    text = compilewatch.render_report()
    assert "start-up to the first step" in text
    phases = {line.split()[0]: float(line.split()[1])
              for line in text.splitlines() if line.startswith("  ")
              and len(line.split()) == 2}
    assert phases["init"] == 4.0 and phases["compile_miss"] == 7.0
    assert phases["cache_load"] == 0.5 and phases["covered"] == 15.5
    missed = text.split("missed the persistent cache")[1]
    assert "slow_step" in missed and "warm_step" not in missed
