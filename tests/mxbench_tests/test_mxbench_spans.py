"""The program's spans as the benchmark reads them (``mxbench/spans.py``,
the ``train_resident`` generator, the eight host-loop readers of the
resident ResNet cell): the step-log reduction and each reader on
hand-made step logs, the xplane loader on recorded traces, the
launch-to-device and idle-gap arithmetic on hand-made intervals, and
the cell's files against BENCHMARK.json."""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

from mxbench import manifest, spans, trace as T
from mxbench.record import Run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
TINY_STEPS = os.path.join(HERE, "data", "tiny_v5e_steps.xplane.pb")
CELL = "resnet50_v1_train_resident_b256"
SPAN_READERS = {
    "host_forward_ms.train_img": "step::forward",
    "host_backward_ms.train_img": "step::backward",
    "host_update_ms.train_img": "step::update",
    "host_update_prep_ms.train_img": "step::update.prep",
    "host_update_launch_ms.train_img": "step::update.launch",
    "host_update_writeback_ms.train_img": "step::update.writeback",
}
NEW = list(SPAN_READERS) + ["launches_per_step.train_img",
                            "launch_to_device_ms.train_img"]


def _run(untraced=None, trace=None, window=None):
    return Run(cell={}, sizes={}, traffic={}, device_kind="TPU v5 lite",
               chips=1, correct=True, attempted=2, failed=0, end_to_end={},
               window_s=1.0, samples=2, flops_per_sample=1.0, peak_bytes=0,
               setup_compiles=0, setup_compile_s=0.0, setup_cache_hits=0,
               untraced_s_per_step=untraced, trace=trace,
               trace_window=window)


def _record(step, seconds, launches):
    """A closed step as ``telemetry.step_log`` gives it."""
    return {"step": step,
            "spans": {name: {"count": 1, "seconds": s, "self_seconds": s}
                      for name, s in seconds.items()},
            "launches": dict(launches), "events": [], "dropped": 0}


LOG = [
    _record(0, {"step::forward": 0.004, "step::backward": 0.001,
                "step::update": 0.040, "step::update.prep": 0.002,
                "step::update.launch": 0.030,
                "step::update.writeback": 0.006}, {"gluon": 1.0}),
    # a classic step: no fused children, the optimizer's own program too
    _record(1, {"step::forward": 0.002, "step::backward": 0.003,
                "step::update": 0.020, "step::optimizer": 0.015},
            {"gluon": 2.0, "sharded": 1.0}),
]
WANT_MS = {"step::forward": 3.0, "step::backward": 2.0, "step::update": 30.0,
           "step::update.prep": 1.0, "step::update.launch": 15.0,
           "step::update.writeback": 3.0}


# ---------------------------------------------------------------------------
# the step log
# ---------------------------------------------------------------------------
def test_per_step_reduction():
    per = spans.per_step(LOG)
    assert per["step_log_steps"] == 2.0
    assert per["launches"] == 2.0            # (1 + 3) / 2, all paths
    for name, ms in WANT_MS.items():
        assert per[name] * 1e3 == pytest.approx(ms)
    assert per["step::optimizer"] * 1e3 == pytest.approx(7.5)
    assert spans.per_step([]) == {}


def test_step_records_reads_the_programs_step_log(monkeypatch):
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh()
    telemetry.reset()
    try:
        for _ in range(3):
            with telemetry.phase("forward"):
                pass
            telemetry.count_launch("gluon")
            telemetry.mark_step()
        got = spans.step_records(2)
        assert [r["step"] for r in got] == [1, 2]
        assert spans.per_step(got)["launches"] == 1.0
        assert spans.step_records(0) == []
        # a program from before the step log: nothing, not an error
        monkeypatch.delattr(telemetry, "step_log")
        assert spans.step_records(2) == []
    finally:
        telemetry.refresh()
        telemetry.reset()


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_on_a_hand_made_step_log(metric):
    reader = manifest.layer_metric(metric)
    assert reader.UNIT == "ms/step"
    untraced = {"wall": 0.09, "step": 0.05}
    untraced.update(spans.per_step(LOG))
    assert reader.read(_run(untraced)) == pytest.approx(
        WANT_MS[SPAN_READERS[metric]])
    # no traced run, and a program that keeps no step log: left out
    assert reader.read(_run(None)) is None
    assert reader.read(_run({"wall": 0.09, "step": 0.05})) is None
    # the log is there but never saw the span (a classic-path window)
    if SPAN_READERS[metric].startswith("step::update."):
        assert reader.read(_run(spans.per_step(LOG[1:]))) == 0.0


def test_launches_reader_on_a_hand_made_step_log():
    reader = manifest.layer_metric("launches_per_step.train_img")
    assert reader.UNIT == "count/step"
    assert reader.read(_run(spans.per_step(LOG[:1]))) == 1.0
    assert reader.read(_run(spans.per_step(LOG))) == 2.0
    assert reader.read(_run(None)) is None
    assert reader.read(_run({"wall": 0.1})) is None


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------
def _hand_made():
    """Three steps on one device: 10 ms programs, launched from inside
    nested step::* spans. Times in ns."""
    ms = 1_000_000
    program, modules, ops, bench = [], [], [], []
    for i, (launch_at, runs_at) in enumerate([(5, 7), (25, 27), (45, 50)]):
        t = 20 * i * ms
        bench.append(T.Op("mxbench/step", t, t + 9 * ms))
        program += [
            T.Op("step::forward", t, t + 2 * ms),
            T.Op("step::backward", t + 2 * ms, t + 3 * ms),
            T.Op("step::update", t + 3 * ms, t + 9 * ms),
            T.Op("step::update.prep", t + 3 * ms, t + 5 * ms),
            T.Op("step::update.launch", launch_at * ms, t + 8 * ms),
            T.Op("step::update.writeback", t + 8 * ms, t + 9 * ms)]
        modules.append(T.Op("jit_runner", runs_at * ms, (runs_at + 10) * ms))
        ops.append(T.Op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                        runs_at * ms, (runs_at + 10) * ms))
    program.sort(key=lambda s: s.start)
    dev = T.Device(ops, [], modules)
    return spans.ProgramTrace({0: dev}, bench, program), (0, 60 * ms)


def test_launch_to_device_on_hand_made_intervals():
    trace, window = _hand_made()
    # 7 - 5, 27 - 25, 50 - 45 ms: the median
    assert spans.launch_to_device_s(trace, 0, window) == pytest.approx(2e-3)
    reader = manifest.layer_metric("launch_to_device_ms.train_img")
    assert reader.UNIT == "ms"
    assert reader.read(_run(trace=trace, window=window)) \
        == pytest.approx(2.0)
    assert reader.read(_run()) is None                      # no trace
    # what trace.py's load returns, or a program without the spans
    plain = T.Trace(trace.devices, trace.spans)
    assert reader.read(_run(trace=plain, window=window)) is None
    bare = spans.ProgramTrace(trace.devices, trace.spans, [])
    assert reader.read(_run(trace=bare, window=window)) is None
    # a launch cut by the window's edge is not a step of the window
    assert spans.launch_to_device_s(
        trace, 0, (20 * 1_000_000, 60 * 1_000_000)) == pytest.approx(3.5e-3)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    trace, window = _hand_made()
    gaps = spans.idle_gaps(trace, 0, window)
    ms = 1e-3
    # idle: 0-7, 17-27, 37-50 ms (busy 7-17, 27-37, 50-60)
    assert [g[1] for g in gaps] == pytest.approx([13 * ms, 10 * ms, 7 * ms])
    # 37-50: of step 3's spans update (43-49) covers 6 ms, its launch
    # (45-48) 3, forward 2: the widest cover names the gap
    assert [g[0] for g in gaps] == ["step::update"] * 3
    # the same gaps as trace.py names them, by the benchmark's own spans
    assert [g[0] for g in T.idle_gaps(trace, 0, window)] \
        == ["mxbench/step"] * 3
    # a span that nests exactly inside another of the same cover wins
    inner = spans.ProgramTrace(
        trace.devices, trace.spans,
        [T.Op("step::update", 0, 7_000_000),
         T.Op("step::update.launch", 1_000_000, 7_000_000),
         T.Op("step::update.launch.call", 1_000_000, 7_000_000 - 1)])
    one = spans.idle_gaps(inner, 0, (1_000_000, 7_000_000), n=1)
    assert one[0][0] == "step::update.launch"
    assert spans.idle_gaps(
        spans.ProgramTrace(trace.devices, trace.spans, []), 0, window,
        n=1) == [["host", pytest.approx(13 * ms)]]


def test_loader_on_a_trace_without_program_spans():
    """PR 23's recording: what a parent commit's trace looks like."""
    got = spans.load(TINY)
    base = T.load(TINY)
    assert got.devices == base.devices and got.spans == base.spans
    assert got.program == []
    window = T.window_of(got)
    assert spans.launch_to_device_s(got, 0, window) is None
    assert T.total(T.busy(got, 0, window)) == T.total(T.busy(base, 0, window))


def test_loader_keeps_step_spans_of_a_recorded_trace(tmp_path):
    """Record a trace here (the CPU has no device plane, so only the
    host half is read): the program's live spans are in it under their
    own names, nested, and nothing else is kept."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from mxnet_tpu import telemetry
    telemetry.enable(True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(2):
                with TraceAnnotation("mxbench/step"):
                    with telemetry.phase("update"):
                        with telemetry.phase("update.launch"):
                            jnp.ones((4, 4)).sum().block_until_ready()
                    with telemetry.span("io::next"):
                        pass
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.refresh()
        telemetry.reset()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    got = spans.program_spans(path)
    assert [s.name for s in got] == ["step::update", "step::update.launch"] * 2
    assert got == sorted(got, key=lambda s: s.start)
    for outer, inner in (got[0:2], got[2:4]):
        assert outer.start <= inner.start and inner.end <= outer.end
    with pytest.raises(ValueError):
        spans.load(path)            # as trace.py: no TPU plane, no trace


@pytest.mark.skipif(not os.path.exists(TINY_STEPS),
                    reason="no recording with program spans")
def test_loader_on_the_recording_with_program_spans():
    """Recorded on one v5e (PR 25): three fused Gluon steps of a
    two-layer net, each under mxbench/step + mxbench/sync, telemetry
    on."""
    got = spans.load(TINY_STEPS)
    names = [s.name for s in got.program]
    for name in ("step::forward", "step::backward", "step::update",
                 "step::update.prep", "step::update.launch",
                 "step::update.launch.lookup", "step::update.launch.call",
                 "step::update.writeback"):
        assert names.count(name) == (6 if name == "step::forward" else 3)
    assert all(s.name.startswith("mxbench/") for s in got.spans)
    window = T.window_of(got)
    updates = [s for s in got.program if s.name == "step::update"]
    for child in got.program:
        if child.name.startswith("step::update."):
            assert any(u.start <= child.start and child.end <= u.end
                       for u in updates)
    wait = spans.launch_to_device_s(got, 0, window)
    # host and device on one clock: a step's program starts after its
    # launch began, and well within a millisecond-scale step
    assert 0 < wait < 5e-3
    gaps = spans.idle_gaps(got, 0, window)
    assert gaps and all(g[0] == "host" or g[0].startswith("step::")
                        for g in gaps)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_benchmark_json_names_the_cell_the_mix_and_the_eight_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": "resnet50_v1",
                     "traffic": "imagenet_resident_b256", "chips": 1,
                     "why": manifest.workload(CELL)["why"]}
    names = [w["name"] for w in bench["workloads"]]
    # appended when it came, after the cells that were there (a later
    # PR appends in turn, so it need not be the last)
    assert names.index(CELL) > names.index("bert_base_pretrain_s128_dp4")
    cell = manifest.workload(CELL)
    recordio = manifest.workload("resnet50_v1_train_recordio")
    assert cell["metrics"] == ["train_images_per_s", "setup_s"]
    assert cell["layer_metrics"] == recordio["layer_metrics"] + NEW
    layer = {m["name"]: m for m in bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW[0])                       # the eight, together
    assert listed[at:at + 8] == NEW
    for name in NEW:
        m = layer[name]
        assert m["layer"] == "host loop" and m["better"] == "lower"
        assert m["moves"] == "train_images_per_s"
        assert m["workloads"] == [CELL]
        assert manifest.layer_metric(name).UNIT == m["unit"]
    assert {layer[n]["source"] for n in SPAN_READERS} == {"host_clock"}
    assert layer["launches_per_step.train_img"]["source"] \
        == "program_counter"
    assert layer["launch_to_device_ms.train_img"]["source"] == "device_trace"
    for name in recordio["layer_metrics"]:          # the nine inherited
        assert CELL in layer[name].get("workloads", [CELL])
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_images_per_s"]
    assert rate["workloads"] == ["resnet50_v1_train_recordio", CELL]
    params, gen = manifest.traffic("imagenet_resident_b256")
    assert params["kind"] == "train_resident"
    assert params["batch_per_chip"] == 256 and params["inflight_steps"] == 2
    assert params["warmup_steps"] == 3 and params["trace_seconds"] == 4
    assert params["optimizer"] == {"name": "sgd", "lr": 0.02,
                                   "momentum": 0.9}
    assert gen.UNITS["train_images_per_s"] == "img/s"


def test_resident_feed_is_seeded_and_shaped_as_the_iterators():
    import types
    _, gen = manifest.traffic("imagenet_resident_b256")
    sizes = {"image_size": 16, "num_classes": 10}

    def feed(seed):
        ctx = types.SimpleNamespace(sizes=sizes, seed=seed)
        return gen.ResidentFeed(ctx, 4, None, check=True)

    x, y = feed(3000000019).host_batch()
    assert x.shape == (4, 3, 16, 16) and x.dtype == "float32"
    assert y.shape == (4,) and y.dtype == "float32"
    assert 0.0 <= x.min() and x.max() <= 1.0 and 0 <= y.min() < 10
    x2, y2 = feed(3000000019).host_batch()
    assert (x == x2).all() and (y == y2).all()
    assert (feed(3000000020).host_batch()[0] != x).any()


def test_rehearsal_prints_the_program_spans_of_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_PALLAS_INTERPRET="1")
    out = subprocess.run(
        [sys.executable, "-m", "mxbench.run", "--rehearse", "--workload",
         CELL, "--seconds", "1", "--seed", "3000000023"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed"
    assert set(NEW) <= set(last["layer_metrics_found"])
    (line,) = [ln for ln in out.stdout.splitlines()
               if "program spans over" in ln]
    assert "launches/step 1.000" in line
    ms = {name: float(value) for name, value in re.findall(
        r"([a-z.]+) ([0-9.]+)", line.split("host ms/step: ")[1].split(";")[0])}
    assert set(ms) == {"forward", "backward", "update", "update.prep",
                       "update.launch", "update.launch.lookup",
                       "update.launch.call", "update.writeback"}
    assert all(v > 0 for v in ms.values())
    kids = ms["update.prep"] + ms["update.launch"] + ms["update.writeback"]
    assert kids <= ms["update"] * 1.0001
    assert ms["update.launch.lookup"] + ms["update.launch.call"] \
        <= ms["update.launch"] * 1.0001
