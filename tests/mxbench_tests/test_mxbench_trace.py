"""The trace reduction: interval arithmetic on hand-made intervals, and
the readers' sums on a small trace recorded on one TPU v5e (PR 23:
three launches of one jitted function holding a Pallas layer norm, a
matmul and a reduction, each under mxbench/step + mxbench/sync)."""
import os

import pytest

from mxbench import trace as T

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_v5e.xplane.pb")


@pytest.mark.parametrize("given, want", [
    ([], []),
    ([(0, 10)], [(0, 10)]),
    ([(5, 7), (0, 3), (2, 4)], [(0, 4), (5, 7)]),
    ([(0, 10), (2, 3), (10, 12)], [(0, 12)]),
    ([(3, 3), (4, 2)], []),
])
def test_union(given, want):
    assert T.union(given) == want
    assert T.total(T.union(given)) == sum(e - s for s, e in want)


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [(5, 9)], [(0, 4)]),
    ([(2, 8)], [(0, 3), (7, 20)], [(3, 7)]),
])
def test_subtract(a, b, want):
    assert T.subtract(a, b) == want


def test_clip():
    assert T.clip([(0, 5), (4, 12), (20, 30)], (3, 10)) == [(3, 5), (4, 10)]


@pytest.mark.parametrize("collectives, others, want", [
    # hidden under compute, half exposed, wholly exposed, and two
    # collectives of which one is hidden
    ([(10, 20)], [(0, 30)], 0),
    ([(10, 20)], [(0, 15)], 5),
    ([(10, 20)], [(0, 5), (25, 30)], 10),
    ([(10, 20), (40, 50)], [(0, 22), (45, 47)], 8),
])
def test_exposed_collective_arithmetic(collectives, others, want):
    assert T.exposed(collectives, others) == want


@pytest.mark.parametrize("name, short, kind", [
    ("%pallas_layer_norm_fwd.1 = bf16[4096,768]{1,0:T(8,128)(2,1)S(1)} "
     "custom-call(bf16[4096,768]{1,0:T(8,128)(2,1)} %x), "
     "custom_call_target=\"tpu_custom_call\"",
     "pallas_layer_norm_fwd.1", "custom-call"),
    ("%convert_reduce_fusion = f32[128,768]{1,0:T(8,128)} fusion(bf16[128,32,"
     "768]{2,1,0:T(8,128)(2,1)S(1)} %p), kind=kLoop",
     "convert_reduce_fusion", "fusion"),
    ("%copy-start = (bf16[768,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,768]"
     "{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(bf16[768,768] %w)",
     "copy-start", "copy-start"),
    ("%all-reduce.3 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0} %g), "
     "replica_groups={{0,1,2,3}}", "all-reduce.3", "all-reduce"),
    ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %g)",
     "all-reduce-start.1", "all-reduce-start"),
    ("%transpose.7.clone", "transpose.7.clone", "transpose"),
])
def test_op_names_and_kinds(name, short, kind):
    assert T.op_name(name) == short
    assert T.op_kind(name) == kind
    assert T.is_collective(name) == kind.startswith("all-reduce")
    assert T.is_relayout(name) == (kind in ("copy-start", "transpose"))
    assert T.is_pallas(name) == short.startswith("pallas_")


def _hand_made():
    def op(name, s, e):
        return T.Op("%%%s = f32[8]{0} %s(f32[8]{0} %%x)"
                    % (name, name.split(".")[0]), s, e)
    dev = T.Device(
        ops=[op("fusion.1", 100, 200), op("all-reduce.1", 200, 260),
             op("fusion.2", 300, 400), op("all-reduce-start.2", 400, 401),
             op("fusion.3", 405, 450), op("all-reduce-done.2", 450, 451),
             op("copy.1", 500, 520)],
        async_ops=[op("all-reduce-start.2", 400, 480)],
        modules=[T.Op("jit_step(1)", 100, 520)])
    spans = [T.Op("mxbench/feed", 90, 95), T.Op("mxbench/step", 95, 300),
             T.Op("mxbench/sync", 300, 600)]
    return T.Trace({0: dev}, spans)


def test_reduction_on_hand_made_trace():
    tr = _hand_made()
    w = T.window_of(tr)
    assert w == (90, 600)
    busy = T.busy(tr, 0, w)
    assert busy == [(100, 260), (300, 401), (405, 451), (500, 520)]
    assert T.total(busy) == 160 + 101 + 46 + 20
    # the synchronous all-reduce (200..260) and the asynchronous one's
    # start-to-done span (400..480), not its 1 ns issue slots
    assert T.collective_intervals(tr, 0, w) == [(200, 260), (400, 480)]
    # exposed: all of the first (60), and of the second what fusion.3
    # (405..450) does not cover: 400..405 and 450..480
    assert T.exposed_collective_s(tr, 0, w) * 1e9 == pytest.approx(95)
    assert T.seconds_where(tr, 0, w, T.is_relayout) * 1e9 == pytest.approx(20)
    assert T.seconds_where(tr, 0, w, T.is_pallas) == 0
    assert T.count_spans(tr, "mxbench/step", w) == 1
    assert T.span_seconds(tr, "mxbench/feed") * 1e9 == pytest.approx(5)
    top = T.top_ops(tr, 0, w, 2)
    assert [r[0] for r in top] == ["fusion.1", "fusion.2"]
    gaps = T.idle_gaps(tr, 0, w, 2)
    assert gaps[0] == ["mxbench/sync", pytest.approx(80e-9)]   # 520..600
    assert gaps[1] == ["mxbench/sync", pytest.approx(49e-9)]   # 451..500


@pytest.fixture(scope="module")
def tiny():
    return T.load(TINY)


def test_spans_are_counted_inside_a_window():
    tr = _hand_made()
    tr.spans.append(T.Op("mxbench/feed", 600, 640))
    w = (290, 610)
    assert T.count_spans(tr, "mxbench/step", w) == 0      # began before
    assert T.count_spans(tr, "mxbench/sync", w) == 1
    assert T.span_seconds(tr, "mxbench/feed", w) == 0      # ends after
    assert T.span_seconds(tr, "mxbench/feed") * 1e9 == pytest.approx(45)


@pytest.mark.parametrize("metric, want", [
    # one traced step, the device busy 327 ns of it; untraced the same
    # step took 1000 ns of wall, 100 of them in the feed
    ("data_wait_ms.train", 100e-6),
    ("host_gap_ms.train", (1000 - 327) * 1e-6),
    ("device_idle_pct.train", 67.3),
    ("device_idle_pct.train_img", 67.3),
    ("device_idle_pct.train_routed", 67.3),
    ("host_gap_ms.train_routed", (1000 - 327) * 1e-6),
])
def test_host_side_readers_use_the_untraced_window(metric, want):
    import types
    from mxbench import manifest
    from mxbench.record import Run
    tr = _hand_made()
    run = types.SimpleNamespace(
        trace=tr, trace_window=T.window_of(tr),
        untraced_s_per_step={"wall": 1000e-9, "feed": 100e-9,
                             "step": 50e-9, "sync": 850e-9})
    run.traced_steps = Run.traced_steps.fget(run)
    run.busy_s_per_step = Run.busy_s_per_step.fget(run)
    assert run.traced_steps == 1
    assert manifest.layer_metric(metric).read(run) == pytest.approx(want)
    run.trace = run.untraced_s_per_step = None
    run.busy_s_per_step = None
    assert manifest.layer_metric(metric).read(run) is None


def test_recorded_trace_structure(tiny):
    assert sorted(tiny.devices) == [0]
    dev = tiny.devices[0]
    assert len(dev.modules) == 3 and len(dev.ops) == 27
    assert [s.name for s in tiny.spans] == ["mxbench/step", "mxbench/sync"] * 3
    kinds = {T.op_kind(o.name) for o in dev.ops}
    assert {"custom-call", "fusion", "copy-start", "copy-done"} <= kinds


def test_recorded_trace_sums(tiny):
    w = T.window_of(tiny)
    span = (w[1] - w[0]) / 1e9
    busy = T.total(T.busy(tiny, 0, w)) / 1e9
    # host and device clocks differ by about 1 ms in this trace, so the
    # first of the three 48 us launches falls before the first host
    # span: two are inside the window
    assert busy == pytest.approx(96.35e-6, rel=1e-3)
    assert 0 < busy < span
    assert 1 - busy / span == pytest.approx(0.9867, abs=1e-3)
    # one pallas_layer_norm_fwd of 11.47 us in each launch
    pallas = T.seconds_where(tiny, 0, w, T.is_pallas)
    assert pallas == pytest.approx(2 * 11.49e-6, rel=2e-2)
    whole = (0.0, w[1])
    assert T.seconds_where(tiny, 0, whole, T.is_pallas) \
        == pytest.approx(3 * 11.47e-6, rel=2e-2)
    assert T.top_ops(tiny, 0, w, 2)[1][0] == "pallas_layer_norm_fwd.1"
    assert T.count_spans(tiny, "mxbench/step", w) == 3
    assert all(name == "mxbench/sync" for name, _ in
               T.idle_gaps(tiny, 0, w, 3))


def test_a_trace_without_a_tpu_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((4, 4)).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no /device:TPU"):
        T.load(T.find_xplane(str(tmp_path)))
