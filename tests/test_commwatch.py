"""Fleet-observability tests (ISSUE 6; docs/OBSERVABILITY.md
"Communication" + "Fleet / MFU"): the collective-comm profiler
(commwatch), cross-rank aggregation with straggler attribution
(telemetry.fleet_snapshot), and the measured MFU/goodput meters.
All tier-1 (`obs` marker, not `slow`) except where noted."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import commwatch, compilewatch, telemetry

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.delenv("MXNET_COMMWATCH", raising=False)
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_STRAGGLER_WARN", raising=False)
    monkeypatch.delenv("MXNET_FLEET_SNAPSHOT_PERIOD", raising=False)
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()
    yield
    telemetry.refresh()
    telemetry.reset()
    compilewatch.reset()


# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------
def test_disabled_gates_are_noops(monkeypatch):
    # telemetry off => commwatch off, record() registers nothing
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    telemetry.refresh()
    assert not commwatch.enabled()
    commwatch.record("allreduce", "dp", 1024, 4, seconds=0.1)
    with commwatch.comm_span("allreduce", "dp", 1024, 4):
        pass
    commwatch.traced_collective("allreduce", "dp",
                                np.zeros((4,), np.float32), 4)
    assert telemetry.snapshot()["counters"] == {}
    # telemetry on but MXNET_COMMWATCH=0 => still off
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_COMMWATCH", "0")
    telemetry.refresh()
    assert telemetry.enabled() and not commwatch.enabled()
    commwatch.record("allreduce", "dp", 1024, 4, seconds=0.1)
    assert not any("mx_comm" in k
                   for k in telemetry.snapshot()["counters"])


def test_record_counters_and_bus_bandwidth():
    commwatch.record("allreduce", "dp", 1000, 4, seconds=0.5)
    snap = telemetry.snapshot()
    assert snap["counters"]['mx_comm_ops_total{axis="dp",op="allreduce"}'] \
        == 1
    assert snap["counters"][
        'mx_comm_bytes_total{axis="dp",op="allreduce"}'] == 1000
    alg = snap["histograms"][
        'mx_comm_bandwidth_bytes_per_sec{axis="dp",op="allreduce"}']
    bus = snap["histograms"][
        'mx_comm_bus_bandwidth_bytes_per_sec{axis="dp",op="allreduce"}']
    np.testing.assert_allclose(alg["sum"], 2000.0)       # 1000 B / .5 s
    # NCCL busbw factor for a 4-way allreduce: 2*(4-1)/4 = 1.5
    np.testing.assert_allclose(bus["sum"], 3000.0)
    # count=3 identical collectives in one record
    commwatch.record("allgather", ("dcn", "dp"), 100, 8, count=3)
    snap = telemetry.snapshot()
    assert snap["counters"][
        'mx_comm_ops_total{axis="dcn+dp",op="allgather"}'] == 3
    assert snap["counters"][
        'mx_comm_bytes_total{axis="dcn+dp",op="allgather"}'] == 300


def test_exposed_vs_overlapped_attribution():
    with commwatch.comm_span("allreduce", "kv", 64, 2):
        time.sleep(0.002)
    with commwatch.exposed_region():
        with commwatch.comm_span("allreduce", "kv", 64, 2):
            time.sleep(0.002)
    snap = telemetry.snapshot()
    exp = snap["counters"].get(
        'mx_comm_exposed_seconds_total{axis="kv",op="allreduce"}', 0)
    ovl = snap["counters"].get(
        'mx_comm_overlapped_seconds_total{axis="kv",op="allreduce"}', 0)
    assert exp > 0 and ovl > 0
    # explicit flag wins over the thread marker
    with commwatch.comm_span("allreduce", "kv2", 64, 2, exposed=True):
        pass
    snap = telemetry.snapshot()
    assert 'mx_comm_exposed_seconds_total{axis="kv2",op="allreduce"}' \
        in snap["counters"]


# ---------------------------------------------------------------------------
# trace-time records + program inventories
# ---------------------------------------------------------------------------
def test_traced_collective_direct_and_inventory():
    x = np.zeros((8, 4), np.float32)          # 128 bytes
    # no active program_watch: counts once, immediately
    commwatch.traced_collective("reduce_scatter", "dp", x, 4)
    snap = telemetry.snapshot()
    assert snap["counters"][
        'mx_comm_bytes_total{axis="dp",op="reduce_scatter"}'] == 128
    # inside program_watch: records become the program inventory,
    # charged once per execution
    with commwatch.program_watch("progA"):
        commwatch.traced_collective("ppermute", "pp", x, 4, count=5)
        time.sleep(0.001)
    with commwatch.program_watch("progA"):
        time.sleep(0.001)                      # cached execution
    snap = telemetry.snapshot()
    assert snap["counters"][
        'mx_comm_ops_total{axis="pp",op="ppermute"}'] == 10  # 5 x 2 execs
    assert snap["counters"][
        'mx_comm_bytes_total{axis="pp",op="ppermute"}'] == 128 * 10
    bw = snap["histograms"][
        'mx_comm_bandwidth_bytes_per_sec{axis="pp",op="ppermute"}']
    assert bw["count"] == 2 and bw["sum"] > 0


def test_hlo_parse_names_mesh_axes():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
    tp_sh = NamedSharding(mesh, P(None, "tp"))
    dp_sh = NamedSharding(mesh, P("dp"))
    rep = NamedSharding(mesh, P())

    def step(w, x):
        def loss(w_):
            return jnp.sum(jnp.tanh(x @ w_) ** 2)
        l, g = jax.value_and_grad(loss)(w)
        return w - 0.1 * g, l

    f = jax.jit(step, in_shardings=(tp_sh, dp_sh),
                out_shardings=(tp_sh, rep))
    w = jax.device_put(jnp.ones((16, 32)), tp_sh)
    x = jax.device_put(jnp.ones((8, 16)), dp_sh)
    compiled = f.lower(w, x).compile()
    colls = commwatch.parse_hlo_collectives(compiled.as_text(), mesh)
    axes = {c["axis"] for c in colls}
    assert any("dp" in a.split("+") for a in axes), colls
    assert all(c["bytes"] > 0 and c["participants"] > 1 for c in colls)
    # register + watch: the inventory is charged per execution and the
    # program FLOPs feed the MFU numerator
    flops = compilewatch._extract_cost(compiled)
    assert flops and flops > 0
    commwatch.register_program("hlo_prog", "hlo_prog",
                               compiled=compiled, mesh=mesh, flops=flops)
    for _ in range(2):
        with commwatch.program_watch("hlo_prog"):
            jax.block_until_ready(compiled(w, x))
    snap = telemetry.snapshot()
    comm_bytes = [v for k, v in snap["counters"].items()
                  if k.startswith("mx_comm_bytes_total")]
    assert sum(comm_bytes) > 0
    np.testing.assert_allclose(
        snap["counters"]["mx_executed_flops_total"], 2 * flops)


def test_iota_replica_group_parsing():
    line = ("  %ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %d), "
            "channel_id=2, replica_groups=[2,4]<=[4,2]T(1,0), "
            "use_global_device_ids=true, to_apply=%add")
    g = commwatch._first_group(line)
    assert g == [0, 2, 4, 6]
    line2 = ("  %ag = f32[8,4]{1,0} all-gather(f32[1,4]{1,0} %p), "
             "replica_groups=[4,2]<=[8], dimensions={0}")
    assert commwatch._first_group(line2) == [0, 1]


def test_tuple_and_async_hlo_forms():
    """The all-reduce combiner emits tuple-result grouped syncs and
    TPU async pairs are -start/-done with mirrored operand/result
    tuples — all payload the inventory must count (and not double-
    count)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
    # combined (tuple-result) sync all-reduce: one member per operand
    combined = ("  %arc = (f32[64]{0}, f32[1024]{0}) "
                "all-reduce(f32[64]{0} %a, f32[1024]{0} %b), "
                "replica_groups={{0,2,4,6},{1,3,5,7}}, to_apply=%add")
    colls = commwatch.parse_hlo_collectives(combined, mesh)
    assert len(colls) == 1
    assert colls[0]["bytes"] == (64 + 1024) * 4
    assert colls[0]["axis"] == "dp"
    assert colls[0]["participants"] == 4
    # async -start: (operand, result) mirror counts ONCE; the -done
    # half is skipped entirely
    async_pair = (
        "  %all-reduce-start.1 = (f32[64]{0}, f32[64]{0}) "
        "all-reduce-start(f32[64]{0} %a), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
        "  %all-reduce-done.1 = f32[64]{0} all-reduce-done("
        "(f32[64]{0}, f32[64]{0}) %all-reduce-start.1), "
        "replica_groups={{0,1,2,3,4,5,6,7}}")
    colls = commwatch.parse_hlo_collectives(async_pair, mesh)
    assert len(colls) == 1
    assert colls[0]["bytes"] == 64 * 4
    assert colls[0]["axis"] == "dp+tp"
    # TPU layouts carry parens INSIDE the tuple ({0:T(256)} tiling) —
    # the tuple arm must not stop at the first ')'
    tiled = ("  %arc = (f32[64]{0:T(256)}, f32[1024]{0:T(256)}) "
             "all-reduce(f32[64]{0:T(256)} %a, f32[1024]{0:T(256)} %b)"
             ", replica_groups={{0,2,4,6},{1,3,5,7}}, to_apply=%add")
    colls = commwatch.parse_hlo_collectives(tiled, mesh)
    assert len(colls) == 1 and colls[0]["bytes"] == (64 + 1024) * 4
    # replica_groups={} = all devices of the program
    allrep = ("  %ar = f32[128]{0} all-reduce(f32[128]{0} %a), "
              "replica_groups={}, to_apply=%add")
    colls = commwatch.parse_hlo_collectives(allrep, mesh)
    assert len(colls) == 1
    assert colls[0]["participants"] == 8
    assert colls[0]["axis"] == "dp+tp"


def test_collective_broadcast_and_ragged_all_to_all_forms():
    """ISSUE 15 satellite: the parser used to SKIP collective-broadcast
    and the ragged all-to-all form entirely — both are first-class now
    (shared by the Level-4 spmd rules)."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
    cb = ("  %cb = f32[128,32]{1,0} collective-broadcast("
          "f32[128,32]{1,0} %x), channel_id=3, "
          "replica_groups={{0,2,4,6},{1,3,5,7}}")
    colls = commwatch.parse_hlo_collectives(cb, mesh)
    assert len(colls) == 1
    assert colls[0]["op"] == "broadcast"
    assert colls[0]["bytes"] == 128 * 32 * 4
    assert colls[0]["participants"] == 4
    assert colls[0]["axis"] == "dp"
    # ragged all-to-all: result is the dense (padded) output buffer;
    # the s64 offset/size operands are metadata, not payload
    rata = ("  %rata = f32[1024,64]{1,0} ragged-all-to-all("
            "f32[1024,64]{1,0} %in, f32[1024,64]{1,0} %outb, "
            "s64[8]{0} %io, s64[8]{0} %ss, s64[8]{0} %oo, "
            "s64[8]{0} %rs), replica_groups={{0,1,2,3,4,5,6,7}}")
    colls = commwatch.parse_hlo_collectives(rata, mesh)
    assert len(colls) == 1
    assert colls[0]["op"] == "all_to_all"
    assert colls[0]["bytes"] == 1024 * 64 * 4
    assert colls[0]["participants"] == 8
    assert colls[0]["axis"] == "dp+tp"
    # records carry the instruction name + result members (the spmd
    # implicit-allgather attribution consumes them)
    assert colls[0]["name"] == "rata"
    assert colls[0]["result"] == [("f32", (1024, 64))]


# ---------------------------------------------------------------------------
# wired sites: kvstore reduce + sharded step on the 8-device dryrun
# ---------------------------------------------------------------------------
def test_kvstore_grouped_reduce_records_comm():
    import jax
    from mxnet_tpu import nd
    ndev = min(4, len(jax.devices()))
    ctxs = [mx.Context("cpu", i) for i in range(ndev)]
    kv = mx.kvstore.create("device")
    names = ["a", "b"]
    values = []
    for k in names:
        reps = [nd.full((16, 4), 1.0, ctx=c) for c in ctxs]
        kv.init(k, reps[0])
        values.append(reps)
    with commwatch.exposed_region():        # the Trainer's marking
        kv.pushpull_list(names, values)
    values[0][0].wait_to_read()
    snap = telemetry.snapshot()
    key = 'mx_comm_bytes_total{axis="kv",op="allreduce"}'
    assert snap["counters"][key] == 2 * 16 * 4 * 4   # 2 keys x 256B
    assert snap["counters"][
        'mx_comm_exposed_seconds_total{axis="kv",op="allreduce"}'] > 0


def test_sharded_step_comm_bandwidth_on_dryrun_mesh(monkeypatch):
    """Single-process bandwidth accounting on the 8-device mesh: the
    GSPMD collectives of a dp x tp sharded step show nonzero bytes AND
    bandwidth, labeled with their mesh axes (ISSUE 6 acceptance)."""
    import jax
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import (MeshConfig, P, ShardedTrainStep,
                                    make_mesh)
    # the CPU mesh has no peak of its own: state one, or no mx_mfu
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e12")
    telemetry.refresh()
    net = nn.HybridSequential()
    # explicit prefix: the tp param_rule must match regardless of how
    # many Dense blocks earlier tests burned off the global name counter
    net.add(nn.Dense(32, activation="relu", prefix="cw_tp0_"),
            nn.Dense(10))
    net.initialize(init=mx.initializer.Xavier())
    net(nd.ones((2, 16)))
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    step = ShardedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh, lr=0.1,
        param_rules=[(r"cw_tp0.*weight", P("tp", None))],
        data_specs=[P("dp"), P("dp")])
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(8, 16).astype(np.float32))
    y = nd.array(rng.randint(0, 10, (8,)).astype(np.float32))
    for _ in range(3):
        loss = step.step(x, y)
    float(jax.device_get(loss))
    rows = commwatch.report()
    for axis in ("dp", "tp"):
        hit = [r for r in rows if axis in r["axis"].split("+")
               and r["bytes"] > 0 and r["algbw"] > 0]
        assert hit, (axis, rows)
    snap = telemetry.snapshot()
    assert snap["counters"].get("mx_executed_flops_total", 0) > 0
    assert snap["gauges"].get("mx_mfu", 0) > 0
    assert snap["steps"] == 3                 # mark_step wired
    # the warmup -> reset -> meter pattern (fleet_report/bert_bench):
    # reset clears the program inventories but the cached executable
    # must RE-register, not silently meter zeros
    telemetry.reset()
    for _ in range(2):
        loss = step.step(x, y)
    float(jax.device_get(loss))
    snap = telemetry.snapshot()
    assert snap["counters"].get("mx_executed_flops_total", 0) > 0
    assert snap["gauges"].get("mx_mfu", 0) > 0
    assert any(k.startswith("mx_comm_bytes_total")
               for k in snap["counters"])


# ---------------------------------------------------------------------------
# MFU / goodput meters
# ---------------------------------------------------------------------------
def test_mfu_gauge_on_known_flops_program(monkeypatch):
    """mx_mfu == executed FLOPs / wall / peak, with the FLOPs coming
    from the program's cost analysis (a 64x64 matmul: XLA reports
    2*64^3) and peak pinned via MXNET_PEAK_FLOPS."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e9")
    telemetry.refresh()
    w = compilewatch.watched_jit(lambda a: a @ a, "mm", "test")
    x = jnp.ones((64, 64), jnp.float32)
    t_lo0 = time.perf_counter()
    telemetry.mark_step()                      # meter window opens
    t_hi0 = time.perf_counter()
    n = 3
    for _ in range(n):
        jax.block_until_ready(w(x))
    t_lo1 = time.perf_counter()
    telemetry.mark_step()
    t_hi1 = time.perf_counter()
    snap = telemetry.snapshot()
    flops = snap["counters"]["mx_executed_flops_total"]
    np.testing.assert_allclose(flops, n * 2 * 64 ** 3)
    mfu = snap["gauges"]["mx_mfu"]
    lo = flops / (t_hi1 - t_lo0) / 1e9         # widest wall window
    hi = flops / max(1e-9, t_lo1 - t_hi0) / 1e9
    assert lo <= mfu <= hi, (lo, mfu, hi)
    assert telemetry.peak_flops() == 1e9


def test_goodput_debits_guard_skips():
    telemetry.mark_step()
    time.sleep(0.03)
    telemetry.mark_step(useful=False)          # guard-skipped step
    time.sleep(0.03)
    telemetry.mark_step()
    gp = telemetry.snapshot()["gauges"]["mx_goodput"]
    # one of two ~equal intervals was useless => goodput ~0.5
    assert 0.2 < gp < 0.8, gp


def test_goodput_debits_stalls():
    telemetry.mark_step()
    time.sleep(0.02)
    telemetry.debit_stall(0.015, kind="checkpoint")
    telemetry.mark_step()
    snap = telemetry.snapshot()
    assert snap["counters"][
        'mx_stall_seconds_total{kind="checkpoint"}'] == 0.015
    assert snap["gauges"]["mx_goodput"] < 0.6


# ---------------------------------------------------------------------------
# fleet layer
# ---------------------------------------------------------------------------
def test_fleet_snapshot_single_process():
    telemetry.mark_step()
    time.sleep(0.005)
    telemetry.mark_step()
    commwatch.record("allreduce", "dp", 512, 4, seconds=0.01,
                     exposed=True)
    view = telemetry.fleet_snapshot()
    assert view["nw"] == 1 and view["slowest"] == 0
    r0 = view["ranks"][0]
    assert r0["steps"] == 2 and r0["step_mean"] > 0
    assert r0["exposed_comm_seconds"] > 0
    assert r0["comm_bytes"] == 512
    snap = telemetry.snapshot()
    assert snap["gauges"]["mx_fleet_ranks"] == 1
    assert telemetry.fleet_last() is not None
    assert "fleet=" in telemetry.heartbeat_line()
    assert "mfu=" in telemetry.heartbeat_line()


def test_fleet_period_triggers_from_mark_step(monkeypatch):
    monkeypatch.setenv("MXNET_FLEET_SNAPSHOT_PERIOD", "2")
    for _ in range(4):
        telemetry.mark_step()
    assert telemetry.fleet_last() is not None
    assert telemetry.snapshot()["gauges"]["mx_fleet_ranks"] == 1


def test_allgather_floats_single_row():
    from mxnet_tpu import dist as dist_mod
    mat = dist_mod.allgather_floats([1.0, 2.5, 3.0])
    assert mat.shape == (1, 3)
    np.testing.assert_allclose(mat[0], [1.0, 2.5, 3.0])


def test_two_rank_fleet_merge_and_straggler_naming():
    """Multi-process acceptance (ISSUE 6): 2 ranks publish through the
    dist store, the merged view and the straggler warning NAME the
    injected slow rank."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TELEMETRY", None)
    env["FLEET_STEPS"] = "5"
    env["FLEET_SLOW_RANK"] = "1"
    env["MXNET_STRAGGLER_WARN"] = "0.2"
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--cpu-devices", "1",
         sys.executable, os.path.join(ROOT, "tools", "fleet_report.py"),
         "--worker"],
        env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert out.stdout.count("FLEET_WORKER_OK") == 2, out.stdout
    # the merged view names rank 1 as the straggler...
    assert "FLEET_STRAGGLER slowest=1" in out.stdout, out.stdout
    # ...and the MXNET_STRAGGLER_WARN warning fires naming it
    assert "straggler: rank 1" in out.stderr, (out.stdout, out.stderr)


# ---------------------------------------------------------------------------
# report surfaces
# ---------------------------------------------------------------------------
def test_report_and_render():
    commwatch.record("allreduce", "dp", 4096, 8, seconds=0.002,
                     exposed=True)
    commwatch.record("allgather", "tp", 2048, 2, seconds=0.001)
    rows = commwatch.report()
    by_key = {(r["op"], r["axis"]): r for r in rows}
    assert by_key[("allreduce", "dp")]["bytes"] == 4096
    assert by_key[("allreduce", "dp")]["exposed_s"] > 0
    assert by_key[("allgather", "tp")]["overlapped_s"] > 0
    text = commwatch.render_report(rows)
    assert "allreduce" in text and "dp" in text
    tot = commwatch.comm_totals()
    assert tot["bytes"] == 4096 + 2048
    assert tot["exposed_seconds"] > 0


def test_trace_summary_comm_table(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_summary
    events = [
        {"ph": "X", "name": "comm::allreduce", "cat": "comm",
         "ts": 0, "dur": 1000.0,
         "args": {"axis": "dp", "bytes": 4096, "exposed": True}},
        {"ph": "X", "name": "comm::allreduce", "cat": "comm",
         "ts": 2000, "dur": 500.0,
         "args": {"axis": "dp", "bytes": 4096, "exposed": False}},
    ]
    rows = trace_summary.summarize_comm(events)
    r = rows[("allreduce", "dp")]
    assert r["count"] == 2 and r["bytes"] == 8192
    assert r["exposed_us"] == 1000.0 and r["overlapped_us"] == 500.0
    text = trace_summary.render_comm(rows)
    assert "allreduce" in text
    # the comm spans the profiler actually writes parse the same way
    from mxnet_tpu import profiler
    profiler.set_state("run")
    with commwatch.comm_span("allreduce", "kv", 256, 4):
        time.sleep(0.001)
    profiler.set_state("stop")
    path = str(tmp_path / "t.json")
    profiler.set_config(filename=path)
    profiler.dump(reset=True)
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    rows = trace_summary.summarize_comm(evs)
    assert ("allreduce", "kv") in rows
