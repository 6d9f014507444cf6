#!/usr/bin/env python
"""Fleet observability report — per-rank step/comm/skew table + the
all-axes collective profile (ISSUE 6 acceptance tool).

A CPU tool: every mode pins JAX to the CPU platform (virtual devices).
Several modes import JAX in this process and then start replica or
worker processes — correct on the CPU only; on a chip the parent would
hold the device its children need. Nothing here reports a device number.

Single-process mode (default; runs under the 8-virtual-device CPU
dryrun in tier-1): drives a workload over EVERY mesh axis the stack
trains with — a dcn x dp x tp ShardedTrainStep (GSPMD-inserted
collectives, harvested from the compiled HLO), the hierarchical
dcn x dp grad sync, a pp=4 GPipe step, an ep=8 MoE layer and sp=4
ring attention (shard_map collectives, recorded at trace time) — then
prints commwatch's per-(op, axis) table and the fleet snapshot, and
GATES: every required axis (dcn dp tp sp pp ep) must show nonzero
bytes AND bandwidth, and the MFU/goodput gauges must be populated
from measured FLOPs x time.

Multi-rank mode: ``--ranks N`` relaunches this script as N processes
through tools/launch.py (env rendezvous, virtual CPU devices); each
worker runs a dist-kvstore trainer loop, publishes its stats through
``telemetry.fleet_snapshot()`` (ONE collective gather under the comm
deadline) and rank 0 prints the merged per-rank table with skew +
slowest-rank attribution. ``FLEET_SLOW_RANK=r`` injects a sleep into
rank r's loop so the straggler path can be exercised end-to-end:
the snapshot must NAME that rank (the 2-rank test in
tests/test_commwatch.py asserts it).

``--zero`` mode: drive the MXNET_ZERO sharded Trainer over a dcn x dp
hierarchy and gate that the per-axis table covers the RS/AG path —
reduce_scatter and allgather with nonzero bytes+bandwidth on both
tiers, the watched ``zero.step`` program executed every step, and the
``mx_zero_state_bytes`` shard gauges populated (ISSUE 8 satellite).

``--modelwatch`` mode (ISSUE 11 satellite): layer-health pass.
Single-process: drive the 8-virtual-device data-parallel Trainer with
MXNET_MODELWATCH=1, inject a ``scaled_grad`` fault late in the run,
print the per-layer health table and GATE that every layer's gauges
populated, the noise-scale meter read out, and the injected exploding
layer was NAMED by an anomaly event. With ``--ranks N --bad-rank r``:
each rank trains under modelwatch, rank r gets the injection, every
rank gathers (anomaly count, worst layer, per-layer norms) over ONE
dist.allgather_floats, and rank 0 prints the merged per-rank
layer-health table and gates that the bad layer is named WITH its
rank.

``--serve`` mode (ISSUE 12 satellite): serving pass. Drive the
8-virtual-device dryrun with a pjit-SHARDED InferenceSession (weights
device_put over the kvstore mesh) behind the continuous-batching
scheduler under a synthetic 3-tenant load, print the per-tenant SLO
table + bucket table + heartbeat serve section, and GATE: nonzero
per-tenant ok counters and latency histograms, the slowest tenant
NAMED (the deliberately full-batch tenant), the bucket table populated
and zero in-ladder bucket misses.

``--elastic`` mode (ISSUE 16 satellite): elastic-topology pass. One
training run on the 8-device dryrun survives the full preemption arc
— live shrink on a slice_preempt fault, live grow when capacity
returns, then a forced reshard failure degrading to
checkpoint-restore — and the gate checks the transition counters
(2 live / 1 restored, ZERO restarts on the live legs), the staged
fragment plans (nonzero programs + moved bytes) and the arxiv
2112.01075 planned-peak gauge.

``--serve-fleet`` mode (ISSUE 17 acceptance): resilient-serving pass.
Three REAL replica processes (spawned, checkpoint-loaded weights, one
deliberately slowed via env-armed replica_slow) behind the
health-gated Router under a mixed-tenant hedged load; one replica is
SIGKILLed mid-load and the fleet KV flapped once; then a queued burst
is drained away with a KV drain notice. GATES: zero dropped requests,
zero duplicate deliveries (counter identity ok == delivered +
hedge-cancelled + failover-discards), nonzero failover AND hedge
counters, the lease-expiry ejection of the killed replica recorded,
the KV flap degraded to last-known-good and recovered, the drained
replica exits 0 with zero client-visible errors, and the fleet table
NAMES the injected-slow replica as slowest.

Usage: python tools/fleet_report.py [--steps 6] [--json] [--no-gate]
       python tools/fleet_report.py --ranks 2 [--slow-rank 1]
       python tools/fleet_report.py --zero [--steps 6]
       python tools/fleet_report.py --modelwatch [--ranks N --bad-rank r]
       python tools/fleet_report.py --serve [--steps 6]
       python tools/fleet_report.py --elastic
       python tools/fleet_report.py --serve-fleet
Exit 0 = all axes present + meters populated (or --no-gate).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REQUIRED_AXES = ("dcn", "dp", "tp", "sp", "pp", "ep")


def _exercise_all_axes(steps: int):
    """Drive collectives over every mesh axis on the local devices."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import commwatch, gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import (MeshConfig, P, ShardedTrainStep,
                                    collectives, make_mesh,
                                    make_moe_layer, make_pipeline_step,
                                    ring_attention, shard_map)

    rng = np.random.RandomState(0)

    # --- dcn x dp x tp: GSPMD collectives from the compiled step ------
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize(init=mx.initializer.Xavier())
    net(nd.ones((2, 16)))
    mesh = make_mesh(MeshConfig(dcn=2, dp=2, tp=2))
    step = ShardedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh, lr=0.05,
        param_rules=[(r"dense0.*weight", P("tp", None))])
    x = nd.array(rng.rand(8, 16).astype(np.float32))
    y = nd.array(rng.randint(0, 8, (8,)).astype(np.float32))
    for _ in range(steps):
        loss = step.step(x, y)
    float(jax.device_get(loss))

    # --- hierarchical dcn x dp grad sync (named shard_map records;
    # the per-shard-input spelling of tests/test_parallel.py) ---------
    hmesh = make_mesh(MeshConfig(dcn=2, dp=4))
    spec = P(("dcn", "dp"))
    grads = {"w": jnp.asarray(rng.rand(8, 16, 8).astype(np.float32)),
             "b": jnp.asarray(rng.rand(8, 8).astype(np.float32))}
    sync = jax.jit(shard_map(
        lambda t: jax.tree_util.tree_map(
            lambda g: g[None],
            collectives.hierarchical_grad_sync(
                jax.tree_util.tree_map(lambda g: g[0], t),
                ici_axis="dp", dcn_axis="dcn")),
        mesh=hmesh, in_specs=(spec,), out_specs=spec))
    with commwatch.program_watch("hier_grad_sync"):
        jax.block_until_ready(sync(grads))
    with commwatch.program_watch("hier_grad_sync"):
        jax.block_until_ready(sync(grads))

    # --- pp=4 GPipe schedule ------------------------------------------
    pmesh = make_mesh(MeshConfig(pp=4))
    pstep = make_pipeline_step(
        lambda W, t: jnp.tanh(t @ W), pmesh, n_micro=2,
        loss_fn=lambda out, lab: jnp.mean((out - lab) ** 2), lr=0.05)
    Ws = jnp.asarray(rng.randn(4, 8, 8).astype(np.float32) * 0.3)
    px = jnp.asarray(rng.randn(2, 4, 8).astype(np.float32))
    py = jnp.asarray(rng.randn(2, 4, 8).astype(np.float32))
    with commwatch.program_watch("pipeline_step"):
        Ws, ploss = pstep(Ws, px, py)
        jax.block_until_ready(ploss)
    with commwatch.program_watch("pipeline_step"):
        jax.block_until_ready(pstep(Ws, px, py)[1])

    # --- ep=8 MoE dispatch/combine ------------------------------------
    emesh = make_mesh(MeshConfig(ep=8))
    apply_fn, params = make_moe_layer(emesh, d=4, d_hidden=8,
                                      capacity=8)
    ex = rng.randn(32, 4).astype(np.float32)
    with commwatch.program_watch("moe_layer"):
        jax.block_until_ready(apply_fn(params, ex))
    with commwatch.program_watch("moe_layer"):
        jax.block_until_ready(apply_fn(params, ex))

    # --- sp=4 ring attention ------------------------------------------
    smesh = make_mesh(MeshConfig(sp=4))
    q = jnp.asarray(rng.randn(2, 16, 2, 4).astype(np.float32))
    ring = jax.jit(shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp"),
        mesh=smesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp")))
    with commwatch.program_watch("ring_attention"):
        jax.block_until_ready(ring(q, q, q))
    with commwatch.program_watch("ring_attention"):
        jax.block_until_ready(ring(q, q, q))


def run_zero(args) -> int:
    """--zero: drive the ZeRO-sharded Trainer (MXNET_ZERO=1, dcn=2
    hierarchy on the 8-device dryrun) and gate that the RS/AG path is
    covered by the per-axis bytes table: reduce_scatter AND allgather
    must show nonzero bytes+bandwidth on BOTH the dp and dcn axes, the
    watched zero.step program must have executed every step, and the
    shard-state gauges must be populated."""
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_ZERO"] = "1"
    os.environ.setdefault("MXNET_ZERO_DCN", "2")
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, commwatch, gluon, nd, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon import zero as zero_mod
    telemetry.refresh()
    assert telemetry.enabled() and commwatch.enabled()

    ndev = min(8, jax.device_count())
    ctxs = [mx.tpu(i) for i in range(ndev)]
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, in_units=32, activation="relu"), nn.Dense(8))
    net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
    net(nd.ones((2, 32), ctx=ctxs[0]))
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01}, kvstore="device")
    rng = np.random.RandomState(1)
    for _ in range(args.steps):
        xs = gluon.utils.split_and_load(
            nd.array(rng.rand(2 * ndev, 32).astype(np.float32)), ctxs)
        ys = gluon.utils.split_and_load(
            nd.array(rng.rand(2 * ndev, 8).astype(np.float32)), ctxs)
        with autograd.record():
            losses = [((net(x) - y) ** 2).sum()
                      for x, y in zip(xs, ys)]
        for l in losses:
            l.backward()
        tr.step(2 * ndev)

    rows = commwatch.report()
    snap = telemetry.snapshot()
    if args.json:
        print(json.dumps({"comm": rows,
                          "gauges": {k: v for k, v in
                                     snap["gauges"].items()
                                     if "zero" in k}}, default=str))
    else:
        print(commwatch.render_report(rows))

    problems = []
    if not isinstance(tr._zero, zero_mod.ZeroEngine):
        problems.append("MXNET_ZERO=1 but the Trainer fell back to the "
                        "replicated path")
    want_axes = ("dp", "dcn") if (tr._zero and tr._zero._n_dcn > 1) \
        else ("dp",)
    for op in ("reduce_scatter", "allgather"):
        for axis in want_axes:
            hits = [r for r in rows
                    if r["op"] == op and r["axis"] == axis
                    and r["bytes"] > 0
                    and (r["algbw"] > 0 or r["busbw"] > 0)]
            if not hits:
                problems.append("%s on axis %r: no nonzero "
                                "bytes+bandwidth" % (op, axis))
    if commwatch.program_execs("zero.step") != args.steps:
        problems.append("zero.step executed %d times, expected %d"
                        % (commwatch.program_execs("zero.step"),
                           args.steps))
    if not any(k.startswith("mx_zero_state_bytes")
               for k in snap["gauges"]):
        problems.append("mx_zero_state_bytes gauges not populated")

    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("ZERO_REPORT_OK")
    return 0


def run_elastic(args) -> int:
    """--elastic (ISSUE 16): elastic-topology pass. One training run
    on the 8-virtual-device dryrun survives a full preemption arc —
    slice_preempt fault -> LIVE shrink to the front half, capacity
    returns -> live grow back, then a forced reshard failure ->
    degradation to checkpoint-restore — and the report gates that the
    arc really took the paths it claims: two live transitions with
    ZERO restarts, exactly one restored transition, the staged
    fragment plans moved real bytes under the 2112.01075 peak bound,
    and training state stayed finite throughout."""
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_ZERO"] = "1"
    os.environ["MXNET_ELASTIC"] = "1"
    os.environ["MXNET_ELASTIC_POLL"] = "1"
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile
    import shutil
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import elastic, faultinject, gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon import zero as zero_mod
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    telemetry.refresh()
    assert telemetry.enabled()
    if jax.device_count() < 8:
        print("SKIP: only %d devices" % jax.device_count())
        return 0

    ctxs = [mx.tpu(i) for i in range(8)]
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, in_units=32, activation="relu"), nn.Dense(8))
    net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01, "momentum": 0.9},
                       kvstore="device")
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[mx.metric.MSE()], trainer=tr,
                    context=ctxs)
    rng = np.random.RandomState(5)
    X = rng.rand(64, 32).astype(np.float32)
    Y = rng.rand(64, 8).astype(np.float32)
    loader = gluon.data.DataLoader(
        gluon.data.ArrayDataset(X, Y), batch_size=8)

    live = telemetry.counter("mx_elastic_transitions_total",
                             kind="live")
    failed = telemetry.counter("mx_elastic_transitions_total",
                               kind="live_failed")
    restored = telemetry.counter("mx_elastic_transitions_total",
                                 kind="restored")
    frags = telemetry.counter("mx_reshard_transitions_total",
                              kind="zero.state")
    moved = telemetry.counter("mx_reshard_moved_bytes_total",
                              kind="zero.state")
    base = {"live": live.get(), "failed": failed.get(),
            "restored": restored.get(), "frags": frags.get(),
            "moved": moved.get()}

    workdir = tempfile.mkdtemp(prefix="mx-fleet-elastic-")
    prefix = os.path.join(workdir, "el")
    arc = []
    try:
        est.fit(loader, epochs=1, ckpt_prefix=prefix)
        # 1) preemption notice mid-run -> live shrink to the front half
        faultinject.set_fault("slice_preempt", 1.0, max_fires=1)
        est.fit(loader, epochs=2, ckpt_prefix=prefix, resume=True)
        arc.append(("shrink 8->4 (slice_preempt)",
                    len(tr._contexts), live.get() - base["live"]))
        shrunk = len(tr._contexts)
        # 2) capacity came back -> live grow
        elastic.request_preemption(8)
        est.fit(loader, epochs=3, ckpt_prefix=prefix, resume=True)
        arc.append(("grow 4->8 (capacity returned)",
                    len(tr._contexts), live.get() - base["live"]))
        grown = len(tr._contexts)
        # 3) forced reshard failure -> degrade to checkpoint-restore
        faultinject.set_fault("reshard_fail", 1.0, max_fires=1)
        elastic.request_preemption(4)
        est.fit(loader, epochs=4, ckpt_prefix=prefix, resume=True)
        arc.append(("shrink 8->4 (reshard_fail -> restore)",
                    len(tr._contexts),
                    restored.get() - base["restored"]))
        final = len(tr._contexts)
    finally:
        faultinject.reset()
        elastic.clear()
        shutil.rmtree(workdir, ignore_errors=True)

    d_live = live.get() - base["live"]
    d_failed = failed.get() - base["failed"]
    d_restored = restored.get() - base["restored"]
    d_frags = frags.get() - base["frags"]
    d_moved = moved.get() - base["moved"]
    peak = telemetry.gauge("mx_reshard_planned_peak_bytes",
                           kind="zero.state").get()
    blk = telemetry.gauge("mx_reshard_block_bytes",
                          kind="zero.state").get()
    finite = all(np.isfinite(p.list_data()[0].asnumpy()).all()
                 for p in tr._params)
    view = {
        "transitions": {"live": d_live, "live_failed": d_failed,
                        "restored": d_restored},
        "fragment_programs": d_frags,
        "moved_bytes": d_moved,
        "planned_peak_bytes": peak,
        "block_bytes": blk,
        "final_devices": final,
        "params_finite": finite,
    }
    if args.json:
        print(json.dumps({"elastic": view, "arc": arc}))
    else:
        print("elastic arc (8-device dryrun, MXNET_ZERO=1):")
        for label, ndev_now, cnt in arc:
            print("  %-38s -> %d devices (counter %d)"
                  % (label, ndev_now, cnt))
        print("  transitions: live=%d live_failed=%d restored=%d"
              % (d_live, d_failed, d_restored))
        print("  fragment plans: %d programs, %d bytes moved, "
              "planned peak %s B (block %s B)"
              % (d_frags, d_moved, peak, blk))

    problems = []
    if not isinstance(tr._zero, zero_mod.ZeroEngine):
        problems.append("MXNET_ZERO=1 but the Trainer fell back to "
                        "the replicated path")
    if shrunk != 4 or grown != 8 or final != 4:
        problems.append("arc device counts off: shrink=%d grow=%d "
                        "final=%d (want 4/8/4)"
                        % (shrunk, grown, final))
    if d_live != 2:
        problems.append("expected 2 LIVE transitions (shrink+grow), "
                        "got %d" % d_live)
    if d_failed != 1 or d_restored != 1:
        problems.append("degradation arc off: live_failed=%d "
                        "restored=%d (want 1/1)"
                        % (d_failed, d_restored))
    if d_frags <= 0 or d_moved <= 0:
        problems.append("no staged fragment programs executed "
                        "(programs=%d moved=%d)" % (d_frags, d_moved))
    # 2112.01075: planned peak = dst shard + ONE staged block, so it
    # can never exceed the whole moved payload plus one block
    if not peak or not blk or peak > d_moved + blk:
        problems.append("2112.01075 peak gauge not plausible: "
                        "peak=%s block=%s moved=%d"
                        % (peak, blk, d_moved))
    if not finite:
        problems.append("non-finite parameter after the arc")

    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("ELASTIC_REPORT_OK")
    return 0


def run_quant(args) -> int:
    """--quant (ISSUE 13 satellite): quantized-collectives pass on the
    8-virtual-device dryrun. Three sub-passes, each metered in its own
    commwatch window:

    1. FLAT dp tier (MXNET_ZERO=1, no dcn, MXNET_KVSTORE_QUANTIZE=
       int8): the dp tier must show nonzero int8 bytes — the wire
       really carries 1-byte payload.
    2. STAGED dcn x dp tier (MXNET_ZERO_DCN=2, default
       MXNET_KVSTORE_QUANTIZE_TIER=dcn): int8 bytes ONLY on the dcn
       tier; every dp (ICI) payload row stays f32 — tiers outside
       QUANTIZE_TIER are untouched.
    3. CONVERGENCE: 20 SGD steps of a bert_tiny MLM-style head on the
       flat data-parallel Trainer, quantized-with-EF final loss within
       2% of the f32 run.
    """
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ.setdefault("MXNET_COMPILE_WARN_N", "0")
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, commwatch, gluon, nd, telemetry
    from mxnet_tpu.gluon import nn
    telemetry.refresh()
    assert telemetry.enabled() and commwatch.enabled()
    ndev = min(8, jax.device_count())
    ctxs = [mx.tpu(i) for i in range(ndev)]
    problems = []

    def zero_pass(dcn):
        telemetry.reset()
        commwatch.reset()
        os.environ["MXNET_ZERO"] = "1"
        os.environ["MXNET_ZERO_DCN"] = str(dcn)
        os.environ["MXNET_KVSTORE_QUANTIZE"] = "int8"
        from mxnet_tpu.gluon import zero as zero_mod
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(64, in_units=32, activation="relu"),
                nn.Dense(8))
        net.initialize(ctx=ctxs, init=mx.initializer.Xavier())
        net(nd.ones((2, 32), ctx=ctxs[0]))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore="device")
        rng = np.random.RandomState(1)
        for _ in range(args.steps):
            xs = gluon.utils.split_and_load(
                nd.array(rng.rand(2 * ndev, 32).astype(np.float32)),
                ctxs)
            ys = gluon.utils.split_and_load(
                nd.array(rng.rand(2 * ndev, 8).astype(np.float32)),
                ctxs)
            with autograd.record():
                losses = [((net(x) - y) ** 2).sum()
                          for x, y in zip(xs, ys)]
            for l in losses:
                l.backward()
            tr.step(2 * ndev)
        assert isinstance(tr._zero, zero_mod.ZeroEngine), \
            "MXNET_ZERO=1 fell back to the replicated path"
        return commwatch.report()

    # --- 1: flat dp tier quantizes --------------------------------
    rows = zero_pass(0)
    print("== flat dp tier (MXNET_KVSTORE_QUANTIZE=int8) ==")
    print(commwatch.render_report(rows))
    int8_dp = [r for r in rows if r["axis"] == "dp"
               and r["dtype"] == "int8" and r["bytes"] > 0]
    if not int8_dp:
        problems.append("flat pass: no nonzero int8 bytes on the dp "
                        "tier")

    # --- 2: staged — only the dcn tier quantizes ------------------
    rows = zero_pass(2)
    print("\n== staged dcn x dp, MXNET_KVSTORE_QUANTIZE_TIER=dcn ==")
    print(commwatch.render_report(rows))
    int8_axes = {r["axis"] for r in rows
                 if r["dtype"] == "int8" and r["bytes"] > 0}
    if int8_axes != {"dcn"}:
        problems.append("staged pass: int8 bytes on axes %s (expected "
                        "only 'dcn' under TIER=dcn)" % (int8_axes,))
    dp_f32 = [r for r in rows if r["axis"] == "dp"
              and r["dtype"] == "f32" and r["bytes"] > 0]
    if not dp_f32:
        problems.append("staged pass: dp (ICI) tier lost its f32 "
                        "payload rows")

    # --- 3: bert_tiny 20-step convergence -------------------------
    os.environ["MXNET_ZERO"] = "0"
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel

    def bert_loss_run(mode):
        os.environ["MXNET_KVSTORE_QUANTIZE"] = mode
        mx.random.seed(11)
        np.random.seed(11)
        net = nn.HybridSequential()
        with net.name_scope():
            bert = BERTModel(num_layers=2, units=32, hidden_size=64,
                             num_heads=4, max_length=32,
                             vocab_size=100, dropout=0.0)
        head = nn.Dense(16, in_units=32)
        net.add(bert)
        bert.initialize(ctx=ctxs, init=mx.initializer.Xavier())
        head.initialize(ctx=ctxs, init=mx.initializer.Xavier())
        params = {**bert.collect_params(), **head.collect_params()}
        tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.05},
                           kvstore="device")
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        rng = np.random.RandomState(12)
        batch, seq = 2 * ndev, 12
        ids = rng.randint(0, 100, (batch, seq)).astype(np.float32)
        tt = np.zeros((batch, seq), np.float32)
        lab = rng.randint(0, 16, (batch,)).astype(np.float32)
        last = None
        for _ in range(20):
            xs = gluon.utils.split_and_load(nd.array(ids), ctxs)
            ts = gluon.utils.split_and_load(nd.array(tt), ctxs)
            ys = gluon.utils.split_and_load(nd.array(lab), ctxs)
            with autograd.record():
                losses = []
                for x, t, y in zip(xs, ts, ys):
                    seq_out = bert(x, t)[0]
                    logits = head(seq_out.mean(axis=1))
                    losses.append(loss_fn(logits, y).mean())
            for l in losses:
                l.backward()
            tr.step(batch)
            last = float(np.mean([l.asnumpy().item()
                                  for l in losses]))
        return last

    loss_q = bert_loss_run("int8")
    loss_f = bert_loss_run("off")
    rel = abs(loss_q - loss_f) / max(abs(loss_f), 1e-9)
    print("\nbert_tiny 20-step SGD: f32 loss %.5f, int8+EF loss %.5f "
          "(rel diff %.4f, bound 0.02)" % (loss_f, loss_q, rel))
    if rel > 0.02:
        problems.append("bert_tiny convergence: quantized final loss "
                        "%.5f vs f32 %.5f (rel %.4f > 0.02)"
                        % (loss_q, loss_f, rel))

    if args.json:
        print(json.dumps({"loss_f32": loss_f, "loss_int8": loss_q,
                          "rel": rel, "problems": problems}))
    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("QUANT_REPORT_OK")
    return 0


def _mw_trainer_loop(steps, inject_after=None, seed_rank=0):
    """A seeded multi-device data-parallel trainer loop under
    MXNET_MODELWATCH; arms scaled_grad after `inject_after` steps.
    Returns (trainer, layer names)."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, faultinject, gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.utils import split_and_load

    ndev = min(8, len(jax.local_devices()))
    ctxs = [mx.Context("cpu", i) if jax.local_devices()[0].platform == "cpu"
            else mx.tpu(i) for i in range(ndev)]
    mx.random.seed(0)                      # identical layers on every rank
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16, activation="relu"), nn.Dense(8))
    net.initialize(init=mx.initializer.Xavier(), ctx=ctxs)
    net(nd.ones((2, 16), ctx=ctxs[0]))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore="device")
    rng = np.random.RandomState(1 + seed_rank)
    batch = 4 * ndev
    for i in range(steps):
        if inject_after is not None and i == inject_after:
            faultinject.set_fault("scaled_grad", 1.0, max_fires=2)
        xs = split_and_load(nd.array(
            rng.rand(batch, 16).astype(np.float32)), ctxs)
        ys = split_and_load(nd.array(
            rng.rand(batch, 8).astype(np.float32)), ctxs)
        with autograd.record():
            losses = [((net(x) - y) ** 2).sum() for x, y in zip(xs, ys)]
        for l in losses:
            l.backward()
        tr.step(batch)
    faultinject.clear("scaled_grad")
    mw = tr.modelwatch
    return tr, (mw.last or {}).get("names", [])


def _print_layer_table(names, entry):
    print("%-24s %12s %12s %12s" % ("layer", "grad_norm", "param_norm",
                                    "upd_ratio"))
    for i, name in enumerate(names):
        r = entry["update_ratios"][i]
        print("%-24s %12.4g %12.4g %12s"
              % (name, entry["grad_norms"][i], entry["param_norms"][i],
                 ("%.3g" % r) if r is not None else "-"))


def run_modelwatch_single(args) -> int:
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_MODELWATCH"] = "1"
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu import modelwatch, telemetry
    telemetry.refresh()
    assert telemetry.enabled() and modelwatch.enabled()

    steps = max(args.steps, 14)            # enough z-score history
    tr, names = _mw_trainer_loop(steps, inject_after=steps - 2)
    mw = tr.modelwatch
    snap = telemetry.snapshot()

    if args.json:
        print(json.dumps({"last": mw.last, "stats": mw.stats(),
                          "anomalies": modelwatch.recent_anomalies()},
                         default=str))
    else:
        _print_layer_table(names, mw.last)
        print("\nmeters: noise_scale=%s suggest_batch=%s anomalies=%d"
              % (mw.noise_scale, mw.suggested_batch(), mw.anomalies))

    problems = []
    for name in names:
        for g in ("mx_layer_grad_norm", "mx_layer_param_norm",
                  "mx_layer_update_ratio"):
            if '%s{param="%s"}' % (g, name) not in snap["gauges"]:
                problems.append("%s not populated for %s" % (g, name))
    if not snap["gauges"].get("mx_grad_noise_scale", 0) > 0:
        problems.append("mx_grad_noise_scale not populated "
                        "(dp=%d replicas)" % len(tr._contexts))
    injected = names[-1] if names else "?"
    named = [a for a in modelwatch.recent_anomalies()
             if a["kind"] == "exploding" and a["param"] == injected]
    if not named:
        problems.append("injected scaled_grad layer %r was not named "
                        "by an anomaly event" % injected)
    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("MODELWATCH_REPORT_OK")
    return 0


def run_modelwatch_worker() -> int:
    """One rank of the multi-process layer-health pass: train under
    modelwatch (rank FLEET_BAD_RANK gets the scaled_grad injection),
    gather every rank's (anomaly count, worst layer, per-layer norms)
    in ONE dist.allgather_floats, and let rank 0 print the merged
    table and gate that the injected layer is named with its rank."""
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_MODELWATCH"] = "1"
    from mxnet_tpu import dist as dist_mod
    from mxnet_tpu import modelwatch, telemetry
    telemetry.refresh()
    dist_mod.initialize()
    rank = dist_mod.rank()
    bad = os.environ.get("FLEET_BAD_RANK")
    bad = int(bad) if bad not in (None, "") else None
    steps = int(os.environ.get("FLEET_STEPS", "16"))
    steps = max(steps, 14)

    tr, names = _mw_trainer_loop(
        steps, inject_after=(steps - 2) if rank == bad else None,
        seed_rank=rank)
    mw = tr.modelwatch
    mine = modelwatch.recent_anomalies()
    # attribute to the FIRST layer that fired (earliest step, then
    # highest z): the injected layer explodes one step before its huge
    # update cascades into every other layer's gradients
    worst_idx, worst_z = -1.0, 0.0
    first_step = None
    for a in mine:
        z = float(a.get("z", 0.0))
        if a["kind"] != "exploding" or a["param"] not in names:
            continue
        step = a.get("step", 0)
        if first_step is None or step < first_step \
                or (step == first_step and z > worst_z):
            first_step = step
            worst_z, worst_idx = z, float(names.index(a["param"]))
    last = mw.last or {}
    gnorms = [float(g) for g in last.get("grad_norms", [0.0] * len(names))]
    vec = [float(len(mine)), worst_idx, worst_z] + gnorms
    mat = dist_mod.allgather_floats(vec, tag="modelwatch-fleet")
    print("MW_WORKER_OK rank=%d anomalies=%d" % (rank, len(mine)),
          flush=True)
    if rank != 0:
        return 0

    print("\nper-rank layer health (%d ranks):" % len(mat))
    print("%-5s %10s %-24s %10s" % ("rank", "anomalies", "worst_layer",
                                    "worst_z"))
    detected_rank, detected_layer = None, None
    best = 0.0
    for r, row in enumerate(mat):
        count, widx, wz = float(row[0]), int(row[1]), float(row[2])
        layer = names[widx] if 0 <= widx < len(names) else "-"
        print("%-5s %10d %-24s %10.3g" % ("r%d" % r, int(count), layer,
                                          wz))
        if wz > best:
            best, detected_rank, detected_layer = wz, r, layer
    if bad is not None:
        injected = names[-1] if names else "?"
        if detected_rank != bad or detected_layer != injected:
            print("MW_FLEET_FAIL: expected rank %d layer %r, detected "
                  "rank %s layer %r" % (bad, injected, detected_rank,
                                        detected_layer))
            return 1
        print("MW_FLEET_BAD rank=%d layer=%s" % (detected_rank,
                                                 detected_layer))
    print("MW_FLEET_OK")
    return 0


def run_modelwatch_launcher(args) -> int:
    import subprocess
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["FLEET_STEPS"] = str(max(args.steps, 16))
    env["FLEET_MODELWATCH"] = "1"
    if args.bad_rank is not None:
        env["FLEET_BAD_RANK"] = str(args.bad_rank)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", str(args.ranks), "--cpu-devices", "2",
         sys.executable, os.path.abspath(__file__), "--worker"],
        env=env, capture_output=True, text=True, timeout=300)
    sys.stdout.write(out.stdout)
    sys.stderr.write(out.stderr)
    ok = (out.returncode == 0
          and out.stdout.count("MW_WORKER_OK") == args.ranks
          and "MW_FLEET_OK" in out.stdout)
    if not ok:
        print("FAIL: modelwatch fleet workers did not all complete")
        return 1
    print("MODELWATCH_REPORT_OK")
    return 0


def run_serve(args) -> int:
    """--serve (ISSUE 12 satellite): drive the 8-virtual-device dryrun
    with a pjit-SHARDED InferenceSession behind the continuous-batching
    scheduler under a synthetic 3-tenant load (one tenant deliberately
    sends full-batch requests — the expected slowest), print the
    per-tenant SLO table + bucket table + heartbeat serve section, and
    GATE: every tenant's ok-counter nonzero, p50/p99 histograms
    populated, the slowest tenant NAMED (and it is the batch tenant),
    the bucket table populated with steady-state hits, zero bucket
    misses, and the weights actually mesh-resident."""
    os.environ["MXNET_TELEMETRY"] = "1"
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serve, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.kvstore import device_mesh
    from mxnet_tpu.serve import tenancy
    telemetry.refresh()
    assert telemetry.enabled()

    devs = jax.devices()[:8]
    if len(devs) < 8:
        print("FAIL: needs the 8-device dryrun mesh")
        return 1
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, in_units=32, flatten=False, activation="relu"),
            nn.Dense(16, flatten=False))
    net.initialize(init=mx.initializer.Xavier())
    x_ex = nd.ones((2, 16, 32))
    # the pjit pattern (SNIPPETS.md [3]): weights device_put with their
    # NamedSharding over the kvstore mesh, jax.jit partitions the
    # serve program — the dense weights shard over the model axis
    mesh = device_mesh(devs, ("mp",))
    sess = net.serve_session(
        x_ex, max_batch=8, seq_axis=1, max_seq=16, mesh=mesh,
        param_specs=[(r".*dense0.*weight", P("mp", None)),
                     (r".*dense1.*weight", P("mp", None))])
    sess.warmup()
    # 'batch' is built to be the slowest on purpose: lowest admission
    # weight AND full-bucket requests — the gate checks the SLO table
    # actually names it
    tenants = [serve.TenantConfig("free", weight=2, deadline_ms=60000),
               serve.TenantConfig("paid", weight=4, deadline_ms=60000),
               serve.TenantConfig("batch", weight=0.5)]
    sched = serve.Scheduler(sess, tenants=tenants)

    rng = np.random.RandomState(1)
    futs = []
    for i in range(max(30, args.steps * 6)):
        if i % 5 == 4:
            # the batch tenant ships full-bucket requests: the most
            # compute per request -> the expected worst p99
            x = rng.rand(8, 16, 32).astype(np.float32)
            futs.append(sched.submit(x, tenant="batch"))
        else:
            b = int(rng.randint(1, 3))
            s = int(rng.randint(4, 17))
            x = rng.rand(b, s, 32).astype(np.float32)
            futs.append(sched.submit(
                x, tenant="paid" if i % 3 else "free"))
    for f in futs:
        f.result(120)
    sched.close()

    rows = tenancy.slo_report(tenants)
    table = sess.bucket_table()
    if args.json:
        print(json.dumps({"tenants": rows, "buckets": table},
                         default=str))
    else:
        print(tenancy.render_slo_report(rows))
        print("\n%-8s %8s %8s %8s" % ("bucket", "warmed", "hits",
                                      "misses"))
        for r in table:
            print("%-8s %8s %8d %8d" % (r["bucket"], r["warmed"],
                                        r["hits"], r["misses"]))
        print("\n" + telemetry.heartbeat_line())

    problems = []
    for t in ("free", "paid", "batch"):
        r = next((r for r in rows if r["tenant"] == t), None)
        if r is None or r["by_code"]["ok"] <= 0:
            problems.append("tenant %r: no ok requests counted" % t)
        elif r["p99_ms"] <= 0 or r["p50_ms"] <= 0:
            problems.append("tenant %r: latency histogram not "
                            "populated" % t)
    if rows and rows[0]["tenant"] != "batch":
        problems.append("slowest tenant named %r, expected the "
                        "full-batch tenant 'batch'" % rows[0]["tenant"])
    if not any(r["hits"] > 0 for r in table):
        problems.append("bucket table has no steady-state hits")
    if sess.bucket_misses() > 0:
        problems.append("%d bucket miss(es) inside the ladder"
                        % sess.bucket_misses())
    shardings = [w.sharding for w in sess._sharded_params]
    if not any(len(s.device_set) == 8 for s in shardings):
        problems.append("no parameter is sharded over the 8-device "
                        "mesh (pjit path not engaged)")

    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("SERVE_REPORT_OK")
    return 0


def _trace_assembly_phase(net, x, ref):
    """The --serve-fleet distributed-tracing gate (ISSUE 18). Returns
    (problems, rendered critical-path table or None).

    Orchestration: four in-process replicas share one REAL scheduler
    (so replica-side spans carry scheduler batch + engine execute),
    every request slow-armed to ~50ms so hedges genuinely launch, and
    replica_crash armed for exactly TWO fires. The one traced hedged
    request then plays out two rounds: round 1's primary and hedge
    both compute and crash before replying (failed attempts), the
    failover round's primary wins while its hedge is superseded
    (cancelled loser). breaker_fails=1 makes round 2 deterministic —
    one conn error opens a crashed replica's breaker, so the retry
    never re-picks a dead endpoint whose lease has not expired yet."""
    import time
    import numpy as np
    from mxnet_tpu import dist, faultinject, nd, serve, tracing
    from mxnet_tpu.serve import fleet

    problems = []
    table = None
    tracing.enable(True, sample=1.0)
    faultinject.clear()
    kv = dist.KV(dist.LocalKV())
    sess = net.serve_session(nd.array(x), max_batch=8)
    sess.warmup()
    sched = serve.Scheduler(sess, max_wait_ms=0, inflight=4)
    reps = [fleet.ReplicaServer(sched, "t%d" % i, kv=kv,
                                heartbeat_s=0.05, miss_k=3,
                                slow_s=0.05) for i in range(4)]
    router = fleet.Router(kv=kv, heartbeat_s=0.05, miss_k=3,
                          retries=4, breaker_fails=1,
                          breaker_ms=60000)
    router.refresh()
    try:
        t_dead = time.time() + 60
        while time.time() < t_dead:
            live = sum(1 for r in router.table()["replicas"].values()
                       if r["alive"])
            if live >= 4:
                break
            time.sleep(0.02)
            router.refresh()
        else:
            return (["trace phase: 4 in-proc replicas never became "
                     "routable"], None)
        # warm the serve path end-to-end before arming any fault
        if not np.allclose(router.infer(x), ref, atol=1e-5):
            return (["trace phase: warm output diverges from the "
                     "reference"], None)

        faultinject.set_fault("replica_slow", 1.0)
        faultinject.set_fault("replica_crash", 1.0, max_fires=2)
        fut = router.submit(x, hedge_ms=20)
        out = fut.result(30)
        if not np.allclose(out, ref, atol=1e-5):
            problems.append("trace phase: traced output diverges from "
                            "the reference")
        # the root span lands when the driver thread finishes; the
        # loser's attempt span when its superseded reply drains
        trace = None
        t_dead = time.time() + 10
        while time.time() < t_dead:
            trace = router.trace(fut.id)
            if trace is not None and trace["complete"] and any(
                    s["cat"] == "attempt"
                    and (s.get("args") or {}).get("outcome")
                    == "superseded" for s in trace["spans"]):
                break
            time.sleep(0.05)
        if trace is None or not trace["complete"]:
            return (problems + ["trace phase: no assembled trace for "
                                "request %s" % fut.id], None)

        spans = trace["spans"]
        atts = [s for s in spans if s["cat"] == "attempt"]
        failed = [s for s in atts
                  if (s.get("args") or {}).get("outcome")
                  not in ("ok", "superseded")]
        lost = [s for s in atts
                if (s.get("args") or {}).get("outcome") == "superseded"]
        won = [s for s in atts
               if (s.get("args") or {}).get("outcome") == "ok"]
        if not failed or not all((s["args"].get("replica")
                                  and s["args"].get("error"))
                                 for s in failed):
            problems.append("trace phase: no failed attempt span with "
                            "replica id + error (attempts: %r)"
                            % [(s["args"].get("kind"),
                                s["args"].get("outcome"))
                               for s in atts])
        if not lost:
            problems.append("trace phase: no cancelled (superseded) "
                            "hedge-loser attempt in the trace")
        if not won:
            problems.append("trace phase: no winning attempt in the "
                            "trace")
        cats = {s["cat"] for s in spans}
        if "sched" not in cats or "engine" not in cats:
            problems.append("trace phase: replica-side scheduler batch "
                            "+ engine execute spans missing (cats: %s)"
                            % sorted(cats))
        bd = router.explain(fut.id)
        if bd is None or bd["dominant"] == "none":
            problems.append("trace phase: critical-path breakdown "
                            "names no dominant phase")
        else:
            table = tracing.render_critical_path(bd, trace["trace_id"])
    except Exception as e:
        problems.append("trace phase: %s: %s" % (type(e).__name__, e))
    finally:
        faultinject.clear()
        router.close()
        for r in reps:
            r.close()
        sched.close()
        tracing.refresh()
        tracing.reset()
    return (problems, table)


def run_serve_fleet(args) -> int:
    """--serve-fleet (ISSUE 17 acceptance): the resilient-serving pass.

    Three REAL replica processes join the fleet KV, load their weights
    from a published checkpoint, and serve a mixed-tenant hedged load
    through the health-gated Router. Mid-load one replica is SIGKILLed
    and the fleet KV flapped once; afterwards a queued burst is drained
    off a second replica with the KV drain notice. One replica is
    deliberately slowed (env-armed replica_slow in the child) so the
    NAMED-slowest gate is deterministic. GATES: zero dropped requests
    (every future delivers the reference output), zero duplicate
    deliveries (counter identity: ok-coded wire replies == client
    deliveries + hedge cancellations + failover discards), nonzero
    failover AND hedge counters, the killed replica ejected on lease
    expiry, the KV flap counted and recovered from (last-known-good
    table, stale flag cleared), the drained replica exits 0 with zero
    client-visible drain sheds, and fleet_table() names the slow
    replica slowest.

    ISSUE 18 adds a distributed-tracing phase: with MXNET_TRACE on at
    sample 1.0, one hedged request rides through a replica_crash
    double-failure (both attempts of the first hedged round crash
    after compute) into a clean hedged round — and must assemble into
    ONE trace containing the failed attempt(s) with replica id and
    error, the cancelled hedge loser, and the winning attempt whose
    replica-side spans include the scheduler batch and engine
    execute; the critical-path table must name the dominant phase."""
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile
    import threading
    import time
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import faultinject, model, nd, serve, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serve import fleet
    telemetry.refresh()
    assert telemetry.enabled()
    faultinject.clear()

    # -- published checkpoint + reference output ----------------------
    prefix = os.path.join(tempfile.mkdtemp(prefix="mx_fleet_report_"),
                          "ck")
    mx.random.seed(7)
    # demo_factory's fixed prefix — the checkpoint must carry the
    # exact names the replica processes look up
    net = nn.HybridSequential(prefix="fleetrep_")
    with net.name_scope():
        net.add(nn.Dense(16, in_units=8, activation="relu"),
                nn.Dense(4, in_units=16))
    net.initialize(init=mx.initializer.Xavier())
    params = {k: p.data() for k, p in net.collect_params().items()}
    model.save_checkpoint(prefix, 0, None, params, {}, sync=True)
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    ref = net(nd.array(x)).asnumpy()

    tenants = [{"name": "free", "weight": 2, "deadline_ms": 30000},
               {"name": "paid", "weight": 4, "deadline_ms": 30000},
               {"name": "batch", "weight": 0.5}]
    mgr = fleet.ReplicaManager(
        n=3, spec={"ckpt_prefix": prefix, "seed": 99, "platform": "cpu",
                   "heartbeat_s": 0.25, "miss_k": 3,
                   "tenants": tenants})
    router = None
    r1_exit = None
    try:
        mgr.spawn("r0")
        mgr.spawn("r1")
        # r2 is the deliberate straggler: replica_slow armed through
        # the child's environment fires on every request (prob 1), so
        # the slowest-replica gate below has a known right answer
        mgr.spawn("r2", extra={
            "slow_s": 0.03,
            "env": {"MXNET_FAULT_INJECT": "replica_slow:1"}})
        mgr.wait_live(timeout=120)
        router = fleet.Router(
            kv=mgr.kv, heartbeat_s=0.25, miss_k=3, retries=2,
            tenants=[serve.TenantConfig(**t) for t in tenants])
        router.refresh()
        # replicas serve the PUBLISHED weights, not their local init
        if not np.allclose(router.infer(x), ref, atol=1e-5):
            print("FAIL: fleet output diverges from the checkpoint "
                  "reference before any fault")
            return 1
        delivered = 1

        # -- phase 1: mixed-tenant hedged load, SIGKILL + KV flap -----
        results, errors = [], []
        names = ("free", "paid", "batch")

        def client(i):
            # alternate hedged / plain requests: hedges chase the slow
            # replica's tail, while the PLAIN requests that hit the
            # killed replica must go through the retry ladder — the
            # failover path the gate below checks (a hedge that eats a
            # conn error never counts as a failover)
            for j in range(16):
                try:
                    results.append(router.submit(
                        x, tenant=names[(i + j) % 3],
                        hedge_ms=8 if j % 2 else 0).result(30))
                except Exception as e:
                    errors.append(e)
                time.sleep(0.01)   # pace: the kill lands mid-load

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        t_dead = time.time() + 10.0
        while len(results) < 8 and not errors and time.time() < t_dead:
            time.sleep(0.01)
        mgr.kill("r0")                       # SIGKILL mid-load
        faultinject.set_fault("kv_flap", 1.0, max_fires=1)
        for t in threads:
            t.join(timeout=60)
        delivered += len(results)

        # -- phase 2: queued burst drained off r1 (KV notice) ---------
        burst = [router.submit(x, tenant="paid") for _ in range(8)]
        mgr.drain("r1")
        for f in burst:
            try:
                results.append(f.result(30))
                delivered += 1
            except Exception as e:
                errors.append(e)
        mgr._procs["r1"].join(timeout=20)
        r1_exit = mgr._procs["r1"].exitcode

        time.sleep(1.5)        # hedge losers land; r0's lease expires
        router.refresh()
        stale = router.table()["stale"]
        rows = fleet.fleet_table()
        snap = telemetry.snapshot()["counters"]
    finally:
        if router is not None:
            router.close()
        faultinject.clear()
        mgr.stop()

    # -- phase 3: distributed-trace assembly under replica_crash ------
    # runs AFTER the counter snapshot so its hedges/failovers cannot
    # disturb the chaos-phase counter identities above
    trace_problems, trace_table = _trace_assembly_phase(net, x, ref)

    def csum(cname, **labels):
        total = 0
        for key, val in snap.items():
            name, lb = telemetry.parse_metric_key(key)
            if name == cname and all(lb.get(k) == v
                                     for k, v in labels.items()):
                total += int(val)
        return total

    counters = {
        "ok": csum("mx_fleet_requests_total", code="ok"),
        "hedge_cancelled": csum("mx_fleet_hedge_cancelled_total"),
        "discarded": csum("mx_fleet_discarded_results_total"),
        "failovers": csum("mx_fleet_failovers_total"),
        "retries": csum("mx_fleet_retries_total"),
        "hedges_launched": csum("mx_fleet_hedges_total",
                                result="launched"),
        "hedges_won": csum("mx_fleet_hedges_total", result="won"),
        "ejected_r0": csum("mx_fleet_ejections_total", replica="r0",
                           reason="lease_expired"),
        "kv_errors": csum("mx_fleet_kv_errors_total"),
        "shed_drain": csum("mx_fleet_shed_total", code="drain"),
    }
    expected = 1 + 4 * 16 + 8

    if args.json:
        print(json.dumps({"rows": rows, "counters": counters,
                          "delivered": delivered, "stale": stale,
                          "r1_exit": r1_exit,
                          "trace_problems": trace_problems},
                         default=str))
    else:
        print(fleet.render_fleet_table(rows))
        print("\ndelivered=%d/%d errors=%d  %s" % (
            delivered, expected, len(errors),
            " ".join("%s=%d" % kv_ for kv_ in sorted(
                counters.items()))))
        if trace_table:
            print()
            print(trace_table)

    problems = []
    if errors:
        problems.append("client-visible error(s): %r" % errors[:3])
    if delivered != expected:
        problems.append("dropped requests: delivered %d of %d"
                        % (delivered, expected))
    if not all(np.allclose(out, ref, atol=1e-5) for out in results):
        problems.append("a delivered output diverges from the "
                        "checkpoint reference")
    # zero duplicates: every ok wire reply beyond the one that
    # delivered its request must have been discarded or
    # hedge-cancelled (an abandoned hedge may be cancelled without
    # ever producing a counted reply, so <= not ==)
    dups = counters["ok"] - delivered
    if dups < 0 or dups > (counters["hedge_cancelled"]
                           + counters["discarded"]):
        problems.append(
            "duplicate-delivery identity broken: %d ok wire replies, "
            "%d delivered, %d hedge-cancelled + %d discarded"
            % (counters["ok"], delivered, counters["hedge_cancelled"],
               counters["discarded"]))
    if counters["failovers"] < 1:
        problems.append("SIGKILL produced no failover")
    if counters["hedges_launched"] < 1 or counters["hedges_won"] < 1:
        problems.append("hedging never engaged (launched=%d won=%d)"
                        % (counters["hedges_launched"],
                           counters["hedges_won"]))
    if counters["ejected_r0"] < 1:
        problems.append("killed replica r0 was never ejected on "
                        "lease expiry")
    if counters["kv_errors"] < 1:
        problems.append("KV flap not observed by the router")
    if stale:
        problems.append("routing table still stale after the KV "
                        "recovered")
    if counters["shed_drain"] != 0:
        problems.append("%d drain shed(s) reached a client — queued "
                        "work must survive the drain"
                        % counters["shed_drain"])
    if r1_exit != 0:
        problems.append("drained replica r1 exitcode %r, expected 0"
                        % (r1_exit,))
    if not rows or rows[0]["replica"] != "r2" \
            or rows[0]["requests"] <= 0:
        problems.append(
            "slowest replica named %r, expected the slow-armed 'r2'"
            % (rows[0]["replica"] if rows else None))
    problems.extend(trace_problems)

    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("SERVE_FLEET_REPORT_OK")
    return 0


def run_single(args) -> int:
    os.environ["MXNET_TELEMETRY"] = "1"
    # the CPU mesh has no peak FLOP/s of its own: state a nominal one so
    # the gate below can check that the meter populates. The mfu this
    # prints is a pipeline check, not a utilization of any device.
    os.environ.setdefault("MXNET_PEAK_FLOPS", "1e12")
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu import commwatch, telemetry
    telemetry.refresh()
    assert telemetry.enabled() and commwatch.enabled()

    _exercise_all_axes(args.steps)

    rows = commwatch.report()
    view = telemetry.fleet_snapshot()
    snap = telemetry.snapshot()
    mfu = snap["gauges"].get("mx_mfu", 0.0)
    goodput = snap["gauges"].get("mx_goodput", 0.0)

    if args.json:
        print(json.dumps({"comm": rows, "fleet": view, "mfu": mfu,
                          "goodput": goodput}, default=str))
    else:
        print(commwatch.render_report(rows))
        print()
        _print_fleet_table(view)
        print("\nmeters: mfu=%.3g goodput=%.3g executed_flops=%.3g"
              % (mfu, goodput,
                 snap["counters"].get("mx_executed_flops_total", 0)))

    problems = []
    for axis in REQUIRED_AXES:
        hits = [r for r in rows
                if axis in r["axis"].split("+")
                and r["bytes"] > 0 and (r["algbw"] > 0 or r["busbw"] > 0)]
        if not hits:
            problems.append("axis %r: no collective with nonzero "
                            "bytes+bandwidth" % axis)
    if mfu <= 0:
        problems.append("mx_mfu not populated (measured-FLOPs meter)")
    if goodput <= 0:
        problems.append("mx_goodput not populated")
    if not view or view.get("nw", 0) < 1:
        problems.append("fleet snapshot empty")

    if problems and not args.no_gate:
        for p in problems:
            print("FAIL: %s" % p)
        return 1
    print("FLEET_REPORT_OK")
    return 0


def _print_fleet_table(view: dict):
    print("fleet: %d rank(s), skew %.1f%%, slowest r%d (%s-bound)"
          % (view["nw"], view["skew"] * 100, view["slowest"],
             view["phase"]))
    print("%-5s %10s %10s %10s %12s %12s %8s %8s"
          % ("rank", "steps", "step_ms", "p99_ms", "comm_ms",
             "exposed_ms", "mfu%", "goodput%"))
    for i, r in enumerate(view["ranks"]):
        print("%-5s %10d %10.2f %10.2f %12.2f %12.2f %8.2f %8.1f"
              % ("r%d" % i, int(r["steps"]), r["step_mean"] * 1e3,
                 r["step_p99"] * 1e3, r["comm_seconds"] * 1e3,
                 r["exposed_comm_seconds"] * 1e3, r["mfu"] * 100,
                 r["goodput"] * 100))


def run_worker() -> int:
    """One rank of the multi-process fleet: join the process group,
    run a local trainer loop (optionally slowed on FLEET_SLOW_RANK —
    the injected straggler), publish this rank's stats through the
    dist store with ONE telemetry.fleet_snapshot() and print
    machine-greppable FLEET_* lines. The training itself stays on the
    local device kvstore: the fleet layer's transport is the
    coordination-service KV (control-plane gRPC), so the merge works
    even on backends without cross-process XLA computations — exactly
    the degraded fleet a straggler hunt happens on."""
    import time
    import numpy as np
    os.environ["MXNET_TELEMETRY"] = "1"
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, dist as dist_mod, gluon, nd, telemetry
    from mxnet_tpu.gluon import nn
    telemetry.refresh()

    dist_mod.initialize()
    rank, nw = dist_mod.rank(), dist_mod.num_workers()
    slow = os.environ.get("FLEET_SLOW_RANK")
    slow = int(slow) if slow not in (None, "") else None
    steps = int(os.environ.get("FLEET_STEPS", "6"))

    import jax
    ctxs = [mx.Context("cpu", i)
            for i in range(len(jax.local_devices()))]
    net = nn.Dense(4)
    net.initialize(init=mx.initializer.Xavier(), ctx=ctxs)
    net(nd.ones((2, 8), ctx=ctxs[0]))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore="device")
    loss_fn = gluon.loss.L2Loss()
    from mxnet_tpu.gluon.utils import split_and_load
    rng = np.random.RandomState(rank)
    batch = 4 * len(ctxs)

    def loop(n, timed):
        for _ in range(n):
            xs = split_and_load(nd.array(
                rng.rand(batch, 8).astype(np.float32)), ctxs)
            ys = split_and_load(nd.array(
                rng.rand(batch, 4).astype(np.float32)), ctxs)
            with autograd.record():
                losses = [loss_fn(net(x), y) for x, y in zip(xs, ys)]
            for l in losses:
                l.backward()
            if timed and slow is not None and rank == slow:
                time.sleep(0.15)        # the injected straggler
            trainer.step(batch)
        for l in losses:
            l.wait_to_read()

    loop(2, timed=False)                # warmup: compile everything
    telemetry.reset()                   # meter the steady state only
    loop(steps, timed=True)

    view = telemetry.fleet_snapshot()
    print("FLEET rank=%d nw=%d step_mean_ms=%.2f comm_ms=%.2f"
          % (rank, view["nw"],
             view["ranks"][rank]["step_mean"] * 1e3,
             view["ranks"][rank]["comm_seconds"] * 1e3), flush=True)
    if rank == 0:
        _print_fleet_table(view)
        print("FLEET_STRAGGLER slowest=%d skew=%.3f phase=%s"
              % (view["slowest"], view["skew"], view["phase"]),
              flush=True)
    print("FLEET_WORKER_OK rank=%d" % rank, flush=True)
    return 0


def run_launcher(args) -> int:
    import subprocess
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # workers pick their own count
    env["FLEET_STEPS"] = str(args.steps)
    if args.slow_rank is not None:
        env["FLEET_SLOW_RANK"] = str(args.slow_rank)
        env.setdefault("MXNET_STRAGGLER_WARN", "0.2")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", str(args.ranks), "--cpu-devices", "2",
         sys.executable, os.path.abspath(__file__), "--worker"],
        env=env, capture_output=True, text=True, timeout=300)
    sys.stdout.write(out.stdout)
    sys.stderr.write(out.stderr)
    if out.returncode != 0 \
            or out.stdout.count("FLEET_WORKER_OK") != args.ranks:
        print("FAIL: fleet workers did not all complete")
        return 1
    print("FLEET_REPORT_OK")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--ranks", type=int, default=0,
                    help="relaunch as N processes via tools/launch.py")
    ap.add_argument("--slow-rank", type=int, default=None,
                    help="with --ranks: inject a sleep into this "
                         "rank's loop (straggler exercise)")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--zero", action="store_true",
                    help="gate the ZeRO RS/AG path: MXNET_ZERO=1 "
                         "trainer over a dcn x dp hierarchy, "
                         "per-axis bytes must cover both tiers")
    ap.add_argument("--serve", action="store_true",
                    help="serving pass: pjit-sharded session on the "
                         "8-device dryrun under a 3-tenant load — "
                         "gates per-tenant counters/histograms, the "
                         "named slowest tenant and the bucket table")
    ap.add_argument("--serve-fleet", action="store_true",
                    help="resilient-serving pass (ISSUE 17): 3 real "
                         "replica processes, mixed-tenant hedged "
                         "load, SIGKILL mid-load + one KV flap + a "
                         "drained burst — gates zero dropped / zero "
                         "duplicated, nonzero failover+hedge "
                         "counters and the named slowest replica")
    ap.add_argument("--quant", action="store_true",
                    help="quantized-collectives pass: int8 bytes on "
                         "the dp tier, f32-only tiers outside "
                         "QUANTIZE_TIER, bert_tiny 20-step "
                         "convergence within 2%% of f32 (ISSUE 13)")
    ap.add_argument("--modelwatch", action="store_true",
                    help="layer-health pass: per-layer gauges + noise "
                         "scale + injected-bad-layer naming (composes "
                         "with --ranks/--bad-rank for the per-rank "
                         "table)")
    ap.add_argument("--bad-rank", type=int, default=None,
                    help="with --modelwatch --ranks: inject "
                         "scaled_grad into this rank's loop — the "
                         "merged table must name its layer AND rank")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic-topology pass (ISSUE 16): one run "
                         "survives shrink -> grow -> forced-failure "
                         "degradation; gates live/restored counters, "
                         "staged fragment bytes and the 2112.01075 "
                         "peak gauge")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--no-gate", action="store_true")
    args = ap.parse_args(argv)
    if args.worker:
        if os.environ.get("FLEET_MODELWATCH"):
            return run_modelwatch_worker()
        return run_worker()
    if args.zero:
        return run_zero(args)
    if args.elastic:
        return run_elastic(args)
    if args.quant:
        return run_quant(args)
    if args.serve_fleet:
        return run_serve_fleet(args)
    if args.serve:
        return run_serve(args)
    if args.modelwatch:
        if args.ranks:
            return run_modelwatch_launcher(args)
        return run_modelwatch_single(args)
    if args.ranks:
        return run_launcher(args)
    return run_single(args)


if __name__ == "__main__":
    sys.exit(main())
