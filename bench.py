"""Flagship benchmark: ResNet-50 v1 training throughput (images/sec) on
one chip — the BASELINE.json:8 headline config. Baseline to beat: NGC
MXNet-era A100 ≈ 3000 img/s fp16 (BASELINE.md; from-memory figure).

Measures the BASELINE-named "HybridBlock/CachedOp" config — the
reference-idiomatic Gluon loop (net.hybridize(); autograd.record();
loss.backward(); trainer.step()) with AMP bf16 — as the HEADLINE
metric, plus the ShardedTrainStep single-program path as a cross-check
key. Both run the NHWC layout pass (symbol/layout_opt.py).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 3000.0  # A100 fp16 ResNet-50, NGC MXNet era (BASELINE.md)


def main():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh
    from mxnet_tpu import runtime

    # a chip-only command: mx.tpu(0) would resolve to the CPU without
    # complaint, so ask JAX first; the device goes into the JSON line
    device = runtime.require_accelerator()
    runtime.enable_compile_cache()

    argv = sys.argv[1:]
    batch = int(argv[0]) if len(argv) > 0 else 128
    steps = int(argv[1]) if len(argv) > 1 else 16

    net = resnet50_v1()
    net.initialize(init=mx.initializer.MSRAPrelu())
    x_small = nd.ones((2, 3, 224, 224))
    net(x_small)  # resolve deferred shapes

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, loss_fn, mesh, lr=0.1, momentum=0.9,
                            dtype="bfloat16",
                            data_specs=[P(), P()])

    rng = np.random.RandomState(0)
    x = rng.rand(batch, 3, 224, 224).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    xs, ys = nd.array(x), nd.array(y)

    # MXNET_BENCH_PIPELINE=1: feed every step from the native RecordIO
    # pipeline (synthetic raw records) instead of one resident batch, so
    # the number includes host decode/augment + host->HBM transfer
    # (not measured on the chip yet; the host pipeline alone sustains
    # >10k img/s, tests/test_io.py::test_native_pipeline_throughput).
    feed = None
    if os.environ.get("MXNET_BENCH_PIPELINE"):
        import tempfile
        from mxnet_tpu import recordio
        from mxnet_tpu.io import ImageRecordIter
        tmp = tempfile.mkdtemp(prefix="benchrec_")
        rec, idx = tmp + "/b.rec", tmp + "/b.idx"
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        raw = (x[0].transpose(1, 2, 0) * 255).astype(np.uint8)
        for i in range(batch * 4):
            w.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(i % 1000), i, 0), raw.tobytes()))
        w.close()
        it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                             data_shape=(3, 224, 224), batch_size=batch,
                             shuffle=True, rand_mirror=True, seed=1,
                             std_r=255.0, std_g=255.0, std_b=255.0)

        def feed():
            nonlocal it
            try:
                b = it.next()
            except StopIteration:
                it.reset()
                b = it.next()
            return b.data[0], b.label[0]

    # wall time by slope: t(N) - t(1) over N-1 steps, each run ending in
    # a scalar readback that materializes the whole chain.
    def run(n):
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            if feed is not None:
                bx, by = feed()
                loss = step.step(bx, by)
            else:
                loss = step.step(xs, ys)
        jax.device_get(loss).item()
        return time.perf_counter() - t0

    # xplane device BUSY time per step (tools/devtime.py); a failure to
    # trace raises. Wall-slope is the mode of the end-to-end pipeline
    # config only.
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))

    def device_img_s(step_fn, sync):
        from devtime import device_ms_per_step
        return batch / device_ms_per_step(step_fn, 10, sync) * 1000.0

    def wall_slope_img_s(runner):
        t1 = min(runner(1) for _ in range(3))
        tn = min(runner(steps) for _ in range(3))
        return batch * (steps - 1) / (tn - t1)

    run(3)  # warmup/compile
    sharded_img_s = device_img_s(
        lambda: step.step(xs, ys),
        lambda o: jax.device_get(o).item()) if feed is None else None
    if sharded_img_s is None:
        sharded_img_s = wall_slope_img_s(run)

    # ------------------------------------------------------------------
    # HEADLINE: the reference-idiomatic Gluon HybridBlock/CachedOp loop
    # (BASELINE.json configs[1]) — AMP bf16, hybridize, Trainer.step.
    # ------------------------------------------------------------------
    from mxnet_tpu.contrib import amp
    amp.init(target_dtype="bfloat16")
    gnet = resnet50_v1()
    gnet.initialize(init=mx.initializer.MSRAPrelu())
    gnet(x_small)
    gnet.hybridize(static_alloc=True, static_shape=True)
    trainer = gluon.Trainer(gnet.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device")
    gloss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    gloss_fn.hybridize(static_alloc=True, static_shape=True)

    def gluon_step(bx, by):
        with autograd.record():
            out = gnet(bx)
            loss = gloss_fn(out, by)
        loss.backward()
        trainer.step(batch)
        return loss

    def grun(n):
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            if feed is not None:
                bx, by = feed()
                loss = gluon_step(bx, by)
            else:
                loss = gluon_step(xs, ys)
        # .item(), not float(): NumPy deprecated float() on ndim>0
        # arrays and the per-sample loss comes back shaped (batch? 1,)
        jax.device_get(loss.sum()._jax()).item()
        return time.perf_counter() - t0

    grun(3)  # warmup/compile
    method = "xplane_device_time"
    gluon_img_s = device_img_s(
        lambda: gluon_step(xs, ys),
        lambda o: jax.device_get(o.sum()._jax()).item()) \
        if feed is None else None
    if gluon_img_s is None:   # pipeline mode measures end-to-end wall
        gluon_img_s = wall_slope_img_s(grun)
        method = "wall_slope"

    # ------------------------------------------------------------------
    # metered pass (ISSUE 6): AFTER the headline numbers (so the
    # instrumentation cannot skew them), run a short telemetry+commwatch
    # loop to populate the measured MFU/goodput gauges and the per-axis
    # comm-bandwidth table — the BENCH_*.json schema fields that make
    # the perf trajectory machine-comparable across rounds.
    # ------------------------------------------------------------------
    mfu = goodput = None
    noise_scale = None
    mw_anomalies = 0
    comm = {}
    from mxnet_tpu import commwatch, telemetry
    _prior = {k: os.environ.get(k)
              for k in ("MXNET_TELEMETRY", "MXNET_MODELWATCH")}
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_MODELWATCH"] = "1"
    telemetry.refresh()
    try:
        for _ in range(5):
            if feed is not None:
                bx, by = feed()
                loss = gluon_step(bx, by)
            else:
                loss = gluon_step(xs, ys)
            jax.device_get(loss.sum()._jax()).item()
        snap = telemetry.snapshot()
        mfu = snap["gauges"].get("mx_mfu")
        goodput = snap["gauges"].get("mx_goodput")
        # training-dynamics fields (ISSUE 11): the noise scale
        # needs >=2 dp replicas — null on this single-chip
        # flagship unless driven over several devices
        noise_scale = snap["gauges"].get("mx_grad_noise_scale")
        mw_anomalies = int(sum(
            v for k, v in snap["counters"].items()
            if k.startswith("mx_modelwatch_anomalies_total")))
        for r in commwatch.report():
            # per-dtype keys: a quantized wire's int8 rows stay
            # distinguishable from the f32 sidecar/tiers
            comm[commwatch.report_key(r)] = {
                "bytes": r["bytes"],
                "algbw_bytes_per_sec": r["algbw"],
                "busbw_bytes_per_sec": r["busbw"]}
    finally:
        # restore the caller's env (don't clobber user-set gates,
        # and don't leave the forced '1's behind if the metered
        # loop throws)
        for k, v in _prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        telemetry.refresh()

    # optimizer-state footprint + ZeRO flag (ISSUE 8 schema fields):
    # the engine only engages on multi-replica loops, so this
    # single-chip flagship reports zero=False unless driven with
    # MXNET_ZERO over several devices
    from mxnet_tpu.gluon import zero as _zero_mod
    from mxnet_tpu.parallel import quantize as _qz
    _qcfg = _qz.from_env()
    from bench_json import emit as _emit
    _emit({
        "metric": "resnet50_v1_train_throughput",
        "value": round(gluon_img_s, 2),
        "unit": "images/sec/chip",
        "device": device,
        "vs_baseline": round(gluon_img_s / BASELINE_IMG_S, 4),
        "path": "gluon_hybridize_trainer",
        "method": method,
        "sharded_train_step_img_s": round(sharded_img_s, 2),
        "mfu": mfu, "goodput": goodput,
        "comm_bandwidth": comm,
        "grad_noise_scale": noise_scale,
        "modelwatch_anomalies": mw_anomalies,
        "optimizer_state_bytes": trainer.optimizer_state_bytes(),
        "zero": isinstance(trainer._zero, _zero_mod.ZeroEngine),
        "quantize": _qcfg.mode if _qcfg is not None else "off",
    }, source="bench.py")


if __name__ == "__main__":
    main()
