#!/usr/bin/env python
"""Serving benchmark — qps / per-bucket latency / bucket misses / MFU
for the mxserve path (ISSUE 12 satellite).

Drives a mixed-shape, 2-tenant request stream through the full stack
(Scheduler -> continuous batching on the dependency engine -> bucketed
InferenceSession -> AOT serve program) and prints ONE JSON line in the
standardized bench schema (bench.py / bert_bench.py convention):

    {"metric": "serve_throughput", "value": <qps>, "unit": "req/s",
     "p50_ms", "p99_ms", "batch1_p50_ms", "buckets": {bucket:
     {count, p50_ms, p99_ms}}, "bucket_misses", "steady_recompiles",
     "mfu", "tokens_per_s", "tenants": {...}}

The headline pass runs AFTER warmup, so compiles never skew the
numbers; ``steady_recompiles`` counts serve programs compiled DURING
the metered stream — the zero-steady-state-recompile contract.

``--gate P99_MS``: exit nonzero when the measured p99 exceeds P99_MS
milliseconds OR any steady-state recompile / bucket miss occurred —
the CI gate for the serving path (CPU dryrun default threshold in
tests: generous; on-chip runs pin a real budget).

Usage: python tools/serve_bench.py [--requests 200] [--gate P99_MS]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--seq", type=int, default=32,
                    help="max sequence rung (pow-2 ladder below it)")
    ap.add_argument("--batch", type=int, default=8,
                    help="max batch rung (pow-2 ladder below it)")
    ap.add_argument("--gate", type=float, default=None,
                    help="exit 1 unless p99 <= this (ms) AND zero "
                         "steady-state recompiles/bucket misses")
    args = ap.parse_args(argv)

    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import compilewatch, nd, telemetry
    from mxnet_tpu import serve
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serve import tenancy
    telemetry.refresh()

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(128, in_units=64, flatten=False, activation="relu"),
            nn.Dense(64, flatten=False))
    net.initialize(init=mx.initializer.Xavier())
    x_ex = nd.ones((2, args.seq, 64))
    sess = net.serve_session(x_ex, max_batch=args.batch, seq_axis=1,
                             max_seq=args.seq)
    sess.warmup()
    n_buckets = len(sess.ladder.all_buckets())
    compiled_after_warmup = len(
        [p for p in compilewatch.programs() if p["fn"] == "serve.forward"])

    sched = serve.Scheduler(sess, tenants=[
        serve.TenantConfig("free", weight=1),
        serve.TenantConfig("paid", weight=4)])

    rng = np.random.RandomState(7)
    flops0 = telemetry.snapshot()["counters"].get(
        "mx_executed_flops_total", 0.0)
    futs = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        b = int(rng.randint(1, args.batch + 1))
        s = int(rng.randint(args.seq // 4, args.seq + 1))
        x = rng.rand(b, s, 64).astype(np.float32)
        futs.append(sched.submit(
            x, tenant="paid" if i % 3 else "free"))
    ok = err = 0
    for f in futs:
        try:
            f.result(120)
            ok += 1
        except Exception:
            err += 1
    wall = time.perf_counter() - t0
    sched.close()

    snap = telemetry.snapshot()
    flops1 = snap["counters"].get("mx_executed_flops_total", 0.0)
    # no peak known for this device kind (the CPU default): no mfu
    peak = telemetry.known_peak_flops()
    mfu = (flops1 - flops0) / wall / peak if wall > 0 and peak else None
    steady = len([p for p in compilewatch.programs()
                  if p["fn"] == "serve.forward"]) - compiled_after_warmup

    # per-bucket latency from the mx_serve_batch_seconds histograms
    buckets = {}
    for key, summ in snap["histograms"].items():
        name, labels = telemetry.parse_metric_key(key)
        if name == "mx_serve_batch_seconds":
            buckets[labels.get("bucket", "?")] = {
                "count": summ["count"],
                "p50_ms": round(summ["p50"] * 1e3, 3),
                "p99_ms": round(summ["p99"] * 1e3, 3)}
    rows = tenancy.slo_report(sched._tenants.values())
    p50 = max((r["p50_ms"] for r in rows), default=0.0)
    p99 = max((r["p99_ms"] for r in rows), default=0.0)
    b1 = buckets.get("b1s%d" % args.seq, {}).get("p50_ms", None)
    tokens_per_s = sum(r["tokens_per_s"] for r in rows)

    import bench_json
    bench_json.emit({
        "metric": "serve_throughput",
        "value": round(ok / wall, 2) if wall > 0 else 0.0,
        "unit": "req/s",
        "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
        "batch1_p50_ms": b1,
        "buckets": buckets,
        "bucket_misses": sess.bucket_misses(),
        "steady_recompiles": steady,
        "warmup_programs": n_buckets,
        "requests_ok": ok, "requests_failed": err,
        "mfu": None if mfu is None else round(mfu, 6),
        "tokens_per_s": round(tokens_per_s, 1),
        "tenants": {r["tenant"]: {"requests": r["requests"],
                                  "p50_ms": round(r["p50_ms"], 3),
                                  "p99_ms": round(r["p99_ms"], 3)}
                    for r in rows},
    }, source="serve_bench")

    if args.gate is not None:
        problems = []
        if err:
            problems.append("%d request(s) failed" % err)
        if p99 > args.gate:
            problems.append("p99 %.2fms > gate %.2fms" % (p99, args.gate))
        if steady > 0:
            problems.append("%d steady-state recompile(s) on the serve "
                            "program" % steady)
        if sess.bucket_misses() > 0:
            problems.append("%d bucket miss(es)" % sess.bucket_misses())
        if problems:
            for p in problems:
                print("SERVE GATE FAIL: %s" % p, file=sys.stderr)
            return 1
        print("SERVE GATE OK: p99 %.2fms <= %.2fms, 0 steady "
              "recompiles, 0 bucket misses" % (p99, args.gate),
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
