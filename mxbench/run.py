"""Run one cell of the benchmark once, in a process of its own.

    python -m mxbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python -m mxbench.run --list
    python -m mxbench.run --rehearse --workload <name>

The last line of standard output is the contract's one JSON object.
``--trace 0`` reports the cell's end-to-end metrics with the profiler
off; ``--trace 1`` is a run of its own that wraps a short steady window
in the profiler and reports the per-layer metrics and ``breakdown``.
Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result. ``--rehearse`` walks the same control
flow at the ``toy`` sizes of the cell's files on whatever JAX finds; it
prints ``"rehearsal"`` in place of the contract line and never a
metric.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

from mxbench import manifest, meters, trace as T

# as early as this module can: setup_s starts at the process's start
_CLOCK = meters.SetupClock()


def say(msg):
    """A free line before the contract's; the stamp is seconds since
    the process started."""
    print("[mxbench %7.2f] %s" % (_CLOCK.now(), msg), flush=True)


def _with_toy(params: dict) -> dict:
    out = {k: v for k, v in params.items() if k != "toy"}
    out.update(params.get("toy", {}))
    return out


def listing() -> dict:
    return {"workloads": manifest.workload_names(),
            "traffic": manifest.names_in("traffic", ".json"),
            "traffic_kinds": manifest.names_in("traffic", ".py"),
            "configs": manifest.names_in("configs", ".json"),
            "layer_metrics": manifest.names_in("layer_metrics", ".py")}


def device_block(devices, run) -> dict:
    import jax
    d0 = devices[0]
    block = {"platform": d0.platform, "kind": d0.device_kind,
             "count": len(jax.devices()),
             "memory_peak_bytes": run.peak_bytes}
    if run.trace is not None:
        lo, hi = run.trace_window
        busy = [T.total(T.busy(run.trace, i, run.trace_window)) / 1e9
                for i in sorted(run.trace.devices)[:run.chips]]
        block["busy_s"] = sum(busy) / len(busy)
        block["window_s"] = (hi - lo) / 1e9
        if len(busy) > 1:
            say("device busy seconds by chip: %s (idle share of the worst: "
                "%.2f%%)" % (busy, 100 * (1 - min(busy) / block["window_s"])))
    return block


def context(workload, seed, seconds, trace, rehearse):
    """(what a generator is given, the generator's module, the cell's
    per-layer readers); the first is None where JAX reports fewer
    devices than the cell asks for. Raises on the CPU unless
    ``rehearse``."""
    cell = manifest.workload(workload)
    sizes, cfgmod, refmod = manifest.config(cell["config"])
    traffic, generator = manifest.traffic(cell["traffic"])
    readers = {name: manifest.layer_metric(name)
               for name in cell["layer_metrics"]}
    if rehearse:
        sizes, traffic = _with_toy(sizes), _with_toy(traffic)

    # compilewatch (the recompile counters) rides the telemetry gate;
    # commwatch would close every ShardedTrainStep.step with a readback
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_COMMWATCH"] = "0"
    import jax
    from mxnet_tpu import runtime
    if not rehearse:
        runtime.require_accelerator()      # raises on the CPU
        say("compile cache at %s" % runtime.enable_compile_cache())
    devices = jax.devices()
    if len(devices) < cell["chips"]:
        say("cell %s needs %d chip(s), JAX reports %d device(s)"
            % (cell["name"], cell["chips"], len(devices)))
        return None, generator, readers
    devices = devices[:cell["chips"]]
    say("devices: %s x %d (%s)" % (devices[0].device_kind, len(devices),
                                   devices[0].platform))
    ctx = types.SimpleNamespace(
        cell=cell, sizes=sizes, cfgmod=cfgmod, refmod=refmod,
        traffic=traffic, devices=devices, seed=seed, seconds=seconds,
        trace=trace, rehearse=rehearse, meter=meters.CompileMeter(),
        clock=_CLOCK, say=say, amp_on=False)
    return ctx, generator, readers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true",
                    help="print the cells, mixes, configurations and "
                         "layer metrics found under mxbench/")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any platform; never reports a metric")
    args = ap.parse_args(argv)
    if args.list:
        print(json.dumps(listing(), indent=1))
        return 0
    if not args.workload:
        ap.error("--workload is required")

    ctx, generator, readers = context(
        args.workload, args.seed, args.seconds, bool(args.trace),
        args.rehearse)
    if ctx is None:
        return 3
    cell, devices = ctx.cell, ctx.devices
    run = generator.run(ctx)

    if args.rehearse:
        print(json.dumps({"rehearsal": "passed" if run.correct else "failed",
                          "workload": cell["name"],
                          "attempted": run.attempted, "failed": run.failed,
                          "layer_metrics_found": sorted(readers)}))
        return 0 if run.correct else 1

    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": {},
            "device": device_block(devices, run)}
    if args.trace:
        for name, reader in readers.items():
            value = reader.read(run)
            if value is not None:
                line["metrics"][name] = {"value": value, "unit": reader.UNIT}
        line["breakdown"] = {
            "device_ops": T.top_ops(run.trace, 0, run.trace_window),
            "idle_gaps": T.idle_gaps(run.trace, 0, run.trace_window)}
    else:
        for name in cell["metrics"]:
            value, unit = run.end_to_end[name]
            line["metrics"][name] = {"value": value, "unit": unit}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
