"""Generator of kind ``train_resident``: the Gluon training job of
``train_stream`` with its input already on the device. One batch is
made from the seed, uploaded once in set-up and waited for, and the
same two device arrays are handed to every step: what is left in the
window is the host loop (forward record, backward plan, fused update)
and the device. That is a job whose input is cached, pre-decoded or
augmented on the device, or whose pipeline keeps ahead of the step.

``GluonLoop``, ``measure`` and ``check_against_reference`` are
``train_stream``'s own, loaded from its file; this file adds the
``resident`` feed and a ``run`` that also reads the program's own
spans (``mxbench/spans.py``): a window's step log into
``Run.untraced_s_per_step`` beside the host seconds ``measure``
clocks, and, in a traced run, the ``step::*`` annotations of the
xplane into ``Run.trace`` (a ``spans.ProgramTrace``: what
``trace.py::load`` returns, and ``program``). A traced run prints the
device's ten longest idle gaps named by program span as a free line.
On a program that has no such spans both stay empty and the readers of
them report nothing.

    window = first launch .. block_until_ready(last loss, parameters)
    train_images_per_s = steps x batch / window

Reads from its traffic file: ``batch_per_chip``, ``optimizer``,
``inflight_steps``, ``warmup_steps``, ``trace_seconds``, ``toy``; the
``loop`` is ``gluon_trainer`` and the ``feed`` is ``resident``.
"""
from __future__ import annotations

import gc
import shutil
import tempfile

import numpy as np

from mxbench import manifest, meters, spans, trace as T
from mxbench.record import Run

_stream = manifest.load_module("traffic", "train_stream.py")
UNITS = _stream.UNITS
SPLIT = ("step::forward", "step::backward", "step::update",
         "step::update.prep", "step::update.launch",
         "step::update.launch.lookup", "step::update.launch.call",
         "step::update.writeback")


class ResidentFeed:
    """One batch with the shape, dtype and layout of the ``data[0]`` /
    ``label[0]`` that ``io.ImageRecordIter`` hands over in the RecordIO
    cell: NCHW float32 in [0, 1] from uint8 pixels, float32 labels."""

    def __init__(self, ctx, batch, seq, check=False):
        side, classes = ctx.sizes["image_size"], ctx.sizes["num_classes"]
        rng = np.random.default_rng(ctx.seed)
        images = rng.integers(0, 256, (batch, side, side, 3), dtype=np.uint8)
        labels = rng.integers(0, classes, batch)
        x = images.astype(np.float32) / 255.0
        self.host = (np.ascontiguousarray(x.transpose(0, 3, 1, 2)),
                     labels.astype(np.float32))
        self.dev = None
        if check:       # only host_batch() is asked of it
            return
        from mxnet_tpu import nd
        self.dev = tuple(nd.array(a) for a in self.host)
        for a in self.dev:
            a.wait_to_read()

    def host_batch(self):
        return self.host

    def next(self):
        return self.dev

    def close(self):
        self.dev = None


# this module's own copy of train_stream (load_module executes the file
# anew), so the feed is added here and to no file that is there
_stream.FEEDS["resident"] = ResidentFeed


def _split_line(per):
    if not per.get("step_log_steps"):
        return "program spans: none (the program keeps no step log)"
    return ("program spans over %d steps, host ms/step: %s; launches/step "
            "%.3f" % (per["step_log_steps"],
                      ", ".join("%s %.3f" % (n[len("step::"):],
                                             per.get(n, 0.0) * 1e3)
                                for n in SPLIT), per["launches"]))


def run(ctx) -> Run:
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import compilewatch
    tr = ctx.traffic
    batch = int(tr["batch_per_chip"]) * len(ctx.devices)
    inflight = int(tr["inflight_steps"])
    (rate,) = [m for m in ctx.cell["metrics"] if m != "setup_s"]

    ctx.say("imports done; checking against the reference")
    correct = _stream.check_against_reference(ctx, None)

    mx.random.seed(ctx.seed % (2 ** 31))
    loop = _stream.GluonLoop(ctx, batch, 0.0, None)
    feed = ResidentFeed(ctx, batch, None)
    ctx.say("loop built, batch resident; warming %d steps"
            % int(tr["warmup_steps"]))
    try:
        n, losses, _, _ = _stream.measure(loop, feed, 0.0, inflight)
        for _ in range(int(tr["warmup_steps"]) - n):
            losses.append(loop.step(*feed.next()))
        loop.wait_all(losses[-1])
        loop.loss_values(losses)
        del losses
        gc.collect()

        seconds = ctx.seconds
        trace_dir = untraced = None
        if ctx.trace and not ctx.rehearse:
            seconds = min(seconds, float(tr["trace_seconds"]))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1      # TraceAnnotations, no more
            # the same window untraced first, as train_stream: the host
            # seconds and the step log are read with the profiler off
            n, _, wall, host = _stream.measure(loop, feed, seconds, inflight)
            untraced = {"wall": wall / n}
            untraced.update({k: v / n for k, v in host.items()})
            untraced.update(spans.per_step(spans.step_records(n)))
            ctx.say("untraced window: %d steps, host ms/step: step %.3f; %s"
                    % (n, untraced["step"] * 1e3, _split_line(untraced)))
            trace_dir = tempfile.mkdtemp(prefix="mxbench_trace_")
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark = ctx.meter.mark()
        programs = len(compilewatch.programs())
        setup_s = ctx.clock.now()
        try:
            steps, losses, wall, host = _stream.measure(loop, feed, seconds,
                                                        inflight)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        late = ctx.meter.since(mark)[0]
        watched = len(compilewatch.programs()) - programs
        values = loop.loss_values(losses)
        failed = int((~np.isfinite(values)).sum())
        ctx.say("window: %d steps of %d samples in %.4f s; loss %.4f -> "
                "%.4f; %d XLA compile(s), %d new watched program(s)"
                % (steps, batch, wall, values[0], values[-1], late, watched))
        ctx.say("host ms/step: feed %.3f, step %.3f, sync %.3f"
                % tuple(host[k] * 1e3 / steps for k in ("feed", "step", "sync")))
        ctx.say(_split_line(spans.per_step(spans.step_records(steps))))
        ctx.say("memory_stats of device 0: %s" % (
            None if ctx.rehearse else ctx.devices[0].memory_stats()))
        correct = correct and failed == 0 and late == 0 and watched == 0
        peak = 0 if ctx.rehearse else meters.peak_bytes(ctx.devices)
    finally:
        feed.close()

    run = Run(cell=ctx.cell, sizes=ctx.sizes, traffic=tr,
              device_kind=ctx.devices[0].device_kind, chips=len(ctx.devices),
              correct=bool(correct), attempted=steps, failed=failed,
              end_to_end={
                  rate: (steps * batch / wall, UNITS[rate]),
                  "setup_s": (setup_s, UNITS["setup_s"])},
              window_s=wall, samples=steps * batch,
              flops_per_sample=ctx.cfgmod.train_flops_per_sample(
                  ctx.sizes, None),
              peak_bytes=peak, setup_compiles=mark[0],
              setup_compile_s=mark[1], setup_cache_hits=mark[2],
              untraced_s_per_step=untraced)
    if trace_dir is not None:
        try:
            run.trace = spans.load(T.find_xplane(trace_dir))
            run.trace_window = T.window_of(run.trace)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.say("idle gaps of device 0 by program span: %s"
                % spans.idle_gaps(run.trace, 0, run.trace_window))
        wait = spans.launch_to_device_s(run.trace, 0, run.trace_window)
        ctx.say("launch to device, median over the traced steps: %s"
                % ("%.3f ms" % (wait * 1e3) if wait is not None
                   else "no %s span in the trace" % spans.LAUNCH_SPAN))
    return run
