"""Generator of kind ``train_stream``: a training job fed a fresh batch
every step. One traffic file of this kind gives the loop
(``sharded_step``: ``parallel.ShardedTrainStep``, one program a step;
``gluon_trainer``: hybridize + autograd + ``gluon.Trainer``), the
optimizer, the batch per chip, and the feed (``token_pool``: integer
ids and labels drawn from a host pool, moved with ``nd.array``;
``raw_recordio``: ``io.ImageRecordIter`` over a raw-record file written
from the seed during set-up). Everything is inside the window: the
feed, the host-to-device copy, the host loop.

    window = first launch .. block_until_ready(last loss, parameters)
    train_samples_per_s = steps x global batch / window
"""
from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time

import numpy as np

from mxbench import meters, trace as T
from mxbench.record import Run

# the rate goes under whichever of these names the cell's file lists
UNITS = {"train_samples_per_s": "samples/s", "train_images_per_s": "img/s",
         "train_routed_samples_per_s": "samples/s", "setup_s": "s"}


# ---------------------------------------------------------------------------
# loops: how one step is launched
# ---------------------------------------------------------------------------
class ShardedLoop:
    def __init__(self, ctx, batch, dropout, seq):
        import jax
        from mxnet_tpu.parallel import (MeshConfig, P, ShardedTrainStep,
                                        make_mesh)
        opt = ctx.traffic["optimizer"]
        self.net, self.loss, n_in = ctx.cfgmod.sharded_parts(
            ctx.sizes, dropout, seq)
        self.weights = ctx.cfgmod.named_weights(self.net, self.loss)
        mesh = make_mesh(MeshConfig(dp=len(ctx.devices)),
                         devices=list(ctx.devices))
        spec = P("dp") if len(ctx.devices) > 1 else P()
        hp = {k: v for k, v in opt.items() if k != "name"}
        self.step_obj = ShardedTrainStep(
            self.net, self.loss, mesh, optimizer=opt["name"],
            dtype=ctx.sizes["compute_dtype"], n_data_inputs=n_in,
            data_specs=[spec] * n_in, seed=ctx.seed % (2 ** 31), **hp)
        self._jax = jax

    def step(self, *batch):
        return self.step_obj.step(*batch)

    def wait(self, loss):
        loss.block_until_ready()

    def wait_all(self, loss):
        self._jax.block_until_ready((loss, self.step_obj.params))

    def loss_values(self, losses):
        return np.asarray(self._jax.device_get(losses), np.float64)

    def compiled(self):
        """The step's AOT executable (on the TPU the step compiles one
        per data shape), or None."""
        found = list(getattr(self.step_obj, "_compiled", {}).values())
        return found[0] if found else None


class GluonLoop:
    def __init__(self, ctx, batch, dropout, seq):
        import jax
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.contrib import amp
        opt = ctx.traffic["optimizer"]
        if ctx.sizes["compute_dtype"] != "float32" and not ctx.amp_on:
            amp.init(target_dtype=ctx.sizes["compute_dtype"])   # process-wide
            ctx.amp_on = True
        self.net, self.loss_fn = ctx.cfgmod.gluon_parts(ctx.sizes)
        self.weights = ctx.cfgmod.named_weights(self.net)
        self.net.hybridize(static_alloc=True, static_shape=True)
        self.loss_fn.hybridize(static_alloc=True, static_shape=True)
        hp = {"learning_rate": opt["lr"]}
        hp.update({k: v for k, v in opt.items() if k not in ("name", "lr")})
        self.trainer = gluon.Trainer(self.net.collect_params(), opt["name"],
                                     hp, kvstore="device")
        self.batch = batch
        self._autograd, self._jax = autograd, jax

    def step(self, x, y):
        with self._autograd.record():
            loss = self.loss_fn(self.net(x), y)
        loss.backward()
        self.trainer.step(self.batch)
        # the bare device array: the NDArray keeps its recorded graph,
        # and with it the step's input batch, alive (a window's worth of
        # them ran the chip out of memory, PR 23)
        return loss._jax()

    def wait(self, loss):
        loss.block_until_ready()

    def wait_all(self, loss):
        self._jax.block_until_ready(
            [loss] + [p.data()._jax() for p in
                      self.net.collect_params().values()])

    def loss_values(self, losses):
        got = self._jax.device_get(losses)
        return np.asarray([np.mean(np.asarray(g, np.float64)) for g in got])

    def compiled(self):
        return None         # three programs a step, none of them AOT


LOOPS = {"sharded_step": ShardedLoop, "gluon_trainer": GluonLoop}


# ---------------------------------------------------------------------------
# feeds: where a step's batch comes from
# ---------------------------------------------------------------------------
class TokenPoolFeed:
    """Integer ids and labels for a token model, drawn batch by batch
    (a seeded order over the pool, wrapping) and moved with
    ``nd.array`` at each step."""

    def __init__(self, ctx, batch, seq, check=False):
        spec = ctx.traffic["feed"]
        rng = np.random.default_rng(ctx.seed)
        pool = batch if check else max(int(spec["pool_sequences"]), batch)
        vocab = ctx.sizes["vocab_size"]
        self.ids = rng.integers(0, vocab, (pool, seq), dtype=np.int32)
        self.labels = rng.integers(0, vocab, (pool, seq), dtype=np.int32)
        self.types = np.zeros((batch, seq), np.int32)
        self.order = rng.permutation(pool)
        self.batch, self.at = batch, 0

    def host_batch(self):
        if self.at + self.batch > len(self.order):
            self.at = 0
        rows = self.order[self.at:self.at + self.batch]
        self.at += self.batch
        return self.ids[rows], self.types, self.labels[rows]

    def next(self):
        from mxnet_tpu import nd
        return tuple(nd.array(a, dtype="int32") for a in self.host_batch())

    def close(self):
        pass


class RawRecordFeed:
    """``io.ImageRecordIter`` (shuffle, random mirror) over a file of
    distinct raw uint8 images written from the seed: the native
    pipeline with no JPEG decode."""

    def __init__(self, ctx, batch, seq, check=False):
        from mxnet_tpu import recordio
        from mxnet_tpu.io import ImageRecordIter
        spec = ctx.traffic["feed"]
        side, classes = ctx.sizes["image_size"], ctx.sizes["num_classes"]
        n = batch if check else max(int(spec["records"]), batch)
        rng = np.random.default_rng(ctx.seed)
        self.images = rng.integers(0, 256, (n, side, side, 3), dtype=np.uint8)
        self.labels = rng.integers(0, classes, n)
        self.batch, self.dir, self.it = batch, None, None
        self.spent, self.say = [0.0, 0.0, 0], ctx.say
        if check:       # only host_batch() is asked of it
            return
        images, labels = self.images, self.labels
        self.dir = tempfile.mkdtemp(prefix="mxbench_rec_")
        rec, idx = (os.path.join(self.dir, "train." + e)
                    for e in ("rec", "idx"))
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for i in range(n):
            w.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(labels[i]), i, 0),
                images[i].tobytes()))
        w.close()
        self.it = ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, side, side),
            batch_size=batch, shuffle=bool(spec["shuffle"]),
            rand_mirror=bool(spec["rand_mirror"]), seed=ctx.seed % (2 ** 31),
            std_r=255.0, std_g=255.0, std_b=255.0)

    def host_batch(self):
        """The first ``batch`` images as the iterator would normalise
        them (for the correctness check, which needs the batch on the
        host): NCHW float32 in [0, 1]."""
        x = self.images[:self.batch].astype(np.float32) / 255.0
        return (np.ascontiguousarray(x.transpose(0, 3, 1, 2)),
                self.labels[:self.batch].astype(np.float32))

    def next(self):
        t0 = time.perf_counter()
        try:
            b = self.it.next()
        except StopIteration:
            self.it.reset()
            b = self.it.next()
        t1 = time.perf_counter()
        # the iterator hands over arrays whose upload an engine op is
        # still doing; autograd's fused backward cannot take such a
        # pending input (AttributeError: 'EngineGate' ... 'out_values',
        # PR 23), so the feed waits for the hand-off here, inside the
        # mxbench/feed span
        b.data[0].wait_to_read()
        b.label[0].wait_to_read()
        self.spent[0] += t1 - t0
        self.spent[1] += time.perf_counter() - t1
        self.spent[2] += 1
        return b.data[0], b.label[0]

    def close(self):
        self.it = None
        if self.spent[2]:
            self.say("feed, host ms a batch over %d batches: iterator %.3f, "
                     "hand-off wait %.3f" % (
                         self.spent[2], self.spent[0] * 1e3 / self.spent[2],
                         self.spent[1] * 1e3 / self.spent[2]))
            self.spent = [0.0, 0.0, 0]
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


FEEDS = {"token_pool": TokenPoolFeed, "raw_recordio": RawRecordFeed}


# ---------------------------------------------------------------------------
def check_against_reference(ctx, seq):
    """Outside the window: the system's losses over a few steps at a
    small batch, dropout 0, same seeded weights and batch, against the
    plain reference's. Step 1 checks the forward, step 2 the backward
    and the optimizer."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    chk = ctx.sizes["check"]
    batch = int(chk["batch"])
    mx.random.seed(ctx.seed % (2 ** 31))
    loop = LOOPS[ctx.traffic["loop"]](ctx, batch, 0.0, seq)
    feed = FEEDS[ctx.traffic["feed"]["type"]](ctx, batch, seq, check=True)
    ctx.say("check: instance built")
    try:
        host = feed.host_batch()
        ints = all(np.issubdtype(a.dtype, np.integer) for a in host)
        dev = [nd.array(a, dtype="int32") if ints else nd.array(a)
               for a in host]
        got = [float(loop.loss_values([loop.step(*dev)])[0])
               for _ in range(int(chk["steps"]))]
        ctx.say("check: system steps done")
        want = ctx.refmod.train_losses(loop.weights, host, ctx.sizes,
                                       ctx.traffic["optimizer"],
                                       int(chk["steps"]))
    finally:
        feed.close()
    ok = bool(np.isfinite(got).all()) \
        and abs(got[0] - want[0]) <= chk["loss_rtol"] * abs(want[0])
    if len(got) > 1:
        drop_g, drop_w = got[0] - got[-1], want[0] - want[-1]
        ok = ok and abs(drop_g - drop_w) <= chk["drop_rtol"] * abs(drop_w)
    ctx.say("check: system losses %s, reference %s, loss_rtol %g, "
            "drop_rtol %g -> %s" % (got, want, chk["loss_rtol"],
                                    chk["drop_rtol"], "ok" if ok else "WRONG"))
    del loop, feed
    gc.collect()
    return ok


def measure(loop, feed, seconds, inflight):
    """Steps until ``seconds`` have passed, at most ``inflight`` of
    them not yet known finished (so the host cannot queue the window's
    work and leave). Returns (steps, losses, wall seconds, host seconds spent in
    each of the three spans)."""
    from jax.profiler import TraceAnnotation
    losses = []
    host = {"feed": 0.0, "step": 0.0, "sync": 0.0}
    t0 = now = time.perf_counter()
    deadline = t0 + seconds

    def spent(name, since):
        t = time.perf_counter()
        host[name] += t - since
        return t

    while True:
        with TraceAnnotation("mxbench/feed"):
            batch = feed.next()
        now = spent("feed", now)
        with TraceAnnotation("mxbench/step"):
            losses.append(loop.step(*batch))
        now = spent("step", now)
        if len(losses) > inflight:
            with TraceAnnotation("mxbench/sync"):
                loop.wait(losses[-1 - inflight])
            now = spent("sync", now)
        if now >= deadline:
            break
    with TraceAnnotation("mxbench/sync"):
        loop.wait_all(losses[-1])
    now = spent("sync", now)
    return len(losses), losses, now - t0, host


def run(ctx) -> Run:
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import compilewatch
    tr = ctx.traffic
    chips = len(ctx.devices)
    per_chip = int(tr["batch_per_chip"])
    batch, seq = per_chip * chips, tr.get("seq")
    inflight = int(tr["inflight_steps"])
    (rate,) = [m for m in ctx.cell["metrics"] if m != "setup_s"]

    ctx.say("imports done; checking against the reference")
    correct = check_against_reference(ctx, seq)

    mx.random.seed(ctx.seed % (2 ** 31))
    loop = LOOPS[tr["loop"]](ctx, batch, float(tr.get("dropout", 0.0)), seq)
    feed = FEEDS[tr["feed"]["type"]](ctx, batch, seq)
    ctx.say("loop and feed built; warming %d steps" % int(tr["warmup_steps"]))
    try:
        # warm this cell's shapes: the step's program(s), the feed's
        # device-side program, and the readback
        n, losses, _, _ = measure(loop, feed, 0.0, inflight)
        for _ in range(int(tr["warmup_steps"]) - n):
            losses.append(loop.step(*feed.next()))
        loop.wait_all(losses[-1])
        loop.loss_values(losses)
        compiled = loop.compiled()
        if compiled is not None:
            mem = compiled.memory_analysis()
            ctx.say("compiled step memory_analysis: arguments %d bytes, "
                    "temporaries %d bytes, outputs %d bytes (aliased %d)"
                    % (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
                       mem.output_size_in_bytes, mem.alias_size_in_bytes))
            ctx.say("tpu_custom_call by kernel in the compiled step: %s"
                    % meters.kernel_counts(compiled.as_text()))
        del losses, compiled
        gc.collect()

        seconds = ctx.seconds
        trace_dir = untraced = None
        if ctx.trace and not ctx.rehearse:
            seconds = min(seconds, float(tr["trace_seconds"]))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1      # the mxbench/* spans, no more
            # The same window untraced first, on the host's clock: on
            # this stack the profiler slows a large host-to-device copy
            # up to 45-fold (19 MB: 21 ms -> 900 ms, for how long into a
            # session varied from run to run; my chip runs, PR 23), so
            # what the host waits for is read from here and only what
            # the device does from the trace.
            n, _, wall, host = measure(loop, feed, seconds, inflight)
            untraced = {"wall": wall / n}
            untraced.update({k: v / n for k, v in host.items()})
            trace_dir = tempfile.mkdtemp(prefix="mxbench_trace_")
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark = ctx.meter.mark()
        programs = len(compilewatch.programs())
        setup_s = ctx.clock.now()
        try:
            steps, losses, wall, host = measure(loop, feed, seconds,
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
        ctx.say("memory_stats of device 0: %s" % (
            None if ctx.rehearse else ctx.devices[0].memory_stats()))
        correct = correct and failed == 0 and late == 0 and watched == 0
        peak = 0 if ctx.rehearse else meters.peak_bytes(ctx.devices)
    finally:
        feed.close()

    run = Run(cell=ctx.cell, sizes=ctx.sizes, traffic=tr,
              device_kind=ctx.devices[0].device_kind, chips=chips,
              correct=bool(correct), attempted=steps, failed=failed,
              end_to_end={
                  rate: (steps * batch / wall, UNITS[rate]),
                  "setup_s": (setup_s, UNITS["setup_s"])},
              window_s=wall, samples=steps * batch,
              flops_per_sample=ctx.cfgmod.train_flops_per_sample(
                  ctx.sizes, seq),
              peak_bytes=peak, setup_compiles=mark[0],
              setup_compile_s=mark[1], setup_cache_hits=mark[2],
              untraced_s_per_step=untraced)
    if trace_dir is not None:
        try:
            run.trace = T.load(T.find_xplane(trace_dir))
            run.trace_window = T.window_of(run.trace)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return run
