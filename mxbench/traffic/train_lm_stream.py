"""Generator of kind ``train_lm_stream``: ``train_stream``'s job (a
training step a fresh batch, everything inside the window) for a
causal language model under ``parallel.ShardedTrainStep`` whose state
fills the chip. ``ShardedLoop``, ``measure`` and ``UNITS`` are
``train_stream``'s own, loaded from its file; this file adds what such
a model needs and that file cannot give:

- the ``token_rows`` feed: two arrays a batch, ``ids`` and ``labels``
  (a pool row holds ``seq + 1`` tokens; labels are the ids shifted by
  one), where ``token_pool`` hands BERT's three;
- a check of the one instance that is then timed, at the cell's own
  batch, length and optimizer: the fp32 masters and AdamW states of the
  system (12 bytes a parameter) and the reference's own (16, gradients
  included) do not fit one chip together, so the reference takes its
  steps first, from the seeded weights, and is gone before the system's
  instance is built from the same seed (``checked_loop``); the instance
  takes the check's steps on the check's batch, its losses are compared
  (``agree``), and the same object, state and compiled step go on into
  the warm-up and the window. ``control`` puts the reference at the
  nearest precision below the configuration's (bf16 masters) in the
  system's place: the same comparison has to call it wrong;
- after the window, the expert layers' per-expert row counts, read once
  from the step's auxiliary states (``Run.expert_rows``, beside the
  rows an expert is routed on average, ``Run.expert_even``), and, in a
  traced run, device seconds by the program's ``jax.named_scope``s
  (``mxbench/scopes.py``: ``Run.scope_seconds``) beside what one step
  executes in each (``configs/<name>.py::scope_costs``:
  ``Run.scope_costs``). Which scopes, and how the counts are read from
  the auxiliary states, is the configuration's to say
  (``configs/<name>.py``: ``SCOPES``, ``expert_rows``).

On a program whose compiled step names no such scope the map stays
empty and the readers report nothing; a program without the model's
module cannot run a cell of this kind at all and stops at the import,
before anything touches the chip.

    window = first launch .. block_until_ready(last loss, parameters)
    train_samples_per_s = steps x global batch / window   (a sample is
                                                           one sequence)

Reads from its traffic file: ``seq``, ``batch_per_chip``, ``optimizer``,
``feed`` (``pool_sequences``), ``inflight_steps``, ``warmup_steps``,
``trace_seconds``, ``toy``; the ``loop`` is ``sharded_step``. Optional:
``weights_seed`` (``seed_weights``): the initialisation is then the
mix's, and ``--seed`` draws the data alone.
"""
from __future__ import annotations

import gc
import shutil
import tempfile

import numpy as np

from mxbench import manifest, meters, scopes, trace as T
from mxbench.record import Run

_stream = manifest.load_module("traffic", "train_stream.py")
UNITS = _stream.UNITS


class TokenRowsFeed:
    """Integer ids and next-token labels drawn batch by batch from a
    host pool of rows of ``seq + 1`` tokens (a seeded order over the
    pool, wrapping), moved with ``nd.array`` at each step."""

    def __init__(self, ctx, batch, seq, check=False):
        rng = np.random.default_rng(ctx.seed)
        pool = batch if check else \
            max(int(ctx.traffic["feed"]["pool_sequences"]), batch)
        self.rows = rng.integers(0, ctx.sizes["vocab_size"], (pool, seq + 1),
                                 dtype=np.int32)
        self.order = rng.permutation(pool)
        self.batch, self.at = batch, 0

    def host_batch(self):
        if self.at + self.batch > len(self.order):
            self.at = 0
        rows = self.rows[self.order[self.at:self.at + self.batch]]
        self.at += self.batch
        return (np.ascontiguousarray(rows[:, :-1]),
                np.ascontiguousarray(rows[:, 1:]))

    def next(self):
        from mxnet_tpu import nd
        return tuple(nd.array(a, dtype="int32") for a in self.host_batch())

    def close(self):
        pass


def seed_weights(ctx):
    """Seed the model's initialisation: from the mix's ``weights_seed``
    where it names one (``--seed`` then draws the token pool and its
    order and nothing of the model: a step whose time follows the
    router's weights takes the same time under every ``--seed``), else
    from ``--seed``. The one place either is read for the weights."""
    import mxnet_tpu as mx
    mx.random.seed(int(ctx.traffic.get("weights_seed", ctx.seed)) % (2 ** 31))


def agree(got, want, chk):
    """The comparison that decides ``correct``: every loss finite, the
    first loss (the forward) within ``loss_rtol`` of the reference's,
    the change over the steps (the backward and the optimizer) within
    ``drop_rtol`` of the reference's change. (ok, the two readings.)"""
    first = abs(got[0] - want[0]) / abs(want[0])
    drop_w = want[0] - want[-1]
    drop = abs((got[0] - got[-1]) - drop_w) / abs(drop_w) \
        if len(got) > 1 else 0.0
    ok = bool(np.isfinite(got).all()) and first <= chk["loss_rtol"] \
        and drop <= chk["drop_rtol"]
    return ok, first, drop


def reference_first(ctx, batch, seq, lower=False):
    """Before the system's instance exists: the seeded weights, the
    check's batch, and the plain reference's losses over the check's
    steps (``lower``: the control's as well)."""
    steps = int(ctx.sizes["check"]["steps"])
    seed_weights(ctx)
    net, loss, _ = ctx.cfgmod.sharded_parts(ctx.sizes, 0.0, seq)
    weights = ctx.cfgmod.named_weights(net, loss)
    del net, loss
    host = TokenRowsFeed(ctx, batch, seq, check=True).host_batch()
    losses = [ctx.refmod.train_losses(weights, host, ctx.sizes,
                                      ctx.traffic["optimizer"], steps)]
    if lower:
        losses.append(ctx.refmod.train_losses(
            weights, host, ctx.sizes, ctx.traffic["optimizer"], steps,
            lower=True))
    gc.collect()
    return weights, host, losses


def checked_loop(ctx, batch, seq):
    """(the cell's one instance, whether it agrees with the reference).
    Outside the window: the reference's losses first, then the instance
    from the same seed (the same weights, held to that), which takes
    the check's steps on the check's batch. Step 1 checks the forward,
    step 2 the backward and the optimizer. What is returned is what was
    checked: one object, one compiled step, the state after the
    check's steps."""
    from mxnet_tpu import nd
    chk = ctx.sizes["check"]
    weights, host, (want,) = reference_first(ctx, batch, seq)
    ctx.say("check: reference steps done, its state freed")
    seed_weights(ctx)
    loop = _stream.ShardedLoop(ctx, batch, float(ctx.traffic.get(
        "dropout", 0.0)), seq)
    same = set(weights) == set(loop.weights) and all(
        np.array_equal(weights[k], v) for k, v in loop.weights.items())
    loop.weights = None             # 4 bytes a parameter of host
    del weights
    gc.collect()
    ctx.say("check: instance built from the same weights: %s" % same)
    dev = [nd.array(a, dtype="int32") for a in host]
    got = [float(loop.loss_values([loop.step(*dev)])[0])
           for _ in range(int(chk["steps"]))]
    ok, first, drop = agree(got, want, chk)
    ctx.say("check: system losses %s, reference %s; first loss off by "
            "%.3g (loss_rtol %g), change off by %.3g (drop_rtol %g) -> %s"
            % (got, want, first, chk["loss_rtol"], drop, chk["drop_rtol"],
               "ok" if ok and same else "WRONG"))
    return loop, ok and same


def control(ctx, batch, seq):
    """The check held against its control: the reference with bf16
    masters and the device's default products in the system's place.
    (ok, the two readings); ``ok`` has to come out False. No cell runs
    it: ``tests/mxbench_tests/test_mxbench_nemotron.py`` does at toy
    widths, PERF.md says how on the chip."""
    _, _, (want, low) = reference_first(ctx, batch, seq, lower=True)
    ok, first, drop = agree(low, want, ctx.sizes["check"])
    ctx.say("control: bf16 masters %s, reference %s; first loss off by "
            "%.3g, change off by %.3g -> %s"
            % (low, want, first, drop, "ok" if ok else "WRONG"))
    return ok, first, drop


def run(ctx) -> Run:
    import jax
    from mxnet_tpu import compilewatch
    tr = ctx.traffic
    batch, seq = int(tr["batch_per_chip"]) * len(ctx.devices), int(tr["seq"])
    inflight = int(tr["inflight_steps"])
    (rate,) = [m for m in ctx.cell["metrics"] if m != "setup_s"]

    ctx.say("imports done; checking against the reference")
    loop, correct = checked_loop(ctx, batch, seq)
    feed = TokenRowsFeed(ctx, batch, seq)
    ctx.say("loop and feed built; warming %d steps" % int(tr["warmup_steps"]))
    n, losses, _, _ = _stream.measure(loop, feed, 0.0, inflight)
    for _ in range(int(tr["warmup_steps"]) - n):
        losses.append(loop.step(*feed.next()))
    loop.wait_all(losses[-1])
    loop.loss_values(losses)
    scope_of, labels = {}, {}
    compiled = loop.compiled()
    if compiled is not None:
        mem = compiled.memory_analysis()
        ctx.say("compiled step memory_analysis: arguments %d bytes, "
                "temporaries %d bytes, outputs %d bytes (aliased %d)"
                % (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
                   mem.output_size_in_bytes, mem.alias_size_in_bytes))
        text = compiled.as_text()
        scope_of = scopes.scope_map(text, ctx.cfgmod.SCOPES)
        labels = scopes.label_map(text)
        ctx.say("tpu_custom_call by kernel in the compiled step: %s; "
                "instructions under a program scope: %d"
                % (meters.kernel_counts(text), len(scope_of)))
        del text
    del losses, compiled
    gc.collect()

    seconds = ctx.seconds
    trace_dir = untraced = None
    if ctx.trace and not ctx.rehearse:
        seconds = min(seconds, float(tr["trace_seconds"]))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the mxbench/* spans, no more
        # the same window untraced first, as train_stream: what the host
        # waits for is read with the profiler off
        n, _, wall, host = _stream.measure(loop, feed, seconds, inflight)
        untraced = {"wall": wall / n}
        untraced.update({k: v / n for k, v in host.items()})
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
    ctx.say("memory_stats of device 0: %s" % (
        None if ctx.rehearse else ctx.devices[0].memory_stats()))
    # the expert layers' counts, published as the program's gauges on
    # the way; {} for a model without expert layers
    rows = ctx.cfgmod.expert_rows(loop.step_obj.aux)
    ctx.say("rows routed to each held expert in the last step: %s"
            % {k: [int(x) for x in v] for k, v in rows.items()})
    correct = correct and failed == 0 and late == 0 and watched == 0
    peak = 0 if ctx.rehearse else meters.peak_bytes(ctx.devices)

    run = Run(cell=ctx.cell, sizes=ctx.sizes, traffic=tr,
              device_kind=ctx.devices[0].device_kind, chips=len(ctx.devices),
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
    run.expert_rows = rows
    run.expert_even = ctx.cfgmod.expert_even_share(ctx.sizes, batch * seq)
    run.scope_costs = ctx.cfgmod.scope_costs(ctx.sizes, seq, batch)
    run.scope_seconds = {}
    if trace_dir is not None:
        try:
            run.trace = T.load(T.find_xplane(trace_dir))
            run.trace_window = T.window_of(run.trace)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.scope_seconds = scopes.seconds_by_scope(
            run.trace, 0, run.trace_window, scope_of)
        named = sum(1 for o in run.trace.devices[0].ops
                    if T.op_name(o.name) in scope_of)
        busy = T.total(T.busy(run.trace, 0, run.trace_window)) / 1e9
        ctx.say("longest instructions under each program scope, ms a step: %s"
                % {k: [[n, l, round(ms, 3)] for n, l, ms in v]
                   for k, v in scopes.top_by_label(
                       run.trace, 0, run.trace_window, scope_of, labels,
                       run.traced_steps).items()})
        ctx.say("device seconds by program scope over %d traced steps "
                "(inner scopes inside their outer): %s; busy %.4f, events "
                "that run no other %.4f; %d of %d events under a scope"
                % (run.traced_steps,
                   {k: round(v, 4) for k, v in sorted(scopes.with_parents(
                       run.scope_seconds, ctx.cfgmod.SCOPES).items())},
                   busy, scopes.leaf_seconds(run.trace, 0, run.trace_window),
                   named, len(run.trace.devices[0].ops)))
    return run
