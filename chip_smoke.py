"""Standing proof that the main path starts on the chip.

    python chip_smoke.py              one TPU chip: device, native build,
                                      train/resnet50, train/bert_base,
                                      serve, timing sanity
    python chip_smoke.py --chips 4    four chips, and only that: BERT-base
                                      dp=4 vs one device, the per-shard
                                      kernels in the compiled dp=4 step,
                                      dropout's masks a shard, then the Gluon
                                      split_and_load loop on 4 contexts vs 1
    python chip_smoke.py --rehearse   the same control flow at toy sizes on
                                      whatever JAX finds (the CPU, with
                                      kernels interpreted); proves nothing
                                      about a chip and never reports "ok"

One process: a chip belongs to the process that first touches JAX. Every
phase fails loudly (an exception, non-zero exit); nothing is wrapped in a
catch-all. Weights and data come from --seed. A smoke, not a benchmark:
the timings it prints are sanity lines, no rate is derived from them.

Last stdout line of a passing chip run, and nothing after it:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

# compilewatch (the recompile counters) rides the telemetry gate; the
# comm watch would close every ShardedTrainStep.step with a readback,
# which the timing-sanity phase must do itself
os.environ["MXNET_TELEMETRY"] = "1"
os.environ["MXNET_COMMWATCH"] = "0"

BERT_BASE = dict(num_layers=12, units=768, hidden_size=3072, num_heads=12)
BERT_TOY = dict(num_layers=2, units=128, hidden_size=256, num_heads=2,
                vocab_size=512, max_length=64)


def say(phase, msg):
    print("[%s] %s" % (phase, msg), flush=True)


class CompileMeter:
    """Every XLA backend compile of the process, as JAX itself reports
    them (jax.monitoring) — the tiny eager-op programs included, so
    "no compile in steps 3-5" means none of any kind. A persistent-cache
    hit still passes through here, with a small duration."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, seconds, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.count, self.seconds, self.cache_hits

    def since(self, mark):
        return (self.count - mark[0], self.seconds - mark[1],
                self.cache_hits - mark[2])


def peak_bytes(dev):
    stats = dev.memory_stats()
    return stats["peak_bytes_in_use"] if stats else None


def check_falling(phase, losses):
    assert all(np.isfinite(losses)), (phase, losses)
    assert losses[-1] < losses[0], \
        "%s: loss did not fall over %d steps: %s" % (phase, len(losses),
                                                     losses)


def run_steps(phase, meter, m0, one_step, n=5):
    """n steps of ``one_step() -> python float``; steps 3..n must not
    compile anything. ``m0``: the meter's mark at the start of the
    phase, so the compile line counts its set-up too."""
    from mxnet_tpu import compilewatch
    losses = []
    for i in range(n):
        if i == 2:
            warm, programs = meter.mark(), len(compilewatch.programs())
        t0 = time.perf_counter()
        losses.append(one_step())
        say(phase, "step %d loss %.5f (%.2fs)"
            % (i + 1, losses[-1], time.perf_counter() - t0))
    if n > 2:
        late = meter.since(warm)[0]
        watched = len(compilewatch.programs()) - programs
        assert late == 0 and watched == 0, \
            "%s: %d XLA compile(s), %d watched program(s) after step 2" \
            % (phase, late, watched)
    n_c, sec, hits = meter.since(m0)
    say(phase, "compiles %d (%.1fs in the compiler, %d persistent-cache "
        "hits); none after step 2" % (n_c, sec, hits))
    return losses


# ---------------------------------------------------------------------------
# phases of the one-chip run
# ---------------------------------------------------------------------------
def phase_device(rehearse):
    import jax
    import jaxlib
    import mxnet_tpu as mx
    from mxnet_tpu import runtime
    from mxnet_tpu.ops.pallas_common import interpret_mode
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    say("device", "%s; jax %s jaxlib %s libtpu %s"
        % (json.dumps(device), jax.__version__, jaxlib.__version__,
           libtpu_version))
    if not rehearse:
        assert runtime.require_accelerator() == device
        assert d0.platform == "tpu", device
        assert mx.tpu(0).jax_device == d0, (mx.tpu(0).jax_device, d0)
        assert mx.current_context() == mx.tpu(0), mx.current_context()
        assert interpret_mode() is False
    say("device", "compile cache at %s" % runtime.enable_compile_cache())
    return device


def phase_native():
    """The .so files are not checked in: build both from io.cc and
    engine.cc on this machine and load them. make and g++ never touch
    JAX, so the child cannot contend for the chip."""
    from mxnet_tpu import native
    proc = subprocess.run(
        ["make", "-C", os.path.join(ROOT, "mxnet_tpu", "native"),
         "clean", "all"], capture_output=True, text=True, timeout=300)
    if proc.returncode:
        print(proc.stdout + proc.stderr, flush=True)
        raise SystemExit("native build failed (exit %d)" % proc.returncode)
    assert native.load_io_lib() is not None
    assert native.load_engine_lib() is not None
    say("native", "libmxtpu_io.so and libmxtpu_engine.so built and loaded")


def phase_resnet50(meter, seed, rehearse):
    """The reference-idiomatic Gluon loop bench.py measures: hybridize,
    autograd.record, backward, Trainer('sgd', kvstore='device').step,
    AMP bf16."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.contrib import amp
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    batch, side = (2, 64) if rehearse else (128, 224)
    m0 = meter.mark()
    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    xs = nd.array(rng.rand(batch, 3, side, side).astype(np.float32))
    ys = nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32))
    amp.init(target_dtype="bfloat16")
    try:
        net = resnet50_v1()
        net.initialize(init=mx.initializer.MSRAPrelu())
        net(nd.ones((2, 3, side, side)))  # resolve deferred shapes
        net.hybridize(static_alloc=True, static_shape=True)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.02, "momentum": 0.9},
                                kvstore="device")
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        loss_fn.hybridize(static_alloc=True, static_shape=True)

        def one_step():
            with autograd.record():
                loss = loss_fn(net(xs), ys)
            loss.backward()
            trainer.step(batch)
            return jax.device_get(loss.mean()._jax()).item()

        losses = run_steps("train/resnet50", meter, m0, one_step)
    finally:
        amp.reset()  # the next phases cast for themselves
    check_falling("train/resnet50", losses)
    say("train/resnet50", "batch %d %dx%d bf16: loss %.4f -> %.4f; peak "
        "memory %s bytes" % (batch, side, side, losses[0], losses[-1],
                             peak_bytes(jax.devices()[0])))


def kernel_counts(compiled_text):
    """tpu_custom_call sites of a compiled program by Pallas kernel
    name (the pallas_call's ``name``, kept in the op_name metadata)."""
    names = collections.Counter()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            op_name = re.search(r'op_name="([^"]*)"', line)
            found = re.findall(r"pallas_(?!call)\w+",
                               op_name.group(1) if op_name else "")
            names[found[-1] if found else "unnamed"] += 1
    return dict(names)


# every Pallas kernel docs/KERNELS.md lists as on by default on the
# BERT training path
BERT_KERNELS = ("pallas_layer_norm_fwd", "pallas_layer_norm_bwd",
                "pallas_selfatt_packed_fwd", "pallas_selfatt_packed_bwd",
                "pallas_dropout_fwd", "pallas_dropout_bwd")


# of those, the ones that keep their XLA compositions in a program
# partitioned over a mesh (ops/pallas_norm.py says why)
MESH_COMPOSITIONS = ("pallas_layer_norm_fwd", "pallas_layer_norm_bwd")


def seq_out(outputs):
    """The encoder's (batch, seq, units) output: a block with one
    output returns it bare, with several as a tuple."""
    return outputs[0] if isinstance(outputs, (list, tuple)) else outputs


def make_bert(rehearse, dropout):
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel
    cfg = BERT_TOY if rehearse else BERT_BASE
    return BERTModel(dropout=dropout, use_pooler=False,
                     use_classifier=False, use_decoder=False, **cfg)


def phase_bert(meter, seed, rehearse):
    """tools/bert_bench.py's build_step: bf16 compute on fp32 masters,
    ShardedTrainStep(optimizer="lamb"), dropout 0.1, chunked-CE head."""
    import jax
    import mxnet_tpu as mx
    from bert_bench import build_step
    batch, seq = (4, 32) if rehearse else (32, 128)
    m0 = meter.mark()
    mx.random.seed(seed)
    step, data = build_step(batch, seq, net=make_bert(rehearse, 0.1))

    def one_step():
        return jax.device_get(step.step(*data)).item()

    losses = run_steps("train/bert_base", meter, m0, one_step)
    check_falling("train/bert_base", losses)
    if not rehearse:
        # on the chip the step is AOT-compiled once per data shape
        assert len(step._compiled) == 1, list(step._compiled)
        found = kernel_counts(next(iter(step._compiled.values())).as_text())
        say("train/bert_base", "tpu_custom_call by kernel: %s"
            % json.dumps(found, sort_keys=True))
        missing = [k for k in BERT_KERNELS if not found.get(k)]
        assert not missing, \
            "default-on Pallas kernels absent from the compiled BERT " \
            "step: %s" % missing
        assert set(found) <= set(BERT_KERNELS), found
    say("train/bert_base", "batch %d seq %d lamb: loss %.4f -> %.4f; peak "
        "memory %s bytes" % (batch, seq, losses[0], losses[-1],
                             peak_bytes(jax.devices()[0])))
    return step, data


def kept_share(y, dx, p):
    """The share of a block of ones that dropout kept, once it is about
    1-p, scaled by 1/(1-p), and the backward's mask the forward's."""
    kept = float((y != 0).mean())
    assert abs(kept - (1 - p)) < 0.01, kept
    np.testing.assert_allclose(y[y != 0], 1 / (1 - p), rtol=1e-2)
    np.testing.assert_array_equal(y, dx)
    return kept


def phase_dropout_kernel(rehearse):
    """The in-kernel-PRNG dropout has no interpreter form, so no CPU
    test has ever executed it: check on the chip that it keeps about
    1-p of the elements, scaled by 1/(1-p), and that the backward
    regenerates the forward's mask."""
    if rehearse:
        return say("dropout", "skipped: pltpu PRNG has no interpreter")
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_dropout import (pallas_dropout,
                                              pallas_dropout_available)
    p, shape = 0.1, (128, 32, 768)
    assert pallas_dropout_available(shape, jnp.bfloat16, p)
    x = jnp.ones(shape, jnp.bfloat16)
    key = jax.random.key(0)
    y, vjp = jax.vjp(lambda a: pallas_dropout(key, a, p), x)
    (dx,) = vjp(jnp.ones_like(y))
    y, dx = np.asarray(y, np.float32), np.asarray(dx, np.float32)
    kept = kept_share(y, dx, p)
    say("dropout", "kept %.4f of %d elements (p=%.1f); backward mask == "
        "forward mask" % (kept, y.size, p))


def phase_serve(meter, seed, rehearse):
    """InferenceSession + Scheduler over the BERT encoder's forward in
    bf16, sequence-bucketed. The zoo's BERT takes no length mask: a
    padded position is a real token to it. So the reference for a
    request is the block's own direct forward over that request padded
    (token 0) to its bucket's length, cut back to the request's length —
    what the session is documented to compute; requests whose length is
    a rung are compared with the plain direct forward."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd, serve
    lengths, rungs, vocab = ([32, 48, 64, 100, 128, 40, 96, 64],
                             (32, 64, 128), 30522)
    if rehearse:
        lengths, rungs, vocab = [8, 12, 16, 25, 32, 10, 24, 16], \
            (8, 16, 32), BERT_TOY["vocab_size"]
    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    net = make_bert(rehearse, 0.1)
    net.initialize()
    net.cast("bfloat16")
    top = rungs[-1]
    example = (nd.array(rng.randint(0, vocab, (1, top)).astype(np.float32)),
               nd.zeros((1, top)))
    m0 = meter.mark()
    sess = serve.InferenceSession(
        net, example_inputs=example, seq_axis=1,
        buckets="4;" + ",".join(map(str, rungs)))
    sched = serve.Scheduler(sess)
    try:
        reqs = [rng.randint(0, vocab, (1, n)).astype(np.float32)
                for n in lengths]
        futs = [sched.submit(ids, np.zeros_like(ids)) for ids in reqs]
        answers = [f.result(timeout=600) for f in futs]
    finally:
        sched.close()
    buckets = [row["bucket"] for row in sess.bucket_table()
               if row["warmed"]]
    assert len(buckets) >= 2, sess.bucket_table()
    worst = 0.0
    for ids, got in zip(reqs, answers):
        n = ids.shape[1]
        rung = next(r for r in rungs if n <= r)
        padded = np.zeros((1, rung), np.float32)
        padded[:, :n] = ids
        with autograd.pause():
            want = seq_out(net(nd.array(padded), nd.zeros((1, rung))))
        want = want.asnumpy().astype(np.float32)[:, :n]
        got = np.asarray(seq_out(got), np.float32)
        assert got.shape == want.shape == (1, n, want.shape[-1]), \
            (got.shape, want.shape)
        assert np.isfinite(got).all()
        # LayerNorm'd activations are O(1); bf16 keeps 8 bits
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
        worst = max(worst, float(np.abs(got - want).max()))
    n_c, sec, hits = meter.since(m0)
    say("serve", "8 requests of lengths %s served from buckets %s; max "
        "|session - direct| %.4f; compiles %d (%.1fs, %d cache hits)"
        % (lengths, sorted(buckets), worst, n_c, sec, hits))


def phase_timing(step, data):
    """One warm BERT step, closed two ways. If block_until_ready did
    not wait for the device, the first figure would be the enqueue time
    — a small fraction of the second."""
    import jax
    t0 = time.perf_counter()
    step.step(*data).block_until_ready()
    bur = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.device_get(step.step(*data)).item()
    get = time.perf_counter() - t0
    say("timing", "one warm bert step: block_until_ready %.2f ms, "
        "device_get().item() %.2f ms (sanity line, not a benchmark)"
        % (bur * 1e3, get * 1e3))
    assert 0.5 < bur / get < 2.0, \
        "the two closings disagree: %.2f ms vs %.2f ms" % (bur * 1e3,
                                                           get * 1e3)


# ---------------------------------------------------------------------------
# --chips 4: only what exists across chips, and what it is compared with
# ---------------------------------------------------------------------------
def collectives_in(compiled_text):
    return dict(collections.Counter(re.findall(
        r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", compiled_text)))


def assert_four_busy(phase, devs):
    if devs[0].platform == "cpu":   # rehearsal: no memory_stats there
        return say(phase, "memory_stats: not reported by the CPU backend")
    used = [d.memory_stats()["bytes_in_use"] for d in devs]
    say(phase, "bytes in use per device: %s" % used)
    assert all(b > 0 for b in used), used


def phase_dp4_sharded(meter, seed, rehearse, devs):
    """BERT-base ShardedTrainStep on MeshConfig(dp=4), global batch
    256, against the same seed and global batch on one device. Dropout
    0 on both sides: the only difference is where the work ran. (A
    batch unlike the length: a row-wise kernel finds the dimension to
    run a shard at a time on by the batch's size.) Then the dp=4 step
    at dropout 0.1: every kernel that runs once a shard is a custom
    call of the compiled step, the norm is not (and the Dense epilogues
    are XLA's fusions on any device)."""
    import jax
    import mxnet_tpu as mx
    from bert_bench import build_step
    from mxnet_tpu.parallel import MeshConfig, make_mesh
    batch, seq = (8, 32) if rehearse else (256, 128)
    runs = {}
    for name, mesh_devs in (("dp=4", devs), ("one device", devs[:1])):
        mx.random.seed(seed)
        mesh = make_mesh(MeshConfig(dp=len(mesh_devs)), devices=mesh_devs)
        step, data = build_step(batch, seq, net=make_bert(rehearse, 0.0),
                                mesh=mesh)
        m0 = meter.mark()
        losses = [jax.device_get(step.step(*data)).item() for _ in range(3)]
        say("dp4/sharded", "%s: losses %s (%d compiles, %.1fs)"
            % ((name, losses) + meter.since(m0)[:2]))
        if len(mesh_devs) > 1:
            on = set().union(*(v.sharding.device_set
                               for v in step.params.values()))
            assert on == set(devs), on
            batch_arr = jax.device_put(data[0]._jax(), step.data_shardings[0])
            shard_devs = {s.device for s in batch_arr.addressable_shards}
            assert shard_devs == set(devs), shard_devs
            assert batch_arr.addressable_shards[0].data.shape[0] \
                == batch // len(devs)
            assert_four_busy("dp4/sharded", devs)
            if step._compiled:
                say("dp4/sharded", "collectives in the compiled step: %s"
                    % collectives_in(next(iter(
                        step._compiled.values())).as_text()))
        runs[name] = losses
        del step, data
        gc.collect()
    assert all(np.isfinite(runs["dp=4"])), runs
    np.testing.assert_allclose(runs["dp=4"], runs["one device"], rtol=2e-2)
    mx.random.seed(seed)
    step, data = build_step(batch, seq, net=make_bert(rehearse, 0.1),
                            mesh=make_mesh(MeshConfig(dp=len(devs)),
                                           devices=devs))
    loss = jax.device_get(step.step(*data)).item()
    assert np.isfinite(loss), loss
    if not rehearse:
        found = kernel_counts(next(iter(step._compiled.values())).as_text())
        say("dp4/sharded", "dropout 0.1: loss %.4f; tpu_custom_call by "
            "kernel: %s" % (loss, json.dumps(found, sort_keys=True)))
        missing = [k for k in BERT_KERNELS
                   if k not in MESH_COMPOSITIONS and not found.get(k)]
        assert not missing, \
            "Pallas kernels that run once a shard absent from the " \
            "compiled dp=4 BERT step: %s" % missing
        assert not set(found) & set(MESH_COMPOSITIONS), found


def phase_dp4_dropout_kernel(rehearse, devs):
    """phase_dropout_kernel's checks a shard: the in-kernel-PRNG
    dropout inside a program partitioned over the four chips keeps
    about 1-p of every shard's elements, its backward regenerates that
    shard's forward mask, and no two shards draw one mask."""
    if rehearse:
        return say("dp4/dropout", "skipped: pltpu PRNG has no interpreter")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from mxnet_tpu.ops.pallas_common import auto_partitioned
    from mxnet_tpu.ops.pallas_dropout import (pallas_dropout,
                                              pallas_dropout_available)
    from mxnet_tpu.parallel import MeshConfig, make_mesh
    p, shape = 0.1, (128, 512, 768)
    mesh = make_mesh(MeshConfig(dp=len(devs)), devices=devs)

    def both(x):
        with auto_partitioned(mesh, batch=("dp", shape[1])):
            assert pallas_dropout_available(shape, jnp.bfloat16, p)
            y, vjp = jax.vjp(
                lambda a: pallas_dropout(jax.random.key(0), a, p), x)
        return y, vjp(jnp.ones_like(y))[0]

    rows = NamedSharding(mesh, PartitionSpec(None, "dp"))
    fn = jax.jit(both, in_shardings=rows)
    x = jax.device_put(jnp.ones(shape, jnp.bfloat16), rows)
    found = kernel_counts(fn.lower(x).compile().as_text())
    assert found == {"pallas_dropout_fwd": 1, "pallas_dropout_bwd": 1}, found
    y, dx = fn(x)
    assert y.sharding.is_equivalent_to(rows, 3), y.sharding
    y, dx = np.asarray(y, np.float32), np.asarray(dx, np.float32)
    shards = np.split(y, len(devs), axis=1)
    kept = [kept_share(s, ds, p)
            for s, ds in zip(shards, np.split(dx, len(devs), axis=1))]
    same = [(i, j) for i in range(len(shards)) for j in range(i)
            if np.array_equal(shards[i], shards[j])]
    assert not same, "shards drew one mask: %s" % same
    say("dp4/dropout", "kept %s of a shard's elements (p=%.1f); backward "
        "mask == forward mask on every shard; no two shards' masks alike"
        % (["%.4f" % k for k in kept], p))


def phase_dp4_gluon(meter, seed, rehearse, devs):
    """The MXNet-idiomatic data-parallel loop on the same block
    (LayerNorm only, so no per-device batch statistics):
    split_and_load over four contexts, Trainer(kvstore='device'),
    against the same loop on one context."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.contrib import amp
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMLoss
    batch, seq = (8, 32) if rehearse else (128, 128)
    vocab = BERT_TOY["vocab_size"] if rehearse else 30522
    units = (BERT_TOY if rehearse else BERT_BASE)["units"]
    rng = np.random.RandomState(seed)
    x = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    y = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    t = np.zeros((batch, seq), np.float32)
    all_ctx = [mx.tpu(i) for i in range(len(devs))]
    assert [c.jax_device for c in all_ctx] == list(devs)
    runs = {}
    amp.init(target_dtype="bfloat16")
    try:
        for name, ctxs in (("4 contexts", all_ctx), ("1 context",
                                                     all_ctx[:1])):
            mx.random.seed(seed)
            net = make_bert(rehearse, 0.0)
            head = BERTMLMLoss(vocab_size=vocab, units=units,
                               prefix="decoder_")
            net.initialize(ctx=ctxs)
            head.initialize(ctx=ctxs)
            net.hybridize(static_alloc=True, static_shape=True)
            head.hybridize(static_alloc=True, static_shape=True)
            params = net.collect_params()
            params.update(head.collect_params())
            trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.05},
                                    kvstore="device")
            m0 = meter.mark()
            losses = []
            for _ in range(3):
                parts = [gluon.utils.split_and_load(a, ctxs)
                         for a in (x, t, y)]
                with autograd.record():
                    ls = [head(seq_out(net(xi, ti)), yi).sum()
                          for xi, ti, yi in zip(*parts)]
                for l in ls:
                    l.backward()
                trainer.step(batch * seq)
                losses.append(sum(l.asnumpy().item() for l in ls)
                              / (batch * seq))
            say("dp4/gluon", "%s: losses %s (%d compiles, %.1fs)"
                % ((name, losses) + meter.since(m0)[:2]))
            if len(ctxs) > 1:
                w = params[sorted(params.keys())[0]]
                on = {next(iter(a._jax().sharding.device_set))
                      for a in w.list_data()}
                assert on == set(devs), on
                shard_on = {next(iter(a._jax().sharding.device_set))
                            for a in parts[0]}
                assert shard_on == set(devs), shard_on
                assert_four_busy("dp4/gluon", devs)
            runs[name] = losses
            del net, head, params, trainer, parts, ls
            gc.collect()
    finally:
        amp.reset()
    assert all(np.isfinite(runs["4 contexts"])), runs
    np.testing.assert_allclose(runs["4 contexts"], runs["1 context"],
                               rtol=2e-2)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any platform; never reports ok")
    args = ap.parse_args()

    t_start = time.perf_counter()
    device = phase_device(args.rehearse)
    if device["count"] < args.chips:
        raise SystemExit("--chips %d asked, JAX reports %d device(s)"
                         % (args.chips, device["count"]))
    meter = CompileMeter()
    import jax
    if args.chips == 4:
        devs = jax.devices()[:4]
        phase_dp4_sharded(meter, args.seed, args.rehearse, devs)
        phase_dp4_dropout_kernel(args.rehearse, devs)
        phase_dp4_gluon(meter, args.seed, args.rehearse, devs)
    else:
        phase_native()
        phase_resnet50(meter, args.seed, args.rehearse)
        gc.collect()
        step, data = phase_bert(meter, args.seed, args.rehearse)
        phase_timing(step, data)
        del step, data
        gc.collect()
        phase_dropout_kernel(args.rehearse)
        phase_serve(meter, args.seed, args.rehearse)
    say("done", "%.0fs; %d XLA compiles, %.1fs in the compiler, %d "
        "persistent-cache hits; peak memory %s bytes"
        % ((time.perf_counter() - t_start,) + meter.mark()
           + (peak_bytes(jax.devices()[0]),)))
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
