#!/usr/bin/env python
"""Chaos harness: short training loop under randomized fault injection,
asserting clean resume (CI smoke for docs/FAULT_TOLERANCE.md).

Per round (seeded, reproducible):

1. Train a reference model N epochs fault-free; record final params.
2. Train a chaos model with per-epoch crash-safe checkpoints while a
   randomly chosen epoch's checkpoint write is killed mid-flight
   (``ckpt_write`` injection) and, optionally, DataLoader workers are
   OOM-killed on their first task (``dl_worker`` injection, exercising
   the respawn supervisor).
3. Simulate the job restart: a FRESH model resumes from the newest
   valid checkpoint (manifest-scanned, checksum-validated) and
   finishes.
4. Assert the resumed run's final params equal the fault-free run's.

``--nan-inject`` switches to the training-guardrails mode
(docs/GUARDRAILS.md): per round, a guarded run (MXNET_GUARD_NONFINITE=
skip_step via an installed GradGuard) trains while the ``nan_grad``
faultinject site poisons gradients on randomly chosen steps; the round
asserts the run FINISHES, final params are finite, and the guard counted
a nonzero number of skipped steps. A final POSTMORTEM round then runs
under the raise policy with modelwatch + MXNET_CRASH_BUNDLE_DIR armed:
the poisoned step must kill the run AND leave behind a crash bundle
(telemetry.crash_bundle) whose anomaly record NAMES the injected
parameter — every chaos crash ships its own diagnosis
(docs/OBSERVABILITY.md 'Crash bundles').

Usage: python tools/chaos_run.py [--seed 0] [--rounds 3] [--epochs 4]
                                 [--nan-inject]
Exit code 0 = every round resumed cleanly.
"""
from __future__ import annotations

import argparse
import os
import random
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def make_estimator(seed, contexts=None, opt_args=None):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Dense(1)
    if contexts:
        net.initialize(mx.initializer.Xavier(), ctx=list(contexts))
    else:
        net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            dict(opt_args or {"learning_rate": 0.05}))
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[mx.metric.MSE()], trainer=trainer,
                    context=list(contexts) if contexts else None)
    return net, est


def make_loader(num_workers=0):
    from mxnet_tpu import gluon
    rs = np.random.RandomState(0)
    X = rs.randn(64, 4).astype(np.float32)
    Y = (X @ np.array([[1.0], [2.0], [-1.0], [0.5]],
                      np.float32)).astype(np.float32)
    return gluon.data.DataLoader(gluon.data.ArrayDataset(X, Y),
                                 batch_size=8, num_workers=num_workers)


def final_params(net):
    return {k: p.data().asnumpy()
            for k, p in net._structural_params().items()}


def run_round(rng, epochs, workdir, rnd):
    from mxnet_tpu import faultinject
    prefix = os.path.join(workdir, "chaos-r%d" % rnd)
    init_seed = rng.randrange(1 << 30)
    crash_epoch = rng.randrange(1, epochs)       # never the last epoch
    kill_workers = rng.random() < 0.5
    num_workers = 2 if kill_workers and hasattr(os, "fork") else 0
    print("[round %d] init_seed=%d crash_epoch=%d dl_worker_kill=%s"
          % (rnd, init_seed, crash_epoch, kill_workers), flush=True)

    # 1) fault-free reference
    faultinject.reset()
    net_ref, est_ref = make_estimator(init_seed)
    est_ref.fit(make_loader(), epochs=epochs)
    ref = final_params(net_ref)

    # 2) chaos run: checkpoint each epoch; the crash_epoch write dies
    faultinject.reset()
    net1, est1 = make_estimator(init_seed)
    if num_workers:
        faultinject.set_fault("dl_worker", 1.0)   # respawn supervisor
    try:
        est1.fit(make_loader(num_workers), epochs=crash_epoch,
                 ckpt_prefix=prefix)
        faultinject.set_fault("ckpt_write", 1.0, max_fires=1)
        est1.fit(make_loader(num_workers), epochs=crash_epoch + 1,
                 ckpt_prefix=prefix, resume=True)
    except Exception as e:
        print("[round %d] checkpoint write lost as planned: %s"
              % (rnd, str(e)[:80]), flush=True)
    else:
        raise AssertionError("injected ckpt_write fault never surfaced")
    finally:
        faultinject.reset()
    bad = "%s-%04d.params" % (prefix, crash_epoch + 1)
    assert not os.path.exists(bad), \
        "truncated checkpoint %s was published" % bad

    # 3) "restarted job": fresh net resumes from the newest VALID ckpt
    net2, est2 = make_estimator(init_seed)
    resumed = est2.resume_from(prefix)
    assert resumed == crash_epoch, (resumed, crash_epoch)
    est2.fit(make_loader(), epochs=epochs, ckpt_prefix=prefix,
             resume=True)

    # 4) clean resume == fault-free result
    got = final_params(net2)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-6)
    print("[round %d] resumed from epoch %d; final params match "
          "fault-free run" % (rnd, resumed), flush=True)


def run_nan_round(rng, epochs, rnd, workdir=None):
    """Guardrails mode: train under random NaN-gradient injection with
    the skip_step policy; the run must finish with finite params and a
    nonzero skipped-step count (ISSUE 2 acceptance). With `workdir`,
    per-epoch checkpoints ride along so the run also exercises the
    async engine path (ISSUE 3: engine op spans + checkpoint counters
    show up in the telemetry a test can assert on)."""
    import numpy as np
    from mxnet_tpu import faultinject, guardrails
    init_seed = rng.randrange(1 << 30)
    nan_prob = 0.35 + 0.35 * rng.random()
    print("[nan round %d] init_seed=%d nan_prob=%.2f"
          % (rnd, init_seed, nan_prob), flush=True)
    faultinject.reset()
    net, est = make_estimator(init_seed)
    guard = guardrails.GradGuard(nonfinite="skip_step")
    est.trainer.grad_guard = guard
    events = []
    unsub = guardrails.on_event(events.append)
    faultinject.set_fault("nan_grad", nan_prob)
    prefix = os.path.join(workdir, "nan-r%d" % rnd) if workdir else None
    try:
        est.fit(make_loader(), epochs=epochs, ckpt_prefix=prefix)
    finally:
        unsub()
        faultinject.reset()
    assert guard.skipped_steps > 0, \
        "nan_grad never fired (prob=%.2f) — raise --epochs" % nan_prob
    for k, v in final_params(net).items():
        assert np.isfinite(v).all(), \
            "param %s went non-finite despite skip_step guard" % k
    skips = sum(1 for e in events if e["kind"] == "skip")
    assert skips == guard.skipped_steps, (skips, guard.skipped_steps)
    assert guard.sync_count == guard.steps, \
        "guard must cost exactly one device sync per checked step"
    print("[nan round %d] finished: %d/%d steps skipped, params finite"
          % (rnd, guard.skipped_steps, guard.steps), flush=True)


def run_postmortem_round(rng, workdir):
    """Crash-bundle acceptance (ISSUE 11): train under modelwatch with
    the raise policy and a one-shot nan_grad injection; the run must
    die with NonFiniteGradientError AND publish exactly one bundle
    directory whose anomaly record names the poisoned parameter."""
    import json
    import numpy as np
    from mxnet_tpu import faultinject, guardrails, telemetry
    bundle_dir = os.path.join(workdir, "bundles")
    os.makedirs(bundle_dir, exist_ok=True)
    init_seed = rng.randrange(1 << 30)
    print("[postmortem round] init_seed=%d bundle_dir=%s"
          % (init_seed, bundle_dir), flush=True)
    prior = {k: os.environ.get(k)
             for k in ("MXNET_TELEMETRY", "MXNET_MODELWATCH",
                       "MXNET_CRASH_BUNDLE_DIR")}
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_MODELWATCH"] = "1"
    os.environ["MXNET_CRASH_BUNDLE_DIR"] = bundle_dir
    telemetry.refresh()
    faultinject.reset()
    try:
        net, est = make_estimator(init_seed)
        guard = guardrails.GradGuard(nonfinite="raise")
        est.trainer.grad_guard = guard
        # a few clean epochs first so the flight-recorder ring holds
        # real history, then a one-shot poison
        est.fit(make_loader(), epochs=2)
        faultinject.set_fault("nan_grad", 1.0, max_fires=1)
        died = False
        try:
            est.fit(make_loader(), epochs=4)
        except guardrails.NonFiniteGradientError as e:
            died = True
            print("[postmortem round] guard raised as designed: %s"
                  % str(e)[:80], flush=True)
        assert died, "raise policy never fired on the injected NaN"
        bundles = [d for d in os.listdir(bundle_dir)
                   if not d.startswith(".")]
        assert len(bundles) == 1, \
            "expected exactly one crash bundle, found %r" % bundles
        bpath = os.path.join(bundle_dir, bundles[0])
        files = set(os.listdir(bpath))
        need = {"anomaly.json", "modelwatch.jsonl", "telemetry.json",
                "trace.json", "programs.json", "heartbeat.txt",
                "env.txt"}
        assert need <= files, "bundle missing %r" % (need - files)
        with open(os.path.join(bpath, "anomaly.json")) as f:
            anomaly = json.load(f)
        suspect_params = [s.get("param") for s in anomaly["suspects"]]
        # nan_grad poisons the FIRST trainable parameter
        injected = est.trainer._params[0].name
        assert injected in suspect_params, \
            "bundle names %r, not the injected %r" % (suspect_params,
                                                      injected)
        ring_lines = sum(
            1 for _ in open(os.path.join(bpath, "modelwatch.jsonl")))
        assert ring_lines > 0, "flight-recorder ring is empty"
        print("[postmortem round] bundle %s names %r (%d ring entries)"
              % (bundles[0], injected, ring_lines), flush=True)
    finally:
        faultinject.reset()
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        telemetry.refresh()


def run_preempt_round(rng, epochs, workdir, rnd, zero=False):
    """Elastic-topology mode (ISSUE 16, docs/ELASTIC.md): a data-parallel
    run survives a slice preemption by resharding LIVE onto the
    surviving devices — zero restarts — and the redistribution is
    bitwise lossless, so the loss curve continues exactly as a run that
    had been handed the same state on the survivor topology.

    Per round:

    1. *Bit-parity*: train on the full device set, snapshot params +
       canonical optimizer-state blob, ``Trainer.reshard_to`` the
       survivor half, assert params AND re-gathered state blob are
       bitwise unchanged; then finish training on the survivors and
       assert final params are bitwise equal to a control run that was
       handed the snapshot on the survivor topology directly.
    2. *Zero restarts*: a full fit under MXNET_ELASTIC=1 with the
       ``slice_preempt`` faultinject site armed must finish in ONE fit
       call (no exception, no resume) with exactly one live transition
       and no checkpoint-restore degradation.
    """
    import mxnet_tpu as mx
    from mxnet_tpu import elastic, faultinject, telemetry
    import jax
    ndev = len(jax.devices())
    assert ndev >= 2, \
        "--preempt needs >=2 devices (got %d); set XLA_FLAGS=" \
        "--xla_force_host_platform_device_count=8" % ndev
    full = [mx.cpu(i) for i in range(min(8, ndev))]
    survivors = full[:max(1, len(full) // 2)]
    init_seed = rng.randrange(1 << 30)
    shrink_epoch = rng.randrange(1, epochs)
    print("[preempt round %d] init_seed=%d devices=%d->%d "
          "shrink_epoch=%d zero=%s"
          % (rnd, init_seed, len(full), len(survivors), shrink_epoch,
             zero), flush=True)
    prefix = os.path.join(workdir, "preempt-r%d" % rnd)
    faultinject.reset()
    elastic.clear()
    opt_args = {"learning_rate": 0.05, "momentum": 0.9}
    prior_zero = os.environ.get("MXNET_ZERO")
    if zero:
        os.environ["MXNET_ZERO"] = "1"
    try:
        _preempt_round_body(rng, epochs, rnd, prefix, full, survivors,
                            init_seed, shrink_epoch, opt_args)
    finally:
        if prior_zero is None:
            os.environ.pop("MXNET_ZERO", None)
        else:
            os.environ["MXNET_ZERO"] = prior_zero


def _preempt_round_body(rng, epochs, rnd, prefix, full, survivors,
                        init_seed, shrink_epoch, opt_args):
    from mxnet_tpu import faultinject, telemetry

    # --- 1) bit-parity of the redistribution itself -------------------
    net1, est1 = make_estimator(init_seed, full, opt_args)
    est1.fit(make_loader(), epochs=shrink_epoch)
    p_before = final_params(net1)
    blob_before = est1.trainer.states_blob()
    est1.trainer.reshard_to(survivors)
    est1.context = list(survivors)   # manual reshard: retarget fit too
    assert list(est1.trainer._contexts) == survivors
    p_after = final_params(net1)
    for k in p_before:
        assert (p_before[k] == p_after[k]).all(), \
            "param %s changed bits across reshard" % k
    assert est1.trainer.states_blob() == blob_before, \
        "optimizer state blob changed across reshard"
    # control: hand the SAME snapshot to a fresh run on the survivors
    net2, est2 = make_estimator(init_seed, survivors, opt_args)
    est2._restore_arg_params(p_before)
    est2.trainer.load_states_blob(blob_before)
    rest = epochs - shrink_epoch
    est1.fit(make_loader(), epochs=rest)
    est2.fit(make_loader(), epochs=rest)
    got1, got2 = final_params(net1), final_params(net2)
    for k in got1:
        assert (got1[k] == got2[k]).all(), \
            "post-reshard continuation diverged from control on %s" % k
    print("[preempt round %d] reshard bit-parity + loss continuation "
          "OK" % rnd, flush=True)

    # --- 2) zero restarts under an injected slice preemption ----------
    live_c = telemetry.counter("mx_elastic_transitions_total",
                               kind="live")
    rest_c = telemetry.counter("mx_elastic_transitions_total",
                               kind="restored")
    live0, rest0 = live_c.get(), rest_c.get()
    prior = {k: os.environ.get(k)
             for k in ("MXNET_ELASTIC", "MXNET_ELASTIC_POLL")}
    os.environ["MXNET_ELASTIC"] = "1"
    os.environ["MXNET_ELASTIC_POLL"] = "1"
    try:
        net3, est3 = make_estimator(init_seed, full, opt_args)
        est3.fit(make_loader(), epochs=1, ckpt_prefix=prefix)
        faultinject.set_fault("slice_preempt", 1.0, max_fires=1)
        # ONE fit call finishes the run: the preemption is absorbed by
        # a live reshard, never by a restart/resume
        est3.fit(make_loader(), epochs=epochs, ckpt_prefix=prefix,
                 resume=True)
        fired = faultinject.fires("slice_preempt")
    finally:
        faultinject.reset()
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert fired == 1, fired
    assert len(est3.trainer._contexts) == len(survivors), \
        est3.trainer._contexts
    assert live_c.get() - live0 == 1, \
        "expected exactly one live transition, got %r" % (
            live_c.get() - live0)
    assert rest_c.get() - rest0 == 0, \
        "run degraded to checkpoint-restore (restarted) %r times" % (
            rest_c.get() - rest0)
    for k, v in final_params(net3).items():
        assert np.isfinite(v).all(), k
    print("[preempt round %d] fit survived slice_preempt with zero "
          "restarts (1 live transition)" % rnd, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--nan-inject", action="store_true",
                    help="guardrails mode: NaN-gradient injection under "
                         "the skip_step policy (no checkpoint chaos)")
    ap.add_argument("--preempt", action="store_true",
                    help="elastic-topology mode: slice preemption "
                         "absorbed by a live reshard, zero restarts "
                         "(docs/ELASTIC.md); odd rounds run under "
                         "MXNET_ZERO")
    args = ap.parse_args(argv)

    if args.preempt:
        # must land before the first jax import (backend creation)
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

    rng = random.Random(args.seed)
    workdir = tempfile.mkdtemp(prefix="mx-chaos-")
    try:
        if args.preempt:
            for rnd in range(args.rounds):
                run_preempt_round(rng, args.epochs, workdir, rnd,
                                  zero=bool(rnd % 2))
            print("CHAOS_OK mode=preempt rounds=%d seed=%d"
                  % (args.rounds, args.seed), flush=True)
            return 0
        if args.nan_inject:
            for rnd in range(args.rounds):
                run_nan_round(rng, args.epochs, rnd, workdir)
            run_postmortem_round(rng, workdir)
            print("CHAOS_OK mode=nan-inject rounds=%d seed=%d"
                  % (args.rounds, args.seed), flush=True)
            return 0
        for rnd in range(args.rounds):
            run_round(rng, args.epochs, workdir, rnd)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print("CHAOS_OK rounds=%d seed=%d" % (args.rounds, args.seed),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
