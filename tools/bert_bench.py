"""BERT-base MLM-style pretraining step benchmark (the COVERAGE_r02
flagship config: 12L/768/12H, seq 128, batch 32, bf16 compute + fp32
masters, LAMB, dropout 0.1).

Usage: python tools/bert_bench.py [batch] [seq]
           [--fusedce | --chunkedce | --densece] [--gate N]
           [--mfu-gate P] [--json]

Head selection (docs/KERNELS.md): the default follows MXNET_CHUNKED_CE
(default on -> the streaming chunked LM-head CE). --densece forces the
reference decoder + log_softmax + pick composition; --fusedce the r5
flash-style full-recompute op; --chunkedce the chunked op explicitly.

--gate N: exit nonzero when measured samples/s < N — the throughput
spelling of the 55% MFU bar (>=1250 at the pinned 12L/768/seq128/b32
config): `python tools/bert_bench.py --gate 1250`.

--mfu-gate P: the MEASURED spelling (ISSUE 6) — turn on telemetry +
commwatch, run a wall-clocked step loop, and gate on the live mx_mfu
gauge (executed FLOPs from the compiled program's cost_analysis /
wall / peak — metered, not the analytic attribution the legacy line
prints). Exits nonzero when MFU% < P OR when the meter failed to
populate (the CPU mesh has no peak FLOP/s: there the gauge populates
only with MXNET_PEAK_FLOPS stated; the 55 bar is an on-chip gate:
`python tools/bert_bench.py --mfu-gate 55`).

--json: emit one machine-comparable JSON line (the BENCH_*.json
schema shared with bench.py): samples/s, analytic TFLOP/s, measured
mfu + goodput, and per-(op,axis) comm bytes/bandwidth.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


class _MLMLoss:
    """Cross-entropy on the decoder head over every position (the
    pretraining-style dense MLM loss used for the round-2 number)."""

    def __call__(self, outputs, labels):
        from mxnet_tpu import symbol as sym_mod
        logits = outputs[-1]           # (seq, batch, vocab)
        logp = sym_mod.log_softmax(logits, axis=-1)
        picked = sym_mod.pick(logp, labels, axis=-1)
        return [sym_mod.negative(picked.mean())]


def _make_head_loss(vocab, units, mode):
    """MLM head as a PARAMETRIC loss — the model-zoo BERTMLMLoss block
    (transform-Dense + LN + fused/chunked matmul+CE; bert.py)."""
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMLoss

    blk = BERTMLMLoss(vocab_size=vocab, units=units, mode=mode,
                      prefix="decoder_")
    blk.initialize()

    class Wrapper:
        """Adapts (model outputs list, labels) -> the parametric block."""

        def __init__(self, b):
            self._blk = b

        def collect_params(self):
            return self._blk.collect_params()

        def __call__(self, outputs, labels):
            seq = outputs[0] if isinstance(outputs, (list, tuple)) \
                else outputs
            return [self._blk(seq, labels).mean()]

    return Wrapper(blk)


def build_step(batch, seq, split_update=False, head_mode="auto",
               net=None, mesh=None):
    """head_mode: 'dense' = in-model decoder + composed CE (the r2
    reference path); 'fused'/'chunked'/'auto' = parametric head loss
    (BERTMLMLoss; 'auto' follows MXNET_CHUNKED_CE).

    ``net``: an uninitialized BERTModel to train in place of BERT-base
    (chip_smoke.py: dropout 0 for the cross-device comparison, a tiny
    one for the CPU rehearsal). ``mesh``: defaults to the first device;
    a mesh with a 'dp' axis shards the batch over it."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.bert import bert_12_768_12
    from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh

    in_model_decoder = head_mode == "dense"
    if net is None:
        net = bert_12_768_12(use_pooler=False, use_classifier=False,
                             use_decoder=in_model_decoder)
    net.initialize()
    vocab = net.word_embed.weight.shape[0]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (2, seq)).astype(np.float32)
    tt = np.zeros((2, seq), np.float32)
    net(nd.array(ids), nd.array(tt))  # resolve deferred shapes

    loss = _MLMLoss() if in_model_decoder else \
        _make_head_loss(vocab, net._units, head_mode)
    if mesh is None:
        mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    spec = P("dp") if mesh.shape.get("dp", 1) > 1 else P()
    step = ShardedTrainStep(net, loss, mesh, optimizer="lamb",
                            lr=1e-3, wd=0.01, dtype="bfloat16",
                            n_data_inputs=3,
                            data_specs=[spec, spec, spec],
                            split_update=split_update)
    x = nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.float32))
    t = nd.array(np.zeros((batch, seq), np.float32))
    # label layout follows the head it feeds: the decoder path scores
    # (seq, batch, vocab) logits; the parametric heads consume
    # outputs[0], which the model returns batch-major (bert.py)
    lab_shape = (seq, batch) if in_model_decoder else (batch, seq)
    y = nd.array(rng.randint(0, vocab, lab_shape).astype(np.float32))
    return step, (x, t, y)


def _pop_float_flag(argv, name):
    """Parse `--name N` / `--name=N` out of argv; returns (value, rest)
    or exits 2 on a malformed value."""
    def _usage():
        print("usage: bert_bench.py %s N  (e.g. %s 1250)" % (name, name),
              file=sys.stderr)
        sys.exit(2)

    if name in argv:                     # space-separated spelling
        gi = argv.index(name)
        try:
            return float(argv[gi + 1]), argv[:gi] + argv[gi + 2:]
        except (IndexError, ValueError):
            _usage()
    for gi, a in enumerate(argv):        # GNU --name=N spelling
        if a.startswith(name + "="):
            try:
                return float(a.split("=", 1)[1]), \
                    argv[:gi] + argv[gi + 1:]
            except (IndexError, ValueError):
                _usage()
    return None, argv


def main():
    import json
    import jax

    argv = sys.argv[1:]
    mfu_gate, argv = _pop_float_flag(argv, "--mfu-gate")
    gate, argv = _pop_float_flag(argv, "--gate")
    emit_json = "--json" in argv
    args = [a for a in argv if not a.startswith("--")]
    batch = int(args[0]) if args else 32
    seq = int(args[1]) if len(args) > 1 else 128

    if "--fusedce" in argv:
        head_mode = "fused"
    elif "--chunkedce" in argv:
        head_mode = "chunked"
    elif "--densece" in argv:
        head_mode = "dense"
    else:
        head_mode = "auto"
    # device numbers only: no chip, no run (tests drive build_step
    # directly; the CPU never goes through main)
    from mxnet_tpu import runtime
    device = runtime.require_accelerator()
    runtime.enable_compile_cache()
    step, data = build_step(batch, seq, split_update="--split" in argv,
                            head_mode=head_mode)
    for _ in range(3):
        loss = step.step(*data)
    float(jax.device_get(loss))

    from devtime import device_ms_per_step
    ms = device_ms_per_step(lambda: step.step(*data), 8,
                            lambda o: float(jax.device_get(o)))
    # FLOP model (fwd+bwd+update ~ 3x fwd): encoder 12 layers x
    # (qkv 3*768^2 + proj 768^2 + ffn 2*768*3072) * 2 MAC + attention
    # 2*2*L*768 per token + decoder head 768*30522 (+768^2 transform)
    per_tok = (12 * (4 * 768 * 768 + 2 * 768 * 3072 + 2 * seq * 768)
               + 768 * 30522 + 768 * 768) * 2 * 3
    samples_s = batch / ms * 1000
    tflops = per_tok * batch * seq / (ms / 1e3) / 1e12
    print(f"device_ms_per_step={ms:.3f} samples/s={samples_s:.1f} "
          f"~TFLOP/s={tflops:.1f} (~{tflops / 197 * 100:.0f}% MFU of "
          f"197 bf16 peak) head={head_mode}")

    mfu = goodput = None
    noise_scale = None
    mw_anomalies = 0
    comm = {}
    if mfu_gate is not None or emit_json:
        # measured meters (ISSUE 6), run AFTER the headline loop —
        # same discipline as bench.py: the instrumentation must not
        # skew the flagship samples/s or the --gate verdict. A
        # wall-clocked loop with a forced readback per step, so
        # mx_step_seconds intervals are honest wall time; executed
        # FLOPs come from the AOT program's cost_analysis charged per
        # execution by commwatch.
        from mxnet_tpu import commwatch, telemetry
        prior_env = os.environ.get("MXNET_TELEMETRY")
        os.environ["MXNET_TELEMETRY"] = "1"
        telemetry.refresh()
        try:
            if not (telemetry.enabled() and commwatch.enabled()):
                print("MFU METER UNAVAILABLE: needs MXNET_TELEMETRY=1 "
                      "and MXNET_COMMWATCH!=0 (MXNET_COMMWATCH=%r in "
                      "env)" % os.environ.get("MXNET_COMMWATCH"))
                sys.exit(2)
            # warmup: the first watched call AOT-compiles + registers
            # the program; reset so compile time doesn't dilute the
            # meter window (the executable re-registers its inventory)
            float(jax.device_get(step.step(*data)))
            telemetry.reset()
            for _ in range(8):
                float(jax.device_get(step.step(*data)))
            snap = telemetry.snapshot()
            mfu = snap["gauges"].get("mx_mfu", 0.0)
            goodput = snap["gauges"].get("mx_goodput", 0.0)
            # standardized training-dynamics fields (ISSUE 11): the
            # sharded single-program step has no Trainer, so these
            # populate only when a modelwatch-driven loop ran in this
            # process (e.g. --split mode's Trainer path under
            # MXNET_MODELWATCH); null/0 otherwise — schema parity with
            # bench.py
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
            print(f"measured: mfu={mfu * 100:.2f}% goodput="
                  f"{goodput * 100:.1f}% "
                  f"(peak={telemetry.peak_flops():.3g} FLOP/s; "
                  f"executed_flops="
                  f"{snap['counters'].get('mx_executed_flops_total', 0):.3g})")
        finally:
            if prior_env is None:
                os.environ.pop("MXNET_TELEMETRY", None)
            else:
                os.environ["MXNET_TELEMETRY"] = prior_env
            telemetry.refresh()

    if emit_json:
        # optimizer-state footprint (ISSUE 8 schema fields): for the
        # single-program ShardedTrainStep the states live as jax-array
        # tuples; `zero` records whether the run asked for ZeRO
        # weight-update sharding (the Gluon-Trainer feature — bench.py
        # reports the engine actually engaging)
        from mxnet_tpu import config as _cfg
        from mxnet_tpu.parallel import quantize as _qz
        _qcfg = _qz.from_env()
        opt_state_bytes = sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for st in step.states.values() for a in st)
        import bench_json
        bench_json.emit({
            "metric": "bert_base_mlm_train_step",
            "value": round(samples_s, 2),
            "unit": "samples/sec/chip",
            "device": device,
            "batch": batch, "seq": seq, "head": head_mode,
            "device_ms_per_step": round(ms, 3),
            "analytic_tflops": round(tflops, 2),
            "mfu": mfu, "goodput": goodput,
            "comm_bandwidth": comm,
            "grad_noise_scale": noise_scale,
            "modelwatch_anomalies": mw_anomalies,
            "optimizer_state_bytes": opt_state_bytes,
            "zero": bool(_cfg.get("MXNET_ZERO")),
            "quantize": _qcfg.mode if _qcfg is not None else "off",
        }, source="bert_bench")

    if mfu_gate is not None:
        if not mfu or mfu <= 0:
            print("MFU GATE FAIL: mx_mfu gauge not populated — the "
                  "measured-FLOPs meter is broken")
            sys.exit(1)
        if mfu * 100 < mfu_gate:
            print(f"MFU GATE FAIL: {mfu * 100:.2f}% < {mfu_gate:.1f}%")
            sys.exit(1)
        print(f"MFU GATE OK: {mfu * 100:.2f}% >= {mfu_gate:.1f}% "
              f"(goodput {goodput * 100:.1f}%)")

    if gate is not None:
        if samples_s < gate:
            print(f"GATE FAIL: {samples_s:.1f} samples/s < {gate:.1f}")
            sys.exit(1)
        print(f"GATE OK: {samples_s:.1f} samples/s >= {gate:.1f}")


if __name__ == "__main__":
    main()
