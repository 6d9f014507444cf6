#!/usr/bin/env python
"""Kernel micro gates (ISSUE 6 CI tooling): the new Pallas/streaming
kernels vs their XLA twins, paired-median scored like
tools/compile_micro.py, plus a compile_report-style zero-recompile
assertion for the new programs.

1. **LayerNorm**: pallas_layer_norm (ops/pallas_norm.py) vs the
   _ln_fused XLA reference, fwd+bwd on the BERT-base shape
   (seq*batch=4096 rows, 768 channels, bf16).
2. **LM-head CE**: _contrib_chunked_lm_head_ce (online softmax over
   vocab chunks) vs the dense _lm_head_ce composition, fwd+bwd at the
   flagship (T=4096, U=768, V=30522) shape — scaled down off-TPU.
3. **Packed flash attention** (round 7): flash_selfatt consuming the
   reference-packed QKV layout directly vs the unfused
   interleaved-matmul composition, fwd+bwd at the BERT-base attention
   shape (L=128, N=32, 12 heads, hd=64).
4. **Zero steady-state recompiles**: every program above is a
   compilewatch.WatchedJit; after warmup, further calls may not compile
   anything (the recompile-storm regression gate for the new kernels).

The speed gates ASSERT only on a real TPU (`--threshold`): in Pallas
interpret mode on CPU the kernels are emulation-slow by construction,
so CPU runs report the ratios and enforce only the recompile gate.
`--json` emits one standardized bench-JSON object (the
bench.py/bert_bench.py schema: metric/value/unit plus per-kernel
candidate-vs-twin rows) so on-chip gate runs seed the kernel-layer
BENCH trajectory; run it under MXNET_AUTOTUNE=measure to record the
autotuned constants alongside (the table rides in the JSON).

Usage: python tools/kernel_micro.py [--repeats 5] [--steps 5]
           [--warmup 3] [--threshold 1.10] [--small] [--json]
Exit 0 = every applicable gate passes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _paired_median(num, den):
    ratios = sorted(n / d for n, d in zip(num, den))
    mid = len(ratios) // 2
    return ratios[mid] if len(ratios) % 2 else \
        (ratios[mid - 1] + ratios[mid]) / 2.0


def _bench(fn, args, repeats, inner=3):
    import jax
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.tree_util.tree_map(
            lambda a: a.block_until_ready()
            if hasattr(a, "block_until_ready") else a, out)
        ts.append((time.perf_counter() - t0) / inner)
    return ts


def _on_tpu():
    import jax
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:
        return False


def build_pairs(small):
    """[(name, candidate_fn, twin_fn, args)] — every fn is a
    compilewatch.WatchedJit over fwd+bwd (grads of a scalar)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.compilewatch import watched_jit
    from mxnet_tpu.ops.nn import _ln_fused
    from mxnet_tpu.ops.pallas_norm import (pallas_layer_norm,
                                           pallas_ln_available)
    from mxnet_tpu.ops.contrib_ops import _lm_head_ce, _make_chunked_ce

    rng = np.random.RandomState(0)
    pairs = []

    # -- LayerNorm ------------------------------------------------------
    M, C = (256, 128) if small else (4096, 768)
    dtype = jnp.float32 if small else jnp.bfloat16
    x = jnp.asarray(rng.randn(M, C).astype(np.float32) + 1.0).astype(dtype)
    g = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(C).astype(np.float32))
    assert pallas_ln_available((M, C), dtype, 1)

    def ln_pallas(x, g, b):
        def s(x, g, b):
            return jnp.sum(pallas_layer_norm(x, g, b, eps=1e-5)
                           .astype(jnp.float32))
        return jax.grad(s, argnums=(0, 1, 2))(x, g, b)

    def ln_xla(x, g, b):
        def s(x, g, b):
            return jnp.sum(_ln_fused(1, 2, 1e-5)(x, g, b)
                           .astype(jnp.float32))
        return jax.grad(s, argnums=(0, 1, 2))(x, g, b)

    pairs.append(("layer_norm",
                  watched_jit(ln_pallas, fn_label="micro.ln_pallas",
                              site="kernel_micro"),
                  watched_jit(ln_xla, fn_label="micro.ln_xla",
                              site="kernel_micro"),
                  (x, g, b)))

    # -- LM-head CE -----------------------------------------------------
    T, U, V, chunk = (64, 32, 200, 64) if small else \
        (4096, 768, 30522, 4096)
    h = jnp.asarray(rng.randn(T, U).astype(np.float32)).astype(dtype)
    w = jnp.asarray((rng.randn(V, U) * 0.05).astype(np.float32)) \
        .astype(dtype)
    bb = jnp.asarray(np.zeros(V, np.float32))
    lab = jnp.asarray(rng.randint(0, V, (T,)).astype(np.int32))
    chunked = _make_chunked_ce(chunk)

    def ce_chunked(h, w, bb):
        def s(h, w, bb):
            return jnp.sum(chunked(h, w, bb, lab))
        return jax.grad(s, argnums=(0, 1, 2))(h, w, bb)

    def ce_dense(h, w, bb):
        def s(h, w, bb):
            return jnp.sum(_lm_head_ce(h, w, bb, lab))
        return jax.grad(s, argnums=(0, 1, 2))(h, w, bb)

    pairs.append(("lm_head_ce",
                  watched_jit(ce_chunked, fn_label="micro.ce_chunked",
                              site="kernel_micro"),
                  watched_jit(ce_dense, fn_label="micro.ce_dense",
                              site="kernel_micro"),
                  (h, w, bb)))

    # -- packed flash attention (round 7) -------------------------------
    from mxnet_tpu.ops.pallas_attention import flash_selfatt, selfatt_plan
    from mxnet_tpu.ops.contrib_ops import (
        interleaved_matmul_selfatt_qk, interleaved_matmul_selfatt_valatt)

    L, N, H, hd = (16, 4, 4, 8) if small else (128, 32, 12, 64)
    qkv = jnp.asarray(rng.randn(L, N, H * 3 * hd).astype(np.float32)) \
        .astype(dtype)
    plan = selfatt_plan(L, H, N, 0.0, dtype=None)
    assert plan is not None
    seeds = jnp.zeros((plan["n_blocks"],), jnp.int32)
    ra = jnp.asarray(rng.randn(L, N, H * hd).astype(np.float32))
    bbh = plan["bbh"]

    def attn_packed(qkv, seeds):
        def s(qkv):
            return jnp.sum(flash_selfatt(qkv, seeds, heads=H,
                                         block_heads=bbh)
                           .astype(jnp.float32) * ra)
        return jax.grad(s)(qkv)

    def attn_unfused(qkv, seeds):
        def s(qkv):
            sc = interleaved_matmul_selfatt_qk(qkv, heads=H)
            att = jax.nn.softmax(sc, axis=-1)
            out = interleaved_matmul_selfatt_valatt(qkv, att, heads=H)
            return jnp.sum(out.astype(jnp.float32) * ra)
        return jax.grad(s)(qkv)

    pairs.append(("selfatt_packed",
                  watched_jit(attn_packed, fn_label="micro.attn_packed",
                              site="kernel_micro"),
                  watched_jit(attn_unfused,
                              fn_label="micro.attn_unfused",
                              site="kernel_micro"),
                  (qkv, seeds)))
    return pairs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--threshold", type=float, default=1.10,
                    help="max candidate/twin paired-median ratio; "
                         "asserted on TPU only")
    ap.add_argument("--small", action="store_true",
                    help="scaled-down shapes (CI smoke on CPU)")
    ap.add_argument("--json", action="store_true",
                    help="emit the standardized bench-JSON object "
                         "(bench.py schema) with per-kernel "
                         "candidate-vs-twin rows")
    args = ap.parse_args(argv)

    os.environ["MXNET_TELEMETRY"] = "1"
    from mxnet_tpu import compilewatch, telemetry
    telemetry.refresh()
    on_tpu = _on_tpu()
    if not on_tpu and not args.small:
        # interpret-mode full shapes take minutes for zero signal
        print("(CPU detected: forcing --small shapes; speed gate is "
              "report-only off-TPU)")
        args.small = True

    pairs = build_pairs(args.small)
    rc = 0
    rows = {}
    for name, cand, twin, data in pairs:
        # warmup compiles both
        for _ in range(max(1, args.warmup)):
            cand(*data)
            twin(*data)
        before = len(compilewatch.programs())
        # interleaved rounds: a load spike inflates both halves and
        # cancels in the per-round ratio (compile_micro method)
        t_c, t_t = [], []
        for _ in range(max(1, args.repeats)):
            t_c += _bench(cand, data, 1)
            t_t += _bench(twin, data, 1)
        median = _paired_median(t_c, t_t)
        print("%-12s candidate %8.3f ms  twin %8.3f ms  "
              "paired-median ratio %.3f"
              % (name, min(t_c) * 1e3, min(t_t) * 1e3, median))
        if on_tpu and args.threshold > 0 and median > args.threshold:
            print("FAIL: %s candidate slower than %.2fx its XLA twin"
                  % (name, args.threshold))
            rc = 1
        # zero steady-state recompiles for the new programs
        steady = [r for r in compilewatch.programs()[before:]
                  if r["fn"].startswith("micro.")]
        if steady:
            for r in steady:
                print("FAIL: steady-state %s of %s: %s"
                      % (r["kind"], r["fn"], r.get("changed")))
            rc = 1
        else:
            print("%-12s zero steady-state recompiles over %d calls OK"
                  % (name, 2 * args.repeats))
        rows[name] = {
            "candidate_ms": round(min(t_c) * 1e3, 4),
            "twin_ms": round(min(t_t) * 1e3, 4),
            "paired_median_ratio": round(median, 4),
            "steady_recompiles": len(steady),
        }
    if args.json:
        # standardized bench-JSON (tools/bench_json.py): one object,
        # metric/value/unit headline plus the per-kernel
        # candidate-vs-twin table — the kernel layer's BENCH row, and
        # the autotune-corpus source perfwatch joins on
        import bench_json
        from mxnet_tpu import autotune
        bench_json.emit({
            "metric": "kernel_micro_worst_paired_median_ratio",
            "value": round(max(r["paired_median_ratio"]
                               for r in rows.values()), 4),
            "unit": "candidate/twin",
            "on_tpu": on_tpu,
            "small": bool(args.small),
            "speed_gate_enforced": bool(on_tpu and args.threshold > 0),
            "kernels": rows,
            "autotune": autotune.mode(),
            "autotune_table": {k: v.get("params") for k, v in
                               autotune.table().items()},
        }, source="kernel_micro")
    if rc == 0:
        print("KERNEL_MICRO_OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
