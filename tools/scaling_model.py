"""Measured + modeled scaling artifact for the 8->256-chip BERT-base DP
target (BASELINE.md: >=90% scaling efficiency; SURVEY §5.8 DCN role).

Two parts:

1. MEASURED (runs here, on the 8-virtual-device CPU mesh): compile the
   framework's own ShardedTrainStep on a dcn=2 x dp=4 mesh and parse the
   optimized HLO for every collective — op kind, bytes, replica groups —
   classifying each group as ICI-only (devices within one slice) or
   DCN-crossing. Also compiles the explicit hierarchical
   reduce_scatter(ICI) -> psum(DCN) -> all_gather(ICI) path and shows
   the DCN-crossing byte drop. These are the numbers SCALING.md cites.

2. MODELED: ring-allreduce cost model for BERT-base (109.5M params) DP
   at 8..256 chips over published v5e fabric numbers, flat vs
   hierarchical, with the allreduce overlapped against backward compute.

Usage: python tools/scaling_model.py [--json]
"""
from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax

# a byte census on the 8-virtual-device CPU mesh, not a device measurement
jax.config.update("jax_platforms", "cpu")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s8": 1, "u8": 1, "pred": 1, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
         "all-to-all")


def _shape_bytes(text):
    """Sum bytes of every dtype[dims] token in an HLO result-type blob."""
    total = 0
    for dt, dims in re.findall(r"(\w+)\[([0-9,]*)\]", text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _parse_groups(line, n_devices):
    """Return list of device-id groups from replica_groups=... (explicit
    {{0,1},{2,3}} or iota [G,S]<=[N] form)."""
    m = re.search(r"replica_groups=\{\{(.*?)\}\}", line)
    if m:
        return [[int(x) for x in grp.split(",") if x]
                for grp in m.group(1).split("},{")]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
                  r"(T\(([0-9,]+)\))?", line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        reshape = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(reshape))).reshape(reshape)
        if m.group(5):
            ids = ids.transpose([int(x) for x in m.group(5).split(",")])
        return ids.reshape(g, s).tolist()
    return [list(range(n_devices))]  # conservative: assume global


def collective_stats(hlo_text, n_devices, slice_size):
    """Per-kind collective bytes, split by whether any replica group
    crosses the slice boundary (device_id // slice_size differs)."""
    stats = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(r"= (.*?) (" + "|".join(_COLL) + r")(-start|-done)?\(",
                      line)
        if not m or m.group(3) == "-done":  # -done carries no new bytes
            continue
        kind = m.group(2)
        nbytes = _shape_bytes(m.group(1))
        groups = _parse_groups(line, n_devices)
        crossing = any(len({d // slice_size for d in g}) > 1
                       for g in groups)
        key = (kind, "dcn" if crossing else "ici")
        c, b = stats.get(key, (0, 0))
        stats[key] = (c + 1, b + nbytes)
    return stats


# ---------------------------------------------------------------------------
def measure_framework_step():
    """Compile the framework DP step on dcn=2 x dp=4 and read its HLO."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderCell
    from mxnet_tpu.parallel import (MeshConfig, P, ShardedTrainStep,
                                    make_mesh)

    units, heads = 64, 4

    class Tiny(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.cell = BERTEncoderCell(units, units * 4, heads,
                                            dropout=0.0)
                self.head = nn.Dense(8, flatten=False)

        def hybrid_forward(self, F, x):
            return F.mean(self.head(self.cell(x)), axis=0)

    net = Tiny()
    net.initialize(init=mx.initializer.Xavier())
    net(nd.ones((2, 2, units)))
    mesh = make_mesh(MeshConfig(dcn=2, dp=4))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss_fn, mesh, lr=0.1, momentum=0.9,
                            data_specs=[P(None, ("dcn", "dp")),
                                        P(("dcn", "dp"))])
    x = nd.array(np.random.RandomState(0)
                 .randn(8, 16, units).astype(np.float32))
    y = nd.array((np.arange(16) % 8).astype(np.float32))
    step.step(x, y)  # compile + run once

    arrays = [jax.device_put(d._jax(), sh)
              for d, sh in zip((x, y), step.data_shardings)]
    hlo = step._fused.lower(step.params, step.aux, step.states,
                            step._t_dev, step._rng_dev,
                            *arrays).compile().as_text()
    n_params = sum(int(np.prod(v.shape)) for v in step.params.values())
    return collective_stats(hlo, 8, 4), n_params


def measure_hierarchical_sync(sizes):
    """Compile hierarchical_grad_sync for the same gradient sizes and
    read its HLO collective split."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as JP
    from mxnet_tpu.parallel import MeshConfig, make_mesh
    from mxnet_tpu.parallel.collectives import hierarchical_grad_sync

    mesh = make_mesh(MeshConfig(dcn=2, dp=4))
    tree = {str(i): np.zeros((8,) + s, np.float32)
            for i, s in enumerate(sizes)}
    spec = JP(("dcn", "dp"))
    f = shard_map(
        lambda t: jax.tree_util.tree_map(
            lambda g: g[None],
            hierarchical_grad_sync(
                jax.tree_util.tree_map(lambda g: g[0], t),
                ici_axis="dp", dcn_axis="dcn")),
        mesh=mesh, in_specs=(spec,), out_specs=spec)
    hlo = jax.jit(f).lower(tree).compile().as_text()
    return collective_stats(hlo, 8, 4)


# ---------------------------------------------------------------------------
# Analytic model. Fabric constants (public figures; per chip, one
# direction — see SCALING.md for sources and sensitivity):
ICI_BW = 45e9          # v5e ICI: 45 GB/s per link direction
ICI_LINKS_RING = 2     # links usable by a 1-D ring on the 2-D torus axis
DCN_BW_HOST = 25e9     # 200 Gbps NIC per v5e host (8 chips/host)
CHIPS_PER_HOST = 8
BERT_PARAMS = 109_514_810   # BERT-base-uncased incl. MLM head
GRAD_BYTES = 4         # fp32 gradient allreduce (bf16 would halve this)
PEAK_FLOPS = 197e12    # v5e bf16 peak
MFU = 0.45             # round-3 builder figure, not re-measured
SEQ, BATCH_PER_CHIP = 128, 32
OVERLAP = 0.7          # fraction of allreduce hidden under backward


def step_compute_s():
    per_tok = (12 * (4 * 768 * 768 + 2 * 768 * 3072 + 2 * SEQ * 768)
               + 768 * 30522 + 768 * 768) * 2 * 3
    return per_tok * SEQ * BATCH_PER_CHIP / (PEAK_FLOPS * MFU)


def ring_allreduce_s(bytes_, n, bw):
    if n <= 1:
        return 0.0
    return 2 * (n - 1) / n * bytes_ / bw


def model_efficiency(n_chips, slice_size):
    """Step-time efficiency vs the 8-chip baseline config."""
    B = BERT_PARAMS * GRAD_BYTES
    t_c = step_compute_s()
    n_slices = max(1, n_chips // slice_size)
    n_ici = min(n_chips, slice_size)
    t_ici = ring_allreduce_s(B, n_ici, ICI_BW * ICI_LINKS_RING)
    if n_slices > 1:
        # hierarchical: RS(ici) leaves B/n_ici per chip; the DCN ring
        # runs between slices at the HOST NIC rate shared by the
        # chips-per-host that sit on that NIC
        dcn_bytes = B / n_ici
        dcn_bw = DCN_BW_HOST / CHIPS_PER_HOST
        t_dcn = ring_allreduce_s(dcn_bytes, n_slices, dcn_bw)
    else:
        t_dcn = 0.0
    t_comm_exposed = max(0.0, (t_ici + t_dcn) * (1 - OVERLAP))
    return t_c / (t_c + t_comm_exposed), t_ici, t_dcn


def main():
    as_json = "--json" in sys.argv
    stats, n_params = measure_framework_step()
    print("== MEASURED: framework ShardedTrainStep, dcn=2 x dp=4 "
          "(8 virtual devices, tiny BERT cell, %d params) ==" % n_params)
    param_bytes = n_params * 4
    ar_bytes = sum(b for (k, w), (c, b) in stats.items()
                   if k == "all-reduce")
    for (kind, where), (cnt, byt) in sorted(stats.items()):
        print("  %-20s %-4s  n=%-3d  %10d bytes" % (kind, where, cnt, byt))
    print("  gradient all-reduce bytes / param bytes = %.3f "
          "(expect ~1: every grad reduced once)"
          % (ar_bytes / param_bytes))

    sizes = [(256, 64), (64,), (64, 64), (257,)]
    hstats = measure_hierarchical_sync(sizes)
    print("== MEASURED: hierarchical_grad_sync (explicit RS/AR/AG) ==")
    for (kind, where), (cnt, byt) in sorted(hstats.items()):
        print("  %-20s %-4s  n=%-3d  %10d bytes" % (kind, where, cnt, byt))
    g_bytes = sum(int(np.prod(s)) for s in sizes) * 4
    dcn_ar = sum(b for (k, w), (c, b) in hstats.items()
                 if w == "dcn")
    print("  grad bytes=%d, DCN-crossing bytes=%d (= grads/n_ici + pad; "
          "flat AR would cross with ALL %d bytes)"
          % (g_bytes, dcn_ar, g_bytes))

    print("== MODEL: BERT-base DP, batch %d/chip, seq %d, fp32 grads ==" %
          (BATCH_PER_CHIP, SEQ))
    print("  compute/step = %.1f ms (%.0f%% MFU of %.0f TF peak); "
          "grad buffer = %.0f MB" %
          (step_compute_s() * 1e3, MFU * 100, PEAK_FLOPS / 1e12,
           BERT_PARAMS * GRAD_BYTES / 1e6))
    rows = []
    for n in (8, 16, 32, 64, 128, 256):
        eff_1, ti1, td1 = model_efficiency(n, 256)   # one big slice
        eff_h, tih, tdh = model_efficiency(n, 64)    # 64-chip slices, DCN
        rows.append((n, eff_1, ti1 + td1, eff_h, tih, tdh))
        print("  %3d chips: single-slice eff=%.3f (AR %.1f ms) | "
              "4x64-slice eff=%.3f (ICI %.1f ms + DCN %.1f ms)"
              % (n, eff_1, (ti1 + td1) * 1e3, eff_h, tih * 1e3,
                 tdh * 1e3))
    eff8, _, _ = model_efficiency(8, 256)
    eff256_1, _, _ = model_efficiency(256, 256)
    eff256_h, _, _ = model_efficiency(256, 64)
    print("  8->256 scaling efficiency: %.1f%% single-slice, %.1f%% "
          "multi-slice hierarchical (target >=90%%)"
          % (eff256_1 / eff8 * 100, eff256_h / eff8 * 100))
    if as_json:
        import json
        print(json.dumps({
            "measured_step": {"%s/%s" % k: v for k, v in stats.items()},
            "measured_hier": {"%s/%s" % k: v for k, v in hstats.items()},
            "model_rows": rows,
            "scaling_8_to_256": {"single_slice": eff256_1 / eff8,
                                 "hierarchical_4x64": eff256_h / eff8}}))


if __name__ == "__main__":
    main()
