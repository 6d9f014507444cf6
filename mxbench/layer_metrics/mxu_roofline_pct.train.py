"""Kernels: the compute-bound share of device-busy time: the model's
FLOPs for the samples of the traced window (configs/<name>.py,
recomputation not counted), over the chips' peak bf16 FLOP/s, over the
seconds device 0 was busy. Not an MFU over wall time: idle time is
``device_idle_pct.train``."""
from mxbench import manifest, trace as T

UNIT = "%"


def read(run):
    if run.trace is None:
        return None
    busy_s = T.total(T.busy(run.trace, 0, run.trace_window)) / 1e9
    if busy_s <= 0:
        return None
    peak = manifest.peaks(run.device_kind)["bf16_flops_per_s"] * run.chips
    return 100.0 * run.samples * run.flops_per_sample / peak / busy_s
