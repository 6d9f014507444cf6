"""Kernels: summed device durations of the custom calls whose name
holds ``pallas_`` on device 0, per step. 0 in a cell whose program
holds no Pallas kernel."""
from mxbench import trace as T

UNIT = "ms/step"


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    return T.seconds_where(run.trace, 0, run.trace_window,
                           T.is_pallas) * 1e3 / run.traced_steps
