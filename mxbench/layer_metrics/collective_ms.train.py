"""Collectives: time of all-reduce / all-gather / reduce-scatter (and
all-to-all, collective-permute) on device 0, per step. Nothing to read
on one chip."""
from mxbench import trace as T

UNIT = "ms/step"


def read(run):
    if run.trace is None or run.chips < 2 or not run.traced_steps:
        return None
    return T.total(T.collective_intervals(
        run.trace, 0, run.trace_window)) / 1e6 / run.traced_steps
