"""Collectives: the part of device 0's collective time during which no
other op runs on that device, per step."""
from mxbench import trace as T

UNIT = "ms/step"


def read(run):
    if run.trace is None or run.chips < 2 or not run.traced_steps:
        return None
    return T.exposed_collective_s(run.trace, 0, run.trace_window) \
        * 1e3 / run.traced_steps
