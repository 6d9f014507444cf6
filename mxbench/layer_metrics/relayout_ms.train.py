"""Graph: device time of copy, copy-start/-done and transpose ops on
device 0, per step: what the layout pass and XLA's layout assignment
left to move."""
from mxbench import trace as T

UNIT = "ms/step"


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    return T.seconds_where(run.trace, 0, run.trace_window,
                           T.is_relayout) * 1e3 / run.traced_steps
