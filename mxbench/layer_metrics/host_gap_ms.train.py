"""Host loop: the step's wall time (untraced window of the traced run)
minus the time device 0 is busy for a step (trace): time per step in
which the chip waited for the host (launches, the feed, the optimizer's
Python)."""
UNIT = "ms/step"


def read(run):
    if run.untraced_s_per_step is None or run.busy_s_per_step is None:
        return None
    return (run.untraced_s_per_step["wall"] - run.busy_s_per_step) * 1e3
