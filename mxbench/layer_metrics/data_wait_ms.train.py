"""Input pipeline: host time inside the ``mxbench/feed`` span (the
iterator's next batch and its hand-off to the device) per step, on the
host's clock over the untraced window that a traced run makes first:
the profiler itself slows large host-to-device copies."""
UNIT = "ms/step"


def read(run):
    if run.untraced_s_per_step is None:
        return None
    return run.untraced_s_per_step["feed"] * 1e3
