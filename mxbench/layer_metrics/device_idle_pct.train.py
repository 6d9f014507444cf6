"""Device: 1 - busy seconds per step (device 0, trace) / wall seconds
per step (untraced window of the traced run). The traced window's own
idle share is in the result's ``device`` block; it reads higher where
the profiler slows the feed."""
UNIT = "%"


def read(run):
    if run.untraced_s_per_step is None or run.busy_s_per_step is None:
        return None
    return 100.0 * (1.0 - run.busy_s_per_step
                    / run.untraced_s_per_step["wall"])
