"""Host loop: milliseconds from the start of a step's
``step::update.launch`` annotation on the host plane to the start of
that step's program on device 0, median over the traced steps
(``spans.launch_to_device_s``): the one number that needs the host's
spans and the device's ops on one clock. With steps in flight it holds
the wait behind the step the device is still running. None without a
trace, or where the trace holds no such span."""
from mxbench import spans

UNIT = "ms"


def read(run):
    if run.trace is None:
        return None
    wait = spans.launch_to_device_s(run.trace, 0, run.trace_window)
    return None if wait is None else wait * 1e3
