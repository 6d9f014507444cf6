"""Host loop: host milliseconds a step inside the program's
``step::update.prep`` span (the fused update's walk over the parameters,
the optimizer's counters and the hyper-parameter arrays), from the step
log (``telemetry.step_log``) of the untraced window that a traced run
makes first. The span times the host whether the device hides it or not.
None where the program keeps no step log."""
from mxbench import spans

UNIT = "ms/step"


def read(run):
    return spans.span_ms(run, "step::update.prep")
