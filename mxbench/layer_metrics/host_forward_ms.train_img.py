"""Host loop: host milliseconds a step inside the program's
``step::forward`` span (a hybridized block's call into its CachedOp
under autograd.record() (gathering the parameters, the signature, the
launch or the deferral), every block of the step summed), from the step
log (``telemetry.step_log``) of the untraced window that a traced run
makes first. The span times the host whether the device hides it or not.
None where the program keeps no step log."""
from mxbench import spans

UNIT = "ms/step"


def read(run):
    return spans.span_ms(run, "step::forward")
