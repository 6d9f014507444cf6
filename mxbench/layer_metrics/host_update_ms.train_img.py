"""Host loop: host milliseconds a step inside the program's
``step::update`` span (the whole of gluon.Trainer.step; parent of the
three spans below it on the fused path), from the step log
(``telemetry.step_log``) of the untraced window that a traced run makes
first. The span times the host whether the device hides it or not. None
where the program keeps no step log."""
from mxbench import spans

UNIT = "ms/step"


def read(run):
    return spans.span_ms(run, "step::update")
