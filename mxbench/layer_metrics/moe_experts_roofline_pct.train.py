"""Kernels: the roofline share of the ``mx.moe.experts`` scope: the least time
the chip could take for what one step executes there (the larger of
its FLOPs over the peak bf16 FLOP/s and its bytes over the peak HBM
bytes/s; the counts are ``configs/<name>.py::scope_costs``,
recomputation included) over the scope's device seconds a step."""
from mxbench import scopes

UNIT = "%"
SCOPE = "mx.moe.experts"


def read(run):
    return scopes.roofline_pct(run, SCOPE)
