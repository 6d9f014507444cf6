"""Kernels: the roofline share of the ``mx.attn.select`` scope: the time to read the causal pairs' float32 score rows once at the peak HBM bytes/s (``configs/<name>.py::scope_costs``; a selection has no product) over the scope's device seconds a step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "%"
SCOPE = "mx.attn.select"


def read(run):
    return scopes.roofline_pct(run, SCOPE)
