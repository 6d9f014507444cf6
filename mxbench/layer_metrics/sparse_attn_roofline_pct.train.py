"""Kernels: the roofline share of the ``mx.attn.sparse`` scope: the least time the chip could take for attention over the selected pairs alone (``configs/<name>.py::scope_costs``; a form that computes the pairs it masks reads low, never over 100%) over the scope's device seconds a step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "%"
SCOPE = "mx.attn.sparse"


def read(run):
    return scopes.roofline_pct(run, SCOPE)
