"""Kernels: the roofline share of the ``mx.attn.window`` scope: the least time the chip could take for attention over the band's pairs alone (``configs/<name>.py::scope_costs``; a schedule that computes whole tiles and masks the band's edges reads low, never over 100%) over the scope's device seconds a step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "%"
SCOPE = "mx.attn.window"


def read(run):
    return scopes.roofline_pct(run, SCOPE)
