"""Kernels: the roofline share of the ``mx.attn.index`` scope: the least time the chip could take for the index scores of the causal pairs (the larger of FLOPs over the peak bf16 FLOP/s and bytes over the peak HBM bytes/s; the counts are ``configs/<name>.py::scope_costs``, the least the mathematics needs) over the scope's device seconds a step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "%"
SCOPE = "mx.attn.index"


def read(run):
    return scopes.roofline_pct(run, SCOPE)
