"""Kernels: the roofline share of the ``mx.conv.gate`` scope: the least time
the chip could take for what one step needs there (its bytes over the
peak HBM bytes/s: a token's ``3 x hidden`` outputs of ``W_in`` read and
``hidden`` values written in the compute dtype, forward and
recomputation, and their gradients likewise in the backward; the
counts are ``configs/<name>.py::scope_costs``) over the scope's device
seconds a step. It reads low exactly when the values make extra trips
through HBM."""
from mxbench import scopes

UNIT = "%"
SCOPE = "mx.conv.gate"


def read(run):
    return scopes.roofline_pct(run, SCOPE)
