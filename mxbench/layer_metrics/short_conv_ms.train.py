"""Kernels: device time of the instructions under the program's ``mx.conv`` scope with its inner ``mx.conv.gate`` (the gated short-convolution mixers whole: the pre-norm, ``W_in``, both gates and the filter's taps, ``W_out``; forward, recomputation and backward) on device 0, per step, over all the conv layers (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.conv"
INNER = "mx.conv.gate"


def read(run):
    outer = scopes.ms_per_step(run, SCOPE)
    if outer is None:
        return None
    return outer + (scopes.ms_per_step(run, INNER) or 0.0)
