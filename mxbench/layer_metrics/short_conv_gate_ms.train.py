"""Kernels: device time of the instructions under the program's ``mx.conv.gate`` scope (a gated short-convolution mixer between its two projections: the ``B`` gate, the depthwise causal filter's taps and the ``C`` gate, all element-wise; forward, recomputation and backward) on device 0, per step, over all the conv layers (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.conv.gate"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
