"""Kernels: device time of the instructions under the program's
``mx.moe.experts`` scope (the routed experts' gather, batched product and sum) on device 0, per step, summed over forward,
recomputation and backward (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.moe.experts"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
