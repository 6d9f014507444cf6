"""Kernels: device time of the instructions under the program's ``mx.attn.sparse`` scope (the attention over the selected keys: scores, masked softmax, context) on device 0, per step, summed over forward, recomputation and backward (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.sparse"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
