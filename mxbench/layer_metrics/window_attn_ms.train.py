"""Kernels: device time of the instructions under the program's ``mx.attn.window`` scope (causal grouped-query attention over a sliding window of keys: the flash kernel's forward and backward over the band's tiles and the ``delta`` reduction, or the composition's banded blocks) on device 0, per step, summed over forward and backward (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.window"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
