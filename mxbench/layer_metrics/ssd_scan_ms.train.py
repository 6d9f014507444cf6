"""Kernels: device time of the instructions under the program's
``mx.mamba2.ssd`` scope (the chunked state-space scan of the Mamba-2 mixers) on device 0, per step, summed over forward,
recomputation and backward (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.mamba2.ssd"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
