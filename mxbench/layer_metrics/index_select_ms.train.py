"""Kernels: device time of the instructions under the program's ``mx.attn.select`` scope (the search for each row's k-th largest index score and the mask of the selected keys, ties included) on device 0, per step, summed over forward and recomputation (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.select"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
