"""Kernels: device time of the instructions under the program's ``mx.attn.index`` scope (the selector's index-score product over a query block's causal pairs, its weighted relu sum, and the index loss on the selected set) on device 0, per step, summed over forward, recomputation and backward (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.index"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
