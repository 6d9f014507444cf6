"""Kernels: device time of the instructions under the program's ``mx.mlp`` scope (a dense gated MLP mixer: the pre-norm, the gate-and-up product, ``silu(gate) * up``, the down product; forward, recomputation and backward) on device 0, per step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.mlp"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
