"""Kernels: device time of the instructions under the program's ``mx.attn.gate`` scope (the sigmoid gate a head on an attention mixer's context: the gate's product ``W_g h``, the sigmoid and the multiply of each head's context before the output projection; forward, recomputation and backward) on device 0, per step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.gate"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
