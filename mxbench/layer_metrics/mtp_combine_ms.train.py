"""Kernels: device time of the instructions under the program's ``mx.mtp`` scope (what a multi-token-prediction module adds outside its block: the norms of the next token's embedding and of the stack's hidden state and the product that combines them; forward, recomputation and backward) on device 0, per step (``mxbench/scopes.py``). The module's block reads under the attention's and the experts' scopes, its head under ``mx.head.ce``. Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.mtp"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
