"""Kernels: device time of the instructions under the program's ``mx.attn.mla`` scope (a latent-attention mixer outside its attention: the pre-norm, the down and up projections of queries and of keys / values with the norm inside each bottleneck, the rotary turn, the assembly of q and k, the output projection; forward, the backward's re-expansion and the gradients) on device 0, per step (``mxbench/scopes.py``). The attention itself reads under ``mx.attn.causal``. Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.mla"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
