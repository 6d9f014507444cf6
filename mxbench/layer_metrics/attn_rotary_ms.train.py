"""Kernels: device time of the instructions under the program's ``mx.attn.rotary`` scope and under no scope inside it (a rotary attention mixer around its attention: the pre-norm, the q / k / v projections, the optional q/k norms, the rotary turn, the output projection; forward, recomputation and backward; the attention itself stands under ``mx.attn.causal`` or ``mx.attn.window`` and the gate under ``mx.attn.gate``, which a configuration's ``SCOPES`` name before this one) on device 0, per step (``mxbench/scopes.py``). Nothing on a
program without the scope."""
from mxbench import scopes

UNIT = "ms/step"
SCOPE = "mx.attn.rotary"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
