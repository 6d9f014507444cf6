"""Kernels: device time of the instructions under the program's
``mx.head.ce`` scope on device 0, per step: the streaming cross-entropy head
(``ops/contrib_ops.py::chunked_lm_head_ce``): the chunk loop's products,
the running log-sum-exp and the pick; in the backward the recomputed
chunk logits and the gradients of hidden states, weight and bias.
Which instructions those are is the program's own table
(``_program_scopes.py``). Nothing without a trace, on a program without
the table, or on one whose step opens no such scope."""
from mxbench import manifest

_scopes = manifest.load_module("layer_metrics", "_program_scopes.py")

UNIT = "ms/step"
SCOPE = "mx.head.ce"


def read(run):
    return _scopes.ms_per_step(run, SCOPE)
