"""Kernels: device time of the instructions under the program's
``mx.embed`` scope on device 0, per step: the ``Embedding`` op's row gather and, in the
backward, the scatter-add into the table's gradient.
Which instructions those are is the program's own table
(``_program_scopes.py``). Nothing without a trace, on a program without
the table, or on one whose step opens no such scope."""
from mxbench import manifest

_scopes = manifest.load_module("layer_metrics", "_program_scopes.py")

UNIT = "ms/step"
SCOPE = "mx.embed"


def read(run):
    return _scopes.ms_per_step(run, SCOPE)
