"""Training step: device time of the instructions under the program's
``mx.params.cast`` scope on device 0, per step: the compute-dtype copies of the float32 masters
and of the data made at the step's start, and the cast of their
gradients back (``parallel/sharded.py::loss_of``).
Which instructions those are is the program's own table
(``_program_scopes.py``). Nothing without a trace, on a program without
the table, or on one whose step opens no such scope."""
from mxbench import manifest

_scopes = manifest.load_module("layer_metrics", "_program_scopes.py")

UNIT = "ms/step"
SCOPE = "mx.params.cast"


def read(run):
    return _scopes.ms_per_step(run, SCOPE)
