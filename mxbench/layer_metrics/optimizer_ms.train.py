"""Training step: device time of the instructions under the program's
``mx.optimizer`` scope on device 0, per step: the float32 cast of each gradient and the optimizer's
update of every parameter: moments, norms, decay, the new master
(``parallel/sharded.py::update_of``).
Which instructions those are is the program's own table
(``_program_scopes.py``). Nothing without a trace, on a program without
the table, or on one whose step opens no such scope."""
from mxbench import manifest

_scopes = manifest.load_module("layer_metrics", "_program_scopes.py")

UNIT = "ms/step"
SCOPE = "mx.optimizer"


def read(run):
    return _scopes.ms_per_step(run, SCOPE)
