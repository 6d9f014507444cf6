"""Training step: seconds of set-up inside the program's `setup::place`
spans (`ShardedTrainStep`: float32 master copies, `device_put` to the
shardings, optimizer-state zeros, the AUTO re-layout after the first
compile) (`_setup_phases.py`). 0 in a cell of the Gluon loop."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "place")
