"""Host loop: seconds of set-up inside the program's `setup::init` spans
(`ParameterDict.initialize`: the initialisers, leaf by leaf, and
deferred initialisation finished at the first forward), less the
compiles and first launches inside them (`_setup_phases.py`)."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "init")
