"""Device: `setup_s` less everything the program's set-up spans and
compile records cover (`_setup_phases.py`): the benchmark's reference
(its compiles and steps) and feed, the warm-up steps' device time, the
interpreter's start. The twin of `unscoped_ms.train` for set-up."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.unattributed(run)
