"""Host loop: seconds of set-up inside the program's `setup::import`
(the package's import, first line to last) and `setup::native` (`make`
of a missing `.so` and `ctypes.CDLL`) spans, each less what ran inside
it (`_setup_phases.py`). None on a program without the spans."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "import", "native")
