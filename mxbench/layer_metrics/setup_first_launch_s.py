"""Device: seconds of set-up inside the program's `setup::first_launch`
spans: the host's side of each program's first call (the executable
loaded onto the device, the launch handed over; not waited for), on
the compile-miss path only (`_setup_phases.py`)."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "first_launch")
