"""Compile: seconds of set-up in the `trace` and `lower` stages of every
compile record of the program (`compilewatch.programs()`: the watched
Gluon sites and the sharded step alike) (`_setup_phases.py`). The
benchmark's `compile_s` does not count these."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "trace_lower")
