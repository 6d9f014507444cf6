"""Compile: seconds of set-up in the `compile` stage of the program's
compile records that JAX's persistent cache served (`persistent_cache`
"hit"): reading and loading cached executables (`_setup_phases.py`)."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "cache_load")
