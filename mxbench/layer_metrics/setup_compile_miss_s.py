"""Compile: seconds of set-up in the `compile` stage of the program's
compile records that JAX's persistent cache did not serve
(`persistent_cache` "miss", or no word: no cache): real compiles. An
earlier line names them (`_setup_phases.py`)."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "compile_miss")
