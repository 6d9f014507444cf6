"""Graph: seconds of set-up inside the program's `setup::graph` spans
(`HybridBlock._build_cache`, `parallel.sharded.trace_block`: symbol
tracing of the block, `layout_opt`, the AMP pass, `compile_graph`)
(`_setup_phases.py`)."""
from mxbench import manifest

_setup = manifest.load_module("layer_metrics", "_setup_phases.py")

UNIT = "s"


def read(run):
    return _setup.seconds(run, "graph")
