"""Device: what the measurement cannot see. Device time on device 0,
per step, of the events that run no other (``scopes.leaf_seconds``)
less the time under any ``mx.*`` scope of the program's own table
(``_program_scopes.py``). In a decoder cell it is what lies between
the blocks; in a BERT cell it is the encoder, whose blocks open no
scope. Nothing without a trace or on a program without the table."""
from mxbench import manifest

_scopes = manifest.load_module("layer_metrics", "_program_scopes.py")

UNIT = "ms/step"


def read(run):
    return _scopes.unscoped_ms_per_step(run)
