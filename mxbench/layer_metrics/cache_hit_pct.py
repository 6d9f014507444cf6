"""Compile: persistent-cache hits over backend compiles during set-up."""
UNIT = "%"


def read(run):
    if not run.setup_compiles:
        return None
    return 100.0 * run.setup_cache_hits / run.setup_compiles
