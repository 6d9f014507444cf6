"""Compile: seconds inside XLA backend compiles during set-up
(``/jax/core/compile/backend_compile_duration``; a persistent-cache hit
passes through with a small duration)."""
UNIT = "s"


def read(run):
    return run.setup_compile_s
