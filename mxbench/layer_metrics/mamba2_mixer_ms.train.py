"""Kernels: device time of the instructions under the program's
``mx.mamba2`` scope (the Mamba-2 mixers whole: the pre-norm, in_proj,
the conv's taps and SiLU, softplus, the gated norm and out_proj, and
the scan under ``mx.mamba2.ssd`` inside it, which ``ssd_scan_ms.train``
reads alone) on device 0, per step, summed over forward, recomputation
and backward (``mxbench/scopes.py``). Nothing on a program without the
scope."""
UNIT = "ms/step"
SCOPE = "mx.mamba2"
INNER = "mx.mamba2.ssd"


def read(run):
    seconds = getattr(run, "scope_seconds", None)
    if not seconds or SCOPE not in seconds or not run.traced_steps:
        return None
    return (seconds[SCOPE] + seconds.get(INNER, 0.0)) * 1e3 \
        / run.traced_steps
