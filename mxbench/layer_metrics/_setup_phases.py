"""Set-up by the program's own timeline: what the nine ``setup_*_s``
readers beside this file share. ``manifest.names_in`` skips a file
whose name starts with ``_``: this is no metric.

``mxnet_tpu.telemetry.startup_phases(until=)`` merges the program's
``setup::*`` spans (import, native, init, graph, place, first_launch)
and ``compilewatch``'s compile records (trace + lower, and the compile
stage by what the persistent cache said: ``compile_miss`` or
``cache_load``) into seconds by phase, each exclusive, on
``time.perf_counter`` (docs/OBSERVABILITY.md "Start-up"). The cut is
the benchmark's own set-up instant: ``setup_s`` is seconds since the
process started (``meters.SetupClock``), so on the program's clock it
lies at ``time.perf_counter() - meters.process_age_s() + setup_s`` (to
the 10 ms of ``/proc``). What the phases leave of ``setup_s`` is
``setup_unattributed_s``: the benchmark's own share (its reference and
feed), the warm-up steps' device time, the interpreter's start.

Every function returns None on a program without ``startup_phases`` (a
commit before it): a reader then leaves its metric out.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from mxbench import meters


def phases(run) -> Optional[Dict[str, float]]:
    """``startup_phases`` up to the set-up instant, read once a run."""
    if not hasattr(run, "setup_phases"):
        run.setup_phases = _phases(run)
    return run.setup_phases


def _phases(run) -> Optional[Dict[str, float]]:
    from mxnet_tpu import compilewatch, telemetry
    read = getattr(telemetry, "startup_phases", None)
    if read is None:
        return None
    setup_s = run.end_to_end["setup_s"][0]
    until = time.perf_counter() - meters.process_age_s() + setup_s
    got = read(until=until)
    missed = [(r["fn"], r["instance"][:80], r["signature"][:4],
               round(r["stages"].get("compile", 0.0), 3))
              for r in getattr(compilewatch, "cache_misses", list)()
              if r["time"] < until]
    print("[mxbench] set-up by the program's phases, seconds of %.3f: %s; "
          "%d set-up span(s), %d compile record(s); %d missed the "
          "persistent cache [fn, instance, first arguments, compile s], "
          "longest first: %s"
          % (setup_s, {k: round(v, 3) for k, v in got.items()},
             len(telemetry.setup_log()), len(compilewatch.programs()),
             len(missed), missed[:12]), flush=True)
    return got


def seconds(run, *names: str) -> Optional[float]:
    """A ``setup_<phase>_s`` reader's number: the phases ``names``
    together."""
    got = phases(run)
    return None if got is None else sum(got[name] for name in names)


def unattributed(run) -> Optional[float]:
    """``setup_s`` less what the program's spans and records cover."""
    got = phases(run)
    if got is None:
        return None
    return run.end_to_end["setup_s"][0] - got["covered"]
