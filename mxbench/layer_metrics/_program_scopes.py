"""Device time by the program's own instruction -> scope table: what
the five ``*_ms.train`` readers beside this file share
(``optimizer_ms``, ``param_cast_ms``, ``lm_head_ms``, ``embed_ms``,
``unscoped_ms``). ``manifest.names_in`` skips a file whose name starts
with ``_``: this is no metric.

Which instruction of the compiled step belongs to which
``jax.named_scope`` is the program's to say, not a list kept here:
``mxnet_tpu.telemetry.device_scope_tables()`` gives the programs that
launched, most recent first (the one a trace last recorded among them,
whatever became of its step), each with the table built from the text
of the executable that ran (the innermost ``mx.*`` element of
``op_name``). The table taken is the first whose module the trace's
"XLA Modules" events name. The trace's events are summed through ``mxbench/scopes.py`` (its
rules for containers and for events without an ``op_name`` of their
own, unchanged).

Every function returns None where there is nothing to read: no trace
in this run, a program without the lookup (a commit before it), no
program that launched, a table that calls itself stale (the executable
came from a compile cache written with other scopes). A reader then
leaves its metric out; none prints 0 for what it cannot see.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from mxbench import scopes, trace as T


def table(run) -> Optional[dict]:
    """The program's table of the step that ran the traced window."""
    if run.trace is None:
        return None
    from mxnet_tpu import telemetry
    lookup = getattr(telemetry, "device_scope_tables", None)
    if lookup is None:
        return None
    ran = {m.name.split("(")[0] for m in run.trace.devices[0].modules}
    for entry in lookup():
        t0 = time.perf_counter()
        found = entry.table()
        if found["module"] in ran:
            print("[mxbench] the program's table of %s: %.2f s to build; "
                  "scopes of the lowering that the executable lacks: %s%s"
                  % (found["module"], time.perf_counter() - t0,
                     found.get("missing", []),
                     "; it calls itself stale" if found["stale"] else ""),
                  flush=True)
            return None if found["stale"] else found
    return None


def seconds(run) -> Optional[dict]:
    """``{"scopes": {scope: device seconds of the traced window},
    "leaf": seconds of every event that runs no other, "named": the
    scopes the table names}``, computed once a run."""
    if not hasattr(run, "program_scope_seconds"):
        run.program_scope_seconds = _seconds(run)
    return run.program_scope_seconds


def _seconds(run) -> Optional[dict]:
    found = table(run)
    if found is None or not run.traced_steps:
        return None
    window, steps = run.trace_window, run.traced_steps
    by_scope = scopes.seconds_by_scope(run.trace, 0, window, found["scopes"])
    leaf = scopes.leaf_seconds(run.trace, 0, window)
    print("[mxbench] program %s (module %s): %d instructions under a scope, "
          "%d under none; ms a step by scope %s of %.3f in events that run "
          "no other; longest under no scope [instruction, label, ms a "
          "step]: %s"
          % (found["program"], found["module"], len(found["scopes"]),
             len(found["unscoped"]),
             {k: round(v * 1e3 / steps, 3)
              for k, v in sorted(by_scope.items())},
             leaf * 1e3 / steps, _longest_unscoped(run, found, steps)),
          flush=True)
    return {"scopes": by_scope, "leaf": leaf,
            "named": set(found["scopes"].values())}


def _longest_unscoped(run, found, steps, n=12) -> list:
    """A free line's worth, no metric reads it: the instructions with
    most device time among the events under no scope."""
    lo, hi = run.trace_window
    per: Dict[str, float] = {}
    for op in run.trace.devices[0].ops:
        s, e = max(op.start, lo), min(op.end, hi)
        if e > s and not scopes.is_container(op.name):
            name = T.op_name(op.name)
            per[name] = per.get(name, 0.0) + (e - s)
    for name, _, ns in scopes._leaves(run.trace, 0, run.trace_window,
                                      found["scopes"]):
        per[name] -= ns
    top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, found["unscoped"].get(name, ""),
             round(ns / 1e6 / steps, 3)] for name, ns in top if ns > 0]


def ms_per_step(run, scope: str) -> Optional[float]:
    """A ``<part>_ms.train`` reader's number: device milliseconds a
    step under ``scope``, forward, recomputation and backward. None
    where the program's table names no instruction under it."""
    got = seconds(run)
    if got is None or scope not in got["named"]:
        return None
    return got["scopes"].get(scope, 0.0) * 1e3 / run.traced_steps


def unscoped_ms_per_step(run) -> Optional[float]:
    """Device milliseconds a step of the events that run no other and
    stand under no scope of the program."""
    got = seconds(run)
    if got is None:
        return None
    return (got["leaf"] - sum(got["scopes"].values())) * 1e3 \
        / run.traced_steps
