"""Device time by ``jax.named_scope``: which instructions of a compiled
step a scope of the program holds, and the trace's seconds under each.

The device trace names an "XLA Ops" event by its HLO line
(``%fusion.12 = bf16[...] fusion(...``) and, on this stack (jax 0.9 /
libtpu 0.0.34), carries no ``op_name`` of its own; the compiled
program's text does: every instruction line of ``compiled.as_text()``
ends in ``metadata={op_name="jit(fused_step)/.../mx.mamba2/
mx.mamba2.ssd/dot_general" ...}``, where a ``jax.named_scope`` is one
path element. The element survives the transformations that wrap it
(``transpose(jvp(mx.mamba2))``, ``checkpoint``, ``rematted_computation``),
so an instruction of a scope's forward, recomputation or backward all
carry the scope's name. ``scope_map`` reads instruction name -> scope
from the text; ``seconds_by_scope`` sums the trace's events through it.
A fusion is one instruction: it goes to the scope its own metadata
names (that of its root), whatever else was fused into it.

An instruction that runs others (``while``, ``conditional``, ``call``)
appears in the trace beside the ones it runs: it is left out of every
sum, so nothing counts twice. What it runs is counted, and an event in
its interval that names no scope of its own (a kernel or a copy the
compiler made carries no ``op_name`` of the program's: its kernel for a
``lax.ragged_dot`` did not) takes the container's.

A program without the scopes (a commit before they were added) gives
an empty map: every function here then returns nothing and the readers
leave their metric out.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Sequence

from mxbench import trace as T

CONTAINERS = ("while", "conditional", "call")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ELEMENT = re.compile(r"[\w.]+")


def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The first of ``scopes`` that is a whole path element of an
    instruction's ``op_name`` (inside ``jvp(...)`` and the like too).
    Which scopes a program names is its configuration's to say
    (``configs/<name>.py::SCOPES``), innermost first: an instruction
    under ``mx.mamba2/mx.mamba2.ssd`` is the scan's, not the rest of
    the mixer's."""
    elements = set(_ELEMENT.findall(op_name))
    for scope in scopes:
        if scope in elements:
            return scope
    return None


def _instructions(compiled_text: str):
    """(instruction name, its ``op_name``) over every computation of a
    compiled program's text."""
    for line in compiled_text.splitlines():
        name = _INSTRUCTION.match(line)
        meta = _OP_NAME.search(line)
        if name and meta:
            yield name.group(1), meta.group(1)


def scope_map(compiled_text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """{instruction name: scope} of the instructions under a scope."""
    found = ((name, scope_of(op_name, scopes))
             for name, op_name in _instructions(compiled_text))
    return {name: scope for name, scope in found if scope is not None}


def label_map(compiled_text: str) -> Dict[str, str]:
    """{instruction name: the last two elements of its ``op_name``}: a
    name to print beside ``fusion.3988``."""
    return {name: "/".join(op_name.split("/")[-2:])
            for name, op_name in _instructions(compiled_text)}


def top_by_label(trace, device: int, window, scopes_of: Dict[str, str],
                 labels: Dict[str, str], steps: int, n: int = 6
                 ) -> Dict[str, list]:
    """For each scope the ``n`` instructions with most device time,
    ``[[instruction, label, ms a step], ...]`` (a free line's worth:
    no metric reads it)."""
    acc: Dict[str, Dict[str, float]] = {}
    for name, scope, ns in _leaves(trace, device, window, scopes_of):
        per = acc.setdefault(scope, {})
        per[name] = per.get(name, 0.0) + ns
    return {scope: [[k, labels.get(k, ""), v / 1e6 / max(steps, 1)]
                    for k, v in sorted(per.items(), key=lambda kv: -kv[1])[:n]]
            for scope, per in sorted(acc.items())}


def is_container(event_name: str) -> bool:
    return T.op_kind(event_name) in CONTAINERS


def _leaves(trace, device: int, window, scopes_of: Dict[str, str]):
    """(instruction name, scope, clipped nanoseconds) of every event
    that runs no other. An event the compiler generated without an
    ``op_name`` of the program's takes the scope of the container whose
    interval holds it, where that has one: a ``conditional`` under
    ``mx.moe.experts`` runs its branch's instructions inside its own
    event."""
    lo, hi = window
    open_: list = []        # (end, scope) of the containers around
    for op in sorted(trace.devices[device].ops, key=lambda o: (o.start,
                                                               -o.end)):
        while open_ and open_[-1][0] <= op.start:
            open_.pop()
        name = T.op_name(op.name)
        scope = scopes_of.get(name) or (open_[-1][1] if open_ else None)
        if is_container(op.name):
            open_.append((op.end, scope))
            continue
        s, e = max(op.start, lo), min(op.end, hi)
        if e > s and scope is not None:
            yield name, scope, e - s


def seconds_by_scope(trace, device: int, window, scopes_of: Dict[str, str]
                     ) -> Dict[str, float]:
    """Summed device seconds of the window's "XLA Ops" events under
    each scope (containers left out, what they run counted). {} for an
    empty map."""
    out: Dict[str, float] = {}
    if not scopes_of:
        return out
    for _, scope, ns in _leaves(trace, device, window, scopes_of):
        out[scope] = out.get(scope, 0.0) + ns / 1e9
    return out


def leaf_seconds(trace, device: int, window) -> float:
    """Summed seconds of every event that runs no other: beside the
    busy union it shows whether events nest or overlap."""
    return T.seconds_where(trace, device, window,
                           lambda name: not is_container(name))


def with_parents(seconds: Dict[str, float],
                 scopes: Iterable[str]) -> Dict[str, float]:
    """Each scope's seconds with its inner scopes' added (``mx.mamba2``
    then holds its scan too)."""
    out = dict(seconds)
    for inner in scopes:
        for outer in scopes:
            if inner != outer and inner.startswith(outer + ".") \
                    and inner in seconds:
                out[outer] = out.get(outer, 0.0) + seconds[inner]
    return out


def ms_per_step(run, scope: str) -> Optional[float]:
    """A ``<kernel>_ms`` reader's number: the scope's device seconds of
    the traced window, per step; None where the run has none."""
    seconds = getattr(run, "scope_seconds", None)
    if not seconds or scope not in seconds or not run.traced_steps:
        return None
    return seconds[scope] * 1e3 / run.traced_steps


def roofline_pct(run, scope: str) -> Optional[float]:
    """A ``<kernel>_roofline_pct`` reader's number: the least time the
    chip could take for what a step executes in the scope (the larger
    of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
    run's ``scope_costs``) over the scope's seconds a step."""
    from mxbench import manifest
    ms = ms_per_step(run, scope)
    costs = getattr(run, "scope_costs", None)
    if not ms or not costs or scope not in costs:
        return None
    peaks = manifest.peaks(run.device_kind)
    flops, nbytes = costs[scope]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)

