"""The reduction from a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to intervals and sums. Every per-layer
metric that names the device trace as its source goes through here, so
every PR computes the same number in the same way.

What a TPU trace looks like (jax 0.9 / libtpu 0.0.34, PR 23): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per program launch), ``XLA Ops`` (one event per executed HLO op, named
by the HLO line: ``%pallas_layer_norm_fwd.1 = bf16[...] custom-call(...``)
and ``Async XLA Ops`` (the start-to-done span of asynchronous ops); one
plane ``/host:CPU`` with a line per thread, on which a
``jax.profiler.TraceAnnotation`` appears under its own name. Times are
nanoseconds from the start of the trace. Host and device clocks agreed
to about 1 ms in the trace this was written against.

Intervals are ``(start, end)`` pairs in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "mxbench/"
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")
RELAYOUT_KINDS = ("copy", "copy-start", "copy-done", "transpose")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%([^\s=]+)")
_OP_KIND = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


class Op(NamedTuple):
    name: str       # the event's name as the trace prints it
    start: float
    end: float


class Device(NamedTuple):
    ops: List[Op]          # "XLA Ops"
    async_ops: List[Op]    # "Async XLA Ops"
    modules: List[Op]      # "XLA Modules"


class Trace(NamedTuple):
    devices: Dict[int, Device]
    spans: List[Op]        # host annotations named mxbench/*


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file. Raises when it holds no TPU plane:
    a trace of the CPU backend has no device to report on."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    spans: List[Op] = []
    seen = []
    for plane in prof.planes:
        seen.append(plane.name)
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {"XLA Ops": [], "Async XLA Ops": [], "XLA Modules": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
            devices[int(m.group(1))] = Device(
                lines["XLA Ops"], lines["Async XLA Ops"],
                lines["XLA Modules"])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Op(ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    if not devices:
        raise ValueError("no /device:TPU:<n> plane in %s (planes: %s)"
                         % (path, seen))
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of ``a`` that no interval of ``b`` covers."""
    cover = union(b)
    out: List[Interval] = []
    for s, e in union(a):
        at = s
        for cs, ce in cover:
            if ce <= at:
                continue
            if cs >= e:
                break
            if cs > at:
                out.append((at, cs))
            at = max(at, ce)
            if at >= e:
                break
        if at < e:
            out.append((at, e))
    return out


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------
def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_NAME.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def op_kind(name: str) -> str:
    """The HLO opcode of an "XLA Ops" event (``custom-call``, ``fusion``,
    ``copy``, ``all-reduce`` ...). Events whose name is cut before the
    opcode fall back to the instruction's name without its numbering."""
    eq = name.find(" = ")
    if eq >= 0:
        m = _OP_KIND.search(name, eq)
        if m:
            return m.group(1)
    return re.sub(r"(\.clone|\.\d+)*$", "", op_name(name))


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(kind == k or kind.startswith(k + "-")
               for k in COLLECTIVE_KINDS)


def is_relayout(name: str) -> bool:
    return op_kind(name) in RELAYOUT_KINDS


def is_pallas(name: str) -> bool:
    return "pallas_" in op_name(name) and op_kind(name) == "custom-call"


def intervals_of(ops: Sequence[Op], pred=None) -> List[Interval]:
    return [(o.start, o.end) for o in ops if pred is None or pred(o.name)]


def window_of(trace: Trace) -> Interval:
    """The traced steady part: first ``mxbench/*`` span's start to the
    last one's end."""
    if not trace.spans:
        raise ValueError("the trace holds no %s* host span" % SPAN_PREFIX)
    return (min(s.start for s in trace.spans),
            max(s.end for s in trace.spans))


def busy(trace: Trace, device: int, window: Interval) -> List[Interval]:
    """Union of the intervals in which an op ran on ``device`` inside
    ``window`` ("XLA Ops"; the program launches where a trace has no op
    line)."""
    dev = trace.devices[device]
    return union(clip(intervals_of(dev.ops or dev.modules), window))


def seconds_where(trace: Trace, device: int, window: Interval, pred) -> float:
    """Summed device durations (not a union: what each op cost) of the
    "XLA Ops" events that ``pred(name)`` accepts, in seconds."""
    return total(clip(intervals_of(trace.devices[device].ops, pred),
                      window)) / 1e9


def collective_intervals(trace: Trace, device: int,
                         window: Interval) -> List[Interval]:
    """Collectives on ``device``: synchronous ones from "XLA Ops", and
    the start-to-done span of asynchronous ones from "Async XLA Ops"
    (whose -start/-done events on "XLA Ops" are only the issue slots)."""
    dev = trace.devices[device]
    sync = [(o.start, o.end) for o in dev.ops if is_collective(o.name)
            and not op_kind(o.name).endswith(("-start", "-done"))]
    asyn = intervals_of(dev.async_ops, is_collective)
    return union(clip(sync + asyn, window))


def exposed(collectives: Iterable[Interval],
            others: Iterable[Interval]) -> float:
    """Nanoseconds of ``collectives`` during which no interval of
    ``others`` (the other ops of that device) runs."""
    return total(subtract(collectives, others))


def exposed_collective_s(trace: Trace, device: int,
                         window: Interval) -> float:
    dev = trace.devices[device]
    others = clip([(o.start, o.end) for o in dev.ops
                   if not is_collective(o.name)], window)
    return exposed(collective_intervals(trace, device, window),
                   others) / 1e9


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------
def top_ops(trace: Trace, device: int, window: Interval,
            n: int = 10) -> List[List]:
    """The ``n`` ops with most device time, ``[[name, seconds], ...]``,
    under the instruction names the trace prints."""
    acc: Dict[str, float] = {}
    for o in trace.devices[device].ops:
        s, e = max(o.start, window[0]), min(o.end, window[1])
        if e > s:
            key = op_name(o.name)
            acc[key] = acc.get(key, 0.0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def idle_gaps(trace: Trace, device: int, window: Interval,
              n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of ``device``, each named by the
    ``mxbench/*`` host span that covers most of it (``host`` where none
    does): ``[[span name, seconds], ...]``."""
    gaps = subtract([window], busy(trace, device, window))
    rows = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "host", 0.0
        for sp in trace.spans:
            c = min(e, sp.end) - max(s, sp.start)
            if c > cover:
                best, cover = sp.name, c
        rows.append([best, (e - s) / 1e9])
    return rows


def count_spans(trace: Trace, name: str, window: Optional[Interval] = None
                ) -> int:
    return sum(1 for s in trace.spans if s.name == name
               and (window is None
                    or (s.start >= window[0] and s.end <= window[1])))


def span_seconds(trace: Trace, name: str,
                 window: Optional[Interval] = None) -> float:
    """Summed duration of the ``name`` spans (those inside ``window``)."""
    return sum(s.end - s.start for s in trace.spans if s.name == name
               and (window is None
                    or (s.start >= window[0] and s.end <= window[1]))) / 1e9
