"""The program's own spans, for the host-loop metrics: the ``step::*``
regions that ``mxnet_tpu.telemetry.span`` marks where the work happens
(docs/OBSERVABILITY.md "Step spans"), read from the two places a live
span goes.

- The step log (``telemetry.step_log``): per closed step, each span
  name's summed seconds and the programs handed to the runtime.
  ``per_step`` reduces a window's records to seconds a step; the
  ``host_*_ms.train_img`` and ``launches_per_step.train_img`` readers
  take it from ``Run.untraced_s_per_step``, where a generator puts it
  beside the host seconds it clocks itself. Source ``host_clock`` /
  ``program_counter``.
- The profiler trace: every live span is a
  ``jax.profiler.TraceAnnotation``, so it sits on the host plane of the
  xplane on the clock of the device's ops. ``trace.py::load`` keeps
  only the benchmark's own ``mxbench/*`` annotations, so ``load`` here
  adds the program's to what it returns: a ``ProgramTrace`` has the
  ``devices`` and ``spans`` every reader of ``trace.py`` uses, and
  ``program``. ``launch_to_device_s`` and ``idle_gaps`` read that.

A program without the spans (a commit before they were added) gives an
empty step log and an empty ``program`` list: every function here then
returns nothing, and the readers leave their metric out.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, NamedTuple, Optional

from mxbench import trace as T

PROGRAM_PREFIX = "step::"
LAUNCH_SPAN = "step::update.launch"


class ProgramTrace(NamedTuple):
    devices: Dict[int, T.Device]
    spans: List[T.Op]          # host annotations named mxbench/*
    program: List[T.Op]        # host annotations named step::*


# ---------------------------------------------------------------------------
# the step log
# ---------------------------------------------------------------------------
def step_records(n: int) -> List[dict]:
    """The program's last ``n`` closed steps, or [] where the program
    keeps no step log."""
    from mxnet_tpu import telemetry
    read = getattr(telemetry, "step_log", None)
    return read(n) if read is not None and n > 0 else []


def per_step(records: Iterable[dict]) -> Dict[str, float]:
    """Seconds a step under each span name over ``records`` (a span that
    a step lacks counts as zero there); ``launches``: programs handed
    to the runtime a step, all paths together; ``step_log_steps``: how
    many records that was. {} for no records."""
    records = list(records)
    if not records:
        return {}
    out: Dict[str, float] = {"launches": 0.0}
    for rec in records:
        for name, row in rec["spans"].items():
            out[name] = out.get(name, 0.0) + row["seconds"]
        out["launches"] += sum(rec["launches"].values())
    out = {k: v / len(records) for k, v in out.items()}
    out["step_log_steps"] = float(len(records))
    return out


def span_ms(run, name: str) -> Optional[float]:
    """What a ``host_*_ms`` reader returns: milliseconds a step under
    span ``name`` in the window's step log, None where the run has no
    step log."""
    per = run.untraced_s_per_step
    if not per or not per.get("step_log_steps"):
        return None
    return per.get(name, 0.0) * 1e3


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------
def program_spans(path: str) -> List[T.Op]:
    """The host annotations of an ``.xplane.pb`` named ``step::*``,
    sorted by start (nanoseconds from the start of the trace, as every
    interval of ``trace.py``)."""
    from jax.profiler import ProfileData
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        found.append(T.Op(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    found.sort(key=lambda s: s.start)
    return found


def load(path: str) -> ProgramTrace:
    base = T.load(path)
    return ProgramTrace(base.devices, base.spans, program_spans(path))


def launch_to_device_s(trace, device: int, window) -> Optional[float]:
    """Median, over the traced steps, of the time from the start of a
    step's ``step::update.launch`` annotation to the start of that
    step's program on ``device`` ("XLA Modules": one event a launch).
    Launches and programs are matched in order: the device runs what it
    is handed in the order it is handed it, and nothing is in flight
    when a window starts. With steps in flight it holds the wait behind
    the step the device is still running. None without such spans."""
    launches = [s for s in getattr(trace, "program", ())
                if s.name == LAUNCH_SPAN and s.start >= window[0]
                and s.end <= window[1]]
    if not launches or device not in trace.devices:
        return None
    starts = sorted(m.start for m in trace.devices[device].modules
                    if m.start >= launches[0].start)
    waits = [m - s.start for s, m in zip(launches, starts)]
    return statistics.median(waits) / 1e9 if waits else None


def idle_gaps(trace, device: int, window, n: int = 10) -> List[List]:
    """``trace.py::idle_gaps`` over the program's spans: the ``n``
    longest idle gaps of ``device``, each named by the innermost
    program span that covers most of it (``host`` where none does).
    Of spans that cover the same, that function keeps the first: the
    shortest here, which is the one inside the others."""
    inner_first = sorted(getattr(trace, "program", ()),
                         key=lambda s: s.end - s.start)
    return T.idle_gaps(T.Trace(trace.devices, inner_first), device, window,
                       n)
