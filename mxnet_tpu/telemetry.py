"""Runtime telemetry — process-wide metrics registry + span tracing.

The paper's engine wraps every kernel and comm call with timestamps;
this module is that spine for the rebuild (ISSUE 3; arxiv 2008.01040
motivates op-level timing as the raw material for perf work, arxiv
2506.17615 the per-collective byte/latency accounting).

Three instrument kinds, one flat process-wide registry:

- :class:`Counter` — monotonically increasing totals
  (``counter(name, **labels).inc()``).
- :class:`Gauge` — point-in-time values (``gauge(name).set(v)`` /
  ``.inc()`` / ``.dec()``).
- :class:`Histogram` — fixed log-scale buckets (4 per decade, 1e-6s to
  1e3s — sized for durations in seconds), tracking count/sum/min/max
  and estimating percentiles from the bucket counts.

Plus a :class:`span` context manager, the ONE span primitive of the
training path. A live span (telemetry on, or the profiler in ``run``)
goes to four places: a ``jax.profiler.TraceAnnotation`` of its name (so
it sits in a ``jax.profiler`` trace beside the device's ops, on the
profiler's clock), the bounded per-step log (:func:`step_log`: name,
start, end, parent span, step), the chrome-trace profiler
(``profiler.record_event``) and a latency histogram (when telemetry is
enabled).

Cost model: everything is gated on ``MXNET_TELEMETRY`` (cached bool —
call :func:`refresh` after mutating the environment). The disabled
path is one attribute check per call site (tools/telemetry_micro.py
asserts <5% overhead on the engine microbench); the enabled path is a
dict lookup plus a lock-guarded float update.

Exposure, three ways (docs/OBSERVABILITY.md):

- :func:`snapshot` — plain dict of every instrument's current value.
- :func:`render_prometheus` — Prometheus text exposition.
- a heartbeat line every ``MXNET_TELEMETRY_HEARTBEAT`` seconds on the
  ``mxnet_tpu.telemetry`` logger: step count + rate, p50/p99 step
  time, pending engine ops and guard-event totals — the flight
  recorder a hung or slow run gets diagnosed from.

Wired call sites: engine.push_async (queued→running→done spans +
per-label latency), kvstore/dist (bytes, call latency, retry/deadline
counters), guardrails.emit, faultinject fires, model checkpoint writes,
Monitor stats, and the per-step phases (``step::<phase>`` spans,
docs/OBSERVABILITY.md "Step spans"), each where its work happens:
``forward`` in HybridBlock's call into its CachedOp under
``autograd.record()`` (the Estimator's own pair around the whole pass
folds the block's into it; a net that is not hybridized has a forward
span only under the Estimator), ``backward`` in ``autograd.backward``,
``update`` around ``Trainer.step`` with ``update.prep`` /
``update.launch`` / ``update.writeback`` on the fused path and
``allreduce`` / ``guard`` / ``optimizer`` on the classic one,
``sharded`` with ``sharded.place`` / ``sharded.launch`` in
``ShardedTrainStep.step``, ``data`` in the Estimator / DataLoader,
and Module.update's allreduce / guard / optimizer. The regions of a
job's start carry ``setup::<phase>`` spans (:func:`setup_phase`: the
package's import, the native libraries' load, ``ParameterDict.initialize``,
``HybridBlock._build_cache`` and ``parallel.sharded.trace_block``,
``ShardedTrainStep``'s placement, a program's first launch), kept apart
from the steps' and merged with compilewatch's records by
:func:`startup_phases` (docs/OBSERVABILITY.md "Start-up").
"""
from __future__ import annotations

import bisect
import collections
import heapq
import logging
import re
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation
try:        # whether a trace is recording has no public reader
    from jax._src import profiler as _jax_profiler
except ImportError:     # a JAX that moved it: no program is ever pinned
    _jax_profiler = None

from . import profiler

__all__ = ["Counter", "Gauge", "Histogram", "span", "phase", "counter",
           "gauge", "histogram", "enabled", "enable", "refresh",
           "snapshot", "render_prometheus", "mark_step", "step_log",
           "setup_phase", "setup_log", "startup_phases", "STARTUP_PHASES",
           "count_launch", "innermost_scope", "hlo_scopes",
           "DeviceProgram", "device_scope_tables",
           "heartbeat_line", "count_event", "guard_event",
           "fault_event", "checkpoint_event", "reset",
           "memory_snapshot", "memory_diff", "ndarray_live",
           "parse_metric_key",
           "debit_stall", "peak_flops", "known_peak_flops",
           "local_fleet_stats",
           "fleet_snapshot", "FLEET_FIELDS", "crash_bundle",
           "install_crash_bundler"]

_LOG = logging.getLogger("mxnet_tpu.telemetry")


# ---------------------------------------------------------------------------
# enable gate — ONE cached attribute read on every hot-path check
# ---------------------------------------------------------------------------
class _State:
    __slots__ = ("on",)

    def __init__(self):
        self.on: Optional[bool] = None     # None = not yet resolved


_STATE = _State()


def _resolve() -> bool:
    from .config import get as _cfg
    _STATE.on = bool(_cfg("MXNET_TELEMETRY"))
    if _STATE.on:
        _maybe_start_heartbeat()
    return _STATE.on


def enabled() -> bool:
    """Whether telemetry collection is on (MXNET_TELEMETRY). The env
    read is CACHED — unlike config.get's live reads — because this gate
    sits on every op dispatch; call :func:`refresh` after changing the
    environment."""
    on = _STATE.on
    if on is None:
        on = _resolve()
    return on


def enable(on: bool = True):
    """Programmatic override of the MXNET_TELEMETRY gate. Disabling
    also stops the heartbeat thread."""
    _STATE.on = bool(on)
    if on:
        _maybe_start_heartbeat()
    else:
        _stop_heartbeat()


def refresh():
    """Drop the cached gate (and heartbeat period) so the next check
    re-reads MXNET_TELEMETRY* from the environment. Also refreshes the
    commwatch gate (MXNET_COMMWATCH) and the cached peak-FLOPs figure
    so one refresh covers every cached observability knob."""
    _STATE.on = None
    _stop_heartbeat()
    _PEAK[0] = None
    try:
        from . import commwatch
        commwatch.refresh()
    except Exception:
        pass
    try:
        from . import tracing
        tracing.refresh()
    except Exception:
        pass
    try:
        from . import perfwatch
        perfwatch.refresh()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------
# log-scale bucket bounds: 4 per decade, 1e-6 .. 1e3 (seconds)
BUCKETS: Tuple[float, ...] = tuple(10.0 ** (e / 4.0)
                                   for e in range(-24, 13))


class Counter:
    """Monotonic counter (thread-safe)."""

    __slots__ = ("name", "labels", "_lock", "value")

    kind = "counter"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, delta: float = 1.0):
        with self._lock:
            self.value += delta

    def get(self) -> float:
        return self.value


class Gauge:
    """Point-in-time value (thread-safe)."""

    __slots__ = ("name", "labels", "_lock", "value")

    kind = "gauge"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self.value = 0.0

    def set(self, value: float):
        with self._lock:
            self.value = float(value)

    def inc(self, delta: float = 1.0):
        with self._lock:
            self.value += delta

    def dec(self, delta: float = 1.0):
        with self._lock:
            self.value -= delta

    def get(self) -> float:
        return self.value


class Histogram:
    """Fixed log-scale-bucket histogram (thread-safe). Buckets are
    shared across every instance (:data:`BUCKETS`) so aggregation
    across processes stays meaningful."""

    __slots__ = ("name", "labels", "_lock", "counts", "count", "sum",
                 "min", "max")

    kind = "histogram"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self.counts = [0] * (len(BUCKETS) + 1)   # +1 = +Inf bucket
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def observe(self, value: float):
        v = float(value)
        i = bisect.bisect_left(BUCKETS, v)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v

    def percentile(self, p: float) -> float:
        """Estimate the p-th percentile (0..100) from bucket counts:
        the upper bound of the bucket holding the target rank (the
        usual Prometheus-style histogram_quantile approximation)."""
        with self._lock:
            total = self.count
            if total == 0:
                return 0.0
            rank = p / 100.0 * total
            seen = 0
            for i, c in enumerate(self.counts):
                seen += c
                if seen >= rank and c:
                    if i < len(BUCKETS):
                        return min(BUCKETS[i], self.max)
                    return self.max
            return self.max

    def summary(self) -> dict:
        with self._lock:
            count, total = self.count, self.sum
            mn = self.min if self.count else 0.0
            mx = self.max if self.count else 0.0
        return {"count": count, "sum": total, "min": mn, "max": mx,
                "p50": self.percentile(50), "p90": self.percentile(90),
                "p99": self.percentile(99)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REG_LOCK = threading.Lock()
_METRICS: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], object] = {}


def _instrument(cls, name: str, labels: dict):
    key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
    m = _METRICS.get(key)              # racy read is fine: dict get is
    if m is None:                      # atomic, creation is locked
        with _REG_LOCK:
            m = _METRICS.get(key)
            if m is None:
                m = cls(name, key[1])
                _METRICS[key] = m
    if type(m) is not cls:
        raise TypeError("metric %r already registered as %s"
                        % (name, type(m).__name__))
    return m


def counter(name: str, /, **labels) -> Counter:
    return _instrument(Counter, name, labels)


def gauge(name: str, /, **labels) -> Gauge:
    return _instrument(Gauge, name, labels)


def histogram(name: str, /, **labels) -> Histogram:
    return _instrument(Histogram, name, labels)


def reset():
    """Drop every registered instrument, the step clock and the
    MFU/goodput meter window (test isolation; production code never
    calls this). Also lets go of the program a trace last recorded
    (``device_scope_tables``'s one strong reference) and with it of its
    executable, if no table was built from it."""
    with _REG_LOCK:
        _METRICS.clear()
    with _STEP_LOCK:
        _STEP["count"] = 0
        _STEP["last"] = None
        _STEP["t0"] = None
        _STEP["useful_s"] = 0.0
        _STEP["stall_s"] = 0.0
        _STEP["flops0"] = 0.0
        _STEP["compile_at_last"] = 0.0
        _STEPLOG.clear()
        _SETUPLOG.clear()
    with _FLEET_LOCK:
        _FLEET["last"] = None
    _LAST_LAUNCHED[0] = _TRACED[0] = None
    with _BUNDLE_LOCK:
        # crash-bundle budget + recent-event tail are per-"run" state:
        # a test (or a deliberate meter re-arm) starting fresh gets the
        # full bundle budget back
        _BUNDLE["written"] = 0
        if _BUNDLE["recent"] is not None:
            _BUNDLE["recent"].clear()
    try:
        from . import commwatch
        commwatch.reset()
    except Exception:
        pass
    try:
        from . import tracing
        tracing.reset()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# spans — chrome trace + latency histogram in one context manager
# ---------------------------------------------------------------------------
class _StepLog:
    """The spans of the step that is open and of the last
    ``STEP_LOG_STEPS`` closed ones. A live span appends one tuple
    ``(name, start, end, parent, step)`` (``time.perf_counter``
    seconds; ``parent`` the enclosing open span's name on the same
    thread, or None; ``step`` the number of steps :func:`mark_step`
    had counted) on exit; :func:`mark_step` closes the open step with
    the launches counted since the last close. Both ends are bounded:
    the ring drops the oldest step, and a step that never closes (a
    process that serves, an evaluation loop) stops taking spans at
    ``OPEN_SPAN_CAP`` and counts what it dropped."""
    STEP_LOG_STEPS = 512
    OPEN_SPAN_CAP = 4096

    __slots__ = ("open", "dropped", "closed", "counted")

    def __init__(self):
        self.clear()

    def clear(self):
        self.open: List[tuple] = []
        self.dropped = 0
        self.closed = collections.deque(maxlen=self.STEP_LOG_STEPS)
        self.counted: Dict[tuple, float] = {}    # totals at the last close


class _OpenSpans(threading.local):
    def __init__(self):
        self.names: List[str] = []     # innermost last


class _SetupLog:
    """The spans of category ``setup`` (:func:`setup_phase`), which
    belong to no step: ``(name, start, end, parent)`` tuples in order of
    exit, ``time.perf_counter`` seconds. A start is a few hundred of
    them; past ``CAP`` a span is counted in ``dropped`` and not kept."""
    CAP = 4096

    __slots__ = ("spans", "dropped")

    def __init__(self):
        self.clear()

    def clear(self):
        self.spans: List[tuple] = []
        self.dropped = 0


_STEPLOG = _StepLog()
_SETUPLOG = _SetupLog()
_OPEN_SPANS = _OpenSpans()
SETUP_CATEGORY = "setup"
LAUNCH_COUNTER = "mx_program_launches_total"
LAUNCH_PATHS = ("gluon", "sharded")
FUSED_STEP_COUNTER = "mx_fused_step_total"
# the counters mark_step closes into the step log: a step's record key
# -> (counter, its label, the label's values)
_STEP_COUNTERS = {
    "launches": (LAUNCH_COUNTER, "path", LAUNCH_PATHS),
    "fused_steps": (FUSED_STEP_COUNTER, "donated", ("1", "0")),
}


class span:
    """Time a region. When live (telemetry on, or the chrome-trace
    profiler in ``run``) it enters a ``jax.profiler.TraceAnnotation``
    of its name, and on exit appends to the step log
    (:func:`step_log`), writes the chrome-trace event (category `cat`)
    and, when telemetry is on, observes histogram `hist` (with
    `labels`). With both off a span is one gate read: no clock, no
    annotation, no record. A span opened inside an open span of the
    same name on the same thread is folded into it (the Estimator's
    ``step::forward`` around a hybridized block's own): one sample,
    not two. Instrumentation failures are swallowed — a span must
    never poison the region it observes. ``cancel()`` inside the block
    drops the record (e.g. a probe that turned out not to be real
    work). A span of category ``setup`` goes to the set-up log
    (:func:`setup_log`) in place of the step log: it belongs to no
    step. ``backdate(t)`` inside the block moves the span's start to
    ``t`` (the package's import began before a span could be made)."""

    __slots__ = ("name", "cat", "hist", "labels", "args", "_t0", "_live",
                 "_ann", "_parent")

    def __init__(self, name: str, cat: str = "telemetry",
                 hist: Optional[str] = None, args: Optional[dict] = None,
                 **labels):
        self.name = name
        self.cat = cat
        self.hist = hist
        self.labels = labels
        self.args = args

    def cancel(self):
        self._live = False

    def backdate(self, t0: float):
        if self._ann is not None:
            self._t0 = min(self._t0, t0)

    def __enter__(self):
        self._ann = None
        try:
            self._live = enabled() or profiler.state() == "run"
            if self._live:
                names = _OPEN_SPANS.names
                if self.name in names:
                    self._live = False      # folded into the open one
                    return self
                self._parent = names[-1] if names else None
                ann = TraceAnnotation(self.name)
                ann.__enter__()
                names.append(self.name)
                self._ann = ann
                self._t0 = time.perf_counter()
        except Exception:
            self._live = False
        return self

    def __exit__(self, *exc):
        ann = self._ann
        if ann is None:
            return False
        try:
            t1 = time.perf_counter()
            ann.__exit__(None, None, None)
            _OPEN_SPANS.names.pop()
            if not self._live:              # cancelled inside the block
                return False
            if self.cat == SETUP_CATEGORY:
                log = _SETUPLOG
                if len(log.spans) < log.CAP:
                    log.spans.append((self.name, self._t0, t1, self._parent))
                else:
                    log.dropped += 1
            else:
                log = _STEPLOG
                if len(log.open) < log.OPEN_SPAN_CAP:
                    log.open.append((self.name, self._t0, t1, self._parent,
                                     _STEP["count"]))
                else:
                    log.dropped += 1
            dt = t1 - self._t0
            profiler.record_event(self.name, self.cat, self._t0 * 1e6,
                                  dt * 1e6, self.args)
            if self.hist is not None and enabled():
                histogram(self.hist, **self.labels).observe(dt)
        except Exception:
            pass
        return False


def phase(name: str) -> span:
    """A step-phase span: chrome-trace event ``step::<name>`` (category
    ``step``) + the ``mx_step_phase_seconds{phase=<name>}`` histogram.
    Phases: data / forward / backward / update (with update.prep /
    update.launch / update.writeback on the fused path, where the
    histogram files the whole of it under ``fused_step``; allreduce /
    guard / optimizer on the classic one) / sharded (sharded.place /
    sharded.launch) / zero_step / modelwatch (the training-dynamics
    read on steps where no guard shares it). The names are stable:
    docs/OBSERVABILITY.md "Step spans"."""
    return span("step::%s" % name, "step", hist="mx_step_phase_seconds",
                phase=name)


# ---------------------------------------------------------------------------
# start-up — the regions of a job's start, from the import to a
# program's first launch, on one timeline with compilewatch's compile
# records (docs/OBSERVABILITY.md "Start-up")
# ---------------------------------------------------------------------------
SETUP_PREFIX = "setup::"
STARTUP_PHASES = ("import", "native", "init", "graph", "place",
                  "trace_lower", "compile_miss", "cache_load",
                  "first_launch")
# a compile record's stage -> the phase its seconds count under; the
# ``compile`` stage (and ``total``, the degraded whole-call timing)
# goes by the record's ``persistent_cache``
_STAGE_PHASE = {"trace": "trace_lower", "lower": "trace_lower"}


def setup_phase(name: str) -> span:
    """A set-up span: chrome-trace event ``setup::<name>`` (category
    ``setup``) + the ``mx_setup_phase_seconds{phase=<name>}`` histogram,
    kept in :func:`setup_log` and in no step's record. Phases, each
    opened where the work happens: import (the package's), native
    (``make`` + ``ctypes.CDLL`` of a native library), init (parameter
    initialisers, deferred ones too), graph (symbol tracing, layout and
    AMP passes, ``compile_graph``), place (``ShardedTrainStep``'s master
    copies, ``device_put``s, optimizer states, AUTO re-layout),
    first_launch (the host's side of a program's first call, on the
    compile-miss path only). The names are stable:
    docs/OBSERVABILITY.md "Start-up"."""
    return span(SETUP_PREFIX + name, SETUP_CATEGORY,
                hist="mx_setup_phase_seconds", phase=name)


def setup_log() -> List[tuple]:
    """Every set-up span kept so far, in order of exit:
    ``(name, start, end, parent)``, ``time.perf_counter`` seconds;
    ``parent`` the enclosing open span's name on the same thread."""
    return list(_SETUPLOG.spans)


def _startup_intervals() -> List[tuple]:
    """``(phase, start, end)`` of every set-up span and of every stage
    of every compile record (stages run back to back from the record's
    ``time``, in the order the record lists them)."""
    out = [(name[len(SETUP_PREFIX):], start, end)
           for name, start, end, _parent in _SETUPLOG.spans]
    from . import compilewatch
    for rec in compilewatch.programs():
        at = rec["time"]
        # no word from JAX (no persistent cache): a real compile
        how = "cache_load" if rec.get("persistent_cache") == "hit" \
            else "compile_miss"
        for stage, dt in rec["stages"].items():
            out.append((_STAGE_PHASE.get(stage, how), at, at + dt))
            at += dt
    return out


def startup_phases(until: Optional[float] = None) -> Dict[str, float]:
    """Seconds of the start by phase (:data:`STARTUP_PHASES`), from
    :func:`setup_log` and ``compilewatch.programs()`` on one clock, up
    to ``until`` (a ``time.perf_counter`` instant; default: the first
    :func:`mark_step`; an interval that starts after it is left out,
    one that straddles it is cut there). Each phase is **exclusive**:
    an instant belongs to the innermost interval that covers it (the
    one that started last), so a compile inside ``setup::init`` counts
    as compile and not as init, and the phases sum to ``covered``, the
    union of all the intervals: what of the start the program's own
    spans and records see."""
    if until is None:
        until = _STEP["t0"]
    intervals = sorted(
        (start, end if until is None else min(end, until), name)
        for name, start, end in _startup_intervals()
        if until is None or start < until)
    out = dict.fromkeys(STARTUP_PHASES, 0.0)
    edges = sorted({t for start, end, _ in intervals for t in (start, end)})
    active: List[tuple] = []    # heap: latest start first, then shortest
    nxt = 0
    for lo, hi in zip(edges, edges[1:]):
        while nxt < len(intervals) and intervals[nxt][0] <= lo:
            start, end, name = intervals[nxt]
            heapq.heappush(active, (-start, end, name))
            nxt += 1
        while active and active[0][1] <= lo:
            heapq.heappop(active)
        if active:
            name = active[0][2]
            out[name] = out.get(name, 0.0) + (hi - lo)
    out["covered"] = sum(out.values())
    return out


# ---------------------------------------------------------------------------
# step clock — per-step breakdown, MFU/goodput meter, heartbeat source
# ---------------------------------------------------------------------------
_STEP_LOCK = threading.Lock()
_STEP = {"count": 0, "last": None, "t0": None, "useful_s": 0.0,
         "stall_s": 0.0, "flops0": 0.0, "compile_at_last": 0.0}

# per-chip bf16 peak FLOP/s by device kind (MXNET_PEAK_FLOPS overrides).
# A kind that is not in the table — the CPU mesh — has no peak: the
# mx_mfu gauge stays unpopulated and peak_flops() raises, so a CPU run
# never reports a v5e utilization.
_PEAK_BY_KIND = (("v6", 918e12), ("trillium", 918e12), ("v5p", 459e12),
                 ("v5", 197e12), ("v4", 275e12), ("v3", 123e12),
                 ("v2", 45e12))
_PEAK = [None]          # cached (refresh() drops it); 0.0 = unknown


def known_peak_flops() -> Optional[float]:
    """MXNET_PEAK_FLOPS when set, else the table's entry for the
    attached device kind, else None."""
    v = _PEAK[0]
    if v is None:
        from .config import get as _cfg
        v = float(_cfg("MXNET_PEAK_FLOPS"))
        if v <= 0:
            import jax
            kind = jax.devices()[0].device_kind.lower()
            v = next((flops for marker, flops in _PEAK_BY_KIND
                      if marker in kind), 0.0)
        _PEAK[0] = v
    return v or None


def peak_flops() -> float:
    """Per-chip peak FLOP/s an MFU divides by. Raises for a device
    kind with no known peak: set MXNET_PEAK_FLOPS to state one."""
    v = known_peak_flops()
    if v is None:
        import jax
        from .base import MXNetError
        raise MXNetError(
            "no peak FLOP/s known for device kind %r: MFU is undefined "
            "here; set MXNET_PEAK_FLOPS to state a peak"
            % jax.devices()[0].device_kind)
    return v


def _executed_flops() -> float:
    m = _METRICS.get(("mx_executed_flops_total", ()))
    return m.get() if m is not None else 0.0


def _compile_seconds() -> float:
    try:
        from . import compilewatch
        return compilewatch.compile_seconds_total()
    except Exception:
        return 0.0


def debit_stall(seconds: float, kind: str = "checkpoint"):
    """Charge a loop stall (checkpoint wait, eval pause, ...) against
    goodput: the time still elapses on the wall clock but is debited
    from the useful-step numerator. Counted into
    ``mx_stall_seconds_total{kind}``. Never raises."""
    try:
        if not enabled() or seconds <= 0:
            return
        with _STEP_LOCK:
            _STEP["stall_s"] += float(seconds)
        counter("mx_stall_seconds_total", kind=kind).inc(seconds)
    except Exception:
        pass


def mark_step(useful: bool = True):
    """Called once per optimizer step (Trainer.step / Module.update /
    ShardedTrainStep.step): counts ``mx_steps_total`` and observes the
    wall time SINCE THE PREVIOUS step into ``mx_step_seconds`` — i.e.
    the full loop including data/forward/backward, not just the update.

    ``useful=False`` marks a step whose update was dropped (a guard
    skip): its interval is debited from goodput. Each mark also
    updates the live meters (ISSUE 6):

    - ``mx_mfu`` — measured model-FLOPs utilization: executed FLOPs
      (``mx_executed_flops_total``, fed by compilewatch's per-program
      cost analysis at execution time — metered, not attributed)
      divided by wall time x :func:`peak_flops`, cumulative over the
      meter window (since the first mark after reset).
    - ``mx_goodput`` — useful-step time over wall time: guard-skipped
      intervals, :func:`debit_stall` charges and compile seconds
      (recompile storms) are debited from the numerator.
    """
    if not enabled():
        return
    now = time.perf_counter()
    flops_now = _executed_flops()
    compile_now = _compile_seconds()
    with _STEP_LOCK:
        last = _STEP["last"]
        _STEP["last"] = now
        prev_count = _STEP["count"]
        _STEP["count"] = prev_count + 1
        if last is None:
            _STEP["t0"] = now
            _STEP["flops0"] = flops_now
            _STEP["compile_at_last"] = compile_now
        else:
            dt = now - last
            compile_dt = max(0.0, compile_now - _STEP["compile_at_last"])
            _STEP["compile_at_last"] = compile_now
            if useful:
                _STEP["useful_s"] += max(0.0, dt - compile_dt)
            t0 = _STEP["t0"]
            wall = now - t0 if t0 is not None else 0.0
            useful_s = max(0.0, _STEP["useful_s"] - _STEP["stall_s"])
            flops0 = _STEP["flops0"]
    counter("mx_steps_total").inc()
    if last is not None:
        histogram("mx_step_seconds").observe(now - last)
        if wall > 0:
            gauge("mx_goodput").set(min(1.0, useful_s / wall))
            peak = known_peak_flops()
            if peak is not None:
                gauge("mx_mfu").set((flops_now - flops0) / wall / peak)
    _close_step(prev_count)
    _maybe_fleet_tick(prev_count + 1)


def count_launch(path: str):
    """One compiled program handed to the runtime on the training path
    -> ``mx_program_launches_total{path=gluon|sharded}``: the whole-graph
    programs (CachedOp's, the fused backward and fused step, the sharded
    step), not the eager per-op ones. :func:`mark_step` closes the
    count into the step log. No-op when telemetry is off."""
    count_event(LAUNCH_COUNTER, path=path)


def _close_step(step: int):
    """Move the open step's spans into the ring, with the launches
    counted since the last close. Runs in :func:`mark_step`, so a span
    still open then lands in the next step's record."""
    log = _STEPLOG
    spans, log.open = log.open, []
    dropped, log.dropped = log.dropped, 0
    counts = {}
    for key, (name, label, values) in _STEP_COUNTERS.items():
        counts[key] = got = {}
        for value in values:
            m = _METRICS.get((name, ((label, value),)))
            if m is not None:
                total = m.get()
                delta = total - log.counted.get((name, value), 0.0)
                log.counted[(name, value)] = total
                if delta:
                    got[value] = delta
    log.closed.append((step, spans, counts, dropped))


def step_log(n: Optional[int] = None) -> List[dict]:
    """The last ``n`` closed steps (all the ring holds when None),
    oldest first. Per step: ``step`` (its index: the steps counted
    before it), ``spans`` {name: {``count``, ``seconds`` (summed
    durations), ``self_seconds`` (``seconds`` minus what the spans
    opened directly inside it cover)}}, ``launches`` {path: programs
    handed to the runtime during the step}, ``fused_steps`` {"1" | "0":
    fused Gluon steps taken with / without donated buffers,
    ``mx_fused_step_total``}, ``events`` (the raw
    ``(name, start, end, parent, step)`` tuples, in order of exit;
    ``time.perf_counter`` seconds) and ``dropped`` (spans the open
    step refused at its cap). The ring holds
    ``_StepLog.STEP_LOG_STEPS`` steps; nothing on the hot path writes
    it out."""
    closed = list(_STEPLOG.closed)
    if n is not None:
        closed = closed[-n:] if n > 0 else []
    out = []
    for step, events, counts, dropped in closed:
        spans: Dict[str, dict] = {}
        for name, start, end, _parent, _step in events:
            row = spans.setdefault(
                name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
            row["count"] += 1
            row["seconds"] += end - start
            row["self_seconds"] += end - start
        for _name, start, end, parent, _step in events:
            if parent in spans:
                spans[parent]["self_seconds"] -= end - start
        out.append({"step": step, "spans": spans,
                    "events": list(events), "dropped": dropped,
                    **{key: dict(got) for key, got in counts.items()}})
    return out


# ---------------------------------------------------------------------------
# device-side scopes — which instruction of a compiled program belongs
# to which ``jax.named_scope`` of the program. The device trace names an
# event by its HLO instruction and nothing else; the compiled program's
# text gives every instruction its ``op_name``, in which a named scope
# is one path element (inside ``jvp(...)``, ``transpose(...)`` and
# ``checkpoint/rematted_computation`` too). The rule, the only one in
# the package: an instruction belongs to the LAST element of its
# ``op_name`` that starts with ``mx.``. No list of scope names exists
# here: a scope an op opens tomorrow shows without an edit.
# (docs/OBSERVABILITY.md "Device-side scopes")
# ---------------------------------------------------------------------------
SCOPE_PREFIX = "mx."

_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PATH_ELEMENT = re.compile(r"[\w.]+")
_QUOTED = re.compile(r'"([^"\n]*)"')


def innermost_scope(op_name: str) -> Optional[str]:
    """The last path element of an instruction's ``op_name`` that
    starts with ``mx.``; None where it names no scope of the program.
    An instruction XLA merged from several ops carries their paths
    joined by ``;``: it goes to the scope of the first path that names
    one, or to a scope nested inside that one (``mx.mamba2.ssd`` inside
    ``mx.mamba2``) where a later path names it."""
    best = None
    for path in op_name.split(";"):
        found = None
        for element in _PATH_ELEMENT.findall(path):
            if element.startswith(SCOPE_PREFIX):
                found = element
        if found is not None and (best is None
                                  or found.startswith(best + ".")):
            best = found
    return best


def hlo_scopes(compiled_text: str
               ) -> Tuple[Optional[str], Dict[str, str], Dict[str, str]]:
    """(the module's name, {instruction name: innermost scope},
    {instruction name: label} of the instructions under no scope) over
    every computation of a compiled program's text
    (``compiled.as_text()``). A label is the last two elements of the
    ``op_name``: what to print beside ``fusion.1797``. A fusion is one
    instruction: it goes to the scope its own metadata names (that of
    its root)."""
    head = _HLO_MODULE.match(compiled_text)
    scopes: Dict[str, str] = {}
    unscoped: Dict[str, str] = {}
    for line in compiled_text.splitlines():
        meta = _HLO_OP_NAME.search(line)
        name = _HLO_INSTRUCTION.match(line) if meta else None
        if name is None:
            continue
        scope = innermost_scope(meta.group(1))
        if scope is not None:
            scopes[name.group(1)] = scope
        else:
            unscoped[name.group(1)] = "/".join(
                meta.group(1).split("/")[-2:])
    return (head.group(1) if head else None), scopes, unscoped


def _lowered_scope_names(lowered_text: str) -> set:
    """The scopes a lowering's text (``as_text(debug_info=True)``)
    names in its locations: what the program opened when it was
    traced, whatever executable was then served for it."""
    return {scope for scope in map(innermost_scope,
                                   _QUOTED.findall(lowered_text))
            if scope is not None}


class DeviceProgram:
    """One compiled program of a training step, as the step publishes
    it: a ``label`` (the step function's name), ``launched`` (the wall
    time at which it last became the program that launches, None before
    its first launch) and, on request, its instruction -> scope table.

    It holds the ``(lowered, compiled)`` pair of ``jax.stages`` objects
    the step ran (the executable is the one that launches, not a second
    compile of it) until the first :meth:`table`, and only the table
    after it. Nothing is parsed before that: the text of a large step
    is tens of MB."""

    __slots__ = ("label", "launched", "_stages", "_table", "_ref",
                 "__weakref__")

    def __init__(self, label: str, lowered, compiled):
        self.label = label
        self.launched: Optional[float] = None
        self._stages: Optional[tuple] = (lowered, compiled)
        self._table: Optional[dict] = None
        self._ref = weakref.ref(self)
        with _DEVICE_LOCK:
            _DEVICE_PROGRAMS.add(self)

    def note_launch(self):
        """On the step path, no lock. The clock is read and the
        process-wide "launched last" set only when another program
        launched in between; a launch under a ``jax.profiler`` trace
        pins the program (see ``_TRACED``), the next one outside a
        trace lets the pin go."""
        if _LAST_LAUNCHED[0] is not self._ref:
            self.launched = time.time()
            _LAST_LAUNCHED[0] = self._ref
        if _tracing():
            _TRACED[0] = self
        elif _TRACED[0] is not None:
            _TRACED[0] = None

    def table(self) -> dict:
        """``{"program", "module", "launched", "scopes", "unscoped",
        "missing", "stale"}``: ``module`` is the name a trace's "XLA
        Modules" event starts with, ``scopes`` is {HLO instruction
        name: innermost ``mx.*`` scope}, ``unscoped`` is {instruction
        name: the end of its ``op_name``} for the rest. ``missing``:
        the scopes the lowering names and the executable does not (the
        compiler may have removed a scope's only instructions; a sum
        over such a scope is not 0, it is unknown). ``stale``: the
        executable names *none* of the lowering's scopes. It then came
        from a compile cache written by a tree with other scopes
        (JAX's cache key leaves debug info out), and every sum over
        ``scopes`` would read 0 where work ran."""
        with _TABLE_LOCK:
            if self._table is None:
                (lowered, compiled), self._stages = self._stages, None
                module, scopes, unscoped = hlo_scopes(compiled.as_text())
                opened = _lowered_scope_names(
                    lowered.as_text(debug_info=True))
                missing = opened - set(scopes.values())
                self._table = {
                    "program": self.label, "module": module,
                    "scopes": scopes, "unscoped": unscoped,
                    "missing": sorted(missing),
                    "stale": bool(opened) and missing == opened}
        return dict(self._table, launched=self.launched)


def _tracing() -> bool:
    """Whether a ``jax.profiler`` trace is being recorded in this
    process (``start_trace`` / ``trace``, ``profiler.set_state("run")``
    among them)."""
    state = getattr(_jax_profiler, "_profile_state", None)
    return getattr(state, "profile_session", None) is not None


_DEVICE_LOCK = threading.Lock()
_TABLE_LOCK = threading.Lock()
_DEVICE_PROGRAMS: "weakref.WeakSet[DeviceProgram]" = weakref.WeakSet()
# a weak reference to the program that launched last: what a launch
# compares itself with, so that a loop over one program stores nothing
_LAST_LAUNCHED: List[Optional["weakref.ref[DeviceProgram]"]] = [None]
# The one strong reference, and only under a trace: the program whose
# launch a ``jax.profiler`` trace last recorded outlives its step, so
# that whoever reduces the trace after the training loop has returned
# (a benchmark's reader, an operator's script at the end of a job)
# still finds the table of what ran. Its executable rides along until
# the table is built; the pin goes at the next launch outside a trace
# and at ``telemetry.reset()``.
_TRACED: List[Optional[DeviceProgram]] = [None]


def device_scope_tables() -> List[DeviceProgram]:
    """Every live step's launched programs, and the one a trace last
    recorded even where its step is gone: most recent launch first. The
    entries are cheap (``label``, ``launched``); ``entry.table()``
    builds one program's table. A reader with a ``jax.profiler`` trace
    and no handle on the step takes ``device_scope_tables()[0]``."""
    with _DEVICE_LOCK:
        found = set(_DEVICE_PROGRAMS)
    if _TRACED[0] is not None:
        found.add(_TRACED[0])
    return sorted((p for p in found if p.launched is not None),
                  key=lambda p: -p.launched)


def _maybe_fleet_tick(step_count: int):
    """MXNET_FLEET_SNAPSHOT_PERIOD: every N steps, publish + merge the
    cross-rank fleet view. Step-count driven (not wall-clock) so every
    rank of a synchronous job reaches the collective on the same step.
    Failures never poison the step."""
    try:
        from .config import get as _cfg
        period = int(_cfg("MXNET_FLEET_SNAPSHOT_PERIOD"))
        if period <= 0 or step_count % period:
            return
        fleet_snapshot()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# fleet layer (ISSUE 6) — cross-rank aggregation with straggler
# attribution. Each rank packs its compact stats into a fixed float
# vector; the vectors ride ONE collective gather over the dist group
# (dist.allgather_floats, under the kvstore comm deadline), and every
# rank merges the same fleet view SPMD-style: per-rank step/comm time,
# per-step skew, the slowest rank and whether comm or compute makes it
# slow. MXNET_STRAGGLER_WARN turns the merged skew into a warning that
# NAMES the offending rank — the evidence line a 256-chip scaling run
# gets diagnosed from.
# ---------------------------------------------------------------------------
FLEET_FIELDS = ("steps", "step_mean", "step_p50", "step_p99",
                "comm_seconds", "exposed_comm_seconds", "comm_bytes",
                "guard_events", "recompiles", "mfu", "goodput",
                "grad_noise_scale", "anomalies")

_FLEET_LOCK = threading.Lock()
_FLEET = {"last": None}


def local_fleet_stats() -> dict:
    """This rank's compact stats vector (the per-rank row of the fleet
    view), read from the live registry."""
    st = _METRICS.get(("mx_step_seconds", ()))
    with _STEP_LOCK:
        steps = _STEP["count"]
    out = {k: 0.0 for k in FLEET_FIELDS}
    out["steps"] = float(steps)
    if st is not None and st.count:
        out["step_mean"] = st.sum / st.count
        out["step_p50"] = st.percentile(50)
        out["step_p99"] = st.percentile(99)
    try:
        from . import commwatch
        tot = commwatch.comm_totals()
        out["comm_seconds"] = tot["seconds"]
        out["exposed_comm_seconds"] = tot["exposed_seconds"]
        out["comm_bytes"] = tot["bytes"]
    except Exception:
        pass
    with _REG_LOCK:
        for m in _METRICS.values():
            if m.name == "mx_guard_events_total":
                out["guard_events"] += m.get()
            elif m.name == "mx_recompiles_total":
                out["recompiles"] += m.get()
            elif m.name == "mx_modelwatch_anomalies_total":
                out["anomalies"] += m.get()
    mfu = _METRICS.get(("mx_mfu", ()))
    gp = _METRICS.get(("mx_goodput", ()))
    noise = _METRICS.get(("mx_grad_noise_scale", ()))
    out["mfu"] = mfu.get() if mfu else 0.0
    out["goodput"] = gp.get() if gp else 0.0
    out["grad_noise_scale"] = noise.get() if noise else 0.0
    return out


def _attribute_phase(ranks: list, slowest: int) -> str:
    """Why is the slowest rank slow: 'comm' when its exposed-comm share
    of step time clearly exceeds the fleet median share (the DCN-bound
    sync signature), else 'compute' (data/kernel-bound)."""
    def share(r):
        busy = r["steps"] * r["step_mean"]
        return r["exposed_comm_seconds"] / busy if busy > 0 else 0.0

    shares = sorted(share(r) for r in ranks)
    med = shares[(len(shares) - 1) // 2]    # lower median, as for skew
    s = share(ranks[slowest])
    return "comm" if s > max(0.02, 1.5 * med) else "compute"


def fleet_snapshot(timeout: Optional[float] = None) -> dict:
    """Publish this rank's stats and merge the fleet view (COLLECTIVE
    on multi-process jobs: every rank must call it together — step-
    driven via MXNET_FLEET_SNAPSHOT_PERIOD, or explicitly from SPMD
    code/tools). Single-process: a 1-rank view, same schema.

    Returns {"nw", "rank", "ranks": [per-rank stat dicts],
    "slowest", "skew", "phase", "step_mean_median"} and exports
    mx_fleet_ranks / mx_fleet_step_skew / mx_fleet_slowest_rank
    gauges. MXNET_STRAGGLER_WARN > 0: a skew beyond the threshold
    warns naming the slowest rank + phase and counts
    mx_straggler_events_total{rank,phase}."""
    if not enabled():
        return {}
    from . import dist as dist_mod
    local = local_fleet_stats()
    vec = [local[k] for k in FLEET_FIELDS]
    mat = dist_mod.allgather_floats(vec, tag="fleet-snapshot",
                                    timeout=timeout)
    ranks = [dict(zip(FLEET_FIELDS, (float(v) for v in row)))
             for row in mat]
    means = [r["step_mean"] for r in ranks]
    slowest = max(range(len(means)), key=lambda i: means[i])
    # LOWER median: with an even rank count the upper median IS the
    # straggler's bucket (2 ranks: upper median = the slowest itself,
    # which would read every skew as zero)
    med = sorted(means)[(len(means) - 1) // 2]
    skew = (means[slowest] - med) / med if med > 0 else 0.0
    phase_name = _attribute_phase(ranks, slowest)
    view = {"nw": len(ranks), "rank": dist_mod.rank(), "ranks": ranks,
            "slowest": slowest, "skew": skew, "phase": phase_name,
            "step_mean_median": med}
    gauge("mx_fleet_ranks").set(len(ranks))
    gauge("mx_fleet_step_skew").set(skew)
    gauge("mx_fleet_slowest_rank").set(slowest)
    with _FLEET_LOCK:
        _FLEET["last"] = view
    try:
        from .config import get as _cfg
        thr = float(_cfg("MXNET_STRAGGLER_WARN"))
    except Exception:
        thr = 0.0
    if thr > 0 and skew > thr and len(ranks) > 1:
        counter("mx_straggler_events_total", rank=str(slowest),
                phase=phase_name).inc()
        _LOG.warning(
            "straggler: rank %d runs %.1f%% slower than the fleet "
            "median (%.1fms vs %.1fms per step over %d steps) — %s-"
            "bound (exposed comm %.1fms/step vs median %.1fms; "
            "MXNET_STRAGGLER_WARN=%g)",
            slowest, skew * 100, means[slowest] * 1e3, med * 1e3,
            int(ranks[slowest]["steps"]), phase_name,
            (ranks[slowest]["exposed_comm_seconds"]
             / max(1.0, ranks[slowest]["steps"])) * 1e3,
            sorted((r["exposed_comm_seconds"] / max(1.0, r["steps"]))
                   for r in ranks)[len(ranks) // 2] * 1e3, thr)
    return view


def fleet_last() -> Optional[dict]:
    """The most recently merged fleet view (None before the first
    fleet_snapshot)."""
    with _FLEET_LOCK:
        return _FLEET["last"]


# ---------------------------------------------------------------------------
# event hooks — guardrails / faultinject / checkpoints call these
# directly (fire-and-forget events become named counters)
# ---------------------------------------------------------------------------
def count_event(name: str, /, **labels):
    """Never-raising counter increment — the primitive for event hooks
    on failure-handling paths, where a telemetry error must not mask
    the real one. No-op when telemetry is off."""
    try:
        if enabled():
            counter(name, **labels).inc()
    except Exception:
        pass


def guard_event(kind: str):
    """One guard event (skip/zero/clip/nonfinite/loss_spike/
    engine_error/watchdog) -> mx_guard_events_total{kind=...}."""
    count_event("mx_guard_events_total", kind=kind)


def fault_event(site: str):
    """One faultinject fire -> mx_fault_injections_total{site=...}."""
    count_event("mx_fault_injections_total", site=site)


def zero_shard_state(ctx_key: str, shard_bytes: float, fragments: int,
                     replicated_bytes: float):
    """Shard-state gauges for the ZeRO weight-update engine
    (gluon/zero.py; docs/ZERO.md): per-replica sharded optimizer-state
    footprint vs what the replicated path would hold on the same
    device. ``mx_zero_state_bytes{ctx}`` is the 1/N shard this replica
    actually allocates, ``mx_zero_state_fragments{ctx}`` the parameter
    fragments it owns, and ``mx_zero_state_saved_bytes{ctx}`` the HBM
    the sharding reclaimed there (replicated − shard). Never raises."""
    try:
        if not enabled():
            return
        gauge("mx_zero_state_bytes", ctx=ctx_key).set(shard_bytes)
        gauge("mx_zero_state_fragments", ctx=ctx_key).set(fragments)
        gauge("mx_zero_state_saved_bytes", ctx=ctx_key).set(
            max(0.0, replicated_bytes - shard_bytes))
    except Exception:
        pass


def checkpoint_event(ok: bool):
    """One checkpoint write outcome -> mx_checkpoint_writes_total /
    mx_checkpoint_errors_total. The failure branch runs before the
    real write error re-raises, and the success branch runs between
    the atomic publish and the manifest update — count_event's
    no-raise contract keeps both safe."""
    count_event("mx_checkpoint_writes_total" if ok
                else "mx_checkpoint_errors_total")


# ---------------------------------------------------------------------------
# live-NDArray memory accounting (ISSUE 4) — fed by NDArray._mem_track
# while the gate is on. The authoritative totals live here (surviving
# reset()'s registry wipe) and are MIRRORED into the
# mx_ndarray_live_bytes{ctx} / mx_ndarray_live_count{ctx} gauges.
# ---------------------------------------------------------------------------
_MEM_LOCK = threading.Lock()
_LIVE_ND: Dict[str, list] = {}      # ctx key -> [bytes, count]


def _mirror_nd(key: str, nbytes: float, count: float):
    try:
        if _STATE.on:
            gauge("mx_ndarray_live_bytes", ctx=key).set(nbytes)
            gauge("mx_ndarray_live_count", ctx=key).set(count)
            return
        # gate off (e.g. a finalizer firing after telemetry.reset()):
        # update existing gauges only — a free must never re-register
        # phantom instruments into a cleaned registry
        lab = (("ctx", key),)
        m = _METRICS.get(("mx_ndarray_live_bytes", lab))
        if m is not None:
            m.set(nbytes)
        m = _METRICS.get(("mx_ndarray_live_count", lab))
        if m is not None:
            m.set(count)
    except Exception:
        pass


def _ndarray_alloc(key: str, nbytes: int):
    # the mirror runs INSIDE _MEM_LOCK so concurrently computed
    # (bytes, count) pairs cannot reach the gauges out of order and
    # leave them stale (lock order _MEM_LOCK -> _REG_LOCK/metric
    # locks; nothing takes them in reverse)
    with _MEM_LOCK:
        rec = _LIVE_ND.setdefault(key, [0, 0])
        rec[0] += nbytes
        rec[1] += 1
        _mirror_nd(key, rec[0], rec[1])


def _ndarray_resize(key: str, delta: int):
    with _MEM_LOCK:
        rec = _LIVE_ND.setdefault(key, [0, 0])
        rec[0] += delta
        _mirror_nd(key, rec[0], rec[1])


def _ndarray_free_box(box):
    """weakref.finalize target — box is [ctx_key, nbytes], mutated in
    place if the array was resized after tracking began, and voided
    (key=None) if the array was untracked as a buffer alias."""
    key, nbytes = box
    if key is None:
        return
    with _MEM_LOCK:
        rec = _LIVE_ND.setdefault(key, [0, 0])
        rec[0] -= nbytes
        rec[1] -= 1
        _mirror_nd(key, rec[0], rec[1])


def ndarray_live(ctx_key: Optional[str] = None) -> dict:
    """Live tracked-NDArray footprint: ``{"bytes", "count"}`` for one
    context key (e.g. ``"tpu(0)"``), or ``{key: {...}}`` for all.
    Tracks arrays created while MXNET_TELEMETRY was on."""
    with _MEM_LOCK:
        if ctx_key is not None:
            b, c = _LIVE_ND.get(ctx_key, (0, 0))
            return {"bytes": b, "count": c}
        return {k: {"bytes": v[0], "count": v[1]}
                for k, v in _LIVE_ND.items()}


def _jit_cache_info() -> dict:
    """Sizes of every jit-program cache in the process (ISSUE 4
    satellite: the caches are unbounded — make that visible)."""
    info: Dict[str, object] = {}
    try:
        from . import compilewatch
        fns, progs = compilewatch.cache_counts()
        info["watched_fns"] = fns
        info["watched_programs"] = progs
    except Exception:
        pass
    try:
        from .ops import jit_cache_info as _ops_info
        info["op_entries"] = _ops_info()["entries"]
    except Exception:
        pass
    try:
        from .ndarray.ndarray import _jitted_with_none_slots
        ci = _jitted_with_none_slots.cache_info()
        info["none_slots"] = {"hits": ci.hits, "misses": ci.misses,
                              "entries": ci.currsize}
    except Exception:
        pass
    return info


def memory_snapshot() -> dict:
    """One structured memory picture for leak hunts: per-context live
    NDArray bytes/counts, jit-cache sizes, and the planned-HBM totals
    XLA reported for every compiled program (``mx_hbm_bytes{kind}`` —
    CUMULATIVE over all programs ever compiled, so a growing
    ``hbm_planned`` diff means *the compiler built more programs*
    (check jit_cache / recompiles), while a growing ``ndarray`` diff
    means live buffers leaked). Pair two snapshots with
    :func:`memory_diff`."""
    hbm = {}
    with _REG_LOCK:
        for m in _METRICS.values():
            if m.name == "mx_hbm_bytes":
                kind = dict(m.labels).get("kind", "?")
                hbm[kind] = m.get()
    return {"ndarray": ndarray_live(), "jit_cache": _jit_cache_info(),
            "hbm_planned": hbm}


def memory_diff(before: dict, after: Optional[dict] = None) -> dict:
    """Delta between two :func:`memory_snapshot` dicts (after − before;
    ``after=None`` snapshots now). Only non-zero entries survive — the
    leak-hunt workflow is snapshot / run the suspect loop / diff."""
    after = memory_snapshot() if after is None else after

    def _num_diff(b, a):
        out = {}
        for k in set(b) | set(a):
            bv, av = b.get(k, 0), a.get(k, 0)
            if isinstance(bv, dict) or isinstance(av, dict):
                sub = _num_diff(bv or {}, av or {})
                if sub:
                    out[k] = sub
            else:
                d = av - bv
                if d:
                    out[k] = d
        return out

    return _num_diff(before, after)


# ---------------------------------------------------------------------------
# exposure
# ---------------------------------------------------------------------------
def _escape(value: str) -> str:
    """Label-value escaping per the Prometheus exposition format —
    kvstore keys are arbitrary user strings; one bad quote must not
    invalidate the whole scrape."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(name: str, labels) -> str:
    if not labels:
        return name
    return "%s{%s}" % (name, ",".join('%s="%s"' % (k, _escape(v))
                                      for k, v in labels))


_KEY_RE = None


def parse_metric_key(key: str) -> Tuple[str, Dict[str, str]]:
    """Inverse of the ``name{label="v",...}`` snapshot-key format
    (:func:`_fmt`): returns ``(name, {label: value})`` with the
    escaping undone. The ONE parser for consumers that aggregate
    snapshot() keys (serve tenancy/bench) — hand-rolled splits drift
    the moment the serializer changes."""
    import re as _re
    global _KEY_RE
    if _KEY_RE is None:
        _KEY_RE = (_re.compile(r"([^{]+)\{(.*)\}$"),
                   _re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"'))
    m = _KEY_RE[0].match(key)
    if not m:
        return key, {}
    labels = {}
    for k, v in _KEY_RE[1].findall(m.group(2)):
        labels[k] = (v.replace("\\n", "\n").replace('\\"', '"')
                     .replace("\\\\", "\\"))
    return m.group(1), labels


def snapshot() -> dict:
    """Everything the registry holds, as one plain dict (schema
    asserted by tests/test_telemetry.py):

    ``{"enabled": bool, "steps": int, "counters": {key: float},
    "gauges": {key: float}, "histograms": {key: {count,sum,min,max,
    p50,p90,p99}}, "jit_cache": {...}}`` where key is
    ``name{label="v",...}`` and jit_cache carries the sizes of every
    jit-program cache (ISSUE 4 — see :func:`_jit_cache_info`)."""
    with _REG_LOCK:
        metrics = list(_METRICS.values())
    out = {"enabled": enabled(), "steps": _STEP["count"],
           "counters": {}, "gauges": {}, "histograms": {},
           "jit_cache": _jit_cache_info()}
    for m in metrics:
        key = _fmt(m.name, m.labels)
        if m.kind == "counter":
            out["counters"][key] = m.get()
        elif m.kind == "gauge":
            out["gauges"][key] = m.get()
        else:
            out["histograms"][key] = m.summary()
    return out


def render_prometheus() -> str:
    """Prometheus text exposition (text/plain; version 0.0.4) of every
    registered instrument — counters and gauges as single samples,
    histograms as cumulative ``_bucket{le=}`` series + ``_sum`` /
    ``_count``."""
    with _REG_LOCK:
        metrics = sorted(_METRICS.values(),
                         key=lambda m: (m.name, m.labels))
    lines = []
    typed = set()
    for m in metrics:
        if m.name not in typed:
            typed.add(m.name)
            lines.append("# TYPE %s %s" % (m.name, m.kind))
        if m.kind in ("counter", "gauge"):
            lines.append("%s %.17g" % (_fmt(m.name, m.labels), m.get()))
            continue
        with m._lock:
            counts = list(m.counts)
            count, total = m.count, m.sum
        cum = 0
        for bound, c in zip(BUCKETS, counts):
            cum += c
            lines.append('%s %d' % (
                _fmt(m.name + "_bucket",
                     m.labels + (("le", "%.6g" % bound),)), cum))
        lines.append('%s %d' % (
            _fmt(m.name + "_bucket", m.labels + (("le", "+Inf"),)),
            count))
        lines.append("%s %.17g" % (_fmt(m.name + "_sum", m.labels),
                                   total))
        lines.append("%s %d" % (_fmt(m.name + "_count", m.labels),
                                count))
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# heartbeat — the periodic flight-recorder line
# ---------------------------------------------------------------------------
_HB_LOCK = threading.Lock()
_HB = {"thread": None, "stop": None, "last_steps": 0, "last_t": None}


def heartbeat_line() -> str:
    """One flight-recorder line: step count, step rate since the last
    heartbeat, p50/p99 step time, pending engine ops, guard-event and
    checkpoint-error totals, the live MFU/goodput meters, and — once a
    fleet view has merged — a fleet section (ranks, per-step skew,
    slowest rank and its phase)."""
    now = time.perf_counter()
    with _STEP_LOCK:
        steps = _STEP["count"]
    with _HB_LOCK:
        last_steps, last_t = _HB["last_steps"], _HB["last_t"]
        _HB["last_steps"], _HB["last_t"] = steps, now
    rate = 0.0
    if last_t is not None and now > last_t:
        rate = (steps - last_steps) / (now - last_t)
    # read-only lookups: an on-demand heartbeat with telemetry off must
    # not register phantom zero-valued instruments as a side effect
    st = _METRICS.get(("mx_step_seconds", ()))
    pend = _METRICS.get(("mx_engine_pending_ops", ()))
    with _REG_LOCK:
        guard_total = sum(m.get() for m in _METRICS.values()
                          if m.name == "mx_guard_events_total")
        ckpt_err = sum(m.get() for m in _METRICS.values()
                       if m.name == "mx_checkpoint_errors_total")
        compiles = sum(m.get() for m in _METRICS.values()
                       if m.name == "mx_compile_total")
        recompiles = sum(m.get() for m in _METRICS.values()
                         if m.name == "mx_recompiles_total")
    # jit-cache size: read-only introspection (no instrument side
    # effects), same contract as the _METRICS.get lookups above
    jit_entries = _jit_cache_info().get("watched_programs", 0)
    mfu = _METRICS.get(("mx_mfu", ()))
    gp = _METRICS.get(("mx_goodput", ()))
    line = ("mx-heartbeat steps=%d rate=%.2f/s step_p50=%.1fms "
            "step_p99=%.1fms pending_engine_ops=%d guard_events=%d "
            "ckpt_errors=%d jit_cache=%d compiles=%d recompiles=%d "
            "mfu=%.1f%% goodput=%.1f%%"
            % (steps, rate,
               st.percentile(50) * 1e3 if st else 0.0,
               st.percentile(99) * 1e3 if st else 0.0,
               int(pend.get()) if pend else 0, int(guard_total),
               int(ckpt_err), int(jit_entries), int(compiles),
               int(recompiles),
               (mfu.get() if mfu else 0.0) * 100,
               (gp.get() if gp else 0.0) * 100))
    # training-dynamics section (modelwatch.py) — read-only lookups,
    # same no-phantom-instrument contract as above
    noise = _METRICS.get(("mx_grad_noise_scale", ()))
    with _REG_LOCK:
        anomalies = sum(m.get() for m in _METRICS.values()
                        if m.name == "mx_modelwatch_anomalies_total")
    if noise is not None and noise.get() > 0:
        line += (" noise_scale=%.4g suggest_batch=%d"
                 % (noise.get(), max(1, int(round(noise.get())))))
    if anomalies:
        line += " layer_anomalies=%d" % int(anomalies)
    fleet = fleet_last()
    if fleet:
        line += (" fleet=nw:%d,skew:%.1f%%,slowest:r%d,phase:%s"
                 % (fleet["nw"], fleet["skew"] * 100, fleet["slowest"],
                    fleet["phase"]))
    # serving section (ISSUE 12, mxnet_tpu/serve): request totals by
    # outcome, live queue depth, worst per-tenant p99, bucket misses —
    # read-only lookups, present only once the process actually serves
    serve_reqs = serve_shed = qdepth = 0.0
    serve_p99 = 0.0
    bucket_miss = 0.0
    with _REG_LOCK:
        for m in _METRICS.values():
            if m.name == "mx_serve_requests_total":
                serve_reqs += m.get()
                if dict(m.labels).get("code") in ("overload", "timeout",
                                                  "drain"):
                    serve_shed += m.get()
            elif m.name == "mx_serve_queue_depth":
                qdepth += m.get()
            elif m.name == "mx_serve_bucket_miss_total":
                bucket_miss += m.get()
            elif m.name == "mx_serve_latency_seconds":
                serve_p99 = max(serve_p99, m.percentile(99))
    if serve_reqs:
        line += (" serve=reqs:%d,shed:%d,qdepth:%d,p99:%.1fms,"
                 "bucket_miss:%d"
                 % (int(serve_reqs), int(serve_shed), int(qdepth),
                    serve_p99 * 1e3, int(bucket_miss)))
    # distributed-tracing section (ISSUE 18): sampled/recorded traces,
    # slow-request exemplars held, and DROPPED spans (ring overflow is
    # counted, never silent) — read-only, present only with activity
    try:
        from . import tracing
        ts = tracing.stats()
        if ts["sampled"] or ts["recorded"] or ts["dropped"]:
            line += (" trace=sampled:%d,spans:%d,dropped:%d,"
                     "exemplars:%d"
                     % (ts["sampled"], ts["recorded"], ts["dropped"],
                        ts["exemplars"]))
    except Exception:
        pass
    # performance-trajectory section (ISSUE 19, perfwatch.py): records
    # ingested into the MXNET_PERF_DB store and confirmed regressions
    # from the last scan — read-only, present only with activity
    with _REG_LOCK:
        perf_ing = sum(m.get() for m in _METRICS.values()
                       if m.name == "mx_perf_ingested_total")
        perf_reg = sum(m.get() for m in _METRICS.values()
                       if m.name == "mx_perf_regressions_total")
    if perf_ing or perf_reg:
        line += (" perf=ingested:%d,regressions:%d"
                 % (int(perf_ing), int(perf_reg)))
    return line


def _heartbeat_loop(stop: threading.Event, period: float):
    while not stop.wait(period):
        try:
            if _STATE.on:          # silent while the registry is off
                _LOG.info(heartbeat_line())
        except Exception:          # the flight recorder must never
            pass                   # take down the run it observes


def _maybe_start_heartbeat():
    if _HB["thread"] is not None:
        return
    try:
        from .config import get as _cfg
        period = float(_cfg("MXNET_TELEMETRY_HEARTBEAT"))
    except Exception:
        return
    if period <= 0:
        return
    with _HB_LOCK:
        if _HB["thread"] is not None:
            return
        stop = threading.Event()
        t = threading.Thread(target=_heartbeat_loop, args=(stop, period),
                             daemon=True, name="mx-telemetry-heartbeat")
        _HB["thread"], _HB["stop"] = t, stop
        _HB["last_steps"], _HB["last_t"] = (_STEP["count"],
                                            time.perf_counter())
        t.start()


def _stop_heartbeat():
    with _HB_LOCK:
        t, stop = _HB["thread"], _HB["stop"]
        _HB["thread"] = _HB["stop"] = None
    if stop is not None:
        stop.set()
    if t is not None:
        t.join(timeout=1.0)


# ---------------------------------------------------------------------------
# crash postmortem bundle (ISSUE 11) — when a run dies for a reason the
# guard/engine layers can name (GradGuard raise, engine poison,
# watchdog), every diagnostic surface this stack maintains is dumped
# into ONE directory so the crash ships its own diagnosis: the last K
# sampled modelwatch vectors + heartbeat lines (the flight recorder),
# the telemetry snapshot, the chrome trace, the compilewatch program
# table, and the environment. Published atomically (files land in a
# tmp dir renamed into place — the profiler.dump pattern lifted to a
# directory), so a log collector never reads a partial bundle.
# ---------------------------------------------------------------------------
import json as _json
import os as _os

_BUNDLE_LOCK = threading.Lock()
_BUNDLE = {"installed": False, "written": 0, "recent": None}
_BUNDLE_CAP = 4          # per-process: an engine poison cascade must
#                          not flood the disk with identical bundles
_BUNDLE_TRIGGERS = {"engine_error", "watchdog"}


def _bundle_dir() -> str:
    try:
        from .config import get as _cfg
        return _cfg("MXNET_CRASH_BUNDLE_DIR") or ""
    except Exception:
        return ""


def _crash_listener(event: dict):
    """guardrails.on_event subscriber: records recent guard events and
    triggers a bundle on the fatal kinds — a GradGuard 'nonfinite'
    under the raise policy (the MXNetError is about to propagate), an
    engine op poisoning its outputs, or a watchdog firing. Never
    raises (it runs on failure paths)."""
    try:
        rec = _BUNDLE["recent"]
        if rec is not None:
            compact = {k: v for k, v in event.items()
                       if isinstance(v, (str, int, float, bool, list,
                                         tuple, type(None)))}
            rec.append(compact)
        kind = event.get("kind")
        if kind in _BUNDLE_TRIGGERS:
            crash_bundle(reason=kind, trigger=event)
        elif kind == "nonfinite" and event.get("policy") == "raise":
            crash_bundle(reason="guard_raise", trigger=event)
    except Exception:
        pass


def install_crash_bundler():
    """Subscribe the crash-bundle trigger to the guard event stream
    (idempotent; wired from mxnet_tpu/__init__). The listener is a
    no-op until MXNET_CRASH_BUNDLE_DIR is set — checked live at fire
    time, so arming postmortems needs no restart."""
    with _BUNDLE_LOCK:
        if _BUNDLE["installed"]:
            return
        _BUNDLE["installed"] = True
        import collections as _collections
        _BUNDLE["recent"] = _collections.deque(maxlen=64)
    from . import guardrails
    guardrails.on_event(_crash_listener)


def crash_bundle(reason: str = "manual", trigger: Optional[dict] = None,
                 dirpath: Optional[str] = None) -> Optional[str]:
    """Write one postmortem bundle; returns its path, or None when
    disabled (no MXNET_CRASH_BUNDLE_DIR and no explicit `dirpath`),
    capped or failed. Contents:

    - ``modelwatch.jsonl`` — the last K sampled training-dynamics
      vectors (one JSON object per line, oldest first)
    - ``anomaly.json`` — the trigger event, modelwatch's suspect-layer
      shortlist (the record that NAMES the offending layer) and the
      recent guard-event tail
    - ``telemetry.json`` — the full metrics snapshot
    - ``trace.json`` — the chrome trace (whatever the profiler holds)
    - ``programs.json`` — compilewatch's per-program table
    - ``traces.json`` — distributed-tracing stats + the slow-request
      exemplar traces every live TraceStore holds (ISSUE 18)
    - ``heartbeat.txt`` — the ring's heartbeat lines + one final line
    - ``env.txt`` — MXNET_*/DMLC_*/JAX*/XLA* environment

    The directory is staged under a dot-tmp name and os.replace'd into
    place — the atomic tmp+rename pattern of profiler.dump. Never
    raises."""
    tmp = None
    try:
        root = dirpath or _bundle_dir()
        if not root:
            return None
        with _BUNDLE_LOCK:
            if _BUNDLE["written"] >= _BUNDLE_CAP:
                return None
            _BUNDLE["written"] += 1
            seq = _BUNDLE["written"]
        safe = "".join(c if c.isalnum() or c in "-_" else "-"
                       for c in str(reason))[:40]
        name = "crash-%s-p%d-%d-%s" % (
            time.strftime("%Y%m%d-%H%M%S"), _os.getpid(), seq, safe)
        final = _os.path.join(root, name)
        tmp = _os.path.join(root, ".tmp-" + name)
        _os.makedirs(tmp, exist_ok=True)

        from . import modelwatch as _mw
        ring = _mw.ring()
        with open(_os.path.join(tmp, "modelwatch.jsonl"), "w") as f:
            for entry in ring:
                e = dict(entry)
                e.pop("heartbeat", None)
                f.write(_json.dumps(e, default=str) + "\n")

        recent = list(_BUNDLE["recent"] or [])
        compact_trigger = None
        if trigger is not None:
            compact_trigger = {
                k: v for k, v in trigger.items()
                if isinstance(v, (str, int, float, bool, list, tuple,
                                  type(None)))}
        anomaly = {"reason": reason, "trigger": compact_trigger,
                   "suspects": _mw.suspects(),
                   "recent_guard_events": recent}
        # the trigger's own attribution (GradGuard names the offending
        # parameters in the 'nonfinite' event) leads the suspect list
        if compact_trigger and compact_trigger.get("params"):
            anomaly["suspects"] = (
                [{"param": p, "kind": "nonfinite",
                  "step": compact_trigger.get("step")}
                 for p in compact_trigger["params"]]
                + [s for s in anomaly["suspects"]
                   if s.get("param") not in
                   set(compact_trigger["params"])])
        with open(_os.path.join(tmp, "anomaly.json"), "w") as f:
            _json.dump(anomaly, f, indent=1, default=str)

        with open(_os.path.join(tmp, "telemetry.json"), "w") as f:
            _json.dump(snapshot(), f, indent=1, default=str)

        from . import profiler as _prof
        with open(_os.path.join(tmp, "trace.json"), "w") as f:
            f.write(_prof.dumps())

        try:
            from . import compilewatch as _cw
            progs = {"report": _cw.report(), "programs": _cw.programs()}
        except Exception:
            progs = {"report": [], "programs": []}
        with open(_os.path.join(tmp, "programs.json"), "w") as f:
            _json.dump(progs, f, indent=1, default=str)

        # slow-request exemplars from every live TraceStore (ISSUE 18):
        # the N worst assembled distributed traces with full span
        # detail — the cross-process complement to trace.json
        try:
            from . import tracing as _trc
            traces = {"stats": _trc.stats(),
                      "exemplars": _trc.exemplar_dump()}
        except Exception:
            traces = {"stats": {}, "exemplars": []}
        with open(_os.path.join(tmp, "traces.json"), "w") as f:
            _json.dump(traces, f, indent=1, default=str)

        with open(_os.path.join(tmp, "heartbeat.txt"), "w") as f:
            for entry in ring:
                hb = entry.get("heartbeat")
                if hb:
                    f.write(hb + "\n")
            f.write(heartbeat_line() + "\n")

        from .config import environ_snapshot
        with open(_os.path.join(tmp, "env.txt"), "w") as f:
            for k, v in environ_snapshot(
                    ("MXNET_", "DMLC_", "JAX", "XLA", "TPU_")).items():
                f.write("%s=%s\n" % (k, v))

        _os.replace(tmp, final)      # atomic publish
        count_event("mx_crash_bundles_total", reason=safe)
        _LOG.warning("crash bundle written: %s (reason=%s)", final,
                     reason)
        return final
    except Exception:
        if tmp is not None:
            try:
                import shutil
                shutil.rmtree(tmp, ignore_errors=True)
            except Exception:
                pass
        # refund the budget slot: a transiently unwritable directory
        # (full disk, permissions) must not eat the cap and silence a
        # LATER real crash's bundle
        try:
            with _BUNDLE_LOCK:
                if _BUNDLE["written"] > 0:
                    _BUNDLE["written"] -= 1
        except Exception:
            pass
        return None
