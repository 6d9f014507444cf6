"""Comm-watch — observability for every collective the stack issues.

PR 3 gave the rebuild process-local eyes and PR 4 watched the
compiler; this module (ISSUE 6) watches the WIRES. Every remaining
ROADMAP headline is a distributed claim — >=90% scaling efficiency for
quantized collectives (EQuARX, arxiv 2506.17615), the DCN-staged
hierarchical allreduce, the 55% MFU bar — and none of them can be
judged without per-collective byte/bandwidth evidence. This is the
NCCL-tests accounting (algbw/busbw per op) rebuilt for the XLA world,
where collectives come from three very different places:

1. **Eager kvstore reduces** (`KVStore('local'/'device'/'tpu')` and the
   dist stores): real Python-level calls. :class:`comm_span` times each
   one and records op kind, mesh axis, participant count, payload
   bytes, algorithm bandwidth (bytes/s of the logical payload) and bus
   bandwidth (algbw x the NCCL per-op factor, e.g. 2(n-1)/n for
   allreduce — the hardware-link view that lets rings of different
   sizes be compared).
2. **GSPMD-inserted collectives** of compiled step programs
   (ShardedTrainStep): these never exist in Python — XLA materializes
   them from shardings. :func:`register_program` parses the compiled
   HLO text for collective instructions, derives payload bytes from
   the instruction shapes and maps each replica group back onto the
   mesh axes it spans (a group varying only along 'dp' IS the 'dp'
   gradient allreduce). :class:`program_watch` then charges the
   program's collective inventory on every execution.
3. **shard_map wrappers** (`parallel/collectives.py` RS/AR/AG/
   ppermute/all_to_all and everything built on them — hierarchical
   dcn x dp, pipeline, MoE, ring attention): traced Python calls with
   the axis name in hand. :func:`traced_collective` records them at
   trace time (shapes are static, so bytes are exact); when the trace
   runs under a :class:`program_watch`, the records become that
   program's inventory (charged per execution); otherwise they count
   once, so ad-hoc shard_map programs still show up.

Exposed-vs-overlapped attribution: a collective that blocks the step
thread (the dist kvstore's DCN-bound grad sync, anything inside
Trainer's 'allreduce' phase) is EXPOSED time — it is what the PR-3
step breakdown shows as comm cost. A collective issued off the step
thread, or riding inside a compiled program where XLA's latency-hiding
scheduler overlaps it with compute, is OVERLAPPED. Callers mark
blocking regions with :func:`exposed_region`; unmarked records count
as overlapped.

Cost model: everything is gated on ``MXNET_COMMWATCH`` (default on)
AND ``MXNET_TELEMETRY``; the disabled path is one cached attribute
read per call site (tools/comm_micro.py asserts <5% on the collectives
hot loop). Metrics (docs/OBSERVABILITY.md "Communication"):
``mx_comm_ops_total{op,axis}``, ``mx_comm_bytes_total{op,axis}``,
``mx_comm_bus_bytes_total{op,axis}`` (payload x bus factor — the unit
in which RS+AG == AR holds exactly, so the ZeRO comm gate compares
sharded vs allreduce paths in it; tools/zero_micro.py),
``mx_comm_seconds{op,axis}``,
``mx_comm_bandwidth_bytes_per_sec{op,axis}`` (algbw),
``mx_comm_bus_bandwidth_bytes_per_sec{op,axis}`` (busbw),
``mx_comm_exposed_seconds_total{op,axis}`` /
``mx_comm_overlapped_seconds_total{op,axis}``, plus ``comm::<op>``
chrome-trace spans. :func:`report` aggregates per-(op, axis) rows for
tools/trace_summary.py and tools/fleet_report.py.
"""
from __future__ import annotations

import logging
import re
import threading
from typing import Dict, List, Optional, Tuple

import numpy as _np

from . import profiler
from . import telemetry

__all__ = ["enabled", "refresh", "record", "comm_span", "exposed_region",
           "traced_collective", "register_program", "program_watch",
           "program_execs", "report", "report_key", "comm_totals",
           "reset", "render_report", "wire_dtype_label", "BUS_FACTORS"]

_LOG = logging.getLogger("mxnet_tpu.commwatch")

# the telemetry gate object — ONE attribute load on the hot path
_TSTATE = telemetry._STATE


class _CState:
    __slots__ = ("on",)

    def __init__(self):
        self.on: Optional[bool] = None


_CSTATE = _CState()


def _resolve() -> bool:
    from .config import get as _cfg
    _CSTATE.on = bool(_cfg("MXNET_COMMWATCH"))
    return _CSTATE.on


def enabled() -> bool:
    """Comm watching needs BOTH gates: MXNET_TELEMETRY (cached by
    telemetry) and MXNET_COMMWATCH (cached here — call :func:`refresh`
    after changing either)."""
    on = _TSTATE.on
    if on is None:
        on = telemetry._resolve()
    if not on:
        return False
    con = _CSTATE.on
    if con is None:
        con = _resolve()
    return con


def refresh():
    """Drop the cached MXNET_COMMWATCH gate (telemetry.refresh() calls
    this too, so one refresh covers both layers)."""
    _CSTATE.on = None


# ---------------------------------------------------------------------------
# bus-bandwidth factors (NCCL-tests conventions): busbw = algbw * f(n).
# The factor converts "logical payload per second" into "bytes every
# hardware link actually moved per second", so rings of different sizes
# compare directly.
# ---------------------------------------------------------------------------
def _f_allreduce(n):
    return 2.0 * (n - 1) / n if n > 1 else 1.0


def _f_shifted(n):
    return (n - 1.0) / n if n > 1 else 1.0


BUS_FACTORS = {
    "allreduce": _f_allreduce,
    "reduce_scatter": _f_shifted,
    "allgather": _f_shifted,
    "all_to_all": _f_shifted,
    "ppermute": lambda n: 1.0,
    "broadcast": lambda n: 1.0,
}


def _axis_label(axis) -> str:
    if isinstance(axis, (list, tuple)):
        return "+".join(str(a) for a in axis)
    return str(axis)


# wire dtypes worth their own byte series: the quantized collectives
# (parallel/quantize.py) whose whole point is moving 1-byte payloads.
# Wider payloads stay UNLABELED (implicitly f32-class) so every
# pre-existing mx_comm_* series keeps its exact label set.
_WIRE_DTYPES = {"int8": "int8", "uint8": "int8",
                "float8_e4m3fn": "fp8", "float8_e5m2": "fp8",
                "s8": "int8", "u8": "int8",
                "f8e4m3fn": "fp8", "f8e5m2": "fp8"}


def wire_dtype_label(dtype) -> Optional[str]:
    """The ``dtype`` label value for a collective payload dtype: a
    short name for the 1-byte quantized wire formats, None (no label)
    for everything else."""
    if dtype is None:
        return None
    return _WIRE_DTYPES.get(str(dtype))


# ---------------------------------------------------------------------------
# thread-local context: exposed-region marker + active trace collector
# ---------------------------------------------------------------------------
_TL = threading.local()


class exposed_region:
    """Mark the enclosed region as step-thread-blocking: collectives
    recorded inside count their wall time as EXPOSED comm (the time
    the PR-3 step breakdown shows), not overlapped."""

    def __enter__(self):
        _TL.exposed = getattr(_TL, "exposed", 0) + 1
        return self

    def __exit__(self, *exc):
        _TL.exposed = max(0, getattr(_TL, "exposed", 1) - 1)
        return False


def _in_exposed() -> bool:
    return getattr(_TL, "exposed", 0) > 0


# ---------------------------------------------------------------------------
# the one record sink
# ---------------------------------------------------------------------------
def record(op: str, axis, nbytes: int, participants: int,
           seconds: Optional[float] = None, exposed: Optional[bool] = None,
           count: int = 1, dtype: Optional[str] = None):
    """Account one (or `count` identical) collective(s). `nbytes` is
    the logical payload of ONE collective; `seconds` (when the caller
    measured wall time) adds latency + algbw/busbw histograms and the
    exposed/overlapped split (`exposed=None` reads the thread's
    :func:`exposed_region` marker). `dtype` labels a low-precision wire
    payload (``int8``/``fp8`` — the quantized collectives); None keeps
    the classic label set, read as f32-class by :func:`report`. Never
    raises."""
    try:
        if not enabled():
            return
        axis = _axis_label(axis)
        lab = {"op": op, "axis": axis}
        if dtype is not None:
            lab["dtype"] = dtype
        telemetry.counter("mx_comm_ops_total", **lab).inc(count)
        telemetry.counter("mx_comm_bytes_total",
                          **lab).inc(nbytes * count)
        # bus-traffic bytes (logical payload x the NCCL bus factor):
        # the unit in which RS+AG == AR holds exactly, so byte gates
        # can compare sharded against allreduce paths (tools/zero_micro)
        factor0 = BUS_FACTORS.get(op, lambda n: 1.0)(max(1, participants))
        telemetry.counter("mx_comm_bus_bytes_total",
                          **lab).inc(nbytes * count * factor0)
        if seconds is None or seconds <= 0:
            return
        telemetry.histogram("mx_comm_seconds", **lab).observe(seconds)
        algbw = nbytes * count / seconds
        telemetry.histogram("mx_comm_bandwidth_bytes_per_sec",
                            **lab).observe(algbw)
        factor = BUS_FACTORS.get(op, lambda n: 1.0)(max(1, participants))
        telemetry.histogram("mx_comm_bus_bandwidth_bytes_per_sec",
                            **lab).observe(algbw * factor)
        if exposed is None:
            exposed = _in_exposed()
        telemetry.counter(
            "mx_comm_exposed_seconds_total" if exposed
            else "mx_comm_overlapped_seconds_total",
            **lab).inc(seconds)
    except Exception:
        pass


class comm_span:
    """Time one eager collective call and record it: chrome-trace
    ``comm::<op>`` event (category ``comm``) with bytes/axis/bandwidth
    args + the :func:`record` metrics. Near-zero when the gate is off;
    instrumentation failures never poison the collective."""

    __slots__ = ("op", "axis", "nbytes", "participants", "exposed",
                 "key", "_t0", "_live")

    def __init__(self, op: str, axis, nbytes: int, participants: int,
                 exposed: Optional[bool] = None, key: Optional[str] = None):
        self.op = op
        self.axis = axis
        self.nbytes = int(nbytes)
        self.participants = int(participants)
        self.exposed = exposed
        self.key = key

    def __enter__(self):
        try:
            self._live = enabled() or profiler.state() == "run"
            if self._live:
                import time
                self._t0 = time.perf_counter()
        except Exception:
            self._live = False
        return self

    def __exit__(self, *exc):
        if not self._live:
            return False
        try:
            import time
            dt = time.perf_counter() - self._t0
            exposed = self.exposed
            if exposed is None:
                exposed = _in_exposed()
            record(self.op, self.axis, self.nbytes, self.participants,
                   seconds=dt, exposed=exposed)
            args = {"axis": _axis_label(self.axis), "bytes": self.nbytes,
                    "participants": self.participants,
                    "exposed": bool(exposed)}
            if dt > 0:
                args["algbw_GBs"] = round(self.nbytes / dt / 1e9, 3)
            if self.key is not None:
                args["key"] = self.key
            profiler.record_event("comm::%s" % self.op, "comm",
                                  self._t0 * 1e6, dt * 1e6, args)
        except Exception:
            pass
        return False


# ---------------------------------------------------------------------------
# trace-time accounting for the shard_map wrappers
# ---------------------------------------------------------------------------
def traced_collective(op: str, axis, x, participants: int, count: int = 1,
                      nbytes: Optional[int] = None,
                      dtype: Optional[str] = None):
    """Called by parallel/collectives.py at TRACE time: shapes are
    static so the payload is exact. Under an active
    :class:`program_watch` the record joins that program's inventory
    (charged per execution); otherwise it counts once so ad-hoc
    shard_map programs still appear in the profile. `nbytes` overrides
    the payload derived from `x` (all_gather's message size is the
    total output, not the per-rank input slice); `dtype` labels a
    quantized wire payload (see :func:`wire_dtype_label`)."""
    if not enabled():
        return
    try:
        if nbytes is None:
            size = int(_np.prod(x.shape)) if getattr(x, "shape", None) else 1
            itemsize = _np.dtype(x.dtype).itemsize \
                if hasattr(x, "dtype") else 4
            nbytes = size * itemsize
        rec = {"op": op, "axis": _axis_label(axis), "bytes": nbytes,
               "participants": int(participants), "count": int(count),
               "dtype": dtype}
        collector = getattr(_TL, "collector", None)
        if collector is not None:
            collector.append(rec)
        else:
            record(op, rec["axis"], nbytes, rec["participants"],
                   count=rec["count"], dtype=dtype)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# program inventories — GSPMD collectives harvested from compiled HLO
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
                "f8e4m3fn": 1, "f8e5m2": 1}

# one collective instruction: optional "ROOT ", name =, shaped result
# (single `f32[16,16]{1,0}` or tuple `(f32[64]{0}, f32[1024]{0})` — the
# all-reduce combiner and async -start forms produce tuples), op,
# operands...  e.g.
#   %all-reduce.1 = f32[16,16]{1,0} all-reduce(...), channel_id=1,
#       replica_groups={{0,2,4,6},{1,3,5,7}}, ...
#   %ag = f32[8,4]{1,0} all-gather(...), replica_groups=[4,2]<=[8], ...
#   %arc = (f32[64]{0}, f32[1024]{0}) all-reduce(a, b), ...
# the tuple arm is lazy-up-to-the-op-name (not [^)]*) because TPU
# layouts put parens INSIDE the tuple: (f32[64]{0:T(256)}, ...).
# ragged-all-to-all (XLA's variable-split form — jax ragged collectives)
# and collective-broadcast are first-class: the bare alternation used
# to skip both shapes entirely (ISSUE 15 satellite).
_COLL_RE = re.compile(
    r"=\s*(\(.*?\)|\w+\[[\d,]*\][^\s]*)\s+"
    r"(ragged-all-to-all|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute|collective-broadcast)"
    r"(?:-start)?\(")
_INSTR_NAME_RE = telemetry._HLO_INSTRUCTION     # one reader of HLO lines
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{\{(\d+),(\d+)\}")

_HLO_OP = {"all-reduce": "allreduce", "all-gather": "allgather",
           "reduce-scatter": "reduce_scatter", "all-to-all": "all_to_all",
           "ragged-all-to-all": "all_to_all",
           "collective-permute": "ppermute",
           "collective-broadcast": "broadcast"}


def _first_group(line: str, n_devices: Optional[int] = None
                 ) -> Optional[List[int]]:
    """Member ids of the first replica group on an HLO collective
    line (ids are logical positions in the program's device
    assignment = mesh.devices.flat order). ``replica_groups={}`` is
    the all-replicas form: one group of every device."""
    m = _GROUPS_RE.search(line)
    if m:
        return [int(v) for v in m.group(1).split(",")]
    if "replica_groups={}" in line and n_devices:
        return list(range(n_devices))
    m = _IOTA_RE.search(line)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(v) for v in m.group(3).split(",")]
        ids = _np.arange(int(_np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(v) for v in m.group(4).split(",")])
        return [int(v) for v in ids.reshape(ngroups, gsize)[0]]
    m = _PAIRS_RE.search(line)
    if m:
        return [int(m.group(1)), int(m.group(2))]
    return None


def _axes_of_group(group: List[int], mesh) -> str:
    """Which mesh axes a replica group spans: the coordinates that vary
    between the group's members. A GSPMD grad allreduce whose group
    varies only along 'dp' IS the dp allreduce."""
    try:
        shape = tuple(mesh.devices.shape)
        names = tuple(mesh.axis_names)
        coords = _np.array([_np.unravel_index(g, shape) for g in group])
        varying = [names[d] for d in range(len(shape))
                   if len(set(coords[:, d])) > 1]
        if varying:
            return "+".join(varying)
        return "self"
    except Exception:
        return "?"


def parse_hlo_collectives(hlo_text: str, mesh=None) -> List[dict]:
    """Collective inventory of one compiled HLO module: for every
    collective instruction, {op, axis, bytes, participants, count=1}.
    Payload-byte conventions (NCCL-tests "message size"): allreduce /
    allgather / ppermute / all_to_all use the instruction's result
    bytes (tuple results — the all-reduce combiner's grouped syncs and
    async ``-start`` forms — sum every member's bytes); reduce-scatter
    uses result x group (the pre-scatter buffer); ragged-all-to-all
    counts the (dense, padded) result buffer it scatters into — the
    upper bound actually reserved on the wire; collective-broadcast
    counts its result once (bus factor 1). `-done` halves of async
    pairs are skipped (the `-start` carries the shape); instructions
    inside while-loop bodies count once per execution of the program,
    like the rest of the inventory.

    Each record also carries the HLO instruction ``name`` and the
    result member list ``result`` = ``[(dtype, shape tuple), ...]`` —
    the Level-4 SPMD rules (staticcheck/spmd_rules.py) attribute
    implicit all-gathers back to program inputs with them.
    """
    out: List[dict] = []
    n_devices = int(mesh.devices.size) if mesh is not None else None
    for line in hlo_text.splitlines():
        if "replica_groups" not in line and "source_target_pairs" not in line:
            continue
        if "-done" in line.split("=")[0]:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_s, hlo_op = m.group(1), m.group(2)
        op = _HLO_OP[hlo_op]
        nm = _INSTR_NAME_RE.match(line)
        members = _SHAPE_RE.findall(result_s)
        if result_s.startswith("(") and len(members) > 1:
            # tuple result. Async -start tuples alias (operands...,
            # results..., [u32[] contexts]): drop the scalar context
            # slots, then halve the mirrored operand/result half so
            # the payload is counted once. Combiner tuples (sync
            # grouped all-reduce) have one member per operand — no
            # mirror, every member is payload.
            members = [mm for mm in members
                       if not (mm[1] == "" and mm[0] in ("u32", "s32"))]
            k = len(members) // 2
            if ("-start(" in line and k
                    and members[:k] == members[k:2 * k]):
                members = members[k:]
        nbytes = 0
        wire = None
        for dtype, shape_s in members:
            size = 1
            if shape_s:
                for d in shape_s.split(","):
                    size *= int(d)
            nbytes += size * _DTYPE_BYTES.get(dtype, 4)
            if wire is None:
                # label GSPMD-materialized quantized payloads too (a
                # mixed tuple keeps the first member's class)
                wire = wire_dtype_label(dtype)
        group = _first_group(line, n_devices)
        participants = len(group) if group else 1
        if op == "reduce_scatter":
            nbytes *= max(1, participants)
        axis = _axes_of_group(group, mesh) if (group and mesh is not None) \
            else "?"
        if axis == "self" or participants <= 1:
            continue                      # degenerate single-member group
        result = [(dtype,
                   tuple(int(d) for d in shape_s.split(",")) if shape_s
                   else ())
                  for dtype, shape_s in members]
        out.append({"op": op, "axis": axis, "bytes": nbytes,
                    "participants": participants, "count": 1,
                    "dtype": wire, "name": nm.group(1) if nm else "?",
                    "result": result})
    return out


# program key -> {"label", "collectives": [rec], "flops", "execs"}
_PROG_LOCK = threading.Lock()
_PROG_INV: Dict[object, dict] = {}


def register_program(key, label: str, compiled=None, mesh=None,
                     flops: Optional[float] = None,
                     hlo_text: Optional[str] = None):
    """Register a compiled program's collective inventory (parsed from
    its HLO) + its cost-analysis FLOPs under `key`. A later
    :class:`program_watch` on the same key charges the inventory —
    and the FLOPs into ``mx_executed_flops_total`` — once per
    execution. Never raises."""
    try:
        if not enabled():
            return
        if hlo_text is None and compiled is not None:
            try:
                hlo_text = compiled.as_text()
            except Exception:
                hlo_text = None
        colls = parse_hlo_collectives(hlo_text, mesh) if hlo_text else []
        with _PROG_LOCK:
            _PROG_INV[key] = {"label": label, "collectives": colls,
                              "flops": flops, "execs": 0,
                              "hlo_seen": hlo_text is not None}
    except Exception:
        pass


class program_watch:
    """Wrap ONE execution of a (possibly jitted) step program.

    - A first call that traces inside the watch has its
      :func:`traced_collective` records harvested as the program's
      inventory (keyed by `key`) — unless :func:`register_program`
      already supplied an HLO-parsed inventory for the key, which
      subsumes them (the shard_map collectives are real HLO
      instructions too; counting both would double-book).
    - Every exit charges the key's inventory: per-collective op/byte
      counters, program-effective bandwidth (payload / program wall
      time — a lower bound: the wall includes the compute the XLA
      scheduler overlaps the collective with), and the program's
      FLOPs into ``mx_executed_flops_total`` (the MFU numerator).
    """

    __slots__ = ("key", "label", "exposed", "_t0", "_live", "_outer")

    def __init__(self, key, label: Optional[str] = None,
                 exposed: bool = False):
        self.key = key
        self.label = label or str(key)
        # compiled-program collectives default to OVERLAPPED (XLA's
        # latency-hiding scheduler); a program that blocks the step
        # thread (the kvstore's quantized grad-sync program) passes
        # exposed=True so its wire time shows up as exposed comm
        self.exposed = bool(exposed)

    def __enter__(self):
        self._live = enabled()
        if not self._live:
            return self
        import time
        self._outer = getattr(_TL, "collector", None)
        _TL.collector = []
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        if not self._live:
            return False
        try:
            import time
            dt = time.perf_counter() - self._t0
            traced = getattr(_TL, "collector", None) or []
            _TL.collector = self._outer
            if exc_type is not None:
                return False
            with _PROG_LOCK:
                inv = _PROG_INV.get(self.key)
                if inv is None:
                    inv = _PROG_INV[self.key] = {
                        "label": self.label, "collectives": [],
                        "flops": None, "execs": 0, "hlo_seen": False}
                if traced and not inv["hlo_seen"] \
                        and not inv["collectives"]:
                    inv["collectives"] = traced
                inv["execs"] += 1
                colls = list(inv["collectives"])
                flops = inv["flops"]
            total_bytes = sum(c["bytes"] * c["count"] for c in colls)
            for c in colls:
                # program-effective attribution: op share of the wall
                # proportional to its byte share => one common
                # effective bandwidth total_bytes/dt for every op
                secs = (dt * (c["bytes"] * c["count"]) / total_bytes
                        if total_bytes and dt > 0 else None)
                record(c["op"], c["axis"], c["bytes"], c["participants"],
                       seconds=secs, exposed=self.exposed,
                       count=c["count"], dtype=c.get("dtype"))
            if flops:
                telemetry.counter("mx_executed_flops_total").inc(flops)
        except Exception:
            pass
        return False


def program_flops(key) -> Optional[float]:
    with _PROG_LOCK:
        inv = _PROG_INV.get(key)
        return inv["flops"] if inv else None


def has_program(key) -> bool:
    """Whether `key` has a registered inventory. Callers that cache
    compiled executables (parallel/sharded.py) use this to re-register
    after telemetry.reset() cleared the inventories, or when the gate
    was off at compile time."""
    with _PROG_LOCK:
        return key in _PROG_INV


def program_execs(key) -> int:
    """Executions charged to `key`'s inventory so far (0 for unknown
    keys). Gates like tools/zero_micro assert the sharded-update
    program really ran once per step instead of silently falling back
    to an unwatched path."""
    with _PROG_LOCK:
        inv = _PROG_INV.get(key)
        return int(inv["execs"]) if inv else 0


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------
def report() -> List[dict]:
    """Per-(op, axis, dtype) rows from the live registry: ops, bytes,
    measured seconds, mean algbw/busbw, exposed/overlapped seconds.
    The table tools/fleet_report.py and trace_summary's comm section
    print. ``dtype`` is ``f32`` for classic (unlabeled) payloads and
    the wire label (``int8``/``fp8``) for quantized collectives, so
    the ~4x wire reduction of MXNET_KVSTORE_QUANTIZE is visible per
    tier in the existing reports."""
    rows: Dict[Tuple[str, str, str], dict] = {}

    def _row(labels):
        lab = dict(labels)
        key = (lab.get("op", "?"), lab.get("axis", "?"),
               lab.get("dtype", "f32"))
        row = rows.get(key)
        if row is None:
            row = rows[key] = {"op": key[0], "axis": key[1],
                               "dtype": key[2], "ops": 0,
                               "bytes": 0.0, "bus_bytes": 0.0,
                               "seconds": 0.0,
                               "algbw": 0.0, "busbw": 0.0,
                               "exposed_s": 0.0, "overlapped_s": 0.0}
        return row

    with telemetry._REG_LOCK:
        metrics = list(telemetry._METRICS.values())
    for m in metrics:
        if m.name == "mx_comm_ops_total":
            _row(m.labels)["ops"] += m.get()
        elif m.name == "mx_comm_bytes_total":
            _row(m.labels)["bytes"] += m.get()
        elif m.name == "mx_comm_bus_bytes_total":
            _row(m.labels)["bus_bytes"] += m.get()
        elif m.name == "mx_comm_seconds":
            _row(m.labels)["seconds"] += m.sum
        elif m.name == "mx_comm_bandwidth_bytes_per_sec":
            row = _row(m.labels)
            row["algbw"] = m.sum / m.count if m.count else 0.0
        elif m.name == "mx_comm_bus_bandwidth_bytes_per_sec":
            row = _row(m.labels)
            row["busbw"] = m.sum / m.count if m.count else 0.0
        elif m.name == "mx_comm_exposed_seconds_total":
            _row(m.labels)["exposed_s"] += m.get()
        elif m.name == "mx_comm_overlapped_seconds_total":
            _row(m.labels)["overlapped_s"] += m.get()
    return sorted(rows.values(), key=lambda r: -r["bytes"])


def report_key(row: dict) -> str:
    """The canonical bench-JSON key for one :func:`report` row:
    ``op/axis`` for classic payloads, ``op/axis/dtype`` for quantized
    wire rows — ONE definition so every bench emitter (bench.py,
    tools/bert_bench.py) shares the schema."""
    dt = row.get("dtype", "f32")
    if dt == "f32":
        return "%s/%s" % (row["op"], row["axis"])
    return "%s/%s/%s" % (row["op"], row["axis"], dt)


def comm_totals() -> dict:
    """(bytes, seconds, exposed seconds) over every op/axis — the
    compact numbers the fleet snapshot publishes per rank."""
    tot = {"bytes": 0.0, "seconds": 0.0, "exposed_seconds": 0.0,
           "ops": 0.0}
    for r in report():
        tot["bytes"] += r["bytes"]
        tot["seconds"] += r["exposed_s"] + r["overlapped_s"]
        tot["exposed_seconds"] += r["exposed_s"]
        tot["ops"] += r["ops"]
    return tot


def _fmt_bytes(v: float) -> str:
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if v >= div:
            return "%.2f%s" % (v / div, unit)
    return "%.0fB" % v


def render_report(rows: Optional[List[dict]] = None) -> str:
    rows = report() if rows is None else rows
    out = ["%-16s %-10s %-6s %8s %10s %10s %11s %11s %10s %10s"
           % ("collective", "axis", "dtype", "ops", "bytes", "seconds",
              "algbw", "busbw", "exposed_s", "overlap_s")]
    for r in rows:
        out.append("%-16s %-10s %-6s %8d %10s %10.4f %9s/s %9s/s "
                   "%10.4f %10.4f"
                   % (r["op"], r["axis"], r.get("dtype", "f32"),
                      r["ops"], _fmt_bytes(r["bytes"]),
                      r["seconds"], _fmt_bytes(r["algbw"]),
                      _fmt_bytes(r["busbw"]), r["exposed_s"],
                      r["overlapped_s"]))
    return "\n".join(out)


def reset():
    """Drop program inventories (test isolation; the metric series live
    in the telemetry registry and clear with telemetry.reset())."""
    with _PROG_LOCK:
        _PROG_INV.clear()
