"""Compile-watch — observability for every ``jax.jit`` program we build.

In this TPU-native rebuild every hot path IS a jitted XLA program:
eager ops dispatch through ``ops._jit_cache``, ``CachedOp._compile``
turns whole symbol graphs into single executables, and the fused
backward jits the entire fwd+bwd tape. PR 3's telemetry sees only
*execution*; this module (ISSUE 4) watches *compilation* — the classic
silent failure mode of compile-to-XLA stacks is a recompile storm
(cf. arxiv 1810.09868: one stray shape re-specializes the world), and
the planned-memory/FLOP figures of each program (the raw features of
arxiv 2008.01040's learned TPU cost model) are what the perf roadmap
is tuned against.

Wrapped sites are the four DYNAMIC jit caches (ops._jit_cache,
_jitted_with_none_slots, CachedOp's three programs, the fused
backward) — the ones keyed on user-data shapes that can storm. The
sharded step (parallel/sharded.py::ShardedTrainStep._executable), which
keeps its own AOT executables, compiles each through
:func:`compile_stages` and leaves the same record a program. The other
static single-compile sites (optimizer fused update, rtc, kvstore
allsum) still call jax.jit directly and are not watched yet.

One primitive: :func:`watched_jit` wraps a pure function in a
:class:`WatchedJit` — a drop-in ``jax.jit`` replacement that, when the
``MXNET_TELEMETRY`` gate is on, keys its OWN cache on the abstract
input signature (shape/dtype/weak-type/device per pytree leaf) and on
a miss compiles through the AOT path (``.trace()``/``.lower()``/
``.compile()``) so each stage is timed separately and the compiled
program's ``cost_analysis()`` / ``memory_analysis()`` are captured.
Misses on an already-seen function are **recompiles**: the new
signature is diffed against the previous one and the record names
exactly which argument changed, what field (shape/dtype/...), and
from/to what. Gate off: the wrapper forwards straight to the plain
``jax.jit`` callable — one attribute check of overhead
(tools/compile_micro.py asserts <5% on the eager-dispatch microbench).

Everything feeds the PR 3 registry (docs/OBSERVABILITY.md
"Compilation"): ``mx_compile_total{fn}`` / ``mx_recompiles_total{fn}``
/ ``mx_compile_cache_hits_total{fn}`` counters,
``mx_compile_seconds{fn,stage}`` histograms, ``mx_compile_flops{fn}``,
``mx_hbm_bytes{kind}`` planned-memory accounting, the
``mx_jit_cache_entries`` gauge, and ``compile::<fn>`` chrome-trace
spans. A recompile-storm guard (``MXNET_COMPILE_WARN_N`` /
``MXNET_COMPILE_STRICT``) warns — or raises — with the full
signature-diff history once one function recompiles too often.

Any failure inside the watch path must never poison the program it
observes: AOT errors degrade the signature entry to the plain jitted
callable (whole-call "total" stage timing), and analysis extraction is
field-by-field guarded — the CPU backend omits several of them.
"""
from __future__ import annotations

import collections
import logging
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.tree_util as jtu

from .base import MXNetError
from . import profiler
from . import telemetry

__all__ = ["WatchedJit", "watched_jit", "enabled", "programs", "report",
           "recompile_log", "cache_counts", "cache_entries", "reset",
           "render_report", "compile_seconds_total", "compile_stages",
           "watch_compile", "publish", "cache_misses"]

_LOG = logging.getLogger("mxnet_tpu.compilewatch")

# the telemetry gate object — read as ONE attribute load in
# WatchedJit.__call__, the hot eager-dispatch path
_TSTATE = telemetry._STATE

# sentinel: this signature is served by the plain jax.jit callable
# (AOT path failed once for it — never retry, never double-compile)
_DEGRADED = object()
# sentinel: signature seen and analyzed; execution goes through the
# plain jax.jit callable by policy (exec_via_jit sites)
_VIA_JIT = object()

# every live wrapper, for the mx_jit_cache_entries gauge and report()
_WATCHED: "weakref.WeakSet[WatchedJit]" = weakref.WeakSet()

# Level-2/4 static-analysis hook (staticcheck/graph_rules.py
# installs): called once per newly compiled signature with (wrapper,
# traced, formatted signature, compiled-or-None) on the MISS path only
# — the cache-hit path never reads it. The hook gates itself on
# MXNET_STATICCHECK / MXNET_STATICCHECK_SPMD; the Level-4 half parses
# the compiled HLO for SPMD hazards and marks collective-issuing
# programs on the wrapper (`issues_collectives`).
_GRAPH_HOOK: List[Optional[Callable]] = [None]

# flat per-program compile records, oldest first (deque cap = O(1)
# eviction even mid-storm; the counters are never capped, so the cap
# is visible as records_dropped)
_PROG_LOCK = threading.Lock()
_PROGRAMS_CAP = 10000
_PROGRAMS: "collections.deque[dict]" = collections.deque(
    maxlen=_PROGRAMS_CAP)
_DROPPED = [0]
_COMPILE_SECONDS = [0.0]   # running total (uncapped; goodput debit)


def enabled() -> bool:
    """Compile watching rides the MXNET_TELEMETRY gate (cached — see
    telemetry.refresh)."""
    return telemetry.enabled()


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------
_SHORT = {"float32": "f32", "float64": "f64", "float16": "f16",
          "bfloat16": "bf16", "int32": "i32", "int64": "i64",
          "int16": "i16", "int8": "i8", "uint8": "u8", "bool": "pred",
          "complex64": "c64"}


def _leaf_sig(x) -> Tuple:
    """Hashable signature of one pytree leaf, at least as fine as the
    jax.jit cache key for the cases our call sites produce: shape,
    dtype, weak-type flag, and the committed device set (an AOT
    executable is device-bound; a same-shape array on another device
    must be a different entry)."""
    shape = getattr(x, "shape", None)
    if shape is None:                       # python scalar leaf
        return ("py", type(x).__name__)
    # dtype and device stay OBJECTS in the key (hashable; stringified
    # only when a record is written) — str(np.dtype) per call is the
    # single biggest cost on the enabled hit path
    dtype = getattr(x, "dtype", None)
    weak = bool(getattr(x, "weak_type", False))
    try:
        devs = x.device
    except Exception:
        try:
            devs = tuple(sorted(str(d) for d in x.devices()))
        except Exception:
            devs = None
    return (tuple(shape), dtype, weak, devs)


def _fmt_leaf(sig) -> str:
    if sig[0] == "py":
        return "py:%s" % sig[1]
    shape, dtype, weak = sig[0], str(sig[1]), sig[2]
    short = _SHORT.get(dtype, dtype)
    return "%s[%s]%s" % (short, ",".join(str(s) for s in shape),
                         "~" if weak else "")


def _arg_sig(arg) -> Tuple[Tuple, Tuple]:
    """(treedef-key, leaf sigs) for one positional argument."""
    leaves, treedef = jtu.tree_flatten(arg)
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


def _fmt_arg(sig) -> str:
    leaves = sig[1]
    if len(leaves) == 1:
        return _fmt_leaf(leaves[0])
    return "pytree{%s}" % ",".join(_fmt_leaf(l) for l in leaves)


def _diff_args(names, old: Sequence, new: Sequence) -> List[dict]:
    """Name exactly what changed between two signatures — the recompile
    attribution record. Each entry: {arg, field, from, to}."""
    changes = []
    if len(old) != len(new):
        changes.append({"arg": "*", "field": "arg_count",
                        "from": len(old), "to": len(new)})
    fields = ("shape", "dtype", "weak_type", "device")
    for i in range(min(len(old), len(new))):
        name = names(i)
        (otd, ol), (ntd, nl) = old[i], new[i]
        if otd != ntd:
            changes.append({"arg": name, "field": "structure",
                            "from": str(otd), "to": str(ntd)})
            continue
        for j, (osig, nsig) in enumerate(zip(ol, nl)):
            if osig == nsig:
                continue
            leaf = name if len(ol) == 1 else "%s[leaf %d]" % (name, j)
            if osig[0] == "py" or nsig[0] == "py":
                changes.append({"arg": leaf, "field": "type",
                                "from": _fmt_leaf(osig),
                                "to": _fmt_leaf(nsig)})
                continue
            for k, field in enumerate(fields):
                if osig[k] != nsig[k]:
                    # dtype/device entries are objects in the key;
                    # records carry readable strings
                    ov, nv = osig[k], nsig[k]
                    if field in ("dtype", "device"):
                        ov, nv = str(ov), str(nv)
                    changes.append({"arg": leaf, "field": field,
                                    "from": ov, "to": nv})
    return changes


# ---------------------------------------------------------------------------
# compiled-program analysis (every field guarded: the CPU backend omits
# flops on some programs, TPU omits others — absence is data, not error)
# ---------------------------------------------------------------------------
def _extract_cost(compiled) -> Optional[float]:
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = cost.get("flops")
        return float(flops) if flops is not None else None
    except Exception:
        return None


def _extract_memory(compiled) -> Dict[str, int]:
    out: Dict[str, int] = {}
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return out
    for kind, attr in (("argument", "argument_size_in_bytes"),
                       ("output", "output_size_in_bytes"),
                       ("temp", "temp_size_in_bytes"),
                       ("code", "generated_code_size_in_bytes"),
                       ("alias", "alias_size_in_bytes")):
        try:
            v = getattr(mem, attr, None)
            if v is not None:
                out[kind] = int(v)
        except Exception:
            pass
    return out


# ---------------------------------------------------------------------------
# the AOT stages, timed — what every compile record is made from
# ---------------------------------------------------------------------------
# JAX says through jax.monitoring whether a compile asked the persistent
# cache and whether it was served from it, on the thread that compiles
_USES_CACHE = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _CacheEvents(threading.local):
    def __init__(self):
        self.requests = 0
        self.hits = 0


_CACHE_EVENTS = _CacheEvents()
_CACHE_LISTENER = [False]


def _on_jax_event(event, **_):
    if event == _CACHE_HIT:
        _CACHE_EVENTS.hits += 1
    elif event == _USES_CACHE:
        _CACHE_EVENTS.requests += 1


def _listen_for_cache():
    if not _CACHE_LISTENER[0]:
        with _PROG_LOCK:
            if not _CACHE_LISTENER[0]:
                jax.monitoring.register_event_listener(_on_jax_event)
                _CACHE_LISTENER[0] = True


def compile_stages(jitted, args):
    """``jitted`` through ``.trace()`` / ``.lower()`` / ``.compile()``
    for ``args``, each stage timed: ``(traced, lowered, compiled,
    stages, persistent_cache)``. ``stages`` is {"trace", "lower",
    "compile"} seconds in that order; ``persistent_cache`` is "hit"
    where JAX served the executable from its persistent cache (the
    ``compile`` stage is then the load), "miss" where it asked the
    cache and compiled, None where it reported neither (no cache
    directory set, a program it does not cache). Raises what the
    stages raise."""
    _listen_for_cache()
    t0 = time.perf_counter()
    traced = jitted.trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    seen = _CACHE_EVENTS
    requests, hits = seen.requests, seen.hits
    compiled = lowered.compile()
    t3 = time.perf_counter()
    word = "hit" if seen.hits > hits \
        else "miss" if seen.requests > requests else None
    return (traced, lowered, compiled,
            {"trace": t1 - t0, "lower": t2 - t1, "compile": t3 - t2}, word)


# ---------------------------------------------------------------------------
# the wrapper
# ---------------------------------------------------------------------------
class WatchedJit:
    """Drop-in ``jax.jit`` with a watched, signature-keyed program
    cache. Positional-args only — our call sites pass no kwargs, and
    skipping the ``**kwargs`` dict keeps the disabled path at one
    attribute check (tools/compile_micro.py's 5% gate).

    Execution policy per site: ``exec_via_jit=True`` (the per-op eager
    sites) runs every call through the plain ``jax.jit`` callable —
    its C++ cache hit is ~2.5x faster per call than an AOT
    executable's Python wrapper — and uses the AOT object ONLY to time
    the stages and pull cost/memory analysis (the one extra compile at
    miss time is cheap for per-op programs). ``False`` (CachedOp, the
    fused backward) executes through the AOT executable: those
    programs take seconds to build, so compiling twice is the worse
    trade and the ~30us/call wrapper cost is amortized over a whole
    model step."""

    __slots__ = ("_jit", "fn_label", "site", "instance", "static_repr",
                 "_arg_names", "_exec_via_jit", "_lock", "_cache",
                 "_flops_by_sig", "_last_sig", "_recompiles",
                 "_diff_history", "_warned", "donate_argnums",
                 "expected_signatures", "issues_collectives",
                 "__weakref__")

    def __init__(self, fn: Callable, fn_label: str, site: str,
                 arg_names: Optional[Sequence[str]] = None,
                 instance: Optional[str] = None,
                 static_repr: Optional[str] = None,
                 exec_via_jit: bool = False,
                 donate_argnums: Sequence[int] = (),
                 keep_unused: bool = False):
        # donated arg slots flow into jax.jit (XLA may alias those
        # input buffers into outputs — the serving path's in/out
        # staging reuse, ISSUE 12) and into the Level-2 graph hook,
        # which checks the donation rules per program label
        self.donate_argnums = tuple(donate_argnums)
        # a site that INTENDS to hold N specialized programs (the serve
        # bucket ladder) sets this so the storm guard only fires past
        # warn_n recompiles BEYOND the planned set — a bucket miss past
        # the ladder still storms, a deliberate warmup never does
        self.expected_signatures = 0
        # set True by the Level-4 SPMD hook when a compiled signature's
        # HLO contains cross-device collectives: the mark the engine's
        # collective-interleave check consumes (staticcheck/race.py) —
        # sticky across signatures, never cleared
        self.issues_collectives = False
        # keep_unused: a donated input the program never reads (a buffer
        # handed over only to be overwritten) stays an input, so an
        # output can take it
        self._jit = jax.jit(fn, donate_argnums=self.donate_argnums,
                            keep_unused=keep_unused)
        self.fn_label = fn_label
        self.site = site
        self.instance = instance or fn_label
        self.static_repr = static_repr
        self._arg_names = list(arg_names) if arg_names else None
        self._exec_via_jit = exec_via_jit
        self._lock = threading.Lock()
        self._cache: Dict[Tuple, Any] = {}    # sig -> compiled | sentinel
        self._flops_by_sig: Dict[Tuple, float] = {}   # MFU numerator
        self._last_sig: Optional[Tuple] = None  # per-arg sigs of last compile
        self._recompiles = 0
        self._diff_history: List[dict] = []
        self._warned = False
        _WATCHED.add(self)

    # -- naming ---------------------------------------------------------
    def _name(self, i: int) -> str:
        if self._arg_names and i < len(self._arg_names):
            return self._arg_names[i]
        return "arg%d" % i

    # -- introspection --------------------------------------------------
    def cache_info(self) -> dict:
        return {"fn": self.fn_label, "site": self.site,
                "instance": self.instance, "entries": len(self._cache),
                "recompiles": self._recompiles}

    @property
    def recompiles(self) -> int:
        return self._recompiles

    def executables(self) -> List[Any]:
        """The AOT executables this wrapper serves calls from (none for
        a signature that runs through the plain jit)."""
        return [e for e in self._cache.values() if hasattr(e, "as_text")]

    # -- dispatch -------------------------------------------------------
    def __call__(self, *args):
        on = _TSTATE.on
        if on is None:
            on = telemetry._resolve()
        if not on:
            return self._jit(*args)
        sig, entry = self._lookup(args)
        return self._dispatch(sig, entry, args)

    def call_phased(self, phase: str, *args):
        """``self(*args)`` under two step-phase spans, for a caller that
        times the host's share of one launch: ``step::<phase>.lookup``
        (the arguments' signature and the program cache) and
        ``step::<phase>.call`` (the executable's own call: the runtime
        takes the arguments, allocates the outputs and enqueues the
        program; a first call compiles here)."""
        with telemetry.phase(phase + ".lookup"):
            sig, entry = self._lookup(args) if telemetry.enabled() \
                else (None, None)
        with telemetry.phase(phase + ".call"):
            return self._dispatch(sig, entry, args)

    def _lookup(self, args):
        """(signature, cached entry or None); signature None where the
        call goes to the plain jit."""
        for a in args:
            if isinstance(a, jax.core.Tracer):
                # called under an outer jax trace (e.g. autograd
                # create_graph replaying a recorded fwd_fn): inline
                # through the plain jit — a trace is not a compile,
                # and AOT-compiling tracer args would record phantom
                # programs (or raise under MXNET_COMPILE_STRICT)
                return None, None
        try:
            sig = tuple(_arg_sig(a) for a in args)
        except Exception:
            return None, None
        return sig, self._cache.get(sig)

    def _dispatch(self, sig, entry, args):
        if sig is None:
            return self._jit(*args)
        if entry is not None:
            telemetry.count_event("mx_compile_cache_hits_total",
                                  fn=self.fn_label)
            self._count_exec(sig)
            return self._serve(sig, entry, args)
        return self._compile_and_call(sig, args)

    def _count_exec(self, sig):
        """One execution of a cached program: its cost-analysis FLOPs
        join mx_executed_flops_total — the measured (not attributed)
        numerator of the mx_mfu gauge (ISSUE 6)."""
        flops = self._flops_by_sig.get(sig)
        if flops:
            try:
                telemetry.counter("mx_executed_flops_total").inc(flops)
            except Exception:
                pass

    def _serve(self, sig, entry, args):
        """Execute one cached signature entry (shared by the fast hit
        path and the under-lock re-check)."""
        if entry is _VIA_JIT or entry is _DEGRADED:
            return self._jit(*args)
        try:
            return entry(*args)
        except Exception as e:
            # aval/device edge the AOT executable rejects but jit
            # handles — degrade this signature permanently, VISIBLY:
            # a swallowed failure here would silently drop all stage/
            # cost data for this program (and re-raise masking: if the
            # plain jit call below fails too, that error propagates)
            self._cache[sig] = _DEGRADED
            telemetry.count_event("mx_compile_degraded_total",
                                  fn=self.fn_label)
            _LOG.warning(
                "compilewatch: AOT executable for %s (%s) failed at "
                "call time (%s: %s); signature degraded to the plain "
                "jitted path", self.fn_label, self.instance,
                type(e).__name__, e)
            return self._jit(*args)

    # -- the miss path --------------------------------------------------
    def _compile_and_call(self, sig, args):
        with self._lock:
            # re-check under the lock: a racing thread may have
            # compiled this signature while we waited
            entry = self._cache.get(sig)
            if entry is not None:
                self._count_exec(sig)
                return self._serve(sig, entry, args)

            is_recompile = self._last_sig is not None
            changed = (_diff_args(self._name, self._last_sig, sig)
                       if is_recompile else [])

            t0 = time.perf_counter()
            stages: Dict[str, float] = {}
            compiled = None
            traced = None
            cache_word = None
            out = _MISSING = object()
            try:
                traced, _, compiled, stages, cache_word = compile_stages(
                    self._jit, args)
            except Exception:
                compiled = None
            if compiled is not None:
                flops = _extract_cost(compiled)
                mem = _extract_memory(compiled)
                # the host's side of the program's first call: the
                # executable loaded onto the device, the launch handed
                # over (not waited for). Through the plain jit (the
                # per-op sites) its own compile of the small program
                # is inside
                with telemetry.setup_phase("first_launch"):
                    if self._exec_via_jit:
                        # analysis-only AOT: drop the executable (jit
                        # keeps its own) and serve every call from the
                        # fast path
                        out = self._jit(*args)
                        self._cache[sig] = _VIA_JIT
                    else:
                        try:
                            out = compiled(*args)
                            self._cache[sig] = compiled
                        except Exception:
                            compiled = None
                            out = _MISSING
            if compiled is None:
                # whole-call fallback: the plain jitted call compiles
                # internally; one "total" stage is the best we can time
                flops, mem = None, {}
                tw0 = time.perf_counter()
                out = self._jit(*args)
                stages = {"total": time.perf_counter() - tw0}
                t0 = tw0        # a record's stages run from its time
                self._cache[sig] = _DEGRADED
            self._last_sig = sig
            if flops:
                self._flops_by_sig[sig] = flops
                self._count_exec(sig)     # the miss call executed too

            record = {
                "site": self.site, "fn": self.fn_label,
                "instance": self.instance,
                "kind": "recompile" if is_recompile else "compile",
                "stages": stages, "flops": flops, "bytes": mem,
                "signature": [_fmt_arg(s) for s in sig],
                "changed": changed, "time": t0,
                "persistent_cache": cache_word,
            }
            if self.static_repr:
                record["static"] = self.static_repr
            gh = _GRAPH_HOOK[0]
            if gh is not None and traced is not None:
                # Level-2/4 graph check, once per new signature; any
                # failure inside must never poison the program
                try:
                    gh(self, traced, record["signature"], compiled)
                except Exception:
                    pass
            if is_recompile:
                self._recompiles += 1
                self._diff_history.append(
                    {"changed": changed,
                     "signature": record["signature"]})
            publish(record)
            if is_recompile:
                self._storm_guard(record)
        return out

    def _storm_guard(self, record: dict):
        """MXNET_COMPILE_WARN_N / MXNET_COMPILE_STRICT: a function that
        keeps recompiling is re-specializing on something — warn with
        the signature-diff history naming what changed each time, or
        raise under strict mode."""
        from .config import get as _cfg
        try:
            warn_n = int(_cfg("MXNET_COMPILE_WARN_N"))
        except Exception:
            warn_n = 0
        if warn_n <= 0 or self._recompiles <= warn_n + \
                max(0, self.expected_signatures - 1):
            return
        history = "; ".join(
            ", ".join("%s.%s %s->%s" % (c["arg"], c["field"],
                                        c["from"], c["to"])
                      for c in h["changed"]) or "<no diff>"
            for h in self._diff_history[-8:])
        msg = ("recompile storm: %s (%s) recompiled %d times "
               "(MXNET_COMPILE_WARN_N=%d); last signature diffs: %s"
               % (self.fn_label, self.instance, self._recompiles,
                  warn_n, history))
        if not self._warned:
            self._warned = True
            _LOG.warning(msg)
        if _cfg("MXNET_COMPILE_STRICT"):
            raise MXNetError(msg)


# -- accounting (never poisons the compiled call) ---------------------------
def publish(record: dict):
    """One compile record into ``programs()``, the ``mx_compile_*``
    instruments and the chrome trace (``compile::<fn>`` from the
    record's ``time`` over its stages). A record is {"site", "fn",
    "instance", "kind" (compile | recompile), "stages" {stage:
    seconds, in the order they ran}, "flops", "bytes", "signature",
    "changed", "time" (``time.perf_counter`` at the first stage's
    start), "persistent_cache" (:func:`compile_stages`)}."""
    try:
        with _PROG_LOCK:
            if len(_PROGRAMS) == _PROGRAMS_CAP:
                _DROPPED[0] += 1      # deque maxlen evicts oldest
            _PROGRAMS.append(record)
        fn = record["fn"]
        telemetry.counter("mx_compile_total", fn=fn).inc()
        if record["kind"] == "recompile":
            telemetry.counter("mx_recompiles_total", fn=fn).inc()
        total = 0.0
        for stage, dt in record["stages"].items():
            telemetry.histogram("mx_compile_seconds", fn=fn,
                                stage=stage).observe(dt)
            total += dt
        with _PROG_LOCK:
            _COMPILE_SECONDS[0] += total
        if record["flops"] is not None:
            telemetry.counter("mx_compile_flops", fn=fn).inc(
                record["flops"])
        for kind, nbytes in record["bytes"].items():
            telemetry.gauge("mx_hbm_bytes", kind=kind).inc(nbytes)
        telemetry.gauge("mx_jit_cache_entries").set(cache_entries())
        args = {"site": record["site"], "instance": record["instance"],
                "kind": record["kind"],
                "signature": record["signature"]}
        for stage, dt in record["stages"].items():
            args["%s_ms" % stage] = round(dt * 1e3, 3)
        for key in ("flops", "persistent_cache"):
            if record[key] is not None:
                args[key] = record[key]
        if record["bytes"]:
            args["bytes"] = record["bytes"]
        if record["changed"]:
            args["changed"] = record["changed"]
        profiler.record_event("compile::%s" % fn, "compile",
                              record["time"] * 1e6, total * 1e6, args)
    except Exception:
        pass


def watch_compile(jitted, args, *, fn: str, site: str, instance: str,
                  recompile: bool, signature: Sequence[tuple]):
    """A site that keeps its own AOT executables (the sharded step):
    ``jitted`` through :func:`compile_stages` for ``args``, with the
    record a watched site leaves. ``signature`` is the ``(shape,
    dtype)`` pairs the site keys its programs on. Returns (lowered,
    compiled); raises what the stages raise. For the telemetry-on path:
    the caller reads the gate."""
    t0 = time.perf_counter()
    _, lowered, compiled, stages, cache_word = compile_stages(jitted, args)
    publish({"site": site, "fn": fn, "instance": instance,
             "kind": "recompile" if recompile else "compile",
             "stages": stages, "flops": _extract_cost(compiled),
             "bytes": _extract_memory(compiled),
             "signature": [_fmt_leaf((tuple(shape), dtype, False, None))
                           for shape, dtype in signature],
             "changed": [], "time": t0, "persistent_cache": cache_word})
    return lowered, compiled


def watched_jit(fn: Callable, fn_label: str, site: str,
                arg_names: Optional[Sequence[str]] = None,
                instance: Optional[str] = None,
                static_repr: Optional[str] = None,
                exec_via_jit: bool = False,
                donate_argnums: Sequence[int] = (),
                keep_unused: bool = False) -> WatchedJit:
    """Wrap ``fn`` for watched jit execution (see module docstring)."""
    return WatchedJit(fn, fn_label, site, arg_names=arg_names,
                      instance=instance, static_repr=static_repr,
                      exec_via_jit=exec_via_jit,
                      donate_argnums=donate_argnums,
                      keep_unused=keep_unused)


# ---------------------------------------------------------------------------
# process-wide introspection
# ---------------------------------------------------------------------------
def cache_counts() -> Tuple[int, int]:
    """(live watched wrappers, total cached program signatures)."""
    ws = list(_WATCHED)
    return len(ws), sum(len(w._cache) for w in ws)


def cache_entries() -> int:
    return cache_counts()[1]


def programs() -> List[dict]:
    """Flat per-program compile records, oldest first."""
    with _PROG_LOCK:
        return list(_PROGRAMS)


def records_dropped() -> int:
    return _DROPPED[0]


def compile_seconds_total() -> float:
    """Wall seconds this process has spent compiling watched programs
    (all stages, uncapped running total). telemetry.mark_step debits
    this from the goodput numerator — a recompile storm mid-training
    is stolen step time, not useful work."""
    return _COMPILE_SECONDS[0]


def recompile_log(fn_label: Optional[str] = None) -> List[dict]:
    """Recompile records (with their attribution diffs), oldest first."""
    return [r for r in programs()
            if r["kind"] == "recompile"
            and (fn_label is None or r["fn"] == fn_label)]


def report() -> List[dict]:
    """Aggregate per-(site, fn) rows for tools/compile_report.py:
    compiles, recompiles, compile seconds, FLOPs, planned HBM bytes."""
    rows: Dict[Tuple[str, str], dict] = {}
    for r in programs():
        key = (r["site"], r["fn"])
        row = rows.get(key)
        if row is None:
            row = rows[key] = {
                "site": r["site"], "fn": r["fn"], "compiles": 0,
                "recompiles": 0, "compile_seconds": 0.0, "flops": 0.0,
                "bytes": {}, "last_signature": None}
        row["compiles"] += 1
        if r["kind"] == "recompile":
            row["recompiles"] += 1
        row["compile_seconds"] += sum(r["stages"].values())
        if r["flops"]:
            row["flops"] += r["flops"]
        for kind, nbytes in r["bytes"].items():
            row["bytes"][kind] = row["bytes"].get(kind, 0) + nbytes
        row["last_signature"] = r["signature"]
    return sorted(rows.values(),
                  key=lambda row: -row["compile_seconds"])


def _fmt_count(v: float) -> str:
    for unit, div in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if v >= div:
            return "%.2f%s" % (v / div, unit)
    return "%.0f" % v


def cache_misses() -> List[dict]:
    """The compile records JAX's persistent cache did not serve
    (``persistent_cache == "miss"``), longest ``compile`` stage first:
    the programs a warm start still compiles."""
    return sorted((r for r in programs()
                   if r.get("persistent_cache") == "miss"),
                  key=lambda r: -r["stages"].get("compile", 0.0))


_MISSES_SHOWN = 20      # a cold start misses with every program


def _render_startup() -> List[str]:
    """The start's seconds by phase and the programs that missed the
    persistent cache (docs/OBSERVABILITY.md "Start-up"); nothing where
    the process recorded no start."""
    phases = telemetry.startup_phases()
    covered = phases.pop("covered")
    if not covered:
        return []
    out = ["", "start-up to the first step, seconds by phase (exclusive):"]
    out += ["  %-14s %9.3f" % kv
            for kv in sorted(phases.items(), key=lambda kv: -kv[1])]
    out.append("  %-14s %9.3f" % ("covered", covered))
    missed = cache_misses()
    if missed:
        out.append("programs that missed the persistent cache "
                   "(compile seconds):")
        out += ["  %-40s %-24s %9.3f"
                % (r["fn"], r["instance"], r["stages"].get("compile", 0.0))
                for r in missed[:_MISSES_SHOWN]]
        rest = missed[_MISSES_SHOWN:]
        if rest:
            out.append("  ... and %d more, %.3f s together" % (
                len(rest), sum(r["stages"].get("compile", 0.0)
                               for r in rest)))
    return out


def render_report(rows: Optional[List[dict]] = None) -> str:
    """The per-program table tools/compile_report.py prints, then the
    start-up's phases and cache misses."""
    rows = report() if rows is None else rows
    out = ["%-24s %-22s %8s %9s %10s %10s %12s"
           % ("callsite", "fn", "compiles", "recompile",
              "compile_s", "flops", "hbm_bytes")]
    for r in rows:
        hbm = sum(v for k, v in r["bytes"].items() if k != "code")
        out.append("%-24s %-22s %8d %9d %10.3f %10s %12s"
                   % (r["site"], r["fn"], r["compiles"], r["recompiles"],
                      r["compile_seconds"],
                      _fmt_count(r["flops"]) if r["flops"] else "-",
                      _fmt_count(hbm) if hbm else "-"))
    return "\n".join(out + _render_startup())


def reset():
    """Drop every per-program record and per-wrapper history (test
    isolation; the wrappers themselves — and their compiled programs —
    stay, matching jax.jit's own cache lifetime)."""
    with _PROG_LOCK:
        _PROGRAMS.clear()
        _DROPPED[0] = 0
        _COMPILE_SECONDS[0] = 0.0
    for w in list(_WATCHED):
        w._recompiles = 0
        w._diff_history = []
        w._warned = False
