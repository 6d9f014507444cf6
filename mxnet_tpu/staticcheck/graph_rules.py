"""Level 2 — graph checker: post-trace, pre-execute (ISSUE 9).

Walks the jaxpr of every program compilewatch's :class:`WatchedJit`
compiles — once per new signature, on the compile MISS path, so the
hot cache-hit path pays nothing — and flags graph-level hazards that
are invisible in source but deterministic in the traced program:

``graph-f32-promotion``        a ``convert_element_type`` bf16->f32 in
                               a program whose inputs are bf16: a
                               silent upcast burning the bf16 MFU
                               budget (ROADMAP item 3). Deliberate
                               f32 accumulations (LayerNorm stats, CE
                               logsumexp) are baselined, not fixed.
``graph-host-callback``        ``pure_callback``/``io_callback``/
                               ``debug_callback`` inside a compiled
                               program: a hidden host round-trip that
                               serializes the async engine.
``graph-collective-in-eval``   psum/all_gather/... in an EVAL-mode
                               program (CachedOp instance ``*/eval``):
                               eval graphs must not pay collective
                               latency — a training-only construct
                               leaked past the mode flag.
``graph-degenerate-broadcast`` a non-scalar operand tiled >=64x into a
                               >=1M-element output: a materialization
                               bomb XLA cannot always fuse away.
``graph-nondonated-update-param`` an update/step program (fused
                               trainer step, zero.step) whose
                               parameter-shaped inputs are not
                               donated: both the old and new copy of
                               every weight are live across the
                               update — double HBM.
``graph-nondonated-serve-input`` a serving forward program
                               (``serve.forward``, ISSUE 12) whose
                               request inputs (``data%d``) are not
                               donated: the session owns those
                               staging buffers outright, so an
                               undonated one holds dead HBM across
                               every forward.

Gate: ``MXNET_STATICCHECK`` (cached; :func:`refresh` after changing
it). The hook additionally rides the compilewatch AOT path, which only
runs under ``MXNET_TELEMETRY=1`` — with telemetry off nothing is
traced through here at all. Findings are recorded process-wide
(:func:`graph_findings`), logged once per (rule, program), and carry
the program label / instance / argument names that recompile
attribution already produces.
"""
from __future__ import annotations

import collections
import logging
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .findings import (Finding, RULES, is_suppressed,
                       parse_suppressions, rule)

__all__ = ["GRAPH_RULES", "enabled", "refresh", "install",
           "check_closed_jaxpr", "graph_findings", "reset",
           "suppressed_at_eqn"]

_LOG = logging.getLogger("mxnet_tpu.staticcheck")

GRAPH_RULES = [
    rule("graph-f32-promotion", "graph", "warn",
         "bf16->f32 convert inside a bf16 program: silent upcast "
         "burning the bf16 MFU budget."),
    rule("graph-host-callback", "graph", "error",
         "Host callback primitive inside a compiled program: hidden "
         "device->host round-trip."),
    rule("graph-collective-in-eval", "graph", "error",
         "Collective communication primitive in an eval-mode "
         "program."),
    rule("graph-degenerate-broadcast", "graph", "warn",
         "Non-scalar operand tiled into a huge output: a "
         "materialization bomb."),
    rule("graph-nondonated-update-param", "graph", "warn",
         "Update program whose parameter-sized input buffers are not "
         "donated: two copies of every weight live across the "
         "update."),
    rule("graph-nondonated-serve-input", "graph", "warn",
         "Serve program whose request-input buffers are not donated: "
         "the dead staging buffer and the outputs are both live "
         "across every forward."),
]

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback_call", "callback"}
_COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "ppermute", "pbroadcast",
                     "all_gather", "all_to_all", "reduce_scatter",
                     "psum_scatter", "allreduce",
                     # what a check_vma shard_map binds them as
                     "psum_invariant", "all_gather_invariant"}
# labels of programs that perform the weight update (donation check)
_UPDATE_LABELS = ("autograd.fused_step", "zero.step", "zero.reduce")
# labels of serving forward programs (ISSUE 12): their request inputs
# — the gluon-convention data%d graph inputs — must be donated
# (CachedOp.serve_program threads donate_argnums through WatchedJit)
_SERVE_LABELS = ("serve.forward",)
import re as _re
_DATA_ARG_RE = _re.compile(r"data\d+$")
_BCAST_MIN_OUT = 1 << 20       # 1M elements
_BCAST_MIN_RATIO = 64

_LOCK = threading.Lock()
_FINDINGS: "collections.deque[Finding]" = collections.deque(maxlen=4096)
_WARNED: set = set()           # (rule, path) pairs already logged
_CHECKED = [0]                 # programs checked (introspection/tests)

_ON = [None]                   # cached MXNET_STATICCHECK gate

# Level-4 SPMD hook (spmd_rules.install sets it): called with
# (wrapper, closed_jaxpr, signature, compiled) after the Level-2 check
# on the same compile-miss path. Separate slot so MXNET_STATICCHECK
# and MXNET_STATICCHECK_SPMD gate independently.
_SPMD_HOOK: List[Optional[Any]] = [None]


def enabled() -> bool:
    on = _ON[0]
    if on is None:
        on = _resolve()
    return on


def _resolve() -> bool:
    try:
        from ..config import get as _cfg
        on = bool(_cfg("MXNET_STATICCHECK"))
    except Exception:
        on = False
    _ON[0] = on
    return on


def refresh():
    """Re-resolve the cached MXNET_STATICCHECK gate (tests/env flips)."""
    _ON[0] = None


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _short_aval(aval) -> str:
    try:
        return "%s[%s]" % (str(aval.dtype),
                           ",".join(str(s) for s in aval.shape))
    except Exception:
        return str(aval)


def _sub_jaxprs(params: Dict[str, Any]):
    """Every nested jaxpr in an eqn's params (pjit/scan/while/cond/
    custom_*), whatever key it hides under."""
    for v in params.values():
        for got in _as_jaxprs(v):
            yield got


def _as_jaxprs(v):
    import jax.extend.core as jcore
    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _as_jaxprs(item)


def _walk_eqns(jaxpr, depth=0):
    if depth > 32:
        return
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub, depth + 1)


def _nelems(aval) -> int:
    n = 1
    for s in getattr(aval, "shape", ()):
        n *= int(s)
    return n


# ---------------------------------------------------------------------------
# inline suppression for graph-level findings: a jaxpr eqn remembers
# the user source line that bound it, so the SAME `# mxlint:
# disable=<rule>` comment syntax the AST rules honor silences a graph/
# spmd finding at the line that built the offending op (HLO-derived
# findings have no source line and take the baseline instead).
# ---------------------------------------------------------------------------
_SUPP_CACHE: "collections.OrderedDict[str, tuple]" = \
    collections.OrderedDict()
_SUPP_CACHE_CAP = 256


def _eqn_frame(eqn):
    from jax._src import source_info_util as siu
    return siu.user_frame(eqn.source_info.traceback)


def suppressed_at_eqn(rule_id: str, eqn) -> bool:
    """Whether the source line that bound `eqn` carries an inline
    ``# mxlint: disable=<rule_id>`` (or its file opts out). Never
    raises; unknown/unreadable sources resolve to not-suppressed."""
    fr = _eqn_frame(eqn)
    if fr is None:
        return False
    try:
        fname = fr.file_name
        line = int(fr.start_line)
    except Exception:
        return False
    ent = _SUPP_CACHE.get(fname)
    if ent is None:
        try:
            with open(fname, encoding="utf-8") as fh:
                src = fh.read()
            ent = parse_suppressions(src) if "mxlint" in src \
                else ({}, set())
        except Exception:
            ent = ({}, set())
        _SUPP_CACHE[fname] = ent
        while len(_SUPP_CACHE) > _SUPP_CACHE_CAP:
            _SUPP_CACHE.popitem(last=False)
    return is_suppressed(rule_id, line, ent[0], ent[1])


def check_closed_jaxpr(closed_jaxpr, label: str,
                       instance: Optional[str] = None,
                       arg_names: Optional[Sequence[str]] = None,
                       donated: Sequence[int] = (),
                       eval_mode: Optional[bool] = None
                       ) -> List[Finding]:
    """Run every graph rule over one ClosedJaxpr. `label`/`instance`
    name the program in findings (the same names compilewatch's
    recompile attribution uses); `arg_names` lets a top-level finding
    name the offending input; `eval_mode` defaults to sniffing an
    ``*/eval`` instance suffix."""
    jaxpr = closed_jaxpr.jaxpr
    path = "%s (%s)" % (label, instance) if instance and \
        instance != label else label
    if eval_mode is None:
        eval_mode = bool(instance) and instance.endswith("/eval")

    def name_of(var) -> Optional[str]:
        try:
            i = jaxpr.invars.index(var)
        except (ValueError, AttributeError):
            return None
        if arg_names and i < len(arg_names):
            return arg_names[i]
        return "arg%d" % i

    def mk(rule_id: str, message: str, text: str) -> Finding:
        return Finding(rule=rule_id, level="graph",
                       severity=RULES[rule_id].severity, path=path,
                       line=0, message=message, text=text)

    out: List[Finding] = []
    bf16_program = any(str(getattr(v.aval, "dtype", "")) == "bfloat16"
                       for v in jaxpr.invars)
    promos: Dict[str, int] = {}
    for eqn in _walk_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "convert_element_type" and bf16_program:
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if str(getattr(src, "dtype", "")) == "bfloat16" \
                    and str(getattr(dst, "dtype", "")) == "float32":
                if suppressed_at_eqn("graph-f32-promotion", eqn):
                    continue
                arg = name_of(eqn.invars[0])
                key = "convert %s->%s%s" % (
                    _short_aval(src), _short_aval(dst),
                    " of input %r" % arg if arg else "")
                promos[key] = promos.get(key, 0) + 1
        elif prim in ("dot_general", "conv_general_dilated") \
                and bf16_program:
            # no convert eqn needed: a mixed bf16/f32 contraction runs
            # the whole MXU pass in f32 — the exact "silently burn the
            # bf16 MFU budget" upcast of ROADMAP item 3
            dts = {str(getattr(v.aval, "dtype", ""))
                   for v in eqn.invars}
            if "bfloat16" in dts and "float32" in dts:
                if suppressed_at_eqn("graph-f32-promotion", eqn):
                    continue
                args = [name_of(v) for v in eqn.invars]
                key = "mixed bf16/f32 %s %s%s" % (
                    prim,
                    "x".join(_short_aval(v.aval) for v in eqn.invars),
                    " (inputs %s)" % [a for a in args if a]
                    if any(args) else "")
                promos[key] = promos.get(key, 0) + 1
        elif prim in _CALLBACK_PRIMS:
            if suppressed_at_eqn("graph-host-callback", eqn):
                continue
            cb = eqn.params.get("callback")
            out.append(mk("graph-host-callback",
                          "host callback %r inside compiled program"
                          % (getattr(cb, "__name__", None) or prim),
                          "%s %s" % (prim, [_short_aval(v.aval)
                                            for v in eqn.invars])))
        elif prim in _COLLECTIVE_PRIMS and eval_mode:
            if suppressed_at_eqn("graph-collective-in-eval", eqn):
                continue
            axes = eqn.params.get("axes") or eqn.params.get(
                "axis_name") or eqn.params.get("axis_index_groups")
            out.append(mk("graph-collective-in-eval",
                          "collective %r over axes %r in an eval-mode "
                          "program" % (prim, axes),
                          "%s %s" % (prim, [_short_aval(v.aval)
                                            for v in eqn.invars])))
        elif prim == "broadcast_in_dim":
            n_in = _nelems(eqn.invars[0].aval)
            n_out = _nelems(eqn.outvars[0].aval)
            if n_in > 1 and n_out >= _BCAST_MIN_OUT \
                    and n_out >= n_in * _BCAST_MIN_RATIO:
                if suppressed_at_eqn("graph-degenerate-broadcast", eqn):
                    continue
                out.append(mk(
                    "graph-degenerate-broadcast",
                    "broadcast tiles %s into %s (%dx)" % (
                        _short_aval(eqn.invars[0].aval),
                        _short_aval(eqn.outvars[0].aval),
                        n_out // max(1, n_in)),
                    "broadcast_in_dim %s->%s" % (
                        _short_aval(eqn.invars[0].aval),
                        _short_aval(eqn.outvars[0].aval))))
    for key, n in sorted(promos.items()):
        out.append(mk("graph-f32-promotion",
                      "silent bf16->f32 promotion (x%d): %s" % (n, key),
                      key))

    if _is_update_label(label, instance):
        out.extend(_check_donation(jaxpr, donated, mk))
    if _is_serve_label(label, instance):
        out.extend(_check_serve_donation(jaxpr, donated, arg_names, mk))
    return out


def _is_update_label(label: str, instance: Optional[str]) -> bool:
    for cand in (label, instance or ""):
        if cand in _UPDATE_LABELS:
            return True
    return False


def _is_serve_label(label: str, instance: Optional[str]) -> bool:
    for cand in (label, instance or ""):
        if cand in _SERVE_LABELS:
            return True
    return False


def _check_serve_donation(jaxpr, donated, arg_names, mk) -> List[Finding]:
    """graph-nondonated-serve-input: every request input of a serve
    program (identified by the gluon ``data%d`` graph-input naming
    convention — weights keep their parameter names and must NOT be
    donated, the trainer still owns them) must be in the donated set.
    Positional, not shape-matched like the update rule: serve inputs
    (tokens) rarely share an aval with the outputs (logits)."""
    donated = set(donated or ())
    missing: List[str] = []
    bytes_held = 0
    for i, v in enumerate(jaxpr.invars):
        name = (arg_names[i] if arg_names and i < len(arg_names)
                else "arg%d" % i)
        if not _DATA_ARG_RE.match(name) or i in donated:
            continue
        missing.append(name)
        try:
            bytes_held += _nelems(v.aval) * v.aval.dtype.itemsize
        except Exception:
            pass
    if missing:
        return [mk("graph-nondonated-serve-input",
                   "request input(s) %s (%d bytes) not donated in a "
                   "serve program — the dead staging buffer stays "
                   "live across every forward"
                   % (", ".join(missing), bytes_held),
                   "undonated=%s bytes=%d" % (",".join(missing),
                                              bytes_held))]
    return []


def _check_donation(jaxpr, donated, mk) -> List[Finding]:
    donated = set(donated or ())
    out_avals = {}
    for v in jaxpr.outvars:
        key = (tuple(getattr(v.aval, "shape", ())),
               str(getattr(v.aval, "dtype", "")))
        out_avals[key] = out_avals.get(key, 0) + 1

    def akey(v):
        return (tuple(getattr(v.aval, "shape", ())),
                str(getattr(v.aval, "dtype", "")))

    # donated inputs consume their matching output slots FIRST — only
    # outputs left over after that can still be alias targets an
    # undonated input failed to claim
    for i, v in enumerate(jaxpr.invars):
        if i in donated and out_avals.get(akey(v), 0) > 0:
            out_avals[akey(v)] -= 1
    undonated = 0
    bytes_held = 0
    for i, v in enumerate(jaxpr.invars):
        if i in donated:
            continue
        key = akey(v)
        if out_avals.get(key, 0) > 0:
            out_avals[key] -= 1
            undonated += 1
            try:
                bytes_held += _nelems(v.aval) * v.aval.dtype.itemsize
            except Exception:
                pass
    if undonated:
        return [mk("graph-nondonated-update-param",
                   "%d parameter-sized input buffer(s) (%d bytes) not "
                   "donated in an update program — old and new copies "
                   "are both live across the update"
                   % (undonated, bytes_held),
                   "undonated=%d bytes=%d" % (undonated, bytes_held))]
    return []


# ---------------------------------------------------------------------------
# the compilewatch hook (one gate read on the compile MISS path only)
# ---------------------------------------------------------------------------
def _donated_invars(wrapper, traced) -> Sequence[int]:
    """The jaxpr inputs a watched program donates. ``donate_argnums``
    counts arguments and the jaxpr's inputs are their leaves: an
    argument that is a list of buffers (the fused step's owned group)
    donates every one of them."""
    try:
        import jax
        return [i for i, info in enumerate(
            jax.tree_util.tree_leaves(traced.args_info)) if info.donated]
    except Exception:
        return getattr(wrapper, "donate_argnums", ()) or ()


def _hook(wrapper, traced, signature, compiled=None) -> None:
    """Called by WatchedJit._compile_and_call once per new signature.
    Any failure in here must never poison the compile (the caller
    swallows, but be cheap about it too). `compiled` is the AOT
    executable (None when the AOT path degraded) — the Level-2 jaxpr
    rules never touch it; the Level-4 SPMD hook parses its HLO."""
    try:
        cj = traced.jaxpr
    except Exception:
        cj = None
    if enabled() and cj is not None:
        found = check_closed_jaxpr(
            cj, wrapper.fn_label, instance=wrapper.instance,
            arg_names=wrapper._arg_names,
            donated=_donated_invars(wrapper, traced))
        with _LOCK:
            _CHECKED[0] += 1
            for f in found:
                f.extra["signature"] = signature
                _FINDINGS.append(f)
                wkey = (f.rule, f.path)
                if wkey not in _WARNED:
                    _WARNED.add(wkey)
                    _LOG.warning("staticcheck: %s", f.render())
        try:
            from .. import telemetry
            for f in found:
                telemetry.counter("mx_staticcheck_findings_total",
                                  rule=f.rule).inc()
        except Exception:
            pass
    sp = _SPMD_HOOK[0]
    if sp is not None:
        try:
            sp(wrapper, cj, signature, compiled)
        except Exception:
            pass


def install():
    """Register the graph hook with compilewatch (idempotent)."""
    from .. import compilewatch
    compilewatch._GRAPH_HOOK[0] = _hook


def graph_findings() -> List[Finding]:
    with _LOCK:
        return list(_FINDINGS)


def programs_checked() -> int:
    return _CHECKED[0]


def reset():
    with _LOCK:
        _FINDINGS.clear()
        _WARNED.clear()
        _CHECKED[0] = 0
    _SUPP_CACHE.clear()
