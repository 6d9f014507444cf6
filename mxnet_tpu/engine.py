"""Execution engine facade — async semantics over XLA's async dispatch.

Ref: src/engine/ :: Engine::PushAsync / WaitForVar / WaitForAll,
threaded_engine_perdevice.cc, naive_engine.cc (MXNET_ENGINE_TYPE).

On TPU the reference's hand-built dependency scheduler is subsumed by the
PJRT runtime: every XLA execution is dispatched asynchronously and the
runtime already orders executions by buffer dependencies, overlapping
host Python with device compute. What this module keeps is the *semantic
surface* the reference exposes:

- ``push(fn)``: run a closure under engine bookkeeping (profiler hooks).
- ``wait_for_var(arr)`` == ``NDArray.wait_to_read`` — block until the
  buffer is materialized; any XLA error raised during async execution
  surfaces HERE, matching the reference's exception-at-wait contract
  (threaded_engine.cc on-complete exception_ptr;
  tests/python/unittest/test_exc_handling.py).
- ``wait_for_all()`` — barrier over everything dispatched so far.
- ``MXNET_ENGINE_TYPE=NaiveEngine`` — synchronous mode: every op blocks
  on completion immediately (deterministic debugging, same env var).

Host-side async work (custom ops, IO stages, checkpoint writers) that
XLA cannot see runs on the NATIVE C++ dependency engine
(mxnet_tpu/native/engine.cc — the ThreadedEngine rebuild: per-var
pending read/write queues, worker pool, exception captured on written
vars and rethrown at wait). ``push_async(fn, read_vars, write_vars)``
is the Engine::PushAsync surface over it.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
import weakref

import jax

from .base import MXNetError, getenv
from . import profiler
from . import telemetry
from . import tracing

__all__ = ["Engine", "engine", "NativeDependencyEngine"]

# Level-3 race-detector hook (staticcheck/race.py): the RaceChecker is
# installed here ONLY while MXNET_ENGINE_RACE_CHECK is on, so the
# disabled-path cost at every touch point is one `is None` check
# (tools/staticcheck_micro.py gates it at <5% on push+wait).
_RACE_HOOK: list = [None]


def _tele_live() -> bool:
    """Whether engine ops should be timed at all: telemetry registry on
    OR the chrome-trace profiler running (spans feed both)."""
    return telemetry.enabled() or profiler.state() == "run"


def _metric_label(label: str) -> str:
    """Histogram label for an op: the part before ':' — op labels embed
    instance detail (e.g. 'checkpoint_write:run-0003.params') that
    would make per-label series unbounded."""
    return label.split(":", 1)[0]


def _enqueue_site() -> str:
    """file:line of the frame that pushed the op (skipping engine
    internals) — cheap (no source IO), recorded per push so an async
    error can name WHERE the poisoned work was scheduled."""
    try:
        f = sys._getframe(2)
        while f is not None and f.f_code.co_filename == __file__:
            f = f.f_back
        if f is None:
            return "<unknown>"
        return "%s:%d" % (f.f_code.co_filename, f.f_lineno)
    except Exception:
        return "<unknown>"


class NativeDependencyEngine:
    """ctypes wrapper over the C++ engine (MXEngine* C ABI).

    Error contract (the reference's exception-at-wait, upgraded): an
    exception raised inside an async op is captured as the ORIGINAL
    Python exception object together with the op's label and enqueue
    site, and re-raised — same type, message augmented with that
    context — at the next ``wait_for_var``/``wait_for_all`` touching a
    poisoned var. Ops depending on a poisoned var fail fast without
    running (poison propagates along dependency edges). A watchdog
    (``MXNET_ENGINE_WATCHDOG`` seconds) turns a hung wait into a
    diagnosable MXNetError listing every pending op's label/enqueue
    site instead of blocking forever.
    """

    def __init__(self, num_workers: int = 2, naive: bool = False):
        import ctypes
        from . import native as native_mod
        lib = native_mod.load_engine_lib()
        self._lib = lib
        self._ct = ctypes
        self._h = lib.MXEngineCreate(num_workers, int(naive))
        # err_out must be c_void_p (not c_char_p: ctypes would hand the
        # callback an immutable bytes copy instead of the writable buf)
        self._cb_type = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p,
                                         ctypes.c_void_p, ctypes.c_int)
        # ONE callback thunk for the engine's whole lifetime, dispatching
        # by the native ctx token: no libffi closure is ever freed while
        # a worker thread could still be inside its native epilogue (the
        # use-after-free window a per-op-closure design has). Python op
        # closures live in _fns and are popped under the GIL inside the
        # dispatch itself — safe, nothing native references them.
        self._fns = {}
        self._meta = {}        # token -> (label, site, reads, writes,
        #                        t_queued, gauge_inc, on_done, tctx);
        #                        lives until the op completes (watchdog
        #                        diagnostics + error attribution +
        #                        telemetry spans + completion callback +
        #                        distributed-trace tagging)
        self._var_errors = {}  # var -> error record (original exception,
        #                        label, site, propagation chain)
        self._live_lock = threading.Lock()
        self._next = 1  # ctypes maps ctx NULL to None; avoid token 0

        def _dispatch(ctx_token, err_out, err_cap):
            with self._live_lock:
                fn = self._fns.pop(ctx_token, None)
                meta = self._meta.get(ctx_token)
                label, site, reads, writes, t_queued, ginc, on_done, \
                    tctx = meta if meta else \
                    ("<unlabeled>", "<unknown>", (), (), None, False,
                     None, None)
                upstream = None
                for rv in reads:
                    rec = self._var_errors.get(rv)
                    if rec is not None:
                        upstream = rec
                        break
            # t_queued non-None == instrumentation was live at push;
            # the queued->running->done span times both stages
            t_run = time.perf_counter() if t_queued is not None else None
            rh = _RACE_HOOK[0]
            race_tok = ctx_token if (rh is not None
                                     and rh.watching(ctx_token)) else None
            if race_tok is not None:
                # publish the RUNNING op so NDArray touch points
                # (EngineGate.force, _set_jax via _race_write) can be
                # checked against its declared read/write sets
                _EXEC_TLS.race_token = ctx_token
            rc = 0
            err_text = None
            if upstream is not None:
                # fail fast: a dependency is poisoned — do NOT run the
                # op; propagate the original error to our write vars
                rc = 1
                rec = dict(upstream)
                rec["via"] = list(rec.get("via") or ()) + [label]
                err_text = ("not run: upstream engine op %r failed "
                            "(%s: %s)" % (rec["label"],
                                          type(rec["exc"]).__name__,
                                          rec["exc"]))
                self._record_error(writes, rec)
            else:
                try:
                    if fn is None:
                        raise MXNetError("engine: unknown op token %r"
                                         % (ctx_token,))
                    fn()
                    if writes:
                        # a successful write establishes fresh data:
                        # drop any stale poison record so later readers
                        # are not failed fast on recovered vars
                        with self._live_lock:
                            for wv in writes:
                                self._var_errors.pop(wv, None)
                except BaseException as e:
                    rc = 1
                    # "consumed" is a shared box: propagated copies of
                    # this record reference the same cell, so the error
                    # surfaces at most ONCE through wait_for_all no
                    # matter how many vars it poisoned
                    rec = {"exc": e, "label": label, "site": site,
                           "via": [], "consumed": [False]}
                    err_text = "%s: %s [engine op %r pushed at %s]" % (
                        type(e).__name__, e, label, site)
                    self._record_error(writes, rec)
                    try:
                        from . import guardrails
                        guardrails.emit("engine_error", label=label,
                                        site=site,
                                        error="%s: %s"
                                        % (type(e).__name__, e))
                    except Exception:
                        pass
            if race_tok is not None:
                _EXEC_TLS.race_token = None
            if rh is not None:
                # on_done runs for EVERY completed op while the hook
                # is installed, not only watched ones: a long-lived op
                # whose happens-before record was FIFO-evicted from
                # the checker (watching() False) must still clear its
                # collective-in-flight mark, or every later collective
                # push false-positives against a phantom op
                try:
                    rh.on_done(ctx_token)
                except Exception:
                    pass
            with self._live_lock:
                self._meta.pop(ctx_token, None)
            if t_run is not None:
                try:
                    self._record_op_done(label, site, t_queued, t_run,
                                         bool(rc), ginc, tctx)
                except Exception:     # observability must never poison
                    pass              # the op's result
            if on_done is not None:
                # completion callback (ISSUE 12: the serve scheduler's
                # continuous-batching in-flight accounting rides here —
                # a finished batch frees its in-flight slot and wakes
                # the batch assembler). Runs AFTER the op's own
                # bookkeeping, on the worker thread; a callback failure
                # must never poison the op's recorded result.
                try:
                    on_done(bool(rc))
                except Exception:
                    pass
            if rc:
                try:
                    # NUL-terminate explicitly; truncate on a safe
                    # boundary (avoid splitting a UTF-8 sequence)
                    msg = (err_text or "engine op failed") \
                        .encode("utf-8", "replace")[:err_cap - 1]
                    ctypes.memmove(err_out, msg + b"\x00", len(msg) + 1)
                except Exception:
                    pass
            return rc

        self._cb = self._cb_type(_dispatch)

    def _record_error(self, writes, rec):
        with self._live_lock:
            for wv in writes:
                self._var_errors.setdefault(wv, rec)

    @staticmethod
    def _record_op_done(label, site, t_queued, t_run, failed, ginc,
                        tctx=None):
        """Close out one op's queued->running->done telemetry: two
        chrome-trace spans (queue wait + execution, category 'engine')
        and, when the registry is on, per-label latency histograms plus
        the pending gauge / error counter. `ginc` records whether the
        push incremented the pending gauge — the dec pairs with THAT
        decision, not with the current enabled() value, so toggling
        telemetry with ops in flight cannot skew the gauge. The dec
        runs FIRST: the caller swallows any exception from this
        method, and a profiler failure after the dec loses only trace
        events, not the gauge's balance (a stuck-high pending count is
        the heartbeat's hang indicator — it must not false-alarm)."""
        t_done = time.perf_counter()
        if ginc:
            telemetry.gauge("mx_engine_pending_ops").dec()
        pargs = {"site": site}
        if tctx is not None:
            pargs["trace"] = tctx.trace_id
        profiler.record_event("engine::%s (queued)" % label, "engine",
                              t_queued * 1e6, (t_run - t_queued) * 1e6,
                              pargs)
        profiler.record_event("engine::%s" % label, "engine",
                              t_run * 1e6, (t_done - t_run) * 1e6,
                              dict(pargs, failed=failed))
        if tctx is not None:
            # distributed-trace copy on the WALL clock (perf_counter
            # stamps anchored at now): replica engine spans must be
            # comparable across processes after skew correction
            now_w = time.time()
            tracing.record_span("engine::%s" % label, "engine",
                                now_w - (t_done - t_run), now_w,
                                ctx=tctx,
                                args={"site": site, "failed": failed,
                                      "queued_us":
                                      (t_run - t_queued) * 1e6})
        if telemetry.enabled():
            ml = _metric_label(label)
            telemetry.histogram("mx_engine_queue_seconds",
                                label=ml).observe(t_run - t_queued)
            telemetry.histogram("mx_engine_op_seconds",
                                label=ml).observe(t_done - t_run)
            if failed:
                telemetry.counter("mx_engine_op_errors_total",
                                  label=ml).inc()

    def new_var(self) -> int:
        return self._lib.MXEngineNewVar(self._h)

    def delete_var(self, var: int) -> bool:
        """True if deleted; False if the var still has pending ops
        (caller may retry after a wait)."""
        ok = self._lib.MXEngineDeleteVar(self._h, var) == 0
        if ok:
            with self._live_lock:
                self._var_errors.pop(var, None)
        return ok

    def push_async(self, fn, read_vars=(), write_vars=(), label=None,
                   on_done=None, collective=None):
        """Schedule `fn()` once all read/write dependencies clear.
        `label` names the op in error context and watchdog diagnostics
        (defaults to the callable's __name__). A raised exception
        poisons the written vars; the ORIGINAL exception re-raises with
        the label + enqueue-site context at wait_for_var/wait_for_all —
        the reference's exception-at-wait contract, with attribution.
        `on_done(failed: bool)`, if given, runs on the worker thread
        after the op completes (success or failure) — the completion
        hook continuous-batching schedulers use for in-flight
        accounting; its exceptions are swallowed.
        `collective`, if given, declares that `fn` executes a compiled
        MULTI-DEVICE collective program: a dict with the program label
        under 'program' and the identity of the serializing lock the
        caller holds around the execution under 'lock' (None = no
        lock). Read only by the Level-3/4 collective-interleave check
        (staticcheck/race.py, ISSUE 15); with the race hook off it
        costs nothing."""
        ct = self._ct
        if label is None:
            label = getattr(fn, "__name__", None) or "<unlabeled>"
        site = _enqueue_site()
        from . import faultinject
        if faultinject.active():
            if read_vars and faultinject.should_fail("engine_dep_drop"):
                # Level-3 validation (staticcheck/race.py): silently
                # drop one DECLARED read edge — the op still runs, but
                # its ordering against that producer is now a
                # scheduling accident, exactly the bug class the race
                # checker must name (two ops + the shared handle)
                read_vars = tuple(read_vars)[1:]
            if collective is not None \
                    and collective.get("lock") is not None \
                    and faultinject.should_fail(
                        "engine_collective_overlap"):
                # Level-4 validation (ISSUE 15): strip the
                # serializing-lock sanction from this collective push
                # — the REAL execution stays lock-protected (no actual
                # deadlock risk), but the checker now sees the exact
                # shape of the PR-12 serve hazard and must name both
                # programs deterministically
                collective = dict(collective, lock=None)
            real_fn = fn

            def fn(real_fn=real_fn, label=label):
                faultinject.maybe_fail(
                    "engine_op", msg="injected fault: engine_op %r" % label)
                real_fn()
        t_queued = None
        ginc = False
        if _tele_live():
            t_queued = time.perf_counter()
            if telemetry.enabled():
                ml = _metric_label(label)
                telemetry.counter("mx_engine_ops_total", label=ml).inc()
                telemetry.gauge("mx_engine_pending_ops").inc()
                ginc = True
        tctx = None
        if tracing.active():
            # sampled ambient context at push time tags this op's
            # completion span with the remote trace (the replica binds
            # the wire context around Scheduler.submit)
            tctx = tracing.current()
            if tctx is not None and not tctx.sampled:
                tctx = None
            if tctx is not None and t_queued is None:
                t_queued = time.perf_counter()
        with self._live_lock:
            token = self._next
            self._next += 1
            self._fns[token] = fn
            self._meta[token] = (label, site, tuple(read_vars),
                                 tuple(write_vars), t_queued, ginc,
                                 on_done, tctx)
        rh = _RACE_HOOK[0]
        if rh is not None:
            # happens-before record BEFORE the native push makes the
            # op runnable — a worker may execute (and touch) it
            # immediately after MXEnginePushAsync returns
            rh.on_push(token, label, site, read_vars, write_vars,
                       collective=collective)
        r = (ct.c_uint64 * max(1, len(read_vars)))(*read_vars)
        w = (ct.c_uint64 * max(1, len(write_vars)))(*write_vars)
        rc = self._lib.MXEnginePushAsync(
            self._h, ct.cast(self._cb, ct.c_void_p),
            ct.c_void_p(token),
            r, len(read_vars), w, len(write_vars))
        if rc != 0:
            with self._live_lock:
                self._fns.pop(token, None)
                self._meta.pop(token, None)
            if ginc:
                telemetry.gauge("mx_engine_pending_ops").dec()
            raise MXNetError(self._lib.MXGetLastError().decode("utf-8", "replace"))

    # ------------------------------------------------------------------
    def _pop_error(self, var):
        with self._live_lock:
            return self._var_errors.pop(var, None)

    @staticmethod
    def _reraise(rec):
        """Re-raise the ORIGINAL exception with op label + enqueue-site
        context (type preserved; original chained as __cause__)."""
        rec.get("consumed", [False])[0] = True
        exc = rec["exc"]
        ctx = "[engine op %r pushed at %s%s]" % (
            rec["label"], rec["site"],
            "; propagated through %s" % rec["via"] if rec.get("via")
            else "")
        try:
            new = type(exc)("%s %s" % (exc, ctx))
        except Exception:
            new = MXNetError("%s: %s %s"
                             % (type(exc).__name__, exc, ctx))
        raise new from exc

    def pending_ops(self):
        """Snapshot of not-yet-completed ops: [(label, site, reads,
        writes, t_queued, gauge_inc, on_done, tctx)] — the watchdog's
        diagnostic dump (t_queued is a perf_counter stamp, or None when
        instrumentation was off at push)."""
        with self._live_lock:
            return list(self._meta.values())

    def _watchdog_deadline(self):
        try:
            from .config import get as _cfg
            return float(_cfg("MXNET_ENGINE_WATCHDOG"))
        except Exception:
            return 0.0

    def _blocking_wait(self, call, what):
        """Run a blocking C wait, optionally under the engine watchdog:
        past the deadline, dump every pending op's label/enqueue-site
        and raise instead of hanging forever."""
        deadline = self._watchdog_deadline()
        if not deadline or deadline <= 0:
            return call()
        box = {}
        done = threading.Event()

        def _run():
            try:
                box["rc"] = call()
            except BaseException as e:   # pragma: no cover - ctypes
                box["err"] = e
            finally:
                done.set()

        t = threading.Thread(target=_run, daemon=True,
                             name="mx-engine-wait")
        t.start()
        if not done.wait(deadline):
            pending = self.pending_ops()
            diag = "\n".join(
                "  op %r (reads=%s writes=%s) pushed at %s"
                % (lbl, list(rd), list(wr), st)
                for lbl, st, rd, wr, *_tq in pending) or "  (none known)"
            try:
                from . import guardrails
                guardrails.emit("watchdog", where="engine", wait=what,
                                deadline=deadline,
                                pending=[p[0] for p in pending])
            except Exception:
                pass
            raise MXNetError(
                "engine watchdog: wait on %s exceeded %.1fs "
                "(MXNET_ENGINE_WATCHDOG); pending op(s):\n%s"
                % (what, deadline, diag))
        if "err" in box:
            raise box["err"]
        return box.get("rc", 0)

    def wait_for_var(self, var: int):
        rc = self._blocking_wait(
            lambda: self._lib.MXEngineWaitForVar(self._h, var),
            "var %d" % var)
        if rc != 0:
            rec = self._pop_error(var)
            if rec is not None:
                self._reraise(rec)
            raise MXNetError(self._lib.MXGetLastError().decode("utf-8", "replace"))

    def wait_for_all(self):
        """Barrier over every pushed op; the first unconsumed async
        error (error-at-wait) re-raises here with its op context."""
        self._blocking_wait(
            lambda: self._lib.MXEngineWaitForAll(self._h), "all")
        with self._live_lock:
            if not self._var_errors:
                return
            # errors already surfaced at a wait_for_var (or an earlier
            # wait_for_all) must not re-raise here — rethrown once
            recs = [r for r in self._var_errors.values()
                    if not r.get("consumed", [False])[0]]
            self._var_errors.clear()
        if recs:
            self._reraise(recs[0])

    def close(self):
        if self._h:
            # drain without raising: close() must always release the
            # native handle, even with unconsumed poisoned vars
            self._lib.MXEngineWaitForAll(self._h)
            self._lib.MXEngineFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class Engine:
    def __init__(self):
        self._naive = getenv("MXNET_ENGINE_TYPE", "") == "NaiveEngine"
        # Ring of recently dispatched buffers so wait_for_all() has a
        # bounded set to block on (PJRT has no global barrier API).
        self._recent = collections.deque(maxlen=4096)
        self._lock = threading.Lock()
        self._bulk_depth = 0

    @property
    def is_naive(self) -> bool:
        return self._naive

    def set_naive(self, naive: bool):
        self._naive = naive

    def on_dispatch(self, buf):
        """Record an async-dispatched jax.Array (called by ndarray layer)."""
        with self._lock:
            self._recent.append(weakref.ref(buf))
        if self._naive:
            try:
                jax.block_until_ready(buf)
            except Exception:
                # naive mode surfaces errors synchronously, like NaiveEngine
                raise

    def wait_for_var(self, buf):
        """Block until buffer ready; async errors re-raise here."""
        return jax.block_until_ready(buf)

    def wait_for_all(self):
        with self._lock:
            refs, self._recent = list(self._recent), collections.deque(maxlen=4096)
        for r in refs:
            buf = r()
            if buf is not None:
                jax.block_until_ready(buf)


_ENGINE = Engine()


def engine() -> Engine:
    return _ENGINE


# ---------------------------------------------------------------------------
# production native-engine instance + NDArray gating
#
# Host-side async work XLA cannot see — custom-op Python callbacks,
# checkpoint file writes, native-IO -> device_put hand-off — runs on ONE
# shared C++ dependency engine (native/engine.cc), so "every mutation
# flows through the engine" (SURVEY §1 L2) holds for the host side too.
# ---------------------------------------------------------------------------
_NATIVE = None
_NATIVE_LOCK = threading.Lock()
_NATIVE_FAILED = [False]
_DEFERRED_VARS: list = []
_EXEC_TLS = threading.local()    # write-vars of the op running HERE


def native_engine() -> NativeDependencyEngine:
    """The process-wide native dependency engine (lazily created).
    Worker count: MXNET_CUSTOM_OP_NUM_THREADS (custom-op contract) or
    MXNET_CPU_WORKER_NTHREADS; MXNET_ENGINE_TYPE=NaiveEngine makes every
    push execute synchronously (determinism/debug)."""
    global _NATIVE
    with _NATIVE_LOCK:
        if _NATIVE is None:
            workers = int(getenv("MXNET_CUSTOM_OP_NUM_THREADS",
                                 getenv("MXNET_CPU_WORKER_NTHREADS", "2")))
            _NATIVE = NativeDependencyEngine(
                num_workers=max(1, workers),
                naive=getenv("MXNET_ENGINE_TYPE", "") == "NaiveEngine")
        return _NATIVE


def native_or_none():
    """native_engine(), or None when the C++ library cannot be built in
    this environment — callers fall back to synchronous execution (the
    pre-engine behavior) instead of failing."""
    if _NATIVE_FAILED[0]:
        return None
    try:
        return native_engine()
    except Exception as e:
        _NATIVE_FAILED[0] = True
        # say so ONCE: silently losing async checkpoints/custom-op
        # dispatch makes failures elsewhere (e.g. a slow save stalling
        # the step loop) undiagnosable
        import warnings
        warnings.warn(
            "native dependency engine unavailable (%s: %s); host-side "
            "async work (checkpoint writes, custom ops) will run "
            "synchronously" % (type(e).__name__, e), RuntimeWarning)
        return None


def native_wait_all():
    """Barrier over the native engine too (part of mx.nd.waitall)."""
    if _NATIVE is not None:
        _NATIVE.wait_for_all()


def push_gated(fn, write_var, read_vars=(), label=None):
    """push_async with the executing-op write set published in TLS, so
    an op reading its OWN gated outputs (legal in reference CustomOp
    forward: outputs are pre-filled writable buffers) does not deadlock
    on its own var."""
    def wrapped(fn=fn, wv=(write_var,)):
        prev = getattr(_EXEC_TLS, "vars", ())
        _EXEC_TLS.vars = wv
        try:
            fn()
        finally:
            _EXEC_TLS.vars = prev
    native_engine().push_async(wrapped, read_vars=read_vars,
                               write_vars=(write_var,),
                               label=label or getattr(fn, "__name__", None))


class EngineGate:
    """NDArray._pending-compatible gate onto a native engine var: an
    array whose value a native-engine op produces carries
    ``_pending = (gate, slot, aval)``; the first value read calls
    ``force()``, which blocks on the var and re-raises any exception the
    op recorded (the reference's error-at-wait contract,
    threaded_engine.cc exception_ptr). The var is freed when the gate
    dies (deferred-retried if the op is still in flight)."""

    __slots__ = ("var", "arrays", "__weakref__")

    def __init__(self, var, arrays=()):
        self.var = var
        self.arrays = list(arrays)
        weakref.finalize(self, _release_var, var)

    def force(self):
        if self.var in getattr(_EXEC_TLS, "vars", ()):
            return   # the producing op itself reads its output buffer
        native_engine().wait_for_var(self.var)   # raises if poisoned
        # success: clear gates (arrays already hold their written bufs)
        for a in self.arrays:
            if a is not None and a._pending is not None \
                    and a._pending[0] is self:
                a._pending = None


def _race_read(arr):
    """Level-3 read touch (called by NDArray._jax behind an inline
    ``_RACE_HOOK[0] is not None`` gate): an op reading an array whose
    value an engine op produced must be ordered after that producer by
    a declared edge. The binding rides ``_race_var`` — stamped at
    :func:`gate_arrays` and PERSISTENT past gate clearing, so the
    hazard is caught on every schedule, not only when the racy
    interleaving actually happens (the whole point: the flake becomes
    deterministic)."""
    rh = _RACE_HOOK[0]
    if rh is None:
        return
    tok = getattr(_EXEC_TLS, "race_token", None)
    if tok is None:
        return              # main-thread read: ordering is the wait
    var = getattr(arr, "_race_var", None)
    if var is not None:
        rh.on_touch(tok, "read", var, (arr,))


def _race_write(arr):
    """Level-3 write touch (called by NDArray._set_jax behind an
    inline ``_RACE_HOOK[0] is not None`` gate): an op rebinding a
    buffer must have declared the array's engine var in its write set.
    A MAIN-thread rebind instead clears the binding — the mutation is
    host-synchronous, later reads are ordered by program order."""
    rh = _RACE_HOOK[0]
    if rh is None:
        return
    tok = getattr(_EXEC_TLS, "race_token", None)
    var = getattr(arr, "_race_var", None)
    if tok is None:
        if var is not None:
            arr._race_var = None
        return
    rh.on_touch(tok, "write", var, (arr,))


def _release_var(var):
    """Gate finalizer: delete the var, deferring when the op is still
    in flight (delete retried on the next gate creation)."""
    try:
        if _NATIVE is None:
            return
        if not _NATIVE.delete_var(var):
            with _NATIVE_LOCK:
                _DEFERRED_VARS.append(var)
    except Exception:
        pass


def _drain_deferred_vars():
    if not _DEFERRED_VARS or _NATIVE is None:
        return
    with _NATIVE_LOCK:
        pend, _DEFERRED_VARS[:] = list(_DEFERRED_VARS), []
    for v in pend:
        try:
            if not _NATIVE.delete_var(v):
                with _NATIVE_LOCK:
                    _DEFERRED_VARS.append(v)
        except Exception:
            pass


def gate_arrays(arrays, avals):
    """Create an engine var + gate and mark `arrays` pending on it.
    Returns (var, gate); the caller pushes the producing op with
    write_vars=(var,) — use push_gated."""
    _drain_deferred_vars()
    var = native_engine().new_var()
    gate = EngineGate(var, arrays)
    race_on = _RACE_HOOK[0] is not None
    for i, (a, aval) in enumerate(zip(arrays, avals)):
        a._pending = (gate, i, aval)
        if race_on:
            # persistent array->var binding for the race detector:
            # survives the gate so an undeclared read is caught even
            # when the producer already finished (see _race_read)
            a._race_var = var
    return var, gate


def read_deps(arrays):
    """Engine vars of inputs still gated on a native-engine op — the
    read-dependency set for a consumer push."""
    deps = []
    for a in arrays:
        p = getattr(a, "_pending", None)
        if p is not None and isinstance(p[0], EngineGate):
            deps.append(p[0].var)
    return deps


def pin_reads(arrays, gate):
    """Register `gate` (a pushed op's write gate) as a pending READER of
    each engine-gated input, so a later main-thread in-place mutation
    waits for the op before rebinding the buffer (the reference
    engine's write-after-read ordering; ADVICE r4: without this the
    deferred op could observe post-mutation values). Non-gated inputs
    are value-snapshotted by the caller instead — cheaper than a pin.

    Returns the pinned targets; the caller MUST call
    unpin_reads(pinned, gate) when the op completes (pins must not
    outlive the read — a completed reader's gate strongly holds its
    output arrays and defers native-var deletion)."""
    pinned = []
    for a in arrays:
        p = getattr(a, "_pending", None)
        if p is None or not isinstance(p[0], EngineGate):
            continue
        tgt = a._base if getattr(a, "_base", None) is not None else a
        if tgt._read_pins is None:
            tgt._read_pins = []
        tgt._read_pins.append(gate)
        pinned.append(tgt)
    return pinned


def unpin_reads(pinned, gate):
    """Drop a completed reader's pins (idempotent; list ops are
    GIL-atomic vs a concurrent consume_read_pins clearing the list)."""
    for tgt in pinned:
        pins = tgt._read_pins
        if pins:
            try:
                pins.remove(gate)
            except ValueError:
                pass


def consume_read_pins(array):
    """Block until every reader pinned on `array` ran, then clear the
    pins. Two exemptions (both deadlock-avoidance, both keep ordering
    sound): the producer writing its OWN still-gated output skips the
    wait and KEEPS the pins — its readers depend on it, and their claim
    is on the value it is about to write; and a reader mutating its own
    buffers skips just itself. A reader's failure is NOT re-raised here
    — it poisons the reader's outputs and surfaces at their wait points
    (error-at-wait contract)."""
    pins = array._read_pins
    if not pins:
        return
    exec_vars = getattr(_EXEC_TLS, "vars", ())
    if exec_vars:
        # Executing inside an engine op. The producer writing its own
        # gated output must not wait (its readers depend on IT); any
        # OTHER worker-side mutation of a pinned array is a
        # var-misdeclaration (the op did not declare the write — ref
        # SURVEY §5.2), and blocking here can deadlock two sibling
        # readers on each other or starve a size-1 pool. Skip the wait,
        # keep the pins for the main thread.
        return
    array._read_pins = None
    for gate in pins:
        try:
            native_engine().wait_for_var(gate.var)
        except Exception:
            pass
