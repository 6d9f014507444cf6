"""InferenceSession — AOT-compiled eval-mode serving of a hybridized
Block (ISSUE 12 tentpole; ROADMAP item 4).

The TPU-native serving idiom is ahead-of-time full-program compilation
(arxiv 1810.09868): the whole model is ONE XLA executable per input
shape, weights stay device-resident, and the host only stages request
bytes in and result bytes out. This class owns that contract on top of
the pieces the stack already has:

- the **program** is the hybridized Block's CachedOp graph in eval
  mode, re-wrapped by :meth:`CachedOp.serve_program` with the request
  (``data%d``) input slots **donated** — the session owns its staging
  buffers outright, so XLA may alias them into outputs instead of
  holding dead input HBM across every forward. Weights ride as plain
  (undonated) arguments and are read live from the Parameters each
  call, so a Trainer updating the same process's weights is served
  with zero recompiles (same avals → same program) and zero staleness.
- **shape bucketing** (:mod:`.bucketing`): requests are padded up to a
  ladder rung, the jit cache is bounded by the ladder, and any shape
  the ladder missed is counted in ``mx_serve_bucket_miss_total`` and
  named by compilewatch's recompile attribution.
- **sharded serving** (SNIPPETS.md [3] pjit pattern): pass a ``mesh``
  (e.g. ``kvstore.device_mesh(jax.devices(), ("mp",))``) and
  ``param_specs`` rules; weights are ``device_put`` once with their
  NamedSharding, requests are replicated (or ``data_spec``-sharded),
  and jax.jit partitions the program over the mesh — the serving path
  for models too big for one chip. Sharded weights are CACHED (a
  cross-device reshard per request would dwarf the forward);
  :meth:`refresh_weights` re-captures them after a training step.

The per-program FLOPs that compilewatch extracts at compile time are
credited on every cache-hit execution, so serving MFU rides the same
``mx_executed_flops_total`` meter training uses (arxiv 2008.01040's
cost-model features doing double duty as the admission scheduler's
cost signal).
"""
from __future__ import annotations

import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import warnings

import numpy as onp

import jax

from ..base import MXNetError
from .. import telemetry
from .. import tracing
from ..context import current_context
from ..ndarray.ndarray import _place, _shares_buffer
from .. import random as rand_mod
from .bucketing import BucketLadder

__all__ = ["InferenceSession"]

_DATA_RE = re.compile(r"data\d+$")

# once-per-process guard for the CPU donation-noise filter
_CPU_DONATION_FILTERED = [False]


def _filter_cpu_donation_noise(devices):
    """On the CPU backend donation is ALWAYS a no-op and jax warns per
    compiled bucket — pure noise, for training programs as much as for
    serving, so a process-wide message filter is safe there. On device
    backends (TPU) nothing is filtered: a donation warning is a real
    double-HBM signal and must stay visible. Installed once, from the
    constructing thread (warnings filters are process-global and NOT
    safe to toggle per call from worker threads)."""
    if _CPU_DONATION_FILTERED[0]:
        return
    try:
        # the filter is process-global, so it must only install when
        # the whole PROCESS is CPU-backed — a CPU session inside a
        # mixed CPU+TPU process must not mute TPU donation warnings
        if all(d.platform == "cpu" for d in devices) \
                and all(d.platform == "cpu" for d in jax.devices()):
            warnings.filterwarnings(
                "ignore",
                message="Some donated buffers were not usable")
            _CPU_DONATION_FILTERED[0] = True
    except Exception:
        pass


def _bucket_key(bucket: Tuple[int, ...]) -> str:
    if len(bucket) == 1:
        return "b%d" % bucket[0]
    return "b%ds%d" % bucket


class InferenceSession:
    """Compiled multi-bucket eval serving of one hybridized Block.

    Parameters
    ----------
    block : HybridBlock
        The model. Hybridized (and its cache built) on demand.
    example_inputs : tuple of NDArray
        Required: their shapes are the template for every non-padded
        dimension, and (when the block has not run hybridized yet) one
        forward over them resolves deferred shapes and builds the
        CachedOp.
    ctx : Context, optional
        Serving device (single-device mode). Defaults to the example
        inputs' context, else the current context.
    buckets : str, optional
        Explicit bucket spec (overrides MXNET_SERVE_BUCKETS).
    seq_axis : int, optional
        The padded sequence axis of the request inputs (e.g. 1 for
        (batch, seq, ...) tokens). None = only the batch axis (0) is
        bucketed.
    max_batch / max_seq : int, optional
        Ladder ceiling for the default pow-2 rungs (defaults: the
        example shapes).
    mesh / param_specs / data_spec
        pjit-sharded serving (see module docstring). ``param_specs``
        is a list of ``(name_regex, PartitionSpec)`` rules, first
        match wins, default replicated.
    donate : bool
        Donate the request input buffers (default True; the
        staticcheck serve rule expects it).
    """

    def __init__(self, block, example_inputs: Optional[Sequence] = None,
                 ctx=None, buckets: Optional[str] = None,
                 seq_axis: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 mesh=None, param_specs=None, data_spec=None,
                 donate: bool = True):
        from ..gluon.block import HybridBlock
        from .. import autograd
        if not isinstance(block, HybridBlock):
            raise MXNetError(
                "InferenceSession serves hybridizable blocks; got %s"
                % type(block).__name__)
        if example_inputs is None:
            raise MXNetError(
                "InferenceSession: example_inputs required — their "
                "shapes fix the non-padded dims (and one forward "
                "builds the CachedOp when needed)")
        if not block._active:
            block.hybridize(static_alloc=True, static_shape=True)
        if block._cached_op is None:
            with autograd.pause():
                block(*example_inputs)
        self._block = block
        self._cop = block._cached_op
        self._input_names = list(block._cached_input_names)

        if ctx is None and example_inputs:
            ctx = example_inputs[0].ctx
        self._ctx = ctx or current_context()

        # request (data%d) vs weight slots, in graph-input order
        self._data_pos = [i for i, n in enumerate(self._input_names)
                          if _DATA_RE.match(n)]
        self._param_pos = [(i, n) for i, n in enumerate(self._input_names)
                           if not _DATA_RE.match(n)]
        if not self._data_pos:
            raise MXNetError("InferenceSession: graph has no data inputs")
        self._all_params = block.collect_params()

        # template shapes/dtypes for every data input (from the traced
        # example); axis 0 is the batch axis, `seq_axis` the padded
        # sequence axis
        data_names = [self._input_names[i] for i in self._data_pos]
        by_name = {"data%d" % i: a for i, a in enumerate(example_inputs)}
        self._templates = []
        for n in data_names:
            a = by_name.get(n)
            if a is None:
                raise MXNetError("InferenceSession: no example for "
                                 "graph input %r" % n)
            self._templates.append((tuple(a.shape), onp.dtype(a.dtype)))
        self._seq_axis = seq_axis

        ex_batch = self._templates[0][0][0]
        ex_seq = (self._templates[0][0][seq_axis]
                  if seq_axis is not None else None)
        self.ladder = BucketLadder.from_env(
            max_batch or ex_batch,
            (max_seq or ex_seq) if seq_axis is not None else None,
            spec=buckets)

        # sharded-serving state (pjit pattern)
        self._mesh = mesh
        self._param_rules = [(re.compile(pat), spec)
                             for pat, spec in (param_specs or [])]
        self._data_spec = data_spec
        self._sharded_params: Optional[List] = None
        if mesh is not None:
            # static pre-compile validation (mxlint Level 4, ISSUE
            # 15): a rank/axis-name/divisibility error in param_specs
            # raises HERE with the parameter and mesh axis named —
            # not as an opaque XLA error mid-AOT-build
            from ..staticcheck import spmd_rules
            spmd_rules.validate_param_specs(
                mesh, self._param_rules,
                [(n, tuple(self._all_params[n].shape))
                 for _i, n in self._param_pos])
            self.refresh_weights()

        self._donate = bool(donate)
        _filter_cpu_donation_noise(
            list(mesh.devices.flat) if mesh is not None
            else [self._ctx.jax_device])
        self._fn = self._cop.serve_program(
            donate_argnums=tuple(self._data_pos) if donate else ())
        # the ladder is the PLANNED program set: its warmup compiles
        # must not read as a recompile storm, anything past it should
        self._fn.expected_signatures = len(self.ladder.all_buckets())
        self._needs_rng = bool(self._cop._needs_rng)
        # which outputs scale with the batch/seq axes, learned by
        # ABSTRACT evaluation at two request shapes (traces, never
        # compiles): the unpad then slices exactly the outputs that
        # scale, instead of a leading-dim==rung heuristic that a
        # batch-reduced output of coincidental size could fool
        self._out_scales = self._detect_out_axes()

        self._lock = threading.Lock()
        # Multi-device collective programs launched from CONCURRENT
        # host threads can interleave their per-device rendezvous and
        # deadlock (observed on the 8-device dryrun with two in-flight
        # serve batches); a sharded session therefore serializes its
        # executions. Single-device programs are stream-ordered by XLA
        # and stay lock-free — the overlap the in-flight cap buys.
        self._exec_lock = threading.Lock() if mesh is not None else None
        self._warm: set = set()
        self._stats: Dict[Tuple[int, ...], list] = {}  # bucket -> [hit, miss]
        self._closed = False

    # ------------------------------------------------------------------
    # weights
    # ------------------------------------------------------------------
    def _spec_for(self, name: str):
        from jax.sharding import PartitionSpec as P
        for pat, spec in self._param_rules:
            if pat.match(name):
                return spec
        return P()

    def refresh_weights(self):
        """(Sharded mode) re-capture the parameters onto the mesh with
        their NamedShardings. Call after a weight update; single-device
        sessions read the live Parameter buffers every request and
        never need this."""
        if self._mesh is None:
            return
        from jax.sharding import NamedSharding
        out, shared = [], []
        for _i, name in self._param_pos:
            p = self._all_params[name]
            buf = p.data(p.list_ctx()[0])._jax()
            placed = jax.device_put(
                buf, NamedSharding(self._mesh, self._spec_for(name)))
            out.append(placed)
            if _shares_buffer(placed, buf):
                shared.append(buf)
        self._sharded_params = out
        # the parameters' own arrays wherever a capture reads their
        # memory (a replicated spec on a mesh that holds the
        # parameter's device): a training step in this process then
        # sees a second holder and leaves these buffers alone
        self._shared_sources = shared

    def _weight_args(self) -> List:
        if self._mesh is not None:
            return list(self._sharded_params)
        ctx = self._ctx
        return [self._all_params[n].data(ctx)._jax()
                for _i, n in self._param_pos]

    # ------------------------------------------------------------------
    def _abstract_specs(self, b: int, s: int) -> List:
        out: List = [None] * len(self._input_names)
        for pos, (shape, dtype) in zip(self._data_pos, self._templates):
            tgt = list(shape)
            tgt[0] = b
            if self._seq_axis is not None and len(tgt) > self._seq_axis:
                tgt[self._seq_axis] = s
            out[pos] = jax.ShapeDtypeStruct(tuple(tgt), dtype)
        for (pos, _n), w in zip(self._param_pos, self._weight_args()):
            out[pos] = jax.ShapeDtypeStruct(tuple(w.shape), w.dtype)
        return out

    def _detect_out_axes(self):
        """Per-output ``(scales_with_batch, scales_with_seq)`` learned
        from two jax.eval_shape passes (b 1->2, seq 2->3). None (fall
        back to the shape heuristic) when the program needs an rng key
        or the probe shapes don't trace (e.g. a kernel wider than the
        probe seq)."""
        if self._needs_rng:
            return None
        try:
            oa = jax.eval_shape(self._fn, *self._abstract_specs(1, 2))
            ob = jax.eval_shape(self._fn, *self._abstract_specs(2, 3))
        except Exception:
            return None
        scales = []
        sax = self._seq_axis
        for a, c in zip(oa, ob):
            batch = (len(a.shape) > 0 and a.shape[0] == 1
                     and c.shape[0] == 2)
            seq = (sax is not None and len(a.shape) > sax
                   and a.shape[sax] == 2 and c.shape[sax] == 3)
            scales.append((batch, seq))
        return scales

    # ------------------------------------------------------------------
    # padding + staging
    # ------------------------------------------------------------------
    def _pad_to(self, x, bucket: Tuple[int, ...], template) -> onp.ndarray:
        shape, dtype = template
        tgt = list(shape)
        tgt[0] = bucket[0]
        if self._seq_axis is not None and len(tgt) > self._seq_axis:
            tgt[self._seq_axis] = bucket[1]
        x = onp.asarray(x, dtype=dtype)
        if x.shape == tuple(tgt):
            return x
        buf = onp.zeros(tuple(tgt), dtype=dtype)
        buf[tuple(slice(0, s) for s in x.shape)] = x
        return buf

    def _stage(self, buf: onp.ndarray):
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = self._data_spec if self._data_spec is not None else P()
            return jax.device_put(buf, NamedSharding(self._mesh, spec))
        return _place(buf, self._ctx)

    # ------------------------------------------------------------------
    # the serving call
    # ------------------------------------------------------------------
    def validate_request(self, hosts: Sequence[onp.ndarray]):
        """One shape contract for BOTH entry points (direct infer and
        Scheduler.submit): arity, >= 1 row, only the batch (and seq)
        axes free, every input's row/seq agreeing with the first.
        Anything else must RAISE — _pad_to would otherwise zero-pad a
        too-small feature axis and serve plausible-looking garbage."""
        if len(hosts) != len(self._data_pos):
            raise MXNetError("serve: expected %d data input(s), got %d"
                             % (len(self._data_pos), len(hosts)))
        if not hosts[0].ndim or hosts[0].shape[0] < 1:
            raise MXNetError("serve: request must have >= 1 row")
        n = int(hosts[0].shape[0])
        sax = self._seq_axis
        if sax is not None and hosts[0].ndim <= sax:
            raise MXNetError(
                "serve: data input 0 has ndim %d but this session "
                "buckets sequence axis %d" % (hosts[0].ndim, sax))
        seq0 = int(hosts[0].shape[sax]) if sax is not None else None
        for i, (h, (tshape, _td)) in enumerate(
                zip(hosts, self._templates)):
            ok = (h.ndim == len(tshape) and h.shape[0] == n
                  and all(d == 0 or d == sax
                          or h.shape[d] == tshape[d]
                          for d in range(h.ndim))
                  and (sax is None or h.ndim <= sax
                       or h.shape[sax] == seq0))
            if not ok:
                raise MXNetError(
                    "serve: data input %d has shape %s, expected %s "
                    "with only the batch%s axis free (shared across "
                    "inputs)"
                    % (i, tuple(h.shape), tshape,
                       "/seq" if sax is not None else ""))

    def _as_host(self, x) -> onp.ndarray:
        if isinstance(x, onp.ndarray):
            return x
        if hasattr(x, "asnumpy"):
            return x.asnumpy()
        return onp.asarray(x)

    def infer(self, *data, _warming: bool = False):
        """Serve one (possibly multi-row) request: pad to the bucket,
        run the compiled program, slice the padding back off. Inputs
        are numpy arrays or NDArrays; outputs are numpy arrays (a
        single array when the graph has one output).

        Thread-safe; used directly for batch-1 latency paths and by
        the continuous-batching :class:`~.scheduler.Scheduler` for
        assembled batches."""
        if self._closed:
            raise MXNetError("InferenceSession is closed")
        hosts = [self._as_host(x) for x in data]
        self.validate_request(hosts)
        b = int(hosts[0].shape[0])
        s = (int(hosts[0].shape[self._seq_axis])
             if self._seq_axis is not None else None)
        bucket, beyond = self.ladder.bucket_for(b, s)

        with self._lock:
            # warm flips only AFTER the first execution returns (end
            # of infer): a concurrent second caller of a cold bucket
            # must classify as cold too, or its blocked-on-compile
            # wall time would pollute the warm-latency histogram as a
            # phantom hit (concurrent cold hits may then over-count
            # misses by one — the conservative direction)
            warm = bucket in self._warm
            # a MISS is either a compile the warmup did not cover, or
            # ANY beyond-ladder request (warmed or not — sustained
            # off-ladder traffic must stay loud, not go quiet after
            # its first compile; docs/SERVING.md contract)
            miss = (not warm) or beyond
            st = self._stats.setdefault(bucket, [0, 0])
            if not _warming:
                st[1 if miss else 0] += 1
                if miss:
                    telemetry.count_event("mx_serve_bucket_miss_total",
                                          bucket=_bucket_key(bucket))

        staged = [self._stage(self._pad_to(h, bucket, t))
                  for h, t in zip(hosts, self._templates)]
        args: List = [None] * len(self._input_names)
        for pos, buf in zip(self._data_pos, staged):
            args[pos] = buf
        for (pos, _n), w in zip(self._param_pos, self._weight_args()):
            args[pos] = w
        if self._needs_rng:
            impl = (self._cop._needs_rng
                    if self._cop._needs_rng != "default" else None)
            key = rand_mod.take_key(self._ctx, impl=impl)
            if self._mesh is not None:
                # the key must live where the sharded program runs —
                # a single-device key fails jit's device consistency
                from jax.sharding import NamedSharding, PartitionSpec
                key = jax.device_put(
                    key, NamedSharding(self._mesh, PartitionSpec()))
            else:
                key = _place(key, self._ctx)
            args = [key] + args

        if self._exec_lock is not None:
            self._exec_lock.acquire()
        try:
            out = self._run(args, bucket, warm, b, s)
        finally:
            if self._exec_lock is not None:
                self._exec_lock.release()
        with self._lock:
            self._warm.add(bucket)
        return out

    def _run(self, args, bucket, warm, b, s):
        # ambient distributed-trace context (the scheduler rebinds the
        # remote trace on the executing thread): the program-forward
        # span lands in the trace ring as nested execute detail
        tctx = tracing.current() if tracing.active() else None
        t0w = time.time() if tctx is not None else 0.0
        with telemetry.span("serve::forward", "serve",
                            hist="mx_serve_batch_seconds",
                            bucket=_bucket_key(bucket)) as sp:
            if not warm:
                # a cold bucket's wall time is COMPILE time —
                # compilewatch records it with stage breakdown;
                # keeping it out of the batch-latency histogram keeps
                # per-bucket p50/p99 about serving, not warmup
                sp.cancel()
            outs = self._fn(*args)
            outs = [jax.device_get(o) for o in outs]
        if tctx is not None:
            tracing.record_span("serve::forward", "serve", t0w,
                                time.time(), ctx=tctx,
                                args={"bucket": _bucket_key(bucket),
                                      "warm": bool(warm)})

        sliced = []
        for i, o in enumerate(outs):
            o = onp.asarray(o)
            sc = self._out_scales[i] if self._out_scales else None
            batched = (sc[0] if sc is not None
                       else o.ndim and o.shape[0] == bucket[0])
            seqful = (sc[1] if sc is not None
                      else (self._seq_axis is not None
                            and o.ndim > self._seq_axis
                            and o.shape[self._seq_axis] == bucket[1]))
            if batched and o.ndim and b != bucket[0]:
                o = o[:b]
            if seqful and self._seq_axis is not None \
                    and o.ndim > self._seq_axis and s != bucket[1]:
                idx = [slice(None)] * o.ndim
                idx[self._seq_axis] = slice(0, s)
                o = o[tuple(idx)]
            sliced.append(o)
        return sliced if len(sliced) > 1 else sliced[0]

    # ------------------------------------------------------------------
    def warmup(self, buckets: Optional[Sequence[Tuple[int, ...]]] = None):
        """Compile every ladder rung ahead of traffic (zeros input).
        Post-warmup steady state compiles NOTHING for in-ladder
        shapes — tools/serve_bench.py gates that with compilewatch's
        program records."""
        for bucket in (buckets or self.ladder.all_buckets()):
            fakes = []
            for shape, dtype in self._templates:
                tgt = list(shape)
                tgt[0] = bucket[0]
                if self._seq_axis is not None and len(tgt) > self._seq_axis:
                    tgt[self._seq_axis] = bucket[-1]
                fakes.append(onp.zeros(tuple(tgt), dtype=dtype))
            self.infer(*fakes, _warming=True)
        return self

    @property
    def max_batch(self) -> int:
        return self.ladder.max_batch

    @property
    def seq_axis(self) -> Optional[int]:
        return self._seq_axis

    def bucket_table(self) -> List[dict]:
        """Per-bucket serving stats: warmed / hits / misses (the table
        fleet_report --serve prints and gates on)."""
        with self._lock:
            keys = sorted(set(self._warm) | set(self._stats))
            return [{"bucket": _bucket_key(k),
                     "warmed": k in self._warm,
                     "hits": self._stats.get(k, [0, 0])[0],
                     "misses": self._stats.get(k, [0, 0])[1]}
                    for k in keys]

    def bucket_misses(self) -> int:
        with self._lock:
            return sum(v[1] for v in self._stats.values())

    def collective_tag(self) -> Optional[dict]:
        """The ``engine.push_async(collective=...)`` descriptor for
        ops that execute this session's program, or None when the
        program is not known to issue cross-device collectives. The
        mark comes from the Level-4 SPMD hook parsing the compiled
        HLO (``WatchedJit.issues_collectives``; needs
        MXNET_STATICCHECK_SPMD + MXNET_TELEMETRY at compile time);
        'lock' is the identity of this session's serializing exec
        lock, so the Level-3 collective-interleave check treats two
        in-flight batches of ONE session as sanctioned while two
        different multi-device programs with no shared lock are the
        PR-12 deadlock shape (staticcheck/race.py, ISSUE 15)."""
        if self._mesh is None \
                or not getattr(self._fn, "issues_collectives", False):
            return None
        return {"program": "%s (%s)" % (self._fn.fn_label,
                                        self._fn.instance),
                "lock": id(self._exec_lock)
                if self._exec_lock is not None else None}

    def close(self):
        self._closed = True
