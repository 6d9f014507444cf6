"""CachedOp — the traced-graph fast path behind HybridBlock.hybridize().

Ref: src/imperative/cached_op.cc :: CachedOp::Forward/Backward,
CachedOpConfig (static_alloc/static_shape, bulking).

TPU mapping (SURVEY.md §3.3): CachedOp ≈ jax.jit cache keyed on input
avals. The whole symbol graph becomes ONE jitted XLA program:
- forward (inference): jit(graph_fn) — XLA fuses/plans memory, which is
  what static_alloc+bulking approximated by hand in the reference.
- forward under autograd: a jitted program computes outputs AND the vjp
  residuals (jax.vjp returned from jit as a Partial pytree); one tape
  node carries the whole subgraph, and backward applies a jitted
  transpose — so fwd and bwd are each a single compiled XLA program
  with stored residuals (no recompute).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax

from .base import MXNetError
from . import autograd
from . import telemetry
from .ndarray import NDArray
from .ndarray.ndarray import _place
from . import random as rand_mod

__all__ = ["CachedOp"]

_UID = [0]


class CachedOp:
    def __init__(self, sym, input_names: List[str],
                 flags: Optional[Sequence] = None):
        """sym: output Symbol; input_names: name order of call arguments."""
        from . import symbol as sym_mod
        self._sym = sym
        self._input_names = list(input_names)
        graph_inputs = sym.list_inputs()
        unknown = [n for n in graph_inputs if n not in self._input_names]
        if unknown:
            raise MXNetError("CachedOp: graph inputs %s not bound" % unknown)
        self._flags = dict(flags or [])
        self._fns: Dict = {}   # (train,) -> jitted forward
        self._vjp_fwd = None   # jitted fn returning (outs, vjp_partial)
        self._bwd = None       # jitted fn applying the vjp partial
        self._needs_rng = False
        # graph-level TPU layout optimization (NHWC conv islands + dead
        # conv-bias elision) on the hybridize fast path — the same passes
        # ShardedTrainStep applies, so the reference-idiomatic
        # hybridize()+Trainer loop gets the optimized graph (ref:
        # BASELINE.json configs[1] "HybridBlock/CachedOp")
        from .symbol.layout_opt import (convert_layout, elide_conv_bias_into_bn,
                                        layout_opt_enabled)
        if layout_opt_enabled():
            self._sym = elide_conv_bias_into_bn(self._sym)
            self._sym = convert_layout(self._sym)
        self._compile()

    def _compile(self):
        from .symbol import compile_graph
        from .compilewatch import watched_jit
        _UID[0] += 1
        self._uid = _UID[0]
        # aux variables (BatchNorm moving stats) are returned as extra
        # outputs from the compiled program and written back after the
        # call — the jit-world equivalent of FMutateInputs
        aux_names = self._sym.list_auxiliary_states()
        self._aux_names = [n for n in aux_names if n in self._input_names]
        self._aux_idx = [self._input_names.index(n) for n in self._aux_names]
        for train in (False, True):
            fn, needs_rng = compile_graph(self._sym, self._input_names,
                                          train=train, return_aux=True)
            self._needs_rng = needs_rng
            names = self._input_names
            aux = self._aux_names

            if needs_rng:
                def flat(rng, *arrays, _fn=fn, _names=names, _aux=aux):
                    outs, aux_d = _fn(dict(zip(_names, arrays)), rng=rng)
                    return tuple(outs) + tuple(aux_d[a] for a in _aux)
            else:
                def flat(*arrays, _fn=fn, _names=names, _aux=aux):
                    outs, aux_d = _fn(dict(zip(_names, arrays)))
                    return tuple(outs) + tuple(aux_d[a] for a in _aux)
            # watched jit (ISSUE 4): stage-timed compiles, per-input
            # recompile attribution (arg names = the graph input
            # names), and cost/memory accounting per program
            watch_names = (["rng"] if needs_rng else []) + list(names)
            self._fns[train] = watched_jit(  # mxlint: disable=scalar-capture (bounded two-iteration loop: exactly one program per train/eval mode, by design)
                flat, fn_label="CachedOp.forward", site="cached_op",
                arg_names=watch_names,
                instance="cop%d/%s" % (self._uid,
                                       "train" if train else "eval"))

            if train:
                self._train_flat = flat
                self._watch_names = watch_names
            else:
                # kept for serve_program(): the serving path re-wraps
                # the eval graph with donated request-input buffers
                self._eval_graph_fn = fn
        self._n_visible = len(self._sym._entries)

        def fwd_vjp(*arrays):
            outs, vjp_fn = jax.vjp(self._train_flat, *arrays)
            return outs, vjp_fn

        self._vjp_fwd = watched_jit(
            fwd_vjp, fn_label="CachedOp.fwd_vjp", site="cached_op",
            arg_names=self._watch_names, instance="cop%d" % self._uid)
        self._bwd = watched_jit(
            lambda vjp_fn, cots: vjp_fn(cots),
            fn_label="CachedOp.bwd", site="cached_op",
            arg_names=["vjp_fn", "cotangents"],
            instance="cop%d" % self._uid)
        # register for the fused-backward program cache (autograd tape
        # bulking): the fused builder resolves ("cop", uid) -> train_flat.
        # A finalizer drops the entry when the CachedOp dies so long-lived
        # processes that hybridize many models don't leak closures.
        import weakref
        autograd._COP_FNS[self._uid] = self._train_flat
        # symbol registry for autograd.get_symbol reconstruction
        autograd._COP_SYMS[self._uid] = (self._sym, list(self._input_names))
        # one finalizer through _release_cop: also evicts _FUSED_CACHE
        # runners whose tape key references this CachedOp (they close
        # over train_flat — popping only _COP_FNS would free nothing)
        weakref.finalize(self, autograd._release_cop, self._uid)
        self._aval_cache: Dict = {}

    # ------------------------------------------------------------------
    def serve_program(self, donate_argnums: Sequence[int] = (),
                      instance: Optional[str] = None):
        """Forward-only (eval) program for the serving path (ISSUE 12).

        The regular eval program (``self._fns[False]``) cannot donate:
        its inputs are live user NDArrays (weights included) that the
        caller keeps. A serving session owns its request staging
        buffers outright — they are dead the moment the program reads
        them — so this variant threads ``donate_argnums`` (indices
        into ``input_names``; the session donates the request/data
        slots, never the weights) through the WatchedJit site, letting
        XLA alias the request buffers into outputs instead of holding
        input AND output copies live across the forward. Aux outputs
        (BatchNorm moving stats) are dropped: eval never writes them
        back, and returning them would pin extra output buffers.

        staticcheck's ``graph-nondonated-serve-input`` rule holds
        serve-labeled programs to this contract (the eval-mode
        ``graph-collective-in-eval`` rule applies too — the instance
        keeps the ``/eval`` suffix)."""
        from .compilewatch import watched_jit
        fn = self._eval_graph_fn
        names = self._input_names
        if self._needs_rng:
            def serve_flat(rng, *arrays, _fn=fn, _names=names):
                outs, _aux = _fn(dict(zip(_names, arrays)), rng=rng)
                return tuple(outs)
        else:
            def serve_flat(*arrays, _fn=fn, _names=names):
                outs, _aux = _fn(dict(zip(_names, arrays)))
                return tuple(outs)
        off = 1 if self._needs_rng else 0     # the rng key is never donated
        watch_names = (["rng"] if self._needs_rng else []) + list(names)
        return watched_jit(
            serve_flat, fn_label="serve.forward", site="serve",
            arg_names=watch_names,
            instance=instance or "cop%d/serve/eval" % self._uid,
            donate_argnums=tuple(off + int(i) for i in donate_argnums))

    # ------------------------------------------------------------------
    def _out_avals(self, arg_avals):
        """Abstract-eval the full output list (visible + aux) for a
        given input-aval signature (cached)."""
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arg_avals)
        got = self._aval_cache.get(sig)
        if got is None:
            got = jax.eval_shape(self._train_flat, *arg_avals)
            got = list(got) if isinstance(got, (tuple, list)) else [got]
            self._aval_cache[sig] = got
        return got

    def _run_vjp(self, args):
        """One forward-with-residuals execution + its backward closure
        (shared by the eager recording path and deferred forcing)."""
        telemetry.count_launch("gluon")
        try:
            all_raw, vjp_partial = self._vjp_fwd(*args)
            bwd = self._bwd

            def vjp_fn(cots):
                cots = cots if isinstance(cots, tuple) else (cots,)
                telemetry.count_launch("gluon")
                return bwd(vjp_partial, tuple(cots))
        except Exception:
            # fallback: eager vjp (still correct, not one fused program)
            all_raw, raw_vjp = jax.vjp(self._train_flat, *args)

            def vjp_fn(cots):
                cots = cots if isinstance(cots, tuple) else (cots,)
                return raw_vjp(tuple(cots))
        return all_raw, vjp_fn

    def _force_node(self, node):
        """Materialize a deferred node outside the fused backward: run
        the two-program vjp path and fill outputs + vjp_fn."""
        raws = []
        for rawv in node.raw_inputs:
            if isinstance(rawv, tuple) and len(rawv) == 3 and rawv[0] == "p":
                prod, slot = rawv[1], rawv[2]
                prod.force()
                raws.append(prod.out_values[slot])
            else:
                raws.append(rawv)
        args = ([node.rng_key] if node.n_rng else []) + raws
        all_raw, node.vjp_fn = self._run_vjp(args)
        autograd._fill_pending(node, all_raw)

    # ------------------------------------------------------------------
    def _write_aux(self, inputs, aux_vals):
        for idx, val in zip(self._aux_idx, aux_vals):
            inputs[idx]._set_jax(val)

    def __call__(self, *inputs: NDArray):
        ctx = inputs[0].ctx
        rng_args = []
        if self._needs_rng:
            # _needs_rng carries the graph's required PRNG impl (set by
            # compile_graph when e.g. a poisson op needs threefry keys)
            impl = self._needs_rng if self._needs_rng != "default" else None
            rng_args = [_place(rand_mod.take_key(ctx, impl=impl), ctx)]

        recording = autograd.is_recording() and any(a._in_graph for a in inputs)
        train = autograd.is_training()
        n_vis = self._n_visible

        if recording and autograd._fused_enabled():
            # DEFER execution: record a pending node. The value is
            # produced either by ONE fused fwd+bwd program at
            # loss.backward() (tape bulking) or on first value read.
            # Pending inputs (outputs of an earlier deferred node) are
            # wired through as graph edges, keeping multi-CachedOp
            # chains (net -> loss block) inside one program.
            raws = []
            arg_avals = []
            for a in inputs:
                p = a._pending
                if p is not None:
                    raws.append(("p", p[0], p[1]))
                    arg_avals.append(jax.ShapeDtypeStruct(
                        tuple(p[2].shape), p[2].dtype))
                else:
                    b = a._jax()
                    raws.append(b)
                    arg_avals.append(jax.ShapeDtypeStruct(b.shape, b.dtype))
            all_avals = self._out_avals(list(rng_args) + arg_avals)
            out_arrays = [NDArray(None, ctx) for _ in range(n_vis)]
            aux_arrays = [inputs[i] for i in self._aux_idx]
            autograd._record_deferred_node(
                "CachedOp", list(inputs), out_arrays, all_avals,
                n_rng=1 if rng_args else 0, n_extra=len(aux_arrays),
                fwd_fn=self._train_flat,
                rng_key=rng_args[0] if rng_args else None,
                raw_inputs=raws, fused_key=("cop", self._uid),
                force_cb=self._force_node, aux_arrays=aux_arrays,
                aux_in=tuple(self._aux_idx))
            return out_arrays if len(out_arrays) > 1 else out_arrays[0]

        raw = [a._jax() for a in inputs]
        if recording:
            args = tuple(rng_args + raw) if self._needs_rng else tuple(raw)
            all_raw, vjp_fn = self._run_vjp(args)
            outs_raw, aux_vals = all_raw[:n_vis], all_raw[n_vis:]
            self._write_aux(inputs, aux_vals)
            out_arrays = [NDArray(_place(b, ctx), ctx) for b in outs_raw]
            avals = [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in all_raw]

            class _Op:
                name = "CachedOp"

            autograd._record_node(_Op, list(inputs), out_arrays, vjp_fn,
                                  avals, n_rng=1 if self._needs_rng else 0,
                                  n_extra=len(aux_vals),
                                  fwd_fn=self._train_flat,
                                  rng_key=rng_args[0] if rng_args else None)
            return out_arrays if len(out_arrays) > 1 else out_arrays[0]

        fn = self._fns[train]
        telemetry.count_launch("gluon")
        all_raw = fn(*rng_args, *raw) if self._needs_rng else fn(*raw)
        outs_raw, aux_vals = all_raw[:n_vis], all_raw[n_vis:]
        if train:
            self._write_aux(inputs, aux_vals)
        out_arrays = [NDArray(_place(b, ctx), ctx) for b in outs_raw]
        return out_arrays if len(out_arrays) > 1 else out_arrays[0]
