"""Gluon Block / HybridBlock / SymbolBlock.

Ref: python/mxnet/gluon/block.py — Block (eager container, name scopes,
collect_params), HybridBlock (hybridize() → trace hybrid_forward to a
Symbol → CachedOp; _build_cache/_call_cached_op; export()), SymbolBlock
(imports an exported symbol+params).

TPU mapping: hybridize compiles the block to ONE jitted XLA program via
CachedOp (SURVEY.md §3.3 "CachedOp ≈ jax.jit keyed on input avals").
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ..base import MXNetError
from ..context import Context, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import symbol as sym_mod
from ..symbol import Symbol
from .. import autograd
from .. import telemetry
from ..cached_op import CachedOp
from .parameter import (Constant, DeferredInitializationError, Parameter,
                        ParameterDict)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope(threading.local):
    def __init__(self):
        self.current = None
        self.counters = {}


_scope = _BlockScope()


class _NameScopeCM:
    def __init__(self, block):
        self._block = block

    def __enter__(self):
        self._old = _scope.current
        _scope.current = self._block
        return self._block._prefix

    def __exit__(self, *exc):
        _scope.current = self._old
        return False


def _gen_prefix(hint: str) -> str:
    parent = _scope.current
    if parent is not None:
        counters = parent._child_counters
        base = parent._prefix
    else:
        counters = _scope.counters
        base = ""
    idx = counters.get(hint, 0)
    counters[hint] = idx + 1
    return "%s%s%d_" % (base, hint, idx)


class Block:
    """Base container (ref: block.py :: Block)."""

    def __init__(self, prefix: Optional[str] = None,
                 params: Optional[ParameterDict] = None):
        hint = re.sub(r"(?!^)([A-Z]+)", r"_\1", type(self).__name__).lower()
        if prefix is None:
            prefix = _gen_prefix(hint)
        elif _scope.current is not None:
            prefix = _scope.current._prefix + prefix
        self._prefix = prefix
        self._child_counters: Dict[str, int] = {}
        self._params = ParameterDict(prefix, shared=params)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []

    # ------------------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return _NameScopeCM(self)

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=repr(block).replace("\n", "\n  "))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            if "_params" in self.__dict__:
                self._params._params[value.name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------------
    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as init_mod
        self.collect_params().initialize(
            init or init_mod.Uniform(), ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def zero_grad(self):
        self.collect_params().zero_grad()

    # ------------------------------------------------------------------
    def _structural_params(self, prefix="") -> "OrderedDict[str, Parameter]":
        """Structure-keyed params: child attribute names joined by '.'
        (ref: Block._collect_params_with_prefix — the save_parameters
        format, robust to prefix renumbering)."""
        ret = OrderedDict()
        for name, p in self._params.items():
            ret[prefix + _strip_prefix(name, self._prefix)] = p
        for cname, child in self._children.items():
            ret.update(child._structural_params(prefix + cname + "."))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        params = self._structural_params()
        arg_dict = {}
        seen = {}
        for name, param in params.items():
            if deduplicate and id(param) in seen:
                continue
            seen[id(param)] = name
            arg_dict[name] = param.data()
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd.load(filename)
        params = self._structural_params()
        full_names = self.collect_params()
        # accept both structural names and full prefixed names
        resolved = {}
        for k, v in loaded.items():
            if k in params:
                resolved[k] = (params[k], v)
            elif k in full_names:
                resolved[k] = (full_names[k], v)
            elif self._prefix + k in full_names:
                resolved[k] = (full_names[self._prefix + k], v)
            elif not ignore_extra:
                raise ValueError(
                    "Parameter %s in file %s unknown to block" % (k, filename))
        if not allow_missing:
            matched = {id(p) for p, _ in resolved.values()}
            for name, p in params.items():
                if id(p) not in matched:
                    raise AssertionError(
                        "Parameter %s missing in file %s" % (name, filename))
        for _, (p, data) in resolved.items():
            if p._data is None and p._deferred_init is None:
                p._shape = tuple(data.shape)
                p.initialize(ctx=ctx or [current_context()])
            elif p._deferred_init is not None:
                p._shape = tuple(data.shape)
                if ctx is not None:
                    p.reset_ctx(ctx)
                p._finish_deferred_init()
            elif ctx is not None:
                p.reset_ctx(ctx)
            p.set_data(data)

    save_params = save_parameters
    load_params = load_parameters

    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        from ..util import is_np_array
        if is_np_array():
            # npx.set_np(): blocks hand back mx.np ndarrays (tape
            # pointers preserved — training must keep working)
            from ..numpy import _to_np_out
            out = _to_np_out(out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (layer type, output shape,
        trainable/shared param counts) by running one forward pass with
        hooks on every descendant block (ref: block.py :: summary).
        Must be called BEFORE hybridize()."""
        for blk in self._iter_blocks():
            if getattr(blk, "_active", False):
                raise AssertionError(
                    "'summary' is only supported before hybridize: the "
                    "traced CachedOp bypasses child forward hooks")
        summary = OrderedDict()
        seen_params = set()
        hooks = []

        import numpy as np

        def _shape_of(out):
            first = out[0] if isinstance(out, (list, tuple)) else out
            return tuple(first.shape)

        def _register(blk, prefix=""):
            def hook(b, _args, out, _name=prefix or type(blk).__name__):
                key = "%s-%d" % (_name, len(summary) + 1)
                n_params = n_shared = 0
                for p in b._params.values() if hasattr(b, "_params") else []:
                    try:
                        sz = int(np.prod(p.shape)) if p.shape else 0
                    except Exception:
                        sz = 0
                    if id(p) in seen_params:
                        n_shared += sz
                    else:
                        seen_params.add(id(p))
                        n_params += sz
                summary[key] = dict(type=type(b).__name__,
                                    output=_shape_of(out),
                                    n_params=n_params, n_shared=n_shared)
            blk.register_forward_hook(hook)
            hooks.append(hook)
            for cname, child in blk._children.items():
                _register(child, (prefix + "." if prefix else "")
                          + type(child).__name__)

        _register(self)
        try:
            self(*inputs)
        finally:
            for blk in self._iter_blocks():
                blk._forward_hooks = [h for h in blk._forward_hooks
                                      if h not in hooks]
        lines = ["-" * 76,
                 "%-34s %-24s %15s" % ("Layer (type)", "Output Shape",
                                       "Param #"),
                 "=" * 76]
        total = shared = 0
        for key, row in summary.items():
            lines.append("%-34s %-24s %15d"
                         % (key + " (" + row["type"] + ")",
                            str(row["output"]), row["n_params"]))
            total += row["n_params"]
            shared += row["n_shared"]
        lines += ["=" * 76,
                  "Total params: %d" % total,
                  "Shared params: %d" % shared,
                  "-" * 76]
        print("\n".join(lines))
        return summary

    def _iter_blocks(self):
        yield self
        for child in self._children.values():
            yield from child._iter_blocks()

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)


def _strip_prefix(name, prefix):
    return name[len(prefix):] if name.startswith(prefix) else name


class HybridBlock(Block):
    """Block tracable to one compiled XLA program (ref: HybridBlock)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = []
        self._cached_op: Optional[CachedOp] = None
        self._cached_graph = None
        self._in_symbolic_call = False

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = [("static_alloc", static_alloc),
                       ("static_shape", static_shape)]
        self._clear_cached_op()
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _clear_cached_op(self):
        self._cached_op = None
        self._cached_graph = None

    def infer_shape(self, *args):
        """Per-layer hook: subclasses with input-dependent param shapes
        override this to complete deferred shapes from real inputs."""
        for child in self._children.values():
            pass  # composite blocks resolve via their children's forwards

    # ------------------------------------------------------------------
    def _build_cache(self, *args):
        with telemetry.setup_phase("graph"):
            self._trace_cached_op(*args)

    def _trace_cached_op(self, *args):
        # trace hybrid_forward with symbolic placeholders
        data_syms = [sym_mod.var("data%d" % i) for i in range(len(args))]
        params = {name: p for name, p in self._collect_params_with_prefix().items()}
        with autograd.pause():
            out = self._symbolic_call(data_syms)
        out_sym = sym_mod.Group(out) if isinstance(out, (list, tuple)) else out
        graph_inputs = out_sym.list_inputs()
        data_names = ["data%d" % i for i in range(len(args))]
        param_syms_by_name = {}
        all_params = self.collect_params()
        input_names, self._cached_params = [], []
        for name in graph_inputs:
            if name in data_names:
                input_names.append(name)
            elif name in all_params:
                input_names.append(name)
                self._cached_params.append(all_params[name])
            else:
                raise MXNetError("hybridize: unknown graph input %r" % name)
        # order: data first then params, preserving graph_inputs order is
        # fine since we feed by name
        self._cached_graph = (data_names, out_sym)
        self._cached_input_names = input_names
        # AMP reaches the compiled path as a graph pass over the traced
        # symbol (the low_precision_pass.cc analogue)
        from ..contrib import amp as amp_mod
        compile_sym = out_sym
        if amp_mod.is_initialized():
            compile_sym = amp_mod.convert_symbol(out_sym)
        self._cached_op = CachedOp(compile_sym, input_names, self._flags)

    def _symbolic_call(self, data_syms):
        out = self.hybrid_forward(sym_mod, *data_syms,
                                  **self._param_syms())
        return out

    def _param_syms(self):
        return {_strip_prefix(name, self._prefix): p.var()
                for name, p in self._direct_params().items()}

    def _direct_params(self):
        """Parameters owned directly by this block (not children)."""
        return {name: p for name, p in self._params.items()}

    def _collect_params_with_prefix(self, prefix=""):
        return dict(self.collect_params().items())

    # ------------------------------------------------------------------
    def _call_cached_op(self, *args):
        if self._cached_op is None:
            self._build_cache(*args)
        if autograd.is_recording():
            # the step's forward on the host: gathering the parameters,
            # the CachedOp's signature and program lookup, and its
            # launch or (fused backward on) deferral
            with telemetry.phase("forward"):
                return self._run_cached_op(args)
        return self._run_cached_op(args)

    def _run_cached_op(self, args):
        ctx = args[0].ctx
        arrays = []
        data_map = {"data%d" % i: a for i, a in enumerate(args)}
        all_params = self.collect_params()
        for name in self._cached_input_names:
            if name in data_map:
                arrays.append(data_map[name])
            else:
                arrays.append(all_params[name].data(ctx))
        return self._cached_op(*arrays)

    # ------------------------------------------------------------------
    def forward(self, x, *args):
        if isinstance(x, Symbol):
            # symbolic pathway (used during tracing / Symbol composition)
            params = {_strip_prefix(name, self._prefix): p.var()
                      for name, p in self._params.items()}
            return self.hybrid_forward(sym_mod, x, *args, **params)
        ctx = x.ctx
        if self._active:
            try:
                return self._call_cached_op(x, *args)
            except DeferredInitializationError:
                self._deferred_init_all(x, *args)
                return self._call_cached_op(x, *args)
        try:
            params = {_strip_prefix(name, self._prefix): p.data(ctx)
                      for name, p in self._params.items()}
        except DeferredInitializationError:
            self.infer_shape(x, *args)
            for p in self._params.values():
                p._finish_deferred_init()
            params = {_strip_prefix(name, self._prefix): p.data(ctx)
                      for name, p in self._params.items()}
        return self.hybrid_forward(nd, x, *args, **params)

    def _deferred_init_all(self, *args):
        """Run one eager forward to resolve every deferred shape."""
        was_active = self._active
        self._active = False
        try:
            with telemetry.setup_phase("init"), autograd.pause():
                self.__call__(*args)
        finally:
            self._active = was_active

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------------
    def serve_session(self, *example_inputs, **kwargs):
        """The export path into the serving subsystem (ISSUE 12):
        build an :class:`mxnet_tpu.serve.InferenceSession` over this
        block's compiled eval graph — AOT-compiled shape buckets,
        donated request buffers, weights read live so a Trainer in the
        same process is served without staleness or recompiles.
        Keyword args pass through (``max_batch``, ``seq_axis``,
        ``buckets``, ``mesh``/``param_specs`` for pjit-sharded
        serving, ...); see docs/SERVING.md. Lazy import — processes
        that never serve never load the subsystem."""
        from ..serve import InferenceSession
        return InferenceSession(
            self, example_inputs=example_inputs or None, **kwargs)

    # ------------------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True):
        """Save symbol JSON + params (ref: HybridBlock.export)."""
        if self._cached_graph is None:
            raise RuntimeError(
                "Please call hybridize() and run forward at least once "
                "before export")
        _, out_sym = self._cached_graph
        out_sym.save("%s-symbol.json" % path)
        arg_dict = {}
        for name, param in self.collect_params().items():
            arg_dict[("aux:" if getattr(param, "_is_aux", False) else "arg:")
                     + name] = param.data()
        nd.save("%s-%04d.params" % (path, epoch), arg_dict)
        return "%s-symbol.json" % path, "%s-%04d.params" % (path, epoch)


class SymbolBlock(HybridBlock):
    """Wrap an arbitrary Symbol as a Block (ref: SymbolBlock.imports)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(outputs)
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._sb_output = outputs
        self._sb_inputs = [i.name if isinstance(i, Symbol) else i
                           for i in inputs]
        input_names = set(self._sb_inputs)
        for name in outputs.list_inputs():
            if name not in input_names:
                self._params.get(name[len(self._params.prefix):],
                                 allow_deferred_init=True)
        if params is not None:
            for name, value in params.items():
                if name in self._params:
                    p = self._params[name]
                    p._shape = tuple(value.shape)
                    p.initialize(ctx=value.ctx)
                    p.set_data(value)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        sym = sym_mod.load(symbol_file)
        if not isinstance(input_names, (list, tuple)):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            arg_dict = nd.load(param_file)
            cleaned = {}
            for k, v in arg_dict.items():
                name = k.split(":", 1)[1] if ":" in k else k
                cleaned[name] = v
            for name, value in cleaned.items():
                if name in ret._params:
                    p = ret._params[name]
                    p._shape = tuple(value.shape)
                    p.initialize(ctx=ctx or current_context())
                    p.set_data(value)
        return ret

    def forward(self, x, *args):
        if isinstance(x, Symbol):
            raise NotImplementedError("symbol-in-symbol SymbolBlock")
        ctx = x.ctx
        feed = {self._sb_inputs[0]: x}
        for name, val in zip(self._sb_inputs[1:], args):
            feed[name] = val
        for name, p in self._params.items():
            feed[name] = p.data(ctx)
        return self._sb_output.eval(_train=autograd.is_training(), **feed)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
