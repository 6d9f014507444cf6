"""Gluon Parameter / ParameterDict.

Ref: python/mxnet/gluon/parameter.py :: Parameter (deferred shape init,
per-ctx replica copies via _init_impl, grad_req) and ParameterDict.
Replicas are per-device committed jax buffers; the SPMD sharded path
(mxnet_tpu.parallel) instead holds ONE jax.Array sharded over a Mesh —
a Parameter can be promoted to that representation without API change.
"""
from __future__ import annotations

from typing import Dict, List, Optional, OrderedDict as TOrderedDict
from collections import OrderedDict

import numpy as np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import autograd
from .. import initializer as init_mod
from .. import symbol as sym_mod
from .. import telemetry

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict", "tensor_types"]

tensor_types = (NDArray,)


class DeferredInitializationError(MXNetError):
    """Raised when a parameter's shape is still unknown (ref: same name)."""


def _shape_complete(shape) -> bool:
    return shape is not None and all(s > 0 for s in shape)


class Parameter:
    def __init__(self, name: str, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self._allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data: Optional[TOrderedDict[Context, NDArray]] = None
        self._grad: Optional[TOrderedDict[Context, NDArray]] = None
        self._deferred_init = None
        self._var = None
        self._ctx_list: Optional[List[Context]] = None
        self._is_aux = False

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self._shape,
                                                      self.dtype)

    # ------------------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        self._grad_req = req
        if req == "null":
            self._grad = None
        elif self._data is not None and self._grad is None:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        # fill unknown (0) dims
        assert len(self._shape) == len(new_shape) and \
            all(s in (0, ns) for s, ns in zip(self._shape, new_shape)), \
            "Expected shape %s is incompatible with given shape %s for %s" \
            % (str(self._shape), str(new_shape), self.name)
        self._shape = tuple(new_shape)

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx_list = list(ctx)
        if init is None:
            init = default_init if self.init is None else self.init
        if not _shape_complete(self._shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, list(ctx), default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter %s: unknown shape %s and "
                "deferred init not allowed" % (self.name, self._shape))
        self._init_impl(init, ctx)

    def _init_impl(self, init, ctx_list):
        self._deferred_init = None
        data = nd.zeros(self._shape, ctx=ctx_list[0], dtype=self.dtype)
        initializer = init_mod.create(init) if not isinstance(
            init, init_mod.Initializer) else init
        initializer(init_mod.InitDesc(self.name), data)
        self._data = OrderedDict()
        for c in ctx_list:
            self._data[c] = data.as_in_context(c)
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = OrderedDict()
        for c, d in self._data.items():
            if self._grad_stype == "row_sparse":
                from ..ndarray import sparse as sp
                g = sp.zeros("row_sparse", d.shape, ctx=c, dtype=d.dtype)
            else:
                g = nd.zeros(d.shape, ctx=c, dtype=d.dtype)
            self._grad[c] = g
            autograd.mark_variables([d], [g], grad_reqs=[self._grad_req])

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if not _shape_complete(self._shape):
            raise DeferredInitializationError(
                "Parameter %s has unknown shape %s" % (self.name, self._shape))
        init, ctx, default_init = self._deferred_init
        with telemetry.setup_phase("init"):
            self._init_impl(init if init is not None else default_init, ctx)

    # ------------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "Parameter %s deferred (shape %s unknown)"
                    % (self.name, self._shape))
            raise RuntimeError(
                "Parameter %s has not been initialized. Call initialize() "
                "first" % self.name)
        if ctx is not None and ctx not in self._data:
            raise RuntimeError(
                "Parameter %s not initialized on context %s (has %s)"
                % (self.name, ctx, list(self._data)))

    def data(self, ctx: Optional[Context] = None) -> NDArray:
        if ctx is None:
            self._check_initialized()
            return next(iter(self._data.values()))
        self._check_initialized(ctx)
        return self._data[ctx]

    def list_data(self) -> List[NDArray]:
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx: Optional[Context] = None) -> NDArray:
        if self._grad is None:
            raise RuntimeError("Parameter %s grad_req='null'" % self.name)
        # fused-update deferral (MXNET_TRAINER_FUSED_UPDATE): a stashed
        # backward not yet consumed by Trainer.step() must run before
        # gradients are observed; cheap no-op otherwise
        from .. import autograd as _ag
        _ag.flush_pending_step()
        if ctx is None:
            return next(iter(self._grad.values()))
        return self._grad[ctx]

    def list_grad(self) -> List[NDArray]:
        if self._grad is None:
            raise RuntimeError("Parameter %s grad_req='null'" % self.name)
        from .. import autograd as _ag
        _ag.flush_pending_step()
        return list(self._grad.values())

    def list_ctx(self) -> List[Context]:
        self._check_initialized()
        return list(self._data.keys())

    def zero_grad(self):
        if self._grad is None:
            return
        for g in self._grad.values():
            if hasattr(g, "_clear"):  # row_sparse: O(1) reset
                g._clear()
            else:
                g[:] = 0.0

    def set_data(self, data):
        self.shape = data.shape if self._shape is None else self._shape
        if self._data is None:
            if self._deferred_init is not None:
                self._shape = tuple(data.shape)
                self._finish_deferred_init()
            else:
                raise RuntimeError("Parameter %s not initialized" % self.name)
        for c, d in self._data.items():
            src = data.as_in_context(c) if isinstance(data, NDArray) \
                else nd.array(data, ctx=c, dtype=self.dtype)
            d._set_jax(src._jax())

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = next(iter(self._data.values()))
            self._data = OrderedDict((c, data.as_in_context(c)) for c in ctx)
            if self._grad_req != "null":
                self._init_grad()
        elif self._deferred_init is not None:
            init, _, default_init = self._deferred_init
            self._deferred_init = (init, list(ctx), default_init)
        self._ctx_list = list(ctx)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        with autograd.pause():
            self._data = OrderedDict(
                (c, d.astype(dtype)) for c, d in self._data.items())
            if self._grad is not None:
                self._grad = OrderedDict(
                    (c, g.astype(dtype)) for c, g in self._grad.items())
                for c in self._data:
                    autograd.mark_variables([self._data[c]], [self._grad[c]],
                                            grad_reqs=[self._grad_req])

    def var(self) -> sym_mod.Symbol:
        if self._var is None:
            self._var = sym_mod.var(self.name, shape=self._shape,
                                    dtype=self.dtype)
            if self._is_aux:
                self._var._entries[0][0].attrs["__aux__"] = True
        return self._var


class Constant(Parameter):
    """Non-learnable constant (ref: gluon Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(np.asarray(value))
        self.value = value

        class _CInit(init_mod.Initializer):
            def _init_weight(self, _, arr):
                arr[:] = value

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype.name, init=_CInit(),
                         differentiable=False)


class ParameterDict:
    """Prefix-scoped parameter dictionary (ref: ParameterDict)."""

    def __init__(self, prefix: str = "", shared: Optional["ParameterDict"] = None):
        self._prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __repr__(self):
        s = "%s(" % (self._prefix + " " if self._prefix else "")
        s += "\n  ".join(str(p) for p in self._params.values())
        return s + ")"

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __getitem__(self, key) -> Parameter:
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def get(self, name, **kwargs) -> Parameter:
        """Get-or-create, with attribute reconciliation (ref: get)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if v is None:
                    continue
                if k == "shape":
                    existing = param._shape
                    if existing is not None and len(existing) == len(tuple(v)):
                        param._shape = tuple(
                            e if e != 0 else n
                            for e, n in zip(existing, tuple(v)))
                    else:
                        param._shape = tuple(v)
                elif getattr(param, k, None) in (None, "write", 1.0):
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None) -> Constant:
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("No constant named %s" % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError("Cannot update with conflicting Parameter %s" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        init = init if init is not None else init_mod.Uniform()
        with telemetry.setup_phase("init"):
            for _, v in self.items():
                v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.values():
            p.reset_ctx(ctx)

    def list_ctx(self):
        s = set()
        for p in self.values():
            if p._data is not None:
                s.update(p.list_ctx())
        return list(s)

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def save(self, fname, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            weight = param.data()
            if not param.name.startswith(strip_prefix):
                raise ValueError("Parameter %s does not start with prefix %s"
                                 % (param.name, strip_prefix))
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(fname, arg_dict)

    def load(self, fname, ctx=None, allow_missing=False, ignore_extra=False,
             restore_prefix=""):
        arg_dict = nd.load(fname)
        if restore_prefix:
            arg_dict = {restore_prefix + k: v for k, v in arg_dict.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    "Parameter %s missing in file %s" % (name, fname)
        for name, data in arg_dict.items():
            if name not in self._params:
                if not ignore_extra:
                    raise ValueError(
                        "Parameter %s in file %s is unknown" % (name, fname))
                continue
            self._params[name].set_data(data)
