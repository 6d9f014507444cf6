"""Single-source operator registry.

Ref: the NNVM op registry (3rdparty/tvm/nnvm :: NNVM_REGISTER_OP,
src/operator/ :: FCompute / FGradient / FMutateInputs). One registration
serves every executor — eager NDArray dispatch, the autograd tape, the
Symbol graph executor, and the CachedOp jit path — exactly as the
reference's single registry feeds Imperative::Invoke, CachedOp and
GraphExecutor (SURVEY.md §1 "One op registry, two executors").

TPU-first design: every op implementation is a *pure JAX function*
``impl(*arrays, **attrs) -> array | tuple``. There are no hand-written
gradients — backward is ``jax.vjp`` of the same impl, so FGradient comes
for free and stays consistent with forward. XLA does kernel fusion and
memory planning; impls therefore favour simple jnp/lax compositions that
XLA can fuse, and Pallas kernels are slotted in per-op where XLA
underperforms.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from ..base import MXNetError

__all__ = ["Operator", "register", "get_op", "list_ops", "jitted",
           "canonical_attrs", "jit_cache_info"]

_OPS: Dict[str, "Operator"] = {}
_ALIASES: Dict[str, str] = {}


class Operator:
    """A registered operator.

    Attributes
    ----------
    name : canonical op name (MXNet-style, e.g. ``FullyConnected``).
    impl : pure JAX function ``(*arrays, **attrs) -> array | tuple``.
    num_outputs : number of user-visible outputs (None = infer from return).
    mutate_aux : mapping extra-output-index -> input-index written back
        (ref: FMutateInputs — e.g. BatchNorm moving stats).
    needs_rng : impl's first array argument is a PRNG key supplied by the
        runtime (ref: ResourceRequest::kRandom).
    rng_impl : force a specific JAX PRNG implementation for the injected
        key (e.g. 'threefry2x32' for the poisson family, which JAX only
        implements for threefry); None = the runtime default
        (MXNET_PRNG_IMPL, 'rbg' hardware PRNG on TPU).
    needs_train_flag : impl takes a ``_train`` bool attr injected from the
        autograd training state (ref: is_train in OpContext).
    """

    def __init__(self, name: str, impl: Callable, num_outputs: Optional[int] = None,
                 mutate_aux: Optional[Dict[int, int]] = None,
                 needs_rng: bool = False, needs_train_flag: bool = False,
                 differentiable: bool = True, rng_impl: Optional[str] = None):
        self.name = name
        self.impl = impl
        self.num_outputs = num_outputs
        self.mutate_aux = mutate_aux or {}
        self.needs_rng = needs_rng
        self.rng_impl = rng_impl
        self.needs_train_flag = needs_train_flag
        self.differentiable = differentiable
        self.__doc__ = impl.__doc__

    def __repr__(self):
        return "Operator(%s)" % self.name

    # ------------------------------------------------------------------
    def bind_attrs(self, attrs: Dict[str, Any]) -> Callable:
        """Close attrs over impl → pure fn of arrays only."""
        impl = self.impl
        if attrs:
            return functools.partial(impl, **attrs)
        return impl

    def jitted(self, attrs_key: Tuple) -> Callable:
        return _jit_cache(self.name, attrs_key)


def register(name: str, aliases: Sequence[str] = (), **opattrs) -> Callable:
    """Decorator registering a pure-JAX impl as an operator."""
    def _reg(fn):
        if name in _OPS:
            raise MXNetError("operator %r already registered" % name)
        op = Operator(name, fn, **opattrs)
        _OPS[name] = op
        for a in aliases:
            _ALIASES[a] = name
        if name.lower() != name and name.lower() not in _ALIASES:
            _ALIASES[name.lower()] = name
        return fn
    return _reg


def get_op(name: str) -> Operator:
    op = _OPS.get(name)
    if op is None:
        canon = _ALIASES.get(name)
        if canon is not None:
            op = _OPS.get(canon)
    if op is None:
        raise MXNetError("unknown operator %r" % name)
    return op


def list_ops() -> List[str]:
    return sorted(_OPS)


def canonical_attrs(attrs: Dict[str, Any]) -> Tuple:
    """Hashable canonical form of op attrs (lists -> tuples) for jit keys."""
    items = []
    for k in sorted(attrs):
        v = attrs[k]
        if isinstance(v, list):
            v = tuple(v)
        elif isinstance(v, dict):
            v = tuple(sorted(v.items()))
        items.append((k, v))
    return tuple(items)


# ---------------------------------------------------------------------------
# jit cache: (op name, canonical attrs) -> jitted callable. jax.jit then
# caches per input aval/device, which is exactly the reference CachedOp
# signature-keyed cache generalized to eager ops (SURVEY.md §3.3 note:
# "CachedOp ≈ jax.jit cache keyed on input avals"). Each entry is a
# compilewatch.WatchedJit so compile time / recompiles / program cost
# are observable per op (ISSUE 4; docs/OBSERVABILITY.md "Compilation").
# ---------------------------------------------------------------------------
_JIT_CACHE: Dict[Tuple, Callable] = {}


def _impl_arg_names(op: "Operator", attrs_key: Tuple):
    """Positional tensor-parameter names of the impl (for recompile
    attribution), with attr names bound by attrs_key removed."""
    import inspect
    try:
        bound = {k for k, _ in attrs_key}
        names = []
        for p in inspect.signature(op.impl).parameters.values():
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD) \
                    and p.name not in bound:
                names.append(p.name)
        return names or None
    except Exception:
        return None


def _jit_cache(name: str, attrs_key: Tuple) -> Callable:
    key = (name, attrs_key)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        from ..compilewatch import watched_jit
        op = _OPS[name]
        fn = watched_jit(op.bind_attrs(dict(attrs_key)),
                         fn_label=name, site="ops.jitted",
                         arg_names=_impl_arg_names(op, attrs_key),
                         instance="%s%r" % (name, attrs_key),
                         static_repr=repr(attrs_key) if attrs_key else None,
                         exec_via_jit=True)
        _JIT_CACHE[key] = fn
    return fn


def jit_cache_info() -> Dict[str, int]:
    """Introspection for telemetry.snapshot(): entry count of the eager
    per-(op, attrs) jit cache (unbounded by design — keyed on static
    attrs, not input shapes; jax.jit holds the per-aval programs)."""
    return {"entries": len(_JIT_CACHE)}


def jitted(op: Operator, attrs: Dict[str, Any]) -> Callable:
    return _jit_cache(op.name, canonical_attrs(attrs))


# import op modules for registration side effects
from . import elemwise   # noqa: E402,F401
from . import reduce_ops  # noqa: E402,F401
from . import matrix    # noqa: E402,F401
from . import init_ops  # noqa: E402,F401
from . import nn        # noqa: E402,F401
from . import random_ops  # noqa: E402,F401
from . import optimizer_ops  # noqa: E402,F401
from . import rnn_ops   # noqa: E402,F401
from . import contrib_ops  # noqa: E402,F401
from . import decoder_ops  # noqa: E402,F401
from . import quantized_ops  # noqa: E402,F401
from . import tensor_tail  # noqa: E402,F401
from . import vision_ops  # noqa: E402,F401
from . import image_ops  # noqa: E402,F401
from . import numpy_ops  # noqa: E402,F401
