"""NDArray — the mutable, async, device-resident n-dim array.

Ref: src/ndarray/ndarray.cc + include/mxnet/ndarray.h :: NDArray (the
Chunk storage owner, views sharing chunks, WaitToRead, CopyFromTo,
autograd AGInfo attachment) and python/mxnet/ndarray/ndarray.py (the
Python surface).

TPU-native design — the central M0 decision (SURVEY.md §7.2 item 1):
XLA buffers are immutable, so MXNet's mutable semantics are provided by
*rebinding*: an NDArray owns a slot pointing at the current jax.Array;
in-place ops compute a new buffer (XLA donates/reuses HBM where it can)
and swap the slot. Views don't copy: a view records (base, index) and
reads through the base lazily (cache keyed on the base's version
counter); writes to a view are `base.at[idx].set(...)` — one fused XLA
scatter — followed by a slot swap on the base. Asynchrony is PJRT's own
dispatch pipeline; `wait_to_read` blocks on the buffer and surfaces any
async error there (exception-at-wait parity, threaded_engine.cc).
"""
from __future__ import annotations

import numbers
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

import weakref

from ..base import MXNetError
from ..context import Context, current_context
from .. import engine as _engine_mod
from ..engine import engine
from ..ops import Operator, canonical_attrs, get_op, jitted
from .. import random as _random
from .. import telemetry as _telemetry

# cached-gate read on the NDArray alloc path (resolves the env once,
# so arrays created before the first op dispatch are tracked too)
_tele_on = _telemetry.enabled

__all__ = ["NDArray", "invoke", "array", "empty", "concatenate", "waitall"]


class NDArray:
    """A device-resident array with MXNet mutation/view/autograd semantics."""

    __slots__ = ("_buf", "_ctx", "_base", "_index", "_cache", "_cache_ver",
                 "_version", "_ag_node", "_ag_out_idx", "_ag_var", "_grad",
                 "_grad_req", "__weakref__", "_dtype_hint", "_rec_slice",
                 "_pending", "_read_pins", "_mem_rec", "_race_var")

    # higher than numpy's so ndarray.__add__(NDArray) defers to us
    __array_priority__ = 1000.0

    def __init__(self, buf=None, ctx: Optional[Context] = None,
                 base: Optional["NDArray"] = None, index=None):
        self._buf = buf
        self._ctx = ctx or current_context()
        self._base = base
        self._index = index
        self._cache = None
        self._cache_ver = -1
        self._version = 0
        self._ag_node = None
        self._ag_out_idx = 0
        self._ag_var = False
        self._grad = None
        self._grad_req = "null"
        self._rec_slice = False
        # deferred-execution marker: (node, slot, aval) when this
        # array's value will be produced by a not-yet-run fused program
        # (autograd deferred CachedOp); reading the value forces it
        self._pending = None
        # gates of native-engine ops READING this array (WAR ordering):
        # an in-place mutation rebinds the buffer, so it must wait for
        # those readers first — the reference engine's write-dep rule
        self._read_pins = None
        # live-bytes accounting box [ctx_key, nbytes] when telemetry is
        # tracking this array (per-context HBM gauges; ISSUE 4)
        self._mem_rec = None
        if buf is not None and base is None and _tele_on():
            self._mem_track(buf)

    # ------------------------------------------------------------------
    # buffer access
    # ------------------------------------------------------------------
    def _jax(self) -> jax.Array:
        """The current immutable jax.Array value of this NDArray."""
        if _engine_mod._RACE_HOOK[0] is not None:
            # MXNET_ENGINE_RACE_CHECK: a worker-side read of an
            # engine-produced value must be covered by a declared edge
            # (staticcheck/race.py). Off: this is one global load +
            # is-None branch.
            _engine_mod._race_read(self)
        p = self._pending          # snapshot: a worker may clear it
        if p is not None:
            p[0].force()           # fills via _set_jax, clears _pending
        if self._base is not None:
            base = self._base
            if self._cache is None or self._cache_ver != base._version:
                self._cache = base._jax()[self._index]
                self._cache_ver = base._version
            return self._cache
        buf = self._buf
        p = self._pending
        if p is not None:
            # gated between the two reads: a fused step on another
            # thread is about to give this buffer to its program
            # (autograd._StepGate gates before it counts references,
            # so a value read before the gate is one it sees held).
            # Wait for its write-back and read again.
            p[0].force()
            buf = self._buf
        return buf

    def _set_jax(self, buf):
        """Rebind to a new buffer (the mutation primitive). The pending
        gate is cleared AFTER the buffer rebinds: a concurrent reader
        (native-engine worker vs main thread) then sees either the gate
        (and waits) or the completed value — never a stale buffer."""
        if _engine_mod._RACE_HOOK[0] is not None:
            # MXNET_ENGINE_RACE_CHECK: a worker-side rebind must be in
            # the running op's declared write set (staticcheck/race.py)
            _engine_mod._race_write(self)
        if self._read_pins:
            # write-after-read: an engine op still reads this buffer
            # (e.g. a deferred custom op); mutating before it runs
            # would feed it post-mutation values (ADVICE r4). The
            # producer writing its own gated output skips this (and
            # keeps the pins) — waiting there would deadlock on the
            # reader that depends on the producer itself.
            from ..engine import consume_read_pins
            consume_read_pins(self)
        if self._base is not None:
            base = self._base
            newbase = base._jax().at[self._index].set(buf)
            base._set_jax(newbase)
            self._cache = None
            self._pending = None
            return
        self._buf = buf
        self._pending = None
        self._version += 1
        self._cache = None
        if buf is not None and (self._mem_rec is not None
                                or _tele_on()):
            self._mem_track(buf)
        engine().on_dispatch(buf)

    def _mem_track(self, buf):
        """Per-context live-NDArray byte accounting (only while the
        telemetry gate is on; freed via weakref.finalize so the gauge
        tracks liveness, not allocation traffic)."""
        try:
            nbytes = int(buf.nbytes)
        except Exception:
            return
        box = self._mem_rec
        if box is None:
            key = str(self._ctx)
            self._mem_rec = box = [key, nbytes]
            _telemetry._ndarray_alloc(key, nbytes)
            weakref.finalize(self, _telemetry._ndarray_free_box, box)
        elif box[1] != nbytes:      # mutation changed the footprint
            _telemetry._ndarray_resize(box[0], nbytes - box[1])
            box[1] = nbytes

    def _mem_untrack(self):
        """Reverse the byte accounting for an NDArray that merely
        ALIASES another tracked array's buffer (detach(), the in-place
        pre-mutation snapshot): charging the same jax buffer twice
        would show phantom growth in every trainer loop's leak diff.
        The box is voided so the finalizer becomes a no-op."""
        box = self._mem_rec
        if box is not None:
            self._mem_rec = None
            _telemetry._ndarray_free_box(box)
            box[0] = None

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        p = self._pending               # snapshot vs worker clearing
        if p is not None:               # aval known without forcing
            return tuple(p[2].shape)
        return tuple(self._jax().shape)

    @property
    def dtype(self):
        p = self._pending
        if p is not None:
            return np.dtype(p[2].dtype)
        return np.dtype(self._jax().dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return invoke("transpose", [self], {})

    @property
    def grad(self) -> Optional["NDArray"]:
        if self._grad is not None:
            # fused-update deferral (MXNET_TRAINER_FUSED_UPDATE): a
            # backward stashed for an armed Trainer must execute before
            # its gradients are observed; cheap None check otherwise
            from .. import autograd as _ag
            _ag.flush_pending_step()
        return self._grad

    # ------------------------------------------------------------------
    # sync / host transfer
    # ------------------------------------------------------------------
    def wait_to_read(self):
        engine().wait_for_var(self._jax())

    def asnumpy(self) -> np.ndarray:
        buf = self._jax()
        engine().wait_for_var(buf)
        return np.asarray(buf)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            self.asnumpy(), "x".join(str(s) for s in self.shape), self._ctx)

    # ------------------------------------------------------------------
    # conversion / copies
    # ------------------------------------------------------------------
    def astype(self, dtype, copy=True) -> "NDArray":
        if not copy and np.dtype(dtype) == self.dtype:
            return self
        return invoke("Cast", [self], {"dtype": np.dtype(dtype).name})

    def copy(self) -> "NDArray":
        return self.copyto(self._ctx)

    def copyto(self, other: Union[Context, "NDArray"]) -> "NDArray":
        if isinstance(other, NDArray):
            other._set_jax(_place(self._jax(), other._ctx))
            return other
        out = NDArray(_place(self._jax(), Context(other)), Context(other))
        return out

    def as_in_context(self, ctx: Context) -> "NDArray":
        if Context(ctx) == self._ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def tolist(self):
        return self.asnumpy().tolist()

    def __reduce__(self):
        # pickle via host numpy (used by optimizer-state save/load)
        return (_unpickle, (self.asnumpy(), self._ctx.device_type,
                            self._ctx.device_id))

    # ------------------------------------------------------------------
    # autograd surface (ref: NDArray AGInfo + python attach_grad)
    # ------------------------------------------------------------------
    @property
    def _in_graph(self) -> bool:
        return self._ag_node is not None or self._ag_var

    def attach_grad(self, grad_req: str = "write", stype=None):
        from .. import autograd  # noqa: F401
        if stype == "row_sparse":
            from . import sparse as sp
            self._grad = sp.zeros("row_sparse", self.shape, self._ctx,
                                  self.dtype)
        else:
            self._grad = NDArray(jnp.zeros_like(self._jax()), self._ctx)
        self._grad_req = grad_req
        self._ag_var = True
        self._ag_node = None

    def detach(self) -> "NDArray":
        out = NDArray(self._jax(), self._ctx)
        out._mem_untrack()          # aliases this array's buffer
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def zero_grad(self):
        if self._grad is not None:
            if hasattr(self._grad, "_clear"):  # row_sparse: O(1) reset
                self._grad._clear()
            else:
                self._grad._set_jax(jnp.zeros_like(self._grad._jax()))

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key) -> "NDArray":
        key = _canon_index(key)
        key = _expand_ellipsis(key, self.ndim)
        from .. import autograd
        recording = autograd.is_recording() and self._in_graph
        if _is_basic_index(key):
            if recording:
                # record a differentiable slice op so backward() flows
                # through the index (ref: slice/at are recorded ops).
                # The result is a recorded COPY, not a view — flag it so
                # a later write-through attempt errors instead of being
                # silently dropped.
                out = invoke("_view_index", [self],
                             {"index": _encode_index(key)})
                out._rec_slice = True
                return out
            # view sharing storage (ref: NDArray::Slice / At share Chunk)
            root, idx = self, key
            if self._base is not None:
                # compose with existing view index so every view points at
                # the root array (single write-through level)
                root = self._base
                idx = _compose_index(self._base._jax().shape, self._index, key)
            view = NDArray(None, self._ctx, base=root, index=idx)
            return view
        # advanced indexing -> gather copy
        if recording:
            if isinstance(key, tuple):
                raise MXNetError(
                    "tuple-form advanced indexing of an array in the "
                    "autograd graph is not supported while recording; "
                    "use take/gather_nd ops instead")
            idx_np = key.asnumpy() if isinstance(key, NDArray) \
                else np.asarray(key)
            if idx_np.dtype == np.bool_:
                # boolean mask -> concrete row indices (mask is host data)
                idx_np = np.nonzero(idx_np.reshape(-1))[0]
            else:
                # normalize negatives: take(mode='clip') would clip them
                idx_np = idx_np.astype(np.int64)
                idx_np = np.where(idx_np < 0, idx_np + self.shape[0], idx_np)
            idx_nd = array(idx_np.astype(np.int32), ctx=self._ctx)
            return invoke("take", [self, idx_nd], {"axis": 0, "mode": "clip"})
        if isinstance(key, NDArray):
            key = key.asnumpy()
            if key.dtype != np.bool_:
                key = key.astype(np.int32)
        return NDArray(self._jax()[key], self._ctx)

    def __setitem__(self, key, value):
        key = _canon_index(key)
        key = _expand_ellipsis(key, self.ndim)
        if self._rec_slice:
            raise MXNetError(
                "cannot write to the result of slicing an array recorded "
                "on the autograd tape: recorded slices are copies, so the "
                "write would not reach the base array; slice-assign the "
                "base array directly")
        from .. import autograd
        if autograd.is_recording() and self._in_graph:
            # record the assignment so gradients stay correct (ref:
            # _slice_assign); a silent untracked write would detach grads
            if not _is_basic_index(key):
                raise MXNetError(
                    "advanced-index assignment to an array in the autograd "
                    "graph is not supported while recording")
            if self._base is not None:
                raise MXNetError(
                    "cannot assign to a view of a recorded array while "
                    "recording; assign through the base array instead")
            val_nd = value if isinstance(value, NDArray) else \
                array(np.asarray(value), ctx=self._ctx, dtype=self.dtype)
            self._recorded_mutation("_slice_assign", [val_nd],
                                    {"index": _encode_index(key)})
            return
        if isinstance(value, NDArray):
            val = value._jax()
        elif isinstance(value, (numbers.Number, np.ndarray, list, tuple)):
            val = jnp.asarray(value, dtype=self.dtype)
        else:
            val = value
        if isinstance(key, NDArray):
            key = key.asnumpy().astype(np.int32)
        cur = self._jax()
        if key == slice(None) if isinstance(key, slice) else False:
            newbuf = jnp.broadcast_to(val, cur.shape).astype(cur.dtype)
        else:
            newbuf = cur.at[key].set(val)
        self._set_jax(newbuf)

    # ------------------------------------------------------------------
    # arithmetic operators (scalar fast-paths mirror _plus_scalar etc.)
    # ------------------------------------------------------------------
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            lhs, rhs = (other, self) if reverse else (self, other)
            return invoke(op, [lhs, rhs], {})
        if isinstance(other, numbers.Number):
            name = scalar_op
            if reverse and op in ("broadcast_sub", "broadcast_div",
                                  "broadcast_power", "broadcast_mod"):
                name = "_r" + scalar_op[1:]
            return invoke(name, [self], {"scalar": float(other)})
        if isinstance(other, np.ndarray):
            return self._binop(array(other, ctx=self._ctx, dtype=self.dtype),
                               op, scalar_op, reverse)
        return NotImplemented

    def __add__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    def __radd__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    def __sub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar", True)
    def __mul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    def __rmul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    def __truediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar", True)
    def __mod__(self, o): return self._binop(o, "broadcast_mod", "_mod_scalar")
    def __rmod__(self, o): return self._binop(o, "broadcast_mod", "_mod_scalar", True)
    def __pow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar")
    def __rpow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar", True)
    def __neg__(self): return invoke("negative", [self], {})
    def __abs__(self): return invoke("abs", [self], {})

    def __eq__(self, o): return self._cmp(o, "broadcast_equal", "_equal_scalar")
    def __ne__(self, o): return self._cmp(o, "broadcast_not_equal", "_not_equal_scalar")
    def __gt__(self, o): return self._cmp(o, "broadcast_greater", "_greater_scalar")
    def __ge__(self, o): return self._cmp(o, "broadcast_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._cmp(o, "broadcast_lesser", "_lesser_scalar")
    def __le__(self, o): return self._cmp(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__  # identity hash despite elementwise __eq__

    def _cmp(self, other, op, scalar_op):
        if isinstance(other, NDArray):
            return invoke(op, [self, other], {})
        if isinstance(other, numbers.Number):
            return invoke(scalar_op, [self], {"scalar": float(other)})
        if other is None:
            return False
        return NotImplemented

    def _recorded_mutation(self, op_name, extra_inputs, attrs):
        """Mutate self under autograd.record() while keeping the tape in
        SSA form: snapshot the pre-mutation value (carrying the old node
        pointer), record the op on the snapshot, rebind self to the
        result's buffer AND node. Without the snapshot, the op's input
        and output would alias one Python object and the chain to
        earlier nodes would be lost."""
        if self._ag_var:
            raise MXNetError(
                "in-place modification of an array with attach_grad() "
                "while recording is not supported (it would overwrite the "
                "leaf the gradient belongs to); use autograd.pause() or "
                "an out-of-place op")
        prev = NDArray(self._jax(), self._ctx)
        prev._mem_untrack()         # aliases this array's buffer
        prev._ag_node = self._ag_node
        prev._ag_out_idx = self._ag_out_idx
        res = invoke(op_name, [prev] + list(extra_inputs), attrs)
        self._set_jax(res._jax())
        self._ag_node = res._ag_node
        self._ag_out_idx = res._ag_out_idx
        return self

    # in-place: compute then rebind (donation-friendly single fusion)
    def _iop(self, o, op, scalar_op):
        from .. import autograd
        if autograd.is_recording() and self._in_graph:
            if isinstance(o, numbers.Number):
                return self._recorded_mutation(scalar_op, [],
                                               {"scalar": float(o)})
            o_nd = o if isinstance(o, NDArray) else \
                array(o, ctx=self._ctx, dtype=self.dtype)
            return self._recorded_mutation(op, [o_nd], {})
        r = self._binop(o, op, scalar_op)
        self._set_jax(r._jax())
        return self

    def __iadd__(self, o):
        return self._iop(o, "broadcast_add", "_plus_scalar")

    def __isub__(self, o):
        return self._iop(o, "broadcast_sub", "_minus_scalar")

    def __imul__(self, o):
        return self._iop(o, "broadcast_mul", "_mul_scalar")

    def __itruediv__(self, o):
        return self._iop(o, "broadcast_div", "_div_scalar")

    # ------------------------------------------------------------------
    # convenience op methods (subset of the reference's fluent API)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        return invoke("Reshape", [self], {"shape": tuple(shape),
                                          "reverse": kwargs.get("reverse", False)})

    def reshape_like(self, other):
        return invoke("reshape_like", [self, other], {})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes if axes else None})

    def flatten(self):
        return invoke("Flatten", [self], {})

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def sum(self, axis=None, keepdims=False):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def abs(self):
        return invoke("abs", [self], {})

    def sqrt(self):
        return invoke("sqrt", [self], {})

    def square(self):
        return invoke("square", [self], {})

    def exp(self):
        return invoke("exp", [self], {})

    def log(self):
        return invoke("log", [self], {})

    def relu(self):
        return invoke("relu", [self], {})

    def sigmoid(self):
        return invoke("sigmoid", [self], {})

    def tanh(self):
        return invoke("tanh", [self], {})

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kw):
        return invoke("one_hot", [self], dict(depth=depth, **kw))

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": shape})

    def astype_like(self, other):
        return self.astype(other.dtype)

    def zeros_like(self):
        return invoke("zeros_like", [self], {})

    def ones_like(self):
        return invoke("ones_like", [self], {})


# ---------------------------------------------------------------------------
# indexing helpers
# ---------------------------------------------------------------------------
def _canon_index(key):
    if isinstance(key, list):
        return np.asarray(key)
    return key


def _expand_ellipsis(key, ndim):
    """Replace a bare/embedded Ellipsis with the full slices it stands for."""
    if key is Ellipsis:
        return tuple(slice(None) for _ in range(ndim))
    if isinstance(key, tuple) and any(k is Ellipsis for k in key):
        pos = key.index(Ellipsis)
        n_named = sum(1 for k in key if k is not None and k is not Ellipsis)
        fill = tuple(slice(None) for _ in range(ndim - n_named))
        return key[:pos] + fill + key[pos + 1:]
    return key


def _encode_index(key):
    """Hashable encoding of a basic index for use as a jitted-op attr."""
    key_t = key if isinstance(key, tuple) else (key,)
    enc = []
    for k in key_t:
        if isinstance(k, (int, np.integer)):
            enc.append(("i", int(k)))
        elif isinstance(k, slice):
            enc.append(("s",
                        None if k.start is None else int(k.start),
                        None if k.stop is None else int(k.stop),
                        None if k.step is None else int(k.step)))
        elif k is None:
            enc.append(("n",))
        else:
            raise MXNetError("unsupported index element %r" % (k,))
    return tuple(enc)


def _is_basic_index(key) -> bool:
    if isinstance(key, (int, np.integer, slice)):
        return True
    if isinstance(key, tuple):
        return all(isinstance(k, (int, np.integer, slice)) or k is None
                   for k in key)
    return False


def _compose_index(base_shape, outer, inner):
    """Compose view-of-view indices into a single index on the root buffer."""
    # normalize both to tuples
    outer = outer if isinstance(outer, tuple) else (outer,)
    inner = inner if isinstance(inner, tuple) else (inner,)
    result = []
    in_i = 0
    for dim, o in enumerate(outer):
        if isinstance(o, (int, np.integer)):
            result.append(o)  # dimension consumed by outer
            continue
        # o is a slice over base dim `dim`
        start, stop, step = o.indices(base_shape[dim])
        if in_i < len(inner):
            iv = inner[in_i]
            in_i += 1
            if isinstance(iv, (int, np.integer)):
                result.append(start + step * (iv if iv >= 0
                                              else (stop - start) // step + iv))
            else:
                n = max(0, (stop - start + (step - 1 if step > 0 else step + 1)) // step)
                s2, e2, st2 = iv.indices(n)
                result.append(slice(start + step * s2, start + step * e2, step * st2))
        else:
            result.append(slice(start, stop, step))
    # leftover inner indices apply to remaining dims
    dim = len(outer)
    for iv in inner[in_i:]:
        result.append(iv)
        dim += 1
    return tuple(result)


def _place(buf, ctx: Context):
    dev = ctx.jax_device
    if hasattr(buf, "devices") and buf.devices() == {dev}:
        return buf
    return jax.device_put(buf, dev)


def _shares_buffer(placed, buf) -> bool:
    """Whether ``placed`` (a ``jax.device_put`` of ``buf``) reads
    ``buf``'s own device memory: a put onto the array's device, or
    replicated over a mesh that holds it, makes a second array over
    the same buffer. A holder of such a copy keeps ``buf`` beside it:
    the Trainer's fused step donates only buffers whose reference
    count shows no second holder (``autograd._PendingStep._owned``),
    and donation would delete the copy along with the source."""
    try:
        mine = buf.unsafe_buffer_pointer()
        return any(s.data.unsafe_buffer_pointer() == mine
                   for s in placed.addressable_shards)
    except Exception:
        return True


# ---------------------------------------------------------------------------
# the eager dispatch path (ref: Imperative::Invoke → PushFCompute →
# Engine::PushAsync; SURVEY.md §3.1)
# ---------------------------------------------------------------------------
def _scatter_none_wrapper(fn, none_slots, total, n_rng):
    """Wrap an op impl so omitted optional tensor slots (None) are
    re-inserted at their positions; the traced arrays carry only the
    present tensors."""
    none_set = frozenset(none_slots)

    def wrapped(*arrays):
        rng_part = arrays[:n_rng]
        rest = list(arrays[n_rng:])
        full = []
        for i in range(total):
            full.append(None if i in none_set else rest.pop(0))
        return fn(*rng_part, *full)
    return wrapped


import functools as _functools  # noqa: E402


@_functools.lru_cache(maxsize=None)
def _jitted_with_none_slots(op, attrs_key, none_slots, total, n_rng):
    from ..compilewatch import watched_jit
    from ..ops import _impl_arg_names
    fn = op.bind_attrs(dict(attrs_key))
    names = _impl_arg_names(op, attrs_key)
    if names is not None:
        # the traced arrays carry only the PRESENT tensors; keep the
        # attribution names aligned with what the wrapper receives
        names = (["rng"] * n_rng
                 + [n for i, n in enumerate(names[n_rng:])
                    if i not in set(none_slots)])
    return watched_jit(_scatter_none_wrapper(fn, none_slots, total, n_rng),
                       fn_label=op.name, site="ndarray.none_slots",
                       arg_names=names,
                       instance="%s%r/none=%r" % (op.name, attrs_key,
                                                  none_slots),
                       static_repr=repr(attrs_key) if attrs_key else None,
                       exec_via_jit=True)


def invoke(op: Union[str, Operator], inputs: Sequence[NDArray],
           attrs: Dict[str, Any], out=None, ctx: Optional[Context] = None):
    """Execute one operator eagerly.

    Not recording: dispatch through a jitted, attr-keyed callable (the
    per-op analogue of the reference's engine push; XLA dispatch is
    async so this returns a future-like buffer immediately).
    Recording: run under jax.vjp and put a node on the autograd graph.
    """
    if isinstance(op, str):
        op = get_op(op)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    actx = attrs.pop("ctx", None)
    if ctx is None:
        ctx = inputs[0]._ctx if inputs else (Context(actx) if actx else current_context())
    if op.needs_train_flag:
        from .. import autograd
        attrs["_train"] = bool(autograd.is_training())

    # None entries = omitted optional tensor slots (nullptr handles in
    # the reference C API): drop them from the traced arrays and
    # re-scatter inside a wrapper so positions reach the impl intact
    none_slots = [i for i, a in enumerate(inputs) if a is None]
    if none_slots:
        total = len(inputs)
        present_idx = [i for i, a in enumerate(inputs) if a is not None]
        inputs = [a for a in inputs if a is not None]
    raw = [a._jax() for a in inputs]
    n_rng = 0
    if op.needs_rng:
        raw.insert(0, _place(_random.take_key(ctx, impl=op.rng_impl), ctx))
        n_rng = 1

    from .. import autograd
    recording = (autograd.is_recording() and op.differentiable
                 and any(a._in_graph for a in inputs))

    # Embedding(sparse_grad=True): don't scatter-add a dense table
    # gradient — record a COO cotangent for the weight instead
    # (ref: FInferStorageType row_sparse grad for Embedding)
    # only when the weight is a LEAF variable — a _SparseCot cannot flow
    # into an upstream node's jax vjp (e.g. weight scaled or amp-cast)
    sparse_emb = (recording and op.name == "Embedding"
                  and attrs.get("sparse_grad")
                  and len(inputs) > 1 and inputs[1]._ag_var)
    if sparse_emb:
        from .sparse import _SparseCot
        fn = jitted(op, attrs)
        out_raw = fn(*raw)
        idx_raw, weight_raw = raw[0], raw[1]
        w_shape = tuple(weight_raw.shape)

        def vjp_fn(cots):
            dy = cots[0] if isinstance(cots, (tuple, list)) else cots
            flat_idx = idx_raw.reshape(-1).astype(jnp.int32)
            flat_dy = dy.reshape((flat_idx.shape[0],) + w_shape[1:])
            return (jnp.zeros_like(idx_raw),
                    _SparseCot(flat_idx, flat_dy, w_shape))
    elif recording:
        fwd_pure = op.bind_attrs(canon_attr_dict(attrs))
        if none_slots:
            fwd_pure = _scatter_none_wrapper(fwd_pure, none_slots, total,
                                             n_rng)
        out_raw, vjp_fn = jax.vjp(fwd_pure, *raw)
    else:
        if none_slots:
            fn = _jitted_with_none_slots(op, canonical_attrs(attrs),
                                         tuple(none_slots), total, n_rng)
        else:
            fn = jitted(op, attrs)
        out_raw = fn(*raw)
        vjp_fn = None

    multi = isinstance(out_raw, (tuple, list))
    outs_raw = list(out_raw) if multi else [out_raw]

    # FMutateInputs: write mutated aux outputs back into their inputs
    n_extra = 0
    if op.mutate_aux:
        for extra_idx, in_idx in op.mutate_aux.items():
            if extra_idx < len(outs_raw):
                inputs[in_idx - 0]._set_jax(outs_raw[extra_idx])
                n_extra += 1
        outs_raw = outs_raw[: len(outs_raw) - n_extra] if n_extra else outs_raw

    out_arrays = [NDArray(_place(b, ctx), ctx) for b in outs_raw]
    for a in out_arrays:
        engine().on_dispatch(a._buf)

    if recording:
        autograd._record_node(op, inputs, out_arrays, vjp_fn,
                              [ _aval(b) for b in (list(out_raw) if multi else [out_raw]) ],
                              n_rng=n_rng, n_extra=n_extra,
                              fwd_fn=fn if sparse_emb else fwd_pure,
                              rng_key=raw[0] if n_rng else None,
                              raw_inputs=raw[n_rng:],
                              fused_key=("op", op.name,
                                         canonical_attrs(attrs),
                                         tuple(none_slots),
                                         total if none_slots else 0,
                                         n_rng),
                              fused_ok=not sparse_emb)

    # out= semantics: write visible outputs into provided arrays
    if out is not None:
        outs = out if isinstance(out, (tuple, list)) else [out]
        if len(outs) != len(out_arrays):
            raise MXNetError(
                "%s: out= provides %d array(s) but the op has %d "
                "output(s) — a partial write would silently drop "
                "state (e.g. momenta)" % (op.name, len(outs),
                                          len(out_arrays)))
        for dst, src in zip(outs, out_arrays):
            dst._set_jax(src._jax())
            if recording:
                dst._ag_node = src._ag_node
                dst._ag_out_idx = src._ag_out_idx
        return out if isinstance(out, (tuple, list)) else outs[0]

    if len(out_arrays) == 1:
        return out_arrays[0]
    return tuple(out_arrays)


def canon_attr_dict(attrs):
    return dict(canonical_attrs(attrs))


def _aval(buf):
    return jax.ShapeDtypeStruct(buf.shape, buf.dtype)


# ---------------------------------------------------------------------------
# creation helpers (python/mxnet/ndarray/utils.py equivalents)
# ---------------------------------------------------------------------------
def array(source_array, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        src = source_array._jax()
        if dtype is not None:
            src = src.astype(np.dtype(dtype))
        return NDArray(_place(src, ctx), ctx)
    was_np = isinstance(source_array, np.ndarray)
    arr = np.asarray(source_array,
                     dtype=np.dtype(dtype) if dtype is not None else None)
    if dtype is None:
        if not was_np:
            arr = arr.astype(np.float32)  # MXNet: lists default to float32
        elif arr.dtype == np.float64:
            arr = arr.astype(np.float32)  # MXNet has no float64 default
    return NDArray(_place(jnp.asarray(arr), ctx), ctx)


def empty(shape, ctx: Optional[Context] = None, dtype="float32") -> NDArray:
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(_place(jnp.zeros(shape, dtype=np.dtype(dtype)), ctx), ctx)


def concatenate(arrays, axis=0, always_copy=True) -> NDArray:
    return invoke("Concat", list(arrays), {"dim": axis})


def waitall():
    """Global barrier: XLA dispatches AND host-side native-engine work
    (custom ops, IO uploads, checkpoint writes) — ref: MXNDArrayWaitAll."""
    engine().wait_for_all()
    from ..engine import native_wait_all
    native_wait_all()


def _unpickle(arr, devtype, devid):
    return array(arr, ctx=Context(devtype, devid))
