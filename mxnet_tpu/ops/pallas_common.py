"""Shared helpers for the Pallas kernel modules — one platform probe,
one partitioning gate and one per-shard wrapper, so the decisions can
never diverge between kernels.

A program that GSPMD partitions over several devices cannot hold a bare
Mosaic kernel (the TPU compiler refuses it). Two answers live here:

* a kernel with no rule stands down: :func:`kernels_allowed` is False
  inside :func:`auto_partitioned` on a mesh of several devices, and the
  op takes its XLA composition (every decoder kernel, and the
  layer-norm kernel, which a shard each lost to XLA's fusions);
* a kernel whose mathematics is local to a sample and that pays (packed
  self-attention, dropout) runs once a shard on the shard's own rows:
  :func:`split_of` says on which dimension, :func:`per_shard` wraps the
  call in a ``jax.shard_map`` over the mesh's batch axes. The per-shard
  function is the one-device function, handed a shard's block: it
  flattens, picks its row blocks and plans from the *local* shape.

``jax.experimental.custom_partitioning`` would let the compiler say
which dimension it split; libtpu 0.0.34 does not implement the PJRT
custom-partitioner extension (the TPU compiler answers "Custom emitter
for CustomSPMDPartitioning not found"), so the batch dimension is found
by its size, which the step that opens the scope states.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import NamedTuple, Optional

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["interpret_mode", "interpret_asked", "auto_partitioned",
           "kernels_allowed", "split_of", "per_shard"]

_TRACING = threading.local()


def interpret_asked() -> bool:
    """True when MXNET_PALLAS_INTERPRET asks for interpreter mode."""
    from ..config import get as _cfg
    return bool(_cfg("MXNET_PALLAS_INTERPRET"))


def interpret_mode() -> bool:
    """True when Pallas kernels must run in interpreter mode: forced by
    MXNET_PALLAS_INTERPRET, or no TPU backend is attached."""
    return interpret_asked() or jax.devices()[0].platform != "tpu"


class _Scope(NamedTuple):
    mesh: object
    axes: Optional[tuple]   # mesh axes the batch is split over
    batch: Optional[int]    # the batch's size before the split


class Split(NamedTuple):
    """How a kernel with a rule takes an operand: ``dim`` split into
    ``shards`` blocks, one a device. ``dim`` None with one shard is the
    whole array in one call (no scope, or a mesh of one device); no
    shard at all is an operand the kernel cannot take (falsy)."""
    dim: Optional[int]
    shards: int

    def __bool__(self):
        return self.shards > 0

    def local(self, shape):
        """A shard's block of an operand of ``shape``."""
        if self.dim is None:
            return tuple(shape)
        return tuple(s // self.shards if i == self.dim else s
                     for i, s in enumerate(shape))


WHOLE = Split(None, 1)
_CANNOT = Split(None, 0)


def _scope():
    """The scope of several devices being traced in, else None."""
    scope = getattr(_TRACING, "scope", None)
    return scope if scope is not None and scope.mesh.size > 1 else None


@contextlib.contextmanager
def auto_partitioned(mesh, batch=None):
    """Scope in which a program that GSPMD will partition over ``mesh``
    is traced. The TPU compiler refuses a Mosaic kernel there ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map" — interpret mode lowers to plain XLA ops and never
    shows it), so with more than one device in the mesh
    :func:`kernels_allowed` is False inside the scope and an op whose
    kernel has no rule takes its XLA composition, which GSPMD can
    partition.

    ``batch`` is ``(axes, size)``: the program's inputs carry a batch of
    ``size`` samples split over the mesh axes ``axes``. A kernel that
    has a rule (:func:`split_of`) finds the batch dimension of its
    operand by that size and runs once a shard (:func:`per_shard`).
    Without it, or where ``axes`` are not all the mesh has (a tensor-
    parallel axis would partition the shard's call again), every kernel
    stands down."""
    prev = getattr(_TRACING, "scope", None)
    axes = size = None
    if batch is not None:
        axes, size = batch
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        if math.prod(mesh.shape[a] for a in axes) != mesh.size:
            axes = size = None
    _TRACING.scope = _Scope(mesh, axes, size)
    try:
        yield
    finally:
        _TRACING.scope = prev


def kernels_allowed() -> bool:
    """False while tracing a program GSPMD partitions over several
    devices; the ``*_available`` function of every kernel that has no
    per-shard rule asks here."""
    return _scope() is None


def _count(kernel, how):
    from .. import telemetry
    telemetry.count_event("mx_pallas_partitioned_total", kernel=kernel,
                          how=how)


def split_of(kernel, shape, dim=None):
    """How ``kernel`` may take an operand of ``shape``: :data:`WHOLE`
    outside a scope of several devices; inside one, a :class:`Split` of
    the batch dimension, or a falsy one where the call cannot run a shard
    at a time and the op takes its composition (counted
    ``mx_pallas_partitioned_total{kernel, how="composition"}``).

    ``dim`` is where the op's layout puts the batch (attention's packed
    ``(L, N, 3·heads·d)``: 1); None looks for it among all dimensions
    but the last (a row-wise kernel: ``(L, N, C)`` and ``(B, T, C)``
    both occur in one model). Either way the dimension must hold the
    scope's batch, alone among those looked at, and divide by the
    shards."""
    scope = _scope()
    if scope is None:
        return WHOLE
    found = _CANNOT
    if scope.batch is not None and scope.batch % scope.mesh.size == 0:
        dims = range(len(shape) - 1) if dim is None else (dim,)
        dims = [d for d in dims if shape[d] == scope.batch]
        if len(dims) == 1:
            found = Split(dims[0], scope.mesh.size)
    if not found:
        _count(kernel, "composition")
    return found


def per_shard(kernel, split, fn, data, seeds):
    """``fn(data, seeds)``, once a shard where ``split`` (what
    :func:`split_of` said of ``data``) has several: ``data`` and the
    result split on ``split.dim``, the per-block ``seeds``, which follow
    the rows, on their first dimension. With the whole array in one
    call this is ``fn(data, seeds)`` itself: on one device nothing is
    wrapped and the traced program is the one it was. Counted once a
    traced call in ``mx_pallas_partitioned_total{kernel,
    how="sharded"}``."""
    if not split:
        raise ValueError("%s: the operand cannot run a shard at a time "
                         "(ask the kernel's *_available first)" % kernel)
    if split.dim is None:
        return fn(data, seeds)
    scope = _scope()
    rows = P(*([None] * split.dim + [scope.axes]))

    def block(data, seeds):
        # a shard's call is a one-device call: nothing inside it asks
        # the mesh again
        prev, _TRACING.scope = _TRACING.scope, None
        try:
            return fn(data, seeds)
        finally:
            _TRACING.scope = prev

    _count(kernel, "sharded")
    return jax.shard_map(block, mesh=scope.mesh,
                         in_specs=(rows, P(scope.axes)), out_specs=rows,
                         check_vma=False)(data, seeds)
