"""Collective helpers over mesh axes.

Thin wrappers over XLA collectives (psum/all_gather/ppermute/
reduce_scatter) for use inside shard_map'ped functions — the TPU-native
replacement for the reference's four comm transports (SURVEY.md §5.8):
intra-host rings, NCCL, ps-lite, Horovod plugin all collapse into these
primitives riding ICI.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

_LOSSY_SYNC_WARNED = False   # once-per-process EF-less quantize warning

__all__ = ["allreduce_sum", "allreduce_mean", "allgather", "reduce_scatter",
           "ring_permute", "barrier_sum", "all_to_all", "axis_size",
           "hierarchical_allreduce", "hierarchical_grad_sync",
           "hierarchical_reduce_scatter", "hierarchical_allgather",
           "pad_to_multiple", "shard_owner_index", "shard_map"]


def axis_size(axis_name) -> int:
    """Static size of a mesh axis from inside shard_map (compat:
    lax.axis_size only exists on newer jax; psum of 1 constant-folds
    to the same int at trace time)."""
    if hasattr(lax, "axis_size"):
        return int(lax.axis_size(axis_name))
    return int(lax.psum(1, axis_name))


def pvary(x, axis_name):
    """Mark a shard-invariant value as varying over `axis_name` for
    shard_map's replication checker. Compat ladder: newest jax spells
    it lax.pcast(to="varying"), 0.5/0.6 lax.pvary; 0.4 has no
    varying-axes type system at all, where the identity is correct."""
    if hasattr(lax, "pcast"):
        return lax.pcast(x, axis_name, to="varying")
    if hasattr(lax, "pvary"):
        return lax.pvary(x, axis_name)
    return x


def _watch(op: str, axis_name, x, participants: int, count: int = 1,
           nbytes: Optional[int] = None):
    """Record one traced collective issue into commwatch (trace-time:
    shapes/dtypes are static, so payload bytes are exact). Never lets an
    accounting failure poison the traced program. `nbytes` overrides the
    payload derived from `x` for collectives whose NCCL-tests message
    size is not the per-rank input (all_gather: total output). A
    low-precision wire payload (int8/fp8 — the quantized collectives
    of parallel/quantize.py) carries a ``dtype`` label so the byte
    counters attribute the TRUE wire bytes per precision."""
    try:
        from .. import commwatch
        commwatch.traced_collective(
            op, axis_name, x, participants, count=count, nbytes=nbytes,
            dtype=commwatch.wire_dtype_label(getattr(x, "dtype", None)))
    except Exception:
        pass


def allreduce_sum(x, axis_name: str):
    """Gradient allreduce (ref: ncclAllReduce in kvstore_nccl.h)."""
    _watch("allreduce", axis_name, x, int(lax.psum(1, axis_name)))
    return lax.psum(x, axis_name)


def allreduce_mean(x, axis_name: str):
    _watch("allreduce", axis_name, x, int(lax.psum(1, axis_name)))
    return lax.pmean(x, axis_name)


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    n = int(lax.psum(1, axis_name))
    # NCCL-tests message-size convention for all_gather is the TOTAL
    # gathered payload (sendcount x nranks), matching the HLO-harvested
    # accounting of GSPMD all-gathers (result shape) — not the per-rank
    # input slice
    try:
        nbytes = int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize * n
    except Exception:
        nbytes = None
    _watch("allgather", axis_name, x, n, nbytes=nbytes)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, scatter_axis: int = 0):
    _watch("reduce_scatter", axis_name, x, int(lax.psum(1, axis_name)))
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                            tiled=True)


def ring_permute(x, axis_name: str, shift: int = 1, *,
                 watch_count: int = 1):
    """Neighbor exchange on the ring — the building block of ring
    attention / pipelined collectives (rides ICI neighbor links).
    `watch_count`: executions per program run the comm profile should
    charge this issue with (a lax.scan body traces ONCE but runs every
    tick — the caller knows the trip count, the trace does not)."""
    n = lax.psum(1, axis_name)
    _watch("ppermute", axis_name, x, int(n), count=watch_count)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = False):
    """The MoE dispatch/combine exchange (ref: no analogue — SURVEY
    §2.4 superset row). Wrapped here so expert-parallel traffic shows
    up in the comm profile like every other collective."""
    _watch("all_to_all", axis_name, x, int(lax.psum(1, axis_name)))
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def barrier_sum(axis_name: str):
    _watch("allreduce", axis_name, jnp.ones(()),
           int(lax.psum(1, axis_name)))
    return lax.psum(jnp.ones(()), axis_name)


def pad_to_multiple(x, n: int, axis: int = 0):
    """Zero-pad `x` along `axis` up to the next multiple of `n` (the
    uneven-shard padding every tiled reduce_scatter/all_gather needs;
    shapes are static so the pad amount folds at trace time)."""
    size = x.shape[axis]
    pad = (-size) % n
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def shard_owner_index(ici_axis: str = "dp", dcn_axis: Optional[str] = None):
    """Global shard index this device owns after
    :func:`hierarchical_reduce_scatter` (inverse of
    :func:`hierarchical_allgather`'s concatenation order). Flat
    (dcn_axis=None): the ici rank. Hierarchical: RS(ici) leaves device
    (d, i) rows [i*n_dcn, (i+1)*n_dcn); RS(dcn) then picks row d of
    that block, so ownership is i*n_dcn + d — NOT the flat device
    order. Checkpoint gather/scatter must apply the same permutation
    (gluon/zero.py)."""
    if dcn_axis is None:
        return lax.axis_index(ici_axis)
    return (lax.axis_index(ici_axis) * axis_size(dcn_axis)
            + lax.axis_index(dcn_axis))


def hierarchical_reduce_scatter(x, ici_axis: str = "dp",
                                dcn_axis: Optional[str] = None,
                                scatter_axis: int = 0):
    """Reduce-scatter staged for the fabric hierarchy (the RS half of
    the arxiv 2112.01075 redistribution decomposition): RS over the
    in-slice ICI axis first, then RS of the 1/n_ici shard over DCN —
    so the cross-slice tier only ever carries 1/n_ici of the payload.
    `x.shape[scatter_axis]` must divide n_ici*n_dcn (use
    :func:`pad_to_multiple`). The resulting shard's global index is
    :func:`shard_owner_index` (a permutation of flat rank order);
    :func:`hierarchical_allgather` inverts it."""
    shard = reduce_scatter(x, ici_axis, scatter_axis=scatter_axis)
    if dcn_axis is None:
        return shard
    return reduce_scatter(shard, dcn_axis, scatter_axis=scatter_axis)


def hierarchical_allgather(x, ici_axis: str = "dp",
                           dcn_axis: Optional[str] = None, axis: int = 0):
    """All-gather inverting :func:`hierarchical_reduce_scatter`'s
    shard placement: AG over DCN first (restoring each ICI rank's
    contiguous block), then AG over ICI — again only 1/n_ici of the
    payload crosses DCN."""
    if dcn_axis is not None:
        x = allgather(x, dcn_axis, axis=axis)
    return allgather(x, ici_axis, axis=axis)


def hierarchical_allreduce(x, ici_axis: str = "dp", dcn_axis: str = "dcn",
                           scatter_axis: int = 0, quant=None,
                           residual=None):
    """Cross-slice allreduce staged for the fabric hierarchy
    (SURVEY §5.8: the DCN tier is the reference's ps-lite multi-node
    role).

    Three phases: reduce_scatter over the in-slice ICI axis, allreduce
    the resulting 1/n_ici shard over the DCN axis, all_gather back over
    ICI. Per-device DCN traffic drops from B bytes (flat allreduce) to
    B/n_ici — on a v5e slice (n_ici=256) that is the difference between
    DCN being the bottleneck and DCN being idle-cheap. Requires
    x.shape[scatter_axis] divisible by the ICI axis size; use
    hierarchical_grad_sync for arbitrary pytrees (it pads).

    `quant` (a parallel.quantize.QuantConfig) switches the staged hops
    :attr:`~parallel.quantize.QuantConfig.tier` selects to the int8/fp8
    wire scheme (EQuARX shape, docs/QUANTIZE.md); requires a flat 1-D
    `x` with scatter_axis=0. With `residual` (same shape, f32) the
    rounding error is error-feedback-carried and ``(out, new_residual)``
    is returned instead of ``out``.
    """
    if quant is not None:
        if x.ndim != 1 or scatter_axis != 0:
            raise ValueError("quantized hierarchical_allreduce needs a "
                             "flat 1-D buffer (got shape %r, "
                             "scatter_axis=%d)" % (tuple(x.shape),
                                                   scatter_axis))
        from . import quantize as qz
        out, new_res = qz.quantized_allreduce(x, ici_axis, dcn_axis,
                                              quant, residual=residual)
        return (out, new_res) if residual is not None else out
    shard = reduce_scatter(x, ici_axis, scatter_axis=scatter_axis)
    shard = allreduce_sum(shard, dcn_axis)
    return allgather(shard, ici_axis, axis=scatter_axis)


def hierarchical_grad_sync(grads, ici_axis: str = "dp",
                           dcn_axis: str = "dcn", quant=None,
                           residual=None):
    """Allreduce a gradient pytree across dcn x ici with one fused
    hierarchical exchange.

    All leaves are flattened and concatenated into a single buffer
    (the analogue of the reference's NCCL key grouping /
    MXNET_KVSTORE_BIGARRAY_BOUND bucketing: one big collective instead
    of one per parameter), padded to a multiple of the ICI axis size,
    then reduce_scatter(ICI) -> psum(DCN) -> all_gather(ICI), and
    unpacked. For use inside shard_map with both axes in scope.

    Quantized wire (docs/QUANTIZE.md): pass `quant` EXPLICITLY — a
    QuantConfig, or the string ``"env"`` to adopt the
    MXNET_KVSTORE_QUANTIZE environment config at TRACE time. The
    default is OFF regardless of the environment: this is a stateless
    helper, and a caller that has not arranged a `residual` would
    otherwise silently drop each call's rounding error — a biased
    gradient sum, exactly the hazard error feedback exists to prevent.
    (The production sync paths — kvstore grouped reduces and the ZeRO
    dcn staging — honor the env variable and carry their residuals
    themselves.) When active, the float-dtype buffers ride the
    int8/fp8 EQuARX scheme on the hops MXNET_KVSTORE_QUANTIZE_TIER
    selects (default: only the DCN hop). With `residual` (a pytree
    shaped like `grads`, f32 leaves) the quantization error is
    error-feedback-carried and the call returns
    ``(synced, new_residual)``; quantizing WITHOUT a residual is
    allowed only for one-shot syncs and warns once per process.
    """
    if quant == "env":
        from . import quantize as qz
        quant = qz.from_env()
    if quant is not None and residual is None:
        global _LOSSY_SYNC_WARNED
        if not _LOSSY_SYNC_WARNED:
            _LOSSY_SYNC_WARNED = True
            import logging
            logging.getLogger("mxnet_tpu.parallel").warning(
                "hierarchical_grad_sync: quantized wire WITHOUT an "
                "error-feedback residual — each call's rounding error "
                "is dropped. Fine for a one-shot sync; pass residual= "
                "in a training loop (docs/QUANTIZE.md).")
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return (grads, residual) if residual is not None else grads
    res_leaves = None
    if residual is not None:
        res_leaves = jax.tree_util.tree_flatten(residual)[0]
        if len(res_leaves) != len(leaves):
            raise ValueError("residual pytree does not match grads")
    n_ici = lax.psum(1, ici_axis)  # static under shard_map
    # one fused buffer PER DTYPE (not a blanket f32 cast, which would
    # silently lose f64 precision / large-int exactness)
    by_dtype = {}
    for i, g in enumerate(leaves):
        by_dtype.setdefault(jnp.result_type(g), []).append(i)
    out = [None] * len(leaves)
    new_res = [None] * len(leaves)
    for dt, idxs in by_dtype.items():
        flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        flat = pad_to_multiple(flat, n_ici)
        quantizable = quant is not None and \
            jnp.issubdtype(dt, jnp.floating) and \
            jnp.finfo(dt).bits <= 32
        rflat = None
        if res_leaves is not None and jnp.issubdtype(dt, jnp.floating):
            rflat = jnp.concatenate(
                [jnp.ravel(res_leaves[i]).astype(jnp.float32)
                 for i in idxs])
            rflat = pad_to_multiple(rflat, n_ici)
        if quantizable:
            synced, rnew = hierarchical_allreduce(
                flat, ici_axis, dcn_axis, quant=quant,
                residual=rflat if rflat is not None
                else jnp.zeros_like(flat, dtype=jnp.float32))
            flat = synced.astype(dt)
        else:
            if rflat is not None:
                # quantize resolved OFF (e.g. quant='env' and the env
                # was cleared mid-run) while the caller still carries a
                # residual: FLUSH it into this exact sync — each
                # replica's carried mass enters the sum exactly once —
                # and return zeros. Dropping it would silently lose the
                # accumulated correction the carry identity conserves.
                flat = (flat.astype(jnp.float32) + rflat).astype(dt)
            flat = hierarchical_allreduce(flat, ici_axis, dcn_axis)
            rnew = None
        off = 0
        for i in idxs:
            g = leaves[i]
            size = int(np.prod(g.shape)) if g.shape else 1
            out[i] = flat[off:off + size].reshape(g.shape)
            if res_leaves is not None:
                new_res[i] = (rnew[off:off + size].reshape(g.shape)
                              if rnew is not None
                              else jnp.zeros(g.shape, jnp.float32))
            off += size
    synced_tree = jax.tree_util.tree_unflatten(treedef, out)
    if residual is not None:
        return synced_tree, jax.tree_util.tree_unflatten(treedef,
                                                         new_res)
    return synced_tree
